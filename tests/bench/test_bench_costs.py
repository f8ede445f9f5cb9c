"""Operations and bytes counted from shapes: the models' training FLOPs
against XLA's own count of a one-client gradient, the codec kernel's
bytes, and the peaks table."""
from __future__ import annotations

import pytest

from conftest import tiny_cell


@pytest.mark.parametrize("workload", ["resnet9.mads.n20",
                                      "lanegcn.mads.n200"])
def test_flops_match_xla(workload):
    """The count is of model FLOPs (3x the forward's matmuls and
    convolutions); XLA's also counts batch norm, softmax and elementwise
    work, and the gradient of the input of the first layer, which no
    one needs.  They agree within a fifth."""
    import jax

    from bench.harness import federation

    cell = tiny_cell(workload)
    config, traffic = cell["config"], cell["traffic"]
    b = federation.build(config, traffic, seed=5)
    ref = b["ref"]
    batch = jax.tree.map(lambda a: a[0],
                         b["shard"].traced_batch(b["batch_key"], 0))
    grad = jax.jit(jax.grad(lambda p, x: ref.loss(p, x, config)))
    cost = grad.lower(b["w0"], batch).compile().cost_analysis()
    xla = cost["flops"] if isinstance(cost, dict) else cost[0]["flops"]
    ours = traffic["batch_size"] * ref.flops_per_sample(config)
    assert ours == pytest.approx(xla, rel=0.2)


def test_resnet9_w64_flops_by_hand():
    """Page's ResNet-9 at width 64 on 32x32 images: 0.76 GFLOP forward."""
    from bench.harness.cli import find_cell
    from bench.configs import resnet9

    config = find_cell("resnet9.mads.n20")["config"]
    fwd = resnet9.flops_per_sample(config) / 3
    convs = (32 * 32 * 64 * 27 + 32 * 32 * 128 * 9 * 64
             + 2 * 16 * 16 * 128 * 9 * 128 + 16 * 16 * 256 * 9 * 128
             + 8 * 8 * 512 * 9 * 256 + 2 * 4 * 4 * 512 * 9 * 512)
    assert fwd == 2 * (convs + 512 * 10)


def test_codec_kernel_bytes():
    from bench.harness.costs import sparsify_quantize_ef_bytes

    # one round of the mads-joint cell: 20 clients x 6,573,130 values,
    # each read once and written twice as float32
    assert sparsify_quantize_ef_bytes(20 * 6_573_130) == 1_577_551_200


def test_peaks_by_device_kind():
    from bench.harness.costs import peaks

    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")
