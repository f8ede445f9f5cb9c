"""The plain reference of Algorithm 1 agrees with the program's
``afl_round`` on one round of each training cell's policy (CPU, tiny
width), so that a wrong reference cannot pass a wrong program."""
from __future__ import annotations

import pytest

# float32 on the CPU: the two differ in summation order only
AGREE = 1e-4


@pytest.mark.parametrize("workload,sparsifier", [
    ("resnet9.mads.n20", "sampled"),
    ("lanegcn.mads.n200", "exact"),
    ("resnet9.mads-joint.n20", "sampled"),
])
def test_reference_matches_afl_round(tiny, workload, sparsifier):
    import jax

    from bench.harness import compare, federation
    from repro.core.afl import afl_round

    cell = tiny(workload, sparsifier=sparsifier, checked_segments=1,
                segment_rounds=1)
    config, traffic = cell["config"], cell["traffic"]
    b = federation.build(config, traffic, seed=2**40 + 3)
    zeta, tau, h2 = (a[:1][0] for a in b["schedule"])
    batch = b["shard"].traced_batch(jax.random.fold_in(b["batch_key"], 0), 0)
    b["state"], m = afl_round(
        b["state"], batch, zeta, tau, h2, b["budgets"], model=b["model"],
        cfg=b["cfg"], fl=b["fl"], policy=b["policy"])
    assert float(jax.numpy.sum(m["success"])) > 0, "no client uploaded"
    hist = {"k_mean": [float(jax.numpy.sum(m["k"])) / max(
        float(jax.numpy.sum(m["success"])), 1.0)],
        "uploads": [float(jax.numpy.sum(m["success"]))]}
    got = {}
    federation.program_readings(b, hist, 0, got)
    want = federation.reference_readings(b, config, traffic)
    nums = compare.federation_numbers(got, want)
    assert nums["kappa"] == 0
    assert max(nums.values()) <= AGREE, nums
