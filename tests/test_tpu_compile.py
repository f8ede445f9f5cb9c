"""Ahead-of-time compiles of the codec kernels for a TPU v5e, no chip needed.

Interpret mode (tests/test_kernels.py) cannot see what the chip's compiler
refuses: blocks that break the (8, 128) tiling, casts Mosaic has no rule
for, fast memory a kernel may not use.  Compiling for a described v5e
topology does.  Both kernels compile at ResNet-9's full size,
s = 6,573,130 f32 (``model.num_params()`` at d_model 64), and under the
per-device ``vmap`` of the codec pass.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and with several test
workers a describe at import would fail in all but one of them.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.sparsify_ef import sparsify_ef, sparsify_quantize_ef

S = 6_573_130  # ResNet-9 at d_model 64
N = 20  # devices per codec pass (Table I)
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, mem
    assert "tpu_custom_call" in compiled.as_text()


def _kernel(kernel, lead, sharding):
    """The compiled kernel and its argument shapes, with leading dims."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    x, scalar = sds(lead + (S,)), sds(lead)
    if kernel == "sparsify_ef":
        return (lambda x, t: sparsify_ef(x, t, interpret=False)), [x, scalar]
    return ((lambda x, t, step, levels, seed: sparsify_quantize_ef(
                x, t, step, levels, seed, 0, interpret=False)),
            [x, scalar, scalar, scalar, sds(lead, jnp.int32)])


@pytest.mark.parametrize("kernel", ["sparsify_ef", "sparsify_quantize_ef"])
def test_codec_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    fn, args = _kernel(kernel, (), one_chip)
    _compile(fn, *args)


@pytest.mark.parametrize("kernel", ["sparsify_ef", "sparsify_quantize_ef"])
def test_vmapped_codec_kernel_compiles_for_v5e(kernel, one_chip,
                                               no_persistent_cache):
    """The codec pass vmaps the kernel over N devices
    (``core.afl.compress_uploads``); the batch dimension must keep every
    block tiling-legal."""
    fn, args = _kernel(kernel, (N,), one_chip)
    _compile(jax.vmap(fn), *args)
