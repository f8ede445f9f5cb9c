"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.decode_attn import decode_attn
from repro.kernels.ref import (
    decode_attn_ref,
    sparsify_ef_ref,
    sparsify_quantize_ef_ref,
    ssd_scan_ref,
)
from repro.kernels.sparsify_ef import (
    _resolve_interpret,
    sparsify_ef,
    sparsify_quantize_ef,
)
from repro.kernels.ssd_scan import ssd_scan
from repro.models.mamba2 import ssd_chunked

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n", [128, 4096, 262144, 300001, 7])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparsify_ef_matches_ref(n, dtype):
    x = jnp.asarray(RNG.normal(0, 1, n), dtype)
    for t in [0.0, 0.3, 1.5, np.inf]:
        u, e, c = sparsify_ef(x, jnp.float32(t))
        ur, er, cr = sparsify_ef_ref(x, jnp.float32(t))
        np.testing.assert_allclose(np.asarray(u, np.float32), np.asarray(ur, np.float32))
        np.testing.assert_allclose(np.asarray(e, np.float32), np.asarray(er, np.float32))
        assert float(c) == float(cr), (n, t)


def test_sparsify_ef_reconstruction():
    x = jnp.asarray(RNG.normal(0, 1, 50000), jnp.float32)
    u, e, _ = sparsify_ef(x, jnp.float32(0.7))
    np.testing.assert_allclose(np.asarray(u + e), np.asarray(x))


def test_interpret_auto_selects_by_backend():
    """interpret=None compiles on TPU and interprets elsewhere (satellite:
    the jitted entry must not silently interpret on TPU)."""
    assert _resolve_interpret(None) == (jax.default_backend() != "tpu")
    assert _resolve_interpret(True) is True
    assert _resolve_interpret(False) is False


@pytest.mark.parametrize("n", [128, 4096, 300001, 7])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparsify_quantize_ef_matches_ref(n, dtype):
    """Fused sparsify+quantize+EF kernel vs oracle: upload/count bit-exact
    (shared counter dither), error within one FMA rounding."""
    x = jnp.asarray(RNG.normal(0, 1, n), dtype)
    step, levels = jnp.float32(0.01), jnp.float32(127.0)
    for t in [0.0, 0.7, np.inf]:
        u, e, c = sparsify_quantize_ef(x, jnp.float32(t), step, levels,
                                       1234, 5)
        ur, er, cr = sparsify_quantize_ef_ref(x, jnp.float32(t), step,
                                              levels, 1234, base=5)
        np.testing.assert_array_equal(
            np.asarray(u, np.float32), np.asarray(ur, np.float32))
        np.testing.assert_allclose(
            np.asarray(e, np.float32), np.asarray(er, np.float32), atol=1e-6)
        assert float(c) == float(cr), (n, t)


def test_kernels_vmapped_over_devices_match_ref():
    """The codec pass vmaps the kernels over devices, each with its own
    threshold/step/seed: the batched kernels still match the oracle."""
    x = jnp.asarray(RNG.normal(0, 1, (3, 300001)), jnp.float32)
    t = jnp.asarray([0.0, 0.7, np.inf], jnp.float32)
    step = jnp.asarray([0.01, 0.02, 0.05], jnp.float32)
    levels = jnp.full((3,), 127.0, jnp.float32)
    seed = jnp.asarray([1, 2, 3], jnp.int32)
    u, e, c = jax.vmap(lambda *a: sparsify_quantize_ef(*a, 5))(
        x, t, step, levels, seed)
    ur, er, cr = jax.vmap(
        lambda *a: sparsify_quantize_ef_ref(*a, base=5))(
        x, t, step, levels, seed)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(ur))
    np.testing.assert_allclose(np.asarray(e), np.asarray(er), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    u, e, c = jax.vmap(sparsify_ef)(x, t)
    ur, er, cr = jax.vmap(sparsify_ef_ref)(x, t)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(ur))
    np.testing.assert_array_equal(np.asarray(e), np.asarray(er))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))


def test_sparsify_quantize_ef_semantics():
    """Upload values sit on the step grid; EF absorbs the quant residual."""
    x = jnp.asarray(RNG.normal(0, 1, 4096), jnp.float32)
    step = jnp.float32(0.25)
    u, e, c = sparsify_quantize_ef(x, jnp.float32(0.5), step, jnp.float32(7.0),
                                   99, 0)
    un = np.asarray(u)
    np.testing.assert_allclose(un / 0.25, np.round(un / 0.25), atol=1e-6)
    np.testing.assert_allclose(np.asarray(u + e), np.asarray(x), atol=1e-6)
    assert float(c) == float(np.sum(np.abs(np.asarray(x)) >= 0.5))
    # base offset changes the dither draw
    u2, _, _ = sparsify_quantize_ef(x, jnp.float32(0.5), step,
                                    jnp.float32(7.0), 99, 4096)
    assert not np.array_equal(un, np.asarray(u2))


def test_ops_sparsify_quantize_dispatch_nd():
    """ops wrapper accepts ND leaves and falls back to ref off-TPU."""
    x = jnp.asarray(RNG.normal(0, 1, (32, 16)), jnp.float32)
    u, e, c = ops.sparsify_quantize_ef(x, 0.5, 0.01, 127.0, 7, base=3)
    ur, er, cr = sparsify_quantize_ef_ref(x, 0.5, 0.01, 127.0, 7, base=3)
    assert u.shape == x.shape
    np.testing.assert_array_equal(np.asarray(u), np.asarray(ur))
    assert float(c) == float(cr)


@pytest.mark.parametrize(
    "b,h,kv,s,d", [(2, 8, 2, 1024, 64), (1, 4, 4, 512, 128), (2, 6, 2, 777, 64),
                   (1, 16, 2, 2048, 128)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_matches_ref(b, h, kv, s, d, dtype):
    q = jnp.asarray(RNG.normal(0, 1, (b, h, d)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, kv, d)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, kv, d)), dtype)
    length = int(0.7 * s)
    out = decode_attn(q, k, v, length)
    ref = decode_attn_ref(q, k, v, length)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_decode_attn_ignores_masked_tail():
    """Entries beyond `length` must not affect the result."""
    b, h, kv, s, d = 1, 4, 2, 512, 64
    q = jnp.asarray(RNG.normal(0, 1, (b, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, kv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, kv, d)), jnp.float32)
    out1 = decode_attn(q, k, v, 100)
    k2 = k.at[:, 100:].set(1e4)
    v2 = v.at[:, 100:].set(-1e4)
    out2 = decode_attn(q, k2, v2, 100)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize(
    "b,s,h,p,n,q", [(2, 256, 4, 64, 32, 64), (1, 128, 2, 32, 16, 32),
                    (1, 512, 8, 64, 64, 128)]
)
def test_ssd_scan_matches_sequential_ref(b, s, h, p, n, q):
    x = jnp.asarray(RNG.normal(0, 1, (b, s, h, p)), jnp.float32)
    a = jnp.asarray(-np.abs(RNG.normal(0, 0.5, (b, s, h))), jnp.float32)
    bb = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    cc = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    y, st = ssd_scan(x, a, bb, cc, chunk=q)
    yr, str_ = ssd_scan_ref(x, a, bb, cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), rtol=2e-4, atol=2e-4)


def test_ssd_chunked_model_path_matches_ref():
    """The pure-jnp chunked SSD used inside the Mamba2 blocks is also exact."""
    b, s, h, p, n = 2, 192, 3, 16, 8
    x = jnp.asarray(RNG.normal(0, 1, (b, s, h, p)), jnp.float32)
    a = jnp.asarray(-np.abs(RNG.normal(0, 0.5, (b, s, h))), jnp.float32)
    bb = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    cc = jnp.asarray(RNG.normal(0, 1, (b, s, n)), jnp.float32)
    y, st = ssd_chunked(x, a, bb, cc, 64)
    yr, str_ = ssd_scan_ref(x, a, bb, cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(str_), rtol=2e-4, atol=2e-4)


def test_ops_dispatch_cpu_falls_back_to_ref():
    x = jnp.asarray(RNG.normal(0, 1, 1024), jnp.float32)
    u, e, c = ops.sparsify_ef(x, 0.5)  # auto on CPU -> ref
    ur, er, cr = sparsify_ef_ref(x, 0.5)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ur))
