"""Top-k gradient sparsification with error feedback (paper §III-D).

TPU adaptation (DESIGN.md §3): MADS computes the sparsification degree k
per round from contact time, so k is a *traced* value — ``jax.lax.top_k``
(static k) cannot be used.  We instead implement S(x) as magnitude
thresholding at the (1 - k/s) quantile of |x|:

* ``exact``  — threshold from a full descending sort (small models /
  simulation mode; bit-exact top-k semantics up to ties);
* ``sampled`` — threshold estimated from a strided sample of m elements
  (distributed mode; O(m log m), k hit within sampling error).

Both keep shapes static: the "upload" is ``x * mask`` and the error memory
update is ``x * (1 - mask)`` — the fused form of these two passes is the
``sparsify_ef`` Pallas kernel.  Bit accounting uses the realised mask
population count: bits = k_actual * (u + log2 s)  (paper eq. 7c).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ops


def bits_for_k(k, s: int, u: int = 32):
    """Upload payload in bits for k selected of s parameters (paper §III-D)."""
    return k * (u + jnp.ceil(jnp.log2(jnp.asarray(s, jnp.float32))))


def k_for_bits(bits, s: int, u: int = 32):
    """Largest k transmittable within ``bits`` (Proposition 1 with bits=tau*A)."""
    k = bits / (u + jnp.ceil(jnp.log2(jnp.asarray(s, jnp.float32))))
    return jnp.clip(k, 0.0, float(s))


def threshold_for_k(x_abs: jax.Array, k, *, method: str = "exact", sample: int = 65536):
    """|x| threshold such that ~k elements exceed it. k may be traced (float)."""
    s = x_abs.size
    k = jnp.clip(jnp.asarray(k, jnp.float32), 0.0, float(s))
    if method == "exact":
        srt = jnp.sort(x_abs.reshape(-1))[::-1]  # descending
        idx = jnp.clip(jnp.floor(k).astype(jnp.int32) - 1, 0, s - 1)
        t = srt[idx]
        # k == 0 -> nothing passes
        return jnp.where(k < 1.0, jnp.inf, t)
    if method == "sampled":
        m = min(sample, s)
        stride = max(s // m, 1)
        sub = jax.lax.slice(x_abs.reshape(-1), (0,), (m * stride,), (stride,))
        srt = jnp.sort(sub)[::-1]
        frac = k / float(s)
        idx = jnp.clip(jnp.floor(frac * m).astype(jnp.int32) - 1, 0, m - 1)
        t = srt[idx]
        return jnp.where(k < 1.0, jnp.inf, t)
    raise ValueError(f"unknown method {method!r}")


def sparsify_topk(x: jax.Array, k, *, method: str = "exact", sample: int = 65536):
    """S(x): keep the ~k largest-magnitude entries.

    Returns (upload, error, k_actual): upload = S(x), error = x - S(x),
    k_actual = realised number of selected entries (for bit accounting).
    """
    x_abs = jnp.abs(x.astype(jnp.float32))
    t = threshold_for_k(x_abs, k, method=method, sample=sample)
    # the fused single-pass op; kernels/ops.py picks kernel or oracle
    upload, error, count = ops.sparsify_ef(x.reshape(-1), t)
    return upload.reshape(x.shape), error.reshape(x.shape), count


def quantize_values(x, bits: int):
    """Symmetric uniform quantisation of the upload VALUES to ``bits`` bits
    (the paper's u; §III-D assumes u=32 floats — transmitting u<32 is a
    beyond-paper extension where Proposition 1 buys k* ~ (32+log2 s)/(u+log2 s)
    more coordinates per contact window and the error-feedback memory
    absorbs the quantisation residual).

    x may be a pytree; returns the dequantised-on-arrival tensor(s) (what
    the MES reconstructs).  bits >= 32 is a no-op.
    """
    if bits >= 32:
        return x

    def q(leaf):
        lf = leaf.astype(jnp.float32)
        amax = jnp.max(jnp.abs(lf))
        levels = float(2 ** (bits - 1) - 1)
        scale = jnp.maximum(amax, 1e-12) / levels
        return (jnp.round(lf / scale) * scale).astype(leaf.dtype)

    return jax.tree.map(q, x)


def _strided_sample(leaf, m: int):
    """~m-element magnitude sample via a rectangular strided slice.

    CRITICAL for the distributed path: flattening a sharded tensor
    (``reshape(-1)``) forces GSPMD to ALL-GATHER it (measured: 3x 16.6 GB f32
    gathers per AFL round on qwen2-moe — §Perf B-series).  A strided
    ``lax.slice`` keeps the shards local and only the tiny sample block is
    ever replicated.  Leading dims are strided first so the (usually sharded)
    trailing dim stays contiguous.
    """
    shape = leaf.shape
    size = leaf.size
    if size <= m or not shape:
        return jnp.abs(leaf.astype(jnp.float32)).reshape(-1)
    strides = [1] * len(shape)
    red = size / m
    order = sorted(range(len(shape)), key=lambda i: (i == len(shape) - 1, -shape[i]))
    for i in order:
        if red <= 1.0:
            break
        st = int(min(shape[i], max(1, round(red))))
        strides[i] = st
        red /= st
    block = jax.lax.slice(leaf, (0,) * len(shape), shape, tuple(strides))
    return jnp.abs(block.astype(jnp.float32)).reshape(-1)


def tree_threshold(tree, k, *, method: str = "exact", sample: int = 65536):
    """GLOBAL |x| threshold across all leaves such that ~k elements pass
    (the paper treats x_n as one flat vector).  k may be traced."""
    leaves = jax.tree.leaves(tree)
    sizes = [l.size for l in leaves]
    s = sum(sizes)
    if method == "exact":
        flat = jnp.concatenate([jnp.abs(l.astype(jnp.float32)).reshape(-1) for l in leaves])
        return threshold_for_k(flat, k, method="exact")
    m_per = [max(int(sample * sz / s), 16) for sz in sizes]
    flat = jnp.concatenate(
        [_strided_sample(l, m) for l, m in zip(leaves, m_per)]
    )
    frac = jnp.clip(jnp.asarray(k, jnp.float32) / float(s), 0.0, 1.0)
    srt = jnp.sort(flat)[::-1]
    idx = jnp.clip(jnp.floor(frac * flat.size).astype(jnp.int32) - 1, 0, flat.size - 1)
    return jnp.where(jnp.asarray(k, jnp.float32) < 1.0, jnp.inf, srt[idx])


def sparsify_tree(tree, k, *, method: str = "exact", sample: int = 65536):
    """Tree-level S(x): one global magnitude threshold across all leaves
    (see ``tree_threshold``)."""
    leaves, treedef = jax.tree.flatten(tree)
    t = tree_threshold(tree, k, method=method, sample=sample)
    ups, errs, ks = [], [], []
    for l in leaves:
        mask = jnp.abs(l.astype(jnp.float32)) >= t
        ups.append(jnp.where(mask, l, jnp.zeros_like(l)))
        errs.append(jnp.where(mask, jnp.zeros_like(l), l))
        ks.append(jnp.sum(mask).astype(jnp.float32))
    return (
        jax.tree.unflatten(treedef, ups),
        jax.tree.unflatten(treedef, errs),
        sum(ks),
    )
