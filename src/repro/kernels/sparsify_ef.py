"""Pallas TPU kernels: fused sparsify(+quantize) + error-feedback update.

The paper's per-round hot spot: every contacted device transforms its
upload vector x (model-sized, 6.5M-72B elements) into
    upload = x * [|x| >= t],   error = x * [|x| < t],   count = popcount
Naive jnp issues three separate elementwise passes (2 reads + 2 writes + a
reduce read).  The fused ``sparsify_ef`` kernel streams x through VMEM once
per block and emits both outputs + a per-block partial count: 1 read + 2
writes — a 40% HBM-traffic cut on a purely memory-bound op.

``sparsify_quantize_ef`` extends the same single pass to the compression
subsystem's quantising codecs (repro/compression): kept values are
stochastically rounded onto the ``levels``-grid with counter-based dither
(``compression.quant.dither_u01`` — pure uint32 hashing, so the upload is
bit-identical to the jnp oracle ``kernels.ref.sparsify_quantize_ef_ref``),
the quantised upload, the DEQUANTISED error memory (x - upload, absorbing
the quantisation residual), and the popcount all leave VMEM in one pass.
A separate quantise stage would re-read the masked upload from HBM;
fusing it is free — a handful of extra VPU flops on a bandwidth-bound op.

Layout: x viewed as (rows, 1024) f32/bf16, blocked (BLOCK_R, 1024) —
lane-dim 1024 = 8 x 128 keeps the VPU tiles full and 128-aligned.  Scalars
come in as (1, 1024) rows and each block's count leaves as one (1, 1024) row
of per-lane int32 counts, so every block stays tiling-legal, also when
``vmap`` adds a batch dimension (tests/test_tpu_compile.py compiles both
kernels for v5e).

``interpret=None`` (the default) auto-selects: compiled on TPU, interpret
mode elsewhere — so production entry points run the real kernel where it
matters without every call site threading backend checks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compression.quant import dither_u01

LANE = 1024
BLOCK_R = 256  # (256, 1024) f32 = 1 MiB per ref — comfortably inside VMEM


def _resolve_interpret(interpret):
    """None -> interpret only off-TPU (compiled where it matters)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _row(v, dtype=jnp.float32):
    """A scalar as one lane-dense (1, LANE) row.

    Scalars ride the kernel as full rows, not as rank-1 blocks: a block of
    one element is not a multiple of the 128-lane tiling, and the chip's
    compiler refuses it.  A row whose dims equal the array's also stays legal
    when ``vmap`` (the per-device codec pass) prepends a batch dimension.
    """
    return jnp.broadcast_to(jnp.asarray(v, dtype), (1, LANE))


def _layout(x):
    """(n,) -> zero-padded (blocks * BLOCK_R, LANE) view, blocks, padded n."""
    n = x.size
    per_block = LANE * BLOCK_R
    blocks = max((n + per_block - 1) // per_block, 1)
    padded = blocks * per_block
    xp = jnp.pad(x.reshape(-1), (0, padded - n)).reshape(blocks * BLOCK_R, LANE)
    return xp, blocks, padded


def _call(kernel, xp, blocks, scalars, interpret):
    """Stream ``xp`` block by block with (1, LANE) scalar rows alongside.

    Outputs the upload and error blocks plus one (1, LANE) row of per-lane
    int32 selection counts per block, so the count needs no cross-lane
    reduction inside the kernel and stays exact.
    """
    block = pl.BlockSpec((BLOCK_R, LANE), lambda i: (i, 0))
    row = pl.BlockSpec((1, LANE), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[block] + [row] * len(scalars),
        out_specs=[block, block,
                   pl.BlockSpec((None, 1, LANE), lambda i: (i, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, xp.dtype),
            jax.ShapeDtypeStruct(xp.shape, xp.dtype),
            jax.ShapeDtypeStruct((blocks, 1, LANE), jnp.int32),
        ],
        interpret=interpret,
    )(xp, *scalars)


def _finish(up, err, cnt, t, n, padded):
    """Slice the padding off; count = exact int total as f32 (the oracle's)."""
    count = jnp.sum(cnt)
    # zero padding passes the threshold only when t <= 0 (threshold_for_k
    # returns +inf for k < 1): take those pad elements back out
    count = count - jnp.where(t <= 0, padded - n, 0)
    return up.reshape(-1)[:n], err.reshape(-1)[:n], count.astype(jnp.float32)


def _kernel(x_ref, t_ref, up_ref, err_ref, cnt_ref):
    x = x_ref[...]
    mask = jnp.abs(x.astype(jnp.float32)) >= t_ref[...]
    zeros = jnp.zeros_like(x)
    up_ref[...] = jnp.where(mask, x, zeros)
    err_ref[...] = jnp.where(mask, zeros, x)
    cnt_ref[...] = jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparsify_ef(x: jax.Array, threshold: jax.Array, *,
                interpret: bool | None = None):
    """x: (n,) -> (upload (n,), error (n,), count scalar f32).

    Pads n up to a LANE*BLOCK_R multiple internally; padding cannot pass the
    threshold unless t <= 0, which the count corrects for.
    """
    interpret = _resolve_interpret(interpret)
    t = jnp.asarray(threshold, jnp.float32)
    xp, blocks, padded = _layout(x)
    up, err, cnt = _call(_kernel, xp, blocks, [_row(t)], interpret)
    return _finish(up, err, cnt, t, x.size, padded)


def _kernel_q(x_ref, t_ref, step_ref, levels_ref, seed_ref, up_ref, err_ref,
              cnt_ref, *, base: int):
    """Scalar rows: threshold, step, levels (f32) and seed (int32)."""
    x = x_ref[...]
    step, levels = step_ref[...], levels_ref[...]
    xf = x.astype(jnp.float32)
    mask = jnp.abs(xf) >= t_ref[...]
    # global flat element index of this block's elements; int32 wrap-around
    # at huge offsets is fine — the uint32 dither hash wraps identically in
    # the jnp oracle
    i = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    idx = base + (i * x.shape[0] + rows) * x.shape[1] + cols
    u = dither_u01(seed_ref[...], idx)
    q = jnp.clip(jnp.floor(xf / step + u), -levels, levels) * step
    upload = jnp.where(mask, q, 0.0).astype(x.dtype)
    up_ref[...] = upload
    err_ref[...] = (xf - upload.astype(jnp.float32)).astype(x.dtype)
    cnt_ref[...] = jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("base", "interpret"))
def sparsify_quantize_ef(x: jax.Array, threshold, step, levels, seed,
                         base: int = 0, *, interpret: bool | None = None):
    """x: (n,) -> (quantised upload (n,), dequantised error (n,), count).

    Same blocking/padding as ``sparsify_ef``; upload/count match
    ``kernels.ref.sparsify_quantize_ef_ref`` bit-for-bit (shared dither;
    error up to one FMA rounding).  ``base`` offsets the dither counter
    for multi-leaf messages.
    """
    interpret = _resolve_interpret(interpret)
    t = jnp.asarray(threshold, jnp.float32)
    scalars = [_row(t), _row(step), _row(levels), _row(seed, jnp.int32)]
    xp, blocks, padded = _layout(x)
    up, err, cnt = _call(functools.partial(_kernel_q, base=int(base)), xp,
                         blocks, scalars, interpret)
    return _finish(up, err, cnt, t, x.size, padded)
