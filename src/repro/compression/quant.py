"""Stochastic uniform quantisation primitives (QSGD-style dithered rounding).

The wire format all quantising codecs share: values are scaled by a single
per-message step ``delta = amax / levels`` and stochastically rounded to the
integer grid ``q = clip(floor(v / delta + u), -levels, levels)`` with dither
``u ~ U[0, 1)`` — an unbiased estimator (``E[q * delta] = v``) whose
residual the error-feedback memory absorbs.  ``levels = 2^(b-1) - 1`` so a
signed value fits in ``b`` bits; the 32-bit float scale is counted once per
message (``SCALE_BITS``).

Dither is COUNTER-BASED, not stateful: ``dither_u01(seed, index)`` hashes
the (seed, global element index) pair with pure uint32 arithmetic
(lowbias32).  The jnp codecs, the pure-jnp kernel oracle, and the fused
Pallas kernel therefore make identical selection/rounding decisions — the
same element always draws the same dither for a given seed, independent of
blocking/sharding — so the quantised upload is bit-identical across
implementations (the error memory may differ by one FMA rounding).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# one fp32 scale per compressed message, counted against the bit budget
SCALE_BITS = 32


def dither_u01(seed, idx):
    """U[0,1) dither for global element indices ``idx`` under ``seed``.

    ``seed``: scalar int32 (may be traced); ``idx``: int array of global
    element positions.  lowbias32 integer hash — identical results as jnp
    on any backend and inside a Pallas kernel body.
    """
    h = idx.astype(jnp.uint32) ^ seed.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    # top 24 bits through int32: exact in float32, so u < 1 always, and the
    # chip's compiler has no uint32 -> float32 cast inside a Pallas kernel
    return (h >> 8).astype(jnp.int32).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def quant_levels(b):
    """Signed integer grid half-width for a ``b``-bit value (b may be traced).

    ``2^(b-1) - 1`` magnitudes plus sign fit in ``b`` bits; floored at 1 so
    a degenerate b never divides by zero (callers gate on b >= 2 anyway).
    """
    return jnp.maximum(2.0 ** (jnp.asarray(b, jnp.float32) - 1.0) - 1.0, 1.0)


def quant_step(amax, levels):
    """Quantisation step ``delta`` mapping [-amax, amax] onto the grid."""
    return jnp.maximum(amax, 1e-12) / levels


def stochastic_round(x, step, levels, seed, base=0):
    """Dequantised stochastic quantisation of ``x`` (any shape).

    Returns ``q * step`` with ``q = clip(floor(x/step + u), -levels,
    levels)`` and dither ``u = dither_u01(seed, base + flat_index)`` —
    ``base`` is the leaf's global element offset so every element of a
    multi-leaf message draws distinct dither.  Unbiased for |x| <= amax.
    """
    xf = x.astype(jnp.float32)
    idx = base + jnp.arange(xf.size).reshape(xf.shape)
    u = dither_u01(jnp.asarray(seed), idx)
    q = jnp.clip(jnp.floor(xf / step + u), -levels, levels)
    return q * step


def seed_from_key(key):
    """Scalar int32 dither seed derived from a jax PRNG key."""
    return jax.random.randint(key, (), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32)


def tree_amax(tree, axis: str | None = None):
    """Global max |value| across every leaf (one scale per message).

    ``axis``: optional mapped axis name (``shard_map``/``pmap``) over which
    the per-shard maxima are ``lax.pmax``-reduced, so every shard of a
    partitioned message derives the same quantisation step (max is
    order-independent, hence exact under any shard layout — the sharded
    contract in core/README.md).
    """
    amax = jnp.max(jnp.stack([
        jnp.max(jnp.abs(l.astype(jnp.float32))) for l in jax.tree.leaves(tree)
    ]))
    if axis is not None:
        amax = jax.lax.pmax(amax, axis)
    return amax
