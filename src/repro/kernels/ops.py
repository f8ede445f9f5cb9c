"""Jit'd dispatch wrappers for the Pallas kernels.

``impl`` selection:
  "auto"              Pallas compiled on TPU, pure-jnp reference elsewhere.
                      A CPU run (the tests) takes the oracle; the kernels
                      are checked there in interpret mode.  The choice reads
                      ``jax.default_backend()`` while tracing, so a program
                      traced in a CPU process holds the oracle even when it
                      is compiled ahead of time for a TPU.
  "pallas"            pl.pallas_call compiled (TPU).
  "pallas_interpret"  kernel body executed in Python on CPU (tests).
  "ref"               pure-jnp oracle.
"""
from __future__ import annotations

import jax

from repro.kernels import ref as REF


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def sparsify_ef(x, threshold, *, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return REF.sparsify_ef_ref(x, threshold)
    from repro.kernels import sparsify_ef as K

    return K.sparsify_ef(x, threshold, interpret=(impl == "pallas_interpret"))


def sparsify_quantize_ef(x, threshold, step, levels, seed, base: int = 0,
                         *, impl: str = "auto"):
    """Fused sparsify + stochastic quantize + EF (compression codecs).

    Accepts any leaf shape; the Pallas path flattens internally.  The jnp
    oracle and the kernel share the counter-based dither of
    ``compression.quant``, so every impl returns identical values.
    """
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return REF.sparsify_quantize_ef_ref(x, threshold, step, levels, seed,
                                            base=base)
    from repro.kernels import sparsify_ef as K

    up, err, cnt = K.sparsify_quantize_ef(
        x.reshape(-1), threshold, step, levels, seed, base,
        interpret=(impl == "pallas_interpret"),
    )
    return up.reshape(x.shape), err.reshape(x.shape), cnt


def decode_attn(q, k, v, length, *, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return REF.decode_attn_ref(q, k, v, length)
    from repro.kernels import decode_attn as K

    return K.decode_attn(q, k, v, length, interpret=(impl == "pallas_interpret"))


def ssd_scan(x, a, b, c, *, chunk: int = 128, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        from repro.models.mamba2 import ssd_chunked

        return ssd_chunked(x, a, b, c, chunk)
    from repro.kernels import ssd_scan as K

    return K.ssd_scan(x, a, b, c, chunk=chunk, interpret=(impl == "pallas_interpret"))
