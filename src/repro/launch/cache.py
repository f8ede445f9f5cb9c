"""Persistent compilation cache for the entry points.

Each entry point (``launch/train.py``, ``launch/sweep.py``,
``launch/soak.py``, ``benchmarks/run.py``, ``chip_smoke.py``) calls
``use_compile_cache()`` first thing in ``main``; importing ``repro`` never
configures the cache, so library users and the tests keep JAX's defaults.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
# A fixed path inside the checkout: the cache directory is part of what a
# later run must find again, so it never holds a temp name, a pid or a time.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other directory is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``.

    The cache key includes the programs' metadata.  JAX's default key
    strips it, so an executable compiled from other code that lowers to
    the same instructions (other ``afl.*`` scopes, or none) would be
    loaded, and a device trace would name its ops by that code's scopes.
    """
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
