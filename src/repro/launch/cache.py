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
    """
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
