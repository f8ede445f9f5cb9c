"""Seconds of set-up spent inside JAX's trace, lower, compile or load from
the persistent cache, by the program's own compile counter (the length of
the union of those spans, so nested ones count once)."""
from bench.harness.phases import setup_compiles

LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(trace):
    totals = setup_compiles(trace)
    return None if totals is None else totals["jax_s"]
