"""Programs JAX lowered in set-up, by the program's own compile counter:
each is then compiled or loaded from the persistent cache."""
from bench.harness.phases import setup_compiles

LAYER = "compile"
UNIT = "programs"
MOVES = "setup_s"


def read(trace):
    totals = setup_compiles(trace)
    return None if totals is None else totals["lowered"]
