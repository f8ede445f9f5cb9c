"""Device time per round of the ``afl.select`` scope: the error-corrected
gradient, its norms, the policy's choice of k and p, and the energy gate.
A fused op counts in the scope of its root (``phases.py``)."""
from bench.harness.phases import phase_ms_per_round

LAYER = "upload decision"
UNIT = "ms"
MOVES = "rounds_per_s"


def read(trace):
    return phase_ms_per_round(trace, "select")
