"""Device time per round of the ``afl.grads`` scope: the vmapped local
gradients of every client and their sum into the cumulative gradients.
A fused op counts in the scope of its root (``phases.py``)."""
from bench.harness.phases import phase_ms_per_round

LAYER = "local gradients"
UNIT = "ms"
MOVES = "rounds_per_s"


def read(trace):
    return phase_ms_per_round(trace, "grads")
