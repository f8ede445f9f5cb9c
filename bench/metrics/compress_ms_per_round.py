"""Device time per round of the ``afl.compress`` scope: the top-k threshold,
the mask and the error feedback, or the codec pass.  A fused op counts in
the scope of its root (``phases.py``)."""
from bench.harness.phases import phase_ms_per_round

LAYER = "threshold + mask/codec"
UNIT = "ms"
MOVES = "rounds_per_s"


def read(trace):
    return phase_ms_per_round(trace, "compress")
