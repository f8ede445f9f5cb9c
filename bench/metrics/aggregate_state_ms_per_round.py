"""Device time per round of the ``afl.aggregate`` and ``afl.state`` scopes
together: the server's mix of the uploads into the global model, then the
local models, the three per-client selects, the staleness, the energy
queues and the error norms.

One metric for both, because XLA fuses the mix into the state pass that
reads it, and a fused op counts in the scope of its root (``phases.py``):
apart, the mix read 0.19 ms a round against the 0.64 ms its 526 MB take
at the v5e's 819 GB/s, and the rest in ``afl.state``.  Every phase metric
reads by fusion roots, so work moves between phases where the compiler
fuses across a scope's edge."""
from bench.harness.phases import phase_ms_per_round

LAYER = "aggregation + state update, fused by XLA"
UNIT = "ms"
MOVES = "rounds_per_s"


def read(trace):
    return phase_ms_per_round(trace, "aggregate", "state")
