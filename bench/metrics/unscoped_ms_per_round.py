"""Device time per round of the segment program that no ``afl.*`` scope
claims: the scan's carry copies and loop bookkeeping.  None where the
program has no scopes at all."""
from bench.harness.phases import of_trace

LAYER = "round"
UNIT = "ms"
MOVES = "rounds_per_s"


def read(trace):
    phases = of_trace(trace)
    if phases is None or not phases["phase_s"]:
        return None
    return 1e3 * phases["unscoped_s"] / trace["rounds"]
