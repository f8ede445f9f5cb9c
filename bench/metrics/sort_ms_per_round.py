"""Device time of the XLA sort ops (the top-k threshold) per round."""
from bench.harness.trace import op_seconds

LAYER = "threshold"
UNIT = "ms"
MOVES = "rounds_per_s"


def is_sort(name: str) -> bool:
    """XLA's sort instructions, ``sort.<n>``."""
    return name.split(".")[0] == "sort"


def read(trace):
    seconds = op_seconds(trace, is_sort)
    if seconds == 0:
        return None
    return 1e3 * seconds / trace["rounds"]
