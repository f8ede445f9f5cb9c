"""Share of the traced window in which no operation ran on the chip."""
LAYER = "device"
UNIT = "%"
MOVES = "rounds_per_s"


def read(trace):
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
