"""Share of the traced ingest window in which no operation ran on the chip."""
LAYER = "device"
UNIT = "%"
MOVES = "ingest_p95_ms"


def read(trace):
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
