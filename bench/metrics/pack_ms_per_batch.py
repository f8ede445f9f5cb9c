"""The server's own ``serve.pack`` span (host packing of a batch of wire
payloads, and its placement): mean over the batches of the traced window."""
LAYER = "ingest host path"
UNIT = "ms"
MOVES = "ingest_p95_ms"


def read(trace):
    packs = trace["pack_s"]
    if not packs:
        return None
    return 1e3 * sum(packs) / len(packs)
