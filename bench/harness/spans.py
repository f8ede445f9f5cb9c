"""Host spans of the harness, and the seed every program input comes from."""
from __future__ import annotations

import hashlib
import time
from contextlib import contextmanager

# Every harness span carries this prefix, so the trace reduction tells them
# from the program's own annotations.
PREFIX = "bench."
# JAX records this event once for each program it lowers, which it then
# compiles or loads from the persistent cache.
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_lowered = [0, False]  # programs lowered so far, listener registered


def seed32(seed: int) -> int:
    """A 31-bit seed drawn from any whole ``--seed``, which may exceed 32
    signed bits: the same seed gives the same inputs."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(digest[:4], "little") & 0x7FFFFFFF


def lowered() -> int:
    """Programs JAX has lowered in this process so far.  The count from a
    window's start to its end should be 0: nothing compiles inside it."""
    if not _lowered[1]:
        import jax

        def count(event, _secs, **_):
            if event == LOWERED:
                _lowered[0] += 1

        jax.monitoring.register_event_duration_secs_listener(count)
        _lowered[1] = True
    return _lowered[0]


class Spans:
    """Named host-clock intervals around the harness's calls into the
    program.  With ``annotate`` each span is also a
    ``jax.profiler.TraceAnnotation``, so a traced run can name what the
    host was doing in each idle gap of the device."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter() - t0))
            if ann is not None:
                ann.__exit__(None, None, None)

    def durations(self, name: str) -> list[float]:
        return [d for n, _, d in self.records if n == name]
