"""Wall-clock span/phase tracing with async-dispatch-safe fencing.

JAX dispatches asynchronously: ``time.time()`` after a jitted call times
the *dispatch*, not the work, unless the result is fenced with
``jax.block_until_ready``.  ``PhaseTracer.span`` records honest wall-clock
phases (compile vs execute vs eval) when the caller fences inside the
span (``tracer.fence(out)``); repeated spans with the same name aggregate
in the summary, so per-round spans stay readable.

Optional profiler hooks: constructing the tracer with ``profile_dir``
(the ``--profile-dir`` flag of train/sweep/benchmarks) wraps each span in
``jax.profiler.TraceAnnotation`` and brackets the run with
``start_trace``/``stop_trace`` so spans line up with the device timeline
in TensorBoard/Perfetto.  Without ``profile_dir`` the tracer costs two
``perf_counter`` calls and a list append per span.
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager
from typing import Optional

import jax


@dataclasses.dataclass
class Span:
    name: str
    start: float  # perf_counter seconds
    duration: float
    meta: dict
    parent: Optional[str] = None  # enclosing span's name (nesting)
    depth: int = 0  # nesting depth at entry (0 = top level)
    error: Optional[str] = None  # exception type name if the body raised


class PhaseTracer:
    """Collects named wall-clock spans; optionally mirrors them into the
    JAX profiler when ``profile_dir`` is set."""

    def __init__(self, profile_dir: Optional[str] = None):
        self.profile_dir = profile_dir or None
        self.spans: list[Span] = []
        self._tracing = False
        self._stack: list[str] = []  # open span names (nesting)

    # -- spans ---------------------------------------------------------------

    @contextmanager
    def span(self, name: str, **meta):
        """Record a wall-clock span.  Exception-safe: a body that raises
        still lands its span (with the exception type under ``error``),
        so a crashed sweep's trace shows WHERE the time went before the
        failure.  Spans nest — an inner span records its enclosing span
        as ``parent`` and its ``depth``, surfaced by ``events()``."""
        ann = None
        if self.profile_dir is not None:
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        parent = self._stack[-1] if self._stack else None
        depth = len(self._stack)
        self._stack.append(name)
        err: Optional[str] = None
        t0 = time.perf_counter()
        try:
            yield self
        except BaseException as e:
            err = type(e).__name__
            raise
        finally:
            self._stack.pop()
            self.spans.append(
                Span(name, t0, time.perf_counter() - t0, dict(meta),
                     parent=parent, depth=depth, error=err)
            )
            if ann is not None:
                ann.__exit__(None, None, None)

    @staticmethod
    def fence(x):
        """Block until ``x``'s arrays are computed (no-op on host data) —
        call before leaving a span so its wall time covers the work.  A
        device error surfaces here and propagates."""
        jax.block_until_ready(x)
        return x

    # -- profiler bracket ----------------------------------------------------

    def start(self) -> None:
        """Begin a device trace under ``profile_dir`` (no-op without).

        A trace that was asked for and cannot start raises: the run does not
        go on silently untraced.
        """
        if self.profile_dir is None or self._tracing:
            return
        jax.profiler.start_trace(self.profile_dir)
        self._tracing = True

    def stop(self) -> None:
        if self._tracing:
            try:
                jax.profiler.stop_trace()
            finally:
                self._tracing = False

    # -- reporting -----------------------------------------------------------

    def totals(self) -> dict:
        """name -> {count, total_s, max_s} aggregated over spans."""
        out: dict = {}
        for s in self.spans:
            agg = out.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                          "max_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += s.duration
            agg["max_s"] = max(agg["max_s"], s.duration)
        return out

    def summary(self) -> str:
        lines = [f"{'phase':<24s} {'count':>6s} {'total_s':>10s} "
                 f"{'mean_ms':>10s} {'max_ms':>10s}"]
        for name, agg in self.totals().items():
            lines.append(
                f"{name:<24s} {agg['count']:>6d} {agg['total_s']:>10.3f} "
                f"{agg['total_s'] / agg['count'] * 1e3:>10.2f} "
                f"{agg['max_s'] * 1e3:>10.2f}"
            )
        return "\n".join(lines)

    def events(self) -> list[dict]:
        """Span records for the JSONL sink (parent/depth attribute nested
        spans; ``error`` marks spans whose body raised)."""
        out = []
        for s in self.spans:
            ev = {"kind": "span", "name": s.name,
                  "start_s": round(s.start, 6),
                  "duration_s": round(s.duration, 6), **s.meta}
            if s.parent is not None:
                ev["parent"] = s.parent
                ev["depth"] = s.depth
            if s.error is not None:
                ev["error"] = s.error
            out.append(ev)
        return out
