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

Device-side phases: ``phase(name)`` opens the ``jax.named_scope``
``afl.<name>`` around a block of the round (``PHASES``).  A scope only
names the HLO instructions it lowers to (their ``op_name`` metadata), so
the compiled program is the same with or without it; ``op_phases`` maps
the instructions of a compiled program's text to their phase, which is
how a device trace's ops are read by phase.

``compiles`` counts, for the whole process, the programs JAX traces,
lowers and compiles and the seconds it spends doing so (``CompileCounter``).
"""
from __future__ import annotations

import dataclasses
import re
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


# -- device phases -----------------------------------------------------------

SCOPE_PREFIX = "afl."
# the blocks of one round of core/afl.py::afl_round (and of the distributed
# step), in the order they run
ROUND_PHASES = ("grads", "select", "compress", "aggregate", "state")
# the scan engine's own blocks around the round: the in-scan minibatch
# gather and the eval at a segment's end
PHASES = ROUND_PHASES + ("sample", "eval")
UNSCOPED = "unscoped"

# the first ``afl.<phase>`` of an op name, at the start of a path component
# or inside a transform's parentheses (``vmap(afl.grads)``)
_PHASE_RE = re.compile(r"(?:^|[/(])" + re.escape(SCOPE_PREFIX) + r"(\w+)")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{$")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME_RE = re.compile(r'metadata=\{[^\n]*?\bop_name="([^"]*)"')
# ``metadata={...}``, whose quoted strings may hold braces
_METADATA_RE = re.compile(r',? metadata=\{(?:[^{}"]|"[^"]*")*\}')
# the stack-frame tables at the head of a compiled program's text
_FRAMES_RE = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.MULTILINE)


def phase(name: str):
    """The named scope ``afl.<name>`` of one phase of the round."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; known: {PHASES}")
    return jax.named_scope(SCOPE_PREFIX + name)


def phase_of(op_name: str) -> str:
    """The phase an op name (an instruction's ``op_name`` metadata, the
    trace's ``tf_op``) lies in, or ``UNSCOPED``."""
    m = _PHASE_RE.search(op_name)
    return m.group(1) if m else UNSCOPED


def op_phases(hlo_text: str) -> dict[str, str]:
    """``{instruction name: phase}`` for every instruction of a compiled
    program's text (``jitted.lower(...).compile().as_text()``), fused
    computations included.  An instruction takes the phase of the first
    ``afl.*`` scope in its ``op_name``; one with none, such as a fusion
    the compiler made up, takes the phase of the first instruction of the
    computation it calls that has one; else ``UNSCOPED``.  The names are
    those that a device trace gives its ops (``fusion.12``)."""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}  # instruction -> computation it calls
    first: dict[str, str] = {}  # computation -> its first scoped phase
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            head = _COMP_RE.match(line)
            if head:
                comp = head.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME_RE.search(line)
        own[name] = phase_of(op.group(1)) if op else UNSCOPED
        if own[name] != UNSCOPED:
            first.setdefault(comp, own[name])
        called = _CALLS_RE.search(line)
        if called:
            calls[name] = called.group(1)
    return {name: p if p != UNSCOPED else first.get(calls.get(name), p)
            for name, p in own.items()}


def strip_metadata(hlo_text: str) -> str:
    """A compiled program's text without what scopes and source lines
    change: each instruction's ``metadata={...}`` and the stack-frame
    tables.  Two programs that differ only in their scopes strip equal."""
    return _FRAMES_RE.sub("", _METADATA_RE.sub("", hlo_text))


# -- compile counter ---------------------------------------------------------

# JAX's monitoring events (jax/_src/dispatch.py, compiler.py,
# compilation_cache.py).  TRACE, LOWER and COMPILE come as time spans, one
# per program: COMPILE covers the compile or its load from the persistent
# cache, so CACHE_LOAD (a duration) lies inside a COMPILE span, and the
# trace of a jitted function called while another traces lies inside that
# one's TRACE span.  Seconds are therefore lengths of unions of spans,
# never sums, and nothing is counted twice.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_STAGES = {TRACE_EVENT: "trace", LOWER_EVENT: "lower",
           COMPILE_EVENT: "compile"}
_COUNTS = {"trace": "traced", "lower": "lowered", "compile": "compiled"}
_MARKS = {CACHE_HIT_EVENT: "cache_hits", CACHE_MISS_EVENT: "cache_misses"}


def _union_s(spans) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(spans):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


class CompileCounter:
    """Programs JAX traced, lowered and compiled (or loaded from the
    persistent cache) in this process, and the seconds it spent at each,
    kept as time spans on ``time.time()``'s clock, each with the name of
    the function JAX reported, so that a reader can count up to a moment
    (``totals(until=...)``) or find the slowest programs (``spans``)."""

    def __init__(self):
        # stage, start, end, the function's name
        self.spans: list[tuple[str, float, float, str]] = []
        self.marks: list[tuple[str, float]] = []  # cache hit/miss, time
        self._installed = False

    def install(self) -> "CompileCounter":
        """Register the listeners with ``jax.monitoring`` (once)."""
        if not self._installed:
            jax.monitoring.register_event_time_span_listener(self._span)
            jax.monitoring.register_event_duration_secs_listener(
                self._duration)
            jax.monitoring.register_event_listener(self._event)
            self._installed = True
        return self

    def _span(self, event, start, end, fun_name="", **_):
        if event in _STAGES:
            self.spans.append((_STAGES[event], start, end, fun_name))

    def _duration(self, event, secs, **_):
        if event == CACHE_LOAD_EVENT:
            now = time.time()
            self.spans.append(("cache_load", now - secs, now, ""))

    def _event(self, event, **_):
        if event in _MARKS:
            self.marks.append((_MARKS[event], time.time()))

    def totals(self, until: Optional[float] = None) -> dict:
        """Counts and seconds of the spans that began before ``until``
        (a ``time.time()``; all of them without).  ``jax_s`` is the time
        inside any of JAX's trace, lower, compile or cache load."""
        until = float("inf") if until is None else until
        spans = [s for s in self.spans if s[1] < until]
        out = {count: sum(s[0] == stage for s in spans)
               for stage, count in _COUNTS.items()}
        out.update({mark: sum(m == mark and t < until for m, t in self.marks)
                    for mark in _MARKS.values()})
        for stage in ("trace", "lower", "compile", "cache_load"):
            out[stage + "_s"] = _union_s(
                (a, b) for st, a, b, _ in spans if st == stage)
        out["jax_s"] = _union_s((a, b) for _, a, b, _ in spans)
        return out


# one per process, counting from the first import of the program
compiles = CompileCounter().install()
