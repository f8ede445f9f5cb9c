"""Profiler trace of a traced window, reduced to device busy time, op times
and idle gaps named by the harness span open in each.

The rule, written once (``PERF.md`` repeats it):

* the window is the host span ``bench.window`` on the profiler's clock;
* device ops are the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane, clipped to the window; an op is named by its
  HLO instruction (``fusion.12``, ``sort.3``), the text of the event's
  name before `` = ``;
* busy time of a chip is the length of the union of its op intervals;
  ``busy_s`` averages it over the chips, and the idle share is
  ``1 - busy_s / window_s``;
* an op's device time is the sum of its events' durations; a control-flow
  op (``while``, ``conditional``, ``call``) spans the ops it runs and is
  left out of the op times, not of the busy time; kernel and sort times
  are sums over the ops a metric's reader selects by name;
* an idle gap is an interval of the window in which no op runs on the
  chip; it is named by the innermost ``bench.*`` span (other than the
  window) that covers most of it, or ``host`` where none does.
"""
from __future__ import annotations

import glob
import os
import shutil
from contextlib import contextmanager

from bench.harness.spans import PREFIX

WINDOW = "window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
TOP = 10
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@contextmanager
def profile(out_dir: str):
    """Trace the body with the JAX profiler into ``out_dir`` (emptied
    first); the Python tracer stays off, the harness's spans are
    ``TraceAnnotation``s."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(out_dir: str) -> str:
    found = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"want one .xplane.pb under {out_dir}, "
                           f"found {len(found)}")
    return found[0]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted intervals covering the same points."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The parts of [lo, hi] that ``busy`` (merged) leaves uncovered."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def name_gap(gap, spans) -> str:
    """The innermost harness span covering most of the gap."""
    a, b = gap
    best, best_key = "host", None
    for name, s, e in spans:
        cover = min(b, e) - max(a, s)
        if cover <= 0.5 * (b - a):
            continue
        key = e - s  # innermost: the shortest span that covers most of it
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best


def reduce_events(device_ops: dict, host_spans: list) -> dict:
    """Reduce events (seconds on one clock) to the trace summary.

    ``device_ops``: ``{chip: [(name, start, end), ...]}``;
    ``host_spans``: ``[(name, start, end), ...]`` of harness spans with the
    prefix removed, one of them ``window``."""
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"want one {WINDOW!r} span, found {len(windows)}")
    lo, hi = windows[0]
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW]
    op_time: dict[str, float] = {}
    op_count: dict[str, int] = {}
    busy, all_gaps = [], []
    for chip, events in sorted(device_ops.items()):
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                   if e > lo and s < hi]
        for n, s, e in clipped:
            if n.split(".")[0] in CONTAINERS:
                continue
            op_time[n] = op_time.get(n, 0.0) + (e - s)
            op_count[n] = op_count.get(n, 0) + 1
        merged = union([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged))
        all_gaps += [(name_gap(g, spans), g[1] - g[0])
                     for g in gaps(merged, lo, hi)]
    if not busy:
        raise RuntimeError("the trace holds no device plane")
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(all_gaps, key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": hi - lo,
        "chips": len(busy),
        "op_time": op_time,
        "op_count": op_count,
        "breakdown": {"device_ops": [[n, t] for n, t in top_ops],
                      "idle_gaps": [[n, t] for n, t in top_gaps]},
    }


def read_xplane(path: str) -> tuple[dict, list]:
    """(device ops per chip, harness spans) from an ``.xplane.pb`` file,
    in seconds on the profiler's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: dict[str, list] = {}
    host_spans = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (op_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host_spans.append((e.name[len(PREFIX):],
                                           e.start_ns * 1e-9,
                                           e.end_ns * 1e-9))
    return device_ops, host_spans


def summarize(out_dir: str) -> dict:
    return reduce_events(*read_xplane(xplane_file(out_dir)))


def op_seconds(summary: dict, match) -> float:
    """Device seconds of the ops whose name ``match`` accepts."""
    return sum(t for n, t in summary["op_time"].items() if match(n))
