"""Device time of the round's phases in a traced window, read from the
profiler's ``.xplane.pb`` by the program's named scopes, and the program's
compile counter up to the window.

The rule, written once (``PERF.md`` repeats it):

* the segment program is the XLA module whose executions (the events of
  the ``XLA Modules`` line of each ``/device:TPU:<n>`` plane) take the most
  time in the window;
* its ops are the ``XLA Ops`` events that start inside one of its
  executions, clipped to the window; control-flow ops are left out, as in
  ``trace.py``, so the op times of other programs never mix in, whatever
  their instruction names;
* an op's phase is the first ``afl.<phase>`` scope of its framework op
  name (the ``tf_op`` stat of the event's metadata, which is the HLO
  instruction's ``op_name``); an op with none is unscoped;
* a fused op is one op: it counts in the phase of its own ``op_name``,
  which XLA takes from the fusion's root, whatever the scopes of the
  instructions fused into it;
* ``phase_s`` sums the op time of each phase; with ``unscoped_s`` it makes
  ``module_s``, the segment program's op time in the window; ``other_s``
  is the op time of the window's ops outside the segment program's runs,
  so ``module_s + other_s`` is the window's op time that ``trace.py``
  reads from the same file through ``ProfileData``;
* set-up's compiles are those the program's own counter
  (``repro.telemetry.tracing.compiles``) saw begin before the profile
  started, which is just before the window; none may begin in the window.

``ProfileData`` gives no event's metadata stats, so the file is read with
the XPlane schema (``tsl/profiler/protobuf/xplane.proto``), declared here
for the ``protobuf`` package with the fields this reduction needs.
"""
from __future__ import annotations

import functools
import re
from bisect import bisect_right

from bench.harness.spans import PREFIX
from bench.harness.trace import (CONTAINERS, DEVICE_PLANE, OPS_LINE, WINDOW,
                                 op_name, xplane_file)

MODULES_LINE = "XLA Modules"
TF_OP = "tf_op"
ENVIRONMENT_PLANE = "Task Environment"
PROFILE_START = "profile_start_time"
UNSCOPED = "unscoped"
# the first afl.<phase> of an op name, at the start of a path component or
# inside a transform's parentheses; kept here, not taken from the program,
# so that a change to the program cannot change what the metrics read
PHASE_RE = re.compile(r"(?:^|[/(])afl\.(\w+)")


def phase_of(tf_op: str) -> str:
    m = PHASE_RE.search(tf_op)
    return m.group(1) if m else UNSCOPED


@functools.cache
def xspace_class():
    """The ``XSpace`` message, with the fields read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    kinds = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
             "double": F.TYPE_DOUBLE, "bytes": F.TYPE_BYTES}
    schema = {
        "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
                  ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
                  ("str_value", 5, "bytes"), ("ref_value", 7, "uint64")],
        "XEventMetadata": [("id", 1, "int64"), ("name", 2, "bytes"),
                           ("stats", 5, "*XStat")],
        "XStatMetadata": [("id", 1, "int64"), ("name", 2, "bytes")],
        "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
                   ("duration_ps", 3, "int64")],
        "XLine": [("name", 2, "bytes"), ("timestamp_ns", 3, "int64"),
                  ("events", 4, "*XEvent")],
        "EventMetadataEntry": [("key", 1, "int64"),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, "int64"),
                              ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, "bytes"), ("lines", 3, "*XLine"),
                   ("event_metadata", 4, "*EventMetadataEntry"),
                   ("stat_metadata", 5, "*StatMetadataEntry"),
                   ("stats", 6, "*XStat")],
        "XSpace": [("planes", 1, "*XPlane")],
    }
    package = "bench_xplane"
    proto = descriptor_pb2.FileDescriptorProto(name=package + ".proto",
                                               package=package)
    for message, fields in schema.items():
        m = proto.message_type.add(name=message)
        for name, number, kind in fields:
            repeated = kind.startswith("*")
            kind = kind.lstrip("*")
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if kind in kinds:
                f.type = kinds[kind]
            else:
                f.type = F.TYPE_MESSAGE
                f.type_name = f".{package}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(package + ".XSpace"))


def _seconds(line, event) -> tuple[float, float]:
    start_ps = line.timestamp_ns * 1000 + event.offset_ps
    return start_ps * 1e-12, (start_ps + event.duration_ps) * 1e-12


def _stat_value(stat, stat_names: dict):
    """A stat's value; a string kept by reference is the name of the stat
    metadata it refers to."""
    if stat.HasField("str_value"):
        return stat.str_value.decode(errors="replace")
    if stat.HasField("ref_value"):
        return stat_names.get(stat.ref_value)
    for field in ("int64_value", "uint64_value", "double_value"):
        if stat.HasField(field):
            return getattr(stat, field)
    return None


def read_events(path: str) -> dict:
    """``{"chips": {plane: (ops, modules)}, "windows": [(start, end)],
    "profile_start_s": ...}`` from an ``.xplane.pb`` file: ops as
    ``(instruction, phase, start, end)``, modules as ``(name, start,
    end)``, on the clock of ``trace.read_xplane``; the profile's start in
    seconds since the epoch, or None."""
    with open(path, "rb") as f:
        space = xspace_class().FromString(f.read())
    chips, windows, profile_start = {}, [], None
    for plane in space.planes:
        name = plane.name.decode()
        stat_names = {e.key: e.value.name.decode()
                      for e in plane.stat_metadata}
        if name == ENVIRONMENT_PLANE:
            for s in plane.stats:
                if stat_names.get(s.metadata_id) == PROFILE_START:
                    profile_start = _stat_value(s, stat_names) * 1e-9
        elif name.startswith(DEVICE_PLANE):
            meta = {}
            for e in plane.event_metadata:
                tf_op = next((_stat_value(s, stat_names) for s in e.value.stats
                              if stat_names.get(s.metadata_id) == TF_OP), "")
                meta[e.key] = (op_name(e.value.name.decode()),
                               phase_of(str(tf_op)))
            ops, modules = [], []
            for line in plane.lines:
                if line.name.decode() == OPS_LINE:
                    ops += [(*meta.get(e.metadata_id, ("", UNSCOPED)),
                             *_seconds(line, e)) for e in line.events]
                elif line.name.decode() == MODULES_LINE:
                    modules += [(meta.get(e.metadata_id, ("",))[0],
                                 *_seconds(line, e)) for e in line.events]
            chips[name] = (ops, modules)
        elif name.startswith("/host:"):
            names = {e.key: e.value.name.decode()
                     for e in plane.event_metadata}
            for line in plane.lines:
                windows += [_seconds(line, e) for e in line.events
                            if names.get(e.metadata_id) == PREFIX + WINDOW]
    return {"chips": chips, "windows": windows,
            "profile_start_s": profile_start}


def phase_seconds(chips: dict, lo: float, hi: float) -> dict | None:
    """Op time of the segment program in ``[lo, hi]``, by phase (the rule
    above); None where no module ran in the window."""
    def clip(s, e):
        return max(0.0, min(e, hi) - max(s, lo))

    module_time: dict[str, float] = {}
    for _, modules in chips.values():
        for name, s, e in modules:
            module_time[name] = module_time.get(name, 0.0) + clip(s, e)
    if not module_time or max(module_time.values()) <= 0:
        return None
    top = max(module_time, key=module_time.get)
    by_phase: dict[str, float] = {}
    other = 0.0
    for ops, modules in chips.values():
        runs = sorted((s, e) for name, s, e in modules if name == top)
        starts = [s for s, _ in runs]
        for name, phase, s, e in ops:
            t = clip(s, e)
            if t <= 0 or name.split(".")[0] in CONTAINERS:
                continue
            i = bisect_right(starts, s) - 1
            if i < 0 or s > runs[i][1]:
                other += t
                continue
            by_phase[phase] = by_phase.get(phase, 0.0) + t
    unscoped = by_phase.pop(UNSCOPED, 0.0)
    return {"module": top, "module_s": sum(by_phase.values()) + unscoped,
            "phase_s": by_phase, "unscoped_s": unscoped, "other_s": other}


def of_trace(trace: dict) -> dict | None:
    """The phases of a traced window (``phase_seconds`` plus
    ``profile_start_s``): ``trace["phases"]`` where the summary holds
    them, else read once from the federation cell's trace directory and
    kept in the summary for the next reader; None where there is no
    trace to read or no single window in it."""
    if "phases" not in trace:
        from bench.harness.federation import TRACE_DIR

        try:
            events = read_events(xplane_file(TRACE_DIR))
        except (OSError, RuntimeError):
            trace["phases"] = None
            return None
        phases = None
        if len(events["windows"]) == 1:
            phases = phase_seconds(events["chips"], *events["windows"][0])
        if phases is not None:
            phases["profile_start_s"] = events["profile_start_s"]
        trace["phases"] = phases
    return trace["phases"]


def phase_ms_per_round(trace: dict, *names: str) -> float | None:
    """Device time per round of the named phases together; None where the
    program has none of these scopes."""
    phases = of_trace(trace)
    if phases is None or not set(names) & set(phases["phase_s"]):
        return None
    return 1e3 * sum(phases["phase_s"].get(n, 0.0)
                     for n in names) / trace["rounds"]


def setup_compiles(trace: dict) -> dict | None:
    """The program's compile counter up to the profile's start, which
    comes after set-up and before the window: ``trace["setup_compiles"]``
    where the summary holds it; None where the program has no counter."""
    if "setup_compiles" not in trace:
        from repro.telemetry import tracing

        counter = getattr(tracing, "compiles", None)
        phases = of_trace(trace)
        start = None if phases is None else phases.get("profile_start_s")
        trace["setup_compiles"] = (None if counter is None or start is None
                                   else counter.totals(until=start))
    return trace["setup_compiles"]
