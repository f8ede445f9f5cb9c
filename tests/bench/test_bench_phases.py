"""Device time by the program's phases and set-up's compiles, read from a
traced window: the reduction on hand-made events, the trace file read
without ``ProfileData``, and the readers on summaries with and without
what they read."""
from __future__ import annotations

import os
import shutil
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "small_trace.xplane.pb")
# a tiny federation with the scopes, traced on one v5e (the HLO protos of
# its `/host:metadata` plane left out)
TINY = os.path.join(HERE, "data", "tiny_afl.xplane.pb")
PHASE_READERS = {"grads_ms_per_round": ("grads",),
                 "select_ms_per_round": ("select",),
                 "compress_ms_per_round": ("compress",),
                 "aggregate_state_ms_per_round": ("aggregate", "state")}
NEW_READERS = tuple(PHASE_READERS) + ("unscoped_ms_per_round",
                                      "setup_jax_s", "setup_programs")


def read(name, summary):
    from bench.harness.cli import load_reader

    return load_reader(name).read(summary)


def test_phase_of_tf_op():
    from bench.harness.phases import UNSCOPED, phase_of

    assert phase_of("jit(run)/while/body/jit(afl_round)/afl.grads/"
                    "vmap(transpose(jvp()))/conv:") == "grads"
    assert phase_of("jit(f)/vmap(afl.state)/sub") == "state"
    assert phase_of("jit(f)/notafl.grads/sub") == UNSCOPED
    assert phase_of("") == UNSCOPED


def test_phase_seconds_by_hand():
    """Ops count only inside the busiest module's runs and the window; a
    name shared with another program's op does not mix in; control flow
    is left out; phases and unscoped sum to the module's op time."""
    from bench.harness.phases import phase_seconds

    modules = [("jit_run(1)", 0.0, 4.0), ("jit_fold_in(2)", 4.5, 5.0),
               ("jit_run(1)", 5.0, 9.0)]
    ops = [
        ("fusion.1", "grads", 0.1, 1.1),      # 1.0
        ("while.2", "unscoped", 0.0, 4.0),    # control flow: out
        ("sort.3", "compress", 1.5, 2.0),     # 0.5
        ("copy.4", "unscoped", 2.0, 2.25),    # 0.25
        ("fusion.1", "unscoped", 4.6, 4.9),   # the other program: out
        ("fusion.1", "grads", 5.0, 6.0),      # 1.0
        ("fusion.5", "state", 8.5, 9.0),      # half in the window: 0.25
    ]
    got = phase_seconds({"/device:TPU:0": (ops, modules)}, 0.0, 8.75)
    assert got["module"] == "jit_run(1)"
    assert got["phase_s"] == pytest.approx({"grads": 2.0, "compress": 0.5,
                                            "state": 0.25})
    assert got["unscoped_s"] == pytest.approx(0.25)
    assert got["module_s"] == pytest.approx(
        sum(got["phase_s"].values()) + got["unscoped_s"])
    assert got["other_s"] == pytest.approx(0.3)  # the other program's op
    # two chips: op times add up, as the op times of trace.py do
    two = phase_seconds({"a": (ops, modules), "b": (ops, modules)},
                        0.0, 8.75)
    assert two["module_s"] == pytest.approx(2 * got["module_s"])
    assert phase_seconds({"a": (ops, [])}, 0.0, 8.75) is None


def test_read_events_matches_profile_data():
    """The trace file read with the XPlane schema gives the ops, times and
    window that ``ProfileData`` gives, and the modules that ran."""
    from bench.harness.phases import UNSCOPED, read_events
    from bench.harness.trace import read_xplane

    events = read_events(RECORDED)
    device, host = read_xplane(RECORDED)
    ops, modules = events["chips"]["/device:TPU:0"]
    want = device["/device:TPU:0"]
    assert [o[0] for o in ops] == [n for n, _, _ in want]
    for (_, _, s, e), (_, ws, we) in zip(ops, want):
        assert s == pytest.approx(ws, abs=2e-9)
        assert e == pytest.approx(we, abs=2e-9)
    assert {o[1] for o in ops} == {UNSCOPED}  # recorded without scopes
    window = [(s, e) for n, s, e in host if n == "window"]
    assert events["windows"] == pytest.approx(window)
    assert {m[0] for m in modules} == {"jit__lambda(10478693613067580193)",
                                      "jit__lambda(2901289077683614864)"}
    assert events["profile_start_s"] == pytest.approx(1792193312.965109)


def test_existing_readers_unchanged_on_the_recorded_trace():
    """The three accepted readers read what they read before these
    readers were added."""
    from bench.harness.trace import read_xplane, reduce_events

    s = reduce_events(*read_xplane(RECORDED))
    s.update(rounds=3, flops_per_round=1e9, kernel_elements_per_round=1,
             peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert read("device_idle_share.train", s) == pytest.approx(
        90.87707014933144, rel=1e-12)
    assert read("round_mfu", s) == pytest.approx(0.03872644425576011,
                                                 rel=1e-12)
    assert read("sort_ms_per_round", s) == pytest.approx(1.164836666666664,
                                                         rel=1e-12)


def test_new_readers_on_a_summary():
    phases = {"module": "jit_run(1)", "module_s": 2.0, "unscoped_s": 0.1,
              "phase_s": {"grads": 1.0, "select": 0.2, "compress": 0.3,
                          "aggregate": 0.1, "state": 0.2, "sample": 0.05,
                          "eval": 0.05},
              "profile_start_s": 0.0}
    compiles = {"lowered": 42, "jax_s": 12.5}
    s = {"rounds": 20, "phases": phases, "setup_compiles": compiles}
    for name, names in PHASE_READERS.items():
        assert read(name, s) == pytest.approx(
            1e3 * sum(phases["phase_s"][n] for n in names) / 20)
    assert read("aggregate_state_ms_per_round", s) == pytest.approx(15.0)
    assert read("unscoped_ms_per_round", s) == pytest.approx(5.0)
    assert read("setup_jax_s", s) == 12.5
    assert read("setup_programs", s) == 42


def test_new_readers_none_without_their_input(tmp_path, monkeypatch):
    """No trace, a program without scopes, a phase it lacks, no compile
    counter: each reader returns None and does not raise."""
    from bench.harness import federation

    s = {"rounds": 20, "phases": None, "setup_compiles": None}
    for name in NEW_READERS:
        assert read(name, dict(s)) is None
    lacking = {"rounds": 20, "phases": {
        "module_s": 1.0, "unscoped_s": 0.5, "phase_s": {"grads": 0.5},
        "profile_start_s": None}}
    assert read("aggregate_state_ms_per_round", dict(lacking)) is None
    only_state = {"rounds": 20, "phases": dict(lacking["phases"],
                                               phase_s={"state": 0.2})}
    assert read("aggregate_state_ms_per_round", only_state) == \
        pytest.approx(10.0)
    assert read("setup_programs", dict(lacking)) is None
    # a trace directory with no trace in it
    monkeypatch.setattr(federation, "TRACE_DIR", str(tmp_path / "none"))
    for name in NEW_READERS:
        assert read(name, {"rounds": 20}) is None
    # the recorded trace: a program with no scopes
    shutil.copy(RECORDED, tmp_path / "small.xplane.pb")
    monkeypatch.setattr(federation, "TRACE_DIR", str(tmp_path))
    summary = {"rounds": 3}
    for name in tuple(PHASE_READERS) + ("unscoped_ms_per_round",):
        assert read(name, summary) is None
    assert summary["phases"]["module_s"] > 0


def test_setup_compiles_count_up_to_the_profile_start():
    """The program's counter, cut at the profile's start: what began after
    it is not set-up."""
    import jax
    import jax.numpy as jnp

    from bench.harness.phases import setup_compiles
    from repro.telemetry.tracing import compiles

    x = jnp.ones(3).block_until_ready()
    start = time.time()
    before = compiles.totals(until=start)
    jax.jit(lambda v: v - 2.0)(x).block_until_ready()
    got = setup_compiles({"phases": {"profile_start_s": start}})
    assert got == before
    assert compiles.totals()["lowered"] == got["lowered"] + 1


def test_recorded_scoped_round():
    """A tiny federation (ResNet-9 at width 4, four clients, two rounds)
    traced on one v5e with the scopes: every phase is found, inside the
    segment program, and the phases sum to its op time."""
    from bench.harness.phases import phase_seconds, read_events

    events = read_events(TINY)
    assert len(events["windows"]) == 1
    got = phase_seconds(events["chips"], *events["windows"][0])
    assert got["module"].startswith("jit_run(")
    assert set(got["phase_s"]) == {"grads", "select", "compress",
                                   "aggregate", "state", "sample", "eval"}
    assert got["module_s"] == pytest.approx(
        sum(got["phase_s"].values()) + got["unscoped_s"], rel=1e-12)
    assert max(got["phase_s"], key=got["phase_s"].get) == "grads"
    assert got["unscoped_s"] < 0.1 * got["module_s"]


@pytest.mark.parametrize("recorded", [RECORDED, TINY])
def test_module_and_other_ops_make_the_window_op_time(recorded):
    """The segment program's ops and the ops outside its runs add up to
    the window's op time as ``trace.py`` reads it through ``ProfileData``:
    no op is lost or counted twice by the module attribution.
    ``ProfileData`` cuts each event's times to whole nanoseconds, so the
    two sums may part by up to a nanosecond an op."""
    from bench.harness.phases import phase_seconds, read_events
    from bench.harness.trace import read_xplane, reduce_events

    events = read_events(recorded)
    got = phase_seconds(events["chips"], *events["windows"][0])
    summary = reduce_events(*read_xplane(recorded))
    want = sum(summary["op_time"].values())
    ops = sum(summary["op_count"].values())
    assert got["module_s"] > 0.5 * want
    assert got["module_s"] + got["other_s"] == pytest.approx(
        want, abs=1e-9 * ops)
