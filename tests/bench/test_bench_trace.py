"""The reduction from a profiler trace to busy time, op times and named
idle gaps: on hand-made events, and on a small trace recorded on a TPU v5e
and checked in beside this file."""
from __future__ import annotations

import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "small_trace.xplane.pb")


def test_union_and_gaps():
    from bench.harness.trace import gaps, union

    merged = union([(3.0, 4.0), (0.0, 1.0), (0.5, 2.0)])
    assert merged == [(0.0, 2.0), (3.0, 4.0)]
    assert gaps(merged, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert gaps(merged, 0.0, 2.0) == []


def test_reduce_events_by_hand():
    from bench.harness.trace import reduce_events

    device = {"/device:TPU:0": [
        ("sort.1", 1.0, 2.0), ("fusion.2", 1.5, 3.0),  # overlap: busy 2
        ("sort.1", 6.0, 7.0),                          # busy 1
        ("fusion.3", 9.5, 11.0),                       # half in the window
    ]}
    host = [("window", 0.0, 10.0), ("dispatch", 0.0, 1.0),
            ("fetch", 3.0, 6.0), ("feed", 7.0, 9.5), ("fetch", 3.2, 5.9)]
    s = reduce_events(device, host)
    assert s["window_s"] == 10.0
    assert s["busy_s"] == pytest.approx(3.5)
    assert s["op_time"]["sort.1"] == pytest.approx(2.0)
    assert s["op_time"]["fusion.3"] == pytest.approx(0.5)
    gaps = dict((round(t, 6), n) for n, t in s["breakdown"]["idle_gaps"])
    # the innermost span covering most of a gap names it
    assert gaps == {1.0: "dispatch", 3.0: "fetch", 2.5: "feed"}
    assert s["breakdown"]["device_ops"][0] == ["sort.1", 2.0]


def test_op_name():
    from bench.harness.trace import op_name

    assert op_name("%sort.19 = (f32[20,79446]{1,0}) sort(f32[20] %x)") == \
        "sort.19"
    assert op_name("copy-done.3") == "copy-done.3"


def test_reduce_needs_one_window():
    from bench.harness.trace import reduce_events

    with pytest.raises(RuntimeError):
        reduce_events({"/device:TPU:0": []}, [("dispatch", 0.0, 1.0)])


def test_recorded_chip_trace():
    """Three steps of a sort and a matmul, a 10 ms host sleep after each
    (span ``feed``), traced on one v5e."""
    from bench.harness.trace import read_xplane, reduce_events

    device, host = read_xplane(RECORDED)
    assert list(device) == ["/device:TPU:0"]
    s = reduce_events(device, host)
    assert 0 < s["busy_s"] < s["window_s"]
    longest = s["breakdown"]["idle_gaps"][:3]
    assert [n for n, _ in longest] == ["feed"] * 3
    assert all(t >= 0.01 for _, t in longest)
    assert any(n.split(".")[0] == "sort" for n in s["op_time"])
    assert all(" " not in n for n in s["op_time"])


def test_readers_on_a_summary():
    """Each reader's arithmetic on a hand-made trace summary; a reader that
    finds nothing to read returns None, never 0."""
    from bench.harness.cli import load_reader

    summary = {
        "busy_s": 3.0, "window_s": 4.0, "chips": 1, "rounds": 20,
        "op_time": {"sort.3": 0.2, "fusion.1": 2.0,
                    "vmap_jit_sparsify_quantize_ef__.7": 0.5},
        "flops_per_round": 1e12, "kernel_elements_per_round": 1_000_000,
        "peaks": {"bf16_flops_per_s": 2e14, "hbm_bytes_per_s": 1e12},
        "pack_s": [0.01, 0.03],
    }
    read = lambda name, s=summary: load_reader(name).read(s)
    assert read("device_idle_share.train") == pytest.approx(25.0)
    assert read("device_idle_share.ingest") == pytest.approx(25.0)
    assert read("sort_ms_per_round") == pytest.approx(10.0)
    assert read("round_mfu") == pytest.approx(100 * 20e12 / 4.0 / 2e14)
    # 20 rounds x 12 bytes x 1e6 values over 1e12 B/s, in 0.5 s
    assert read("codec_kernel_roofline") == pytest.approx(100 * 2.4e-4 / 0.5)
    assert read("pack_ms_per_batch") == pytest.approx(20.0)
    empty = dict(summary, op_time={"fusion.1": 2.0}, pack_s=[])
    for name in ("sort_ms_per_round", "codec_kernel_roofline",
                 "pack_ms_per_batch"):
        assert read(name, empty) is None
