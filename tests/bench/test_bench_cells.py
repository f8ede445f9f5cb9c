"""The benchmark is driven by data: cells, configurations, traffic mixes,
limits and metric readers are files found by name, and the same seed
gives the same traffic."""
from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from conftest import ROOT, cell_files, tiny_cell


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_files():
    from bench.harness.cli import find_cell

    for w in spec()["workloads"]:
        cell = find_cell(w["name"])
        assert cell == cell_files(w["name"])
        assert cell["traffic"]["kind"] in ("federation", "ingest")
        assert set(cell["limits"]["limits"])
        assert cell["chips"] == 1


def test_every_metric_has_a_reader_that_declares_it():
    from bench.harness.cli import load_reader

    s = spec()
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["per_layer"]:
        reader = load_reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            m["layer"], m["unit"], m["moves"])
        assert m["moves"] in e2e


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    """A later cell adds a traffic file and a workloads entry, and edits
    no file of the harness."""
    from bench.harness.cli import find_cell

    for part in ("BENCHMARK.json", "bench"):
        src = os.path.join(ROOT, part)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(
            src, tmp_path / part)
    s = spec()
    s["workloads"].append({"name": "resnet9.mads.n40", "config":
                           "resnet9-w64", "traffic": "mads.n40",
                           "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    traffic = json.loads((tmp_path / "bench/traffic/mads.n20.json")
                         .read_text())
    traffic["num_devices"] = 40
    (tmp_path / "bench/traffic/mads.n40.json").write_text(
        json.dumps(traffic))
    shutil.copy(tmp_path / "bench/limits/resnet9.mads.n20.json",
                tmp_path / "bench/limits/resnet9.mads.n40.json")
    cell = find_cell("resnet9.mads.n40", root=str(tmp_path))
    assert cell["traffic"]["num_devices"] == 40
    with pytest.raises(KeyError):
        find_cell("resnet9.mads.n80", root=str(tmp_path))


def test_cell_metrics_follow_their_workloads():
    """A per-layer metric is read in the cells it lists, and only where the
    cell reports the end-to-end metric it moves."""
    from bench.harness.cli import cell_metrics

    s = {"end_to_end": [{"name": "rounds_per_s", "workloads": ["a", "b"]},
                        {"name": "ingest_p95_ms", "workloads": ["c"]},
                        {"name": "setup_s"}],
         "per_layer": [{"name": "idle", "moves": "rounds_per_s"},
                       {"name": "kernel", "moves": "rounds_per_s",
                        "workloads": ["b"]},
                       {"name": "pack", "moves": "ingest_p95_ms",
                        "workloads": ["c"]}]}
    names = lambda cell, e2e: [m["name"] for m in cell_metrics(s, cell, e2e)[1]]
    assert names("a", ["rounds_per_s", "setup_s"]) == ["idle"]
    assert names("b", ["rounds_per_s", "setup_s"]) == ["idle", "kernel"]
    assert names("c", ["ingest_p95_ms", "setup_s"]) == ["pack"]
    e2e, _ = cell_metrics(s, "c", ["rounds_per_s", "ingest_p95_ms", "setup_s"])
    assert [m["name"] for m in e2e] == ["ingest_p95_ms", "setup_s"]


def test_seed32_is_stable_and_takes_large_seeds():
    from bench.harness.spans import seed32

    assert seed32(2**40 + 1) == seed32(2**40 + 1)
    assert seed32(2**40 + 1) != seed32(2**40 + 2)
    assert 0 <= seed32(-5) < 2**31


@pytest.mark.parametrize("workload", ["resnet9.mads.n20",
                                      "lanegcn.mads.n200"])
def test_same_seed_same_traffic(workload):
    import jax

    from bench.harness import federation

    cell = tiny_cell(workload)
    config, traffic = cell["config"], cell["traffic"]

    def draw(seed):
        b = federation.build(config, traffic, seed)
        rows = b["shard"].traced_batch(jax.random.fold_in(b["batch_key"],
                                                          1), 0)
        return ([np.asarray(a) for a in b["schedule"]],
                [np.asarray(a) for a in jax.tree.leaves(b["w0"])],
                [np.asarray(a) for a in jax.tree.leaves(rows)],
                np.asarray(b["budgets"]))

    one, again, other = draw(2**33 + 1), draw(2**33 + 1), draw(7)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(again)):
        np.testing.assert_array_equal(a, b)
    assert any(not np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(one), jax.tree.leaves(other)))
