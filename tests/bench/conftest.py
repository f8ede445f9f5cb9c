"""Shared set-up of the benchmark's own tests: the checkout root on the
import path, and tiny copies of the cells that a CPU test run can hold."""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

# Every cell the harness has files for, whether BENCHMARK.json lists it or
# not: (configuration, traffic mix).
CELLS = {
    "resnet9.mads.n20": ("resnet9-w64", "mads.n20"),
    "lanegcn.mads.n200": ("lanegcn-d128", "mads.n200"),
    "resnet9.mads-joint.n20": ("resnet9-w64", "mads-joint.n20"),
    "resnet9.ingest.steady": ("resnet9-w64", "ingest.steady"),
}


def cell_files(workload: str) -> dict:
    """The cell as ``bench.harness.cli.find_cell`` gives it, read from its
    files alone; a cell whose limits are not set yet holds none."""
    from bench.harness.cli import load_json

    config, traffic = CELLS[workload]
    bench = os.path.join(ROOT, "bench")
    limits = os.path.join(bench, "limits", workload + ".json")
    return {
        "name": workload, "chips": 1,
        "config": load_json(os.path.join(bench, "configs", config + ".json")),
        "traffic": load_json(os.path.join(bench, "traffic",
                                          traffic + ".json")),
        "limits": (load_json(limits) if os.path.exists(limits)
                   else {"limits": {}}),
        "spec": load_json(os.path.join(ROOT, "BENCHMARK.json")),
    }


def tiny_cell(workload: str, **traffic_over) -> dict:
    """The named cell at a width and population a CPU test can hold:
    ResNet-9 at width 4, LaneGCN at width 8, four clients, segments of
    two rounds, short inter-contact gaps so that clients upload, and a
    narrow band so that an upload holds a share of the model, not all."""
    from repro.configs import get_config
    from repro.models.registry import build_model

    cell = cell_files(workload)
    config = cell["config"]
    if config["model"] == "resnet9":
        config["model_config"]["d_model"] = config["d_model"] = 4
    else:
        config["model_config"].update(d_model=8, d_ff=16)
        config.update(d_model=8, d_ff=16)
    config["params"] = build_model(get_config(config["arch"]).replace(
        **config["model_config"])).num_params()
    traffic = cell["traffic"]
    traffic.update(num_devices=4, samples_per_client=40, eval_samples=16,
                   schedule_rounds=8, segment_rounds=2, trace_segments=1,
                   fl={"mean_intercontact": 20.0, "mean_contact": 6.0,
                       "sample_size": 256, "bandwidth": 2e4})
    traffic.update(traffic_over)
    return cell


@pytest.fixture
def tiny():
    return tiny_cell
