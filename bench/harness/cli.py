"""The benchmark's command line: find the cell, run it, print one JSON line.

Everything specific to a cell is data found by name:

* ``BENCHMARK.json`` (checkout root) names the cell's configuration and
  traffic mix, and lists the metrics;
* ``bench/configs/<config>.json`` holds the model's sizes and names its
  plain reference module beside it;
* ``bench/traffic/<traffic>.json`` holds the traffic's parameters; its
  ``kind`` picks the module that runs it, ``bench/harness/<kind>.py``;
* ``bench/metrics/<metric>.py`` reads one per-layer metric from a traced run;
* ``bench/limits/<workload>.json`` holds the limits of the comparison that
  decides ``correct``.

A new cell adds files and ``BENCHMARK.json`` entries and edits none.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, root: str = ROOT) -> dict:
    """The workload entry of ``<root>/BENCHMARK.json``, its configuration
    and traffic files, and its limits."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    limits = load_json(os.path.join(root, "bench", "limits", name + ".json"))
    return {"name": name, "chips": cell["chips"], "config": config,
            "traffic": traffic, "limits": limits, "spec": spec}


def cell_metrics(spec: dict, workload: str, end_to_end: list[str]) -> tuple:
    """(end-to-end metric entries, per-layer metric entries) of a cell."""
    def listed(m):
        return workload in m["workloads"] if "workloads" in m else True

    e2e = [m for m in spec["end_to_end"]
           if m["name"] in end_to_end and listed(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if m["moves"] in names and listed(m)]
    return e2e, layer


def load_reader(name: str):
    """``bench/metrics/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def find_chips(chips: int):
    """The first ``chips`` TPU devices, or SystemExit with no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform!r} "
                         "devices; this benchmark measures only a TPU")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def use_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path
    inside the checkout (``$JAX_COMPILATION_CACHE_DIR`` wins), holding
    every program so that only a cell's first run compiles."""
    import jax

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             start: float, devices) -> dict:
    """Drive one run of the cell; returns the result line as a dict."""
    runner = importlib.import_module(
        "bench.harness." + cell["traffic"]["kind"])
    res = runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                     start=start, devices=devices)
    e2e, layer = cell_metrics(cell["spec"], cell["name"], res["reports"])
    units = {m["name"]: m["unit"] for m in e2e + layer}
    if trace:
        metrics = {}
        for m in layer:
            value = load_reader(m["name"]).read(res["trace"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]} for m in e2e}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": res["device"]}
    if trace:
        line["device"]["busy_s"] = res["trace"]["busy_s"]
        line["device"]["window_s"] = res["trace"]["window_s"]
        line["breakdown"] = res["trace"]["breakdown"]
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in res["checks"]}
    return line


def main(argv: list[str], *, start: float | None = None) -> int:
    start = time.perf_counter() if start is None else start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = find_cell(args.workload)
    use_cache()
    devices = find_chips(cell["chips"])
    line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), start=start, devices=devices)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
