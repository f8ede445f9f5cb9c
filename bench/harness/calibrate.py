#!/usr/bin/env python3
"""Read the numbers that a federation cell's limits are set from.

    python3 bench/harness/calibrate.py --workload resnet9.mads.n20 \\
        --seeds 1,2,3 [--control 1,2,3] [--fault half_batch:1,2,3]
    python3 bench/harness/calibrate.py --workload resnet9.ingest.steady \\
        --rates 200,400,800 --seconds 5

For each seed, in one process on the chip: the program against the
float32 reference (the lower readings); the control, the reference at
bfloat16 in the program's place (upper readings); each named fault
planted under the timed path; and, as a witness, the program at the
highest matmul precision.  A federation cell runs its checked
segments; an ingest cell runs a window of ``--seconds`` at its rate.
``--rates`` sweeps an ingest cell's arrival rate to find the highest it
sustains.  One JSON line per reading on stdout, with the readings of both
sides, so that any number can be worked out again from it; it is not part
of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def seeds(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", action="append", default=[],
                    help="name:seed,seed,...")
    ap.add_argument("--highest", default="",
                    help="seeds to run the program at the highest matmul "
                         "precision too (a witness, not a limit)")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench.harness import compare, faults, federation
    from bench.harness.cli import find_cell, find_chips, use_cache
    from bench.harness.spans import Spans

    cell = find_cell(args.workload)
    use_cache()
    find_chips(cell["chips"])
    config, traffic = cell["config"], cell["traffic"]
    if traffic["kind"] == "ingest":
        return calibrate_ingest(args, cell)

    def emit(kind, seed, got, want, t0):
        nums = compare.federation_numbers(got, want)
        print(json.dumps({"workload": args.workload, "kind": kind,
                          "seed": seed, "numbers": nums,
                          "worst": compare.worst_leaf_names(got, want),
                          "got": got, "want": want,
                          "seconds": time.perf_counter() - t0}), flush=True)

    wants = {}  # the float32 reference's readings, by seed

    def reference(b, seed):
        if seed not in wants:
            wants[seed] = federation.reference_readings(b, config, traffic)
        return wants[seed]

    control = set(seeds(args.control))
    for seed in seeds(args.seeds) + sorted(control - set(seeds(args.seeds))):
        t0 = time.perf_counter()
        b, got, _ = federation.checked(config, traffic, seed, Spans())
        federation.free_program(b)
        want = reference(b, seed)
        if seed in seeds(args.seeds):
            emit("program", seed, got, want, t0)
        if seed in control:
            t0 = time.perf_counter()
            low = federation.reference_readings(b, config, traffic,
                                                dtype=jnp.bfloat16)
            emit("control", seed, low, want, t0)
    for seed in seeds(args.highest):
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            b, got, _ = federation.checked(config, traffic, seed, Spans())
        federation.free_program(b)
        emit("program_highest", seed, got, reference(b, seed), t0)
    for spec in args.fault:
        name, _, text = spec.partition(":")
        for seed in seeds(text):
            t0 = time.perf_counter()
            b, got, _ = federation.checked(config, traffic, seed, Spans(),
                                           fault=faults.FAULTS[name])
            federation.free_program(b)
            emit(name, seed, got, reference(b, seed), t0)
    return 0


def calibrate_ingest(args, cell) -> int:
    import jax.numpy as jnp
    import numpy as np

    from bench.harness import faults, ingest
    from bench.harness.spans import Spans

    config, traffic = cell["config"], cell["traffic"]

    def window(seed, rate=None, fault=None):
        t = dict(traffic, rate_per_s=rate or traffic["rate_per_s"])
        b = ingest.build(config, t, seed)
        if fault is not None:
            fault(b)
        rec = ingest.serve(b, t, seed, args.seconds, Spans())
        return b, t, rec

    def numbers(b, t, rec, dtype=None):
        server = b.pop("server")
        w, ingested = server.w, server.snapshot()["counters"]["ingested"]
        applied = int(np.sum(np.isfinite(rec["done"])))
        want = ingest.reference_w(b, rec, t)
        if dtype is not None:  # the control in the program's place
            w = ingest.reference_w(b, rec, t, dtype=dtype)
        return ingest.ingest_numbers(ingest.readings(b, w, want), applied,
                                     ingested)

    def emit(kind, seed, nums, **extra):
        print(json.dumps({"workload": args.workload, "kind": kind,
                          "seed": seed, "numbers": nums, **extra}),
              flush=True)

    for rate in seeds(args.rates):
        b, t, rec = window(1000 + rate, rate)
        lat = (rec["done"] - rec["due"]) * 1e3
        quarters = np.array_split(lat, 4)
        emit("sweep", 1000 + rate, {}, rate=rate, offered=int(lat.size),
             applied=int(np.sum(np.isfinite(lat))),
             refused=int(np.sum(rec["refused"])),
             p50_ms_by_quarter=[float(np.median(q)) for q in quarters],
             p95_ms=float(np.percentile(lat, 95)),
             late_p95_ms=float(np.percentile(rec["late"], 95) * 1e3),
             last_done_s=float(np.max(rec["done"][np.isfinite(rec["done"])])))
    for seed in seeds(args.seeds):
        emit("program", seed, numbers(*window(seed)))
    for seed in seeds(args.control):
        emit("control", seed, numbers(*window(seed), dtype=jnp.bfloat16))
    for spec in args.fault:
        name, _, text = spec.partition(":")
        for seed in seeds(text):
            emit(name, seed, numbers(*window(
                seed, fault=faults.INGEST_FAULTS[name])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
