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

    python3 bench/harness/calibrate.py --workload resnet9.mads.n20 \\
        --set-limits readings.jsonl

prints the limits that ``limits_from`` sets from such lines (the rule is in
its docstring), without a chip.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


# Rule (a): every limit is at least MARGIN times the largest sound reading.
# Rule (b): the control and each fault pass some limit by PASS times.
MARGIN, PASS = 2.0, 1.5
# How far a kind's least reading of a number lies above the sound maximum
# for it to set that number's upper reading: the control and a state left
# unchanged three times, any other fault ten times.
FAR = {"control": 3.0, "unchanged_state": 3.0}
FAULT_FAR = 10.0
# The numbers the rule may hold: the worst-leaf gaps, the count, the
# energy and the last contacts.  The whole-model gaps (``<name>_all``) are
# read but not held: on sound seeds their largest reading is ten times
# their median, against three to five times for the worst leaf, and every
# kind that passes one of them passes a worst-leaf number too.
HELD = ("count", "energy", "kappa")
# The sound program sets the lower readings.  Neither the witness at the
# highest precision nor the benchmark's own runs on seeds held out of the
# calibration (``held_out``, their checked numbers) set anything.
SOUND = "program"
ASIDE = ("program_highest", "held_out")


def seeds(text: str) -> list[int]:
    return [int(x) for x in text.split(",") if x]


def passes(value: float, limit: float) -> bool:
    """A reading fails a limit by rule (b)'s factor (any reading over a
    limit of 0 does)."""
    return value > limit and value >= PASS * limit


def two_digits(x: float) -> float:
    """``x`` cut down to two significant digits."""
    e = math.floor(math.log10(x)) - 1
    return float(f"{math.floor(x / 10 ** e + 1e-9)}e{e}")


def limits_from(readings: list[dict]) -> dict:
    """The limits the rule sets from calibration lines (``kind``, ``seed``,
    ``numbers``).

    For each number, the lower reading L is the largest sound one, and
    the upper U the least reading of the kinds (the control and the
    faults) whose least reading lies ``FAR`` (``FAULT_FAR``) times above
    L.  A number with no U gets no limit; one that reads 0 on every sound
    seed is exact and gets 0; else the limit lies between MARGIN x L and
    U / PASS, six tenths of the way up in logarithm.  A kind that sets no
    U is held by the worst-leaf numbers (``compare.LEAFWISE``, where a
    changed gradient shows most): each of their limits drops to c x L,
    with c the largest multiple, one for them all and at least MARGIN, by
    which that kind passes one of them by PASS x on every seed.  Limits
    are cut down to two digits.  Raises where (a) or (b) fails."""
    from bench.harness.compare import LEAFWISE

    sound = [r["numbers"] for r in readings if r["kind"] == SOUND]
    faulty = [r for r in readings if r["kind"] not in (SOUND, *ASIDE)]
    kinds = {r["kind"] for r in faulty}
    lower = {name: max(n[name] for n in sound) for name in LEAFWISE + HELD}

    def least(kind, name):
        return min(r["numbers"][name] for r in faulty if r["kind"] == kind)

    limits, held = {}, set()
    for name, low in lower.items():
        uppers = {k: least(k, name) for k in kinds}
        uppers = {k: v for k, v in uppers.items()
                  if v > 0 and v >= FAR.get(k, FAULT_FAR) * low}
        if not uppers:
            continue
        held |= set(uppers)
        lo, hi = MARGIN * low, min(uppers.values()) / PASS
        limits[name] = 0 if low == 0 else two_digits(lo ** 0.4 * hi ** 0.6)
    for kind in sorted(kinds - held):
        c = min(max(r["numbers"][name] / lower[name] for name in LEAFWISE)
                for r in faulty if r["kind"] == kind) / PASS
        c = math.floor(c * 10) / 10
        if c < MARGIN:
            raise ValueError(f"{kind} lies within {MARGIN * PASS}x the sound "
                             f"maximum of every worst-leaf number")
        for name in LEAFWISE:
            limits[name] = min(limits.get(name, math.inf),
                               two_digits(c * lower[name]))
    for name, limit in limits.items():
        if limit < MARGIN * lower[name]:
            raise ValueError(f"{name}: limit {limit} under rule (a)")
    for r in faulty:
        if not any(passes(r["numbers"][k], v) for k, v in limits.items()):
            raise ValueError(f"{r['kind']} on seed {r['seed']} passes no "
                             f"limit by {PASS}x: {r['numbers']}")
    return limits


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
    ap.add_argument("--set-limits", default="",
                    help="a file of calibration lines to set limits from")
    args = ap.parse_args(argv)
    if args.set_limits:
        with open(args.set_limits) as f:
            readings = [json.loads(line) for line in f if line.strip()]
        print(json.dumps({"limits": limits_from(readings)}, indent=2))
        return 0

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
