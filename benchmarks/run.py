"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see each bench module's docstring
for the figure mapping).  Select subsets with
``python -m benchmarks.run --only mobility,mads``.

Observability (repro/telemetry):

* ``--out-dir DIR`` — export each suite's rows as ``DIR/BENCH_<suite>.json``
  trajectory files (previous exports of the same suite are carried in a
  bounded ``history`` list); feed two of them to ``tools/bench_compare.py``
  to gate regressions.
* ``--profile-dir DIR`` — wrap each suite in a ``jax.profiler`` trace and
  per-suite wall-clock spans (printed as a phase table at the end).
* ``--smoke`` — reduced iteration counts for suites that support it (CI).

A suite that raises is logged with its traceback and printed as a
``<suite>_FAILED`` row; the other suites still run, and the command then
exits 1.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time

MODULES = [
    ("theory", "benchmarks.bench_theory"),
    ("kernels", "benchmarks.bench_kernels"),
    ("compression", "benchmarks.bench_compression"),
    ("mobility", "benchmarks.bench_mobility"),
    ("serve", "benchmarks.bench_serve"),
    ("afl", "benchmarks.bench_afl"),
    ("mads", "benchmarks.bench_mads"),
    ("trajectory", "benchmarks.bench_trajectory"),
    ("ablation", "benchmarks.bench_ablation"),
    ("roofline", "benchmarks.bench_roofline"),
]


def _call_run(mod, smoke: bool):
    """Invoke ``mod.run()``, forwarding ``smoke=`` when the suite accepts it."""
    if smoke and "smoke" in inspect.signature(mod.run).parameters:
        return mod.run(smoke=True)
    return mod.run()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated subset of: "
                    + ",".join(n for n, _ in MODULES))
    ap.add_argument("--out-dir", default="",
                    help="export BENCH_<suite>.json per suite here")
    ap.add_argument("--profile-dir", default="",
                    help="jax.profiler trace output dir (also enables "
                         "TraceAnnotation spans)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced iteration counts (suites that support it)")
    args = ap.parse_args()
    only = {s for s in args.only.split(",") if s}

    import importlib

    sys.path.insert(0, "src")  # python -m benchmarks.run without PYTHONPATH
    from repro.launch.cache import use_compile_cache
    from repro.telemetry import PhaseTracer, export_bench
    from repro.utils import get_logger

    use_compile_cache()
    log = get_logger("repro.bench")
    tracer = PhaseTracer(profile_dir=args.profile_dir or None)
    if args.profile_dir:
        tracer.start()

    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name, modname in MODULES:
        if only and name not in only:
            continue
        mod = importlib.import_module(modname)
        rows = []
        try:
            with tracer.span(name):
                rows = list(_call_run(mod, args.smoke))
            for row in rows:
                print(row)
                sys.stdout.flush()
        except Exception as e:  # noqa: BLE001 - report, run the rest, exit 1
            log.exception("suite %s failed", name)
            print(f"{name}_FAILED,0,{type(e).__name__}:{e}")
            failed.append(name)
        if args.out_dir and rows:
            path = export_bench(name, rows, out_dir=args.out_dir,
                                meta={"smoke": bool(args.smoke)})
            log.info("wrote %s", path)

    if args.profile_dir:
        tracer.stop()
    if tracer.spans:
        log.info("suite wall clock:\n%s", tracer.summary())
    log.info("total_wall_s=%.1f", time.time() - t0)
    if failed:
        log.error("failed suites: %s", ",".join(failed))
        sys.exit(1)


if __name__ == "__main__":
    main()
