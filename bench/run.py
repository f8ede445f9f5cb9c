#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last stdout line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its per-layer metrics are all
named in ``BENCHMARK.json`` at the checkout's root and found as files under
``bench/`` (see ``bench/harness/cli.py``).  The run needs a TPU: without
one it exits non-zero and prints no result.
"""
import os
import sys
import time

START = time.perf_counter()  # set-up is counted from here

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

if __name__ == "__main__":
    from bench.harness.cli import main

    sys.exit(main(sys.argv[1:], start=START))
