"""Without a TPU the benchmark exits non-zero and prints no result; so it
does in a directory that holds only ``BENCHMARK.json`` and the files under
its paths."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "resnet9.mads.n20", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_no_tpu_no_result():
    proc = run(ROOT)
    assert proc.returncode != 0
    assert no_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    proc = run(tmp_path)
    assert proc.returncode != 0
    assert no_result(proc.stdout)
