"""``correct`` comes out true for the sound program and false for each fault
a federation cell can have, with the harness's look for a chip skipped
(CPU, tiny width; the cell's own limits).  The control, the plain
reference at bfloat16 in the program's place, fails the same limits."""
from __future__ import annotations

import time

import pytest

from conftest import tiny_cell

SEED = 2**35 + 11


def drive(cell, fault=None):
    import jax

    from bench.harness import federation

    return federation.run(cell, seed=SEED, seconds=0.5, trace=False,
                          start=time.perf_counter(),
                          devices=jax.devices()[:1], fault=fault)


@pytest.fixture(scope="module")
def cell():
    return tiny_cell("resnet9.mads.n20")


def test_sound_program_is_correct(cell):
    """The whole run's result line, as ``bench/run.py`` prints it."""
    import jax

    from bench.harness.cli import run_cell

    line = run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                    start=time.perf_counter(), devices=jax.devices()[:1])
    assert line["correct"], line["checks"]
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"rounds_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line["checks"]) == list(cell["limits"]["limits"])
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


@pytest.mark.parametrize("name", ["half_batch", "unchanged_state",
                                  "double_energy"])
def test_fault_is_not_correct(cell, name):
    from bench.harness.faults import FAULTS

    res = drive(cell, FAULTS[name])
    assert not res["correct"], res["checks"]


def test_control_fails_the_limits(cell):
    import jax.numpy as jnp

    from bench.harness import compare, federation
    from bench.harness.spans import Spans

    config, traffic = cell["config"], cell["traffic"]
    b, _, _ = federation.checked(config, traffic, SEED, Spans())
    federation.free_program(b)
    want = federation.reference_readings(b, config, traffic)
    low = federation.reference_readings(b, config, traffic,
                                        dtype=jnp.bfloat16)
    checks = compare.federation_checks(low, want, cell["limits"])
    assert any(value > limit for _, value, limit in checks), checks
