"""The limits that decide ``correct`` in ``resnet9.mads.n20`` hold against
the chip calibration they were set from (``data/<cell>.calibration.jsonl``:
one line per reading, its kind, seed and numbers), so that an edit to the
limits cannot drift from the evidence."""
from __future__ import annotations

import json
import os

import pytest

from conftest import ROOT

WORKLOAD = "resnet9.mads.n20"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    WORKLOAD + ".calibration.jsonl")
FAULTY = ("control", "half_batch", "unchanged_state", "double_energy")


@pytest.fixture(scope="module")
def readings():
    with open(DATA) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.fixture(scope="module")
def limits():
    with open(os.path.join(ROOT, "bench", "limits", WORKLOAD + ".json")) as f:
        return json.load(f)


def seeds_of(readings, kind):
    return {r["seed"] for r in readings if r["kind"] == kind}


def test_calibration_covers_the_seeds(readings):
    sound = seeds_of(readings, "program")
    assert len(sound) >= 24
    assert {12, 21, 22, 1099511627} <= sound
    for kind in FAULTY:
        assert len(seeds_of(readings, kind)) >= 8, kind
        assert seeds_of(readings, kind) <= sound, kind


def test_every_limit_is_twice_the_sound_maximum(readings, limits):
    """Rule (a), the margin for seeds no one has run; a limit of 0 is an
    exact comparison, which no sound reading may miss."""
    from bench.harness.calibrate import MARGIN

    sound = [r["numbers"] for r in readings if r["kind"] == "program"]
    for name, limit in limits["limits"].items():
        top = max(n[name] for n in sound)
        assert (top == 0) if limit == 0 else (limit >= MARGIN * top), (
            name, limit, top)


@pytest.mark.parametrize("kind", FAULTY)
def test_control_and_faults_fail_a_limit(readings, limits, kind):
    """Rule (b): on every seed, the reading passes one limit by PASS x."""
    from bench.harness.calibrate import passes

    runs = [r for r in readings if r["kind"] == kind]
    assert runs
    for r in runs:
        assert any(passes(r["numbers"][k], v)
                   for k, v in limits["limits"].items()), (r["seed"],
                                                           r["numbers"])


def test_limits_follow_the_rule(readings, limits):
    from bench.harness.calibrate import limits_from

    assert limits_from(readings) == limits["limits"]


def test_every_limit_has_its_why(limits):
    assert set(limits["why"]) == set(limits["limits"])
    for text in limits["why"].values():
        assert text.strip() and "\n" not in text
