"""The comparison that decides ``correct``: the program's readings against
the plain reference's, each number beside its limit.

Norms are compared leaf by leaf, as the gap between the program's norm of
a leaf and the reference's, over the larger of the reference's norm of
that leaf and of the median leaf; the worst leaf is the number.  Leaves
whose gradient in the reference is under a thousandth of the median
leaf's (nought to rounding) are left out by that rule, not by name.
Each such quantity is also compared over the whole model (``<name>_all``):
the gap between the two norms of every leaf together, over the
reference's.  That number is steady where one small leaf is not.
"""
from __future__ import annotations

import math
import statistics

# Leaves whose reference gradient is under this share of the median leaf's
# move by round-off alone and are left out of the leaf-wise numbers.
NOUGHT = 1e-3
# the per-leaf norms the federation readings hold
LEAFWISE = ("grad1", "dw", "dwn", "err", "gsum")


def kept_leaves(grad_ref: dict) -> list[str]:
    med = statistics.median(grad_ref.values())
    return [k for k, v in grad_ref.items() if v >= NOUGHT * med]


def worst_leaf(got: dict, want: dict, leaves: list[str]) -> float:
    """Where most leaves are nought in the reference (an error memory
    after uploads of nearly everything), the largest leaf is the floor."""
    floor = (statistics.median(want[k] for k in leaves)
             or max(want[k] for k in leaves))
    if floor == 0:
        return 0.0 if all(got[k] == 0 for k in leaves) else float("inf")
    gaps = [abs(got[k] - want[k]) / max(want[k], floor) for k in leaves]
    return math.inf if any(math.isnan(g) for g in gaps) else max(gaps)


def worst_leaf_names(got: dict, want: dict) -> dict:
    """Which leaf gives each leaf-wise number (for the record of a look)."""
    leaves = kept_leaves(want["grad1"])
    out = {}
    for name in LEAFWISE:
        floor = (statistics.median(want[name][k] for k in leaves)
                 or max(want[name][k] for k in leaves) or 1.0)
        out[name] = max(leaves, key=lambda k: abs(
            got[name][k] - want[name][k]) / max(want[name][k], floor))
    return out


def whole_model(got: dict, want: dict) -> float:
    """Gap of the norms over every leaf together, over the reference's."""
    g = math.sqrt(sum(v * v for v in got.values()))
    w = math.sqrt(sum(v * v for v in want.values()))
    return abs(g - w) / w if w > 0 else (0.0 if g == 0 else math.inf)


def worst_count(got: list, want: list) -> float:
    return max(abs(g - w) / max(w, 1.0) for g, w in zip(got, want))


def finite_or_inf(x: float) -> float:
    """A number that is not finite (a NaN from a broken run) fails every
    limit."""
    return x if math.isfinite(x) else math.inf


def federation_numbers(got: dict, want: dict) -> dict:
    """Every number the federation comparison can hold to a limit."""
    if set(got["grad1"]) != set(want["grad1"]):
        raise ValueError("program and reference leaves differ")
    leaves = kept_leaves(want["grad1"])
    out = {}
    for name in LEAFWISE:
        out[name] = worst_leaf(got[name], want[name], leaves)
        out[name + "_all"] = whole_model(got[name], want[name])
    out["count"] = worst_count(got["count"], want["count"])
    out["energy"] = abs(got["energy"] - want["energy"]) / max(
        want["energy"], 1e-30)
    out["kappa"] = float(sum(g != w for g, w in zip(got["kappa"],
                                                    want["kappa"])))
    return {k: finite_or_inf(v) for k, v in out.items()}


def federation_checks(got: dict, want: dict, limits: dict) -> list:
    """``(name, number, limit)`` for each number the cell's limits hold."""
    nums = federation_numbers(got, want)
    return [(name, nums[name], limit)
            for name, limit in limits["limits"].items()]
