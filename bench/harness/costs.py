"""Operations and bytes from shapes, and the chip's peaks they are held to.

The model FLOP counts live beside each configuration's plain reference
(``bench/configs/<model>.py::flops_per_sample``) and use the helpers here.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; unknown is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def conv_flops(out_elems: int, window: int, cin: int) -> float:
    """Multiply-adds of a convolution, counted as 2 FLOPs each:
    ``out_elems`` outputs (positions x channels), each over a
    ``window`` x ``cin`` patch."""
    return 2.0 * out_elems * window * cin


def dense_flops(rows: int, fan_in: int, fan_out: int) -> float:
    return 2.0 * rows * fan_in * fan_out


def train_flops(forward: float) -> float:
    """Forward plus backward: the backward pass costs twice the forward
    (gradients of activations and of weights).  Recomputation, norms and
    elementwise work are not model FLOPs and are not counted."""
    return 3.0 * forward


def sparsify_quantize_ef_bytes(elements: int, itemsize: int = 4) -> int:
    """Bytes one ``sparsify_quantize_ef`` call must move: it reads the
    signal and writes the dequantised upload and the error memory, each
    ``elements`` values; the per-call scalars and lane counts are
    negligible and not counted."""
    return 3 * elements * itemsize
