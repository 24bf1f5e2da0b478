"""Roofline arithmetic shared by the kernels' cost modules
(``bench/costs/<kernel>.py``), operations of the model, and the chip's
peaks (``bench/peaks.json``, keyed by ``device_kind``).

A kernel's roofline bound is the larger of its operations over the peak
rate and its bytes over HBM bandwidth: the least time the chip could take.
Bytes count each operand and result once (what the algorithm must move);
a kernel that re-reads an operand shows it as a lower share.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "f64": 8}


def peaks(device_kind: str) -> dict:
    """The peak table of one chip kind; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def nbytes(operand) -> int:
    dtype, shape = operand
    return ITEMSIZE[dtype] * int(np.prod(shape, dtype=np.int64))


def bound_s(ops: float, nbytes_: float, peak_ops: float,
            peak_bw: float) -> tuple[float, str]:
    """(least seconds, bounding side)."""
    t_c, t_m = ops / peak_ops, nbytes_ / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def token_ops(dims: dict, context: int, head: bool) -> float:
    """Model operations of one token at ``context`` attended positions:
    2 per MAC of the packed linears, 4 * d per position per layer of
    attention, and the tied LM head where the token yields logits."""
    d, ff, L, V = dims["d"], dims["ff"], dims["L"], dims["V"]
    lin = 2.0 * L * (4 * d * d + 2 * d * ff)
    att = 4.0 * L * d * context
    return lin + att + (2.0 * d * V if head else 0.0)
