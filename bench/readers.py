"""Arithmetic shared by the per-layer metric readers in
``bench/metrics/``.  Each reader gets the run's context: the system's host
counters, the reduced trace (``bench.trace.reduce``), the records of the
requests and the windows.  A reader returns None where it finds nothing
to read."""
from __future__ import annotations

import importlib

import numpy as np

from bench import model as bm, roofline, trace


def in_window(ctx: dict, t: float) -> bool:
    lo, hi = ctx["window"] or (None, None)
    return lo is not None and lo <= t <= hi


def traced_calls(ctx: dict, kind: str) -> list:
    """Host records of the decode or chunk calls made while tracing."""
    sysm = ctx["system"]
    calls = sysm.decode_calls if kind == "decode" else sysm.chunk_calls
    return [c for c in calls if in_window(ctx, c[0])]


def step_ms(ctx: dict, prog: str):
    """Mean device time of one run of the decode or chunk program."""
    t = trace.module_times(ctx["trace"], prog)
    return 1e3 * float(np.mean(t)) if t else None


def idle_share(ctx: dict):
    red = ctx["trace"]
    if not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def kernel_share(ctx: dict, kernel: str, prog: str):
    """Roofline share (%) of one kernel's calls in one program: the sum of
    their bounds (``bench/costs/<kernel>.py``) over the sum of their
    device times."""
    red = ctx["trace"]
    ev = [(name, e - s) for p, k, name, s, e in red["kernels"]
          if p == prog and k == kernel]
    if not ev:
        return None
    costs = importlib.import_module(f"bench.costs.{kernel}")
    bound = costs.bound_s(ctx, prog, ev, roofline.peaks(ctx["device_kind"]))
    return 100.0 * bound / (sum(d for _, d in ev) / 1e9)


def mfu(ctx: dict):
    """Model operations of every token served while tracing over the
    traced window and the int8 peak (%)."""
    red = ctx["trace"]
    if not red["window_s"]:
        return None
    m = bm.dims(ctx["cfg"])
    ops = 0.0
    for c in traced_calls(ctx, "decode"):
        ops += sum(roofline.token_ops(m, n, True) for n in c[3])
    for _, n, done, last in traced_calls(ctx, "chunk"):
        ops += sum(roofline.token_ops(m, done + i + 1, False)
                   for i in range(n))
        ops += 2.0 * m["d"] * m["V"] if last else 0.0
    pk = roofline.peaks(ctx["device_kind"])
    return 100.0 * ops / red["window_s"] / pk["int8_ops"]

