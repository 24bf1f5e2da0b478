"""Scheduler: active decode rows over max_batch, averaged over the decode
steps of the measured window (host counter), in %."""
import numpy as np


def read(ctx):
    sysm = ctx["system"]
    b = sysm.engine.cfg.max_batch
    rows = [c[1] for c in sysm.decode_calls if ctx["t0"] <= c[0] <= ctx["t1"]]
    return 100.0 * float(np.mean(rows)) / b if rows else None
