"""Paper Table 4 analog: numerical-precision schemes for the inverse/merge.

Measures (a) the pure merge error || x inv(A) (A w) - x w ||^2 / numel under
fp32 vs fp64 over many random draws (paper: 1000 runs at 4096x4096; scaled
to 200 runs at 512x512), and (b) wall-time + final PPL of an AffineQuant
calibration run at each solve precision.

TPU note (DESIGN.md §3): v5e has no fp64 unit — the fp32 row is the
deployment path, and the GM-maintained strict diagonal dominance is exactly
what keeps its merge error ~1e-10 (vs the paper's 2.58e-3 on *unstructured*
random matrices).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import equivalence as eq
from repro.core.calibration import CalibConfig, quantize_dense_model
from repro.core.quantizer import QuantConfig

from benchmarks import common

H, NTOK, RUNS = 512, 256, 200


def merge_error_stats():
    errs32, errs64 = [], []
    # paper samples *random* transform matrices; we sample both random and
    # SDD-structured (what GM actually produces) to show why fp32 suffices
    for struct in ("random", "sdd"):
        e32, e64 = [], []
        for i in range(RUNS if not common.FAST else 20):
            key = jax.random.PRNGKey(i)
            if struct == "random":
                a = jnp.eye(H) + 0.5 * jax.random.normal(key, (H, H)) / np.sqrt(H)
            else:
                a = jnp.eye(H) + 0.3 * jax.random.normal(key, (H, H)) / H
            w = jax.random.normal(jax.random.fold_in(key, 1), (H, H))
            x = jax.random.normal(jax.random.fold_in(key, 2), (NTOK, H))
            e32.append(float(eq.merge_error(x, w, a, jnp.float32)))
            with jax.enable_x64(True):
                e64.append(float(eq.merge_error(
                    jnp.asarray(np.asarray(x), jnp.float64),
                    jnp.asarray(np.asarray(w), jnp.float64),
                    jnp.asarray(np.asarray(a), jnp.float64), jnp.float64)))
        errs32.append((struct, float(np.mean(e32))))
        errs64.append((struct, float(np.mean(e64))))
    return errs32, errs64


def run(arch: str = "llama-micro"):
    rows = []
    t0 = time.perf_counter()
    errs32, errs64 = merge_error_stats()
    us = (time.perf_counter() - t0) * 1e6
    for (s, e32), (_, e64) in zip(errs32, errs64):
        rows.append((f"table4/merge_error/{s}", us / 2,
                     f"fp32={e32:.3e};fp64={e64:.3e}"))

    # calibration at both precisions: runtime + ppl
    cfg, model, params = common.trained_model(arch, steps=600)
    calib, test = common.eval_sets(cfg)
    qcfg = QuantConfig(w_bits=2, a_bits=16, group_size=0, lwc=True)
    for name, dt in (("float", "float32"),):
        t0 = time.perf_counter()
        q, _ = quantize_dense_model(
            params, cfg, qcfg,
            CalibConfig(epochs=common.EPOCHS, alpha=0.1, solve_dtype=dt),
            calib, log=False)
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"table4/calib/{name}", us,
                     f"ppl={common.ppl(model, q, test):.4f}"))
    with jax.enable_x64(True):
        t0 = time.perf_counter()
        q, _ = quantize_dense_model(
            params, cfg, qcfg,
            CalibConfig(epochs=common.EPOCHS, alpha=0.1,
                        solve_dtype="float64"), calib, log=False)
        us = (time.perf_counter() - t0) * 1e6
        p = common.ppl(model, q, test)
    rows.append((f"table4/calib/double", us, f"ppl={p:.4f}"))
    return rows


if __name__ == "__main__":
    common.emit(run())
