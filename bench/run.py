#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` at the repository root) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  Set-up makes the packed weights from
the seed on the device, builds the paged engine, compiles its programs and
warms every shape the window uses; then the mix's generator drives
``Engine.submit`` / ``Engine.step`` for ``--seconds``.  After the window
the served tokens of a sample of finished requests are checked against
the plain reference (``bench/correct.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of a few seconds in the middle of the
window gives its per-layer metrics (``bench/metrics/<name>.py``).  The
last line of standard output is one JSON object; the numbers compared for
``correct`` are also the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

TRACE_SECONDS = 4.0       # length of the traced window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str) -> tuple[dict, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return bench, cell
    raise SystemExit(f"bench/run.py: no workload {name!r} in BENCHMARK.json")


def end_to_end(tr, t0: float, t1: float) -> dict:
    """Host-clock metrics of the window [t0, t1]: output tokens per
    second, and the 99th percentile (linear interpolation) of every gap
    between consecutive output tokens of a request, both in the window."""
    in_win = lambda t: t0 <= t <= t1
    out = sum(1 for r in tr.records for t in r.times[:r.target] if in_win(t))
    gaps = [b - a for r in tr.records
            for a, b in zip(r.times[:r.target], r.times[1:r.target])
            if in_win(a) and in_win(b)]
    p99 = float(np.percentile(gaps, 99)) if gaps else float("inf")
    return {"out_tok_s": {"value": out / (t1 - t0), "unit": "tokens/s"},
            "itl_p99_ms": {"value": 1e3 * p99, "unit": "ms"},
            "_counts": {"tokens": out, "gaps": len(gaps)}}


def load_limits(cell: str) -> dict:
    """The cell's limits and the readings they were set from."""
    with open(os.path.join(ROOT, "bench", "limits", f"{cell}.json")) as f:
        return json.load(f)


def read_metric(name: str, ctx: dict):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, on_chip: bool = True, hooks=None,
             control: bool = False) -> dict:
    """One run; returns the result object.  ``on_chip=False`` (the
    harness's own tests on the CPU) skips the chip-only steps: the kernel
    check, the trace and the memory reading.  ``hooks`` lets a test break
    the timed path after set-up.  ``control`` (``bench/calibrate.py``)
    also reads the float8 control and the planted fault: every reading
    goes under ``readings``, and the control's own verdict, by the same
    check and limits, under ``control_correct``."""
    import jax
    from bench import correct, load, model as bm, system
    from bench import trace as tr_mod
    from repro.utils import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = bm.load_config(cell["config"])
    mix = load.load_mix(cell["traffic"])
    dev = jax.devices()[0]
    vocab = cfg["vocab_size"]

    t = time.perf_counter()
    params = system.build_params(cfg, seed)
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    sysm = system.System(cfg, mix, params, load.max_context(mix))
    kernels = sysm.check_kernels() if on_chip else {}
    t_engine = time.perf_counter() - t
    t = time.perf_counter()
    system.warm_up(sysm, vocab)
    t_warm = time.perf_counter() - t
    if hooks is not None:
        hooks(sysm)
    compiles = tr_mod.CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        start = max(0.0, seconds / 2 - TRACE_SECONDS / 2)
        sysm.plan_trace(trace_dir, start, min(TRACE_SECONDS, seconds))
    t_ready = time.perf_counter()
    tr, t0, t1 = load.generator(mix).run(sysm, mix, seed, seconds, vocab)
    setup_s = t0 - T_START
    log(f"setup {setup_s:.3f} s: weights {t_weights:.3f} s, engine and "
        f"compile {t_engine:.3f} s, warm-up {t_warm:.3f} s, ramp "
        f"{t0 - t_ready:.3f} s; kernels "
        f"{ {k: sorted(v) for k, v in kernels.items()} }")
    sysm.finish_trace()
    n_compiles = compiles.stop(t0, t1)
    e2e = end_to_end(tr, t0, t1)
    log(f"window {t1 - t0:.3f} s: {e2e['_counts']}, compilations inside "
        f"it: {n_compiles}")
    peak = None
    if on_chip:
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    attempted = [r for r in tr.records if r.submitted <= t1]
    failed = sum(1 for r in attempted if r.failed)

    result = {"attempted": len(attempted), "failed": failed}
    if trace:
        red = tr_mod.reduce(trace_dir, sysm.hlo)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"cfg": cfg, "mix": mix, "system": sysm, "trace": red,
               "window": sysm.trace_window, "records": tr.records,
               "t0": t0, "t1": t1, "device_kind": dev.device_kind}
        names = [m["name"] for m in bench["per_layer"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]
        metrics = {}
        for name in names:
            val = read_metric(name, ctx)
            if val is not None:
                unit = next(m["unit"] for m in bench["per_layer"]
                            if m["name"] == name)
                metrics[name] = {"value": val, "unit": unit}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = tr_mod.breakdown(red)
    else:
        names = [m["name"] for m in bench["end_to_end"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]
        e2e["setup_s"] = {"value": setup_s, "unit": "s"}
        metrics = {n: e2e[n] for n in names}

    seqs = correct.served(tr.records)
    del tr, sysm, params
    correct.free_device()
    t = time.perf_counter()
    got = correct.readings(cfg, seed, seqs, correct.pad_len(mix),
                           control=control)
    limits = load_limits(cell["name"])
    ok, compared = correct.check(got["program"], limits)
    if control:
        result["readings"] = got
        result["control_correct"] = correct.check(got["control"],
                                                  limits)[0]
    prog = got["program"]
    log(f"reference: {len(seqs)} requests, {got['served_tokens']} served "
        f"tokens, pool codes of {got['kv_requests']} requests, "
        f"{time.perf_counter() - t:.3f} s; widest gap "
        f"{prog['widest_gap']!r} (not compared)")
    for k, v in compared.items():
        log(f"{k} {v['value']!r} limit {v['limit']!r}")
    result.update(correct=ok, metrics=metrics, device=device,
                  compared=compared)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell = load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench/run.py: cell {cell['name']} needs {cell['chips']} TPU "
            f"chip(s); JAX sees {len(devices)} {devices[0].platform} "
            "device(s). Nothing was run.")
        return 3
    result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace))
    order = ["correct", "attempted", "failed", "metrics", "device"]
    if "breakdown" in result:
        order.append("breakdown")
    order.append("compared")
    print(json.dumps({k: result[k] for k in order}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
