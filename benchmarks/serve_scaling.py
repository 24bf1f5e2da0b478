"""Sharded-serving scaling bench: per-device-count rows for BENCH_serve.json.

Runs the serve_bench mixed-length trace through the mesh-native Engine on
1/2/4/8 virtual devices (meshes ``(data, model)`` = (1,1), (1,2), (2,2),
(2,4)) and records, per mesh:

- tokens/s over a warmed measured pass (pass 1 compiles every bucket and
  the decode step; pass 2 is steady-state — compile time differs per mesh
  so an unwarmed pass would drown the scaling signal in XLA frontend time),
- per-device weight + KV HBM bytes (``Engine.memory_report()`` — the
  tentpole's memory win: both shrink along the model axis because QTensor
  codes/scales are column-parallel and the KV pool splits its head dim),
- collective wire bytes per decode step, read from the compiled decode
  HLO via ``repro.hlo_analysis`` (one all-reduce per layer from the
  row-parallel projections — the cost side of the TP ledger),
- token identity against the (1,1) mesh (GSPMD must not change a single
  sampled token).

On a TPU host the sweep runs in this process over ``jax.devices()``
(the meshes that fit the chips present): a chip belongs to one process,
so a child could not reach it.  On CPU the sweep needs
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set BEFORE jax
initializes, which the benchmark runner's process has long passed — so
there it runs in a CPU-only subprocess (same pattern as
``tests/test_sharded_serving.py``) and hands back one JSON document.
``serve_bench.run()`` merges it as the ``scaling`` section of
``BENCH_serve.json``; on a CPU container tokens/s across virtual devices
measures *overhead*, not speedup — the per-device byte columns and the
collective ledger are the real trajectory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

MESHES = ((1, 1), (1, 2), (2, 2), (2, 4))

PAGE_SIZE = 16
MAX_BATCH = 4
MAX_LEN = 192
TRACE = [8, 40, 16, 96, 24, 64, 8, 120, 32, 12, 80, 18]


def sweep(meshes) -> dict:
    """Serve the trace on each ``(data, model)`` mesh of ``meshes`` in this
    process; returns the ``scaling`` doc."""
    import time

    import jax
    import numpy as np

    from repro import hlo_analysis
    from repro.configs import get_config
    from repro.core.quantizer import QuantConfig
    from repro.launch.mesh import make_serving_mesh
    from repro.models import build_model
    from repro.serve.engine import Engine, ServeConfig
    from repro.serve.kv_cache import pages_for
    from repro.serve.quantized import QuantizedModel, quantize_lm_packed

    fast = bool(int(os.environ.get("BENCH_FAST", "0")))
    max_new = 8 if fast else 16
    n_req = 6 if fast else len(TRACE)
    cfg = get_config("llama-micro")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    # the w4a4kv4 deployment point — the stack the tentpole shards
    qcfg = QuantConfig(w_bits=4, a_bits=4, group_size=32, lwc=False,
                       kv_bits=4)
    packed = quantize_lm_packed(params, cfg, qcfg)
    qm = QuantizedModel(cfg, qcfg, kernel_mode="ref",
                        flash_block_kv=PAGE_SIZE)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in TRACE[:n_req]]
    lens = [len(p) + max_new for p in prompts]
    num_pages = MAX_BATCH * pages_for(int(np.percentile(lens, 95)),
                                      PAGE_SIZE)

    def trace_pass(eng):
        reqs = [eng.submit(p) for p in prompts]
        t0 = time.monotonic()
        eng.run(max_steps=4000)
        dt = time.monotonic() - t0
        toks = sum(len(r.out_tokens) for r in reqs)
        return [tuple(int(t) for t in r.out_tokens) for r in reqs], toks, dt

    def bench(dm):
        scfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                           max_new=max_new, prefill_bucket=32, paged=True,
                           page_size=PAGE_SIZE, num_pages=num_pages)
        eng = Engine(qm, packed, scfg, mesh=make_serving_mesh(*dm))
        trace_pass(eng)                       # warmup (compiles)
        outs, toks, dt = trace_pass(eng)
        eng._kv.verify()
        rep = eng.memory_report()
        with eng._bound():
            hlo = eng._decode.lower(
                eng.params, eng._last_tok, eng._kv.cache, eng._idle_keys,
                eng._zero_poison).compile().as_text()
        coll = hlo_analysis.analyze_hlo(hlo)["collectives"]
        return outs, {
            "mesh": list(dm), "device_count": rep["device_count"],
            "tokens_per_s": toks / dt, "wall_s": dt, "new_tokens": toks,
            "weight_bytes_per_device": rep["weight_bytes_per_device"],
            "kv_bytes_per_device": rep["kv_bytes_per_device"],
            "decode_collective_bytes_per_step": coll["total_bytes"],
            "decode_collective_ops": {k: v for k, v in
                                      coll["count_by_kind"].items() if v},
        }

    base_outs = None
    rows = []
    for dm in meshes:
        outs, row = bench(tuple(dm))
        if base_outs is None:
            base_outs = outs
        row["token_identical"] = outs == base_outs
        rows.append(row)
    dev = jax.devices()[0]
    return {"quant": "w4a4g32kv4", "platform": dev.platform,
            "device_kind": dev.device_kind,
            "trace_prompt_lens": [int(len(p)) for p in prompts],
            "max_new": max_new, "rows": rows}


def run_scaling() -> dict:
    """The mesh sweep; returns the ``scaling`` doc.  On TPU it runs in
    this process over the chips present; elsewhere in a CPU child with 8
    virtual devices."""
    import jax
    if jax.default_backend() == "tpu":
        n = len(jax.devices())
        return sweep([m for m in MESHES if m[0] * m[1] <= n])
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    child = ("import json; from benchmarks import serve_scaling as s; "
             "print('SCALING-JSON:' + json.dumps(s.sweep(s.MESHES)))")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"scaling sweep child failed\n--- stdout ---\n{proc.stdout}\n"
            f"--- stderr ---\n{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith("SCALING-JSON:"):
            return json.loads(line[len("SCALING-JSON:"):])
    raise RuntimeError(f"no SCALING-JSON line in child output:\n"
                       f"{proc.stdout}\n{proc.stderr}")


def scaling_rows(doc: dict) -> list:
    """CSV rows (harness contract) from the scaling doc."""
    rows = []
    for r in doc["rows"]:
        us_per_tok = 1e6 * r["wall_s"] / max(r["new_tokens"], 1)
        d, m = r["mesh"]
        rows.append((
            f"serve/scaling_d{d}m{m}_w4a4kv4", us_per_tok,
            f"devices={r['device_count']};tok_s={r['tokens_per_s']:.1f};"
            f"w_KiB_per_dev={r['weight_bytes_per_device'] / 2**10:.1f};"
            f"kv_KiB_per_dev={r['kv_bytes_per_device'] / 2**10:.1f};"
            f"coll_B_step={r['decode_collective_bytes_per_step']:.0f};"
            f"token_identical={r['token_identical']}"))
    return rows
