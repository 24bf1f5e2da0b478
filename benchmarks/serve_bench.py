"""Engine-level serving benchmark: linear vs paged KV cache under a fixed
mixed-length request trace, plus inter-token latency under long-prompt
arrival (whole-prompt vs chunked admission).

Measures what the kernel benchmarks cannot: scheduler throughput AND tail
latency. The same trace (prompt lengths spanning 3..~120 tokens, FIFO
submission) runs through the linear slot-table engine and the paged engine,
on the packed w4a8 + kv8 serving stack (ref kernels — CPU container; the
*relative* numbers are layout/scheduling effects, not kernel effects,
because every mode runs the identical tile math).

The **inter-token-latency trace** (DESIGN.md §10) starts short requests
decoding, then drops a long prompt on the queue mid-flight: with
whole-prompt admission the long prefill monopolizes one step and every
in-flight decode stalls behind it (the p99 spike); with chunked admission
(``prefill_chunk`` tokens per step) the stall is bounded by one chunk.
Both engines run the trace twice — the first pass warms every compile so
the measured pass is steady-state kernel time.  p50/p99 are computed over
the short requests' consecutive-token gaps.

Besides the CSV rows this writes ``benchmarks/artifacts/BENCH_serve.json``:
tokens/s, requests/s and cache bytes per layout, p50/p99 inter-token
latency per admission mode, the traces themselves, and the paged pool
accounting (pool pages, peak in use, preemptions) — the machine-readable
serving-perf trajectory CI uploads per commit.

The paged pool is sized to the trace's working set (max_batch concurrent
sequences at the P95 trace length), NOT to ``max_batch * max_len`` — that
sizing is the memory win: the linear cache must reserve worst-case
``max_len`` per slot while pages track live tokens.

The **degraded-mode row** (DESIGN.md §12) replays the trace with the pool
halved and a seeded 50%-probability allocator brown-out injected for 40
allocations: the failure model's promise is graceful degradation, so the
row reports tokens/s and completion rate against the clean paged run,
asserts the run terminates (storm guard + watchdog bound every livelock),
and audits page conservation afterwards.  ``benchmarks.run --faults``
runs ONLY this row plus its clean baseline (the CI smoke), merging the
``degraded`` section into an existing ``BENCH_serve.json``.

The **scaling rows** (DESIGN.md §13) replay the trace through the
mesh-native sharded engine on 1/2/4/8 virtual CPU devices — per-device
weight + KV bytes, collective bytes per decode step, token identity vs
the 1-device mesh.  On CPU they run in a subprocess (``serve_scaling.py``;
the device-count flag must precede jax init), on a TPU host in this
process over the chips present, and land as the ``scaling`` section of
``BENCH_serve.json``.

The **prefix rows** (DESIGN.md §14) replay a shared-system-prompt trace
— every request repeats the same ``SYS_LEN``-token system prompt before
its own suffix — through the refcounted prefix-cache engine and the
no-sharing chunked engine: hit rate, prefill tokens skipped (which must
track ``matched_tokens`` exactly when nothing preempts), tokens/s vs
no-sharing, and a token_identical flag.  The run asserts zero page
leaks (``verify()`` + free-list identity) before reporting.
``benchmarks.run --prefix`` runs ONLY this trace (the CI smoke),
merging the ``prefix`` section into an existing ``BENCH_serve.json``.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.configs import get_config
from repro.core.quantizer import QuantConfig
from repro.models import build_model
from repro.serve import faults as flt
from repro.serve.engine import Engine, RequestStatus, ServeConfig
from repro.serve.kv_cache import pages_for
from repro.serve.quantized import QuantizedModel, quantize_lm_packed

BENCH_SERVE_JSON = common.ART / "BENCH_serve.json"

# --faults (benchmarks.run): skip the full matrix and run only the clean
# paged baseline + the fault-injected degraded-mode row — the CI smoke
# that serving stays live under injected pool pressure (DESIGN.md §12)
FAULTS_ONLY = False

# --prefix (benchmarks.run): run only the shared-system-prompt trace —
# the CI smoke that prefix caching skips prefill work and stays
# token-identical to the no-sharing engine (DESIGN.md §14)
PREFIX_ONLY = False

ARCH = "llama-micro"
PAGE_SIZE = 16
MAX_LEN = 192
MAX_BATCH = 4
MAX_NEW = 8 if common.FAST else 16
# fixed mixed-length trace: short chat turns + a few long-context requests
TRACE = [8, 40, 16, 96, 24, 64, 8, 120, 32, 12, 80, 18]
N_REQ = 6 if common.FAST else len(TRACE)

# inter-token-latency trace: 2 short decoders + a long prompt arriving
# mid-flight (DESIGN.md §10).  The chunk is sized well below the long
# prompt so the bounded-stall effect dominates the per-step overhead of
# the miniature model.
ITL_SHORTS = [12, 9]
ITL_LONG = 320 if common.FAST else 512
ITL_CHUNK = 8
ITL_MAX_NEW = 24 if common.FAST else 40
ITL_MAX_LEN = ITL_LONG + ITL_MAX_NEW + 8

# shared-system-prompt trace (DESIGN.md §14): SYS_LEN tokens (full pages
# + a tail, so the tail-page rule is exercised) repeated by every request
SYS_LEN = 42


def _run_engine(qm, packed, prompts, paged: bool):
    lens = [len(p) + MAX_NEW for p in prompts]
    if paged:
        # pool for max_batch concurrent sequences at the P95 trace length
        p95 = int(np.percentile(lens, 95))
        num_pages = MAX_BATCH * pages_for(p95, PAGE_SIZE)
    else:
        num_pages = 0
    scfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                       max_new=MAX_NEW, prefill_bucket=32, paged=paged,
                       page_size=PAGE_SIZE, num_pages=num_pages)
    eng = Engine(qm, packed, scfg)
    for p in prompts:
        eng.submit(p)
    t0 = time.monotonic()
    done = eng.run()
    dt = time.monotonic() - t0
    toks = sum(len(r.out_tokens) for r in done)
    stats = {
        "tokens_per_s": toks / dt,
        "requests_per_s": len(done) / dt,
        "wall_s": dt,
        "new_tokens": toks,
        "cache_bytes": eng._kv.cache_bytes(),
        "outputs": [r.out_tokens for r in done],
    }
    if paged:
        al = eng._kv.allocator
        stats.update(pool_pages=al.num_pages, page_size=PAGE_SIZE,
                     peak_pages_in_use=al.peak_in_use,
                     preemptions=sum(r.preemptions for r in done))
    return stats


def _itl_engine(qm, packed, prompts_short, prompt_long, chunked: bool):
    """Inter-token latency of in-flight decodes while a long prompt
    arrives.  Runs the trace twice on ONE engine (same jit caches): pass 1
    warms every compile (decode step, chunk step / prefill buckets), pass
    2 is measured.  Returns consecutive-token gaps of the short requests
    in milliseconds."""
    scfg = ServeConfig(max_batch=len(prompts_short) + 1, max_len=ITL_MAX_LEN,
                       max_new=ITL_MAX_NEW, prefill_bucket=32,
                       prefill_chunk=ITL_CHUNK if chunked else 0)
    eng = Engine(qm, packed, scfg)

    def trace_pass():
        times: dict[int, list] = {}
        on_tok = lambda r, t: times.setdefault(r.rid, []).append(
            time.monotonic())
        shorts = [eng.submit(p, on_token=on_tok) for p in prompts_short]
        for _ in range(3):          # shorts admit and start decoding
            eng.step()
        eng.submit(prompt_long, on_token=on_tok)
        eng.run()
        deltas = []
        for r in shorts:
            deltas += list(np.diff(times[r.rid]))
        return [1e3 * d for d in deltas]

    trace_pass()                    # warmup (compiles)
    deltas = trace_pass()
    return {"p50_ms": float(np.percentile(deltas, 50)),
            "p99_ms": float(np.percentile(deltas, 99)),
            "max_ms": float(np.max(deltas)), "n_gaps": len(deltas)}


def _run_degraded(qm, packed, prompts):
    """Paged engine under injected pool pressure: half the clean pool plus
    a seeded 50%-probability allocator brown-out bounded at 40 failures.
    The guarantee under test is graceful degradation (DESIGN.md §12): the
    run terminates (storm guard + watchdog), the non-failed majority still
    completes, and the pool conserves every page."""
    lens = [len(p) + MAX_NEW for p in prompts]
    clean_pages = MAX_BATCH * pages_for(int(np.percentile(lens, 95)),
                                        PAGE_SIZE)
    num_pages = max(pages_for(max(lens) + 1, PAGE_SIZE), clean_pages // 2)
    plan = flt.FaultPlan(
        flt.Fault(flt.ALLOC_FAIL, after_step=3, count=40, prob=0.5), seed=7)
    scfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN, max_new=MAX_NEW,
                       prefill_bucket=32, paged=True, page_size=PAGE_SIZE,
                       num_pages=num_pages)
    eng = Engine(qm, packed, scfg, faults=plan)
    for p in prompts:
        eng.submit(p)
    t0 = time.monotonic()
    done = eng.run(max_steps=4000)   # hang backstop; watchdog bounds this
    dt = time.monotonic() - t0
    eng._kv.verify()                 # no page leaked or double-owned
    assert eng._kv.allocator.num_free == num_pages, "page leak under faults"
    toks = sum(len(r.out_tokens) for r in done)
    n_ok = sum(r.status is RequestStatus.COMPLETED for r in done)
    return {
        "tokens_per_s": toks / dt, "wall_s": dt, "new_tokens": toks,
        "pool_pages": num_pages, "clean_pool_pages": clean_pages,
        "faults_fired": len(plan.log),
        "completion_rate": n_ok / len(done),
        "statuses": dict(sorted(eng.status_counts().items())),
    }


def _run_prefix(qm, packed, prompts, prefix: bool):
    """Paged + chunked engine over a shared-system-prompt trace, with or
    without the refcounted prefix cache.  Audits the pool before
    reporting: ``verify()`` + free-list identity == zero page leaks."""
    lens = [len(p) + MAX_NEW for p in prompts]
    num_pages = MAX_BATCH * pages_for(int(np.percentile(lens, 95)),
                                      PAGE_SIZE)
    scfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                       max_new=MAX_NEW, prefill_bucket=32, paged=True,
                       page_size=PAGE_SIZE, num_pages=num_pages,
                       prefill_chunk=PAGE_SIZE, prefix_cache=prefix)
    eng = Engine(qm, packed, scfg)
    for p in prompts:
        eng.submit(p)
    t0 = time.monotonic()
    done = eng.run()
    dt = time.monotonic() - t0
    eng._kv.verify()
    assert eng._kv.allocator.num_free == eng._kv.allocator.num_pages, \
        "page leak in prefix trace"
    toks = sum(len(r.out_tokens) for r in done)
    return {"tokens_per_s": toks / dt, "wall_s": dt, "new_tokens": toks,
            "preemptions": sum(r.preemptions for r in done),
            "outputs": [r.out_tokens for r in done],
            **eng.prefix_stats}


def _prefix_doc_and_rows(qm, packed, vocab):
    """The shared-system-prompt trace -> the ``prefix`` section of
    BENCH_serve.json + its CSV rows.  The no-sharing baseline runs twice
    (first pass pays the chunked-prefill compiles) so the reported
    tokens/s ratio compares steady-state passes."""
    rng = np.random.default_rng(14)
    suffixes = TRACE[:N_REQ]
    sys_prompt = rng.integers(0, vocab, SYS_LEN)
    prompts = [np.concatenate([sys_prompt, rng.integers(0, vocab, n)])
               for n in suffixes]
    _run_prefix(qm, packed, prompts, prefix=False)          # warmup
    base = _run_prefix(qm, packed, prompts, prefix=False)
    shared = _run_prefix(qm, packed, prompts, prefix=True)
    identical = shared["outputs"] == base["outputs"]
    skipped = base["prefilled_tokens"] - shared["prefilled_tokens"]
    doc = {
        "sys_prompt_len": SYS_LEN, "suffix_lens": suffixes,
        "page_size": PAGE_SIZE,
        "lookups": shared["lookups"], "hits": shared["hits"],
        "hit_rate": shared["hits"] / max(shared["lookups"], 1),
        "matched_tokens": shared["matched_tokens"],
        "prefill_tokens": shared["prefilled_tokens"],
        "prefill_tokens_base": base["prefilled_tokens"],
        "prefill_tokens_skipped": skipped,
        "tokens_per_s": shared["tokens_per_s"],
        "base_tokens_per_s": base["tokens_per_s"],
        "speedup": shared["tokens_per_s"] / base["tokens_per_s"],
        "preemptions": shared["preemptions"],
        "token_identical": identical,
    }
    # skipped prefill must track the matched tokens exactly when nothing
    # preempted (a resume re-prefills, which re-counts)
    if shared["preemptions"] == 0:
        assert skipped == shared["matched_tokens"], doc
    us_per_tok = 1e6 * shared["wall_s"] / max(shared["new_tokens"], 1)
    rows = [("serve/engine_prefix_cache_w4a8kv8", us_per_tok,
             f"tok_s={doc['tokens_per_s']:.1f};hit_rate="
             f"{doc['hit_rate']:.2f};prefill_skipped="
             f"{doc['prefill_tokens_skipped']};base_tok_s="
             f"{doc['base_tokens_per_s']:.1f};token_identical={identical}")]
    return doc, rows


def _degraded_doc_and_rows(qm, packed, prompts, clean_paged):
    deg = _run_degraded(qm, packed, prompts)
    deg["clean_tokens_per_s"] = clean_paged["tokens_per_s"]
    rows = [("serve/engine_paged_degraded_w4a8kv8",
             1e6 * deg["wall_s"] / max(deg["new_tokens"], 1),
             f"tok_s={deg['tokens_per_s']:.1f};completion_rate="
             f"{deg['completion_rate']:.2f};pool={deg['pool_pages']}/"
             f"{deg['clean_pool_pages']};faults={deg['faults_fired']};"
             f"clean_tok_s={deg['clean_tokens_per_s']:.1f}")]
    return deg, rows


def run():
    cfg = get_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    qcfg = QuantConfig(w_bits=4, a_bits=8, group_size=32, lwc=False,
                       kv_bits=8)
    packed = quantize_lm_packed(params, cfg, qcfg)
    qm = QuantizedModel(cfg, qcfg, kernel_mode="ref",
                        flash_block_kv=PAGE_SIZE)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in TRACE[:N_REQ]]

    if FAULTS_ONLY:
        # CI smoke: clean paged baseline + the degraded row only, merged
        # into an existing BENCH_serve.json when the full suite ran first
        pgd = _run_engine(qm, packed, prompts, paged=True)
        deg, rows = _degraded_doc_and_rows(qm, packed, prompts, pgd)
        common.ART.mkdir(parents=True, exist_ok=True)
        doc = (json.loads(BENCH_SERVE_JSON.read_text())
               if BENCH_SERVE_JSON.exists() else
               {"arch": ARCH, "quant": "w4a8g32kv8", "kernel_mode": "ref"})
        doc["degraded"] = deg
        BENCH_SERVE_JSON.write_text(json.dumps(doc, indent=2))
        return rows

    if PREFIX_ONLY:
        # CI smoke: the shared-system-prompt trace only, merged into an
        # existing BENCH_serve.json when the full suite ran first
        pfx, rows = _prefix_doc_and_rows(qm, packed, cfg.vocab_size)
        common.ART.mkdir(parents=True, exist_ok=True)
        doc = (json.loads(BENCH_SERVE_JSON.read_text())
               if BENCH_SERVE_JSON.exists() else
               {"arch": ARCH, "quant": "w4a8g32kv8", "kernel_mode": "ref"})
        doc["prefix"] = pfx
        BENCH_SERVE_JSON.write_text(json.dumps(doc, indent=2))
        return rows

    lin = _run_engine(qm, packed, prompts, paged=False)
    pgd = _run_engine(qm, packed, prompts, paged=True)
    identical = lin["outputs"] == pgd["outputs"]

    # the w4a4kv4 deployment point: same trace on the packed-nibble KV
    # cache (int4 weights + activations + KV, bf16 block-32 scales) —
    # the cache-bytes delta vs kv8 is the tentpole's serving-memory win
    qcfg4 = QuantConfig(w_bits=4, a_bits=4, group_size=32, lwc=False,
                        kv_bits=4)
    packed4 = quantize_lm_packed(params, cfg, qcfg4)
    qm4 = QuantizedModel(cfg, qcfg4, kernel_mode="ref",
                         flash_block_kv=PAGE_SIZE)
    lin4 = _run_engine(qm4, packed4, prompts, paged=False)
    pgd4 = _run_engine(qm4, packed4, prompts, paged=True)
    identical4 = lin4["outputs"] == pgd4["outputs"]

    # inter-token latency: long-prompt arrival against in-flight decodes
    shorts = [rng.integers(0, cfg.vocab_size, n) for n in ITL_SHORTS]
    long_p = rng.integers(0, cfg.vocab_size, ITL_LONG)
    itl_whole = _itl_engine(qm, packed, shorts, long_p, chunked=False)
    itl_chunk = _itl_engine(qm, packed, shorts, long_p, chunked=True)

    # degraded mode: same trace under injected pool pressure
    deg, deg_rows = _degraded_doc_and_rows(qm, packed, prompts, pgd)

    # prefix caching: shared-system-prompt trace, sharing vs no-sharing
    pfx, pfx_rows = _prefix_doc_and_rows(qm, packed, cfg.vocab_size)

    # mesh scaling: the sharded engine on 1/2/4/8 devices (virtual CPU
    # devices in a subprocess; on TPU the chips present, in-process)
    from benchmarks import serve_scaling
    scaling = serve_scaling.run_scaling()

    doc = {
        "arch": ARCH, "quant": "w4a8g32kv8", "kernel_mode": "ref",
        "trace_prompt_lens": [int(len(p)) for p in prompts],
        "max_batch": MAX_BATCH, "max_len": MAX_LEN, "max_new": MAX_NEW,
        "token_identical": identical,
        "linear": {k: v for k, v in lin.items() if k != "outputs"},
        "paged": {k: v for k, v in pgd.items() if k != "outputs"},
        "cache_mem_ratio": lin["cache_bytes"] / pgd["cache_bytes"],
        "w4a4kv4": {
            "quant": "w4a4g32kv4", "token_identical": identical4,
            "linear": {k: v for k, v in lin4.items() if k != "outputs"},
            "paged": {k: v for k, v in pgd4.items() if k != "outputs"},
            "kv4_vs_kv8_cache_ratio":
                pgd["cache_bytes"] / pgd4["cache_bytes"],
        },
        "itl": {
            "trace": {"short_prompt_lens": ITL_SHORTS,
                      "long_prompt_len": ITL_LONG,
                      "prefill_chunk": ITL_CHUNK,
                      "max_new": ITL_MAX_NEW},
            "whole_prompt": itl_whole,
            "chunked": itl_chunk,
            "p99_ratio": itl_whole["p99_ms"] / itl_chunk["p99_ms"],
        },
        "degraded": deg,
        "prefix": pfx,
        "scaling": scaling,
    }
    common.ART.mkdir(parents=True, exist_ok=True)
    BENCH_SERVE_JSON.write_text(json.dumps(doc, indent=2))

    rows = []
    for tag, st in (("linear", lin), ("paged", pgd)):
        us_per_tok = 1e6 * st["wall_s"] / max(st["new_tokens"], 1)
        rows.append((
            f"serve/engine_{tag}_w4a8kv8", us_per_tok,
            f"tok_s={st['tokens_per_s']:.1f};req_s="
            f"{st['requests_per_s']:.2f};cache_MiB="
            f"{st['cache_bytes'] / 2**20:.2f}"))
    rows.append(("serve/linear_vs_paged_cache_ratio",
                 0.0, f"ratio={doc['cache_mem_ratio']:.2f};"
                      f"token_identical={identical}"))
    for tag, st in (("linear", lin4), ("paged", pgd4)):
        us_per_tok = 1e6 * st["wall_s"] / max(st["new_tokens"], 1)
        rows.append((
            f"serve/engine_{tag}_w4a4kv4", us_per_tok,
            f"tok_s={st['tokens_per_s']:.1f};req_s="
            f"{st['requests_per_s']:.2f};cache_MiB="
            f"{st['cache_bytes'] / 2**20:.2f};"
            f"token_identical={identical4}"))
    rows.append(("serve/kv4_vs_kv8_paged_cache_ratio", 0.0,
                 f"ratio={doc['w4a4kv4']['kv4_vs_kv8_cache_ratio']:.2f}"))
    for tag, itl in (("whole", itl_whole), ("chunked", itl_chunk)):
        rows.append((f"serve/itl_{tag}_prefill", itl["p99_ms"] * 1e3,
                     f"p50_ms={itl['p50_ms']:.2f};p99_ms="
                     f"{itl['p99_ms']:.2f};max_ms={itl['max_ms']:.2f}"))
    rows.append(("serve/itl_chunked_vs_whole_p99", 0.0,
                 f"ratio={doc['itl']['p99_ratio']:.2f}x"))
    rows.extend(deg_rows)
    rows.extend(pfx_rows)
    rows.extend(serve_scaling.scaling_rows(scaling))
    return rows
