"""Serving launcher: batched continuous-batching engine demo.

    PYTHONPATH=src python -m repro.launch.serve --arch llama-mini \
        --ckpt checkpoints/llama-mini --requests 8 --max-new 16 \
        [--quantize] [--packed] [--abits 8] [--kvbits 8]

``--quantize`` runs the prompts through the AffineQuant-calibrated model
(fake-quant effective weights — identical serving graph) and reports the
agreement rate against the fp model.

``--packed`` (implies ``--quantize``) additionally runs the REAL deployment
pipeline: calibrate -> finalize(deploy="packed") -> QTensor tree ->
QuantizedModel -> Engine. The decode path serves packed sub-byte codes
quantized exactly once on the calibrated LWC grid (no fp-weight fallback),
and the launcher reports token agreement vs fp plus the weight-memory
compression.

``--abits < 16`` serves the weight-activation path (paper Table 3; w4a4 via
``--wbits 4 --abits 4``): every packed matmul routes through the fused
dynamic-act-quant int kernel (``kernels.ops.quant_matmul``), with no
fp-activation fallback in prefill or decode. ``--kvbits < 16`` additionally
stores the KV cache as int8 codes + per-(token, head) scales; decode
attention reads that cache as stored through ``kernels.ops.flash_decode``
(in-register tile dequant, length-bounded KV grid — DESIGN.md §8), and the
launcher reports KV-cache memory alongside the weight memory.

``--kernel-mode`` picks the kernel dispatch for the packed path: ``auto``
(default) compiles Pallas on TPU and falls back to the portable XLA paths
here; ``ref`` forces the tile-structured reference math (the flash-decode
lowering without a TPU); ``interpret`` executes the Pallas kernel bodies in
Python (slow — parity checks only).

``--prefill-chunk N`` turns on chunked admission (DESIGN.md §10): prompts
prefill in N-token chunks through ``prefill_chunk``, one chunk per engine
step interleaved with decode, so long prompts never stall in-flight
decodes for more than one chunk of work — token-identical to whole-prompt
admission because every prefill path reads the cache as stored through the
same tiled kernel.

``--ttl-s`` / ``--max-queue`` exercise the failure model (DESIGN.md §12):
per-request wall-clock deadlines (expired requests finish
``FAILED_DEADLINE`` with their partial stream) and bounded-queue
backpressure (rejected submits are logged, not raised as tracebacks).
Whenever any request ends non-``COMPLETED`` the launcher prints a
per-status histogram next to the throughput line.  Each run also logs the
engine's host time per step by phase: the self time of each
``serve.*`` span of ``Engine.span_records`` (``repro.serve.spans``), so
``decode.wait`` is the time the host waited on the device.

``--paged`` serves through the page-table KV cache (DESIGN.md §9): the
engine allocates fixed-size pages (``--page-size``) from a global pool on
admission, grows sequences page-by-page, preempts the longest sequence when
the pool runs dry, and reclaims pages on completion — so cache memory
tracks live tokens instead of ``max_batch × max_len`` slots.  The launcher
runs the linear engine too and reports token agreement plus the cache
memory ratio.

``--prefix-cache`` (needs ``--paged``) turns on refcounted prefix-page
sharing (DESIGN.md §14): full pages of already-served prompts stay
content-addressable after release, and a new request whose token prefix
matches adopts those pages instead of re-prefilling them.  The launcher
demos it with a cold wave plus a replay wave of the same prompts on one
engine and reports hit rate, adopted tokens, and token agreement against
the plain paged run.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.calibration import CalibConfig, quantize_dense_model
from repro.core.quantizer import QuantConfig
from repro.data import MarkovCorpus
from repro.models import build_model
from repro.serve import spans
from repro.serve.engine import Engine, QueueFull, RequestStatus, ServeConfig
from repro.train import checkpoints
from repro.utils import logger


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-mini")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--packed", action="store_true",
                    help="serve real packed QTensor weights (implies "
                         "--quantize): calibrate -> pack -> Engine")
    ap.add_argument("--wbits", type=int, default=4)
    ap.add_argument("--abits", type=int, default=16,
                    help="activation bits for the packed path (16 = fp "
                         "activations; 8/4 = fused int-activation kernel)")
    ap.add_argument("--kvbits", type=int, default=16,
                    help="KV-cache bits for the packed path (>= 16 = model "
                         "dtype; 8 = int8 codes + per-(token, head) f32 "
                         "scales; 4 = packed int4 nibbles + bf16 block-32 "
                         "microscaling scales)")
    ap.add_argument("--kernel-mode", default="auto",
                    choices=["auto", "pallas", "interpret", "ref"],
                    help="kernel dispatch for the packed path (see module "
                         "docstring)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="> 0: chunked admission — prompts prefill in "
                         "chunks of this many tokens, one chunk per engine "
                         "step interleaved with decode (bounds inter-token "
                         "latency under long-prompt arrival; token-"
                         "identical to whole-prompt admission); 0 = "
                         "whole-prompt bucketed prefill")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the page-table KV cache (page-pool "
                         "allocation, preemption, reclamation) and report "
                         "agreement + memory vs the linear engine")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page for --paged")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size for --paged (0 = live-trace "
                         "sizing: max_batch * pages(prompt_len + max_new))")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted prefix-page sharing over the paged "
                         "cache (needs --paged; chunked admission is "
                         "implied — defaults to --page-size chunks): "
                         "matching prompt prefixes adopt resident pages "
                         "instead of re-prefilling (DESIGN.md §14)")
    ap.add_argument("--ttl-s", type=float, default=0.0,
                    help="per-request wall-clock deadline in seconds "
                         "(0 = none); expired requests finish "
                         "FAILED_DEADLINE with their partial stream "
                         "(DESIGN.md §12)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue (0 = unbounded): submits "
                         "past this many pending requests are rejected "
                         "with backpressure instead of queued")
    ap.add_argument("--mesh-data", type=int, default=1,
                    help="data-parallel mesh axis for mesh-native serving "
                         "(batch splits across it); 1x1 = single device")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="tensor-parallel mesh axis: QTensor weights go "
                         "column-parallel and KV pools split their head "
                         "dim across it — token-identical to single-device "
                         "(DESIGN.md §13)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # fail loudly on cache widths no kernel serves — a typo like
    # --kvbits 6 must not silently fall back to the fp cache path
    if args.kvbits < 16 and args.kvbits not in (4, 8):
        ap.error(f"--kvbits {args.kvbits} unsupported: use 4 (packed int4 "
                 "+ bf16 block-32 scales), 8 (int8 + f32 per-(token, head) "
                 "scales), or >= 16 (fp cache)")
    if args.prefix_cache and not args.paged:
        ap.error("--prefix-cache shares pages of the paged KV cache: "
                 "add --paged")

    mesh = None
    if args.mesh_data > 1 or args.mesh_model > 1:
        from repro.launch.mesh import make_serving_mesh
        try:
            mesh = make_serving_mesh(args.mesh_data, args.mesh_model)
        except ValueError as e:
            ap.error(str(e))
        logger.info("serving mesh: (data=%d, model=%d) over %d %s devices",
                    args.mesh_data, args.mesh_model, mesh.devices.size,
                    mesh.devices.flat[0].platform)

    cfg = get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.ckpt:
        from repro.optim import AdamConfig
        from repro.train.step import init_train_state
        state = init_train_state(model, jax.random.PRNGKey(args.seed),
                                 AdamConfig())
        state, _ = checkpoints.restore(args.ckpt, state)
        params = state.params

    corpus = MarkovCorpus(vocab=cfg.vocab_size, seed=args.seed)
    prompts = [corpus.sample(1, args.prompt_len, seed=100 + i)[0]
               for i in range(args.requests)]

    scfg = ServeConfig(max_batch=args.max_batch,
                       max_len=args.prompt_len + args.max_new + 8,
                       max_new=args.max_new,
                       prefill_chunk=args.prefill_chunk,
                       default_ttl_s=args.ttl_s,
                       max_queue=args.max_queue)
    if args.prefill_chunk:
        logger.info("chunked admission: prompts prefill in %d-token chunks "
                    "interleaved with decode steps (token-identical to "
                    "whole-prompt; bounds inter-token latency)",
                    args.prefill_chunk)

    def run(p, tag, serving_model=None, cfg_serve=None):
        eng = Engine(serving_model or model, p, cfg_serve or scfg,
                     mesh=mesh)
        if mesh is not None:
            rep = eng.memory_report()
            logger.info("[%s] per-device resident memory: weights %.2f "
                        "MiB, kv cache %.2f MiB (x%d devices)", tag,
                        rep["weight_bytes_per_device"] / 2**20,
                        rep["kv_bytes_per_device"] / 2**20,
                        rep["device_count"])
        for pr in prompts:
            try:
                eng.submit(pr)
            except QueueFull as e:
                # backpressure, not an error: the request is terminal
                # REJECTED_QUEUE_FULL and shows up in the status summary
                logger.warning("[%s] %s", tag, e)
            except ValueError as e:
                # config error (prompt can NEVER be served by this pool /
                # max_len) — actionable message, no traceback
                ap.error(f"unservable request: {e}")
        t0 = time.monotonic()
        done = eng.run()
        dt = time.monotonic() - t0
        ok = [r for r in done if r.status is RequestStatus.COMPLETED]
        total_new = sum(len(r.out_tokens) for r in done)
        logger.info("[%s] %d requests, %d tokens in %.2fs (%.1f tok/s)",
                    tag, len(done), total_new, dt, total_new / dt)
        if len(ok) != len(done):
            counts = ", ".join(f"{k}={v}" for k, v in
                               sorted(eng.status_counts().items()))
            logger.warning("[%s] %d/%d requests completed (%s)", tag,
                           len(ok), len(done), counts)
        per = spans.phase_ms(eng.span_records())
        logger.info("[%s] host ms per step: %s", tag, ", ".join(
            f"{k.removeprefix('serve.')} {v:.3f}" for k, v in per.items()))
        return [r.out_tokens for r in done], eng

    def agreement(a_outs, b_outs):
        pairs = [(a, b) for a, b in zip(a_outs, b_outs) if a and b]
        if not pairs:                 # everything rejected / expired
            return float("nan")
        return np.mean([np.mean(np.array(a[:len(b)]) == np.array(b[:len(a)]))
                        for a, b in pairs])

    fp_out, fp_eng = run(params, "fp")

    if args.quantize or args.packed:
        if not args.packed and (args.abits < 16 or args.kvbits < 16):
            logger.warning("without --packed, --abits only changes the "
                           "calibration objective (activation-aware sites/"
                           "loss) — the --quantize simulation still SERVES "
                           "fp activations — and --kvbits has no effect")
        qcfg = QuantConfig(w_bits=args.wbits, a_bits=args.abits,
                           group_size=64, kv_bits=args.kvbits)
        ccfg = CalibConfig(epochs=5)
        calib = jnp.asarray(corpus.sample(16, args.prompt_len, seed=777))
        qparams, cal_info = quantize_dense_model(
            params, cfg, qcfg, ccfg, calib, log=False)
        q_out, _ = run(qparams, f"affinequant-w{args.wbits}")
        logger.info("greedy-token agreement fp vs quant: %.1f%%",
                    100 * agreement(fp_out, q_out))

        if args.packed:
            # real deployment: ONE quantization on the calibrated LWC grid,
            # packed QTensor leaves served end-to-end by the engine (same
            # calibration — finalize_model only re-merges, no second Adam)
            from repro.core.calibration import finalize_model
            from repro.serve.quantized import QuantizedModel, quantize_lm_packed
            from repro.utils import tree_bytes
            pparams = finalize_model(params, cal_info["block_qps"], cfg,
                                     qcfg, ccfg, deploy="packed")
            pparams = quantize_lm_packed(pparams, cfg, qcfg)  # pass-through
            qmodel = QuantizedModel(cfg, qcfg, kernel_mode=args.kernel_mode)
            tag = f"affinequant-{qcfg.tag()}-packed"
            if args.abits < 16:
                logger.info("decode matmul path: fused w%da%d int kernel "
                            "(per-token dynamic activation quant, no "
                            "fp-activation fallback)", args.wbits, args.abits)
            on_tpu = jax.default_backend() == "tpu"
            flash = (args.kernel_mode in ("pallas", "interpret", "ref")
                     or (args.kernel_mode == "auto" and on_tpu))
            logger.info(
                "decode attention path: %s over the %s KV cache",
                "fused flash-decode (in-register tile dequant, "
                "length-bounded KV grid)" if flash
                else "portable decode_attention fallback (full-cache read)",
                f"int{args.kvbits}-coded" if args.kvbits < 16 else "fp")
            p_out, p_eng = run(pparams, tag, qmodel)
            logger.info("greedy-token agreement fp vs packed-%s: %.1f%%",
                        qcfg.tag(), 100 * agreement(fp_out, p_out))
            logger.info("greedy-token agreement quant vs packed-%s: %.1f%%",
                        qcfg.tag(), 100 * agreement(q_out, p_out))
            logger.info("weight memory: fp %.2f MiB -> packed %.2f MiB "
                        "(%.2fx)", tree_bytes(params) / 2**20,
                        tree_bytes(pparams) / 2**20,
                        tree_bytes(params) / tree_bytes(pparams))
            if args.kvbits < 16:
                # shape-only: report sizes without allocating either cache
                fp_cache = build_model(cfg).cache_specs(args.max_batch,
                                                        scfg.max_len)
                q_cache = qmodel.cache_specs(args.max_batch, scfg.max_len)
                logger.info("kv-cache memory (batch=%d, len=%d): fp %.2f MiB"
                            " -> kv%d %.2f MiB (%.2fx)", args.max_batch,
                            scfg.max_len, tree_bytes(fp_cache) / 2**20,
                            args.kvbits, tree_bytes(q_cache) / 2**20,
                            tree_bytes(fp_cache) / tree_bytes(q_cache))

    if args.paged:
        import dataclasses as _dc

        from repro.serve.kv_cache import pages_for
        # paged engine over whatever the best serving stack above was;
        # default pool sized to the LIVE trace (max_batch concurrent
        # sequences at their final length), not the linear worst case —
        # that sizing is the memory win the layout exists for
        serving = (qmodel, pparams, p_eng, "packed") if args.packed \
            else (None, params, fp_eng, "fp")
        smodel, sparams, lin_eng, stag = serving
        num_pages = args.num_pages or args.max_batch * pages_for(
            args.prompt_len + args.max_new + 1, args.page_size)
        pcfg = _dc.replace(scfg, paged=True, page_size=args.page_size,
                           num_pages=num_pages)
        pg_out, pg_eng = run(sparams, f"{stag}-paged", smodel, pcfg)
        base_out = p_out if args.packed else fp_out
        logger.info("greedy-token agreement %s linear vs paged: %.1f%%",
                    stag, 100 * agreement(base_out, pg_out))
        al = pg_eng._kv.allocator
        logger.info("page pool: %d pages x %d tokens; peak in use %d, "
                    "free after drain %d", al.num_pages, args.page_size,
                    al.peak_in_use, al.num_free)
        logger.info("kv-cache memory: linear %.2f MiB -> paged pool %.2f "
                    "MiB (%.2fx)", lin_eng._kv.cache_bytes() / 2**20,
                    pg_eng._kv.cache_bytes() / 2**20,
                    lin_eng._kv.cache_bytes() / pg_eng._kv.cache_bytes())

        if args.prefix_cache:
            # cold wave + replay wave on ONE engine: released full pages
            # stay content-addressable, so the replay adopts them instead
            # of re-prefilling — the multi-turn / repeated-system-prompt
            # serving pattern (DESIGN.md §14)
            # released pages only stay adoptable while the pool doesn't
            # recycle them: size it to the whole trace's working set (or
            # trust --num-pages), not the live max_batch sizing
            px_pages = args.num_pages or args.requests * pages_for(
                args.prompt_len + args.max_new + 1, args.page_size)
            pxcfg = _dc.replace(
                pcfg, prefix_cache=True, num_pages=px_pages,
                prefill_chunk=args.prefill_chunk or args.page_size)
            px_eng = Engine(smodel or model, sparams, pxcfg, mesh=mesh)
            waves = []
            for wave in ("cold", "replay"):
                reqs = [px_eng.submit(pr) for pr in prompts]
                t0 = time.monotonic()
                px_eng.run()
                dt = time.monotonic() - t0
                waves.append([r.out_tokens for r in reqs])
                total_new = sum(len(r.out_tokens) for r in reqs)
                logger.info("[%s-prefix %s] %d requests, %d tokens in "
                            "%.2fs (%.1f tok/s)", stag, wave, len(reqs),
                            total_new, dt, total_new / dt)
            stats = px_eng.prefix_stats
            logger.info("prefix cache: %d/%d lookups hit, %d prompt tokens "
                        "adopted from resident pages, %d prefilled",
                        stats["hits"], stats["lookups"],
                        stats["matched_tokens"], stats["prefilled_tokens"])
            logger.info("greedy-token agreement %s paged vs prefix-cache: "
                        "cold %.1f%%, replay %.1f%%", stag,
                        100 * agreement(pg_out, waves[0]),
                        100 * agreement(pg_out, waves[1]))
    return 0


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
