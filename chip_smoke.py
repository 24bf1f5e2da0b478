#!/usr/bin/env python3
"""Smoke run of the served path on a TPU at LLaMA-7B width.

    python3 chip_smoke.py             # one chip: calibrate -> pack -> serve
    python3 chip_smoke.py --chips 4   # four chips: sharded vs one device

One process drives the library entry points the ``repro.launch``
calibrate and serve CLIs use, at the full width of ``llama-7b``
(d_model 4096, 32 heads, head_dim 128, d_ff 11008, vocab 32000) with
random weights made from ``--seed``:

1. device     — JAX must see a TPU; anything else exits non-zero.
2. calibrate  — one block through ``quantize_dense_model`` (AffineQuant:
                ``calibrate_block`` then ``finalize_block(deploy="packed")``)
                on MarkovCorpus sequences; the loss must stay finite and
                end no higher than it started.
3. pack       — the 32-layer W4A4 g64 tree, layer by layer (block 0 is the
                calibrated one, the rest go through
                ``quantize_layers_packed``); the fp tree of the whole model
                never exists.
4. serve      — ``Engine`` + ``QuantizedModel(kernel_mode="auto")`` over a
                paged kv8 cache with chunked prefill; every request must end
                COMPLETED.
5. kernels    — the compiled decode and chunk programs must call the Pallas
                kernels (``tpu_custom_call``), not interpret mode or a
                fallback.
6. reference  — Pallas vs ``kernel_mode="ref"`` (see ``compare_ref``):
                the served 32-layer engine's chunk program on the K/V
                codes its first layer wrote (its logits reported), the
                served configuration cut to its first layer on logits,
                the paged flash kernels vs the XLA fallback attention,
                and the first layer with float32 activations.

``--chips 4`` runs only the sharded phase: one packed tree served on a
``(data=1, model=4)`` mesh and on one device, tokens compared.

Timings and memory printed along the way are smoke readings, not benchmark
metrics.  Any failure raises; the last line of a passing run is the JSON
object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llama-7b"
CALIB_SEQS, CALIB_LEN, CALIB_EPOCHS = 16, 128, 4
PROMPT_LENS = (128, 512, 256, 384, 160, 448, 192, 320)
MAX_NEW = 32
PAGE_SIZE = 16
PREFILL_CHUNK = 128
# Pallas vs reference.  Both programs store bf16 at every kernel operand
# (``ops._materialize``) and share every quantizer; they differ where a
# kernel's f32 summation order (flash: about 7e-8 relative) moves a
# value across a rounding boundary.  Logits of the served configuration
# cut to its first layer, relative L2:
REF_TOL = 1e-2
# the served stack's first-layer K/V codes: share allowed to differ (by
# one step at most)
KV_FLIP_TOL = 1e-3
# the first layer with float32 activations, and the paged flash kernels
# vs the XLA fallback attention: f32 summation order only
F32_TOL = ATTN_TOL = 1e-4
REF_CHUNK = 32


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Prints a phase's wall time (compilation included) on exit."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"[{self.name}] start")
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"[{self.name}] done in {time.monotonic() - self.t0:.1f} s "
                "(smoke reading, compilation included)")
        return False


def model_config():
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), dtype="bfloat16")


def quant_config():
    from repro.core.quantizer import QuantConfig
    return QuantConfig(w_bits=4, a_bits=4, group_size=64, kv_bits=8)


def init_shared(cfg, seed: int) -> dict:
    """Embedding and final norm from the seed (the non-layer params)."""
    from repro.models import layers
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    dtype = jnp.dtype(cfg.dtype)
    return {"embed": layers.embed_init(key, cfg.vocab_size, cfg.d_model,
                                       dtype),
            "ln_f": layers.init_norm(cfg.d_model, cfg.norm, dtype)}


def layer_key(seed: int, i: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed), 1 + i)


def calibrate_block0(cfg, qcfg, shared, seed: int):
    """AffineQuant calibration of block 0; returns (packed one-layer
    subtree with a leading layer axis, per-epoch losses)."""
    from repro.core.calibration import CalibConfig, quantize_dense_model
    from repro.data import MarkovCorpus
    from repro.models import transformer

    block = transformer.init_block(layer_key(seed, 0), cfg,
                                   jnp.dtype(cfg.dtype))
    params = dict(shared, layers=jax.tree_util.tree_map(lambda x: x[None],
                                                        block))
    corpus = MarkovCorpus(vocab=cfg.vocab_size, seed=seed)
    calib = jnp.asarray(corpus.sample(CALIB_SEQS, CALIB_LEN, seed=777))
    packed, info = quantize_dense_model(
        params, dataclasses.replace(cfg, num_layers=1), qcfg,
        CalibConfig(epochs=CALIB_EPOCHS), calib, log=False, deploy="packed")
    return packed["layers"], [float(x) for x in info["block_losses"][0]]


def pack_model(cfg, qcfg, shared, layer0, seed: int) -> dict:
    """Serving tree: ``layer0`` (packed, leading layer axis) followed by
    RTN-packed layers 1.. made one at a time from the seed."""
    from repro.models import transformer
    from repro.serve.quantized import quantize_layers_packed

    @jax.jit
    def pack_layer(key):
        block = transformer.init_block(key, cfg, jnp.dtype(cfg.dtype))
        lp = quantize_layers_packed(
            jax.tree_util.tree_map(lambda x: x[None], block), cfg, qcfg)
        # the calibration shift merged into block 0 gave it norm and q/k/v
        # biases; zero biases are exact and keep one tree structure
        return _zero_fill(layer0, lp)

    parts = [layer0] + [pack_layer(layer_key(seed, i))
                        for i in range(1, cfg.num_layers)]
    layers = jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *parts)
    del parts
    return dict(shared, layers=layers)


def _zero_fill(template, tree):
    """``tree`` with a zero array wherever ``template`` has a leaf that
    ``tree`` lacks."""
    if isinstance(template, dict):
        tree = tree or {}
        return {k: _zero_fill(v, tree.get(k)) for k, v in template.items()}
    return jnp.zeros(template.shape, template.dtype) if tree is None else tree


def make_prompts(cfg, seed: int) -> list:
    from repro.data import MarkovCorpus
    corpus = MarkovCorpus(vocab=cfg.vocab_size, seed=seed)
    return [corpus.sample(1, n, seed=100 + i)[0]
            for i, n in enumerate(PROMPT_LENS)]


def serve_config():
    from repro.serve.engine import ServeConfig
    return ServeConfig(max_batch=len(PROMPT_LENS),
                       max_len=max(PROMPT_LENS) + MAX_NEW + PAGE_SIZE,
                       max_new=MAX_NEW, prefill_chunk=PREFILL_CHUNK,
                       paged=True, page_size=PAGE_SIZE)


def serve(engine, prompts) -> list:
    """Serve ``prompts`` to completion; every request must COMPLETE with
    its full ``max_new`` tokens.  Returns the token streams."""
    from repro.serve.engine import RequestStatus
    reqs = [engine.submit(p) for p in prompts]
    t0 = time.monotonic()
    engine.run()
    dt = time.monotonic() - t0
    bad = [(r.rid, r.status.name, r.error) for r in reqs
           if r.status is not RequestStatus.COMPLETED]
    if bad:
        raise RuntimeError(f"requests not COMPLETED: {bad}")
    short = [r.rid for r in reqs if len(r.out_tokens) != MAX_NEW]
    if short:
        raise RuntimeError(f"requests ended before max_new: {short}")
    toks = sum(len(r.out_tokens) for r in reqs)
    log(f"  served {len(reqs)} requests, {toks} new tokens in {dt:.1f} s "
        "(smoke reading, compilation included)")
    return [list(map(int, r.out_tokens)) for r in reqs]


def pallas_kernels(hlo: str) -> set:
    """Names of the Pallas kernels a compiled program calls: the jitted
    kernel entry point named on each ``tpu_custom_call`` line."""
    names = set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.update(re.findall(
                r"(w4a8_matmul|dequant_matmul|flash_decode_paged|"
                r"flash_prefill_paged|flash_decode|flash_prefill)", line))
    return names


def check_kernels(engine) -> None:
    """The engine's compiled decode and chunk programs must call the
    Pallas kernels; ``auto`` must have resolved to compiled Pallas."""
    from repro.kernels import ops
    mode = engine.model.kernel_mode
    if ops._resolve(mode) != "pallas":
        raise RuntimeError(f"kernel_mode={mode!r} resolved to "
                           f"{ops._resolve(mode)!r}, not compiled Pallas")
    b, c = engine.cfg.max_batch, engine.cfg.prefill_chunk
    decode = engine._decode.lower(
        engine.params, engine._last_tok, engine._kv.cache,
        engine._idle_keys, engine._zero_poison).compile().as_text()
    zeros = jnp.zeros((b,), jnp.int32)
    chunk = engine._chunk.lower(
        engine.params, jnp.zeros((b, c), jnp.int32), zeros,
        engine._kv.cache, zeros).compile().as_text()
    for name, hlo, want in (
            ("decode", decode, {"w4a8_matmul", "flash_decode_paged"}),
            ("chunk", chunk, {"w4a8_matmul", "flash_prefill_paged"})):
        got = pallas_kernels(hlo)
        log(f"  compiled {name} step calls Pallas kernels: {sorted(got)} "
            f"({hlo.count('tpu_custom_call')} tpu_custom_call mentions)")
        if not want <= got:
            raise RuntimeError(f"{name} step lacks Pallas kernels "
                               f"{sorted(want - got)}")


def ref_logits(engine, cfg, qcfg, params, prompts):
    """One prefill chunk and one decode step on fresh caches: the
    engine's own compiled chunk and decode programs, and
    ``kernel_mode="ref"`` on the same tree.  Returns
    ``([(name, pallas_logits, ref_logits)], engine_decode_tokens,
    pallas_cache, ref_cache)``; logits are the last row, float32, and
    the caches hold the chunk's K/V."""
    from repro.serve.kv_cache import PagedCache
    from repro.serve.quantized import QuantizedModel

    scfg = engine.cfg
    b, c = scfg.max_batch, scfg.prefill_chunk
    toks = jnp.asarray(np.stack([p[:c] for p in prompts]), jnp.int32)
    chunk_len = jnp.full((b,), c, jnp.int32)
    offset = jnp.zeros((b,), jnp.int32)

    def fresh_cache(model):
        store = PagedCache(model, b, scfg.max_len, scfg.page_size,
                           num_pages=scfg.num_pages)
        for slot in range(b):
            if not store.reserve(slot, c + 1):
                raise RuntimeError("comparison pool too small")
        return store.cache

    lg_c, cache = engine._chunk(params, toks, chunk_len,
                                fresh_cache(engine.model), offset)
    nxt = jnp.argmax(lg_c[:, -1], axis=-1).astype(jnp.int32)[:, None]
    # the engine's decode program samples (greedy) and returns tokens;
    # the logits come from the same decode_step it wraps
    tok_d, _, _ = engine._decode(params, nxt, cache, engine._idle_keys,
                                 engine._zero_poison)
    lg_d, _ = jax.jit(engine.model.decode_step)(params, nxt, cache)
    ref = QuantizedModel(cfg, qcfg, kernel_mode="ref")
    rc, rcache = jax.jit(functools.partial(ref.prefill_chunk,
                                           last_only=True))(
        params, {"tokens": toks, "chunk_len": chunk_len},
        fresh_cache(ref), offset)
    rd, _ = jax.jit(ref.decode_step)(params, nxt, rcache)
    last = lambda x: np.asarray(x[:, -1], np.float32)
    return ([("prefill chunk", last(lg_c), last(rc)),
             ("decode step", last(lg_d), last(rd))],
            np.asarray(tok_d), cache, rcache)


def compare(pairs, label: str, tol, logits: bool = True) -> None:
    """Print Pallas-vs-reference distance (and, for logits, argmax
    agreement); fail beyond ``tol`` (None: report only)."""
    for name, got, want in pairs:
        if not (np.isfinite(got).all() and np.isfinite(want).all()):
            raise RuntimeError(f"{label} {name}: non-finite output")
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        bound = "not gated" if tol is None else f"tolerance {tol:.0e}"
        msg = (f"  {label} {name}: rel L2 {rel:.3e} ({bound}), max |diff| "
               f"{float(np.abs(got - want).max()):.3e}")
        if logits:
            agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
            msg += f", argmax agreement {agree:.3f} over {got.shape[0]} rows"
        log(msg)
        if tol is not None and not rel <= tol:
            raise RuntimeError(f"{label} {name}: rel L2 {rel} > {tol}")


def check_attention(cache, cfg, mode: str) -> None:
    """The compiled paged flash kernels vs the XLA fallback attention
    (``models.attention``, which shares no code with the kernels'
    per-tile math; the ``ref`` oracles do share it) on layer 0 of the
    served engine's kv8 pool after one chunk, float32 queries.  ``mode``
    is the engine's resolved kernel mode."""
    from repro.kernels import ops
    kv = (cache.k[0], cache.v[0], cache.k_scale[0], cache.v_scale[0])
    b, pt = cache.page_table.shape[0], cache.page_table
    hq, d = cfg.num_heads, cfg.resolved_head_dim
    kd, kc = jax.random.split(jax.random.PRNGKey(7))
    qd = jax.random.normal(kd, (b, 1, hq, d), jnp.float32)
    qc = jax.random.normal(kc, (b, REF_CHUNK, hq, d), jnp.float32)
    cur = jnp.full((b,), PREFILL_CHUNK, jnp.int32)
    off = cur - REF_CHUNK          # the chunk's last rows, causal
    run = {}
    for m in (mode, "xla"):
        with jax.default_matmul_precision("highest"):
            run[m] = (ops.flash_decode(qd, kv, cur, page_table=pt, mode=m),
                      ops.flash_prefill(qc, kv, off,
                                        jnp.full((b,), REF_CHUNK),
                                        page_table=pt, mode=m))
    flat = lambda x: np.asarray(x, np.float32).reshape(-1, d)
    compare([("decode", flat(run[mode][0]), flat(run["xla"][0])),
             ("prefill", flat(run[mode][1]), flat(run["xla"][1]))],
            "paged flash kernel vs XLA attention, layer 0", ATTN_TOL,
            logits=False)


def kv_code_flips(cache, rcache, n_tok: int):
    """Per layer: the share of K/V codes of the first ``n_tok`` positions
    that differ between two caches filled by the same chunk, and the
    largest difference in quantization steps."""
    if not np.array_equal(cache.page_table, rcache.page_table):
        raise RuntimeError("comparison caches hold different page tables")
    pages = cache.page_table[:, :n_tok // cache.page_size]

    def flips(a, b):
        d = jnp.abs(a[:, pages].astype(jnp.int32)
                    - b[:, pages].astype(jnp.int32))
        axes = tuple(range(1, d.ndim))
        return jnp.mean(d > 0, axis=axes), jnp.max(d, axis=axes)

    (fk, mk), (fv, mv) = flips(cache.k, rcache.k), flips(cache.v, rcache.v)
    return (np.asarray(jnp.maximum(fk, fv)),
            np.asarray(jnp.maximum(mk, mv)))


def first_layer(engine, cfg, qcfg, params, dtype: str, chunk: int):
    """The served engine's configuration cut to its first layer (the
    calibrated block) with activations in ``dtype``."""
    from repro.serve.engine import Engine
    from repro.serve.quantized import QuantizedModel
    cast = (lambda x: x.astype(dtype) if jnp.issubdtype(
        x.dtype, jnp.floating) and x.dtype != jnp.float32 else x)
    cfg1 = dataclasses.replace(cfg, num_layers=1, dtype=dtype)
    params1 = jax.tree_util.tree_map(cast, dict(
        params, layers=jax.tree_util.tree_map(lambda x: x[:1],
                                              params["layers"])))
    engine1 = Engine(
        QuantizedModel(cfg1, qcfg, kernel_mode=engine.model.kernel_mode),
        params1, dataclasses.replace(engine.cfg, prefill_chunk=chunk))
    return engine1, cfg1, params1


def compare_ref(engine, cfg, qcfg, params, prompts) -> None:
    """Pallas vs ``kernel_mode="ref"`` on the chip.

    A random-weight W4A4 stack is chaotic: where a kernel's f32
    summation order moves a value across a bf16 rounding boundary, the
    next activation quantizer may flip an int4 code, and the layers
    after it amplify that to an O(1) logit change at full depth.  So the
    served 32-layer engine's own chunk program is gated where the stack
    has not amplified anything yet, on the K/V codes its first layer
    wrote, and its logits are reported; the same configuration cut to
    its first layer is gated on logits; the paged flash kernels on the
    XLA fallback attention; and, as an extra check, the first layer with
    float32 activations.
    """
    from repro.kernels import ops

    pairs, tok_d, cache, rcache = ref_logits(engine, cfg, qcfg, params,
                                             prompts)
    compare(pairs, f"served bf16 {cfg.num_layers}-layer stack, Pallas vs "
            "ref logits", None)
    agree = np.mean(tok_d == pairs[1][2].argmax(-1))
    log(f"  engine decode program tokens: {agree:.3f} equal to the ref "
        "argmax (not gated)")
    share, steps = kv_code_flips(cache, rcache, engine.cfg.prefill_chunk)
    log("  K/V codes differing from ref, by layer (share, max steps): "
        + ", ".join(f"{i}: {f:.1e} ({m})"
                    for i, (f, m) in enumerate(zip(share, steps))))
    if not (share[0] <= KV_FLIP_TOL and steps[0] <= 1):
        raise RuntimeError(f"first-layer K/V codes differ from ref: share "
                           f"{share[0]}, up to {steps[0]} steps")
    check_attention(cache, cfg, ops._resolve(engine.model.kernel_mode))
    del cache, rcache

    for dtype, chunk, tol in (("bfloat16", engine.cfg.prefill_chunk,
                               REF_TOL),
                              ("float32", REF_CHUNK, F32_TOL)):
        engine1, cfg1, params1 = first_layer(engine, cfg, qcfg, params,
                                             dtype, chunk)
        pairs1, tok1, c1, r1 = ref_logits(engine1, cfg1, qcfg, params1,
                                          prompts)
        del c1, r1
        compare(pairs1, f"first layer, {dtype} activations, chunk {chunk},"
                " Pallas vs ref logits", tol)
        log(f"  engine decode program tokens: "
            f"{np.mean(tok1 == pairs1[1][2].argmax(-1)):.3f} equal to the "
            "ref argmax")


def peak_memory(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def one_chip(args) -> None:
    from repro.serve.engine import Engine
    from repro.serve.quantized import QuantizedModel
    from repro.utils import tree_bytes

    cfg, qcfg = model_config(), quant_config()
    shared = init_shared(cfg, args.seed)
    with Phase("calibrate"):
        layer0, losses = calibrate_block0(cfg, qcfg, shared, args.seed)
        log(f"  block 0 AffineQuant loss: first {losses[0]:.6g}, last "
            f"{losses[-1]:.6g} over {len(losses)} epochs")
        if not np.isfinite(losses).all():
            raise RuntimeError(f"non-finite calibration loss: {losses}")
        if losses[-1] > losses[0]:
            raise RuntimeError(f"calibration loss rose: {losses}")
    with Phase("pack"):
        params = pack_model(cfg, qcfg, shared, layer0, args.seed)
        jax.block_until_ready(params)
        log(f"  packed W4A4 g64 tree: {cfg.num_layers} layers, "
            f"{tree_bytes(params['layers']) / 2**30:.2f} GiB layers + "
            f"{tree_bytes(shared) / 2**30:.2f} GiB embedding/norm")
    prompts = make_prompts(cfg, args.seed)
    with Phase("serve"):
        engine = Engine(QuantizedModel(cfg, qcfg, kernel_mode="auto"),
                        params, serve_config())
        serve(engine, prompts)
    with Phase("kernels"):
        check_kernels(engine)
    with Phase("reference"):
        compare_ref(engine, cfg, qcfg, params, prompts)
    log(f"peak device memory: {peak_memory(jax.devices()[0])} "
        "(smoke reading)")


def four_chips(args) -> None:
    from repro.launch.mesh import make_serving_mesh
    from repro.serve.engine import Engine
    from repro.serve.quantized import QuantizedModel

    cfg, qcfg = model_config(), quant_config()
    shared = init_shared(cfg, args.seed)
    with Phase("pack"):
        # the sharded phase alone: every layer RTN-packed (no calibration)
        from repro.models import transformer
        from repro.serve.quantized import quantize_layers_packed
        block = transformer.init_block(layer_key(args.seed, 0), cfg,
                                       jnp.dtype(cfg.dtype))
        layer0 = jax.jit(lambda b: quantize_layers_packed(
            jax.tree_util.tree_map(lambda x: x[None], b), cfg, qcfg))(block)
        del block
        params = pack_model(cfg, qcfg, shared, layer0, args.seed)
        jax.block_until_ready(params)
    qm = QuantizedModel(cfg, qcfg, kernel_mode="auto")
    prompts = make_prompts(cfg, args.seed)
    outs, reports = {}, {}
    for name, mesh in (("single-device", None),
                       ("mesh (data=1, model=4)", make_serving_mesh(1, 4))):
        with Phase(f"serve {name}"):
            engine = Engine(qm, params, serve_config(), mesh=mesh)
            reports[name] = engine.memory_report()
            outs[name] = serve(engine, prompts)
            del engine
        rep = reports[name]
        log(f"  {name}: weights {rep['weight_bytes_per_device'] / 2**20:.1f}"
            f" MiB/device, KV pool {rep['kv_bytes_per_device'] / 2**20:.1f}"
            f" MiB/device over {rep['device_count']} device(s)")
    a, b = outs.values()
    same = sum(x == y for x, y in zip(a, b))
    tok = np.mean([np.mean(np.asarray(x) == np.asarray(y))
                   for x, y in zip(a, b)])
    log(f"  sharded vs single-device: {same}/{len(a)} streams identical, "
        f"token agreement {tok:.4f}")
    if same != len(a):
        raise RuntimeError("sharded serving is not token-identical to "
                           "single-device serving")
    for d in jax.devices()[:4]:
        log(f"peak device memory {d.id}: {peak_memory(d)} (smoke reading)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the full one-chip smoke; 4: only the sharded "
                         "(data=1, model=4) phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    from repro.utils import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x{len(devices)}; "
        f"{ARCH} width, W4A4 g64 weights, kv8 paged cache")
    if args.chips == 4:
        four_chips(args)
    else:
        one_chip(args)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
