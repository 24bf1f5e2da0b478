"""Chunked flash-prefill over the quantized paged/linear KV cache.

Contract under test (DESIGN.md §10), via ``tests/kernel_conformance``:
  * ``ops.flash_prefill`` in interpret mode is BIT-identical to
    ``ref.flash_prefill_ref`` / ``flash_prefill_paged_ref`` under jit for
    every (kv_bits, GQA group, block/page size, ragged offset/chunk_len)
    combination, and matches the XLA fallback + a from-scratch numpy
    softmax to fp tolerance;
  * **splitting invariance**: running a prompt as one big chunk, as many
    small chunks, or one row at a time (== ``flash_decode``) produces
    BIT-identical per-row outputs — the property that makes chunked engine
    admission token-identical to whole-prompt prefill and preemption
    resume exact;
  * ``prefill_chunk`` == whole-prompt ``prefill`` at the model level
    (logits, cache contents, subsequent decode), both cache layouts;
  * the kv8 and kv4 prefill paths carry NO fp (B, S, Hkv, D) cache
    intermediate (jaxpr traversal, XLA fallback as positive control) — the
    regression guard for fused quantize-on-write;
  * pad rows (chunk_len masking) neither write the cache nor attend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kernel_conformance as kc
from repro.configs import get_config
from repro.core.quantizer import QuantConfig
from repro.kernels import ops
from repro.models import build_model
from repro.serve import kv_cache as kvc
from repro.serve.quantized import QuantizedModel, quantize_lm_packed

CHUNK = 6


# ---------------------------------------------------------------------------
# kernel conformance (the acceptance sweep)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
@pytest.mark.parametrize("g", kc.GQA_GROUPS)
@pytest.mark.parametrize("block_kv", kc.KV_BLOCKS)
def test_prefill_interpret_bit_identical_to_ref(kv_bits, g, block_kv):
    """Ragged (offset, chunk_len) in one batch: pure self-attention
    (offset 0), a mid-cache chunk, a tile-straddling chunk, and a
    partially-padded row, all bit-identical through the chunk-end-masked
    grid."""
    b, s, hkv, d = 4, 64, 2, 32
    key = jax.random.PRNGKey(kv_bits * 10 + g + block_kv)
    q, kv, _ = kc.make_cache_inputs(key, b, s, hkv, g, d, kv_bits,
                                    chunk=CHUNK)
    off = jnp.asarray([0, 17, block_kv - 2, s - CHUNK], jnp.int32)
    cl = jnp.asarray([CHUNK, CHUNK, CHUNK, 3], jnp.int32)
    kc.assert_interpret_matches_ref(ops.flash_prefill, q, kv, off, cl,
                                    static=dict(block_kv=block_kv))


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
@pytest.mark.parametrize("g", kc.GQA_GROUPS)
@pytest.mark.parametrize("page_size", kc.KV_BLOCKS)
def test_prefill_paged_interpret_bit_identical_to_ref(kv_bits, g, page_size):
    """Paged sweep over shuffled, non-contiguous page tables — chunk ends
    at a page boundary, mid-page, and inside the first page."""
    b, hkv, d = 3, 2, 32
    lens = [CHUNK, page_size, 2 * page_size + 3]   # totals after the chunk
    key = jax.random.PRNGKey(kv_bits + g + page_size)
    q, kv, pt, _ = kc.make_paged_inputs(key, b, hkv, g, d, page_size, lens,
                                        kv_bits, chunk=CHUNK)
    off = jnp.asarray([0, page_size - CHUNK, 2 * page_size + 3 - CHUNK],
                      jnp.int32)
    cl = jnp.full((b,), CHUNK, jnp.int32)
    kc.assert_interpret_matches_ref(ops.flash_prefill, q, kv, off, cl,
                                    page_table=pt)


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
def test_prefill_matches_fallback_and_oracle(kv_bits):
    """Fused kernel vs the XLA chunk_prefill_attention fallback (mode
    'auto' off-TPU) vs a from-scratch numpy softmax per (row, position)."""
    b, s, hkv, g, d = 3, 48, 2, 2, 32
    q, kv, (k_fp, v_fp) = kc.make_cache_inputs(
        jax.random.PRNGKey(kv_bits), b, s, hkv, g, d, kv_bits, chunk=CHUNK)
    off = jnp.asarray([0, 11, s - CHUNK], jnp.int32)
    cl = jnp.asarray([CHUNK, 4, CHUNK], jnp.int32)
    y = kc.assert_matches_fallback(ops.flash_prefill, q, kv, off, cl,
                                   static=dict(block_kv=16))
    y_np = kc.prefill_softmax_oracle(q, k_fp, v_fp, np.asarray(off),
                                     np.asarray(cl))
    np.testing.assert_allclose(np.asarray(y), y_np, rtol=1e-4, atol=1e-4)


def test_prefill_paged_matches_gather_fallback():
    b, hkv, g, d, ps = 3, 2, 2, 16, 16
    lens = [5, ps + 3, 2 * ps]
    q, kv, pt, deq = kc.make_paged_inputs(jax.random.PRNGKey(5), b, hkv, g,
                                          d, ps, lens, 8, chunk=CHUNK)
    off = jnp.asarray([0, ps - 3, 2 * ps - CHUNK], jnp.int32)
    cl = jnp.asarray([5, CHUNK, CHUNK], jnp.int32)
    y = kc.assert_matches_fallback(ops.flash_prefill, q, kv, off, cl,
                                   page_table=pt)
    k_full, v_full = kc.gathered(deq[0], pt), kc.gathered(deq[1], pt)
    y_np = kc.prefill_softmax_oracle(q, k_full, v_full, np.asarray(off),
                                     np.asarray(cl))
    np.testing.assert_allclose(np.asarray(y), y_np, rtol=1e-4, atol=1e-4)


def test_prefill_interpret_smoke():
    """Tiny single-tile interpret run (the CI fast-lane smoke)."""
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(0), 2, 16, 2, 2, 8, 8,
                                    chunk=4)
    y = ops.flash_prefill(q, kv, jnp.zeros((2,), jnp.int32),
                          jnp.asarray([4, 2], jnp.int32), mode="interpret")
    assert y.shape == (2, 4, 4, 8) and bool(jnp.isfinite(y).all())


def test_prefill_kv4_interpret_smoke():
    """Tiny packed-nibble prefill interpret run (the CI kv4 canary)."""
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(0), 2, 16, 2, 2, 32,
                                    4, chunk=4)
    y = ops.flash_prefill(q, kv, jnp.zeros((2,), jnp.int32),
                          jnp.asarray([4, 2], jnp.int32), mode="interpret")
    assert y.shape == (2, 4, 4, 32) and bool(jnp.isfinite(y).all())


def test_prefill_pad_rows_return_zeros():
    """chunk_len masking: fully-idle rows (chunk_len 0 — the engine's
    decoding slots during another slot's chunk) and partial pad rows
    return zeros on every mode."""
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(1), 2, 32, 2, 2, 16,
                                    8, chunk=4)
    off = jnp.asarray([9, 0], jnp.int32)
    cl = jnp.asarray([0, 2], jnp.int32)
    for mode in ("interpret", "ref", "auto"):
        y = np.asarray(ops.flash_prefill(q, kv, off, cl, mode=mode,
                                         block_kv=16))
        assert (y[0] == 0).all(), mode          # idle row
        assert (y[1, 2:] == 0).all(), mode      # pad tail
        assert (y[1, :2] != 0).any(), mode      # valid rows attend


# ---------------------------------------------------------------------------
# splitting invariance: chunks == whole == decode, bit-for-bit
# ---------------------------------------------------------------------------

def test_prefill_splitting_invariance():
    """One 8-row chunk vs two 4-row chunks vs eight 1-row chunks, and each
    1-row chunk vs flash_decode at that position — the theorem chunked
    serving rests on: trailing fully-masked tiles are exact no-ops in the
    online-softmax state, so a row's result does not depend on which chunk
    delivered it.

    XLA re-fuses the graph per chunk SHAPE, so cross-chunk-size
    comparisons (the whole-vs-chunked engine contract) agree to float32
    ULPs — far below any argmax gap, hence token-identical engines.  A
    1-row prefill chunk vs the 1-token decode kernel (the preempt/resume
    and chunk-boundary contract) agrees to the same ULPs: decode sums q·k
    and P·V on the VPU in its own order (``attend_page``), prefill on the
    MXU (``attend_tile``), by design."""
    b, s, hkv, g, d, c = 2, 64, 2, 2, 16, 8
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(2), b, s, hkv, g, d,
                                    8, chunk=c)
    off = jnp.asarray([0, 23], jnp.int32)
    full = jnp.full((b,), c, jnp.int32)
    run = lambda qq, oo, ll: np.asarray(ops.flash_prefill(
        qq, kv, oo, ll, mode="interpret", block_kv=16))
    y_whole = run(q, off, full)
    half = jnp.full((b,), c // 2, jnp.int32)
    ulps = dict(rtol=3e-6, atol=3e-7)
    np.testing.assert_allclose(y_whole[:, :4], run(q[:, :4], off, half),
                               **ulps)
    np.testing.assert_allclose(y_whole[:, 4:], run(q[:, 4:], off + 4, half),
                               **ulps)
    one = jnp.ones((b,), jnp.int32)
    for i in range(c):
        row = run(q[:, i:i + 1], off + i, one)
        np.testing.assert_allclose(y_whole[:, i:i + 1], row, **ulps)
        # decode's per-page op order is its own: f32 ULPs, like chunk sizes
        dec = np.asarray(ops.flash_decode(q[:, i:i + 1], kv, off + i + 1,
                                          mode="interpret", block_kv=16))
        np.testing.assert_allclose(row, dec, **ulps)


# ---------------------------------------------------------------------------
# model level: prefill_chunk == whole-prompt prefill (both cache layouts)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def micro():
    cfg = get_config("llama-micro")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _chunked_prefill(qm, packed, toks, lengths, cache, chunk):
    """Drive prefill_chunk over a prompt batch in `chunk`-token slices,
    returning (last-valid-row logits, cache) like whole-prompt prefill."""
    bsz, t = toks.shape
    off = jnp.zeros((bsz,), jnp.int32)
    last = None
    for start in range(0, t, chunk):
        sub = toks[:, start:start + chunk]
        if sub.shape[1] < chunk:
            sub = jnp.pad(sub, ((0, 0), (0, chunk - sub.shape[1])))
        cl = jnp.clip(lengths - start, 0, chunk)
        lg, cache = jax.jit(qm.prefill_chunk)(
            packed, {"tokens": sub, "chunk_len": cl}, cache, off)
        off = off + cl
        if last is None:
            last = np.zeros((bsz,) + lg.shape[2:], np.float32)
        for b in range(bsz):
            if int(cl[b]) > 0:
                last[b] = np.asarray(lg[b, int(cl[b]) - 1])
    return last, cache


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
def test_quantized_chunked_prefill_matches_whole(micro, kv_bits):
    """Ragged lengths, 4-token chunks vs one whole-prompt call: last-token
    logits, cache contents and the next decode step agree to f32 ULPs
    (XLA re-fuses per chunk shape — see
    test_prefill_splitting_invariance) with identical argmax, so the
    chunked and whole-prompt ENGINES are token-identical."""
    cfg, _, params = micro
    qcfg = QuantConfig(w_bits=8, a_bits=16, group_size=32, lwc=False,
                       kv_bits=kv_bits)
    packed = quantize_lm_packed(params, cfg, qcfg)
    qm = QuantizedModel(cfg, qcfg, kernel_mode="ref", flash_block_kv=8)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                              cfg.vocab_size)
    lengths = jnp.asarray([12, 7], jnp.int32)
    lg_w, cache_w = qm.prefill(packed, {"tokens": toks, "lengths": lengths},
                               max_len=32)
    lg_c, cache_c = _chunked_prefill(qm, packed, toks, lengths,
                                     qm.init_cache(2, 32), chunk=4)
    ulps = dict(rtol=3e-6, atol=3e-6)
    np.testing.assert_allclose(np.asarray(lg_w[:, 0]), lg_c, **ulps)
    np.testing.assert_array_equal(np.argmax(np.asarray(lg_w[:, 0]), -1),
                                  np.argmax(lg_c, -1))
    for key in cache_w:
        np.testing.assert_allclose(np.asarray(cache_w[key], np.float32),
                                   np.asarray(cache_c[key], np.float32),
                                   err_msg=key, **ulps)
    tok = jnp.argmax(lg_w[:, -1:], -1).astype(jnp.int32)
    d_w, _ = jax.jit(qm.decode_step)(packed, tok, cache_w)
    d_c, _ = jax.jit(qm.decode_step)(packed, tok, cache_c)
    np.testing.assert_allclose(np.asarray(d_w), np.asarray(d_c), **ulps)
    np.testing.assert_array_equal(np.argmax(np.asarray(d_w), -1),
                                  np.argmax(np.asarray(d_c), -1))


def test_quantized_chunked_prefill_paged_matches_linear(micro):
    """Chunked prefill through the page-table cache (ref, tile == page) is
    bit-identical to the linear layout, chunk by chunk, and the caches
    decode identically afterwards."""
    cfg, _, params = micro
    qcfg = QuantConfig(w_bits=8, a_bits=16, group_size=32, lwc=False,
                       kv_bits=8)
    packed = quantize_lm_packed(params, cfg, qcfg)
    qm = QuantizedModel(cfg, qcfg, kernel_mode="ref", flash_block_kv=8)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 10), 0,
                              cfg.vocab_size)
    store = kvc.PagedCache(qm, max_batch=2, max_len=32, page_size=8)
    for slot in range(2):
        assert store.reserve(slot, 10)
    lengths = jnp.full((2,), 10, jnp.int32)
    lg_p, cache_p = _chunked_prefill(qm, packed, toks, lengths, store.cache,
                                     chunk=4)
    lg_l, cache_l = _chunked_prefill(qm, packed, toks, lengths,
                                     qm.init_cache(2, 32), chunk=4)
    np.testing.assert_array_equal(lg_p, lg_l)
    tok = jnp.argmax(jnp.asarray(lg_l)[:, None], -1).astype(jnp.int32)
    d_p, _ = jax.jit(qm.decode_step)(packed, tok, cache_p)
    d_l, _ = jax.jit(qm.decode_step)(packed, tok, cache_l)
    np.testing.assert_array_equal(np.asarray(d_p), np.asarray(d_l))


def test_fp_model_prefill_chunk_matches_dense_prefill(micro):
    """The fp trunk's chunked path (XLA fallback off-TPU) agrees with the
    dense whole-prompt prefill to fp tolerance, and writes the same
    cache."""
    cfg, model, params = micro
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 12), 0,
                              cfg.vocab_size)
    lg_w, cache_w = model.prefill(params, {"tokens": toks}, max_len=32)
    cache = model.init_cache(2, 32)
    off = jnp.zeros((2,), jnp.int32)
    for start in range(0, 12, 6):
        lg, cache = jax.jit(model.prefill_chunk)(
            params, {"tokens": toks[:, start:start + 6]}, cache, off)
        off = off + 6
    np.testing.assert_allclose(np.asarray(lg[:, -1:]), np.asarray(lg_w),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache["k"]),
                               np.asarray(cache_w["k"]), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(cache["len"]),
                                  np.asarray(cache_w["len"]))


def test_unsupported_families_reject_chunked_prefill():
    """Sliding windows (ring-buffer caches) fall outside the chunked
    write/read contract — supports_chunked_prefill gates the engine."""
    import dataclasses as dc
    wcfg = dc.replace(get_config("llama-micro"), window=16)
    model = build_model(wcfg)
    assert not model.supports_chunked_prefill
    with pytest.raises(NotImplementedError, match="chunked"):
        model.prefill_chunk(None, {"tokens": jnp.zeros((1, 4), jnp.int32)},
                            None, jnp.zeros((1,), jnp.int32))


# ---------------------------------------------------------------------------
# no fp cache materialization on the fused quantize-on-write path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [4, 8])
def test_prefill_chunk_quantized_has_no_fp_cache_intermediate(micro,
                                                              kv_bits):
    """Acceptance: the kv8 AND kv4 chunked-prefill paths carry NO fp
    (B, S, Hkv, D) cache intermediate — the chunk is quantized on write
    ((B, C, Hkv, D) fp only, C < S) and attention dequantizes per tile in
    registers.  The XLA-fallback jaxpr is the positive control (it
    dequantizes the full cache)."""
    cfg, _, params = micro
    qcfg = QuantConfig(w_bits=4, a_bits=8, group_size=32, lwc=False,
                       kv_bits=kv_bits)
    packed = quantize_lm_packed(params, cfg, qcfg)
    b, s, c = 2, 24, 6
    d = cfg.resolved_head_dim
    batch = {"tokens": jnp.zeros((b, c), jnp.int32),
             "chunk_len": jnp.full((b,), c, jnp.int32)}
    off = jnp.full((b,), 7, jnp.int32)

    def jaxpr_for(mode):
        qm = QuantizedModel(cfg, qcfg, kernel_mode=mode)
        cache = qm.init_cache(b, s)
        return jax.make_jaxpr(qm.prefill_chunk)(packed, batch, cache,
                                                off).jaxpr

    fused = kc.fp_cache_avals(jaxpr_for("interpret"), s, cfg.num_kv_heads, d)
    assert not fused, f"fp cache intermediates on fused prefill: {fused}"
    control = kc.fp_cache_avals(jaxpr_for("auto"), s, cfg.num_kv_heads, d)
    assert control, "positive control lost: fallback no longer materializes"


@pytest.mark.parametrize("kv_bits", [4, 8])
def test_prefill_chunk_paged_quantized_has_no_logical_cache_gather(micro,
                                                                   kv_bits):
    """Paged mirror: the fused chunked-prefill path never gathers the page
    table into a logical (B, S_log, Hkv, D) fp cache — at kv4 the pool
    stays packed nibbles end to end."""
    cfg, _, params = micro
    qcfg = QuantConfig(w_bits=4, a_bits=8, group_size=32, lwc=False,
                       kv_bits=kv_bits)
    packed = quantize_lm_packed(params, cfg, qcfg)
    b, ps, mpps, c = 2, 8, 3, 6
    d = cfg.resolved_head_dim
    batch = {"tokens": jnp.zeros((b, c), jnp.int32),
             "chunk_len": jnp.full((b,), c, jnp.int32)}
    off = jnp.zeros((b,), jnp.int32)

    def jaxpr_for(mode):
        qm = QuantizedModel(cfg, qcfg, kernel_mode=mode)
        store = kvc.PagedCache(qm, max_batch=b, max_len=ps * mpps,
                               page_size=ps)
        for slot in range(b):
            store.reserve(slot, c)
        return jax.make_jaxpr(qm.prefill_chunk)(packed, batch, store.cache,
                                                off).jaxpr

    s_log = ps * mpps
    fused = kc.fp_cache_avals(jaxpr_for("interpret"), s_log,
                              cfg.num_kv_heads, d)
    assert not fused, f"logical-cache fp intermediates: {fused}"
    control = kc.fp_cache_avals(jaxpr_for("auto"), s_log, cfg.num_kv_heads,
                                d)
    assert control, "positive control lost: fallback no longer gathers"


def test_pad_rows_do_not_write_cache(micro):
    """chunk_len-masked rows leave the cache untouched (the engine decodes
    other slots between chunks — their rows must never be clobbered)."""
    cfg, _, params = micro
    qcfg = QuantConfig(w_bits=8, a_bits=16, group_size=32, lwc=False,
                       kv_bits=8)
    packed = quantize_lm_packed(params, cfg, qcfg)
    qm = QuantizedModel(cfg, qcfg, kernel_mode="ref", flash_block_kv=8)
    cache = qm.init_cache(2, 16)
    marker = jnp.full_like(cache["k"], 7)
    cache = dict(cache, k=marker, v=marker,
                 len=jnp.asarray([5, 0], jnp.int32))
    batch = {"tokens": jnp.zeros((2, 4), jnp.int32),
             "chunk_len": jnp.asarray([0, 4], jnp.int32)}
    _, out = jax.jit(qm.prefill_chunk)(packed, batch, cache,
                                       jnp.asarray([5, 0], jnp.int32))
    # row 0 (idle, chunk_len 0): cache bytes and len unchanged
    np.testing.assert_array_equal(np.asarray(out["k"][:, 0]),
                                  np.asarray(marker[:, 0]))
    assert int(out["len"][0]) == 5
    # row 1 wrote exactly positions 0..3
    assert int(out["len"][1]) == 4
    assert bool(jnp.any(out["k"][:, 1, :4] != 7))
    np.testing.assert_array_equal(np.asarray(out["k"][:, 1, 4:]),
                                  np.asarray(marker[:, 1, 4:]))
