"""Fused flash-decode over the quantized KV cache: parity + capacity.

Contract under test (DESIGN.md §8), asserted through the shared
``tests/kernel_conformance`` harness:
  * ``ops.flash_decode`` in interpret mode is BIT-identical to
    ``ref.flash_decode_ref`` under jit for every (kv_bits, GQA group,
    block_kv, ragged cur_len) combination;
  * both match ``attn_lib.decode_attention`` and a from-scratch softmax
    oracle to fp tolerance;
  * ``QuantizedModel.decode_step`` on the fused path never materializes the
    full fp KV cache (asserted on the jaxpr);
  * a full cache is never corrupted by further decode steps (writes drop,
    ``len`` saturates).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kernel_conformance as kc
from repro.configs import get_config
from repro.core.quantizer import QuantConfig
from repro.kernels import flash_decode as fd
from repro.kernels import ops
from repro.models import build_model
from repro.serve.quantized import QuantizedModel, quantize_lm_packed


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
@pytest.mark.parametrize("g", kc.GQA_GROUPS)
@pytest.mark.parametrize("block_kv,rows", [
    *(pytest.param(n, "ragged", id=str(n)) for n in kc.KV_BLOCKS),
    *(pytest.param(n, "block_edges", id=f"{n}-block_edges")
      for n in kc.KV_BLOCKS)])
def test_flash_decode_interpret_bit_identical_to_ref(kv_bits, g, block_kv,
                                                     rows):
    """Ragged cur_len in one batch: near-empty, mid-tile, and full-cache
    rows; or rows at the edges of the kernel's blocks of tiles (empty,
    one token, a tile boundary, a block boundary and one past it, a tile
    count no multiple of the block) — all bit-identical through the
    length-masked grid."""
    b, s, hkv, d = 3, 64, 2, 32
    cur = [1, 37, s]
    if rows == "block_edges":
        ppb = max(1, fd.BLOCK_TOKENS // (block_kv * g))
        s = (2 * ppb + 1) * block_kv
        cur = [0, 1, block_kv, ppb * block_kv, ppb * block_kv + 1, s - 5]
        b = len(cur)
    key = jax.random.PRNGKey(kv_bits * 10 + g)
    q, kv, _ = kc.make_cache_inputs(key, b, s, hkv, g, d, kv_bits)
    if rows == "block_edges":
        pools = [x.reshape(-1, block_kv, *x.shape[2:]) for x in kv]
        assert fd.pages_per_block(pools, g, s // block_kv) == ppb
    kc.assert_interpret_matches_ref(ops.flash_decode, q, kv,
                                    jnp.array(cur, jnp.int32),
                                    static=dict(block_kv=block_kv))


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
@pytest.mark.parametrize("g", kc.GQA_GROUPS)
def test_flash_decode_matches_fallback_and_oracle(kv_bits, g):
    """Kernel vs decode_attention (the portable fallback, via mode='auto'
    off-TPU) vs a from-scratch numpy softmax — three independent paths."""
    b, s, hkv, d = 3, 48, 2, 32
    key = jax.random.PRNGKey(kv_bits + g)
    q, kv, (k_fp, v_fp) = kc.make_cache_inputs(key, b, s, hkv, g, d, kv_bits)
    cur = jnp.array([1, 23, s - 1], jnp.int32)
    y_int = kc.assert_matches_fallback(ops.flash_decode, q, kv, cur,
                                       static=dict(block_kv=16))
    y_np = kc.softmax_oracle(q, k_fp, v_fp, np.asarray(cur))
    np.testing.assert_allclose(np.asarray(y_int), y_np, rtol=1e-4, atol=1e-4)


def test_flash_decode_interpret_smoke():
    """Tiny single-tile interpret run (the CI fast-lane smoke)."""
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(0), 2, 16, 2, 2, 8, 8)
    y = ops.flash_decode(q, kv, jnp.array([3, 16], jnp.int32),
                         mode="interpret")
    assert y.shape == (2, 1, 4, 8) and bool(jnp.isfinite(y).all())


def test_flash_decode_kv4_interpret_smoke():
    """Tiny packed-nibble interpret run (the CI kv4 canary): ragged
    cur_len lands mid-block so the scale-broadcast masking is exercised."""
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(0), 2, 16, 2, 2, 32, 4)
    assert kv[0].shape[-1] == 16 and kv[2].dtype == jnp.bfloat16
    y = ops.flash_decode(q, kv, jnp.array([5, 16], jnp.int32),
                         mode="interpret")
    assert y.shape == (2, 1, 4, 32) and bool(jnp.isfinite(y).all())


def test_flash_decode_zero_length_rows_return_zeros():
    """cur_len == 0 visits no KV tile: zeros for that row on EVERY mode —
    including the auto/XLA fallback, where an all-masked softmax would
    otherwise emit the uniform mean of the slots. Decode always passes
    cur_len + 1 >= 1; this pins the edge."""
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(1), 2, 32, 2, 2, 16, 8)
    cur = jnp.array([0, 32], jnp.int32)
    for mode in ("interpret", "ref", "auto"):
        y = ops.flash_decode(q, kv, cur, mode=mode, block_kv=16)
        np.testing.assert_array_equal(np.asarray(y[0]),
                                      np.zeros_like(np.asarray(y[0])))
        assert bool(jnp.any(y[1] != 0))


def test_flash_decode_clamps_block_to_ragged_max_len():
    """S=56 is no multiple of the default page: the dispatcher cuts it into
    14-token pages, the largest that divide it, and still matches the
    fallback."""
    b, s, hkv, g, d = 2, 56, 2, 2, 16
    assert fd.linear_page(s, fd.DEFAULT_BLOCK_KV) == 14
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(2), b, s, hkv, g, d, 8)
    cur = jnp.array([5, 56], jnp.int32)
    kc.assert_matches_fallback(ops.flash_decode, q, kv, cur)


@pytest.mark.parametrize("s,page", [(53, 1), (1208, 8)])
def test_flash_decode_ragged_max_len_cuts_whole_pages(s, page):
    """A prime S and a 1000 + 200 token run's S (no multiple of 16) cut
    into whole pages no longer than the default, never one page of the
    whole cache; the block stays within its slot cap, and the result is
    the oracle's bit for bit and the fallback's to fp tolerance."""
    b, hkv, g, d = 2, 2, 1, 16
    assert fd.linear_page(s, fd.DEFAULT_BLOCK_KV) == page
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(s), b, s, hkv, g, d, 8)
    pools = [x.reshape(-1, page, *x.shape[2:]) for x in kv]
    assert fd.pages_per_block(pools, g, s // page) == fd.MAX_PAGES_PER_BLOCK
    cur = jnp.array([s - page - 1, s], jnp.int32)
    kc.assert_interpret_matches_ref(ops.flash_decode, q, kv, cur)
    kc.assert_matches_fallback(ops.flash_decode, q, kv, cur)


def test_pages_per_block_refuses_page_over_vmem():
    """A page whose double-buffered pools alone pass the block's VMEM
    budget is refused, not run as a block of one page."""
    page = jax.ShapeDtypeStruct((4, 4096, 40, 128), jnp.int8)
    scales = jax.ShapeDtypeStruct((4, 4096, 40), jnp.float32)
    with pytest.raises(AssertionError, match="BLOCK_VMEM"):
        fd.pages_per_block([page, page, scales, scales], 1, 4)


def test_flash_decode_rejects_bad_inputs():
    q, kv, _ = kc.make_cache_inputs(jax.random.PRNGKey(3), 2, 16, 2, 1, 8, 16)
    cur = jnp.array([4, 8], jnp.int32)
    with pytest.raises(TypeError, match="kv"):
        ops.flash_decode(q, kv + (kv[0],), cur)
    with pytest.raises(ValueError, match="one-token"):
        ops.flash_decode(jnp.concatenate([q, q], axis=1), kv, cur)


# ---------------------------------------------------------------------------
# serving integration: no full-cache dequant, capacity semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [4, 8])
def test_decode_step_quantized_has_no_full_cache_dequantize(kv_bits):
    """Acceptance: kv_bits=8 AND kv_bits=4 decode on the fused path carry
    NO fp (B, S, Hkv, D) intermediate — the int8 / packed-nibble cache is
    dequantized per tile in registers only (kv4's bf16 block scales are
    (B, S, Hkv, D//32), far from the matcher's (S, Hkv, D) tail). The
    `auto` (off-TPU decode_attention fallback) jaxpr is the positive
    control: it DOES materialize the fp cache, proving the traversal would
    catch one."""
    cfg = get_config("llama-micro")
    qcfg = QuantConfig(w_bits=4, a_bits=8, group_size=32, lwc=False,
                       kv_bits=kv_bits)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    packed = quantize_lm_packed(params, cfg, qcfg)
    b, s = 2, 24
    d = cfg.resolved_head_dim
    tok = jnp.zeros((b, 1), jnp.int32)

    def jaxpr_for(mode):
        qm = QuantizedModel(cfg, qcfg, kernel_mode=mode)
        cache = qm.init_cache(b, s)
        cache = dict(cache, len=jnp.full((b,), 7, jnp.int32))
        return jax.make_jaxpr(qm.decode_step)(packed, tok, cache).jaxpr

    fused = kc.fp_cache_avals(jaxpr_for("interpret"), s, cfg.num_kv_heads, d)
    assert not fused, f"full-cache fp intermediates on fused path: {fused}"
    # tile-mirroring ref at block_kv < S is also materialization-free
    control = kc.fp_cache_avals(jaxpr_for("auto"), s, cfg.num_kv_heads, d)
    assert control, "positive control lost: fallback no longer materializes"


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
def test_decode_past_capacity_drops_write_and_saturates(kv_bits):
    """A decode step on a full cache must not clobber slot S-1 and must
    leave `len` saturated at S (observable exhaustion, no corruption)."""
    cfg = get_config("llama-micro")
    qcfg = QuantConfig(w_bits=8, a_bits=16, group_size=32, lwc=False,
                       kv_bits=kv_bits)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    packed = quantize_lm_packed(params, cfg, qcfg)
    qm = QuantizedModel(cfg, qcfg, kernel_mode="ref")
    b, s = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(4), (b, s), 0,
                              cfg.vocab_size)
    _, cache = qm.prefill(packed, {"tokens": toks}, max_len=s)
    assert int(cache["len"][0]) == s  # full from prefill
    tok = jnp.zeros((b, 1), jnp.int32)
    logits, cache2 = jax.jit(qm.decode_step)(packed, tok, cache)
    np.testing.assert_array_equal(np.asarray(cache2["k"]),
                                  np.asarray(cache["k"]))
    np.testing.assert_array_equal(np.asarray(cache2["v"]),
                                  np.asarray(cache["v"]))
    np.testing.assert_array_equal(np.asarray(cache2["len"]),
                                  np.full((b,), s))
    assert bool(jnp.isfinite(logits).all())


def test_fp_model_decode_past_capacity_drops_write_and_saturates():
    """Same capacity contract for the fp serving model."""
    cfg = get_config("llama-micro")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(5), (b, s), 0,
                              cfg.vocab_size)
    _, cache = model.prefill(params, {"tokens": toks}, max_len=s)
    tok = jnp.zeros((b, 1), jnp.int32)
    logits, cache2 = jax.jit(model.decode_step)(params, tok, cache)
    np.testing.assert_array_equal(np.asarray(cache2["k"]),
                                  np.asarray(cache["k"]))
    np.testing.assert_array_equal(np.asarray(cache2["len"]),
                                  np.full((b,), s))
    assert bool(jnp.isfinite(logits).all())


def test_quantized_decode_full_cache_attends_everything():
    """At cur_len == S the fused path must attend ALL stored positions
    (regression guard for an off-by-one in the tile mask)."""
    b, s, hkv, g, d = 2, 32, 2, 2, 16
    q, kv, (k_fp, v_fp) = kc.make_cache_inputs(jax.random.PRNGKey(6), b, s,
                                               hkv, g, d, 8)
    cur = jnp.full((b,), s, jnp.int32)
    y = ops.flash_decode(q, kv, cur, mode="interpret", block_kv=16)
    y_np = kc.softmax_oracle(q, k_fp, v_fp, np.asarray(cur))
    np.testing.assert_allclose(np.asarray(y), y_np, rtol=1e-4, atol=1e-4)
