"""Packed low-bit serving for dense/MoE LMs (the paper's deployment target).

The whole pipeline speaks ONE quantized-weight representation,
:class:`repro.core.qtensor.QTensor` (packed sub-byte codes + per-group
scale/zp, pytree-registered):

    calibrate : finalize_block(deploy="packed") quantizes each transformed
                linear ONCE on its LWC-learned grid and emits QTensor leaves
    pack      : quantize_lm_packed passes a calibrated tree through untouched
                (no re-quantization); a raw fp tree is direct-quantized onto
                the identical RTN grid
    serve     : QuantizedModel reads QTensor fields; matmuls route through
                repro.kernels.ops.dequant_matmul (Pallas on TPU, reference
                math elsewhere — bit-identical results)

so ``QuantizedModel.prefill/decode_step`` evaluate exactly the grid the
calibration loss optimized — one rounding end-to-end (paper §3.3
zero-overhead deployment). Full-matrix transform sites that cannot merge
into a norm keep their activation-side factor as a small ``attn_t`` /
``mlp_t`` = {"a_inv", optional "shift"} applied after the norm; every large
linear stays packed (no fp-weight fallback in the decode path).

Weight-activation serving (``qcfg.a_bits < 16``, the paper's W4A4 Table 3
setting): every packed matmul routes through ``kernels.ops.quant_matmul``,
which fuses per-token dynamic activation quantization into the int-MXU
kernel — activations hit the MXU as int8 lanes, never materialized in int8
in HBM, and there is no fp-activation fallback in the decode path.
``qcfg.kv_bits < 16`` additionally quantizes the KV cache on write (prefill
and decode): ``kv_bits=8`` stores int8 codes with a per-(token, head)
float32 scale (~2x cache memory), ``kv_bits=4`` stores packed int4 nibbles
(two codes per byte along head_dim) with one bf16 scale per block of 32
values (~4x codes, and scale overhead down from 4 B per (token, head) row
to 2 B per 32 values). Decode attention reads the
cache **as stored** through ``kernels.ops.flash_decode`` (DESIGN.md §8): the
fused Pallas kernel dequantizes per KV tile in registers and bounds work to
the valid ``cur_len`` tiles — no full-cache fp materialization, no
``max_len``-proportional HBM reads (w4a4kv8 + flash numbers in
EXPERIMENTS.md §Perf / BENCH_decode.json).

Cache capacity: a decode step past ``max_len`` does NOT corrupt the cache —
the overflowing K/V write is dropped (slot ``max_len - 1`` keeps its token)
and ``cache["len"]`` saturates at ``max_len``, so exhaustion is observable
as ``len == max_len``; the Engine retires sequences before that point.

Failure-model contract (DESIGN.md §12): quantization conserves poison, it
never launders it.  Int8/int4 *codes* cannot encode NaN, but the fp scale
factor can — ``_kv_quantize`` of a non-finite K/V row yields a NaN scale
(``max(|NaN|) = NaN``), so dequantizing that row is NaN again and the
corruption surfaces in that row's attention output and final logits.
Because every batch row flows through per-row attention/norms/matmuls, a
non-finite value in one sequence cannot reach a co-batched sequence's
logits — which is what lets the Engine's per-row ``isfinite`` check
quarantine exactly the poisoned slot (pinned by
tests/test_engine_faults.py::test_kv_quantize_conserves_nan).

``QuantizedModel`` exposes the same ``decode_step`` / ``prefill`` /
``init_cache`` interface as ``repro.models.Model`` so the continuous-
batching ``Engine`` and the dry-run lower it unchanged.

Why this matters at scale: bf16 weights of a 132B MoE do not fit TP-only on
a 256-chip v5e pod (16.5 GiB/device), forcing FSDP weight gathers on *every
decode step*. At w4 the same weights are 4.1 GiB/device — resident, no
per-step collective. That swing is quantified in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qtensor import QTensor, tree_has_qtensor
from repro.core.quantizer import QuantConfig, quantize_codes
from repro.kernels import ops
from repro.kernels.quantize_pack import (KV_BLOCK, kv4_check_head_dim,
                                         kv4_quantize)
from repro.models import layers
from repro.models.model import build_model
from repro.models.transformer import sinusoidal_at

PACKED_WEIGHTS = ("wq", "wk", "wv", "wo")
PACKED_MLP = ("w_gate", "w_up", "w_down")


def quantize_lm_packed(params: dict, cfg: ModelConfig, qcfg: QuantConfig
                       ) -> dict:
    """Adapter to the packed-serving tree: QTensor leaves for every linear.

    * A tree that already holds QTensor leaves (output of
      ``quantize_dense_model(..., deploy="packed")``) passes through
      untouched — the calibrated codes ARE the serving codes, there is no
      second quantization.
    * A raw fp tree is direct-quantized (RTN grid, identical math to
      ``fake_quant_weight`` with ``lwc=False``) onto the same QTensor
      representation.

    Embeddings / norms / biases / router stay fp (standard).
    """
    if tree_has_qtensor(params):
        return params
    out = {"embed": params["embed"], "ln_f": params["ln_f"]}
    if "head" in params:
        out["head"] = params["head"]
    out["layers"] = quantize_layers_packed(params["layers"], cfg, qcfg)
    return out


def quantize_layers_packed(lp: dict, cfg: ModelConfig, qcfg: QuantConfig
                           ) -> dict:
    """The layer half of :func:`quantize_lm_packed`: a stacked fp layers
    subtree (any number of layers) -> its packed serving subtree.  Lets a
    caller pack a model layer by layer without ever holding the fp tree
    of the whole model."""
    new_lp = {}
    for k in ("ln_attn", "ln_mlp", "attn_t", "mlp_t"):
        if k in lp:
            new_lp[k] = lp[k]
    for k in ("bq", "bk", "bv"):
        if k in lp:
            new_lp[k] = lp[k]
    for k in PACKED_WEIGHTS:
        new_lp[k] = quantize_codes(lp[k], qcfg)
    if cfg.num_experts:
        new_lp["moe"] = {"router": lp["moe"]["router"]}
        for k in PACKED_MLP:
            if k in lp["moe"]:
                new_lp["moe"][k] = quantize_codes(lp["moe"][k], qcfg)
    else:
        new_lp["mlp"] = {}
        for k in PACKED_MLP:
            if k in lp["mlp"]:
                new_lp["mlp"][k] = quantize_codes(lp["mlp"][k], qcfg)
        for k in ("b_gate", "b_up"):
            if k in lp["mlp"]:
                new_lp["mlp"][k] = lp["mlp"][k]
    return new_lp


def _act_transform(t: Optional[dict], h: jax.Array) -> jax.Array:
    """Apply a full-site activation factor h_t = (h - shift) @ inv(A)."""
    if t is None:
        return h
    if "shift" in t:
        h = h - t["shift"].astype(h.dtype)
    return h @ t["a_inv"].astype(h.dtype)


def _kv_quantize(x: jax.Array, kv_bits: int
                 ) -> tuple[jax.Array, jax.Array]:
    """Quantize-on-write entry point for the KV cache, both formats.

    ``kv_bits=8``: symmetric per-(token, head) int8 —
    x (..., H, D) -> (codes int8 (..., H, D), scale f32 (..., H)).

    ``kv_bits=4``: block-32 microscaling sub-byte layout
    (:func:`repro.kernels.quantize_pack.kv4_quantize`) —
    x (..., H, D) -> (packed nibbles int8 (..., H, D//2), scales bf16
    (..., H, D//32)).  The cache write helpers are generic over trailing
    dims, so both layouts ride the same destination formulas; the kernels
    tell them apart by the scale's rank (kv4 scales are code-rank).
    """
    if kv_bits == 4:
        return kv4_quantize(x)
    xf = x.astype(jnp.float32)
    qmax = 2.0 ** (kv_bits - 1) - 1.0
    bound = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8)
    scale = bound / qmax
    q = jnp.clip(jnp.round(xf / scale[..., None]), -qmax - 1.0, qmax)
    return q.astype(jnp.int8), scale


@dataclasses.dataclass(frozen=True)
class QuantizedModel:
    """Model-compatible wrapper serving QTensor trees (dense/MoE).

    ``flash_block_kv`` overrides the flash-decode KV tile size (None =
    kernel default, clamped to a single tile for miniature caches)."""
    cfg: ModelConfig
    qcfg: QuantConfig
    kernel_mode: str = "auto"
    flash_block_kv: Optional[int] = None

    def __post_init__(self):
        # int-lane widths only: 9..15 would wrap on the int8 cast
        if self.qcfg.a_bits < 16 and not 2 <= self.qcfg.a_bits <= 8:
            raise ValueError(f"a_bits={self.qcfg.a_bits}: use 2..8 or >= 16")
        # the cache has exactly three layouts: fp (>= 16), int8 codes +
        # per-(token, head) f32 scales (8), packed nibbles + block-32 bf16
        # scales (4) — anything else would silently serve a layout no
        # kernel reads
        if self.qcfg.kv_bits < 16 and self.qcfg.kv_bits not in (4, 8):
            raise ValueError(f"kv_bits={self.qcfg.kv_bits}: use 4 (packed "
                             "int4 + block-32 bf16 scales), 8 (int8 + "
                             "per-(token, head) f32 scales), or >= 16 (fp)")
        if self.qcfg.kv_bits == 4:
            kv4_check_head_dim(self.cfg.resolved_head_dim)
        if self.cfg.window:
            # the packed decode uses a linear drop-at-capacity cache and the
            # flash kernel masks a contiguous valid prefix — ring-buffer
            # wrap/masking (see transformer.apply_block_decode) is not
            # implemented here
            raise NotImplementedError(
                "packed serving does not support sliding-window attention")

    def _mm(self, x: jax.Array, qt: QTensor) -> jax.Array:
        if self.qcfg.a_bits < 16:
            # W·A path: fused dynamic act-quant + int-MXU kernel — no
            # fp-activation fallback anywhere in prefill or decode
            return ops.quant_matmul(x, qt, a_bits=self.qcfg.a_bits,
                                    mode=self.kernel_mode)
        return ops.dequant_matmul(x, qt, mode=self.kernel_mode)

    @property
    def _kv_quantized(self) -> bool:
        return self.qcfg.kv_bits < 16

    @property
    def supports_padded_prefill(self) -> bool:
        """Bucketed engine prefill (end-padded prompts + per-sequence
        ``lengths``) is exact for the causal transformer trunk."""
        return True

    @property
    def supports_chunked_prefill(self) -> bool:
        """Chunked engine admission (``ServeConfig.prefill_chunk > 0``):
        every prefill already routes through :meth:`prefill_chunk`, so
        chunked and whole-prompt admission are token-identical."""
        return True

    # cache API identical to Model (quantized serving narrows/splits the
    # trailing dims when kv_bits < 16 — see models.transformer.init_cache)
    def init_cache(self, batch: int, max_len: int) -> dict:
        model = build_model(self.cfg)
        if not self._kv_quantized:
            return model.init_cache(batch, max_len)
        # shape-only query — materializing the fp cache here would cost the
        # very allocation the quantized cache exists to avoid
        base = jax.eval_shape(lambda: model.init_cache(batch, max_len))
        kshape = base["k"].shape
        if self.qcfg.kv_bits == 4:
            d = kshape[-1]
            return {"k": jnp.zeros(kshape[:-1] + (d // 2,), jnp.int8),
                    "v": jnp.zeros(kshape[:-1] + (d // 2,), jnp.int8),
                    "k_scale": jnp.zeros(kshape[:-1] + (d // KV_BLOCK,),
                                         jnp.bfloat16),
                    "v_scale": jnp.zeros(kshape[:-1] + (d // KV_BLOCK,),
                                         jnp.bfloat16),
                    "len": jnp.zeros((batch,), jnp.int32)}
        return {"k": jnp.zeros(kshape, jnp.int8),
                "v": jnp.zeros(kshape, jnp.int8),
                "k_scale": jnp.zeros(kshape[:-1], jnp.float32),
                "v_scale": jnp.zeros(kshape[:-1], jnp.float32),
                "len": jnp.zeros((batch,), jnp.int32)}

    def init_paged_cache(self, batch: int, num_pages: int, page_size: int,
                         max_pages_per_seq: int):
        """Paged pool cache (``repro.serve.kv_cache.PagedKVCache``): code
        pages + scale pages when ``kv_bits < 16`` (int8 + f32 at kv8,
        packed nibbles + block-32 bf16 at kv4), fp pages otherwise.  Same
        per-token layout as the linear cache, page-blocked so pool memory
        tracks live tokens instead of ``batch * max_len``."""
        from repro.serve.kv_cache import make_paged_cache
        cfg = self.cfg
        return make_paged_cache(
            num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, batch=batch,
            num_pages=num_pages, page_size=page_size,
            max_pages_per_seq=max_pages_per_seq, dtype=cfg.dtype,
            quantized=self._kv_quantized, kv_bits=self.qcfg.kv_bits)

    def cache_specs(self, batch: int, max_len: int) -> dict:
        cache = jax.eval_shape(lambda: self.init_cache(batch, max_len))
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)

    def paged_cache_specs(self, batch: int, num_pages: int, page_size: int,
                          max_pages_per_seq: int):
        from repro.serve import kv_cache
        return kv_cache.paged_cache_specs(self, batch, num_pages,
                                          page_size, max_pages_per_seq)

    # ------------------------------------------------------------------
    # prefill: chunked forward over the cache AS STORED
    # ------------------------------------------------------------------
    def prefill(self, params, batch, max_len: int):
        """Full-prompt prefill == ONE chunk of :meth:`prefill_chunk` at
        offset 0.

        ``batch["lengths"]`` (B,) int32, if present, marks per-sequence
        valid prompt lengths for bucketed engine prefill: prompts are
        end-padded to a shared bucket, so causality keeps every valid
        position exact; logits are gathered at ``lengths - 1`` and the
        cache ``len`` records the true lengths.  Pad positions neither
        write the cache nor attend (chunk-row masking), and prompt tokens
        attend the cache exactly as decode will (dequantized int8 codes at
        ``kv_bits < 16``, never a transient fp copy) — so whole-prompt and
        C-token chunked admission are token-identical (same-shape calls
        bit-identical, cross-shape to f32 ULPs; see
        ``kernels.ops.flash_prefill``).  Returns (last-valid-token logits
        (B, 1, vocab), cache)."""
        tokens = batch["tokens"]
        bsz, t = tokens.shape
        lengths = batch.get("lengths")
        lengths = (jnp.full((bsz,), t, jnp.int32) if lengths is None
                   else jnp.asarray(lengths, jnp.int32))
        cache = self.init_cache(bsz, max(max_len, t))
        x, cache = self._forward_chunk(
            params, tokens, lengths, cache, jnp.zeros((bsz,), jnp.int32))
        # gather the last valid hidden row BEFORE the head: whole-prompt
        # prefill never materializes (B, T, vocab) logits
        with jax.named_scope("head"):
            x = x[jnp.arange(bsz), lengths - 1][:, None]
            return self._head(params, x), cache

    def prefill_chunk(self, params, batch, cache, offset, *,
                      last_only: bool = False):
        """One C-token prefill chunk on packed weights, written into (and
        attending) ``cache`` — linear dict or ``PagedKVCache``.

        ``batch`` = {"tokens": (B, C), optional "chunk_len": (B,) valid
        rows (idle engine rows pass 0)}; ``offset`` (B,) int32 is each
        sequence's pre-chunk cache length.  Quantize-on-write is fused into
        the chunk: at ``kv_bits < 16`` the chunk's K/V enter the cache as
        int8 codes + per-(token, head) f32 scales and attention reads the
        codes back through ``ops.flash_prefill`` — the only fp K/V
        intermediate is the (B, C, Hkv, D) chunk itself, never the
        (B, S, Hkv, D) cache (jaxpr-pinned).  Returns
        (logits (B, C, vocab), new_cache) with ``len``/``lens`` advanced to
        ``offset + chunk_len`` — or logits (B, 1, vocab) gathered at the
        last valid row (pre-head, like :meth:`prefill`) when ``last_only``
        (static): the engine's chunk steps only ever read that row, so
        they skip the (B, C, vocab) head matmul."""
        tokens = batch["tokens"]
        bsz, c = tokens.shape
        chunk_len = batch.get("chunk_len")
        chunk_len = (jnp.full((bsz,), c, jnp.int32) if chunk_len is None
                     else jnp.asarray(chunk_len, jnp.int32))
        x, cache = self._forward_chunk(params, tokens, chunk_len, cache,
                                       offset)
        with jax.named_scope("head"):
            if last_only:
                x = x[jnp.arange(bsz),
                      jnp.maximum(chunk_len - 1, 0)][:, None]
            return self._head(params, x), cache

    def _forward_chunk(self, params, tokens, chunk_len, cache, offset):
        """Chunk trunk shared by :meth:`prefill` and :meth:`prefill_chunk`:
        embed → scan blocks (cache write + as-stored attention) — returns
        the pre-``ln_f`` hidden states (B, C, d) and the updated cache, so
        whole-prompt prefill can gather one row before the vocab matmul
        while the chunked engine keeps per-row logits."""
        from repro.serve.kv_cache import PagedKVCache
        cfg = self.cfg
        bsz, c = tokens.shape
        offset = jnp.asarray(offset, jnp.int32)
        chunk_len = jnp.asarray(chunk_len, jnp.int32)
        paged = isinstance(cache, PagedKVCache)
        with jax.named_scope("embed"):
            pos = offset[:, None] + jnp.arange(c)[None, :]
            x = jnp.take(params["embed"], tokens, axis=0)
            if cfg.rope_theta == 0:
                x = x + sinusoidal_at(pos, cfg.d_model).astype(x.dtype)
        if paged:
            kv_in = (cache.k, cache.v)
            if self._kv_quantized:
                kv_in += (cache.k_scale, cache.v_scale)
            pt, psz = cache.page_table, cache.page_size
        else:
            kv_in = (cache["k"], cache["v"])
            if self._kv_quantized:
                kv_in += (cache["k_scale"], cache["v_scale"])
            pt, psz = None, None

        def body(h, xs):
            lp, kv = xs[0], xs[1:]
            h, kv = self._block_prefill_chunk(lp, h, kv, pos, offset,
                                              chunk_len, pt, psz)
            return h, kv

        x, kv_new = self._decoder(body, x, params["layers"], kv_in)
        if paged:
            new = {"k": kv_new[0], "v": kv_new[1],
                   "lens": jnp.minimum(offset + chunk_len, cache.capacity)}
            if self._kv_quantized:
                new["k_scale"], new["v_scale"] = kv_new[2], kv_new[3]
            return x, dataclasses.replace(cache, **new)
        s = cache["k"].shape[2]
        new_cache = {"k": kv_new[0], "v": kv_new[1],
                     "len": jnp.minimum(offset + chunk_len, s)}
        if self._kv_quantized:
            new_cache["k_scale"], new_cache["v_scale"] = kv_new[2], kv_new[3]
        return x, new_cache

    def _block_prefill_chunk(self, p, x, kv, pos, offset, chunk_len,
                             page_table, page_size):
        from repro.serve.kv_cache import (chunk_write_dest,
                                          linear_chunk_write_dest,
                                          paged_chunk_write)
        b, c = x.shape[0], x.shape[1]
        q, k, v = self._qkv(p, x, pos)
        with jax.named_scope("kv_write"):
            if page_table is not None:
                num_pages = kv[0].shape[0]
                dest = chunk_write_dest(page_table, offset, chunk_len, c,
                                        page_size, num_pages)
                write = lambda pool, val: paged_chunk_write(pool, val, dest)
            else:
                # pad rows / past-capacity positions resolve OOB: scatter
                # drops
                dest = linear_chunk_write_dest(offset, chunk_len, c,
                                               kv[0].shape[1])
                bidx = jnp.arange(b)[:, None]
                write = lambda ct, val: ct.at[bidx, dest].set(
                    val.astype(ct.dtype))
            if len(kv) == 4:
                # fused quantize-on-write: the chunk enters the cache as
                # codes
                kc, vc, ksc, vsc = kv
                kq, k_s = _kv_quantize(k, self.qcfg.kv_bits)
                vq, v_s = _kv_quantize(v, self.qcfg.kv_bits)
                kv = (write(kc, kq), write(vc, vq),
                      write(ksc, k_s), write(vsc, v_s))
            else:
                kc, vc = kv
                kv = (write(kc, k), write(vc, v))
        # attention reads the cache AS STORED (prefix + this chunk):
        # in-register tile dequant, chunk-end-masked KV grid — bit-identical
        # per row to flash_decode over the same cache (resume exactness)
        with jax.named_scope("attn.flash"):
            out = ops.flash_prefill(q, kv, offset, chunk_len,
                                    block_kv=self.flash_block_kv,
                                    page_table=page_table,
                                    mode=self.kernel_mode)
        return self._out_and_mlp(p, x, out), kv

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def decode_step(self, params, token, cache):
        from repro.serve.kv_cache import PagedKVCache
        if isinstance(cache, PagedKVCache):
            return self._decode_step_paged(params, token, cache)
        cur_len = cache["len"]
        x = self._embed_token(params, token, cur_len)
        if self._kv_quantized:
            kv_in = (cache["k"], cache["v"],
                     cache["k_scale"], cache["v_scale"])
        else:
            kv_in = (cache["k"], cache["v"])

        def body(h, xs):
            lp, kv = xs[0], xs[1:]
            h, kv = self._block_decode(lp, h, kv, cur_len)
            return h, kv

        x, kv_new = self._decoder(body, x, params["layers"], kv_in)
        with jax.named_scope("head"):
            logits = self._head(params, x)
        # len saturates at capacity: a full cache is observable (len == S),
        # never silently wrapped or overgrown
        s = cache["k"].shape[2]
        new_cache = {"k": kv_new[0], "v": kv_new[1],
                     "len": jnp.minimum(cur_len + 1, s)}
        if self._kv_quantized:
            new_cache["k_scale"], new_cache["v_scale"] = kv_new[2], kv_new[3]
        return logits, new_cache

    def _embed_token(self, params, token, cur_len):
        """Decode-step embedding of ``token`` (B, 1) at position
        ``cur_len`` (B,)."""
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], token, axis=0)
            if self.cfg.rope_theta == 0:
                pe = sinusoidal_at(cur_len, self.cfg.d_model)
                x = x + pe[:, None, :].astype(x.dtype)
        return x

    def _decoder(self, body, x, layer_params, kv_in):
        """The layer ``scan`` over stacked layer params and KV pools."""
        if not self.cfg.scan_layers:
            raise NotImplementedError("packed serving assumes scan layout")
        with jax.named_scope("decoder"):
            return jax.lax.scan(body, x, (layer_params,) + kv_in)

    def _head(self, params, x):
        """Final norm and logits (tied embedding unless ``head``)."""
        x = layers.apply_norm(params["ln_f"], x, self.cfg.norm)
        head = params.get("head")
        return x @ (head if head is not None else params["embed"].T)

    def _qkv(self, p, x, pos):
        """Shared q/k/v half of every block (norm → transform → packed
        matmuls → RoPE at the absolute positions ``pos`` (B, T)) — one
        implementation for prefill and decode and both cache layouts, so
        the paths cannot drift."""
        cfg = self.cfg
        b, t = x.shape[0], x.shape[1]
        with jax.named_scope("attn.qkv"):
            h = layers.apply_norm(p["ln_attn"], x, cfg.norm)
            h = _act_transform(p.get("attn_t"), h)
            q = self._mm(h, p["wq"])
            k = self._mm(h, p["wk"])
            v = self._mm(h, p["wv"])
            if "bq" in p:
                q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
            hd = cfg.resolved_head_dim
            q = q.reshape(b, t, cfg.num_heads, hd)
            k = k.reshape(b, t, cfg.num_kv_heads, hd)
            v = v.reshape(b, t, cfg.num_kv_heads, hd)
            if cfg.rope_theta > 0:
                q = layers.apply_rope(q, pos, cfg.rope_theta)
                k = layers.apply_rope(k, pos, cfg.rope_theta)
        return q, k, v

    def _out_and_mlp(self, p, x, out):
        """Output projection of the attention ``out`` (B, T, H, D) and the
        MLP half of a block, each added to the residual ``x``."""
        b, t = x.shape[0], x.shape[1]
        with jax.named_scope("attn.out"):
            x = x + self._mm(out.reshape(b, t, -1), p["wo"])
        with jax.named_scope("mlp"):
            return x + self._mlp(p, x)

    def _block_decode(self, p, x, kv, cur_len):
        b = x.shape[0]
        q, k, v = self._qkv(p, x, cur_len[:, None])
        s = kv[0].shape[1]
        with jax.named_scope("kv_write"):
            # a full cache drops the write: the saturated index s is out
            # of bounds and OOB scatter updates are dropped, so slot s-1
            # is never clobbered (len saturation in decode_step makes
            # exhaustion visible)
            write_idx = jnp.minimum(cur_len, s)
            bidx = jnp.arange(b)
            if len(kv) == 4:
                # quantize-on-write (kv_bits < 16); attention reads codes
                kc, vc, ksc, vsc = kv
                kq, k_s = _kv_quantize(k[:, 0], self.qcfg.kv_bits)
                vq, v_s = _kv_quantize(v[:, 0], self.qcfg.kv_bits)
                kc = kc.at[bidx, write_idx].set(kq)
                vc = vc.at[bidx, write_idx].set(vq)
                ksc = ksc.at[bidx, write_idx].set(k_s)
                vsc = vsc.at[bidx, write_idx].set(v_s)
                kv = (kc, vc, ksc, vsc)
            else:
                kc, vc = kv
                kc = kc.at[bidx, write_idx].set(k[:, 0].astype(kc.dtype))
                vc = vc.at[bidx, write_idx].set(v[:, 0].astype(vc.dtype))
                kv = (kc, vc)
        # fused flash-decode over the cache AS STORED: int8 codes dequantized
        # per KV tile in registers, KV grid length-masked to the valid tiles
        # (pallas/interpret/ref); `auto` off-TPU is the portable
        # decode_attention fallback — the only path that materializes fp K/V
        with jax.named_scope("attn.flash"):
            out = ops.flash_decode(q, kv, jnp.minimum(cur_len + 1, s),
                                   block_kv=self.flash_block_kv,
                                   mode=self.kernel_mode)
        return self._out_and_mlp(p, x, out), kv

    # ------------------------------------------------------------------
    # paged decode (PagedKVCache: page pools + per-sequence page tables)
    # ------------------------------------------------------------------
    def _decode_step_paged(self, params, token, cache):
        """One decode step over the paged cache: the token's K/V land in
        the sequence's current page (via the page table), attention walks
        only the allocated pages.  Same math as the linear path — only the
        cache addressing differs."""
        cur_len = cache.lens
        x = self._embed_token(params, token, cur_len)
        if self._kv_quantized:
            kv_in = (cache.k, cache.v, cache.k_scale, cache.v_scale)
        else:
            kv_in = (cache.k, cache.v)

        def body(h, xs):
            lp, kv = xs[0], xs[1:]
            h, kv = self._block_decode_paged(lp, h, kv, cur_len,
                                             cache.page_table,
                                             cache.page_size)
            return h, kv

        x, kv_new = self._decoder(body, x, params["layers"], kv_in)
        with jax.named_scope("head"):
            logits = self._head(params, x)
        new = {"k": kv_new[0], "v": kv_new[1],
               "lens": jnp.minimum(cur_len + 1, cache.capacity)}
        if self._kv_quantized:
            new["k_scale"], new["v_scale"] = kv_new[2], kv_new[3]
        return logits, dataclasses.replace(cache, **new)

    def _block_decode_paged(self, p, x, kv, cur_len, page_table, page_size):
        from repro.serve.kv_cache import paged_token_write, token_write_dest
        q, k, v = self._qkv(p, x, cur_len[:, None])
        num_pages = kv[0].shape[0]
        with jax.named_scope("kv_write"):
            # write through the page table; unallocated pages / at-capacity
            # sequences resolve to an out-of-bounds index and the scatter
            # drops the write (the linear drop-at-capacity contract, paged)
            dest = token_write_dest(page_table, cur_len, page_size,
                                    num_pages)
            if len(kv) == 4:
                kc, vc, ksc, vsc = kv
                kq, k_s = _kv_quantize(k[:, 0], self.qcfg.kv_bits)
                vq, v_s = _kv_quantize(v[:, 0], self.qcfg.kv_bits)
                kv = (paged_token_write(kc, kq, dest),
                      paged_token_write(vc, vq, dest),
                      paged_token_write(ksc, k_s, dest),
                      paged_token_write(vsc, v_s, dest))
            else:
                kc, vc = kv
                kv = (paged_token_write(kc, k[:, 0], dest),
                      paged_token_write(vc, v[:, 0], dest))
        cap = page_table.shape[1] * page_size
        with jax.named_scope("attn.flash"):
            out = ops.flash_decode(q, kv, jnp.minimum(cur_len + 1, cap),
                                   page_table=page_table,
                                   mode=self.kernel_mode)
        return self._out_and_mlp(p, x, out), kv

    # ------------------------------------------------------------------
    # shared mlp half (prefill + decode)
    # ------------------------------------------------------------------
    def _mlp(self, p, x):
        cfg = self.cfg
        h2 = layers.apply_norm(p["ln_mlp"], x, cfg.norm)
        h2 = _act_transform(p.get("mlp_t"), h2)
        if cfg.num_experts:
            return self._moe_apply(p["moe"], h2)
        mp = p["mlp"]

        def lin(wn, bn):
            y = self._mm(h2, mp[wn])
            if bn in mp:
                y = y + mp[bn]
            return y

        if cfg.act in ("swiglu", "geglu"):
            gate_fn = (jax.nn.silu if cfg.act == "swiglu"
                       else lambda z: jax.nn.gelu(z, approximate=True))
            inner = gate_fn(lin("w_gate", "b_gate")) * lin("w_up", "b_up")
        elif cfg.act == "gelu":
            inner = jax.nn.gelu(lin("w_up", "b_up"), approximate=True)
        else:
            inner = jax.nn.relu(lin("w_up", "b_up"))
        return self._mm(inner, mp["w_down"])

    def _moe_apply(self, mp, h2):
        """MoE on packed experts: the dense-dispatch capacity path of
        repro.models.moe dominates at decode batch sizes; expert weights are
        dequantized from their (single-rounding) codes for the gather. This
        is the one site that stays fp-activation even under a_bits < 16 —
        routing a dynamic expert gather through the fused int kernel needs
        per-expert block indexing (future work); dense models have no such
        fallback."""
        cfg = self.cfg
        from repro.models import moe as moe_lib
        params = {"router": mp["router"],
                  "w_up": mp["w_up"].dequantize(h2.dtype),
                  "w_down": mp["w_down"].dequantize(h2.dtype)}
        if "w_gate" in mp:
            params["w_gate"] = mp["w_gate"].dequantize(h2.dtype)
        y, _ = moe_lib.apply_moe(params, h2, top_k=cfg.top_k,
                                 capacity_factor=cfg.capacity_factor,
                                 act=cfg.act)
        return y

    # ---- sharding for the dry-run ----
    def param_logical_axes(self) -> dict:
        cfg = self.cfg
        L = ("layers",)

        def norm_ax():
            ax = {"scale": L + (None,)}
            if cfg.norm == "layernorm":
                ax["bias"] = L + (None,)
            return ax

        def q_ax(out_name):
            # packed/scale/zp share the weight's (K, N) sharding; at serve
            # time the K axis stays *unsharded over data* (weights resident,
            # no FSDP gather per step) — the quantized memory footprint is
            # what makes that possible.
            return {"packed": L + (None, out_name),
                    "scale": L + (None, out_name),
                    "zp": L + (None, out_name)}

        lx = {"ln_attn": norm_ax(), "ln_mlp": norm_ax(),
              "wq": q_ax("heads"), "wk": q_ax("kv_heads"),
              "wv": q_ax("kv_heads"), "wo": q_ax("fsdp_embed")}
        if cfg.qkv_bias:
            lx.update(bq=L + ("heads",), bk=L + ("kv_heads",),
                      bv=L + ("kv_heads",))
        if cfg.num_experts:
            def qe_ax():
                return {"packed": L + ("expert", None, None),
                        "scale": L + ("expert", None, None),
                        "zp": L + ("expert", None, None)}
            lx["moe"] = {"router": L + (None, None), "w_up": qe_ax(),
                         "w_down": qe_ax()}
            if cfg.act in ("swiglu", "geglu"):
                lx["moe"]["w_gate"] = qe_ax()
        else:
            lx["mlp"] = {"w_gate": q_ax("mlp"), "w_up": q_ax("mlp"),
                         "w_down": q_ax("fsdp_embed")}
            if cfg.act not in ("swiglu", "geglu"):
                lx["mlp"].pop("w_gate")
        axes = {"embed": ("vocab", None), "layers": lx, "ln_f": {"scale": (None,)}}
        if self.cfg.norm == "layernorm":
            axes["ln_f"]["bias"] = (None,)
        if not cfg.tie_embeddings:
            axes["head"] = (None, "vocab")
        return axes

    def cache_logical_axes(self, cache_specs) -> dict:
        from repro.serve.kv_cache import (PagedKVCache,
                                          paged_cache_logical_axes)
        if isinstance(cache_specs, PagedKVCache):
            return paged_cache_logical_axes(cache_specs)
        axes = build_model(self.cfg).cache_logical_axes(cache_specs)
        if "k_scale" in cache_specs:
            # quantized KV cache: scales shadow the code tensors (head dim
            # over TP, like the codes) — kv8 drops the head_dim axis, kv4
            # keeps a (narrower) block axis after the heads
            sc = ("layers", "batch", None, "cache_heads")
            if cache_specs["k_scale"].ndim == 5:
                sc = ("layers", "batch", None, "cache_heads", None)
            axes["k_scale"] = sc
            axes["v_scale"] = sc
        return axes
