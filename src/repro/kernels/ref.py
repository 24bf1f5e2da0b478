"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` implements the exact math its kernel must reproduce; kernel
tests sweep shapes/dtypes and assert allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.packing import unpack
from repro.kernels.flash_decode import (NEG_INF, attend_page, finish,
                                        kv_format, load_page, merge_dims,
                                        split_dims)
from repro.kernels.flash_prefill import attend_tile


def dequant_matmul_ref(x: jax.Array, packed: jax.Array, scale: jax.Array,
                       zp: jax.Array, *, bits: int, group_size: int,
                       out_dtype=None) -> jax.Array:
    """x (M, K) float  @  dequant(packed (K//8*bits, N)) -> (M, N).

    scale/zp: (K // group_size, N) float32 (group along K).
    """
    m, k = x.shape
    n = packed.shape[-1]
    codes = unpack(packed, bits, k).astype(jnp.float32)       # (K, N)
    g = group_size if group_size else k
    cg = codes.reshape(k // g, g, n)
    w = (cg - zp[:, None, :]) * scale[:, None, :]
    w = w.reshape(k, n).astype(x.dtype)
    out = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def int8_matmul_ref(x_q: jax.Array, w_q: jax.Array, x_scale: jax.Array,
                    w_scale: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    """int8 x (M, K) @ int8 w (K, N) -> float (M, N).

    x_scale (M, 1) per-token, w_scale (N,) per-channel, both float32.
    """
    acc = jnp.dot(x_q.astype(jnp.int32), w_q.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * x_scale.astype(jnp.float32) \
        * w_scale.astype(jnp.float32)[None, :]
    return out.astype(out_dtype)


def w8a8_dynamic_ref(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                     out_dtype=None) -> jax.Array:
    """Dynamic per-token activation quantization + int8 matmul."""
    xf = x.astype(jnp.float32)
    bound = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), 1e-8)
    x_scale = bound / 127.0
    x_q = jnp.clip(jnp.round(xf / x_scale), -128, 127).astype(jnp.int8)
    out = int8_matmul_ref(x_q, w_q, x_scale, w_scale)
    return out.astype(out_dtype or x.dtype)


def quant_matmul_ref(x: jax.Array, packed: jax.Array, scale: jax.Array,
                     zp: jax.Array, *, bits: int, group_size: int,
                     a_bits: int = 8, out_dtype=None) -> jax.Array:
    """Fused weight+activation integer matmul oracle (the W·A serving path).

    Activations get per-token dynamic symmetric ``a_bits`` quantization
    (int8 lanes); packed per-group asymmetric weight codes are *centered*
    by ``off = 2^(bits-1)`` so 8-bit codes also fit int8 lanes, and the
    zero-point is folded into a per-group row-sum correction:

        sum_k x_q (c - zp) = dot(x_q, c - off) + rowsum(x_q) * (off - zp)

    The per-group float32 epilogue (scale multiply, sequential group
    accumulation, final activation-scale multiply) mirrors the kernel's op
    order exactly, so ``w4a8_matmul`` in interpret mode with ``bk >= K``
    (one K block == whole-row activation scale) is bit-identical to this.
    """
    from repro.kernels.int8_matmul import act_scale
    m, k = x.shape
    n = packed.shape[-1]
    xf = x.astype(jnp.float32)
    qmax = 2.0 ** (a_bits - 1) - 1.0
    a_scale = act_scale(x, a_bits)
    x_q = jnp.clip(jnp.round(xf / a_scale), -qmax - 1.0, qmax
                   ).astype(jnp.int8)

    off = 2 ** (bits - 1)
    c8 = unpack(packed, bits, k).astype(jnp.int32) - off      # (K, N)
    g = group_size if group_size else k
    assert k % g == 0, (k, g)   # QTensor effective-group invariant
    xq32 = x_q.astype(jnp.int32)
    acc = jnp.zeros((m, n), jnp.float32)
    for gi in range(k // g):
        sl = slice(gi * g, (gi + 1) * g)
        dot = jnp.dot(xq32[:, sl], c8[sl],
                      preferred_element_type=jnp.int32)
        rsum = jnp.sum(xq32[:, sl], axis=1, keepdims=True)
        acc = acc + scale[gi][None, :] * (
            dot.astype(jnp.float32)
            + rsum.astype(jnp.float32) * (off - zp[gi])[None, :])
    out = acc * a_scale
    return out.astype(out_dtype or x.dtype)


def _flash_grid(q, seq, n_tiles, fetch, live, valid, *, scale, kv_bits):
    """Walk the flash-prefill grid in plain jnp, one sequence at a time:
    KV tile ``t`` inner, every head per tile — the same
    :func:`repro.kernels.flash_prefill.attend_tile` per (tile, head) in the
    same order, with ``lax.cond`` standing in for the kernel's predicated
    tiles.  Walking one sequence per loop iteration (not a batch at once)
    is what keeps interpret mode BIT-identical to this under jit: XLA
    fuses, and so rounds, a batched loop body differently.

    ``q`` (B, Hkv, R, D) f32; ``seq`` a pytree of per-sequence arrays
    (leading dim B) handed to ``fetch(a, t)`` -> (k, v, ks, vs) tiles
    (block_kv, Hkv, ...), ``live(a, t)`` bool scalar and ``valid(a, t)``
    mask broadcastable to (R, block_kv).  Returns acc / l
    (B, Hkv, R, D) f32.  Only one KV tile is dequantized at a time —
    never the full cache.
    """
    _, hkv, r, d = q.shape

    def one(xs):
        qb, a = xs

        def tile(t, st):
            def update(st):
                k, v, ks, vs = fetch(a, t)
                ok = valid(a, t)

                def head(h, st):
                    # dynamic head index: one head's math per loop body
                    # (unrolling Hkv heads, as the kernel does, makes the
                    # oracle slow to compile at real head counts)
                    sel = lambda x: jax.lax.dynamic_index_in_dim(
                        x, h, 1, keepdims=False)
                    if kv_bits == 8:
                        hs = [jax.lax.dynamic_slice_in_dim(x, h, 1, 1)
                              for x in (ks, vs)]
                    elif kv_bits == 4:
                        hs = [sel(ks), sel(vs)]
                    else:
                        hs = [None, None]
                    new = attend_tile(
                        jax.lax.dynamic_index_in_dim(qb, h, 0, False),
                        sel(k), sel(v), *hs, ok,
                        *(jax.lax.dynamic_index_in_dim(x, h, 0, False)
                          for x in st), scale=scale, kv_bits=kv_bits)
                    return tuple(x.at[h].set(y) for x, y in zip(st, new))

                return jax.lax.fori_loop(0, hkv, head, st)
            return jax.lax.cond(live(a, t), update, lambda st: st, st)

        init = (jnp.full((hkv, r, 1), NEG_INF, jnp.float32),
                jnp.zeros((hkv, r, 1), jnp.float32),
                jnp.zeros((hkv, r, d), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, n_tiles, tile, init)
        return acc / jnp.maximum(l, 1e-30)

    return jax.lax.map(one, (q, seq))


def _linear_fetch(block_kv):
    """Tiles of one sequence's linear cache slab ``a["kv"]``."""
    def fetch(a, t):
        tile = lambda x: None if x is None else jax.lax.dynamic_slice_in_dim(
            x, t * block_kv, block_kv, axis=0)
        return tuple(tile(x) for x in a["kv"])
    return fetch


def _paged_fetch(k_pool, v_pool, k_scale, v_scale):
    """Tile ``t`` gathers pool page ``a["pt"][t]``, clamped like the
    kernels' index maps to the last page holding ``a["total"]`` tokens
    (only live tiles are read)."""
    ps = k_pool.shape[1]

    def fetch(a, t):
        last = jnp.maximum((a["total"] + ps - 1) // ps - 1, 0)
        page = jnp.maximum(a["pt"][jnp.minimum(t, last)], 0)
        get = lambda x: None if x is None else x[page]
        return get(k_pool), get(v_pool), get(k_scale), get(v_scale)
    return fetch


def flash_decode_paged_ref(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           cur_len: jax.Array, k_scale=None, v_scale=None,
                           *, scale=None, out_dtype=None) -> jax.Array:
    """Page-walking oracle for the flash-decode kernel (its contract).

    q (B, Hkv, G, D); ``k_pool``/``v_pool`` are page pools
    (num_pages, page_size, Hkv, Dk) — kv8 int8 codes (Dk = D) when
    ``k_scale``/``v_scale`` pools (num_pages, page_size, Hkv) f32 are
    given, kv4 packed nibbles (Dk = D//2) when the scale pools are 4D
    (num_pages, page_size, Hkv, D//32) bf16, fp otherwise;
    ``page_table`` (B, max_pages_per_seq) int32 (−1 = unallocated);
    ``cur_len`` (B,) valid positions.  One sequence per loop iteration,
    its live pages in order, each gathered from the pool and run through
    :func:`repro.kernels.flash_decode.attend_page` for every query row of
    every head — the kernel's math, page for page, whatever its blocks —
    so interpret mode is BIT-IDENTICAL to this under jit.  Walking one
    sequence per iteration (not a batch at once) is what keeps it so: XLA
    fuses, and so rounds, a batched loop body differently.  Only one page
    is dequantized at a time — never the full cache.  A zero-length row
    returns zeros.
    """
    bsz, hkv, g, d = q.shape
    ps = k_pool.shape[1]
    kv_bits = kv_format(k_pool, k_scale, v_scale)
    scale = scale if scale is not None else d ** -0.5
    pools = (k_pool, v_pool, k_scale, v_scale)

    def one(xs):
        qb, pt, cur = xs
        qs = [qb[gi] * scale for gi in range(g)]
        acc0 = jnp.zeros(qb.shape, jnp.float32)

        def page(t, st):
            def update(st):
                idx = jnp.maximum(pt[t], 0)
                page = load_page(*(None if x is None else x[idx]
                                   for x in pools), kv_bits=kv_bits)
                new = [attend_page(qs[gi], page, cur - t * ps,
                                   *(x[gi] for x in st))
                       for gi in range(g)]
                return tuple(jnp.stack(x) for x in zip(*new))
            return jax.lax.cond(t * ps < cur, update, lambda st: st, st)

        init = (jnp.full((g, hkv, 1), NEG_INF, jnp.float32),
                jnp.zeros((g, hkv, 1), jnp.float32), acc0)
        _, l, acc = jax.lax.fori_loop(0, pt.shape[0], page, init)
        return finish(acc, l)

    qg = jnp.swapaxes(q, 1, 2).astype(jnp.float32)         # (B, G, Hkv, D)
    if kv_bits == 4:
        qg = split_dims(qg)
    out = jax.lax.map(one, (qg, page_table.astype(jnp.int32),
                            cur_len.astype(jnp.int32)))
    if kv_bits == 4:
        out = merge_dims(out)
    return jnp.swapaxes(out, 1, 2).astype(out_dtype or q.dtype)


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     cur_len: jax.Array, k_scale=None, v_scale=None, *,
                     scale=None, block_kv: int = 128,
                     out_dtype=None) -> jax.Array:
    """Linear-cache oracle of the flash-decode kernel (its contract).

    q (B, Hkv, G, D); k/v (B, S, Hkv, D) — kv8 int8 codes when 3D
    ``k_scale`` / ``v_scale`` (B, S, Hkv) f32 are given, kv4 packed nibbles
    (B, S, Hkv, D//2) when the scales are 4D (B, S, Hkv, D//32) bf16 block
    grids, fp otherwise; cur_len (B,) valid positions.  Each ``block_kv``
    tile is one page: the cache is the pool of its tiles under the
    identity page table, as in ``flash_decode.flash_decode``, walked by
    :func:`flash_decode_paged_ref` — so the kernel in interpret mode is
    BIT-IDENTICAL to this under jit.  Positions ``>= cur_len[b]`` are
    masked; a zero-length row returns zeros.
    """
    bsz, s = k.shape[0], k.shape[1]
    assert s % block_kv == 0, (s, block_kv)
    n = s // block_kv
    pools = [None if x is None else x.reshape(bsz * n, block_kv, *x.shape[2:])
             for x in (k, v, k_scale, v_scale)]
    table = jnp.arange(bsz * n, dtype=jnp.int32).reshape(bsz, n)
    return flash_decode_paged_ref(q, pools[0], pools[1], table, cur_len,
                                  pools[2], pools[3], scale=scale,
                                  out_dtype=out_dtype)


def _prefill_masks(offset, chunk_len, c, g, tile):
    """(per-sequence args, live, valid, row_live) of the chunked-prefill
    grid: position ``p`` is valid for row ``r`` (chunk token ``r // G``)
    iff ``p <= offset + r // G`` and ``r // G < chunk_len``;
    ``chunk_len == 0`` sequences visit no tiles."""
    off = offset.astype(jnp.int32)
    cl = chunk_len.astype(jnp.int32)
    seq = {"off": off, "cl": cl, "total": jnp.where(cl > 0, off + cl, 0)}
    row_tok = jax.lax.broadcasted_iota(jnp.int32, (c * g, tile), 0) // g
    kv_iota = jax.lax.broadcasted_iota(jnp.int32, (c * g, tile), 1)
    live = lambda a, t: t * tile < a["total"]
    valid = lambda a, t: ((t * tile + kv_iota <= a["off"] + row_tok)
                          & (row_tok < a["cl"]))
    row_live = (jax.lax.broadcasted_iota(jnp.int32, (c * g, 1), 0) // g
                < cl[:, None, None, None])              # (B, 1, R, 1)
    return seq, live, valid, row_live


def flash_prefill_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                      offset: jax.Array, chunk_len: jax.Array,
                      k_scale=None, v_scale=None, *, scale=None,
                      block_kv: int = 128, out_dtype=None) -> jax.Array:
    """Tile-structured chunked-prefill oracle (the fused kernel's contract).

    q (B, Hkv, C, G, D) — a C-token query chunk at absolute positions
    ``offset[b] + i`` attending the cache k/v (B, S, Hkv, D) — kv8 int8
    codes when 3D ``k_scale``/``v_scale`` (B, S, Hkv) f32 are given, kv4
    packed nibbles (B, S, Hkv, D//2) when the scales are 4D
    (B, S, Hkv, D//32) bf16 block grids, fp otherwise —
    **as stored**, with the chunk's own K/V already written.  Walks
    ``flash_prefill.flash_prefill``'s grid op-for-op, so the kernel in
    interpret mode is BIT-IDENTICAL to this under jit.  Position ``p`` is
    valid for chunk row ``i`` iff ``p <= offset[b] + i`` and
    ``i < chunk_len[b]``; pad rows return zeros.
    """
    bsz, hkv, c, g, d = q.shape
    s = k.shape[1]
    assert s % block_kv == 0, (s, block_kv)
    seq, live, valid, row_live = _prefill_masks(offset, chunk_len, c, g,
                                                block_kv)
    seq["kv"] = (k, v, k_scale, v_scale)
    out = _flash_grid(
        q.astype(jnp.float32).reshape(bsz, hkv, c * g, d), seq,
        s // block_kv, _linear_fetch(block_kv), live, valid,
        scale=scale if scale is not None else d ** -0.5,
        kv_bits=kv_format(k, k_scale, v_scale))
    out = jnp.where(row_live, out, 0.0)
    return out.reshape(bsz, hkv, c, g, d).astype(out_dtype or q.dtype)


def flash_prefill_paged_ref(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, page_table: jax.Array,
                            offset: jax.Array, chunk_len: jax.Array,
                            k_scale=None, v_scale=None, *, scale=None,
                            out_dtype=None) -> jax.Array:
    """Tile-mirroring oracle for the paged chunked-prefill kernel.

    Pools/page-table layout as in :func:`flash_decode_paged_ref`; one tile
    == one page, tile ``t`` gathers pool page ``page_table[b, t]`` and the
    grid of ``flash_prefill.flash_prefill_paged`` runs op-for-op —
    interpret mode is BIT-IDENTICAL to this under jit.  Pad rows return
    zeros.
    """
    bsz, hkv, c, g, d = q.shape
    ps = k_pool.shape[1]
    seq, live, valid, row_live = _prefill_masks(offset, chunk_len, c, g, ps)
    seq["pt"] = page_table.astype(jnp.int32)
    out = _flash_grid(
        q.astype(jnp.float32).reshape(bsz, hkv, c * g, d), seq,
        page_table.shape[1], _paged_fetch(k_pool, v_pool, k_scale, v_scale),
        live, valid,
        scale=scale if scale is not None else d ** -0.5,
        kv_bits=kv_format(k_pool, k_scale, v_scale))
    out = jnp.where(row_live, out, 0.0)
    return out.reshape(bsz, hkv, c, g, d).astype(out_dtype or q.dtype)


def quantize_pack_ref(w: jax.Array, *, bits: int, group_size: int
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-group asymmetric quantize + pack. w (K, N) float.

    Returns (packed (K//8*bits, N) uint8, scale (K//g, N) f32, zp same).
    """
    from repro.core.packing import pack
    k, n = w.shape
    g = group_size if group_size else k
    wg = w.astype(jnp.float32).reshape(k // g, g, n)
    wmax = jnp.max(wg, axis=1)
    wmin = jnp.min(wg, axis=1)
    scale = jnp.maximum(wmax - wmin, 1e-8) / (2 ** bits - 1)
    zp = jnp.round(-wmin / scale)
    codes = jnp.clip(jnp.round(wg / scale[:, None, :]) + zp[:, None, :],
                     0, 2 ** bits - 1)
    codes = codes.reshape(k, n).astype(jnp.uint8)
    return pack(codes, bits), scale, zp
