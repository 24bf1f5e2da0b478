"""Pallas TPU kernel: fused flash-decode attention over the quantized KV cache.

One-token decode attention reads the cache **as stored** and dequantizes
each KV tile in registers on its way to the MXU — three formats, inferred
from the scale operands:

    kv16  k/v (B, S, Hkv, D) fp, no scales
    kv8   k/v (B, S, Hkv, D) int8 + per-(token, head) f32 scales (B, S, Hkv)
    kv4   k/v (B, S, Hkv, D//2) int8 packed nibbles + bf16 block-32
          microscaling scales (B, S, Hkv, D//32) — a 4D scale, one rank
          higher than kv8's, which is how the format is told apart

The full-cache fp materialization the XLA fallback pays every layer, every
step (``(B, S, Hkv, D)`` floats) never exists on this path.  The kv4
epilogue is :func:`repro.kernels.quantize_pack.kv4_dequant` — two VREG
shifts to unpack the nibbles plus a block-scale multiply — shared with the
ref oracles so bit-identity survives the sub-byte layout.

Layout and grid:

    q        (B, Hkv, G, D)    GQA groups folded next to their KV head so
                               one q block (G, D) attends one KV head
    k / v    (B, S, Hkv, Dk)   the cache tensors, untouched
                               (Dk = D//2 packed int4, else D)
    k/v scale                  (B, S, Hkv) f32 for kv8;
                               (B, S, Hkv, D//32) bf16 for kv4
    cur_len  (B,) int32        valid positions per sequence (scalar-prefetch)

    grid (B, ceil(S / block_kv))   — KV tiles innermost; ALL heads per step

One grid step moves one KV tile of every head: the K/V block is
``(1, block_kv, Hkv, Dk)``, so the last two block dims are the array's own
``(Hkv, Dk)`` and Mosaic's (8, 128) block rule holds for any head count,
head dim and tile (a per-head block would put a 1 in the second-minor
place, which the TPU lowering refuses).  For the same reason the kv8 scale
block is ``(1, block_kv, Hkv)``.  The kernel body loops over the heads and
reads each head's ``(block_kv, Dk)`` slice with a strided VMEM load; the
per-head math is :func:`attend_tile`.

The KV grid is **length-masked**: tile ``t`` of sequence ``b`` only computes
when ``t * block_kv < cur_len[b]``, and the BlockSpec index map clamps
out-of-range tiles to the last valid tile — Pallas skips the copy when the
block index repeats, so a sequence at ``cur_len=500`` in a 32k-slot cache
moves ~2% of the HBM bytes the full-``max_len`` fallback moves. The online-
softmax state (running max, denominator, f32 accumulator per head) lives in
VMEM scratch across the KV tiles of one sequence; the output is written on
the last tile.

``ref.flash_decode_ref`` is the pure-jnp oracle: it walks the same grid with
the same :func:`attend_tile` per (tile, head), so interpret mode is
bit-identical to it under jit. ``models.attention.decode_attention`` is the
portable XLA fallback whose results this kernel matches to fp tolerance.

Paged variant (``flash_decode_paged``): the cache is a global pool of
fixed-size pages ``(num_pages, page_size, Hkv, D)`` plus per-sequence page
tables ``(B, max_pages_per_seq) int32`` (``repro.serve.kv_cache``).  The KV
grid walks the sequence's page table instead of a contiguous slab: both
``cur_len`` and the page table are scalar-prefetch operands, and each KV
tile's BlockSpec index map *gathers* its page from the pool —
``page_table[b, min(t, last_valid)]`` — so HBM traffic stays bounded by
``ceil(cur_len / page_size)`` pages per sequence (out-of-range tiles repeat
the last valid page index and Pallas skips the copy).  A page is one
contiguous ``(page_size, Hkv, Dk)`` slab, so each step is one DMA per
tensor whatever the page size.  The kernel body is the SAME ``_kernel`` as
the linear variant (one tile == one page).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quantize_pack import (KV_BLOCK, kv4_check_head_dim,
                                         kv4_dequant)

NEG_INF = -1e30
DEFAULT_BLOCK_KV = 512
# scoped VMEM for both flash kernels (the v5e default is 16 MiB of its
# 128): a (B, C=128) prefill chunk keeps every head's f32 q, output,
# accumulator and lane-padded softmax state of one sequence resident,
# about 16 MiB before the per-tile temporaries
VMEM_LIMIT = 64 * 2**20
# f32 products on the MXU: at default precision XLA (the oracle on TPU)
# and Mosaic would each round the operands to bf16 their own way
HIGHEST = jax.lax.Precision.HIGHEST


def attend_tile(q, k, v, ks, vs, valid, m, l, acc, *, scale: float,
                kv_bits: int):
    """One online-softmax step of one head over one KV tile.

    ``q`` (R, D) f32 query rows; ``k``/``v`` (block_kv, Dk) as stored;
    ``ks``/``vs`` the head's scales — (block_kv, 1) f32 at kv8,
    (block_kv, D//32) bf16 at kv4, None at kv16; ``valid`` a bool mask
    broadcastable to (R, block_kv); ``m``/``l`` (R, 1) and ``acc`` (R, D)
    the running state.  Returns the new ``(m, l, acc)``.  THE shared
    per-tile math of both flash kernels and their ref oracles, so the op
    order cannot drift between them.
    """
    if kv_bits == 4:
        # in-register nibble unpack + block-32 microscaling dequant
        k = kv4_dequant(k, ks)
        v = kv4_dequant(v, vs)
    else:
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
        if kv_bits == 8:
            # int8 codes * per-(token, head) f32 scale
            k = k * ks
            v = v * vs
    rows = q.shape[0]
    if rows == 1:
        # one query row (MHA decode): run it as two identical rows.  XLA's
        # CPU backend turns a one-row dot into a multiply-reduce whose
        # summation order depends on the surrounding fusion, which would
        # break the interpret-vs-oracle bit-identity; the MXU pads rows to
        # a full tile anyway, so the duplicate row is free on TPU
        q, m, l, acc = (jnp.concatenate([x, x]) for x in (q, m, l, acc))
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32) * scale      # (R, block_kv)
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jax.lax.dot_general(
        p, v, dimension_numbers=(((1,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)
    return m_new[:rows], l_new[:rows], acc_new[:rows]


def head_scales(ks, vs, h: int, kv_bits: int, *lead):
    """Head ``h``'s scale slices of a ``(*lead, block_kv, Hkv[, D//32])``
    scale tile (array or VMEM ref) in the shape :func:`attend_tile`
    takes."""
    if kv_bits == 8:
        idx = (*lead, slice(None), slice(h, h + 1))
    elif kv_bits == 4:
        idx = (*lead, slice(None), h, slice(None))
    else:
        return None, None
    return ks[idx], vs[idx]


def kv_format(k, k_scale, v_scale) -> int:
    """kv_bits of a cache tuple: 4 when the scales are code-rank (block
    grids), 8 when they are one rank lower, 16 without scales."""
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)
    if not quantized:
        return 16
    return 4 if k_scale.ndim == k.ndim else 8


def _kernel(lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_kv: int, n_tiles: int,
            scale: float, kv_bits: int):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur = lens_ref[b]

    @pl.when(t * block_kv < cur)
    def _tile():
        pos = t * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        valid = pos < cur
        for h in range(q_ref.shape[1]):
            ks, vs = head_scales(ks_ref, vs_ref, h, kv_bits, 0)
            m, l, acc = attend_tile(
                q_ref[0, h].astype(jnp.float32), k_ref[0, :, h, :],
                v_ref[0, :, h, :], ks, vs, valid,
                m_ref[h], l_ref[h], acc_ref[h], scale=scale, kv_bits=kv_bits)
            m_ref[h] = m
            l_ref[h] = l
            acc_ref[h] = acc

    @pl.when(t == n_tiles - 1)
    def _done():
        # cur_len == 0 leaves l == 0: the row returns zeros (documented)
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def _call(kernel, in_specs, n_tiles, q, scalars, args, interpret):
    """Shared pallas_call of both layouts: one (Hkv, G, D) q/out block per
    sequence, KV tiles innermost."""
    bsz, hkv, g, d = q.shape
    q_map = lambda b, t, *_: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(bsz, n_tiles),
        in_specs=[pl.BlockSpec((1, hkv, g, d), q_map)] + in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),   # running max
            pltpu.VMEM((hkv, g, 1), jnp.float32),   # running denominator
            pltpu.VMEM((hkv, g, d), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*scalars, q, *args)


def kv_specs(kv_bits, tile, hkv, d, kv_map, scale_map, k, v, k_scale,
             v_scale):
    """BlockSpecs + operands of a KV tile of every head — codes
    ``(1, tile, Hkv, Dk)``, kv8 scales ``(1, tile, Hkv)``, kv4 block
    scales ``(1, tile, Hkv, D//32)`` — shared by both flash kernels."""
    dk = d // 2 if kv_bits == 4 else d
    in_specs = [pl.BlockSpec((1, tile, hkv, dk), kv_map),
                pl.BlockSpec((1, tile, hkv, dk), kv_map)]
    args = [k, v]
    if kv_bits == 4:
        sspec = pl.BlockSpec((1, tile, hkv, d // KV_BLOCK), kv_map)
        in_specs += [sspec, sspec]
        args += [k_scale, v_scale]
    elif kv_bits == 8:
        sspec = pl.BlockSpec((1, tile, hkv), scale_map)
        in_specs += [sspec, sspec]
        args += [k_scale, v_scale]
    return in_specs, args


def bind_absent_scales(body, n_scalars: int):
    """Keep one kernel body: bind the fp cache's absent scale refs to
    None."""
    def kernel(*refs):
        scalars, rest = refs[:n_scalars], refs[n_scalars:]
        qr, kr, vr, *tail = rest
        return body(*scalars, qr, kr, vr, None, None, *tail)
    return kernel


@functools.partial(jax.jit, static_argnames=("scale", "block_kv",
                                             "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 cur_len: jax.Array, k_scale=None, v_scale=None, *,
                 scale: float | None = None,
                 block_kv: int = DEFAULT_BLOCK_KV,
                 interpret: bool = False) -> jax.Array:
    """Flash-decode over the cache as stored. Returns (B, Hkv, G, D) q.dtype.

    ``k``/``v`` are kv8 int8 codes when 3D ``k_scale``/``v_scale`` (both or
    neither) are given, kv4 packed nibbles when the scales are 4D block-32
    grids, fp otherwise. ``cur_len`` counts valid positions; positions
    ``>= cur_len[b]`` are masked, a zero-length row returns zeros.
    Requires ``S % block_kv == 0`` (the ops wrapper clamps).
    """
    bsz, hkv, g, d = q.shape
    s = k.shape[1]
    assert s % block_kv == 0, (s, block_kv)
    kv_bits = kv_format(k, k_scale, v_scale)
    dk = d // 2 if kv_bits == 4 else d
    assert k.shape == v.shape == (bsz, s, hkv, dk), \
        (q.shape, k.shape, v.shape, kv_bits)
    if kv_bits == 4:
        kv4_check_head_dim(d)
        assert k_scale.shape == v_scale.shape == (bsz, s, hkv, d // KV_BLOCK)
    elif kv_bits == 8:
        assert k_scale.shape == v_scale.shape == (bsz, s, hkv)
    n_tiles = s // block_kv
    scale = scale if scale is not None else d ** -0.5

    def tile_idx(b, t, lens):
        # clamp out-of-range tiles to the last valid tile: a repeated block
        # index is not re-fetched, so masked tiles move no HBM bytes
        last = jnp.maximum(pl.cdiv(lens[b], block_kv) - 1, 0)
        return jnp.minimum(t, last)

    in_specs, args = kv_specs(
        kv_bits, block_kv, hkv, d,
        lambda b, t, lens: (b, tile_idx(b, t, lens), 0, 0),
        lambda b, t, lens: (b, tile_idx(b, t, lens), 0),
        k, v, k_scale, v_scale)
    kernel = functools.partial(_kernel, block_kv=block_kv, n_tiles=n_tiles,
                               scale=scale, kv_bits=kv_bits)
    if kv_bits == 16:
        kernel = bind_absent_scales(kernel, 1)
    return _call(kernel, in_specs, n_tiles, q, [cur_len.astype(jnp.int32)],
                 args, interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_decode_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                       page_table: jax.Array, cur_len: jax.Array,
                       k_scale=None, v_scale=None, *,
                       scale: float | None = None,
                       interpret: bool = False) -> jax.Array:
    """Flash-decode over a paged pool. Returns (B, Hkv, G, D) q.dtype.

    ``k``/``v`` are page pools ``(num_pages, page_size, Hkv, Dk)`` — kv8
    int8 codes (Dk = D) when ``k_scale``/``v_scale`` pools ``(num_pages,
    page_size, Hkv)`` are given, kv4 packed nibbles (Dk = D//2) when the
    scale pools are 4D ``(num_pages, page_size, Hkv, D//32)`` bf16, fp
    otherwise.  ``page_table`` (B, max_pages_per_seq) int32 maps logical
    page ``t`` of sequence ``b`` to a pool page (−1 = unallocated; only
    entries below ``ceil(cur_len[b] / page_size)`` are read).  One KV tile
    == one page; the grid is ``(B, max_pages_per_seq)`` and tile ``t``
    DMAs pool page ``page_table[b, t]`` via its BlockSpec index map.
    """
    bsz, hkv, g, d = q.shape
    num_pages, page_size = k.shape[0], k.shape[1]
    n_tiles = page_table.shape[1]
    assert page_table.shape == (bsz, n_tiles), (page_table.shape, bsz)
    kv_bits = kv_format(k, k_scale, v_scale)
    dk = d // 2 if kv_bits == 4 else d
    assert k.shape == v.shape == (num_pages, page_size, hkv, dk), \
        (q.shape, k.shape, v.shape, kv_bits)
    if kv_bits == 4:
        kv4_check_head_dim(d)
        assert k_scale.shape == v_scale.shape == \
            (num_pages, page_size, hkv, d // KV_BLOCK)
    elif kv_bits == 8:
        assert k_scale.shape == v_scale.shape == (num_pages, page_size, hkv)
    scale = scale if scale is not None else d ** -0.5

    def page(b, t, lens, pt):
        # the page GATHER lives here, in the index map: out-of-range tiles
        # clamp to the last valid logical page, whose pool index then
        # repeats — Pallas skips the DMA, so HBM bytes stay bounded by
        # ceil(cur_len / page_size) pages. max(—, 0) guards the cur_len == 0
        # row (page_table row may be all −1; compute is predicated off).
        last = jnp.maximum(pl.cdiv(lens[b], page_size) - 1, 0)
        return jnp.maximum(pt[b, jnp.minimum(t, last)], 0)

    in_specs, args = kv_specs(
        kv_bits, page_size, hkv, d,
        lambda b, t, lens, pt: (page(b, t, lens, pt), 0, 0, 0),
        lambda b, t, lens, pt: (page(b, t, lens, pt), 0, 0),
        k, v, k_scale, v_scale)
    # one tile == one page: reuse the linear kernel body verbatim so the
    # two layouts cannot diverge in op order
    body = functools.partial(_kernel, block_kv=page_size, n_tiles=n_tiles,
                             scale=scale, kv_bits=kv_bits)
    if kv_bits == 16:
        body = bind_absent_scales(body, 1)
    kernel = functools.partial(
        lambda lens, pt, *rest, inner: inner(lens, *rest), inner=body)
    return _call(kernel, in_specs, n_tiles, q,
                 [cur_len.astype(jnp.int32), page_table.astype(jnp.int32)],
                 args, interpret)
