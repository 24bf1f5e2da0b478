"""Pallas TPU kernel: chunked causal flash-prefill over the quantized KV
cache.

A query *chunk* of ``C`` tokens (absolute positions ``offset[b] ..
offset[b] + chunk_len[b] - 1`` per sequence) attends the already-written
cache prefix AND itself causally.  The chunk's K/V have already been
quantized-on-write into the cache by the caller (``prefill_chunk`` in the
serving models), so the kernel reads ONE source — the cache **as stored**,
in the same three formats as ``flash_decode`` (inferred from the scale
operands): fp (kv16), int8 + per-(token, head) f32 scales (kv8), or packed
int4 nibbles + bf16 block-32 microscaling scales one rank higher (kv4,
dequantized per tile by the shared
:func:`repro.kernels.quantize_pack.kv4_dequant` epilogue).  The fp
``(B, S, Hkv, D)`` cache materialization of the old whole-prompt prefill
never exists on this path (jaxpr-pinned, like the decode kernel's).

Layout and grid:

    q         (B, Hkv, C, G, D)   GQA groups folded next to their KV head;
                                  flattened outside the kernel to (C*G, D)
                                  rows per head where row r is chunk token
                                  r // G
    k / v     (B, S, Hkv, Dk)     the cache tensors, untouched
                                  (Dk = D//2 packed int4, else D)
    k/v scale                     (B, S, Hkv) f32 for kv8;
                                  (B, S, Hkv, D//32) bf16 for kv4
    offset    (B,) int32          chunk's first absolute position
                                  (scalar-prefetch)
    chunk_len (B,) int32          valid chunk rows per sequence
                                  (scalar-prefetch; rows past it are pads)

    grid (B, ceil(S / block_kv))   — KV tiles innermost; ALL heads per
                                     step

One grid step moves one KV tile of every head: the K/V block is
``(1, block_kv, Hkv, Dk)``, so the last two block dims are the array's own
``(Hkv, Dk)`` and Mosaic's (8, 128) block rule holds for any head count,
head dim and tile (a per-head block would put a 1 in the second-minor
place, which the TPU lowering refuses); the kv8 scale block is
``(1, block_kv, Hkv)`` for the same reason.  The body loops over the heads
and reads each head's ``(block_kv, Dk)`` slice with a strided VMEM load.

The KV grid is **length-masked** on the chunk's end: tile ``t`` of sequence
``b`` only computes when ``t * block_kv < offset[b] + chunk_len[b]``, and
the BlockSpec index map clamps out-of-range tiles to the last valid tile
(repeated block index == skipped copy), so HBM traffic is bounded by the
tokens actually attended, not ``max_len``.  Masking inside a tile is
per-(row, position): position ``p`` is valid for row ``r`` iff
``p <= offset + r // G`` (causal across the chunk/prefix boundary) and
``r // G < chunk_len`` (pad rows are fully masked and return zeros).

Splitting invariance (the chunked-serving contract): for a fixed cache and
tile size, each query row's online-softmax state walks the same KV tiles in
the same order whether the row arrives in a C-token chunk or the
whole-prompt "one big chunk" — trailing fully-masked tiles are exact no-ops
(``exp(NEG_INF - m)`` underflows to 0 and ``corr`` is exactly 1).  Calls at
different chunk sizes compile to differently-fused XLA graphs and agree to
f32 ULPs, which is why the engine equivalence contract is stated as token
identity.  A one-token decode step walks the same tiles but runs
``flash_decode``'s own per-page math (one query row per head, products on
the VPU: ``flash_decode.attend_page``), so a 1-row chunk agrees with it to
the same f32 ULPs, not bit for bit — the preempt/resume contract is token
identity too.  ``ref.flash_prefill_ref`` is the tile-mirroring oracle;
interpret mode is bit-identical to it under jit.

Paged variant (``flash_prefill_paged``): the cache is the page pool of
``repro.serve.kv_cache`` — ``(num_pages, page_size, Hkv, D)`` plus
per-sequence page tables — and the KV grid walks the page table (both
scalars AND the table are scalar-prefetch operands; the page gather lives
in the BlockSpec index map; one tile == one page).  The kernel body is
shared verbatim with the linear variant, and the per-(tile, head) math is
:func:`attend_tile`, so the two layouts cannot drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_decode import NEG_INF, VMEM_LIMIT, kv_format
from repro.kernels.quantize_pack import (KV_BLOCK, kv4_check_head_dim,
                                         kv4_dequant)

DEFAULT_BLOCK_KV = 512
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
    per-tile math of the prefill kernel and its ref oracles, so the op
    order cannot drift between them.  A chunk brings ``C·G`` rows per
    head, so both products run on the MXU (decode's one row per head runs
    :func:`repro.kernels.flash_decode.attend_page` on the VPU instead).
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
        # one query row: run it as two identical rows.  XLA's CPU backend
        # turns a one-row dot into a multiply-reduce whose summation order
        # depends on the surrounding fusion, which would break the
        # interpret-vs-oracle bit-identity; the MXU pads rows to a full
        # tile anyway, so the duplicate row is free on TPU
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


def kv_specs(kv_bits, tile, hkv, d, kv_map, scale_map, k, v, k_scale,
             v_scale):
    """BlockSpecs + operands of a KV tile of every head — codes
    ``(1, tile, Hkv, Dk)``, kv8 scales ``(1, tile, Hkv)``, kv4 block
    scales ``(1, tile, Hkv, D//32)`` — shared by both layouts."""
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


def _kernel(offs_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_kv: int, n_tiles: int,
            g: int, scale: float, kv_bits: int):
    b = pl.program_id(0)
    t = pl.program_id(1)
    r = q_ref.shape[2]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = offs_ref[b]
    cl = lens_ref[b]

    # cl == 0 rows (idle/decoding sequences riding along in an engine
    # chunk step) visit NO tiles: their output is zeros either way, and
    # gating here keeps their prefix out of the DMA/compute stream
    @pl.when((t * block_kv < off + cl) & (cl > 0))
    def _tile():
        kv_pos = t * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (r, block_kv), 1)
        row_tok = jax.lax.broadcasted_iota(jnp.int32, (r, block_kv), 0) // g
        # causal across the chunk/prefix boundary + pad-row masking
        valid = (kv_pos <= off + row_tok) & (row_tok < cl)
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
        # pad rows (row_tok >= chunk_len) are fully masked, but masked
        # scores all equal NEG_INF so p == exp(0) == 1 accumulates junk —
        # zero them explicitly (valid-row values pass through unchanged)
        live = jax.lax.broadcasted_iota(jnp.int32, (1, r, 1), 1) // g < cl
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out = jnp.where(live, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def _call(kernel, in_specs, n_tiles, q, scalars, args, interpret):
    """Shared pallas_call of both layouts: q (B, Hkv, C, G, D) runs as
    (B, Hkv, C*G, D) rows, one (Hkv, C*G, D) block per sequence."""
    bsz, hkv, c, g, d = q.shape
    r = c * g
    out_map = lambda b, t, *_: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(bsz, n_tiles),
        in_specs=[pl.BlockSpec((1, hkv, r, d), out_map)] + in_specs,
        out_specs=pl.BlockSpec((1, hkv, r, d), out_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, r, 1), jnp.float32),   # running max
            pltpu.VMEM((hkv, r, 1), jnp.float32),   # running denominator
            pltpu.VMEM((hkv, r, d), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, r, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*scalars, q.reshape(bsz, hkv, r, d), *args)
    return out.reshape(bsz, hkv, c, g, d)


@functools.partial(jax.jit, static_argnames=("scale", "block_kv",
                                             "interpret"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                  offset: jax.Array, chunk_len: jax.Array,
                  k_scale=None, v_scale=None, *,
                  scale: float | None = None,
                  block_kv: int = DEFAULT_BLOCK_KV,
                  interpret: bool = False) -> jax.Array:
    """Chunked causal prefill over the cache as stored.

    q (B, Hkv, C, G, D); returns the same shape in q.dtype.  ``k``/``v``
    are kv8 int8 codes when 3D ``k_scale``/``v_scale`` (both or neither)
    are given, kv4 packed nibbles when the scales are 4D block-32 grids,
    fp otherwise; the chunk's own K/V must already be written at
    positions ``offset .. offset + chunk_len - 1``.  Pad rows
    (``i >= chunk_len[b]``) return zeros.  Requires ``S % block_kv == 0``
    (the ops wrapper clamps).
    """
    bsz, hkv, c, g, d = q.shape
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

    def tile_idx(b, t, offs, lens):
        # clamp out-of-range tiles to the last valid tile: a repeated block
        # index is not re-fetched, so masked tiles move no HBM bytes.
        # cl == 0 rows attend nothing — clamp them to tile 0 (one DMA).
        total = jnp.where(lens[b] > 0, offs[b] + lens[b], 0)
        return jnp.minimum(t, jnp.maximum(pl.cdiv(total, block_kv) - 1, 0))

    in_specs, args = kv_specs(
        kv_bits, block_kv, hkv, d,
        lambda b, t, offs, lens: (b, tile_idx(b, t, offs, lens), 0, 0),
        lambda b, t, offs, lens: (b, tile_idx(b, t, offs, lens), 0),
        k, v, k_scale, v_scale)
    body = functools.partial(_kernel, block_kv=block_kv, n_tiles=n_tiles,
                             g=g, scale=scale, kv_bits=kv_bits)
    if kv_bits == 16:
        body = bind_absent_scales(body, 2)
    return _call(body, in_specs, n_tiles, q,
                 [offset.astype(jnp.int32), chunk_len.astype(jnp.int32)],
                 args, interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_prefill_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                        page_table: jax.Array, offset: jax.Array,
                        chunk_len: jax.Array, k_scale=None, v_scale=None, *,
                        scale: float | None = None,
                        interpret: bool = False) -> jax.Array:
    """Chunked causal prefill over a paged pool.  q (B, Hkv, C, G, D).

    ``k``/``v`` are page pools ``(num_pages, page_size, Hkv, Dk)`` — kv8
    int8 codes (Dk = D) when ``k_scale``/``v_scale`` pools ``(num_pages,
    page_size, Hkv)`` are given, kv4 packed nibbles (Dk = D//2) when the
    scale pools are 4D ``(num_pages, page_size, Hkv, D//32)`` bf16, fp
    otherwise.  ``page_table`` (B, max_pages_per_seq) int32 maps logical
    page ``t`` of sequence ``b`` to a pool page (−1 = unallocated; only
    entries below ``ceil((offset + chunk_len) / page_size)`` are read).
    One KV tile == one page, gathered in the BlockSpec index map exactly
    like ``flash_decode_paged``.
    """
    bsz, hkv, c, g, d = q.shape
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

    def page(b, t, offs, lens, pt):
        # the page GATHER lives in the index map (see flash_decode_paged):
        # out-of-range tiles repeat the last valid pool page so Pallas
        # skips the DMA; cl == 0 rows clamp to logical page 0, and
        # max(—, 0) guards its possibly-(−1) table entry.
        total = jnp.where(lens[b] > 0, offs[b] + lens[b], 0)
        last = jnp.maximum(pl.cdiv(total, page_size) - 1, 0)
        return jnp.maximum(pt[b, jnp.minimum(t, last)], 0)

    in_specs, args = kv_specs(
        kv_bits, page_size, hkv, d,
        lambda b, t, offs, lens, pt: (page(b, t, offs, lens, pt), 0, 0, 0),
        lambda b, t, offs, lens, pt: (page(b, t, offs, lens, pt), 0, 0),
        k, v, k_scale, v_scale)
    # one tile == one page: reuse the linear kernel body verbatim so the
    # two layouts cannot diverge in op order
    body = functools.partial(_kernel, block_kv=page_size, n_tiles=n_tiles,
                             g=g, scale=scale, kv_bits=kv_bits)
    if kv_bits == 16:
        body = bind_absent_scales(body, 2)
    kernel = functools.partial(
        lambda offs, lens, pt, *rest, inner: inner(offs, lens, *rest),
        inner=body)
    return _call(kernel, in_specs, n_tiles, q,
                 [offset.astype(jnp.int32), chunk_len.astype(jnp.int32),
                  page_table.astype(jnp.int32)], args, interpret)
