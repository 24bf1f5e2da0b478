"""Pallas TPU kernel: fused flash-decode attention over the quantized KV cache.

One-token decode attention reads the cache **as stored** and dequantizes
each KV page in registers on its way through the math — three formats,
inferred from the scale operands:

    kv16  k/v (…, T, Hkv, D) fp, no scales
    kv8   k/v (…, T, Hkv, D) int8 + per-(token, head) f32 scales (…, T, Hkv)
    kv4   k/v (…, T, Hkv, D//2) int8 packed nibbles + bf16 block-32
          microscaling scales (…, T, Hkv, D//32) — a scale one rank
          higher than kv8's, which is how the format is told apart

The full-cache fp materialization the XLA fallback pays every layer, every
step (``(B, S, Hkv, D)`` floats) never exists on this path.  The kv4
epilogue is :func:`repro.kernels.quantize_pack.kv4_dequant_halves` — two
VREG shifts to unpack the nibbles plus a block-scale multiply, without
interleaving them back — shared with the ref oracle so bit-identity
survives the sub-byte layout.

Layout:

    q           (B, Hkv, G, D)     GQA groups folded next to their KV head;
                                   the kernel takes it as (B, G, Hkv, D)
    k / v       (P, T, Hkv, Dk)    a pool of P pages of T tokens of every
                                   head (Dk = D//2 packed int4, else D)
    k/v scale                      (P, T, Hkv) f32 for kv8;
                                   (P, T, Hkv, D//32) bf16 for kv4
    page_table  (B, max_pages) int32   logical page -> pool page
    cur_len     (B,) int32             valid positions per sequence
                                       (both scalar-prefetch)

The unit of math is one page of every head (:func:`attend_page`): one
online-softmax step of all Hkv heads over the page's T tokens as a few
vector-wide ops, heads on sublanes and the head dim on lanes.  One query
row per head would leave the MXU all but empty, so both products run on
the VPU in f32: q·k is an elementwise product summed over lanes, the
scores are gathered into (Hkv, T) with the tokens on lanes so the softmax
runs on a few vregs, and P·V adds each token's V row times its
probability, in token order.  The kv8 scales
multiply the scores and the probabilities, not the codes — ``(c·s)·q =
s·(c·q)`` — which takes a multiply per element out of both products; kv4's
block scales vary along D and stay on the codes.  At kv4 q and the
accumulator are held as their even and odd dims (:func:`split_dims`), the
order of the packed bytes, so no page is interleaved in registers.  The
running max, denominator and accumulator of every head live in VMEM
scratch across the pages of one sequence; the output is written after its
last block.

Grid and DMA: ``grid (B, ceil(max_pages / pages_per_block))`` — one grid
step is one block of ``pages_per_block`` pages of one sequence.  Each page
slot of a block is a BlockSpec operand of its own on the same pool, whose
index map gathers pool page ``page_table[b, i * pages_per_block + j]``, so a
page of all heads stays one DMA per tensor and the pipeline fetches the
next block while this one computes.  A slot at or past
``ceil(cur_len / T)`` keeps the pool page it last held, Pallas skips its
copy, and the step skips its math; blocks past a sequence's last live page
cost the grid step alone.  (Manual ``make_async_copy``s from HBM pools
would need the pool's minor dim to be a multiple of 128: Mosaic refuses to
slice the kv8 scale pool (…, T, Hkv) f32 and both kv4 pools, while a block
that spans the pool's own trailing dims compiles for every format.)
:func:`pages_per_block` picks the block from the shapes.

The linear cache ``(B, S, Hkv, Dk)`` is the pool ``(B·S/block_kv, block_kv,
Hkv, Dk)`` of its tiles — a reshape of the leading dims, which moves no
bytes — walked through the identity page table, so both layouts run the
one kernel and one tile of the linear cache is one page.

``ref.flash_decode_paged_ref`` is the pure-jnp oracle: it walks the same
pages in the same order with the same :func:`attend_page`, so interpret
mode is bit-identical to it under jit.  ``models.attention.decode_attention``
is the portable XLA fallback whose results this kernel matches to fp
tolerance.  Chunked prefill (``flash_prefill``) keeps its own per-head MXU
tile: a chunk has ``C·G`` query rows per head, which fill the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quantize_pack import (KV_BLOCK, kv4_check_head_dim,
                                         kv4_dequant_halves)

NEG_INF = -1e30
# linear cache: tokens of one page of the per-page math (the paged pool's
# default page); the DMA block is pages_per_block of them
DEFAULT_BLOCK_KV = 16
# scoped VMEM for both flash kernels (the v5e default is 16 MiB of its
# 128): a (B, C=128) prefill chunk keeps every head's f32 q, output,
# accumulator and lane-padded softmax state of one sequence resident,
# about 16 MiB before the per-tile temporaries
VMEM_LIMIT = 64 * 2**20
# decode: the double-buffered page blocks of one grid step stay under this
# share of VMEM_LIMIT, the rest is the page math's f32 temporaries
BLOCK_VMEM = 16 * 2**20
# decode: tokens of one grid step's block, times G — enough to hide a
# block's DMA behind the previous block's math, while the unrolled
# per-page math of a block stays a few pages long
BLOCK_TOKENS = 128
# decode: page slots of one grid step — each slot is a BlockSpec operand
# of every pool, with its own DMA and pipeline bookkeeping
MAX_PAGES_PER_BLOCK = 16


def kv_format(k, k_scale, v_scale) -> int:
    """kv_bits of a cache tuple: 4 when the scales are code-rank (block
    grids), 8 when they are one rank lower, 16 without scales."""
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)
    if not quantized:
        return 16
    return 4 if k_scale.ndim == k.ndim else 8


def split_dims(x):
    """(..., Hkv, D) -> (..., 2, Hkv, D//2): the even dims, then the odd
    dims — the layout of q and the accumulator at kv4, where a packed byte
    holds one even and one odd dim (:func:`kv4_dequant_halves`)."""
    return jnp.moveaxis(x.reshape(*x.shape[:-1], -1, 2), -1, -3)


def merge_dims(x):
    """Inverse of :func:`split_dims`."""
    return jnp.moveaxis(x, -3, -1).reshape(*x.shape[:-3], x.shape[-2], -1)


def load_page(k, v, ks, vs, *, kv_bits: int):
    """A page as :func:`attend_page` reads it, once for all the query rows
    of its heads: ``k``/``v`` (T, Hkv, Dk) as stored and ``ks``/``vs`` the
    page's scales — (T, Hkv) f32 at kv8, (T, Hkv, D//32) bf16 at kv4, None
    at kv16.  Returns ``(kf, v_rows, ks, vs)``: K in f32 — (T, Hkv, D), or
    at kv4 (2, T, Hkv, D//2) in :func:`split_dims` layout — its T V rows
    laid out the same way, and the kv8 scales (None otherwise), which
    multiply the scores and the probabilities instead of the codes."""
    t = k.shape[0]
    if kv_bits == 4:
        # in-register nibble unpack + block-32 microscaling dequant
        kf = kv4_dequant_halves(k, ks)                     # (2, T, Hkv, D/2)
        vf = kv4_dequant_halves(v, vs)
        return kf, [vf[:, i] for i in range(t)], None, None
    return (k.astype(jnp.float32),
            [v[i].astype(jnp.float32) for i in range(t)], ks, vs)


def attend_page(q, page, n_valid, m, l, acc):
    """One online-softmax step of every KV head over one page.

    ``q`` f32, one query row per head, already times the softmax scale:
    (Hkv, D), or at kv4 (2, Hkv, D//2) in :func:`split_dims` layout;
    ``page`` what :func:`load_page` returns; ``n_valid`` how many leading
    tokens of the page count; ``m``/``l`` (Hkv, 1) and ``acc`` (laid out
    as ``q``) the running state.  Returns the new ``(m, l, acc)``.  THE
    per-page math of the decode kernel and its ref oracle, so the op order
    cannot drift between them.

    The scores are gathered into (Hkv, T), tokens on lanes, so the softmax
    runs on a few vregs rather than on one lane-sparse vreg per (token,
    head group); P·V adds the page's tokens one at a time, in order.
    """
    kf, v_rows, ks, vs = page
    t = len(v_rows)
    if kf.ndim == 4:                                       # kv4 halves
        s = jnp.sum(kf[0] * q[0] + kf[1] * q[1], axis=-1, keepdims=True)
    else:
        s = jnp.sum(kf * q, axis=-1, keepdims=True)        # (T, Hkv, 1)
    s = jnp.concatenate([s[i] for i in range(t)], axis=-1)  # (Hkv, T)
    if ks is not None:
        s = s * ks.T                                       # s·(c·q)
    valid = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < n_valid
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    if vs is not None:
        p = p * vs.T
    pv = p[:, 0:1] * v_rows[0]
    for i in range(1, t):
        pv = pv + p[:, i:i + 1] * v_rows[i]
    return m_new, l_new, acc * corr + pv


def _vmem_bytes(shape, dtype) -> int:
    """VMEM footprint of an array: the minor dim padded to 128 lanes, the
    second-minor to the dtype's sublane tile."""
    item = jnp.dtype(dtype).itemsize
    *lead, rows, cols = shape
    sub = 8 * 4 // item
    n = -(-rows // sub) * sub * (-(-cols // 128) * 128) * item
    for x in lead:
        n *= x
    return n


def pages_per_block(pools, g: int, max_pages: int) -> int:
    """Pages of one grid step, from the shapes: about ``BLOCK_TOKENS / G``
    tokens (G query rows per head multiply the per-page products), at most
    ``MAX_PAGES_PER_BLOCK`` slots, never more than a sequence's pages, and
    the double-buffered blocks of every pool under ``BLOCK_VMEM``."""
    page_size = pools[0].shape[1]
    per_page = sum(_vmem_bytes(x.shape[1:], x.dtype) for x in pools)
    by_vmem = BLOCK_VMEM // (2 * per_page)
    assert by_vmem >= 1, ("one double-buffered page exceeds BLOCK_VMEM",
                          [x.shape for x in pools])
    by_tokens = BLOCK_TOKENS // (page_size * g)
    return max(1, min(max_pages, MAX_PAGES_PER_BLOCK, by_tokens, by_vmem))


def linear_page(s: int, block_kv: int) -> int:
    """Tokens of one page of a linear cache of ``s`` slots: the largest
    divisor of ``s`` no larger than ``block_kv``, so a ragged ``s`` still
    cuts into whole pages and never into one page of the whole cache."""
    return max(t for t in range(1, min(block_kv, s) + 1) if s % t == 0)


def _kernel(lens_ref, pt_ref, q_ref, *refs, ppb: int, n_blocks: int,
            n_pools: int, page_size: int, scale: float, kv_bits: int):
    slots = refs[:n_pools * ppb]            # pool-major: slot j of pool t
    o_ref, m_ref, l_ref, acc_ref = refs[n_pools * ppb:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    cur = lens_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * ppb * page_size < cur)
    def _block():
        qs = [q_ref[0, gi].astype(jnp.float32) * scale
              for gi in range(q_ref.shape[1])]
        for j in range(ppb):
            first = (i * ppb + j) * page_size

            @pl.when(first < cur)
            def _page():
                stored = [slots[t * ppb + j][0] for t in range(n_pools)]
                page = load_page(*stored, *[None] * (4 - n_pools),
                                 kv_bits=kv_bits)
                for gi, q in enumerate(qs):
                    m, l, acc = attend_page(
                        q, page, cur - first, m_ref[gi], l_ref[gi],
                        acc_ref[gi])
                    m_ref[gi] = m
                    l_ref[gi] = l
                    acc_ref[gi] = acc

    @pl.when(i == n_blocks - 1)
    def _done():
        # cur_len == 0 leaves l == 0: the row returns zeros (documented)
        o_ref[0] = finish(acc_ref[...], l_ref[...]).astype(o_ref.dtype)


def finish(acc, l):
    """Output rows from the final state: ``acc`` (G, Hkv, D) or, at kv4,
    (G, 2, Hkv, D//2) over ``l`` (G, Hkv, 1)."""
    if acc.ndim > l.ndim:
        l = l[:, None]
    return acc / jnp.maximum(l, 1e-30)


def _slot_page(b, i, lens, pt, *, j: int, ppb: int, page_size: int):
    """Pool page of slot ``j`` of block ``i`` of sequence ``b``: the page
    GATHER lives here, in the index map.  A slot past the last live logical
    page keeps the last live page that fell in it, so the index repeats
    and Pallas skips the DMA; a slot no live page of the sequence falls in
    takes the last live page.  max(—, 0) guards the cur_len == 0 row, whose
    table row may be all −1 (its math is predicated off)."""
    last = jnp.maximum(pl.cdiv(lens[b], page_size) - 1, 0)
    own = jnp.where(last >= j, last - (last - j) % ppb, last)
    return jnp.maximum(pt[b, jnp.minimum(i * ppb + j, own)], 0)


def _flash_decode_pages(q, k, v, page_table, cur_len, k_scale, v_scale, *,
                        scale, ppb: int, name: str, interpret: bool):
    """The pallas_call over a page pool with ``ppb`` pages a grid step."""
    bsz, hkv, g, d = q.shape
    n_pages = page_table.shape[1]
    kv_bits = kv_format(k, k_scale, v_scale)
    pools = [k, v] + ([] if kv_bits == 16 else [k_scale, v_scale])
    n_blocks = pl.cdiv(n_pages, ppb)
    page_size = k.shape[1]
    qg = jnp.swapaxes(q, 1, 2)                              # (B, G, Hkv, D)
    if kv_bits == 4:
        qg = split_dims(qg)                          # (B, G, 2, Hkv, D//2)
    row = lambda b, i, *_: (b,) + (0,) * (qg.ndim - 1)
    in_specs = [pl.BlockSpec((1,) + qg.shape[1:], row)]
    for x in pools:
        tail = (0,) * (x.ndim - 1)
        for j in range(ppb):
            page = functools.partial(_slot_page, j=j, ppb=ppb,
                                     page_size=page_size)
            in_specs.append(pl.BlockSpec(
                (1,) + x.shape[1:],
                lambda b, i, lens, pt, page=page, tail=tail:
                (page(b, i, lens, pt),) + tail))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1,) + qg.shape[1:], row),
        scratch_shapes=[
            pltpu.VMEM((g, hkv, 1), jnp.float32),   # running max
            pltpu.VMEM((g, hkv, 1), jnp.float32),   # running denominator
            pltpu.VMEM(qg.shape[1:], jnp.float32),  # output accumulator
        ],
    )
    kernel = functools.partial(
        _kernel, ppb=ppb, n_blocks=n_blocks, n_pools=len(pools),
        page_size=page_size, scale=scale if scale is not None else d ** -0.5,
        kv_bits=kv_bits)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(cur_len.astype(jnp.int32), page_table.astype(jnp.int32), qg,
      *[x for x in pools for _ in range(ppb)])
    if kv_bits == 4:
        out = merge_dims(out)
    return jnp.swapaxes(out, 1, 2)


def _check_pools(q, k, v, k_scale, v_scale):
    bsz, hkv, g, d = q.shape
    num_pages, page_size = k.shape[0], k.shape[1]
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


@functools.partial(jax.jit, static_argnames=("scale", "block_kv",
                                             "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 cur_len: jax.Array, k_scale=None, v_scale=None, *,
                 scale: float | None = None,
                 block_kv: int = DEFAULT_BLOCK_KV,
                 interpret: bool = False) -> jax.Array:
    """Flash-decode over the cache as stored. Returns (B, Hkv, G, D) q.dtype.

    ``k``/``v`` (B, S, Hkv, Dk) are kv8 int8 codes when 3D
    ``k_scale``/``v_scale`` (both or neither) are given, kv4 packed nibbles
    when the scales are 4D block-32 grids, fp otherwise. ``cur_len``
    counts valid positions; positions ``>= cur_len[b]`` are masked, a
    zero-length row returns zeros.  Requires ``S % block_kv == 0`` (the
    ops wrapper picks :func:`linear_page`): each ``block_kv`` tile is one
    page of the paged kernel, walked through the identity page table.
    """
    bsz, s = k.shape[0], k.shape[1]
    assert s % block_kv == 0, (s, block_kv)
    n = s // block_kv
    pools = [None if x is None else x.reshape(bsz * n, block_kv, *x.shape[2:])
             for x in (k, v, k_scale, v_scale)]
    _check_pools(q, *pools)
    table = jnp.arange(bsz * n, dtype=jnp.int32).reshape(bsz, n)
    ppb = pages_per_block([x for x in pools if x is not None], q.shape[2], n)
    return _flash_decode_pages(q, pools[0], pools[1], table, cur_len,
                               pools[2], pools[3], scale=scale, ppb=ppb,
                               name="flash_decode", interpret=interpret)


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
    entries below ``ceil(cur_len[b] / page_size)`` are read).  A grid step
    is a block of :func:`pages_per_block` pages of one sequence, each page
    one DMA per tensor and one :func:`attend_page` of every head.
    """
    assert page_table.shape[0] == q.shape[0], (page_table.shape, q.shape)
    _check_pools(q, k, v, k_scale, v_scale)
    pools = [x for x in (k, v, k_scale, v_scale) if x is not None]
    ppb = pages_per_block(pools, q.shape[2], page_table.shape[1])
    return _flash_decode_pages(q, k, v, page_table, cur_len, k_scale,
                               v_scale, scale=scale, ppb=ppb,
                               name="flash_decode_paged",
                               interpret=interpret)
