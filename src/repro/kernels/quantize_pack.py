"""Pallas TPU kernel: per-group asymmetric quantize + sub-byte pack — plus
the kv4 microscaling quantize/dequant pair the sub-byte KV cache shares.

Offline weight preparation for the serving path: streams a bf16/f32 weight
through VMEM once and emits packed uint8 codes + per-group scale/zp. The
group axis is K (input features), matching the dequant-matmul layout.

    grid (K/g, N/bn)       one program per (group, N-block)
    w block  (g, bn)       VMEM in
    packed   (g//8*bits, bn) VMEM out
    scale/zp (1, 1, bn)    VMEM out (rows of a (K/g, 1, N) array)

kv4 (MX-style microscaling, ``kv_bits=4``): :func:`kv4_quantize` packs K/V
vectors into two int4 codes per byte along D with ONE bf16 scale per block
of ``KV_BLOCK`` = 32 values — 2 B of scale per 32 values instead of the kv8
layout's 4 B f32 per whole (token, head) row.  :func:`kv4_dequant` is the
ONE unpack + block-scale epilogue shared verbatim by the flash-prefill
kernel bodies, their tile-mirroring ref oracles, and the XLA fallbacks —
sharing it is what keeps interpret mode bit-identical to ``ref`` at
kv_bits=4; the decode kernel and its oracle share
:func:`kv4_dequant_halves`, the same products without the interleave.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import nibble_halves, pack_nibbles, unpack_nibbles

KV_BLOCK = 32     # values sharing one bf16 microscaling scale
KV4_QMAX = 7.0    # symmetric int4 grid: codes in [-8, 7]


def kv4_check_head_dim(d: int) -> None:
    """kv4 needs D % 32 == 0: one bf16 scale per 32-value block and two
    codes per byte (32 | D implies 2 | D)."""
    if d % KV_BLOCK != 0:
        raise ValueError(
            f"kv_bits=4 requires head_dim % {KV_BLOCK} == 0 (one bf16 scale "
            f"per {KV_BLOCK}-value block, two int4 codes per byte); got "
            f"head_dim={d}")


def kv4_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Block-32 microscaling int4 quantization along the last axis.

    x (..., D) fp -> (packed codes int8 (..., D//2), scales bf16
    (..., D//32)).  Symmetric per block: ``scale = bf16(max|x_block| / 7)``;
    codes are rounded against the bf16-ROUNDED scale (the exact value
    :func:`kv4_dequant` reads back), so quantize -> dequant round-trips on
    one grid.  The serving quantize-on-write path and the test-input
    builders both call this, so the cache layout cannot drift.
    """
    d = x.shape[-1]
    kv4_check_head_dim(d)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // KV_BLOCK, KV_BLOCK)
    bound = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8)
    scales = (bound / KV4_QMAX).astype(jnp.bfloat16)
    q = jnp.clip(jnp.round(xf / scales.astype(jnp.float32)[..., None]),
                 -KV4_QMAX - 1.0, KV4_QMAX).astype(jnp.int8)
    return pack_nibbles(q.reshape(*x.shape[:-1], d)), scales


def kv4_dequant(packed: jax.Array, scales: jax.Array) -> jax.Array:
    """In-register unpack + block-scale dequant: (..., D//2) int8 packed
    codes + (..., D//32) bf16 scales -> (..., D) float32.

    THE shared kv4 epilogue: the flash-prefill kernel bodies run it on
    (block_kv, D//2) tiles, their ref oracles on (B, block_kv, Hkv, D//2)
    slices, and the XLA fallbacks on the whole cache — same elementwise op
    order everywhere, so interpret mode stays bit-identical to ``ref``.
    """
    codes = unpack_nibbles(packed)                       # (..., D) int32
    block_scale = jnp.repeat(scales.astype(jnp.float32), KV_BLOCK, axis=-1)
    return codes.astype(jnp.float32) * block_scale


def kv4_dequant_halves(packed: jax.Array, scales: jax.Array) -> jax.Array:
    """:func:`kv4_dequant` without the lane interleave: (..., D//2) int8
    packed codes + (..., D//32) bf16 scales -> (2, ..., D//2) float32, the
    even dims then the odd dims.  Byte ``j`` holds dims ``2j`` and
    ``2j + 1``, both in block ``j // (KV_BLOCK // 2)``, so each value is
    the same code times the same scale as in :func:`kv4_dequant`.  The
    decode kernel's page of every head runs on the halves: Mosaic spends
    minutes compiling the interleave of such a page, and VMEM runs out.
    """
    lo, hi = (x.astype(jnp.float32) for x in nibble_halves(packed))
    block = jax.lax.broadcasted_iota(jnp.int32, packed.shape,
                                     packed.ndim - 1) // (KV_BLOCK // 2)
    sc = scales.astype(jnp.float32)
    s = jnp.broadcast_to(sc[..., :1], packed.shape)
    for b in range(1, scales.shape[-1]):
        s = jnp.where(block == b, sc[..., b:b + 1], s)
    return jnp.stack([lo * s, hi * s])


def _pack_block(codes: jax.Array, bits: int) -> jax.Array:
    """(g, bn) uint32 codes -> (g//8*bits, bn) uint8 bytes (little-endian
    8-value groups; inverse of dequant_matmul._unpack_block)."""
    g, bn = codes.shape
    cu = codes.reshape(g // 8, 8, bn)
    out = []
    for byte_idx in range(bits):
        acc = jnp.zeros((g // 8, bn), jnp.uint32)
        for j in range(8):
            bit_off = j * bits
            lo, hi = bit_off // 8, (bit_off + bits - 1) // 8
            if lo == byte_idx:
                acc = acc | ((cu[:, j, :] << jnp.uint32(bit_off % 8))
                             & jnp.uint32(0xFF))
            elif hi == byte_idx and lo != hi:
                acc = acc | (cu[:, j, :] >> jnp.uint32(8 - bit_off % 8))
        out.append(acc.astype(jnp.uint8))
    packed = jnp.stack(out, axis=1)          # (g//8, bits, bn)
    return packed.reshape(g // 8 * bits, bn)


def _kernel(w_ref, p_ref, s_ref, z_ref, *, bits: int):
    wf = w_ref[...].astype(jnp.float32)
    wmax = jnp.max(wf, axis=0, keepdims=True)
    wmin = jnp.min(wf, axis=0, keepdims=True)
    scale = jnp.maximum(wmax - wmin, 1e-8) / (2 ** bits - 1)
    zp = jnp.round(-wmin / scale)
    # f32 -> int32 -> uint32: Mosaic casts floats to signed ints only
    codes = jnp.clip(jnp.round(wf / scale) + zp, 0, 2 ** bits - 1
                     ).astype(jnp.int32).astype(jnp.uint32)
    p_ref[...] = _pack_block(codes, bits)
    s_ref[0] = scale
    z_ref[0] = zp


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bn",
                                             "interpret"))
def quantize_pack(w: jax.Array, *, bits: int, group_size: int,
                  bn: int = 256, interpret: bool = False):
    """Returns (packed (K//8*bits, N), scale (K//g, N) f32, zp (K//g, N))."""
    k, n = w.shape
    g = group_size if group_size else k
    assert k % g == 0 and g % 8 == 0 and n % bn == 0, (k, g, n, bn)
    rows = g // 8 * bits
    # scale/zp rows are written as (K//g, 1, N): a (1, bn) block over a
    # (K//g, N) array would put a 1 in the second-minor place, which the
    # TPU lowering refuses; the unit axis is dropped after the call
    packed, scale, zp = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=(k // g, n // bn),
        in_specs=[pl.BlockSpec((g, bn), lambda gi, j: (gi, j))],
        out_specs=[
            pl.BlockSpec((rows, bn), lambda gi, j: (gi, j)),
            pl.BlockSpec((1, 1, bn), lambda gi, j: (gi, 0, j)),
            pl.BlockSpec((1, 1, bn), lambda gi, j: (gi, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k // 8 * bits, n), jnp.uint8),
            jax.ShapeDtypeStruct((k // g, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((k // g, 1, n), jnp.float32),
        ],
        interpret=interpret,
    )(w)
    return packed, scale.reshape(k // g, n), zp.reshape(k // g, n)
