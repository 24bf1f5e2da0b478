"""Sub-byte integer packing for deployed quantized weights.

TPU HBM has no sub-byte addressable type before int4 support on v5p, so we
store quantized codes packed into ``uint8`` containers and unpack inside the
dequant-matmul kernel (VREG bit ops are cheap relative to the HBM stream).

Layout: groups of 8 consecutive values along the *input-channel* axis are
packed into ``bits`` bytes (8 values x b bits = b bytes exactly for any
b <= 8). This keeps the packed tensor contiguous along the same axis the
matmul streams, so a (bk, bn) weight block maps to a (bk*bits/8, bn) packed
block — a clean BlockSpec for the Pallas kernel.

A second, simpler layout serves the kv4 cache (:func:`pack_nibbles` /
:func:`unpack_nibbles`): two SIGNED int4 codes per int8 byte along the
*last* axis (byte ``j`` holds value ``2j`` in its low nibble and ``2j+1``
in its high nibble), so a (block_kv, D) KV tile maps to a (block_kv, D//2)
packed tile and the unpack is two VREG shifts — the attention kernels call
:func:`unpack_nibbles` in-register on each tile.

All functions are jit-safe and shape-polymorphic in the leading dims.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PACK_GROUP = 8  # values per packing unit


def packed_rows(d_in: int, bits: int) -> int:
    """Number of packed uint8 rows for ``d_in`` unpacked rows."""
    if d_in % PACK_GROUP != 0:
        raise ValueError(f"d_in={d_in} must be a multiple of {PACK_GROUP}")
    return d_in // PACK_GROUP * bits


def pack(codes: jax.Array, bits: int) -> jax.Array:
    """Pack uint8 codes (..., d_in, d_out), values < 2**bits, into bytes.

    Returns shape (..., d_in // 8 * bits, d_out). Leading dims (stacked
    experts, scan-layer stacks) pass through untouched.
    """
    if not (1 <= bits <= 8):
        raise ValueError(f"bits must be in [1, 8], got {bits}")
    lead, (d_in, d_out) = codes.shape[:-2], codes.shape[-2:]
    if d_in % PACK_GROUP != 0:
        raise ValueError(f"d_in={d_in} must be a multiple of {PACK_GROUP}")
    c = codes.astype(jnp.uint32).reshape(
        lead + (d_in // PACK_GROUP, PACK_GROUP, d_out))
    # Accumulate 8 values of `bits` bits into one little-endian 64-bit lane,
    # materialized as two uint32 halves to stay in 32-bit-friendly ops.
    lo = jnp.zeros(lead + (d_in // PACK_GROUP, d_out), jnp.uint32)
    hi = jnp.zeros(lead + (d_in // PACK_GROUP, d_out), jnp.uint32)
    for k in range(PACK_GROUP):
        s = k * bits
        v = c[..., k, :]
        if s < 32:
            lo = lo | (v << jnp.uint32(s))
            if s + bits > 32:  # straddles the 32-bit boundary
                hi = hi | (v >> jnp.uint32(32 - s))
        else:
            hi = hi | (v << jnp.uint32(s - 32))
    # Emit `bits` little-endian bytes of the 64-bit lane.
    out = []
    for byte_idx in range(bits):
        bit_off = byte_idx * 8
        if bit_off < 32:
            b = (lo >> jnp.uint32(bit_off)) & jnp.uint32(0xFF)
            if bit_off + 8 > 32:
                b = b | ((hi << jnp.uint32(32 - bit_off)) & jnp.uint32(0xFF))
        else:
            b = (hi >> jnp.uint32(bit_off - 32)) & jnp.uint32(0xFF)
        out.append(b.astype(jnp.uint8))
    packed = jnp.stack(out, axis=-2)  # (..., d_in//8, bits, d_out)
    return packed.reshape(lead + (d_in // PACK_GROUP * bits, d_out))


def unpack(packed: jax.Array, bits: int, d_in: int) -> jax.Array:
    """Inverse of :func:`pack`. Returns uint8 codes (..., d_in, d_out)."""
    if not (1 <= bits <= 8):
        raise ValueError(f"bits must be in [1, 8], got {bits}")
    n_units = d_in // PACK_GROUP
    lead, d_out = packed.shape[:-2], packed.shape[-1]
    p = packed.reshape(lead + (n_units, bits, d_out)).astype(jnp.uint32)
    # Rebuild the 64-bit lane (as two uint32 halves) from little-endian bytes.
    lo = jnp.zeros(lead + (n_units, d_out), jnp.uint32)
    hi = jnp.zeros(lead + (n_units, d_out), jnp.uint32)
    for byte_idx in range(bits):
        bit_off = byte_idx * 8
        b = p[..., byte_idx, :]
        if bit_off < 32:
            lo = lo | (b << jnp.uint32(bit_off))
            if bit_off + 8 > 32:
                hi = hi | (b >> jnp.uint32(32 - bit_off))
        else:
            hi = hi | (b << jnp.uint32(bit_off - 32))
    mask = jnp.uint32(2 ** bits - 1)
    vals = []
    for k in range(PACK_GROUP):
        s = k * bits
        if s + bits <= 32:
            v = (lo >> jnp.uint32(s)) & mask
        elif s >= 32:
            v = (hi >> jnp.uint32(s - 32)) & mask
        else:  # straddle
            v = ((lo >> jnp.uint32(s)) | (hi << jnp.uint32(32 - s))) & mask
        vals.append(v)
    codes = jnp.stack(vals, axis=-2)  # (..., n_units, 8, d_out)
    return codes.reshape(lead + (d_in, d_out)).astype(jnp.uint8)


def pack_nibbles(codes: jax.Array) -> jax.Array:
    """Pack signed int4 codes (values in [-8, 7]) two-per-byte along the
    LAST axis: (..., D) -> (..., D // 2) int8.

    Byte ``j`` holds value ``2j`` in its low nibble and value ``2j + 1`` in
    its high nibble, so a contiguous D-vector stays contiguous packed — the
    kv4 cache layout the flash kernels read tile-by-tile.
    """
    d = codes.shape[-1]
    if d % 2 != 0:
        raise ValueError(f"pack_nibbles needs an even last axis (two codes "
                         f"per byte); got D={d}")
    c = codes.astype(jnp.int32) & 0xF
    lo = c[..., 0::2]
    hi = c[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.int8)


def nibble_halves(packed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The signed low and high nibbles of (..., D // 2) int8 bytes, each
    (..., D // 2) int32 in [-8, 7]: byte ``j`` holds dims ``2j`` (low) and
    ``2j + 1`` (high).

    Two arithmetic shifts per byte — the int8 -> int32 upcast already
    sign-extends bit 7, so ``>> 4`` yields the signed high nibble and
    ``<< 28 >> 28`` the signed low nibble.  Cheap enough to run in-register
    inside the flash kernels' per-tile dequant epilogue.
    """
    xi = packed.astype(jnp.int32)
    return (xi << 28) >> 28, xi >> 4


def unpack_nibbles(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_nibbles`: (..., D // 2) int8 -> (..., D) int32
    with values sign-extended back to [-8, 7] (:func:`nibble_halves`
    interleaved along D)."""
    lo, hi = nibble_halves(packed)
    d2 = packed.shape[-1]
    return jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], d2 * 2)
