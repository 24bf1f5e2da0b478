"""Pallas TPU kernel: fused sub-byte dequantize + matmul (weight-only path).

    y = x @ dequant(packed_w)          x: (M, K) bf16/f32
                                       packed_w: (K // 8 * bits, N) uint8
                                       scale/zp: (K // group, N) f32

TPU adaptation of the paper's deployment story (GPU int4 kernels): the
quantized weight stays packed in HBM and streams through VMEM at 1/4 the
bf16 bandwidth; nibbles are unpacked with VREG shift/mask ops and fed to the
MXU as bf16 tiles with fp32 accumulation. Block tiling:

    grid (M/bm, N/bn, K/bk)
    x block       (bm, bk)            VMEM
    packed block  (bk // 8 * bits, bn) VMEM   (same K-major stream order)
    scale/zp      (bk // group, bn)   VMEM
    acc scratch   (bm, bn) f32        VMEM, written to y on the last k step

Matmul dims are multiples of the 128x128 MXU tile by construction
(bm=bn=128, bk=512 defaults). Supported bits: 2, 4, 8 (3-bit is a storage
format only — deployment unpacks it offline; see DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import ceil_div

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 512


def _unpack_block(p: jax.Array, bits: int, bk: int) -> jax.Array:
    """(bk // 8 * bits, bn) uint8 -> (bk, bn) uint8 codes (little-endian
    8-value groups, matching repro.core.packing)."""
    n_units = bk // 8
    bn = p.shape[-1]
    pu = p.reshape(n_units, bits, bn).astype(jnp.uint32)
    vals = []
    for j in range(8):                       # j-th value of each unit
        bit_off = j * bits
        byte_idx = bit_off // 8
        shift = bit_off % 8
        v = (pu[:, byte_idx, :] >> jnp.uint32(shift))
        if shift + bits > 8:                 # straddles into the next byte
            v = v | (pu[:, byte_idx + 1, :] << jnp.uint32(8 - shift))
        vals.append(v & jnp.uint32(2 ** bits - 1))
    codes = jnp.stack(vals, axis=1)          # (n_units, 8, bn)
    return codes.reshape(bk, bn)


def _kernel(x_ref, p_ref, s_ref, z_ref, o_ref, acc_ref, *, bits: int,
            group: int, bk: int, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # codes < 2**bits: the int32 hop is exact, and Mosaic has no
    # uint32 -> f32 cast
    codes = _unpack_block(p_ref[...], bits, bk).astype(jnp.int32).astype(
        jnp.float32)
    scale = s_ref[...].astype(jnp.float32)       # (bk // group, bn)
    zp = z_ref[...].astype(jnp.float32)
    gk = bk // scale.shape[0]
    w = (codes.reshape(scale.shape[0], gk, -1) - zp[:, None, :]) \
        * scale[:, None, :]
    w = w.reshape(bk, -1).astype(x_ref.dtype)
    acc_ref[...] += jnp.dot(x_ref[...], w,
                            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "bm",
                                             "bn", "bk", "interpret"))
def dequant_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
                   zp: jax.Array, *, bits: int, group_size: int,
                   bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                   bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """Fused dequant-matmul via pallas_call. Requires M % bm == K % bk ==
    N % bn == 0 and group_size % ... — the ops.py wrapper handles padding
    and block-size selection."""
    m, k = x.shape
    n = packed.shape[-1]
    g = group_size if group_size else k
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bk % g == 0 or g % bk == 0, (bk, g)
    rows_per_bk = bk // 8 * bits
    sg = max(bk // g, 1)
    n_k = k // bk

    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, group=g, bk=bk, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((rows_per_bk, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((sg, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((sg, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, packed, scale, zp)
