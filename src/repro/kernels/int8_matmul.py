"""Pallas TPU kernel: w8a8 int matmul with fused dynamic activation quant.

    y = dequant( quant8(x) @ w_q )     x: (M, K) bf16/f32
                                       w_q: (K, N) int8
                                       w_scale: (N,) f32 per-channel

Three kernels:
  * ``int8_matmul``      — takes pre-quantized activations (x_q, x_scale);
  * ``w8a8_matmul``      — fuses the per-token max/scale/round prologue, so
                           activations stream HBM->VMEM once in bf16 and hit
                           the MXU as int8 (v5e int8 path = 2x bf16 rate);
  * ``w4a8_matmul``      — the packed-QTensor weight-activation path: the
                           same fused activation prologue, plus in-kernel
                           unpack of sub-byte weight codes to int8 lanes and
                           the per-group scale/zero-point epilogue.

The w4a4 deployment (paper Table 3) uses these kernels too: int4 values
live in int8 lanes on the MXU (no int4 datapath on v5e); the *memory* win
comes from the packed weight storage, the *compute* win from the int8 MXU
rate — see DESIGN.md §3 hardware adaptation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dequant_matmul import _unpack_block

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 128, 128, 512


def _int8_kernel(xq_ref, xs_ref, w_ref, ws_ref, o_ref, acc_ref, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xq_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ki == n_k - 1)
    def _done():
        out = acc_ref[...].astype(jnp.float32) \
            * xs_ref[...].astype(jnp.float32) \
            * ws_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def int8_matmul(x_q: jax.Array, x_scale: jax.Array, w_q: jax.Array,
                w_scale: jax.Array, *, bm: int = DEFAULT_BM,
                bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """x_q (M, K) int8, x_scale (M, 1) f32, w_q (K, N) int8, w_scale (N,)."""
    m, k = x_q.shape
    n = w_q.shape[-1]
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    n_k = k // bk
    ws2d = w_scale.reshape(1, n)

    return pl.pallas_call(
        functools.partial(_int8_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bm, 1), lambda i, j, ki: (i, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((1, bn), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, x_scale, w_q, ws2d)


def _w8a8_kernel(x_ref, w_ref, ws_ref, o_ref, acc_ref, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-(token, K-slab) dynamic quantization: each slab's contribution is
    # dequantized with its own scale before accumulation, so partial sums
    # add exactly — finer-grained (error <=) than whole-row scales.
    xf = x_ref[...].astype(jnp.float32)
    slab_max = jnp.maximum(jnp.max(jnp.abs(xf), axis=1, keepdims=True), 1e-8)
    scale = slab_max / 127.0
    x_q = jnp.clip(jnp.round(xf / scale), -128, 127).astype(jnp.int8)
    part = jax.lax.dot_general(
        x_q, w_ref[...], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)
    acc_ref[...] += part * scale

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * ws_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def w8a8_matmul(x: jax.Array, w_q: jax.Array, w_scale: jax.Array, *,
                bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """Fused dynamic per-token int8 quant + matmul.

    NOTE: per-K-slab scale with running-max rescaling is *exactly* the
    per-token whole-row quantizer when n_k == 1 (bk >= K); for n_k > 1 it is
    a slightly finer-grained variant (per-slab scales) whose error is <= the
    whole-row scheme — tests compare against the ref under bk >= K and
    against an error bound otherwise.
    """
    m, k = x.shape
    n = w_q.shape[-1]
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    n_k = k // bk
    ws2d = w_scale.reshape(1, n)
    return pl.pallas_call(
        functools.partial(_w8a8_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((1, bn), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_q, ws2d)


def _w4a8_kernel(x_ref, as_ref, p_ref, s_ref, z_ref, o_ref, acc_ref, *,
                 bits: int, a_bits: int, group: int, bk: int, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # fused activation prologue: this K-slab's codes in int8 lanes, on the
    # token's whole-row scale (a_bits=4 uses the [-8, 7] sub-range)
    qmax = 2.0 ** (a_bits - 1) - 1.0
    a_scale = as_ref[...]                          # (bm, 1) f32
    xf = x_ref[...].astype(jnp.float32)
    x_q = jnp.clip(jnp.round(xf / a_scale), -qmax - 1.0, qmax
                   ).astype(jnp.int8)

    # unpack sub-byte codes and center by off = 2^(bits-1) so even 8-bit
    # codes fit int8 lanes; the asymmetric zero-point becomes a per-group
    # row-sum correction:  x_q (c - zp) = x_q (c - off) + rowsum(x_q)(off - zp)
    off = 2 ** (bits - 1)
    codes = _unpack_block(p_ref[...], bits, bk).astype(jnp.int32)
    c8 = (codes - off).astype(jnp.int8)
    scale = s_ref[...].astype(jnp.float32)        # (bk // group, bn)
    zp = z_ref[...].astype(jnp.float32)
    xq32 = x_q.astype(jnp.int32)
    for gi in range(bk // group):                 # static unroll over groups
        sl = slice(gi * group, (gi + 1) * group)
        dot = jax.lax.dot_general(
            x_q[:, sl], c8[sl],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        rsum = jnp.sum(xq32[:, sl], axis=1, keepdims=True)
        # groups accumulate in K order into one f32 sum, as in the oracle
        acc_ref[...] += scale[gi][None, :] * (
            dot.astype(jnp.float32)
            + rsum.astype(jnp.float32) * (off - zp[gi])[None, :])

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * a_scale).astype(o_ref.dtype)


def act_scale(x: jax.Array, a_bits: int) -> jax.Array:
    """Per-token dynamic symmetric activation scale over the whole row:
    x (M, K) -> (M, 1) f32.  Shared by the kernel wrapper and the oracle,
    so both quantize on the same grid however K is tiled."""
    qmax = 2.0 ** (a_bits - 1) - 1.0
    xf = x.astype(jnp.float32)
    return jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                       1e-8) / qmax


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "a_bits",
                                             "bm", "bn", "bk", "interpret"))
def w4a8_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
                zp: jax.Array, *, bits: int, group_size: int,
                a_bits: int = 8, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """Fused dynamic activation quant + packed sub-byte weight matmul.

    x (M, K) float; packed (K // 8 * bits, N) uint8 codes; scale/zp
    (K // group, N) float32 per-group affine grid (the QTensor fields).
    Returns (M, N) float32: the caller rounds to its activation dtype
    with an XLA convert, the same op its oracle program uses.
    Despite the name this is the general w{2,4,8}a{4,8} kernel — codes are
    widened to int8 MXU lanes in-kernel whatever ``bits`` is.

    The per-token activation scale spans the whole row whatever ``bk``
    (:func:`act_scale`, computed once before the kernel and streamed as a
    (bm, 1) block), so interpret mode is bit-identical to
    ``ref.quant_matmul_ref``.
    """
    m, k = x.shape
    n = packed.shape[-1]
    g = group_size if group_size else k
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bk % g == 0 and bk % 8 == 0, (bk, g)
    rows_per_bk = bk // 8 * bits
    sg = bk // g
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_w4a8_kernel, bits=bits, a_bits=a_bits, group=g,
                          bk=bk, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bm, 1), lambda i, j, ki: (i, 0)),
            pl.BlockSpec((rows_per_bk, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((sg, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((sg, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, act_scale(x, a_bits), packed, scale, zp)
