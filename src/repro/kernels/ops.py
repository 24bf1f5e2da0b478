"""Backend-dispatching jit wrappers around the Pallas kernels.

``use_pallas='auto'`` (default) compiles the kernels on TPU and falls back
to the pure-jnp reference math on CPU/GPU (identical results — the refs ARE
the oracles). ``'interpret'`` forces pallas interpret mode (kernel body
executed in Python — used by the test suite to validate the kernels on
CPU). Wrappers also handle M-padding so callers can pass ragged token
counts.
"""
from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as _sharding
from repro.core.qtensor import QTensor
from repro.kernels import dequant_matmul as dq
from repro.kernels import flash_decode as fd
from repro.kernels import flash_prefill as fp
from repro.kernels import int8_matmul as i8
from repro.kernels import quantize_pack as qp
from repro.kernels import ref
from repro.utils import next_multiple

Mode = Literal["auto", "pallas", "interpret", "ref"]


# ---------------------------------------------------------------------------
# tensor-parallel (shard_map) dispatch for the flash kernels
# ---------------------------------------------------------------------------
# With a mesh bound via ``repro.sharding.use_mesh``, the attention kernels
# run under ``shard_map`` with the KV-head dim split across the "model"
# axis: GQA folding already gives every KV head its own q block, so each
# shard runs the UNCHANGED kernel body on its head slice (per-shard Hkv,
# head-sliced cache pools) and the per-shard outputs concatenate along the
# head dim — the only collective of the attention block is the all-gather
# GSPMD inserts afterwards for the (replicated-K) wo matmul.  Page tables,
# lengths and offsets are replicated: pages stay device-local, so the
# page-table gather in the kernel's BlockSpec index map never crosses
# devices (DESIGN.md §13).  The batch dim additionally splits over "data"
# when it divides.

def _tp_mesh(hq: int, hkv: int, b: int):
    """(mesh, dp_axis) when the bound mesh head-splits these shapes over
    "model"; None when unsharded dispatch should run (no mesh, size-1
    model axis, or head counts that do not divide)."""
    mesh = _sharding.current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    m = mesh.shape["model"]
    if m <= 1 or hq % m or hkv % m:
        return None
    dp = ("data" if "data" in mesh.axis_names and mesh.shape["data"] > 1
          and b % mesh.shape["data"] == 0 else None)
    return mesh, dp


def _tp_columns(m: int, n: int):
    """(mesh, dp_axis) when the bound mesh splits a packed matmul's output
    columns over "model"; None for unsharded dispatch.  A Mosaic kernel
    cannot be partitioned by GSPMD, so under a mesh every packed matmul
    runs under shard_map as a column-parallel call: x whole along K on
    every shard, the weight's codes/scales/zero-points sliced along N
    (a local slice where the weight is stored replicated), each output
    column computed in full on one shard — the same math as one device."""
    mesh = _sharding.current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    mm = mesh.shape["model"]
    if mm <= 1 or n % mm:
        return None
    dp = ("data" if "data" in mesh.axis_names and mesh.shape["data"] > 1
          and m % mesh.shape["data"] == 0 else None)
    return mesh, dp


def _tp_matmul(fn, x2, packed, scale, zp):
    """Run ``fn(x2, packed, scale, zp)`` column-parallel when a mesh is
    bound (see :func:`_tp_columns`), else directly."""
    tp = _tp_columns(x2.shape[0], packed.shape[-1])
    if tp is None:
        return fn(x2, packed, scale, zp)
    mesh, dp = tp
    col = P(None, "model")
    out = _tp_call(
        mesh, lambda a: fn(a["x"], a["packed"], a["scale"], a["zp"]),
        {"x": x2, "packed": packed, "scale": scale, "zp": zp},
        {"x": P(dp, None), "packed": col, "scale": col, "zp": col},
        P(dp, "model"))
    # gather whole rows back: every op between the kernels (norms, RoPE,
    # residual adds, KV quantize-on-write) then reduces over the same
    # values in the same order as on one device — a GSPMD-partitioned
    # norm would sum in another order, and one flipped bf16/int4 rounding
    # is enough to change a greedy token
    return jax.lax.with_sharding_constraint(
        out, NamedSharding(mesh, P(dp, None)))


def _kv_entry_specs(k_scale, dp, paged: bool):
    """PartitionSpecs for cache entries: head dim (index 2) over "model";
    linear entries batch-split over ``dp``, pool entries replicated over
    "data" (the page pool has no batch dim)."""
    lead = (None, None) if paged else (dp, None)
    kv = P(*lead, "model", None)
    sc = (None if k_scale is None else
          P(*lead, "model", *([None] * (k_scale.ndim - 3))))
    return kv, sc


def _tp_call(mesh, fn, args: dict, specs: dict, out_spec: P):
    """Run ``fn`` over dict-packed args under shard_map (dropping entries
    that are None so the arg/spec pytrees stay congruent)."""
    live = {n: a for n, a in args.items() if a is not None}
    live_specs = {n: specs[n] for n in live}
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=(live_specs,),
                            out_specs=out_spec, check_vma=False)
    return wrapped(live)


def _materialize(x):
    """``x`` as a stored array: XLA may not fuse its producer into its
    consumer.  A kernel reads and writes whole arrays in their storage
    dtype (bf16 activations stay rounded to bf16 in HBM); without this
    boundary XLA may keep an activation in f32 across a
    ``.astype(bfloat16)`` that it fuses into the oracle's quantizer or
    into ``act_scale``, and the two programs would then quantize other
    values.  Every op below passes its activations in and out through
    here, in every mode."""
    return jax.lax.optimization_barrier(x)


def _backend() -> str:
    return jax.default_backend()


def _resolve(mode: Mode) -> str:
    if mode == "auto":
        return "pallas" if _backend() == "tpu" else "ref"
    return mode


def _pick_bm(m: int, bm: int) -> tuple[int, int]:
    """Pad M to a legal block multiple; small-batch decode uses one block."""
    if m >= bm:
        return next_multiple(m, bm), bm
    pad = next_multiple(m, 8)
    return pad, pad


def _clamp_blocks(k: int, n: int, blocks: dict, group: int) -> dict:
    """Clamp bk/bn to the actual problem (small miniature models)."""
    out = dict(blocks)
    bk = out.get("bk", dq.DEFAULT_BK)
    bn = out.get("bn", dq.DEFAULT_BN)
    if k % bk != 0:
        bk = k            # single K block (K of the miniatures is small)
    if group and bk % group != 0 and group % bk != 0:
        bk = k
    if n % bn != 0:
        bn = n
    out["bk"], out["bn"] = bk, bn
    return out


def dequant_matmul(x, packed, scale=None, zp=None, *,
                   bits: Optional[int] = None,
                   group_size: Optional[int] = None,
                   mode: Mode = "auto", **blocks):
    """y = x @ dequant(packed). x (..., K); returns (..., N).

    ``packed`` is either a :class:`repro.core.qtensor.QTensor` (scale / zp /
    bits / group_size taken from it — the deployment fast path) or a raw
    packed uint8 array with explicit ``scale``/``zp``/``bits``/``group_size``.
    """
    if isinstance(packed, QTensor):
        qt = packed
        packed, scale, zp = qt.packed, qt.scale, qt.zp
        # the QTensor's static metadata is authoritative: explicit kwargs
        # that disagree would unpack the codes on the wrong bit layout
        if bits is not None and bits != qt.bits:
            raise ValueError(f"bits={bits} conflicts with QTensor.bits="
                             f"{qt.bits}")
        if group_size is not None and group_size != qt.group_size:
            raise ValueError(f"group_size={group_size} conflicts with "
                             f"QTensor.group_size={qt.group_size}")
        bits, group_size = qt.bits, qt.group_size
    if bits is None or group_size is None or scale is None or zp is None:
        raise TypeError("dequant_matmul needs a QTensor or explicit "
                        "packed/scale/zp/bits/group_size")
    lead = x.shape[:-1]
    k = x.shape[-1]
    impl = _resolve(mode)

    def run(x2, packed, scale, zp):
        if impl == "ref" or bits == 3:  # 3-bit: storage-only format, ref math
            return ref.dequant_matmul_ref(x2, packed, scale, zp, bits=bits,
                                          group_size=group_size)
        m = x2.shape[0]
        bm = blocks.get("bm", dq.DEFAULT_BM)
        m_pad, bm = _pick_bm(m, bm)
        blk = _clamp_blocks(k, packed.shape[-1],
                            {n: v for n, v in blocks.items() if n != "bm"},
                            group_size)
        x_p = jnp.pad(x2, ((0, m_pad - m), (0, 0))) if m_pad != m else x2
        out = dq.dequant_matmul(x_p, packed, scale, zp, bits=bits,
                                group_size=group_size, bm=bm,
                                interpret=(impl == "interpret"), **blk)
        return out[:m]

    out = _tp_matmul(run, _materialize(x.reshape(-1, k)), packed, scale, zp)
    return _materialize(out).reshape(*lead, out.shape[-1])


def quant_matmul(x, qt: QTensor, *, a_bits: int = 8, mode: Mode = "auto",
                 **blocks):
    """y = dyn_quant(x, a_bits) @ dequant(qt) — the W·A serving fast path.

    ``qt`` must be a :class:`repro.core.qtensor.QTensor`; its static
    bits/group_size select the in-kernel unpack layout. ``a_bits >= 16``
    degrades to the weight-only :func:`dequant_matmul` path (fp
    activations); ``a_bits < 16`` routes through the fused
    :func:`repro.kernels.int8_matmul.w4a8_matmul` kernel (pallas /
    interpret) or its ref oracle — activations are quantized per-token
    inside the kernel, never materialized in int8 in HBM.

    3-bit weights are a storage-only format (no in-kernel unpack): ref math.
    """
    if not isinstance(qt, QTensor):
        raise TypeError("quant_matmul needs a QTensor weight; raw packed "
                        "arrays go through dequant_matmul")
    if a_bits >= 16:
        return dequant_matmul(x, qt, mode=mode, **blocks)
    if not 2 <= a_bits <= 8:
        # quantized codes live in int8 lanes: 9..15 would wrap on the cast
        raise ValueError(f"a_bits={a_bits} unsupported: use 2..8 (int8 "
                         "lanes) or >= 16 (fp activations)")
    lead = x.shape[:-1]
    k = x.shape[-1]
    impl = _resolve(mode)
    bits = qt.bits
    g = qt.group_size or k          # 0 = per-channel: one K-wide group

    def run(x2, packed, scale, zp):
        # kernel and oracle both return f32; the one rounding to the
        # activation dtype is XLA's convert, the same op in every program
        if impl == "ref" or bits == 3:
            return ref.quant_matmul_ref(x2, packed, scale, zp, bits=bits,
                                        group_size=qt.group_size,
                                        a_bits=a_bits,
                                        out_dtype=jnp.float32
                                        ).astype(x2.dtype)
        m = x2.shape[0]
        m_pad, bm = _pick_bm(m, blocks.get("bm", i8.DEFAULT_BM))
        n = packed.shape[-1]
        bk = blocks.get("bk", i8.DEFAULT_BK)
        bn = blocks.get("bn", i8.DEFAULT_BN)
        # NOT _clamp_blocks: this path requires the strict bk % g == 0 (the
        # kernel's scale/zp BlockSpec steps one group-slab per K block; the
        # weight-only kernel also tolerates g % bk == 0, this one does not)
        if k % bk != 0 or bk % g != 0:
            bk = k   # single K block
        if n % bn != 0:
            bn = n
        x_p = jnp.pad(x2, ((0, m_pad - m), (0, 0))) if m_pad != m else x2
        out = i8.w4a8_matmul(x_p, packed, scale, zp, bits=bits,
                             group_size=g, a_bits=a_bits, bm=bm, bn=bn,
                             bk=bk, interpret=(impl == "interpret"))
        return out[:m].astype(x2.dtype)

    out = _tp_matmul(run, _materialize(x.reshape(-1, k)), qt.packed,
                     qt.scale, qt.zp)
    return _materialize(out).reshape(*lead, out.shape[-1])


def w8a8_matmul(x, w_q, w_scale, *, mode: Mode = "auto", **blocks):
    """y = dyn_quant8(x) @ w_q * scales. x (..., K); returns (..., N)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    impl = _resolve(mode)
    if impl == "ref":
        out = ref.w8a8_dynamic_ref(x2, w_q, w_scale)
    else:
        bm = blocks.pop("bm", i8.DEFAULT_BM)
        m_pad, bm = _pick_bm(m, bm)
        blocks = _clamp_blocks(k, w_q.shape[-1], blocks, 0)
        x_p = jnp.pad(x2, ((0, m_pad - m), (0, 0))) if m_pad != m else x2
        out = i8.w8a8_matmul(x_p, w_q, w_scale, bm=bm,
                             interpret=(impl == "interpret"), **blocks)
        out = out[:m]
    return out.reshape(*lead, out.shape[-1])


def flash_decode(q, kv, cur_len, *, scale=None, block_kv: Optional[int] = None,
                 page_table=None, mode: Mode = "auto"):
    """One-token decode attention over the KV cache **as stored**.

    q (B, 1, Hq, D); ``kv`` is the cache tuple exactly as the serving model
    carries it — ``(k, v)`` fp, or ``(k, v, k_scale, v_scale)``: kv8 int8
    codes (B, S, Hkv, D) + per-(token, head) f32 scales (B, S, Hkv), or kv4
    packed nibbles (B, S, Hkv, D//2) + bf16 block-32 scales
    (B, S, Hkv, D//32) — the 4D (code-rank) scale is what marks the packed
    format. ``cur_len`` (B,) int32 counts valid positions (the just-written
    token included).  Returns (B, 1, Hq, D) in q.dtype.

    **Paged cache**: with ``page_table`` (B, max_pages_per_seq) int32, the
    kv entries are page *pools* — (num_pages, page_size, Hkv, D) codes and
    (num_pages, page_size, Hkv) scales — and the fused kernel walks the
    page table a block of pages per grid step (each page of all heads one
    DMA per tensor, gathered in the BlockSpec index map, and one
    online-softmax step of every head; ``block_kv`` is ignored). ``ref`` runs
    :func:`repro.kernels.ref.flash_decode_paged_ref` (bit-identical to
    interpret mode under jit); ``auto`` off-TPU gathers the table with XLA
    (``pool[page_table]``) and falls back to ``decode_attention`` — the one
    paged path that materializes the logical cache.

    Modes: ``pallas``/``interpret`` run the fused
    :func:`repro.kernels.flash_decode.flash_decode` kernel — the linear
    cache cut into pages of ``block_kv`` tokens and walked like a pool,
    per-page in-register dequant, length-masked, no full-cache fp
    materialization. ``ref`` runs :func:`repro.kernels.ref.flash_decode_ref`,
    the page-walking oracle (bit-identical to interpret mode under jit;
    still page-at-a-time, so it also never materializes the full fp cache).
    ``auto`` compiles the kernel on TPU and otherwise falls back to the
    portable :func:`repro.models.attention.decode_attention` XLA path —
    the one place the quantized cache is dequantized in full (CPU/GPU only;
    the fused path exists to avoid exactly that on TPU).

    ``block_kv`` (the linear cache's page) defaults to
    ``flash_decode.DEFAULT_BLOCK_KV``; whenever S is not a multiple of it
    (miniature configs, a ragged ``max_len``) the page is the largest
    divisor of S below it (``flash_decode.linear_page``); head_dim needs
    no clamping — it is the innermost (lane) dimension at any size.
    """
    k, v, k_scale, v_scale = _unpack_kv(kv)
    b, t, hq, d = q.shape
    if t != 1:
        raise ValueError(f"flash_decode is a one-token decode kernel; got "
                         f"T={t}")
    # auto off-TPU falls back to XLA decode_attention, NOT the tile oracle:
    # the oracle is the test contract, the fallback is the fast portable path
    impl = ("pallas" if _backend() == "tpu" else "xla") if mode == "auto" \
        else mode
    q = _materialize(q)
    dt = q.dtype
    if impl != "xla":
        # the kernels and their oracles take and return f32 rows; the one
        # rounding to the activation dtype is XLA's, in every program
        q = q.astype(jnp.float32)
    tp = _tp_mesh(hq, k.shape[2], b)
    if tp is not None:
        mesh, dp = tp
        paged = page_table is not None
        kv_sp, sc_sp = _kv_entry_specs(k_scale, dp, paged)
        args = {"q": q, "k": k, "v": v, "k_scale": k_scale,
                "v_scale": v_scale, "cur_len": jnp.asarray(cur_len),
                "page_table": None if page_table is None
                else jnp.asarray(page_table)}
        specs = {"q": P(dp, None, "model", None), "k": kv_sp, "v": kv_sp,
                 "k_scale": sc_sp, "v_scale": sc_sp, "cur_len": P(dp),
                 "page_table": P(dp, None)}
        fn = lambda a: _flash_decode_dispatch(
            a["q"], a["k"], a["v"], a.get("k_scale"), a.get("v_scale"),
            a["cur_len"], a.get("page_table"), scale, impl, block_kv)
        return _materialize(_tp_call(
            mesh, fn, args, specs, P(dp, None, "model", None)).astype(dt))
    return _materialize(_flash_decode_dispatch(
        q, k, v, k_scale, v_scale, cur_len, page_table, scale, impl,
        block_kv).astype(dt))


def _flash_decode_dispatch(q, k, v, k_scale, v_scale, cur_len, page_table,
                           scale, impl, block_kv):
    """Impl-dispatch half of :func:`flash_decode`; shapes are read locally
    so the same body runs unsharded or as the per-shard shard_map region."""
    b, _, hq, d = q.shape
    if page_table is not None:
        return _flash_decode_paged(q, k, v, k_scale, v_scale, page_table,
                                   cur_len, scale, impl)
    s, hkv = k.shape[1], k.shape[2]
    if impl == "xla":
        from repro.models import attention as attn_lib
        if k_scale is not None and k_scale.ndim == k.ndim:
            # kv4: the one path that materializes the dequantized cache
            k = qp.kv4_dequant(k, k_scale).astype(q.dtype)
            v = qp.kv4_dequant(v, v_scale).astype(q.dtype)
        elif k_scale is not None:
            k = (k.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
            v = (v.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
        out = attn_lib.decode_attention(q, k.astype(q.dtype),
                                        v.astype(q.dtype), cur_len,
                                        scale=scale)
        # fused-path contract: zero-length rows return zeros (an all-masked
        # softmax would otherwise emit the uniform mean of the slots)
        return jnp.where((cur_len > 0)[:, None, None, None], out,
                         jnp.zeros_like(out))
    # the linear cache's page: whole pages of at most block_kv tokens
    # (miniature / ragged max_len take the largest divisor of S)
    bkv = fd.linear_page(s, block_kv or fd.DEFAULT_BLOCK_KV)
    q4 = q.reshape(b, hkv, hq // hkv, d)
    if impl == "ref":
        out = ref.flash_decode_ref(q4, k, v, cur_len, k_scale, v_scale,
                                   scale=scale, block_kv=bkv)
    else:
        out = fd.flash_decode(q4, k, v, cur_len, k_scale, v_scale,
                              scale=scale, block_kv=bkv,
                              interpret=(impl == "interpret"))
    return out.reshape(b, 1, hq, d)


def _flash_decode_paged(q, k, v, k_scale, v_scale, page_table, cur_len,
                        scale, impl):
    """Paged dispatch half of :func:`flash_decode` (kv entries are pools)."""
    b, _, hq, d = q.shape
    num_pages, ps, hkv = k.shape[0], k.shape[1], k.shape[2]
    packed = k_scale is not None and k_scale.ndim == k.ndim
    dk = d // 2 if packed else d
    if k.shape != (num_pages, ps, hkv, dk):
        raise ValueError(f"paged kv pools must be (P, page_size, Hkv, "
                         f"{'D//2 packed' if packed else 'D'}); got "
                         f"{k.shape}")
    if page_table.ndim != 2 or page_table.shape[0] != b:
        raise ValueError(f"page_table must be (B, max_pages_per_seq); got "
                         f"{page_table.shape} for B={b}")
    if impl == "xla":
        from repro.models import attention as attn_lib
        pt = jnp.maximum(page_table, 0)
        s_log = page_table.shape[1] * ps
        kk = k[pt].reshape(b, s_log, hkv, dk)
        vv = v[pt].reshape(b, s_log, hkv, dk)
        if packed:
            ks = k_scale[pt].reshape(b, s_log, hkv, -1)
            vs = v_scale[pt].reshape(b, s_log, hkv, -1)
            kk = qp.kv4_dequant(kk, ks).astype(q.dtype)
            vv = qp.kv4_dequant(vv, vs).astype(q.dtype)
        elif k_scale is not None:
            ks = k_scale[pt].reshape(b, s_log, hkv)
            vs = v_scale[pt].reshape(b, s_log, hkv)
            kk = (kk.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
            vv = (vv.astype(jnp.float32) * vs[..., None]).astype(q.dtype)
        out = attn_lib.decode_attention(q, kk.astype(q.dtype),
                                        vv.astype(q.dtype), cur_len,
                                        scale=scale)
        # fused-path contract: zero-length rows return zeros
        return jnp.where((cur_len > 0)[:, None, None, None], out,
                         jnp.zeros_like(out))
    q4 = q.reshape(b, hkv, hq // hkv, d)
    if impl == "ref":
        out = ref.flash_decode_paged_ref(q4, k, v, page_table, cur_len,
                                         k_scale, v_scale, scale=scale)
    else:
        out = fd.flash_decode_paged(q4, k, v, page_table, cur_len,
                                    k_scale, v_scale, scale=scale,
                                    interpret=(impl == "interpret"))
    return out.reshape(b, 1, hq, d)


def _unpack_kv(kv):
    if len(kv) == 4:
        return kv
    if len(kv) == 2:
        return kv[0], kv[1], None, None
    raise TypeError(f"kv must be (k, v) or (k, v, k_scale, v_scale), "
                    f"got {len(kv)} entries")


def flash_prefill(q, kv, offset, chunk_len, *, scale=None,
                  block_kv: Optional[int] = None, page_table=None,
                  mode: Mode = "auto"):
    """Chunked causal prefill attention over the KV cache **as stored**.

    q (B, C, Hq, D) — a C-token query chunk whose token ``i`` sits at
    absolute position ``offset[b] + i``; ``kv`` is the cache tuple exactly
    as the serving model carries it — ``(k, v)`` fp, or ``(k, v, k_scale,
    v_scale)`` kv8 int8 codes + per-(token, head) f32 scales, or kv4
    packed nibbles + 4D bf16 block-32 scales (see :func:`flash_decode`) —
    with the chunk's
    own (quantized-on-write) K/V already stored at positions ``offset ..
    offset + chunk_len - 1``.  ``chunk_len`` (B,) int32 counts valid chunk
    rows; pad rows (``i >= chunk_len[b]``) return zeros, so idle sequences
    in a batched engine chunk step pass ``chunk_len == 0``.  Returns
    (B, C, Hq, D) in q.dtype.

    **Paged cache**: with ``page_table`` (B, max_pages_per_seq) int32, the
    kv entries are page *pools* and the fused kernel walks the page table
    (one KV tile == one page; ``block_kv`` is ignored), mirroring
    :func:`flash_decode`.

    Modes follow :func:`flash_decode`: ``pallas``/``interpret`` run the
    fused :func:`repro.kernels.flash_prefill.flash_prefill` kernel (per-
    tile in-register dequant, chunk-end-masked KV grid, no full-cache fp
    materialization); ``ref`` runs the tile-mirroring oracle
    (bit-identical to interpret mode under jit); ``auto`` compiles the
    kernel on TPU and otherwise falls back to the portable
    :func:`repro.models.attention.chunk_prefill_attention` XLA path — the
    one prefill path that materializes the dequantized fp cache.

    Splitting invariance: with a fixed cache and tile size, a row's result
    does not depend on which chunk delivered it — trailing fully-masked
    tiles are exact no-ops.  Same-shape calls are bit-identical (C == 1
    equals ``flash_decode`` bit-for-bit); different chunk sizes re-fuse
    under XLA and agree to f32 ULPs — the contract that makes chunked
    engine admission token-identical to whole-prompt prefill.
    """
    k, v, k_scale, v_scale = _unpack_kv(kv)
    b, c, hq, d = q.shape
    if c < 1:
        raise ValueError(f"flash_prefill needs a non-empty chunk; got C={c}")
    offset = jnp.asarray(offset, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    impl = ("pallas" if _backend() == "tpu" else "xla") if mode == "auto" \
        else mode
    q = _materialize(q)
    dt = q.dtype
    if impl != "xla":
        # the kernels and their oracles take and return f32 rows; the one
        # rounding to the activation dtype is XLA's, in every program
        q = q.astype(jnp.float32)
    tp = _tp_mesh(hq, k.shape[2], b)
    if tp is not None:
        mesh, dp = tp
        paged = page_table is not None
        kv_sp, sc_sp = _kv_entry_specs(k_scale, dp, paged)
        args = {"q": q, "k": k, "v": v, "k_scale": k_scale,
                "v_scale": v_scale, "offset": offset,
                "chunk_len": chunk_len,
                "page_table": None if page_table is None
                else jnp.asarray(page_table)}
        specs = {"q": P(dp, None, "model", None), "k": kv_sp, "v": kv_sp,
                 "k_scale": sc_sp, "v_scale": sc_sp, "offset": P(dp),
                 "chunk_len": P(dp), "page_table": P(dp, None)}
        fn = lambda a: _flash_prefill_dispatch(
            a["q"], a["k"], a["v"], a.get("k_scale"), a.get("v_scale"),
            a["offset"], a["chunk_len"], a.get("page_table"), scale, impl,
            block_kv)
        return _materialize(_tp_call(
            mesh, fn, args, specs, P(dp, None, "model", None)).astype(dt))
    return _materialize(_flash_prefill_dispatch(
        q, k, v, k_scale, v_scale, offset, chunk_len, page_table, scale,
        impl, block_kv).astype(dt))


def _flash_prefill_dispatch(q, k, v, k_scale, v_scale, offset, chunk_len,
                            page_table, scale, impl, block_kv):
    """Impl-dispatch half of :func:`flash_prefill`; shapes are read locally
    so the same body runs unsharded or as the per-shard shard_map region."""
    b, c, hq, d = q.shape
    if page_table is not None:
        return _flash_prefill_paged(q, k, v, k_scale, v_scale, page_table,
                                    offset, chunk_len, scale, impl)
    s, hkv = k.shape[1], k.shape[2]
    if impl == "xla":
        from repro.models import attention as attn_lib
        if k_scale is not None and k_scale.ndim == k.ndim:
            # kv4: the one path that materializes the dequantized cache
            k = qp.kv4_dequant(k, k_scale).astype(q.dtype)
            v = qp.kv4_dequant(v, v_scale).astype(q.dtype)
        elif k_scale is not None:
            k = (k.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
            v = (v.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
        return attn_lib.chunk_prefill_attention(
            q, k.astype(q.dtype), v.astype(q.dtype), offset, chunk_len,
            scale=scale)
    bkv = block_kv or fp.DEFAULT_BLOCK_KV
    if bkv > s or s % bkv != 0:
        bkv = s              # single tile (miniature / ragged max_len)
    q5 = q.reshape(b, c, hkv, hq // hkv, d).transpose(0, 2, 1, 3, 4)
    if impl == "ref":
        out = ref.flash_prefill_ref(q5, k, v, offset, chunk_len, k_scale,
                                    v_scale, scale=scale, block_kv=bkv)
    else:
        out = fp.flash_prefill(q5, k, v, offset, chunk_len, k_scale,
                               v_scale, scale=scale, block_kv=bkv,
                               interpret=(impl == "interpret"))
    return out.transpose(0, 2, 1, 3, 4).reshape(b, c, hq, d)


def _flash_prefill_paged(q, k, v, k_scale, v_scale, page_table, offset,
                         chunk_len, scale, impl):
    """Paged dispatch half of :func:`flash_prefill` (kv entries are pools)."""
    b, c, hq, d = q.shape
    num_pages, ps, hkv = k.shape[0], k.shape[1], k.shape[2]
    packed = k_scale is not None and k_scale.ndim == k.ndim
    dk = d // 2 if packed else d
    if k.shape != (num_pages, ps, hkv, dk):
        raise ValueError(f"paged kv pools must be (P, page_size, Hkv, "
                         f"{'D//2 packed' if packed else 'D'}); got "
                         f"{k.shape}")
    if page_table.ndim != 2 or page_table.shape[0] != b:
        raise ValueError(f"page_table must be (B, max_pages_per_seq); got "
                         f"{page_table.shape} for B={b}")
    if impl == "xla":
        from repro.models import attention as attn_lib
        pt = jnp.maximum(page_table, 0)
        s_log = page_table.shape[1] * ps
        kk = k[pt].reshape(b, s_log, hkv, dk)
        vv = v[pt].reshape(b, s_log, hkv, dk)
        if packed:
            ks = k_scale[pt].reshape(b, s_log, hkv, -1)
            vs = v_scale[pt].reshape(b, s_log, hkv, -1)
            kk = qp.kv4_dequant(kk, ks).astype(q.dtype)
            vv = qp.kv4_dequant(vv, vs).astype(q.dtype)
        elif k_scale is not None:
            ks = k_scale[pt].reshape(b, s_log, hkv)
            vs = v_scale[pt].reshape(b, s_log, hkv)
            kk = (kk.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
            vv = (vv.astype(jnp.float32) * vs[..., None]).astype(q.dtype)
        return attn_lib.chunk_prefill_attention(
            q, kk.astype(q.dtype), vv.astype(q.dtype), offset, chunk_len,
            scale=scale)
    q5 = q.reshape(b, c, hkv, hq // hkv, d).transpose(0, 2, 1, 3, 4)
    if impl == "ref":
        out = ref.flash_prefill_paged_ref(q5, k, v, page_table, offset,
                                          chunk_len, k_scale, v_scale,
                                          scale=scale)
    else:
        out = fp.flash_prefill_paged(q5, k, v, page_table, offset,
                                     chunk_len, k_scale, v_scale,
                                     scale=scale,
                                     interpret=(impl == "interpret"))
    return out.transpose(0, 2, 1, 3, 4).reshape(b, c, hq, d)


def quantize_pack(w, *, bits: int, group_size: int, mode: Mode = "auto",
                  **blocks):
    impl = _resolve(mode)
    if impl == "ref" or bits == 3:
        return ref.quantize_pack_ref(w, bits=bits, group_size=group_size)
    return qp.quantize_pack(w, bits=bits, group_size=group_size,
                            interpret=(impl == "interpret"), **blocks)
