"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

Bit-identity sweeps run through the shared ``tests/kernel_conformance``
harness (the same jit-wrapped interpret-vs-ref assertion the flash-decode /
flash-prefill / paged sweeps use)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kernel_conformance as kc
from repro.core.quantizer import QuantConfig, quantize_codes
from repro.kernels import ops, ref
from repro.kernels.dequant_matmul import dequant_matmul
from repro.kernels.int8_matmul import int8_matmul, w4a8_matmul, w8a8_matmul
from repro.kernels.quantize_pack import quantize_pack


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("m,k,n,g", [(64, 128, 64, 32), (128, 256, 128, 64),
                                     (64, 128, 64, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dequant_matmul_allclose(bits, m, k, n, g, dtype):
    key = jax.random.PRNGKey(bits * 1000 + m)
    w = jax.random.normal(key, (k, n), jnp.float32)
    packed, scale, zp = ref.quantize_pack_ref(w, bits=bits, group_size=g)
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k)).astype(dtype)
    y_ref = ref.dequant_matmul_ref(x, packed, scale, zp, bits=bits,
                                   group_size=g)
    y_ker = dequant_matmul(x, packed, scale, zp, bits=bits, group_size=g,
                           bm=64, bn=64, bk=128, interpret=True)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(y_ker, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("k,n,g", [(128, 64, 32), (256, 128, 128)])
def test_quantize_pack_kernel_matches_ref(bits, k, n, g):
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n), jnp.float32)
    p_ref, s_ref, z_ref = ref.quantize_pack_ref(w, bits=bits, group_size=g)
    p, s, z = quantize_pack(w, bits=bits, group_size=g, bn=n, interpret=True)
    assert (np.asarray(p) == np.asarray(p_ref)).all()
    np.testing.assert_allclose(s, s_ref, rtol=1e-6)
    np.testing.assert_allclose(z, z_ref, rtol=1e-6)


@pytest.mark.parametrize("m,k,n", [(64, 128, 64), (128, 512, 128)])
@pytest.mark.slow
def test_int8_matmul_exact(m, k, n):
    key = jax.random.PRNGKey(m + k)
    xq = jax.random.randint(key, (m, k), -128, 128).astype(jnp.int8)
    xs = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (m, 1))) + 0.1
    wq = jax.random.randint(jax.random.fold_in(key, 2), (k, n), -128, 128
                            ).astype(jnp.int8)
    ws = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (n,))) + 0.1
    y_ref = ref.int8_matmul_ref(xq, wq, xs, ws)
    y_ker = int8_matmul(xq, xs, wq, ws, bm=64, bn=64, bk=128, interpret=True)
    np.testing.assert_allclose(y_ker, y_ref, rtol=1e-5)


def test_w8a8_fused_matches_ref_single_slab():
    m, k, n = 64, 256, 64
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (m, k))
    wq = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -128, 128
                            ).astype(jnp.int8)
    ws = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (n,))) + 0.1
    y_ref = ref.w8a8_dynamic_ref(x, wq, ws)
    y_ker = w8a8_matmul(x, wq, ws, bm=64, bn=64, bk=256, interpret=True)
    np.testing.assert_allclose(y_ker, y_ref, rtol=1e-4, atol=1e-3)


@pytest.mark.slow
def test_w8a8_per_slab_error_bounded():
    """bk < K uses per-slab scales: error vs exact fp must stay below the
    whole-row scheme's worst case."""
    m, k, n = 64, 512, 64
    key = jax.random.PRNGKey(10)
    x = jax.random.normal(key, (m, k))
    wq = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -128, 128
                            ).astype(jnp.int8)
    ws = jnp.full((n,), 0.01, jnp.float32)
    y_fp = x @ wq.astype(jnp.float32) * ws[None, :]
    y_slab = w8a8_matmul(x, wq, ws, bm=64, bn=64, bk=128, interpret=True)
    rel = float(jnp.linalg.norm(y_slab - y_fp) / jnp.linalg.norm(y_fp))
    assert rel < 0.02


# ---------------------------------------------------------------------------
# fused weight-activation kernel (w4a8_matmul) vs its oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("a_bits", [4, 8])
@pytest.mark.parametrize("g", [32, 0])
def test_w4a8_matmul_bit_identical_to_ref(bits, a_bits, g):
    """K == one K block (whole-row activation scale, the dispatcher clamp
    for K < DEFAULT_BK): quant_matmul in interpret mode must be
    BIT-IDENTICAL to the ref oracle — same op sequence, same XLA
    fusions."""
    m, k, n = 64, 128, 64
    key = jax.random.PRNGKey(bits * 100 + a_bits)
    qt = quantize_codes(jax.random.normal(key, (k, n), jnp.float32),
                        QuantConfig(w_bits=bits, group_size=g, lwc=False))
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k), jnp.float32)
    kc.assert_interpret_matches_ref(ops.quant_matmul, x, qt,
                                    static=dict(a_bits=a_bits))


def test_w4a8_close_to_dequant_matmul():
    """The int-activation path approximates the fp-activation path to the
    activation-quantization error (small for a8, larger for a4)."""
    m, k, n, g = 64, 128, 64, 32
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (k, n), jnp.float32)
    packed, scale, zp = ref.quantize_pack_ref(w, bits=4, group_size=g)
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k), jnp.float32)
    y_fp = ref.dequant_matmul_ref(x, packed, scale, zp, bits=4, group_size=g)
    for a_bits, tol in ((8, 0.02), (4, 0.25)):
        y = ref.quant_matmul_ref(x, packed, scale, zp, bits=4, group_size=g,
                                 a_bits=a_bits)
        rel = float(jnp.linalg.norm(y - y_fp) / jnp.linalg.norm(y_fp))
        assert rel < tol, (a_bits, rel)


@pytest.mark.slow
def test_w4a8_per_slab_error_bounded():
    """bk < K streams the weights in K-slabs, but every token keeps ONE
    whole-row activation scale: the result matches the oracle up to f32
    summation order, and its error vs the fp-activation product stays
    small."""
    m, k, n, g = 64, 512, 64, 64
    key = jax.random.PRNGKey(4)
    w = jax.random.normal(key, (k, n), jnp.float32)
    packed, scale, zp = ref.quantize_pack_ref(w, bits=4, group_size=g)
    x = jax.random.normal(jax.random.fold_in(key, 1), (m, k), jnp.float32)
    y_fp = ref.dequant_matmul_ref(x, packed, scale, zp, bits=4, group_size=g)
    y = w4a8_matmul(x, packed, scale, zp, bits=4, group_size=g, a_bits=8,
                    bm=64, bn=64, bk=128, interpret=True)
    y_ref = ref.quant_matmul_ref(x, packed, scale, zp, bits=4, group_size=g,
                                 a_bits=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)
    rel = float(jnp.linalg.norm(y - y_fp) / jnp.linalg.norm(y_fp))
    assert rel < 0.02, rel


@pytest.mark.parametrize("a_bits", [4, 8])
def test_quant_matmul_dispatch_ragged_batch(a_bits):
    """Ragged M (non-multiple-of-block token counts) through the dispatcher:
    interpret == ref bit-for-bit — per-token scales are padding-invariant."""
    k, n, g = 128, 64, 32
    key = jax.random.PRNGKey(21)
    qt = quantize_codes(jax.random.normal(key, (k, n)),
                        QuantConfig(w_bits=4, group_size=g, lwc=False))
    x = jax.random.normal(jax.random.fold_in(key, 1), (3, 37, k))
    y = kc.assert_interpret_matches_ref(ops.quant_matmul, x, qt,
                                        static=dict(a_bits=a_bits))
    assert y.shape == (3, 37, n)


def test_w8a8_dispatch_ragged_batch():
    """w8a8 (pre-quantized int8 weights) on ragged M: interpret vs ref."""
    k, n = 128, 64
    key = jax.random.PRNGKey(22)
    wq = jax.random.randint(key, (k, n), -128, 128).astype(jnp.int8)
    ws = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (n,))) + 0.1
    x = jax.random.normal(jax.random.fold_in(key, 2), (37, k))
    run_ref = jax.jit(lambda *a: ops.w8a8_matmul(*a, mode="ref"))
    run_int = jax.jit(lambda *a: ops.w8a8_matmul(*a, mode="interpret"))
    np.testing.assert_allclose(np.asarray(run_int(x, wq, ws)),
                               np.asarray(run_ref(x, wq, ws)),
                               rtol=1e-6, atol=1e-6)


def test_quant_matmul_per_channel_group_zero():
    """group_size=0 (per-channel, one K-wide group) through the interpret
    path must not trip the block clamp, for any K % bk remainder."""
    n = 64
    for k in (128, 512):   # k < DEFAULT_BK and k == DEFAULT_BK
        qt = quantize_codes(jax.random.normal(jax.random.PRNGKey(k), (k, n)),
                            QuantConfig(w_bits=4, group_size=0, lwc=False))
        qt = qt.__class__(qt.packed, qt.scale, qt.zp, qt.bits, 0)  # raw 0
        x = jax.random.normal(jax.random.PRNGKey(k + 1), (8, k))
        y = ops.quant_matmul(x, qt, a_bits=8, mode="interpret")
        y_ref = ops.quant_matmul(x, qt, a_bits=8, mode="ref")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)


def test_quant_matmul_rejects_unrepresentable_a_bits():
    """a_bits in 9..15 would wrap on the int8 cast — must raise, not
    silently corrupt."""
    k, n = 128, 64
    qt = quantize_codes(jax.random.normal(jax.random.PRNGKey(30), (k, n)),
                        QuantConfig(w_bits=4, group_size=32, lwc=False))
    x = jax.random.normal(jax.random.PRNGKey(31), (8, k))
    with pytest.raises(ValueError, match="a_bits"):
        ops.quant_matmul(x, qt, a_bits=12, mode="ref")


def test_quant_matmul_a16_falls_back_to_dequant():
    k, n, g = 128, 64, 32
    qt = quantize_codes(jax.random.normal(jax.random.PRNGKey(23), (k, n)),
                        QuantConfig(w_bits=4, group_size=g, lwc=False))
    x = jax.random.normal(jax.random.PRNGKey(24), (8, k))
    y = ops.quant_matmul(x, qt, a_bits=16, mode="ref")
    y_dq = ops.dequant_matmul(x, qt, mode="ref")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_dq))


def test_quant_matmul_bits3_falls_back_to_ref_math():
    """3-bit is a storage-only format: the dispatcher must not try the
    in-kernel unpack even in interpret mode."""
    k, n = 128, 64
    qt = quantize_codes(jax.random.normal(jax.random.PRNGKey(25), (k, n)),
                        QuantConfig(w_bits=3, group_size=0, lwc=False))
    x = jax.random.normal(jax.random.PRNGKey(26), (8, k))
    y = ops.quant_matmul(x, qt, a_bits=8, mode="interpret")
    y_ref = ref.quant_matmul_ref(x, qt.packed, qt.scale, qt.zp, bits=3,
                                 group_size=qt.group_size, a_bits=8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6)


def test_ops_dispatch_ragged_batch():
    k, n, g = 128, 64, 32
    key = jax.random.PRNGKey(11)
    packed, scale, zp = ref.quantize_pack_ref(
        jax.random.normal(key, (k, n)), bits=4, group_size=g)
    x = jax.random.normal(jax.random.fold_in(key, 1), (3, 37, k))
    y_ref = ops.dequant_matmul(x, packed, scale, zp, bits=4, group_size=g,
                               mode="ref")
    y_int = ops.dequant_matmul(x, packed, scale, zp, bits=4, group_size=g,
                               mode="interpret", bn=64, bk=128)
    np.testing.assert_allclose(y_int, y_ref, rtol=1e-4, atol=1e-4)


def test_ops_bits3_falls_back_to_ref():
    k, n = 128, 64
    w = jax.random.normal(jax.random.PRNGKey(12), (k, n))
    packed, scale, zp = ops.quantize_pack(w, bits=3, group_size=0,
                                          mode="interpret")
    x = jax.random.normal(jax.random.PRNGKey(13), (8, k))
    y = ops.dequant_matmul(x, packed, scale, zp, bits=3, group_size=0,
                           mode="interpret")
    y_ref = ref.dequant_matmul_ref(x, packed, scale, zp, bits=3, group_size=0)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5)
