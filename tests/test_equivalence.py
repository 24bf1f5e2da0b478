"""Zero-overhead merging tests (paper §3.3) incl. the Table-4 fp32/fp64
merge-error ablation."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import affine as af
from repro.core import equivalence as eq


def test_merge_diag_into_norm():
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (32,))
    beta = jax.random.normal(jax.random.fold_in(key, 1), (32,))
    a = jnp.exp(0.3 * jax.random.normal(jax.random.fold_in(key, 2), (32,)))
    shift = 0.2 * jax.random.normal(jax.random.fold_in(key, 3), (32,))
    g2, b2 = eq.merge_diag_into_norm(g, beta, a, shift)
    xhat = jax.random.normal(jax.random.fold_in(key, 4), (7, 32))
    want = (xhat * g + beta - shift) / a
    got = xhat * g2 + b2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_merge_inv_into_producer():
    key = jax.random.PRNGKey(1)
    w_prev = jax.random.normal(key, (16, 24))
    b_prev = jax.random.normal(jax.random.fold_in(key, 1), (24,))
    a = jnp.eye(24) + 0.01 * jax.random.normal(jax.random.fold_in(key, 2),
                                               (24, 24))
    a_inv = jnp.linalg.inv(a)
    shift = 0.1 * jax.random.normal(jax.random.fold_in(key, 3), (24,))
    w2, b2 = eq.merge_inv_into_producer(w_prev, b_prev, a_inv, shift)
    u = jax.random.normal(jax.random.fold_in(key, 4), (5, 16))
    want = ((u @ w_prev + b_prev) - shift) @ a_inv
    got = u @ w2 + b2
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_fuse_effective_weight_identity_without_quant():
    """inv(A) @ (A @ W) == W when Q is the identity."""
    key = jax.random.PRNGKey(2)
    w = jax.random.normal(key, (32, 16))
    a = jnp.eye(32) + 0.02 * jax.random.normal(jax.random.fold_in(key, 1),
                                               (32, 32))
    a_inv = jnp.linalg.inv(a)
    w_eff = eq.fuse_effective_weight(a @ w, a_inv)
    np.testing.assert_allclose(w_eff, w, rtol=1e-3, atol=1e-4)


def test_merge_error_fp32_vs_fp64():
    """Paper Table 4: fp64 inverse merge error << fp32 merge error, and the
    strictly-diagonally-dominant structure keeps BOTH tiny."""
    key = jax.random.PRNGKey(3)
    h = 128
    a = jnp.eye(h) + 0.2 * jax.random.normal(key, (h, h)) / h
    w = jax.random.normal(jax.random.fold_in(key, 1), (h, h))
    x = jax.random.normal(jax.random.fold_in(key, 2), (64, h))
    err32 = float(eq.merge_error(x, w, a, solve_dtype=jnp.float32))
    with jax.enable_x64(True):
        err64 = float(eq.merge_error(jnp.asarray(np.asarray(x)),
                                     jnp.asarray(np.asarray(w)),
                                     jnp.asarray(np.asarray(a)),
                                     solve_dtype=jnp.float64))
    assert err64 < err32
    assert err32 < 1e-8      # SDD => well-conditioned even in fp32
