"""Plain reference of the served OPT block, for the ``correct`` check.

It imports nothing of the program.  It makes each layer's weights from the
seed (``bench.model``), quantizes them itself (round to nearest, 4 bits,
groups of 64 along the input), and runs whole sequences through the model
layer by layer in float32 ``jax.numpy``, rounding to the activation dtype
at the points where the configuration stores activations (embedding plus
positions, norm outputs, every matmul output, bias adds, attention output,
residual adds).  The quantizers are the configuration's: per-token
symmetric 4-bit activations on a whole-row scale, asymmetric 4-bit weight
codes per group of 64, and keys and values stored as int8 with one float32
scale per (token, head).  Attention is an ordinary causal softmax over the
dequantized keys and values.  Besides the final hidden states it gives the
int8 K and V codes of the first layers, as the served pool stores them.

With ``act_dtype="float8_e4m3fn"`` the same code is the control: the
reference in the next precision below the configuration's bfloat16
activations (scaled per row, as an fp8 path would store them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import GROUP, LEVELS, dims, layer_weights, shared_weights

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def _rnd(x, dt):
    """Round float32 ``x`` to dtype ``dt`` and back.  An 8-bit float is
    scaled per row so that the row's largest magnitude maps to the
    format's largest finite value, as an fp8 path would store it."""
    if jnp.dtype(dt).itemsize == 1:
        top = float(jnp.finfo(dt).max)
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-30) / top
        return (x / s).astype(dt).astype(jnp.float32) * s
    return x.astype(dt).astype(jnp.float32)


def rtn_dequant(w: jax.Array) -> jax.Array:
    """Round-to-nearest W4 g64 (asymmetric, zero point rounded),
    dequantized."""
    k, n = w.shape
    wg = w.reshape(k // GROUP, GROUP, n)
    wmax = wg.max(axis=1, keepdims=True)
    wmin = wg.min(axis=1, keepdims=True)
    scale = jnp.maximum(wmax - wmin, 1e-8) / LEVELS
    zp = jnp.round(-wmin / scale)
    q = jnp.clip(jnp.round(wg / scale) + zp, 0, LEVELS)
    return ((q - zp) * scale).reshape(k, n)


def a4_matmul(h: jax.Array, w: jax.Array, a_bits: int) -> jax.Array:
    """Per-token symmetric ``a_bits`` activations times dequantized W."""
    qmax = 2.0 ** (a_bits - 1) - 1.0
    a = jnp.maximum(jnp.max(jnp.abs(h), axis=-1, keepdims=True), 1e-8) / qmax
    xq = jnp.clip(jnp.round(h / a), -qmax - 1.0, qmax)
    return jnp.matmul(xq, w, precision=HI) * a


def kv8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(int8 codes, per-(token, head) scale) of (..., H, D) keys or
    values."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127
    return jnp.clip(jnp.round(x / s), -128, 127), s


def layer_norm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * scale + bias


def sinusoid(t: int, d: int) -> jax.Array:
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * i / d)[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def quantize_layer(w: dict, dt) -> dict:
    q = {k: rtn_dequant(w[k]) for k in ("wq", "wk", "wv", "wo", "w_up",
                                         "w_down")}
    for k in ("ln_attn_scale", "ln_attn_bias", "bq", "bk", "bv",
              "ln_mlp_scale", "ln_mlp_bias"):
        q[k] = _rnd(w[k], dt)
    return q


def _attention(q, k, v, hd):
    """One sequence: q, k, v (T, H, D) f32, causal softmax."""
    t = q.shape[0]
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) * hd ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hts,shd->thd", p, v, precision=HI)


def block(x, p, *, h: int, a_bits: int, dt):
    """One OPT block over (N, T, d) rounded activations; returns its
    output and the int8 K and V codes (N, T, H, D) it stores."""
    n, t, d = x.shape
    hd = d // h
    mm = lambda a, w: _rnd(a4_matmul(a, w, a_bits), dt)
    y = _rnd(layer_norm(x, p["ln_attn_scale"], p["ln_attn_bias"]), dt)
    q = _rnd(mm(y, p["wq"]) + p["bq"], dt).reshape(n, t, h, hd)
    k = _rnd(mm(y, p["wk"]) + p["bk"], dt).reshape(n, t, h, hd)
    v = _rnd(mm(y, p["wv"]) + p["bv"], dt).reshape(n, t, h, hd)
    (kc, ks), (vc, vs) = kv8(k), kv8(v)
    att = jax.lax.map(lambda a: _attention(*a, hd), (q, kc * ks, vc * vs))
    x = _rnd(x + mm(_rnd(att.reshape(n, t, d), dt), p["wo"]), dt)
    y = _rnd(layer_norm(x, p["ln_mlp_scale"], p["ln_mlp_bias"]), dt)
    u = jax.nn.relu(mm(y, p["w_up"]))
    return (_rnd(x + mm(u, p["w_down"]), dt), kc.astype(jnp.int8),
            vc.astype(jnp.int8))


class Reference:
    """The reference model of one configuration in one activation dtype.
    Its programs compile once and serve every seed."""

    def __init__(self, cfg: dict, act_dtype: str = "bfloat16"):
        self.cfg = cfg
        self.dt = dt = jnp.dtype(act_dtype)
        m = self.m = dims(cfg)
        a_bits = cfg["serving"]["a_bits"]
        self._params = jax.jit(
            lambda key, i: quantize_layer(layer_weights(cfg, key, i), dt))
        self._block = jax.jit(functools.partial(block, h=m["h"],
                                                a_bits=a_bits, dt=dt))

        def embed(tokens, key):
            sh = shared_weights(cfg, key)
            x = jnp.take(_rnd(sh["embed"], dt), tokens, axis=0)
            pe = _rnd(sinusoid(tokens.shape[1], m["d"]), dt)
            return _rnd(x + pe[None], dt)

        def final(x, key):
            sh = shared_weights(cfg, key)
            return _rnd(layer_norm(x, _rnd(sh["ln_f_scale"], dt),
                                   _rnd(sh["ln_f_bias"], dt)), dt)

        self._embed = jax.jit(embed)
        self._final = jax.jit(final)
        self._table = jax.jit(
            lambda key: _rnd(shared_weights(cfg, key)["embed"], dt))

    def final_hidden(self, key: jax.Array, tokens: np.ndarray,
                     kv_layers: int = 0) -> tuple[jax.Array, list]:
        """(N, T) token ids (sequences padded at the end) -> (N, T, d)
        float32 final-norm outputs, rounded to the activation dtype, and
        the (K, V) int8 codes (N, T, H, D) of the first ``kv_layers``
        layers, on the host.  One layer at a time: only one layer's
        weights live at once."""
        x = self._embed(jnp.asarray(tokens, jnp.int32), key)
        codes = []
        for i in range(self.m["L"]):
            x, kc, vc = self._block(x, self._params(key, i))
            if i < kv_layers:
                codes.append((np.asarray(kc), np.asarray(vc)))
        return self._final(x, key), codes

    def head_table(self, key: jax.Array) -> jax.Array:
        """The tied head (V, d) as this precision stores it."""
        return self._table(key)


def logits(hidden: jax.Array, table: jax.Array) -> jax.Array:
    """Logits (rows, V) float32 of hidden rows against the tied head."""
    return jnp.matmul(hidden, table.T, precision=HI)
