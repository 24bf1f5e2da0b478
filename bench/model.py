"""Configuration files and seeded weights of the benchmark's models.

A configuration file (``bench/configs/<name>.json``) holds the published
sizes under the Hugging Face ``config.json`` keys, the serving precisions
under ``serving`` and the random-weight scales under ``init``.  This module
turns it into the program's ``ModelConfig`` and makes the float weights
of each layer from the seed.  The weights are drawn on the W4 grid itself
(``w = (c - z) * s`` with integer codes ``c`` in [0, 15], each 64-row group
holding a 0 and a 15), so round-to-nearest quantization recovers them
exactly in the program and in the reference alike, with no value near a
rounding tie.  Nothing here imports the program; ``program_config`` does,
lazily, for the harness side only.
"""
from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
GROUP = 64
LEVELS = 15


def load_config(name: str) -> dict:
    path = os.path.join(HERE, "configs", f"{name}.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["name"] = name
    return cfg


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "h": h, "hd": d // h,
            "ff": cfg["ffn_dim"], "V": cfg["vocab_size"],
            "P": cfg["max_position_embeddings"]}


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the OPT
    block the repo serves (pre-LN, ReLU MLP, q/k/v biases, positions
    without RoPE, tied embedding) in the file's activation dtype."""
    from repro.configs.base import ModelConfig
    from repro.core.quantizer import QuantConfig
    m = dims(cfg)
    s = cfg["serving"]
    mc = ModelConfig(
        name=cfg["name"], family="dense", num_layers=m["L"], d_model=m["d"],
        num_heads=m["h"], num_kv_heads=m["h"], d_ff=m["ff"],
        vocab_size=m["V"], act=cfg["activation_function"], norm="layernorm",
        qkv_bias=cfg["enable_bias"], rope_theta=0.0,
        tie_embeddings=cfg["tie_word_embeddings"], dtype=s["dtype"],
        remat=False)
    qc = QuantConfig(w_bits=s["w_bits"], a_bits=s["a_bits"],
                     group_size=s["group_size"], kv_bits=s["kv_bits"])
    return mc, qc


def seed_key(seed: int) -> jax.Array:
    """The PRNG key of a run's weights.  Seeds run past 32 bits: the low
    and the high word are folded in one after the other."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def _grid_weight(key, k_in: int, n_out: int, std: float) -> jax.Array:
    """(k_in, n_out) f32 weight on an exact W4 g64 grid with element std
    about ``std``."""
    kc, ks, kz = jax.random.split(key, 3)
    g = k_in // GROUP
    codes = jnp.clip(jnp.round(7.5 + 2.5 * jax.random.normal(
        kc, (g, GROUP, n_out))), 0, LEVELS)
    # each group's extremes sit at a row that moves with the column, so
    # the range is exactly [0, 15] steps and no row is special
    row = jnp.arange(GROUP)[None, :, None]
    lo = (jnp.arange(n_out)[None, None, :] * 7
          + jnp.arange(g)[:, None, None] * 13) % GROUP
    codes = jnp.where(row == lo, 0.0, codes)
    codes = jnp.where(row == (lo + GROUP // 2) % GROUP, float(LEVELS), codes)
    zp = 7.0 + jax.random.bernoulli(kz, 0.5, (g, 1, n_out))
    scale = (std / 2.5) * jax.random.uniform(ks, (g, 1, n_out),
                                             minval=0.75, maxval=1.25)
    return ((codes - zp) * scale).reshape(k_in, n_out)


def layer_weights(cfg: dict, key: jax.Array, i) -> dict:
    """Layer ``i``'s float weights (f32) from the run's ``seed_key``; ``i``
    may be traced, so one compiled program makes every layer."""
    m = dims(cfg)
    init = cfg["init"]
    d, ff = m["d"], m["ff"]
    std, out_std = init["std"], init["std"] / math.sqrt(2 * m["L"])
    ks = jax.random.split(jax.random.fold_in(key, 1 + i), 13)
    vec = lambda k, n, s: s * jax.random.normal(k, (n,))
    return {
        "ln_attn_scale": 1.0 + vec(ks[0], d, init["norm_std"]),
        "ln_attn_bias": vec(ks[1], d, init["bias_std"]),
        "wq": _grid_weight(ks[2], d, d, std),
        "wk": _grid_weight(ks[3], d, d, std),
        "wv": _grid_weight(ks[4], d, d, std),
        "bq": vec(ks[5], d, init["bias_std"]),
        "bk": vec(ks[6], d, init["bias_std"]),
        "bv": vec(ks[7], d, init["bias_std"]),
        "wo": _grid_weight(ks[8], d, d, out_std),
        "ln_mlp_scale": 1.0 + vec(ks[9], d, init["norm_std"]),
        "ln_mlp_bias": vec(ks[10], d, init["bias_std"]),
        "w_up": _grid_weight(ks[11], d, ff, std),
        "w_down": _grid_weight(ks[12], ff, d, out_std),
    }


def shared_weights(cfg: dict, key: jax.Array) -> dict:
    """Embedding (tied LM head) and final norm, f32."""
    m = dims(cfg)
    init = cfg["init"]
    ke, ks, kb = jax.random.split(jax.random.fold_in(key, 0), 3)
    return {"embed": init["embed_std"] * jax.random.normal(
                ke, (m["V"], m["d"])),
            "ln_f_scale": 1.0 + init["norm_std"] * jax.random.normal(
                ks, (m["d"],)),
            "ln_f_bias": init["bias_std"] * jax.random.normal(
                kb, (m["d"],))}
