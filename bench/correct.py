"""The ``correct`` check: what the timed path produced against the plain
reference.

Once the window has closed, every request that was served a token is run
through ``bench.reference`` over its prompt and served tokens.  Two
numbers are compared, each with its limit (``bench/limits/<cell>.json``):

- ``kv_code_steps``: the mean absolute difference, in int8 steps,
  between the K and V codes that the served pool holds in its first
  layer for the requests still decoding when the window closed (read
  before the clients let go) and the codes the reference stores at the
  same positions.  These codes are written by the chunk program (prompt
  positions) and the decode program (served positions) through the
  embedding, the positions, the LayerNorm, the ``wk``/``wv`` packed
  matmuls and the kv8 quantizer.  Deeper layers are not compared: on the
  chip the program's codes there lie within a factor of about two of the
  float8 control's (PERF.md).
- ``mean_logit_gap``: the mean, over served tokens, of the gap by which
  the served token's logit lies below the reference's best logit at that
  position: 0 where the program served the reference's greedy token.  It
  covers the full depth and the tied head, and catches a token altered
  where it is produced, which the codes cannot see (the reference reads
  the served tokens).

The control is the same reference in float8 (``act_dtype=CONTROL``), put
in the program's place: its own K and V codes, and its own argmax at each
served position.  ``altered`` is the fault of a token altered where it is
produced, planted in the reference put in the program's place: every
fourth served position takes the id after the reference's greedy token.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import load, model as bm, reference as ref

ROWS = 256   # logit rows per head matmul
CONTROL = "float8_e4m3fn"   # one precision step below bfloat16
KV_LAYERS = 1               # pool layers read when the window closes
ALTER_EVERY = 4             # the planted fault's period, in served tokens


def served(records: list) -> list:
    """(prompt, served tokens, pool codes or None) of every unfailed
    request that was served a token, in submission order."""
    return [(np.asarray(r.prompt), r.tokens, r.kv) for r in records
            if r.finished is not None and not r.failed and r.target > 0
            and len(r.req.out_tokens) >= r.target]


def pad_len(mix: dict) -> int:
    """The reference's fixed sequence length for a mix (one compile): the
    longest context the mix declares, in whole blocks of 128."""
    return -(-load.max_context(mix) // 128) * 128


def batch(seqs: list, t: int):
    """(prompt, served, _) triples -> (N, t) tokens and the rows and
    tokens each served token is read at."""
    toks = np.zeros((len(seqs), t), np.int32)
    rows, want = [], []
    for n, (prompt, served_, _) in enumerate(seqs):
        full = np.concatenate([prompt, np.asarray(served_, np.int32)])
        if len(full) > t:
            raise ValueError(f"a served context of {len(full)} tokens is "
                             f"longer than the reference's {t}")
        toks[n, :len(full)] = full
        p = len(prompt)
        for j, tok in enumerate(served_):
            rows.append((n, p - 1 + j))
            want.append(tok)
    return toks, np.asarray(rows), np.asarray(want)


def _logit_rows(hidden, table, rows):
    """Logits (len(rows), V) of the selected hidden rows, in blocks."""
    out = []
    for s in range(0, len(rows), ROWS):
        r = rows[s:s + ROWS]
        out.append(np.asarray(ref.logits(hidden[r[:, 0], r[:, 1]], table)))
    return np.concatenate(out)


def kv_steps(codes: list, ref_codes: list) -> np.ndarray:
    """Per layer: the mean absolute difference, in int8 steps, between
    the K and V codes in ``codes`` (one (K, V) pair of (layers, written,
    H, D) arrays per sequence, or None) and the reference's (K, V) pair
    of (N, T, H, D) arrays at the same positions."""
    diff = np.zeros(len(ref_codes))
    total = 0
    for n, kv in enumerate(codes):
        if kv is None:
            continue
        w = kv[0].shape[1]
        total += 2 * kv[0][0].size
        for i, rkv in enumerate(ref_codes):
            for got, want in zip(kv, rkv):
                diff[i] += np.abs(got[i].astype(np.int32)
                                  - want[n, :w].astype(np.int32)).sum()
    return diff / total if total else np.full(len(ref_codes), np.inf)


def readings(cfg: dict, seed: int, seqs: list, t: int,
             control: bool = False) -> dict:
    """The numbers compared, for the program and, with ``control``, for
    the float8 control and the planted fault, with per-layer shares and
    the widest gap besides."""
    key = bm.seed_key(seed)
    toks, rows, want = batch(seqs, t)
    idx = np.arange(len(want))
    r = ref.Reference(cfg, "bfloat16")
    hidden, rcodes = r.final_hidden(key, toks, KV_LAYERS)
    lg = _logit_rows(hidden, r.head_table(key), rows)
    del hidden
    best = lg.max(axis=-1)

    def numbers(gap, layers):
        return {"mean_logit_gap": float(gap.mean()) if gap.size else np.inf,
                "kv_code_steps": float(layers.max()),   # worst layer
                "widest_gap": float(gap.max(initial=0.0)),
                "kv_by_layer": [float(x) for x in layers]}

    out = {"served_tokens": int(len(want)),
           "kv_requests": sum(kv is not None for _, _, kv in seqs),
           "program": numbers(best - lg[idx, want],
                              kv_steps([kv for _, _, kv in seqs],
                                          rcodes))}
    if control:
        c = ref.Reference(cfg, CONTROL)
        ch, ccodes = c.final_hidden(key, toks, KV_LAYERS)
        pick = _logit_rows(ch, c.head_table(key), rows).argmax(axis=-1)
        del ch
        # the control's codes at the positions the pool was read at
        cc = [None if kv is None else
              tuple(np.stack([x[n, :kv[0].shape[1]] for x in xs])
                    for xs in zip(*ccodes))
              for n, (_, _, kv) in enumerate(seqs)]
        out["control"] = numbers(best - lg[idx, pick],
                                 kv_steps(cc, rcodes))
        greedy = lg.argmax(axis=-1)
        altered = np.zeros(len(want))
        pos = rows[:, 1] - np.asarray([len(seqs[n][0]) - 1
                                       for n in rows[:, 0]])
        hit = pos % ALTER_EVERY == ALTER_EVERY - 1
        alt = (greedy[hit] + 1) % lg.shape[1]
        altered[hit] = best[hit] - lg[idx[hit], alt]
        out["altered"] = {"mean_logit_gap": float(altered.mean())}
    return out


def free_device() -> None:
    """Drop every live device array (the program's state), so the
    reference runs in a freed chip."""
    for a in jax.live_arrays():
        a.delete()


def check(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, each number compared beside its limit)."""
    compared = {k: {"value": numbers[k], "limit": limits[k]["limit"]}
                for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in compared.values())
    return bool(ok), compared
