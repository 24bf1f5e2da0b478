"""Closed loop: ``clients`` callers, each sending its next request as soon
as the previous one has its output.  Requests come in rounds of
``clients``: every round holds the mix's prompt and output lengths at the
same evenly spaced quantiles, shuffled by the seed, so every seed offers
the same work in another order.  A finished client takes the next request
in line.

With ``ramp``, the first round is admitted before the window opens, and
the window opens once every client's first request has its first token:
the window then starts from a full batch, as a server does that has been
running, and not from an empty one.  The ramp counts as set-up.  When the
window closes the clients stop reading: requests still running end with
the tokens they were served."""
from __future__ import annotations

import itertools
import time

import numpy as np

from bench import load
from bench.drive import Tracker


def rounds(mix: dict, seed: int, vocab: int, n_rounds: int) -> list:
    """(prompt ids, output length) for ``n_rounds`` rounds of requests."""
    c = mix["clients"]
    r = load.rng(seed, 1)
    ids = load.rng(seed, 2)
    p_q, o_q = load.quantiles(mix["prompt"], c), load.quantiles(
        mix["output"], c)
    out = []
    for _ in range(n_rounds):
        for p, o in zip(r.permutation(p_q), r.permutation(o_q)):
            out.append((ids.integers(0, vocab, int(p), dtype=np.int32),
                        int(o)))
    return out


def run(system, mix: dict, seed: int, seconds: float, vocab: int):
    """Drive the engine for ``seconds``; returns (tracker, t0, t1)."""
    clients = mix["clients"]
    reqs = rounds(mix, seed, vocab, mix["rounds"])
    queue = itertools.cycle(reqs)   # rounds repeat once all are sent
    tr = Tracker(system)

    def send(c: int) -> None:
        prompt, out = next(queue)
        tr.submit(prompt, out, client=c)

    for c in range(clients):
        send(c)
    if mix.get("ramp"):
        first = list(tr.records)
        while not all(r.times or r.failed for r in first):
            for rec in tr.step():
                send(rec.client)
    t0 = time.perf_counter()
    system.window_started(t0)
    t1 = t0 + seconds
    while time.perf_counter() < t1:
        for rec in tr.step():
            if time.perf_counter() < t1:
                send(rec.client)
    tr.close()
    return tr, t0, t1
