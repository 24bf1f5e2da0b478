"""Request bookkeeping shared by the load generators: host-clock times of
every request's submission and tokens, and the client-side end of a
request once it has its output length."""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np


@dataclasses.dataclass(eq=False)
class Record:
    prompt: np.ndarray
    target: int                   # output tokens the client reads
    client: int = -1
    submitted: float = 0.0
    req: object = None            # the engine's Request
    times: list = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    failed: bool = False
    kv: Optional[tuple] = None    # pool codes read when the window closed

    @property
    def tokens(self) -> list:
        return list(map(int, self.req.out_tokens[:self.target]))


class Tracker:
    """Submits requests to a ``bench.system.System`` and ends each one
    when the client has read its ``target`` tokens."""

    def __init__(self, system):
        self.system = system
        self.records: list[Record] = []
        self.live: list[Record] = []

    def submit(self, prompt, target: int, client: int = -1) -> Record:
        rec = Record(prompt=prompt, target=target, client=client)
        rec.submitted = time.perf_counter()
        rec.req = self.system.engine.submit(
            prompt, on_token=lambda r, t, rec=rec: rec.times.append(
                time.perf_counter()))
        self.records.append(rec)
        self.live.append(rec)
        return rec

    def reap(self) -> list[Record]:
        """After a step: end the requests that have their output length
        (the engine's own max_new retires the longest) or failed."""
        from repro.serve.engine import RequestStatus
        eng = self.system.engine
        ended = []
        for rec in self.live:
            req = rec.req
            if len(req.out_tokens) >= rec.target:
                if not req.done:
                    eng.cancel(req.rid)
                rec.finished = rec.times[rec.target - 1]
                ended.append(rec)
            elif req.done:
                rec.failed = req.status is not RequestStatus.COMPLETED
                rec.finished = time.perf_counter()
                ended.append(rec)
        if ended:
            gone = set(map(id, ended))
            self.live = [r for r in self.live if id(r) not in gone]
        return ended

    def step(self) -> list[Record]:
        self.system.step()
        return self.reap()

    def close(self) -> None:
        """The clients stop reading: every live request is cancelled and
        ends with the tokens it was served.  Before that, the K and V codes
        the pool holds for each decoding request's first layers are read
        for the ``correct`` check."""
        from bench.correct import KV_LAYERS
        eng = self.system.engine
        codes = self.system.kv_codes([r.req for r in self.live], KV_LAYERS)
        for rec, kv in zip(self.live, codes):
            rec.kv = kv
        for rec in self.live:
            if not rec.req.done:
                eng.cancel(rec.req.rid)
            rec.target = len(rec.req.out_tokens)
            rec.finished = rec.times[-1] if rec.times else None
        self.live = []
