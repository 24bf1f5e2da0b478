"""Host spans of the serving engine: where a step's host time goes.

``Engine.step`` opens a span around each of its phases (``serve.step``
and, inside it, ``serve.deadlines``, ``serve.admit``, ``serve.prefill``,
``serve.capacity``, ``serve.decode.dispatch``, ``serve.decode.wait``,
``serve.emit``).  A :class:`SpanRecorder` keeps them in a bounded
in-memory ring of :class:`Span` records stamped with
``time.perf_counter_ns()``, and opens a ``jax.profiler.TraceAnnotation``
of the same name around each, so that while the profiler runs every span
also lies on the device trace's timeline.

The recorder is always on: a span costs about three microseconds of host
time, against a decode step of milliseconds or more.  The ring keeps the
newest :data:`CAPACITY` spans; an engine stepping every 10 ms with eight
spans a step fills it in about 80 s.  It holds plain tuples of atomic
values (attributes as ``(key, value)`` pairs, lists frozen to tuples),
which the garbage collector stops tracking at their first collection: a
full ring neither triggers full collections nor adds work to them.
"""
from __future__ import annotations

import time
from collections import deque
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 16


class Span(NamedTuple):
    """One closed span.  ``parent`` is the ``id`` of the span that was
    open around it (-1 at the top); spans of one request carry its
    ``rid`` (or ``rids``) in ``attrs``."""
    name: str
    start_ns: int
    end_ns: int
    parent: int
    attrs: dict
    id: int


class _Open:
    """Context manager of one open span; ``attrs`` may be filled in while
    it is open."""
    __slots__ = ("_rec", "_ann", "name", "attrs", "id", "parent",
                 "start_ns")

    def __init__(self, rec: "SpanRecorder", name: str, attrs: dict):
        self._rec = rec
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Open":
        rec = self._rec
        self.id = rec._next_id
        rec._next_id += 1
        self.parent = rec._open[-1] if rec._open else -1
        rec._open.append(self.id)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        rec = self._rec
        rec._open.pop()
        attrs = tuple((k, tuple(v) if type(v) is list else v)
                      for k, v in self.attrs.items())
        rec._ring.append((self.name, self.start_ns, end, self.parent, attrs,
                          self.id))


class SpanRecorder:
    """Bounded ring of the newest ``capacity`` closed spans."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: deque[tuple] = deque(maxlen=capacity)
        self._open: list[int] = []
        self._next_id = 0

    def span(self, name: str, **attrs) -> _Open:
        """``with recorder.span("serve.emit", rows=3) as sp: ...``"""
        return _Open(self, name, attrs)

    def records(self, t0: Optional[float] = None,
                t1: Optional[float] = None) -> list[Span]:
        """Closed spans that start at or after ``t0`` and end at or before
        ``t1`` (``time.perf_counter()`` seconds; None = unbounded), in the
        order they closed: children before their parent."""
        lo = -1 if t0 is None else int(t0 * 1e9)
        hi = None if t1 is None else int(t1 * 1e9)
        return [Span(n, s, e, p, dict(a), i) for n, s, e, p, a, i in self._ring
                if s >= lo and (hi is None or e <= hi)]

    def __len__(self) -> int:
        return len(self._ring)


def self_ns(records: list[Span]) -> dict[int, int]:
    """Self time of each span (by ``id``): its length minus the lengths
    of its children among ``records``."""
    own = {s.id: s.end_ns - s.start_ns for s in records}
    for s in records:
        if s.parent in own:
            own[s.parent] -= s.end_ns - s.start_ns
    return own


def phase_ms(records: list[Span]) -> dict[str, float]:
    """Self time of each span name in ms per ``serve.step``, over the
    steps among ``records``."""
    steps = sum(1 for s in records if s.name == "serve.step")
    if not steps:
        return {}
    own = self_ns(records)
    out: dict[str, float] = {}
    for s in records:
        out[s.name] = out.get(s.name, 0.0) + own[s.id] / 1e6 / steps
    return out
