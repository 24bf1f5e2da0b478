"""Host spans of ``Engine.step`` (``repro.serve.spans``), the benchmark's
reading of them, and the engine names the benchmark reads.

A small paged engine with chunked admission runs a trace that preempts a
mid-prefill request and quarantines a NaN row; every span must sit in its
``serve.step``, request spans carry their ``rid``, and the ring stays
bounded.  The contract tests pin what ``bench/system.py`` and
``bench/trace.py`` read of the engine, so that a rename fails here rather
than in the benchmark.
"""
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.quantizer import QuantConfig
from repro.models import build_model
from repro.serve import faults as flt
from repro.serve import spans
from repro.serve.engine import Engine, RequestStatus, ServeConfig
from repro.serve.faults import Fault, FaultPlan
from repro.serve.quantized import QuantizedModel, quantize_lm_packed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import phases  # noqa: E402

PS = 8
PHASES = {"serve.deadlines", "serve.admit", "serve.prefill",
          "serve.prefill.wait", "serve.capacity", "serve.decode.dispatch",
          "serve.decode.wait", "serve.emit"}


@pytest.fixture(scope="module")
def served():
    cfg = get_config("llama-micro")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    qcfg = QuantConfig(w_bits=8, a_bits=16, group_size=32, lwc=False,
                       kv_bits=8)
    qm = QuantizedModel(cfg, qcfg, kernel_mode="ref", flash_block_kv=PS)
    return cfg, qm, quantize_lm_packed(params, cfg, qcfg)


@pytest.fixture(scope="module")
def traced(served):
    """Three requests through a tight chunked pool: the short one's decode
    runs the pool dry while the long one is mid-prefill (a preemption),
    and the third's first token comes out of a NaN row."""
    cfg, qm, packed = served
    plan = FaultPlan(Fault(point=flt.NAN_LOGITS, rid=2, after_step=20))
    eng = Engine(qm, packed, ServeConfig(
        max_batch=2, max_len=64, max_new=10, page_size=PS, paged=True,
        num_pages=6, prefill_chunk=4), faults=plan)
    rng = np.random.default_rng(0)
    for n in (10, 30, 6):
        eng.submit(rng.integers(0, cfg.vocab_size, n))
    reqs = eng.run(max_steps=500)
    return eng, plan, reqs


def test_trace_saw_a_preemption_and_a_nan(traced):
    eng, plan, reqs = traced
    assert sum(r.preemptions for r in reqs) > 0
    assert plan.fired(flt.NAN_LOGITS) == 1
    assert reqs[2].status is RequestStatus.FAILED_NAN


def test_every_phase_sits_in_its_step(traced):
    eng, _, _ = traced
    recs = eng.span_records()
    by_id = {s.id: s for s in recs}
    steps = [s for s in recs if s.name == "serve.step"]
    assert [s.attrs["step"] for s in steps] == list(range(eng._step_idx))
    assert all(s.parent == -1 for s in steps)
    assert {s.name for s in recs} == PHASES | {"serve.step"}
    for s in recs:
        if s.name == "serve.step":
            continue
        parent = by_id[s.parent]
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
        top = parent
        while top.parent != -1:
            top = by_id[top.parent]
        assert top.name == "serve.step"
    # the readback of a final chunk is a child of its prefill span
    for s in recs:
        if s.name == "serve.prefill.wait":
            assert by_id[s.parent].name == "serve.prefill"


def test_request_spans_carry_the_rid(traced):
    eng, _, reqs = traced
    recs = eng.span_records()
    rids = {r.rid for r in reqs}
    prefill = [s for s in recs if s.name == "serve.prefill"]
    assert {s.attrs["rid"] for s in prefill} == rids
    for s in prefill:
        assert s.attrs["tokens"] > 0 and s.attrs["offset"] >= 0
    admitted = [rid for s in recs if s.name == "serve.admit"
                for rid in s.attrs["rids"]]
    preempted = [rid for s in recs if s.name == "serve.capacity"
                 for rid in s.attrs["preempted"]]
    assert preempted
    # each request is admitted once, and once more after each eviction
    assert sorted(admitted) == sorted(list(rids) + preempted)
    for s in recs:
        if s.name == "serve.decode.dispatch":
            assert 1 <= s.attrs["rows"] <= 2 and s.attrs["context"] >= 2


def test_phase_self_times_add_up_to_the_step(traced):
    eng, _, _ = traced
    recs = eng.span_records()
    steps = [s for s in recs if s.name == "serve.step"]
    per = spans.phase_ms(recs)
    mean_step = sum(s.end_ns - s.start_ns for s in steps) / 1e6 / len(steps)
    assert sum(per.values()) == pytest.approx(mean_step, rel=1e-9)
    assert all(v >= 0 for v in per.values())


def test_records_between_times(traced):
    eng, _, _ = traced
    recs = eng.span_records()
    mid = recs[len(recs) // 2]
    later = eng.span_records(mid.start_ns / 1e9)
    assert later and all(s.start_ns >= mid.start_ns for s in later)
    assert eng.span_records(0, recs[0].start_ns / 1e9 - 1) == []


def test_ring_is_bounded(traced):
    rec = spans.SpanRecorder(capacity=5)
    for i in range(12):
        with rec.span("outer", i=i):
            with rec.span("inner"):
                pass
    got = rec.records()
    assert len(rec) == 5 and [s.id for s in got] == [18, 21, 20, 23, 22]
    eng, _, _ = traced
    assert eng._spans._ring.maxlen == spans.CAPACITY


def _span(name, start, end, sid):
    return spans.Span(name, start, end, -1, {}, sid)


def test_host_gap_from_a_span_list():
    """Four decode steps: the gap runs from the end of one readback to
    the end of the next dispatch; gaps outside the window, or overlapping
    the traced interval, are left out."""
    ms = 1_000_000
    recs = []
    for k, (d0, d1, w1) in enumerate([(0, 2, 10), (13, 14, 20),
                                      (24, 27, 30), (35, 36, 40)]):
        recs += [_span("serve.decode.dispatch", d0 * ms, d1 * ms, 2 * k),
                 _span("serve.decode.wait", d1 * ms, w1 * ms, 2 * k + 1)]
    assert phases.decode_gaps(recs, 0, 100 * ms) == [4 * ms, 7 * ms, 6 * ms]
    assert phases.decode_gaps(recs, 0, 100 * ms,
                              skip=(21 * ms, 22 * ms)) == [4 * ms, 6 * ms]
    assert phases.decode_gaps(recs, 15 * ms, 100 * ms) == [7 * ms, 6 * ms]

    class Eng:
        def span_records(self):
            return recs

    class Sys:
        engine = Eng()

    ctx = {"system": Sys(), "t0": 0.0, "t1": 0.1, "window": (0.021, 0.022)}
    assert phases.host_gap_ms(ctx) == pytest.approx(5.0)
    # a program without spans reads nothing
    Sys.engine = object()
    assert phases.host_gap_ms(ctx) is None


# ---------------------------------------------------------------------------
# what the benchmark reads of the engine
# ---------------------------------------------------------------------------

BENCH_READS = ("_decode", "_chunk", "_admit_chunked", "_slots",
               "_prefill_prog", "_seq_len", "_kv", "_last_tok",
               "_idle_keys", "_zero_poison", "_all")


def test_engine_names_the_benchmark_reads(served):
    _, qm, packed = served
    eng = Engine(qm, packed, ServeConfig(max_batch=2, max_len=64, max_new=4,
                                         page_size=PS, paged=True,
                                         prefill_chunk=8))
    for name in BENCH_READS:
        assert hasattr(eng, name), name
    b, c = eng.cfg.max_batch, eng.cfg.prefill_chunk
    zeros = np.zeros((b,), np.int32)
    dec = eng._decode.lower(eng.params, eng._last_tok, eng._kv.cache,
                            eng._idle_keys, eng._zero_poison).compile()
    chk = eng._chunk.lower(eng.params, np.zeros((b, c), np.int32), zeros,
                           eng._kv.cache, zeros).compile()
    assert "_decode_and_sample" in dec.as_text().splitlines()[0]
    assert "_chunk_prefill_call" in chk.as_text().splitlines()[0]
