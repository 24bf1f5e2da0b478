#!/usr/bin/env python3
"""Where a traced run's device time and idle time go, by the program's own
names: one ``--trace 1`` run of a cell, reported by engine phase and by
scope.

    python3 bench/phase_report.py --workload <cell> --seed <n> [--seconds 51]

Prints one JSON object on its last line:

- ``result``: what ``bench/run.py --trace 1`` prints (``metrics`` with
  every per-layer metric, ``device``, ``breakdown``, ``correct``);
- ``decode_ms``: device time per decode-program run of each class of
  ``bench/phases.py`` (kernels, ``kv_pool``, ``weight_slice``, each
  other scope, ``unscoped``), their sum and ``decode_step_ms``;
- ``idle_s``: the traced window's device idle time by the innermost
  ``serve.*`` span open in each gap ("caller, between steps" outside
  ``serve.step``), from the trace's host plane;
- ``gaps_in_trace``: the engine's host gaps of the decode steps inside
  the traced interval (their count and sum), beside the device's idle
  time there;
- ``span_cost_us``: host time of one span opened and closed with the
  profiler off, and the spans a step opens.

It needs the chip, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run  # noqa: E402  (starts the set-up clock)


def span_cost_us(n: int = 100_000) -> float:
    from repro.serve.spans import SpanRecorder
    rec = SpanRecorder()
    t = time.perf_counter()
    for _ in range(n):
        with rec.span("serve.emit"):
            pass
    return (time.perf_counter() - t) / n * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    bench, cell = run.load_cell(args.workload)

    import jax
    from bench import phases, trace
    if jax.devices()[0].platform != "tpu":
        run.log("bench/phase_report.py needs a TPU. Nothing was run.")
        return 3
    held, report = {}, {}
    reduce = trace.reduce

    def reduce_and_read(path, hlo):
        """The run's own reduction, then this report's readings, while
        the system still holds the device (run_cell frees it after)."""
        red = reduce(path, hlo)
        sysm = held.pop("system")
        spans = [(ev.name, ev.start_ns, ev.end_ns)
                 for plane in trace.load(path).planes
                 if plane.name.startswith("/host")
                 for line in plane.lines for ev in line.events
                 if ev.name.startswith("serve.")]
        ctx = {"system": sysm, "trace": red}
        per = phases.decode_classes(ctx)
        step = run.read_metric("decode_step_ms", ctx)
        report["decode_ms"] = {"classes": per, "sum": sum(per.values()),
                               "decode_step_ms": step}
        report["idle_s"] = phases.idle_by_phase(red, spans)
        w0, w1 = (int(t * 1e9) for t in sysm.trace_window)
        gaps = phases.decode_gaps(sysm.engine.span_records(), w0, w1)
        report["gaps_in_trace"] = {
            "steps": len(gaps), "sum_s": sum(gaps) / 1e9,
            "device_idle_s": (red["window_s"] or 0) - (red["busy_s"] or 0)}
        recs = sysm.engine.span_records()
        report["spans_per_step"] = len(recs) / max(
            1, sum(s.name == "serve.step" for s in recs))
        return red

    trace.reduce = reduce_and_read
    result = run.run_cell(bench, cell, args.seed, args.seconds, True,
                          hooks=lambda sysm: held.update(system=sysm))
    report["span_cost_us"] = span_cost_us()
    report["result"] = result
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
