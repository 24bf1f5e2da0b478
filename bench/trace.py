"""Reduction of a profiler trace to per-layer numbers.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  The TPU's plane (``/device:TPU:0``) has a line of program runs
(``XLA Modules``) and a line of operations (``XLA Ops``); the host plane
holds the harness's ``bench.*`` spans.  The traced window runs from the
first to the last ``bench.step`` span in the trace.

Kernel calls are told apart by the compiled programs' HLO: each
``tpu_custom_call`` instruction names its Pallas kernel and lists its
operand shapes, and an operation event carries that instruction's name.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
import time

import jax

DECODE_MODULE = "_decode_and_sample"
CHUNK_MODULE = "_chunk_prefill_call"
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops


class CompileCounter:
    """Counts programs lowered or compiled after it is made."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.times: list = []
        self._on = True
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, duration: float, **kw) -> None:
        if self._on and event in self.EVENTS:
            self.times.append(time.perf_counter())

    def stop(self, since: float, until: float) -> int:
        """Stop counting; the number of events in [``since``, ``until``]."""
        self._on = False
        return sum(since <= t <= until for t in self.times)


def custom_calls(hlo: str) -> dict:
    """Instruction name -> (kernel name, [(dtype, shape), ...] operands,
    (dtype, shape) result) for every ``tpu_custom_call`` of an HLO text."""
    out = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        lhs, rhs = line.split("=", 1)
        name = op_name(lhs)
        kern = re.sub(r"\.\d+$", "", name)
        call = rhs.split("custom-call(", 1)
        res = _SHAPE.findall(call[0])
        # the operand shapes are listed in the layout constraints
        ops = [(d, tuple(int(x) for x in s.split(",") if x))
               for d, s in _SHAPE.findall(_braced(
                   rhs, "operand_layout_constraints={"))]
        out[name] = (kern, ops,
                     [(d, tuple(int(x) for x in s.split(",") if x))
                      for d, s in res])
    return out


def _braced(text: str, key: str) -> str:
    """What follows ``key`` up to its matching closing brace."""
    i = text.find(key)
    if i < 0:
        return ""
    i += len(key)
    depth = 1
    for j in range(i, len(text)):
        depth += {"{": 1, "}": -1}.get(text[j], 0)
        if depth == 0:
            return text[i:j]
    return text[i:]


def op_name(text: str) -> str:
    """The instruction name of an HLO line or of a trace event (whose name
    is the instruction's text)."""
    return text.split(" = ", 1)[0].replace("ROOT ", "").strip().lstrip("%")


def load(path: str):
    """A trace from a profiler directory (its newest ``.xplane.pb``), an
    ``.xplane.pb`` file, or a gzipped one."""
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return jax.profiler.ProfileData.from_serialized_xspace(f.read())
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return jax.profiler.ProfileData.from_file(path)


def _union(intervals) -> float:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(path: str, hlo: dict) -> dict:
    """The trace at ``path`` (see :func:`load`) reduced to plain lists
    (nanoseconds on the trace's clock)."""
    pd = load(path)
    calls = {k: custom_calls(v) for k, v in hlo.items()}
    spans = []
    modules, ops = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif plane.name.startswith("/device:TPU:0") and \
                "Core" not in plane.name[len("/device:TPU:0"):]:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(ev.name, ev.start_ns, ev.end_ns)
                                for ev in line.events]
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        name = op_name(ev.name)
                        if not name.startswith(CONTAINERS):
                            ops.append((name, ev.start_ns, ev.end_ns))
    steps = [s for s in spans if s[0] == "bench.step"]
    if not steps or not ops:
        return {"busy_s": None, "window_s": None, "spans": spans,
                "modules": modules, "ops": [], "kernels": [], "lo": 0,
                "hi": 0}
    lo = min(s[1] for s in steps)
    hi = max(s[2] for s in steps)
    ops = [(n, *_clip(s, e, lo, hi)) for n, s, e in ops if e > lo and s < hi]
    modules = [(n, *_clip(s, e, lo, hi)) for n, s, e in modules
               if e > lo and s < hi]
    busy = _union((s, e) for _, s, e in ops)
    kernels = []
    mods = sorted(modules, key=lambda m: m[1])
    for name, s, e in ops:
        prog = _program_of(mods, s)
        info = calls.get(prog, {}).get(name)
        if info is not None:
            kernels.append((prog, info[0], name, s, e))
    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            "lo": lo, "hi": hi, "spans": spans, "modules": modules,
            "ops": ops, "kernels": kernels, "calls": calls}


def _program_of(mods, t) -> str:
    """'decode' or 'chunk' for the module run that covers time ``t``."""
    for name, s, e in mods:
        if s <= t < e:
            if DECODE_MODULE in name:
                return "decode"
            if CHUNK_MODULE in name:
                return "chunk"
            return name
    return ""


def module_times(red: dict, prog: str) -> list:
    key = DECODE_MODULE if prog == "decode" else CHUNK_MODULE
    return [(e - s) / 1e9 for n, s, e in red["modules"] if key in n]


def idle_gaps(red: dict) -> list:
    """(host span the gap fell in, seconds) for each gap between device
    operations, longest first."""
    ops = sorted((s, e) for _, s, e in red["ops"])
    gaps, end = [], red["lo"]
    for s, e in ops:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if red["hi"] > end:
        gaps.append((end, red["hi"]))
    inner = [sp for sp in red["spans"] if sp[0] != "bench.step"]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        what = next((n for n, s, e in inner if s <= mid < e), "bench.step")
        out.append((what, (b - a) / 1e9))
    return sorted(out, key=lambda g: -g[1])


def breakdown(red: dict) -> dict:
    """Top device operations by time and the longest idle gaps."""
    per: dict = {}
    for n, s, e in red["ops"]:
        per[n] = per.get(n, 0) + (e - s) / 1e9
    kern = {n: k for _, k, n, _, _ in red["kernels"]}
    top = sorted(per.items(), key=lambda kv: -kv[1])[:10]
    gaps: dict = {}
    for what, sec in idle_gaps(red):
        gaps[what] = gaps.get(what, 0) + sec
    return {"device_ops": [[f"{kern[n]}:{n}" if n in kern else n, v]
                           for n, v in top],
            "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                key=lambda kv: -kv[1])[:10]}
