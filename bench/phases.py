"""Readings of the program's own measurement: the engine's host spans
(``repro.serve.spans``) and the named scopes of its compiled programs.

Host side: ``Engine.span_records`` holds one ``serve.step`` span per
engine step with its phases inside.  The host gap of a decode step is the
time from the end of one ``serve.decode.wait`` (the token readback: the
device has finished the decode program) to the end of the next
``serve.decode.dispatch`` (the next decode program is queued): the host
time with no decode program queued.

Device side: every operation of a compiled program carries the
``jax.named_scope`` path it was traced under in its HLO metadata
(``op_name="jit(_decode_and_sample)/decoder/while/body/closed_call/
kv_write/scatter"``).  :func:`classify` gives each instruction one class:

- ``kernels``: a Pallas ``tpu_custom_call`` (``w4a8_matmul``,
  ``flash_decode_paged``);
- ``kv_pool``: under ``kv_write`` (quantize-on-write and the paged
  write), or added by the ``decoder`` layer loop itself (no child scope)
  and reading or writing a KV pool array: the loop's slice of each
  layer's pool out of the stack and its restack;
- ``weight_slice``: added by the ``decoder`` loop itself and reading a
  stacked packed weight (``QTensor`` codes, scales, zero points): the
  per-layer copies that become a kernel's operands;
- ``decoder``: the loop's other own operations (counter, bias slices);
- the innermost scope otherwise (``embed``, ``attn.qkv``, ``attn.flash``,
  ``attn.out``, ``mlp``, ``head``, ``sample``);
- ``unscoped``: none of these.

An instruction without metadata inside the ``decoder`` loop's body
(a layout copy the compiler added) counts as the loop's own.  A program
compiled without the scopes has no ``decoder`` loop, and its readers
return None.
"""
from __future__ import annotations

import re

import numpy as np

from bench import trace

SCOPES = ("embed", "decoder", "attn.qkv", "kv_write", "attn.flash",
          "attn.out", "mlp", "head", "sample")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$")
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_DTYPES = {"int8": "s8", "uint8": "u8", "int32": "s32", "float32": "f32",
           "bfloat16": "bf16", "float16": "f16"}


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def engine_spans(ctx: dict):
    """The engine's span records, or None where the program has none."""
    records = getattr(ctx["system"].engine, "span_records", None)
    return None if records is None else records()


def decode_gaps(records, lo_ns: int, hi_ns: int, skip=None) -> list:
    """Host gaps (ns) between consecutive decode steps that lie in
    [``lo_ns``, ``hi_ns``]; a gap that overlaps ``skip`` (an interval in
    ns, such as the traced one) is left out."""
    waits = sorted(s.end_ns for s in records if s.name == "serve.decode.wait")
    disp = sorted(s.end_ns for s in records
                  if s.name == "serve.decode.dispatch")
    gaps = []
    for w in waits:
        i = np.searchsorted(disp, w)
        if i == len(disp):
            break
        d = disp[i]
        if w < lo_ns or d > hi_ns:
            continue
        if skip is not None and w < skip[1] and d > skip[0]:
            continue
        gaps.append(d - w)
    return gaps


def host_gap_ms(ctx: dict):
    """Mean host gap of the window's decode steps outside the traced
    interval, in ms."""
    records = engine_spans(ctx)
    if not records:
        return None
    skip = None
    if ctx.get("window") and ctx["window"][1] is not None:
        skip = tuple(int(t * 1e9) for t in ctx["window"])
    gaps = decode_gaps(records, int(ctx["t0"] * 1e9), int(ctx["t1"] * 1e9),
                       skip)
    return float(np.mean(gaps)) / 1e6 if gaps else None


# ---------------------------------------------------------------------------
# compiled programs
# ---------------------------------------------------------------------------

def _shapes(text: str) -> list:
    return [(d, tuple(int(x) for x in s.split(",") if x))
            for d, s in _SHAPE.findall(text)]


def instructions(hlo: str) -> dict:
    """Instruction name -> dict(opcode, shapes: result shapes, operands:
    names, op_name, kernel: is a ``tpu_custom_call``, comp: computation,
    body: the body computation of a ``while``)."""
    out, comp = {}, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name, shape, opcode, rest = m.groups()
        args = rest.split(")", 1)[0]
        meta = re.search(r'op_name="([^"]*)"', line)
        body = re.search(r"body=%([\w.\-]+)", line) if opcode == "while" \
            else None
        out[name] = {"opcode": opcode, "shapes": _shapes(shape),
                     "operands": re.findall(r"%([\w.\-]+)", args),
                     "op_name": meta.group(1) if meta else "",
                     "kernel": 'custom_call_target="tpu_custom_call"' in line,
                     "comp": comp, "body": body.group(1) if body else None}
    return out


def scopes_of(op_name: str) -> list:
    return [s for s in re.split(r"[/;]", op_name) if s in SCOPES]


def array_shapes(engine) -> tuple[set, set]:
    """(pool shapes, packed-weight shapes) as (HLO dtype, dims): each KV
    pool array and each stacked ``QTensor`` leaf of the layers, whole and
    one layer of it (with and without its unit layer axis); a pool layer
    also as the (pages x page size, ...) rows its token writes address."""
    import jax
    from repro.core.qtensor import QTensor

    def forms(x) -> set:
        d = _DTYPES.get(str(x.dtype), str(x.dtype))
        s = tuple(x.shape)
        return {(d, s), (d, s[1:]), (d, (1,) + s[1:])}

    cache = engine._kv.cache
    pools = set()
    for name in ("k", "v", "k_scale", "v_scale"):
        x = getattr(cache, name, None)
        if x is not None:
            d = _DTYPES.get(str(x.dtype), str(x.dtype))
            rows = (d, (x.shape[1] * x.shape[2],) + tuple(x.shape[3:]))
            pools |= forms(x) | {rows}
    weights = set()
    leaves = jax.tree_util.tree_leaves(
        engine.params["layers"], is_leaf=lambda x: isinstance(x, QTensor))
    for leaf in leaves:
        if isinstance(leaf, QTensor):
            for x in (leaf.packed, leaf.scale, leaf.zp):
                weights |= forms(x)
    return pools, weights


def decoder_loops(instrs: dict) -> set:
    """Body computations of the ``while`` loops under the ``decoder``
    scope."""
    return {i["body"] for i in instrs.values()
            if i["body"] and "decoder" in scopes_of(i["op_name"])}


def classify(instrs: dict, pools: set, weights: set) -> dict:
    """Instruction name -> class (module docstring) for every instruction
    of a program; ``{}`` where the program has no ``decoder`` loop."""
    loops = decoder_loops(instrs)
    if not loops:
        return {}
    out = {}
    for name, ins in instrs.items():
        if ins["kernel"]:
            out[name] = "kernels"
            continue
        sc = scopes_of(ins["op_name"])
        inner = sc[-1] if sc else None
        if inner is None and not ins["op_name"] and ins["comp"] in loops:
            inner = "decoder"
        if ins["opcode"] == "while":
            out[name] = inner or "unscoped"
        elif inner == "kv_write":
            out[name] = "kv_pool"
        elif inner == "decoder":
            # array operands (a tuple is the loop's carry, not an array)
            opnd = [instrs[o]["shapes"][0] for o in ins["operands"]
                    if len(instrs.get(o, {}).get("shapes", ())) == 1]
            if any(s in pools for s in ins["shapes"] + opnd):
                out[name] = "kv_pool"
            elif any(s in weights for s in opnd):
                out[name] = "weight_slice"
            else:
                out[name] = "decoder"
        else:
            out[name] = inner or "unscoped"
    return out


def decode_classes(ctx: dict) -> dict:
    """Device time (ms) per decode-program run of each class, over the
    traced window's decode runs; ``{}`` where the decode program has no
    ``decoder`` scope or no decode run was traced."""
    sysm = ctx["system"]
    red = ctx["trace"]
    instrs = instructions(sysm.hlo["decode"])
    runs = len(trace.module_times(red, "decode"))
    out: dict = {}
    if decoder_loops(instrs) and runs:
        cls = classify(instrs, *array_shapes(sysm.engine))
        mods = sorted(red["modules"], key=lambda m: m[1])
        for name, s, e in red["ops"]:
            if trace._program_of(mods, s) != "decode":
                continue
            c = cls.get(name, "unscoped")
            out[c] = out.get(c, 0.0) + (e - s) / 1e6 / runs
    return out


def class_ms(ctx: dict, name: str):
    """Device time (ms) per decode-program run of one class; None where
    the decode program has no ``decoder`` scope."""
    per = decode_classes(ctx)
    return per.get(name, 0.0) if per else None


# ---------------------------------------------------------------------------
# idle time by engine phase (a traced run's host plane)
# ---------------------------------------------------------------------------

def _segments(spans: list) -> list:
    """(start, end, name) pieces of the timeline, each labelled with the
    innermost of the (properly nested) ``spans`` open over it."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    starts = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out, stack, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        stack = [sp for sp in stack if sp[2] > a]
        while i < len(starts) and starts[i][1] <= a:
            if starts[i][2] > a:
                stack.append(starts[i])
            i += 1
        if stack:
            out.append((a, b, stack[-1][0]))
    return out


def idle_by_phase(red: dict, spans: list) -> dict:
    """Seconds of the traced window's device idle time by the innermost
    ``serve.*`` host span open over it ("caller, between steps" outside
    every one).  ``spans``: (name, start, end) on the trace's clock."""
    ops = sorted((s, e) for _, s, e in red["ops"])
    gaps, end = [], red["lo"]
    for s, e in ops:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if red["hi"] > end:
        gaps.append((end, red["hi"]))
    segs = _segments([sp for sp in spans if sp[0].startswith("serve.")])
    out: dict = {}
    j = 0
    for a, b in gaps:
        covered = 0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            lo, hi, name = segs[k]
            t = min(hi, b) - max(lo, a)
            if t > 0:
                out[name] = out.get(name, 0.0) + t / 1e9
                covered += t
            k += 1
        if b - a > covered:
            key = "caller, between steps"
            out[key] = out.get(key, 0.0) + (b - a - covered) / 1e9
    return out
