"""The system under test: packed weights from the seed, the paged engine,
its compiled programs, the warm-up, and the harness's spans and counters
around the engine's calls.

Everything timed goes through ``Engine.submit`` / ``Engine.step`` over
``QuantizedModel(kernel_mode="auto")``.  The decode and chunk programs are
compiled once in set-up; the harness checks that they call the Pallas
kernels and hands those same executables to the engine, wrapped in
``jax.profiler.TraceAnnotation`` spans that also count the rows and the
context each call serves.
"""
from __future__ import annotations

import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import model as bm

KERNELS = ("w4a8_matmul", "flash_decode_paged", "flash_prefill_paged",
           "flash_decode", "flash_prefill", "dequant_matmul")
WANT = {"decode": {"w4a8_matmul", "flash_decode_paged"},
        "chunk": {"w4a8_matmul", "flash_prefill_paged"}}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _program_layer(cfg, mc, qc, key, i):
    """Layer ``i`` in the program's packed tree layout (leading axis 1)."""
    from repro.serve.quantized import quantize_layers_packed
    w = bm.layer_weights(cfg, key, i)
    dt = jnp.dtype(mc.dtype)
    lead = lambda x: x[None]
    act = lambda x: x.astype(dt)[None]
    lp = {"ln_attn": {"scale": act(w["ln_attn_scale"]),
                      "bias": act(w["ln_attn_bias"])},
          "ln_mlp": {"scale": act(w["ln_mlp_scale"]),
                     "bias": act(w["ln_mlp_bias"])},
          "bq": act(w["bq"]), "bk": act(w["bk"]), "bv": act(w["bv"]),
          "wq": lead(w["wq"]), "wk": lead(w["wk"]), "wv": lead(w["wv"]),
          "wo": lead(w["wo"]),
          "mlp": {"w_up": lead(w["w_up"]), "w_down": lead(w["w_down"])}}
    return quantize_layers_packed(lp, mc, qc)


def build_params(cfg: dict, seed: int) -> dict:
    """The served tree, made on the device: one jitted program packs a
    layer from the seed straight into a preallocated stack (donated, so
    the stack is never copied), then the embedding and final norm."""
    mc, qc = bm.program_config(cfg)
    key = bm.seed_key(seed)
    L = mc.num_layers
    one = jax.eval_shape(lambda k: _program_layer(cfg, mc, qc, k, 0), key)
    stack = jax.jit(lambda: jax.tree_util.tree_map(
        lambda s: jnp.zeros((L,) + s.shape[1:], s.dtype), one))()

    def put(stack, key, i):
        lp = _program_layer(cfg, mc, qc, key, i)
        return jax.tree_util.tree_map(
            lambda s, x: jax.lax.dynamic_update_index_in_dim(s, x[0], i, 0),
            stack, lp)

    put = jax.jit(put, donate_argnums=0)
    for i in range(L):
        stack = put(stack, key, jnp.int32(i))
    dt = jnp.dtype(mc.dtype)

    def shared(key):
        sh = bm.shared_weights(cfg, key)
        return {"embed": sh["embed"].astype(dt),
                "ln_f": {"scale": sh["ln_f_scale"].astype(dt),
                         "bias": sh["ln_f_bias"].astype(dt)}}

    params = dict(jax.jit(shared)(key), layers=stack)
    return jax.block_until_ready(params)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def serve_config(cfg: dict, mix: dict, max_len: int):
    """The engine's settings for a cell: the pool holds ``max_len``
    tokens in every slot, the longest context the mix declares."""
    from repro.serve.engine import ServeConfig
    from repro.serve.kv_cache import pages_for
    s = cfg["serving"]
    b = mix["max_batch"]
    return ServeConfig(
        max_batch=b, max_len=max_len, max_new=int(mix["output"]["max"]),
        prefill_chunk=s["prefill_chunk"], paged=True,
        page_size=s["page_size"],
        num_pages=b * pages_for(max_len, s["page_size"]))


def pallas_kernels(hlo: str) -> set:
    """Names of the Pallas kernels on the ``tpu_custom_call`` lines of a
    compiled program."""
    names = set()
    pat = re.compile("|".join(KERNELS))
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.update(pat.findall(line))
    return names


class System:
    """Engine plus the harness's wrappers, counters and spans."""

    def __init__(self, cfg: dict, mix: dict, params, max_len: int):
        from repro.serve.engine import Engine
        from repro.serve.quantized import QuantizedModel
        mc, qc = bm.program_config(cfg)
        self.cfg, self.mix = cfg, mix
        model = QuantizedModel(mc, qc, kernel_mode="auto")
        self.engine = eng = Engine(model, params,
                                   serve_config(cfg, mix, max_len))
        self.hlo = {}
        self.decode_calls: list = []   # (t, rows, context, [per row])
        self.chunk_calls: list = []    # (t, prompt tokens, offset, last)
        self._plan = None              # [dir, start, length, origin, on]
        self.trace_window = None       # (start, stop) of the trace
        self._compile(eng)
        self._wrap(eng)

    def _compile(self, eng) -> None:
        """Compile the decode and chunk programs once and keep the
        executables and their optimized HLO."""
        b, c = eng.cfg.max_batch, eng.cfg.prefill_chunk
        zeros = jnp.zeros((b,), jnp.int32)
        self._decode_exe = eng._decode.lower(
            eng.params, eng._last_tok, eng._kv.cache, eng._idle_keys,
            eng._zero_poison).compile()
        self._chunk_exe = eng._chunk.lower(
            eng.params, jnp.zeros((b, c), jnp.int32), zeros, eng._kv.cache,
            zeros).compile()
        self.hlo = {"decode": self._decode_exe.as_text(),
                    "chunk": self._chunk_exe.as_text()}

    def check_kernels(self) -> dict:
        """Kernel names each compiled program calls; raises where a
        program lacks one the served path needs."""
        got = {k: pallas_kernels(v) for k, v in self.hlo.items()}
        for k, want in WANT.items():
            if not want <= got[k]:
                raise RuntimeError(f"compiled {k} program lacks Pallas "
                                   f"kernels {sorted(want - got[k])}")
        return got

    def _wrap(self, eng) -> None:
        from jax.profiler import TraceAnnotation
        dec, chk, admit = self._decode_exe, self._chunk_exe, \
            eng._admit_chunked

        def decode(params, tok, cache, keys, poison):
            rows = [i for i, s in enumerate(eng._slots)
                    if s is not None and eng._prefill_prog[i] is None]
            self.decode_calls.append(
                (time.perf_counter(), len(rows),
                 sum(eng._seq_len[i] + 1 for i in rows),
                 [eng._seq_len[i] + 1 for i in rows]))
            with TraceAnnotation("bench.decode"):
                return dec(params, tok, cache, keys, poison)

        def chunk(params, tokens, chunk_len, cache, offset):
            # the engine advances the oldest mid-prefill slot (host state,
            # read without a device transfer)
            slot = min((i for i, p in enumerate(eng._prefill_prog)
                        if p is not None),
                       key=lambda i: eng._prefill_prog[i][0].rid)
            done = eng._seq_len[slot]
            left = len(eng._prefill_prog[slot][1]) - done
            n = min(eng.cfg.prefill_chunk, left)
            self.chunk_calls.append((time.perf_counter(), n, done,
                                     n == left))
            with TraceAnnotation("bench.chunk"):
                return chk(params, tokens, chunk_len, cache, offset)

        def admit_chunked():
            with TraceAnnotation("bench.admit"):
                admit()

        eng._decode, eng._chunk, eng._admit_chunked = decode, chunk, \
            admit_chunked

    def plan_trace(self, path: str, start: float, length: float) -> None:
        """Trace the device from ``start`` seconds into the window for
        ``length`` seconds, at step boundaries."""
        self._plan = [path, start, length, None, False]

    def window_started(self, t0: float) -> None:
        """The generator's window opens at ``t0`` (after any ramp)."""
        if self._plan is not None:
            self._plan[3] = t0

    def _trace_tick(self, now: float) -> None:
        plan = self._plan
        if plan[3] is None:
            return
        if not plan[4] and now - plan[3] >= plan[1]:
            jax.block_until_ready(self.engine._kv.cache)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # spans only, no Python calls
            jax.profiler.start_trace(plan[0], profiler_options=opts)
            plan[4] = True
            self.trace_window = (time.perf_counter(), None)
        elif plan[4] and now - plan[3] >= plan[1] + plan[2]:
            self.finish_trace()

    def finish_trace(self) -> None:
        """Stop a running trace (after the device has finished)."""
        if self._plan is None or not self._plan[4]:
            return
        jax.block_until_ready(self.engine._kv.cache)
        self.trace_window = (self.trace_window[0], time.perf_counter())
        jax.profiler.stop_trace()
        self._plan = None

    def step(self) -> int:
        from jax.profiler import TraceAnnotation
        if self._plan is not None:
            self._trace_tick(time.perf_counter())
        with TraceAnnotation("bench.step"):
            return self.engine.step()

    def kv_codes(self, reqs: list, layers: int) -> list:
        """For each request: the int8 K and V codes (layers, written, H, D)
        that the pool holds for it in its first ``layers`` layers, on the
        host; None where the request is not decoding in a slot."""
        eng = self.engine
        cache = eng._kv.cache
        ps = cache.page_size
        table = np.asarray(cache.page_table)
        pools = [np.asarray(cache.k[:layers]), np.asarray(cache.v[:layers])]
        out = []
        for req in reqs:
            slot = next((i for i, r in enumerate(eng._slots) if r is req),
                        None)
            if slot is None or eng._prefill_prog[slot] is not None:
                out.append(None)
                continue
            n = eng._seq_len[slot]
            pages = table[slot, :-(-n // ps)]
            out.append(tuple(
                x[:, pages].reshape(layers, -1, *x.shape[3:])[:, :n]
                for x in pools))
        return out

    def reset_counters(self) -> None:
        self.decode_calls.clear()
        self.chunk_calls.clear()


def warm_up(system: System, vocab: int) -> None:
    """Run every program and eager op the window will use, so nothing
    compiles inside it: a reservation of every page count the mix's
    prompts need, then one request through a final prefill chunk, its
    one-row sampler, decode steps and cancellation."""
    from repro.serve.kv_cache import pages_for
    eng = system.engine
    mix = system.mix
    ps = eng.cfg.page_size
    lo = pages_for(int(mix["prompt"]["min"]), ps)
    hi = pages_for(int(mix["prompt"]["max"]), ps)
    for n in range(lo, hi + 1):
        if not eng._kv.reserve(0, n * ps):
            raise RuntimeError(f"pool cannot hold {n} pages for one slot")
        eng._kv.free(0)
    req = eng.submit(np.full((ps,), 1, np.int32))
    while len(req.out_tokens) < 3 and not req.done:
        system.step()
    eng.cancel(req.rid)
    eng._all.clear()
    jax.block_until_ready(eng._kv.cache)
    system.reset_counters()
