"""Scheduler-grade serving engine: continuous batching over one
fixed-shape decode step, with bucketed batch prefill, paged-KV admission
control, preemption, on-device sampling and streaming.

The engine owns a slot-table of ``max_batch`` sequences sharing one cache
(the jitted decode step is shape-stable — production TPU serving
requirement) and a FIFO queue of pending requests. Per iteration it

  1. **admits**: pops a FIFO prefix run of pending requests whose prompts
     pad to the same bucket (``prefill_bucket`` multiples — a bounded
     compile set instead of one compile per prompt length) and prefills
     them in ONE batched call; end-padding is exact for causal-attention
     models (``model.supports_padded_prefill`` — recurrent families group
     by exact length instead). Paged mode reserves each prompt's
     ``ceil(len / page_size)`` pages before prefill and the prefilled K/V
     are spliced into those pages.
     **Chunked admission** (``prefill_chunk > 0``, vLLM-style): prompts
     are instead fed through ``model.prefill_chunk`` in
     ``<= prefill_chunk`` token chunks, ONE chunk per engine step,
     interleaved with the decode step — a long prompt never stalls
     in-flight decodes for more than one chunk's worth of work (the
     inter-token-latency bound BENCH_serve.json measures), and the chunk
     call is a single compile (shape-stable ``(max_batch,
     prefill_chunk)``) instead of one per bucket. Because every prefill
     path reads the cache as stored through the same tiled kernel
     (DESIGN.md §10), chunked and whole-prompt admission are
     token-IDENTICAL for dense models (MoE routing competes per chunk —
     the same approximation bucket padding makes, see the caveat below);
     preemption resume re-enters through the same chunked path;
  2. **ensures capacity** (paged): a sequence crossing a page boundary gets
     one page from the free list; when the pool runs dry the engine
     preempts the *longest* active sequence — frees its pages and re-queues
     it at the queue head (resume = re-prefill prompt + generated tokens,
     whose next-token logits match the unpreempted decode);
  3. **decodes + samples on device**: one jitted step computes logits AND
     the next token — greedy at ``temperature == 0``, otherwise
     temperature/top-k sampling with a per-(request, position) PRNG key
     (``fold_in(fold_in(seed, rid), n_generated)``), so sampled streams are
     reproducible and independent of slot placement or preemption;
  4. **retires**: EOS / ``max_new`` / capacity; completed slots return
     their pages to the free list (linear slots just reset ``len``).

All scheduling state (queue, slot lengths, page free list) is host-side —
the loop never blocks on a device sync to schedule; the only readback per
step is the sampled token batch itself (plus its per-row finiteness flag,
which rides the same transfer).

Failure model (DESIGN.md §12): every request ends in exactly one terminal
:class:`RequestStatus`, surfaced through ``on_done``.  The jitted decode
and the chunk-prefill completion fold a per-row ``isfinite`` reduction
over the final logits into the existing sample readback, so a non-finite
value escaping a quantized matmul quarantines ONLY its own slot
(``FAILED_NAN``: pages scrubbed then freed — survivors stay
token-identical to solo runs).  Pool pressure degrades instead of
livelocking: requests whose resume can never fit the idle pool fail fast
with ``FAILED_POOL``; a request evicted ``max_preemptions`` times (or
``stall_preemptions`` times without growing) is failed rather than
re-queued; and a no-progress watchdog (``watchdog_steps``) fails the
largest page-owner when the whole engine stops moving.  Backpressure is
explicit: ``max_queue`` bounds the pending deque and ``submit`` raises
:class:`QueueFull`.  Deadlines (``ttl_s``) are wall-clock, checked
host-side at step boundaries.  ``cancel(rid)`` reclaims pages whether the
request is queued, mid-prefill or decoding.  Callbacks that raise are
isolated per-request (``FAILED_CALLBACK`` for ``on_token``;
logged-and-detached for ``on_done``) and never unwind the step loop.  A
``repro.serve.faults.FaultPlan`` passed to the constructor drives every
one of these paths deterministically from tests.

Cache layouts are behind ``repro.serve.kv_cache`` stores: ``LinearCache``
(contiguous ``max_batch × max_len`` slab) and ``PagedCache``
(``ServeConfig.paged``) — a page pool + per-sequence page tables, so cache
memory tracks live tokens, not slots (DESIGN.md §9). The engine calls only
``reserve`` / ``splice`` / ``ensure_append`` / ``free`` and never inspects
cache-entry ranks.

Prefix caching (``ServeConfig.prefix_cache``, DESIGN.md §14): chunked
admission passes the resume tokens to ``reserve``, which adopts the
longest resident full-page prefix (refcounted shares of immutable pages)
and reports the matched token count; ``_seq_len`` starts there, so
``_advance_prefill`` chunk-prefills only the novel suffix — prefill work
drops proportionally to the hit rate, token-identically to the no-sharing
engine.  Preemption and completion just drop references; FAILED_NAN
retirement quarantines co-readers of shared pages instead of scrubbing
live KV (see ``_retire_slot``).  ``prefix_stats`` is read by
``launch/serve.py``'s prefix-cache line and by the tests.

Host spans (``repro.serve.spans``): ``step`` records each phase of the
iteration — ``serve.step`` with ``serve.deadlines``, ``serve.admit``,
``serve.prefill`` (and ``serve.prefill.wait`` around a final chunk's
readback), ``serve.capacity``, ``serve.decode.dispatch``,
``serve.decode.wait`` and ``serve.emit`` inside it — in an always-on
bounded ring; :meth:`Engine.span_records` returns them.

Quantized serving: pass a ``repro.serve.quantized.QuantizedModel`` over a
QTensor tree — ``Model`` and ``QuantizedModel`` expose the same
``prefill`` / ``decode_step`` / ``init_cache`` / ``init_paged_cache``
interface, so the engine is oblivious to quantization.

Caveat (MoE): expert-capacity routing competes across every token in a
prefill batch, so bucket padding — and likewise chunk boundaries in
chunked admission — can shift routing for valid tokens; dense/GQA models
are exact, MoE prefill is the documented approximation in both modes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import enum
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding
from repro.models.model import Model
from repro.serve import faults as flt
from repro.serve import kv_cache
from repro.serve import spans as spans_lib
from repro.utils import logger, next_multiple


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 512
    max_new: int = 64
    eos_token: int = -1          # -1: never terminates early
    temperature: float = 0.0     # 0 = greedy
    top_k: int = 0               # 0 = full categorical (when sampling)
    seed: int = 0                # PRNG seed for sampling
    prefill_bucket: int = 32     # prompt-length bucket granularity
    prefill_chunk: int = 0       # > 0: chunked admission — prompts prefill
    #                              in <= prefill_chunk token chunks (one
    #                              chunk per engine step, vLLM-style token
    #                              budget) interleaved with decode steps;
    #                              0 = whole-prompt bucketed prefill
    paged: bool = False          # page-table KV cache + admission control
    page_size: int = 64
    num_pages: int = 0           # 0 = auto (max_batch * pages(max_len))
    max_pages_per_seq: int = 0   # 0 = auto (ceil(max_len / page_size))
    prefix_cache: bool = False   # refcounted prefix-page sharing across
    #                              requests (DESIGN.md §14): admission
    #                              adopts the longest resident full-page
    #                              prefix and chunked prefill resumes at
    #                              the first novel token; needs paged=True
    #                              and prefill_chunk > 0
    # failure model (DESIGN.md §12) --------------------------------------
    max_queue: int = 0           # > 0: bound the pending deque; submit
    #                              raises QueueFull past it (backpressure)
    default_ttl_s: float = 0.0   # > 0: wall-clock TTL applied to every
    #                              submit without an explicit ttl_s
    max_preemptions: int = 64    # evictions per request before FAILED_POOL
    stall_preemptions: int = 16  # consecutive no-growth evictions per
    #                              request before FAILED_POOL (mid-prefill
    #                              victims never grow — this is their cap)
    watchdog_steps: int = 16     # consecutive no-progress engine steps
    #                              before degrading (fail largest owner)
    integrity_checks: bool = False   # debug: device/host page-table
    #                                  cross-check on every free


class RequestStatus(enum.Enum):
    """Lifecycle of a request; exactly one terminal state per request."""
    QUEUED = "queued"
    RUNNING = "running"
    COMPLETED = "completed"                      # EOS / max_new / capacity
    FAILED_NAN = "failed_nan"                    # non-finite logits
    FAILED_DEADLINE = "failed_deadline"          # wall-clock TTL expired
    FAILED_POOL = "failed_pool"                  # pool can/will never serve
    FAILED_CALLBACK = "failed_callback"          # on_token raised
    REJECTED_QUEUE_FULL = "rejected_queue_full"  # backpressure at submit
    CANCELLED = "cancelled"                      # cancel(rid)

    @property
    def terminal(self) -> bool:
        return self not in (RequestStatus.QUEUED, RequestStatus.RUNNING)


class QueueFull(RuntimeError):
    """submit() backpressure: the bounded admission queue is at
    ``ServeConfig.max_queue``.  ``.request`` carries the rejected request
    (terminal status ``REJECTED_QUEUE_FULL``)."""

    def __init__(self, msg: str, request: "Request" = None):
        super().__init__(msg)
        self.request = request


@dataclasses.dataclass(eq=False)
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,) int32
    out_tokens: list = dataclasses.field(default_factory=list)
    status: RequestStatus = RequestStatus.QUEUED
    error: Optional[str] = None  # human-readable cause for FAILED_* states
    deadline: Optional[float] = None   # absolute time.monotonic() TTL
    preemptions: int = 0
    stalls: int = 0              # consecutive evictions without growth
    last_evict_len: int = -1     # resume_len at the previous eviction
    on_token: Optional[Callable[["Request", int], None]] = None
    on_done: Optional[Callable[["Request"], None]] = None

    @property
    def done(self) -> bool:
        """True once the request reached a terminal status."""
        return self.status.terminal

    @property
    def resume_len(self) -> int:
        """Length of :meth:`resume_tokens` without materializing it."""
        return len(self.prompt) + len(self.out_tokens)

    def resume_tokens(self) -> np.ndarray:
        """Prompt for (re-)admission: original prompt plus everything
        generated so far — the prefill's next-token logits continue the
        stream exactly where the preempted decode left off."""
        if not self.out_tokens:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.out_tokens, np.int32)])


class Engine:
    """Continuous-batching engine; optionally mesh-native (DESIGN.md §13).

    With ``mesh`` (a ``(data, model)`` mesh from
    ``repro.launch.mesh.make_serving_mesh``), weights and KV pools are
    sharded once at construction — QTensor leaves column-parallel over
    "model" via the packing-aware joint resolution, cache pools over their
    KV-head dim — and every jitted step runs with the mesh bound so the
    flash kernels take their shard_map path.  The scheduler is untouched:
    page tables and lengths are replicated host-authored state, so
    admission/eviction/preemption stay zero-device-sync, and sharded
    serving is token-identical to single-device.  ``rules`` defaults to
    :func:`repro.sharding.make_serving_rules`.
    """

    def __init__(self, model: Model, params, cfg: ServeConfig,
                 faults: Optional[flt.FaultPlan] = None, *,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[dict] = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        if cfg.max_new < 1:
            raise ValueError(f"max_new={cfg.max_new} unsupported: a request "
                             f"must be allowed at least one generated token")
        if cfg.max_queue < 0:
            raise ValueError(f"max_queue={cfg.max_queue} unsupported: use 0 "
                             f"(unbounded) or a positive queue bound")
        if cfg.prefix_cache and not (cfg.paged and cfg.prefill_chunk > 0):
            raise ValueError(
                "prefix_cache=True needs paged=True and prefill_chunk > 0: "
                "prefix reuse shares whole pool pages and resumes chunked "
                "prefill at the first novel token (DESIGN.md §14)")
        self._faults = faults
        if cfg.paged:
            self._kv = kv_cache.PagedCache(
                model, cfg.max_batch, cfg.max_len, cfg.page_size,
                num_pages=cfg.num_pages,
                max_pages_per_seq=cfg.max_pages_per_seq,
                faults=faults, integrity_checks=cfg.integrity_checks,
                prefix_cache=cfg.prefix_cache)
        else:
            self._kv = kv_cache.LinearCache(model, cfg.max_batch,
                                            cfg.max_len)
        self._mesh = mesh
        self._rules = (dict(rules) if rules is not None
                       else sharding.make_serving_rules()
                       if mesh is not None else None)
        self._cache_shardings = None
        if mesh is not None:
            # shard once, at rest: committed params + cache pools pin the
            # layout for every subsequent jitted step (the in-jit
            # constraints below keep the outputs on the same fixpoint)
            self.params = jax.device_put(
                params, sharding.tree_shardings(
                    model.param_logical_axes(), params, mesh, self._rules))
            cache = self._kv.cache
            self._cache_shardings = sharding.tree_shardings(
                model.cache_logical_axes(cache), cache, mesh, self._rules)
            self._kv.cache = jax.device_put(cache, self._cache_shardings)
        self._decode = jax.jit(self._decode_and_sample)
        # per-instance jit (like _decode): a class-level jit with static
        # `self` would retain every engine's cache buffers process-wide
        self._prefill = jax.jit(self._prefill_call, static_argnums=(3,))
        self._pending: deque[Request] = deque()
        self._all: list[Request] = []
        self._slots: list[Optional[Request]] = [None] * cfg.max_batch
        self._seq_len = [0] * cfg.max_batch          # host-side cache lens
        self._next_rid = 0                            # monotonic request ids
        self._last_tok = jnp.zeros((cfg.max_batch, 1), jnp.int32)
        self._base_key = jax.random.PRNGKey(cfg.seed)
        self._idle_keys = jnp.zeros((cfg.max_batch,)
                                    + self._base_key.shape,
                                    self._base_key.dtype)
        self._zero_poison = jnp.zeros((cfg.max_batch,), jnp.float32)
        if mesh is not None:
            # replicate the host-authored step inputs so the decode jit
            # sees one stable input signature from the first call on
            rep = NamedSharding(mesh, P())
            self._last_tok = jax.device_put(self._last_tok, rep)
            self._base_key = jax.device_put(self._base_key, rep)
            self._idle_keys = jax.device_put(self._idle_keys, rep)
            self._zero_poison = jax.device_put(self._zero_poison, rep)
        self._supports_padded = bool(
            getattr(model, "supports_padded_prefill", False))
        # chunked admission: per-slot (request, resume tokens) for prompts
        # mid-prefill (None = slot idle or decoding); tokens written so
        # far is _seq_len[slot], same as for decoding slots
        self._prefill_prog: list[Optional[tuple]] = [None] * cfg.max_batch
        self._step_idx = 0
        self._watchdog = 0       # consecutive steps without progress
        self._progress = 0       # tokens streamed + chunks + retirements
        self._spans = spans_lib.SpanRecorder()
        # prefix-cache accounting (launch/serve.py's prefix line): one
        # lookup per chunked admission, matched tokens skip prefill entirely
        self.prefix_stats = {"lookups": 0, "hits": 0,
                             "matched_tokens": 0, "prefilled_tokens": 0}
        if cfg.prefill_chunk:
            if not getattr(model, "supports_chunked_prefill", False):
                raise ValueError(
                    f"chunked admission (prefill_chunk={cfg.prefill_chunk}) "
                    f"needs model.prefill_chunk; "
                    f"{type(model).__name__} does not support it")
            self._chunk = jax.jit(self._chunk_prefill_call)

    def _bound(self):
        """Mesh-binding context for jitted calls (no-op single-device)."""
        if self._mesh is None:
            return contextlib.nullcontext()
        return sharding.use_mesh(self._mesh, self._rules)

    def _pin_cache(self, cache):
        """Constrain a jitted step's cache output to the canonical
        shardings, so the step loop's cache carry is a sharding fixpoint
        (host-side splice/free scatters preserve it between steps)."""
        if self._cache_shardings is None:
            return cache
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, cache, self._cache_shardings)

    def _pin_replicated(self, *xs):
        """Constrain per-step host readbacks (tokens, flags) replicated."""
        if self._mesh is None:
            return xs
        rep = NamedSharding(self._mesh, P())
        return tuple(jax.lax.with_sharding_constraint(x, rep) for x in xs)

    def _chunk_prefill_call(self, params, tokens, chunk_len, cache, offset):
        """The one jitted chunk step (shape-stable: (max_batch,
        prefill_chunk) tokens — ONE compile for all of chunked admission,
        vs one per bucket x group size for whole-prompt prefill).
        ``last_only``: only the final chunk's last valid row is ever
        sampled, so chunk steps skip the (B, C, vocab) head matmul and
        return (B, 1, vocab)."""
        logits, cache = self.model.prefill_chunk(
            params, {"tokens": tokens, "chunk_len": chunk_len}, cache,
            offset, last_only=True)
        return logits, self._pin_cache(cache)

    def _prefill_call(self, params, tokens, lengths, bucket: int):
        """Whole-prompt batched prefill, jitted per (bucket, group size) —
        the bounded compile set the bucketing exists for (an eager call
        would re-trace the layer scan on every admission)."""
        batch = {"tokens": tokens}
        if lengths is not None:
            batch["lengths"] = lengths
        return self.model.prefill(params, batch, max_len=bucket)

    # ------------------------------------------------------------------
    # submission / cancellation
    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, on_token=None, on_done=None,
               ttl_s: Optional[float] = None) -> Request:
        """Queue a request.  Raises :class:`ValueError` on prompts the
        engine can NEVER serve (empty, or exceeding what the idle pool can
        hold) and :class:`QueueFull` past ``max_queue`` — both before any
        engine state changes, so a rejected submit is side-effect free.
        ``ttl_s`` overrides ``cfg.default_ttl_s`` (0 = no deadline)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(f"prompt must be a non-empty 1-D token array; "
                             f"got shape {prompt.shape}")
        if not self._kv.fits_idle(int(prompt.size) + 1):
            raise ValueError(
                f"prompt length {prompt.size} unservable: "
                + self._kv.unservable_reason(int(prompt.size) + 1))
        req = Request(rid=self._next_rid, prompt=prompt, on_token=on_token,
                      on_done=on_done)
        self._next_rid += 1
        ttl = self.cfg.default_ttl_s if ttl_s is None else ttl_s
        if ttl and ttl > 0:
            req.deadline = time.monotonic() + ttl
        if self.cfg.max_queue and len(self._pending) >= self.cfg.max_queue:
            self._all.append(req)
            self._finish_request(
                req, RequestStatus.REJECTED_QUEUE_FULL,
                error=f"admission queue full ({self.cfg.max_queue} "
                      f"pending): backpressure — retry later")
            raise QueueFull(req.error, request=req)
        self._pending.append(req)
        self._all.append(req)
        return req

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it is — queued, mid-prefill, or
        decoding — reclaiming its pages.  Returns False if ``rid`` is
        unknown or already terminal."""
        for req in self._pending:
            if req.rid == rid:
                self._pending.remove(req)
                self._finish_request(req, RequestStatus.CANCELLED)
                return True
        for slot, req in enumerate(self._slots):
            if req is not None and req.rid == rid:
                self._retire_slot(slot, RequestStatus.CANCELLED)
                return True
        return False

    def status_counts(self) -> dict:
        """Terminal-status histogram over every request this engine saw."""
        counts: dict = {}
        for r in self._all:
            counts[r.status.name] = counts.get(r.status.name, 0) + 1
        return counts

    def memory_report(self) -> dict:
        """Per-device resident bytes: weights and KV cache, measured from
        the arrays' addressable shards (max over devices — even sharding
        makes them uniform).  The scaling bench's per-device-footprint
        rows and ``launch/serve.py``'s startup report both read this."""
        def per_device(tree) -> int:
            per: dict = {}
            for leaf in jax.tree_util.tree_leaves(tree):
                if not isinstance(leaf, jax.Array):
                    continue
                for s in leaf.addressable_shards:
                    per[s.device.id] = per.get(s.device.id, 0) \
                        + s.data.nbytes
            return max(per.values()) if per else 0
        n = self._mesh.devices.size if self._mesh is not None else 1
        return {"device_count": int(n),
                "weight_bytes_per_device": per_device(self.params),
                "kv_bytes_per_device": per_device(self._kv.cache)}

    def span_records(self, t0: Optional[float] = None,
                     t1: Optional[float] = None) -> list[spans_lib.Span]:
        """The host spans of the steps between ``time.perf_counter()``
        times ``t0`` and ``t1`` (None = unbounded), from the newest
        ``repro.serve.spans.CAPACITY`` kept."""
        return self._spans.records(t0, t1)

    # ------------------------------------------------------------------
    # termination plumbing (the ONLY places a request goes terminal)
    # ------------------------------------------------------------------
    def _finish_request(self, req: Request, status: RequestStatus,
                        error: Optional[str] = None) -> None:
        req.status = status
        req.error = error
        if error is not None:
            logger.debug("rid=%d -> %s: %s", req.rid, status.name, error)
        self._progress += 1
        self._dispatch_done(req)

    def _retire_slot(self, slot: int, status: RequestStatus,
                     error: Optional[str] = None) -> None:
        """Terminal path for an occupied slot: scrub poisoned pages, free,
        clear scheduling state, then fire on_done.

        FAILED_NAN under prefix sharing (DESIGN.md §14): the slot's
        exclusively-owned pages may hold non-finite K/V, so they are zeroed
        before the free list recycles them (masked attention rows still
        enter ``p @ v`` with weight 0.0 and ``0.0 * NaN = NaN``).  A SHARED
        page cannot be scrubbed — other readers attend to it live — so
        ``quarantine`` unmaps it and reports the co-readers, and each is
        failed FAILED_NAN in turn (recursively, so transitive readers fall
        too and pages whose refcount has dropped to 1 get scrubbed by the
        later retirement)."""
        req = self._slots[slot]
        co_readers: list[int] = []
        if status is RequestStatus.FAILED_NAN:
            co_readers = self._kv.quarantine(slot)
        self._slots[slot] = None
        self._seq_len[slot] = 0
        self._prefill_prog[slot] = None
        self._kv.free(slot)
        self._finish_request(req, status, error)
        for other in co_readers:
            if self._slots[other] is not None:
                self._retire_slot(
                    other, RequestStatus.FAILED_NAN,
                    error=f"shared prefix page(s) poisoned by rid="
                          f"{req.rid} (FAILED_NAN quarantine)")

    def _dispatch_token(self, req: Request, tok: int) -> bool:
        """Record + stream one token; False when the user callback raised
        (the request fails as FAILED_CALLBACK, the step loop survives)."""
        req.out_tokens.append(tok)
        self._progress += 1
        if self._faults is not None and self._faults.fires(
                flt.CALLBACK_RAISE, rid=req.rid):
            return False
        if req.on_token is None:
            return True
        try:
            req.on_token(req, tok)
            return True
        except Exception:
            logger.exception("on_token callback for rid=%d raised — "
                             "failing the request", req.rid)
            return False

    def _dispatch_done(self, req: Request) -> None:
        """Fire on_done exactly once; a raising callback is detached and
        logged (the request is already terminal — nothing to fail)."""
        cb, req.on_done = req.on_done, None
        if cb is None:
            return
        try:
            cb(req)
        except Exception:
            logger.exception("on_done callback for rid=%d raised — "
                             "detached (request already terminal)", req.rid)

    # ------------------------------------------------------------------
    # deadlines + fail-fast admission
    # ------------------------------------------------------------------
    def _expired(self, req: Request, now: float) -> bool:
        if req.deadline is not None and now > req.deadline:
            return True
        return (self._faults is not None
                and self._faults.fires(flt.DEADLINE, rid=req.rid))

    def _check_deadlines(self) -> None:
        """Retire TTL-expired requests (queued or slotted) at the step
        boundary — host-side wall clock, no device work."""
        now = time.monotonic()
        for slot, req in enumerate(self._slots):
            if req is not None and self._expired(req, now):
                self._retire_slot(slot, RequestStatus.FAILED_DEADLINE,
                                  error=f"deadline exceeded after "
                                        f"{len(req.out_tokens)} tokens")
        if not self._pending:
            return
        kept: deque[Request] = deque()
        while self._pending:
            req = self._pending.popleft()
            if self._expired(req, now):
                self._finish_request(req, RequestStatus.FAILED_DEADLINE,
                                     error="deadline exceeded while queued")
            else:
                kept.append(req)
        self._pending = kept

    def _shed_unservable(self) -> None:
        """Fail-fast requests whose resume can NEVER fit the idle pool
        (e.g. grown past it through evict/resume cycles) — waiting cannot
        help, and re-queueing them forever is the livelock the old
        engine-wide RuntimeError papered over."""
        kept: deque[Request] = deque()
        while self._pending:
            req = self._pending.popleft()
            if self._kv.fits_idle(req.resume_len + 1):
                kept.append(req)
            else:
                self._finish_request(
                    req, RequestStatus.FAILED_POOL,
                    error=f"resume length {req.resume_len} unservable: "
                          + self._kv.unservable_reason(req.resume_len + 1))
        self._pending = kept

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------
    def _req_keys(self, reqs) -> jax.Array:
        """Per-(request, position) sampling keys: reproducible across
        engines, slot placements and preemptions.  Greedy mode ignores
        keys, so skip the per-step fold_in dispatch and pass a constant."""
        if self.cfg.temperature <= 0:
            if len(reqs) == self.cfg.max_batch:
                return self._idle_keys
            return self._idle_keys[:len(reqs)]
        rids = jnp.asarray([r.rid for r in reqs], jnp.int32)
        cnts = jnp.asarray([len(r.out_tokens) for r in reqs], jnp.int32)
        fold = lambda r, c: jax.random.fold_in(
            jax.random.fold_in(self._base_key, r), c)
        return jax.vmap(fold)(rids, cnts)

    def _sample(self, lg: jax.Array, keys: jax.Array) -> jax.Array:
        """lg (N, V) -> (N,) int32. Greedy at temperature 0, else
        temperature/top-k categorical (jit-safe; config is static)."""
        if self.cfg.temperature <= 0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        lg = lg.astype(jnp.float32) / self.cfg.temperature
        if self.cfg.top_k > 0:
            kth = jax.lax.top_k(lg, self.cfg.top_k)[0][:, -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        return jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)

    def _decode_and_sample(self, params, tok, cache, keys, poison):
        """One jitted decode + sample + per-row finiteness flag.  ``poison``
        is the NAN_LOGITS injection vector (0.0 when inactive — adding it
        is numerically neutral); ``ok`` rides the sampled-token transfer,
        so NaN detection costs no extra device sync."""
        logits, cache = self.model.decode_step(params, tok, cache)
        with jax.named_scope("sample"):
            lg = logits[:, -1, :] + poison[:, None]
            ok = jnp.all(jnp.isfinite(lg), axis=-1)
            nxt, ok = self._pin_replicated(self._sample(lg, keys), ok)
        return nxt, ok, self._pin_cache(cache)

    def _poison(self, active: list[int]) -> jax.Array:
        """NAN_LOGITS injection vector for this decode step (one entry per
        slot; NaN poisons that row's logits inside the jitted step)."""
        if self._faults is None:
            return self._zero_poison
        vec = None
        for i in active:
            if self._faults.fires(flt.NAN_LOGITS, rid=self._slots[i].rid,
                                  slot=i):
                if vec is None:
                    vec = np.zeros((self.cfg.max_batch,), np.float32)
                vec[i] = np.nan
        return self._zero_poison if vec is None else jnp.asarray(vec)

    # ------------------------------------------------------------------
    # admission: bucketed batch prefill
    # ------------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        """Pad-to-bucket prompt length (a bounded compile set). Models that
        cannot take end padding (recurrent state) get exact lengths."""
        if not self._supports_padded:
            return n
        return min(next_multiple(n, self.cfg.prefill_bucket),
                   self._kv.capacity)

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _admit(self) -> None:
        with self._spans.span("serve.admit", rids=[]) as sp:
            self._shed_unservable()
            free = self._free_slots()
            while free and self._pending:
                # FIFO prefix run sharing one bucket -> one batched prefill
                bucket = self._bucket(self._pending[0].resume_len)
                group: list[Request] = []
                while (self._pending and len(group) < len(free)
                       and self._bucket(self._pending[0].resume_len)
                       == bucket):
                    group.append(self._pending.popleft())
                # paged: reserve prompt pages up front; requests that do
                # not fit go back to the queue head (FIFO order preserved)
                fitted: list[tuple[int, Request, int]] = []
                for req in group:
                    slot = free[len(fitted)]
                    if not self._kv.reserve(slot, req.resume_len):
                        break
                    fitted.append((slot, req, req.resume_len))
                overflow = group[len(fitted):]
                self._pending.extendleft(reversed(overflow))
                if not fitted:
                    # pool (transiently) dry: wait for completions to free
                    # pages; a queue that can never drain trips the
                    # watchdog
                    return
                free = free[len(fitted):]
                rids = [req.rid for _, req, _ in fitted]
                sp.attrs["rids"] += rids
                with self._spans.span(
                        "serve.prefill", rids=rids, offset=0,
                        tokens=sum(ln for _, _, ln in fitted)):
                    free.extend(self._prefill_group(fitted, bucket))

    def _prefill_group(self, fitted: list, bucket: int) -> list[int]:
        """Prefill one bucket's ``(slot, request, length)`` group in one
        batched call, sample each first token and hand the slots to
        decode.  Returns the slots that retired straight from prefill."""
        tokens = np.zeros((len(fitted), bucket), np.int32)
        lengths = np.asarray([ln for _, _, ln in fitted], np.int32)
        for row, (_, req, ln) in enumerate(fitted):
            tokens[row, :ln] = req.resume_tokens()
        with self._bound():
            logits, cache1 = self._prefill(
                self.params, jnp.asarray(tokens),
                jnp.asarray(lengths) if self._supports_padded else None,
                bucket)
        lg = logits[:, -1, :]
        if self._faults is not None:
            pv = np.zeros((len(fitted),), np.float32)
            for row, (_, req, _) in enumerate(fitted):
                if self._faults.fires(flt.NAN_LOGITS, rid=req.rid):
                    pv[row] = np.nan
            if np.isnan(pv).any():
                lg = lg + jnp.asarray(pv)[:, None]
        ok_dev = jnp.all(jnp.isfinite(lg), axis=-1)
        toks_dev = self._sample(lg, self._req_keys([r for _, r, _ in fitted]))
        with self._spans.span("serve.prefill.wait"):
            toks, ok = jax.device_get((toks_dev, ok_dev))
        slot_ids, slot_toks, assigned = [], [], []
        for row, (slot, req, ln) in enumerate(fitted):
            self._kv.splice(slot, cache1, row, int(ln))
            self._slots[slot] = req
            self._seq_len[slot] = int(ln)
            req.status = RequestStatus.RUNNING
            assigned.append(slot)
            if not bool(ok[row]):
                self._retire_slot(slot, RequestStatus.FAILED_NAN,
                                  error="non-finite logits at prefill")
                continue
            tok = int(toks[row])
            if not self._dispatch_token(req, tok):
                self._retire_slot(slot, RequestStatus.FAILED_CALLBACK,
                                  error="on_token callback raised")
                continue
            slot_ids.append(slot)
            slot_toks.append(tok)
            self._maybe_finish(slot, tok)
        if slot_ids:
            self._last_tok = self._last_tok.at[
                jnp.asarray(slot_ids), 0].set(jnp.asarray(slot_toks))
        # a request can retire straight from prefill (EOS / max_new=1 /
        # quarantine): hand its slot back so this pass can refill it
        return [s for s in assigned if self._slots[s] is None]

    # ------------------------------------------------------------------
    # chunked admission (ServeConfig.prefill_chunk > 0)
    # ------------------------------------------------------------------
    def _admit_chunked(self) -> None:
        """Assign pending requests to free slots (FIFO) and queue their
        prompts for chunk-sized prefill; the prefill work itself happens in
        :meth:`_advance_prefill`, one chunk per engine step, so a long
        prompt never monopolizes the step loop.  Paged mode reserves the
        prompt's pages up front exactly like whole-prompt admission (same
        free-list accounting, same preemption sizes)."""
        with self._spans.span("serve.admit", rids=[]) as sp:
            self._shed_unservable()
            for slot in self._free_slots():
                if not self._pending:
                    return
                req = self._pending[0]
                toks = req.resume_tokens()
                if not self._kv.reserve(slot, req.resume_len, tokens=toks):
                    # pool (transiently) dry: wait for completions to free
                    # pages; a queue that can never drain trips the
                    # watchdog
                    return
                self._pending.popleft()
                self._slots[slot] = req
                sp.attrs["rids"].append(req.rid)
                # prefix hit: the matched tokens are already resident in
                # shared pages — chunked prefill resumes at the first novel
                # token (matched is a page multiple, so the slot's writes
                # only ever touch its fresh exclusive pages)
                matched = self._kv.matched_tokens(slot)
                self._seq_len[slot] = matched
                req.status = RequestStatus.RUNNING
                self._prefill_prog[slot] = (req, toks)
                if self.cfg.prefix_cache:
                    st = self.prefix_stats
                    st["lookups"] += 1
                    st["hits"] += int(matched > 0)
                    st["matched_tokens"] += matched

    def _advance_prefill(self) -> bool:
        """Advance the FIFO-oldest mid-prefill slot by one chunk of up to
        ``prefill_chunk`` tokens (the per-step prefill token budget).  On
        the final chunk, sample the request's first token and hand the
        slot to decode — the same gather-at-last-valid-row + sample the
        whole-prompt path performs, so the two admission modes are
        token-identical."""
        slots = [i for i in range(self.cfg.max_batch)
                 if self._prefill_prog[i] is not None]
        if not slots:
            return False
        slot = min(slots, key=lambda i: self._prefill_prog[i][0].rid)
        req, toks = self._prefill_prog[slot]
        done = self._seq_len[slot]          # tokens written so far
        c = self.cfg.prefill_chunk
        n = min(c, len(toks) - done)
        with self._spans.span("serve.prefill", rid=req.rid, tokens=n,
                              offset=done):
            tokens = np.zeros((self.cfg.max_batch, c), np.int32)
            tokens[slot, :n] = toks[done:done + n]
            chunk_len = np.zeros((self.cfg.max_batch,), np.int32)
            chunk_len[slot] = n
            # every row passes its host-known true length: rows with
            # chunk_len == 0 neither write nor attend, and the chunk call
            # resyncs their device lens (decode steps write a droppable
            # garbage token ahead of mid-prefill slots — the next chunk
            # overwrites it before it is ever attended)
            offsets = np.asarray(self._seq_len, np.int32)
            with self._bound():
                logits, cache = self._chunk(
                    self.params, jnp.asarray(tokens), jnp.asarray(chunk_len),
                    self._kv.cache, jnp.asarray(offsets))
            self._kv.cache = cache
            self._seq_len[slot] = done + n
            self._progress += 1
            self.prefix_stats["prefilled_tokens"] += n
            if done + n < len(toks):
                return True
            # prompt fully prefilled: sample the first token from the last
            # valid chunk row (the chunk call already gathered it) and
            # start decoding.  The finiteness flag rides the same readback
            # as the sampled token — only this final chunk ever syncs.
            self._prefill_prog[slot] = None
            lg = logits[slot]
            if self._faults is not None and self._faults.fires(
                    flt.NAN_LOGITS, rid=req.rid, slot=slot):
                lg = lg + jnp.float32(np.nan)
            tok_arr = self._sample(lg, self._req_keys([req]))
            ok_dev = jnp.all(jnp.isfinite(lg))
            with self._spans.span("serve.prefill.wait"):
                tok_host, ok = jax.device_get((tok_arr, ok_dev))
            if not bool(ok):
                self._retire_slot(slot, RequestStatus.FAILED_NAN,
                                  error="non-finite logits at prefill")
                return True
            # finite final-row logits certify every attended K/V row finite
            # (a NaN anywhere within lens would have propagated) — only now
            # may the sequence's full pages enter the prefix map
            self._kv.register_prefix(slot, toks)
            tok = int(tok_host[0])
            if not self._dispatch_token(req, tok):
                self._retire_slot(slot, RequestStatus.FAILED_CALLBACK,
                                  error="on_token callback raised")
                return True
            self._last_tok = self._last_tok.at[slot, 0].set(tok)
            self._maybe_finish(slot, tok)
        return True

    # ------------------------------------------------------------------
    # preemption (paged admission control)
    # ------------------------------------------------------------------
    def _preempt(self, slot: int) -> None:
        """Evict a slot, requeueing at the head — unless this request is
        storming (``max_preemptions`` lifetime evictions, or
        ``stall_preemptions`` consecutive evictions without growing —
        the no-progress signature of a pool too small for the working
        set), in which case it fails with FAILED_POOL instead of cycling
        forever."""
        req = self._slots[slot]
        logger.debug("preempt rid=%d (len=%d): pool dry", req.rid,
                     self._seq_len[slot])
        grew = req.resume_len > req.last_evict_len
        req.stalls = 0 if grew else req.stalls + 1
        req.last_evict_len = req.resume_len
        req.preemptions += 1
        self._slots[slot] = None
        self._seq_len[slot] = 0
        self._prefill_prog[slot] = None   # mid-prefill victims restart
        self._kv.free(slot)
        if (req.preemptions > self.cfg.max_preemptions
                or req.stalls >= self.cfg.stall_preemptions):
            self._finish_request(
                req, RequestStatus.FAILED_POOL,
                error=f"preemption storm: evicted {req.preemptions}x "
                      f"({req.stalls} consecutive without progress) — the "
                      f"pool is too small for the working set")
            return
        req.status = RequestStatus.QUEUED
        self._pending.appendleft(req)   # resumes first when pages free up

    def _ensure_capacity(self, active: list[int]) -> list[int]:
        """Make every active slot's next token write page-backed; evict the
        sequence holding the most pages (mid-prefill prompts included —
        their pages are reserved up front, so a half-prefilled long prompt
        is the biggest reclaim) when the pool runs dry.  A preempted
        mid-prefill request restarts through the same chunked path on
        resume, token-identically."""
        with self._spans.span("serve.capacity", preempted=[]) as sp:
            for slot in list(active):
                if self._slots[slot] is None:
                    continue
                while not self._kv.ensure_append(slot, self._seq_len[slot]):
                    live = [i for i, s in enumerate(self._slots)
                            if s is not None]
                    # rank victims by what their eviction actually frees:
                    # shared pages survive the free (their other readers
                    # keep them live), so only exclusively-owned pages count
                    victim = max(live, key=lambda i: (
                        self._kv.reclaimable_pages(i), self._seq_len[i], -i))
                    sp.attrs["preempted"].append(self._slots[victim].rid)
                    self._preempt(victim)
                    if victim == slot:
                        break
        return [i for i in active if self._slots[i] is not None]

    # ------------------------------------------------------------------
    # the engine loop
    # ------------------------------------------------------------------
    def _maybe_finish(self, slot: int, tok: int) -> None:
        req = self._slots[slot]
        cache_full = self._seq_len[slot] >= self._kv.capacity - 1
        if (tok == self.cfg.eos_token
                or len(req.out_tokens) >= self.cfg.max_new or cache_full):
            self._retire_slot(slot, RequestStatus.COMPLETED)

    def _degrade(self) -> None:
        """Watchdog action after ``watchdog_steps`` no-progress steps:
        something (a starved queue, an injected allocator fault) has
        wedged the engine — fail ONE request (the largest page owner, or
        the queue head when no slot is live) with FAILED_POOL so the rest
        of the trace can move, rather than spinning forever."""
        live = [i for i, s in enumerate(self._slots) if s is not None]
        if live:
            victim = max(live, key=lambda i: (
                self._kv.reclaimable_pages(i), self._seq_len[i], -i))
            self._retire_slot(
                victim, RequestStatus.FAILED_POOL,
                error=f"watchdog: no engine progress for "
                      f"{self.cfg.watchdog_steps} steps — failing the "
                      f"largest page owner to unwedge the pool")
        elif self._pending:
            req = self._pending.popleft()
            self._finish_request(
                req, RequestStatus.FAILED_POOL,
                error=f"watchdog: admission starved for "
                      f"{self.cfg.watchdog_steps} steps — the pool never "
                      f"freed enough pages to admit this request")
        self._watchdog = 0

    def step(self) -> int:
        """One engine iteration: deadlines + admit + (chunked mode) one
        prefill chunk + ensure pages + one batched decode step.  Chunked
        admission interleaves a bounded ``prefill_chunk`` tokens of prompt
        work with every decode step, so in-flight decodes keep streaming
        while a long prompt drips in.  Returns the number of sequences
        advanced."""
        with self._spans.span("serve.step", step=self._step_idx):
            progress0 = self._progress
            if self._faults is not None:
                self._faults.begin_step(self._step_idx)
            with self._spans.span("serve.deadlines"):
                self._check_deadlines()
            if self.cfg.prefill_chunk:
                self._admit_chunked()
                did_chunk = self._advance_prefill()
            else:
                self._admit()
                did_chunk = False
            active = [i for i, s in enumerate(self._slots)
                      if s is not None and self._prefill_prog[i] is None]
            if self.cfg.paged:
                active = self._ensure_capacity(active)
            if active:
                self._decode_active(active)
            self._step_idx += 1
            if self._progress == progress0 and (
                    self._pending or any(s is not None for s in self._slots)):
                self._watchdog += 1
                if self._watchdog > self.cfg.watchdog_steps:
                    self._degrade()
            else:
                self._watchdog = 0
            return len(active) + int(did_chunk)

    def _decode_active(self, active: list[int]) -> None:
        """One batched decode step over the ``active`` slots: dispatch,
        read the sampled tokens back, stream them and retire."""
        with self._spans.span(
                "serve.decode.dispatch", rows=len(active),
                context=sum(self._seq_len[i] + 1 for i in active)):
            reqs = [self._slots[i] if (self._slots[i] is not None
                                       and self._prefill_prog[i] is None)
                    else _IDLE_REQ for i in range(self.cfg.max_batch)]
            with self._bound():
                nxt, ok_dev, cache = self._decode(
                    self.params, self._last_tok, self._kv.cache,
                    self._req_keys(reqs), self._poison(active))
            self._kv.cache = cache
            self._last_tok = nxt[:, None]
        with self._spans.span("serve.decode.wait"):
            nxt_host, ok = jax.device_get((nxt, ok_dev))
        with self._spans.span("serve.emit"):
            for i in active:
                req = self._slots[i]
                if req is None:
                    # already retired mid-loop: a FAILED_NAN quarantine on
                    # an earlier slot failed this one as a co-reader of a
                    # poisoned shared page — its sampled token is void
                    continue
                if not bool(ok[i]):
                    # quarantine ONLY this slot: scrub + free its pages,
                    # fail it, keep the rest of the batch streaming
                    self._retire_slot(i, RequestStatus.FAILED_NAN,
                                      error=f"non-finite logits at decode "
                                            f"step {len(req.out_tokens)}")
                    continue
                tok = int(nxt_host[i])
                if not self._dispatch_token(req, tok):
                    self._retire_slot(i, RequestStatus.FAILED_CALLBACK,
                                      error="on_token callback raised")
                    continue
                self._seq_len[i] += 1
                self._maybe_finish(i, tok)

    def run(self, max_steps: int = 0) -> list[Request]:
        """Drain the queue; returns every submitted request, in
        submission order.  ``max_steps > 0`` bounds the loop (tests /
        hang detection): exceeding it raises RuntimeError."""
        steps = 0
        while any(not r.done for r in self._all):
            n = self.step()
            steps += 1
            if max_steps and steps >= max_steps:
                live = [r.rid for r in self._all if not r.done]
                raise RuntimeError(
                    f"run() exceeded max_steps={max_steps} with requests "
                    f"{live} still live — engine wedged?")
            if n == 0 and not self._pending:
                break
        return self._all


_IDLE_REQ = Request(rid=0, prompt=np.zeros((1,), np.int32))
