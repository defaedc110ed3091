"""Continuous-batching inference engine.

This is the component the reference stubs with a per-tier ``time.Sleep``
(cmd/queue-manager/main.go:139-153) and the seam its Worker exposes as
``ProcessFunc`` (internal/priorityqueue/worker.go:33): messages drained
from the priority queues become generation requests; the engine packs
them into a fixed set of decode slots and advances every active sequence
one token per batched device step.

Scheduling model (TPU-first):

- **Fixed batch geometry.** One compiled decode program for
  (batch_size, max_pages); admission/finish/preemption only permute which
  sequence occupies which slot — nothing recompiles at runtime.
- **Strict-priority admission with step-boundary preemption** (BASELINE
  config #4): pending requests are served in (priority, arrival) order;
  when no slot is free, an arriving request preempts the least-urgent
  running sequence iff strictly more urgent. The preempted sequence keeps
  its KV pages and resumes without re-prefill — preemption costs a slot
  swap, not recomputation. (The reference's strict-priority poll,
  cmd/queue-manager/main.go:112-124, can only reorder waiting messages;
  it cannot displace running work.)
- **Paged KV with conversation pinning** (BASELINE config #3): completed
  conversations keep their pages resident (pinned via
  :class:`PageAllocator`); the next turn prefills only its new tokens on
  top of the cached KV (continuation prefill, models/llama.py).
  Ownership is single-writer: admitting a conversation request *adopts*
  the cached pages (the cache entry is removed); finishing re-caches
  them. Pins are dropped by the conversation service's eviction
  (``on_evict`` hook — one eviction policy for host state and HBM state,
  state_manager.go:354-403), by the pin TTL, or by pool pressure (LRU).
- **Pool-pressure shedding:** when pages run out, idle pinned
  conversations are reclaimed LRU-first; if still short, the least
  urgent running sequence is preempted *with* page release and later
  resumes by re-prefilling prompt+generated (correct, slower — the
  pathological case, bounded to the lowest tier).
"""

from __future__ import annotations

import heapq
import itertools
import queue
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from llmq_tpu import chaos
from llmq_tpu.core.clock import Clock, SYSTEM_CLOCK
from llmq_tpu.core.types import Message, Priority
from llmq_tpu.engine.executor import Executor, HostStaging
from llmq_tpu.engine.kv_allocator import PageAllocator
from llmq_tpu.engine.tokenizer import Tokenizer, get_tokenizer
from llmq_tpu.metrics.registry import get_metrics
from llmq_tpu.observability.critical_path import (
    get_critical_path, note_first_token as boot_note_first_token)
from llmq_tpu.observability.device import get_device_telemetry
from llmq_tpu.observability.usage import (DEFAULT_TENANT, RequestUsage,
                                          get_usage_ledger,
                                          sanitize_tenant)
from llmq_tpu.tenancy import get_tenant_registry, weighted_token_caps
from llmq_tpu.utils.logging import get_logger
from llmq_tpu.utils import profiling
from llmq_tpu.utils.profiling import SpanRecorder, capture_held

log = get_logger("engine")


def _prefetch(arr) -> None:
    """Queue a device→host transfer at DISPATCH time. The transfer rides
    behind the producing program on the device queue and lands ~RTT
    after the value exists — so a later blocking fetch finds it already
    delivered instead of paying dispatch-to-host latency then."""
    try:
        arr.copy_to_host_async()
    except (AttributeError, RuntimeError):
        pass


def _pack_prefill_slices(cands, S, T, budget, tenant_caps):
    """Pack prefill candidates (most urgent first) into ≤S slices of
    ≤T tokens each, ≤budget total. With ``tenant_caps`` (multi-tenant
    contention, docs/tenancy.md) pass 1 packs each tenant only up to
    its weight-proportional share; pass 2 hands any leftover out in
    plain urgency order, including WIDENING a slice pass 1 truncated
    at its tenant's cap — so caps bind exactly when the budget is
    genuinely contended and unclaimed share is never stranded
    (work-conserving). Returns ``[(seq, token_ids)]``."""
    pf_plan = []
    plan_idx: Dict[int, int] = {}    # seq.order → index into pf_plan
    packed = 0
    packed_by_tenant: Dict[str, int] = {}
    passes = (True, False) if tenant_caps is not None else (False,)
    for capped in passes:
        for seq in cands:
            if packed >= budget:
                break
            idx = plan_idx.get(seq.order)
            if idx is None and len(pf_plan) >= S:
                continue             # no slice slots left; widen only
            have = len(pf_plan[idx][1]) if idx is not None else 0
            width = min(T - have, budget - packed)
            tid = seq.req.tenant_id
            if capped:
                width = min(width,
                            tenant_caps.get(tid, budget)
                            - packed_by_tenant.get(tid, 0))
            if width <= 0:
                continue
            sl = seq.todo_ids[:have + width]
            added = len(sl) - have   # todo may be shorter than width
            if added <= 0:
                continue
            if idx is None:
                plan_idx[seq.order] = len(pf_plan)
                pf_plan.append((seq, sl))
            else:
                pf_plan[idx] = (seq, sl)
            packed += added
            packed_by_tenant[tid] = packed_by_tenant.get(tid, 0) + added
    return pf_plan


@dataclass
class GenRequest:
    """One generation request (decoupled from the queue-plane Message so
    the engine is usable as a plain library)."""

    id: str
    prompt: str
    priority: Priority = Priority.NORMAL
    conversation_id: str = ""
    history_text: str = ""       # full-history fallback on conversation KV miss
    max_new_tokens: int = 0      # 0 → engine default
    temperature: float = 0.0
    #: Billing identity for the usage plane (docs/observability.md
    #: "Usage & goodput") — who this request's hardware consumption is
    #: attributed to.
    tenant_id: str = DEFAULT_TENANT

    @classmethod
    def from_message(cls, msg: Message) -> "GenRequest":
        md = msg.metadata or {}
        return cls(
            id=msg.id,
            prompt=msg.content,
            priority=msg.priority,
            conversation_id=msg.conversation_id,
            history_text=str(md.get("history_text", "")),
            max_new_tokens=int(md.get("max_new_tokens", 0) or 0),
            temperature=float(md.get("temperature", 0.0) or 0.0),
            tenant_id=sanitize_tenant(getattr(msg, "tenant_id", "")),
        )


@dataclass
class GenResult:
    text: str = ""
    tokens: List[int] = field(default_factory=list)
    prompt_tokens: int = 0
    cached_tokens: int = 0       # KV reused from the conversation cache
    finish_reason: str = ""      # eos | length | cancelled | error
    error: str = ""
    #: Which KV tier served this request's conversation re-arrival
    #: (docs/tiering.md): "hbm" | "host" | "store" | "recompute"; ""
    #: when the tiering plane is off or no cached state was involved.
    kv_tier: str = ""


class GenHandle:
    """Caller-side future for a submitted request."""

    def __init__(self, request: GenRequest) -> None:
        self.request = request
        self.result: Optional[GenResult] = None
        self.submitted_at = time.perf_counter()
        self.finished_at: Optional[float] = None   # per-request latency
        #: Lifecycle timestamps (perf_counter) the engine records:
        #: ``admitted`` (slot taken), ``prefill_done`` (first token
        #: sampled and fetched), ``first_token`` (first non-EOS token
        #: committed host-side). Feeds the bench's per-request latency
        #: decomposition and the API's first-token metric.
        self.marks: Dict[str, float] = {}
        #: Per-request usage attribution (observability/usage.py),
        #: filled at finish when the usage plane is enabled:
        #: device_seconds, waste_seconds(+reason), kv_page_seconds,
        #: saved_prefill_device_seconds, tenant.
        self.usage: Optional[Dict] = None
        self._on_token = None
        self._done = threading.Event()
        self._cancelled = threading.Event()

    def on_token(self, cb) -> None:
        """Register a streaming callback ``cb(token_id: int)`` invoked
        for every committed token, in order, from the engine thread.
        Tokens arrive in device-chunk granularity bursts (the engine
        commits a fetched chunk at once) — callbacks must be cheap and
        must not call back into the engine."""
        self._on_token = cb

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self) -> None:
        self._cancelled.set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def _finish(self, result: GenResult) -> None:
        # First writer wins: the zero-duplicate completion contract. A
        # crash recovery racing a queued completion-executor finish for
        # the same handle must not overwrite the delivered result (the
        # recovery drains the pool first, but the guard makes the
        # contract hold even if a future caller forgets to).
        if self._done.is_set():
            return
        self.result = result
        self.finished_at = time.perf_counter()
        self._done.set()

    @property
    def latency(self) -> Optional[float]:
        """Submit → finish seconds, once done."""
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at


class _Sequence:
    """Engine-internal state of one admitted request."""

    __slots__ = ("req", "handle", "prompt_ids", "generated", "pages",
                 "block_table", "pos", "cached_len", "last_token", "slot",
                 "prefilled", "order", "adopted", "prefill_ids",
                 "prefill_start", "carry", "written_ids", "rebuild",
                 "todo_ids", "todo_pos", "todo_rebuild", "todo_resume",
                 "first_handle", "eff_prio", "arrival", "prefix_match",
                 "reuse_counted", "mixed_pending", "pf_tokens_run",
                 "usage", "pending_emit", "served_tier", "cp_decode_s",
                 "tails")

    def __init__(self, req: GenRequest, handle: GenHandle, order: int,
                 max_pages: int) -> None:
        self.req = req
        self.handle = handle
        self.order = order
        self.prompt_ids: List[int] = []
        self.generated: List[int] = []   # sampled output tokens (no EOS)
        self.pages: List[int] = []
        self.block_table = np.zeros(max_pages, np.int32)
        self.pos = 0              # tokens whose KV is written
        self.cached_len = 0       # prefix reused from conversation cache
        self.last_token = 0       # most recent sampled token (next decode input)
        self.slot: Optional[int] = None
        self.prefilled = False
        self.adopted = False      # conversation cache adoption attempted
        self.prefill_ids: List[int] = []  # what prefill saw (for resume)
        self.prefill_start = 0
        self.carry: List[int] = []        # cache's pending token (see _ConvKV)
        #: Token ids whose KV occupies positions [0, pos) — the exact
        #: content of this sequence's pages. Lets a page-releasing
        #: preemption (or a capacity fold) rebuild the FULL context,
        #: including adopted conversation history, by re-prefilling.
        self.written_ids: List[int] = []
        self.rebuild = False      # pages were released; re-prefill written_ids
        #: Incremental-prefill state: tokens not yet run, next write
        #: position, and the completion context snapshotted at admission.
        self.todo_ids: List[int] = []
        self.todo_pos = 0
        self.todo_rebuild = False
        self.todo_resume: Optional[int] = None
        #: Device array holding the final prefill chunk's sampled first
        #: token (async prefill): dispatched without a host sync, fetched
        #: on a later engine step so the ~RTT of the sync overlaps other
        #: scheduling/compute instead of serializing admission.
        self.first_handle = None
        #: Effective priority: starts at the request's tier and is
        #: PROMOTED one tier per elapsed multiple of the tier's
        #: max_wait_time while pending (SLA-aware scheduling — the
        #: reference config's per-tier max_wait, pkg/config/config.go:
        #: 151-156, which its code never consults).
        self.eff_prio = int(req.priority)
        self.arrival = 0.0
        #: Active radix-tree prefix match (prefixcache.PrefixMatch): the
        #: sequence holds one allocator ref per matched page (inside
        #: ``pages``) and one lock per matched node — unlocked whenever
        #: the pages leave the sequence (finish, shed, un-match).
        self.prefix_match = None
        #: Row tails this sequence took on its way (``[(end token, tail
        #: slot)]``, docs/prefix_cache.md "Tails"): each waits for the
        #: radix node that ends there, which exists once the stream is
        #: published; what is left when the sequence ends is freed.
        self.tails: List = []
        #: Hit/miss counted for this REQUEST (first admission only —
        #: a shed-and-rebuilt sequence must not re-count its reuse).
        self.reuse_counted = False
        #: In-flight MIXED chunks that carry a prefill slice of this
        #: sequence: one more at each such dispatch, one fewer when the
        #: chunk is processed. Non-zero keeps the host-assembled paths
        #: off the sequence (its slices are on the device queue); only a
        #: carried dispatch may queue the next slice behind them.
        self.mixed_pending = 0
        #: Prefill tokens actually run for this admission (all dispatch
        #: paths) — feeds the learned prefill-rate EWMA at completion.
        self.pf_tokens_run = 0
        #: Usage-plane accumulator (observability/usage.py): charged by
        #: the engine thread with this sequence's pro-rata share of
        #: every measured chunk; None with the plane disabled (the hard
        #: off-switch — every charge point is then one None check).
        self.usage: Optional[RequestUsage] = None
        #: Tokens committed but not yet delivered to the streaming
        #: callback (async-pipeline completion offload): the engine
        #: thread appends here and flushes one batch job per chunk to
        #: the completion executor — SSE framing never runs on the
        #: step-dispatch path. Always empty with the pipeline off.
        self.pending_emit: List[int] = []
        #: KV tier that served this re-arrival (tiering plane only;
        #: "" otherwise) — lands on GenResult.kv_tier.
        self.served_tier = ""
        #: Critical-path plane: device+readback seconds attributed to
        #: this sequence's DECODE rows (pro-rata chunk shares, same
        #: weighting as the usage charge). Splits the decode span into
        #: decode_compute vs decode_stall at decomposition time. Stays
        #: 0.0 with the plane disabled.
        self.cp_decode_s = 0.0

    def sort_key(self):
        return (self.eff_prio, self.order)


class _InflightChunk:
    """A dispatched-but-unfetched decode chunk: the executor handle plus
    the per-slot sequence snapshot and budgets it was dispatched with.
    Processing uses the SNAPSHOT refs — a slot re-assigned after
    dispatch belongs to a sequence that never participated.
    ``fetch_box`` is the fetcher thread's completion cell
    ({ev, out, err}); None when the engine fetches inline.
    ``pf`` is set for MIXED chunks: the (seq, n_tokens, final)
    snapshot of the prefill slices fused into the program — their
    handle.fetch() returns (decode tokens, slice first-tokens)."""

    __slots__ = ("handle", "seqs", "budgets", "fetch_box", "pf",
                 "dispatch_s", "dispatched_at", "chunk")

    def __init__(self, handle, seqs, budgets, pf=None,
                 dispatch_s: float = 0.0,
                 dispatched_at: float = 0.0, chunk: int = 0) -> None:
        self.handle = handle
        #: The serial number of the ``engine.dispatch`` that sent it:
        #: its ``engine.fetch`` and ``engine.commit`` carry the same.
        self.chunk = chunk
        self.seqs = seqs          # List[Optional[_Sequence]], len B
        self.budgets = budgets    # np.ndarray (B,) int32
        self.fetch_box = None
        self.pf = pf              # List[(seq, n_tokens, final)] | None
        #: Host-side assembly + dispatch seconds for this chunk — the
        #: "dispatch" leg of the step decomposition; the device/readback
        #: legs are measured at fetch (observability/device.py).
        self.dispatch_s = dispatch_s
        #: perf_counter when the program was handed to the device queue
        #: — the start of this chunk's device span. The telemetry's
        #: overlap attribution (timed_fetch) needs it to split the span
        #: into novel device time vs time that overlapped other
        #: in-flight chunks (the pipelining win).
        self.dispatched_at = dispatched_at


class _CompletionPool:
    """Off-path completion executor (docs/performance.md "Async
    pipeline"): token-stream callbacks, trace recording,
    detokenization and handle completion run here, so the engine
    thread's only job between dispatches is packing the next chunk.
    Jobs for one request key always land on the same worker (FIFO per
    worker), so per-request token order — and tokens-before-done — are
    preserved at any worker count."""

    def __init__(self, workers: int, name: str,
                 spans: SpanRecorder) -> None:
        self._qs: List[queue.Queue] = [queue.Queue()
                                       for _ in range(max(1, workers))]
        self._threads: List[threading.Thread] = []
        for i, q in enumerate(self._qs):
            t = threading.Thread(
                target=self._loop,
                args=(q, spans.loop(f"completion.{i}.{name}")),
                name=f"completion-{i}-{name}", daemon=True)
            t.start()
            self._threads.append(t)

    def _loop(self, q: queue.Queue, watch: profiling.LoopWatch) -> None:
        """One beat a job; the wait for the next job has no bound, so
        it is a rest, not a gap. What a job was inside when it overran
        is its ``engine.deliver`` span (a consumer's callback that
        blocks)."""
        watch.open()
        try:
            while True:
                watch.rest()
                fn = q.get()
                watch.wake()
                if fn is None:
                    return
                try:
                    fn()
                except Exception:  # noqa: BLE001 — a broken consumer
                    # must not kill the worker; the next request's jobs
                    # still run
                    log.exception("completion job failed")
        finally:
            watch.close()

    def submit(self, key: str, fn) -> None:
        self._qs[hash(key) % len(self._qs)].put(fn)

    def drain(self, timeout: float = 10.0) -> bool:
        """Barrier: returns True once every job submitted before the
        call has run (crash recovery's completion-dedup depends on it —
        a queued finish must land before handles are re-failed). A
        timeout (a worker wedged inside a blocking stream callback) is
        returned AND logged loudly — the caller's dedup guarantee is
        weakened and that must not be silent."""
        evs = []
        for q in self._qs:
            ev = threading.Event()
            q.put(ev.set)
            evs.append(ev)
        ok = True
        for ev in evs:
            if not ev.wait(timeout):
                ok = False
        if not ok:
            log.error(
                "completion pool drain timed out after %.1fs — a queued "
                "completion may land after the barrier (duplicate-"
                "delivery risk if this was a crash-recovery drain)",
                timeout)
        return ok

    def stop(self) -> None:
        for q in self._qs:
            q.put(None)
        for t in self._threads:
            t.join(timeout=5.0)


class _EngineMetrics:
    """The engine's Prometheus children, each bound once.
    ``family.labels(engine, ...)`` takes a lock, builds a tuple and
    looks a dict up on every call (``metrics.py:labels`` stood among
    the idle gaps of PR 22's traces), and a chunk touches a dozen of
    them. ``m("decode_steps")`` / ``m("preemptions", tier)`` returns
    the child, made at its first use — so a family this engine never
    touches gains no series."""

    __slots__ = ("_families", "_engine", "_kids")

    def __init__(self, families, engine: str) -> None:
        self._families = families
        self._engine = engine
        self._kids: Dict = {}

    def __call__(self, family: str, *labels: str):
        key = (family, labels) if labels else family
        kid = self._kids.get(key)
        if kid is None:
            kid = self._kids[key] = getattr(
                self._families, family).labels(self._engine, *labels)
        return kid


@dataclass
class _ConvKV:
    """A conversation's KV kept resident in HBM between turns."""

    pages: List[int]
    block_table: np.ndarray
    length: int                  # tokens cached
    last_used: float
    #: The token ids backing the cached KV, positions [0, length) — kept
    #: so the cache can be rebuilt from text if its pages are reclaimed
    #: mid-turn, and so an over-capacity turn can fold the prefix into a
    #: sliding-window re-prefill.
    tokens: List[int] = field(default_factory=list)
    #: On a "length" finish the final sampled token never went through a
    #: decode step, so its KV is absent — the next turn must prefill it
    #: first or the cached history silently misses one token.
    pending: Optional[int] = None


class InferenceEngine:
    def __init__(
        self,
        executor: Executor,
        tokenizer: Optional[Tokenizer] = None,
        *,
        name: str = "engine0",
        max_decode_steps: int = 256,
        preemption: bool = True,
        kv_pin_ttl: float = 600.0,
        realtime_admission_ms: float = 50.0,
        enable_metrics: bool = True,
        clock: Optional[Clock] = None,
        tier_max_wait: Optional[Dict[Priority, float]] = None,
        prefix_cache=None,
        mixed_batch=None,
        async_pipeline=None,
        kv_tiering=None,
    ) -> None:
        self.executor = executor
        self.spec = executor.spec
        self.tokenizer = tokenizer or get_tokenizer()
        self.name = name
        self.max_decode_steps = max_decode_steps
        self.preemption_enabled = preemption
        self.kv_pin_ttl = kv_pin_ttl
        #: Target admission latency for a pending REALTIME request; the
        #: chunk cap derives from this and the measured step time.
        self.realtime_admission_ms = realtime_admission_ms
        self._clock = clock or SYSTEM_CLOCK
        #: Per-tier SLA bound: a pending request older than its tier's
        #: max_wait_time is promoted one tier per elapsed multiple
        #: (deadline-aware admission; starvation bound for low tiers).
        self.tier_max_wait = dict(tier_max_wait or {})
        self._metrics = get_metrics() if enable_metrics else None
        self._m = (_EngineMetrics(self._metrics, name)
                   if self._metrics else None)
        # Per-engine recorder: stats must not mix spans across engines.
        # The one span primitive (utils/profiling.py): the step's
        # vocabulary below lands in this ring and, while a profiler
        # capture is held, on the device trace's clock. A JAX
        # executor brings the ring its warm-up wrote to
        # (``engine.warmup.compile``, one span per program).
        self._prof = getattr(executor, "spans", None)
        if self._prof is None:
            self._prof = SpanRecorder()
        #: The engine loop's watch (utils/profiling.LoopWatch): one
        #: beat an iteration of ``_loop``, the waits on the device as
        #: its named waits. Opened by the loop's thread (an engine
        #: stepped by a test or a bench keeps the waits' account
        #: alone).
        self._watch = self._prof.loop(f"engine.{name}")
        #: The open ``engine.wait`` span of the idle stretch the loop
        #: is in (None while it works), and whether its last poll was
        #: ended by the wake event.
        self._wait_span = None
        self._woken = False
        #: Serial number of the newest ``engine.dispatch``.
        self._dispatch_serial = 0
        #: Device telemetry plane (observability/device.py): step-time
        #: decomposition, live tok/s + MFU, HBM accounting — shared by
        #: name with the executor (compile-cache side) and read live by
        #: /metrics, GET /api/v1/engine/stats and bench rate points.
        self._telemetry = get_device_telemetry(name,
                                               metrics=enable_metrics)
        # Weak provider: the telemetry registry is process-lived; a
        # strong ref to the engine would keep every test/bench engine
        # (and its device arrays) alive forever.
        _eng_ref = weakref.ref(self)

        def _hbm_provider():
            eng = _eng_ref()
            return eng._hbm_snapshot() if eng is not None else None

        self._telemetry.set_hbm_provider(_hbm_provider)
        # Model identity for the MFU estimator. Skipped when already
        # configured: a builder-constructed JaxExecutor shares this
        # very instance (same name) and configured it in its own
        # __init__ — repeating would walk param_count over the full
        # tree a second time at startup.
        info_fn = getattr(executor, "telemetry_info", None)
        if info_fn is not None and self._telemetry.n_params == 0:
            try:
                self._telemetry.configure_model(**info_fn())
            except Exception:  # noqa: BLE001 — telemetry must not block init
                log.exception("telemetry model info failed for %s", name)
        #: All tokens committed to sequences (device telemetry's live
        #: decode-rate source; engine-local so metrics-off benches can
        #: still read it).
        self.tokens_generated_total = 0
        #: Usage plane (observability/usage.py): the process-wide
        #: attribution ledger this engine charges. Hard off-switch:
        #: with ``observability.usage.enabled`` false every charge
        #: point below reduces to one attribute check.
        self._usage = get_usage_ledger()
        #: Critical-path plane (observability/critical_path.py): with
        #: ``observability.critical_path.enabled`` false every extra
        #: mark/accumulation site below reduces to one attribute check
        #: — byte-identical to pre-feature behavior.
        self._cp = get_critical_path()
        #: Tenancy plane (llmq_tpu/tenancy/, docs/tenancy.md): decode
        #: fairness past the queue — under multi-tenant contention the
        #: chunk's decode-row token budget and the mixed batcher's
        #: prefill-token budget are capped at weight-proportional
        #: shares. Disabled (the default), each fused-step check is one
        #: attribute read.
        self._tenancy = get_tenant_registry()

        #: dp page universes (mesh-native executor, docs/multihost.md):
        #: when the executor serves a dp×tp mesh, batch rows shard over
        #: dp in contiguous blocks of B/dp and the pool's page axis
        #: splits the same way — the allocator mirrors that split so a
        #: sequence's pages are handed out of the universe its rows
        #: compute on. 1 (every non-mesh executor) is byte-identical
        #: to the unsharded allocator.
        self.dp_shards = max(1, int(getattr(executor, "dp_shards", 1)))
        self._rows_per_shard = max(
            1, self.spec.batch_size // self.dp_shards)
        self.allocator = PageAllocator(self.spec.num_pages,
                                       self.spec.page_size,
                                       dp_shards=self.dp_shards)
        #: Radix-tree prefix KV cache (docs/prefix_cache.md). None when
        #: disabled — every code path below then degrades to the exact
        #: pre-cache behavior (the config's hard off-switch).
        #: ``prefix_cache`` accepts a core.config.PrefixCacheConfig or
        #: anything with the same fields.
        self._prefix_cache = None
        self._row_tail = None
        if prefix_cache is not None and getattr(prefix_cache, "enabled",
                                                False):
            from llmq_tpu.prefixcache import PrefixCache
            #: What rebuilds a row's state at a page boundary, for a
            #: family that says so and an executor that holds tail
            #: slots (``executor.row_tail``), else None: a prefix hit
            #: for a row-state family is then declined.
            self._row_tail = getattr(executor, "row_tail", None)
            tail_kw = {}
            if self._row_tail is not None:
                page_bytes = sum(
                    int(np.prod(shape)) * np.dtype(dt).itemsize
                    for shape, dt in executor.kv_page_spec())
                tail_kw = {"tail_slots": self._row_tail["slots"],
                           "tail_cost_pages": -(-self._row_tail["bytes"]
                                                // max(1, page_bytes))}
            self._prefix_cache = PrefixCache(
                self.allocator, self.spec.page_size,
                max_pages=int(getattr(prefix_cache, "max_cached_pages", 0)),
                policy=getattr(prefix_cache, "eviction", "lru"), **tail_kw)
        #: Admission-level reuse counters (engine-local so benches with
        #: prometheus disabled can still read them): an admission that
        #: starts from cached KV — a pinned conversation or a radix
        #: match — is a hit; a from-scratch prefill is a miss.
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.cached_prefill_tokens_total = 0
        self._state_manager = None
        self._slots: List[Optional[_Sequence]] = [None] * self.spec.batch_size
        self._pending: List = []           # heap of (prio, order, _Sequence)
        self._inbox: List[_Sequence] = []  # submitted, not yet in heap
        self._conv_cache: Dict[str, _ConvKV] = {}
        self._conv_busy: Dict[str, int] = {}    # conv id → holder seq.order
        self._conv_drop_pending: set = set()    # dropped while busy
        #: Token streams of conversations whose HBM pin was reclaimed
        #: (TTL / pool pressure) while their prefix may still live in
        #: the radix tree: a later DELETE must still be able to prune
        #: that content (the delete contract). Maps conv id → up to 4
        #: remembered streams (an expired pin and a later no-history
        #: turn publish DIVERGENT branches; all must prune on delete).
        #: Bounded FIFO; entries clear on delete. Only populated when
        #: the prefix cache is enabled.
        self._conv_evicted_tokens: Dict[str, List[List[int]]] = {}
        self._order = itertools.count()
        #: Async decode pipeline (docs/performance.md "Async
        #: pipeline"). ``async_pipeline`` accepts a
        #: core.config.AsyncPipelineConfig or anything with its fields;
        #: None/disabled keeps the exact pre-pipeline scheduling (one
        #: in-flight chunk + one carried dispatch, completions
        #: inline) — the config's hard off-switch.
        self._pipe_cfg = (async_pipeline
                          if async_pipeline is not None
                          and getattr(async_pipeline, "enabled", False)
                          else None)
        #: Bound on dispatched-but-unreconciled chunks. The off-switch
        #: value 2 IS today's scheduling: one in flight plus at most
        #: one carried dispatch per step.
        self._pipe_depth = (max(1, min(4, int(getattr(
            self._pipe_cfg, "depth", 2))))
            if self._pipe_cfg is not None else 2)
        #: Completion executor lanes (0 = completions inline on the
        #: engine thread, the pre-pipeline behavior).
        self._completion_workers = (max(1, min(8, int(getattr(
            self._pipe_cfg, "completion_workers", 1))))
            if self._pipe_cfg is not None else 0)
        self._completion: Optional[_CompletionPool] = None
        #: Dispatched-but-unfetched chunks, oldest first (pipelined
        #: path). See _emit_chunk / _dispatch_carried / step().
        self._inflight: "deque[_InflightChunk]" = deque()
        #: Chunks dispatched at each pipeline occupancy (depth AFTER
        #: the dispatch) — the bench's depth histogram. Keys are
        #: PREALLOCATED for every reachable depth so the engine thread
        #: only ever updates existing entries: stats scrapes and bench
        #: delta loops iterate this dict lock-free from other threads,
        #: and a first-seen-key insert could resize it mid-iteration.
        self.pipeline_depth_hist: Dict[int, int] = {
            d: 0 for d in range(1, 5)}
        #: Why a pipeline fill stopped, one count each time it did
        #: (``_fill_refusal`` / ``_dispatch_carried``; keys
        #: preallocated like the histogram's): ``depth`` the pipeline
        #: is as deep as configured; ``free_slot`` a row is free and
        #: something waits for the host (an arrival, a pending request,
        #: prompt slices) — the batch is NOT full; ``urgent_pending``
        #: every row is taken but an arrival is not ingested yet or the
        #: head of the pending heap may preempt; ``cancelled`` a seated
        #: request was cancelled; ``geometry`` a prefilled row is not in
        #: the newest chunk's snapshot; ``pages`` the chunk's pages
        #: would take shedding a sequence; ``row_ended`` a seated row
        #: can take no further step and only the host ends it;
        #: ``nothing_to_decode`` no row has budget left beyond the
        #: chunks in flight; ``tenancy`` tenant fairness caps budgets in
        #: the host's assembly only.
        self.fill_refusals: Dict[str, int] = {
            k: 0 for k in ("depth", "free_slot", "urgent_pending",
                           "cancelled", "geometry", "pages", "row_ended",
                           "nothing_to_decode", "tenancy")}
        self._fill_stopped = ""
        #: Host staging buffers for chunk assembly (tokens/positions/
        #: block tables/temps) — per-dispatch np.zeros churn killer.
        #: Budgets stay freshly allocated: the _InflightChunk reads
        #: them again at process time, after the ring may have rotated.
        self._staging = HostStaging(ring=max(8, self._pipe_depth + 4))
        self._mu = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        #: Fetch offload lanes: dedicated threads perform the blocking
        #: device→host fetches so the scheduling thread can keep
        #: servicing arrivals (admission + prefill dispatch) while
        #: transfers are in transit — without this, every new request
        #: waits out the current chunk's full fetch (~chunk compute +
        #: RTT) before it is even admitted. lane → (thread, queue);
        #: see _offload_fetch for why chunk and resolve lanes are
        #: separate.
        self._fetch_lanes: Dict[str, tuple] = {}
        #: Tiered KV plane (llmq_tpu/tiering/, docs/tiering.md):
        #: HBM → host-DRAM → store hierarchy under the pins and the
        #: radix tree. ``kv_tiering`` accepts a
        #: core.config.KVTieringConfig or anything with its fields;
        #: None/disabled (the default) keeps the exact HBM-only
        #: behavior — every tiering call site below is one None check.
        self._tiering = None
        if kv_tiering is not None and getattr(kv_tiering, "enabled",
                                              False):
            from llmq_tpu.tiering import KVTieringPlane
            self._tiering = KVTieringPlane(
                kv_tiering, name, executor, clock=self._clock,
                metrics=enable_metrics,
                # A finished extract/load wakes the loop so a pending
                # promotion's admission retries immediately.
                on_ready=self._wake.set)
            _eng_tier_ref = weakref.ref(self)

            def _hbm_tier():
                eng = _eng_tier_ref()
                if eng is None or eng._tiering is None:
                    return None
                n = eng.allocator.pinned_pages()
                return n, n * eng._tiering.pool.page_nbytes

            self._tiering.hbm_provider = _hbm_tier
        #: Prefix-handle tier notes deferred out of self._mu (the
        #: state manager's lock sits ABOVE the engine's — updating the
        #: handle under _mu would invert the order). Engine-thread
        #: only; flushed right after the lock drops.
        self._pending_tier_notes: List = []
        #: DISPATCHES of decode-capable programs (chunks, mixed
        #: chunks) — ``get_stats()["decode_steps"]``
        #: and ``llm_queue_decode_steps_total``; a chunk runs up to
        #: ``chunk_size`` device steps, counted in ``device_steps``.
        self.steps = 0
        #: Counted at the dispatch (``_note_dispatch``): device steps
        #: dispatched (a chunk's longest row budget), row-steps (the sum
        #: of its row budgets: tokens it commits unless a row meets EOS
        #: first), and preemptions by what the victim lost.
        self.device_steps = 0
        self.row_steps = 0
        self.preemptions = {"slot": 0, "release": 0}
        #: A model family that keeps ROW STATE beside its pages (a
        #: state-space layer's recurrent state: ``models/__init__.py``;
        #: the executor says how many bytes a row) cannot be rebuilt
        #: from pages: a prefix match, a pinned conversation, a tiering
        #: promotion or a disaggregated hand-over gives K/V for its
        #: attention layers and nothing for the others. So the engine
        #: adopts none of them for such a family (cached length 0, the
        #: whole context prefilled — token for token, through ``carry``
        #: where the cache remembered the stream), counts each one it
        #: declined, and preempts by release and rebuild only (the
        #: victim's row goes to another sequence, and its state with
        #: it). ``get_stats()["row_state"]``.
        self._row_state_bytes = int(getattr(
            executor, "row_state_bytes_per_row", 0) or 0)
        #: A family whose window layers keep their keys in row state
        #: (``executor.attention_window``): what a decode chunk's rows
        #: attend to with and without the window, the key chunks the
        #: window start skipped, and the slabs' tokens inside windows
        #: against those reserved. ``get_stats()["window"]``.
        self._window = getattr(executor, "attention_window", None)
        self.window_counts = {"dispatches": 0, "context_tokens": 0,
                              "window_tokens": 0, "chunks_visited": 0,
                              "chunks_skipped": 0, "cache_live_tokens": 0,
                              "cache_reserved_tokens": 0}
        #: What one layer's fused decode attention does for a chunk's
        #: rows at its first step (``executor.attn_work``; None where
        #: the decode attention is not that kernel's): on the dispatch
        #: span while a capture is held (``_attn_counts``).
        self._attn_work = getattr(executor, "attn_work", None)
        #: What one recurrent layer's scan kernel does for a program's
        #: prompt chunks (``executor.scan_work``; None without one): on
        #: the dispatch span while a capture is held.
        self._scan_work = getattr(executor, "scan_work", None)
        self.row_state_rebuilds = 0
        self.row_state_declined = {"prefix": 0, "conversation": 0,
                                   "tiering": 0, "disagg": 0}
        #: Prefix hits ADOPTED by a family whose tails rebuild its rows
        #: (``_row_tail``): adoptions, tails copied out of rows, and of
        #: the tokens the radix walk matched those it gave up because
        #: the deepest tail lay before the deepest matched block.
        self.row_tail_counts = {"adopted": 0, "tails_taken": 0,
                                "matched_tokens": 0, "match_cut_tokens": 0}
        #: (seq, position before, position after) of prompt slices
        #: handed to a chunk that is not dispatched yet: their stride
        #: boundaries' tails are taken once it is (``_take_due_tails``).
        self._tails_due: List = []
        #: A routed model's counters, summed over every chunk fetched
        #: (``ChunkHandle.stats``, models/deepseek_v3.py): tokens each
        #: expert received, then the experts that received any summed
        #: over the routed layers run, then those layer runs. ``None``
        #: until a chunk brings some (a dense model never does).
        self._moe: Optional[np.ndarray] = None
        #: Prefill key blocks (visited, the table held) of the mixed
        #: chunks committed (``_note_key_blocks``).
        self._key_blocks = np.zeros((2,), np.int64)
        #: Token-budget mixed prefill+decode batching
        #: (docs/architecture.md "Mixed step"). ``mixed_batch`` accepts
        #: a core.config.MixedBatchConfig or anything with the same
        #: fields; None/disabled keeps the exact pre-mixed scheduling
        #: (the config's hard off-switch).
        self._mixed_cfg = (mixed_batch
                           if mixed_batch is not None
                           and getattr(mixed_batch, "enabled", False)
                           else None)
        self.mixed_steps = 0
        self.mixed_prefill_tokens_total = 0
        self.mixed_slice_tokens_total = 0
        #: Decode-stall attribution: estimated ms decode rows spent (or
        #: would spend) behind prefill work dispatched while they were
        #: active. Unfused prefill programs serialize with the decode
        #: chunk on the device queue — their full slice counts; mixed
        #: iterations bound it by the token budget.
        self.prefill_stall_events = 0
        self.prefill_stall_ms_total = 0.0
        #: Learned prefill throughput (tokens/s EWMA over completed
        #: admissions) — drives the stall estimate above and, via
        #: ``on_prefill_observed``, the ResourceScheduler's budgeted
        #: prefill-rate estimator.
        self.prefill_tps_ewma: Optional[float] = None
        #: Optional ``fn(tokens: int, seconds: float)`` invoked once per
        #: completed prefill (e.g. ResourceScheduler.observe_prefill).
        self.on_prefill_observed = None
        #: Disaggregation plane (llmq_tpu/disagg/,
        #: docs/disaggregation.md). ``disagg_role`` is what this
        #: replica advertises on /health and to the role-aware router;
        #: ``on_conversation_cached`` fires (engine thread, outside
        #: self._mu) right after a finished turn pins its conversation
        #: KV — a prefill replica's coordinator demotes + publishes it
        #: to the exchange from there. Both default to inert.
        self.disagg_role = "unified"
        self.on_conversation_cached = None

    # -- submission ----------------------------------------------------------

    def submit(self, req: GenRequest, *, on_token=None) -> GenHandle:
        handle = GenHandle(req)
        if on_token is not None:
            # Attached BEFORE the engine can see the sequence — a
            # post-submit attach could miss the first committed tokens.
            handle.on_token(on_token)
        seq = _Sequence(req, handle, next(self._order),
                        self.spec.max_pages_per_seq)
        if self._usage.enabled:
            seq.usage = RequestUsage()
        if self._tiering is not None and req.conversation_id:
            # Re-arrival prefetch (docs/tiering.md): a store-tier
            # entry's blob starts loading NOW, overlapping queue wait
            # and admission instead of serializing with them. A turn
            # for a conversation this replica holds nothing for may
            # live on the disagg exchange — remote=True extends the
            # prefetch there (docs/disaggregation.md). The REST path
            # carries no history_text, so conversation identity is the
            # only handoff signal; prepare() no-ops the remote branch
            # when no exchange is wired, and misses are negative-cached
            # per conversation.
            remote = False
            if req.history_text or self._tiering.exchange is not None:
                with self._mu:
                    remote = req.conversation_id not in self._conv_cache
            self._tiering.prepare(req.conversation_id, remote=remote)
        with self._mu:
            self._inbox.append(seq)
        self._wake.set()
        return handle

    def generate(self, prompt: str, *, max_new_tokens: int = 0,
                 temperature: float = 0.0, conversation_id: str = "",
                 priority: Priority = Priority.NORMAL,
                 timeout: Optional[float] = 120.0) -> GenResult:
        """Synchronous convenience: submit + wait (engine loop must be
        running, or stepped by another thread)."""
        h = self.submit(GenRequest(
            id=f"gen-{next(self._order)}", prompt=prompt,
            priority=priority, conversation_id=conversation_id,
            max_new_tokens=max_new_tokens, temperature=temperature))
        if not h.wait(timeout):
            h.cancel()
            raise TimeoutError("generate timed out")
        assert h.result is not None
        if h.result.finish_reason == "error":
            raise RuntimeError(h.result.error)
        return h.result

    # -- worker seam (reference worker.go:33 ProcessFunc) --------------------

    def process_fn(self, ctx, msg: Message) -> None:
        """Plug into queueing.Worker: fills the execution seam the
        reference leaves to an HTTP endpoint. Blocks until the engine
        finishes the message (honoring the worker's deadline)."""
        req = GenRequest.from_message(msg)
        handle = self.submit(req)
        timeout = ctx.remaining() if ctx is not None else None
        if not handle.wait(timeout):
            handle.cancel()
            raise TimeoutError(
                f"engine did not finish message {msg.id} before deadline")
        res = handle.result
        assert res is not None
        if res.finish_reason == "error":
            raise RuntimeError(res.error)
        if res.finish_reason == "cancelled":
            raise RuntimeError("request cancelled")
        msg.response = res.text
        usage = {
            "prompt_tokens": res.prompt_tokens,
            "cached_tokens": res.cached_tokens,
            "completion_tokens": len(res.tokens),
            "finish_reason": res.finish_reason,
        }
        if handle.usage is not None:
            # Attribution ledger summary (observability/usage.py):
            # rides the generate_sync response back to the gateway, so
            # cross-host callers see their cost too.
            usage.update(handle.usage)
        msg.metadata["usage"] = usage

    # -- conversation service hooks (BASELINE config #3) ---------------------

    def attach_conversation_manager(self, state_manager) -> None:
        """Tie KV pin lifetime to the conversation service: touches
        refresh the pin, evictions free the pages — the executor-side
        registration the conversation service's on_touch/on_evict hooks
        exist for."""
        state_manager.on_touch(lambda conv: self.touch_conversation(conv.id))
        state_manager.on_evict(lambda conv: self.drop_conversation(conv.id))
        #: Kept so finished turns can record their prefix handle on the
        #: conversation (state_manager.record_prefix_handle). Never
        #: called while holding self._mu: the state manager fires its
        #: eviction hooks under its own lock, so the lock order is
        #: strictly state-manager → engine.
        self._state_manager = state_manager
        if self._tiering is not None:
            if self._tiering.store is None:
                # Spill-tier wiring (docs/tiering.md): the tiering
                # plane reuses the conversation store's KV-payload
                # seam when the backend implements it (sqlite/memory/
                # redis all do); a store without it simply disables
                # the store tier.
                store = getattr(state_manager, "store", None)
                if store is not None and hasattr(store, "save_kv"):
                    self._tiering.store = store
            # Worker-side degradations (failed extract/spill/load,
            # bound drops) downgrade the prefix handle, so
            # prefill_estimate never promises a prefix nothing can
            # serve. Fired with no plane lock held; takes only the
            # state manager's lock — no ordering cycle.
            self._tiering.on_tier_change = self._tier_changed_cb

    def _tier_changed_cb(self, conversation_id: str, tier: str) -> None:
        """Tiering-plane callback (worker thread): forward an
        asynchronous tier change to the recorded prefix handle."""
        sm = self._state_manager
        if sm is None:
            return
        try:
            sm.update_prefix_handle_tier(conversation_id, tier)
        except Exception:  # noqa: BLE001 — bookkeeping, not a gate
            log.exception("prefix-handle tier update failed for %s",
                          conversation_id)

    def hint_arrival(self, conversation_id: str) -> None:
        """Prefetch hint from outside the engine (any thread): the
        cluster router's affinity pass calls this the moment placement
        resolves to this replica — ``record_placement`` says who is
        coming back, and a store-tier conversation starts its blob
        load before the request even finishes dispatch."""
        if self._tiering is not None and conversation_id:
            self._tiering.prepare(conversation_id)

    def touch_conversation(self, conv_id: str) -> None:
        with self._mu:
            kv = self._conv_cache.get(conv_id)
            if kv is not None:
                kv.last_used = self._clock.now()

    def drop_conversation(self, conv_id: str) -> None:
        with self._mu:
            self._drop_conversation_locked(conv_id)

    def _drop_conversation_locked(self, conv_id: str,
                                  invalidate: bool = True) -> None:
        """``invalidate`` distinguishes the conversation being DELETED
        (service eviction/delete → its content must not linger in the
        radix tree) from merely losing its HBM pin (TTL / pool
        pressure → the tree is exactly the fallback that lets turn N+1
        still reuse the prefix, so it must survive)."""
        streams = list(self._conv_evicted_tokens.pop(conv_id, None) or [])
        kv = self._conv_cache.pop(conv_id, None)
        if kv is not None:
            self.allocator.unpin(conv_id)
            if self._usage.enabled:
                # The HBM pin's page-second meter closes HERE — at
                # demotion too: host/store residency is not the priced
                # HBM resource, so billing ends when the pages leave
                # the pool (pinned by tests/test_kv_tiering.py).
                self._usage.unpin_kv(conv_id)
            if not invalidate and self._tiering is not None:
                # Demote instead of dying: the plane dispatches the
                # payload gather (device FIFO order makes the free
                # below safe — the gather reads the pool before any
                # later program can rewrite these pages) and the
                # blocking transfer rides the tiering worker.
                tier = self._tiering.demote(conv_id, kv.pages,
                                            kv.tokens, kv.length,
                                            kv.pending)
                self._note_tier(conv_id,
                                "host" if tier == "host" else "dropped")
            elif not invalidate:
                # Tiering off and the pin reclaimed: the prefix handle
                # stays optimistic while the radix tree still covers
                # the stream (turn N+1 adopts those blocks), but when
                # nothing holds it anywhere the KV is gone for good —
                # the handle must say so (prefill_estimate's
                # non-cached contract, tests/test_kv_tiering.py).
                covered = (self._prefix_cache.cached_blocks(kv.tokens)
                           if self._prefix_cache is not None else 0)
                if covered == 0:
                    self._note_tier(conv_id, "dropped")
            self.allocator.free(kv.pages)
            streams.append(kv.tokens)
        if invalidate and self._tiering is not None:
            # Conversation deleted: no tier may keep serving its
            # content (host buffers returned, store blob deleted).
            self._tiering.forget(conv_id)
        if self._prefix_cache is not None and streams:
            if invalidate:
                # Conversation-delete invalidation: prune EVERY stream
                # this conversation ever published (a pin that expired
                # and a later no-history turn diverge into separate
                # branches — the newest alone would leave the older
                # branch matchable). Each prune takes the unlocked,
                # childless tail; a prefix shared with another live
                # stream (locked, or an interior node) survives.
                for t in streams:
                    self._prefix_cache.invalidate(t)
            else:
                # Pin merely reclaimed (TTL / pressure): remember the
                # streams so a LATER delete still honors the contract.
                # Never popped on re-pin — a superseding stream may
                # diverge, and re-invalidating a live prefix is a no-op.
                # Bounded in TOKENS (not just entries): the lists hold
                # full written histories, and hoarding gigabytes for a
                # delete that may never come inverts the trade — oldest
                # entries fall off first (their tree content is likely
                # LRU-evicted by then anyway).
                self._conv_evicted_tokens[conv_id] = streams[-4:]
                budget = 1_000_000
                total = sum(len(t) for ss in self._conv_evicted_tokens.values()
                            for t in ss)
                while (total > budget or
                       len(self._conv_evicted_tokens) > 4096):
                    oldest = next(iter(self._conv_evicted_tokens))
                    if oldest == conv_id and len(self._conv_evicted_tokens) == 1:
                        break
                    dropped = self._conv_evicted_tokens.pop(oldest)
                    total -= sum(len(t) for t in dropped)
        if kv is None and conv_id in self._conv_busy:
            # An active sequence owns the pages; don't re-cache at finish.
            self._conv_drop_pending.add(conv_id)

    def demote_conversation(self, conv_id: str) -> None:
        """Release a conversation's HBM pin THROUGH the tiering plane
        (any thread). Unlike :meth:`drop_conversation` this never
        invalidates — the token stream and payload survive as a plane
        entry. The disagg publish path and drain migration use this to
        turn a warm pin into something the exchange can serialize."""
        with self._mu:
            self._drop_conversation_locked(conv_id, invalidate=False)
        self._flush_tier_notes()

    def rehydrate_tiered_conversations(self) -> int:
        """Restart recovery (docs/disaggregation.md "Rehydration"):
        scan the store for spilled KV blobs this replica owns, re-adopt
        them as ready store-tier entries, and re-register their prefix
        handles at tier="store" — so a re-arrival after a process
        restart is a store-tier hit, not a recompute. Returns the
        number of conversations adopted."""
        if self._tiering is None:
            return 0
        adopted = self._tiering.rehydrate(owner=self.name)
        sm = self._state_manager
        if sm is not None:
            for cid, meta in adopted:
                try:
                    # record_prefix_handle never creates — after a
                    # restart the conversation must be faulted back in
                    # from the store first (same store the blob lives
                    # in, so a rehydratable blob implies a loadable
                    # conversation).
                    sm.get_or_create(cid)
                    sm.record_prefix_handle(cid, {
                        "length": int(meta.get("length") or 0),
                        "pages": int(meta.get("n_pages") or 0),
                        "updated_at": self._clock.now(),
                        "tier": "store"})
                except Exception:  # noqa: BLE001 — accounting only
                    log.exception("prefix-handle rehydrate failed "
                                  "for %s", cid)
        return len(adopted)

    def cached_conversations(self) -> List[str]:
        with self._mu:
            return list(self._conv_cache)

    # -- prefix-handle tier notes (docs/tiering.md) ---------------------------

    def _note_tier(self, conv_id: str, tier: str) -> None:
        """Queue a prefix-handle ``tier`` update. Deferred because the
        callers hold ``self._mu`` and the state manager's lock sits
        ABOVE the engine's in the ordering; engine-thread only, flushed
        by :meth:`_flush_tier_notes` right after the lock drops."""
        if self._state_manager is not None:
            self._pending_tier_notes.append((conv_id, tier))

    def _flush_tier_notes(self) -> None:
        if not self._pending_tier_notes:
            return
        notes, self._pending_tier_notes = self._pending_tier_notes, []
        sm = self._state_manager
        if sm is None:
            return
        for cid, tier in notes:
            try:
                sm.update_prefix_handle_tier(cid, tier)
            except Exception:  # noqa: BLE001 — bookkeeping, not a gate
                log.exception("prefix-handle tier update failed for %s",
                              cid)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        # A DEAD thread object (crashed loop) must not block a restart
        # — the supervisor's recovery path is start() after
        # recover_after_crash(); only a LIVE thread makes this a no-op.
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name=f"engine-{self.name}",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        lanes, self._fetch_lanes = self._fetch_lanes, {}
        for t, q in lanes.values():
            q.put(None)
        for t, q in lanes.values():
            t.join(timeout=10.0)
        # Completion executor last: fetch lanes can no longer enqueue
        # work, so a drain here sees every queued job. Recreated lazily
        # if the engine restarts.
        comp, self._completion = self._completion, None
        if comp is not None:
            comp.drain()
            comp.stop()
        # Tiering worker after the loop: no more demotions/promotions
        # can be dispatched; lazily re-created on engine restart.
        if self._tiering is not None:
            self._tiering.stop()
        # Executor-side worker teardown (the echo backend's simulated
        # device-queue thread); optional seam, lazily re-created if the
        # executor is driven again.
        close = getattr(self.executor, "close", None)
        if close is not None:
            try:
                close()
            except Exception:  # noqa: BLE001 — teardown must not raise
                log.exception("executor close failed for %s", self.name)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def healthy(self) -> bool:
        """Health probe for LoadBalancer ``local://`` endpoints: alive
        iff the engine loop is running (a stopped or crashed engine
        advances the LB state machine to UNHEALTHY → failover)."""
        return self.running

    def recover_after_crash(self) -> Dict:
        """Crash-recovery reset (engine/supervisor.py,
        docs/robustness.md): called ONLY with the loop thread dead.
        Every sequence the crashed loop owned — slot holders, pending,
        inbox — has its pages/slots/locks released and its handle
        finished with reason "error", which unblocks the worker thread
        parked in ``process_fn`` → it raises → the worker retry path
        requeues through the delayed queue + WAL (at-least-once, DLQ
        backstop). Handles that already FINISHED before the crash are
        left untouched — the completion-dedup half of the contract: a
        completed request is never also pushed through the retry path,
        so no final token is ever emitted twice.

        Returns counts for the supervisor's log/metrics. The engine is
        restart-ready afterwards (``start()`` brings up a fresh loop).
        """
        assert not self.running, "recover_after_crash needs a dead loop"
        # Every in-flight chunk's device output is unreachable (the
        # dead loop owned their reconciles); drop the snapshots — their
        # sequences are failed below and the retry re-prefills from
        # scratch. With the async pipeline this can be TWO (depth)
        # chunks, not one; the invariants are the same per chunk.
        self._inflight.clear()
        # Completion-dedup barrier: a finish the dead loop already
        # queued on the completion executor must LAND before the
        # handle.done checks below — otherwise a completed request
        # would also be re-failed into the retry path (duplicate).
        self._drain_completions()
        with self._mu:
            inbox, self._inbox = self._inbox, []
        pending = [s for (_, _, s) in self._pending]
        self._pending = []
        holders = [s for s in self._slots if s is not None]
        recovered = 0
        already_done = 0
        for seq in holders + pending + inbox:
            if seq.slot is not None:
                try:
                    self.executor.release_slot(seq.slot)
                except Exception:  # noqa: BLE001 — executor state may
                    pass           # be mid-crash; the reset must win
                self._slots[seq.slot] = None
                seq.slot = None
            seq.first_handle = None
            seq.mixed_pending = 0
            if seq.handle.done:
                # Finished before the crash: dedup — do NOT re-fail or
                # re-queue; the worker already owns the outcome.
                already_done += 1
                if seq.pages:
                    self.allocator.free(seq.pages)
                    seq.pages = []
                continue
            self._finish(seq, "error",
                         "engine crashed; request requeued by supervisor",
                         waste_reason="crash")
            recovered += 1
        self._wake.clear()
        log.warning(
            "engine %s crash recovery: %d request(s) failed over to the "
            "retry path, %d already finished (deduped)",
            self.name, recovered, already_done)
        return {"recovered": recovered, "already_done": already_done}

    def _loop(self) -> None:
        watch = self._watch.open()
        try:
            while not self._stop.is_set():
                try:
                    did_work = self.step()
                except Exception:  # noqa: BLE001
                    log.exception("engine step failed")
                    did_work = False
                # One beat an iteration. An iteration that only polled
                # is judged and kept out of the median, or an idle
                # engine would measure its poll interval.
                load = self._load()
                watch.beat(did_work, **load)
                if not did_work:
                    self._idle_poll(load)
        except BaseException:
            # A BaseException (injected chaos.EngineCrash, interpreter
            # teardown, a bug in the except path) kills this thread.
            # Log the death loudly — the supervisor
            # (engine/supervisor.py) detects it and owns recovery.
            log.exception("engine %s loop DIED — thread exiting; "
                          "supervisor recovery takes over", self.name)
            raise
        finally:
            self._close_wait()
            watch.close()

    def _load(self) -> Dict[str, int]:
        """What waits and what runs, as the loop's beats and the
        ``engine.wait`` span carry it: ``pending`` (arrived, not
        admitted), ``active`` rows, ``inflight`` chunks. Lock-free
        reads of three lengths and one walk over the rows."""
        return {"pending": len(self._pending) + len(self._inbox),
                "active": sum(1 for s in self._slots if s is not None),
                "inflight": len(self._inflight)}

    def _idle_poll(self, load: Dict[str, int]) -> None:
        """The loop asleep: one 5 ms poll of the wake event, as the
        watch's named wait ``idle``, under ONE ``engine.wait`` span
        for the whole idle stretch — it opens at the first step that
        did no work, with the load at that instant, and closes when a
        step has work again (``_close_wait``), so an idle second is
        one ring entry and one trace event, not two hundred. A capture
        that begins inside a stretch splits it once, so the rest of
        the stretch lands in the capture."""
        span = self._wait_span
        if span is not None and not span.annotated and capture_held():
            self._close_wait("capture")
            span = None
        if span is None:
            span = self._wait_span = self._prof.span("engine.wait", **load)
            span.__enter__()
        with self._watch.wait("idle"):
            self._woken = self._wake.wait(0.005)
        self._wake.clear()

    def _close_wait(self, woke: str = "") -> None:
        """End the idle stretch, if one is open: ``woke`` says what
        ended it — ``arrival`` (a submit set the wake event), ``ready``
        (the event was set with nothing in the inbox: the tiering
        plane's ``on_ready``), ``timeout`` (a poll ran out and the next
        step found work by itself), ``stop``, ``capture``."""
        span = self._wait_span
        if span is None:
            return
        self._wait_span = None
        if not woke:
            woke = ("stop" if self._stop.is_set() else
                    "timeout" if not self._woken else
                    "arrival" if self._inbox else "ready")
        span.note(woke=woke)
        span.__exit__(None, None, None)

    @property
    def _chunk_inflight(self) -> Optional[_InflightChunk]:
        """Newest in-flight chunk (None with the pipeline empty) — the
        pre-deque name, kept for tests/instrumentation that probe
        whether dispatched work is outstanding."""
        return self._inflight[-1] if self._inflight else None

    # -- core step -----------------------------------------------------------

    def step(self) -> bool:
        """One scheduling round. Returns True if any work happened.
        Single stepper at a time — either the engine thread or a
        test/bench driving it synchronously.

        Pipelined decode (async-capable executors): the oldest
        dispatched chunk is reconciled here — and first the pipeline is
        FILLED to ``async_pipeline.depth`` chunks dispatched from the
        newest chunk's device-carried end state *before* the oldest
        one's tokens are fetched, so the fetch's host↔device round-trip
        and the host's assembly overlap the in-flight chunks' compute
        and the device never idles between chunks. Two situations allow
        a fill (``_can_fill``):

        - nothing waits for the host (no arrival, nothing pending, no
          prompt slice to run): the next chunk is the decode rows again;
        - every row is taken — a FULL BATCH — and nobody waiting could
          displace a row: nothing the fetch would tell the host can
          seat a request sooner, so the next chunk is dispatched from
          the carry whatever it has to hold: decode rows, the prompt
          slices of seated sequences (a carried MIXED chunk), rows
          joining behind their final slice, with pages found by
          evicting cache that nobody holds.

        A free row with anyone waiting, a cancellation, or a waiter
        that may preempt stops the fill and drains the pipeline one
        chunk per step down to the reconcile-then-fresh-dispatch path,
        which rebuilds the batch from host state — so seating,
        preemption and shedding only ever act on reconciled
        bookkeeping."""
        # Chaos seam (docs/robustness.md): kind "error" is absorbed by
        # the loop's except (one lost round); kind "crash" is a
        # BaseException that sails past it and KILLS the engine thread
        # — the supervisor's restart path is the handler under test.
        chaos.fault("engine.step", engine=self.name)
        if not self._has_step_work():
            # Nothing submitted, pending, seated or in flight: the
            # round is housekeeping, and a step that did no work opens
            # no span.
            self._expire_pins()
            self._set_gauges()
            return False
        if self._wait_span is not None:
            self._close_wait()
        with self._prof.span("engine.step"):
            return self._step()

    def _has_step_work(self) -> bool:
        with self._mu:
            if self._inbox:
                return True
        if self._pending or self._inflight:
            return True
        return any(s is not None for s in self._slots)

    def _step(self) -> bool:
        """A round with work in it, under ``engine.step``. Each phase
        opens its span of the fixed vocabulary (docs/observability.md
        "The engine step") where it has something to do: ``ingest``,
        ``admit``, ``prefill_advance``, ``fill``, ``resolve``,
        ``reconcile`` (``fetch`` + ``commit``), ``assemble`` with
        ``dispatch`` inside."""
        self._ingest()
        self._expire_pins()
        # Everything BEFORE the reconcile overlaps the in-flight chunk's
        # device compute: admission + prefill dispatches only queue more
        # programs behind it (preemption and page-shedding — which WOULD
        # touch rows the chunk is still decoding — are deferred while
        # one is in flight; see _admit/_alloc_pages).
        admitted = self._admit()       # free slots only while in flight
        prefilled = self._advance_prefill()
        if self._inflight:
            # Carry BEFORE the blocking resolve: a just-admitted
            # sequence must still hold an UNRESOLVED first_handle at
            # the carry decision so it enters via the join plan
            # (device-side override). Resolving first would flip it to
            # prefilled-but-not-in-chunk → geometry_changed → no
            # carried dispatch → its tokens wait a whole extra reconcile
            # cycle (measured: realtime tail_ms p99 +190 ms when the
            # fetch-wait servicing made resolves early).
            #
            # Pipeline fill: keep dispatching from the newest chunk's
            # device-carried end state until ``depth`` chunks are in
            # flight (depth 2 = the classic double buffer and the
            # pre-pipeline scheduling: at most ONE carried dispatch
            # per step, since one chunk is always reconciled below).
            # The span opens once a fill may go ahead, and says how
            # many chunks went out and why it stopped; a fill refused
            # outright is counted in ``fill_refusals`` alone.
            if self._can_fill():
                with self._prof.span("engine.fill") as fill:
                    dispatched = 0
                    while True:
                        if self._dispatch_carried(
                                self._inflight[-1]) is None:
                            break
                        dispatched += 1
                        if not self._can_fill():
                            break
                    fill.note(dispatched=dispatched,
                              stopped=self._fill_stopped)
            # Resolve AFTER dispatch, BEFORE processing: join rows'
            # first tokens must commit before any of their chunk rows
            # do (the chunk being processed may contain join rows from
            # the previous cycle).
            self._resolve_prefills()
            # Reconcile the OLDEST chunk. It stays in the deque while
            # its fetch completes: the servicing admissions inside
            # _process_chunk consult ``self._inflight`` to defer
            # preemption/shedding, and its rows are still untouchable.
            infl = self._inflight[0]
            with self._prof.span("engine.reconcile"):
                self._process_chunk(infl)
            self._inflight.popleft()
            if not self._inflight:
                # Reconciled: re-run admission NOW, when preemption and
                # page-shedding are legal again (the pre-reconcile
                # _admit above skips them while rows are in flight —
                # without this second pass an urgent arrival could
                # never displace a decoding sequence, because each step
                # ends with a fresh chunk in flight). The extra prefill
                # pass runs ONLY when this admission actually seated
                # someone (its first bucket shouldn't wait a cycle);
                # unconditional, it would double the one-bucket-per-step
                # bound for every mid-prefill sequence.
                if self._admit():
                    self._advance_prefill()
                # Then assemble the next chunk fresh from the
                # just-reconciled state — fused with budgeted prefill
                # slices when mixed batching has both kinds of work.
                self._assemble()
            self._set_gauges()
            return True
        # No chunk in flight: DISPATCH before resolving — a final
        # prefill chunk dispatched this step still holds an unresolved
        # first_handle, so it joins this decode chunk device-to-device
        # (resolving first would block ~1 RTT and then decode without
        # the join). Sync executors never produce first_handles, so
        # the join-commit ordering (first token at resolve, rows at
        # the next reconcile) is preserved on every path.
        stepped = self._assemble()
        resolved = self._resolve_prefills()
        return resolved or admitted or prefilled or stepped

    def _can_fill(self) -> bool:
        """The pipeline has room and the host could do nothing better
        with the oldest chunk's tokens first: the next chunk may be
        dispatched from the newest one's device-carried end state.
        A refusal is counted under its reason."""
        why = self._fill_refusal()
        if why is None:
            return True
        self._refuse_fill(why)
        return False

    def _refuse_fill(self, why: str) -> None:
        """Count one stopped fill (``fill_refusals``) and keep the
        reason for the ``engine.fill`` span."""
        self.fill_refusals[why] += 1
        self._fill_stopped = why

    def _fill_refusal(self) -> Optional[str]:
        """Why the next chunk may NOT be dispatched from the carry
        (a key of ``fill_refusals``), or None when it may.

        With a row free the rule is the one the pipeline always had:
        anything that needs host-side scheduling (``_has_scheduling_
        work``) and any prompt slice left to run (``_mixed_work_
        waiting``: it must ride the next host-assembled MIXED chunk)
        stop the fill, so an arrival that can be seated is seated at
        the next reconcile, within one chunk.

        With every row taken (a FULL BATCH) waiting for the host helps
        no request unless somebody waiting may displace a row: the
        inbox must be ingested, and the head of the pending heap must
        not be able to preempt (preemption off, or it is no more
        urgent than the least urgent decoding row). Then the fill goes
        ahead with requests pending and slices to run — the carried
        chunk holds them (``_dispatch_carried``). Tenant fairness
        caps decode budgets on the host-assembled path only, so with
        several tenants seated a full batch keeps the old rule."""
        if len(self._inflight) >= self._pipe_depth:
            return "depth"
        free = False
        for s in self._slots:
            if s is None:
                free = True
            elif s.handle.cancelled:
                return "cancelled"
        if self._geometry_changed(self._inflight[-1]):
            return "geometry"
        if free:
            if self._has_scheduling_work() or self._mixed_work_waiting():
                return "free_slot"
            return None
        with self._mu:
            if self._inbox:
                return "urgent_pending"
        if self._head_may_preempt():
            return "urgent_pending"
        slices = self._mixed_work_waiting()
        if ((slices or self._pending) and self._tenancy.enabled
                and len({s.req.tenant_id for s in self._slots}) > 1):
            return "tenancy"
        return None

    def _head_may_preempt(self) -> bool:
        """The most urgent pending request could take a decoding
        sequence's row at a reconcile (``_admit_pending``'s test)."""
        if not self.preemption_enabled or not self._pending:
            return False
        prio, order, _ = self._pending[0]
        victim = self._least_urgent_active()
        return victim is not None and victim.sort_key() > (prio, order)

    def _assemble(self) -> bool:
        """Host assembly of the next chunk from reconciled state
        (``engine.assemble``: a fresh plan — eligibility,
        ``_budget_chunk_rows`` — then ``_emit_chunk``'s staging) and
        its dispatch (``engine.dispatch`` inside, the executor call
        alone). Nothing seated: no span."""
        if not any(s is not None for s in self._slots):
            self._set_gauges()
            return False
        with self._prof.span("engine.assemble"):
            plan = self._plan_mixed() if self._mixed_applicable() else None
            if plan is None:
                # No slice to run — or none left of a mixed plan (every
                # candidate shed or cancelled while its rows were
                # budgeted: rare), which is planned AGAIN as a decode
                # chunk, joining rows and all: the second budgeting pass
                # is idempotent (pages already ensured, need <= 0).
                plan = self._plan_decode()
            if plan is None:
                self._set_gauges()
                return False
            self._emit_chunk(*plan)
            return True

    def run_until_idle(self, max_steps: int = 100000) -> None:
        for _ in range(max_steps):
            did = self.step()
            if not did:
                with self._mu:
                    idle = (not self._inbox and not self._pending
                            and not self._inflight
                            and all(s is None for s in self._slots))
                if idle:
                    # Flush queued completion jobs so a caller checking
                    # handle.result right after idle sees every finish
                    # delivered (the async-pipeline offload otherwise
                    # races synchronous test/bench drivers).
                    self._drain_completions()
                    return
        raise RuntimeError("engine did not go idle")

    # -- internals -----------------------------------------------------------

    def _ingest(self) -> None:
        with self._mu:
            newly, self._inbox = self._inbox, []
        if not newly and not (self._pending and self.tier_max_wait):
            return              # no arrival, nobody to promote
        with self._prof.span("engine.ingest"):
            now = self._clock.now()
            for seq in newly:
                seq.arrival = now
                heapq.heappush(self._pending,
                               (seq.eff_prio, seq.order, seq))
            self._promote_overdue()

    def _promote_overdue(self) -> None:
        """SLA-aware tier promotion: a pending request that has waited
        past its tier's max_wait_time gains one tier per elapsed
        multiple (floor REALTIME), then the heap is rebuilt so admission
        — and preemption urgency — see the promoted priority. An
        overdue low request beats a fresh normal arrival."""
        if not self.tier_max_wait or not self._pending:
            return
        now = self._clock.now()
        changed = False
        for _, _, seq in self._pending:
            mw = self.tier_max_wait.get(seq.req.priority)
            if not mw or mw <= 0:
                continue
            promo = int((now - seq.arrival) / mw)
            eff = max(int(Priority.REALTIME), int(seq.req.priority) - promo)
            if eff != seq.eff_prio:
                seq.eff_prio = eff
                changed = True
        if changed:
            self._pending = [(s.eff_prio, o, s)
                             for (_, o, s) in self._pending]
            heapq.heapify(self._pending)

    def _slot_shard(self, slot: int) -> int:
        """dp universe of a batch row: the batch dim shards over dp in
        contiguous blocks (NamedSharding partitioning), so rows
        [d·B/dp, (d+1)·B/dp) — and their pages — belong to replica d."""
        if self.dp_shards <= 1:
            return 0
        return min(slot // self._rows_per_shard, self.dp_shards - 1)

    def _free_slot(self, prefer_shard: Optional[int] = None
                   ) -> Optional[int]:
        """First free slot; with ``prefer_shard`` (a sequence adopting
        KV pages that already live in one dp universe) a free slot in
        that universe wins so the adoption stays replica-local —
        falling back to any free slot (cross-universe reads are
        correct, just not communication-free)."""
        fallback = None
        for i, s in enumerate(self._slots):
            if s is None:
                if (prefer_shard is None or self.dp_shards <= 1
                        or self._slot_shard(i) == prefer_shard):
                    return i
                if fallback is None:
                    fallback = i
        return fallback

    def _least_urgent_active(
            self, exclude: Optional[_Sequence] = None, *,
            include_prefilling: bool = False) -> Optional[_Sequence]:
        """Least-urgent slot holder. Mid-prefill sequences are excluded
        from SLOT preemption (their partial prefill can't resume in
        place — slot-only preemption would replay chunks), but they ARE
        valid victims for page-RELEASE shedding (include_prefilling):
        release folds their un-run remainder into ``written_ids`` and
        restarts via the rebuild path, so a low-tier long prompt can
        never hold the pool against a realtime sequence (priority
        inversion)."""
        worst: Optional[_Sequence] = None
        for s in self._slots:
            if s is None or s is exclude:
                continue
            if not s.prefilled and not include_prefilling:
                continue
            if worst is None or s.sort_key() > worst.sort_key():
                worst = s
        return worst

    def _admit(self) -> bool:
        if not self._pending:
            return False
        with self._prof.span("engine.admit"):
            return self._admit_pending()

    def _admit_pending(self) -> bool:
        admitted = False
        #: Entries popped because their conversation's previous turn is
        #: still live — re-queued after the loop. SKIPPED, not a
        #: head-of-line break: the holder may itself be PENDING (it was
        #: preempted mid-turn) and sorted BEHIND this more urgent turn —
        #: breaking would deadlock the whole engine (found by the
        #: randomized soak: every slot idle, 35 requests pending,
        #: forever). Capacity stays RESERVED for the most urgent blocked
        #: turn: entries less urgent than it are deferred (old
        #: head-of-line semantics) — except the blocked conversations'
        #: own holders, which must seat to unblock their waiters.
        conv_blocked = []
        deferred = []
        blocked_floor = None
        blocked_holders = set()
        while self._pending:
            prio, order, seq = self._pending[0]
            if seq.handle.cancelled:
                heapq.heappop(self._pending)
                self._finish(seq, "cancelled")
                continue
            conv = seq.req.conversation_id
            if conv:
                holder = self._conv_busy.get(conv)
                if holder is not None and holder != seq.order:
                    # One live sequence per conversation (turn order):
                    # this turn waits — but only THIS turn.
                    heapq.heappop(self._pending)
                    conv_blocked.append((prio, order, seq))
                    if blocked_floor is None or (prio, order) < blocked_floor:
                        blocked_floor = (prio, order)
                    blocked_holders.add(holder)
                    continue
            if (blocked_floor is not None and (prio, order) > blocked_floor
                    and seq.order not in blocked_holders):
                # Less urgent than a blocked conversation turn: don't
                # seat it in front (unbounded inversion when preemption
                # is off) — but keep scanning, the blocked turn's
                # holder may be deeper in the heap.
                heapq.heappop(self._pending)
                deferred.append((prio, order, seq))
                continue
            prefer = None
            if self.dp_shards > 1:
                # Keep adoptions replica-local: a sequence resuming onto
                # pages it already holds, or adopting its conversation's
                # pinned KV, prefers a row in those pages' dp universe.
                if seq.pages:
                    prefer = self.allocator.shard_of(seq.pages[0])
                elif conv:
                    with self._mu:
                        kv = self._conv_cache.get(conv)
                        if kv is not None and kv.pages:
                            prefer = self.allocator.shard_of(kv.pages[0])
            slot = self._free_slot(prefer)
            if (slot is None and self.preemption_enabled
                    and not self._inflight):
                # No preemption while a chunk is in flight: the victim's
                # rows are still decoding on device and its host-side
                # position bookkeeping would go stale. The pending
                # request blocks the carried dispatch, so the next
                # reconcile clears the chunk and preemption runs one
                # cycle later.
                victim = self._least_urgent_active()
                if victim is not None and victim.sort_key() > (prio, order):
                    self._preempt(victim, release_pages=False)
                    slot = self._free_slot()
            if slot is None:
                break
            heapq.heappop(self._pending)
            if not self._start_sequence(seq, slot):
                # Could not get pages even after shedding: push back and
                # stop admitting this round.
                heapq.heappush(self._pending, (prio, order, seq))
                break
            admitted = True
        for entry in conv_blocked:
            heapq.heappush(self._pending, entry)
        for entry in deferred:
            heapq.heappush(self._pending, entry)
        return admitted

    def _preempt(self, victim: _Sequence, release_pages: bool) -> None:
        """Step-boundary preemption: the victim's slot is handed over; its
        KV pages stay resident (cheap resume) unless the pool itself is
        the contended resource, in which case it later resumes by
        re-prefilling its full written context (``written_ids`` — which
        includes any adopted conversation history)."""
        assert victim.slot is not None
        # A row's state goes with the row: no cheap resume for a family
        # that keeps one.
        release_pages = release_pages or self._row_state_bytes > 0
        self._slots[victim.slot] = None
        self.executor.release_slot(victim.slot)
        victim.slot = None
        self.preemptions["release" if release_pages else "slot"] += 1
        victim.handle.marks.setdefault("preempted", time.perf_counter())
        if release_pages:
            self._release_sequence_pages(victim)
        heapq.heappush(self._pending,
                       (victim.eff_prio, victim.order, victim))
        if self._metrics:
            self._m("preemptions", victim.req.priority.tier_name).inc()
        # Engine-thread logs carry the request identity via explicit
        # fields (the contextvar binding lives on worker/API threads).
        log.info("preempted %s (%s)%s", victim.req.id,
                 victim.req.priority.tier_name,
                 " releasing pages" if release_pages else "",
                 extra={"fields": {
                     "request_id": victim.req.id,
                     "conversation_id": victim.req.conversation_id}})

    def _release_sequence_pages(self, seq: _Sequence,
                                waste_reason: str = "preempt") -> None:
        """Take ``seq``'s KV pages back into the pool. The sequence will
        rebuild by re-prefilling ``written_ids`` when next admitted —
        device time that the usage plane bills as waste under
        ``waste_reason`` ("preempt" for a priority preemption, "shed"
        for pool-pressure reclaim of a pending sequence)."""
        if seq.usage is not None:
            if not seq.usage.waste_reason:
                seq.usage.waste_reason = waste_reason
            self._usage.tracker.update(seq.req.id, 0)
        if seq.prefix_match is not None:
            # The shed pages include radix-matched shared pages: drop
            # their in-flight node pins (the free below drops this
            # sequence's page refs; the tree's own refs keep shared KV
            # alive for everyone else). The rebuild re-matches.
            self._prefix_cache.unlock(seq.prefix_match)
            seq.prefix_match = None
        if seq.pages:
            self.allocator.free(seq.pages)
            seq.pages = []
        if seq.tails:
            self._drop_tails(seq)
        seq.block_table[:] = 0
        seq.pos = 0
        seq.cached_len = 0
        # An in-flight async prefill's sampled token refers to released
        # pages; the rebuild re-prefills and re-samples at the same
        # position.
        seq.first_handle = None
        if seq.todo_ids:
            # Mid-prefill victim: fold the un-run remainder into
            # written_ids so the rebuild re-prefills the COMPLETE
            # context (adopted history + chunks written + remainder).
            seq.written_ids = seq.written_ids + seq.todo_ids
            seq.todo_ids = []
        if seq.prefilled or seq.written_ids:
            seq.rebuild = True
        seq.prefilled = False

    # -- row tails (docs/prefix_cache.md "Tails") ------------------------------

    def _tail_room(self, written: int, end: int) -> bool:
        """Whether a row that has written ``written`` tokens — and may
        write a pipeline's worth of decode steps more before a copy
        dispatched now runs — still holds the tail before ``end``."""
        overrun = (int(getattr(self.executor, "chunk_size", 1))
                   * self._pipe_depth)
        return (self.spec.page_size <= end <= written
                and written - end + overrun
                <= self._row_tail["slack_tokens"])

    def _take_tail(self, seq: _Sequence, row: int, end: int) -> None:
        """Copy the tail before token ``end`` out of batch row ``row``
        (which holds ``seq``'s stream up to ``seq.pos``) into a slot
        that waits on ``seq`` for its radix node. Not where the tree
        has that node's tail already, nor without a slot to take."""
        if (not self._tail_room(seq.pos, end)
                or self._prefix_cache.has_tail(seq.written_ids, end)):
            return
        slot = self._prefix_cache.take_tail_slot()
        if slot is None:
            return
        self.executor.export_row_tail(row, end // self.spec.page_size, slot)
        seq.tails.append((end, slot))
        self.row_tail_counts["tails_taken"] += 1

    def _take_stride_tail(self, seq: _Sequence, before: int,
                          after: int) -> None:
        """``seq``'s prefill has been DISPATCHED from ``before`` to
        ``after``: take the tail of the last multiple of the family's
        stride it passed (a shared context ends inside a prompt, and
        its blocks are published only when the sequence ends)."""
        if self._row_tail is None or seq.slot is None:
            return
        stride = self._row_tail["stride"]
        end = after // stride * stride
        if end > before:
            self._take_tail(seq, seq.slot, end)

    def _take_due_tails(self) -> None:
        """The slices handed to the chunk that was just dispatched
        (``_take_slices``)."""
        due, self._tails_due = self._tails_due, []
        for seq, before, after in due:
            self._take_stride_tail(seq, before, after)

    def _publish_tails(self, seq: _Sequence, row: Optional[int]) -> None:
        """``seq``'s stream was just published (``insert``): take the
        tail at its page-aligned end out of ``row`` (the next turn's
        stream is this one plus what was typed) and hang every tail the
        sequence holds on its node."""
        if self._row_tail is None:
            return
        ps = self.spec.page_size
        end = min(len(seq.written_ids) // ps, len(seq.pages)) * ps
        if row is not None:
            self._take_tail(seq, row, end)
        for at, slot in seq.tails:
            self._prefix_cache.attach_tail(seq.written_ids, at, slot)
        seq.tails = []

    def _drop_tails(self, seq: _Sequence) -> None:
        for _, slot in seq.tails:
            self._prefix_cache.free_tail_slot(slot)
        seq.tails = []

    def _unmatch(self, seq: _Sequence) -> None:
        """Undo a radix match that could not complete admission: unlock
        the nodes, release this sequence's page refs and reset its
        position state so a retry recomputes (and re-matches) cleanly."""
        self._prefix_cache.unlock(seq.prefix_match)
        seq.prefix_match = None
        if seq.pages:
            self.allocator.free(seq.pages)
            seq.pages = []
        seq.block_table[:] = 0
        seq.pos = 0
        seq.cached_len = 0
        if seq.usage is not None:
            self._usage.tracker.update(seq.req.id, 0)

    def _reclaim_idle_conversation(self) -> bool:
        """LRU-evict one idle pinned conversation to relieve pool
        pressure. Returns True if pages were freed."""
        with self._mu:
            if not self._conv_cache:
                return False
            if (self._tiering is not None
                    and self._tiering.eviction_policy == "saved_rate"):
                # Demotion economics v2 (ROADMAP 4c): evict the pin
                # with the lowest measured saved-prefill rate — a
                # conversation whose KV keeps earning its HBM outlives
                # a cold one; recency breaks ties (and carries the
                # whole ranking when the ledger has no signal).
                rate = self._usage.conversation_saved_rate
                cid = min(self._conv_cache,
                          key=lambda c: (rate(c),
                                         self._conv_cache[c].last_used))
            else:
                cid = min(self._conv_cache,
                          key=lambda c: self._conv_cache[c].last_used)
            self._drop_conversation_locked(cid, invalidate=False)
        self._flush_tier_notes()
        log.info("evicted conversation KV %s under pool pressure", cid,
                 extra={"fields": {"conversation_id": cid}})
        return True

    def _reclaim_pending_pages(self, requester: _Sequence) -> bool:
        """Release pages held by a *pending* sequence (slot-preempted
        earlier, pages kept for cheap resume) that is strictly less
        urgent than ``requester``. Without this, pages parked in the
        pending heap are invisible to shedding and admission can
        deadlock with the pool exhausted and every slot empty."""
        worst: Optional[_Sequence] = None
        for _, _, seq in self._pending:
            if seq is requester or not seq.pages:
                continue
            if worst is None or seq.sort_key() > worst.sort_key():
                worst = seq
        if worst is None or worst.sort_key() <= requester.sort_key():
            return False
        worst.handle.marks.setdefault("preempted", time.perf_counter())
        self._release_sequence_pages(worst, waste_reason="shed")
        log.info("reclaimed pages of pending %s for %s",
                 worst.req.id, requester.req.id,
                 extra={"fields": {"request_id": requester.req.id,
                                   "victim_id": worst.req.id}})
        return True

    def _alloc_pages(self, n: int, requester: _Sequence,
                     shard: Optional[int] = None) -> Optional[List[int]]:
        """Allocate with shedding, in increasing order of damage: idle
        pinned conversation KV (LRU) first, then pages parked with
        less-urgent *pending* sequences, then preempt-with-release of a
        strictly less-urgent runner. A victim is only ever less urgent
        than ``requester`` — a low-tier request can never strip a
        realtime sequence's KV (priority inversion).

        ``shard`` pins the allocation to the requester's slot's dp page
        universe (mesh path). A full universe falls back to any
        universe with room BEFORE any shedding runs — bounded
        non-locality is strictly cheaper than destroying cached KV or
        preempting a runner while another replica's universe sits
        idle (and it also avoids the admission deadlock where the
        pinned universe is held entirely by more-urgent work)."""
        try:
            # Chaos seam: a simulated HBM allocation failure behaves
            # exactly like pool exhaustion — the requester stays
            # pending and retries next round (never lost, never
            # half-admitted).
            chaos.fault("engine.hbm_alloc", engine=self.name)
        except chaos.ChaosFault:
            return None
        while True:
            pages = self.allocator.alloc(n, shard=shard)
            if pages is not None:
                return pages
            if shard is not None:
                pages = self.allocator.alloc(n)
                if pages is not None:
                    return pages
            # Shed deficit vs the FULLEST universe: every universe is
            # now short (the fallback above failed), and an eviction
            # only helps once SOME universe can hold all n pages
            # (dp=1: exactly the old n - available()).
            deficit = n - max(self.allocator.available_by_shard())
            if self._prefix_cache is not None and self._prefix_cache.evict_pages(
                    deficit) > 0:
                # Cheapest shed first: zero-ref radix leaves cost no
                # recompute for any RUNNING sequence (in-flight matches
                # are lock-pinned and skipped; a future turn merely
                # re-prefills what it would have reused).
                continue
            if self._reclaim_idle_conversation():
                continue
            if self._reclaim_pending_pages(requester):
                continue
            if self._inflight:
                # Page-shedding a decoding row would free pages the
                # in-flight chunk is still writing; defer to the next
                # reconcile (the unadmitted request blocks the carried
                # dispatch).
                return None
            victim = self._least_urgent_active(exclude=requester,
                                               include_prefilling=True)
            if (victim is not None and self.preemption_enabled
                    and victim.sort_key() > requester.sort_key()):
                self._preempt(victim, release_pages=True)
                continue
            return None

    def _try_promote(self, seq: _Sequence, conv: str,
                     shard: Optional[int] = None) -> str:
        """Tiered-KV promotion at re-arrival (docs/tiering.md): pull
        ``conv``'s demoted entry back into the device pool so the
        ordinary adoption path below runs unchanged against a
        rehydrated ``_ConvKV``. Returns:

        - ``"none"`` — the plane holds nothing for this conversation;
        - ``"wait"`` — an extract/store-load (or a transiently
          contended pool) is still in flight: the sequence stays
          pending and decode keeps running — promote latency hides
          behind admission;
        - ``"done"`` — promoted (host/store hit) OR degraded to the
          recompute fallback: ``seq.carry`` then holds the exact
          remembered token stream, so the re-prefill is token-for-token
          what the cached KV held (no reliance on ``history_text``).
        """
        plane = self._tiering
        cp = self._cp.enabled
        t_claim = time.perf_counter() if cp else 0.0
        status, entry = plane.claim(conv)
        if status != "ready":
            if cp and status == "wait":
                # Private mark (never emitted as a stage itself): the
                # FIRST admission attempt that had to wait opens the
                # promote/claim span; _stamp_promote renames it once
                # the serving entry reveals whether this was a local
                # tier promote or a disagg exchange claim.
                seq.handle.marks.setdefault("_promote_wait", t_claim)
            return status
        t0 = time.perf_counter()
        restorable = (entry.length > 0
                      and (entry.payload is not None
                           or (plane.content_free
                               and entry.tier == "host")))
        if restorable and self._row_state_bytes:
            # Pages without the rows' state: the remembered stream is
            # recomputed instead (the fallback below).
            restorable = False
            self.row_state_declined[
                "disagg" if getattr(entry, "from_exchange", False)
                else "tiering"] += 1
        pages: Optional[List[int]] = None
        if restorable:
            need = PageAllocator.pages_for(entry.length,
                                           self.spec.page_size)
            pages = self._alloc_pages(need, seq, shard)
            if pages is None:
                if self._inflight:
                    # Transient: shedding is deferred while chunks are
                    # in flight — put the entry back and retry at the
                    # next reconcile instead of degrading to recompute.
                    plane.restash(conv, entry)
                    return "wait"
                restorable = False
        if restorable and entry.payload is not None:
            leaves = plane.unpack(entry)
            try:
                self.executor.import_kv_pages(pages, leaves)
            except Exception:  # noqa: BLE001 — degrade, never corrupt
                log.exception("kv inject failed for %s; recomputing",
                              conv)
                self.allocator.free(pages)
                pages = None
                restorable = False
        if restorable:
            assert pages is not None
            bt = np.zeros(self.spec.max_pages_per_seq, np.int32)
            bt[:len(pages)] = pages
            rec = _ConvKV(pages=list(pages), block_table=bt,
                          length=entry.length,
                          last_used=self._clock.now(),
                          tokens=list(entry.tokens),
                          pending=entry.pending)
            with self._mu:
                self._conv_cache[conv] = rec
            self.allocator.pin(conv, pages)
            plane.note_promoted(entry, entry.source_tier,
                                (time.perf_counter() - t0) * 1e3)
            plane.release(entry)
            seq.served_tier = entry.source_tier
            if cp:
                self._stamp_promote(seq, entry, t_claim)
            self._note_tier(conv, "hbm")
            self._flush_tier_notes()
            return "done"
        # Recompute fallback: the remembered stream re-enters through
        # ``carry`` (the continuation-prefill path), and the prompt is
        # encoded WITHOUT the history_text fallback — the carry IS the
        # history, exact to the token.
        plane.release(entry)
        seq.carry = list(entry.tokens) + (
            [entry.pending] if entry.pending is not None else [])
        if not seq.prompt_ids:
            text = seq.req.prompt
            if not seq.carry and seq.req.history_text:
                # An entry with NO remembered stream (an exchange-claim
                # placeholder that degraded before its fetch landed)
                # must not drop the conversation history — fall back to
                # the ordinary history-text re-prefill instead.
                text = seq.req.history_text + seq.req.prompt
            seq.prompt_ids = (self.tokenizer.encode(text)
                              or [self.tokenizer.bos_id])
        plane.note_promoted(entry, "recompute",
                            (time.perf_counter() - t0) * 1e3)
        seq.served_tier = "recompute"
        if cp:
            self._stamp_promote(seq, entry, t_claim)
        self._note_tier(conv, "dropped")
        self._flush_tier_notes()
        return "done"

    @staticmethod
    def _stamp_promote(seq: _Sequence, entry, t_claim: float) -> None:
        """Close the tiering-wait span on the handle marks: named
        ``handoff_claim`` when the entry materialized from the disagg
        exchange (a cross-replica prefill→decode handoff), else
        ``kv_promote`` (local tier hierarchy / recompute fallback).
        The span opens at the first waiting admission attempt
        (``_promote_wait``) or this claim call, whichever came first."""
        marks = seq.handle.marks
        name = ("handoff_claim"
                if getattr(entry, "from_exchange", False)
                else "kv_promote")
        marks.setdefault(f"{name}_start",
                         marks.pop("_promote_wait", t_claim))
        marks.setdefault(f"{name}_done", time.perf_counter())
        # Store fault domain attribution (docs/robustness.md): how much
        # of the promote/claim wait was the conversation store itself
        # (load / exchange fetch). Underscore key: never an event of
        # its own — _record_trace attaches it as meta on the span-close
        # event so the critical-path plane can subtract store waits.
        store_ms = float(getattr(entry, "store_ms", 0.0) or 0.0)
        if store_ms > 0.0:
            marks["_store_wait_ms"] = (
                marks.get("_store_wait_ms", 0.0) + store_ms)

    def _start_sequence(self, seq: _Sequence, slot: int) -> bool:
        """Admit ``seq`` into ``slot``. Returns False only when pages are
        unavailable (seq stays pending). May finish the sequence
        immediately (EOS on prefill / capacity error)."""
        req = seq.req
        conv = req.conversation_id
        if not seq.prefilled:
            # Adopt the conversation's cached KV exactly once (single
            # ownership: the cache entry moves into this sequence).
            if conv and not seq.adopted:
                promoted = False
                if self._tiering is not None:
                    with self._mu:
                        resident = conv in self._conv_cache
                    if not resident:
                        status = self._try_promote(
                            seq, conv, self._slot_shard(slot))
                        if status == "wait":
                            return False
                        promoted = status == "done"
                with self._mu:
                    kv = self._conv_cache.pop(conv, None)
                    if kv is not None:
                        self.allocator.unpin(conv)
                    self._conv_busy[conv] = seq.order
                seq.adopted = True
                if kv is not None and self._usage.enabled:
                    # The pin's page-second meter ends here; the pages
                    # continue on THIS sequence's meter below.
                    self._usage.unpin_kv(conv)
                if kv is not None and self._row_state_bytes:
                    # The pin holds pages and no row state: give the
                    # pages back and prefill the remembered stream.
                    self.allocator.free(kv.pages)
                    seq.carry = list(kv.tokens) + (
                        [kv.pending] if kv.pending is not None else [])
                    self.row_state_declined["conversation"] += 1
                    kv = None
                if kv is not None and self._tiering is not None \
                        and not promoted:
                    # Pin still resident — the hierarchy's top tier.
                    self._tiering.note_hit("hbm")
                    seq.served_tier = "hbm"
                if kv is not None:
                    seq.cached_len = kv.length
                    seq.pos = kv.length
                    seq.block_table[:] = kv.block_table
                    seq.pages = list(kv.pages)
                    seq.written_ids = list(kv.tokens)
                    if kv.pending is not None:
                        seq.carry = [kv.pending]
            if not seq.prompt_ids:
                text = req.prompt
                # (a declined pin put the remembered stream into
                # ``carry``: the carry IS the history, exact to the
                # token, as on the recompute path — ``history_text``
                # beside it would prefill the history twice)
                if (seq.cached_len == 0 and req.history_text
                        and not seq.carry):
                    text = req.history_text + req.prompt
                ids = self.tokenizer.encode(text)
                seq.prompt_ids = ids or [self.tokenizer.bos_id]

            resume_last: Optional[int] = None
            if seq.rebuild and self._row_state_bytes:
                self.row_state_rebuilds += 1
            if seq.rebuild:
                # Pages were reclaimed mid-flight: re-prefill the exact
                # written context (adopted history + prompt + generated
                # so far), then resume decoding from the newest token.
                ids = list(seq.written_ids)
                start_pos = 0
                if seq.generated:
                    resume_last = seq.generated[-1]
                elif seq.carry:
                    # Never produced a token: the carry tail re-enters
                    # through ids; nothing to resume.
                    pass
            else:
                start_pos = seq.cached_len
                # KV to (re)build: prompt plus all previously sampled
                # tokens except the newest (whose KV is written by its
                # decode step).
                ids = seq.carry + seq.prompt_ids
                if seq.generated:
                    ids = ids + seq.generated[:-1]
                    resume_last = seq.generated[-1]

            capacity = self.spec.max_pages_per_seq * self.spec.page_size
            if start_pos + len(ids) + 1 > capacity and start_pos > 0:
                # The cached prefix + new tokens exceed the block table.
                # Fold the prefix into a from-scratch rebuild so the
                # window can slide. The fold moves the history tokens
                # into ``carry`` (not just this attempt's local ``ids``):
                # if the page allocation below fails and the sequence
                # retries admission later, the retry recomputes the SAME
                # folded stream — otherwise the adopted history would be
                # silently dropped.
                seq.carry = seq.written_ids + seq.carry
                seq.written_ids = []
                ids = seq.carry + seq.prompt_ids
                if seq.generated:
                    ids = ids + seq.generated[:-1]
                if seq.pages:
                    self.allocator.free(seq.pages)
                    seq.pages = []
                seq.block_table[:] = 0
                start_pos = 0
                seq.pos = 0
                seq.cached_len = 0
            if len(ids) + 1 > capacity:
                keep = capacity - max(
                    1, min(self.max_decode_steps, capacity // 4))
                if keep < 1:
                    self._finish(seq, "error",
                                 "prompt exceeds KV capacity")
                    return True
                ids = ids[-keep:]
            # Radix prefix reuse: a from-scratch prefill (first turn of a
            # conversation, a conversation whose pinned KV was reclaimed,
            # a rebuild, or any request sharing a system prompt) adopts
            # the longest cached page-aligned prefix instead of
            # re-prefilling it. The partial-block tail and at least the
            # final token stay in ``ids`` and are prefilled normally —
            # the continuation-prefill path the conversation cache
            # already exercises. Matched pages are shared (ref-counted);
            # this sequence's writes start at ``start_pos`` and land in
            # its own fresh blocks, never in a shared page (COW by block).
            match_seed: Optional[List[int]] = None
            if (self._prefix_cache is not None and start_pos == 0
                    and not seq.pages and len(ids) > 1):
                tailed = self._row_tail is not None
                m = self._prefix_cache.match(ids, need_tail=tailed)
                if tailed:
                    self.row_tail_counts["matched_tokens"] += (
                        m.length + m.cut_tokens)
                    self.row_tail_counts["match_cut_tokens"] += m.cut_tokens
                if m.nodes and self._row_state_bytes and not tailed:
                    # Pages without what rebuilds the row's state: the
                    # match is given back whole (its node pins and its
                    # page references) and counted.
                    self._prefix_cache.unlock(m)
                    self.allocator.free(m.pages)
                    self.row_state_declined["prefix"] += 1
                elif m.nodes:
                    n_m = len(m.pages)
                    seq.pages = list(m.pages)
                    seq.block_table[:n_m] = m.pages
                    seq.prefix_match = m
                    seq.pos = m.length
                    seq.cached_len = m.length
                    match_seed = ids[:m.length]
                    ids = ids[m.length:]
                    start_pos = m.length
            have = len(seq.pages)
            need = PageAllocator.pages_for(
                start_pos + len(ids) + 1, self.spec.page_size) - have
            if need > self.allocator.total:
                self._finish(seq, "error",
                             f"request needs {need} pages; pool has "
                             f"{self.allocator.total}")
                return True
            if need > 0:
                pages = self._alloc_pages(need, seq,
                                          self._slot_shard(slot))
                if pages is None:
                    if match_seed is not None:
                        # Give the matched pages back (a retried
                        # admission recomputes ids from scratch, so
                        # holding a partial match here would replay the
                        # matched tokens at shifted positions).
                        self._unmatch(seq)
                    elif seq.pages:
                        # Still pending WITH pages (adopted KV kept for
                        # the retry): meter them while it waits.
                        self._usage_pages(seq)
                    return False
                seq.block_table[have:have + need] = pages
                seq.pages.extend(pages)
            if match_seed is not None and self._row_tail is not None:
                # The admission stands: the matched node's tail goes
                # into this row's state at the match's end, and the
                # prefill dispatched after it continues from there.
                self.executor.import_row_tail(
                    seq.prefix_match.tail, slot,
                    seq.prefix_match.length // self.spec.page_size)
                self.row_tail_counts["adopted"] += 1

            # Incremental prefill: the sequence takes its slot NOW but
            # runs at most one prefill bucket per engine step
            # (_advance_prefill), so a long prompt can't stall every
            # decoding sequence for its whole duration — the classic
            # continuous-batching prefill stall, bounded here to one
            # bucket per step.
            seq.todo_ids = ids
            seq.todo_pos = start_pos
            seq.todo_rebuild = seq.rebuild
            seq.todo_resume = resume_last
            seq.rebuild = False
            if seq.todo_rebuild or start_pos == 0 or match_seed is not None:
                # written_ids must mirror [0, pos): seed it with the
                # matched prefix (empty when starting truly from
                # scratch); prefill chunks append the rest.
                seq.written_ids = list(match_seed or [])
            if not (seq.todo_rebuild and seq.generated):
                seq.prefill_ids = ids
                seq.prefill_start = start_pos
            if self._prefix_cache is not None and not seq.reuse_counted:
                seq.reuse_counted = True
                if seq.cached_len > 0:
                    self.prefix_hits += 1
                    self.cached_prefill_tokens_total += seq.cached_len
                else:
                    self.prefix_misses += 1
                if self._metrics:
                    self._m("prefix_cache_hits" if seq.cached_len > 0
                            else "prefix_cache_misses").inc()
                    if seq.cached_len > 0:
                        self._m("cached_prefill_tokens").inc(
                            seq.cached_len)
            seq.slot = slot
            self._slots[slot] = seq        # slot held; prefilled=False
            seq.handle.marks.setdefault("admitted", time.perf_counter())
            self._usage_pages(seq)
            return True
        # Resuming a slot-only preemption: KV intact, just take the slot
        # (per-slot-state executors re-register their context).
        self.executor.resume(slot, seq.prefill_ids, seq.prefill_start)
        seq.slot = slot
        self._slots[slot] = seq
        seq.handle.marks.setdefault("admitted", time.perf_counter())
        return True

    def _advance_prefill(self) -> bool:
        """Run ONE prefill bucket for the most urgent mid-prefill
        sequence; completes its admission when the last chunk lands.
        Returns True if any prefill work ran.

        With an async-capable executor the bucket program is DISPATCHED
        without a host sync; the final chunk's sampled token is fetched
        by ``_resolve_prefills`` on a later step, so the host↔device
        round-trip overlaps other scheduling/decode work instead of
        serializing admission.
        """
        cands = [s for s in self._slots
                 if s is not None and not s.prefilled
                 and s.first_handle is None and not s.mixed_pending]
        # Reap EVERY cancelled candidate — a cancelled low-tier prompt
        # must not hold its slot and pages just because more urgent
        # prefill work keeps winning the head-of-line pick.
        reaped = False
        for s in list(cands):
            if s.handle.cancelled:
                self._finish_active(s, "cancelled")
                cands.remove(s)
                reaped = True
        if not cands:
            return reaped
        decode_active = any(s is not None and s.prefilled
                            for s in self._slots)
        if self._mixed_on() and decode_active:
            # Mixed mode owns prefill while decode rows are hot: the
            # next mixed iteration runs these sequences' slices INSIDE
            # the decode program (budget-bounded) instead of dedicated
            # bucket programs that would stall it for the whole bucket.
            return reaped
        with self._prof.span("engine.prefill_advance"):
            self._dispatch_prefill_buckets(cands, decode_active)
        return True

    def _dispatch_prefill_buckets(self, cands, decode_active: bool) -> None:
        """``_advance_prefill``'s dispatch half: one bucket-chunk for
        each of ``cands`` (the single most urgent one on a sync
        executor), each program launch an ``engine.dispatch``."""
        buckets = getattr(self.executor, "prefill_buckets", None)
        t_dispatch0 = time.perf_counter()
        prefill_async = getattr(self.executor, "prefill_async", None)
        # Async executors: dispatch ONE bucket for EVERY waiting
        # sequence this step (the programs just queue on the device —
        # no host syncs between them), so an admission wave onboards in
        # one cycle instead of one-sequence-per-step. Sync executors
        # keep the single most-urgent pick.
        cands.sort(key=lambda s: s.sort_key())
        prefill_multi = getattr(self.executor, "prefill_multi_async",
                                None)
        npf = getattr(self.executor, "prefill_batch", 1)
        use_multi = (prefill_multi is not None and npf > 1
                     and len(cands) > 1)
        if prefill_async is None and not use_multi:
            cands = cands[:1]               # sync executor: one per step

        # Pop one bucket-chunk per candidate (shared by every dispatch
        # path — the accounting below must stay identical between them).
        work = []
        for seq in cands:
            seq.handle.marks.setdefault("prefill_start",
                                        time.perf_counter())
            chunk_len = buckets[-1] if buckets else len(seq.todo_ids)
            chunk = seq.todo_ids[:chunk_len]
            seq.todo_ids = seq.todo_ids[chunk_len:]
            if not seq.todo_ids:
                seq.handle.marks.setdefault("prefill_last_dispatched",
                                            time.perf_counter())
            work.append((seq, chunk))

        handles: List = [None] * len(work)

        def row_of(seq) -> tuple:
            # the batch row whose state the chunk continues: an operand
            # only of a family that keeps one
            return (seq.slot,) if self._row_state_bytes else ()

        if use_multi:
            # Batched admission waves: npf prompts' chunks per program
            # (weights stream once per wave); ALL waves dispatch this
            # step — the programs just queue on the device. A trailing
            # singleton uses the cheaper single-prefill program instead
            # of an NPF-row padded batch.
            for i0 in range(0, len(work), npf):
                grp = work[i0:i0 + npf]
                if len(grp) == 1 and prefill_async is not None:
                    seq, chunk = grp[0]
                    with self._prefill_dispatch("prefill", [chunk]):
                        handles[i0] = prefill_async(
                            chunk, seq.todo_pos, seq.block_table,
                            seq.req.temperature, *row_of(seq))
                    continue
                with self._prefill_dispatch("prefill_multi",
                                            [c for _, c in grp]):
                    hs = prefill_multi(
                        [(chunk, seq.todo_pos, seq.block_table,
                          seq.req.temperature) + row_of(seq)
                         for seq, chunk in grp])
                handles[i0:i0 + len(grp)] = hs
        elif prefill_async is not None:
            for i, (seq, chunk) in enumerate(work):
                with self._prefill_dispatch("prefill", [chunk]):
                    handles[i] = prefill_async(chunk, seq.todo_pos,
                                               seq.block_table,
                                               seq.req.temperature,
                                               *row_of(seq))
        else:
            seq, chunk = work[0]
            with self._prefill_dispatch("prefill", [chunk]):
                first = self.executor.prefill(chunk, seq.todo_pos,
                                              seq.block_table,
                                              seq.req.temperature,
                                              seq.slot)

        dispatched = sum(len(c) for _, c in work)
        self._note_prefill_dispatch(
            dispatched, time.perf_counter() - t_dispatch0,
            decode_active=decode_active, fused=False)
        for (seq, chunk), handle in zip(work, handles):
            seq.todo_pos += len(chunk)
            seq.pos = seq.todo_pos
            seq.pf_tokens_run += len(chunk)
            seq.written_ids.extend(chunk)
            self._take_stride_tail(seq, seq.todo_pos - len(chunk),
                                   seq.todo_pos)
            if seq.todo_ids:
                continue                    # more buckets next step
            if handle is not None:
                seq.first_handle = handle   # fetched next step
                _prefetch(handle)
            else:
                self._complete_prefill(seq, first)
                self._flush_emits(seq)

    def _resolve_prefills(self) -> bool:
        """Fetch the first tokens of async prefills dispatched on earlier
        steps and complete those admissions. All pending handles are
        fetched in ONE host transfer (device-side stack) — an admission
        wave pays one round-trip, not one per sequence."""
        pending = [s for s in self._slots
                   if s is not None and s.first_handle is not None]
        if not pending:
            return False
        gather = getattr(self.executor, "gather_scalars", None)
        handles = [s.first_handle for s in pending]
        if gather is not None and len(pending) > 1:
            fetch = lambda: gather(handles)              # noqa: E731
        else:
            fetch = lambda: [int(np.asarray(h)) for h in handles]  # noqa: E731
        with self._prof.span("engine.resolve"):
            # Offload the blocking transfer so arrivals keep being
            # admitted during the wait (same pattern as chunk fetches
            # — without this, resolve waits of ~chunk+RTT showed up as
            # 170-240 ms realtime queue_ms tails).
            box = self._offload_fetch(fetch, lane="resolve")
            self._service_while(box["ev"], "resolve")
            if box["err"] is not None:
                raise box["err"]
            vals = box["out"]
            for seq, first, h in zip(pending, vals, handles):
                if seq.first_handle is not h or seq.slot is None:
                    # Shed, cancelled, or re-admitted during the
                    # servicing wait (page-release preemption nulls
                    # first_handle and requeues the sequence): the
                    # fetched sample belongs to a prefill whose pages
                    # are gone — drop it; the rebuild path re-prefills
                    # and re-samples at the same position.
                    continue
                seq.first_handle = None
                self._complete_prefill(seq, int(first))
                self._flush_emits(seq)   # first token: no chunk's wait
        return True

    def _note_prefill_dispatch(self, tokens: int, host_seconds: float,
                               *, decode_active: bool,
                               fused: bool) -> None:
        """Account one round of prefill dispatches as decode-stall when
        decode rows were active. The stall is the LARGER of the
        measured host time (sync executors block right here) and the
        learned device-time estimate (async dispatches return in µs
        while the program still serializes with — or, fused, rides
        inside — the decode chunk). Mixed iterations bound ``tokens``
        by the budget; that bound is exactly what this histogram makes
        visible."""
        if tokens <= 0:
            return
        est_ms = host_seconds * 1e3
        if self.prefill_tps_ewma and self.prefill_tps_ewma > 0:
            est_ms = max(est_ms,
                         tokens / self.prefill_tps_ewma * 1e3)
        if not decode_active:
            return
        self.prefill_stall_events += 1
        self.prefill_stall_ms_total += est_ms
        if self._metrics:
            self._m("prefill_stall_ms",
                    "mixed" if fused else "program").observe(est_ms)

    def _observe_prefill_rate(self, seq: _Sequence) -> None:
        """Feed the learned prefill-rate EWMA (and the registered
        scheduler hook) from a completed admission's measured
        prefill_start → prefill_done span."""
        marks = seq.handle.marks
        t0 = marks.get("prefill_start")
        t1 = marks.get("prefill_done")
        tokens = seq.pf_tokens_run
        if t0 is None or t1 is None or t1 <= t0 or tokens <= 0:
            return
        dt = t1 - t0
        rate = tokens / dt
        if self.prefill_tps_ewma is None:
            self.prefill_tps_ewma = rate
        else:
            self.prefill_tps_ewma = (0.8 * self.prefill_tps_ewma
                                     + 0.2 * rate)
        if self.on_prefill_observed is not None:
            try:
                self.on_prefill_observed(tokens, dt)
            except Exception:  # noqa: BLE001 — accounting, not a gate
                log.exception("on_prefill_observed hook failed")

    def _complete_prefill(self, seq: _Sequence, first: int) -> None:
        """Admission-completion after the final prefill chunk."""
        if seq.todo_rebuild and seq.generated:
            # KV is rebuilt, but per-slot-state executors (the echo
            # mock) must see the ORIGINAL prefill stream, not the
            # history+output mix we just replayed.
            self.executor.resume(seq.slot, seq.prefill_ids,
                                 seq.prefill_start)
        seq.prefilled = True
        seq.handle.marks.setdefault("prefill_done", time.perf_counter())
        self._observe_prefill_rate(seq)
        if seq.todo_resume is not None:
            seq.last_token = seq.todo_resume
            return
        self._commit_token(seq, first)   # EOS / append / metrics / limit

    def _budget_for(self, seq: _Sequence, chunk: int) -> int:
        """Token budget for ``seq`` this chunk: bounded by the remaining
        max_new_tokens allowance and the block-table capacity."""
        limit = seq.req.max_new_tokens or self.max_decode_steps
        remaining = max(1, limit - len(seq.generated))
        capacity = self.spec.max_pages_per_seq * self.spec.page_size
        headroom = capacity - seq.pos
        return max(1, min(chunk, remaining, headroom))

    def _ensure_decode_pages(self, seq: _Sequence, budget: int) -> bool:
        """The next ``budget`` decode steps write KV at positions
        ``[seq.pos, seq.pos+budget)`` — make sure pages back them."""
        need = PageAllocator.pages_for(
            seq.pos + budget, self.spec.page_size) - len(seq.pages)
        if need <= 0:
            return True
        pages = self._alloc_pages(
            need, seq,
            None if seq.slot is None else self._slot_shard(seq.slot))
        if pages is None:
            return False
        seq.block_table[len(seq.pages):len(seq.pages) + need] = pages
        seq.pages.extend(pages)
        self._usage_pages(seq)
        return True

    def _admission_cap(self) -> int:
        """Adaptive decode granularity: the chunk budget
        IS the admission latency — an urgent request waiting on pages or
        its conversation's running turn must not wait out a full 64-step
        chunk. The cap only binds for urgent waiters: aggressive caps
        under saturation collapse throughput (every chunk pays a fixed
        dispatch+fetch cost). The while-loop chunk program exits early
        at the budget — no recompilation, one program.

        Tier- and model-aware: a REALTIME waiter's
        cap is its latency target divided by the MEASURED per-step ms
        (executor.step_ms, from warmup) — ~4 steps on 8B (14 ms/step),
        ~14 on 1B — instead of a flat 16 that costs 8B realtime
        arrivals ~230 ms of admission delay before prefill starts."""
        if not self._pending or self._pending[0][0] > int(Priority.HIGH):
            # No urgent waiter → full chunks. (An occupancy-based
            # "latency mode" with half-size chunks was tried and
            # REVERTED: on high-RTT runtimes the pipelined chunk
            # cadence is (RTT + compute)/2, so doubling the chunk
            # count cost more tail latency at 5 req/s than the halved
            # admission wait saved — p99 553→767 ms measured.)
            return 1 << 30
        if self._pending[0][0] > int(Priority.REALTIME):
            return 16
        step_ms = getattr(self.executor, "step_ms", None) or 4.0
        cap = max(2, min(16, int(self.realtime_admission_ms / step_ms)))
        if self._prefix_cache is not None:
            # Cache-aware sizing: when the realtime waiter's context is
            # expected mostly CACHED, its first token follows admission
            # almost immediately (the prefill is just the tail), so the
            # admission wait IS its TTFT — halve the chunk cap to admit
            # it sooner. A waiter facing a big uncached prefill keeps
            # the standard cap: tighter chunks would tax the whole
            # batch without moving its prefill-dominated TTFT.
            head = self._pending[0][2]
            # Estimate the waiter's prompt TOKENS from its text length
            # (prefill_estimate's contract) — tokenization hasn't
            # happened yet and must not on this hot path.
            cpt = getattr(self.tokenizer, "chars_per_token", 1.0) or 1.0
            est_tokens = max(1, int(len(head.req.prompt) / cpt))
            cached, new = self.prefill_estimate(
                head.req.conversation_id, est_tokens)
            if cached > new:
                cap = max(2, cap // 2)
        return cap

    def prefill_estimate(self, conversation_id: str,
                         prompt_tokens: int) -> "tuple[int, int]":
        """(expected_cached, expected_new) prefill tokens for an
        arriving request — the cache-aware admission seam (used by the
        realtime chunk cap above and by
        ResourceScheduler.set_prefill_estimator). A conversation with
        pinned KV reports its resident length; with the pin reclaimed,
        the conversation service's recorded prefix handle stands in —
        the radix tree usually still holds the committed full blocks
        (optimistic: LRU may have evicted them, but this is a sizing
        heuristic, not an allocation). Otherwise the estimate is
        conservatively all-new (tree matches need the token ids, which
        don't exist before tokenization)."""
        cached = 0
        if conversation_id:
            with self._mu:
                kv = self._conv_cache.get(conversation_id)
                if kv is not None:
                    cached = kv.length
            if (cached == 0 and self._state_manager is not None
                    and self._prefix_cache is not None):
                # Outside self._mu: the state manager's lock sits ABOVE
                # the engine's in the ordering.
                try:
                    h = self._state_manager.prefix_handle(conversation_id)
                except Exception:  # noqa: BLE001 — estimate, not a gate
                    h = None
                if h and str(h.get("tier", "")) != "dropped":
                    # "hbm"/"host"/"store"/unset: the prefix is either
                    # still in the radix tree or promotable from a
                    # lower tier — either way the prefill is mostly
                    # skipped. "dropped" (pin reclaimed, no tiering)
                    # means the KV is gone for good: all-new prefill.
                    ps = self.spec.page_size
                    cached = (int(h.get("length", 0)) // ps) * ps
        return cached, max(0, int(prompt_tokens))

    # -- mixed prefill+decode batching (docs/architecture.md) ----------------

    def _mixed_on(self) -> bool:
        """Mixed batching configured AND the executor carries a mixed
        program (slice geometry > 0 plus a dispatch entrypoint)."""
        if self._mixed_cfg is None:
            return False
        if int(getattr(self.executor, "mixed_prefill_slices", 0)) <= 0:
            return False
        if int(getattr(self.executor, "mixed_slice_tokens", 0)) <= 0:
            return False
        return (getattr(self.executor, "mixed_chunk_start", None)
                is not None
                or getattr(self.executor, "mixed_chunk", None) is not None)

    def _mixed_work_waiting(self) -> bool:
        """Any mid-prefill slot with slices left to run (whether or not
        one is already riding the in-flight chunk). With a row free it
        stops the fill so the reconcile can fuse them into a
        host-assembled mixed chunk; under a full batch the carried
        chunk takes them itself."""
        if not self._mixed_on():
            return False
        return any(s is not None and not s.prefilled and s.todo_ids
                   for s in self._slots)

    def _mixed_applicable(self) -> bool:
        """Dispatch a MIXED chunk this round: mixed batching is on,
        decode rows are active (with no decode work the dedicated
        prefill pipeline is strictly faster — full buckets, async
        waves), and at least one mid-prefill slot has a dispatchable
        slice."""
        if not self._mixed_on():
            return False
        if not any(s is not None and s.prefilled for s in self._slots):
            return False
        return any(s is not None and not s.prefilled and s.todo_ids
                   and s.first_handle is None and not s.mixed_pending
                   for s in self._slots)

    def _has_scheduling_work(self) -> bool:
        """Anything that requires host-side scheduling before the next
        chunk WHILE A ROW IS FREE (and therefore forbids dispatching it
        from device-carried state: ``_fill_refusal``): an arrival, a
        pending request, a cancellation. With every row taken a pending
        request that cannot displace anyone needs no scheduling, and
        this is not consulted. Mid-prefill sequences do NOT block the
        fill: their lanes are latched in the carry and their bucket
        programs just queue behind the chunk — they join via a lane
        override, or a fresh dispatch once resolved
        (_geometry_changed)."""
        with self._mu:
            if self._inbox:
                return True
        if self._pending:
            return True
        for s in self._slots:
            if s is not None and s.handle.cancelled:
                return True
        return False

    def _geometry_changed(self, infl: _InflightChunk) -> bool:
        """A prefilled sequence not in the in-flight chunk's snapshot
        (fresh admission that completed prefill) needs a host-assembled
        dispatch to join the batch — its lane in the carry is latched."""
        for i, s in enumerate(self._slots):
            if s is not None and s.prefilled and infl.seqs[i] is not s:
                return True
        return False

    # -- counts at the dispatch (docs/observability.md "The engine step") -----

    def _live_kv(self) -> "tuple[int, int]":
        """(pages, tokens written in them) of the sequences that own a
        row or wait with pages — KV reserved against KV in use. A walk
        over rows and the pending heap: only while a capture is held."""
        pages = tokens = 0
        for s in self._slots:
            if s is not None:
                pages += len(s.pages)
                tokens += s.pos
        for _, _, s in self._pending:
            if s.pages:
                pages += len(s.pages)
                tokens += s.pos
        return pages, tokens

    def _dispatch_span(self, entry: str, *, steps: int = 0, rows: int = 0,
                       row_steps: int = 0, context_tokens: int = 0,
                       prefill_tokens: int = 0, longest: int = 0,
                       chunk: bool = True, state_rows: int = 0,
                       slice_lens: Sequence[int] = ()):
        """``engine.dispatch``: the span around ONE executor call and
        nothing else, with the counts taken where the work is handed
        over. ``program`` is the executor's name for what runs (its
        ``_aot`` key, ``jit_<program>`` on the device trace); ``steps``
        the device steps dispatched (the longest row budget; 0 for a
        dedicated prefill program), ``rows`` the rows with a budget,
        ``row_steps`` the sum of the budgets, ``inflight`` the chunks
        in flight once this one is, ``context_tokens`` the tokens the
        rows attend to at the first step (host bookkeeping: a
        carried dispatch counts each unreconciled chunk's full
        budget), ``prefill_tokens`` the prompt tokens riding along and
        ``slice_tokens`` the rows the program's products run for them,
        padding included (the executor's ``slice_tokens``: the live row
        tiles of a mixed chunk, bucket x rows of a prefill program, 0
        otherwise).
        ``pages_live`` / ``tokens_live`` (``_live_kv``), the attention
        kernel's schedule (``_attn_counts``) and, for prompt chunks of
        ``slice_lens`` tokens in a family whose recurrent layers scan
        them in a kernel, ``scan_chunks`` / ``scan_chunks_live`` of ONE
        layer's call (``executor.scan_work``: the grid's steps a head
        block and those under a slice's length, by these lengths) only
        while a capture is held. The same quantities accumulate for
        ``get_stats()``. ``chunk`` is the dispatch's serial number:
        the ``engine.fetch`` and ``engine.commit`` of the chunk it sent
        carry the same, so a reader of a capture joins the three."""
        self.device_steps += steps
        self.row_steps += row_steps
        self._dispatch_serial += 1
        name_fn = getattr(self.executor, "program_name", None)
        slice_fn = getattr(self.executor, "slice_tokens", None)
        slice_tokens = (0 if slice_fn is None else slice_fn(
            entry, prefill_tokens if chunk else longest, rows))
        if chunk:            # a mixed chunk: the dedicated programs'
            self.mixed_slice_tokens_total += slice_tokens  # are apart
        counts = {
            "program": (entry if name_fn is None
                        else name_fn(entry, longest)),
            "steps": steps, "rows": rows, "row_steps": row_steps,
            "inflight": len(self._inflight) + (1 if chunk else 0),
            "context_tokens": context_tokens,
            "prefill_tokens": prefill_tokens,
            "slice_tokens": slice_tokens,
            "chunk": self._dispatch_serial}
        hc_fn = getattr(self.executor, "hc_rows_live", None)
        hc_rows = hc_fn(prefill_tokens) if chunk and hc_fn else None
        if hc_rows is not None:      # rows the mixed step's sites run
            counts["hc_rows_live"] = hc_rows
        if self._window and chunk and rows:
            counts.update(self._window_counts())
        if capture_held():
            counts["pages_live"], counts["tokens_live"] = self._live_kv()
            if chunk and rows and self._attn_work is not None:
                counts.update(self._attn_counts())
            work = (self._scan_work(entry, slice_lens)
                    if self._scan_work is not None and slice_lens else None)
            if work is not None:
                counts["scan_chunks"], counts["scan_chunks_live"] = work
            if self._row_state_bytes:
                # rows whose state the program updates: its decode rows
                # and, of a prefill or a mixed chunk, its prompt chunks'
                counts["state_rows"] = rows + state_rows
        return self._prof.span("engine.dispatch", **counts)

    def _attn_counts(self) -> Dict[str, int]:
        """One attention layer's fused decode call at a chunk's first
        step, by the host's bookkeeping of the decoding rows' contexts
        (``executor.attn_work``; computed only while a capture is held:
        it sorts and walks the batch): ``attn_steps``,
        ``attn_row_chunks``, ``attn_row_chunks_full`` of a
        full-attention layer and, for a family with window layers,
        ``window_steps`` / ``window_chunks_full`` of one of those —
        nothing where the decode attention is another kernel's."""
        ctx = np.asarray([s.pos for s in self._slots
                          if s is not None and s.prefilled], np.int64)
        work = self._attn_work(ctx + 1)
        if work is None:
            return {}
        out = dict(zip(("attn_steps", "attn_row_chunks",
                        "attn_row_chunks_full"), work))
        if self._window:
            steps, _, full = self._attn_work(ctx + 1, window=True)
            out["window_steps"], out["window_chunks_full"] = steps, full
        return out

    def _window_counts(self) -> Dict[str, int]:
        """One window layer's counts at a chunk's first step, by the
        host's bookkeeping: the decoding rows' contexts bounded by the
        window (``window_tokens``, beside ``context_tokens``), the key
        chunks their attention visits and those the window start
        skipped, and the tokens inside windows that the seated
        sequences' slabs hold against the tokens all slabs reserve."""
        w = self._window
        seated = [s for s in self._slots if s is not None]
        ctx = np.asarray([s.pos for s in seated if s.prefilled], np.int64)
        visited, skipped = self.executor.window_chunks(ctx + 1)
        out = {"window_tokens": int(np.minimum(ctx, w["tokens"]).sum()),
               "window_chunks": visited, "window_chunks_skipped": skipped,
               "window_live": int(sum(min(s.pos, w["tokens"])
                                      for s in seated)),
               "window_reserved": self.spec.batch_size * w["slab_tokens"]}
        acc = self.window_counts
        acc["dispatches"] += 1
        acc["context_tokens"] += int(ctx.sum())
        acc["window_tokens"] += out["window_tokens"]
        acc["chunks_visited"] += visited
        acc["chunks_skipped"] += skipped
        acc["cache_live_tokens"] += out["window_live"]
        acc["cache_reserved_tokens"] += out["window_reserved"]
        return out

    def _prefill_dispatch(self, entry: str, chunks):
        """``_dispatch_span`` for a dedicated prefill program over
        ``chunks`` (one prompt chunk a row)."""
        return self._dispatch_span(
            entry, rows=len(chunks),
            prefill_tokens=sum(len(c) for c in chunks),
            longest=max(len(c) for c in chunks), chunk=False,
            slice_lens=[len(c) for c in chunks])

    def _dispatch_carried(
            self, infl: _InflightChunk) -> Optional[_InflightChunk]:
        """Plan the next chunk from the in-flight chunk's
        device-carried end state, BEFORE its tokens are fetched, and
        send it (``_emit_chunk``). Returns None, with the reason counted
        (``fill_refusals``), when that isn't possible: the caller
        reconciles instead.

        **Decode rows.** Budgets use conservative upper bounds (as if
        every chunk in flight consumes its full budget on every row):
        a row that cannot be bounded safely gets budget 0 and enters
        latched (done_in).

        **Joining rows** enter as lane overrides (first token
        device-to-device, position + done-latch overridden — the lane
        may have belonged to a finished sequence): a just-admitted
        sequence whose final prefill chunk is dispatched but
        unresolved (``first_handle``) and, under a full batch, one
        whose FINAL slice rides an in-flight mixed chunk (that chunk's
        ``pf_first[i]``, still on the device). Their first token is
        committed when the prefill is resolved or the mixed chunk
        reconciled — always before this chunk's rows. Without the
        join, an arrival during a chunk waits out BOTH that chunk and
        the next one before its same-step join on the fresh path — a
        full chunk of avoidable admission latency, the single largest
        term in realtime p99 under load.

        **Prefill slices**, under a full batch: the prompt slices of
        seated mid-prefill sequences ride along as a carried MIXED
        chunk, packed as ``_plan_mixed`` packs them (their pages were
        granted at admission). One sequence's consecutive slices may
        ride consecutive chunks in flight: the device queue is FIFO.

        **Pages.** A carried chunk never takes a page from a seated or
        a pending sequence and never preempts — those mutate rows the
        chunks in flight are still decoding. Under a full batch it may
        evict what nobody holds: zero-reference radix leaves, then
        idle conversation pins (``_alloc_pages``' first two rungs).
        With a row free it does not: the fresh path, one chunk away,
        decides what an admission is worth."""
        B = self.spec.batch_size
        full = all(s is not None for s in self._slots)
        chunk = self._chunk_steps()
        capacity = self.spec.max_pages_per_seq * self.spec.page_size
        plan = []   # (seq, slot, budget, pages_needed)
        ctx = 0     # context tokens the rows attend to (upper bound)
        for slot in range(B):
            seq = infl.seqs[slot]
            if seq is None or seq.slot != slot or not seq.prefilled:
                continue
            # Bounds accumulate over EVERY in-flight chunk this row
            # rides (pipeline depth > 2 chains several): the row's
            # host-side pos/generated were last reconciled before the
            # OLDEST chunk, so each unreconciled chunk may consume its
            # full budget before this one runs.
            prev_b = sum(int(c.budgets[slot]) for c in self._inflight
                         if c.seqs[slot] is seq)
            gen_upper = len(seq.generated) + prev_b
            pos_upper = seq.pos + prev_b
            limit = seq.req.max_new_tokens or self.max_decode_steps
            b = min(chunk, limit - gen_upper, capacity - pos_upper)
            if b <= 0:
                if not prev_b:
                    # Nothing of this row is in flight and it can take
                    # no step: only the host-assembled path ends it,
                    # and a full batch would never get there.
                    self._refuse_fill("row_ended")
                    return None
                continue
            need = PageAllocator.pages_for(
                pos_upper + b, self.spec.page_size) - len(seq.pages)
            plan.append((seq, slot, b, max(0, need)))
            ctx += pos_upper
        # Joining rows: same eligibility as _plan_decode's join path
        # (final prefill dispatched, not a rebuild/resume), minus rows
        # already snapshotted into ANY in-flight chunk.
        join_plan = []   # (seq, slot, budget, pages_needed)
        first_of = {}    # slot → the override's device scalar
        for slot in range(B):
            seq = self._slots[slot]
            if (seq is None or seq.prefilled
                    or any(c.seqs[slot] is seq for c in self._inflight)
                    or seq.todo_ids
                    or seq.todo_resume is not None or seq.todo_rebuild
                    or seq.handle.cancelled):
                continue
            first = seq.first_handle
            if first is None and full:
                first = self._final_slice_first(seq)
            if first is None:
                continue
            b = self._budget_for(seq, chunk) - 1   # resolve commits one
            if b <= 0:
                continue
            need = PageAllocator.pages_for(
                seq.pos + b, self.spec.page_size) - len(seq.pages)
            join_plan.append((seq, slot, b, max(0, need)))
            first_of[slot] = first
            ctx += seq.pos
        if not plan and not join_plan:
            self._refuse_fill("nothing_to_decode")
            return None
        pf_plan, pf_budget = [], 0
        if full and self._mixed_on():
            pf_plan, pf_budget = self._pack_slices(
                [s for s in self._slots
                 if not s.prefilled and s.todo_ids
                 and s.first_handle is None and not s.handle.cancelled])
        # Growth must not shed: every universe the plan draws from
        # needs headroom up front (a GLOBAL sum would pass while one dp
        # universe is exhausted, breaking the no-shedding assert
        # below).
        need_by_shard: Dict[int, int] = {}
        for seq, slot, _, n in plan + join_plan:
            need_by_shard[self._slot_shard(slot)] = (
                need_by_shard.get(self._slot_shard(slot), 0) + n)
        for d, n in need_by_shard.items():
            if n > self.allocator.available(shard=d) and not (
                    full and self._evict_unheld(n, d)):
                self._refuse_fill("pages")   # would shed → reconcile
                return None
        for seq, slot, _, need in plan + join_plan:
            if need > 0:
                pages = self.allocator.alloc(
                    need, shard=self._slot_shard(slot))
                assert pages is not None    # checked above
                seq.block_table[len(seq.pages):len(seq.pages) + need] = pages
                seq.pages.extend(pages)
                self._usage_pages(seq)
        return self._emit_chunk(
            [row[:3] for row in plan + join_plan],
            [(slot, first_of[slot], seq.pos)
             for seq, slot, _, _ in join_plan],
            ctx, pf_plan, pf_budget, carry=infl)

    def _final_slice_first(self, seq: _Sequence):
        """For a sequence whose FINAL prefill slice rides a mixed chunk
        in flight: that chunk's sampled first token as a lane
        override's device scalar (``pf_first[i]``, not fetched for
        this). None when no chunk in flight holds its final slice."""
        if not seq.mixed_pending:
            return None
        for c in self._inflight:
            for i, (s, _n, final) in enumerate(c.pf or ()):
                if s is seq and final:
                    return c.handle.pf_first_at(i)
        return None

    def _evict_unheld(self, n: int, shard: int) -> bool:
        """Make ``n`` pages available in page universe ``shard`` out of
        what nobody holds — zero-reference radix leaves, then idle
        conversation pins, ``_alloc_pages``' first two rungs and no
        further: no page a chunk in flight reads or writes is touched.
        False when that is not enough."""
        have = self.allocator.available(shard=shard)
        while have < n:
            if not ((self._prefix_cache is not None
                     and self._prefix_cache.evict_pages(n - have) > 0)
                    or self._reclaim_idle_conversation()):
                return False
            # Neither rung knows of universes (dp mesh): a pass that
            # freed pages only elsewhere ends the attempt, rather than
            # emptying the cache for a universe it cannot help.
            was, have = have, self.allocator.available(shard=shard)
            if have <= was:
                return False
        return True

    def _commit_row(self, seq: _Sequence, row: np.ndarray,
                    budget: int) -> None:
        """Commit one sequence's sampled tokens from a chunk output row.
        Token j's KV was written at ``seq.pos`` when it was fed — the
        position bookkeeping here must mirror the device loop exactly."""
        for j in range(budget):
            nxt = int(row[j])
            seq.written_ids.append(seq.last_token)
            seq.pos += 1
            self._commit_token(seq, nxt)
            if seq.slot is None:   # finished (eos/length/cancel)
                return
        if (seq.slot is not None and seq.pos
                >= self.spec.max_pages_per_seq * self.spec.page_size):
            # Block table exhausted: the row can take no further step,
            # so it ends here, like a row at its token limit
            # (``_commit_token``) — not at the next host-assembled
            # chunk, which a full batch may never come to.
            self._finish_active(seq, "length")

    def _offload_fetch(self, fn, lane: str = "chunk") -> Dict:
        """Run a blocking device→host fetch on a fetcher thread;
        returns the completion box ({ev, out, err}) the caller waits on
        via ``_service_while`` — so the scheduling thread keeps
        admitting arrivals during every transfer wait. Callers must
        tolerate the serviced admissions mutating engine state: when no
        chunk is in flight the admission path may preempt/shed
        MID-PREFILL sequences, so a resolve's pending snapshot must be
        re-validated after the wait (see _resolve_prefills).

        Two LANES (threads): resolve fetches must not queue behind the
        chunk fetch — a prefill's sampled scalar usually lands long
        before the chunk completes, and serializing them through one
        FIFO thread gated every resolve on chunk completion (measured
        +160 ms realtime p50 at 5 req/s)."""
        import queue as _queue

        lanes = self._fetch_lanes
        if lane not in lanes:
            q = _queue.Queue()
            t = threading.Thread(target=self._fetch_loop, args=(q,),
                                 name=f"fetch-{lane}-{self.name}",
                                 daemon=True)
            t.start()
            lanes[lane] = (t, q)
        box = {"ev": threading.Event(), "out": None, "err": None}
        lanes[lane][1].put((fn, box))
        return box

    def _start_fetch(self, infl: _InflightChunk) -> None:
        """Hand the chunk's blocking fetch to the fetcher thread (the
        D2H transfer itself was already queued by ``_prefetch`` at
        dispatch; the fetch itself is ONE batched transfer across all
        rows — never per-row blocking). The timed wrapper splits the
        wait into device execute vs token readback and attributes the
        pipeline overlap against the dispatch timestamp — the fetch box
        then holds ``(result, device_s, readback_s, overlapped_s)``."""
        infl.fetch_box = self._offload_fetch(
            lambda: self._telemetry.timed_fetch(
                infl.handle, dispatched_at=infl.dispatched_at))

    def _fetch_loop(self, q) -> None:
        while True:
            item = q.get()
            if item is None:
                return
            fn, box = item
            try:
                box["out"] = fn()
            except Exception as e:  # noqa: BLE001 — re-raised at caller
                box["err"] = e
            box["ev"].set()

    def _service_while(self, ev: threading.Event, wait: str) -> None:
        """Service arrivals while a transfer completes: ingest +
        free-slot admission + the admitted wave's first prefill bucket
        (all non-blocking dispatches). While a chunk is in flight the
        usual guards defer shedding/preemption; with NO chunk in
        flight (resolve-only waits) the admission path MAY shed
        mid-prefill sequences — callers holding snapshots must
        re-validate them after the wait (see _resolve_prefills).

        The whole wait is the loop watch's named wait ``wait``
        (``fetch`` / ``resolve``): a device transfer that stalls shows
        as the loop's overrun, ``inside`` this wait, in the one
        ``loop_stall`` line."""
        with self._watch.wait(wait):
            while not ev.wait(0.002):
                if self._wake.is_set():
                    self._wake.clear()
                    self._ingest()
                    if self._admit():
                        self._advance_prefill()

    def _stalls(self) -> "tuple[int, float]":
        """(overruns of the loop that fell inside a wait on the
        device, their seconds): the watch's account of ``fetch`` and
        ``resolve``."""
        waits = [self._watch.wait(n) for n in ("fetch", "resolve")]
        return (sum(w.stalls for w in waits),
                sum(w.stall_s for w in waits))

    @property
    def stall_events(self) -> int:
        return self._stalls()[0]

    @property
    def stall_ms_total(self) -> float:
        return self._stalls()[1] * 1e3

    # -- completion offload (docs/performance.md "Async pipeline") ------------

    def _completion_pool(self) -> _CompletionPool:
        """Lazy singleton (same pattern as the fetch lanes): only
        engines that actually run the async pipeline spawn completion
        threads. Single-caller discipline: created from the engine
        thread (or the supervisor's recovery path with the loop dead),
        never concurrently."""
        p = self._completion
        if p is None:
            p = self._completion = _CompletionPool(
                self._completion_workers, self.name, self._prof)
        return p

    def _drain_completions(self) -> bool:
        if self._completion is not None:
            return self._completion.drain()
        return True

    def _note_dispatch_depth(self, depth: int) -> None:
        """One chunk dispatched at pipeline occupancy ``depth``. Plain
        indexed increment on preallocated keys (1..4): stats scrapes
        iterate the dict lock-free, so a first-seen-key resize must be
        impossible — an out-of-range depth is a bug and fails loudly
        here instead of silently growing the dict."""
        self.pipeline_depth_hist[depth] += 1

    def _flush_emits(self, seq: _Sequence) -> None:
        """Ship a sequence's buffered token callbacks to the completion
        executor as ONE batch job (chunk-granularity, same cadence the
        callbacks already documented). No-op with nothing buffered —
        callable liberally after every commit site."""
        if not seq.pending_emit:
            return
        toks, seq.pending_emit = seq.pending_emit, []
        handle = seq.handle
        req_id = seq.req.id

        def emit() -> None:
            cb = handle._on_token
            if cb is None:
                return
            with self._prof.span("engine.deliver", tokens=len(toks)):
                # The last leg of TTFT: ``first_token`` was stamped on
                # the engine thread at commit; this is the instant the
                # token is handed to its consumer.
                handle.marks.setdefault("first_token_out",
                                        time.perf_counter())
                for t in toks:
                    try:
                        cb(t)
                    except Exception:  # noqa: BLE001 — broken consumer
                        log.exception(
                            "on_token callback failed; detaching",
                            extra={"fields": {"request_id": req_id}})
                        handle._on_token = None
                        return

        self._completion_pool().submit(req_id, emit)

    def _deliver_finish(self, seq: _Sequence, reason: str,
                        error: str) -> None:
        """Completion-executor tail of ``_finish``: trace recording,
        detokenization and the handle completion — everything that
        talks to the request, nothing that touches engine state. Runs
        AFTER the sequence's last token batch (same request key, FIFO
        worker), so streams always see tokens, then done."""
        with self._prof.span("engine.deliver", finish=reason):
            try:
                self._record_trace(seq, reason)
            except Exception:  # noqa: BLE001 — tracing must not block delivery
                log.exception("trace record failed for %s", seq.req.id)
            res = GenResult(
                text=self.tokenizer.decode(seq.generated),
                tokens=list(seq.generated),
                prompt_tokens=len(seq.prompt_ids),
                cached_tokens=seq.cached_len,
                finish_reason=reason,
                error=error,
                kv_tier=seq.served_tier)
            seq.handle._finish(res)

    # -- usage attribution (observability/usage.py) ---------------------------

    def _cp_decode_share(self, chunk_s: float, parts,
                         decode_rows) -> None:
        """Decode rows' pro-rata share of one chunk's serial device
        cost accumulates into ``cp_decode_s`` — the critical-path
        decode compute/stall split reads it off the terminal trace
        event (observability/critical_path.py). ``parts`` is the full
        ``[(seq, weight, waste)]`` list the chunk ran (prefill slices
        included, so shares stay overlap-truthful); ``decode_rows`` is
        the ``[(seq, weight)]`` subset actually decoding."""
        if chunk_s <= 0:
            return
        total_w = 0
        for _, w, _ in parts:
            total_w += w
        if total_w <= 0:
            return
        for seq, w in decode_rows:
            seq.cp_decode_s += chunk_s * (w / total_w)

    def _charge_step(self, device_s: float, parts) -> None:
        """Split one measured chunk's device-execute seconds pro-rata
        across the rows/slices that rode it. ``parts`` is
        ``[(seq, weight, waste)]`` — weight is decode budget or slice
        tokens; ``waste`` marks rebuild re-prefill (work a preemption/
        shed already paid for once). Plain float adds on the engine
        thread; the ledger sees one conservation note per chunk."""
        u = self._usage
        if not u.enabled or device_s <= 0:
            return
        total_w = 0
        for _, w, _ in parts:
            total_w += w
        attributed = 0.0
        if total_w > 0:
            for seq, w, waste in parts:
                ru = seq.usage
                if ru is None:
                    continue
                share = device_s * (w / total_w)
                if waste:
                    ru.waste_s += share
                    if not ru.waste_reason:
                        ru.waste_reason = "preempt"
                else:
                    ru.device_s += share
                attributed += share
        u.note_step(device_s, attributed)

    def _usage_pages(self, seq: _Sequence) -> None:
        """Refresh the page-seconds tracker with ``seq``'s current
        holding: radix-matched pages are SHARED (fractional charge
        across sharers), the rest exclusive. Called after every
        page-set mutation — admission/growth/release-shaped events,
        never per token."""
        u = self._usage
        if not u.enabled or seq.usage is None:
            return
        shared = (seq.prefix_match.pages
                  if seq.prefix_match is not None else ())
        u.tracker.update(seq.req.id,
                         len(seq.pages) - len(shared), shared)

    def _process_chunk(self, infl: _InflightChunk) -> None:
        """Commit an in-flight chunk's tokens. Uses the dispatch-time
        snapshot; cancellations are deliberately NOT acted on here (the
        reconcile/fresh path owns them — a carried chunk may
        already be running on rows a cancel would free).

        While the fetcher thread waits on the transfer, this thread
        SERVICES ARRIVALS: ingest + free-slot admission + the admitted
        wave's first prefill bucket (all non-blocking dispatches that
        queue behind the in-flight work). An arrival therefore starts
        prefilling within ~ms of submit and its first token joins the
        next chunk — instead of queueing behind a full chunk-fetch
        wall. Shedding/preemption stay deferred (same invariants as the
        pre-reconcile admission pass)."""
        box = infl.fetch_box
        if box is None:
            with self._prof.span("engine.fetch", chunk=infl.chunk), \
                    self._watch.wait("fetch"):
                out, device_s, readback_s, overlapped_s = \
                    self._telemetry.timed_fetch(
                        infl.handle, dispatched_at=infl.dispatched_at)
        else:
            with self._prof.span("engine.fetch", chunk=infl.chunk):
                self._service_while(box["ev"], "fetch")
            if box["err"] is not None:
                raise box["err"]
            out, device_s, readback_s, overlapped_s = box["out"]
        with self._prof.span("engine.commit", chunk=infl.chunk) as span:
            self._commit_chunk(infl, out, device_s, readback_s,
                               overlapped_s)
            self._note_moe(getattr(infl.handle, "stats", None), span)
            self._note_key_blocks(
                getattr(infl.handle, "key_blocks", None), span)

    def _note_moe(self, stats, span) -> None:
        """Fold a fetched chunk's routed-layer counters (they came over
        with its tokens) into ``get_stats()["moe"]`` and onto the span
        that covers the commit: what a capture's readers sum. The
        held experts' loads ride as one string (``n3_0_5_...``: the
        profiler reads a string that starts with a digit as a number),
        and only while a capture is held."""
        if stats is None:
            return
        st = np.asarray(stats, np.int64)
        self._moe = st if self._moe is None else self._moe + st
        if capture_held():
            c = self._moe_counts(st)
            span.note(moe_pairs=int(c["load"].sum()),
                      moe_touched=c["touched"], moe_layer_runs=c["runs"],
                      moe_zero_slots=c["zero_slots"],
                      moe_away_slots=c["away_slots"],
                      moe_load="n" + "_".join(map(str, c["load"].tolist())))
            if "hc_row_sum_err" in c:
                span.note(hc_row_sum_err=c["hc_row_sum_err"])

    def _note_key_blocks(self, key_blocks, span) -> None:
        """Fold a mixed chunk's prefill key blocks (``(visited, the
        table holds)`` of one attention of its mixed step, reckoned by
        the executor at the dispatch) into
        ``get_stats()["mixed_key_blocks"]`` and, while a capture is
        held, onto the span that covers the commit: how much of the
        block tables' windows the prefill attention ran over."""
        if key_blocks is None:
            return
        self._key_blocks += np.asarray(key_blocks[:2], np.int64)
        if capture_held():
            span.note(pf_key_blocks=int(key_blocks[0]),
                      pf_table_blocks=int(key_blocks[1]))
            if len(key_blocks) > 2:     # the slices' LIVE keys and pairs
                span.note(pf_live_keys=int(key_blocks[2]),
                          pf_live_pairs=int(key_blocks[3]))

    def _moe_counts(self, st: np.ndarray) -> Dict[str, Any]:
        """A family's step counters by name, by the layout the family
        states (``models/__init__.py`` ``step_stats_layout``): ``load``
        an array, the others ints, 0 for one the family does not
        count."""
        layout = getattr(self.executor, "step_stats_layout", None) or {}
        first, end = layout["load"]
        out: Dict[str, Any] = {"load": st[first:end]}
        for name in ("touched", "runs", "zero_slots", "away_slots"):
            out[name] = int(st[layout[name]]) if name in layout else 0
        if "hc_row_sum_err" in layout:     # a family of several streams
            out["hc_row_sum_err"] = int(st[layout["hc_row_sum_err"]])
        return out

    def _commit_chunk(self, infl: _InflightChunk, out, device_s: float,
                      readback_s: float, overlapped_s: float) -> None:
        """``_process_chunk`` once the tokens are on the host
        (``engine.commit``): attribution, the rows' commits, the
        flushes to the completion pool, a mixed chunk's finished
        prefills."""
        pf_first = None
        if infl.pf is not None:
            out, pf_first = out      # mixed chunk: (decode, slice firsts)
        if self._usage.enabled or self._cp.enabled:
            # Attribute BEFORE committing: rows that finish during the
            # commit loop (EOS) finalize their ledger record there and
            # must already carry this chunk's share, weighed by the
            # rows' dispatch budgets.
            parts = []
            decode_rows = []
            for slot in range(self.spec.batch_size):
                seq = infl.seqs[slot]
                if seq is not None and seq.slot == slot:
                    w = max(1, int(infl.budgets[slot]))
                    parts.append((seq, w, False))
                    decode_rows.append((seq, w))
            if infl.pf is not None:
                for seq, n_tok, _final in infl.pf:
                    parts.append((seq, n_tok, seq.todo_rebuild))
            if self._usage.enabled:
                self._charge_step(device_s, parts)
            if self._cp.enabled:
                # Serial cost = novel device time + readback —
                # overlapped spans are already excluded by timed_fetch.
                self._cp_decode_share(device_s + readback_s, parts,
                                      decode_rows)
        tok0 = self.tokens_generated_total
        for slot in range(self.spec.batch_size):
            seq = infl.seqs[slot]
            if seq is None or seq.slot != slot:
                continue    # finished while the chunk was in flight
            self._commit_row(seq, out[slot], int(infl.budgets[slot]))
            self._flush_emits(seq)
        if infl.pf is not None:
            self._finish_mixed_prefills(infl.pf, pf_first)
        self._telemetry.note_step(infl.dispatch_s, device_s, readback_s,
                                  self.tokens_generated_total - tok0,
                                  overlapped_s=overlapped_s)
        self._set_gauges()

    def _budget_chunk_rows(self, chunk: int, rows) -> Dict[int, int]:
        """Eligibility + budgeting of a host-assembled chunk's rows —
        both fresh plans call it (``_plan_decode``, ``_plan_mixed``)
        and neither stages anything: reap cancelled/length rows, back
        each survivor's budget with pages (preempt-with-release when
        the pool can't), and return seq.order → budget."""
        budgets_by_order: Dict[int, int] = {}
        for seq in rows:
            if seq.slot is None:
                continue  # shed by an earlier sequence's page allocation
            if seq.handle.cancelled:
                self._finish_active(seq, "cancelled")
                continue
            if seq.pos // self.spec.page_size >= self.spec.max_pages_per_seq:
                self._finish_active(seq, "length")  # block table exhausted
                continue
            budget = self._budget_for(seq, chunk)
            if not seq.prefilled:
                # Joining row (decode path only): the resolve will
                # commit the prefill-sampled token FIRST, so the row
                # may emit one fewer (0 latches the row — harmless; its
                # admission still completes at resolve).
                budget = max(0, budget - 1)
            if budget and not self._ensure_decode_pages(seq, budget):
                # Pool exhausted even after shedding everyone else:
                # requeue this one rather than truncating its output.
                if seq.slot is not None:  # may have been shed already
                    self._preempt(seq, release_pages=True)
                continue
            budgets_by_order[seq.order] = budget
        if self._tenancy.enabled:
            self._apply_decode_fairness(rows, budgets_by_order)
        return budgets_by_order

    def _apply_decode_fairness(self, rows, budgets_by_order) -> None:
        """Tenancy plane, engine level (docs/tenancy.md): when rows
        from MORE THAN ONE tenant share a chunk, cap each tenant's
        slice of the chunk's total decode-token budget at its
        weight-proportional share — so queue-level fairness holds past
        admission into the fused step. Uncontended (single tenant, or
        everyone under their share) the caps never bind and the chunk
        is byte-identical to the unfair one. A row's budget never drops
        below 1 (a zero budget would latch the row); budgets shrunk
        here only delay tokens to the next chunk — pages were already
        ensured for the larger budget, so no allocation is retracted.
        """
        by_tenant: Dict[str, List[_Sequence]] = {}
        for seq in rows:
            if seq.slot is not None and seq.order in budgets_by_order:
                by_tenant.setdefault(seq.req.tenant_id, []).append(seq)
        if len(by_tenant) < 2:
            return   # free when uncontended
        total = sum(budgets_by_order[s.order]
                    for ss in by_tenant.values() for s in ss)
        if total <= 0:
            return
        caps = weighted_token_caps(
            {t: self._tenancy.weight_for(t) for t in by_tenant}, total)
        for tenant, seqs in by_tenant.items():
            t_sum = sum(budgets_by_order[s.order] for s in seqs)
            cap = caps.get(tenant, t_sum)
            if t_sum <= cap:
                continue
            scale = cap / t_sum
            for s in seqs:
                b = budgets_by_order[s.order]
                if b > 1:
                    budgets_by_order[s.order] = max(1, int(b * scale))

    def _chunk_steps(self) -> int:
        """The most steps a row may take in the next chunk: the
        executor's chunk size under the admission cap."""
        return min(max(1, getattr(self.executor, "chunk_size", 1)),
                   self._admission_cap())

    def _plan_decode(self) -> Optional[tuple]:
        """The plan of a host-assembled DECODE chunk — ``(rows,
        overrides, context_tokens)`` for ``_emit_chunk`` — or None with
        no row to step: the prefilled rows and the joining ones,
        budgets exact through ``_budget_chunk_rows``."""
        chunk = self._chunk_steps()
        active = [s for s in self._slots
                  if s is not None and s.prefilled]
        # Same-step decode JOIN: a sequence whose final prefill chunk is
        # dispatched-but-unresolved can enter THIS chunk — its sampled
        # first token is fed device-to-device (lane override), never
        # waiting out the resolve round-trip. Its admission completes at
        # the next _resolve_prefills, which always runs before this
        # chunk is processed, so commit order stays first-token-then-row
        # (an EOS first token finishes the sequence there and the row is
        # discarded; the garbage KV it wrote lands in pages that any
        # later owner rewrites before reading). Rebuild-resume rows are
        # excluded — their replayed first sample is discarded by design.
        joining = []
        if chunk > 1 and getattr(self.executor, "decode_chunk_start",
                                 None) is not None:
            joining = [s for s in self._slots
                       if s is not None and not s.prefilled
                       and s.first_handle is not None
                       and not s.todo_ids and s.todo_resume is None
                       and not s.todo_rebuild
                       and not s.handle.cancelled]
        budgets_by_order = self._budget_chunk_rows(chunk,
                                                   list(active) + joining)
        active = [s for s in self._slots
                  if s is not None and s.prefilled]
        joining = [s for s in joining
                   if s.slot is not None and s.first_handle is not None
                   and s.order in budgets_by_order]
        if not active and not joining:
            return None
        return ([(s, s.slot, budgets_by_order.get(s.order, 1))
                 for s in active + joining],
                [(s.slot, s.first_handle, s.pos) for s in joining],
                sum(s.pos for s in active + joining))

    def _plan_mixed(self) -> Optional[tuple]:
        """The plan of a host-assembled MIXED chunk — ``(rows, None,
        context_tokens, pf_plan, pf_budget)`` for ``_emit_chunk`` — or
        None when the packing came back empty: the active decode rows'
        chunk plus up to ``mixed_batch.prefill_token_budget`` tokens of
        pending prefill slices, fused into a single device program
        (executor ``mixed_chunk_start`` / ``mixed_chunk``). This
        replaces the "prefill program, then decode chunk" serialization
        whenever both kinds of work coexist: decode rows keep emitting
        every iteration and their prefill-induced stall is bounded by
        the budget instead of the longest admitted prompt. Token
        streams are identical to the unfused path — slices write the
        same KV at the same positions, the final slice samples the same
        first token, decode rows never read another sequence's pages.

        No joining rows: a sequence with an unresolved ``first_handle``
        beside a mixed chunk is left for the next one.
        """
        active = [s for s in self._slots
                  if s is not None and s.prefilled]
        budgets_by_order = self._budget_chunk_rows(self._chunk_steps(),
                                                   active)
        active = [s for s in self._slots
                  if s is not None and s.prefilled]
        # Prefill slices, most urgent first — packed AFTER decode
        # budgeting (its page allocation may shed a mid-prefill victim;
        # the pack must see the post-shed state: packing BEFORE it
        # would reintroduce the stale-slice bug, a shed victim's
        # todo_ids fold into its rebuild stream).
        cands = [s for s in self._slots
                 if s is not None and not s.prefilled and s.todo_ids
                 and s.first_handle is None and not s.mixed_pending]
        for s in list(cands):
            if s.handle.cancelled:
                self._finish_active(s, "cancelled")
                cands.remove(s)
        pf_plan, pf_budget = self._pack_slices(cands)
        if not pf_plan:
            # Every candidate was shed/cancelled DURING decode
            # budgeting (a page-pressure race — _mixed_applicable
            # guaranteed one existed at entry): ``_assemble`` plans a
            # plain chunk instead.
            return None
        return ([(s, s.slot, budgets_by_order.get(s.order, 1))
                 for s in active], None, sum(s.pos for s in active),
                pf_plan, pf_budget)

    def _emit_chunk(self, rows, overrides, ctx: int, pf_plan=(),
                    pf_budget: int = 0,
                    carry: Optional[_InflightChunk] = None
                    ) -> _InflightChunk:
        """Send the chunk a plan describes: the ONE way a decode or a
        mixed chunk reaches the executor, whoever planned it
        (``_plan_decode``, ``_plan_mixed``, ``_dispatch_carried``).
        ``rows``: ``(seq, slot, budget)`` of every row that steps, the
        joining rows last; ``overrides``: their lanes (None: the call
        names none); ``ctx``: the context tokens the rows attend to;
        ``pf_plan`` / ``pf_budget``: ``_pack_slices``' answer, handed
        over here — with slices the chunk is a ``mixed_chunk``;
        ``carry``: the chunk in flight this one starts from — tokens
        and positions stay on the device, and its row snapshot is this
        one's with the joining rows laid over it.

        Pipelined (the executor has the ``*_start`` call; a chunk from
        the host needs ``chunk_size`` > 1 too) the chunk is appended to
        ``_inflight`` HERE and its fetch started. Otherwise the one
        SERIAL arm: the blocking call, then ``_commit_chunk``, as for
        a fetched one."""
        B = self.spec.batch_size
        t_asm = time.perf_counter()   # step decomposition: dispatch leg
        st = self._staging            # per-dispatch alloc churn killer
        tokens = positions = None
        if carry is None:
            tokens = st.take("chunk.tok", (B,), np.int32)
            positions = st.take("chunk.pos", (B,), np.int32)
        block_tables = st.take("chunk.bt",
                               (B, self.spec.max_pages_per_seq), np.int32)
        temps = st.take("chunk.temp", (B,), np.float32)
        budgets = np.zeros(B, np.int32)   # read again at process time
        seqs = [None] * B if carry is None else list(carry.seqs)
        for seq, slot, budget in rows:
            if carry is None:
                # A joining row's input token is a device scalar (its
                # prefill's sample): the host placeholder is overridden.
                if seq.prefilled:
                    tokens[slot] = seq.last_token
                positions[slot] = seq.pos
            block_tables[slot] = seq.block_table
            temps[slot] = seq.req.temperature
            budgets[slot] = budget
            seqs[slot] = seq
        decoding = len(rows) - len(overrides or ())
        ex = self.executor
        chunked = getattr(ex, "chunk_size", 1) > 1
        pf, infl_pf, packed = (), None, 0
        if pf_plan:
            packed = sum(len(sl) for _, sl in pf_plan)
            pf, infl_pf = self._take_slices(pf_plan, pf_budget, decoding)
            entry = "mixed_chunk"
            pipelined = getattr(ex, "mixed_chunk_start", None) is not None
        else:
            pipelined = (getattr(ex, "decode_chunk_start", None) is not None
                         and (chunked or carry is not None))
            entry = ("decode_chunk" if pipelined or (
                chunked and hasattr(ex, "decode_chunk")) else "decode")
        arrays = (tokens, positions, block_tables, temps, budgets)
        lanes = {} if overrides is None else {"overrides": overrides}
        if carry is not None:
            lanes["carry"] = carry.handle
        handle = out = None
        t_call = time.perf_counter()
        with self._dispatch_span(
                entry, steps=int(budgets.max()),
                rows=int(np.count_nonzero(budgets)),
                row_steps=int(budgets.sum()), context_tokens=ctx,
                prefill_tokens=packed, state_rows=len(pf),
                slice_lens=[len(sl[1]) for sl in pf]):
            if pipelined and pf_plan:
                handle = ex.mixed_chunk_start(*arrays, pf, **lanes)
            elif pipelined:
                handle = ex.decode_chunk_start(*arrays, **lanes)
            elif pf_plan:           # the serial arm: one blocking call
                out = ex.mixed_chunk(*arrays, pf)
            elif entry == "decode":
                out = ex.decode(*arrays[:4])[:, None]
            else:
                out = ex.decode_chunk(*arrays)
        now = time.perf_counter()   # handed to the device queue, or done
        if pf_plan:
            self._mixed_dispatched(handle, infl_pf, packed, now - t_call,
                                   decoding > 0)
        else:
            _prefetch(getattr(handle, "out", None))
        infl = _InflightChunk(
            handle, seqs, budgets, pf=infl_pf,
            dispatch_s=(now if pipelined else t_call) - t_asm,
            dispatched_at=now, chunk=self._dispatch_serial)
        self.steps += 1
        if self._metrics:
            self._m("decode_steps").inc()
        if pipelined:   # its tokens are fetched on a LATER step
            self._inflight.append(infl)
            self._note_dispatch_depth(len(self._inflight))
            self._start_fetch(infl)
            return infl
        t_done = time.perf_counter()
        out = ((np.asarray(out[0]), out[1]) if pf_plan
               else np.asarray(out))     # readback fence (no-op for echo)
        self._commit_chunk(infl, out, now - t_call,
                           time.perf_counter() - t_done, 0.0)
        return infl

    def _pack_slices(self, cands) -> "tuple[list, int]":
        """``([(seq, token_ids)], budget)``: the mid-prefill sequences
        ``cands``, most urgent first, packed into the compiled mixed
        program's slice grid under the token budget — the one packing
        of a host-assembled (``_plan_mixed``) and of a carried
        (``_dispatch_carried``) mixed chunk."""
        S = int(getattr(self.executor, "mixed_prefill_slices", 0))
        T = int(getattr(self.executor, "mixed_slice_tokens", 0))
        # The dispatch can never out-pack the compiled program's S
        # slices of T tokens (the builder sets T = budget // S, so the
        # clamp only bites on an executor built by hand).
        budget = min(int(self._mixed_cfg.prefill_token_budget), S * T)
        cands.sort(key=lambda s: s.sort_key())
        # Tenancy plane (docs/tenancy.md): under multi-tenant
        # contention for the prefill budget, pack with per-tenant
        # weight-proportional caps; with tenancy off (or one tenant)
        # the single uncapped pass packs identically to the
        # pre-tenancy loop.
        tenant_caps = None
        if self._tenancy.enabled:
            cand_tenants = {s.req.tenant_id for s in cands}
            if len(cand_tenants) > 1:
                tenant_caps = weighted_token_caps(
                    {t: self._tenancy.weight_for(t)
                     for t in cand_tenants}, budget)
        return _pack_prefill_slices(cands, S, T, budget,
                                    tenant_caps), budget

    def _take_slices(self, pf_plan, budget: int,
                     decode_rows: int) -> "tuple[list, list]":
        """Hand ``pf_plan``'s slices over to the chunk about to be
        dispatched: the executor's ``pf`` tuples and the in-flight
        snapshot ``[(seq, n_tokens, final)]``, each sequence's prefill
        bookkeeping advanced past its slice."""
        pf = []
        infl_pf = []
        for seq, sl in pf_plan:
            seq.handle.marks.setdefault("prefill_start",
                                        time.perf_counter())
            pf.append((seq.slot, sl, seq.todo_pos, seq.block_table,
                       seq.req.temperature))
            seq.todo_ids = seq.todo_ids[len(sl):]
            seq.todo_pos += len(sl)
            seq.pos = seq.todo_pos
            seq.pf_tokens_run += len(sl)
            seq.written_ids.extend(sl)
            if self._row_tail is not None:
                self._tails_due.append((seq, seq.todo_pos - len(sl),
                                        seq.todo_pos))
            if not seq.todo_ids:
                # The FINAL slice is handed to a chunk: what is left of
                # TTFT is that chunk's run and its reconcile.
                seq.handle.marks.setdefault("prefill_last_dispatched",
                                            time.perf_counter())
            infl_pf.append((seq, len(sl), not seq.todo_ids))
        if self._metrics:
            packed = sum(n for _, n, _ in infl_pf)
            self._m("mixed_step_decode_rows").set(decode_rows)
            self._m("mixed_step_prefill_tokens").set(packed)
            self._m("mixed_budget_utilization").set(
                packed / budget if budget else 0.0)
        return pf, infl_pf

    def _mixed_dispatched(self, handle, infl_pf, packed: int,
                          host_seconds: float,
                          decode_active: bool) -> None:
        """Accounting of one mixed chunk handed to the executor
        (``handle`` None: it ran in the call): the stall estimate, the
        transfers queued behind the program, the slices' in-flight
        latch (``_finish_mixed_prefills`` clears it), the counters."""
        self._note_prefill_dispatch(packed, host_seconds,
                                    decode_active=decode_active,
                                    fused=True)
        self._take_due_tails()
        _prefetch(getattr(handle, "out", None))
        _prefetch(getattr(handle, "pf_first", None))
        for seq, _, _ in infl_pf:
            seq.mixed_pending += 1
        self.mixed_steps += 1
        self.mixed_prefill_tokens_total += packed

    def _finish_mixed_prefills(self, pf, pf_first) -> None:
        """Reconcile the prefill slices of a processed mixed chunk:
        clear the in-flight latch and complete admissions whose FINAL
        slice ran (their sampled first token is ``pf_first[i]``)."""
        for i, (seq, _n, final) in enumerate(pf):
            seq.mixed_pending = max(0, seq.mixed_pending - 1)
            if seq.slot is None or seq.prefilled:
                continue   # shed or superseded while in flight
            if seq.handle.cancelled:
                self._finish_active(seq, "cancelled")
                continue
            if final:
                self._complete_prefill(seq, int(pf_first[i]))
                self._flush_emits(seq)   # admission first token: no
                #                          extra chunk of SSE latency

    def _commit_token(self, seq: _Sequence, nxt: int) -> None:
        if nxt == self.spec.eos_id:
            self._finish_active(seq, "eos")
            return
        seq.generated.append(nxt)
        seq.last_token = nxt
        self.tokens_generated_total += 1
        handle = seq.handle
        if len(seq.generated) == 1:
            handle.marks.setdefault("first_token", time.perf_counter())
            if self._cp.enabled:
                # Boot telemetry: the process's first committed token
                # EVER closes the replica_ready_seconds decomposition
                # (idempotent — one flag check after it fires).
                boot_note_first_token()
        if handle._on_token is not None:
            if self._completion_workers > 0:
                # Async pipeline: SSE framing/streaming callbacks run
                # on the completion executor, not the dispatch path —
                # buffered here, flushed one batch job per chunk.
                seq.pending_emit.append(nxt)
            else:
                if len(seq.generated) == 1:
                    # No completion workers: the hand-over is here.
                    handle.marks.setdefault("first_token_out",
                                            time.perf_counter())
                try:
                    handle._on_token(nxt)
                except Exception:  # noqa: BLE001 — broken stream consumer
                    log.exception("on_token callback failed; detaching",
                                  extra={"fields": {
                                      "request_id": seq.req.id}})
                    handle._on_token = None
        if self._metrics:
            self._m("generated_tokens", seq.req.priority.tier_name).inc()
        limit = seq.req.max_new_tokens or self.max_decode_steps
        if len(seq.generated) >= limit:
            self._finish_active(seq, "length")

    def _finish_active(self, seq: _Sequence, reason: str) -> None:
        if self._cp.enabled and seq.generated:
            # Critical path: decode ends HERE — everything after (page
            # trim, prefix publish, pin, exchange publish, detok +
            # handle finish on the completion pool) is the
            # "completion" segment.
            seq.handle.marks.setdefault("decode_done",
                                        time.perf_counter())
        row = seq.slot          # its state is the sequence's until reseated
        if seq.slot is not None:
            self.executor.release_slot(seq.slot)
            self._slots[seq.slot] = None
            seq.slot = None
        conv = seq.req.conversation_id
        # Publish the finished sequence's full-block KV prefix into the
        # radix tree (tree retains its own page refs; the sequence's
        # refs are released below exactly as before) — this is how a
        # later turn, or an unrelated request sharing a system prompt,
        # finds the pages. Skipped on a written_ids/pos mismatch: a
        # mis-keyed block would serve wrong KV to whoever matches it.
        publish = (self._prefix_cache is not None
                   and reason in ("eos", "length")
                   and len(seq.written_ids) == seq.pos)
        handle_rec = None
        pinned = False
        if conv and reason in ("eos", "length"):
            # Trim pages past the written length before pinning: decode
            # budgets allocate ahead (and a joined row that finished at
            # resolve wrote only garbage there) — pinning them would
            # hold pool capacity for KV no turn will ever read.
            keep = PageAllocator.pages_for(seq.pos, self.spec.page_size)
            if len(seq.pages) > keep:
                extra = seq.pages[keep:]
                seq.pages = seq.pages[:keep]
                seq.block_table[keep:keep + len(extra)] = 0
                self.allocator.free(extra)
            with self._mu:
                if conv in self._conv_drop_pending:
                    self._conv_drop_pending.discard(conv)
                    if seq.prefix_match is not None:
                        # Unlock BEFORE invalidating: this sequence's
                        # own match pins the deepest path nodes, and
                        # invalidate() stops at the first locked node —
                        # pruning would silently no-op against our own
                        # lock. The sequence is finishing; its pages
                        # are freed right here.
                        self._prefix_cache.unlock(seq.prefix_match)
                        seq.prefix_match = None
                    self.allocator.free(seq.pages)
                    if self._prefix_cache is not None:
                        # Deleted mid-turn: earlier turns' published
                        # blocks are prefixes of this written stream —
                        # prune what's exclusively this conversation's.
                        self._prefix_cache.invalidate(seq.written_ids)
                else:
                    if len(seq.written_ids) != seq.pos:
                        log.warning(
                            "written_ids/pos mismatch for %s: %d vs %d",
                            seq.req.id, len(seq.written_ids), seq.pos,
                            extra={"fields": {
                                "request_id": seq.req.id,
                                "conversation_id": conv}})
                    if publish:
                        self._prefix_cache.insert(seq.written_ids,
                                                  list(seq.pages))
                        self._publish_tails(seq, row)
                    self._conv_cache[conv] = _ConvKV(
                        pages=list(seq.pages),
                        block_table=seq.block_table.copy(),
                        length=seq.pos,
                        last_used=self._clock.now(),
                        tokens=list(seq.written_ids),
                        pending=(seq.last_token if reason == "length"
                                 else None))
                    self.allocator.pin(conv, seq.pages)
                    pinned = True
                    if self._usage.enabled:
                        # Between-turns KV residency: the request's own
                        # meter closes at _finish; the pin meter bills
                        # the conversation/tenant until adoption/drop.
                        self._usage.pin_kv(conv, len(seq.pages),
                                           seq.req.tenant_id)
                    if self._prefix_cache is not None:
                        handle_rec = {"length": seq.pos,
                                      "pages": len(seq.pages),
                                      "updated_at": self._clock.now(),
                                      "tier": "hbm"}
            seq.pages = []
        elif publish and seq.pages:
            self._prefix_cache.insert(seq.written_ids, list(seq.pages))
            self._publish_tails(seq, row)
        if handle_rec is not None and self._state_manager is not None:
            # Outside self._mu: the state manager's lock is ABOVE the
            # engine's in the ordering (its eviction hooks call back in).
            try:
                self._state_manager.record_prefix_handle(conv, handle_rec)
            except Exception:  # noqa: BLE001 — accounting, not a gate
                log.exception("prefix-handle record failed for %s", conv)
        if pinned and self.on_conversation_cached is not None:
            # Disagg publish hook (docs/disaggregation.md): the turn's
            # conversation KV is pinned and adoptable — a prefill
            # replica's coordinator demotes + publishes it to the
            # exchange from here. Outside self._mu (the hook demotes,
            # which takes the lock itself).
            try:
                self.on_conversation_cached(conv)
                if self._cp.enabled:
                    # Stage event (not a mark: the publish is wall-time
                    # NOW, no perf anchor needed) — the stitched
                    # ?format=chrome timeline shows where the disagg
                    # handoff left this replica.
                    from llmq_tpu import observability
                    observability.record(
                        seq.req.id, "kv_publish", engine=self.name,
                        priority=seq.req.priority.tier_name,
                        conversation=conv, role=self.disagg_role)
            except Exception:  # noqa: BLE001 — publish is best-effort
                log.exception("on_conversation_cached failed for %s",
                              conv)
        self._finish(seq, reason)

    def _record_trace(self, seq: _Sequence, reason: str) -> None:
        """Stamp the engine-side lifecycle events for a finished
        sequence into the flight recorder (docs/observability.md).
        Handle marks are perf_counter-based; the wall anchor shifts
        them onto the shared clock. One call per request — never per
        token — so the trace plane stays off the decode hot path."""
        from llmq_tpu import observability
        rec = observability.get_recorder()
        if not rec.enabled:
            return
        anchor = observability.perf_anchor()
        prio = seq.req.priority.tier_name
        marks = seq.handle.marks
        events = [(stage, marks[stage] + anchor,
                   {"engine": self.name, "priority": prio})
                  for stage in ("admitted", "kv_promote_start",
                                "kv_promote_done", "handoff_claim_start",
                                "handoff_claim_done", "prefill_start",
                                "prefill_last_dispatched",
                                "prefill_done", "first_token",
                                "first_token_out", "preempted",
                                "decode_done")
                  if stage in marks]
        store_wait_ms = marks.get("_store_wait_ms", 0.0)
        if store_wait_ms > 0.0:
            # Store fault domain (docs/robustness.md): attach the store
            # round-trip share to the promote/claim span-close event so
            # the critical-path plane attributes store waits without a
            # new stage.
            for i, (stage, ts, ev_meta) in enumerate(events):
                if stage in ("kv_promote_done", "handoff_claim_done"):
                    events[i] = (stage, ts, dict(
                        ev_meta, store_wait_ms=round(store_wait_ms, 3)))
        # Cancellation (client closed the stream / gave up) is its own
        # terminal: neither a success nor a failure the flight recorder
        # should retain.
        terminal = ("completed" if reason in ("eos", "length")
                    else "cancelled" if reason == "cancelled"
                    else "failed")
        meta = {"engine": self.name, "priority": prio,
                "finish_reason": reason,
                "completion_tokens": len(seq.generated),
                "prompt_tokens": len(seq.prompt_ids),
                "cached_tokens": seq.cached_len,
                "tenant": seq.req.tenant_id}
        if self._cp.enabled and seq.cp_decode_s > 0:
            # Decode-span attribution for the critical-path split
            # (decode_compute vs decode_stall) — carried on the
            # terminal event so the scrape-time join needs no engine
            # reference.
            meta["decode_device_s"] = round(seq.cp_decode_s, 6)
        if seq.handle.usage is not None:
            # Cost next to latency: the trace/flight-recorder surfaces
            # show this request's attributed usage.
            meta["usage"] = seq.handle.usage
        # Trace timestamps must share the flight recorder's wall-clock
        # timeline (W3C trace alignment), not the engine's injectable
        # clock.  # lint: allow-wallclock
        events.append((terminal, time.time(), meta))
        rec.record_many(seq.req.id, events)

    def _finish(self, seq: _Sequence, reason: str, error: str = "",
                waste_reason: str = "") -> None:
        if seq.prefix_match is not None:
            self._prefix_cache.unlock(seq.prefix_match)
            seq.prefix_match = None
        if seq.pages:
            self.allocator.free(seq.pages)
            seq.pages = []
        if seq.tails:
            self._drop_tails(seq)
        conv = seq.req.conversation_id
        if conv:
            with self._mu:
                if self._conv_busy.get(conv) == seq.order:
                    del self._conv_busy[conv]
                self._conv_drop_pending.discard(conv)
        if seq.usage is not None and self._usage.enabled:
            # Close the attribution: page-seconds from the tracker,
            # prefix-reuse credit from the learned prefill rate, then
            # one ledger finalize — delivered output keeps its device
            # time useful; failures/cancellations reclassify ALL of it
            # as waste (``waste_reason`` pins the cause when the caller
            # knows it, e.g. "crash" from the supervisor's recovery).
            ru = seq.usage
            ru.kv_page_s += self._usage.tracker.close(seq.req.id)
            if seq.cached_len > 0 and self.prefill_tps_ewma:
                ru.saved_prefill_device_s = (
                    seq.cached_len / self.prefill_tps_ewma)
            seq.handle.usage = self._usage.finalize(
                seq.req.id, ru,
                tenant=seq.req.tenant_id,
                priority=seq.req.priority.tier_name,
                engine=self.name,
                conversation=conv,
                tokens=len(seq.generated),
                prompt_tokens=len(seq.prompt_ids),
                ok=reason in ("eos", "length"),
                waste_reason=waste_reason or (
                    "cancelled" if reason == "cancelled" else "error"))
        if self._completion_workers > 0:
            # Engine state is fully released above; the request-facing
            # tail (trace, detok, handle completion) moves off the
            # dispatch path. Ordering: the token flush precedes the
            # finish job on the same request key, so the stream's
            # consumer sees every token before done.
            self._flush_emits(seq)
            self._completion_pool().submit(
                seq.req.id,
                lambda: self._deliver_finish(seq, reason, error))
            return
        self._record_trace(seq, reason)
        res = GenResult(
            text=self.tokenizer.decode(seq.generated),
            tokens=list(seq.generated),
            prompt_tokens=len(seq.prompt_ids),
            cached_tokens=seq.cached_len,
            finish_reason=reason,
            error=error,
            kv_tier=seq.served_tier)
        seq.handle._finish(res)

    def _expire_pins(self) -> None:
        if self.kv_pin_ttl <= 0:
            return
        now = self._clock.now()
        with self._mu:
            stale = [cid for cid, kv in self._conv_cache.items()
                     if now - kv.last_used > self.kv_pin_ttl]
            for cid in stale:
                # Pin TTL only ends HBM *residency priority* — the radix
                # tree keeps the prefix for turn N+1 (evicted there only
                # by LRU/pressure), so no invalidate.
                self._drop_conversation_locked(cid, invalidate=False)
        self._flush_tier_notes()

    def device_identity(self) -> Optional[Dict]:
        """Platform, device kind and device count this engine's
        executor sits on (``/health``, the boot log line); None for a
        device-free backend (echo)."""
        t = self._telemetry
        if not t.platform:
            return None
        return {"platform": t.platform, "kind": t.device_kind,
                "count": t.device_count}

    def _hbm_snapshot(self) -> Dict:
        """HBM accounting for the device-telemetry plane: pool
        occupancy/fragmentation + prefix/pin footprints from the host
        allocator, per-chip byte totals from the executor when it has a
        device (JaxExecutor.hbm_info). Called from the /metrics scrape
        and stats routes — never the step path."""
        alloc = self.allocator
        used, total = alloc.used(), alloc.total
        out: Dict = {
            "kv_pages_used": used,
            "kv_pages_total": total,
            "kv_pool_occupancy": round(used / total, 4) if total else 0.0,
            "kv_pool_fragmentation": alloc.fragmentation(),
            "pinned_pages": alloc.pinned_pages(),
            "prefix_cache_pages": (self._prefix_cache.pages
                                   if self._prefix_cache is not None
                                   else 0),
        }
        if alloc.dp_shards > 1:
            # Mesh path: free pages per dp universe — a replica can be
            # page-starved while the GLOBAL count looks healthy.
            out["kv_pages_free_by_dp_shard"] = alloc.available_by_shard()
        info_fn = getattr(self.executor, "hbm_info", None)
        if info_fn is not None:
            try:
                out["chips"] = info_fn()
            except Exception:  # noqa: BLE001 — accounting, not a gate
                log.exception("hbm_info failed for %s", self.name)
        return out

    def _set_gauges(self) -> None:
        if not self._metrics:
            return
        self._m("kv_pages_in_use").set(
            self.allocator.used())
        self._m("kv_pinned_conversations").set(
            len(self._conv_cache))
        self._m("batch_occupancy").set(
            sum(1 for s in self._slots if s is not None))
        if self._prefix_cache is not None:
            self._m("prefix_cache_pages").set(
                self._prefix_cache.pages)

    # -- stats ---------------------------------------------------------------

    def pending_count(self) -> int:
        """Cheap queue-depth probe (one lock, two lens) for admission
        gates that must not pay the full get_stats() build."""
        with self._mu:
            return len(self._pending) + len(self._inbox)

    def get_stats(self) -> Dict:
        with self._mu:
            pending = len(self._pending) + len(self._inbox)
            cached = len(self._conv_cache)
        out = {
            "name": self.name,
            "slots": self.spec.batch_size,
            "active": sum(1 for s in self._slots if s is not None),
            "pending": pending,
            # DISPATCHES of decode-capable programs, counted on the
            # host (the benchmark reads it as such); the device steps
            # and row-steps they asked for follow, counted at the
            # dispatch too (docs/observability.md "The engine step").
            "decode_steps": self.steps,
            "device_steps": self.device_steps,
            "row_steps": self.row_steps,
            "preemptions": dict(self.preemptions),
            "tokens_generated": self.tokens_generated_total,
            "kv_pages_used": self.allocator.used(),
            "kv_pages_total": self.allocator.total,
            "cached_conversations": cached,
            "stall_events": self.stall_events,
            "stall_ms_total": round(self.stall_ms_total, 1),
            "prefill_stall_events": self.prefill_stall_events,
            "prefill_stall_ms_total": round(self.prefill_stall_ms_total,
                                            1),
            "prefill_tps_ewma": (round(self.prefill_tps_ewma, 1)
                                 if self.prefill_tps_ewma else None),
            "profile": self._prof.summary(),
            # Every long-lived loop of the process (this engine's, the
            # workers', the completion threads'): beats, the running
            # median gap, the longest gap and what it was inside, the
            # overruns, the named waits (docs/observability.md "Loops
            # and stalls").
            "loops": profiling.loops(),
            # Device telemetry plane (docs/observability.md "Device
            # telemetry"): step decomposition, live tok/s + MFU, HBM,
            # compile-cache state.
            "device": self._telemetry.snapshot(),
        }
        if self._window:
            out["window"] = {**self._window, **self.window_counts}
        if self._row_state_bytes:
            out["row_state"] = {
                "rows": self.spec.batch_size,
                "bytes_per_row": self._row_state_bytes,
                "bytes": self._row_state_bytes * self.spec.batch_size,
                "rebuilds": self.row_state_rebuilds,
                "declined": dict(self.row_state_declined)}
            if self._row_tail is not None:
                # Tails (docs/prefix_cache.md): prefix hits adopted,
                # tails copied out of rows, the pool's slots and those
                # that hold one (on a node, or waiting for theirs), and
                # of the tokens the radix walk matched those given up
                # to the tails' grain.
                out["row_state"].update(
                    self.row_tail_counts,
                    tail_slots=self._row_tail["slots"],
                    tail_slots_live=self._prefix_cache.tail_slots_in_use,
                    tail_bytes=self._row_tail["bytes"],
                    tail_stride=self._row_tail["stride"])
        if self._pipe_cfg is not None:
            # Async pipeline (docs/performance.md): occupancy histogram
            # (chunks dispatched at each in-flight depth) + the
            # telemetry's overlap ratio — what bench.py reports as
            # per-rate-point ``point["pipeline"]`` deltas.
            out["pipeline"] = {
                "depth": self._pipe_depth,
                "completion_workers": self._completion_workers,
                "depth_hist": {str(k): v for k, v in
                               sorted(self.pipeline_depth_hist.items())
                               if v},
                # Why fills stopped (``fill_refusals``): "not full"
                # (free_slot) against "could not" (pages, geometry…).
                "fill_refusals": {k: v for k, v in
                                  self.fill_refusals.items() if v},
                "overlap_ratio": self._telemetry.overlap_ratio(),
            }
        if self._mixed_cfg is not None:
            out["mixed_batch"] = {
                "steps": self.mixed_steps,
                "prefill_tokens": self.mixed_prefill_tokens_total,
                # the rows the mixed programs computed for them
                "slice_tokens": self.mixed_slice_tokens_total,
                "prefill_token_budget":
                    int(self._mixed_cfg.prefill_token_budget),
            }
        if self._moe is not None:
            c = self._moe_counts(self._moe)
            load, runs = c["load"], c["runs"]
            out["moe"] = {
                # (token, expert) pairs multiplied HERE, routed-layer
                # runs (steps x routed layers), distinct held experts a
                # run touched on average, the busiest held expert's
                # tokens over the mean's, and the slots that went to
                # zero-compute experts and to experts another chip
                # holds (0 for a family with neither).
                "pairs": int(load.sum()), "layer_runs": runs,
                "experts_touched_mean": (c["touched"] / runs if runs
                                         else 0.0),
                "load_max_over_mean": (float(load.max() / load.mean())
                                       if load.sum() else 0.0),
                "load": load.tolist(),
                "zero_slots": c["zero_slots"],
                "away_slots": c["away_slots"],
            }
            if "hc_row_sum_err" in c:
                # The sites' worst |row sum - 1| x 1e6 a pass, summed
                # over the steps run (``layer_runs`` / the routed layers
                # held of them): 0 would be exact.
                out["moe"]["hc_row_sum_err_sum"] = c["hc_row_sum_err"]
        if self._key_blocks[1]:
            # Key blocks the mixed steps' prefill attention ran over and
            # those their slices' block tables held (one attention's,
            # summed over the mixed chunks committed).
            out["mixed_key_blocks"] = {"visited": int(self._key_blocks[0]),
                                       "table": int(self._key_blocks[1])}
        if self._tiering is not None:
            # Tiered KV plane (docs/tiering.md): residency per tier,
            # hit breakdown incl. recompute, spill/round-trip counts.
            out["kv_tiering"] = self._tiering.stats()
        if self._prefix_cache is not None:
            pc = self._prefix_cache.get_stats()
            total = self.prefix_hits + self.prefix_misses
            pc["admission_hits"] = self.prefix_hits
            pc["admission_misses"] = self.prefix_misses
            pc["admission_hit_rate"] = (
                round(self.prefix_hits / total, 4) if total else 0.0)
            pc["cached_prefill_tokens"] = self.cached_prefill_tokens_total
            pc["shared_pages"] = self.allocator.shared_pages()
            if self._row_state_bytes:
                # A family with row state: the matches declined, and
                # with tails what ``row_state`` says of them.
                pc["declined"] = self.row_state_declined["prefix"]
                if self._row_tail is not None:
                    pc.update(
                        self.row_tail_counts,
                        tail_slots_live=self._prefix_cache.tail_slots_in_use)
            out["prefix_cache"] = pc
        return out
