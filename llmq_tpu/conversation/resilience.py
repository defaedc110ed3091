"""Store fault domain: bounded deadlines, retry, breaker, brownout ladder.

Since the tiering spill (docs/tiering.md), the disagg KV exchange
(docs/disaggregation.md), placement records and restart rehydration all
ride the conversation store, a stalled or dead sqlite/redis backend can
block hot paths that were designed to degrade, not hang. This module
wraps any ``ConversationStore`` / ``KVPayloadStore`` backend in a
decorator that makes every store call **bounded and classifiable**:

- **Per-op wall deadline** (``store.resilience.op_timeout_s``): each op
  runs on a small dedicated thread pool and the caller waits at most
  the deadline — a dead OR slow (brownout) store can never hold a
  promote lane, a publish, or a conversation load longer than the
  budget. Deadline misses surface as :class:`StoreOpTimeout`.
- **Seeded jittered-exponential retry** for retryable errors only —
  sqlite ``database is locked`` and redis connection resets. Bounded by
  ``retries``; everything else fails immediately.
- **Store-scoped circuit breaker** (the PR 5 core, reused verbatim):
  consecutive FAULTS trip it OPEN, deadline misses never count
  (timeout-neutral rule), one half-open probe per backoff window.
  Because slow-not-dead stores would otherwise never trip anything,
  ``timeout_threshold`` consecutive deadline misses flip a parallel
  **timeout-degraded** rung that admits one probe op per
  ``probe_interval_s`` and sheds the rest via
  :class:`StoreDegradedError`.
- **Chaos points** ``store.get`` / ``store.put`` / ``store.delete`` /
  ``store.kv`` are compiled into the real seam (fired inside the
  worker thread so injected *latency* is bounded by the deadline too,
  exactly like a slow real backend).
- **Degraded-mode contract**: consumers never see a hang — they see a
  fast exception and take their config-declared ladder rung (tiering
  parks demotions in host + recompute-on-promote, exchange skips
  publish / claims recompute, state manager serves its in-memory cache
  and journals writes to a bounded replay buffer, placement falls back
  to role/load-only routing). Recovery callbacks fire on the first
  confirmed success after a degraded stretch so journals drain.

Telemetry is buffered and flushed at scrape time
(``flush_metrics`` ← metrics/registry.exposition), the same
discipline as the tiering/disagg planes: ``store_op_ms{op,outcome}``,
``store_retries_total``, ``store_breaker_state``,
``store_degraded{consumer}``.

Off-switch: ``store.resilience.enabled=false`` (default) — ``wrap_store``
is simply never called and the raw backend is byte-identical to today.
"""

from __future__ import annotations

import concurrent.futures
import random
import sqlite3
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from llmq_tpu import chaos
from llmq_tpu.core.clock import SYSTEM_CLOCK, Clock
from llmq_tpu.core.config import StoreResilienceConfig
from llmq_tpu.loadbalancer.circuit_breaker import (STATE_VALUE,
                                                   CircuitBreaker)
from llmq_tpu.utils.logging import get_logger

log = get_logger("store.resilience")

#: Live wrappers, for scrape-time flush (mirrors tiering._PLANES).
_STORES: "weakref.WeakSet[ResilientStore]" = weakref.WeakSet()

#: Consumers that may register for the store_degraded gauge — must stay
#: in lockstep with LABEL_CONTRACT["consumer"].
CONSUMERS = ("tiering", "exchange", "state", "placement")


class StoreDegradedError(RuntimeError):
    """Shed fast: the store is degraded (breaker OPEN or repeated
    deadline misses) and this op did not win the probe slot."""


class StoreOpTimeout(TimeoutError):
    """The op missed its per-op wall deadline (dead or slow store)."""


def _retryable(exc: BaseException) -> bool:
    """Only transient contention/connection blips are worth a retry —
    a missing table or a typed failure retried is just a slower
    failure."""
    if isinstance(exc, sqlite3.OperationalError):
        msg = str(exc).lower()
        return "locked" in msg or "busy" in msg
    if isinstance(exc, (ConnectionError, ConnectionResetError)):
        return True                        # redis connect resets
    return False


class ResilientStore:
    """Decorator over a ``ConversationStore`` backend. Wrap KV-capable
    backends with :class:`ResilientKVStore` (via :func:`wrap_store`) so
    ``hasattr(store, "save_kv")`` feature detection keeps working."""

    def __init__(self, inner: Any, config: Optional[StoreResilienceConfig]
                 = None, *, clock: Optional[Clock] = None) -> None:
        cfg = config or StoreResilienceConfig(enabled=True)
        self.inner = inner
        self.config = cfg
        self._clock = clock or SYSTEM_CLOCK
        self._mu = threading.Lock()
        self._rng = random.Random(cfg.seed)
        bcfg = cfg.breaker
        #: metrics=None on purpose: the endpoint-breaker families stay
        #: clean; the store layer emits store_breaker_state itself.
        self._breaker: Optional[CircuitBreaker] = None
        if getattr(bcfg, "enabled", True):
            self._breaker = CircuitBreaker(
                "store",
                failure_threshold=getattr(bcfg, "failure_threshold", 3),
                base_backoff=getattr(bcfg, "base_backoff", 1.0),
                max_backoff=getattr(bcfg, "max_backoff", 30.0),
                jitter=getattr(bcfg, "jitter", 0.2),
                clock=self._clock, seed=cfg.seed, metrics=None)
        #: One small pool bounds EVERY op (including chaos latency);
        #: pool exhaustion under a wedged backend surfaces as deadline
        #: misses, which is exactly the truth.
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="store-res")
        self._closed = False
        # Timeout-degraded rung (breaker is timeout-neutral).
        self._consec_timeouts = 0
        self._timeout_degraded = False
        self._next_probe = 0.0
        self._was_degraded = False
        self._consumers: set = set()
        self._recovery_cbs: List[Callable[[], None]] = []
        # Buffered telemetry, drained at scrape.
        self._op_samples: List[Tuple[str, str, float]] = []
        self._retries_delta = 0
        self.totals: Dict[str, int] = {
            "ops": 0, "errors": 0, "timeouts": 0, "retries": 0,
            "shed": 0}
        _STORES.add(self)

    # -- consumer / recovery registry ------------------------------------

    def register_consumer(self, name: str) -> None:
        """Duck-typed: consumers call this if present so the
        ``store_degraded{consumer}`` gauge reports exactly the planes
        actually riding this store."""
        if name in CONSUMERS:
            with self._mu:
                self._consumers.add(name)

    def on_recovery(self, cb: Callable[[], None]) -> None:
        """Fired (no lock held) on the first confirmed success after a
        degraded stretch — the state manager drains its replay buffer
        here."""
        self._recovery_cbs.append(cb)

    # -- degraded-state machine ------------------------------------------

    @property
    def degraded(self) -> bool:
        """Fast check for consumers choosing a ladder rung *before*
        paying for an op. True while the breaker holds the store out of
        rotation or the timeout rung is active."""
        br = self._breaker
        if br is not None and br.blocked():
            return True
        return self._timeout_degraded

    def _admit(self, op: str) -> None:
        br = self._breaker
        if br is not None and not br.allow():
            self._note(op, "shed", 0.0)
            raise StoreDegradedError(
                f"store breaker open ({op}); retry in {br.retry_in():.2f}s")
        if self._timeout_degraded:
            now = self._clock.now()
            with self._mu:
                if now < self._next_probe:
                    probe = False
                else:
                    self._next_probe = now + max(
                        0.0, self.config.probe_interval_s)
                    probe = True
            if not probe:
                # Give the breaker its probe slot back — this call
                # never dispatched.
                if br is not None:
                    br.record_timeout()
                self._note(op, "shed", 0.0)
                raise StoreDegradedError(
                    f"store timeout-degraded ({op}); probe pending")

    def _note(self, op: str, outcome: str, ms: float) -> None:
        with self._mu:
            self.totals["ops"] += 1
            if outcome == "error":
                self.totals["errors"] += 1
            elif outcome == "timeout":
                self.totals["timeouts"] += 1
            elif outcome == "shed":
                self.totals["shed"] += 1
            if len(self._op_samples) < 10_000:
                self._op_samples.append((op, outcome, ms))

    def _on_success(self, op: str, t0: float) -> None:
        if self._breaker is not None:
            self._breaker.record_success()
        fire: List[Callable[[], None]] = []
        with self._mu:
            self._consec_timeouts = 0
            self._timeout_degraded = False
        if self._was_degraded and not self.degraded:
            self._was_degraded = False
            fire = list(self._recovery_cbs)
            log.info("store recovered: resuming store-tier traffic")
        self._note(op, "ok", (self._clock.now() - t0) * 1e3)
        for cb in fire:
            try:
                cb()
            except Exception:  # noqa: BLE001 — recovery is best-effort
                log.exception("store recovery callback failed")

    def _on_timeout(self, op: str, t0: float) -> None:
        if self._breaker is not None:
            self._breaker.record_timeout()   # neutral: no fault counted
        with self._mu:
            self._consec_timeouts += 1
            if (self._consec_timeouts >= max(1, self.config.timeout_threshold)
                    and not self._timeout_degraded):
                self._timeout_degraded = True
                self._next_probe = self._clock.now() + max(
                    0.0, self.config.probe_interval_s)
                log.error(
                    "store timeout-degraded: %d consecutive ops missed the "
                    "%.0fms deadline; consumers fall back (host-tier parks, "
                    "recompute, cache-only history)", self._consec_timeouts,
                    self.config.op_timeout_s * 1e3)
        self._was_degraded = self._was_degraded or self.degraded
        self._note(op, "timeout", (self._clock.now() - t0) * 1e3)

    def _on_failure(self, op: str, t0: float, exc: BaseException) -> None:
        if self._breaker is not None:
            self._breaker.record_failure()
        self._was_degraded = self._was_degraded or self.degraded
        log.warning("store.%s failed: %s", op, exc)
        self._note(op, "error", (self._clock.now() - t0) * 1e3)

    # -- bounded dispatch -------------------------------------------------

    def _run(self, point: str, op: str, fn: Callable[[], Any]) -> Any:
        """Executes in the pool worker: the chaos seam fires HERE so an
        injected 200ms brownout is bounded by the same deadline a slow
        real backend is."""
        chaos.fault(point, op=op)
        return fn()

    def _call(self, op: str, point: str, fn: Callable[[], Any]) -> Any:
        self._admit(op)
        t0 = self._clock.now()
        cfg = self.config
        attempt = 0
        while True:
            if self._closed:
                raise StoreDegradedError("store closed")
            try:
                fut = self._pool.submit(self._run, point, op, fn)
            except RuntimeError as e:       # pool shut down under us
                raise StoreDegradedError("store closed") from e
            try:
                result = fut.result(timeout=max(0.001, cfg.op_timeout_s))
            except (TimeoutError, concurrent.futures.TimeoutError) as e:
                # Deadline miss, ChaosTimeout or ChaosPartialResponse:
                # one rung — timeout-neutral for the breaker, counted
                # toward the timeout-degraded ladder. (On 3.11+ the two
                # classes are the same alias; on older runtimes they
                # are distinct — catch both.)
                fut.cancel()
                self._on_timeout(op, t0)
                raise StoreOpTimeout(
                    f"store.{op} exceeded the "
                    f"{cfg.op_timeout_s * 1e3:.0f}ms op deadline") from e
            except Exception as e:
                if attempt < max(0, cfg.retries) and _retryable(e):
                    attempt += 1
                    with self._mu:
                        self.totals["retries"] += 1
                        self._retries_delta += 1
                        backoff = min(
                            cfg.retry_max_backoff_s,
                            cfg.retry_base_backoff_s * (2 ** (attempt - 1)))
                        backoff *= 1.0 + cfg.retry_jitter * (
                            2.0 * self._rng.random() - 1.0)
                    time.sleep(max(0.0, backoff))  # lint: allow-wallclock
                    continue
                self._on_failure(op, t0, e)
                raise
            else:
                self._on_success(op, t0)
                return result

    # -- ConversationStore surface ----------------------------------------

    def save(self, conversation) -> None:
        return self._call("put", "store.put",
                          lambda: self.inner.save(conversation))

    def load(self, conversation_id: str):
        return self._call("get", "store.get",
                          lambda: self.inner.load(conversation_id))

    def list_user(self, user_id: str):
        return self._call("list", "store.get",
                          lambda: self.inner.list_user(user_id))

    def delete(self, conversation_id: str) -> None:
        return self._call("delete", "store.delete",
                          lambda: self.inner.delete(conversation_id))

    def close(self) -> None:
        self._closed = True
        self._pool.shutdown(wait=False)
        self.inner.close()

    # -- introspection ----------------------------------------------------

    def resilience_stats(self) -> Dict[str, Any]:
        """The /health + cluster-overview ``store`` block."""
        with self._mu:
            totals = dict(self.totals)
            consumers = sorted(self._consumers)
        out: Dict[str, Any] = {
            "resilience": True,
            "degraded": self.degraded,
            "timeout_degraded": self._timeout_degraded,
            "consumers": consumers,
            **totals,
        }
        if self._breaker is not None:
            out["breaker"] = self._breaker.get_stats()
        return out

    def flush_metrics(self) -> None:
        """Scrape-time drain (registry.exposition) — ops never touch a
        label child."""
        from llmq_tpu.metrics.registry import get_metrics
        m = get_metrics()
        if m is None:
            return
        with self._mu:
            samples, self._op_samples = self._op_samples, []
            retries, self._retries_delta = self._retries_delta, 0
            consumers = sorted(self._consumers)
        for op, outcome, ms in samples:
            m.store_op_ms.labels(op=op, outcome=outcome).observe(ms)
        if retries:
            m.store_retries.inc(retries)
        if self._closed:
            # Closed but not collected yet: its last samples count, its
            # breaker and health must not overwrite a live store's.
            return
        if self._breaker is not None:
            m.store_breaker_state.set(
                float(STATE_VALUE[self._breaker.state]))
        degraded = 1.0 if self.degraded else 0.0
        for c in consumers:
            m.store_degraded.labels(consumer=c).set(degraded)


class ResilientKVStore(ResilientStore):
    """KV-payload-capable variant: adds the ``KVPayloadStore`` surface
    so tiering spill / the KV exchange feature-detect it exactly as
    they do the raw backend."""

    def save_kv(self, conversation_id: str, blob: bytes) -> None:
        return self._call("kv_put", "store.kv",
                          lambda: self.inner.save_kv(conversation_id, blob))

    def load_kv(self, conversation_id: str):
        return self._call("kv_get", "store.kv",
                          lambda: self.inner.load_kv(conversation_id))

    def delete_kv(self, conversation_id: str) -> None:
        return self._call("kv_delete", "store.kv",
                          lambda: self.inner.delete_kv(conversation_id))

    def list_kv(self):
        return self._call("kv_list", "store.kv",
                          lambda: self.inner.list_kv())


def wrap_store(inner: Any, config: Optional[StoreResilienceConfig] = None,
               *, clock: Optional[Clock] = None) -> ResilientStore:
    """Wrap ``inner`` preserving its KV capability (hasattr-based
    feature detection downstream keeps working)."""
    cls = ResilientKVStore if hasattr(inner, "save_kv") else ResilientStore
    return cls(inner, config, clock=clock)


def flush_metrics() -> None:
    """Module-level scrape hook (metrics/registry.exposition)."""
    for store in list(_STORES):
        store.flush_metrics()
