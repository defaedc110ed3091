"""Store fault domain (llmq_tpu/conversation/resilience.py,
docs/robustness.md "Store fault domain"): bounded deadlines, seeded
retry, the store-scoped breaker + timeout-degraded rung, chaos points
``store.get``/``store.put``/``store.delete``/``store.kv`` compiled
into the real seam, and every consumer's degraded ladder rung:

- wrapper units: deadline → StoreOpTimeout, retry classification
  (sqlite locked / connection resets only), breaker trip → fast
  StoreDegradedError shed → half-open probe → recovery callbacks;
- the timeout-degraded rung for slow-not-dead (brownout) stores —
  timeout-neutral for the breaker, one probe per ``probe_interval_s``;
- state manager: cache-only reads + journaled write-behind while
  degraded, replay buffer bound, drain on recovery;
- tiering: ``_store_ok`` gates spill/promote off a degraded store;
- exchange: publish skips while degraded, claim respects the
  ``claim_ttl_s`` wall budget under injected store latency (the
  promote lane never stalls — recompute instead);
- SqliteStore bounded ``database is locked`` retry (unit + a
  cross-connection 4-thread contention run);
- WAL OSError rung: admission-path faults shed an explicit 503
  (+ Retry-After) through the REST layer, worker-side faults are
  counted + logged and the loop survives;
- /health ``store`` block presence (and absence for raw backends),
  the new metric families, the off-switch;
- acceptance: a store blackout mid-workload on echo AND CPU-JAX
  engines (tiering + exchange enabled, async pipeline depth 2) —
  zero loss/dup, bounded per-request latency while the store is dead,
  store-tier hits resume + the replay buffer drains after recovery.
"""

from __future__ import annotations

import json
import sqlite3
import threading
import time
import urllib.error
import urllib.request

import pytest

from llmq_tpu import chaos
from llmq_tpu.api.server import ApiServer
from llmq_tpu.chaos import InvariantChecker
from llmq_tpu.conversation.persistence import InMemoryStore, SqliteStore
from llmq_tpu.conversation.resilience import (ResilientKVStore,
                                              ResilientStore,
                                              StoreDegradedError,
                                              StoreOpTimeout, _retryable,
                                              wrap_store)
from llmq_tpu.conversation.state_manager import StateManager
from llmq_tpu.core.clock import FakeClock
from llmq_tpu.core.config import (AsyncPipelineConfig, BreakerConfig,
                                  ChaosConfig, ConversationConfig,
                                  KVTieringConfig, PrefixCacheConfig,
                                  StoreResilienceConfig, default_config)
from llmq_tpu.core.errors import ConversationNotFoundError
from llmq_tpu.core.types import Conversation, Message
from llmq_tpu.disagg import KVExchange
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import EchoExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.queueing.queue_manager import QueueManager

pytestmark = [
    pytest.mark.chaos,
    pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"),
]


@pytest.fixture(autouse=True)
def _chaos_reset():
    """Every scenario leaves the process with chaos DISARMED."""
    yield
    chaos.configure(None)


def _arm(seed: int, *rules) -> chaos.FaultInjector:
    inj = chaos.configure(ChaosConfig(enabled=True, seed=seed))
    for r in rules:
        inj.add_rule(**r)
    return inj


def wait_until(fn, timeout=5.0, step=0.002):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if fn():
            return True
        time.sleep(step)
    return False


def _rcfg(**kw) -> StoreResilienceConfig:
    """Test-tuned resilience config: tight deadline, deterministic
    (jitter-free) breaker, sub-second windows."""
    breaker = kw.pop("breaker", None) or BreakerConfig(
        enabled=True, failure_threshold=3, base_backoff=5.0,
        max_backoff=20.0, jitter=0.0)
    base = dict(enabled=True, op_timeout_s=0.05, retries=2,
                retry_base_backoff_s=0.001, retry_max_backoff_s=0.005,
                retry_jitter=0.2, timeout_threshold=2,
                probe_interval_s=10.0, seed=7)
    base.update(kw)
    return StoreResilienceConfig(breaker=breaker, **base)


class ScriptedStore:
    """InMemoryStore front whose next ``fail_times`` ops raise
    ``fail_with(...)`` and whose every op sleeps ``sleep_s`` first —
    a scriptable dead/slow (brownout) backend."""

    def __init__(self):
        self.raw = InMemoryStore()
        self.fail_with = ConnectionError
        self.fail_times = 0
        self.sleep_s = 0.0
        self.calls = []

    def _gate(self, name):
        self.calls.append(name)
        if self.sleep_s:
            time.sleep(self.sleep_s)
        if self.fail_times > 0:
            self.fail_times -= 1
            raise self.fail_with(f"scripted {name} fault")

    def save(self, conv):
        self._gate("save")
        self.raw.save(conv)

    def load(self, cid):
        self._gate("load")
        return self.raw.load(cid)

    def list_user(self, uid):
        self._gate("list_user")
        return self.raw.list_user(uid)

    def delete(self, cid):
        self._gate("delete")
        self.raw.delete(cid)

    def save_kv(self, cid, blob):
        self._gate("save_kv")
        self.raw.save_kv(cid, blob)

    def load_kv(self, cid):
        self._gate("load_kv")
        return self.raw.load_kv(cid)

    def delete_kv(self, cid):
        self._gate("delete_kv")
        self.raw.delete_kv(cid)

    def list_kv(self):
        self._gate("list_kv")
        return self.raw.list_kv()

    def close(self):
        self.raw.close()


def _conv(cid="c1", uid="u1") -> Conversation:
    return Conversation(id=cid, user_id=uid, created_at=1.0,
                        updated_at=1.0, last_active_at=1.0)


def _trip(store, inner, n=3):
    """Drive ``n`` consecutive faults through the wrapper so the
    breaker opens (retries must be 0 in the wrapper's config)."""
    inner.fail_times = n
    for _ in range(n):
        with pytest.raises(ConnectionError):
            store.load_kv("x")
    assert store.degraded


# -- wrapper units -------------------------------------------------------------


class TestWrapStore:
    def test_picks_kv_class_by_feature_detection(self):
        kv = wrap_store(InMemoryStore(), _rcfg())
        assert isinstance(kv, ResilientKVStore)
        assert hasattr(kv, "save_kv")

        class NoKV:
            def save(self, c): pass
            def load(self, cid): return None
            def list_user(self, uid): return []
            def delete(self, cid): pass
            def close(self): pass

        plain = wrap_store(NoKV(), _rcfg())
        assert isinstance(plain, ResilientStore)
        assert not isinstance(plain, ResilientKVStore)
        # Downstream hasattr-based spill detection must keep working.
        assert not hasattr(plain, "save_kv")
        kv.close()
        plain.close()

    def test_roundtrip_is_transparent(self):
        store = wrap_store(InMemoryStore(), _rcfg())
        store.save(_conv("c1"))
        loaded = store.load("c1")
        assert loaded is not None and loaded.id == "c1"
        store.save_kv("c1", b"\x00payload\xff")
        assert store.load_kv("c1") == b"\x00payload\xff"
        assert store.list_kv() == ["c1"]
        store.delete_kv("c1")
        assert store.load_kv("c1") is None
        assert list(store.list_user("u1")) == ["c1"]
        store.delete("c1")
        assert store.load("c1") is None
        assert store.totals["errors"] == 0
        store.close()

    def test_retryable_classification(self):
        assert _retryable(sqlite3.OperationalError("database is locked"))
        assert _retryable(sqlite3.OperationalError("database is busy"))
        assert not _retryable(sqlite3.OperationalError("no such table: x"))
        assert _retryable(ConnectionResetError("reset"))
        assert _retryable(ConnectionError("refused"))
        assert not _retryable(ValueError("nope"))


class TestDeadlineAndRetry:
    def test_deadline_bounds_a_slow_store(self):
        inner = ScriptedStore()
        inner.sleep_s = 0.5
        store = wrap_store(inner, _rcfg(op_timeout_s=0.05))
        t0 = time.perf_counter()
        with pytest.raises(StoreOpTimeout):
            store.load_kv("c1")
        # The caller got out at the deadline, not the backend's pace.
        assert time.perf_counter() - t0 < 0.4
        assert store.totals["timeouts"] == 1
        # Timeout-neutral rule: deadline misses never count as faults.
        assert store.resilience_stats()["breaker"]["state"] == "closed"
        store.close()

    def test_retry_on_sqlite_locked_then_success(self):
        inner = ScriptedStore()
        inner.fail_with = lambda m: sqlite3.OperationalError(
            "database is locked")
        inner.fail_times = 2
        store = wrap_store(inner, _rcfg(retries=2))
        inner.raw.save_kv("c1", b"blob")
        assert store.load_kv("c1") == b"blob"
        assert store.totals["retries"] == 2
        assert store.totals["errors"] == 0
        store.close()

    def test_retry_on_connection_reset(self):
        inner = ScriptedStore()
        inner.fail_with = ConnectionResetError
        inner.fail_times = 1
        store = wrap_store(inner, _rcfg(retries=1))
        store.save_kv("c1", b"x")
        assert inner.raw.load_kv("c1") == b"x"
        assert store.totals["retries"] == 1
        store.close()

    def test_non_retryable_fails_immediately(self):
        inner = ScriptedStore()
        inner.fail_with = ValueError
        inner.fail_times = 5
        store = wrap_store(inner, _rcfg(retries=2))
        with pytest.raises(ValueError):
            store.load_kv("c1")
        assert inner.calls.count("load_kv") == 1   # no retry burned
        assert store.totals["errors"] == 1
        assert store.totals["retries"] == 0
        store.close()

    def test_retries_are_bounded(self):
        inner = ScriptedStore()
        inner.fail_with = lambda m: sqlite3.OperationalError(
            "database is locked")
        inner.fail_times = 100
        store = wrap_store(inner, _rcfg(retries=2))
        with pytest.raises(sqlite3.OperationalError):
            store.load_kv("c1")
        assert store.totals["retries"] == 2        # 1 try + 2 retries
        assert inner.calls.count("load_kv") == 3
        store.close()


class TestBreakerAndDegradedLadder:
    def test_trip_sheds_fast_without_touching_the_backend(self):
        fk = FakeClock()
        inner = ScriptedStore()
        store = wrap_store(inner, _rcfg(retries=0), clock=fk)
        _trip(store, inner)
        dispatched = len(inner.calls)
        t0 = time.perf_counter()
        with pytest.raises(StoreDegradedError):
            store.load_kv("x")
        assert time.perf_counter() - t0 < 0.05     # no round-trip paid
        assert len(inner.calls) == dispatched      # backend never saw it
        assert store.totals["shed"] == 1
        assert store.resilience_stats()["breaker"]["state"] == "open"
        store.close()

    def test_probe_recovers_and_fires_recovery_callbacks(self):
        fk = FakeClock()
        inner = ScriptedStore()
        store = wrap_store(inner, _rcfg(retries=0), clock=fk)
        store.register_consumer("tiering")
        store.register_consumer("nonsense")        # not in the contract
        fired = []
        store.on_recovery(lambda: fired.append(1))
        _trip(store, inner)
        assert fired == []                          # not yet recovered
        fk.advance(6.0)                             # past base_backoff
        assert not store.degraded                   # window elapsed
        inner.raw.save_kv("x", b"back")
        assert store.load_kv("x") == b"back"        # half-open probe wins
        assert fired == [1]
        st = store.resilience_stats()
        assert st["breaker"]["state"] == "closed"
        assert st["consumers"] == ["tiering"]       # closed enum enforced
        assert st["degraded"] is False
        store.close()

    def test_timeout_degraded_rung_probes_on_interval(self):
        fk = FakeClock()
        inner = ScriptedStore()
        inner.sleep_s = 0.2
        store = wrap_store(
            inner, _rcfg(op_timeout_s=0.05, timeout_threshold=2,
                         probe_interval_s=10.0),
            clock=fk)
        for _ in range(2):
            t0 = time.perf_counter()
            with pytest.raises(StoreOpTimeout):
                store.load_kv("c1")
            assert time.perf_counter() - t0 < 0.4   # bounded every time
        assert store.degraded
        assert store.resilience_stats()["timeout_degraded"] is True
        # The breaker stayed closed: timeouts are rung fuel, not faults.
        assert store.resilience_stats()["breaker"]["state"] == "closed"
        # Inside the probe window: shed without dispatching.
        dispatched = len(inner.calls)
        with pytest.raises(StoreDegradedError):
            store.load_kv("c1")
        assert len(inner.calls) == dispatched
        # Past the window the probe goes through; a success clears it.
        fk.advance(11.0)
        inner.sleep_s = 0.0
        inner.raw.save_kv("c1", b"ok")
        assert store.load_kv("c1") == b"ok"
        assert not store.degraded
        store.close()


class TestChaosPoints:
    def test_store_kv_error_fires_in_the_seam(self):
        store = wrap_store(InMemoryStore(), _rcfg())
        _arm(41, {"point": "store.kv", "kind": "error", "times": 1})
        with pytest.raises(chaos.ChaosFault):
            store.load_kv("c1")
        assert store.totals["errors"] == 1
        store.load_kv("c1")                         # rule exhausted
        store.close()

    def test_match_filters_on_op(self):
        store = wrap_store(InMemoryStore(), _rcfg())
        _arm(42, {"point": "store.kv", "kind": "error", "times": 1,
                  "match": {"op": "kv_put"}})
        assert store.load_kv("c1") is None          # kv_get: filtered
        with pytest.raises(chaos.ChaosFault):
            store.save_kv("c1", b"x")
        store.close()

    def test_injected_latency_is_bounded_by_the_deadline(self):
        """The chaos seam fires INSIDE the pool worker, so a 300ms
        injected brownout hits the same 50ms deadline a slow real
        backend would."""
        store = wrap_store(InMemoryStore(), _rcfg(op_timeout_s=0.05))
        _arm(43, {"point": "store.get", "kind": "latency",
                  "latency_ms": 300, "times": 1})
        t0 = time.perf_counter()
        with pytest.raises(StoreOpTimeout):
            store.load("c1")
        assert time.perf_counter() - t0 < 0.25
        store.close()


# -- state manager degraded mode -----------------------------------------------


class TestStateManagerDegraded:
    def _stack(self, **rkw):
        fk = FakeClock()
        inner = ScriptedStore()
        store = wrap_store(inner, _rcfg(retries=0, **rkw), clock=fk)
        sm = StateManager(ConversationConfig(persist=True), store=store)
        return fk, inner, store, sm

    def test_writes_journal_and_reads_serve_cache_while_degraded(self):
        fk, inner, store, sm = self._stack()
        # Three failing saves trip the breaker; each is journaled.
        inner.fail_times = 3
        for i in range(3):
            sm.create("u1", conversation_id=f"c{i}")
        assert store.degraded
        assert sm.replay_pending() == 3
        # A degraded-mode write never pays a store round-trip.
        dispatched = len(inner.calls)
        sm.create("u1", conversation_id="c3")
        assert len(inner.calls) == dispatched
        assert sm.replay_pending() == 4
        # Reads: cached conversations serve, unknown ids fail fast
        # without a store hit.
        assert sm.get("c0").id == "c0"
        with pytest.raises(ConversationNotFoundError):
            sm.get("never-existed")
        assert len(inner.calls) == dispatched
        store.close()

    def test_recovery_drains_the_replay_buffer(self):
        fk, inner, store, sm = self._stack()
        inner.fail_times = 3
        for i in range(3):
            sm.create("u1", conversation_id=f"c{i}")
        assert sm.replay_pending() == 3
        fk.advance(6.0)                            # breaker window over
        sm.create("u1", conversation_id="c3")      # probe save succeeds
        assert sm.replay_pending() == 0
        for i in range(4):
            assert inner.raw.load(f"c{i}") is not None
        store.close()

    def test_replay_buffer_is_bounded(self):
        fk, inner, store, sm = self._stack(replay_buffer=4)
        inner.fail_times = 3
        for i in range(10):
            sm.create("u1", conversation_id=f"c{i}")
        assert store.degraded
        assert sm.replay_pending() == 4            # deque maxlen
        store.close()

    def test_consumers_registered(self):
        _, _, store, sm = self._stack()
        assert set(store.resilience_stats()["consumers"]) == {
            "state", "placement"}
        store.close()


# -- tiering degraded mode -----------------------------------------------------


class TestTieringDegraded:
    def test_store_ok_gates_off_a_degraded_store(self):
        import numpy as np

        from llmq_tpu.tiering import KVTieringPlane

        class _Exec:
            def kv_page_spec(self):
                return [((2, 4, 8), np.dtype(np.float32))]

            def export_kv_pages(self, pages):
                return [np.zeros((2, len(pages), 8), np.float32)]

            def import_kv_pages(self, pages, leaves):
                pass

        fk = FakeClock()
        inner = ScriptedStore()
        # The breaker is what gates here, on the fake clock: the wall
        # deadline of an op is one no stalled worker misses (a miss is
        # a timeout, not the scripted fault, and holds ``degraded`` on
        # a rung the fake clock does not end).
        store = wrap_store(inner, _rcfg(retries=0, op_timeout_s=5.0),
                           clock=fk)
        plane = KVTieringPlane(KVTieringConfig(enabled=True), "p", _Exec())
        plane.store = store
        assert "tiering" in store.resilience_stats()["consumers"]
        assert plane._store_ok()                   # noqa: SLF001
        _trip(store, inner)
        # Degraded: spill/store-promote paths gate off → demotions park
        # in host, store-tier promotes recompute instead of blocking.
        assert not plane._store_ok()               # noqa: SLF001
        fk.advance(6.0)
        assert plane._store_ok()                   # noqa: SLF001
        plane.stop()
        store.close()


# -- exchange degraded mode + claim wall budget (satellite) --------------------


class TestExchangeDegraded:
    def test_publish_skips_while_degraded(self):
        fk = FakeClock()
        inner = ScriptedStore()
        store = wrap_store(inner, _rcfg(retries=0), clock=fk)
        x = KVExchange(store, role="prefill", metrics=False)
        assert "exchange" in store.resilience_stats()["consumers"]
        _trip(store, inner)
        x.publish("c1", [], [], meta={"tokens": [1, 2, 3]})
        assert inner.raw.list_kv() == []           # no round-trip paid
        assert x.totals["fallback"] == 1
        assert x.totals["published"] == 0
        store.close()

    def test_claim_under_injected_latency_degrades_to_recompute(self):
        """The satellite pin: a brownout (injected store latency) at
        claim time must respect the wall budget and fall back to
        recompute — the promote lane never blocks on the store."""
        store = wrap_store(InMemoryStore(), _rcfg(op_timeout_s=0.05))
        x = KVExchange(store, role="decode", claim_ttl_s=2.0,
                       metrics=False)
        x.publish("c1", [], [], meta={"tokens": [1, 2]})
        _arm(51, {"point": "store.kv", "kind": "latency",
                  "latency_ms": 400, "times": 1, "match": {"op": "kv_get"}})
        t0 = time.perf_counter()
        assert x.claim("c1") is None               # recompute, not stall
        assert time.perf_counter() - t0 < 0.35
        assert x.totals["fallback"] == 1
        # The entry survives the shed claim and is consumable after.
        got = x.claim("c1")
        assert got is not None and got[2]["tokens"] == [1, 2]
        store.close()

    def test_claim_wall_budget_on_a_raw_slow_store(self):
        """The belt for raw backends (resilience off): a claim that
        spent longer in the store than claim_ttl_s is dropped."""

        class SlowLoad(InMemoryStore):
            def load_kv(self, cid):
                time.sleep(0.08)
                return super().load_kv(cid)

        raw = SlowLoad()
        x = KVExchange(raw, role="decode", claim_ttl_s=0.05,
                       metrics=False)
        x.publish("c1", [], [], meta={"tokens": [9]})
        assert x.claim("c1") is None
        assert x.totals["fallback"] == 1
        assert raw.list_kv() == []                 # entry deleted


# -- sqlite locked retry (satellite) -------------------------------------------


class TestSqliteLockedRetry:
    def test_locked_retry_unit(self, tmp_path):
        store = SqliteStore(str(tmp_path / "u.db"))
        attempts = {"n": 0}

        def flaky():
            attempts["n"] += 1
            if attempts["n"] <= 2:
                raise sqlite3.OperationalError("database is locked")
            return "ok"

        assert store._with_locked_retry(flaky) == "ok"  # noqa: SLF001
        assert attempts["n"] == 3
        store.close()

    def test_locked_retry_is_bounded_and_selective(self, tmp_path):
        store = SqliteStore(str(tmp_path / "b.db"))
        calls = {"n": 0}

        def always_locked():
            calls["n"] += 1
            raise sqlite3.OperationalError("database is locked")

        with pytest.raises(sqlite3.OperationalError):
            store._with_locked_retry(always_locked)  # noqa: SLF001
        assert calls["n"] == 1 + store._LOCKED_RETRIES  # noqa: SLF001

        def schema_error():
            raise sqlite3.OperationalError("no such table: kv_payloads")

        calls["n"] = 0
        with pytest.raises(sqlite3.OperationalError):
            store._with_locked_retry(schema_error)   # noqa: SLF001
        store.close()

    def test_cross_connection_contention_four_threads(self, tmp_path):
        """Two independent connections (separate SqliteStore instances
        over one file) hammered by 4 threads: the busy_timeout + the
        bounded locked-retry must absorb every lock race — no
        OperationalError escapes, every write readable."""
        path = str(tmp_path / "cont.db")
        stores = [SqliteStore(path), SqliteStore(path)]
        errors = []
        stop = threading.Event()

        def worker(wid):
            st = stores[wid % 2]
            try:
                for i in range(60):
                    cid = f"w{wid}-{i % 5}"
                    st.save_kv(cid, bytes([wid]) * 1024)
                    blob = st.load_kv(cid)
                    assert blob is None or blob[:1] == bytes([wid])
                    if i % 9 == 0:
                        st.delete_kv(cid)
                    if stop.is_set():
                        return
            except Exception as e:  # noqa: BLE001 — collected for assert
                errors.append(e)
                stop.set()

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        for st in stores:
            st.close()


# -- WAL OSError rung (satellite) ----------------------------------------------


class _Client:
    def __init__(self, port):
        self.base = f"http://127.0.0.1:{port}"

    def post(self, path, body):
        req = urllib.request.Request(
            self.base + path, data=json.dumps(body).encode(),
            method="POST", headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return resp.status, json.loads(resp.read()), dict(
                    resp.headers)
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read()), dict(e.headers)


class TestWalShed:
    def test_admission_path_fault_sheds_503_with_retry_after(
            self, tmp_path):
        """An ENOSPC-shaped WAL append fault on push must surface as an
        explicit 503 + Retry-After at the REST edge — the at-least-once
        promise is refused, not silently broken — and the stack keeps
        serving afterwards."""
        from llmq_tpu.queueing.factory import QueueFactory, QueueType

        cfg = default_config()
        cfg.queue.enable_metrics = False
        cfg.queue.worker.process_interval = 0.005
        cfg.loadbalancer.health_check_interval = 0.0
        cfg.queue.wal_dir = str(tmp_path)
        tok = ByteTokenizer()
        engine = InferenceEngine(
            EchoExecutor(batch_size=4, eos_id=tok.eos_id), tok,
            name="walshed", enable_metrics=False, max_decode_steps=16)
        engine.start()
        factory = QueueFactory(cfg)
        factory.create_queue_manager("standard", QueueType.STANDARD)
        server = ApiServer(cfg, queue_factory=factory, engine=engine)
        port = server.start(host="127.0.0.1", port=0)
        client = _Client(port)
        try:
            _arm(61, {"point": "wal.append", "kind": "oserror",
                      "times": 1, "match": {"op": "push"}})
            status, payload, hdrs = client.post(
                "/api/v1/messages",
                {"id": "wal0", "content": "x", "user_id": "u"})
            assert status == 503
            assert "WAL push failed" in payload["error"]
            assert payload["retry_after"] == 1.0
            assert hdrs.get("Retry-After") is not None
            # Rule exhausted: the next push is admitted normally.
            status, _, _ = client.post(
                "/api/v1/messages",
                {"id": "wal1", "content": "x", "user_id": "u"})
            assert status in (200, 202)
        finally:
            server.stop()
            factory.stop_all()
            engine.stop()

    def test_worker_side_fault_is_counted_and_loop_survives(
            self, tmp_path):
        """A WAL OSError on a worker-side op (complete) must NOT kill
        the worker loop: the op is counted in wal_errors_total{op},
        logged loudly, and processing continues (at-least-once replay
        covers the durability gap)."""
        from llmq_tpu.metrics.registry import exposition

        mgr = QueueManager("walstore",
                           wal_path=str(tmp_path / "w.wal"))
        _arm(62, {"point": "wal.append", "kind": "oserror", "times": 1,
                  "match": {"op": "complete"}})
        qname = mgr.push_message(Message(id="m0", content="x",
                                         user_id="u"))
        msg = mgr.pop_message(qname)
        mgr.complete_message(msg, 0.0, qname)       # fault swallowed
        assert mgr.total_pending() == 0
        # The manager is still fully functional after the fault.
        qname = mgr.push_message(Message(id="m1", content="x",
                                         user_id="u"))
        msg = mgr.pop_message(qname)
        mgr.complete_message(msg, 0.0, qname)
        mgr.stop()
        assert b'wal_errors_total{op="complete"} 1' in exposition()


# -- /health block + metric families + off-switch ------------------------------


class TestHealthAndMetrics:
    def _server(self, sm):
        from llmq_tpu.queueing.factory import QueueFactory, QueueType

        cfg = default_config()
        cfg.queue.enable_metrics = False
        cfg.loadbalancer.health_check_interval = 0.0
        tok = ByteTokenizer()
        engine = InferenceEngine(
            EchoExecutor(batch_size=2, eos_id=tok.eos_id), tok,
            name="storehealth", enable_metrics=False)
        engine.start()
        factory = QueueFactory(cfg)
        factory.create_queue_manager("standard", QueueType.STANDARD)
        server = ApiServer(cfg, queue_factory=factory, engine=engine,
                           state_manager=sm)
        return server, factory, engine

    def test_health_carries_store_block_when_wrapped(self):
        store = wrap_store(InMemoryStore(), _rcfg())
        sm = StateManager(ConversationConfig(persist=True), store=store)
        server, factory, engine = self._server(sm)
        port = server.start(host="127.0.0.1", port=0)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/health")
            with urllib.request.urlopen(req, timeout=10) as resp:
                body = json.loads(resp.read())
            blk = body["store"]
            assert blk["resilience"] is True
            assert blk["degraded"] is False
            assert blk["replay_pending"] == 0
            assert set(blk["consumers"]) == {"state", "placement"}
            assert blk["breaker"]["state"] == "closed"
        finally:
            server.stop()
            factory.stop_all()
            engine.stop()
            store.close()

    def test_raw_backend_has_no_store_block(self):
        """Off-switch shape: with resilience disabled nothing is
        wrapped and pre-feature health bodies stay byte-identical."""
        cfg = default_config()
        assert cfg.store.resilience.enabled is False
        assert cfg.store.enabled is False
        raw = InMemoryStore()
        assert not hasattr(raw, "degraded")
        assert not hasattr(raw, "resilience_stats")
        sm = StateManager(ConversationConfig(persist=True), store=raw)
        assert sm._store_degraded() is False        # noqa: SLF001
        server, factory, engine = self._server(sm)
        try:
            assert server._store_block() is None    # noqa: SLF001
        finally:
            factory.stop_all()
            engine.stop()

    def test_new_metric_families_flush_at_scrape(self):
        from llmq_tpu.metrics.registry import exposition

        store = wrap_store(InMemoryStore(), _rcfg())
        store.register_consumer("exchange")
        store.save_kv("c1", b"x")
        assert store.load_kv("c1") == b"x"
        text = exposition()
        assert b"store_op_ms" in text
        assert b'store_op_ms_count{op="kv_put",outcome="ok"}' in text
        assert b"store_retries_total" in text
        assert b"store_breaker_state 0.0" in text
        assert b'store_degraded{consumer="exchange"} 0.0' in text
        # The buffer drained: totals persist, samples do not re-emit.
        assert store.totals["ops"] == 2
        store.close()

    def test_a_closed_store_leaves_the_gauges_to_the_live_one(self):
        """A store that was closed with its breaker open and is not
        collected yet still flushes at the scrape (its last samples
        count), but its breaker and health are no longer anybody's
        state: the live store's gauges stand, whichever flushes last."""
        from llmq_tpu.metrics.registry import get_metrics

        dead = wrap_store(InMemoryStore(), _rcfg())
        dead.register_consumer("exchange")
        for _ in range(3):
            dead._breaker.record_failure()          # noqa: SLF001
        assert dead.resilience_stats()["breaker"]["state"] == "open"
        dead.close()
        live = wrap_store(InMemoryStore(), _rcfg())
        live.register_consumer("exchange")
        live.flush_metrics()
        dead.flush_metrics()            # the weak set's order is free
        m = get_metrics()
        assert m.store_breaker_state._value.get() == 0.0  # noqa: SLF001
        assert m.store_degraded.labels(
            consumer="exchange")._value.get() == 0.0      # noqa: SLF001
        live.close()


# -- acceptance: blackout mid-workload -----------------------------------------


def _accept_rcfg(seed=11) -> StoreResilienceConfig:
    """Acceptance tuning: real-clock breaker with sub-second backoff so
    recovery happens inside the test's wall budget."""
    return StoreResilienceConfig(
        enabled=True, op_timeout_s=0.25, retries=1,
        retry_base_backoff_s=0.001, retry_max_backoff_s=0.005,
        timeout_threshold=3, probe_interval_s=0.05, seed=seed,
        breaker=BreakerConfig(enabled=True, failure_threshold=3,
                              base_backoff=0.15, max_backoff=0.6,
                              jitter=0.0))


def _turn(eng, sm, checker, rid, conv, prompt, budget_s=4.0):
    """One closed-loop turn through the real submit path: invariant
    tracking + the service layer's state write + a hard wall bound (a
    dead store must never stall the hot path past its deadline)."""
    checker.submitted(rid)
    sm.add_message(conv, Message(id=rid, content=prompt, user_id="u"))
    t0 = time.perf_counter()
    h = eng.submit(GenRequest(id=rid, prompt=prompt,
                              conversation_id=conv, max_new_tokens=8))
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    assert h.result is not None and h.result.finish_reason in (
        "eos", "length"), rid
    assert wall < budget_s, (
        f"{rid} took {wall:.2f}s with the store dead — hot path stalled")
    checker.completed(rid, tokens=h.result.tokens)
    return h


class TestStoreBlackoutAcceptance:
    def test_echo_engine_blackout_recovery(self):
        """The tentpole acceptance bar on the echo engine: tiering +
        exchange + state manager over ONE wrapped store, async pipeline
        depth 2; a store blackout mid-workload sheds to the degraded
        ladder (bounded latency, zero loss), and after the store comes
        back store-tier hits resume and the replay buffer drains."""
        store = wrap_store(InMemoryStore(), _accept_rcfg())
        sm = StateManager(ConversationConfig(persist=True), store=store)
        tok = ByteTokenizer()
        fclock = FakeClock()
        eng = InferenceEngine(
            EchoExecutor(batch_size=4, page_size=8, num_pages=128,
                         max_pages_per_seq=16, eos_id=tok.eos_id,
                         chunk_size=4),
            tok, name="storechaos-echo", enable_metrics=False,
            kv_pin_ttl=5.0, clock=fclock,
            kv_tiering=KVTieringConfig(enabled=True, host_capacity_mb=4,
                                       host_max_conversations=16,
                                       store_spill=True),
            prefix_cache=PrefixCacheConfig(enabled=True),
            async_pipeline=AsyncPipelineConfig(enabled=True, depth=2))
        eng.attach_conversation_manager(sm)
        x = KVExchange(store, role="unified", metrics=False)
        eng._tiering.exchange = x                   # noqa: SLF001
        checker = InvariantChecker()
        convs = [f"bc{i}" for i in range(4)]
        try:
            # Warm phase: a turn per conversation, then demote to the
            # host tier (echo is content-free — real store-tier spill
            # payloads are the JAX leg's job; here the store carries
            # state saves + the exchange).
            for i, c in enumerate(convs):
                _turn(eng, sm, checker, f"{c}.t1", c, f"warm {i} text")
            fclock.advance(6.0)
            eng.step()
            plane = eng._tiering                    # noqa: SLF001
            assert wait_until(
                lambda: sum(plane.counts().values()) == len(convs))
            _turn(eng, sm, checker, f"{convs[0]}.t2", convs[0], " more")
            pre = eng.get_stats()["kv_tiering"]["hits"]
            assert pre["host"] >= 1

            # Blackout: every store-backed plane faults at once. Every
            # turn must still complete inside its wall budget.
            _arm(71, {"point": "store.*", "kind": "error", "times": 500})
            for i, c in enumerate(convs):
                _turn(eng, sm, checker, f"{c}.t3", c, f" blackout {i}")
            for i in range(4, 8):                   # fresh arrivals too
                _turn(eng, sm, checker, f"bc{i}.t1", f"bc{i}",
                      f"new {i} during blackout")
            st = store.resilience_stats()
            assert st["breaker"]["trips"] >= 1      # breaker tripped
            assert store.totals["errors"] >= 3
            assert store.totals["shed"] > 0         # fast-fail, not hang
            assert sm.replay_pending() > 0          # writes journaled

            # Store comes back: breaker probes within its sub-second
            # backoff, recovery drains the journal.
            chaos.configure(None)
            assert wait_until(lambda: not store.degraded, timeout=5.0)
            _turn(eng, sm, checker, f"{convs[1]}.t4", convs[1], " back")
            assert wait_until(lambda: sm.replay_pending() == 0,
                              timeout=5.0)
            for c in convs:
                assert store.inner.load(c) is not None

            # Store round-trips resume: a publish→claim handoff lands
            # through the recovered store, and host-tier promotes keep
            # serving.
            x.publish("hand", [], [], meta={"tokens": [1, 2]})
            got = x.claim("hand")
            assert got is not None and got[2]["tokens"] == [1, 2]
            fclock.advance(6.0)
            eng.step()
            assert wait_until(
                lambda: sum(plane.counts().values()) >= len(convs))
            hits0 = eng.get_stats()["kv_tiering"]["hits"]["host"]
            _turn(eng, sm, checker, f"{convs[2]}.t5", convs[2], " again")
            assert eng.get_stats()["kv_tiering"]["hits"]["host"] > hits0
            checker.check()                         # zero loss/dup
        finally:
            eng.stop()
            store.close()

    def test_jax_engine_blackout_matches_baseline(self):
        """CPU-JAX leg: a conversation whose KV sat in the STORE tier
        decodes its next turn during a blackout token-for-token equal
        to a pin-resident baseline — recompute-on-promote, bounded,
        zero loss — and the plane recovers after."""
        import jax

        from llmq_tpu.engine.executor import JaxExecutor
        from llmq_tpu.models.llama import init_params, llama3_tiny

        mcfg = llama3_tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                           ffn_dim=128, vocab_size=512, max_seq_len=256)
        params = init_params(jax.random.PRNGKey(0), mcfg)

        def build(tiering, store):
            tok = ByteTokenizer()
            ex = JaxExecutor(mcfg, params, batch_size=2, page_size=8,
                             num_pages=64, prefill_buckets=[16, 64],
                             eos_id=tok.eos_id, chunk_size=4)
            fclock = FakeClock()
            eng = InferenceEngine(
                ex, tok, name="storechaos-jax", enable_metrics=False,
                max_decode_steps=12, clock=fclock, kv_pin_ttl=5.0,
                kv_tiering=tiering,
                async_pipeline=AsyncPipelineConfig(enabled=True,
                                                   depth=2))
            if store is not None and eng._tiering is not None:
                eng._tiering.store = store          # noqa: SLF001
            return eng, fclock

        prompts = {"j0": ("the quick brown fox", " jumps over"),
                   "j1": ("a slow green turtle", " crawls by")}

        # Baseline: pin-resident, no tiering, no store.
        eng, _ = build(None, None)
        base = {}
        for c, (p1, p2) in prompts.items():
            h1 = eng.submit(GenRequest(id=f"{c}.b1", prompt=p1,
                                       conversation_id=c,
                                       max_new_tokens=8))
            eng.run_until_idle()
            h2 = eng.submit(GenRequest(id=f"{c}.b2", prompt=p2,
                                       conversation_id=c,
                                       max_new_tokens=8))
            eng.run_until_idle()
            base[c] = (h1.result.tokens, h2.result.tokens)
        eng.stop()
        assert all(t1 and t2 for t1, t2 in base.values())

        # Chaos leg: tiering over a wrapped store, one conversation
        # forced to the store tier, blackout during its second turn.
        store = wrap_store(InMemoryStore(), _accept_rcfg(seed=12))
        checker = InvariantChecker()
        eng, fclock = build(
            KVTieringConfig(enabled=True, host_max_conversations=1,
                            store_spill=True), store)
        sm = StateManager(ConversationConfig(persist=True), store=store)
        eng.attach_conversation_manager(sm)
        plane = eng._tiering                        # noqa: SLF001
        try:
            out = {}
            for c, (p1, _) in prompts.items():
                # Warm turns pay one-time JAX compile; only the
                # blackout turns below hold the strict wall budget.
                h = _turn(eng, sm, checker, f"{c}.t1", c, p1,
                          budget_s=60.0)
                out[c] = [h.result.tokens]
            fclock.advance(6.0)
            eng.step()

            def spilled():
                """Both demoted, and the first spilled to the store
                tier when the second claimed the single host slot (on
                the plane's worker: the tier, not the entries alone,
                is what the asserts below rest on)."""
                return plane.counts() == {"host": 1, "store": 1,
                                          "recompute": 0}

            assert wait_until(spilled, timeout=30.0)
            assert store.totals["ops"] > 0

            _arm(72, {"point": "store.*", "kind": "error", "times": 200})
            for c, (_, p2) in prompts.items():
                h = _turn(eng, sm, checker, f"{c}.t2", c, p2)
                out[c].append(h.result.tokens)
            assert store.resilience_stats()["breaker"]["trips"] >= 1
            assert store.totals["errors"] > 0
            # Recompute-on-promote is CORRECT: token-for-token equal to
            # the pin-resident baseline even with the store dead.
            for c in prompts:
                assert (out[c][0], out[c][1]) == base[c], c

            chaos.configure(None)

            def recovered():
                """A probe that succeeds fires the recovery; one shed
                (the breaker's window, or the timeout rung a stalled
                worker set) or late is asked again."""
                try:
                    store.load("j0")
                except Exception:  # noqa: BLE001 — shed / deadline
                    return False
                return not store.degraded

            assert wait_until(recovered, timeout=30.0, step=0.02)
            assert wait_until(lambda: sm.replay_pending() == 0,
                              timeout=30.0)

            # Store tier resumes: demote again against the healthy
            # store, and the next promote comes back as a STORE hit.
            fclock.advance(6.0)
            eng.step()
            assert wait_until(spilled, timeout=30.0)
            for c, (p1, _) in prompts.items():
                _turn(eng, sm, checker, f"{c}.t3", c, p1,
                      budget_s=60.0)
            assert eng.get_stats()["kv_tiering"]["hits"]["store"] >= 1
            checker.check()
        finally:
            eng.stop()
            store.close()
