"""Tiered KV plane (llmq_tpu/tiering/, docs/tiering.md): HBM →
host-DRAM → store hierarchy under the prefix cache and conversation
pins — host-pool/codec units, the plane's demote/promote/spill/
recompute state machine, the prefix-cache demotion seam, the sqlite
spill-store hardening, prefix-handle tier semantics, engine
integration on echo AND CPU-mode JAX (token-for-token equivalence per
tier, off-switch byte-equivalence), async-pipeline interplay, usage
billing at demotion, and the new metric families."""

import threading
import time

import jax
import numpy as np
import pytest

from llmq_tpu.core.clock import FakeClock
from llmq_tpu.core.config import (ConversationConfig, KVTieringConfig,
                                  PrefixCacheConfig)
from llmq_tpu.conversation.persistence import InMemoryStore, SqliteStore
from llmq_tpu.conversation.state_manager import StateManager
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import EchoExecutor, JaxExecutor
from llmq_tpu.engine.kv_allocator import PageAllocator
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.observability.usage import get_usage_ledger
from llmq_tpu.prefixcache import PrefixCache
from llmq_tpu.tiering import (HostTierPool, KVTieringPlane, decode_blob,
                              encode_blob, pack_pages,
                              page_payload_nbytes, unpack_pages)


@pytest.fixture(autouse=True)
def _usage_off():
    led = get_usage_ledger()
    led.reconfigure(enabled=False)
    led.clear()
    yield
    led.reconfigure(enabled=False)
    led.clear()


def wait_until(fn, timeout=5.0, step=0.002):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if fn():
            return True
        time.sleep(step)
    return False


def claim_ready(plane, conv_id):
    """``plane.claim`` once the worker's extract has landed: an entry
    counts as on the host before it is ready, and ``claim`` says "wait"
    (and hands over nothing) until then."""
    got = []
    assert wait_until(lambda: got.append(plane.claim(conv_id)) or
                      got[-1][0] == "ready")
    return got[-1]


# -- host pool -----------------------------------------------------------------


class TestHostTierPool:
    def test_take_give_lifecycle(self):
        pool = HostTierPool(capacity_bytes=1024, page_nbytes=256)
        assert pool.total_buffers == 4
        bufs = pool.take(3)
        assert bufs is not None and len(bufs) == 3
        assert pool.free_buffers() == 1
        assert pool.used_bytes() == 3 * 256
        pool.give(bufs)
        assert pool.free_buffers() == 4

    def test_all_or_nothing(self):
        pool = HostTierPool(1024, 256)
        held = pool.take(3)
        assert pool.take(2) is None          # only 1 left
        assert pool.free_buffers() == 1      # nothing partially taken
        pool.give(held)

    def test_double_give_is_noop(self):
        pool = HostTierPool(512, 256)
        bufs = pool.take(1)
        pool.give(bufs)
        pool.give(bufs)                      # second give ignored
        assert pool.free_buffers() == 2
        # The freed slot can be handed out again exactly once.
        a = pool.take(2)
        assert a is not None and pool.take(1) is None
        pool.give(a)

    def test_foreign_arrays_ignored(self):
        pool = HostTierPool(512, 256)
        pool.give([np.zeros(256, np.uint8)])
        assert pool.free_buffers() == 2

    def test_buffers_are_arena_views(self):
        pool = HostTierPool(1024, 128)
        bufs = pool.take(2)
        for b in bufs:
            assert b.base is pool._arena     # one allocation total
        pool.give(bufs)

    def test_zero_page_bytes(self):
        pool = HostTierPool(1 << 20, 0)      # content-free backend
        assert pool.total_buffers == 0 and pool.total_bytes == 0


# -- codec ---------------------------------------------------------------------


def _leaves(n_pages, seed=0):
    """Per-leaf page gathers shaped like a tiny int8-KV cache tree:
    (L, N, page, flat-heads) values + (L, N, heads, page) scales."""
    rng = np.random.default_rng(seed)
    import ml_dtypes

    return [
        rng.integers(-100, 100, (2, n_pages, 8, 16)).astype(np.int8),
        rng.standard_normal((2, n_pages, 2, 8)).astype(
            ml_dtypes.bfloat16),
        rng.standard_normal((2, n_pages, 8, 16)).astype(np.float32),
    ]


def _specs(leaves):
    return [((l.shape[0],) + l.shape[2:], np.dtype(l.dtype))
            for l in leaves]


class TestCodec:
    def test_pack_unpack_roundtrip(self):
        leaves = _leaves(3)
        specs = _specs(leaves)
        per = page_payload_nbytes(specs)
        bufs = [np.empty(per, np.uint8) for _ in range(3)]
        pack_pages(leaves, bufs)
        out = unpack_pages(bufs, specs)
        for a, b in zip(leaves, out):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(np.asarray(a, np.uint8).view(np.uint8)
                                  if False else a.view(np.uint8),
                                  b.view(np.uint8))

    def test_blob_roundtrip(self):
        leaves = _leaves(2, seed=7)
        specs = _specs(leaves)
        per = page_payload_nbytes(specs)
        bufs = [np.empty(per, np.uint8) for _ in range(2)]
        pack_pages(leaves, bufs)
        blob = encode_blob(bufs, specs)
        bufs2, specs2 = decode_blob(blob)
        assert [tuple(s) for s, _ in specs2] == [tuple(s)
                                                 for s, _ in specs]
        for a, b in zip(bufs, bufs2):
            assert np.array_equal(a, b)

    def test_corrupt_blob_raises(self):
        leaves = _leaves(1)
        specs = _specs(leaves)
        per = page_payload_nbytes(specs)
        bufs = [np.empty(per, np.uint8)]
        pack_pages(leaves, bufs)
        blob = encode_blob(bufs, specs)
        with pytest.raises(ValueError):
            decode_blob(b"garbage" + blob)
        with pytest.raises(ValueError):
            decode_blob(blob[:-10])          # truncated payload


# -- plane state machine (fake executor) ---------------------------------------


class FakeKVExec:
    """Numpy-backed 'device': deterministic payload per page id so the
    tests can assert content fidelity end to end."""

    def __init__(self):
        self.injected = {}

    def kv_page_spec(self):
        return [((2, 4, 8), np.dtype(np.float32))]

    def export_kv_pages(self, pages):
        out = np.stack(
            [np.full((2, 4, 8), float(p), np.float32) for p in pages],
            axis=1)
        return [out]

    def import_kv_pages(self, pages, leaves):
        for i, p in enumerate(pages):
            self.injected[p] = np.asarray(leaves[0][:, i]).copy()


def mk_plane(cfg=None, execu=None, clock=None, store=None):
    plane = KVTieringPlane(cfg or KVTieringConfig(enabled=True),
                           "test", execu or FakeKVExec(), clock=clock)
    if store is not None:
        plane.store = store
    return plane


class TestPlaneStateMachine:
    def test_demote_then_host_claim(self):
        plane = mk_plane()
        plane.demote("c", [3, 5], list(range(16)), 16, None)
        assert wait_until(lambda: plane.counts()["host"] == 1)
        status, entry = claim_ready(plane, "c")
        assert status == "ready" and entry.tier == "host"
        leaves = plane.unpack(entry)
        # Content fidelity: page 3's payload is all-3.0, page 5 all-5.0.
        assert np.all(np.asarray(leaves[0][:, 0]) == 3.0)
        assert np.all(np.asarray(leaves[0][:, 1]) == 5.0)
        plane.release(entry)
        assert plane.pool.free_buffers() == plane.pool.total_buffers
        assert plane.claim("c") == ("none", None)
        plane.stop()

    def test_spill_to_store_and_load_back(self):
        plane = mk_plane(KVTieringConfig(enabled=True, host_capacity_mb=0),
                         store=InMemoryStore())
        plane.demote("c", [7], list(range(8)), 8, 42)
        assert wait_until(lambda: plane.counts()["store"] == 1)
        assert plane.stats()["spills"] == 1
        assert plane.prepare("c")            # kicks the load
        status = "wait"
        for _ in range(500):
            status, entry = plane.claim("c")
            if status == "ready":
                break
            time.sleep(0.002)
        assert status == "ready"
        assert entry.source_tier == "store"
        assert entry.pending == 42
        leaves = plane.unpack(entry)
        assert np.all(np.asarray(leaves[0][:, 0]) == 7.0)
        plane.release(entry)
        plane.stop()

    def test_claim_triggers_load_without_prepare(self):
        plane = mk_plane(KVTieringConfig(enabled=True, host_capacity_mb=0),
                         store=InMemoryStore())
        plane.demote("c", [2], list(range(8)), 8, None)
        assert wait_until(lambda: plane.counts()["store"] == 1)
        status = "wait"
        for _ in range(500):
            status, entry = plane.claim("c")
            if status == "ready":
                break
            time.sleep(0.002)
        assert status == "ready" and entry.payload is not None
        plane.release(entry)
        plane.stop()

    def test_no_store_degrades_to_recompute(self):
        plane = mk_plane(KVTieringConfig(enabled=True, host_capacity_mb=0,
                                         store_spill=False))
        plane.demote("c", [2], [1, 2, 3], 3, None)
        assert wait_until(lambda: plane.counts()["recompute"] == 1)
        status, entry = plane.claim("c")
        assert status == "ready" and entry.payload is None
        assert entry.tokens == [1, 2, 3]
        plane.release(entry)
        plane.stop()

    def test_promote_timeout_falls_back_to_recompute(self):
        plane = mk_plane(KVTieringConfig(enabled=True,
                                         promote_timeout_s=0.02))
        # An entry that never becomes ready (no worker ran: inject one
        # manually in the not-ready state).
        from llmq_tpu.tiering.plane import TierEntry
        entry = TierEntry("c", [1, 2], 2, None, 1, 0.0)
        with plane._mu:
            plane._entries["c"] = entry
        assert plane.claim("c")[0] == "wait"
        time.sleep(0.03)
        status, got = plane.claim("c")
        assert status == "ready" and got.payload is None
        assert got.tokens == [1, 2]          # recompute still exact
        plane.stop()

    def test_forget_drops_all_tiers(self):
        store = InMemoryStore()
        plane = mk_plane(KVTieringConfig(enabled=True, host_capacity_mb=0),
                         store=store)
        plane.demote("c", [4], list(range(8)), 8, None)
        assert wait_until(lambda: store.load_kv("c") is not None)
        plane.forget("c")
        assert wait_until(lambda: store.load_kv("c") is None)
        assert plane.claim("c") == ("none", None)
        plane.stop()

    def test_restash_puts_entry_back(self):
        plane = mk_plane()
        plane.demote("c", [3], list(range(8)), 8, None)
        assert wait_until(lambda: plane.counts()["host"] == 1)
        status, entry = claim_ready(plane, "c")
        assert status == "ready"
        plane.restash("c", entry)
        status2, entry2 = plane.claim("c")
        assert status2 == "ready" and entry2 is entry
        plane.release(entry2)
        plane.stop()

    def test_host_bound_spills_coldest(self):
        clock = FakeClock()
        plane = mk_plane(KVTieringConfig(enabled=True,
                                         host_max_conversations=2),
                         clock=clock, store=InMemoryStore())
        for i in range(3):
            plane.demote(f"c{i}", [i + 1], list(range(8)), 8, None)
            # Wait for the extract itself (counts alone flip at demote
            # time): spill victims must be READY residents.
            assert wait_until(
                lambda i=i: plane._entries[f"c{i}"].ready.is_set()
                or plane._entries[f"c{i}"].spilling)
            clock.advance(1.0)
        assert wait_until(lambda: plane.counts()["store"] == 1
                          and plane.counts()["host"] == 2)
        # The coldest (first-demoted) conversation is the spilled one.
        with plane._mu:
            assert plane._entries["c0"].tier == "store"
        plane.stop()

    def test_round_trip_counted_inside_window(self):
        clock = FakeClock()
        plane = mk_plane(clock=clock)
        plane.demote("c", [3], list(range(8)), 8, None)
        assert wait_until(lambda: plane.counts()["host"] == 1)
        status, entry = claim_ready(plane, "c")
        plane.note_promoted(entry, "host", 0.1)
        plane.release(entry)
        assert plane.stats()["round_trips"] == 1
        # Outside the window: no thrash.
        plane.demote("c", [4], list(range(8)), 8, None)
        assert wait_until(lambda: plane.counts()["host"] == 1)
        clock.advance(3600.0)
        status, entry = claim_ready(plane, "c")
        plane.note_promoted(entry, "host", 0.1)
        plane.release(entry)
        assert plane.stats()["round_trips"] == 1
        plane.stop()

    def test_timeout_claim_racing_spill_leaks_no_buffers(self):
        """A promote-timeout claim racing a QUEUED spill must not leak
        host-pool buffers: the spill job owns its buffers exclusively
        (popped at claim-for-spill) and returns them itself even when
        the entry was abandoned mid-flight."""
        gate = threading.Event()

        class SlowStore(InMemoryStore):
            def save_kv(self, cid, blob):
                gate.wait(5.0)
                super().save_kv(cid, blob)

        # Pool holds exactly one conversation; host bound of 1 entry.
        spec_bytes = page_payload_nbytes(FakeKVExec().kv_page_spec())
        cfg = KVTieringConfig(enabled=True, host_max_conversations=1,
                              promote_timeout_s=0.01)
        cfg.host_capacity_mb = 0        # replaced below with raw bytes
        plane = KVTieringPlane(cfg, "leak", FakeKVExec())
        plane.pool = HostTierPool(2 * spec_bytes, spec_bytes)
        plane.store = SlowStore()
        plane.demote("c0", [1], list(range(8)), 8, None)
        assert wait_until(lambda: plane.counts()["host"] == 1)
        # Second demote pushes past the bound → spill of c0 queued,
        # blocked inside save_kv by the gate.
        plane.demote("c1", [2], list(range(8)), 8, None)
        assert wait_until(
            lambda: plane._entries["c0"].spilling
            or plane._entries["c0"].tier == "store")
        # Claim c0 while its spill is stuck → promote timeout →
        # recompute fallback.
        deadline = time.perf_counter() + 2.0
        status = "wait"
        while time.perf_counter() < deadline:
            status, entry = plane.claim("c0")
            if status != "wait":
                break
            time.sleep(0.005)
        assert status == "ready" and entry.payload is None
        plane.release(entry)
        gate.set()                       # spill completes late
        assert wait_until(
            lambda: plane.pool.free_buffers() + 1
            == plane.pool.total_buffers)  # only c1's entry holds one
        plane.stop()

    def test_wait_since_resets_on_publish_and_restash(self):
        plane = mk_plane()
        gate = threading.Event()
        plane._submit(lambda: gate.wait(5.0))   # park the worker
        plane.demote("c", [3], list(range(8)), 8, None)
        # Claim while the extract is parked: starts the timeout epoch.
        assert plane.claim("c")[0] == "wait"
        with plane._mu:
            entry = plane._entries["c"]
        assert entry.wait_since is not None
        gate.set()
        assert wait_until(lambda: entry.ready.is_set())
        # Publication resets the epoch (a LATER wait gets the full
        # timeout, instead of inheriting this one's elapsed part).
        assert entry.wait_since is None
        status, got = plane.claim("c")
        assert status == "ready"
        got.wait_since = 123.0
        plane.restash("c", got)
        assert got.wait_since is None
        plane.stop()

    def test_async_degradation_fires_tier_change(self):
        """A worker-side degradation (spill fails, no payload
        preserved) downgrades the prefix handle through the
        on_tier_change callback — prefill_estimate must not keep
        promising a prefix nothing can serve."""

        class BrokenStore(InMemoryStore):
            def save_kv(self, cid, blob):
                raise RuntimeError("store down")

        changes = []
        plane = mk_plane(KVTieringConfig(enabled=True,
                                         host_capacity_mb=0),
                         store=BrokenStore())
        plane.on_tier_change = lambda cid, tier: changes.append(
            (cid, tier))
        plane.demote("c", [2], list(range(8)), 8, None)
        assert wait_until(lambda: plane.counts()["recompute"] == 1)
        assert ("c", "dropped") in changes
        plane.stop()

    def test_content_free_metadata_entry(self):
        class Echoish:
            kv_content_free = True

        plane = mk_plane(execu=Echoish())
        plane.demote("c", [1, 2], [9, 8, 7], 3, None)
        status, entry = plane.claim("c")     # ready immediately
        assert status == "ready"
        assert entry.tier == "host" and entry.payload is None
        assert plane.content_free
        plane.release(entry)
        plane.stop()


# -- prefix-cache demotion seam (satellite, standalone) ------------------------


class TestPrefixCacheDemotionSeam:
    def _cache(self, pages=32, page_size=4):
        alloc = PageAllocator(pages, page_size)
        return alloc, PrefixCache(alloc, page_size)

    def test_default_is_plain_free(self):
        alloc, pc = self._cache()
        pages = alloc.alloc(2)
        ids = list(range(8))
        pc.insert(ids, pages)
        alloc.free(pages)                    # caller's refs
        freed = pc.evict_pages(2)
        assert freed == 2
        assert alloc.available() == alloc.total

    def test_callback_sees_token_path_and_page(self):
        alloc, pc = self._cache()
        pages = alloc.alloc(3)
        ids = list(range(12))
        pc.insert(ids, pages)
        alloc.free(pages)
        seen = []
        pc.set_demotion_callback(lambda path, page: seen.append(
            (list(path), page)))
        assert pc.evict_pages(3) == 3
        # Leaves evict bottom-up: the deepest block first, each with
        # its FULL root→node token path.
        paths = sorted(seen, key=lambda s: len(s[0]))
        assert [p for p, _ in paths] == [ids[:4], ids[:8], ids[:12]]
        assert {pg for _, pg in seen} == set(pages)

    def test_callback_skipped_for_shared_pages(self):
        alloc, pc = self._cache()
        pages = alloc.alloc(1)
        ids = list(range(4))
        pc.insert(ids, pages)                # tree retains; we hold too
        seen = []
        pc.set_demotion_callback(lambda path, page: seen.append(page))
        # Tree eviction under max_pages pressure takes ANY zero-lock
        # leaf; the page is still shared with us → no demotion signal.
        assert pc._evict_locked(target_nodes=1) == 0   # not last holder
        assert seen == []
        alloc.free(pages)

    def test_invalidate_never_fires_callback(self):
        """Delete contract: invalidated content must not be captured
        into a lower tier."""
        alloc, pc = self._cache()
        pages = alloc.alloc(2)
        ids = list(range(8))
        pc.insert(ids, pages)
        alloc.free(pages)
        seen = []
        pc.set_demotion_callback(lambda path, page: seen.append(page))
        assert pc.invalidate(ids) == 2
        assert seen == []

    def test_callback_failure_does_not_break_eviction(self):
        alloc, pc = self._cache()
        pages = alloc.alloc(2)
        pc.insert(list(range(8)), pages)
        alloc.free(pages)

        def boom(path, page):
            raise RuntimeError("demoter broke")

        pc.set_demotion_callback(boom)
        assert pc.evict_pages(2) == 2
        assert alloc.available() == alloc.total


# -- sqlite spill store hardening (satellite) ----------------------------------


class TestSqliteSpillStore:
    def test_kv_blob_roundtrip(self, tmp_path):
        store = SqliteStore(str(tmp_path / "kv.db"))
        blob = bytes(range(256)) * 17        # binary, not utf-8 safe
        store.save_kv("c1", blob)
        assert store.load_kv("c1") == blob
        store.save_kv("c1", b"v2")           # upsert
        assert store.load_kv("c1") == b"v2"
        store.delete_kv("c1")
        assert store.load_kv("c1") is None
        store.close()

    def test_migration_on_pre_tiering_db(self, tmp_path):
        """An existing database without kv_payloads upgrades in place
        on open (idempotent CREATE IF NOT EXISTS migration)."""
        import sqlite3

        path = str(tmp_path / "old.db")
        conn = sqlite3.connect(path)
        conn.execute(
            """CREATE TABLE conversations (
                id TEXT PRIMARY KEY, user_id TEXT NOT NULL,
                state TEXT NOT NULL, context TEXT NOT NULL DEFAULT '',
                messages TEXT NOT NULL DEFAULT '[]',
                metadata TEXT NOT NULL DEFAULT '{}',
                created_at REAL NOT NULL, updated_at REAL NOT NULL,
                last_active_at REAL NOT NULL)""")
        conn.commit()
        conn.close()
        store = SqliteStore(path)
        store.save_kv("c", b"payload")
        assert store.load_kv("c") == b"payload"
        store.close()

    def test_busy_timeout_and_wal_set(self, tmp_path):
        store = SqliteStore(str(tmp_path / "t.db"))
        conn = store._conn()
        assert conn.execute("PRAGMA busy_timeout").fetchone()[0] == 10000
        assert conn.execute(
            "PRAGMA journal_mode").fetchone()[0].lower() == "wal"
        store.close()

    def test_concurrent_save_load_never_locks(self, tmp_path):
        """The spill tier's contract: 4 threads hammering save/load/
        delete concurrently never raise 'database is locked' (WAL +
        busy_timeout)."""
        from llmq_tpu.core.types import Conversation

        store = SqliteStore(str(tmp_path / "conc.db"))
        errors = []
        stop = threading.Event()

        def worker(wid):
            try:
                for i in range(120):
                    cid = f"c{wid}-{i % 7}"
                    store.save_kv(cid, bytes([wid]) * 2048)
                    store.load_kv(cid)
                    conv = Conversation(
                        id=cid, user_id=f"u{wid}", created_at=1.0,
                        updated_at=1.0, last_active_at=1.0)
                    store.save(conv)
                    store.load(cid)
                    if i % 11 == 0:
                        store.delete_kv(cid)
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
        store.close()


# -- prefix-handle tier semantics (satellite) ----------------------------------


def mk_echo_engine(tiering=None, pin_ttl=600.0, clock=None, pages=128,
                   metrics=False, **kw):
    tok = ByteTokenizer()
    ex = EchoExecutor(batch_size=4, page_size=8, num_pages=pages,
                      max_pages_per_seq=16, eos_id=tok.eos_id,
                      chunk_size=4, **kw)
    return InferenceEngine(ex, tok, enable_metrics=metrics,
                           name="tiertest", kv_pin_ttl=pin_ttl,
                           clock=clock, kv_tiering=tiering,
                           prefix_cache=PrefixCacheConfig(enabled=True))


def run_turn(eng, rid, prompt, conv, tokens=8):
    h = eng.submit(GenRequest(id=rid, prompt=prompt,
                              conversation_id=conv,
                              max_new_tokens=tokens))
    eng.run_until_idle()
    assert h.result is not None and h.result.finish_reason in (
        "eos", "length")
    return h


class TestPrefixHandleTier:
    def test_handle_outlives_residency_estimate_per_tier(self):
        """The record_prefix_handle docstring promise, pinned: after
        the pin is reclaimed the handle survives — and its tier field
        decides the prefill estimate. Demoted (tiering on) → still
        cached (promotable); tiering off with the radix tree ALSO
        emptied → dropped → a correct non-cached estimate."""
        clock = FakeClock()
        for tiering, expect_cached in ((KVTieringConfig(enabled=True),
                                        True), (None, False)):
            eng = mk_echo_engine(tiering=tiering, pin_ttl=5.0,
                                 clock=clock)
            sm = StateManager(ConversationConfig(), clock=clock)
            eng.attach_conversation_manager(sm)
            sm.get_or_create("c", "u")
            run_turn(eng, "t1", "hello world conversation", "c")
            h = sm.prefix_handle("c")
            assert h is not None and h["tier"] == "hbm"
            cached0, _ = eng.prefill_estimate("c", 10)
            assert cached0 > 0               # pin resident
            if tiering is None:
                # Radix loses the blocks too (LRU pressure analogue):
                # the reclaim below must then mark the handle dropped.
                eng._prefix_cache.invalidate_all()
            clock.advance(6.0)
            eng.step()                       # TTL reclaim
            assert "c" not in eng.cached_conversations()
            h = sm.prefix_handle("c")
            assert h is not None             # handle OUTLIVES the pin
            assert h["tier"] == ("host" if tiering else "dropped")
            cached, new = eng.prefill_estimate("c", 10)
            if expect_cached:
                assert cached > 0            # promotable from host
            else:
                assert cached == 0           # gone for good: all-new
            assert new == 10
            eng.stop()
            sm.stop()

    def test_estimate_stays_optimistic_with_radix_fallback(self):
        """Tiering off, pin reclaimed, radix still holding the blocks:
        the handle stays promotable and the estimate stays cached —
        exactly the pre-tiering behavior (turn N+1 adopts the tree)."""
        clock = FakeClock()
        eng = mk_echo_engine(tiering=None, pin_ttl=5.0, clock=clock)
        sm = StateManager(ConversationConfig(), clock=clock)
        eng.attach_conversation_manager(sm)
        sm.get_or_create("c", "u")
        run_turn(eng, "t1", "hello world conversation", "c")
        clock.advance(6.0)
        eng.step()
        assert sm.prefix_handle("c")["tier"] == "hbm"
        cached, _ = eng.prefill_estimate("c", 10)
        assert cached > 0
        eng.stop()
        sm.stop()

    def test_promotion_moves_handle_back_to_hbm(self):
        clock = FakeClock()
        eng = mk_echo_engine(tiering=KVTieringConfig(enabled=True),
                             pin_ttl=5.0, clock=clock)
        sm = StateManager(ConversationConfig(), clock=clock)
        eng.attach_conversation_manager(sm)
        sm.get_or_create("c", "u")
        run_turn(eng, "t1", "hello world", "c")
        clock.advance(6.0)
        eng.step()
        assert sm.prefix_handle("c")["tier"] == "host"
        run_turn(eng, "t2", " again", "c")
        # Promotion re-pinned, then the finish re-recorded the handle.
        assert sm.prefix_handle("c")["tier"] == "hbm"
        eng.stop()
        sm.stop()

    def test_update_prefix_handle_tier_contract(self):
        sm = StateManager(ConversationConfig())
        assert not sm.update_prefix_handle_tier("nope", "host")
        sm.get_or_create("c", "u")
        assert not sm.update_prefix_handle_tier("c", "host")  # no handle
        sm.record_prefix_handle("c", {"length": 32, "pages": 4,
                                      "tier": "hbm"})
        assert sm.update_prefix_handle_tier("c", "store")
        assert sm.prefix_handle("c")["tier"] == "store"
        assert sm.prefix_handle("c")["length"] == 32   # rest untouched

    def test_unpin_after_demotion_bills_tenant(self):
        """Economics seam: the HBM pin's page-second meter closes AT
        DEMOTION (host residency is not the priced HBM resource), and
        the accrued page-seconds land on the pinning tenant."""
        led = get_usage_ledger()
        led.reconfigure(enabled=True)
        led.clear()
        eng = mk_echo_engine(tiering=KVTieringConfig(enabled=True),
                             pin_ttl=0.05)
        h = eng.submit(GenRequest(id="t1", prompt="hello world billing",
                                  conversation_id="c", max_new_tokens=8,
                                  tenant_id="acme"))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        time.sleep(0.08)                     # real time: the tracker
        eng.step()                           # integrates wall-clock
        assert "c" not in eng.cached_conversations()
        snap = led.snapshot()
        assert snap["totals"]["pinned_kv_page_seconds"] > 0
        assert snap["tenants"]["acme"]["kv_page_seconds"] > 0
        eng.stop()


# -- echo engine integration ---------------------------------------------------


class TestEchoEngineTiering:
    def test_off_switch_builds_nothing(self):
        eng = mk_echo_engine(tiering=KVTieringConfig(enabled=False))
        assert eng._tiering is None
        assert "kv_tiering" not in eng.get_stats()
        eng.stop()

    def test_demote_promote_equivalence_vs_resident_pin(self):
        """Token-for-token: tiering ON with the pin expired between
        turns produces the same streams as the pin never expiring."""
        clock_a, clock_b = FakeClock(), FakeClock()
        eng_a = mk_echo_engine(pin_ttl=600.0, clock=clock_a)   # resident
        eng_b = mk_echo_engine(tiering=KVTieringConfig(enabled=True),
                               pin_ttl=5.0, clock=clock_b)
        outs = []
        for eng, clock in ((eng_a, clock_a), (eng_b, clock_b)):
            h1 = run_turn(eng, "t1", "the quick brown fox", "c")
            clock.advance(6.0)
            eng.step()
            h2 = run_turn(eng, "t2", " jumps over the dog", "c")
            outs.append((h1.result.tokens, h2.result.tokens,
                         h2.result.cached_tokens))
        assert outs[0][0] == outs[1][0]
        assert outs[0][1] == outs[1][1]
        assert outs[1][2] > 0                # promotion actually served
        st = eng_b.get_stats()["kv_tiering"]
        assert st["hits"]["host"] == 1 and st["demotions"] == 1
        assert "c" not in eng_a.cached_conversations() or True
        eng_a.stop()
        eng_b.stop()

    def test_pool_pressure_demotes_instead_of_killing(self):
        """A new admission that pressure-reclaims an idle pinned
        conversation demotes it — the later re-arrival is a host hit,
        not a recompute."""
        eng = mk_echo_engine(tiering=KVTieringConfig(enabled=True),
                             pages=17)       # 16 allocatable pages
        run_turn(eng, "t1", "x" * 40, "alpha", tokens=4)
        assert "alpha" in eng.cached_conversations()
        # A fat single-shot request forces pool pressure.
        run_turn(eng, "big", "y" * 100, "", tokens=4)
        assert "alpha" not in eng.cached_conversations()
        st = eng.get_stats()["kv_tiering"]
        assert st["demotions"] == 1
        h = run_turn(eng, "t2", "more text", "alpha", tokens=4)
        assert h.result.cached_tokens > 0
        assert eng.get_stats()["kv_tiering"]["hits"]["host"] == 1
        eng.stop()

    def test_delete_forgets_all_tiers(self):
        clock = FakeClock()
        eng = mk_echo_engine(tiering=KVTieringConfig(enabled=True),
                             pin_ttl=5.0, clock=clock)
        sm = StateManager(ConversationConfig(), clock=clock)
        eng.attach_conversation_manager(sm)
        sm.get_or_create("c", "u")
        run_turn(eng, "t1", "private content", "c")
        clock.advance(6.0)
        eng.step()                           # demoted to host tier
        assert eng.get_stats()["kv_tiering"]["entries"] == 1
        sm.delete("c")                       # on_evict → drop + forget
        assert eng.get_stats()["kv_tiering"]["entries"] == 0
        run_turn(eng, "t2", "fresh start", "c")
        st = eng.get_stats()["kv_tiering"]
        assert st["promotions"] == 0         # nothing served the return
        eng.stop()
        sm.stop()

    def test_async_pipeline_interplay(self):
        """Demote/promote under the PR 10 pipeline (depth 2, simulated
        device latency): streams match the pin-resident baseline and
        the promotion still lands as a host hit."""
        from llmq_tpu.core.config import AsyncPipelineConfig

        def build(tiering, clock):
            tok = ByteTokenizer()
            ex = EchoExecutor(batch_size=4, page_size=8, num_pages=128,
                              max_pages_per_seq=16, eos_id=tok.eos_id,
                              chunk_size=4, async_chunks=True,
                              step_delay_s=0.001)
            return InferenceEngine(
                ex, tok, enable_metrics=False, name="tierpipe",
                kv_pin_ttl=5.0 if tiering else 600.0, clock=clock,
                kv_tiering=tiering,
                async_pipeline=AsyncPipelineConfig(enabled=True,
                                                   depth=2))

        outs = []
        for tiering in (None, KVTieringConfig(enabled=True)):
            clock = FakeClock()
            eng = build(tiering, clock)
            eng.start()
            h1 = eng.submit(GenRequest(id="t1", prompt="pipeline text",
                                       conversation_id="c",
                                       max_new_tokens=10))
            assert h1.wait(30.0)
            clock.advance(6.0)
            if tiering is not None:
                assert wait_until(
                    lambda: "c" not in eng.cached_conversations())
                assert wait_until(lambda: eng.get_stats()
                                  ["kv_tiering"]["host_entries"] == 1)
            h2 = eng.submit(GenRequest(id="t2", prompt=" and more",
                                       conversation_id="c",
                                       max_new_tokens=10))
            assert h2.wait(30.0)
            outs.append((h1.result.tokens, h2.result.tokens))
            if tiering is not None:
                st = eng.get_stats()["kv_tiering"]
                assert st["hits"]["host"] == 1, st
            eng.stop()
        assert outs[0] == outs[1]


# -- CPU-mode JAX engine integration -------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    from llmq_tpu.models.llama import init_params, llama3_tiny

    cfg = llama3_tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                      ffn_dim=128, vocab_size=512, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def run_jax_two_turns(cfg, params, tiering_cfg, store=None, expire=True,
                      cache_dtype=None):
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=2, page_size=8,
                     num_pages=64, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id, chunk_size=4,
                     cache_dtype=cache_dtype)
    clock = FakeClock()
    eng = InferenceEngine(ex, tok, enable_metrics=False,
                          max_decode_steps=12, clock=clock,
                          kv_pin_ttl=5.0 if expire else 600.0,
                          kv_tiering=tiering_cfg)
    if store is not None and eng._tiering is not None:
        eng._tiering.store = store
    h1 = eng.submit(GenRequest(id="t1", prompt="the quick brown fox",
                               conversation_id="c", max_new_tokens=10))
    eng.run_until_idle()
    if expire:
        clock.advance(6.0)
        eng.step()
        assert "c" not in eng.cached_conversations()
        if eng._tiering is not None:
            assert wait_until(lambda: sum(
                eng._tiering.counts().values()) == 1)
    h2 = eng.submit(GenRequest(id="t2", prompt=" jumps over",
                               conversation_id="c", max_new_tokens=10))
    eng.run_until_idle()
    eng.stop()
    return eng, (h1, h2)


class TestJaxEngineTiering:
    def test_every_tier_token_for_token(self, tiny_model):
        """The acceptance pin: host-tier, store-tier and recompute
        promotions all decode turn 2 exactly like the pin-resident
        baseline (real KV payload round-trips bit-exact through the
        host pool and the store blob)."""
        cfg, params = tiny_model
        _, base = run_jax_two_turns(cfg, params, None, expire=False)
        base_toks = [h.result.tokens for h in base]
        assert all(base_toks)

        eng, out = run_jax_two_turns(cfg, params,
                                     KVTieringConfig(enabled=True))
        st = eng.get_stats()["kv_tiering"]
        assert st["hits"]["host"] == 1, st
        assert [h.result.tokens for h in out] == base_toks
        assert out[1].result.cached_tokens > 0

        eng, out = run_jax_two_turns(
            cfg, params,
            KVTieringConfig(enabled=True, host_capacity_mb=0),
            store=InMemoryStore())
        st = eng.get_stats()["kv_tiering"]
        assert st["spills"] == 1 and st["hits"]["store"] == 1, st
        assert [h.result.tokens for h in out] == base_toks

        eng, out = run_jax_two_turns(
            cfg, params,
            KVTieringConfig(enabled=True, host_capacity_mb=0,
                            store_spill=False))
        st = eng.get_stats()["kv_tiering"]
        assert st["hits"]["recompute"] == 1, st
        assert [h.result.tokens for h in out] == base_toks

    def test_int8_kv_payload_roundtrip(self, tiny_model):
        """int8-KV: the quantization scale pools ride the payload as
        ordinary cache leaves — promotion restores values AND scales."""
        import dataclasses

        import jax.numpy as jnp

        cfg, params = tiny_model
        cfg = dataclasses.replace(cfg, pallas=False)
        _, base = run_jax_two_turns(cfg, params, None, expire=False,
                                    cache_dtype=jnp.int8)
        eng, out = run_jax_two_turns(cfg, params,
                                     KVTieringConfig(enabled=True),
                                     cache_dtype=jnp.int8)
        st = eng.get_stats()["kv_tiering"]
        assert st["hits"]["host"] == 1, st
        assert [h.result.tokens for h in out] == [h.result.tokens
                                                  for h in base]
        # The payload spec carried all four leaves.
        specs = eng.executor.kv_page_spec()
        assert len(specs) == 4

    def test_off_switch_matches_no_tiering(self, tiny_model):
        """enabled:false is byte-identical to a pre-plane engine: no
        plane object, no worker thread, same streams."""
        cfg, params = tiny_model
        before = {t.name for t in threading.enumerate()}
        eng_off, off = run_jax_two_turns(
            cfg, params, KVTieringConfig(enabled=False), expire=False)
        assert eng_off._tiering is None
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("kv-tiering")
                    and t.name not in before]
        _, none = run_jax_two_turns(cfg, params, None, expire=False)
        assert [h.result.tokens for h in off] == [h.result.tokens
                                                  for h in none]


# -- metrics -------------------------------------------------------------------


class TestTieringMetrics:
    def test_families_exposed_and_hits_counted(self):
        from llmq_tpu.metrics.registry import exposition

        clock = FakeClock()
        eng = mk_echo_engine(tiering=KVTieringConfig(enabled=True),
                             pin_ttl=5.0, clock=clock, metrics=True)
        run_turn(eng, "t1", "metric text", "c")
        clock.advance(6.0)
        eng.step()
        run_turn(eng, "t2", " more", "c")
        exp = exposition().decode()
        for fam in ("llm_queue_kv_tier_pages",
                    "llm_queue_kv_tier_bytes",
                    "llm_queue_kv_tier_hits_total",
                    "llm_queue_kv_tier_round_trips_total",
                    "llm_queue_kv_promote_ms",
                    "llm_queue_kv_demote_ms"):
            assert fam in exp, fam
        assert ('llm_queue_kv_tier_hits_total{engine="tiertest",'
                'tier="host"}') in exp
        assert ('llm_queue_kv_demote_ms_count{engine="tiertest"}'
                ) in exp
        eng.stop()


# -- cross-OS-process blob handoff over real HTTP (satellite) ------------------


class TestCrossProcessBlobHandoff:
    """The disagg exchange's transport-level contract: a blob encoded
    in one OS process survives a REAL network hop and decodes in
    another process bit-identically — including the int8 KV pages and
    their float32 scale pool — and a blob torn in transit raises (the
    importer degrades to recompute, never injects garbage)."""

    def test_http_transfer_int8_scales_bit_identical(self):
        import http.server
        import os
        import subprocess
        import sys

        rng = np.random.default_rng(33)
        n_pages = 4
        # An int8-quantized cache tree: quantized pages + their scale
        # pool, riding as ordinary leaves with their own specs.
        pages_i8 = rng.integers(-128, 128, (2, n_pages, 8, 16)
                                ).astype(np.int8)
        scales = rng.random((2, n_pages, 8)).astype(np.float32)
        leaves = [pages_i8, scales]
        specs = [((leaf.shape[0],) + leaf.shape[2:], leaf.dtype)
                 for leaf in leaves]
        per = page_payload_nbytes(specs)
        bufs = [np.empty(per, np.uint8) for _ in range(n_pages)]
        pack_pages(leaves, bufs)
        blob = encode_blob(bufs, specs,
                           meta={"conv_id": "c", "tokens": [1, 2, 3],
                                 "length": 3, "n_pages": n_pages})

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                # /blob: the published entry; /torn: cut mid-payload,
                # as a crashed publisher/partial write would leave it.
                body = blob if self.path == "/blob" else blob[:-16]
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        port = srv.server_address[1]

        child = f"""
import urllib.request
import numpy as np
from llmq_tpu.tiering import decode_blob, unpack_pages
from llmq_tpu.tiering.plane import blob_meta

with urllib.request.urlopen(
        "http://127.0.0.1:{port}/blob", timeout=10) as r:
    blob = r.read()
meta = blob_meta(blob)
assert meta["tokens"] == [1, 2, 3], meta
bufs, specs = decode_blob(blob)
leaves = unpack_pages(bufs, specs)
rng = np.random.default_rng(33)
want_i8 = rng.integers(-128, 128, (2, {n_pages}, 8, 16)).astype(np.int8)
want_sc = rng.random((2, {n_pages}, 8)).astype(np.float32)
assert leaves[0].dtype == np.int8
assert np.array_equal(leaves[0], want_i8)
print("PAYLOAD_OK", flush=True)
# Bit-identity of the scale pool: byte-level comparison, not almost-
# equal — a single flipped mantissa bit would dequantize every value
# in the page.
assert leaves[1].dtype == np.float32
assert np.array_equal(leaves[1].view(np.uint8), want_sc.view(np.uint8))
print("SCALES_BIT_IDENTICAL", flush=True)
with urllib.request.urlopen(
        "http://127.0.0.1:{port}/torn", timeout=10) as r:
    torn = r.read()
try:
    decode_blob(torn)
except ValueError:
    print("TORN_DEGRADES_TO_RECOMPUTE", flush=True)
else:
    raise AssertionError("torn blob decoded")
"""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        try:
            out = subprocess.run(
                [sys.executable, "-c", child],
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                env=env, capture_output=True, text=True, timeout=120)
        finally:
            srv.shutdown()
        assert out.returncode == 0, out.stderr
        assert "PAYLOAD_OK" in out.stdout
        assert "SCALES_BIT_IDENTICAL" in out.stdout
        assert "TORN_DEGRADES_TO_RECOMPUTE" in out.stdout
