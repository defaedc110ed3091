"""Conversation service tests.

The reference has ZERO tests for any of its three conversation managers
(SURVEY.md §4); this covers the unified manager + both usable stores."""

import pytest

from llmq_tpu.core.config import ConversationConfig
from llmq_tpu.core.errors import ConversationNotFoundError
from llmq_tpu.core.types import Conversation, ConversationState, Message
from llmq_tpu.conversation import InMemoryStore, SqliteStore, StateManager


@pytest.fixture(params=["memory", "sqlite"])
def store(request, tmp_path):
    if request.param == "memory":
        yield InMemoryStore()
    else:
        s = SqliteStore(str(tmp_path / "conv.db"))
        yield s
        s.close()


@pytest.fixture
def sm(fake_clock, store) -> StateManager:
    cfg = ConversationConfig(max_context_length=100, max_idle_time=60.0,
                             ttl=3600.0, max_conversations_per_user=3,
                             max_conversations=10)
    return StateManager(cfg, store=store, clock=fake_clock)


class TestLifecycle:
    def test_get_or_create(self, sm):
        c = sm.get_or_create("c1", "u1")
        assert c.id == "c1" and c.user_id == "u1"
        assert sm.get_or_create("c1").id == "c1"
        assert sm.count() == 1

    def test_get_missing_raises(self, sm):
        with pytest.raises(ConversationNotFoundError):
            sm.get("nope")

    def test_create_and_delete(self, sm):
        c = sm.create("u1")
        assert sm.get(c.id)
        assert sm.delete(c.id)
        with pytest.raises(ConversationNotFoundError):
            sm.get(c.id)

    def test_update_state(self, sm):
        c = sm.create("u1")
        sm.update_state(c.id, ConversationState.PAUSED)
        assert sm.get(c.id).state == ConversationState.PAUSED


class TestMessagesAndContext:
    def test_add_message(self, sm):
        c = sm.add_message("c1", Message(content="hello", user_id="u1"))
        assert len(c.messages) == 1
        assert c.messages[0].conversation_id == "c1"

    def test_window_trims_by_chars(self, sm):
        # max_context_length=100 in the fixture.
        for i in range(10):
            sm.add_message("c1", Message(content="x" * 30, user_id="u1"))
        c = sm.get("c1")
        assert len(c.messages) == 3  # 90 chars fits, 120 does not
        total = sum(len(m.content) for m in c.messages)
        assert total <= 100

    def test_record_response_builds_context(self, sm):
        m = Message(content="q", user_id="u1")
        m.response = "a" * 80
        sm.add_message("c1", m)
        sm.record_response("c1", m)
        c = sm.get("c1")
        assert c.context == "a" * 80
        m2 = Message(content="q2", user_id="u1")
        m2.response = "b" * 80
        sm.record_response("c1", m2)
        # context capped at max_context_length.
        assert len(sm.get("c1").context) == 100
        assert sm.get("c1").context.endswith("b" * 80)


class TestPersistence:
    def test_reload_from_store_after_restart(self, fake_clock, store):
        cfg = ConversationConfig()
        sm1 = StateManager(cfg, store=store, clock=fake_clock)
        sm1.add_message("c1", Message(content="persisted", user_id="u1"))
        # "Restart": new manager, same store (state_manager.go:86-95).
        sm2 = StateManager(cfg, store=store, clock=fake_clock)
        c = sm2.get("c1")
        assert c.messages[0].content == "persisted"

    def test_user_conversations_include_archived(self, fake_clock, store):
        cfg = ConversationConfig(max_conversations_per_user=2)
        sm = StateManager(cfg, store=store, clock=fake_clock)
        ids = []
        for i in range(3):
            c = sm.create("u1")
            ids.append(c.id)
            fake_clock.advance(1.0)
        # Oldest archived out of memory but still listed via the store.
        assert sm.count() == 2
        got = {c.id for c in sm.user_conversations("u1")}
        assert got == set(ids)


class TestCleanup:
    def test_idle_eviction(self, sm, fake_clock):
        sm.create("u1")
        fake_clock.advance(61.0)  # max_idle_time=60
        assert sm.run_cleanup_once() == 1
        assert sm.count() == 0

    def test_active_not_evicted(self, sm, fake_clock):
        sm.create("u1")
        fake_clock.advance(30.0)
        assert sm.run_cleanup_once() == 0

    def test_ttl_eviction(self, fake_clock, store):
        cfg = ConversationConfig(ttl=100.0, max_idle_time=0)
        sm = StateManager(cfg, store=store, clock=fake_clock)
        c = sm.create("u1")
        fake_clock.advance(50.0)
        c.last_active_at = fake_clock.now()
        assert sm.run_cleanup_once() == 0
        fake_clock.advance(51.0)
        assert sm.run_cleanup_once() == 1

    def test_completed_linger(self, fake_clock, store):
        cfg = ConversationConfig(ttl=0, max_idle_time=0)
        sm = StateManager(cfg, store=store, clock=fake_clock)
        c = sm.create("u1")
        sm.update_state(c.id, ConversationState.COMPLETED)
        fake_clock.advance(23 * 3600.0)
        assert sm.run_cleanup_once() == 0
        fake_clock.advance(2 * 3600.0)
        assert sm.run_cleanup_once() == 1


class TestKVPinningHooks:
    def test_touch_and_evict_hooks(self, sm, fake_clock):
        touched, evicted = [], []
        sm.on_touch(lambda c: touched.append(c.id))
        sm.on_evict(lambda c: evicted.append(c.id))
        sm.get_or_create("c1", "u1")
        assert touched == ["c1"]
        fake_clock.advance(61.0)
        sm.run_cleanup_once()
        assert evicted == ["c1"]

    def test_hook_failure_does_not_break(self, sm):
        sm.on_touch(lambda c: (_ for _ in ()).throw(RuntimeError("hook")))
        c = sm.get_or_create("c1", "u1")  # no raise
        assert c.id == "c1"


class TestCaps:
    def test_global_cap(self, fake_clock, store):
        cfg = ConversationConfig(max_conversations=2,
                                 max_conversations_per_user=100)
        sm = StateManager(cfg, store=store, clock=fake_clock)
        for i in range(3):
            sm.create(f"u{i}")
            fake_clock.advance(1.0)
        assert sm.count() == 2


class _FakePipeline:
    def __init__(self, r):
        self._r = r
        self._ops = []

    def __getattr__(self, name):
        def op(*a, **kw):
            self._ops.append((name, a, kw))
            return self
        return op

    def execute(self):
        for name, a, kw in self._ops:
            getattr(self._r, name)(*a, **kw)
        self._ops = []


class _FakeRedis:
    """Minimal redis-protocol double covering exactly what RedisStore
    uses (get/set/sadd/smembers/srem/delete/expire/pipeline/close);
    values round-trip as bytes like the real client."""

    def __init__(self):
        self.kv = {}
        self.sets = {}
        self.ttls = {}

    def set(self, k, v, ex=None):
        self.kv[k] = v.encode() if isinstance(v, str) else v
        if ex is not None:
            self.ttls[k] = ex

    def get(self, k):
        return self.kv.get(k)

    def delete(self, k):
        self.kv.pop(k, None)
        self.sets.pop(k, None)

    def sadd(self, k, *members):
        self.sets.setdefault(k, set()).update(
            m.encode() if isinstance(m, str) else m for m in members)

    def smembers(self, k):
        return set(self.sets.get(k, set()))

    def srem(self, k, *members):
        s = self.sets.get(k, set())
        for m in members:
            s.discard(m.encode() if isinstance(m, str) else m)

    def expire(self, k, ttl):
        self.ttls[k] = ttl

    def pipeline(self):
        return _FakePipeline(self)

    def close(self):
        pass


def _real_redis():
    """A live Redis (client lib + reachable server) or None. The CI
    workflow runs a redis:7 service so TestRedisStoreReal executes
    there; locally it skips when no server is up."""
    try:
        import redis
    except ImportError:
        return None
    try:
        client = redis.Redis.from_url("redis://localhost:6379/0",
                                      socket_connect_timeout=0.3,
                                      socket_timeout=0.5)
        client.ping()
        return client
    except Exception:  # noqa: BLE001 — any failure means "unavailable"
        return None


@pytest.mark.skipif(_real_redis() is None,
                    reason="no real redis server/client available")
class TestRedisStoreReal:
    """The SAME contract as TestRedisStore, against a REAL server
   : exercises actual RESP encoding, server-side TTLs
    and set semantics the in-memory double can only approximate."""

    @pytest.fixture
    def rstore(self):
        from llmq_tpu.conversation.persistence import RedisStore
        client = _real_redis()
        store = RedisStore("redis://localhost:6379/0",
                           prefix="llmq-test:", ttl=60.0, client=client)
        yield store
        for k in client.scan_iter("llmq-test:*"):
            client.delete(k)
        store.close()

    def test_roundtrip_and_user_index(self, rstore):
        c = Conversation(id="cr1", user_id="u1")
        c.add_message("hello", "hi there")
        rstore.save(c)
        back = rstore.load("cr1")
        assert back is not None
        assert back.id == "cr1" and back.user_id == "u1"
        assert back.messages[0].content == "hello"
        assert rstore.list_user("u1") == ["cr1"]

    def test_delete_removes_blob_and_membership(self, rstore):
        for cid in ("ca", "cb"):
            rstore.save(Conversation(id=cid, user_id="u2"))
        rstore.delete("ca")
        assert rstore.load("ca") is None
        assert rstore.list_user("u2") == ["cb"]
        assert rstore.load("cb") is not None

    def test_server_side_ttl_set(self, rstore):
        rstore.save(Conversation(id="ct", user_id="u3"))
        client = _real_redis()
        ttl = client.ttl("llmq-test:ct")
        assert 0 < ttl <= 60
        uttl = client.ttl("llmq-test:user:u3")
        assert 0 < uttl <= 60


class TestRedisStore:
    """RedisStore against an injected in-memory client: exercises the
    reference's key scheme (persistence.go:46-82) — {prefix}{conv_id}
    JSON blob + {prefix}user:{uid} membership set, TTL on both."""

    @pytest.fixture
    def rstore(self):
        from llmq_tpu.conversation.persistence import RedisStore
        fake = _FakeRedis()
        return RedisStore(prefix="llmq:", ttl=3600, client=fake), fake

    def test_save_load_roundtrip(self, rstore):
        store, fake = rstore
        conv = Conversation(id="c1", user_id="u1")
        conv.messages.append(Message(id="m1", content="hi", user_id="u1"))
        store.save(conv)
        assert "llmq:c1" in fake.kv                      # blob key
        assert b"c1" in fake.sets["llmq:user:u1"]        # membership set
        assert fake.ttls["llmq:c1"] == 3600              # TTL applied
        got = store.load("c1")
        assert got is not None and got.id == "c1"
        assert got.messages[0].content == "hi"

    def test_list_user_and_delete(self, rstore):
        store, fake = rstore
        for i in range(3):
            store.save(Conversation(id=f"c{i}", user_id="u1"))
        assert store.list_user("u1") == ["c0", "c1", "c2"]
        store.delete("c1")
        assert store.load("c1") is None
        assert store.list_user("u1") == ["c0", "c2"]

    def test_state_manager_over_redis(self, fake_clock):
        """The unified conversation service runs end-to-end over the
        redis backend: restart reloads from the store."""
        from llmq_tpu.conversation.persistence import RedisStore
        fake = _FakeRedis()
        cfg = ConversationConfig(persist=True)
        sm = StateManager(cfg, store=RedisStore(client=fake),
                          clock=fake_clock)
        conv = sm.create("u9")
        sm.add_message(conv.id, Message(id="m", content="x", user_id="u9"))
        sm2 = StateManager(cfg, store=RedisStore(client=fake),
                           clock=fake_clock)
        got = sm2.get(conv.id)
        assert got is not None and got.messages[0].content == "x"
