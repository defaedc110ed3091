"""Foundation tests: data model, config tree, clocks.

Covers the surface of reference pkg/models/message.go and
pkg/config/config.go (the reference has no tests for either)."""

import os

import pytest

from llmq_tpu.core.clock import FakeClock
from llmq_tpu.core.config import (
    Config,
    default_config,
    load_config,
)
from llmq_tpu.core.types import (
    Conversation,
    ConversationState,
    Message,
    MessageStatus,
    Priority,
    PRIORITY_TIERS,
)


class TestPriority:
    def test_ordering(self):
        # Lower value = more urgent (reference message.go:15-22).
        assert Priority.REALTIME < Priority.HIGH < Priority.NORMAL < Priority.LOW

    def test_tier_names(self):
        assert PRIORITY_TIERS == ("realtime", "high", "normal", "low")
        assert Priority.REALTIME.tier_name == "realtime"

    def test_parse(self):
        assert Priority.parse("2") == Priority.HIGH
        assert Priority.parse("high") == Priority.HIGH
        assert Priority.parse(3) == Priority.NORMAL
        assert Priority.parse(Priority.LOW) == Priority.LOW
        with pytest.raises(ValueError):
            Priority.parse("urgent-ish")


class TestMessage:
    def test_defaults(self):
        # max_retries=3, timeout=30s (reference message.go:76-91).
        m = Message(content="hi")
        assert m.max_retries == 3
        assert m.timeout == 30.0
        assert m.status == MessageStatus.PENDING
        assert m.priority == Priority.NORMAL
        assert m.id  # uuid assigned

    def test_roundtrip(self):
        m = Message(content="hello", priority=Priority.HIGH,
                    metadata={"user_priority": 1})
        m2 = Message.from_dict(m.to_dict())
        assert m2.id == m.id
        assert m2.priority == Priority.HIGH
        assert m2.metadata == {"user_priority": 1}

    def test_can_retry(self):
        m = Message(max_retries=2)
        assert m.can_retry()
        m.retry_count = 2
        assert not m.can_retry()


class TestConversation:
    def test_roundtrip(self):
        c = Conversation(user_id="u1")
        c.messages.append(Message(content="hi", conversation_id=c.id))
        d = c.to_dict()
        assert d["message_count"] == 1
        c2 = Conversation.from_dict(d)
        assert c2.id == c.id and len(c2.messages) == 1
        assert c2.state == ConversationState.ACTIVE


class TestConfig:
    def test_defaults_match_reference(self):
        # The canonical 4 tiers (reference config.go:151-156).
        cfg = default_config()
        tiers = {lvl.priority: lvl for lvl in cfg.queue.levels}
        assert tiers[1].max_wait_time == 1.0 and tiers[1].max_concurrent == 100
        assert tiers[2].max_wait_time == 5.0 and tiers[2].max_concurrent == 200
        assert tiers[3].max_wait_time == 30.0 and tiers[3].max_concurrent == 500
        assert tiers[4].max_wait_time == 300.0 and tiers[4].max_concurrent == 1000
        # Worker defaults (config.go:169-173).
        assert cfg.queue.worker.max_batch_size == 10
        assert cfg.queue.worker.process_interval == 0.1
        assert cfg.queue.worker.max_concurrent == 50
        # Retry defaults (config.go:174-179).
        assert cfg.queue.retry.initial_backoff == 1.0
        assert cfg.queue.retry.max_backoff == 60.0
        assert cfg.queue.retry.backoff_multiplier == 2.0
        assert cfg.queue.retry.max_retries == 3

    def test_yaml_load_and_env_override(self, tmp_path, monkeypatch):
        p = tmp_path / "c.yaml"
        p.write_text("server: {port: 9999}\nqueue: {max_queue_size: 42}\n")
        monkeypatch.setenv("LLMQ_SERVER_HOST", "1.2.3.4")
        monkeypatch.setenv("LLMQ_QUEUE_WORKER_MAX_CONCURRENT", "7")
        cfg = load_config(str(p))
        assert cfg.server.port == 9999
        assert cfg.queue.max_queue_size == 42
        assert cfg.server.host == "1.2.3.4"
        assert cfg.queue.worker.max_concurrent == 7

    def test_unknown_strategy_rejected(self):
        # The reference silently falls back on unknown strategy names
        # (scheduler.go:105-107, load_balancer.go:272-274); we raise.
        from llmq_tpu.core.config import LoadBalancerConfig
        with pytest.raises(ValueError):
            LoadBalancerConfig(strategy="weighted_round_robin")

    def test_unknown_key_rejected(self, tmp_path):
        p = tmp_path / "c.yaml"
        p.write_text("serverr: {port: 1}\n")
        with pytest.raises(ValueError):
            load_config(str(p))

    def test_a_removed_option_is_refused_by_name(self, tmp_path):
        """``executor.speculation`` left with PR 44: a YAML that still
        names it fails at load, with the key, rather than being read
        past (docs/configuration.md)."""
        p = tmp_path / "c.yaml"
        p.write_text("executor:\n  speculation: {enabled: false}\n")
        with pytest.raises(ValueError, match=r"executor\.speculation"):
            load_config(str(p))

    def test_repo_canonical_config_loads(self):
        path = os.path.join(os.path.dirname(__file__), "..", "configs", "config.yaml")
        cfg = load_config(path, env=False)
        assert isinstance(cfg, Config)
        assert cfg.loadbalancer.strategy == "adaptive_load"


class TestFakeClock:
    def test_advance(self):
        clk = FakeClock(start=100.0)
        assert clk.now() == 100.0
        clk.advance(5.0)
        assert clk.now() == 105.0

    def test_callbacks(self):
        clk = FakeClock(start=0.0)
        fired = []
        clk.call_at(10.0, lambda: fired.append(1))
        clk.advance(5.0)
        assert not fired
        clk.advance(5.0)
        assert fired == [1]
