"""Multi-engine serving through the LoadBalancer.

End-to-end on the message path the reference never wires (SURVEY §3.5):
QueueManager → Worker → EngineRouter.process_fn → LoadBalancer
get_endpoint → engine.process_fn → release_endpoint. Covers conversation
affinity across replicas, per-endpoint load feedback, and failover when
an engine dies (health state machine → UNHEALTHY → traffic moves).
"""


import pytest

from llmq_tpu.core.config import LoadBalancerConfig
from llmq_tpu.core.types import Message, MessageStatus
from llmq_tpu.engine.engine import InferenceEngine
from llmq_tpu.engine.executor import EchoExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.loadbalancer import EndpointStatus, EngineRouter, LoadBalancer
from llmq_tpu.queueing.queue_manager import QueueManager
from llmq_tpu.queueing.worker import Worker


def make_engine(name: str) -> InferenceEngine:
    tok = ByteTokenizer()
    ex = EchoExecutor(batch_size=4, page_size=8, num_pages=128,
                      max_pages_per_seq=16, eos_id=tok.eos_id)
    eng = InferenceEngine(ex, tok, name=name, enable_metrics=False,
                          max_decode_steps=32)
    eng.start()
    return eng


@pytest.fixture
def duo():
    """Two live echo engines behind one LoadBalancer + router."""
    lb = LoadBalancer(LoadBalancerConfig(strategy="round_robin",
                                         health_check_interval=0))
    router = EngineRouter(lb)
    engines = [make_engine("e0"), make_engine("e1")]
    for e in engines:
        router.register_engine(e)
    yield lb, router, engines
    for e in engines:
        e.stop()


class TestEngineRouter:
    def test_messages_route_across_engines(self, duo):
        lb, router, engines = duo
        qm = QueueManager("routed", enable_metrics=False)
        w = Worker("w0", qm, router.process_fn)
        msgs = [Message(id=f"m{i}", content=f"hello {i}", timeout=30.0)
                for i in range(6)]
        for m in msgs:
            qm.push_message(m)
        w.process_batch()
        assert all(m.status == MessageStatus.COMPLETED for m in msgs)
        assert all(m.response for m in msgs)
        # Round-robin spread both engines.
        used = {m.metadata["endpoint_id"] for m in msgs}
        assert used == {"e0", "e1"}
        stats = {ep.id: ep.total_requests for ep in lb.endpoints()}
        assert stats["e0"] == 3 and stats["e1"] == 3
        # Response-time EWMA fed back on release.
        assert all(ep.response_time > 0 for ep in lb.endpoints())

    def test_conversation_affinity_pins_replica(self, duo):
        lb, router, engines = duo
        qm = QueueManager("conv", enable_metrics=False)
        w = Worker("w0", qm, router.process_fn)
        # Interleave two conversations; every turn of a conversation
        # must land on the engine holding its KV.
        msgs = []
        for turn in range(3):
            for conv in ("ca", "cb"):
                m = Message(id=f"{conv}-{turn}", content=f"turn {turn}",
                            conversation_id=conv, timeout=30.0)
                msgs.append(m)
                qm.push_message(m)
                w.process_batch()
        by_conv = {}
        for m in msgs:
            by_conv.setdefault(m.conversation_id, set()).add(
                m.metadata["endpoint_id"])
        assert all(len(eps) == 1 for eps in by_conv.values()), by_conv
        # The pinned engine actually reused the conversation KV.
        for conv, (eid,) in ((c, tuple(e)) for c, e in by_conv.items()):
            eng = next(e for e in engines if e.name == eid)
            assert conv in eng.cached_conversations()

    def test_dead_engine_fails_over(self, duo):
        lb, router, engines = duo
        e0, e1 = engines
        e0.stop()                      # killed replica
        # Health state machine: consecutive failures → UNHEALTHY.
        for _ in range(5):
            lb.check_health_once()
        assert lb.get_endpoint_by_id("e0").status == EndpointStatus.UNHEALTHY
        assert lb.get_endpoint_by_id("e1").status == EndpointStatus.HEALTHY

        qm = QueueManager("failover", enable_metrics=False)
        w = Worker("w0", qm, router.process_fn)
        msgs = [Message(id=f"f{i}", content="x", timeout=30.0)
                for i in range(4)]
        for m in msgs:
            qm.push_message(m)
        w.process_batch()
        assert all(m.status == MessageStatus.COMPLETED for m in msgs)
        assert {m.metadata["endpoint_id"] for m in msgs} == {"e1"}

        # Recovery: restart e0, probes pass, traffic returns (through
        # DEGRADED first, per the state machine).
        e0.start()
        for _ in range(6):
            lb.check_health_once()
        assert lb.get_endpoint_by_id("e0").status in (
            EndpointStatus.HEALTHY, EndpointStatus.DEGRADED)
        more = [Message(id=f"r{i}", content="x", timeout=30.0)
                for i in range(4)]
        for m in more:
            qm.push_message(m)
        w.process_batch()
        assert {m.metadata["endpoint_id"] for m in more} == {"e0", "e1"}

    def test_affinity_failover_rebuilds_conversation(self, duo):
        """A conversation pinned to a replica that dies continues on the
        surviving one via the history_text fallback path."""
        lb, router, engines = duo
        e0, e1 = engines
        qm = QueueManager("cf", enable_metrics=False)
        w = Worker("w0", qm, router.process_fn)
        m1 = Message(id="t1", content="first turn", conversation_id="cx",
                     timeout=30.0)
        qm.push_message(m1)
        w.process_batch()
        first_ep = m1.metadata["endpoint_id"]
        dead = next(e for e in engines if e.name == first_ep)
        alive = next(e for e in engines if e.name != first_ep)
        dead.stop()
        for _ in range(5):
            lb.check_health_once()
        m2 = Message(id="t2", content="second turn", conversation_id="cx",
                     timeout=30.0,
                     metadata={"history_text": m1.content + m1.response})
        qm.push_message(m2)
        w.process_batch()
        assert m2.status == MessageStatus.COMPLETED
        assert m2.metadata["endpoint_id"] == alive.name


class TestRouterErrors:
    def test_engine_error_feeds_error_rate(self):
        lb = LoadBalancer(LoadBalancerConfig(strategy="round_robin",
                                             health_check_interval=0))
        router = EngineRouter(lb)
        eng = make_engine("solo")
        router.register_engine(eng)
        qm = QueueManager("err", enable_metrics=False)
        qm.config.queue.retry.max_retries = 0

        def broken(ctx, msg):
            raise RuntimeError("endpoint exploded")

        eng.process_fn = broken
        w = Worker("w0", qm, router.process_fn)
        m = Message(id="boom", content="x", timeout=5.0, max_retries=0)
        qm.push_message(m)
        w.process_batch()
        assert m.status in (MessageStatus.FAILED, MessageStatus.TIMEOUT)
        ep = lb.get_endpoint_by_id("solo")
        assert ep.total_errors == 1 and ep.error_rate > 0
        eng.stop()
