"""Split-deployment spool transport.

The reference's split compose deployment never processes anything (its
gateway and queue-manager build independent in-process queues). These
tests drive the real transport end-to-end: producer publish → consumer
claim → local queue → worker/engine → done-ack → collector, plus the
at-least-once guarantees (claim mutual exclusion, stale-claim
reclamation, poison parking) and the App-level gateway↔consumer wiring.
"""

import os
import threading
import time


from llmq_tpu.core.types import Message, MessageStatus, Priority
from llmq_tpu.queueing.queue_manager import QueueManager
from llmq_tpu.queueing.spool import (SpoolCollector, SpoolConsumer,
                                     SpoolProducer, pending_files)
from llmq_tpu.queueing.worker import Worker


class TestSpoolCore:
    def test_publish_claim_deliver_ack_collect(self, tmp_path):
        sd = str(tmp_path / "spool")
        prod = SpoolProducer(sd)
        got = []
        cons = SpoolConsumer(sd, lambda q, m: got.append((q, m)))
        m = Message(id="m1", content="hello", priority=Priority.HIGH)
        prod.push(m, "high")
        assert pending_files(sd)
        assert cons.run_once() == 1
        assert not pending_files(sd)
        (qname, delivered), = got
        assert qname == "high"
        assert delivered.id == "m1" and delivered.content == "hello"
        assert delivered.priority == Priority.HIGH

        delivered.response = "world"
        delivered.status = MessageStatus.COMPLETED
        cons.ack_done(delivered)
        done = []
        coll = SpoolCollector(sd, done.append)
        assert coll.run_once() == 1
        assert done[0].id == "m1" and done[0].response == "world"
        assert coll.run_once() == 0        # ack consumed exactly once

    def test_priority_order_preserved_across_processes(self, tmp_path):
        sd = str(tmp_path / "spool")
        prod = SpoolProducer(sd)
        for i, prio in enumerate([Priority.LOW, Priority.REALTIME,
                                  Priority.NORMAL, Priority.HIGH]):
            prod.push(Message(id=f"m{i}", content="x", priority=prio))
        order = []
        cons = SpoolConsumer(sd, lambda q, m: order.append(m.priority))
        cons.run_once()
        assert order == sorted(order)      # realtime first, low last

    def test_claim_mutual_exclusion(self, tmp_path):
        sd = str(tmp_path / "spool")
        prod = SpoolProducer(sd)
        for i in range(20):
            prod.push(Message(id=f"m{i}", content="x"))
        seen = []
        lock = threading.Lock()

        def deliver(q, m):
            with lock:
                seen.append(m.id)

        consumers = [SpoolConsumer(sd, deliver, consumer_id=f"c{i}")
                     for i in range(3)]
        threads = [threading.Thread(target=c.run_once) for c in consumers]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(seen) == sorted(f"m{i}" for i in range(20))
        assert len(seen) == len(set(seen))  # nobody double-claimed

    def test_stale_claim_reclaimed(self, tmp_path):
        sd = str(tmp_path / "spool")
        prod = SpoolProducer(sd)
        prod.push(Message(id="m1", content="x"))

        died = SpoolConsumer(sd, lambda q, m: (_ for _ in ()).throw(
            KeyboardInterrupt()), consumer_id="dead", claim_ttl=0.1)
        # Simulate a consumer that claimed then died: rename by hand.
        name = pending_files(sd)[0]
        os.rename(os.path.join(sd, name),
                  os.path.join(sd, f"{name}.dead.claim"))
        assert not pending_files(sd)
        time.sleep(0.15)
        got = []
        cons = SpoolConsumer(sd, lambda q, m: got.append(m),
                             consumer_id="alive", claim_ttl=0.1)
        assert cons.run_once() == 1        # reclaimed + delivered
        assert got[0].id == "m1"
        del died

    def test_poison_file_parked_not_wedging(self, tmp_path):
        sd = str(tmp_path / "spool")
        prod = SpoolProducer(sd)
        with open(os.path.join(sd, "0-000-000001-bad.msg"), "w") as f:
            f.write("{not json")
        prod.push(Message(id="good", content="x"))
        got = []
        cons = SpoolConsumer(sd, lambda q, m: got.append(m.id))
        cons.run_once()
        assert got == ["good"]
        assert any(n.endswith(".poison") for n in os.listdir(sd))


class TestSplitDeployment:
    def test_gateway_to_consumer_roundtrip(self, tmp_path):
        """Two queue planes in one test process, connected ONLY by the
        spool directory — the split compose topology: gateway pushes →
        relay → spool → consumer → worker → ack → collector updates the
        gateway's message."""
        sd = str(tmp_path / "spool")

        # Gateway side.
        gw = QueueManager("gateway", enable_metrics=False)
        prod = SpoolProducer(sd)
        msg = Message(id="m1", content="ping", timeout=30.0)
        gw.push_message(msg)
        for m in gw.drain_in_priority_order(10):
            prod.push(m)

        # Consumer side: separate manager + worker + "engine".
        cm = QueueManager("consumer", enable_metrics=False)
        cons = SpoolConsumer(sd, lambda q, m: cm.push_message(m, q))

        def process(ctx, m):
            m.response = m.content + " pong"
            ack = Message.from_dict(m.to_dict())
            ack.status = MessageStatus.COMPLETED
            cons.ack_done(ack)

        w = Worker("w0", cm, process)
        assert cons.run_once() == 1
        w.process_batch()

        # Gateway collects the result.
        done = []
        coll = SpoolCollector(sd, done.append)
        assert coll.run_once() == 1
        assert done[0].response == "ping pong"
        assert done[0].status == MessageStatus.COMPLETED

    def test_app_level_split_wiring(self, tmp_path):
        """The actual entrypoint wiring: a gateway App and a
        queue-manager App (echo engine) sharing only spool_dir."""
        from llmq_tpu.__main__ import App
        from llmq_tpu.core.config import default_config

        sd = str(tmp_path / "spool")
        gcfg = default_config()
        gcfg.queue.spool_dir = sd
        gcfg.metrics.enabled = False
        gcfg.loadbalancer.health_check_interval = 0
        gateway = App(gcfg, with_api=True, with_workers=False,
                      with_engine=False)

        ccfg = default_config()
        ccfg.queue.spool_dir = sd
        ccfg.metrics.enabled = False
        ccfg.loadbalancer.health_check_interval = 0
        ccfg.queue.worker.process_interval = 0.01
        consumer = App(ccfg, with_api=False, with_workers=True,
                       with_engine=True)
        # Don't bind the API port; start only the moving parts we need.
        consumer.start()
        gateway.spool_collector.start()
        gateway._spool_relay.start()
        try:
            mgr = gateway.factory.get_queue_manager("standard")
            msg = Message(id="e2e", content="split hello", timeout=30.0)
            gateway.message_store.record(msg)
            mgr.push_message(msg)
            deadline = time.time() + 15.0
            while (msg.status != MessageStatus.COMPLETED
                   and time.time() < deadline):
                time.sleep(0.05)
            assert msg.status == MessageStatus.COMPLETED
            assert msg.response          # echo of the prompt
            assert msg.metadata["usage"]["completion_tokens"] > 0
            # Gateway queue stats saw the completion.
            stats = mgr.get_stats("normal")
            assert stats.completed_count == 1
        finally:
            gateway._stop.set()
            gateway.spool_collector.stop()
            consumer.stop()
