"""Self-healing control plane (docs/controlplane.md).

Covers the reconcile loop (burn/backlog scale-up, idle scale-down with
the measured-capacity guard, cooldown + action rate limit), the
degradation ladder's hysteresis and its admission actuation at the
overload shedder, replica pools (local engines + exec contract), the
operator surfaces (overview block, pause/resume, /health visibility),
the autoscaler clock-discipline satellite — and the two CHAOS
scenarios the acceptance criteria pin: a seeded replica kill
mid-stream at 2-chunk pipeline depth (controller replaces it,
InvariantChecker proves zero-loss/zero-dup/monotone, recovery lands
inside the configured budget) and a flapping replica (breaker +
controller don't thrash: the scale-action rate limit holds).
"""

from __future__ import annotations

import json
import time
from typing import Dict, Optional

import pytest

from llmq_tpu import chaos
from llmq_tpu.api.overload import OverloadShedder
from llmq_tpu.api.server import ApiServer
from llmq_tpu.chaos import InvariantChecker
from llmq_tpu.cluster.router import ClusterRouter
from llmq_tpu.controlplane import (DegradationLadder, LocalEnginePool,
                                   ReplicaController, build_controller)
from llmq_tpu.controlplane.pool import ExecReplicaPool
from llmq_tpu.core.clock import FakeClock
from llmq_tpu.core.config import (BreakerConfig, ChaosConfig,
                                  ClusterConfig, ControlPlaneConfig,
                                  LoadBalancerConfig, OverloadConfig,
                                  ReplicaPoolConfig, SupervisorConfig,
                                  default_config, default_rungs)
from llmq_tpu.core.types import Message, Priority
from llmq_tpu.engine import ByteTokenizer, EchoExecutor, InferenceEngine
from llmq_tpu.loadbalancer.load_balancer import (Endpoint,
                                                 EndpointStatus,
                                                 LoadBalancer)
from llmq_tpu.queueing.dead_letter_queue import DeadLetterQueue
from llmq_tpu.queueing.queue_manager import QueueManager
from llmq_tpu.queueing.worker import Worker

pytestmark = [
    # The chaos kill scenario crashes engine threads on purpose.
    pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"),
]


@pytest.fixture(autouse=True)
def _chaos_reset():
    yield
    chaos.configure(None)


class FakeBurn:
    """Injectable SLO-tracker stand-in: tests set the burn directly."""

    def __init__(self) -> None:
        self.fast = 0.0
        self.slow = 0.0

    def burn_rates(self) -> Dict:
        return {"ttft": {"5m": {"burn_rate": self.fast},
                         "1h": {"burn_rate": self.slow}}}


class FakeManager:
    def __init__(self, pending: int = 0) -> None:
        self.pending = pending

    def total_pending(self) -> int:
        return self.pending


def _echo_engine(name: str, *, pipelined: bool = False,
                 step_delay_s: float = 0.0) -> InferenceEngine:
    from llmq_tpu.core.config import AsyncPipelineConfig
    tok = ByteTokenizer()
    ex = EchoExecutor(batch_size=8, page_size=8, num_pages=512,
                      max_pages_per_seq=16, eos_id=tok.eos_id,
                      chunk_size=4, async_chunks=pipelined,
                      step_delay_s=step_delay_s)
    return InferenceEngine(
        ex, tok, name=name, enable_metrics=False, max_decode_steps=32,
        async_pipeline=(AsyncPipelineConfig(enabled=True, depth=2)
                        if pipelined else None))


def _router(**cluster_kw) -> ClusterRouter:
    lb = LoadBalancer(LoadBalancerConfig(strategy="round_robin",
                                         health_check_interval=0.0))
    cluster_kw.setdefault("failover_retries", 3)
    cluster_kw.setdefault(
        "breaker", BreakerConfig(failure_threshold=3,
                                 base_backoff=0.05, jitter=0.2))
    return ClusterRouter(lb, config=ClusterConfig(**cluster_kw),
                         enable_metrics=False)


def _controller(router, *, pool=None, cfg: Optional[ControlPlaneConfig] = None,
                burn: Optional[FakeBurn] = None,
                manager=None, shedder=None, clock=None,
                supervisor=None) -> ReplicaController:
    return ReplicaController(
        config=cfg or ControlPlaneConfig(enabled=True, interval=0.0),
        router=router, pool=pool, queue_manager=manager,
        shedder=shedder, slo_tracker=burn or FakeBurn(),
        supervisor=supervisor, clock=clock, enable_metrics=False)


def _pool(prefix: str = "pool", *, pipelined: bool = False,
          max_restarts: int = 0) -> LocalEnginePool:
    def factory(seq: int) -> InferenceEngine:
        return _echo_engine(f"{prefix}-{seq}", pipelined=pipelined)

    return LocalEnginePool(
        factory, supervise=True,
        supervisor_config=SupervisorConfig(check_interval=0.02,
                                           max_restarts=max_restarts))


def _stack(process_like, checker, name: str, *, backoff: float = 0.05):
    """QueueManager + Worker + DLQ wired into the invariant checker
    (the chaos-plane harness pattern from tests/test_chaos.py)."""
    cfg = default_config()
    cfg.queue.enable_metrics = False
    cfg.queue.worker.process_interval = 0.005
    cfg.queue.retry.initial_backoff = backoff
    cfg.queue.retry.max_backoff = backoff * 4
    mgr = QueueManager(name, config=cfg, enable_metrics=False)
    dlq = DeadLetterQueue(name=f"{name}-dlq")
    dlq.add_handler(lambda item: checker.dead_lettered(item.message.id))
    orig_complete = mgr.complete_message

    def complete(m, t=0.0, q=None):
        checker.completed(m.id)
        orig_complete(m, t, q)

    mgr.complete_message = complete
    worker = Worker("w0", mgr, process_like.process_fn,
                    dead_letter_queue=dlq)
    return mgr, worker, dlq


def _await(pred, timeout: float = 30.0, msg: str = "condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


# -- reconcile-loop unit behavior ---------------------------------------------

class TestReconcile:
    def test_bootstrap_to_min_replicas(self):
        router = _router()
        ctl = _controller(router, pool=_pool("boot"),
                          cfg=ControlPlaneConfig(
                              enabled=True, interval=0.0,
                              min_replicas=2, max_replicas=4))
        try:
            out = ctl.run_once()
            assert out["target"] == 2
            assert len(router.lb.endpoints()) == 2
            assert [a for a, _ in out["actions"]].count("scale_up") == 2
        finally:
            ctl.stop()

    def test_burn_drives_scale_up_with_cooldown(self):
        clock = FakeClock()
        burn = FakeBurn()
        router = _router()
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 min_replicas=1, max_replicas=4,
                                 cooldown=10.0)
        ctl = _controller(router, pool=_pool("burnup"), cfg=cfg,
                          burn=burn, clock=clock)
        try:
            ctl.run_once()                       # bootstrap → 1
            assert ctl.target == 1
            burn.fast = cfg.fast_burn_threshold + 1
            out = ctl.run_once()
            assert ("scale_up", "burn_fast") in out["actions"]
            assert ctl.target == 2
            # Cooldown: the very next hot tick must NOT scale again.
            out = ctl.run_once()
            assert ("skip", "cooldown") in out["actions"]
            assert ctl.target == 2
            clock.advance(11.0)
            out = ctl.run_once()
            assert ctl.target == 3
            # Slow-window burn is its own trigger.
            burn.fast = 0.0
            burn.slow = cfg.slow_burn_threshold + 1
            clock.advance(11.0)
            out = ctl.run_once()
            assert ("scale_up", "burn_slow") in out["actions"]
            assert ctl.target == 4
            # max_replicas is a hard ceiling.
            clock.advance(11.0)
            out = ctl.run_once()
            assert ctl.target == 4
        finally:
            ctl.stop()

    def test_backlog_drives_scale_up(self):
        clock = FakeClock()
        router = _router()
        mgr = FakeManager(pending=1000)
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 backlog_per_replica=64,
                                 max_replicas=4, cooldown=0.0)
        ctl = _controller(router, pool=_pool("backlog"), cfg=cfg,
                          manager=mgr, clock=clock)
        try:
            ctl.run_once()
            out = ctl.run_once()
            assert ("scale_up", "backlog") in out["actions"]
        finally:
            ctl.stop()

    def test_idle_scale_down_drains_then_decommissions(self):
        clock = FakeClock()
        router = _router()
        pool = _pool("down")
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 min_replicas=1, max_replicas=4,
                                 cooldown=5.0)
        ctl = _controller(router, pool=pool, cfg=cfg, clock=clock,
                          manager=FakeManager(0))
        try:
            ctl.run_once()
            ctl.target = 3
            ctl.run_once()                       # provisions to 3
            assert len(router.lb.endpoints()) == 3
            clock.advance(6.0)
            out = ctl.run_once()                 # idle → drain one
            assert ("scale_down", "idle") in out["actions"]
            assert ctl.target == 2
            draining = [e for e in router.lb.endpoints()
                        if e.status == EndpointStatus.DRAINING]
            assert len(draining) == 1
            out = ctl.run_once()                 # idle endpoint reaped
            eps = router.lb.endpoints()           # (cooldown holds the
            assert len(eps) == 2                  # next scale-down)
            assert all(e.status != EndpointStatus.DRAINING
                       for e in eps)
            assert pool.decommissioned == 1
            # Keep idling: converges to min_replicas and STOPS there.
            for _ in range(6):
                clock.advance(6.0)
                ctl.run_once()
            assert ctl.target == 1
            assert len(router.lb.endpoints()) == 1
        finally:
            ctl.stop()

    def test_capacity_guard_blocks_scale_down(self):
        """The measured tokens/s must keep headroom after a drain —
        otherwise the idle branch is vetoed (reason=capacity skip)."""
        clock = FakeClock()
        router = _router()
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 min_replicas=1, cooldown=0.0,
                                 scale_down_headroom=1.5)
        ctl = _controller(router, pool=_pool("cap"), cfg=cfg,
                          clock=clock, manager=FakeManager(0))
        try:
            ctl.run_once()
            ctl.target = 2
            ctl.run_once()
            # Simulate a measured-load observation: peak 100 tok/s per
            # replica, current load 150 tok/s → one replica (100) can't
            # cover 150×1.5; the guard must veto.
            ctl._peak_replica_tok_s = 100.0
            obs = {"tokens_per_s": 150.0}
            assert not ctl._capacity_allows_scale_down(obs, healthy_n=2)
            assert ctl.action_counts.get("skip:capacity") == 1
            # Load falls → scale-down allowed again.
            assert ctl._capacity_allows_scale_down(
                {"tokens_per_s": 40.0}, healthy_n=2)
        finally:
            ctl.stop()

    def test_action_rate_limit_holds(self):
        """The thrash guard: no more than max_actions_per_minute
        scale/replace actions in any rolling 60s window."""
        clock = FakeClock()
        burn = FakeBurn()
        router = _router()
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 min_replicas=1, max_replicas=8,
                                 cooldown=0.0, max_actions_per_minute=3)
        ctl = _controller(router, pool=_pool("thrash"), cfg=cfg,
                          burn=burn, clock=clock)
        try:
            ctl.run_once()                       # bootstrap (1 action)
            burn.fast = 100.0
            for _ in range(10):
                ctl.run_once()
                clock.advance(0.5)
            assert ctl.scale_action_total() <= 3
            assert ctl.action_counts.get("skip:rate_limited", 0) > 0
            # Window expires → actions resume.
            clock.advance(61.0)
            out = ctl.run_once()
            assert ("scale_up", "burn_fast") in out["actions"]
            # <= 0 disables the limit entirely (repo "0 = unlimited").
            ctl.config.max_actions_per_minute = 0
            before = ctl.scale_action_total()
            for _ in range(4):
                ctl.run_once()
            assert ctl.scale_action_total() >= before + 3
        finally:
            ctl.stop()

    def test_down_static_peer_does_not_pin_fleet_or_recovery(self):
        """An UNHEALTHY endpoint the controller does NOT own (a static
        peer) must block neither idle scale-down nor recovery
        completion — it is not the controller's to fix."""
        clock = FakeClock()
        router = _router()
        dead_peer = Endpoint(id="peer-down", url="http://10.0.0.9:1",
                             status=EndpointStatus.UNHEALTHY)
        router.lb.add_endpoint(dead_peer)
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 min_replicas=1, max_replicas=4,
                                 cooldown=5.0)
        ctl = _controller(router, pool=_pool("peerdown"), cfg=cfg,
                          clock=clock, manager=FakeManager(0))
        try:
            ctl.run_once()
            ctl.target = 3
            ctl.run_once()
            clock.advance(6.0)
            out = ctl.run_once()         # idle despite the dead peer
            assert ("scale_down", "idle") in out["actions"]
            # Recovery must also close over the dead peer: simulate a
            # replacement having happened.
            ctl._recovering_since = clock.now() - 2.0
            clock.advance(6.0)
            ctl.run_once()
            assert ctl.last_recovery_s is not None
        finally:
            ctl.stop()

    def test_pause_still_reaps_inflight_drain(self):
        """Pause stops NEW decisions; a drain already in flight is
        still completed (a drained replica must not burn
        replica-seconds for the whole pause)."""
        clock = FakeClock()
        router = _router()
        pool = _pool("pausedrain")
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 min_replicas=1, cooldown=5.0)
        ctl = _controller(router, pool=pool, cfg=cfg, clock=clock,
                          manager=FakeManager(0))
        try:
            ctl.run_once()
            ctl.target = 2
            ctl.run_once()
            clock.advance(6.0)
            out = ctl.run_once()         # starts the drain
            assert ("scale_down", "idle") in out["actions"]
            ctl.pause()
            out = ctl.run_once()         # paused tick reaps it
            assert out["paused"] is True
            assert len(router.lb.endpoints()) == 1
            assert pool.decommissioned == 1
        finally:
            ctl.stop()

    def test_paused_observes_but_never_acts(self):
        burn = FakeBurn()
        router = _router()
        ctl = _controller(router, pool=_pool("paused"), burn=burn,
                          cfg=ControlPlaneConfig(
                              enabled=True, interval=0.0,
                              min_replicas=2, cooldown=0.0))
        try:
            ctl.pause()
            burn.fast = 100.0
            out = ctl.run_once()
            assert out["paused"] is True
            assert out["actions"] == []
            assert len(router.lb.endpoints()) == 0   # nothing built
            snap = ctl.snapshot()
            assert snap["paused"] is True
            assert snap["inputs"]["fast_burn"] == 100.0  # still fresh
            burn.fast = 0.0
            ctl.resume()
            out = ctl.run_once()
            assert out["paused"] is False
            assert len(router.lb.endpoints()) == 2   # acts again
            assert ctl.action_counts.get("pause:operator") == 1
            assert ctl.action_counts.get("resume:operator") == 1
        finally:
            ctl.stop()


# -- degradation ladder -------------------------------------------------------

class TestLadder:
    def _shedder(self, registry=None):
        cfg = OverloadConfig(enabled=True, queue_depth_limit=100,
                             deadline_headroom=1.0)
        return OverloadShedder(cfg, None, tenant_registry=registry,
                               enable_metrics=False)

    def test_hysteresis_escalate_and_relax(self):
        ladder = DegradationLadder(default_rungs(),
                                   relax_after_ticks=3)
        assert ladder.tick(hot=True, calm=False) == "escalate"
        assert ladder.level == 1
        assert ladder.tick(hot=True, calm=False) == "escalate"
        assert ladder.tick(hot=True, calm=False) == "escalate"
        assert ladder.level == 3
        assert ladder.tick(hot=True, calm=False) is None  # top rung
        # Two calm ticks then a neutral tick: the streak resets.
        assert ladder.tick(hot=False, calm=True) is None
        assert ladder.tick(hot=False, calm=True) is None
        assert ladder.tick(hot=False, calm=False) is None
        assert ladder.level == 3
        # Three CONSECUTIVE calm ticks relax exactly one rung.
        for _ in range(2):
            assert ladder.tick(hot=False, calm=True) is None
        assert ladder.tick(hot=False, calm=True) == "relax"
        assert ladder.level == 2

    def test_rungs_tighten_admission_in_order(self):
        """Rung 2 sheds the batch tier with an explicit 429
        reason=degraded; rung 0 restores byte-identical admission."""
        from llmq_tpu.api.server import ApiError
        shedder = self._shedder()
        ladder = DegradationLadder(default_rungs(), shedder=shedder,
                                   relax_after_ticks=1)
        low = Message(id="m-low", content="x", user_id="u",
                      priority=Priority.LOW)
        rt = Message(id="m-rt", content="x", user_id="u",
                     priority=Priority.REALTIME)
        shedder.admit(low, None, 0.0)            # level 0: admitted
        ladder.tick(hot=True, calm=False)        # rung 1: tighten only
        shedder.admit(low, None, 0.0)            # still admitted
        ladder.tick(hot=True, calm=False)        # rung 2: shed batch
        with pytest.raises(ApiError) as ei:
            shedder.admit(low, None, 0.0)
        assert ei.value.status == 429
        assert "degraded" in ei.value.message
        shedder.admit(rt, None, 0.0)             # realtime survives
        assert shedder.get_stats()["shed"]["degraded"] == 1
        assert shedder.get_stats()["degradation"] == "shed_batch"
        ladder.tick(hot=False, calm=True)        # relax → rung 1
        shedder.admit(low, None, 0.0)            # batch admitted again
        ladder.tick(hot=False, calm=True)        # rung 0
        assert shedder._degradation is None      # noqa: SLF001
        assert shedder.get_stats()["degradation"] is None

    def test_backlog_and_headroom_factors_scale_thresholds(self):
        from llmq_tpu.api.server import ApiError
        shedder = self._shedder()
        mgr = FakeManager(pending=80)            # under the 100 limit
        msg = Message(id="m0", content="x", user_id="u")
        shedder.admit(msg, mgr, 0.0)             # admitted at level 0
        shedder.set_degradation({"name": "tighten",
                                 "backlog_factor": 0.7})
        with pytest.raises(ApiError) as ei:      # 80 >= 100×0.7
            shedder.admit(msg, mgr, 0.0)
        assert ei.value.status == 429
        assert "backlog" in ei.value.message

    def test_low_weight_tenants_shed_last_rung(self):
        from llmq_tpu import tenancy
        from llmq_tpu.api.server import ApiError
        from llmq_tpu.core.config import TenancyConfig
        reg = tenancy.configure_tenancy(TenancyConfig(
            enabled=True,
            tenants={"gold": {"weight": 4.0},
                     "bronze": {"weight": 0.5}}))
        try:
            shedder = self._shedder(registry=reg)
            shedder.set_degradation(default_rungs()[2])
            gold = Message(id="g0", content="x", user_id="u",
                           priority=Priority.REALTIME,
                           tenant_id="gold")
            bronze = Message(id="b0", content="x", user_id="u",
                             priority=Priority.REALTIME,
                             tenant_id="bronze")
            shedder.admit(gold, None, 0.0)       # weight 4 ≥ 1.0: kept
            with pytest.raises(ApiError) as ei:  # weight .5 < 1.0: shed
                shedder.admit(bronze, None, 0.0)
            assert ei.value.status == 429
            assert "weight" in ei.value.message
        finally:
            tenancy.reset_tenancy()

    def test_controller_ladder_integration(self):
        """Hot burn escalates before scaling alone can help; calm burn
        relaxes in reverse order — all through run_once."""
        clock = FakeClock()
        burn = FakeBurn()
        router = _router()
        shedder = self._shedder()
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 min_replicas=1, max_replicas=1,
                                 cooldown=0.0, relax_after_ticks=2)
        ctl = _controller(router, pool=_pool("lad"), cfg=cfg,
                          burn=burn, clock=clock, shedder=shedder)
        try:
            ctl.run_once()
            burn.fast = 2.0                      # ≥ escalate_burn
            out = ctl.run_once()
            assert ("escalate", "burn_fast") in out["actions"]
            assert ctl.ladder.level == 1
            assert shedder._degradation is not None  # noqa: SLF001
            burn.fast = 0.0
            ctl.run_once()
            out = ctl.run_once()
            assert ("relax", "recovered") in out["actions"]
            assert ctl.ladder.level == 0
            assert shedder._degradation is None  # noqa: SLF001
        finally:
            ctl.stop()


# -- chaos scenarios (the acceptance criteria) --------------------------------

class TestChaosRecovery:
    @pytest.mark.chaos
    def test_kill_replica_mid_stream_controller_restores_slo(self):
        """THE acceptance scenario: a seeded EngineCrash kills replica
        pool-1 mid-stream with the async pipeline at 2 chunks in
        flight. Its supervisor gives up (fails out of rotation), the
        controller decommissions and replaces it, failover + retry own
        the in-between — and the InvariantChecker proves zero loss,
        zero duplicate completions, monotone streams, with recovery
        (kill→target-met, burn < 1.0 on the fast window) inside the
        configured budget."""
        chaos.configure(ChaosConfig(enabled=True, seed=11, faults=[
            {"point": "engine.step", "kind": "crash", "times": 1,
             "after": 8, "match": {"engine": "kill-1"}}]))
        checker = InvariantChecker()
        router = _router()
        pool = _pool("kill", pipelined=True, max_restarts=0)
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 min_replicas=2, max_replicas=3,
                                 cooldown=0.0, recovery_budget_s=20.0)
        ctl = _controller(router, pool=pool, cfg=cfg)
        mgr, worker, dlq = _stack(router, checker, "killrec")
        t_kill: Dict[str, float] = {}
        try:
            ctl.run_once()                       # bootstrap 2 replicas
            assert len(router.lb.endpoints()) == 2
            # A LIVE token stream on the doomed replica: the crash
            # lands mid-stream (2 chunks carried in flight), and
            # the monotone invariant must hold — streamed tokens are a
            # prefix of the recorded result, never replayed/extended.
            from llmq_tpu.engine.engine import GenRequest
            doomed = router.lb.get_endpoint_by_id("kill-1") \
                .metadata["engine"]
            sh = doomed.submit(
                GenRequest(id="stream0", prompt="stream through the "
                                                "kill " * 4,
                           max_new_tokens=32),
                on_token=checker.on_token("stream0"))
            checker.submitted("stream0")
            worker.start()
            for i in range(14):
                m = Message(id=f"k{i}", content=f"kill payload {i} " * 3,
                            user_id="u", timeout=25.0)
                checker.submitted(m.id)
                mgr.push_message(m)
            # Tick until the crash fires and the replica is replaced.
            deadline = time.time() + 20.0
            replaced = False
            while time.time() < deadline:
                ctl.run_once()
                if not replaced and ctl.action_counts.get(
                        "replace:replica_dead"):
                    replaced = True
                    t_kill["replaced_at"] = time.time()
                s = checker.summary()
                if (replaced
                        and sum(s["terminal"].values()) >= 14
                        and ctl.last_recovery_s is not None):
                    break
                time.sleep(0.05)
            # The mid-stream request died with the replica: its handle
            # was failed by the supervisor/decommission recovery, its
            # streamed tokens a PREFIX of the recorded result.
            assert sh.wait(5.0)
            assert sh.result.finish_reason == "error"
            assert len(checker._streams.get("stream0", [])) >= 2, \
                "crash was not mid-stream"
            checker.failed("stream0")
            checker.completed("stream0", tokens=sh.result.tokens)
            # (the completed record above only carries result tokens
            # for the monotone check — same terminal as the failure)
            checker._terminal["stream0"].remove("completed")
            s = checker.summary()
            assert sum(s["terminal"].values()) >= 15, s
        finally:
            worker.stop()
            mgr.stop()
            ctl.stop()
        checker.check()                  # zero loss/dup + monotone
        total = (s["terminal"].get("completed", 0)
                 + s["terminal"].get("dead_lettered", 0))
        assert total == 14, s            # every queued request landed
        assert s["terminal"].get("failed", 0) == 1   # the dead stream
        assert dlq.size() == 0                   # nothing even parked
        # The chaos plane really killed the engine…
        inj = chaos.get_injector()
        assert inj.get_stats()["injected"].get("engine.step:crash") == 1
        # …and the controller really replaced it.
        assert ctl.action_counts.get("replace:replica_dead", 0) >= 1
        # Recovery (replacement → back at target with burn<1) landed
        # inside the budget.
        assert ctl.last_recovery_s is not None
        assert ctl.last_recovery_s <= cfg.recovery_budget_s, \
            ctl.last_recovery_s
        # The cluster is whole again: 2 healthy replicas, pool-3 is
        # the replacement.
        eps = router.lb.endpoints()
        assert len(eps) == 2
        assert all(e.status in (EndpointStatus.HEALTHY,
                                EndpointStatus.DEGRADED) for e in eps)
        assert pool.get_stats()["provisioned"] == 3

    @pytest.mark.chaos
    def test_flapping_replica_breaker_and_controller_dont_thrash(self):
        """A flapping HTTP replica (seeded p=0.4 transport faults):
        breakers absorb the flaps, dispatch keeps succeeding via
        failover, and the controller neither replaces the flapping
        replica (its /health stays green) nor thrashes scale actions —
        the rate limit holds."""
        chaos.configure(ChaosConfig(enabled=True, seed=21, faults=[
            {"point": "transport.request", "kind": "error",
             "probability": 0.4}]))
        checker = InvariantChecker()
        engines, servers, urls = [], [], []
        for i in range(2):
            eng = _echo_engine(f"flapctl{i}")
            eng.start()
            api = ApiServer(default_config(), engine=eng)
            port = api.start(host="127.0.0.1", port=0)
            engines.append(eng)
            servers.append(api)
            urls.append(f"http://127.0.0.1:{port}")
        router = _router()
        for url in urls:
            router.register_remote(url,
                                   endpoint_id=url.split("//")[1])
        cfg = ControlPlaneConfig(enabled=True, interval=0.0,
                                 min_replicas=2, max_replicas=4,
                                 cooldown=0.0, max_actions_per_minute=2,
                                 backlog_per_replica=4)
        ctl = _controller(router, pool=_pool("flapspill"), cfg=cfg,
                          manager=None)
        mgr, worker, dlq = _stack(router, checker, "flapctl")
        ctl.queue_manager = mgr
        try:
            ctl.run_once()
            worker.start()
            for i in range(16):
                m = Message(id=f"fl{i}", content=f"flap {i}",
                            user_id="u", timeout=15.0)
                checker.submitted(m.id)
                mgr.push_message(m)
            deadline = time.time() + 40.0
            while time.time() < deadline:
                ctl.run_once()
                s = checker.summary()
                if sum(s["terminal"].values()) >= 16:
                    break
                time.sleep(0.05)
            s = checker.summary()
        finally:
            worker.stop()
            mgr.stop()
            for api in servers:
                api.stop()
            for eng in engines:
                eng.stop()
            ctl.stop()
        checker.check()
        total = (s["terminal"].get("completed", 0)
                 + s["terminal"].get("dead_lettered", 0))
        assert total == 16, s
        # Faults really flowed…
        inj = chaos.get_injector()
        assert inj.get_stats()["injected"].get(
            "transport.request:error", 0) > 0
        # …but the flapping replicas were never "replaced" (their
        # health stayed green — the breaker owns transient faults)…
        assert ctl.action_counts.get("replace:replica_dead", 0) == 0
        assert ctl.action_counts.get("replace:breaker_open", 0) == 0
        # …and total scale actions stayed inside the hard rate limit.
        assert ctl.scale_action_total() <= cfg.max_actions_per_minute


# -- operator surfaces --------------------------------------------------------

class TestApiSurfaces:
    def _server(self):
        router = _router()
        eng = _echo_engine("apisrv")
        router.register_engine(eng)
        ctl = _controller(router, pool=_pool("api"),
                          cfg=ControlPlaneConfig(enabled=True,
                                                 interval=0.0))
        srv = ApiServer(default_config(), engine=eng,
                        cluster_router=router, controller=ctl)
        return srv, ctl, eng

    def test_overview_gains_controller_block(self):
        srv, ctl, eng = self._server()
        try:
            ctl.run_once()
            status, payload, _ = srv.dispatch(
                "GET", "/api/v1/cluster/overview", b"")
            assert status == 200
            blk = payload["controller"]
            assert blk["enabled"] is True
            assert blk["paused"] is False
            assert blk["target_replicas"] >= 1
            assert "rung" in blk and "inputs" in blk
            assert "fast_burn" in blk["inputs"]
            assert "last_seconds" in blk["recovery"]
        finally:
            ctl.stop()
            eng.stop()

    def test_admin_pause_resume_and_health_visibility(self):
        srv, ctl, eng = self._server()
        try:
            status, payload, _ = srv.dispatch("GET", "/health", b"")
            assert payload["controller"] == "running"
            status, payload, _ = srv.dispatch(
                "POST", "/api/v1/admin/controller",
                json.dumps({"action": "pause"}).encode())
            assert status == 200 and payload["status"] == "paused"
            assert ctl.paused
            _, payload, _ = srv.dispatch("GET", "/health", b"")
            assert payload["controller"] == "paused"
            _, payload, _ = srv.dispatch(
                "GET", "/api/v1/admin/controller", b"")
            assert payload["paused"] is True
            status, payload, _ = srv.dispatch(
                "POST", "/api/v1/admin/controller",
                json.dumps({"action": "resume"}).encode())
            assert payload["status"] == "running"
            status, _, _ = srv.dispatch(
                "POST", "/api/v1/admin/controller",
                json.dumps({"action": "explode"}).encode())
            assert status == 400
        finally:
            ctl.stop()
            eng.stop()

    def test_disabled_is_distinct_from_paused(self):
        """No controller (controlplane.enabled=false): the admin route
        503s and /health carries NO controller field at all."""
        srv = ApiServer(default_config())
        status, _, _ = srv.dispatch(
            "POST", "/api/v1/admin/controller",
            json.dumps({"action": "pause"}).encode())
        assert status == 503
        _, payload, _ = srv.dispatch("GET", "/health", b"")
        assert "controller" not in payload


# -- wiring + off-switch ------------------------------------------------------

class TestWiring:
    def test_off_switch_builds_nothing(self):
        cfg = default_config()
        assert cfg.controlplane.enabled is False
        assert build_controller(cfg, router=object()) is None

    def test_app_wires_controller_over_local_engine(self):
        from llmq_tpu.__main__ import App
        cfg = default_config()
        cfg.executor.backend = "echo"
        cfg.queue.enable_metrics = False
        cfg.loadbalancer.health_check_interval = 0.0
        cfg.controlplane.enabled = True
        cfg.controlplane.interval = 0.0
        app = App(cfg, with_api=True, with_workers=True,
                  with_engine=True)
        try:
            # The controller forced a cluster router over the local
            # engine so provisioned replicas would receive traffic.
            assert app.cluster_router is not None
            assert app.controller is not None
            assert app.api.controller is app.controller
            assert app.controller.ladder is not None
            # The ladder actuates through the API server's shedder.
            assert app.controller.ladder.shedder is app.api.shedder
        finally:
            app.stop()

    def test_controller_supersedes_legacy_autoscaler(self):
        """Two reconcilers must never share one LoadBalancer: with the
        control plane on, serve's legacy threshold autoscaler is not
        built (it would strip endpoints the controller re-provisions);
        with it off, the autoscaler still runs."""
        from llmq_tpu.__main__ import App
        cfg = default_config()
        cfg.executor.backend = "echo"
        cfg.queue.enable_metrics = False
        cfg.loadbalancer.health_check_interval = 0.0
        cfg.controlplane.enabled = True
        cfg.controlplane.interval = 0.0
        app = App(cfg, with_api=True, with_workers=True,
                  with_engine=True, with_scheduler=True)
        try:
            assert app.controller is not None
            assert app.autoscaler is None
        finally:
            app.stop()
        cfg2 = default_config()
        cfg2.executor.backend = "echo"
        cfg2.queue.enable_metrics = False
        app2 = App(cfg2, with_api=True, with_workers=True,
                   with_engine=True, with_scheduler=True)
        try:
            assert app2.controller is None
            assert app2.autoscaler is not None
        finally:
            app2.stop()

    def test_load_exports_config_path_for_subprocess_replicas(self,
                                                              tmp_path,
                                                              monkeypatch):
        """--config must reach subprocess pool replicas: _load exports
        the resolved path as LLMQ_CONFIG so spawned children serve the
        SAME configuration instead of silently falling back to
        defaults."""
        import argparse
        import os

        from llmq_tpu.__main__ import _load
        cfg_file = tmp_path / "replica.yaml"
        cfg_file.write_text("server: {port: 9321}\n")
        monkeypatch.delenv("LLMQ_CONFIG", raising=False)
        args = argparse.Namespace(config=str(cfg_file), host=None,
                                  port=None, backend=None,
                                  log_format=None, peers=None)
        cfg = _load(args)
        assert cfg.server.port == 9321
        assert os.environ["LLMQ_CONFIG"] == str(cfg_file.resolve())

    def test_app_default_config_has_no_controller(self):
        from llmq_tpu.__main__ import App
        cfg = default_config()
        cfg.executor.backend = "echo"
        cfg.queue.enable_metrics = False
        app = App(cfg, with_api=True, with_workers=True,
                  with_engine=True)
        try:
            assert app.controller is None
            assert app.api.controller is None
        finally:
            app.stop()


# -- pools --------------------------------------------------------------------

class TestPools:
    def test_local_pool_lifecycle(self):
        pool = _pool("lifec")
        ep = pool.provision(1)
        assert ep is not None and ep.metadata["pool"] is True
        eng = ep.metadata["engine"]
        assert eng.running
        pool.decommission(ep)
        assert not eng.running
        stats = pool.get_stats()
        assert stats["provisioned"] == 1
        assert stats["decommissioned"] == 1

    def test_local_pool_decommission_recovers_crashed_engine(self):
        """Decommissioning a DEAD replica fails its in-flight handles
        over to the retry path (zero-loss depends on this)."""
        from llmq_tpu.engine.engine import GenRequest
        chaos.configure(ChaosConfig(enabled=True, seed=3, faults=[
            {"point": "engine.step", "kind": "crash", "times": 1,
             "match": {"engine": "dead-1"}}]))
        pool = _pool("dead", max_restarts=0)
        ep = pool.provision(1)
        eng = ep.metadata["engine"]
        h = eng.submit(GenRequest(id="d0", prompt="doomed",
                                  max_new_tokens=16))
        _await(lambda: not eng.running, 5.0, "engine crash")
        pool.decommission(ep)
        assert h.wait(2.0)
        assert h.result.finish_reason == "error"

    def test_exec_pool_contract(self, tmp_path):
        """provision_cmd → URL (stdout or template) → readiness gate
        on /health → ready Endpoint; decommission_cmd env contract;
        rollback on a replica that never becomes ready."""
        import threading
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        class _Health(BaseHTTPRequestHandler):
            def do_GET(self):
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"ok")

            def log_message(self, fmt, *args):
                pass

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Health)
        port = httpd.server_address[1]
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        try:
            marker = tmp_path / "decommissioned"
            cfg = ReplicaPoolConfig(
                kind="exec",
                provision_cmd=(f"echo ignored; "
                               f"echo http://127.0.0.1:{port}"),
                decommission_cmd=f"echo $LLMQ_REPLICA_ID >> {marker}",
                ready_timeout=5.0)
            pool = ExecReplicaPool(cfg)
            ep = pool.provision(7)
            assert ep is not None
            assert ep.url == f"http://127.0.0.1:{port}"
            assert ep.id == f"127.0.0.1:{port}"
            assert ep.metadata["pool"] is True
            pool.decommission(ep)
            assert marker.read_text().strip() == f"127.0.0.1:{port}"
            # url_template wins over stdout.
            cfg2 = ReplicaPoolConfig(
                kind="exec", provision_cmd="echo whatever",
                url_template=f"http://127.0.0.1:{port}",
                ready_timeout=5.0)
            ep2 = ExecReplicaPool(cfg2).provision(3)
            assert ep2 is not None
            assert ep2.url == f"http://127.0.0.1:{port}"
        finally:
            httpd.shutdown()
        # A failing provision_cmd yields None, not a crash.
        cfg3 = ReplicaPoolConfig(kind="exec", provision_cmd="exit 3")
        assert ExecReplicaPool(cfg3).provision(1) is None
        # A replica that never answers /health is rolled back: None,
        # and decommission_cmd runs so the orchestrator isn't left
        # scaled up.
        rollback = tmp_path / "rollback"
        cfg4 = ReplicaPoolConfig(
            kind="exec", provision_cmd="echo http://127.0.0.1:9",
            decommission_cmd=f"echo $LLMQ_REPLICA_SEQ >> {rollback}",
            ready_timeout=0.3)
        assert ExecReplicaPool(cfg4).provision(5) is None
        assert rollback.read_text().strip() == "5"

    def test_subprocess_pool_serves_real_replica(self):
        """One real ``python -m llmq_tpu serve`` echo replica: the
        pool provisions it ready, the router dispatches to it over
        HTTP, and decommission SIGTERMs it down."""
        import socket

        from llmq_tpu.controlplane.pool import SubprocessReplicaPool
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            base = s.getsockname()[1]
        pool = SubprocessReplicaPool(ReplicaPoolConfig(
            kind="subprocess", base_port=base,
            args=["--backend", "echo"], ready_timeout=45.0))
        router = _router()
        ep = pool.provision(0)
        try:
            assert ep is not None, "replica never became ready"
            router.lb.add_endpoint(ep)
            msg = Message(id="sub0", content="subprocess replica",
                          user_id="u", timeout=30.0)
            router.process_fn(None, msg)
            assert msg.response
        finally:
            pool.stop()
        assert pool.get_stats()["live"] == 0


# -- autoscaler clock-discipline satellite ------------------------------------

class TestAutoscalerClock:
    def test_adaptive_strategy_follows_injected_clock(self):
        """The time-of-day heuristic must read the INJECTED clock, so
        FakeClock drives scaling decisions deterministically (no
        wall-clock leakage)."""
        import calendar

        from llmq_tpu.core.config import SchedulerConfig
        from llmq_tpu.queueing.queue_manager import QueueManager
        from llmq_tpu.scheduling.autoscaler import Autoscaler

        # Wed 2026-07-29 11:00 local → business hours; 23:00 → off.
        biz = calendar.timegm((2026, 7, 29, 11, 0, 0))
        off = calendar.timegm((2026, 7, 29, 23, 0, 0))
        # timegm is UTC; shift so LOCAL time is the intended hour.
        shift = (calendar.timegm(time.localtime(biz))
                 - int(biz))
        clock = FakeClock(start=float(biz - shift))
        mgr = QueueManager("asclk", enable_metrics=False)
        lb = LoadBalancer(LoadBalancerConfig(
            health_check_interval=0.0))
        made = []

        def provision(seq):
            ep = Endpoint(id=f"as{seq}", url=f"local://as{seq}")
            made.append(ep)
            return ep

        a = Autoscaler(mgr, lb,
                       SchedulerConfig(strategy="adaptive",
                                       min_endpoints=1,
                                       max_endpoints=4, cooldown=0.0),
                       provision_fn=provision,
                       decommission_fn=lambda ep: None,
                       clock=clock)
        lb.add_endpoint(Endpoint(id="seed", url="local://seed"))
        out = a.run_once()
        # Business hours: scales toward max-1 = 3.
        assert out["action"] == "up"
        assert len(lb.endpoints()) == 3
        # Advance the SAME clock to 23:00 local → off-hours target 1.
        clock.advance(float(off - biz))
        out = a.run_once()
        assert out["action"] == "down"
        assert len(lb.endpoints()) == 1
        mgr.stop()
