"""Runnable entrypoints: ``python -m llmq_tpu <command>``.

The reference ships four binaries under ``cmd/`` (server, api-gateway,
queue-manager, scheduler — cmd/server/main.go:26-119,
cmd/queue-manager/main.go:73-84, cmd/scheduler/main.go). Here they are
subcommands of one module sharing one wiring function, which also fixes
the reference's architectural split-brain: its api-gateway and
queue-manager each build *independent in-process queues*
(cmd/api-gateway/main.go:66, cmd/queue-manager/main.go:58), so in the
compose deployment the consumer never sees the producer's messages
(SURVEY.md §5 "Distributed communication backend"). Our gateway and
consumer modes are explicit single-process slices of the same monolith
wiring instead.

Commands:

- ``serve``          — the monolith: config → queues → workers → engine →
                       conversation service → API server; graceful
                       shutdown on SIGINT/SIGTERM (main.go:109-118).
                       Unlike the reference, workers are actually created
                       (its startWorkers leaves a TODO, main.go:172-193).
- ``queue-manager``  — consumer daemon: queues + workers + engine, no
                       HTTP. The per-tier simulated sleep the reference
                       runs here (main.go:139-153) is replaced by the
                       real continuous-batching engine.
- ``gateway``        — API server + queues only (no workers/engine): the
                       producer edge.
- ``scheduler``      — autoscaler monitor loop over the load balancer
                       (cmd/scheduler/main.go:68-76).
- ``check``          — load config, build everything on the configured
                       backend, run one request end-to-end, exit.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading
import time
from typing import List, Optional

from llmq_tpu.core.config import Config, load_config
from llmq_tpu.utils.logging import configure_logging, get_logger

log = get_logger("main")


class App:
    """One wired process. Which parts exist depends on the mode flags."""

    def __init__(self, cfg: Config, *, with_api: bool, with_workers: bool,
                 with_engine: bool, with_scheduler: bool = False) -> None:
        from llmq_tpu.api import ApiServer, MessageStore
        from llmq_tpu.conversation.persistence import make_store
        from llmq_tpu.conversation.state_manager import StateManager
        from llmq_tpu.loadbalancer.load_balancer import LoadBalancer
        from llmq_tpu.preprocessor.preprocessor import Preprocessor
        from llmq_tpu.queueing.factory import QueueFactory, QueueType
        from llmq_tpu.scheduling.autoscaler import Autoscaler
        from llmq_tpu.scheduling.resource_scheduler import ResourceScheduler

        self.cfg = cfg
        self.factory = QueueFactory(cfg)
        # The reference monolith creates standard/delayed/priority
        # managers (cmd/server/main.go:172-193).
        self.factory.create_queue_manager("standard", QueueType.STANDARD)
        self.factory.create_queue_manager("delayed", QueueType.DELAYED)
        self.factory.create_queue_manager("priority", QueueType.PRIORITY)

        self.preprocessor = Preprocessor()
        store = make_store(cfg.persistence.backend,
                           sqlite_path=cfg.persistence.sqlite_path,
                           redis_url=cfg.persistence.redis_url,
                           key_prefix=cfg.persistence.key_prefix)
        # Store fault domain (conversation/resilience.py,
        # docs/robustness.md): bounded op deadlines + retry + breaker
        # around the ONE store every store-backed plane shares. Hard
        # off-switch: store.resilience.enabled=false (default) keeps
        # the raw backend — nothing below can tell the difference.
        if cfg.store.resilience.enabled:
            from llmq_tpu.conversation.resilience import wrap_store
            store = wrap_store(store, cfg.store.resilience)
        self.state_manager = StateManager(cfg.conversation, store=store)
        self.load_balancer = LoadBalancer(cfg.loadbalancer)
        self.resource_scheduler = ResourceScheduler(cfg.resource_scheduler)

        self.engine = None
        self.engine_allocation = None
        if with_engine:
            from llmq_tpu.engine import build_engine
            self.engine = build_engine(cfg, warmup=(cfg.executor.backend == "jax"))
            # BASELINE config #3: conversation eviction frees pinned KV.
            self.engine.attach_conversation_manager(self.state_manager)
            # Cache-aware admission (docs/prefix_cache.md): token-sized
            # resource requests are charged only their expected-NEW
            # prefill tokens, not context the prefix cache will serve.
            eng = self.engine
            self.resource_scheduler.set_prefill_estimator(
                lambda md: eng.prefill_estimate(
                    str(md.get("conversation_id", "")),
                    int(md.get("prompt_tokens", 0) or 0)))
            # The scheduler LEARNS the serving geometry's real prefill
            # rate (budgeted, under mixed batching) from the engine's
            # completed admissions instead of assuming a static figure.
            self.engine.on_prefill_observed = (
                self.resource_scheduler.observe_prefill)
            if cfg.executor.backend == "jax":
                self._register_chip_resources()

        # Engine crash supervisor (engine/supervisor.py,
        # docs/robustness.md): detects a dead engine thread, fails the
        # in-flight handles over to the worker retry path (WAL
        # at-least-once, completions deduped) and restarts the loop.
        self.supervisor = None
        if self.engine is not None and cfg.executor.supervisor.enabled:
            from llmq_tpu.engine.supervisor import EngineSupervisor
            self.supervisor = EngineSupervisor(
                self.engine, config=cfg.executor.supervisor,
                enable_metrics=cfg.queue.enable_metrics)

        # Cluster serving plane (llmq_tpu/cluster/, docs/multihost.md):
        # a non-empty ``cluster.peers`` builds the replica-set router
        # over THIS process's LoadBalancer — the same instance the API
        # server's POST /api/v1/endpoints feeds, so runtime-added hosts
        # receive traffic from the live router with no restart.
        self.cluster_router = None
        if cfg.cluster.enabled:
            from llmq_tpu.cluster import build_cluster_router
            self.cluster_router = build_cluster_router(
                cfg, self.load_balancer,
                state_manager=self.state_manager, engine=self.engine)
            log.info("cluster plane up: %d peer(s)%s",
                     len(cfg.cluster.peers),
                     " + local engine" if (self.engine is not None
                                           and cfg.cluster.include_local)
                     else "")

        # Prefill/decode disaggregation plane (llmq_tpu/disagg/,
        # docs/disaggregation.md): role + KV-exchange wiring over the
        # SAME conversation store the state manager persists to — the
        # store tier becomes the cluster-wide handoff channel. Hard
        # off-switch: disagg.enabled=false builds None and nothing
        # below changes.
        self.disagg = None
        if cfg.disagg.enabled and self.engine is not None:
            from llmq_tpu.disagg import build_disagg
            self.disagg = build_disagg(
                cfg, self.engine, store,
                enable_metrics=cfg.queue.enable_metrics)
            if self.disagg is not None:
                log.info("disagg plane up: role=%s exchange=%s",
                         self.disagg.role,
                         self.disagg.exchange is not None)
        # Self-healing control plane (llmq_tpu/controlplane/,
        # docs/controlplane.md): the controller needs the replica-set
        # routing seam, so a serve process WITHOUT configured peers
        # gets a ClusterRouter built over its own engine — provisioned
        # replicas then actually receive traffic. The controller itself
        # is wired after the API server below (it applies the ladder at
        # the server's overload shedder).
        self.controller = None
        if (cfg.controlplane.enabled and self.cluster_router is None
                and self.engine is not None):
            from llmq_tpu.cluster.router import ClusterRouter
            self.cluster_router = ClusterRouter(
                self.load_balancer, config=cfg.cluster,
                state_manager=self.state_manager,
                enable_metrics=cfg.queue.enable_metrics)
            self.cluster_router.register_engine(self.engine)
            log.info("control plane: cluster router built over the "
                     "local engine")

        if cfg.disagg.enabled and self.cluster_router is not None:
            # Router-side role steering (after BOTH router-construction
            # paths): the learned prefill-rate estimator decides which
            # first turns are "long" enough for a prefill replica.
            self.cluster_router.disagg = cfg.disagg
            self.cluster_router.prefill_eta = (
                self.resource_scheduler.prefill_eta_ms)

        # Split-deployment transport (queueing/spool.py): consumer side
        # pulls spooled messages into the local queues and acks results;
        # gateway side relays drained messages out and applies acks.
        self.spool_consumer = None
        self.spool_producer = None
        self.spool_collector = None
        self._spool_relay: Optional[threading.Thread] = None
        spool_dir = cfg.queue.spool_dir

        # A gateway with cluster peers gets WORKERS: its queues drain
        # through the router to the replicas over HTTP (the reference's
        # gateway accepts messages nothing ever consumes).
        if self.cluster_router is not None and not with_workers:
            with_workers = True
        self.workers: List = []
        if with_workers:
            if self.engine is None and self.cluster_router is None:
                raise ValueError("workers need an engine or cluster "
                                 "peers (use --backend echo for a "
                                 "model-free process)")
            process_fn = (self.cluster_router.process_fn
                          if self.cluster_router is not None
                          else self.engine.process_fn)
            self._spool_ack_failure = None
            # Spool and cluster are alternative transports; with peers
            # configured the cluster router owns the dispatch seam.
            if (spool_dir and not with_api and self.engine is not None
                    and self.cluster_router is None):
                process_fn = self._wire_spool_consumer(spool_dir)
            self.workers = self.factory.create_workers(
                "standard", cfg.queue.worker.count, process_fn,
                on_permanent_failure=self._spool_ack_failure)

        self.message_store = MessageStore()
        self.api: Optional[ApiServer] = None
        if with_api:
            self.api = ApiServer(
                cfg,
                queue_factory=self.factory,
                preprocessor=self.preprocessor,
                state_manager=self.state_manager,
                load_balancer=self.load_balancer,
                resource_scheduler=self.resource_scheduler,
                engine=self.engine,
                cluster_router=self.cluster_router,
                drain_hook=self.drain,
                message_store=self.message_store,
            )
            if spool_dir and not with_workers:
                self._wire_spool_gateway(spool_dir)

        # Control-plane controller (after the API server: the ladder
        # actuates through its overload shedder). Hard off-switch:
        # controlplane.enabled=false builds NOTHING — every path above
        # ran exactly as before.
        if cfg.controlplane.enabled and self.cluster_router is not None:
            from llmq_tpu.controlplane import build_controller
            self.controller = build_controller(
                cfg, self.cluster_router,
                queue_manager=self.factory.get_queue_manager("standard"),
                shedder=(self.api.shedder if self.api is not None
                         else None),
                supervisor=self.supervisor)
            if self.api is not None:
                self.api.controller = self.controller
            if self.controller is not None:
                log.info("control plane up: %d..%d replicas, %d ladder "
                         "rung(s), pool=%s",
                         cfg.controlplane.min_replicas,
                         cfg.controlplane.max_replicas,
                         len(cfg.controlplane.rungs),
                         cfg.controlplane.pool.kind)

        self.autoscaler = None
        if with_scheduler and self.controller is None:
            # The legacy threshold autoscaler and the control plane
            # must never share a LoadBalancer: both add/remove
            # endpoints, and the autoscaler (no burn signal, no pool
            # ownership) would strip endpoints the controller then
            # re-provisions — two reconcilers fighting. The controller
            # supersedes it whenever it exists.
            mgr = self.factory.get_queue_manager("standard")
            self.autoscaler = Autoscaler(mgr, self.load_balancer,
                                         cfg.scheduler)

        self._stop = threading.Event()
        #: Set when the stop signal was SIGTERM — the orchestrated
        #: "please leave the replica set" signal; commands then drain
        #: before stopping (SIGINT stays an immediate stop).
        self._term = threading.Event()
        self._drain_mu = threading.Lock()
        self._drain_started = False
        self._drain_done = threading.Event()
        self._drain_idle = False

    # -- graceful drain (docs/multihost.md) ----------------------------------

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Leave the replica set gracefully: /health flips to
        "draining" (peers' probes stop routing here), workers stop
        pulling NEW messages while in-flight calls finish, then wait —
        bounded by ``cluster.drain_timeout`` — for the engine to go
        idle. Returns True when fully idle at the end. Concurrent
        callers (admin-drain thread vs. the SIGTERM path) converge on
        ONE drain: late callers BLOCK until it completes and return its
        result — an instant "done" here would let the stop cascade tear
        the engine down under the very in-flight work the drain exists
        to protect."""
        if timeout is None:
            timeout = self.cfg.cluster.drain_timeout
        with self._drain_mu:
            already = self._drain_started
            self._drain_started = True
        if already:
            self._drain_done.wait(max(0.0, timeout) + 10.0)
            return self._drain_idle
        log.info("draining (timeout %.0fs) ...", timeout)
        if self.api is not None:
            self.api.draining = True
        if (self.cluster_router is not None
                and self.cluster_router._local_endpoint_id):  # noqa: SLF001
            # Local replica out of the in-process router too.
            self.cluster_router.drain_endpoint(
                self.cluster_router._local_endpoint_id)  # noqa: SLF001
        for w in self.workers:
            w.stop(wait=True)      # finishes in-flight dispatches
        deadline = time.monotonic() + max(0.0, timeout)
        idle = True
        if self.engine is not None:
            while time.monotonic() < deadline:
                s = self.engine.get_stats()
                if s["active"] == 0 and s["pending"] == 0:
                    break
                time.sleep(0.05)
            else:
                idle = False
        if self.disagg is not None:
            # Cross-replica prefix migration (docs/disaggregation.md):
            # every warm conversation this replica still holds goes to
            # the KV exchange, so peers resume them with store-tier
            # hits instead of recompute. Bounded flush: the publishes
            # must be durable before the stop cascade kills the plane.
            try:
                if (self.disagg.publish_warm()
                        and self.disagg.plane is not None):
                    self.disagg.plane.flush_jobs(
                        timeout=max(1.0, timeout / 2))
            except Exception:  # noqa: BLE001 — drain must complete
                log.exception("drain-time kv migration failed")
        log.info("drain complete (idle=%s)", idle)
        self._drain_idle = idle
        self._drain_done.set()
        return idle

    def _register_chip_resources(self) -> None:
        """Account the engine's chips in the ResourceScheduler: discover
        the live topology, register it as schedulable CHIP/HBM_GB
        resources, and allocate the engine's footprint — so
        /api/v1/resources reflects real usage and further placements
        (more engines, training jobs) schedule against the remainder.
        (r3 verdict: topology/scheduler were parity-complete but inert.)
        """
        from llmq_tpu.scheduling.resource_scheduler import (
            ResourceRequest, ResourceType)
        from llmq_tpu.scheduling.topology import TpuTopology

        topo = TpuTopology.discover()
        mesh = self.cfg.tpu.mesh_shape
        n_chips = 1
        for v in (mesh or {}).values():
            n_chips *= max(1, int(v))
        n_chips = min(n_chips, max(1, topo.num_chips))
        own = self.resource_scheduler.register_topology_resources(
            topo, chips_per_resource=max(n_chips, 1))
        #: Resources THIS process registered — the set its heartbeat
        #: vouches for (never externally-registered workers).
        self._own_resource_ids = [r.id for r in own]
        try:
            alloc = self.resource_scheduler.request_resource_now(
                ResourceRequest(
                    model_type="llm",
                    capabilities={"tpu"},
                    amounts={ResourceType.CHIP: float(n_chips)},
                    metadata={"engine": self.engine.name,
                              "model": self.cfg.model.name,
                              "pinned": True},
                ))
        except Exception:  # noqa: BLE001 — accounting, not a gate
            log.exception("chip allocation failed; engine runs anyway")
            return
        self.engine_allocation = alloc
        self._start_chip_heartbeat()
        log.info("engine %s holds %d chip(s) of %s (%.0f GB HBM total)",
                 self.engine.name, n_chips, topo.slice_name,
                 topo.total_hbm_gb)

    def _start_chip_heartbeat(self) -> None:
        """Keep THIS engine's chip resource ALIVE while the engine is:
        the scheduler's monitor marks resources offline on heartbeat
        timeout (reference :477-492 semantics), and a serving process
        that registers chips but never heartbeats them reports its own
        chips offline 30 s in. The engine's liveness IS the heartbeat
        signal — a dead engine thread stops the beat and the scheduler
        correctly ages its chips out.

        Only resources THIS process registered (its own topology slice,
        which includes the one backing ``self.engine_allocation``) are
        beaten: beating every resource with a ``tpu`` capability would
        vouch for externally-registered workers this process knows
        nothing about, keeping dead ones online forever (round-5
        ADVICE)."""
        import threading

        sched = self.resource_scheduler
        interval = max(1.0, sched.config.heartbeat_timeout / 3.0)
        own = list(getattr(self, "_own_resource_ids", []))
        alloc = self.engine_allocation
        if alloc is not None and alloc.resource_id not in own:
            own.append(alloc.resource_id)

        def beat() -> None:
            while not self._hb_stop.wait(interval):
                if self.engine is None or not self.engine.running:
                    continue
                for rid in own:
                    sched.heartbeat(rid)

        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(target=beat, daemon=True,
                                           name="chip-heartbeat")
        self._hb_thread.start()

    # -- split-deployment spool wiring ---------------------------------------

    def _wire_spool_consumer(self, spool_dir: str):
        """Queue-manager side: spooled messages land in the local
        queues; results (success or exhausted-retry failure) are acked
        into done/ for the gateway. Returns the worker process_fn."""
        from llmq_tpu.core.types import Message, MessageStatus
        from llmq_tpu.queueing.spool import SpoolConsumer

        mgr = self.factory.get_queue_manager("standard")
        consumer = SpoolConsumer(
            spool_dir, lambda q, m: mgr.push_message(m, q))
        self.spool_consumer = consumer
        inner = self.engine.process_fn

        def process(ctx, msg):
            inner(ctx, msg)
            ack = Message.from_dict(msg.to_dict())
            ack.status = MessageStatus.COMPLETED
            consumer.ack_done(ack)

        def ack_failure(msg, reason):
            # Fires from EVERY permanent-failure path — synchronous
            # error, timeout, watchdog abandonment — so the gateway
            # always gets a terminal record (workers.on_permanent_
            # failure seam).
            ack = Message.from_dict(msg.to_dict())
            ack.status = MessageStatus.FAILED
            ack.error = reason
            consumer.ack_done(ack)

        self._spool_ack_failure = ack_failure
        return process

    def _wire_spool_gateway(self, spool_dir: str) -> None:
        """Gateway side: a relay thread drains the local queues into the
        spool (messages stay in-flight locally — WAL-covered across
        restarts); the collector applies done-records so polling clients
        see responses and queue stats see completions."""
        from llmq_tpu.core.types import MessageStatus
        from llmq_tpu.queueing.spool import SpoolCollector, SpoolProducer

        mgr = self.factory.get_queue_manager("standard")
        self.spool_producer = SpoolProducer(spool_dir)

        def on_done(done) -> None:
            orig = self.message_store.get(done.id)
            if orig is not None:
                orig.response = done.response
                orig.error = done.error
                orig.status = done.status
                orig.metadata.update(done.metadata or {})
                target = orig
            else:
                target = done
            from llmq_tpu import observability
            if done.status == MessageStatus.COMPLETED:
                mgr.complete_message(target)
                observability.record(done.id, "completed",
                                     source="spool")
            else:
                mgr.fail_message(target, 0.0)
                observability.record(done.id, "failed", source="spool",
                                     reason=done.error)

        self.spool_collector = SpoolCollector(spool_dir, on_done)

        def relay_loop() -> None:
            while not self._stop.is_set():
                try:
                    batch = mgr.drain_in_priority_order(64)
                except Exception:  # noqa: BLE001
                    log.exception("spool relay drain failed")
                    self._stop.wait(1.0)
                    continue
                # On ANY push failure, requeue the whole undelivered
                # remainder — drained messages are out of the queue, and
                # dropping them strands their clients in PROCESSING
                # forever. The relay itself must survive (a dead relay
                # silently strands every future request).
                undelivered = []
                for i, m in enumerate(batch):
                    try:
                        self.spool_producer.push(m)
                    except Exception:  # noqa: BLE001
                        log.exception(
                            "spool push failed; requeueing %d messages",
                            len(batch) - i)
                        undelivered = batch[i:]
                        break
                for m in undelivered:
                    try:
                        mgr.push_message(m)
                    except Exception:  # noqa: BLE001
                        log.exception("requeue of %s failed", m.id)
                if undelivered:
                    self._stop.wait(1.0)
                elif not batch:
                    self._stop.wait(0.05)

        self._spool_relay = threading.Thread(
            target=relay_loop, name="spool-relay", daemon=True)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        self.state_manager.start()
        self.resource_scheduler.start()
        if self.cfg.loadbalancer.health_check_interval > 0:
            self.load_balancer.start()
        if self.engine is not None:
            self.engine.start()
        if self.supervisor is not None:
            self.supervisor.start()
        for w in self.workers:
            w.start()
        if self.autoscaler is not None:
            self.autoscaler.start()
        if self.controller is not None:
            self.controller.start()
        if self.spool_consumer is not None:
            self.spool_consumer.start()
        if self.spool_collector is not None:
            self.spool_collector.start()
        if self._spool_relay is not None:
            self._spool_relay.start()
        if self.api is not None:
            port = self.api.start()
            log.info("serving on %s:%d", self.cfg.server.host, port)

    def stop(self) -> None:
        """Shutdown cascade mirroring cmd/server/main.go:109-118."""
        log.info("shutting down ...")
        if self.controller is not None:
            # FIRST: a live controller would react to the teardown
            # below (replicas "dying") with replacements.
            self.controller.stop()
        if self.supervisor is not None:
            # BEFORE the engine stops: a supervisor that outlives the
            # deliberate engine.stop() would "recover" it as a crash.
            self.supervisor.stop()
        if getattr(self, "_hb_stop", None) is not None:
            self._hb_stop.set()
        if self.api is not None:
            self.api.stop()
        self._stop.set()                # stops the spool relay loop
        if self.spool_consumer is not None:
            self.spool_consumer.stop()
        if self.spool_collector is not None:
            self.spool_collector.stop()
        if self._spool_relay is not None:
            self._spool_relay.join(timeout=5.0)
        if self.autoscaler is not None:
            self.autoscaler.stop()
        self.factory.stop_all()
        if self.engine_allocation is not None:
            try:
                self.resource_scheduler.release_allocation(
                    self.engine_allocation.id, self.engine_allocation.token)
            except Exception:  # noqa: BLE001
                log.exception("chip allocation release failed")
        if self.engine is not None:
            self.engine.stop()
        self.load_balancer.stop()
        self.resource_scheduler.stop()
        self.state_manager.stop()
        self._stop.set()

    def wait(self) -> None:
        """Block until SIGINT/SIGTERM. SIGTERM marks the stop as
        ORCHESTRATED (compose/k8s scale-down) — the command then drains
        in-flight work before tearing down; SIGINT stays immediate."""
        signal.signal(signal.SIGINT, lambda *a: self._stop.set())

        def on_term(*_a) -> None:
            self._term.set()
            self._stop.set()

        signal.signal(signal.SIGTERM, on_term)
        try:
            while not self._stop.is_set():
                self._stop.wait(0.5)
        except KeyboardInterrupt:
            pass

    def shutdown(self) -> None:
        """wait()-aware teardown: drain first on SIGTERM (or after an
        admin drain request — drain() then blocks until the in-progress
        drain really finishes), then the stop cascade."""
        if self._term.is_set() or self._drain_started:
            self.drain()
        self.stop()


def _load(args) -> Config:
    cfg = load_config(args.config) if args.config else load_config()
    if args.config:
        # Children this process spawns (the control plane's subprocess
        # replica pool) must serve the SAME configuration: export the
        # resolved path so load_config in the child finds it through
        # the LLMQ_CONFIG env inheritance — a replica silently falling
        # back to defaults would join the LB with the wrong
        # model/limits/tenancy settings.
        import os
        os.environ["LLMQ_CONFIG"] = os.path.abspath(args.config)
    if args.host:
        cfg.server.host = args.host
    if args.port is not None:
        cfg.server.port = args.port
    if args.backend:
        cfg.executor.backend = args.backend
    if getattr(args, "log_format", None):
        cfg.logging.format = args.log_format
    if getattr(args, "peers", None):
        # Comma-separated replica URLs; ClusterConfig.__post_init__
        # normalizes the string form.
        cfg.cluster.peers = args.peers
        cfg.cluster.__post_init__()
    configure_logging(cfg.logging.level, cfg.logging.format,
                      cfg.logging.output)
    # Trace plane (docs/observability.md): size/enable the process
    # flight recorder before any component records a stage event.
    from llmq_tpu import observability
    observability.configure(cfg.observability)
    # Chaos plane (docs/robustness.md): armed ONLY when
    # chaos.enabled is true — disabled, every fault point is a single
    # attribute check.
    from llmq_tpu import chaos
    chaos.configure(cfg.chaos)
    # Tenancy plane (docs/tenancy.md): the shared registry (weights,
    # quotas, in-flight counters) must be configured before the queue
    # managers build their fair schedulers against it.
    from llmq_tpu import tenancy
    tenancy.configure_tenancy(cfg.tenancy)
    _maybe_join_cluster()
    return cfg


def _maybe_join_cluster() -> None:
    """Multi-host bring-up from env (docs/deployment.md): when
    LLMQ_COORDINATOR is set, every entrypoint joins the jax.distributed
    cluster BEFORE any backend work — a 70B TP deployment spans hosts
    as ONE pjit program, so the rendezvous must precede engine build.
    Fails fast on a broken rendezvous (distributed_init propagates)."""
    import os

    coordinator = os.environ.get("LLMQ_COORDINATOR")
    if not coordinator:
        return
    missing = [k for k in ("LLMQ_NUM_PROCESSES", "LLMQ_PROCESS_ID")
               if k not in os.environ]
    if missing:
        raise SystemExit(
            f"LLMQ_COORDINATOR is set but {', '.join(missing)} "
            "is not — multi-host bring-up needs all three "
            "(see docs/deployment.md)")
    from llmq_tpu.parallel.mesh import distributed_init

    distributed_init(
        coordinator=coordinator,
        num_processes=int(os.environ["LLMQ_NUM_PROCESSES"]),
        process_id=int(os.environ["LLMQ_PROCESS_ID"]),
        initialization_timeout=int(
            os.environ.get("LLMQ_CLUSTER_TIMEOUT", "300")))


def cmd_serve(args) -> int:
    cfg = _load(args)
    # Serve-boot decomposition (docs/observability.md "Critical path &
    # boot telemetry"): open THIS process's boot record before the App
    # builds the engine — the builder/executor stamp weights/compile/
    # warmup into it, /health advertises it, and a parent ReplicaPool
    # adopts it across the process seam. One no-op call when off.
    import time as _time
    from llmq_tpu.observability import critical_path as _cp
    serve_id = f"serve:{cfg.server.host}:{cfg.server.port}"
    t_boot0 = _time.perf_counter()
    _cp.boot_begin(serve_id, "serve", process=True)
    app = App(cfg, with_api=True, with_workers=True, with_engine=True,
              with_scheduler=True)
    app.start()
    _cp.boot_ready(serve_id, _time.perf_counter() - t_boot0)
    app.wait()
    app.shutdown()
    return 0


def cmd_queue_manager(args) -> int:
    cfg = _load(args)
    app = App(cfg, with_api=False, with_workers=True, with_engine=True)
    app.start()
    log.info("queue-manager consuming with %d workers (%s engine)",
             len(app.workers), cfg.executor.backend)
    app.wait()
    app.shutdown()
    return 0


def cmd_gateway(args) -> int:
    cfg = _load(args)
    app = App(cfg, with_api=True, with_workers=False, with_engine=False)
    app.start()
    if app.cluster_router is not None:
        log.info("gateway routing to %d endpoint(s)",
                 len(app.load_balancer.endpoints()))
    app.wait()
    app.shutdown()
    return 0


def cmd_scheduler(args) -> int:
    cfg = _load(args)
    app = App(cfg, with_api=False, with_workers=False, with_engine=False,
              with_scheduler=True)
    app.start()
    log.info("scheduler monitoring (strategy=%s)", cfg.scheduler.strategy)
    app.wait()
    app.stop()
    return 0


def cmd_check(args) -> int:
    """Build the full monolith on the CONFIGURED backend, run one
    message end-to-end, exit 0/1. The last line names the backend (and
    the device a jax engine sits on), so an echo pass can never be
    read as a model pass."""
    cfg = _load(args)
    app = App(cfg, with_api=True, with_workers=True, with_engine=True)
    # Ephemeral port so a parallel real instance doesn't collide.
    cfg.server.port = 0
    app.start()
    ok = False
    try:
        import json
        import urllib.request
        port = app.api._httpd.server_address[1]  # noqa: SLF001
        body = json.dumps({"content": "smoke check", "user_id": "check",
                           "metadata": {"max_new_tokens": 8}}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/v1/messages", data=body,
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            mid = json.loads(resp.read())["message_id"]
        deadline = time.time() + 30
        while time.time() < deadline:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/api/v1/messages/{mid}",
                    timeout=10) as resp:
                m = json.loads(resp.read())
            if m["status"] == "completed":
                # Echo must answer with the content; a random-init
                # model may legitimately decode to an empty string.
                ok = bool(m["response"]) or cfg.executor.backend != "echo"
                break
            time.sleep(0.05)
    finally:
        app.stop()
    from llmq_tpu.observability.device import describe_device
    log.info("CHECK %s backend=%s device=%s", "OK" if ok else "FAILED",
             cfg.executor.backend,
             describe_device(app.engine.device_identity()))
    return 0 if ok else 1


def cmd_scenarios(args) -> int:
    """Scenario engine (docs/scenarios.md): compile the named (or
    ``scenarios.run``-configured) workload specs and drive them
    closed-loop — against an in-process echo engine by default, or a
    remote gateway with ``--gateway`` — emitting one summary JSON line
    per run plus ``SCENARIO_<name>.json`` when ``scenarios.emit_json``
    is on. Exit 1 if any run fails or violates an invariant."""
    import json
    import logging

    cfg = _load(args)
    scn = cfg.scenarios
    names = list(args.names or scn.run)
    if not names:
        if not scn.enabled:
            log.error("scenarios.enabled is false and no scenario "
                      "names were given — pass names on the command "
                      "line or set scenarios.run")
            return 2
        from llmq_tpu.scenarios import SHIPPED
        names = list(SHIPPED)
    from llmq_tpu.scenarios import GatewayTarget, load_named, run_scenario

    # Scenario runs narrate per-request preemption/eviction at INFO —
    # megabytes on a 10^4-turn run; warnings and errors still surface.
    for noisy in ("llmq.engine", "llmq.supervisor", "llmq.tiering"):
        logging.getLogger(noisy).setLevel(logging.WARNING)
    scale = args.scale if args.scale is not None else scn.scale
    rc = 0
    for name in names:
        spec = load_named(name, directory=scn.dir)
        if spec.seed == 0 and scn.default_seed:
            spec.seed = scn.default_seed
        target = GatewayTarget(args.gateway) if args.gateway else None
        try:
            rep = run_scenario(spec, target=target, scale=scale,
                               out_dir=scn.out_dir,
                               emit_json=scn.emit_json,
                               directory=scn.dir)
        except Exception as e:  # noqa: BLE001 — one failed scenario
            log.error("scenario %s failed: %s: %s",  # must not eat the rest
                      name, type(e).__name__, e)
            rc = 1
            continue
        req = rep["requests"]
        violations = rep["invariants"]["violations"]
        if violations:
            rc = 1
        sys.stdout.write(json.dumps({
            "scenario": name,
            "scale": scale,
            "goodput_tps": rep["goodput"].get(
                "tokens_per_device_second"),
            "slo_attainment": rep["slo"]["attainment"],
            "completed": req["completed"],
            "failed": req["failed"],
            "shed": req["shed"],
            "chaos_events_fired": req["chaos_events_fired"],
            "engine_recoveries": req["engine_recoveries"],
            "invariant_violations": violations,
            "report_path": rep.get("report_path"),
        }) + "\n")
        sys.stdout.flush()
    return rc


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="llmq_tpu",
        description="TPU-native LLM message queue + serving framework")
    parser.add_argument("--config", "-c", help="config YAML path")
    parser.add_argument("--host", help="override server.host")
    parser.add_argument("--port", type=int, help="override server.port")
    parser.add_argument("--backend", choices=["echo", "jax"],
                        help="override executor.backend")
    parser.add_argument("--log-format", choices=["json", "console"],
                        help="override logging.format (structured JSON "
                             "with request_id/conversation_id/endpoint "
                             "fields, or human console lines)")
    parser.add_argument("--peers",
                        help="comma-separated replica base URLs "
                             "(override cluster.peers): serve/gateway "
                             "route through the cluster plane")
    sub = parser.add_subparsers(dest="command", required=True)
    sub.add_parser("serve", help="monolith: API + workers + engine")
    sub.add_parser("queue-manager", help="consumer daemon (no HTTP)")
    sub.add_parser("gateway", help="API edge (no workers/engine)")
    sub.add_parser("scheduler", help="autoscaler monitor loop")
    sub.add_parser("check", help="end-to-end smoke check, then exit")
    scn = sub.add_parser(
        "scenarios",
        help="run workload scenarios closed-loop (docs/scenarios.md)")
    scn.add_argument("names", nargs="*",
                     help="scenario names (default: scenarios.run, "
                          "or all shipped when scenarios.enabled)")
    scn.add_argument("--scale", type=float, default=None,
                     help="arrival/population scale factor "
                          "(default: scenarios.scale)")
    scn.add_argument("--gateway", default="",
                     help="drive a remote gateway URL instead of an "
                          "in-process echo engine")
    args = parser.parse_args(argv)
    return {
        "serve": cmd_serve,
        "queue-manager": cmd_queue_manager,
        "gateway": cmd_gateway,
        "scheduler": cmd_scheduler,
        "check": cmd_check,
        "scenarios": cmd_scenarios,
    }[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
