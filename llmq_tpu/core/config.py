"""Typed configuration tree + loader.

Capability parity with reference ``pkg/config/config.go``:

- Typed config tree Server/Database/Queue/Scheduler/LoadBalancer/Logging/
  Metrics (config.go:9-104), extended with the TPU execution-plane sections
  the reference lacks (``model``, ``executor``, ``tpu``).
- ``load_config`` = YAML file + environment-variable override
  (config.go:106-125 uses Viper AutomaticEnv; here ``LLMQ_A_B_C=x``
  overrides ``a.b.c``).
- ``default_config`` carries the reference's canonical defaults: the four
  queue tiers realtime 1s/100 · high 5s/200 · normal 30s/500 · low 5m/1000
  (config.go:151-156), worker batch=10 / interval=100ms / concurrent=50
  (config.go:169-173), retry backoff 1s→60s ×2.0 max 3 (config.go:174-179).

Unlike the reference — whose canonical configs/config.yaml names strategies
that don't exist in code and silently falls back (SURVEY.md §5 "Config") —
unknown strategy names here raise at load time.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import yaml

from llmq_tpu.core.types import Priority

VALID_LB_STRATEGIES = ("round_robin", "least_connections", "weighted_random", "adaptive_load")
VALID_SCHEDULER_STRATEGIES = ("static", "dynamic", "adaptive", "hybrid")
VALID_DISAGG_ROLES = ("prefill", "decode", "unified")


@dataclass
class ServerConfig:
    """Reference config.go:19-25, plus SSE admission control (the
    streaming path bypasses the queue plane, so it needs its own
    backpressure)."""
    host: str = "0.0.0.0"
    port: int = 8080
    read_timeout: float = 30.0
    write_timeout: float = 30.0
    #: Concurrent SSE streams accepted before new ones get 429; <= 0
    #: disables the cap.
    max_concurrent_streams: int = 32
    #: Engine pending-queue depth above which new streams get 503
    #: (shed before the backlog grows unbounded); <= 0 disables.
    stream_pending_limit: int = 256


@dataclass
class PersistenceConfig:
    """Durable conversation/message store.

    Replaces the reference's Postgres+Redis pair (config.go:27-48) with a
    pluggable backend: "memory" | "sqlite" | "redis" (redis gated on the
    client lib being importable).
    """
    backend: str = "memory"
    sqlite_path: str = "llmq_state.db"
    redis_url: str = "redis://localhost:6379/0"
    key_prefix: str = "llmq:"
    cache_ttl: float = 24 * 3600.0  # statemanager/manager.go:229-241 (24h)


@dataclass
class QueueLevelConfig:
    """One priority tier (reference config.go:57-62)."""
    priority: int = int(Priority.NORMAL)
    max_wait_time: float = 30.0
    max_concurrent: int = 500

    @property
    def name(self) -> str:
        return Priority(self.priority).tier_name


@dataclass
class WorkerConfig:
    """Reference config.go:64-69; defaults from :169-173."""
    count: int = 4
    max_batch_size: int = 10
    process_interval: float = 0.1
    max_concurrent: int = 50
    # Hard per-message deadline enforcement (reference worker.go:166
    # context.WithTimeout semantics): a process function that wedges past
    # ``message.timeout * hard_deadline_grace`` is abandoned by the
    # watchdog — its slot is freed and the message takes the
    # timeout/retry path. The wedged call keeps running on its (daemon)
    # thread; Python cannot kill it.
    #
    # At-least-once implication: abandonment means the original call may
    # STILL complete its side effects after the retry re-executes them —
    # duplicate execution. The grace multiple exists to keep that risk
    # confined to genuinely wedged calls: the cooperative deadline (what
    # ``ctx.expired()`` reports, and what counts as a timeout) stays at
    # 1× ``message.timeout``; a merely-slow handler that returns between
    # 1× and ``grace``× completes normally (work is never discarded and
    # re-executed — the module invariant). Only calls still running at
    # grace× are declared wedged. Set grace to 1.0 for strict reference
    # context.WithTimeout semantics (and accept duplicates for any slow
    # handler), or hard_deadline=False for purely cooperative deadlines.
    hard_deadline: bool = True
    hard_deadline_grace: float = 2.0


@dataclass
class RetryConfig:
    """Reference config.go:71-77; defaults from :174-179."""
    max_retries: int = 3
    initial_backoff: float = 1.0
    max_backoff: float = 60.0
    backoff_multiplier: float = 2.0
    strategy: str = "exponential"  # "exponential" | "fixed"


@dataclass
class QueueConfig:
    """Reference config.go:50-55."""
    max_queue_size: int = 10000
    levels: List[QueueLevelConfig] = field(default_factory=lambda: default_queue_levels())
    worker: WorkerConfig = field(default_factory=WorkerConfig)
    retry: RetryConfig = field(default_factory=RetryConfig)
    enable_metrics: bool = True
    # New: forward exhausted retries to the dead-letter queue (the
    # reference leaves this unwired; SURVEY.md #7 "Not wired").
    dead_letter_enabled: bool = True
    dead_letter_max_size: int = 1000
    stale_message_age: float = 3600.0  # cleanupStaleMessages stub (queue_manager.go:549-553), real here
    #: Directory for per-manager write-ahead logs; "" disables. The
    #: reference's queues are memory-only — every pending message dies
    #: with the process (SURVEY §5). With a wal_dir, pending and
    #: in-flight messages survive restarts (at-least-once redelivery).
    wal_dir: str = ""
    #: Shared spool directory for the SPLIT deployment (gateway and
    #: queue-manager as separate processes): the gateway relays drained
    #: messages into the spool, the queue-manager consumes and
    #: acknowledges them (queueing/spool.py). "" = monolith (in-process
    #: queues). The reference's split deployment has NO transport at
    #: all — its consumer never sees the producer's messages.
    spool_dir: str = ""


@dataclass
class SchedulerConfig:
    """Reference config.go:79-86."""
    strategy: str = "dynamic"
    monitor_interval: float = 10.0
    scale_up_threshold: int = 100
    scale_down_threshold: int = 10
    min_endpoints: int = 1
    max_endpoints: int = 10
    cooldown: float = 60.0

    def __post_init__(self) -> None:
        if self.strategy not in VALID_SCHEDULER_STRATEGIES:
            raise ValueError(
                f"unknown scheduler strategy {self.strategy!r}; valid: {VALID_SCHEDULER_STRATEGIES}")


@dataclass
class ResourceSchedulerConfig:
    """TPU-generalised resource scheduler (reference resource_scheduler.go:49-66)."""
    allocation_timeout: float = 300.0
    heartbeat_timeout: float = 30.0
    pending_process_interval: float = 1.0
    monitor_interval: float = 5.0
    scale_up_load: float = 0.8
    scale_down_load: float = 0.2
    scale_cooldown: float = 120.0


@dataclass
class LoadBalancerConfig:
    """Reference config.go:88-93."""
    strategy: str = "round_robin"
    health_check_interval: float = 30.0
    max_retries: int = 3
    session_affinity: bool = True
    session_ttl: float = 1800.0

    def __post_init__(self) -> None:
        if self.strategy not in VALID_LB_STRATEGIES:
            raise ValueError(
                f"unknown load balancer strategy {self.strategy!r}; valid: {VALID_LB_STRATEGIES}")


VALID_CLUSTER_AFFINITY = ("prefix", "session", "none")


@dataclass
class BreakerConfig:
    """Per-endpoint circuit breaker for remote dispatch
    (loadbalancer/circuit_breaker.py, docs/robustness.md). Trips on
    consecutive endpoint FAULTS (deadline misses never count), holds
    the endpoint out of rotation for a jittered exponential backoff,
    then admits one half-open probe dispatch."""
    enabled: bool = True
    #: Consecutive failures that trip CLOSED → OPEN.
    failure_threshold: int = 3
    #: First OPEN window in seconds; doubles per consecutive trip.
    base_backoff: float = 1.0
    max_backoff: float = 30.0
    #: ± fraction of the backoff randomized (seeded per endpoint, so
    #: scenarios replay deterministically).
    jitter: float = 0.2


@dataclass
class StoreResilienceConfig:
    """Store fault domain (conversation/resilience.py,
    docs/robustness.md): bounded deadlines, seeded retry and a
    store-scoped breaker wrapped around whichever ConversationStore /
    KVPayloadStore backend serves the tiering spill, the KV exchange,
    placement records and restart rehydration. Off by default — the
    wrapped store is byte-identical to the raw backend when disabled."""
    enabled: bool = False
    #: Hard wall deadline per store operation, in seconds. A dead OR
    #: slow store can never hold a hot path longer than this (plus
    #: bounded retries below).
    op_timeout_s: float = 0.25
    #: Bounded retry attempts for retryable errors only (sqlite
    #: ``database is locked``, redis connection resets).
    retries: int = 2
    #: Jittered-exponential retry backoff (seconds), seeded so chaos
    #: scenarios replay deterministically.
    retry_base_backoff_s: float = 0.01
    retry_max_backoff_s: float = 0.2
    retry_jitter: float = 0.2
    #: Consecutive per-op deadline misses that flip the store into
    #: timeout-degraded mode (the breaker core is timeout-neutral, so
    #: slow-not-dead stores need their own ladder rung).
    timeout_threshold: int = 3
    #: While timeout-degraded, one probe op is admitted per interval;
    #: everything else sheds fast to the consumer's degraded mode.
    probe_interval_s: float = 1.0
    #: Bounded replay buffer of conversation writes journaled by the
    #: state manager while the store is degraded; drained on recovery.
    replay_buffer: int = 256
    #: Seed for retry jitter and the breaker's backoff jitter.
    seed: int = 0
    #: Store-scoped breaker (same core as cluster dispatch, PR 5 rules:
    #: faults trip it, deadline misses never do).
    breaker: BreakerConfig = field(default_factory=BreakerConfig)


@dataclass
class StoreConfig:
    """Store-tier fault domain knobs (docs/robustness.md)."""
    resilience: StoreResilienceConfig = field(
        default_factory=StoreResilienceConfig)

    @property
    def enabled(self) -> bool:
        """Off-switch alias: the plane is the resilience wrapper."""
        return self.resilience.enabled


@dataclass
class ClusterConfig:
    """Replica-set serving plane (llmq_tpu/cluster/, docs/multihost.md).

    New scope: the reference has no multi-host dispatch at all (its
    scheduler fabricates worker URLs nothing ever calls,
    scheduler.go:299-301). ``peers`` is the whole bring-up story: a
    non-empty list makes serve/gateway modes construct a ClusterRouter
    over the listed replica base URLs and install it as the Worker
    process_fn — no hand-built router, no code changes."""
    #: Replica base URLs (``http://host:port``). Accepts a YAML list or
    #: a comma-separated string (the env-var form,
    #: ``LLMQ_CLUSTER_PEERS=http://a:8080,http://b:8080``).
    peers: List[str] = field(default_factory=list)
    #: serve mode: also register THIS process's engine as a
    #: ``local://`` endpoint so the replica set includes the local chip.
    include_local: bool = True
    #: Per-dispatch failover budget: how many OTHER replicas to try when
    #: a dispatch fails with a transport/replica error (timeouts never
    #: fail over — the work may have executed). 0 disables in-dispatch
    #: failover (the worker retry path + DLQ remain the backstop).
    failover_retries: int = 2
    #: Load above which conversation affinity spills to another replica
    #: (Endpoint.load is connections-based, in [0, 1]).
    spill_load: float = 0.9
    #: Affinity policy: "prefix" (conversation placement handles via the
    #: state manager, EWMA spill — the default), "session" (LB session
    #: map only), "none".
    affinity: str = "prefix"
    #: Graceful-drain bound for SIGTERM / admin drain: stop new
    #: dispatch, wait up to this many seconds for in-flight work.
    drain_timeout: float = 30.0
    #: HTTP transport budget per dispatch to a peer (seconds).
    peer_timeout: float = 120.0
    #: Per-endpoint circuit breaker for the dispatch path
    #: (docs/robustness.md).
    breaker: BreakerConfig = field(default_factory=BreakerConfig)

    def __post_init__(self) -> None:
        if isinstance(self.peers, str):
            self.peers = [p for p in
                          (s.strip() for s in self.peers.split(","))
                          if p]
        self.peers = [p.rstrip("/") for p in self.peers]
        if self.affinity not in VALID_CLUSTER_AFFINITY:
            raise ValueError(
                f"unknown cluster affinity {self.affinity!r}; "
                f"valid: {VALID_CLUSTER_AFFINITY}")

    @property
    def enabled(self) -> bool:
        return bool(self.peers)


@dataclass
class DisaggConfig:
    """Prefill/decode disaggregation plane (llmq_tpu/disagg/,
    docs/disaggregation.md): specialize replicas by role and hand
    conversation KV between them through the store tier acting as a
    cluster-wide KV exchange. Hard off-switch: ``enabled: false`` (the
    default) builds nothing — routing, tiering and the engine are
    byte-identical to unified behavior, pinned by test."""
    enabled: bool = False
    #: This replica's role: "prefill" serves first turns of long
    #: prompts, publishes each finished turn's conversation KV to the
    #: exchange and releases its local pin; "decode" claims published
    #: KV and serves follow-up turns; "unified" does both (participates
    #: in the exchange for migration/rehydration only).
    role: str = "unified"
    #: First-turn routing threshold in prompt tokens (estimated): at or
    #: past this, the turn routes to a prefill replica. Used when the
    #: ResourceScheduler has no learned prefill rate yet.
    long_prompt_tokens: int = 512
    #: Learned-rate threshold: when the ResourceScheduler's prefill
    #: estimator has observations, a first turn whose expected prefill
    #: time is at or past this many milliseconds is "long".
    long_prompt_ms: float = 250.0
    #: Exchange-entry time-to-live: a claim finding an older entry
    #: deletes it and falls back to recompute (a dead prefill replica's
    #: publication must never serve stale KV forever).
    claim_ttl_s: float = 120.0
    #: Prefill replicas publish each finished turn's conversation KV to
    #: the exchange and release the local HBM pin (their HBM is for
    #: prefill throughput, not decode-idle pins).
    publish_on_finish: bool = True
    #: On startup, scan the shared KV store for spilled blobs this
    #: replica owns and re-register them at tier="store" instead of
    #: orphaning them (replica restart rehydration).
    rehydrate_on_start: bool = True
    #: Negative-cache TTL for exchange lookups that missed: a follow-up
    #: turn re-checks the exchange at most this often (seconds).
    miss_ttl_s: float = 5.0

    def __post_init__(self) -> None:
        if self.role not in VALID_DISAGG_ROLES:
            raise ValueError(
                f"unknown disagg role {self.role!r}; "
                f"valid: {VALID_DISAGG_ROLES}")


@dataclass
class ConversationConfig:
    """Unified conversation service (reference spreads this over three
    managers; cmd/server/main.go:72-80 carries these defaults)."""
    max_conversations: int = 1000
    max_context_length: int = 4096
    max_conversations_per_user: int = 100
    ttl: float = 7 * 24 * 3600.0
    max_idle_time: float = 1800.0
    cleanup_interval: float = 300.0
    persist: bool = True


@dataclass
class LoggingConfig:
    """Reference config.go:95-99."""
    level: str = "info"
    format: str = "json"
    output: str = "stdout"


@dataclass
class SloConfig:
    """SLO targets + error-budget burn rates (observability/slo.py,
    docs/observability.md "Device telemetry"). Burn rate 1.0 = spending
    exactly the allowed error budget; deployments/alerts.yml pages on
    fast burn over the short window, warns on slow burn over the long
    one. FED by the flight recorder's metrics flush: requires
    ``observability.enabled`` and ``emit_metrics`` — with either off
    the tracker is force-disabled (and a warning logged) rather than
    reporting 0 burn with no feed."""
    enabled: bool = True
    #: TTFT target (ms) every request is held to; <= 0 disables.
    ttft_p99_ms: float = 2000.0
    #: End-to-end target (ms) for REALTIME-tier requests (the
    #: reference's 500 ms load-test gate); <= 0 disables.
    realtime_p99_ms: float = 500.0
    #: Promised success fraction (0.99 → 1 % error budget).
    objective: float = 0.99
    #: Rolling burn-rate windows in seconds (short = fast burn,
    #: long = slow burn).
    windows_s: List[float] = field(default_factory=lambda: [300.0,
                                                            3600.0])


@dataclass
class UsageConfig:
    """Usage plane: per-request resource attribution, goodput and waste
    decomposition (observability/usage.py, docs/observability.md
    "Usage & goodput"). ``enabled: false`` is a hard off-switch: the
    engine's charge points reduce to one attribute check and the
    ledger records nothing."""
    enabled: bool = True
    #: Distinct tenant ids that get their own Prometheus label before
    #: overflow collapses to "other" (JSON rollups keep exact ids).
    max_tenants: int = 64
    #: Per-conversation rollups kept (LRU).
    max_conversations: int = 1024
    #: Rolling window for the goodput gauge (seconds).
    goodput_window_s: float = 300.0


@dataclass
class CriticalPathConfig:
    """Critical-path plane: per-request latency attribution and
    replica-boot decomposition (observability/critical_path.py,
    docs/observability.md "Critical path & boot telemetry").
    ``enabled: false`` is a hard off-switch: no extra marks are
    stamped, the scrape-time join is skipped, and behavior is
    byte-identical to pre-feature code. FED by the flight recorder's
    metrics flush: requires ``observability.enabled`` and
    ``emit_metrics`` — with either off the analyzer is force-disabled
    (and a warning logged) rather than reporting empty rollups with
    no feed."""
    enabled: bool = True
    #: Finished per-request decompositions kept for the
    #: ``GET /api/v1/analysis/critical-path`` recent sample list.
    recent_capacity: int = 256
    #: Replica boot records kept in the boot registry (LRU by
    #: replica id) for /health, cluster overview and recovery joins.
    boot_capacity: int = 64


@dataclass
class ObservabilityConfig:
    """Request-lifecycle trace plane (llmq_tpu/observability/,
    docs/observability.md). ``enabled: false`` is a hard off-switch:
    no events are recorded anywhere and every ``record`` call returns
    after one attribute check."""
    enabled: bool = True
    #: Most recent request timelines kept in the flight-recorder ring.
    recorder_capacity: int = 1024
    #: Finished timelines retained separately because they breached the
    #: SLA or failed (survive ring eviction).
    slow_capacity: int = 256
    #: End-to-end latency above which a finished request counts as an
    #: SLA breach and is retained in the slow buffer; <= 0 disables
    #: breach tracking (failures are still retained).
    sla_ms: float = 5000.0
    #: Feed the Prometheus stage histograms on each terminal event.
    emit_metrics: bool = True
    #: Replica side: include this host's recorded events for the
    #: request in the ``POST /api/v1/generate`` response so the
    #: gateway can stitch a cross-process timeline.
    propagate_trace: bool = True
    #: SLO targets / burn-rate windows (observability/slo.py).
    slo: SloConfig = field(default_factory=SloConfig)
    #: Usage plane: attribution ledger, goodput, waste decomposition
    #: (observability/usage.py).
    usage: UsageConfig = field(default_factory=UsageConfig)
    #: Critical-path plane: per-request segment decomposition + replica
    #: boot telemetry (observability/critical_path.py).
    critical_path: CriticalPathConfig = field(
        default_factory=CriticalPathConfig)


@dataclass
class ChaosConfig:
    """Deterministic fault injection (llmq_tpu/chaos/,
    docs/robustness.md). ``enabled: false`` (the DEFAULT) is a hard
    off-switch: no injector exists and every compiled-in fault point is
    a single attribute check — behavior identical to pre-chaos code."""
    enabled: bool = False
    #: Seeds every rule's RNG: same seed + same rules + same call
    #: sequence ⇒ the same faults fire at the same places.
    seed: int = 0
    #: Fault rules, each ``{point, kind, probability, times,
    #: latency_ms, match}`` (chaos/injector.py FaultRule). Points:
    #: transport.request, transport.probe, engine.step,
    #: engine.hbm_alloc, wal.append, wal.fsync (fnmatch patterns OK).
    faults: List[Dict[str, Any]] = field(default_factory=list)


@dataclass
class TenantClassConfig:
    """One tenant class: fairness weight + quota envelope
    (llmq_tpu/tenancy/, docs/tenancy.md). Used both for named entries
    under ``tenancy.tenants`` and as the default class every unlisted
    tenant falls into."""
    #: Weighted-fair-queueing weight: under contention a tenant's token
    #: share within each priority level converges to
    #: ``weight / sum(weights of active tenants)``.
    weight: float = 1.0
    #: Sustained token admission rate (prompt + expected completion
    #: tokens per second) enforced at the API edge; <= 0 → unlimited.
    token_rate: float = 0.0
    #: Token-bucket burst capacity; <= 0 → one second of ``token_rate``
    #: (no extra burst headroom beyond the sustained rate).
    burst_tokens: float = 0.0
    #: Concurrent dispatched (popped, unfinished) messages; <= 0 →
    #: unlimited. Enforced at worker dispatch: the fair dequeue defers a
    #: capped tenant's queued work rather than rejecting it.
    max_inflight: int = 0
    #: Queued (pending) messages across the manager's tier queues;
    #: <= 0 → unlimited. Exceeding it is a 429 at the overload seam.
    max_queue_depth: int = 0

    def __post_init__(self) -> None:
        if self.weight <= 0:
            raise ValueError(
                f"tenancy weight must be > 0 (got {self.weight})")


@dataclass
class TenancyConfig:
    """Tenancy plane (llmq_tpu/tenancy/, docs/tenancy.md): weighted
    fair dequeue, per-tenant quotas and burst isolation over
    ``Message.tenant_id``. ``enabled: false`` (the DEFAULT) is a hard
    off-switch: no fair scheduler or registry state exists and the
    dequeue path is byte-identical to FIFO-within-priority."""
    enabled: bool = False
    #: Named tenant classes: tenant id → TenantClassConfig fields
    #: (weight, token_rate, burst_tokens, max_inflight,
    #: max_queue_depth). Unlisted tenants use ``default``.
    tenants: Dict[str, Any] = field(default_factory=dict)
    #: The class every tenant NOT listed in ``tenants`` belongs to.
    default: TenantClassConfig = field(
        default_factory=TenantClassConfig)
    #: Rolling window (seconds) for the achieved-share gauge
    #: (``tenant_share_ratio``).
    share_window_s: float = 60.0


@dataclass
class ScenariosConfig:
    """Scenario engine (llmq_tpu/scenarios/, docs/scenarios.md):
    trace-driven workload plane that compiles declarative scenario
    specs (YAML files under ``dir``) into closed-loop traffic against
    the real serve path and scores each run with the usage plane's
    goodput. ``enabled: false`` (the DEFAULT) is a hard off-switch —
    the package is a tool, never imported by the serving path, so
    "off" literally means zero import cost."""
    enabled: bool = False
    #: Directory holding named scenario YAML specs (the shipped five
    #: live in configs/scenarios/).
    dir: str = "configs/scenarios"
    #: Scenario names to run when the bench/CLI asks for "configured
    #: scenarios" ([] = every shipped scenario at reduced scale).
    run: List[str] = field(default_factory=list)
    #: Global multiplier on arrival rates and conversation caps — the
    #: same named spec serves as CI smoke (0.05) and full soak (1.0).
    scale: float = 1.0
    #: Where ``SCENARIO_<name>.json`` reports are written.
    out_dir: str = "."
    #: Write the per-run JSON report (the in-memory report dict is
    #: returned either way).
    emit_json: bool = True
    #: Seed for specs that don't pin one (same spec + seed ⇒ identical
    #: arrival/turn schedules).
    default_seed: int = 0


@dataclass
class OverloadConfig:
    """Adaptive overload shedding at the API layer (api/overload.py,
    docs/robustness.md): reject work the system cannot serve within
    its SLA with an explicit 429/503 + Retry-After instead of letting
    the backlog melt the engine. ``enabled: false`` is a hard
    off-switch — no admission checks run at all."""
    enabled: bool = True
    #: Total queued messages (across this manager's queues) above which
    #: new submissions get 429. 0 → 90% of queue.max_queue_size.
    queue_depth_limit: int = 0
    #: Shed when (estimated wait + prefill ETA) exceeds the request's
    #: timeout × this factor — the request cannot meet its own SLA.
    #: <= 0 disables the deadline-headroom check.
    deadline_headroom: float = 1.0
    #: Baseline Retry-After seconds when no better estimate exists.
    retry_after: float = 1.0


@dataclass
class AsyncPipelineConfig:
    """Asynchronously pipelined decode hot path (docs/performance.md
    "Async pipeline"): the engine keeps up to ``depth`` dispatched
    decode/mixed chunks in flight (double-buffered ``_InflightChunk``s
    chained through device-resident carries), token readback runs on a
    dedicated fetch thread that batches the device→host transfer
    across all rows, and sampling bookkeeping / detokenization / SSE
    framing move onto a small completion executor — so the engine
    thread's only job between dispatches is packing the next chunk.
    ``enabled: false`` is a hard off-switch: the engine schedules
    exactly as it did before the subsystem existed (single in-flight
    chunk + one carried dispatch, all completions inline on the
    engine thread, the echo executor fully synchronous)."""
    enabled: bool = True
    #: Dispatched-but-unreconciled chunks the engine may keep in
    #: flight. 2 = classic double buffering (the next chunk's compute
    #: hides the current chunk's readback); 1 disables the carried
    #: dispatch entirely (reconcile every chunk — strictly tighter than
    #: the off-switch, which keeps one carried dispatch).
    depth: int = 2
    #: Threads on the completion executor. Jobs for one request always
    #: land on the same worker, so per-request token/finish order is
    #: preserved at any worker count.
    completion_workers: int = 1

    def __post_init__(self) -> None:
        if not 1 <= self.depth <= 4:
            raise ValueError(
                f"async_pipeline.depth must be in [1, 4] "
                f"(got {self.depth})")
        if not 1 <= self.completion_workers <= 8:
            raise ValueError(
                f"async_pipeline.completion_workers must be in [1, 8] "
                f"(got {self.completion_workers})")


VALID_POOL_KINDS = ("none", "subprocess", "exec")


@dataclass
class ReplicaPoolConfig:
    """Provision seam for the control plane (controlplane/pool.py,
    docs/controlplane.md): where new replicas come from when the
    controller scales up, and how they are torn down on scale-down or
    replacement. Part of the ``controlplane`` subsystem — its
    off-switch is ``controlplane.enabled``."""
    #: "none" (controller never provisions — self-healing/ladder only),
    #: "subprocess" (spawn ``python -m llmq_tpu serve`` replicas on
    #: this host), "exec" (run provision_cmd/decommission_cmd — the
    #: compose/k8s hook).
    kind: str = "none"
    #: subprocess pool: replica N listens on ``base_port + N``.
    base_port: int = 8200
    #: subprocess pool: extra CLI args for the replica (e.g.
    #: ``[--backend, echo]``).
    args: List[str] = field(default_factory=list)
    #: exec pool: shell command run to bring up replica N (env carries
    #: ``LLMQ_REPLICA_SEQ``). Its LAST stdout line is the replica base
    #: URL unless ``url_template`` is set.
    provision_cmd: str = ""
    #: exec pool: shell command run to tear replica N down (env carries
    #: ``LLMQ_REPLICA_SEQ``/``LLMQ_REPLICA_ID``/``LLMQ_REPLICA_URL``).
    decommission_cmd: str = ""
    #: exec pool: replica base URL pattern, e.g.
    #: ``http://llmq-replica-{seq}:8080``; overrides stdout parsing.
    url_template: str = ""
    #: Seconds to wait for a provisioned replica's /health to answer
    #: before declaring the provision failed.
    ready_timeout: float = 20.0

    def __post_init__(self) -> None:
        if self.kind not in VALID_POOL_KINDS:
            raise ValueError(
                f"unknown replica pool kind {self.kind!r}; "
                f"valid: {VALID_POOL_KINDS}")


def default_rungs() -> List[Dict[str, Any]]:
    """The canonical degradation ladder (docs/controlplane.md): each
    rung tightens admission further; the controller climbs one rung per
    hot tick and relaxes in reverse order with hysteresis.

    Rung fields: ``name``; ``headroom_factor`` scales
    ``overload.deadline_headroom`` down (shed sooner);
    ``backlog_factor`` scales the backlog 429 threshold down;
    ``shed_priorities`` rejects those tiers outright (batch first);
    ``shed_tenant_weight_below`` rejects tenants whose configured
    fairness weight is under the bound (lowest-value traffic last)."""
    return [
        {"name": "tighten", "headroom_factor": 0.7,
         "backlog_factor": 0.7},
        {"name": "shed_batch", "headroom_factor": 0.5,
         "backlog_factor": 0.5, "shed_priorities": ["low"]},
        {"name": "shed_low_weight", "headroom_factor": 0.4,
         "backlog_factor": 0.4, "shed_priorities": ["low", "normal"],
         "shed_tenant_weight_below": 1.0},
    ]


@dataclass
class ControlPlaneConfig:
    """Self-healing control plane (llmq_tpu/controlplane/,
    docs/controlplane.md): a reconciliation controller that closes the
    observe→decide→act loop — SLO-burn-driven scaling through the
    replica pool, replacement of dead replicas, and a degradation
    ladder that tightens admission before SLOs burn. ``enabled:
    false`` (the DEFAULT) is a hard off-switch: no controller exists
    and every serving path is byte-identical to pre-controlplane
    behavior."""
    enabled: bool = False
    #: Reconcile tick period (seconds); <= 0 disables the loop thread
    #: (ticks must then be driven manually — tests do this).
    interval: float = 2.0
    min_replicas: int = 1
    max_replicas: int = 8
    #: Scale up when the FAST-window SLO burn rate crosses this
    #: (standard multi-window multi-burn-rate: 14.4x ≈ a 30-day budget
    #: gone in 2 days — the paging threshold).
    fast_burn_threshold: float = 14.4
    #: Scale up when the SLOW-window burn rate crosses this (6x
    #: sustained drains the budget well before the period ends).
    slow_burn_threshold: float = 6.0
    #: Queue backlog above ``backlog_per_replica × healthy replicas``
    #: also triggers scale-up (capacity signal that leads the burn).
    backlog_per_replica: int = 64
    #: Minimum seconds between deliberate scale decisions (replacement
    #: of a dead replica is exempt — healing must not wait).
    cooldown: float = 10.0
    #: Hard rate limit on scale/replace actions (thrash guard — the
    #: chaos flapping scenario pins it); <= 0 disables the limit.
    max_actions_per_minute: int = 6
    #: Recovery budget (seconds): kill→SLO-met above this logs an
    #: error; the chaos lane asserts recovery lands inside it.
    recovery_budget_s: float = 30.0
    #: Scale-down guard: keep ``(replicas - 1) × per-replica peak
    #: tokens/s >= measured load × this`` — never drain below the
    #: capacity the measured tokens/s requires.
    scale_down_headroom: float = 1.5
    #: Ladder hysteresis: escalate a rung when the fast burn rate is
    #: at/above this (1.0 = budget being spent exactly at the allowed
    #: rate — act BEFORE the paging threshold)…
    escalate_burn: float = 1.0
    #: …and relax one rung only after ``relax_after_ticks`` consecutive
    #: ticks with fast burn at/below this.
    relax_burn: float = 0.5
    relax_after_ticks: int = 3
    #: Degradation ladder rungs, mildest first (see
    #: :func:`default_rungs` for the field reference).
    rungs: List[Dict[str, Any]] = field(default_factory=default_rungs)
    #: Provision seam (controlplane/pool.py).
    pool: ReplicaPoolConfig = field(default_factory=ReplicaPoolConfig)

    def __post_init__(self) -> None:
        if self.min_replicas < 1:
            raise ValueError("controlplane.min_replicas must be >= 1")
        if self.max_replicas < self.min_replicas:
            raise ValueError(
                "controlplane.max_replicas must be >= min_replicas")


@dataclass
class SupervisorConfig:
    """Engine crash supervisor (engine/supervisor.py,
    docs/robustness.md): detects a dead engine thread, fails the
    in-flight handles (→ worker retry → WAL at-least-once redelivery,
    already-finished handles deduped) and restarts the loop. A crash
    LOOP is bounded: more than ``max_restarts`` within
    ``restart_window`` seconds stops restarting — the engine stays
    down, /health reports it, and the replica fails out of rotation."""
    enabled: bool = True
    check_interval: float = 0.5
    max_restarts: int = 5
    restart_window: float = 60.0


@dataclass
class MetricsConfig:
    """Reference config.go:100-104. Unlike the reference (which never
    mounts promhttp — SURVEY.md §5), the API server really serves this."""
    enabled: bool = True
    port: int = 9090
    path: str = "/metrics"


@dataclass
class ModelConfig:
    """Execution-plane model selection (new scope; BASELINE configs #2/#5)."""
    name: str = "llama3-tiny"          # a name of the models/ registry (models.model_names())
    checkpoint_path: str = ""           # orbax checkpoint dir; empty → random init
    tokenizer_path: str = ""            # local HF tokenizer dir; empty → bytes
    # Safetensors re-exports of Meta-original interleaved-rotary
    # checkpoints need the layout permutation (checkpoint.py); HF-native
    # checkpoints must leave this False.
    meta_rope_layout: bool = False
    dtype: str = "bfloat16"
    # "" | "int8": w8a8 dynamic quantization (ops/quant.py). int8 halves
    # the weight HBM footprint/bandwidth — the only way llama3-8B fits a
    # single 16 GB v5e chip (BASELINE config #2).
    quantization: str = ""
    # "" | "int8": quantized KV cache (per-token-per-head scales,
    # ops/quant.py int8-KV section): halves the pool bytes and the
    # decode step's KV read traffic — 8B serves B=64 instead of B=32.
    kv_quantization: str = ""
    max_seq_len: int = 2048
    vocab_size: int = 0                 # 0 → model default


VALID_PREFIX_EVICTION = ("lru", "fifo")


@dataclass
class PrefixCacheConfig:
    """Radix-tree prefix KV cache (prefixcache/radix.py,
    docs/prefix_cache.md). ``enabled: false`` is a hard off-switch —
    the engine then behaves exactly as it did before the subsystem
    existed (no tree, no ref sharing, no extra metrics movement)."""
    enabled: bool = True
    #: Cap on pages the tree may hold; 0 = bounded only by the KV pool
    #: (pool pressure evicts zero-ref leaves on demand).
    max_cached_pages: int = 0
    #: "lru" (default) or "fifo" — which zero-ref leaf goes first.
    eviction: str = "lru"
    #: Tail slots on the device, for a model family whose row state a
    #: window of K/V rebuilds (docs/prefix_cache.md "Tails"): each holds
    #: what lets ONE cached page boundary be adopted by such a family
    #: (its size is the family's: ``get_stats()["row_state"]``). 0 (the
    #: default): none — a prefix hit for a row-state family is declined
    #: and counted, as before there were tails. Ignored by a family
    #: whose pages are its whole cache.
    row_tail_slots: int = 0

    def __post_init__(self) -> None:
        if self.row_tail_slots < 0:
            raise ValueError(
                f"prefix_cache.row_tail_slots {self.row_tail_slots} < 0")
        if self.eviction not in VALID_PREFIX_EVICTION:
            raise ValueError(
                f"unknown prefix-cache eviction policy {self.eviction!r}; "
                f"valid: {VALID_PREFIX_EVICTION}")


@dataclass
class KVTieringConfig:
    """Tiered KV plane (llmq_tpu/tiering/, docs/tiering.md): HBM →
    host-DRAM → conversation-store hierarchy under the radix prefix
    cache and the conversation pins. Cold pinned/prefix KV demotes to
    preallocated host buffers instead of dying with its pin, promotes
    back with async prefetch at conversation re-arrival, and the
    coldest entries spill to the conversation store — recompute from
    the remembered token stream is the final fallback. ``enabled:
    false`` (the DEFAULT) is a hard off-switch: no plane, no worker
    thread, byte-identical HBM-only behavior."""
    enabled: bool = False
    #: Pinned host-DRAM budget for demoted page payloads (MiB). The
    #: pool is preallocated page-granular buffers (HostStaging's
    #: churn-kill discipline); content-free backends (echo) hold
    #: metadata-only entries bounded by ``host_max_conversations``.
    host_capacity_mb: int = 256
    #: Cap on conversations resident in the host tier (payload or
    #: metadata-only); the coldest spill to the store past it.
    host_max_conversations: int = 4096
    #: Spill the coldest host-tier entries to the conversation store
    #: (persistence.py KV-payload seam). Off → past-capacity entries
    #: fall back to recompute instead.
    store_spill: bool = True
    #: Seconds a promotion may wait on an in-flight extract/store load
    #: before admission falls back to recompute-from-tokens.
    promote_timeout_s: float = 5.0
    #: Demotion economics (ROADMAP 4c): "saved_rate" ranks evictions at
    #: every tier boundary (HBM pin reclaim, host→store spill) by the
    #: usage ledger's per-conversation ``saved_prefill_device_seconds``
    #: accrual rate — the measured recompute cost an eviction forfeits
    #: — with LRU as the tiebreak (and the exact fallback when the
    #: ledger is off or has no signal). "lru" restores pure
    #: least-recently-used.
    eviction_policy: str = "saved_rate"

    def __post_init__(self) -> None:
        if self.host_capacity_mb < 0:
            raise ValueError("kv_tiering.host_capacity_mb must be >= 0")
        if self.host_max_conversations < 1:
            raise ValueError(
                "kv_tiering.host_max_conversations must be >= 1")
        if self.promote_timeout_s <= 0:
            raise ValueError("kv_tiering.promote_timeout_s must be > 0")
        if self.eviction_policy not in ("lru", "saved_rate"):
            raise ValueError(
                f"kv_tiering.eviction_policy must be 'lru' or "
                f"'saved_rate' (got {self.eviction_policy!r})")


@dataclass
class MixedBatchConfig:
    """Token-budget mixed prefill+decode batching (docs/architecture.md
    "Mixed step"). When pending prefill work coexists with active decode
    rows, the engine fuses up to ``prefill_token_budget`` tokens of
    prefill slices into the SAME device program as the decode chunk —
    decode latency is then bounded by the budget instead of the longest
    admitted prompt. ``enabled: false`` is a hard off-switch: the engine
    schedules exactly as it did before the subsystem existed (dedicated
    prefill programs serialized with decode chunks)."""
    enabled: bool = True
    #: Max prefill tokens fused into one mixed iteration, across all
    #: slices. The decode rows' per-chunk stall is bounded by the time
    #: this many prefill tokens take.
    prefill_token_budget: int = 128
    #: Prefill sequences whose next slice can ride one mixed iteration
    #: (the compiled program's slice-row count; each row is
    #: ``prefill_token_budget // max_slices`` tokens wide).
    max_slices: int = 2

    def __post_init__(self) -> None:
        if self.prefill_token_budget < 8:
            raise ValueError(
                "mixed_batch.prefill_token_budget must be >= 8 "
                f"(got {self.prefill_token_budget})")
        if not 1 <= self.max_slices <= 16:
            raise ValueError(
                f"mixed_batch.max_slices must be in [1, 16] "
                f"(got {self.max_slices})")

    @property
    def slice_tokens(self) -> int:
        """Width of one compiled slice row."""
        return max(1, self.prefill_token_budget // self.max_slices)


@dataclass
class MeshConfig:
    """Mesh-native serving executor (docs/multihost.md "Mesh-native
    executor"). When enabled, the JAX executor builds a named
    ``dp×tp`` device mesh and serves THROUGH it: params shard per the
    regex partition-rule table (parallel/sharding.py), the paged KV
    pool splits its KV-head axis over ``tp`` and its page axis over
    ``dp`` (each dp replica owns its page universe, mirrored by the
    host allocator), every compiled program lowers under the mesh with
    explicit in/out shardings, and the warmup/export cache is keyed on
    the mesh geometry so single-chip artifacts can never serve a mesh
    (or vice versa). ``enabled: false`` (the DEFAULT) is a hard
    off-switch: no mesh is built and the executor is byte-identical to
    the single-chip path. The legacy ``tpu.mesh_shape`` knob still
    builds a mesh when this block is off (back-compat alias)."""
    enabled: bool = False
    #: Named axis sizes, e.g. {"dp": 2, "tp": 4}. Must multiply to the
    #: visible device count; one axis may be -1 (inferred).
    shape: Dict[str, int] = field(default_factory=dict)

    def __post_init__(self) -> None:
        for ax, n in (self.shape or {}).items():
            if ax not in ("dp", "tp"):
                raise ValueError(
                    f"executor.mesh.shape axis must be 'dp' or 'tp' "
                    f"(got {ax!r})")
            if not isinstance(n, int) or (n < 1 and n != -1):
                raise ValueError(
                    f"executor.mesh.shape[{ax!r}] must be a positive "
                    f"int or -1 (got {n!r})")
        if self.enabled and not self.shape:
            raise ValueError("executor.mesh.enabled requires a shape")


@dataclass
class ExecutorConfig:
    """Continuous-batching engine knobs (new scope)."""
    backend: str = "echo"               # echo | jax
    max_batch_size: int = 8             # decode slots
    prefill_buckets: List[int] = field(default_factory=lambda: [128, 512, 2048])
    kv_pages: int = 512
    page_size: int = 16                 # tokens per KV page
    max_decode_steps: int = 256
    # Decode steps per device program call: sampling + EOS latching stay
    # on-device for this many tokens, amortizing host↔device latency.
    # Also the engine's admission/preemption granularity.
    decode_chunk: int = 16
    # Prompts per batched-prefill program: an admission wave streams the
    # weights once for up to this many prompts' chunks.
    prefill_batch: int = 4
    preemption: bool = True
    kv_pin_ttl: float = 600.0           # per-conversation KV pin TTL in HBM
    prefix_cache: PrefixCacheConfig = field(default_factory=PrefixCacheConfig)
    kv_tiering: KVTieringConfig = field(default_factory=KVTieringConfig)
    mixed_batch: MixedBatchConfig = field(default_factory=MixedBatchConfig)
    async_pipeline: AsyncPipelineConfig = field(
        default_factory=AsyncPipelineConfig)
    supervisor: SupervisorConfig = field(default_factory=SupervisorConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)


@dataclass
class TPUConfig:
    """Mesh/topology declaration (new scope; BASELINE config #5). The
    platform is not a setting (``JAX_PLATFORMS`` chooses it) and
    neither is the compile-cache directory
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``:
    parallel/mesh.enable_compilation_cache)."""
    mesh_shape: Dict[str, int] = field(default_factory=dict)  # e.g. {"dp": 1, "tp": 8}


@dataclass
class Config:
    server: ServerConfig = field(default_factory=ServerConfig)
    persistence: PersistenceConfig = field(default_factory=PersistenceConfig)
    queue: QueueConfig = field(default_factory=QueueConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    resource_scheduler: ResourceSchedulerConfig = field(default_factory=ResourceSchedulerConfig)
    loadbalancer: LoadBalancerConfig = field(default_factory=LoadBalancerConfig)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    disagg: DisaggConfig = field(default_factory=DisaggConfig)
    conversation: ConversationConfig = field(default_factory=ConversationConfig)
    store: StoreConfig = field(default_factory=StoreConfig)
    logging: LoggingConfig = field(default_factory=LoggingConfig)
    metrics: MetricsConfig = field(default_factory=MetricsConfig)
    observability: ObservabilityConfig = field(
        default_factory=ObservabilityConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    overload: OverloadConfig = field(default_factory=OverloadConfig)
    controlplane: ControlPlaneConfig = field(
        default_factory=ControlPlaneConfig)
    tenancy: TenancyConfig = field(default_factory=TenancyConfig)
    scenarios: ScenariosConfig = field(default_factory=ScenariosConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    executor: ExecutorConfig = field(default_factory=ExecutorConfig)
    tpu: TPUConfig = field(default_factory=TPUConfig)

    def level_for(self, priority: Priority) -> QueueLevelConfig:
        for lvl in self.queue.levels:
            if lvl.priority == int(priority):
                return lvl
        return QueueLevelConfig(priority=int(priority))

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def default_queue_levels() -> List[QueueLevelConfig]:
    """The canonical 4 tiers (reference config.go:151-156)."""
    return [
        QueueLevelConfig(priority=int(Priority.REALTIME), max_wait_time=1.0, max_concurrent=100),
        QueueLevelConfig(priority=int(Priority.HIGH), max_wait_time=5.0, max_concurrent=200),
        QueueLevelConfig(priority=int(Priority.NORMAL), max_wait_time=30.0, max_concurrent=500),
        QueueLevelConfig(priority=int(Priority.LOW), max_wait_time=300.0, max_concurrent=1000),
    ]


def default_config() -> Config:
    """Reference GetDefaultConfig (config.go:127-203)."""
    return Config()


def _merge(obj: Any, data: Dict[str, Any], path: str = "") -> Any:
    """Recursively apply a dict onto a dataclass tree."""
    if not dataclasses.is_dataclass(obj):
        return data
    fields = {f.name: f for f in dataclasses.fields(obj)}
    for key, value in data.items():
        k = key.replace("-", "_")
        if k not in fields:
            raise ValueError(f"unknown config key: {path + key}")
        current = getattr(obj, k)
        if dataclasses.is_dataclass(current) and isinstance(value, dict):
            _merge(current, value, path + key + ".")
        elif k == "levels" and isinstance(value, list):
            obj.levels = [  # type: ignore[attr-defined]
                _merge(QueueLevelConfig(), lv, path + "levels.") for lv in value
            ]
        else:
            setattr(obj, k, value)
    # Re-validate (dataclass __post_init__ does not rerun on setattr).
    post = getattr(obj, "__post_init__", None)
    if post is not None:
        post()
    return obj


def _apply_env(cfg: Config, environ: Optional[Dict[str, str]] = None) -> None:
    """``LLMQ_SERVER_PORT=9000`` overrides ``server.port`` (Viper
    AutomaticEnv analogue, config.go:113)."""
    env = os.environ if environ is None else environ
    # Validation runs once per touched section AFTER every variable is
    # applied: settings that are only valid together (mesh enabled +
    # its shape) must not depend on the order of the environment.
    touched: Dict[int, Any] = {}
    for key, raw in env.items():
        if not key.startswith("LLMQ_"):
            continue
        parts = [p.lower() for p in key[len("LLMQ_"):].split("_")]
        # Greedy walk: match the longest joined field names.
        obj: Any = cfg
        i = 0
        ok = True
        while i < len(parts) and ok:
            if not dataclasses.is_dataclass(obj):
                ok = False
                break
            names = {f.name for f in dataclasses.fields(obj)}
            for j in range(len(parts), i, -1):
                cand = "_".join(parts[i:j])
                if cand in names:
                    if j == len(parts):
                        cur = getattr(obj, cand)
                        setattr(obj, cand, _coerce(raw, cur))
                        touched[id(obj)] = obj
                        i = j
                    else:
                        obj = getattr(obj, cand)
                        i = j
                    break
            else:
                ok = False
        # Unknown env keys are ignored (they may belong to other tools).
    for obj in touched.values():
        # Re-validate, mirroring _merge (an env var must not sneak in a
        # strategy name YAML would reject).
        post = getattr(obj, "__post_init__", None)
        if post is not None:
            post()


def _coerce(raw: str, current: Any) -> Any:
    if isinstance(current, bool):
        return raw.strip().lower() in ("1", "true", "yes", "on")
    if isinstance(current, int) and not isinstance(current, bool):
        return int(raw)
    if isinstance(current, float):
        return float(raw)
    if isinstance(current, list):
        return yaml.safe_load(raw)
    if isinstance(current, dict):
        return yaml.safe_load(raw)
    return raw


def load_config(path: Optional[str] = None, env: bool = True) -> Config:
    """YAML + env override, mirroring LoadConfig (config.go:106-125).

    Search order when ``path`` is None: ``./config.yaml``,
    ``./configs/config.yaml`` (reference searches {configPath, ., ./configs}).
    """
    cfg = default_config()
    if path is None:
        # CONFIG_PATH analogue. An explicitly-requested path (flag OR
        # env) that doesn't exist must fail fast, not silently serve
        # defaults — `path` stays set so the loop's else-branch raises.
        path = os.environ.get("LLMQ_CONFIG") or None
    candidates = [path] if path else ["config.yaml", os.path.join("configs", "config.yaml")]
    for cand in candidates:
        if cand and os.path.exists(cand):
            with open(cand, "r") as f:
                data = yaml.safe_load(f) or {}
            _merge(cfg, data)
            break
    else:
        if path:
            raise FileNotFoundError(f"config file not found: {path}")
    if env:
        _apply_env(cfg)
    return cfg
