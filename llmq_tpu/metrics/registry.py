"""Prometheus metrics, namespace ``llm_queue``.

Parity with the reference's seven metric families
(queue_manager.go:77-156): pending/processing gauges, completed/failed
counters, wait/process-time histograms, operations counter — plus
executor-plane families the reference cannot have (decode steps, KV pages).

Two reference gaps fixed here:

- The reference never mounts promhttp (SURVEY.md §5 "Metrics") — our API
  server serves :ref:`exposition` at ``/metrics``.
- ``CompleteMessage`` labels priority ``"unknown"``
  (queue_manager.go:388-389) — we track the message's priority and label
  correctly.

Metric families are process-level singletons so tests creating many
QueueManagers don't trip duplicate registration (the reference's tests
disable metrics entirely for this reason, tests/queue_factory_test.go:24).
"""

from __future__ import annotations

import threading
from typing import Optional

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

REGISTRY = CollectorRegistry()

_NAMESPACE = "llm_queue"
_LOCK = threading.Lock()
_SINGLETON: Optional["QueueMetrics"] = None

_WAIT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 2.5, 5, 10, 30, 60, 300)
_PROC_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30)
#: Sub-request stage latencies (admission waits, prefill, token gaps)
#: live well under a second; finer low end than the queue buckets.
_STAGE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                  0.25, 0.5, 1, 2.5, 5, 10, 30)
#: Per-chunk step-time components in MILLISECONDS: sub-0.1 ms host
#: dispatches on echo, up to seconds for a stalled transfer.
_STEP_MS_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10,
                    25, 50, 100, 250, 500, 1000, 2500)
#: Program compiles: sub-second export-cache loads up to multi-minute
#: cold Mosaic lowerings (303 s observed in BENCH_r03).
_COMPILE_BUCKETS = (0.1, 0.5, 1, 2.5, 5, 10, 30, 60, 120, 300, 600)
#: Critical-path segments in MILLISECONDS: sub-ms completion-pool lag
#: on echo up to multi-minute queue waits under saturation.
_CP_MS_BUCKETS = (0.1, 0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500,
                  1000, 2500, 5000, 10000, 30000, 60000, 300000)
#: Replica boot stages: sub-100 ms echo factory calls up to the
#: multi-minute cold Mosaic compile (same ceiling as _COMPILE_BUCKETS).
_BOOT_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1, 2.5, 5, 10, 30, 60, 120,
                 300, 600)

#: Metrics-cardinality contract (tests/test_metrics_cardinality.py):
#: EVERY label any family in this registry uses must appear here.
#: A frozenset value is a closed enum the observed label values must
#: stay within; ``None`` marks labels bounded by configuration or
#: hardware (engine names, endpoint ids, chip indices, program names,
#: queue/manager names, rolling-window labels) — those may not carry
#: per-request values (request ids, UUIDs), which the guard test
#: rejects by pattern. Adding a label without extending this table
#: fails the guard on purpose: unbounded label sets are how Prometheus
#: instances die.
LABEL_CONTRACT = {
    "manager": None,
    "queue": None,
    "engine": None,
    "endpoint": None,
    "chip": None,
    "program": None,
    "window": None,     # "5m"/"1h"-style, validated by pattern
    "priority": frozenset({"realtime", "high", "normal", "low",
                           "unknown"}),
    "operation": frozenset({"push", "pop", "batch_pop", "complete",
                            "fail", "requeue", "retry_stash", "remove"}),
    "status": frozenset({"success", "error", "healthy", "degraded",
                         "unhealthy", "draining"}),
    "tenant": None,     # client-supplied — bounded by the usage
                        # ledger (max_tenants + "other" collapse;
                        # id-shaped values never become labels)
    "reason": frozenset({"affinity", "spill", "select", "failover",
                         "handoff", "backlog", "sla", "engine_down",
                         # usage-plane waste decomposition
                         # (observability/usage.py WASTE_REASONS):
                         "retry", "crash", "preempt", "shed",
                         "cancelled", "error",
                         # tenancy plane (llmq_tpu/tenancy/):
                         # "tenant_quota" on requests_shed_total;
                         # rate/queue_depth/inflight on
                         # tenant_quota_rejections_total
                         # (tenancy.registry.QUOTA_REASONS).
                         "tenant_quota", "rate", "queue_depth",
                         "inflight",
                         # control plane (llmq_tpu/controlplane/):
                         # controller_actions_total reasons, plus
                         # "degraded" on requests_shed_total (the
                         # ladder's admission rejections).
                         "burn_fast", "burn_slow", "replica_dead",
                         "breaker_open", "rate_limited", "cooldown",
                         "recovered", "idle", "operator", "capacity",
                         "degraded"}),
    # Control plane (llmq_tpu/controlplane/controller.py): what the
    # reconcile loop did. Closed enum — the cardinality guard rejects
    # any action outside it.
    "action": frozenset({"scale_up", "scale_down", "replace",
                         "escalate", "relax", "pause", "resume",
                         "skip"}),
    "path": frozenset({"mixed", "program"}),
    # Tiered KV plane (llmq_tpu/tiering/, docs/tiering.md): where a
    # conversation's KV lives / what served a re-arrival. Closed enum
    # — "recompute" appears on hits only (nothing resides there).
    "tier": frozenset({"hbm", "host", "store", "recompute"}),
    # Disaggregation plane (llmq_tpu/disagg/, docs/disaggregation.md):
    # which role this replica plays in the prefill/decode split.
    # Closed enum — mirrors core.config.VALID_DISAGG_ROLES.
    "role": frozenset({"prefill", "decode", "unified"}),
    "point": None,      # compiled-in chaos fault points (fnmatch keys)
    "kind": frozenset({"error", "timeout", "partial", "oserror",
                       "latency", "crash"}),
    "code": frozenset({"429", "503", "500"}),
    "slo": frozenset({"ttft", "realtime"}),
    # Critical-path plane (observability/critical_path.py): the
    # exhaustive per-request segment decomposition. Closed enum —
    # mirrors critical_path.SEGMENTS.
    "segment": frozenset({"queue_wait", "dispatch", "admission",
                          "kv_promote", "handoff_claim", "prefill",
                          "decode_compute", "decode_stall",
                          "completion"}),
    # Replica boot decomposition (critical_path.BOOT_STAGES) on
    # llm_queue_replica_ready_seconds.
    "stage": frozenset({"provision", "artifact", "weights", "compile",
                        "warmup", "first_token"}),
    # Store fault domain (conversation/resilience.py,
    # docs/robustness.md): which store-backed plane is running its
    # degraded ladder rung. Closed enum — mirrors resilience.CONSUMERS.
    "consumer": frozenset({"tiering", "exchange", "state", "placement"}),
    # store_op_ms / wal_errors_total op label: the store-op surface
    # plus the WAL journal ops. Closed enum.
    "op": frozenset({"get", "put", "delete", "list",
                     "kv_get", "kv_put", "kv_delete", "kv_list",
                     # WAL journal ops (queueing/wal.py)
                     "push", "pop", "complete", "fail", "requeue",
                     "stash", "remove", "fsync"}),
    "outcome": frozenset({"ok", "error", "timeout", "shed"}),
}


class QueueMetrics:
    """The 7 queue-plane families (queue_manager.go:77-156) + executor families."""

    def __init__(self, registry: CollectorRegistry) -> None:
        ns = _NAMESPACE
        labels = ["manager", "queue", "priority"]
        self.pending = Gauge(
            f"{ns}_messages_pending", "Pending messages per queue", labels,
            registry=registry)
        self.processing = Gauge(
            f"{ns}_messages_processing", "In-flight messages per queue", labels,
            registry=registry)
        self.completed = Counter(
            f"{ns}_messages_completed_total", "Completed messages", labels,
            registry=registry)
        self.failed = Counter(
            f"{ns}_messages_failed_total", "Failed messages", labels,
            registry=registry)
        self.wait_time = Histogram(
            f"{ns}_message_wait_seconds", "Queue wait time", labels,
            buckets=_WAIT_BUCKETS, registry=registry)
        self.process_time = Histogram(
            f"{ns}_message_process_seconds", "Processing time", labels,
            buckets=_PROC_BUCKETS, registry=registry)
        self.operations = Counter(
            f"{ns}_operations_total", "Queue operations",
            ["manager", "operation", "status"], registry=registry)
        # Execution plane (new scope):
        self.decode_steps = Counter(
            f"{ns}_decode_steps_total", "Engine decode steps", ["engine"],
            registry=registry)
        self.generated_tokens = Counter(
            f"{ns}_generated_tokens_total", "Tokens generated", ["engine", "priority"],
            registry=registry)
        self.kv_pages_in_use = Gauge(
            f"{ns}_kv_pages_in_use", "Paged KV cache pages in use", ["engine"],
            registry=registry)
        self.kv_pinned_conversations = Gauge(
            f"{ns}_kv_pinned_conversations", "Conversations with pinned KV", ["engine"],
            registry=registry)
        self.batch_occupancy = Gauge(
            f"{ns}_batch_occupancy", "Decode-slot occupancy", ["engine"],
            registry=registry)
        self.preemptions = Counter(
            f"{ns}_preemptions_total", "Step-boundary preemptions",
            ["engine", "priority"], registry=registry)
        # Prefix cache (prefixcache/radix.py, docs/prefix_cache.md):
        self.prefix_cache_hits = Counter(
            f"{ns}_prefix_cache_hits_total",
            "Admissions that adopted a cached KV prefix", ["engine"],
            registry=registry)
        self.prefix_cache_misses = Counter(
            f"{ns}_prefix_cache_misses_total",
            "Admissions that found no cached prefix", ["engine"],
            registry=registry)
        self.cached_prefill_tokens = Counter(
            f"{ns}_cached_prefill_tokens_total",
            "Prompt tokens whose prefill was skipped (KV served from "
            "the prefix cache or a pinned conversation)", ["engine"],
            registry=registry)
        self.prefix_cache_pages = Gauge(
            f"{ns}_prefix_cache_pages",
            "KV pages currently held by the radix prefix cache",
            ["engine"], registry=registry)
        # Tiered KV plane (llmq_tpu/tiering/, docs/tiering.md):
        # residency per tier, re-arrival hit breakdown (incl. the
        # recompute fallback), and the demote/promote host-side
        # latency histograms. Flushed at scrape (tiering.flush_metrics)
        # — the demote/promote paths only buffer.
        self.kv_tier_pages = Gauge(
            f"{ns}_kv_tier_pages",
            "KV pages resident per tier (hbm = pinned conversation "
            "pages in the device pool; host/store = demoted entries)",
            ["engine", "tier"], registry=registry)
        self.kv_tier_bytes = Gauge(
            f"{ns}_kv_tier_bytes",
            "Serialized KV payload bytes resident per tier",
            ["engine", "tier"], registry=registry)
        self.kv_tier_hits = Counter(
            f"{ns}_kv_tier_hits_total",
            "Conversation re-arrivals by the tier that served their "
            "cached prefix (recompute = re-prefilled from the "
            "remembered token stream)", ["engine", "tier"],
            registry=registry)
        self.kv_tier_round_trips = Counter(
            f"{ns}_kv_tier_round_trips_total",
            "Demote→promote round-trips within the thrash window "
            "(a hot conversation bouncing between HBM and the host "
            "tier — the KVTierThrashing alert watches this)",
            ["engine"], registry=registry)
        self.kv_promote_ms = Histogram(
            f"{ns}_kv_promote_ms",
            "Host-side promotion work per re-arrival (page alloc + "
            "payload unpack + inject dispatch; the device transfer "
            "itself hides behind admission)", ["engine"],
            buckets=_STEP_MS_BUCKETS, registry=registry)
        self.kv_demote_ms = Histogram(
            f"{ns}_kv_demote_ms",
            "Host-side demotion work per reclaimed pin (gather "
            "dispatch + entry registration; the device→host transfer "
            "runs on the tiering worker)", ["engine"],
            buckets=_STEP_MS_BUCKETS, registry=registry)
        # Disaggregation plane (llmq_tpu/disagg/, docs/
        # disaggregation.md): the KV exchange's lifecycle counters and
        # the publish→claim handoff latency. ``role`` is the PUBLISHING
        # side for published/expired (who wrote the entry the event is
        # about is unknowable at claim time — the claimer labels with
        # its OWN role for claimed/fallback). Flushed at scrape
        # (disagg.flush_metrics) — publish/claim only buffer.
        self.kv_exchange_published = Counter(
            f"{ns}_kv_exchange_published_total",
            "Conversation KV entries published to the cluster-wide "
            "exchange (store tier under claimable keys)", ["role"],
            registry=registry)
        self.kv_exchange_claimed = Counter(
            f"{ns}_kv_exchange_claimed_total",
            "Exchange entries claimed (consumed) by a replica",
            ["role"], registry=registry)
        self.kv_exchange_expired = Counter(
            f"{ns}_kv_exchange_expired_total",
            "Exchange entries found past claim_ttl_s at claim time "
            "(publisher likely died mid-handoff; claimer recomputed)",
            ["role"], registry=registry)
        self.kv_exchange_fallback = Counter(
            f"{ns}_kv_exchange_fallback_total",
            "Handoffs that degraded to recompute (torn blob, store "
            "error, or no published entry for a routed conversation)",
            ["role"], registry=registry)
        self.kv_handoff_ms = Histogram(
            f"{ns}_kv_handoff_ms",
            "Publish→claim latency for exchange entries (wall clock "
            "across processes — how long KV waited in the exchange)",
            ["role"], buckets=_STEP_MS_BUCKETS, registry=registry)
        # Mixed prefill+decode batching (docs/architecture.md "Mixed
        # step"): per-iteration occupancy of the fused program, plus
        # the decode-stall attribution histogram. ``path`` on the stall
        # histogram is "mixed" (slices fused into the decode chunk —
        # bounded by mixed_batch.prefill_token_budget) or "program"
        # (dedicated prefill programs serializing with the chunk — the
        # unfused path's unbounded stall).
        self.mixed_step_decode_rows = Gauge(
            f"{ns}_mixed_step_decode_rows",
            "Decode rows in the most recent mixed iteration",
            ["engine"], registry=registry)
        self.mixed_step_prefill_tokens = Gauge(
            f"{ns}_mixed_step_prefill_tokens",
            "Prefill tokens fused into the most recent mixed iteration",
            ["engine"], registry=registry)
        self.mixed_budget_utilization = Gauge(
            f"{ns}_mixed_budget_utilization",
            "Fused prefill tokens / prefill_token_budget for the most "
            "recent mixed iteration", ["engine"], registry=registry)
        self.prefill_stall_ms = Histogram(
            f"{ns}_prefill_stall_ms",
            "Estimated milliseconds active decode rows stalled behind "
            "one round of prefill dispatches",
            ["engine", "path"],
            buckets=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50, 100,
                     250, 500, 1000, 2500),
            registry=registry)
        # Cluster serving plane (llmq_tpu/cluster/, docs/multihost.md):
        # ``reason`` is why the endpoint was chosen — "affinity" (the
        # conversation's prefix-holding replica), "spill" (affine
        # replica saturated/draining → rerouted), "select" (no affinity;
        # LB strategy), "failover" (retried here after another replica
        # failed mid-dispatch).
        self.cluster_dispatch = Counter(
            f"{ns}_cluster_dispatch_total",
            "Messages dispatched to a cluster endpoint",
            ["endpoint", "reason"], registry=registry)
        self.cluster_affinity_hit_rate = Gauge(
            f"{ns}_cluster_affinity_hit_rate",
            "Fraction of affinity-eligible dispatches routed to the "
            "conversation's prefix-holding replica (lifetime)",
            registry=registry)
        self.cluster_failovers = Counter(
            f"{ns}_cluster_failovers_total",
            "In-dispatch failovers away from a failed endpoint",
            ["endpoint"], registry=registry)
        self.cluster_drains = Counter(
            f"{ns}_cluster_drains_total",
            "Drain transitions per endpoint", ["endpoint"],
            registry=registry)
        self.cluster_endpoints = Gauge(
            f"{ns}_cluster_endpoints", "Registered endpoints by status",
            ["status"], registry=registry)
        # Request-lifecycle stage histograms (llmq_tpu/observability/,
        # docs/observability.md): observed ONCE per request at its
        # terminal trace event, from the flight recorder's stage
        # deltas. ``endpoint`` is the cluster endpoint id when the
        # request crossed the router, else the engine name, else
        # "local".
        stage_labels = ["priority", "endpoint"]
        self.stage_queue_wait = Histogram(
            f"{ns}_stage_queue_wait_seconds",
            "enqueued → scheduled (time in the priority queues)",
            stage_labels, buckets=_WAIT_BUCKETS, registry=registry)
        self.stage_dispatch = Histogram(
            f"{ns}_stage_dispatch_seconds",
            "scheduled → dispatched (worker pop to endpoint handoff)",
            stage_labels, buckets=_STAGE_BUCKETS, registry=registry)
        self.stage_admission = Histogram(
            f"{ns}_stage_admission_seconds",
            "dispatched → admitted (engine admission wait)",
            stage_labels, buckets=_STAGE_BUCKETS, registry=registry)
        self.stage_prefill = Histogram(
            f"{ns}_stage_prefill_seconds",
            "prefill_start → first_token",
            stage_labels, buckets=_STAGE_BUCKETS, registry=registry)
        self.ttft = Histogram(
            f"{ns}_ttft_seconds",
            "enqueued → first_token (user-perceived time to first token)",
            stage_labels, buckets=_WAIT_BUCKETS, registry=registry)
        self.decode_interarrival = Histogram(
            f"{ns}_decode_interarrival_seconds",
            "Mean inter-token gap over the request's decode phase",
            stage_labels, buckets=_STAGE_BUCKETS, registry=registry)
        self.sla_breaches = Counter(
            f"{ns}_sla_breaches_total",
            "Requests whose end-to-end latency breached "
            "observability.sla_ms", ["priority"], registry=registry)
        self.flightrecorder_timelines = Gauge(
            f"{ns}_flightrecorder_timelines",
            "Request timelines currently held in the flight-recorder "
            "ring", registry=registry)
        self.flightrecorder_slow_retained = Gauge(
            f"{ns}_flightrecorder_slow_retained",
            "Finished timelines retained for SLA breach / failure",
            registry=registry)
        self.dead_letter_depth = Gauge(
            f"{ns}_dead_letter_depth",
            "Messages currently parked in a dead-letter queue",
            ["queue"], registry=registry)
        self.dlq_handler_errors = Counter(
            f"{ns}_dlq_handler_errors_total",
            "DLQ handler/subscriber callbacks that raised (the push "
            "itself and the remaining handlers still ran)",
            ["queue"], registry=registry)
        # Robustness plane (llmq_tpu/chaos/, docs/robustness.md):
        self.chaos_injected = Counter(
            f"{ns}_chaos_injected_total",
            "Faults injected by the chaos plane", ["point", "kind"],
            registry=registry)
        self.requests_shed = Counter(
            f"{ns}_requests_shed_total",
            "Requests rejected by overload shedding; reason is "
            "backlog|sla|engine_down, code the HTTP status returned",
            ["reason", "code"], registry=registry)
        self.circuit_breaker_state = Gauge(
            f"{ns}_circuit_breaker_state",
            "Per-endpoint breaker state (0=closed, 1=half_open, 2=open)",
            ["endpoint"], registry=registry)
        self.circuit_breaker_trips = Counter(
            f"{ns}_circuit_breaker_trips_total",
            "Breaker transitions into OPEN per endpoint", ["endpoint"],
            registry=registry)
        # Store fault domain (conversation/resilience.py,
        # docs/robustness.md "Store fault domain"): every op on the
        # wrapped conversation store, its bounded-retry count, the
        # store-scoped breaker, and which consumers are currently on
        # their degraded ladder rung. Flushed at scrape
        # (resilience.flush_metrics) — ops only buffer.
        self.store_op_ms = Histogram(
            f"{ns}_store_op_ms",
            "Store operation latency by op and outcome (ok|error|"
            "timeout|shed; shed = refused fast while degraded)",
            ["op", "outcome"], buckets=_STEP_MS_BUCKETS,
            registry=registry)
        self.store_retries = Counter(
            f"{ns}_store_retries_total",
            "Bounded retries of retryable store errors (sqlite locked, "
            "redis connection resets)", registry=registry)
        self.store_breaker_state = Gauge(
            f"{ns}_store_breaker_state",
            "Store-scoped breaker state (0=closed, 1=half_open, 2=open)",
            registry=registry)
        self.store_degraded = Gauge(
            f"{ns}_store_degraded",
            "1 while the named consumer is running its degraded ladder "
            "rung (tiering parks in host, exchange recomputes, state "
            "serves cache + journals, placement routes role/load-only)",
            ["consumer"], registry=registry)
        # WAL fault rung (queueing/wal.py + queue_manager.py): journal
        # appends/fsyncs that hit an OSError (ENOSPC). Admission-path
        # failures shed the request with a 503; worker-side ops log
        # loudly and keep the worker loop alive.
        self.wal_errors = Counter(
            f"{ns}_wal_errors_total",
            "WAL journal operations that failed with an OSError "
            "(disk full / IO error); push failures shed 503, "
            "worker-side ops degrade durability but keep serving",
            ["op"], registry=registry)
        self.engine_restarts = Counter(
            f"{ns}_engine_restarts_total",
            "Engine loop restarts performed by the supervisor",
            ["engine"], registry=registry)
        self.engine_recovered_requests = Counter(
            f"{ns}_engine_recovered_requests_total",
            "In-flight requests failed over to the retry path by an "
            "engine crash recovery", ["engine"], registry=registry)
        # Device telemetry plane (llmq_tpu/observability/device.py,
        # docs/observability.md "Device telemetry"): per-chunk step
        # decomposition, live decode rate + MFU, HBM accounting,
        # compile/export-cache visibility, SLO burn rates.
        self.step_dispatch_ms = Histogram(
            f"{ns}_step_dispatch_ms",
            "Host-side batch assembly + program dispatch per decode/"
            "mixed chunk (ms)", ["engine"],
            buckets=_STEP_MS_BUCKETS, registry=registry)
        self.step_device_ms = Histogram(
            f"{ns}_step_device_ms",
            "Device execution per chunk: dispatch until the output "
            "array is ready (ms)", ["engine"],
            buckets=_STEP_MS_BUCKETS, registry=registry)
        self.step_readback_ms = Histogram(
            f"{ns}_step_readback_ms",
            "Token readback per chunk: device→host transfer of the "
            "sampled token matrix (ms)", ["engine"],
            buckets=_STEP_MS_BUCKETS, registry=registry)
        self.step_overlapped_ms = Histogram(
            f"{ns}_step_overlapped_ms",
            "Part of a chunk's device span that overlapped other "
            "in-flight work (async pipeline) — attributed explicitly "
            "so step_device_ms stays truthful (ms)", ["engine"],
            buckets=_STEP_MS_BUCKETS, registry=registry)
        self.pipeline_overlap_ratio = Gauge(
            f"{ns}_pipeline_overlap_ratio",
            "Fraction of in-flight device-span time hidden by the "
            "async decode pipeline (0 = fully serial)", ["engine"],
            registry=registry)
        self.decode_tokens_per_s = Gauge(
            f"{ns}_decode_tokens_per_s",
            "Decode tokens/s over the telemetry trailing window",
            ["engine"], registry=registry)
        self.mfu_pct = Gauge(
            f"{ns}_mfu_pct",
            "Live decode MFU estimate (percent of device peak FLOPs; "
            "0 for the echo backend)", ["engine"], registry=registry)
        self.host_device_rtt_ms = Gauge(
            f"{ns}_host_device_rtt_ms",
            "Measured host<->device round-trip floor (ms)", ["engine"],
            registry=registry)
        self.hbm_weights_bytes = Gauge(
            f"{ns}_hbm_weights_bytes",
            "Model weight bytes resident per chip", ["engine", "chip"],
            registry=registry)
        self.hbm_kv_pool_bytes = Gauge(
            f"{ns}_hbm_kv_pool_bytes",
            "Paged-KV pool bytes resident per chip", ["engine", "chip"],
            registry=registry)
        self.hbm_free_bytes = Gauge(
            f"{ns}_hbm_free_bytes",
            "Free HBM per chip (runtime memory_stats; absent on "
            "backends without it)", ["engine", "chip"],
            registry=registry)
        self.hbm_limit_bytes = Gauge(
            f"{ns}_hbm_limit_bytes",
            "Total HBM per chip (runtime memory_stats)",
            ["engine", "chip"], registry=registry)
        self.kv_pool_occupancy = Gauge(
            f"{ns}_kv_pool_occupancy",
            "Fraction of allocatable KV pages in use", ["engine"],
            registry=registry)
        self.kv_pool_fragmentation = Gauge(
            f"{ns}_kv_pool_fragmentation",
            "External fragmentation of the free page-id space "
            "(1 - largest contiguous free run / free pages)",
            ["engine"], registry=registry)
        self.compile_cache_hits = Counter(
            f"{ns}_compile_cache_hits_total",
            "Warmup programs served from the export disk cache",
            ["engine"], registry=registry)
        self.compile_cache_misses = Counter(
            f"{ns}_compile_cache_misses_total",
            "Warmup programs that had to trace+lower+compile",
            ["engine"], registry=registry)
        self.compile_seconds = Histogram(
            f"{ns}_compile_seconds",
            "Per-program warmup compile (or export-cache load) time",
            ["engine", "program"], buckets=_COMPILE_BUCKETS,
            registry=registry)
        self.warmup_progress = Gauge(
            f"{ns}_warmup_progress",
            "Warmup completion fraction (0..1) — programs compiled / "
            "programs planned", ["engine"], registry=registry)
        # Usage plane (llmq_tpu/observability/usage.py,
        # docs/observability.md "Usage & goodput"): who consumed the
        # hardware. ``tenant`` is bounded by the ledger (max_tenants;
        # overflow and id-shaped values collapse to "other").
        self.usage_device_seconds = Counter(
            f"{ns}_usage_device_seconds_total",
            "Attributed device-execute seconds behind DELIVERED output "
            "(useful work)", ["tenant", "priority"], registry=registry)
        self.usage_waste_seconds = Counter(
            f"{ns}_usage_waste_seconds_total",
            "Attributed device-execute seconds that bought no delivered "
            "output, by cause (retry|failover|crash|preempt|shed|"
            "cancelled|error)", ["reason"], registry=registry)
        self.usage_kv_page_seconds = Counter(
            f"{ns}_usage_kv_page_seconds_total",
            "KV page-seconds held (pages x wall time; shared prefix "
            "pages charged fractionally to their sharers)", ["tenant"],
            registry=registry)
        self.usage_saved_prefill_seconds = Counter(
            f"{ns}_usage_saved_prefill_device_seconds_total",
            "Estimated prefill device-seconds SAVED by prefix-cache / "
            "conversation-KV hits", ["tenant"], registry=registry)
        self.goodput_tokens_per_device_s = Gauge(
            f"{ns}_goodput_tokens_per_device_second",
            "Rolling goodput: SLO-met completion tokens per attributed "
            "device-second (waste counts in the denominator)",
            registry=registry)
        self.usage_tenants_tracked = Gauge(
            f"{ns}_usage_tenants_tracked",
            "Distinct tenants with usage rollups this process",
            registry=registry)
        # Tenancy plane (llmq_tpu/tenancy/, docs/tenancy.md): fairness
        # and quota visibility. ``tenant`` shares the usage ledger's
        # first-come max_tenants bound (overflow/id-shaped → "other");
        # gauges refresh at scrape time via tenancy.flush_metrics.
        self.tenant_virtual_time = Gauge(
            f"{ns}_tenant_virtual_time",
            "Weighted-fair-queueing virtual time per tenant (tokens / "
            "weight served; higher = further over its share)",
            ["tenant"], registry=registry)
        self.tenant_share_ratio = Gauge(
            f"{ns}_tenant_share_ratio",
            "Achieved token share / configured weight share over the "
            "tenancy.share_window_s rolling window (1.0 = exactly the "
            "configured share)", ["tenant"], registry=registry)
        self.tenant_quota_rejections = Counter(
            f"{ns}_tenant_quota_rejections_total",
            "Per-tenant quota enforcement events: rate and queue_depth "
            "are admission 429s, inflight counts dispatch-time "
            "deferrals by the in-flight cap", ["reason"],
            registry=registry)
        self.tenant_inflight = Gauge(
            f"{ns}_tenant_inflight",
            "Dispatched (popped, unfinished) messages per tenant",
            ["tenant"], registry=registry)
        # Unlabeled on purpose: the evicted ids are exactly the ones an
        # id spray mints, so a per-tenant label would be the cardinality
        # leak this counter exists to make visible.
        self.tenant_registry_evictions = Counter(
            f"{ns}_tenant_registry_evictions_total",
            "Unconfigured-tenant runtime state evicted by the tenant "
            "registry's LRU bound (MAX_TRACKED) — nonzero means an id "
            "spray is churning bucket/counter state",
            registry=registry)
        # Control plane (llmq_tpu/controlplane/, docs/controlplane.md):
        # the reconcile loop's actions and state. Incremented on the
        # controller tick (2s cadence — not a hot path, no deferred
        # flush needed).
        self.controller_actions = Counter(
            f"{ns}_controller_actions_total",
            "Control-plane reconcile actions (scale_up/scale_down/"
            "replace/escalate/relax/pause/resume; skip = an action the "
            "rate limit or cooldown suppressed)", ["action", "reason"],
            registry=registry)
        self.controller_rung = Gauge(
            f"{ns}_controller_rung",
            "Active degradation-ladder rung (0 = no degradation)",
            registry=registry)
        self.controller_target_replicas = Gauge(
            f"{ns}_controller_target_replicas",
            "Replica count the controller is reconciling toward",
            registry=registry)
        self.controller_live_replicas = Gauge(
            f"{ns}_controller_live_replicas",
            "Healthy/degraded replicas the controller observes",
            registry=registry)
        self.controller_recovery_seconds = Histogram(
            f"{ns}_controller_recovery_seconds",
            "Replica-loss recovery time: first replacement action "
            "until the cluster is back at target with SLO burn < 1",
            buckets=(0.5, 1, 2.5, 5, 10, 20, 30, 60, 120, 300),
            registry=registry)
        self.controller_paused = Gauge(
            f"{ns}_controller_paused",
            "1 while an operator has paused the controller "
            "(distinct from controlplane.enabled=false)",
            registry=registry)
        # Critical-path plane (observability/critical_path.py,
        # docs/observability.md "Critical path & boot telemetry"):
        # per-request latency attribution + replica boot decomposition.
        # Both fed at scrape time (recorder flush / boot-registry
        # flush) — nothing here touches the request hot path.
        self.critical_path_ms = Histogram(
            f"{ns}_critical_path_ms",
            "Per-request end-to-end latency attributed to one "
            "critical-path segment (segments conserve: they sum to "
            "the recorded e2e per request)", ["segment", "priority"],
            buckets=_CP_MS_BUCKETS, registry=registry)
        self.critical_path_dominant = Counter(
            f"{ns}_critical_path_dominant_total",
            "Requests whose largest critical-path segment was this "
            "one — the fleet-wide 'where does time go' headline",
            ["segment", "priority"], registry=registry)
        self.replica_ready_seconds = Histogram(
            f"{ns}_replica_ready_seconds",
            "Replica boot decomposition: seconds per boot stage "
            "(provision → artifact → weights → compile → warmup → "
            "first_token) across all ReplicaPool kinds + serve boot",
            ["stage"], buckets=_BOOT_BUCKETS, registry=registry)
        # SLO layer (llmq_tpu/observability/slo.py): burn rate 1.0 =
        # spending exactly the allowed error budget over the window.
        self.slo_burn_rate = Gauge(
            f"{ns}_slo_burn_rate",
            "Error-budget burn rate per SLO and rolling window",
            ["slo", "window"], registry=registry)
        self.slo_error_budget_remaining = Gauge(
            f"{ns}_slo_error_budget_remaining",
            "Remaining error-budget fraction over the longest window "
            "(0 = exhausted)", ["slo"], registry=registry)


def get_metrics() -> QueueMetrics:
    global _SINGLETON
    with _LOCK:
        if _SINGLETON is None:
            _SINGLETON = QueueMetrics(REGISTRY)
        return _SINGLETON


def exposition() -> bytes:
    """Prometheus text exposition for the API server's /metrics route."""
    get_metrics()  # ensure the families exist even before first increment
    try:
        # Stage-histogram observations are deferred off the request hot
        # path; the scrape is where they land (docs/observability.md).
        # This also FEEDS the SLO tracker, so it must run before the
        # SLO flush below.
        from llmq_tpu.observability.recorder import get_recorder
        get_recorder().flush_metrics()
    except Exception:  # noqa: BLE001 — scrape must not fail on trace plane
        pass
    try:
        # Device gauges (tok/s, MFU, HBM) refresh at scrape time too —
        # same hot-path discipline as the stage histograms.
        from llmq_tpu.observability.device import flush_all
        flush_all()
    except Exception:  # noqa: BLE001
        pass
    try:
        from llmq_tpu.observability.slo import get_slo_tracker
        get_slo_tracker().flush()
    except Exception:  # noqa: BLE001
        pass
    try:
        # Usage plane: finalized attribution records drain into the
        # per-tenant/waste counters here, after the recorder flush
        # above fed the goodput join.
        from llmq_tpu.observability.usage import get_usage_ledger
        get_usage_ledger().flush()
    except Exception:  # noqa: BLE001
        pass
    try:
        # Tiered KV plane: per-tier residency gauges, hit counters and
        # the buffered demote/promote histograms (docs/tiering.md).
        from llmq_tpu.tiering import flush_metrics as tiering_flush
        tiering_flush()
    except Exception:  # noqa: BLE001
        pass
    try:
        # Disaggregation plane: buffered exchange lifecycle counters +
        # handoff-latency observations (docs/disaggregation.md).
        from llmq_tpu.disagg import flush_metrics as disagg_flush
        disagg_flush()
    except Exception:  # noqa: BLE001
        pass
    try:
        # Critical-path plane: buffered replica-boot stage observations
        # (the per-request segment join rides the recorder flush above).
        from llmq_tpu.observability.critical_path import flush_boot_metrics
        flush_boot_metrics()
    except Exception:  # noqa: BLE001
        pass
    try:
        # Tenancy plane: buffered quota-rejection counts + per-tenant
        # virtual-time / share-ratio / in-flight gauges (after the
        # usage flush so the shared tenant-label bound is warm).
        from llmq_tpu.tenancy import flush_metrics as tenancy_flush
        tenancy_flush()
    except Exception:  # noqa: BLE001
        pass
    try:
        # Store fault domain: buffered per-op latency samples, retry
        # counts, breaker state and the per-consumer degraded gauges
        # (docs/robustness.md "Store fault domain").
        from llmq_tpu.conversation.resilience import \
            flush_metrics as store_flush
        store_flush()
    except Exception:  # noqa: BLE001
        pass
    return generate_latest(REGISTRY)
