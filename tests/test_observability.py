"""Request-lifecycle trace plane (llmq_tpu/observability/,
docs/observability.md): traceparent propagation, flight-recorder
ring/SLA retention, stage histograms, Chrome export, the REST trace
routes, structured log context — and the overhead guard that keeps the
trace plane under 3 % of an echo-engine request."""

from __future__ import annotations

import json
import logging
import threading
import time

import pytest

from llmq_tpu import observability
from llmq_tpu.api.server import ApiServer
from llmq_tpu.core.config import ObservabilityConfig, default_config
from llmq_tpu.core.types import Message
from llmq_tpu.engine import ByteTokenizer, EchoExecutor, InferenceEngine
from llmq_tpu.observability import (FlightRecorder, chrome_trace,
                                    make_traceparent, parse_traceparent,
                                    trace_id_for)
from llmq_tpu.utils.logging import (ConsoleFormatter, JsonFormatter,
                                    bind_log_context, reset_log_context)


# -- W3C trace context --------------------------------------------------------

class TestTraceContext:
    def test_uuid_message_id_is_the_trace_id(self):
        rid = "8c94e42e-6f3f-4a73-a18f-000000000001"
        assert trace_id_for(rid) == rid.replace("-", "")

    def test_non_uuid_id_hashes_deterministically(self):
        a, b = trace_id_for("msg-7"), trace_id_for("msg-7")
        assert a == b and len(a) == 32
        assert trace_id_for("msg-8") != a

    def test_header_roundtrip(self):
        hdr = make_traceparent("8c94e42e-6f3f-4a73-a18f-000000000001")
        ctx = parse_traceparent(hdr)
        assert ctx is not None
        assert ctx.trace_id == "8c94e42e6f3f4a73a18f000000000001"
        assert len(ctx.span_id) == 16
        assert ctx.to_header() == hdr

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-short-span-01",
        "00-" + "0" * 32 + "-abcdefabcdef1234-01",   # all-zero trace id
        "ff-" + "a" * 32 + "-abcdefabcdef1234-01",   # forbidden version
    ])
    def test_malformed_headers_are_none(self, bad):
        assert parse_traceparent(bad) is None


# -- flight recorder ----------------------------------------------------------

def _finish_timeline(rec, rid, *, duration=0.01, fail=False, t0=None):
    t0 = time.time() if t0 is None else t0
    rec.record(rid, "enqueued", ts=t0, priority="normal")
    rec.record(rid, "scheduled", ts=t0 + duration / 4)
    rec.record(rid, "first_token", ts=t0 + duration / 2)
    rec.record(rid, "failed" if fail else "completed",
               ts=t0 + duration, completion_tokens=5)


class TestFlightRecorder:
    def test_ring_eviction_is_bounded(self):
        rec = FlightRecorder(capacity=4, emit_metrics=False)
        for i in range(10):
            rec.record(f"r{i}", "enqueued")
        assert len(rec) == 4
        assert rec.get("r0") is None          # evicted
        assert rec.get("r9") is not None
        assert rec.get_stats()["dropped"] == 6

    def test_sla_breach_retained_after_ring_eviction(self):
        rec = FlightRecorder(capacity=2, sla_ms=50.0, emit_metrics=False)
        _finish_timeline(rec, "slow-1", duration=0.2)   # 200ms > 50ms
        for i in range(5):                               # flush the ring
            rec.record(f"noise{i}", "enqueued")
        tl = rec.get("slow-1")                           # from slow buffer
        assert tl is not None and tl.breached
        assert [t.request_id for t in rec.slow()] == ["slow-1"]
        assert rec.get_stats()["sla_breaches"] == 1

    def test_fast_requests_not_retained(self):
        rec = FlightRecorder(capacity=8, sla_ms=10_000.0,
                             emit_metrics=False)
        _finish_timeline(rec, "fast", duration=0.001)
        assert rec.slow() == []
        assert not rec.get("fast").breached

    def test_failed_requests_always_retained(self):
        rec = FlightRecorder(capacity=8, sla_ms=10_000.0,
                             emit_metrics=False)
        _finish_timeline(rec, "boom", duration=0.001, fail=True)
        assert [t.request_id for t in rec.slow()] == ["boom"]

    def test_cancelled_requests_finalize_but_are_not_retained(self):
        """A client disconnect is terminal but not a failure — a burst
        of ordinary disconnects must not evict real failures from the
        retention buffer."""
        rec = FlightRecorder(capacity=8, sla_ms=10_000.0,
                             emit_metrics=False)
        rec.record("gone", "enqueued")
        rec.record("gone", "cancelled")
        assert rec.get("gone").finalized
        assert rec.slow() == []

    def test_recent_zero_limit_returns_nothing(self):
        rec = FlightRecorder(capacity=8, emit_metrics=False)
        rec.record("r", "enqueued")
        assert rec.recent(0) == []
        assert rec.recent(-3) == []
        assert len(rec.recent(5)) == 1

    def test_slow_buffer_is_bounded(self):
        rec = FlightRecorder(capacity=64, slow_capacity=3, sla_ms=1.0,
                             emit_metrics=False)
        for i in range(8):
            _finish_timeline(rec, f"s{i}", duration=0.05)
        assert [t.request_id for t in rec.slow()] == ["s5", "s6", "s7"]

    def test_stage_latencies(self):
        rec = FlightRecorder(emit_metrics=False)
        t0 = 1000.0
        rec.record("r", "enqueued", ts=t0, priority="high")
        rec.record("r", "scheduled", ts=t0 + 0.5)
        rec.record("r", "dispatched", ts=t0 + 0.6, endpoint="ep0")
        rec.record("r", "admitted", ts=t0 + 0.7)
        rec.record("r", "prefill_start", ts=t0 + 0.75)
        rec.record("r", "first_token", ts=t0 + 1.0)
        rec.record("r", "completed", ts=t0 + 2.0, completion_tokens=11)
        lat = rec.get("r").stage_latencies()
        assert lat["queue_wait"] == pytest.approx(0.5)
        assert lat["dispatch"] == pytest.approx(0.1)
        assert lat["admission"] == pytest.approx(0.1)
        assert lat["prefill"] == pytest.approx(0.25)
        assert lat["ttft"] == pytest.approx(1.0)
        assert lat["decode_interarrival"] == pytest.approx(1.0 / 10)
        d = rec.get("r").to_dict()
        assert d["priority"] == "high" and d["endpoint"] == "ep0"

    def test_merge_stitches_and_dedups(self):
        rec = FlightRecorder(emit_metrics=False)
        rec.record("r", "enqueued", ts=1.0)
        remote = [{"stage": "admitted", "ts": 2.0, "host": "replica:1"},
                  {"stage": "completed", "ts": 3.0, "host": "replica:1"}]
        rec.merge("r", remote)
        rec.merge("r", remote)            # idempotent
        tl = rec.get("r")
        assert [e.stage for e in tl.sorted_events()] == [
            "enqueued", "admitted", "completed"]
        assert "replica:1" in tl.to_dict()["hosts"]
        # Merged terminal events do NOT finalize (the remote host owns
        # its own histograms); the local terminal stamp does.
        assert not tl.finalized

    def test_disabled_recorder_records_nothing(self):
        rec = FlightRecorder(enabled=False, emit_metrics=False)
        rec.record("r", "enqueued")
        assert len(rec) == 0 and rec.get("r") is None

    def test_reconfigure_in_place(self):
        rec = FlightRecorder(capacity=100, emit_metrics=False)
        for i in range(50):
            rec.record(f"r{i}", "enqueued")
        rec.reconfigure(capacity=10, sla_ms=1.0, enabled=True)
        assert len(rec) == 10
        cfg = ObservabilityConfig(enabled=True, recorder_capacity=7,
                                  sla_ms=123.0)
        singleton = observability.configure(cfg)
        try:
            assert singleton is observability.get_recorder()
            assert singleton.capacity == 7 and singleton.sla_ms == 123.0
        finally:
            # The singleton is the process's: a ring of 7 left behind
            # evicted timelines under whichever test file the worker
            # ran next (tests/test_critical_path.py, by file order).
            observability.configure(ObservabilityConfig())

    def test_concurrent_record_and_read(self):
        rec = FlightRecorder(capacity=128, sla_ms=1.0,
                             emit_metrics=False)
        stop = threading.Event()
        errors = []

        def writer(i):
            n = 0
            while not stop.is_set():
                _finish_timeline(rec, f"w{i}-{n}", duration=0.01)
                n += 1

        def reader():
            while not stop.is_set():
                try:
                    rec.recent(10)
                    rec.slow()
                    rec.get_stats()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = ([threading.Thread(target=writer, args=(i,))
                    for i in range(4)]
                   + [threading.Thread(target=reader) for _ in range(2)])
        for t in threads:
            t.start()
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert not errors
        assert len(rec) <= 128


# -- metrics ------------------------------------------------------------------

class TestStageMetrics:
    def test_terminal_event_feeds_stage_histograms(self):
        from llmq_tpu.metrics.registry import exposition
        rec = FlightRecorder(emit_metrics=True, sla_ms=1.0)
        t0 = time.time()
        rec.record("m", "enqueued", ts=t0, priority="realtime")
        rec.record("m", "scheduled", ts=t0 + 0.01)
        rec.record("m", "dispatched", ts=t0 + 0.02, endpoint="epX")
        rec.record("m", "admitted", ts=t0 + 0.03)
        rec.record("m", "prefill_start", ts=t0 + 0.03)
        rec.record("m", "first_token", ts=t0 + 0.05)
        rec.record("m", "completed", ts=t0 + 0.1, completion_tokens=4)
        # Observation is deferred off the hot path; the singleton is
        # flushed by exposition() itself, a standalone recorder here.
        assert rec.flush_metrics() == 1
        exp = exposition().decode()
        for family in ("llm_queue_stage_queue_wait_seconds",
                       "llm_queue_stage_dispatch_seconds",
                       "llm_queue_stage_admission_seconds",
                       "llm_queue_stage_prefill_seconds",
                       "llm_queue_ttft_seconds",
                       "llm_queue_decode_interarrival_seconds",
                       "llm_queue_sla_breaches_total",
                       "llm_queue_flightrecorder_timelines",
                       "llm_queue_dead_letter_depth"):
            assert family in exp, family
        assert ('llm_queue_ttft_seconds_count'
                '{endpoint="epX",priority="realtime"}') in exp
        # 100ms end-to-end breached the 1ms SLA.
        assert 'llm_queue_sla_breaches_total{priority="realtime"}' in exp


# -- chrome export ------------------------------------------------------------

class TestChromeExport:
    def test_hosts_become_processes_and_stages_slices(self):
        rec = FlightRecorder(emit_metrics=False)
        rec.record("r", "enqueued", ts=10.0, host="gw:1")
        rec.record("r", "dispatched", ts=10.1, host="gw:1")
        rec.merge("r", [{"stage": "admitted", "ts": 10.2,
                         "host": "replica:2"},
                        {"stage": "completed", "ts": 10.5,
                         "host": "replica:2"}])
        doc = chrome_trace([rec.get("r")])
        names = {e["args"].get("name") for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert {"gw:1", "replica:2"} <= names
        slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert any(e["name"] == "enqueued→dispatched" for e in slices)
        assert any(e["name"] == "admitted→completed" for e in slices)

    def test_span_recorder_spans_stitch_in(self):
        from llmq_tpu.utils.profiling import SpanRecorder
        prof = SpanRecorder()
        with prof.span("engine.dispatch", rows=3):
            pass
        rec = FlightRecorder(emit_metrics=False)
        rec.record("r", "enqueued")
        doc = chrome_trace([rec.get("r")], spans=prof.snapshot(),
                           jax_trace_dir="/tmp/xprof")
        assert any(e.get("name") == "engine.dispatch"
                   for e in doc["traceEvents"])
        assert doc["otherData"]["jax_trace_dir"] == "/tmp/xprof"


# -- REST routes --------------------------------------------------------------

def _echo_engine(name="obs0"):
    eng = InferenceEngine(EchoExecutor(batch_size=4), ByteTokenizer(),
                          name=name, enable_metrics=False)
    eng.start()
    return eng


class TestTraceRoutes:
    def test_trace_route_404_then_200(self):
        api = ApiServer(default_config())
        status, out, _ = api.dispatch(
            "GET", "/api/v1/requests/nope/trace", b"")
        assert status == 404
        observability.record("known-req", "enqueued", priority="low")
        status, out, _ = api.dispatch(
            "GET", "/api/v1/requests/known-req/trace", b"")
        assert status == 200
        assert out["request_id"] == "known-req"
        assert out["trace_id"] == trace_id_for("known-req")
        assert out["events"][0]["stage"] == "enqueued"

    def test_chrome_format(self):
        api = ApiServer(default_config())
        observability.record("chrome-req", "enqueued")
        observability.record("chrome-req", "completed")
        status, out, _ = api.dispatch(
            "GET", "/api/v1/requests/chrome-req/trace?format=chrome", b"")
        assert status == 200 and "traceEvents" in out

    def test_flightrecorder_admin_route(self):
        api = ApiServer(default_config())
        observability.record("fr-req", "enqueued")
        status, out, _ = api.dispatch(
            "GET", "/api/v1/admin/flightrecorder?limit=5", b"")
        assert status == 200
        assert out["enabled"] is True
        assert any(t["request_id"] == "fr-req" for t in out["recent"])

    def test_generate_sync_records_traceparent_and_returns_trace(self):
        eng = _echo_engine("obs-replica")
        api = ApiServer(default_config(), engine=eng)
        try:
            msg_id = "8c94e42e-6f3f-4a73-a18f-00000000aaaa"
            hdr = make_traceparent(msg_id)
            body = json.dumps({"id": msg_id, "content": "hello trace",
                               "user_id": "t", "timeout": 30}).encode()
            status, out, _ = api.dispatch(
                "POST", "/api/v1/generate", body,
                headers={"Traceparent": hdr})
            assert status == 200 and out["response"] == "hello trace"
            # The replica ships its stage events back for stitching...
            stages = [e["stage"] for e in out["trace"]]
            assert "dispatched" in stages and "completed" in stages
            assert "admitted" in stages and "first_token" in stages
            # ...and bound the caller's W3C context to its timeline.
            tl = observability.get_recorder().get(msg_id)
            dispatched = next(e for e in tl.events
                              if e.stage == "dispatched")
            assert dispatched.meta["traceparent"] == hdr
            assert tl.trace_id == parse_traceparent(hdr).trace_id
        finally:
            eng.stop()

    def test_sse_stream_carries_traceparent_header(self):
        import urllib.request
        eng = _echo_engine("obs-sse")
        api = ApiServer(default_config(), engine=eng)
        port = api.start(host="127.0.0.1", port=0)
        try:
            body = json.dumps({"content": "stream me", "user_id": "t",
                               "stream": True}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/v1/messages", data=body,
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=15) as resp:
                tp = resp.headers.get("traceparent")
                rid = resp.headers.get("X-Request-Id")
                resp.read()
            assert parse_traceparent(tp) is not None
            assert parse_traceparent(tp).trace_id == trace_id_for(rid)
            tl = observability.get_recorder().get(rid)
            stages = {e.stage for e in tl.events}
            assert {"enqueued", "dispatched", "first_token",
                    "completed"} <= stages
        finally:
            api.stop()
            eng.stop()


# -- structured logging -------------------------------------------------------

class TestLogContext:
    def _record(self):
        return logging.LogRecord("llmq.test", logging.INFO, __file__, 1,
                                 "hello %s", ("world",), None)

    def test_json_formatter_merges_bound_fields(self):
        token = bind_log_context(request_id="r-1",
                                 conversation_id="c-1", endpoint="ep9")
        try:
            out = json.loads(JsonFormatter().format(self._record()))
        finally:
            reset_log_context(token)
        assert out["msg"] == "hello world"
        assert out["request_id"] == "r-1"
        assert out["conversation_id"] == "c-1"
        assert out["endpoint"] == "ep9"
        # Binding is scoped: after reset the fields are gone.
        out2 = json.loads(JsonFormatter().format(self._record()))
        assert "request_id" not in out2

    def test_console_formatter_appends_fields(self):
        token = bind_log_context(request_id="r-2")
        try:
            line = ConsoleFormatter().format(self._record())
        finally:
            reset_log_context(token)
        assert "request_id=r-2" in line

    def test_bindings_do_not_leak_across_threads(self):
        seen = {}

        def other():
            seen["ctx"] = json.loads(
                JsonFormatter().format(self._record()))

        token = bind_log_context(request_id="main-thread")
        try:
            t = threading.Thread(target=other)
            t.start()
            t.join()
        finally:
            reset_log_context(token)
        assert "request_id" not in seen["ctx"]

    def test_worker_thread_context_resets_after_each_dispatch(self):
        """Audit pin (ISSUE 6 satellite): ``Worker._run_one`` must
        leave its thread's log context EXACTLY as it found it after
        every dispatch — success or failure. Worker pool threads are
        reused across requests, so a leaked binding would stamp request
        B's log lines with request A's identity. The audit found the
        bind/reset pair correct (reset in ``finally``); this test pins
        it against regression."""
        from llmq_tpu.queueing.queue_manager import QueueManager
        from llmq_tpu.queueing.worker import Worker
        from llmq_tpu.utils.logging import current_log_context

        seen = []

        def fn(ctx, msg):
            seen.append(current_log_context())
            if msg.content == "boom":
                raise RuntimeError("boom")

        cfg = default_config()
        cfg.queue.enable_metrics = False
        mgr = QueueManager("ctx-audit", config=cfg)
        worker = Worker("ctx-audit", mgr, fn)
        try:
            reset_log_context()   # known-clean baseline on this thread

            mgr.push_message(Message(id="ctx-a", content="ok",
                                     conversation_id="conv-a",
                                     timeout=5.0))
            worker.process_one_sync(mgr.pop_message("normal"))
            # Bound during the dispatch, gone after it.
            assert seen[0].get("request_id") == "ctx-a"
            assert seen[0].get("conversation_id") == "conv-a"
            assert current_log_context() == {}

            # Failure path: the reset runs in a finally, so a raising
            # process_fn must not leak either.
            mgr.push_message(Message(id="ctx-b", content="boom",
                                     timeout=5.0))
            worker.process_one_sync(mgr.pop_message("normal"))
            assert seen[1].get("request_id") == "ctx-b"
            # No bleed of the PREVIOUS request's fields into this one.
            assert seen[1].get("conversation_id") != "conv-a"
            assert current_log_context() == {}

            # Nested on top of an outer binding: the token restore must
            # bring back exactly the outer context, not empty it.
            outer = bind_log_context(service="gateway")
            try:
                mgr.push_message(Message(id="ctx-c", content="ok",
                                         timeout=5.0))
                worker.process_one_sync(mgr.pop_message("normal"))
                assert seen[2].get("request_id") == "ctx-c"
                assert seen[2].get("service") == "gateway"  # merged
                assert current_log_context() == {"service": "gateway"}
            finally:
                reset_log_context(outer)
        finally:
            worker.stop()

    def test_worker_binds_request_context(self):
        from llmq_tpu.core.types import Priority
        from llmq_tpu.queueing.queue_manager import QueueManager
        from llmq_tpu.queueing.worker import Worker
        cfg = default_config()
        cfg.queue.enable_metrics = False
        mgr = QueueManager("obs-ctx", config=cfg)
        captured = {}

        def process(ctx, msg):
            from llmq_tpu.utils.logging import current_log_context
            captured.update(current_log_context())

        w = Worker("ctx-test", mgr, process)
        msg = Message(id="bound-1", content="x",
                      conversation_id="conv-9",
                      priority=Priority.NORMAL)
        mgr.push_message(msg)
        w.process_batch()
        assert captured["request_id"] == "bound-1"
        assert captured["conversation_id"] == "conv-9"


# -- lifecycle integration (engine) -------------------------------------------

class TestEngineTimeline:
    def test_engine_stamps_lifecycle_stages(self):
        eng = _echo_engine("obs-engine")
        try:
            msg = Message(id="eng-trace-1", content="time me",
                          timeout=30.0)
            observability.record(msg.id, "enqueued", priority="normal")
            eng.process_fn(None, msg)
            tl = observability.get_recorder().get(msg.id)
            stages = [e.stage for e in tl.sorted_events()]
            for s in ("enqueued", "admitted", "prefill_start",
                      "first_token", "completed"):
                assert s in stages, (s, stages)
            # Wall-clock ordering survived the perf_counter conversion.
            idx = {s: stages.index(s) for s in stages}
            assert idx["admitted"] <= idx["first_token"] < idx["completed"]
            lat = tl.stage_latencies()
            assert "ttft" in lat and lat["ttft"] >= 0
        finally:
            eng.stop()


# -- overhead guard (acceptance criterion: <= 3 % on the echo path) -----------

class TestOverheadGuard:
    def test_per_request_stamping_under_3pct_of_echo_request(self):
        """The full per-request trace cost (the exact 9-event stamping
        pattern the serve path produces, including terminal finalize)
        must stay under 3 % of one request through the echo-engine
        bench path (queue → worker → engine, bench_poisson_echo's
        wiring) — the bound the acceptance criterion puts on
        trace-plane overhead. Deterministic decomposition rather than
        a wall-clock A/B: run-to-run scheduler noise on shared CI
        exceeds 3 %, the per-call stamping cost does not."""
        from llmq_tpu.queueing.queue_manager import QueueManager
        from llmq_tpu.queueing.worker import Worker
        eng = _echo_engine("obs-bench")
        cfg = default_config()
        cfg.queue.enable_metrics = False
        cfg.queue.worker.process_interval = 0.002
        cfg.queue.worker.max_batch_size = 128
        mgr = QueueManager("obs-bench", config=cfg)
        worker = Worker("obs-bench", mgr, eng.process_fn)
        worker.start()
        try:
            n = 40
            t0 = time.perf_counter()
            for i in range(n):
                mgr.push_message(Message(id=f"bench-{i}",
                                         content="measure me",
                                         timeout=30.0))
            deadline = time.time() + 30
            while time.time() < deadline:
                if (worker.stats.to_dict()["succeeded"] >= n):
                    break
                time.sleep(0.002)
            per_request = (time.perf_counter() - t0) / n
            assert worker.stats.to_dict()["succeeded"] >= n
        finally:
            worker.stop()
            eng.stop()

        import gc
        rec = FlightRecorder(capacity=8192, sla_ms=5000.0,
                             emit_metrics=True)

        def stamp_batch(k0: int, m: int) -> float:
            t0 = time.perf_counter()
            for i in range(k0, k0 + m):
                rid = f"ovh-{i}"
                ts = time.time()
                rec.record(rid, "enqueued", ts=ts, priority="normal")
                rec.record(rid, "scheduled", ts=ts, worker="w0",
                           priority="normal", retry_count=0)
                rec.record(rid, "dispatched", ts=ts, endpoint="e0",
                           reason="select", priority="normal")
                rec.record_many(rid, [
                    ("admitted", ts,
                     {"engine": "e0", "priority": "normal"}),
                    ("prefill_start", ts, {"engine": "e0"}),
                    ("prefill_done", ts, {"engine": "e0"}),
                    ("first_token", ts, {"engine": "e0"}),
                    ("completed", ts, {"engine": "e0",
                                       "completion_tokens": 16}),
                ])
                rec.record(rid, "completed", ts=ts, worker="w0",
                           priority="normal", endpoint="e0")
            return (time.perf_counter() - t0) / m
        # Best-of-batches: the stamping cost is deterministic; GC
        # pauses and neighbor-test threads are not. The minimum is the
        # honest per-request cost.
        gc.collect()
        per_timeline = min(stamp_batch(k * 100, 100) for k in range(6))
        assert per_timeline < 0.03 * per_request, (
            f"trace stamping {per_timeline * 1e6:.1f}µs/request vs "
            f"echo bench request {per_request * 1e6:.1f}µs — over the "
            f"3% budget")
