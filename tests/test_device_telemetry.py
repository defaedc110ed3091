"""Device telemetry plane (llmq_tpu/observability/device.py,
docs/observability.md "Device telemetry"): step-time decomposition
through the echo and JAX serving paths, the shared MFU/RTT math bench
uses, HBM accounting, compile/export-cache visibility, SLO burn rates —
and the <3 % step-path overhead guard the acceptance criterion sets."""

from __future__ import annotations

import time

import pytest

import jax

from llmq_tpu.core.config import SloConfig, default_config
from llmq_tpu.engine import ByteTokenizer, EchoExecutor, InferenceEngine
from llmq_tpu.engine.engine import GenRequest
from llmq_tpu.engine.kv_allocator import PageAllocator
from llmq_tpu.metrics.registry import REGISTRY
from llmq_tpu.observability.device import (DeviceTelemetry,
                                           UnknownDeviceError, decode_mfu,
                                           get_device_telemetry,
                                           measure_rtt, peak_flops)
from llmq_tpu.observability.slo import (SloTracker, configure_slo,
                                        get_slo_tracker, window_label)


def _echo_engine(name, *, chunk=4, metrics=True, batch=4):
    eng = InferenceEngine(
        EchoExecutor(batch_size=batch, chunk_size=chunk),
        ByteTokenizer(), name=name, enable_metrics=metrics)
    return eng


def _serve(eng, n=6, prompt="device telemetry", max_new=12):
    handles = [eng.submit(GenRequest(id=f"{eng.name}-{i}", prompt=prompt,
                                     max_new_tokens=max_new))
               for i in range(n)]
    eng.run_until_idle()
    assert all(h.result.finish_reason in ("eos", "length")
               for h in handles), [h.result for h in handles]
    return handles


# -- shared math (the bench dedup satellite) ----------------------------------

class TestSharedMath:
    def test_peak_flops_table(self):
        assert peak_flops("TPU v5e") == 197e12
        assert peak_flops("TPU v5p") == 459e12
        assert peak_flops("TPU v5 lite") == 197e12   # v5e's device_kind

    def test_unknown_device_has_no_peak(self):
        """A device the table does not list gets NO peak — never
        v5e's: an error wherever a chip number is produced, no figure
        in the live telemetry (CPU tests)."""
        from llmq_tpu.observability.device import (decode_hbm_bw_util,
                                                   peak_hbm_bandwidth)
        for kind in ("cpu", "unknown-device", ""):
            with pytest.raises(UnknownDeviceError):
                peak_flops(kind)
            with pytest.raises(UnknownDeviceError):
                peak_hbm_bandwidth(kind)
        with pytest.raises(UnknownDeviceError):
            decode_mfu(1000, 10**9, "cpu")
        with pytest.raises(UnknownDeviceError):
            decode_hbm_bw_util(6400, 64, 1, 1, 1, "cpu")
        t = DeviceTelemetry("unknown-peak", metrics=False)
        t.configure_model(n_params=10**9, device_kind="cpu",
                          platform="cpu", device_count=1)
        t.note_step(0.001, 0.001, 0.001, tokens=64)
        assert t.tokens_per_s() > 0
        assert t.mfu() is None
        snap = t.snapshot()
        assert snap["mfu_pct"] is None
        assert snap["model"]["platform"] == "cpu"
        assert snap["model"]["device_count"] == 1
        t.configure_model(n_params=10**9, device_kind="TPU v5 lite")
        assert t.mfu() > 0

    def test_int8_doubles_peak(self):
        assert peak_flops("TPU v5e", quant="int8") == 2 * 197e12

    def test_decode_mfu_formula(self):
        # 1000 tok/s on a 1B model: 2e12 FLOP/s of 197e12 peak.
        assert decode_mfu(1000, 10**9, "v5e") == pytest.approx(
            2e12 / 197e12)
        assert decode_mfu(0, 10**9, "v5e") == 0.0
        assert decode_mfu(1000, 0, "v5e") == 0.0   # echo: no params

    def test_measure_rtt_on_cpu(self):
        rtt = measure_rtt(samples=3)
        assert 0 < rtt < 5000

    def test_decode_hbm_bw_util_formula(self):
        from llmq_tpu.observability.device import (decode_hbm_bw_util,
                                                   peak_hbm_bandwidth)
        assert peak_hbm_bandwidth("TPU v5e") == 819e9
        # 64 rows at 6400 tok/s = 100 steps/s; 2 GB weights + 64 rows
        # × 100 KB/token × 512 tokens of live KV per step.
        got = decode_hbm_bw_util(6400, 64, 2 * 10**9, 100_000, 512,
                                 "v5e")
        want = 100 * (2 * 10**9 + 64 * 100_000 * 512) / 819e9
        assert got == pytest.approx(want)
        assert decode_hbm_bw_util(0, 64, 1, 1, 1, "v5e") == 0.0


# -- step decomposition through the serving path ------------------------------

class TestStepDecomposition:
    def test_echo_sync_path_populates_all_three_legs(self):
        eng = _echo_engine("dev-echo")
        _serve(eng)
        dev = eng.get_stats()["device"]
        steps = dev["steps"]
        assert steps["count"] > 0
        # Sync path: every leg observed once per chunk, device leg
        # carries the executor call.
        for leg in ("dispatch_ms", "device_ms", "readback_ms"):
            assert steps[leg]["count"] == steps["count"]
        assert steps["device_ms"]["total_ms"] > 0
        assert dev["tokens_total"] > 0
        assert dev["decode_tokens_per_s"] > 0
        # Echo has no params → MFU pins to 0 rather than lying.
        assert dev["mfu_pct"] == 0.0

    def test_step_histograms_exported_with_engine_label(self):
        eng = _echo_engine("dev-metrics")
        _serve(eng)
        from llmq_tpu.metrics.registry import exposition
        exp = exposition().decode()
        for fam in ("llm_queue_step_dispatch_ms_count",
                    "llm_queue_step_device_ms_count",
                    "llm_queue_step_readback_ms_count"):
            assert f'{fam}{{engine="dev-metrics"}}' in exp, fam
        assert REGISTRY.get_sample_value(
            "llm_queue_step_device_ms_count",
            {"engine": "dev-metrics"}) > 0
        # Scrape-time gauges refreshed by the exposition flush.
        assert REGISTRY.get_sample_value(
            "llm_queue_decode_tokens_per_s",
            {"engine": "dev-metrics"}) > 0

    def test_metrics_off_engine_still_tracks_host_side(self):
        # Bench engines run with enable_metrics=False yet read
        # per-rate-point device telemetry from get_stats.
        eng = _echo_engine("dev-nometrics", metrics=False)
        _serve(eng)
        dev = eng.get_stats()["device"]
        assert dev["steps"]["count"] > 0
        assert dev["tokens_total"] > 0

    def test_mixed_path_notes_steps(self):
        cfg = default_config()
        cfg.executor.decode_chunk = 4
        cfg.executor.mixed_batch.prefill_token_budget = 32
        from llmq_tpu.engine import build_engine
        eng = build_engine(cfg, name="dev-mixed", enable_metrics=False)
        _serve(eng, n=8, prompt="mixed telemetry " * 4)
        stats = eng.get_stats()
        assert stats["mixed_batch"]["steps"] > 0
        assert stats["device"]["steps"]["count"] > 0


# -- HBM accounting ------------------------------------------------------------

class TestHbmAccounting:
    def test_allocator_fragmentation(self):
        alloc = PageAllocator(17, 16)
        pages = alloc.alloc(12)
        assert alloc.fragmentation() == 0.0       # one contiguous run
        # Free every other page: the free space is maximally interleaved.
        alloc.free(pages[::2])
        assert alloc.fragmentation() > 0.4
        alloc.free(pages[1::2])
        assert alloc.fragmentation() == 0.0       # whole pool free again

    def test_engine_hbm_snapshot(self):
        eng = _echo_engine("dev-hbm")
        _serve(eng, n=2, prompt="hold pages",
               max_new=4)
        hbm = eng._hbm_snapshot()
        assert hbm["kv_pages_total"] > 0
        assert 0.0 <= hbm["kv_pool_occupancy"] <= 1.0
        assert 0.0 <= hbm["kv_pool_fragmentation"] <= 1.0
        assert "prefix_cache_pages" in hbm

    def test_occupancy_gauge_set_at_scrape(self):
        eng = _echo_engine("dev-hbm-gauge")
        _serve(eng, n=2)
        from llmq_tpu.metrics.registry import exposition
        exposition()
        val = REGISTRY.get_sample_value(
            "llm_queue_kv_pool_occupancy", {"engine": "dev-hbm-gauge"})
        assert val is not None and 0.0 <= val <= 1.0


# -- JAX executor: compile telemetry + per-chip HBM + pipelined split ---------

def _tiny_executor(name, **kw):
    from llmq_tpu.engine.executor import JaxExecutor
    from llmq_tpu.models.llama import init_params, llama3_tiny
    cfg = llama3_tiny(max_seq_len=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return JaxExecutor(cfg, params, batch_size=4, page_size=16,
                       num_pages=33, chunk_size=4,
                       prefill_buckets=[16, 32], eos_id=-1,
                       telemetry_name=name, **kw)


class TestJaxTelemetry:
    def test_warmup_compile_and_export_cache_telemetry(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("LLMQ_EXPORT_CACHE_DIR", str(tmp_path))
        ex = _tiny_executor("dev-jax-cold")
        ex.warmup()
        snap = get_device_telemetry("dev-jax-cold").snapshot()
        comp = snap["compile"]
        # Cold start: every program was a cache miss, each with a
        # recorded compile time; warmup progress completed.
        assert comp["cache_misses"] >= len(ex._aot) > 0
        assert comp["cache_hits"] == 0
        assert set(comp["programs"]) == set(ex._aot)
        assert all(p["seconds"] > 0 for p in comp["programs"].values())
        # ... with what XLA's own cache answered (off here:
        # tests/conftest.py disables it), apart from whether the
        # export artifact existed (``source``), and the executable's
        # serialized size — taken only on a hit, so none here.
        assert {(p["xla_cache"], p["executable_bytes"])
                for p in comp["programs"].values()} == {("off", None)}
        # One warm-up span a program, in the ring the engine takes over.
        spans = [sp for sp in ex.spans.snapshot()
                 if sp.name == "engine.warmup.compile"]
        assert sorted(sp.meta["program"] for sp in spans) == sorted(ex._aot)
        assert comp["warmup_done"] == comp["warmup_total"]
        assert snap["host_device_rtt_ms"] is not None
        # Model identity feeds the MFU estimator.
        assert snap["model"]["n_params"] > 0

        # Warm restart: the export cache serves every program — hits.
        ex2 = _tiny_executor("dev-jax-warm")
        ex2.warmup()
        comp2 = get_device_telemetry("dev-jax-warm").snapshot()["compile"]
        assert comp2["cache_hits"] > 0
        srcs = {p["source"] for p in comp2["programs"].values()}
        assert "export_cache" in srcs

    @pytest.mark.parametrize("events, want", [
        ((), "off"), (("asked",), "miss"), (("asked", "served"), "hit"),
        (("asked", "served", "asked"), "miss")])
    def test_xla_cache_outcome_is_per_thread(self, events, want):
        """``XLA_CACHE`` reads JAX's own monitoring events on the
        thread that compiled: the warm-up compiles a program a thread,
        so another thread's lookups must not leak into this one's."""
        import threading

        from jax import monitoring

        from llmq_tpu.observability.device import XLA_CACHE

        names = {"asked": XLA_CACHE._ASKED, "served": XLA_CACHE._SERVED}
        XLA_CACHE.watch()
        since = XLA_CACHE.mark()
        other = threading.Thread(target=lambda: [
            monitoring.record_event(n) for n in names.values()])
        other.start()
        other.join()
        for e in events:
            monitoring.record_event(names[e])
        assert XLA_CACHE.outcome(since) == want

    def test_stale_export_misses_another_slice_geometry(self, tmp_path,
                                                        monkeypatch):
        """The export-cache key includes the mixed slice geometry: a
        disk cache written at one slice width must MISS for an
        executor of another (``mixed_chunk`` has other shapes under the
        same name, so every program is lowered again, zero hits)."""
        monkeypatch.setenv("LLMQ_EXPORT_CACHE_DIR", str(tmp_path))
        ex_a = _tiny_executor("dev-jax-exp-s64")
        ex_a.warmup()
        ex_b = _tiny_executor("dev-jax-exp-s32", mixed_slice_tokens=32)
        assert ex_a._export_cache_key() != ex_b._export_cache_key()
        ex_b.warmup()
        comp = get_device_telemetry(
            "dev-jax-exp-s32").snapshot()["compile"]
        assert comp["cache_hits"] == 0
        assert not ex_b._from_export_cache

    def test_hbm_info_reports_resident_bytes(self):
        ex = _tiny_executor("dev-jax-hbm")
        chips = ex.hbm_info()
        assert len(chips) >= 1
        c0 = chips[0]
        assert c0["weights_bytes"] > 0
        assert c0["kv_pool_bytes"] > 0

    def test_pipelined_engine_splits_device_and_readback(self):
        ex = _tiny_executor("dev-jax-pipe")
        eng = InferenceEngine(ex, ByteTokenizer(), name="dev-jax-pipe",
                              max_decode_steps=6, enable_metrics=False)
        _serve(eng, n=3, prompt="ab", max_new=4)
        dev = eng.get_stats()["device"]
        steps = dev["steps"]
        assert steps["count"] > 0
        # Pipelined fetch records all three legs per chunk.
        assert steps["dispatch_ms"]["count"] == steps["count"]
        assert steps["device_ms"]["count"] == steps["count"]
        assert steps["readback_ms"]["count"] == steps["count"]


# -- SLO burn rates ------------------------------------------------------------

class TestSlo:
    def test_window_labels(self):
        assert window_label(300) == "5m"
        assert window_label(3600) == "1h"
        assert window_label(90) == "90s"

    def test_burn_rate_math(self):
        t = SloTracker(targets={"ttft": 100.0}, objective=0.99,
                       windows_s=(300.0,), metrics=False)
        for _ in range(98):
            t.observe("ttft", 50.0)
        for _ in range(2):
            t.observe("ttft", 500.0)
        rates = t.burn_rates()["ttft"]["5m"]
        # 2 % breaches against a 1 % budget → burn rate 2.0.
        assert rates["burn_rate"] == pytest.approx(2.0)
        assert rates["requests"] == 100 and rates["breaches"] == 2

    def test_zero_traffic_burns_nothing(self):
        t = SloTracker(targets={"ttft": 100.0}, metrics=False)
        assert t.burn_rates()["ttft"]["5m"]["burn_rate"] == 0.0

    def test_flush_sets_gauges(self):
        t = get_slo_tracker()
        configure_slo(SloConfig())
        t.observe("realtime", 10_000.0)    # one breach
        t.flush()
        v = REGISTRY.get_sample_value(
            "llm_queue_slo_burn_rate", {"slo": "realtime", "window": "5m"})
        assert v is not None and v > 0
        rem = REGISTRY.get_sample_value(
            "llm_queue_slo_error_budget_remaining", {"slo": "realtime"})
        assert rem is not None and 0.0 <= rem <= 1.0

    def test_recorder_feeds_slo_tracker(self):
        from llmq_tpu.observability.recorder import FlightRecorder
        configure_slo(SloConfig(ttft_p99_ms=50.0, realtime_p99_ms=50.0))
        tracker = get_slo_tracker()
        before = tracker.burn_rates()["ttft"]["5m"]["requests"]
        rec = FlightRecorder(capacity=16, emit_metrics=True)
        t0 = time.time()
        rec.record("slo-req-1", "enqueued", ts=t0, priority="realtime")
        rec.record("slo-req-1", "first_token", ts=t0 + 0.2)
        rec.record("slo-req-1", "completed", ts=t0 + 0.4,
                   completion_tokens=3)
        rec.flush_metrics()
        rates = tracker.burn_rates()
        assert rates["ttft"]["5m"]["requests"] > before
        # 200 ms TTFT and 400 ms e2e against 50 ms targets: breaches.
        assert rates["ttft"]["5m"]["breaches"] >= 1
        assert rates["realtime"]["5m"]["breaches"] >= 1

    def test_disabled_slo_config_clears_targets(self):
        tracker = configure_slo(SloConfig(enabled=False))
        assert tracker.targets == {}
        tracker.observe("ttft", 10.0)       # no-op, must not raise
        assert tracker.burn_rates() == {}
        configure_slo(SloConfig())          # restore for other tests

    def test_slo_force_disabled_when_trace_plane_off(self):
        # The tracker is FED by the recorder's flush: with the trace
        # plane off it would report 0 burn forever — configure() must
        # disable it visibly instead (no targets in snapshots).
        from llmq_tpu.core.config import ObservabilityConfig
        from llmq_tpu.observability.recorder import configure
        try:
            configure(ObservabilityConfig(enabled=False))
            assert get_slo_tracker().targets == {}
        finally:
            configure(ObservabilityConfig())    # restore
        assert get_slo_tracker().targets       # fed again


# -- cluster overview rollup ---------------------------------------------------

class TestClusterOverview:
    def test_local_rollup_aggregates_device_blocks(self):
        from llmq_tpu.cluster.router import ClusterRouter
        from llmq_tpu.core.config import ClusterConfig
        from llmq_tpu.loadbalancer.load_balancer import LoadBalancer
        eng = _echo_engine("dev-overview")
        _serve(eng, n=3)
        router = ClusterRouter(LoadBalancer(), config=ClusterConfig(),
                               enable_metrics=False)
        router.register_engine(eng)
        out = router.overview()
        assert out["aggregate"]["endpoints"] == 1
        assert out["aggregate"]["reporting"] == 1
        rep = out["replicas"][0]
        assert rep["device"]["steps"]["count"] > 0
        assert rep["engine"]["tokens_generated"] > 0

    def test_unreachable_remote_degrades_per_replica(self):
        from llmq_tpu.cluster.router import ClusterRouter
        from llmq_tpu.core.config import ClusterConfig
        from llmq_tpu.loadbalancer.load_balancer import LoadBalancer
        router = ClusterRouter(LoadBalancer(), config=ClusterConfig(),
                               enable_metrics=False)
        router.register_remote("http://127.0.0.1:1",   # nothing listens
                               endpoint_id="gone")
        out = router.overview()
        assert out["aggregate"]["reporting"] == 0
        assert "error" in out["replicas"][0]


# -- overhead guard (acceptance: instrumentation < 3 % of an echo step) --------

class TestOverheadGuard:
    def test_note_step_under_3pct_of_echo_request(self):
        """Deterministic decomposition, mirroring the PR-3 trace-plane
        guard: measure one echo request end-to-end through the engine,
        then the per-call cost of the full per-chunk instrumentation
        (3 perf_counter reads + note_step), and require
        chunks-per-request × per-call < 3 % of the request."""
        eng = _echo_engine("dev-overhead", chunk=1)
        n, max_new = 24, 16
        t0 = time.perf_counter()
        _serve(eng, n=n, max_new=max_new)
        per_request = (time.perf_counter() - t0) / n
        # Actual instrumented chunks per request (decode steps batch
        # across slots, so this is far below max_new).
        calls_per_request = eng.get_stats()["device"]["steps"]["count"] / n

        tel = DeviceTelemetry("dev-overhead-probe", metrics=True)
        # MIN over several batches: the guard measures the code's
        # cost, not the CI box's scheduler noise — a single batch
        # inflated by a contended core flaked this test once already.
        per_call = float("inf")
        for _ in range(5):
            m = 2000
            t0 = time.perf_counter()
            for _ in range(m):
                a = time.perf_counter()
                b = time.perf_counter()
                c = time.perf_counter()
                tel.note_step(b - a, c - b, 0.0, 1)
            per_call = min(per_call, (time.perf_counter() - t0) / m)
        cost = calls_per_request * per_call
        assert cost < 0.03 * per_request, (
            f"instrumentation {cost * 1e6:.1f}µs/request "
            f"({calls_per_request:.1f} chunks × {per_call * 1e6:.1f}µs) "
            f"vs request {per_request * 1e6:.1f}µs")
