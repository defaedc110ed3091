"""Speculation plane (llmq_tpu/speculation/, docs/performance.md
"Speculative decoding"): the n-gram drafter, the k-step verify window
with device-resident sampling, accept/rollback through the paged
allocator, and the equivalence contract — with speculation ON the
committed per-request streams are TOKEN-FOR-TOKEN identical to the
plane off, on echo and CPU-JAX engines, across mixed-batch configs,
prefix continuation, the 2-deep async pipeline, preemption and chaos
crash recovery under the invariant checker. The echo executor's
``verify_accept_cap`` seam drives the reject/EOS-mid-window state
machine deterministically without hardware; the KV rollback edges
(page-boundary reject, same-window page return, dp universes) are
pinned against the allocator; attribution through verify windows keeps
the usage-ledger and critical-path conservation invariants within 2 %.
``executor.speculation.enabled: false`` is a hard off-switch: no
drafter exists, no stats block appears, streams are byte-identical to
a pre-speculation engine."""

from __future__ import annotations

import numpy as np
import pytest

import jax

from llmq_tpu import chaos
from llmq_tpu.chaos import InvariantChecker
from llmq_tpu.core.config import (AsyncPipelineConfig, ChaosConfig,
                                  KVTieringConfig, MixedBatchConfig,
                                  PrefixCacheConfig, SpeculationConfig,
                                  SupervisorConfig)
from llmq_tpu.core.types import Priority
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import (EchoExecutor, JaxExecutor,
                                      verify_host_ncommit)
from llmq_tpu.engine.kv_allocator import PageAllocator
from llmq_tpu.engine.supervisor import EngineSupervisor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.llama import get_config, init_params
from llmq_tpu.speculation import NgramDrafter, propose_ngram

pytestmark = [pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")]


def spec_cfg(k=4, ngram=3, device_sampling=True):
    return SpeculationConfig(enabled=True, draft_k=k, ngram_max=ngram,
                             device_sampling=device_sampling)


def pipe_cfg(depth=2):
    return AsyncPipelineConfig(enabled=True, depth=depth,
                               completion_workers=1)


def make_echo_engine(spec=None, pipe=None, slots=4, chunk=4,
                     num_pages=256, name="spectest", metrics=False,
                     **kw):
    tok = ByteTokenizer()
    on = pipe is not None and pipe.enabled
    ex = EchoExecutor(batch_size=slots, page_size=8, num_pages=num_pages,
                      max_pages_per_seq=16, eos_id=tok.eos_id,
                      chunk_size=chunk, mixed_prefill_slices=2,
                      mixed_slice_tokens=8, async_chunks=on)
    eng = InferenceEngine(ex, tok, enable_metrics=metrics, name=name,
                          max_decode_steps=64, speculation=spec,
                          async_pipeline=pipe, **kw)
    return eng, ex


WAVE = [
    # Repetitive prompts: the echo stream replays them, so the n-gram
    # lookup has real structure to exploit (acceptance > 0).
    ("hello world hello world hello tokens " * 3, Priority.NORMAL),
    ("short", Priority.REALTIME),
    ("medium sized prompt here", Priority.LOW),
    ("another quite long prompt for slicing " * 2, Priority.HIGH),
    ("fifth request", Priority.NORMAL),
]


def drive_wave(eng, wave=WAVE, conv=None, max_new=40):
    handles = []
    for i, (prompt, prio) in enumerate(wave):
        handles.append(eng.submit(GenRequest(
            id=f"r{i}", prompt=prompt, priority=prio,
            conversation_id=(conv[i] if conv else ""),
            max_new_tokens=max_new)))
        eng.step()
        eng.step()
    eng.run_until_idle()
    return handles


# -- drafter unit behavior -----------------------------------------------------


class TestNgramDrafter:
    def test_repeating_context_proposes_continuation(self):
        ctx = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3]
        # Suffix 3-gram (1,2,3) last occurred at index 4 → followed by
        # 4, 1, 2, 3 — capped at k.
        assert propose_ngram(ctx, 4) == [4, 1, 2, 3]
        assert propose_ngram(ctx, 2) == [4, 1]

    def test_longest_suffix_match_wins(self):
        # 1-gram suffix (9) matches at index 1 (follow: 5); the 2-gram
        # (2, 9) matches at 3 (follow: 7) — the 2-gram must win even
        # though both exist.
        ctx = [8, 9, 5, 2, 9, 7, 1, 2, 9]
        assert propose_ngram(ctx, 1) == [7]

    def test_most_recent_occurrence_wins_within_a_length(self):
        ctx = [1, 2, 5, 0, 1, 2, 6, 0, 1, 2]
        assert propose_ngram(ctx, 1, ngram_max=2) == [6]

    def test_novel_context_proposes_nothing(self):
        assert propose_ngram([1, 2, 3, 4, 5], 4) == []
        assert propose_ngram([7], 4) == []
        assert propose_ngram([], 4) == []
        assert propose_ngram([1, 2, 1, 2], 0) == []

    def test_drafter_caps_at_draft_k_and_counts(self):
        d = NgramDrafter(draft_k=2, ngram_max=3)
        got = d.propose([1, 2, 3, 1, 2, 3, 1, 2, 3])
        assert len(got) <= 2
        assert d.windows_drafted == 1
        d.propose([9, 8, 7])
        assert d.windows_empty == 1

    def test_drafter_failure_degrades_to_empty(self):
        d = NgramDrafter(draft_k=4)
        assert d.propose(None) == []     # un-sliceable context
        assert d.windows_empty == 1


# -- the accept rule (host-side oracle shared by both accept modes) -----------


class TestVerifyAcceptRule:
    def test_all_accepted_commits_whole_window(self):
        out = np.array([[5, 6, 7, 8]], np.int32)
        drafts = np.array([[5, 6, 7]], np.int32)
        n = verify_host_ncommit(out, drafts, np.array([4]), eos=-1)
        assert n.tolist() == [4]

    def test_first_mismatch_freezes_with_correction_committed(self):
        out = np.array([[5, 9, 7, 8]], np.int32)
        drafts = np.array([[5, 6, 7]], np.int32)
        # Step 0 matches draft 5; step 1 samples 9 != draft 6 — the 9
        # IS the correction and commits, nothing after it does.
        n = verify_host_ncommit(out, drafts, np.array([4]), eos=-1)
        assert n.tolist() == [2]

    def test_eos_freezes_even_when_draft_agrees(self):
        out = np.array([[5, 0, 7, 8]], np.int32)
        drafts = np.array([[5, 0, 7]], np.int32)
        n = verify_host_ncommit(out, drafts, np.array([4]), eos=0)
        assert n.tolist() == [2]

    def test_undrafted_and_inactive_rows(self):
        out = np.array([[5, 6], [9, 9]], np.int32)
        drafts = np.array([[6], [9]], np.int32)
        n = verify_host_ncommit(out, drafts, np.array([1, 0]), eos=-1)
        assert n.tolist() == [1, 0]


# -- echo equivalence: every scheduling shape ---------------------------------


class TestEchoEquivalence:
    def run(self, spec, pipe=None, **kw):
        eng, _ = make_echo_engine(spec, pipe, **kw)
        handles = drive_wave(eng)
        stats = eng.get_stats()
        eng.stop()
        return [h.result.tokens for h in handles], stats

    def test_wave_streams_identical_and_cadence_broken(self):
        on, s_on = self.run(spec_cfg())
        off, s_off = self.run(None)
        assert on == off
        sp = s_on["speculation"]
        assert sp["tokens_accepted"] > 0
        assert sp["acceptance_rate"] > 0
        # The headline: more than one token committed per host fetch.
        assert sp["readback_cadence"] > 1.0
        assert "speculation" not in s_off

    def test_2_deep_pipeline_streams_identical(self):
        on, s_on = self.run(spec_cfg(), pipe_cfg(depth=2))
        off, _ = self.run(None, pipe_cfg(depth=2))
        plain, _ = self.run(None)
        assert on == off == plain
        assert s_on["speculation"]["readback_cadence"] > 1.0

    def test_mixed_batch_config_streams_identical(self):
        mixed = MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                 max_slices=2)
        on, s_on = self.run(spec_cfg(), mixed_batch=mixed)
        off, s_off = self.run(None, mixed_batch=mixed)
        assert on == off
        # Speculation forces the unfused path: the fused mixed program
        # never runs while the plane is on.
        assert s_on["mixed_batch"]["steps"] == 0
        assert s_off["mixed_batch"]["steps"] > 0

    def test_prefix_continuation_streams_identical(self):
        def run(spec):
            eng, _ = make_echo_engine(
                spec, prefix_cache=PrefixCacheConfig(enabled=True))
            out = []
            for turn in range(3):
                handles = drive_wave(
                    eng,
                    wave=[(f"turn {turn} repeats itself turn {turn} "
                           "repeats itself", Priority.NORMAL)] * 3,
                    conv=[f"c{i}" for i in range(3)], max_new=24)
                out.append([h.result.tokens for h in handles])
            hits = eng.prefix_hits
            eng.stop()
            return out, hits

        on, hits_on = run(spec_cfg())
        off, hits_off = run(None)
        assert on == off
        assert hits_on > 0 and hits_off > 0

    def test_preemption_equivalence_single_slot(self):
        def run(spec):
            eng, _ = make_echo_engine(spec, slots=1)
            low = eng.submit(GenRequest(
                id="low", prompt="background drone work " * 4,
                priority=Priority.LOW, max_new_tokens=48))
            for _ in range(6):
                eng.step()
            rt = eng.submit(GenRequest(
                id="rt", prompt="urgent realtime request",
                priority=Priority.REALTIME, max_new_tokens=8))
            eng.run_until_idle()
            eng.stop()
            return low.result.tokens, rt.result.tokens

        assert run(spec_cfg()) == run(None)

    def test_off_switch_is_a_pre_speculation_engine(self):
        eng_off, _ = make_echo_engine(SpeculationConfig(enabled=False))
        eng_none, _ = make_echo_engine(None)
        assert eng_off._drafter is None and eng_none._drafter is None
        assert not eng_off._spec_on
        out_off = [h.result.tokens for h in drive_wave(eng_off)]
        out_none = [h.result.tokens for h in drive_wave(eng_none)]
        assert out_off == out_none
        assert "speculation" not in eng_off.get_stats()
        assert eng_off.steps == eng_none.steps
        eng_off.stop()
        eng_none.stop()


class TestEchoChaosRecovery:
    @pytest.fixture(autouse=True)
    def _chaos_reset(self):
        yield
        chaos.configure(None)

    def test_crash_with_verify_window_in_flight(self):
        """Chaos ``engine.step`` crash with a verify chunk dispatched:
        the supervisor drops the snapshot, the streamed prefix stays
        monotone (no token from the dead window leaks), and a retry
        completes on the restarted engine — zero loss, zero dup."""
        inj = chaos.configure(ChaosConfig(enabled=True, seed=21))
        checker = InvariantChecker()
        eng, _ = make_echo_engine(spec_cfg(), pipe_cfg(depth=2),
                                  name="spec-chaos")
        h = eng.submit(GenRequest(id="s0",
                                  prompt="stream me through a crash " * 3,
                                  max_new_tokens=48),
                       on_token=checker.on_token("s0"))
        checker.submitted("s0")
        for _ in range(200):
            eng.step()
            if (eng._inflight
                    and len(checker._streams.get("s0", [])) >= 3):
                break
        assert eng._inflight
        inj.add_rule("engine.step", kind="crash", times=1)
        eng.start()
        import time as _t
        deadline = _t.time() + 5.0
        while eng.running and _t.time() < deadline:
            _t.sleep(0.01)
        assert not eng.running
        sup = EngineSupervisor(eng, config=SupervisorConfig(),
                               enable_metrics=False)
        assert sup.check_once()
        assert not eng._inflight
        assert h.wait(2.0)
        assert h.result.finish_reason == "error"
        checker.failed("s0")
        checker.completed("s0", tokens=h.result.tokens)
        checker._terminal["s0"].remove("completed")  # monotone check only
        h2 = eng.submit(GenRequest(id="s1",
                                   prompt="stream me through a crash " * 3,
                                   max_new_tokens=24),
                        on_token=checker.on_token("s1"))
        checker.submitted("s1")
        assert h2.wait(10.0)
        assert h2.result.finish_reason in ("eos", "length")
        eng._drain_completions()
        checker.completed("s1", tokens=h2.result.tokens)
        eng.stop()
        sup.stop()
        checker.check()
        assert eng.spec_tokens_accepted > 0


# -- the deterministic verify seam (satellite 2) ------------------------------


class TestAcceptCapSeam:
    def test_cap_zero_rejects_everything_stream_unchanged(self):
        eng, ex = make_echo_engine(spec_cfg())
        ex.verify_accept_cap = lambda slot, n_drafts: 0
        out = [h.result.tokens for h in drive_wave(eng)]
        sp = eng.get_stats()["speculation"]
        eng.stop()
        ctl, _ = make_echo_engine(None)
        ctl_out = [h.result.tokens for h in drive_wave(ctl)]
        ctl.stop()
        # Every draft rejected: the correction token IS the true next
        # token, so the stream is unchanged — but no draft ever lands.
        assert out == ctl_out
        assert sp["tokens_proposed"] > 0
        assert sp["tokens_accepted"] == 0
        assert sp["acceptance_rate"] == 0.0

    def test_cap_zero_cadence_collapses_to_one_per_row(self):
        """Single slot so the cadence is per-row: with every draft
        rejected each fetch carries exactly one committed token — the
        floor the plane exists to break, restored on demand."""
        eng, ex = make_echo_engine(spec_cfg(), slots=1)
        ex.verify_accept_cap = lambda slot, n_drafts: 0
        h = eng.submit(GenRequest(id="c0", prompt="cap cap cap cap cap",
                                  max_new_tokens=24))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        sp = eng.get_stats()["speculation"]
        eng.stop()
        assert sp["readback_cadence"] <= 1.0 + 1e-9

    def test_alternating_cap_changes_counts_not_streams(self):
        def run(cap):
            eng, ex = make_echo_engine(spec_cfg())
            ex.verify_accept_cap = cap
            out = [h.result.tokens for h in drive_wave(eng)]
            sp = eng.get_stats()["speculation"]
            eng.stop()
            return out, sp

        calls = {"n": 0}

        def alternating(slot, n_drafts):
            calls["n"] += 1
            return n_drafts if calls["n"] % 2 else 1

        full, sp_full = run(None)
        alt, sp_alt = run(alternating)
        assert alt == full
        assert 0 < sp_alt["tokens_accepted"] < sp_full["tokens_accepted"]
        # More rejections → more windows to finish the same streams.
        assert sp_alt["windows"] >= sp_full["windows"]

    def test_eos_inside_accepted_window(self):
        """A row whose echo stream ends mid-window: EOS rides the
        accepted run, the row finishes with reason "eos", trailing
        window steps never commit, and the pool drains to zero."""
        eng, _ = make_echo_engine(spec_cfg(k=8, ngram=2), chunk=16)
        h = eng.submit(GenRequest(id="e0",
                                  prompt="ab ab ab ab ab ab ab",
                                  max_new_tokens=64))
        eng.run_until_idle()
        assert h.result.finish_reason == "eos"
        sp = eng.get_stats()["speculation"]
        assert sp["tokens_accepted"] > 0
        ctl, _ = make_echo_engine(None, chunk=16)
        h2 = ctl.submit(GenRequest(id="e0", prompt="ab ab ab ab ab ab ab",
                                   max_new_tokens=64))
        ctl.run_until_idle()
        assert h.result.tokens == h2.result.tokens
        assert eng.allocator.used() == eng.allocator.pinned_pages()
        eng.stop()
        ctl.stop()


# -- KV rollback edges (satellite 3) ------------------------------------------


class TestKVRollback:
    def test_rejected_window_pages_return_to_pool(self):
        """cap=0 forces a rollback on every drafted window; pages
        allocated for the rejected tail (including page-boundary
        crossings) must come back — the pool never creeps and drains
        to exactly the pinned set at idle."""
        eng, ex = make_echo_engine(spec_cfg(k=6, ngram=2), chunk=8)
        ex.verify_accept_cap = lambda slot, n_drafts: 0
        freed = []
        orig_free = eng.allocator.free

        def spy_free(pages):
            freed.extend(pages)
            orig_free(pages)

        eng.allocator.free = spy_free
        handles = drive_wave(eng, wave=[
            ("xy xy xy xy xy xy xy xy xy xy", Priority.NORMAL)] * 3,
            max_new=48)
        assert all(h.result.finish_reason in ("eos", "length")
                   for h in handles)
        assert freed                      # rollbacks actually trimmed
        assert eng.allocator.used() == eng.allocator.pinned_pages()
        eng.stop()

    def test_reject_at_page_boundary_trims_exactly(self):
        """Windows sized past a page boundary with every draft
        rejected: after each reconcile the rows hold exactly
        pages_for(pos) pages — the boundary page allocated for the
        rejected tail is returned, not leaked and not double-freed."""
        eng, ex = make_echo_engine(spec_cfg(k=6, ngram=2), chunk=8)
        ex.verify_accept_cap = lambda slot, n_drafts: 0
        h = eng.submit(GenRequest(id="pb",
                                  prompt="qr qr qr qr qr qr qr qr",
                                  max_new_tokens=40))
        for _ in range(64):
            eng.step()
            for seq in eng._slots:
                if seq is not None and seq.prefilled:
                    want = PageAllocator.pages_for(seq.pos,
                                                   eng.spec.page_size)
                    assert len(seq.pages) == want, (seq.pos, seq.pages)
            if h.result is not None:
                break
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        assert eng.allocator.used() == eng.allocator.pinned_pages()
        eng.stop()

    def test_freed_window_page_returns_to_its_dp_universe(self):
        """The allocator resolves a freed page's universe from its id:
        a page grabbed from universe 1 for a verify window that gets
        rejected goes back to universe 1's free list — never leaking
        into universe 0 (where a batch row it can't serve would grab
        it)."""
        alloc = PageAllocator(32, 8, dp_shards=2)
        before = alloc.available_by_shard()
        window = alloc.alloc(3, shard=1)
        assert window and all(alloc.shard_of(p) == 1 for p in window)
        assert alloc.available_by_shard()[1] == before[1] - 3
        alloc.free(window)                # the _spec_trim path
        assert alloc.available_by_shard() == before

    def test_speculation_with_tiering_demotion(self):
        """Speculation × kv_tiering: multi-turn conversations whose
        pins demote to the host tier between turns decode identically
        with the plane on, and the demoted blobs round-trip."""
        from llmq_tpu.core.clock import FakeClock

        def run(spec):
            clock = FakeClock()
            eng, _ = make_echo_engine(
                spec, name="spec-tier", kv_pin_ttl=5.0, clock=clock,
                kv_tiering=KVTieringConfig(enabled=True),
                prefix_cache=PrefixCacheConfig(enabled=True))
            out = []
            for turn in range(3):
                handles = drive_wave(
                    eng,
                    wave=[(f"tier turn {turn} tier turn {turn}",
                           Priority.NORMAL)] * 2,
                    conv=["cv0", "cv1"], max_new=16)
                out.append([h.result.tokens for h in handles])
                clock.advance(6.0)        # TTL reclaim → demote
                eng.step()
            stats = eng.get_stats()
            eng.stop()
            return out, stats

        on, s_on = run(spec_cfg())
        off, s_off = run(None)
        assert on == off
        assert s_on["kv_tiering"]["demotions"] > 0
        assert s_on["speculation"]["tokens_accepted"] > 0


# -- attribution conservation (satellite 1) -----------------------------------


class TestAttributionConservation:
    @pytest.fixture(autouse=True)
    def _ledger(self):
        from llmq_tpu.observability.usage import (get_usage_ledger,
                                                  reset_usage)
        reset_usage()
        get_usage_ledger().reconfigure(enabled=True, max_tenants=64)
        yield
        reset_usage()

    def test_usage_conserved_with_multi_token_commits(self):
        from llmq_tpu.observability.usage import get_usage_ledger
        led = get_usage_ledger()
        eng, _ = make_echo_engine(spec_cfg(), name="spec-usage")
        hs = [eng.submit(GenRequest(
                  id=f"u{i}", prompt="usage usage usage usage " * 2,
                  max_new_tokens=24, tenant_id=f"tenant-{i % 2}"))
              for i in range(8)]
        eng.run_until_idle()
        assert all(h.result.finish_reason in ("eos", "length")
                   for h in hs)
        # The windows genuinely carried k > 1 commits — the weighting
        # under test is the accepted-count share, not plain budgets.
        assert eng.spec_tokens_accepted > 0
        assert eng.spec_commits_total > eng.spec_windows
        measured = eng._telemetry._device.total_ms / 1e3
        accounted = led.attributed_device_s + led.unattributed_device_s
        assert measured > 0
        assert accounted == pytest.approx(measured, rel=0.02)
        eng.stop()

    def test_critical_path_segments_conserve(self):
        from llmq_tpu.observability.critical_path import get_critical_path
        from llmq_tpu.observability.recorder import get_recorder
        rec = get_recorder()
        rec.flush_metrics()
        ana = get_critical_path()
        ana.clear()
        ana.reconfigure(enabled=True, recent_capacity=256)
        try:
            eng, _ = make_echo_engine(spec_cfg(), name="spec-cp")
            hs = [eng.submit(GenRequest(
                      id=f"spec-cp{i}", prompt="conserve conserve conserve ",
                      max_new_tokens=24))
                  for i in range(6)]
            eng.run_until_idle()
            assert all(h.result.finish_reason in ("eos", "length")
                       for h in hs)
            assert eng.spec_tokens_accepted > 0
            eng.stop()
            rec.flush_metrics()
            snap = ana.snapshot(recent=256)
            assert snap["requests"] >= 6
            assert snap["conservation_failures"] == 0
            for r in snap["recent"]:
                seg_sum = sum(r["segments_ms"].values())
                tol = max(0.02 * r["total_ms"], 0.06)
                assert abs(seg_sum - r["total_ms"]) <= tol, r
        finally:
            rec.flush_metrics()
            ana.clear()

# -- metrics families (tentpole: observability contract) ----------------------


class TestSpecMetrics:
    def test_families_exported_with_engine_label(self):
        from llmq_tpu.metrics.registry import REGISTRY, exposition
        eng, _ = make_echo_engine(spec_cfg(), name="spec-metrics",
                                  metrics=True)
        drive_wave(eng)
        eng.stop()
        exp = exposition().decode()
        for fam in ("llm_queue_spec_acceptance_rate_count",
                    "llm_queue_spec_tokens_proposed_total",
                    "llm_queue_spec_tokens_accepted_total",
                    "llm_queue_spec_readback_cadence"):
            assert f'{fam}{{engine="spec-metrics"}}' in exp, fam
        assert REGISTRY.get_sample_value(
            "llm_queue_spec_tokens_proposed_total",
            {"engine": "spec-metrics"}) > 0
        assert REGISTRY.get_sample_value(
            "llm_queue_spec_acceptance_rate_count",
            {"engine": "spec-metrics"}) > 0
        cadence = REGISTRY.get_sample_value(
            "llm_queue_spec_readback_cadence",
            {"engine": "spec-metrics"})
        assert cadence is not None and cadence > 1.0

    def test_device_snapshot_carries_speculation_block(self):
        eng, _ = make_echo_engine(spec_cfg(), name="spec-snap")
        drive_wave(eng)
        dev = eng.get_stats()["device"]
        eng.stop()
        sp = dev.get("speculation")
        assert sp is not None
        assert sp["proposed"] > 0
        assert 0.0 < sp["acceptance_rate"] <= 1.0
        assert sp["readback_cadence"] > 1.0


# -- CPU-mode JAX: the real verify programs -----------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama3-tiny", max_seq_len=256, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_jax_engine(tiny_model, spec, *, device_sampling=True, pipe=None,
                    slots=2, max_decode_steps=16):
    cfg, params = tiny_model
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=slots, page_size=8,
                     num_pages=96, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id, chunk_size=4,
                     speculation_draft_k=(spec.draft_k if spec else 0),
                     speculation_device_sampling=device_sampling)
    return InferenceEngine(ex, tok, enable_metrics=False,
                           max_decode_steps=max_decode_steps,
                           speculation=spec, async_pipeline=pipe)


JWAVE = [
    ("a long prompt that needs slicing into chunks", Priority.LOW),
    ("second prompt arrives", Priority.NORMAL),
    ("urgent!", Priority.REALTIME),
]


def drive_jax(eng, temps=None, max_new=12):
    handles = []
    for i, (p, prio) in enumerate(JWAVE):
        handles.append(eng.submit(GenRequest(
            id=f"j{i}", prompt=p, priority=prio, max_new_tokens=max_new,
            temperature=(temps[i] if temps else 0.0))))
        eng.step()
        eng.step()
    eng.run_until_idle()
    out = [h.result.tokens for h in handles]
    stats = eng.get_stats()
    eng.stop()
    return out, stats


class TestJaxEquivalence:
    def test_greedy_streams_identical_both_accept_modes(self, tiny_model):
        """Greedy CPU-mode JAX with admission waves and a realtime
        preemption: device-accept AND host-accept verify programs
        commit byte-identical streams to the plane being off — the
        teacher-forced decode-shaped construction, end to end."""
        off, s_off = drive_jax(make_jax_engine(tiny_model, None))
        dev, s_dev = drive_jax(
            make_jax_engine(tiny_model, spec_cfg(k=3)))
        host, s_host = drive_jax(
            make_jax_engine(tiny_model, spec_cfg(k=3),
                            device_sampling=False))
        assert dev == off
        assert host == off
        assert "speculation" not in s_off
        assert (s_dev["speculation"]["windows"]
                == s_host["speculation"]["windows"])
        assert (s_dev["speculation"]["tokens_committed"]
                == s_host["speculation"]["tokens_committed"])

    def test_pipelined_spec_streams_identical(self, tiny_model):
        on, s_on = drive_jax(
            make_jax_engine(tiny_model, spec_cfg(k=3), pipe=pipe_cfg()))
        off, _ = drive_jax(make_jax_engine(tiny_model, None))
        assert on == off
        assert s_on["speculation"]["fetches"] > 0

    def test_temperature_modes_agree(self, tiny_model):
        """Seeded temperature sampling: the committed stream is a
        function of (row, absolute position, prefix) via the fixed
        position-keyed base key — the device-accept and host-accept
        programs draw identical streams."""
        temps = [0.8, 0.9, 0.7]
        dev, _ = drive_jax(
            make_jax_engine(tiny_model, spec_cfg(k=3)), temps=temps)
        host, _ = drive_jax(
            make_jax_engine(tiny_model, spec_cfg(k=3),
                            device_sampling=False), temps=temps)
        assert dev == host


class TestJaxKVIntegrity:
    def test_rollback_leaves_committed_kv_bitwise_intact(self, tiny_model):
        """Executor-seam rollback probe (``paged_pool_window``): drive
        a slot with verify windows whose drafts are GARBAGE (every
        window rejects at step 0 and rolls back; host-accept mode even
        writes the stale tail), re-dispatching each next window from
        the committed position — then read the committed KV region out
        of the pool. It must be bitwise identical to a control executor
        that decoded sequentially, and the committed tokens must match
        the control's samples."""
        from llmq_tpu.ops.attention import paged_pool_window
        cfg, params = tiny_model
        K = 3
        B = 1
        prompt = [11, 12, 13, 14, 15, 16, 17, 18]

        def mk(draft_k, device_sampling=False):
            return JaxExecutor(cfg, params, batch_size=B, page_size=8,
                               num_pages=16, prefill_buckets=[16],
                               eos_id=-1, chunk_size=1,
                               speculation_draft_k=draft_k,
                               speculation_device_sampling=device_sampling)

        bt = np.zeros(8, np.int32)
        bt[:4] = [1, 2, 3, 4]            # 32 token positions backed

        # Control: sequential single-step decode. ``pos`` is the write
        # position of the pending token (the engine's seq.pos): prefill
        # wrote [0, len(prompt)), the sample lands at len(prompt).
        ctl = mk(0)
        tok = ctl.prefill(prompt, 0, bt, 0.0, 0)
        ctl_stream = []
        pos = len(prompt)
        for _ in range(8):
            nxt = ctl.decode(np.array([tok], np.int32),
                             np.array([pos], np.int32), bt[None, :],
                             np.zeros(1, np.float32))
            tok = int(np.asarray(nxt)[0])
            ctl_stream.append(tok)
            pos += 1

        # Speculated: garbage drafts, every window rejected at step 0
        # (ncommit == 1) — the stale tail written past the commit point
        # must never contaminate what later windows read.
        ex = mk(K, device_sampling=False)
        tok = ex.prefill(prompt, 0, bt, 0.0, 0)
        spec_stream = []
        pos = len(prompt)
        while len(spec_stream) < 8:
            drafts = np.full((B, K), 500, np.int32)   # never sampled
            out, ncommit = ex.verify_chunk(
                np.array([tok], np.int32), np.array([pos], np.int32),
                bt[None, :], np.zeros(1, np.float32), drafts,
                np.full(B, K + 1, np.int32))
            out = np.asarray(out)
            n = int(np.asarray(ncommit)[0])
            assert n == 1                 # garbage rejects immediately
            spec_stream.extend(int(t) for t in out[0, :n])
            tok = int(out[0, n - 1])
            pos += n
        assert spec_stream[:8] == ctl_stream

        # The committed KV region [0, pos) is bitwise what sequential
        # decode wrote — rollback re-writes repaired every stale
        # position. (The stale tail past ``pos`` is deliberately NOT
        # probed: it is exactly the region seq_lens masking guards.)
        end = len(prompt) + 8
        for pool in ("k", "v"):
            got = np.asarray(paged_pool_window(
                ex.cache[pool], jax.numpy.asarray(bt), 0, end))
            want = np.asarray(paged_pool_window(
                ctl.cache[pool], jax.numpy.asarray(bt), 0, end))
            np.testing.assert_array_equal(got, want)
