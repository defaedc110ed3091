"""Token-budget mixed prefill+decode batching (docs/architecture.md
"Mixed step"): the fused scheduling path must be TOKEN-FOR-TOKEN
equivalent to the unfused one — across admission waves, preemption and
resume, and prefix-cache continuation prefill — while honoring the
prefill token budget per iteration and populating the stall
attribution metrics. ``mixed_batch.enabled: false`` is a hard
off-switch: the executor must never see a mixed dispatch."""

import jax
import jax.numpy as jnp
import pytest

from llmq_tpu.core.config import MixedBatchConfig, PrefixCacheConfig
from llmq_tpu.core.types import Priority
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import EchoExecutor, JaxExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import llama
from llmq_tpu.models.llama import get_config, init_params
from mixed_tight import (CASES, JOINED, check, check_served,  # noqa: F401
                         tight_step)


def mixed_cfg(enabled=True, budget=16, slices=2):
    return MixedBatchConfig(enabled=enabled, prefill_token_budget=budget,
                            max_slices=slices)


def make_echo_engine(mixed=None, slots=4, chunk=4, **kw):
    tok = ByteTokenizer()
    ex = EchoExecutor(batch_size=slots, page_size=8, num_pages=256,
                      max_pages_per_seq=16, eos_id=tok.eos_id,
                      chunk_size=chunk, mixed_prefill_slices=2,
                      mixed_slice_tokens=8)
    eng = InferenceEngine(ex, tok, enable_metrics=False,
                          max_decode_steps=64, mixed_batch=mixed, **kw)
    return eng, ex


WAVE = [
    ("hello world this is a long prompt " * 3, Priority.NORMAL),
    ("short", Priority.REALTIME),
    ("medium sized prompt here", Priority.LOW),
    ("another quite long prompt for slicing " * 2, Priority.HIGH),
    ("fifth request", Priority.NORMAL),
    ("sixth one goes last", Priority.LOW),
]


def drive_wave(eng, wave=WAVE, conv=None, steps_between=2,
               max_new=40):
    """Submit a wave with interleaved scheduling; returns handles."""
    handles = []
    for i, (prompt, prio) in enumerate(wave):
        handles.append(eng.submit(GenRequest(
            id=f"r{i}", prompt=prompt, priority=prio,
            conversation_id=(conv[i] if conv else ""),
            max_new_tokens=max_new)))
        for _ in range(steps_between):
            eng.step()
    eng.run_until_idle()
    return handles


class TestEchoEquivalence:
    def test_admission_wave_streams_identical(self):
        def run(mixed):
            eng, _ = make_echo_engine(mixed)
            handles = drive_wave(eng)
            return [h.result.tokens for h in handles], eng.get_stats()

        on, s_on = run(mixed_cfg())
        off, s_off = run(None)
        assert on == off
        # The fused path actually ran (long prompts + active decode
        # rows force mixed iterations) and the unfused path never
        # tracked mixed state.
        assert s_on["mixed_batch"]["steps"] > 0
        assert s_on["mixed_batch"]["prefill_tokens"] > 0
        assert "mixed_batch" not in s_off

    def test_preemption_equivalence_single_slot(self):
        """Preemption/resume (slot handoff + page-release rebuild)
        under mixed batching: per-request streams must not change."""
        def run(mixed):
            eng, _ = make_echo_engine(mixed, slots=1)
            low = eng.submit(GenRequest(
                id="low", prompt="background work " * 4,
                priority=Priority.LOW, max_new_tokens=48))
            for _ in range(6):
                eng.step()
            rt = eng.submit(GenRequest(
                id="rt", prompt="urgent realtime request",
                priority=Priority.REALTIME, max_new_tokens=8))
            eng.run_until_idle()
            return low.result.tokens, rt.result.tokens

        assert run(mixed_cfg()) == run(None)

    def test_conversation_continuation_equivalence(self):
        """Turn-2 continuation prefill over pinned conversation KV
        rides the mixed path identically."""
        def run(mixed):
            eng, _ = make_echo_engine(mixed)
            out = []
            for turn in range(3):
                handles = drive_wave(
                    eng,
                    wave=[(f"turn {turn} says something longish "
                           f"{'x' * (10 * turn)}", Priority.NORMAL)] * 3,
                    conv=[f"c{i}" for i in range(3)],
                    max_new=24)
                out.append([h.result.tokens for h in handles])
            return out

        assert run(mixed_cfg()) == run(None)

    def test_budget_honored_and_slices_capped(self):
        """Every mixed dispatch fuses ≤ prefill_token_budget tokens
        across ≤ max_slices slices, each ≤ the executor slice width."""
        eng, ex = make_echo_engine(mixed_cfg(budget=16, slices=2))
        seen = []
        orig = ex.mixed_chunk

        def spy(tokens, positions, block_tables, temps, budgets, pf):
            seen.append([(slot, len(t)) for slot, t, *_ in pf])
            return orig(tokens, positions, block_tables, temps,
                        budgets, pf)

        ex.mixed_chunk = spy
        drive_wave(eng)
        assert seen, "mixed path never dispatched"
        for pf in seen:
            assert 1 <= len(pf) <= 2
            assert sum(n for _, n in pf) <= 16
            assert all(n <= ex.mixed_slice_tokens for _, n in pf)

    def test_off_switch_no_mixed_calls(self):
        """enabled=false → the executor NEVER sees a mixed dispatch,
        even though it supports one (hard off-switch)."""
        eng, ex = make_echo_engine(mixed_cfg(enabled=False))

        def boom(*a, **kw):
            raise AssertionError("mixed dispatch with mixed_batch off")

        ex.mixed_chunk = boom
        handles = drive_wave(eng)
        assert all(h.result.finish_reason in ("eos", "length")
                   for h in handles)

    def test_cancellation_mid_prefill(self):
        """A cancelled mid-prefill sequence is reaped from the mixed
        path without leaking its slot or pages."""
        eng, ex = make_echo_engine(mixed_cfg())
        keep = eng.submit(GenRequest(id="keep", prompt="steady " * 10,
                                     max_new_tokens=32))
        for _ in range(4):
            eng.step()
        doomed = eng.submit(GenRequest(
            id="doomed", prompt="a very long prompt " * 8,
            priority=Priority.LOW, max_new_tokens=32))
        eng.step()
        doomed.cancel()
        eng.run_until_idle()
        assert doomed.result.finish_reason == "cancelled"
        assert keep.result.finish_reason in ("eos", "length")
        assert eng.allocator.used() == eng.allocator.pinned_pages()
        assert all(s is None for s in eng._slots)


class TestPrefillRateEstimator:
    def test_engine_learns_and_feeds_scheduler(self):
        from llmq_tpu.scheduling.resource_scheduler import (
            ResourceScheduler)

        sched = ResourceScheduler()
        eng, _ = make_echo_engine(mixed_cfg())
        eng.on_prefill_observed = sched.observe_prefill
        drive_wave(eng)
        assert eng.prefill_tps_ewma and eng.prefill_tps_ewma > 0
        stats = sched.get_stats()
        assert stats["prefill_observations"] > 0
        assert stats["prefill_tokens_per_s"] > 0
        eta = sched.prefill_eta_ms(100)
        assert eta is not None and eta >= 0
        # Stall attribution populated engine-side too.
        s = eng.get_stats()
        assert s["prefill_stall_events"] > 0
        assert s["prefill_stall_ms_total"] >= 0

    def test_prefill_eta_before_observations(self):
        from llmq_tpu.scheduling.resource_scheduler import (
            ResourceScheduler)

        sched = ResourceScheduler()
        assert sched.prefill_eta_ms(100) is None
        sched.observe_prefill(0, 1.0)          # ignored
        sched.observe_prefill(100, 0.0)        # ignored
        assert sched.get_stats()["prefill_observations"] == 0


class TestStallMetrics:
    def test_prefill_stall_histogram_populated(self):
        """With metrics ON, mixed iterations observe the
        llm_queue_prefill_stall_ms histogram and set the occupancy
        gauges (the CI smoke's assertion)."""
        from llmq_tpu.metrics.registry import exposition, get_metrics

        get_metrics()
        tok = ByteTokenizer()
        ex = EchoExecutor(batch_size=4, page_size=8, num_pages=256,
                          max_pages_per_seq=16, eos_id=tok.eos_id,
                          chunk_size=4, mixed_prefill_slices=2,
                          mixed_slice_tokens=8)
        eng = InferenceEngine(ex, tok, enable_metrics=True,
                              name="mixedtest", max_decode_steps=64,
                              mixed_batch=mixed_cfg())
        drive_wave(eng)
        exp = exposition().decode()
        assert "llm_queue_prefill_stall_ms" in exp
        assert ('llm_queue_prefill_stall_ms_count{engine="mixedtest",'
                'path="mixed"}') in exp
        assert "llm_queue_mixed_step_prefill_tokens" in exp
        assert "llm_queue_mixed_budget_utilization" in exp


# -- CPU-mode JAX equivalence --------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama3-tiny", max_seq_len=256, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_jax_engine(tiny_model, mixed, *, slots=3, prefix_cache=None,
                    max_decode_steps=16, cache_dtype=None):
    # Three slices of 8: three row tiles, so that the mixed step's
    # loop over the live ones (``ops/rows.worth_a_loop``) runs.
    cfg, params = tiny_model
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=slots, page_size=8,
                     num_pages=96, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id, chunk_size=4,
                     cache_dtype=cache_dtype,
                     mixed_prefill_slices=3, mixed_slice_tokens=8)
    assert ("k_scale" in ex.cache) == (cache_dtype is not None)
    return InferenceEngine(ex, tok, enable_metrics=False,
                           max_decode_steps=max_decode_steps,
                           prefix_cache=prefix_cache, mixed_batch=mixed)


@pytest.mark.parametrize("case", [*CASES, *JOINED])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_the_tight_mixed_step_computes_what_the_parts_do(tight_step, dtype,
                                                         case):
    """``llama.forward_mixed`` over tight slices and live tiles against
    ``forward_prefill`` + ``forward_decode`` over the same pool
    (``tests/mixed_tight.py``): in float32, so that nothing but the
    layout could tell them apart, and in bfloat16 as served (the
    slices' logits read 0.018-0.029 apart and K/V one or two bf16
    steps, 0.016-0.023; the decode rows' are bit for bit)."""
    cfg = get_config("llama3-tiny", max_seq_len=128, vocab_size=512,
                     dtype=dtype)
    params = init_params(jax.random.PRNGKey(0), cfg)
    if dtype == jnp.float32:
        check(tight_step, llama, cfg, params, case, page=8)
    else:
        check_served(tight_step, llama, cfg, params, case, page=8,
                     atol=6e-2, pages_atol=5e-2)


#: The KV pool's type: the model's own (bf16, the SmolLM2 cells) and
#: int8 with bf16 scale pools (the Mistral cell, whose prompts ride
#: mixed steps through the int8-KV prefill). Either way a prompt's K/V
#: is written to the pool before anything attends to it and every read
#: is of the pool, so a prompt cut into 8-token slices sees the values
#: a whole-bucket prefill sees: the streams are held token for token.
@pytest.mark.parametrize("cache_dtype", [None, jnp.int8],
                         ids=["bf16", "int8"])
class TestJaxEquivalence:
    def test_wave_with_preemption_streams_identical(self, tiny_model,
                                                    cache_dtype):
        """Greedy CPU-mode JAX: admission waves (slices spanning
        iterations) + a realtime arrival that preempts — identical
        per-request token streams with mixed batching on vs off."""
        def run(mixed):
            eng = make_jax_engine(tiny_model, mixed, slots=2,
                                  cache_dtype=cache_dtype)
            handles = []
            wave = [("a long prompt that needs slicing into chunks",
                     Priority.LOW),
                    ("second prompt arrives", Priority.NORMAL),
                    ("urgent!", Priority.REALTIME),
                    ("fourth one trails behind the others",
                     Priority.HIGH)]
            for i, (p, prio) in enumerate(wave):
                handles.append(eng.submit(GenRequest(
                    id=f"j{i}", prompt=p, priority=prio,
                    max_new_tokens=10)))
                eng.step()
                eng.step()
            eng.run_until_idle()
            return ([h.result.tokens for h in handles],
                    eng.get_stats())

        on, s_on = run(mixed_cfg(budget=24, slices=3))
        off, _ = run(None)
        assert s_on["mixed_batch"]["steps"] > 0, "fused path never ran"
        assert on == off

    def test_prefix_cache_continuation_equivalence(self, tiny_model,
                                                   cache_dtype):
        """Multi-turn conversations over the radix prefix cache:
        continuation prefill (cached KV + tail slices) must decode
        identically through the mixed path. (The prompts are ones on
        which no step of either stream is a near-tie: with random
        weights the first two logits of a step can lie 0.002 apart,
        an eighth of a bf16 step at their size, and the mixed step's
        looped products round a bf16 chain in another order than the
        prefill program's. What holds the two programs together in
        bf16, tie or not, is ``mixed_tight``'s bound on the logits.)"""
        def run(mixed):
            eng = make_jax_engine(
                tiny_model, mixed, cache_dtype=cache_dtype,
                prefix_cache=PrefixCacheConfig(enabled=True))
            out = []
            for turn in range(2):
                handles = []
                for c in range(3):
                    handles.append(eng.submit(GenRequest(
                        id=f"t{turn}c{c}",
                        prompt=f" turn {turn} in conversation {c}",
                        conversation_id=f"conv{c}",
                        max_new_tokens=8)))
                    eng.step()
                eng.run_until_idle()
                out.append([h.result.tokens for h in handles])
            # Reuse actually happened on turn 2.
            assert eng.prefix_hits > 0 or any(
                h.result.cached_tokens > 0 for h in handles)
            return out

        assert run(mixed_cfg(budget=24, slices=3)) == run(None)

    def test_multi_chunk_generation_through_mixed(self, tiny_model,
                                                  cache_dtype):
        """A generation spanning several chunks while later arrivals
        prefill through the fused program runs to full length."""
        eng = make_jax_engine(tiny_model, mixed_cfg(budget=24, slices=3),
                              max_decode_steps=24,
                              cache_dtype=cache_dtype)
        first = eng.submit(GenRequest(id="first", prompt="go",
                                      max_new_tokens=24))
        for _ in range(4):
            eng.step()
        later = eng.submit(GenRequest(
            id="later", prompt="a later long prompt to slice up",
            max_new_tokens=6))
        eng.run_until_idle()
        assert first.result.finish_reason in ("eos", "length")
        assert later.result.finish_reason in ("eos", "length")
        if first.result.finish_reason == "length":
            assert len(first.result.tokens) == 24
        assert eng.allocator.used() == eng.allocator.pinned_pages()
