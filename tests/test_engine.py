"""Inference engine tests: allocator, tokenizer, echo end-to-end,
JAX-executor correctness, conversation KV reuse, preemption, pool
pressure, and the Worker process_fn seam.

The engine replaces the reference's simulated LLM processing
(cmd/queue-manager/main.go:139-153) behind the ProcessFunc seam
(worker.go:33); these tests are the evidence the seam is actually filled."""

import threading

import numpy as np
import pytest

from mixed_tight import tight_step  # noqa: F401 — a fixture

from llmq_tpu.core.clock import FakeClock
from llmq_tpu.core.types import Message, MessageStatus, Priority
from llmq_tpu.engine import (
    ByteTokenizer,
    EchoExecutor,
    GenRequest,
    InferenceEngine,
    JaxExecutor,
    PageAllocator,
)


# -- tokenizer ----------------------------------------------------------------

class TestByteTokenizer:
    def test_roundtrip(self):
        t = ByteTokenizer()
        for text in ("hello", "héllo wörld", "日本語", ""):
            assert t.decode(t.encode(text)) == text

    def test_ids_above_specials(self):
        t = ByteTokenizer()
        ids = t.encode("abc")
        assert all(i >= 3 for i in ids)
        assert t.vocab_size == 259


# -- allocator ----------------------------------------------------------------

class TestPageAllocator:
    def test_reserves_page_zero(self):
        a = PageAllocator(8, 16)
        got = set()
        while True:
            p = a.alloc(1)
            if p is None:
                break
            got.update(p)
        assert 0 not in got
        assert got == set(range(1, 8))

    def test_all_or_nothing(self):
        a = PageAllocator(5, 16)
        assert a.alloc(10) is None
        assert a.available() == 4  # nothing leaked
        pages = a.alloc(4)
        assert len(pages) == 4
        a.free(pages)
        assert a.available() == 4

    def test_pin_accounting(self):
        a = PageAllocator(8, 16)
        pages = a.alloc(3)
        a.pin("conv1", pages)
        assert a.pinned_pages() == 3
        back = a.unpin("conv1")
        assert back == pages
        assert a.pinned_pages() == 0

    def test_pages_for(self):
        assert PageAllocator.pages_for(1, 16) == 1
        assert PageAllocator.pages_for(16, 16) == 1
        assert PageAllocator.pages_for(17, 16) == 2


# -- echo engine --------------------------------------------------------------

def make_echo_engine(slots=4, num_pages=64, page_size=8, max_pages=16,
                     clock=None, **kw):
    tok = ByteTokenizer()
    ex = EchoExecutor(batch_size=slots, page_size=page_size,
                      num_pages=num_pages, max_pages_per_seq=max_pages,
                      eos_id=tok.eos_id)
    return InferenceEngine(ex, tok, enable_metrics=False, clock=clock, **kw)


class TestEchoEngine:
    def test_single_request_echoes(self):
        eng = make_echo_engine()
        h = eng.submit(GenRequest(id="r1", prompt="hello"))
        eng.run_until_idle()
        assert h.done
        assert h.result.text == "hello"
        assert h.result.finish_reason == "eos"
        assert h.result.prompt_tokens == 5
        # All pages returned to the pool.
        assert eng.allocator.used() == 0

    def test_batched_requests(self):
        eng = make_echo_engine(slots=4)
        prompts = [f"message-{i}" for i in range(10)]
        handles = [eng.submit(GenRequest(id=f"r{i}", prompt=p))
                   for i, p in enumerate(prompts)]
        eng.run_until_idle()
        for h, p in zip(handles, prompts):
            assert h.result.text == p
        assert eng.allocator.used() == 0

    def test_max_new_tokens_truncates(self):
        eng = make_echo_engine()
        h = eng.submit(GenRequest(id="r1", prompt="abcdefgh",
                                  max_new_tokens=3))
        eng.run_until_idle()
        assert h.result.text == "abc"
        assert h.result.finish_reason == "length"

    def test_cancellation(self):
        eng = make_echo_engine(slots=1)
        h1 = eng.submit(GenRequest(id="r1", prompt="x" * 50))
        h2 = eng.submit(GenRequest(id="r2", prompt="y" * 50))
        h2.cancel()
        eng.run_until_idle()
        assert h1.result.finish_reason == "eos"
        assert h2.result.finish_reason == "cancelled"

    def test_priority_order_single_slot(self):
        eng = make_echo_engine(slots=1)
        finish_order = []
        hs = {}
        for name, prio in (("low", Priority.LOW), ("rt", Priority.REALTIME),
                           ("norm", Priority.NORMAL)):
            hs[name] = eng.submit(GenRequest(id=name, prompt="zz",
                                             priority=prio))
        # Nothing admitted yet; first step admits in priority order.
        for _ in range(100):
            eng.step()
            for name, h in hs.items():
                if h.done and name not in finish_order:
                    finish_order.append(name)
            if len(finish_order) == 3:
                break
        assert finish_order == ["rt", "norm", "low"]


class TestPreemption:
    def test_realtime_preempts_low(self):
        eng = make_echo_engine(slots=1)
        hlow = eng.submit(GenRequest(id="low", prompt="L" * 40,
                                     priority=Priority.LOW))
        eng.step()  # admit low, first decode
        assert not hlow.done
        hrt = eng.submit(GenRequest(id="rt", prompt="R" * 4,
                                    priority=Priority.REALTIME))
        eng.run_until_idle()
        assert hrt.result.text == "R" * 4
        assert hlow.result.text == "L" * 40  # resumed and completed intact
        assert hrt.result.finish_reason == "eos"

    def test_no_preemption_when_disabled(self):
        eng = make_echo_engine(slots=1, preemption=False)
        hlow = eng.submit(GenRequest(id="low", prompt="L" * 40,
                                     priority=Priority.LOW))
        eng.step()
        eng.submit(GenRequest(id="rt", prompt="R" * 4,
                              priority=Priority.REALTIME))
        # Low finishes first because it cannot be displaced.
        for _ in range(200):
            eng.step()
            if hlow.done:
                break
        assert hlow.done

    def test_equal_priority_never_preempts(self):
        eng = make_echo_engine(slots=1)
        h1 = eng.submit(GenRequest(id="a", prompt="A" * 30,
                                   priority=Priority.HIGH))
        eng.step()
        h2 = eng.submit(GenRequest(id="b", prompt="B" * 5,
                                   priority=Priority.HIGH))
        for _ in range(200):
            eng.step()
            if h1.done and h2.done:
                break
        # FIFO within tier: a (earlier) completed before b started late.
        assert h1.done and h2.done


class TestConversationKV:
    def test_second_turn_reuses_cache(self):
        eng = make_echo_engine()
        h1 = eng.submit(GenRequest(id="t1", prompt="first turn",
                                   conversation_id="c1"))
        eng.run_until_idle()
        assert h1.result.cached_tokens == 0
        used_after_t1 = eng.allocator.used()
        assert used_after_t1 > 0  # pages stay pinned for the conversation
        assert eng.cached_conversations() == ["c1"]

        h2 = eng.submit(GenRequest(id="t2", prompt="second",
                                   conversation_id="c1"))
        eng.run_until_idle()
        assert h2.result.cached_tokens == len("first turn") + len("first turn")
        # turn-1 prompt + its echoed output are in the cache
        assert h2.result.text == "second"

    def test_conversation_eviction_frees_pages(self):
        eng = make_echo_engine()
        eng.submit(GenRequest(id="t1", prompt="hello", conversation_id="c1"))
        eng.run_until_idle()
        assert eng.allocator.used() > 0
        eng.drop_conversation("c1")
        assert eng.allocator.used() == 0
        assert eng.cached_conversations() == []

    def test_pin_ttl_expiry(self):
        clock = FakeClock()
        eng = make_echo_engine(clock=clock, kv_pin_ttl=10.0)
        eng.submit(GenRequest(id="t1", prompt="hello", conversation_id="c1"))
        eng.run_until_idle()
        assert eng.cached_conversations() == ["c1"]
        clock.advance(11.0)
        eng.step()
        assert eng.cached_conversations() == []
        assert eng.allocator.used() == 0

    def test_touch_refreshes_ttl(self):
        clock = FakeClock()
        eng = make_echo_engine(clock=clock, kv_pin_ttl=10.0)
        eng.submit(GenRequest(id="t1", prompt="hello", conversation_id="c1"))
        eng.run_until_idle()
        clock.advance(8.0)
        eng.touch_conversation("c1")
        clock.advance(8.0)
        eng.step()
        assert eng.cached_conversations() == ["c1"]  # touch reset the clock

    def test_overdue_low_beats_fresh_normal(self):
        """SLA-aware promotion: a LOW request older than
        its tier's max_wait_time is promoted and admitted ahead of a
        NORMAL request that arrived later — without promotion, strict
        (priority, arrival) order would admit the normal first."""
        clock = FakeClock()
        eng = make_echo_engine(
            slots=1, clock=clock,
            tier_max_wait={Priority.LOW: 5.0})
        # Occupy the single slot so both contenders queue.
        blocker = eng.submit(GenRequest(id="block", prompt="x" * 40,
                                        priority=Priority.REALTIME))
        eng.step()
        assert blocker.done is False
        low = eng.submit(GenRequest(id="low", prompt="lo",
                                    priority=Priority.LOW))
        eng.step()             # low is pending, slot busy
        clock.advance(6.0)     # past LOW's max_wait → one-tier promotion
        normal = eng.submit(GenRequest(id="norm", prompt="no",
                                       priority=Priority.NORMAL))
        eng.run_until_idle()
        assert low.done and normal.done
        # Promoted low (effective NORMAL, earlier arrival) finished
        # before the fresh normal.
        assert low.finished_at < normal.finished_at

    def test_no_promotion_without_max_wait(self):
        """Same scenario, no tier_max_wait: strict priority order — the
        fresh normal beats the older low."""
        clock = FakeClock()
        eng = make_echo_engine(slots=1, clock=clock)
        blocker = eng.submit(GenRequest(id="block", prompt="x" * 40,
                                        priority=Priority.REALTIME))
        eng.step()
        low = eng.submit(GenRequest(id="low", prompt="lo",
                                    priority=Priority.LOW))
        eng.step()
        clock.advance(6.0)
        normal = eng.submit(GenRequest(id="norm", prompt="no",
                                       priority=Priority.NORMAL))
        eng.run_until_idle()
        assert normal.finished_at < low.finished_at
        del blocker

    def test_urgent_conv_turn_behind_preempted_holder_no_deadlock(self):
        """A conversation's turn 2 (urgent) must not deadlock admission
        when turn 1's sequence was preempted and sits BEHIND it in the
        pending queue (found by the randomized soak: the old
        head-of-line break left every slot idle forever)."""
        eng = make_echo_engine(slots=1)
        t1 = eng.submit(GenRequest(id="t1", prompt="turn one " + "x" * 30,
                                   priority=Priority.NORMAL,
                                   conversation_id="cc"))
        eng.step()                       # t1 admitted, starts prefill
        # A realtime non-conv request preempts t1 mid-generation.
        rt = eng.submit(GenRequest(id="rt", prompt="urgent",
                                   priority=Priority.REALTIME))
        # Turn 2 arrives REALTIME: more urgent than the preempted t1,
        # but must wait for it (turn order) without blocking the world.
        t2 = eng.submit(GenRequest(id="t2", prompt="turn two",
                                   priority=Priority.REALTIME,
                                   conversation_id="cc"))
        eng.run_until_idle()
        for h in (t1, rt, t2):
            assert h.done and h.result.finish_reason == "eos"
        # Turn order respected: t2 finished after t1.
        assert t2.finished_at > t1.finished_at
        assert t2.result.cached_tokens > 0   # and reused t1's KV

    def test_blocked_conv_turn_reserves_capacity_no_preemption(self):
        """preemption=False: a blocked urgent conversation turn must
        still RESERVE capacity — less urgent non-conversation work can't
        fill the slots in front of it (it would then wait out full LOW
        generations with no preemption to rescue it)."""
        eng = make_echo_engine(slots=2, preemption=False)
        t1 = eng.submit(GenRequest(id="t1", prompt="turn one " + "x" * 40,
                                   priority=Priority.NORMAL,
                                   conversation_id="cc"))
        eng.step()                      # t1 seated (slot 0)
        t2 = eng.submit(GenRequest(id="t2", prompt="turn two",
                                   priority=Priority.REALTIME,
                                   conversation_id="cc"))
        lows = [eng.submit(GenRequest(id=f"lo{i}", prompt="bg " + "y" * 50,
                                      priority=Priority.LOW))
                for i in range(3)]
        eng.run_until_idle()
        assert all(h.done for h in (t1, t2, *lows))
        # t2 ran before at least the later LOW requests: with 2 slots,
        # one LOW may ride alongside t1, but the reserved slot goes to
        # t2 the moment t1 finishes — t2 must beat the last low.
        assert t2.finished_at < max(lo.finished_at for lo in lows)

    def test_pool_pressure_evicts_lru_conversation(self):
        # 23 usable pages of 8 tokens; each conversation pins 8 pages
        # (30 prompt + 30 echo + 1), so the 16-page "big" request must
        # reclaim the LRU conversation (ca) to finish.
        eng = make_echo_engine(num_pages=24, page_size=8, max_pages=16)
        eng.submit(GenRequest(id="a", prompt="a" * 30, conversation_id="ca"))
        eng.run_until_idle()
        eng.submit(GenRequest(id="b", prompt="b" * 30, conversation_id="cb"))
        eng.run_until_idle()
        assert set(eng.cached_conversations()) == {"ca", "cb"}
        # A big non-conversation request forces LRU eviction of ca.
        h = eng.submit(GenRequest(id="big", prompt="x" * 60))
        eng.run_until_idle()
        assert h.result.text == "x" * 60
        assert "ca" not in eng.cached_conversations()

    def test_concurrent_same_conversation_serialised(self):
        eng = make_echo_engine(slots=4)
        h1 = eng.submit(GenRequest(id="t1", prompt="one", conversation_id="c"))
        h2 = eng.submit(GenRequest(id="t2", prompt="two", conversation_id="c"))
        eng.run_until_idle()
        assert h1.result.finish_reason == "eos"
        assert h2.result.finish_reason == "eos"
        # Turn 2 saw turn 1's cache (its tokens + echo).
        assert h2.result.cached_tokens == 2 * len("one")


class TestEngineThread:
    def test_background_loop_and_generate(self):
        eng = make_echo_engine()
        eng.start()
        try:
            res = eng.generate("threaded", timeout=10.0)
            assert res.text == "threaded"
        finally:
            eng.stop()
        assert not eng.running

    def test_process_fn_seam(self):
        """Worker drains the queue into the engine — the reference's
        ProcessFunc seam (worker.go:33) filled by real execution."""
        from llmq_tpu.queueing.queue_manager import QueueManager
        from llmq_tpu.queueing.worker import Worker

        eng = make_echo_engine()
        eng.start()
        qm = QueueManager("engine-test", enable_metrics=False)
        w = Worker("w0", qm, eng.process_fn)
        try:
            msgs = [Message(content=f"payload-{i}",
                            priority=Priority(1 + i % 4)) for i in range(8)]
            for m in msgs:
                qm.push_message(m)
            w.start()
            deadline = threading.Event()
            for _ in range(100):
                if all(m.status == MessageStatus.COMPLETED for m in msgs):
                    break
                deadline.wait(0.05)
            assert all(m.status == MessageStatus.COMPLETED for m in msgs)
            for m in msgs:
                assert m.response == m.content
                assert m.metadata["usage"]["completion_tokens"] > 0
        finally:
            w.stop()
            eng.stop()


class TestPagePressure:
    """Shedding-order correctness under pool exhaustion."""

    def test_low_request_cannot_strip_realtime_pages(self):
        # 7 usable pages of 8 tokens. A realtime sequence occupies most
        # of the pool; a LOW request that cannot fit must WAIT, not
        # preempt-with-release the more urgent runner.
        eng = make_echo_engine(slots=2, num_pages=8, page_size=8,
                               max_pages=8)
        victims = []
        orig = eng._preempt
        eng._preempt = lambda v, release_pages: (
            victims.append((v.req.id, release_pages)),
            orig(v, release_pages))[-1]
        hrt = eng.submit(GenRequest(id="rt", prompt="R" * 24,
                                    priority=Priority.REALTIME))
        eng.step()  # admit rt: 25-token footprint → 4 pages
        hlow = eng.submit(GenRequest(id="low", prompt="L" * 24,
                                     priority=Priority.LOW))
        eng.step()
        assert not hlow.done
        assert eng.get_stats()["active"] == 1  # low is waiting, not admitted
        eng.run_until_idle()
        assert hrt.result.text == "R" * 24
        assert hlow.result.text == "L" * 24
        assert ("rt", True) not in victims  # realtime never stripped

    def test_pending_held_pages_are_reclaimable(self):
        # One slot: LOW gets slot-preempted by HIGH (keeps pages), then
        # REALTIME needs those parked pages — shedding must find them
        # rather than deadlock.
        eng = make_echo_engine(slots=1, num_pages=12, page_size=8,
                               max_pages=12)
        hlow = eng.submit(GenRequest(id="low", prompt="L" * 40,
                                     priority=Priority.LOW))
        eng.step()  # low admitted, holds ~6 pages
        hhigh = eng.submit(GenRequest(id="h", prompt="H" * 30,
                                      priority=Priority.HIGH))
        hrt = eng.submit(GenRequest(id="rt", prompt="R" * 30,
                                    priority=Priority.REALTIME))
        eng.run_until_idle()
        assert hrt.result.text == "R" * 30
        assert hhigh.result.text == "H" * 30
        assert hlow.result.text == "L" * 40  # rebuilt after page loss
        assert eng.allocator.used() == 0

    def test_released_conversation_turn_rebuilds_history(self):
        """A conversation sequence whose pages are reclaimed mid-turn
        must rebuild with its full adopted history, not just the turn's
        prompt (echo streams history+prompt, so the echoed text proves
        what context the rebuild saw)."""
        eng = make_echo_engine(slots=1, num_pages=16, page_size=8,
                               max_pages=16)
        h1 = eng.submit(GenRequest(id="t1", prompt="hist", max_new_tokens=4,
                                   conversation_id="c", priority=Priority.LOW))
        eng.run_until_idle()
        assert h1.result.text == "hist"
        # Turn 2 adopts the cache, then is preempted-with-release by a
        # realtime burst big enough to need its pages.
        h2 = eng.submit(GenRequest(id="t2", prompt="-two",
                                   conversation_id="c", priority=Priority.LOW))
        eng.step()  # admit turn 2 (adopts cache)
        # 15 usable pages = 120 tokens; rt needs 105 (14 pages) which
        # forces reclaiming t2's adopted pages but still fits the pool.
        hrt = eng.submit(GenRequest(id="rt", prompt="X" * 52,
                                    priority=Priority.REALTIME))
        eng.run_until_idle()
        assert hrt.result.text == "X" * 52
        # Echo replays the prefill stream it saw: turn 1 ended by length,
        # so its pending token 't' leads turn 2's stream ("t-two"). A
        # rebuild that lost the adopted context or misaligned the echo
        # would produce a different string.
        assert h2.result.text == "t-two"
        assert h2.result.finish_reason == "eos"
        assert eng.allocator.used() >= 0


class TestChunkedDecode:
    """decode_chunk semantics: K steps per call must be indistinguishable
    from K single steps (EOS latching, budgets, page accounting)."""

    def test_echo_chunked_equals_single(self):
        for prompt in ("hello", "a" * 23, "xy"):
            e1 = make_echo_engine(slots=2)
            tok = ByteTokenizer()
            ex = EchoExecutor(batch_size=2, page_size=8, num_pages=64,
                              max_pages_per_seq=16, eos_id=tok.eos_id,
                              chunk_size=4)
            ek = InferenceEngine(ex, tok, enable_metrics=False)
            h1 = e1.submit(GenRequest(id="r", prompt=prompt))
            hk = ek.submit(GenRequest(id="r", prompt=prompt))
            e1.run_until_idle()
            ek.run_until_idle()
            assert hk.result.text == h1.result.text == prompt
            assert hk.result.finish_reason == h1.result.finish_reason
            assert ek.allocator.used() == 0

    def test_chunked_respects_max_new_tokens(self):
        tok = ByteTokenizer()
        ex = EchoExecutor(batch_size=1, page_size=8, num_pages=64,
                          max_pages_per_seq=16, eos_id=tok.eos_id,
                          chunk_size=8)
        eng = InferenceEngine(ex, tok, enable_metrics=False)
        h = eng.submit(GenRequest(id="r", prompt="abcdefghij",
                                  max_new_tokens=3))
        eng.run_until_idle()
        assert h.result.text == "abc"
        assert h.result.finish_reason == "length"

    def test_chunked_conversation_pending_token(self):
        """A length-finish inside a chunk leaves the final token's KV
        unwritten; the next turn must carry it (same as single-step)."""
        tok = ByteTokenizer()
        ex = EchoExecutor(batch_size=1, page_size=8, num_pages=64,
                          max_pages_per_seq=16, eos_id=tok.eos_id,
                          chunk_size=4)
        eng = InferenceEngine(ex, tok, enable_metrics=False)
        h1 = eng.submit(GenRequest(id="t1", prompt="abcdef",
                                   conversation_id="c", max_new_tokens=6))
        eng.run_until_idle()
        assert h1.result.finish_reason == "length"
        h2 = eng.submit(GenRequest(id="t2", prompt="gh",
                                   conversation_id="c"))
        eng.run_until_idle()
        assert h2.result.finish_reason == "eos"
        assert h2.result.cached_tokens > 0


# -- JAX executor -------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from llmq_tpu.models.llama import init_params, llama3_tiny

    cfg = llama3_tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                      ffn_dim=128, vocab_size=512, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_jax_engine(tiny_model, slots=2, num_pages=64, page_size=8, **kw):
    cfg, params = tiny_model
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=slots, page_size=page_size,
                     num_pages=num_pages, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id)
    return InferenceEngine(ex, tok, enable_metrics=False,
                           max_decode_steps=8, **kw)


def reference_greedy(cfg, params, prompt_ids, n_steps):
    """Dense single-sequence greedy decode, independent of the engine."""
    import jax.numpy as jnp

    from llmq_tpu.models.llama import forward_decode, forward_prefill, init_kv_pages

    page_size = 8
    pages = init_kv_pages(cfg, 64, page_size)
    max_pages = 32
    bt = jnp.arange(1, max_pages + 1, dtype=jnp.int32)[None, :]
    n = len(prompt_ids)
    toks = jnp.asarray(prompt_ids, jnp.int32)[None, :]
    pos = jnp.arange(n, dtype=jnp.int32)[None, :]
    logits, pages = forward_prefill(params, cfg, toks, pos,
                                    jnp.asarray([n], jnp.int32), pages, bt)
    out = [int(jnp.argmax(logits[0, n - 1]))]
    cur = out[0]
    for i in range(n_steps - 1):
        lg, pages = forward_decode(
            params, cfg, jnp.asarray([cur], jnp.int32),
            jnp.asarray([n + i], jnp.int32), pages, bt)
        cur = int(jnp.argmax(lg[0]))
        out.append(cur)
    return out


class TestJaxEngine:
    def test_multi_chunk_generation_spans_chunks(self, tiny_model):
        """A generation LONGER than chunk_size must produce identical
        tokens through the pipelined/carried path (chunk_size=4) and
        the single-step path (chunk_size=1) — and run to its full length
        (r4: a carry bug latched budget-paused rows as done, truncating
        every multi-chunk generation with a phantom EOS)."""
        cfg, params = tiny_model
        tok = ByteTokenizer()

        def run(chunk):
            ex = JaxExecutor(cfg, params, batch_size=2, page_size=8,
                             num_pages=64, prefill_buckets=[16, 64],
                             eos_id=tok.eos_id, chunk_size=chunk)
            eng = InferenceEngine(ex, tok, enable_metrics=False,
                                  max_decode_steps=64)
            h = eng.submit(GenRequest(id="r", prompt="span the chunks",
                                      max_new_tokens=20))
            eng.run_until_idle()
            return h.result

        piped = run(4)      # 20 tokens span 5 chunks
        single = run(1)
        assert piped.tokens == single.tokens
        if piped.finish_reason == "length":
            assert len(piped.tokens) == 20

    def test_pipelined_soak_randomized(self, tiny_model):
        """Randomized soak of the pipelined engine: mixed priorities,
        conversations, multi-chunk generations and mid-flight
        cancellations. Invariants at idle: every handle resolved, page
        accounting balances (used == pinned conversation pages), no
        sequence state leaked."""
        import random as _random

        cfg, params = tiny_model
        tok = ByteTokenizer()
        ex = JaxExecutor(cfg, params, batch_size=3, page_size=8,
                         num_pages=96, prefill_buckets=[16, 64],
                         eos_id=tok.eos_id, chunk_size=4)
        eng = InferenceEngine(ex, tok, enable_metrics=False,
                              max_decode_steps=24, kv_pin_ttl=0)
        rng = _random.Random(123)
        prios = [Priority.REALTIME, Priority.HIGH, Priority.NORMAL,
                 Priority.LOW]
        handles = []
        for i in range(40):
            conv = f"c{rng.randrange(6)}" if rng.random() < 0.4 else ""
            h = eng.submit(GenRequest(
                id=f"s{i}", prompt=f"prompt {i} " + "x" * rng.randrange(40),
                priority=rng.choice(prios), conversation_id=conv,
                max_new_tokens=rng.randrange(1, 20)))
            handles.append(h)
            # Interleave scheduling with arrivals + random cancels.
            for _ in range(rng.randrange(4)):
                eng.step()
            if rng.random() < 0.15:
                rng.choice(handles).cancel()
        eng.run_until_idle()
        assert all(h.done for h in handles)
        for h in handles:
            assert h.result.finish_reason in ("eos", "length",
                                              "cancelled"), h.result
        # Page accounting: everything not pinned to a conversation is
        # back in the pool.
        assert eng.allocator.used() == eng.allocator.pinned_pages()
        assert all(s is None for s in eng._slots)
        assert eng._chunk_inflight is None
        assert not eng._pending and not eng._inbox
        # Conversations still answer a follow-up turn correctly.
        convs = eng.cached_conversations()
        if convs:
            h2 = eng.submit(GenRequest(id="follow", prompt=" more",
                                       conversation_id=convs[0],
                                       max_new_tokens=4))
            eng.run_until_idle()
            assert h2.result.finish_reason in ("eos", "length")
            assert h2.result.cached_tokens > 0

    def test_preemption_defers_while_chunk_inflight(self, tiny_model):
        """Pipelined executor: a realtime arrival while low-tier chunks
        are in flight must still preempt and finish first — preemption
        is DEFERRED to the reconcile (never applied to rows the device
        is still decoding), not dropped."""
        cfg, params = tiny_model
        tok = ByteTokenizer()
        ex = JaxExecutor(cfg, params, batch_size=1, page_size=8,
                         num_pages=64, prefill_buckets=[16, 64],
                         eos_id=tok.eos_id, chunk_size=4)
        eng = InferenceEngine(ex, tok, enable_metrics=False,
                              max_decode_steps=40)
        low = eng.submit(GenRequest(id="low", prompt="background work",
                                    priority=Priority.LOW,
                                    max_new_tokens=40))
        # Steps until a chunk is in flight for the low request.
        for _ in range(50):
            eng.step()
            if eng._chunk_inflight is not None:
                break
        assert eng._chunk_inflight is not None
        rt = eng.submit(GenRequest(id="rt", prompt="urgent",
                                   priority=Priority.REALTIME,
                                   max_new_tokens=4))
        eng.run_until_idle()
        assert rt.result.finish_reason in ("eos", "length")
        assert low.result.finish_reason in ("eos", "length")
        # The realtime request finished BEFORE the preempted low one.
        assert rt.finished_at < low.finished_at
        # And the preempted low request still produced its full output.
        if low.result.finish_reason == "length":
            assert len(low.result.tokens) == 40

    def test_batched_prefill_matches_sequential(self, tiny_model):
        """An admission wave through the batched-prefill program
        (prefill_batch=4) must produce exactly the tokens the
        one-sequence-per-program path produces, including a
        continuation turn over cached KV."""
        cfg, params = tiny_model
        tok = ByteTokenizer()
        prompts = ["alpha prompt one", "beta two", "gamma three is longer",
                   "delta", "epsilon five"]

        def run(npf):
            ex = JaxExecutor(cfg, params, batch_size=8, page_size=8,
                             num_pages=128, prefill_buckets=[16, 64],
                             eos_id=tok.eos_id, chunk_size=4,
                             prefill_batch=npf)
            eng = InferenceEngine(ex, tok, enable_metrics=False,
                                  max_decode_steps=6)
            hs = [eng.submit(GenRequest(id=f"r{i}", prompt=p,
                                        conversation_id=f"c{i}",
                                        max_new_tokens=6))
                  for i, p in enumerate(prompts)]
            eng.run_until_idle()
            first = [h.result.tokens for h in hs]
            # Turn 2: continuation prefill over the cached KV.
            h2 = eng.submit(GenRequest(id="t2", prompt=" more",
                                       conversation_id="c0",
                                       max_new_tokens=6))
            eng.run_until_idle()
            assert h2.result.cached_tokens > 0
            return first, h2.result.tokens

        batched, b2 = run(4)
        single, s2 = run(1)
        assert batched == single
        assert b2 == s2

    def test_greedy_matches_reference(self, tiny_model):
        cfg, params = tiny_model
        eng = make_jax_engine(tiny_model)
        prompt = "hello world"
        h = eng.submit(GenRequest(id="r", prompt=prompt, max_new_tokens=6))
        eng.run_until_idle()
        got = h.result.tokens
        tok = ByteTokenizer()
        want = reference_greedy(cfg, params, tok.encode(prompt), 6)
        # EOS may cut the engine's output short; compare the prefix.
        assert got == want[: len(got)]
        assert len(got) >= 1

    def test_batched_equals_single(self, tiny_model):
        """Continuous batching must not change any sequence's tokens."""
        eng2 = make_jax_engine(tiny_model, slots=2)
        prompts = ["alpha beta", "gamma delta epsilon"]
        hs = [eng2.submit(GenRequest(id=f"r{i}", prompt=p, max_new_tokens=5))
              for i, p in enumerate(prompts)]
        eng2.run_until_idle()

        for p, h in zip(prompts, hs):
            eng1 = make_jax_engine(tiny_model, slots=1)
            h1 = eng1.submit(GenRequest(id="solo", prompt=p, max_new_tokens=5))
            eng1.run_until_idle()
            assert h.result.tokens == h1.result.tokens

    def test_conversation_continuation_matches_full_prefill(self, tiny_model):
        """Turn 2 on cached KV must produce the same tokens as prefilling
        the whole history from scratch (numeric KV-reuse correctness)."""
        t1, t2 = "abc", "defg"
        # Engine A: two turns through the conversation cache.
        engA = make_jax_engine(tiny_model)
        h1 = engA.submit(GenRequest(id="t1", prompt=t1, conversation_id="c",
                                    max_new_tokens=4))
        engA.run_until_idle()
        h2 = engA.submit(GenRequest(id="t2", prompt=t2, conversation_id="c",
                                    max_new_tokens=4))
        engA.run_until_idle()
        assert h2.result.cached_tokens > 0

        # Engine B: one shot over the concatenated history.
        tok = ByteTokenizer()
        history = tok.encode(t1) + h1.result.tokens + tok.encode(t2)
        cfg, params = tiny_model
        want = reference_greedy(cfg, params, history, 4)
        got = h2.result.tokens
        assert got == want[: len(got)]

    def test_long_prompt_chunked_prefill(self, tiny_model):
        """Prompts beyond the largest bucket stream through it in chunks."""
        cfg, params = tiny_model
        eng = make_jax_engine(tiny_model)  # buckets [16, 64]
        prompt = "x" * 100                 # > 64 → two chunks
        h = eng.submit(GenRequest(id="r", prompt=prompt, max_new_tokens=3))
        eng.run_until_idle()
        tok = ByteTokenizer()
        want = reference_greedy(cfg, params, tok.encode(prompt), 3)
        assert h.result.tokens == want[: len(h.result.tokens)]


class _ChunkSpyExecutor(EchoExecutor):
    """Echo executor that exposes prefill buckets and records the
    interleaving of prefill chunks and decode steps."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.prefill_buckets = [4]       # tiny bucket → many chunks
        self.trace: list = []
        self._partial: dict = {}

    def prefill(self, tokens, start_pos, block_table, temperature, slot):
        self.trace.append(("prefill", slot, len(tokens)))
        # Accumulate chunks so the echo stream is the FULL prompt.
        if slot in self._partial and self._partial[slot][1] == start_pos:
            prev, _ = self._partial[slot]
            tokens = prev + list(tokens)
            start_pos = start_pos - len(prev)
        first = super().prefill(tokens, start_pos, block_table,
                                temperature, slot)
        self._partial[slot] = (list(tokens),
                               start_pos + len(tokens))
        return first

    def decode(self, tokens, positions, block_tables, temperatures):
        self.trace.append(("decode",))
        return super().decode(tokens, positions, block_tables,
                              temperatures)


class TestIncrementalPrefill:
    def test_long_prompt_interleaves_with_decode(self):
        """A long prompt admitted while another sequence decodes must
        NOT stall it: prefill buckets and decode steps alternate."""
        tok = ByteTokenizer()
        ex = _ChunkSpyExecutor(batch_size=2, page_size=4, num_pages=64,
                               max_pages_per_seq=16, eos_id=tok.eos_id)
        eng = InferenceEngine(ex, tok, enable_metrics=False,
                              max_decode_steps=12)
        # Sequence A: 16-token prompt (4 prefill buckets), 16-token echo
        # → keeps decoding while B prefills.
        ha = eng.submit(GenRequest(id="a", prompt="a" * 16,
                                   max_new_tokens=30))
        for _ in range(6):   # 4 prefill buckets + a couple decode steps
            eng.step()
        assert any(t[0] == "decode" for t in ex.trace)
        # Sequence B: 30-token prompt → 8 buckets of 4 on slot 1.
        hb = eng.submit(GenRequest(id="b", prompt="x" * 30,
                                   max_new_tokens=4))
        eng.run_until_idle()
        assert ha.done and hb.done
        assert ha.result.finish_reason in ("eos", "length")
        assert hb.result.finish_reason in ("eos", "length")
        # B's prompt ran as multiple bucket chunks...
        b_chunks = [t for t in ex.trace if t[0] == "prefill" and t[1] == 1]
        assert len(b_chunks) >= 8, ex.trace
        # ...and decode steps happened BETWEEN them (no stall).
        first_b = ex.trace.index(b_chunks[0])
        last_b = ex.trace.index(b_chunks[-1])
        between = ex.trace[first_b:last_b]
        assert any(t[0] == "decode" for t in between), ex.trace
        # Echo correctness survives chunked prefill: b echoes its prompt.
        assert hb.result.text == "xxxx", hb.result

    def test_mid_prefill_not_preemptible(self):
        """A realtime arrival must not strip a mid-prefill sequence's
        slot (partial state can't restart); it waits for a real victim."""
        tok = ByteTokenizer()
        ex = _ChunkSpyExecutor(batch_size=1, page_size=4, num_pages=64,
                               max_pages_per_seq=16, eos_id=tok.eos_id)
        eng = InferenceEngine(ex, tok, enable_metrics=False,
                              max_decode_steps=4)
        hb = eng.submit(GenRequest(id="slow", prompt="y" * 20,
                                   priority=Priority.LOW,
                                   max_new_tokens=2))
        eng.step()                         # admitted, first bucket runs
        hr = eng.submit(GenRequest(id="rt", prompt="hi",
                                   priority=Priority.REALTIME,
                                   max_new_tokens=2))
        eng.step()                         # rt pending; slow keeps slot
        assert not hb.done
        eng.run_until_idle()
        assert hb.done and hr.done
        assert hb.result.finish_reason in ("eos", "length")
        assert hr.result.finish_reason in ("eos", "length")

    def test_pool_pressure_strips_midprefill_low_tier(self):
        """Priority inversion guard: a LOW sequence mid-prefill must
        yield its pages when a REALTIME decoding sequence needs one —
        and later restart via the rebuild path with its full prompt."""
        tok = ByteTokenizer()
        # Pool: 15 usable pages of 4 slots = 60 tokens.
        ex = _ChunkSpyExecutor(batch_size=2, page_size=4, num_pages=16,
                               max_pages_per_seq=16, eos_id=tok.eos_id)
        eng = InferenceEngine(ex, tok, enable_metrics=False,
                              max_decode_steps=24)
        # Realtime: 12-token prompt (3 buckets), echoes 12 tokens.
        hr = eng.submit(GenRequest(id="rt", prompt="r" * 12,
                                   priority=Priority.REALTIME,
                                   max_new_tokens=20))
        for _ in range(4):
            eng.step()                    # rt prefilled, starts decoding
        assert any(t[0] == "decode" for t in ex.trace)
        # Low: 40-token prompt grabs most remaining pages, mid-prefill.
        hl = eng.submit(GenRequest(id="lo", prompt="l" * 40,
                                   priority=Priority.LOW,
                                   max_new_tokens=4))
        eng.step()                        # low admitted, 1st bucket only
        # Drive to completion: rt will need new pages for decode growth;
        # the pool is exhausted → low's pages must be reclaimable.
        eng.run_until_idle()
        assert hr.done and hr.result.finish_reason in ("eos", "length")
        assert hr.result.text == "r" * 12, hr.result   # echo intact
        assert hl.done and hl.result.finish_reason in ("eos", "length")
        assert hl.result.text == "l" * 4, hl.result    # rebuilt correctly


# -- the leaves the executor lays transposed ----------------------------------

def _laid_model(**kw):
    import jax

    from llmq_tpu.models.llama import init_params, llama3_tiny

    cfg = llama3_tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                      ffn_dim=128, vocab_size=512, max_seq_len=256, **kw)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _laid_executor(cfg, params, **kw):
    return JaxExecutor(cfg, params, batch_size=2, page_size=8, num_pages=64,
                       prefill_buckets=[16, 64], eos_id=ByteTokenizer().eos_id,
                       chunk_size=4, mixed_prefill_slices=2,
                       mixed_slice_tokens=16, **kw)


class TestLaidParams:
    """``llama.DEVICE_LAYOUT``: the executor lays ``wq``, ``wk`` and
    ``wv`` transposed on the device once, when it takes the tree
    (``executor.lay_params``) — the physical layout alone."""

    NAMES = ("wq", "wk", "wv")

    def test_three_leaves_are_laid_and_counted(self):
        from llmq_tpu.engine.executor import _lies

        cfg, params = _laid_model()
        before = {n: np.asarray(params["layers"][n], np.float32)
                  for n in params["layers"]}
        ex = _laid_executor(cfg, params)
        nbytes = sum(before[n].size * 2 for n in self.NAMES)
        assert ex.relaid == {"leaves": 3, "bytes": nbytes}
        assert ex.telemetry_info()["relaid"] == ex.relaid
        for name, leaf in ex.params["layers"].items():
            assert _lies(leaf, "transposed") == (name in self.NAMES), name
            # shape, dtype and values are what they were
            assert leaf.shape == before[name].shape
            np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                          before[name])
        eng = InferenceEngine(ex, ByteTokenizer(), enable_metrics=False)
        assert eng.get_stats()["device"]["relaid"] == ex.relaid

    def test_the_tree_handed_in_stays_whole_and_holds_nothing_twice(self):
        """The laid leaf takes the original's place in the tree that
        was handed in (nobody keeps 3 x 201 MB beside the pool for the
        harness's sake), and a second executor over the same tree holds
        the same leaves: laid once."""
        cfg, params = _laid_model()
        first = _laid_executor(cfg, params)
        assert first.params is params
        laid = {n: params["layers"][n] for n in self.NAMES}
        second = _laid_executor(cfg, params)
        assert second.relaid == first.relaid
        for n in self.NAMES:
            assert second.params["layers"][n] is laid[n]

    @pytest.mark.parametrize("which", ["quantized", "no-name"])
    def test_what_is_not_asked_for_comes_back_the_same_objects(
            self, which, monkeypatch):
        import jax

        from llmq_tpu.models import llama

        if which == "quantized":
            cfg, _ = _laid_model()
            params = llama.init_params_quantized(jax.random.PRNGKey(0), cfg)
        else:
            cfg, params = _laid_model()
            monkeypatch.delattr(llama, "DEVICE_LAYOUT")
        leaves = jax.tree.leaves(params)
        ex = _laid_executor(cfg, params)
        assert ex.relaid == {"leaves": 0, "bytes": 0}
        assert ex.params is params
        assert all(a is b for a, b in zip(jax.tree.leaves(ex.params),
                                          leaves))

    def test_a_described_tree_is_laid_as_a_description(self):
        """``scripts/whole_copies.py`` and ``tests/test_tpu_compile.py``
        hand the executor ShapeDtypeStructs: it describes its pool
        beside them and its programs take the three leaves in their
        layout."""
        import jax

        from llmq_tpu.engine.executor import _lies, describe

        cfg, params = _laid_model()
        ex = _laid_executor(cfg, describe(
            params, jax.sharding.SingleDeviceSharding(jax.devices()[0])))
        assert ex.relaid["leaves"] == 3
        assert all(isinstance(x, jax.ShapeDtypeStruct)
                   for x in jax.tree.leaves((ex.params, ex.cache)))
        for name, fn, operands, _ in ex.programs():
            got = operands[0]["layers"]
            assert sorted(n for n in got if _lies(
                got[n], "transposed")) == sorted(self.NAMES), name
            fn.lower(*operands)

    @pytest.mark.parametrize("case", ["two-tiles-whole-full",
                                      "four-tiles-lead-crosses-an-edge",
                                      "all-slices-full"])
    def test_the_logits_are_bit_equal(self, case, tight_step):
        """``forward_prefill``, ``forward_decode`` and ``forward_mixed``
        over the laid tree against the same over the tree as it was
        made: the products are the same products."""
        import jax

        from llmq_tpu.engine.executor import lay_params
        from llmq_tpu.models import llama
        import mixed_tight

        cfg, plain = _laid_model()
        laid = jax.tree.map(lambda x: x, plain)
        assert lay_params(llama, laid)["leaves"] == 3
        assert laid["layers"]["wq"] is not plain["layers"]["wq"]
        want = mixed_tight.both_ways(tight_step, llama, cfg, plain, case,
                                     page=8)
        got = mixed_tight.both_ways(tight_step, llama, cfg, laid, case,
                                    page=8)
        for a, b in zip(want, got):
            for name in ("dec", "pf"):
                np.testing.assert_array_equal(a[name], b[name])
            for name in a["pages"]:
                np.testing.assert_array_equal(a["pages"][name],
                                              b["pages"][name])

    @pytest.mark.parametrize("served,laid", [
        ("smollm2-1.7b-bf16", {"leaves": 3, "bytes": 603_979_776}),
        # int8 leaves with their scales are never laid
        ("mistral-7b-v0.3-w8kv8", {"leaves": 0, "bytes": 0}),
        # in_proj row-major, the four attention layers' wq, wk and wv
        ("granite-4.0-h-micro-bf16", {"leaves": 4,
                                      "bytes": 1_305_477_120}),
        # a family that names no leaf
        ("kanana-2-30b-a3b-bf16", {"leaves": 0, "bytes": 0}),
    ])
    def test_a_served_tree_s_description_is_laid_by_its_family_s_table(
            self, served, laid):
        """``lay_params`` over the DESCRIPTION of each benchmark
        configuration's parameters as served: what it lays is the
        family's ``DEVICE_LAYOUT`` and nothing else, each leaf in the
        order its entry names, every other leaf the object it was."""
        import json
        import os

        import jax

        from benchmark.harness import contract
        from llmq_tpu.engine.executor import (LAYOUTS, _order, describe,
                                              lay_params)
        from llmq_tpu.models import family_of

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               served + ".json"), encoding="utf-8") as f:
            doc = json.load(f)
        adapter = contract.load_family(
            contract.family_dir(contract.load_benchmark(), doc), "adapter")
        mcfg = adapter.register(doc["server"]["model"]["name"], doc)
        params = describe(
            jax.eval_shape(adapter.param_builder(mcfg,
                                                 doc["server"]["model"]),
                           jax.random.PRNGKey(0)),
            jax.sharding.SingleDeviceSharding(jax.devices()[0]))
        before = dict(params["layers"])
        fam = family_of(mcfg)
        assert lay_params(fam, params) == laid
        table = getattr(fam, "DEVICE_LAYOUT", {}) if laid["leaves"] else {}
        for name, leaf in params["layers"].items():
            if name in table:
                assert _order(leaf) == LAYOUTS[table[name]](leaf.ndim), name
                assert (leaf.shape, leaf.dtype) == (before[name].shape,
                                                    before[name].dtype)
            else:
                assert leaf is before[name], name
        assert lay_params(fam, params) == laid      # and nothing twice
        assert all(params["layers"][n] is not before[n] for n in table)

    def test_the_streams_are_those_of_the_tree_as_it_was(self, monkeypatch):
        """A tiny engine over the laid leaves against the same engine
        built with the family's answer patched empty: token for token,
        through prefill, mixed and decode chunks."""
        from llmq_tpu.core.config import MixedBatchConfig
        from llmq_tpu.models import llama

        def streams(lay):
            if not lay:
                monkeypatch.setattr(llama, "DEVICE_LAYOUT", {})
            cfg, params = _laid_model()
            ex = _laid_executor(cfg, params)
            assert ex.relaid["leaves"] == (3 if lay else 0)
            eng = InferenceEngine(
                ex, ByteTokenizer(), enable_metrics=False,
                max_decode_steps=24,
                mixed_batch=MixedBatchConfig(enabled=True,
                                             prefill_token_budget=32,
                                             max_slices=2))
            hs = [eng.submit(GenRequest(id="a", prompt="the first runs alone",
                                        max_new_tokens=20))]
            for _ in range(3):
                eng.step()
            hs += [eng.submit(GenRequest(
                id=f"b{i}", prompt=f"prompt {i} joins a running batch " * 2,
                max_new_tokens=12)) for i in range(3)]
            eng.run_until_idle()
            assert eng.get_stats()["mixed_batch"]["steps"] > 0
            return [h.result.tokens for h in hs]

        laid = streams(True)
        assert all(laid)
        assert laid == streams(False)
