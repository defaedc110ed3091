"""Async host↔device decode pipeline (docs/performance.md "Async
pipeline"): double-buffered chunk dispatch, batched readback on the
fetch thread, and off-path completions must be TOKEN-FOR-TOKEN
equivalent to the synchronous path — across plain decode waves, mixed
prefill+decode batching, prefix-cache continuation turns, preemption,
cancellation mid-flight and crash recovery with chunks in flight.
``executor.async_pipeline.enabled: false`` is a hard off-switch pinned
byte-identical to the pre-pipeline scheduling, and the overlap
decomposition (``step_overlapped_ms`` / ``pipeline_overlap_ratio``)
must prove the pipeline actually hides wall-clock without inflating
``step_device_ms``."""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from llmq_tpu import chaos
from llmq_tpu.chaos import InvariantChecker
from llmq_tpu.core.config import (AsyncPipelineConfig, ChaosConfig,
                                  MixedBatchConfig, PrefixCacheConfig,
                                  SupervisorConfig)
from llmq_tpu.core.types import Priority
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import (EchoExecutor, HostStaging,
                                      JaxExecutor)
from llmq_tpu.engine.supervisor import EngineSupervisor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.llama import get_config, init_params


def pipe_cfg(enabled=True, depth=2, workers=1):
    return AsyncPipelineConfig(enabled=enabled, depth=depth,
                               completion_workers=workers)


def mixed_cfg(budget=16, slices=2):
    return MixedBatchConfig(enabled=True, prefill_token_budget=budget,
                            max_slices=slices)


def make_echo_engine(pipe=None, mixed=None, slots=4, chunk=4,
                     delay=0.0, metrics=False, name="pipetest", dp=1,
                     **kw):
    """Echo engine; the executor's futures API is exposed exactly when
    the pipeline config is enabled — the builder's wiring. ``dp`` > 1
    splits rows and pages into that many universes, as a mesh does."""
    tok = ByteTokenizer()
    on = pipe is not None and pipe.enabled
    ex = EchoExecutor(batch_size=slots, page_size=8, num_pages=256,
                      max_pages_per_seq=16, eos_id=tok.eos_id,
                      chunk_size=chunk, mixed_prefill_slices=2,
                      mixed_slice_tokens=8, async_chunks=on,
                      step_delay_s=delay)
    if dp > 1:
        ex.dp_shards = dp
    eng = InferenceEngine(ex, tok, enable_metrics=metrics, name=name,
                          max_decode_steps=64, mixed_batch=mixed,
                          async_pipeline=pipe, **kw)
    return eng, ex


WAVE = [
    ("hello world this is a long prompt " * 3, Priority.NORMAL),
    ("short", Priority.REALTIME),
    ("medium sized prompt here", Priority.LOW),
    ("another quite long prompt for slicing " * 2, Priority.HIGH),
    ("fifth request", Priority.NORMAL),
    ("sixth one goes last", Priority.LOW),
]


REFUSAL_KEYS = {"depth", "free_slot", "urgent_pending", "cancelled",
                "geometry", "pages", "row_ended", "nothing_to_decode",
                "tenancy"}


def drive_wave(eng, wave=WAVE, conv=None, steps_between=2, max_new=40):
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


def closed_loop(eng, clients=6, per_client=4, filler="xyz ", fill_mod=5,
                fill_base=6, new_base=24, new_step=0,
                priority=Priority.LOW):
    """More clients than rows, each sending its next request when its
    last one ended (the saturated cells' traffic): every row is taken
    nearly always, and requests end and are replaced all the time.
    Prompts run over several mixed slices; ``new_step`` staggers the
    output lengths so rows do not all end in the same chunk. Returns
    ``({(client, k): tokens}, {(client, k): finish_reason})``."""
    handles, live = {}, {}
    sent = dict.fromkeys(range(clients), 0)

    def send(c):
        k = sent[c]
        sent[c] += 1
        handles[c, k] = live[c] = eng.submit(GenRequest(
            id=f"c{c}k{k}", priority=priority,
            prompt=(f"client {c} request {k} "
                    + filler * (fill_base + (c + k) % fill_mod)),
            max_new_tokens=new_base + new_step * ((c + 2 * k) % 4)))

    for c in range(clients):
        send(c)
    for _ in range(20000):
        eng.step()
        eng._drain_completions()
        for c, h in list(live.items()):
            if h.done:
                del live[c]
                if sent[c] < per_client:
                    send(c)
        if not live:
            break
    eng.run_until_idle()
    assert not live
    return ({k: h.result.tokens for k, h in handles.items()},
            {k: h.result.finish_reason for k, h in handles.items()})


def dispatches(eng):
    """``(program, inflight)`` of every ``engine.dispatch`` so far."""
    return [(s.meta["program"], s.meta["inflight"])
            for s in eng._prof.snapshot() if s.name == "engine.dispatch"]


class TestEchoEquivalence:
    @pytest.mark.parametrize("depth", [2, 3])
    def test_full_batch_closed_loop_equivalence(self, depth):
        """A full batch keeps the pipeline deep: with more clients than
        rows and prompts longer than a slice, requests are pending all
        the time and seated sequences have slices to run, and the next
        chunk still goes out from the carry — decode rows, slices and
        joining rows in it. Streams are depth 1's token for token,
        nothing is lost, and most chunks were dispatched with another
        in flight."""
        def run(d):
            eng, _ = make_echo_engine(pipe_cfg(depth=d), mixed=mixed_cfg(),
                                      delay=0.0005)
            toks, why = closed_loop(eng)
            stats = eng.get_stats()
            eng.stop()
            return toks, why, stats

        toks, why, stats = run(depth)
        ref, ref_why, ref_stats = run(1)
        assert toks == ref and why == ref_why
        assert set(why.values()) <= {"length", "eos"}
        assert len(toks) == 24 and all(toks.values())     # zero loss
        hist = stats["pipeline"]["depth_hist"]
        deep = sum(v for k, v in hist.items() if int(k) >= 2)
        assert deep > sum(hist.values()) / 2, hist
        assert hist.get(str(depth), 0) > 0
        assert stats["mixed_batch"]["steps"] > 0
        assert stats["mixed_batch"]["prefill_tokens"] \
            == ref_stats["mixed_batch"]["prefill_tokens"]
        assert list(ref_stats["pipeline"]["depth_hist"]) == ["1"]
        # every stopped fill was counted under a reason
        assert set(stats["pipeline"]["fill_refusals"]) <= REFUSAL_KEYS

    def test_decode_wave_equivalence(self):
        def run(pipe):
            eng, _ = make_echo_engine(pipe)
            handles = drive_wave(eng)
            stats = eng.get_stats()
            eng.stop()
            return [h.result.tokens for h in handles], stats

        on, s_on = run(pipe_cfg())
        off, s_off = run(None)
        assert on == off
        # The pipeline actually ran 2-deep, and the off path never
        # tracked pipeline state.
        assert s_on["pipeline"]["depth_hist"].get("2", 0) > 0
        assert "pipeline" not in s_off

    def test_mixed_batch_equivalence(self):
        def run(pipe):
            eng, _ = make_echo_engine(pipe, mixed=mixed_cfg())
            handles = drive_wave(eng)
            stats = eng.get_stats()
            eng.stop()
            return [h.result.tokens for h in handles], stats

        on, s_on = run(pipe_cfg())
        off, _ = run(None)
        assert on == off
        assert s_on["mixed_batch"]["steps"] > 0   # fused path really ran

    def test_conversation_continuation_equivalence(self):
        """Turn-N continuation prefill over pinned conversation KV and
        the radix tree rides the pipelined path identically."""
        def run(pipe):
            eng, _ = make_echo_engine(
                pipe, mixed=mixed_cfg(),
                prefix_cache=PrefixCacheConfig(enabled=True))
            out = []
            for turn in range(3):
                handles = drive_wave(
                    eng,
                    wave=[(f"turn {turn} says something longish "
                           f"{'x' * (10 * turn)}", Priority.NORMAL)] * 3,
                    conv=[f"c{i}" for i in range(3)],
                    max_new=24)
                out.append([h.result.tokens for h in handles])
            eng.stop()
            return out

        assert run(pipe_cfg()) == run(None)

    def test_depth3_equivalence_and_bound(self):
        def run(pipe):
            eng, _ = make_echo_engine(pipe, delay=0.0005)
            handles = drive_wave(eng)
            stats = eng.get_stats()
            eng.stop()
            return [h.result.tokens for h in handles], stats

        d3, s3 = run(pipe_cfg(depth=3))
        off, _ = run(None)
        assert d3 == off
        hist = s3["pipeline"]["depth_hist"]
        assert hist.get("3", 0) > 0          # reached 3 in flight
        assert all(int(k) <= 3 for k in hist)  # never past the bound

    def test_depth1_reconciles_every_chunk(self):
        """depth=1 disables the carried dispatch entirely — every chunk is
        reconciled before the next dispatch, streams unchanged."""
        eng, _ = make_echo_engine(pipe_cfg(depth=1))
        handles = drive_wave(eng)
        stats = eng.get_stats()
        eng.stop()
        ctl, _ = make_echo_engine(None)
        ctl_handles = drive_wave(ctl)
        assert ([h.result.tokens for h in handles]
                == [h.result.tokens for h in ctl_handles])
        assert list(stats["pipeline"]["depth_hist"]) == ["1"]

    def test_off_switch_byte_identical(self):
        """enabled=false restores the pre-pipeline engine exactly: the
        executor's futures API is hidden, no completion threads spawn,
        step/scheduling counters and streams match an engine built
        without the subsystem."""
        def run(pipe):
            eng, ex = make_echo_engine(pipe)
            handles = drive_wave(eng)
            out = ([h.result.tokens for h in handles], eng.steps,
                   eng.get_stats().get("pipeline"))
            comp = eng._completion
            eng.stop()
            return out, ex, comp

        off, ex_off, comp_off = run(pipe_cfg(enabled=False))
        ctl, ex_ctl, comp_ctl = run(None)
        assert off == ctl
        assert off[2] is None                   # no pipeline stats block
        assert ex_off.decode_chunk_start is None
        assert ex_off.mixed_chunk_start is None
        assert comp_off is None and comp_ctl is None


class TestCompletionExecutor:
    @pytest.mark.parametrize("workers", [1, 2])
    def test_stream_order_and_done_after_tokens(self, workers):
        """Per-request token order is the committed order, the handle
        completes only after every token callback ran, and callbacks
        run on completion threads — never the dispatching one."""
        eng, _ = make_echo_engine(pipe_cfg(workers=workers))
        streams = {}
        threads = set()
        done_after = {}

        def cb(rid):
            def on_token(t):
                threads.add(threading.current_thread().name)
                streams.setdefault(rid, []).append(t)
            return on_token

        handles = []
        for i, (prompt, prio) in enumerate(WAVE):
            h = eng.submit(GenRequest(id=f"s{i}", prompt=prompt,
                                      priority=prio, max_new_tokens=24),
                           on_token=cb(f"s{i}"))
            handles.append((f"s{i}", h))
            eng.step()
            eng.step()
        eng.run_until_idle()
        for rid, h in handles:
            assert h.wait(5.0)
            done_after[rid] = streams.get(rid, [])
            assert h.result.tokens == done_after[rid]
        assert threads
        assert all(t.startswith("completion-") for t in threads), threads
        eng.stop()

    def test_inline_callbacks_with_pipeline_off(self):
        """Off switch: callbacks stay on the stepping thread (the
        pre-pipeline behavior) and no completion pool exists."""
        eng, _ = make_echo_engine(None)
        seen = []
        h = eng.submit(GenRequest(id="x", prompt="inline tokens",
                                  max_new_tokens=8),
                       on_token=lambda t: seen.append(
                           threading.current_thread().name))
        eng.run_until_idle()
        assert h.result is not None
        assert seen and all(n == threading.current_thread().name
                            for n in seen)
        assert eng._completion is None


class TestCancellationPreemption:
    def test_cancel_with_chunk_in_flight(self):
        """A cancel landing while chunks are dispatched is acted on at
        the fresh-dispatch path only: the stale futures' tokens are
        dropped with the row, no slot or page leaks."""
        eng, _ = make_echo_engine(pipe_cfg(), delay=0.001)
        doomed = eng.submit(GenRequest(id="doomed",
                                       prompt="cancel me mid flight " * 4,
                                       max_new_tokens=48))
        keep = eng.submit(GenRequest(id="keep", prompt="steady " * 6,
                                     max_new_tokens=32))
        for _ in range(30):
            eng.step()
            if eng._chunk_inflight is not None:
                break
        assert eng._chunk_inflight is not None
        doomed.cancel()
        eng.run_until_idle()
        assert doomed.result.finish_reason == "cancelled"
        assert keep.result.finish_reason in ("eos", "length")
        assert eng.allocator.used() == eng.allocator.pinned_pages()
        assert all(s is None for s in eng._slots)
        eng.stop()

    def test_preemption_equivalence_single_slot(self):
        """Slot preemption with the pipeline in flight is deferred to
        the reconcile (rows on device are untouchable), then runs —
        streams identical to the synchronous path."""
        def run(pipe):
            eng, _ = make_echo_engine(pipe, slots=1)
            low = eng.submit(GenRequest(
                id="low", prompt="background work " * 4,
                priority=Priority.LOW, max_new_tokens=48))
            for _ in range(6):
                eng.step()
            rt = eng.submit(GenRequest(
                id="rt", prompt="urgent realtime request",
                priority=Priority.REALTIME, max_new_tokens=8))
            eng.run_until_idle()
            eng.stop()
            return low.result.tokens, rt.result.tokens

        assert run(pipe_cfg()) == run(None)


class TestFullBatchRule:
    """What ``_can_fill`` allows depends on one observable: is every
    row taken. With a row free the pipeline's old rule holds line for
    line; with none, nobody waiting can be seated sooner by a fetch,
    unless they may preempt."""

    def test_a_free_row_keeps_the_parents_dispatches(self):
        """The open-loop cells' guarantee: with a row free at every
        decision, the programs dispatched and the chunks in flight at
        each dispatch are those of the rule the pipeline had before
        (no fill while anything is pending, ingested or not, or a
        slice is left to run) — here installed in place of
        ``_fill_refusal`` and run over the same arrivals."""
        def run(parents_rule):
            eng, _ = make_echo_engine(pipe_cfg(), mixed=mixed_cfg(),
                                      slots=4)
            if parents_rule:
                def refusal():
                    if len(eng._inflight) >= eng._pipe_depth:
                        return "depth"
                    if (eng._has_scheduling_work()
                            or eng._mixed_work_waiting()):
                        return "free_slot"
                    if eng._geometry_changed(eng._inflight[-1]):
                        return "geometry"
                    return None
                eng._fill_refusal = refusal
            toks, _ = closed_loop(eng, clients=3, per_client=4,
                                  new_step=5)
            seen, stats = dispatches(eng), eng.get_stats()
            eng.stop()
            return toks, seen, stats

        toks, seen, stats = run(False)
        ref_toks, ref_seen, _ = run(True)
        assert toks == ref_toks
        assert seen == ref_seen
        # the run had fills, fresh mixed chunks and refusals in it, and
        # never a carried mixed chunk (a row was always free)
        assert ("decode_chunk", 2) in seen and ("mixed_chunk", 1) in seen
        assert ("mixed_chunk", 2) not in seen
        why = stats["pipeline"]["fill_refusals"]
        assert why.get("free_slot", 0) > 0
        assert not why.get("urgent_pending") and not why.get("pages")

    def test_realtime_arrival_drains_a_full_batch_within_one_chunk(self):
        """A waiter that may preempt stops the fill at once: the one
        chunk left in flight is reconciled in the next step, and that
        reconcile seats it in the row of the least urgent sequence."""
        eng, _ = make_echo_engine(pipe_cfg(), mixed=mixed_cfg(), slots=2)
        low = [eng.submit(GenRequest(
            id=f"low{i}", prompt=f"background {i} " + "work " * 10,
            priority=Priority.LOW, max_new_tokens=56)) for i in range(4)]
        for _ in range(200):
            eng.step()
            if (eng.pipeline_depth_hist[2] >= 4 and eng._pending
                    and len(eng._inflight) == 1
                    and all(s is not None and s.prefilled
                            for s in eng._slots)):
                break
        assert eng.pipeline_depth_hist[2] >= 4   # full batch, depth 2
        assert all(s is not None and s.prefilled for s in eng._slots)
        rt = eng.submit(GenRequest(id="rt", prompt="urgent realtime",
                                   priority=Priority.REALTIME,
                                   max_new_tokens=8))
        before = dict(eng.fill_refusals)
        eng.step()
        assert eng.fill_refusals["urgent_pending"] \
            == before["urgent_pending"] + 1
        assert eng.preemptions == {"slot": 1, "release": 0}
        assert any(s is not None and s.req.id == "rt" for s in eng._slots)
        eng.run_until_idle()
        assert rt.result.finish_reason in ("eos", "length")
        for h in low:                      # the victim resumed: no loss
            assert h.result.finish_reason in ("eos", "length")
            assert len(h.result.tokens) > 0
        ctl, _ = make_echo_engine(None, mixed=mixed_cfg(), slots=2)
        ctl_low = [ctl.submit(GenRequest(
            id=f"low{i}", prompt=f"background {i} " + "work " * 10,
            priority=Priority.LOW, max_new_tokens=56)) for i in range(4)]
        ctl.run_until_idle()
        assert ([h.result.tokens for h in low]
                == [h.result.tokens for h in ctl_low])
        eng.stop()
        ctl.stop()

    @staticmethod
    def _full_pipeline(eng, clients=4):
        """Closed loop by hand up to a full batch at depth 2 with
        requests pending; returns the handles."""
        hs = [eng.submit(GenRequest(
            id=f"p{i}", prompt=f"page hungry {i} " + "tok " * 6,
            priority=Priority.LOW, max_new_tokens=60))
            for i in range(clients)]
        for _ in range(200):
            eng.step()
            if (eng.pipeline_depth_hist[2] >= 2 and eng._pending
                    and all(s is not None and s.prefilled
                            for s in eng._slots)):
                return hs
        raise AssertionError("no full batch at depth 2")

    def test_carried_chunk_evicts_what_nobody_holds(self):
        """The free list empty and zero-reference radix leaves in the
        tree: a carried dispatch evicts them for its rows' next pages
        and proceeds — no reconcile for want of pages."""
        eng, _ = make_echo_engine(
            pipe_cfg(), mixed=mixed_cfg(), slots=2,
            prefix_cache=PrefixCacheConfig(enabled=True))
        for i in range(3):                 # leaves nobody holds
            eng.submit(GenRequest(id=f"seed{i}", max_new_tokens=4,
                                  prompt=f"seed number {i} " + "leaf " * 12))
        eng.run_until_idle()
        leaves = eng._prefix_cache.get_stats()["pages"]
        assert leaves >= 6
        hs = self._full_pipeline(eng)
        held = eng.allocator.alloc(eng.allocator.available())
        assert held and eng.allocator.available() == 0
        deep0 = eng.pipeline_depth_hist[2]
        for _ in range(12):
            eng.step()
        assert eng._prefix_cache.get_stats()["evicted_pages"] > 0
        assert eng.fill_refusals["pages"] == 0
        assert eng.pipeline_depth_hist[2] >= deep0 + 10
        assert eng.preemptions == {"slot": 0, "release": 0}
        eng.allocator.free(held)
        eng.run_until_idle()
        assert all(h.result.finish_reason in ("eos", "length")
                   for h in hs)
        eng.stop()

    def test_carried_chunk_sheds_nothing(self):
        """Only seated and pending sequences' pages are left: the
        carried dispatch answers None (``pages``), and no sequence —
        seated, or pending with pages after a slot preemption — has
        lost a page or its row to it."""
        eng, _ = make_echo_engine(pipe_cfg(), mixed=mixed_cfg(), slots=2)
        hs = self._full_pipeline(eng, clients=3)
        # A pending sequence that holds pages: a realtime arrival takes
        # the least urgent row, the victim keeps its KV.
        hs.append(eng.submit(GenRequest(
            id="rt", prompt="urgent " * 3, priority=Priority.REALTIME,
            max_new_tokens=60)))
        for _ in range(200):
            eng.step()
            if (any(q.pages for _, _, q in eng._pending)
                    and all(s is not None and s.prefilled
                            for s in eng._slots)
                    and len(eng._inflight) == 1):
                break
        assert any(q.pages for _, _, q in eng._pending)
        held = eng.allocator.alloc(eng.allocator.available())
        everyone = ([s for s in eng._slots]
                    + [q for _, _, q in eng._pending])
        calls = []
        inner = eng._dispatch_carried

        def watched(infl):
            before = [(q.slot, list(q.pages)) for q in everyone]
            out = inner(infl)
            calls.append((out is None and eng._fill_stopped,
                          before == [(q.slot, list(q.pages))
                                     for q in everyone]))
            return out

        eng._dispatch_carried = watched
        for _ in range(40):
            eng.step()
            if ("pages", True) in calls or ("pages", False) in calls:
                break
        assert ("pages", True) in calls and ("pages", False) not in calls
        assert eng.fill_refusals["pages"] >= 1
        eng._dispatch_carried = inner
        eng.allocator.free(held)
        eng.run_until_idle()
        assert all(h.result.finish_reason in ("eos", "length")
                   for h in hs)
        eng.stop()

    def test_eviction_for_one_universe_stops_when_it_cannot_help(self):
        """A dp mesh, every leaf nobody holds in universe 0, both
        free lists empty: for a row of universe 0 the eviction finds
        its pages; for a row of universe 1 it ends after the one pass
        that freed pages only elsewhere (neither rung knows of
        universes) and leaves the rest of the cache alone."""
        eng, _ = make_echo_engine(
            pipe_cfg(), mixed=mixed_cfg(), slots=4, dp=2,
            prefix_cache=PrefixCacheConfig(enabled=True))
        per = eng.allocator.pages_per_shard
        for i in range(3):       # one at a time: row 0, universe 0
            eng.submit(GenRequest(id=f"seed{i}", max_new_tokens=4,
                                  prompt=f"seed number {i} " + "leaf " * 12))
            eng.run_until_idle()
        leaves = eng._prefix_cache.get_stats()["pages"]
        assert leaves >= 6
        assert eng.allocator.available_by_shard() == [per - 1 - leaves, per]
        held = [eng.allocator.alloc(eng.allocator.available(shard=d),
                                    shard=d) for d in (0, 1)]
        assert eng.allocator.available() == 0
        assert eng._evict_unheld(2, 1) is False
        assert eng.allocator.available_by_shard() == [2, 0]
        assert eng._prefix_cache.get_stats()["pages"] == leaves - 2
        assert eng._evict_unheld(3, 0) is True
        assert eng.allocator.available_by_shard() == [3, 0]
        for h in held:
            eng.allocator.free(h)
        eng.stop()

    def test_a_row_ended_inside_a_chunk_trims_into_its_own_universe(self):
        """A row commits up to ``decode_chunk`` tokens a fetch and its
        chunks' budgets — the carried one's too — are backed with pages
        ahead. When its stream ends inside a chunk, the pin keeps the
        pages of what was written and the rest go back to the free list
        of the row's OWN universe of a dp mesh: none leaks to the
        other one, where no row of this one could use it."""
        eng, _ = make_echo_engine(pipe_cfg(), mixed=mixed_cfg(), slots=4,
                                  dp=2)
        before = eng.allocator.available_by_shard()
        hs = [eng.submit(GenRequest(
                  id=f"r{i}", conversation_id=f"conv{i}", max_new_tokens=60,
                  prompt=f"row {i} " + "says so " * (2 + i)))
              for i in range(4)]
        eng.run_until_idle()
        assert [h.result.finish_reason for h in hs] == ["eos"] * 4
        assert eng.pipeline_depth_hist[2] > 0      # chunks were carried
        ps = eng.spec.page_size
        pinned = [0, 0]
        for i, h in enumerate(hs):
            kv = eng._conv_cache[f"conv{i}"]
            # the echo's EOS fell inside a chunk: a budget ran past it
            assert len(h.result.tokens) % 4
            assert len(kv.pages) == eng.allocator.pages_for(kv.length, ps)
            (shard,) = {eng.allocator.shard_of(p) for p in kv.pages}
            pinned[shard] += len(kv.pages)
        assert min(pinned) > 0                     # both universes used
        assert eng.allocator.available_by_shard() == [
            before[d] - pinned[d] for d in (0, 1)]
        assert eng.allocator.used() == eng.allocator.pinned_pages()
        eng.stop()

    @pytest.mark.parametrize("clients", [6, 12])
    def test_a_row_at_its_block_tables_end_leaves_a_full_batch(self,
                                                               clients):
        """Liveness under a full batch: a request that runs into its
        block table's capacity (100 prompt tokens + 28 of 64 asked for
        = 128) ends there with ``length``, in the step depth 1 ends it
        in — it does not hold its row until the pipeline happens to
        drain, which with more clients than rows it may never do. No
        row is ever left seated with no step to take."""
        def run(depth):
            eng, _ = make_echo_engine(pipe_cfg(depth=depth),
                                      mixed=mixed_cfg())
            cap = eng.spec.max_pages_per_seq * eng.spec.page_size
            live, sent, ended, toks = {}, dict.fromkeys(range(clients), 0), {}, {}

            def send(c):
                k = sent[c]
                sent[c] += 1
                text = f"client {c} request {k} " + "x" * 100
                live[c] = (k, eng.submit(GenRequest(
                    id=f"c{c}k{k}", priority=Priority.LOW,
                    prompt=text[:100 if c == 0 else 24 + c],
                    max_new_tokens=64 if c == 0 else 40 + 3 * c)))

            for c in range(clients):
                send(c)
            for step in range(2000):
                eng.step()
                eng._drain_completions()
                assert not any(s is not None and s.prefilled
                               and s.pos >= cap for s in eng._slots)
                for c, (k, h) in list(live.items()):
                    if h.done:
                        ended[c, k] = step
                        toks[c, k] = (h.result.finish_reason,
                                      h.result.tokens)
                        del live[c]
                        if sent[c] < 3:
                            send(c)
                if not live:
                    break
            assert not live
            deep = eng.pipeline_depth_hist[2]
            eng.stop()
            return ended, toks, deep

        ended, toks, deep = run(2)
        ref_ended, ref_toks, _ = run(1)
        assert toks == ref_toks
        assert all(toks[0, k][0] == "length" and len(toks[0, k][1]) == 29
                   for k in range(3))
        assert ended[0, 0] <= ref_ended[0, 0] + 1
        assert max(ended.values()) <= max(ref_ended.values()) * 1.25
        assert deep > 20                    # and the batch WAS full

    def test_a_row_only_the_host_can_end_stops_the_fill(self):
        """Should a seated row with nothing in flight be unable to
        step all the same (here: put at its capacity by hand), the
        carried dispatch gives up (``row_ended``) instead of skipping
        the row for ever, and the reconcile that follows ends it."""
        eng, _ = make_echo_engine(pipe_cfg(), mixed=mixed_cfg(), slots=2)
        hs = self._full_pipeline(eng)
        while len(eng._inflight) != 1 or not all(
                s is not None and s.prefilled for s in eng._slots):
            eng.step()
        stuck = eng._slots[0]
        assert eng._inflight[0].seqs[0] is stuck
        eng._inflight[0].budgets[0] = 0    # nothing of it in flight
        stuck.pos = eng.spec.max_pages_per_seq * eng.spec.page_size
        assert eng._dispatch_carried(eng._inflight[-1]) is None
        assert eng.fill_refusals["row_ended"] == 1
        eng.step()
        eng.step()
        eng._drain_completions()   # ``done`` is the completion thread's
        assert stuck.slot is None and stuck.handle.done
        assert stuck.handle.result.finish_reason == "length"
        eng.run_until_idle()
        assert all(h.done for h in hs)
        eng.stop()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
class TestCrashRecovery:
    @pytest.fixture(autouse=True)
    def _chaos_reset(self):
        yield
        chaos.configure(None)

    def test_crash_with_two_chunks_in_flight_zero_loss_zero_dup(self):
        """Chaos ``engine.step`` crash while TWO chunks are dispatched
        (depth-3 steady state): the supervisor recovers every snapshot,
        the queued completions drain before handles are re-failed
        (zero duplicate), the stream stays a monotone prefix, and a
        retry completes cleanly (zero loss)."""
        inj = chaos.configure(ChaosConfig(enabled=True, seed=21))
        checker = InvariantChecker()
        eng, _ = make_echo_engine(pipe_cfg(depth=3), delay=0.001)
        sup = EngineSupervisor(eng, config=SupervisorConfig(),
                               enable_metrics=False)
        h = eng.submit(GenRequest(id="s0",
                                  prompt="stream me through a crash " * 3,
                                  max_new_tokens=48),
                       on_token=checker.on_token("s0"))
        checker.submitted("s0")
        # Drive synchronously until the pipeline is 3-deep-capable and
        # holds TWO dispatched chunks between steps (depth-3 steady
        # state), with tokens already streamed.
        for _ in range(200):
            eng.step()
            if (len(eng._inflight) >= 2
                    and len(checker._streams.get("s0", [])) >= 3):
                break
        assert len(eng._inflight) >= 2
        eng._drain_completions()
        assert len(checker._streams.get("s0", [])) >= 3
        # Arm the crash and hand the engine to its loop thread: the
        # FIRST threaded step dies with both chunks in flight.
        inj.add_rule("engine.step", kind="crash", times=1)
        eng.start()
        import time as _t
        deadline = _t.time() + 5.0
        while eng.running and _t.time() < deadline:
            _t.sleep(0.01)
        assert not eng.running
        assert sup.check_once()            # detect + recover + restart
        assert not eng._inflight           # every snapshot dropped
        assert h.wait(2.0)
        assert h.result.finish_reason == "error"
        checker.failed("s0")
        checker.completed("s0", tokens=h.result.tokens)
        checker._terminal["s0"].remove("completed")  # monotone check only
        # Retry (new id) completes on the restarted, still-pipelined
        # engine.
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


    def test_crash_with_a_carried_mixed_chunk_in_flight(self):
        """The crash lands with two chunks dispatched under a full
        batch, a MIXED chunk that started from the carry among them
        (prompt slices on the device queue, their sequences latched):
        every request the dead loop owned fails over exactly once, the
        latches are cleared, no page or row leaks, and the restarted
        engine serves the retries."""
        inj = chaos.configure(ChaosConfig(enabled=True, seed=27))
        eng, ex = make_echo_engine(pipe_cfg(depth=3), mixed=mixed_cfg(),
                                   slots=2, delay=0.001)
        sup = EngineSupervisor(eng, config=SupervisorConfig(),
                               enable_metrics=False)
        carried = []
        inner = ex.mixed_chunk_start

        def watched(*a, carry=None, **kw):
            h = inner(*a, carry=carry, **kw)
            if carry is not None:
                carried.append(h)
            return h

        ex.mixed_chunk_start = watched
        hs = [eng.submit(GenRequest(
            id=f"m{i}", prompt=f"crash under load {i} " + "pad " * 8,
            priority=Priority.LOW, max_new_tokens=40 + 7 * i))
            for i in range(5)]
        for _ in range(400):
            eng.step()
            if (len(eng._inflight) >= 2 and carried
                    and any(c.handle is carried[-1]
                            for c in eng._inflight)):
                break
        assert any(c.handle is carried[-1] for c in eng._inflight)
        assert any(s is not None and s.mixed_pending
                   for s in eng._slots)
        done_before = [h.done for h in hs]
        inj.add_rule("engine.step", kind="crash", times=1)
        eng.start()
        import time as _t
        deadline = _t.time() + 5.0
        while eng.running and _t.time() < deadline:
            _t.sleep(0.01)
        assert not eng.running
        assert sup.check_once()
        assert not eng._inflight
        for h, was_done in zip(hs, done_before):
            assert h.wait(2.0)
            if was_done:
                assert h.result.finish_reason in ("eos", "length")
            else:
                assert h.result.finish_reason == "error"
        assert all(s is None for s in eng._slots) and not eng._pending
        assert eng.allocator.used() == eng.allocator.pinned_pages()
        retry = [eng.submit(GenRequest(
            id=f"m{i}-retry", prompt=f"crash under load {i} " + "pad " * 8,
            priority=Priority.LOW, max_new_tokens=40 + 7 * i))
            for i in range(5)]
        for h in retry:
            assert h.wait(10.0)
            assert h.result.finish_reason in ("eos", "length")
        eng.stop()
        sup.stop()


class TestOverlapTelemetry:
    def test_overlap_measured_and_device_not_inflated(self):
        """With a simulated device delay, the pipeline's hidden
        wall-clock lands in overlapped_ms (ratio > 0) while summed
        step_device_ms stays ≤ the phase's wall-clock (no
        double-counting)."""
        import time as _t

        eng, _ = make_echo_engine(pipe_cfg(), delay=0.002,
                                  name="overlap-echo")
        t0 = _t.perf_counter()
        drive_wave(eng, max_new=32)
        wall_ms = (_t.perf_counter() - t0) * 1e3
        snap = eng._telemetry.snapshot()
        steps = snap["steps"]
        assert snap["pipeline_overlap_ratio"] > 0
        assert steps["overlapped_ms"]["total_ms"] > 0
        assert steps["device_ms"]["total_ms"] <= wall_ms
        assert eng.get_stats()["pipeline"]["overlap_ratio"] > 0
        eng.stop()

    def test_serial_path_reports_zero_overlap(self):
        eng, _ = make_echo_engine(None, name="serial-echo")
        drive_wave(eng)
        snap = eng._telemetry.snapshot()
        assert snap["pipeline_overlap_ratio"] == 0.0
        assert snap["steps"]["overlapped_ms"]["total_ms"] == 0.0
        eng.stop()

    def test_metric_families_exposed(self):
        from llmq_tpu.metrics.registry import exposition, get_metrics

        get_metrics()
        eng, _ = make_echo_engine(pipe_cfg(), delay=0.001, metrics=True,
                                  name="pipemetrics")
        drive_wave(eng, max_new=16)
        exp = exposition().decode()
        assert "llm_queue_step_overlapped_ms" in exp
        assert ('llm_queue_pipeline_overlap_ratio{engine="pipemetrics"}'
                in exp)
        eng.stop()

    def test_timed_fetch_overlap_attribution(self, monkeypatch):
        """Unit pin for the serial-attribution math: two chunks whose
        spans overlap split into novel device time + overlapped time;
        without dispatched_at the old serial split is exact. On a clock
        the test advances — a wait IS its length, whatever the worker's
        load — so the attribution's identities and order are exact:
        overlapped + novel = span, B hidden behind A, C with nothing to
        overlap reads 0.0."""
        from llmq_tpu.observability import device
        from llmq_tpu.observability.device import DeviceTelemetry

        class Clock:
            now = 100.0

            def perf_counter(self):
                return self.now

            time = perf_counter

        clock = Clock()
        monkeypatch.setattr(device, "time", clock)
        tel = DeviceTelemetry("tf-unit", metrics=False)

        class Out:
            def __init__(self, delay):
                self.delay = delay

            def block_until_ready(self):
                clock.now += self.delay

        class H:
            def __init__(self, delay):
                self.out = Out(delay)

            def fetch(self):
                clock.now += 0.002          # the readback
                return np.zeros(1)

        def near(x):
            return pytest.approx(x, abs=1e-9)

        # Chunk A: dispatched now, 20ms compute.
        t_dispatch = clock.now
        _, dev_a, rb_a, ov_a = tel.timed_fetch(H(0.02),
                                               dispatched_at=t_dispatch)
        assert (dev_a, rb_a, ov_a) == (near(0.02), near(0.002), 0.0)
        # Chunk B: dispatched BEFORE chunk A finished (its span of 18 ms
        # overlaps the attributed window) — the overlap is attributed,
        # not double-counted as device time: only the 1 ms past A's
        # window is novel.
        t_b = clock.now
        _, dev_b, _, ov_b = tel.timed_fetch(
            H(0.001), dispatched_at=t_dispatch + 0.005)
        span_b = t_b + 0.001 - (t_dispatch + 0.005)
        assert dev_b == near(0.001) and ov_b == near(0.017)
        assert dev_b + ov_b == near(span_b)
        assert ov_b > dev_b            # hidden behind chunk A's window
        # No dispatched_at → exact old behavior: wait is device time.
        _, dev_c, _, ov_c = tel.timed_fetch(H(0.003))
        assert dev_c == near(0.003)
        assert ov_c == 0.0


class TestHostStaging:
    def test_ring_rotation_and_fill(self):
        st = HostStaging(ring=3)
        bufs = [st.take("t", (4,), np.int32) for _ in range(3)]
        assert len({id(b) for b in bufs}) == 3     # distinct slots
        bufs[0][:] = 7
        again = st.take("t", (4,), np.int32)       # wraps to slot 0
        assert again is bufs[0]
        assert (again == 0).all()                  # re-zeroed
        ones = st.take("t2", (2,), np.int32, fill=1)
        assert (ones == 1).all()
        raw = st.take("t3", (2,), np.int32, fill=None)
        assert raw.shape == (2,)

    def test_arange_cached_readonly(self):
        st = HostStaging()
        a = st.arange(8)
        assert a is st.arange(8)
        assert not a.flags.writeable
        assert (a == np.arange(8)).all()

    def test_geometries_do_not_collide(self):
        st = HostStaging(ring=2)
        a = st.take("x", (4,), np.int32)
        b = st.take("x", (8,), np.int32)
        c = st.take("x", (4,), np.float32)
        assert a.shape == (4,) and b.shape == (8,)
        assert c.dtype == np.float32


# -- CPU-mode JAX equivalence --------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama3-tiny", max_seq_len=256, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def tiny_model_f32():
    """float32 weights and cache: a first token sampled by a mixed
    slice and one sampled by a bucket program agree to rounding, so
    runs that route prompts differently still compare token for token
    (in bf16 a near-tie between two logits flips with the route)."""
    import jax.numpy as jnp

    cfg = get_config("llama3-tiny", max_seq_len=256, vocab_size=512,
                     dtype=jnp.float32)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def make_jax_engine(tiny_model, pipe, *, slots=2, mixed=None,
                    prefix_cache=None, max_decode_steps=16):
    cfg, params = tiny_model
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=slots, page_size=8,
                     num_pages=96, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8)
    return InferenceEngine(ex, tok, enable_metrics=False,
                           max_decode_steps=max_decode_steps,
                           prefix_cache=prefix_cache, mixed_batch=mixed,
                           async_pipeline=pipe)


class TestJaxEquivalence:
    @pytest.mark.parametrize("depth", [2, 3])
    def test_full_batch_closed_loop_equivalence(self, tiny_model_f32,
                                                depth, monkeypatch):
        """Greedy CPU-mode JAX under a full batch: carried mixed
        chunks and rows that join behind their final slice through
        ``pf_first`` on the device decode depth 1's streams."""
        from llmq_tpu.engine.executor import MixedChunkHandle

        joins = []
        inner = MixedChunkHandle.pf_first_at
        monkeypatch.setattr(
            MixedChunkHandle, "pf_first_at",
            lambda self, i: joins.append(i) or inner(self, i))

        def run(d):
            eng = make_jax_engine(tiny_model_f32, pipe_cfg(depth=d),
                                  slots=3, mixed=mixed_cfg(),
                                  max_decode_steps=64)
            toks, why = closed_loop(eng, clients=5, per_client=3,
                                    filler="xy ", fill_mod=3, fill_base=0,
                                    new_base=14, new_step=9)
            stats = eng.get_stats()
            eng.stop()
            return toks, why, stats

        toks, why, stats = run(depth)
        n_joins = len(joins)
        ref, ref_why, _ = run(1)
        assert len(joins) == n_joins           # depth 1 never joins so
        assert n_joins > 0
        assert toks == ref and why == ref_why
        assert len(toks) == 15 and all(toks.values())
        hist = stats["pipeline"]["depth_hist"]
        deep = sum(v for k, v in hist.items() if int(k) >= 2)
        assert deep > sum(hist.values()) / 2, hist
        assert stats["mixed_batch"]["steps"] > 0

    def test_wave_with_preemption_streams_identical(self, tiny_model):
        """Greedy CPU-mode JAX: admission waves + a realtime arrival
        that preempts — identical per-request streams with the
        pipeline at depth 2 and 3 vs off."""
        def run(pipe):
            eng = make_jax_engine(tiny_model, pipe)
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
            out = [h.result.tokens for h in handles]
            stats = eng.get_stats()
            eng.stop()
            return out, stats

        off, _ = run(None)
        d2, s2 = run(pipe_cfg(depth=2))
        d3, _ = run(pipe_cfg(depth=3))
        assert d2 == off
        assert d3 == off
        assert s2["pipeline"]["overlap_ratio"] >= 0.0

    def test_mixed_prefix_continuation_equivalence(self, tiny_model):
        """Multi-turn conversations over the radix prefix cache with
        mixed batching — the pipelined engine decodes identically."""
        def run(pipe):
            eng = make_jax_engine(
                tiny_model, pipe, slots=3, mixed=mixed_cfg(),
                prefix_cache=PrefixCacheConfig(enabled=True))
            out = []
            for turn in range(2):
                handles = []
                for c in range(3):
                    handles.append(eng.submit(GenRequest(
                        id=f"t{turn}c{c}",
                        prompt=f" turn {turn} for conversation {c}",
                        conversation_id=f"conv{c}",
                        max_new_tokens=8)))
                    eng.step()
                eng.run_until_idle()
                out.append([h.result.tokens for h in handles])
            assert eng.prefix_hits > 0 or any(
                h.result.cached_tokens > 0 for h in handles)
            eng.stop()
            return out

        assert run(pipe_cfg()) == run(None)
