"""Waiting gets an owner (docs/observability.md "Loops and stalls"):
the loop watch of ``utils/profiling``, the ``engine.wait`` span of the
loop asleep, the chunk id on a chunk's three spans, the mark
``prefill_last_dispatched``. Fake clocks where a clock does; the echo
engine where an engine must run."""

import logging
import threading
import time

import pytest

from llmq_tpu.utils import profiling
from llmq_tpu.utils.profiling import (STALL_FACTOR, STALL_FLOOR_S,
                                      SpanRecorder)


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt
        return self.t


@pytest.fixture
def stalls():
    """The ``loop_stall`` lines logged while the test runs, as the
    fields each carries (the ``llmq`` loggers do not propagate)."""
    lines = []

    class _Tap(logging.Handler):
        def emit(self, record):
            if record.getMessage() == "loop_stall":
                lines.append(record.fields)

    tap = _Tap(level=logging.WARNING)
    logger = logging.getLogger("llmq.profiling")
    logger.addHandler(tap)
    yield lines
    logger.removeHandler(tap)


@pytest.fixture
def loops():
    """Open watches on one fake clock, closed when the test ends."""
    clock, rec, made = _Clock(), SpanRecorder(), []

    def make(name):
        w = rec.loop(name, clock=clock).open()
        made.append(w)
        return w

    make.clock, make.rec = clock, rec
    yield make
    for w in made:
        w.close()


def _beat_steadily(clock, watch, n=40, gap=0.010, **counts):
    for _ in range(n):
        clock.tick(gap)
        watch.beat(**counts)


class TestLoopWatch:
    def test_a_beats_account(self, loops):
        w = loops("acct")
        _beat_steadily(loops.clock, w, n=30, gap=0.010, popped=0)
        loops.clock.tick(0.100)
        w.beat(popped=3)
        acct = profiling.loops()["acct"]
        assert acct["beats"] == 31 and acct["overruns"] == 0
        # the running median sits within a step or two of the gap
        assert 8.0 <= acct["median_ms"] <= 13.0
        assert acct["longest_ms"] == pytest.approx(100.0)
        assert acct["longest_counts"] == {"popped": 3}
        assert acct["longest_at_perf"] == pytest.approx(
            loops.clock.t - 0.100)
        assert acct["last_beat_age_ms"] == 0.0

    def test_an_overrun_is_logged_once_with_the_other_loops_ages(
            self, loops, stalls):
        a, b, c = loops("stalled"), loops("beating"), loops("asleep")
        clock = loops.clock
        for _ in range(40):
            clock.tick(0.010)
            a.beat(pending=0)
            b.beat()
        c.rest()                      # in a wait without a bound
        for _ in range(50):           # a is held for 0.5 s; b goes on
            clock.tick(0.010)
            b.beat()
        t_end = clock.t
        a.beat(pending=2)
        assert len(stalls) == 1
        (line,) = stalls
        assert line["loop"] == "stalled" and line["pending"] == 2
        assert line["gap_ms"] == pytest.approx(500.0)
        assert line["end_perf"] == pytest.approx(t_end)
        assert line["start_perf"] == pytest.approx(t_end - 0.5)
        assert line["end_wall"] - line["start_wall"] == pytest.approx(0.5)
        # (every live loop of the process is there: other tests' too)
        ages = line["others_age_ms"]
        assert "stalled" not in ages
        assert (ages["beating"], ages["asleep"]) == (0.0, None)
        assert profiling.loops()["stalled"]["overruns"] == 1
        # it lifted the median: the same gap again is the loop's new
        # regime, and says nothing
        clock.tick(0.5)
        a.beat(pending=2)
        assert len(stalls) == 1
        # all loops gapping together: every age is the gap
        _beat_steadily(clock, a, n=60, gap=0.010)
        clock.tick(0.8)
        a.beat()
        assert len(stalls) == 2
        assert stalls[1]["others_age_ms"]["beating"] == pytest.approx(
            800.0 + 600.0 + 500.0)

    @pytest.mark.parametrize("steady,gap", [
        (0.002, 0.200),      # 100 x the median, under the floor
        (0.050, 0.900),      # over the floor, under 20 x the median
    ], ids=["under_the_floor", "under_the_factor"])
    def test_no_overrun_under_either_threshold(self, loops, stalls,
                                               steady, gap):
        w = loops("quiet")
        _beat_steadily(loops.clock, w, n=60, gap=steady)
        assert gap < STALL_FLOOR_S or gap < STALL_FACTOR * steady
        loops.clock.tick(gap)
        w.beat()
        assert stalls == []
        acct = profiling.loops()["quiet"]
        assert acct["overruns"] == 0
        assert acct["longest_ms"] == pytest.approx(gap * 1e3)

    def test_a_poll_is_judged_and_kept_out_of_the_median(self, loops,
                                                         stalls):
        w = loops("polling")
        clock = loops.clock
        for _ in range(30):                 # work: 100 ms an iteration
            clock.tick(0.100)
            w.beat(True)
        for _ in range(400):                # two idle seconds of polls
            clock.tick(0.005)
            w.beat(False)
        acct = profiling.loops()["polling"]
        assert acct["beats"] == 30
        assert 80.0 <= acct["median_ms"] <= 125.0
        clock.tick(1.0)                     # an ordinary long step
        w.beat(True)
        assert stalls == []
        clock.tick(3.0)                     # a poll that overslept
        w.beat(False)
        assert [s["gap_ms"] for s in stalls] == [pytest.approx(3000.0)]

    def test_a_named_wait_is_what_the_loop_was_inside(self, loops,
                                                      stalls):
        w = loops("waiter")
        clock = loops.clock
        _beat_steadily(clock, w, n=40, gap=0.010)
        clock.tick(0.002)
        with w.wait("sem"):
            assert profiling.loops()["waiter"]["inside"] == "sem"
            clock.tick(0.600)
        clock.tick(0.002)
        w.beat(popped=2, sem_free=0)
        (line,) = stalls
        assert line["inside"] == "sem" and line["sem_free"] == 0
        sem = profiling.loops()["waiter"]["waits"]["sem"]
        assert sem["count"] == 1 and sem["stalls"] == 1
        assert sem["max_ms"] == pytest.approx(600.0)
        assert sem["stall_ms"] == pytest.approx(604.0)
        assert profiling.loops()["waiter"]["inside"] is None

    def test_the_span_the_thread_was_inside_is_read_from_the_ring(
            self, loops, stalls):
        w = loops("spanned")
        clock, rec = loops.clock, loops.rec
        _beat_steadily(clock, w, n=40, gap=0.010)
        t0 = clock.t
        clock.tick(0.700)
        # (recorded when they close, as ``span()`` does; the innermost
        # one that holds half of the gap is named)
        rec.record("engine.step", t0, 0.700)
        rec.record("engine.commit", t0 + 0.1, 0.550)
        rec.record("engine.ingest", t0, 0.001)
        other = threading.Thread(target=lambda: rec.record(
            "engine.deliver", t0, 0.690))            # another thread's
        other.start()
        other.join()
        w.beat()
        assert stalls[0]["inside"] == "engine.commit"

    def test_a_collection_inside_the_gap_is_named(self, loops, stalls):
        """The garbage collector holds every thread; the line says how
        much of the gap it ran (its own clock: the collector's hook)."""
        import gc
        w = loops("collected")
        assert profiling._on_gc in gc.callbacks
        _beat_steadily(loops.clock, w, n=40, gap=0.010)
        profiling._on_gc("start", {"generation": 2})
        time.sleep(0.03)
        profiling._on_gc("stop", {"generation": 2})
        loops.clock.tick(0.5)
        w.beat()
        loops.clock.tick(0.010)
        w.beat()
        _beat_steadily(loops.clock, w, n=60, gap=0.010)
        loops.clock.tick(0.5)          # a gap with no collection in it
        w.beat()
        assert 25.0 <= stalls[0]["gc_ms"] < 500.0
        assert stalls[1]["gc_ms"] < 25.0

    def test_no_gap_is_counted_over_a_rest(self, loops, stalls):
        w = loops("pool")
        clock = loops.clock
        for _ in range(30):
            w.wake()
            clock.tick(0.001)          # a job
            w.rest()
            clock.tick(5.0)            # no job for five seconds
        assert stalls == []
        assert profiling.loops()["pool"]["last_beat_age_ms"] is None
        assert profiling.loops()["pool"]["longest_ms"] < 2.0
        w.close()
        assert "pool" not in profiling.loops()

    def test_a_beat_takes_no_lock_and_costs_microseconds(self):
        """A count, not a speed: the beat's own work on this CPU, with
        the three counts the engine hands it."""
        w = SpanRecorder().loop("cost")
        mu_before = profiling._LOOPS_MU.acquire(blocking=False)
        assert mu_before                       # held by this test ...
        try:
            t0 = time.perf_counter()
            for _ in range(20000):
                w.beat(pending=0, active=0, inflight=0)   # ... and free
            per_beat = (time.perf_counter() - t0) / 20000
        finally:
            profiling._LOOPS_MU.release()
        assert per_beat < 20e-6


# -- the engine ----------------------------------------------------------------


def _echo_engine(name, pipelined=True, mixed=None, slots=4, **kw):
    from llmq_tpu.core.config import AsyncPipelineConfig
    from llmq_tpu.engine import EchoExecutor, InferenceEngine
    from llmq_tpu.engine.tokenizer import ByteTokenizer
    tok = ByteTokenizer()
    ex = EchoExecutor(batch_size=slots, page_size=8, num_pages=256,
                      max_pages_per_seq=16, eos_id=tok.eos_id,
                      chunk_size=4, async_chunks=pipelined,
                      mixed_prefill_slices=2, mixed_slice_tokens=8, **kw)
    pipe = AsyncPipelineConfig(enabled=True) if pipelined else None
    return InferenceEngine(ex, tok, enable_metrics=False, name=name,
                           max_decode_steps=64, async_pipeline=pipe,
                           mixed_batch=mixed)


def _request(rid, n=30, max_new=12, **kw):
    from llmq_tpu.engine.engine import GenRequest
    return GenRequest(id=rid, prompt="p" * n, max_new_tokens=max_new, **kw)


class TestEngineWait:
    def test_one_span_over_a_stretch_of_many_polls(self):
        eng = _echo_engine("waits")
        eng.start()
        try:
            time.sleep(0.15)                     # ~30 polls, no work
            assert len(eng._prof) == 0           # the stretch is open
            h = eng.submit(_request("w1"))
            assert h.wait(10.0)
            time.sleep(0.10)
            acct = eng.get_stats()["loops"]["engine.waits"]
        finally:
            eng.stop()
        waits = [s for s in eng._prof.snapshot() if s.name == "engine.wait"]
        steps = [s for s in eng._prof.snapshot() if s.name == "engine.step"]
        # the idle stretch before the request, the one after it
        assert len(waits) == 2
        first, last = waits
        assert first.duration >= 0.14
        assert first.meta == {"pending": 0, "active": 0, "inflight": 0,
                              "woke": "arrival"}
        assert last.meta["woke"] == "stop"
        # a stretch ends before the step that has work begins
        assert first.start + first.duration <= steps[0].start
        assert steps[-1].start + steps[-1].duration <= last.start
        # each poll was the watch's named wait, none of them a beat
        assert acct["waits"]["idle"]["count"] >= 20
        assert acct["beats"] == len(steps)
        assert "engine.waits" not in profiling.loops()     # closed

    def test_a_capture_that_begins_inside_a_stretch_splits_it_once(
            self, monkeypatch):
        class _Ann:
            held = False

            def __init__(self, name, **kw):
                self.name = name

            @classmethod
            def is_enabled(cls):
                return cls.held

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

            def set_metadata(self, **kw):
                pass

        monkeypatch.setattr(profiling, "_annotation_cls", lambda: _Ann)
        eng = _echo_engine("split")
        eng.start()
        try:
            time.sleep(0.05)
            _Ann.held = True
            time.sleep(0.10)
        finally:
            eng.stop()
        woke = [s.meta["woke"] for s in eng._prof.snapshot()
                if s.name == "engine.wait"]
        assert woke == ["capture", "stop"]


class TestChunkId:
    @pytest.mark.parametrize("mixed", [False, True],
                             ids=["decode_chunks", "mixed_chunks"])
    def test_one_id_on_a_chunks_dispatch_fetch_and_commit(self, mixed):
        from llmq_tpu.core.config import MixedBatchConfig
        cfg = (MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                max_slices=2) if mixed else None)
        eng = _echo_engine(f"chunks-{mixed}", mixed=cfg)
        hs = [eng.submit(_request(f"c{i}", n=20 + 9 * i))
              for i in range(2)]
        for _ in range(3):
            eng.step()
        hs += [eng.submit(_request(f"c{i}", n=40)) for i in (2, 3, 4)]
        eng.run_until_idle()
        assert all(h.result.finish_reason == "length" for h in hs)
        spans = eng._prof.snapshot()
        by = {name: [s.meta["chunk"] for s in spans if s.name == name]
              for name in ("engine.dispatch", "engine.fetch",
                           "engine.commit")}
        # every dispatch has the next serial number, prefills included
        assert by["engine.dispatch"] == list(
            range(1, len(by["engine.dispatch"]) + 1))
        # the chunks are fetched and committed in the order they went
        assert by["engine.fetch"] == by["engine.commit"] == sorted(
            by["engine.fetch"])
        chunks = [s.meta["chunk"] for s in spans
                  if s.name == "engine.dispatch" and s.meta["steps"] > 0]
        assert by["engine.fetch"] == chunks and len(chunks) > 3
        if mixed:
            assert eng.get_stats()["mixed_batch"]["steps"] > 0
        # a chunk's dispatch ends before its fetch begins
        start = {(s.name, s.meta["chunk"]): s for s in spans
                 if s.name in by}
        for n in chunks:
            d, f, c = (start[(k, n)] for k in by)
            assert d.start + d.duration <= f.start <= c.start


class TestLastSliceMark:
    @pytest.mark.parametrize("path", ["bucket", "mixed"])
    def test_between_prefill_start_and_first_token(self, path):
        from llmq_tpu import observability
        from llmq_tpu.core.config import MixedBatchConfig
        cfg = (MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                max_slices=2) if path == "mixed" else None)
        eng = _echo_engine(f"mark-{path}", mixed=cfg)
        first = eng.submit(_request(f"{path}-a", n=20, max_new=40),
                           on_token=lambda t: None)
        for _ in range(4):
            eng.step()                # ``a`` decodes: the next one's
        late = eng.submit(_request(f"{path}-b", n=50, max_new=8),
                          on_token=lambda t: None)   # slices ride chunks
        eng.run_until_idle()
        assert (eng.get_stats().get("mixed_batch", {}).get("steps", 0)
                > 0) == (path == "mixed")
        for h in (first, late):
            m = h.marks
            assert (m["admitted"] <= m["prefill_start"]
                    <= m["prefill_last_dispatched"] <= m["first_token"])
        if path == "mixed":
            # 50 tokens at 8 a slice: the last slice went out chunks
            # after the first
            m = late.marks
            assert m["prefill_last_dispatched"] > m["prefill_start"]
        rec = observability.get_recorder()
        if rec.enabled:
            stages = [e.stage for e in rec.get(f"{path}-b").events]
            assert "prefill_last_dispatched" in stages
        from llmq_tpu.observability.recorder import STAGE_ORDER
        assert STAGE_ORDER.index("prefill_start") < STAGE_ORDER.index(
            "prefill_last_dispatched") < STAGE_ORDER.index("first_token")

    @pytest.mark.parametrize("cut", [0.9, None, 0.2],
                             ids=["with_the_cut", "without_it",
                                  "out_of_order"])
    def test_decompose_still_conserves(self, cut):
        from llmq_tpu.observability.critical_path import decompose
        from llmq_tpu.observability.recorder import Timeline, TraceEvent
        events = [("enqueued", 0.0), ("scheduled", 0.1),
                  ("dispatched", 0.2), ("admitted", 0.3),
                  ("prefill_start", 0.5), ("prefill_done", 1.0),
                  ("first_token", 1.0), ("decode_done", 2.0),
                  ("completed", 2.1)]
        if cut is not None:
            events.insert(5, ("prefill_last_dispatched", cut))
        tl = Timeline("r")
        for stage, ts in events:
            tl.events.append(TraceEvent(stage, ts, "h0", None))
        d = decompose(tl)
        assert sum(d["segments"].values()) == pytest.approx(d["total_s"])
        assert d["total_s"] == pytest.approx(2.1)
        # the cut divides "prefill" and adds nothing to it
        assert d["segments"]["prefill"] == pytest.approx(0.5)
        assert d["segments"]["admission"] == pytest.approx(0.3)
        if cut == 0.9:
            assert d["prefill_cut"] == {
                "slices_s": pytest.approx(0.4),
                "reconcile_s": pytest.approx(0.1)}
        else:
            assert "prefill_cut" not in d


# -- a stall made on purpose ---------------------------------------------------


class TestAStallMadeOnPurpose:
    def test_a_worker_behind_its_semaphore_is_named(self, stalls):
        """One concurrency slot, held for 0.5 s by the first message
        while the second is already popped: the dispatch loop blocks on
        the semaphore. One ``loop_stall`` line names the worker's loop
        and that wait; the engine's loop went on beating; and the
        second request's queue leg holds the half second."""
        from llmq_tpu import observability
        from llmq_tpu.core.config import default_config
        from llmq_tpu.core.types import Message, Priority
        from llmq_tpu.queueing.queue_manager import QueueManager
        from llmq_tpu.queueing.worker import Worker

        rec = observability.get_recorder()
        if not rec.enabled:
            pytest.skip("flight recorder disabled")
        cfg = default_config()
        cfg.queue.worker.max_concurrent = 1
        cfg.queue.worker.process_interval = 0.01
        cfg.queue.worker.max_batch_size = 8
        eng = _echo_engine("stall-engine")
        held = threading.Event()

        def process(ctx, msg):
            if msg.id == "stall-1":
                held.wait(0.5)
            eng.process_fn(ctx, msg)

        manager = QueueManager("stall-q", cfg)
        worker = Worker("stall-w", manager, process)
        eng.start()
        worker.start()
        try:
            time.sleep(0.3)          # the loop learns its own tick
            t_push = time.time()
            for rid in ("stall-1", "stall-2"):
                msg = Message(id=rid, content="p" * 24,
                              priority=Priority.NORMAL, timeout=30.0,
                              metadata={"max_tokens": 4})
                observability.record(rid, "enqueued",
                                     priority="normal")
                manager.push_message(msg)
            deadline = time.time() + 10.0
            while time.time() < deadline and not all(
                    (tl := rec.get(r)) is not None
                    and tl.first_ts("completed") is not None
                    for r in ("stall-1", "stall-2")):
                time.sleep(0.02)
            time.sleep(0.05)
            others = profiling.loops()
        finally:
            worker.stop()
            eng.stop()
        mine = [s for s in stalls if s["loop"] == "worker.stall-w"]
        assert len(mine) == 1, stalls
        (line,) = mine
        assert line["inside"] == "sem"
        assert 450.0 <= line["gap_ms"] <= 900.0
        assert line["popped"] == 2
        # the engine's loop was beating all along: the program's stall
        assert line["others_age_ms"]["engine.stall-engine"] < 100.0
        assert not [s for s in stalls if s["loop"] == "engine.stall-engine"]
        assert others["worker.stall-w"]["waits"]["sem"]["stalls"] == 1
        # ... and the benchmark's reader finds the half second in the
        # second request's queue leg
        import os
        import sys
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from benchmark.harness import waits
        st = {}
        for e in rec.get("stall-2").events:
            st.setdefault(e.stage, e.ts)
        taken = st.get("scheduled", st.get("dispatched"))
        assert 0.45 <= taken - st["enqueued"] <= 0.9
        request = {"id": "stall-2", "ok": True, "due": t_push,
                   "t_first": st["first_token"], "stages": st}
        legs = waits.tail_legs({"requests": [request]})
        assert legs["requests"] == 1
        assert 450.0 <= legs["queue"] <= 900.0
        assert sum(legs[k] for k in waits.LEGS) == pytest.approx(
            legs["ttft"], abs=1e-6)
