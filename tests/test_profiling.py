"""Tracing/profiling subsystem (SURVEY.md §5 — real code here, unlike
the reference's docs-only pprof/Jaeger recipes)."""

import os
import threading
import time
import types

import pytest

from llmq_tpu.utils import profiling
from llmq_tpu.utils.profiling import Span, SpanRecorder, trace


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: says whether a
    capture is held and records what was opened and closed."""

    held = True
    log: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs
        type(self).log.append(("init", name, kwargs))

    @classmethod
    def is_enabled(cls):
        return cls.held

    def __enter__(self):
        type(self).log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        type(self).log.append(("exit", self.name, exc[0]))
        return False

    def set_metadata(self, **kwargs):
        type(self).log.append(("metadata", self.name, kwargs))


@pytest.fixture
def fake_capture(monkeypatch):
    _FakeAnnotation.held = True
    _FakeAnnotation.log = []
    monkeypatch.setattr(profiling, "_annotation_cls",
                        lambda: _FakeAnnotation)
    return _FakeAnnotation


class TestSpanRecorder:
    def test_span_and_summary(self):
        rec = SpanRecorder()
        with rec.span("queue.pop"):
            pass
        with rec.span("queue.pop"):
            pass
        with rec.span("engine.dispatch", rows=3):
            pass
        s = rec.summary()
        assert s["queue.pop"]["count"] == 2
        assert s["engine.dispatch"]["count"] == 1
        assert s["engine.dispatch"]["mean_ms"] >= 0

    def test_capacity_bound(self):
        rec = SpanRecorder(capacity=10)
        for i in range(50):
            rec.record(f"s{i}", 0.0, 0.001)
        assert len(rec.snapshot()) == 10
        assert rec.snapshot()[-1].name == "s49"

    @pytest.mark.parametrize("counts", [{}, {"rows": 3, "program": "p"}],
                             ids=["bare", "counts"])
    def test_one_call_yields_ring_entry_and_annotation(self, fake_capture,
                                                       counts):
        """THE span primitive: one call site, two sinks — the ring the
        stats route reads, and (a capture being held) an annotation
        for the same interval with the counts as its arguments."""
        rec = SpanRecorder()
        with rec.span("engine.dispatch", **counts):
            time.sleep(0.001)
        (s,) = rec.snapshot()
        assert (s.name, s.meta) == ("engine.dispatch", counts or None)
        assert s.duration >= 0.001 and s.tid == threading.get_ident()
        assert fake_capture.log == [
            ("init", "engine.dispatch", counts),
            ("enter", "engine.dispatch"),
            ("exit", "engine.dispatch", None)]

    @pytest.mark.parametrize("held", [True, False],
                             ids=["capture_held", "no_capture"])
    def test_note_adds_counts_known_after_the_body(self, fake_capture,
                                                   held):
        """``note`` inside the body: the counts land in the ring's
        record beside those given at the call, and — a capture being
        held — in the annotation's arguments."""
        fake_capture.held = held
        rec = SpanRecorder()
        with rec.span("engine.fill", a=1) as sp:
            sp.note(dispatched=2, stopped="depth")
        (s,) = rec.snapshot()
        assert s.meta == {"a": 1, "dispatched": 2, "stopped": "depth"}
        meta = [e for e in fake_capture.log if e[0] == "metadata"]
        assert meta == ([("metadata", "engine.fill",
                          {"dispatched": 2, "stopped": "depth"})]
                        if held else [])

    def test_no_annotation_while_no_capture_is_held(self, fake_capture):
        fake_capture.held = False
        rec = SpanRecorder()
        with rec.span("engine.step", n=1):
            pass
        assert fake_capture.log == []          # not even constructed
        assert [s.name for s in rec.snapshot()] == ["engine.step"]
        assert not profiling.capture_held()
        fake_capture.held = True
        assert profiling.capture_held()

    def test_no_jax_in_the_process_no_annotation(self, monkeypatch):
        """A device-free backend must not import JAX for its spans."""
        monkeypatch.setattr(profiling, "sys",
                            types.SimpleNamespace(modules={}))
        rec = SpanRecorder()
        with rec.span("engine.step"):
            pass
        assert profiling._annotation_cls() is None
        assert not profiling.capture_held()
        assert len(rec) == 1

    @pytest.mark.parametrize("held", [True, False],
                             ids=["capture_held", "no_capture"])
    def test_body_errors_propagate(self, fake_capture, held):
        """(Was ``annotate``'s test.) The body's exception comes out
        untouched; the span is still closed and recorded."""
        fake_capture.held = held
        rec = SpanRecorder()
        with pytest.raises(ValueError, match="original"):
            with rec.span("x"):
                raise ValueError("original")
        assert [s.name for s in rec.snapshot()] == ["x"]
        if held:
            assert fake_capture.log[-1] == ("exit", "x", ValueError)

    def test_real_annotation_class_resolves_on_cpu(self):
        """(Was ``annotate``'s active-path test.) With JAX in the
        process the class is ``jax.profiler.TraceAnnotation``, no
        capture is held, and the body runs exactly once."""
        import jax
        ran = []
        rec = SpanRecorder()
        with rec.span("cpu-region", k=1):
            ran.append(1)
        assert ran == [1]
        assert profiling._annotation_cls() is jax.profiler.TraceAnnotation
        assert not profiling.capture_held()

    def test_cost_with_no_capture_held(self):
        """With no capture held a span is two clock reads, one
        ``is_enabled`` and a ring append: a few Python calls' worth.
        Bound generously (shared CI cores): 10 µs, best of five."""
        import jax  # noqa: F401 — the real is_enabled path
        rec = SpanRecorder()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(2000):
                with rec.span("engine.dispatch", rows=3, steps=8):
                    pass
            best = min(best, (time.perf_counter() - t0) / 2000)
        assert best < 10e-6, f"{best * 1e6:.2f} µs a span"

    def test_concurrent_record_snapshot(self):
        """The multi-worker serve path has N dispatch threads recording
        spans while the API stats route snapshots/summarizes — all
        three must interleave without losing the lock discipline (no
        RuntimeError from mutating the deque mid-copy, no torn
        summaries, ring bound respected throughout)."""
        import time as _time

        rec = SpanRecorder(capacity=256)
        stop = threading.Event()
        errors = []

        def worker(i):
            n = 0
            try:
                while not stop.is_set():
                    with rec.span(f"dispatch.{i}", seq=n):
                        pass
                    rec.record("engine.dispatch", 0.0, 0.001,
                               {"w": i})
                    n += 1
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def reader():
            try:
                while not stop.is_set():
                    snap = rec.snapshot()
                    assert len(snap) <= 256
                    summ = rec.summary()
                    for d in summ.values():
                        assert d["count"] >= 1
                    len(rec)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = ([threading.Thread(target=worker, args=(i,))
                    for i in range(4)]
                   + [threading.Thread(target=reader) for _ in range(2)])
        for t in threads:
            t.start()
        _time.sleep(0.4)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert not errors, errors
        assert len(rec.snapshot()) <= 256


class TestChromeExport:
    """``observability/chrome.py`` is the exporter of the ring
    (``SpanRecorder.dump_chrome_trace`` went: nothing called it)."""

    def _doc(self, spans):
        from llmq_tpu.observability.chrome import chrome_trace
        return [e for e in chrome_trace([], spans=spans,
                                        span_anchor=0.0)["traceEvents"]
                if e["ph"] == "X"]

    @pytest.mark.parametrize("meta", [{"foo": 1}, None],
                             ids=["counts", "bare"])
    def test_span_becomes_a_complete_event(self, meta):
        """(Was ``dump_chrome_trace``'s test.)"""
        rec = SpanRecorder()
        with rec.span("a", **(meta or {})):
            pass
        (ev,) = self._doc(rec.snapshot())
        s = rec.snapshot()[0]
        assert ev["name"] == "a" and ev["args"] == (meta or {})
        assert ev["ts"] == pytest.approx(s.start * 1e6)
        assert ev["dur"] == pytest.approx(s.duration * 1e6)

    def test_one_track_per_recording_thread(self):
        rec = SpanRecorder()
        with rec.span("engine.step"):
            with rec.span("engine.assemble"):
                pass
        t = threading.Thread(
            target=lambda: rec.record("engine.deliver", 0.0, 0.001))
        t.start()
        t.join()
        evs = {e["name"]: e["tid"] for e in self._doc(rec.snapshot())}
        assert evs["engine.step"] == evs["engine.assemble"]
        assert evs["engine.deliver"] != evs["engine.step"]
        # a hand-made span (no thread recorded) still exports
        assert self._doc([Span("x", 0.0, 1.0)])[0]["tid"] >= 2


#: The step's fixed vocabulary (docs/observability.md "The engine
#: step"): what may stand directly under what, on the engine thread.
STEP_CHILDREN = {"engine.ingest", "engine.admit", "engine.prefill_advance",
                 "engine.fill", "engine.resolve", "engine.reconcile",
                 "engine.assemble"}
PARENTS = {
    "engine.fetch": {"engine.reconcile"},
    "engine.commit": {"engine.reconcile"},
    "engine.dispatch": {"engine.assemble", "engine.fill",
                        "engine.prefill_advance"},
    # arrivals are serviced while a transfer is waited for
    "engine.ingest": {"engine.step", "engine.fetch", "engine.resolve"},
    "engine.admit": {"engine.step", "engine.fetch", "engine.resolve"},
    "engine.prefill_advance": {"engine.step", "engine.fetch",
                               "engine.resolve"},
    "engine.fill": {"engine.step"}, "engine.resolve": {"engine.step"},
    "engine.reconcile": {"engine.step"}, "engine.assemble": {"engine.step"},
}
#: Order of a step's own phases while a chunk is in flight.
PHASE_RANK = {"engine.fill": 0, "engine.resolve": 1, "engine.reconcile": 2,
              "engine.assemble": 3}


def _step_engine(pipelined=True, slots=4, chunk=4, metrics=False, **kw):
    from llmq_tpu.core.config import AsyncPipelineConfig
    from llmq_tpu.engine import EchoExecutor, InferenceEngine
    from llmq_tpu.engine.tokenizer import ByteTokenizer
    tok = ByteTokenizer()
    ex = EchoExecutor(batch_size=slots, page_size=8,
                      num_pages=kw.pop("num_pages", 256),
                      max_pages_per_seq=16, eos_id=tok.eos_id,
                      chunk_size=chunk, async_chunks=pipelined)
    pipe = AsyncPipelineConfig(enabled=True) if pipelined else None
    return InferenceEngine(ex, tok, enable_metrics=metrics,
                           name=kw.pop("name", "steptest"),
                           max_decode_steps=64, async_pipeline=pipe, **kw)


def _run_wave(eng, n=5, max_new=12):
    from llmq_tpu.engine.engine import GenRequest
    # Echo answers with the prompt and then EOS: prompts longer than
    # max_new, so every request ends "length" and no row budget is cut
    # short by an EOS.
    hs = [eng.submit(GenRequest(id=f"r{i}", prompt="p" * (20 + 7 * i),
                                max_new_tokens=max_new),
                     on_token=lambda t: None) for i in range(n)]
    eng.run_until_idle()
    assert all(h.result.finish_reason == "length" for h in hs)
    return hs


def _nest(spans):
    """[(span, parent name or None)] for the spans of one thread, by
    containment of their intervals."""
    out, stack = [], []
    for s in sorted(spans, key=lambda s: (s.start, -s.duration)):
        while stack and s.start >= stack[-1].start + stack[-1].duration:
            stack.pop()
        out.append((s, stack[-1].name if stack else None))
        stack.append(s)
    return out


class TestEngineStep:
    def test_a_step_with_no_work_opens_no_span(self):
        eng = _step_engine()
        assert eng.step() is False
        assert len(eng._prof) == 0

    def test_vocabulary_nested_and_in_order(self):
        eng = _step_engine()
        _run_wave(eng)
        spans = eng._prof.snapshot()
        steps = [s for s in spans if s.name == "engine.step"]
        assert steps
        engine_tid = steps[0].tid
        on_engine = [s for s in spans if s.tid == engine_tid]
        off_engine = [s for s in spans if s.tid != engine_tid]
        # the completion pool delivers; the engine thread never does
        assert off_engine and {s.name for s in off_engine} == {
            "engine.deliver"}
        assert "engine.deliver" not in {s.name for s in on_engine}
        seen = set()
        phases = {}
        for s, parent in _nest(on_engine):
            seen.add(s.name)
            if s.name == "engine.step":
                assert parent is None
                phases[id(s)] = cur = []
                continue
            assert parent in PARENTS[s.name], (s.name, parent)
            if parent == "engine.step" and s.name in PHASE_RANK:
                cur.append(s.name)
        # (echo has no async prefill, so nothing to resolve: the JAX
        # engine below shows ``engine.resolve``)
        assert seen == (STEP_CHILDREN - {"engine.resolve"}) | {
            "engine.step", "engine.fetch", "engine.commit",
            "engine.dispatch"}
        for names in phases.values():
            ranks = [PHASE_RANK[n] for n in names]
            assert ranks == sorted(ranks), names
        # fetch, then commit, inside every reconcile
        inner = [(s.name, p) for s, p in _nest(on_engine)
                 if p == "engine.reconcile"]
        assert [n for n, _ in inner][:2] == ["engine.fetch",
                                             "engine.commit"]

    @pytest.mark.parametrize("pipelined", [True, False],
                             ids=["pipelined", "sync"])
    def test_counts_are_taken_at_the_dispatch(self, pipelined):
        eng = _step_engine(pipelined=pipelined)
        hs = _run_wave(eng)
        disp = [s.meta for s in eng._prof.snapshot()
                if s.name == "engine.dispatch"]
        chunks = [m for m in disp if m["steps"] > 0]
        prefills = [m for m in disp if m["steps"] == 0]
        st = eng.get_stats()
        # every token but each request's first (its prefill's sample)
        # was committed from a chunk row: the row-steps dispatched
        assert sum(m["row_steps"] for m in chunks) == (
            st["tokens_generated"] - len(hs))
        assert st["row_steps"] == sum(m["row_steps"] for m in chunks)
        # device_steps counts STEPS, decode_steps counts DISPATCHES
        assert st["device_steps"] == sum(m["steps"] for m in chunks)
        assert st["decode_steps"] == len(chunks)
        assert st["device_steps"] > st["decode_steps"]
        for m in chunks:
            assert m["program"] == "decode_chunk"
            assert 1 <= m["rows"] <= 4 and m["rows"] <= m["row_steps"]
            assert m["row_steps"] <= m["rows"] * m["steps"]
            assert m["context_tokens"] >= 20 * m["rows"]
            assert 1 <= m["inflight"] <= 2
        assert {m["program"] for m in prefills} == {"prefill"}
        assert sum(m["prefill_tokens"] for m in prefills) == sum(
            20 + 7 * i for i in range(len(hs)))
        # pages and tokens alive are walked only while a capture is held
        assert all("pages_live" not in m for m in disp)

    def test_live_kv_rides_the_dispatch_while_a_capture_is_held(
            self, fake_capture):
        eng = _step_engine()
        _run_wave(eng, n=3)
        chunks = [kw for op, _name, *rest in fake_capture.log
                  if op == "init" and _name == "engine.dispatch"
                  for kw in rest if kw["steps"] > 0]
        assert chunks
        for kw in chunks:
            assert kw["tokens_live"] >= kw["context_tokens"] > 0
            assert kw["pages_live"] * 8 >= kw["tokens_live"]
        names = {e[1] for e in fake_capture.log if e[0] == "init"}
        assert {"engine.step", "engine.reconcile", "engine.fetch",
                "engine.commit", "engine.assemble",
                "engine.deliver"} <= names

    @pytest.mark.parametrize("mark", ["first_token_out", "preempted"])
    def test_request_marks_reach_the_recorder(self, mark):
        from llmq_tpu import observability
        from llmq_tpu.core.types import Priority
        from llmq_tpu.engine.engine import GenRequest
        rec = observability.get_recorder()
        if not rec.enabled:
            pytest.skip("flight recorder disabled")
        eng = _step_engine(slots=1, name=f"marks-{mark}")
        low = eng.submit(GenRequest(id=f"low-{mark}", prompt="L" * 40,
                                    priority=Priority.LOW),
                         on_token=lambda t: None)
        eng.step()
        eng.step()
        eng.submit(GenRequest(id=f"rt-{mark}", prompt="R" * 4,
                              priority=Priority.REALTIME))
        eng.run_until_idle()
        assert low.result.text == "L" * 40
        stages = {e.stage: e.ts for e in rec.get(f"low-{mark}").events}
        assert mark in stages
        if mark == "first_token_out":
            # handed over after it was committed, before the end
            assert stages["first_token"] <= stages[mark] <= stages[
                "completed"]
            # no callback, nothing handed over: no mark
            assert mark not in {e.stage for e in
                                rec.get(f"rt-{mark}").events}
        else:
            assert eng.get_stats()["preemptions"] == {"slot": 1,
                                                      "release": 0}
            assert stages["admitted"] <= stages[mark] <= stages[
                "completed"]

    def test_page_release_preemption_is_counted_apart(self):
        from llmq_tpu.core.types import Priority
        from llmq_tpu.engine.engine import GenRequest
        # 7 usable pages of 8 tokens, two rows: the realtime arrival
        # can only get its pages by stripping the low runner's.
        eng = _step_engine(slots=2, num_pages=8, pipelined=False)
        low = eng.submit(GenRequest(id="low", prompt="L" * 20,
                                    priority=Priority.LOW))
        eng.step()
        rt = eng.submit(GenRequest(id="rt", prompt="R" * 20,
                                   priority=Priority.REALTIME))
        eng.run_until_idle(max_steps=2000)
        assert rt.result.text == "R" * 20 and low.result.text == "L" * 20
        assert eng.get_stats()["preemptions"]["release"] >= 1
        assert "preempted" in low.marks and "preempted" not in rt.marks

    def test_prometheus_children_are_bound_once(self):
        eng = _step_engine(metrics=True, name="bound-once")
        m = eng._m
        before = m("decode_steps")._value.get()
        _run_wave(eng, n=2)
        assert m("decode_steps") is m("decode_steps")
        assert m("generated_tokens", "normal") is m("generated_tokens",
                                                    "normal")
        assert m("decode_steps")._value.get() - before == eng.steps
        # a family this engine never touched gained no child here
        assert "spec_acceptance" not in {
            k if isinstance(k, str) else k[0] for k in m._kids}


class TestJaxEngineUnderACapture:
    """The tiny JAX engine on the CPU, warmed up, driven while a real
    profiler capture is held: the spans are on the profiler's clock
    with their counts, and the programs carry their names."""

    @pytest.fixture(scope="class")
    def captured(self, tmp_path_factory):
        import glob

        import jax
        from jax.profiler import ProfileData

        from llmq_tpu.core.config import (AsyncPipelineConfig,
                                          MixedBatchConfig)
        from llmq_tpu.engine import InferenceEngine
        from llmq_tpu.engine.engine import GenRequest
        from llmq_tpu.engine.executor import JaxExecutor
        from llmq_tpu.engine.tokenizer import ByteTokenizer
        from llmq_tpu.models.llama import get_config, init_params
        cfg = get_config("llama3-tiny", max_seq_len=256, vocab_size=512)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tok = ByteTokenizer()
        ex = JaxExecutor(cfg, params, batch_size=2, page_size=8,
                         num_pages=96, prefill_buckets=[16, 64],
                         eos_id=tok.eos_id, chunk_size=4,
                         mixed_prefill_slices=2, mixed_slice_tokens=8)
        ex.warmup()
        eng = InferenceEngine(
            ex, tok, enable_metrics=False, max_decode_steps=16,
            mixed_batch=MixedBatchConfig(enabled=True,
                                         prefill_token_budget=16,
                                         max_slices=2),
            async_pipeline=AsyncPipelineConfig(enabled=True))
        out = str(tmp_path_factory.mktemp("capture"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            for i, p in enumerate(["a long prompt that needs slicing up",
                                   "second prompt arrives", "third"]):
                eng.submit(GenRequest(id=f"c{i}", prompt=p,
                                      max_new_tokens=10),
                           on_token=lambda t: None)
                eng.step()
                eng.step()
            eng.run_until_idle()
        finally:
            jax.profiler.stop_trace()
            eng.stop()
        (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        lines = []      # one per host thread: [(name, start, dur, args)]
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for ln in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                       for e in ln.events if e.name.startswith("engine.")]
                if evs:
                    lines.append(evs)
        return ex, eng, lines

    def test_spans_land_on_the_profilers_clock_with_their_counts(
            self, captured):
        ex, eng, lines = captured
        (engine_line,) = [ln for ln in lines
                          if any(e[0] == "engine.step" for e in ln)]
        names = {e[0] for e in engine_line}
        assert STEP_CHILDREN | {"engine.fetch", "engine.commit",
                                "engine.dispatch"} <= names
        assert "engine.deliver" not in names
        assert any(e[0] == "engine.deliver" for ln in lines
                   if ln is not engine_line for e in ln)
        ring = {s.name for s in eng._prof.snapshot()}
        assert names <= ring          # one call site, both sinks
        disp = [e[3] for e in engine_line if e[0] == "engine.dispatch"]
        assert {"program", "steps", "rows", "row_steps", "inflight",
                "pages_live", "tokens_live", "context_tokens",
                "prefill_tokens"} <= set(disp[0])
        # every dispatch names a program of the executor's AOT set
        assert {d["program"] for d in disp} <= set(ex._aot)
        assert any(d["program"].startswith("prefill") for d in disp)
        assert any(d["steps"] > 0 and d["pages_live"] > 0 for d in disp)
        # a dispatch lies inside its step on that clock
        steps = [(e[1], e[1] + e[2]) for e in engine_line
                 if e[0] == "engine.step"]
        for e in engine_line:
            if e[0] == "engine.dispatch":
                assert any(a <= e[1] and e[1] + e[2] <= b
                           for a, b in steps)

    def test_warmup_spans_show_in_the_engines_profile(self, captured):
        """The warm-up opens ``engine.warmup.compile`` once a program in
        the executor's ring, and the engine built over that executor
        takes the ring as its own: the spans sit beside the step's in
        ``get_stats()["profile"]`` (``/api/v1/engine/stats``)."""
        ex, eng, _lines = captured
        prof = eng.get_stats()["profile"]
        assert prof["engine.warmup.compile"]["count"] == len(ex._aot)
        assert "engine.step" in prof and eng._prof is ex.spans

    def test_programs_carry_their_names(self, captured):
        ex, _eng, _lines = captured
        assert {"decode_chunk", "mixed_chunk", "prefill_b16",
                "prefill_b64"} <= set(ex._aot)
        for key, exe in ex._aot.items():
            assert f"jit_{key}" in exe.as_text()[:400], key
        assert ex.program_name("prefill", 9) == "prefill_b16"
        assert ex.program_name("prefill", 40) == "prefill_b64"
        assert ex.program_name("prefill_multi", 16) == "prefill_multi_b16"
        assert ex.program_name("mixed_chunk") == "mixed_chunk"
        assert ex.program_name("decode_chunk") == "decode_chunk"


class TestDeviceTrace:
    def test_noop_without_a_directory(self, monkeypatch):
        # ``trace`` reads no environment: the caller names the
        # directory, and with none given nothing is captured.
        monkeypatch.setenv("LLMQ_TRACE_DIR", "/nonexistent/never-made")
        with trace("unit", None):
            x = 1 + 1
        assert x == 2
        assert not os.path.exists("/nonexistent/never-made")

    def test_writes_trace_dir(self, tmp_path, monkeypatch):
        # bench.py's form: the ambient directory, read by the caller.
        from llmq_tpu.utils.profiling import trace_dir
        monkeypatch.setenv("LLMQ_TRACE_DIR", str(tmp_path))
        import jax
        import jax.numpy as jnp
        with trace("unit", trace_dir()):
            jnp.zeros(8).block_until_ready()
        out = tmp_path / "unit"
        assert out.exists()
        # jax.profiler writes a plugins/profile tree with trace files.
        found = [f for _, _, fs in os.walk(out) for f in fs]
        assert found, "profiler produced no files"

    def test_engine_stats_include_profile(self):
        from llmq_tpu.engine import EchoExecutor, InferenceEngine
        from llmq_tpu.engine.tokenizer import ByteTokenizer
        tok = ByteTokenizer()
        ex = EchoExecutor(batch_size=2, eos_id=tok.eos_id)
        eng = InferenceEngine(ex, tok, enable_metrics=False)
        from llmq_tpu.engine.engine import GenRequest
        h = eng.submit(GenRequest(id="r1", prompt="hi", max_new_tokens=4))
        eng.run_until_idle()
        assert h.done
        stats = eng.get_stats()
        assert "engine.dispatch" in stats["profile"]
        assert "engine.step" in stats["profile"]

    def test_explicit_dir_overrides_missing_env(self, tmp_path,
                                                monkeypatch):
        # The on-demand profile endpoint path: no ambient LLMQ_TRACE_DIR,
        # capture goes where the caller says.
        monkeypatch.delenv("LLMQ_TRACE_DIR", raising=False)
        import jax.numpy as jnp
        with trace("ondemand", dir=str(tmp_path)):
            jnp.zeros(4).block_until_ready()
        assert (tmp_path / "ondemand").exists()


class TestOnDemandProfile:
    def _server(self):
        from llmq_tpu.api.server import ApiServer
        from llmq_tpu.core.config import default_config
        return ApiServer(default_config())

    def test_single_flight_409_then_released(self, tmp_path,
                                             monkeypatch):
        """POST /api/v1/admin/profile: 202 with the trace path; a
        concurrent capture 409s; once the capture finishes the flight
        is released and the trace dir is readable (the acceptance
        criterion's single-flight contract). The output location is
        server-controlled (LLMQ_TRACE_DIR / tempdir) — a request-body
        path would be an arbitrary-write primitive."""
        import json as _json
        import time as _time

        from llmq_tpu.observability import device
        monkeypatch.setenv("LLMQ_TRACE_DIR", str(tmp_path))
        api = self._server()
        body = _json.dumps({"duration_ms": 100, "label": "t409",
                            "dir": "/definitely/not/honored"}).encode()
        status, out, _ = api.dispatch("POST", "/api/v1/admin/profile",
                                      body)
        assert status == 202, out
        # Body "dir" ignored; capture lands under the operator's dir.
        assert out["path"].startswith(str(tmp_path))
        status2, out2, _ = api.dispatch("POST", "/api/v1/admin/profile",
                                        b"{}")
        assert status2 == 409
        assert "already running" in out2["error"]
        # Bounded wait for release (profiler session start/stop on CPU
        # costs seconds; the capture itself is 100 ms).
        deadline = _time.time() + 60
        while _time.time() < deadline:
            if not device.profile_status()["active"]:
                break
            _time.sleep(0.1)
        st = device.profile_status()
        assert not st["active"], "capture never released the flight"
        assert st["last"]["label"] == "t409"
        found = [f for _, _, fs in os.walk(out["path"]) for f in fs]
        assert found, "on-demand capture produced no trace files"
        # Flight released: a new capture is accepted again.
        status3, out3, _ = api.dispatch(
            "POST", "/api/v1/admin/profile",
            _json.dumps({"duration_ms": 10}).encode())
        assert status3 == 202, out3
        deadline = _time.time() + 60
        while _time.time() < deadline:
            if not device.profile_status()["active"]:
                break
            _time.sleep(0.1)
        assert not device.profile_status()["active"]

    def test_bad_duration_is_400(self):
        api = self._server()
        status, out, _ = api.dispatch(
            "POST", "/api/v1/admin/profile",
            b'{"duration_ms": "soon"}')
        assert status == 400

    def test_status_route_reports_idle(self):
        api = self._server()
        status, out, _ = api.dispatch("GET", "/api/v1/admin/profile",
                                      b"")
        assert status == 200
        assert out["active"] in (False, True)


# -- ``slice_tokens`` of a mixed chunk: the rows the products run --------------

#: (prompt tokens of the packed plan, decode rows, slices, their width)
_PLANS = [(1, 4, 2, 8), (8, 4, 2, 8), (9, 4, 2, 8), (16, 4, 2, 8),
          (300, 64, 2, 512), (630, 64, 2, 512), (1000, 128, 4, 512),
          (1920, 128, 4, 512), (2048, 128, 4, 512), (200, 32, 2, 256)]


@pytest.mark.parametrize("family", ["llama", "deepseek_v3", "longcat_flash"])
@pytest.mark.parametrize("tokens,batch,slices,width", _PLANS)
def test_a_mixed_chunk_counts_its_live_tiles_rows(family, tokens, batch,
                                                  slices, width):
    """``slice_tokens`` of a mixed chunk is the live tiles' rows less
    the decode rows among them, by the family's rule — which is
    ``ops/rows.live_rows``' rule: its trip count times the tile — never
    under the prompt tokens, never over S x T; ``deepseek_v3``, whose
    mixed step runs no row tiles, and any program whose slice rows are
    two tiles or fewer (``rows.worth_a_loop``, asked of the rows behind
    the lead: 512 behind SmolLM2's 32 run whole) count every row; and
    the echo backend, which imports no JAX, counts as a Llama program
    does."""
    from llmq_tpu.engine.executor import EchoExecutor
    from llmq_tpu.models import family as family_module
    from llmq_tpu.ops import rows
    fam = family_module(family)
    lead = batch            # both families' decode rows lead the slices'
    tile = rows.row_tile(width)
    assert tile == min(width, 256)
    got = fam.mixed_live_rows(tokens, batch, slices, width)
    assert tokens <= got <= slices * width
    if family == "deepseek_v3" or not rows.worth_a_loop(
            slices * width, tile):
        assert got == slices * width   # no row tiles: every row runs
    else:
        trips = -(-(lead + tokens) // tile)      # live_rows' trip count
        assert got == min(trips * tile, lead + slices * width) - lead
        assert got < tokens + tile
    echo = EchoExecutor(batch_size=batch, page_size=8, num_pages=64,
                        max_pages_per_seq=8, mixed_prefill_slices=slices,
                        mixed_slice_tokens=width)
    assert EchoExecutor.ROW_TILE == rows.ROW_TILE
    assert echo.slice_tokens("mixed_chunk", tokens) == (
        family_module("llama").mixed_live_rows(tokens, batch, slices, width))


@pytest.mark.parametrize("family", ["granitemoehybrid", "solar_open2"])
@pytest.mark.parametrize("tokens,batch,slices,width", _PLANS + [
    (1, 32, 16, 512), (4100, 32, 16, 512), (8192, 32, 16, 512)])
def test_a_mixed_chunk_counts_its_slice_rows_tiles_where_no_row_leads(
        family, tokens, batch, slices, width):
    """The two row-state families run their decode rows through
    products of their own, so no row leads the slices' tight rows: a
    mixed chunk's ``slice_tokens`` is the live tiles' rows of the S x T
    buffer alone — ``live_rows``' trip count times the tile, whatever
    the batch — and every row where the rows are two tiles or fewer."""
    from llmq_tpu.models import family as family_module
    from llmq_tpu.ops import rows
    fam = family_module(family)
    tile = rows.row_tile(width)
    got = fam.mixed_live_rows(tokens, batch, slices, width)
    assert got == fam.mixed_live_rows(tokens, 0, slices, width)
    assert tokens <= got <= slices * width
    if not rows.worth_a_loop(slices * width, tile):
        assert got == slices * width
    else:
        assert got == min(-(-tokens // tile) * tile, slices * width)
        assert got < tokens + tile
