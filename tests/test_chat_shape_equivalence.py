"""The chat mix's shapes against a serial engine (ROADMAP.md Design 9).

The open-loop chat cell meets the engine with arrivals while a row is
free, bursts over a full batch, rows that end inside a chunk, long
prompts sliced under decode rows, cancellations, a trickle and arrivals
INSIDE a step, while the loop waits on a fetch — each with carried
chunks in flight. A seeded plan of submissions and cancellations, keyed
by the STEP they land before or inside (no wall clock), is served
twice: by the engine under test (``async_pipeline`` depth 2,
mixed batching on or off, prefix cache on or off) and by a serial one
(``async_pipeline.enabled=false``, ``mixed_batch.enabled=false``).
Every request's token stream must be equal, none lost, none delivered
twice, and every reason a fill stopped for must be one
``InferenceEngine.fill_refusals`` names.

The echo executor's stream is what its prefill was handed, so it is NOT
the same with and without a matched prefix: where a case runs the
prefix cache the serial engine runs it too, and both trees are seeded
with the shared prefix before the plan, so that what an arrival matches
does not depend on who finished first.

Its own file so that ``--dist loadfile`` may give it its own worker.
"""

from __future__ import annotations

import random

import pytest

from llmq_tpu.core.config import PrefixCacheConfig
from llmq_tpu.core.types import Priority
from llmq_tpu.engine.engine import GenRequest

from test_async_pipeline import (REFUSAL_KEYS, dispatches,  # noqa: F401
                                 make_echo_engine, make_jax_engine,
                                 mixed_cfg, pipe_cfg, tiny_model_f32)

SLOTS = 4           # rows of both engines
SLICE = 8           # tokens a mixed slice holds (both helpers' executors)
WORDS = ("alpha", "bravo", "delta", "echo", "kilo", "lima", "oscar",
         "tango", "zulu")
#: What the prefix-cache cases' prompts share: three pages of 8 tokens.
SHARED = "system: answer briefly. "

LOW, NORMAL, HIGH, REALTIME = (Priority.LOW, Priority.NORMAL,
                               Priority.HIGH, Priority.REALTIME)


class Plan:
    """Submissions and cancellations by the step they land before
    (``events``) or INSIDE (``inside``: while that step waits on a
    transfer, if it does, and right after it otherwise)."""

    def __init__(self, seed: int, shared: bool) -> None:
        self.rng = random.Random(seed)
        self.shared = shared
        self.events = {}        # step -> [("submit", GenRequest) | ("cancel", id)]
        self.inside = {}        # the same, for the step's first wait
        self.ids = []
        self.cancelled = set()

    def submit(self, step: int, rid: str, tokens: int, new: int,
               prio: Priority = NORMAL, inside: bool = False) -> None:
        """A prompt of exactly ``tokens`` bytes (the byte tokenizer: one
        token each) that starts with its id, behind ``SHARED`` where the
        case runs the prefix cache: what is left to prefill, and what
        the echo executor echoes, is ``tokens`` long either way."""
        body = rid + " " + " ".join(self.rng.choice(WORDS)
                                    for _ in range(tokens))
        prompt = (SHARED if self.shared else "") + body[:tokens]
        self.ids.append(rid)
        at = self.inside if inside else self.events
        at.setdefault(step, []).append(("submit", GenRequest(
            id=rid, prompt=prompt, priority=prio, max_new_tokens=new)))

    def cancel(self, step: int, rid: str, inside: bool = False) -> None:
        self.cancelled.add(rid)
        at = self.inside if inside else self.events
        at.setdefault(step, []).append(("cancel", rid))

    @property
    def steps(self) -> int:
        return max(list(self.events) + list(self.inside)) + 1


class _HeldOnce:
    """A transfer's event that reads as not yet set the first time it
    is asked: ``_service_while`` then runs its body once — as it does
    when the transfer really is slower than an arrival — before it
    waits on the transfer itself."""

    def __init__(self, ev) -> None:
        self.ev, self.held = ev, True

    def wait(self, timeout=None) -> bool:
        if self.held:
            self.held = False
            return False
        return self.ev.wait(timeout)


def serve(eng, plan: Plan):
    """Drive ``plan`` step by step; returns ``(handles, streamed,
    seen)``: ``streamed[id]`` the tokens delivered through ``on_token``,
    ``seen[k]`` the engine as step ``k``'s events met it — free rows,
    rows decoding, chunks in flight, requests done — and, where the
    plan has events INSIDE step ``k``, ``seated_inside``: the requests
    seated by the wait they landed in (None where nothing landed in a
    wait: the plan had nothing for it, or the step waited on nothing,
    as a serial engine's never does, and they landed after it).

    Events inside a step land in its first ``_service_while``: the
    engine's is wrapped HERE, for this plan, to submit them and then
    delegate with the transfer's event held once, so that the wait
    services them whatever the transfer's speed."""
    if plan.shared:
        eng.submit(GenRequest(id="seed", prompt=SHARED + "seed",
                              max_new_tokens=2))
        eng.run_until_idle()
    handles, streamed, seen = {}, {}, []

    def land(events) -> None:
        for ev in events:
            if ev[0] == "submit":
                req = ev[1]
                streamed[req.id] = []
                handles[req.id] = eng.submit(
                    req, on_token=streamed[req.id].append)
            else:
                handles[ev[1]].cancel()

    due = []                    # this step's inside events, until landed
    service = eng._service_while

    def service_landing(ev, wait):
        if not due:
            return service(ev, wait)
        events = due[:]
        due.clear()
        land(events)
        service(_HeldOnce(ev), wait)
        seen[-1]["seated_inside"] = {
            s.req.id for s in eng._slots if s is not None} & {
            e[1].id for e in events if e[0] == "submit"}

    eng._service_while = service_landing
    try:
        for k in range(plan.steps):
            eng._drain_completions()
            seen.append({
                "free": sum(s is None for s in eng._slots),
                "decoding": sum(s is not None and s.prefilled
                                for s in eng._slots),
                "inflight": len(eng._inflight),
                "done": {r for r, h in handles.items() if h.done},
                "seated_inside": None})
            land(plan.events.get(k, ()))
            due[:] = plan.inside.get(k, ())
            eng.step()
            land(due)           # the step waited on nothing
            due.clear()
    finally:
        del eng._service_while  # the instance's: the class's is back
    eng.run_until_idle()
    eng._drain_completions()
    return handles, streamed, seen


# -- the seven shapes ----------------------------------------------------------
#
# Each builds its plan and returns ``(plan, check)``; ``check(seen, eng,
# d0, mixed)`` holds the engine under test to the shape's own condition
# — that the plan met the engine in the state the shape is about —
# from ``seen``, the ``(program, inflight)`` dispatches since ``d0`` and
# the engine's counters.


def carried(eng, d0):
    """Dispatches at depth 2 since ``d0``: a chunk sent from the carry."""
    return [d for d in dispatches(eng)[d0:] if d[1] == 2]


def burst_into_free_rows(seed, shared):
    p = Plan(seed, shared)
    p.submit(0, "a0", 48, 48)
    p.submit(0, "a1", 44, 46)
    for i, prio in enumerate((NORMAL, HIGH, LOW)):
        p.submit(8, f"b{i}", 26 + 7 * i, 18 + 5 * i, prio)

    def check(seen, eng, d0, mixed):
        assert seen[8]["decoding"] == 2 and seen[8]["free"] == 2
        assert seen[8]["inflight"] == 1
        assert carried(eng, d0)
    return p, check


def burst_over_full_batch(seed, shared):
    p = Plan(seed, shared)
    for i in range(SLOTS):
        p.submit(0, f"a{i}", 48 - 6 * i, 48 - 3 * i)
    for i, prio in enumerate((LOW, REALTIME, NORMAL, HIGH)):
        p.submit(10, f"b{i}", 26 + 6 * i, 14 + 3 * i, prio)

    def check(seen, eng, d0, mixed):
        assert seen[10]["free"] == 0 and seen[10]["decoding"] == SLOTS
        assert seen[10]["inflight"] == 1
        # two chunks were in flight in the steps before the burst
        assert len(carried(eng, d0)) >= 3
    return p, check


def finish_then_join(seed, shared):
    p = Plan(seed, shared)
    p.submit(0, "a0", 48, 48)
    p.submit(0, "a1", 44, 46)
    p.submit(0, "len", 30, 6)          # ends by its limit inside a chunk
    p.submit(0, "eos", 26, 40)         # the echo's EOS inside a chunk
    p.submit(7, "join0", 29, 20)
    p.submit(16, "join1", 25, 16, HIGH)

    def check(seen, eng, d0, mixed):
        # the row that ended is free, the others' chunk still in flight
        assert "len" in seen[7]["done"]
        assert seen[7]["free"] == 1 and seen[7]["inflight"] == 1
        assert seen[8]["free"] == 0        # taken on the next step
        assert carried(eng, d0)
    return p, check


def long_prompt_sliced(seed, shared):
    p = Plan(seed, shared)
    for i in range(3):
        p.submit(0, f"a{i}", 48 - 4 * i, 48 - 3 * i)
    p.submit(8, "long", 5 * SLICE + 3, 12)

    def check(seen, eng, d0, mixed):
        assert seen[8]["decoding"] == 3 and seen[8]["free"] == 1
        ds = dispatches(eng)[d0:]
        if mixed:
            at = [i for i, d in enumerate(ds) if d[0] == "mixed_chunk"]
            assert len(at) >= 3
            between = ds[at[0]:at[-1] + 1]
            assert any(d[1] == 2 for d in between)
        assert carried(eng, d0)
    return p, check


def cancel_mid_burst(seed, shared):
    p = Plan(seed, shared)
    for i in range(SLOTS):
        p.submit(0, f"a{i}", 48 - 6 * i, 48 - 3 * i)
    p.cancel(9, "a1")
    p.submit(10, "b0", 31, 18)
    p.submit(10, "b1", 26, 12, HIGH)

    def check(seen, eng, d0, mixed):
        assert seen[9]["free"] == 0 and seen[9]["inflight"] == 1
        assert eng.fill_refusals["cancelled"] > 0
    return p, check


def trickle(seed, shared):
    p = Plan(seed, shared)
    for i in range(10):
        p.submit(2 * i, f"t{i}", 25 + (5 * i) % 11, 5 + (3 * i) % 6,
                 (NORMAL, LOW, HIGH)[i % 3])

    def check(seen, eng, d0, mixed):
        assert all(seen[2 * i]["free"] >= 1 for i in range(10))
    return p, check


def arrival_inside_a_step(seed, shared):
    """Arrivals that land while a step waits on its fetch: two into
    free rows (one of them long: sliced), one onto a full batch, and a
    cancellation — ``_service_while`` ingests, seats and starts the
    prefill with chunks in flight."""
    p = Plan(seed, shared)
    p.submit(0, "a0", 48, 48)
    p.submit(0, "a1", 44, 46)
    p.submit(8, "in0", 27, 20, inside=True)
    p.submit(8, "in1", 5 * SLICE + 3, 14, HIGH, inside=True)
    p.submit(9, "in2", 30, 16, REALTIME, inside=True)
    p.cancel(10, "a1", inside=True)
    p.submit(14, "in3", 26, 12, LOW, inside=True)

    def check(seen, eng, d0, mixed):
        assert seen[8]["decoding"] == 2 and seen[8]["free"] == 2
        assert seen[8]["inflight"] == 1
        # both were seated by the wait they landed in, mid-step
        assert seen[8]["seated_inside"] == {"in0", "in1"}
        assert seen[9]["free"] == 0
        # the third landed in a wait too, onto a full batch: not seated
        assert seen[9]["inflight"] >= 1
        assert seen[9]["seated_inside"] == set()
        assert seen[10]["seated_inside"] == set()      # the cancel's wait
        assert seen[14]["seated_inside"] is not None
        assert carried(eng, d0)
    return p, check


SHAPES = {f.__name__: f for f in (
    burst_into_free_rows, burst_over_full_batch, finish_then_join,
    long_prompt_sliced, cancel_mid_burst, trickle,
    arrival_inside_a_step)}


def compare(plan, got, ref, eng, refusals0):
    """Every stream of ``got`` (the engine under test) against ``ref``
    (the serial engine): equal, whole, delivered once."""
    handles, streamed, _ = got
    ref_handles, ref_streamed, _ = ref
    assert sorted(handles) == sorted(ref_handles) == sorted(plan.ids)
    for rid in plan.ids:
        res, ref_res = handles[rid].result, ref_handles[rid].result
        assert handles[rid].done and ref_handles[rid].done, rid
        # delivered once, in order: the streamed tokens ARE the result
        assert streamed[rid] == res.tokens, rid
        assert ref_streamed[rid] == ref_res.tokens, rid
        if rid in plan.cancelled:
            # cut where the cancel met it: one stream leads the other
            assert res.finish_reason == ref_res.finish_reason == "cancelled"
            n = min(len(res.tokens), len(ref_res.tokens))
            assert res.tokens[:n] == ref_res.tokens[:n], rid
            continue
        assert res.finish_reason in ("eos", "length"), (rid, res)
        assert res.finish_reason == ref_res.finish_reason, rid
        assert res.tokens == ref_res.tokens, rid
        assert res.tokens, rid
    counted = {k for k, v in eng.fill_refusals.items()
               if v > refusals0.get(k, 0)}
    assert counted <= REFUSAL_KEYS == set(eng.fill_refusals)
    # nothing left seated, no page leaked
    assert all(s is None for s in eng._slots)
    tree = (eng._prefix_cache.get_stats()["pages"]
            if eng._prefix_cache is not None else 0)
    assert eng.allocator.used() == eng.allocator.pinned_pages() + tree


@pytest.mark.parametrize("prefix", [False, True],
                         ids=["unique", "shared_prefix"])
@pytest.mark.parametrize("mixed", [True, False],
                         ids=["mixed", "unfused"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_echo_streams_equal_the_serial_engine(shape, mixed, prefix):
    plan, check = SHAPES[shape](seed=44, shared=prefix)
    eng, _ = make_echo_engine(
        pipe_cfg(depth=2), mixed=mixed_cfg() if mixed else None,
        slots=SLOTS, name=f"chat-{shape}",
        prefix_cache=PrefixCacheConfig(enabled=prefix))
    ser, _ = make_echo_engine(
        pipe_cfg(enabled=False), mixed=None, slots=SLOTS,
        name=f"serial-{shape}",
        prefix_cache=PrefixCacheConfig(enabled=prefix))
    try:
        got = serve(eng, plan)
        ref = serve(ser, plan)
        check(got[2], eng, 0, mixed)
        compare(plan, got, ref, eng, {})
        if prefix:
            assert eng.prefix_hits > 0
    finally:
        eng.stop()
        ser.stop()


@pytest.fixture(scope="module")
def jax_pair(tiny_model_f32):  # noqa: F811
    """The engine under test and the serial one on the tiny float32
    model, compiled once for the seven shapes: an engine is idle again
    after a plan (``compare`` holds it to that), so the next one finds
    it as a new one would."""
    eng = make_jax_engine(tiny_model_f32, pipe_cfg(depth=2), slots=SLOTS,
                          mixed=mixed_cfg(), max_decode_steps=64)
    ser = make_jax_engine(tiny_model_f32, pipe_cfg(enabled=False),
                          slots=SLOTS, mixed=None, max_decode_steps=64)
    yield eng, ser
    eng.stop()
    ser.stop()


@pytest.mark.parametrize("shape", list(SHAPES))
def test_jax_streams_equal_the_serial_engine(shape, jax_pair):
    eng, ser = jax_pair
    plan, check = SHAPES[shape](seed=44, shared=False)
    d0, refusals0 = len(dispatches(eng)), dict(eng.fill_refusals)
    got = serve(eng, plan)
    ref = serve(ser, plan)
    check(got[2], eng, d0, True)
    compare(plan, got, ref, eng, refusals0)
