"""Self-tests of ``harness/waits.py`` and of the ten metrics built on it
(``python3 -m pytest benchmark/selftest/test_waits.py -q``; standard
library): the tail's seven legs on a run recorded on the v5e, the two
``engine.wait`` shares on a cut of a recorded capture, the fills on a
small trace whose reasons are known."""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import contract, spans, stats, waits  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "selftest", "data")
OPEN_LOOP = ["smollm2-chat-bursts", "smollm2-sessions-prefix"]
#: name -> (source, layer, moves, cells: None = every cell)
NEW = {
    "ttft_tail_entry_ms": ("host_clock", "entry / API (api/server.py)",
                           "ttft_p95_ms", OPEN_LOOP),
    "ttft_tail_queue_ms": ("program_span", "queue plane (queueing/, native/)",
                           "ttft_p95_ms", OPEN_LOOP),
    "ttft_tail_admission_ms": ("program_span",
                               "worker to engine (engine/engine.py)",
                               "ttft_p95_ms", OPEN_LOOP),
    "ttft_tail_slot_ms": ("program_span",
                          "worker to engine (engine/engine.py)",
                          "ttft_p95_ms", OPEN_LOOP),
    "ttft_tail_slices_ms": ("program_span", "executor (engine/executor.py)",
                            "ttft_p95_ms", OPEN_LOOP),
    "ttft_tail_reconcile_ms": ("program_span",
                               "worker to engine (engine/engine.py)",
                               "ttft_p95_ms", OPEN_LOOP),
    "ttft_tail_deliver_ms": ("program_span",
                             "engine, completion pool (engine/engine.py)",
                             "ttft_p95_ms", OPEN_LOOP),
    "idle_wait_empty_share": ("device_trace", "device", "tpot_p50_ms", None),
    "idle_wait_starved_share": ("device_trace",
                                "worker to engine (engine/engine.py)",
                                "tpot_p50_ms", None),
    "fill_short_share": ("program_counter",
                         "worker to engine (engine/engine.py)",
                         "tpot_p50_ms", None),
}


@pytest.fixture(scope="module")
def bench():
    return contract.load_benchmark()


def _recorded():
    with open(os.path.join(DATA, "waits_requests_chat.json")) as f:
        return json.load(f)["requests"]


def _sample():
    with open(os.path.join(DATA, "waits_spans_sessions_sample.json")) as f:
        return json.load(f)


def _read(bench, name, run):
    return contract.load_reader(bench, name)(run)


def _traced_run(tmp_path, trace):
    """A run whose first capture's directory holds ``trace`` as the
    cached neutral form."""
    d = tmp_path / "trace0"
    d.mkdir()
    with open(d / spans.NEUTRAL_FILE, "w") as f:
        json.dump(trace, f)
    return {"requests": [], "captures": [{"dir": str(d)}]}


# -- the entries, by name -------------------------------------------------------

@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_is_within_the_contract(bench, name):
    """Looked up BY NAME, never by position: a later PR appends."""
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    source, layer, moves, cells = NEW[name]
    every = [w["name"] for w in bench["workloads"]]
    assert entry["source"] == source and entry["layer"] == layer
    assert entry["moves"] == moves
    assert entry["unit"] == ("ms" if name.endswith("_ms") else "%")
    assert entry["better"] == "lower"
    want = cells if cells is not None else every
    # (a later cell may be appended to the list)
    assert entry["workloads"][:len(want)] == want
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # each listed cell reports the end-to-end metric it moves
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == moves]
    assert set(entry["workloads"]) <= set(moved.get("workloads", every))
    assert callable(contract.load_reader(bench, name))


# -- the tail's legs ------------------------------------------------------------

def test_the_seven_legs_add_up_to_the_tails_mean_ttft(bench):
    run = {"requests": _recorded()}
    legs = {leg: _read(bench, f"ttft_tail_{leg}_ms", run)
            for leg in waits.LEGS}
    good = [r for r in run["requests"] if r["ok"]]
    ttfts = sorted(stats.ttft_ms(r) for r in good)
    edge = stats.percentile(ttfts, 90)
    tail = [t for t in ttfts if t >= edge]
    assert len(tail) == waits.tail_legs(run)["requests"] == 24
    assert waits.tail_legs(run)["left_out"] == 0
    assert abs(sum(legs.values()) - sum(tail) / len(tail)) < 1.0
    assert all(v is not None and v > -1.0 for v in legs.values())
    # this run's tail waited its turn in the mixed budget: the two legs
    # no percentile metric reads hold most of it
    assert legs["slot"] + legs["slices"] > 0.5 * sum(legs.values())


@pytest.mark.parametrize("gone", ["prefill_last_dispatched", "enqueued"])
def test_a_program_without_a_mark_gives_none_not_a_wrong_split(bench, gone):
    """The parent of the PR that added ``prefill_last_dispatched``
    stamps none: all seven legs are left out of its line."""
    requests = copy.deepcopy(_recorded())
    for r in requests:
        r["stages"].pop(gone, None)
    run = {"requests": requests}
    assert all(_read(bench, f"ttft_tail_{leg}_ms", run) is None
               for leg in waits.LEGS)
    assert waits.tail_legs({"requests": []}) is None


def test_a_request_missing_a_mark_is_left_out_of_all_seven():
    requests = copy.deepcopy(_recorded())
    whole = waits.tail_legs({"requests": copy.deepcopy(requests)})
    worst = max((r for r in requests if r["ok"]), key=stats.ttft_ms)
    del worst["stages"]["admitted"]
    legs = waits.tail_legs({"requests": requests})
    assert legs["requests"] == whole["requests"] - 1
    assert legs["left_out"] == 1
    assert abs(sum(legs[k] for k in waits.LEGS) - legs["ttft"]) < 1e-6
    assert legs["ttft"] < whole["ttft"]


# -- the loop asleep ------------------------------------------------------------

@pytest.mark.parametrize("pending,kind", [(0, waits.EMPTY),
                                          (2, waits.STARVED)])
def test_the_wait_shares_are_parts_of_the_unnamed_share(pending, kind):
    trace = _sample()
    for ev in spans.engine_line(trace)["events"]:
        if ev[0] == waits.WAIT:
            ev[3]["pending"] = pending
    unnamed = spans.reduce_neutral(trace)["idle_share"][spans.UNNAMED]
    shares = waits.reduce_waits(trace)
    other = waits.STARVED if kind == waits.EMPTY else waits.EMPTY
    # recorded: one stretch of 269 ms with nothing to serve, in a cut of
    # 769 ms around it
    assert shares[kind] == pytest.approx(35.0012, abs=1e-3)
    assert shares[other] == 0.0
    assert shares[waits.EMPTY] + shares[waits.STARVED] <= unnamed + 1e-9
    assert (shares[waits.EMPTY] + shares[waits.STARVED]
            + shares[spans.UNNAMED]) == pytest.approx(unnamed, abs=1e-9)


def test_the_readers_on_the_recorded_capture(bench, tmp_path):
    run = _traced_run(tmp_path, _sample())
    empty = _read(bench, "idle_wait_empty_share", run)
    starved = _read(bench, "idle_wait_starved_share", run)
    unnamed = _read(bench, "idle_unnamed_share", run)
    assert empty == pytest.approx(35.0012, abs=1e-3) and starved == 0.0
    assert empty + starved <= unnamed
    # 10 fills in the cut: 9 stopped at the pipeline's depth, one
    # found nothing to decode as the idle stretch began
    assert [f["stopped"] for f in waits.fills(run)].count("depth") == 9
    assert len(waits.fills(run)) == 10
    assert _read(bench, "fill_short_share", run) == pytest.approx(10.0)


def _strip(trace, *, waits_too, chunk_too):
    for ev in spans.engine_line(trace)["events"]:
        if chunk_too and len(ev) > 3:
            ev[3].pop("chunk", None)
    line = spans.engine_line(trace)
    if waits_too:
        line["events"] = [e for e in line["events"] if e[0] != waits.WAIT]
    return trace


def test_a_program_that_opens_no_wait_gives_none(bench, tmp_path):
    """The parent's program: no ``engine.wait``, no ``chunk`` on a
    dispatch. The two readers find nothing to read and do not raise."""
    run = _traced_run(tmp_path, _strip(_sample(), waits_too=True,
                                       chunk_too=True))
    assert _read(bench, "idle_wait_empty_share", run) is None
    assert _read(bench, "idle_wait_starved_share", run) is None
    # PR 27's count is the parent's too
    assert _read(bench, "fill_short_share", run) == pytest.approx(10.0)


def test_a_loop_that_never_slept_reads_zero(bench, tmp_path):
    """This PR's program in a saturated cell: its dispatches carry
    ``chunk`` and the capture holds no ``engine.wait``."""
    run = _traced_run(tmp_path, _strip(_sample(), waits_too=True,
                                       chunk_too=False))
    assert _read(bench, "idle_wait_empty_share", run) == 0.0
    assert _read(bench, "idle_wait_starved_share", run) == 0.0


def test_no_capture_gives_none(bench):
    run = {"requests": [], "captures": []}
    for name in ("idle_wait_empty_share", "idle_wait_starved_share",
                 "fill_short_share"):
        assert _read(bench, name, run) is None


# -- the fills, and the chunk id ------------------------------------------------

def _small_trace(stopped):
    """A device busy for 10 ms and an engine thread whose steps each
    hold one fill with a known reason."""
    events = []
    for i, why in enumerate(stopped):
        t = 1e6 * i
        events.append(["engine.step", t, 9e5])
        events.append(["engine.fill", t + 1e4, 1e5,
                       {"dispatched": 1 if why == "depth" else 0,
                        "stopped": why}])
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "thread": 0,
             "events": [["busy", 0.0, 1e6 * len(stopped)]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "thread": 1, "events": events}]}]}


@pytest.mark.parametrize("stopped,share", [
    (["depth", "depth", "depth", "free_slot"], 25.0),
    (["depth"] * 5, 0.0),
    (["pages", "row_ended", "depth", "urgent_pending", "depth"], 60.0),
])
def test_fill_short_share_on_known_reasons(bench, tmp_path, stopped, share):
    run = _traced_run(tmp_path, _small_trace(stopped))
    assert [f["stopped"] for f in waits.fills(run)] == stopped
    assert _read(bench, "fill_short_share", run) == pytest.approx(share)


def test_a_chunks_three_spans_join_by_its_id():
    """``engine.dispatch``, ``engine.fetch`` and ``engine.commit`` of one
    chunk carry one ``chunk``: inside a capture the commit beside a
    dispatch is of the chunk sent TWO dispatches earlier, which is what
    pairing by position got wrong (``harness/commits.py``)."""
    by = {"engine.dispatch": {}, "engine.fetch": {}, "engine.commit": {}}
    events = spans.engine_line(_sample())["events"]
    for ev in events:
        if ev[0] in by and len(ev) > 3 and "chunk" in ev[3]:
            by[ev[0]][ev[3]["chunk"]] = ev
    joined = sorted(set(by["engine.dispatch"]) & set(by["engine.fetch"])
                    & set(by["engine.commit"]))
    assert len(joined) >= 5
    # the serial counts every dispatch: one between two chunks that has
    # no fetch is a dedicated prefill program's
    for n in set(range(joined[0], joined[-1])) - set(joined):
        assert by["engine.dispatch"][n][3]["steps"] == 0
    for n in joined:
        d, f, c = (by[k][n] for k in by)
        assert d[1] + d[2] <= f[1] and f[1] + f[2] <= c[1] + 1.0
        assert d[3]["steps"] > 0
    # by position the nearest commit BEFORE a dispatch is an older chunk's
    n = joined[-1]
    d = by["engine.dispatch"][n]
    before = [e for e in events if e[0] == "engine.commit"
              and e[1] + e[2] <= d[1]]
    assert before and before[-1][3]["chunk"] < n
