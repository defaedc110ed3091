"""Self-tests of ``harness/spans.py`` and of the metrics that read it,
for the CPU sandbox (``python3 -m pytest benchmark/selftest -q``):
the reduction on a small trace in the neutral form whose shares are
exact arithmetic, on a slice of a capture recorded on the v5e, and the
way from a real ``.xplane.pb`` to the neutral form through the
subprocess."""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import contract, spans  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "selftest", "data")
NEW = ("idle_reconcile_share", "idle_schedule_share", "idle_assemble_share",
       "idle_unnamed_share", "dispatch_inflight_mean", "decode_rows_mean",
       "kv_used_of_reserved", "preempted_share", "deliver_lag_p95_ms")
IDLE = NEW[:4]


@pytest.fixture(scope="module")
def bench():
    return contract.load_benchmark()


def _run(tmp_path, data_file, requests=()):
    """A run as the readers see it, whose first capture's directory
    holds ``data_file`` as the cached neutral form."""
    d = tmp_path / "trace0"
    d.mkdir()
    shutil.copy(os.path.join(DATA, data_file), d / spans.NEUTRAL_FILE)
    return {"requests": list(requests),
            "captures": [{"dir": str(d)}, {"dir": str(tmp_path / "none")}],
            "config": {"server": {"executor": {"page_size": 16}}}}


def _small():
    with open(os.path.join(DATA, "trace_spans_small.json")) as f:
        return json.load(f)


def test_innermost_span_owns_each_piece():
    line = spans.engine_line(_small())
    assert line["thread"] == 3          # the thread that holds engine.step
    pieces = spans.innermost(line["events"])
    assert all(a < b for a, b, _n in pieces)
    assert all(x[1] <= y[0] for x, y in zip(pieces, pieces[1:]))
    assert pieces[:4] == [(500, 2000, "engine.step"),
                          (2000, 11000, "engine.fetch"),
                          (11000, 14000, "engine.commit"),
                          (14000, 15000, "engine.step")]
    # nothing is named between the two steps
    assert not any(a < 40000 and b > 30000 for a, b, _n in pieces)
    own = {}
    for a, b, n in pieces:
        own[n] = own.get(n, 0) + b - a
    assert own["engine.dispatch"] == 2500 + 2000 + 1000
    assert own["engine.prefill_advance"] == 4000 - 2000
    assert sum(own.values()) == 29500 + 22000


def test_idle_is_attributed_by_overlap_and_the_shares_add_up():
    red = spans.reduce_neutral(_small())
    assert red["window_s"] == pytest.approx(65e-6)
    assert red["busy_s"] == pytest.approx(21e-6)
    assert red["idle_s"] == pytest.approx(44e-6)
    by = {k: round(v * 1e9) for k, v in red["idle_by_span_s"].items()}
    assert by == {"engine.fetch": 8000, "engine.commit": 4000,
                  "engine.step": 5500, "engine.ingest": 500,
                  "engine.admit": 2000, "engine.prefill_advance": 2000,
                  "engine.resolve": 2000, "engine.assemble": 3000,
                  "engine.fill": 2000, "engine.dispatch": 5000,
                  "unnamed": 10000}
    sh = red["idle_share"]
    assert sh["reconcile"] == pytest.approx(100 * 12000 / 65000)
    assert sh["schedule"] == pytest.approx(100 * 12000 / 65000)
    assert sh["assemble"] == pytest.approx(100 * 10000 / 65000)
    assert sh["unnamed"] == pytest.approx(100 * 10000 / 65000)
    # the completion pool's thread is never asked: its deliver span
    # covers most of the unnamed stretch
    assert "engine.deliver" not in red["idle_by_span_s"]
    assert sum(sh.values()) == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]))
    assert red["modules"] == ["jit_decode_chunk(1)", "jit_mixed_chunk(2)"]
    assert [d["program"] for d in red["dispatches"]] == [
        "decode_chunk", "prefill_multi_b256", "decode_chunk"]


def test_each_new_metric_on_the_small_trace(tmp_path, bench):
    reqs = [{"stages": {"first_token": 1.0, "first_token_out": 1.004}},
            {"stages": {"first_token": 2.0, "first_token_out": 2.030,
                        "preempted": 2.5}},
            {"stages": {"first_token": 3.0, "first_token_out": 3.010}},
            {"stages": {"first_token": 4.0, "first_token_out": 4.002}},
            {"stages": {}}]              # still running: no timeline
    for r in reqs:
        r["ok"] = True
    run = _run(tmp_path, "trace_spans_small.json", reqs)
    got = {n: contract.load_reader(bench, n)(run) for n in NEW}
    assert got["idle_reconcile_share"] == pytest.approx(18.4615, abs=1e-3)
    assert got["idle_schedule_share"] == pytest.approx(18.4615, abs=1e-3)
    assert got["idle_assemble_share"] == pytest.approx(15.3846, abs=1e-3)
    assert got["idle_unnamed_share"] == pytest.approx(15.3846, abs=1e-3)
    idle = contract.load_reader(bench, "device_idle_share")(
        {"captures": [{"reduced": {"devices": 1, "busy_s": 21e-6,
                                   "window_s": 65e-6}}]})
    assert sum(got[n] for n in IDLE) == pytest.approx(idle, abs=1e-9)
    assert got["dispatch_inflight_mean"] == pytest.approx(1.5)
    assert got["decode_rows_mean"] == pytest.approx(50 / 16)
    assert got["kv_used_of_reserved"] == pytest.approx(
        100 * (150 + 160 + 170) / ((10 + 12 + 12) * 16))
    assert got["preempted_share"] == pytest.approx(25.0)
    assert got["deliver_lag_p95_ms"] == pytest.approx(30.0)


def test_the_shares_add_up_on_a_slice_of_a_chip_trace(tmp_path, bench):
    path = os.path.join(DATA, "trace_spans_chip_sample.json")
    if not os.path.exists(path):
        pytest.skip("no recorded slice yet")
    with open(path) as f:
        red = spans.reduce_neutral(json.load(f))
    assert red is not None and red["dispatches"]
    assert sum(red["idle_share"].values()) == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]), abs=1e-6)
    # the programs carry their names
    assert red["modules"] and not any(
        m.startswith("jit_call") for m in red["modules"])
    assert {d["program"] for d in red["dispatches"]} <= {
        m.split("(")[0][len("jit_"):] for m in red["modules"]} | {
        "prefill_b256", "prefill_b1024", "prefill_multi_b256",
        "prefill_multi_b1024", "decode_chunk", "mixed_chunk"}
    run = _run(tmp_path, "trace_spans_chip_sample.json")
    for n in NEW[:7]:
        assert contract.load_reader(bench, n)(run) is not None, n


def test_a_program_without_the_spans_reads_as_nothing(tmp_path, bench):
    """The parent of the PR that added the spans: its capture holds no
    ``engine.step`` and its requests neither mark. Every new reader
    returns nothing and none raises."""
    with open(os.path.join(DATA, "trace_small.json")) as f:
        old = json.load(f)               # tracered's form, PR 23
    assert spans.reduce_neutral(old) is None
    d = tmp_path / "trace0"
    d.mkdir()
    (d / spans.NEUTRAL_FILE).write_text(json.dumps(old))
    run = {"requests": [{"stages": {"first_token": 1.0, "admitted": 0.5}}],
           "captures": [{"dir": str(d)}],
           "config": {"server": {"executor": {"page_size": 16}}}}
    for n in NEW:
        assert contract.load_reader(bench, n)(run) is None, n
    # no capture at all, or a directory that is not there
    for caps in ([], [{"dir": str(tmp_path / "absent")}], [{}]):
        run["captures"] = caps
        for n in NEW:
            assert contract.load_reader(bench, n)(run) is None, n


def test_the_new_entries_are_within_the_contract(bench):
    assert contract.check_names(bench) == []
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # by name: later PRs append their own entries after these
    assert set(NEW) <= set(by_name)
    for n in NEW:
        assert set(by_name[n]) <= {"name", "unit", "better", "source",
                                   "layer", "moves", "workloads"}
    assert by_name["deliver_lag_p95_ms"]["workloads"] == [
        "smollm2-chat-bursts", "smollm2-sessions-prefix"]
    assert all(by_name[n]["source"] == "device_trace" for n in IDLE)


@pytest.mark.skipif(os.environ.get("BENCH_SELFTEST_FAST") == "1",
                    reason="BENCH_SELFTEST_FAST=1")
def test_from_a_real_xplane_through_the_subprocess(tmp_path):
    """A capture made here on the CPU around the program's own span
    primitive: ``neutral_of`` parses it in a subprocess, keeps the
    result beside the trace, and the events carry their arguments and
    their thread. (A CPU capture has no device plane: nothing to
    reduce, and ``of_run`` says so with ``None``.)"""
    import subprocess
    script = (
        "import sys, threading; sys.path.insert(0, %r)\n"
        "import jax\n"
        "from llmq_tpu.utils.profiling import SpanRecorder\n"
        "rec = SpanRecorder()\n"
        "o = jax.profiler.ProfileOptions(); o.python_tracer_level = 0\n"
        "jax.profiler.start_trace(%r, profiler_options=o)\n"
        "def deliver():\n"
        "    with rec.span('engine.deliver', tokens=4): pass\n"
        "with rec.span('engine.step'):\n"
        "    with rec.span('engine.dispatch', program='decode_chunk',\n"
        "                  steps=8, rows=3):\n"
        "        t = threading.Thread(target=deliver); t.start(); t.join()\n"
        "with rec.span('other.thing'): pass\n"
        "jax.profiler.stop_trace()\n" % (ROOT, str(tmp_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    tr = spans.neutral_of(str(tmp_path))
    assert os.path.exists(tmp_path / spans.NEUTRAL_FILE)
    line = spans.engine_line(tr)
    by_name = {e[0]: e for e in line["events"]}
    assert set(by_name) == {"engine.step", "engine.dispatch"}
    assert by_name["engine.dispatch"][3] == {
        "program": "decode_chunk", "steps": 8, "rows": 3}
    others = [ln for p_ in tr["planes"] for ln in p_["lines"]
              if ln is not line]
    assert [e[0] for ln in others for e in ln["events"]] == [
        "engine.deliver"]
    assert others[0]["thread"] != line["thread"]
    assert spans.of_run({"captures": [{"dir": str(tmp_path)}]}) is None
