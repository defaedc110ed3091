"""The family ``mellum`` (PR 54) under the contract, in ``BENCHMARK.json``
and in a rehearsal of its own (``data/rehearsal_mellum.json``: a toy of
the same block — two kinds of attention layer each rotated by its own
table, a window of 24, 16 softmax-routed experts — under
``tiny_completion_sessions``, the short mix of ``completion_sessions``
with the same keys: editor sessions over shared project contexts, every
turn the last one's stream plus what was typed). What
``test_families.py`` holds every family to, for the family that came
after it, and the new traffic file's keys rehearsed on the CPU.
(``data/rehearsal.json`` is a file the benchmark already had and is not
edited: the toy and its mix have files of their own, as the families
since PR 41.)"""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.harness import contract, plan

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "data", "rehearsal_mellum.json")
FAMILY = os.path.join(ROOT, "benchmark", "families", "mellum")


@pytest.mark.parametrize("bench_file,cell", [
    (None, "mellum2-completion-sessions"),
    (REHEARSAL, "tiny-mellum-completion")], ids=["BENCHMARK.json",
                                                 "rehearsal_mellum.json"])
def test_the_cell_resolves_to_the_family_with_the_whole_surface(bench_file,
                                                                cell):
    bench = contract.load_benchmark(bench_file)
    assert contract.check_names(bench) == []
    got = contract.resolve_cell(bench, cell)
    assert got["family_dir"] == FAMILY and got["config"]["family"] == "mellum"
    shapes = contract.load_family(FAMILY, "shapes")
    assert all(hasattr(shapes, n) for n in contract.FAMILY_SURFACE["shapes"])
    model = got["config"]["model"]
    assert set(model) == set(shapes.MODEL_KEYS)
    sliding, full = shapes.layer_kinds(model)
    assert sliding + full == model["num_hidden_layers"] and full >= 1
    assert shapes.attn_calls_per_step(model) == model["num_hidden_layers"]
    for m in got["per_layer"]:
        assert callable(contract.load_reader(bench, m["name"]))
    names = {m["name"] for m in got["end_to_end"]}
    assert {"setup_s", "tpot_p50_ms"} <= names
    reported = {m["name"] for m in got["per_layer"]}
    assert {"prefix_declined_share", "prefix_match_cut_share", "row_tail_ms",
            "row_tail_slots_live_share", "moe_experts_touched"} <= reported
    # every metric the cell reports moves a metric the cell reports (the
    # real cell is off ``ttft_p95_ms`` and ``tpot_p95_ms``: PERF.md, PR 54)
    assert {m["moves"] for m in got["per_layer"]} <= names


def test_the_short_mix_has_the_cell_s_keys_and_plans_sessions():
    """``tiny_completion_sessions`` against ``completion_sessions``: the
    same keys (only those ``harness/plan.py`` reads, and notes), and a
    plan whose turns carry their session's history and one of the
    shared contexts."""
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "completion_sessions.json")) as f:
        real = json.load(f)
    with open(os.path.join(HERE, "traffic",
                           "tiny_completion_sessions.json")) as f:
        tiny = json.load(f)
    assert set(tiny) == set(real)
    assert set(tiny["session"]) | {"gap_note"} == set(real["session"])
    assert real["session"]["system_tokens"] == 6144
    assert [c["count"] for c in real["session"]["turns"]] == [6, 8, 10, 12]
    assert real["session"]["gap_s"] == [2.0, 4.0]
    assert real["tiers"] == [{"name": "realtime", "priority": 1,
                              "share": 1.0, "timeout_s": 120}]
    reqs = plan.open_plan(real, 7, 48.0)
    window = [r for r in reqs if r["phase"] == "window"]
    first = [r for r in window if r["turn"] == 0]
    assert first and all(r["prompt_tokens"] >= 6144 + 32 for r in first)
    later = [r for r in window if r["turn"] > 0]
    assert all(r["history_text"] and 32 <= r["prompt_tokens"] <= 2048
               for r in later)
    # the longest plannable stream stays under the served context
    longest = max(len(r["history_text"]) + r["prompt_tokens"]
                  + 128 * (r["turn"] + 1) for r in reqs)
    assert longest < 32768
    # a full population of sessions is warm before the window opens
    ramp = -min(r["due"] for r in reqs)
    assert ramp >= 12 * real["session"]["gap_s"][1] - 1


def test_who_imports_what_in_the_family():
    imports = {}
    for part in contract.FAMILY_SURFACE:
        with open(os.path.join(FAMILY, part + ".py")) as f:
            imports[part] = re.findall(
                r"^\s*(?:from|import)\s+([\w.]+)", f.read(), re.M)
    assert set(imports["shapes"]) <= {"__future__", "typing"}
    assert not [m for m in imports["reference"]
                if m.startswith(("llmq_tpu", "benchmark")) or "adapter" in m]
    assert any(m.startswith("llmq_tpu") for m in imports["adapter"])
    assert os.path.exists(os.path.join(FAMILY, "README.md"))


def test_the_readers_of_the_new_metrics_return_nothing_without_their_counters():
    """On a parent of the PR (no ``declined`` / ``matched_tokens`` /
    ``tail_slots`` in the marks, no ``row_tail`` scope) each new reader
    returns ``None`` and does not raise."""
    bench = contract.load_benchmark()
    bare = {"opened": {"prefix_cache": {"hits": 1}},
            "closed": {"prefix_cache": {"hits": 5}}, "captures": [],
            "requests": []}
    for name in ("prefix_declined_share", "prefix_match_cut_share",
                 "row_tail_slots_live_share", "row_tail_ms"):
        assert contract.load_reader(bench, name)(dict(bare)) is None, name
        assert contract.load_reader(bench, name)({}) is None, name
    marks = {"opened": {"prefix_cache": {
        "declined": 0, "admission_hits": 10, "admission_misses": 2,
        "matched_tokens": 1000, "match_cut_tokens": 0, "tail_slots": 64,
        "tail_slots_live": 32}},
        "closed": {"prefix_cache": {
            "declined": 1, "admission_hits": 28, "admission_misses": 4,
            "matched_tokens": 9000, "match_cut_tokens": 2000,
            "tail_slots": 64, "tail_slots_live": 48}}}
    read = lambda name: contract.load_reader(bench, name)(marks)
    assert read("prefix_declined_share") == 5.0
    assert read("prefix_match_cut_share") == 25.0
    assert read("row_tail_slots_live_share") == 62.5


@pytest.mark.skipif(os.environ.get("BENCH_SELFTEST_FAST") == "1",
                    reason="BENCH_SELFTEST_FAST=1")
def test_the_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    """``run.py`` on the toy, CPU, tracing off: the family's adapter
    registers the file, the check judges the toy's prompt through both
    caches and the adopted path, the engine serves the sessions with no
    failed request."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tiny-mellum-completion", "--seed", "3400000123",
         "--seconds", "8", "--trace", "0", "--benchmark-file", REHEARSAL,
         "--platform", "cpu"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 10
    assert {"tpot_p50_ms", "setup_s"} <= set(line["metrics"])
