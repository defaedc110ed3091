"""Self-tests of the benchmark, for the CPU sandbox:

    python3 -m pytest benchmark/selftest -q

They are not part of the repo's tier-1 tests (``tests/``). The last one
is rehearsal 1 of the on-chip-measurement guide: a whole run of a tiny
cell on the CPU (about half a minute); ``BENCH_SELFTEST_FAST=1`` skips it.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import contract, plan, stats, tracered  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "selftest", "data")
SEEDS = [0, 1, 17, 2 ** 31 + 5, 3000000001]
#: The recorded traces are of the program serving a Llama block.
LLAMA = os.path.join(ROOT, "benchmark", "families", "llama")


def _reduce(trace):
    shapes = contract.load_family(LLAMA, "shapes")
    return tracered.reduce_neutral(trace, shapes.DECODE_ATTN,
                                   shapes.PREFILL_ATTN)


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return contract.load_benchmark()


# -- the plan -------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["chat_bursts", "sessions_prefix"])
def test_open_plan_offers_the_same_work_for_five_seeds(mix, bench):
    traffic = _traffic(mix)
    window = float(bench["run_seconds"])
    plans = [plan.open_plan(copy.deepcopy(traffic), s, window)
             for s in SEEDS]
    offered = [plan.offered(p) for p in plans]
    for key in ("requests", "prompt_tokens", "output_tokens"):
        values = [o[key] for o in offered]
        assert max(values) - min(values) <= 0.01 * min(values), (key, values)
    assert offered[0]["requests"] >= 100
    # the skeleton (due instants) is the same; text and order are not
    dues = [[round(r["due"], 9) for r in p] for p in plans]
    assert all(d == dues[0] for d in dues)
    assert plans[0][5]["content"] != plans[1][5]["content"]
    # lengths and tiers are the skeleton's too: the same request by
    # request, so that no seed moves a long prompt to a burst's head
    shape = [[(r["user_tokens"], r["output_tokens"], r["tier"]) for r in p]
             for p in plans]
    assert all(x == shape[0] for x in shape)
    assert len({t[0] for t in shape[0]}) > 20
    # the same seed gives the same plan
    again = plan.open_plan(copy.deepcopy(traffic), SEEDS[1], window)
    assert again == plans[1]


def test_each_stretch_holds_the_classes_its_file_fixes():
    traffic = _traffic("chat_bursts")
    p = plan.open_plan(traffic, 5, 30.0)
    period = plan.period_of(traffic)
    burst = [r for r in p if 0 <= r["due"] < period
             and r["due"] - 0 >= traffic["segments"][0]["seconds"]]
    assert len(burst) == traffic["segments"][1]["arrivals"]
    tiers = sorted(r["tier"] for r in burst)
    want = plan.largest_remainder(
        [t["share"] for t in traffic["tiers"]], len(burst))
    assert [tiers.count(t["name"]) for t in traffic["tiers"]] == want


def test_sessions_carry_their_history_and_share_prefixes():
    traffic = _traffic("sessions_prefix")
    p = plan.open_plan(traffic, 9, 30.0)
    by_session = {}
    for r in p:
        by_session.setdefault(r["session"], []).append(r)
    firsts = [s[0] for s in by_session.values() if s[0]["turn"] == 0]
    prefixes = {r["content"][:traffic["session"]["system_tokens"]]
                for r in firsts}
    assert len(prefixes) == traffic["session"]["system_prompts"]
    turns = next(s for s in by_session.values() if len(s) >= 3)
    assert turns[1]["history_text"] == turns[0]["content"]
    assert turns[2]["history_text"] == turns[0]["content"] + turns[1]["content"]
    longest = max(len(r["history_text"]) + r["prompt_tokens"]
                  + 128 * (r["turn"] + 1) for r in p)
    assert longest < 4096
    in_window = [r for r in p if r["phase"] == "window"]
    assert sum(1 for r in in_window if r["turn"] > 0) > 0.6 * len(in_window)


def test_closed_plan_same_multiset_and_staggered_first_requests():
    traffic = _traffic("decode_saturated")
    a = plan.closed_plan(traffic, 1, rows=32, max_context=4096)
    b = plan.closed_plan(traffic, 2, rows=32, max_context=4096)
    assert len(a) == 40

    def later(cl, key):
        return sorted(r[key] for c in cl for r in c[1:])
    assert sum(later(a, "prompt_tokens")) + sum(
        c[0]["prompt_tokens"] for c in a) == sum(
        later(b, "prompt_tokens")) + sum(c[0]["prompt_tokens"] for c in b)
    assert [r["content"] for r in a[3]] != [r["content"] for r in b[3]]
    firsts = sorted(c[0]["output_tokens"] for c in a)
    # remaining lengths spread from a few tokens to whole outputs
    assert firsts[0] < 64 and firsts[-1] > 500
    assert all(r["prompt_tokens"] + r["output_tokens"] < 2048
               for c in a for r in c)


# -- arithmetic on marks --------------------------------------------------------

def test_percentile_and_tpot_on_recorded_marks():
    with open(os.path.join(DATA, "marks.json")) as f:
        reqs = json.load(f)["requests"]
    good = [r for r in reqs if r["ok"]]
    ttft = stats.collect(good, stats.ttft_ms)
    assert sorted(round(v) for v in ttft) == [50, 100, 150, 200, 250, 300,
                                              500, 1000]
    # 10 requests, 2 failed and sort last: p50 is the 5th, p90 and p95
    # fall among the missing
    assert round(stats.percentile(ttft, 50, missing=2)) == 250
    assert round(stats.percentile(ttft, 80, missing=2)) == 1000
    assert stats.percentile(ttft, 95, missing=2) == float("inf")
    assert round(stats.percentile(ttft, 95)) == 1000
    tpot = stats.collect(good, stats.tpot_ms)
    assert len(tpot) == 7            # the one-token stream has no gap
    assert sorted(round(v, 3) for v in tpot) == [10.0, 10.0, 12.5, 12.5,
                                                 20.0, 20.0, 20.0]
    assert stats.percentile([], 50) is None
    assert round(stats.spread([10, 11, 12, 13, 14, 15]), 4) == round(
        (14.25 - 10.75) / 12.5, 4)


# -- the trace reduction --------------------------------------------------------

def test_trace_reduction_on_a_small_recorded_trace():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        red = _reduce(json.load(f))
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(15e-6)
    assert red["window_s"] == pytest.approx(65e-6)
    # a while's self time leaves out its body: nothing is counted twice
    assert red["ops"]["while"][0] == pytest.approx(0.0)
    assert red["ops"]["fusion"] == [pytest.approx(10e-6), 5]
    assert red["ops"]["fused_decode_attention_pallas"][0] == pytest.approx(5e-6)
    assert sum(v[0] for v in red["ops"].values()) == pytest.approx(
        red["busy_s"])
    assert red["modules"]["jit__decode_chunk(1)"] == [pytest.approx(15e-6), 2]
    # both runs hold decode attention calls and no prefill attention
    assert red["programs"] == {"decode": [pytest.approx(15e-6), 2, 5, 0]}
    # the gap is named by the innermost host span that is not a wait
    assert red["idle_gaps"][0][0] == "engine.py:_commit_row"
    assert red["idle_gaps"][0][1] == pytest.approx(50e-6)
    assert tracered.top_ops(red, 1)[0][0] == "fusion"
    assert tracered.host_name("$/a/b/metrics.py:12 labels") == "metrics.py:labels"
    assert tracered.op_name("fusion.6066") == "fusion"
    assert tracered.op_name("fusion.6066.remat") == "fusion.6066.remat"


def test_trace_reduction_on_a_slice_of_a_chip_trace():
    """40 ms of a capture on the v5e: one mixed chunk under way."""
    with open(os.path.join(DATA, "trace_chip_sample.json")) as f:
        red = _reduce(json.load(f))
    assert red["devices"] == 1
    assert 0.9 < red["busy_s"] / red["window_s"] <= 1.0
    assert tracered.top_ops(red, 1)[0][0] == "paged_prefill_attention_pallas"
    assert sum(v[0] for v in red["ops"].values()) == pytest.approx(
        red["busy_s"], rel=1e-6)
    # the exported program is "jit_call(<fingerprint>)": it is known as
    # a mixed chunk by the kernels that ran inside it
    mixed = red["programs"]["mixed"]
    assert mixed[1] == 1 and mixed[2] > 0 and mixed[3] > 0
    assert "decode" not in red["programs"]
    # under a family whose kernels have other names the same trace
    # holds no decode step: the patterns are the family's, not tracered's
    with open(os.path.join(DATA, "trace_chip_sample.json")) as f:
        other = tracered.reduce_neutral(json.load(f), "latent_decode",
                                        "latent_prefill")
    assert set(other["programs"]) == {"other"}
    assert other["busy_s"] == red["busy_s"]


# -- driven by data -------------------------------------------------------------

def test_a_cell_config_mix_and_metric_come_from_new_files_alone(tmp_path, bench):
    new = tmp_path / "extra"
    (new / "traffic").mkdir(parents=True)
    (new / "metrics").mkdir()
    (new / "configs").mkdir()
    with open(os.path.join(DATA, "tiny-rehearsal.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "new-config"
    (new / "configs" / "new-config.json").write_text(json.dumps(cfg))
    mix = _traffic("chat_bursts")
    mix["segments"] = [{"name": "flat", "seconds": 5.0, "arrivals": 10}]
    (new / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    (new / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return float(len(run['requests']))\n")
    b = copy.deepcopy(bench)
    b["_root"] = str(tmp_path)
    for c in b["configs"]:        # the accepted files stay where they are
        c["file"] = os.path.join(ROOT, c["file"])
    b["paths"] = [os.path.join(ROOT, p) for p in bench["paths"]] + ["extra"]
    b["configs"].append({"name": "new-config", "source": "none",
                         "file": "extra/configs/new-config.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "new-cell", "config": "new-config",
                           "traffic": "new_mix", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "new_metric", "unit": "requests",
                           "better": "higher", "source": "program_counter",
                           "layer": "load generator (benchmark)",
                           "moves": "tpot_p50_ms",
                           "workloads": ["new-cell"]})
    assert contract.check_names(b) == []
    cell = contract.resolve_cell(b, "new-cell")
    assert cell["config"]["model"]["hidden_size"] == 128
    p = plan.open_plan(cell["traffic"], 1, 10.0)
    assert plan.offered(p)["requests"] == 20
    names = [m["name"] for m in cell["per_layer"]]
    assert "new_metric" in names and "batch_rows_mean" not in names
    read = contract.load_reader(b, "new_metric")
    assert read({"requests": [1, 2, 3]}) == 3.0
    # an old metric's reader is still found in the accepted directory
    assert callable(contract.load_reader(b, "tpot_p50_ms"))


def test_every_reader_returns_nothing_when_there_is_nothing_to_read(bench):
    empty = {"requests": [], "opened": {"t": 1.0, "tokens": 0},
             "closed": {"t": 1.0, "tokens": 0}, "captures": [],
             "setup_s": 1.0, "config": {}, "device": {"kind": "cpu"}}
    for m in bench["end_to_end"] + bench["per_layer"]:
        value = contract.load_reader(bench, m["name"])(empty)
        assert value is None or m["name"] == "setup_s", m["name"]


# -- BENCHMARK.json -------------------------------------------------------------

def test_benchmark_json_is_within_the_contract(bench):
    assert contract.check_names(bench) == []
    assert set(bench) - {"_root"} == {"command", "paths", "run_seconds",
                                     "configs", "workloads", "end_to_end",
                                     "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_experts_per_tok")
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert not any(k in widths or k.endswith(("_dim", "_rank"))
                       for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert set(c["reduced"]) == set(body["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        cell = contract.resolve_cell(bench, w["name"])
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        for m in cell["end_to_end"] + cell["per_layer"]:
            assert callable(contract.load_reader(bench, m["name"]))
    for root, _dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in root:
            continue
        for name in files:
            assert all(ch.isalnum() or ch in "_.-" for ch in name), name


def test_peaks_table_refuses_an_unknown_device():
    from benchmark.harness.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_shape_functions_on_the_published_sizes(bench):
    smol = contract.resolve_cell(bench, "smollm2-chat-bursts")
    mis = contract.resolve_cell(bench, "mistral7b-decode-saturated")
    assert smol["family_dir"] == mis["family_dir"] == LLAMA
    shapes = contract.load_family(LLAMA, "shapes")
    smol, mis = smol["config"]["model"], mis["config"]["model"]
    assert shapes.kv_bytes_per_token(smol, 2) == 196608
    assert shapes.kv_bytes_per_token(mis, 1) == 65536 + 1024
    assert round(shapes.param_count(smol) / 1e9, 2) == 1.71
    assert round(shapes.param_count(mis) / 1e9, 2) == 7.25
    assert shapes.attn_calls_per_step(smol) == 24
    # this family's least bytes and operations of a step's attention do
    # not depend on the rows of the batch
    assert shapes.decode_attn_bytes(smol, 2, 4, 1000.0) == \
        shapes.decode_attn_bytes(smol, 2, 31, 1000.0) == 196608 * 1000.0


# -- rehearsal 1: the whole command on the CPU at a tiny size ------------------

@pytest.mark.skipif(os.environ.get("BENCH_SELFTEST_FAST") == "1",
                    reason="BENCH_SELFTEST_FAST=1")
def test_a_tiny_cell_runs_end_to_end_on_the_cpu():
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--benchmark-file",
           os.path.join(DATA, "rehearsal.json"), "--platform", "cpu",
           "--workload", "tiny-bursts", "--seed", "3000000001",
           "--seconds", "6", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 20
    assert res["device"]["platform"] == "cpu"     # so: not a measurement
    assert set(res["metrics"]) == {"ttft_p95_ms", "tpot_p50_ms",
                                   "tpot_p95_ms", "setup_s"}
    # without an accelerator the real command fails and prints no result
    p = subprocess.run(cmd[:2] + ["--workload", "smollm2-chat-bursts",
                                  "--seed", "1", "--seconds", "1",
                                  "--trace", "0"],
                       cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "correct" not in p.stdout
