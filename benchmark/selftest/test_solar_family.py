"""The family ``solar_open2`` (PR 52) under the contract, in
``BENCHMARK.json`` and in a rehearsal of its own
(``data/rehearsal_solar.json``: a toy of the same block — one period
``G K K K``, a share of 8 of 16 experts — under ``tiny_saturated``), the
issue's arithmetic by the family's ``shapes``, and the two readers the PR
brought (``kda_scan_roofline``, ``slices_attn_ms``) on a hand-made
capture and on captures without their scopes or counts, where they give
nothing and raise nothing. What ``test_ling_family.py`` holds its family
to, for the family that came after it."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import contract, scopes
from benchmark.harness.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
REHEARSAL = os.path.join(DATA, "rehearsal_solar.json")
FAMILY = os.path.join(ROOT, "benchmark", "families", "solar_open2")
CELL = "solar2-longdoc-saturated"
NEW = ("kda_scan_roofline", "slices_attn_ms")


@pytest.mark.parametrize("bench_file,cell", [
    (None, CELL), (REHEARSAL, "tiny-solar-saturated")],
    ids=["BENCHMARK.json", "rehearsal_solar.json"])
def test_the_cell_resolves_to_the_family_with_the_whole_surface(bench_file,
                                                                cell):
    bench = contract.load_benchmark(bench_file)
    assert contract.check_names(bench) == []
    got = contract.resolve_cell(bench, cell)
    assert got["family_dir"] == FAMILY
    assert got["config"]["family"] == "solar_open2"
    shapes = contract.load_family(FAMILY, "shapes")
    assert all(hasattr(shapes, n) for n in contract.FAMILY_SURFACE["shapes"])
    model = got["config"]["model"]
    assert set(model) <= set(shapes.MODEL_KEYS)
    lo, hi = shapes.held_experts(model)
    assert hi - lo == model["n_routed_experts"]
    kda, gqa = shapes.layer_kinds(model)
    assert kda + gqa == model["num_hidden_layers"] and (kda, gqa) == (3, 1)
    assert shapes.attn_calls_per_step(model) == gqa
    for m in got["per_layer"]:
        assert callable(contract.load_reader(bench, m["name"]))
    assert {m["name"] for m in got["end_to_end"]} == {"setup_s",
                                                      "tpot_p50_ms"}


def test_the_cell_is_the_issues():
    """40 clients on 32 rows, one closed loop, one tier, unique prompts
    4,096-32,768 in three bands (0.40 / 0.35 / 0.25) and outputs
    512-2,048 in Trinity's, one chip; exactly one configuration, one
    cell and two metrics appended, eleven cells of 24, none on four
    chips."""
    bench = contract.load_benchmark()
    got = contract.resolve_cell(bench, CELL)
    assert got["cell"] == bench["workloads"][-1] and got["cell"]["chips"] == 1
    assert got["cell"]["traffic"] == "longdoc_saturated"
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 9
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert bench["configs"][-1]["name"] == got["cell"]["config"]
    traffic, ex = got["traffic"], got["config"]["server"]["executor"]
    assert traffic["loop"] == "closed" and traffic["requests_per_client"] == 8
    assert round(traffic["clients_per_row"] * ex["max_batch_size"]) == 40
    assert ex["max_batch_size"] == 32 and ex["page_size"] == 128
    assert traffic["tiers"] == [{"name": "low", "priority": 4, "share": 1.0,
                                 "timeout_s": 900}]
    assert [tuple(c["range"]) for c in traffic["prompt_tokens"]] == [
        (4096, 8192), (8192, 16384), (16384, 32768)]
    assert [tuple(c["range"]) for c in traffic["output_tokens"]] == [
        (512, 1024), (1024, 1536), (1536, 2048)]
    assert [c["share"] for c in traffic["prompt_tokens"]] == [
        c["share"] for c in traffic["output_tokens"]] == [0.4, 0.35, 0.25]
    assert (traffic["trace_at"], traffic["trace_seconds"]) == ([0.3, 0.6],
                                                               3.0)
    assert got["config"]["max_position_embeddings"] == 32768 + 2048
    assert ex["kv_pages"] >= ex["max_batch_size"] * 34816 // ex["page_size"]
    names = {m["name"] for m in got["per_layer"]}
    assert {"ssm_update_ms", "ssm_update_roofline", "ssm_scan_ms",
            "kda_gates_ms", "attn_full_ms", "moe_experts_touched",
            "moe_load_max_over_mean", "moe_held_pairs_per_expert",
            "plain_decode_step_ms", "mixed_step_ms", "mixed_step_share",
            "slices_dense_ms", "decode_dense_ms", "mixed_slice_live_share",
            "device_unscoped_share", "idle_wait_empty_share",
            "idle_wait_starved_share", "fill_short_share",
            "decode_attn_roofline", "decode_step_roofline",
            "moe_route_ms"} | set(NEW) <= names
    # (``moe_ffn_roofline`` read 128 % here: its reader takes two ``gmm``
    # calls for a layer run and a held share multiplies in blocks of
    # pairs, two calls a BLOCK; ``output_tok_s`` spread 5.5 % over six
    # seeds: PERF.md section 6, PR 52)
    assert "moe_ffn_roofline" not in names and "output_tok_s" not in {
        m["name"] for m in got["end_to_end"]}
    # (by name, and no "is the last entry": a later PR appends)
    for name, unit, layer in (
            ("kda_scan_roofline", "%", "kernels (ops/pallas/)"),
            ("slices_attn_ms", "ms", "model step (models/llama.py)")):
        new = [m for m in bench["per_layer"] if m["name"] == name]
        assert len(new) == 1 and new[0]["workloads"] == [CELL]
        assert (new[0]["moves"], new[0]["source"], new[0]["unit"],
                new[0]["layer"]) == ("tpot_p50_ms", "device_trace", unit,
                                     layer)


def test_what_a_step_must_move_is_what_the_issue_reckoned():
    bench = contract.load_benchmark()
    config = contract.resolve_cell(bench, CELL)["config"]
    model = config["model"]
    shapes = contract.load_family(FAMILY, "shapes")
    assert shapes.gqa_params(model) == 109_051_904
    assert shapes.kda_params(model) == 137_723_904
    assert shapes.param_count(model) == 3_308_353_344       # 6.62 GB
    assert 250.2e9 < shapes.published_param_count(
        model, config["published"]) < 250.4e9
    assert shapes.state_bytes_per_row(model) == 3 * (
        128 * 8192 * 4 + 3 * 24576 * 2) == 13_025_280
    assert shapes.kv_bytes_per_token(model, 2) == 4096
    assert shapes.ssm_update_bytes(model, 32) == 32 * 3 * 2 * 128 * 8192 * 4
    assert 21 < shapes.experts_touched(model, 32) < 23
    # a plain step at 32 rows and a 17k mean context: ~7.2 GB = 8.8 ms
    step = shapes.decode_step_bytes(model, 2, 2, 32, 32 * 17000)
    assert 7.0e9 < step < 7.5e9
    assert 0.28 < shapes.decode_attn_bytes(model, 2, 32, 32 * 17000) \
        / step < 0.34                                   # K/V a third
    routed = 4 * shapes.moe_ffn_bytes(model, 2,
                                      shapes.experts_touched(model, 32))
    assert 0.36 < routed / step < 0.42                  # two fifths
    assert 0.10 < shapes.ssm_update_bytes(model, 32) / step < 0.13
    # the scan: a live chunk of one layer moves 10.5 MB and its products
    # are two orders under that at the peaks, so bytes set the least time
    pk = peaks_for("TPU v5 lite")
    by_bytes = shapes.kda_scan_bytes(model, 1) / pk["hbm_bytes_per_s"]
    by_flops = shapes.kda_scan_flops(model, 1) / pk["bf16_flops"]
    assert shapes.kda_scan_bytes(model, 1) == 64 * (5 * 8192 + 64) * 4
    assert 12e-6 < by_bytes < 13.5e-6 and 2e-6 < by_flops < by_bytes
    # the arguments at 32 rows: 11.6 GB, 69 % of the chip
    ex = config["server"]["executor"]
    total = (2 * shapes.param_count(model)
             + (ex["max_batch_size"] + 1) * shapes.state_bytes_per_row(model)
             + ex["kv_pages"] * ex["page_size"] * 4096)
    assert 11.5e9 < total < 11.7e9


def test_who_imports_what_in_the_family():
    imports = {}
    for part in contract.FAMILY_SURFACE:
        with open(os.path.join(FAMILY, part + ".py")) as f:
            text = f.read()
        imports[part] = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", text,
                                   re.M)
        if part == "reference":
            assert 'default_matmul_precision("highest")' in text
    assert set(imports["shapes"]) <= {"__future__", "typing"}
    assert not [m for m in imports["reference"]
                if m.startswith(("llmq_tpu", "benchmark")) or "adapter" in m]
    assert any(m.startswith("llmq_tpu") for m in imports["adapter"])
    assert os.path.exists(os.path.join(FAMILY, "README.md"))


def test_the_parent_process_stays_off_jax_for_the_cell():
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r)\n"
         "from benchmark.harness import contract, readers\n"
         "b = contract.load_benchmark()\n"
         "c = contract.resolve_cell(b, %r)\n"
         "s = readers.family_shapes(c)\n"
         "s.decode_step_bytes(c['config']['model'], 2, 2, 32, 5e5)\n"
         "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
         % (ROOT, CELL)], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


# -- the two new readers --------------------------------------------------------

SCAN_NS, ATTN_NS = 15_000_000, 6_000_000      # a whole mixed run's, made up
#: the decode attention's name in a trace: what counts a loop's steps
DEC = "fused_decode_attention.6"


def _capture(tmp_path, with_scopes=True, with_counts=True):
    """A hand-made capture of the cell: ONE whole ``jit_mixed_chunk``
    run whose mixed step holds the scan kernel (3 calls), the slices'
    prefill attention, the decode rows' update and attention, and a
    decode loop behind it; the engine thread's two dispatches carry
    ``scan_chunks`` 40 with 40 and 30 live."""
    d = tmp_path / "trace0"
    d.mkdir()
    mixed = "jit_mixed_chunk(7)"
    step = "jit(x)/mixed_step/jit(forward_mixed)/"
    loop = "jit(x)/decode_loop/while"
    names = ["scan.1", "pf_attn.2", "update.3", "dec_attn.4", "while.5",
             DEC, "mlp.7", "upd.8"]
    at = {n: i for i, n in enumerate(names)}
    paths = {"scan.1": step + "slices/ssm_scan/pallas_call",
             "pf_attn.2": step + "slices/attn/attn_full/pallas_call",
             "update.3": step + "decode_rows/ssm_update/pallas_call",
             "dec_attn.4": step + "decode_rows/attn/attn_full/pallas_call",
             "while.5": loop,
             DEC: loop + "/body/jit(forward_decode)/attn/attn_full/"
                              "fused_decode_attention",
             "mlp.7": step + "mlp/dot_general",
             "upd.8": loop + "/body/jit(forward_decode)/ssm_update/"
                             "pallas_call"}
    if not with_scopes:
        paths = {"scan.1": step + "slices/mlp/x", "pf_attn.2": step
                 + "slices/attn/x", "update.3": step + "decode_rows/mlp/x",
                 "dec_attn.4": step + "decode_rows/attn/x",
                 "while.5": loop, DEC: loop + "/body/attn/"
                 "fused_decode_attention", "mlp.7": step + "mlp/x",
                 "upd.8": loop + "/body/mlp/x"}
    t = 1_000_000
    ops = []
    for name, ns in (("scan.1", SCAN_NS // 3), ("scan.1", SCAN_NS // 3),
                     ("scan.1", SCAN_NS // 3), ("pf_attn.2", ATTN_NS),
                     ("update.3", 1_400_000), ("dec_attn.4", 900_000),
                     ("mlp.7", 20_000_000)):
        ops.append([at[name], t, ns, 0])
        t += ns
    ops.append([at["while.5"], t, 7_000_000, 0])
    ops.append([at[DEC], t + 100, 900_000, 0])
    ops.append([at["upd.8"], t + 1_000_000, 1_400_000, 0])
    end = t + 7_000_000
    (d / scopes.NEUTRAL_FILE).write_text(json.dumps({
        "vocabulary": scopes.program_vocabulary(),
        "modules": {mixed: paths},
        "planes": [{"name": "/device:TPU:0", "t0_ns": 0.0, "lo_ns": 0.0,
                    "hi_ns": float(end + 1_000_000), "names": names,
                    "runs": [[mixed, 1_000_000, end - 1_000_000]],
                    "ops": ops}]}))

    def dispatch(at_ns, **counts):
        return ["engine.dispatch", at_ns, 10.0, counts]

    live = ({"scan_chunks": 40}, {"scan_chunks": 40})
    counts = [dict(c, scan_chunks_live=n) if with_counts else {}
              for c, n in zip(live, (40, 30))]
    (d / "spans_neutral.json").write_text(json.dumps({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "thread": 0,
             "events": [["busy", 0.0, float(end)]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "thread": 1, "events": [
                ["engine.step", 0.0, float(end)],
                dispatch(10.0, program="mixed_chunk", steps=8, rows=30,
                         prefill_tokens=2560, slice_tokens=2560,
                         **counts[0]),
                dispatch(5e6, program="mixed_chunk", steps=8, rows=30,
                         prefill_tokens=1900, slice_tokens=2560,
                         **counts[1])]}]}]}))
    bench = contract.load_benchmark()
    got = contract.resolve_cell(bench, CELL)
    return bench, {"captures": [{"dir": str(d), "reduced": {"devices": 1},
                                 "samples": [{"rows": 28.0,
                                              "context_tokens": 28 * 17e3}]}],
                   "config": got["config"], "family_dir": got["family_dir"],
                   "device": {"kind": "TPU v5 lite"}, "requests": []}


def test_the_new_readers_on_a_hand_made_capture_of_the_cell(tmp_path):
    bench, run = _capture(tmp_path)
    read = {n: contract.load_reader(bench, n)(run) for n in NEW}
    assert read["slices_attn_ms"] == pytest.approx(ATTN_NS / 1e6)
    shapes = contract.load_family(FAMILY, "shapes")
    model = run["config"]["model"]
    chunks = (40 + 30) / 2 * 3          # the mean dispatch's, three layers
    least = shapes.kda_scan_bytes(model, chunks) / peaks_for(
        "TPU v5 lite")["hbm_bytes_per_s"]
    assert shapes.kda_scan_flops(model, chunks) / peaks_for(
        "TPU v5 lite")["bf16_flops"] < least
    assert read["kda_scan_roofline"] == pytest.approx(
        100 * least / (SCAN_NS / 1e9))
    assert 5 < read["kda_scan_roofline"] < 100
    # the accepted readers the cell is appended to read the same capture
    for n in ("ssm_scan_ms", "ssm_update_ms", "attn_full_ms",
              "mixed_step_ms"):
        assert contract.load_reader(bench, n)(run) is not None, n


@pytest.mark.parametrize("lacks", ["scopes", "counts", "capture"])
def test_a_program_without_the_scope_or_the_count_gives_them_nothing(
        tmp_path, lacks):
    """A parent of PR 52 under this PR's benchmark files: no ``ssm_scan``
    or ``attn_full`` under the slices, or no ``scan_chunks`` on its
    dispatches, or no capture at all — ``None``, and nothing raises; nor
    for another family's capture (granite's recorded one)."""
    bench, run = _capture(tmp_path, with_scopes=lacks != "scopes",
                          with_counts=lacks != "counts")
    if lacks == "capture":
        run["captures"] = []
    got = {n: contract.load_reader(bench, n)(run) for n in NEW}
    assert got["kda_scan_roofline"] is None
    assert (got["slices_attn_ms"] is None) == (lacks != "counts")
    other = os.path.join(DATA, "scopes_granite4h_mixed_chunk.json")
    d = tmp_path / "other"
    d.mkdir()
    shutil.copy(other, d / scopes.NEUTRAL_FILE)
    cell = contract.resolve_cell(bench, "granite4h-decode-saturated")
    run = {"captures": [{"dir": str(d), "reduced": {"devices": 1},
                         "samples": []}], "config": cell["config"],
           "family_dir": cell["family_dir"],
           "device": {"kind": "TPU v5 lite"}, "requests": []}
    assert [contract.load_reader(bench, n)(run) for n in NEW] == [None, None]


def test_the_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    """``run.py`` on the toy, CPU, tracing off: the family's adapter
    registers the file, the check judges the toy's own sequence through
    the scan, the mixed step, the update and the pages, the engine
    serves the mix with no failed request."""
    if os.environ.get("BENCH_SELFTEST_FAST"):
        pytest.skip("BENCH_SELFTEST_FAST")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tiny-solar-saturated", "--seed", "4500000123",
         "--seconds", "8", "--trace", "0", "--benchmark-file", REHEARSAL,
         "--platform", "cpu"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 20
    assert set(line["metrics"]) == {"tpot_p50_ms", "setup_s"}
