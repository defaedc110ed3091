"""The family ``ling_hybrid`` (PR 45) under the contract, in
``BENCHMARK.json`` and in a rehearsal of its own
(``data/rehearsal_ling.json``: a toy of the same block — two periods of
``K K L`` behind a dense layer, a share of 8 of 16 experts in 4 groups —
under ``tiny_saturated``), and the readers of its scopes on a recorded
capture of its cell: the accepted ``ssm_*`` readers read the delta-rule
mixer by the ROLE names it stands under with no edit, and the one new
reader (``kda_gates_ms``) gives nothing for a program without its scope.
What ``test_afmoe_family.py`` and ``test_ssm_metrics.py`` hold their
families to, for the family that came after them."""

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
REHEARSAL = os.path.join(DATA, "rehearsal_ling.json")
FAMILY = os.path.join(ROOT, "benchmark", "families", "ling_hybrid")
CELL = "ling3-reasoning-saturated"
SAMPLE = "scopes_ling3_mixed_chunk.json"
ROWS = 80.0      # what decoded while the capture was held


@pytest.mark.parametrize("bench_file,cell", [
    (None, CELL), (REHEARSAL, "tiny-ling-saturated")],
    ids=["BENCHMARK.json", "rehearsal_ling.json"])
def test_the_cell_resolves_to_the_family_with_the_whole_surface(bench_file,
                                                                cell):
    bench = contract.load_benchmark(bench_file)
    assert contract.check_names(bench) == []
    got = contract.resolve_cell(bench, cell)
    assert got["family_dir"] == FAMILY
    assert got["config"]["family"] == "ling_hybrid"
    shapes = contract.load_family(FAMILY, "shapes")
    assert all(hasattr(shapes, n) for n in contract.FAMILY_SURFACE["shapes"])
    model = got["config"]["model"]
    assert set(model) <= set(shapes.MODEL_KEYS)
    lo, hi = shapes.held_experts(model)
    assert hi - lo == model["num_experts"]
    kda, latent = shapes.layer_kinds(model)
    assert kda + latent == model["num_hidden_layers"] and latent >= 1
    assert shapes.attn_calls_per_step(model) == latent
    for m in got["per_layer"]:
        assert callable(contract.load_reader(bench, m["name"]))
    assert {"setup_s", "tpot_p50_ms"} <= {m["name"]
                                         for m in got["end_to_end"]}


def test_the_cell_is_the_issues():
    """160 clients on 128 rows, one closed loop, prompts 512-2,048 and
    outputs 1,024-4,096 in three adjacent bands each, one chip."""
    bench = contract.load_benchmark()
    got = contract.resolve_cell(bench, CELL)
    assert got["cell"]["chips"] == 1
    assert got["cell"]["traffic"] == "reasoning_saturated"
    traffic, ex = got["traffic"], got["config"]["server"]["executor"]
    assert traffic["loop"] == "closed"
    assert round(traffic["clients_per_row"] * ex["max_batch_size"]) == 160
    assert ex["max_batch_size"] == 128 and ex["page_size"] == 128
    assert [tuple(c["range"]) for c in traffic["prompt_tokens"]] == [
        (512, 1024), (1024, 1536), (1536, 2048)]
    assert [tuple(c["range"]) for c in traffic["output_tokens"]] == [
        (1024, 2048), (2048, 3072), (3072, 4096)]
    assert [c["share"] for c in traffic["prompt_tokens"]] == [
        c["share"] for c in traffic["output_tokens"]] == [0.4, 0.35, 0.25]
    assert got["config"]["max_position_embeddings"] == 2048 + 4096
    assert ex["kv_pages"] >= ex["max_batch_size"] * 6144 // ex["page_size"]
    names = {m["name"] for m in got["per_layer"]}
    assert {"ssm_update_ms", "ssm_update_roofline", "ssm_scan_ms",
            "kda_gates_ms", "moe_experts_touched",
            "moe_held_pairs_per_expert", "decode_attn_roofline",
            "decode_step_roofline", "device_unscoped_share"} <= names
    # (by name, and no "is the last entry": a later PR appends)
    new = [m for m in bench["per_layer"] if m["name"] == "kda_gates_ms"]
    assert len(new) == 1 and CELL in new[0]["workloads"]
    assert (new[0]["moves"], new[0]["source"]) == ("tpot_p50_ms",
                                                   "device_trace")


def test_what_a_step_must_move_is_what_the_issue_reckoned():
    bench = contract.load_benchmark()
    model = contract.resolve_cell(bench, CELL)["config"]["model"]
    shapes = contract.load_family(FAMILY, "shapes")
    assert shapes.layer_kinds(model) == (6, 1)
    assert shapes.dense_layers(model) == 1
    assert shapes.state_bytes_per_row(model) == 6 * (
        128 * 4096 * 4 + 3 * 12288 * 2)
    assert shapes.ssm_update_bytes(model, 100) == 100 * 6 * 2 * 128 * 4096 * 4
    assert shapes.kv_bytes_per_token(model, 2) == 1152
    assert 110 < shapes.experts_touched(model, 128) < 112
    step = shapes.decode_step_bytes(model, 2, 2, 128, 128 * 2400)
    assert 12.4e9 < step < 12.9e9
    assert 0.24 < shapes.ssm_update_bytes(model, 128) / step < 0.27
    routed = 6 * shapes.moe_ffn_bytes(model, 2,
                                      shapes.experts_touched(model, 128))
    assert 0.60 < routed / step < 0.64
    assert shapes.param_count(model) == 5_231_790_016


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
         "s.decode_step_bytes(c['config']['model'], 2, 2, 128, 3e5)\n"
         "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
         % (ROOT, CELL)], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def _run(tmp_path, bench, data_file, cell):
    """``test_ssm_metrics._run``: a run as the readers see it."""
    path = os.path.join(DATA, data_file)
    if not os.path.exists(path):
        pytest.skip("no recorded sample yet")
    d = tmp_path / "trace0"
    d.mkdir()
    shutil.copy(path, d / scopes.NEUTRAL_FILE)
    got = contract.resolve_cell(bench, cell)
    return {"captures": [{"dir": str(d), "reduced": {"devices": 1},
                          "samples": [{"rows": ROWS,
                                       "context_tokens": ROWS * 2400}]}],
            "config": got["config"], "family_dir": got["family_dir"],
            "device": {"kind": "TPU v5 lite"}, "requests": []}


def test_the_scope_readers_on_a_recorded_capture_of_the_cell(tmp_path):
    """One whole ``jit_mixed_chunk`` run of the cell's traced chip run
    (``harness/scopes.py <trace_dir> <out.json> <millis>``)."""
    bench = contract.load_benchmark()
    run = _run(tmp_path, bench, SAMPLE, CELL)
    names = ("ssm_update_ms", "ssm_update_roofline", "ssm_scan_ms",
             "kda_gates_ms", "plain_decode_step_ms", "mixed_step_ms",
             "device_unscoped_share")
    read = {n: contract.load_reader(bench, n)(run) for n in names}
    assert all(v is not None for v in read.values()), read
    red, steps = scopes.of_run(run), scopes.plain_steps(run)
    # one latent layer a step: a decode attention call a plain step
    assert steps == scopes.decode_attn_calls(red) and steps >= 8
    update = scopes.under(red, scopes.LOOP, ("ssm_update",)) / steps
    shapes = contract.load_family(run["family_dir"], "shapes")
    least = (shapes.ssm_update_bytes(run["config"]["model"], ROWS)
             / peaks_for("TPU v5 lite")["hbm_bytes_per_s"])
    assert read["ssm_update_roofline"] == pytest.approx(100 * least / update)
    assert 20 < read["ssm_update_roofline"] <= 100
    assert read["kda_gates_ms"] == pytest.approx(
        scopes.under(red, scopes.LOOP, ("kda_gates",)) / steps * 1e3)
    assert 0 < read["kda_gates_ms"] < read["ssm_update_ms"] \
        < read["plain_decode_step_ms"]
    assert 0 < read["ssm_scan_ms"] < read["mixed_step_ms"]
    assert read["device_unscoped_share"] < 5


def test_a_program_without_the_scope_gives_the_new_reader_nothing(tmp_path):
    """Granite's capture (a recurrent mixer, no ``kda_gates``; and so
    any parent of PR 45): ``None``, and nothing raises — also for no
    capture at all."""
    bench = contract.load_benchmark()
    reader = contract.load_reader(bench, "kda_gates_ms")
    run = _run(tmp_path, bench, "scopes_granite4h_mixed_chunk.json",
               "granite4h-decode-saturated")
    assert reader(run) is None
    run["captures"] = []
    run.pop("_scopes", None)
    assert reader(run) is None


def test_the_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    """``run.py`` on the toy, CPU, tracing off: the family's adapter
    registers the file, the check judges the toy's own sequence through
    the scan, the mixed step and the update, the engine serves the mix
    with no failed request."""
    if os.environ.get("BENCH_SELFTEST_FAST"):
        pytest.skip("BENCH_SELFTEST_FAST")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tiny-ling-saturated", "--seed", "4500000123",
         "--seconds", "8", "--trace", "0", "--benchmark-file", REHEARSAL,
         "--platform", "cpu"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 20
    assert set(line["metrics"]) == {"tpot_p50_ms", "setup_s"}
