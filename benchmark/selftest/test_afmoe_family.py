"""The family ``afmoe`` (PR 41) under the contract, in ``BENCHMARK.json``
and in a rehearsal of its own (``data/rehearsal_afmoe.json``: a toy of
the same block — two kinds of attention layer, a window of 24, a share
of 8 of 16 experts — under ``tiny_longshort``, prompts shorter and
several windows longer than the window in one queue). What
``test_families.py`` holds every family to, for the family that came
after it: a ``model_config`` PR adds files beside the accepted ones and
edits none."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.harness import contract

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "data", "rehearsal_afmoe.json")
FAMILY = os.path.join(ROOT, "benchmark", "families", "afmoe")


@pytest.mark.parametrize("bench_file,cell", [
    (None, "trinity-longshort-saturated"),
    (REHEARSAL, "tiny-afmoe-longshort")], ids=["BENCHMARK.json",
                                               "rehearsal_afmoe.json"])
def test_the_cell_resolves_to_the_family_with_the_whole_surface(bench_file,
                                                                cell):
    bench = contract.load_benchmark(bench_file)
    assert contract.check_names(bench) == []
    got = contract.resolve_cell(bench, cell)
    assert got["family_dir"] == FAMILY and got["config"]["family"] == "afmoe"
    shapes = contract.load_family(FAMILY, "shapes")
    assert all(hasattr(shapes, n) for n in contract.FAMILY_SURFACE["shapes"])
    model = got["config"]["model"]
    assert set(model) == set(shapes.MODEL_KEYS)
    assert shapes.held_experts(model)[1] - shapes.held_experts(model)[0] == \
        model["num_experts"]
    sliding, full = shapes.layer_kinds(model)
    assert sliding + full == model["num_hidden_layers"] and full >= 1
    assert shapes.attn_calls_per_step(model) == model["num_hidden_layers"]
    for m in got["per_layer"]:
        assert callable(contract.load_reader(bench, m["name"]))
    names = {m["name"] for m in got["end_to_end"]}
    assert names == {"setup_s", "tpot_p50_ms"}


def test_the_sum_of_contexts_bounds_the_sliding_layers_from_below():
    """``decode_attn_bytes`` of a SUM of contexts is at most what any
    split of it over rows of at most ``max_position_embeddings`` reads
    (``attn_window_bytes`` of the rows' ``min(context, window)`` beside
    the full layers): no accepted roofline can pass 100 % through it."""
    bench = contract.load_benchmark()
    model = contract.resolve_cell(
        bench, "trinity-longshort-saturated")["config"]["model"]
    shapes = contract.load_family(FAMILY, "shapes")
    W, most = model["sliding_window"], model["max_position_embeddings"]
    for rows in ([most] * 3, [600, 9000, 4096, 50], [W] * 64,
                 [most, 1, 1, 1], [300] * 64):
        total = sum(rows)
        exact = (shapes.kv_layer_bytes(model, 2) * total
                 + shapes.attn_window_bytes(
                     model, 2, sum(min(c, W) for c in rows)))
        assert shapes.decode_attn_bytes(model, 2, len(rows),
                                        total) <= exact + 1e-6, rows
    assert shapes.decode_attn_bytes(model, 2, 3, 3 * most) == (
        shapes.kv_layer_bytes(model, 2) * 3 * most
        + shapes.attn_window_bytes(model, 2, 3 * W))


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


def test_the_parent_process_stays_off_jax_for_the_cell():
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r)\n"
         "from benchmark.harness import contract, readers\n"
         "b = contract.load_benchmark()\n"
         "c = contract.resolve_cell(b, 'trinity-longshort-saturated')\n"
         "s = readers.family_shapes(c)\n"
         "s.decode_step_bytes(c['config']['model'], 2, 2, 64, 2.7e5)\n"
         "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
         % ROOT], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_the_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    """``run.py`` on the toy, CPU, tracing off: the family's adapter
    registers the file, the check judges the toy's prompt through both
    caches, the engine serves the mix with no failed request."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tiny-afmoe-longshort", "--seed", "3400000123",
         "--seconds", "8", "--trace", "0", "--benchmark-file", REHEARSAL,
         "--platform", "cpu"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 20
    assert set(line["metrics"]) == {"tpot_p50_ms", "setup_s"}
