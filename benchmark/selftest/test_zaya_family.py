"""The family ``zaya`` (PR 48) under the contract, in ``BENCHMARK.json``
and in a rehearsal of its own (``data/rehearsal_zaya.json``: a toy of the
same block — three layers of compressed convolutional attention and a
top-1 router network with its carry — under ``tiny_saturated``), and the
two readers it brings on a synthetic capture: ``cca_mix_ms`` and
``moe_route_ms`` read their scopes under ``decode_loop`` over the plain
decode steps, and give nothing for a program without them. What
``test_ling_family.py`` holds its family to, for the one that came
after."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import contract, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
REHEARSAL = os.path.join(DATA, "rehearsal_zaya.json")
FAMILY = os.path.join(ROOT, "benchmark", "families", "zaya")
CELL = "zaya1-reasoning-saturated"
NEW = ("cca_mix_ms", "moe_route_ms")


@pytest.mark.parametrize("bench_file,cell", [
    (None, CELL), (REHEARSAL, "tiny-zaya-saturated")],
    ids=["BENCHMARK.json", "rehearsal_zaya.json"])
def test_the_cell_resolves_to_the_family_with_the_whole_surface(bench_file,
                                                                cell):
    bench = contract.load_benchmark(bench_file)
    assert contract.check_names(bench) == []
    got = contract.resolve_cell(bench, cell)
    assert got["family_dir"] == FAMILY
    assert got["config"]["family"] == "zaya"
    shapes = contract.load_family(FAMILY, "shapes")
    assert all(hasattr(shapes, n) for n in contract.FAMILY_SURFACE["shapes"])
    model = got["config"]["model"]
    assert set(model) <= set(shapes.MODEL_KEYS)
    assert shapes.attn_calls_per_step(model) == model["num_hidden_layers"]
    for m in got["per_layer"]:
        assert callable(contract.load_reader(bench, m["name"]))
    assert {"setup_s", "tpot_p50_ms"} <= {m["name"]
                                         for m in got["end_to_end"]}
    assert set(NEW) <= {m["name"] for m in got["per_layer"]}


def test_the_cell_is_the_issues():
    """80 clients on 64 rows, one closed loop, prompts 512-2,048 and
    outputs 1,024-4,096, one chip; one configuration, one cell and two
    metrics appended, nothing else of the file moved."""
    bench = contract.load_benchmark()
    got = contract.resolve_cell(bench, CELL)
    assert got["cell"]["chips"] == 1
    assert got["cell"]["traffic"] == "reasoning_saturated"
    assert len(got["cell"]["why"]) <= 200
    traffic, ex = got["traffic"], got["config"]["server"]["executor"]
    assert traffic["loop"] == "closed"
    assert round(traffic["clients_per_row"] * ex["max_batch_size"]) == 80
    assert (ex["max_batch_size"], ex["page_size"], ex["prefill_buckets"],
            ex["decode_chunk"]) == (64, 128, [512], 16)
    assert got["config"]["max_position_embeddings"] == 2048 + 4096
    assert bench["configs"][-1]["name"] == "zaya1-8b-bf16-pp2"
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW)
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
        assert (m["unit"], m["source"]) == ("ms", "device_trace")
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | {
        "plain_decode_step_ms", "mixed_step_ms", "mixed_step_share",
        "slices_dense_ms", "decode_dense_ms", "mixed_slice_live_share",
        "device_unscoped_share", "moe_experts_touched",
        "moe_load_max_over_mean", "moe_ffn_roofline"}


def test_what_a_step_must_move_is_what_the_issue_reckoned():
    bench = contract.load_benchmark()
    model = contract.resolve_cell(bench, CELL)["config"]["model"]
    shapes = contract.load_family(FAMILY, "shapes")
    assert shapes.cca_params(model) + 2 * 1280 + 2 == 5_575_682
    assert shapes.router_params(model) + 5 * 256 + 16 == 660_752
    assert 16 * shapes.expert_params(model) == 201_326_592
    assert shapes.param_count(model) == 4_688_805_224
    assert shapes.param_count(dict(model, num_hidden_layers=40)) \
        == 8_840_475_344
    assert shapes.kv_bytes_per_token(model, 2) == 20 * 1024
    assert shapes.state_bytes_per_row(model) == 20 * 2688 * 4
    assert 15.6 < shapes.experts_touched(model, 64) < 15.8
    step = shapes.decode_step_bytes(model, 2, 2, 64, 64 * 2400)
    assert 12.2e9 < step < 12.8e9
    routed = 20 * shapes.moe_ffn_bytes(model, 2,
                                       shapes.experts_touched(model, 64))
    assert 0.62 < routed / step < 0.66
    assert 0.24 < shapes.decode_attn_bytes(model, 2, 64, 64 * 2400) / step \
        < 0.27
    assert shapes.rope_theta(model) == 5e6 and shapes.rotary_dim(model) == 64


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
         "s.decode_step_bytes(c['config']['model'], 2, 2, 64, 1.5e5)\n"
         "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
         % (ROOT, CELL)], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


# -- the two new readers on a synthetic capture ---------------------------------

STEPS, LAYERS = 4, 3
#: self time in ns of one event under each path of one decode step's
#: layer: what the readers must add up
NS = {"cca_mix": 700.0, "qkv": 100.0, "attn": 900.0, "attn_out": 200.0,
      "moe_route": 500.0, "moe_experts": 3000.0}


def _synthetic(vocabulary, with_scopes=True):
    """A neutral capture (``harness/scopes.py``'s form) of ONE whole run
    of a chunk program: ``STEPS`` decode steps of ``LAYERS`` layers under
    ``decode_loop``, each layer one event a path and one decode attention
    kernel call; a program ``with_scopes`` False names the mix and the
    router's work by no scope of the vocabulary."""
    mod = "jit_decode_chunk(48)"
    names, ins, ops = [], {}, []
    t = 10_000.0

    def event(kind, path, dur):
        nonlocal t
        name = f"{kind}.{len(names)}"
        names.append(name)
        ins[name] = f"jit(decode_chunk)/jit(main)/{path}/{kind}"
        ops.append([len(names) - 1, t, dur, 0])
        t += dur + 10.0

    for _ in range(STEPS):
        for _ in range(LAYERS):
            for scope, dur in NS.items():
                kind = ("fused_decode_attention_pallas" if scope == "attn"
                        else "fusion")
                if not with_scopes and scope in ("cca_mix", "moe_route"):
                    scope = "qkv" if scope == "cca_mix" else "mlp"
                event(kind, f"decode_loop/while/body/{scope}", dur)
    return {"vocabulary": vocabulary, "modules": {mod: ins},
            "planes": [{"name": "/device:TPU:0", "t0_ns": 0.0, "lo_ns": 0.0,
                        "hi_ns": t + 50_000.0, "names": names,
                        "runs": [[mod, 9_000.0, t - 8_000.0]], "ops": ops}]}


def _run(tmp_path, trace, cell=CELL, bench=None):
    bench = bench or contract.load_benchmark()
    d = tmp_path / "trace0"
    d.mkdir()
    with open(d / scopes.NEUTRAL_FILE, "w") as f:
        json.dump(trace, f)
    got = contract.resolve_cell(bench, cell)
    model = dict(got["config"]["model"], num_hidden_layers=LAYERS)
    return {"captures": [{"dir": str(d), "reduced": {"devices": 1},
                          "samples": [{"rows": 64.0,
                                       "context_tokens": 64 * 2400.0}]}],
            "config": dict(got["config"], model=model),
            "family_dir": got["family_dir"],
            "device": {"kind": "TPU v5 lite"}, "requests": []}


def test_the_two_readers_on_a_synthetic_capture(tmp_path):
    from llmq_tpu.utils.profiling import SCOPES
    assert "cca_mix" in SCOPES and "moe_route" in SCOPES
    bench = contract.load_benchmark()
    run = _run(tmp_path, _synthetic(list(SCOPES)))
    assert scopes.plain_steps(run) == STEPS
    read = {n: contract.load_reader(bench, n)(run) for n in NEW}
    assert read["cca_mix_ms"] == pytest.approx(LAYERS * NS["cca_mix"] / 1e6)
    assert read["moe_route_ms"] == pytest.approx(
        LAYERS * NS["moe_route"] / 1e6)
    plain = contract.load_reader(bench, "plain_decode_step_ms")(run)
    assert plain == pytest.approx(LAYERS * sum(NS.values()) / 1e6)
    assert contract.load_reader(bench, "decode_dense_ms")(run) \
        == pytest.approx(LAYERS * (NS["qkv"] + NS["attn_out"]) / 1e6)


@pytest.mark.parametrize("case", ["no-such-scope-in-the-vocabulary",
                                  "no-work-under-the-scopes",
                                  "no-capture"])
def test_a_program_without_the_scopes_gives_the_readers_nothing(tmp_path,
                                                                case):
    """A parent of PR 48 (a vocabulary without ``cca_mix``), a family
    that names nothing so, and a run with no capture: ``None``, and
    nothing raises."""
    from llmq_tpu.utils.profiling import SCOPES
    bench = contract.load_benchmark()
    vocabulary = [s for s in SCOPES if s != "cca_mix"]
    if case == "no-such-scope-in-the-vocabulary":
        run = _run(tmp_path, _synthetic(vocabulary))
        assert contract.load_reader(bench, "cca_mix_ms")(run) is None
        assert contract.load_reader(bench, "moe_route_ms")(run) > 0
        return
    run = _run(tmp_path, _synthetic(list(SCOPES), with_scopes=False))
    if case == "no-capture":
        run["captures"] = []
    for name in NEW:
        assert contract.load_reader(bench, name)(run) is None, name


def test_granites_recorded_capture_gives_the_new_readers_nothing(tmp_path):
    """A recorded capture of a family with neither scope."""
    bench = contract.load_benchmark()
    path = os.path.join(DATA, "scopes_granite4h_mixed_chunk.json")
    d = tmp_path / "trace0"
    d.mkdir()
    shutil.copy(path, d / scopes.NEUTRAL_FILE)
    got = contract.resolve_cell(bench, "granite4h-decode-saturated")
    run = {"captures": [{"dir": str(d), "reduced": {"devices": 1},
                         "samples": [{"rows": 64.0,
                                      "context_tokens": 64 * 700.0}]}],
           "config": got["config"], "family_dir": got["family_dir"],
           "device": {"kind": "TPU v5 lite"}, "requests": []}
    for name in NEW:
        assert contract.load_reader(bench, name)(run) is None, name


def test_the_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    """``run.py`` on the toy, CPU, tracing off: the family's adapter
    registers the file, the check judges the toy's own sequence through
    slices, mixed steps and decode steps, the engine serves the mix with
    no failed request."""
    if os.environ.get("BENCH_SELFTEST_FAST"):
        pytest.skip("BENCH_SELFTEST_FAST")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tiny-zaya-saturated", "--seed", "4800000123",
         "--seconds", "8", "--trace", "0", "--benchmark-file", REHEARSAL,
         "--platform", "cpu"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 20
    assert set(line["metrics"]) == {"tpot_p50_ms", "setup_s"}
