"""The family ``xing`` (PR 58) under the contract, in ``BENCHMARK.json``
and in a rehearsal of its own (``data/rehearsal_xing.json``: a toy of
the same block — four streams, a low-rank query, a YaRN table of 64
positions, 16 experts — under ``tiny_saturated``), the issue's
arithmetic by the family's ``shapes``, and the four readers the PR
brought (``hc_mix_ms``, ``hc_mix_roofline``, ``latent_prefill_roofline``,
``hc_row_sum_err``) on a hand-made capture and on captures without their
scopes or counts, where they give nothing and raise nothing. Every entry
of ``BENCHMARK.json`` is looked up BY NAME: a later PR appends."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.harness import contract, scopes
from benchmark.harness.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
REHEARSAL = os.path.join(DATA, "rehearsal_xing.json")
FAMILY = os.path.join(ROOT, "benchmark", "families", "xing")
CELL, CONFIG = "xing4-longdoc-saturated", "xing4.0-29b-a4b-bf16-pp7"
NEW = ("hc_mix_ms", "hc_mix_roofline", "latent_prefill_roofline",
       "hc_row_sum_err")


def _named(entries, name):
    got = [e for e in entries if e["name"] == name]
    assert len(got) == 1, name
    return got[0]


@pytest.mark.parametrize("bench_file,cell", [
    (None, CELL), (REHEARSAL, "tiny-xing-saturated")],
    ids=["BENCHMARK.json", "rehearsal_xing.json"])
def test_the_cell_resolves_to_the_family_with_the_whole_surface(bench_file,
                                                                cell):
    bench = contract.load_benchmark(bench_file)
    assert contract.check_names(bench) == []
    got = contract.resolve_cell(bench, cell)
    assert got["family_dir"] == FAMILY and got["config"]["family"] == "xing"
    shapes = contract.load_family(FAMILY, "shapes")
    assert all(hasattr(shapes, n) for n in contract.FAMILY_SURFACE["shapes"])
    assert set(got["config"]["model"]) == set(shapes.MODEL_KEYS)
    for m in got["per_layer"]:
        assert callable(contract.load_reader(bench, m["name"]))
    names = {m["name"] for m in got["end_to_end"]}
    assert names == {"setup_s", "tpot_p50_ms"}
    assert {m["moves"] for m in got["per_layer"]} <= names
    assert set(NEW) <= {m["name"] for m in got["per_layer"]}


def test_the_cell_is_the_issues():
    """40 clients on 32 rows over the EXISTING long-document mix, one
    chip; one configuration, one cell and four metrics added, each by
    its name; the accepted lists the cell joins, and the two it does
    not."""
    bench = contract.load_benchmark()
    cell = _named(bench["workloads"], CELL)
    entry = _named(bench["configs"], CONFIG)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc_saturated", 1)
    assert entry["reduced"] == ["num_hidden_layers",
                                "max_position_embeddings"]
    assert entry["source"].endswith("Xing4.0-29B-A4B/blob/main/config.json")
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    got = contract.resolve_cell(bench, CELL)
    traffic, ex = got["traffic"], got["config"]["server"]["executor"]
    assert traffic["loop"] == "closed" and traffic["requests_per_client"] == 8
    assert round(traffic["clients_per_row"] * ex["max_batch_size"]) == 40
    assert (ex["max_batch_size"], ex["page_size"]) == (32, 128)
    assert got["config"]["max_position_embeddings"] == 32768 + 2048
    names = {m["name"] for m in got["per_layer"]}
    assert {"plain_decode_step_ms", "mixed_step_ms", "mixed_step_share",
            "slices_dense_ms", "decode_dense_ms", "mixed_slice_live_share",
            "device_unscoped_share", "idle_wait_empty_share",
            "idle_wait_starved_share", "fill_short_share",
            "moe_experts_touched", "moe_load_max_over_mean", "moe_route_ms",
            "slices_attn_ms", "decode_attn_roofline",
            "decode_step_roofline"} | set(NEW) <= names
    # (``moe_ffn_roofline``'s reader takes two ``gmm`` calls for a layer
    # run; this family's mixed step multiplies the live pairs a BLOCK at
    # a time, as Mellum's and Solar's: PERF.md section 6, PR 52 and 58)
    assert "moe_ffn_roofline" not in names
    for name, unit, source, layer in (
            ("hc_mix_ms", "ms", "device_trace",
             "model step (models/llama.py)"),
            ("hc_mix_roofline", "%", "device_trace", "kernels (ops/pallas/)"),
            ("latent_prefill_roofline", "%", "device_trace",
             "kernels (ops/pallas/)"),
            ("hc_row_sum_err", "ppm", "program_counter",
             "model step (models/llama.py)")):
        new = _named(bench["per_layer"], name)
        assert new["workloads"] == [CELL]
        assert (new["moves"], new["source"], new["unit"], new["layer"]) == (
            "tpot_p50_ms", source, unit, layer)


def test_what_the_stage_holds_is_what_the_issue_reckoned():
    """The issue's arithmetic by ``shapes.py``: a site 344,091
    parameters, attention 28,411,136 a layer, a routed layer 744,989,046
    and the dense one 128,196,918, 4,792,669,828 held = 9.59 GB, 29.5 B
    at the published depth and 4.4 B a token; 6,912 B of cache a token;
    the sites' least traffic at a full mixed step."""
    got = contract.resolve_cell(contract.load_benchmark(), CELL)
    shapes = contract.load_family(FAMILY, "shapes")
    held = got["config"]["model"]
    assert shapes.site_params(held) == 344_091
    norms = held["kv_lora_rank"] + held["q_lora_rank"]
    assert shapes.attn_params(held) + norms == 28_411_136
    one = dict(held, num_hidden_layers=1)
    assert (shapes.param_count(dict(one, dense_layers_held=1))
            - 939_524_096 - 3_584) == 128_196_918
    assert (shapes.param_count(dict(one, dense_layers_held=0))
            - 939_524_096 - 3_584) == 744_989_046
    assert shapes.param_count(held) == 4_792_669_828
    assert "4,792,669,828 parameters" in got["config"]["deployment"]
    assert round(shapes.weight_bytes(held, 2) / 1e9, 2) == 9.59
    whole = dict(held, num_hidden_layers=40, dense_layers_held=2)
    assert round(shapes.param_count(whole) / 1e9, 1) == 29.5
    assert round(shapes.active_param_count(whole) / 1e9, 1) == 4.4
    assert shapes.kv_bytes_per_token(held, 2) == 6_912
    assert round(shapes.experts_touched(held, 32), 1) == 55.9
    # a full budget's sites: the streams in and out, twelve sites
    assert shapes.hc_mix_bytes(held, 8192) == 12 * (
        8192 * 2 * 4 * 3584 * 4 + 344_091 * 4)
    # 512 queries a slice: the expanded form is the fewer operations
    keys, q = 20_000.0, 512.0
    pairs = q * keys
    assert shapes.latent_prefill_flops(held, keys, q, pairs) == (
        2 * 512 * 32 * 256 * keys + 2 * 32 * 320 * pairs)
    assert shapes.latent_prefill_flops(held, keys, 8.0, 8 * keys) < (
        2 * 512 * 32 * 256 * keys)


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
         "c = contract.resolve_cell(b, %r)\n"
         "s = readers.family_shapes(c)\n"
         "s.decode_step_bytes(c['config']['model'], 2, 2, 32, 5e5)\n"
         "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
         % (ROOT, CELL)], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


# -- the four new readers -------------------------------------------------------

MIX_NS, ATTN_NS, LOOP_MIX_NS = 30_000_000, 200_000_000, 1_500_000
#: the decode attention's name in a trace: what counts a loop's steps
DEC = "latent_decode_attention_pallas.6"


def _capture(tmp_path, with_scopes=True, with_counts=True):
    """A hand-made capture of the cell: ONE whole ``jit_mixed_chunk``
    run whose mixed step holds the sites (two fusions), the slices'
    prefill attention and a product, and a decode loop of ONE step (six
    decode attention calls) with the sites' fusion; two dispatches carry
    ``hc_rows_live`` 4,128 and 6,176, two commits the live keys and
    pairs, the routed counters and ``hc_row_sum_err``."""
    d = tmp_path / "trace0"
    d.mkdir()
    mixed = "jit_mixed_chunk(7)"
    step = "jit(x)/mixed_step/jit(forward_mixed)/"
    loop = "jit(x)/decode_loop/while"
    names = ["project.1", "apply.2", "pf_attn.3", "mlp.4", "while.5", DEC,
             "mix.7"]
    at = {n: i for i, n in enumerate(names)}
    hc = "hc_mix/" if with_scopes else "mlp/"
    paths = {"project.1": step + hc + ("hc_project/" if with_scopes else "")
             + "dot_general",
             "apply.2": step + hc + ("hc_apply/" if with_scopes else "")
             + "fusion",
             "pf_attn.3": step + "slices/" + (
                 "attn_full/attn/latent_prefill_attention/while"
                 if with_scopes else "attn/while"),
             "mlp.4": step + "mlp/dot_general",
             "while.5": loop,
             DEC: loop + "/body/jit(forward_decode)/attn/"
                         "latent_decode_attention_pallas",
             "mix.7": loop + "/body/jit(forward_decode)/" + hc + "fusion"}
    t = 1_000_000
    ops = []
    for name, ns in (("project.1", MIX_NS // 3), ("apply.2", 2 * MIX_NS // 3),
                     ("pf_attn.3", ATTN_NS), ("mlp.4", 20_000_000)):
        ops.append([at[name], t, ns, 0])
        t += ns
    ops.append([at["while.5"], t, 17_000_000, 0])
    for k in range(6):
        ops.append([at[DEC], t + 100 + k * 1_000_000, 600_000, 0])
    ops.append([at["mix.7"], t + 8_000_000, LOOP_MIX_NS, 0])
    end = t + 17_000_000
    (d / scopes.NEUTRAL_FILE).write_text(json.dumps({
        "vocabulary": scopes.program_vocabulary(),
        "modules": {mixed: paths},
        "planes": [{"name": "/device:TPU:0", "t0_ns": 0.0, "lo_ns": 0.0,
                    "hi_ns": float(end + 1_000_000), "names": names,
                    "runs": [[mixed, 1_000_000, end - 1_000_000]],
                    "ops": ops}]}))
    rows = ({"hc_rows_live": 4128}, {"hc_rows_live": 6176})
    live = ({"pf_live_keys": 90_000, "pf_live_pairs": 40_000_000,
             "hc_row_sum_err": 48},
            {"pf_live_keys": 110_000, "pf_live_pairs": 60_000_000,
             "hc_row_sum_err": 32})
    events = [["engine.step", 0.0, float(end)]]
    for k, (r, c) in enumerate(zip(rows, live)):
        events.append(["engine.dispatch", 10.0 + k * 5e6, 10.0, dict(
            program="mixed_chunk", steps=16, rows=30, prefill_tokens=4000,
            slice_tokens=4096 + 2048 * k, **(r if with_counts else {}))])
        events.append(["engine.commit", 2e6 + k * 5e6, 10.0, dict(
            moe_layer_runs=80, moe_touched=4400, moe_pairs=60000,
            pf_key_blocks=480, pf_table_blocks=1088,
            **(c if with_counts else {}))])
    (d / "spans_neutral.json").write_text(json.dumps({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "thread": 0,
             "events": [["busy", 0.0, float(end)]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "thread": 1, "events": events}]}]}))
    bench = contract.load_benchmark()
    got = contract.resolve_cell(bench, CELL)
    return bench, {"captures": [{"dir": str(d), "reduced": {"devices": 1},
                                 "samples": [{"rows": 30.0,
                                              "context_tokens": 30 * 14e3}]}],
                   "config": got["config"], "family_dir": got["family_dir"],
                   "device": {"kind": "TPU v5 lite"}, "requests": []}


def test_the_new_readers_on_a_hand_made_capture_of_the_cell(tmp_path):
    bench, run = _capture(tmp_path)
    read = {n: contract.load_reader(bench, n)(run) for n in NEW}
    shapes = contract.load_family(FAMILY, "shapes")
    model, pk = run["config"]["model"], peaks_for("TPU v5 lite")
    # one plain step in the capture: its sites' fusion
    assert read["hc_mix_ms"] == pytest.approx(LOOP_MIX_NS / 1e6)
    # the mean dispatch's rows against the mixed step's two fusions
    rows = (4128 + 6176) / 2
    least = max(shapes.hc_mix_bytes(model, rows) / pk["hbm_bytes_per_s"],
                shapes.hc_mix_flops(model, rows) / pk["bf16_flops"])
    assert read["hc_mix_roofline"] == pytest.approx(
        100 * least / (MIX_NS / 1e9), rel=1e-6)
    assert 5 < read["hc_mix_roofline"] < 100
    keys, pairs = 100_000.0, 50_000_000.0
    least = 6 * shapes.latent_prefill_flops(model, keys, 4000.0,
                                            pairs) / pk["bf16_flops"]
    assert 6 * shapes.latent_prefill_bytes(
        model, 2, keys, 4000.0) / pk["hbm_bytes_per_s"] < least
    assert read["latent_prefill_roofline"] == pytest.approx(
        100 * least / (ATTN_NS / 1e9), rel=1e-6)
    # 160 routed-layer runs over 5 routed layers: 32 steps, 80 ppm summed
    assert read["hc_row_sum_err"] == pytest.approx(80 / 32)
    # the accepted readers the cell is appended to read the same capture
    for n in ("slices_attn_ms", "mixed_step_ms", "plain_decode_step_ms",
              "moe_experts_touched"):
        assert contract.load_reader(bench, n)(run) is not None, n
    assert contract.load_reader(bench, "slices_attn_ms")(run) == (
        pytest.approx(ATTN_NS / 1e6))


@pytest.mark.parametrize("lacks", ["scopes", "counts", "capture"])
def test_a_program_without_the_scope_or_the_count_gives_them_nothing(
        tmp_path, lacks):
    """A parent of PR 58 under this PR's benchmark files: no ``hc_mix``
    scope, or no ``hc_rows_live`` / ``pf_live_keys`` / ``hc_row_sum_err``
    on its spans, or no capture at all — ``None``, and nothing raises;
    nor for another latent family's recorded capture (LongCat's)."""
    bench, run = _capture(tmp_path, with_scopes=lacks != "scopes",
                          with_counts=lacks != "counts")
    if lacks == "capture":
        run["captures"] = []
    got = {n: contract.load_reader(bench, n)(run) for n in NEW}
    if lacks == "counts":       # a scope needs no count,
        assert got.pop("hc_mix_ms") is not None
    if lacks == "scopes":       # and a counter no scope
        assert got.pop("hc_row_sum_err") == 2.5
    assert set(got.values()) == {None}, got
    assert contract.load_reader(bench, "hc_mix_ms")({}) is None
    other = os.path.join(DATA, "scopes_longcat_mixed_chunk.json")
    d = tmp_path / "other"
    d.mkdir()
    import shutil
    shutil.copy(other, d / scopes.NEUTRAL_FILE)
    cell = contract.resolve_cell(bench, "longcat-decode-saturated")
    run = {"captures": [{"dir": str(d), "reduced": {"devices": 1},
                         "samples": []}], "config": cell["config"],
           "family_dir": cell["family_dir"],
           "device": {"kind": "TPU v5 lite"}, "requests": []}
    assert [contract.load_reader(bench, n)(run) for n in NEW] == [None] * 4


def test_the_rehearsal_runs_the_cell_end_to_end_on_the_cpu():
    """``run.py`` on the toy, CPU, tracing off: the family's adapter
    registers the file, the check judges the toy's own sequence through
    a prefill, mixed steps and decode steps of one latent pool, the
    engine serves the mix with no failed request."""
    if os.environ.get("BENCH_SELFTEST_FAST"):
        pytest.skip("BENCH_SELFTEST_FAST")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tiny-xing-saturated", "--seed", "5800000123",
         "--seconds", "8", "--trace", "0", "--benchmark-file", REHEARSAL,
         "--platform", "cpu"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 20
    assert set(line["metrics"]) == {"tpot_p50_ms", "setup_s"}
