"""The three readers of the state-space scopes (``ssm_update_ms``,
``ssm_update_roofline``, ``ssm_scan_ms``: PR 39) on a recorded capture of
the cell that has them, on one of a cell that has not, and the entries
that PR appended to ``BENCHMARK.json``.

``data/scopes_granite4h_mixed_chunk.json`` is 700 ms of a traced chip
run of ``granite4h-decode-saturated`` in the neutral form
(``harness/scopes.py <trace_dir> <out.json> 700``): one whole
``jit_mixed_chunk`` run — a mixed step and the decode loop's steps
after it — with the instructions that ran there."""

import json
import os
import shutil

import pytest

from benchmark.harness import contract, scopes
from benchmark.harness.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
CELL = "granite4h-decode-saturated"
SAMPLE = "scopes_granite4h_mixed_chunk.json"
NEW = ("ssm_update_ms", "ssm_update_roofline", "ssm_scan_ms")
ROWS = 61.0


@pytest.fixture(scope="module")
def bench():
    return contract.load_benchmark()


def _run(tmp_path, bench, data_file, cell):
    """A run as the readers see it: the first capture's directory holds
    ``data_file`` as the cached neutral form, and the capture its
    samples of the load (``harness/readers.mean_load``)."""
    path = os.path.join(DATA, data_file)
    if not os.path.exists(path):
        pytest.skip("no recorded sample yet")
    d = tmp_path / "trace0"
    d.mkdir()
    shutil.copy(path, d / scopes.NEUTRAL_FILE)
    got = contract.resolve_cell(bench, cell)
    return {"captures": [{"dir": str(d), "reduced": {"devices": 1},
                          "samples": [{"rows": ROWS,
                                       "context_tokens": ROWS * 900}]}],
            "config": got["config"], "family_dir": got["family_dir"],
            "device": {"kind": "TPU v5 lite"}, "requests": []}


def test_the_three_readers_on_a_recorded_capture(tmp_path, bench):
    run = _run(tmp_path, bench, SAMPLE, CELL)
    read = {n: contract.load_reader(bench, n)(run) for n in NEW}
    red = scopes.of_run(run)
    steps = scopes.plain_steps(run)
    # four attention layers a step: the decode attention calls under
    # ``decode_loop`` over four
    assert steps == scopes.decode_attn_calls(red) / 4 and steps >= 8
    update = scopes.under(red, scopes.LOOP, ("ssm_update",)) / steps
    conv = scopes.under(red, scopes.LOOP, ("ssm_conv",)) / steps
    assert update > 0 and conv > 0
    assert read["ssm_update_ms"] == pytest.approx((update + conv) * 1e3)
    # by the scope: most of a plain step (ISSUE 39: 58 % of its bytes),
    # and never more than the step
    plain = contract.load_reader(bench, "plain_decode_step_ms")(run)
    assert 0.3 * plain < read["ssm_update_ms"] < plain
    shapes = contract.load_family(run["family_dir"], "shapes")
    nbytes = shapes.ssm_update_bytes(run["config"]["model"], ROWS)
    assert nbytes == ROWS * 36 * 2 * 128 * 4096 * 4
    least = nbytes / peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert read["ssm_update_roofline"] == pytest.approx(
        100 * least / update)
    assert 20 < read["ssm_update_roofline"] < 100
    # the scan is of the slices: the decode rows' update inside the
    # mixed step is not in it, and it is less than the mixed step
    mixed = contract.load_reader(bench, "mixed_step_ms")(run)
    runs = scopes.runs_holding(red, scopes.MIXED)
    assert runs >= 1
    assert read["ssm_scan_ms"] == pytest.approx(scopes.under(
        red, scopes.MIXED, ("ssm_conv", "ssm_scan"),
        without=scopes.DECODE_ROWS) / runs * 1e3)
    assert 0 < read["ssm_scan_ms"] < mixed
    assert scopes.under(red, scopes.MIXED, ("ssm_update",)) > 0
    # and the accepted scope metrics read this family with no edit
    for n in ("decode_dense_ms", "slices_dense_ms", "mixed_step_share",
              "device_unscoped_share"):
        assert contract.load_reader(bench, n)(run) is not None, n
    assert contract.load_reader(bench, "device_unscoped_share")(run) < 5


def test_a_program_without_the_scopes_gives_the_readers_nothing(tmp_path,
                                                                bench):
    """SmolLM2's capture (no state-space layer; and so any parent of
    PR 39): nothing under the three scopes, so each reader returns
    ``None`` and none raises — also for a capture with no load samples
    and for no capture at all."""
    run = _run(tmp_path, bench, "scopes_smollm2_mixed_chunk.json",
               "smollm2-decode-saturated")
    for n in NEW:
        assert contract.load_reader(bench, n)(run) is None, n
    sub = tmp_path / "hybrid"
    sub.mkdir()
    run = _run(sub, bench, SAMPLE, CELL)
    run["captures"][0]["samples"] = []
    assert contract.load_reader(bench, "ssm_update_roofline")(run) is None
    run["captures"] = []
    run.pop("_scopes", None)
    for n in NEW:
        assert contract.load_reader(bench, n)(run) is None, n


def test_the_appended_entries_are_within_the_contract(bench):
    assert contract.check_names(bench) == []
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    for n in NEW:
        m = by_name[n]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["moves"], m["source"]) == ("tpot_p50_ms", "device_trace")
        assert m["workloads"] == [CELL] and m["layer"] in layers
    assert by_name["ssm_update_roofline"]["unit"] == "%"
    cell = contract.resolve_cell(bench, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "decode_saturated_deep"
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tpot_p50_ms", "output_tok_s", "setup_s"}
    got = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {"batch_rows_mean", "decode_step_roofline",
                       "decode_attn_roofline", "plain_decode_step_ms",
                       "mixed_step_ms", "mixed_step_share",
                       "slices_dense_ms", "decode_dense_ms",
                       "mixed_slice_live_share",
                       "device_unscoped_share"} <= got
    assert not {m for m in got if m.startswith("moe_")}
    # 80 clients on its 64 rows
    traffic, rows = cell["traffic"], cell["config"]["server"]["executor"][
        "max_batch_size"]
    assert traffic["loop"] == "closed" and rows == 64
    assert traffic["clients_per_row"] * rows == 80


def test_the_kept_cell_is_data_alone(bench):
    """``smollm2-short-steady`` (PERF.md section 7's row 2), if it is in
    the benchmark: a traffic file and an entry on a configuration the
    benchmark had; one steady stretch, nothing shared."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if "smollm2-short-steady" not in cells:
        pytest.skip("the kept cell was left out (CHANGES.md, PR 39)")
    cell = contract.resolve_cell(bench, "smollm2-short-steady")
    assert cell["cell"]["config"] == "smollm2-1.7b-bf16"
    assert {m["name"] for m in cell["end_to_end"]} == {
        "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms", "setup_s"}
    t = cell["traffic"]
    assert t["loop"] == "open" and len(t["segments"]) == 1
    assert "session" not in t and t["ramp_periods"] == 1
    assert [c["range"] for c in t["prompt_tokens"]][0][0] == 32
    assert [c["range"] for c in t["prompt_tokens"]][-1][1] == 128
    assert [c["range"] for c in t["output_tokens"]][0][0] == 16
    assert [c["range"] for c in t["output_tokens"]][-1][1] == 64
    with open(os.path.join(HERE, "..", "traffic", "chat_bursts.json")) as f:
        assert t["tiers"] == json.load(f)["tiers"]
    seg = t["segments"][0]
    assert t["rate"]["mean_per_s"] == pytest.approx(
        seg["arrivals"] / seg["seconds"])
    assert 0.6 <= t["rate"]["mean_per_s"] / t["rate"]["knee_per_s"] <= 0.8
