"""The five readers that came with the family ``afmoe`` (``attn_window_ms``,
``attn_full_ms``, ``attn_window_roofline``, ``window_cache_live_share``,
``window_chunks_skipped_share``: PR 41) on a recorded capture of the
cell that has them, on one of a cell that has not, and the entries that
PR appended to ``BENCHMARK.json``.

``data/scopes_trinity_mixed_chunk.json`` is 400 ms of a traced chip run
of ``trinity-longshort-saturated`` in the neutral form
(``harness/scopes.py <trace_dir> <out.json> 400``): whole
``jit_mixed_chunk`` runs — a mixed step and the decode loop's steps
after it — with the instructions that ran there;
``data/spans_trinity_sample.json`` is the same capture's host side
(``harness/spans.py <trace_dir> <out.json> 1500``): the engine thread's
``engine.*`` spans with the counts the program leaves on them."""

import os
import shutil

import pytest

from benchmark.harness import contract, scopes, spans
from benchmark.harness.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
CELL = "trinity-longshort-saturated"
SCOPES_SAMPLE = "scopes_trinity_mixed_chunk.json"
SPANS_SAMPLE = "spans_trinity_sample.json"
BY_SCOPE = ("attn_window_ms", "attn_full_ms")
BY_COUNTER = ("window_cache_live_share", "window_chunks_skipped_share")
NEW = BY_SCOPE + ("attn_window_roofline",) + BY_COUNTER
ROWS = 60.0


@pytest.fixture(scope="module")
def bench():
    return contract.load_benchmark()


def _run(tmp_path, bench, cell, scopes_file=None, spans_file=None):
    """A run as the readers see it: the first capture's directory holds
    the recorded files as the cached neutral forms."""
    d = tmp_path / "trace0"
    d.mkdir()
    for name, cached in ((scopes_file, scopes.NEUTRAL_FILE),
                         (spans_file, spans.NEUTRAL_FILE)):
        if name is None:
            continue
        path = os.path.join(DATA, name)
        if not os.path.exists(path):
            pytest.skip("no recorded sample yet")
        shutil.copy(path, d / cached)
    got = contract.resolve_cell(bench, cell)
    return {"captures": [{"dir": str(d), "reduced": {"devices": 1},
                          "samples": [{"rows": ROWS,
                                       "context_tokens": ROWS * 4200}]}],
            "config": got["config"], "family_dir": got["family_dir"],
            "device": {"kind": "TPU v5 lite"}, "requests": []}


def test_the_five_readers_on_a_recorded_capture(tmp_path, bench):
    run = _run(tmp_path, bench, CELL, SCOPES_SAMPLE, SPANS_SAMPLE)
    read = {n: contract.load_reader(bench, n)(run) for n in NEW}
    red = scopes.of_run(run)
    steps = scopes.plain_steps(run)
    # five attention layers a step, of either kind
    assert steps == scopes.decode_attn_calls(red) / 5 and steps >= 8
    window = scopes.under(red, scopes.LOOP, ("attn_window",)) / steps
    full = scopes.under(red, scopes.LOOP, ("attn_full",)) / steps
    assert read["attn_window_ms"] == pytest.approx(window * 1e3)
    assert read["attn_full_ms"] == pytest.approx(full * 1e3)
    # both lie under the accepted ``attn`` scope, and are less than the
    # plain step the accepted reader gives
    plain = contract.load_reader(bench, "plain_decode_step_ms")(run)
    both = scopes.under(red, scopes.LOOP, ("attn",)) / steps * 1e3
    assert both == pytest.approx(read["attn_window_ms"]
                                 + read["attn_full_ms"])
    assert 0 < both < plain
    # the roofline: the program's window-bounded counter, exactly
    got = [d for d in spans.chunks(run) if "window_tokens" in d]
    assert got and all(d["window_tokens"] <= d["context_tokens"]
                       for d in got)
    tokens = sum(d["window_tokens"] for d in got) / len(got)
    shapes = contract.load_family(run["family_dir"], "shapes")
    nbytes = shapes.attn_window_bytes(run["config"]["model"], 2, tokens)
    assert nbytes == 4 * 4096 * tokens
    least = nbytes / peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert read["attn_window_roofline"] == pytest.approx(
        100 * least / window)
    assert 20 < read["attn_window_roofline"] < 100
    # the counters: what the slabs hold of what they reserve, and the
    # visits the window start saved
    assert all(d["window_reserved"] == 64 * 41 * 128 for d in got)
    assert read["window_cache_live_share"] == pytest.approx(
        100 * sum(d["window_live"] for d in got)
        / sum(d["window_reserved"] for d in got))
    assert 10 < read["window_cache_live_share"] < 4096 / (41 * 128) * 100
    assert 0 < read["window_chunks_skipped_share"] < 100
    # and the accepted readers read this family with no edit
    for n in ("decode_dense_ms", "slices_dense_ms", "mixed_step_share",
              "mixed_step_ms", "device_unscoped_share"):
        assert contract.load_reader(bench, n)(run) is not None, n
    assert contract.load_reader(bench, "device_unscoped_share")(run) < 5


def test_a_program_without_the_scopes_and_counters_gives_nothing(tmp_path,
                                                                 bench):
    """SmolLM2's capture (no window layer; and so any parent of PR 41):
    nothing under the two scopes and no counter on a dispatch, so each
    reader returns ``None`` and none raises — also for no capture at
    all."""
    run = _run(tmp_path, bench, "smollm2-decode-saturated",
               "scopes_smollm2_mixed_chunk.json",
               "trace_spans_chip_sample.json")
    for n in NEW:
        assert contract.load_reader(bench, n)(run) is None, n
    run["captures"] = []
    run.pop("_scopes", None)
    run.pop("_spans", None)
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
        assert m["moves"] == "tpot_p50_ms"
        assert m["workloads"] == [CELL] and m["layer"] in layers
        assert m["source"] == ("program_counter" if n in BY_COUNTER
                               else "device_trace")
    assert by_name["attn_window_roofline"]["unit"] == "%"
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW)
    cell = contract.resolve_cell(bench, CELL)
    assert bench["workloads"][-1] == cell["cell"]
    assert cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "longshort_saturated"
    # no ``output_tok_s``: which prompts fall into a 48 s window moves it
    # by more than a new cell may spread (PERF.md section 6, PR 41)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tpot_p50_ms", "setup_s"}
    got = {m["name"] for m in cell["per_layer"]}
    assert "batch_rows_mean" not in got      # it moves ``output_tok_s``
    assert set(NEW) | {"decode_rows_mean", "decode_step_roofline",
                       "decode_attn_roofline", "plain_decode_step_ms",
                       "mixed_step_ms", "mixed_step_share",
                       "slices_dense_ms", "decode_dense_ms",
                       "mixed_slice_live_share", "device_unscoped_share",
                       "moe_experts_touched", "moe_load_max_over_mean",
                       "moe_held_pairs_per_expert"} <= got
    assert "moe_zero_slot_share" not in got
    # its reader holds the counts of the chunks COMMITTED in a capture
    # against the kernel time of the chunks that RAN in it; this cell's
    # chunks differ (what prompt each carries) and it read 92.6-103.8 %
    # over four traced runs: no share may pass 105 % (PERF.md section 7)
    assert "moe_ffn_roofline" not in got
    # 80 clients on its 64 rows, three prompt bands in one queue
    traffic, rows = cell["traffic"], cell["config"]["server"]["executor"][
        "max_batch_size"]
    assert traffic["loop"] == "closed" and rows == 64
    assert traffic["clients_per_row"] * rows == 80
    assert traffic["requests_per_client"] >= 8
    assert [(c["range"], c["share"]) for c in traffic["prompt_tokens"]] == [
        ([256, 1024], 0.6), ([5120, 8192], 0.25), ([8192, 12288], 0.15)]
    assert [(c["range"], c["share"]) for c in traffic["output_tokens"]] == [
        ([512, 1024], 0.4), ([1024, 1536], 0.35), ([1536, 2048], 0.25)]
    assert traffic["ramp_s"] >= 24 and traffic["trace_at"] == [0.3, 0.6]
    assert traffic["trace_seconds"] == 3.0
    assert [t["name"] for t in traffic["tiers"]] == ["low"]
    assert traffic["tiers"][0]["timeout_s"] == 900
    # the longest request fits the block table
    assert 12288 + 2048 <= cell["config"]["max_position_embeddings"]
