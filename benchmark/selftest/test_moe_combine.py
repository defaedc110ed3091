"""``moe_combine_ms`` (PR 59): a mixed step's device time under the
program's ``moe_combine`` scope, on the recorded captures the selftests
have — three cut from routed cells' chip runs (LongCat, Ling and
Trinity, each a share of its experts: a block's rows scatter-added to
their tokens), two from cells that route nothing (SmolLM2, granite) —
and its entry in ``BENCHMARK.json``."""

import os
import shutil

import pytest

from benchmark.harness import contract, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
NAME = "moe_combine_ms"
CELLS = ["xing4-longdoc-saturated", "mellum2-completion-sessions",
         "solar2-longdoc-saturated"]
#: sample -> (the cell it was cut from, whole runs of its program that
#: holds a mixed step, ms a run under ``mixed_step/moe_combine``)
ROUTED = {
    "scopes_longcat_mixed_chunk.json": ("longcat-decode-saturated", 1, 0.4738),
    "scopes_ling3_mixed_chunk.json": ("ling3-reasoning-saturated", 2, 3.9375),
    "scopes_trinity_mixed_chunk.json": ("trinity-longshort-saturated", 2,
                                        0.4712),
}
UNROUTED = {
    "scopes_smollm2_mixed_chunk.json": "smollm2-decode-saturated",
    "scopes_granite4h_mixed_chunk.json": "granite4h-decode-saturated",
}


@pytest.fixture(scope="module")
def bench():
    return contract.load_benchmark()


def _run(tmp_path, bench, data_file, cell):
    """A run as the readers see it, whose first capture's directory
    holds ``data_file`` as the cached neutral form."""
    path = os.path.join(DATA, data_file)
    if not os.path.exists(path):
        pytest.skip("no recorded sample yet")
    d = tmp_path / "trace0"
    d.mkdir()
    shutil.copy(path, d / scopes.NEUTRAL_FILE)
    got = contract.resolve_cell(bench, cell)
    return {"captures": [{"dir": str(d)}], "config": got["config"],
            "family_dir": got["family_dir"]}


def test_the_entry_is_the_issues(bench):
    """Appended last, nothing else of the file touched: milliseconds
    off the device trace, the routed layer's, moving the gap between
    tokens, in the two cells whose mixed step is told its live rows and
    in the one that runs the same traffic through the share form."""
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": "routed feed-forward (ops/moe.py)",
        "moves": "tpot_p50_ms", "workloads": CELLS}
    assert sum(m["layer"] == entry["layer"] for m in bench["per_layer"]) > 1
    cells = {w["name"]: w for w in bench["workloads"]}
    tpot, = (m for m in bench["end_to_end"] if m["name"] == "tpot_p50_ms")
    for cell in CELLS:
        assert cell in tpot.get("workloads", cells)
        assert NAME in {m["name"]
                        for m in contract.resolve_cell(bench, cell)["per_layer"]}
    assert NAME not in {m["name"] for m in contract.resolve_cell(
        bench, "kanana2-decode-saturated")["per_layer"]}


@pytest.mark.parametrize("data_file", sorted(ROUTED))
def test_a_routed_sample_reads_its_mixed_steps_combine(tmp_path, bench,
                                                       data_file):
    """The reader against the sample's own events: the self times of
    the operations whose ``op_name`` holds ``mixed_step`` first and
    ``moe_combine`` anywhere, over the whole runs that hold a mixed
    step; the decode loop's ``moe_combine`` is not in it."""
    cell, runs, ms = ROUTED[data_file]
    run = _run(tmp_path, bench, data_file, cell)
    got = contract.load_reader(bench, NAME)(run)
    assert got == pytest.approx(ms, rel=1e-3)
    red = scopes.of_run(run)
    assert scopes.runs_holding(red, scopes.MIXED) == runs
    mixed = sum(s for p, (s, _n) in red["paths"].items()
                if p.split("/")[0] == scopes.MIXED and "moe_combine" in p)
    loop = sum(s for p, (s, _n) in red["paths"].items()
               if p.split("/")[0] == scopes.LOOP and "moe_combine" in p)
    assert got == pytest.approx(mixed / runs * 1e3) and loop > 0
    assert got < contract.load_reader(bench, "mixed_step_ms")(run)


@pytest.mark.parametrize("data_file", sorted(UNROUTED))
def test_a_program_without_the_scope_gives_nothing(tmp_path, bench,
                                                   data_file):
    run = _run(tmp_path, bench, data_file, UNROUTED[data_file])
    assert contract.load_reader(bench, "mixed_step_ms")(run) > 0
    assert contract.load_reader(bench, NAME)(run) is None


def test_a_run_without_a_capture_gives_nothing(bench):
    read = contract.load_reader(bench, NAME)
    assert read({}) is None and read({"captures": []}) is None

