"""Self-tests of ``harness/scopes.py`` and of the metrics that read it,
for the CPU sandbox (``python3 -m pytest benchmark/selftest -q``): the
reduction on two samples cut from chip captures (one SmolLM2 and one
LongCat mixed chunk: ``scopes.py <trace_dir> <out> <millis>``), the
seven entries within the contract, and the way from a real
``.xplane.pb`` through the subprocess. The reduction's arithmetic on a
hand-made capture is ``tests/test_device_scopes.py``'s."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import contract, scopes, tracered  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "selftest", "data")
NEW = ("plain_decode_step_ms", "mixed_step_ms", "mixed_step_share",
       "slices_dense_ms", "decode_dense_ms", "mixed_slice_live_share",
       "device_unscoped_share")
DEVICE = tuple(n for n in NEW if n != "mixed_slice_live_share")
#: sample -> (the cell it was cut from, decode steps of its chunk
#: program, the row kinds its family's mixed step takes apart)
SAMPLES = {
    "scopes_smollm2_mixed_chunk.json": ("smollm2-decode-saturated", 8),
    "scopes_longcat_mixed_chunk.json": ("longcat-decode-saturated", 16),
}


@pytest.fixture(scope="module")
def bench():
    return contract.load_benchmark()


def _sample(name):
    path = os.path.join(DATA, name)
    if not os.path.exists(path):
        pytest.skip("no recorded sample yet")
    with open(path) as f:
        return json.load(f)


def _run(tmp_path, bench, data_file):
    """A run as the readers see it, whose first capture's directory
    holds ``data_file`` as the cached neutral form."""
    d = tmp_path / "trace0"
    d.mkdir()
    shutil.copy(os.path.join(DATA, data_file), d / scopes.NEUTRAL_FILE)
    cell = contract.resolve_cell(bench, SAMPLES[data_file][0])
    return {"captures": [{"dir": str(d)}], "config": cell["config"],
            "family_dir": cell["family_dir"]}


@pytest.mark.parametrize("data_file", sorted(SAMPLES))
def test_a_chip_sample_conserves_tracereds_self_times(data_file):
    tr = _sample(data_file)
    red = scopes.reduce_neutral(tr)
    assert red is not None and red["whole_runs"] >= 1
    # the same events, by tracered's rule: name by name and in sum
    want = {}
    for p in tr["planes"]:
        events = [[p["names"][e[0]], e[1], e[2]] for e in p["ops"]
                  if e[3] >= 0]       # every run of a sample is whole
        for k, v in tracered.self_times(events).items():
            want[k] = want.get(k, 0.0) + v[0] / len(tr["planes"])
    total = sum(want.values())
    assert red["busy_s"] == pytest.approx(total, rel=1e-3)
    assert sum(v[0] for v in red["paths"].values()) == pytest.approx(
        red["busy_s"], rel=1e-9)
    for k, v in want.items():
        assert red["ops"][k][0] == pytest.approx(v, rel=1e-6, abs=1e-12)


@pytest.mark.parametrize("data_file", sorted(SAMPLES))
def test_a_chip_sample_reads_by_the_programs_names(data_file):
    tr = _sample(data_file)
    cell, steps = SAMPLES[data_file]
    bench = contract.load_benchmark()
    shapes = contract.load_family(
        contract.resolve_cell(bench, cell)["family_dir"], "shapes")
    red = scopes.reduce_neutral(tr, shapes.DECODE_ATTN)
    firsts = {p.split("/")[0] for p in red["paths"]}
    assert {"mixed_step", "decode_loop"} <= firsts
    heads = {"/".join(p.split("/")[:2]) for p in red["paths"]}
    assert {"mixed_step/slices", "mixed_step/decode_rows",
            "mixed_step/sample", "decode_loop/qkv", "decode_loop/attn",
            "decode_loop/attn_out", "decode_loop/mlp", "decode_loop/head",
            "decode_loop/sample"} <= heads
    # the program is named, whole, and its loop ran the chunk out: the
    # mixed step is the first of the chunk's steps
    mixed = [p for n, p in red["programs"].items() if "mixed_chunk" in n]
    assert len(mixed) == 1 and mixed[0]["runs"] >= 1
    model = contract.resolve_cell(bench, cell)["config"]["model"]
    per_step = shapes.attn_calls_per_step(model)
    assert mixed[0]["decode_attn_calls"] == pytest.approx(
        mixed[0]["runs"] * (steps - 1) * per_step)
    # what stays under no name is small
    unscoped = red["paths"].get(scopes.UNSCOPED, [0.0])[0]
    assert unscoped < 0.05 * red["busy_s"]
    assert scopes.table(red).splitlines()[0].startswith("path")


@pytest.mark.parametrize("data_file", sorted(SAMPLES))
def test_the_device_metrics_on_a_chip_sample(tmp_path, bench, data_file):
    _sample(data_file)
    run = _run(tmp_path, bench, data_file)
    got = {n: contract.load_reader(bench, n)(run) for n in DEVICE}
    assert all(v is not None and v > 0 for v in got.values()), got
    _cell, steps = SAMPLES[data_file]
    red = scopes.of_run(run)
    mixed = next(p for n, p in red["programs"].items()
                 if "mixed_chunk" in n)
    # a chunk is its mixed step and its plain steps, to a few per cent
    # (what lies between them: the program's own glue)
    chunk_ms = mixed["seconds"] / mixed["runs"] * 1e3
    assert got["mixed_step_ms"] + (steps - 1) * got[
        "plain_decode_step_ms"] == pytest.approx(chunk_ms, rel=0.05)
    assert got["decode_dense_ms"] < got["plain_decode_step_ms"]
    assert got["slices_dense_ms"] < got["mixed_step_ms"]
    assert 0 < got["mixed_step_share"] < 100
    assert got["device_unscoped_share"] < 5


def test_a_program_without_the_vocabulary_reads_as_nothing(tmp_path, bench):
    """The parent of the PR that added the scopes: its neutral form has
    an empty vocabulary, its dispatches no ``slice_tokens``. Every new
    reader returns nothing and none raises."""
    tr = _sample("scopes_smollm2_mixed_chunk.json")
    tr["vocabulary"] = []
    assert scopes.reduce_neutral(tr, "fused_decode_attention") is None
    d = tmp_path / "trace0"
    d.mkdir()
    (d / scopes.NEUTRAL_FILE).write_text(json.dumps(tr))
    shutil.copy(os.path.join(DATA, "trace_spans_chip_sample.json"),
                d / "spans_neutral.json")      # PR 24's: no slice_tokens
    cell = contract.resolve_cell(bench, "smollm2-decode-saturated")
    run = {"captures": [{"dir": str(d)}], "config": cell["config"],
           "family_dir": cell["family_dir"], "requests": []}
    for n in NEW:
        assert contract.load_reader(bench, n)(run) is None, n
    # no capture at all, or a directory that is not there
    for caps in ([], [{"dir": str(tmp_path / "absent")}], [{}]):
        run = {"captures": caps, "config": cell["config"],
               "family_dir": cell["family_dir"], "requests": []}
        for n in NEW:
            assert contract.load_reader(bench, n)(run) is None, n


def test_the_live_share_from_the_dispatch_counts(tmp_path, bench):
    """``mixed_slice_live_share`` on a hand-made capture of the engine
    thread: two mixed chunks, a plain one and a dedicated prefill."""
    def dispatch(t, **counts):
        return ["engine.dispatch", t, 10.0, counts]
    events = [["engine.step", 0.0, 1000.0],
              dispatch(10.0, program="mixed_chunk", steps=8, rows=3,
                       prefill_tokens=300, slice_tokens=1024),
              dispatch(30.0, program="decode_chunk", steps=8, rows=3,
                       prefill_tokens=0, slice_tokens=0),
              dispatch(50.0, program="mixed_chunk", steps=8, rows=3,
                       prefill_tokens=212, slice_tokens=1024),
              dispatch(70.0, program="prefill_b512", steps=0, rows=1,
                       prefill_tokens=400, slice_tokens=512)]
    d = tmp_path / "trace0"
    d.mkdir()
    (d / "spans_neutral.json").write_text(json.dumps({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "thread": 0,
             "events": [["busy", 0.0, 900.0]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "thread": 1, "events": events}]}]}))
    run = {"captures": [{"dir": str(d)}]}
    assert contract.load_reader(bench, "mixed_slice_live_share")(
        run) == pytest.approx(100.0 * 512 / 2048)


def test_the_new_entries_are_within_the_contract(bench):
    assert contract.check_names(bench) == []
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(by_name)
    cells = {w["name"] for w in bench["workloads"]}
    for n in NEW:
        m = by_name[n]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] == "tpot_p50_ms"
        assert m["workloads"] and set(m["workloads"]) <= cells
        assert m["source"] == ("program_counter"
                               if n == "mixed_slice_live_share"
                               else "device_trace")
    # appended: nothing that was there moved
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NEW}
    assert {by_name[n]["layer"] for n in NEW} <= layers


@pytest.mark.skipif(os.environ.get("BENCH_SELFTEST_FAST") == "1",
                    reason="BENCH_SELFTEST_FAST=1")
def test_from_a_real_xplane_through_the_subprocess(tmp_path, capsys):
    """A capture made here on the CPU around a program that uses the
    vocabulary: ``neutral_of`` parses it in a subprocess and keeps the
    result beside the trace; the wire decoder finds the program's
    optimised HLO with the scopes in it. A CPU capture has no device
    plane: nothing to reduce, ``of_run`` says so with ``None`` and the
    command line says so plainly."""
    script = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax, jax.numpy as jnp\n"
        "from llmq_tpu.utils.profiling import scope\n"
        "def chunk(x, w):\n"
        "    with scope('decode_loop'):\n"
        "        def body(c):\n"
        "            with scope('mlp'):\n"
        "                return c[0] + 1, jnp.tanh(jnp.dot(c[1], w))\n"
        "        return jax.lax.while_loop(lambda c: c[0] < 3, body,\n"
        "                                  (0, x))[1]\n"
        "f = jax.jit(chunk); x = jnp.ones((8, 8))\n"
        "f(x, x).block_until_ready()\n"
        "o = jax.profiler.ProfileOptions(); o.python_tracer_level = 0\n"
        "jax.profiler.start_trace(%r, profiler_options=o)\n"
        "f(x, x).block_until_ready()\n"
        "jax.profiler.stop_trace()\n" % (ROOT, str(tmp_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    tr = scopes.neutral_of(str(tmp_path))
    assert os.path.exists(tmp_path / scopes.NEUTRAL_FILE)
    assert tr["planes"] == [] and "decode_loop" in tr["vocabulary"]
    assert scopes.reduce_neutral(tr) is None
    assert scopes.of_run({"captures": [{"dir": str(tmp_path)}]}) is None
    with open(tracered.find_xplane(str(tmp_path)), "rb") as f:
        hlo = scopes.hlo_modules(f.read())
    mine = [ins for name, ins in hlo.items() if name.startswith("jit_chunk")]
    assert mine and {scopes.scope_path(v, set(tr["vocabulary"]))
                     for v in mine[0].values()} >= {
        "decode_loop", "decode_loop/mlp", scopes.UNSCOPED}
    q = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "harness",
                                      "scopes.py"), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert q.returncode == 1 and "nothing to read" in q.stderr
