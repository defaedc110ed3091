"""Self-tests of the seam between the harness and a model family
(``<path>/families/<family>/``: ``shapes.py``, ``reference.py``,
``adapter.py``), for the CPU sandbox:

    python3 -m pytest benchmark/selftest/test_families.py -q

The last one serves a tiny cell through a family the harness has never
seen (a copy of ``families/llama`` under another name, in a temporary
directory added to ``paths``): what a PR that brings a new family does
with real files. ``BENCH_SELFTEST_FAST=1`` skips it.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import contract  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "selftest", "data")
REHEARSAL = os.path.join(DATA, "rehearsal.json")


def _configs(bench):
    for entry in bench["configs"]:
        with open(os.path.join(bench["_root"], entry["file"])) as f:
            yield entry["name"], json.load(f)


@pytest.mark.parametrize("bench_file", [None, REHEARSAL],
                         ids=["BENCHMARK.json", "rehearsal.json"])
def test_every_configuration_names_a_family_with_the_whole_surface(bench_file):
    bench = contract.load_benchmark(bench_file)
    for name, config in _configs(bench):
        fdir = contract.family_dir(bench, config)
        assert os.path.basename(fdir) == config["family"], name
        for part, surface in contract.FAMILY_SURFACE.items():
            mod = contract.load_family(fdir, part)
            assert all(hasattr(mod, n) for n in surface), (name, part)
        # the model block is the family's keys of the file, all present
        keys = contract.load_family(fdir, "shapes").MODEL_KEYS
        assert all(k in config for k in keys), name


def test_a_missing_or_unknown_family_is_an_error_not_a_default(tmp_path):
    bench = contract.load_benchmark(REHEARSAL)
    with open(os.path.join(DATA, "tiny-rehearsal.json")) as f:
        config = json.load(f)
    for family in (None, "", 3, "no/slash", "never-heard-of-it"):
        bad = dict(config, family=family)
        if family is None:
            del bad["family"]
        with pytest.raises(contract.ContractError):
            contract.family_dir(bench, bad)
    # a family that lacks a part, or a name of its surface
    fam = tmp_path / "families" / "half"
    fam.mkdir(parents=True)
    (fam / "shapes.py").write_text("MODEL_KEYS = ()\n")
    with pytest.raises(contract.ContractError, match="does not define"):
        contract.load_family(str(fam), "shapes")
    with pytest.raises(contract.ContractError, match="no such part"):
        contract.load_family(str(fam), "adapter")
    with pytest.raises(contract.ContractError, match="no such part"):
        contract.load_family(str(fam), "child")


def test_the_harness_names_no_family_no_kernel_and_no_model_module():
    named = re.compile(r"llama|fused_decode|paged_prefill|"
                       r"num_key_value_heads|llmq_tpu\.models")
    files = [os.path.join(ROOT, "benchmark", "run.py")]
    for sub in ("harness", "metrics"):
        d = os.path.join(ROOT, "benchmark", sub)
        files += [os.path.join(d, n) for n in sorted(os.listdir(d))
                  if n.endswith(".py")]
    for path in files:
        with open(path) as f:
            hits = [ln for ln in f if named.search(ln)]
        assert not hits, (path, hits)


def test_who_imports_what_in_a_family():
    """``shapes.py`` stays off JAX (the parent and the readers import
    it); ``reference.py`` imports neither the program nor the adapter;
    only ``adapter.py`` imports the program."""
    fdir = os.path.join(ROOT, "benchmark", "families", "llama")
    imports = {}
    for part in contract.FAMILY_SURFACE:
        with open(os.path.join(fdir, part + ".py")) as f:
            imports[part] = re.findall(
                r"^\s*(?:from|import)\s+([\w.]+)", f.read(), re.M)
    assert set(imports["shapes"]) <= {"__future__", "typing"}
    assert not [m for m in imports["reference"]
                if m.startswith(("llmq_tpu", "benchmark")) or "adapter" in m]
    assert any(m.startswith("llmq_tpu") for m in imports["adapter"])
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r)\n"
         "from benchmark.harness import contract, readers\n"
         "b = contract.load_benchmark(%r)\n"
         "c = contract.resolve_cell(b, 'tiny-saturated')\n"
         "readers.family_shapes(c).param_count(c['config']['model'])\n"
         "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
         % (ROOT, REHEARSAL)], capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_the_import_paths_older_than_families_still_answer():
    """``tests/`` and ``scripts/`` use them; a benchmark PR may not
    edit those (``contract.first_family``)."""
    from benchmark.harness import child, reference
    llama = os.path.join(ROOT, "benchmark", "families", "llama")
    assert contract.MODEL_KEYS == contract.load_family(
        llama, "shapes").MODEL_KEYS
    assert child.register_model is contract.load_family(
        llama, "adapter").register
    assert reference.reference_logits is contract.load_family(
        llama, "reference").logits_by_dims
    with pytest.raises(AttributeError):
        contract.NO_SUCH_NAME


# -- a family the harness has never seen ----------------------------------------

def _run(bench_file, seed="3000000011"):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--benchmark-file", bench_file, "--platform", "cpu",
           "--workload", "tiny-saturated", "--seed", seed,
           "--seconds", "4", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.skipif(os.environ.get("BENCH_SELFTEST_FAST") == "1",
                    reason="BENCH_SELFTEST_FAST=1")
def test_a_family_in_a_new_directory_of_paths_serves_a_cell(tmp_path):
    extra = tmp_path / "extra"
    shutil.copytree(os.path.join(ROOT, "benchmark", "families", "llama"),
                    extra / "families" / "elsewhere",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(DATA, "tiny-rehearsal-w8kv8.json")) as f:
        config = json.load(f)
    config["family"] = "elsewhere"
    (extra / "configs").mkdir()
    cfg_file = extra / "configs" / "tiny-rehearsal-w8kv8.json"
    cfg_file.write_text(json.dumps(config))
    with open(REHEARSAL) as f:
        bench = json.load(f)
    bench["paths"].append(str(extra))
    for c in bench["configs"]:
        if c["name"] == "tiny-rehearsal-w8kv8":
            c["file"] = str(cfg_file)
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    cell = contract.resolve_cell(
        contract.load_benchmark(str(bench_file)), "tiny-saturated")
    assert cell["family_dir"] == str(extra / "families" / "elsewhere")

    new, old = _run(str(bench_file)), _run(REHEARSAL)
    for res in (new, old):
        assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
        res["checks"]["logits"].pop("phases_s")
    # the same weights from the same seed through the same check: equal
    # to the last digit, and the same result line
    assert new["checks"]["logits"] == old["checks"]["logits"]
    assert new["checks"]["logits"]["positions"] == 8
    assert set(new) == set(old) and set(new["metrics"]) == set(
        old["metrics"]) == {"tpot_p50_ms", "output_tok_s", "setup_s"}
    assert new["device"]["platform"] == "cpu"     # so: not a measurement
