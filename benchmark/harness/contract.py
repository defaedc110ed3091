"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own, found by name:

- configuration ``c``  -> the ``file`` its ``configs`` entry gives
- traffic mix ``t``    -> ``<path>/traffic/t.json`` in any of ``paths``
- metric ``m``         -> ``<path>/metrics/m.py`` in any of ``paths``
- model family ``f``   -> ``<path>/families/f/`` in any of ``paths``:
  ``shapes.py``, ``reference.py`` and ``adapter.py`` (``FAMILY_SURFACE``);
  a configuration file names its family in its top-level key ``family``

so a later PR adds a cell, a configuration, a mix, a metric or a family
by adding files and entries, and edits none. Standard library only: the
parent process imports this and must stay off JAX (of a family it loads
``shapes.py`` alone; the server child loads the other two).
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
#: What each file of a family has to define. ``shapes.py``: standard
#: library only (this process and the metric readers import it).
#: ``reference.py``: the plain float32 reference; it imports neither
#: the program nor the adapter. ``adapter.py``: the one file that
#: imports the program.
FAMILY_SURFACE = {
    "shapes": ("MODEL_KEYS", "DECODE_ATTN", "PREFILL_ATTN",
               "attn_calls_per_step", "param_count", "matmul_params",
               "kv_bytes_per_token", "decode_step_bytes",
               "decode_step_flops", "decode_attn_bytes",
               "decode_attn_flops", "prefill_attn_bytes",
               "prefill_attn_flops"),
    "reference": ("reference_logits",),
    "adapter": ("register", "param_builder", "serving_path"),
}
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ContractError(ValueError):
    """``BENCHMARK.json`` or a file it names does not hold what the
    harness needs."""


def load_benchmark(path: Optional[str] = None) -> Dict[str, Any]:
    path = path or os.path.join(ROOT, "BENCHMARK.json")
    with open(path, "r", encoding="utf-8") as f:
        bench = json.load(f)
    bench["_root"] = ROOT
    return bench


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e.get("name") == name:
            return e
    raise ContractError(f"no {what} named {name!r} in BENCHMARK.json")


def _find(bench: Dict, sub: str, filename: str) -> str:
    for p in bench["paths"]:
        cand = os.path.join(bench["_root"], p, sub, filename)
        if os.path.isfile(cand):
            return cand
    raise ContractError(
        f"{sub}/{filename} is in none of the benchmark's paths "
        f"{bench['paths']}")


def family_dir(bench: Dict, config: Dict) -> str:
    """The directory of the family a configuration file names. A
    missing or unknown family is an error, never a default."""
    family = config.get("family")
    if not (isinstance(family, str) and NAME_RE.match(family)):
        raise ContractError(
            f"configuration {config.get('name')!r} names no model family "
            f"(top-level key \"family\"): {family!r}")
    for p in bench["paths"]:
        cand = os.path.join(bench["_root"], p, "families", family)
        if os.path.isdir(cand):
            return cand
    raise ContractError(
        f"families/{family}/ is in none of the benchmark's paths "
        f"{bench['paths']}")


_FAMILY_PARTS: Dict[str, ModuleType] = {}


def load_family(directory: str, part: str) -> ModuleType:
    """``shapes``, ``reference`` or ``adapter`` of the family in
    ``directory`` (``family_dir``), loaded once a process (the
    reference's compiled functions live in its module) and held to
    ``FAMILY_SURFACE``."""
    path = os.path.join(os.path.abspath(directory), part + ".py")
    mod = _FAMILY_PARTS.get(path)
    if mod is None:
        if part not in FAMILY_SURFACE or not os.path.isfile(path):
            raise ContractError(f"{path}: no such part of a model family")
        name = "benchmark_family_" + re.sub(
            r"\W", "_", os.path.basename(os.path.dirname(path))) + "_" + part
        spec = importlib.util.spec_from_file_location(name, path)
        assert spec is not None and spec.loader is not None
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        lacks = [n for n in FAMILY_SURFACE[part] if not hasattr(mod, n)]
        if lacks:
            raise ContractError(f"{path} does not define {lacks}")
        _FAMILY_PARTS[path] = mod
    return mod


def first_family(part: str) -> ModuleType:
    """A part of the family of ``BENCHMARK.json``'s first
    configuration. Only for the three import paths that are older than
    families (``contract.MODEL_KEYS``, ``child.register_model``,
    ``harness.reference.reference_logits``), which ``tests/`` and
    ``scripts/`` still use and a benchmark PR may not edit: when those
    ask the family by ``family_dir`` / ``load_family``, this goes."""
    bench = load_benchmark()
    with open(os.path.join(bench["_root"], bench["configs"][0]["file"]),
              "r", encoding="utf-8") as f:
        return load_family(family_dir(bench, json.load(f)), part)


def __getattr__(name: str) -> Any:
    if name == "MODEL_KEYS":
        return first_family("shapes").MODEL_KEYS
    raise AttributeError(name)


def metric_applies(metric: Dict, workload: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or workload in cells


def resolve_cell(bench: Dict, workload: str) -> Dict[str, Any]:
    """The cell's entry with its configuration, its traffic mix, its
    family's directory and the metrics it reports, each loaded from its
    own file. ``config["model"]`` is the family's ``MODEL_KEYS`` of the
    configuration file."""
    cell = _by_name(bench["workloads"], workload, "workload")
    cfg_entry = _by_name(bench["configs"], cell["config"], "config")
    with open(os.path.join(bench["_root"], cfg_entry["file"]), "r",
              encoding="utf-8") as f:
        config = json.load(f)
    with open(_find(bench, "traffic", cell["traffic"] + ".json"), "r",
              encoding="utf-8") as f:
        traffic = json.load(f)
    fdir = family_dir(bench, config)
    keys = load_family(fdir, "shapes").MODEL_KEYS
    config["model"] = {k: config[k] for k in keys if k in config}
    return {
        "cell": cell, "config_entry": cfg_entry, "config": config,
        "traffic": traffic, "family_dir": fdir,
        "end_to_end": [m for m in bench["end_to_end"]
                       if metric_applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"]
                      if metric_applies(m, workload)],
    }


def load_reader(bench: Dict, metric_name: str) -> Callable[[Any], Any]:
    """``read(run)`` of the metric's own file. It returns the value, or
    ``None`` where it found nothing to read."""
    path = _find(bench, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + re.sub(r"\W", "_", metric_name), path)
    assert spec is not None and spec.loader is not None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise ContractError(f"{path} defines no read(run)")
    return mod.read


def check_names(bench: Dict) -> List[str]:
    """Every name, unit and source within the contract's characters and
    lengths; every ``moves`` an end-to-end metric that each of the
    metric's cells reports. Returns the faults found (none = sound)."""
    bad: List[str] = []

    def name_ok(s: Any, what: str) -> None:
        if not (isinstance(s, str) and NAME_RE.match(s)):
            bad.append(f"{what}: bad name {s!r}")

    def line_ok(s: Any, what: str) -> None:
        if not (isinstance(s, str) and 1 <= len(s) <= 200
                and "\n" not in s and "\t" not in s):
            bad.append(f"{what}: not one line of 1..200 characters")

    cells = [w["name"] for w in bench["workloads"]]
    for c in bench["configs"]:
        name_ok(c["name"], "config")
        line_ok(c["source"], f"config {c['name']} source")
        line_ok(c["why"], f"config {c['name']} why")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
    for w in bench["workloads"]:
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        line_ok(w["why"], f"workload {w['name']} why")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        name_ok(m["name"], "metric")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"metric {m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"metric {m['name']}: source {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cells:
                bad.append(f"metric {m['name']}: unknown cell {c!r}")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']}: source {m['source']!r}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"end-to-end {m['name']}: bound {m['bound']}")
    for m in bench["per_layer"]:
        line_ok(m["layer"], f"metric {m['name']} layer")
        target = e2e.get(m["moves"])
        if target is None:
            bad.append(f"metric {m['name']}: moves unknown "
                       f"{m['moves']!r}")
            continue
        for c in m.get("workloads", cells):
            if not metric_applies(target, c):
                bad.append(f"metric {m['name']} moves {m['moves']}, "
                           f"which cell {c} does not report")
    for c in cells:
        if not metric_applies(e2e.get("setup_s", {"workloads": []}), c):
            bad.append(f"cell {c} does not report setup_s")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    if len(names) != len(set(names)):
        bad.append("two metrics share a name")
    return bad
