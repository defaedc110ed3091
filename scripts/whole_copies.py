#!/usr/bin/env python3
"""Whole copies in a configuration's serving programs, with no chip.

    python3 scripts/whole_copies.py --config benchmark/configs/<name>.json
        [--layers N] [--program NAME ...] [--keep DIR]

Every serving program of a benchmark configuration is lowered for a
DESCRIBED v5e the way the executor lowers it — the family's adapter
registers the model and describes its parameters as served (int8 leaves
as int8), ``engine/builder.executor_geometry`` reads the file's
``server`` block, and a :class:`JaxExecutor` built over the DESCRIPTION
of the parameters (it then describes its pool and row state too, lays
the leaves its family wants transposed, holds no buffer) hands over its
``programs()`` — and compiled by the TPU compiler that is installed with
JAX. Then every ``copy``, ``transpose`` and ``copy-done`` (an
asynchronous copy's end) in the optimised program whose result is as
large as a stacked parameter leaf or a leaf of the pool or row state is
printed with its layout, its operands — an operand that is a parameter
of the program with the layout it ENTERS in — the computation it stands
in (the entry computation: once a run of the program, which a capture
reads as ``(unscoped)``; a loop's body: once a step) and the scope it
stands under (``op_name``). Under the moves, the program's
REMATERIALISED instructions (``.remat`` in the name: XLA computes the
value again rather than keep it) whose result is as large as one
layer's slice of the smallest such leaf, counted by result.

Such a copy runs at EVERY run of the program: SmolLM2's ``decode_chunk``
and ``mixed_chunk`` copied ``wq``, ``wk`` and ``wv`` whole (201 MB each,
1.89 ms a run) until the executor laid them transposed once (PERF.md,
PR 47) — XLA wanted the matrices with the contracted axis minor and
loop-invariant motion lifted the transposition out of the decode loop.
The guards of ``tests/test_tpu_compile.py`` hold the programs it was
found in; this prints what any other configuration's programs hold
(``ROADMAP.md`` Queue 1 item 7 (d)). granite's two chunk programs
copied ``in_proj`` (1.26 GB) out of ``{1,2,0}`` — the TPU's default
layout of a stacked leaf whose last axis is no multiple of 128 lanes
puts the axis before it minor — and its mixed step multiplied by it
three times a layer, two of them rematerialised (PERF.md, PR 49): the
entry layout and the ``.remat`` count are the two lines that show it.

Nothing runs: this says nothing about results or times. About 7-50 s a
program at full depth on this machine's CPU; ``--layers`` cuts the
depth where the family's adapter takes a cut depth (a whole-stack copy
shows at 4 layers as at 24; a small stack is also PREFETCHED whole,
``copy-done`` into ``S(1)``, where the served one is not). The TPU
library is loaded by this process: run it alone
(``docs/observability.md``).

``--lowered`` compiles nothing and prints a digest of each program's
LOWERED text instead (StableHLO with its Mosaic payloads, which carry
their call sites' file paths and line numbers): what XLA's cache key is
made from. Two trees unpacked IN TURN AT THE SAME PATH that print the
same digests give the same programs — how PR 55 showed that an edit of
``ops/moe.py`` left the six other routed cells' programs where they
were (a second digest, with every location, moves with any line).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import re
import sys
import tempfile
import time
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: an instruction that moves its operand whole: ``%name = type[dims]{layout}
#: op(operands)``
_MOVE = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.-]+) = (?P<type>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?P<layout>\{[^ ]*\})? (?P<op>copy|transpose|copy-done)"
    r"\((?P<operands>[^)]*)\)(?P<rest>.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: a parameter of a computation: ``%name = type[dims]{layout} parameter(n)``
_PARAMETER = re.compile(
    r"^\s*%?(?P<name>[\w.-]+) = (?P<type>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?P<layout>\{[^ ]*\})? parameter\(\d+\)")
#: an instruction XLA computes again instead of keeping its value
_REMAT = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.-]*\.remat\d*(?:\.\d+)?) = "
    r"(?P<type>\w+)\[(?P<dims>[\d,]*)\](?P<layout>\{[^ ]*\})? ")
#: the head of a computation: ``ENTRY %main.1 (...) -> ... {`` or
#: ``%region_0.2 (...) -> ... {``
_COMPUTATION = re.compile(
    r"^(?P<entry>ENTRY )?%?(?P<name>[\w.-]+) \(.*\{\s*$")


def entry_parameters(text: str) -> Dict[str, str]:
    """Name -> ``type[dims]{layout}`` of each parameter of the entry
    computation of a compiled program's ``text``: how it ENTERS."""
    found: Dict[str, str] = {}
    inside = False
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            inside = bool(head["entry"])
            continue
        p = _PARAMETER.match(line) if inside else None
        if p is not None:
            found[p["name"]] = f"{p['type']}[{p['dims']}]{p['layout'] or ''}"
    return found


def whole_moves(text: str, big: Dict[str, str]) -> List[Dict[str, str]]:
    """The copies and transposes in a compiled program's ``text`` whose
    result has the dimensions of an entry of ``big`` (``"24,2048,2048"
    -> "params.layers.wq"``); ``enters``: how an operand that is a
    parameter of the program enters it."""
    found = []
    inside = ""
    enters = entry_parameters(text)
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            inside = ("the entry computation" if head["entry"]
                      else head["name"])
            continue
        m = _MOVE.match(line)
        if m is None or m["dims"] not in big:
            continue
        scope = _OP_NAME.search(m["rest"])
        found.append({
            "op": m["op"], "name": m["name"],
            "result": f"{m['type']}[{m['dims']}]{m['layout'] or ''}",
            "like": big[m["dims"]], "operands": m["operands"],
            "inside": inside,
            "under": scope.group(1) if scope else "(no name)",
            "enters": "; ".join(
                f"{name} enters as {enters[name]}"
                for name in re.findall(r"%?([\w.-]+)", m["operands"])
                if name in enters)})
    return found


_SHAPE = re.compile(r"\w+\[(?P<dims>[\d,]*)\](?:\{(?P<order>[\d,]*))?")


def _elements(dims: str) -> int:
    return math.prod(int(d) for d in dims.split(",")) if dims else 1


def enters_otherwise(text: str, least: int) -> List[str]:
    """The parameters of the entry computation of ``text`` with
    ``least`` elements or more that do NOT enter row-major (XLA writes
    a layout minor axis first: ``{2,1,0}`` is row-major, ``{1,2,0}``
    has the axis before the last minor), as ``name type[dims]{layout}``:
    a leaf the executor laid so, or one the backend lays so by itself —
    the TPU does for a last axis that is no multiple of its 128
    lanes."""
    found = []
    for name, enters in entry_parameters(text).items():
        m = _SHAPE.match(enters)
        if not m["dims"] or m["order"] is None:
            continue
        rank = m["dims"].count(",") + 1
        if _elements(m["dims"]) >= least and m["order"] != ",".join(
                str(i) for i in reversed(range(rank))):
            found.append(f"{name} {enters}")
    return found


def rematerialised(text: str, least: int) -> Dict[str, int]:
    """Result (``bf16[1024,8512]{0,1...}``) -> how many instructions of
    ``text`` with ``.remat`` in their name give it, for results of at
    least ``least`` elements: what XLA computes AGAIN instead of keeping
    (a product whose result has three late readers, three times)."""
    count: Dict[str, int] = {}
    for line in text.splitlines():
        m = _REMAT.match(line)
        if m is not None and _elements(m["dims"]) >= least:
            result = f"{m['type']}[{m['dims']}]{m['layout'] or ''}"
            count[result] = count.get(result, 0) + 1
    return count


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a benchmark configuration "
                         "(benchmark/configs/*.json)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers (0: as served)")
    ap.add_argument("--program", action="append", default=[],
                    help="only this program (repeatable; default: all)")
    ap.add_argument("--lowered", action="store_true",
                    help="print a digest of each program's lowered text "
                         "and compile nothing")
    ap.add_argument("--keep", default="",
                    help="write each program's optimised text into this "
                         "directory (<program>.hlo.txt)")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import contract
    from llmq_tpu.core.config import load_config
    from llmq_tpu.engine.builder import executor_geometry
    from llmq_tpu.engine.executor import JaxExecutor, describe
    from llmq_tpu.engine.tokenizer import get_tokenizer

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the kernels' routes ask the backend; the programs are for the chip
    jax.default_backend = lambda: "tpu"
    os.environ.pop("LLMQ_PALLAS", None)

    with open(args.config, "r", encoding="utf-8") as f:
        doc = json.load(f)
    if args.layers:
        doc["num_hidden_layers"] = args.layers
    srv = doc["server"]
    adapter = contract.load_family(
        contract.family_dir(contract.load_benchmark(), doc), "adapter")
    mcfg = adapter.register(srv["model"]["name"], doc)
    with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
        json.dump(srv, f)              # JSON is YAML
        f.flush()
        cfg = load_config(f.name, env=False)

    params = describe(jax.eval_shape(
        adapter.param_builder(mcfg, srv["model"]), jax.random.PRNGKey(0)),
        chip)
    ex = JaxExecutor(
        mcfg, params, **executor_geometry(cfg),
        eos_id=get_tokenizer(getattr(cfg.model, "tokenizer_path", "")).eos_id,
        telemetry_metrics=False)
    print(f"{srv['model']['name']}: {mcfg.n_layers} layers, laid "
          f"by the executor: {ex.relaid}")

    big: Dict[str, str] = {}
    slices = []                 # one layer's elements of each stacked leaf
    for label, tree in (("params", ex.params), ("pool", ex.cache),
                        ("row_state", ex.row_state)):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if leaf.size > 1 << 20:
                big.setdefault(",".join(map(str, leaf.shape)),
                               label + jax.tree_util.keystr(path))
                if label == "params" and leaf.ndim == 3:
                    slices.append(leaf.size // leaf.shape[0])
    slice_of = max(1 << 20, min(slices, default=0))
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
    total = 0
    for name, fn, operands, _routes in ex.programs():
        if args.program and name not in args.program:
            continue
        t0 = time.perf_counter()
        if args.lowered:
            lowered = fn.lower(*operands).as_text()
            print(f"{name}: {hashlib.md5(lowered.encode()).hexdigest()}")
            continue
        text = fn.lower(*operands).compile().as_text()
        if args.keep:
            with open(os.path.join(args.keep, name + ".hlo.txt"), "w",
                      encoding="utf-8") as f:
                f.write(text)
        moves = whole_moves(text, big)
        total += len(moves)
        print(f"{name}: {len(moves)} whole "
              f"cop{'y' if len(moves) == 1 else 'ies'} "
              f"({time.perf_counter() - t0:.0f} s)")
        for mv in moves:
            print(f"  {mv['op']} {mv['name']} = {mv['result']}  like "
                  f"{mv['like']}\n    of ({mv['operands']})\n"
                  + (f"    {mv['enters']}\n" if mv["enters"] else "")
                  + f"    in {mv['inside']}, under {mv['under']}")
        for line in enters_otherwise(text, 1 << 20):
            print(f"  enters otherwise than row-major: {line}")
        again = rematerialised(text, slice_of)
        print(f"  rematerialised, of {slice_of} elements or more: "
              f"{sum(again.values())}")
        for result, n in sorted(again.items()):
            print(f"    {n} x {result}")
    if not args.lowered:
        print(f"{total} in all")
    return 0


if __name__ == "__main__":
    sys.exit(main())
