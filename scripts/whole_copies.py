#!/usr/bin/env python3
"""Whole copies in a configuration's serving programs, with no chip.

    python3 scripts/whole_copies.py --config benchmark/configs/<name>.json
        [--layers N] [--program NAME ...]

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
printed with its layout, its operands, the computation it stands in (the entry computation: once a
run of the program, which a capture reads as ``(unscoped)``; a loop's
body: once a step) and the scope it stands under (``op_name``).

Such a copy runs at EVERY run of the program: SmolLM2's ``decode_chunk``
and ``mixed_chunk`` copied ``wq``, ``wk`` and ``wv`` whole (201 MB each,
1.89 ms a run) until the executor laid them transposed once (PERF.md,
PR 47) — XLA wanted the matrices with the contracted axis minor and
loop-invariant motion lifted the transposition out of the decode loop.
The guards of ``tests/test_tpu_compile.py`` hold the programs it was
found in; this prints what any other configuration's programs hold
(``ROADMAP.md`` Queue 1 item 7 (d)).

Nothing runs: this says nothing about results or times. About 7-50 s a
program at full depth on this machine's CPU; ``--layers`` cuts the
depth where the family's adapter takes a cut depth (a whole-stack copy
shows at 4 layers as at 24; a small stack is also PREFETCHED whole,
``copy-done`` into ``S(1)``, where the served one is not). The TPU
library is loaded by this process: run it alone
(``docs/observability.md``).
"""

from __future__ import annotations

import argparse
import json
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
#: the head of a computation: ``ENTRY %main.1 (...) -> ... {`` or
#: ``%region_0.2 (...) -> ... {``
_COMPUTATION = re.compile(
    r"^(?P<entry>ENTRY )?%?(?P<name>[\w.-]+) \(.*\{\s*$")


def whole_moves(text: str, big: Dict[str, str]) -> List[Dict[str, str]]:
    """The copies and transposes in a compiled program's ``text`` whose
    result has the dimensions of an entry of ``big`` (``"24,2048,2048"
    -> "params.layers.wq"``)."""
    found = []
    inside = ""
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
            "under": scope.group(1) if scope else "(no name)"})
    return found


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a benchmark configuration "
                         "(benchmark/configs/*.json)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the model to this many layers (0: as served)")
    ap.add_argument("--program", action="append", default=[],
                    help="only this program (repeatable; default: all)")
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
          f"transposed by the executor: {ex.relaid}")

    big: Dict[str, str] = {}
    for label, tree in (("params", ex.params), ("pool", ex.cache),
                        ("row_state", ex.row_state)):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if leaf.size > 1 << 20:
                big.setdefault(",".join(map(str, leaf.shape)),
                               label + jax.tree_util.keystr(path))
    total = 0
    for name, fn, operands, _routes in ex.programs():
        if args.program and name not in args.program:
            continue
        t0 = time.perf_counter()
        text = fn.lower(*operands).compile().as_text()
        moves = whole_moves(text, big)
        total += len(moves)
        print(f"{name}: {len(moves)} whole "
              f"cop{'y' if len(moves) == 1 else 'ies'} "
              f"({time.perf_counter() - t0:.0f} s)")
        for mv in moves:
            print(f"  {mv['op']} {mv['name']} = {mv['result']}  like "
                  f"{mv['like']}\n    of ({mv['operands']})\n"
                  f"    in {mv['inside']}, under {mv['under']}")
    print(f"{total} in all")
    return 0


if __name__ == "__main__":
    sys.exit(main())
