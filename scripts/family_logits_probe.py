#!/usr/bin/env python3
"""How far a routed family's serving path lies from its plain
reference on the chip, beside the control one precision down: the
readings a configuration's ``tolerance`` is written from.

    chiprun -- python3 scripts/family_logits_probe.py \\
        --config benchmark/configs/<name>.json --seeds 11,12,13 \\
        --out chiprun_out/probe.jsonl

For every seed: weights from the seed (the family's ``adapter``
builder, as ``benchmark/harness/child.py`` makes them), one prompt of
the configuration's smallest prefill bucket less two tokens, the
family's ``served_many`` (every position of one prefill; 128 decode
positions through the cache), the family's ``reference_forward`` over
every position, and the same with ``lowp=True``. One JSON line a seed:
for each group the ``clean_quantile`` of the positions' RMS
differences and the worst position, served against the reference and
the control against the reference at the SAME positions, and the RMS
of the reference's logits. The family must have the routed families'
surface (``reference.JUDGED``, ``reference_forward(..., lowp=)``,
``judge``). A family whose reference is routed by the served path's
choices judges both for itself (``reference.judged_groups``: one
verdict a group, served and each ``--control``). One process for all
seeds: the programs compile once.

``--check`` runs, for every seed, ``benchmark/harness/child.py``
``check_logits`` itself, as a run of the cell does before it serves (the
harness's prompts, the family's ``reference_logits`` with whatever it
judges besides), and prints its verdict or the family's ``NotCorrect``.
``--setattr module.attr=expression`` (repeatable) breaks the program on
purpose first — the control that shows what the check refuses:

    ... --check --setattr "llmq_tpu.models.afmoe._window=lambda cfg, kind: None"
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--tokens", type=int, default=0,
                    help="length of the judged sequence (default: the "
                         "smallest prefill bucket less two); a family "
                         "whose served_many prefills in slices takes more")
    ap.add_argument("--check", action="store_true",
                    help="run the harness's check_logits, not the readings")
    ap.add_argument("--setattr", action="append", default=[],
                    metavar="MODULE.ATTR=EXPRESSION")
    ap.add_argument("--control", action="append", default=[],
                    metavar="NAME[+NAME]",
                    help="what the control holds one precision down "
                         "(repeatable; default: all of it at once): of "
                         "reference.LOWP for a family that judges its "
                         "groups itself (reference.judged_groups), else "
                         "the value of reference_forward's lowp")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.harness import child, contract
    from llmq_tpu.parallel import enable_compilation_cache
    enable_compilation_cache()

    with open(os.path.join(ROOT, args.config), encoding="utf-8") as f:
        config = json.load(f)
    bench = contract.load_benchmark()
    fdir = contract.family_dir(bench, config)
    adapter = contract.load_family(fdir, "adapter")
    reference = contract.load_family(fdir, "reference")
    keys = contract.load_family(fdir, "shapes").MODEL_KEYS
    model = {k: config[k] for k in keys if k in config}
    srv = config["server"]
    tol = dict(config["tolerance"])
    mcfg = adapter.register(srv["model"]["name"], config)
    path = adapter.serving_path(mcfg, srv)
    served_many, _ = reference.JUDGED
    controls = ([tuple(c.split("+")) for c in args.control]
                or [tuple(getattr(reference, "LOWP", ()))])
    for patch in args.setattr:
        target, expression = patch.split("=", 1)
        module, attr = target.rsplit(".", 1)
        setattr(importlib.import_module(module), attr, eval(expression))
    # the harness keeps its check programs under this name: a broken
    # program must not be found under the whole one's
    path.ident += "".join(args.setattr)
    T = args.tokens or int(min(srv["executor"]["prefill_buckets"])) - 2
    print(json.dumps({"device": str(jax.devices()[0]), "tokens": T,
                      "setattr": args.setattr}), flush=True)

    def emit(line):
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)),
                        exist_ok=True)
            with open(os.path.join(ROOT, args.out), "a",
                      encoding="utf-8") as f:
                f.write(json.dumps(line) + "\n")

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        params = child.make_params(seed, adapter.param_builder(
            mcfg, srv["model"]))
        if args.check:
            line = {"seed": seed, "setattr": args.setattr}
            try:
                got = child.check_logits(
                    params, path, reference.reference_logits,
                    {"config": dict(config, model=model), "seed": seed})
                line.update(correct=got["ok"], rms=got["rms"],
                            phases_s=got["phases_s"])
            except reference.NotCorrect as e:
                line.update(correct=False, not_correct=str(e))
            line["seconds"] = round(time.perf_counter() - t0, 1)
            del params
            emit(line)
            continue
        tokens = np.random.default_rng(seed).integers(
            3, mcfg.vocab_size, T, dtype=np.int32)
        if hasattr(reference, "judged_groups"):
            # a family that routes its reference by the served choices
            # judges served and control for itself, group by group
            line = {"seed": seed}
            served = served_many(params, tokens)
            for name, lowp in [("served", ())] + [
                    (f"control.{'+'.join(c)}", c) for c in controls]:
                for group, got in reference.judged_groups(
                        params, tokens, model, served, tol, lowp):
                    line[f"{group}.{name}"] = got
            line["seconds"] = round(time.perf_counter() - t0, 1)
            del params, served
            emit(line)
            continue
        every = np.arange(T)
        ref, margins = reference.reference_forward(params, tokens, model,
                                                   every)
        # the control: all of it at once (``lowp=True``), or with
        # ``--control NAME`` the part a family's reference takes by name
        # (``lowp="router"``)
        lows = {("control." + "+".join(c) if args.control else "control"):
                np.asarray(reference.reference_forward(
                    params, tokens, model, every,
                    lowp="+".join(c) if args.control else True)[0])
                for c in (controls if args.control else [()])}
        ref, margins = map(np.asarray, (ref, margins))
        line = {"seed": seed,
                "reference_rms": float(np.sqrt((ref * ref).mean()))}
        for group, (at, served) in served_many(params, tokens).items():
            at = np.asarray(at)
            # a family whose limits follow the context takes the positions
            by = (at,) if "at" in inspect.signature(
                reference.judge).parameters else ()
            for name, got in [("served", np.asarray(served))] + [
                    (name, low[at]) for name, low in lows.items()]:
                j = reference.judge(got, ref[at], margins[at], tol, *by)
                rms = np.sqrt(((got - ref[at]) ** 2).mean(-1))
                line[f"{group}.{name}"] = {
                    "rms_clean": j["rms_clean"], "rms": j["rms"],
                    "median": float(np.median(rms)),
                    "p90": float(np.quantile(rms, 0.9)), "ok": j["ok"],
                    "near_tie_share": j["near_tie_share"],
                    "bands": j.get("bands", {})}
        line["seconds"] = round(time.perf_counter() - t0, 1)
        del params
        emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
