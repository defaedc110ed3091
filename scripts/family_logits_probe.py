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
``judge``). One process for all seeds: the programs compile once."""

from __future__ import annotations

import argparse
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
    adapter.serving_path(mcfg, srv)
    served_many, _ = reference.JUDGED
    T = int(min(srv["executor"]["prefill_buckets"])) - 2
    print(json.dumps({"device": str(jax.devices()[0]), "tokens": T}),
          flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        params = child.make_params(seed, adapter.param_builder(
            mcfg, srv["model"]))
        tokens = np.random.default_rng(seed).integers(
            3, mcfg.vocab_size, T, dtype=np.int32)
        every = np.arange(T)
        ref, margins = reference.reference_forward(params, tokens, model,
                                                   every)
        low, _ = reference.reference_forward(params, tokens, model, every,
                                             lowp=True)
        ref, low, margins = map(np.asarray, (ref, low, margins))
        line = {"seed": seed,
                "reference_rms": float(np.sqrt((ref * ref).mean()))}
        for group, (at, served) in served_many(params, tokens).items():
            at = np.asarray(at)
            for name, got in (("served", np.asarray(served)),
                              ("control", low[at])):
                j = reference.judge(got, ref[at], margins[at], tol)
                rms = np.sqrt(((got - ref[at]) ** 2).mean(-1))
                line[f"{group}.{name}"] = {
                    "rms_clean": j["rms_clean"], "rms": j["rms"],
                    "median": float(np.median(rms)),
                    "p90": float(np.quantile(rms, 0.9)), "ok": j["ok"],
                    "near_tie_share": j["near_tie_share"]}
        line["seconds"] = round(time.perf_counter() - t0, 1)
        del params
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)),
                        exist_ok=True)
            with open(os.path.join(ROOT, args.out), "a",
                      encoding="utf-8") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
