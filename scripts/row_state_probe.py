#!/usr/bin/env python3
"""How far a row-state family's serving path lies from its plain
reference on the chip, beside the control one precision down (the
recurrent state HELD in bfloat16 between tokens; weights and the
recurrence's own arithmetic unchanged): the readings a configuration's
``tolerance.decode_rms`` is written from.

    chiprun -- python3 scripts/row_state_probe.py \\
        --config benchmark/configs/granite-4.0-h-micro-bf16.json \\
        --seeds 11,12,13,14,15,16 --out chiprun_out/state_probe.jsonl

For every seed: weights from the seed (the family's ``adapter`` builder,
as ``benchmark/harness/child.py`` makes them), the prompt the harness's
check judges (the smallest prefill bucket less two tokens), the family's
``served_many`` (what precedes the judged positions as a prompt in two
slices through the mixed step, the state carried between the programs,
then ``--steps`` teacher-forced decode steps at the served batch width
to the prompt's end) for the configuration as it
stands and for the control, and the family's ``reference_forward`` over
every position once. One JSON line a seed: for each of the two, each
judged row's ``reference.judge`` readings (the worst position's RMS
difference, the mean, the last quarter's mean). One process for all
seeds: the programs compile once."""

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
    ap.add_argument("--steps", type=int, default=0,
                    help="decode steps judged (default: the adapter's)")
    ap.add_argument("--control", default="bfloat16")
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
    # ``judge`` is called here, not ``reference_logits``: a reading over
    # the configuration's limit is recorded (``ok`` false), not raised
    tol = dict(config["tolerance"])
    if args.steps:
        adapter.JUDGED_STEPS = args.steps
    name = srv["model"]["name"]
    paths = {}
    for which, extra in (("served", {}),
                         ("control", {"control": {
                             "state_dtype": args.control}})):
        mcfg = adapter.register(f"{name}.{which}",
                                {**config, **extra, "tolerance": tol})
        adapter.serving_path(mcfg, srv)
        paths[which] = (mcfg, reference.JUDGED[0])
    reference.JUDGED = None
    bucket = int(min(srv["executor"]["prefill_buckets"]))
    n = bucket - 5 + 3                   # harness/child.check_logits
    dev = jax.devices()[0]
    print(f"{config['name']}: {n} tokens, {adapter.JUDGED_STEPS} judged "
          f"decode steps, control state {args.control}, device "
          f"{dev.device_kind}", flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        mcfg = paths["served"][0]
        params = child.make_params(seed, adapter.param_builder(
            mcfg, srv["model"]))
        rng = np.random.default_rng(seed % (2 ** 31))
        tokens = rng.integers(3, mcfg.vocab_size, n, dtype=np.int32)
        ref = np.asarray(reference.reference_forward(
            params, tokens, model, np.arange(n)))
        rec = {"seed": seed, "tokens": n, "steps": adapter.JUDGED_STEPS,
               "reference_rms": float(np.sqrt((ref * ref).mean()))}
        for which, (_cfg, served_many) in paths.items():
            for group, (at, served) in served_many(params, tokens).items():
                rec[f"{which}.{group}"] = reference.judge(
                    served, ref[np.asarray(at)], tol)
        rec["seconds"] = round(time.perf_counter() - t0, 1)
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(
                os.path.join(ROOT, args.out))), exist_ok=True)
            with open(os.path.join(ROOT, args.out), "a",
                      encoding="utf-8") as f:
                f.write(line + "\n")
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
