#!/usr/bin/env python3
"""How far a closed-loop traffic mix ALONE spreads tokens/s from seed to
seed: the benchmark's own plan (``benchmark/harness/plan.closed_plan``:
which client gets which lengths is the seed's) through a toy of the
engine's chunk loop, on the host.

    python3 scripts/closed_loop_spread.py benchmark/traffic/<mix>.json \\
        [--rows 64] [--budget 4096] [--slices 4] [--seeds 36]

The toy: ``rows`` batch rows, a chunk of ``--chunk`` decode steps whose
first step also carries up to ``--slices`` prompt slices of ``--budget /
--slices`` tokens (one a sequence), a step's time = weights + the
decoding rows' K/V bytes (a full layer, and ``--window-layers`` layers
bounded by ``--window``) + the slices' operations, with round constants.
It prints the median, the standard deviation and the quartile spread of
every six seeds. EVERY number is a count from this toy, never a device
metric: it says whether a mix can be admitted under a spread limit
before chip time is spent on it, not what the chip will read (PERF.md
§6, PR 41: at a prompt budget that binds it followed the chip's six
seeds with r = 0.97, at the committed budget only in magnitude)."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import plan  # noqa: E402

KV_TOKEN_BYTES = 4096            # one layer's K and V of one token
WEIGHTS_S, BYTES_S = 3.9e-3, 700e9
TOKEN_FLOPS, ATTN_KEY_FLOPS = 2.1e9, 4 * 48 * 128
DENSE_FLOPS_S, ATTN_FLOPS_S = 140e12, 100e12


def tokens_per_s(traffic, seed, *, rows, window_s, budget, slices, chunk,
                 window, window_layers):
    clients = plan.closed_plan(traffic, seed, rows, 1 << 30)
    ramp = float(traffic["ramp_s"])
    end = ramp + window_s
    sent = [0] * len(clients)
    pending, seated = list(range(len(clients))), []
    t = out = 0.0
    while t < end:
        while pending and len(seated) < rows:
            c = pending.pop(0)
            r = clients[c][sent[c]]
            sent[c] += 1
            seated.append({"c": c, "prompt": r["prompt_tokens"], "done": 0,
                           "left": r["output_tokens"], "made": 0,
                           "wait": 1})
        flops, used, fresh = 0.0, 0, []
        for s in seated:
            if s["done"] >= s["prompt"]:
                continue
            if s["wait"]:
                s["wait"] -= 1
            elif used < slices:
                n = min(budget // slices, s["prompt"] - s["done"])
                mid = s["done"] + n / 2
                flops += n * (TOKEN_FLOPS / DENSE_FLOPS_S + ATTN_KEY_FLOPS
                              * (window_layers * min(mid, window) + mid)
                              / ATTN_FLOPS_S)
                s["done"] += n
                used += 1
                if s["done"] >= s["prompt"]:
                    fresh.append(s)
        decoding = [s for s in seated
                    if s["done"] >= s["prompt"] and s not in fresh]
        kv = sum(KV_TOKEN_BYTES * (c + window_layers * min(c, window))
                 for c in (s["prompt"] + s["made"] for s in decoding))
        dt = chunk * (WEIGHTS_S + kv / BYTES_S) + flops
        inside = max(0.0, min(t + dt, end) - max(t, ramp)) / dt
        t += dt
        for s in decoding:
            n = min(chunk, s["left"])
            s["left"] -= n
            s["made"] += n
            out += n * inside
        for s in [s for s in seated if s["left"] <= 0]:
            seated.remove(s)
            if sent[s["c"]] < len(clients[s["c"]]):
                pending.append(s["c"])
    return out / window_s


def quartile_spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("traffic")
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--seconds", type=float, default=48.0)
    ap.add_argument("--budget", type=int, default=4096)
    ap.add_argument("--slices", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--window", type=int, default=4096)
    ap.add_argument("--window-layers", type=int, default=4)
    ap.add_argument("--seeds", type=int, default=36)
    ap.add_argument("--first-seed", type=int, default=4100001000)
    args = ap.parse_args()
    with open(args.traffic, encoding="utf-8") as f:
        traffic = json.load(f)
    got = [tokens_per_s(traffic, args.first_seed + i, rows=args.rows,
                        window_s=args.seconds, budget=args.budget,
                        slices=args.slices, chunk=args.chunk,
                        window=args.window,
                        window_layers=args.window_layers)
           for i in range(args.seeds)]
    print(json.dumps({
        "toy_tokens_per_s_median": round(statistics.median(got), 1),
        "sd_share": round(statistics.pstdev(got) / statistics.mean(got), 4),
        "quartile_spread_of_each_six": [
            round(quartile_spread(got[i:i + 6]), 4)
            for i in range(0, len(got) - 5, 6)]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
