#!/usr/bin/env python3
"""Run a list of benchmark runs one after another (one process each)
and keep every result line: how the sets of runs in PERF.md were made.

    chiprun -- python3 benchmark/selftest/chip_runs.py <tag> <run> [<run> ...]

A run is ``workload:seed:seconds:trace[:rate_scale]``. Result lines go
to ``chiprun_out/<tag>.jsonl`` as they come (a lost call keeps what it
had), with the wall time of each run; a failed run's server log tail is
printed. Standard library only; never imports JAX."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    tag, runs = sys.argv[1], sys.argv[2:]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{tag}.jsonl")
    rc_all = 0
    for spec in runs:
        parts = spec.split(":")
        workload, seed, seconds, trace = parts[:4]
        cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
               "--workload", workload, "--seed", seed, "--seconds", seconds,
               "--trace", trace]
        if len(parts) > 4:
            cmd += ["--rate-scale", parts[4]]
        if os.environ.get("BENCH_FILE"):
            cmd += ["--benchmark-file", os.environ["BENCH_FILE"]]
        if os.environ.get("BENCH_PLATFORM"):
            cmd += ["--platform", os.environ["BENCH_PLATFORM"]]
        t0 = time.time()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        last = (p.stdout.strip().splitlines() or [""])[-1]
        try:
            res = json.loads(last)
        except ValueError:
            res = None
        rec = {"run": spec, "rc": p.returncode, "wall_s": round(wall, 1),
               "result": res}
        with open(out_path, "a", encoding="utf-8") as f:
            f.write(json.dumps(rec) + "\n")
        log = os.path.join(ROOT, ".bench_work", f"{workload}.t{trace}",
                           "server.log")
        if os.path.exists(log):      # kept: what the server said
            n = sum(1 for _ in open(out_path))
            with open(log, "rb") as f, open(os.path.join(
                    out_dir, f"{tag}.{n}.server.log"), "wb") as g:
                g.write(f.read()[-400_000:])
        if res is None or p.returncode != 0:
            rc_all = 1
            print(f"RUN {spec} FAILED rc={p.returncode}\n"
                  f"{p.stderr[-1500:]}")
            log = os.path.join(ROOT, ".bench_work",
                               f"{workload}.t{trace}", "server.log")
            if os.path.exists(log):
                with open(log, "r", errors="replace") as f:
                    print(f.read()[-4000:])
        else:
            m = {k: round(v["value"], 3)
                 for k, v in res["metrics"].items()}
            print(f"RUN {spec} wall={wall:.0f}s correct={res['correct']} "
                  f"failed={res['failed']}/{res['attempted']} {m} "
                  f"tails={res.get('tails')} load={res.get('load')} "
                  f"logits={res['checks']['logits'].get('phases_s')} {res['checks']['logits']['max_abs']:.3f}/"
                  f"{res['checks']['logits']['rms']:.3f} "
                  f"compiles={res['checks']['compiles_in_window']} "
                  f"stages={res['setup_stages_s']} "
                  f"peak={res['device'].get('memory_peak_bytes')}")
            if trace == "1":
                print("  breakdown:", json.dumps(res.get("breakdown"))[:1500])
                print("  trace:", json.dumps(res.get("trace"))[:2500])
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
