#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent is standard library only and never imports JAX: the chip
belongs to the server child (``harness/child.py``), which this process
starts, drives over HTTP as a client would, and stops. The last line of
standard output is the result, one JSON object. Without the chips the
cell asks for, the child fails to start and this process exits non-zero
having printed no result. See ``benchmark/README.md``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.harness import contract, loadgen, plan as planner  # noqa: E402

#: The child must be serving within this (the first run of a cell in a
#: checkout compiles; the contract allows that run 1200 s in all).
READY_S = 1080.0
STOP_S = 40.0


class Child:
    """The server child and the two pipes to it."""

    def __init__(self, spec: Dict[str, Any], log_path: str) -> None:
        self.spec = spec
        r, w = os.pipe()
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["JAX_PLATFORMS"] = spec["platform"]
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env.setdefault("TPU_LOG_DIR", "disabled")
        spec_path = os.path.join(spec["workdir"], "spec.json")
        with open(spec_path, "w", encoding="utf-8") as f:
            json.dump(spec, f)
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "harness", "child.py"),
             "--spec", spec_path, "--reply-fd", str(w)],
            cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=self._log,
            stderr=subprocess.STDOUT, pass_fds=(w,))
        os.close(w)
        self._rf = os.fdopen(r, "r")
        self._seq = 0
        self._answers: Dict[int, Dict[str, Any]] = {}
        self._cv = threading.Condition()
        self.ready: Optional[Dict[str, Any]] = None
        self.on_done = lambda rid: None
        self.eof = False
        threading.Thread(target=self._read, daemon=True,
                         name="bench-reply").start()

    def _read(self) -> None:
        for line in self._rf:
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            if msg.get("event") == "done":
                self.on_done(msg["id"])
                continue
            with self._cv:
                if msg.get("event") == "ready":
                    self.ready = msg
                elif "re" in msg:
                    self._answers[msg["re"]] = msg
                self._cv.notify_all()
        with self._cv:
            self.eof = True
            self._cv.notify_all()

    def wait_ready(self, timeout: float) -> Dict[str, Any]:
        deadline = time.monotonic() + timeout
        with self._cv:
            while self.ready is None:
                if self.eof or self.proc.poll() is not None:
                    raise RuntimeError("the server child ended before it "
                                       "was ready; see its log")
                left = deadline - time.monotonic()
                if left <= 0:
                    raise RuntimeError("the server child was not ready "
                                       f"within {timeout:.0f} s")
                self._cv.wait(min(left, 0.5))
            return self.ready

    def ask(self, op: str, timeout: float = 120.0, **kw) -> Dict[str, Any]:
        self._seq += 1
        seq = self._seq
        assert self.proc.stdin is not None
        self.proc.stdin.write((json.dumps(
            dict(kw, op=op, seq=seq)) + "\n").encode())
        self.proc.stdin.flush()
        deadline = time.monotonic() + timeout
        with self._cv:
            while seq not in self._answers:
                if self.eof:
                    raise RuntimeError(f"the server child ended during "
                                       f"{op!r}")
                left = deadline - time.monotonic()
                if left <= 0:
                    raise RuntimeError(f"no answer to {op!r} within "
                                       f"{timeout:.0f} s")
                self._cv.wait(min(left, 0.5))
            return self._answers.pop(seq)

    def stop(self) -> int:
        """Ask the child to stop and wait until it has ended; kill it
        if it does not. No process outlives a run."""
        try:
            if self.proc.poll() is None:
                self.ask("quit", timeout=10.0)
        except (RuntimeError, OSError):
            pass
        try:
            rc = self.proc.wait(timeout=STOP_S)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            rc = self.proc.wait(timeout=10.0)
        self._log.close()
        return rc

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10.0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def work_dir(workload: str, trace: int) -> str:
    d = os.path.join(ROOT, ".bench_work", f"{workload}.t{trace}")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def stream_records(taps: Dict[str, List[Any]], opened: Dict[str, Any],
                   closed: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Closed loop: every stream that delivered tokens inside the
    window, cut to its part inside the window."""
    t0, t1 = opened["t"], closed["t"]
    s_open, s_close = opened["streams"], closed["streams"]
    out = []
    for rid, rec in taps.items():
        _ts, t_first, t_last, n = rec[0], rec[1], rec[2], rec[3]
        if t_first is None or t_last < t0 or t_first > t1:
            continue
        if rid in s_close:
            _f, end_t, end_n = s_close[rid]
        elif t_last <= t1:
            end_t, end_n = t_last, n
        else:
            continue      # ended between the close mark and its snapshot
        if rid in s_open:
            _f, beg_t, beg_n = s_open[rid]
        elif t_first >= t0:
            beg_t, beg_n = t_first, 1
        else:
            continue
        out.append({"id": rid, "t_first": beg_t, "t_last": end_t,
                    "n_tokens": end_n - beg_n + 1, "whole": False})
    return out


def load_report(traffic, the_plan, requests, opened, closed, t_open, W):
    """What the knee sweep reads: offered against delivered output
    tokens per second, and the requests in the system (due, not yet
    ended) at the end of each period of the window: the same phase of
    the arrival pattern each time, so a growing backlog shows."""
    def backlog(t: float) -> int:
        n = 0
        for r in requests:
            done = (r.get("stages") or {}).get("completed")
            if r["due"] <= t and (done is None or done > t):
                n += 1
        return n
    off = planner.offered(the_plan)
    period = planner.period_of(traffic)
    ends = [period * (i + 1) for i in range(int(W / period + 1e-9))]
    return {"offered": off,
            "offered_output_tok_s": off["output_tokens"] / W,
            "delivered_output_tok_s": ((closed["tokens"] - opened["tokens"])
                                       / (closed["t"] - opened["t"])),
            "backlog_at_period_ends": [backlog(t_open + t) for t in ends],
            "rows_busy_open": opened.get("active"),
            "rows_busy_close": closed.get("active"),
            "kv_pages_used_close": closed.get("kv_pages_used")}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--benchmark-file", default=None,
                    help="rehearsal only: another BENCHMARK.json")
    ap.add_argument("--platform", default="tpu",
                    help="rehearsal only: 'cpu'; a result that does not "
                         "say 'tpu' is not a measurement")
    ap.add_argument("--rate-scale", type=float, default=1.0,
                    help="knee sweep only: multiply every stretch's "
                         "arrivals (open loop)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "llmq_tpu")):
        sys.stderr.write("the system under test (llmq_tpu/) is not in this "
                         "checkout\n")
        return 2

    bench = contract.load_benchmark(args.benchmark_file)
    cell = contract.resolve_cell(bench, args.workload)
    workdir = work_dir(args.workload, args.trace)
    spec = {"workload": args.workload, "seed": args.seed,
            "platform": args.platform, "chips": cell["cell"]["chips"],
            "config": cell["config"], "family_dir": cell["family_dir"],
            "workdir": workdir, "port": free_port()}
    child = Child(spec, os.path.join(workdir, "server.log"))
    try:
        result = drive(args, bench, cell, child, spec)
    except BaseException:
        child.kill()
        raise
    rc = child.stop()
    if rc != 0:
        sys.stderr.write(f"the server child exited with code {rc}\n")
        return 1
    print(json.dumps(result))
    return 0


def drive(args, bench, cell, child: Child, spec) -> Dict[str, Any]:
    traffic, config = cell["traffic"], cell["config"]
    W = float(args.seconds)
    rows = int(config["server"]["executor"]["max_batch_size"])
    max_ctx = int(config["model"]["max_position_embeddings"])
    is_open = traffic["loop"] == "open"
    if is_open and args.rate_scale != 1.0:
        for seg in traffic["segments"]:
            seg["arrivals"] = max(1, round(seg["arrivals"]
                                           * args.rate_scale))
    if is_open:
        the_plan = planner.open_plan(traffic, args.seed, W)
    else:
        clients = planner.closed_plan(traffic, args.seed, rows, max_ctx)
    ready = child.wait_ready(READY_S)
    sender = loadgen.Sender("127.0.0.1", spec["port"])
    stop = threading.Event()
    captures_at = [float(x) for x in traffic.get("trace_at", [0.3])]
    trace_s = float(traffic.get("trace_seconds", 3.0))

    if is_open:
        ramp_s = -min(r["due"] for r in the_plan)
        t_open = time.perf_counter() + ramp_s + 0.25
        timer = loadgen.run_open(the_plan, sender, t_open, stop)
    else:
        loop = loadgen.ClosedLoop(clients, sender)
        child.on_done = loop.on_done
        child.ask("watch", on=True)
        loop.start()
        t_open = time.perf_counter() + float(traffic["ramp_s"])
    loadgen.wait_until(t_open)
    opened = child.ask("mark")
    setup_s = opened["t"] - T_PROCESS
    if args.trace:
        for i, frac in enumerate(captures_at):
            loadgen.wait_until(t_open + frac * W)
            # With two captures the first leaves the Python tracer
            # off (clean device numbers); the last has it on, to name
            # the idle gaps by what the host was doing.
            last = i == len(captures_at) - 1
            child.ask("trace", timeout=180.0, seconds=trace_s,
                      dir=os.path.join(spec["workdir"], f"trace{i}"),
                      python=1 if last else 0)
    loadgen.wait_until(t_open + W)
    closed = child.ask("mark")

    unfinished: List[str] = []
    if is_open:
        loadgen.wait_until(t_open + W + float(traffic.get("tail_s", 0.0)))
        stop.set()
        timer.join(timeout=5.0)
        time.sleep(0.2)
        sent = sender.results()
        ids = [r["id"] for r in the_plan if r["phase"] == "window"
               and sent.get(r["id"], {}).get("status") == 202]
        unfinished = child.ask(
            "drain", timeout=float(traffic["drain_s"]) + 30.0, ids=ids,
            limit_s=float(traffic["drain_s"]))["unfinished"]
    else:
        loop.stop()
        child.ask("watch", on=False)
        sent = sender.results()
    dump_path = os.path.join(spec["workdir"], "dump.json")
    child.ask("dump", timeout=300.0, out=dump_path)
    sender.close()
    with open(dump_path, "r", encoding="utf-8") as f:
        dump = json.load(f)

    # -- the run, as the metric readers see it --------------------------------
    taps, timelines = dump["taps"], dump["timelines"]
    requests: List[Dict[str, Any]] = []
    failed = 0
    if is_open:
        for r in the_plan:
            if r["phase"] != "window":
                continue
            s = sent.get(r["id"], {})
            tap = taps.get(r["id"])
            tl = timelines.get(r["id"], {})
            meta = tl.get("meta", {})
            ok = (s.get("status") == 202 and r["id"] not in unfinished
                  and meta.get("finish_reason") in ("length", "eos"))
            rec = {"id": r["id"], "due": t_open + r["due"], "ok": ok,
                   "tier": r["tier"], "turn": r.get("turn", 0),
                   "planned_prompt_tokens": r["prompt_tokens"],
                   "planned_output_tokens": r["output_tokens"],
                   "t_sent": s.get("t_sent"), "rtt_s": s.get("rtt_s"),
                   "status": s.get("status"), "whole": True,
                   "stages": tl.get("stages", {}), "meta": meta}
            if tap and ok:
                rec.update(t_submit=tap[0], t_first=tap[1], t_last=tap[2],
                           n_tokens=tap[3])
            failed += 0 if ok else 1
            requests.append(rec)
        attempted = len(requests)
    else:
        requests = stream_records(taps, opened, closed)
        bad = [rid for rid, s in sent.items() if s["status"] != 202]
        bad += [rid for rid, tl in timelines.items()
                if tl.get("meta", {}).get("terminal") in ("failed",
                                                          "cancelled")
                and rid in taps]
        failed = len(set(bad))
        attempted = len(requests) + failed
        for rec in requests:
            rec["ok"] = True
            rec["stages"] = timelines.get(rec["id"], {}).get("stages", {})
            rec["meta"] = timelines.get(rec["id"], {}).get("meta", {})

    compiles = None
    if (opened.get("backend_compiles") is not None
            and closed.get("backend_compiles") is not None):
        compiles = closed["backend_compiles"] - opened["backend_compiles"]
    run = {
        "workload": args.workload, "seed": args.seed, "window_s": W,
        "trace": bool(args.trace), "loop": traffic["loop"],
        "requests": requests, "attempted": attempted, "failed": failed,
        "opened": opened, "closed": closed, "setup_s": setup_s,
        "captures": dump.get("captures", []), "config": config,
        "family_dir": cell["family_dir"], "traffic": traffic,
        "device": ready["device"], "ready": ready,
        "compiles_in_window": compiles,
    }
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in cell["per_layer" if args.trace else "end_to_end"]:
        value = contract.load_reader(bench, m["name"])(run)
        if value is None or (isinstance(value, float)
                             and not math.isfinite(value)):
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct = bool(ready["correctness"]["ok"] and compiles == 0
                   and failed == 0 and attempted > 0)
    device = dict(ready["device"],
                  memory_peak_bytes=dump.get("memory_peak_bytes"))
    result: Dict[str, Any] = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
        "checks": {"logits": ready["correctness"],
                   "compiles_in_window": compiles,
                   "unfinished": len(unfinished),
                   "recorder_dropped": dump["recorder"]["dropped"]},
        "setup_stages_s": ready["stages_s"],
    }
    from benchmark.harness import stats
    good = [r for r in requests if r.get("ok")]
    result["tails"] = {
        name: {f"p{q}": stats.percentile(stats.collect(good, fn), q,
                                         len(requests) - len(good))
               for q in (50, 75, 90, 95, 99)}
        for name, fn in (("ttft_ms", stats.ttft_ms if is_open else
                          (lambda r: None)), ("tpot_ms", stats.tpot_ms))}
    if is_open:
        result["load"] = load_report(traffic, the_plan, requests, opened, closed,
                                     t_open, W)
    result["trace"] = [
        {"python_tracer": c["python"], "start_s": c["start_s"],
         "held_s": c["held_s"], "stop_s": c["stop_s"],
         "reduce_s": c.get("reduce_s"), "samples": len(c["samples"]),
         "dispatches": (c["after"]["decode_steps"]
                        - c["before"]["decode_steps"]),
         "programs": c["reduced"].get("programs"),
         "modules": sorted(c["reduced"]["modules"].items(),
                           key=lambda kv: -kv[1][0])[:12]}
        for c in dump.get("captures", []) if c.get("reduced")]
    if not is_open:
        result["load"] = {
            "clients": len(clients), "clients_out_of_requests": loop.exhausted,
            **{f"{k}_{edge}": mark.get(k) for edge, mark in
               (("open", opened), ("close", closed))
               for k in ("active", "pending", "kv_pages_used")}}
    caps = [c for c in dump.get("captures", []) if c.get("reduced")]
    if caps:
        red = caps[0]["reduced"]
        if red["devices"]:
            from benchmark.harness.tracered import top_ops
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {
                "device_ops": top_ops(red, 10),
                "idle_gaps": [list(g) for g in
                              caps[-1]["reduced"]["idle_gaps"][:10]]}
    return result


if __name__ == "__main__":
    sys.exit(main())
