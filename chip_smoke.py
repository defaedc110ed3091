#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # one chip, llama3-1b, shipped config

Drives the main path once through the entry points a user calls, at the
full width and depth of ``llama3-1b`` with random weights from a seed:

1. **serve phase** — starts ``python -m llmq_tpu --backend jax serve`` as
   a child (``JAX_PLATFORMS=tpu`` in ITS environment, so JAX itself
   refuses to start without a chip), waits for ``/health``, then answers
   one request per priority tier, a two-turn conversation whose second
   turn must hit the prefix cache, one SSE stream and a concurrent burst
   that must run fused mixed steps through the 2-deep pipeline; checks
   from ``engine/stats`` and ``/metrics`` that the engine sits on the
   expected chips with weights and KV on every one of them, that warm-up
   finished and nothing compiled after ready; SIGTERM, clean exit.
2. **kernel phase** — only after the server child has exited (a chip
   belongs to one process at a time), ``scripts/chip_kernel_check.py``:
   at the serve phase's exact geometry the compiled programs contain the
   Mosaic custom calls their routes name, and teacher-forced logits of
   the kernel path agree with the pure-JAX path and a float32 run.

This parent is stdlib-only and never imports ``jax`` or ``llmq_tpu``: the
chip belongs to the children. Any failing phase raises — there is no
``except`` around a phase — and the process exits non-zero having printed
no result line. On success the LAST stdout line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reports it in the server child.

Builder runs reuse the same script with configuration overrides, e.g.
``--mesh tp=4`` on a four-chip host or ``--set LLMQ_MODEL_NAME=llama3-8b
--set LLMQ_MODEL_QUANTIZATION=int8 ...`` (README "Running on the chip").
``--backend echo`` exists for the CPU unit test of this parent's logic
(tests/test_chip_smoke.py); it can never pass the device phase.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")

#: Whole-run budget in seconds (the contract allows 1200, compilation
#: included); every wait below is cut to what is left of it.
BUDGET_S = 1150.0
#: SIGTERM → exit bound (verify SKILL: serve exits < 14 s after traffic).
SHUTDOWN_S = 30.0


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: Any, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    sys.stdout.write(f"[chip_smoke] {msg}\n")
    sys.stdout.flush()


def http(method: str, url: str, body: Optional[Dict] = None,
         timeout: float = 30.0) -> Tuple[int, Any]:
    """One JSON request; returns (status, parsed body or text)."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    text = raw.decode("utf-8", "replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def metric(exposition: str, family: str) -> float:
    """Sum of a family's samples in a Prometheus exposition (0.0 when
    the family has no sample)."""
    total = 0.0
    for line in exposition.splitlines():
        m = re.match(rf"llm_queue_{family}(?:{{[^}}]*}})? (\S+)$", line)
        if m:
            total += float(m.group(1))
    return total


class Server:
    """The ``serve`` child: start, find its port, stop."""

    def __init__(self, backend: str, platform: str,
                 overrides: Dict[str, str], log_path: str) -> None:
        self.backend = backend
        self.platform = platform
        self.overrides = dict(overrides)
        self.log_path = log_path
        self.proc: Optional[subprocess.Popen] = None
        self.base = ""
        self.started_at = 0.0

    def child_env(self) -> Dict[str, str]:
        # The sandbox exports JAX_PLATFORMS=cpu; the child must not
        # inherit it. A compile cache placed from outside passes through.
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["JAX_PLATFORMS"] = self.platform
        env.setdefault("LLMQ_MODEL_NAME", "llama3-1b")
        env.update(self.overrides)
        return env

    def start(self) -> None:
        os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
        self.started_at = time.monotonic()
        with open(self.log_path, "wb") as logf:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "llmq_tpu", "--host", "127.0.0.1",
                 "--port", "0", "--backend", self.backend, "serve"],
                cwd=HERE, env=self.child_env(), stdout=logf,
                stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def wait_healthy(self, deadline: float) -> float:
        """Block until ``/health`` says ``engine: running``; returns
        start-to-healthy seconds. The port is read from the child's own
        "serving on host:port" log line (``--port 0`` = ephemeral)."""
        assert self.proc is not None
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server child exited with code {self.proc.returncode} "
                    f"before it was healthy; log tail:\n"
                    f"{self.log_text()[-3000:]}")
            if not self.base:
                m = re.search(r"serving on [^\s\"]*?:(\d+)", self.log_text())
                if m:
                    self.base = f"http://127.0.0.1:{m.group(1)}"
            if self.base:
                try:
                    status, body = http("GET", self.base + "/health",
                                        timeout=5.0)
                except OSError:
                    status, body = 0, None
                if (status == 200 and isinstance(body, dict)
                        and body.get("engine") == "running"):
                    return time.monotonic() - self.started_at
            time.sleep(0.25)
        raise SmokeFailure(
            f"server not healthy within budget; log tail:\n"
            f"{self.log_text()[-3000:]}")

    def stop(self) -> float:
        """SIGTERM; the child must drain and exit within SHUTDOWN_S
        with exactly one "shutting down" line. Returns exit seconds."""
        assert self.proc is not None
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=SHUTDOWN_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"server still running {SHUTDOWN_S:.0f}s after SIGTERM"
            ) from None
        took = time.monotonic() - t0
        check(rc == 0, f"server exited with code {rc} after SIGTERM")
        n = self.log_text().count("shutting down")
        check(n == 1, f'expected one "shutting down" log line, saw {n}')
        return took

    def kill(self) -> None:
        """Unconditional cleanup: no child outlives this script."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10.0)


# -- request helpers -----------------------------------------------------------


def submit(base: str, content: str, *, priority: int = 3,
           max_new_tokens: int = 16, conversation_id: str = "") -> str:
    # An explicit deadline: with the default 30 s a cold server sheds
    # every LOW-tier message (its no-samples wait estimate IS 30 s).
    body: Dict[str, Any] = {
        "content": content, "user_id": "chip-smoke", "priority": priority,
        "timeout": 300.0, "metadata": {"max_new_tokens": max_new_tokens}}
    if conversation_id:
        body["conversation_id"] = conversation_id
    status, resp = http("POST", base + "/api/v1/messages", body)
    check(status == 202, f"POST /api/v1/messages -> {status}: {resp}")
    return resp["message_id"]


def wait_completed(base: str, mid: str, timeout: float = 120.0) -> Dict:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        status, msg = http("GET", f"{base}/api/v1/messages/{mid}")
        check(status == 200, f"GET message {mid} -> {status}: {msg}")
        if msg["status"] == "completed":
            return msg
        check(msg["status"] != "failed",
              f"message {mid} failed: {msg.get('error')}")
        time.sleep(0.02)
    raise SmokeFailure(f"message {mid} not completed within {timeout:.0f}s")


def ask(base: str, content: str, **kw) -> Tuple[Dict, float]:
    """Submit + poll to completed; returns (message, end-to-end ms)."""
    t0 = time.monotonic()
    msg = wait_completed(base, submit(base, content, **kw))
    return msg, (time.monotonic() - t0) * 1e3


def usage_of(msg: Dict) -> Dict:
    usage = (msg.get("metadata") or {}).get("usage")
    check(isinstance(usage, dict), f"message {msg['id']} has no usage")
    return usage


# -- serve-phase checks --------------------------------------------------------


def phase_device(srv: Server, report: Dict, expect_chips: int) -> None:
    """The engine sits on the chips it should, with weights and KV on
    every one of them; warm-up finished."""
    _, health = http("GET", srv.base + "/health")
    dev = health.get("device")
    check(isinstance(dev, dict), f"/health carries no device: {health}")
    check(dev["platform"] == "tpu",
          f"server came up on platform {dev['platform']!r}, not tpu")
    _, stats = http("GET", srv.base + "/api/v1/engine/stats")
    model = stats["device"]["model"]
    check("tpu" in model["device_kind"].lower(),
          f"device_kind {model['device_kind']!r} is not a TPU kind")
    check(model["n_chips"] == expect_chips,
          f"engine serves on {model['n_chips']} chip(s), "
          f"expected {expect_chips}")
    chips = stats["device"]["hbm"].get("chips") or []
    check(len(chips) == expect_chips,
          f"{len(chips)} chip(s) hold model state, expected {expect_chips}")
    for c in chips:
        check(c["weights_bytes"] > 0 and c["kv_pool_bytes"] > 0,
              f"chip {c['chip']} holds no weights or no KV: {c}")
        check(c["limit_bytes"] is not None,
              f"chip {c['chip']} reports no HBM limit (CPU has no "
              f"memory_stats): {c}")
    _, expo = http("GET", srv.base + "/metrics")
    check(metric(expo, "warmup_progress") == 1.0,
          f"warmup_progress is {metric(expo, 'warmup_progress')}, not 1.0")
    comp = stats["device"]["compile"]
    _, queues = http("GET", srv.base + "/api/v1/queues/stats")
    m = re.search(r"warmup measured decode step ~([\d.]+) ms",
                  srv.log_text())
    report.update({
        "device": dev,
        "n_chips": model["n_chips"],
        "hbm_chips": chips,
        "programs": {name: p["source"]
                     for name, p in comp["programs"].items()},
        "routes": {name: p["routes"]
                   for name, p in comp["programs"].items()},
        "programs_compiled": comp["cache_misses"],
        "programs_from_cache": comp["cache_hits"],
        "warmup_s": comp["warmup_s"],
        "host_device_rtt_ms": stats["device"]["host_device_rtt_ms"],
        "warmup_decode_step_ms": float(m.group(1)) if m else None,
        "queue_core": queues["standard"].get("core"),
        "boot_stages_s": (health.get("boot") or {}).get("stages_s"),
    })


def phase_tiers(srv: Server, report: Dict) -> None:
    """One message per priority tier, polled to completed."""
    out = {}
    for prio, tier in ((1, "realtime"), (2, "high"), (3, "normal"),
                       (4, "low")):
        msg, ms = ask(srv.base, f"tier {tier}: say something short",
                      priority=prio, max_new_tokens=16)
        usage = usage_of(msg)
        check(usage["completion_tokens"] > 0,
              f"{tier} request produced no tokens: {usage}")
        out[tier] = {"e2e_ms": round(ms, 1),
                     "tokens_out": usage["completion_tokens"]}
    report["tiers"] = out


def phase_conversation(srv: Server, report: Dict) -> None:
    """Two turns on one conversation: the second must reuse cached KV."""
    conv = f"chip-smoke-{os.getpid()}"
    m1, _ = ask(srv.base, "first turn of a short conversation about "
                "paged attention", conversation_id=conv, max_new_tokens=12)
    m2, ms2 = ask(srv.base, " and a second turn that continues it",
                  conversation_id=conv, max_new_tokens=12)
    cached = usage_of(m2)["cached_tokens"]
    check(cached > 0, f"turn 2 reused no cached tokens: {usage_of(m2)}")
    report["conversation"] = {
        "turn1_prompt_tokens": usage_of(m1)["prompt_tokens"],
        "turn2_cached_tokens": cached, "turn2_e2e_ms": round(ms2, 1)}


def phase_stream(srv: Server, report: Dict) -> None:
    """One SSE request: ``start`` -> token deltas -> ``done``."""
    body = {"content": "stream a few tokens back to me",
            "user_id": "chip-smoke", "priority": 1, "stream": True,
            "metadata": {"max_new_tokens": 24}}
    req = urllib.request.Request(
        srv.base + "/api/v1/messages", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    events: List[Tuple[str, Dict]] = []
    t0 = time.monotonic()
    with urllib.request.urlopen(req, timeout=120.0) as resp:
        check(resp.status == 200, f"stream POST -> {resp.status}")
        ctype = resp.headers.get("Content-Type", "")
        check("text/event-stream" in ctype, f"stream Content-Type {ctype!r}")
        name = "message"
        for raw in resp:
            line = raw.decode("utf-8", "replace").rstrip("\r\n")
            if line.startswith("event: "):
                name = line[len("event: "):]
            elif line.startswith("data: "):
                events.append((name, json.loads(line[len("data: "):])))
                name = "message"
    ms = (time.monotonic() - t0) * 1e3
    names = [n for n, _ in events]
    check(len(names) >= 2 and names[0] == "start" and names[-1] == "done",
          f"stream events out of order: {names}")
    done = events[-1][1]
    check(done["finish_reason"] in ("eos", "length"),
          f"stream finished with {done['finish_reason']!r}")
    n_out = done["usage"]["completion_tokens"]
    check(n_out > 0, f"stream produced no tokens: {done}")
    # The deltas must add up to the stored response. (Random weights
    # over a 128k vocabulary mostly sample ids the byte tokenizer has
    # no text for, so an empty response with no delta is legitimate.)
    deltas = "".join(d["token"] for n, d in events
                     if n == "message" and "token" in d)
    _, stored = http("GET", f"{srv.base}/api/v1/messages/"
                            f"{done['message_id']}")
    check(stored["status"] == "completed",
          f"streamed message stored as {stored['status']!r}")
    check(deltas == stored["response"],
          f"stream deltas {deltas!r} != stored response "
          f"{stored['response']!r}")
    report["stream"] = {"first_token_ms": done["first_token_ms"],
                        "e2e_ms": round(ms, 1), "tokens_out": n_out,
                        "deltas": names.count("message")}


def phase_burst(srv: Server, report: Dict) -> None:
    """The two default-on paths need specific traffic. Four long
    decodes run ALONE first: with no scheduling work waiting, the
    engine keeps two chunks in flight (the 2-deep pipeline). Then eight
    long-ish prompts arrive staggered WHILE those rows decode: their
    budgeted prefill slices ride the decode chunks (fused mixed steps).
    A burst that lands all at once is prefilled in dedicated waves,
    and pending requests hold the pipeline at depth 1 — neither path
    would show."""
    prompt = ("the queue drains into a continuous batching engine on one "
              "chip and every prompt in this burst is long enough to need "
              "several budgeted prefill slices while earlier rows decode; ")
    # The decoders run alone for ~8 chunks (16 steps each at the
    # warm-up's measured step), then the prompts start arriving while
    # they still have half of their 256 tokens to go.
    step_ms = report.get("warmup_decode_step_ms") or 4.0
    solo_s = max(0.4, 8 * 16 * step_ms / 1e3)
    plan = ([(0.0, f"[decoder {i}] keep generating", 256)
             for i in range(4)]
            + [(solo_s + 0.03 * i, f"[{i}] " + prompt * 4, 32)
               for i in range(8)])
    results: List[Any] = [None] * len(plan)

    def one(i: int) -> None:
        delay, content, max_new = plan[i]
        time.sleep(delay)
        try:
            results[i] = ask(srv.base, content, priority=3,
                             max_new_tokens=max_new)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(plan))]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300.0)
    wall = time.monotonic() - t0
    check(not any(t.is_alive() for t in threads),
          "burst requests still running after 300s")
    for r in results:
        if isinstance(r, BaseException):
            raise r
    tokens = sum(usage_of(m)["completion_tokens"] for m, _ in results)
    _, stats = http("GET", srv.base + "/api/v1/engine/stats")
    mixed = stats.get("mixed_batch") or {}
    check(mixed.get("steps", 0) > 0,
          f"burst ran no fused mixed steps: {mixed}")
    pipe = stats.get("pipeline") or {}
    check(int((pipe.get("depth_hist") or {}).get("2", 0)) > 0,
          f"burst never had 2 chunks in flight: {pipe}")
    steps = stats["device"]["steps"]
    report["burst"] = {
        "requests": len(plan), "wall_s": round(wall, 2),
        "tokens_out": tokens,
        "e2e_ms_max": round(max(ms for _, ms in results), 1),
        "mixed_steps": mixed["steps"],
        "mixed_prefill_tokens": mixed.get("prefill_tokens"),
        "pipeline_depth_hist": pipe.get("depth_hist"),
        "step_dispatch_ms_mean": steps["dispatch_ms"]["mean_ms"],
        "step_device_ms_mean": steps["device_ms"]["mean_ms"],
        "step_readback_ms_mean": steps["readback_ms"]["mean_ms"]}


def serve_phase(srv: Server, report: Dict, expect_chips: int,
                deadline: float) -> None:
    srv.start()
    report["start_to_healthy_s"] = round(srv.wait_healthy(deadline), 1)
    say(f"healthy after {report['start_to_healthy_s']}s at {srv.base}")
    phase_device(srv, report, expect_chips)
    say(f"device ok: {report['device']} chips={report['n_chips']} "
        f"compiled={report['programs_compiled']} "
        f"cached={report['programs_from_cache']} "
        f"rtt={report['host_device_rtt_ms']}ms "
        f"decode_step={report['warmup_decode_step_ms']}ms "
        f"queue_core={report['queue_core']}")
    _, expo = http("GET", srv.base + "/metrics")
    misses0 = metric(expo, "compile_cache_misses_total")
    _, stats = http("GET", srv.base + "/api/v1/engine/stats")
    compiles0 = stats["device"]["compile"]["backend_compiles"]
    phase_tiers(srv, report)
    say(f"tiers ok: {report['tiers']}")
    phase_conversation(srv, report)
    say(f"conversation ok: {report['conversation']}")
    phase_stream(srv, report)
    say(f"stream ok: {report['stream']}")
    phase_burst(srv, report)
    say(f"burst ok: {report['burst']}")
    _, expo = http("GET", srv.base + "/metrics")
    misses1 = metric(expo, "compile_cache_misses_total")
    check(misses1 == misses0,
          f"a warm-up program compiled after ready: "
          f"compile_cache_misses_total {misses0} -> {misses1}")
    _, stats = http("GET", srv.base + "/api/v1/engine/stats")
    # Every XLA compilation in the process, eager one-liners included
    # (the warm-up counters above cannot see those; the warm-up runs
    # the serving loop's eager ops so that none is left for a request).
    compiles1 = stats["device"]["compile"]["backend_compiles"]
    check(compiles1 == compiles0,
          f"{compiles1 - compiles0} XLA compilation(s) after ready: "
          f"device.compile.backend_compiles {compiles0} -> {compiles1}")
    report["backend_compiles_after_ready"] = compiles1 - compiles0
    report["sigterm_exit_s"] = round(srv.stop(), 1)
    say(f"clean exit {report['sigterm_exit_s']}s after SIGTERM; "
        f"no XLA compilation after ready")


def kernel_phase(srv: Server, report: Dict, deadline: float) -> None:
    """Second child, started only now that the server has exited."""
    out_path = os.path.join(OUT_DIR, "chip_kernel_check.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "scripts",
                                      "chip_kernel_check.py"),
         "--out", out_path],
        cwd=HERE, env=srv.child_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
        timeout=max(1.0, deadline - time.monotonic()))
    log_path = os.path.join(OUT_DIR, "chip_kernel_check.log")
    with open(log_path, "w") as f:
        f.write(proc.stdout)
    check(proc.returncode == 0,
          f"kernel check exited with code {proc.returncode}; output tail:\n"
          f"{proc.stdout[-4000:]}")
    with open(out_path) as f:
        report["kernels"] = json.load(f)


def parse_mesh(text: str) -> Dict[str, int]:
    """``tp=4`` / ``dp=2,tp=2`` -> ordered axis sizes."""
    shape = {}
    for part in text.split(","):
        name, _, size = part.partition("=")
        if not name or not size.isdigit():
            raise argparse.ArgumentTypeError(
                f"mesh axis {part!r}: expected name=size")
        shape[name.strip()] = int(size)
    return shape


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="environment override for both children "
                         "(LLMQ_* config keys), repeatable")
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    help="serve on a mesh, e.g. tp=4 or dp=2,tp=2 "
                         "(four-chip host)")
    ap.add_argument("--backend", choices=["jax", "echo"], default="jax",
                    help="echo = CPU unit test of this script's logic")
    ap.add_argument("--skip-kernels", action="store_true",
                    help="serve phase only (builder runs)")
    ap.add_argument("--tag", default="",
                    help="suffix for the files written under chiprun_out/")
    args = ap.parse_args(argv)

    overrides: Dict[str, str] = {}
    for item in args.set:
        key, sep, val = item.partition("=")
        if not sep:
            ap.error(f"--set {item!r}: expected KEY=VALUE")
        overrides[key] = val
    expect_chips = 1
    if args.mesh:
        overrides["LLMQ_EXECUTOR_MESH_ENABLED"] = "true"
        overrides["LLMQ_EXECUTOR_MESH_SHAPE"] = json.dumps(args.mesh)
        for size in args.mesh.values():
            expect_chips *= size

    deadline = time.monotonic() + BUDGET_S
    tag = f"_{args.tag}" if args.tag else ""
    srv = Server(args.backend, "tpu" if args.backend == "jax" else "cpu",
                 overrides,
                 os.path.join(OUT_DIR, f"chip_smoke_serve{tag}.log"))
    report: Dict[str, Any] = {"overrides": overrides}
    report_path = os.path.join(OUT_DIR, f"chip_smoke{tag}.json")

    def write_report() -> None:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)

    t0 = time.monotonic()
    try:
        serve_phase(srv, report, expect_chips, deadline)
    finally:
        srv.kill()
    write_report()      # the serve record survives a kernel-phase failure
    if not args.skip_kernels:
        kernel_phase(srv, report, deadline)
        say(f"kernels ok: {json.dumps(report['kernels'].get('summary'))}")
    report["total_s"] = round(time.monotonic() - t0, 1)
    write_report()
    say("report: " + json.dumps(
        {k: v for k, v in report.items()
         if k not in ("kernels", "hbm_chips", "routes")}, sort_keys=True))
    sys.stdout.write(json.dumps({"ok": True, "device": report["device"]})
                     + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
