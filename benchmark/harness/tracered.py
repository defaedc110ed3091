"""From a profiler capture to numbers: device busy time, time per
device operation, time per program, and the idle gaps named by what the
host was doing in them.

``xplane_to_neutral`` turns JAX's ``.xplane.pb`` into a plain form
(``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}``) and ``reduce_neutral`` works on that form alone,
so the reduction is checked on a small recorded trace with no profiler
(``selftest/``). Only ``xplane_to_neutral`` needs JAX.

Device planes are those named ``/device:TPU:<n>``. On such a plane the
line ``XLA Ops`` holds one event per executed operation (nested: a
``while`` spans its body) and ``XLA Modules`` one per program run.
Busy time is the UNION of the operation intervals, averaged over the
device planes; an operation's time is its SELF time (its children's
intervals taken out), so a loop is not counted on top of its body.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Host events that say a thread is waiting, not working: never the
#: name of a gap.
WAITS = re.compile(r"(^|[ :.])(wait|sleep|select|poll|acquire|accept|recv|"
                   r"recv_into|readinto|read|readline|get|join|_wait_for_tstate_lock|"
                   r"serve_forever|handle_request|run|_bootstrap|"
                   r"_bootstrap_inner|__call__|wrapper|inner)$")
MIN_GAP_NS = 20_000
LOOKBACK_NS = 200_000_000


def classify_programs(modules: List[List[Any]], ops: List[List[Any]],
                      decode_attn: str,
                      prefill_attn: str) -> Dict[str, List[float]]:
    """Program runs by what ran inside them, since an exported program
    is named ``jit_call(<fingerprint>)`` whatever it does: a run that
    holds decode attention calls is a ``decode`` chunk, one that holds
    prefill attention calls too a ``mixed`` chunk, one with prefill
    attention alone a ``prefill`` program. The two kernels are known by
    the patterns of the cell's model family (its ``shapes.py``
    ``DECODE_ATTN`` / ``PREFILL_ATTN``). kind -> [seconds, runs, decode
    attention calls, prefill attention calls]."""
    dec_rx, pre_rx = re.compile(decode_attn), re.compile(prefill_attn)
    dec = sorted(e[1] for e in ops if dec_rx.search(op_name(e[0])))
    pre = sorted(e[1] for e in ops if pre_rx.search(op_name(e[0])))
    out: Dict[str, List[float]] = {}
    for _name, start, dur in modules:
        nd = bisect.bisect_left(dec, start + dur) - bisect.bisect_left(
            dec, start)
        npf = bisect.bisect_left(pre, start + dur) - bisect.bisect_left(
            pre, start)
        kind = ("mixed" if nd and npf else "decode" if nd else
                "prefill" if npf else "other")
        acc = out.setdefault(kind, [0.0, 0, 0, 0])
        acc[0] += dur / 1e9
        acc[1] += 1
        acc[2] += nd
        acc[3] += npf
    return out


def xplane_to_neutral(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            lines.append({"name": ln.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def host_name(raw: str) -> str:
    """``$engine.py:3401 _commit_row`` -> ``engine.py:_commit_row``."""
    m = re.match(r"^\$?(?:.*/)?([^/:\s]+):\d+ (\S+)$", raw)
    if m:
        return f"{m.group(1)}:{m.group(2)}"
    return raw.lstrip("$")


def op_name(raw: str) -> str:
    """``fusion.6066`` -> ``fusion``: operations of one kind add up. An
    event's name may be the whole HLO instruction (``name = type
    custom-call(...)``): what stands before `` = `` is the name.
    Names that carry more than a counter (``fusion.6066.remat``, a
    kernel's name) stay as they are."""
    raw = raw.split(" = ", 1)[0].strip().lstrip("%")
    m = re.match(r"^([A-Za-z_][\w\-]*?)\.\d+$", raw)
    return m.group(1) if m else raw


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def self_times(events: List[List[Any]]) -> Dict[str, List[float]]:
    """name -> [self seconds, calls]. Events of one line nest or follow
    one another; a parent's self time leaves out its children."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: Dict[str, List[float]] = {}
    stack: List[List[float]] = []     # [end_ns, child_ns, name, dur]

    def close(item) -> None:
        name = op_name(item[2])
        acc = out.setdefault(name, [0.0, 0])
        acc[0] += max(0.0, item[3] - item[1]) / 1e9
        acc[1] += 1

    for name, start, dur in evs:
        while stack and start >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][1] += dur
        stack.append([start + dur, 0.0, name, dur])
    while stack:
        close(stack.pop())
    return out


def reduce_neutral(trace: Dict[str, Any], decode_attn: str,
                   prefill_attn: str) -> Dict[str, Any]:
    dev_planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    host_events: List[List[Any]] = []
    for p in trace["planes"]:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                host_events.extend(e for e in ln["events"] if e[2] > 0)
    if not dev_planes:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "ops": {},
                "modules": {}, "programs": {}, "idle_gaps": []}
    busy_total, ops, modules, programs = 0.0, {}, {}, {}
    t_lo, t_hi = float("inf"), 0.0
    gaps: List[Tuple[float, float]] = []
    for p in dev_planes:
        for ln in p["lines"]:
            if ln["name"] == OPS_LINE:
                ivs = [(e[1], e[1] + e[2]) for e in ln["events"]]
                if not ivs:
                    continue
                u = union(ivs)
                busy_total += sum(b - a for a, b in u) / 1e9
                t_lo, t_hi = min(t_lo, u[0][0]), max(t_hi, u[-1][1])
                if p is dev_planes[0]:
                    gaps = [(u[i][1], u[i + 1][0])
                            for i in range(len(u) - 1)
                            if u[i + 1][0] - u[i][1] >= MIN_GAP_NS]
                for k, v in self_times(ln["events"]).items():
                    acc = ops.setdefault(k, [0.0, 0])
                    acc[0] += v[0]
                    acc[1] += v[1]
            elif ln["name"] == MODULES_LINE:
                for name, _s, dur in ln["events"]:
                    acc = modules.setdefault(name, [0.0, 0])
                    acc[0] += dur / 1e9
                    acc[1] += 1
        by_line = {ln["name"]: ln["events"] for ln in p["lines"]}
        for k, v in classify_programs(
                by_line.get(MODULES_LINE, []), by_line.get(OPS_LINE, []),
                decode_attn, prefill_attn).items():
            acc = programs.setdefault(k, [0.0, 0, 0, 0])
            for i in range(4):
                acc[i] += v[i]
    n = len(dev_planes)
    named: Dict[str, float] = {}
    host_events.sort(key=lambda e: e[1])
    starts = [e[1] for e in host_events]
    for a, b in gaps:
        # The innermost host span that covers most of the gap and is
        # not a wait. Spans that began more than LOOKBACK_NS before the
        # gap are outer frames: never the innermost, so not searched.
        best, best_dur = None, float("inf")
        lo = bisect.bisect_left(starts, a - LOOKBACK_NS)
        hi = bisect.bisect_left(starts, b)
        for name, s, d in host_events[lo:hi]:
            overlap = min(b, s + d) - max(a, s)
            if overlap >= 0.5 * (b - a) and d < best_dur:
                hn = host_name(name)
                if not WAITS.search(hn):
                    best, best_dur = hn, d
        key = best or "(no host event)"
        named[key] = named.get(key, 0.0) + (b - a) / 1e9
    return {
        "devices": n,
        "busy_s": busy_total / n,
        "window_s": (t_hi - t_lo) / 1e9 if t_hi > t_lo else 0.0,
        "ops": {k: [v[0] / n, v[1]] for k, v in ops.items()},
        "modules": {k: [v[0] / n, v[1]] for k, v in modules.items()},
        "programs": {k: [v[0] / n] + [x / n for x in v[1:]]
                     for k, v in programs.items()},
        "idle_gaps": sorted(named.items(), key=lambda kv: -kv[1])[:10],
    }


def reduce_dir(trace_dir: str, decode_attn: str,
               prefill_attn: str) -> Dict[str, Any]:
    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_neutral(xplane_to_neutral(path), decode_attn,
                          prefill_attn)


def top_ops(red: Dict[str, Any], k: int = 10) -> List[List[Any]]:
    return [[name, v[0]] for name, v in
            sorted(red["ops"].items(), key=lambda kv: -kv[1][0])[:k]]


def sample(trace_dir: str, out_path: str, millis: float) -> None:
    """Cut the first ``millis`` ms of device activity out of a capture
    and write it in the neutral form: how ``selftest/data`` gets a
    small recorded trace."""
    import json
    tr = xplane_to_neutral(find_xplane(trace_dir) or "")
    starts = [e[1] for p in tr["planes"] if DEVICE_PLANE.match(p["name"])
              for ln in p["lines"] if ln["name"] == OPS_LINE
              for e in ln["events"]]
    t0 = min(starts)
    t1 = t0 + millis * 1e6
    planes = []
    for p in tr["planes"]:
        lines = []
        for ln in p["lines"]:
            evs = [[e[0], e[1] - t0, e[2]] for e in ln["events"]
                   if e[1] < t1 and e[1] + e[2] > t0]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump({"planes": planes}, f)


if __name__ == "__main__":
    import sys
    sample(sys.argv[1], sys.argv[2], float(sys.argv[3]))
