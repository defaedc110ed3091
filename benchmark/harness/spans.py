"""The engine step seen from inside a capture: the program's own host
spans (``engine.*``, ``utils/profiling.SpanRecorder.span``) on the
device trace's clock, with the counts they carry.

``tracered.py`` names idle gaps from the SECOND capture, where the
Python tracer slows the host several times over and the name is
whatever thread's frame is innermost. This module reads the FIRST
capture (Python tracer off): the program's spans are there at full
speed, and only the thread that holds ``engine.step`` is asked.

``xplane_to_neutral`` turns the capture's ``.xplane.pb`` into a plain
form that keeps what ``tracered``'s form drops: an event's arguments
and its thread —

    {"planes": [{"name", "lines": [{"name", "thread", "events":
        [[name, start_ns, dur_ns] or [name, start_ns, dur_ns, {args}],
         ...]}]}]}

— holding the device planes' ``XLA Modules`` line and their ``XLA Ops``
line merged into the union of its intervals (events named ``busy``:
operation times by name are ``tracered``'s business) and, of the host
planes, the lines and events named ``engine.*``. Only that
function needs JAX; the metric readers run in the benchmark's parent
process, which never imports it, so ``neutral_of`` runs this file as a
subprocess (``JAX_PLATFORMS=cpu``: the chip belongs to the server
child) and keeps the result beside the trace: nine readers, one parse.
``reduce_neutral`` is standard library alone and is checked on a small
recorded trace (``selftest/test_spans.py``).

Idle time is attributed by overlap, nanosecond by nanosecond: the idle
intervals of a device (the complement of the union of its operation
intervals between its first and last operation — exactly what
``device_idle_share`` is one minus) are cut against the engine
thread's spans, and each piece goes to the INNERMOST span that covers
it. The pieces add up to the idle time, so the shares add up to
``device_idle_share``. A program that opens no ``engine.step`` span
(the parent of the PR that added them) gives ``None``: its readers
return nothing.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any, Dict, List, Optional, Tuple

if __name__ == "__main__":       # run as a file: find the package
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.harness.tracered import (DEVICE_PLANE, MODULES_LINE,  # noqa: E402
                                        OPS_LINE, find_xplane, union)

NEUTRAL_FILE = "spans_neutral.json"
PREFIX = "engine."
STEP = "engine.step"
DISPATCH = "engine.dispatch"
#: The innermost span's name -> the share it is counted in. Idle under
#: ``engine.step`` itself (between its phases: gauges, pin expiry, the
#: loop's own glue) is scheduling; idle under no span at all is
#: ``unnamed`` (the loop asleep, or another thread has the interpreter).
GROUPS = {
    "engine.reconcile": "reconcile", "engine.fetch": "reconcile",
    "engine.commit": "reconcile",
    "engine.ingest": "schedule", "engine.admit": "schedule",
    "engine.prefill_advance": "schedule", "engine.resolve": "schedule",
    STEP: "schedule",
    "engine.assemble": "assemble", "engine.fill": "assemble",
    DISPATCH: "assemble",
}
UNNAMED = "unnamed"

# -- .xplane.pb -> neutral (needs JAX) -----------------------------------------


def xplane_to_neutral(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    planes = []
    for pl in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(pl.name))
        if not device and not pl.name.startswith("/host:"):
            continue
        lines = []
        for index, ln in enumerate(pl.lines):
            if device and ln.name == MODULES_LINE:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in ln.events]
            elif device and ln.name == OPS_LINE:
                # The union of the operation intervals is all that is
                # asked of them here (their times by name are
                # ``tracered``'s): a megabyte, not forty.
                events = [["busy", a, b - a] for a, b in union(
                    [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                     for e in ln.events])]
            elif device:
                continue
            else:
                events = []
                for e in ln.events:
                    if not e.name.startswith(PREFIX):
                        continue
                    args = {k: v for k, v in e.stats
                            if isinstance(v, (int, float, str))}
                    ev = [e.name, float(e.start_ns), float(e.duration_ns)]
                    events.append(ev + [args] if args else ev)
            if events:
                lines.append({"name": ln.name, "thread": index,
                              "events": events})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def neutral_of(trace_dir: str) -> Optional[Dict[str, Any]]:
    """The capture under ``trace_dir`` in the neutral form: read from
    the file kept beside it, made by a subprocess the first time."""
    cached = os.path.join(trace_dir, NEUTRAL_FILE)
    if not os.path.exists(cached):
        if find_xplane(trace_dir) is None:
            return None
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.setdefault("TPU_LOG_DIR", "disabled")
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), trace_dir],
            env=env, capture_output=True, text=True, timeout=600)
        if p.returncode != 0 or not os.path.exists(cached):
            sys.stderr.write("spans: no neutral form of "
                             f"{trace_dir}: {p.stderr[-800:]}\n")
            return None
    with open(cached, "r", encoding="utf-8") as f:
        return json.load(f)


# -- neutral -> numbers (standard library) -------------------------------------


def innermost(events: List[List[Any]]) -> List[Tuple[float, float, str]]:
    """Disjoint ``(a, b, name)`` pieces of one thread's nested spans,
    each named by the innermost span that covers it, in time order."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []        # (end, name), outermost first
    cursor = 0.0

    def close(until: float) -> None:
        """Pop every span that ended by ``until``; each owns the time
        from the cursor to its end."""
        nonlocal cursor
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        name, start, dur = ev[0], ev[1], ev[2]
        close(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][1]))
        cursor = start
        # a child may not outlast its parent (a few ns of clock jitter)
        end = min(start + dur, stack[-1][0]) if stack else start + dur
        stack.append((end, name))
    close(float("inf"))
    return out


def attribute(idle: List[Tuple[float, float]],
              pieces: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds of ``idle`` under each name of ``pieces`` (both
    sorted, each disjoint); what no piece covers goes to ``UNNAMED``."""
    by_name: Dict[str, float] = {}
    i = 0
    for a, b in idle:
        covered = 0.0
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            lo, hi = max(a, pieces[j][0]), min(b, pieces[j][1])
            if hi > lo:
                by_name[pieces[j][2]] = by_name.get(pieces[j][2], 0.0) \
                    + hi - lo
                covered += hi - lo
            j += 1
        by_name[UNNAMED] = by_name.get(UNNAMED, 0.0) + (b - a) - covered
    return by_name


def engine_line(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The host line (thread) that holds ``engine.step``: the engine
    thread. The one with most of them, should two engines trace."""
    best, best_n = None, 0
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            n = sum(1 for e in ln["events"] if e[0] == STEP)
            if n > best_n:
                best, best_n = ln, n
    return best


def reduce_neutral(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``None`` unless the capture holds a device plane with operations
    and a thread with ``engine.step`` spans. Otherwise the idle time by
    innermost span and by share, and the dispatches with their counts:

        {"window_s", "busy_s", "idle_s", "idle_by_span_s": {name: s},
         "idle_share": {group: % of the window},
         "dispatches": [{args of each engine.dispatch in the window}],
         "modules": [names on XLA Modules], "engine_thread"}
    """
    line = engine_line(trace)
    devices = []
    for p in trace["planes"]:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        ops = [e for ln in p["lines"] if ln["name"] == OPS_LINE
               for e in ln["events"]]
        if ops:
            devices.append((p, union([(e[1], e[1] + e[2]) for e in ops])))
    if line is None or not devices:
        return None
    pieces = innermost(line["events"])
    # As ``tracered.reduce_neutral`` has it: the window runs from the
    # first operation of any device to the last; busy time is the mean
    # over the devices of the union of their operation intervals.
    n = len(devices)
    t_lo = min(u[0][0] for _p, u in devices)
    t_hi = max(u[-1][1] for _p, u in devices)
    window = t_hi - t_lo
    busy = 0.0
    by_span: Dict[str, float] = {}
    for _p, u in devices:
        busy += sum(b - a for a, b in u) / n
        edges = [t_lo] + [t for iv in u for t in iv] + [t_hi]
        idle = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]
        for name, ns in attribute(idle, pieces).items():
            by_span[name] = by_span.get(name, 0.0) + ns / n
    shares = {g: 0.0 for g in set(GROUPS.values()) | {UNNAMED}}
    for name, ns in by_span.items():
        shares[GROUPS.get(name, UNNAMED)] += 100.0 * ns / window
    dispatches = [e[3] for e in line["events"]
                  if e[0] == DISPATCH and len(e) > 3
                  and t_lo <= e[1] <= t_hi]
    modules = sorted({e[0] for p, _u in devices for ln in p["lines"]
                      if ln["name"] == MODULES_LINE for e in ln["events"]})
    return {"window_s": window / 1e9, "busy_s": busy / 1e9,
            "idle_s": (window - busy) / 1e9,
            "idle_by_span_s": {k: v / 1e9 for k, v in sorted(
                by_span.items(), key=lambda kv: -kv[1])},
            "idle_share": shares, "dispatches": dispatches,
            "modules": modules, "engine_thread": line.get("thread")}


def of_run(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of the run's FIRST capture (Python tracer off), or
    ``None`` where there is none or it shows no engine step. Kept on
    the run, so the readers of one run parse once."""
    if "_spans" not in run:
        caps = run.get("captures") or []
        trace_dir = caps[0].get("dir") if caps else None
        trace = (neutral_of(trace_dir)
                 if trace_dir and os.path.isdir(trace_dir) else None)
        run["_spans"] = reduce_neutral(trace) if trace else None
    return run["_spans"]


def idle_share(run: Dict[str, Any], group: str) -> Optional[float]:
    red = of_run(run)
    return None if red is None else red["idle_share"][group]


def chunks(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The capture's dispatches of programs that decode (``steps`` >
    0: chunks, mixed chunks, verify windows), with their counts."""
    red = of_run(run)
    if red is None:
        return []
    return [d for d in red["dispatches"] if d.get("steps", 0) > 0]


def sample(trace_dir: str, out_path: str, millis: float) -> None:
    """Cut ``millis`` ms out of a capture, from the start of its first
    ``engine.step`` that begins while the device works, and write them
    in the neutral form: how ``selftest/data`` gets a small recorded
    trace. Every event is clipped to the cut."""
    tr = neutral_of(trace_dir)
    assert tr is not None, trace_dir
    line = engine_line(tr)
    assert line is not None, "no engine.step in the capture"
    ops_lo = min(e[1] for p in tr["planes"] if DEVICE_PLANE.match(p["name"])
                 for ln in p["lines"] if ln["name"] == OPS_LINE
                 for e in ln["events"])
    t0 = min(e[1] for e in line["events"] if e[0] == STEP and e[1] >= ops_lo)
    t1 = t0 + millis * 1e6
    for p in tr["planes"]:
        for ln in p["lines"]:
            cut = []
            for e in ln["events"]:
                a, b = max(e[1], t0), min(e[1] + e[2], t1)
                if b > a:
                    cut.append([e[0], a - t0, b - a] + e[3:])
            ln["events"] = cut
        p["lines"] = [ln for ln in p["lines"] if ln["events"]]
    tr["planes"] = [p for p in tr["planes"] if p["lines"]]
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(tr, f, separators=(",", ":"))


if __name__ == "__main__":
    if len(sys.argv) == 4:
        sample(sys.argv[1], sys.argv[2], float(sys.argv[3]))
    else:
        _dir = sys.argv[1]
        _tmp = os.path.join(_dir, f"{NEUTRAL_FILE}.{os.getpid()}.tmp")
        with open(_tmp, "w", encoding="utf-8") as _f:
            json.dump(xplane_to_neutral(find_xplane(_dir) or ""), _f,
                      separators=(",", ":"))
        os.replace(_tmp, os.path.join(_dir, NEUTRAL_FILE))
