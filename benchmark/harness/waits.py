"""Waiting, read two ways from what the program itself records.

**A request's wait, leg by leg** (``tail_legs``). Between the instant a
request is due and the instant the tap sees its first token lie seven
legs, cut at marks the flight recorder holds for every request of the
WHOLE window (``run["requests"][i]["stages"]``):

    due -> enqueued -> taken by a worker (scheduled / dispatched)
        -> admitted -> prefill_start -> prefill_last_dispatched
        -> first_token -> the tap's first token

``entry``, ``queue``, ``admission``, ``slot``, ``slices``,
``reconcile``, ``deliver``. They are read over "the tail": the
window's requests whose TTFT (``stats.ttft_ms``, as ``ttft_p95_ms``
takes it) is at or above the window's 90th percentile, and each leg is
the MEAN over those requests, so the seven add up to the tail's mean
TTFT — the sum telescopes, every stamp being ``perf_counter`` of one
machine (the recorder's wall stamps are shifted back by one anchor a
dump, so what the wall clock drifted between a stamp and the dump sits
in the two outer legs and cancels in the sum). Percentiles of different
requests do not add; means over the same requests do.

A request missing a mark is left out of all seven; where NO request of
the tail has them all (the parent of the PR that added
``prefill_last_dispatched``; a window with no finished request) every
leg is ``None``: never a split over the marks that happen to be there.

**The loop asleep, by what waited** (``wait_idle``). The engine loop
opens ONE ``engine.wait`` span an idle stretch, with ``pending``,
``active`` and ``inflight`` at its opening. The device's idle time of
the FIRST capture that falls under those spans (``harness/spans.py``'s
attribution: innermost span of the engine thread, nanosecond by
nanosecond) is divided in two: ``empty`` — nothing pending, no row
active, no chunk in flight: the traffic's idle, no program can have it
back — and ``starved`` — the loop slept while something waited. Both
are parts of ``idle_unnamed_share``; what is left of that share after
them is idle under no span at all: another thread had the interpreter.
A program that opens no ``engine.wait`` gives ``None`` — unless its
``engine.dispatch`` spans carry ``chunk`` (the same PR's), which says
the loop never slept inside the capture: then both are 0.

**The pipeline left short** (``fills``): the ``engine.fill`` spans of
the first capture with the reason each gave for stopping.

Standard library only.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.harness import spans, stats
from benchmark.harness.tracered import DEVICE_PLANE, OPS_LINE, union

Run = Dict[str, Any]

LEGS = ("entry", "queue", "admission", "slot", "slices", "reconcile",
        "deliver")
TAIL_Q = 90
WAIT = "engine.wait"
FILL = "engine.fill"
EMPTY, STARVED = "empty", "starved"


# -- the tail's legs -----------------------------------------------------------


def cuts(r: Dict[str, Any]) -> Optional[List[float]]:
    """The eight instants that bound a request's seven legs, or
    ``None`` where one is missing."""
    st = r.get("stages") or {}
    taken = next((st[k] for k in ("scheduled", "dispatched") if k in st),
                 None)
    pts = [r.get("due"), st.get("enqueued"), taken, st.get("admitted"),
           st.get("prefill_start"), st.get("prefill_last_dispatched"),
           st.get("first_token"), r.get("t_first")]
    return None if any(p is None for p in pts) else pts


def tail_legs(run: Run) -> Optional[Dict[str, float]]:
    """Mean milliseconds of each leg over the tail, with ``ttft`` (the
    same requests' mean TTFT: what the seven add up to), ``requests``
    (how many were read) and ``left_out`` (of the tail, missing a
    mark). Kept on the run: seven readers, one pass."""
    if "_tail_legs" not in run:
        run["_tail_legs"] = _tail_legs(run)
    return run["_tail_legs"]


def _tail_legs(run: Run) -> Optional[Dict[str, float]]:
    good = [r for r in run["requests"] if r.get("ok")]
    edge = stats.percentile(stats.collect(good, stats.ttft_ms), TAIL_Q,
                            len(run["requests"]) - len(good))
    if edge is None:
        return None
    tail = [r for r in good
            if stats.ttft_ms(r) is not None and stats.ttft_ms(r) >= edge]
    rows = [c for c in map(cuts, tail) if c is not None]
    if not rows:
        return None
    out = {leg: sum(c[i + 1] - c[i] for c in rows) / len(rows) * 1e3
           for i, leg in enumerate(LEGS)}
    out["ttft"] = sum(c[-1] - c[0] for c in rows) / len(rows) * 1e3
    out["requests"] = len(rows)
    out["left_out"] = len(tail) - len(rows)
    return out


def leg(name: str):
    """The reader of one leg (``metrics/ttft_tail_<name>_ms.py``)."""
    def read(run: Run) -> Optional[float]:
        legs = tail_legs(run)
        return None if legs is None else legs[name]
    return read


# -- the first capture's engine thread -----------------------------------------


def _first_trace(run: Run) -> Optional[Dict[str, Any]]:
    """The first capture in the neutral form, where ``spans.of_run``
    finds an engine step and a device at work in it. Kept on the run."""
    if "_wait_trace" not in run:
        trace = None
        if spans.of_run(run) is not None:
            trace = spans.neutral_of(run["captures"][0]["dir"])
        run["_wait_trace"] = trace
    return run["_wait_trace"]


def _engine_events(run: Run) -> List[List[Any]]:
    """The engine thread's ``engine.*`` events of the first capture."""
    trace = _first_trace(run)
    return spans.engine_line(trace)["events"] if trace else []


def _kind(ev: List[Any]) -> str:
    args = ev[3] if len(ev) > 3 else {}
    waiting = (args.get("pending", 0) or args.get("active", 0)
               or args.get("inflight", 0))
    return STARVED if waiting else EMPTY


def reduce_waits(trace: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """``{"empty": %, "starved": %, "unnamed": %}`` of the traced
    window: device idle time under ``engine.wait`` spans by what waited
    at their opening, and under no span at all. The window, the idle
    intervals and the attribution are ``spans.reduce_neutral``'s."""
    line = spans.engine_line(trace)
    devices = []
    for p in trace["planes"]:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        ops = [e for ln in p["lines"] if ln["name"] == OPS_LINE
               for e in ln["events"]]
        if ops:
            devices.append(union([(e[1], e[1] + e[2]) for e in ops]))
    if line is None or not devices:
        return None
    pieces = spans.innermost([[_kind(e), e[1], e[2]] if e[0] == WAIT else e
                              for e in line["events"]])
    t_lo = min(u[0][0] for u in devices)
    t_hi = max(u[-1][1] for u in devices)
    ns = {EMPTY: 0.0, STARVED: 0.0, spans.UNNAMED: 0.0}
    for u in devices:
        edges = [t_lo] + [t for iv in u for t in iv] + [t_hi]
        idle = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]
        for name, v in spans.attribute(idle, pieces).items():
            if name in ns:
                ns[name] += v / len(devices)
    return {k: 100.0 * v / (t_hi - t_lo) for k, v in ns.items()}


def wait_idle(run: Run) -> Optional[Dict[str, float]]:
    if "_wait_idle" not in run:
        shares = None
        if any(e[0] == WAIT for e in _engine_events(run)):
            shares = reduce_waits(_first_trace(run))
        elif any(e[0] == spans.DISPATCH and len(e) > 3 and "chunk" in e[3]
                 for e in _engine_events(run)):
            # the program names its idle stretches, and had none here
            shares = {EMPTY: 0.0, STARVED: 0.0}
        run["_wait_idle"] = shares
    return run["_wait_idle"]


def wait_share(kind: str):
    def read(run: Run) -> Optional[float]:
        shares = wait_idle(run)
        return None if shares is None else shares[kind]
    return read


def fills(run: Run) -> List[Dict[str, Any]]:
    """The arguments (``dispatched``, ``stopped``) of every
    ``engine.fill`` span of the first capture, in time order."""
    return [e[3] for e in _engine_events(run)
            if e[0] == FILL and len(e) > 3 and "stopped" in e[3]]
