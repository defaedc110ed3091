"""Tracing / profiling hooks (SURVEY.md §5: the reference documents
pprof/Jaeger wiring but implements none of it; here tracing is real
code).

Two layers:

- **Device tracing** — :func:`trace` wraps a code region in
  ``jax.profiler`` (xprof): one trace captures XLA program timings, HBM
  transfers and TPU utilization, viewable in XProf/perfetto/tensorboard.
  The caller names the directory (``POST /api/v1/admin/profile``).
- **Host spans** — :class:`SpanRecorder`, the one span primitive:
  ``span(name, **counts)`` writes an in-process ring (name, start,
  duration, counts; ``GET /api/v1/engine/stats`` and the chrome export
  of ``observability/chrome.py`` read it) and, while a capture is held,
  the same interval as a ``TraceAnnotation`` on the device trace's
  clock. The engine step's vocabulary: docs/observability.md.
- **Loop watches** — ``SpanRecorder.loop(name)``: a long-lived loop
  (the engine's, a worker's, a completion thread's) beats once an
  iteration; the watch keeps the loop's account of its own gaps and
  logs ONE ``loop_stall`` line when a gap overruns, with the age of
  every other loop's last beat (docs/observability.md "Loops and
  stalls").
- **Device scopes** — :func:`scope`, ``jax.named_scope`` held to the
  fixed vocabulary :data:`SCOPES`: the names the traced programs give
  their own parts, which a capture's optimised HLO carries in every
  instruction's ``op_name`` (``benchmark/harness/scopes.py`` reads
  them). Metadata alone: trace time on the host, nothing on the device.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Optional

from llmq_tpu.utils.logging import get_logger

log = get_logger("profiling")

TRACE_DIR_ENV = "LLMQ_TRACE_DIR"


def trace_dir() -> Optional[str]:
    return os.environ.get(TRACE_DIR_ENV) or None


@contextmanager
def trace(label: str, dir: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace of the region under ``dir`` (the
    on-demand ``POST /api/v1/admin/profile`` path); no-op with none
    given. Safe on any backend."""
    if not dir:
        yield
        return
    import jax

    out = os.path.join(dir, label)
    os.makedirs(out, exist_ok=True)
    log.info("tracing %s → %s", label, out)
    with jax.profiler.trace(out):
        yield
    log.info("trace written to %s (view with xprof/tensorboard)", out)


#: Every name a traced program may give a part of itself, at three
#: levels (docs/observability.md "Device scopes"): the chunk programs'
#: steps, the row kinds of a mixed step, the model's modules. Short:
#: each is stored in every instruction's metadata of every executable.
SCOPES = (
    "mixed_step", "decode_loop", "prefill", "sample",
    "slices", "decode_rows",
    "embed", "qkv", "kv_write", "attn", "latent_prefill_attention",
    "attn_window", "attn_full", "attn_gate", "attn_out", "mlp",
    "moe_route", "moe_experts", "moe_combine", "head",
    "act_quant", "ssm_conv", "ssm_update", "ssm_scan", "kda_gates",
    "cca_mix", "hc_mix", "hc_project", "hc_apply",
    "row_tail", "export", "import",
)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES` and no
    other: the context under which a traced program's operations carry
    ``name`` in their ``op_name``. Entered while a program is traced,
    never while it runs."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not a device scope: {SCOPES}")
    import jax
    return jax.named_scope(name)


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` once JAX is in the process,
    ``None`` before: a capture is held through ``jax.profiler``, so
    without JAX there is none to land in — and a device-free backend
    (echo) must not import JAX for the sake of its spans."""
    jax = sys.modules.get("jax")
    # (``profiler`` is missing only while ``import jax`` is under way
    # on another thread)
    profiler = getattr(jax, "profiler", None)
    return getattr(profiler, "TraceAnnotation", None)


def capture_held() -> bool:
    """True while a profiler capture is held in this process (about
    0.1 µs). Counts that cost more than reading a field are computed
    only then."""
    cls = _annotation_cls()
    return cls is not None and cls.is_enabled()


@dataclass(slots=True)
class Span:
    name: str
    start: float      # perf_counter seconds
    duration: float
    meta: Optional[Dict] = None
    tid: int = 0      # thread that ran the span


class _OpenSpan:
    """One ``SpanRecorder.span`` interval: a plain context manager (no
    generator frame). Body exceptions propagate untouched."""

    __slots__ = ("_rec", "_name", "_counts", "_t0", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str,
                 counts: Optional[Dict]) -> None:
        self._rec = rec
        self._name = name
        self._counts = counts
        self._ann = None

    def __enter__(self) -> "_OpenSpan":
        cls = _annotation_cls()
        if cls is not None and cls.is_enabled():
            # On the profiler's clock, beside the device planes; the
            # counts become the event's arguments.
            self._ann = (cls(self._name, **self._counts) if self._counts
                         else cls(self._name))
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    @property
    def annotated(self) -> bool:
        """The span opened while a capture was held: it is an event of
        that capture."""
        return self._ann is not None

    def note(self, **counts) -> None:
        """Counts known only once the body ran (how many chunks a fill
        dispatched, why it stopped): added to the ring's record and,
        while a capture is held, to the annotation's arguments."""
        self._counts = {**(self._counts or {}), **counts}
        if self._ann is not None:
            self._ann.set_metadata(**counts)

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self._rec.record(self._name, self._t0, dt, self._counts)
        return False


# -- loop watches ---------------------------------------------------------------

#: An OVERRUN is a gap between two beats of one loop that is over
#: ``STALL_FACTOR`` times the loop's running median gap AND over
#: ``STALL_FLOOR_S``. Constants, not settings: the threshold comes from
#: what the loop measures of itself.
STALL_FACTOR = 20.0
STALL_FLOOR_S = 0.25
#: The running median moves by this factor a beat, towards the gap.
_MEDIAN_STEP = 1.1

#: Every live watch of the process by name: what "the other loops" of a
#: ``loop_stall`` line are. Written at ``open()`` / ``close()`` alone.
_LOOPS: Dict[str, "LoopWatch"] = {}
_LOOPS_MU = threading.Lock()
#: [seconds the garbage collector has run in this process, when the
#: collection under way began]. A collection holds the interpreter:
#: every loop gaps together, and a ``loop_stall`` line says how much of
#: its gap the collector took (``gc_ms``) — the one holder of the
#: interpreter that can be named from inside it.
_GC = [0.0, 0.0]


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        _GC[1] = time.perf_counter()
    else:
        _GC[0] += time.perf_counter() - _GC[1]


class _NamedWait:
    """One named wait of a loop (``LoopWatch.wait(name)``): a place
    where the loop's thread blocks on something else — a semaphore, a
    device transfer, its own idle poll. One object a name, re-entered:
    a loop is one thread. Its account: ``count``, ``total_s``,
    ``max_s``, and the overruns of the loop that fell inside it
    (``stalls``, ``stall_s``)."""

    __slots__ = ("_loop", "name", "count", "total_s", "max_s", "stalls",
                 "stall_s", "_t0")

    def __init__(self, loop: "LoopWatch", name: str) -> None:
        self._loop = loop
        self.name = name
        self.count = 0
        self.total_s = self.max_s = self.stall_s = 0.0
        self.stalls = 0
        self._t0 = 0.0

    def __enter__(self) -> "_NamedWait":
        self._t0 = self._loop._clock()
        self._loop.inside = self.name
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        loop = self._loop
        dt = loop._clock() - self._t0
        loop.inside = None
        self.count += 1
        self.total_s += dt
        if dt > self.max_s:
            self.max_s = dt
        if dt > loop._gap_wait_s:       # the longest since the last beat
            loop._gap_wait_s = dt
            loop._gap_wait = self
        return False

    def account(self) -> Dict[str, Any]:
        return {"count": self.count,
                "total_ms": round(self.total_s * 1e3, 3),
                "max_ms": round(self.max_s * 1e3, 3),
                "stalls": self.stalls,
                "stall_ms": round(self.stall_s * 1e3, 3)}


class LoopWatch:
    """A long-lived loop's account of its own gaps
    (``SpanRecorder.loop(name)``). The loop calls ``beat(**counts)``
    once an iteration: two field reads of the clock's one value and a
    few field updates, under no lock — each field has one writer, the
    loop's own thread; other threads only read ``last``. Kept: beats,
    the last beat, a running median of the gap (it steps by
    ``_MEDIAN_STEP`` towards each gap), the longest gap with its
    instant, what the loop was inside and the counts of the beat that
    ended it, and the overruns.

    An overrun (``STALL_FACTOR``, ``STALL_FLOOR_S``) is seen by the
    loop itself at the beat that ends the gap, and logged as ONE
    ``loop_stall`` warning with the age of every other loop's last
    beat at that instant: one loop stalled while the others beat is
    the program's; all of them gapping together is the interpreter
    held, or the machine. The overrun then lifts the median to
    ``gap / STALL_FACTOR``: a loop that enters a slower regime (a
    worker under backpressure) says so once, not every iteration.

    ``beat(False, ...)`` is the beat of an iteration that only slept on
    purpose (an idle poll): its gap is judged like any other and kept
    out of the median and of ``beats``, or a loop that polls while idle
    would measure its poll interval. ``rest()`` / ``wake()`` bracket a
    wait that has no bound (a queue's ``get``): no gap is counted over
    it, and the others see the loop as resting, not as late."""

    __slots__ = ("name", "_rec", "_clock", "beats", "last", "median_s",
                 "longest_s", "longest_at", "longest_inside",
                 "longest_counts", "overruns", "resting", "inside",
                 "_waits", "_gap_wait", "_gap_wait_s", "_gc_at")

    def __init__(self, name: str, rec: "SpanRecorder",
                 clock: Callable[[], float]) -> None:
        self.name = name
        self._rec = rec
        self._clock = clock
        self.beats = 0
        self.last = clock()
        self.median_s = 0.0
        self.longest_s = self.longest_at = 0.0
        self.longest_inside = ""
        self.longest_counts: Optional[Dict] = None
        self.overruns = 0
        self.resting = True          # until the loop's thread opens it
        #: The named wait the thread is in NOW (read by a stats scrape:
        #: where a loop that never reaches its next beat hangs).
        self.inside: Optional[str] = None
        self._waits: Dict[str, _NamedWait] = {}
        self._gap_wait: Optional[_NamedWait] = None
        self._gap_wait_s = 0.0
        self._gc_at = _GC[0]

    def wait(self, name: str) -> _NamedWait:
        w = self._waits.get(name)
        if w is None:
            w = self._waits[name] = _NamedWait(self, name)
        return w

    def beat(self, feed: bool = True, **counts) -> None:
        now = self._clock()
        gap = now - self.last
        self.last = now
        m = self.median_s
        if feed:
            self.beats += 1
            self.median_s = (gap if m <= 0.0 else
                             m * _MEDIAN_STEP if gap > m else
                             m / _MEDIAN_STEP)
        overrun = gap > STALL_FLOOR_S and gap > STALL_FACTOR * m > 0.0
        if overrun or gap > self.longest_s:
            self._long_gap(gap, now, m, counts, overrun)
        self._gap_wait = None
        self._gap_wait_s = 0.0
        self._gc_at = _GC[0]

    def rest(self) -> None:
        """The iteration is done and what follows is a wait without a
        bound: a beat, then no gap until ``wake()``."""
        self.beat()
        self.resting = True

    def wake(self) -> None:
        self.last = self._clock()
        self._gc_at = _GC[0]
        self.resting = False

    def open(self) -> "LoopWatch":
        """The loop's thread starts: it is one of the process's live
        loops from here (a new one of the same name takes its place),
        and its first gap starts now."""
        with _LOOPS_MU:
            _LOOPS[self.name] = self
            if _on_gc not in gc.callbacks:
                gc.callbacks.append(_on_gc)
        self.wake()
        return self

    def close(self) -> None:
        """The loop ended: the others stop counting its age, and its
        account is logged once (``loop_account``: what a run's log
        keeps of a loop that never overran — its longest gap, and what
        it was inside). The account stays readable on the watch."""
        self.resting = True
        with _LOOPS_MU:
            live = _LOOPS.get(self.name) is self
            if live:
                del _LOOPS[self.name]
        if live and self.beats:
            log.info("loop_account", extra={"fields": {
                "loop": self.name, **self.account()}})

    # -- the rare path ---------------------------------------------------------

    def _inside_of(self, start: float, gap: float) -> str:
        """The innermost span or named wait of this thread that holds
        at least half of the gap: the smallest such interval. Read from
        the ring after the fact (a span is recorded when it closes), so
        a beat pays nothing for it."""
        cands = []
        if self._gap_wait is not None:
            cands.append((self._gap_wait_s, self._gap_wait.name))
        tid = threading.get_ident()
        end = start + gap
        for s in self._rec.snapshot():
            if s.tid == tid and s.start < end and s.start + s.duration > start:
                cands.append((min(end, s.start + s.duration)
                              - max(start, s.start), s.name))
        held = [c for c in cands if c[0] >= gap / 2.0]
        return min(held)[1] if held else ""

    def _long_gap(self, gap: float, now: float, median: float,
                  counts: Dict, overrun: bool) -> None:
        start = now - gap
        inside = self._inside_of(start, gap)
        if gap > self.longest_s:
            self.longest_s, self.longest_at = gap, start
            self.longest_inside, self.longest_counts = inside, counts
        if not overrun:
            return
        self.overruns += 1
        if self._gap_wait is not None and self._gap_wait.name == inside:
            self._gap_wait.stalls += 1
            self._gap_wait.stall_s += gap
        self.median_s = max(self.median_s, gap / STALL_FACTOR)
        with _LOOPS_MU:
            others = [w for w in _LOOPS.values() if w is not self]
        # wall - perf_counter, as ``observability.perf_anchor()`` has
        # it for the flight recorder's events  # lint: allow-wallclock
        anchor = time.time() - time.perf_counter()
        log.warning("loop_stall", extra={"fields": {
            "loop": self.name, "gap_ms": round(gap * 1e3, 1),
            "median_ms": round(median * 1e3, 3),
            "start_perf": round(start, 6), "end_perf": round(now, 6),
            "start_wall": round(start + anchor, 6),
            "end_wall": round(now + anchor, 6),
            "inside": inside,
            # of the gap, what the garbage collector ran (it holds
            # every thread: all loops gap together, by about this)
            "gc_ms": round((_GC[0] - self._gc_at) * 1e3, 1), **counts,
            # ms since each other loop's last beat; None: resting in a
            # wait without a bound, by its own word
            "others_age_ms": {w.name: (None if w.resting else
                                       round((now - w.last) * 1e3, 1))
                              for w in others}}})

    def account(self) -> Dict[str, Any]:
        now = self._clock()
        return {
            "beats": self.beats,
            "last_beat_age_ms": (None if self.resting else
                                 round((now - self.last) * 1e3, 1)),
            "inside": self.inside,
            "median_ms": round(self.median_s * 1e3, 3),
            "longest_ms": round(self.longest_s * 1e3, 1),
            "longest_at_perf": round(self.longest_at, 6),
            "longest_inside": self.longest_inside,
            "longest_counts": self.longest_counts,
            "overruns": self.overruns,
            "waits": {n: w.account() for n, w in list(self._waits.items())},
        }


def loops() -> Dict[str, Dict[str, Any]]:
    """Every live loop's account by name (``get_stats()["loops"]``,
    ``GET /api/v1/engine/stats``)."""
    with _LOOPS_MU:
        watches = list(_LOOPS.values())
    return {w.name: w.account() for w in watches}


class SpanRecorder:
    """THE host-span primitive: ``span(name, **counts)`` writes a
    bounded in-memory ring (``GET /api/v1/engine/stats`` ``profile``,
    the chrome export) AND, while a profiler capture is held, opens a
    ``jax.profiler.TraceAnnotation(name, **counts)`` for the same
    interval, so the span lands on the device trace's clock with its
    counts as event arguments. With no capture held a span costs two
    clock reads, one ``is_enabled`` check and one ring append."""

    def __init__(self, capacity: int = 4096) -> None:
        self.capacity = capacity
        self._spans: deque = deque(maxlen=capacity)  # O(1) bounded append
        self._mu = threading.Lock()

    def span(self, name: str, **counts) -> _OpenSpan:
        return _OpenSpan(self, name, counts or None)

    def loop(self, name: str,
             clock: Callable[[], float] = time.perf_counter) -> LoopWatch:
        """A watch for the long-lived loop ``name`` (process-wide
        unique). The loop's thread calls ``open()`` when it starts,
        ``beat()`` once an iteration and ``close()`` when it ends;
        this ring is where an overrun looks for the span the thread
        was inside. Until it is opened the watch only keeps its named
        waits' account (a loop's body driven by a test)."""
        return LoopWatch(name, self, clock)

    def record(self, name: str, start: float, duration: float,
               meta: Optional[Dict] = None) -> None:
        span = Span(name, start, duration, meta, threading.get_ident())
        with self._mu:
            self._spans.append(span)

    def snapshot(self) -> List[Span]:
        with self._mu:
            return list(self._spans)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-name count/total/mean/max in milliseconds."""
        out: Dict[str, Dict[str, float]] = {}
        for s in self.snapshot():
            d = out.setdefault(s.name, {"count": 0, "total_ms": 0.0,
                                        "max_ms": 0.0})
            d["count"] += 1
            d["total_ms"] += s.duration * 1e3
            d["max_ms"] = max(d["max_ms"], s.duration * 1e3)
        for d in out.values():
            d["mean_ms"] = d["total_ms"] / max(1, d["count"])
            d["total_ms"] = round(d["total_ms"], 3)
            d["mean_ms"] = round(d["mean_ms"], 3)
            d["max_ms"] = round(d["max_ms"], 3)
        return out

    def __len__(self) -> int:
        with self._mu:
            return len(self._spans)
