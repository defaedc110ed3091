"""Tracing / profiling hooks (SURVEY.md §5: the reference documents
pprof/Jaeger wiring but implements none of it; here tracing is real
code).

Two layers:

- **Device tracing** — :func:`trace` wraps a code region in
  ``jax.profiler`` (xprof): one trace captures XLA program timings, HBM
  transfers and TPU utilization, viewable in XProf/perfetto/tensorboard.
  Enabled ambiently by setting ``LLMQ_TRACE_DIR`` (bench.py and the
  engine loop honor it).
- **Host spans** — :class:`SpanRecorder`, the one span primitive:
  ``span(name, **counts)`` writes an in-process ring (name, start,
  duration, counts; ``GET /api/v1/engine/stats`` and the chrome export
  of ``observability/chrome.py`` read it) and, while a capture is held,
  the same interval as a ``TraceAnnotation`` on the device trace's
  clock. The engine step's vocabulary: docs/observability.md.
- **Device scopes** — :func:`scope`, ``jax.named_scope`` held to the
  fixed vocabulary :data:`SCOPES`: the names the traced programs give
  their own parts, which a capture's optimised HLO carries in every
  instruction's ``op_name`` (``benchmark/harness/scopes.py`` reads
  them). Metadata alone: trace time on the host, nothing on the device.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional

from llmq_tpu.utils.logging import get_logger

log = get_logger("profiling")

TRACE_DIR_ENV = "LLMQ_TRACE_DIR"


def trace_dir() -> Optional[str]:
    return os.environ.get(TRACE_DIR_ENV) or None


@contextmanager
def trace(label: str = "llmq", dir: Optional[str] = None) -> Iterator[None]:
    """Capture a jax.profiler trace of the region if LLMQ_TRACE_DIR is
    set (or an explicit ``dir`` is given — the on-demand
    ``POST /api/v1/admin/profile`` path); no-op otherwise. Safe on any
    backend."""
    d = dir or trace_dir()
    if not d:
        yield
        return
    import jax

    out = os.path.join(d, label)
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
    "cca_mix",
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

    def clear(self) -> None:
        with self._mu:
            self._spans.clear()

    def __len__(self) -> int:
        with self._mu:
            return len(self._spans)
