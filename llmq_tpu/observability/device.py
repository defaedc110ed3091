"""Device telemetry plane (docs/observability.md "Device telemetry").

PR 3 made *requests* legible (stage timelines, flight recorder); this
module makes the *device* legible while serving — the numbers that were
previously computed only offline in bench.py and therefore invisible in
production:

- **Step-time decomposition** — every decode/mixed chunk is split into
  host dispatch (batch assembly + program dispatch), device execute
  (dispatch → output ready) and token readback (device→host transfer),
  exported as ``step_{dispatch,device,readback}_ms`` histograms. This
  is the measurement the APEX-style async-pipeline work (ROADMAP item
  4) will be judged against: you cannot erase an RTT you never see.
- **Live MFU / decode tok/s** — the FLOPs math bench.py used offline
  (``mfu_pct``) lives here now; bench and the serving path share one
  implementation, and a gauge tracks the trailing-window decode rate.
- **HBM accounting** — per-chip weights/KV-pool footprints, pool
  occupancy/fragmentation, free headroom (``jax`` ``memory_stats``
  where the backend provides it).
- **Compile/export-cache visibility** — per-program compile seconds,
  export-cache hit/miss counters and a warmup-progress gauge, so the
  303 s compile surface of BENCH_r03 is attributable per program.
- **On-demand profiling** — a single-flight ``jax.profiler`` capture
  behind ``POST /api/v1/admin/profile`` (concurrent captures 409).

One :class:`DeviceTelemetry` per engine name (process-singleton map,
like ``metrics.get_metrics``): the engine, its executor, the bench and
the API server all read/write the same live registry. Hot-path writes
(``note_step``) are a few dict/deque updates plus three histogram
observes — the <3 % step-path budget is guarded by
tests/test_device_telemetry.py.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from llmq_tpu.utils.logging import get_logger

log = get_logger("observability.device")

# -- shared FLOPs / RTT math (moved out of bench.py; bench imports these) -----

#: device_kind substring → peak bf16 FLOP/s per chip, from Google
#: Cloud's TPU documentation (v5e: "TPU v5e", 197 TFLOP/s bf16,
#: 393 TOP/s int8). The ONE peaks table: bench and serving both read
#: it. A device that is not listed has NO peak — see :func:`peak_flops`.
PEAK_BF16_FLOPS = {
    "v5 lite": 197e12, "v5e": 197e12,
    "v5p": 459e12, "v4": 275e12, "v6": 918e12,
}


class UnknownDeviceError(LookupError):
    """``device_kind`` is not in the peaks table: a utilization figure
    for it would be a guess. Live telemetry reports no figure (CPU
    tests); anything producing a chip number lets this propagate."""


def _peak(table: Dict[str, float], device_kind: str) -> float:
    kl = (device_kind or "").lower()
    for k, v in table.items():
        if k in kl:
            return v
    raise UnknownDeviceError(
        f"no published peak for device_kind {device_kind!r}; add it to "
        f"the table in observability/device.py with its source")


def peak_flops(device_kind: str, quant: str = "") -> float:
    """Peak FLOP/s for a device kind; int8 weights double the v5e MXU
    path's rate. Raises :class:`UnknownDeviceError` for a kind the
    table does not list."""
    peak = _peak(PEAK_BF16_FLOPS, device_kind)
    return peak * 2 if quant == "int8" else peak


def decode_mfu(tokens_per_s: float, n_params: int, device_kind: str,
               quant: str = "", n_chips: int = 1) -> float:
    """Decode-phase model FLOPs utilization as a FRACTION: each token
    costs ~2·n_params FLOPs (the dense matmuls; attention is negligible
    at serving context lengths). ``n_params`` is what one token
    multiplies with: every parameter of a dense model, the ACTIVE count
    of a routed one (the executor passes its family's
    ``active_param_count``; a held count would overstate a sparse
    model's utilization by its experts' ratio). ``n_chips`` scales the denominator to
    the serving mesh's aggregate peak — a dp2×tp4 engine is measured
    against 8 chips' FLOPs, not one (docs/multihost.md)."""
    if tokens_per_s <= 0 or n_params <= 0:
        return 0.0
    return (tokens_per_s * 2.0 * n_params
            / (peak_flops(device_kind, quant) * max(1, int(n_chips))))


#: device_kind substring → peak HBM bandwidth (bytes/s), same source.
#: Decode attention and the weight stream are BANDWIDTH-bound — MFU
#: alone under-tells the story (a 2× MFU gain at the same bandwidth
#: utilization just means fewer wasted bytes per useful FLOP), so the
#: bench reports both side by side.
PEAK_HBM_BYTES = {
    "v5 lite": 819e9, "v5e": 819e9,
    "v5p": 2765e9, "v4": 1228e9, "v6": 1640e9,
}


def peak_hbm_bandwidth(device_kind: str) -> float:
    """Peak HBM bytes/s for a device kind (raises
    :class:`UnknownDeviceError` like :func:`peak_flops`)."""
    return _peak(PEAK_HBM_BYTES, device_kind)


def decode_hbm_bw_util(tokens_per_s: float, batch: int,
                       weight_bytes: int, kv_bytes_per_token: int,
                       mean_context: float, device_kind: str,
                       n_chips: int = 1, dp: int = 1) -> float:
    """Achieved HBM-bandwidth utilization of the decode loop as a
    FRACTION: each decode STEP streams the weights once for the whole
    batch plus each row's live KV window (≈ mean_context tokens), and
    steps/s = tokens_per_s / batch. Explicit arithmetic over the model
    constants — a lower bound (activations, page padding and the KV
    writeback are excluded), reported next to MFU so bandwidth-bound
    kernels are judged on the axis they are actually bound by.

    Mesh accounting: ``n_chips`` scales the peak like
    :func:`decode_mfu` (aggregate bandwidth of the serving mesh), and
    ``dp`` scales the WEIGHT traffic — weights replicate per dp group,
    so each of the dp replicas streams its own copy of the (tp-
    sharded) weights every step, while KV pages are globally
    partitioned and stream once."""
    if tokens_per_s <= 0 or batch <= 0:
        return 0.0
    steps_per_s = tokens_per_s / batch
    bytes_per_step = (weight_bytes * max(1, int(dp))
                      + batch * kv_bytes_per_token * max(0.0, mean_context))
    return (steps_per_s * bytes_per_step
            / (peak_hbm_bandwidth(device_kind) * max(1, int(n_chips))))


def device_identity() -> Dict[str, Any]:
    """What JAX says this process runs on — platform, device kind and
    device count — for the boot log line, ``/health``, ``check`` and
    every printed result: a server that came up on the CPU must not be
    able to look like one that came up on a chip."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def describe_device(ident: Optional[Dict[str, Any]]) -> str:
    """One-token form of :func:`device_identity` for log lines:
    ``tpu:TPU v5 litex1`` (``no device`` for a device-free backend)."""
    if not ident:
        return "no device"
    return f"{ident['platform']}:{ident['kind']}x{ident['count']}"


class _BackendCompiles:
    """Process-wide count of XLA backend compilations, fed by JAX's own
    monitoring event (one per program compiled or loaded from the
    persistent cache, warm-up or not). The warm-up counters only see
    warm-up; this is what shows a program compiled AFTER ready — an
    unwarmed shape, an eager op on a first request."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._watching = False
        self.count = 0

    def watch(self) -> None:
        """Register the listener once (JAX offers no unregister)."""
        with self._mu:
            if self._watching:
                return
            self._watching = True
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, _secs: float, **_kw) -> None:
        if name == self._EVENT:
            with self._mu:
                self.count += 1


BACKEND_COMPILES = _BackendCompiles()


class _XlaCacheLookups:
    """What XLA's persistent compilation cache answered, per THREAD,
    from JAX's own monitoring events (one when a compile asks the
    cache, one more when the cache serves it). JAX runs the listener
    on the thread that compiles, and the warm-up compiles its programs
    on a thread each, so a thread's two counts read before and after a
    compile say which way THAT program went (``outcome``)."""

    _ASKED = "/jax/compilation_cache/compile_requests_use_cache"
    _SERVED = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._watching = False
        self._local = threading.local()

    def watch(self) -> None:
        """Register the listener once (as ``_BackendCompiles.watch``)."""
        with self._mu:
            if self._watching:
                return
            self._watching = True
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == self._ASKED:
            self._local.asked = getattr(self._local, "asked", 0) + 1
        elif name == self._SERVED:
            self._local.served = getattr(self._local, "served", 0) + 1

    def mark(self) -> Tuple[int, int]:
        """This thread's (asked, served) counts so far."""
        return (getattr(self._local, "asked", 0),
                getattr(self._local, "served", 0))

    def outcome(self, since: Tuple[int, int]) -> str:
        """``hit``: every compile of this thread since ``since`` was
        served by the cache; ``miss``: one was compiled; ``off``: none
        asked (no cache directory, or below its thresholds)."""
        asked, served = self.mark()
        asked, served = asked - since[0], served - since[1]
        if asked == 0:
            return "off"
        return "hit" if served == asked else "miss"


XLA_CACHE = _XlaCacheLookups()


def measure_rtt(samples: int = 5) -> float:
    """Host↔device round-trip floor in ms (median of ``samples`` tiny
    synchronous dispatch+fetch cycles): every synchronous fetch pays
    this. Shared by bench.py and executor warmup."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros(8, jnp.int32)
    np.asarray(f(x))    # compile outside the timed loop
    rtts = []
    for _ in range(max(1, samples)):
        t0 = time.perf_counter()
        np.asarray(f(x))
        rtts.append(time.perf_counter() - t0)
    return sorted(rtts)[len(rtts) // 2] * 1e3


# -- per-engine telemetry ------------------------------------------------------


class _StepStat:
    """Running count/sum/max/last for one step component (ms)."""

    __slots__ = ("count", "total_ms", "max_ms", "last_ms")

    def __init__(self) -> None:
        self.count = 0
        self.total_ms = 0.0
        self.max_ms = 0.0
        self.last_ms = 0.0

    def add(self, ms: float) -> None:
        self.count += 1
        self.total_ms += ms
        self.last_ms = ms
        if ms > self.max_ms:
            self.max_ms = ms

    def to_dict(self) -> Dict[str, float]:
        return {
            "count": self.count,
            "total_ms": round(self.total_ms, 3),
            "mean_ms": round(self.total_ms / self.count, 3)
            if self.count else 0.0,
            "max_ms": round(self.max_ms, 3),
            "last_ms": round(self.last_ms, 3),
        }


class DeviceTelemetry:
    """Live device-plane state for one engine name.

    Writers: the engine's scheduling thread (``note_step``), the
    executor's warmup threads (``note_compile``/``note_warmup``).
    Readers: the /metrics scrape (``flush``), ``get_stats`` snapshots,
    and bench's per-rate-point attribution. A small lock guards the
    cross-thread aggregates; the prometheus client is internally
    thread-safe."""

    #: Trailing window for the live decode-rate gauge.
    RATE_WINDOW_S = 30.0

    def __init__(self, name: str, *, metrics: bool = True) -> None:
        self.name = name
        #: When False, ``note_step`` skips the prometheus observes but
        #: keeps the host-side aggregates (bench engines run with
        #: metrics off yet still read per-rate-point telemetry).
        self.metrics_enabled = metrics
        self._mu = threading.Lock()
        self._dispatch = _StepStat()
        self._device = _StepStat()
        self._readback = _StepStat()
        self._overlapped = _StepStat()
        #: High-water mark (perf_counter) of device time already
        #: attributed to some chunk — the serial-attribution state that
        #: keeps ``step_device_ms`` truthful under the async pipeline:
        #: a chunk's device span is only credited where it extends past
        #: what earlier chunks were already charged for; the rest is
        #: ``overlapped_ms`` (see ``timed_fetch``).
        self._accounted_until = 0.0
        self._tokens_total = 0
        self._tok_window: deque = deque()   # (ts, n_tokens)
        # Model identity for the MFU estimator (executor fills these).
        self.n_params = 0
        self.device_kind = ""
        self.platform = ""
        self.device_count = 0
        self.quant = ""
        self.n_chips = 1
        #: Stacked parameter leaves the executor laid transposed on the
        #: device when it took the tree, and their bytes
        #: (``engine/executor.lay_params``); zeros where it laid none.
        self.relaid: Dict[str, int] = {"leaves": 0, "bytes": 0}
        self.rtt_ms: Optional[float] = None
        # Compile/export-cache surface (executor warmup fills these).
        self._compile: Dict[str, Dict[str, Any]] = {}
        self._cache_hits = 0
        self._cache_misses = 0
        self._warmup_done = 0
        self._warmup_total = 0
        self.warmup_s: Optional[float] = None
        #: Callback returning the HBM snapshot dict (engine registers
        #: it; see InferenceEngine._hbm_snapshot).
        self._hbm_provider: Optional[Callable[[], Dict]] = None
        #: Cached labeled histogram children: ``.labels()`` revalidates
        #: on every call (~3 µs × 3 families) — cached, observing the
        #: whole backlog at scrape time stays cheap.
        self._step_hists: Optional[tuple] = None
        #: Step observations awaiting histogram observe — drained by
        #: ``flush`` at scrape time, the same deferred-observation
        #: design as the recorder's stage histograms: prometheus costs
        #: stay off the decode hot path entirely (the <3 % budget).
        #: Bounded; under scrape outage the newest observations win.
        self._pending_steps: deque = deque(maxlen=8192)

    # -- wiring ---------------------------------------------------------------

    def configure_model(self, *, n_params: int = 0, device_kind: str = "",
                        platform: str = "", device_count: int = 0,
                        quant: str = "", n_chips: int = 1,
                        relaid: Optional[Dict[str, int]] = None) -> None:
        self.n_params = int(n_params)
        self.relaid = dict(relaid or {"leaves": 0, "bytes": 0})
        self.device_kind = device_kind
        self.platform = platform
        self.device_count = int(device_count)
        self.quant = quant
        self.n_chips = max(1, int(n_chips))

    def set_hbm_provider(self, fn: Optional[Callable[[], Dict]]) -> None:
        self._hbm_provider = fn

    def set_rtt(self, rtt_ms: float) -> None:
        self.rtt_ms = float(rtt_ms)
        if self.metrics_enabled:
            self._metrics().host_device_rtt_ms.labels(self.name).set(
                self.rtt_ms)

    @staticmethod
    def _metrics():
        from llmq_tpu.metrics.registry import get_metrics
        return get_metrics()

    # -- step decomposition (hot path) ----------------------------------------

    def note_step(self, dispatch_s: float, device_s: float,
                  readback_s: float, tokens: int,
                  overlapped_s: float = 0.0) -> None:
        """One decode/mixed chunk's timing split. Called once per chunk
        from the engine thread — budgeted at <3 % of the echo step path
        (guarded in tests). ``overlapped_s`` is the part of the chunk's
        device span that overlapped other accounted work (pipelined
        decode) — kept OUT of ``step_device_ms`` so summed device time
        never exceeds wall-clock."""
        d_ms = dispatch_s * 1e3
        x_ms = device_s * 1e3
        r_ms = readback_s * 1e3
        o_ms = overlapped_s * 1e3
        now = time.time()
        with self._mu:
            self._dispatch.add(d_ms)
            self._device.add(x_ms)
            self._readback.add(r_ms)
            self._overlapped.add(o_ms)
            if tokens > 0:
                self._tokens_total += tokens
                self._tok_window.append((now, tokens))
            # Prune opportunistically so the deque stays bounded even
            # if nothing ever flushes.
            horizon = now - self.RATE_WINDOW_S
            while self._tok_window and self._tok_window[0][0] < horizon:
                self._tok_window.popleft()
        if self.metrics_enabled:
            self._pending_steps.append((d_ms, x_ms, r_ms, o_ms))

    def timed_fetch(self, handle, dispatched_at: Optional[float] = None):
        """Fetch a chunk handle's tokens with the device-execute /
        readback split: ``block_until_ready`` on the output array
        bounds device execution, the ``fetch()`` that follows is the
        host transfer (``np.asarray``/``device_get`` is the completion
        fence either way, so readback absorbs any under-wait). Returns ``(result, device_s, readback_s,
        overlapped_s)``.

        Overlap attribution (ISSUE 10): the serial measurement model —
        "the wait IS the device time" — double-counts once chunks
        overlap: with two chunks in flight, chunk N+1's wait would
        include (or hide) time already attributed to chunk N. With
        ``dispatched_at`` (perf_counter at dispatch), the chunk's
        device span is ``[dispatched_at, ready]``; only the part past
        the high-water mark of already-attributed time is NOVEL and
        charged to ``device_s`` (further capped by the measured wait,
        so post-ready idle between fetches is never billed as device
        time); the remainder of the span is returned as
        ``overlapped_s`` — the wall-clock the pipeline actually hid.
        Without ``dispatched_at`` the accounting degenerates to the old
        serial split exactly (device_s = wait, overlapped_s = 0)."""
        t0 = time.perf_counter()
        out = getattr(handle, "out", None)
        if out is not None:
            ready = getattr(out, "block_until_ready", None)
            if ready is not None:
                try:
                    ready()
                except Exception:  # noqa: BLE001 — split is best-effort
                    pass
        t1 = time.perf_counter()
        res = handle.fetch()
        t2 = time.perf_counter()
        wait_s = t1 - t0
        span_start = dispatched_at if dispatched_at else t0
        with self._mu:
            acc = self._accounted_until
            span = max(0.0, t1 - span_start)
            novel = max(0.0, t1 - max(span_start, acc))
            device_s = min(novel, wait_s)
            overlapped_s = max(0.0, span - device_s)
            if t1 > acc:
                self._accounted_until = t1
        return res, device_s, t2 - t1, overlapped_s

    # -- decode rate / MFU ----------------------------------------------------

    def tokens_per_s(self) -> float:
        """Decode rate over the trailing window (0 when idle)."""
        now = time.time()
        horizon = now - self.RATE_WINDOW_S
        with self._mu:
            while self._tok_window and self._tok_window[0][0] < horizon:
                self._tok_window.popleft()
            if not self._tok_window:
                return 0.0
            total = sum(n for _, n in self._tok_window)
            span = now - self._tok_window[0][0]
        if span < 0.05:
            span = 0.05   # burst floor: avoid a div-by-~0 rate spike
        return total / span

    def mfu(self, rate: Optional[float] = None) -> Optional[float]:
        """Live decode MFU as a fraction, or None on a device the
        peaks table does not list (CPU tests, echo): no figure beats
        one computed against another chip's peak."""
        if rate is None:
            rate = self.tokens_per_s()
        try:
            return decode_mfu(rate, self.n_params, self.device_kind,
                              self.quant, self.n_chips)
        except UnknownDeviceError:
            return None

    def _overlap_ratio_locked(self) -> float:
        """Single implementation of overlapped/(overlapped+device) —
        the /metrics gauge and the stats snapshot must never drift
        apart. Caller holds ``self._mu``."""
        o = self._overlapped.total_ms
        d = self._device.total_ms
        return o / (o + d) if (o + d) > 0 else 0.0

    def overlap_ratio(self) -> float:
        """Fraction of total in-flight device-span time that overlapped
        other accounted work — 0 on a fully serial engine, ~0.5 with a
        saturated depth-2 pipeline. The ``pipeline_overlap_ratio``
        gauge and the bench's ``point["pipeline"]`` read this."""
        with self._mu:
            return self._overlap_ratio_locked()

    # -- compile / warmup -----------------------------------------------------

    def note_compile(self, program: str, seconds: float,
                     cache_hit: bool,
                     routes: Optional[Dict[str, str]] = None,
                     xla_cache: Optional[str] = None,
                     executable_bytes: Optional[int] = None) -> None:
        """One program's warmup compile (or export-cache load).
        ``program`` is a compiled-program name (decode_chunk,
        mixed_chunk, prefill_b<N>…) — a config-bounded label set.
        ``cache_hit``: the EXPORT artifact existed (no tracing, no
        lowering). ``xla_cache``: what XLA's persistent cache answered
        for the executable itself (``_XlaCacheLookups.outcome``) — the
        two miss apart: a cache that evicted the entry leaves the
        artifact and compiles again. ``executable_bytes``: the
        serialized executable, what a cache entry holds before
        compression; ``None`` unless XLA's cache served it (a fresh
        compilation is not sized). ``routes``: which implementation each attention
        op took (ops/attention.kernel_routes)."""
        with self._mu:
            self._compile[program] = {
                "seconds": round(seconds, 3),
                "source": "export_cache" if cache_hit else "compiled",
                "xla_cache": xla_cache,
                "executable_bytes": executable_bytes,
                "routes": dict(routes or {}),
            }
            if cache_hit:
                self._cache_hits += 1
            else:
                self._cache_misses += 1
        if self.metrics_enabled:
            m = self._metrics()
            if cache_hit:
                m.compile_cache_hits.labels(self.name).inc()
            else:
                m.compile_cache_misses.labels(self.name).inc()
            m.compile_seconds.labels(self.name, program).observe(seconds)

    def note_warmup(self, done: int, total: int) -> None:
        with self._mu:
            self._warmup_done = done
            self._warmup_total = total
        if self.metrics_enabled and total > 0:
            self._metrics().warmup_progress.labels(self.name).set(
                done / total)

    def note_warmup_complete(self, seconds: float) -> None:
        self.warmup_s = round(seconds, 2)
        with self._mu:
            if self._warmup_total == 0:
                self._warmup_total = self._warmup_done = 1
            else:
                self._warmup_done = self._warmup_total
        if self.metrics_enabled:
            self._metrics().warmup_progress.labels(self.name).set(1.0)

    # -- scrape-time flush / snapshot -----------------------------------------

    def flush(self) -> None:
        """Drain the pending step observations into the histograms and
        set the live gauges (rate, MFU, HBM) — called from the /metrics
        scrape path, keeping all prometheus costs off the decode hot
        path (same design as recorder.flush_metrics)."""
        if not self.metrics_enabled:
            return
        m = self._metrics()
        hists = self._step_hists
        if hists is None:
            hists = (m.step_dispatch_ms.labels(self.name),
                     m.step_device_ms.labels(self.name),
                     m.step_readback_ms.labels(self.name),
                     m.step_overlapped_ms.labels(self.name))
            self._step_hists = hists
        while True:
            try:
                d_ms, x_ms, r_ms, o_ms = self._pending_steps.popleft()
            except IndexError:
                break
            hists[0].observe(d_ms)
            hists[1].observe(x_ms)
            hists[2].observe(r_ms)
            hists[3].observe(o_ms)
        m.pipeline_overlap_ratio.labels(self.name).set(
            self.overlap_ratio())
        rate = self.tokens_per_s()
        m.decode_tokens_per_s.labels(self.name).set(rate)
        mfu = self.mfu(rate)
        if mfu is not None:
            m.mfu_pct.labels(self.name).set(mfu * 100.0)
        hbm = self._hbm()
        if hbm is None:
            return
        m.kv_pool_occupancy.labels(self.name).set(
            hbm.get("kv_pool_occupancy", 0.0))
        m.kv_pool_fragmentation.labels(self.name).set(
            hbm.get("kv_pool_fragmentation", 0.0))
        for chip in hbm.get("chips", ()):
            cid = str(chip.get("chip", "0"))
            m.hbm_weights_bytes.labels(self.name, cid).set(
                chip.get("weights_bytes", 0))
            m.hbm_kv_pool_bytes.labels(self.name, cid).set(
                chip.get("kv_pool_bytes", 0))
            if chip.get("free_bytes") is not None:
                m.hbm_free_bytes.labels(self.name, cid).set(
                    chip["free_bytes"])
            if chip.get("limit_bytes") is not None:
                m.hbm_limit_bytes.labels(self.name, cid).set(
                    chip["limit_bytes"])

    def _hbm(self) -> Optional[Dict]:
        if self._hbm_provider is None:
            return None
        try:
            return self._hbm_provider()
        except Exception:  # noqa: BLE001 — telemetry must not fail scrapes
            log.exception("hbm provider failed for %s", self.name)
            return None

    def snapshot(self) -> Dict[str, Any]:
        """The ``device`` block of ``GET /api/v1/engine/stats`` — and
        what bench attaches per rate point."""
        rate = self.tokens_per_s()
        mfu = self.mfu(rate)
        with self._mu:
            out: Dict[str, Any] = {
                "steps": {
                    "count": self._dispatch.count,
                    "dispatch_ms": self._dispatch.to_dict(),
                    "device_ms": self._device.to_dict(),
                    "readback_ms": self._readback.to_dict(),
                    "overlapped_ms": self._overlapped.to_dict(),
                },
                "pipeline_overlap_ratio": round(
                    self._overlap_ratio_locked(), 4),
                "tokens_total": self._tokens_total,
                "decode_tokens_per_s": round(rate, 1),
                "mfu_pct": (round(mfu * 100.0, 3)
                            if mfu is not None else None),
                "model": {
                    "n_params": self.n_params,
                    "platform": self.platform,
                    "device_kind": self.device_kind,
                    "device_count": self.device_count,
                    "quant": self.quant or "bf16",
                    "n_chips": self.n_chips,
                },
                "relaid": dict(self.relaid),
                "host_device_rtt_ms": (round(self.rtt_ms, 2)
                                       if self.rtt_ms is not None
                                       else None),
                "compile": {
                    # Process-wide, warm-up or not (see _BackendCompiles).
                    "backend_compiles": BACKEND_COMPILES.count,
                    "programs": dict(self._compile),
                    "cache_hits": self._cache_hits,
                    "cache_misses": self._cache_misses,
                    "warmup_done": self._warmup_done,
                    "warmup_total": self._warmup_total,
                    "warmup_s": self.warmup_s,
                },
            }
        hbm = self._hbm()
        if hbm is not None:
            out["hbm"] = hbm
        return out


# -- process registry ----------------------------------------------------------

_TELEMETRY_LOCK = threading.Lock()
_TELEMETRY: Dict[str, DeviceTelemetry] = {}


def get_device_telemetry(name: str = "engine0",
                         metrics: Optional[bool] = None) -> DeviceTelemetry:
    """Per-engine-name singleton (the engine, its executor and the
    bench all address the same instance). ``metrics`` updates the
    prometheus on/off flag when given."""
    with _TELEMETRY_LOCK:
        t = _TELEMETRY.get(name)
        if t is None:
            t = DeviceTelemetry(name, metrics=metrics
                                if metrics is not None else True)
            _TELEMETRY[name] = t
        elif metrics is not None:
            t.metrics_enabled = metrics
        return t


def held_accelerator() -> str:
    """Platform of the accelerator an engine in THIS process was built
    on ("" when none: echo engines, CPU JAX). A chip belongs to one
    process at a time, so launchers ask before spawning a child that
    would need it. Reads the registry only — never initializes JAX."""
    with _TELEMETRY_LOCK:
        for t in _TELEMETRY.values():
            if t.platform and t.platform != "cpu":
                return t.platform
    return ""


def flush_all() -> None:
    """Refresh every engine's live gauges — called from the /metrics
    exposition path."""
    with _TELEMETRY_LOCK:
        ts = list(_TELEMETRY.values())
    for t in ts:
        t.flush()


def reset_telemetry() -> None:
    """Drop all instances (tests only — prometheus families persist)."""
    with _TELEMETRY_LOCK:
        _TELEMETRY.clear()


# -- on-demand profiling (single-flight) ---------------------------------------


class ProfileInProgress(RuntimeError):
    """A jax.profiler capture is already running — concurrent captures
    would corrupt each other's sessions (the profiler is a process-wide
    singleton), so the API answers 409."""


_PROFILE_LOCK = threading.Lock()
_PROFILE_ACTIVE: Optional[Dict[str, Any]] = None
_PROFILE_LAST: Optional[Dict[str, Any]] = None

MAX_PROFILE_S = 60.0


def start_profile(*, duration_s: float = 1.0, label: str = "ondemand",
                  base_dir: Optional[str] = None) -> Dict[str, Any]:
    """Kick off a BOUNDED background ``jax.profiler`` capture through
    :func:`utils.profiling.trace` and return its descriptor
    immediately. Raises :class:`ProfileInProgress` when a capture is
    already live (the endpoint's 409). The capture is clamped to
    ``MAX_PROFILE_S`` — an unbounded trace would fill the disk on a
    busy replica."""
    global _PROFILE_ACTIVE
    duration_s = min(max(float(duration_s), 0.01), MAX_PROFILE_S)
    with _PROFILE_LOCK:
        if _PROFILE_ACTIVE is not None:
            raise ProfileInProgress(
                f"profile capture already running "
                f"(started {_PROFILE_ACTIVE['started']:.0f}, "
                f"path {_PROFILE_ACTIVE['path']})")
        out_dir = base_dir or tempfile.mkdtemp(prefix="llmq-profile-")
        info = {
            "label": label,
            "path": os.path.join(out_dir, label),
            "duration_s": duration_s,
            "started": time.time(),
        }
        _PROFILE_ACTIVE = info

    def run() -> None:
        global _PROFILE_ACTIVE, _PROFILE_LAST
        from llmq_tpu.utils.profiling import trace
        try:
            with trace(label, dir=out_dir):
                time.sleep(duration_s)
        except Exception:  # noqa: BLE001 — a failed capture must not wedge
            log.exception("profile capture failed (%s)", info["path"])
        finally:
            with _PROFILE_LOCK:
                _PROFILE_LAST = dict(info)
                _PROFILE_LAST["finished"] = time.time()
                _PROFILE_ACTIVE = None

    threading.Thread(target=run, name="llmq-profile", daemon=True).start()
    return dict(info)


def profile_status() -> Dict[str, Any]:
    """Current capture state for the admin route: the active capture
    descriptor (if any) plus the last finished one."""
    with _PROFILE_LOCK:
        return {
            "active": _PROFILE_ACTIVE is not None,
            "capture": dict(_PROFILE_ACTIVE) if _PROFILE_ACTIVE else None,
            "last": dict(_PROFILE_LAST) if _PROFILE_LAST else None,
        }


__all__: List[str] = [
    "DeviceTelemetry", "ProfileInProgress", "UnknownDeviceError",
    "decode_mfu", "describe_device", "device_identity", "flush_all",
    "get_device_telemetry", "held_accelerator", "measure_rtt",
    "peak_flops",
    "profile_status", "reset_telemetry", "start_profile",
]
