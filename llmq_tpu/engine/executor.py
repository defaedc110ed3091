"""Execution backends for the continuous-batching engine.

The engine (engine.py) owns scheduling — slots, admission, preemption,
page tables; an :class:`Executor` owns compute — prefill a prompt's KV and
produce the first token, then advance every active slot one token per
decode step. Two backends:

- :class:`EchoExecutor` — deterministic, JAX-free: "generates" the prompt
  back. BASELINE config #1's mock LLM endpoint, and the queue-plane
  benchmark backend (replaces the reference's simulated per-tier sleep,
  cmd/queue-manager/main.go:139-153, with actual instant compute).
- :class:`JaxExecutor` — the TPU path (BASELINE configs #2/#3/#5): paged
  KV pool in device memory, bucketed prefill (one compile per bucket),
  one fixed-geometry jitted decode program for the whole batch with the
  KV pool **donated** so XLA updates it in place instead of copying the
  pool every step, and in-jit sampling so only token ids cross back to
  the host.

Decode runs **multiple steps per host round-trip** (``decode_chunk``): a
``lax.scan`` over K inner steps keeps sampling on device, latches EOS
(finished rows stop advancing and scatter their KV to reserved page 0),
and honors a per-sequence token ``budget`` — so one host↔device transfer
returns up to K tokens per sequence. Host↔device latency is amortized
K× instead of being paid per token;
the engine's scheduling granularity (admission/preemption) becomes K
tokens, which bounds realtime admission latency to K decode steps.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Protocol, Tuple

import numpy as np

from llmq_tpu.utils.logging import get_logger
from llmq_tpu.utils.profiling import SpanRecorder, scope

log = get_logger("executor")


class HostStaging:
    """Preallocated, ring-rotated host staging buffers per (tag,
    geometry) — the dispatch paths' ``np.zeros``/``np.asarray(...).copy``
    churn killer (ISSUE 10 satellite, measured via the PR 6
    ``step_dispatch_ms`` gauge): a dispatch takes a buffer, fills it and
    hands it straight to ``jnp.asarray``/the program, instead of
    allocating (and page-faulting) a fresh array per chunk.

    Buffers ROTATE through a small ring rather than being reused
    immediately: ``jax.device_put`` may alias aligned host memory
    (zero-copy on the CPU backend), so a buffer must not be rewritten
    while the dispatch that used it can still read it. The engine
    bounds in-flight chunks at ``async_pipeline.depth`` (≤ 4) and
    prefill waves at one dispatch per slot, so a ring sized past those
    bounds guarantees the slot being rewritten belongs to a dispatch
    that has long been consumed.

    Single-writer by design: only the engine's scheduling thread takes
    buffers (same discipline as the executor call sites themselves)."""

    def __init__(self, ring: int = 8) -> None:
        self._ring = max(2, int(ring))
        self._bufs: Dict[Tuple, List[np.ndarray]] = {}
        self._idx: Dict[Tuple, int] = {}
        self._aranges: Dict[int, np.ndarray] = {}

    def take(self, tag: str, shape, dtype,
             fill: Optional[int] = 0) -> np.ndarray:
        """Next ring buffer for ``(tag, shape, dtype)``, pre-filled with
        ``fill`` (None skips the memset — caller overwrites fully)."""
        key = (tag, tuple(shape) if hasattr(shape, "__len__") else (shape,),
               np.dtype(dtype))
        ring = self._bufs.get(key)
        if ring is None:
            ring = [np.empty(key[1], key[2]) for _ in range(self._ring)]
            self._bufs[key] = ring
            self._idx[key] = 0
        i = self._idx[key]
        self._idx[key] = (i + 1) % self._ring
        buf = ring[i]
        if fill is not None:
            buf.fill(fill)
        return buf

    def arange(self, n: int) -> np.ndarray:
        """Cached read-only ``np.arange(n, int32)`` template (prefill
        position vectors are ``arange + start`` — no reason to rebuild
        the ramp per dispatch)."""
        a = self._aranges.get(n)
        if a is None:
            a = np.arange(n, dtype=np.int32)
            a.setflags(write=False)
            self._aranges[n] = a
        return a


@dataclass(frozen=True)
class ExecutorSpec:
    """Geometry the engine schedules against."""

    batch_size: int          # decode slots
    page_size: int           # tokens per KV page
    num_pages: int           # total pool pages (page 0 reserved)
    max_pages_per_seq: int   # block-table width
    eos_id: int


class Executor(Protocol):
    spec: ExecutorSpec
    #: Tokens produced per decode_chunk call (1 → engine single-steps).
    chunk_size: int

    def prefill(self, tokens: List[int], start_pos: int,
                block_table: np.ndarray, temperature: float,
                slot: int) -> int:
        """Write ``tokens``' KV at absolute positions
        ``[start_pos, start_pos+len)`` through ``block_table`` and return
        the first sampled next token."""
        ...

    def decode(self, tokens: np.ndarray, positions: np.ndarray,
               block_tables: np.ndarray,
               temperatures: np.ndarray) -> np.ndarray:
        """One batched decode step. All arrays are full batch-size; the
        engine ignores outputs of inactive slots (their rows point at
        page 0). Returns (B,) next tokens."""
        ...

    def decode_chunk(self, tokens: np.ndarray, positions: np.ndarray,
                     block_tables: np.ndarray, temperatures: np.ndarray,
                     budgets: np.ndarray) -> np.ndarray:
        """Up to ``chunk_size`` decode steps in one device program.

        Per-row semantics, identical to ``chunk_size`` single ``decode``
        calls: step j writes the KV of the current token at the current
        position, samples the next. A row stops (latches) when it samples
        EOS or exhausts its ``budgets[b]`` steps; latched rows emit EOS
        and write KV to reserved page 0. Rows with budget 0 never run.
        Returns (B, chunk_size) next tokens."""
        ...

    def release_slot(self, slot: int) -> None:
        """Slot freed by the engine (sequence finished or preempted)."""
        ...

    def resume(self, slot: int, tokens: List[int], start_pos: int) -> None:
        """A previously-prefilled sequence re-enters ``slot`` after a
        slot-only preemption (its KV pages are intact, no re-prefill).
        ``tokens``/``start_pos`` are what its prefill saw. Stateless
        backends ignore this; per-slot-state backends re-register."""
        ...


# -- echo ----------------------------------------------------------------------


class _EchoOutProbe:
    """Stands in for the device output array on the echo async path so
    ``DeviceTelemetry.timed_fetch`` can time the simulated device
    execution: ``block_until_ready`` waits for the device-queue thread
    to run the program (no ``copy_to_host_async`` on purpose — the
    engine's ``_prefetch`` treats its absence as a no-op)."""

    __slots__ = ("_ev",)

    def __init__(self, ev: threading.Event) -> None:
        self._ev = ev

    def block_until_ready(self) -> None:
        self._ev.wait()


class _EchoFirstToken:
    """``pf_first[i]`` of an in-flight echo MIXED chunk as a lane
    override's "device scalar": read only when the joining chunk's
    program runs, by which time the FIFO device queue has run the
    mixed chunk and set it."""

    __slots__ = ("_handle", "_i")

    def __init__(self, handle: "EchoChunkHandle", i: int) -> None:
        self._handle = handle
        self._i = i

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._handle.pf_first[self._i], dtype)


class EchoChunkHandle:
    """In-flight echo chunk (``async_chunks`` mode): results materialize
    when the executor's device-queue thread runs the program. Carry
    surface mirrors :class:`ChunkHandle` — ``_tok``/``_pos``/``_done``
    are read by the NEXT chained program's closure, which is safe
    because the device queue is FIFO: by the time program N+1 runs,
    program N has completed and set them."""

    __slots__ = ("out", "_ev", "_out", "_tok", "_pos", "_done",
                 "pf_first", "_err", "_mixed")

    def __init__(self, mixed: bool = False) -> None:
        self._ev = threading.Event()
        self.out = _EchoOutProbe(self._ev)
        self._out = None
        self._tok = None
        self._pos = None
        self._done = None
        self.pf_first = None
        self._err: Optional[BaseException] = None
        self._mixed = mixed

    def _set(self, out, tok, pos, done, pf_first=None) -> None:
        self._out, self._tok, self._pos, self._done = out, tok, pos, done
        self.pf_first = pf_first
        self._ev.set()

    def _fail(self, err: BaseException) -> None:
        self._err = err
        self._ev.set()

    def pf_first_at(self, i: int) -> _EchoFirstToken:
        """Slice ``i``'s sampled first token as a lane override's
        scalar (parity with :meth:`MixedChunkHandle.pf_first_at`)."""
        return _EchoFirstToken(self, i)

    def fetch(self):
        self._ev.wait()
        if self._err is not None:
            raise self._err
        if self._mixed:
            return self._out, self.pf_first
        return self._out


class EchoExecutor:
    """Echoes the prompt: token i of the response is prompt token i; after
    the full prompt, EOS. No device, no KV reads — but the engine still
    drives the full slot/page machinery against it."""

    #: Tiered-KV contract (docs/tiering.md): this backend's "KV" has no
    #: content — a sequence's state is fully determined by the token
    #: stream the engine (re-)registers at prefill. The tiering plane
    #: may therefore demote/promote conversations as METADATA-ONLY
    #: entries (no payload extraction) with exact correctness.
    kv_content_free = True

    def __init__(self, batch_size: int = 8, page_size: int = 16,
                 num_pages: int = 512, max_pages_per_seq: int = 32,
                 eos_id: int = 2, chunk_size: int = 1,
                 mixed_prefill_slices: int = 2,
                 mixed_slice_tokens: int = 64,
                 async_chunks: bool = False,
                 step_delay_s: float = 0.0,
                 prefill_delay_per_token_s: float = 0.0) -> None:
        self.spec = ExecutorSpec(batch_size, page_size, num_pages,
                                 max_pages_per_seq, eos_id)
        self.chunk_size = chunk_size
        #: Mixed-batch geometry (engine packing limits; the echo backend
        #: has no compiled program, so these are just caps).
        self.mixed_prefill_slices = max(0, mixed_prefill_slices)
        self.mixed_slice_tokens = max(0, mixed_slice_tokens)
        self._slot_prompt: Dict[int, List[int]] = {}
        self._slot_end: Dict[int, int] = {}   # absolute pos after prompt
        self._mu = threading.Lock()
        #: Async-pipeline mode (docs/performance.md "Async pipeline"):
        #: chunks dispatch to a FIFO "device queue" thread and return
        #: futures (EchoChunkHandle) — the same surface JaxExecutor's
        #: decode_chunk_start gives the engine, so the pipelined engine
        #: path runs (and is tested) without a device. Disabled, the
        #: start entrypoints are hidden (None) and the executor is
        #: byte-identical to the pre-pipeline synchronous one.
        self._async_chunks = bool(async_chunks)
        #: Simulated per-chunk device latency: 0 keeps the queue-plane
        #: benches instant; the overlap smoke sets a couple of ms so
        #: pipeline_overlap_ratio is deterministic, not a thread race.
        self._step_delay_s = max(0.0, float(step_delay_s))
        #: Simulated prefill compute, proportional to tokens registered
        #: (a real device's prefill scales with prompt length; the echo
        #: backend's is otherwise free). 0 by default; the disagg bench
        #: sets it so long-prompt prefill trains cost wall-clock on
        #: whichever replica runs them.
        self._prefill_delay_per_token_s = max(
            0.0, float(prefill_delay_per_token_s))
        self._devq: Optional[queue.Queue] = None
        self._dev_thread: Optional[threading.Thread] = None
        if not self._async_chunks:
            # Hide the futures API: the engine feature-detects
            # decode_chunk_start/mixed_chunk_start with getattr — a
            # None instance attribute keeps it on the sync path.
            self.decode_chunk_start = None    # type: ignore[assignment]
            self.mixed_chunk_start = None     # type: ignore[assignment]

    #: ``ops/rows.ROW_TILE`` (this backend imports no JAX, and ``ops``
    #: does; tests/test_profiling.py holds the two equal).
    ROW_TILE = 256

    def slice_tokens(self, entry: str, tokens: int = 0, rows: int = 1) -> int:
        """Parity with :meth:`JaxExecutor.slice_tokens`: a mixed chunk
        counts the row tiles its ``tokens`` fill, laid tight behind
        the decode rows that lead them, less those rows (a Llama
        program's rule, ``ops/rows.tile_rows``); a prefill pads nothing
        here."""
        if entry == "mixed_chunk":
            tile = min(self.mixed_slice_tokens, self.ROW_TILE)
            total = self.mixed_prefill_slices * self.mixed_slice_tokens
            lead = self.spec.batch_size
            if total <= 2 * tile:          # ``ops/rows.worth_a_loop``
                return total
            return min(-(-(lead + tokens) // tile) * tile,
                       lead + total) - lead
        return tokens * rows if entry.startswith("prefill") else 0

    def _register_prefill(self, slot: int, tokens: List[int],
                          start_pos: int) -> List[int]:
        """Register a prefill chunk for ``slot`` and return the slot's
        ACCUMULATED prefill stream. A chunk contiguous with what the
        slot already holds EXTENDS it (budgeted mixed-batch slices, or
        a prefill finished across paths); anything else replaces —
        a fresh admission or a resume re-registration."""
        cur_end = self._slot_end.get(slot)
        if cur_end is not None and cur_end == start_pos:
            self._slot_prompt[slot].extend(tokens)
        else:
            self._slot_prompt[slot] = list(tokens)
        self._slot_end[slot] = start_pos + len(tokens)
        return self._slot_prompt[slot]

    def prefill(self, tokens: List[int], start_pos: int,
                block_table: np.ndarray, temperature: float,
                slot: int) -> int:
        if self._prefill_delay_per_token_s:
            time.sleep(len(tokens) * self._prefill_delay_per_token_s)
        with self._mu:
            stream = self._register_prefill(slot, list(tokens), start_pos)
        return stream[0] if stream else self.spec.eos_id

    def decode(self, tokens: np.ndarray, positions: np.ndarray,
               block_tables: np.ndarray,
               temperatures: np.ndarray) -> np.ndarray:
        out = np.full(self.spec.batch_size, self.spec.eos_id, np.int32)
        with self._mu:
            for slot, prompt in self._slot_prompt.items():
                # positions[slot] is the absolute position of the last
                # emitted token; k is its index in the echo stream.
                k = int(positions[slot]) - self._slot_end[slot]
                nxt = k + 1
                if 0 <= nxt < len(prompt):
                    out[slot] = prompt[nxt]
        return out

    def decode_chunk(self, tokens: np.ndarray, positions: np.ndarray,
                     block_tables: np.ndarray, temperatures: np.ndarray,
                     budgets: np.ndarray) -> np.ndarray:
        if self._step_delay_s:
            # Simulated device latency applies to the SYNC path too, so
            # a pipelined-vs-synchronous A/B (the CI overlap smoke)
            # compares against the same simulated device. 0 by default
            # — the queue-plane benches stay instant.
            time.sleep(self._step_delay_s)
        K = self.chunk_size
        B = self.spec.batch_size
        out = np.full((B, K), self.spec.eos_id, np.int32)
        tok = np.asarray(tokens, np.int32).copy()
        pos = np.asarray(positions, np.int32).copy()
        done = np.asarray(budgets, np.int32) <= 0
        for j in range(K):
            active = ~done
            nxt = self.decode(tok, pos, block_tables, temperatures)
            nxt = np.where(active, nxt, self.spec.eos_id).astype(np.int32)
            out[:, j] = nxt
            pos = pos + active.astype(np.int32)
            done = done | (nxt == self.spec.eos_id) | (j + 1 >= budgets)
            tok = nxt
        return out

    def mixed_chunk(self, tokens: np.ndarray, positions: np.ndarray,
                    block_tables: np.ndarray, temperatures: np.ndarray,
                    budgets: np.ndarray, pf) -> tuple:
        """Mixed-batch parity with the JAX ``_mixed_chunk`` program, so
        the engine's budgeted scheduling path runs in CPU/queue-plane
        tests and benches. ``pf``: one ``(slot, tokens, start_pos,
        block_table, temperature)`` tuple per prefill slice (the block
        table is unused here). Slice KV "writes" happen before the
        decode steps, mirroring the fused program; returns
        ``(out (B, K), pf_first (S,))`` where ``pf_first[i]`` is the
        sampled next token as of slice i's end — meaningful to the
        engine only for a sequence's FINAL slice."""
        pf_first = np.full(len(pf), self.spec.eos_id, np.int32)
        if self._prefill_delay_per_token_s:
            # The fused step pays for its slice tokens: a step carrying
            # a long prefill train is slower for every co-resident
            # decode row, exactly the continuous-batching interference.
            time.sleep(sum(len(toks) for _s, toks, _p, _bt, _t in pf)
                       * self._prefill_delay_per_token_s)
        with self._mu:
            for i, (slot, toks, start_pos, _bt, _temp) in enumerate(pf):
                stream = self._register_prefill(slot, list(toks),
                                                start_pos)
                if stream:
                    pf_first[i] = stream[0]
        out = self.decode_chunk(tokens, positions, block_tables,
                                temperatures, budgets)
        return out, pf_first

    # -- async futures API (docs/performance.md "Async pipeline") ------------

    def _device_submit(self, fn, mixed: bool = False) -> "EchoChunkHandle":
        """Enqueue one simulated device program. The single FIFO worker
        thread mirrors a real accelerator's in-order execution stream —
        chained carries read the PREVIOUS handle's end state, which FIFO
        order guarantees is set by then."""
        if self._devq is None:
            self._devq = queue.Queue()
            self._dev_thread = threading.Thread(
                target=self._device_loop, args=(self._devq,),
                name="echo-device", daemon=True)
            self._dev_thread.start()
        h = EchoChunkHandle(mixed=mixed)
        self._devq.put((fn, h))
        return h

    def _device_loop(self, q: queue.Queue) -> None:
        # The queue rides in as an argument (not re-read from self):
        # close() nulls the attribute before posting the shutdown
        # sentinel, and the loop must keep draining ITS queue.
        while True:
            item = q.get()
            if item is None:
                return
            fn, h = item
            try:
                fn(h)
            except BaseException as e:  # noqa: BLE001 — surfaced at fetch
                h._fail(e)

    def close(self) -> None:
        """Stop the simulated device-queue thread (engine.stop() calls
        this through the optional executor-close seam). Lazily
        re-created if the executor dispatches again afterwards."""
        q, self._devq = self._devq, None
        t, self._dev_thread = self._dev_thread, None
        if q is not None:
            q.put(None)
        if t is not None:
            t.join(timeout=5.0)

    def _run_chunk_async(self, tok, pos, frozen, budgets):
        """Chunk body with the JAX program's carry semantics
        (_decode_chunk): ``frozen`` (done_in/EOS) is a PERSISTENT latch
        carried out; budget exhaustion only pauses the row for this
        chunk. The sync ``decode_chunk`` keeps its original
        budget-conflating loop untouched (identical OUT matrix; it
        never carries state), so the off-switch path stays
        byte-identical to the pre-pipeline code."""
        K, B = self.chunk_size, self.spec.batch_size
        eos = self.spec.eos_id
        out = np.full((B, K), eos, np.int32)
        tok = np.asarray(tok, np.int32).copy()
        pos = np.asarray(pos, np.int32).copy()
        frozen = np.asarray(frozen, bool).copy()
        budgets = np.asarray(budgets, np.int32)
        for j in range(K):
            active = (~frozen) & (j < budgets)
            if not active.any():
                break           # the while_loop's early exit
            nxt = self.decode(tok, pos, None, None)
            out[:, j] = np.where(active, nxt, eos).astype(np.int32)
            tok = np.where(active, nxt, tok).astype(np.int32)
            pos = pos + active.astype(np.int32)
            frozen = frozen | (active & (nxt == eos))
        return out, tok, pos, frozen

    def _lane_seed(self, tokens, positions, carry, overrides):
        """Snapshot a chunk's lane inputs at dispatch and return the
        closure the device-queue thread calls for ``(tok, pos, done)``:
        the previous chunk's end state with ``carry`` (read when the
        program RUNS — FIFO order has set it by then), else the host
        arrays with no row latched; ``overrides`` re-seed a lane
        (slot, first-token scalar, pos) for a join. The engine's
        staging buffers may be rewritten before the program runs,
        hence the copies."""
        B = self.spec.batch_size
        toks = (None if tokens is None
                else np.asarray(tokens, np.int32).copy())
        poss = (None if positions is None
                else np.asarray(positions, np.int32).copy())
        ovr = [(int(s), sc, int(p)) for s, sc, p in (overrides or ())]

        def lanes():
            if carry is not None:
                tok, pos, done = carry._tok, carry._pos, carry._done
            else:
                tok, pos = toks, poss
                done = np.zeros(B, bool)
            tok = np.asarray(tok, np.int32).copy()
            pos = np.asarray(pos, np.int32).copy()
            done = np.asarray(done, bool).copy()
            for slot, sc, p in ovr:
                tok[slot] = int(np.asarray(sc))
                pos[slot] = p
                done[slot] = False
            return tok, pos, done

        return lanes

    def decode_chunk_start(self, tokens, positions, block_tables,
                           temperatures, budgets,
                           carry: Optional["EchoChunkHandle"] = None,
                           overrides: Optional[List] = None
                           ) -> "EchoChunkHandle":
        """Futures-returning decode chunk (parity with
        JaxExecutor.decode_chunk_start): dispatch returns immediately;
        with ``carry``, tok/pos/done come from the previous chunk's end
        state; ``overrides`` re-seed a lane (slot, first-token, pos) for
        a same-step join. Inputs are SNAPSHOTTED at dispatch — the
        engine's staging buffers may be rewritten before the program
        runs."""
        buds = np.asarray(budgets, np.int32).copy()
        lanes = self._lane_seed(tokens, positions, carry, overrides)

        def run(h: "EchoChunkHandle") -> None:
            if self._step_delay_s:
                time.sleep(self._step_delay_s)
            h._set(*self._run_chunk_async(*lanes(), buds))

        return self._device_submit(run)

    def mixed_chunk_start(self, tokens, positions, block_tables,
                          temperatures, budgets, pf: List,
                          carry: Optional["EchoChunkHandle"] = None,
                          overrides: Optional[List] = None
                          ) -> "EchoChunkHandle":
        """Futures-returning mixed chunk: slice registration happens on
        the device-queue thread (FIFO — before any later chained
        chunk), mirroring the fused program writing slice KV inside the
        same dispatch. ``carry`` / ``overrides`` as in
        ``decode_chunk_start``."""
        buds = np.asarray(budgets, np.int32).copy()
        lanes = self._lane_seed(tokens, positions, carry, overrides)
        pf_snap = [(int(slot), list(t), int(sp))
                   for slot, t, sp, _bt, _temp in pf]

        def run(h: "EchoChunkHandle") -> None:
            if self._step_delay_s:
                time.sleep(self._step_delay_s)
            if self._prefill_delay_per_token_s:
                time.sleep(sum(len(t) for _s, t, _p in pf_snap)
                           * self._prefill_delay_per_token_s)
            pf_first = np.full(len(pf_snap), self.spec.eos_id, np.int32)
            with self._mu:
                for i, (slot, t, sp) in enumerate(pf_snap):
                    stream = self._register_prefill(slot, t, sp)
                    if stream:
                        pf_first[i] = stream[0]
            out, tok, pos, done = self._run_chunk_async(*lanes(), buds)
            h._set(out, tok, pos, done, pf_first=pf_first)

        return self._device_submit(run, mixed=True)

    def release_slot(self, slot: int) -> None:
        with self._mu:
            self._slot_prompt.pop(slot, None)
            self._slot_end.pop(slot, None)

    def resume(self, slot: int, tokens: List[int], start_pos: int) -> None:
        with self._mu:
            self._slot_prompt[slot] = list(tokens)
            self._slot_end[slot] = start_pos + len(tokens)


# -- JAX ----------------------------------------------------------------------


class ChunkHandle:
    """In-flight decode chunk: ``out`` is the (B, K) token matrix to
    fetch; ``tok``/``pos``/``done`` are the device-resident end state a
    carried next chunk consumes directly (no host round-trip)."""

    __slots__ = ("out", "tok", "pos", "done", "stats")

    def __init__(self, out, tok, pos, done, stats=None) -> None:
        self.out = out
        self.tok = tok
        self.pos = pos
        self.done = done
        #: The family's step counters summed over the chunk (a routed
        #: model's expert loads), on the device until ``fetch`` brings
        #: them over with the tokens; ``None`` for a family without.
        self.stats = stats

    def fetch(self) -> np.ndarray:
        """Blocking host transfer of the chunk's sampled tokens (and,
        in the same ``device_get``, of its counters)."""
        if self.stats is None:
            return np.asarray(self.out)
        import jax

        out, self.stats = jax.device_get((self.out, self.stats))
        return np.asarray(out)


class MixedChunkHandle:
    """In-flight MIXED chunk (decode rows + budgeted prefill slices in
    one program): same carry surface as :class:`ChunkHandle` (tok/pos/
    done are the decode rows' device-resident end state) plus
    ``pf_first`` — the per-slice sampled next tokens the engine commits
    for sequences whose FINAL slice rode this chunk."""

    __slots__ = ("out", "tok", "pos", "done", "pf_first", "stats",
                 "key_blocks")

    def __init__(self, out, tok, pos, done, pf_first, stats=None,
                 key_blocks=None) -> None:
        self.out = out
        self.tok = tok
        self.pos = pos
        self.done = done
        self.pf_first = pf_first
        self.stats = stats     # as ChunkHandle.stats
        #: (visited, the table holds): the key blocks one prefill
        #: attention of this chunk's mixed step runs over its slices,
        #: reckoned on the host (None: the family counts none).
        self.key_blocks = key_blocks

    def pf_first_at(self, i: int):
        """Slice ``i``'s sampled first token, still on the device: the
        scalar of a lane override ``(slot, scalar, pos)`` through which
        a sequence whose FINAL slice rode this chunk joins the next one
        before this one is fetched."""
        return self.pf_first[i]

    def fetch(self) -> tuple:
        """Blocking host transfer: ``(decode tokens (B, K),
        slice first-tokens (S,))`` — ONE batched ``device_get`` for
        both arrays instead of two serial blocking transfers (each
        transfer pays the host↔device round-trip)."""
        import jax

        out, pf, self.stats = jax.device_get(
            (self.out, self.pf_first, self.stats))
        return np.asarray(out), np.asarray(pf)


def _is_quantized_tree(params) -> bool:
    """int8 weights? The attention's output projection at ``layers.wo``
    says (a tree without it stacks its mixers by kind, and has none)."""
    from llmq_tpu.ops.quant import is_quantized
    return is_quantized(params["layers"].get("wo"))


#: The layouts a family may name for a stacked leaf (..., in, out) of
#: its parameters (its ``DEVICE_LAYOUT``, ``models/__init__.py``), as
#: the major-to-minor order of a leaf of ``n`` axes. ``transposed``:
#: ``in`` — the axis a product contracts — minor. ``row_major``:
#: ``out`` minor — what a leaf has wherever the backend does not choose
#: otherwise, which the TPU does for a last axis that is no multiple of
#: its 128 lanes (granite's ``in_proj``, 8,512 wide, lies transposed by
#: default: PERF.md, PR 49).
LAYOUTS: Dict[str, Callable[[int], Tuple[int, ...]]] = {
    "transposed": lambda n: tuple(range(n - 2)) + (n - 1, n - 2),
    "row_major": lambda n: tuple(range(n)),
}


def _order(leaf) -> Optional[Tuple[int, ...]]:
    """The major-to-minor order ``leaf`` (an array on a device, or the
    description of one) says of itself that it lies in; None where it
    says nothing (a description that leaves the layout to the
    backend)."""
    layout = getattr(getattr(leaf, "format", None), "layout", None)
    order = getattr(layout, "major_to_minor", None)
    return None if order is None else tuple(order)


def _lies(leaf, how: str) -> bool:
    """Does ``leaf`` lie as ``LAYOUTS[how]`` has it?"""
    return _order(leaf) == LAYOUTS[how](leaf.ndim)


def lay_params(fam, params) -> Dict[str, int]:
    """Lay the stacked leaves that ``fam`` names a layout for (its
    ``DEVICE_LAYOUT``, ``models/__init__.py``: leaf -> a name of
    :data:`LAYOUTS`) in that layout on the device, ONCE, and say what
    was laid: ``{"leaves", "bytes"}``.

    The PHYSICAL layout alone (``jax.experimental.layout``): a laid leaf
    has the shape, the dtype and the values it had, so the forward
    functions, ``x @ wq`` and every other reader of the tree stay as
    they are, and a program lowered against the laid leaf
    (:func:`describe`) multiplies with it where it lies instead of
    copying the whole stack into that layout at the start of every run.

    The laid leaf takes the original's place IN ``params["layers"]``:
    the tree that was handed in stays whole and means what it meant,
    and the original — 201 MB a leaf at SmolLM2's sizes, 1.26 GB at
    granite's, beside a pool that fills the chip — is freed as soon as
    nobody else holds it, not kept for the life of whoever built the
    tree. A leaf that is not a plain array (int8 with its scales:
    ``ops/quant.is_quantized``), a leaf that already lies so (a tree a
    second executor is built over; a ``row_major`` leaf on a backend
    whose default that is) and a family without the table pass through
    as the objects they are. A DESCRIPTION of a leaf
    (``jax.ShapeDtypeStruct`` with its sharding) is laid as a
    description."""
    import jax
    from jax.experimental.layout import Format, Layout

    laid = {"leaves": 0, "bytes": 0}
    layers = params.get("layers", {}) if isinstance(params, dict) else {}
    for name, how in getattr(fam, "DEVICE_LAYOUT", {}).items():
        leaf = layers.get(name)
        if getattr(leaf, "sharding", None) is None or leaf.ndim < 2:
            continue        # absent, quantized, or on no device
        if not _lies(leaf, how):
            fmt = Format(Layout(major_to_minor=LAYOUTS[how](leaf.ndim)),
                         leaf.sharding)
            if isinstance(leaf, jax.ShapeDtypeStruct):
                leaf = jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                            sharding=fmt)
            else:
                leaf = jax.device_put(leaf, fmt)
            layers[name] = leaf
        laid["leaves"] += 1
        laid["bytes"] += leaf.size * leaf.dtype.itemsize
    return laid


def describe(tree, on=None, layouts=None):
    """``tree``'s leaves as a lowering takes them
    (``jax.ShapeDtypeStruct``): shape, dtype and where each lies — the
    sharding ``on`` or, without one, the leaf's own (mesh path: the AOT
    program must be partitioned exactly like the runtime arrays) and,
    for a leaf :func:`lay_params` laid (``layouts``: the family's
    ``DEVICE_LAYOUT``, leaf name -> layout), its layout: the compiled
    program then takes the leaf as it lies."""
    import jax

    layouts = layouts or {}

    def one(path, x):
        how = layouts.get(getattr(path[-1], "key", None)) if path else None
        where = on or (x.format if how and _lies(x, how)
                       else getattr(x, "sharding", None))
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where)
    return jax.tree_util.tree_map_with_path(one, tree)


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under another ``__name__``: ``jax.jit`` names the XLA
    module it builds ``jit_<__name__>``."""
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return call


class JaxExecutor:
    """Paged continuous-batching executor over a model family of
    ``llmq_tpu/models/`` (the Llama block, the DeepSeek-V3 block).

    Compilation surface is bounded by design: one decode program for the
    fixed (B, max_pages) geometry, and one prefill program per length
    bucket (``prefill_buckets``); prompts longer than the largest bucket
    stream through it in chunks (continuation prefill over the same block
    table). The KV pool is donated through every call, so the working set
    stays at one pool (plus transient activations) in HBM.

    **Sharded serving** (``mesh=``): pass a ``jax.sharding.Mesh`` with a
    ``tp`` axis and the executor serves the model tensor-parallel —
    params sharded per ``parallel/sharding.param_shardings`` (quantized
    trees included), the KV pool sharded on the KV-head axis (each chip
    holds only its heads' cache — how a 70B cache fits a v5e-16,
    BASELINE config #5), and every prefill/decode program jitted under
    GSPMD, which inserts the ICI collectives (one all-reduce after wo /
    w_down, logits all-gather at the head). This is the serving seam the
    reference stubs with fabricated worker URLs
    (/root/reference/internal/scheduler/scheduler.go:299-301). Batch-dim
    arrays stay replicated: data parallelism across requests is engine
    replication (LoadBalancer over engines), not intra-engine sharding.
    The Pallas kernels are single-chip programs, so sharded tracing uses
    the pure-JAX paths GSPMD can partition (cfg.pallas=False).
    """

    def __init__(self, model_cfg, params, *, batch_size: int = 8,
                 page_size: int = 16, num_pages: int = 512,
                 prefill_buckets: Optional[List[int]] = None,
                 top_k: int = 0, top_p: float = 1.0, eos_id: int = 2,
                 cache_dtype=None, seed: int = 0,
                 chunk_size: int = 16, prefill_batch: int = 4,
                 mixed_prefill_slices: int = 2,
                 mixed_slice_tokens: int = 64, row_tail_slots: int = 0,
                 mesh=None, telemetry_name: str = "engine0",
                 telemetry_metrics: Optional[bool] = None) -> None:
        import jax
        import jax.numpy as jnp
        from functools import partial

        from llmq_tpu.models import family_of
        from llmq_tpu.ops.sampling import sample_token

        self._jax = jax
        self._jnp = jnp
        self.mesh = mesh
        #: The model family's module (models/__init__.py): the serving
        #: programs below are built from ITS forward functions and pool,
        #: at ITS config for forward-only programs.
        fam = self._family = family_of(model_cfg)
        model_cfg = fam.serving_config(model_cfg)
        fam.check_serving(
            model_cfg,
            quantization=("int8" if _is_quantized_tree(params) else ""),
            kv_quantization=("int8" if cache_dtype is not None
                             and jnp.dtype(cache_dtype) == jnp.int8
                             else ""),
            mesh=mesh is not None and mesh.size > 1)
        init_kv_pages = fam.init_kv_pages
        #: Counters a forward pass of this family returns after the
        #: cache (a routed model: tokens an expert, experts touched);
        #: 0 for a family that counts nothing. The chunk programs sum
        #: them over their steps and return the sum as their last
        #: output (``None`` at 0: no output, the same program as
        #: before there were any).
        n_stats = fam.step_stats_size(model_cfg)
        #: ... and where each lies (``models/__init__.py``): what the
        #: engine reads a fetched chunk's counters by.
        self.step_stats_layout = fam.step_stats_layout(model_cfg)
        #: ``(seq_lens, T, page_size, max_pages) -> (visited, the table
        #: holds)`` for a family whose prefill attention loops over key
        #: blocks (``models/__init__.py``), else None: what a mixed
        #: chunk's handle carries as ``key_blocks``.
        self._mixed_key_blocks = getattr(fam, "mixed_key_blocks", None)

        #: Row state beside the pages (``models/__init__.py``): leaves
        #: indexed (layer, batch row, ...) for a family that has one —
        #: its programs then carry and donate ``(pages, row state)``
        #: where the others carry the pages, and take the batch rows
        #: of their prompt chunks as one more operand — else ``None``,
        #: and every program is what it was before there was any.
        bind = getattr(fam, "bind_cache", None)
        if bind is not None:
            # A family whose row state is cut in the pool's pages (a
            # window layer's slab) is told the page size and the most
            # tokens one program writes for ONE sequence before it
            # reads: a prefill bucket, or a mixed step's slice (the
            # engine packs one slice a sequence a step:
            # ``engine._pack_prefill_slices``).
            model_cfg = bind(
                model_cfg, page_size=page_size,
                step_tokens=max(max(prefill_buckets or [32, 128, 512]),
                                int(mixed_slice_tokens)))
        #: Parameters that are only DESCRIBED (``jax.ShapeDtypeStruct``
        #: leaves with their sharding, on a device that may itself be
        #: described: ``scripts/whole_copies.py``,
        #: ``tests/test_tpu_compile.py``) get a described pool and row
        #: state beside them: such an executor lowers its programs
        #: (``programs``) as a served one does, holds no buffer and
        #: runs nothing.
        lead = jax.tree.leaves(params)[0]

        def held(make):
            if isinstance(lead, jax.ShapeDtypeStruct):
                return describe(jax.eval_shape(make), lead.sharding)
            return make()
        self.row_state = held(
            lambda: fam.init_row_state(model_cfg, batch_size))
        self.row_state_bytes_per_row = (
            fam.row_state_bytes_per_row(model_cfg)
            if self.row_state is not None else 0)
        has_rows = self.row_state is not None
        #: ``{"tokens", "layers", "slab_tokens"}`` for a family whose
        #: attention has window layers that keep their keys in row
        #: state (``attention_window``), else None: what the engine
        #: counts a window cache by (``get_stats()["window"]``).
        window_fn = getattr(fam, "attention_window", None)
        self.attention_window = (window_fn(model_cfg)
                                 if window_fn is not None else None)

        def forward_prefill(params, cfg, tokens, positions, lengths, cache,
                            bts, last_only, rows=()):
            if not has_rows:
                return fam.forward_prefill(params, cfg, tokens, positions,
                                           lengths, cache, bts,
                                           last_only=last_only)
            last, pages, state = fam.forward_prefill(
                params, cfg, tokens, positions, lengths, cache[0], bts,
                last_only=last_only, row_state=cache[1], rows=rows[0])
            return last, (pages, state)

        def with_state(fn, args, cache_at, kw, rows_kw):
            """``fn`` of a family with row state: the cache operand is
            ``(pages, state)`` and comes back so; the counters, for a
            family that counts, after it."""
            pages, state = args[cache_at]
            if n_stats:
                kw = dict(kw, stats=True)
            out = fn(*args[:cache_at], pages, *args[cache_at + 1:],
                     row_state=state, **kw, **rows_kw)
            n = len(out) - (3 if n_stats else 2)
            return (out[:n] + ((out[n], out[n + 1]),)
                    + ((out[n + 2],) if n_stats else (None,)))

        def forward_decode(params, cfg, tok, pos, cache, bts, active=None,
                           acc=None):
            if has_rows:
                logits, cache, st = with_state(
                    fam.forward_decode, (params, cfg, tok, pos, cache, bts),
                    4, {"active": active}, {})
                return logits, cache, (st if st is None or acc is None
                                       else acc + st)
            if not n_stats:
                return fam.forward_decode(params, cfg, tok, pos, cache, bts,
                                          active=active) + (None,)
            logits, cache, st = fam.forward_decode(
                params, cfg, tok, pos, cache, bts, active=active, stats=True)
            return logits, cache, (st if acc is None else acc + st)

        def forward_mixed(*args, dec_active=None, rows=()):
            if has_rows:
                return with_state(fam.forward_mixed, args, 4,
                                  {"dec_active": dec_active},
                                  {"pf_rows": rows[0]})
            if not n_stats:
                return fam.forward_mixed(*args, dec_active=dec_active) + (
                    None,)
            return fam.forward_mixed(*args, dec_active=dec_active,
                                     stats=True)

        def stats0():
            return jnp.zeros((n_stats,), jnp.int32) if n_stats else None
        #: dp universes of the paged pool (docs/multihost.md): > 1 when
        #: the mesh has a dp axis that divides BOTH the batch and the
        #: page count — the batch dim then shards over dp, the pool's
        #: page axis splits into per-replica page universes, and the
        #: host allocator (engine/kv_allocator.py) mirrors the split.
        self.dp_shards = 1
        if mesh is not None and mesh.size > 1:
            import dataclasses

            from llmq_tpu.ops.quant import is_quantized
            from llmq_tpu.parallel.sharding import (
                kv_cache_shardings, param_shardings, shard_params)

            model_cfg = dataclasses.replace(model_cfg, pallas=False)
            quantized = is_quantized(params["layers"]["wq"])
            # Regex partition-rule table → NamedSharding pytree →
            # device_put placement (the pjit serving-stack shape): tp
            # shards heads/MLP/vocab, dp replicates the weights.
            params = shard_params(
                params, param_shardings(model_cfg, mesh,
                                        quantized=quantized,
                                        params=params))
            dp = int(mesh.shape.get("dp", 1))
            if dp > 1:
                if num_pages % dp == 0 and batch_size % dp == 0:
                    self.dp_shards = dp
                else:
                    log.warning(
                        "mesh dp=%d does not divide num_pages=%d / "
                        "batch_size=%d; dp degrades to replication",
                        dp, num_pages, batch_size)
            self._kv_shardings = kv_cache_shardings(
                model_cfg, mesh,
                quantized=(jnp.dtype(cache_dtype or model_cfg.dtype)
                           == jnp.int8),
                num_pages=(num_pages if self.dp_shards > 1 else 0))
        else:
            self._kv_shardings = None
        self.model_cfg = model_cfg
        #: What :func:`lay_params` laid on the device in the layouts
        #: the family names: ``get_stats()["device"]["relaid"]``.
        self._layouts = dict(getattr(fam, "DEVICE_LAYOUT", {}))
        self.relaid = lay_params(fam, params)
        if self.relaid["leaves"]:
            log.info("laid %d stacked parameter leaves on the device as "
                     "their family asks (%d bytes)", self.relaid["leaves"],
                     self.relaid["bytes"])
        self.params = params
        max_pages_per_seq = max(
            1, model_cfg.max_seq_len // page_size)
        self.spec = ExecutorSpec(batch_size, page_size, num_pages,
                                 max_pages_per_seq, eos_id)
        if self.attention_window is not None:
            # the chunk the windowed decode kernel visits keys by: what
            # ``window_chunks`` counts in
            from llmq_tpu.ops.pallas.fused_decode import chunk_tokens
            slab = next(iter(self.row_state.values()))
            self._window_chunk_tokens = chunk_tokens(
                batch_size, page_size, max_pages_per_seq, slab.shape[3],
                slab.dtype.itemsize)
        self.chunk_size = max(1, chunk_size)
        self._top_k = top_k
        self._top_p = top_p
        #: Sequences per batched-prefill program (admission waves run
        #: their prompts through ONE program: the dense matmuls — where
        #: the weight streaming is — batch across prompts; the
        #: per-sequence KV-write/attention kernels row-loop inside).
        self.prefill_batch = max(1, min(prefill_batch, batch_size))
        self.prefill_buckets = sorted(prefill_buckets or [32, 128, 512])
        #: Mixed-batch program geometry: S slice rows fused into the
        #: decode chunk, each ``mixed_slice_tokens`` wide — a slice's
        #: width, nothing else (0 disables — no mixed program is built
        #: or compiled). See ``_mixed_chunk`` below.
        self.mixed_prefill_slices = max(0, mixed_prefill_slices)
        self.mixed_slice_tokens = max(0, mixed_slice_tokens)
        if self.mixed_prefill_slices == 0 or self.mixed_slice_tokens == 0:
            self.mixed_prefill_slices = 0
            self.mixed_slice_tokens = 0
        if self._kv_shardings is not None:
            # Create the pool ALREADY sharded (out_shardings) — a 70B
            # pool materialized on one chip before resharding would OOM
            # the chip sharding exists to relieve.
            self.cache = jax.jit(
                lambda: init_kv_pages(model_cfg, num_pages, page_size,
                                      dtype=cache_dtype),
                out_shardings=self._kv_shardings)()
        else:
            self.cache = held(
                lambda: init_kv_pages(model_cfg, num_pages, page_size,
                                      dtype=cache_dtype))
        #: How the fused decode kernel cuts a call of this geometry
        #: (``attn_work`` counts by it); None where the decode steps'
        #: attention is not that kernel's — a family whose pool it does
        #: not read (the latent ones), off the TPU, under a mesh, at a
        #: geometry it refuses: the predicate the dispatchers and
        #: ``decode_order`` go by.
        self._decode_plan = None
        pools = tuple(self.cache[name]
                      for name in ("k", "v", "k_scale", "v_scale")
                      if name in self.cache)
        if pools and pools[0].ndim == 4:
            from llmq_tpu.ops.attention import fused_decode_route
            from llmq_tpu.ops.pallas.fused_decode import _tile_plan
            if fused_decode_route(batch_size, pools, max_pages_per_seq,
                                  model_cfg.head_dim,
                                  getattr(model_cfg, "pallas", True))[0]:
                self._decode_plan = _tile_plan(
                    batch_size, page_size, max_pages_per_seq,
                    pools[0].shape[3], pools[0].dtype.itemsize)
        self._key = jax.random.PRNGKey(seed)

        cfg = model_cfg
        eos = eos_id

        # Pin the cache's OUTPUT sharding on the mesh path: donated
        # buffers leave the program with whatever sharding GSPMD found
        # profitable (it happily splits the flat H_kv·D axis even when
        # the head count doesn't divide), and the next program's
        # AOT-compiled signature would then reject the resharded pool.
        if self._kv_shardings is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            _repl = NamedSharding(mesh, PartitionSpec())
            # Batch-dim arrays (tokens/positions/block tables/carries)
            # shard over dp when the pool does: contiguous row blocks
            # of B/dp land with their dp replica's page universe. A
            # tp-only mesh keeps them replicated — today's layout.
            _batch = (NamedSharding(mesh, PartitionSpec("dp"))
                      if self.dp_shards > 1 else _repl)
            self._batch_shd = _batch if self.dp_shards > 1 else None
            kvs = dict(self._kv_shardings)
            jit_step = partial(jax.jit, donate_argnums=(1,),
                               out_shardings=(_repl, kvs))
            # decode returns ((B,) toks, cache) — batch-sharded.
            jit_decode = partial(jax.jit, donate_argnums=(1,),
                                 out_shardings=(_batch, kvs))
            # decode_chunk returns (out, tok, pos, done, cache); the
            # tail three are the dp-sharded device-resident carry the
            # pipelined next chunk consumes without ever leaving the
            # mesh (sharded-array futures).
            jit_chunk = partial(jax.jit, donate_argnums=(1,),
                                out_shardings=(_batch, _batch, _batch,
                                               _batch, kvs, None))
            # mixed_chunk returns (out, tok, pos, done, pf_first, cache);
            # pf_first is slice-indexed (not batch) → replicated.
            jit_mixed = partial(jax.jit, donate_argnums=(1,),
                                out_shardings=(_batch, _batch, _batch,
                                               _batch, _repl, kvs, None))
        else:
            self._batch_shd = None
            jit_step = partial(jax.jit, donate_argnums=(1,))
            jit_decode = jit_step
            jit_chunk = jit_step
            jit_mixed = jit_step

        @jit_step
        def _prefill_step(params, cache, tokens, positions, lengths,
                          block_tables, temperature, key, *rows):
            with scope("prefill"):
                last, cache = forward_prefill(
                    params, cfg, tokens, positions, lengths, cache,
                    block_tables, True, rows)          # (1, V) f32
            with scope("sample"):
                tok = sample_token(last, key, temperature=temperature,
                                   top_k=top_k, top_p=top_p)[0]
            return tok, cache

        @jit_step
        def _prefill_multi(params, cache, tokens, positions, lengths,
                           block_tables, temperatures, key, *rows):
            """Batched prefill: N prompts' chunks through one program —
            per-row last-token sampling; padded rows (length ≤ 1,
            all-zero block table) write only reserved page 0."""
            with scope("prefill"):
                last, cache = forward_prefill(
                    params, cfg, tokens, positions, lengths, cache,
                    block_tables, True, rows)          # (N, V)
            with scope("sample"):
                toks = sample_token(last, key, temperature=temperatures,
                                    top_k=top_k, top_p=top_p)
            return toks, cache

        @jit_decode
        def _decode_step(params, cache, tokens, positions, block_tables,
                         temperatures, key):
            logits, cache, _ = forward_decode(
                params, cfg, tokens, positions, cache, block_tables)
            with scope("sample"):
                toks = sample_token(logits, key, temperature=temperatures,
                                    top_k=top_k, top_p=top_p)
            return toks, cache

        K = self.chunk_size

        @jit_chunk
        def _decode_chunk(params, cache, tokens, positions, block_tables,
                          temperatures, budgets, done_in, key):
            """Up to K decode steps on device: sampling, EOS latching and
            per-row budgets stay in the program; one host transfer of
            (B, K) token ids per call — or NONE, when the next call
            consumes the returned carry directly (pipelined decode).

            ``lax.while_loop`` instead of a scan: the program EXITS as
            soon as every row is done (EOS-latched, budget-exhausted, or
            latched on ENTRY via ``done_in`` — how a carried next
            chunk keeps rows the host has since finished frozen on
            reserved page 0), so small budgets cost exactly the steps
            run — one compiled program serves every granularity from 1
            to K (adaptive admission latency).

            Returns ``(out (B, K), tok (B,), pos (B,), done (B,),
            cache)`` — the tail three are the device-resident carry the
            next call can take WITHOUT a host round-trip.
            """
            B = tokens.shape[0]
            keys = jax.random.split(key, K)
            out0 = jnp.full((B, K), eos, jnp.int32)
            # Two distinct latches — conflating them truncates every
            # multi-chunk generation: ``done_in``/EOS are PERSISTENT
            # (carried out: the row is finished for good), while budget
            # exhaustion is THIS-CHUNK-ONLY (the row merely pauses; the
            # carried next chunk resumes it from the carried
            # tok/pos with a fresh budget).
            frozen0 = done_in
            # 2 decode steps per loop iteration: halves the while-loop's
            # per-iteration control overhead (~0.3 ms/step at 1B B=64 on
            # v5e); budgets stay EXACT via the per-step active mask —
            # only the early-exit granularity coarsens to 2.
            UNROLL = 2 if K % 2 == 0 else 1

            def cond(st):
                j, _, _, _, frozen, _, _ = st
                return (j < K) & jnp.any(~frozen & (j < budgets))

            def body(st):
                j, cache, tok, pos, frozen, out, acc = st
                for u in range(UNROLL):
                    active = (~frozen) & (j + u < budgets)
                    logits, cache, acc = forward_decode(
                        params, cfg, tok, pos, cache, block_tables,
                        active=active, acc=acc)
                    with scope("sample"):
                        nxt = sample_token(logits, keys[j + u],
                                           temperature=temperatures,
                                           top_k=top_k, top_p=top_p)
                        emit = jnp.where(active, nxt,
                                         eos).astype(jnp.int32)
                        out = jax.lax.dynamic_update_slice(
                            out, emit[:, None], (0, j + u))
                        # Budget-paused rows keep their last REAL token
                        # — it is the next chunk's input; only active
                        # rows advance.
                        tok = jnp.where(active, nxt.astype(jnp.int32),
                                        tok)
                        pos = pos + active.astype(jnp.int32)
                        frozen = frozen | (active & (nxt == eos))
                return (j + UNROLL, cache, tok, pos, frozen, out, acc)

            with scope("decode_loop"):
                _, cache, tok, pos, frozen, out, acc = jax.lax.while_loop(
                    cond, body,
                    (jnp.int32(0), cache, tokens, positions, frozen0, out0,
                     stats0()))
            return out, tok, pos, frozen, cache, acc

        S, T = self.mixed_prefill_slices, self.mixed_slice_tokens
        _mixed_chunk = None
        if S > 0:

            @jit_mixed
            def _mixed_chunk(params, cache, tokens, positions,
                             block_tables, temperatures, budgets, done_in,
                             pf_tokens, pf_positions, pf_lengths, pf_starts,
                             pf_block_tables, pf_temps, key, *pf_rows):
                """Token-budget MIXED chunk: one device program that
                advances the decode rows up to K steps AND runs S
                prefill slices of up to T tokens each over the shared
                paged pool. Step 0 is the fused pass (forward_mixed:
                slice KV writes ride the same layer traversal as the
                decode rows, so the per-layer weight stream is paid
                once for both); steps 1..K-1 are the plain decode body
                with the same EOS/budget latching as ``_decode_chunk``.
                The decode rows' prefill-induced stall is thereby
                bounded by S·T tokens (the engine's
                ``mixed_batch.prefill_token_budget``), not by the
                longest admitted prompt.

                Returns ``(out (B, K), tok, pos, done, pf_first (S,),
                cache)`` — the decode tail carry is identical to
                ``_decode_chunk``'s; ``pf_first[i]`` samples slice i's
                last valid position (the admission first-token when the
                slice is a sequence's final one; garbage the engine
                ignores otherwise)."""
                B = tokens.shape[0]
                keys = jax.random.split(key, K + 1)
                out = jnp.full((B, K), eos, jnp.int32)
                frozen = done_in
                active0 = (~frozen) & (budgets > 0)
                with scope("mixed_step"):
                    dec_logits, pf_logits, cache, acc = forward_mixed(
                        params, cfg, tokens, positions, cache,
                        block_tables, pf_tokens, pf_positions, pf_lengths,
                        pf_starts, pf_block_tables, dec_active=active0,
                        **({"rows": pf_rows} if pf_rows else {}))
                    with scope("sample"):
                        pf_first = sample_token(
                            pf_logits, keys[K],
                            temperature=pf_temps, top_k=top_k, top_p=top_p)
                        nxt = sample_token(dec_logits, keys[0],
                                           temperature=temperatures,
                                           top_k=top_k, top_p=top_p)
                        emit = jnp.where(active0, nxt,
                                         eos).astype(jnp.int32)
                        out = out.at[:, 0].set(emit)
                        tok = jnp.where(active0, nxt.astype(jnp.int32),
                                        tokens)
                        pos = positions + active0.astype(jnp.int32)
                        frozen = frozen | (active0 & (nxt == eos))

                def cond(st):
                    j, _, _, _, fr, _, _ = st
                    return (j < K) & jnp.any(~fr & (j < budgets))

                def body(st):
                    j, cache, tok, pos, fr, out, acc = st
                    active = (~fr) & (j < budgets)
                    logits, cache, acc = forward_decode(
                        params, cfg, tok, pos, cache, block_tables,
                        active=active, acc=acc)
                    with scope("sample"):
                        nxt = sample_token(logits, keys[j],
                                           temperature=temperatures,
                                           top_k=top_k, top_p=top_p)
                        emit = jnp.where(active, nxt,
                                         eos).astype(jnp.int32)
                        out = jax.lax.dynamic_update_slice(
                            out, emit[:, None], (0, j))
                        tok = jnp.where(active, nxt.astype(jnp.int32), tok)
                        pos = pos + active.astype(jnp.int32)
                        fr = fr | (active & (nxt == eos))
                    return (j + 1, cache, tok, pos, fr, out, acc)

                with scope("decode_loop"):
                    (_, cache, tok, pos, frozen, out,
                     acc) = jax.lax.while_loop(
                        cond, body,
                        (jnp.int32(1), cache, tok, pos, frozen, out, acc))
                return out, tok, pos, frozen, pf_first, cache, acc

        self._prefill_step = _prefill_step
        self._prefill_multi = _prefill_multi
        self._decode_step = _decode_step
        self._decode_chunk = _decode_chunk
        self._mixed_chunk = _mixed_chunk
        #: AOT-compiled executables by program name (filled by warmup;
        #: call sites prefer these — the jit wrappers re-trace on first
        #: call, the executables don't).
        self._aot: Dict[str, object] = {}
        #: Program names whose executable came from the export disk
        #: cache this start (drives the minimal-smoke fast path).
        self._from_export_cache: set = set()
        #: Per compiled program, which implementation each attention op
        #: took (ops/attention.kernel_routes) — logged at warmup and
        #: exported through the device telemetry's compile block.
        self.program_routes: Dict[str, Dict[str, str]] = {}
        #: Measured per-decode-step ms (set by warmup) — the engine's
        #: tier-aware admission cap converts its latency target into a
        #: step budget with this.
        self.step_ms: Optional[float] = None
        #: Device telemetry (observability/device.py): compile-cache
        #: hit/miss + per-program compile seconds land here during
        #: warmup; the engine built on top of this executor shares the
        #: same instance by name (builder passes its engine name).
        #: ``telemetry_metrics`` matters because warmup runs BEFORE the
        #: engine exists to set the flag — a metrics-off bench/engine
        #: must not have its warmup write prometheus families.
        from llmq_tpu.observability.device import (BACKEND_COMPILES,
                                                   XLA_CACHE,
                                                   get_device_telemetry)
        BACKEND_COMPILES.watch()
        XLA_CACHE.watch()
        #: The host-span ring (utils/profiling.py). The warm-up opens
        #: one ``engine.warmup.compile`` span per program in it; the
        #: engine built over this executor takes it as its own, so the
        #: spans show behind ``/api/v1/engine/stats`` ``profile``.
        self.spans = SpanRecorder()
        self._telemetry = get_device_telemetry(telemetry_name,
                                               metrics=telemetry_metrics)
        self._telemetry.configure_model(**self.telemetry_info())
        #: (device id → static weights/KV byte totals) — computed
        #: lazily on the first hbm_info() call; the donated cache
        #: rebinds every step but its shapes (= bytes) never change.
        self._hbm_static: Optional[Dict[int, Dict[str, int]]] = None
        self._warm_mu = threading.Lock()
        self._warm_done = 0
        self._warm_hit_s = 0.0
        self._warm_miss_s = 0.0
        #: Boot decomposition of the last warmup() (critical_path.py):
        #: {"artifact": s, "compile": s, "warmup": s} — export-cache
        #: loads vs trace+lower+compile (AOT wall pro-rated by the
        #: per-program hit/miss seconds, since programs compile in
        #: parallel) vs the smoke/calibration remainder.
        self.warmup_split: Dict[str, float] = {}
        #: Reusable host staging buffers per (program, geometry): the
        #: per-dispatch np.zeros churn killer. Decode/mixed tags are
        #: bounded by the pipeline depth (≤ 4); prefill tags are NOT
        #: intrinsically bounded (an onboarding storm dispatches one
        #: bucket per slot per step with no host sync), so every
        #: prefill dispatch ticks ``_staging_fence`` — which blocks on
        #: the just-dispatched program every ring-2 same-tag dispatches
        #: to fence all earlier programs (FIFO device stream) before
        #: their staging buffers can be rewritten.
        self._staging = HostStaging(ring=max(8, batch_size + 4))
        self._staging_fence_counts: Dict[str, int] = {}
        #: Lazily-built donated scatter program of the tiered-KV plane's
        self._kv_inject = None      # promotions: one compile in all
        self._init_row_tails(fam, model_cfg, int(row_tail_slots), held)

    def telemetry_info(self) -> Dict:
        """Model identity for the MFU estimator — shared with the
        engine's telemetry registration (same math bench.py uses)."""
        quant = "int8" if _is_quantized_tree(self.params) else ""
        # What one token multiplies with: every parameter of a dense
        # block, the routed share of a sparse one.
        n_params = self._family.active_param_count(self.model_cfg)
        from llmq_tpu.observability.device import device_identity
        ident = device_identity()
        return {"n_params": n_params,
                "relaid": self.relaid,
                "platform": ident["platform"],
                "device_kind": ident["kind"],
                "device_count": ident["count"],
                "quant": quant,
                # MFU denominator scales with the mesh: N chips serve
                # N× the peak FLOPs (bench + live gauge agree).
                "n_chips": (self.mesh.size
                            if self.mesh is not None else 1)}

    @property
    def _pool(self):
        """What the serving programs donate and return at operand 1: the
        page pool, and beside it the row state of a family that has
        one."""
        if self.row_state is None:
            return self.cache
        return self.cache, self.row_state

    @_pool.setter
    def _pool(self, pool) -> None:
        if self.row_state is None:
            self.cache = pool
        else:
            self.cache, self.row_state = pool

    def window_chunks(self, seq_lens) -> tuple:
        """``(visited, skipped)`` key chunks of ONE window layer's
        decode attention over rows of ``seq_lens``
        (``ops/pallas/fused_decode.window_chunks``)."""
        from llmq_tpu.ops.pallas.fused_decode import window_chunks
        return window_chunks(seq_lens, self._window_chunk_tokens,
                             self.attention_window["tokens"])

    def attn_work(self, seq_lens, window: bool = False):
        """``(steps, row_chunks, row_chunks_full)`` of ONE attention
        layer's decode call over the decoding rows' ``seq_lens`` (the
        rest of the batch counted dead): the kernel's (tile, chunk)
        steps, the (row, chunk) pairs whose products run and those of
        them in a step where the whole tile is live
        (``ops/pallas/fused_decode.decode_work`` on the rows in the
        order the step hands them over) — a full-attention layer's, or
        with ``window`` a window layer's. A seat that holds no row
        counts as the context the family's step hands the kernel for it
        (its ``IDLE_ROW_CONTEXT``; 1, position 0's, where it names
        none). None where the decode steps' attention is not that
        kernel's."""
        if self._decode_plan is None:
            return None
        from llmq_tpu.ops.pallas.fused_decode import decode_work
        lens = np.full(self.spec.batch_size,
                       getattr(self._family, "IDLE_ROW_CONTEXT", 1), np.int64)
        lens[:len(seq_lens)] = seq_lens
        steps, computed, _, full = decode_work(
            lens, self._decode_plan,
            self.attention_window["tokens"] if window else None,
            ordered=True)
        return steps, computed, full

    def scan_work(self, entry: str, lengths) -> Optional[tuple]:
        """``(chunks, chunks_live)`` of ONE recurrent layer's scan
        kernel call in the program behind ``entry`` over prompt chunks
        of ``lengths`` tokens: the steps of its grid a head block — the
        program's slices (a mixed chunk's ``mixed_prefill_slices``, the
        empty ones too; a prefill program's rows) by their width in the
        kernel's steps — and those that start under a slice's length,
        the others being skipped. None where the family has no such
        kernel or the program's slices go to XLA's scan (the family's
        ``scan_step_tokens``)."""
        step_of = getattr(self._family, "scan_step_tokens", None)
        if step_of is None or not lengths:
            return None
        if entry == "mixed_chunk":
            S, T = self.mixed_prefill_slices, self.mixed_slice_tokens
        elif entry in ("prefill", "prefill_multi"):
            S = self.prefill_batch if entry == "prefill_multi" else 1
            T = self._bucket_for(max(1, max(lengths)))
        else:
            return None
        step = step_of(self.model_cfg, T)
        if step is None:
            return None
        return S * (T // step), sum(-(-n // step) for n in lengths)

    def _rows_arg(self, rows) -> tuple:
        """The batch rows of a program's prompt chunks as its last
        operand — ``()`` for a family without row state, whose programs
        take none."""
        if self.row_state is None:
            return ()
        if any(r is None for r in rows):
            raise ValueError(
                f"model {self.model_cfg.name!r} keeps row state: a "
                f"prefill names its sequence's batch row (slot=)")
        return (self._jnp.asarray(rows, self._jnp.int32),)

    def hbm_info(self) -> List[Dict]:
        """Per-chip HBM accounting: weights / KV-pool bytes resident on
        each local device (sharded trees split per device via sharding
        METADATA), plus free/limit from the runtime's ``memory_stats``
        where the backend provides it (TPU yes, CPU no).

        Metadata-only by design: this runs on the scrape thread while
        the engine thread donates ``self.cache`` every step — touching
        shard BUFFERS (``.data.nbytes``) would race their deletion
        ("Array has been deleted"); shape/dtype/sharding survive
        donation."""
        import math

        jax = self._jax
        if self._hbm_static is None:
            per: Dict[int, Dict[str, int]] = {}
            zero = {"weights_bytes": 0, "kv_pool_bytes": 0,
                    "row_state_bytes": 0}

            def add(tree, key: str) -> None:
                for leaf in jax.tree.leaves(tree):
                    shape = getattr(leaf, "shape", None)
                    dtype = getattr(leaf, "dtype", None)
                    if shape is None or dtype is None:
                        continue
                    itemsize = np.dtype(dtype).itemsize
                    sharding = getattr(leaf, "sharding", None)
                    devs = list(getattr(sharding, "addressable_devices",
                                        None) or [])
                    if devs:
                        try:
                            shard_bytes = (
                                math.prod(sharding.shard_shape(shape))
                                * itemsize)
                        except Exception:  # noqa: BLE001 — fallback split
                            shard_bytes = (math.prod(shape) * itemsize
                                           // len(devs))
                        for dv in devs:
                            d = per.setdefault(dv.id, dict(zero))
                            d[key] += int(shard_bytes)
                    else:
                        d = per.setdefault(0, dict(zero))
                        d[key] += int(math.prod(shape) * itemsize)

            add(self.params, "weights_bytes")
            add(self.cache, "kv_pool_bytes")
            add((self.row_state, self.row_tails), "row_state_bytes")
            self._hbm_static = per
        chips = []
        for dev in jax.local_devices():
            d = self._hbm_static.get(dev.id)
            if d is None:
                continue   # chip holds no model state (unsharded run)
            entry = {"chip": str(dev.id), "kind": dev.device_kind,
                     "weights_bytes": d.get("weights_bytes", 0),
                     "kv_pool_bytes": d.get("kv_pool_bytes", 0),
                     "free_bytes": None, "limit_bytes": None}
            if self.row_state is not None:
                entry["row_state_bytes"] = d.get("row_state_bytes", 0)
            try:
                stats = dev.memory_stats() or {}
                limit = stats.get("bytes_limit")
                in_use = stats.get("bytes_in_use")
                if limit is not None:
                    entry["limit_bytes"] = int(limit)
                    if in_use is not None:
                        entry["free_bytes"] = int(limit) - int(in_use)
            except Exception:  # noqa: BLE001 — CPU backends lack stats
                pass
            chips.append(entry)
        return chips

    # -- helpers -------------------------------------------------------------

    def _staging_fence(self, tag: str, out) -> None:
        """Staging-aliasing fence for the unbounded-dispatch prefill
        paths: ``device_put`` may zero-copy alias a staging buffer, so
        a buffer must not be rewritten (ring wrap) while its program is
        still queued. Blocking on the NEWEST program's output every
        ring-2 same-tag dispatches guarantees — the device stream is
        FIFO — that every earlier program consumed its inputs before
        the ring can reach them again."""
        cnt = self._staging_fence_counts.get(tag, 0) + 1
        self._staging_fence_counts[tag] = cnt
        if cnt % (self._staging._ring - 2) == 0:
            try:
                out.block_until_ready()
            except Exception:  # noqa: BLE001 — a failed program surfaces
                pass           # at its own fetch, not at the fence

    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def _next_key(self):
        self._key, sub = self._jax.random.split(self._key)
        return sub

    def _batch_arr(self, x, dtype):
        """Place one batch-dim operand. Off the dp path this is exactly
        ``jnp.asarray`` (byte-identical single-chip/tp behavior); on a
        dp mesh the host staging buffer is explicitly ``device_put``
        with the dp batch sharding — each replica receives its
        contiguous B/dp rows, assembled straight from the staging
        buffer (no full-batch replica on any one chip)."""
        if self._batch_shd is None:
            return self._jnp.asarray(x, dtype)
        if isinstance(x, self._jax.Array):
            # Device-resident carry: already dp-sharded by the previous
            # program's out_shardings; device_put is then a no-op.
            return self._jax.device_put(x, self._batch_shd)
        return self._jax.device_put(np.asarray(x, dtype),
                                    self._batch_shd)

    def _zeros_done(self):
        """Fresh all-false done latch, placed like every other batch
        operand (dp-sharded on the dp path, plain otherwise)."""
        return self._batch_arr(
            np.zeros(self.spec.batch_size, np.bool_), np.bool_)

    def _routes(self, *, decode: bool = False,
                prefill_rows: int = 0) -> Dict[str, str]:
        """Attention-op routes of one program at this executor's
        geometry, by the model family's ``routes`` (for the Llama block
        :func:`llmq_tpu.ops.attention.kernel_routes`)."""
        return self._family.routes(
            self.model_cfg, self.cache, batch=self.spec.batch_size,
            page_size=self.spec.page_size,
            max_pages=self.spec.max_pages_per_seq, decode=decode,
            prefill_rows=prefill_rows)

    def _export_cache_dir(self) -> Optional[str]:
        """Directory for serialized post-lowering program artifacts
        (``jax.export``). LLMQ_EXPORT_CACHE_DIR overrides; otherwise an
        ``export/`` subdir of the persistent XLA compilation cache when
        one is configured. Mesh programs export too (the sharded
        StableHLO carries the partition annotations) — the cache KEY
        carries the full mesh geometry (``_export_cache_key``), so a
        single-chip artifact can never be deserialized into a mesh
        serving process, nor a stale-geometry artifact into a reshaped
        mesh (pinned by tests/test_scale.py).

        Why this exists on top of the XLA cache: XLA *compilation* is
        fully cached across restarts, but Python tracing + Mosaic
        kernel LOWERING is not — measured ~27 s per 8B program
        (docs/performance.md "Warmup anatomy"), making a warm 8B
        restart ~160 s. ``jax.export`` serializes the post-lowering
        StableHLO (Mosaic payloads embedded, donation attributes
        preserved), so a restart deserializes + hits the XLA cache
        instead of re-lowering."""
        import os

        d = os.environ.get("LLMQ_EXPORT_CACHE_DIR")
        if d:
            return d
        cache = self._jax.config.jax_compilation_cache_dir
        return os.path.join(cache, "export") if cache else None

    def _export_cache_key(self) -> str:
        """Geometry + model identity + runtime identity + CODE identity:
        anything that changes the lowered program must change the key.
        Code identity hashes the source files the programs trace
        through (model + ops + this file) — without it, editing
        forward_decode would silently serve the stale pre-edit
        computation from the cache. Params and cache enter with their
        PARTITION SPECS, not just shapes: an edit to the partition
        rules (parallel/sharding.py) must not serve a mesh artifact
        lowered under the old layout."""
        import hashlib
        import os

        import jax

        h = hashlib.sha256()
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        src_dirs = [os.path.join(pkg, "models"), os.path.join(pkg, "ops"),
                    os.path.join(pkg, "ops", "pallas")]
        src_files = [os.path.abspath(__file__)]
        for d in src_dirs:
            if os.path.isdir(d):
                src_files.extend(
                    os.path.join(d, f) for f in sorted(os.listdir(d))
                    if f.endswith(".py"))
        for path in src_files:
            try:
                with open(path, "rb") as f:
                    h.update(f.read())
            except OSError:
                pass
        cfg = self.model_cfg

        def leaf_ident(x):
            spec = getattr(getattr(x, "sharding", None), "spec", None)
            return (x.shape, str(x.dtype), str(spec), _order(x))

        # Mesh identity: (axis names, axis sizes, dp page universes).
        # A single-chip artifact must MISS when the same model builds
        # on a mesh, a dp2×tp4 artifact must MISS on tp8 (geometry
        # change), and vice versa — a lowered program's collectives
        # and sharding annotations are part of its identity.
        mesh_ident = (None if self.mesh is None else
                      (tuple(self.mesh.axis_names),
                       tuple(int(self.mesh.shape[a])
                             for a in self.mesh.axis_names),
                       self.dp_shards))
        ident = repr((jax.__version__, jax.devices()[0].device_kind,
                      cfg, self.spec, self.chunk_size, self.prefill_batch,
                      tuple(self.prefill_buckets), self._top_k,
                      self._top_p,
                      ("mesh", mesh_ident),
                      # Mixed-batch geometry: (S, T) changes the mixed
                      # program's shapes — artifacts must not collide
                      # across budget/slice reconfigurations.
                      (self.mixed_prefill_slices,
                       self.mixed_slice_tokens),
                      jax.tree.map(leaf_ident, self.params),
                      # Cache tree identity: bf16-KV and int8-KV lower
                      # different programs — colliding keys would make
                      # alternating configs evict each other's artifacts.
                      jax.tree.map(leaf_ident, self.cache)))
        h.update(ident.encode())
        return h.hexdigest()[:16]

    def programs(self) -> List[tuple]:
        """Every program this executor serves with, as ``(name, the
        jitted function, its abstract operands, its attention
        routes)``: what the warm-up lowers and compiles, from abstract
        shapes alone (the donated multi-GB pool is a ShapeDtypeStruct,
        so no second pool is ever allocated). The parameters are
        described as they LIE (:func:`describe`): a leaf
        :func:`lay_params` laid enters the program in its layout."""
        import jax

        jnp = self._jnp
        spec = self.spec

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        def bsds(shape, dtype):
            """Batch-dim aval: carries the dp sharding on the dp path
            so the AOT signature matches the device_put'd dispatch
            arrays exactly; plain aval otherwise (today's)."""
            if self._batch_shd is None:
                return jax.ShapeDtypeStruct(shape, dtype)
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=self._batch_shd)

        p = describe(self.params, layouts=self._layouts)
        c = describe(self._pool)
        # the batch rows of a program's prompt chunks: an operand of a
        # family that keeps row state, none of the others
        rows_of = ((lambda n: (sds((n,), jnp.int32),))
                   if self.row_state is not None else (lambda n: ()))
        key = sds((2,), jnp.uint32)
        B, MP = spec.batch_size, spec.max_pages_per_seq
        i32, f32 = jnp.int32, jnp.float32

        jobs = []
        NPF = self.prefill_batch
        for T in self.prefill_buckets:
            jobs.append((f"prefill_b{T}", self._prefill_step,
                         (p, c, sds((1, T), i32), sds((1, T), i32),
                          sds((1,), i32), sds((1, MP), i32),
                          sds((1,), f32), key) + rows_of(1),
                         self._routes(prefill_rows=1)))
            if NPF > 1:
                jobs.append((f"prefill_multi_b{T}", self._prefill_multi,
                             (p, c, sds((NPF, T), i32),
                              sds((NPF, T), i32), sds((NPF,), i32),
                              sds((NPF, MP), i32), sds((NPF,), f32),
                              key) + rows_of(NPF),
                             self._routes(prefill_rows=NPF)))
        dec_routes = self._routes(decode=True)
        if self.chunk_size > 1:
            # The engine decodes through ``decode_chunk`` whenever the
            # chunk is longer than one step (its admission cap never
            # goes below 2), so the single-step program would be
            # compiled, kept in the cache and never dispatched.
            jobs.append(("decode_chunk", self._decode_chunk,
                         (p, c, bsds((B,), i32), bsds((B,), i32),
                          bsds((B, MP), i32), bsds((B,), f32),
                          bsds((B,), i32), bsds((B,), jnp.bool_), key),
                         dec_routes))
        else:
            jobs.append(("decode", self._decode_step,
                         (p, c, bsds((B,), i32), bsds((B,), i32),
                          bsds((B, MP), i32), bsds((B,), f32), key),
                         dec_routes))
        if self._mixed_chunk is not None:
            S, T = self.mixed_prefill_slices, self.mixed_slice_tokens
            jobs.append(("mixed_chunk", self._mixed_chunk,
                         (p, c, bsds((B,), i32), bsds((B,), i32),
                          bsds((B, MP), i32), bsds((B,), f32),
                          bsds((B,), i32), bsds((B,), jnp.bool_),
                          sds((S * T,), i32), sds((S * T,), i32),
                          sds((S,), i32), sds((S + 1,), i32),
                          sds((S, MP), i32), sds((S,), f32), key)
                         + rows_of(S),
                         self._routes(decode=True, prefill_rows=S)))
        return jobs

    def _warmup_parallel(self) -> None:
        """AOT-compile every program (``programs``) CONCURRENTLY from
        abstract shapes and keep the executables.

        ``jit.lower(...).compile()`` needs no real buffers and XLA
        compilation releases the GIL, so the decode-chunk giant and all
        prefill buckets compile in parallel — first-start warmup costs
        max(program) instead of sum(programs). The compiled executables are stored in
        ``self._aot`` and CALLED directly at runtime (the call sites
        prefer them over the jit wrappers), so each program is traced
        exactly once; with the persistent compilation cache
        (parallel/mesh.enable_compilation_cache) a restart pays only
        tracing + cache deserialization — and with the EXPORT cache
        (``_export_cache_dir``) not even the tracing + Mosaic lowering:
        warm restarts deserialize the lowered module per program.
        """
        import os

        import jax
        from jax import export as jexport
        from concurrent.futures import ThreadPoolExecutor

        from llmq_tpu.observability.device import XLA_CACHE

        jobs = self.programs()

        exp_dir = self._export_cache_dir()
        exp_key = self._export_cache_key() if exp_dir else None
        if exp_dir:
            os.makedirs(exp_dir, exist_ok=True)

        def note(name: str, t0: float, how: str,
                 asked: Tuple[int, int]) -> None:
            # Compile-cache observability (docs/observability.md
            # "Device telemetry"): per-program compile seconds +
            # hit/miss counters + the warmup-progress gauge, so the
            # geometry grid's compile cost is attributable per program
            # — and, apart from the export artifact, whether XLA's own
            # cache still held the executable and what it weighs.
            # Logged here, as each program ends, not in job order.
            dt = time.perf_counter() - t0
            cache_hit = how == "export cache"
            xla = XLA_CACHE.outcome(asked)
            # Sized only where XLA's cache served it: the runtime still
            # holds those bytes (under 1 s for SmolLM2's six programs,
            # 600 MB). A freshly COMPILED executable is not sized — a
            # first start is when a size is least missed — and the
            # next start reports it.
            nbytes = (len(self._aot[name].runtime_executable().serialize())
                      if xla == "hit" else None)
            routes = self.program_routes[name]
            self._telemetry.note_compile(name, dt, cache_hit, routes=routes,
                                         xla_cache=xla,
                                         executable_bytes=nbytes)
            log.info("warmup compiled %s (%s, xla cache %s, %.1f s, "
                     "%s bytes) routes: %s", name, how, xla, dt, nbytes,
                     " ".join(f"{op}={impl}" for op, impl in routes.items()))
            with self._warm_mu:
                self._warm_done += 1
                done = self._warm_done
                # Boot decomposition (critical_path.py): hit vs miss
                # per-program seconds pro-rate the AOT wall into the
                # "artifact" (export-cache load) vs "compile" (trace +
                # lower + compile) boot stages.
                if cache_hit:
                    self._warm_hit_s += dt
                else:
                    self._warm_miss_s += dt
            self._telemetry.note_warmup(done, len(jobs))

        def compile_one(job) -> None:
            # No fallback in here: a failed export-cache load, a failed
            # export or a Mosaic rejection fails the warm-up with the
            # compiler's own message — on the chip those are exactly
            # the faults a quiet re-lower would hide.
            name, fn, args, routes = job
            self.program_routes[name] = routes
            t0 = time.perf_counter()
            asked = XLA_CACHE.mark()
            if not exp_dir:
                self._aot[name] = fn.lower(*args).compile()
                note(name, t0, "compiled", asked)
                return
            path = os.path.join(exp_dir, f"{exp_key}-{name}.jaxexp")
            hit = os.path.exists(path)
            if hit:
                with open(path, "rb") as f:
                    exported = jexport.deserialize(bytearray(f.read()))
            else:
                # One lowering, used for BOTH the executable and the
                # serialized artifact: export captures the lowered
                # StableHLO (Mosaic payloads + donation included),
                # then compiling its .call skips re-lowering.
                exported = jexport.export(fn)(*args)
            # Re-jit the (de)serialized call with the SAME donation:
            # the exported module carries the aliasing attributes, so
            # the pool stays in-place. Through a function named after
            # the program's key, so the device trace's ``XLA Modules``
            # line reads ``jit_<program>`` and not ``jit_call`` for
            # every one of them. The key holds nothing that differs
            # between starts: the compile cache hits as before.
            self._aot[name] = jax.jit(
                _named(exported.call, name),
                donate_argnums=(1,)).lower(*args).compile()
            if hit:
                self._from_export_cache.add(name)
            else:
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(exported.serialize())
                os.replace(tmp, path)
            note(name, t0, "export cache" if hit else "exported", asked)

        def compile_in_span(job) -> None:
            with self.spans.span("engine.warmup.compile", program=job[0]):
                compile_one(job)

        with self._warm_mu:
            self._warm_done = 0
            self._warm_hit_s = 0.0
            self._warm_miss_s = 0.0
        self._telemetry.note_warmup(0, len(jobs))
        with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
            list(pool.map(compile_in_span, jobs))    # raises a job's error

    def warmup(self) -> None:
        """Compile the decode step and every prefill bucket up front
        (the reference has no analogue; SURVEY §7 'warmup at startup'):
        parallel AOT compile, then one tiny execution per program as a
        smoke pass (near-free — the executables already exist).

        When EVERY program deserialized from the export disk cache, the
        smoke pass shrinks to the smallest bucket + the decode programs:
        the artifacts were smoke-tested when first exported (same code
        identity, enforced by the cache key), and the big-bucket
        executions are what keeps a warm restart from hitting its <60 s
        target.

        The smoke pass also runs the serving loop's EAGER device ops —
        the batched-prefill row split and the lane-join scatters — in
        every array flavour the loop produces, so that nothing compiles
        after ready (chip_smoke.py asserts a zero
        ``device.compile.backend_compiles`` delta over its requests):
        before, the first requests paid 10-13 small XLA compilations."""
        t_warm0 = time.perf_counter()
        self._warmup_parallel()
        # Boot decomposition: split the AOT wall between "artifact"
        # (export-cache deserialize) and "compile" (trace + lower +
        # compile) pro-rata on the per-program hit/miss seconds — the
        # programs compile in parallel, so per-program sums exceed the
        # wall and only the ratio is trustworthy.
        aot_wall = time.perf_counter() - t_warm0
        with self._warm_mu:
            hit_s, miss_s = self._warm_hit_s, self._warm_miss_s
        self.warmup_split = {}
        if hit_s + miss_s > 0:
            self.warmup_split["artifact"] = aot_wall * (
                hit_s / (hit_s + miss_s))
            self.warmup_split["compile"] = aot_wall * (
                miss_s / (hit_s + miss_s))
        elif aot_wall > 0:
            self.warmup_split["compile"] = aot_wall
        spec = self.spec
        cache_warm = bool(self._aot) and all(
            name in self._from_export_cache for name in self._aot)
        bt = np.zeros((1, spec.max_pages_per_seq), np.int32)
        prev = 0
        for b in (self.prefill_buckets[:1] if cache_warm
                  else self.prefill_buckets):
            # One full-size prefill per bucket: lengths prev+1..b
            # stream a chunk of exactly size-b through the bucket-b
            # program.
            n = min(b, prev + 1)
            self.prefill([1] * n, 0, bt[0], 0.0, 0)
            if self.prefill_batch > 1:
                # The admission-wave program of the same bucket, and
                # the eager per-row split of its result.
                self.prefill_multi_async([([1] * n, 0, bt[0], 0.0, 0)])
            prev = b
        if self.row_tail is not None:
            # The two tail programs, at boundary 0: every page of the
            # tail lies before the sequence's start, so both copies
            # move page 0 (nobody's).
            self.export_row_tail(0, 0, 0)
            self.import_row_tail(0, 0, 0)
        # Reset pool: warmup wrote garbage KV into page 0 only (block
        # table all-zero), which is never read — nothing to clean.
        zeros_b = np.zeros(spec.batch_size, np.int32)
        zbt = np.zeros((spec.batch_size, spec.max_pages_per_seq), np.int32)
        ztemp = np.zeros(spec.batch_size, np.float32)
        if self.chunk_size == 1:
            self.decode(zeros_b, zeros_b, zbt, ztemp)
        mixed = None
        if self._mixed_chunk is not None:
            # Mixed-chunk smoke: one trash slice + 1-step decode
            # budgets, all writes land on reserved page 0.
            mixed = self.mixed_chunk_start(
                zeros_b, zeros_b, zbt, ztemp,
                np.ones(spec.batch_size, np.int32),
                [(0, [1], 0, zbt[0], 0.0)])
            mixed.fetch()
        if self.chunk_size > 1:
            self.decode_chunk(zeros_b, zeros_b, zbt, ztemp,
                              np.ones(spec.batch_size, np.int32))
            # Lane joins: a just-prefilled row's first token enters the
            # batch device-to-device through eager scatters, and XLA
            # compiles those once per flavour of the lane arrays —
            # host-born, then a previous chunk's carry.
            first = self.prefill_async([1], 0, bt[0], 0.0, 0)
            join = [(0, first, 0)]
            ones_b = np.ones(spec.batch_size, np.int32)
            h = self.decode_chunk_start(
                zeros_b, zeros_b, zbt, ztemp, ones_b, overrides=join)
            h = self.decode_chunk_start(
                None, None, zbt, ztemp, ones_b, carry=h, overrides=join)
            if mixed is not None:
                # Under a full batch a row whose FINAL slice rode a
                # mixed chunk joins the next chunk from that chunk's
                # ``pf_first``, indexed on the device: every slice
                # index once, scattered into the mixed chunk's carry
                # (the lanes are set up the same for either program).
                for i in range(self.mixed_prefill_slices):
                    h = self.decode_chunk_start(
                        None, None, zbt, ztemp, ones_b, carry=mixed,
                        overrides=[(0, mixed.pf_first_at(i), 0)])
            h.fetch()
            # Per-step cost estimate for the engine's tier-aware
            # admission cap: time (1-step, K-step) chunk PAIRS — both
            # pay one host round-trip, so the difference isolates
            # compute. One pair is fragile: a randomly-initialized
            # model can sample EOS, latching rows so the K-step chunk
            # exits early (overestimating per-step speed), and one-off
            # host stalls corrupt either timing. So: several
            # pairs, each K-step chunk's EFFECTIVE step count read from
            # its own output (first-EOS position per row — the
            # while-loop runs until the LAST live row is done), median
            # across pairs, then a sanity clamp before this number sets
            # the realtime chunk cap. Warmup writes land on reserved
            # page 0 only.
            import time as _time

            K = self.chunk_size
            ones = np.ones(spec.batch_size, np.int32)
            full = np.full(spec.batch_size, K, np.int32)
            samples = []
            for _ in range(3):
                t0 = _time.perf_counter()
                self.decode_chunk(zeros_b, zeros_b, zbt, ztemp, ones)
                t1 = _time.perf_counter()
                out = self.decode_chunk(zeros_b, zeros_b, zbt, ztemp,
                                        full)
                t2 = _time.perf_counter()
                # Effective steps = the longest row before EOS latched
                # (the device loop keeps iterating while ANY row lives).
                live = out != spec.eos_id           # (B, K)
                eff = int(live.any(axis=0).sum()) or 1
                if eff > 1:
                    samples.append(((t2 - t1) - (t1 - t0)) / (eff - 1)
                                   * 1e3)
            if samples:
                samples.sort()
                est = samples[len(samples) // 2]
                # Clamp: a negative/zero pair (stall hit the 1-step
                # timing) or an absurd outlier must not set the cap.
                self.step_ms = float(min(250.0, max(0.05, est)))
                log.info("warmup measured decode step ~%.2f ms "
                         "(median of %d pairs)", self.step_ms,
                         len(samples))
            else:
                self.step_ms = None
                log.warning("decode step timing unusable (EOS latched "
                            "every chunk); admission cap falls back")
        total_warm = time.perf_counter() - t_warm0
        # The smoke executions + step calibration above are the
        # "warmup" boot stage proper.
        self.warmup_split["warmup"] = max(0.0, total_warm - aot_wall)
        self._telemetry.note_warmup_complete(total_warm)
        # The serving-path RTT floor: live on /metrics so tail-latency
        # numbers are interpretable without re-running the bench.
        from llmq_tpu.observability.device import measure_rtt
        self._telemetry.set_rtt(measure_rtt())

    # -- Executor API --------------------------------------------------------

    def program_name(self, entry: str, tokens: int = 0) -> str:
        """Key in ``_aot`` (and so ``jit_<key>`` on the device trace's
        ``XLA Modules`` line) of the program that ``entry`` — a
        dispatch method's name less ``_start`` / ``_async`` — runs;
        ``tokens`` is the longest prompt chunk of a prefill dispatch.
        What the engine puts on its ``engine.dispatch`` span."""
        if entry in ("prefill", "prefill_multi"):
            return f"{entry}_b{self._bucket_for(max(1, tokens))}"
        return entry

    def slice_tokens(self, entry: str, tokens: int = 0, rows: int = 1) -> int:
        """Rows the program behind ``entry`` runs its row-wise products
        over for the ``tokens`` prompt tokens of one dispatch, padding
        included. A mixed chunk (``tokens`` all its slices' together,
        laid tight): the row tiles that hold one, less the decode rows
        that share them where the family runs both through one product
        (its ``mixed_live_rows``), by the rule the program runs by
        (``ops/rows.live_rows``). A prefill program (``tokens`` its
        longest row's): its bucket for each of its rows
        (``prefill_multi`` always runs ``prefill_batch`` of them). 0 for
        a program that takes no prompt tokens. What the engine puts on
        its ``engine.dispatch`` span beside the live ``prefill_tokens``."""
        if entry == "mixed_chunk":
            return self._family.mixed_live_rows(
                tokens, self.spec.batch_size, self.mixed_prefill_slices,
                self.mixed_slice_tokens)
        if entry == "prefill_multi":
            return self._bucket_for(max(1, tokens)) * self.prefill_batch
        if entry == "prefill":
            return self._bucket_for(max(1, tokens))
        return 0

    def hc_rows_live(self, tokens: int) -> Optional[int]:
        """Rows the hyper-connection sites of a mixed chunk's MIXED STEP
        run for ``tokens`` prompt tokens (the family's ``hc_rows_live``:
        its decode rows and the live row tiles behind them); None for a
        family whose residual is one stream."""
        fn = getattr(self._family, "hc_rows_live", None)
        if fn is None or not self.mixed_prefill_slices:
            return None
        return fn(tokens, self.spec.batch_size, self.mixed_prefill_slices,
                  self.mixed_slice_tokens)

    def _prefill_chunk(self, chunk: List[int], start_pos: int, bt,
                       temperature: float, slot: Optional[int] = None):
        """Launch ONE bucketed prefill program (no host sync): pads the
        chunk to its bucket, clamps padding positions, updates the
        donated cache. Returns the sampled-token device array."""
        jnp = self._jnp
        T = self._bucket_for(len(chunk))
        padded = self._staging.take(f"prefill{T}.tok", (T,), np.int32)
        padded[: len(chunk)] = chunk
        positions = self._staging.take(f"prefill{T}.pos", (T,), np.int32,
                                       fill=None)
        np.add(self._staging.arange(T), start_pos, out=positions)
        np.minimum(positions, start_pos + len(chunk) - 1, out=positions)
        fn = self._aot.get(f"prefill_b{T}", self._prefill_step)
        tok, self._pool = fn(
            self.params, self._pool,
            jnp.asarray(padded)[None, :],
            jnp.asarray(positions, jnp.int32)[None, :],
            jnp.asarray([len(chunk)], jnp.int32),
            bt,
            jnp.asarray([temperature], jnp.float32),
            self._next_key(), *self._rows_arg([slot]))
        self._staging_fence(f"prefill{T}", tok)
        return tok

    def prefill(self, tokens: List[int], start_pos: int,
                block_table: np.ndarray, temperature: float,
                slot: int) -> int:
        jnp = self._jnp
        spec = self.spec
        bt = jnp.asarray(block_table, jnp.int32)[None, :]
        pos = start_pos
        remaining = list(tokens)
        tok = None
        # No explicit fence needed: _prefill_chunk's per-tag staging
        # fence bounds outstanding same-bucket dispatches for EVERY
        # caller (this loop, prefill_async, the engine's waves).
        while remaining:
            chunk = remaining[: self.prefill_buckets[-1]]
            remaining = remaining[len(chunk):]
            tok = self._prefill_chunk(chunk, pos, bt, temperature, slot)
            pos += len(chunk)
        if tok is None:
            return spec.eos_id
        return int(tok)

    def prefill_multi_async(self, reqs: List) -> List:
        """Prefill up to ``prefill_batch`` prompts' chunks in ONE
        program dispatch (no host sync): the weight streaming of the
        dense path is paid once for the whole admission wave instead of
        per sequence. ``reqs``: (tokens, start_pos, block_table,
        temperature) per sequence — and its batch row, for a family
        that keeps row state — each chunk ≤ the largest bucket.
        Returns one device scalar (sampled first token) per request.
        """
        jnp = self._jnp
        N = self.prefill_batch
        assert 0 < len(reqs) <= N, len(reqs)
        T = self._bucket_for(max(len(r[0]) for r in reqs))
        st = self._staging
        toks = st.take(f"pfm{T}.tok", (N, T), np.int32)
        poss = st.take(f"pfm{T}.pos", (N, T), np.int32)
        lens = st.take(f"pfm{T}.len", (N,), np.int32, fill=1)
        bts = st.take(f"pfm{T}.bt", (N, self.spec.max_pages_per_seq),
                      np.int32)
        temps = st.take(f"pfm{T}.temp", (N,), np.float32)
        # a padded row names one past the last batch row: nobody's state
        slots = [self.spec.batch_size] * N
        for i, (t, sp, bt, temp, *slot) in enumerate(reqs):
            slots[i] = slot[0] if slot else None
            toks[i, :len(t)] = t
            np.add(st.arange(T), sp, out=poss[i])
            np.minimum(poss[i], sp + len(t) - 1, out=poss[i])
            lens[i] = len(t)
            bts[i] = bt
            temps[i] = temp
        fn = self._aot.get(f"prefill_multi_b{T}", self._prefill_multi)
        out, self._pool = fn(
            self.params, self._pool, jnp.asarray(toks),
            jnp.asarray(poss), jnp.asarray(lens), jnp.asarray(bts),
            jnp.asarray(temps), self._next_key(), *self._rows_arg(slots))
        self._staging_fence(f"pfm{T}", out)
        return [out[i] for i in range(len(reqs))]

    def prefill_async(self, tokens: List[int], start_pos: int,
                      block_table: np.ndarray, temperature: float,
                      slot: Optional[int] = None):
        """Single-bucket prefill WITHOUT the host sync: returns the
        sampled first token as a device array (fetch it when needed).
        Steady-state admission throughput — benchmarks and future
        sync-free engine paths; tokens must fit the largest bucket."""
        if len(tokens) > self.prefill_buckets[-1]:
            raise ValueError("prefill_async requires a single-bucket chunk")
        bt = self._jnp.asarray(block_table, self._jnp.int32)[None, :]
        return self._prefill_chunk(list(tokens), start_pos, bt, temperature,
                                   slot)

    def decode(self, tokens: np.ndarray, positions: np.ndarray,
               block_tables: np.ndarray,
               temperatures: np.ndarray) -> np.ndarray:
        jnp = self._jnp
        fn = self._aot.get("decode", self._decode_step)
        toks, self._pool = fn(
            self.params, self._pool,
            self._batch_arr(tokens, jnp.int32),
            self._batch_arr(positions, jnp.int32),
            self._batch_arr(block_tables, jnp.int32),
            self._batch_arr(temperatures, jnp.float32),
            self._next_key())
        return np.asarray(toks)

    def _chunk_lanes(self, tokens, positions, carry, overrides):
        """``(tok, pos, done)`` a chunk program starts from: the
        previous chunk's device-resident end state with ``carry`` (a
        :class:`ChunkHandle` or a :class:`MixedChunkHandle` — one
        carry surface), else the host arrays with no row latched; then
        the lane ``overrides`` (see ``decode_chunk_start``)."""
        jnp = self._jnp
        if carry is not None:
            tok_in, pos_in, done_in = carry.tok, carry.pos, carry.done
        else:
            tok_in = self._batch_arr(tokens, jnp.int32)
            pos_in = self._batch_arr(positions, jnp.int32)
            done_in = self._zeros_done()
        for slot, tok_dev, pos in (overrides or ()):
            # Eager scatters preserve the carry's dp sharding (pinned
            # by test), so the AOT program's input signature holds.
            tok_in = tok_in.at[slot].set(tok_dev.astype(jnp.int32))
            pos_in = pos_in.at[slot].set(jnp.int32(pos))
            done_in = done_in.at[slot].set(False)
        return tok_in, pos_in, done_in

    def decode_chunk_start(self, tokens, positions,
                           block_tables: np.ndarray,
                           temperatures: np.ndarray,
                           budgets: np.ndarray,
                           carry: Optional["ChunkHandle"] = None,
                           overrides: Optional[List] = None
                           ) -> "ChunkHandle":
        """Dispatch one chunk WITHOUT a host sync.

        With ``carry`` (the previous call's handle), tokens/positions/
        done stay device-resident — the chunk starts immediately from
        the prior chunk's end state, no host round-trip on the critical
        path (pipelined decode: the engine fetches ``carry.out`` while
        this chunk runs). Without it, inputs come from host arrays and
        no row starts latched.

        ``overrides`` — (slot, device_scalar, pos) triples whose input
        token comes DEVICE-to-device (a just-prefilled sequence's
        sampled first token joins the batch without ever visiting the
        host: same-step decode join, one pipeline cycle saved per
        request). The lane's position and done-latch are overridden
        too, so a join can land on a carry lane whose previous owner
        finished (its latch must clear for the new sequence).
        """
        jnp = self._jnp
        fn = self._aot.get("decode_chunk", self._decode_chunk)
        tok_in, pos_in, done_in = self._chunk_lanes(
            tokens, positions, carry, overrides)
        out, tok, pos, done, self._pool, stats = fn(
            self.params, self._pool,
            tok_in, pos_in,
            self._batch_arr(block_tables, jnp.int32),
            self._batch_arr(temperatures, jnp.float32),
            self._batch_arr(budgets, jnp.int32),
            done_in,
            self._next_key())
        return ChunkHandle(out, tok, pos, done, stats)

    def decode_chunk(self, tokens: np.ndarray, positions: np.ndarray,
                     block_tables: np.ndarray, temperatures: np.ndarray,
                     budgets: np.ndarray) -> np.ndarray:
        h = self.decode_chunk_start(tokens, positions, block_tables,
                                    temperatures, budgets)
        return h.fetch()

    def mixed_chunk_start(self, tokens, positions,
                          block_tables: np.ndarray,
                          temperatures: np.ndarray,
                          budgets: np.ndarray,
                          pf: List,
                          carry=None,
                          overrides: Optional[List] = None
                          ) -> "MixedChunkHandle":
        """Dispatch one MIXED chunk (no host sync): the decode rows'
        chunk plus up to ``mixed_prefill_slices`` budgeted prefill
        slices in a single program. ``pf``: ``(slot, tokens, start_pos,
        block_table, temperature)`` per slice, each ≤
        ``mixed_slice_tokens`` tokens (``slot`` is engine bookkeeping —
        the program addresses slices by block table — except for a
        family that keeps row state: there it is the row whose state
        the slice continues).

        The slices' tokens and absolute positions are laid TIGHT, back
        to back in one buffer of S·T rows (``ops/rows.py``), and the
        program is told where each slice starts and, last of
        ``pf_starts``, how many rows hold a token: its row-wise
        products run over those rows' tiles and no others. An unused
        slice starts behind the last token, holds no row there and
        keeps length 1: one trash token against reserved page 0,
        exactly like ``prefill_multi_async``.

        ``carry`` and ``overrides`` mean what they mean to
        ``decode_chunk_start``: the decode rows start from the previous
        chunk's device-resident end state, and a lane is re-seeded for
        a joining row. Same compiled program either way — it always
        took ``tok / pos / done`` as device arrays."""
        if self._mixed_chunk is None:
            raise RuntimeError("mixed batching disabled for this executor")
        jnp = self._jnp
        S, T = self.mixed_prefill_slices, self.mixed_slice_tokens
        assert 0 < len(pf) <= S, len(pf)
        st = self._staging
        pf_toks = st.take("mixed.tok", (S * T,), np.int32)
        pf_poss = st.take("mixed.pos", (S * T,), np.int32)
        pf_lens = st.take("mixed.len", (S,), np.int32, fill=1)
        pf_starts = st.take("mixed.start", (S + 1,), np.int32, fill=None)
        pf_bts = st.take("mixed.bt", (S, self.spec.max_pages_per_seq),
                         np.int32)
        pf_temps = st.take("mixed.temp", (S,), np.float32)
        at = 0
        # an unused slice names one past the last batch row: nobody's
        pf_rows = [self.spec.batch_size] * S
        for i, (slot, t, sp, bt, temp) in enumerate(pf):
            pf_rows[i] = slot
            n = len(t)
            assert 0 < n <= T, n
            pf_toks[at:at + n] = t
            np.add(st.arange(T)[:n], sp, out=pf_poss[at:at + n])
            pf_starts[i] = at
            pf_lens[i] = n
            pf_bts[i] = bt
            pf_temps[i] = temp
            at += n
        pf_starts[len(pf):] = at
        fn = self._aot.get("mixed_chunk", self._mixed_chunk)
        tok_in, pos_in, done_in = self._chunk_lanes(
            tokens, positions, carry, overrides)
        out, tok, pos, done, pf_first, self._pool, stats = fn(
            self.params, self._pool,
            tok_in, pos_in,
            self._batch_arr(block_tables, jnp.int32),
            self._batch_arr(temperatures, jnp.float32),
            self._batch_arr(budgets, jnp.int32),
            done_in,
            jnp.asarray(pf_toks), jnp.asarray(pf_poss),
            jnp.asarray(pf_lens), jnp.asarray(pf_starts),
            jnp.asarray(pf_bts), jnp.asarray(pf_temps),
            self._next_key(), *self._rows_arg(pf_rows))
        key_blocks = None
        if self._mixed_key_blocks is not None:
            # the slices' contexts as the program reads them
            # (``ops/rows.grid_positions``; an empty slot is one trash
            # token at the first dead row's position 0: a context of 1)
            first = pf_poss[pf_starts[:len(pf)]].astype(np.int64)
            n_new = pf_lens[:len(pf)].astype(np.int64)
            # ... and, behind (visited, the table holds), what its LIVE
            # work is: the keys its used slices' contexts hold and the
            # (query, visible key) pairs of their tokens
            key_blocks = tuple(self._mixed_key_blocks(
                pf_poss[pf_starts[:S]] + pf_lens, T, self.spec.page_size,
                self.spec.max_pages_per_seq)) + (
                int((first + n_new).sum()),
                int((n_new * first + n_new * (n_new + 1) // 2).sum()))
        return MixedChunkHandle(out, tok, pos, done, pf_first, stats,
                                key_blocks)

    # -- tiered KV page transport (llmq_tpu/tiering/, docs/tiering.md) --------

    #: Pages scattered per inject program call: ONE compiled program
    #: serves every promotion (shorter groups pad with reserved page 0,
    #: whose content is trash by convention — everything scatters
    #: there), instead of one compile per conversation page count.
    KV_INJECT_TILE = 8

    def kv_page_spec(self) -> List[Tuple[Tuple[int, ...], np.dtype]]:
        """Per-cache-leaf payload shape/dtype for ONE page, leaves in
        ``jax.tree.leaves`` order (k, k_scale, v, v_scale for int8 KV —
        the scale pools ride as ordinary leaves). The page axis (1) is
        removed; the tiering plane's codec keys off this."""
        leaves = self._jax.tree.leaves(self.cache)
        return [((int(leaf.shape[0]),) + tuple(int(d)
                                               for d in leaf.shape[2:]),
                 np.dtype(leaf.dtype)) for leaf in leaves]

    def export_kv_pages(self, pages: List[int]) -> List:
        """DISPATCH the gather of ``pages``' payloads out of the device
        pool — returns device arrays (one per cache leaf, shaped
        ``(L, N, ...)``), no host sync: the caller's worker thread does
        the blocking ``device_get``. Engine-thread only (reads the
        live ``self.cache`` binding); safe against the donated pool
        because the device stream is FIFO — the gather executes before
        any later program can rewrite the pages."""
        idx = self._jnp.asarray(pages, self._jnp.int32)
        return [leaf[:, idx] for leaf in self._jax.tree.leaves(self.cache)]

    def import_kv_pages(self, pages: List[int], leaves: List) -> None:
        """Scatter host payloads back into the device pool at fresh
        ``pages`` (promotion). Engine-thread only — this REBINDS
        ``self.cache`` (donated jitted scatter, so the pool updates in
        place; no transient second pool). The dispatch returns without
        a host sync: a continuation prefill dispatched right after
        reads the injected pages correctly because the device stream
        is FIFO."""
        jax, jnp = self._jax, self._jnp
        if self._kv_inject is None:
            kw = ({"out_shardings": self._kv_shardings}
                  if self._kv_shardings is not None else {})
            self._kv_inject = jax.jit(
                lambda cache, idx, p: jax.tree.map(
                    lambda c, q: c.at[:, idx].set(q), cache, p),
                donate_argnums=(0,), **kw)
        treedef = jax.tree.structure(self.cache)
        T = self.KV_INJECT_TILE
        n = len(pages)
        for i0 in range(0, n, T):
            ids = list(pages[i0:i0 + T])
            grp = [np.asarray(lf[:, i0:i0 + T]) for lf in leaves]
            pad = T - len(ids)
            if pad:
                ids.extend([0] * pad)    # reserved trash page
                grp = [np.concatenate(
                    [g, np.zeros(g.shape[:1] + (pad,) + g.shape[2:],
                                 g.dtype)], axis=1) for g in grp]
            payload = jax.tree.unflatten(
                treedef, [jnp.asarray(g) for g in grp])
            self.cache = self._kv_inject(
                self.cache, jnp.asarray(ids, jnp.int32), payload)

    def gather_scalars(self, arrs: List) -> np.ndarray:
        """Fetch an admission wave's device scalars with overlapped
        transfers (async copy per handle, then ONE batched
        ``device_get`` across the wave): no per-size program to
        compile, and the wall cost is ~one round-trip instead of one
        blocking per-row fetch each."""
        for a in arrs:
            try:
                a.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass
        vals = self._jax.device_get(list(arrs))
        return np.array([int(v) for v in vals], dtype=np.int64)

    def release_slot(self, slot: int) -> None:
        pass  # no per-slot host state

    def resume(self, slot: int, tokens: List[int], start_pos: int) -> None:
        pass  # block tables carry everything

    # -- row tails (docs/prefix_cache.md "Tails") ------------------------------
    # (at the END of the class: the serving programs above keep their
    # lines, and with them their place in XLA's cache)

    def _init_row_tails(self, fam, model_cfg, slots: int, held) -> None:
        """Tails (``models/__init__.py``: ``row_tail``): a pool of
        ``slots`` copies of what rebuilds a row's state at a page
        boundary, for a family that says what does, and the two programs
        that move one between a batch row and a slot. ``self.row_tail``:
        the family's ``{"pages", "stride", "slack_tokens", "bytes"}``
        and ``"slots"``; None where the family has none, no slot was
        asked for or a mesh serves — the engine then declines a prefix
        hit as before there were any."""
        self.row_tail = self.row_tails = None
        tail_fn = getattr(fam, "row_tail", None)
        sharded = self.mesh is not None and self.mesh.size > 1
        if slots <= 0 or self.row_state is None or tail_fn is None or sharded:
            return
        self.row_tail = dict(tail_fn(model_cfg), slots=slots)
        self.row_tails = held(lambda: fam.init_row_tails(model_cfg, slots))

        def row_tail_export(state, tails, row, end_page, slot):
            with scope("row_tail"), scope("export"):
                return fam.export_row_tail(model_cfg, state, tails, row,
                                           end_page, slot)

        def row_tail_import(state, tails, slot, row, end_page):
            with scope("row_tail"), scope("import"):
                return fam.import_row_tail(model_cfg, state, tails, slot,
                                           row, end_page)

        self._tail_export = self._jax.jit(row_tail_export,
                                          donate_argnums=(1,))
        self._tail_import = self._jax.jit(row_tail_import,
                                          donate_argnums=(0,))

    def export_row_tail(self, row: int, end_page: int, slot: int) -> None:
        """DISPATCH the copy of batch row ``row``'s tail before page
        boundary ``end_page`` into tail ``slot``. Engine-thread only, no
        host sync: the device stream is FIFO, so the copy reads what
        every program dispatched before it wrote and nothing later."""
        i32 = np.int32
        self.row_tails = self._tail_export(
            self.row_state, self.row_tails, i32(row), i32(end_page),
            i32(slot))

    def import_row_tail(self, slot: int, row: int, end_page: int) -> None:
        """DISPATCH the copy of tail ``slot`` into batch row ``row``'s
        state at page boundary ``end_page``: a prefill of that row
        dispatched after it continues from ``end_page * page_size``.
        REBINDS ``self.row_state`` (donated, in place)."""
        i32 = np.int32
        self.row_state = self._tail_import(
            self.row_state, self.row_tails, i32(slot), i32(row),
            i32(end_page))
