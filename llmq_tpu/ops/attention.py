"""Attention ops: causal prefill + paged decode.

The paged layout (BASELINE north star) stores KV in fixed-size pages
indexed by per-sequence block tables, so conversations of different
lengths share one HBM pool with no per-request reallocation and no
recompilation (static shapes throughout — XLA traces once per batch
geometry bucket).

One implementation per step kind, chosen here and nowhere else:

- pure JAX (this module): :func:`paged_decode_attention` and its
  ``_pooled`` / ``_q8`` forms, the scatter writes and
  :func:`blockwise_prefill_attention` (online softmax over KV chunks —
  no (B, H, T, S) f32 logits tensor). The semantics reference the
  kernels are tested against, and what serves off the TPU, under a
  mesh and at geometries a kernel's predicate refuses.
- ``ops/pallas/fused_decode.py`` — decode: the new token's KV write
  fused with attention over the row's live pages (bf16 and int8 pools).
- ``ops/pallas/prefill_attention.py`` — prefill attention of a slice
  over its paged context (bf16 and int8 pools).
- ``ops/pallas/kv_write.py`` — the decode and prefill page writes where
  the fused decode kernel does not serve.

:func:`_kernel_route` and the ``_ok`` predicates decide between them
as pure functions of geometry, backend and ``LLMQ_PALLAS`` (``0``
forces pure JAX); the dispatchers (:func:`paged_decode_step`,
:func:`paged_kv_write_prefill`, :func:`dispatch_prefill_attention` and
the ``_q8`` forms) call them at trace time, and :func:`kernel_routes`
evaluates the same predicates outside a trace so the executor can log,
per compiled program, which implementation each op took.
"""

from __future__ import annotations

import os
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# -- nested-jit kernel wrappers ------------------------------------------------
#
# The Pallas kernel bodies are expensive to TRACE (hundreds of pl.when
# closures per call: ~5-8s each), and the model's layer loops are
# unrolled, so direct calls re-trace the identical kernel L times —
# tracing, not XLA compilation, dominated the 300s warmup (r3). Wrapping
# each kernel in its own jax.jit makes layers 2..L hit the trace cache:
# one kernel trace per program instead of L. Measured on v5e: 8-layer
# decode trace 42s -> 5.8s, identical outputs, step not slower (the
# nested-pjit boundary does NOT break the pool aliasing — XLA still
# updates the donated pools in place).

# Double-checked locking (not lru_cache: concurrent first calls from the
# executor's PARALLEL warmup threads would each build a private jit
# wrapper and re-trace the kernel — the exact cost this exists to kill).
_KERNEL_JITS: dict = {}
_KERNEL_JITS_LOCK = threading.Lock()


def _kernel_jit(name: str, make):
    fn = _KERNEL_JITS.get(name)
    if fn is None:
        with _KERNEL_JITS_LOCK:
            fn = _KERNEL_JITS.get(name)
            if fn is None:
                fn = _KERNEL_JITS[name] = make()
    return fn


def _jit_fused_decode():
    def make():
        from llmq_tpu.ops.pallas.fused_decode import (
            fused_decode_attention_pallas)
        return jax.jit(fused_decode_attention_pallas,
                       static_argnames=("pages_per_chunk", "interpret",
                                        "window"))
    return _kernel_jit("fused_decode", make)


def _jit_kv_write():
    def make():
        from llmq_tpu.ops.pallas.kv_write import kv_cache_write_pallas
        return jax.jit(kv_cache_write_pallas,
                       static_argnames=("interpret",))
    return _kernel_jit("kv_write", make)


def _jit_kv_prefill_write():
    def make():
        from llmq_tpu.ops.pallas.kv_write import kv_prefill_write_pallas
        return jax.jit(kv_prefill_write_pallas,
                       static_argnames=("interpret",))
    return _kernel_jit("kv_prefill_write", make)


def _jit_prefill_attention():
    def make():
        from llmq_tpu.ops.pallas.prefill_attention import (
            paged_prefill_attention_pallas)
        return jax.jit(paged_prefill_attention_pallas,
                       static_argnames=("pages_per_chunk", "q_block",
                                        "interpret", "window"))
    return _kernel_jit("prefill_attention", make)


def causal_prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             *, q_offset: jnp.ndarray | int = 0) -> jnp.ndarray:
    """Causal self-attention for prefill.

    q: (B, T, H, D); k, v: (B, S, H_kv, D) where S >= T (S may include a
    previously-cached prefix; ``q_offset`` is the absolute position of
    q's first token, scalar or per-batch (B,)).
    Returns (B, T, H, D). Softmax in f32.

    GQA via grouped einsum — query heads are reshaped to
    (H_kv groups × n_rep) instead of repeating K/V ``n_rep``× in memory:
    the MXU consumes bf16 operands directly (f32 accumulation via
    ``preferred_element_type``), and no (B, S, H, D) f32 copy of the
    cache is ever materialized — on TPU that repeat+cast costs more HBM
    traffic than the attention math itself.
    """
    B, T, H, D = q.shape
    S = k.shape[1]
    Hkv = k.shape[2]
    n_rep = H // Hkv
    scale = D ** -0.5
    qg = q.reshape(B, T, Hkv, n_rep, D)
    logits = jnp.einsum("btgrd,bsgd->bgrts", qg, k,
                        preferred_element_type=jnp.float32) * scale
    q_pos = jnp.arange(T)[:, None] + jnp.asarray(q_offset).reshape(-1, 1, 1)  # (B|1,T,1)
    kv_pos = jnp.arange(S)[None, None, :]
    mask = (kv_pos <= q_pos)[:, None, None, :, :]  # (B|1,1,1,T,S)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrts,bsgd->btgrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, D).astype(q.dtype)


def _gqa_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                seq_lens: jnp.ndarray, window=None) -> jnp.ndarray:
    """Shared decode-attention math: q (B, H, D) against gathered
    history k/v (B, S, H_kv, D), masked beyond ``seq_lens`` and, under
    a ``window``, before ``seq_lens - window`` (the row's last
    ``window`` keys, its current token counted). GQA via grouped einsum
    (no K/V repeat). Returns (B, H, D)."""
    B, H, D = q.shape
    S = k.shape[1]
    Hkv = k.shape[2]
    n_rep = H // Hkv
    scale = D ** -0.5
    qg = q.reshape(B, Hkv, n_rep, D)
    logits = jnp.einsum("bgrd,bsgd->bgrs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(S)[None, :] < seq_lens[:, None]  # (B, S)
    if window is not None:
        mask = mask & (jnp.arange(S)[None, :] >= seq_lens[:, None] - window)
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, D).astype(q.dtype)


def paged_decode_attention(
    q: jnp.ndarray,            # (B, H, D) — one new token per sequence
    k_pages: jnp.ndarray,      # (P, page_size, H_kv, D) global page pool
    v_pages: jnp.ndarray,      # (P, page_size, H_kv, D)
    block_tables: jnp.ndarray,  # (B, max_pages) int32 page ids (pad = any valid id)
    seq_lens: jnp.ndarray,     # (B,) int32 — tokens already in cache incl. current
) -> jnp.ndarray:
    """Single-token decode attention over a single-layer paged KV pool
    (the semantics reference the Pallas kernel is tested against).

    Gathers each sequence's pages via its block table, masks beyond
    ``seq_lens`` and runs GQA attention. Returns (B, H, D).
    """
    B, H, D = q.shape
    page_size = k_pages.shape[1]
    S = block_tables.shape[1] * page_size
    Hkv = k_pages.shape[2]
    # Gather: (B, max_pages, page_size, H_kv, D) → (B, S, H_kv, D)
    k = k_pages[block_tables].reshape(B, S, Hkv, D)
    v = v_pages[block_tables].reshape(B, S, Hkv, D)
    return _gqa_attend(q, k, v, seq_lens)


def paged_decode_attention_pooled(
    q: jnp.ndarray,            # (B, H, D)
    k_pool: jnp.ndarray,       # (L, P, page_size, H_kv·D) all-layer pool
    v_pool: jnp.ndarray,       # (L, P, page_size, H_kv·D)
    block_tables: jnp.ndarray,  # (B, max_pages) int32
    seq_lens: jnp.ndarray,     # (B,) int32
    layer: jnp.ndarray,        # scalar int32 — which layer's pages to read
    window=None,               # static: see _gqa_attend
) -> jnp.ndarray:
    """Decode attention reading layer ``layer`` of the stacked FLAT pool
    (see models/llama.py:init_kv_pages for why the pool stores H_kv·D
    as one axis).

    The pool keeps its layer dimension so forward_decode's unrolled
    layer loop threads one pool buffer through every layer (scan
    formulations force XLA to materialize pool copies — see the
    comment in llama.py:forward_decode). The combined gather
    ``k_pool[layer, block_tables]`` stays a single XLA gather; only the
    gathered VALUE is unflattened to heads, never the pool buffer.
    """
    B, H, D = q.shape
    page_size = k_pool.shape[2]
    S = block_tables.shape[1] * page_size
    Hkv = k_pool.shape[3] // D
    k = k_pool[layer, block_tables].reshape(B, S, Hkv, D)
    v = v_pool[layer, block_tables].reshape(B, S, Hkv, D)
    return _gqa_attend(q, k, v, seq_lens, window)


def pallas_mode() -> str:
    """``LLMQ_PALLAS``: ``auto`` (default — kernels on a TPU backend,
    pure JAX elsewhere), ``0`` (pure JAX everywhere) or ``interpret``
    (kernel bodies through the Pallas interpreter: CPU test coverage
    only). ``interpret`` on a TPU backend is an error — it would serve
    the interpreter's program on the chip the kernels were written
    for."""
    mode = os.environ.get("LLMQ_PALLAS", "auto")
    if mode not in ("auto", "0", "interpret"):
        raise ValueError(
            f"LLMQ_PALLAS={mode!r}: expected auto, 0 or interpret")
    if mode == "interpret" and jax.default_backend() == "tpu":
        raise RuntimeError(
            "LLMQ_PALLAS=interpret on a TPU backend: interpret mode is "
            "for CPU test coverage; unset it (or set 0 for the pure-JAX "
            "path)")
    return mode


def _kernel_route(gd: int, *, extra_ok: bool = True, enabled: bool = True):
    """Shared LLMQ_PALLAS routing policy for the paged-KV kernels.

    Returns (use_kernel, interpret). Kernel eligibility: not disabled
    (``LLMQ_PALLAS=0`` or ``enabled=False`` — the caller's static
    opt-out, e.g. mesh-sharded programs where GSPMD cannot partition a
    single-chip Pallas call), ``extra_ok``, ``gd`` (= H_kv·D, the
    pool's flat trailing axis) lane-aligned, and either a TPU backend
    or ``LLMQ_PALLAS=interpret`` (CI coverage of kernel bodies without
    a TPU)."""
    mode = pallas_mode()
    if mode == "0" or not enabled or not extra_ok or gd % 128:
        return False, False
    if jax.default_backend() == "tpu":
        return True, False
    if mode == "interpret":
        return True, True
    return False, False


def _fused_decode_ok(B: int, page_size: int, max_pages: int, gd: int,
                     itemsize: int) -> bool:
    """bf16 fused decode kernel eligibility. page_size % 8: the kernel
    writes back the 8-sublane tile holding the new row — sub-8 pages
    can't. The tile plan must also be legal for this geometry
    (large-GD models at big pages force an illegal sub-8 row tile —
    the split write-kernel + pooled-attention path serves those)."""
    from llmq_tpu.ops.pallas.fused_decode import fused_kernel_viable
    return page_size % 8 == 0 and fused_kernel_viable(
        B, page_size, max_pages, gd, itemsize)


def _prefill_attn_ok(rows_ok: bool, head_dim: int) -> bool:
    """Prefill attention kernel eligibility: it cuts a page's lanes into
    head windows of ``max(128, head_dim)``, so a head fills a divisor or
    a multiple of 128 lanes (64 and 128 in every registered model)."""
    return rows_ok and (128 % head_dim == 0 or head_dim % 128 == 0)


def _scale_pages_ok(page_size: int, n_kv_heads: int,
                    n_scale_heads: int) -> bool:
    """What the int8-KV kernels ask of a scale page, a (H_kv, page_size)
    block. page_size % 128: its LANE dim is page_size — Mosaic rejects
    the page DMA slice when it isn't lane-tile aligned (found by an
    on-chip A/B at ps=16). H_kv = 8 fills its minimum sublane tile.
    Serving configs for int8 KV want 128-token pages anyway (per-page
    DMA cost); smaller pages take the pure path."""
    return page_size % 128 == 0 and n_kv_heads == n_scale_heads == 8


def _fused_decode_q8_ok(B: int, page_size: int, max_pages: int, gd: int,
                        n_kv_heads: int, n_scale_heads: int) -> bool:
    """int8-KV fused decode kernel eligibility: the scale page's
    condition and a legal tile plan."""
    from llmq_tpu.ops.pallas.fused_decode import fused_kernel_viable
    return (_scale_pages_ok(page_size, n_kv_heads, n_scale_heads)
            and fused_kernel_viable(B, page_size, max_pages, gd, 1))


def _prefill_attn_q8_ok(rows_ok: bool, head_dim: int, page_size: int,
                        n_kv_heads: int, n_scale_heads: int) -> bool:
    """int8-KV prefill attention kernel eligibility: the bf16 kernel's
    condition and the scale page's."""
    return (_prefill_attn_ok(rows_ok, head_dim)
            and _scale_pages_ok(page_size, n_kv_heads, n_scale_heads))


def kernel_routes(*, batch: int, page_size: int, max_pages: int,
                  n_kv_heads: int, head_dim: int,
                  kv_itemsize: int, quant_kv: bool, enabled: bool,
                  multi_ok: bool, decode: bool = False,
                  prefill_rows: int = 0) -> dict:
    """Which implementation each attention op of ONE serving program
    takes at this geometry: ``{op: "pallas:<kernel function>" | "xla"}``;
    a fused decode kernel is followed by the plan it was built with
    and by what it is handed,
    ``(rows=<a tile>,chunk_tokens=<a chunk>,ordered)``: the step's rows
    in :func:`decode_order`'s order.
    Evaluates the very predicates the dispatchers below call at trace
    time (nested-jit trace caching makes a trace-time recorder miss
    programs), so the executor can log the decision where the program
    is built and chip_smoke.py can hold the compiled text against it.

    ``decode``: the program runs decode steps over ``batch`` rows;
    ``prefill_rows``: it runs prefill over that many rows (0 = none)."""
    gd = n_kv_heads * head_dim

    def pick(ok: bool, kernel: str) -> str:
        use, interp = _kernel_route(gd, extra_ok=ok, enabled=enabled)
        if not use:
            return "xla"
        return f"pallas{'-interpret' if interp else ''}:{kernel}"

    def fused(ok: bool, kernel: str, itemsize: int):
        route = pick(ok, kernel)
        if route == "xla":
            return route
        from llmq_tpu.ops.pallas.fused_decode import _tile_plan
        plan = _tile_plan(batch, page_size, max_pages, gd, itemsize)
        return (f"{route}(rows={plan.rows},"
                f"chunk_tokens={plan.chunk_tokens},ordered)")

    out = {}
    if prefill_rows:
        rows_ok = prefill_rows == 1 or multi_ok
        out["prefill_write"] = ("xla" if quant_kv
                                else pick(rows_ok, "_kv_prefill_kernel"))
        out["prefill_attention"] = (
            pick(_prefill_attn_q8_ok(rows_ok, head_dim, page_size,
                                     n_kv_heads, n_kv_heads),
                 "_prefill_attn_kernel_q8") if quant_kv
            else pick(_prefill_attn_ok(rows_ok, head_dim),
                      "_prefill_attn_kernel"))
    if decode:
        if quant_kv:
            route = fused(_fused_decode_q8_ok(batch, page_size, max_pages,
                                              gd, n_kv_heads, n_kv_heads),
                          "_fused_kernel_q8", 1)
            out["decode_write"] = out["decode_attention"] = route
        else:
            route = fused(_fused_decode_ok(batch, page_size, max_pages, gd,
                                           kv_itemsize),
                          "_fused_kernel", kv_itemsize)
            out["decode_attention"] = route
            out["decode_write"] = (route if route != "xla"
                                   else pick(True, "_kv_write_kernel"))
    return out


def fused_decode_route(B: int, pools, max_pages: int, head_dim: int,
                       enabled: bool):
    """``(use_kernel, interpret)`` of a decode step of ``B`` rows over
    ``pools`` — (k, v) or the int8 (k, v, k_scale, v_scale): what
    :func:`paged_decode_step` / :func:`paged_decode_step_q8` take and
    :func:`decode_order` is made for."""
    k_pool = pools[0]
    _, _, page_size, gd = k_pool.shape
    if len(pools) == 4:
        ok = _fused_decode_q8_ok(B, page_size, max_pages, gd,
                                 gd // head_dim, pools[2].shape[2])
    else:
        ok = _fused_decode_ok(B, page_size, max_pages, gd,
                              k_pool.dtype.itemsize)
    return _kernel_route(gd, enabled=enabled, extra_ok=ok)


class DecodeOrder(NamedTuple):
    """A decode step's rows in the order the fused kernel is handed
    them (:func:`decode_order`)."""
    rows: jnp.ndarray       # (B,) int32: the batch row at each place
    places: jnp.ndarray     # (B,) int32: each batch row's place


def rows_by_place(order: Optional[DecodeOrder], *per_row):
    """Arrays of one entry a batch row, laid out by place — as they
    came where no order was made (:func:`decode_order` gave None)."""
    if order is None:
        return per_row
    return tuple(x[order.rows] for x in per_row)


def _rows_back(order: Optional[DecodeOrder], by_place):
    """What the kernel computed by place, a batch row at a time again."""
    return by_place if order is None else by_place[order.places]


def decode_order(seq_lens, pools, max_pages: int, head_dim: int, *,
                 enabled: bool = True) -> Optional[DecodeOrder]:
    """The order in which a decode step's rows reach the fused decode
    kernel: longest context first, ``seq_len`` 0 last, ties by row — so
    the eight rows of a kernel tile end in the same chunks, and a step
    in which a tile's rows are all live is the rule (the kernel's
    design note, v5). Made ONCE a step from the ``seq_lens`` the step
    has, for all its layers (as ``ops/ssm.decode_walk`` is), and handed
    to :func:`paged_decode_step` / :func:`paged_decode_step_q8` with the
    step's ``block_tables``, ``seq_lens`` and ``page_of`` laid out by it
    (:func:`rows_by_place`, once a step too: they are every layer's);
    ``None`` where the step's attention is not the kernel's (off the
    TPU, under a mesh, at a geometry its predicate refuses): nothing is
    permuted there. A count of the rows that come before each row —
    B x B comparisons in one fusion, no sort — gives every row its
    place."""
    B = seq_lens.shape[0]
    if not fused_decode_route(B, pools, max_pages, head_dim, enabled)[0]:
        return None
    row = jnp.arange(B, dtype=jnp.int32)
    mine, other = seq_lens[:, None], seq_lens[None, :]
    before = (other > mine) | ((other == mine) & (row[None, :] < row[:, None]))
    places = jnp.sum(before, axis=1, dtype=jnp.int32)
    rows = jnp.sum(jnp.where(places[None, :] == row[:, None], row[None, :],
                             0), axis=1, dtype=jnp.int32)
    return DecodeOrder(rows, places)


def paged_kv_write(k_pool, v_pool, k_new, v_new, page_of, slot_of, layer,
                   *, distinct_pages: bool = False, enabled: bool = True):
    """Write N token rows into layer ``layer`` of the stacked pool.

    TPU + ``distinct_pages=True`` (decode: every live row targets its
    own page): Pallas page-RMW kernel with input/output aliasing — XLA
    scatter costs ~13µs/row on TPU regardless of row size and would
    dominate the whole decode step. Elsewhere (and for prefill, whose
    rows share pages): the .at[] scatter.
    Pools FLAT (L, P, page_size, H_kv·D); k_new/v_new (N, H_kv, D).
    """
    N = k_new.shape[0]
    kn = k_new.reshape(N, -1)
    vn = v_new.reshape(N, -1)
    use_kernel, interpret = _kernel_route(
        k_pool.shape[3], extra_ok=distinct_pages, enabled=enabled)
    if use_kernel:
        return _jit_kv_write()(k_pool, v_pool, kn, vn,
                               page_of, slot_of, layer,
                               interpret=interpret)
    k_pool = k_pool.at[layer, page_of, slot_of].set(kn)
    v_pool = v_pool.at[layer, page_of, slot_of].set(vn)
    return k_pool, v_pool


def paged_kv_write_prefill(k_pool, v_pool, k, v, block_tables, positions,
                           lengths, layer, *, enabled: bool = True,
                           multi_ok: bool = False):
    """Write a prefill chunk's KV (k/v: (B, T, H_kv, D)) into layer
    ``layer`` of the stacked pool.

    TPU kernel path (B == 1, or any B with the serving executor's
    ``multi_ok`` batched-prefill opt-in — row-looped aliased calls):
    Pallas page-RMW kernel — each chunk touches T/page_size contiguous
    pages, merged and written with two DMAs instead of T ~13µs scatter
    rows. The chunk's KV is first shifted into a page-aligned buffer
    (token t at row ``start%page_size + t``) with ONE contiguous
    dynamic-update-slice so the kernel only needs static block slices.
    Otherwise (general B, CPU, unaligned heads): an .at[] scatter with
    coordinates derived from the same block_tables/positions/lengths.
    """
    B, T = k.shape[0], k.shape[1]
    page_size = k_pool.shape[2]
    GD = k_pool.shape[3]
    # B > 1 only via the serving executor's batched-prefill opt-in
    # (multi_ok): the kernels have no VJP, and the B > 1 training path
    # must keep the differentiable fallback.
    use_kernel, interpret = _kernel_route(
        k_pool.shape[3], extra_ok=(B == 1 or multi_ok), enabled=enabled)
    if use_kernel:
        # The write kernel is per-sequence; B > 1 (batched prefill)
        # chains one aliased call per row through the pool — the dense
        # matmuls around this are what batching amortizes.
        fn = _jit_kv_prefill_write()
        n_wp = -(-T // page_size) + 1
        for b in range(B):
            start = positions[b, 0]
            n_tok = lengths[b]
            # Buffer must hold max_offset (page_size-1) + T rows,
            # rounded to whole pages — T//page_size + 1 under-allocates
            # for non-multiple buckets and dynamic_update_slice would
            # silently clamp.
            aligned_k = jnp.zeros((n_wp * page_size, GD), k.dtype)
            aligned_v = jnp.zeros((n_wp * page_size, GD), v.dtype)
            off = start % page_size
            aligned_k = jax.lax.dynamic_update_slice(
                aligned_k, k[b].reshape(T, GD), (off, 0))
            aligned_v = jax.lax.dynamic_update_slice(
                aligned_v, v[b].reshape(T, GD), (off, 0))
            k_pool, v_pool = fn(
                k_pool, v_pool, aligned_k, aligned_v, block_tables[b],
                start, n_tok, layer, interpret=interpret)
        return k_pool, v_pool
    # Scatter coordinates: padding rows (beyond lengths) → page 0.
    valid = (jnp.arange(T)[None, :] < lengths[:, None])     # (B, T)
    flat_valid = valid.reshape(-1)
    flat_pos = positions.reshape(-1)
    page_of = jnp.where(
        flat_valid,
        block_tables[jnp.repeat(jnp.arange(B), T), flat_pos // page_size],
        0)
    slot_of = jnp.where(flat_valid, flat_pos % page_size, 0)
    k_pool = k_pool.at[layer, page_of, slot_of].set(k.reshape(-1, GD))
    v_pool = v_pool.at[layer, page_of, slot_of].set(v.reshape(-1, GD))
    return k_pool, v_pool


def dispatch_prefill_attention(q, k_pool, v_pool, block_tables, positions,
                               seq_lens, layer, *, enabled: bool = True,
                               multi_ok: bool = False,
                               window=None) -> jnp.ndarray:
    """Prefill-chunk attention over the paged pool; q (B, T, H, D).
    ``window`` (static): a query sees its last ``window`` keys, itself
    counted; ``None``: all of them.

    TPU kernel path (B == 1, or any B with ``multi_ok`` — per-row
    kernel reads don't break the pool aliasing): Pallas paged prefill
    kernel reading the pool directly — an XLA gather between the
    layers' aliased KV-writes makes XLA insert full-pool defensive
    copies (measured 3-4x total prefill cost), and the gather also
    materializes the padded window. Without the opt-in, B > 1 (the
    differentiated training path — the kernels have no VJP) falls back
    to gather + blockwise online-softmax attention.

    CONTIGUITY REQUIREMENT (kernel path): ``positions`` rows must be
    contiguous — the kernel derives every q position as
    ``positions[b, 0] + row`` and ignores the rest of the array, while
    the fallback honors ``positions`` elementwise. The executor always
    passes contiguous chunks (padding rows past ``seq_lens`` are
    discarded); any caller with genuinely non-contiguous positions must
    set ``LLMQ_PALLAS=0`` or results will differ between TPU and CPU.
    """
    B, T = q.shape[0], q.shape[1]
    page_size = k_pool.shape[2]
    use_kernel, interpret = _kernel_route(
        k_pool.shape[3], enabled=enabled,
        extra_ok=_prefill_attn_ok(B == 1 or multi_ok, q.shape[3]))
    if use_kernel:
        # Per-sequence kernel, row-looped for batched prefill: pure
        # READS of the pool — B opaque kernel consumers don't make XLA
        # copy it (only a gather between aliased writes does).
        fn = _jit_prefill_attention()
        outs = [fn(q[b], k_pool, v_pool, block_tables[b],
                   positions[b, 0], layer, interpret=interpret,
                   window=window)
                for b in range(B)]
        return outs[0][None] if B == 1 else jnp.stack(outs)
    S = block_tables.shape[1] * page_size
    D = q.shape[3]
    Hkv = k_pool.shape[3] // D
    k_hist = k_pool[layer, block_tables].reshape(B, S, Hkv, D)
    v_hist = v_pool[layer, block_tables].reshape(B, S, Hkv, D)
    return blockwise_prefill_attention(q, k_hist, v_hist, positions,
                                       seq_lens, window=window)


def paged_decode_step(q, k_new, v_new, k_pool, v_pool, block_tables,
                      seq_lens, page_of, slot_of, layer, *,
                      enabled: bool = True, window=None, order=None):
    """One decode layer's KV write + attention, fused where possible.
    ``window`` (static): a row sees its last ``window`` keys, its
    current token counted; ``None``: all of them.

    TPU: ONE Pallas kernel does both — the current token's K/V is
    merged into the attention's own page fetch (in-register self-
    attention for the newest token) and the merged page is written back
    through the aliased pool, halving per-layer kernel launches and
    dropping the write kernel's separate page round-trip. Fallback:
    the row-RMW write kernel / scatter followed by pooled attention.

    ``order`` (:func:`decode_order` of this step's ``seq_lens``, or
    None): the kernel is handed the rows in that order. ``q``, the new
    rows and the attention returned are by batch row whatever the order
    (a gather each, here); ``block_tables``, ``seq_lens`` and
    ``page_of``, every layer's, come ALREADY by place.
    Returns (attn, k_pool, v_pool).
    """
    use_kernel, interpret = fused_decode_route(
        q.shape[0], (k_pool, v_pool), block_tables.shape[1], q.shape[2],
        enabled)
    assert order is None or use_kernel, "an order is the kernel's alone"
    if use_kernel:
        q, k_new, v_new = rows_by_place(order, q, k_new, v_new)
        attn, (k_pool, v_pool) = _jit_fused_decode()(
            q, k_new, v_new, k_pool, v_pool, block_tables, seq_lens,
            page_of, layer, interpret=interpret, window=window)
        return _rows_back(order, attn), k_pool, v_pool
    k_pool, v_pool = paged_kv_write(k_pool, v_pool, k_new, v_new,
                                    page_of, slot_of, layer,
                                    distinct_pages=True, enabled=enabled)
    attn = paged_decode_attention_pooled(q, k_pool, v_pool, block_tables,
                                         seq_lens, layer, window)
    return attn, k_pool, v_pool


def blockwise_prefill_attention(
    q: jnp.ndarray,          # (B, T, H, D)
    k_hist: jnp.ndarray,     # (B, S, H_kv, D)
    v_hist: jnp.ndarray,     # (B, S, H_kv, D)
    positions: jnp.ndarray,  # (B, T) absolute position of each query
    seq_lens: jnp.ndarray,   # (B,) visible history length
    *,
    block_size: int = 512,
    window=None,
) -> jnp.ndarray:
    """Prefill attention with online softmax over KV chunks.

    Same semantics as the full-logits version (mask: kv_pos <= q_pos and
    kv_pos < seq_len; under a ``window`` also kv_pos > q_pos - window)
    but peak memory is O(B·H·T·block_size) f32 instead
    of O(B·H·T·S) — the difference between GBs-per-layer and MBs at 8k
    context. ``lax.scan`` over chunks keeps one
    compiled body; XLA fuses mask+softmax into the chunk matmuls.
    """
    B, T, H, D = q.shape
    S = k_hist.shape[1]
    Hkv = k_hist.shape[2]
    n_rep = H // Hkv
    Sb = min(block_size, S)
    while S % Sb:
        Sb -= 1
    n_blocks = S // Sb
    scale = D ** -0.5
    qg = q.reshape(B, T, Hkv, n_rep, D)

    # (n_blocks, B, Sb, ...) leading-axis chunks for scan.
    k_c = jnp.moveaxis(k_hist.reshape(B, n_blocks, Sb, Hkv, D), 1, 0)
    v_c = jnp.moveaxis(v_hist.reshape(B, n_blocks, Sb, Hkv, D), 1, 0)

    def body(carry, xs):
        m_prev, l_prev, acc = carry                         # (B,T,g,r,·)
        i, k_b, v_b = xs
        logits = jnp.einsum("btgrd,bsgd->btgrs", qg, k_b,
                            preferred_element_type=jnp.float32) * scale
        kv_pos = i * Sb + jnp.arange(Sb)[None, :]           # (1, Sb)
        mask = ((kv_pos[:, None, :] <= positions[:, :, None])
                & (kv_pos[:, None, :] < seq_lens[:, None, None]))  # (B,T,Sb)
        if window is not None:
            mask = mask & (kv_pos[:, None, :]
                           > positions[:, :, None] - window)
        mask = mask[:, :, None, None, :]                    # (B,T,1,1,Sb)
        logits = jnp.where(mask, logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # Explicit zero for masked entries: a fully-masked chunk keeps
        # m_new at NEG_INF and exp(logits - m_new) would be exp(0)=1.
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "btgrs,bsgd->btgrd", p.astype(v_b.dtype), v_b,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, T, Hkv, n_rep, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, T, Hkv, n_rep, 1), jnp.float32)
    acc0 = jnp.zeros((B, T, Hkv, n_rep, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(n_blocks), k_c, v_c))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, T, H, D).astype(q.dtype)


# -- int8 KV cache paths -------------------------------------------------------
#
# Pool layout: data pools stay FLAT (L, P, page_size, H_kv·D) in int8;
# scale pools are (L, P, H_kv, page_size) bf16 (ops/quant.py rationale:
# H_kv = 8 fills the minimum sublane tile, and (head, position) is the
# logits layout, so kernels consume scales transpose-free). These
# functions mirror the bf16 paths one-for-one; ``pools`` is the 4-tuple
# (k_pool, v_pool, k_scale, v_scale).


def _scale_scatter(scale_pool, layer, page_of, slot_of, scales):
    """Write per-(row, head) scales (N, H_kv) at [layer, page_of[n], :,
    slot_of[n]]."""
    Hkv = scale_pool.shape[2]
    heads = jnp.arange(Hkv)
    return scale_pool.at[
        layer, page_of[:, None], heads[None, :], slot_of[:, None]
    ].set(scales.astype(scale_pool.dtype))


def _dequant_window(k_pool, scale_pool, layer, block_tables, D):
    """Gather + dequantize one layer's pages for a batch of block
    tables: returns (B, S, H_kv, D) bf16."""
    B, n_pages = block_tables.shape
    page_size = k_pool.shape[2]
    Hkv = k_pool.shape[3] // D
    S = n_pages * page_size
    qv = k_pool[layer, block_tables].reshape(
        B, n_pages, page_size, Hkv, D)
    sc = scale_pool[layer, block_tables]          # (B, n_pages, Hkv, ps)
    sc = jnp.moveaxis(sc, 2, 3)                   # (B, n_pages, ps, Hkv)
    x = qv.astype(jnp.float32) * sc.astype(jnp.float32)[..., None]
    return x.reshape(B, S, Hkv, D).astype(jnp.bfloat16)


def paged_decode_step_q8(q, k_new, v_new, pools, block_tables, seq_lens,
                         page_of, slot_of, layer, *, enabled: bool = True,
                         order=None):
    """One decode layer against the int8 KV pools: quantize the current
    token's K/V per (row, head), write rows + scales, attend over the
    dequantized paged history. ``order``: :func:`paged_decode_step`'s.
    Returns (attn, pools).

    TPU path: the int8 fused kernel (fused_decode.py) — same
    write+attend fusion as bf16, half the page DMA bytes. Fallback:
    scatter + gather-dequant + the shared GQA attention.
    """
    from llmq_tpu.ops.quant import quantize_kv_rows

    k_pool, v_pool, ks_pool, vs_pool = pools
    B, H, D = q.shape
    use_kernel, interpret = fused_decode_route(
        B, pools, block_tables.shape[1], D, enabled)
    assert order is None or use_kernel, "an order is the kernel's alone"
    kq, kscale = quantize_kv_rows(k_new)    # (B, Hkv, D) i8, (B, Hkv)
    vq, vscale = quantize_kv_rows(v_new)

    if use_kernel:
        # By place AFTER the rows are quantised: a gather in front of
        # it moves the fusion in which XLA rounds them (excess
        # precision) and with that int8 steps of the whole model; so,
        # the served 64-row step is the seated step's to the bit on the
        # chip (PERF.md §6, PR 43).
        q, kq, kscale, vq, vscale = rows_by_place(order, q, kq, kscale,
                                                  vq, vscale)
        attn, pools = _jit_fused_decode_q8()(
            q, kq, kscale, vq, vscale, pools, block_tables, seq_lens,
            page_of, layer, interpret=interpret)
        return _rows_back(order, attn), pools

    k_pool = k_pool.at[layer, page_of, slot_of].set(kq.reshape(B, -1))
    v_pool = v_pool.at[layer, page_of, slot_of].set(vq.reshape(B, -1))
    ks_pool = _scale_scatter(ks_pool, layer, page_of, slot_of, kscale)
    vs_pool = _scale_scatter(vs_pool, layer, page_of, slot_of, vscale)
    k = _dequant_window(k_pool, ks_pool, layer, block_tables, D)
    v = _dequant_window(v_pool, vs_pool, layer, block_tables, D)
    attn = _gqa_attend(q, k, v, seq_lens)
    return attn, (k_pool, v_pool, ks_pool, vs_pool)


def _jit_fused_decode_q8():
    def make():
        from llmq_tpu.ops.pallas.fused_decode import (
            fused_decode_attention_q8_pallas)
        return jax.jit(fused_decode_attention_q8_pallas,
                       static_argnames=("pages_per_chunk", "interpret"))
    return _kernel_jit("fused_decode_q8", make)


def paged_kv_write_prefill_q8(pools, k, v, block_tables, positions,
                              lengths, layer):
    """Prefill-chunk write into the int8 pools: quantize every (token,
    head) row and write rows + scales PAGE BY PAGE. k/v: (B, T, H_kv, D).

    A chunk's tokens are contiguous (``positions[b, 0] + t``, the
    convention of every prefill caller), so they cover ``T/page_size +
    1`` consecutive pages of the row's block table at most: those pages
    are read, the new rows and scale columns selected into them, and the
    pages written back — a few page-sized updates a pool where a scatter
    by token moved ``B·T`` rows a data pool and ``B·T·H_kv`` single
    elements a scale pool (477 µs a layer for Mistral's two 512-token
    slices, more than their attention: PERF.md §6, PR 33). Pure XLA and
    nothing aliased, so it also serves inside ``forward_prefill``'s
    rolled loop, on the CPU and under a mesh. A page slot past the
    chunk's valid tokens rewrites reserved page 0 with itself."""
    from llmq_tpu.ops.quant import quantize_kv_rows

    B, T = k.shape[0], k.shape[1]
    page_size, GD = pools[0].shape[2], pools[0].shape[3]
    max_pages = block_tables.shape[1]
    n_wp = -(-T // page_size) + 1          # pages a chunk can touch
    kq, kscale = quantize_kv_rows(k)       # (B, T, Hkv, D), (B, T, Hkv)
    vq, vscale = quantize_kv_rows(v)
    start = positions[:, 0]
    off = start % page_size
    # Which pages, and which of their slots the chunk fills.
    page_idx = start[:, None] // page_size + jnp.arange(n_wp)[None]  # (B, n)
    slot_pos = (page_idx[:, :, None] * page_size
                + jnp.arange(page_size)[None, None])                # (B, n, ps)
    fresh = ((slot_pos >= start[:, None, None])
             & (slot_pos < (start + lengths)[:, None, None]))
    live = fresh.any(axis=2) & (page_idx < max_pages)
    pid = jnp.where(live, jnp.take_along_axis(
        block_tables, jnp.clip(page_idx, 0, max_pages - 1), axis=1), 0)
    fresh = fresh & live[:, :, None]

    def paged(x):
        """(B, T, ...) → page-aligned (B, n_wp, page_size, ...): token t
        at slot ``off + t`` of the chunk's first page onwards."""
        pad = jnp.zeros((B, n_wp * page_size) + x.shape[2:], x.dtype)
        x = jax.vmap(lambda buf, rows, o: jax.lax.dynamic_update_slice_in_dim(
            buf, rows, o, 0))(pad, x, off)
        return x.reshape((B, n_wp, page_size) + x.shape[2:])

    out = []
    for i, new in enumerate((kq.reshape(B, T, GD), vq.reshape(B, T, GD),
                             kscale, vscale)):
        new = paged(new)                   # (B, n, ps, GD | Hkv)
        mask = fresh[..., None]
        if i >= 2:   # a scale page is (H_kv, page_size): slots on lanes
            new, mask = jnp.moveaxis(new, 2, 3), fresh[:, :, None, :]
        old = pools[i][layer, pid]         # (B, n) pages
        out.append(pools[i].at[layer, pid].set(
            jnp.where(mask, new.astype(old.dtype), old)))
    return tuple(out)


def _jit_prefill_attention_q8():
    def make():
        from llmq_tpu.ops.pallas.prefill_attention import (
            paged_prefill_attention_q8_pallas)
        return jax.jit(paged_prefill_attention_q8_pallas,
                       static_argnames=("pages_per_chunk", "q_block",
                                        "interpret"))
    return _kernel_jit("prefill_attention_q8", make)


def dispatch_prefill_attention_q8(q, pools, block_tables, positions,
                                  seq_lens, layer, *, enabled: bool = True,
                                  multi_ok: bool = False) -> jnp.ndarray:
    """Prefill-chunk attention over the int8 pools; q (B, T, H, D).

    TPU kernel path (the rows :func:`dispatch_prefill_attention` takes,
    at 128-token pages with eight KV heads): the int8 twin of the paged
    prefill kernel, row-looped, reading pages and scale pages as they
    lie and told each slice's valid length (``seq_lens`` less its first
    position), so its work follows the context and the slice — pure
    READS of the pools, as the bf16 kernel's. Same CONTIGUITY
    REQUIREMENT on ``positions``. Otherwise: gather + dequantize the
    block table's whole window, then the blockwise online-softmax."""
    k_pool, v_pool, ks_pool, vs_pool = pools
    B, D = q.shape[0], q.shape[3]
    use_kernel, interpret = _kernel_route(
        k_pool.shape[3], enabled=enabled,
        extra_ok=_prefill_attn_q8_ok(B == 1 or multi_ok, D,
                                     k_pool.shape[2], k_pool.shape[3] // D,
                                     ks_pool.shape[2]))
    if use_kernel:
        fn = _jit_prefill_attention_q8()
        outs = [fn(q[b], pools, block_tables[b], positions[b, 0],
                   seq_lens[b] - positions[b, 0], layer,
                   interpret=interpret)
                for b in range(B)]
        return outs[0][None] if B == 1 else jnp.stack(outs)
    k_hist = _dequant_window(k_pool, ks_pool, layer, block_tables, D)
    v_hist = _dequant_window(v_pool, vs_pool, layer, block_tables, D)
    return blockwise_prefill_attention(q, k_hist, v_hist, positions,
                                       seq_lens)


def decode_geometry(positions, block_tables, active, pools, head_dim: int,
                    *, enabled: bool = True):
    """What the paged GQA layers of a family whose hidden rows STAY BY
    BATCH ROW (its other layers keep state by row) need of a decode
    step's rows, with no slabs beside the pool: ``(block_tables,
    page_of, slot_of, seq_lens, order)`` — ``page_of`` 0 for a row that
    is not active (it writes to page 0), ``seq_lens`` 0 for it (it
    attends to nothing), and the ``order`` the attention kernel wants
    its rows in (:func:`decode_order`, made here ONCE for the step's
    layers, with the table, ``page_of`` and ``seq_lens`` laid out by
    it; None where the kernel does not serve). ``pools``: the layers'
    ``(k, v)`` pools ``(L, P, page_size, ...)``."""
    B = positions.shape[0]
    ps = pools[0].shape[2]
    live = jnp.ones((B,), bool) if active is None else active
    page_of = jnp.where(
        live, block_tables[jnp.arange(B), positions // ps], 0)
    seq_lens = jnp.where(live, positions + 1, 0)
    order = decode_order(seq_lens, pools, block_tables.shape[1], head_dim,
                         enabled=enabled)
    seq_lens, block_tables, page_of = rows_by_place(order, seq_lens,
                                                    block_tables, page_of)
    return block_tables, page_of, positions % ps, seq_lens, order
