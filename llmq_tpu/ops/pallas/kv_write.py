"""Pallas TPU kernel: paged KV-cache write (decode path).

XLA's scatter on TPU costs ~13µs per updated row regardless of row size
(measured on v5e: 512 rows ≈ 7-11 ms — as slow as the rest of the
decode step combined). Serving writes one (H_kv·D)-sized row per
sequence per layer per step, so the scatter is pure per-index overhead.
vLLM's TPU backend ships a dedicated kv-cache-update kernel for the
same reason.

Mosaic constrains DMA granularity to the (8, 128) tile (a lone
(1, H_kv·D) row is not a legal slice on either side of a copy), so the
kernel works at **page granularity — read, modify, write**:

    for each row i:  page = pool[layer, page_of[i]]       (DMA → VMEM)
                     page[slot_of[i]] = new_row_i          (vector select)
                     pool[layer, page_of[i]] = page        (DMA → HBM)

double-buffered across rows, with **input/output aliasing** so the pool
is updated in place. A page round-trip is 2·page_size·GD bytes — for
B=32, 16 layers that's ~2 MB/step, noise next to the weight traffic.

CORRECTNESS CONSTRAINT (row kernel): all live rows in one call must
target **distinct pages** (their RMWs are concurrent). Decode satisfies
this by construction — each sequence owns its pages; inactive rows all
target reserved page 0, whose content is never read. Prefill writes
many slots of the same page and uses the second kernel in this module
(``kv_prefill_write_pallas``): the chunk's contiguous token range is
shifted into a page-aligned buffer and each touched page is merged and
written exactly once.

The new rows arrive as a whole (N, GD) VMEM block; row i is extracted
with an iota-mask reduction (dynamic sublane indexing is as illegal as
dynamic DMA rows — a masked sum over ≤64 sublanes is cheap VPU work).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kv_write_kernel(
    # scalar prefetch (SMEM)
    page_of_ref,     # (N,) int32
    slot_of_ref,     # (N,) int32
    layer_ref,       # (1,) int32
    # inputs
    k_new_ref,       # (N_pad, GD) VMEM
    v_new_ref,       # (N_pad, GD) VMEM
    k_hbm,           # (L, P, page_size, GD) ANY — aliased to output 0
    v_hbm,           # (L, P, page_size, GD) ANY — aliased to output 1
    # outputs (same buffers via input_output_aliases; DMAs target these)
    k_out,
    v_out,
    # scratch
    k_page,          # (2, page_size, GD) VMEM — double-buffered pages
    v_page,          # (2, page_size, GD) VMEM
    sem,             # DMA semaphores (2, 2)
    *,
    n_rows: int,
    page_size: int,
):
    """Single-program grid: loop rows with a 2-deep fetch pipeline."""
    lyr = layer_ref[0]
    n_pad = k_new_ref.shape[0]

    def fetch(i, slot):
        @pl.when(i < n_rows)
        def _():
            p = page_of_ref[i]
            pltpu.make_async_copy(
                k_hbm.at[lyr, p], k_page.at[slot], sem.at[0, slot]).start()
            pltpu.make_async_copy(
                v_hbm.at[lyr, p], v_page.at[slot], sem.at[1, slot]).start()

    fetch(0, 0)

    def select_row(new_ref, i):
        # Row i of the (N_pad, GD) block via mask-reduce (no dynamic
        # sublane indexing).
        rows = jax.lax.broadcasted_iota(jnp.int32, (n_pad, 1), 0)
        m = (rows == i).astype(jnp.float32)
        return jnp.sum(new_ref[...].astype(jnp.float32) * m,
                       axis=0, keepdims=True)                # (1, GD)

    def body(i, _):
        slot = jax.lax.rem(i, 2)
        fetch(i + 1, 1 - slot)
        p = page_of_ref[i]
        s = slot_of_ref[i]
        pltpu.make_async_copy(
            k_hbm.at[lyr, p], k_page.at[slot], sem.at[0, slot]).wait()
        pltpu.make_async_copy(
            v_hbm.at[lyr, p], v_page.at[slot], sem.at[1, slot]).wait()

        sl = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
        keep = sl != s                                        # (ps, 1)
        k_row = select_row(k_new_ref, i).astype(k_page.dtype)  # (1, GD)
        v_row = select_row(v_new_ref, i).astype(v_page.dtype)
        k_page[slot] = jnp.where(keep, k_page[slot], k_row)
        v_page[slot] = jnp.where(keep, v_page[slot], v_row)

        pltpu.make_async_copy(
            k_page.at[slot], k_out.at[lyr, p], sem.at[0, slot]).start()
        pltpu.make_async_copy(
            v_page.at[slot], v_out.at[lyr, p], sem.at[1, slot]).start()
        pltpu.make_async_copy(
            k_page.at[slot], k_out.at[lyr, p], sem.at[0, slot]).wait()
        pltpu.make_async_copy(
            v_page.at[slot], v_out.at[lyr, p], sem.at[1, slot]).wait()
        return 0

    jax.lax.fori_loop(0, n_rows, body, 0)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def kv_cache_write_pallas(
    k_pool: jnp.ndarray,      # (L, P, page_size, H_kv·D) FLAT
    v_pool: jnp.ndarray,
    k_new: jnp.ndarray,       # (N, H_kv·D) — one DISTINCT page per row
    v_new: jnp.ndarray,
    page_of: jnp.ndarray,     # (N,) int32
    slot_of: jnp.ndarray,     # (N,) int32
    layer: jnp.ndarray | int = 0,
    *,
    interpret: bool = False,
):
    """Write N token rows (distinct pages!) into the pool in place.
    Returns the updated (k_pool, v_pool) — the same buffers, aliased."""
    L, P, page_size, GD = k_pool.shape
    N = k_new.shape[0]
    if GD % 128:
        raise ValueError(f"H_kv*D = {GD} must be a multiple of 128")

    kernel = functools.partial(_kv_write_kernel, n_rows=N,
                               page_size=page_size)
    n_pad = _round_up(N, 8)                     # sublane-aligned block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n_pad, GD), lambda c, *_: (0, 0)),
            pl.BlockSpec((n_pad, GD), lambda c, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, page_size, GD), k_pool.dtype),
            pltpu.VMEM((2, page_size, GD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kn = jnp.pad(k_new.reshape(N, GD), ((0, n_pad - N), (0, 0))
                 ).astype(k_pool.dtype)
    vn = jnp.pad(v_new.reshape(N, GD), ((0, n_pad - N), (0, 0))
                 ).astype(v_pool.dtype)
    # Operand order: 3 scalar-prefetch args, then kn, vn, kf, vf →
    # aliased operand indices 5/6 onto outputs 0/1.
    k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_of.astype(jnp.int32), slot_of.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      kn, vn, k_pool, v_pool)
    return (k_out, v_out)


def _kv_prefill_kernel(
    # scalar prefetch (SMEM)
    block_table_ref,  # (max_pages,) int32 — the sequence's block table
    meta_ref,         # (3,) int32 — [start_pos, n_tokens, layer]
    # inputs
    k_new_ref,        # (n_wp·ps, GD) VMEM — page-ALIGNED chunk KV
    v_new_ref,        # (n_wp·ps, GD) VMEM
    k_hbm,            # (L, P, page_size, GD) ANY — aliased to output 0
    v_hbm,            # (L, P, page_size, GD) ANY — aliased to output 1
    # outputs (aliased buffers; DMAs target these)
    k_out,
    v_out,
    # scratch
    k_page,           # (page_size, GD) VMEM (partial-page RMW)
    v_page,           # (page_size, GD) VMEM
    sem,              # DMA semaphores (2, n_wp)
    rmw_sem,          # DMA semaphores (2,)
    *,
    page_size: int,
    max_pages: int,
    n_wp: int,
):
    """Static unroll over the chunk's pages. Fully-covered pages (the
    common case — all but the ≤2 edge pages of a chunk) are written
    with one direct async DMA each, ALL in flight concurrently; partial
    edge pages do a serial fetch-merge-write so pre-existing slots
    (continuation prefill) survive. Every page in a call is distinct
    (consecutive block-table entries), so the writes can't race."""
    start = meta_ref[0]
    n_tok = meta_ref[1]
    lyr = meta_ref[2]

    def page_coords(j):
        page_idx = start // page_size + j
        in_table = page_idx < max_pages
        pid = jnp.where(
            in_table, block_table_ref[jnp.where(in_table, page_idx, 0)], 0)
        page_lo = page_idx * page_size
        write_lo = jnp.maximum(start, page_lo)
        write_hi = jnp.minimum(start + n_tok, page_lo + page_size)
        full = jnp.logical_and(write_lo == page_lo,
                               write_hi == page_lo + page_size)
        return pid, page_lo, write_lo, write_hi, full

    # Phase 1: kick off every full page's direct write.
    for j in range(n_wp):  # static unroll
        pid, _, write_lo, write_hi, full = page_coords(j)

        @pl.when(full)
        def _():
            pltpu.make_async_copy(
                k_new_ref.at[pl.ds(j * page_size, page_size)],
                k_out.at[lyr, pid], sem.at[0, j]).start()
            pltpu.make_async_copy(
                v_new_ref.at[pl.ds(j * page_size, page_size)],
                v_out.at[lyr, pid], sem.at[1, j]).start()

    # Phase 2: RMW the partial pages (serial; at most 2 per chunk).
    for j in range(n_wp):
        pid, page_lo, write_lo, write_hi, full = page_coords(j)
        partial_pg = jnp.logical_and(write_lo < write_hi,
                                     jnp.logical_not(full))

        @pl.when(partial_pg)
        def _():
            pltpu.make_async_copy(k_hbm.at[lyr, pid], k_page,
                                  rmw_sem.at[0]).start()
            pltpu.make_async_copy(v_hbm.at[lyr, pid], v_page,
                                  rmw_sem.at[1]).start()
            pltpu.make_async_copy(k_hbm.at[lyr, pid], k_page,
                                  rmw_sem.at[0]).wait()
            pltpu.make_async_copy(v_hbm.at[lyr, pid], v_page,
                                  rmw_sem.at[1]).wait()

            sl = page_lo + jax.lax.broadcasted_iota(
                jnp.int32, (page_size, 1), 0)              # absolute pos
            fresh = jnp.logical_and(sl >= write_lo, sl < write_hi)
            k_page[...] = jnp.where(
                fresh, k_new_ref[pl.ds(j * page_size, page_size)],
                k_page[...])
            v_page[...] = jnp.where(
                fresh, v_new_ref[pl.ds(j * page_size, page_size)],
                v_page[...])

            pltpu.make_async_copy(k_page, k_out.at[lyr, pid],
                                  rmw_sem.at[0]).start()
            pltpu.make_async_copy(v_page, v_out.at[lyr, pid],
                                  rmw_sem.at[1]).start()
            pltpu.make_async_copy(k_page, k_out.at[lyr, pid],
                                  rmw_sem.at[0]).wait()
            pltpu.make_async_copy(v_page, v_out.at[lyr, pid],
                                  rmw_sem.at[1]).wait()

    # Phase 3: drain the full-page writes.
    for j in range(n_wp):
        pid, _, _, _, full = page_coords(j)

        @pl.when(full)
        def _():
            pltpu.make_async_copy(
                k_new_ref.at[pl.ds(j * page_size, page_size)],
                k_out.at[lyr, pid], sem.at[0, j]).wait()
            pltpu.make_async_copy(
                v_new_ref.at[pl.ds(j * page_size, page_size)],
                v_out.at[lyr, pid], sem.at[1, j]).wait()


def kv_prefill_write_pallas(
    k_pool: jnp.ndarray,       # (L, P, page_size, H_kv·D) FLAT
    v_pool: jnp.ndarray,
    k_aligned: jnp.ndarray,    # (n_wp·page_size, H_kv·D), page-aligned
    v_aligned: jnp.ndarray,
    block_table: jnp.ndarray,  # (max_pages,) int32
    start_pos: jnp.ndarray,    # scalar int32 — absolute pos of token 0
    n_tokens: jnp.ndarray,     # scalar int32 — valid tokens in the chunk
    layer: jnp.ndarray | int = 0,
    *,
    interpret: bool = False,
):
    """Write a prefill chunk's KV into the pool in place (page RMW).

    ``k_aligned`` must hold token t at row ``start_pos % page_size + t``
    (leading rows are don't-care) — one contiguous dynamic-update-slice
    for the caller, static page-block slicing for the kernel.
    """
    L, P, page_size, GD = k_pool.shape
    if GD % 128:
        raise ValueError(f"H_kv*D = {GD} must be a multiple of 128")
    n_wp = k_aligned.shape[0] // page_size
    max_pages = block_table.shape[0]

    kernel = functools.partial(_kv_prefill_kernel, page_size=page_size,
                               max_pages=max_pages, n_wp=n_wp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),     # single program; pages statically unrolled inside
        in_specs=[
            pl.BlockSpec((n_wp * page_size, GD), lambda c, *_: (0, 0)),
            pl.BlockSpec((n_wp * page_size, GD), lambda c, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((page_size, GD), k_pool.dtype),
            pltpu.VMEM((page_size, GD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, n_wp)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    meta = jnp.stack([jnp.asarray(start_pos, jnp.int32),
                      jnp.asarray(n_tokens, jnp.int32),
                      jnp.asarray(layer, jnp.int32)])
    k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table.astype(jnp.int32), meta,
      k_aligned.reshape(-1, GD).astype(k_pool.dtype),
      v_aligned.reshape(-1, GD).astype(v_pool.dtype),
      k_pool, v_pool)
    return (k_out, v_out)
