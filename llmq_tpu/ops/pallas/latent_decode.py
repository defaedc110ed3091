"""Pallas TPU kernels for a LATENT page pool (DeepSeek-V3's MLA): the
decode-step write of one latent row a sequence, and decode attention in
the absorbed form over the cached latents.

The pool is ONE leaf ``(L, P, page_size, W)``: a token's row holds its
normalised latent ``c`` (``kv_lora_rank`` values), its RoPE key (one
for all heads) and zeros up to ``W``, a multiple of 128 lanes — a page
is then one lane-aligned DMA, and a score is ONE contraction of the
row with ``[q~ | q_rope | 0]`` (models/deepseek_v3.py has the layout's
reckoning). All heads of a sequence read the same rows: the "KV head"
is one, ``W`` wide, under every query head.

``latent_decode_attention_pallas`` — the unit of work is a live chunk
of a live row, as ``fused_decode.py`` v4 has it: the grid is the batch
rows; a row loops over ITS chunks of ``pages_per_chunk`` pages (a
``fori_loop`` up to the row's last live chunk, so the steps follow
``seq_lens`` and not the block table's width), fetching each page by a
manual DMA into one of two scratch slots. Every chunk starts the next
chunk's DMAs before it waits for its own, across rows too (the slot's
parity is a consumed-chunk counter in SMEM, which outlives a grid
step; a row tells its successor through an SMEM flag that its first
chunk is already on its way). A dead row (``seq_len`` 0) costs a few
scalar reads and writes zeros. Online softmax in float32; masked
logits are SELECTED to -1e30 and the rows of a chunk past the
sequence's end are zeroed before the second product (0 x NaN of stale
scratch is NaN).

``latent_write_pallas`` — one new row a sequence, the pool aliased in
place. A row moves the SUBLANE TILE of its page that holds its slot
``s`` — rows ``[s // R * R, s // R * R + R)``, ``R`` the pool dtype's
rows a packed tile (``tile_rows``: 16 of bf16), the smallest aligned
piece a DMA moves: 20 KB each way at W = 640 where the whole page is
164 KB — read into a scratch slot, the new row selected in, written
back. The rows go round a ring of ``2 * WRITE_AHEAD`` slots: a read is
started ``WRITE_AHEAD`` rows before its merge and a write-back is
waited for when its slot is next read into, so a DMA's latency is paid
once a call and not once a row. A pool whose ``page_size`` is no
multiple of ``R`` moves the whole page instead (``write_rows``; no
served configuration is such a pool). Live rows target distinct pages
(the decode invariant); rows that are not live all target reserved
page 0, which is nobody's: two of them in one tile may lose each
other's bytes there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
#: Tokens a chunk aims at: wide enough that a chunk's two products
#: outweigh its fixed cost, small enough that two slots of it are a
#: megabyte of VMEM at W = 640.
CHUNK_TOKENS = 512
#: Reads the decode write keeps in flight ahead of the row it merges
#: (twice as many scratch slots: a slot's write-back has that many rows
#: to land before the slot is read into again). The best of 1 .. 64
#: tried on the chip at 128 and at 64 rows (PERF.md §6, PR 51).
WRITE_AHEAD = 16


def pages_per_chunk(page_size: int, max_pages: int) -> int:
    return max(1, min(max_pages, CHUNK_TOKENS // page_size))


def tile_rows(dtype) -> int:
    """Rows of one packed sublane tile: eight sublanes of 32 bits (16
    rows of bf16), the smallest aligned piece of a page a DMA moves."""
    return 32 // jnp.dtype(dtype).itemsize


def write_rows(pool) -> int:
    """Rows of its page a decode row's write moves: the sublane tile
    that holds the new row where the page is whole tiles, else the
    page."""
    page_size, R = pool.shape[2], tile_rows(pool.dtype)
    return R if page_size % R == 0 else page_size


def _latent_write_kernel(page_of_ref, slot_of_ref, layer_ref, new_ref,
                         pool_hbm, pool_out, piece, sem, *, n_rows: int,
                         rows: int, ahead: int):
    """Row ``i`` reads the ``rows`` rows of its page around its slot
    into scratch slot ``i % (2 * ahead)``, selects the new row in, and
    writes them back. ``ahead`` reads are in flight ahead of the row
    being merged, and a write-back is waited for when its scratch slot
    is next read into, ``ahead`` rows later (at the end, what is still
    on its way)."""
    lyr = layer_ref[0]
    slots = 2 * ahead

    def copy(i, back: bool):
        """Row ``i``'s piece of its page into its scratch slot, or
        ``back`` to the pool."""
        k = jax.lax.rem(i, slots)
        lo = pl.multiple_of(slot_of_ref[i] // rows * rows, rows)
        there = (pool_out if back else pool_hbm).at[
            lyr, page_of_ref[i], pl.ds(lo, rows)]
        src, dst = (piece.at[k], there) if back else (there, piece.at[k])
        return pltpu.make_async_copy(src, dst, sem.at[int(back), k])

    for i in range(min(ahead, n_rows)):
        copy(i, False).start()

    def body(i, _):
        @pl.when(i + ahead < n_rows)
        def _():
            @pl.when(i >= ahead)
            def _():
                copy(i - ahead, True).wait()
            copy(i + ahead, False).start()

        copy(i, False).wait()
        k = jax.lax.rem(i, slots)
        row = new_ref[pl.ds(i, 1), :].astype(piece.dtype)
        at = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        piece[k] = jnp.where(at == jax.lax.rem(slot_of_ref[i], rows), row,
                             piece[k])
        copy(i, True).start()
        return 0

    jax.lax.fori_loop(0, n_rows, body, 0)
    for i in range(max(0, n_rows - slots), n_rows):
        copy(i, True).wait()


def latent_write_pallas(pool: jnp.ndarray, new: jnp.ndarray,
                        page_of: jnp.ndarray, slot_of: jnp.ndarray,
                        layer: jnp.ndarray | int = 0, *,
                        interpret: bool = False) -> jnp.ndarray:
    """Write ``new`` (N, W), one row a sequence, into layer ``layer`` of
    ``pool`` (L, P, page_size, W) at ``(page_of, slot_of)``, in place:
    what ``pool.at[layer, page_of, slot_of].set(new)`` stores, by moving
    ``write_rows(pool)`` rows of each page. Live rows must target
    distinct pages; page 0 is nobody's (rows aimed at one tile of it may
    lose one another's bytes)."""
    _L, _P, page_size, W = pool.shape
    N = new.shape[0]
    if W % 128 or page_size % 8:
        raise ValueError(f"latent pool needs W % 128 == 0 and "
                         f"page_size % 8 == 0, got {W}, {page_size}")
    rows = write_rows(pool)
    n_pad = -(-N // 8) * 8
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(1,),
        in_specs=[pl.BlockSpec((n_pad, W), lambda c, *_: (0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((2 * WRITE_AHEAD, rows, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2 * WRITE_AHEAD))])
    # The new rows as float32 (every pool dtype's values are exact
    # there): a row is then ONE dynamic sublane load, which a packed
    # dtype does not have.
    return pl.pallas_call(
        functools.partial(_latent_write_kernel, n_rows=N, rows=rows,
                          ahead=WRITE_AHEAD),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_of.astype(jnp.int32), slot_of.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.pad(new.astype(pool.dtype).astype(jnp.float32),
              ((0, n_pad - N), (0, 0))), pool)


def _latent_decode_kernel(bt_ref, lens_ref, layer_ref, q_ref, pool_hbm,
                          o_ref, buf, sem, cnt, pref, *, page_size: int,
                          ppc: int, rank: int):
    b = pl.program_id(0)
    B = pl.num_programs(0)
    lyr = layer_ref[0]
    n = lens_ref[b]
    tc = ppc * page_size
    n_pages = (n + page_size - 1) // page_size
    n_chunks = (n + tc - 1) // tc

    def copies(row, c, slot, pages_live, go):
        """Start (``go``) or wait for the DMAs of chunk ``c`` of
        ``row``: the same predicate, so each started copy is waited."""
        for p in range(ppc):
            j = c * ppc + p

            @pl.when(j < pages_live)
            def _():
                cp = pltpu.make_async_copy(
                    pool_hbm.at[lyr, bt_ref[row, j]],
                    buf.at[slot, pl.ds(p * page_size, page_size)],
                    sem.at[slot])
                cp.start() if go else cp.wait()

    @pl.when(b == 0)
    def _():
        cnt[0] = 0
        pref[0] = 0

    nxt = jnp.minimum(b + 1, B - 1)
    n_next = jnp.where(b + 1 < B, lens_ref[nxt], 0)
    next_pages = (n_next + page_size - 1) // page_size

    @pl.when(n == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        pref[0] = 0

    @pl.when(n > 0)
    def _():
        @pl.when(pref[0] == 0)
        def _():
            copies(b, 0, jax.lax.rem(cnt[0], 2), n_pages, True)

        q = q_ref[0]                                       # (H, W)
        H = q.shape[0]

        def chunk(c, carry):
            m, l, acc = carry
            slot = jax.lax.rem(cnt[0], 2)

            @pl.when(c + 1 < n_chunks)
            def _():
                copies(b, c + 1, 1 - slot, n_pages, True)

            @pl.when((c + 1 == n_chunks) & (n_next > 0))
            def _():
                copies(nxt, 0, 1 - slot, next_pages, True)

            copies(b, c, slot, n_pages, False)
            kv = buf[slot]                                 # (tc, W)
            s = jax.lax.dot_general(
                q, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # (H, tc)
            pos = c * tc + jax.lax.broadcasted_iota(jnp.int32, (1, tc), 1)
            s = jnp.where(pos < n, s, NEG)
            m_new = jnp.maximum(jnp.maximum(
                m, jnp.max(s, axis=1, keepdims=True)), -1e29)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            rows = c * tc + jax.lax.broadcasted_iota(jnp.int32, (tc, 1), 0)
            c_lat = jnp.where(rows < n, kv[:, :rank], 0)
            acc = alpha * acc + jnp.dot(
                p.astype(kv.dtype), c_lat,
                preferred_element_type=jnp.float32)        # (H, rank)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            cnt[0] = cnt[0] + 1
            return m_new, l, acc

        m0 = jnp.full((H, 1), NEG, jnp.float32)
        l0 = jnp.zeros((H, 1), jnp.float32)
        a0 = jnp.zeros((H, rank), jnp.float32)
        _m, l, acc = jax.lax.fori_loop(0, n_chunks, chunk, (m0, l0, a0))
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        pref[0] = (n_next > 0).astype(jnp.int32)


def latent_decode_attention_pallas(q: jnp.ndarray, pool: jnp.ndarray,
                                   block_tables: jnp.ndarray,
                                   seq_lens: jnp.ndarray,
                                   layer: jnp.ndarray | int, *, rank: int,
                                   interpret: bool = False) -> jnp.ndarray:
    """softmax(q . rows) over each sequence's first ``seq_lens`` cached
    rows, times their first ``rank`` values.

    q (B, H, W): ``[q~ * scale | q_rope * scale | 0]`` a head;
    pool (L, P, page_size, W); block_tables (B, max_pages);
    seq_lens (B,): 0 marks a dead row (its output is 0). The current
    token's row is already in the pool. Returns (B, H, rank) float32."""
    B, H, W = q.shape
    _L, _P, page_size, Wp = pool.shape
    max_pages = block_tables.shape[1]
    if W != Wp or W % 128 or rank % 128 or page_size % 8:
        raise ValueError(f"latent decode: q {q.shape} over pool "
                         f"{pool.shape}, rank {rank}")
    ppc = pages_per_chunk(page_size, max_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppc * page_size, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32)])
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, page_size=page_size,
                          ppc=ppc, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.astype(pool.dtype), pool)
