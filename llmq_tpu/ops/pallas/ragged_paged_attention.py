"""Pallas TPU kernel: RAGGED paged attention — one launch for a mixed
prefill+decode batch (PAPERS.md "Ragged Paged Attention", arxiv
2604.15464).

The mixed step previously launched, per layer: one row-looped prefill
attention kernel per slice (per-bucket programs), one KV-write kernel
per slice, and one fused decode kernel — (2S + 1) launches stitched
together by the (S, T) mixed geometry grid. This kernel takes the whole
ragged batch — B decode rows (q_len = 1) and up to S prefill slices of
VARIABLE length packed into one token buffer — in ONE launch over the
shared paged KV pool, with per-row (q_start, q_len, kv_len) descriptors
instead of bucket padding. A 100-token slice and 63 decode rows cost
exactly their live pages.

Grid: ``(n_dec_tiles + n_pf_blocks, num_chunks)``, chunks minor.

- Grid rows ``[0, NT)`` are **decode tiles** — the proven fused-decode
  v3 machinery verbatim (fused_decode.py): R-row tiles with per-lane
  block tables, cross-pair double-buffered page DMAs chained through a
  consumed-fetch counter in SMEM, block-diagonal GQA q built in VMEM,
  tile-sliced merge of the current token into its fetched page with an
  8-sublane writeback (attention + KV write stay FUSED).
- Grid rows ``[NT, NT + NB)`` are **slice q-blocks** — the proven
  prefill machinery (prefill_attention.py): ``qblk`` query tokens ×
  H block-diagonal rows against the owner slice's pages, causal
  visibility from the descriptors. Each q-block is mapped to its owning
  slice by a scalar-prefetched ``owner`` table (the packed q buffer is
  ragged: slices occupy back-to-back qblk-aligned segments, so block
  ownership is data, not shape). Dead blocks (beyond the packed
  payload) skip every DMA and flush zeros.

Both halves share one online f32 softmax shape, one chunk width
(``ppc`` pages) and the scalar-prefetched descriptor tables:
``block_tables``/``seq_lens`` carry B decode rows then S slice rows.

The int8 variant fuses KV dequantization in-kernel: scale pools ride
as extra page leaves fetched next to their data pages, K scales
multiply logits group-wise and V scales fold into the probabilities at
the VMEM edge — the 8B int8 path stops round-tripping dequantized
pages through HBM (the old prefill-side gather+dequant materialized
the full bf16 window per slice per layer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

_CONSUMED = 0   # SMEM state: decode fetches consumed (slot parity)


def _ragged_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,   # (B+S, MP) int32 — decode rows then slice rows
    seq_lens_ref,       # (B+S,) int32 — decode: pos+1; slice: qstart+qlen
    write_page_ref,     # (B,) int32 — decode rows' current-token page
    pf_meta_ref,        # (S, 3) int32 — [qoff, qlen, qstart] per slice
    owner_ref,          # (NB,) int32 — owning slice per q-block; -1 dead
    layer_ref,          # (1,) int32
    # inputs
    q_dec_ref,          # (R, H, D) VMEM — raw decode q (bd built in VMEM)
    k_new_ref,          # (R, GD) VMEM — decode rows' current K
    v_new_ref,          # (R, GD) VMEM
    bias_ref,           # (R, 1, 8, Sc) bf16 — decode liveness bias
    q_pf_ref,           # (qblk·H, GD) VMEM — slice q-block, block-diag
    k_hbm, v_hbm,       # (L, P, ps, GD) ANY — aliased to outputs
    # outputs
    out_dec_ref,        # (R, H, D) VMEM
    out_pf_ref,         # (qblk·H, GD) VMEM
    k_out, v_out,       # aliased pools
    # scratch
    m_d, l_d, acc_d,    # (R,H,1),(R,H,1),(R,H,GD) f32 — decode softmax
    qbd_ref,            # (R, H, GD) — block-diag decode q
    kd_s, vd_s,         # (2, R, ppc, ps, GD) — decode page scratch
    m_p, l_p, acc_p,    # (qblk·H,1),(qblk·H,1),(qblk·H,GD) f32 — slices
    kp_s, vp_s,         # (2, ppc, ps, GD) — slice page scratch
    state,              # SMEM (1,) int32
    sem_d,              # DMA (2, 2) — decode fetches [pool, slot]
    wsem,               # DMA (2, R) — decode writebacks [pool, lane]
    sem_p,              # DMA (2, 2, ppc) — slice fetches
    *,
    rows_per_tile: int,
    pages_per_chunk: int,
    page_size: int,
    num_chunks: int,
    n_dec_tiles: int,
    n_pf_blocks: int,
    q_block: int,       # qblk — slice tokens per grid row
    batch: int,
    n_heads: int,
    n_rep: int,
    scale: float,
):
    r = pl.program_id(0)
    c = pl.program_id(1)
    R = rows_per_tile
    ppc = pages_per_chunk
    chunk_tokens = ppc * page_size
    NT = n_dec_tiles
    H = n_heads
    lyr = layer_ref[0]

    # ---- decode half: fused_decode v3 machinery, tiles 0..NT-1 ----------

    def drow(tile, lane):
        # Clamped lane→row map: tile index is the GRID row, which runs
        # past NT on slice rows — every unconditional descriptor read
        # must stay in bounds.
        return jnp.minimum(tile * R + lane, batch - 1)

    def row_c_last(tile, lane):
        eff = jnp.maximum(seq_lens_ref[drow(tile, lane)], 1)
        return (eff - 1) // chunk_tokens

    def tile_c_last(tile):
        m = row_c_last(tile, 0)
        for j in range(1, R):
            m = jnp.maximum(m, row_c_last(tile, j))
        return m

    def start_fetch_dec(tile, chunk, slot):
        base = chunk * ppc
        for i in range(R):
            row = drow(tile, i)
            eff = jnp.maximum(seq_lens_ref[row], 1)
            for j in range(ppc):
                live = (base + j) * page_size < eff

                @pl.when(live)
                def _():
                    pid = block_tables_ref[row, base + j]
                    pltpu.make_async_copy(
                        k_out.at[lyr, pid], kd_s.at[slot, i, j],
                        sem_d.at[0, slot]).start()
                    pltpu.make_async_copy(
                        v_out.at[lyr, pid], vd_s.at[slot, i, j],
                        sem_d.at[1, slot]).start()

    def wait_fetch_dec(tile, chunk, slot):
        base = chunk * ppc
        for i in range(R):
            row = drow(tile, i)
            eff = jnp.maximum(seq_lens_ref[row], 1)
            for j in range(ppc):
                live = (base + j) * page_size < eff

                @pl.when(live)
                def _():
                    pid = block_tables_ref[row, base + j]
                    pltpu.make_async_copy(
                        k_out.at[lyr, pid], kd_s.at[slot, i, j],
                        sem_d.at[0, slot]).wait()
                    pltpu.make_async_copy(
                        v_out.at[lyr, pid], vd_s.at[slot, i, j],
                        sem_d.at[1, slot]).wait()

    @pl.when(jnp.logical_and(r == 0, c == 0))
    def _():
        state[_CONSUMED] = 0
        # Stale VMEM can hold NaN; the additive mask only yields exact
        # zeros if dead-position operands are finite (fused_decode.py).
        kd_s[...] = jnp.zeros_like(kd_s)
        vd_s[...] = jnp.zeros_like(vd_s)
        start_fetch_dec(0, 0, 0)

    is_dec = r < NT

    @pl.when(jnp.logical_and(is_dec, c == 0))
    def _():
        # -1e29 floor (not -1e30): a fully-masked chunk keeps m at the
        # floor so p = exp(-1e30 + 1e29) underflows to exactly 0.
        m_d[...] = jnp.full_like(m_d, -1e29)
        l_d[...] = jnp.zeros_like(l_d)
        acc_d[...] = jnp.zeros_like(acc_d)
        qbd_ref[...] = jnp.zeros_like(qbd_ref)
        D = q_dec_ref.shape[2]
        Hkv = H // n_rep
        for g in range(Hkv):
            qbd_ref[:, g * n_rep:(g + 1) * n_rep, g * D:(g + 1) * D] = (
                q_dec_ref[:, g * n_rep:(g + 1) * n_rep, :])

    c_last_d = tile_c_last(jnp.minimum(r, NT - 1))
    dec_fetched = jnp.logical_and(is_dec, c <= c_last_d)

    @pl.when(dec_fetched)
    def _():
        consumed = state[_CONSUMED]
        slot = jax.lax.rem(consumed, 2)
        nslot = 1 - slot

        # Cross-pair prefetch chain (possibly crossing into the next
        # decode tile; the chain ends at the last decode pair — slice
        # blocks self-warm like the prefill kernel always has).
        @pl.when(c < c_last_d)
        def _():
            start_fetch_dec(r, c + 1, nslot)

        @pl.when(jnp.logical_and(c == c_last_d, r + 1 < NT))
        def _():
            start_fetch_dec(r + 1, 0, nslot)

        wait_fetch_dec(r, c, slot)

        # Merge each lane whose current position lives in this chunk
        # into its fetched page and write back the 8-sublane tile
        # holding the new row — this IS the decode cache write.
        kn_all = k_new_ref[...]
        vn_all = v_new_ref[...]
        for i in range(R):
            row = drow(r, i)
            cur = seq_lens_ref[row] - 1
            cur_page_j = cur // page_size
            cur_chunk = cur_page_j // ppc
            jj = cur_page_j - cur_chunk * ppc
            s = cur - cur_page_j * page_size
            do_merge = c == cur_chunk
            tile_lo = (s // 8) * 8
            for j in range(ppc):
                @pl.when(jnp.logical_and(do_merge, j == jj))
                def _():
                    sl = jax.lax.broadcasted_iota(
                        jnp.int32, (page_size, 1), 0)
                    keep = sl != s
                    kd_s[slot, i, j] = jnp.where(
                        keep, kd_s[slot, i, j],
                        kn_all[i:i + 1].astype(kd_s.dtype))
                    vd_s[slot, i, j] = jnp.where(
                        keep, vd_s[slot, i, j],
                        vn_all[i:i + 1].astype(vd_s.dtype))
                    wp = write_page_ref[row]
                    pltpu.make_async_copy(
                        kd_s.at[slot, i, j, pl.ds(tile_lo, 8)],
                        k_out.at[lyr, wp, pl.ds(tile_lo, 8)],
                        wsem.at[0, i]).start()
                    pltpu.make_async_copy(
                        vd_s.at[slot, i, j, pl.ds(tile_lo, 8)],
                        v_out.at[lyr, wp, pl.ds(tile_lo, 8)],
                        wsem.at[1, i]).start()

        Sc = chunk_tokens
        GD = acc_d.shape[2]
        q = qbd_ref[...]                                  # (R, H, GD)
        k = kd_s[slot].reshape(R, Sc, GD)
        v = vd_s[slot].reshape(R, Sc, GD)
        dims = (((2,), (2,)), ((0,), (0,)))
        logits = jax.lax.dot_general(
            q, k, dims,
            preferred_element_type=jnp.float32) * scale    # (R, H, Sc)
        bias = bias_ref[...].reshape(R, 8, Sc)[:, :1, :]
        logits = logits + jnp.broadcast_to(
            bias.astype(jnp.float32), (R, H, Sc))

        m_prev = m_d[...]
        l_prev = l_d[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_d[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_d[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # (R, H, GD)
        acc_d[...] = acc_d[...] * alpha + pv

        # Drain this pair's writebacks after the attention math (DMA
        # overlaps compute; done before the slot can be refetched).
        for i in range(R):
            row = drow(r, i)
            cur = seq_lens_ref[row] - 1
            cur_chunk = (cur // page_size) // ppc

            @pl.when(c == cur_chunk)
            def _():
                wp = write_page_ref[row]
                pltpu.make_async_copy(
                    kd_s.at[slot, i, 0, pl.ds(0, 8)],
                    k_out.at[lyr, wp, pl.ds(0, 8)],
                    wsem.at[0, i]).wait()
                pltpu.make_async_copy(
                    vd_s.at[slot, i, 0, pl.ds(0, 8)],
                    v_out.at[lyr, wp, pl.ds(0, 8)],
                    wsem.at[1, i]).wait()

        state[_CONSUMED] = consumed + 1

    @pl.when(jnp.logical_and(is_dec, c == num_chunks - 1))
    def _():
        res = acc_d[...] / jnp.maximum(l_d[...], 1e-30)    # (R, H, GD)
        D = out_dec_ref.shape[2]
        Hkv = H // n_rep
        for g in range(Hkv):
            out_dec_ref[:, g * n_rep:(g + 1) * n_rep, :] = res[
                :, g * n_rep:(g + 1) * n_rep,
                g * D:(g + 1) * D].astype(out_dec_ref.dtype)

    # ---- slice half: prefill q-blocks, rows NT..NT+NB-1 -----------------

    qb = jnp.clip(r - NT, 0, n_pf_blocks - 1)
    own_raw = owner_ref[qb]
    own = jnp.maximum(own_raw, 0)
    qoff = pf_meta_ref[own, 0]
    qlen = pf_meta_ref[own, 1]
    qstart = pf_meta_ref[own, 2]
    is_pf = r >= NT
    blk_live = jnp.logical_and(is_pf, own_raw >= 0)
    # Absolute position of this block's first q token, live row count,
    # and the last visible position (drives page liveness).
    blk_tok0 = qb * q_block
    pos0 = qstart + (blk_tok0 - qoff)
    n_live = jnp.clip(qoff + qlen - blk_tok0, 0, q_block)
    block_max_pos = pos0 + jnp.maximum(n_live, 1) - 1
    bt_row = jnp.minimum(batch + own, block_tables_ref.shape[0] - 1)

    def start_chunk_pf(chunk, slot):
        base = chunk * ppc
        for j in range(ppc):
            page_start = (base + j) * page_size
            in_grid = chunk < num_chunks
            live = jnp.logical_and(in_grid, page_start <= block_max_pos)

            @pl.when(jnp.logical_and(blk_live, live))
            def _():
                pid = block_tables_ref[bt_row, base + j]
                pltpu.make_async_copy(
                    k_out.at[lyr, pid], kp_s.at[slot, j],
                    sem_p.at[0, slot, j]).start()
                pltpu.make_async_copy(
                    v_out.at[lyr, pid], vp_s.at[slot, j],
                    sem_p.at[1, slot, j]).start()

            @pl.when(jnp.logical_and(
                    is_pf, jnp.logical_and(in_grid,
                                           jnp.logical_not(live))))
            def _():
                # Never-copied scratch could hold NaN; 0-weight × NaN
                # would poison the p·V matmul.
                vp_s[slot, j] = jnp.zeros_like(vp_s[slot, j])

    def wait_chunk_pf(chunk, slot):
        base = chunk * ppc
        for j in range(ppc):
            page_start = (base + j) * page_size

            @pl.when(page_start <= block_max_pos)
            def _():
                pid = block_tables_ref[bt_row, base + j]
                pltpu.make_async_copy(
                    k_out.at[lyr, pid], kp_s.at[slot, j],
                    sem_p.at[0, slot, j]).wait()
                pltpu.make_async_copy(
                    v_out.at[lyr, pid], vp_s.at[slot, j],
                    sem_p.at[1, slot, j]).wait()

    @pl.when(jnp.logical_and(is_pf, c == 0))
    def _():
        m_p[...] = jnp.full_like(m_p, -1e29)
        l_p[...] = jnp.zeros_like(l_p)
        acc_p[...] = jnp.zeros_like(acc_p)
        start_chunk_pf(0, 0)

    slot_p = jax.lax.rem(c, 2)
    chunk_start = c * chunk_tokens

    @pl.when(jnp.logical_and(blk_live, chunk_start <= block_max_pos))
    def _():
        start_chunk_pf(c + 1, 1 - slot_p)
        wait_chunk_pf(c, slot_p)

        Sc = chunk_tokens
        TbH = acc_p.shape[0]
        GD = acc_p.shape[1]
        q = q_pf_ref[...]                                  # (TbH, GD)
        k = kp_s[slot_p].reshape(Sc, GD)
        v = vp_s[slot_p].reshape(Sc, GD)
        dims = (((1,), (1,)), ((), ()))
        logits = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32), dims,
            preferred_element_type=jnp.float32) * scale    # (TbH, Sc)
        # Causal visibility from the descriptors: q row t·H+h is token
        # pos0 + t (dead past n_live), kv column s is chunk_start + s.
        row_tok = jax.lax.broadcasted_iota(
            jnp.int32, (TbH, 1), 0) // H
        q_pos = pos0 + row_tok
        kv_pos = chunk_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, Sc), 1)
        live = jnp.logical_and(kv_pos <= q_pos, row_tok < n_live)
        logits = jnp.where(live, logits, NEG_INF)

        m_prev = m_p[...]
        l_prev = l_p[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_p[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_p[...] = m_new
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (TbH, GD)
        acc_p[...] = acc_p[...] * alpha + pv

    @pl.when(jnp.logical_and(is_pf, c == num_chunks - 1))
    def _():
        # Dead blocks/rows: l stays 0 → emit 0, matching every paged
        # kernel's flush.
        out_pf_ref[...] = (acc_p[...]
                           / jnp.maximum(l_p[...], 1e-30)
                           ).astype(out_pf_ref.dtype)


def _ragged_plan(B: int, page_size: int, max_pages: int, GD: int,
                 itemsize: int, pages_per_chunk: int = 0):
    """Tile/chunk sizing under the ~12 MB scoped-VMEM budget, shared by
    the bf16 and int8 variants (the int8 scale scratch is noise next to
    the page scratch). Returns (R, ppc) or None when no legal plan
    exists — same legality rule as fused_decode._tile_plan: row tiles
    must be 8 (when it divides B) or B."""
    def scratch_bytes(r_, ppc_):
        dec = 2 * 2 * r_ * ppc_ * page_size * GD * itemsize
        pf = 2 * 2 * ppc_ * page_size * GD * itemsize
        return dec + pf

    if pages_per_chunk <= 0:
        pages_per_chunk = max(1, 256 // page_size)
    candidates = ([8] if B % 8 == 0 and B != 8 else []) + [B]
    for R in candidates:
        ppc = min(pages_per_chunk, max_pages)
        while max_pages % ppc:
            ppc -= 1
        while ppc > 1 and scratch_bytes(R, ppc) > 12 * 2**20:
            ppc = max(1, ppc // 2)
            while max_pages % ppc:
                ppc -= 1
        if scratch_bytes(R, ppc) <= 12 * 2**20:
            return R, ppc
    return None


def ragged_kernel_viable(B: int, page_size: int, max_pages: int, GD: int,
                         n_heads: int, q_block: int = 8,
                         itemsize: int = 2) -> bool:
    """Whether the ragged kernel has a legal plan for this geometry.
    Callers route to the split bucket/fused path when False."""
    return (GD % 128 == 0
            and page_size % 8 == 0
            and (q_block * n_heads) % 8 == 0
            and _ragged_plan(B, page_size, max_pages, GD,
                             itemsize) is not None)


def _owners(pf_qoff, pf_qlen, n_blocks: int, q_block: int):
    """Owning slice per q-block from the packed-layout descriptors
    (block token starts are qblk-aligned by the host packing contract);
    -1 marks blocks beyond every live segment."""
    starts = jnp.arange(n_blocks, dtype=jnp.int32) * q_block  # (NB,)
    lo = pf_qoff.astype(jnp.int32)[None, :]                   # (1, S)
    hi = lo + pf_qlen.astype(jnp.int32)[None, :]
    inside = jnp.logical_and(starts[:, None] >= lo,
                             starts[:, None] < hi)            # (NB, S)
    any_live = jnp.any(inside, axis=1)
    own = jnp.argmax(inside, axis=1).astype(jnp.int32)
    return jnp.where(any_live, own, -1)


def ragged_mixed_attention_pallas(
    q_dec: jnp.ndarray,         # (B, H, D) — decode rows' q
    k_new: jnp.ndarray,         # (B, H_kv, D) or (B, GD) — current K rows
    v_new: jnp.ndarray,
    q_pf: jnp.ndarray,          # (N, H, D) — packed slice q tokens
    k_pool: jnp.ndarray,        # (L, P, ps, GD) FLAT
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B+S, MP) int32 — decode rows, slices
    seq_lens: jnp.ndarray,      # (B+S,) int32
    write_page: jnp.ndarray,    # (B,) int32
    pf_qoff: jnp.ndarray,       # (S,) int32 — qblk-aligned segment starts
    pf_qlen: jnp.ndarray,       # (S,) int32 — live tokens per slice
    pf_qstart: jnp.ndarray,     # (S,) int32 — absolute pos of first token
    layer: jnp.ndarray | int = 0,
    *,
    q_block: int = 8,
    pages_per_chunk: int = 0,
    interpret: bool = False,
):
    """One ragged launch: decode attention + fused decode KV write for
    the B rows AND causal paged attention for every packed slice token.
    Slice KV must already be in the pool (the per-layer prefill write
    runs first — see ops/attention.ragged_mixed_step). Returns
    ``(attn_dec (B, H, D), attn_pf (N, H, D), (k_pool, v_pool))``."""
    B, H, D = q_dec.shape
    N = q_pf.shape[0]
    L, P, page_size, GD = k_pool.shape
    Hkv = GD // D
    MP = block_tables.shape[1]
    n_rep = H // Hkv
    if GD % 128:
        raise ValueError(f"H_kv*D = {GD} must be a multiple of 128")
    if N % q_block:
        raise ValueError(f"packed capacity {N} must be a multiple of "
                         f"q_block {q_block}")
    plan = _ragged_plan(B, page_size, MP, GD, k_pool.dtype.itemsize,
                        pages_per_chunk)
    if plan is None:
        raise ValueError(
            f"no legal ragged plan for B={B} page_size={page_size} "
            f"GD={GD} (route via ragged_kernel_viable before calling)")
    R, ppc = plan
    NT = B // R
    NB = N // q_block
    num_chunks = MP // ppc

    # Decode liveness bias, chunk-blocked — fused_decode's layout.
    Sc = ppc * page_size
    dec_lens = seq_lens[:B]
    pos_all = (jnp.arange(num_chunks * Sc, dtype=jnp.int32)
               .reshape(1, num_chunks, 1, Sc))
    bias = jnp.where(pos_all < dec_lens.reshape(B, 1, 1, 1),
                     0.0, NEG_INF).astype(jnp.bfloat16)
    bias = jnp.broadcast_to(bias, (B, num_chunks, 8, Sc))
    kn = k_new.reshape(B, GD).astype(k_pool.dtype)
    vn = v_new.reshape(B, GD).astype(v_pool.dtype)

    # Slice q: block-diagonal rows (prefill_attention's host layout).
    eye = jnp.eye(Hkv, dtype=q_pf.dtype)
    q_pf_bd = jnp.einsum("tgrd,gh->tgrhd",
                         q_pf.reshape(N, Hkv, n_rep, D),
                         eye).reshape(N * H, GD)
    pf_meta = jnp.stack([pf_qoff.astype(jnp.int32),
                         pf_qlen.astype(jnp.int32),
                         pf_qstart.astype(jnp.int32)], axis=1)
    owner = _owners(pf_qoff, pf_qlen, NB, q_block)

    kernel = functools.partial(
        _ragged_kernel, rows_per_tile=R, pages_per_chunk=ppc,
        page_size=page_size, num_chunks=num_chunks, n_dec_tiles=NT,
        n_pf_blocks=NB, q_block=q_block, batch=B, n_heads=H,
        n_rep=n_rep, scale=D ** -0.5)
    TbH = q_block * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(NT + NB, num_chunks),
        in_specs=[
            pl.BlockSpec((R, H, D),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0, 0)),
            pl.BlockSpec((R, GD),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0)),
            pl.BlockSpec((R, GD),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0)),
            pl.BlockSpec((R, 1, 8, Sc),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), c,
                                           0, 0)),
            pl.BlockSpec((TbH, GD),
                         lambda r, c, *_: (jnp.clip(r - NT, 0, NB - 1),
                                           0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((R, H, D),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0, 0)),
            pl.BlockSpec((TbH, GD),
                         lambda r, c, *_: (jnp.clip(r - NT, 0, NB - 1),
                                           0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, GD), jnp.float32),
            pltpu.VMEM((R, H, GD), q_dec.dtype),
            pltpu.VMEM((2, R, ppc, page_size, GD), k_pool.dtype),
            pltpu.VMEM((2, R, ppc, page_size, GD), v_pool.dtype),
            pltpu.VMEM((TbH, 1), jnp.float32),
            pltpu.VMEM((TbH, 1), jnp.float32),
            pltpu.VMEM((TbH, GD), jnp.float32),
            pltpu.VMEM((2, ppc, page_size, GD), k_pool.dtype),
            pltpu.VMEM((2, ppc, page_size, GD), v_pool.dtype),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, R)),
            pltpu.SemaphoreType.DMA((2, 2, ppc)),
        ],
    )
    # Operands: 6 scalar-prefetch, then q_dec, kn, vn, bias, q_pf,
    # pools → pool operands 11/12 alias outputs 2/3.
    out_dec, out_pf, k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q_dec.dtype),
                   jax.ShapeDtypeStruct((N * H, GD), q_pf.dtype),
                   jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={11: 2, 12: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      write_page.astype(jnp.int32), pf_meta, owner,
      jnp.asarray(layer, jnp.int32).reshape(1),
      q_dec, kn, vn, bias, q_pf_bd, k_pool, v_pool)
    # Un-blockdiagonal the slice output: (N·H, GD) → (N, H, D).
    out5 = out_pf.reshape(N, Hkv, n_rep, Hkv, D)
    attn_pf = jnp.einsum("tgrhd,gh->tgrd", out5,
                         jnp.eye(Hkv, dtype=out_pf.dtype))
    return (out_dec.astype(q_dec.dtype),
            attn_pf.reshape(N, H, D).astype(q_pf.dtype),
            (k_out, v_out))


# -- int8 KV variant -----------------------------------------------------------
#
# Deltas vs the bf16 kernel, mirroring fused_decode's q8 shape:
# 1. pool pages are int8 (half the page DMA bytes on BOTH halves);
# 2. per-(token, kv-head) bf16 scale pools (L, P, H_kv, ps) are fetched
#    next to their data pages on separate semaphores and merged/written
#    back by the decode half;
# 3. dequantization fuses at the matmuls: K scales multiply logits
#    group-wise ((head, position) IS the logits layout), V scales fold
#    into the probabilities — no dequantized page ever touches HBM.


def _ragged_kernel_q8(
    # scalar prefetch
    block_tables_ref, seq_lens_ref, write_page_ref, pf_meta_ref,
    owner_ref, layer_ref,
    # inputs
    q_dec_ref,          # (R, H, D) bf16
    k_new_ref,          # (R, GD) int8 — pre-quantized current rows
    v_new_ref,
    kns_ref,            # (R, Hkv, ps) bf16 — new K scales, pre-broadcast
    vns_ref,
    bias_ref,           # (R, 1, 8, Sc) bf16
    q_pf_ref,           # (qblk·H, GD) bf16 block-diag
    k_hbm, v_hbm,       # int8 ANY — aliased
    ks_hbm, vs_hbm,     # (L, P, Hkv, ps) bf16 ANY — aliased
    # outputs
    out_dec_ref, out_pf_ref,
    k_out, v_out, ks_out, vs_out,
    # scratch
    m_d, l_d, acc_d, qbd_ref,
    kd_s, vd_s,                     # (2, R, ppc, ps, GD) int8
    ksd_s, vsd_s,                   # (2, R, ppc, Hkv, ps) bf16
    m_p, l_p, acc_p,
    kp_s, vp_s,                     # (2, ppc, ps, GD) int8
    ksp_s, vsp_s,                   # (2, ppc, Hkv, ps) bf16
    state, sem_d, ssem_d, wsem, swsem, sem_p, ssem_p,
    *,
    rows_per_tile: int,
    pages_per_chunk: int,
    page_size: int,
    num_chunks: int,
    n_dec_tiles: int,
    n_pf_blocks: int,
    q_block: int,
    batch: int,
    n_heads: int,
    n_rep: int,
    scale: float,
):
    r = pl.program_id(0)
    c = pl.program_id(1)
    R = rows_per_tile
    ppc = pages_per_chunk
    chunk_tokens = ppc * page_size
    NT = n_dec_tiles
    H = n_heads
    Hkv = H // n_rep
    lyr = layer_ref[0]

    def drow(tile, lane):
        return jnp.minimum(tile * R + lane, batch - 1)

    def row_c_last(tile, lane):
        eff = jnp.maximum(seq_lens_ref[drow(tile, lane)], 1)
        return (eff - 1) // chunk_tokens

    def tile_c_last(tile):
        m = row_c_last(tile, 0)
        for j in range(1, R):
            m = jnp.maximum(m, row_c_last(tile, j))
        return m

    def start_fetch_dec(tile, chunk, slot):
        base = chunk * ppc
        for i in range(R):
            row = drow(tile, i)
            eff = jnp.maximum(seq_lens_ref[row], 1)
            for j in range(ppc):
                live = (base + j) * page_size < eff

                @pl.when(live)
                def _():
                    pid = block_tables_ref[row, base + j]
                    pltpu.make_async_copy(
                        k_out.at[lyr, pid], kd_s.at[slot, i, j],
                        sem_d.at[0, slot]).start()
                    pltpu.make_async_copy(
                        v_out.at[lyr, pid], vd_s.at[slot, i, j],
                        sem_d.at[1, slot]).start()
                    pltpu.make_async_copy(
                        ks_out.at[lyr, pid], ksd_s.at[slot, i, j],
                        ssem_d.at[0, slot]).start()
                    pltpu.make_async_copy(
                        vs_out.at[lyr, pid], vsd_s.at[slot, i, j],
                        ssem_d.at[1, slot]).start()

    def wait_fetch_dec(tile, chunk, slot):
        base = chunk * ppc
        for i in range(R):
            row = drow(tile, i)
            eff = jnp.maximum(seq_lens_ref[row], 1)
            for j in range(ppc):
                live = (base + j) * page_size < eff

                @pl.when(live)
                def _():
                    pid = block_tables_ref[row, base + j]
                    pltpu.make_async_copy(
                        k_out.at[lyr, pid], kd_s.at[slot, i, j],
                        sem_d.at[0, slot]).wait()
                    pltpu.make_async_copy(
                        v_out.at[lyr, pid], vd_s.at[slot, i, j],
                        sem_d.at[1, slot]).wait()
                    pltpu.make_async_copy(
                        ks_out.at[lyr, pid], ksd_s.at[slot, i, j],
                        ssem_d.at[0, slot]).wait()
                    pltpu.make_async_copy(
                        vs_out.at[lyr, pid], vsd_s.at[slot, i, j],
                        ssem_d.at[1, slot]).wait()

    @pl.when(jnp.logical_and(r == 0, c == 0))
    def _():
        state[_CONSUMED] = 0
        kd_s[...] = jnp.zeros_like(kd_s)
        vd_s[...] = jnp.zeros_like(vd_s)
        # Scale scratch must be FINITE too: dead positions contribute
        # k_stale·scale_stale through the masked softmax.
        ksd_s[...] = jnp.zeros_like(ksd_s)
        vsd_s[...] = jnp.zeros_like(vsd_s)
        start_fetch_dec(0, 0, 0)

    is_dec = r < NT

    @pl.when(jnp.logical_and(is_dec, c == 0))
    def _():
        m_d[...] = jnp.full_like(m_d, -1e29)
        l_d[...] = jnp.zeros_like(l_d)
        acc_d[...] = jnp.zeros_like(acc_d)
        qbd_ref[...] = jnp.zeros_like(qbd_ref)
        D = q_dec_ref.shape[2]
        for g in range(Hkv):
            qbd_ref[:, g * n_rep:(g + 1) * n_rep, g * D:(g + 1) * D] = (
                q_dec_ref[:, g * n_rep:(g + 1) * n_rep, :])

    c_last_d = tile_c_last(jnp.minimum(r, NT - 1))
    dec_fetched = jnp.logical_and(is_dec, c <= c_last_d)

    @pl.when(dec_fetched)
    def _():
        consumed = state[_CONSUMED]
        slot = jax.lax.rem(consumed, 2)
        nslot = 1 - slot

        @pl.when(c < c_last_d)
        def _():
            start_fetch_dec(r, c + 1, nslot)

        @pl.when(jnp.logical_and(c == c_last_d, r + 1 < NT))
        def _():
            start_fetch_dec(r + 1, 0, nslot)

        wait_fetch_dec(r, c, slot)

        kn_all = k_new_ref[...]
        vn_all = v_new_ref[...]
        for i in range(R):
            row = drow(r, i)
            cur = seq_lens_ref[row] - 1
            cur_page_j = cur // page_size
            cur_chunk = cur_page_j // ppc
            jj = cur_page_j - cur_chunk * ppc
            s = cur - cur_page_j * page_size
            do_merge = c == cur_chunk
            tile_lo = (s // 8) * 8
            for j in range(ppc):
                @pl.when(jnp.logical_and(do_merge, j == jj))
                def _():
                    sl = jax.lax.broadcasted_iota(
                        jnp.int32, (page_size, 1), 0)
                    keep = sl != s
                    kd_s[slot, i, j] = jnp.where(
                        keep, kd_s[slot, i, j],
                        kn_all[i:i + 1].astype(kd_s.dtype))
                    vd_s[slot, i, j] = jnp.where(
                        keep, vd_s[slot, i, j],
                        vn_all[i:i + 1].astype(vd_s.dtype))
                    li = jax.lax.broadcasted_iota(
                        jnp.int32, (ksd_s.shape[3], page_size), 1)
                    skeep = li != s
                    ksd_s[slot, i, j] = jnp.where(
                        skeep, ksd_s[slot, i, j], kns_ref[i])
                    vsd_s[slot, i, j] = jnp.where(
                        skeep, vsd_s[slot, i, j], vns_ref[i])
                    wp = write_page_ref[row]
                    pltpu.make_async_copy(
                        kd_s.at[slot, i, j, pl.ds(tile_lo, 8)],
                        k_out.at[lyr, wp, pl.ds(tile_lo, 8)],
                        wsem.at[0, i]).start()
                    pltpu.make_async_copy(
                        vd_s.at[slot, i, j, pl.ds(tile_lo, 8)],
                        v_out.at[lyr, wp, pl.ds(tile_lo, 8)],
                        wsem.at[1, i]).start()
                    pltpu.make_async_copy(
                        ksd_s.at[slot, i, j],
                        ks_out.at[lyr, wp], swsem.at[0, i]).start()
                    pltpu.make_async_copy(
                        vsd_s.at[slot, i, j],
                        vs_out.at[lyr, wp], swsem.at[1, i]).start()

        Sc = chunk_tokens
        GD = acc_d.shape[2]
        q = qbd_ref[...]
        k = kd_s[slot].reshape(R, Sc, GD).astype(jnp.bfloat16)
        v = vd_s[slot].reshape(R, Sc, GD).astype(jnp.bfloat16)
        dims = (((2,), (2,)), ((0,), (0,)))
        logits = jax.lax.dot_general(
            q, k, dims,
            preferred_element_type=jnp.float32) * scale

        def head_scales_dec(s_scratch):
            """(2, R, ppc, Hkv, ps) scratch → (R, H, Sc) f32 multiplier
            (fused_decode.py rationale: value-slice the slot ONCE)."""
            full = s_scratch[slot]                   # (R, ppc, Hkv, ps)
            pages = [full[:, j] for j in range(ppc)]
            hs = (pages[0] if ppc == 1
                  else jnp.concatenate(pages, axis=2))     # (R, Hkv, Sc)
            rows = []
            for g in range(Hkv):
                rows.extend([hs[:, g:g + 1, :]] * n_rep)
            return jnp.concatenate(rows, axis=1).astype(jnp.float32)

        logits = logits * head_scales_dec(ksd_s)
        bias = bias_ref[...].reshape(R, 8, Sc)[:, :1, :]
        logits = logits + jnp.broadcast_to(
            bias.astype(jnp.float32), (R, H, Sc))

        m_prev = m_d[...]
        l_prev = l_d[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_d[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_d[...] = m_new
        p = p * head_scales_dec(vsd_s)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_d[...] = acc_d[...] * alpha + pv

        for i in range(R):
            row = drow(r, i)
            cur = seq_lens_ref[row] - 1
            cur_chunk = (cur // page_size) // ppc

            @pl.when(c == cur_chunk)
            def _():
                wp = write_page_ref[row]
                pltpu.make_async_copy(
                    kd_s.at[slot, i, 0, pl.ds(0, 8)],
                    k_out.at[lyr, wp, pl.ds(0, 8)],
                    wsem.at[0, i]).wait()
                pltpu.make_async_copy(
                    vd_s.at[slot, i, 0, pl.ds(0, 8)],
                    v_out.at[lyr, wp, pl.ds(0, 8)],
                    wsem.at[1, i]).wait()
                pltpu.make_async_copy(
                    ksd_s.at[slot, i, 0],
                    ks_out.at[lyr, wp], swsem.at[0, i]).wait()
                pltpu.make_async_copy(
                    vsd_s.at[slot, i, 0],
                    vs_out.at[lyr, wp], swsem.at[1, i]).wait()

        state[_CONSUMED] = consumed + 1

    @pl.when(jnp.logical_and(is_dec, c == num_chunks - 1))
    def _():
        res = acc_d[...] / jnp.maximum(l_d[...], 1e-30)
        D = out_dec_ref.shape[2]
        for g in range(Hkv):
            out_dec_ref[:, g * n_rep:(g + 1) * n_rep, :] = res[
                :, g * n_rep:(g + 1) * n_rep,
                g * D:(g + 1) * D].astype(out_dec_ref.dtype)

    # ---- slice half ------------------------------------------------------

    qb = jnp.clip(r - NT, 0, n_pf_blocks - 1)
    own_raw = owner_ref[qb]
    own = jnp.maximum(own_raw, 0)
    qoff = pf_meta_ref[own, 0]
    qlen = pf_meta_ref[own, 1]
    qstart = pf_meta_ref[own, 2]
    is_pf = r >= NT
    blk_live = jnp.logical_and(is_pf, own_raw >= 0)
    blk_tok0 = qb * q_block
    pos0 = qstart + (blk_tok0 - qoff)
    n_live = jnp.clip(qoff + qlen - blk_tok0, 0, q_block)
    block_max_pos = pos0 + jnp.maximum(n_live, 1) - 1
    bt_row = jnp.minimum(batch + own, block_tables_ref.shape[0] - 1)

    def start_chunk_pf(chunk, slot):
        base = chunk * ppc
        for j in range(ppc):
            page_start = (base + j) * page_size
            in_grid = chunk < num_chunks
            live = jnp.logical_and(in_grid, page_start <= block_max_pos)

            @pl.when(jnp.logical_and(blk_live, live))
            def _():
                pid = block_tables_ref[bt_row, base + j]
                pltpu.make_async_copy(
                    k_out.at[lyr, pid], kp_s.at[slot, j],
                    sem_p.at[0, slot, j]).start()
                pltpu.make_async_copy(
                    v_out.at[lyr, pid], vp_s.at[slot, j],
                    sem_p.at[1, slot, j]).start()
                pltpu.make_async_copy(
                    ks_out.at[lyr, pid], ksp_s.at[slot, j],
                    ssem_p.at[0, slot, j]).start()
                pltpu.make_async_copy(
                    vs_out.at[lyr, pid], vsp_s.at[slot, j],
                    ssem_p.at[1, slot, j]).start()

            @pl.when(jnp.logical_and(
                    is_pf, jnp.logical_and(in_grid,
                                           jnp.logical_not(live))))
            def _():
                vp_s[slot, j] = jnp.zeros_like(vp_s[slot, j])
                vsp_s[slot, j] = jnp.zeros_like(vsp_s[slot, j])

    def wait_chunk_pf(chunk, slot):
        base = chunk * ppc
        for j in range(ppc):
            page_start = (base + j) * page_size

            @pl.when(page_start <= block_max_pos)
            def _():
                pid = block_tables_ref[bt_row, base + j]
                pltpu.make_async_copy(
                    k_out.at[lyr, pid], kp_s.at[slot, j],
                    sem_p.at[0, slot, j]).wait()
                pltpu.make_async_copy(
                    v_out.at[lyr, pid], vp_s.at[slot, j],
                    sem_p.at[1, slot, j]).wait()
                pltpu.make_async_copy(
                    ks_out.at[lyr, pid], ksp_s.at[slot, j],
                    ssem_p.at[0, slot, j]).wait()
                pltpu.make_async_copy(
                    vs_out.at[lyr, pid], vsp_s.at[slot, j],
                    ssem_p.at[1, slot, j]).wait()

    @pl.when(jnp.logical_and(is_pf, c == 0))
    def _():
        m_p[...] = jnp.full_like(m_p, -1e29)
        l_p[...] = jnp.zeros_like(l_p)
        acc_p[...] = jnp.zeros_like(acc_p)
        start_chunk_pf(0, 0)

    slot_p = jax.lax.rem(c, 2)
    chunk_start = c * chunk_tokens

    @pl.when(jnp.logical_and(blk_live, chunk_start <= block_max_pos))
    def _():
        start_chunk_pf(c + 1, 1 - slot_p)
        wait_chunk_pf(c, slot_p)

        Sc = chunk_tokens
        TbH = acc_p.shape[0]
        GD = acc_p.shape[1]
        q = q_pf_ref[...]
        k = kp_s[slot_p].reshape(Sc, GD).astype(jnp.bfloat16)
        v = vp_s[slot_p].reshape(Sc, GD).astype(jnp.bfloat16)
        dims = (((1,), (1,)), ((), ()))
        logits = jax.lax.dot_general(
            q, k, dims,
            preferred_element_type=jnp.float32) * scale    # (TbH, Sc)

        def head_scales_pf(s_scratch):
            """(2, ppc, Hkv, ps) scratch → (TbH, Sc) f32 multiplier:
            the (head, position) layout expanded to the q-row layout
            (token-major × H rows, g-major head order)."""
            full = s_scratch[slot_p]                  # (ppc, Hkv, ps)
            pages = [full[j] for j in range(ppc)]
            hs = (pages[0] if ppc == 1
                  else jnp.concatenate(pages, axis=1))     # (Hkv, Sc)
            rows = []
            for g in range(Hkv):
                rows.extend([hs[g:g + 1, :]] * n_rep)
            per_tok = jnp.concatenate(rows, axis=0)        # (H, Sc)
            return jnp.concatenate(
                [per_tok] * (TbH // H), axis=0).astype(jnp.float32)

        logits = logits * head_scales_pf(ksp_s)
        row_tok = jax.lax.broadcasted_iota(
            jnp.int32, (TbH, 1), 0) // H
        q_pos = pos0 + row_tok
        kv_pos = chunk_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, Sc), 1)
        live = jnp.logical_and(kv_pos <= q_pos, row_tok < n_live)
        logits = jnp.where(live, logits, NEG_INF)

        m_prev = m_p[...]
        l_prev = l_p[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_p[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_p[...] = m_new
        p = p * head_scales_pf(vsp_s)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_p[...] = acc_p[...] * alpha + pv

    @pl.when(jnp.logical_and(is_pf, c == num_chunks - 1))
    def _():
        out_pf_ref[...] = (acc_p[...]
                           / jnp.maximum(l_p[...], 1e-30)
                           ).astype(out_pf_ref.dtype)


def ragged_mixed_attention_q8_pallas(
    q_dec: jnp.ndarray,         # (B, H, D) bf16
    k_new_q: jnp.ndarray,       # (B, H_kv, D) int8 — pre-quantized
    k_new_scale: jnp.ndarray,   # (B, H_kv) bf16
    v_new_q: jnp.ndarray,
    v_new_scale: jnp.ndarray,
    q_pf: jnp.ndarray,          # (N, H, D) bf16
    pools,                      # (k, v, k_scale, v_scale)
    block_tables: jnp.ndarray,  # (B+S, MP)
    seq_lens: jnp.ndarray,      # (B+S,)
    write_page: jnp.ndarray,    # (B,)
    pf_qoff: jnp.ndarray,
    pf_qlen: jnp.ndarray,
    pf_qstart: jnp.ndarray,
    layer: jnp.ndarray | int = 0,
    *,
    q_block: int = 8,
    pages_per_chunk: int = 0,
    interpret: bool = False,
):
    """int8-KV ragged launch (see _ragged_kernel_q8). Returns
    ``(attn_dec, attn_pf (N, H, D), pools)``."""
    k_pool, v_pool, ks_pool, vs_pool = pools
    B, H, D = q_dec.shape
    N = q_pf.shape[0]
    L, P, page_size, GD = k_pool.shape
    Hkv = GD // D
    MP = block_tables.shape[1]
    n_rep = H // Hkv
    if GD % 128:
        raise ValueError(f"H_kv*D = {GD} must be a multiple of 128")
    if N % q_block:
        raise ValueError(f"packed capacity {N} must be a multiple of "
                         f"q_block {q_block}")
    plan = _ragged_plan(B, page_size, MP, GD, k_pool.dtype.itemsize,
                        pages_per_chunk)
    if plan is None:
        raise ValueError(
            f"no legal ragged q8 plan for B={B} page_size={page_size} "
            f"GD={GD}")
    R, ppc = plan
    NT = B // R
    NB = N // q_block
    num_chunks = MP // ppc

    Sc = ppc * page_size
    dec_lens = seq_lens[:B]
    pos_all = (jnp.arange(num_chunks * Sc, dtype=jnp.int32)
               .reshape(1, num_chunks, 1, Sc))
    bias = jnp.where(pos_all < dec_lens.reshape(B, 1, 1, 1),
                     0.0, NEG_INF).astype(jnp.bfloat16)
    bias = jnp.broadcast_to(bias, (B, num_chunks, 8, Sc))
    kn = k_new_q.reshape(B, GD)
    vn = v_new_q.reshape(B, GD)
    kns = jnp.broadcast_to(
        k_new_scale.astype(jnp.bfloat16)[:, :, None], (B, Hkv, page_size))
    vns = jnp.broadcast_to(
        v_new_scale.astype(jnp.bfloat16)[:, :, None], (B, Hkv, page_size))
    eye = jnp.eye(Hkv, dtype=q_pf.dtype)
    q_pf_bd = jnp.einsum("tgrd,gh->tgrhd",
                         q_pf.reshape(N, Hkv, n_rep, D),
                         eye).reshape(N * H, GD)
    pf_meta = jnp.stack([pf_qoff.astype(jnp.int32),
                         pf_qlen.astype(jnp.int32),
                         pf_qstart.astype(jnp.int32)], axis=1)
    owner = _owners(pf_qoff, pf_qlen, NB, q_block)

    kernel = functools.partial(
        _ragged_kernel_q8, rows_per_tile=R, pages_per_chunk=ppc,
        page_size=page_size, num_chunks=num_chunks, n_dec_tiles=NT,
        n_pf_blocks=NB, q_block=q_block, batch=B, n_heads=H,
        n_rep=n_rep, scale=D ** -0.5)
    TbH = q_block * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(NT + NB, num_chunks),
        in_specs=[
            pl.BlockSpec((R, H, D),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0, 0)),
            pl.BlockSpec((R, GD),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0)),
            pl.BlockSpec((R, GD),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0)),
            pl.BlockSpec((R, Hkv, page_size),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0, 0)),
            pl.BlockSpec((R, Hkv, page_size),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0, 0)),
            pl.BlockSpec((R, 1, 8, Sc),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), c,
                                           0, 0)),
            pl.BlockSpec((TbH, GD),
                         lambda r, c, *_: (jnp.clip(r - NT, 0, NB - 1),
                                           0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((R, H, D),
                         lambda r, c, *_: (jnp.minimum(r, NT - 1), 0, 0)),
            pl.BlockSpec((TbH, GD),
                         lambda r, c, *_: (jnp.clip(r - NT, 0, NB - 1),
                                           0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, GD), jnp.float32),
            pltpu.VMEM((R, H, GD), q_dec.dtype),
            pltpu.VMEM((2, R, ppc, page_size, GD), k_pool.dtype),
            pltpu.VMEM((2, R, ppc, page_size, GD), v_pool.dtype),
            pltpu.VMEM((2, R, ppc, Hkv, page_size), ks_pool.dtype),
            pltpu.VMEM((2, R, ppc, Hkv, page_size), vs_pool.dtype),
            pltpu.VMEM((TbH, 1), jnp.float32),
            pltpu.VMEM((TbH, 1), jnp.float32),
            pltpu.VMEM((TbH, GD), jnp.float32),
            pltpu.VMEM((2, ppc, page_size, GD), k_pool.dtype),
            pltpu.VMEM((2, ppc, page_size, GD), v_pool.dtype),
            pltpu.VMEM((2, ppc, Hkv, page_size), ks_pool.dtype),
            pltpu.VMEM((2, ppc, Hkv, page_size), vs_pool.dtype),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, R)),
            pltpu.SemaphoreType.DMA((2, R)),
            pltpu.SemaphoreType.DMA((2, 2, ppc)),
            pltpu.SemaphoreType.DMA((2, 2, ppc)),
        ],
    )
    # Operands: 6 scalar-prefetch, q_dec, kn, vn, kns, vns, bias, q_pf,
    # then the four pools at operands 13-16 aliased to outputs 2-5.
    out_dec, out_pf, k_out, v_out, ks_out, vs_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q_dec.dtype),
                   jax.ShapeDtypeStruct((N * H, GD), q_pf.dtype),
                   jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
                   jax.ShapeDtypeStruct(ks_pool.shape, ks_pool.dtype),
                   jax.ShapeDtypeStruct(vs_pool.shape, vs_pool.dtype)],
        input_output_aliases={13: 2, 14: 3, 15: 4, 16: 5},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      write_page.astype(jnp.int32), pf_meta, owner,
      jnp.asarray(layer, jnp.int32).reshape(1),
      q_dec, kn, vn, kns, vns, bias, q_pf_bd,
      k_pool, v_pool, ks_pool, vs_pool)
    out5 = out_pf.reshape(N, Hkv, n_rep, Hkv, D)
    attn_pf = jnp.einsum("tgrhd,gh->tgrd", out5,
                         jnp.eye(Hkv, dtype=out_pf.dtype))
    return (out_dec.astype(q_dec.dtype),
            attn_pf.reshape(N, H, D).astype(q_pf.dtype),
            (k_out, v_out, ks_out, vs_out))
