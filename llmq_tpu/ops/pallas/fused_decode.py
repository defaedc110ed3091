"""Pallas TPU kernel: FUSED decode attention + KV-cache write.

One kernel per layer does both the current tokens' cache write and the
paged attention read — vs a write kernel (kv_write.py) followed by a
separate attention pass, with their doubled launch overhead and a
second page round-trip.

Design (v3 — third shape of this kernel; the numbers that drove it):

- r2 kernel: per-row grid, per-row page-merge writeback, within-row
  double buffering → ~34µs/row at B=64 (≈16ms of a 21ms decode step),
  flat in seq_len. The merge (full-batch masked row extraction,
  page-wide selects, staging copies) and the per-row cold DMA stall
  dominated; actual page bandwidth was noise.
- **Row tiles**: the grid is (B/R tiles, chunks); each step fetches R
  rows' pages and runs ONE batched dot_general over the tile —
  amortizing per-step scalar/dispatch overhead R× vs per-row grids.
- **Cross-pair prefetch chain**: each live (tile, chunk) pair starts
  the next live pair's DMAs (crossing tile boundaries) into the
  alternate scratch slot; slot parity is a consumed-fetch counter in
  SMEM, not ``chunk % 2``, because dead chunks are skipped.
- **Tile-sliced merge**: the current token's K/V row is selected into
  its (already fetched) page in scratch and the merged page is written
  back as ONE full-page DMA per pool. The tile's k_new/v_new rows
  arrive as a BlockSpec slice (free), so the r2 kernel's masked
  extraction disappears; sub-page DMAs are impossible anyway (Mosaic
  requires 2nd-minor slices tile-aligned — a (1, GD) row write doesn't
  compile). Writeback waits land AFTER the attention math, so the DMA
  overlaps compute but is guaranteed done before this scratch slot can
  be refetched (the next pair's prefetch targets the other slot; the
  pair after that reuses this one only after this step ends).
- Fetch/wait liveness is keyed on ``eff_len = max(seq_len, 1)`` so a
  ``seq_len == 0`` row still pairs starts with waits exactly.
- Scratch is zeroed ONCE per call: dead positions inside a live chunk
  contribute exactly 0 through the masked softmax, which is safe only
  if stale scratch is finite (uninitialized VMEM can hold NaN bit
  patterns; NaN + -1e30 = NaN and 0·NaN = NaN).
- The mask rides an additive bf16 bias INPUT (0 / -1e30, broadcast
  over H so the block's last-two dims are tile-aligned): Mosaic can't
  stack SMEM scalars into vectors inside the kernel.
- The online-softmax max floor is -1e29, not -inf: a fully-masked
  chunk then yields p = exp(-1e30 + 1e29) = 0 exactly instead of
  exp(0) = 1 pulling stale V into the accumulator.
- DMA semaphores are shared per (pool, slot): TPU sflag space is ~2KB
  (≈500 semaphores) — a per-(row, page) array doesn't fit. All sharers
  copy identical byte counts, so per-copy waits drain in any order.

Chunk sizing: per-DMA issue cost is per PAGE, so serving configs want
large pages (128-256 tokens); chunks default to ~256 tokens so chunks
beyond a row's length skip both their DMAs and their masked matmuls.

Same shape strategy as the other kernels: block-diagonal Q (one
batched MXU matmul for all heads), pages flattened to (ps, H_kv·D),
online softmax in f32 scratch. Constraints: all live rows target
distinct pages (decode invariant), H_kv·D % 128 == 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

_CONSUMED = 0   # SMEM state: fetches consumed so far (slot parity)


def _fused_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,   # (B, max_pages) int32
    seq_lens_ref,       # (B,) int32 — pos+1 (current token included)
    write_page_ref,     # (B,) int32 — pool page id for the current token
    layer_ref,          # (1,) int32
    # inputs
    q_ref,              # (R, H, D) VMEM — RAW query heads; the
                        # block-diagonal GQA layout is built in VMEM
                        # scratch once per tile (an H×GD q in HBM cost
                        # ~0.3 ms/step of pure traffic at B=64)
    k_new_ref,          # (R, GD) VMEM — this tile's current K rows
    v_new_ref,          # (R, GD) VMEM
    bias_ref,           # (R, 1, 8, S) bf16 — 0 live, -1e30 masked; 8
                        # identical sublane rows (min tile), broadcast
                        # to H in-register (ADVICE r3: an H-wide bias
                        # was 4x the HBM traffic for H=32)
    k_hbm,              # (L, P, ps, GD) ANY — aliased to output 1
    v_hbm,              # (L, P, ps, GD) ANY — aliased to output 2
    # outputs
    out_ref,            # (R, H, D) VMEM — attention output, this tile
    k_out,              # aliased pools (all DMAs target these)
    v_out,
    # scratch
    m_ref, l_ref, acc_ref,          # (R,H,1),(R,H,1),(R,H,GD) f32
    qbd_ref,                        # (R, H, GD) VMEM — block-diag q
    k_scratch, v_scratch,           # (2, R, ppc, ps, GD) VMEM
    state,                          # SMEM (1,) int32
    sem,                            # DMA (2, 2) — [pool, slot] fetches
    wsem,                           # DMA (2, R) — [pool, row] writebacks
    *,
    rows_per_tile: int,
    pages_per_chunk: int,
    page_size: int,
    num_chunks: int,
    batch: int,
    n_rep: int,
    scale: float,
):
    t = pl.program_id(0)
    c = pl.program_id(1)
    R = rows_per_tile
    ppc = pages_per_chunk
    chunk_tokens = ppc * page_size
    num_tiles = pl.num_programs(0)
    lyr = layer_ref[0]

    def row_c_last(row):
        eff = jnp.maximum(seq_lens_ref[row], 1)
        return (eff - 1) // chunk_tokens

    def tile_c_last(tile):
        m = row_c_last(tile * R)
        for r in range(1, R):
            m = jnp.maximum(m, row_c_last(tile * R + r))
        return m

    def start_fetch(tile, chunk, slot):
        """Start DMAs for every live (row, page) of (tile, chunk).
        Liveness uses the TARGET rows' eff_len — must match wait_fetch
        exactly or semaphores corrupt."""
        base = chunk * ppc
        for r in range(R):
            row = tile * R + r
            eff = jnp.maximum(seq_lens_ref[row], 1)
            for j in range(ppc):
                live = (base + j) * page_size < eff

                @pl.when(live)
                def _():
                    pid = block_tables_ref[row, base + j]
                    pltpu.make_async_copy(
                        k_out.at[lyr, pid], k_scratch.at[slot, r, j],
                        sem.at[0, slot]).start()
                    pltpu.make_async_copy(
                        v_out.at[lyr, pid], v_scratch.at[slot, r, j],
                        sem.at[1, slot]).start()

    def wait_fetch(tile, chunk, slot):
        base = chunk * ppc
        for r in range(R):
            row = tile * R + r
            eff = jnp.maximum(seq_lens_ref[row], 1)
            for j in range(ppc):
                live = (base + j) * page_size < eff

                @pl.when(live)
                def _():
                    pid = block_tables_ref[row, base + j]
                    pltpu.make_async_copy(
                        k_out.at[lyr, pid], k_scratch.at[slot, r, j],
                        sem.at[0, slot]).wait()
                    pltpu.make_async_copy(
                        v_out.at[lyr, pid], v_scratch.at[slot, r, j],
                        sem.at[1, slot]).wait()

    @pl.when(jnp.logical_and(t == 0, c == 0))
    def _():
        state[_CONSUMED] = 0
        # BOTH pools: dead positions contribute through q·k_stale +
        # bias and p·v_stale — the additive mask only yields exactly-0
        # contributions if stale scratch is finite (fresh VMEM can hold
        # NaN, and NaN + -1e30 = NaN straight through the softmax).
        k_scratch[...] = jnp.zeros_like(k_scratch)
        v_scratch[...] = jnp.zeros_like(v_scratch)
        start_fetch(0, 0, 0)

    @pl.when(c == 0)
    def _():
        # Floor at -1e29 (not -1e30): if every position of a chunk is
        # masked, m stays at the floor and p = exp(-1e30 - (-1e29))
        # underflows to exactly 0 — with the floor at the mask value
        # itself, p would be exp(0) = 1 and stale V would leak.
        m_ref[...] = jnp.full_like(m_ref, -1e29)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # Build the block-diagonal GQA q for this tile: group g's
        # queries live in GD columns [g·D, (g+1)·D) so ONE batched
        # matmul serves all heads against the (S, GD) page layout.
        qbd_ref[...] = jnp.zeros_like(qbd_ref)
        D = q_ref.shape[2]
        Hkv = q_ref.shape[1] // n_rep
        for g in range(Hkv):
            qbd_ref[:, g * n_rep:(g + 1) * n_rep, g * D:(g + 1) * D] = (
                q_ref[:, g * n_rep:(g + 1) * n_rep, :])

    c_last = tile_c_last(t)
    fetched = c <= c_last

    @pl.when(fetched)
    def _():
        consumed = state[_CONSUMED]
        slot = jax.lax.rem(consumed, 2)
        nslot = 1 - slot

        # Prefetch the next live pair (possibly the next tile) while
        # this pair computes — kills the per-tile cold stall.
        @pl.when(c < c_last)
        def _():
            start_fetch(t, c + 1, nslot)

        @pl.when(jnp.logical_and(c == c_last, t + 1 < num_tiles))
        def _():
            start_fetch(t + 1, 0, nslot)

        wait_fetch(t, c, slot)

        # Merge each row whose current position lives in this chunk
        # into its fetched page, and start the full-page writeback —
        # this IS the cache write. The new rows arrive pre-sliced for
        # the tile, so the select is one (ps, GD) where per row.
        kn_all = k_new_ref[...]                          # (R, GD)
        vn_all = v_new_ref[...]
        for r in range(R):
            row = t * R + r
            cur = seq_lens_ref[row] - 1
            cur_page_j = cur // page_size
            cur_chunk = cur_page_j // ppc                # -1 if seq==0
            jj = cur_page_j - cur_chunk * ppc
            s = cur - cur_page_j * page_size
            do_merge = c == cur_chunk
            # Write back only the 8-sublane tile holding the new row,
            # not the whole page: at page_size 256 a full-page RMW write
            # is 256x write amplification (~33 MB/call at B=64 — half
            # the kernel's traffic). The tile offset is a multiple of 8
            # by construction, satisfying Mosaic's sublane alignment.
            tile_lo = (s // 8) * 8
            for j in range(ppc):
                @pl.when(jnp.logical_and(do_merge, j == jj))
                def _():
                    sl = jax.lax.broadcasted_iota(
                        jnp.int32, (page_size, 1), 0)
                    keep = sl != s
                    k_scratch[slot, r, j] = jnp.where(
                        keep, k_scratch[slot, r, j],
                        kn_all[r:r + 1].astype(k_scratch.dtype))
                    v_scratch[slot, r, j] = jnp.where(
                        keep, v_scratch[slot, r, j],
                        vn_all[r:r + 1].astype(v_scratch.dtype))
                    wp = write_page_ref[row]
                    pltpu.make_async_copy(
                        k_scratch.at[slot, r, j, pl.ds(tile_lo, 8)],
                        k_out.at[lyr, wp, pl.ds(tile_lo, 8)],
                        wsem.at[0, r]).start()
                    pltpu.make_async_copy(
                        v_scratch.at[slot, r, j, pl.ds(tile_lo, 8)],
                        v_out.at[lyr, wp, pl.ds(tile_lo, 8)],
                        wsem.at[1, r]).start()

        S = chunk_tokens
        GD = acc_ref.shape[2]
        q = qbd_ref[...]                                # (R, H, GD)
        k = k_scratch[slot].reshape(R, S, GD)
        v = v_scratch[slot].reshape(R, S, GD)
        # Batched over the tile: contract GD, batch dim R. Operands stay
        # bf16 — the MXU consumes bf16 natively with f32 accumulation;
        # f32 inputs run emulated at a fraction of the rate.
        dims = (((2,), (2,)), ((0,), (0,)))
        logits = jax.lax.dot_general(
            q, k, dims,
            preferred_element_type=jnp.float32) * scale   # (R, H, S)
        H = acc_ref.shape[1]
        # The bias carries 8 identical sublane rows; take one and let
        # the VPU broadcast it across the H query heads (same values —
        # liveness varies only per (row, position)).
        bias = bias_ref[...].reshape(R, 8, S)[:, :1, :]
        logits = logits + jnp.broadcast_to(
            bias.astype(jnp.float32), (R, H, S))

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (R, H, GD)
        acc_ref[...] = acc_ref[...] * alpha + pv

        # Drain this pair's writebacks. Placed after the attention math
        # so the page DMAs overlap it; completing before the step ends
        # keeps the slot-reuse invariant (see module docstring). The
        # wait descriptor's page index is irrelevant — only the byte
        # count (one page) and the semaphore matter.
        for r in range(R):
            row = t * R + r
            cur = seq_lens_ref[row] - 1
            cur_chunk = (cur // page_size) // ppc

            @pl.when(c == cur_chunk)
            def _():
                wp = write_page_ref[row]
                pltpu.make_async_copy(
                    k_scratch.at[slot, r, 0, pl.ds(0, 8)],
                    k_out.at[lyr, wp, pl.ds(0, 8)],
                    wsem.at[0, r]).wait()
                pltpu.make_async_copy(
                    v_scratch.at[slot, r, 0, pl.ds(0, 8)],
                    v_out.at[lyr, wp, pl.ds(0, 8)],
                    wsem.at[1, r]).wait()

        state[_CONSUMED] = consumed + 1

    @pl.when(c == num_chunks - 1)
    def _():
        # Zero guard: a seq_len == 0 row computes no chunk, leaving l at
        # 0 — emit 0 (matching the other paged kernels) instead of 0/0.
        res = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)  # (R,H,GD)
        # Un-blockdiagonal: group g's heads only populated columns
        # [g·D, (g+1)·D) — emit the compact (R, H, D) directly (the
        # old H×GD output cost another ~0.3 ms/step of HBM traffic).
        D = out_ref.shape[2]
        Hkv = out_ref.shape[1] // n_rep
        for g in range(Hkv):
            out_ref[:, g * n_rep:(g + 1) * n_rep, :] = res[
                :, g * n_rep:(g + 1) * n_rep,
                g * D:(g + 1) * D].astype(out_ref.dtype)


def _tile_plan(B: int, page_size: int, max_pages: int, GD: int,
               itemsize: int, pages_per_chunk: int = 0):
    """Row-tile/chunk sizing under the ~12 MB scoped-VMEM budget.
    Returns (R, ppc) or None when no LEGAL plan exists: Mosaic requires
    the (R, GD) blocks' second-minor dim divisible by 8 OR equal to the
    whole array dim — so the only legal row tiles are R=8 (when it
    divides B) and R=B (whole-array block, covers B<8 and odd B)."""
    def kv_scratch_bytes(r_, ppc_):
        return 2 * 2 * r_ * ppc_ * page_size * GD * itemsize

    if pages_per_chunk <= 0:
        pages_per_chunk = max(1, 256 // page_size)
    candidates = ([8] if B % 8 == 0 and B != 8 else []) + [B]
    for R in candidates:
        ppc = min(pages_per_chunk, max_pages)
        while max_pages % ppc:
            ppc -= 1
        while ppc > 1 and kv_scratch_bytes(R, ppc) > 12 * 2**20:
            ppc = max(1, ppc // 2)
            while max_pages % ppc:
                ppc -= 1
        if kv_scratch_bytes(R, ppc) <= 12 * 2**20:
            return R, ppc
    return None


def fused_kernel_viable(B: int, page_size: int, max_pages: int, GD: int,
                        itemsize: int = 2) -> bool:
    """Whether the fused kernel has a legal tile plan for this geometry
    (large-GD models at big page sizes may not — e.g. llama3-8b's
    GD=1024 at 256-token pages forces R=4, an illegal block). Callers
    route to the split write+attention path when False."""
    return _tile_plan(B, page_size, max_pages, GD, itemsize) is not None


def fused_decode_attention_pallas(
    q: jnp.ndarray,             # (B, H, D)
    k_new: jnp.ndarray,         # (B, H_kv, D) or (B, H_kv·D)
    v_new: jnp.ndarray,
    k_pool: jnp.ndarray,        # (L, P, page_size, H_kv·D) FLAT
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, max_pages) int32
    seq_lens: jnp.ndarray,      # (B,) int32 (pos+1, incl. current)
    write_page: jnp.ndarray,    # (B,) int32 — pool page id to write
    layer: jnp.ndarray | int = 0,
    *,
    pages_per_chunk: int = 0,
    interpret: bool = False,
):
    """Fused decode step: write the current tokens' KV into the pool
    (in place, aliased) AND return attention over the updated history.
    Returns (attn (B, H, D), k_pool, v_pool).

    ``write_page`` must equal ``block_tables[b, (seq_lens[b]-1)//ps]``
    for live rows (the engine's invariant) or 0 for inactive rows.
    All live rows' write pages must be distinct.

    ``pages_per_chunk=0`` (default) sizes chunks to ~256 tokens.
    """
    B, H, D = q.shape
    L, P, page_size, GD = k_pool.shape
    Hkv = GD // D
    max_pages = block_tables.shape[1]
    n_rep = H // Hkv
    if GD % 128:
        raise ValueError(f"H_kv*D = {GD} must be a multiple of 128")
    plan = _tile_plan(B, page_size, max_pages, GD, k_pool.dtype.itemsize,
                      pages_per_chunk)
    if plan is None:
        raise ValueError(
            f"no legal fused-kernel tile plan for B={B} "
            f"page_size={page_size} GD={GD} (route via "
            f"fused_kernel_viable before calling)")
    R, ppc = plan
    num_tiles = B // R
    num_chunks = max_pages // ppc

    # q goes in RAW (B, H, D); the kernel builds the block-diagonal GQA
    # layout in VMEM (the old HBM-materialized H×GD q + H×GD output
    # cost ~0.6 ms/step of pure traffic at B=64, H=32).
    # Additive mask, chunk-blocked: (B, num_chunks, 8, S) with 0 on
    # positions < seq_len and -1e30 beyond (built here because Mosaic
    # can't stack SMEM scalars into vectors; 8 identical sublane rows —
    # the MINIMUM tile-aligned height, broadcast to H inside the kernel
    # — instead of H copies: at H=32 that is 4x less bias HBM traffic;
    # bf16 because its exponent range covers -1e30 at half the bytes).
    S = ppc * page_size
    pos_all = (jnp.arange(num_chunks * S, dtype=jnp.int32)
               .reshape(1, num_chunks, 1, S))
    bias = jnp.where(pos_all < seq_lens.reshape(B, 1, 1, 1),
                     0.0, NEG_INF).astype(jnp.bfloat16)
    bias = jnp.broadcast_to(bias, (B, num_chunks, 8, S))
    kn = k_new.reshape(B, GD).astype(k_pool.dtype)
    vn = v_new.reshape(B, GD).astype(v_pool.dtype)

    kernel = functools.partial(
        _fused_kernel, rows_per_tile=R, pages_per_chunk=ppc,
        page_size=page_size, num_chunks=num_chunks, batch=B,
        n_rep=n_rep, scale=D ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(num_tiles, num_chunks),
        in_specs=[
            pl.BlockSpec((R, H, D), lambda t, c, *_: (t, 0, 0)),
            pl.BlockSpec((R, GD), lambda t, c, *_: (t, 0)),
            pl.BlockSpec((R, GD), lambda t, c, *_: (t, 0)),
            pl.BlockSpec((R, 1, 8, S), lambda t, c, *_: (t, c, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((R, H, D), lambda t, c, *_: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, GD), jnp.float32),
            pltpu.VMEM((R, H, GD), q.dtype),
            pltpu.VMEM((2, R, ppc, page_size, GD), k_pool.dtype),
            pltpu.VMEM((2, R, ppc, page_size, GD), v_pool.dtype),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, R)),
        ],
    )
    # Operands: 4 scalar-prefetch, then q, kn, vn, bias, pools →
    # pool operands 8/9 alias outputs 1/2. Pools are ALREADY flat
    # (L, P, ps, GD) — any reshape here would break XLA's aliasing and
    # copy both pools every call (see init_kv_pages).
    out, k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q.dtype),
                   jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      write_page.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q, kn, vn, bias, k_pool, v_pool)
    return out.astype(q.dtype), (k_out, v_out)


# -- int8 KV variant -----------------------------------------------------------
#
# Same structure as _fused_kernel with three deltas:
# 1. pool pages are int8 (HALF the fetch/writeback DMA bytes — decode is
#    bandwidth-bound, so this is the point);
# 2. per-(token, kv-head) bf16 scale pools (L, P, H_kv, page_size) ride
#    along: scale pages are fetched/merged/written back next to their
#    data pages on separate semaphores (DMA semaphore sharers must copy
#    identical byte counts; scale pages are 2·H_kv·ps bytes vs GD·ps);
# 3. dequantization happens in-register at the matmuls: K scales
#    multiply LOGITS groupwise (the (head, position) scale layout IS the
#    logits layout — no transpose), V scales fold into the probabilities
#    before the PV matmul.


def _fused_kernel_q8(
    # scalar prefetch (SMEM)
    block_tables_ref, seq_lens_ref, write_page_ref, layer_ref,
    # inputs
    q_ref,              # (R, H, D) VMEM bf16
    k_new_ref,          # (R, GD) VMEM int8 — pre-quantized current rows
    v_new_ref,          # (R, GD) VMEM int8
    kns_ref,            # (R, Hkv, ps) bf16 — new K scales, pre-broadcast
    vns_ref,            # (R, Hkv, ps) bf16
    bias_ref,           # (R, 1, 8, S) bf16
    k_hbm, v_hbm,       # (L, P, ps, GD) int8 ANY — aliased
    ks_hbm, vs_hbm,     # (L, P, Hkv, ps) bf16 ANY — aliased
    # outputs
    out_ref,            # (R, H, D)
    k_out, v_out, ks_out, vs_out,
    # scratch
    m_ref, l_ref, acc_ref, qbd_ref,
    k_scratch, v_scratch,           # (2, R, ppc, ps, GD) int8
    ks_scratch, vs_scratch,         # (2, R, ppc, Hkv, ps) bf16
    state, sem, ssem, wsem, swsem,
    *,
    rows_per_tile: int,
    pages_per_chunk: int,
    page_size: int,
    num_chunks: int,
    batch: int,
    n_rep: int,
    scale: float,
):
    t = pl.program_id(0)
    c = pl.program_id(1)
    R = rows_per_tile
    ppc = pages_per_chunk
    chunk_tokens = ppc * page_size
    num_tiles = pl.num_programs(0)
    lyr = layer_ref[0]

    def row_c_last(row):
        eff = jnp.maximum(seq_lens_ref[row], 1)
        return (eff - 1) // chunk_tokens

    def tile_c_last(tile):
        m = row_c_last(tile * R)
        for r in range(1, R):
            m = jnp.maximum(m, row_c_last(tile * R + r))
        return m

    def start_fetch(tile, chunk, slot):
        base = chunk * ppc
        for r in range(R):
            row = tile * R + r
            eff = jnp.maximum(seq_lens_ref[row], 1)
            for j in range(ppc):
                live = (base + j) * page_size < eff

                @pl.when(live)
                def _():
                    pid = block_tables_ref[row, base + j]
                    pltpu.make_async_copy(
                        k_out.at[lyr, pid], k_scratch.at[slot, r, j],
                        sem.at[0, slot]).start()
                    pltpu.make_async_copy(
                        v_out.at[lyr, pid], v_scratch.at[slot, r, j],
                        sem.at[1, slot]).start()
                    pltpu.make_async_copy(
                        ks_out.at[lyr, pid], ks_scratch.at[slot, r, j],
                        ssem.at[0, slot]).start()
                    pltpu.make_async_copy(
                        vs_out.at[lyr, pid], vs_scratch.at[slot, r, j],
                        ssem.at[1, slot]).start()

    def wait_fetch(tile, chunk, slot):
        base = chunk * ppc
        for r in range(R):
            row = tile * R + r
            eff = jnp.maximum(seq_lens_ref[row], 1)
            for j in range(ppc):
                live = (base + j) * page_size < eff

                @pl.when(live)
                def _():
                    pid = block_tables_ref[row, base + j]
                    pltpu.make_async_copy(
                        k_out.at[lyr, pid], k_scratch.at[slot, r, j],
                        sem.at[0, slot]).wait()
                    pltpu.make_async_copy(
                        v_out.at[lyr, pid], v_scratch.at[slot, r, j],
                        sem.at[1, slot]).wait()
                    pltpu.make_async_copy(
                        ks_out.at[lyr, pid], ks_scratch.at[slot, r, j],
                        ssem.at[0, slot]).wait()
                    pltpu.make_async_copy(
                        vs_out.at[lyr, pid], vs_scratch.at[slot, r, j],
                        ssem.at[1, slot]).wait()

    @pl.when(jnp.logical_and(t == 0, c == 0))
    def _():
        state[_CONSUMED] = 0
        k_scratch[...] = jnp.zeros_like(k_scratch)
        v_scratch[...] = jnp.zeros_like(v_scratch)
        # Scale scratch must be FINITE too: dead positions contribute
        # k_stale·scale_stale through the masked softmax; a NaN scale
        # would ride straight through the additive mask.
        ks_scratch[...] = jnp.zeros_like(ks_scratch)
        vs_scratch[...] = jnp.zeros_like(vs_scratch)
        start_fetch(0, 0, 0)

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -1e29)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        qbd_ref[...] = jnp.zeros_like(qbd_ref)
        D = q_ref.shape[2]
        Hkv = q_ref.shape[1] // n_rep
        for g in range(Hkv):
            qbd_ref[:, g * n_rep:(g + 1) * n_rep, g * D:(g + 1) * D] = (
                q_ref[:, g * n_rep:(g + 1) * n_rep, :])

    c_last = tile_c_last(t)
    fetched = c <= c_last

    @pl.when(fetched)
    def _():
        consumed = state[_CONSUMED]
        slot = jax.lax.rem(consumed, 2)
        nslot = 1 - slot

        @pl.when(c < c_last)
        def _():
            start_fetch(t, c + 1, nslot)

        @pl.when(jnp.logical_and(c == c_last, t + 1 < num_tiles))
        def _():
            start_fetch(t + 1, 0, nslot)

        wait_fetch(t, c, slot)

        kn_all = k_new_ref[...]                          # (R, GD) int8
        vn_all = v_new_ref[...]
        for r in range(R):
            row = t * R + r
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
                    k_scratch[slot, r, j] = jnp.where(
                        keep, k_scratch[slot, r, j],
                        kn_all[r:r + 1].astype(k_scratch.dtype))
                    v_scratch[slot, r, j] = jnp.where(
                        keep, v_scratch[slot, r, j],
                        vn_all[r:r + 1].astype(v_scratch.dtype))
                    # Scale column s ← this row's per-head scales (the
                    # input arrives pre-broadcast along ps, so the
                    # merge is one lane-select).
                    li = jax.lax.broadcasted_iota(
                        jnp.int32, (ks_scratch.shape[3], page_size), 1)
                    skeep = li != s
                    ks_scratch[slot, r, j] = jnp.where(
                        skeep, ks_scratch[slot, r, j], kns_ref[r])
                    vs_scratch[slot, r, j] = jnp.where(
                        skeep, vs_scratch[slot, r, j], vns_ref[r])
                    wp = write_page_ref[row]
                    pltpu.make_async_copy(
                        k_scratch.at[slot, r, j, pl.ds(tile_lo, 8)],
                        k_out.at[lyr, wp, pl.ds(tile_lo, 8)],
                        wsem.at[0, r]).start()
                    pltpu.make_async_copy(
                        v_scratch.at[slot, r, j, pl.ds(tile_lo, 8)],
                        v_out.at[lyr, wp, pl.ds(tile_lo, 8)],
                        wsem.at[1, r]).start()
                    # Scale pages are tiny (Hkv·ps bf16): write whole.
                    pltpu.make_async_copy(
                        ks_scratch.at[slot, r, j],
                        ks_out.at[lyr, wp], swsem.at[0, r]).start()
                    pltpu.make_async_copy(
                        vs_scratch.at[slot, r, j],
                        vs_out.at[lyr, wp], swsem.at[1, r]).start()

        S = chunk_tokens
        GD = acc_ref.shape[2]
        Hkv = ks_scratch.shape[3]
        H = acc_ref.shape[1]
        q = qbd_ref[...]                                # (R, H, GD)
        k = k_scratch[slot].reshape(R, S, GD).astype(jnp.bfloat16)
        v = v_scratch[slot].reshape(R, S, GD).astype(jnp.bfloat16)
        dims = (((2,), (2,)), ((0,), (0,)))
        logits = jax.lax.dot_general(
            q, k, dims,
            preferred_element_type=jnp.float32) * scale   # (R, H, S)

        def head_scales(s_scratch):
            """(2, R, ppc, Hkv, ps) scratch → (R, H, S) f32 multiplier:
            pages lane-concatenated into the chunk's S axis, groups
            expanded to their n_rep query heads (g-major head order —
            matches the block-diagonal q layout). Reads the slot's
            scratch ONCE and slices the VALUE — a mixed ref-slice
            (``[slot, :, j]``) mis-lowered on real Mosaic (caught by an
            on-chip A/B; interpret mode masked it)."""
            full = s_scratch[slot]                   # (R, ppc, Hkv, ps)
            pages = [full[:, j] for j in range(ppc)]
            hs = (pages[0] if ppc == 1
                  else jnp.concatenate(pages, axis=2))     # (R, Hkv, S)
            rows = []
            for g in range(Hkv):
                rows.extend([hs[:, g:g + 1, :]] * n_rep)
            return jnp.concatenate(rows, axis=1).astype(jnp.float32)

        # Dequantize K: the (head, position) scale layout IS the logits
        # layout — one elementwise multiply, no transpose.
        logits = logits * head_scales(ks_scratch)
        bias = bias_ref[...].reshape(R, 8, S)[:, :1, :]
        logits = logits + jnp.broadcast_to(
            bias.astype(jnp.float32), (R, H, S))

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        # Dequantize V by folding its scales into the probabilities
        # BEFORE the PV matmul: out = Σ_s (p·vscale)[s] · v_int8[s].
        p = p * head_scales(vs_scratch)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (R, H, GD)
        acc_ref[...] = acc_ref[...] * alpha + pv

        for r in range(R):
            row = t * R + r
            cur = seq_lens_ref[row] - 1
            cur_chunk = (cur // page_size) // ppc

            @pl.when(c == cur_chunk)
            def _():
                wp = write_page_ref[row]
                pltpu.make_async_copy(
                    k_scratch.at[slot, r, 0, pl.ds(0, 8)],
                    k_out.at[lyr, wp, pl.ds(0, 8)],
                    wsem.at[0, r]).wait()
                pltpu.make_async_copy(
                    v_scratch.at[slot, r, 0, pl.ds(0, 8)],
                    v_out.at[lyr, wp, pl.ds(0, 8)],
                    wsem.at[1, r]).wait()
                pltpu.make_async_copy(
                    ks_scratch.at[slot, r, 0],
                    ks_out.at[lyr, wp], swsem.at[0, r]).wait()
                pltpu.make_async_copy(
                    vs_scratch.at[slot, r, 0],
                    vs_out.at[lyr, wp], swsem.at[1, r]).wait()

        state[_CONSUMED] = consumed + 1

    @pl.when(c == num_chunks - 1)
    def _():
        res = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)  # (R,H,GD)
        D = out_ref.shape[2]
        Hkv = out_ref.shape[1] // n_rep
        for g in range(Hkv):
            out_ref[:, g * n_rep:(g + 1) * n_rep, :] = res[
                :, g * n_rep:(g + 1) * n_rep,
                g * D:(g + 1) * D].astype(out_ref.dtype)


def fused_decode_attention_q8_pallas(
    q: jnp.ndarray,             # (B, H, D) bf16
    k_new_q: jnp.ndarray,       # (B, H_kv, D) int8 — pre-quantized
    k_new_scale: jnp.ndarray,   # (B, H_kv) bf16
    v_new_q: jnp.ndarray,
    v_new_scale: jnp.ndarray,
    pools,                      # (k, v, k_scale, v_scale) — k/v int8
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    write_page: jnp.ndarray,
    layer: jnp.ndarray | int = 0,
    *,
    pages_per_chunk: int = 0,
    interpret: bool = False,
):
    """int8-KV fused decode step (see _fused_kernel_q8). Returns
    (attn (B, H, D), pools)."""
    k_pool, v_pool, ks_pool, vs_pool = pools
    B, H, D = q.shape
    L, P, page_size, GD = k_pool.shape
    Hkv = GD // D
    max_pages = block_tables.shape[1]
    n_rep = H // Hkv
    if GD % 128:
        raise ValueError(f"H_kv*D = {GD} must be a multiple of 128")
    plan = _tile_plan(B, page_size, max_pages, GD, k_pool.dtype.itemsize,
                      pages_per_chunk)
    if plan is None:
        raise ValueError(
            f"no legal q8 fused tile plan for B={B} "
            f"page_size={page_size} GD={GD}")
    R, ppc = plan
    num_tiles = B // R
    num_chunks = max_pages // ppc

    S = ppc * page_size
    pos_all = (jnp.arange(num_chunks * S, dtype=jnp.int32)
               .reshape(1, num_chunks, 1, S))
    bias = jnp.where(pos_all < seq_lens.reshape(B, 1, 1, 1),
                     0.0, NEG_INF).astype(jnp.bfloat16)
    bias = jnp.broadcast_to(bias, (B, num_chunks, 8, S))
    kn = k_new_q.reshape(B, GD)
    vn = v_new_q.reshape(B, GD)
    # Scales pre-broadcast along the page dim: the kernel's merge is
    # then a single lane-select against the fetched scale page.
    kns = jnp.broadcast_to(
        k_new_scale.astype(jnp.bfloat16)[:, :, None], (B, Hkv, page_size))
    vns = jnp.broadcast_to(
        v_new_scale.astype(jnp.bfloat16)[:, :, None], (B, Hkv, page_size))

    kernel = functools.partial(
        _fused_kernel_q8, rows_per_tile=R, pages_per_chunk=ppc,
        page_size=page_size, num_chunks=num_chunks, batch=B,
        n_rep=n_rep, scale=D ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(num_tiles, num_chunks),
        in_specs=[
            pl.BlockSpec((R, H, D), lambda t, c, *_: (t, 0, 0)),
            pl.BlockSpec((R, GD), lambda t, c, *_: (t, 0)),
            pl.BlockSpec((R, GD), lambda t, c, *_: (t, 0)),
            pl.BlockSpec((R, Hkv, page_size), lambda t, c, *_: (t, 0, 0)),
            pl.BlockSpec((R, Hkv, page_size), lambda t, c, *_: (t, 0, 0)),
            pl.BlockSpec((R, 1, 8, S), lambda t, c, *_: (t, c, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((R, H, D), lambda t, c, *_: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, GD), jnp.float32),
            pltpu.VMEM((R, H, GD), q.dtype),
            pltpu.VMEM((2, R, ppc, page_size, GD), k_pool.dtype),
            pltpu.VMEM((2, R, ppc, page_size, GD), v_pool.dtype),
            pltpu.VMEM((2, R, ppc, Hkv, page_size), ks_pool.dtype),
            pltpu.VMEM((2, R, ppc, Hkv, page_size), vs_pool.dtype),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, R)),
            pltpu.SemaphoreType.DMA((2, R)),
        ],
    )
    # Operand order: 4 scalar-prefetch, q, kn, vn, kns, vns, bias, then
    # the four pools at operands 10-13 aliased to outputs 1-4.
    out, k_out, v_out, ks_out, vs_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q.dtype),
                   jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
                   jax.ShapeDtypeStruct(ks_pool.shape, ks_pool.dtype),
                   jax.ShapeDtypeStruct(vs_pool.shape, vs_pool.dtype)],
        input_output_aliases={10: 1, 11: 2, 12: 3, 13: 4},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      write_page.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q, kn, vn, kns, vns, bias, k_pool, v_pool, ks_pool, vs_pool)
    return out.astype(q.dtype), (k_out, v_out, ks_out, vs_out)
