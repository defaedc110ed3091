"""Pallas TPU kernel: FUSED decode attention + KV-cache write.

One kernel per layer does both the current tokens' cache write and the
paged attention read — vs a write kernel (kv_write.py) followed by a
separate attention pass, with their doubled launch overhead and a
second page round-trip. Two entry points share every line of the
schedule: ``fused_decode_attention_pallas`` (bf16 pools) and
``fused_decode_attention_q8_pallas`` (int8 pools with bf16 scale
pools).

Design (v5 — fifth shape of this kernel; the numbers that drove it):

- r2 kernel: per-row grid, per-row page-merge writeback, within-row
  double buffering → ~34µs/row at B=64 (≈16ms of a 21ms decode step),
  flat in seq_len. The merge (full-batch masked row extraction,
  page-wide selects, staging copies) and the per-row cold DMA stall
  dominated; actual page bandwidth was noise.
- v3: a grid of (B/R row tiles, max_pages/ppc chunks), every step one
  dot_general batched over the tile's R rows, the mask an additive
  bias INPUT. Far from its K/V-bytes roofline in every cell (PERF.md
  §6, PR 29; ledger, PR 28): 63 % at 31 rows, 14-20 % at the 2-4 rows
  the open-loop cells hold. The grid was the block table's WIDTH
  (SmolLM2: 4 tiles x 64 chunks of 64 tokens = 256 steps a call, of
  which a 660-token context is live in 11 a row; a dead step still
  paid the pipeline's step and a bias block fetch, ≈0.24 µs), a tile
  computed all eight rows up to its LONGEST row (DMAs were skipped for
  a row's dead pages, its products were not), and a 64-token chunk
  half-filled the MXU's tiles on the token axis.
- **v4: the unit of work is a live chunk of a live row.** The grid is
  the row tiles alone; inside a tile a ``fori_loop`` runs over the
  chunks up to the tile's last live one, and inside a chunk the rows
  that hold a position there (:func:`_live_pages`, the ONE predicate a
  row's DMA starts, its DMA waits and its products share) are listed
  and visited; nothing else is. The steps a call runs follow the
  batch's ``seq_lens``, not ``max_pages``; a dead row (``seq_len`` 0)
  costs a few scalar reads and nothing else. :func:`decode_work` is the
  same schedule counted on the host. On the chip (PERF.md §6, PR 29;
  one call, v3 → v4): 4 rows of 360 tokens 85 → 27 µs, 2 rows of 2,000
  180 → 54, 31 rows mixed over 300-1,500 417 → 326, Mistral's 60 rows
  over 300-1,500 291 → 241; a block table twice as wide changes none.
- **Listed rows go two at a time** (``_GROUP``; a ragged step's rows
  since v5): a row's visit is a
  dependent chain — QK^T on the MXU, the softmax on the VPU, PV on the
  MXU — whose latencies nothing fills, ≈0.37 µs a visit; the products
  of two rows in ONE straight line of code let the scheduler fill one
  row's bubbles with the other's work (Mistral, 60 rows of 660: 197 →
  183 µs; groups of 4 or 8: 180, 179 — the rest is v3's eight-row
  batch, 165, which also multiplied for rows that were not there).
- **v5: the rows come ordered by context, and a step in which the
  whole tile is live is one static block** (PR 43). v4 met its rows in
  SEAT order, so a tile of eight held contexts drawn at random and was
  ragged in every chunk but its first: at Mistral's served mix (59 of 64
  rows over 130-1,536 tokens) 31 % of the row-chunks ran in a step where
  all eight rows were live, and a tile ran to its one long row's last
  chunk (42.7 steps a call). Handed the rows longest first
  (``ops/attention.decode_order``: a count of the rows that come before
  each row, made ONCE a decode step on the device from the ``seq_lens``
  the step has; dead rows last, ties by row) the same kernel runs 30.7
  steps and 91 % of its row-chunks in full steps (``decode_work``'s
  fourth count, on the host). A full step is ONE group of the tile's R
  rows whose list is 0 … R − 1: the rows are prepared and finished as
  any group's (one trace of ``prepare`` / ``finish`` serves both paths:
  the program grew by 10-14 kB of text a call, not by a second kernel),
  and the products name their rows STATICALLY — every row's logits,
  then every row's softmax update and second product, in one straight
  line. What the one-row visit
  could not hide (its QK^T → softmax → PV chain, the int8 → bf16
  conversion before each product) the scheduler now fills with the
  other rows' work: a listed visit reads its row from SMEM, so every
  index of it is dynamic and two visits' stores to ``m/l/acc`` cannot be
  told apart; a static block's can. On the chip (Mistral's geometry, 60
  rows, µs a call; PERF.md §6, PR 43): the cell's mix over 300-1,500
  shuffled 252.1 → 190.5 (the order alone 242.8, the static block alone
  in seat order 226.9: neither half shows alone); 60 rows of 660 191.8 →
  149.1, under v3's eight-row batch (164.5) without its waste. Outputs
  are v4's to the bit in every order (an order changes no row's
  arithmetic). How the order reaches the body: the dispatchers
  (``ops/attention.paged_decode_step`` / ``_q8``, ``order=``) gather q
  and the new rows by place and put the attention back by batch row at
  every call, the block tables, ``seq_lens`` and write pages being laid
  out once a step — ONE mechanism for every family (``granitemoehybrid``'s
  Mamba layers keep state by batch row, so its rows could not move for
  a step anyway), and the program around the call is the parent's.
  Tried and not kept: blocks of 4 rows (190.2: level with 8), of 2
  (198.6), eight static visits one after another in one straight line
  (232.5; listed pairs: 242.8) — the gain is in the block's order, all
  its rows' first products before any row's softmax, which the
  compiler's scheduler keeps and does not find for itself, so the block
  is the tile and there is no setting; and moving the step's hidden
  rows once in ``llama`` / ``afmoe`` instead of the gathers a call
  (3.3 µs a call at Mistral's geometry, 0.6 % of a step: a second
  mechanism in two families for less than the cell resolves, and its
  gather in front of the final norm cost the logits their bit-equality
  with the parent's).
- **The mask is made in the kernel**: per-row code compares an iota
  with the row's ``seq_len`` scalar, so nothing has to stack SMEM
  scalars into a vector — the bias array (2 MiB a call at SmolLM2's
  geometry), its XLA producer and its per-step block fetch are gone.
- **Chunks of ≥128 tokens** (:func:`_tile_plan`): as wide as 16 MiB of
  K/V scratch allows, up to 256 tokens — 128 at SmolLM2's geometry, 256
  at Mistral's, each the best of the widths tried on the chip. The
  scratch passes the compiler's default scoped VMEM, and the plan says
  so through ``vmem_limit_bytes``.
- **Cross-pair prefetch chain** (kept): each (tile, chunk) step starts
  the next step's DMAs (crossing tile boundaries) into the alternate
  scratch slot before it waits for its own; slot parity is a
  consumed-step counter in SMEM, which persists across grid steps.
- **Per-row fetch semaphores**: a row's pages signal ``sem[pool, slot,
  r]``, so a group's products start when ITS rows' pages have landed
  while the later rows' are still in flight (TPU sflag space is ~2KB,
  ≈500 semaphores: per-(row, page) does not fit, per-(pool, slot, row)
  does). All sharers of a semaphore copy identical byte counts, so
  per-page waits drain in any order; ONE wait for a whole chunk's bytes
  works too (a DMA semaphore counts bytes) and bought 1.5 %: not kept.
- **Tile-sliced merge** (kept): the current token's K/V row is selected
  into its (already fetched) page in scratch, and only the 8-sublane
  tile holding it is written back (sub-tile DMAs are impossible:
  Mosaic requires 2nd-minor slices tile-aligned). The writeback is
  waited for AFTER the row's attention math, so the DMA overlaps
  compute but is done before this scratch slot can be refetched (the
  next step's prefetch targets the other slot).
- **Finite scratch under the mask** (kept, narrowed to where it
  matters): masked logits are SELECTED to -1e30, so stale K scratch
  never reaches the softmax; the probabilities of masked positions are
  exactly 0, but 0·NaN = NaN in the PV product, so what multiplies them
  must be finite: the dead tail pages of a row's last chunk are zeroed
  in the V scratch (bf16 pools) or the V-scale scratch (int8 pools:
  int8 data has no NaN) before that chunk's products. Fresh VMEM can
  hold NaN bit patterns.
- The online-softmax max floor is -1e29, not the mask value: were a
  chunk ever fully masked, p = exp(-1e30 + 1e29) = 0 exactly instead
  of exp(0) = 1 pulling stale V into the accumulator.
- Per-row state: a row's running max / sum / accumulator and its
  block-diagonal q are initialised at ITS first chunk and its output is
  written at ITS last, so a tile's fixed cost follows its live rows
  too; dead rows emit the zeros the tile's output block starts as.

The rolled loops (rows, pages) keep the kernel's code — and the Mosaic
payload each layer's call carries — a few rows' worth, whatever R and
the chunk width are: one call compiles to 0.9-1.2 MB against v3's
3.3-5.2.

Same shape strategy as the other kernels: block-diagonal Q (one MXU
matmul for all heads of a row), pages flattened to (ps, H_kv·D), online
softmax in f32 scratch, bf16 MXU operands with f32 accumulation.
Constraints: all live rows target distinct pages (decode invariant),
H_kv·D % 128 == 0.

int8 KV: pool pages are int8 (HALF the fetch DMA bytes — decode is
bandwidth-bound, so this is the point); per-(token, kv-head) bf16 scale
pools (L, P, H_kv, page_size) ride along, their pages fetched / merged /
written back next to their data pages on semaphores of their own (scale
pages are 2·H_kv·ps bytes vs GD·ps); dequantization happens in-register
at the two products: K scales multiply LOGITS groupwise (the (head,
position) scale layout IS the logits layout — no transpose), V scales
fold into the probabilities before the PV matmul.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

_CONSUMED = 0   # SMEM state: steps consumed so far (slot parity)
#: Live rows whose products run as one straight line of code.
_GROUP = 2

#: Tokens a chunk holds where the scratch budget allows.
CHUNK_TOKENS = 256
#: Most K/V scratch a plan may take: two slots of K and of V
#: for a tile's rows. A v5e core has 128 MiB of VMEM; on the chip 16 MiB
#: (128-token chunks at SmolLM2's 4 KiB a token and pool) beat 32 (256)
#: at every occupancy, and Mistral's 256-token chunks (8 MiB) beat both
#: 128 and 512 (PERF.md §6, PR 29).
SCRATCH_BUDGET_BYTES = 16 * 2**20
#: What a call needs beside that scratch: the f32 accumulator and the
#: block-diagonal q of a tile, the pipelined q / new-row / output
#: blocks, one row's operands in flight and the compiler's own scratch.
_VMEM_HEADROOM_BYTES = 12 * 2**20


class DecodePlan(NamedTuple):
    """How one call is cut (:func:`_tile_plan`)."""
    rows: int               # rows a tile (R)
    pages_per_chunk: int
    chunk_tokens: int       # pages_per_chunk * page_size
    scratch_bytes: int      # K and V scratch, two slots
    vmem_limit_bytes: int   # what the call asks of the compiler


def _first_chunk(seq_len, chunk_tokens: int, window, xp=jnp):
    """The first chunk a row visits: 0, or under a ``window`` the chunk
    that holds its oldest visible key, position ``seq_len - window``
    (the current token is position ``seq_len - 1`` and counts among
    the ``window`` keys it sees)."""
    if window is None:
        return 0
    return xp.maximum(seq_len - window, 0) // chunk_tokens


def _live_pages(seq_len, chunk, pages_per_chunk: int, page_size: int,
                xp=jnp, window=None):
    """How many of ``chunk``'s pages hold a position below ``seq_len``:
    0 where the row is dead in the chunk — past its last position or,
    under a ``window``, before :func:`_first_chunk` (the visit starts
    at a chunk's first page: the pages of that chunk that lie before
    the window are fetched and masked). The ONE liveness predicate —
    a row's DMA starts, DMA waits and products all follow it (starts
    and waits that disagree corrupt the semaphores)."""
    pages = (seq_len + (page_size - 1)) // page_size
    n = xp.clip(pages - chunk * pages_per_chunk, 0, pages_per_chunk)
    if window is None:
        return n
    first = _first_chunk(seq_len, pages_per_chunk * page_size, window, xp)
    return xp.where(chunk >= first, n, 0)


def _tile_chunks(seq_lens, chunk_tokens: int, xp=jnp):
    """Steps a tile runs: up to its longest row's last live chunk, and
    one for a tile with no live row (it hands the prefetch chain on)."""
    longest = seq_lens[0]
    for s in seq_lens[1:]:
        longest = xp.maximum(longest, s)
    return xp.maximum((longest + (chunk_tokens - 1)) // chunk_tokens, 1)


def _tile_first_chunk(seq_lens, n_chunks, chunk_tokens: int, window,
                      xp=jnp):
    """Where a tile's steps start: 0, or under a ``window`` the earliest
    first chunk of its live rows (a dead row has none), at most the
    tile's last step."""
    if window is None:
        return 0
    first = n_chunks - 1
    for s in seq_lens:
        first = xp.minimum(first, xp.where(
            s > 0, _first_chunk(s, chunk_tokens, window, xp), first))
    return first


def _decode_kernel(*refs, quantized: bool, rows_per_tile: int,
                   pages_per_chunk: int, page_size: int, n_rep: int,
                   scale: float, window=None):
    """Both kernels' body. ``refs`` (scalar prefetch, inputs, outputs,
    scratch), the int8 form's extras in brackets:

    block_tables (B, max_pages), seq_lens (B,) — pos+1, current token
    included —, write_page (B,), layer (1,): int32 SMEM;
    q (R, H, D) RAW query heads (the block-diagonal GQA layout is built
    in VMEM: an H×GD q in HBM cost ~0.3 ms/step of pure traffic at
    B=64); k_new, v_new (R, GD) this tile's current rows [int8,
    pre-quantized]; [kns, vns (R, Hkv, ps) bf16 new scales, pre-
    broadcast along the page]; the pools (L, P, ps, GD) [and scale pools
    (L, P, Hkv, ps)] in ANY, aliased to the outputs after ``out``
    (R, H, D) — all DMAs target the outputs;
    m, l (R, H, 1), acc (R, H, GD) f32; qbd (R, H, GD); k_buf, v_buf
    (2, R, ppc, ps, GD) [ks_buf, vs_buf (2, R, ppc, Hkv, ps)]; state
    SMEM (1,); live_rows, live_pages_of SMEM (R,) — the rows live in
    the step's chunk and their page counts; sem DMA (pools, 2, R)
    fetches; wsem DMA (pools, R) writebacks.
    """
    n_pools = 4 if quantized else 2
    refs = iter(refs)

    def take(n):
        return [next(refs) for _ in range(n)]

    bt_ref, seq_lens_ref, write_page_ref, layer_ref = take(4)
    q_ref, k_new_ref, v_new_ref = take(3)
    new_scale_refs = take(2 if quantized else 0)
    take(n_pools)           # the pools as inputs: aliased to the outputs
    out_ref, *pools = take(1 + n_pools)
    m_ref, l_ref, acc_ref, qbd_ref = take(4)
    bufs = take(n_pools)
    state, live_rows, live_pages_of, sem, wsem = take(5)
    k_buf, v_buf = bufs[:2]

    t = pl.program_id(0)
    num_tiles = pl.num_programs(0)
    R = rows_per_tile
    ppc = pages_per_chunk
    S = ppc * page_size
    H, GD = acc_ref.shape[1], acc_ref.shape[2]
    D = q_ref.shape[2]
    Hkv = H // n_rep
    lyr = layer_ref[0]

    def live_pages(row, chunk):
        return _live_pages(seq_lens_ref[row], chunk, ppc, page_size,
                           window=window)

    def first_chunk(row):
        if window is None:
            return 0
        return _first_chunk(seq_lens_ref[row], S, window)

    def tile_first_chunk(tile):
        """Where ``tile``'s steps start."""
        if window is None:
            return 0
        lens = [seq_lens_ref[tile * R + r] for r in range(R)]
        return _tile_first_chunk(lens, _tile_chunks(lens, S), S, window)

    def start_fetch(tile, chunk, slot):
        """Start the DMAs of every live (row, page) of (tile, chunk)."""
        def row_body(r, _):
            row = tile * R + r

            def page_body(j, _):
                pid = bt_ref[row, chunk * ppc + j]
                for i in range(n_pools):
                    pltpu.make_async_copy(
                        pools[i].at[lyr, pid], bufs[i].at[slot, r, j],
                        sem.at[i, slot, r]).start()
                return 0

            jax.lax.fori_loop(0, live_pages(row, chunk), page_body, 0)
            return 0

        jax.lax.fori_loop(0, R, row_body, 0)

    def wait_row(r, n_live, slot):
        """Wait for the row's ``n_live`` pages. A wait descriptor's
        addresses are irrelevant — only its byte count and the
        semaphore matter."""
        def page_body(j, _):
            for i in range(n_pools):
                landed = bufs[i].at[slot, r, j]
                pltpu.make_async_copy(landed, landed,
                                      sem.at[i, slot, r]).wait()
            return 0

        jax.lax.fori_loop(0, n_live, page_body, 0)

    def writeback(r, slot, j, tile_lo, wp):
        """The merged row's copies back to the pools: the 8-sublane tile
        holding it (at page_size 256 a full-page write is 256x write
        amplification; the offset is a multiple of 8 by construction,
        which is Mosaic's sublane alignment), and the whole scale page
        (tiny: Hkv·ps bf16)."""
        copies = [pltpu.make_async_copy(
            bufs[i].at[slot, r, j, pl.ds(tile_lo, 8)],
            pools[i].at[lyr, wp, pl.ds(tile_lo, 8)],
            wsem.at[i, r]) for i in range(2)]
        copies += [pltpu.make_async_copy(
            bufs[i].at[slot, r, j], pools[i].at[lyr, wp],
            wsem.at[i, r]) for i in range(2, n_pools)]
        return copies

    def new_row(ref, r):
        """Row ``r`` of the tile's (R, GD) block as (1, GD): a masked
        sum over the block's few sublanes, exact (one term is not 0) —
        a dynamic one-row slice of a packed dtype is not something to
        ask of Mosaic."""
        x = ref[...]
        mine = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) == r
        return jnp.sum(jnp.where(mine, x.astype(jnp.float32), 0.0),
                       axis=0, keepdims=True).astype(x.dtype)

    def head_rows(g):
        """Where KV head ``g``'s query heads sit: (block-diagonal row,
        query head) pairs. Rows are REP-major — row j·Hkv + g holds
        query head g·n_rep + j — so the rows of one repetition are the
        KV heads in order, which is the layout of a scale page."""
        return [(j * Hkv + g, g * n_rep + j) for j in range(n_rep)]

    def head_scales(pages):
        """(ppc, Hkv, ps) scale pages → (H, S) f32 multiplier: pages
        lane-concatenated into the chunk's S axis, and that (Hkv, S)
        block repeated once a repetition (whole sublane tiles at the
        eight KV heads the int8 kernel serves: no shuffle). Slices the
        VALUE read once from the scratch — a mixed ref-slice
        (``[slot, :, j]``) mis-lowered on real Mosaic (caught by an
        on-chip A/B; interpret mode masked it)."""
        hs = (pages[0] if ppc == 1 else jnp.concatenate(
            [pages[j] for j in range(ppc)], axis=1))       # (Hkv, S)
        hs = hs.astype(jnp.float32)
        return hs if n_rep == 1 else jnp.concatenate([hs] * n_rep, axis=0)

    def last_chunk(row, c):
        """Whether ``c`` is the chunk the row's current token lives in
        (its last live chunk), and the token's position."""
        cur = seq_lens_ref[row] - 1
        cur_page = cur // page_size
        return cur_page // ppc == c, cur, cur_page

    def prepare(r, row, c, slot, n_live):
        """Before row ``r``'s products in its live chunk ``c``: its state
        set up at its first chunk, its pages waited for, and in its last
        chunk the current token merged and its writeback started."""
        last, cur, cur_page = last_chunk(row, c)

        @pl.when(c == first_chunk(row))
        def _():
            # Floor at -1e29 (not -1e30): see the module docstring.
            m_ref[r] = jnp.full(m_ref.shape[1:], -1e29, m_ref.dtype)
            l_ref[r] = jnp.zeros(l_ref.shape[1:], l_ref.dtype)
            acc_ref[r] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)
            # Block-diagonal GQA q: group g's queries live in GD columns
            # [g·D, (g+1)·D) so ONE matmul serves all heads against the
            # (S, GD) page layout.
            qbd_ref[r] = jnp.zeros(qbd_ref.shape[1:], qbd_ref.dtype)
            for g in range(Hkv):
                for at, h in head_rows(g):
                    qbd_ref[r, at:at + 1, g * D:(g + 1) * D] = (
                        q_ref[r, h:h + 1, :])

        wait_row(r, n_live, slot)

        @pl.when(last)
        def _():
            # What multiplies the probabilities must be finite beyond
            # the row's last page too (0·NaN = NaN): zero the dead tail.
            tail = bufs[3] if quantized else v_buf

            def zero_page(j, _):
                tail[slot, r, j] = jnp.zeros(tail.shape[3:], tail.dtype)
                return 0

            jax.lax.fori_loop(n_live, ppc, zero_page, 0)

            # Merge the current token into its fetched page and start
            # the writeback — this IS the cache write. The new rows
            # arrive pre-sliced for the tile, so the select is one
            # (ps, GD) where.
            j = cur_page - c * ppc
            s = cur - cur_page * page_size
            keep = jax.lax.broadcasted_iota(
                jnp.int32, (page_size, 1), 0) != s
            k_buf[slot, r, j] = jnp.where(
                keep, k_buf[slot, r, j], new_row(k_new_ref, r))
            v_buf[slot, r, j] = jnp.where(
                keep, v_buf[slot, r, j], new_row(v_new_ref, r))
            if quantized:
                # Scale column s ← this row's per-head scales (the
                # input arrives pre-broadcast along ps, so the merge is
                # one lane-select).
                skeep = jax.lax.broadcasted_iota(
                    jnp.int32, (Hkv, page_size), 1) != s
                for buf, new in zip(bufs[2:], new_scale_refs):
                    buf[slot, r, j] = jnp.where(
                        skeep, buf[slot, r, j], new[r])
            for copy in writeback(r, slot, j, (s // 8) * 8,
                                  write_page_ref[row]):
                copy.start()

    def scores(r, row, c, slot):
        """Row ``r``'s masked logits (H, S) over its live chunk ``c``."""
        q = qbd_ref[r]                                       # (H, GD)
        k = k_buf[slot, r].reshape(S, GD)
        if quantized:
            k = k.astype(jnp.bfloat16)
        # Operands stay bf16 — the MXU consumes bf16 natively with f32
        # accumulation; f32 inputs run emulated at a fraction of the
        # rate.
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (H, S)
        if quantized:
            # Dequantize K: the (head, position) scale layout IS the
            # logits layout — one elementwise multiply, no transpose.
            logits = logits * head_scales(bufs[2][slot, r])
        pos = c * S + jax.lax.broadcasted_iota(jnp.int32, (H, S), 1)
        seen = pos < seq_lens_ref[row]
        if window is not None:
            seen = seen & (pos >= seq_lens_ref[row] - window)
        return jnp.where(seen, logits, NEG_INF)

    def absorb(r, logits, slot):
        """The online-softmax update of ``m/l/acc[r]`` by a chunk's
        ``logits`` and the row's second product."""
        v = v_buf[slot, r].reshape(S, GD)
        if quantized:
            v = v.astype(jnp.bfloat16)
        m_prev = m_ref[r]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[r] = alpha * l_ref[r] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[r] = m_new
        if quantized:
            # Dequantize V by folding its scales into the probabilities
            # BEFORE the PV matmul: out = Σ_s (p·vscale)[s] · v_int8[s].
            p = p * head_scales(bufs[3][slot, r])
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (H, GD)
        acc_ref[r] = acc_ref[r] * alpha + pv

    def attend(rs, c, slot):
        """The two products of the rows ``rs`` (tile rows) over their
        live chunk ``c``, ONE straight line of code: every row's logits,
        then every row's update — so the rows' MXU and VPU chains
        interleave. Over static ``rs`` (a full step's block) no index
        of it is read from SMEM."""
        logits = [scores(r, t * R + r, c, slot) for r in rs]
        for r, x in zip(rs, logits):
            absorb(r, x, slot)

    def finish(r, row, slot):
        """After the products of the row's LAST chunk: drain its
        writebacks — after the attention math, so the DMAs overlapped
        it; before the step ends, which keeps the slot-reuse invariant
        (see the module docstring) — and emit its output."""
        for copy in writeback(r, slot, 0, 0, write_page_ref[row]):
            copy.wait()
        # Un-blockdiagonal: group g's heads only populated columns
        # [g·D, (g+1)·D) — emit the compact (H, D) directly (an H×GD
        # output cost another ~0.3 ms/step of HBM traffic).
        res = acc_ref[r] / l_ref[r]                          # (H, GD)
        for g in range(Hkv):
            for at, h in head_rows(g):
                out_ref[r, h:h + 1, :] = res[
                    at:at + 1, g * D:(g + 1) * D].astype(out_ref.dtype)

    first_c = tile_first_chunk(t)

    @pl.when(t == 0)
    def _():
        state[_CONSUMED] = 0
        start_fetch(0, first_c, 0)

    # A dead row (seq_len 0) computes nothing: it emits 0, like the
    # other paged kernels.
    out_ref[...] = jnp.zeros_like(out_ref)
    n_chunks = _tile_chunks([seq_lens_ref[t * R + r] for r in range(R)], S)
    # The next tile's first chunk, for the prefetch across tiles (the
    # last tile names itself: nothing is started for it).
    next_c = (0 if window is None
              else tile_first_chunk(jnp.minimum(t + 1, num_tiles - 1)))

    def chunk_body(c, _):
        consumed = state[_CONSUMED]
        slot = jax.lax.rem(consumed, 2)

        # Prefetch the next step (possibly the next tile's first chunk)
        # while this one computes — kills the per-tile cold stall.
        more = c + 1 < n_chunks

        @pl.when(jnp.logical_or(more, t + 1 < num_tiles))
        def _():
            start_fetch(jnp.where(more, t, t + 1),
                        jnp.where(more, c + 1, next_c), 1 - slot)

        def note_live(r, n):
            n_live = live_pages(t * R + r, c)
            live_rows[n] = r
            live_pages_of[n] = n_live
            return n + (n_live > 0).astype(jnp.int32)

        n_rows = jax.lax.fori_loop(0, R, note_live, 0)

        # A step in which the whole tile is live is ONE group, and its
        # list is 0 … R − 1: its products name their rows statically.
        full = n_rows == R
        group = jnp.where(full, R, _GROUP)

        def visit(g, _):
            """One group of the live rows: each prepared, then their
            products — a full group's as ONE straight line, so the rows'
            MXU and VPU chains interleave — then the finished rows'
            outputs."""
            first = g * group
            end = jnp.minimum(first + group, n_rows)

            def each(fn):
                def body(i, _):
                    r = live_rows[i]
                    fn(i, r, t * R + r)
                    return 0
                jax.lax.fori_loop(first, end, body, 0)

            each(lambda i, r, row: prepare(r, row, c, slot,
                                           live_pages_of[i]))
            listed = jnp.logical_not(full)

            @pl.when(listed & (end - first == _GROUP))
            def _():
                for i in range(_GROUP):
                    attend([live_rows[first + i]], c, slot)

            @pl.when(listed & (end - first < _GROUP))
            def _():
                each(lambda i, r, row: attend([r], c, slot))

            @pl.when(full)
            def _():
                attend(list(range(R)), c, slot)

            def finish_if_last(i, r, row):
                @pl.when(last_chunk(row, c)[0])
                def _():
                    finish(r, row, slot)

            each(finish_if_last)
            return 0

        jax.lax.fori_loop(0, (n_rows + group - 1) // group, visit, 0)
        state[_CONSUMED] = consumed + 1
        return 0

    jax.lax.fori_loop(first_c, n_chunks, chunk_body, 0)


def _fused_kernel(*refs, **static):
    """The bf16-pool kernel (its name is what ``kernel_routes`` logs)."""
    _decode_kernel(*refs, quantized=False, **static)


def _fused_kernel_q8(*refs, **static):
    """The int8-pool kernel."""
    _decode_kernel(*refs, quantized=True, **static)


def _tile_plan(B: int, page_size: int, max_pages: int, GD: int,
               itemsize: int, pages_per_chunk: int = 0):
    """Row tile and chunk width: a pure function of the shapes. Returns
    a :class:`DecodePlan`, or None when no LEGAL plan exists: Mosaic
    requires the (R, GD) blocks' second-minor dim divisible by 8 OR
    equal to the whole array dim — so the only legal row tiles are R=8
    (when it divides B) and R=B (whole-array block, covers B<8 and odd
    B). The chunk is the widest that divides the block table, holds at
    most ``CHUNK_TOKENS`` (``pages_per_chunk``, if given, sets the limit
    in pages instead) and whose scratch — two slots of K and of V for a
    tile's rows — is within ``SCRATCH_BUDGET_BYTES`` (an int8 pool's
    scale pages, 2 bytes a KV head beside a token's H_kv·D, ride in the
    headroom). The limit asked of the compiler also holds what a full
    step's block of an int8 pool stands up before its first product:
    the K and V chunks of the tile's rows as bf16."""
    limit = pages_per_chunk if pages_per_chunk > 0 else max(
        1, CHUNK_TOKENS // page_size)
    for R in ([8] if B % 8 == 0 and B != 8 else []) + [B]:
        for ppc in range(min(limit, max_pages), 0, -1):
            scratch = 2 * 2 * R * ppc * page_size * GD * itemsize
            if max_pages % ppc == 0 and scratch <= SCRATCH_BUDGET_BYTES:
                temporaries = (2 * R * ppc * page_size * GD * 2
                               if itemsize == 1 else 0)
                return DecodePlan(
                    R, ppc, ppc * page_size, scratch,
                    scratch + _VMEM_HEADROOM_BYTES + temporaries)
    return None


def chunk_tokens(B: int, page_size: int, max_pages: int, GD: int,
                 itemsize: int = 2) -> int:
    """The tokens in one key chunk of a call of this geometry: what
    :func:`window_chunks` counts in (a page where no plan is legal and
    the XLA form serves)."""
    plan = _tile_plan(B, page_size, max_pages, GD, itemsize)
    return plan.chunk_tokens if plan is not None else page_size


def window_chunks(seq_lens, chunk_tokens: int, window=None):
    """``(visited, skipped)``: the (row, chunk) pairs in which a row of
    ``seq_lens`` holds a position it can see, and those that lie wholly
    before its ``window`` (0 without one) — the kernel's schedule in
    closed form, for a count at every dispatch."""
    seq_lens = np.asarray(seq_lens, np.int64)
    skipped = _first_chunk(seq_lens, chunk_tokens, window, np)
    return (int((-(-seq_lens // chunk_tokens) - skipped).sum()),
            int(np.sum(skipped)))


def decode_work(seq_lens, plan: DecodePlan, window=None, *,
                ordered: bool = False):
    """What one call does for a batch of ``seq_lens`` under ``plan``,
    counted on the host by the kernel's own schedule: ``(steps,
    row_chunks_computed, row_chunks_live, row_chunks_full)`` — the
    (tile, chunk) steps its loops run, the (row, chunk) pairs whose
    products run, the pairs in which a row holds a position it can see
    (under a ``window``: from the chunk of its oldest visible key on),
    and the pairs that ran in a step where the whole tile was live (the
    static path, where the plan has one). The second and third are
    equal when the kernel does the batch's work and no more; none
    depends on the block table's width. ``seq_lens`` are in the order
    the kernel is handed, or by batch row with ``ordered``: they are
    then put in ``ops/attention.decode_order``'s order first (longest
    first, ties by row), as the device does."""
    seq_lens = np.asarray(seq_lens, np.int64)
    if ordered:
        seq_lens = seq_lens[np.argsort(-seq_lens, kind="stable")]
    S, R = plan.chunk_tokens, plan.rows
    tiles = seq_lens.reshape(-1, R)
    by_row = list(tiles.T)
    n_chunks = _tile_chunks(by_row, S, np)                      # (tiles,)
    first = _tile_first_chunk(by_row, n_chunks, S, window, np)
    chunk = np.arange(n_chunks.max())[None, :, None]
    live = _live_pages(tiles[:, None, :], chunk, plan.pages_per_chunk,
                       S // plan.pages_per_chunk, np, window) > 0
    live &= ((chunk >= np.reshape(first, (-1, 1, 1)))
             & (chunk < n_chunks[:, None, None]))       # the steps it runs
    return (int(np.sum(n_chunks - first)), int(live.sum()),
            window_chunks(seq_lens, S, window)[0],
            R * int(live.all(axis=-1).sum()))


def fused_kernel_viable(B: int, page_size: int, max_pages: int, GD: int,
                        itemsize: int = 2) -> bool:
    """Whether the fused kernel has a legal tile plan for this geometry
    (large-GD models at big page sizes may not). Callers route to the
    split write+attention path when False."""
    return _tile_plan(B, page_size, max_pages, GD, itemsize) is not None


def _fused_call(q, new_rows, pools, block_tables, seq_lens, write_page,
                layer, *, pages_per_chunk: int, interpret: bool,
                window=None):
    """One ``pallas_call`` of :func:`_decode_kernel`. ``new_rows``: the
    tile-sliced inputs after q — (k, v) rows, for int8 pools followed by
    their pre-broadcast scales; ``pools``: (k, v) or (k, v, k_scale,
    v_scale), FLAT (L, P, ps, GD) — any reshape here would break XLA's
    aliasing and copy both pools every call (see init_kv_pages)."""
    B, H, D = q.shape
    k_pool = pools[0]
    _, _, page_size, GD = k_pool.shape
    quantized = len(pools) == 4
    Hkv = GD // D
    max_pages = block_tables.shape[1]
    if GD % 128:
        raise ValueError(f"H_kv*D = {GD} must be a multiple of 128")
    plan = _tile_plan(B, page_size, max_pages, GD, k_pool.dtype.itemsize,
                      pages_per_chunk)
    if plan is None:
        raise ValueError(
            f"no legal fused-kernel tile plan for B={B} "
            f"page_size={page_size} GD={GD} (route via "
            f"fused_kernel_viable before calling)")
    R, ppc = plan.rows, plan.pages_per_chunk

    kernel = functools.partial(
        _fused_kernel_q8 if quantized else _fused_kernel, rows_per_tile=R,
        pages_per_chunk=ppc, page_size=page_size, n_rep=H // Hkv,
        scale=D ** -0.5, window=window)

    def tile(*block):
        return pl.BlockSpec(block, lambda t, *_: (t,) + (0,) * (len(block) - 1))

    any_space = [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B // R,),
        in_specs=[tile(R, H, D)] + [tile(R, *x.shape[1:]) for x in new_rows]
        + any_space,
        out_specs=[tile(R, H, D)] + any_space,
        scratch_shapes=[
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, 1), jnp.float32),
            pltpu.VMEM((R, H, GD), jnp.float32),
            pltpu.VMEM((R, H, GD), q.dtype),
        ] + [pltpu.VMEM((2, R, ppc) + p.shape[2:], p.dtype) for p in pools]
        + [
            pltpu.SMEM((1,), jnp.int32),
            pltpu.SMEM((R,), jnp.int32),
            pltpu.SMEM((R,), jnp.int32),
            pltpu.SemaphoreType.DMA((len(pools), 2, R)),
            pltpu.SemaphoreType.DMA((len(pools), R)),
        ],
    )
    # Operands: 4 scalar-prefetch, q, the new rows, then the pools,
    # aliased to the outputs after ``out``.
    first_pool = 5 + len(new_rows)
    out, *pools_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, D), q.dtype)] + [
            jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={first_pool + i: 1 + i
                              for i in range(len(pools))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=plan.vmem_limit_bytes),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      write_page.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q, *new_rows, *pools)
    return out, tuple(pools_out)


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
    window: int | None = None,
):
    """Fused decode step: write the current tokens' KV into the pool
    (in place, aliased) AND return attention over the updated history.
    Returns (attn (B, H, D), (k_pool, v_pool)).

    ``window``: a row sees its last ``window`` keys, itself counted
    (positions ``seq_len - window .. seq_len - 1``), and visits no
    chunk wholly before them; ``None``: every key, the program there
    was before there was a window.

    ``write_page`` must equal ``block_tables[b, (seq_lens[b]-1)//ps]``
    for live rows (the engine's invariant) or 0 for inactive rows.
    All live rows' write pages must be distinct.

    ``pages_per_chunk=0`` (default) lets :func:`_tile_plan` size the
    chunk.
    """
    B = q.shape[0]
    GD = k_pool.shape[3]
    kn = k_new.reshape(B, GD).astype(k_pool.dtype)
    vn = v_new.reshape(B, GD).astype(v_pool.dtype)
    out, pools = _fused_call(
        q, (kn, vn), (k_pool, v_pool), block_tables, seq_lens, write_page,
        layer, pages_per_chunk=pages_per_chunk, interpret=interpret,
        window=window)
    return out.astype(q.dtype), pools


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
    """int8-KV fused decode step (the module docstring's last
    paragraph). Returns (attn (B, H, D), pools)."""
    B = q.shape[0]
    _, _, page_size, GD = pools[0].shape
    Hkv = pools[2].shape[2]
    # Scales pre-broadcast along the page dim: the kernel's merge is
    # then a single lane-select against the fetched scale page.
    kns, vns = (jnp.broadcast_to(
        s.astype(jnp.bfloat16)[:, :, None], (B, Hkv, page_size))
        for s in (k_new_scale, v_new_scale))
    out, pools = _fused_call(
        q, (k_new_q.reshape(B, GD), v_new_q.reshape(B, GD), kns, vns),
        tuple(pools), block_tables, seq_lens, write_page, layer,
        pages_per_chunk=pages_per_chunk, interpret=interpret)
    return out.astype(q.dtype), pools
