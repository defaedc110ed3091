"""Pallas TPU kernels: paged prefill (chunk) attention, single sequence,
over bf16 pools (``paged_prefill_attention_pallas``) and over int8 pools
with bf16 scale pools (``paged_prefill_attention_q8_pallas``); one body.

The serving prefill path processes ONE sequence per call (the executor
streams prompt chunks through bucketed programs). Its attention must
read the paged pool — the chunk attends to previously cached history
(continuation turns) plus itself causally. Doing that read as an XLA
gather has two costs: the gather materializes the padded window, and —
worse — a gather consuming the pool between the aliased Pallas
KV-writes of successive layers makes XLA insert full-pool defensive
copies (measured: it tripled prefill time). Reading through a Pallas
kernel keeps the pool's only consumers opaque custom calls with clean
buffer dependencies, mirroring the decode path.

Tile plan (:func:`prefill_tile_plan`, a pure function of the shapes):

- **Lanes hold heads.** The flat ``H_kv·D`` axis of a page is cut into
  head windows of ``W = max(128, D)`` lanes: one KV head at D >= 128,
  ``128 // D`` neighbours below. The query heads of a window's KV heads
  are stacked on the ROWS of one ``(R·Tb, W)`` tile (R = heads per
  window × n_rep; head h's D lanes sit under its KV head's, zero
  elsewhere), built in VMEM from the ``(Tb, H·D)`` q block as it
  arrives, so every (q block × K/V chunk × window) meets the MXU as
  one 2D matmul over 128-aligned lane windows of the whole pages in
  scratch. Wasted MXU lanes: W / D, never H_kv.
- **The loop follows the context.** The grid runs over q blocks only;
  inside, a ``fori_loop`` with a trip count computed from ``start_pos``
  walks the K/V chunks up to the q block's last visible position, with
  double-buffered whole-page DMAs (pages past that position inside the
  last chunk are neither fetched nor waited for).
- **Precision**: operands in the pool's dtype (bf16 in serving), f32
  accumulation, f32 softmax statistics, ``p`` cast to the pool's dtype
  before PV — what ``fused_decode.py`` and
  ``blockwise_prefill_attention`` do.
- q comes in and the result goes out as ``(T, H·D)``, a free reshape of
  ``(T, H, D)``: nothing wider than that exists in HBM.

int8 KV (the ``quantized`` branches of :func:`_prefill_body`; what
``fused_decode.py``'s ``_q8`` twin added to its sibling): pool pages are
int8, and each comes with its ``(H_kv, page_size)`` bf16 scale page on
a semaphore of its own. A window's K and V are converted to the
query's dtype for the products (exact: |x| <= 127); K's scales
multiply the LOGITS, together with the softmax scale — (head,
position) is the logits' layout, so a window's multiplier is a row of
the chunk's scale pages — and V's fold into ``p`` before PV. (The
XLA path, ``_dequant_window`` + blockwise, gathers and dequantises the
block table's WHOLE width a slice: it serves where this cannot.)
**The q side follows what is there too**: the twin is told the slice's
valid length; no row looks past the last valid one, and a q block
wholly past it fetches nothing (its q block included), multiplies
nothing and returns zeros, so an empty slice costs its grid steps.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

#: What the kernel asks Mosaic for (the default scoped limit is 16 MiB
#: of the v5e's 128 MiB) and what the plan's own estimate must stay
#: under: the rest is left to the compiler's temporaries.
VMEM_LIMIT_BYTES = 32 * 2**20
VMEM_BUDGET_BYTES = 20 * 2**20
#: K/V chunk length the plan aims for: long enough to amortize a loop
#: step, short enough that a chunk past the context costs little.
CHUNK_TOKENS = 256
#: Rows of the stacked q tile (one matmul's M) the plan aims for.
MAX_TILE_ROWS = 512


class PrefillPlan(NamedTuple):
    """Tile sizes of one call, from its shapes alone."""
    q_block: int            # Tb: query tokens per grid step
    pages_per_chunk: int    # whole pages per K/V chunk
    chunk_tokens: int       # S = pages_per_chunk · page_size
    num_chunks: int         # chunks that cover the block table
    lane_width: int         # W: lanes of one head window
    num_windows: int        # H_kv·D / W
    heads_per_tile: int     # R: query heads stacked on a tile's rows
    lane_waste: float       # W / D: MXU lanes fed per useful lane
    vmem_bytes: int         # estimate, see prefill_tile_plan

    def steps(self, n_tokens: int, start_pos: int,
              length: int | None = None, window: int | None = None) -> int:
        """Loop steps (q block × live K/V chunk) of a call whose first
        query sits at ``start_pos``; each does ``num_windows`` pairs of
        matmuls. ``length``: the slice's valid rows, where the kernel
        is told (int8 pools) — a block past them runs no step and none
        looks past the last of them. ``window``: a block starts at the
        chunk of its FIRST query's oldest visible key."""
        total = 0
        for qb in range(n_tokens // self.q_block):
            first_q = start_pos + qb * self.q_block
            last = first_q + self.q_block - 1
            if length is not None:
                if qb * self.q_block >= length:
                    break
                last = min(last, start_pos + length - 1)
            first = (0 if window is None
                     else max(first_q - window + 1, 0) // self.chunk_tokens)
            total += min(last // self.chunk_tokens + 1,
                         self.num_chunks) - first
        return total


def _largest_divisor(n: int, at_most: int) -> int:
    d = max(1, min(n, at_most))
    while n % d:
        d -= 1
    return d


def prefill_tile_plan(n_tokens: int, n_heads: int, n_kv_heads: int,
                      head_dim: int, page_size: int, max_pages: int,
                      itemsize: int, *, q_block: int = 0,
                      pages_per_chunk: int = 0,
                      q_itemsize: int = 0) -> PrefillPlan:
    """The kernel's tile sizes as a pure function of the call's shapes.

    ``q_block`` / ``pages_per_chunk`` > 0 pin those two (tests); 0 lets
    the plan choose: chunks of about ``CHUNK_TOKENS``, then the largest
    q block whose stacked tile has at most ``MAX_TILE_ROWS`` rows and
    whose VMEM estimate stays under ``VMEM_BUDGET_BYTES``.
    ``itemsize`` is the pool's; ``q_itemsize`` the query's where it
    differs (int8 pools: bf16 queries, and a window's K and V
    dequantised to them before the products).
    """
    T, H, Hkv, D = n_tokens, n_heads, n_kv_heads, head_dim
    qsz = q_itemsize or itemsize
    GD = Hkv * D
    W = max(128, D)
    if GD % W or W % D or W % 128:
        raise ValueError(
            f"H_kv*D = {GD} with D = {D} does not cut into 128-lane "
            f"head windows")
    R = (W // D) * (H // Hkv)
    ppc = _largest_divisor(
        max_pages, pages_per_chunk or max(1, CHUNK_TOKENS // page_size))
    S = ppc * page_size

    def vmem(tb: int) -> int:
        rows = H * tb                          # all windows together
        acc = rows * W * 4                     # f32 accumulator
        stats = 2 * rows * 128 * 4             # m, l: (rows, 1) f32 pads
                                               # to a full lane tile
        stacked = rows * W * qsz               # stacked q tiles
        blocks = 2 * 2 * tb * H * D * qsz      # q, out: double-buffered
        kv = 2 * 2 * S * GD * itemsize         # K, V: two slots each
        temps = 3 * R * tb * S * 4             # logits, p, mask of a tile
        if qsz != itemsize:
            temps += 2 * S * W * qsz           # a window's K, V as q's dtype
            kv += 2 * 2 * S * Hkv * 2          # bf16 scale pages, two slots
        return acc + stats + stacked + blocks + kv + temps

    if q_block:
        tb = _largest_divisor(T, q_block)
    else:
        tb = T      # halved only while the half is a whole bf16 tile (16)
        while tb % 32 == 0 and (
                R * tb > MAX_TILE_ROWS or vmem(tb) > VMEM_BUDGET_BYTES):
            tb //= 2
    return PrefillPlan(
        q_block=tb, pages_per_chunk=ppc, chunk_tokens=S,
        num_chunks=max_pages // ppc, lane_width=W, num_windows=GD // W,
        heads_per_tile=R, lane_waste=W / D, vmem_bytes=vmem(tb))


def _lane_window(x, lo: int, width: int):
    """``x`` with every lane outside [lo, lo + width) zeroed."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= lo) & (lane < lo + width), x, 0)


def _prefill_body(*refs, quantized: bool, plan: PrefillPlan,
                  page_size: int, head_dim: int, n_rep: int, scale: float,
                  window=None):
    """Both kernels' body. ``refs`` (scalar prefetch, inputs, outputs,
    scratch), the int8 form's extras in brackets:

    block_table (max_pages,), meta (2 [3],) — start_pos, layer [, the
    slice's valid length] —: int32 SMEM; q (Tb, H·D) this block's raw
    query heads; the pools (L, P, page_size, GD) [and their scale pools
    (L, P, H_kv, page_size)] in ANY; out (Tb, H·D);
    qs (n_w, R·Tb, W) stacked q tiles; m, l (n_w, R·Tb, 1), acc
    (n_w, R·Tb, W) f32; k, v scratch (2, S, GD) [scale scratch (2, ppc,
    H_kv, page_size)]; sem DMA (pools, 2) — [pool, slot].
    """
    n_pools = 4 if quantized else 2
    refs = iter(refs)

    def take(n):
        return [next(refs) for _ in range(n)]

    block_table_ref, meta_ref, q_ref = take(3)
    pools = take(n_pools)
    out_ref, qs_ref, m_ref, l_ref, acc_ref = take(5)
    bufs = take(n_pools)
    (sem,) = take(1)
    k_scratch, v_scratch = bufs[:2]

    qb = pl.program_id(0)
    Tb, ppc, S = plan.q_block, plan.pages_per_chunk, plan.chunk_tokens
    W, n_w, R = plan.lane_width, plan.num_windows, plan.heads_per_tile
    D = head_dim
    start = meta_ref[0]
    lyr = meta_ref[1]
    # Last absolute position any q row of this block can see, and the
    # K/V chunks up to it: the only ones this block spends a step on.
    block_max_pos = start + (qb + 1) * Tb - 1
    if quantized:
        # The q side follows what is there too: rows past the slice's
        # valid length are padding, so the block looks no further than
        # the last valid row does, and a block wholly past it (below)
        # neither fetches nor multiplies.
        length = meta_ref[2]
        last_valid = start + length - 1
        block_max_pos = jnp.minimum(block_max_pos, last_valid)
    n_live = jnp.minimum(block_max_pos // S + 1, plan.num_chunks)
    # Under a window the block's chunks start where its FIRST query's
    # oldest visible key lies (position q - window + 1): the later
    # queries' windows start no earlier.
    first_c = (0 if window is None else
               jnp.maximum(start + qb * Tb - (window - 1), 0) // S)

    def chunk_dmas(chunk, slot, wait: bool):
        """Start (or wait for) the live pages of ``chunk``. Liveness is
        the same predicate both times, so starts and waits pair; every
        copy on a semaphore moves one page, so waits drain in any
        order."""
        base = chunk * ppc
        for j in range(ppc):  # static unroll

            @pl.when((base + j) * page_size <= block_max_pos)
            def _():
                pid = 0 if wait else block_table_ref[base + j]
                rows = pl.ds(j * page_size, page_size)
                for pool, (hbm, scratch) in enumerate(zip(pools, bufs)):
                    # a data page lands on its rows of the chunk, a
                    # scale page (H_kv, page_size) in its own place
                    to = (scratch.at[slot, rows] if pool < 2
                          else scratch.at[slot, j])
                    dma = pltpu.make_async_copy(
                        hbm.at[lyr, pid], to, sem.at[pool, slot])
                    dma.wait() if wait else dma.start()

    def head_scales(pages):
        """(ppc, H_kv, page_size) scale pages → (H_kv, S) f32: the
        chunk's pages lane-concatenated. (head, position) is the
        logits' layout (``ops/quant.py``), so a window's multiplier is
        a row of this, no transpose."""
        hs = (pages[0] if ppc == 1 else jnp.concatenate(
            [pages[j] for j in range(ppc)], axis=1))
        return hs.astype(jnp.float32)

    def window_rows(hs, w):
        """The multiplier of window ``w``'s tile: one row of ``hs``
        where the window is one KV head (D >= 128, broadcast over the
        tile), else each of its KV heads' row over that head's
        ``n_rep·Tb`` tile rows."""
        per = W // D
        if per == 1:
            return hs[w:w + 1, :]
        return jnp.concatenate(
            [jnp.broadcast_to(hs[w * per + g:w * per + g + 1, :],
                              (n_rep * Tb, S)) for g in range(per)], axis=0)

    def block():
        @pl.when(qb == 0)
        def _():
            # A dead page inside a live chunk is never copied, and what
            # the scratch holds there meets p = 0 in the PV matmul: it
            # has to be finite (fresh VMEM can hold NaN; 0 × NaN = NaN).
            # Later blocks find zeros or pages of this call's own
            # context. Over int8 pools the data cannot be NaN; the V
            # scales that multiply p can.
            for buf in (bufs[2:] if quantized else bufs):
                buf[...] = jnp.zeros_like(buf)

        chunk_dmas(first_c, 0 if window is None
                   else jax.lax.rem(first_c, 2), wait=False)

        # Stack the query heads while chunk 0 is in flight. Window w
        # holds heads [w·R, (w+1)·R): head h goes to rows [i·Tb,
        # (i+1)·Tb) of the tile, i = h mod R, under the lanes of its KV
        # head.
        for h in range(n_w * R):
            w, i = divmod(h, R)
            if D >= 128:
                x = q_ref[:, h * D:(h + 1) * D]
            else:
                at = (h * D) % 128             # where q_ref has the head
                to = (i // n_rep) * D          # where its KV head sits
                lo = (h * D) // 128 * 128
                x = q_ref[:, lo:lo + 128].astype(jnp.float32)
                if at != to:
                    x = pltpu.roll(x, (to - at) % 128, 1)
                x = _lane_window(x, to, D)
            qs_ref[w, i * Tb:(i + 1) * Tb, :] = x.astype(qs_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        # Row r of a tile is token r mod Tb of the block, whatever its
        # head.
        q_pos = start + qb * Tb + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (R * Tb, 1), 0), Tb)
        if quantized:
            # a padding row sees what the last valid row sees
            q_pos = jnp.minimum(q_pos, last_valid)

        def chunk_step(c, carry):
            slot = jax.lax.rem(c, 2)

            @pl.when(c + 1 < n_live)
            def _():
                chunk_dmas(c + 1, 1 - slot, wait=False)

            chunk_dmas(c, slot, wait=True)
            # Causal visibility by absolute position, the same for
            # every window. Chunk 0 shows every row position 0, so m is
            # real from the first step on and a chunk a row sees nothing
            # of gives it p = exp(-1e30 - m) = 0 exactly.
            kv_pos = c * S + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
            live = kv_pos <= q_pos                          # (R·Tb, S)
            if window is not None:
                live = live & (kv_pos > q_pos - window)
            if quantized:
                # K's scales go on the logits with the softmax scale,
                # V's on p before PV (as ``fused_decode.py``'s twin).
                k_scales = head_scales(bufs[2][slot]) * scale
                v_scales = head_scales(bufs[3][slot])
            for w in range(n_w):  # static, 128-aligned lane windows
                k = k_scratch[slot, :, w * W:(w + 1) * W]   # (S, W)
                v = v_scratch[slot, :, w * W:(w + 1) * W]
                if quantized:
                    k = k.astype(qs_ref.dtype)
                    v = v.astype(qs_ref.dtype)
                logits = jax.lax.dot_general(
                    qs_ref[w], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                logits = logits * (window_rows(k_scales, w) if quantized
                                   else scale)
                logits = jnp.where(live, logits, NEG_INF)
                m_prev = m_ref[w]
                m_new = jnp.maximum(
                    m_prev, jnp.max(logits, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(logits - m_new)                 # (R·Tb, S)
                if window is not None:
                    # a block's first chunk can lie wholly before a
                    # LATER query's window: m is still -1e30 there and
                    # exp(0) = 1 would count what the row cannot see
                    p = jnp.where(live, p, 0.0)
                l_ref[w] = alpha * l_ref[w] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
                m_ref[w] = m_new
                if quantized:
                    p = p * window_rows(v_scales, w)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)     # (R·Tb, W)
                acc_ref[w] = acc_ref[w] * alpha + pv
            return carry

        jax.lax.fori_loop(first_c, n_live, chunk_step, 0)

        # Unstack: head h's result is rows [i·Tb, (i+1)·Tb) of its
        # tile, lanes of its KV head; it goes back to where q_ref had
        # the head.
        def head_out(h):
            w, i = divmod(h, R)
            rows = slice(i * Tb, (i + 1) * Tb)
            return acc_ref[w, rows, :] / jnp.maximum(l_ref[w, rows, :],
                                                     1e-30)

        if D >= 128:
            for h in range(n_w * R):
                out_ref[:, h * D:(h + 1) * D] = head_out(h).astype(
                    out_ref.dtype)
        else:
            per = 128 // D
            for g in range(n_w * R // per):    # one 128-lane store each
                tile = None
                for h in range(g * per, (g + 1) * per):
                    at = (h * D) % 128
                    to = (h % R // n_rep) * D
                    x = head_out(h)
                    if at != to:
                        x = pltpu.roll(x, (at - to) % 128, 1)
                    x = _lane_window(x, at, D)
                    tile = x if tile is None else tile + x
                out_ref[:, g * 128:(g + 1) * 128] = tile.astype(
                    out_ref.dtype)

    if not quantized:
        block()
        return

    # A q block wholly past the slice's valid length: no DMA, no
    # product; zeros out, so what follows the kernel stays finite.
    pl.when(qb * Tb < length)(block)

    @pl.when(qb * Tb >= length)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _prefill_attn_kernel(*refs, **static):
    """The bf16-pool kernel (its name is what ``kernel_routes`` logs)."""
    _prefill_body(*refs, quantized=False, **static)


def _prefill_attn_kernel_q8(*refs, **static):
    """The int8-pool kernel."""
    _prefill_body(*refs, quantized=True, **static)


def _prefill_call(q, pools, block_table, meta, *, pages_per_chunk: int,
                  q_block: int, interpret: bool, window=None):
    """One ``pallas_call`` of :func:`_prefill_body`. ``pools``: (k, v)
    or (k, v, k_scale, v_scale), FLAT (L, P, page_size, GD) and (L, P,
    H_kv, page_size); ``meta``: the scalars after the block table —
    (start_pos, layer) and, for int8 pools, the slice's valid length.
    Returns (T, H, D)."""
    T, H, D = q.shape
    k_pool = pools[0]
    L, P, page_size, GD = k_pool.shape
    quantized = len(pools) == 4
    Hkv = GD // D
    max_pages = block_table.shape[0]
    plan = prefill_tile_plan(
        T, H, Hkv, D, page_size, max_pages, k_pool.dtype.itemsize,
        q_block=q_block, pages_per_chunk=pages_per_chunk,
        q_itemsize=q.dtype.itemsize if quantized else 0)
    Tb, S, W = plan.q_block, plan.chunk_tokens, plan.lane_width
    n_w, rows = plan.num_windows, plan.heads_per_tile * plan.q_block
    ppc = plan.pages_per_chunk

    kernel = functools.partial(
        _prefill_attn_kernel_q8 if quantized else _prefill_attn_kernel,
        plan=plan, page_size=page_size, head_dim=D, n_rep=H // Hkv,
        scale=D ** -0.5, window=window)
    if quantized:
        # A block past the valid length asks for the last live block's
        # q again: the pipeline then fetches nothing for it.
        def q_index(b, _bt, meta):
            return (jnp.minimum(b, jnp.maximum(
                (meta[2] + Tb - 1) // Tb - 1, 0)), 0)
    else:
        def q_index(b, *_):
            return (b, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T // Tb,),
        in_specs=[pl.BlockSpec((Tb, H * D), q_index)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((Tb, H * D), lambda b, *_: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_w, rows, W), q.dtype if quantized
                       else k_pool.dtype),
            pltpu.VMEM((n_w, rows, 1), jnp.float32),
            pltpu.VMEM((n_w, rows, 1), jnp.float32),
            pltpu.VMEM((n_w, rows, W), jnp.float32),
            pltpu.VMEM((2, S, GD), pools[0].dtype),
            pltpu.VMEM((2, S, GD), pools[1].dtype),
        ] + [pltpu.VMEM((2, ppc) + p.shape[2:], p.dtype)
             for p in pools[2:]]
        + [pltpu.SemaphoreType.DMA((len(pools), 2))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(block_table.astype(jnp.int32),
      jnp.stack([jnp.asarray(x, jnp.int32) for x in meta]),
      q.reshape(T, H * D), *pools)
    return out.reshape(T, H, D)


def paged_prefill_attention_pallas(
    q: jnp.ndarray,             # (T, H, D) — ONE sequence's chunk
    k_pool: jnp.ndarray,        # (L, P, page_size, H_kv·D) FLAT
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,   # (max_pages,) int32
    start_pos: jnp.ndarray,     # scalar int32 — absolute pos of q row 0
    layer: jnp.ndarray | int = 0,
    *,
    pages_per_chunk: int = 0,
    q_block: int = 0,
    interpret: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """Causal paged attention for a prefill chunk. Returns (T, H, D).

    Visibility: kv position <= q position (covers both in-chunk
    causality and previously cached history) and, under a ``window``,
    kv position > q position - ``window`` (a query sees ``window``
    keys, itself counted; a q block visits no chunk wholly before its
    first query's); ``None`` is the program there was before. Requires H_kv·D to cut
    into 128-lane head windows (D a divisor or a multiple of 128).
    ``pages_per_chunk`` / ``q_block`` = 0 (default) let
    :func:`prefill_tile_plan` choose.
    """
    return _prefill_call(
        q, (k_pool, v_pool), block_table, (start_pos, layer),
        pages_per_chunk=pages_per_chunk, q_block=q_block,
        interpret=interpret, window=window)


def paged_prefill_attention_q8_pallas(
    q: jnp.ndarray,             # (T, H, D) bf16 — ONE sequence's chunk
    pools,                      # (k, v, k_scale, v_scale) — k/v int8
    block_table: jnp.ndarray,   # (max_pages,) int32
    start_pos: jnp.ndarray,     # scalar int32 — absolute pos of q row 0
    length: jnp.ndarray,        # scalar int32 — valid rows of q
    layer: jnp.ndarray | int = 0,
    *,
    pages_per_chunk: int = 0,
    q_block: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """The same over int8 pools with bf16 scale pools (the module
    docstring's last paragraph). Rows of q from ``length`` on are
    padding: what they return is not attention (zeros for a whole q
    block of them). Needs ``page_size % 128 == 0`` (a scale page's lane
    axis) and the eight scale heads that fill its sublane tile."""
    return _prefill_call(
        q, tuple(pools), block_table, (start_pos, layer, length),
        pages_per_chunk=pages_per_chunk, q_block=q_block,
        interpret=interpret)
