"""Pallas TPU kernel: paged decode attention (the serving hot path).

Semantics reference: :func:`llmq_tpu.ops.attention.paged_decode_attention`
(pure JAX), which this kernel is tested against in interpret mode
(tests/test_pallas.py) and must match within matmul precision.

Why a kernel at all — the pure-JAX path does

    k = k_pages[block_tables]        # (B, S, H_kv, D) gather

which XLA lowers to a materialized gather: every decode step reads the
*entire padded window* (max_pages × page_size tokens per sequence) out of
HBM, writes the gathered copy back to HBM, and reads it again for the
attention matmul — 3× the traffic of the live KV, independent of how
short the sequences actually are. Decode attention is purely
HBM-bandwidth-bound (arithmetic intensity ~1 FLOP/byte), so that factor
is the speedup ceiling.

This kernel instead:

- **scalar-prefetches** ``block_tables`` and ``seq_lens`` into SMEM
  (PrefetchScalarGridSpec), so page indices are known before the body
  runs;
- keeps the page pools in **HBM** (``memory_space=ANY``) and issues
  explicit per-page **async DMAs** into double-buffered VMEM scratch —
  each live page is read exactly once, no gathered copy is ever
  materialized;
- **skips dead pages entirely**: pages at positions ≥ ``seq_lens[b]``
  are neither copied nor computed (``pl.when``), so a 100-token sequence
  in an 8k-wide block table costs 7 pages of traffic, not 512;
- accumulates with an **online softmax** (flash-decoding style) across
  page chunks, in f32, entirely in VMEM scratch — numerically identical
  to a full-window softmax.

**GQA via block-diagonal Q (the Mosaic-shaped trick).** TPU DMA and
vector layouts want the minor dimension 128-aligned, and Mosaic only
lowers plain 2D matmuls — both rule out per-head slicing of a
``(page_size, H_kv, 64)`` page. So the kernel works on pages flattened
to ``(page_size, H_kv·D)`` (≥128 lanes, one DMA per page) and receives Q
as a **block-diagonal** ``(H, H_kv·D)`` matrix: row h carries q_h in its
group's D-wide block and zeros elsewhere. Then

    logits = Q_bd @ K_flatᵀ          # (H, S) — one MXU matmul, all heads
    acc   += softmax_chunk @ V_flat  # (H, H_kv·D)

computes every head's attention against *its own* KV head in single 2D
matmuls (the zero blocks null out cross-head terms), and the caller
extracts each row's diagonal block to get (H, D). The extra MXU work
(H_kv× the minimal FLOPs) is noise — the kernel is DMA-bound.

Grid: ``(B, num_chunks)``, chunks minor, so for a fixed sequence the
chunk loop runs back-to-back and the VMEM accumulators carry across it.
DMA double buffering overlaps chunk c's compute with chunk c+1's copies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,   # (B, max_pages) int32
    seq_lens_ref,       # (B,) int32
    layer_ref,          # (1,) int32 — which pool layer to read
    # inputs
    q_ref,              # (1, H, GD) VMEM — block-diagonal per head group
    k_hbm,              # (L, P, page_size, GD) in HBM/ANY
    v_hbm,              # (L, P, page_size, GD) in HBM/ANY
    # outputs
    out_ref,            # (1, H, GD) VMEM
    # scratch
    m_ref,              # (H, 1) f32   running max
    l_ref,              # (H, 1) f32   running denominator
    acc_ref,            # (H, GD) f32  running numerator
    k_scratch,          # (2, ppc, page_size, GD) VMEM
    v_scratch,          # (2, ppc, page_size, GD) VMEM
    sem,                # DMA semaphores (2, 2, ppc)
    *,
    pages_per_chunk: int,
    page_size: int,
    num_chunks: int,
    scale: float,
):
    b = pl.program_id(0)
    c = pl.program_id(1)
    ppc = pages_per_chunk
    seq_len = seq_lens_ref[b]
    lyr = layer_ref[0]

    def start_chunk(chunk, slot):
        """Kick off async copies of every live page of ``chunk``. Dead
        pages (beyond seq_len) get their V scratch zeroed instead: their
        softmax weight is exactly 0, but 0 × stale-garbage could still
        poison the p·V matmul (0·NaN = NaN), so the operand itself must
        be clean. K scratch can stay stale — garbage logits are replaced
        by NEG_INF before they are used."""
        base = chunk * ppc
        for j in range(ppc):  # static unroll
            page_start = (base + j) * page_size
            in_grid = chunk < num_chunks
            live = jnp.logical_and(in_grid, page_start < seq_len)

            @pl.when(live)
            def _():
                pid = block_tables_ref[b, base + j]
                pltpu.make_async_copy(
                    k_hbm.at[lyr, pid], k_scratch.at[slot, j],
                    sem.at[0, slot, j]).start()
                pltpu.make_async_copy(
                    v_hbm.at[lyr, pid], v_scratch.at[slot, j],
                    sem.at[1, slot, j]).start()

            @pl.when(jnp.logical_and(in_grid, jnp.logical_not(live)))
            def _():
                v_scratch[slot, j] = jnp.zeros_like(v_scratch[slot, j])

    def wait_chunk(chunk, slot):
        base = chunk * ppc
        for j in range(ppc):
            page_start = (base + j) * page_size

            @pl.when(page_start < seq_len)
            def _():
                pltpu.make_async_copy(
                    k_hbm.at[lyr, block_tables_ref[b, base + j]],
                    k_scratch.at[slot, j], sem.at[0, slot, j]).wait()
                pltpu.make_async_copy(
                    v_hbm.at[lyr, block_tables_ref[b, base + j]],
                    v_scratch.at[slot, j], sem.at[1, slot, j]).wait()

    # Warm the pipeline: chunk 0 of each sequence kicks off its own DMA.
    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        start_chunk(0, 0)

    slot = jax.lax.rem(c, 2)
    chunk_start = c * ppc * page_size

    @pl.when(chunk_start < seq_len)
    def _():
        # Overlap: start the next chunk's copies before computing on this
        # one (double buffering).
        start_chunk(c + 1, 1 - slot)
        wait_chunk(c, slot)

        S = ppc * page_size
        GD = acc_ref.shape[1]
        q = q_ref[0]                                      # (H, GD) bl-diag
        k = k_scratch[slot].reshape(S, GD)
        v = v_scratch[slot].reshape(S, GD)
        dims = (((1,), (1,)), ((), ()))                   # contract GD
        logits = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32), dims,
            preferred_element_type=jnp.float32) * scale    # (H, S)
        pos = chunk_start + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        live = pos < seq_len                               # (1, S)
        logits = jnp.where(live, logits, NEG_INF)

        m_prev = m_ref[...]                                # (H, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)                        # (H, S)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (H, GD)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(c == num_chunks - 1)
    def _():
        # Zero guard: seq_lens[b] == 0 skips every chunk, leaving l at 0
        # — emit 0 (matching the prefill kernel's flush) instead of 0/0.
        out_ref[0] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


def paged_decode_attention_pallas(
    q: jnp.ndarray,             # (B, H, D)
    k_pool: jnp.ndarray,        # (L, P, page_size, H_kv·D) or (P, ps, H_kv·D)
    v_pool: jnp.ndarray,        # same shape as k_pool (FLAT head dim)
    block_tables: jnp.ndarray,  # (B, max_pages) int32
    seq_lens: jnp.ndarray,      # (B,) int32
    layer: jnp.ndarray | int = 0,  # scalar int32 — pool layer to read
    *,
    pages_per_chunk: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged decode attention on TPU via Pallas. Returns (B, H, D).

    Drop-in for :func:`llmq_tpu.ops.attention.paged_decode_attention_pooled`
    (and for the single-layer reference when given 4-D pools);
    ``interpret=True`` runs the kernel on CPU for tests. Requires
    ``H_kv · D`` to be a multiple of 128 (lane tiling) — true for every
    Llama-3 family member (8·64, 8·128, …).

    The layer index arrives via scalar prefetch, so the pool never
    needs a per-layer slice materialized — forward_decode's unrolled
    layer loop passes each static layer index straight through while
    threading one pool buffer across all layers.
    """
    if k_pool.ndim == 3:                 # single-layer convenience form
        k_pool = k_pool[None]
        v_pool = v_pool[None]
    B, H, D = q.shape
    L, P, page_size, GD = k_pool.shape
    Hkv = GD // D
    max_pages = block_tables.shape[1]
    n_rep = H // Hkv
    if GD % 128:
        raise ValueError(f"H_kv*D = {GD} must be a multiple of 128")
    ppc = min(pages_per_chunk, max_pages)
    # Grid must tile max_pages exactly; shrink the chunk if it doesn't.
    while max_pages % ppc:
        ppc -= 1
    num_chunks = max_pages // ppc

    # Block-diagonal Q: row h = q_h placed in its group's D-block.
    eye = jnp.eye(Hkv, dtype=q.dtype)                      # (g, g')
    q_bd = jnp.einsum("bgrd,gh->bgrhd", q.reshape(B, Hkv, n_rep, D),
                      eye).reshape(B, H, GD)

    kernel = functools.partial(
        _decode_kernel,
        pages_per_chunk=ppc,
        page_size=page_size,
        num_chunks=num_chunks,
        scale=D ** -0.5,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, num_chunks),
        in_specs=[
            pl.BlockSpec((1, H, GD), lambda b, c, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, GD), lambda b, c, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, GD), jnp.float32),
            pltpu.VMEM((2, ppc, page_size, GD), k_pool.dtype),
            pltpu.VMEM((2, ppc, page_size, GD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, ppc)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, GD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q_bd, k_pool, v_pool)
    # Extract each row's diagonal block: (B, H, GD) → (B, H, D).
    out5 = out.reshape(B, Hkv, n_rep, Hkv, D)
    res = jnp.einsum("bgrhd,gh->bgrd", out5, jnp.eye(Hkv, dtype=out.dtype))
    return res.reshape(B, H, D).astype(q.dtype)
