"""Pallas TPU kernel: paged prefill (chunk) attention, single sequence.

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

Shape strategy (same tricks as the decode kernel, see
paged_attention.py): GQA via **block-diagonal Q** — q row (t, h) covers
lanes [g(h)·D, (g(h)+1)·D) of the H_kv·D-wide flattened head dim, so
every (q-block × kv-chunk) product is one 2D MXU matmul and per-head
slicing (illegal lane granularity) never happens. Pages DMA HBM→VMEM
per chunk; fully-masked chunks (beyond the q block's last visible
position) are skipped entirely; online softmax accumulates across
chunks in f32 scratch.

Grid: (n_q_blocks, n_kv_chunks), kv minor — accumulators carry across
the kv loop of each q block, reset at chunk 0, flushed at the last
chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _prefill_attn_kernel(
    # scalar prefetch (SMEM)
    block_table_ref,   # (max_pages,) int32
    meta_ref,          # (2,) int32 — [start_pos, layer]
    # inputs
    q_ref,             # (TbH, GD) VMEM — block-diagonal q rows
    k_hbm,             # (L, P, page_size, GD) ANY
    v_hbm,             # (L, P, page_size, GD) ANY
    # outputs
    out_ref,           # (TbH, GD) VMEM
    # scratch
    m_ref,             # (TbH, 1) f32
    l_ref,             # (TbH, 1) f32
    acc_ref,           # (TbH, GD) f32
    k_scratch,         # (2, ppc, page_size, GD) VMEM
    v_scratch,         # (2, ppc, page_size, GD) VMEM
    sem,               # DMA semaphores (2, 2, ppc)
    *,
    pages_per_chunk: int,
    page_size: int,
    num_chunks: int,
    q_block: int,      # Tb — query tokens per grid row
    n_heads: int,
    scale: float,
):
    qb = pl.program_id(0)
    c = pl.program_id(1)
    ppc = pages_per_chunk
    start = meta_ref[0]
    lyr = meta_ref[1]
    # Last absolute position any q row of this block can see.
    block_max_pos = start + (qb + 1) * q_block - 1

    def start_chunk(chunk, slot):
        base = chunk * ppc
        for j in range(ppc):  # static unroll
            page_start = (base + j) * page_size
            in_grid = chunk < num_chunks
            live = jnp.logical_and(in_grid, page_start <= block_max_pos)

            @pl.when(live)
            def _():
                pid = block_table_ref[base + j]
                pltpu.make_async_copy(
                    k_hbm.at[lyr, pid], k_scratch.at[slot, j],
                    sem.at[0, slot, j]).start()
                pltpu.make_async_copy(
                    v_hbm.at[lyr, pid], v_scratch.at[slot, j],
                    sem.at[1, slot, j]).start()

            @pl.when(jnp.logical_and(in_grid, jnp.logical_not(live)))
            def _():
                # Never-copied scratch could hold NaN; 0-weight × NaN
                # would poison the p·V matmul.
                v_scratch[slot, j] = jnp.zeros_like(v_scratch[slot, j])

    def wait_chunk(chunk, slot):
        base = chunk * ppc
        for j in range(ppc):
            page_start = (base + j) * page_size

            @pl.when(page_start <= block_max_pos)
            def _():
                pltpu.make_async_copy(
                    k_hbm.at[lyr, block_table_ref[base + j]],
                    k_scratch.at[slot, j], sem.at[0, slot, j]).wait()
                pltpu.make_async_copy(
                    v_hbm.at[lyr, block_table_ref[base + j]],
                    v_scratch.at[slot, j], sem.at[1, slot, j]).wait()

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        start_chunk(0, 0)

    slot = jax.lax.rem(c, 2)
    chunk_start = c * ppc * page_size

    @pl.when(chunk_start <= block_max_pos)
    def _():
        start_chunk(c + 1, 1 - slot)
        wait_chunk(c, slot)

        S = ppc * page_size
        TbH = acc_ref.shape[0]
        GD = acc_ref.shape[1]
        q = q_ref[...]                                     # (TbH, GD)
        k = k_scratch[slot].reshape(S, GD)
        v = v_scratch[slot].reshape(S, GD)
        dims = (((1,), (1,)), ((), ()))
        logits = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32), dims,
            preferred_element_type=jnp.float32) * scale     # (TbH, S)
        # Causal visibility by absolute position: q row r is token
        # start + qb·Tb + r//H; kv column s is position chunk_start + s.
        q_pos = (start + qb * q_block
                 + jax.lax.broadcasted_iota(jnp.int32, (TbH, 1), 0)
                 // n_heads)
        kv_pos = chunk_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, S), 1)
        live = kv_pos <= q_pos                              # (TbH, S)
        logits = jnp.where(live, logits, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)                         # (TbH, S)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (TbH, GD)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(c == num_chunks - 1)
    def _():
        out_ref[...] = (acc_ref[...]
                        / jnp.maximum(l_ref[...], 1e-30)
                        ).astype(out_ref.dtype)


def paged_prefill_attention_pallas(
    q: jnp.ndarray,             # (T, H, D) — ONE sequence's chunk
    k_pool: jnp.ndarray,        # (L, P, page_size, H_kv·D) FLAT
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,   # (max_pages,) int32
    start_pos: jnp.ndarray,     # scalar int32 — absolute pos of q row 0
    layer: jnp.ndarray | int = 0,
    *,
    pages_per_chunk: int = 8,
    q_block: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal paged attention for a prefill chunk. Returns (T, H, D).

    Visibility: kv position <= q position (covers both in-chunk
    causality and previously cached history). Requires H_kv·D % 128 == 0
    and T % q_block == 0 (the executor's buckets are powers of two).
    """
    T, H, D = q.shape
    L, P, page_size, GD = k_pool.shape
    Hkv = GD // D
    max_pages = block_table.shape[0]
    n_rep = H // Hkv
    if GD % 128:
        raise ValueError(f"H_kv*D = {GD} must be a multiple of 128")
    qb = min(q_block, T)
    while T % qb:
        qb -= 1
    ppc = min(pages_per_chunk, max_pages)
    while max_pages % ppc:
        ppc -= 1

    def vmem_est(qb_, ppc_):
        # f32 acc/m/l + double-buffered KV scratch + q/out BLOCKS —
        # Mosaic DOUBLE-BUFFERS grid in/out blocks, so q and out each
        # cost 2 buffers (undercounting this OOM'd scoped vmem for
        # GD=1024 models: 16.94M vs the 16M limit).
        acc = qb_ * H * (GD + 2) * 4
        kv = 2 * 2 * ppc_ * page_size * GD * k_pool.dtype.itemsize
        qo = 2 * 2 * qb_ * H * GD * q.dtype.itemsize
        return acc + kv + qo

    # Stay under the ~16 MB VMEM scoped limit with headroom: shrink the
    # KV chunk first (large pages made the default 8-page chunk 2 MB+
    # per buffer), then the q block.
    while ppc > 1 and vmem_est(qb, ppc) > 10 * 2**20:
        ppc = max(1, ppc // 2)
        while max_pages % ppc:
            ppc -= 1
    while qb > 8 and vmem_est(qb, ppc) > 10 * 2**20:
        qb //= 2
        while T % qb:
            qb -= 1
    n_qb = T // qb
    num_chunks = max_pages // ppc

    # Block-diagonal q rows: row (t, h) carries q[t, h] in group block.
    eye = jnp.eye(Hkv, dtype=q.dtype)
    q_bd = jnp.einsum("tgrd,gh->tgrhd", q.reshape(T, Hkv, n_rep, D),
                      eye).reshape(T * H, GD)

    kernel = functools.partial(
        _prefill_attn_kernel,
        pages_per_chunk=ppc,
        page_size=page_size,
        num_chunks=num_chunks,
        q_block=qb,
        n_heads=H,
        scale=D ** -0.5,
    )
    TbH = qb * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_qb, num_chunks),
        in_specs=[
            pl.BlockSpec((TbH, GD), lambda b, c, *_: (b, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((TbH, GD), lambda b, c, *_: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((TbH, 1), jnp.float32),
            pltpu.VMEM((TbH, 1), jnp.float32),
            pltpu.VMEM((TbH, GD), jnp.float32),
            pltpu.VMEM((2, ppc, page_size, GD), k_pool.dtype),
            pltpu.VMEM((2, ppc, page_size, GD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, ppc)),
        ],
    )
    meta = jnp.stack([jnp.asarray(start_pos, jnp.int32),
                      jnp.asarray(layer, jnp.int32)])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T * H, GD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_table.astype(jnp.int32), meta,
      q_bd, k_pool, v_pool)
    # Extract each row's diagonal block: (T·H, GD) → (T, H, D).
    out5 = out.reshape(T, Hkv, n_rep, Hkv, D)
    res = jnp.einsum("tgrhd,gh->tgrd", out5, jnp.eye(Hkv, dtype=out.dtype))
    return res.reshape(T, H, D).astype(q.dtype)
