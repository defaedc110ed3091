"""Pallas TPU kernel: the delta rule's decode state update
(``ops/kda.py``), in place.

One token a row moves a layer's state ``S`` (``(d_k, H * d_v)`` float32
a row, 2 MiB at 128 x 4,096) through::

    u  = sum_k k[k] (a[k] S[k, :])       what the decayed state answers
    S <- a * S + (b k) (v - u)^T         a, k, b k, q: columns of a head
    o  = sum_k q[k] S[k, :]

Unlike ``ops/pallas/ssm_update.py``'s update — a lane-wise decay and a
rank-one add, one pass — the state has to be REDUCED over before it can
be written, so a row is visited twice; both visits happen in VMEM, and
HBM sees what it sees there: every live row's state read once and
written once. The walk is that kernel's (``ssm_update.walk``): only the
live rows (``ops/ssm.decode_walk``), a whole row a step copied into a
VMEM slot, updated where it lands and copied back to where it came
from, the next rows on their way in meanwhile; the leaf stays in HBM
and is aliased in and out.

What is a column of the state — the decay ``a``, ``k``, ``b k`` and
``q``, d_k values a head each — comes packed ``(rows, d_k, 4 H)``: the
key dimension already on the sublanes, so the body broadcasts a lane
along its head's 128 lanes and transposes nothing. ``v`` and ``o`` are
``(rows, H, d_v)``, a head a sublane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmq_tpu.ops.pallas.ssm_update import SLOTS, STATE_VMEM_BYTES, walk

#: Lanes of the packed columns: 4 H of them are used.
COLS = 128


def kda_update_viable(d_k: int, n_heads: int, d_v: int) -> bool:
    """Whether the kernel takes a state of ``(d_k, n_heads * d_v)`` a
    row: a head's values one 128-lane tile, whole sublane tiles, the
    four columns a head within ``COLS`` lanes, and ``SLOTS`` whole rows
    within the walk's VMEM."""
    return (d_v == 128 and d_k % 8 == 0 and 4 * n_heads <= COLS
            and SLOTS * d_k * n_heads * d_v * 4 <= STATE_VMEM_BYTES)


def _kernel(layer_ref, rows_ref, live_ref, cols_ref, v_ref, pool_in, o_ref,
            pool, slots, read_sem, write_sem, *, n_heads: int):
    del pool_in                         # aliased: ``pool`` is the leaf
    _, dk, _ = slots.shape
    lyr = layer_ref[0]

    def update(k, slot, arrived):
        row = rows_ref[k]
        cols = cols_ref[row]                                   # (dk, COLS)
        arrived()
        for h in range(n_heads):        # a head: one (dk, 128) tile

            def col(j):
                at = j * n_heads + h
                return jnp.broadcast_to(cols[:, at:at + 1], (dk, 128))

            at = pl.ds(h * 128, 128)
            s = slots[slot, :, at] * col(0)
            u = jnp.sum(s * col(1), axis=0, keepdims=True)
            new = s + col(2) * (v_ref[row, h:h + 1, :] - u)
            slots[slot, :, at] = new
            o_ref[row, h:h + 1, :] = jnp.sum(new * col(3), axis=0,
                                             keepdims=True)

    o_ref[...] = jnp.zeros_like(o_ref)
    # a live row is one step
    walk(live_ref[0], lambda k: pool.at[lyr, rows_ref[k]], slots, read_sem,
         write_sem, update)


# One function under ``jit`` with the layer as an operand: a program
# traces and lowers the kernel once, not once a layer
# (``ssm_update_pallas``).
@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_update_pallas(pool: jnp.ndarray, layer, q: jnp.ndarray,
                      k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                      beta: jnp.ndarray, rows: jnp.ndarray, n_live, *,
                      interpret: bool = False):
    """``pool`` (L, R, d_k, H*d_v) float32, updated in place at
    ``layer`` in the first ``n_live`` of ``rows`` (B,) int32, each a row
    < B of the leaf and none twice (``ops/ssm.decode_walk``); ``q``,
    ``k``, ``g`` (B, H, d_k), ``v`` (B, H, d_v), ``beta`` (B, H) as
    ``ops/kda.kda_update`` takes them. Returns ``(o (B, H, d_v) float32,
    zeros in a row that was not named; pool)``."""
    _, _, dk, W = pool.shape
    B, H, dv = v.shape
    if pool.dtype != jnp.float32 or not kda_update_viable(dk, H, dv):
        raise ValueError(f"kda update kernel: pool {pool.shape} "
                         f"{pool.dtype}, {H} heads")
    f32 = jnp.float32
    kf = k.astype(f32)
    cols = jnp.concatenate(
        [jnp.swapaxes(x, 1, 2) for x in (
            jnp.exp(g.astype(f32)), kf, kf * beta.astype(f32)[..., None],
            q.astype(f32))], axis=-1)                          # (B, dk, 4H)
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, COLS - 4 * H)))
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(1,),
        in_specs=[in_vmem, in_vmem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[in_vmem, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((SLOTS, dk, W), f32),
                        pltpu.SemaphoreType.DMA((SLOTS,)),
                        pltpu.SemaphoreType.DMA((SLOTS,))])
    whole = B * (dk * COLS + 2 * H * dv) * 4
    return pl.pallas_call(
        functools.partial(_kernel, n_heads=H),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: layer, rows, n_live, cols, v, pool
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=STATE_VMEM_BYTES + whole + (8 << 20)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
      jnp.asarray(n_live, jnp.int32).reshape(1), cols, v.astype(f32), pool)
