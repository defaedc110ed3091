"""Pallas TPU kernel: the delta rule's decode state update
(``ops/kda.py``), in place.

One token a row moves a layer's state ``S`` (``(d_k, H * d_v)`` float32
a row: 2 MiB at 128 x 4,096, 32 heads; 4 MiB at 128 x 8,192, 64 heads)
through::

    u  = sum_k k[k] (a[k] S[k, :])       what the decayed state answers
    S <- a * S + (b k) (v - u)^T         a, k, b k, q: columns of a head
    o  = sum_k q[k] S[k, :]

Unlike ``ops/pallas/ssm_update.py``'s update — a lane-wise decay and a
rank-one add, one pass — the state has to be REDUCED over before it can
be written, so a row is visited twice; both visits happen in VMEM, and
HBM sees what it sees there: every live row's state read once and
written once. The walk is that kernel's (``ssm_update.walk``): only the
live rows (``ops/ssm.decode_walk``), a HEAD BLOCK of a row a step
copied into a VMEM slot, updated where it lands and copied back to
where it came from, the next blocks on their way in meanwhile; the leaf
stays in HBM and is aliased in and out. The heads of a row are
independent, so a block is whole heads: the WHOLE row where its four
columns a head fit ``COLS`` lanes and ``SLOTS`` rows fit the walk's
VMEM (up to 32 heads: one step a live row, one contiguous run of the
leaf), else blocks of ``COLS / 4`` = 32 heads (:func:`head_blocks`: 64
heads are two steps a live row, 2 MiB each, 4,096 lanes of every one
of the row's d_k sublanes).

What is a column of the state — the decay ``a``, ``k``, ``b k`` and
``q``, d_k values a head each — comes packed A BLOCK, ``(rows * blocks,
d_k, 4 heads-a-block)``: the key dimension already on the sublanes, so
the body broadcasts a lane along its head's 128 lanes and transposes
nothing. ``v`` and ``o`` are ``(rows * blocks, heads-a-block, d_v)``, a
head a sublane. Neither the decay's form nor beta's range is the
kernel's business: both reach it as data (``a`` in (0, 1], ``b k``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmq_tpu.ops.pallas.ssm_update import SLOTS, STATE_VMEM_BYTES, walk

#: Lanes of the packed columns of a head block: 4 a head are used.
COLS = 128


def head_blocks(n_heads: int) -> int:
    """Steps of the walk a live row takes: 1 while a row's four columns
    a head fit ``COLS`` lanes (32 heads), else its heads in whole blocks
    of ``COLS / 4`` (0: the heads are no whole blocks)."""
    per = COLS // 4
    if n_heads <= per:
        return 1
    return n_heads // per if n_heads % per == 0 else 0


def kda_update_viable(d_k: int, n_heads: int, d_v: int) -> bool:
    """Whether the kernel takes a state of ``(d_k, n_heads * d_v)`` a
    row: a head's values one 128-lane tile, whole sublane tiles, and the
    HEAD-BLOCK rule — the row walked whole where its four columns a head
    fit ``COLS`` lanes, else in whole blocks of ``COLS / 4`` heads
    (:func:`head_blocks`) — with ``SLOTS`` blocks within the walk's
    VMEM (three 2 MiB blocks of 32 heads at d_k 128; three whole 4 MiB
    rows of 64 heads would be 12 MiB of its 8)."""
    blocks = head_blocks(n_heads)
    return (d_v == 128 and d_k % 8 == 0 and blocks > 0
            and SLOTS * d_k * (n_heads // blocks) * d_v * 4
            <= STATE_VMEM_BYTES)


def _kernel(layer_ref, rows_ref, live_ref, cols_ref, v_ref, pool_in, o_ref,
            pool, slots, read_sem, write_sem, *, n_heads: int, blocks: int):
    """``n_heads`` the heads of a block, ``blocks`` the blocks a row."""
    del pool_in                         # aliased: ``pool`` is the leaf
    _, dk, lanes = slots.shape
    lyr = layer_ref[0]

    # (One block a row is a branch of its own, here and in ``update``,
    # for ONE reason: the 32-head call then traces to the program the
    # accepted 32-head cell ran before head blocks, to the digest
    # (``tests/test_kda.py``). ``pl.ds(0, lanes)`` names the same block.)
    def block_at(k):
        if blocks == 1:                 # the whole row, as it lies
            return pool.at[lyr, rows_ref[k]]
        return pool.at[lyr, rows_ref[k // blocks], :,
                       pl.ds(k % blocks * lanes, lanes)]

    def update(k, slot, arrived):
        # block ``k % blocks`` of the row: its packed operands' index
        row = (rows_ref[k] if blocks == 1
               else rows_ref[k // blocks] * blocks + k % blocks)
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
    # a live row is ``blocks`` steps
    walk(live_ref[0] * blocks, block_at, slots, read_sem, write_sem, update)


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
    nb = head_blocks(H)
    hb = H // nb                        # heads a block
    cols = jnp.concatenate(
        [jnp.swapaxes(x, 1, 2) for x in (
            jnp.exp(g.astype(f32)), kf, kf * beta.astype(f32)[..., None],
            q.astype(f32))], axis=-1)                          # (B, dk, 4H)
    if nb > 1:                          # a block's four columns side by side
        cols = cols.reshape(B, dk, 4, nb, hb).transpose(0, 3, 1, 2, 4)
        cols = cols.reshape(B * nb, dk, 4 * hb)
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, COLS - 4 * hb)))
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(1,),
        in_specs=[in_vmem, in_vmem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[in_vmem, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((SLOTS, dk, W // nb), f32),
                        pltpu.SemaphoreType.DMA((SLOTS,)),
                        pltpu.SemaphoreType.DMA((SLOTS,))])
    whole = B * (nb * dk * COLS + 2 * H * dv) * 4
    o, pool = pl.pallas_call(
        functools.partial(_kernel, n_heads=hb, blocks=nb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B * nb, hb, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: layer, rows, n_live, cols, v, pool
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=STATE_VMEM_BYTES + whole + (8 << 20)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
      jnp.asarray(n_live, jnp.int32).reshape(1), cols,
      v.astype(f32).reshape(B * nb, hb, dv), pool)
    return o.reshape(B, H, dv), pool
