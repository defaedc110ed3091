"""Pallas TPU kernel: the Mamba-2 decode state update, in place.

One token a row moves a layer's state ``H`` (``ops/ssm.py``: ``(N,
H*P)`` float32 a row, 2 MiB at granite-4.0-h-micro's 128 x 4,096)
through::

    H <- H * decay + B (x) (dt X)        decay, dt X: one value a lane
    y  = sum_n H[n, :] * C[n]            a sum down the sublanes

which is all of a decode step's state traffic: every live row's ``H``
read once and written once, 36 layers a step, and nothing but traffic:
with its body cut down to a copy the kernel takes what it takes whole,
6.3 us a 2 MiB row in and out = 660 GB/s, where XLA's own in-place
pass over 2 GiB moves 683 — the chip's ceiling for a stream that reads
AND writes is 83 % of its 819 GB/s, and no block shape, blocks a step
or reads in flight moved the row's cost (PERF.md section 6, PR 40). So
what the kernel can save is every byte and every operation that is not
a live row's state, and it is its walk of the stacked leaf ``(layers,
rows, N, H*P)``, which stays in HBM and is aliased in and out
(``input_output_aliases``); the walk is the kernel's own, by hand:

- **Only the live rows.** The caller hands it their indices
  (``ops/ssm.decode_walk``, made once a decode step) and the kernel
  loops over those: the state of a row that does not decode is never
  moved — bit-unchanged because nothing names it, not because it was
  copied — and a batch with no live row moves nothing at all.
- **A block of a row at a time, updated where it lands.** A step's
  block is the widest run of a row's lanes of which ``SLOTS`` fit
  ``STATE_VMEM_BYTES`` — :func:`_lanes`, a function of the row's shape
  alone; at 128 x 4,096 float32 the WHOLE row, one contiguous 2 MiB run
  of the leaf. It is copied into a VMEM slot, updated in place there,
  and copied back to where it came from; while it is updated the next
  ``AHEAD`` blocks are on their way in and the last one on its way out
  (with one block ahead in two slots the reads wait on the writes: 495
  us a layer of 64 rows against 426 with two in three, and a third in
  four adds nothing).
- **Nothing spread in HBM.** ``decay``, ``dt X`` and ``y`` lie whole in
  VMEM ((rows, H*P) float32, 1 MiB each at the served sizes; ``y``
  starts as zeros, so the rows the walk never visits read zero), and so
  do ``B`` and ``C`` as they come ((rows, N)): a row's N values are
  laid down the sublanes by one tile transpose each (spread over 128
  lanes by XLA they were 8 MiB written and read again a layer). The
  body multiplies tiles by tiles and broadcasts only rows down
  sublanes, and works through its block 128 lanes at a time, so what
  is live is a few tiles whatever the block.

Beside it the two copies a prompt slice needs: :func:`state_rows_read`
(the states of the rows a program's slices continue) and
:func:`state_rows_write` (the states behind the slices' last tokens,
in place). XLA's gather and ``dynamic_update_slice`` do the same — but
the scan between them prefers the state dimension minor-most, XLA
gives the WHOLE leaf that layout for their sake, and a program without
the update kernel (the prefill program) copied the leaf in and out,
4.9 GB each way at the served sizes. A Mosaic call takes its operands
in the default layout, so these two keep the leaf as it lies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM slots a block of state is copied into, updated in and copied
#: out of, and how many blocks ahead of the one being updated are on
#: their way in (the third slot is the one on its way out).
SLOTS, AHEAD = 3, 2
#: VMEM the slots may take: whole rows at 128 x 4,096 float32, 3 x 2 MiB.
STATE_VMEM_BYTES = 8 << 20
#: The update call's scoped VMEM, stated and not left to the compiler's
#: default: the slots, the operands that lie whole beside them
#: (``decay``, ``dt X``, ``y``: 3 MiB at the served sizes) and the
#: body's own.
VMEM_LIMIT_BYTES = STATE_VMEM_BYTES + (6 << 20)
#: Lanes of the state a grid step of the two row COPIES holds: (N,
#: LANE_BLOCK) float32 is 512 KiB at N = 128.
LANE_BLOCK = 1024


def ssm_update_viable(n_state: int, width: int) -> bool:
    """Whether the kernel takes a state of ``(n_state, width)`` a row:
    whole (8, 128) tiles."""
    return n_state % 8 == 0 and width % 128 == 0


def _lanes(n_state: int, width: int) -> int:
    """Lanes of a row a step of the walk holds: the widest block of
    whole 128-lane tiles that divides the row and of which ``SLOTS``
    (float32) fit ``STATE_VMEM_BYTES`` (128 lanes where none does)."""
    return max((lanes for lanes in range(128, width + 1, 128)
                if width % lanes == 0
                and SLOTS * n_state * lanes * 4 <= STATE_VMEM_BYTES),
               default=128)


def _copy_lanes(width: int) -> int:
    return LANE_BLOCK if width % LANE_BLOCK == 0 else 128


def walk(steps, block_at, slots, read_sem, write_sem, update):
    """The walk of the in-place update kernels (this file's and
    ``ops/pallas/kda_update.py``'s): ``steps`` blocks of the leaf, step
    ``k``'s at ``block_at(k)`` (a view of the leaf in HBM, of a slot's
    shape), each copied into the VMEM slot ``k % SLOTS``, handed to
    ``update(k, slot, arrived)`` — which calls ``arrived()`` once,
    when it needs the block, and leaves the new block in the slot —
    and copied back to where it came from; the next ``AHEAD`` blocks
    are on their way in meanwhile and the last one on its way out."""

    def copy(k, read):
        slot = k % SLOTS
        if read:
            return pltpu.make_async_copy(block_at(k), slots.at[slot],
                                         read_sem.at[slot])
        return pltpu.make_async_copy(slots.at[slot], block_at(k),
                                     write_sem.at[slot])

    for k in range(AHEAD):
        @pl.when(k < steps)
        def _():
            copy(k, True).start()

    def step(k, _):
        update(k, k % SLOTS, copy(k, True).wait)
        copy(k, False).start()
        ahead = k + AHEAD

        @pl.when(ahead < steps)
        def _():
            @pl.when(ahead >= SLOTS)    # its slot's last block is out
            def _():
                copy(ahead - SLOTS, False).wait()
            copy(ahead, True).start()
        return 0

    jax.lax.fori_loop(0, steps, step, 0)
    for back in range(1, SLOTS + 1):    # the writes nothing waited for
        @pl.when(steps >= back)
        def _():
            copy(steps - back, False).wait()


def _kernel(layer_ref, rows_ref, live_ref, decay_ref, dtx_ref, bm_ref,
            cm_ref, pool_in, y_ref, pool, slots, read_sem, write_sem, *,
            blocks: int):
    del pool_in                         # aliased: ``pool`` is the leaf
    _, N, lanes = slots.shape
    lyr = layer_ref[0]

    def block_at(k):
        row, block = rows_ref[k // blocks], k % blocks
        return pool.at[lyr, row, :, pl.ds(block * lanes, lanes)]

    # A row of an operand that lies whole in VMEM: Mosaic loads eight
    # sublanes from an aligned start and no single one from a traced
    # row, so it is picked out of its group of eight (and ``y`` put
    # back into its group: every row is written once, over zeros).
    def group_of(row):
        return (pl.ds(pl.multiple_of(row // 8 * 8, 8), 8),
                jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0) == row % 8)

    def picked(ref, group, pick, at=slice(None)):
        return jnp.sum(jnp.where(pick, ref[group, at], 0.0), axis=0,
                       keepdims=True)

    def down_sublanes(row):             # (1, N) on the lanes -> (N, 128)
        return jnp.broadcast_to(row, (128, N)).T

    def update(k, slot, arrived):
        row = rows_ref[k // blocks]
        group, pick = group_of(row)
        bb = down_sublanes(picked(bm_ref, group, pick))
        cb = down_sublanes(picked(cm_ref, group, pick))
        # ``decay``, ``dt X`` and ``y`` come (rows * blocks, lanes)
        group, pick = group_of(row * blocks + k % blocks)
        arrived()

        # (a loop and not 32 trips written out: every program holds the
        # kernel once a Mamba layer, and written out it cost each of
        # them most of a second of tracing and lowering a layer)
        def tile(j, _):                 # 128 lanes of the block
            at = pl.ds(pl.multiple_of(j * 128, 128), 128)
            new = (slots[slot, :, at] * picked(decay_ref, group, pick, at)
                   + bb * picked(dtx_ref, group, pick, at))
            slots[slot, :, at] = new
            y_ref[group, at] = jnp.where(
                pick, jnp.sum(new * cb, axis=0, keepdims=True),
                y_ref[group, at])
            return 0

        jax.lax.fori_loop(0, lanes // 128, tile, 0)

    y_ref[...] = jnp.zeros_like(y_ref)
    # a live row is ``blocks`` steps
    walk(live_ref[0] * blocks, block_at, slots, read_sem, write_sem, update)


# One function under ``jit``: a program calls it once a Mamba layer with
# the layer as an operand, and JAX then traces and lowers the kernel
# once a program (once a named scope: the scope stays in every call's
# name) and not 36 times — what a run pays again for every program that
# is not loaded from an export artifact (PERF.md section 6, PR 40).
@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_update_pallas(pool: jnp.ndarray, layer, decay: jnp.ndarray,
                      dtx: jnp.ndarray, bm: jnp.ndarray, cm: jnp.ndarray,
                      rows: jnp.ndarray, n_live, *, interpret: bool = False):
    """``pool`` (L, R, N, W) float32, updated in place at ``layer`` in
    the first ``n_live`` of ``rows`` (B,) int32, each a row < B of the
    leaf and none twice (``ops/ssm.decode_walk``); ``decay``, ``dtx``
    (B, W) float32 (``exp(dt A)`` and ``dt X``, each head's value
    repeated over its lanes); ``bm``, ``cm`` (B, N). Returns ``(y (B, W)
    float32 = H C without the skip term, zeros in a row that was not
    named; pool)``."""
    _, _, N, W = pool.shape
    B = decay.shape[0]
    if pool.dtype != jnp.float32 or not ssm_update_viable(N, W):
        raise ValueError(f"ssm update kernel: pool {pool.shape} "
                         f"{pool.dtype}")
    lanes = _lanes(N, W)
    blocks = W // lanes
    f32 = jnp.float32

    def whole(v, rows):                 # (rows, ...) in groups of eight
        v = v.astype(f32).reshape(rows, -1)
        return jnp.pad(v, ((0, -rows % 8), (0, 0)))

    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(1,),
        in_specs=[in_vmem, in_vmem, in_vmem, in_vmem,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[in_vmem, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((SLOTS, N, lanes), f32),
                        pltpu.SemaphoreType.DMA((SLOTS,)),
                        pltpu.SemaphoreType.DMA((SLOTS,))])
    y, pool = pl.pallas_call(
        functools.partial(_kernel, blocks=blocks),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((-(-B * blocks // 8) * 8, lanes),
                                        f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: layer, rows, n_live, decay, dtx, bm, cm, pool
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
      jnp.asarray(n_live, jnp.int32).reshape(1),
      whole(decay, B * blocks), whole(dtx, B * blocks), whole(bm, B),
      whole(cm, B), pool)
    return y[:B * blocks].reshape(B, W), pool


def _rows_kernel(layer_ref, rows_ref, src_ref, *rest):
    # ``rest``: the aliased leaf (untouched here: its blocks are the
    # output's), then the output
    del layer_ref, rows_ref             # the index maps read them
    out_ref = rest[-1]
    out_ref[...] = src_ref[...].reshape(out_ref.shape)


def _rows_call(pool, layer, rows, operands, in_specs, out_specs, out_shape,
               aliases, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows.shape[0], pool.shape[3] // _copy_lanes(pool.shape[3])),
        in_specs=in_specs, out_specs=out_specs)
    return pl.pallas_call(
        _rows_kernel, grid_spec=grid_spec, out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
      *operands)


def state_rows_read(pool: jnp.ndarray, layer, rows: jnp.ndarray, *,
                    interpret: bool = False) -> jnp.ndarray:
    """``pool[layer, rows]`` (S, N, W) of the stacked leaf ``pool``
    (L, R, N, W); ``rows`` (S,) each a row of the leaf."""
    _, _, N, W = pool.shape
    lanes = _copy_lanes(W)
    return _rows_call(
        pool, layer, rows, (pool,),
        [pl.BlockSpec((1, 1, N, lanes),
                      lambda s, j, lyr, rows: (lyr[0], rows[s], 0, j))],
        pl.BlockSpec((1, N, lanes), lambda s, j, *_: (s, 0, j)),
        jax.ShapeDtypeStruct((rows.shape[0], N, W), pool.dtype), {},
        interpret)


def state_rows_write(pool: jnp.ndarray, layer, rows: jnp.ndarray,
                     new: jnp.ndarray, *, interpret: bool = False
                     ) -> jnp.ndarray:
    """``pool[layer, rows[s]] = new[s]`` in place (``new`` (S, N, W) in
    the leaf's type). Two slices never name one row, except the row
    that is nobody's, where the last one written stays."""
    _, _, N, W = pool.shape
    lanes = _copy_lanes(W)
    # operands: layer, rows, new, pool -> the leaf is operand 3
    return _rows_call(
        pool, layer, rows, (new.astype(pool.dtype), pool),
        [pl.BlockSpec((1, N, lanes), lambda s, j, *_: (s, 0, j)),
         pl.BlockSpec(memory_space=pl.ANY)],
        pl.BlockSpec((1, 1, N, lanes),
                     lambda s, j, lyr, rows: (lyr[0], rows[s], 0, j)),
        jax.ShapeDtypeStruct(pool.shape, pool.dtype), {3: 0}, interpret)
