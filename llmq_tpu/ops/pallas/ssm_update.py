"""Pallas TPU kernel: the Mamba-2 decode state update, in place.

One token a row moves a layer's state ``H`` (``ops/ssm.py``: ``(N,
H*P)`` float32 a row, 2 MiB at granite-4.0-h-micro's 128 x 4,096)
through::

    H <- H * decay + B (x) (dt X)        decay, dt X: one value a lane
    y  = sum_n H[n, :] * C[n]            a sum down the sublanes

which is all of a decode step's state traffic: every live row's ``H``
read once and written once, 36 layers a step. The kernel walks the
stacked leaf ``(layers, rows, N, H*P)`` a (row, lane block) at a time
through the BlockSpec pipeline and writes each block back where it came
from (``input_output_aliases``): one read and one write of ``H`` a row
a layer, both products on the way, nothing else of the leaf touched.

``B`` and ``C`` arrive already spread over 128 lanes (``(rows, N,
128)``, 64 KiB a row each: 3 % of the state's bytes), so the kernel
multiplies tiles by tiles and broadcasts only rows down sublanes —
nothing crosses lanes and nothing is transposed in VMEM.

A row that is not ``active`` is copied through unchanged (its block is
still read and written: the grid visits every row; a skipped fetch
would take a hand-rolled pipeline — PERF.md section 7).

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

#: Lanes of the state a grid step holds: (N, LANE_BLOCK) float32 is
#: 512 KiB at N = 128; in and out, double-buffered, 2 MiB of VMEM.
LANE_BLOCK = 1024


def ssm_update_viable(n_state: int, width: int) -> bool:
    """Whether the kernel takes a state of ``(n_state, width)`` a row:
    whole (8, 128) tiles."""
    return n_state % 8 == 0 and width % 128 == 0


def _lanes(width: int) -> int:
    return LANE_BLOCK if width % LANE_BLOCK == 0 else 128


def _kernel(layer_ref, active_ref, decay_ref, dtx_ref, bb_ref, cb_ref,
            state_ref, y_ref, out_ref, *, lanes: int):
    del layer_ref                       # the index maps read it
    row = pl.program_id(0)

    @pl.when(active_ref[row] != 0)
    def _():
        bb, cb = bb_ref[0], cb_ref[0]                      # (N, 128)
        for j in range(lanes // 128):
            at = slice(j * 128, (j + 1) * 128)
            new = (state_ref[0, 0, :, at] * decay_ref[0, :, at]
                   + bb * dtx_ref[0, :, at])
            out_ref[0, 0, :, at] = new
            y_ref[0, :, at] = jnp.sum(new * cb, axis=0, keepdims=True)

    @pl.when(active_ref[row] == 0)
    def _():
        out_ref[...] = state_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssm_update_pallas(pool: jnp.ndarray, layer, decay: jnp.ndarray,
                      dtx: jnp.ndarray, bm: jnp.ndarray, cm: jnp.ndarray,
                      active: jnp.ndarray, *, interpret: bool = False):
    """``pool`` (L, R, N, W) float32, its first B rows updated in place
    at ``layer`` (R >= B: a family's leaf holds one row more, nobody's);
    ``decay``, ``dtx`` (B, W) float32 (``exp(dt A)`` and ``dt X``, each
    head's value repeated over its lanes); ``bm``, ``cm`` (B, N);
    ``active`` (B,) bool. Returns ``(y (B, W) float32 = H C without the
    skip term, pool)``."""
    _, _, N, W = pool.shape
    B = decay.shape[0]
    if pool.dtype != jnp.float32 or not ssm_update_viable(N, W):
        raise ValueError(f"ssm update kernel: pool {pool.shape} "
                         f"{pool.dtype}")
    lanes = _lanes(W)
    f32 = jnp.float32
    spread = lambda v: jnp.broadcast_to(                    # noqa: E731
        v.astype(f32)[:, :, None], (B, N, 128))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, W // lanes),
        in_specs=[
            pl.BlockSpec((1, 1, lanes), lambda b, j, *_: (b, 0, j)),
            pl.BlockSpec((1, 1, lanes), lambda b, j, *_: (b, 0, j)),
            pl.BlockSpec((1, N, 128), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, N, 128), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, N, lanes),
                         lambda b, j, lyr, _: (lyr[0], b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, lanes), lambda b, j, *_: (b, 0, j)),
            pl.BlockSpec((1, 1, N, lanes),
                         lambda b, j, lyr, _: (lyr[0], b, 0, j)),
        ])
    y, pool = pl.pallas_call(
        functools.partial(_kernel, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, W), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands: layer, active, decay, dtx, bb, cb, pool -> index 6
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32),
      decay.astype(f32)[:, None, :], dtx.astype(f32)[:, None, :],
      spread(bm), spread(cm), pool)
    return y[:, 0], pool


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
        grid=(rows.shape[0], pool.shape[3] // _lanes(pool.shape[3])),
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
    lanes = _lanes(W)
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
    lanes = _lanes(W)
    # operands: layer, rows, new, pool -> the leaf is operand 3
    return _rows_call(
        pool, layer, rows, (new.astype(pool.dtype), pool),
        [pl.BlockSpec((1, N, lanes), lambda s, j, *_: (s, 0, j)),
         pl.BlockSpec(memory_space=pl.ANY)],
        pl.BlockSpec((1, 1, N, lanes),
                     lambda s, j, lyr, rows: (lyr[0], rows[s], 0, j)),
        jax.ShapeDtypeStruct(pool.shape, pool.dtype), {3: 0}, interpret)
