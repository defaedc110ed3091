"""Row-wise work over the LIVE rows of a padded buffer, and the moves
between a mixed step's two layouts.

A mixed step's prompt tokens lie TIGHT: slice after slice with no gap,
in one buffer of S x T rows of which the first ``n_live`` hold a token
(``executor.mixed_chunk_start`` packs them so; ``pf_starts`` (S + 1,)
says where each slice starts and, last, how many rows are live in
all); where a family runs its B decode rows through the same products
they LEAD that buffer (``lead``), always live, and the live prefix is
``lead + n_live`` rows. What is a row's own — norms, projections, RoPE, the dense
feed-forwards, dynamic activation quantisation — can run over that
prefix a TILE of rows at a time (:func:`live_rows`): the trip count is
read on the device, so one compiled program multiplies as many tiles as
hold a token and no more. Which blocks do is each family's
``forward_mixed``'s to say (where a block is small the loop costs what
the rows save). Attention and the KV write keep the (S, T) grid, a
slice a row (:func:`rows_to_grid`, :func:`grid_to_rows`).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: Rows of a tile at the machine's ridge: a tile of R rows does 2 R
#: operations a weight, which against a 2-byte weight at 197 TFLOP/s
#: and 819 GB/s (TPU v5e) takes as long as reading it at R = 240, and
#: against a 1-byte int8 weight at 393 TOP/s likewise; rounded up to
#: whole 128-row tiles of the matrix unit. At this size re-reading a
#: layer's matrices a tile hides under the tile's own products.
ROW_TILE = 256


def row_tile(width: int) -> int:
    """Rows of a tile for slices ``width`` tokens wide."""
    return min(width, ROW_TILE)


def worth_a_loop(total: int, tile: int) -> bool:
    """THE rule for whether rows are run a tile at a time, which
    :func:`live_rows` (the program) and :func:`tile_rows` (the host's
    count) both ask: ``total``, the rows that MAY be dead, are more
    than two tiles. Rows that lead them and are always live (a mixed
    step's decode rows, ``lead``) are not counted: they cannot be
    skipped, so they give a loop nothing to save (SmolLM2's 32 decode
    rows before 512 slice rows run whole, 544 rows; Mistral's 64 before
    1,024 loop). With two tiles or fewer a loop can skip one at most,
    and a program pays for holding it at every start: SmolLM2's
    ``mixed_chunk`` (two 256-row tiles, a loop a layer) took
    13.8-15.1 s to load from XLA's cache as a ``while`` and as a
    ``cond`` a tile, against 11.6-12.0 without (PERF.md, PR 38)."""
    return total > 2 * tile


def tile_rows(n_live: int, tile: int, total: int, lead: int = 0) -> int:
    """Host arithmetic, by the rule :func:`live_rows` runs by: the rows
    its tiles COVER when ``n_live`` of ``total`` rows are live behind
    ``lead`` rows that are always live, less those ``lead`` rows (all
    ``total`` where they are not :func:`worth_a_loop`). Where
    ``tile`` does not divide ``lead + total`` and all of it is live,
    the last tile is moved back and runs some rows a second time: those
    are covered once and counted once, so this never passes
    ``total`` while the loop ran up to ``tile - 1`` rows more."""
    if not worth_a_loop(total, tile):
        return total
    return min(-(-(lead + n_live) // tile) * tile, lead + total) - lead


def live_rows(fn: Callable, n_live, tile: int, *rows, lead: int = 0):
    """``fn`` over the first ``n_live`` rows of ``rows`` (arrays with
    one leading axis M, the first ``lead`` of them always live and
    counted in ``n_live``), ``tile`` rows at a time: ``fn(*tiles)`` takes
    the arrays cut to ``tile`` rows and returns an array or a tuple of
    arrays of ``tile`` rows, each row its own row's function. Returns
    the same with M rows: rows past the last live tile are ZERO and
    were never computed; rows of the last live tile past ``n_live`` are
    whatever ``fn`` makes of what lay there.

    ONE ``while`` whose trip count, ``ceil(n_live / tile)``, the device
    reads from ``n_live`` (a traced scalar): ``fn`` is traced once and
    stands once in the program, whatever M. (Where the ``M - lead``
    rows behind the lead are not :func:`worth_a_loop` all M run whole:
    every row is computed and the program holds no loop.) Where
    ``tile`` does not divide M the last tile is moved back to end at M
    and computes some rows again, to the same values."""
    m = rows[0].shape[0]
    tile = min(tile, m)
    if not worth_a_loop(m - lead, tile):
        return fn(*rows)
    closed, shapes = jax.make_jaxpr(fn, return_shape=True)(
        *(jax.ShapeDtypeStruct((tile,) + x.shape[1:], x.dtype)
          for x in rows))
    leaves, tree = jax.tree.flatten(shapes)

    def body(i, outs):
        at = jnp.minimum(i * tile, m - tile)
        got = jax.core.eval_jaxpr(
            closed.jaxpr, closed.consts,
            *(lax.dynamic_slice_in_dim(x, at, tile) for x in rows))
        return tuple(lax.dynamic_update_slice_in_dim(o, g, at, 0)
                     for o, g in zip(outs, got))

    outs = lax.fori_loop(
        0, (n_live + tile - 1) // tile, body,
        tuple(jnp.zeros((m,) + s.shape[1:], s.dtype) for s in leaves))
    return jax.tree.unflatten(tree, outs)


def grid_positions(positions, lengths, starts, width: int):
    """The grid's geometry from the tight one: ``(positions (S, width),
    contexts (S,))`` as ``forward_prefill`` takes them — each slice's
    positions contiguous from its first token's and held at its last
    valid one past ``lengths``; a slice's context ends behind its last
    token. An unused slice (length 1, starting at the first dead row,
    whose position is 0) comes out as one token at position 0."""
    first = positions[starts[:-1]]
    grid = first[:, None] + jnp.minimum(jnp.arange(width)[None, :],
                                        lengths[:, None] - 1)
    return grid, first + lengths


def rows_to_grid(x, starts, width: int, lead: int = 0):
    """Tight rows x (lead + S * width, ...) -> the grid (S, width, ...):
    slice ``s`` is the ``width`` rows from ``lead + starts[s]``. Past a
    slice's length they are the next slice's rows, or dead ones: what
    the grid's consumers mask by the slices' lengths. S contiguous
    copies. ``starts[s] + width <= S * width`` by construction (slice
    ``s`` starts behind at most ``s`` full ones), so no copy is
    clamped."""
    return jnp.stack([lax.dynamic_slice_in_dim(x, lead + starts[s], width)
                      for s in range(starts.shape[0] - 1)])


def grid_to_rows(grid, starts, into, lead: int = 0):
    """The inverse: the grid's slices (S, width, ...) laid into the
    tight buffer ``into`` from ``lead + starts[s]``, IN ORDER, so that
    what a slice holds past its length is overwritten by the slice
    behind it and the last one's lies past the live rows."""
    for s in range(starts.shape[0] - 1):
        into = lax.dynamic_update_slice_in_dim(into, grid[s],
                                               lead + starts[s], 0)
    return into


def pack_grid(tokens, positions, lengths, used=None):
    """Host side (NumPy), for a caller that holds its slices as a
    right-padded (S, T) grid: ``(tokens (S T,), positions (S T,),
    starts (S + 1,))`` as ``forward_mixed`` takes them, the first
    ``used`` slices (all by default) laid tight in order and the rest
    unused. ``executor.mixed_chunk_start`` packs its staging buffers
    itself, by the same rule."""
    tokens, positions = np.asarray(tokens), np.asarray(positions)
    S, T = tokens.shape
    lens = np.asarray(lengths)[:S if used is None else used]
    starts = np.full(S + 1, lens.sum(), np.int32)
    starts[:len(lens)] = np.cumsum(lens) - lens
    tight = np.zeros((2, S * T), np.int32)
    for s, n in enumerate(lens):
        tight[0, starts[s]:starts[s] + n] = tokens[s, :n]
        tight[1, starts[s]:starts[s] + n] = positions[s, :n]
    return tight[0], tight[1], starts
