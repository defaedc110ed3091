"""Routed feed-forward (DeepSeek-V3's, LongCat-Flash's): a float32
router (sigmoid scores, or a softmax over all of them) with a
selection-only correction bias, the top ``k`` of ``E`` experts a token,
and a GROUPED product that multiplies each token with its own experts
and no others.

``route`` scores (one product) and ``choose`` chooses and weighs — the
one choice of every family, also of one whose scores come from a router
network of its own (``models/zaya.py``); ``routed_ffn`` sorts the (token, expert)
pairs by expert, gathers the tokens in that order and runs the three
SwiGLU matrices as grouped products over the sorted rows (one product
a group, the group being the expert's rows), then weighs and adds each
token's ``k`` results. An expert that got no token has an empty group:
nothing is multiplied for it and its matrices are not read. Rows that
must not count (a decode row that is not live, a slice's padding) are
sorted behind every group and multiplied by nothing.

The grouped product is JAX's own Pallas kernel for it on the TPU
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: ``gmm`` in a device
trace, two calls a routed layer) and ``jax.lax.ragged_dot``
elsewhere, by the policy every kernel of this repo follows
(``ops/attention._kernel_route``: ``LLMQ_PALLAS``). Both were measured on the chip at this model's
widths (PERF.md §6, PR 31): both read only the experts a batch
touches, and the kernel is the faster by a quarter at 64 rows. The
kernel's tiles are cut to the matrices it multiplies: ``gmm_tiling``, a
function of the call's shapes and the VMEM budget alone, whose
docstring states the rule (PERF.md §6, PR 57: a block that overhangs
its matrix is multiplied in full, and a contraction tile that does not
divide it is masked at every visit).

**A chip's share of the experts** (``routed_ffn(..., held=(lo, hi))``):
the router still scores every expert and a token still chooses its
``k`` among all of them, but this chip holds only the matrices of the
experts ``lo .. hi - 1`` (``w_gate_up[0]`` is expert ``lo``'s). A pair
whose expert is held elsewhere is multiplied with nothing here: the
sum this chip returns is its PART of the routed layer's result, and the
parts of all shares add up to the whole (``tests/test_moe_share.py``).
Nothing here stands in for the other chips or for the exchange with
them. **Zero-compute experts** (``n_routed``): a chosen index at or
above it is an identity expert, which adds ``g_e x`` and enters no
group (``identity_gate`` gives the token's summed weight of them; the
token's home chip adds it). A share's held pairs, and the live pairs
of a tight mixed step (``n_live``), are a small and varying part of the
``N k`` chosen ones: multiplied a BLOCK of sorted pairs at a time, then
scatter-added by token (``_held_blocks``) or gathered (``_routed_live``).

``stats`` of a call: tokens each (held) expert received and how many
experts received any — with a share or zero-compute experts also the
slots that went to zero-compute experts and to experts held elsewhere
— which the serving programs sum over their steps and layers
(``engine.get_stats()["moe"]``; the family states the layout:
``step_stats_layout``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from llmq_tpu.utils.logging import get_logger
from llmq_tpu.utils.profiling import scope

log = get_logger("ops.moe")


def _limit(sel: jnp.ndarray, n_group: int, topk_group: int) -> jnp.ndarray:
    """``route``'s selection scores ``sel`` (N, E) = s + bias, float32,
    LIMITED TO GROUPS (DeepSeek-V3's ``n_group`` / ``topk_group``): the
    E experts lie in ``n_group`` groups of E / n_group neighbours, a
    group's score is the sum of its top 2 of ``sel``, the best
    ``topk_group`` groups are kept and the experts of every other group
    are out of the choice (-inf). ``n_group`` 1 (``route``'s default):
    ``sel`` as it came — the program of a router without groups, which
    ``tests/test_kda.py`` pins."""
    sel = sel.astype(jnp.float32)
    if n_group == 1:
        return sel
    N, E = sel.shape
    if E % n_group or not 0 < topk_group <= n_group:
        raise ValueError(f"{E} experts in {n_group} groups, the best "
                         f"{topk_group} kept")
    by_group = sel.reshape(N, n_group, E // n_group)
    score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)        # (N, G)
    kept = jnp.zeros((N, n_group), bool).at[
        jnp.arange(N)[:, None], lax.top_k(score, topk_group)[1]].set(True)
    return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(N, E)


def choose(scores: jnp.ndarray, bias: jnp.ndarray, *, top_k: int,
           scale: float, norm_topk: bool = True, n_group: int = 1,
           topk_group: int = 1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The choice, for every family: ``scores`` (N, E) float32 — a
    token's score of each expert, whatever made it (``route``'s one
    product; a family's own router network) — -> (experts (N, k) int32,
    gates (N, k) float32). The ``k`` experts are the top ``k`` of
    ``scores + bias``, among the best ``topk_group`` of ``n_group``
    groups where there are groups (``_limit``); the gates are the chosen
    ``scores`` (WITHOUT the bias), normalised to sum 1 where
    ``norm_topk``, times ``scale``. No scope of its own: the caller's
    ``moe_route`` covers the scores and the choice."""
    _, experts = lax.top_k(_limit(scores + bias, n_group, topk_group), top_k)
    g = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), g * scale


def route(x: jnp.ndarray, w_router: jnp.ndarray, bias: jnp.ndarray, *,
          top_k: int, scale: float, norm_topk: bool = True, n_group: int = 1,
          topk_group: int = 1, scoring: str = "sigmoid"
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``x`` (N, D) -> (experts (N, k) int32, gates (N, k) float32).

    s = sigmoid(x W_r) — or, ``scoring="softmax"``, softmax over all E
    of x W_r — in float32 at the highest matmul precision (a
    bf16 pass swaps near-tied experts); experts and gates are
    :func:`choose`'s of ``s``."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown router scoring {scoring!r}")
    with scope("moe_route"):
        logits = jnp.dot(x.astype(jnp.float32),
                         w_router.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        s = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
        return choose(s, bias, top_k=top_k, scale=scale,
                      norm_topk=norm_topk, n_group=n_group,
                      topk_group=topk_group)


#: Scoped VMEM a Mosaic kernel is compiled with on a v5e where its call
#: names no limit, as megablox's does not.
VMEM_SCOPED = 16 * 2 ** 20
#: What the rule lets the kernel's own buffers take of it; the rest is
#: the compiler's (its scoped use is ``gmm_tile_bytes`` give or take
#: 1 MB at the served widths: ``tests/test_tpu_compile.py``).
VMEM_BUDGET = 12 * 2 ** 20


def gmm_tile_bytes(tm: int, tk: int, tn: int, itemsize: int = 2) -> int:
    """VMEM the grouped product holds at these tiles: the weight block,
    the rows and the output, each twice (the pipeline fetches the next
    while this one is multiplied), and the float32 accumulator."""
    return 2 * itemsize * (tk * tn + tm * tk + tm * tn) + 4 * tm * tn


def _tiles_of(d: int) -> Tuple[int, ...]:
    """The tiles a dimension may be cut in: the multiples of 128 lanes
    that divide it; the whole of a dimension that is no such multiple
    (a tiny model's: a block as wide as its array is legal)."""
    return tuple(t for t in range(128, d + 1, 128) if d % t == 0) or (d,)


@functools.lru_cache(maxsize=None)
def gmm_tiling(m: int, K: int, N: int, itemsize: int = 2
               ) -> Tuple[int, int, int]:
    """The grouped product's tiles (rows, contraction, output) for ``m``
    sorted rows times ``(E, K, N)`` matrices of ``itemsize`` bytes an
    element: megablox's ``tiling=``, asked at trace time and logged
    once a distinct shape.

    The kernel's grid is (output tiles, visits, contraction tiles), a
    visit being one overlap of a row tile with an expert's rows, and
    every grid step multiplies a FULL ``(tm, tk) x (tk, tn)`` block
    whatever part of it lies inside the matrix; where ``tk`` does not
    divide ``K`` the last contraction step also masks both blocks in
    float32 on the vector units. So:

    * ``tk`` and ``tn`` DIVIDE ``K`` and ``N`` (``_tiles_of``): no block
      overhangs its matrix and no step is masked — the work is the
      matrix's, and a visit stays bound by the expert's bytes;
    * ONE contraction step (``tk`` = ``K``) wherever an output tile of
      it fits ``VMEM_BUDGET`` (``gmm_tile_bytes``): the weight block's
      index is then (expert, 0, output tile), which the pipeline does
      not fetch again when the next row tile belongs to the same
      expert, the rows' block stays while one row tile is visited, and
      no partial sum goes through the accumulator twice. Measured at
      every served shape it is as fast as any other exact tiling at a
      decode step's rows and 9-45 % faster where an expert has more
      rows than a tile, down to an output tile of 256;
    * then the largest weight block that fits (the fewest grid steps a
      visit), the wider output tile among equals;
    * ``tm`` is 128: a visit then does 128 flop a weight byte against
      the chip's 240 (197 Tflop/s / 819 GB/s) and stays bound by the
      bytes; at 256 rows it is bound by the MXU before any padding,
      and a decode step's few rows an expert save no visit for it
      (measured: slower at every row count but a full grid, equal
      there).

    ``m`` does not enter the choice (the measurements: PERF.md section
    6, PR 57); it is megablox's signature and the log's key."""
    tm = 128
    fits = [(tk, tn) for tk in _tiles_of(K) for tn in _tiles_of(N)
            if gmm_tile_bytes(tm, tk, tn, itemsize) <= VMEM_BUDGET]
    if not fits:
        raise ValueError(f"no tiles of {K} x {N} fit {VMEM_BUDGET} bytes")
    tk, tn = max(fits, key=lambda t: (t[0] == K, t[0] * t[1], t[1]))
    log.info("gmm tiling m=%d K=%d N=%d: tiles (%d, %d, %d), %d k-steps, "
             "%d n-steps, padded/true work %.3f", m, K, N, tm, tk, tn,
             K // tk, N // tn, (m + -m % tm) / m)
    return tm, tk, tn


def moe_grouped_matmul_pallas(xs: jnp.ndarray, w: jnp.ndarray,
                              counts: jnp.ndarray, *,
                              interpret: bool = False) -> jnp.ndarray:
    """xs (M, K) sorted by group, w (E, K, N), counts (E,) -> (M, N):
    row i times the matrix of its group. Rows behind the last group
    come out undefined."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m = xs.shape[0]
    tiles = gmm_tiling(m, *w.shape[1:], w.dtype.itemsize)
    out = gmm(jnp.pad(xs, ((0, -m % tiles[0]), (0, 0))), w, counts,
              preferred_element_type=xs.dtype, tiling=tiles,
              interpret=interpret)
    return out[:m]


def _grouped(xs, w, counts):
    from llmq_tpu.ops.attention import _kernel_jit, _kernel_route
    use, interp = _kernel_route(128)
    if not use:
        return lax.ragged_dot(xs, w, counts)
    fn = _kernel_jit("moe_grouped_matmul", lambda: jax.jit(
        moe_grouped_matmul_pallas, static_argnames=("interpret",)))
    return fn(xs, w, counts, interpret=interp)


#: Sorted pairs a block of ``_held_blocks`` multiplies: two row tiles
#: of the grouped product. A decode step of 128 rows brings 32 held
#: pairs on average to 16 of 768 experts: one block.
HELD_BLOCK = 256


def identity_gate(experts: jnp.ndarray, gates: jnp.ndarray, n_routed: int,
                  live: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(N,) float32: each token's summed weight of the zero-compute
    (identity) experts it chose — the indices at or above ``n_routed``.
    The routed layer adds this times the token itself. 0 for a row
    that is not live."""
    with scope("moe_combine"):
        g = jnp.sum(jnp.where(experts >= n_routed, gates, 0.0), axis=-1)
        return g if live is None else jnp.where(live, g, 0.0)


def _held_blocks(x, key, gates, w_gate_up, w_down, counts, k):
    """The held pairs' weighted SwiGLU results summed by token, (N, D)
    float32. ``key`` (N k,): each pair's group among the held experts,
    or ``E_held`` for a pair that is multiplied with nothing; ``counts``
    (E_held,) the groups' sizes. The pairs are sorted by group and
    taken ``blk`` (``HELD_BLOCK``) at a time while any are left: a block's
    groups are the groups' overlaps with its rows, its tokens' rows are
    gathered, multiplied and added to their tokens."""
    N, D = x.shape
    F = w_gate_up.shape[-1] // 2
    M, blk = key.shape[0], HELD_BLOCK
    with scope("moe_route"):
        order = jnp.pad(jnp.argsort(key, stable=True), (0, -M % blk))
        ends = jnp.cumsum(counts)
        starts, n_held = ends - counts, ends[-1]
        flat_gates = gates.reshape(-1)

    def block(b, y):
        with scope("moe_route"):
            lo = b * blk
            pairs = lax.dynamic_slice(order, (lo,), (blk,))
            tok = pairs // k
            size = (jnp.clip(ends, lo, lo + blk)
                    - jnp.clip(starts, lo, lo + blk))
            xs = x[tok]
        with scope("moe_experts"):
            gu = _grouped(xs, w_gate_up, size)
            a = (jax.nn.silu(gu[:, :F].astype(jnp.float32)).astype(x.dtype)
                 * gu[:, F:])
            ys = _grouped(a, w_down, size)
        with scope("moe_combine"):
            w = jnp.where(lo + jnp.arange(blk) < n_held, flat_gates[pairs],
                          0.0)
            ys = jnp.where(w[:, None] != 0,
                           ys.astype(jnp.float32) * w[:, None], 0.0)
            return y.at[tok].add(ys)

    return lax.fori_loop(0, (n_held + blk - 1) // blk, block,
                         jnp.zeros((N, D), jnp.float32))


def routed_ffn(x: jnp.ndarray, experts: jnp.ndarray, gates: jnp.ndarray,
               w_gate_up: jnp.ndarray, w_down: jnp.ndarray,
               live: Optional[jnp.ndarray] = None, *,
               held: Optional[Tuple[int, int]] = None,
               n_routed: Optional[int] = None, n_live=None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """sum_i gates_i SwiGLU_{experts_i}(x) for every token of ``x``
    (N, D). ``w_gate_up`` (E, D, 2F) holds each expert's gate and up
    matrices side by side, ``w_down`` (E, F, D). ``live`` (N,) bool:
    rows that are not live are multiplied with nothing and come out 0.

    Returns (y (N, D) in ``x.dtype``, stats (E + 1,) int32: the tokens
    each expert received, then the number of experts that received
    any).

    ``held=(lo, hi)``: the matrices are those of the experts ``lo ..
    hi - 1`` of the router's indices and the sum runs over the chosen
    experts among them alone; ``n_routed``: the router's indices at or
    above it are zero-compute experts, which are not multiplied here
    (``identity_gate``). With either, stats is (E_held + 3,): the
    tokens each held expert received, the held experts that received
    any, the slots that chose a zero-compute expert, the slots whose
    expert is held elsewhere. All held, none zero-compute: the call
    without either, to the bit. ``n_live``: see ``_routed_live``."""
    N, k = experts.shape
    E, _, F2 = w_gate_up.shape
    F = F2 // 2
    flat = experts.reshape(-1)
    lo, hi = held if held is not None else (0, E)
    if hi - lo != E:
        raise ValueError(f"held {held}: {E} experts' matrices were given")
    if (lo, hi) != (0, E) or n_routed not in (None, E):
        return _routed_share(x, flat, gates, w_gate_up, w_down, live, k,
                             lo, n_routed)
    if n_live is not None:
        return _routed_live(x, flat, gates, w_gate_up, w_down, live, n_live)
    with scope("moe_route"):
        if live is not None:     # (dead rows sort behind every group)
            flat = jnp.where(jnp.repeat(live, k), flat, E)
        order = jnp.argsort(flat, stable=True)
        counts = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
        xs = x[order // k]                                 # (N*k, D)
    with scope("moe_experts"):
        gu = _grouped(xs, w_gate_up, counts)
        a = (jax.nn.silu(gu[:, :F].astype(jnp.float32)).astype(x.dtype)
             * gu[:, F:])
        ys = _grouped(a, w_down, counts)                   # (N*k, D)
    with scope("moe_combine"):
        w = jnp.where(flat[order] < E, gates.reshape(-1)[order], 0.0)
        ys = jnp.where(w[:, None] != 0,
                       ys.astype(jnp.float32) * w[:, None], 0.0)
        y = jnp.zeros((N * k, x.shape[-1]), jnp.float32).at[order].set(ys)
        y = jnp.sum(y.reshape(N, k, -1), axis=1).astype(x.dtype)
        stats = jnp.concatenate(
            [counts, jnp.sum(counts > 0, dtype=jnp.int32)[None]])
        return y, stats


def _routed_share(x, flat, gates, w_gate_up, w_down, live, k, lo, n_routed):
    """``routed_ffn`` for a share of the experts and / or zero-compute
    experts: ``flat`` (N k,) the chosen router indices."""
    E = w_gate_up.shape[0]
    with scope("moe_route"):
        alive = (jnp.repeat(live, k) if live is not None
                 else jnp.ones(flat.shape, jnp.bool_))
        here = alive & (flat >= lo) & (flat < lo + E)
        zero = (alive & (flat >= n_routed) if n_routed is not None
                else jnp.zeros(flat.shape, jnp.bool_))
        key = jnp.where(here, flat - lo, E)
        counts = jnp.zeros((E,), jnp.int32).at[key].add(1, mode="drop")
    y = _held_blocks(x, key, gates, w_gate_up, w_down, counts, k)
    with scope("moe_combine"):
        n_zero = jnp.sum(zero, dtype=jnp.int32)
        n_away = jnp.sum(alive, dtype=jnp.int32) - n_zero - jnp.sum(counts)
        stats = jnp.concatenate(
            [counts, jnp.stack([jnp.sum(counts > 0, dtype=jnp.int32),
                                n_zero, n_away])])
        return y.astype(x.dtype), stats


#: Sorted pairs a block of ``_routed_live`` multiplies. A tight mixed
#: step of Mellum 2 brings ~4,000 live pairs to 64 experts: a block
#: reads again the one expert it shares with the block before it.
LIVE_BLOCK = 2048
#: Tokens a tile of ``_routed_live``'s sum by token reads back at once
#: (``k`` held rows each): of 128, 256, 512 and 1,024 the fastest at
#: Xing's and Mellum's widths, full and part full, by 0.5-2.5 % of a
#: routed layer over 256 (PERF.md section 6, PR 59).
LIVE_TILE = 128


def _routed_live(x, flat, gates, w_gate_up, w_down, live, n_live):
    """``routed_ffn`` where the caller says how many rows, LYING FIRST,
    can be live (``n_live``, a traced scalar: a tight mixed step's
    decode rows and the slices' tokens behind them, ``ops/rows.py``; a
    row at or past it is dead whatever ``live`` says). Every expert is
    held, so the pairs that are multiplied are the first
    ``sum(counts)`` of the sorted order and a live token has exactly
    ``k`` of them, at places known before any product runs. Two loops:

    * over ``LIVE_BLOCK`` sorted pairs at a time while live pairs are
      left (``_held_blocks``'s gather and products): a block WRITES its
      results, in ``x.dtype`` as the grouped product returns them, to
      its rows of ONE ``(N k up to a whole block, D)`` array in the
      sorted order — a contiguous slice, no index a row. Rows at or
      past ``sum(counts)`` hold whatever lay there;
    * over ``LIVE_TILE`` tokens at a time while the tile's first token
      is under ``n_live``: a token GATHERS its ``k`` rows back by the
      inverse of the sort, weighs them in float32 and adds slot 0
      first, then 1 ... k - 1 — the plain form's sum to the bit on the
      CPU (XLA's TPU reduction over that form's middle axis adds in
      another order: one bfloat16 step apart). A pair that is not live
      weighs an exact 0 and is selected away, whatever its row holds.

    The sort keeps its static length; no row is scatter-added. What
    the sum needs beyond the products — the array, the inverse, the
    second loop — stands under ``moe_combine``. Same ``stats``."""
    N, k = gates.shape
    D, (E, _, F2) = x.shape[-1], w_gate_up.shape
    F, M = F2 // 2, N * k
    blk, tile = min(LIVE_BLOCK, -(-M // 128) * 128), min(LIVE_TILE, N)
    with scope("moe_route"):
        alive = jnp.arange(N) < n_live
        if live is not None:
            alive = alive & live
        key = jnp.where(jnp.repeat(alive, k), flat, E)
        counts = jnp.zeros((E,), jnp.int32).at[key].add(1, mode="drop")
        place = jnp.argsort(key, stable=True)
        order = jnp.pad(place, (0, -M % blk))
        ends = jnp.cumsum(counts)
        starts, n_held = ends - counts, ends[-1]

    def block(b, held):
        with scope("moe_route"):
            lo = b * blk
            size = (jnp.clip(ends, lo, lo + blk)
                    - jnp.clip(starts, lo, lo + blk))
            xs = x[lax.dynamic_slice(order, (lo,), (blk,)) // k]
        with scope("moe_experts"):
            gu = _grouped(xs, w_gate_up, size)
            a = (jax.nn.silu(gu[:, :F].astype(jnp.float32)).astype(x.dtype)
                 * gu[:, F:])
            ys = _grouped(a, w_down, size)
        with scope("moe_combine"):
            return lax.dynamic_update_slice(held, ys, (lo, 0))

    def tokens(i, y):
        at = jnp.minimum(i * tile, N - tile)
        w = lax.dynamic_slice_in_dim(weight, at, tile, 1)[..., None]
        rows = held[lax.dynamic_slice_in_dim(inv, at, tile, 1)]  # (k, tile, D)
        rows = jnp.where(w != 0, rows.astype(jnp.float32) * w, 0.0)
        return lax.dynamic_update_slice_in_dim(       # slot 0 first, then 1 …
            y, functools.reduce(jnp.add, rows).astype(x.dtype), at, 0)

    with scope("moe_combine"):
        held = jnp.zeros((order.shape[0], D), x.dtype)
    held = lax.fori_loop(0, (n_held + blk - 1) // blk, block, held)
    with scope("moe_combine"):
        # (slot-major, (k, N): a tile's sum adds k whole (tile, D) slabs)
        inv = jnp.argsort(place).reshape(N, k).T   # where pair (t, j) lies
        weight = jnp.where(alive, gates.T, 0.0)
        y = lax.fori_loop(0, (n_live + tile - 1) // tile, tokens,
                          jnp.zeros((N, D), x.dtype))
        stats = jnp.concatenate(
            [counts, jnp.sum(counts > 0, dtype=jnp.int32)[None]])
        return y, stats


def share_counts(st: jnp.ndarray, n_held: int) -> jnp.ndarray:
    """``routed_ffn``'s counters of one layer as a family's
    ``step_stats_layout`` has them, ``(n_held + 2,)``: the tokens each
    held expert received, the held experts that received any, the slots
    whose expert is held elsewhere. All experts held (``routed_ffn``'s
    plain form, ``(n_held + 1,)``): none is away; a share (``(n_held +
    3,)``): without its count of zero-compute slots, which a family
    with none does not report."""
    if st.shape[0] == n_held + 1:
        return jnp.concatenate([st, jnp.zeros((1,), jnp.int32)])
    return jnp.concatenate([st[:n_held + 1], st[n_held + 2:]])


def pass_extras(per_layer, width: int, stats: bool, chosen: bool) -> tuple:
    """What a routed family's forward function returns after its cache
    and row state, from its layers' ``(stats, experts)`` (``(None,
    None)`` for a dense layer): with ``stats`` one pass's counters —
    the routed layers' counts ``(width,)`` summed, then how many routed
    layers ran; with ``chosen`` the experts each routed layer chose for
    each row of the stream ``(routed layers, N, k)`` int32 — what the
    benchmark's reference is routed by, so that a near-tie that falls
    the other way in bfloat16 does not hide what the precision does
    (``benchmark/families/ling_hybrid``)."""
    got = [(st, ex) for st, ex in per_layer if st is not None]
    out: tuple = ()
    if stats:
        total = sum((st for st, _ in got), jnp.zeros((width,), jnp.int32))
        out += (jnp.concatenate(
            [total, jnp.full((1,), len(got), jnp.int32)]),)
    if chosen:
        out += (jnp.stack([ex for _, ex in got]),)
    return out
