"""``ops/moe.routed_ffn(n_live=...)``: the routed experts of a stream
whose live rows LIE FIRST (a tight mixed step's: ``models/mellum.py``,
``models/xing.py``), multiplied a block of sorted pairs at a time while
live pairs are left and summed by token a tile of tokens at a time
while live tokens are left, each token GATHERING its ``k`` results —
against the plain form over the same rows, which makes every array
``N k`` rows long; and, WITHOUT the count, ``routed_ffn``'s two forms
held to the equations they traced before the count existed (the six
cells that share ``ops/moe.py`` and pass none keep their programs).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from llmq_tpu.ops import moe
from llmq_tpu.utils.profiling import scope

N, D, F, E, K, BLOCK, TILE = 40, 32, 16, 8, 4, 32, 16
#: the decode rows that lead, the last of them not active
LEAD = 4
#: case -> (rows that can be live, experts the router may choose)
LIVE_CASES = {
    "nothing-live": (0, E),
    "no-live-slice": (LEAD, E),                 # the decode rows alone
    "every-row-live": (N, E),
    "pairs-end-on-a-block-s-edge": (16 + 1, E),     # 16 live rows x 4
    "pairs-end-off-a-block-s-edge": (18, E),
    "an-expert-with-no-token": (23, E - 1),
}


def _layer(dtype, n_experts=E, seed=55):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((N, D)).astype(np.float32)
    scores = rng.random((N, E)).astype(np.float32)
    scores[:, n_experts:] = -1.0                # never chosen
    experts = np.argsort(-scores, axis=1)[:, :K].astype(np.int32)
    gates = np.take_along_axis(scores, experts, axis=1)
    w_gu = rng.standard_normal((E, D, 2 * F)).astype(np.float32) / D ** 0.5
    w_d = rng.standard_normal((E, F, D)).astype(np.float32) / F ** 0.5
    return (jnp.asarray(x, dtype), jnp.asarray(experts), jnp.asarray(gates),
            jnp.asarray(w_gu, dtype), jnp.asarray(w_d, dtype))


def _both_forms(case_rows, n_experts, dtype):
    """(told, plain, mask, stats of both) over ``case_rows`` rows that
    can be live, the decode row ``LEAD - 1`` not active."""
    x, experts, gates, w_gu, w_d = _layer(dtype, n_experts)
    active = jnp.arange(N) != LEAD - 1          # what ``live`` says
    mask = active & (jnp.arange(N) < case_rows)
    plain, plain_stats = jax.jit(moe.routed_ffn)(x, experts, gates, w_gu,
                                                 w_d, mask)
    told, told_stats = jax.jit(moe.routed_ffn)(
        x, experts, gates, w_gu, w_d, active, n_live=jnp.int32(case_rows))
    assert told.dtype == plain.dtype == dtype and told.shape == (N, D)
    np.testing.assert_array_equal(np.asarray(told_stats),
                                  np.asarray(plain_stats))
    return (np.asarray(told, np.float32), np.asarray(plain, np.float32),
            np.asarray(mask), np.asarray(plain_stats))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_the_told_form_is_the_plain_form_on_live_rows_and_zero_on_dead(
        monkeypatch, case, dtype):
    """Blocks of 32 sorted pairs over 160, tiles of 16 tokens over 40:
    a live row's result is the plain form's TO THE BIT, in float32 and
    in bfloat16 (the grouped product of a block's rows gives each row
    what the product of all rows gives it — ``ragged_dot`` here — the
    results are held in the type they come in, and a token's ``k`` are
    weighed in float32 and added in the slots' order as the plain form
    adds them), a dead row's — past the count, or a decode row that is
    not active — is exactly zero, and ``stats`` are equal."""
    monkeypatch.setattr(moe, "LIVE_BLOCK", BLOCK)
    monkeypatch.setattr(moe, "LIVE_TILE", TILE)
    n_live, n_experts = LIVE_CASES[case]
    told, plain, mask, stats = _both_forms(n_live, n_experts, dtype)
    pairs = int(stats[:E].sum())
    assert pairs == int(mask.sum()) * K
    if case == "pairs-end-on-a-block-s-edge":
        assert pairs == 2 * BLOCK
    if case == "an-expert-with-no-token":
        assert stats[E - 1] == 0
    assert not told[~mask].any()
    assert not pairs or np.abs(plain[mask]).max() > 0.1
    np.testing.assert_array_equal(told, plain)
    # without a mask the count alone says what is live
    x, experts, gates, w_gu, w_d = _layer(dtype, n_experts)
    alone, _ = jax.jit(moe.routed_ffn)(x, experts, gates, w_gu, w_d,
                                       n_live=jnp.int32(n_live))
    rows = np.arange(N) < n_live
    assert not np.asarray(alone, np.float32)[~rows].any()
    np.testing.assert_array_equal(np.asarray(alone, np.float32)[mask],
                                  told[mask])


#: rows that can be live, against tiles of ``TILE`` tokens: none, the
#: decode rows alone, one under / on / one over a tile's edge, a last
#: tile that is moved back to end at N (40 is no multiple of 16), all
TILE_EDGES = (0, LEAD, TILE - 1, TILE, TILE + 1, 2 * TILE + 1, N - 1, N)


@pytest.mark.parametrize("n_live", TILE_EDGES)
def test_the_sum_by_token_reads_the_live_tiles_and_no_dead_pair(
        monkeypatch, n_live):
    """The second loop's edges. The grouped product is made to return
    NaN in every row behind its last group (the kernel's "undefined"),
    and the held array is therefore NaN wherever a dead pair points
    inside a written block: a dead row — past the count, in a tile that
    is not read or in one that is, or the decode row that is not active
    inside the first tile — still comes out EXACTLY zero, and a live
    one equals the plain form to the bit."""
    monkeypatch.setattr(moe, "LIVE_BLOCK", BLOCK)
    monkeypatch.setattr(moe, "LIVE_TILE", TILE)
    grouped = moe._grouped

    def undefined_behind(xs, w, counts):
        rows = jnp.arange(xs.shape[0])[:, None] < jnp.sum(counts)
        return jnp.where(rows, grouped(xs, w, counts), jnp.nan)

    monkeypatch.setattr(moe, "_grouped", undefined_behind)
    told, plain, mask, _ = _both_forms(n_live, E, jnp.float32)
    assert mask.sum() == max(0, n_live - (n_live >= LEAD))
    assert np.isfinite(told).all() and not told[~mask].any()
    np.testing.assert_array_equal(told[mask], plain[mask])


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_the_told_form_scatter_adds_no_row_of_results():
    """Of the told form's program, loops included: the experts'
    histogram (int32, one number a pair) is its only scatter; no
    float array is scattered into, added or set — each token gathers.
    The share form, for contrast, scatter-adds (N, D) float32 a block."""
    x, experts, gates, w_gu, w_d = _layer(jnp.bfloat16)

    def scatters(**kw):
        jaxpr = jax.make_jaxpr(lambda *a: moe.routed_ffn(*a, **kw))(
            x, experts, gates, w_gu, w_d)
        return sorted((e.primitive.name, str(e.outvars[0].aval.dtype),
                       e.outvars[0].aval.shape)
                      for e in _equations(jaxpr.jaxpr)
                      if e.primitive.name.startswith("scatter"))

    assert scatters(n_live=jnp.int32(N)) == [("scatter-add", "int32", (E,))]
    assert ("scatter-add", "float32", (N, D)) in scatters(held=(0, E),
                                                          n_routed=E + 1)


# -- without the count: today's two programs -------------------------------------
# ``routed_ffn``, ``_routed_share`` and ``_held_blocks`` as they stood at
# the parent of the PR that brought the count (PR 55), body for body.

def _held_blocks_before(x, key, gates, w_gate_up, w_down, counts, k):
    N, D = x.shape
    F = w_gate_up.shape[-1] // 2
    M, blk = key.shape[0], moe.HELD_BLOCK
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
            gu = moe._grouped(xs, w_gate_up, size)
            a = (jax.nn.silu(gu[:, :F].astype(jnp.float32)).astype(x.dtype)
                 * gu[:, F:])
            ys = moe._grouped(a, w_down, size)
        with scope("moe_combine"):
            w = jnp.where(lo + jnp.arange(blk) < n_held, flat_gates[pairs],
                          0.0)
            ys = jnp.where(w[:, None] != 0,
                           ys.astype(jnp.float32) * w[:, None], 0.0)
            return y.at[tok].add(ys)

    return lax.fori_loop(0, (n_held + blk - 1) // blk, block,
                         jnp.zeros((N, D), jnp.float32))


def _routed_ffn_before(x, experts, gates, w_gate_up, w_down, live=None, *,
                       held=None, n_routed=None):
    N, k = experts.shape
    E, _, F2 = w_gate_up.shape
    F = F2 // 2
    flat = experts.reshape(-1)
    lo, hi = held if held is not None else (0, E)
    if (lo, hi) != (0, E) or n_routed not in (None, E):
        return _routed_share_before(x, flat, gates, w_gate_up, w_down, live,
                                    k, lo, n_routed)
    with scope("moe_route"):
        if live is not None:
            flat = jnp.where(jnp.repeat(live, k), flat, E)
        order = jnp.argsort(flat, stable=True)
        counts = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
        xs = x[order // k]
    with scope("moe_experts"):
        gu = moe._grouped(xs, w_gate_up, counts)
        a = (jax.nn.silu(gu[:, :F].astype(jnp.float32)).astype(x.dtype)
             * gu[:, F:])
        ys = moe._grouped(a, w_down, counts)
    with scope("moe_combine"):
        w = jnp.where(flat[order] < E, gates.reshape(-1)[order], 0.0)
        ys = jnp.where(w[:, None] != 0,
                       ys.astype(jnp.float32) * w[:, None], 0.0)
        y = jnp.zeros((N * k, x.shape[-1]), jnp.float32).at[order].set(ys)
        y = jnp.sum(y.reshape(N, k, -1), axis=1).astype(x.dtype)
        stats = jnp.concatenate(
            [counts, jnp.sum(counts > 0, dtype=jnp.int32)[None]])
        return y, stats


def _routed_share_before(x, flat, gates, w_gate_up, w_down, live, k, lo,
                         n_routed):
    E = w_gate_up.shape[0]
    with scope("moe_route"):
        alive = (jnp.repeat(live, k) if live is not None
                 else jnp.ones(flat.shape, jnp.bool_))
        here = alive & (flat >= lo) & (flat < lo + E)
        zero = (alive & (flat >= n_routed) if n_routed is not None
                else jnp.zeros(flat.shape, jnp.bool_))
        key = jnp.where(here, flat - lo, E)
        counts = jnp.zeros((E,), jnp.int32).at[key].add(1, mode="drop")
    y = _held_blocks_before(x, key, gates, w_gate_up, w_down, counts, k)
    with scope("moe_combine"):
        n_zero = jnp.sum(zero, dtype=jnp.int32)
        n_away = jnp.sum(alive, dtype=jnp.int32) - n_zero - jnp.sum(counts)
        stats = jnp.concatenate(
            [counts, jnp.stack([jnp.sum(counts > 0, dtype=jnp.int32),
                                n_zero, n_away])])
        return y.astype(x.dtype), stats


#: caller -> (rows, hidden, expert width, matrices given, k, the
#: keywords its ``routed_ffn`` call passes): the shapes of the cells
#: that share ``ops/moe.py`` and pass no count, in small
CALLERS = {
    # Kanana-2 (deepseek_v3): 128 experts, 6 a token, all held
    "kanana2-plain-form": (48, 64, 24, 16, 6, {}),
    # ZAYA1: 16 experts, ONE a token, all held
    "zaya1-plain-form-top-1": (48, 64, 32, 16, 1, {}),
    # LongCat-Flash: a share of 512 + 256 zero-compute, 12 a token
    "longcat-share-and-zero-compute": (
        48, 64, 32, 4, 12, {"held": (8, 12), "n_routed": 32}),
    # Trinity, Ling, Solar: a share and no zero-compute expert
    "a-share-alone": (48, 64, 32, 4, 8, {"held": (4, 8)}),
}


@pytest.mark.parametrize("masked", [True, False], ids=["live-mask", "no-mask"])
@pytest.mark.parametrize("caller", sorted(CALLERS))
def test_without_the_count_routed_ffn_traces_what_it_traced(caller, masked):
    """Equation for equation: the jaxpr of ``routed_ffn`` called as each
    family that passes no count calls it is the jaxpr of the frozen
    copy above (the plain form for Kanana-2 and ZAYA1, the share form
    for LongCat, Trinity, Ling and Solar), in bfloat16 as served."""
    rows, dim, width, given, k, kw = CALLERS[caller]
    args = (jnp.zeros((rows, dim), jnp.bfloat16),
            jnp.zeros((rows, k), jnp.int32),
            jnp.zeros((rows, k), jnp.float32),
            jnp.zeros((given, dim, 2 * width), jnp.bfloat16),
            jnp.zeros((given, width, dim), jnp.bfloat16))
    if masked:
        args += (jnp.ones((rows,), bool),)

    def traced(fn):
        return str(jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args))

    now = traced(moe.routed_ffn)
    assert now == traced(_routed_ffn_before)
    assert ("while" in now) == bool(kw)         # a share's blocks loop
