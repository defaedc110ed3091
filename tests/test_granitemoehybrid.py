"""The Granite-4.0-H block (``models/granitemoehybrid.py``: Mamba-2
state-space layers beside grouped-query attention layers in a published
order, a shared SwiGLU in every layer, ROW STATE beside the page pool)
held to its family's plain float32 reference
(``benchmark/families/granitemoehybrid/reference.py``: the recurrence
token by token, no cache, no code shared with ``llmq_tpu``) at a tiny
width, on seeded weights.

Logits, never tokens. The weights here are float32, so the served path
differs from the reference by float32 rounding alone (``TOL``: measured
2e-7 to 4e-7 on logits of standard deviation 0.14); each broken path
below moves them by a thousand times that or more. The tiny model is
two periods of ``m m a m``, so both kinds of layer and both orders of
neighbour occur, and its scan runs 8-token chunks, so every prompt here
crosses chunk edges.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import contract
from llmq_tpu.core.config import MixedBatchConfig, PrefixCacheConfig
from llmq_tpu.core.types import Priority
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import (deepseek_v3, family_of, get_config, llama,
                             longcat_flash, model_names)
from llmq_tpu.models import granitemoehybrid as gm
from llmq_tpu.ops import rows as rows_mod
from llmq_tpu.ops import ssm
from llmq_tpu.ops.rows import pack_grid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(REPO, "benchmark", "families", "granitemoehybrid")
reference = contract.load_family(FAMILY, "reference")

PAGE, BUCKET, DECODED = 8, 48, 24
#: float32 against float32, the worst position's largest difference:
#: measured 4e-7 here; the mildest broken path below gives 2e-3.
TOL = 2e-5


def hf_model(cfg):
    """The configuration under the public ``config.json``'s keys: what
    the reference reads."""
    return {"layer_types": list(cfg.layer_types),
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "mamba_n_heads": cfg.mamba_n_heads,
            "mamba_d_state": cfg.mamba_d_state, "mamba_n_groups": 1,
            "num_local_experts": 0,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "rms_norm_eps": cfg.norm_eps}


@pytest.fixture(scope="module")
def tiny():
    cfg = gm.granite4h_tiny(dtype=jnp.float32, max_seq_len=128)
    params = gm.init_params(jax.random.PRNGKey(39), cfg)
    seq = np.random.default_rng(39).integers(3, cfg.vocab_size, 40 + DECODED,
                                             dtype=np.int32)
    return cfg, params, seq


def block_table(cfg, n_rows=1):
    mp = cfg.max_seq_len // PAGE
    return (1 + np.arange(n_rows)[:, None] * mp
            + np.arange(mp)[None, :]).astype(np.int32)


def serve(cfg, params, seq, cuts, row=1, batch=3):
    """Prefill seq[:cuts[-1]] in the bucket-padded chunks ``cuts``
    bounds (every position's logits), then teacher-forced decode steps
    through the page pool and the row state to the end of ``seq``, the
    sequence in batch row ``row`` of ``batch``. Returns the logits at
    every position and the row state's other rows' largest value."""
    bt = np.zeros((batch, cfg.max_seq_len // PAGE), np.int32)
    bt[row] = block_table(cfg)[0]
    cache = gm.init_kv_pages(cfg, 1 + bt.shape[1], PAGE)
    state = gm.init_row_state(cfg, batch)
    out, start = [], 0
    for end in cuts:
        n = end - start
        toks = np.zeros((1, BUCKET), np.int32)
        toks[0, :n] = seq[start:end]
        pos = start + np.minimum(np.arange(BUCKET, dtype=np.int32),
                                 n - 1)[None]
        logits, cache, state = gm.forward_prefill(
            params, cfg, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray([n], jnp.int32), cache, jnp.asarray(bt[row:row + 1]),
            row_state=state, rows=jnp.asarray([row], jnp.int32))
        out.extend(np.asarray(logits)[0, :n])
        start = end
    active = np.arange(batch) == row
    for p in range(cuts[-1], len(seq)):
        tok, pos = np.zeros(batch, np.int32), np.zeros(batch, np.int32)
        tok[row], pos[row] = seq[p], p
        logits, cache, state = gm.forward_decode(
            params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
            jnp.asarray(bt), active=jnp.asarray(active), row_state=state)
        out.append(np.asarray(logits)[row])
    # (the leaves hold one row more than the batch: nobody's)
    others = max((float(jnp.abs(leaf[:, ~np.append(active, False)]).max())
                  for leaf in jax.tree.leaves(state) if leaf.size),
                 default=0.0)
    return np.stack(out), others


def worst(served, cfg, params, seq, at=None):
    at = np.arange(len(seq)) if at is None else np.asarray(at)
    ref = np.asarray(reference.reference_forward(params, seq, hf_model(cfg),
                                                 at))
    return float(np.abs(np.asarray(served) - ref).max())


# -- (a) the served path against the reference --------------------------------


@pytest.mark.parametrize("cuts", [(40,), (17, 40), (8, 19, 40)],
                         ids=["one-slice", "two-slices", "three-slices"])
def test_prefill_then_decode_through_pages_and_row_state(tiny, cuts):
    """A prompt in one, two and three ragged slices of a 48-token
    bucket (the padding neither decays nor feeds the state nor enters
    the convolution window), then 24 decode steps: the logits at EVERY
    position against the reference's full forward pass, and nothing of
    the rows it does not own is touched."""
    cfg, params, seq = tiny
    served, others = serve(cfg, params, seq, cuts)
    assert served.shape == (len(seq), cfg.vocab_size)
    assert worst(served, cfg, params, seq) < TOL
    assert others == 0.0


@pytest.fixture
def small_tile(monkeypatch):
    """A mixed step of its own jit, traced with 8-row tiles in slices
    16 wide: a tile's edge falls inside a slice and the row-wise blocks
    loop (``ops/rows.worth_a_loop``)."""
    monkeypatch.setattr(rows_mod, "ROW_TILE", 8)
    return jax.jit(gm.forward_mixed.__wrapped__, static_argnames=("cfg",))


def test_a_mixed_step_carries_the_state_from_program_to_program(tiny,
                                                                small_tile):
    """Three mixed steps over one pool and one row state. Row 2 decodes
    throughout; row 0's prompt arrives in three tight slices, one a
    step (9, 13 and 5 tokens: ragged, and its state is carried from one
    program to the next in the row-state leaves); row 1's arrives whole
    in the second step beside it. Every logit the steps return is the
    reference's."""
    cfg, params, seq = tiny
    B, S, T = 3, 3, 16
    bt = block_table(cfg, B)
    cache = gm.init_kv_pages(cfg, 1 + bt.size, PAGE)
    state = gm.init_row_state(cfg, B)
    other = np.random.default_rng(7).integers(3, cfg.vocab_size, 11,
                                              dtype=np.int32)
    dec_seq = seq[::-1].copy()
    # row 2: a prompt of 6 through the prefill program, then decode
    toks = np.zeros((1, BUCKET), np.int32)
    toks[0, :6] = dec_seq[:6]
    _, cache, state = gm.forward_prefill(
        params, cfg, jnp.asarray(toks),
        jnp.asarray(np.minimum(np.arange(BUCKET), 5)[None].astype(np.int32)),
        jnp.asarray([6], jnp.int32), cache, jnp.asarray(bt[2:3]),
        last_only=True, row_state=state, rows=jnp.asarray([2], jnp.int32))
    plan = [[(0, seq, 0, 9)],
            [(0, seq, 9, 22), (1, other, 0, 11)],
            [(0, seq, 22, 27)]]
    worst_of = 0.0
    for j, slices in enumerate(plan):
        g_t, g_p = np.zeros((S, T), np.int32), np.zeros((S, T), np.int32)
        lens, pf_rows = np.ones(S, np.int32), np.full(S, B, np.int32)
        pf_bt = np.zeros((S, bt.shape[1]), np.int32)
        for s, (row, ids, a, b) in enumerate(slices):
            g_t[s, :b - a], g_p[s, :b - a] = ids[a:b], np.arange(a, b)
            lens[s], pf_rows[s], pf_bt[s] = b - a, row, bt[row]
        pf_tok, pf_pos, starts = pack_grid(g_t, g_p, lens, used=len(slices))
        tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        tok[2], pos[2] = dec_seq[6 + j], 6 + j
        dec, pf, cache, state = small_tile(
            params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
            jnp.asarray(bt), jnp.asarray(pf_tok), jnp.asarray(pf_pos),
            jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bt),
            dec_active=jnp.asarray(np.arange(B) == 2), row_state=state,
            pf_rows=jnp.asarray(pf_rows))
        worst_of = max(worst_of, worst(np.asarray(dec)[2:3], cfg, params,
                                       dec_seq[:7 + j], [6 + j]))
        for s, (row, ids, a, b) in enumerate(slices):
            worst_of = max(worst_of, worst(np.asarray(pf)[s:s + 1], cfg,
                                           params, ids[:b], [b - 1]))
    # and the three rows decode on from what the steps left
    tok = np.asarray([seq[27], other[10], dec_seq[9]], np.int32)
    # row 1's prompt was 11 tokens: its next input is a token of ours
    ids1 = np.append(other, 5).astype(np.int32)
    tok[1] = ids1[11]
    logits, cache, state = gm.forward_decode(
        params, cfg, jnp.asarray(tok), jnp.asarray([27, 11, 9], jnp.int32),
        cache, jnp.asarray(bt), row_state=state)
    for row, ids in ((0, seq[:28]), (1, ids1), (2, dec_seq[:10])):
        worst_of = max(worst_of, worst(np.asarray(logits)[row:row + 1], cfg,
                                       params, ids, [len(ids) - 1]))
    assert worst_of < TOL


#: case -> (batch row, length, start position) of each used slice of
#: three, 12 wide over 8-row tiles: 36 rows, so the fifth tile is moved
#: back to end at the last row. Rows 0 and 1 decode, row 2 holds a
#: sequence that sits this step out.
_MIXED_PARTS = {
    # nothing is live: the tile loops run no trip, every slice unused
    "no-live-slice-row": [],
    "one-row-past-a-tile-edge": [(3, 9, 0)],
    # the moved-back last tile computes z for rows 28..31 a second time
    "all-rows-live": [(3, 12, 0), (4, 12, 0), (5, 12, 0)],
    "a-start-beside-a-continuation": [(3, 6, 10), (4, 5, 0)],
    "a-tile-edge-inside-each-slice": [(5, 7, 0), (3, 8, 10), (4, 9, 0)],
}


@pytest.mark.parametrize("case", sorted(_MIXED_PARTS))
def test_a_mixed_step_is_its_parts(tiny, small_tile, case):
    """One mixed step against its parts over the same pool and row
    state: each slice through ``forward_prefill``, then the rows
    through ``forward_decode`` — every logit the step returns, every
    page but page 0 and every row of both row-state leaves but nobody's.
    The mixed step multiplies a Mamba layer's ``xBC | dt`` over all S*T
    rows and its ``z`` inside the live tiles; the parts multiply all
    three at once."""
    cfg, params, seq = tiny
    plan = _MIXED_PARTS[case]
    B, S, T = 6, 3, 12
    bt = block_table(cfg, B)
    cache = gm.init_kv_pages(cfg, 1 + bt.size, PAGE)
    state = gm.init_row_state(cfg, B)
    rng = np.random.default_rng(sorted(_MIXED_PARTS).index(case))

    def draw(n):
        return rng.integers(3, cfg.vocab_size, n, dtype=np.int32)

    def prefill(cache, state, row, toks, start, width):
        n = len(toks)
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = toks
        pos = start + np.minimum(np.arange(width, dtype=np.int32), n - 1)
        logits, cache, state = gm.forward_prefill(
            params, cfg, jnp.asarray(padded), jnp.asarray(pos[None]),
            jnp.asarray([n], jnp.int32), cache, jnp.asarray(bt[row:row + 1]),
            last_only=True, row_state=state,
            rows=jnp.asarray([row], jnp.int32))
        return np.asarray(logits)[0], cache, state

    contexts = (5, 12, 3)               # what rows 0..2 hold
    for row, n in enumerate(contexts):
        _, cache, state = prefill(cache, state, row, draw(n), 0, BUCKET)
    for row, _, start in plan:
        if start:                       # what a continuing slice follows
            _, cache, state = prefill(cache, state, row, draw(start), 0,
                                      BUCKET)
    slices = [draw(n) for _, n, _ in plan]
    tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
    tok[:3], pos[:3] = draw(3), contexts
    active = np.arange(B) < 2

    ref_cache, ref_state = jax.tree.map(jnp.copy, (cache, state))
    ref_pf = []
    for (row, _, start), toks in zip(plan, slices):
        logits, ref_cache, ref_state = prefill(ref_cache, ref_state, row,
                                               toks, start, T)
        ref_pf.append(logits)
    ref_dec, ref_cache, ref_state = gm.forward_decode(
        params, cfg, jnp.asarray(tok), jnp.asarray(pos), ref_cache,
        jnp.asarray(bt), active=jnp.asarray(active), row_state=ref_state)

    g_t, g_p = np.zeros((S, T), np.int32), np.zeros((S, T), np.int32)
    lens, pf_rows = np.ones(S, np.int32), np.full(S, B, np.int32)
    pf_bt = np.zeros((S, bt.shape[1]), np.int32)
    for s, ((row, n, start), toks) in enumerate(zip(plan, slices)):
        g_t[s, :n], g_p[s, :n] = toks, start + np.arange(n)
        lens[s], pf_rows[s], pf_bt[s] = n, row, bt[row]
    pf_tok, pf_pos, starts = pack_grid(g_t, g_p, lens, used=len(plan))
    assert starts[-1] == sum(n for _, n, _ in plan)
    dec, pf, cache, state = small_tile(
        params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
        jnp.asarray(bt), jnp.asarray(pf_tok), jnp.asarray(pf_pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bt),
        dec_active=jnp.asarray(active), row_state=state,
        pf_rows=jnp.asarray(pf_rows))

    np.testing.assert_allclose(np.asarray(dec)[active],
                               np.asarray(ref_dec)[active], atol=TOL)
    if plan:
        np.testing.assert_allclose(np.asarray(pf)[:len(plan)],
                                   np.stack(ref_pf), atol=TOL)
    for name in cache:
        np.testing.assert_allclose(np.asarray(cache[name][:, 1:]),
                                   np.asarray(ref_cache[name][:, 1:]),
                                   atol=TOL, err_msg=name)
    for name in state:
        np.testing.assert_allclose(np.asarray(state[name][:, :B]),
                                   np.asarray(ref_state[name][:, :B]),
                                   atol=TOL, err_msg=name)
        # the row that sits out keeps what it held, to the bit
        np.testing.assert_array_equal(np.asarray(state[name][:, 2]),
                                      np.asarray(ref_state[name][:, 2]))


# -- (b) the chunked scan against the recurrence -------------------------------


def _recurrence(state, x, dt, a, bm, cm, d, length):
    """Token by token in NumPy float64, the state ``(N, H, P)``."""
    H, P = x.shape[1:]
    h = np.asarray(state, np.float64).reshape(-1, H, P).copy()
    ys = np.zeros(x.shape, np.float64)
    for t in range(length):
        h = (h * np.exp(dt[t] * a)[None, :, None]
             + bm[t][:, None, None] * (dt[t][:, None] * x[t])[None])
        ys[t] = np.einsum("nhp,n->hp", h, cm[t]) + d[:, None] * x[t]
    return ys, h.reshape(h.shape[0], -1)


@pytest.mark.parametrize("lengths", [(21, 5), (8, 16), (1, 23), (0, 13)],
                         ids=["ragged", "on-chunk-edges", "one-token",
                              "an-empty-slice"])
def test_the_chunked_scan_is_the_recurrence(lengths):
    """``ops/ssm.ssm_scan`` (8-token chunks: masked products inside a
    chunk, the state carried between) over slices whose lengths are not
    multiples of the chunk, from a state that is not zero: every valid
    token's output and the state behind each slice's LAST VALID token
    are the token-by-token recurrence's. A slice's padding neither
    decays the state nor feeds it."""
    S, T, H, P, N, chunk = 2, 24, 4, 8, 16, 8
    rng = np.random.default_rng(sum(lengths))
    x = rng.standard_normal((S, T, H, P)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (S, T, H))
                ).astype(np.float32)
    a = -rng.uniform(1, 16, H).astype(np.float32)
    bm, cm = (rng.standard_normal((S, T, N)).astype(np.float32)
              for _ in range(2))
    d = rng.standard_normal(H).astype(np.float32)
    state = rng.standard_normal((S, N, H * P)).astype(np.float32)
    y, last = ssm.ssm_scan(jnp.asarray(state), jnp.asarray(x),
                           jnp.asarray(dt), jnp.asarray(a), jnp.asarray(bm),
                           jnp.asarray(cm), jnp.asarray(d),
                           jnp.asarray(lengths, jnp.int32), chunk)
    for s, n in enumerate(lengths):
        want_y, want_h = _recurrence(state[s], x[s], dt[s], a, bm[s], cm[s],
                                     d, n)
        np.testing.assert_allclose(np.asarray(y)[s, :n], want_y[:n],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(last)[s], want_h, rtol=2e-4,
                                   atol=2e-4)


def test_the_convolution_s_window_ends_at_the_last_valid_input():
    """``conv_slices`` over a slice of 5 valid inputs in 12, then
    ``conv_step`` token by token from the window it left, is the
    convolution over the inputs laid end to end."""
    rng = np.random.default_rng(3)
    C, K, T = 6, 4, 12
    xs = rng.standard_normal((1, 9, C)).astype(np.float32)
    w = rng.standard_normal((C, K)).astype(np.float32)
    b = rng.standard_normal(C).astype(np.float32)
    padded = np.concatenate([np.zeros((K - 1, C), np.float32), xs[0]])
    want = sum(padded[j:j + 9] * w[:, j] for j in range(K)) + b
    want = want / (1 + np.exp(-want))
    grid = np.zeros((1, T, C), np.float32)
    grid[0, :5] = xs[0, :5]
    grid[0, 5:] = 99.0                       # padding that must not enter
    y, win = ssm.conv_slices(jnp.zeros((1, K - 1, C)), jnp.asarray(grid),
                             jnp.asarray([5]), jnp.asarray(w), jnp.asarray(b))
    got = [np.asarray(y)[0, :5]]
    for t in range(5, 9):
        y1, win = ssm.conv_step(win, jnp.asarray(xs[:, t]), jnp.asarray(w),
                                jnp.asarray(b))
        got.append(np.asarray(y1))
    np.testing.assert_allclose(np.concatenate(got), want, rtol=1e-5,
                               atol=1e-5)


# -- (c) the update kernel ----------------------------------------------------


#: which rows of the batch decode -> (``active``, the leaf's rows beyond
#: the batch): eight rows and the row that is nobody's, and PR 39's
#: case (three of five, a leaf of the batch's size).
_LIVE = {
    "all": ([True] * 8, 1),
    "none": ([False] * 8, 1),
    "the-first": ([True] + [False] * 7, 1),
    "the-last": ([False] * 7 + [True], 1),
    "alternating": ([False, True] * 4, 1),
    "five-of-eight": ([True, True, False, True, False, False, True, True],
                      1),
    "three-of-five-no-spare-row": ([True, False, True, True, False], 0),
}
#: a step's block of the (16, 2,048) row -> the VMEM its three slots
#: may take for ``_lanes`` to pick it
_BLOCKS = {"whole-row": (2048, None), "1024-lanes": (1024, 3 * 16 * 1024 * 4),
           "128-lanes": (128, 3 * 16 * 128 * 4)}


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


@pytest.mark.parametrize("block", sorted(_BLOCKS))
@pytest.mark.parametrize("live", sorted(_LIVE))
def test_the_update_kernel_is_the_plain_update_in_place(monkeypatch, live,
                                                        block):
    """``ops/pallas/ssm_update.py`` in interpret mode against
    ``ops/ssm.ssm_update`` on layer 1 of a stacked leaf of three, over
    which rows decode and every block the shape rule can pick: the live
    rows' state and output are the plain update's (to an ulp here: XLA's
    CPU backend contracts the update's multiply-add and the interpreter
    does not; on the chip the two are bit-equal,
    ``scripts/bench_kernel.py``); every row that does not decode, the
    row that is nobody's and the other layers come back bit for bit;
    ``y`` of a row that does not decode is the skip term alone (the
    kernel's part exactly zero). The row that is nobody's holds NaNs,
    so a live row that read it would show."""
    from llmq_tpu.ops.pallas import ssm_update as su

    monkeypatch.setenv("LLMQ_PALLAS", "interpret")
    lanes, budget = _BLOCKS[block]
    if budget is not None:
        monkeypatch.setattr(su, "STATE_VMEM_BYTES", budget)
    active, spare = _LIVE[live]
    active = np.asarray(active)
    L, B, N, H, P = 3, len(active), 16, 16, 128
    assert su._lanes(N, H * P) == lanes
    rng = np.random.default_rng(5)
    pool = rng.standard_normal((L, B + spare, N, H * P)).astype(np.float32)
    pool[:, B:] = np.nan
    x = rng.standard_normal((B, H, P)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (B, H))
                ).astype(np.float32)
    a = -rng.uniform(1, 16, H).astype(np.float32)
    bm, cm = (rng.standard_normal((B, N)).astype(np.float32)
              for _ in range(2))
    d = rng.standard_normal(H).astype(np.float32)
    assert ssm.update_route(N, H * P, jnp.float32) == (True, True)
    rows, n_live = ssm.decode_walk(jnp.asarray(active))
    alive = np.flatnonzero(active)
    assert rows.shape == (B,) and int(n_live) == len(alive)
    assert list(np.asarray(rows)[:len(alive)]) == list(alive)
    args = [jnp.asarray(v) for v in (x, dt, a, bm, cm, d, active)]
    y, got = ssm.ssm_update_layer(jnp.asarray(pool), 1, *args)
    want_y, want = ssm.ssm_update(jnp.asarray(pool[1, :B]), *args)
    y, got = np.asarray(y), np.asarray(got)
    np.testing.assert_allclose(got[1, :B][active], np.asarray(want)[active],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[active], np.asarray(want_y)[active],
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(_bits(y[~active]),
                          _bits((d[None, :, None] * x)[~active]))
    assert np.array_equal(_bits(got[1, :B][~active]),
                          _bits(pool[1, :B][~active]))
    assert np.array_equal(_bits(got[1, B:]), _bits(pool[1, B:]))
    assert np.array_equal(_bits(got[[0, 2]]), _bits(pool[[0, 2]]))


def test_the_update_route_is_for_a_float32_state_of_whole_tiles():
    """A state that is not float32, or not of whole (8, 128) tiles, is
    XLA's; and the block of a grid step follows the row's shape alone:
    whole rows at the served (128, 4,096), the widest block that fits
    for a row too large for that."""
    from llmq_tpu.ops.pallas import ssm_update as su

    assert ssm.update_route(16, 256, jnp.bfloat16) == (False, False)
    assert ssm.update_route(20, 256, jnp.float32) == (False, False)
    assert su._lanes(128, 4096) == 4096
    assert su._lanes(128, 8192) == 4096
    assert su._lanes(1024, 31 * 128) == 128
    assert (su.SLOTS * 128 * 4096 * 4 <= su.STATE_VMEM_BYTES
            < su.VMEM_LIMIT_BYTES)


def test_the_kernel_serves_the_decode_step(tiny, monkeypatch):
    """The decode program with the kernel on its path (interpret mode;
    a width of whole lane tiles) against the same program on XLA's
    fusion: the logits and the row state of a step after a prefill."""
    cfg = gm.granite4h_tiny(dtype=jnp.float32, max_seq_len=128, pallas=True)
    params, seq = tiny[1], tiny[2]

    def step(mode):
        monkeypatch.setenv("LLMQ_PALLAS", mode)
        jax.clear_caches()
        routes = gm.routes(cfg, gm.init_kv_pages(cfg, 2, PAGE), batch=3,
                           page_size=PAGE, max_pages=16, decode=True)
        served, _ = serve(cfg, params, seq[:14], (12,))
        return routes["ssm_update"], served

    how_k, with_kernel = step("interpret")
    how_x, plain = step("0")
    jax.clear_caches()
    assert how_k == "pallas-interpret:ssm_update_pallas" and how_x == "xla"
    np.testing.assert_allclose(with_kernel, plain, rtol=0, atol=1e-6)


# -- (d) what the tolerance on the chip cannot see ----------------------------


@pytest.mark.parametrize("name", ["embedding_multiplier",
                                  "residual_multiplier",
                                  "attention_multiplier", "logits_scaling"])
def test_a_multiplier_left_out_fails_the_comparison(tiny, name):
    """Each of the four multipliers set to 1 in the PROGRAM, the
    reference reading the published value: the logits part by a hundred
    times ``TOL`` or more."""
    cfg, params, seq = tiny
    broken = dataclasses.replace(cfg, **{name: 1.0})
    served, _ = serve(broken, params, seq[:20], (12,))
    assert worst(served, cfg, params, seq[:20]) > 100 * TOL


def test_the_attention_layers_rotate_nothing():
    """``position_embedding_type`` "nope". A rotary embedding survives
    a shift of ALL positions (it is relative), so that says nothing;
    what it cannot survive is the ORDER of the context changing. With
    one attention layer the last position's logits are the same for any
    order of the tokens before it — and the same model with two Mamba
    layers before the attention tells the orders apart, so the test can
    fail."""
    rng = np.random.default_rng(11)
    for types, same in (((gm.ATTENTION,), True),
                        ((gm.MAMBA, gm.MAMBA, gm.ATTENTION), False)):
        cfg = gm.granite4h_tiny(dtype=jnp.float32, max_seq_len=128,
                                layer_types=types)
        params = gm.init_params(jax.random.PRNGKey(2), cfg)
        seq = rng.integers(3, cfg.vocab_size, 20, dtype=np.int32)
        turned = np.concatenate([seq[:-1][::-1], seq[-1:]])
        a, _ = serve(cfg, params, seq, (20,))
        b, _ = serve(cfg, params, turned, (20,))
        gap = float(np.abs(a[-1] - b[-1]).max())
        assert (gap < TOL) if same else (gap > 100 * TOL)
        # and the program is the reference either way
        assert worst(a, cfg, params, seq) < TOL


def test_a_state_held_one_precision_down_is_told_apart(tiny):
    """The benchmark's control (``state_dtype`` bfloat16: the state
    rounded between tokens, the recurrence still float32) against the
    float32 state: 24 decode steps move the logits by twenty times
    what the float32 state gives (measured 4.7e-4 against 4e-7: small
    beside a logit, which is why the chip's check judges many steps —
    ``benchmark/families/granitemoehybrid/README.md``)."""
    cfg, params, seq = tiny
    served, _ = serve(dataclasses.replace(cfg, state_dtype=jnp.bfloat16),
                      params, seq, (40,))
    assert worst(served, cfg, params, seq) > 10 * TOL


# -- (e) through the executor and the engine ----------------------------------


def make_engine(tiny, batch=2, **kw):
    cfg, params, _ = tiny
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=batch, page_size=PAGE,
                     num_pages=96, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8)
    return InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=24,
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                     max_slices=2), **kw), ex


def generate(eng, rid, prompt, n=12, **kw):
    h = eng.submit(GenRequest(id=rid, prompt=prompt, max_new_tokens=n,
                              temperature=0.0, **kw))
    eng.run_until_idle()
    assert h.done
    return h.result


def test_the_executor_holds_row_state_beside_the_pages(tiny):
    cfg = tiny[0]
    eng, ex = make_engine(tiny, batch=3)
    assert set(ex.cache) == {"k", "v"}
    assert ex.cache["k"].shape[0] == cfg.n_attention == 2
    # three rows and the one that is nobody's
    assert ex.row_state["ssm"].shape == (cfg.n_mamba, 4, cfg.mamba_d_state,
                                         cfg.mamba_inner)
    assert ex.row_state["conv"].shape == (cfg.n_mamba, 4, 3 * cfg.conv_width)
    per_row = gm.row_state_bytes_per_row(cfg)
    assert ex.row_state_bytes_per_row == per_row == sum(
        x.nbytes for x in jax.tree.leaves(ex.row_state)) // 4
    # everything that walks the page pytree keeps seeing pages only
    L, P, ps, W = ex.cache["k"].shape
    assert ex.kv_page_spec() == [((L, ps, W), np.dtype(np.float32))] * 2
    chip = ex.hbm_info()[0]
    assert chip["row_state_bytes"] == 4 * per_row
    assert chip["kv_pool_bytes"] == 2 * ex.cache["k"].nbytes
    routes = ex._routes(decode=True, prefill_rows=1)
    assert routes["ssm_update"] == "xla" and routes["ssm_scan"] == "xla"
    stats = eng.get_stats()["row_state"]
    assert stats == {"rows": 3, "bytes_per_row": per_row,
                     "bytes": 3 * per_row, "rebuilds": 0,
                     "declined": {"prefix": 0, "conversation": 0,
                                  "tiering": 0, "disagg": 0}}
    with pytest.raises(ValueError, match="names its sequence's batch row"):
        ex.prefill_async([1, 2, 3], 0, np.zeros(16, np.int32), 0.0)


def test_a_row_reused_by_a_second_sequence_starts_from_zero(tiny):
    """One batch row: the second sequence decodes in the row the first
    left its state in, and gives the tokens it gives alone — its first
    slice zeroes the row inside the program, no host call does."""
    alone, _ = make_engine(tiny, batch=1)
    want = generate(alone, "b", "the second prompt, somewhat longer")
    eng, _ = make_engine(tiny, batch=1)
    generate(eng, "a", "a first prompt that leaves a state behind")
    got = generate(eng, "b", "the second prompt, somewhat longer")
    assert got.tokens == want.tokens and len(got.tokens) == 12


def test_a_preempted_sequence_is_rebuilt_not_resumed(tiny):
    """One row, a low-tier generation and then a realtime arrival: the
    victim's row goes to the arrival and its state with it, so it is
    released (never kept for a cheap resume), rebuilt by prefilling what
    it had written, and gives the tokens it gives unpreempted."""
    prompt = "background work that is interrupted"
    alone, _ = make_engine(tiny, batch=1)
    want = generate(alone, "low", prompt, n=20)
    eng, _ = make_engine(tiny, batch=1)
    low = eng.submit(GenRequest(id="low", prompt=prompt, max_new_tokens=20,
                                priority=Priority.LOW))
    for _ in range(50):
        eng.step()
        if eng._chunk_inflight is not None:
            break
    rt = eng.submit(GenRequest(id="rt", prompt="urgent", max_new_tokens=4,
                               priority=Priority.REALTIME))
    eng.run_until_idle()
    stats = eng.get_stats()
    assert rt.finished_at < low.finished_at
    assert low.result.tokens == want.tokens
    assert stats["row_state"]["rebuilds"] == 1
    assert stats["preemptions"] == {"slot": 0, "release": 1}


def test_a_prefix_match_is_declined_and_counted(tiny):
    """Two requests that share 40 tokens, the prefix cache on: pages
    would give the second K/V for the attention layers and nothing for
    the Mamba layers, so the match is declined (cached length 0, the
    whole prompt prefilled), counted, and the tokens are those of the
    cache off."""
    shared = "the same forty-odd characters of system prompt: "
    plain, _ = make_engine(tiny)
    want = generate(plain, "b", shared + "second question")
    eng, _ = make_engine(tiny, prefix_cache=PrefixCacheConfig(enabled=True))
    first = generate(eng, "a", shared + "first question")
    second = generate(eng, "b", shared + "second question")
    stats = eng.get_stats()["row_state"]
    assert first.cached_tokens == 0 and second.cached_tokens == 0
    assert second.tokens == want.tokens
    assert stats["declined"]["prefix"] == 1


def test_a_pinned_conversation_is_declined_and_its_stream_prefilled(tiny):
    """Turn 2 of a conversation: the pin holds pages and no row state,
    so the pages go back to the pool, the remembered stream is prefilled
    and the turn gives the tokens the whole history gives as one
    prompt's continuation would — here: the same engine with the
    history handed over as text."""
    eng, _ = make_engine(tiny)
    one = generate(eng, "t1", "first turn of a conversation",
                   conversation_id="c")
    two = generate(eng, "t2", " and a second", conversation_id="c")
    stats = eng.get_stats()["row_state"]
    assert stats["declined"]["conversation"] == 1
    assert two.cached_tokens == 0 and len(two.tokens) == 12
    assert len(one.tokens) == 12


# -- (f) the registry ---------------------------------------------------------


def test_registry_serves_the_family_at_the_published_sizes():
    assert model_names()["granite-4.0-h-micro"] == "granitemoehybrid"
    cfg = get_config("granite-4.0-h-micro")
    assert family_of(cfg) is gm
    assert cfg.layer_types.count(gm.ATTENTION) == 4
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == gm.ATTENTION] == [5, 15, 25, 35]
    assert cfg.period == 10 and cfg.n_mamba == 36
    assert gm.param_count_analytic(cfg) == 3_191_396_096
    assert gm.kv_bytes_per_token(cfg) == 8192
    assert gm.row_state_bytes_per_row(cfg) == 76_437_504
    assert cfg.mamba_inner == 4096 and cfg.conv_width == 4352
    pages = jax.eval_shape(lambda: gm.init_kv_pages(cfg, 832, 128))
    assert pages["k"].shape == (4, 832, 128, 512)
    state = jax.eval_shape(lambda: gm.init_row_state(cfg, 64))
    assert state["ssm"].shape == (36, 65, 128, 4096)
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (36, 65, 3 * 4352)
    assert gm.granite4h_tiny().period == 4
    for fam in (llama, deepseek_v3, longcat_flash):
        assert fam.init_row_state(None, 8) is None
        assert fam.row_state_bytes_per_row(None) == 0


@pytest.mark.parametrize("what,match", [
    ("int8-weights", "model.quantization='int8'"),
    ("int8-cache", "model.kv_quantization='int8'"),
    ("mesh", "executor.mesh"),
])
def test_registry_refuses_with_an_error_that_names_the_setting(what, match):
    cfg = gm.granite4h_tiny(max_seq_len=64)
    with pytest.raises(ValueError, match=match):
        if what == "int8-weights":
            gm.init_params_quantized(jax.random.PRNGKey(0), cfg)
        else:
            params = jax.eval_shape(
                lambda: gm.init_params(jax.random.PRNGKey(0), cfg))
            kw = {"int8-cache": dict(cache_dtype=jnp.int8),
                  "mesh": dict(mesh=jax.sharding.Mesh(
                      np.array(jax.devices()[:2]), ("tp",)))}[what]
            JaxExecutor(cfg, params, batch_size=2, page_size=8,
                        num_pages=16, **kw)


# -- (g) loading published weights --------------------------------------------


def _synthetic_checkpoint(tmp_path, cfg):
    """A safetensors checkpoint of ``cfg``'s sizes under the public
    tensor names (from memory of ``modeling_granitemoehybrid.py``: no
    network here), written to ``tmp_path``; returns its tensors."""
    st = pytest.importorskip("safetensors.numpy")
    rng = np.random.default_rng(0)

    def w(*shape, scale=0.05):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    D, F, I, C = cfg.dim, cfg.ffn_dim, cfg.mamba_inner, cfg.conv_width
    Hm = cfg.mamba_n_heads
    t = {"model.embed_tokens.weight": w(cfg.vocab_size, D),
         "model.norm.weight": 1 + w(D)}
    for l, kind in enumerate(cfg.layer_types):
        pre = f"model.layers.{l}."
        t[pre + "input_layernorm.weight"] = 1 + w(D)
        t[pre + "post_attention_layernorm.weight"] = 1 + w(D)
        t[pre + "shared_mlp.input_linear.weight"] = w(2 * F, D)
        t[pre + "shared_mlp.output_linear.weight"] = w(D, F)
        if kind == gm.MAMBA:
            t[pre + "mamba.in_proj.weight"] = w(I + C + Hm, D)
            t[pre + "mamba.conv1d.weight"] = w(C, 1, cfg.mamba_d_conv,
                                               scale=0.3)
            t[pre + "mamba.conv1d.bias"] = w(C)
            t[pre + "mamba.dt_bias"] = w(Hm, scale=1.0)
            t[pre + "mamba.A_log"] = np.log(
                rng.uniform(1, 16, Hm)).astype(np.float32)
            t[pre + "mamba.D"] = 1 + w(Hm)
            t[pre + "mamba.norm.weight"] = 1 + w(I)
            t[pre + "mamba.out_proj.weight"] = w(D, I)
        else:
            t[pre + "self_attn.q_proj.weight"] = w(cfg.n_heads
                                                   * cfg.head_dim, D)
            for n in ("k", "v"):
                t[pre + f"self_attn.{n}_proj.weight"] = w(
                    cfg.n_kv_heads * cfg.head_dim, D)
            t[pre + "self_attn.o_proj.weight"] = w(D, cfg.n_heads
                                                   * cfg.head_dim)
    st.save_file(t, str(tmp_path / "model.safetensors"))
    return t


def test_a_published_checkpoint_is_loaded(tmp_path):
    """``import_hf_granitemoehybrid`` on a synthetic safetensors
    checkpoint: each leaf lands where the program reads it — the
    attention layers' and the Mamba layers' stacks by their own
    indices, ``input_linear`` split into gate and up — and the imported
    model is the reference."""
    from llmq_tpu.models.checkpoint import import_hf
    cfg = gm.granite4h_tiny(dtype=jnp.float32, max_seq_len=128)
    t = _synthetic_checkpoint(tmp_path, cfg)
    F = cfg.ffn_dim
    params = import_hf(str(tmp_path), cfg)
    want = jax.eval_shape(lambda: gm.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(
        lambda x: x.shape, want)
    lay = params["layers"]
    # layer 6 is the second attention layer, layer 4 the fourth Mamba's
    assert np.array_equal(np.asarray(lay["wk"][1]),
                          t["model.layers.6.self_attn.k_proj.weight"].T)
    assert np.array_equal(np.asarray(lay["in_proj"][3]),
                          t["model.layers.4.mamba.in_proj.weight"].T)
    assert np.array_equal(np.asarray(lay["conv_w"][3]),
                          t["model.layers.4.mamba.conv1d.weight"][:, 0])
    up = t["model.layers.5.shared_mlp.input_linear.weight"]
    assert np.array_equal(np.asarray(lay["w_gate"][5]), up[:F].T)
    assert np.array_equal(np.asarray(lay["w_up"][5]), up[F:].T)
    seq = np.random.default_rng(2).integers(3, cfg.vocab_size, 30,
                                            dtype=np.int32)
    served, _ = serve(cfg, params, seq, (11, 20))
    assert worst(served, cfg, params, seq) < TOL


def test_a_laid_tree_means_what_it_meant(tmp_path):
    """An executor over the imported checkpoint lays ``in_proj``
    row-major and the attention layers' ``wq``, ``wk``, ``wv``
    transposed (``gm.DEVICE_LAYOUT``) IN the tree it was handed: shapes
    and values stay, the logits are those of the tree as it was
    imported, and a second executor over the laid tree lays nothing
    again."""
    from llmq_tpu.engine.executor import _lies
    from llmq_tpu.models.checkpoint import import_hf
    cfg = gm.granite4h_tiny(dtype=jnp.float32, max_seq_len=128)
    _synthetic_checkpoint(tmp_path, cfg)
    plain = import_hf(str(tmp_path), cfg)
    params = jax.tree.map(lambda x: x, plain)

    def executor(tree):
        return JaxExecutor(cfg, tree, batch_size=2, page_size=PAGE,
                           num_pages=96, prefill_buckets=[16, 64], eos_id=2,
                           chunk_size=4, mixed_prefill_slices=2,
                           mixed_slice_tokens=8)

    ex = executor(params)
    names = sorted(gm.DEVICE_LAYOUT)
    assert names == ["in_proj", "wk", "wq", "wv"]
    assert ex.params is params
    assert ex.relaid == {"leaves": 4, "bytes": sum(
        plain["layers"][n].size * 4 for n in names)}
    for name, leaf in params["layers"].items():
        assert _lies(leaf, "transposed") == (name in ("wq", "wk", "wv")), name
        assert leaf.shape == plain["layers"][name].shape
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(plain["layers"][name]))
    assert _lies(params["layers"]["in_proj"], "row_major")
    # every program's description names the four leaves' layouts
    for name, _, operands, _ in ex.programs():
        got = operands[0]["layers"]
        assert sorted(n for n in got
                      if got[n].format.layout is not None) == names, name
    seq = np.random.default_rng(2).integers(3, cfg.vocab_size, 30,
                                            dtype=np.int32)
    served, _ = serve(cfg, ex.params, seq, (11, 20))
    as_imported, _ = serve(cfg, plain, seq, (11, 20))
    np.testing.assert_allclose(served, as_imported, atol=TOL)
    assert worst(served, cfg, params, seq) < TOL
    laid = dict(params["layers"])
    second = executor(params)
    assert second.relaid == ex.relaid
    assert all(second.params["layers"][n] is laid[n] for n in laid)


# -- (h) the older families' programs take no row state -----------------------


@pytest.mark.parametrize("name", ["llama3-tiny", "deepseek-v3-tiny",
                                  "longcat-flash-tiny"])
def test_a_family_without_row_state_serves_the_parent_s_programs(name):
    """The chunk programs of a family whose pages are its whole cache
    take the page leaves at operand 1 and nothing for a row (no
    row-state operand, no batch-row operand) and return the page leaves
    alone in the cache's place."""
    cfg = get_config(name, max_seq_len=64)
    fam = family_of(cfg)
    params = fam.init_params(jax.random.PRNGKey(0), cfg)
    ex = JaxExecutor(cfg, params, batch_size=2, page_size=8, num_pages=24,
                     prefill_buckets=[16], eos_id=2, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8)
    assert ex.row_state is None and ex.row_state_bytes_per_row == 0
    assert ex._pool is ex.cache and ex._rows_arg([0, None]) == ()
    assert "row_state_bytes" not in ex.hbm_info()[0]
    B, MP, S, T = 2, 8, 2, 8
    i32, f32 = jnp.int32, jnp.float32
    key = jax.random.PRNGKey(0)
    z = jnp.zeros
    calls = {
        "prefill": (ex._prefill_step, (
            z((1, 16), i32), z((1, 16), i32), jnp.ones((1,), i32),
            z((1, MP), i32), z((1,), f32), key), 1),
        "decode_chunk": (ex._decode_chunk, (
            z((B,), i32), z((B,), i32), z((B, MP), i32), z((B,), f32),
            jnp.ones((B,), i32), z((B,), bool), key), 4),
        "mixed_chunk": (ex._mixed_chunk, (
            z((B,), i32), z((B,), i32), z((B, MP), i32), z((B,), f32),
            jnp.ones((B,), i32), z((B,), bool), z((S * T,), i32),
            z((S * T,), i32), jnp.ones((S,), i32),
            jnp.arange(S + 1, dtype=i32), z((S, MP), i32), z((S,), f32),
            key), 5),
    }
    n_params = len(jax.tree.leaves(ex.params))
    pages = jax.tree.structure(ex.cache)
    for prog, (fn, args, cache_at) in calls.items():
        lowered = fn.lower(ex.params, ex.cache, *args)
        flat_in = jax.tree.leaves(lowered.in_avals)
        assert len(flat_in) == n_params + pages.num_leaves + len(args), prog
        out = lowered.out_info
        assert jax.tree.structure(out[cache_at]) == pages, prog
        assert [x.shape for x in jax.tree.leaves(out[cache_at])] == [
            x.shape for x in jax.tree.leaves(ex.cache)], prog
    assert ex.model_cfg.FAMILY != "granitemoehybrid"
