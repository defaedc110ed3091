"""The Solar-Open2 block (``models/solar_open2.py``: Kimi Delta Attention
— low-rank gates, an unbounded decay, beta in (0, 2) — as ROW STATE in
three layers of four beside gated softmax GQA without rotary positions
over a K/V page pool, a sigmoid router beside a shared expert in every
layer, serving ONE CHIP'S SHARE of the experts) held to its family's
plain float32 reference (``benchmark/families/solar_open2/reference.py``,
which shares no code with ``llmq_tpu`` and computes the recurrence a
token at a time) at a tiny width, on seeded weights.

Logits, never tokens. The weights here are float32, so the served path
differs from the reference by float32 rounding alone and the comparison
is tight (``TOL``): each of the broken paths below — the items of the
configuration file's ``assumed`` and the mechanisms the tolerance on the
chip cannot see — moves the logits by ten times that or more. The tiny
model is one period, ``G K K K``; it holds experts 8-15 of 16, so
both kinds of slot occur.
"""

import dataclasses
import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import contract
from llmq_tpu.core.config import MixedBatchConfig, PrefixCacheConfig
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import family_of, get_config, model_names
from llmq_tpu.models import solar_open2 as so
from llmq_tpu.ops.rows import pack_grid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(REPO, "benchmark", "families", "solar_open2")
reference = contract.load_family(FAMILY, "reference")

PAGE, BUCKET, ROWS = 8, 32, 3
#: float32 against float32: measured 2e-7 to 2e-6 here; the mildest
#: broken path gives over 1e-4.
TOL = {"clean_quantile": 0.25, "rms_clean": 1e-5, "rms_worst": 1e-5,
       "margin_eps": 1e-7, "growth": float("inf"), "margin_decisive": 0.0,
       "state_rel": [0.0], "kv_rel": 0.0}


def hf_model(cfg):
    """The configuration under the public ``config.json``'s keys, with
    the share as the benchmark's file states it: what the reference
    reads."""
    lo, hi = cfg.held
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "gqa_layers": list(cfg.gqa_layers), "use_rope": False,
            "use_gqa_gate": True, "kda_use_full_proj": False,
            "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
            "linear_attn_config": {
                "short_conv_kernel_size": cfg.kda_conv,
                "head_dim": cfg.kda_head_dim, "num_heads": cfg.kda_heads,
                "num_kv_heads": None},
            "n_routed_experts": hi - lo,
            "router_experts": cfg.n_routed_experts,
            "expert_share": {"chips": cfg.n_routed_experts // (hi - lo),
                             "index": lo // (hi - lo)},
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk_prob": cfg.norm_topk_prob,
            "rms_norm_eps": cfg.norm_eps, "vocab_size": cfg.vocab_size}


@pytest.fixture(scope="module")
def tiny():
    # (ONE period, G K K K: half the tiny model's layers to trace)
    cfg = so.solar_open2_tiny(dtype=jnp.float32, max_seq_len=128,
                              held_experts=(8, 16), n_layers=4,
                              gqa_layers=(0,))
    params = so.init_params(jax.random.PRNGKey(52), cfg)
    # A selection bias that is not zero: one that the gates must not see.
    params["moe"]["router_bias"] = 0.01 * jax.random.normal(
        jax.random.PRNGKey(5), params["moe"]["router_bias"].shape)
    seq = np.random.default_rng(52).integers(3, cfg.vocab_size, 100,
                                             dtype=np.int32)
    return cfg, params, seq


def block_table(cfg, n_rows=ROWS):
    mp = cfg.max_seq_len // PAGE
    return (1 + np.arange(n_rows)[:, None] * mp
            + np.arange(mp)[None, :]).astype(np.int32)


def new_cache(cfg, n_rows=ROWS):
    return (so.init_kv_pages(cfg, 1 + n_rows * (cfg.max_seq_len // PAGE),
                             PAGE), so.init_row_state(cfg, n_rows))


def prefill(fns, cfg, params, cache, state, bt, seq, start, end, row):
    """One bucket-padded prefill of seq[start:end] at its absolute
    positions in batch row ``row``; the last valid position's logits."""
    n = end - start
    toks = np.zeros((1, BUCKET), np.int32)
    toks[0, :n] = seq[start:end]
    pos = start + np.minimum(np.arange(BUCKET, dtype=np.int32), n - 1)[None]
    logits, cache, state = fns.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([n], jnp.int32), cache, jnp.asarray(bt[row:row + 1]),
        last_only=True, row_state=state, rows=jnp.asarray([row], jnp.int32))
    return np.asarray(logits)[0], cache, state


def serve(cfg, params, seq, cuts, fns=so, row=1, carry=True, window=True):
    """Prefill seq[:cuts[-1]] in the slices ``cuts`` bounds, in batch
    row ``row`` of ``ROWS``, then teacher-forced decode steps through
    the state and the pages to the end of ``seq`` (the other rows not
    active). ``carry`` False: the scan's state is NOT handed to decode
    (zeros in its place); ``window`` False: nor is the convolution's
    window. Returns the logits at positions cuts[-1] - 1 .. len(seq) - 1
    and those positions."""
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    out, start = [], 0
    for end in cuts:
        logits, cache, state = prefill(fns, cfg, params, cache, state, bt,
                                       seq, start, end, row)
        start = end
    out.append(logits)
    zero = so.init_row_state(cfg, ROWS)
    state = {"kda": state["kda"] if carry else zero["kda"],
             "conv": state["conv"] if window else zero["conv"]}
    active = jnp.asarray(np.arange(ROWS) == row)
    for p in range(cuts[-1], len(seq)):
        tok, pos = np.zeros(ROWS, np.int32), np.zeros(ROWS, np.int32)
        tok[row], pos[row] = seq[p], p
        logits, cache, state = fns.forward_decode(
            params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
            jnp.asarray(bt), active=active, row_state=state)
        out.append(np.asarray(logits)[row])
    return np.stack(out), list(range(cuts[-1] - 1, len(seq)))


def verdict(cfg, params, seq, served, rows):
    ref = reference.routed_forward(params, seq, hf_model(cfg), rows)
    return reference.judge(served, np.asarray(ref.logits), ref.margins,
                           ref.swapped, [0.0], None, TOL)


def test_the_family_is_registered():
    assert model_names()["solar-open2-tiny"] == "solar_open2"
    assert model_names()["solar-open2-250b"] == "solar_open2"
    cfg = get_config("solar-open2-250b")
    assert family_of(cfg) is so
    assert [l for l, k in enumerate(cfg.layer_types)
            if k == so.GQA] == list(range(0, 48, 4))
    assert (cfg.n_kda, cfg.n_gqa, cfg.n_routed_layers) == (36, 12, 48)
    # the name's: 250.3 B, 14.7 B a token
    assert 250.2e9 < so.param_count_analytic(cfg) < 250.4e9
    assert 14.6e9 < so.active_param_count(cfg) < 14.9e9
    tiny = get_config("solar-open2-tiny")
    assert tiny.layer_types == (so.GQA, so.KDA, so.KDA, so.KDA) * 2
    assert so.param_count(so.init_params(jax.random.PRNGKey(0), tiny)) \
        == so.param_count_analytic(tiny)
    with pytest.raises(ValueError, match="gqa_layers"):
        get_config("solar-open2-tiny", gqa_layers=(0, 9))


# -- the served path against the reference ------------------------------------


def _prefill_50(cfg, params, seq, fns=so, **kw):
    toks = np.zeros((1, 64), np.int32)
    toks[0, :50] = seq[:50]
    pos = np.minimum(np.arange(64, dtype=np.int32), 49)[None]
    cache, state = new_cache(cfg, 1)
    return fns.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([50], jnp.int32), cache, jnp.asarray(block_table(cfg, 1)),
        row_state=state, rows=jnp.zeros((1,), jnp.int32), **kw)


def test_prefill_every_position(tiny):
    cfg, params, seq = tiny
    logits, _, _ = _prefill_50(cfg, params, seq)
    got = verdict(cfg, params, seq[:50], np.asarray(logits)[0, :50],
                  list(range(50)))
    assert got["ok"] and got["near_tie_share"] == 0, got


@pytest.mark.parametrize("cuts", [(20,), (32, 64, 70), (13, 45, 46, 75)],
                         ids=["one-slice", "whole-slices",
                              "mid-chunk-and-one-token"])
def test_prefill_in_slices_then_decode_through_state_and_pages(tiny, cuts):
    """The chunked scan carries its state from slice to slice (a slice
    of 13 ends in the middle of a chunk of 8, one of 32 on its edge, one
    is a single token), and the one-token update continues what the scan
    left; the GQA layers read the pages the slices and the steps
    wrote."""
    cfg, params, seq = tiny
    served, rows = serve(cfg, params, seq, cuts)
    got = verdict(cfg, params, seq, served, rows)
    assert got["ok"] and got["near_tie_share"] == 0, got


def _counts(cfg, st):
    layout, st = so.step_stats_layout(cfg), np.asarray(st)
    assert st.shape == (so.step_stats_size(cfg),)
    out = {k: int(st[i]) for k, i in layout.items() if k != "load"}
    out["load"] = st[slice(*layout["load"])]
    return out


def _mixed(cfg, params, cache, state, bt, dec, slices, T=BUCKET, S=2, **kw):
    """One mixed step: ``dec`` {row: (token, position)} decode rows of
    ``ROWS``, ``slices`` [(row, tokens, start)] on an (S, T) grid."""
    tok, pos = np.zeros(ROWS, np.int32), np.zeros(ROWS, np.int32)
    for r, (t, p) in dec.items():
        tok[r], pos[r] = t, p
    g_t, g_p = np.zeros((S, T), np.int32), np.zeros((S, T), np.int32)
    lens, rows = np.ones((S,), np.int32), np.full((S,), ROWS, np.int32)
    pf_bt = np.zeros((S, bt.shape[1]), np.int32)
    for i, (r, toks, start) in enumerate(slices):
        n = len(toks)
        g_t[i, :n], g_p[i, :n] = toks, start + np.arange(n)
        lens[i], rows[i], pf_bt[i] = n, r, bt[r]
    pf_tok, pf_pos, starts = pack_grid(g_t, g_p, lens, used=len(slices))
    return so.forward_mixed(
        params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
        jnp.asarray(bt), jnp.asarray(pf_tok), jnp.asarray(pf_pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bt),
        dec_active=jnp.asarray([r in dec for r in range(ROWS)]),
        row_state=state, pf_rows=jnp.asarray(rows), **kw)


def test_a_mixed_step_with_a_live_and_an_idle_slice(tiny):
    """Two decode rows and a prompt slice that continues a third row's
    context (the second slice idle), in one fused step, against the
    reference's full forward pass of each; the routed counters; and the
    row that does not decode keeps its state to the bit."""
    cfg, params, seq = tiny
    other = np.random.default_rng(7).integers(3, cfg.vocab_size, 90,
                                              dtype=np.int32)
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    for s, row, upto in ((seq, 0, 70), (other, 1, 45), (other, 2, 50)):
        for a in range(0, upto, BUCKET):
            _, cache, state = prefill(so, cfg, params, cache, state, bt, s,
                                      a, min(a + BUCKET, upto), row)
    dec, pf, cache, state, st = _mixed(
        cfg, params, cache, state, bt,
        {0: (seq[70], 70), 1: (other[45], 45)},
        [(2, other[50:79], 50)], stats=True)
    for served, s, row in ((dec[0], seq, 70), (dec[1], other, 45),
                           (pf[0], other, 78)):
        got = verdict(cfg, params, s[:row + 1], np.asarray(served)[None],
                      [row])
        assert got["ok"], got
    c = _counts(cfg, st)
    live = 29 + 2              # (the idle slice's trash token is no row's)
    assert c["runs"] == cfg.n_layers == 4
    assert (c["load"].sum() + c["away_slots"]
            == live * cfg.n_experts_per_tok * c["runs"])
    assert c["load"].sum() > 0 and c["away_slots"] > 0
    assert 0 < c["touched"] <= c["runs"] * cfg.n_held
    # a second step in which row 1 does not decode: its state stays
    before = jax.tree_util.tree_map(lambda x: np.asarray(x[:, 1]), state)
    *_, state = _mixed(cfg, params, cache, state, bt,
                       {0: (seq[71], 71)}, [(2, other[79:85], 79)])
    after = jax.tree_util.tree_map(lambda x: np.asarray(x[:, 1]), state)
    for k in before:
        assert (before[k] == after[k]).all(), k


def test_the_programs_hand_out_the_experts_they_chose(tiny):
    """``chosen=True``: every forward function returns, last, the
    experts each layer chose for each row of its stream — what the
    benchmark's reference is routed by. At float32 nothing is a
    near-tie, so the reference routed BY them is the reference: nothing
    swapped, the same logits; the program's state behind the last
    position is the reference's (its leaf ``(d_k, H d_v)`` as ``(H, d_k,
    d_v)``), and so are the pages' rows (``[k | v]``)."""
    cfg, params, seq = tiny
    L, k = cfg.n_layers, cfg.n_experts_per_tok
    logits, cache, state, took = _prefill_50(cfg, params, seq, chosen=True)
    assert took.shape == (L, 64, k) and took.dtype == jnp.int32
    forced = np.asarray(took)[:, :50]
    every = list(range(50))
    own = reference.routed_forward(params, seq[:50], hf_model(cfg), every,
                                   snaps=[49])
    ref = reference.routed_forward(params, seq[:50], hf_model(cfg), every,
                                   forced=forced, snaps=[49])
    assert not np.asarray(ref.swapped).any()
    np.testing.assert_array_equal(np.asarray(ref.logits),
                                  np.asarray(own.logits))
    leaf = np.asarray(state["kda"][:, 0])               # (L_k, d_k, H d_v)
    held = leaf.reshape(leaf.shape[:2] + (cfg.kda_heads, -1)).transpose(
        0, 2, 1, 3)
    assert ref.states.shape == (cfg.n_kda, 1) + held.shape[1:]
    assert max(reference.layer_distances(held, ref.states[:, 0])) < 1e-5
    pages = block_table(cfg, 1)[0, :7]                  # 56 >= 50 tokens
    rows = np.concatenate(
        [np.asarray(cache[x][:, pages]).reshape(cfg.n_gqa, 7 * PAGE, -1)
         for x in ("k", "v")], -1)[:, :50]
    assert ref.kv.shape == rows.shape
    assert max(reference.layer_distances(rows, ref.kv)) < 1e-5
    got = reference.judge(np.asarray(logits)[0, :50], np.asarray(ref.logits),
                          ref.margins, ref.swapped, [0.0], None, TOL)
    assert got["ok"] and got["swapped_share"] == 0, got
    # with the counters, the choices come after them; a decode step and
    # a mixed step hand out theirs a row of their streams
    *_, st, again = _prefill_50(cfg, params, seq, stats=True, chosen=True)
    assert st.shape == (so.step_stats_size(cfg),)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(took))
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    *_, took_d = so.forward_decode(
        params, cfg, jnp.asarray(seq[:ROWS]), jnp.zeros((ROWS,), jnp.int32),
        cache, jnp.asarray(bt), row_state=state, chosen=True)
    assert took_d.shape == (L, ROWS, k)
    np.testing.assert_array_equal(np.sort(np.asarray(took_d)[:, 0]),
                                  np.sort(forced[:, 0]))
    *_, took_m = _mixed(cfg, params, cache, state, bt, {0: (seq[1], 1)},
                        [(2, seq[:20], 0)], chosen=True)
    assert took_m.shape == (L, 2 * BUCKET + ROWS, k)
    np.testing.assert_array_equal(np.sort(np.asarray(took_m)[:, :20]),
                                  np.sort(forced[:, :20]))


def test_the_control_one_precision_down_says_which_part(tiny):
    """``lowp``: each name of ``reference.LOWP`` rounds its own part —
    the state held in bfloat16 moves the states (and the logits through
    them) and no K/V row of the GQA layer in front of them, the keys and
    values in 8 bits the cached rows by 2-4 % (and every state behind
    that layer), the router's product in
    bfloat16 neither state nor rows before the gates have moved the
    stream; ``True`` is all three and another name is refused."""
    cfg, params, seq = tiny
    model, every = hf_model(cfg), list(range(50))
    ref = reference.routed_forward(params, seq[:50], model, every,
                                   snaps=[49])
    *_, took = _prefill_50(cfg, params, seq, chosen=True)
    forced = np.asarray(took)[:, :50]

    def apart(lowp):
        low = reference.routed_forward(params, seq[:50], model, every, lowp,
                                       forced, snaps=[49])
        return (float(np.abs(np.asarray(low.logits - ref.logits)).max()),
                reference.layer_distances(low.states[:, 0],
                                          ref.states[:, 0]),
                reference.layer_distances(low.kv, ref.kv))

    by_state, states, kv = apart(("state",))
    assert by_state > 1e-4 and states[0] > 1e-3 and kv == [0.0]
    logits, states, kv = apart(("kv",))
    assert logits > 1e-5 and 0.02 < kv[0] < 0.04 and states[0] > 0
    logits, states, kv = apart(("router",))     # the gates alone move
    assert 0 < logits < by_state and kv[0] == 0
    assert apart(True) == apart(reference.LOWP)
    with pytest.raises(ValueError, match="lowp"):
        reference.routed_forward(params, seq[:50], model, every, ("pool",))


def test_position_zero_starts_from_a_zero_state(tiny):
    """A row that held another sequence: a prompt that starts at
    position 0 reads nothing of it."""
    cfg, params, seq = tiny
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    junk = np.random.default_rng(3).integers(3, cfg.vocab_size, 40,
                                             dtype=np.int32)
    _, cache, state = prefill(so, cfg, params, cache, state, bt, junk, 0, 30,
                              1)
    assert float(jnp.abs(state["kda"][:, 1]).max()) > 0
    logits, cache, state = prefill(so, cfg, params, cache, state, bt, seq,
                                   0, 25, 1)
    got = verdict(cfg, params, seq[:25], logits[None], [24])
    assert got["ok"], got


# -- ``tests/mixed_tight.py``'s cases, with row state ---------------------------


@pytest.fixture
def tight(monkeypatch):
    """``tight(as_written)`` -> the three forward functions, the mixed
    step under a jit of this module's own that is traced (at its first
    call, inside the test) with ``mixed_tight.TILE``-row tiles in slices
    ``WIDTH`` wide, so that a tile's edge falls inside a slice; no other
    test meets a program traced with the small tile. ``as_written``: all
    three compiled to round where their source rounds
    (``mixed_tight._forward`` has why)."""
    import mixed_tight as mt
    monkeypatch.setattr(mt.rows, "ROW_TILE", mt.TILE)

    def fns(as_written):
        if as_written not in _TIGHT:
            opts = ({"xla_allow_excess_precision": False} if as_written
                    else None)
            _TIGHT[as_written] = SimpleNamespace(**{
                name: (getattr(so, name) if not as_written
                       and name != "forward_mixed" else jax.jit(
                    getattr(so, name).__wrapped__, compiler_options=opts,
                    static_argnames=("cfg", "stats", "chosen") + also))
                for name, also in (("forward_prefill", ("last_only",)),
                                   ("forward_decode", ()),
                                   ("forward_mixed", ()))})
        return _TIGHT[as_written]
    return fns


_TIGHT = {}


def _both_ways(fns, cfg, params, case):
    """The case's plan apart (each slice through ``forward_prefill``,
    then the rows' ``forward_decode``) and together (one
    ``forward_mixed`` over tight slices), over the same pool and row
    state: ``{"dec", "pf", "pages", "state", "took"}`` each — ``took``
    the experts chosen for the used slices' tokens, in the plan's order,
    then for the active decode rows. The decode rows own batch rows
    0 .. B - 1, slice ``s`` row B + s; the last decode row is not
    active."""
    import mixed_tight as mt
    S, T, plan = mt.shape_of(case)
    B = len(mt.DECODE)
    rng = np.random.default_rng(sorted(mt.CASES).index(case))
    mp = cfg.max_seq_len // PAGE
    bts = block_table(cfg, B + S)
    cache, state = new_cache(cfg, B + S)

    def draw(n):
        return rng.integers(3, cfg.vocab_size, n, dtype=np.int32)

    def one(cache, state, row, toks, start, width=T):
        n = len(toks)
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = toks
        pos = start + np.minimum(np.arange(width, dtype=np.int32), n - 1)
        logits, cache, state, took = fns.forward_prefill(
            params, cfg, jnp.asarray(padded), jnp.asarray(pos[None]),
            jnp.asarray([n], jnp.int32), cache, jnp.asarray(bts[row][None]),
            last_only=True, row_state=state,
            rows=jnp.asarray([row], jnp.int32), chosen=True)
        return np.asarray(logits)[0], cache, state, np.asarray(took)[:, :n]

    for b, n in enumerate(mt.DECODE):
        _, cache, state, _ = one(cache, state, b, draw(n), 0)
    for s, (_, start) in enumerate(plan):
        if start:
            _, cache, state, _ = one(cache, state, B + s, draw(start), 0)
    slices = [draw(n) for n, _ in plan]
    dec_tok, dec_pos = draw(B), np.asarray(mt.DECODE, np.int32)
    active = np.arange(B) < B - 1

    def result(dec, pf, cache, state, took):
        return {"dec": np.asarray(dec)[active],
                "pf": np.asarray(pf)[:len(plan)],
                "pages": {x: np.asarray(cache[x][:, 1:], np.float32)
                          for x in cache},
                "state": {x: np.asarray(v[:, :B + S], np.float32)
                          for x, v in state.items()},
                "took": np.concatenate(took, axis=1)}

    ref_c, ref_s = jax.tree.map(jnp.copy, (cache, state))
    ref_pf, ref_took = [], []
    for s, (toks, (_, start)) in enumerate(zip(slices, plan)):
        logits, ref_c, ref_s, took = one(ref_c, ref_s, B + s, toks, start)
        ref_pf.append(logits)
        ref_took.append(took)
    ref_dec, ref_c, ref_s, took = fns.forward_decode(
        params, cfg, jnp.asarray(dec_tok), jnp.asarray(dec_pos), ref_c,
        jnp.asarray(bts[:B]), active=jnp.asarray(active), row_state=ref_s,
        chosen=True)
    parts = result(ref_dec, np.stack(ref_pf), ref_c, ref_s,
                   ref_took + [np.asarray(took)[:, active]])

    g_t, g_p = np.zeros((S, T), np.int32), np.zeros((S, T), np.int32)
    lens, rows = np.ones((S,), np.int32), np.full((S,), B + S, np.int32)
    pf_bt = np.zeros((S, mp), np.int32)
    for s, (toks, (n, start)) in enumerate(zip(slices, plan)):
        g_t[s, :n], g_p[s, :n] = toks, start + np.arange(n)
        lens[s], rows[s], pf_bt[s] = n, B + s, bts[B + s]
    pf_tok, pf_pos, starts = pack_grid(g_t, g_p, lens, used=len(plan))
    bt_dec = np.zeros((B + S, mp), np.int32)
    bt_dec[:B] = bts[:B]
    tok, pos = np.zeros(B + S, np.int32), np.zeros(B + S, np.int32)
    tok[:B], pos[:B] = dec_tok, dec_pos
    live = np.zeros(B + S, bool)
    live[:B] = active
    dec, pf, cache, state, took = fns.forward_mixed(
        params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
        jnp.asarray(bt_dec), jnp.asarray(pf_tok), jnp.asarray(pf_pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bt),
        dec_active=jnp.asarray(live), row_state=state,
        pf_rows=jnp.asarray(rows), chosen=True)
    took = np.asarray(took)
    assert took.shape == (cfg.n_layers, S * T + B + S, cfg.n_experts_per_tok)
    # the slices' S * T GRID rows, then the decode rows
    in_grid = [took[:, s * T:s * T + n] for s, (n, _) in enumerate(plan)]
    return parts, result(np.asarray(dec)[:B], pf, cache, state,
                         in_grid + [took[:, S * T:S * T + B][:, active]])


@pytest.mark.parametrize("served", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(__import__("mixed_tight").CASES))
def test_the_mixed_step_over_tight_slices_computes_what_the_parts_do(
        tiny, tight, case, served):
    """``tests/mixed_tight.py``'s ``CASES`` — slices full, ending on and
    beside a tile's edge, unused, of one token, continuing a context —
    through ``forward_mixed`` (rows in live tiles of 8, the convolution
    a slice in use at a time) against ``forward_prefill`` +
    ``forward_decode`` over the same pool AND row state: the logits, the
    K/V pages, both row-state leaves of every row, and the chosen
    experts in GRID order. In float32, and in bfloat16 as served with
    all three programs compiled to round where their source rounds:
    there the two ways read 0.0 apart in every case, state and choices
    included — no row that holds a token is computed differently. (Left
    to keep a bfloat16 chain wide inside a fusion, XLA's CPU backend
    does so in the whole-grid parts and not in a loop's body, and the
    logits read 0.04-0.35 apart through 0-11 swapped experts.)"""
    cfg, params, _ = tiny
    atol = 2e-5
    if served:
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        params = so.init_params(jax.random.PRNGKey(53), cfg)
        atol = 1e-3
    parts, mixed = _both_ways(tight(served), cfg, params, case)
    for r in (parts, mixed):
        r.update(r.pop("pages"), **r.pop("state"))
    assert set(parts) == {"dec", "pf", "k", "v", "kda", "conv", "took"}
    for name in set(parts) - {"took"}:
        assert np.abs(parts[name] - mixed[name]).max() <= atol, name
    np.testing.assert_array_equal(np.sort(mixed["took"]),
                                  np.sort(parts["took"]))


# -- the broken paths, each of which the comparison refuses -------------------


def _retraced():
    """The model's forward functions, each under a NEW function and a
    ``jax.jit`` of its own: a patched helper must be traced again, not
    found in the cache of the function it was traced under."""
    def fresh(fn):
        def call(*args, **kw):
            return fn(*args, **kw)
        return jax.jit(call, static_argnums=(1,),
                       static_argnames=("last_only", "stats"))

    return SimpleNamespace(
        forward_prefill=fresh(so.forward_prefill.__wrapped__),
        forward_decode=fresh(so.forward_decode.__wrapped__))


def _kda_in_with(change):
    def kda_in(x, kp, i, cfg, _sound=so._kda_in):
        return change(*_sound(x, kp, i, cfg), x=x, kp=kp, i=i, cfg=cfg)
    return kda_in


def _decay_without_a_log(qkv, g, b, z, *, kp, i, cfg, **_):
    return qkv, g / jnp.repeat(jnp.exp(kp["a_log"][i]),
                               cfg.kda_head_dim), b, z


def _half_a_pair(which):
    """``ops/kda.low_rank`` with the pair's up-projection dropped where
    the caller asks for the decay's (``exact``) or the gate's: the r
    values of the down-projection repeated over the channels."""
    def low_rank(x, down, up, *, exact=False, _sound=so.low_rank):
        if exact != (which == "decay"):
            return _sound(x, down, up, exact=exact)
        mid = jnp.dot(x, down, preferred_element_type=jnp.float32)
        return jnp.tile(mid, (1, up.shape[1] // up.shape[0]))
    return low_rank


def _route_bias_in_the_gates(x, w, bias, *, top_k, scale, **kw):
    s = jax.nn.sigmoid(jnp.dot(x, w.astype(jnp.float32))) + bias
    g, experts = jax.lax.top_k(s, top_k)
    return experts, g / (jnp.sum(g, -1, keepdims=True) + 1e-20) * scale


FAULTS = [
    "beta-not-doubled", "decay-without-its-a-log", "decay-pair-s-half-dropped",
    "gate-pair-s-half-dropped", "gqa-gate-dropped", "rotary-applied",
    "bias-in-the-gates", "share-s-offset-wrong", "no-l2-norm",
    "shared-expert-dropped", "gqa-at-the-wrong-layers"]


def _broken(name, monkeypatch, cfg, params):
    """``(cfg, params)`` as a program with that fault would serve them;
    the reference keeps the sound ones."""
    if name == "beta-not-doubled":
        monkeypatch.setattr(so, "_kda_in", _kda_in_with(
            lambda qkv, g, b, z, **_: (qkv, g, b / 2, z)))
    elif name == "decay-without-its-a-log":
        monkeypatch.setattr(so, "_kda_in",
                            _kda_in_with(_decay_without_a_log))
    elif name == "decay-pair-s-half-dropped":
        monkeypatch.setattr(so, "low_rank", _half_a_pair("decay"))
    elif name == "gate-pair-s-half-dropped":
        monkeypatch.setattr(so, "low_rank", _half_a_pair("gate"))
    elif name == "gqa-gate-dropped":
        def close(h, attn, gate, gp, i, cfg, _sound=so._attn_close):
            return _sound(h, attn, jnp.full_like(gate, 1e4), gp, i, cfg)
        monkeypatch.setattr(so, "_attn_close", close)
    elif name == "rotary-applied":
        from llmq_tpu.ops.rope import apply_rope, rope_cos_sin

        # (rotated by the position in the program's own rows: a prefill
        # slice's rows 0 .. T - 1, a decode step's row index — any
        # rotation at all is a fault here)
        def qkvg(x, gp, i, cfg, _sound=so._qkvg):
            q, k, v, gate = _sound(x, gp, i, cfg)
            pos = jnp.broadcast_to(jnp.arange(q.shape[-3]), q.shape[:-2])
            cos, sin = rope_cos_sin(pos, cfg.head_dim, 10000.0)
            return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v, gate
        monkeypatch.setattr(so, "_qkvg", qkvg)
    elif name == "bias-in-the-gates":
        monkeypatch.setattr(so, "route", _route_bias_in_the_gates)
    elif name == "share-s-offset-wrong":
        # experts 0-7's slots multiplied with experts 8-15's matrices
        cfg = dataclasses.replace(cfg, held_experts=(0, 8))
    elif name == "no-l2-norm":
        monkeypatch.setattr(so, "_unit", lambda x, cfg: x)
    elif name == "shared-expert-dropped":
        params = {**params, "moe": {**params["moe"], "ws_down": jnp.zeros_like(
            params["moe"]["ws_down"])}}
    elif name == "gqa-at-the-wrong-layers":
        # K G K K in place of G K K K: as many layers of each kind, so
        # the same tree serves
        cfg = dataclasses.replace(cfg, gqa_layers=(1,))
    else:
        raise AssertionError(name)
    return cfg, params


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_path_is_refused_by_ten_times_the_limit(tiny, monkeypatch,
                                                         fault):
    """Every item of ``assumed`` and every mechanism, taken out of the
    PROGRAM: prefill in slices and decode through state and pages read
    ten times the limit or more against the sound reference."""
    cfg, params, seq = tiny
    bad_cfg, bad_params = _broken(fault, monkeypatch, cfg, params)
    served, rows = serve(bad_cfg, bad_params, seq, (32, 64, 70),
                         fns=_retraced())
    got = verdict(cfg, params, seq, served, rows)
    # (not "<=": without the norm beta k k^T is no contraction and the
    # state runs away to NaN, which is refused too)
    assert not got["ok"] and not got["rms_clean"] <= 10 * TOL["rms_clean"], \
        got


@pytest.mark.parametrize("kw", [{"carry": False}, {"window": False}],
                         ids=["scan-s-state-not-handed-on",
                              "conv-window-not-moved"])
def test_what_the_slices_leave_behind_and_decode_does_not_get_is_refused(
        tiny, kw):
    cfg, params, seq = tiny
    served, rows = serve(cfg, params, seq, (32, 64, 70), **kw)
    got = verdict(cfg, params, seq, served[1:], rows[1:])
    assert not got["ok"] and got["rms_clean"] > 10 * TOL["rms_clean"], got


def test_int8_and_a_mesh_are_refused_by_name_and_no_loader_is_written(tiny):
    cfg, _, _ = tiny
    for kw, word in (({"quantization": "int8"}, "model.quantization"),
                     ({"kv_quantization": "int8"}, "model.kv_quantization"),
                     ({"mesh": True}, "executor.mesh")):
        with pytest.raises(ValueError, match=word):
            so.check_serving(cfg, **kw)
    with pytest.raises(ValueError, match="model.kv_quantization"):
        so.init_kv_pages(cfg, 4, PAGE, jnp.int8)
    with pytest.raises(ValueError, match="no checkpoint loader"):
        so.import_hf("/nowhere", cfg)
    so.check_serving(cfg)


def test_the_counts_the_tracing_reads_on_the_host():
    """``scan_step_tokens`` (what ``scan_chunks`` is counted in),
    ``mixed_key_blocks`` (``pf_key_blocks`` / ``pf_table_blocks``) and the
    ``routes`` line's plan of the update kernel, at the served widths."""
    cfg = get_config("solar-open2-250b", n_layers=4, gqa_layers=(0,),
                     held_experts=(0, 40), vocab_size=24576,
                     max_seq_len=34816)
    # five slices of 512 at contexts of 512, 17k, 34k and two idle
    visited, table = so.mixed_key_blocks(
        np.asarray([512, 17000, 34816, 1, 1]), 512, 128, 272)
    assert (visited, table) == (1 + 34 + 68 + 1 + 1, 5 * 68)
    cache = jax.eval_shape(lambda: so.init_kv_pages(cfg, 8, 128))
    lines = so.routes(cfg, cache, batch=32, page_size=128, max_pages=272,
                      decode=True, prefill_rows=5)
    assert set(lines) >= {"ssm_update", "ssm_scan", "decode_attention",
                          "prefill_attention"}
    # off the TPU the routes are XLA's and no plan is named
    assert lines["ssm_update"] == "xla" and so.scan_step_tokens(cfg, 512) \
        is None


# -- through the executor and the engine --------------------------------------


def make_engine(tiny, batch=2, **kw):
    cfg, params, _ = tiny
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=batch, page_size=PAGE,
                     num_pages=96, prefill_buckets=[16, 32],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8)
    return InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=64,
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                     max_slices=2), **kw), ex


def generate(eng, rid, prompt, n=12, **kw):
    h = eng.submit(GenRequest(id=rid, prompt=prompt, max_new_tokens=n,
                              temperature=0.0, **kw))
    eng.run_until_idle()
    assert h.done
    return h.result


def test_the_executor_carries_row_state_beside_kv_pages(tiny):
    """A fifth family through the executor's row-state plumbing with no
    edit to it: K/V pages over the GQA layers alone, the delta rule's
    state and window over the KDA layers."""
    cfg = tiny[0]
    eng, ex = make_engine(tiny, batch=3)
    assert set(ex.cache) == {"k", "v"}
    assert set(ex.row_state) == {"kda", "conv"}
    assert ex.cache["k"].shape == (cfg.n_gqa, 96, PAGE, 64)
    assert ex.row_state["kda"].shape == (cfg.n_kda, 4, 32, 128)
    per_row = so.row_state_bytes_per_row(cfg)
    assert ex.row_state_bytes_per_row == per_row == sum(
        x.nbytes for x in jax.tree.leaves(ex.row_state)) // 4
    assert ex.attention_window is None
    assert eng.get_stats()["row_state"]["bytes_per_row"] == per_row
    with pytest.raises(ValueError, match="names its sequence's batch row"):
        ex.prefill_async([1, 2, 3], 0, np.zeros(16, np.int32), 0.0)


def test_served_through_the_engine_as_alone(tiny):
    """Requests through ``InferenceEngine`` over the executor's prefill,
    decode-chunk and mixed-chunk programs: a prompt that joins a
    running batch (its slices ride mixed steps) yields the tokens it
    yields alone, and a row that another sequence left is started from a
    zero state; the routed counters fill by the family's layout and the
    mixed steps' key blocks by ``mixed_key_blocks``."""
    prompt = "a prompt of fifty-odd bytes whose slices ride mixed steps"
    alone, _ = make_engine(tiny)
    want = generate(alone, "a", prompt, n=16)
    eng, _ = make_engine(tiny)
    first = eng.submit(GenRequest(id="long", prompt="x" * 20,
                                  max_new_tokens=40, temperature=0.0))
    for _ in range(3):
        eng.step()
    second = eng.submit(GenRequest(id="b", prompt=prompt, max_new_tokens=16,
                                   temperature=0.0))
    eng.run_until_idle()
    assert first.done and second.done
    assert second.result.tokens == want.tokens and len(want.tokens) == 16
    again = generate(eng, "c", prompt, n=16)
    assert again.tokens == want.tokens
    stats = eng.get_stats()
    assert stats["mixed_batch"]["steps"] > 0
    moe = stats["moe"]
    assert moe["layer_runs"] > 0 and moe["pairs"] > 0
    assert moe["away_slots"] > 0 and len(moe["load"]) == tiny[0].n_held
    blocks = stats["mixed_key_blocks"]
    assert 0 < blocks["visited"] <= blocks["table"]


def test_a_prefix_match_and_a_second_turn_are_declined_and_counted(tiny):
    shared = "the same forty-odd characters of system prompt: "
    plain, _ = make_engine(tiny)
    want = generate(plain, "b", shared + "second question")
    eng, _ = make_engine(tiny, prefix_cache=PrefixCacheConfig(enabled=True))
    generate(eng, "a", shared + "first question", conversation_id="c")
    second = generate(eng, "b", shared + "second question")
    assert second.cached_tokens == 0 and second.tokens == want.tokens
    turn = generate(eng, "a2", " and then?", conversation_id="c",
                    history_text=shared + "first question")
    assert turn.cached_tokens == 0
    declined = eng.get_stats()["row_state"]["declined"]
    assert declined["prefix"] >= 1 and sum(declined.values()) >= 2
