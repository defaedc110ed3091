"""The Ling-3.0-flash block (``models/ling_hybrid.py``: delta-rule
linear attention with a decay a channel as ROW STATE beside one latent
attention layer in a period over a latent page pool, a group-limited
sigmoid router beside a shared expert, serving ONE CHIP'S SHARE of the
experts) held to its family's plain float32 reference
(``benchmark/families/ling_hybrid/reference.py``, which shares no code
with ``llmq_tpu`` and computes the recurrence a token at a time) at a
tiny width, on seeded weights.

Logits, never tokens. The weights here are float32, so the served path
differs from the reference by float32 rounding alone and the comparison
is tight (``TOL``): each of the broken paths below — every item of the
configuration file's ``assumed`` and every mechanism, the things the
tolerance on the chip cannot see — moves the logits by ten times that
or more. The tiny model is two periods of ``K K L`` with layer 0 dense;
it holds experts 8-15 of 16 (groups 2 and 3 of 4), so both kinds of
slot occur.
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
from llmq_tpu.models import family_of, get_config, latent, model_names
from llmq_tpu.models import ling_hybrid as lh
from llmq_tpu.ops.rows import pack_grid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(REPO, "benchmark", "families", "ling_hybrid")
reference = contract.load_family(FAMILY, "reference")

PAGE, BUCKET, ROWS = 8, 32, 3
#: float32 against float32: measured 2e-7 to 1e-6 here; the mildest
#: broken path gives over 1e-4.
TOL = {"clean_quantile": 0.25, "rms_clean": 1e-5, "rms": 1e-5,
       "margin_eps": 1e-7, "growth": float("inf"), "margin_decisive": 0.0,
       "state_rel": [0.0], "latent_rel": 0.0}


def hf_model(cfg):
    """The configuration under the public ``config.json``'s keys, with
    the share as the benchmark's file states it: what the reference
    reads."""
    lo, hi = cfg.held
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "num_attention_heads": cfg.n_heads,
            "layer_group_size": cfg.layer_group_size,
            "first_k_dense_replace": cfg.first_k_dense,
            "dense_layers_held": cfg.first_k_dense,
            "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": None,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "kda_lower_bound": cfg.kda_lower_bound,
            "num_experts": hi - lo, "router_experts": cfg.n_routed_experts,
            "expert_share": {"chips": cfg.n_routed_experts // (hi - lo),
                             "index": lo // (hi - lo)},
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk_prob": cfg.norm_topk_prob,
            "score_function": "sigmoid", "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "vocab_size": cfg.vocab_size}


@pytest.fixture(scope="module")
def tiny():
    cfg = lh.ling_hybrid_tiny(dtype=jnp.float32, max_seq_len=128,
                              held_experts=(8, 16))
    params = lh.init_params(jax.random.PRNGKey(45), cfg)
    # A selection bias that is not zero: one that the gates must not see.
    params["moe"]["router_bias"] = 0.01 * jax.random.normal(
        jax.random.PRNGKey(5), params["moe"]["router_bias"].shape)
    seq = np.random.default_rng(45).integers(3, cfg.vocab_size, 100,
                                             dtype=np.int32)
    return cfg, params, seq


def block_table(cfg, n_rows=ROWS):
    mp = cfg.max_seq_len // PAGE
    return (1 + np.arange(n_rows)[:, None] * mp
            + np.arange(mp)[None, :]).astype(np.int32)


def new_cache(cfg, n_rows=ROWS):
    return (lh.init_kv_pages(cfg, 1 + n_rows * (cfg.max_seq_len // PAGE),
                             PAGE), lh.init_row_state(cfg, n_rows))


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


def serve(cfg, params, seq, cuts, fns=lh, row=1, carry=True):
    """Prefill seq[:cuts[-1]] in the slices ``cuts`` bounds, in batch
    row ``row`` of ``ROWS``, then teacher-forced decode steps through
    the state and the pool to the end of ``seq`` (the other rows not
    active). ``carry`` False: the scan's state is NOT handed to decode
    (a zero row state in its place). Returns the logits at positions
    cuts[-1] - 1 .. len(seq) - 1 and those positions."""
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    out, start = [], 0
    for end in cuts:
        logits, cache, state = prefill(fns, cfg, params, cache, state, bt,
                                       seq, start, end, row)
        start = end
    out.append(logits)
    if not carry:
        state = lh.init_row_state(cfg, ROWS)
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
    assert model_names()["ling-hybrid-tiny"] == "ling_hybrid"
    assert model_names()["ling-3.0-flash"] == "ling_hybrid"
    cfg = get_config("ling-3.0-flash")
    assert family_of(cfg) is lh
    assert cfg.layer_types.count(lh.LATENT) == 7
    assert [l for l, k in enumerate(cfg.layer_types)
            if k == lh.LATENT] == [5, 11, 17, 23, 29, 35, 41]
    tiny = get_config("ling-hybrid-tiny")
    assert tiny.layer_types == (lh.KDA, lh.KDA, lh.LATENT) * 2
    assert lh.param_count(lh.init_params(jax.random.PRNGKey(0), tiny)) \
        == lh.param_count_analytic(tiny)


# -- the served path against the reference ------------------------------------


def test_prefill_every_position(tiny):
    cfg, params, seq = tiny
    toks = np.zeros((1, 64), np.int32)
    toks[0, :50] = seq[:50]
    pos = np.minimum(np.arange(64, dtype=np.int32), 49)[None]
    cache, state = new_cache(cfg, 1)
    logits, _, _ = lh.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([50], jnp.int32), cache, jnp.asarray(block_table(cfg, 1)),
        row_state=state, rows=jnp.zeros((1,), jnp.int32))
    got = verdict(cfg, params, seq[:50], np.asarray(logits)[0, :50],
                  list(range(50)))
    assert got["ok"] and got["near_tie_share"] == 0, got


@pytest.mark.parametrize("cuts", [(20,), (32, 64, 70), (13, 45, 60, 81)],
                         ids=["one-slice", "whole-slices", "mid-chunk"])
def test_prefill_in_slices_then_decode_through_state_and_pool(tiny, cuts):
    """The chunked scan carries its state from slice to slice (a slice
    of 13 ends in the middle of a chunk of 8, one of 32 on its edge),
    and the one-token update continues what the scan left."""
    cfg, params, seq = tiny
    served, rows = serve(cfg, params, seq, cuts)
    got = verdict(cfg, params, seq, served, rows)
    assert got["ok"] and got["near_tie_share"] == 0, got


def test_the_scans_state_not_handed_to_decode_is_refused(tiny):
    cfg, params, seq = tiny
    served, rows = serve(cfg, params, seq, (32, 64, 70), carry=False)
    got = verdict(cfg, params, seq, served[1:], rows[1:])
    assert not got["ok"] and got["rms_clean"] > 10 * TOL["rms_clean"], got


def _counts(cfg, st):
    layout, st = lh.step_stats_layout(cfg), np.asarray(st)
    assert st.shape == (lh.step_stats_size(cfg),)
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
    return lh.forward_mixed(
        params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
        jnp.asarray(bt), jnp.asarray(pf_tok), jnp.asarray(pf_pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bt),
        dec_active=jnp.asarray([r in dec for r in range(ROWS)]),
        row_state=state, pf_rows=jnp.asarray(rows), **kw)


def test_a_mixed_step_with_tight_slices(tiny):
    """Two decode rows and a prompt slice that continues a third row's
    context (its tokens tight), in one fused step, against the
    reference's full forward pass of each; the routed counters; and the
    row that does not decode keeps its state to the bit."""
    cfg, params, seq = tiny
    other = np.random.default_rng(7).integers(3, cfg.vocab_size, 90,
                                              dtype=np.int32)
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    for s, row, upto in ((seq, 0, 70), (other, 1, 45), (other, 2, 50)):
        for a in range(0, upto, BUCKET):
            _, cache, state = prefill(lh, cfg, params, cache, state, bt, s,
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
    live = 29 + 2 + 1          # and the unused slice's one trash token
    assert c["runs"] == cfg.n_routed_layers == 5
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


def _prefill_50(cfg, params, seq, **kw):
    toks = np.zeros((1, 64), np.int32)
    toks[0, :50] = seq[:50]
    pos = np.minimum(np.arange(64, dtype=np.int32), 49)[None]
    cache, state = new_cache(cfg, 1)
    return lh.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([50], jnp.int32), cache, jnp.asarray(block_table(cfg, 1)),
        row_state=state, rows=jnp.zeros((1,), jnp.int32), **kw)


def test_the_programs_hand_out_the_experts_they_chose(tiny):
    """``chosen=True``: every forward function returns, last, the
    experts each routed layer chose for each row of its stream — what
    the benchmark's reference is routed by. At float32 nothing is a
    near-tie, so they are the reference's own choice at every position,
    and the reference routed BY them is the reference: nothing swapped,
    the same logits; the program's state behind the last position is
    the reference's (its leaf ``(d_k, H d_v)`` as ``(H, d_k, d_v)``)."""
    cfg, params, seq = tiny
    Lr, k = cfg.n_routed_layers, cfg.n_experts_per_tok
    logits, _, state, took = _prefill_50(cfg, params, seq, chosen=True)
    assert took.shape == (Lr, 64, k) and took.dtype == jnp.int32
    forced = np.asarray(took)[:, :50]
    every = list(range(50))
    own = reference.routed_forward(params, seq[:50], hf_model(cfg), every,
                                   snaps=[49])
    ref = reference.routed_forward(params, seq[:50], hf_model(cfg), every,
                                   forced=forced, snaps=[49])
    assert not np.asarray(ref.swapped).any()
    assert ref.swapped.shape == ref.margins.shape == (Lr, 50)
    np.testing.assert_array_equal(np.asarray(ref.logits),
                                  np.asarray(own.logits))
    leaf = np.asarray(state["kda"][:, 0])               # (L_k, d_k, H d_v)
    held = leaf.reshape(leaf.shape[:2] + (cfg.n_heads, -1)).transpose(
        0, 2, 1, 3)
    assert ref.states.shape == (cfg.n_kda, 1) + held.shape[1:]
    assert max(reference.layer_distances(held, ref.states[:, 0])) < 1e-5
    got = reference.judge(np.asarray(logits)[0, :50], np.asarray(ref.logits),
                          ref.margins, ref.swapped, [0.0], None, TOL)
    assert got["ok"] and got["swapped_share"] == 0, got
    # with the counters, the choices come after them; a decode step and
    # a mixed step hand out theirs a row of their streams
    *_, st, again = _prefill_50(cfg, params, seq, stats=True, chosen=True)
    assert st.shape == (lh.step_stats_size(cfg),)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(took))
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    *_, took_d = lh.forward_decode(
        params, cfg, jnp.asarray(seq[:ROWS]), jnp.zeros((ROWS,), jnp.int32),
        cache, jnp.asarray(bt), row_state=state, chosen=True)
    assert took_d.shape == (Lr, ROWS, k)
    np.testing.assert_array_equal(np.sort(np.asarray(took_d)[:, 0]),
                                  np.sort(forced[:, 0]))
    *_, took_m = _mixed(cfg, params, cache, state, bt, {0: (seq[1], 1)},
                        [(2, seq[:20], 0)], chosen=True)
    assert took_m.shape == (Lr, 2 * BUCKET + ROWS, k)
    np.testing.assert_array_equal(np.sort(np.asarray(took_m)[:, :20]),
                                  np.sort(forced[:, :20]))


def test_a_clear_choice_the_served_path_did_not_make_is_refused(tiny):
    """The reference routed by choices that are NOT its own: the
    position is flagged with the margin its own choice had, the logits
    from there on are another model's, and ``judge`` refuses the group
    by ``margin_decisive`` however close the logits are."""
    cfg, params, seq = tiny
    model, every = hf_model(cfg), list(range(50))
    *_, took = _prefill_50(cfg, params, seq, chosen=True)
    forced = np.array(np.asarray(took)[:, :50])
    own = reference.routed_forward(params, seq[:50], model, every)
    layer, at = 1, 30
    margin = float(own.margins[layer, at])
    assert margin > 1e-4                   # a clear choice, at float32
    absent = next(e for e in range(cfg.n_routed_experts)
                  if e not in forced[layer, at])
    forced[layer, at, 0] = absent
    ref = reference.routed_forward(params, seq[:50], model, every,
                                   forced=forced)
    swapped = np.asarray(ref.swapped)
    assert swapped[layer, at] and swapped.sum() == 1
    assert float(ref.margins[layer, at]) == pytest.approx(margin, rel=1e-5)
    moved = np.abs(np.asarray(ref.logits) - np.asarray(own.logits)).max(-1)
    assert (moved[:at] == 0).all() and moved[at] > 1e-4
    loose = dict(TOL, rms_clean=1.0, rms=1.0)
    got = reference.judge(np.asarray(own.logits), np.asarray(ref.logits),
                          ref.margins, ref.swapped, [0.0], None, loose)
    assert not got["ok"] and got["swap_margin"] == pytest.approx(margin,
                                                                 rel=1e-5)
    assert reference.judge(
        np.asarray(own.logits), np.asarray(ref.logits), ref.margins,
        ref.swapped, [0.0], None,
        dict(loose, margin_decisive=2 * margin))["ok"]
    # a position that gives no choice (negative) is routed by the
    # reference's own
    forced[layer, at] = -1
    ref = reference.routed_forward(params, seq[:50], model, every,
                                   forced=forced)
    assert not np.asarray(ref.swapped).any()
    np.testing.assert_array_equal(np.asarray(ref.logits),
                                  np.asarray(own.logits))


def test_the_control_one_precision_down_says_which_part(tiny):
    """``lowp``: each name of ``reference.LOWP`` rounds its own part
    and nothing else — the state held in bfloat16 moves the states (and
    the logits through them), the latent in 8 bits the cached rows and
    no state before the first latent layer, the router's product in
    bfloat16 neither once the choices are given; ``True`` is all three
    and another name is refused."""
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
                reference.layer_distances(low.latents, ref.latents))

    by_state, states, latents = apart(("state",))
    assert by_state > 1e-4 and states[0] > 1e-3 and latents[0] > 0
    logits, states, latents = apart(("latent",))
    assert logits > 1e-5 and 0.02 < latents[0] < 0.04
    assert states[0] == states[1] == 0 and states[2] > 0   # K K L | K ...
    logits, states, latents = apart(("router",))   # the gates alone move
    assert 0 < logits < by_state and states[0] == states[1] == 0
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
    _, cache, state = prefill(lh, cfg, params, cache, state, bt, junk, 0, 30,
                              1)
    assert float(jnp.abs(state["kda"][:, 1]).max()) > 0
    logits, cache, state = prefill(lh, cfg, params, cache, state, bt, seq,
                                   0, 25, 1)
    got = verdict(cfg, params, seq[:25], logits[None], [24])
    assert got["ok"], got


# -- ``tests/mixed_tight.py``'s cases, with row state ---------------------------


def _both_ways(cfg, params, case):
    """The case's plan apart (each slice through ``forward_prefill``,
    then the rows' ``forward_decode``) and together (one
    ``forward_mixed`` over tight slices), over the same pool and row
    state: ``{"dec", "pf", "pages", "state"}`` each. The decode rows own
    batch rows 0 .. B - 1, slice ``s`` row B + s; the last decode row is
    not active."""
    import mixed_tight as mt
    S, T, plan = mt.shape_of(case)
    B = len(mt.DECODE)
    rng = np.random.default_rng(sorted(mt.CASES).index(case))
    mp = cfg.max_seq_len // PAGE
    bts = (1 + np.arange((B + S) * mp).reshape(B + S, mp)).astype(np.int32)
    cache = lh.init_kv_pages(cfg, 1 + (B + S) * mp, PAGE)
    state = lh.init_row_state(cfg, B + S)

    def draw(n):
        return rng.integers(3, cfg.vocab_size, n, dtype=np.int32)

    def one(cache, state, row, toks, start, width=T):
        n = len(toks)
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = toks
        pos = start + np.minimum(np.arange(width, dtype=np.int32), n - 1)
        logits, cache, state = lh.forward_prefill(
            params, cfg, jnp.asarray(padded), jnp.asarray(pos[None]),
            jnp.asarray([n], jnp.int32), cache, jnp.asarray(bts[row][None]),
            last_only=True, row_state=state,
            rows=jnp.asarray([row], jnp.int32))
        return np.asarray(logits)[0], cache, state

    for b, n in enumerate(mt.DECODE):
        _, cache, state = one(cache, state, b, draw(n), 0)
    for s, (_, start) in enumerate(plan):
        if start:
            _, cache, state = one(cache, state, B + s, draw(start), 0)
    slices = [draw(n) for n, _ in plan]
    dec_tok, dec_pos = draw(B), np.asarray(mt.DECODE, np.int32)
    active = np.arange(B) < B - 1

    def result(dec, pf, cache, state):
        return {"dec": np.asarray(dec)[active],
                "pf": np.asarray(pf)[:len(plan)],
                "pages": np.asarray(cache["ckv"][:, 1:], np.float32),
                "state": {k: np.asarray(v[:, :B + S], np.float32)
                          for k, v in state.items()}}

    ref_c, ref_s = jax.tree.map(jnp.copy, (cache, state))
    ref_pf = []
    for s, (toks, (_, start)) in enumerate(zip(slices, plan)):
        logits, ref_c, ref_s = one(ref_c, ref_s, B + s, toks, start)
        ref_pf.append(logits)
    ref_dec, ref_c, ref_s = lh.forward_decode(
        params, cfg, jnp.asarray(dec_tok), jnp.asarray(dec_pos), ref_c,
        jnp.asarray(bts[:B]), active=jnp.asarray(active), row_state=ref_s)
    parts = result(ref_dec, np.stack(ref_pf), ref_c, ref_s)

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
    dec, pf, cache, state = lh.forward_mixed(
        params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
        jnp.asarray(bt_dec), jnp.asarray(pf_tok), jnp.asarray(pf_pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bt),
        dec_active=jnp.asarray(live), row_state=state,
        pf_rows=jnp.asarray(rows))
    return parts, result(np.asarray(dec)[:B], pf, cache, state)


@pytest.mark.parametrize("served", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(__import__("mixed_tight").CASES))
def test_the_mixed_step_over_tight_slices_computes_what_the_parts_do(
        tiny, case, served):
    """``tests/mixed_tight.py``'s ``CASES`` — slices full, ending on and
    beside a tile's edge, unused, of one token, continuing a context —
    through ``forward_mixed`` against ``forward_prefill`` +
    ``forward_decode`` over the same pool AND row state: the logits, the
    pages and every row's state, in float32 and in bfloat16 as served
    (where a rounding may swap an expert: the median position is held)."""
    cfg, params, _ = tiny
    atol = 2e-5
    if served:
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        params = lh.init_params(jax.random.PRNGKey(45), cfg)
        atol = 8e-2
    parts, mixed = _both_ways(cfg, params, case)
    for k in ("dec", "pf"):
        gap = np.abs(parts[k] - mixed[k]).max(-1)
        assert (np.median(gap) if served else gap.max()) <= atol, (k, gap)
    assert np.abs(parts["pages"] - mixed["pages"]).max() <= atol
    for k, v in parts["state"].items():
        assert np.abs(v - mixed["state"][k]).max() <= atol, k


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
        forward_prefill=fresh(lh.forward_prefill.__wrapped__),
        forward_decode=fresh(lh.forward_decode.__wrapped__))


def _kda_in_with(change):
    def kda_in(x, kp, i, cfg, _sound=lh._kda_in):
        return change(*_sound(x, kp, i, cfg), x=x, kp=kp, i=i, cfg=cfg)
    return kda_in


def _softplus_decay(qkv, g, b, z, *, x, kp, i, cfg):
    """Kimi Linear's unbounded gate, -exp(A_log) softplus(x W_f + b_f),
    where the safe gate's bounded sigmoid is assumed."""
    f = (jnp.dot(x, kp["wf"][i]) + kp["b_f"][i]).reshape(g.shape)
    return qkv, -jnp.exp(kp["a_log"][i])[:, None] * jax.nn.softplus(f), b, z


def _decay_a_head(qkv, g, b, z, **_):
    return qkv, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape), \
        b, z


def _route_bias_in_the_gates(x, w, bias, *, top_k, scale, n_group,
                             topk_group, **kw):
    from llmq_tpu.ops.moe import _limit
    s = jax.nn.sigmoid(jnp.dot(x, w.astype(jnp.float32))) + bias
    g, experts = jax.lax.top_k(_limit(s, n_group, topk_group), top_k)
    return experts, g / (jnp.sum(g, -1, keepdims=True) + 1e-20) * scale


_CONV_STEP, _CONV_SLICES = lh.conv_step, lh.conv_slices

FAULTS = [
    "no-decay", "softplus-decay-in-place-of-the-safe-gate",
    "decay-a-head-not-a-channel", "no-beta", "no-l2-norm", "no-q-scale",
    "no-convolution", "kda-gate-dropped", "kda-head-norm-dropped",
    "latent-gate-dropped", "latent-at-the-wrong-layers", "groups-unlimited",
    "bias-in-the-gates", "no-renormalisation", "no-route-scale",
    "shared-expert-dropped", "rope-dropped-on-the-latent-layers"]


def _broken(name, monkeypatch, cfg, params):
    """``(cfg, params)`` as a program with that fault would serve them;
    the reference keeps the sound ones."""
    if name == "no-decay":
        monkeypatch.setattr(lh, "_kda_in", _kda_in_with(
            lambda qkv, g, b, z, **_: (qkv, jnp.zeros_like(g), b, z)))
    elif name.startswith("softplus-decay"):
        monkeypatch.setattr(lh, "_kda_in", _kda_in_with(_softplus_decay))
    elif name.startswith("decay-a-head"):
        monkeypatch.setattr(lh, "_kda_in", _kda_in_with(_decay_a_head))
    elif name == "no-beta":
        monkeypatch.setattr(lh, "_kda_in", _kda_in_with(
            lambda qkv, g, b, z, **_: (qkv, g, jnp.ones_like(b), z)))
    elif name == "no-l2-norm":
        monkeypatch.setattr(lh, "l2_norm", lambda x: x)
    elif name == "no-q-scale":
        def heads(y, cfg, _sound=lh._kda_heads):
            q, k, v = _sound(y, cfg)
            return q * cfg.kda_head_dim ** 0.5, k, v
        monkeypatch.setattr(lh, "_kda_heads", heads)
    elif name == "no-convolution":
        monkeypatch.setattr(lh, "conv_step", lambda pool, l, x, w, live: (
            jax.nn.silu(x.astype(jnp.float32)),
            _CONV_STEP(pool, l, x, w, live)[1]))
        monkeypatch.setattr(lh, "conv_slices", lambda win, x, n, w, b: (
            jax.nn.silu(x.astype(jnp.float32)),
            _CONV_SLICES(win, x, n, w, b)[1]))
    elif name == "kda-gate-dropped":
        monkeypatch.setattr(lh, "_kda_in", _kda_in_with(
            lambda qkv, g, b, z, **_: (qkv, g, b, jnp.full_like(z, 1e4))))
    elif name == "kda-head-norm-dropped":
        def out(h, o, z, kp, i, cfg, _sound=lh._kda_out):
            # the gain that undoes the norm a head
            scale = jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                             + cfg.norm_eps)
            return _sound(h, o * scale, z, kp, i, cfg)
        monkeypatch.setattr(lh, "_kda_out", out)
    elif name == "latent-gate-dropped":
        monkeypatch.setattr(latent, "head_gate",
                            lambda cfg, lp, l, x, o: o)
    elif name == "latent-at-the-wrong-layers":
        # K L K in place of K K L: as many layers of each kind, so the
        # same tree serves
        types = (lh.KDA, lh.LATENT, lh.KDA) * 2
        monkeypatch.setattr(lh.LingHybridConfig, "layer_types",
                            property(lambda self: types))
    elif name == "groups-unlimited":
        cfg = dataclasses.replace(cfg, n_group=1, topk_group=1)
    elif name == "bias-in-the-gates":
        monkeypatch.setattr(lh, "route", _route_bias_in_the_gates)
    elif name == "no-renormalisation":
        cfg = dataclasses.replace(cfg, norm_topk_prob=False)
    elif name == "no-route-scale":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif name == "shared-expert-dropped":
        params = {**params, "moe": {**params["moe"], "ws_down": jnp.zeros_like(
            params["moe"]["ws_down"])}}
    elif name == "rope-dropped-on-the-latent-layers":
        monkeypatch.setattr(latent, "apply_rope", lambda x, cos, sin: x)
    else:
        raise AssertionError(name)
    return cfg, params


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_path_is_refused_by_ten_times_the_limit(tiny, monkeypatch,
                                                         fault):
    """Every item of ``assumed`` and every mechanism, taken out of the
    PROGRAM: prefill in slices and decode through state and pool read
    ten times the limit or more against the sound reference."""
    cfg, params, seq = tiny
    bad_cfg, bad_params = _broken(fault, monkeypatch, cfg, params)
    served, rows = serve(bad_cfg, bad_params, seq, (32, 64, 70),
                         fns=_retraced())
    got = verdict(cfg, params, seq, served, rows)
    assert not got["ok"] and got["rms_clean"] > 10 * TOL["rms_clean"], got


def test_a_clamped_layer_int8_and_a_mesh_are_refused_by_name(tiny):
    cfg, _, _ = tiny
    for kw, word in (({"quantization": "int8"}, "model.quantization"),
                     ({"kv_quantization": "int8"}, "model.kv_quantization"),
                     ({"mesh": True}, "executor.mesh")):
        with pytest.raises(ValueError, match=word):
            lh.check_serving(cfg, **kw)
    clamped = dataclasses.replace(cfg, expert_swiglu_limit=(0,) * 5 + (4.0,))
    with pytest.raises(ValueError, match="expert_swiglu_limit"):
        lh.check_serving(clamped)
    with pytest.raises(ValueError, match="shared_swiglu_limit"):
        lh.check_serving(get_config("ling-3.0-flash"))
    # the published limits past the layers held are no one's business
    lh.check_serving(get_config("ling-3.0-flash", n_layers=7))


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


def test_the_executor_carries_row_state_beside_the_latent_pool(tiny):
    """Nothing in the executor takes row state for K/V pages' companion
    or a latent pool for a cache without row state."""
    cfg = tiny[0]
    eng, ex = make_engine(tiny, batch=3)
    assert set(ex.cache) == {"ckv"} and set(ex.row_state) == {"kda", "conv"}
    assert ex.cache["ckv"].shape == (cfg.n_latent, 96, PAGE, 256)
    assert ex.row_state["kda"].shape == (cfg.n_kda, 4, 32, 128)
    per_row = lh.row_state_bytes_per_row(cfg)
    assert ex.row_state_bytes_per_row == per_row == sum(
        x.nbytes for x in jax.tree.leaves(ex.row_state)) // 4
    assert ex.attention_window is None and ex._decode_plan is None
    assert eng.get_stats()["row_state"]["bytes_per_row"] == per_row
    with pytest.raises(ValueError, match="names its sequence's batch row"):
        ex.prefill_async([1, 2, 3], 0, np.zeros(16, np.int32), 0.0)


def test_served_through_the_engine_as_alone(tiny):
    """Requests through ``InferenceEngine`` over the executor's prefill,
    decode-chunk and mixed-chunk programs: a prompt that joins a
    running batch (its slices ride mixed steps) yields the tokens it
    yields alone, and a row that another sequence left is started from a
    zero state; the routed counters fill by the family's layout."""
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
