"""A chip's share of a routed layer (``ops/moe.routed_ffn(held=...)``,
``models/longcat_flash.py``, ``models/afmoe.py``,
``models/ling_hybrid.py``, ``models/solar_open2.py``): the router scores
every expert, a token chooses among all, this chip multiplies the pairs
whose expert it holds, adds what a token's home chip adds (LongCat's
zero-compute experts, afmoe's shared expert) and leaves the rest out.
The test that TIES THE SHARE TO THE MODEL, one case a family that
serves a share (``FAMILIES``): at a small size (LongCat: 32 experts + 16
zero-compute ones in 4 shares of 8; afmoe: 32 experts in 16 shares of 2
beside a shared expert; ling_hybrid: 32 experts in 4 GROUPS of 8, the
best 2 groups kept, in 4 shares of 8 — a share a group — beside a shared
expert; solar_open2: 32 experts in EIGHT shares of 4, no groups, beside
a shared expert counted once), one routed layer's partial results over all
shares, with the home chip's part and the dense path counted once, add
up to what the family's plain reference
(``benchmark/families/<family>/reference.py``) gives for the UNCUT
layer; and with everything held and no zero-compute expert
``routed_ffn`` is the call without a share, to the bit."""

import dataclasses
import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import contract
from llmq_tpu.models import afmoe as am
from llmq_tpu.models import ling_hybrid as lh
from llmq_tpu.models import longcat_flash as lf
from llmq_tpu.models import solar_open2 as so
from llmq_tpu.ops import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

E, Z, SHARES, K = 32, 16, 4, 6
HELD = E // SHARES
PAGE, T = 8, 40


def _reference(family):
    return contract.load_family(
        os.path.join(REPO, "benchmark", "families", family), "reference")


# -- LongCat-Flash: 4 shares of 8, zero-compute experts at home ----------------

def _lf_model_of(cfg):
    """``cfg`` under the benchmark file's keys: what the reference
    reads, with the share as ``expert_share`` states it."""
    lo, hi = cfg.held
    return {"num_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "q_lora_rank": cfg.q_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "mla_scale_q_lora": cfg.mla_scale_q_lora,
            "mla_scale_kv_lora": cfg.mla_scale_kv_lora,
            "n_routed_experts": hi - lo, "router_experts": E,
            "expert_share": {"chips": E // (hi - lo),
                             "index": lo // (hi - lo)},
            "zero_expert_num": Z, "moe_topk": K,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


def _lf_uncut():
    return lf.longcat_flash_tiny(
        dtype=jnp.float32, max_seq_len=64, n_layers=1, n_routed_experts=E,
        zero_expert_num=Z, n_experts_per_tok=K)


def _lf_all_positions(fns, cfg, params, seq):
    mp = cfg.max_seq_len // PAGE
    cache = lf.init_kv_pages(cfg, 1 + mp, PAGE)
    logits, _, st = fns.forward_prefill(
        params, cfg, jnp.asarray(seq[None]),
        jnp.arange(len(seq), dtype=jnp.int32)[None],
        jnp.asarray([len(seq)], jnp.int32), cache,
        jnp.arange(1, 1 + mp, dtype=jnp.int32)[None], stats=True)
    return np.asarray(logits)[0], np.asarray(st)


def _lf_all_shares(fam, monkeypatch, slots):
    """``lf._routed`` as the SUM of the shares' partial results: each
    chip's held pairs; the zero-compute experts, which every chip would
    add for its own rows, once."""
    def routed_by_all_shares(params, cfg, l, u, live):
        experts, gates = moe.route(
            u, params["moe"]["router"][l], params["moe"]["router_bias"][l],
            top_k=K, scale=cfg.routed_scaling_factor, norm_topk=False,
            scoring="softmax")
        total, stats = 0.0, []
        for s in range(fam.shares):
            scfg, sp = share_of(fam, cfg, params, s)
            y, st = moe.routed_ffn(
                u, experts, gates, sp["moe"]["we_gate_up"][l],
                sp["moe"]["we_down"][l], live, held=scfg.held, n_routed=E)
            total = total + y
            stats.append(st)
        slots.append(stats)
        zero = moe.identity_gate(experts, gates, E, live)
        return total + zero[:, None] * u, stats[0]

    monkeypatch.setattr(lf, "_routed", routed_by_all_shares)


# -- afmoe: 16 shares of 2, the shared expert at home --------------------------

def _am_model_of(cfg):
    lo, hi = cfg.held
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "layer_types": list(cfg.layer_types),
            "sliding_window": cfg.sliding_window,
            "num_dense_layers": cfg.n_dense_layers,
            "num_experts": hi - lo, "router_experts": E,
            "expert_share": {"chips": E // (hi - lo),
                             "index": lo // (hi - lo)},
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "route_scale": cfg.route_scale, "route_norm": cfg.route_norm,
            "mup_enabled": cfg.mup_enabled, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta}


def _am_uncut():
    """One routed layer (a sliding one: the window of 24 is shorter
    than the 40 tokens), 32 experts with 4 a token, all held."""
    return am.bind_cache(
        am.afmoe_tiny(dtype=jnp.float32, max_seq_len=64,
                      layer_types=(am.SLIDING,), n_dense_layers=0,
                      n_routed_experts=E, n_experts_per_tok=4),
        page_size=PAGE, step_tokens=T)


def _am_all_positions(fns, cfg, params, seq):
    mp = cfg.max_seq_len // PAGE
    cache = am.init_kv_pages(cfg, 1 + mp, PAGE)
    logits, _, _, st = fns.forward_prefill(
        params, cfg, jnp.asarray(seq[None]),
        jnp.arange(len(seq), dtype=jnp.int32)[None],
        jnp.asarray([len(seq)], jnp.int32), cache,
        jnp.arange(1, 1 + mp, dtype=jnp.int32)[None], stats=True)
    return np.asarray(logits)[0], np.asarray(st)


def _am_all_shares(fam, monkeypatch, slots):
    """``am.routed_ffn`` (called with every expert's matrices: the
    uncut configuration) as the SUM of the sixteen shares' partial
    results; the shared expert is ``am._ffn``'s own, computed once."""
    def routed_by_all_shares(x, experts, gates, w_gate_up, w_down, live, *,
                             held, n_routed):
        assert held == (0, E) and n_routed == E
        total, stats = 0.0, []
        for s in range(fam.shares):
            lo, hi = s * fam.held, (s + 1) * fam.held
            y, st = moe.routed_ffn(x, experts, gates, w_gate_up[lo:hi],
                                   w_down[lo:hi], live, held=(lo, hi),
                                   n_routed=E)
            total = total + y
            stats.append(st)
        slots.append(stats)
        load = jnp.concatenate([st[:fam.held] for st in stats])
        touched = sum(st[fam.held] for st in stats)
        return total, jnp.concatenate([load, touched[None]])

    monkeypatch.setattr(am, "routed_ffn", routed_by_all_shares)


# -- ling_hybrid: 4 shares of 8, a group-limited router, the shared expert -----

def _lh_model_of(cfg):
    lo, hi = cfg.held
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "num_attention_heads": cfg.n_heads,
            "layer_group_size": cfg.layer_group_size,
            "first_k_dense_replace": cfg.first_k_dense,
            "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": None,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "kda_lower_bound": cfg.kda_lower_bound,
            "num_experts": hi - lo, "router_experts": E,
            "expert_share": {"chips": E // (hi - lo),
                             "index": lo // (hi - lo)},
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk_prob": cfg.norm_topk_prob,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


def _lh_uncut():
    """One routed layer behind a KDA mixer, 32 experts in 4 groups of 8
    with the top 4 of the best 2 groups, all held."""
    return lh.ling_hybrid_tiny(
        dtype=jnp.float32, max_seq_len=64, n_layers=1, first_k_dense=0,
        n_routed_experts=E, n_experts_per_tok=4, n_group=4, topk_group=2)


def _lh_all_positions(fns, cfg, params, seq):
    mp = cfg.max_seq_len // PAGE
    cache = lh.init_kv_pages(cfg, 1 + mp, PAGE)
    logits, _, _, st = fns.forward_prefill(
        params, cfg, jnp.asarray(seq[None]),
        jnp.arange(len(seq), dtype=jnp.int32)[None],
        jnp.asarray([len(seq)], jnp.int32), cache,
        jnp.arange(1, 1 + mp, dtype=jnp.int32)[None], stats=True)
    return np.asarray(logits)[0], np.asarray(st)


def _lh_all_shares(fam, monkeypatch, slots):
    """``lh.routed_ffn`` as the SUM of the four shares' partial results
    (``_am_all_shares``); the shared expert is ``lh._ffn``'s own, once."""
    _am_all_shares(fam, monkeypatch, slots)
    monkeypatch.setattr(lh, "routed_ffn", am.routed_ffn)


# -- solar_open2: 8 shares of 4, no groups, the shared expert once ---------------

def _so_model_of(cfg):
    lo, hi = cfg.held
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "gqa_layers": list(cfg.gqa_layers),
            "linear_attn_config": {
                "short_conv_kernel_size": cfg.kda_conv,
                "head_dim": cfg.kda_head_dim, "num_heads": cfg.kda_heads,
                "num_kv_heads": None},
            "n_routed_experts": hi - lo, "router_experts": E,
            "expert_share": {"chips": E // (hi - lo),
                             "index": lo // (hi - lo)},
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk_prob": cfg.norm_topk_prob,
            "rms_norm_eps": cfg.norm_eps, "vocab_size": cfg.vocab_size}


def _so_uncut():
    """One routed layer behind a KDA mixer, 32 experts with 4 a token,
    all held (the deployment's eight shares are of 4 each here)."""
    return so.solar_open2_tiny(
        dtype=jnp.float32, max_seq_len=64, n_layers=1, gqa_layers=(),
        n_routed_experts=E, n_experts_per_tok=4)


def _so_all_positions(fns, cfg, params, seq):
    mp = cfg.max_seq_len // PAGE
    cache = so.init_kv_pages(cfg, 1 + mp, PAGE)
    logits, _, _, st = fns.forward_prefill(
        params, cfg, jnp.asarray(seq[None]),
        jnp.arange(len(seq), dtype=jnp.int32)[None],
        jnp.asarray([len(seq)], jnp.int32), cache,
        jnp.arange(1, 1 + mp, dtype=jnp.int32)[None], stats=True)
    return np.asarray(logits)[0], np.asarray(st)


def _so_all_shares(fam, monkeypatch, slots):
    """``so.routed_ffn`` as the SUM of the eight shares' partial results
    (``_am_all_shares``); the shared expert is ``so._ffn``'s own, once."""
    _am_all_shares(fam, monkeypatch, slots)
    monkeypatch.setattr(so, "routed_ffn", am.routed_ffn)


FAMILIES = {
    "solar_open2": SimpleNamespace(
        name="solar_open2", mod=so, shares=8, held=E // 8, k=4,
        uncut=_so_uncut, model_of=_so_model_of, bias=0.01,
        all_positions=_so_all_positions, all_shares=_so_all_shares,
        zero_at=None, away_at=E // 8 + 2),
    "ling_hybrid": SimpleNamespace(
        name="ling_hybrid", mod=lh, shares=SHARES, held=HELD, k=4,
        uncut=_lh_uncut, model_of=_lh_model_of, bias=0.01,
        all_positions=_lh_all_positions, all_shares=_lh_all_shares,
        zero_at=None, away_at=HELD + 2),
    "longcat_flash": SimpleNamespace(
        name="longcat_flash", mod=lf, shares=SHARES, held=HELD, k=K,
        uncut=_lf_uncut, model_of=_lf_model_of, bias=0.005,
        all_positions=_lf_all_positions, all_shares=_lf_all_shares,
        zero_at=HELD + 1, away_at=HELD + 2),
    "afmoe": SimpleNamespace(
        name="afmoe", mod=am, shares=16, held=E // 16, k=4,
        uncut=_am_uncut, model_of=_am_model_of, bias=0.01,
        all_positions=_am_all_positions, all_shares=_am_all_shares,
        zero_at=None, away_at=E // 16 + 2),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def uncut(request):
    fam = FAMILIES[request.param]
    cfg = fam.uncut()
    params = fam.mod.init_params(jax.random.PRNGKey(2), cfg)
    params["moe"]["router_bias"] = fam.bias * jax.random.normal(
        jax.random.PRNGKey(3), params["moe"]["router_bias"].shape)
    seq = np.random.default_rng(2).integers(3, cfg.vocab_size, T,
                                            dtype=np.int32)
    return fam, cfg, params, seq


def share_of(fam, cfg, params, s):
    """(cfg, params) as chip ``s`` of the family's shares holds them:
    its experts' matrices alone, everything else whole."""
    lo, hi = s * fam.held, (s + 1) * fam.held
    m = dict(params["moe"])
    m["we_gate_up"] = tuple(w[lo:hi] for w in m["we_gate_up"])
    m["we_down"] = tuple(w[lo:hi] for w in m["we_down"])
    return (dataclasses.replace(cfg, held_experts=(lo, hi)),
            {**params, "moe": m})


def _unjitted(fam):
    return SimpleNamespace(
        forward_prefill=fam.mod.forward_prefill.__wrapped__)


def test_the_shares_add_up_to_the_uncut_layer(uncut, monkeypatch):
    """The model's layer with its routed part replaced by the SUM of
    the shares' partial results (each chip's held pairs; what a token's
    home chip adds — the zero-compute experts, the shared expert —
    once; the attention and the dense paths, data-parallel, once)
    against the reference's uncut layer, which loops over all 32
    experts."""
    fam, cfg, params, seq = uncut
    slots = []
    fam.all_shares(fam, monkeypatch, slots)
    served, _ = fam.all_positions(_unjitted(fam), cfg, params, seq)
    ref, _ = _reference(fam.name).reference_forward(
        params, seq, fam.model_of(cfg), np.arange(T))
    rms = np.sqrt(np.mean((served - np.asarray(ref)) ** 2, -1))
    assert rms.max() < 2e-5, rms.max()
    # every slot is held by exactly one share or is zero-compute
    stats = np.asarray(slots[0])
    pairs, away = stats[:, :fam.held].sum(-1), stats[:, fam.away_at]
    zero = (stats[:, fam.zero_at] if fam.zero_at is not None
            else np.zeros_like(pairs))
    assert np.all(zero == zero[0]) and (zero[0] > 0) == (
        fam.zero_at is not None)
    assert np.all(pairs + zero + away == T * fam.k)
    assert pairs.sum() + zero[0] == T * fam.k and np.all(pairs > 0)


@pytest.mark.parametrize("s", range(SHARES))
def test_a_share_alone_is_its_reference_s_partial_result(uncut, s):
    """Chip ``s``'s served logits against the reference given the same
    share: what the absent experts would have added is left out in
    both, and that partial result goes on to the head."""
    fam, cfg, params, seq = uncut
    reference = _reference(fam.name)
    scfg, sp = share_of(fam, cfg, params, s)
    served, st = fam.all_positions(fam.mod, scfg, sp, seq)
    ref, _ = reference.reference_forward(sp, seq, fam.model_of(scfg),
                                         np.arange(T))
    rms = np.sqrt(np.mean((served - np.asarray(ref)) ** 2, -1))
    assert rms.max() < 2e-5, rms.max()
    whole, _ = reference.reference_forward(params, seq, fam.model_of(cfg),
                                           np.arange(T))
    # ... and it is NOT the uncut layer's: the share matters
    assert np.sqrt(np.mean((served - np.asarray(whole)) ** 2)) > 1e-3
    layout = fam.mod.step_stats_layout(scfg)
    assert st[layout["runs"]] == 1 and st[layout["away_slots"]] > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_everything_held_and_no_zero_expert_is_the_old_call_to_the_bit(
        dtype):
    """The sibling family's call (all experts held, none zero-compute,
    sigmoid scores, renormalised) takes the path it took before there
    were shares: the same result and counters whether or not the share
    is spelt out."""
    N, D, F, k = 50, 64, 32, 6
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((N, D)), dtype)
    w_r = jnp.asarray(rng.standard_normal((D, E)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(E) * 0.02, jnp.float32)
    w_gu = jnp.asarray(rng.standard_normal((E, D, 2 * F)) * 0.2, dtype)
    w_d = jnp.asarray(rng.standard_normal((E, F, D)) * 0.2, dtype)
    live = jnp.asarray(rng.random(N) > 0.2)
    experts, gates = moe.route(x, w_r, bias, top_k=k, scale=2.448)
    y0, s0 = moe.routed_ffn(x, experts, gates, w_gu, w_d, live)
    y1, s1 = moe.routed_ffn(x, experts, gates, w_gu, w_d, live,
                            held=(0, E), n_routed=E)
    assert np.array_equal(np.asarray(y0, np.float32),
                          np.asarray(y1, np.float32))
    assert np.array_equal(np.asarray(s0), np.asarray(s1))
    assert s0.shape == (E + 1,)
    with pytest.raises(ValueError, match="32 experts' matrices"):
        moe.routed_ffn(x, experts, gates, w_gu, w_d, live, held=(0, 8))


def _loop_over_held(x, experts, gates, w_gate_up, w_down, lo, live):
    F = w_down.shape[1]
    y = np.zeros_like(x)
    for n in range(x.shape[0]):
        for e, g in zip(experts[n], gates[n]):
            if live[n] and lo <= e < lo + w_down.shape[0]:
                gu = x[n] @ w_gate_up[e - lo]
                a = gu[:F] / (1 + np.exp(-gu[:F])) * gu[F:]
                y[n] += g * (a @ w_down[e - lo])
    return y


@pytest.mark.parametrize("case", ["one-block", "three-blocks",
                                  "no-held-pair", "bias-chooses"])
def test_a_share_against_a_loop_over_its_experts(case):
    """The softmax router by the published rule, and the held pairs'
    sum a block of sorted pairs at a time — none, one block, several —
    against a loop over the held experts."""
    D, F, k, lo = 32, 16, 6, 8
    N = {"three-blocks": 260}.get(case, 20)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((N, D)).astype(np.float32)
    w_r = (rng.standard_normal((D, E + Z)) * 0.3).astype(np.float32)
    bias = np.zeros(E + Z, np.float32)
    if case == "bias-chooses":
        bias = (rng.standard_normal(E + Z) * 0.05).astype(np.float32)
    elif case == "no-held-pair":
        bias[lo:lo + HELD] = -1.0
    elif case == "three-blocks":
        bias[lo:lo + HELD] = 0.05        # most tokens choose held experts
    w_gu = (rng.standard_normal((HELD, D, 2 * F)) * 0.2).astype(np.float32)
    w_d = (rng.standard_normal((HELD, F, D)) * 0.2).astype(np.float32)
    experts, gates = moe.route(jnp.asarray(x), jnp.asarray(w_r),
                               jnp.asarray(bias), top_k=k, scale=6.0,
                               norm_topk=False, scoring="softmax")
    experts, gates = np.asarray(experts), np.asarray(gates)
    logits = x @ w_r
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-(p + bias), axis=-1, kind="stable")[:, :k]
    assert np.array_equal(np.sort(experts, -1), np.sort(want, -1))
    np.testing.assert_allclose(
        gates, 6.0 * np.take_along_axis(p, experts, -1), rtol=2e-5)
    live = rng.random(N) > 0.15
    y, stats = moe.routed_ffn(
        jnp.asarray(x), jnp.asarray(experts), jnp.asarray(gates),
        jnp.asarray(w_gu), jnp.asarray(w_d), jnp.asarray(live),
        held=(lo, lo + HELD), n_routed=E)
    ref = _loop_over_held(x, experts, gates, w_gu, w_d, lo, live)
    np.testing.assert_allclose(np.asarray(y), ref, atol=3e-5)
    chosen = experts[live].reshape(-1)
    counts = np.bincount(chosen[(chosen >= lo) & (chosen < lo + HELD)] - lo,
                         minlength=HELD)
    stats = np.asarray(stats)
    assert np.array_equal(stats[:HELD], counts)
    assert stats[HELD] == (counts > 0).sum()
    assert stats[HELD + 1] == (chosen >= E).sum()
    assert stats[HELD + 2] == len(chosen) - counts.sum() - (chosen >= E).sum()
    if case == "no-held-pair":
        assert counts.sum() == 0 and not np.asarray(y).any()
    if case == "three-blocks":
        assert counts.sum() > 2 * moe.HELD_BLOCK
    zero = np.asarray(moe.identity_gate(
        jnp.asarray(experts), jnp.asarray(gates), E, jnp.asarray(live)))
    np.testing.assert_allclose(
        zero, np.where(experts >= E, gates, 0).sum(-1) * live, rtol=1e-6)


# -- ``route`` split into its scores and ``choose`` (PR 48) ----------------------


def _route_before_the_split(x, w_router, bias, *, top_k, scale,
                            norm_topk=True, n_group=1, topk_group=1,
                            scoring="sigmoid"):
    """``ops/moe.route`` as it stood at the parent of the PR that split
    it, body for body: the scores and the choice in one function."""
    from jax import lax
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    s = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    _, experts = lax.top_k(moe._limit(s + bias, n_group, topk_group), top_k)
    g = jnp.take_along_axis(s, experts, axis=-1)
    if norm_topk:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), g * scale


#: caller -> the keywords its ``route`` call passes (``models/*.py``)
ROUTE_CALLERS = {
    "deepseek_v3": dict(top_k=6, scale=2.448, norm_topk=True),
    "longcat_flash": dict(top_k=12, scale=6.0, norm_topk=False,
                          scoring="softmax"),
    "afmoe": dict(top_k=4, scale=2.826, norm_topk=True, scoring="sigmoid"),
    "ling_hybrid": dict(top_k=8, scale=2.5, norm_topk=True, n_group=8,
                        topk_group=4),
    "kda-pinned-defaults": dict(top_k=4, scale=2.5),
}


@pytest.mark.parametrize("caller", sorted(ROUTE_CALLERS))
def test_choose_behind_route_gives_its_callers_what_route_gave(caller):
    """Experts and gates to the BIT, jitted and not, for the keywords
    each routed family calls ``route`` with; and ``choose`` of the same
    scores is ``route``'s choice (one choice, whoever made the scores)."""
    kw = ROUTE_CALLERS[caller]
    rng = np.random.default_rng(48)
    x = jnp.asarray(rng.normal(size=(96, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 64)) / 8, jnp.bfloat16)
    bias = jnp.asarray(rng.uniform(-0.02, 0.02, size=(64,)), jnp.float32)
    for wrap in (lambda f: f, jax.jit):
        new = wrap(lambda x, w, b: moe.route(x, w, b, **kw))(x, w, bias)
        old = wrap(lambda x, w, b: _route_before_the_split(
            x, w, b, **kw))(x, w, bias)
        for got, want in zip(new, old):
            assert got.dtype == want.dtype
            assert (np.asarray(got) == np.asarray(want)).all()
    from jax import lax
    logits = jnp.dot(x, w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, -1) if kw.get("scoring") == "softmax"
              else jax.nn.sigmoid(logits))
    rest = {k: v for k, v in kw.items() if k != "scoring"}
    for got, want in zip(moe.choose(scores, bias, **rest), new):
        assert (np.asarray(got) == np.asarray(want)).all()


# -- what the routed families share of a pass's counters (PR 52) ---------------

@pytest.mark.parametrize("form", ["all-held", "a-share"])
def test_a_layer_s_counters_in_the_layout_the_families_state(form):
    """``routed_ffn`` counts (load, touched) with every expert held and
    (load, touched, zero, away) for a share: a family's
    ``step_stats_layout`` has (load, touched, away) in both."""
    load = jnp.asarray([3, 0, 2, 1], jnp.int32)
    st = (jnp.concatenate([load, jnp.asarray([3], jnp.int32)])
          if form == "all-held" else
          jnp.concatenate([load, jnp.asarray([3, 7, 5], jnp.int32)]))
    got = np.asarray(moe.share_counts(st, 4))
    assert got.tolist() == [3, 0, 2, 1, 3, 0 if form == "all-held" else 5]


@pytest.mark.parametrize("flags", [(False, False), (True, False),
                                   (False, True), (True, True)])
def test_a_pass_s_extras_skip_the_dense_layers(flags):
    stats, chosen = flags
    st = jnp.asarray([1, 2, 2, 0], jnp.int32)
    ex = jnp.zeros((5, 2), jnp.int32)
    out = moe.pass_extras([(None, None), (st, ex), (st, ex + 1)], 4, stats,
                          chosen)
    assert len(out) == stats + chosen
    if stats:
        assert np.asarray(out[0]).tolist() == [2, 4, 4, 0, 2]
    if chosen:
        assert out[-1].shape == (2, 5, 2) and int(out[-1][1].min()) == 1


def test_a_caller_without_row_state_gets_a_zero_one_of_its_batch():
    from llmq_tpu.ops.ssm import own_rows
    made = []

    def init(batch):
        made.append(batch)
        return {"s": jnp.zeros((batch + 1, 2))}

    state, rows = own_rows(init, 3, None, None)
    assert made == [3] and np.asarray(rows).tolist() == [0, 1, 2]
    mine, given = {"s": jnp.ones((4, 2))}, jnp.asarray([2, 0], jnp.int32)
    state, rows = own_rows(init, 2, mine, given)
    assert state is mine and rows is given and made == [3]


def test_the_decode_geometry_of_rows_that_stay_by_batch_row():
    """A row that is not active writes to page 0 and attends to nothing;
    off the TPU no order is made and the rows stay as they came."""
    from llmq_tpu.ops.attention import decode_geometry
    pools = (jnp.zeros((1, 9, 8, 16)),) * 2
    tables = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    positions = jnp.asarray([9, 3, 15], jnp.int32)
    active = jnp.asarray([True, False, True])
    bts, page_of, slot_of, seq_lens, order = decode_geometry(
        positions, tables, active, pools, 16)
    assert order is None and bts is tables
    assert np.asarray(page_of).tolist() == [2, 0, 6]
    assert np.asarray(slot_of).tolist() == [1, 3, 7]
    assert np.asarray(seq_lens).tolist() == [10, 0, 16]
    *_, seq_lens, _ = decode_geometry(positions, tables, None, pools, 16)
    assert np.asarray(seq_lens).tolist() == [10, 4, 16]
