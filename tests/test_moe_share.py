"""A chip's share of a routed layer (``ops/moe.routed_ffn(held=...)``,
``models/longcat_flash.py``): the router scores every expert, a token
chooses among all, this chip multiplies the pairs whose expert it
holds, adds the zero-compute experts and leaves the rest out. The test
that TIES THE SHARE TO THE MODEL: at a small size (32 experts + 16
zero-compute ones, 4 shares of 8), one routed layer's partial results
over all shares, with the zero-compute part and the dense path counted
once, add up to what the family's plain reference
(``benchmark/families/longcat_flash/reference.py``) gives for the
UNCUT layer; and with everything held and no zero-compute expert
``routed_ffn`` is the call without a share, to the bit."""

import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import contract
from llmq_tpu.models import longcat_flash as lf
from llmq_tpu.ops import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = contract.load_family(
    os.path.join(REPO, "benchmark", "families", "longcat_flash"), "reference")

E, Z, SHARES, K = 32, 16, 4, 6
HELD = E // SHARES
PAGE, T = 8, 40


def model_of(cfg):
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


@pytest.fixture(scope="module")
def uncut():
    cfg = lf.longcat_flash_tiny(
        dtype=jnp.float32, max_seq_len=64, n_layers=1, n_routed_experts=E,
        zero_expert_num=Z, n_experts_per_tok=K)
    params = lf.init_params(jax.random.PRNGKey(2), cfg)
    params["moe"]["router_bias"] = 0.005 * jax.random.normal(
        jax.random.PRNGKey(3), params["moe"]["router_bias"].shape)
    seq = np.random.default_rng(2).integers(3, cfg.vocab_size, T,
                                            dtype=np.int32)
    return cfg, params, seq


def share_of(cfg, params, s):
    """(cfg, params) as chip ``s`` of ``SHARES`` holds them: its
    experts' matrices alone, everything else whole."""
    lo, hi = s * HELD, (s + 1) * HELD
    m = dict(params["moe"])
    m["we_gate_up"] = tuple(w[lo:hi] for w in m["we_gate_up"])
    m["we_down"] = tuple(w[lo:hi] for w in m["we_down"])
    return (dataclasses.replace(cfg, held_experts=(lo, hi)),
            {**params, "moe": m})


def all_positions(fns, cfg, params, seq):
    mp = cfg.max_seq_len // PAGE
    cache = lf.init_kv_pages(cfg, 1 + mp, PAGE)
    logits, _, st = fns.forward_prefill(
        params, cfg, jnp.asarray(seq[None]),
        jnp.arange(len(seq), dtype=jnp.int32)[None],
        jnp.asarray([len(seq)], jnp.int32), cache,
        jnp.arange(1, 1 + mp, dtype=jnp.int32)[None], stats=True)
    return np.asarray(logits)[0], np.asarray(st)


class _Unjitted:
    forward_prefill = staticmethod(lf.forward_prefill.__wrapped__)


def test_the_shares_add_up_to_the_uncut_layer(uncut, monkeypatch):
    """The model's layer with its routed part replaced by the SUM of
    the four shares' partial results (each chip's held pairs; the
    zero-compute experts, which every chip would add for its own rows,
    once; both attentions and both dense SwiGLUs, data-parallel, once)
    against the reference's uncut layer, which loops over all 32
    experts."""
    cfg, params, seq = uncut
    slots = []

    def routed_by_all_shares(params, cfg, l, u, live):
        experts, gates = moe.route(
            u, params["moe"]["router"][l], params["moe"]["router_bias"][l],
            top_k=K, scale=cfg.routed_scaling_factor, norm_topk=False,
            scoring="softmax")
        total, stats = 0.0, []
        for s in range(SHARES):
            scfg, sp = share_of(cfg, params, s)
            y, st = moe.routed_ffn(
                u, experts, gates, sp["moe"]["we_gate_up"][l],
                sp["moe"]["we_down"][l], live, held=scfg.held, n_routed=E)
            total = total + y
            stats.append(st)
        slots.append(stats)
        zero = moe.identity_gate(experts, gates, E, live)
        return total + zero[:, None] * u, stats[0]

    monkeypatch.setattr(lf, "_routed", routed_by_all_shares)
    served, _ = all_positions(_Unjitted, cfg, params, seq)
    ref, _ = reference.reference_forward(params, seq, model_of(cfg),
                                         np.arange(T))
    rms = np.sqrt(np.mean((served - np.asarray(ref)) ** 2, -1))
    assert rms.max() < 2e-5, rms.max()
    # every slot is held by exactly one share or is zero-compute
    stats = np.asarray(slots[0])
    pairs, zero, away = (stats[:, :HELD].sum(-1), stats[:, HELD + 1],
                         stats[:, HELD + 2])
    assert np.all(zero == zero[0]) and zero[0] > 0
    assert np.all(pairs + zero + away == T * K)
    assert pairs.sum() + zero[0] == T * K and np.all(pairs > 0)


@pytest.mark.parametrize("s", range(SHARES))
def test_a_share_alone_is_its_reference_s_partial_result(uncut, s):
    """Chip ``s``'s served logits against the reference given the same
    share: what the absent experts would have added is left out in
    both, and that partial result goes on to the head."""
    cfg, params, seq = uncut
    scfg, sp = share_of(cfg, params, s)
    served, st = all_positions(lf, scfg, sp, seq)
    ref, _ = reference.reference_forward(sp, seq, model_of(scfg),
                                         np.arange(T))
    rms = np.sqrt(np.mean((served - np.asarray(ref)) ** 2, -1))
    assert rms.max() < 2e-5, rms.max()
    whole, _ = reference.reference_forward(params, seq, model_of(cfg),
                                           np.arange(T))
    # ... and it is NOT the uncut layer's: the share matters
    assert np.sqrt(np.mean((served - np.asarray(whole)) ** 2)) > 1e-3
    layout = lf.step_stats_layout(scfg)
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
