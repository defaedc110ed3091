"""The LongCat-Flash block (``models/longcat_flash.py``: a
shortcut-connected double layer — two latent attentions, two dense
SwiGLUs, a routed layer beside them — with a softmax router over real
and zero-compute experts, serving ONE CHIP'S SHARE of the experts) held
to its family's plain float32 reference
(``benchmark/families/longcat_flash/reference.py``, which shares no
code with ``llmq_tpu``) at a tiny width, on seeded weights.

Logits, never tokens. The weights here are float32, so the served path
differs from the reference by float32 rounding alone and the comparison
is tight (``TOL``): each of the broken paths below — the things the
tolerance on the chip cannot see — moves the logits by ten to ten
thousand times that. The tiny model holds experts 8-15 of 16 real ones
beside 8 zero-compute ones, so every kind of slot occurs: held, held
elsewhere, zero-compute.
"""

import dataclasses
import os

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
from llmq_tpu.models import latent
from llmq_tpu.models import longcat_flash as lf
from llmq_tpu.ops import moe
from llmq_tpu.ops.rows import pack_grid
from mixed_tight import (CASES, check, check_served,  # noqa: F401
                         tight_step)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(REPO, "benchmark", "families", "longcat_flash")
reference = contract.load_family(FAMILY, "reference")

PAGE, BUCKET = 8, 48
#: float32 against float32: measured 2e-6 to 6e-6 here; the mildest
#: broken path gives 1.6e-3.
TOL = {"clean_quantile": 0.25, "rms_clean": 1e-4, "rms": 1e-4,
       "margin_eps": 1e-7}


def hf_model(cfg):
    """The configuration under the public ``config.json``'s keys, with
    the share as the benchmark's file states it: what the reference
    reads."""
    lo, hi = cfg.held
    return {"num_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "q_lora_rank": cfg.q_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "mla_scale_q_lora": cfg.mla_scale_q_lora,
            "mla_scale_kv_lora": cfg.mla_scale_kv_lora,
            "n_routed_experts": hi - lo,
            "router_experts": cfg.n_routed_experts,
            "expert_share": {"chips": cfg.n_routed_experts // (hi - lo),
                             "index": lo // (hi - lo)},
            "zero_expert_num": cfg.zero_expert_num,
            "zero_expert_type": "identity", "moe_topk": cfg.n_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


@pytest.fixture(scope="module")
def tiny():
    cfg = lf.longcat_flash_tiny(dtype=jnp.float32, max_seq_len=128,
                                held_experts=(8, 16))
    params = lf.init_params(jax.random.PRNGKey(34), cfg)
    # A selection bias that is not zero: one that the weights must not see.
    params["moe"]["router_bias"] = 0.01 * jax.random.normal(
        jax.random.PRNGKey(5), params["moe"]["router_bias"].shape)
    seq = np.random.default_rng(34).integers(3, cfg.vocab_size, 44,
                                             dtype=np.int32)
    return cfg, params, seq


def block_table(cfg, n_rows=1):
    mp = cfg.max_seq_len // PAGE
    return (1 + np.arange(n_rows)[:, None] * mp
            + np.arange(mp)[None, :]).astype(np.int32)


def prefill(fns, cfg, params, cache, bt, seq, start, end):
    """One bucket-padded prefill of seq[start:end] at its absolute
    positions; the last valid position's logits."""
    n = end - start
    toks = np.zeros((1, BUCKET), np.int32)
    toks[0, :n] = seq[start:end]
    pos = start + np.minimum(np.arange(BUCKET, dtype=np.int32), n - 1)[None]
    logits, cache = fns.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([n], jnp.int32), cache, jnp.asarray(bt),
        last_only=True)
    return np.asarray(logits)[0], cache


def serve(cfg, params, seq, cuts, fns=lf, cache_dtype=None):
    """Prefill seq[:cuts[-1]] in the chunks ``cuts`` bounds, then
    teacher-forced decode steps through the latent cache to the end of
    ``seq``. Returns the logits at positions cuts[-1] - 1 ..
    len(seq) - 1 and those positions."""
    bt = block_table(cfg)
    cache = lf.init_kv_pages(cfg, 1 + bt.shape[1], PAGE, dtype=cache_dtype)
    out, start = [], 0
    for end in cuts:
        logits, cache = prefill(fns, cfg, params, cache, bt, seq, start, end)
        start = end
    out.append(logits)
    for p in range(cuts[-1], len(seq)):
        logits, cache = fns.forward_decode(
            params, cfg, jnp.asarray(seq[p:p + 1]),
            jnp.asarray([p], jnp.int32), cache, jnp.asarray(bt))
        out.append(np.asarray(logits)[0])
    return np.stack(out), list(range(cuts[-1] - 1, len(seq)))


def verdict(cfg, params, seq, served, rows):
    ref, margins = reference.reference_forward(params, seq, hf_model(cfg),
                                               rows)
    return reference.judge(served, np.asarray(ref), np.asarray(margins), TOL)


# -- the served path against the reference ------------------------------------


@pytest.mark.parametrize("cuts", [(40,), (17, 40), (8, 16, 39)],
                         ids=["one-prefill", "continuation",
                              "three-chunks-mid-page"])
def test_prefill_then_decode_through_the_doubled_cache(tiny, cuts):
    cfg, params, seq = tiny
    served, rows = serve(cfg, params, seq, cuts)
    got = verdict(cfg, params, seq, served, rows)
    assert got["ok"] and got["near_tie_share"] == 0, got


def test_a_mixed_step(tiny):
    """Two decode rows one token and two prompt slices (one of them a
    continuation over cached pages) in one fused step, the slices'
    tokens and the rows side by side in every projection, against the
    reference's full forward pass of each."""
    cfg, params, seq = tiny
    other = np.random.default_rng(7).integers(3, cfg.vocab_size, 30,
                                              dtype=np.int32)
    bt = block_table(cfg, 4)
    cache = lf.init_kv_pages(cfg, 1 + 4 * bt.shape[1], PAGE)
    _, cache = prefill(lf, cfg, params, cache, bt[0:1], seq, 0, 20)
    _, cache = prefill(lf, cfg, params, cache, bt[1:2], other, 0, 11)
    _, cache = prefill(lf, cfg, params, cache, bt[3:4], other, 0, 9)
    pf_tok = np.zeros((2, BUCKET), np.int32)
    pf_tok[0, :25], pf_tok[1, :14] = seq[:25], other[9:23]
    pf_pos = np.stack([np.minimum(np.arange(BUCKET), 24),
                       9 + np.minimum(np.arange(BUCKET), 13)]).astype(
                           np.int32)
    dec, pf, cache, st = lf.forward_mixed(
        params, cfg, jnp.asarray([seq[20], other[11], 0]),
        jnp.asarray([20, 11, 0], jnp.int32), cache, jnp.asarray(bt[:3]),
        *map(jnp.asarray, pack_grid(pf_tok, pf_pos, [25, 14])[:2]),
        jnp.asarray([25, 14], jnp.int32), jnp.asarray([0, 25, 39], jnp.int32),
        jnp.asarray(bt[2:4]), dec_active=jnp.asarray([True, True, False]),
        stats=True)
    assert pf.shape == (2, cfg.vocab_size)      # the last valid positions
    for served, s, row in ((dec[0], seq, 20), (dec[1], other, 11),
                           (pf[0], seq, 24), (pf[1], other, 22)):
        got = verdict(cfg, params, s[:row + 1], np.asarray(served)[None],
                      [row])
        assert got["ok"], got
    # the counters: every live token's k slots are held, zero or away
    c = _counts(cfg, st)
    live = 25 + 14 + 2
    assert c["runs"] == cfg.n_layers
    assert (c["load"].sum() + c["zero_slots"] + c["away_slots"]
            == live * cfg.n_experts_per_tok * cfg.n_layers)
    assert c["load"].sum() > 0 and c["zero_slots"] > 0 and c["away_slots"] > 0
    assert 0 < c["touched"] <= cfg.n_layers * cfg.n_held


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("served", [False, True], ids=["float32", "bf16"])
def test_the_tight_mixed_step_computes_what_the_parts_do(tiny, tight_step,
                                                         served, case):
    """``forward_mixed`` over tight slices and live tiles, the decode
    rows in front,
    against ``forward_prefill`` + ``forward_decode`` over the same pool
    (``tests/mixed_tight.py``): in float32, and in bfloat16 as served
    (the logits read 0.024-0.049 apart, the latent rows 0.039-0.172 (eleven bf16
    steps of a value of 2.75, where both ways lie 0.64 from float32))."""
    cfg, params, _ = tiny
    if not served:
        return check(tight_step, lf, cfg, params, case, page=PAGE)
    cfg = lf.longcat_flash_tiny(dtype=jnp.bfloat16, max_seq_len=128,
                                    held_experts=(8, 16))
    check_served(tight_step, lf, cfg,
                 lf.init_params(jax.random.PRNGKey(34), cfg), case,
                 page=PAGE, atol=1e-1, pages_atol=0.35)


def _counts(cfg, st):
    layout, st = lf.step_stats_layout(cfg), np.asarray(st)
    assert st.shape == (lf.step_stats_size(cfg),)
    out = {k: int(st[i]) for k, i in layout.items() if k != "load"}
    out["load"] = st[slice(*layout["load"])]
    return out


def test_absorbed_decode_equals_unabsorbed_attention_with_the_scales(tiny):
    """One attention's decode in the absorbed form (s_kv folded into
    the query's latent part and the output) against the expanded form
    (s_kv on the expanded keys' product and the output) over the same
    cached latents, which carry no scale."""
    cfg, params, _ = tiny
    assert cfg.kv_scale == pytest.approx(2 ** 0.5) and cfg.q_scale == 2.0
    lp, rng = params["layers"], np.random.default_rng(3)
    bt = jnp.asarray(block_table(cfg))
    pool = jnp.asarray(rng.standard_normal(
        (cfg.n_attn, 1 + bt.shape[1], PAGE, cfg.latent_width)) * 0.3,
        jnp.float32)
    n, H = 21, cfg.n_heads
    qn = jnp.asarray(rng.standard_normal((1, H, cfg.qk_nope_head_dim)),
                     jnp.float32)
    qr = jnp.asarray(rng.standard_normal((1, H, cfg.qk_rope_head_dim)),
                     jnp.float32)
    row = pool[3, bt[0, (n - 1) // PAGE], (n - 1) % PAGE][None]
    absorbed, _ = latent.latent_decode_attention(
        cfg, lp, 3, qn, qr, row, pool, bt, jnp.asarray([n]),
        bt[:, (n - 1) // PAGE], jnp.asarray([(n - 1) % PAGE]))
    expanded = latent.latent_prefill_attention(
        cfg, lp, 3, qn[:, None], qr[:, None], pool, bt,
        jnp.asarray([[n - 1]]), jnp.asarray([n]))
    np.testing.assert_allclose(np.asarray(absorbed),
                               np.asarray(expanded)[:, 0], atol=5e-5)


# -- the broken paths, each of which the comparison refuses -------------------


def _route_bf16(x, w, b, **kw):
    return moe.route(x.astype(jnp.bfloat16).astype(jnp.float32),
                     w.astype(jnp.bfloat16), b, **kw)


def _route_bias_in_weights(x, w, bias, *, top_k, scale, **kw):
    p = jax.nn.softmax(jnp.dot(x, w.astype(jnp.float32)), -1) + bias
    g, experts = jax.lax.top_k(p, top_k)
    return experts, g * scale


def _route_renormalised(x, w, bias, **kw):
    return moe.route(x, w, bias, **{**kw, "norm_topk": True})


def _identity_dropped(experts, gates, n_routed, live=None):
    return jnp.zeros(experts.shape[:1], jnp.float32)


def _identity_multiplied(x, experts, gates, w_gate_up, w_down, live=None, *,
                         held, n_routed):
    """A zero-compute slot ALSO runs through a held expert's matrices
    (the one its index falls on modulo the held ones)."""
    lo, hi = held
    wrapped = jnp.where(experts >= n_routed, lo + experts % (hi - lo),
                        experts)
    return moe.routed_ffn(x, wrapped, gates, w_gate_up, w_down, live,
                          held=held, n_routed=n_routed)


def _layer_shortcut_from_h2(params, cfg, l, h, cos, sin, attend, live):
    """``lf._layer`` with the routed layer reading N'_0 of h2 (after
    the first dense SwiGLU) instead of u."""
    from llmq_tpu.ops.norms import rms_norm
    at, ff = params["layers"], params["ffn"]
    a0, a1 = 2 * l, 2 * l + 1

    def attention(a, h):
        x = rms_norm(h, at["attn_norm"][a], cfg.norm_eps).astype(cfg.dtype)
        q_nope, q_rope, row = lf.qkv(cfg, at, a, x[None], cos, sin)
        return h + jnp.dot(attend(a, q_nope[0], q_rope[0], row[0]),
                           at["wo"][a])

    def dense(a, x):
        return lf._mlp(x.astype(cfg.dtype), ff["w_gate"][a], ff["w_up"][a],
                       ff["w_down"][a])

    h = attention(a0, h)
    u = rms_norm(h, ff["mlp_norm"][a0], cfg.norm_eps)
    h = h + dense(a0, u)
    m, st = lf._routed(params, cfg, l,
                       rms_norm(h, ff["mlp_norm"][a0], cfg.norm_eps), live)
    h = attention(a1, h)
    return h + dense(a1, rms_norm(h, ff["mlp_norm"][a1], cfg.norm_eps)) + m, st


def _write_first_attention_s_layer(pool, rows, bts, positions, lengths, l):
    return latent.latent_write_prefill(pool, rows, bts, positions, lengths,
                                       l - l % 2)


class _Unjitted:
    """The model's forward functions without their ``jax.jit`` (a
    patched helper must be traced again, not found in the cache)."""
    forward_prefill = staticmethod(lf.forward_prefill.__wrapped__)
    forward_decode = staticmethod(lf.forward_decode.__wrapped__)


def _broken(name, monkeypatch, cfg):
    """``cfg`` as a program with that fault would serve it; the
    reference keeps the sound one."""
    if name == "router-in-bfloat16":
        monkeypatch.setattr(lf, "route", _route_bf16)
    elif name == "no-s_q":
        cfg = dataclasses.replace(cfg, mla_scale_q_lora=False)
    elif name == "no-s_kv":
        cfg = dataclasses.replace(cfg, mla_scale_kv_lora=False)
    elif name == "bias-in-the-weights":
        monkeypatch.setattr(lf, "route", _route_bias_in_weights)
    elif name == "renormalised-weights":
        monkeypatch.setattr(lf, "route", _route_renormalised)
    elif name == "zero-compute-experts-dropped":
        monkeypatch.setattr(lf, "identity_gate", _identity_dropped)
    elif name == "zero-compute-experts-multiplied":
        monkeypatch.setattr(lf, "routed_ffn", _identity_multiplied)
    elif name == "shortcut-read-from-h2":
        monkeypatch.setattr(lf, "_layer", _layer_shortcut_from_h2)
    elif name == "second-attention-writes-the-first-s-cache-layer":
        monkeypatch.setattr(lf, "latent_write_prefill",
                            _write_first_attention_s_layer)
    return cfg


@pytest.mark.parametrize("fault", [
    "router-in-bfloat16", "no-s_q", "no-s_kv", "bias-in-the-weights",
    "renormalised-weights", "zero-compute-experts-dropped",
    "zero-compute-experts-multiplied", "shortcut-read-from-h2",
    "second-attention-writes-the-first-s-cache-layer"])
def test_a_broken_path_fails_the_comparison(tiny, monkeypatch, fault):
    cfg, params, seq = tiny
    served, rows = serve(_broken(fault, monkeypatch, cfg), params, seq,
                         (40,), fns=_Unjitted)
    got = verdict(cfg, params, seq, served, rows)
    assert not got["ok"], (fault, got)
    assert got["rms"] > 10 * TOL["rms"], got


def test_the_control_one_precision_down_is_refused(tiny):
    """The reference's own ``lowp`` form (router product in bfloat16,
    latents in 8 bits): what the comparison must refuse, and does."""
    cfg, params, seq = tiny
    rows = list(range(39, len(seq)))
    low, _ = reference.reference_forward(params, seq, hf_model(cfg), rows,
                                         lowp=True)
    got = verdict(cfg, params, seq, np.asarray(low), rows)
    assert not got["ok"] and got["rms_clean"] > 100 * TOL["rms_clean"], got


def _adapter_config(cfg, tolerance):
    """``cfg`` as a configuration file states it: what
    ``adapter.register`` reads, with a ``tolerance``."""
    return {**hf_model(cfg), "vocab_size": cfg.vocab_size,
            "ffn_hidden_size": cfg.ffn_dim,
            "expert_ffn_hidden_size": cfg.moe_ffn_dim,
            "max_position_embeddings": cfg.max_seq_len,
            "tolerance": tolerance}


@pytest.mark.parametrize("dtype,ok", [(jnp.float32, True),
                                      (jnp.bfloat16, False)],
                         ids=["as-stated", "one-precision-down"])
def test_the_benchmark_s_check_judges_many_positions(tiny, monkeypatch,
                                                     dtype, ok):
    """As the sibling family: ``adapter.serving_path`` hands the
    reference ``served_many``, and ``reference_logits`` judges every
    prefill position and the decode positions through the doubled
    cache before it answers. The adapter registers the file's share
    (``expert_share``) as the program's ``held_experts``."""
    cfg, params, seq = tiny
    adapter = contract.load_family(FAMILY, "adapter")
    monkeypatch.setattr(reference, "JUDGED", None)
    name = "tiny-under-the-check"
    mcfg = adapter.register(name, _adapter_config(cfg, dict(TOL)))
    monkeypatch.delitem(lf.MODEL_CONFIGS, name)
    assert mcfg.held == cfg.held == (8, 16) and mcfg.n_routed_experts == 16
    assert dataclasses.replace(mcfg, name=cfg.name,
                               dtype=cfg.dtype) == cfg
    adapter.serving_path(
        dataclasses.replace(mcfg, dtype=dtype),
        {"executor": {"page_size": PAGE, "prefill_buckets": [BUCKET]}})
    served_many, tol = reference.JUDGED
    assert tol == TOL
    rows = [len(seq) - 2, len(seq) - 1]
    want, _ = reference.reference_forward(params, seq, hf_model(cfg), rows)
    if ok:
        groups = served_many(params, seq)
        steps = (len(seq) - 1) // adapter.JUDGED_ROWS
        assert list(groups["prefill"][0]) == list(range(len(seq)))
        assert sorted(groups["decode"][0]) == list(
            range(len(seq) - adapter.JUDGED_ROWS * steps, len(seq)))
        got = reference.reference_logits(params, seq, hf_model(cfg), rows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        with pytest.raises(reference.NotCorrect, match="prefill.*rms_clean"):
            reference.reference_logits(params, seq, hf_model(cfg), rows)


def test_the_builder_draws_the_tree_the_program_serves(tiny):
    cfg = dataclasses.replace(tiny[0], dtype=jnp.bfloat16)
    adapter = contract.load_family(FAMILY, "adapter")
    params = jax.jit(adapter.param_builder(cfg, {}))(
        jax.random.key(3, impl="rbg"))
    want = jax.eval_shape(lambda: lf.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), want)
    bias = np.asarray(params["moe"]["router_bias"])
    assert 0 < np.abs(bias).max() <= adapter.ROUTER_BIAS


# -- through the executor and the engine --------------------------------------


def make_engine(tiny, **kw):
    cfg, params, _ = tiny
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=3, page_size=PAGE,
                     num_pages=96, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8)
    assert set(ex.cache) == {"ckv"}
    assert ex.cache["ckv"].shape[0] == 2 * cfg.n_layers
    return InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=16,
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                     max_slices=2), **kw), ex


def generate(eng, rid, prompt, n=10):
    h = eng.submit(GenRequest(id=rid, prompt=prompt, max_new_tokens=n,
                              temperature=0.0))
    assert h.wait(120)
    return h.result


def test_prefix_cache_hit_on_the_doubled_cache_leaf(tiny):
    """A second prompt that shares 40 tokens with the first is served
    from the radix cache's latent pages — both attentions' layers of
    them — and decodes what it decodes without the cache; the engine
    reads the family's counters by the family's layout."""
    cfg = tiny[0]
    shared = "the same forty-odd characters of system prompt: "
    plain, _ = make_engine(tiny)
    plain.start()
    want = generate(plain, "b", shared + "second question")
    plain.stop()
    eng, _ = make_engine(tiny, prefix_cache=PrefixCacheConfig(enabled=True))
    eng.start()
    first = generate(eng, "a", shared + "first question")
    second = generate(eng, "b", shared + "second question")
    stats = eng.get_stats()
    eng.stop()
    assert first.cached_tokens == 0 and second.cached_tokens >= 40
    assert second.tokens == want.tokens
    m = stats["moe"]
    assert m["layer_runs"] > 0 and m["pairs"] > 0
    assert len(m["load"]) == cfg.n_held and sum(m["load"]) == m["pairs"]
    assert 0 < m["experts_touched_mean"] <= cfg.n_held
    assert m["zero_slots"] > 0 and m["away_slots"] > 0
    # a live token fills k slots a routed layer it runs through
    assert (m["pairs"] + m["zero_slots"] + m["away_slots"]
            ) % cfg.n_experts_per_tok == 0


def test_a_page_exported_and_injected_back(tiny):
    """The tiering plane's transport treats the pool as a pytree of
    (L, P, ...) leaves: the doubled leaf goes out and comes back into
    other pages bit for bit."""
    _, ex = make_engine(tiny)
    L, P, ps, W = ex.cache["ckv"].shape
    ex.cache = {"ckv": jax.random.normal(jax.random.PRNGKey(0),
                                         (L, P, ps, W), jnp.float32)}
    assert ex.kv_page_spec() == [((L, ps, W), np.dtype(np.float32))]
    before = np.asarray(ex.cache["ckv"])
    out = [np.asarray(x) for x in ex.export_kv_pages([3, 7, 11])]
    assert out[0].shape == (L, 3, ps, W)
    ex.import_kv_pages([20, 21, 22], out)
    after = np.asarray(ex.cache["ckv"])
    assert np.array_equal(after[:, [20, 21, 22]], before[:, [3, 7, 11]])


def test_executor_reports_the_latent_routes_and_the_active_count(tiny):
    cfg, _, _ = tiny
    _, ex = make_engine(tiny)
    routes = ex._routes(decode=True, prefill_rows=1)
    assert set(routes) == {"prefill_write", "prefill_attention",
                           "decode_write", "decode_attention"}
    assert ex.step_stats_layout == lf.step_stats_layout(cfg)
    info = ex.telemetry_info()
    assert info["n_params"] == lf.active_param_count(cfg)
    assert info["n_params"] < lf.param_count_analytic(cfg)
    assert lf.param_count(ex.params) == lf.param_count_analytic(cfg)


# -- the registry -------------------------------------------------------------


def test_registry_serves_the_family_by_name_at_the_published_sizes():
    assert model_names()["longcat-flash-chat"] == "longcat_flash"
    cfg = get_config("longcat-flash-chat")
    assert family_of(cfg) is lf
    assert lf.param_count_analytic(cfg) == 560_664_980_480
    cut = get_config("longcat-flash-chat", n_layers=4, vocab_size=16384,
                     held_experts=(0, 16))
    assert lf.param_count_analytic(cut) == 5_172_749_312
    assert lf.kv_bytes_per_token(cut) == 8 * 1152
    assert cut.q_scale == 2.0 and cut.kv_scale == pytest.approx(12 ** 0.5)
    shape = jax.eval_shape(lambda: lf.init_kv_pages(cut, 1664, 128))
    assert shape["ckv"].shape == (8, 1664, 128, 640)
    assert lf.step_stats_size(cut) == 16 + 4


@pytest.mark.parametrize("what,match", [
    ("held-experts", r"held_experts \(8, 40\) of 16"),
    ("int8-weights", "model.quantization='int8'"),
    ("int8-cache", "model.kv_quantization='int8'"),
    ("mesh", "executor.mesh"),
])
def test_registry_refuses_with_an_error_that_names_the_setting(what, match):
    cfg = lf.longcat_flash_tiny(max_seq_len=64)
    with pytest.raises(ValueError, match=match):
        if what == "held-experts":
            lf.longcat_flash_tiny(held_experts=(8, 40))
        elif what == "int8-weights":
            lf.init_params_quantized(jax.random.PRNGKey(0), cfg)
        else:
            params = jax.eval_shape(
                lambda: lf.init_params(jax.random.PRNGKey(0), cfg))
            kw = {"int8-cache": dict(cache_dtype=jnp.int8),
                  "mesh": dict(mesh=jax.sharding.Mesh(
                      np.array(jax.devices()[:2]), ("tp",)))}[what]
            JaxExecutor(cfg, params, batch_size=2, page_size=8,
                        num_pages=16, **kw)


# -- loading published weights -------------------------------------------------


def test_a_published_checkpoint_s_share_is_loaded(tmp_path):
    """``import_hf_longcat_flash`` on a synthetic safetensors checkpoint
    of the WHOLE tiny model under the public tensor names (from memory
    of ``modeling_longcat_flash.py``: no network here): the held
    experts and the held rows of the vocabulary alone are read, each
    leaf lands where the program reads it, and the imported share
    serves."""
    st = pytest.importorskip("safetensors.numpy")
    from llmq_tpu.models.checkpoint import import_hf
    cfg = lf.longcat_flash_tiny(dtype=jnp.float32, held_experts=(4, 8),
                                vocab_size=256, max_seq_len=128)
    rng = np.random.default_rng(0)

    def w(o, i):
        return (rng.standard_normal((o, i)) * 0.05).astype(np.float32)

    D, H, R = cfg.dim, cfg.n_heads, cfg.n_routed_experts + cfg.zero_expert_num
    t = {"model.embed_tokens.weight": w(512, D),
         "model.norm.weight": np.ones(D, np.float32),
         "lm_head.weight": w(512, D)}
    for l in range(cfg.n_layers):
        pre = f"model.layers.{l}."
        for j in (0, 1):
            a = f"{pre}self_attn.{j}."
            t[a + "q_a_proj.weight"] = w(cfg.q_lora_rank, D)
            t[a + "q_a_layernorm.weight"] = np.ones(cfg.q_lora_rank,
                                                    np.float32)
            t[a + "q_b_proj.weight"] = w(H * cfg.qk_head_dim,
                                         cfg.q_lora_rank)
            t[a + "kv_a_proj_with_mqa.weight"] = w(
                cfg.kv_lora_rank + cfg.qk_rope_head_dim, D)
            t[a + "kv_a_layernorm.weight"] = np.ones(cfg.kv_lora_rank,
                                                     np.float32)
            t[a + "kv_b_proj.weight"] = w(
                H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                cfg.kv_lora_rank)
            t[a + "o_proj.weight"] = w(D, H * cfg.v_head_dim)
            for n in ("input_layernorm", "post_attention_layernorm"):
                t[f"{pre}{n}.{j}.weight"] = np.ones(D, np.float32)
            for n, (o, i) in (("gate", (cfg.ffn_dim, D)),
                              ("up", (cfg.ffn_dim, D)),
                              ("down", (D, cfg.ffn_dim))):
                t[f"{pre}mlps.{j}.{n}_proj.weight"] = w(o, i)
        t[pre + "mlp.router.classifier.weight"] = w(R, D)
        t[pre + "mlp.router.e_score_correction_bias"] = (
            rng.standard_normal(R) * 0.01).astype(np.float32)
        for e in range(cfg.n_routed_experts):
            for n, (o, i) in (("gate", (cfg.moe_ffn_dim, D)),
                              ("up", (cfg.moe_ffn_dim, D)),
                              ("down", (D, cfg.moe_ffn_dim))):
                t[f"{pre}mlp.experts.{e}.{n}_proj.weight"] = w(o, i)
    st.save_file(t, str(tmp_path / "model.safetensors"))
    params = import_hf(str(tmp_path), cfg)
    want = jax.eval_shape(lambda: lf.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(
        lambda x: x.shape, want)
    F = cfg.moe_ffn_dim
    gu = np.asarray(params["moe"]["we_gate_up"][1][2])   # expert 4 + 2
    pre = "model.layers.1.mlp.experts.6."
    assert np.array_equal(gu[:, :F], t[pre + "gate_proj.weight"].T)
    assert np.array_equal(gu[:, F:], t[pre + "up_proj.weight"].T)
    assert np.array_equal(np.asarray(params["embed"]),
                          t["model.embed_tokens.weight"][:256])
    assert np.array_equal(        # attention 1 of layer 1 is index 3
        np.asarray(params["layers"]["wkv_b"][3]),
        t["model.layers.1.self_attn.1.kv_b_proj.weight"].T)
    assert np.array_equal(
        np.asarray(params["ffn"]["w_down"][2]),
        t["model.layers.1.mlps.0.down_proj.weight"].T)
    seq = np.random.default_rng(2).integers(3, cfg.vocab_size, 30,
                                            dtype=np.int32)
    served, rows = serve(cfg, params, seq, (20,))
    assert verdict(cfg, params, seq, served, rows)["ok"]
