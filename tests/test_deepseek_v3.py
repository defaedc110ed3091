"""The DeepSeek-V3 block (``models/deepseek_v3.py``: latent attention
over a latent page pool, routed experts with shared ones) held to its
family's plain float32 reference
(``benchmark/families/deepseek_v3/reference.py``, which shares no code
with ``llmq_tpu``) at a tiny width, on seeded weights.

Logits, never tokens: random weights give near-ties. The weights here
are float32, so the served path differs from the reference by float32
rounding alone and the comparison is tight (``TOL``): each of the
broken paths below — a router or a cache in a lower precision than
stated, a scaling factor or a normalisation left out, the selection
bias in the gates, the key's RoPE half dropped, one expert's output
skipped — moves the logits by ten to a thousand times that. The
reference routes for itself; ``reference.judge`` holds a quantile of
the positions and the worst one to a limit each (on the chip in bf16
most positions have a swapped expert somewhere; at float32 none has,
and no position's 6th-to-7th, here 4th-to-5th, margin is under
``margin_eps``).
"""

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
from llmq_tpu.models import deepseek_v3 as ds
from llmq_tpu.models import family_of, get_config, model_names
from llmq_tpu.ops import moe
from llmq_tpu.ops.rows import pack_grid
from mixed_tight import (CASES, check, check_served,  # noqa: F401
                         tight_step)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(REPO, "benchmark", "families", "deepseek_v3")
reference = contract.load_family(FAMILY, "reference")

PAGE, BUCKET = 8, 48
#: float32 against float32: measured 1e-6 to 4e-6 here; the mildest
#: broken path (the router's product in bfloat16) gives 2e-3.
TOL = {"clean_quantile": 0.25, "rms_clean": 1e-4, "rms": 1e-4,
       "margin_eps": 1e-5}


def hf_model(cfg):
    """The configuration under the public ``config.json``'s keys: what
    the reference reads."""
    return {"num_hidden_layers": cfg.n_layers,
            "first_k_dense_replace": cfg.first_k_dense,
            "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": None,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk_prob": cfg.norm_topk_prob, "n_group": 1,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


@pytest.fixture(scope="module")
def tiny():
    cfg = ds.deepseek_v3_tiny(dtype=jnp.float32, max_seq_len=128)
    params = ds.init_params(jax.random.PRNGKey(31), cfg)
    # A selection bias that is not zero: one that the gates must not see.
    params["moe"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(5), params["moe"]["router_bias"].shape)
    seq = np.random.default_rng(31).integers(3, cfg.vocab_size, 44,
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


def serve(cfg, params, seq, cuts, fns=ds, cache_dtype=None):
    """Prefill seq[:cuts[-1]] in the chunks ``cuts`` bounds (a chunked
    continuation prefill over the cached context when there are
    several), then teacher-forced decode steps through the latent
    cache to the end of ``seq``. Returns the logits at positions
    cuts[-1] - 1 .. len(seq) - 1 and those positions."""
    bt = block_table(cfg)
    cache = ds.init_kv_pages(cfg, 1 + bt.shape[1], PAGE, dtype=cache_dtype)
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
def test_prefill_then_decode_through_the_latent_cache(tiny, cuts):
    """Whole prefill, and chunked continuation prefill over cached
    context (a chunk that starts and ends mid-page), then decode."""
    cfg, params, seq = tiny
    served, rows = serve(cfg, params, seq, cuts)
    got = verdict(cfg, params, seq, served, rows)
    assert got["ok"] and got["near_tie_share"] == 0, got


def test_a_mixed_step(tiny):
    """Two decode rows one token and one prompt slice in one fused
    step, against the reference's full forward pass of each."""
    cfg, params, seq = tiny
    other = np.random.default_rng(7).integers(3, cfg.vocab_size, 30,
                                              dtype=np.int32)
    bt = block_table(cfg, 3)
    cache = ds.init_kv_pages(cfg, 1 + 3 * bt.shape[1], PAGE)
    _, cache = prefill(ds, cfg, params, cache, bt[0:1], seq, 0, 20)
    _, cache = prefill(ds, cfg, params, cache, bt[1:2], other, 0, 11)
    pf_tok = np.zeros((1, BUCKET), np.int32)
    pf_tok[0, :25] = seq[:25]
    pf_pos = np.minimum(np.arange(BUCKET, dtype=np.int32), 24)[None]
    dec, pf, cache = ds.forward_mixed(
        params, cfg, jnp.asarray([seq[20], other[11], 0]),
        jnp.asarray([20, 11, 0], jnp.int32), cache, jnp.asarray(bt),
        *map(jnp.asarray, pack_grid(pf_tok, pf_pos, [25])[:2]),
        jnp.asarray([25], jnp.int32), jnp.asarray([0, 25], jnp.int32),
        jnp.asarray(bt[2:3]), dec_active=jnp.asarray([True, True, False]))
    assert pf.shape == (1, cfg.vocab_size)      # the last valid position
    for served, s, row in ((dec[0], seq, 20), (dec[1], other, 11),
                           (pf[0], seq, 24)):
        got = verdict(cfg, params, s[:row + 1], np.asarray(served)[None],
                      [row])
        assert got["ok"], got


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("served", [False, True], ids=["float32", "bf16"])
def test_the_tight_mixed_step_computes_what_the_parts_do(tiny, tight_step,
                                                         served, case):
    """``forward_mixed`` over tight slices (back on the grid at the door)
    against ``forward_prefill`` + ``forward_decode`` over the same pool
    (``tests/mixed_tight.py``): in float32, and in bfloat16 as served
    (the logits read 0.019-0.030 apart, the latent rows 0.016-0.031)."""
    cfg, params, _ = tiny
    if not served:
        return check(tight_step, ds, cfg, params, case, page=PAGE)
    cfg = ds.deepseek_v3_tiny(dtype=jnp.bfloat16, max_seq_len=128)
    check_served(tight_step, ds, cfg,
                 ds.init_params(jax.random.PRNGKey(31), cfg), case,
                 page=PAGE, atol=6e-2, pages_atol=6e-2)


def test_absorbed_decode_equals_unabsorbed_attention(tiny):
    """One layer's decode attention in the absorbed form (the decode
    path) against the expanded form (the prefill path) over the same
    cached latents."""
    cfg, params, _ = tiny
    lp, rng = params["layers"], np.random.default_rng(3)
    bt = jnp.asarray(block_table(cfg))
    pool = jnp.asarray(rng.standard_normal(
        (cfg.n_layers, 1 + bt.shape[1], PAGE, cfg.latent_width)) * 0.3,
        jnp.float32)
    n, H = 21, cfg.n_heads
    qn = jnp.asarray(rng.standard_normal((1, H, cfg.qk_nope_head_dim)),
                     jnp.float32)
    qr = jnp.asarray(rng.standard_normal((1, H, cfg.qk_rope_head_dim)),
                     jnp.float32)
    row = pool[1, bt[0, (n - 1) // PAGE], (n - 1) % PAGE][None]
    absorbed, _ = ds.latent_decode_attention(
        cfg, lp, 1, qn, qr, row, pool, bt, jnp.asarray([n]),
        bt[:, (n - 1) // PAGE], jnp.asarray([(n - 1) % PAGE]))
    expanded = ds.latent_prefill_attention(
        cfg, lp, 1, qn[:, None], qr[:, None], pool, bt,
        jnp.asarray([[n - 1]]), jnp.asarray([n]))
    np.testing.assert_allclose(np.asarray(absorbed),
                               np.asarray(expanded)[:, 0], atol=2e-5)


# -- the broken paths, each of which the comparison refuses -------------------


def _route_bf16(x, w, b, **kw):
    return moe.route(x.astype(jnp.bfloat16).astype(jnp.float32),
                     w.astype(jnp.bfloat16), b, **kw)


def _route_bias_in_gates(x, w, bias, *, top_k, scale, norm_topk=True):
    s = jax.nn.sigmoid(jnp.dot(x, w.astype(jnp.float32))) + bias
    g, experts = jax.lax.top_k(s, top_k)
    return experts, g / jnp.sum(g, -1, keepdims=True) * scale


def _qkv_no_rope_key(cfg, lp, l, x, cos, sin):
    qn, qr, row = _QKV(cfg, lp, l, x, cos, sin)
    r = cfg.kv_lora_rank
    return qn, qr, row.at[..., r:r + cfg.qk_rope_head_dim].set(0)


_QKV = ds._qkv


class _Unjitted:
    """The model's forward functions without their ``jax.jit`` (a
    patched helper must be traced again, not found in the cache)."""
    forward_prefill = staticmethod(ds.forward_prefill.__wrapped__)
    forward_decode = staticmethod(ds.forward_decode.__wrapped__)


def _broken(name, monkeypatch, cfg, params):
    """(cfg, params, cache dtype) as a program with that fault would
    serve them; the reference keeps the sound ones."""
    if name == "router-in-bfloat16":
        monkeypatch.setattr(ds, "route", _route_bf16)
    elif name == "latent-cache-in-bfloat16":
        return cfg, params, jnp.bfloat16
    elif name == "no-routed-scaling-factor":
        cfg = ds.deepseek_v3_tiny(**{**cfg.__dict__,
                                     "routed_scaling_factor": 1.0})
    elif name == "no-normalisation":
        cfg = ds.deepseek_v3_tiny(**{**cfg.__dict__,
                                     "norm_topk_prob": False})
    elif name == "bias-in-the-gates":
        monkeypatch.setattr(ds, "route", _route_bias_in_gates)
    elif name == "rope-half-of-the-key-dropped":
        monkeypatch.setattr(ds, "_qkv", _qkv_no_rope_key)
    elif name == "one-expert-skipped":
        # The expert that got most tokens gives nothing, in any layer.
        e = _busiest(cfg, params)
        m = dict(params["moe"])
        m["we_down"] = tuple(d.at[e].set(0.0) for d in m["we_down"])
        params = {**params, "moe": m}
    return cfg, params, None


def _busiest(cfg, params):
    seq = np.random.default_rng(31).integers(3, cfg.vocab_size, 44,
                                             dtype=np.int32)
    bt = block_table(cfg)
    cache = ds.init_kv_pages(cfg, 1 + bt.shape[1], PAGE)
    _, _, st = ds.forward_prefill(
        params, cfg, jnp.asarray(seq[None, :40]),
        jnp.arange(40, dtype=jnp.int32)[None], jnp.asarray([40], jnp.int32),
        cache, jnp.asarray(bt), stats=True)
    return int(np.argmax(np.asarray(st)[:cfg.n_routed_experts]))


@pytest.mark.parametrize("fault", [
    "router-in-bfloat16", "latent-cache-in-bfloat16",
    "no-routed-scaling-factor", "no-normalisation", "bias-in-the-gates",
    "rope-half-of-the-key-dropped", "one-expert-skipped"])
def test_a_broken_path_fails_the_comparison(tiny, monkeypatch, fault):
    cfg, params, seq = tiny
    bcfg, bparams, cache_dtype = _broken(fault, monkeypatch, cfg, params)
    served, rows = serve(bcfg, bparams, seq, (40,), fns=_Unjitted,
                         cache_dtype=cache_dtype)
    got = verdict(cfg, params, seq, served, rows)
    assert not got["ok"], (fault, got)
    assert got["rms"] > 10 * TOL["rms"], got


def test_the_control_one_precision_down_is_refused(tiny):
    """The reference's own ``lowp`` form (router product in bfloat16,
    latent in 8 bits): what the comparison must refuse, and does."""
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
            "hidden_size": cfg.dim, "v_head_dim": cfg.v_head_dim,
            "intermediate_size": cfg.ffn_dim,
            "moe_intermediate_size": cfg.moe_ffn_dim,
            "n_routed_experts": cfg.n_routed_experts,
            "n_shared_experts": cfg.n_shared_experts,
            "max_position_embeddings": cfg.max_seq_len,
            "tolerance": tolerance}


@pytest.mark.parametrize("dtype,ok", [(jnp.float32, True),
                                      (jnp.bfloat16, False)],
                         ids=["as-stated", "one-precision-down"])
def test_the_benchmark_s_check_judges_many_positions(tiny, monkeypatch,
                                                     dtype, ok):
    """The harness's check holds the worst of 8 positions to one limit
    and asks no family how to judge; so ``adapter.serving_path`` hands
    the reference ``served_many`` and ``reference_logits`` — the call
    in which the check gives the family the weights and a prompt —
    judges every prefill position and the decode positions through the
    latent cache before it answers. A serving path one precision below
    the stated one ends the run there."""
    import dataclasses
    cfg, params, seq = tiny
    adapter = contract.load_family(FAMILY, "adapter")
    monkeypatch.setattr(reference, "JUDGED", None)
    name = "tiny-under-the-check"
    mcfg = adapter.register(name, _adapter_config(cfg, dict(TOL)))
    monkeypatch.delitem(ds.MODEL_CONFIGS, name)
    adapter.serving_path(
        dataclasses.replace(mcfg, dtype=dtype),
        {"executor": {"page_size": PAGE, "prefill_buckets": [BUCKET]}})
    served_many, tol = reference.JUDGED
    assert tol == TOL
    groups = served_many(params, seq)
    steps = (len(seq) - 1) // adapter.JUDGED_ROWS
    assert list(groups["prefill"][0]) == list(range(len(seq)))
    assert sorted(groups["decode"][0]) == list(
        range(len(seq) - adapter.JUDGED_ROWS * steps, len(seq)))
    rows = [len(seq) - 2, len(seq) - 1]
    want, _ = reference.reference_forward(params, seq, hf_model(cfg), rows)
    if ok:
        got = reference.reference_logits(params, seq, hf_model(cfg), rows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        with pytest.raises(reference.NotCorrect, match="prefill.*rms_clean"):
            reference.reference_logits(params, seq, hf_model(cfg), rows)
        # a sequence under ``min_positions`` is answered and not judged
        monkeypatch.setitem(tol, "min_positions", len(seq) + 1)
        got = reference.reference_logits(params, seq, hf_model(cfg), rows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the routed layer against a loop over the experts -------------------------


def _loop_over_experts(x, experts, gates, w_gate_up, w_down):
    F = w_down.shape[1]
    y = np.zeros_like(x)
    for n in range(x.shape[0]):
        for e, g in zip(experts[n], gates[n]):
            gu = x[n] @ w_gate_up[e]
            a = gu[:F] / (1 + np.exp(-gu[:F])) * gu[F:]
            y[n] += g * (a @ w_down[e])
    return y


@pytest.mark.parametrize("case", ["tie-at-the-last-place", "bias-chooses",
                                  "an-expert-gets-no-token",
                                  "neighbours-share-no-expert"])
def test_routed_layer_against_a_loop_over_experts(case):
    E, k, D, F, N = 16, 6, 32, 16, 5
    rng = np.random.default_rng(11)
    x = rng.standard_normal((N, D)).astype(np.float32)
    w_r = (rng.standard_normal((D, E)) * 0.3).astype(np.float32)
    bias = np.zeros(E, np.float32)
    if case == "tie-at-the-last-place":
        # Token 0 reads row 0 of W_r alone, where experts 9 and 2 hold
        # the 6th and 7th place with the same score.
        x[0] = 0.0
        x[0, 0] = 1.0
        w_r[0] = np.linspace(-2.0, 2.0, E)[rng.permutation(E)]
        by_rank = np.argsort(-w_r[0])
        tied = sorted(by_rank[5:7])
        w_r[0, tied[1]] = w_r[0, tied[0]]
    elif case == "bias-chooses":
        bias = rng.standard_normal(E).astype(np.float32)
    elif case == "an-expert-gets-no-token":
        bias[4] = -10.0
    elif case == "neighbours-share-no-expert":
        # tokens 0 and 1 see opposite halves of the experts
        w_r = np.zeros((D, E), np.float32)
        w_r[0, :8], w_r[0, 8:] = 1.0, -1.0
        w_r[1] = np.linspace(0.01, 0.08, E)      # no ties inside a half
        x[0, :2], x[1, :2] = (5.0, 1.0), (-5.0, 1.0)
    w_gu = (rng.standard_normal((E, D, 2 * F)) * 0.2).astype(np.float32)
    w_d = (rng.standard_normal((E, F, D)) * 0.2).astype(np.float32)
    experts, gates = moe.route(jnp.asarray(x), jnp.asarray(w_r),
                               jnp.asarray(bias), top_k=k, scale=2.448)
    experts, gates = np.asarray(experts), np.asarray(gates)
    # The selection by the published rule, worked out here.
    s = 1 / (1 + np.exp(-(x @ w_r)))
    want = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :k]
    assert np.array_equal(np.sort(experts, -1), np.sort(want, -1))
    chosen = np.take_along_axis(s, experts, -1)
    np.testing.assert_allclose(
        gates, 2.448 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    if case == "tie-at-the-last-place":
        # ... and the lower index takes the place, as the reference has it.
        assert tied[0] in experts[0] and tied[1] not in experts[0]
    if case == "an-expert-gets-no-token":
        assert not (experts == 4).any()
    if case == "neighbours-share-no-expert":
        assert not set(experts[0]) & set(experts[1])
    live = np.array([True, True, True, False, True])
    y, stats = moe.routed_ffn(jnp.asarray(x), jnp.asarray(experts),
                              jnp.asarray(gates), jnp.asarray(w_gu),
                              jnp.asarray(w_d), jnp.asarray(live))
    ref = _loop_over_experts(x, experts, gates, w_gu, w_d) * live[:, None]
    np.testing.assert_allclose(np.asarray(y), ref, atol=2e-5)
    counts = np.bincount(experts[live].reshape(-1), minlength=E)
    assert np.array_equal(np.asarray(stats)[:E], counts)
    assert int(stats[E]) == int((counts > 0).sum())


# -- through the executor and the engine --------------------------------------


def make_engine(tiny, **kw):
    cfg, params, _ = tiny
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=3, page_size=PAGE,
                     num_pages=96, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8)
    assert set(ex.cache) == {"ckv"}
    return InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=16,
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                     max_slices=2), **kw), ex


def generate(eng, rid, prompt, n=10):
    h = eng.submit(GenRequest(id=rid, prompt=prompt, max_new_tokens=n,
                              temperature=0.0))
    assert h.wait(120)
    return h.result


def test_prefix_cache_hit_on_latent_pages(tiny):
    """A second prompt that shares 40 tokens with the first is served
    from the radix cache's latent pages and decodes what it decodes
    without the cache."""
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
    assert stats["moe"]["layer_runs"] > 0 and stats["moe"]["pairs"] > 0
    assert 0 < stats["moe"]["experts_touched_mean"] <= tiny[0].n_routed_experts


def test_a_page_exported_and_injected_back(tiny):
    """The tiering plane's transport treats the pool as a pytree of
    (L, P, ...) leaves: the latent pool's one leaf goes out and comes
    back into other pages bit for bit."""
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
    keep = [p for p in range(1, P) if p not in (20, 21, 22)]
    assert np.array_equal(after[:, keep], before[:, keep])


def test_executor_reports_the_latent_routes_and_the_active_count(tiny):
    cfg, _, _ = tiny
    _, ex = make_engine(tiny)
    routes = ex._routes(decode=True, prefill_rows=1)
    assert set(routes) == {"prefill_write", "prefill_attention",
                           "decode_write", "decode_attention"}
    info = ex.telemetry_info()
    assert info["n_params"] == ds.active_param_count(cfg)
    assert info["n_params"] < ds.param_count_analytic(cfg)
    assert ds.param_count(ex.params) == ds.param_count_analytic(cfg)


# -- the registry -------------------------------------------------------------


def test_registry_serves_both_families_by_name():
    names = model_names()
    assert names["kanana-2-30b-a3b"] == "deepseek_v3"
    assert names["mistral-7b-v0.3"] == "llama"
    cfg = get_config("kanana-2-30b-a3b")
    assert family_of(cfg) is ds
    assert ds.param_count_analytic(cfg) == 30_670_815_104
    assert ds.param_count_analytic(
        get_config("kanana-2-30b-a3b", n_layers=8)) == 5_069_642_624
    assert ds.kv_bytes_per_token(cfg) == 48 * 1152
    assert ds.init_kv_pages(ds.deepseek_v3_tiny(), 4, 8)["ckv"].shape == (
        3, 4, 8, 256)


@pytest.mark.parametrize("what,match", [
    ("unknown-name", "unknown model 'no-such-model'.*kanana-2-30b-a3b"),
    ("first_k_dense", "first_k_dense 9 of 8"),
    ("int8-weights", "model.quantization='int8'"),
    ("int8-cache", "model.kv_quantization='int8'"),
    ("mesh", "executor.mesh"),
])
def test_registry_refuses_with_an_error_that_names_the_setting(what, match):
    cfg = ds.deepseek_v3_tiny(max_seq_len=64)
    with pytest.raises(ValueError, match=match):
        if what == "unknown-name":
            get_config("no-such-model")
        elif what == "first_k_dense":
            get_config("kanana-2-30b-a3b", n_layers=8, first_k_dense=9)
        elif what == "int8-weights":
            ds.init_params_quantized(jax.random.PRNGKey(0), cfg)
        else:
            params = jax.eval_shape(
                lambda: ds.init_params(jax.random.PRNGKey(0), cfg))
            kw = {"int8-cache": dict(cache_dtype=jnp.int8),
                  "mesh": dict(mesh=jax.sharding.Mesh(
                      np.array(jax.devices()[:2]), ("tp",)))}[what]
            JaxExecutor(cfg, params, batch_size=2, page_size=8,
                        num_pages=16, **kw)


def test_a_low_rank_query_is_served():
    """``q_lora_rank`` (``models/latent.py``) in this family: decode
    through the latent cache gives what one whole prefill gives, and
    the tree holds the three query leaves in place of ``wq``."""
    cfg = ds.deepseek_v3_tiny(dtype=jnp.float32, max_seq_len=128,
                              q_lora_rank=48)
    params = ds.init_params(jax.random.PRNGKey(7), cfg)
    assert {"wq_a", "q_norm", "wq_b"} <= set(params["layers"])
    assert "wq" not in params["layers"]
    assert ds.param_count(params) == ds.param_count_analytic(cfg)
    seq = np.random.default_rng(7).integers(3, cfg.vocab_size, 30,
                                            dtype=np.int32)
    through_cache, rows = serve(cfg, params, seq, (20,))
    whole, _ = serve(cfg, params, seq, (30,))
    np.testing.assert_allclose(through_cache[-1], whole[0], atol=2e-4)
    assert rows[-1] == 29


def test_builder_error_lists_the_registry(monkeypatch):
    from llmq_tpu.core.config import default_config
    from llmq_tpu.engine import build_engine
    cfg = default_config()
    cfg.executor.backend = "jax"
    cfg.model.name = "llama3-nope"
    with pytest.raises(ValueError, match="deepseek-v3-tiny.*llama3-tiny"):
        build_engine(cfg, warmup=False)


# -- loading published weights -------------------------------------------------


class TestHfImport:
    """``import_hf_deepseek_v3`` on a synthetic safetensors checkpoint
    at a cut shape, under the public tensor names (from memory of
    ``modeling_deepseek_v3.py``: no network here)."""

    @pytest.fixture(scope="class")
    def hf_dir(self, tmp_path_factory):
        st = pytest.importorskip("safetensors.numpy")
        cfg = ds.deepseek_v3_tiny(n_routed_experts=4, n_experts_per_tok=2,
                                  dtype=jnp.float32)
        rng = np.random.default_rng(0)

        def w(o, i):
            return (rng.standard_normal((o, i)) * 0.05).astype(np.float32)

        D, H = cfg.dim, cfg.n_heads
        t = {"model.embed_tokens.weight": w(cfg.vocab_size, D),
             "model.norm.weight": np.ones(D, np.float32),
             "lm_head.weight": w(cfg.vocab_size, D)}
        for i in range(cfg.n_layers):
            a, m = f"model.layers.{i}.self_attn.", f"model.layers.{i}.mlp."
            t[a + "q_proj.weight"] = w(H * cfg.qk_head_dim, D)
            t[a + "kv_a_proj_with_mqa.weight"] = w(
                cfg.kv_lora_rank + cfg.qk_rope_head_dim, D)
            t[a + "kv_a_layernorm.weight"] = np.ones(cfg.kv_lora_rank,
                                                     np.float32)
            t[a + "kv_b_proj.weight"] = w(
                H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                cfg.kv_lora_rank)
            t[a + "o_proj.weight"] = w(D, H * cfg.v_head_dim)
            for n in ("input_layernorm", "post_attention_layernorm"):
                t[f"model.layers.{i}.{n}.weight"] = np.ones(D, np.float32)
            if i < cfg.first_k_dense:
                shapes = {"": cfg.ffn_dim}
            else:
                t[m + "gate.weight"] = w(cfg.n_routed_experts, D)
                t[m + "gate.e_score_correction_bias"] = (
                    rng.standard_normal(cfg.n_routed_experts) * 0.1
                ).astype(np.float32)
                shapes = {f"experts.{e}.": cfg.moe_ffn_dim
                          for e in range(cfg.n_routed_experts)}
                shapes["shared_experts."] = (cfg.n_shared_experts
                                             * cfg.moe_ffn_dim)
            for pre, f in shapes.items():
                t[m + pre + "gate_proj.weight"] = w(f, D)
                t[m + pre + "up_proj.weight"] = w(f, D)
                t[m + pre + "down_proj.weight"] = w(D, f)
        d = tmp_path_factory.mktemp("hf")
        st.save_file(t, str(d / "model.safetensors"))
        return str(d), cfg, t

    def test_names_shapes_and_layout(self, hf_dir):
        from llmq_tpu.models.checkpoint import import_hf
        path, cfg, t = hf_dir
        params = import_hf(path, cfg)
        want = jax.eval_shape(lambda: ds.init_params(jax.random.PRNGKey(0),
                                                     cfg))
        assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(
            lambda x: x.shape, want)
        assert ds.param_count(params) == ds.param_count_analytic(cfg)
        F = cfg.moe_ffn_dim
        gu = np.asarray(params["moe"]["we_gate_up"][1][3])
        pre = "model.layers.2.mlp.experts.3."
        assert np.array_equal(gu[:, :F], t[pre + "gate_proj.weight"].T)
        assert np.array_equal(gu[:, F:], t[pre + "up_proj.weight"].T)
        assert np.array_equal(
            np.asarray(params["moe"]["router_bias"][0]),
            t["model.layers.1.mlp.gate.e_score_correction_bias"])
        assert np.array_equal(
            np.asarray(params["layers"]["wkv_b"][2]),
            t["model.layers.2.self_attn.kv_b_proj.weight"].T)

    def test_interleaved_rope_rows_are_permuted_once(self, hf_dir):
        """A score under the PUBLISHED rotation (pairs side by side) of
        the published rows equals the score under this repo's rotation
        (two halves) of the imported rows: for q's rope part, per head,
        and for the one rope key."""
        from llmq_tpu.models.checkpoint import import_hf
        from llmq_tpu.ops.rope import apply_rope, rope_cos_sin
        path, cfg, t = hf_dir
        params = import_hf(path, cfg)
        dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
        rng = np.random.default_rng(1)
        x = rng.standard_normal((2, cfg.dim)).astype(np.float32)
        pos = np.array([3, 11])

        def rotate_pairs(v, p):          # v (..., dr), pairs (2j, 2j+1)
            ang = p * cfg.rope_theta ** (-np.arange(dr // 2) / (dr // 2))
            a, b = v[..., 0::2], v[..., 1::2]
            out = np.empty_like(v)
            out[..., 0::2] = a * np.cos(ang) - b * np.sin(ang)
            out[..., 1::2] = b * np.cos(ang) + a * np.sin(ang)
            return out

        wq = t["model.layers.0.self_attn.q_proj.weight"]
        wa = t["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"]
        q_pub = (x[0] @ wq.T).reshape(cfg.n_heads, dn + dr)[:, dn:]
        k_pub = (x[1] @ wa.T)[r:]
        want = rotate_pairs(q_pub, pos[0]) @ rotate_pairs(k_pub, pos[1])

        cos, sin = rope_cos_sin(jnp.asarray(pos)[:, None], dr,
                                cfg.rope_theta)
        q = (x[0] @ np.asarray(params["layers"]["wq"][0])).reshape(
            1, 1, cfg.n_heads, dn + dr)[..., dn:]
        k = (x[1] @ np.asarray(params["layers"]["wkv_a"][0]))[r:].reshape(
            1, 1, 1, dr)
        q = np.asarray(apply_rope(jnp.asarray(q), cos[0:1], sin[0:1]))[0, 0]
        k = np.asarray(apply_rope(jnp.asarray(k), cos[1:2], sin[1:2]))[0, 0, 0]
        np.testing.assert_allclose(q @ k, want, atol=1e-5)

    def test_imported_model_serves(self, hf_dir):
        from llmq_tpu.models.checkpoint import import_hf
        path, cfg, _ = hf_dir
        params = import_hf(path, cfg)
        seq = np.random.default_rng(2).integers(3, cfg.vocab_size, 20,
                                                dtype=np.int32)
        cfg = ds.deepseek_v3_tiny(**{**cfg.__dict__, "max_seq_len": 128})
        served, rows = serve(cfg, params, seq, (16,))
        got = verdict(cfg, params, seq, served, rows)
        assert got["ok"], got
