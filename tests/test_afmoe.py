"""The Arcee ``afmoe`` block (``models/afmoe.py``: gated grouped-query
attention whose layers are sliding-window layers with rotary positions
or full layers without, a norm on both sides of every sublayer, a
sigmoid-routed layer beside a shared expert, serving ONE CHIP'S SHARE of
the experts; the sliding layers' keys and values in a slab a batch row)
held to its family's plain float32 reference
(``benchmark/families/afmoe/reference.py``, which shares no code with
``llmq_tpu``) at a tiny width, on seeded weights.

Logits, never tokens. The weights here are float32, so the served path
differs from the reference by float32 rounding alone and the comparison
is tight (``TOL``): each of the broken paths below — the things the
tolerance on the chip cannot see — moves the logits by ten to a hundred
thousand times that. The tiny model's window is 24 tokens and its test
sequence 100, so every sliding layer's slab (7 pages of 8) wraps
several times; it holds experts 8-15 of 16, so both kinds of slot occur.
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
from llmq_tpu.models import afmoe as am
from llmq_tpu.models import family_of, get_config, model_names
from llmq_tpu.ops.rows import pack_grid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(REPO, "benchmark", "families", "afmoe")
reference = contract.load_family(FAMILY, "reference")

PAGE, BUCKET, ROWS = 8, 32, 3
#: float32 against float32: measured 1e-6 to 2e-6 here; the mildest
#: broken path gives 4e-4.
TOL = {"clean_quantile": 0.25, "rms_clean": 2e-5, "rms": 2e-5,
       "margin_eps": 1e-7}


def hf_model(cfg):
    """The configuration under the public ``config.json``'s keys, with
    the share as the benchmark's file states it: what the reference
    reads."""
    lo, hi = cfg.held
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "layer_types": list(cfg.layer_types),
            "sliding_window": cfg.sliding_window,
            "num_dense_layers": cfg.n_dense_layers,
            "intermediate_size": cfg.ffn_dim,
            "moe_intermediate_size": cfg.moe_ffn_dim,
            "num_experts": hi - lo, "router_experts": cfg.n_routed_experts,
            "expert_share": {"chips": cfg.n_routed_experts // (hi - lo),
                             "index": lo // (hi - lo)},
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "num_shared_experts": cfg.n_shared_experts,
            "route_scale": cfg.route_scale, "route_norm": cfg.route_norm,
            "score_func": "sigmoid", "mup_enabled": cfg.mup_enabled,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


@pytest.fixture(scope="module")
def tiny():
    cfg = am.bind_cache(
        am.afmoe_tiny(dtype=jnp.float32, max_seq_len=128,
                      held_experts=(8, 16)),
        page_size=PAGE, step_tokens=BUCKET)
    params = am.init_params(jax.random.PRNGKey(41), cfg)
    # A selection bias that is not zero: one that the gates must not see.
    params["moe"]["router_bias"] = 0.01 * jax.random.normal(
        jax.random.PRNGKey(5), params["moe"]["router_bias"].shape)
    seq = np.random.default_rng(41).integers(3, cfg.vocab_size, 100,
                                             dtype=np.int32)
    return cfg, params, seq


def block_table(cfg, n_rows=ROWS):
    mp = cfg.max_seq_len // PAGE
    return (1 + np.arange(n_rows)[:, None] * mp
            + np.arange(mp)[None, :]).astype(np.int32)


def new_cache(cfg, n_rows=ROWS):
    return (am.init_kv_pages(cfg, 1 + n_rows * (cfg.max_seq_len // PAGE),
                             PAGE), am.init_row_state(cfg, n_rows))


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


def serve(cfg, params, seq, cuts, fns=am, row=1):
    """Prefill seq[:cuts[-1]] in the slices ``cuts`` bounds, in batch
    row ``row`` of ``ROWS``, then teacher-forced decode steps through
    both kinds of cache to the end of ``seq`` (the other rows not
    active). Returns the logits at positions cuts[-1] - 1 ..
    len(seq) - 1 and those positions."""
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    out, start = [], 0
    for end in cuts:
        logits, cache, state = prefill(fns, cfg, params, cache, state, bt,
                                       seq, start, end, row)
        start = end
    out.append(logits)
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
    ref, margins = reference.reference_forward(params, seq, hf_model(cfg),
                                               rows)
    return reference.judge(served, np.asarray(ref), np.asarray(margins), TOL)


# -- the served path against the reference ------------------------------------


@pytest.mark.parametrize("cuts", [(20,), (32, 64, 70), (13, 45, 60, 81)],
                         ids=["inside-the-window", "slices-past-the-window",
                              "slices-mid-page"])
def test_prefill_in_slices_then_decode_through_both_caches(tiny, cuts):
    """Sequences of 100 tokens against a window of 24: the slices and
    the decode steps read a slab that has wrapped."""
    cfg, params, seq = tiny
    assert len(seq) > 4 * cfg.sliding_window
    served, rows = serve(cfg, params, seq, cuts)
    got = verdict(cfg, params, seq, served, rows)
    assert got["ok"] and got["near_tie_share"] == 0, got


def _counts(cfg, st):
    layout, st = am.step_stats_layout(cfg), np.asarray(st)
    assert st.shape == (am.step_stats_size(cfg),)
    out = {k: int(st[i]) for k, i in layout.items() if k != "load"}
    out["load"] = st[slice(*layout["load"])]
    return out


def _mixed(cfg, params, cache, state, bt, dec, slices, T=BUCKET, S=2,
           **kw):
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
    return am.forward_mixed(
        params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
        jnp.asarray(bt), jnp.asarray(pf_tok), jnp.asarray(pf_pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bt),
        dec_active=jnp.asarray([r in dec for r in range(ROWS)]),
        row_state=state, pf_rows=jnp.asarray(rows), **kw)


def test_a_mixed_step(tiny):
    """Two decode rows beyond the window and a prompt slice that
    continues a third row's context, in one fused step, against the
    reference's full forward pass of each; and the routed counters."""
    cfg, params, seq = tiny
    other = np.random.default_rng(7).integers(3, cfg.vocab_size, 90,
                                              dtype=np.int32)
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    for s, row, upto in ((seq, 0, 70), (other, 1, 45), (other, 2, 50)):
        for a in range(0, upto, BUCKET):
            _, cache, state = prefill(am, cfg, params, cache, state, bt, s,
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
    assert c["runs"] == cfg.n_routed_layers == 7
    assert (c["load"].sum() + c["away_slots"]
            == live * cfg.n_experts_per_tok * c["runs"])
    assert c["load"].sum() > 0 and c["away_slots"] > 0
    assert 0 < c["touched"] <= c["runs"] * cfg.n_held


def test_several_slices_of_one_prompt_in_one_mixed_step(tiny):
    """A mixed step that carries TWO consecutive slices of one long
    prompt writes both before either attends: the first slice's window
    reaches back 23 keys behind its first token while the second has
    already written 32 tokens ahead. A slab that holds the window and
    BOTH slices (``bind_cache`` with their sum) serves it: neither
    slice reads a key the other overwrote. A slab cut for ONE slice a
    sequence — what the executor binds — does not, and the comparison
    sees it: so the engine's packing rule (one slice a sequence a step)
    is what the bound rests on, and is pinned here."""
    from llmq_tpu.engine.engine import _pack_prefill_slices
    from types import SimpleNamespace
    long = SimpleNamespace(order=1, todo_ids=list(range(100)),
                           req=SimpleNamespace(tenant_id="t"))
    short = SimpleNamespace(order=2, todo_ids=list(range(5)),
                            req=SimpleNamespace(tenant_id="t"))
    plan = _pack_prefill_slices([long, short], 4, 32, 128, None)
    assert [(s.order, len(sl)) for s, sl in plan] == [(1, 32), (2, 5)]
    cfg, params, seq = tiny
    wide = am.bind_cache(cfg, page_size=PAGE, step_tokens=2 * BUCKET)
    assert wide.slab_pages == (24 + 64) // PAGE + 1 == 12
    assert cfg.slab_pages == (24 + 32) // PAGE + 1 == 8

    def run(cfg):
        bt = block_table(cfg)
        cache, state = new_cache(cfg)
        _, cache, state = prefill(am, cfg, params, cache, state, bt, seq,
                                  0, 30, 1)
        _, pf, *_ = _mixed(cfg, params, cache, state, bt, {},
                           [(1, seq[30:62], 30), (1, seq[62:94], 62)])
        return [verdict(cfg, params, seq[:row + 1],
                        np.asarray(pf[i])[None], [row])
                for i, row in ((0, 61), (1, 93))]

    assert all(got["ok"] for got in run(wide)), run(wide)
    narrow = run(dataclasses.replace(cfg, slab_pages=7))
    assert not narrow[0]["ok"] and narrow[0]["rms"] > 10 * TOL["rms"]


# -- the broken paths, each of which the comparison refuses -------------------


def _route_bias_in_the_gates(x, w, bias, *, top_k, scale, **kw):
    s = jax.nn.sigmoid(jnp.dot(x, w.astype(jnp.float32))) + bias
    g, experts = jax.lax.top_k(s, top_k)
    return experts, g / (jnp.sum(g, -1, keepdims=True) + 1e-20) * scale


def _no_gate(h, attn, gate, lp, l, cfg, _sound=am._attn_close):
    return _sound(h, attn, jnp.full_like(gate, 1e4), lp, l, cfg)


def _post_norm_dropped(name):
    def norm(f, lp, which, l, cfg, _sound=am._post_norm):
        return (f.astype(jnp.float32) if which == name
                else _sound(f, lp, which, l, cfg))
    return norm


def _every_layer_in_the_page_pool(monkeypatch):
    """A sliding layer writes (and reads) the FULL layers' pool, at its
    own index among the sliding layers and through its slab's table."""
    monkeypatch.setattr(am, "_pools", lambda kind, kv, rs: (kv["k"],
                                                            kv["v"]))
    monkeypatch.setattr(am, "_put", lambda kind, kv, rs, k, v: (
        {"k": k, "v": v}, rs))


def _retraced():
    """The model's forward functions, each under a NEW function and a
    ``jax.jit`` of its own: a patched helper must be traced again, not
    found in the cache of the function it was traced under."""
    from types import SimpleNamespace

    def fresh(fn):
        def call(*args, **kw):
            return fn(*args, **kw)
        return jax.jit(call, static_argnums=(1,),
                       static_argnames=("last_only", "stats"))

    return SimpleNamespace(
        forward_prefill=fresh(am.forward_prefill.__wrapped__),
        forward_decode=fresh(am.forward_decode.__wrapped__))


FAULTS = [
    "no-gate", "no-qk-norm", "rope-on-a-full-layer",
    "no-rope-on-a-sliding-layer", "window-off", "window-one-too-wide",
    "window-one-too-narrow", "post-attention-norm-dropped",
    "post-mlp-norm-dropped", "no-sqrt-hidden-on-the-embedding",
    "bias-in-the-gates", "no-renormalisation", "no-route-scale",
    "shared-expert-dropped", "a-sliding-layer-writes-the-full-layers-cache"]


def _broken(name, monkeypatch, cfg, params):
    """``(cfg, params)`` as a program with that fault would serve them;
    the reference keeps the sound ones."""
    W = cfg.sliding_window
    if name == "no-gate":
        monkeypatch.setattr(am, "_attn_close", _no_gate)
    elif name == "no-qk-norm":
        monkeypatch.setattr(am, "_head_norm", lambda x, w, cfg: x)
    elif name == "rope-on-a-full-layer":
        monkeypatch.setattr(am, "_rotates", lambda cfg, l: True)
    elif name == "no-rope-on-a-sliding-layer":
        monkeypatch.setattr(am, "_rotates", lambda cfg, l: False)
    elif name == "window-off":
        monkeypatch.setattr(am, "_window", lambda cfg, kind: None)
    elif name.startswith("window-one-too"):
        off = 1 if name.endswith("wide") else -1
        monkeypatch.setattr(am, "_window", lambda cfg, kind: (
            W + off if kind == am.SLIDING else None))
    elif name.endswith("norm-dropped"):
        monkeypatch.setattr(am, "_post_norm", _post_norm_dropped(
            "post_attn_norm" if "attention" in name else "post_mlp_norm"))
    elif name == "no-sqrt-hidden-on-the-embedding":
        cfg = dataclasses.replace(cfg, mup_enabled=False)
    elif name == "bias-in-the-gates":
        monkeypatch.setattr(am, "route", _route_bias_in_the_gates)
    elif name == "no-renormalisation":
        cfg = dataclasses.replace(cfg, route_norm=False)
    elif name == "no-route-scale":
        cfg = dataclasses.replace(cfg, route_scale=1.0)
    elif name == "shared-expert-dropped":
        params = {**params, "moe": {
            **params["moe"],
            "ws_down": jnp.zeros_like(params["moe"]["ws_down"])}}
    elif name == "a-sliding-layer-writes-the-full-layers-cache":
        _every_layer_in_the_page_pool(monkeypatch)
    else:
        raise AssertionError(name)
    return cfg, params


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_path_fails_the_comparison(tiny, monkeypatch, fault):
    cfg, params, seq = tiny
    bad_cfg, bad_params = _broken(fault, monkeypatch, cfg, params)
    served, rows = serve(bad_cfg, bad_params, seq, (32, 64, 70),
                         fns=_retraced())
    got = verdict(cfg, params, seq, served, rows)
    assert not got["ok"], (fault, got)
    assert got["rms"] > 10 * TOL["rms"], (fault, got)


def test_the_control_one_precision_down_is_refused(tiny):
    """The reference's own ``lowp`` form (router product in bfloat16, K
    and V in 8 bits): what the comparison must refuse, and does."""
    cfg, params, seq = tiny
    rows = list(range(69, len(seq)))
    low, _ = reference.reference_forward(params, seq, hf_model(cfg), rows,
                                         lowp=True)
    got = verdict(cfg, params, seq, np.asarray(low), rows)
    assert not got["ok"] and got["rms_clean"] > 100 * TOL["rms_clean"], got


def test_the_kernels_serve_both_kinds_of_layer(tiny, monkeypatch):
    """``LLMQ_PALLAS=interpret``: the fused decode kernel, the prefill
    kernel and the prefill write kernel over the pool AND over the
    slabs, the sliding layers' calls with the window — bf16 operands in
    the kernels, so held to the pure path in the same type."""
    cfg, params, seq = tiny
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                          if x.dtype == jnp.float32 and x.ndim > 1 else x,
                          params)
    seq = seq[:60]
    monkeypatch.setenv("LLMQ_PALLAS", "0")
    pure, _ = serve(cfg, params, seq, (32, 50), fns=_retraced())
    monkeypatch.setenv("LLMQ_PALLAS", "interpret")
    routes = am.routes(am.serving_config(cfg), am.init_kv_pages(cfg, 2, PAGE),
                       batch=ROWS, page_size=PAGE,
                       max_pages=cfg.max_seq_len // PAGE, decode=True,
                       prefill_rows=1)
    assert routes["decode_attention"].startswith(
        "pallas-interpret:_fused_kernel")
    assert routes["decode_attention_window"].endswith("[window=24]")
    assert routes["prefill_attention_window"] == (
        "pallas-interpret:_prefill_attn_kernel[window=24]")
    served, _ = serve(cfg, params, seq, (32, 50), fns=_retraced())
    # by position: where the kernels' bf16 rounding swapped a token's
    # 4th and 5th expert in one of the 7 routed layers the logits differ
    # by 0.1-0.2 (four of the 11 positions here; unrelated logits differ
    # by 1.4); the others agree to bf16
    rms = np.sqrt(np.mean((served - pure) ** 2, -1))
    assert np.median(rms) < 0.03 and rms.max() < 0.5, rms


# -- through the executor and the engine --------------------------------------


def make_engine(tiny, batch=2, slots=0, **kw):
    cfg, params, _ = tiny
    tok = ByteTokenizer()
    ex = JaxExecutor(dataclasses.replace(cfg, page_size=0, slab_pages=0),
                     params, batch_size=batch, page_size=PAGE,
                     num_pages=96, prefill_buckets=[16, 32],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8,
                     row_tail_slots=slots)
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


def test_the_executor_binds_the_slabs_to_its_pages_and_its_step(tiny):
    cfg = tiny[0]
    eng, ex = make_engine(tiny, batch=3)
    # the window, the largest bucket (32 > the 8 of a mixed slice), a page
    n = (24 + 32) // PAGE + 1
    assert ex.model_cfg.page_size == PAGE and ex.model_cfg.slab_pages == n
    assert set(ex.cache) == {"k", "v"} and set(ex.row_state) == {"wk", "wv"}
    assert ex.cache["k"].shape[0] == cfg.n_full == 2
    # three rows' slabs behind the reserved page
    assert ex.row_state["wk"].shape == (cfg.n_sliding, 1 + 3 * n, PAGE, 128)
    per_row = am.row_state_bytes_per_row(ex.model_cfg)
    assert ex.row_state_bytes_per_row == per_row == sum(
        x.nbytes for x in jax.tree.leaves(ex.row_state)) // (3 * n + 1) * n
    assert ex.attention_window == {"tokens": 24, "layers": 6,
                                   "slab_tokens": n * PAGE}
    assert ex._window_chunk_tokens == 128
    stats = eng.get_stats()
    assert stats["row_state"]["bytes_per_row"] == per_row
    assert stats["window"]["dispatches"] == 0
    with pytest.raises(ValueError, match="names its sequence's batch row"):
        ex.prefill_async([1, 2, 3], 0, np.zeros(16, np.int32), 0.0)


def test_a_window_layer_s_cache_is_bounded_whatever_the_context(tiny):
    """A context of more than 3 x W through the engine: what a sliding
    layer holds for the row is its slab — the window, one step's
    writes and a page — while the full layers' pages grow; the tokens
    are those of the same sequence served alone from the start."""
    cfg = tiny[0]
    W = cfg.sliding_window
    prompt = "a prompt of fifty-odd bytes that passes the window twice"
    eng, ex = make_engine(tiny, batch=2)
    # (the tiny block table is ONE chunk of the decode kernel's plan:
    # count in chunks of two pages, so that the window start skips some)
    ex._window_chunk_tokens = 2 * PAGE
    got = generate(eng, "a", prompt, n=40)
    assert len(prompt) + 40 > 3 * W
    win = eng.get_stats()["window"]
    slab = win["slab_tokens"]
    assert slab <= W + 32 + PAGE             # W + a step's writes + a page
    assert win["cache_reserved_tokens"] == win["dispatches"] * 2 * slab
    assert 0 < win["cache_live_tokens"] <= win["dispatches"] * W
    assert win["window_tokens"] < win["context_tokens"]
    assert win["window_tokens"] <= win["dispatches"] * W
    assert win["chunks_skipped"] > 0 and win["chunks_visited"] > 0
    # a second sequence in the row the first left: nothing is zeroed,
    # what it left is masked
    again = generate(eng, "b", prompt, n=40)
    assert again.tokens == got.tokens and len(got.tokens) == 40


def test_a_prefix_match_is_adopted_where_there_are_tail_slots(tiny):
    """The same two prompts twice. Without tail slots (the default) the
    match is declined and counted, as it was before there were tails;
    with them the second prompt adopts the first one's blocks up to the
    deepest tail (the stride boundary at 32 of the 48 shared tokens:
    the window's last 24 tokens of K and V copied into its ring) and
    gives the tokens it gives cold (``tests/test_row_tails.py`` has the
    wrapped ring, the next turn and the broken import)."""
    shared = "the same forty-odd characters of system prompt: "
    plain, _ = make_engine(tiny)
    want = generate(plain, "b", shared + "second question")
    eng, _ = make_engine(tiny, prefix_cache=PrefixCacheConfig(enabled=True))
    generate(eng, "a", shared + "first question")
    second = generate(eng, "b", shared + "second question")
    assert second.cached_tokens == 0 and second.tokens == want.tokens
    assert eng.get_stats()["row_state"]["declined"]["prefix"] == 1
    assert "adopted" not in eng.get_stats()["row_state"]
    eng, ex = make_engine(
        tiny, slots=4,
        prefix_cache=PrefixCacheConfig(enabled=True, row_tail_slots=4))
    assert ex.row_tail == {"pages": 3, "stride": 32, "slack_tokens": 40,
                           "bytes": 6 * 3 * PAGE * 128 * 4 * 2, "slots": 4}
    assert ex.row_tails["wk"].shape == (6, 4 * 3, PAGE, 128)
    generate(eng, "a", shared + "first question")
    second = generate(eng, "b", shared + "second question")
    stats = eng.get_stats()["row_state"]
    assert second.cached_tokens == 32 and second.tokens == want.tokens
    assert stats["declined"]["prefix"] == 0 and stats["adopted"] == 1
    assert (stats["matched_tokens"], stats["match_cut_tokens"]) == (48, 16)


# -- the registry --------------------------------------------------------------


def test_registry_serves_the_family_at_the_published_sizes():
    assert model_names()["trinity-large-preview"] == "afmoe"
    assert model_names()["afmoe-tiny"] == "afmoe"
    cfg = get_config("trinity-large-preview")
    assert family_of(cfg) is am
    assert cfg.n_layers == 60 and cfg.n_sliding == 45 and cfg.n_full == 15
    assert cfg.layer_types[:5] == (am.SLIDING,) * 3 + (am.FULL, am.SLIDING)
    assert cfg.n_routed_layers == 54 and cfg.n_held == 256
    # the one-chip share of the benchmark's configuration
    share = dataclasses.replace(
        cfg, layer_types=cfg.layer_types[:5], n_dense_layers=1,
        held_experts=(0, 16), vocab_size=25024, max_seq_len=14336)
    assert am.param_count_analytic(share) == (
        2_509_962_240 + 5 * 2 * 128 + 4 * 256)   # + head norms and biases
    assert am.kv_bytes_per_token(share) == 4096
    bound = am.bind_cache(share, page_size=128, step_tokens=1024)
    assert bound.slab_pages == 41
    assert am.row_state_bytes_per_row(bound) == 4 * 41 * 128 * 4096
    state = jax.eval_shape(lambda: am.init_row_state(bound, 64))
    assert state["wk"].shape == (4, 1 + 64 * 41, 128, 1024)
    with pytest.raises(ValueError, match="bind_cache"):
        am.init_row_state(share, 64)


@pytest.mark.parametrize("what,match", [
    ("int8-weights", "model.quantization='int8'"),
    ("int8-cache", "model.kv_quantization='int8'"),
    ("mesh", "executor.mesh"),
])
def test_registry_refuses_with_an_error_that_names_the_setting(what, match):
    cfg = am.afmoe_tiny(max_seq_len=64)
    with pytest.raises(ValueError, match=match):
        if what == "int8-weights":
            am.init_params_quantized(jax.random.PRNGKey(0), cfg)
        else:
            params = jax.eval_shape(
                lambda: am.init_params(jax.random.PRNGKey(0), cfg))
            kw = {"int8-cache": dict(cache_dtype=jnp.int8),
                  "mesh": dict(mesh=jax.sharding.Mesh(
                      np.array(jax.devices()[:2]), ("tp",)))}[what]
            JaxExecutor(cfg, params, batch_size=2, page_size=8,
                        num_pages=16, **kw)


def test_a_published_checkpoint_is_loaded(tmp_path):
    """``import_hf_afmoe`` on a synthetic safetensors checkpoint under
    the published tensor names: the held experts, the held rows of the
    vocabulary, and the served logits those weights give."""
    from safetensors.numpy import save_file

    cfg = am.bind_cache(
        am.afmoe_tiny(dtype=jnp.float32, max_seq_len=128,
                      held_experts=(8, 16), vocab_size=384),
        page_size=PAGE, step_tokens=BUCKET)
    whole = dataclasses.replace(cfg, held_experts=None, vocab_size=512)
    src = am.init_params(jax.random.PRNGKey(9), whole)
    lp, moe_p, t = src["layers"], src["moe"], {}
    t["model.embed_tokens.weight"] = np.asarray(src["embed"])
    t["lm_head.weight"] = np.asarray(src["lm_head"]).T
    t["model.norm.weight"] = np.asarray(src["final_norm"])
    names = {"attn_norm": "input_layernorm",
             "post_attn_norm": "post_attention_layernorm",
             "mlp_norm": "pre_mlp_layernorm",
             "post_mlp_norm": "post_mlp_layernorm",
             "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm"}
    mats = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
            "wg": "gate_proj", "wo": "o_proj"}
    for l in range(cfg.n_layers):
        pre = f"model.layers.{l}."
        for k, v in names.items():
            t[f"{pre}{v}.weight"] = np.asarray(lp[k][l])
        for k, v in mats.items():
            t[f"{pre}self_attn.{v}.weight"] = np.asarray(lp[k][l]).T
        if l < cfg.n_dense_layers:
            for k in ("gate", "up", "down"):
                t[f"{pre}mlp.{k}_proj.weight"] = np.asarray(
                    src["dense"][f"w_{k}"][l]).T
            continue
        i = l - cfg.n_dense_layers
        t[f"{pre}mlp.router.gate.weight"] = np.asarray(moe_p["router"][i]).T
        t[f"{pre}mlp.expert_bias"] = 0.01 * np.random.default_rng(
            l).standard_normal(16).astype(np.float32)
        for k in ("gate", "up", "down"):
            t[f"{pre}mlp.shared_experts.{k}_proj.weight"] = np.asarray(
                moe_p[f"ws_{k}"][i]).T
        F = cfg.moe_ffn_dim
        for e in range(16):
            gu = np.asarray(moe_p["we_gate_up"][i][e])
            t[f"{pre}mlp.experts.{e}.gate_proj.weight"] = gu[:, :F].T
            t[f"{pre}mlp.experts.{e}.up_proj.weight"] = gu[:, F:].T
            t[f"{pre}mlp.experts.{e}.down_proj.weight"] = np.asarray(
                moe_p["we_down"][i][e]).T
    save_file({k: np.ascontiguousarray(v) for k, v in t.items()},
              str(tmp_path / "model.safetensors"))
    params = am.import_hf(str(tmp_path), cfg)
    want = jax.eval_shape(lambda: am.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(
        lambda x: x.shape, want)
    np.testing.assert_array_equal(
        np.asarray(params["moe"]["we_down"][2]),
        np.asarray(moe_p["we_down"][2][8:16]))
    seq = np.random.default_rng(2).integers(3, cfg.vocab_size, 50,
                                            dtype=np.int32)
    served, rows = serve(cfg, params, seq, (32, 44))
    got = verdict(cfg, params, seq, served, rows)
    assert got["ok"], got
