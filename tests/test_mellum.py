"""The JetBrains ``mellum`` block (``models/mellum.py``: grouped-query
attention whose layers are sliding-window or full, each rotated by the
table of its kind — plain, or YaRN's with its attention factor — a
per-head norm on q and k, a softmax-routed feed-forward in every layer;
the sliding layers' keys and values in ``models/afmoe``'s slabs) held to
its family's plain float32 reference
(``benchmark/families/mellum/reference.py``, which shares no code with
``llmq_tpu``) at a tiny width, on seeded weights.

Logits, never tokens. The weights here are float32, so the served path
differs from the reference by float32 rounding alone and the comparison
is tight (``TOL``): each broken path and each ``assumed`` item left out
moves the logits by ten times that and more. The tiny model's window is
24 tokens and its test sequence 100, so every sliding layer's slab
wraps several times.
"""

import dataclasses
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import contract
from llmq_tpu.core.config import MixedBatchConfig
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import family_of, get_config, model_names
from llmq_tpu.models import mellum as ml
from llmq_tpu.ops import moe
from llmq_tpu.ops.rope import rope_cos_sin, yarn_inv_freq
from llmq_tpu.ops.rows import pack_grid
from mixed_tight import (CASES, JOINED, RING, check,  # noqa: F401
                         check_served, tight_step)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(REPO, "benchmark", "families", "mellum")
reference = contract.load_family(FAMILY, "reference")

PAGE, BUCKET, ROWS = 8, 32, 3
#: float32 against float32: measured 1e-6 to 3e-6 here.
F32 = 2e-5
TOL = {"clean_quantile": 0.25, "rms_clean": F32, "rms": F32,
       "margin_eps": 1e-7}
#: ``rope_parameters.full_attention`` as published.
PUBLISHED_YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                  "original_max_position_embeddings": 8192, "beta_fast": 32,
                  "beta_slow": 1, "attention_factor": 1.2772588722239782}


def hf_model(cfg):
    """The configuration under the public ``config.json``'s keys: what
    the reference reads."""
    y = cfg.rope_full
    full = ({"rope_type": "default", "rope_theta": cfg.rope_theta}
            if y is None else
            {"rope_type": "yarn", "rope_theta": cfg.rope_theta,
             "factor": y.factor,
             "original_max_position_embeddings": y.original_max_position,
             "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
             "attention_factor": y.attention_factor})
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "vocab_size": cfg.vocab_size,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "layer_types": list(cfg.layer_types),
            "mlp_layer_types": ["sparse"] * cfg.n_layers,
            "sliding_window": cfg.sliding_window,
            "moe_intermediate_size": cfg.moe_ffn_dim,
            "num_experts": cfg.n_routed_experts,
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "norm_topk_prob": cfg.route_norm, "qk_norm": cfg.qk_norm,
            "rms_norm_eps": cfg.norm_eps,
            "rope_parameters": {
                "full_attention": full,
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": cfg.rope_theta}}}


@pytest.fixture(scope="module")
def tiny():
    cfg = ml.bind_cache(ml.mellum_tiny(dtype=jnp.float32, max_seq_len=128),
                        page_size=PAGE, step_tokens=BUCKET)
    params = ml.init_params(jax.random.PRNGKey(41), cfg)
    # gains that are not one: a norm left out must show
    for i, n in enumerate(("q_norm", "k_norm")):
        params["layers"][n] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(7 + i), params["layers"][n].shape)
    seq = np.random.default_rng(41).integers(3, cfg.vocab_size, 100,
                                             dtype=np.int32)
    return cfg, params, seq


def block_table(cfg, n_rows=ROWS):
    mp = cfg.max_seq_len // PAGE
    return (1 + np.arange(n_rows)[:, None] * mp
            + np.arange(mp)[None, :]).astype(np.int32)


def new_cache(cfg, n_rows=ROWS):
    return (ml.init_kv_pages(cfg, 1 + n_rows * (cfg.max_seq_len // PAGE),
                             PAGE), ml.init_row_state(cfg, n_rows))


def prefill(cfg, params, cache, state, bt, seq, start, end, row,
            last_only=True):
    """One bucket-padded prefill of seq[start:end] at its absolute
    positions in batch row ``row``."""
    n = end - start
    toks = np.zeros((1, BUCKET), np.int32)
    toks[0, :n] = seq[start:end]
    pos = start + np.minimum(np.arange(BUCKET, dtype=np.int32), n - 1)[None]
    logits, cache, state = ml.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([n], jnp.int32), cache, jnp.asarray(bt[row:row + 1]),
        last_only=last_only, row_state=state,
        rows=jnp.asarray([row], jnp.int32))
    return np.asarray(logits)[0], cache, state


def decode(cfg, params, cache, state, bt, seq, first, row):
    """Teacher-forced decode steps of batch row ``row`` from position
    ``first`` to the end of ``seq`` (the other rows not active)."""
    out = []
    active = jnp.asarray(np.arange(ROWS) == row)
    for p in range(first, len(seq)):
        tok, pos = np.zeros(ROWS, np.int32), np.zeros(ROWS, np.int32)
        tok[row], pos[row] = seq[p], p
        logits, cache, state = ml.forward_decode(
            params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
            jnp.asarray(bt), active=active, row_state=state)
        out.append(np.asarray(logits)[row])
    return out, cache, state


def serve(cfg, params, seq, cuts, row=1):
    """Prefill seq[:cuts[-1]] in the slices ``cuts`` bounds, in batch
    row ``row``, then decode to the end of ``seq``. Returns the logits
    at positions cuts[-1] - 1 .. len(seq) - 1 and those positions."""
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    start = 0
    for end in cuts:
        logits, cache, state = prefill(cfg, params, cache, state, bt, seq,
                                       start, end, row)
        start = end
    rest, _, _ = decode(cfg, params, cache, state, bt, seq, cuts[-1], row)
    return np.stack([logits] + rest), list(range(cuts[-1] - 1, len(seq)))


def ref_logits(cfg, params, seq, rows):
    got, margins = reference.reference_forward(params, seq, hf_model(cfg),
                                               rows)
    return np.asarray(got), np.asarray(margins)


def held(served, cfg, params, seq, rows):
    ref, margins = ref_logits(cfg, params, seq, rows)
    return reference.judge(served, ref, margins, TOL)


# -- the served path against the reference -------------------------------------


def test_prefill_and_decode_through_slabs_and_pages(tiny):
    """A prompt of 70 in three slices (the ring wraps: 70 > W + the
    slack), then 30 decode steps: every judged position is the
    reference's to float32 rounding."""
    cfg, params, seq = tiny
    served, rows = serve(cfg, params, seq, (32, 64, 70))
    got = held(served, cfg, params, seq, rows)
    assert got["ok"] and got["positions"] == 31, got


def test_the_mixed_step_is_the_same_model(tiny):
    """The same prompt through ``forward_mixed``, one live slice of 8 a
    step beside a row that decodes, then the decode steps."""
    cfg, params, seq = tiny
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    S, T, row = 2, 8, 2
    for a in range(0, 64, T):
        g_t, g_p = np.zeros((S, T), np.int32), np.zeros((S, T), np.int32)
        g_t[0], g_p[0] = seq[a:a + T], np.arange(a, a + T)
        lens = np.array([T, 1], np.int32)
        pf_tok, pf_pos, pf_start = pack_grid(g_t, g_p, lens, used=1)
        pf_bts = np.zeros((S, bt.shape[1]), np.int32)
        pf_bts[0] = bt[row]
        _, pf_logits, cache, state = ml.forward_mixed(
            params, cfg, jnp.zeros((ROWS,), jnp.int32),
            jnp.zeros((ROWS,), jnp.int32), cache, jnp.asarray(bt),
            jnp.asarray(pf_tok), jnp.asarray(pf_pos), jnp.asarray(lens),
            jnp.asarray(pf_start), jnp.asarray(pf_bts),
            dec_active=jnp.zeros((ROWS,), bool), row_state=state,
            pf_rows=jnp.asarray([row, ROWS], jnp.int32))
    rest, _, _ = decode(cfg, params, cache, state, bt, seq, 64, row)
    served = np.stack([np.asarray(pf_logits)[0]] + rest)
    got = held(served, cfg, params, seq, list(range(63, len(seq))))
    assert got["ok"], got


@pytest.mark.parametrize("served", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", [*CASES, *JOINED, *RING])
def test_the_tight_mixed_step_computes_what_the_parts_do(tight_step, case,
                                                         served):
    """``forward_mixed`` — one stream, the decode rows leading the
    slices' tight tokens, front and close in live tiles of 8 rows, the
    experts over the live pairs — against ``forward_prefill`` of each
    slice + ``forward_decode`` of the rows over the same pool AND slabs
    (``tests/mixed_tight.py``): the logits, the full layers' pages and
    the sliding layers' slabs. A tile's edge inside a slice, an unused
    slice, one token, a continuation behind cached history, all slices
    full with the last tile moved back, a decode row that is not
    active; at a window of 4 tokens in slabs of 24, so every case
    passes the window and ``RING``'s wraps the ring; both kinds of
    layer. In float32, and in bfloat16 as served with the programs
    compiled to round where their source rounds
    (``mixed_tight._forward``): there the two ways read 0.0 apart in
    ten of the twelve cases and one bfloat16 step (0.008-0.03) in two —
    the live pairs' results are added to their token in the sorted
    order, not the slots'. (Left to keep a bfloat16 chain wide inside a
    fusion, XLA's CPU backend does so differently in a loop's body: the
    logits then read 0.03-0.55 apart through swapped experts.)"""
    cfg = ml.bind_cache(
        ml.mellum_tiny(dtype=jnp.bfloat16 if served else jnp.float32,
                       max_seq_len=64, sliding_window=4),
        page_size=4, step_tokens=16)
    assert ml.attention_window(cfg)["slab_tokens"] == 24
    params = ml.init_params(jax.random.PRNGKey(55), cfg)
    if served:
        return check_served(tight_step, ml, cfg, params, case, page=4,
                            atol=6e-2, pages_atol=6e-2, as_written=True)
    check(tight_step, ml, cfg, params, case, page=4)


def test_the_adopted_path_gives_the_cold_path_s_logits(tiny):
    """What the prefix cache does for this family, at the model's own
    functions: row 0 prefills 64 tokens and its tail before E = 48 is
    exported once the ring has wrapped (48 > W + slack - a bucket);
    row 2 — whose ring holds another sequence's K and V — imports it,
    shares row 0's pages below E, prefills seq[E:70] from E and decodes:
    the reference's logits, to the same float32 limit. Without the
    import the same steps are refused."""
    cfg, params, seq = tiny
    tail = ml.row_tail(cfg)
    assert tail["pages"] == 3 and tail["stride"] == 32
    E = 48
    bt = block_table(cfg)

    def adopted(do_import):
        cache, state = new_cache(cfg)
        # row 2's ring first holds a sequence of its own
        other = (seq[::-1] * 7 + 3) % cfg.vocab_size
        for a in (0, 32):
            _, cache, state = prefill(cfg, params, cache, state, bt,
                                      other, a, a + 32, 2)
        for a in (0, 32):
            _, cache, state = prefill(cfg, params, cache, state, bt, seq,
                                      a, a + 32, 0)
        tails = ml.init_row_tails(cfg, 2)
        tails = ml.export_row_tail(cfg, state, tails, jnp.int32(0),
                                   jnp.int32(E // PAGE), jnp.int32(1))
        if do_import:
            state = ml.import_row_tail(cfg, state, tails, jnp.int32(1),
                                       jnp.int32(2), jnp.int32(E // PAGE))
        bt2 = bt.copy()
        bt2[2, :E // PAGE] = bt[0, :E // PAGE]
        logits, cache, state = prefill(cfg, params, cache, state, bt2, seq,
                                       E, 70, 2, last_only=False)
        rest, _, _ = decode(cfg, params, cache, state, bt2, seq, 70, 2)
        return np.concatenate([logits[:70 - E], np.stack(rest)])

    rows = list(range(E, len(seq)))
    got = held(adopted(True), cfg, params, seq, rows)
    assert got["ok"] and got["positions"] == len(seq) - E, got
    broken = held(adopted(False), cfg, params, seq, rows)
    assert not broken["ok"] and broken["rms_clean"] > 10 * F32, broken


# -- the rotary tables ----------------------------------------------------------


def closed_form(head_dim, rope):
    """YaRN's frequencies written out once more, in float64, from the
    five published numbers."""
    half, theta = head_dim // 2, float(rope["rope_theta"])
    n0, s = rope["original_max_position_embeddings"], rope["factor"]
    f = theta ** (-np.arange(half) / half)

    def pair(b):
        return head_dim * math.log(n0 / (2 * math.pi * b)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair(rope["beta_fast"])), 0)
    hi = min(math.ceil(pair(rope["beta_slow"])), half - 1)
    gamma = np.clip((np.arange(half) - lo) / (hi - lo), 0, 1)
    return f / s * gamma + f * (1 - gamma), (lo, hi)


def test_the_yarn_table_is_the_closed_form_at_the_published_numbers():
    want, (lo, hi) = closed_form(128, PUBLISHED_YARN)
    assert (lo, hi) == (18, 35)
    got = np.asarray(yarn_inv_freq(
        128, 500000.0, factor=16, original_max_position=8192,
        beta_fast=32, beta_slow=1))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = 500000.0 ** (-np.arange(64) / 64)
    # the fast pairs as they were, the slow ones a sixteenth, a ramp between
    np.testing.assert_allclose(got[:19], plain[:19], rtol=2e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=2e-6)
    assert np.all(np.diff(got / plain)[18:35] < 0)
    # the reference's own writing-out agrees
    ref, factor = reference.inv_freq(PUBLISHED_YARN, 128)
    np.testing.assert_allclose(ref, want, rtol=2e-6)
    assert factor == pytest.approx(0.1 * math.log(16) + 1)
    assert ml.Yarn().attention_factor == factor


def test_each_kind_of_layer_rotates_by_its_own_table():
    cfg = get_config("mellum2-12b-a2.5b")
    pos = jnp.asarray([[0, 1, 1023, 8191, 32767]], jnp.int32)
    tables = ml.rope_tables(cfg, pos)
    cos_p, sin_p = rope_cos_sin(pos, 128, 500000.0)
    np.testing.assert_array_equal(tables[ml.SLIDING][0], cos_p)
    np.testing.assert_array_equal(tables[ml.SLIDING][1], sin_p)
    cos_f, sin_f = tables[ml.FULL]
    a = ml.Yarn().attention_factor
    # cos^2 + sin^2 = the factor squared, at every position and pair
    np.testing.assert_allclose(np.asarray(cos_f ** 2 + sin_f ** 2), a * a,
                               rtol=1e-5)
    # the fast pairs turn as the plain ones, the slowest a sixteenth
    np.testing.assert_allclose(np.asarray(cos_f[..., :19]),
                               a * np.asarray(cos_p[..., :19]), atol=1e-3)
    slow = np.asarray(pos, np.float64)[..., None] * (
        500000.0 ** (-np.arange(35, 64) / 64) / 16)
    np.testing.assert_allclose(np.asarray(sin_f[..., 35:]), a * np.sin(slow),
                               atol=2e-3)
    # cut to another context the factor stays what was published
    short = dataclasses.replace(cfg, max_seq_len=32768)
    assert short.rope_full.attention_factor == a


# -- the window's edge ------------------------------------------------------------


def test_the_window_sees_its_last_w_keys_and_no_more(tiny):
    """i - j = W - 1 is seen, i - j = W is not: the logits at position
    p do not move when the token at p - W is changed under a model of
    sliding layers alone, and do when the token at p - W + 1 is."""
    cfg, params, seq = tiny
    only = dataclasses.replace(cfg, layer_types=(ml.SLIDING,) * 2)
    p_only = jax.tree.map(lambda x: x, params)
    W, p = cfg.sliding_window, 60

    def at(tokens):
        served, rows = serve(only, p_only, tokens[:p + 1], (32, p))
        return served[rows.index(p)]

    base = at(seq)
    # two layers: layer 1's query p reads layer 0's outputs back to
    # p - W + 1, which read tokens back to p - 2 W + 2
    outside, inside = seq.copy(), seq.copy()
    outside[p - 2 * W + 1] = (seq[p - 2 * W + 1] + 7) % cfg.vocab_size
    inside[p - 2 * W + 2] = (seq[p - 2 * W + 2] + 7) % cfg.vocab_size
    assert np.abs(at(outside) - base).max() == 0.0
    assert np.abs(at(inside) - base).max() > 1e-4
    # and the reference draws the same edge
    model = hf_model(only)
    ref = lambda t: np.asarray(reference.reference_forward(
        p_only, t[:p + 1], model, [p])[0][0])
    assert np.abs(ref(outside) - ref(seq)).max() < 1e-6
    assert np.abs(ref(inside) - ref(seq)).max() > 1e-4


# -- routing ----------------------------------------------------------------------


def test_routing_is_softmax_then_top_k_renormalised(tiny):
    cfg, params, _ = tiny
    y = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.dim), jnp.float32)
    w = params["layers"]["router"][0]
    experts, gates = moe.route(
        y, w, jnp.zeros((cfg.n_routed_experts,), jnp.float32),
        top_k=cfg.n_experts_per_tok, scale=1.0, norm_topk=True,
        scoring="softmax")
    s = np.asarray(jax.nn.softmax(
        jnp.dot(y, w, precision=jax.lax.Precision.HIGHEST), -1), np.float64)
    want = np.argsort(-s, -1)[:, :cfg.n_experts_per_tok]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(want, -1))
    g = np.take_along_axis(s, np.asarray(experts), -1)
    np.testing.assert_allclose(np.asarray(gates),
                               g / g.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-5)
    # softmax over ALL experts, not over the chosen: without the
    # renormalisation the gates are the probabilities themselves
    _, raw = moe.route(
        y, w, jnp.zeros((cfg.n_routed_experts,), jnp.float32),
        top_k=cfg.n_experts_per_tok, scale=1.0, norm_topk=False,
        scoring="softmax")
    np.testing.assert_allclose(np.asarray(raw), g, rtol=1e-5)
    assert np.all(np.asarray(raw).sum(-1) < 1.0)
    # ... in float32 at the highest matmul precision: the same scores
    # from a bfloat16 product are off by a hundred times the limit the
    # gates are held to above (on the chip no judged distribution shows
    # a router's precision: benchmark/families/mellum/README.md)
    low = jax.nn.softmax(jnp.dot(
        y.astype(jnp.bfloat16), w.astype(jnp.bfloat16)).astype(jnp.float32))
    low = np.take_along_axis(np.asarray(low, np.float64),
                             np.asarray(experts), -1)
    assert np.abs(low / g - 1).max() > 100 * 1e-5


# -- what is assumed, and what the published numbers say --------------------------


def _without(name):
    def model(cfg):
        if name == "qk_norm":
            return dataclasses.replace(cfg, qk_norm=False)
        if name == "yarn":
            return dataclasses.replace(cfg, rope_full=None)
        if name == "attention_factor":
            return dataclasses.replace(cfg, rope_full=dataclasses.replace(
                cfg.rope_full, attention_factor=1.0))
        if name == "norm_topk_prob":
            return dataclasses.replace(cfg, route_norm=False)
        raise KeyError(name)
    return model


@pytest.mark.parametrize("item", ["qk_norm", "yarn", "attention_factor",
                                  "norm_topk_prob"])
def test_an_item_left_out_is_refused_by_ten_times_the_float32_limit(tiny,
                                                                    item):
    """The ``assumed`` per-head norm, and what the published
    ``rope_parameters`` and ``norm_topk_prob`` say: the served path
    WITHOUT the item, against the reference with it."""
    cfg, params, seq = tiny
    served, rows = serve(_without(item)(cfg), params, seq, (32, 64, 70))
    got = held(served, cfg, params, seq, rows)
    assert not got["ok"] and got["rms_clean"] > 10 * F32, got


# -- the registry ----------------------------------------------------------------


def test_registry_serves_the_family_at_the_published_sizes():
    assert model_names()["mellum2-12b-a2.5b"] == "mellum"
    assert model_names()["mellum-tiny"] == "mellum"
    cfg = get_config("mellum2-12b-a2.5b")
    assert family_of(cfg) is ml
    assert cfg.n_layers == 28 and cfg.n_sliding == 21 and cfg.n_full == 7
    assert cfg.layer_types[:4] == (ml.SLIDING,) * 3 + (ml.FULL,)
    assert round(ml.param_count_analytic(cfg) / 1e9, 2) == 12.15
    assert round(ml.active_param_count(cfg) / 1e9, 2) == 2.44
    # the benchmark's cut: the first 12 layers
    cut = dataclasses.replace(cfg, layer_types=cfg.layer_types[:12],
                              max_seq_len=32768)
    assert ml.param_count_analytic(cut) == 5_465_959_680
    assert ml.kv_bytes_per_token(cut) == 6_144
    bound = ml.bind_cache(cut, page_size=128, step_tokens=512)
    assert bound.slab_pages == 13
    assert ml.row_state_bytes_per_row(bound) == 9 * 13 * 128 * 512 * 2 * 2
    tail = ml.row_tail(bound)
    assert tail == {"pages": 8, "stride": 2048, "slack_tokens": 640,
                    "bytes": 18_874_368}
    tails = jax.eval_shape(lambda: ml.init_row_tails(bound, 4))
    assert tails["wk"].shape == (9, 32, 128, 512)
    with pytest.raises(ValueError, match="bind_cache"):
        ml.init_row_state(cut, 8)


@pytest.mark.parametrize("what,match", [
    ("int8-weights", "model.quantization='int8'"),
    ("int8-cache", "model.kv_quantization='int8'"),
    ("mesh", "executor.mesh"),
])
def test_registry_refuses_with_an_error_that_names_the_setting(what, match):
    cfg = ml.mellum_tiny(max_seq_len=64)
    with pytest.raises(ValueError, match=match):
        if what == "int8-weights":
            ml.init_params_quantized(jax.random.PRNGKey(0), cfg)
        else:
            params = jax.eval_shape(
                lambda: ml.init_params(jax.random.PRNGKey(0), cfg))
            kw = {"int8-cache": dict(cache_dtype=jnp.int8),
                  "mesh": dict(mesh=jax.sharding.Mesh(
                      np.array(jax.devices()[:2]), ("tp",)))}[what]
            JaxExecutor(cfg, params, batch_size=2, page_size=8,
                        num_pages=16, **kw)


# -- through the engine -------------------------------------------------------------


def make_engine(tiny, batch=2, slots=0, **kw):
    cfg, params, _ = tiny
    tok = ByteTokenizer()
    ex = JaxExecutor(dataclasses.replace(cfg, page_size=0, slab_pages=0),
                     params, batch_size=batch, page_size=PAGE,
                     num_pages=160, prefill_buckets=[16, 32],
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


def test_the_engine_serves_it_and_counts_its_experts(tiny):
    cfg = tiny[0]
    eng, ex = make_engine(tiny)
    got = generate(eng, "a", "a prompt of forty-odd bytes through the engine")
    assert len(got.tokens) == 12
    stats = eng.get_stats()
    assert stats["moe"]["layer_runs"] > 0
    assert len(stats["moe"]["load"]) == cfg.n_routed_experts
    assert stats["window"]["tokens"] == 24 and stats["window"]["layers"] == 6
    assert set(ex.row_state) == {"wk", "wv"} and ex.row_tail is None
    assert "adopted" not in stats["row_state"]
