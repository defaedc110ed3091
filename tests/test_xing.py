"""The ``xing4_0`` block (``models/xing.py``: DeepSeek-V3's layer around
a residual of four streams mixed by hyper-connections, ``ops/hyper.py``,
under YaRN-scaled positions) held to its family's plain float32
reference (``benchmark/families/xing/reference.py``, which shares no
code with ``llmq_tpu``) at a tiny width, on seeded weights.

Logits, never tokens. The weights are float32, so the served path
differs from the reference by float32 rounding alone (``TOL``: measured
1e-6 to 5e-6 here), and each reading the published keys do not settle
(``assumed`` in ``benchmark/configs/xing4.0-29b-a4b-bf16-pp7.json``) has
a test that moves the result by ten times that limit or more when the
reading is dropped. One module-scoped fixture serves every test of the
model: a prompt past the tiny YaRN table's ``original_max_position``
through prefill, a mixed step and decode steps of one latent pool.
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
from llmq_tpu.models import deepseek_v3 as ds
from llmq_tpu.models import family_of, get_config, latent, xing
from llmq_tpu.ops import hyper, rows
from llmq_tpu.ops.rope import YarnScaling
from mixed_tight import CASES, check, tight_step  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(REPO, "benchmark", "families", "xing")
reference = contract.load_family(FAMILY, "reference")

PAGE, BUCKET = 8, 16
#: float32 against float32; the mildest dropped reading moves a logit's
#: RMS by 1e-3.
TOL = {"clean_quantile": 0.25, "rms_clean": 1e-4, "rms": 1e-4,
       "margin_eps": 1e-6}
SITE = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)


def hf_model(cfg):
    """The configuration under the public ``config.json``'s keys: what
    the reference reads."""
    y = cfg.rope_scaling
    return {"num_hidden_layers": cfg.n_layers,
            "first_k_dense_replace": cfg.first_k_dense,
            "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": cfg.q_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk_prob": cfg.norm_topk_prob, "n_group": 1,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "vocab_size": cfg.vocab_size, "hc_mult": cfg.hc_mult,
            "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
            "hc_eps": cfg.hc_eps, "mhc_h_res_clamp_min": cfg.hc_clamp[0],
            "mhc_h_res_clamp_max": cfg.hc_clamp[1],
            "rope_scaling": None if y is None else {
                "type": "yarn", "factor": y.factor,
                "original_max_position_embeddings": y.original_max_position,
                "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim}}


def tiny_params(cfg, seed=31):
    params = xing.init_params(jax.random.PRNGKey(seed), cfg)
    params["moe"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(5), params["moe"]["router_bias"].shape)
    # sites whose biases differ from stream to stream, as trained ones do
    bias = params["hc"]["bias"]
    params["hc"]["bias"] = bias + 0.5 * jax.random.normal(
        jax.random.PRNGKey(6), bias.shape)
    return params


def serve(cfg, params, seq, fns=xing):
    """``seq`` through the three programs over ONE pool: row 0 prefills
    3 buckets; a mixed step holds row 1's first two buckets as slices
    beside row 0's next token; both rows then decode. Returns (logits,
    positions, the counters of the passes)."""
    mp = cfg.max_seq_len // PAGE
    bts = (1 + np.arange(2 * mp).reshape(2, mp)).astype(np.int32)
    cache = xing.init_kv_pages(cfg, 1 + 2 * mp, PAGE)
    out, at, counters = [], [], []
    n0 = 3 * BUCKET
    for a in range(0, n0, BUCKET):
        pos = a + np.arange(BUCKET, dtype=np.int32)[None]
        logits, cache, st = fns.forward_prefill(
            params, cfg, jnp.asarray(seq[None, a:a + BUCKET]),
            jnp.asarray(pos), jnp.asarray([BUCKET], jnp.int32), cache,
            jnp.asarray(bts[:1]), stats=True)
        out.append(np.asarray(logits)[0])
        at += range(a, a + BUCKET)
        counters.append(np.asarray(st))
    # the mixed step: row 1's tokens 0..27 as two slices (16 + 12)
    lens = np.asarray([BUCKET, 12], np.int32)
    grid = np.zeros((2, 2, BUCKET), np.int32)
    grid[0, 0], grid[1, 0] = seq[:BUCKET], np.arange(BUCKET)
    grid[0, 1, :12], grid[1, 1, :12] = seq[BUCKET:28], BUCKET + np.arange(12)
    tok, pos, starts = rows.pack_grid(grid[0], grid[1], lens)
    dec, pf, cache, st = fns.forward_mixed(
        params, cfg, jnp.asarray([seq[n0], 0]), jnp.asarray([n0, 0]), cache,
        jnp.asarray(bts), jnp.asarray(tok), jnp.asarray(pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(bts[[1, 1]]),
        dec_active=jnp.asarray([True, False]), stats=True)
    out += [np.asarray(dec)[:1], np.asarray(pf)]
    at += [n0, BUCKET - 1, 27]
    counters.append(np.asarray(st))
    for j in range(len(seq) - n0 - 1):
        p = np.asarray([n0 + 1 + j, 28 + j], np.int32)
        logits, cache, st = fns.forward_decode(
            params, cfg, jnp.asarray(seq[p]), jnp.asarray(p), cache,
            jnp.asarray(bts), active=jnp.ones((2,), bool), stats=True)
        out.append(np.asarray(logits))
        at += list(p)
        counters.append(np.asarray(st))
    return np.concatenate(out), np.asarray(at), counters


@pytest.fixture(scope="module")
def tiny():
    cfg = xing.xing_tiny(dtype=jnp.float32, max_seq_len=64, n_layers=2)
    assert cfg.rope_scaling.original_max_position == 32
    params = tiny_params(cfg)
    seq = np.random.default_rng(31).integers(3, cfg.vocab_size, 56,
                                             dtype=np.int32)
    served, at, counters = serve(cfg, params, seq)
    ref, margins = reference.reference_forward(
        params, seq, hf_model(cfg), np.arange(len(seq)))
    return SimpleTiny(cfg, params, seq, served, at, counters,
                      np.asarray(ref), np.asarray(margins))


@dataclasses.dataclass
class SimpleTiny:
    cfg: object
    params: dict
    seq: np.ndarray
    served: np.ndarray
    at: np.ndarray
    counters: list
    ref: np.ndarray
    margins: np.ndarray

    def verdict(self, served=None):
        served = self.served if served is None else served
        return reference.judge(served, self.ref[self.at],
                               self.margins[self.at], TOL)


# -- a site ---------------------------------------------------------------------


def _site(seed=3, R=9, n=4, C=32, alpha=(0.7, 0.9, 1.5)):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    m = 2 * n + n * n
    return {"x": jax.random.normal(k[0], (R, n, C), jnp.float32) * 2.0,
            "y": jax.random.normal(k[1], (R, C), jnp.float32),
            "phi": jax.random.normal(k[2], (n * C, m), jnp.float32)
            * (n * C) ** -0.5,
            "alpha": jnp.asarray(alpha, jnp.float32),
            "bias": jax.random.normal(k[3], (m,), jnp.float32)}


def _program_site(s, sinkhorn=None, **kw):
    if sinkhorn is not None:
        real, hyper.sinkhorn = hyper.sinkhorn, sinkhorn
    try:
        h_pre, h_post, h_res = hyper.project(
            s["x"], s["phi"], s["alpha"], s["bias"], **{**SITE, **kw})
    finally:
        if sinkhorn is not None:
            hyper.sinkhorn = real
    return (hyper.read(s["x"], h_pre),
            hyper.write(s["x"], h_res, h_post, s["y"]), h_res)


def _reference_site(s):
    hc = {k: s[k][None, None] for k in ("phi", "alpha", "bias")}
    with jax.default_matmul_precision("highest"):
        u, h_post, h_res = reference.site_open(s["x"], hc, 0, 0, **SITE)
        return u, reference.site_shut(s["x"], h_post, h_res, s["y"]), h_res


def test_a_site_is_the_reference_s():
    """Both halves of a site of ``ops/hyper.py`` — the sub-layer's input
    and the new streams — against the reference's, which keeps its
    matrices (rows, n, n) and writes the mix as an einsum."""
    s = _site()
    u, x2, h_res = _program_site(s)
    ru, rx2, r_res = _reference_site(s)
    np.testing.assert_allclose(u, ru, atol=1e-5)
    np.testing.assert_allclose(x2, rx2, atol=1e-5)
    np.testing.assert_allclose(np.moveaxis(np.asarray(h_res), -1, 0), r_res,
                               atol=1e-6)


def test_h_res_is_doubly_stochastic_and_a_clamped_site_stays_finite():
    """At a trained site's spread (``alpha`` 0.1 on N(0, 1) products
    over the training-start biases) twenty steps leave every row and
    column sum within 1e-5 of 1 — ``row_sum_error`` is what the serving
    step counts; with scores far past the clamp (``alpha_res`` 1e4: every
    entry of A at -30 or +30, exp(A) from 1e-13 to 1e13) the site stays
    finite and non-negative."""
    s = _site(alpha=(0.1, 0.1, 0.1))
    s["bias"] = xing.hc_init(xing.xing_tiny())["bias"][0, 0]
    _, _, h_res = _program_site(s)
    h = np.asarray(h_res)
    assert np.abs(h.sum(0) - 1).max() < 1e-5
    assert np.abs(h.sum(1) - 1).max() < 1e-5
    assert float(hyper.row_sum_error(h_res).max()) == pytest.approx(
        np.abs(h.sum(1) - 1).max())
    s = _site(alpha=(0.7, 0.9, 1e4))
    u, x2, h_res = _program_site(s)
    a = np.asarray(s["x"].reshape(9, -1) @ s["phi"])[:, 8:] * 1e4
    assert (np.abs(a) > 30).mean() > 0.9
    for got in (u, x2, h_res):
        assert np.isfinite(np.asarray(got)).all()
    assert float(jnp.min(h_res)) >= 0.0
    np.testing.assert_allclose(x2, _reference_site(s)[1], atol=1e-4)


# -- ASSUMED: what the keys do not settle, one test each ------------------------


def _cols_first(a, iters, eps):
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def _eps_outside(a, iters, eps):
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / jnp.sum(m, axis=1, keepdims=True) + eps
        m = m / jnp.sum(m, axis=0, keepdims=True) + eps
    return m


@pytest.mark.parametrize("dropped, at_least", [(_cols_first, 1e-3),
                                               (_eps_outside, 1e-3)],
                         ids=["rows-before-columns", "eps-joins-each-sum"])
def test_assumed_the_sinkhorn_step(dropped, at_least):
    """ASSUMED: a Sinkhorn step normalises the rows, then the columns,
    and ``hc_eps`` joins each sum before the division. The limit of the
    iteration does not depend on the order, so the reading shows where
    twenty steps have not converged — a site whose scores spread widely
    (``alpha_res`` 6) — and in a row whose entries all sit at the lower
    clamp (its sum, 4e-13, is far under ``hc_eps``: with the sum joined
    the row stays small; normalised without it the row is 1 / n each)."""
    s = _site(alpha=(0.7, 0.9, 6.0))
    s["bias"] = s["bias"].at[8:12].set(-100.0)       # row 0 of A clamped
    _, x2, _ = _program_site(s)
    _, want, _ = _reference_site(s)
    assert np.abs(np.asarray(x2) - want).max() < 1e-4
    _, broken, _ = _program_site(s, sinkhorn=dropped)
    assert np.abs(np.asarray(broken) - want).max() > at_least


def _prefill_first(cfg, params, seq):
    """The first bucket alone, every position: one small program."""
    cache = xing.init_kv_pages(cfg, 1 + cfg.max_seq_len // PAGE, PAGE)
    bt = (1 + np.arange(cfg.max_seq_len // PAGE))[None].astype(np.int32)
    logits, _ = xing.forward_prefill.__wrapped__(
        params, cfg, jnp.asarray(seq[None, :BUCKET]),
        jnp.arange(BUCKET, dtype=jnp.int32)[None],
        jnp.asarray([BUCKET], jnp.int32), cache, jnp.asarray(bt))
    return np.asarray(logits)[0]


def _rms(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2, -1)).max())


hyper_project = hyper.project


@pytest.mark.parametrize("reading", ["norm-has-no-weight", "fan-out-copies",
                                     "collapse-sums", "m2-on-the-scale"])
def test_assumed_readings_of_the_model(tiny, monkeypatch, reading):
    """ASSUMED, one case each: the flattened norm before Phi carries no
    weight of its own (dropped: x~ is scaled — the RMS cancels a
    constant, so the weight here differs by stream); the embedding is
    COPIED into every stream (dropped: into stream 0 alone); the last
    streams are SUMMED (dropped: averaged — the final RMSNorm cancels a
    constant factor, so: stream 0 alone); YaRN's temperature squared
    multiplies the softmax scale (dropped: ``(dn + dr) ** -0.5`` alone).
    Each moves the first bucket's logits by over ten times ``TOL``."""
    t = tiny
    want = t.ref[:BUCKET]
    assert _rms(t.served[:BUCKET], want) < TOL["rms"]
    if reading == "norm-has-no-weight":
        w = jnp.asarray([0.5, 1.0, 1.5, 2.0])[None, :, None]
        monkeypatch.setattr(
            hyper, "project",
            lambda x, *a, **kw: hyper_project(x * w, *a, **kw))
    elif reading == "fan-out-copies":
        monkeypatch.setattr(
            xing, "_fan_out",
            lambda h, cfg: jnp.zeros((h.shape[0], cfg.hc_mult, h.shape[1]),
                                     h.dtype).at[:, 0].set(h))
    elif reading == "collapse-sums":
        monkeypatch.setattr(xing, "_collapse", lambda x: x[:, 0])
    else:
        monkeypatch.setattr(latent.LatentDims, "softmax_scale",
                            property(lambda self: self.qk_head_dim ** -0.5))
    assert _rms(_prefill_first(t.cfg, t.params, t.seq), want) > 10 * TOL["rms"]


# -- the served path against the reference ------------------------------------


def test_prefill_mixed_and_decode_through_the_pool_are_the_reference_s(tiny):
    """Three buckets of prefill (a continuation over cached pages), a
    mixed step (two slices of another row beside a decode row, one of
    them short, one batch row not active) and decode steps of both rows,
    all through one latent pool: every position's logits, positions 32
    to 55 past the tiny YaRN table's ``original_max_position``."""
    got = tiny.verdict()
    assert got["ok"] and got["near_tie_share"] == 0, got
    assert (tiny.at >= 32).sum() > 20 and got["positions"] == len(tiny.at)


def test_the_yarn_table_matters_past_the_original_positions(tiny):
    """The same weights without ``rope_scaling`` (plain frequencies, no
    temperature) are another model: the check above cannot pass by
    ignoring the keys."""
    t = tiny
    plain = dataclasses.replace(t.cfg, rope_scaling=None)
    served = _prefill_first(plain, t.params, t.seq)
    assert _rms(served, t.ref[:BUCKET]) > 10 * TOL["rms"]
    inv = latent.yarn_inv_freq(16, 10000.0, factor=8.0,
                               original_max_position=32)
    freqs, factor, temper = reference.yarn_table(hf_model(t.cfg))
    np.testing.assert_allclose(inv, freqs, rtol=1e-6)
    assert factor == 1.0
    assert temper == pytest.approx((0.1 * np.log(8.0) + 1) ** 2)
    assert t.cfg.softmax_scale == pytest.approx(48 ** -0.5 * temper)


@pytest.mark.parametrize("part", reference.LOWP)
def test_the_control_s_parts_are_each_refused_at_float32(tiny, part):
    """The benchmark's control holds four things one precision down
    (``reference.LOWP``). On the chip the served path's bfloat16 products
    read above three of them (the router's product, the streams and the
    Sinkhorn steps are float32 in the program), so the cell's limit
    refuses the four together by the cache's 8 bits; here, at float32,
    each ALONE moves the logits past ``TOL``."""
    t = tiny
    low, _ = reference.reference_forward(t.params, t.seq, hf_model(t.cfg),
                                         t.at, lowp=(part,))
    got = t.verdict(np.asarray(low))
    assert not got["ok"] and got["median"] > 2 * TOL["rms_clean"], got


def test_the_step_counter_holds_the_sites_to_one(tiny):
    """``hc_row_sum_err`` (``step_stats_layout``): every pass's worst
    ``|row sum - 1|`` x 1e6 — small at these sites, and the rows that
    are not live (a bucket's padding, the batch row that is not active)
    do not count."""
    cfg = tiny.cfg
    layout = xing.step_stats_layout(cfg)
    assert layout["hc_row_sum_err"] == xing.step_stats_size(cfg) - 1
    assert layout["runs"] == ds.step_stats_layout(cfg)["runs"]
    for st in tiny.counters:
        assert st.shape == (xing.step_stats_size(cfg),)
        assert 0 <= st[layout["hc_row_sum_err"]] < 100
        assert st[layout["runs"]] == cfg.n_routed_layers


@pytest.mark.parametrize("case", [*CASES])
def test_the_tight_mixed_step_computes_what_the_parts_do(tight_step, case):
    """``forward_mixed`` — one tight stream, the decode rows leading,
    front and close in live tiles of 8 rows, the sites among them —
    against ``forward_prefill`` of each slice + ``forward_decode`` of the
    rows over the same pool (``tests/mixed_tight.py``): a tile's edge
    inside a slice, an unused slice, a continuation, a decode row that
    is not active (48 slice rows behind 3 decode rows that lead: the
    rows loop)."""
    cfg = xing.xing_tiny(dtype=jnp.float32, max_seq_len=64, n_layers=2)
    check(tight_step, xing, cfg, tiny_params(cfg, 55), case, page=4)


@pytest.mark.parametrize("tokens", [1, 224, 225, 4100, 8192])
def test_mixed_live_rows_is_the_rows_the_program_ran(tokens):
    """``mixed_live_rows`` (and ``hc_rows_live``, which adds the decode
    rows) at the served shape — sixteen 512-token slices behind 32
    decode rows that LEAD them — against the rows ``live_rows`` computes
    with that lead."""
    S, T, B = 16, 512, 32
    tile = rows.row_tile(T)
    got = xing.mixed_live_rows(tokens, B, S, T)
    assert xing.hc_rows_live(tokens, B, S, T) == got + B
    run = jax.jit(lambda x, n: rows.live_rows(lambda t: t + 1, n, tile, x,
                                              lead=B))
    out = np.asarray(run(jnp.zeros((B + S * T, 1), jnp.float32),
                         jnp.int32(B + tokens)))
    assert int((out > 0).sum()) - B == got
    assert tokens <= got < tokens + tile and got <= S * T


# -- through the engine ---------------------------------------------------------


def test_the_engine_serves_the_family_and_adopts_a_prefix_hit(tiny):
    """App's path below the queue: engine -> executor -> the family's
    three programs, with nothing of the family's own outside ``models/``
    and ``ops/``. The cache is pages alone, so a second prompt that
    shares 40 tokens with the first is served from the radix cache's
    latent pages (as Kanana's, whose test holds the adopted path to the
    cold one's tokens on the same engine code), and asked again gives
    the same tokens; the sites' counter comes through ``get_stats()``,
    and the executor counts a mixed step's site rows by the family's
    rule."""
    cfg, params = tiny.cfg, tiny.params
    tok = ByteTokenizer()

    def engine(**kw):
        ex = JaxExecutor(cfg, params, batch_size=2, page_size=8,
                         num_pages=64, prefill_buckets=[16],
                         eos_id=tok.eos_id, chunk_size=4,
                         mixed_prefill_slices=2, mixed_slice_tokens=8)
        return InferenceEngine(
            ex, tok, enable_metrics=False, max_decode_steps=16,
            mixed_batch=MixedBatchConfig(enabled=True,
                                         prefill_token_budget=16,
                                         max_slices=2), **kw), ex

    def generate(eng, rid, prompt):
        h = eng.submit(GenRequest(id=rid, prompt=prompt, max_new_tokens=8,
                                  temperature=0.0))
        assert h.wait(120)
        return h.result

    shared = "the same forty-odd characters of system prompt: "
    eng, ex = engine(prefix_cache=PrefixCacheConfig(enabled=True))
    assert set(ex.cache) == {"ckv"} and ex.row_state is None
    assert ex.hc_rows_live(5) == xing.hc_rows_live(5, 2, 2, 8) == 2 + 16
    assert ex.slice_tokens("mixed_chunk", 5) == 16
    eng.start()
    first = generate(eng, "a", shared + "first question")
    second = generate(eng, "b", shared + "second question")
    again = generate(eng, "c", shared + "second question")
    stats = eng.get_stats()
    eng.stop()
    assert first.cached_tokens == 0 and second.cached_tokens >= 40
    assert second.tokens and again.tokens == second.tokens
    moe = stats["moe"]
    steps = moe["layer_runs"] / cfg.n_routed_layers
    assert steps > 0 and 0 <= moe["hc_row_sum_err_sum"] < 100 * steps


# -- hc_mult 1 is the family deepseek_v3 --------------------------------------


def test_one_stream_and_plain_positions_is_deepseek_v3_bit_for_bit():
    """``hc_mult`` 1 without ``rope_scaling`` is ``models/deepseek_v3``'s
    program: the same weights give the same logits to the bit through
    prefill and decode, and through the mixed step (tight rows here, the
    grid there) to the last float32 digit."""
    kw = dict(dtype=jnp.float32, max_seq_len=64, n_layers=2, q_lora_rank=48)
    d_cfg = ds.deepseek_v3_tiny(**kw)
    x_cfg = xing.xing_tiny(hc_mult=1, rope_scaling=None,
                           rope_theta=d_cfg.rope_theta, **kw)
    params = ds.init_params(jax.random.PRNGKey(7), d_cfg)
    assert "hc" not in xing.init_params(jax.random.PRNGKey(7), x_cfg)
    assert xing.param_count_analytic(x_cfg) == ds.param_count_analytic(d_cfg)
    seq = np.random.default_rng(7).integers(3, 512, 56, dtype=np.int32)
    mine, at, _ = serve(x_cfg, params, seq)
    theirs, at2, _ = serve(d_cfg, params, seq, fns=ds)
    assert (at == at2).all()
    mixed = slice(3 * BUCKET, 3 * BUCKET + 3)
    exact = np.ones(len(at), bool)
    exact[mixed] = False
    assert (mine[exact][:3 * BUCKET] == theirs[exact][:3 * BUCKET]).all()
    np.testing.assert_allclose(mine, theirs, atol=2e-5)


# -- the registry and the refusals ----------------------------------------------


def test_the_published_model_is_registered_with_its_sizes():
    cfg = get_config("xing4.0-29b-a4b")
    assert family_of(cfg) is xing and cfg.FAMILY == "xing"
    assert (cfg.dim, cfg.n_layers, cfg.hc_mult, cfg.q_lora_rank) == (
        3584, 40, 4, 768)
    assert hyper.site_param_count(4, 3584) == 344_091
    assert round(xing.param_count_analytic(cfg) / 1e9, 1) == 29.5
    assert round(xing.active_param_count(cfg) / 1e9, 1) == 4.4
    held = dataclasses.replace(cfg, n_layers=6, first_k_dense=1)
    assert xing.param_count_analytic(held) == 4_792_669_828
    assert cfg.rope_scaling == YarnScaling(64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 2.00474, rel=1e-5)
    assert xing.kv_bytes_per_token(held) == 6_912
    assert xing.init_row_state(held, 4) is None


@pytest.mark.parametrize("kw, named", [
    ({"quantization": "int8"}, "model.quantization='int8'"),
    ({"kv_quantization": "int8"}, "model.kv_quantization='int8'"),
    ({"mesh": True}, "executor.mesh")])
def test_check_serving_refuses_by_name(kw, named):
    with pytest.raises(ValueError) as e:
        xing.check_serving(xing.xing_tiny(), **kw)
    assert named in str(e.value) and "family xing" in str(e.value)


def test_a_served_multi_token_layer_is_refused_by_name():
    """The program: ``n_nextn_served``; the benchmark's adapter: a file
    with ``num_nextn_predict_layers`` that does not list it under
    ``left_out`` asks for it to be served."""
    with pytest.raises(ValueError, match="num_nextn_predict_layers=1"):
        xing.xing_tiny(n_nextn_served=1)
    adapter = contract.load_family(FAMILY, "adapter")
    bench = contract.load_benchmark()
    config = contract.resolve_cell(bench, "xing4-longdoc-saturated")["config"]
    assert config["num_nextn_predict_layers"] == 1
    got = adapter.register("xing-test-left-out", config)
    assert got.n_nextn_served == 0 and got.n_layers == 6
    assert got.first_k_dense == 1 and got.n_routed_layers == 5
    with pytest.raises(ValueError, match="num_nextn_predict_layers=1"):
        adapter.register("xing-test-served", {**config, "left_out": []})
    with pytest.raises(ValueError, match="every expert on the chip"):
        adapter.register("xing-test-ep", {**config, "ep_size": 8})
    xing.MODEL_CONFIGS.pop("xing-test-left-out")
