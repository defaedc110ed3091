"""The latent prefill attention (``models/latent.latent_prefill_attention``:
a loop over the LIVE key blocks of each row's block-table window with a
running softmax) held to a plain one-window attention written here, and
the two latent families' prefilling programs held to what they computed
when the attention scored the whole window at once.

The geometry is the served one at a quarter: slices of 128 tokens over
32-token pages and a 16-page table (512 tokens), so a key block is 4
pages = 128 tokens and the table holds 4 — where the benchmark's
configurations have 512-token slices, 128-token pages, 2,048-token
tables. The cases' positions are the served ones divided by four.
"""

import functools
import re
import threading
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llmq_tpu.core.config import MixedBatchConfig
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import deepseek_v3 as ds
from llmq_tpu.models import latent
from llmq_tpu.models import longcat_flash as lf
from llmq_tpu.ops.rows import pack_grid

T, PAGE, MAX_PAGES = 128, 32, 16


class _Dims(latent.LatentDims, SimpleNamespace):
    """What the attention reads of a configuration."""


def dims(heads: int, scaled: bool, dtype):
    """32 heads with s_kv 1 (Kanana's kind) or 64 heads with s_kv
    sqrt(12) (LongCat's), at small head widths."""
    return _Dims(dim=384, n_heads=heads, kv_lora_rank=32, q_lora_rank=None,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 mla_scale_q_lora=False, mla_scale_kv_lora=scaled,
                 norm_eps=1e-6, dtype=dtype)


def whole_window(cfg, lp, l, q_nope, q_rope, pool, block_tables, positions,
                 seq_lens):
    """The attention as it was before the loop: every row's WHOLE
    block-table window gathered, expanded and scored at once (float32
    scores of (B, H, T, table tokens))."""
    B, T_ = q_nope.shape[:2]
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    rows = pool[l][block_tables].reshape(B, -1, pool.shape[-1])
    wk, wv = latent.wkv_b(cfg, lp, l)
    k_nope = jnp.einsum("bsr,rhn->bshn", rows[..., :r], wk)
    v = jnp.einsum("bsr,rhv->bshv", rows[..., :r], wv)
    s_nope = jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
    s_kv = cfg.kv_scale
    if s_kv != 1.0:
        s_nope = s_nope * s_kv
    s = s_nope + jnp.einsum("bthr,bsr->bhts", q_rope, rows[..., r:r + dr],
                            preferred_element_type=jnp.float32)
    key_pos = jnp.arange(rows.shape[1])
    mask = ((key_pos[None, None, :] <= positions[:, :, None])
            & (key_pos[None, None, :] < seq_lens[:, None, None]))
    p = jax.nn.softmax(jnp.where(mask[:, None], s * cfg.qk_head_dim ** -0.5,
                                 latent.NEG), axis=-1)
    if s_kv != 1.0:
        o = (jnp.einsum("bhts,bshv->bthv", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
             * s_kv).astype(v.dtype)
    else:
        o = jnp.einsum("bhts,bshv->bthv", p.astype(v.dtype), v)
    return o.reshape(B, T_, -1)


def plain(cfg, w, q_nope, q_rope, pool_l, block_tables, positions, seq_lens):
    """One window, float64, NumPy: softmax((q_nope . s_kv k_nope +
    q_rope . k_rope) / sqrt(d)) (s_kv v) over the keys a position sees;
    zeros for a row with no context."""
    f = lambda a: np.asarray(a.astype(jnp.float32), np.float64)  # noqa: E731
    q_nope, q_rope, pool_l, w = f(q_nope), f(q_rope), f(pool_l), f(w)
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv, H = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.n_heads
    w = w.reshape(r, H, dn + dv)
    B, T_ = q_nope.shape[:2]
    out = np.zeros((B, T_, H, dv))
    for b in range(B):
        if seq_lens[b] == 0:
            continue
        rows = pool_l[block_tables[b]].reshape(-1, pool_l.shape[-1])
        c, k_rope = rows[:, :r], rows[:, r:r + dr]
        k_nope = np.einsum("sr,rhn->shn", c, w[..., :dn]) * cfg.kv_scale
        v = np.einsum("sr,rhv->shv", c, w[..., dn:]) * cfg.kv_scale
        s = (np.einsum("thn,shn->hts", q_nope[b], k_nope)
             + np.einsum("thr,sr->hts", q_rope[b], k_rope))
        s *= cfg.qk_head_dim ** -0.5
        key = np.arange(rows.shape[0])
        see = ((key[None, :] <= positions[b][:, None])
               & (key[None, :] < seq_lens[b]))
        s = np.where(see[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("hts,shv->thv", p, v)
    return out.reshape(B, T_, -1)


#: case -> the call's rows as (start, valid tokens); (0, 0) is a row
#: with no context (seq_len 0). The served positions divided by four.
CASES = {
    "fresh-prompt-inside-one-block": [(0, 75)],
    "prompt-ends-on-a-block-edge": [(0, 128)],
    "continuation-ends-on-a-block-edge": [(128, 128)],
    "continuation-at-175-over-cached-pages-2-blocks": [(175, 75)],
    "context-of-375-3-blocks": [(300, 75)],
    "full-table-4-blocks": [(384, 128)],
    "two-slices-of-unequal-trip-counts": [(0, 75), (300, 75)],
    "no-context-beside-a-live-slice": [(0, 0), (175, 75)],
    "right-padded-positions": [(40, 9), (0, 1)],
}


def _call(case, cfg, seed=0):
    rng = np.random.default_rng(seed)
    rows = CASES[case]
    B, H, W = len(rows), cfg.n_heads, cfg.latent_width
    n_pages = 1 + B * MAX_PAGES
    pool = rng.standard_normal((2, n_pages, PAGE, W)) * 0.5
    pool[..., cfg.kv_lora_rank + cfg.qk_rope_head_dim:] = 0.0
    bt = np.zeros((B, MAX_PAGES), np.int32)
    positions = np.zeros((B, T), np.int32)
    seq_lens = np.zeros((B,), np.int32)
    for b, (start, n) in enumerate(rows):
        if n:
            # pages in no order, only as far as the context reaches
            held = -(-(start + n) // PAGE)
            bt[b, :held] = 1 + b * MAX_PAGES + rng.permutation(MAX_PAGES)[
                :held]
            # the executor's layout: padding repeats the last position
            positions[b] = start + np.minimum(np.arange(T), n - 1)
            seq_lens[b] = start + n
    w = rng.standard_normal((2, cfg.kv_lora_rank, H * (
        cfg.qk_nope_head_dim + cfg.v_head_dim))) * cfg.kv_lora_rank ** -0.5
    q_nope = rng.standard_normal((B, T, H, cfg.qk_nope_head_dim))
    q_rope = rng.standard_normal((B, T, H, cfg.qk_rope_head_dim))
    d = cfg.dtype
    return ({"wkv_b": jnp.asarray(w, d)}, jnp.asarray(q_nope, d),
            jnp.asarray(q_rope, d), jnp.asarray(pool, d), bt, positions,
            seq_lens)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads,scaled", [(32, False), (64, True)],
                         ids=["32-heads-s_kv-1", "64-heads-s_kv-sqrt12"])
@pytest.mark.parametrize("case", list(CASES))
def test_block_loop_against_a_plain_window(case, heads, scaled, dtype, tol):
    """The limits are absolute, times s_kv (outputs of order 0.1 s_kv
    to 0.5 s_kv). float32: rounding alone, measured up to 1e-6.
    bfloat16: the reference is computed in float64 from the same
    bfloat16 inputs, so the difference is the served precisions' (bf16
    K, V and probabilities, float32 scores and sums): measured 0.7e-3
    to 9e-3 here, where the whole-window form read 0.7e-3 to 1.1e-2."""
    cfg = dims(heads, scaled, dtype)
    lp, q_nope, q_rope, pool, bt, positions, seq_lens = _call(case, cfg)
    got = latent.latent_prefill_attention(
        cfg, lp, 1, q_nope, q_rope, pool, jnp.asarray(bt),
        jnp.asarray(positions), jnp.asarray(seq_lens))
    assert got.shape == (len(bt), T, heads * cfg.v_head_dim)
    assert got.dtype == dtype
    got = np.asarray(got.astype(jnp.float32), np.float64)
    assert np.isfinite(got).all()
    want = plain(cfg, lp["wkv_b"][1], q_nope, q_rope, pool[1], bt, positions,
                 seq_lens)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * cfg.kv_scale)
    for b, (_, n) in enumerate(CASES[case]):
        if not n:
            assert not got[b].any()        # zeros, not a mean of trash


def test_the_loop_runs_the_live_blocks_and_no_more():
    """The rule the program and the executor's count share."""
    k = latent.prefill_key_blocks
    assert k(np.array([75]), T, PAGE, MAX_PAGES) == (4, 1, 4)
    assert k(np.array([128]), T, PAGE, MAX_PAGES) == (4, 1, 4)
    assert k(np.array([129, 3]), T, PAGE, MAX_PAGES) == (4, 2, 4)
    assert k(np.array([0]), T, PAGE, MAX_PAGES) == (4, 0, 4)
    assert k(np.array([512]), T, PAGE, MAX_PAGES) == (4, 4, 4)
    # the served geometry: 512-token slices, 128-token pages, 16 pages
    assert k(np.array([300]), 512, 128, 16) == (4, 1, 4)
    # a slice narrower than a page, a table narrower than a slice, a
    # table that is no whole number of blocks
    assert k(np.array([40]), 8, 16, 6) == (1, 3, 6)
    assert k(np.array([40]), 128, 16, 6) == (6, 1, 1)
    assert k(np.array([90]), 48, 16, 8) == (3, 2, 3)
    # side by side every slice runs the longest's blocks; one at a time
    # each its own (an empty slot of the executor's is one trash token)
    ctx = np.array([75, 375, 1, 1])
    assert ds.mixed_key_blocks(ctx, T, PAGE, MAX_PAGES) == (12, 16)
    assert lf.mixed_key_blocks(ctx, T, PAGE, MAX_PAGES) == (6, 16)


def test_a_table_that_is_no_whole_number_of_blocks():
    """48-token slices over 16-token pages: a block is 3 pages and the
    8-page table ends inside the third block."""
    cfg = dims(32, False, jnp.float32)
    rng = np.random.default_rng(5)
    H, W, t, ps, mp = cfg.n_heads, cfg.latent_width, 48, 16, 8
    pool = jnp.asarray(rng.standard_normal((1, 1 + mp, ps, W)) * 0.5,
                       jnp.float32)
    bt = (1 + rng.permutation(mp))[None].astype(np.int32)
    positions = (80 + np.arange(t))[None].astype(np.int32)
    seq_lens = np.array([128], np.int32)
    lp = {"wkv_b": jnp.asarray(rng.standard_normal(
        (1, 32, H * 32)) * 32 ** -0.5, jnp.float32)}
    q_nope = jnp.asarray(rng.standard_normal((1, t, H, 16)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((1, t, H, 8)), jnp.float32)
    got = latent.latent_prefill_attention(
        cfg, lp, 0, q_nope, q_rope, pool, jnp.asarray(bt),
        jnp.asarray(positions), jnp.asarray(seq_lens))
    want = plain(cfg, lp["wkv_b"][0], q_nope, q_rope, pool[0], bt, positions,
                 seq_lens)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=0,
                               atol=2e-5)


# -- the families' programs against the whole-window form ---------------------

TINY_PAGE, TINY_T = 8, 16         # a block is 2 pages; the table holds 4


def _tiny(fam):
    if fam is lf:
        cfg = lf.longcat_flash_tiny(dtype=jnp.float32, max_seq_len=64,
                                    held_experts=(8, 16))
    else:
        cfg = ds.deepseek_v3_tiny(dtype=jnp.float32, max_seq_len=64)
    return cfg, fam.init_params(jax.random.PRNGKey(35), cfg)


def _programs(fam, monkeypatch, attention):
    """``forward_prefill`` and ``forward_mixed`` traced anew with
    ``attention`` as the family's prefill attention."""
    monkeypatch.setattr(fam, "latent_prefill_attention", attention)

    def anew(fn, static):
        # JAX keeps traces by the function's identity: a wrapper of its
        # own, or the second attention would find the first one's trace
        @functools.wraps(fn)
        def run(*args, **kw):
            return fn(*args, **kw)
        return jax.jit(run, static_argnames=static)

    return (anew(fam.forward_prefill.__wrapped__,
                 ("cfg", "last_only", "stats")),
            anew(fam.forward_mixed.__wrapped__, ("cfg", "stats")))


def _drive(fam, cfg, params, programs):
    """A prompt prefilled to a block edge, continued across it, then a
    mixed step: a fresh slice, a continuation into the table's third
    block and two decode rows (one of them not active). Every output
    and the pool."""
    forward_prefill, forward_mixed = programs
    rng = np.random.default_rng(35)
    seq = rng.integers(3, cfg.vocab_size, 60, dtype=np.int32)
    mp = cfg.max_seq_len // TINY_PAGE
    bt = (1 + np.arange(3)[:, None] * mp + np.arange(mp)[None, :]).astype(
        np.int32)
    cache = fam.init_kv_pages(cfg, 1 + 3 * mp, TINY_PAGE)
    outs = []

    def prefill(cache, row, start, n):
        toks = np.zeros((1, TINY_T), np.int32)
        toks[0, :n] = seq[start:start + n]
        pos = start + np.minimum(np.arange(TINY_T, dtype=np.int32), n - 1)
        logits, cache = forward_prefill(
            params, cfg, jnp.asarray(toks), jnp.asarray(pos[None]),
            jnp.asarray([n], jnp.int32), cache, jnp.asarray(bt[row:row + 1]))
        outs.append(np.asarray(logits)[0, :n])
        return cache

    cache = prefill(cache, 0, 0, 16)          # ends on the block's edge
    cache = prefill(cache, 0, 16, 14)         # two blocks
    cache = prefill(cache, 1, 0, 5)
    pf_tok = np.zeros((2, TINY_T), np.int32)
    pf_tok[0, :11], pf_tok[1, :12] = seq[40:51], seq[30:42]
    pf_pos = np.stack([np.minimum(np.arange(TINY_T), 10),
                       30 + np.minimum(np.arange(TINY_T), 11)]).astype(
                           np.int32)
    tok, pos, starts = pack_grid(pf_tok, pf_pos, [11, 12])
    out = forward_mixed(
        params, cfg, jnp.asarray([seq[5], 0]), jnp.asarray([5, 0], jnp.int32),
        cache, jnp.asarray(bt[1:3]), jnp.asarray(tok), jnp.asarray(pos),
        jnp.asarray([11, 12], jnp.int32), jnp.asarray(starts),
        jnp.asarray(bt[[2, 0]]), dec_active=jnp.asarray([True, False]))
    dec, pf, cache = out[:3]
    outs += [np.asarray(dec)[0], np.asarray(pf)]
    # page 0 is every masked write's target: trash by convention
    return outs, np.asarray(cache["ckv"])[:, 1:]


@pytest.mark.parametrize("fam", [ds, lf], ids=["deepseek_v3",
                                               "longcat_flash"])
def test_the_families_programs_compute_what_they_did(fam, monkeypatch):
    """float32 weights: the two forms differ by the order of float32
    sums alone (measured 4e-6 to 5e-6 on logits up to 5, and as much on
    the pool's rows)."""
    cfg, params = _tiny(fam)
    new, pool_new = _drive(fam, cfg, params, _programs(
        fam, monkeypatch, latent.latent_prefill_attention))
    old, pool_old = _drive(fam, cfg, params, _programs(
        fam, monkeypatch, whole_window))
    for a, b in zip(new, old):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-4)
    np.testing.assert_allclose(pool_new, pool_old, rtol=0, atol=1e-4)


def _f32_shapes(text):
    return {tuple(int(d) for d in m.split("x"))
            for m in re.findall(r"tensor<((?:\d+x)+)f32>", text)
            for m in [m.rstrip("x")]}


def test_no_float32_scores_over_the_table_in_the_mixed_program(monkeypatch):
    """The lowered ``forward_mixed`` of a tiny LongCat whose table
    holds 4 blocks (24-token slices over 8-token pages: a block is 3
    pages, the table 12 = 96 tokens; no other size of the model is 96)
    holds no float32 array over a slice's tokens AND the table's: the
    scores never span the table again. The whole-window form does: what
    the search would find."""
    cfg = lf.longcat_flash_tiny(dtype=jnp.float32, max_seq_len=96,
                                held_experts=(8, 16))
    params = lf.init_params(jax.random.PRNGKey(35), cfg)
    t, mp, H = 24, 12, cfg.n_heads
    cache = lf.init_kv_pages(cfg, 1 + 4 * mp, TINY_PAGE)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

    def lowered(attention):
        _, forward_mixed = _programs(lf, monkeypatch, attention)
        return forward_mixed.lower(
            params, cfg, i32(2), i32(2), cache, i32(2, mp), i32(2 * t),
            i32(2 * t), i32(2), i32(3), i32(2, mp)).as_text()

    def over_the_table(text):
        return [s for s in _f32_shapes(text)
                if H in s and t in s and mp * TINY_PAGE in s]

    assert over_the_table(lowered(whole_window))
    text = lowered(latent.latent_prefill_attention)
    assert not over_the_table(text)
    # ... while the scores of ONE block are there
    assert [s for s in _f32_shapes(text)
            if H in s and s.count(t) >= 2], "no (H, T, block) scores found"


# -- the counter, through the executor and the engine -------------------------


def test_the_engine_counts_the_key_blocks_of_its_mixed_steps():
    cfg = ds.deepseek_v3_tiny(dtype=jnp.float32, max_seq_len=128)
    params = ds.init_params(jax.random.PRNGKey(35), cfg)
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=3, page_size=TINY_PAGE,
                     num_pages=96, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8)
    eng = InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=24,
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                     max_slices=2))
    eng.start()
    try:
        first = eng.submit(GenRequest(id="a", prompt="a long decode " * 2,
                                      max_new_tokens=24, temperature=0.0))
        seen = threading.Event()         # decoding: the next prompts'
        first.on_token(lambda *a: seen.set())    # slices ride mixed steps
        assert seen.wait(120)
        rest = [eng.submit(GenRequest(id=f"b{i}", prompt="x" * 40,
                                      max_new_tokens=4, temperature=0.0))
                for i in range(2)]
        assert first.wait(120) and all(h.wait(120) for h in rest)
        stats = eng.get_stats()
    finally:
        eng.stop()
    assert stats["mixed_batch"]["steps"] > 0
    kb = stats["mixed_key_blocks"]
    # 8-token slices over 8-token pages: a block is a page, a table 16;
    # a 40-token prompt's slices see 1 to 5 blocks, never the table
    steps = stats["mixed_batch"]["steps"]
    assert kb["table"] == steps * 2 * 16
    assert steps * 2 <= kb["visited"] <= steps * 2 * 5
