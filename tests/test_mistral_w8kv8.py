"""Mistral-7B-v0.3 as it is deployed on one chip — w8a8 weights over an
int8 paged cache, GQA 4:1, head 128, 128-token pages — held to the
plain float32 reference (``benchmark/harness/reference.py``, which
shares no code with ``llmq_tpu``) at a tiny width, on seeded weights.

The state is built by the EXECUTOR's own programs (``prefill_b128``,
then ``decode_chunk`` through the paged int8 cache, then a
``mixed_chunk`` with one prompt slice riding on the decoding rows);
those return sampled tokens, so the logits at each compared position
are read with one teacher-forced ``forward_decode`` over the cache the
programs left behind, and ``forward_prefill(last_only=True)`` gives the
prefill's own. Logits, never tokens: random weights give near-ties.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness.reference import reference_logits
from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.models import llama
from llmq_tpu.models.llama import (forward_decode, forward_prefill,
                                   get_config, init_kv_pages,
                                   init_params_quantized)
from llmq_tpu.ops.attention import (dispatch_prefill_attention_q8,
                                    paged_kv_write_prefill_q8)
from mixed_tight import (CASES, JOINED, check, check_served,  # noqa: F401
                         tight_step)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Error RMS over reference RMS (the reference's logits have RMS about
#: 1). The float32 reference takes an int8 weight at its dequantised
#: value and has no activation or KV quantisation, so what is left is
#: the served path's own rounding: dynamic int8 activations in every
#: matmul, int8 K/V with one bf16 scale per token and head, bf16
#: activations. On the chip, at the published widths and 32 layers,
#: that measured 0.064-0.077 (PERF.md, PR 23 and PR 26), and the
#: benchmark's file allows 0.15; here, at 2 layers, it measures
#: 0.03-0.05. 0.1 is above both and far under what a wrong scale, page
#: or head mapping gives (about 1.4: the ``scales-forced-to-1`` case).
TOL_RMS = 0.1

PAGE, BUCKET, CHUNK, ROWS, SLICE = 128, 128, 4, 4, 64
N_PROMPT = (100, 37)          # rows 0 and 1, prefilled through the bucket
N_SLICE = 50                  # row 2, rides the mixed step as one slice


def tiny_mistral():
    return get_config("mistral-7b-v0.3", vocab_size=512, dim=512,
                      n_layers=2, n_heads=4, n_kv_heads=1, ffn_dim=512,
                      max_seq_len=512)


def rel_rms(got, ref):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.sqrt(np.mean((got - ref) ** 2)) /
                 np.sqrt(np.mean(ref ** 2)))


@pytest.fixture(scope="module")
def served():
    """Runs the schedule once; every case reads what it left."""
    cfg = tiny_mistral()
    assert (cfg.head_dim, cfg.n_heads // cfg.n_kv_heads) == (128, 4)
    params = init_params_quantized(jax.random.PRNGKey(26), cfg)
    ex = JaxExecutor(cfg, params, batch_size=ROWS, page_size=PAGE,
                     num_pages=1 + ROWS * 4, prefill_buckets=[BUCKET],
                     chunk_size=CHUNK, prefill_batch=1, eos_id=-1,
                     cache_dtype=jnp.int8, mixed_prefill_slices=1,
                     mixed_slice_tokens=SLICE)
    assert set(ex.cache) == {"k", "v", "k_scale", "v_scale"}
    mcfg, MP = ex.model_cfg, ex.spec.max_pages_per_seq
    rng = np.random.default_rng(26)
    bts = np.zeros((ROWS, MP), np.int32)
    for r in range(ROWS):
        bts[r] = 1 + r * MP + np.arange(MP)
    active2 = np.arange(ROWS) < 2

    def ref(seq, row):
        return np.asarray(reference_logits(
            params, np.asarray(seq, np.int32), n_layers=cfg.n_layers,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            eps=cfg.norm_eps, theta=cfg.rope_theta, rows=[row]))[0]

    def probe(cache, seqs, active):
        """Teacher-forced next-step logits of rows 0..len(seqs)-1 over
        ``cache``: the row's newest token at its position, attending to
        everything the programs wrote before it."""
        tok = np.zeros(ROWS, np.int32)
        pos = np.zeros(ROWS, np.int32)
        for r, s in enumerate(seqs):
            tok[r], pos[r] = s[-1], len(s) - 1
        logits, _ = forward_decode(
            params, mcfg, jnp.asarray(tok), jnp.asarray(pos), cache,
            jnp.asarray(bts), active=jnp.asarray(active))
        return np.asarray(logits, np.float32)

    out = {}
    # 1. the prefill bucket, one prompt a call (executor.prefill)
    seqs = []
    for r, n in enumerate(N_PROMPT):
        prompt = rng.integers(3, cfg.vocab_size, n).tolist()
        first = ex.prefill(prompt, 0, bts[r], 0.0, r)
        seqs.append(prompt + [int(first)])
    got = probe(ex.cache, seqs, active2)
    out["decode-after-prefill"] = [
        (got[r], ref(seqs[r], len(seqs[r]) - 1)) for r in range(2)]

    # 2. a decode chunk through the paged int8 cache
    tok = np.zeros(ROWS, np.int32)
    pos = np.zeros(ROWS, np.int32)
    for r in range(2):
        tok[r], pos[r] = seqs[r][-1], len(seqs[r]) - 1
    budgets = np.where(active2, CHUNK, 0).astype(np.int32)
    chunk = ex.decode_chunk(tok, pos, bts, np.zeros(ROWS, np.float32),
                            budgets)
    for r in range(2):
        seqs[r] += [int(t) for t in chunk[r]]
    got = probe(ex.cache, seqs, active2)
    out["decode-after-chunk"] = [
        (got[r], ref(seqs[r], len(seqs[r]) - 1)) for r in range(2)]

    # 3. a mixed step: rows 0-1 decode one step, row 2's prompt slice
    #    rides the same program
    slice_toks = rng.integers(3, cfg.vocab_size, N_SLICE).tolist()
    for r in range(2):
        tok[r], pos[r] = seqs[r][-1], len(seqs[r]) - 1
    budgets = np.where(active2, 1, 0).astype(np.int32)
    mixed, pf_first = ex.mixed_chunk_start(
        tok, pos, bts, np.zeros(ROWS, np.float32), budgets,
        [(2, slice_toks, 0, bts[2], 0.0)]).fetch()
    for r in range(2):
        seqs[r].append(int(mixed[r, 0]))
    seqs.append(slice_toks + [int(pf_first[0])])
    got = probe(ex.cache, seqs, np.arange(ROWS) < 3)
    out["mixed-decode-rows"] = [
        (got[r], ref(seqs[r], len(seqs[r]) - 1)) for r in range(2)]
    out["mixed-slice-row"] = [(got[2], ref(seqs[2], len(seqs[2]) - 1))]

    # 4. the same probe with every K/V scale forced to 1: must be far out
    ones = dict(ex.cache,
                k_scale=jnp.ones_like(ex.cache["k_scale"]),
                v_scale=jnp.ones_like(ex.cache["v_scale"]))
    bad = probe(ones, seqs, np.arange(ROWS) < 3)
    out["scales-forced-to-1"] = [(bad[r], o[1]) for r, o in enumerate(
        out["mixed-decode-rows"] + out["mixed-slice-row"])]

    # 5. the prefill program's own logits (last position), fresh pool
    n = N_PROMPT[0]
    toks = np.zeros((1, BUCKET), np.int32)
    toks[0, :n] = seqs[0][:n]
    posn = np.minimum(np.arange(BUCKET, dtype=np.int32), n - 1)[None]
    fresh = init_kv_pages(mcfg, 1 + ROWS * 4, PAGE, dtype=jnp.int8)
    args = (jnp.asarray(toks), jnp.asarray(posn),
            jnp.asarray([n], jnp.int32), fresh, jnp.asarray(bts[:1]))
    logits, _ = forward_prefill(params, mcfg, *args, last_only=True)
    out["prefill"] = [(np.asarray(logits, np.float32)[0],
                       ref(seqs[0][:n], n - 1))]
    # 6. rolled against unrolled, every operation rounded on its own
    with jax.disable_jit():
        out["rolled"] = (
            forward_prefill(params, mcfg, *args, last_only=True),
            unrolled_prefill(params, mcfg, *args))
    return out


def unrolled_prefill(params, cfg, tokens, positions, lengths, kv_cache,
                     block_tables):
    """``forward_prefill(last_only=True)`` over int8 pools with the
    layers as a Python loop, as the program ran before its loop was
    rolled: the same per-layer operations in the same order. Run under
    ``jax.disable_jit()`` the two agree bit for bit. COMPILED they do
    not, on any backend: XLA fuses a loop body differently from
    unrolled code and keeps bf16 intermediates in float32 inside a
    fusion, so from the second layer on the int8 K/V differ by a step
    (0.07 in the logits here) — which is why the compiled program is
    held to the reference's tolerance and not to the old program."""
    from llmq_tpu.models.llama import _logits, _mlp
    from llmq_tpu.ops.norms import rms_norm
    from llmq_tpu.ops.quant import embed_lookup, layer_slice, linear
    from llmq_tpu.ops.rope import apply_rope, rope_cos_sin

    def run(params, tokens, positions, lengths, kv_cache, block_tables):
        B, T = tokens.shape
        h = embed_lookup(params["embed"], tokens, cfg.dtype)
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        valid = jnp.arange(T)[None, :] < lengths[:, None]
        seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
        lp = params["layers"]
        pools = (kv_cache["k"], kv_cache["v"], kv_cache["k_scale"],
                 kv_cache["v_scale"])
        for l in range(cfg.n_layers):
            hn = rms_norm(h, lp["attn_norm"][l], cfg.norm_eps)
            q, k, v = (linear(hn, layer_slice(lp[w], l)).reshape(
                B, T, heads, cfg.head_dim) for w, heads in (
                    ("wq", cfg.n_heads), ("wk", cfg.n_kv_heads),
                    ("wv", cfg.n_kv_heads)))
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            pools = paged_kv_write_prefill_q8(
                pools, k, v, block_tables, positions, lengths,
                jnp.int32(l))
            attn = dispatch_prefill_attention_q8(
                q, pools, block_tables, positions, seq_lens, l)
            h = h + linear(attn.reshape(B, T, -1),
                           layer_slice(lp["wo"], l))
            hn2 = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
            h = h + _mlp(hn2, layer_slice(lp["w_gate"], l),
                         layer_slice(lp["w_up"], l),
                         layer_slice(lp["w_down"], l))
        h = rms_norm(h[jnp.arange(B), lengths - 1], params["final_norm"],
                     cfg.norm_eps)
        return _logits(params, h), dict(zip(
            ("k", "v", "k_scale", "v_scale"), pools))

    return run(params, tokens, positions, lengths, kv_cache, block_tables)


@pytest.mark.parametrize("case", [
    "prefill", "decode-after-prefill", "decode-after-chunk",
    "mixed-decode-rows", "mixed-slice-row", "scales-forced-to-1",
    "rolled-equals-unrolled"])
def test_w8kv8_path_against_float32_reference(served, case):
    if case == "rolled-equals-unrolled":
        (logits, cache), (u_logits, u_cache) = served["rolled"]
        assert np.array_equal(np.asarray(logits), np.asarray(u_logits))
        for name in ("k", "v", "k_scale", "v_scale"):
            assert np.array_equal(np.asarray(cache[name]),
                                  np.asarray(u_cache[name])), name
        assert np.asarray(cache["k"]).any()
        return
    errs = [rel_rms(got, ref) for got, ref in served[case]]
    for _got, ref in served[case]:
        assert 0.5 < float(np.sqrt(np.mean(ref ** 2))) < 2.0
    if case == "scales-forced-to-1":
        # the tolerance is tight enough to refuse a wrong variant
        assert min(errs) > 3 * TOL_RMS, errs
    else:
        assert max(errs) <= TOL_RMS, errs


@pytest.mark.parametrize("case", [*CASES, *JOINED])
@pytest.mark.parametrize("served", [False, True], ids=["float32", "bf16"])
def test_the_tight_mixed_step_computes_what_the_parts_do(tight_step, served,
                                                         case):
    """``llama.forward_mixed`` with int8 weights over int8 pools, its
    slices tight and its ``linear`` (each row's own activation scale
    with it) run over the live tiles, against ``forward_prefill`` +
    ``forward_decode`` over the same pools (``tests/mixed_tight.py``):
    with float32 activations, and with bfloat16 ones as served (there
    the two read bit for bit equal — both compiled ``as_written``:
    since the decode rows go through the slices' products the two
    programs differ in shape, and XLA's CPU backend, left to keep a
    bfloat16 chain wide where it fuses one, rounds an activation to
    another int8 quantum in one of them). The tolerance is one step of
    an int8 K/V value's scale: a product rounded the other way may move
    one."""
    cfg = tiny_mistral()
    if not served:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = init_params_quantized(jax.random.PRNGKey(26), cfg)
    if served:
        check_served(tight_step, llama, cfg, params, case, page=PAGE,
                     cache_dtype=jnp.int8, atol=2e-2, pages_atol=2e-2,
                     as_written=True)
    else:
        check(tight_step, llama, cfg, params, case, page=PAGE,
              cache_dtype=jnp.int8, atol=2e-2)


def test_config_holds_the_published_sizes():
    """``get_config("mistral-7b-v0.3")`` against the benchmark's
    configuration file, key by key (the file cuts only the context)."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mistral-7b-v0.3-w8kv8.json")) as f:
        pub = json.load(f)
    cfg = get_config("mistral-7b-v0.3")
    assert list(pub["reduced"]) == ["max_position_embeddings"]
    assert cfg.max_seq_len == 32768        # the source's, not the file's
    got = {"hidden_size": cfg.dim, "intermediate_size": cfg.ffn_dim,
           "num_hidden_layers": cfg.n_layers,
           "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.n_kv_heads,
           "head_dim": cfg.head_dim, "vocab_size": cfg.vocab_size,
           "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
           "tie_word_embeddings": cfg.tie_embeddings}
    assert got == {k: pub[k] for k in got}
    assert pub["sliding_window"] is None and pub["hidden_act"] == "silu"


def test_build_engine_serves_it_as_int8_over_int8(monkeypatch):
    """``model: {name: mistral-7b-v0.3, quantization: int8,
    kv_quantization: int8}`` through ``build_engine``, shapes only: the
    weights are ``jax.eval_shape`` of the program's own int8 init (no
    7 B on the CPU) and the pool is a few pages."""
    from llmq_tpu.core.config import default_config
    from llmq_tpu.engine import build_engine
    from llmq_tpu.ops.quant import is_quantized

    conf = default_config()
    conf.executor.backend = "jax"
    conf.model.name = "mistral-7b-v0.3"
    conf.model.quantization = "int8"
    conf.model.kv_quantization = "int8"
    conf.model.max_seq_len = 2048
    conf.executor.max_batch_size = 8
    conf.executor.page_size = 128
    conf.executor.kv_pages = 4
    mcfg = get_config("mistral-7b-v0.3", max_seq_len=2048)
    params = jax.eval_shape(
        lambda: init_params_quantized(jax.random.PRNGKey(0), mcfg))
    engine = build_engine(conf, params=params, enable_metrics=False)
    ex = engine.executor
    assert dataclasses.replace(ex.model_cfg,
                               pallas_batched_prefill=False) == mcfg
    assert is_quantized(ex.params["layers"]["w_down"])
    assert ex.params["layers"]["w_down"]["q"].shape == (32, 14336, 4096)
    assert ex.cache["k"].dtype == jnp.int8
    assert ex.cache["k"].shape == (32, 4, 128, 8 * 128)
    assert ex.cache["k_scale"].shape == (32, 4, 8, 128)
    assert ex.spec.max_pages_per_seq == 16
    B, MP = 8, 16
    logits, cache = jax.eval_shape(
        lambda p, c: forward_decode(
            p, ex.model_cfg, jnp.zeros(B, jnp.int32),
            jnp.zeros(B, jnp.int32), c, jnp.zeros((B, MP), jnp.int32)),
        params, ex.cache)
    assert logits.shape == (B, 32768)
    assert cache["v_scale"].shape == ex.cache["v_scale"].shape
