"""int8 w8a8 quantization: parity against the bf16 model, sharding
congruence, and footprint math (the path that fits
llama3-8B on a 16 GB chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmq_tpu.models.llama import (
    forward_decode,
    forward_prefill,
    get_config,
    init_kv_pages,
    init_params,
    llama3_tiny,
)
from llmq_tpu.ops.quant import (
    dequantize_weight,
    embed_lookup,
    is_quantized,
    params_bytes,
    qdot,
    quantize_embedding,
    quantize_params,
    quantize_weight,
)

CFG = llama3_tiny(dtype=jnp.float32, tie_embeddings=False)
PAGE, NPAGES, MAXP = 4, 64, 8


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def qparams(params):
    return quantize_params(params)


class TestLeafOps:
    def test_roundtrip_error_small(self):
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
        qw = quantize_weight(w)
        back = dequantize_weight(qw, jnp.float32)
        # int8 symmetric per-channel: max error is half a quant step.
        step = np.asarray(qw["s"]).max()
        assert np.abs(np.asarray(back - w)).max() <= step * 0.51

    def test_qdot_close_to_dense(self):
        k1, k2 = jax.random.split(jax.random.PRNGKey(2))
        x = jax.random.normal(k1, (8, 64), jnp.float32)
        w = jax.random.normal(k2, (64, 32), jnp.float32)
        y = qdot(x, quantize_weight(w))
        ref = x @ w
        rel = np.linalg.norm(np.asarray(y - ref)) / np.linalg.norm(np.asarray(ref))
        assert rel < 0.02

    def test_embed_lookup_and_scale_shape(self):
        e = jax.random.normal(jax.random.PRNGKey(3), (16, 8), jnp.float32)
        qe = quantize_embedding(e)
        assert qe["q"].dtype == jnp.int8 and qe["s"].shape == (16, 1)
        toks = jnp.asarray([0, 5, 15])
        got = embed_lookup(qe, toks, jnp.float32)
        assert np.allclose(np.asarray(got), np.asarray(e[toks]), atol=0.05)

    def test_int8_native_dot_dtype(self):
        # The MXU path: int8 x int8 must accumulate in int32, not float.
        a = jnp.ones((4, 8), jnp.int8)
        b = jnp.ones((8, 4), jnp.int8)
        out = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        assert out.dtype == jnp.int32 and int(out[0, 0]) == 8


class TestPytree:
    def test_quantize_params_structure(self, params, qparams):
        assert is_quantized(qparams["embed"])
        assert is_quantized(qparams["lm_head"])
        for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            leaf = qparams["layers"][name]
            assert is_quantized(leaf), name
            assert leaf["q"].dtype == jnp.int8
            assert leaf["q"].shape == params["layers"][name].shape
            # scale keeps the contraction dim as 1
            assert leaf["s"].shape[-2] == 1
        # norms untouched
        assert qparams["layers"]["attn_norm"].dtype == params["layers"]["attn_norm"].dtype

    def test_idempotent(self, qparams):
        again = quantize_params(qparams)
        assert again["layers"]["wq"]["q"] is qparams["layers"]["wq"]["q"]

    def test_footprint_halved_vs_f32(self, params, qparams):
        # f32 tiny params → int8 should be ~1/4 the bytes (scales add <2%).
        assert params_bytes(qparams) < params_bytes(params) * 0.30


class TestForwardParity:
    """Quantized forward must track the bf16/f32 model closely enough to
    serve: high top-1 agreement and high logit cosine similarity."""

    def _run_prefill(self, p, cache):
        B, T = 2, 12
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (B, T)), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
        lens = jnp.asarray([T, T], jnp.int32)
        bt = np.zeros((B, MAXP), np.int32)
        bt[0, :3] = [1, 2, 3]
        bt[1, :3] = [4, 5, 6]
        return forward_prefill(p, CFG, toks, pos, lens, cache,
                               jnp.asarray(bt))

    def test_prefill_parity(self, params, qparams):
        logits_f, _ = self._run_prefill(params, init_kv_pages(CFG, NPAGES, PAGE, dtype=jnp.float32))
        logits_q, _ = self._run_prefill(qparams, init_kv_pages(CFG, NPAGES, PAGE, dtype=jnp.float32))
        lf = np.asarray(logits_f).reshape(-1, CFG.vocab_size)
        lq = np.asarray(logits_q).reshape(-1, CFG.vocab_size)
        cos = np.sum(lf * lq, -1) / (
            np.linalg.norm(lf, axis=-1) * np.linalg.norm(lq, axis=-1) + 1e-9)
        assert cos.min() > 0.99, f"cosine {cos.min()}"
        agree = (lf.argmax(-1) == lq.argmax(-1)).mean()
        assert agree >= 0.9, f"top-1 agreement {agree}"

    def test_decode_parity(self, params, qparams):
        cache_f = init_kv_pages(CFG, NPAGES, PAGE, dtype=jnp.float32)
        cache_q = init_kv_pages(CFG, NPAGES, PAGE, dtype=jnp.float32)
        _, cache_f = self._run_prefill(params, cache_f)
        _, cache_q = self._run_prefill(qparams, cache_q)
        B, T = 2, 12
        toks = jnp.asarray([7, 9], jnp.int32)
        pos = jnp.asarray([T, T], jnp.int32)
        bt = np.zeros((B, MAXP), np.int32)
        bt[0, :4] = [1, 2, 3, 7]
        bt[1, :4] = [4, 5, 6, 8]
        lf, _ = forward_decode(params, CFG, toks, pos, cache_f, jnp.asarray(bt))
        lq, _ = forward_decode(qparams, CFG, toks, pos, cache_q, jnp.asarray(bt))
        lf, lq = np.asarray(lf), np.asarray(lq)
        cos = np.sum(lf * lq, -1) / (
            np.linalg.norm(lf, axis=-1) * np.linalg.norm(lq, axis=-1) + 1e-9)
        assert cos.min() > 0.99

    def test_tied_embeddings_parity(self):
        cfg = llama3_tiny(dtype=jnp.float32, tie_embeddings=True)
        p = init_params(jax.random.PRNGKey(5), cfg)
        qp = quantize_params(p)
        B, T = 1, 8
        toks = jnp.asarray(np.arange(T)[None, :], jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
        lens = jnp.asarray([T], jnp.int32)
        bt = np.zeros((B, MAXP), np.int32)
        bt[0, :2] = [1, 2]
        cache = init_kv_pages(cfg, NPAGES, PAGE, dtype=jnp.float32)
        cache2 = init_kv_pages(cfg, NPAGES, PAGE, dtype=jnp.float32)
        lf, _ = forward_prefill(p, cfg, toks, pos, lens, cache, jnp.asarray(bt))
        lq, _ = forward_prefill(qp, cfg, toks, pos, lens, cache2, jnp.asarray(bt))
        lf = np.asarray(lf).reshape(-1, cfg.vocab_size)
        lq = np.asarray(lq).reshape(-1, cfg.vocab_size)
        cos = np.sum(lf * lq, -1) / (
            np.linalg.norm(lf, axis=-1) * np.linalg.norm(lq, axis=-1) + 1e-9)
        assert cos.min() > 0.99


class TestSharded:
    def test_quantized_tp_forward_matches_single(self, qparams):
        """int8 model under an 8-way tp mesh == single-device run."""
        from jax.sharding import Mesh
        from llmq_tpu.parallel.sharding import (param_shardings,
                                                shard_params)

        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = Mesh(np.array(devs[:8]).reshape(8), ("tp",))
        shardings = param_shardings(CFG, mesh, quantized=True)
        # Trees must be congruent — this throws on mismatch.
        sharded = shard_params(qparams, shardings)

        B, T = 2, 12
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (B, T)), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
        lens = jnp.asarray([T, T], jnp.int32)
        bt = np.zeros((B, MAXP), np.int32)
        bt[0, :3] = [1, 2, 3]
        bt[1, :3] = [4, 5, 6]
        cache1 = init_kv_pages(CFG, NPAGES, PAGE, dtype=jnp.float32)
        cache2 = init_kv_pages(CFG, NPAGES, PAGE, dtype=jnp.float32)
        with mesh:
            ls, _ = forward_prefill(sharded, CFG, toks, pos, lens, cache2,
                                    jnp.asarray(bt))
        l1, _ = forward_prefill(qparams, CFG, toks, pos, lens, cache1,
                                jnp.asarray(bt))
        assert np.allclose(np.asarray(ls), np.asarray(l1), atol=2e-2)


class TestSizing:
    def test_8b_int8_fits_v5e(self):
        """The point of the exercise: 8B int8 + KV pool < 16 GB HBM."""
        cfg = get_config("llama3-8b")
        p8 = 8.03e9  # params
        int8_bytes = p8 * 1.0
        kv_per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2
        kv_pool = 16 * 1024 * kv_per_tok  # 16 seqs x 1024 ctx, bf16
        assert int8_bytes + kv_pool < 15.5e9
        assert 2 * p8 > 16e9  # and bf16 provably does NOT fit


class TestInt8KVCache:
    """int8 KV pools (per-token-per-head scales) vs the bf16 cache:
    same model, same inputs — logits must agree within quantization
    tolerance through prefill, continuation, and decode."""

    def _setup(self, dtype):
        from llmq_tpu.models.llama import (get_config, init_kv_pages,
                                           init_params)
        cfg = get_config("llama3-tiny", max_seq_len=128, pallas=False,
                         n_kv_heads=2)
        params = init_params(jax.random.PRNGKey(1), cfg)
        B, pages_per_seq, page = 2, 8, 16
        cache = init_kv_pages(cfg, B * pages_per_seq + 1, page,
                              dtype=dtype)
        bt = np.zeros((B, pages_per_seq), np.int32)
        n = 1
        for b in range(B):
            for p in range(pages_per_seq):
                bt[b, p] = n
                n += 1
        return cfg, params, cache, jnp.asarray(bt)

    def test_prefill_and_decode_match_bf16(self):
        from llmq_tpu.models.llama import (forward_decode,
                                           forward_prefill)

        T = 24
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, T), 5, 500,
                                  jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(T), (2, T))
        lengths = jnp.full((2,), T, jnp.int32)

        outs = {}
        for name, dt in (("bf16", None), ("int8", jnp.int8)):
            cfg, params, cache, bt = self._setup(dt)
            if name == "int8":
                assert set(cache) == {"k", "v", "k_scale", "v_scale"}
            logits, cache = forward_prefill(params, cfg, toks, positions,
                                            lengths, cache, bt)
            # one decode step on top of the prefilled history
            last = toks[:, -1]
            pos = jnp.full((2,), T, jnp.int32)
            dlogits, cache = forward_decode(params, cfg, last, pos, cache,
                                            bt)
            outs[name] = (np.asarray(logits), np.asarray(dlogits))

        # int8 KV quantization error is ~0.5% per value; logits are
        # sums over D=32 — tolerance is loose but far below the
        # bf16-vs-int8-weights gap that would indicate a real bug.
        p_ref, d_ref = outs["bf16"]
        p_q, d_q = outs["int8"]
        ref_scale = np.abs(p_ref).max()
        assert np.abs(p_q - p_ref).max() < 0.05 * ref_scale, (
            np.abs(p_q - p_ref).max(), ref_scale)
        assert np.abs(d_q - d_ref).max() < 0.05 * np.abs(d_ref).max()

    def test_int8_cache_layout(self):
        from llmq_tpu.models.llama import init_kv_pages, llama3_tiny
        cfg = llama3_tiny(n_kv_heads=2)
        c = init_kv_pages(cfg, 9, 16, dtype=jnp.int8)
        assert c["k"].dtype == jnp.int8
        assert c["k_scale"].shape == (cfg.n_layers, 9, 2, 16)
        assert c["k_scale"].dtype == jnp.bfloat16

    def test_build_engine_int8_kv_generates(self):
        """config.model.kv_quantization='int8' through build_engine:
        pools carry scale leaves and generation + turn-2 KV reuse work
        (CPU, tiny model — the serving wiring, not the kernel)."""
        from llmq_tpu.core.config import default_config
        from llmq_tpu.engine import build_engine

        cfg = default_config()
        cfg.executor.backend = "jax"
        cfg.model.name = "llama3-tiny"
        cfg.model.max_seq_len = 128
        cfg.model.kv_quantization = "int8"
        cfg.executor.max_batch_size = 2
        cfg.executor.page_size = 16
        cfg.executor.kv_pages = 17
        cfg.executor.prefill_buckets = [16]
        cfg.executor.decode_chunk = 4
        eng = build_engine(cfg, warmup=False)
        assert "k_scale" in eng.executor.cache
        eng.start()
        try:
            r1 = eng.generate("hi there", max_new_tokens=4,
                              conversation_id="c")
            assert r1.tokens
            r2 = eng.generate(" again", max_new_tokens=4,
                              conversation_id="c")
            assert r2.cached_tokens > 0
        finally:
            eng.stop()
