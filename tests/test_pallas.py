"""The serving Pallas kernels (ops/pallas/: kv_write, fused_decode,
prefill_attention) vs the pure-JAX semantics reference.

The kernels run in interpret mode here — CPU CI covers the kernel
bodies (DMA schedule, online softmax, masking) without TPU hardware; the
decode kernels at the served geometries are in
tests/test_decode_kernels.py, on-device numerics in
scripts/chip_kernel_check.py on the real chip.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llmq_tpu.ops.attention import (  # noqa: E402
    blockwise_prefill_attention,
    causal_prefill_attention,
)


def _flat2(pool):
    """Stacked-pool view: (L, P, ps, H_kv, D) → (L, P, ps, H_kv·D)."""
    return pool.reshape(*pool.shape[:3], -1)


class TestKvWriteKernels:
    def test_decode_row_write(self):
        from llmq_tpu.ops.pallas.kv_write import kv_cache_write_pallas
        rng = np.random.default_rng(0)
        L, P, ps, Hkv, D, N = 3, 40, 8, 2, 64, 12
        k = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
        kn = jnp.asarray(rng.standard_normal((N, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.standard_normal((N, Hkv, D)), jnp.float32)
        page = jnp.asarray(np.arange(1, N + 1), jnp.int32)   # distinct
        slot = jnp.asarray(np.arange(N) % ps, jnp.int32)
        kf, vf = _flat2(k), _flat2(v)
        ref_k = kf.at[1, page, slot].set(kn.reshape(N, -1))
        ref_v = vf.at[1, page, slot].set(vn.reshape(N, -1))
        ok, ov = kv_cache_write_pallas(kf, vf, kn.reshape(N, -1),
                                       vn.reshape(N, -1), page, slot, 1,
                                       interpret=True)
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(ref_k))
        np.testing.assert_array_equal(np.asarray(ov), np.asarray(ref_v))

    @pytest.mark.parametrize("start,n_tok", [(0, 32), (5, 20), (13, 32),
                                             (8, 8), (19, 1)])
    def test_prefill_page_write(self, start, n_tok):
        """Page-RMW prefill write == scatter, incl. partial edge pages
        and preservation of pre-existing KV before the chunk start."""
        from llmq_tpu.ops.pallas.kv_write import kv_prefill_write_pallas
        rng = np.random.default_rng(start * 100 + n_tok)
        L, P, ps, Hkv, D = 2, 16, 8, 2, 64
        mp = 8                                    # block-table width
        k = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
        bt = jnp.asarray(rng.permutation(np.arange(1, P))[:mp], jnp.int32)
        kn = jnp.asarray(rng.standard_normal((n_tok, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.standard_normal((n_tok, Hkv, D)), jnp.float32)
        # scatter reference
        pos = start + np.arange(n_tok)
        page = np.asarray(bt)[pos // ps]
        slot = pos % ps
        kf, vf = _flat2(k), _flat2(v)
        ref_k = kf.at[1, page, slot].set(kn.reshape(n_tok, -1))
        ref_v = vf.at[1, page, slot].set(vn.reshape(n_tok, -1))
        # kernel: page-aligned buffer, bucket length T >= n_tok
        T = 32
        n_wp = T // ps + 1
        ak = np.zeros((n_wp * ps, Hkv * D), np.float32)
        av = np.zeros((n_wp * ps, Hkv * D), np.float32)
        off = start % ps
        ak[off:off + n_tok] = np.asarray(kn).reshape(n_tok, -1)
        av[off:off + n_tok] = np.asarray(vn).reshape(n_tok, -1)
        ok, ov = kv_prefill_write_pallas(
            kf, vf, jnp.asarray(ak), jnp.asarray(av), bt,
            jnp.int32(start), jnp.int32(n_tok), 1, interpret=True)
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(ref_k))
        np.testing.assert_array_equal(np.asarray(ov), np.asarray(ref_v))

    def test_prefill_write_nonmultiple_bucket(self, monkeypatch):
        """Bucket T not a multiple of page_size with a mid-page
        continuation start: the aligned buffer must not clamp (review
        regression: T//ps+1 pages under-allocated → silent KV shift)."""
        from llmq_tpu.ops.attention import paged_kv_write_prefill
        rng = np.random.default_rng(7)
        L, P, ps, Hkv, D = 2, 16, 16, 2, 64
        T, start, n_tok = 24, 28, 24         # off=12, off+T=36 > 2*ps
        mp = 8
        k_pool = jnp.asarray(rng.standard_normal((L, P, ps, Hkv * D)),
                             jnp.float32)
        v_pool = jnp.asarray(rng.standard_normal((L, P, ps, Hkv * D)),
                             jnp.float32)
        bt = jnp.asarray(np.arange(1, mp + 1), jnp.int32)[None]
        k = jnp.asarray(rng.standard_normal((1, T, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, T, Hkv, D)), jnp.float32)
        positions = (start + jnp.arange(T))[None].astype(jnp.int32)
        lengths = jnp.asarray([n_tok], jnp.int32)
        monkeypatch.setenv("LLMQ_PALLAS", "0")
        jax.clear_caches()
        rk, rv = paged_kv_write_prefill(k_pool, v_pool, k, v, bt,
                                        positions, lengths, 1)
        monkeypatch.setenv("LLMQ_PALLAS", "interpret")
        jax.clear_caches()
        ok, ov = paged_kv_write_prefill(k_pool, v_pool, k, v, bt,
                                        positions, lengths, 1)
        jax.clear_caches()
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))

    def test_forward_prefill_dispatch_interpret(self, monkeypatch):
        """forward_prefill B=1 routes through the prefill-write kernel
        under LLMQ_PALLAS=interpret and matches the scatter path."""
        from llmq_tpu.models.llama import (forward_prefill, get_config,
                                           init_kv_pages, init_params)
        cfg = get_config("llama3-tiny", max_seq_len=64, dim=256,
                         n_heads=4, n_kv_heads=2)
        params = init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray([[5, 9, 2, 7, 1, 3, 8, 4]], jnp.int32)
        pos = jnp.arange(8)[None, :].astype(jnp.int32)
        lens = jnp.asarray([8], jnp.int32)
        bt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
        monkeypatch.setenv("LLMQ_PALLAS", "0")
        jax.clear_caches()
        cache = init_kv_pages(cfg, 16, 8)
        ref_logits, ref_cache = forward_prefill(params, cfg, toks, pos,
                                                lens, cache, bt)
        monkeypatch.setenv("LLMQ_PALLAS", "interpret")
        jax.clear_caches()
        cache = init_kv_pages(cfg, 16, 8)
        out_logits, out_cache = forward_prefill(params, cfg, toks, pos,
                                                lens, cache, bt)
        jax.clear_caches()
        np.testing.assert_allclose(np.asarray(out_logits),
                                   np.asarray(ref_logits),
                                   atol=3e-2, rtol=3e-2)
        # written pages identical (pages 1..4 hold the 8 tokens)
        np.testing.assert_allclose(
            np.asarray(out_cache["k"][:, 1:5]),
            np.asarray(ref_cache["k"][:, 1:5]), atol=3e-2, rtol=3e-2)

    def test_batched_prefill_kernel_route_interpret(self, monkeypatch):
        """B>1 forward_prefill with the serving executor's
        pallas_batched_prefill opt-in routes the row-looped kernels
        (interpret mode) and matches the pure-JAX path — the production
        batched-admission route (r4), otherwise only exercised on TPU."""
        import dataclasses

        from llmq_tpu.models.llama import (forward_prefill, get_config,
                                           init_kv_pages, init_params)
        cfg = get_config("llama3-tiny", max_seq_len=64, dim=256,
                         n_heads=4, n_kv_heads=2)
        params = init_params(jax.random.PRNGKey(0), cfg)
        B, T = 3, 8
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(1, 500, (B, T)), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
        lens = jnp.asarray([8, 5, 8], jnp.int32)
        bt = jnp.asarray(np.arange(1, B * 4 + 1, dtype=np.int32)
                         .reshape(B, 4))
        monkeypatch.setenv("LLMQ_PALLAS", "0")
        jax.clear_caches()
        cache = init_kv_pages(cfg, 16, 8)
        ref_logits, ref_cache = forward_prefill(params, cfg, toks, pos,
                                                lens, cache, bt)
        monkeypatch.setenv("LLMQ_PALLAS", "interpret")
        jax.clear_caches()
        kcfg = dataclasses.replace(cfg, pallas_batched_prefill=True)
        cache = init_kv_pages(cfg, 16, 8)
        out_logits, out_cache = forward_prefill(params, kcfg, toks, pos,
                                                lens, cache, bt)
        jax.clear_caches()
        # Compare only VALID rows' logits (padding rows differ — the
        # kernel derives q positions from positions[b, 0] and discards
        # nothing; the executor slices at lengths-1).
        for b in range(B):
            n = int(lens[b])
            np.testing.assert_allclose(
                np.asarray(out_logits[b, :n]),
                np.asarray(ref_logits[b, :n]), atol=3e-2, rtol=3e-2)
        np.testing.assert_allclose(
            np.asarray(out_cache["k"][:, 1:13]),
            np.asarray(ref_cache["k"][:, 1:13]), atol=3e-2, rtol=3e-2)


class TestFusedDecode:
    def test_matches_unfused(self, monkeypatch):
        """Fused write+attention == scatter-write + pooled attention,
        including page-boundary positions and the pool update."""
        from llmq_tpu.ops.pallas.fused_decode import (
            fused_decode_attention_pallas)
        from llmq_tpu.ops.attention import (paged_decode_attention_pooled,
                                            paged_kv_write)
        monkeypatch.setenv("LLMQ_PALLAS", "0")   # pure reference path
        rng = np.random.default_rng(3)
        L, P, ps, Hkv, D, H, B = 2, 24, 8, 2, 64, 4, 3
        mp = 6
        k_pool = jnp.asarray(rng.standard_normal((L, P, ps, Hkv * D)),
                             jnp.float32)
        v_pool = jnp.asarray(rng.standard_normal((L, P, ps, Hkv * D)),
                             jnp.float32)
        bt = jnp.asarray(
            rng.permutation(np.arange(1, P))[:B * mp].reshape(B, mp),
            jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        kn = jnp.asarray(rng.standard_normal((B, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.standard_normal((B, Hkv, D)), jnp.float32)
        positions = jnp.asarray([0, 15, 37], jnp.int32)  # page edges
        seq_lens = positions + 1
        page_of = bt[jnp.arange(B), positions // ps]
        slot_of = positions % ps
        rk, rv = paged_kv_write(k_pool, v_pool, kn, vn, page_of,
                                slot_of, 1)
        ref = paged_decode_attention_pooled(q, rk, rv, bt, seq_lens, 1)
        attn, (ok, ov) = fused_decode_attention_pallas(
            q, kn, vn, k_pool, v_pool, bt, seq_lens, page_of, 1,
            pages_per_chunk=2, interpret=True)
        np.testing.assert_allclose(np.asarray(attn), np.asarray(ref),
                                   atol=3e-2, rtol=3e-2)
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))

    def test_full_row_tile_mixed_lengths(self, monkeypatch):
        """B=8 exercises the real R=8 tile path (cross-pair prefetch
        chain, SMEM slot parity, per-row merge in a shared tile) with
        wildly mixed seq_lens including zero — B=3 degenerates to R=1
        and would leave all of that untested."""
        from llmq_tpu.ops.pallas.fused_decode import (
            fused_decode_attention_pallas)
        from llmq_tpu.ops.attention import (paged_decode_attention_pooled,
                                            paged_kv_write)
        monkeypatch.setenv("LLMQ_PALLAS", "0")   # pure reference path
        rng = np.random.default_rng(11)
        L, P, ps, Hkv, D, H, B = 2, 80, 8, 2, 64, 4, 8
        mp = 8
        k_pool = jnp.asarray(rng.standard_normal((L, P, ps, Hkv * D)),
                             jnp.float32)
        v_pool = jnp.asarray(rng.standard_normal((L, P, ps, Hkv * D)),
                             jnp.float32)
        bt = jnp.asarray(
            rng.permutation(np.arange(1, P))[:B * mp].reshape(B, mp),
            jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        kn = jnp.asarray(rng.standard_normal((B, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.standard_normal((B, Hkv, D)), jnp.float32)
        # page edges, full window, and a zero-length (inactive) row
        seq_lens = jnp.asarray([1, 8, 9, 0, 64, 33, 16, 57], jnp.int32)
        positions = jnp.maximum(seq_lens - 1, 0)
        live = seq_lens > 0
        page_of = jnp.where(live, bt[jnp.arange(B), positions // ps], 0)
        slot_of = positions % ps
        kn_w = jnp.where(live[:, None, None], kn, 0)
        vn_w = jnp.where(live[:, None, None], vn, 0)
        rk, rv = paged_kv_write(k_pool, v_pool, kn_w, vn_w, page_of,
                                slot_of, 1)
        ref = paged_decode_attention_pooled(q, rk, rv, bt, seq_lens, 1)
        attn, (ok, ov) = fused_decode_attention_pallas(
            q, kn, vn, k_pool, v_pool, bt, seq_lens, page_of, 1,
            pages_per_chunk=2, interpret=True)
        a, r = np.asarray(attn), np.asarray(ref)
        mask = np.asarray(live)
        np.testing.assert_allclose(a[mask], r[mask], atol=3e-2, rtol=3e-2)
        # zero-length row emits exactly 0 (the documented contract)
        assert np.all(a[~mask] == 0)
        # pools: live rows' pages updated; the seq-0 row wrote nothing
        # except possibly reserved page 0 (never read) — compare all
        # non-reserved pages.
        np.testing.assert_array_equal(np.asarray(ok)[:, 1:],
                                      np.asarray(rk)[:, 1:])
        np.testing.assert_array_equal(np.asarray(ov)[:, 1:],
                                      np.asarray(rv)[:, 1:])

    def test_model_dispatch_under_interpret(self, monkeypatch):
        """forward_decode routes through the fused kernel when
        LLMQ_PALLAS=interpret and produces the same logits as pure JAX."""
        monkeypatch.setenv("LLMQ_PALLAS", "0")
        from llmq_tpu.models.llama import (forward_decode, get_config,
                                           init_kv_pages, init_params)
        # H_kv·head_dim must be 128-aligned for the kernel path: 2·64.
        cfg = get_config("llama3-tiny", max_seq_len=64, dim=256,
                         n_heads=4, n_kv_heads=2)
        params = init_params(jax.random.PRNGKey(0), cfg)
        cache = init_kv_pages(cfg, 16, 8)
        bt = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
        toks = jnp.asarray([7], jnp.int32)
        pos = jnp.asarray([3], jnp.int32)
        ref, _ = forward_decode(params, cfg, toks, pos, cache, bt)
        monkeypatch.setenv("LLMQ_PALLAS", "interpret")
        # The env var is read at trace time; equal configs share a jit
        # cache entry, so force a retrace to route through the kernel.
        jax.clear_caches()
        out, _ = forward_decode(params, cfg, toks, pos, cache, bt)
        # bf16 compute: kernel and pure-JAX paths accumulate in
        # different orders, so logits at ~2.5 magnitude legitimately
        # differ by a few bf16 ulps (~0.016 each) — 5e-2 covers that
        # without masking a real indexing/masking bug (those show up
        # as O(1) divergence on many elements, not 0.03 on one).
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-2, rtol=5e-2)
        jax.clear_caches()  # don't leak interpret-mode traces to others


#: name -> (H, H_kv, D, page_size, max_pages, T, pages_per_chunk, q_block).
#: ``gqa-2x64-ps8`` is the shape the kernel was first tested at; the
#: others are the served head layouts scaled down: full multi-head D 64
#: (SmolLM2: two heads share a 128-lane window), GQA D 64 (llama3-1b:
#: half the query heads roll to their KV head's lanes), GQA D 128
#: (llama3-8b: a window per KV head) and a 128-token page.
_PREFILL_GEOMS = {
    "gqa-2x64-ps8": (4, 2, 64, 8, 8, 16, 2, 8),
    "mha-4x64": (4, 4, 64, 16, 8, 32, 2, 16),
    "gqa-2x64-rep4": (8, 2, 64, 16, 8, 32, 2, 16),
    "gqa-2x128": (4, 2, 128, 16, 8, 32, 2, 16),
    "gqa-2x128-ps128": (4, 2, 128, 128, 2, 32, 1, 16),
    "mha-4x64-plan": (4, 4, 64, 16, 32, 64, 0, 0),
}


def _prefill_case(geom, start, dtype):
    """One call of the kernel and of gather + blockwise on the same
    pool; returns (kernel output, reference), both (T, H, D)."""
    from llmq_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention_pallas)
    H, Hkv, D, ps, mp, T, ppc, qb = _PREFILL_GEOMS[geom]
    rng = np.random.default_rng(start)
    L, P = 2, mp + 3
    k_pool = jnp.asarray(rng.standard_normal((L, P, ps, Hkv * D)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((L, P, ps, Hkv * D)), dtype)
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:mp], jnp.int32)
    q = jnp.asarray(rng.standard_normal((1, T, H, D)), dtype)
    positions = (start + jnp.arange(T))[None, :].astype(jnp.int32)
    seq_lens = jnp.asarray([start + T], jnp.int32)

    k_hist = k_pool[1, bt[None]].reshape(1, mp * ps, Hkv, D)
    v_hist = v_pool[1, bt[None]].reshape(1, mp * ps, Hkv, D)
    # (gathered VALUES may be unflattened freely; the pool may not)
    ref = blockwise_prefill_attention(q, k_hist, v_hist, positions,
                                      seq_lens)
    out = paged_prefill_attention_pallas(
        q[0], k_pool, v_pool, bt, jnp.int32(start), 1,
        pages_per_chunk=ppc, q_block=qb, interpret=True)
    assert out.shape == (T, H, D) and out.dtype == q.dtype
    return np.asarray(out, np.float32), np.asarray(ref[0], np.float32)


class TestPrefillAttentionKernel:
    @pytest.mark.parametrize("start", [0, 24])
    def test_matches_blockwise(self, start):
        """Paged prefill attention kernel == gather + blockwise, for a
        fresh prompt (start=0) and a continuation chunk (start=24)."""
        out, ref = _prefill_case("gqa-2x64-ps8", start, jnp.float32)
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("start", [0, 21, 70])
    @pytest.mark.parametrize("geom", ["mha-4x64", "gqa-2x64-rep4",
                                      "gqa-2x128"])
    def test_head_layouts_and_offsets(self, geom, start, dtype):
        """Every served head layout at a fresh prompt (chunks 1-3 dead),
        an offset that is not page-aligned (the slice ends part way
        into chunk 1) and one beyond two whole 32-token chunks (the
        chunk boundary at 96 falls inside the slice), in float32 and in
        the serving dtype (bf16 operands, f32 accumulation on both
        sides, so they differ by one rounding of the output)."""
        out, ref = _prefill_case(geom, start, dtype)
        tol = 1e-4 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_128_token_pages(self, dtype):
        """llama3-8b's serving page: one page a chunk, the slice
        (positions 100-131) astride the page boundary."""
        out, ref = _prefill_case("gqa-2x128-ps128", 100, dtype)
        tol = 1e-4 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)

    def test_plan_chooses_tiles_when_not_pinned(self):
        """No ``q_block`` / ``pages_per_chunk``: the plan's own tiles
        (a 256-token chunk over 16-token pages) give the same result."""
        out, ref = _prefill_case("mha-4x64-plan", 230, jnp.float32)
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


#: Mistral-7B-v0.3 as ``mistral-7b-v0.3-w8kv8`` serves it: 32 query heads
#: over 8 KV heads of 128, 128-token pages, 16 a block table, 512-token
#: slices. (H, H_kv, D, page_size, max_pages, T)
_Q8_SERVED = (32, 8, 128, 128, 16, 512)

#: case -> ((start_pos, valid length) of each slice of one call, geometry)
_Q8_PREFILL_CASES = {
    "fresh-150": ([(0, 150)], _Q8_SERVED),
    "full-512": ([(0, 512)], _Q8_SERVED),
    "continuation-512": ([(512, 300)], _Q8_SERVED),
    "continuation-1408": ([(1408, 512)], _Q8_SERVED),
    # the slice's last token in the last slot of a page (position 383)
    "page-edge": ([(128, 256)], _Q8_SERVED),
    "empty": ([(0, 0)], _Q8_SERVED),
    "two-slices": ([(0, 301), (640, 37)], _Q8_SERVED),
    # D = 64: two KV heads share a 128-lane window, so a tile's rows
    # take two different rows of the scale pages
    "two-heads-a-window": ([(100, 64)], (16, 8, 64, 128, 4, 64)),
}


def _q8_prefill_inputs(slices, geom, seed):
    """int8 pools whose slices' contexts ``[0, start + length)`` went in
    through the pure write, one block table a slice, and a query block
    a slice: (q (B, T, H, D), pools, block tables, positions, seq_lens).
    The pages of a block table that its context does not reach hold NaN
    scales: what a kernel that fetched past the context would multiply
    by."""
    from llmq_tpu.ops.attention import paged_kv_write_prefill_q8
    H, Hkv, D, ps, mp, T = geom
    B = len(slices)
    rng = np.random.default_rng(seed)
    L, P = 2, B * mp + 1
    pools = tuple(jnp.zeros((L, P, ps, Hkv * D), jnp.int8)
                  for _ in range(2)) + tuple(
        jnp.ones((L, P, Hkv, ps), jnp.bfloat16) for _ in range(2))
    bts = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(B, mp),
                      jnp.int32)
    for b, (start, length) in enumerate(slices):
        for at in range(0, start + length, T):
            n = min(T, start + length - at)
            k, v = (jnp.asarray(rng.standard_normal((1, T, Hkv, D)),
                                jnp.bfloat16) for _ in range(2))
            pos = (at + jnp.arange(T))[None].astype(jnp.int32)
            pools = paged_kv_write_prefill_q8(
                pools, k, v, bts[b:b + 1], pos, jnp.asarray([n], jnp.int32),
                1)
    dead = np.concatenate([np.asarray(bts[b, -(-(start + n) // ps):])
                           for b, (start, n) in enumerate(slices)])
    pools = pools[:2] + tuple(p.at[:, dead].set(jnp.nan)
                              for p in pools[2:])
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
    starts = jnp.asarray([s for s, _ in slices], jnp.int32)
    positions = starts[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    seq_lens = jnp.asarray([s + n for s, n in slices], jnp.int32)
    return q, pools, bts, positions, seq_lens


class TestPrefillWriteQ8:
    """``paged_kv_write_prefill_q8`` writes page by page; what it leaves
    is what a write token by token leaves."""

    @pytest.mark.parametrize("slices", [
        [(0, 150)], [(0, 512)], [(512, 300)], [(1408, 512)], [(128, 256)],
        [(0, 0)], [(0, 301), (640, 37)], [(1, 1)], [(127, 2)],
        [(1920, 128)]], ids=lambda s: "+".join(f"{n}at{a}" for a, n in s))
    def test_equals_a_write_by_token(self, slices):
        from llmq_tpu.ops.attention import paged_kv_write_prefill_q8
        from llmq_tpu.ops.quant import quantize_kv_rows
        Hkv, D, ps, mp, T = 8, 16, 128, 16, 512
        B = len(slices)
        rng = np.random.default_rng(B + slices[0][0])
        L, P = 2, B * mp + 1
        pools = tuple(jnp.asarray(rng.integers(-127, 128, (L, P, ps, Hkv * D)),
                                  jnp.int8) for _ in range(2)) + tuple(
            jnp.asarray(rng.uniform(0.01, 0.02, (L, P, Hkv, ps)),
                        jnp.bfloat16) for _ in range(2))
        bts = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(B, mp),
                          jnp.int32)
        k, v = (jnp.asarray(rng.standard_normal((B, T, Hkv, D)),
                            jnp.bfloat16) for _ in range(2))
        starts = jnp.asarray([a for a, _ in slices], jnp.int32)
        positions = starts[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        lengths = jnp.asarray([n for _, n in slices], jnp.int32)
        got = paged_kv_write_prefill_q8(pools, k, v, bts, positions, lengths,
                                        1)
        want = [np.array(p) for p in pools]
        (kq, ks), (vq, vs) = quantize_kv_rows(k), quantize_kv_rows(v)
        for b, (start, n) in enumerate(slices):
            for t in range(n):
                page = int(bts[b, (start + t) // ps])
                slot = (start + t) % ps
                want[0][1, page, slot] = np.asarray(kq[b, t]).reshape(-1)
                want[1][1, page, slot] = np.asarray(vq[b, t]).reshape(-1)
                want[2][1, page, :, slot] = np.asarray(ks[b, t])
                want[3][1, page, :, slot] = np.asarray(vs[b, t])
        for a, b in zip(got, want):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


class TestPrefillAttentionKernelQ8:
    """``paged_prefill_attention_q8_pallas`` against what served int8
    prefill before it: ``_dequant_window`` + the blockwise softmax."""

    @pytest.mark.parametrize("case", sorted(_Q8_PREFILL_CASES))
    def test_matches_dequantised_blockwise(self, case, monkeypatch):
        from llmq_tpu.ops.attention import (_dequant_window,
                                            dispatch_prefill_attention_q8)
        from llmq_tpu.ops.pallas.prefill_attention import prefill_tile_plan
        slices, geom = _Q8_PREFILL_CASES[case]
        q, pools, bts, positions, seq_lens = _q8_prefill_inputs(
            slices, geom, seed=len(case))
        H, Hkv, D, ps, mp, T = geom
        tb = prefill_tile_plan(T, H, Hkv, D, ps, mp, 1, q_itemsize=2).q_block
        # The reference gathers the whole block table, dead pages too:
        # over pools whose NaN scales are made finite.
        clean = pools[:2] + tuple(jnp.nan_to_num(p) for p in pools[2:])
        ref = blockwise_prefill_attention(
            q, _dequant_window(clean[0], clean[2], 1, bts, D),
            _dequant_window(clean[1], clean[3], 1, bts, D), positions,
            seq_lens)
        monkeypatch.setenv("LLMQ_PALLAS", "interpret")
        out = dispatch_prefill_attention_q8(
            q, pools, bts, positions, seq_lens, 1, multi_ok=True)
        assert out.shape == q.shape and out.dtype == q.dtype
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        assert np.isfinite(out).all()
        for b, (_, n) in enumerate(slices):
            np.testing.assert_allclose(out[b, :n], ref[b, :n],
                                       atol=3e-2, rtol=3e-2)
            # q blocks wholly past the valid rows are not computed
            assert not out[b, -(-n // tb) * tb:].any()

    def test_steps_follow_the_slice(self):
        """The plan's count of the kernel's loop steps: a 150-token
        prompt in a 512-token slice runs two q blocks of one chunk
        each, an empty slice none, and a full slice at the table's end
        all of its chunks."""
        from llmq_tpu.ops.pallas.prefill_attention import prefill_tile_plan
        H, Hkv, D, ps, mp, T = _Q8_SERVED
        plan = prefill_tile_plan(T, H, Hkv, D, ps, mp, 1, q_itemsize=2)
        assert (plan.q_block, plan.chunk_tokens) == (128, 256)
        assert plan.steps(T, 0, 150) == 2
        assert plan.steps(T, 0, 0) == 0
        assert plan.steps(T, 0, 512) == 1 + 1 + 2 + 2
        assert plan.steps(T, 1408, 512) == 6 + 7 + 7 + 8
        assert plan.steps(T, 1408, 512) == plan.steps(T, 1408)

    def test_forward_mixed_kernel_route_equals_pure(self, monkeypatch):
        """One mixed step over int8 pools — eight decode rows and two
        prompt slices of different lengths, one of them a continuation
        — through the kernels (interpret mode) and through pure JAX:
        the same logits and the same four pools."""
        from llmq_tpu.models.llama import (forward_mixed, get_config,
                                           init_kv_pages, init_params)
        from llmq_tpu.ops.attention import kernel_routes
        cfg = get_config("llama3-tiny", max_seq_len=512, dim=256,
                         n_heads=16, n_kv_heads=8, n_layers=2,
                         pallas_batched_prefill=True)
        ps, mp, B, S, T = 128, 4, 8, 2, 128
        params = init_params(jax.random.PRNGKey(33), cfg)
        rng = np.random.default_rng(33)
        bts = jnp.asarray(1 + np.arange((B + S) * mp).reshape(B + S, mp),
                          jnp.int32)
        dec_pos = jnp.asarray([1, 127, 128, 200, 255, 256, 300, 511],
                              jnp.int32)
        pf_start = np.asarray([0, 130])
        pf_len = jnp.asarray([70, 128], jnp.int32)
        args = (jnp.asarray(rng.integers(3, cfg.vocab_size, B), jnp.int32),
                dec_pos)
        from llmq_tpu.ops.rows import pack_grid
        tok, pos, starts = pack_grid(
            rng.integers(3, cfg.vocab_size, (S, T)),
            pf_start[:, None] + np.arange(T)[None], pf_len)
        pf_args = (jnp.asarray(tok), jnp.asarray(pos), pf_len,
                   jnp.asarray(starts), bts[B:])

        def cache():
            # history under the decode rows and the continuing slice,
            # through the pure write (unit-normal K/V, as a model's)
            from llmq_tpu.ops.attention import paged_kv_write_prefill_q8
            rng2 = np.random.default_rng(7)
            c = init_kv_pages(cfg, 1 + (B + S) * mp, ps, dtype=jnp.int8)
            pools = (c["k"], c["v"], c["k_scale"], c["v_scale"])
            held = jnp.asarray(list(np.asarray(dec_pos)) + list(pf_start),
                               jnp.int32)
            pos = jnp.broadcast_to(jnp.arange(512, dtype=jnp.int32),
                                   (B + S, 512))
            for layer in range(cfg.n_layers):
                k, v = (jnp.asarray(rng2.standard_normal(
                    (B + S, 512, 8, cfg.head_dim)), jnp.bfloat16)
                    for _ in range(2))
                pools = paged_kv_write_prefill_q8(pools, k, v, bts, pos,
                                                  held, layer)
            return dict(zip(("k", "v", "k_scale", "v_scale"), pools))

        got = {}
        for mode in ("0", "interpret"):
            monkeypatch.setenv("LLMQ_PALLAS", mode)
            jax.clear_caches()      # the route is read at trace time
            got[mode] = forward_mixed(params, cfg, *args, cache(),
                                      bts[:B], *pf_args)
        jax.clear_caches()
        routes = kernel_routes(
            batch=B, page_size=ps, max_pages=mp, n_kv_heads=8,
            head_dim=cfg.head_dim, kv_itemsize=1, quant_kv=True,
            enabled=True, multi_ok=True, decode=True, prefill_rows=S)
        assert routes["prefill_attention"] == (
            "pallas-interpret:_prefill_attn_kernel_q8")
        pure, kern = got["0"], got["interpret"]
        # The decode rows' logits (the fused decode kernel alone: they
        # never see a slice) and the slices' (which the prefill kernel
        # feeds), both within the _q8 kernels' tolerance. The limit is
        # set from each route's own distance from the same step in
        # float32 (bf16 leaves widened, the same int8 pools), largest
        # absolute difference, slices on the (S, T) grid as before PR 38
        # -> laid tight: pure 0.062 -> 0.059, kernels 0.051 -> 0.056,
        # the two routes apart 0.032 -> 0.037 (the decode rows': 0.042,
        # 0.052 and 0.045 apart, either way). The routes differ by less
        # than either does from float32; 3e-2, which held the slices
        # while they read 0.032, sat inside that rounding.
        for a, b, tol in zip(pure[:2], kern[:2], (5e-2, 5e-2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=tol, rtol=tol)
        # Layer 0's K/V go in before any attention: bit for bit. Layer
        # 1's follow layer 0's attention output, which the two routes
        # round differently: held as the values the pools stand for.
        def values(cache, name):
            # (page 0 is reserved: nothing a row owns lives there)
            from llmq_tpu.ops.quant import dequantize_kv
            x, sc = cache[name][:, 1:], cache[name + "_scale"][:, 1:]
            deq = dequantize_kv(x.reshape(*x.shape[:3], 8, cfg.head_dim),
                                jnp.moveaxis(sc, 2, 3), jnp.float32)
            return [np.asarray(a, np.float32) for a in (x, sc, deq)]

        for name in ("k", "v"):
            for a, b in zip(values(pure[2], name)[:2],
                            values(kern[2], name)[:2]):
                np.testing.assert_array_equal(a[0], b[0])
            # (a projection of the layer's output, as the logits are:
            # 11 values of 1.3 million differ by 0.03-0.042)
            np.testing.assert_allclose(
                values(kern[2], name)[2], values(pure[2], name)[2],
                atol=5e-2, rtol=5e-2)


#: The bf16 serving geometries: name -> (H, H_kv, D, page_size,
#: max_pages, grid steps of the block-diagonal plan this one replaced
#: for a 256-token slice: (T / qb) x (max_pages / ppc) at the qb / ppc
#: its VMEM estimate collapsed to).
_SERVED = {
    "smollm2-1.7b": (32, 32, 64, 16, 256, 8192),
    "llama3-1b": (32, 8, 64, 16, 256, 2048),
    "llama3-8b": (32, 8, 128, 16, 256, 4096),
    "llama3-8b-ps128": (32, 8, 128, 128, 16, 2 * 16),
}


class TestPrefillTilePlan:
    """The plan is a pure function of the shapes, so the regression
    nobody saw (the kernel was only ever tested at H = 4, GD = 128 while
    SmolLM2's H = 32, GD = 2048 collapsed it to 8-token q blocks and
    one-page chunks) is held here, on the CPU, at the served widths."""

    @pytest.mark.parametrize("T", [256, 1024])
    @pytest.mark.parametrize("name", sorted(_SERVED))
    def test_served_geometries(self, name, T):
        from llmq_tpu.ops.pallas.prefill_attention import (
            VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES, prefill_tile_plan)
        H, Hkv, D, ps, mp, old_steps = _SERVED[name]
        plan = prefill_tile_plan(T, H, Hkv, D, ps, mp, 2)
        assert plan.vmem_bytes <= VMEM_BUDGET_BYTES < VMEM_LIMIT_BYTES
        assert plan.lane_waste <= 128 / D
        assert plan.lane_width % 128 == 0
        assert plan.num_windows * plan.lane_width == Hkv * D
        assert T % plan.q_block == 0 and plan.q_block % 16 == 0
        assert mp % plan.pages_per_chunk == 0
        assert plan.chunk_tokens >= min(256, mp * ps)
        if T == 256 and ps == 16:
            # A 256-token slice that ends at a 560-token context.
            assert plan.steps(T, 304) * 50 <= old_steps
            # The loop follows the context, not the block table.
            assert plan.steps(T, 304) < plan.steps(T, 3000)
        assert plan.steps(T, 0) <= (T // plan.q_block) * plan.num_chunks

    def test_steps_count_live_chunks_only(self):
        from llmq_tpu.ops.pallas.prefill_attention import prefill_tile_plan
        plan = prefill_tile_plan(256, 32, 32, 64, 16, 256, 2)
        # q blocks of 128 over 256-token chunks: the block that ends at
        # position 431 sees chunks 0-1, the one that ends at 559 0-2.
        assert (plan.q_block, plan.chunk_tokens) == (128, 256)
        assert plan.steps(256, 304) == 2 + 3
        # Past the block table's end every chunk is live, none more.
        assert plan.steps(256, 4096) == 2 * plan.num_chunks

    def test_rejects_heads_that_do_not_fill_lanes(self):
        from llmq_tpu.ops.pallas.prefill_attention import prefill_tile_plan
        with pytest.raises(ValueError, match="128-lane"):
            prefill_tile_plan(16, 4, 1, 64, 16, 8, 2)     # GD = 64
        with pytest.raises(ValueError, match="128-lane"):
            prefill_tile_plan(16, 4, 4, 96, 16, 8, 2)     # D = 96


class TestBlockwisePrefill:
    def test_matches_full_softmax(self):
        rng = np.random.default_rng(4)
        B, T, S, H, Hkv, D = 2, 8, 48, 8, 2, 32
        q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
        positions = jnp.asarray(
            np.stack([np.arange(T), np.arange(10, 10 + T)]), jnp.int32)
        seq_lens = jnp.asarray([T, 10 + T], jnp.int32)

        # Full-softmax reference with the same mask.
        qg = q.reshape(B, T, Hkv, H // Hkv, D)
        logits = jnp.einsum("btgrd,bsgd->bgrts", qg, k) * (D ** -0.5)
        kv_pos = jnp.arange(S)[None, None, :]
        mask = ((kv_pos <= positions[:, :, None])
                & (kv_pos < seq_lens[:, None, None]))
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        ref = jnp.einsum("bgrts,bsgd->btgrd", probs, v).reshape(B, T, H, D)

        for bs in (8, 16, 48, 512):
            out = blockwise_prefill_attention(q, k, v, positions, seq_lens,
                                              block_size=bs)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-2, rtol=2e-2)

    def test_matches_causal_prefill(self):
        """Zero-offset case must agree with causal_prefill_attention."""
        rng = np.random.default_rng(5)
        B, T, H, Hkv, D = 2, 16, 4, 2, 32
        q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
        seq_lens = jnp.full((B,), T, jnp.int32)
        ref = causal_prefill_attention(q, k, v)
        out = blockwise_prefill_attention(q, k, v, positions, seq_lens,
                                          block_size=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)


class TestFusedDecodeQ8:
    def _mk(self, rng, B=8, L=2, P=33, ps=8, Hkv=8, D=16, H=16, mp=4):
        from llmq_tpu.ops.quant import quantize_kv_rows
        GD = Hkv * D
        k_pool = jnp.zeros((L, P, ps, GD), jnp.int8)
        v_pool = jnp.zeros((L, P, ps, GD), jnp.int8)
        ks = jnp.zeros((L, P, Hkv, ps), jnp.bfloat16)
        vs = jnp.zeros((L, P, Hkv, ps), jnp.bfloat16)
        # Pre-populate history through the PURE write path so both
        # implementations read identical quantized pools.
        hist_k = jnp.asarray(rng.standard_normal((B, mp * ps, Hkv, D)),
                             jnp.float32)
        hist_v = jnp.asarray(rng.standard_normal((B, mp * ps, Hkv, D)),
                             jnp.float32)
        bt = jnp.asarray(
            rng.permutation(np.arange(1, P))[:B * mp].reshape(B, mp),
            jnp.int32)
        return (k_pool, v_pool, ks, vs), hist_k, hist_v, bt

    def test_matches_pure_q8(self, monkeypatch):
        from llmq_tpu.ops.attention import paged_decode_step_q8
        from llmq_tpu.ops.pallas.fused_decode import (
            fused_decode_attention_q8_pallas)
        from llmq_tpu.ops.quant import quantize_kv_rows

        rng = np.random.default_rng(7)
        B, Hkv, D, H, ps, mp = 8, 8, 16, 16, 8, 4
        pools, hist_k, hist_v, bt = self._mk(rng)
        # Write two history tokens per row via the pure path.
        monkeypatch.setenv("LLMQ_PALLAS", "0")
        positions = jnp.asarray([0, 3, 7, 8, 15, 20, 25, 29], jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
        for step in range(2):
            pos = positions + step
            page_of = bt[jnp.arange(B), pos // ps]
            slot_of = pos % ps
            _, pools = paged_decode_step_q8(
                q, hist_k[:, step], hist_v[:, step], pools, bt, pos + 1,
                page_of, slot_of, 1)
        # Step 3: pure vs kernel from the SAME pool state.
        pos = positions + 2
        seq_lens = pos + 1
        page_of = bt[jnp.arange(B), pos // ps]
        slot_of = pos % ps
        kn, vn = hist_k[:, 2], hist_v[:, 2]
        ref_attn, ref_pools = paged_decode_step_q8(
            q, kn, vn, pools, bt, seq_lens, page_of, slot_of, 1)
        kq, ksc = quantize_kv_rows(kn)
        vq, vsc = quantize_kv_rows(vn)
        attn, out_pools = fused_decode_attention_q8_pallas(
            q, kq, ksc, vq, vsc, pools, bt, seq_lens, page_of, 1,
            pages_per_chunk=2, interpret=True)
        np.testing.assert_allclose(
            np.asarray(attn, np.float32), np.asarray(ref_attn, np.float32),
            atol=3e-2, rtol=3e-2)
        for a, b in zip(out_pools, ref_pools):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))
