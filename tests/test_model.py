"""Model-layer tests: llama forward correctness, paged KV semantics,
sampling, checkpointing. Runs on CPU in f32 for exact-ish numerics."""

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
    llama3_70b,
    llama3_8b,
    llama3_tiny,
    loss_fn,
    param_count,
)
from llmq_tpu.ops.attention import causal_prefill_attention, paged_decode_attention
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.rope import apply_rope, rope_cos_sin
from llmq_tpu.ops.sampling import greedy, sample_token

CFG = llama3_tiny(dtype=jnp.float32)
PAGE, NPAGES, MAXP = 4, 64, 8


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def fresh_cache():
    return init_kv_pages(CFG, NPAGES, PAGE, dtype=jnp.float32)


def tables(*page_lists):
    bt = np.zeros((len(page_lists), MAXP), np.int32)
    for i, pages in enumerate(page_lists):
        bt[i, :len(pages)] = pages
    return jnp.asarray(bt)


class TestConfigs:
    def test_known_architectures(self):
        c8 = llama3_8b()
        assert (c8.dim, c8.n_layers, c8.n_heads, c8.n_kv_heads) == (4096, 32, 32, 8)
        c70 = llama3_70b()
        assert (c70.dim, c70.n_layers, c70.n_heads) == (8192, 80, 64)
        assert get_config("llama3-tiny").name == "llama3-tiny"
        with pytest.raises(ValueError):
            get_config("llama4-900b")

    def test_param_count_tiny(self, params):
        assert param_count(params) == 426_624


class TestForward:
    def test_prefill_decode_equivalence(self, params):
        """The core correctness invariant: decoding token t with cached
        prefix must produce the same logits as full prefill at t."""
        B, T = 2, 10
        key = jax.random.PRNGKey(1)
        tokens = jax.random.randint(key, (B, T), 0, CFG.vocab_size)
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
        bt = tables([1, 2, 3], [4, 5, 6])
        full_logits, _ = forward_prefill(
            params, CFG, tokens, positions, jnp.array([T, T]), fresh_cache(), bt)
        # Prefill 6, then decode tokens 6..9 one at a time.
        cache = fresh_cache()
        _, cache = forward_prefill(
            params, CFG, tokens[:, :6], positions[:, :6], jnp.array([6, 6]),
            cache, bt)
        for t in range(6, T):
            step_logits, cache = forward_decode(
                params, CFG, tokens[:, t], jnp.array([t, t]), cache, bt)
            np.testing.assert_allclose(
                step_logits, full_logits[:, t], rtol=2e-4, atol=2e-4)

    def test_ragged_prefill_padding_isolated(self, params):
        """A short sequence padded inside a batch must produce the same
        logits as alone — page 0 absorbs padding garbage."""
        key = jax.random.PRNGKey(2)
        toks = jax.random.randint(key, (1, 5), 0, CFG.vocab_size)
        pos5 = jnp.arange(5)[None, :]
        solo, _ = forward_prefill(params, CFG, toks, pos5, jnp.array([5]),
                                  fresh_cache(), tables([1, 2]))
        batch_toks = jnp.concatenate(
            [jnp.pad(toks, ((0, 0), (0, 3))),
             jax.random.randint(key, (1, 8), 0, CFG.vocab_size)])
        pos8 = jnp.broadcast_to(jnp.arange(8), (2, 8))
        batched, _ = forward_prefill(
            params, CFG, batch_toks, pos8, jnp.array([5, 8]),
            fresh_cache(), tables([1, 2], [3, 4]))
        np.testing.assert_allclose(batched[0, :5], solo[0], rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("lengths", [[5], [3, 8, 1]])
    def test_last_only_is_the_last_valid_row_of_the_full_logits(
            self, params, lengths):
        """``last_only=True`` — the branch every SERVED prefill program
        takes — gathers each row's last valid token before the final
        norm and head: same logits as slicing the full (B, T, V) run,
        same cache, for one row and for ragged rows."""
        B, T = len(lengths), 8
        toks = jax.random.randint(jax.random.PRNGKey(7), (B, T), 0,
                                  CFG.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(T), (B, T))
        lens = jnp.asarray(lengths, jnp.int32)
        bt = tables(*[[1 + 2 * i, 2 + 2 * i] for i in range(B)])
        full, cache_full = forward_prefill(params, CFG, toks, pos, lens,
                                           fresh_cache(), bt)
        last, cache_last = forward_prefill(params, CFG, toks, pos, lens,
                                           fresh_cache(), bt,
                                           last_only=True)
        assert last.shape == (B, CFG.vocab_size)
        np.testing.assert_allclose(
            last, full[jnp.arange(B), lens - 1], rtol=1e-5, atol=1e-5)
        for name in cache_full:
            np.testing.assert_array_equal(cache_last[name],
                                          cache_full[name])

    def test_conversation_continuation(self, params):
        """Turn 2 prefill over retained pages == one long prefill
        (BASELINE config #3: KV reuse across turns)."""
        key = jax.random.PRNGKey(3)
        toks = jax.random.randint(key, (1, 8), 0, CFG.vocab_size)
        pos = jnp.arange(8)[None, :]
        bt = tables([1, 2])
        full, _ = forward_prefill(params, CFG, toks, pos, jnp.array([8]),
                                  fresh_cache(), bt)
        cache = fresh_cache()
        _, cache = forward_prefill(params, CFG, toks[:, :4], pos[:, :4],
                                   jnp.array([4]), cache, bt)
        cont, _ = forward_prefill(params, CFG, toks[:, 4:], pos[:, 4:],
                                  jnp.array([4]), cache, bt)
        np.testing.assert_allclose(cont[0], full[0, 4:], rtol=2e-4, atol=2e-4)

    def test_pages_isolate_sequences(self, params):
        """Two sequences with disjoint pages must not see each other."""
        key = jax.random.PRNGKey(4)
        toks = jax.random.randint(key, (2, 6), 0, CFG.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
        together, _ = forward_prefill(
            params, CFG, toks, pos, jnp.array([6, 6]), fresh_cache(),
            tables([1, 2], [3, 4]))
        alone0, _ = forward_prefill(
            params, CFG, toks[:1], pos[:1], jnp.array([6]), fresh_cache(),
            tables([1, 2]))
        np.testing.assert_allclose(together[0], alone0[0], rtol=2e-4, atol=2e-4)

    def test_loss_and_grad_finite(self, params):
        toks = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                                  CFG.vocab_size)
        bt = tables([1, 2], [3, 4])
        val, grads = jax.value_and_grad(loss_fn)(
            params, CFG, toks, fresh_cache(), bt)
        assert jnp.isfinite(val)
        assert all(bool(jnp.isfinite(g).all())
                   for g in jax.tree_util.tree_leaves(grads))


class TestOps:
    def test_rms_norm_unit_scale(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        out = rms_norm(x, jnp.ones(64))
        rms = jnp.sqrt(jnp.mean(out ** 2, axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)

    def test_rope_preserves_norm_and_relativity(self):
        q = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 2, 8))
        pos = jnp.arange(4)[None, :]
        cos, sin = rope_cos_sin(pos, 8)
        q_rot = apply_rope(q, cos, sin)
        np.testing.assert_allclose(
            jnp.linalg.norm(q_rot, axis=-1), jnp.linalg.norm(q, axis=-1),
            rtol=1e-5)
        # Relative property: <R(p)q, R(p+k)v> depends only on k.
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 2, 8))
        v_rot = apply_rope(v, cos, sin)
        d01 = jnp.sum(q_rot[0, 0] * v_rot[0, 1])
        cos2, sin2 = rope_cos_sin(pos + 5, 8)
        q_rot2 = apply_rope(q, cos2, sin2)
        v_rot2 = apply_rope(v, cos2, sin2)
        d01_shift = jnp.sum(q_rot2[0, 0] * v_rot2[0, 1])
        np.testing.assert_allclose(d01, d01_shift, rtol=1e-4, atol=1e-5)

    def test_paged_decode_matches_dense(self):
        """paged_decode_attention == dense attention over the gathered
        history."""
        key = jax.random.PRNGKey(3)
        B, H, HKV, D, page = 2, 4, 2, 8, 4
        q = jax.random.normal(key, (B, H, D))
        k_pages = jax.random.normal(jax.random.PRNGKey(4), (16, page, HKV, D))
        v_pages = jax.random.normal(jax.random.PRNGKey(5), (16, page, HKV, D))
        bt = jnp.array([[1, 2, 0, 0], [3, 4, 5, 0]])
        seq_lens = jnp.array([6, 11])
        out = paged_decode_attention(q, k_pages, v_pages, bt, seq_lens)
        # Dense reference for row 1:
        k_hist = k_pages[bt[1]].reshape(-1, HKV, D)[:11]
        v_hist = v_pages[bt[1]].reshape(-1, HKV, D)[:11]
        attn = causal_prefill_attention(
            q[1][None, None], k_hist[None], v_hist[None], q_offset=10)
        np.testing.assert_allclose(out[1], attn[0, 0], rtol=1e-5, atol=1e-5)


class TestSampling:
    def test_greedy(self):
        logits = jnp.array([[0.1, 5.0, 0.2], [3.0, 0.0, 0.1]])
        np.testing.assert_array_equal(greedy(logits), [1, 0])

    def test_temperature_zero_is_greedy(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 100))
        out = sample_token(logits, jax.random.PRNGKey(1), temperature=0.0)
        np.testing.assert_array_equal(out, greedy(logits))

    def test_top_k_restricts_support(self):
        logits = jnp.array([[10.0, 9.0, -50.0, -50.0]])
        for i in range(20):
            tok = sample_token(logits, jax.random.PRNGKey(i),
                               temperature=1.0, top_k=2)
            assert int(tok[0]) in (0, 1)

    def test_top_p_keeps_head(self):
        logits = jnp.log(jnp.array([[0.6, 0.3, 0.05, 0.05]]))
        for i in range(20):
            tok = sample_token(logits, jax.random.PRNGKey(i),
                               temperature=1.0, top_p=0.7)
            assert int(tok[0]) in (0, 1)

    def test_per_sequence_temperature(self):
        logits = jnp.stack([jnp.array([5.0, 0.0]), jnp.array([5.0, 0.0])])
        out = sample_token(logits, jax.random.PRNGKey(0),
                           temperature=jnp.array([0.0, 1.0]))
        assert int(out[0]) == 0  # greedy row


class TestCheckpoint:
    def test_roundtrip(self, params, tmp_path):
        from llmq_tpu.models.checkpoint import load_checkpoint, save_checkpoint

        path = str(tmp_path / "ckpt")
        save_checkpoint(path, params)
        restored = load_checkpoint(path, template=params)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
