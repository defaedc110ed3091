"""Ragged paged-attention plane (docs/performance.md "Ragged
attention"; PAPERS.md arxiv 2604.15464).

Three layers of pinning:

1. **Interpret-mode kernel equivalence** — the ragged Pallas kernel
   (bf16 + int8 variants) against the pure-JAX references in
   ops/attention.py: decode-only, prefill-only, mixed, GQA, int8
   scales, seq_len == 0 rows, slices crossing page boundaries. Runs
   the kernel BODY on CPU via ``interpret=True`` — no TPU needed.
2. **Engine-level token-for-token equivalence** — ragged on vs off
   through echo and CPU-JAX engines (pure fallback = the exact
   bucket-path ops), including prefix-cache continuation and the
   2-deep async pipeline.
3. **Surface collapse** — ragged warmup compiles strictly fewer
   programs (no per-bucket prefill), and the export-cache key includes
   the ragged geometry (a stale bucket-grid export must miss).

The compiled path (real Mosaic lowering: layout legality, DMA alignment,
scoped-VMEM fit) is not pytest's to check — tests always run on the
CPU. ``python chip_smoke.py --set
LLMQ_EXECUTOR_RAGGED_ATTENTION_ENABLED=true`` compiles the kernel on
the chip at llama3-1b geometry and holds its logits against the
pure-JAX path and a float32 run.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llmq_tpu.core.config import (AsyncPipelineConfig, MixedBatchConfig,
                                  PrefixCacheConfig)
from llmq_tpu.core.types import Priority
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import EchoExecutor, JaxExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.llama import get_config, init_params
from llmq_tpu.ops.attention import (RAGGED_Q_BLOCK,
                                    _dequant_window, _gqa_attend,
                                    _scale_scatter,
                                    blockwise_prefill_attention,
                                    paged_decode_attention_pooled)
from llmq_tpu.ops.pallas.ragged_paged_attention import (
    ragged_kernel_viable, ragged_mixed_attention_pallas,
    ragged_mixed_attention_q8_pallas)
from llmq_tpu.ops.quant import quantize_kv_rows

QBLK = RAGGED_Q_BLOCK


# -- interpret-mode kernel harness ---------------------------------------------


class Geometry:
    """One mixed-batch geometry: decode rows with varied lengths and
    slices with (qstart, qlen) descriptors packed qblk-aligned. Builds
    pools, tables, packed buffers and the pure-JAX references."""

    def __init__(self, *, B, dec_lens, slices, H, Hkv, D, page_size,
                 max_pages, num_pages=64, n_layers=1, layer=0, seed=0,
                 int8=False):
        rng = np.random.RandomState(seed)
        self.B, self.H, self.Hkv, self.D = B, H, Hkv, D
        self.GD = Hkv * D
        self.ps, self.MP, self.layer = page_size, max_pages, layer
        self.int8 = int8
        L = n_layers
        if int8:
            self.k_pool = jnp.asarray(
                rng.randint(-127, 127, (L, num_pages, page_size, self.GD)),
                jnp.int8)
            self.v_pool = jnp.asarray(
                rng.randint(-127, 127, (L, num_pages, page_size, self.GD)),
                jnp.int8)
            self.ks_pool = jnp.asarray(
                rng.rand(L, num_pages, Hkv, page_size) * 0.1, jnp.bfloat16)
            self.vs_pool = jnp.asarray(
                rng.rand(L, num_pages, Hkv, page_size) * 0.1, jnp.bfloat16)
        else:
            self.k_pool = jnp.asarray(
                rng.randn(L, num_pages, page_size, self.GD),
                jnp.float32).astype(jnp.bfloat16)
            self.v_pool = jnp.asarray(
                rng.randn(L, num_pages, page_size, self.GD),
                jnp.float32).astype(jnp.bfloat16)
        self.dec_lens = np.asarray(dec_lens, np.int32)
        assert len(dec_lens) == B
        used = 1
        self.dec_bt = np.zeros((B, max_pages), np.int32)
        self.write_page = np.zeros(B, np.int32)
        for b in range(B):
            n = -(-max(1, int(self.dec_lens[b])) // page_size)
            for j in range(n):
                self.dec_bt[b, j] = used
                used += 1
            if self.dec_lens[b] > 0:
                self.write_page[b] = self.dec_bt[
                    b, (self.dec_lens[b] - 1) // page_size]
        self.S = len(slices)
        self.pf_qstart = np.asarray([s[0] for s in slices], np.int32)
        self.pf_qlen = np.asarray([s[1] for s in slices], np.int32)
        self.pf_qoff = np.zeros(self.S, np.int32)
        off = 0
        for i, (_st, ln) in enumerate(slices):
            self.pf_qoff[i] = off
            off += -(-ln // QBLK) * QBLK
        self.N = max(QBLK, off)
        self.pf_bt = np.zeros((self.S, max_pages), np.int32)
        for i in range(self.S):
            n = -(-int(self.pf_qstart[i] + self.pf_qlen[i]) // page_size)
            for j in range(n):
                self.pf_bt[i, j] = used
                used += 1
        assert used <= num_pages
        self.q_dec = jnp.asarray(rng.randn(B, H, D),
                                 jnp.float32).astype(jnp.bfloat16)
        self.k_new = jnp.asarray(rng.randn(B, Hkv, D),
                                 jnp.float32).astype(jnp.bfloat16)
        self.v_new = jnp.asarray(rng.randn(B, Hkv, D),
                                 jnp.float32).astype(jnp.bfloat16)
        self.q_pf = jnp.asarray(rng.randn(self.N, H, D),
                                jnp.float32).astype(jnp.bfloat16)
        self.bt_all = jnp.asarray(
            np.concatenate([self.dec_bt, self.pf_bt], 0))
        self.seq_all = jnp.asarray(np.concatenate(
            [self.dec_lens, self.pf_qstart + self.pf_qlen]))

    def run_kernel(self):
        if self.int8:
            kq, ks = quantize_kv_rows(self.k_new)
            vq, vs = quantize_kv_rows(self.v_new)
            self._kq, self._ks, self._vq, self._vs = kq, ks, vq, vs
            return ragged_mixed_attention_q8_pallas(
                self.q_dec, kq, ks, vq, vs, self.q_pf,
                (self.k_pool, self.v_pool, self.ks_pool, self.vs_pool),
                self.bt_all, self.seq_all, jnp.asarray(self.write_page),
                jnp.asarray(self.pf_qoff), jnp.asarray(self.pf_qlen),
                jnp.asarray(self.pf_qstart), self.layer, interpret=True)
        return ragged_mixed_attention_pallas(
            self.q_dec, self.k_new, self.v_new, self.q_pf,
            self.k_pool, self.v_pool, self.bt_all, self.seq_all,
            jnp.asarray(self.write_page), jnp.asarray(self.pf_qoff),
            jnp.asarray(self.pf_qlen), jnp.asarray(self.pf_qstart),
            self.layer, interpret=True)

    def ref_decode(self):
        """Scatter the current rows, then the pooled pure-JAX decode
        attention (rows with seq_len 0 produce garbage both places —
        masked out of the comparison by the caller)."""
        lens = np.maximum(self.dec_lens, 1)
        slot = (lens - 1) % self.ps
        if self.int8:
            kp = self.k_pool.at[self.layer, self.write_page, slot].set(
                self._kq.reshape(self.B, self.GD))
            vp = self.v_pool.at[self.layer, self.write_page, slot].set(
                self._vq.reshape(self.B, self.GD))
            ksp = _scale_scatter(self.ks_pool, self.layer,
                                 jnp.asarray(self.write_page),
                                 jnp.asarray(slot), self._ks)
            vsp = _scale_scatter(self.vs_pool, self.layer,
                                 jnp.asarray(self.write_page),
                                 jnp.asarray(slot), self._vs)
            k = _dequant_window(kp, ksp, self.layer,
                                jnp.asarray(self.dec_bt), self.D)
            v = _dequant_window(vp, vsp, self.layer,
                                jnp.asarray(self.dec_bt), self.D)
            return _gqa_attend(self.q_dec, k, v, jnp.asarray(self.dec_lens))
        kp = self.k_pool.at[self.layer, self.write_page, slot].set(
            self.k_new.reshape(self.B, self.GD))
        vp = self.v_pool.at[self.layer, self.write_page, slot].set(
            self.v_new.reshape(self.B, self.GD))
        return paged_decode_attention_pooled(
            self.q_dec, kp, vp, jnp.asarray(self.dec_bt),
            jnp.asarray(self.dec_lens), self.layer)

    def ref_slice(self, i):
        """Blockwise online-softmax reference for slice i's tokens."""
        T = int(self.pf_qlen[i])
        W = self.MP * self.ps
        qs = self.q_pf[int(self.pf_qoff[i]):int(self.pf_qoff[i]) + T][None]
        if self.int8:
            kh = _dequant_window(self.k_pool, self.ks_pool, self.layer,
                                 jnp.asarray(self.pf_bt[i][None]), self.D)
            vh = _dequant_window(self.v_pool, self.vs_pool, self.layer,
                                 jnp.asarray(self.pf_bt[i][None]), self.D)
        else:
            kh = self.k_pool[self.layer,
                             jnp.asarray(self.pf_bt[i])].reshape(
                                 1, W, self.Hkv, self.D)
            vh = self.v_pool[self.layer,
                             jnp.asarray(self.pf_bt[i])].reshape(
                                 1, W, self.Hkv, self.D)
        pos = jnp.asarray(self.pf_qstart[i] + np.arange(T))[None]
        sl = jnp.asarray([self.pf_qstart[i] + self.pf_qlen[i]])
        return blockwise_prefill_attention(qs, kh, vh, pos, sl)[0]

    def check(self, tol=0.15):
        attn_d, attn_p, pools = self.run_kernel()
        ref_d = self.ref_decode()
        live = self.dec_lens > 0
        err_d = np.abs(np.asarray(attn_d, np.float32)
                       - np.asarray(ref_d, np.float32))[live]
        assert err_d.size == 0 or err_d.max() < tol, err_d.max()
        for i in range(self.S):
            if self.pf_qlen[i] == 0:
                continue
            ref = self.ref_slice(i)
            got = attn_p[int(self.pf_qoff[i]):
                         int(self.pf_qoff[i]) + int(self.pf_qlen[i])]
            err = np.abs(np.asarray(got, np.float32)
                         - np.asarray(ref, np.float32))
            assert err.max() < tol, (i, err.max())
        return attn_d, attn_p, pools


class TestInterpretKernel:
    def test_mixed_decode_and_slices(self):
        g = Geometry(B=4, dec_lens=[1, 7, 13, 25], H=4, Hkv=2, D=64,
                     page_size=8, max_pages=4,
                     slices=[(5, 10), (0, 3)], seed=0)
        _, _, (k_out, _v) = g.check()
        # The kernel's fused writeback actually landed the new rows.
        slot = (g.dec_lens - 1) % g.ps
        wrote = np.asarray(k_out[g.layer, g.write_page, slot])
        want = np.asarray(g.k_new.reshape(g.B, g.GD), np.float32)
        assert np.abs(wrote.astype(np.float32) - want).max() == 0.0

    def test_decode_only_no_live_slices(self):
        # One dead padding slice (qlen 0 → owner-less blocks): a pure
        # decode batch through the ragged launch.
        g = Geometry(B=8, dec_lens=[1, 2, 3, 8, 9, 16, 17, 31],
                     H=4, Hkv=2, D=64, page_size=8, max_pages=4,
                     slices=[(0, 0)], seed=1)
        g.check()

    def test_prefill_only_frozen_decode_rows(self):
        # seq_len == 0 decode rows (frozen lanes writing to page 0).
        g = Geometry(B=4, dec_lens=[0, 0, 0, 0], H=4, Hkv=2, D=64,
                     page_size=8, max_pages=4,
                     slices=[(0, 12), (0, 7), (3, 5)], seed=2)
        _, attn_p, _ = g.check()
        assert np.all(np.isfinite(np.asarray(attn_p, np.float32)))

    def test_slice_crossing_page_boundary_with_history(self):
        # 20-token slice starting mid-page at absolute position 11:
        # spans three pages and attends to cached history.
        g = Geometry(B=4, dec_lens=[5, 1, 9, 2], H=8, Hkv=4, D=32,
                     page_size=8, max_pages=6,
                     slices=[(11, 20), (0, 1)], seed=3)
        g.check()

    def test_gqa_multiple_query_groups(self):
        g = Geometry(B=4, dec_lens=[3, 30, 12, 1], H=16, Hkv=2, D=64,
                     page_size=8, max_pages=4,
                     slices=[(2, 9)], seed=4)
        g.check()

    def test_nonzero_layer_of_stacked_pool(self):
        g = Geometry(B=4, dec_lens=[4, 6, 2, 10], H=4, Hkv=2, D=64,
                     page_size=8, max_pages=2, n_layers=3, layer=2,
                     slices=[(0, 5)], seed=5)
        g.check()

    def test_int8_scales_mixed(self):
        g = Geometry(B=2, dec_lens=[3, 140], H=16, Hkv=8, D=16,
                     page_size=128, max_pages=2,
                     slices=[(2, 9), (0, 4)], seed=6, int8=True)
        _, _, pools = g.check()
        # Scale writeback for the decode rows landed.
        slot = (g.dec_lens - 1) % g.ps
        wrote = np.asarray(pools[2][g.layer, g.write_page, :, slot],
                           np.float32)
        assert np.abs(wrote - np.asarray(g._ks, np.float32)).max() == 0.0

    def test_int8_long_slice_multiblock(self):
        g = Geometry(B=2, dec_lens=[1, 2], H=8, Hkv=8, D=16,
                     page_size=128, max_pages=2,
                     slices=[(0, 20), (5, 3)], seed=7, int8=True)
        g.check()

    def test_viability_gate(self):
        assert ragged_kernel_viable(4, 8, 4, 128, 4)
        assert not ragged_kernel_viable(4, 8, 4, 129, 4)   # lane align
        assert not ragged_kernel_viable(4, 6, 4, 128, 4)   # sublane ps
        # q_block × heads must stay sublane-aligned.
        assert not ragged_kernel_viable(4, 8, 4, 128, 3, q_block=1)


# -- engine-level token-for-token equivalence ----------------------------------


WAVE = [
    ("hello world this is a long prompt " * 3, Priority.NORMAL),
    ("short", Priority.REALTIME),
    ("medium sized prompt here", Priority.LOW),
    ("another quite long prompt for slicing " * 2, Priority.HIGH),
    ("fifth request", Priority.NORMAL),
    ("sixth one goes last", Priority.LOW),
]


def drive_wave(eng, wave=WAVE, conv=None, max_new=24):
    handles = []
    for i, (prompt, prio) in enumerate(wave):
        handles.append(eng.submit(GenRequest(
            id=f"r{i}", prompt=prompt, priority=prio,
            conversation_id=(conv[i] if conv else ""),
            max_new_tokens=max_new)))
        eng.step()
        eng.step()
    eng.run_until_idle()
    return handles


def make_echo_engine(ragged: bool, **kw):
    """Echo engines differ between ragged on/off only in the packing
    geometry the executor reports (capacity-wide slices vs fixed
    widths) — the stream contract must hold across that re-packing."""
    tok = ByteTokenizer()
    ex = EchoExecutor(batch_size=4, page_size=8, num_pages=256,
                      max_pages_per_seq=16, eos_id=tok.eos_id,
                      chunk_size=4, mixed_prefill_slices=2,
                      mixed_slice_tokens=(16 if ragged else 8), **kw)
    mixed = MixedBatchConfig(enabled=True, prefill_token_budget=16,
                             max_slices=2)
    return InferenceEngine(ex, tok, enable_metrics=False,
                           max_decode_steps=64, mixed_batch=mixed)


class TestEchoEquivalence:
    def test_token_budget_packing_streams_identical(self):
        def run(ragged):
            eng = make_echo_engine(ragged)
            handles = drive_wave(eng, max_new=40)
            return [h.result.tokens for h in handles]

        assert run(True) == run(False)

    def test_async_pipeline_two_deep(self):
        def run(ragged):
            tok = ByteTokenizer()
            ex = EchoExecutor(batch_size=4, page_size=8, num_pages=256,
                              max_pages_per_seq=16, eos_id=tok.eos_id,
                              chunk_size=4, mixed_prefill_slices=2,
                              mixed_slice_tokens=(16 if ragged else 8),
                              async_chunks=True)
            eng = InferenceEngine(
                ex, tok, enable_metrics=False, max_decode_steps=64,
                mixed_batch=MixedBatchConfig(enabled=True,
                                             prefill_token_budget=16,
                                             max_slices=2),
                async_pipeline=AsyncPipelineConfig(enabled=True, depth=2))
            handles = drive_wave(eng, max_new=32)
            eng.stop()
            return [h.result.tokens for h in handles]

        assert run(True) == run(False)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama3-tiny", max_seq_len=256, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_jax_engine(tiny_model, ragged: bool, *, slots=3,
                    prefix_cache=None, pipeline=None,
                    max_decode_steps=16):
    cfg, params = tiny_model
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=slots, page_size=8,
                     num_pages=96, prefill_buckets=[16, 64],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8,
                     ragged_attention=ragged, ragged_token_capacity=16,
                     ragged_max_slices=2)
    return InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=max_decode_steps,
        prefix_cache=prefix_cache,
        mixed_batch=MixedBatchConfig(enabled=True,
                                     prefill_token_budget=16,
                                     max_slices=2),
        async_pipeline=pipeline)


class TestJaxEquivalence:
    """CPU-mode JAX (greedy): the ragged path runs the pure fallback —
    the exact bucket-path ops — so streams are token-for-token
    identical, while ALL prefill routes through the ragged program
    (no bucket programs exist on the ragged executor)."""

    def test_wave_with_preemption_streams_identical(self, tiny_model):
        def run(ragged):
            eng = make_jax_engine(tiny_model, ragged, slots=2)
            handles = []
            wave = [("a long prompt that needs slicing into chunks",
                     Priority.LOW),
                    ("second prompt arrives", Priority.NORMAL),
                    ("urgent!", Priority.REALTIME),
                    ("fourth one trails behind the others",
                     Priority.HIGH)]
            for i, (p, prio) in enumerate(wave):
                handles.append(eng.submit(GenRequest(
                    id=f"j{i}", prompt=p, priority=prio,
                    max_new_tokens=10)))
                eng.step()
                eng.step()
            eng.run_until_idle()
            return ([h.result.tokens for h in handles], eng)

        on, eng_on = run(True)
        off, _ = run(False)
        assert on == off
        assert eng_on.mixed_steps > 0, "ragged mixed path never ran"
        assert not any(p.startswith("prefill")
                       for p in eng_on.executor._aot)

    def test_prefix_cache_continuation_equivalence(self, tiny_model):
        def run(ragged):
            eng = make_jax_engine(
                tiny_model, ragged,
                prefix_cache=PrefixCacheConfig(enabled=True))
            out = []
            for turn in range(2):
                handles = []
                for c in range(3):
                    handles.append(eng.submit(GenRequest(
                        id=f"t{turn}c{c}",
                        prompt=f" turn {turn} for conversation {c}",
                        conversation_id=f"conv{c}",
                        max_new_tokens=8)))
                    eng.step()
                eng.run_until_idle()
                out.append([h.result.tokens for h in handles])
            assert eng.prefix_hits > 0 or any(
                h.result.cached_tokens > 0 for h in handles)
            return out

        assert run(True) == run(False)

    def test_async_pipeline_two_deep_equivalence(self, tiny_model):
        def run(ragged):
            eng = make_jax_engine(
                tiny_model, ragged,
                pipeline=AsyncPipelineConfig(enabled=True, depth=2))
            handles = drive_wave(eng, wave=WAVE[:4], max_new=8)
            eng.stop()
            return [h.result.tokens for h in handles]

        assert run(True) == run(False)

    def test_long_prompt_streams_through_capacity_chunks(self, tiny_model):
        """A prompt far beyond the packed capacity streams through
        repeated ragged dispatches (the executor re-chunks), then
        decodes to full length."""
        eng = make_jax_engine(tiny_model, True, max_decode_steps=12)
        h = eng.submit(GenRequest(id="long", prompt="x" * 150,
                                  max_new_tokens=12))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        assert h.result.prompt_tokens >= 150
        assert eng.allocator.used() == eng.allocator.pinned_pages()


# -- surface collapse + export-cache key ---------------------------------------


class TestSurfaceCollapse:
    def test_ragged_compiles_fewer_programs(self, tiny_model):
        cfg, params = tiny_model

        def warm(**kw):
            ex = JaxExecutor(cfg, params, batch_size=4, page_size=8,
                             num_pages=33, chunk_size=4,
                             prefill_buckets=[16, 32], eos_id=-1,
                             mixed_prefill_slices=2,
                             mixed_slice_tokens=8, **kw)
            ex.warmup()
            return ex

        bucket = warm(telemetry_name="rag-off")
        ragged = warm(telemetry_name="rag-on", ragged_attention=True,
                      ragged_token_capacity=16)
        assert len(ragged._aot) < len(bucket._aot)
        assert "ragged_chunk" in ragged._aot
        assert not any(p.startswith("prefill") for p in ragged._aot)

    def test_export_cache_key_includes_ragged_geometry(self, tiny_model):
        cfg, params = tiny_model

        def key(**kw):
            ex = JaxExecutor(cfg, params, batch_size=4, page_size=8,
                             num_pages=33, chunk_size=4,
                             prefill_buckets=[16, 32], eos_id=-1,
                             mixed_prefill_slices=2,
                             mixed_slice_tokens=8,
                             telemetry_name="rag-key", **kw)
            return ex._export_cache_key()

        k_bucket = key()
        k_ragged = key(ragged_attention=True, ragged_token_capacity=16)
        k_ragged2 = key(ragged_attention=True, ragged_token_capacity=32)
        assert k_bucket != k_ragged, "stale bucket-grid export would hit"
        assert k_ragged != k_ragged2, "capacity must be part of the key"
