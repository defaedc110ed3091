"""Scale sanity: 8B/70B sizing math and the HF checkpoint import path.

The big configs are never materialized in CI (70B is ~141 GB of bf16);
these tests pin down the *arithmetic* the scheduler and deployment docs
rely on — param counts of the public Llama-3 architectures, HBM-fit
against the topology table — and exercise ``import_hf_llama`` end-to-end
on a synthetic 2-layer safetensors checkpoint.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llmq_tpu.models.llama import (  # noqa: E402
    forward_prefill,
    get_config,
    init_kv_pages,
    init_params,
    kv_bytes_per_token,
    param_count,
    param_count_analytic,
    weight_bytes,
)
from llmq_tpu.scheduling.topology import TpuTopology  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestParamCounts:
    def test_analytic_matches_materialized(self):
        for name in ("llama3-tiny",):
            cfg = get_config(name)
            params = init_params(jax.random.PRNGKey(0), cfg)
            assert param_count(params) == param_count_analytic(cfg)

    def test_llama3_1b(self):
        # Public Llama-3.2-1B: 1.24B parameters (tied embeddings).
        n = param_count_analytic(get_config("llama3-1b"))
        assert abs(n - 1.236e9) / 1.236e9 < 0.01, n

    def test_llama3_8b(self):
        # Public Llama-3-8B: 8.03B parameters.
        n = param_count_analytic(get_config("llama3-8b"))
        assert abs(n - 8.03e9) / 8.03e9 < 0.01, n

    def test_llama3_70b(self):
        # Public Llama-3-70B: 70.6B parameters.
        n = param_count_analytic(get_config("llama3-70b"))
        assert abs(n - 70.6e9) / 70.6e9 < 0.01, n


class TestHbmFit:
    """BASELINE sizing claims, checked against topology.py's HBM table."""

    def _fits(self, cfg, topo, *, kv_tokens: int = 0,
              overhead_frac: float = 0.1) -> bool:
        need = weight_bytes(cfg) + kv_tokens * kv_bytes_per_token(cfg)
        budget = topo.total_hbm_gb * (1 - overhead_frac) * 1e9
        return need <= budget

    def test_1b_fits_single_v5e(self):
        # The single-chip bench model: 1B bf16 (2.5 GB) + a 4096-token
        # KV pool on one 16 GB v5e chip.
        cfg = get_config("llama3-1b")
        topo = TpuTopology.declare(1, kind="v5e")
        assert self._fits(cfg, topo, kv_tokens=64 * 4096)

    def test_8b_needs_multichip(self):
        # 8B bf16 is ~16.06 GB — does NOT fit one 16 GB v5e chip; fits
        # v5e-8 with a large KV pool (BASELINE config #2 on v5e-8).
        cfg = get_config("llama3-8b")
        one = TpuTopology.declare(1, kind="v5e")
        eight = TpuTopology.declare(8, kind="v5e")
        assert not self._fits(cfg, one)
        # 64 concurrent 8k sequences: 64·8192 tokens × 128 KiB = 68 GB.
        assert self._fits(cfg, eight, kv_tokens=64 * 8192)

    def test_70b_needs_v5e16(self):
        # 70B bf16 is ~141 GB — exceeds v5e-8 (128 GB), fits 2-host
        # v5e-16 (256 GB) with KV headroom: BASELINE config #5.
        cfg = get_config("llama3-70b")
        eight = TpuTopology.declare(8, kind="v5e")
        sixteen = TpuTopology.declare(16, num_hosts=2, kind="v5e")
        assert not self._fits(cfg, eight)
        # 24 concurrent 8k sequences: 24·8192 tokens × 320 KiB = 63 GB.
        assert self._fits(cfg, sixteen, kv_tokens=24 * 8192)

    def test_kv_bytes_per_token(self):
        # 8B: 2 × 32 layers × 8 kv-heads × 128 dim × 2 B = 131072 B/token.
        assert kv_bytes_per_token(get_config("llama3-8b")) == 131072


_AOT_70B = r"""
import sys
sys.path.insert(0, {repo!r})
import jax
import jax.numpy as jnp
from llmq_tpu.models.llama import (forward_decode, get_config,
                                   init_kv_pages, init_params_quantized)
from llmq_tpu.parallel.mesh import make_mesh
from llmq_tpu.parallel.sharding import (batch_sharding,
                                        kv_cache_shardings,
                                        param_shardings)

assert len(jax.devices()) == 16, len(jax.devices())
# The flagship serving config (BASELINE #5): llama3-70b int8 on a
# 2-host v5e-16, dp x tp = 2 x 8 — tp=8 so the 8 GQA KV heads still
# shard (tp=16 would force full KV replication per chip).
cfg = get_config("llama3-70b", max_seq_len=8192)
mesh = make_mesh({{"dp": 2, "tp": 8}})
B, page_size = 8, 128
mpps = cfg.max_seq_len // page_size
num_pages = B * mpps + 1

# ABSTRACT params/cache: eval_shape traces the initializers without a
# byte of HBM — 70B int8 is ~70 GB that CI never materializes.
abs_params = jax.eval_shape(
    lambda: init_params_quantized(jax.random.PRNGKey(0), cfg))
abs_cache = jax.eval_shape(lambda: init_kv_pages(cfg, num_pages,
                                                 page_size))

def with_sharding(avals, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        avals, shardings)

a_params = with_sharding(abs_params,
                         param_shardings(cfg, mesh, quantized=True))
a_cache = with_sharding(dict(abs_cache), dict(kv_cache_shardings(cfg, mesh)))
a_tok = jax.ShapeDtypeStruct((B,), jnp.int32,
                             sharding=batch_sharding(mesh, 1))
a_pos = jax.ShapeDtypeStruct((B,), jnp.int32,
                             sharding=batch_sharding(mesh, 1))
a_bt = jax.ShapeDtypeStruct((B, mpps), jnp.int32,
                            sharding=batch_sharding(mesh, 2))

f = jax.jit(lambda p, t, pos, c, bt: forward_decode(p, cfg, t, pos, c, bt))
compiled = f.lower(a_params, a_tok, a_pos, a_cache, a_bt).compile()

# Record that the flagship sharding FITS a v5e chip: per-device
# argument bytes (weights shard over tp; cache over tp KV heads) under
# the 16 GB HBM with scheduler headroom.
mem = compiled.memory_analysis()
per_dev_gb = mem.argument_size_in_bytes / 1e9
assert per_dev_gb < 16.0 * 0.9, f"{{per_dev_gb:.1f}} GB/chip"
print(f"AOT70B OK {{per_dev_gb:.2f}} GB/chip", flush=True)
"""


@pytest.mark.skipif(
    os.environ.get("LLMQ_SKIP_MULTIPROC") == "1",
    reason="multi-process test disabled")
def test_70b_dp2tp8_aot_lowering_compiles():
    """Flagship multi-chip validity without HBM: the REAL llama3-70b
    int8 config AOT-lowers and compiles at dp*tp=16 from
    ShapeDtypeStructs on a 16-virtual-device CPU mesh, and the
    per-device argument footprint fits a 16 GB v5e chip. Subprocess:
    the test session's JAX is pinned to 8 devices (conftest)."""
    script = _AOT_70B.format(repo=REPO)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "AOT70B OK" in p.stdout, p.stdout


class TestHfImport:
    """import_hf_llama on a synthetic 2-layer safetensors checkpoint:
    at the tiny Llama shape, and at a Mistral-7B-v0.3 shape cut to size
    (GQA 4:1, head 128) — ``MistralForCausalLM`` names its tensors as
    ``LlamaForCausalLM`` does and has no bias, so one importer loads
    both."""

    @pytest.fixture(params=["llama3-tiny", "mistral-7b-v0.3"])
    def hf_dir(self, tmp_path, request):
        st = pytest.importorskip("safetensors.numpy")
        cut = ({} if request.param == "llama3-tiny" else
               dict(vocab_size=512, dim=512, n_layers=2, n_heads=4,
                    n_kv_heads=1, ffn_dim=256))
        cfg = get_config(request.param, **cut)
        rng = np.random.default_rng(0)

        def w(o, i):
            return (rng.standard_normal((o, i)) * 0.02).astype(np.float32)

        tensors = {"model.embed_tokens.weight": w(cfg.vocab_size, cfg.dim),
                   "model.norm.weight": np.ones(cfg.dim, np.float32),
                   "lm_head.weight": w(cfg.vocab_size, cfg.dim)}
        hd = cfg.head_dim
        for i in range(cfg.n_layers):
            p = f"model.layers.{i}"
            tensors[f"{p}.self_attn.q_proj.weight"] = w(
                cfg.n_heads * hd, cfg.dim)
            tensors[f"{p}.self_attn.k_proj.weight"] = w(
                cfg.n_kv_heads * hd, cfg.dim)
            tensors[f"{p}.self_attn.v_proj.weight"] = w(
                cfg.n_kv_heads * hd, cfg.dim)
            tensors[f"{p}.self_attn.o_proj.weight"] = w(
                cfg.dim, cfg.n_heads * hd)
            tensors[f"{p}.mlp.gate_proj.weight"] = w(cfg.ffn_dim, cfg.dim)
            tensors[f"{p}.mlp.up_proj.weight"] = w(cfg.ffn_dim, cfg.dim)
            tensors[f"{p}.mlp.down_proj.weight"] = w(cfg.dim, cfg.ffn_dim)
            tensors[f"{p}.input_layernorm.weight"] = np.ones(
                cfg.dim, np.float32)
            tensors[f"{p}.post_attention_layernorm.weight"] = np.ones(
                cfg.dim, np.float32)
        st.save_file(tensors, str(tmp_path / "model.safetensors"))
        return tmp_path, cfg, tensors

    def test_import_shapes_and_values(self, hf_dir):
        from llmq_tpu.models.checkpoint import import_hf_llama
        tmp_path, cfg, tensors = hf_dir
        params = import_hf_llama(str(tmp_path), cfg)
        assert param_count(params) == param_count_analytic(cfg)
        # HF stores (out, in); ours is (in, out): verbatim transpose —
        # NO rope permutation for HF safetensors (ADVICE r1 high).
        want = tensors["model.layers.0.self_attn.q_proj.weight"].T
        got = np.asarray(params["layers"]["wq"][0], np.float32)
        np.testing.assert_allclose(got, want.astype(np.float32), atol=2e-2)

    def test_imported_model_runs(self, hf_dir):
        from llmq_tpu.models.checkpoint import import_hf_llama
        tmp_path, cfg, _ = hf_dir
        params = import_hf_llama(str(tmp_path), cfg)
        cache = init_kv_pages(cfg, 8, 8)
        B, T = 1, 4
        toks = jnp.asarray([[5, 9, 2, 7]], jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
        lens = jnp.full((B,), T, jnp.int32)
        bt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        logits, _ = forward_prefill(params, cfg, toks, pos, lens, cache, bt)
        assert logits.shape == (B, T, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()

    def test_meta_rope_permutation(self, hf_dir):
        """meta_rope_layout=True applies the interleaved→split-half
        permutation; verify it is exactly HF's conversion permutation."""
        from llmq_tpu.models.checkpoint import _permute_meta_rope
        _, cfg, _ = hf_dir
        hd = cfg.head_dim
        n = cfg.n_heads
        # Build a marker matrix: row index encodes (head, dim_pos).
        w = np.arange(n * hd, dtype=np.float32)[:, None] * np.ones(
            (1, cfg.dim), np.float32)
        out = _permute_meta_rope(w, n)
        # Meta interleaved row order per head: [0,2,4,...,1,3,5,...]
        for h in range(n):
            rows = out[h * hd:(h + 1) * hd, 0] - h * hd
            expect = np.concatenate([np.arange(0, hd, 2),
                                     np.arange(1, hd, 2)])
            np.testing.assert_array_equal(rows, expect)
