"""Engine-level tensor-parallel serving.

The full serving stack — InferenceEngine → JaxExecutor(mesh) → sharded
model → sampled tokens — on the virtual 8-device CPU mesh: params and
the KV pool are genuinely partitioned over the ``tp`` axis (asserted on
the arrays), and the engine's output must be IDENTICAL to the
single-device engine (greedy, same weights). Covers bf16 and int8
(ADVICE r3: quantization must thread into param_shardings on the mesh
path), plus the builder's ``tpu.mesh_shape`` wiring.
"""

import jax

from llmq_tpu.core.types import Priority
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.llama import init_params, llama3_tiny
from llmq_tpu.parallel import make_mesh


def tp_cfg(**kw):
    # KV heads divisible by 8 so the tp sharding is REAL on every axis
    # (the tiny default's 2 KV heads would silently replicate).
    defaults = dict(dim=256, n_heads=8, n_kv_heads=8, ffn_dim=512,
                    vocab_size=512, max_seq_len=256)
    defaults.update(kw)
    return llama3_tiny(**defaults)


def build_engine_pair(params, cfg, mesh):
    tok = ByteTokenizer()
    kw = dict(batch_size=4, page_size=16, num_pages=65, chunk_size=4,
              prefill_buckets=[32], eos_id=tok.eos_id)
    ex_tp = JaxExecutor(cfg, params, mesh=mesh, **kw)
    ex_1 = JaxExecutor(cfg, params, **kw)
    eng_tp = InferenceEngine(ex_tp, tok, name="tp", enable_metrics=False,
                             max_decode_steps=8)
    eng_1 = InferenceEngine(ex_1, tok, name="one", enable_metrics=False,
                            max_decode_steps=8)
    return eng_tp, eng_1, ex_tp


def run_requests(engine, reqs):
    handles = [engine.submit(GenRequest(**r)) for r in reqs]
    engine.run_until_idle()
    return [h.result for h in handles]


REQS = [
    dict(id="a", prompt="hello tensor parallel", conversation_id="c1"),
    dict(id="b", prompt="second request", priority=Priority.REALTIME),
    dict(id="c", prompt="third one", conversation_id="c2"),
]


class TestShardedServing:
    def test_tp8_engine_matches_single_device(self):
        mesh = make_mesh({"tp": 8})
        cfg = tp_cfg()
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng_tp, eng_1, ex_tp = build_engine_pair(params, cfg, mesh)

        # The sharding is real: wq's output axis and the pool's KV-head
        # axis are split 8 ways.
        wq = ex_tp.params["layers"]["wq"]
        assert wq.sharding.spec == jax.sharding.PartitionSpec(
            None, None, "tp")
        shard_shapes = {s.data.shape for s in wq.addressable_shards}
        assert shard_shapes == {(cfg.n_layers, cfg.dim, cfg.dim // 8)}
        kv = ex_tp.cache["k"]
        assert kv.addressable_shards[0].data.shape[-1] == (
            kv.shape[-1] // 8)

        res_tp = run_requests(eng_tp, REQS)
        res_1 = run_requests(eng_1, REQS)
        for r_tp, r_1 in zip(res_tp, res_1):
            assert r_tp.finish_reason in ("eos", "length")
            assert r_tp.tokens == r_1.tokens
            assert r_tp.text == r_1.text

        # Turn 2 on a cached conversation: continuation prefill over the
        # SHARDED pool must also match.
        t2_tp = run_requests(eng_tp, [dict(id="a2", prompt=" more",
                                           conversation_id="c1")])[0]
        t2_1 = run_requests(eng_1, [dict(id="a2", prompt=" more",
                                         conversation_id="c1")])[0]
        assert t2_tp.cached_tokens > 0
        assert t2_tp.cached_tokens == t2_1.cached_tokens
        assert t2_tp.tokens == t2_1.tokens

    def test_tp8_int8_engine(self):
        """ADVICE r3: int8 + mesh must compose — quantized {q,s} leaves
        get the same named-axis shardings as the bf16 weights."""
        from llmq_tpu.ops.quant import quantize_params

        mesh = make_mesh({"tp": 8})
        cfg = tp_cfg()
        params = quantize_params(init_params(jax.random.PRNGKey(0), cfg))
        eng_tp, eng_1, ex_tp = build_engine_pair(params, cfg, mesh)
        wq = ex_tp.params["layers"]["wq"]
        assert wq["q"].sharding.spec == jax.sharding.PartitionSpec(
            None, None, "tp")
        assert wq["s"].sharding.spec == jax.sharding.PartitionSpec(
            None, None, "tp")
        res_tp = run_requests(eng_tp, REQS)
        res_1 = run_requests(eng_1, REQS)
        for r_tp, r_1 in zip(res_tp, res_1):
            assert r_tp.finish_reason in ("eos", "length")
            assert r_tp.tokens == r_1.tokens

    def test_dp_tp_mesh_also_serves(self):
        """A dp×tp mesh (the multi-host shape) serves correctly: dp is
        simply unused by the executor's shardings (engine replication
        handles data parallelism), tp partitions as usual."""
        mesh = make_mesh({"dp": 2, "tp": 4})
        cfg = tp_cfg()
        params = init_params(jax.random.PRNGKey(0), cfg)
        eng_tp, eng_1, _ = build_engine_pair(params, cfg, mesh)
        res_tp = run_requests(eng_tp, REQS[:2])
        res_1 = run_requests(eng_1, REQS[:2])
        for r_tp, r_1 in zip(res_tp, res_1):
            assert r_tp.tokens == r_1.tokens

    def test_builder_mesh_shape_wiring(self):
        """config.tpu.mesh_shape builds a meshed executor end-to-end."""
        from llmq_tpu.core.config import default_config
        from llmq_tpu.engine.builder import build_engine

        cfg = default_config()
        cfg.executor.backend = "jax"
        cfg.executor.max_batch_size = 2
        cfg.executor.kv_pages = 33
        cfg.executor.decode_chunk = 2
        cfg.executor.prefill_buckets = [32]
        cfg.model.name = "llama3-tiny"
        cfg.model.max_seq_len = 128
        cfg.tpu.mesh_shape = {"tp": 8}
        engine = build_engine(cfg, warmup=False, enable_metrics=False)
        assert engine.executor.mesh is not None
        res = run_requests(engine, [dict(id="x", prompt="hi")])[0]
        assert res.finish_reason in ("eos", "length")
