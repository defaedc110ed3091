"""Mesh-native serving executor (ISSUE 15, ROADMAP item 1).

The full serving stack on the virtual 8-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, pinned by
conftest): dp2×tp4 serves the mixed workload — prefill waves, decode,
prefix continuation, preemption, tiering demote/promote, async
pipeline depth 2 — and every token it commits is held, teacher-forced,
to the float32 reference's logits within a stated tolerance (sampled
streams of two bf16 partitionings are NOT compared: on random weights a
reduction-order tie flips the argmax); the paged pool's page axis
genuinely splits into per-replica
universes mirrored by the host allocator; the warmup/export cache is
keyed on the mesh geometry; and ``executor.mesh.enabled=false`` keeps
the exact single-chip path.
"""

import dataclasses
import os
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from llmq_tpu.core.config import MeshConfig, default_config  # noqa: E402
from llmq_tpu.core.types import Priority  # noqa: E402
from llmq_tpu.engine.engine import GenRequest, InferenceEngine  # noqa: E402
from llmq_tpu.engine.executor import JaxExecutor  # noqa: E402
from llmq_tpu.engine.kv_allocator import PageAllocator  # noqa: E402
from llmq_tpu.engine.tokenizer import ByteTokenizer  # noqa: E402
from llmq_tpu.models.llama import (  # noqa: E402
    forward_prefill,
    init_kv_pages,
    init_params,
    llama3_tiny,
)
from llmq_tpu.parallel import make_mesh  # noqa: E402
from llmq_tpu.parallel.sharding import (  # noqa: E402
    LLAMA_PARTITION_RULES,
    kv_cache_shardings,
    match_partition_rules,
    param_shardings,
    resolve_rules,
)

P = jax.sharding.PartitionSpec


def tp_cfg(**kw):
    # Head/ffn/vocab counts divisible by tp=4 AND tp=8 so the sharding
    # is real on every axis in both geometries.
    defaults = dict(dim=256, n_heads=8, n_kv_heads=8, ffn_dim=512,
                    vocab_size=512, max_seq_len=256)
    defaults.update(kw)
    return llama3_tiny(**defaults)


@pytest.fixture(scope="module")
def tiny(request):
    cfg = tp_cfg()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


#: How far below the float32 reference's best logit a committed token's
#: reference logit may sit. Logits here have std ≈ 1; a bf16 path is
#: within ~0.03 of float32 per logit (measured: tp4 vs float32 0.021,
#: single-device bf16 vs float32 0.023), so a near-tie can flip the
#: argmax by a few hundredths — while a sharding, KV-placement or
#: resume fault moves logits by O(1) and lands far outside.
REF_GAP_TOL = 0.125


@pytest.fixture(scope="module")
def f32_reference(tiny):
    """Teacher-forced float32 ``jax.numpy`` logits of the SAME weights:
    ``ref(ids)[i]`` is the next-token distribution after ``ids[:i+1]``.
    One pure-JAX prefill over a private pool, padded to the model's
    whole context so every call reuses one compiled program."""
    import jax.numpy as jnp

    cfg, params = tiny
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32, pallas=False)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    T, ps = cfg.max_seq_len, 16
    n_pages = T // ps
    bt = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]

    def ref(ids):
        assert 0 < len(ids) <= T, len(ids)
        toks = np.zeros((1, T), np.int32)
        toks[0, :len(ids)] = ids
        pos = np.minimum(np.arange(T, dtype=np.int32), len(ids) - 1)[None]
        logits, _ = forward_prefill(
            p32, cfg32, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray([len(ids)], jnp.int32),
            init_kv_pages(cfg32, n_pages + 1, ps), bt)
        return np.asarray(logits[0, :len(ids)])

    return ref


def assert_follows_reference(ref, ctx_ids, gen_ids):
    """Every committed token is, given the engine's OWN context
    (teacher forcing), within REF_GAP_TOL of the float32 reference's
    best next-token logit."""
    assert gen_ids, "nothing generated"
    ctx_ids, gen_ids = list(ctx_ids), list(gen_ids)
    logits = ref(ctx_ids + gen_ids)
    for j, t in enumerate(gen_ids):
        row = logits[len(ctx_ids) + j - 1]
        gap = float(row.max() - row[t])
        assert gap <= REF_GAP_TOL, (
            f"token {j} (id {t}): reference logit {float(row[t]):.3f} "
            f"is {gap:.3f} below the reference best "
            f"{float(row.max()):.3f} (id {int(row.argmax())})")


def conversation_stream(engine, conv_id, result):
    """(context, generated) of a conversation's latest turn, read from
    the engine's own record of what it fed the model: the pinned
    entry's written tokens plus the pending last sample."""
    entry = engine._conv_cache[conv_id]
    full = list(entry.tokens)
    if entry.pending is not None:
        full.append(entry.pending)
    gen = list(result.tokens)
    if full[-len(gen):] != gen:
        # An EOS finish: the EOS sample is committed but never written.
        full += gen[-1:]
    assert full[-len(gen):] == gen, (full[-len(gen) - 2:], gen)
    return full[:-len(gen)], gen


def run_requests(engine, reqs):
    handles = [engine.submit(GenRequest(**r)) for r in reqs]
    engine.run_until_idle()
    return [h.result for h in handles]


def run_preemption_phase(engine):
    """Deterministic preemption choreography: fill every slot with LOW
    decoders, let them run a step, then land REALTIME arrivals — the
    late urgents must preempt. Returns (prompt, tokens) per request:
    a preempted-and-resumed stream must still follow the reference
    given its own context (slot preemption resumes exactly)."""
    lows = [engine.submit(GenRequest(
        id=f"L{i}", prompt=f"steady background work {i}",
        priority=Priority.LOW, max_new_tokens=12)) for i in range(4)]
    # One step: the wave is seated (slots held, prefills dispatched)
    # but far from done — the urgents land mid-flight.
    engine.step()
    rts = [engine.submit(GenRequest(
        id=f"R{i}", prompt=f"urgent {i}", priority=Priority.REALTIME,
        max_new_tokens=6)) for i in range(2)]
    engine.run_until_idle()
    return [(h.request.prompt, h.result.tokens) for h in lows + rts]


def wait_until(fn, timeout=5.0, step=0.002):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if fn():
            return True
        time.sleep(step)
    return False


# -- partition-rule table ------------------------------------------------------


class TestPartitionRules:
    def test_rules_match_expected_layout(self, tiny):
        cfg, params = tiny
        specs = match_partition_rules(LLAMA_PARTITION_RULES, params)
        lay = specs["layers"]
        assert lay["wq"] == P(None, None, "tp")
        assert lay["wo"] == P(None, "tp", None)
        assert lay["w_down"] == P(None, "tp", None)
        assert lay["attn_norm"] == P()
        assert specs["embed"] == P("tp", None)

    def test_unmatched_param_raises(self):
        with pytest.raises(ValueError, match="no partition rule"):
            match_partition_rules(
                [(r"^only_this$", P())],
                {"mystery": np.zeros((4, 4), np.float32)})

    def test_divisibility_clamps_to_replication(self):
        """An axis the mesh can't divide evenly replicates — the rule
        still names tp, the resolver clamps exactly that axis."""
        mesh = make_mesh({"tp": 8})
        cfg = llama3_tiny(ffn_dim=84)      # 84 % 8 != 0
        sh = param_shardings(cfg, mesh)
        assert sh["layers"]["w_gate"].spec == P(None, None, None)
        assert sh["layers"]["w_down"].spec == P(None, None, None)
        assert sh["layers"]["wq"].spec == P(None, None, "tp")
        # The KV cache's head axis (n_kv_heads=2) can't split 8 ways
        # either — the pool replicates while wq stays sharded.
        assert kv_cache_shardings(cfg, mesh)["k"].spec == P(
            None, None, None, None)

    def test_quantized_scale_rides_weight_rule(self, tiny):
        """{q, s} leaves take the weight's named axes; the size-1
        contraction axis of the scale clamps to replication."""
        mesh = make_mesh({"dp": 2, "tp": 4})
        cfg, _ = tiny
        sh = param_shardings(cfg, mesh, quantized=True)
        assert sh["layers"]["wo"]["q"].spec == P(None, "tp", None)
        assert sh["layers"]["wo"]["s"].spec == P(None, None, None)
        assert sh["layers"]["wq"]["s"].spec == P(None, None, "tp")

    def test_resolve_rules_generic_tree(self):
        mesh = make_mesh({"dp": 2, "tp": 4})
        tree = {"a": np.zeros((8, 16), np.float32),
                "scalar": np.zeros((), np.float32)}
        out = resolve_rules([(r".", P("tp", None))], tree, mesh)
        assert out["a"].spec == P("tp", None)
        assert out["scalar"].spec == P()

    def test_kv_shardings_grow_dp_page_axis(self, tiny):
        cfg, _ = tiny
        mesh = make_mesh({"dp": 2, "tp": 4})
        kv = kv_cache_shardings(cfg, mesh, quantized=True, num_pages=64)
        assert kv["k"].spec == P(None, "dp", None, "tp")
        assert kv["k_scale"].spec == P(None, "dp", "tp", None)
        # num_pages not divisible by dp → page axis replicates.
        kv2 = kv_cache_shardings(cfg, mesh, num_pages=65)
        assert kv2["k"].spec == P(None, None, None, "tp")
        # Legacy call shape (no num_pages): unchanged layout.
        kv3 = kv_cache_shardings(cfg, mesh)
        assert kv3["k"].spec == P(None, None, None, "tp")


# -- dp page universes (host allocator) ----------------------------------------


class TestDpAllocator:
    def test_universe_ranges(self):
        al = PageAllocator(64, 16, dp_shards=2)
        assert al.pages_per_shard == 32
        a = al.alloc(3, shard=0)
        b = al.alloc(3, shard=1)
        assert all(1 <= p < 32 for p in a)
        assert all(32 <= p < 64 for p in b)
        assert [al.shard_of(p) for p in a + b] == [0, 0, 0, 1, 1, 1]

    def test_page0_reserved_only_in_shard0(self):
        al = PageAllocator(8, 16, dp_shards=2)
        assert al.available(shard=0) == 3   # 1..3
        assert al.available(shard=1) == 4   # 4..7
        assert al.available() == 7 == al.total

    def test_all_or_nothing_per_universe(self):
        al = PageAllocator(8, 16, dp_shards=2)
        assert al.alloc(4, shard=1) is not None
        # Shard 1 exhausted: a pinned alloc fails even though shard 0
        # has room (the caller decides whether to fall back).
        assert al.alloc(1, shard=1) is None
        assert al.alloc(1, shard=0) is not None

    def test_unpinned_alloc_picks_fullest_universe(self):
        al = PageAllocator(8, 16, dp_shards=2)
        assert al.alloc(2, shard=0) is not None   # shard0: 1 left
        pages = al.alloc(1)
        assert al.shard_of(pages[0]) == 1

    def test_free_returns_to_owning_universe(self):
        al = PageAllocator(16, 16, dp_shards=2)
        pages = al.alloc(8, shard=1)
        assert al.available(shard=1) == 0
        al.free(pages)
        assert al.available(shard=1) == 8
        assert al.available_by_shard() == [7, 8]

    def test_dp1_is_byte_identical_to_unsharded(self):
        old_like = PageAllocator(16, 16)
        new = PageAllocator(16, 16, dp_shards=1)
        for _ in range(3):
            assert old_like.alloc(4) == new.alloc(4)
        assert old_like.available() == new.available()

    def test_indivisible_pages_raise(self):
        with pytest.raises(ValueError, match="dp shards"):
            PageAllocator(65, 16, dp_shards=2)

    def test_bad_shard_raises(self):
        al = PageAllocator(16, 16, dp_shards=2)
        with pytest.raises(ValueError, match="bad dp shard"):
            al.alloc(1, shard=2)


# -- end-to-end equivalence ----------------------------------------------------


WAVE = [
    # More requests than slots → pending heap + admission waves; mixed
    # tiers → preemption pressure; two conversations → continuation.
    dict(id="a", prompt="hello tensor parallel mesh",
         conversation_id="c1"),
    dict(id="b", prompt="second request", priority=Priority.REALTIME),
    dict(id="c", prompt="third one", conversation_id="c2"),
    dict(id="d", prompt="a rather longer prompt that streams through "
                        "more than one prefill chunk easily",
         priority=Priority.LOW),
    dict(id="e", prompt="fifth", priority=Priority.REALTIME),
    dict(id="f", prompt="sixth request runs too"),
]


def build_engine(cfg, params, mesh=None, *, pipeline=None, mixed=None,
                 tiering=None, clock=None, pin_ttl=600.0,
                 batch_size=4, num_pages=64, max_decode_steps=8):
    from llmq_tpu.core.config import PrefixCacheConfig

    tok = ByteTokenizer()
    kw = dict(batch_size=batch_size, page_size=16, num_pages=num_pages,
              chunk_size=4, prefill_buckets=[32], eos_id=tok.eos_id)
    if mixed is not None:
        kw.update(mixed_prefill_slices=mixed.max_slices,
                  mixed_slice_tokens=mixed.slice_tokens)
    ex = JaxExecutor(cfg, params, mesh=mesh, **kw)
    eng = InferenceEngine(
        ex, tok, name="mesh" if mesh is not None else "one",
        enable_metrics=False, max_decode_steps=max_decode_steps,
        prefix_cache=PrefixCacheConfig(enabled=True),
        mixed_batch=mixed, async_pipeline=pipeline,
        kv_tiering=tiering, clock=clock, kv_pin_ttl=pin_ttl)
    return eng


class TestMeshServing:
    def test_dp2tp4_mixed_workload_follows_reference(self, tiny,
                                                     f32_reference):
        """The acceptance pin: waves + decode + prefix continuation +
        preemption + 2-deep async pipeline + mixed batching on dp2×tp4,
        every committed token held teacher-forced to the float32
        reference (REF_GAP_TOL) — the whole composition, pipeline AND
        mixed batching ON, must keep every row's KV and sampling
        right."""
        from llmq_tpu.core.config import (AsyncPipelineConfig,
                                          MixedBatchConfig)

        cfg, params = tiny
        tok = ByteTokenizer()
        mesh = make_mesh({"dp": 2, "tp": 4})
        pipe = AsyncPipelineConfig(enabled=True, depth=2)
        mixed = MixedBatchConfig(enabled=True, prefill_token_budget=32,
                                 max_slices=2)
        eng_m = build_engine(cfg, params, mesh, pipeline=pipe,
                             mixed=mixed)

        # The sharding is real: dp splits the pool's page axis, tp the
        # KV-head axis — each chip holds 1/8 of the cache.
        ex = eng_m.executor
        assert ex.dp_shards == 2
        kv = ex.cache["k"]
        assert kv.sharding.spec == P(None, "dp", None, "tp")
        shard_shape = kv.addressable_shards[0].data.shape
        assert shard_shape[1] == kv.shape[1] // 2
        assert shard_shape[3] == kv.shape[3] // 4

        res_m = run_requests(eng_m, WAVE)
        for w, r_m in zip(WAVE, res_m):
            assert r_m.finish_reason in ("eos", "length")
            ctx = tok.encode(w["prompt"])
            assert r_m.prompt_tokens == len(ctx)
            assert_follows_reference(f32_reference, ctx, r_m.tokens)

        # Prefix continuation over the dp-sharded pool: turn 2 of both
        # conversations adopts cached KV and still follows.
        t2 = [dict(id="a2", prompt=" more", conversation_id="c1"),
              dict(id="c2t", prompt=" again", conversation_id="c2")]
        r2_m = run_requests(eng_m, t2)
        for w, r_m in zip(t2, r2_m):
            assert r_m.cached_tokens > 0
            ctx, gen = conversation_stream(eng_m, w["conversation_id"],
                                           r_m)
            assert len(ctx) > r_m.cached_tokens   # history + new turn
            assert_follows_reference(f32_reference, ctx, gen)

        # Late-arriving REALTIME over a full batch: preemption REALLY
        # fires on the mesh engine, and every stream still follows.
        preempts = []
        orig = eng_m._preempt
        eng_m._preempt = (  # type: ignore[method-assign]
            lambda victim, release_pages: (
                preempts.append(victim.req.id),
                orig(victim, release_pages))[-1])
        streams = run_preemption_phase(eng_m)
        assert preempts, "no preemption occurred on the mesh engine"
        for prompt, toks in streams:
            assert_follows_reference(f32_reference, tok.encode(prompt),
                                     toks)
        eng_m.stop()

    def test_dp_page_locality(self, tiny):
        """Rows in dp shard d draw pages from universe d: serve one
        request per slot and check every live sequence's pages against
        its slot's universe."""
        cfg, params = tiny
        mesh = make_mesh({"dp": 2, "tp": 4})
        eng = build_engine(cfg, params, mesh, max_decode_steps=64)
        reqs = [GenRequest(id=f"s{i}", prompt=f"slot filler {i}",
                           max_new_tokens=48) for i in range(4)]
        handles = [eng.submit(r) for r in reqs]
        # Step until every slot is seated and prefilled, then verify
        # locality while the sequences are still live.
        for _ in range(200):
            eng.step()
            seated = [s for s in eng._slots if s is not None and s.pages]
            if len(seated) == 4:
                break
        checked = 0
        for slot, seq in enumerate(eng._slots):
            if seq is None or not seq.pages:
                continue
            want = eng._slot_shard(slot)
            for p in seq.pages:
                assert eng.allocator.shard_of(p) == want, (slot, p)
            checked += 1
        assert checked == 4
        eng.run_until_idle()
        assert all(h.result is not None for h in handles)
        eng.stop()

    def test_tiering_demote_promote_equivalence(self, tiny):
        """HBM→host demotion and promotion over the dp-sharded pool:
        turn 2 after a pin expiry is token-for-token the resident-pin
        baseline (the KV payload round-trips through the host tier of
        a mesh executor)."""
        from llmq_tpu.core.clock import FakeClock
        from llmq_tpu.core.config import KVTieringConfig

        cfg, params = tiny
        outs = []
        for tiering in (None, KVTieringConfig(enabled=True)):
            mesh = make_mesh({"dp": 2, "tp": 4})
            clock = FakeClock()
            eng = build_engine(cfg, params, mesh, tiering=tiering,
                               clock=clock,
                               pin_ttl=5.0 if tiering else 600.0,
                               max_decode_steps=10)
            h1 = eng.submit(GenRequest(id="t1",
                                       prompt="the quick brown fox",
                                       conversation_id="c",
                                       max_new_tokens=8))
            eng.run_until_idle()
            if tiering is not None:
                clock.advance(6.0)
                eng.step()
                assert "c" not in eng.cached_conversations()
                assert wait_until(lambda: sum(
                    eng._tiering.counts().values()) == 1)
            h2 = eng.submit(GenRequest(id="t2", prompt=" jumps over",
                                       conversation_id="c",
                                       max_new_tokens=8))
            eng.run_until_idle()
            if tiering is not None:
                st = eng.get_stats()["kv_tiering"]
                assert st["hits"]["host"] == 1, st
                assert h2.result.cached_tokens > 0
            outs.append((h1.result.tokens, h2.result.tokens))
            eng.stop()
        assert outs[0] == outs[1]

    def test_tp4_subset_mesh_serves(self, tiny, f32_reference):
        """tp4 over a 4-device subset of the 8 — the second CI-lane
        geometry: a mesh need not span every visible device. (tp8
        serving incl. continuation is test_engine_tp.py's pin.)"""
        cfg, params = tiny
        tok = ByteTokenizer()
        mesh = make_mesh({"tp": 4}, devices=jax.devices()[:4])
        eng_m = build_engine(cfg, params, mesh)
        assert eng_m.executor.dp_shards == 1
        res_m = run_requests(eng_m, WAVE[:2])
        for w, r_m in zip(WAVE[:2], res_m):
            assert_follows_reference(f32_reference,
                                     tok.encode(w["prompt"]), r_m.tokens)
        assert len(eng_m.executor.hbm_info()) == 4
        eng_m.stop()

    def test_reference_check_catches_a_wrong_stream(self, f32_reference):
        """The oracle has teeth: a stream with one token swapped for an
        arbitrary id is far outside the tolerance."""
        tok = ByteTokenizer()
        ctx = tok.encode("hello tensor parallel mesh")
        good = [int(f32_reference(ctx)[-1].argmax())]
        assert_follows_reference(f32_reference, ctx, good)
        with pytest.raises(AssertionError, match="below the reference"):
            assert_follows_reference(f32_reference, ctx, [good[0] ^ 1])

    def test_indivisible_dp_degrades_to_replication(self, tiny):
        """dp that doesn't divide the batch/pool builds with dp as pure
        replication (correctness first) — the executor reports it and
        the allocator keeps one universe."""
        cfg, params = tiny
        mesh = make_mesh({"dp": 2, "tp": 4})
        tok = ByteTokenizer()
        ex = JaxExecutor(cfg, params, mesh=mesh, batch_size=3,
                         page_size=16, num_pages=65, chunk_size=4,
                         prefill_buckets=[32], eos_id=tok.eos_id)
        assert ex.dp_shards == 1
        assert ex.cache["k"].sharding.spec == P(None, None, None, "tp")


# -- per-chip HBM accounting ---------------------------------------------------


class TestPerChipHbm:
    def test_truthful_split_dp2tp4(self, tiny):
        cfg, params = tiny
        mesh = make_mesh({"dp": 2, "tp": 4})
        tok = ByteTokenizer()
        ex = JaxExecutor(cfg, params, mesh=mesh, batch_size=4,
                         page_size=16, num_pages=64, chunk_size=4,
                         prefill_buckets=[32], eos_id=tok.eos_id)
        chips = ex.hbm_info()
        assert len(chips) == 8
        total_kv = sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree.leaves(ex.cache))
        # KV: page axis /dp × head axis /tp → every chip holds exactly
        # 1/8; the per-chip reports SUM to the true pool size (no
        # double-count).
        assert all(c["kv_pool_bytes"] == total_kv // 8 for c in chips)
        assert sum(c["kv_pool_bytes"] for c in chips) == total_kv
        # Weights: tp shards the big matmuls, dp REPLICATES — each
        # chip truthfully reports its tp shard (norms replicated), and
        # chips within/across dp replicas agree.
        w = {c["weights_bytes"] for c in chips}
        assert len(w) == 1
        total_w = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(params))
        per_chip = w.pop()
        assert total_w / 4 * 0.9 < per_chip < total_w / 4 * 1.2
        assert per_chip < total_w / 2    # replication not double-counted

    def test_hbm_gauge_cardinality_contract(self, tiny):
        """The per-chip gauge families stay within the label contract:
        one series per (engine, chip), chip ids are the 8 local
        devices, and a scrape after serving carries all of them."""
        from llmq_tpu.metrics.registry import get_metrics
        from llmq_tpu.observability.device import get_device_telemetry

        cfg, params = tiny
        mesh = make_mesh({"dp": 2, "tp": 4})
        tok = ByteTokenizer()
        ex = JaxExecutor(cfg, params, mesh=mesh, batch_size=4,
                         page_size=16, num_pages=64, chunk_size=4,
                         prefill_buckets=[32], eos_id=tok.eos_id,
                         telemetry_name="meshhbm",
                         telemetry_metrics=True)
        eng = InferenceEngine(ex, tok, name="meshhbm",
                              enable_metrics=True, max_decode_steps=4)
        run_requests(eng, [dict(id="x", prompt="hello")])
        get_device_telemetry("meshhbm").flush()
        m = get_metrics()
        fams = {"hbm_weights_bytes": m.hbm_weights_bytes,
                "hbm_kv_pool_bytes": m.hbm_kv_pool_bytes}
        for name, fam in fams.items():
            chip_ids = set()
            for metric in fam.collect():
                for s in metric.samples:
                    if s.labels.get("engine") != "meshhbm":
                        continue
                    chip_ids.add(s.labels["chip"])
            want = {str(d.id) for d in jax.local_devices()}
            assert chip_ids == want, (name, chip_ids)
        eng.stop()


# -- mesh-keyed warmup/export cache --------------------------------------------


class TestMeshExportCacheKey:
    def _executor(self, mesh, **kw):
        # The key/cache behavior doesn't need shardable head counts --
        # the smallest tiny model keeps the five warmups cheap.
        cfg = llama3_tiny()
        params = init_params(jax.random.PRNGKey(0), cfg)
        tok = ByteTokenizer()
        args = dict(batch_size=2, page_size=16, num_pages=34,
                    chunk_size=2, prefill_buckets=[16],
                    eos_id=tok.eos_id)
        args.update(kw)
        return JaxExecutor(cfg, params, mesh=mesh, **args)

    def test_key_changes_with_mesh_geometry(self):
        k_single = self._executor(None)._export_cache_key()
        k_tp8 = self._executor(make_mesh({"tp": 8}))._export_cache_key()
        dp2tp4 = self._executor(make_mesh({"dp": 2, "tp": 4}))
        keys = {k_single, k_tp8, dp2tp4._export_cache_key()}
        assert len(keys) == 3
        # Deterministic per geometry.
        again = self._executor(make_mesh({"dp": 2, "tp": 4}))
        assert again._export_cache_key() == dp2tp4._export_cache_key()

    def test_key_changes_with_param_shardings(self):
        """An edit to the partition rules changes the key: the params
        enter with their partition specs, not just shapes — a mesh
        artifact lowered under the old layout must MISS."""
        from types import SimpleNamespace

        from llmq_tpu.engine.executor import ExecutorSpec

        cfg = tp_cfg()
        mesh = make_mesh({"tp": 4}, devices=jax.devices()[:4])
        avals = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        cache = jax.eval_shape(lambda: init_kv_pages(cfg, 34, 16))

        def key_for(rules):
            placed = jax.tree.map(
                lambda a, shd: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=shd),
                avals, resolve_rules(rules, avals, mesh))
            stub = SimpleNamespace(
                model_cfg=cfg, spec=ExecutorSpec(2, 16, 34, 16, 2),
                chunk_size=2, prefill_batch=1, prefill_buckets=[16],
                _top_k=0, _top_p=1.0, mixed_prefill_slices=0,
                mixed_slice_tokens=0, mesh=mesh, dp_shards=1,
                params=placed, cache=dict(cache))
            return JaxExecutor._export_cache_key(stub)

        edited = ([(r"(^|/)wo(/|$)", P(None, None, "tp"))]
                  + list(LLAMA_PARTITION_RULES))
        assert key_for(LLAMA_PARTITION_RULES) == key_for(
            list(LLAMA_PARTITION_RULES))
        assert key_for(LLAMA_PARTITION_RULES) != key_for(edited)

    def test_mesh_keying_end_to_end(self, tmp_path, monkeypatch):
        """One flow over a real export dir: a cache primed single-chip
        HITS on a single-chip rebuild but MISSES (0 hits) when the
        same model builds on a mesh; the mesh's own artifacts hit on
        the same geometry and MISS after a reshape (mirrors the PR 13
        stale-bucket pin)."""
        monkeypatch.setenv("LLMQ_EXPORT_CACHE_DIR", str(tmp_path))
        ex1 = self._executor(None)
        ex1.warmup()
        assert not ex1._from_export_cache
        assert any(f.suffix == ".jaxexp" for f in tmp_path.iterdir())

        ex2 = self._executor(None)
        ex2.warmup()
        assert ex2._from_export_cache        # same geometry -> hits

        exm = self._executor(make_mesh({"dp": 2, "tp": 4}))
        exm.warmup()
        assert not exm._from_export_cache    # single-chip prime -> MISS

        exm2 = self._executor(make_mesh({"dp": 2, "tp": 4}))
        exm2.warmup()
        assert exm2._from_export_cache       # same mesh -> its artifacts

        ex8 = self._executor(make_mesh({"tp": 8}))
        ex8.warmup()
        assert not ex8._from_export_cache    # reshaped mesh -> MISS


# -- config / builder off-switch -----------------------------------------------


class TestMeshConfig:
    def test_defaults_off(self):
        cfg = default_config()
        assert cfg.executor.mesh.enabled is False
        assert cfg.executor.mesh.shape == {}

    def test_validation(self):
        with pytest.raises(ValueError, match="dp' or 'tp"):
            MeshConfig(shape={"zz": 2})
        with pytest.raises(ValueError, match="positive int"):
            MeshConfig(shape={"dp": 0})
        with pytest.raises(ValueError, match="requires a shape"):
            MeshConfig(enabled=True)
        MeshConfig(enabled=True, shape={"dp": 2, "tp": -1})

    def test_builder_executor_mesh_block(self):
        from llmq_tpu.engine.builder import build_engine

        cfg = default_config()
        cfg.executor.backend = "jax"
        cfg.executor.max_batch_size = 4
        cfg.executor.kv_pages = 64
        cfg.executor.decode_chunk = 2
        cfg.executor.prefill_buckets = [32]
        cfg.model.name = "llama3-tiny"
        cfg.model.max_seq_len = 128
        cfg.executor.mesh.enabled = True
        cfg.executor.mesh.shape = {"dp": 2, "tp": 4}
        engine = build_engine(cfg, warmup=False, enable_metrics=False)
        assert engine.executor.mesh is not None
        assert engine.executor.dp_shards == 2
        assert engine.allocator.dp_shards == 2
        res = run_requests(engine, [dict(id="x", prompt="hi")])[0]
        assert res.finish_reason in ("eos", "length")
        engine.stop()

    def test_off_switch_builds_single_chip(self):
        """mesh.enabled=false (default) + no legacy tpu.mesh_shape →
        no mesh object at all: the exact single-chip executor."""
        from llmq_tpu.engine.builder import build_engine

        cfg = default_config()
        cfg.executor.backend = "jax"
        cfg.executor.max_batch_size = 2
        cfg.executor.kv_pages = 33
        cfg.executor.decode_chunk = 2
        cfg.executor.prefill_buckets = [32]
        cfg.model.name = "llama3-tiny"
        cfg.model.max_seq_len = 128
        engine = build_engine(cfg, warmup=False, enable_metrics=False)
        assert engine.executor.mesh is None
        assert engine.executor.dp_shards == 1
        assert engine.allocator.dp_shards == 1
        engine.stop()

    def test_legacy_tpu_mesh_shape_still_wires(self):
        from llmq_tpu.engine.builder import build_engine

        cfg = default_config()
        cfg.executor.backend = "jax"
        cfg.executor.max_batch_size = 2
        cfg.executor.kv_pages = 32
        cfg.executor.decode_chunk = 2
        cfg.executor.prefill_buckets = [32]
        cfg.model.name = "llama3-tiny"
        cfg.model.max_seq_len = 128
        cfg.tpu.mesh_shape = {"tp": 8}
        engine = build_engine(cfg, warmup=False, enable_metrics=False)
        assert engine.executor.mesh is not None
        engine.stop()


# -- demotion economics v2 (ROADMAP 4c satellite) ------------------------------


class TestDemotionEconomics:
    def test_hot_conversation_outlives_cold_under_pressure(self):
        """A conversation with a measured saved-prefill rate outlives a
        cold (but more recently used) one when pool pressure reclaims
        a pin — value ranking, not recency."""
        from llmq_tpu.core.config import KVTieringConfig
        from llmq_tpu.engine.engine import _ConvKV
        from llmq_tpu.observability.usage import (RequestUsage,
                                                  get_usage_ledger,
                                                  reset_usage)
        from llmq_tpu.engine.executor import EchoExecutor

        reset_usage()
        led = get_usage_ledger()
        led.reconfigure(enabled=True)
        try:
            tok = ByteTokenizer()
            ex = EchoExecutor(batch_size=2, page_size=8, num_pages=32,
                              max_pages_per_seq=8, eos_id=tok.eos_id)
            eng = InferenceEngine(
                ex, tok, enable_metrics=False, name="econ",
                kv_tiering=KVTieringConfig(enabled=True))
            assert eng._tiering.eviction_policy == "saved_rate"
            # "hot" keeps earning saved-prefill credit; "cold" never
            # did — but was touched MORE recently.
            u = RequestUsage()
            u.saved_prefill_device_s = 2.0
            led.finalize("r-hot", u, tenant="t", priority="normal",
                         engine="econ", conversation="hot", tokens=4)
            for cid, ts in (("hot", 10.0), ("cold", 99.0)):
                pages = eng.allocator.alloc(2)
                bt = np.zeros(eng.spec.max_pages_per_seq, np.int32)
                bt[:2] = pages
                eng._conv_cache[cid] = _ConvKV(
                    pages=pages, block_table=bt, length=8,
                    last_used=ts, tokens=list(range(8)))
                eng.allocator.pin(cid, pages)
            assert eng._reclaim_idle_conversation()
            assert "hot" in eng._conv_cache       # survived
            assert "cold" not in eng._conv_cache  # evicted first
            eng.stop()
        finally:
            reset_usage()

    def test_lru_policy_restores_recency(self):
        from llmq_tpu.core.config import KVTieringConfig

        cfg = KVTieringConfig(enabled=True, eviction_policy="lru")
        assert cfg.eviction_policy == "lru"
        with pytest.raises(ValueError, match="eviction_policy"):
            KVTieringConfig(eviction_policy="mru")

    def test_plane_spill_ranks_by_saved_rate(self):
        """Host→store spill picks the lowest-value entry, not the
        least recent, when the ledger has signal."""
        from llmq_tpu.core.clock import FakeClock
        from llmq_tpu.core.config import KVTieringConfig
        from llmq_tpu.conversation.persistence import InMemoryStore
        from llmq_tpu.observability.usage import (RequestUsage,
                                                  get_usage_ledger,
                                                  reset_usage)
        from llmq_tpu.tiering import KVTieringPlane

        class FakeKVExec:
            def kv_page_spec(self):
                return [((2, 4, 8), np.dtype(np.float32))]

            def export_kv_pages(self, pages):
                return [np.stack([np.full((2, 4, 8), float(p),
                                          np.float32) for p in pages],
                                 axis=1)]

            def import_kv_pages(self, pages, leaves):
                pass

        reset_usage()
        led = get_usage_ledger()
        led.reconfigure(enabled=True)
        try:
            clock = FakeClock()
            plane = KVTieringPlane(
                KVTieringConfig(enabled=True, host_max_conversations=2),
                "econplane", FakeKVExec(), clock=clock, metrics=False)
            plane.store = InMemoryStore()
            assert plane.eviction_policy == "saved_rate"
            u = RequestUsage()
            u.saved_prefill_device_s = 3.0
            led.finalize("r-hot2", u, tenant="t", priority="normal",
                         engine="econplane", conversation="hot",
                         tokens=4)
            # "hot" is demoted FIRST (oldest last_used) — pure LRU
            # would spill it; value ranking spills the cold ones.
            for cid in ("hot", "cold", "third"):
                plane.demote(cid, [1], list(range(8)), 8, None)
                assert wait_until(
                    lambda c=cid: plane._entries[c].ready.is_set()
                    or plane._entries[c].spilling)
                clock.advance(5.0)
            assert wait_until(lambda: plane.counts()["store"] == 1
                              and plane.counts()["host"] == 2)
            with plane._mu:
                assert plane._entries["hot"].tier == "host"
                assert plane._entries["cold"].tier == "store"
            plane.stop()
        finally:
            reset_usage()


class TestDpAllocLadder:
    def test_cross_universe_fallback_beats_shedding(self):
        """A full universe with room elsewhere must take the
        cross-universe pages — NOT destroy pinned conversation KV or
        preempt anything (bounded non-locality is the cheapest rung)."""
        from llmq_tpu.engine.engine import (GenHandle, _ConvKV,
                                            _Sequence)
        from llmq_tpu.engine.executor import EchoExecutor

        tok = ByteTokenizer()
        ex = EchoExecutor(batch_size=4, page_size=8, num_pages=32,
                          max_pages_per_seq=8, eos_id=tok.eos_id)
        ex.dp_shards = 2
        eng = InferenceEngine(ex, tok, enable_metrics=False,
                              name="ladder")
        assert eng.allocator.dp_shards == 2
        # A pinned conversation in universe 1 — the ladder's shed
        # victim if it ever gets that far.
        pin = eng.allocator.alloc(2, shard=1)
        bt = np.zeros(eng.spec.max_pages_per_seq, np.int32)
        bt[:2] = pin
        eng._conv_cache["pinme"] = _ConvKV(
            pages=pin, block_table=bt, length=8, last_used=0.0,
            tokens=list(range(8)))
        eng.allocator.pin("pinme", pin)
        # Exhaust universe 0 entirely.
        assert eng.allocator.alloc(
            eng.allocator.available(shard=0), shard=0) is not None
        req = GenRequest(id="x", prompt="hi")
        seq = _Sequence(req, GenHandle(req), 0,
                        eng.spec.max_pages_per_seq)
        got = eng._alloc_pages(2, seq, shard=0)
        assert got is not None
        assert all(eng.allocator.shard_of(p) == 1 for p in got)
        assert "pinme" in eng._conv_cache     # no shedding happened
        eng.stop()


# -- 8B tp4 AOT lowering (extends tests/test_scale.py's flagship set) ----------


_AOT_8B_TP4 = r"""
import sys
sys.path.insert(0, {repo!r})
import jax
import jax.numpy as jnp
from llmq_tpu.models.llama import (forward_decode, get_config,
                                   init_kv_pages, init_params)
from llmq_tpu.parallel.mesh import make_mesh
from llmq_tpu.parallel.sharding import (batch_sharding,
                                        kv_cache_shardings,
                                        param_shardings)

assert len(jax.devices()) == 8, len(jax.devices())
# BASELINE config #2: llama3-8b bf16 on v5e-8, tp=4 over a dp2 x tp4
# mesh (8 GQA KV heads shard 4 ways; dp splits the page axis).
cfg = get_config("llama3-8b", max_seq_len=8192)
mesh = make_mesh({{"dp": 2, "tp": 4}})
B, page_size = 8, 128
mpps = cfg.max_seq_len // page_size
num_pages = B * mpps + 2   # even → dp-divisible

abs_params = jax.eval_shape(
    lambda: init_params(jax.random.PRNGKey(0), cfg))
abs_cache = jax.eval_shape(lambda: init_kv_pages(cfg, num_pages,
                                                 page_size))

def with_sharding(avals, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        avals, shardings)

a_params = with_sharding(abs_params, param_shardings(cfg, mesh))
a_cache = with_sharding(dict(abs_cache),
                        dict(kv_cache_shardings(cfg, mesh,
                                                num_pages=num_pages)))
a_tok = jax.ShapeDtypeStruct((B,), jnp.int32,
                             sharding=batch_sharding(mesh, 1))
a_pos = jax.ShapeDtypeStruct((B,), jnp.int32,
                             sharding=batch_sharding(mesh, 1))
a_bt = jax.ShapeDtypeStruct((B, mpps), jnp.int32,
                            sharding=batch_sharding(mesh, 2))

f = jax.jit(lambda p, t, pos, c, bt: forward_decode(p, cfg, t, pos, c, bt))
compiled = f.lower(a_params, a_tok, a_pos, a_cache, a_bt).compile()
mem = compiled.memory_analysis()
per_dev_gb = mem.argument_size_in_bytes / 1e9
assert per_dev_gb < 16.0 * 0.9, f"{{per_dev_gb:.1f}} GB/chip"

# Export-cache key identity under the mesh-aware cache: the REAL key
# function over the flagship geometry (abstract trees carry shapes +
# dtypes, which is all the key hashes).
from types import SimpleNamespace
from llmq_tpu.engine.executor import ExecutorSpec, JaxExecutor

def key_for(mesh_, dp_shards, cache):
    stub = SimpleNamespace(
        model_cfg=cfg,
        spec=ExecutorSpec(B, page_size, num_pages, mpps, 2),
        chunk_size=16, prefill_batch=4, prefill_buckets=[512],
        _top_k=0, _top_p=1.0, mixed_prefill_slices=0,
        mixed_slice_tokens=0, mesh=mesh_,
        dp_shards=dp_shards, params=abs_params, cache=cache)
    return JaxExecutor._export_cache_key(stub)

k_mesh = key_for(mesh, 2, a_cache)
k_single = key_for(None, 1, dict(abs_cache))
k_tp8 = key_for(make_mesh({{"tp": 8}}), 1, dict(abs_cache))
assert len({{k_mesh, k_single, k_tp8}}) == 3, (k_mesh, k_single, k_tp8)
assert k_mesh == key_for(mesh, 2, a_cache)
print(f"AOT8B OK {{per_dev_gb:.2f}} GB/chip", flush=True)
"""


@pytest.mark.skipif(os.environ.get("LLMQ_SKIP_MULTIPROC") == "1",
                    reason="multi-process test disabled")
def test_8b_tp4_aot_lowering_and_mesh_cache_key():
    """8B bf16 at dp2×tp4 AOT-lowers from ShapeDtypeStructs on the
    8-virtual-device CPU mesh, fits a 16 GB v5e chip per-device, and
    the export-cache key separates mesh/single-chip/re-geometried
    artifacts (ISSUE 15 acceptance)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = _AOT_8B_TP4.format(repo=repo)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "AOT8B OK" in p.stdout, p.stdout
