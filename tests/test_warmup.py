"""Warmup / AOT-compile path coverage.

The executor's parallel warmup AOT-compiles every program from abstract
shapes and serves through the stored executables (executor.py:_aot). A
signature drift between the ShapeDtypeStruct specs and the real call
sites would otherwise be swallowed by warmup()'s fallback and silently
reintroduce the multi-minute serial warmup — these tests make that
drift loud.
"""

import numpy as np
import pytest

import jax

from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.models.llama import init_params, llama3_tiny
from llmq_tpu.parallel import make_mesh


def build(mesh=None, chunk=4):
    cfg = llama3_tiny(max_seq_len=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return JaxExecutor(cfg, params, batch_size=4, page_size=16,
                       num_pages=33, chunk_size=chunk,
                       prefill_buckets=[16, 32], eos_id=-1, mesh=mesh)


class TestWarmup:
    def test_aot_programs_built_and_serving(self):
        ex = build()
        ex.warmup()
        # Loud failure if the AOT pass fell back: every program must be
        # present (a spec/signature drift would leave _aot empty).
        # (No single-step ``decode``: with chunks longer than a step
        # the engine never dispatches it —
        # test_single_step_program_only_without_chunks.)
        assert set(ex._aot) == {"prefill_b16", "prefill_b32",
                                "prefill_multi_b16", "prefill_multi_b32",
                                "decode_chunk",
                                "mixed_chunk"}, set(ex._aot)

        # Serving goes through the executables and matches the jit path.
        bt = np.zeros((4, ex.spec.max_pages_per_seq), np.int32)
        bt[0, :2] = [1, 2]
        first = ex.prefill([5, 6, 7], 0, bt[0], 0.0, 0)
        toks = np.full(4, first, np.int32)
        pos = np.full(4, 3, np.int32)
        out_aot = ex.decode_chunk(toks, pos, bt, np.zeros(4, np.float32),
                                  np.full(4, 4, np.int32))

        ex2 = build()   # no warmup: jit wrappers
        first2 = ex2.prefill([5, 6, 7], 0, bt[0], 0.0, 0)
        out_jit = ex2.decode_chunk(toks, pos, bt, np.zeros(4, np.float32),
                                   np.full(4, 4, np.int32))
        assert first == first2
        # Row 0 owns real pages; rows 1-3 point at reserved page 0,
        # whose (never-read-in-production) contents differ between a
        # warmed and an unwarmed executor — compare only the real row.
        assert (out_aot[0] == out_jit[0]).all()

    def test_single_step_program_only_without_chunks(self):
        """``decode`` and ``decode_chunk`` exclude each other: the
        warm-up compiles the one the engine can dispatch at this
        ``chunk_size`` and not the other (at 7 B an executable of tens
        of MB that no request would ever run)."""
        ex = build(chunk=1)
        ex.warmup()
        assert "decode" in ex._aot and "decode_chunk" not in ex._aot
        bt = np.zeros((4, ex.spec.max_pages_per_seq), np.int32)
        out = ex.decode(np.ones(4, np.int32), np.zeros(4, np.int32), bt,
                        np.zeros(4, np.float32))
        assert out.shape == (4,)

    def test_nothing_compiles_after_warmup(self):
        """The serving loop's eager device ops — the batched-prefill
        row split, the lane-join scatters over host-born and carried
        lane arrays — all ran during warm-up: dispatching them again
        compiles nothing (chip_smoke.py asserts the same over HTTP).
        The batch and wave sizes are this test's alone, so no earlier
        test in the session can have compiled these shapes for it."""
        from llmq_tpu.observability.device import BACKEND_COMPILES

        cfg = llama3_tiny(max_seq_len=128)
        ex = JaxExecutor(cfg, init_params(jax.random.PRNGKey(0), cfg),
                         batch_size=6, page_size=16, num_pages=33,
                         chunk_size=4, prefill_buckets=[16, 32],
                         prefill_batch=3, eos_id=-1)
        BACKEND_COMPILES.watch()
        ex.warmup()
        before = BACKEND_COMPILES.count

        B, MP = 6, ex.spec.max_pages_per_seq
        bt = np.zeros((B, MP), np.int32)
        bt[:3, 0] = [1, 2, 3]
        firsts = ex.prefill_multi_async(
            [([5, 6, 7], 0, bt[0], 0.0), ([8] * 20, 0, bt[1], 0.0)])
        firsts.append(ex.prefill_async([9, 9], 0, bt[2], 0.0))
        zeros, temps = np.zeros(B, np.int32), np.zeros(B, np.float32)
        budgets = np.full(B, 4, np.int32)
        h = ex.decode_chunk_start(
            zeros, zeros, bt, temps, budgets,
            overrides=[(0, firsts[0], 3), (1, firsts[1], 20)])
        h2 = ex.decode_chunk_start(
            None, None, bt, temps, budgets, carry=h,
            overrides=[(2, firsts[2], 2)])
        assert h2.fetch().shape == (B, 4)
        ex.mixed_chunk_start(zeros, zeros, bt, temps, budgets,
                             [(3, [1, 2, 3], 0, bt[3], 0.0)]).fetch()
        assert BACKEND_COMPILES.count == before

    def test_a_full_batchs_carried_chunks_compile_nothing(self):
        """What a full batch adds to the serving loop ran during
        warm-up too: a MIXED chunk started from a carry, and the join
        of a row whose final slice rode it — ``pf_first`` indexed on
        the device at either slice, scattered into a lane of a mixed
        and of a decode chunk. First use after ready compiles nothing.
        A batch size of this test's own, as above."""
        from llmq_tpu.observability.device import BACKEND_COMPILES

        cfg = llama3_tiny(max_seq_len=128)
        ex = JaxExecutor(cfg, init_params(jax.random.PRNGKey(0), cfg),
                         batch_size=5, page_size=16, num_pages=33,
                         chunk_size=4, prefill_buckets=[16, 32],
                         mixed_prefill_slices=2, mixed_slice_tokens=16,
                         eos_id=-1)
        BACKEND_COMPILES.watch()
        ex.warmup()
        before = BACKEND_COMPILES.count

        B, MP = 5, ex.spec.max_pages_per_seq
        bt = np.zeros((B, MP), np.int32)
        bt[:4, 0] = [1, 2, 3, 4]
        zeros, temps = np.zeros(B, np.int32), np.zeros(B, np.float32)
        budgets = np.full(B, 4, np.int32)
        h = ex.decode_chunk_start(zeros, zeros, bt, temps, budgets)
        m = ex.mixed_chunk_start(
            None, None, bt, temps, budgets,
            [(2, [5, 6, 7], 0, bt[2], 0.0), (3, [8] * 16, 0, bt[3], 0.0)],
            carry=h)
        m2 = ex.mixed_chunk_start(
            None, None, bt, temps, budgets,
            [(4, [9, 9], 0, bt[4], 0.0)], carry=m,
            overrides=[(2, m.pf_first_at(0), 3),
                       (3, m.pf_first_at(1), 16)])
        h2 = ex.decode_chunk_start(
            None, None, bt, temps, budgets, carry=m2,
            overrides=[(4, m2.pf_first_at(0), 2)])
        assert h2.fetch().shape == (B, 4)
        out, firsts = m2.fetch()
        assert out.shape == (B, 4) and firsts.shape == (2,)
        assert BACKEND_COMPILES.count == before

    @pytest.mark.parametrize("n_slices", [1, 2, 3])
    def test_one_mixed_program_serves_every_packing(self, n_slices):
        """After warm-up, mixed chunks with 1..S slices of every length
        class — one token, under a tile, a tile, over one, a full slice
        — compile nothing: how many row tiles hold a token is a trip
        count the device reads, not a shape. And the program table is
        the one there was: ONE ``mixed_chunk``. (Slices 16 wide, so a
        tile is the whole 16 rows; S = 3 of this test's own.)"""
        from llmq_tpu.observability.device import BACKEND_COMPILES

        cfg = llama3_tiny(max_seq_len=128)
        S, T = 3, 16
        ex = JaxExecutor(cfg, init_params(jax.random.PRNGKey(0), cfg),
                         batch_size=7, page_size=16, num_pages=33,
                         chunk_size=4, prefill_buckets=[16, 32],
                         mixed_prefill_slices=S, mixed_slice_tokens=T,
                         eos_id=-1)
        BACKEND_COMPILES.watch()
        ex.warmup()
        assert set(ex._aot) == {"prefill_b16", "prefill_b32",
                                "prefill_multi_b16", "prefill_multi_b32",
                                "decode_chunk", "mixed_chunk"}
        before = BACKEND_COMPILES.count
        B, MP = 7, ex.spec.max_pages_per_seq
        bt = np.zeros((B, MP), np.int32)
        bt[:, 0] = 1 + np.arange(B)
        zeros, temps = np.zeros(B, np.int32), np.zeros(B, np.float32)
        budgets = np.full(B, 4, np.int32)
        carry = None
        for n in (1, 7, 15, 16):
            lens = [n, 16, 1][:n_slices]
            carry = ex.mixed_chunk_start(
                zeros if carry is None else None,
                zeros if carry is None else None, bt, temps, budgets,
                [(4 + i, [3 + i] * m, 0, bt[4 + i], 0.0)
                 for i, m in enumerate(lens)], carry=carry)
            # the 16-row tiles that hold a row, less the 7 decode rows
            # that lead the slices' through the same products
            assert ex.slice_tokens("mixed_chunk", sum(lens)) == (
                min(-(-(B + sum(lens)) // T) * T, B + S * T) - B)
        out, firsts = carry.fetch()
        assert out.shape == (B, 4) and firsts.shape == (S,)
        assert BACKEND_COMPILES.count == before

    def test_warmup_on_mesh(self):
        """AOT specs carry the arrays' shardings — the mesh path must
        compile and serve through the executables too."""
        ex = build(mesh=make_mesh({"tp": 8}))
        ex.warmup()
        assert "decode_chunk" in ex._aot
        bt = np.zeros((4, ex.spec.max_pages_per_seq), np.int32)
        bt[0, :2] = [1, 2]
        first = ex.prefill([5, 6, 7], 0, bt[0], 0.0, 0)
        assert isinstance(first, int)

    def test_failed_aot_fails_the_warmup(self, tmp_path, monkeypatch):
        """No quiet fallback: if a program's AOT lowering breaks,
        warmup() raises the compiler's own error instead of logging it
        and serving through lazily-compiled jit wrappers (on the chip
        that hid exactly the faults bring-up exists to find — e.g. an
        8B batched-prefill program that cannot fit HBM)."""

        class _Boom:
            def __init__(self, inner):
                self.inner = inner

            def lower(self, *a, **k):
                raise RuntimeError("boom")

            def trace(self, *a, **k):
                raise RuntimeError("boom")

            def __call__(self, *a, **k):
                return self.inner(*a, **k)

        # Its own export dir: an artifact another test exported for the
        # same geometry would be LOADED, and nothing would be lowered.
        monkeypatch.setenv("LLMQ_EXPORT_CACHE_DIR", str(tmp_path))
        ex = build()
        ex._decode_chunk = _Boom(ex._decode_chunk)
        with pytest.raises(RuntimeError, match="boom"):
            ex.warmup()

    def test_export_cache_roundtrip(self, tmp_path, monkeypatch):
        """Warm restart via the jax.export disk cache: second warmup
        deserializes every program (no re-lowering) and serves outputs
        identical to the freshly-compiled path."""
        monkeypatch.setenv("LLMQ_EXPORT_CACHE_DIR", str(tmp_path))

        ex = build()
        ex.warmup()
        assert len(list(tmp_path.glob("*.jaxexp"))) == 6   # all exported

        bt = np.zeros((4, ex.spec.max_pages_per_seq), np.int32)
        bt[0, :2] = [1, 2]
        first = ex.prefill([5, 6, 7], 0, bt[0], 0.0, 0)
        toks = np.full(4, first, np.int32)
        pos = np.full(4, 3, np.int32)
        out_cold = ex.decode_chunk(toks, pos, bt, np.zeros(4, np.float32),
                                   np.full(4, 4, np.int32))

        ex2 = build()   # same geometry → cache hit for every program
        ex2.warmup()
        first2 = ex2.prefill([5, 6, 7], 0, bt[0], 0.0, 0)
        out_warm = ex2.decode_chunk(toks, pos, bt,
                                    np.zeros(4, np.float32),
                                    np.full(4, 4, np.int32))
        assert first == first2
        assert (out_cold[0] == out_warm[0]).all()

    def test_export_cache_key_tracks_code(self, tmp_path, monkeypatch):
        """Editing model/ops source must change the cache key — a stale
        artifact silently serving old code is the failure mode."""
        monkeypatch.setenv("LLMQ_EXPORT_CACHE_DIR", str(tmp_path))
        ex = build()
        k1 = ex._export_cache_key()
        import llmq_tpu.models as m
        import os
        llama_path = os.path.join(os.path.dirname(m.__file__), "llama.py")
        orig = open(llama_path).read()
        try:
            with open(llama_path, "a") as f:
                f.write("\n# cache-key probe\n")
            k2 = ex._export_cache_key()
        finally:
            with open(llama_path, "w") as f:
                f.write(orig)
        assert k1 != k2
