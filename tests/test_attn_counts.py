"""``engine.dispatch`` carries what one layer's fused decode attention
does for the chunk's rows: ``attn_steps``, ``attn_row_chunks``,
``attn_row_chunks_full`` (and a window layer's ``window_steps`` /
``window_chunks_full`` beside ``window_chunks``) — the executor's
``attn_work``, i.e. ``ops/pallas/fused_decode.decode_work`` on the
decoding rows' contexts in the order the step hands them to the kernel
(``ops/attention.decode_order``), an empty seat counted as the context
the family's step hands the kernel for it — while a capture is held, and
where the decode attention IS that kernel's (the predicate the
dispatchers go by). Held here, on the CPU, for a ``llama`` and an
``afmoe`` tiny configuration: the span's numbers are the schedule's at
the contexts the engine held at that dispatch. The counts are the host's
bookkeeping of a kernel that runs on the chip: nothing here times or
runs it.

Since PR 46 the span of a program that carries prompt chunks also holds
``scan_chunks`` / ``scan_chunks_live`` where a family's recurrent layers
scan them in a kernel (``ling_hybrid``: ``executor.scan_work`` by the
family's ``scan_step_tokens``), held here the same way."""

import dataclasses

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llmq_tpu.core.config import MixedBatchConfig  # noqa: E402
from llmq_tpu.engine import engine as engine_module  # noqa: E402
from llmq_tpu.engine.engine import GenRequest, InferenceEngine  # noqa: E402
from llmq_tpu.engine.executor import JaxExecutor  # noqa: E402
from llmq_tpu.engine.tokenizer import ByteTokenizer  # noqa: E402
from llmq_tpu.models import afmoe, get_config, llama  # noqa: E402
from llmq_tpu.ops.pallas.fused_decode import _tile_plan, decode_work  # noqa: E402

PAGE, ROWS = 8, 4


def _llama():
    cfg = get_config("llama3-tiny", max_seq_len=128, vocab_size=512)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _afmoe():
    cfg = afmoe.bind_cache(
        afmoe.afmoe_tiny(dtype=jnp.float32, max_seq_len=128,
                         held_experts=(8, 16)),
        page_size=PAGE, step_tokens=32)
    params = afmoe.init_params(jax.random.PRNGKey(41), cfg)
    return dataclasses.replace(cfg, page_size=0, slab_pages=0), params


@pytest.mark.parametrize("family", ["llama", "afmoe"])
def test_the_dispatch_span_carries_the_attention_kernel_s_schedule(
        monkeypatch, family):
    cfg, params = {"llama": _llama, "afmoe": _afmoe}[family]()
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=ROWS, page_size=PAGE,
                     num_pages=96, prefill_buckets=[16, 32],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8)
    # on the CPU the kernel does not serve, so there is no plan and
    # nothing is counted
    assert ex._decode_plan is None and ex.attn_work([5, 9]) is None
    # count as the chip would — and, the tiny block table being ONE
    # chunk of the plan, in chunks of one page, so that rows end in
    # different chunks of a tile
    pool = ex.cache["k"]
    ex._decode_plan = plan = _tile_plan(
        ROWS, PAGE, ex.spec.max_pages_per_seq, pool.shape[3],
        pool.dtype.itemsize, pages_per_chunk=1)
    if family == "afmoe":
        ex._window_chunk_tokens = plan.chunk_tokens
    assert (plan.rows, plan.chunk_tokens) == (ROWS, PAGE)
    eng = InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=64,
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                     max_slices=2))
    window = ex.attention_window["tokens"] if family == "afmoe" else None
    idle = 0 if family == "afmoe" else 1    # an empty seat's seq_len
    held = [True]
    monkeypatch.setattr(engine_module, "capture_held", lambda: held[0])

    seen = []
    work = eng._attn_work

    def recorded(seq_lens, window=False):
        held = sorted(s.pos + 1 for s in eng._slots
                      if s is not None and s.prefilled)
        assert sorted(int(n) for n in seq_lens) == held
        seen.append((window, [int(n) for n in seq_lens]))
        return work(seq_lens, window)

    eng._attn_work = recorded
    prompts = ["a", "a prompt of thirty characters.", "a middling one",
               "the longest of the four prompts, well past four pages"]
    handles = [eng.submit(GenRequest(id=str(i), prompt=p,
                                     max_new_tokens=20 + 7 * i,
                                     temperature=0.0))
               for i, p in enumerate(prompts)]
    eng.run_until_idle()
    assert all(h.done for h in handles)

    spans = [s.meta for s in eng._prof.snapshot()
             if s.name == "engine.dispatch" and s.meta
             and "attn_steps" in s.meta]
    full_layer = [lens for w, lens in seen if not w]
    assert len(spans) == len(full_layer) > 3
    for meta, lens in zip(spans, full_layer):
        padded = lens + [idle] * (ROWS - len(lens))
        steps, computed, live, full = decode_work(padded, plan, ordered=True)
        assert computed == live
        assert (meta["attn_steps"], meta["attn_row_chunks"],
                meta["attn_row_chunks_full"]) == (steps, computed, full)
        if window is not None:
            w_steps, w_computed, _, w_full = decode_work(
                padded, plan, window, ordered=True)
            assert (meta["window_steps"], meta["window_chunks"],
                    meta["window_chunks_full"]) == (w_steps, w_computed,
                                                    w_full)
            assert w_computed <= computed
    # four rows decoded side by side for a while (full steps), and one
    # outlived the others: no full step where an empty seat attends to
    # nothing, one — chunk 0, the tile's four rows — where it is handed
    # position 0's token
    assert any(m["attn_row_chunks_full"] > ROWS for m in spans)
    assert any(m["attn_row_chunks_full"] == ROWS * idle for m in spans)
    assert any(0 < m["attn_row_chunks_full"] < m["attn_row_chunks"]
               for m in spans)
    # no capture held: the dispatch path pays for none of it
    held[0] = False
    n = len(seen)
    h = eng.submit(GenRequest(id="late", prompt="once more",
                              max_new_tokens=8, temperature=0.0))
    eng.run_until_idle()
    assert h.done and len(seen) == n


@pytest.mark.parametrize("mode", ["interpret", "0"])
def test_the_plan_is_there_where_the_kernel_serves(monkeypatch, mode):
    """The executor counts by a plan exactly where the dispatchers take
    the kernel: with it on (interpret mode here; the TPU in serving) at
    a geometry it accepts, and not with it off; the routes say the
    same."""
    monkeypatch.setenv("LLMQ_PALLAS", mode)
    cfg = get_config("llama3-tiny", dim=256, n_heads=4, n_kv_heads=2,
                     max_seq_len=128, vocab_size=512)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    ex = JaxExecutor(cfg, params, batch_size=8, page_size=16, num_pages=96,
                     prefill_buckets=[16], eos_id=ByteTokenizer().eos_id,
                     chunk_size=4)
    route = ex._routes(decode=True)["decode_attention"]
    if mode == "0":
        assert ex._decode_plan is None and ex.attn_work([5, 9]) is None
        assert route == "xla"
        return
    plan = _tile_plan(8, 16, 8, 128, 2)
    assert ex._decode_plan == plan
    assert route == (f"pallas-interpret:_fused_kernel(rows=8,"
                     f"chunk_tokens={plan.chunk_tokens},ordered)")
    # six empty seats count as the one token a llama step hands the
    # kernel for them: chunk 0 is a full step of the tile
    assert ex.attn_work([5, 9]) == decode_work([9, 5] + [1] * 6, plan)[:2] + (
        8,)


def test_a_family_with_another_kernel_counts_nothing():
    """The latent families' decode attention is not this kernel's: no
    plan, no counts on the span, no ``attn`` block."""
    from llmq_tpu.models import deepseek_v3

    cfg = get_config("deepseek-v3-tiny", max_seq_len=64)
    params = deepseek_v3.init_params(jax.random.PRNGKey(0), cfg)
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=2, page_size=PAGE,
                     num_pages=32, prefill_buckets=[16],
                     eos_id=tok.eos_id, chunk_size=4)
    assert ex._decode_plan is None and ex.attn_work([5, 9]) is None
    eng = InferenceEngine(ex, tok, enable_metrics=False, max_decode_steps=8)
    h = eng.submit(GenRequest(id="a", prompt="hello", max_new_tokens=6,
                              temperature=0.0))
    eng.run_until_idle()
    assert h.done
    assert not any("attn_steps" in (s.meta or {})
                   for s in eng._prof.snapshot())



def _ling(kda_head_dim=128):
    from llmq_tpu.models import ling_hybrid as lh
    cfg = lh.ling_hybrid_tiny(dtype=jnp.float32, max_seq_len=256, n_layers=3,
                              kda_head_dim=kda_head_dim, kda_chunk=16)
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, lh.init_params(jax.random.PRNGKey(46), cfg),
                     batch_size=ROWS, page_size=PAGE, num_pages=160,
                     prefill_buckets=[64, 128], eos_id=tok.eos_id,
                     chunk_size=4, mixed_prefill_slices=2,
                     mixed_slice_tokens=128)
    return ex, tok


def test_the_scan_kernel_s_chunks_are_counted_where_it_serves(monkeypatch):
    """``executor.scan_work``: the grid's 64-token steps a head block of
    ONE layer's call — every slice of the program, the empty ones too —
    and those under a slice's length; nothing where the slices go to
    XLA's scan (the kernel off, a head width that is not its) or the
    family has no such kernel."""
    ex, _ = _ling()
    # on the CPU the kernel does not serve
    assert ex.scan_work("mixed_chunk", [128, 5]) is None
    monkeypatch.setenv("LLMQ_PALLAS", "interpret")
    assert ex.scan_work("mixed_chunk", [128, 5]) == (4, 3)
    assert ex.scan_work("mixed_chunk", [65]) == (4, 2)
    assert ex.scan_work("mixed_chunk", [64]) == (4, 1)
    assert ex.scan_work("prefill", [100]) == (2, 2)     # the 128 bucket
    assert ex.scan_work("prefill", [7]) == (1, 1)       # the 64 bucket
    assert ex.scan_work("decode_chunk", [7]) is None
    assert ex.scan_work("mixed_chunk", []) is None
    assert _ling(kda_head_dim=32)[0].scan_work("mixed_chunk", [5]) is None
    cfg, params = _llama()
    llama_ex = JaxExecutor(cfg, params, batch_size=ROWS, page_size=PAGE,
                           num_pages=96, prefill_buckets=[16],
                           eos_id=ByteTokenizer().eos_id, chunk_size=4)
    assert llama_ex.scan_work("prefill", [5]) is None


def test_the_dispatch_span_carries_the_scan_kernel_s_chunks(monkeypatch):
    """While a capture is held, a program that carries prompt chunks
    says how many steps of the scan kernel's grid one layer's call has
    and how many run, by the engine's own bookkeeping of the slices'
    lengths; a decode chunk says nothing, and nothing is counted with no
    capture held."""
    ex, tok = _ling()
    eng = InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=64,
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=256,
                                     max_slices=2))
    held = [True]
    monkeypatch.setattr(engine_module, "capture_held", lambda: held[0])
    seen = []
    work = eng._scan_work

    def recorded(entry, lens):
        # count as the chip would: the route is asked for here alone, no
        # program is traced inside this call
        monkeypatch.setenv("LLMQ_PALLAS", "interpret")
        try:
            seen.append((entry, list(lens), work(entry, lens)))
        finally:
            monkeypatch.delenv("LLMQ_PALLAS")
        return seen[-1][2]

    eng._scan_work = recorded
    prompts = ["short", "x" * 150, "y" * 70, "z" * 200]
    handles = [eng.submit(GenRequest(id=str(i), prompt=p,
                                     max_new_tokens=12 + 5 * i,
                                     temperature=0.0))
               for i, p in enumerate(prompts)]
    eng.run_until_idle()
    assert all(h.done for h in handles)
    spans = [s.meta for s in eng._prof.snapshot()
             if s.name == "engine.dispatch" and s.meta]
    counted = [m for m in spans if "scan_chunks" in m]
    assert len(counted) == len(seen) >= 2
    for meta, (entry, lens, got) in zip(counted, seen):
        assert meta["program"].startswith(entry)
        assert sum(lens) == meta["prefill_tokens"]
        assert (meta["scan_chunks"], meta["scan_chunks_live"]) == got
        assert got[1] == sum(-(-n // 64) for n in lens) <= got[0]
        if entry == "mixed_chunk":
            assert got[0] == 2 * 128 // 64
    assert any(e == "mixed_chunk" for e, _, _ in seen)
    assert any(m["scan_chunks_live"] < m["scan_chunks"] for m in counted)
    assert not any("scan_chunks" in m for m in spans
                   if m["program"] == "decode_chunk")
    held[0] = False
    n = len(seen)
    h = eng.submit(GenRequest(id="late", prompt="once more",
                              max_new_tokens=8, temperature=0.0))
    eng.run_until_idle()
    assert h.done and len(seen) == n
