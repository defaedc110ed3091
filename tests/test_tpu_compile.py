"""The TPU compiler's verdict without a chip: the serving path's two
prefill attention kernels, its two fused decode kernels, and the int8-KV
prefill program at Mistral-7B-v0.3's widths, compiled for a DESCRIBED
v5e at the served sizes.

Interpret mode (tests/test_pallas.py) checks the kernel's arithmetic
and cannot see what the TPU compiler refuses: a lane slice off the
tiling, a scratch set over the VMEM limit, a DMA it cannot express.
The compiler is installed with JAX and compiles for a topology that is
described, not attached; nothing runs, so this says nothing about
results or times. Every such compile lives in THIS file: the worker
that is given it loads the TPU library once, inside the fixture, and no
module describes a topology while it is imported.
"""

import os
from functools import partial

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


#: name -> (T, H, H_kv, D, page_size, max_pages, layers, pool pages):
#: the benchmark's cells (both buckets), the smoke's llama3-1b, and
#: llama3-8b at its 128-token serving page.
_GEOMETRIES = {
    "smollm2-1.7b-b256": (256, 32, 32, 64, 16, 256, 24, 3328),
    "smollm2-1.7b-b1024": (1024, 32, 32, 64, 16, 256, 24, 3328),
    "llama3-1b-b512": (512, 32, 8, 64, 16, 128, 16, 512),
    "llama3-8b-ps128-b512": (512, 32, 8, 128, 128, 16, 32, 264),
    "zaya1-8b-pp2-b512": (512, 8, 2, 128, 128, 48, 20, 2048),
}


@pytest.mark.parametrize("name", sorted(_GEOMETRIES))
def test_prefill_attention_compiles_for_v5e(one_chip, name):
    from llmq_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention_pallas)

    T, H, Hkv, D, ps, mp, L, P = _GEOMETRIES[name]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((L, P, ps, Hkv * D), jnp.bfloat16)
    compiled = jax.jit(paged_prefill_attention_pallas).lower(
        arg((T, H, D), jnp.bfloat16), pool, pool, arg((mp,), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: name -> (T, H, H_kv, D, page_size, max_pages, layers, pool pages):
#: int8 pools as ``mistral-7b-v0.3-w8kv8`` serves them, and eight KV
#: heads of 64 (llama3-1b's: two share a lane window, so a tile takes
#: two rows of a scale page).
_Q8_GEOMETRIES = {
    "mistral-7b-w8kv8-b512": (512, 32, 8, 128, 128, 16, 32, 832),
    "llama3-1b-kv8-b512": (512, 32, 8, 64, 128, 16, 16, 264),
}


@pytest.mark.parametrize("name", sorted(_Q8_GEOMETRIES))
def test_prefill_attention_q8_compiles_for_v5e(one_chip, name):
    """The int8 twin: int8 page DMAs beside (8, 128) bf16 scale pages,
    the int8 -> bf16 conversion of a 128-lane window, a scalar-prefetch
    index map for the q block. One Mosaic call, named so that a device
    trace and the benchmark's ``PREFILL_ATTN`` pattern find it."""
    from llmq_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention_q8_pallas)

    T, H, Hkv, D, ps, mp, L, P = _Q8_GEOMETRIES[name]

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = ((arg((L, P, ps, Hkv * D), jnp.int8),) * 2
             + (arg((L, P, Hkv, ps), jnp.bfloat16),) * 2)
    text = jax.jit(paged_prefill_attention_q8_pallas).lower(
        arg((T, H, D), jnp.bfloat16), pools, arg((mp,)), arg(()), arg(()),
        arg(())).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%paged_prefill_attention_q8_pallas" in text
    assert len(text) < 100_000


#: name -> (rows, H, H_kv, D, page_size, max_pages, layers, pool pages,
#: int8 KV): the benchmark's two Llama configurations and granite's four
#: attention layers as served, the smoke's llama3-1b, and a batch of
#: one tile (Trinity's two: ``test_the_windowed_kernels_compile_for_v5e``).
_DECODE_GEOMETRIES = {
    "smollm2-1.7b": (32, 32, 32, 64, 16, 256, 24, 3328, False),
    "mistral-7b-w8kv8": (64, 32, 8, 128, 128, 16, 32, 832, True),
    "granite-4.0-h-micro": (64, 32, 8, 64, 128, 16, 4, 832, False),
    "llama3-1b": (8, 32, 8, 64, 16, 128, 16, 512, False),
    "smollm2-1.7b-one-tile": (8, 32, 32, 64, 16, 256, 24, 3328, False),
    "zaya1-8b-pp2": (64, 8, 2, 128, 128, 48, 20, 2048, False),
}


@pytest.mark.parametrize("name", sorted(_DECODE_GEOMETRIES))
def test_fused_decode_compiles_for_v5e(one_chip, name):
    """The plan's scratch passes the compiler's default scoped VMEM (16
    MiB) at SmolLM2's geometry: Mosaic has to take the
    ``vmem_limit_bytes`` the plan states, and the rolled row and page
    loops with their DMAs. One call's program stays a fraction of the
    v3 kernel's (3.3-5.2 MB serialized: PERF.md §6, PR 29) — every layer
    of every decode program carries one. Since PR 43 the plan's static
    block of a full step is in it (eight rows' products in one straight
    line: + 10-14 kB of text a call; over int8 pools the stated limit
    holds the block's bf16 copies too)."""
    from llmq_tpu.ops.pallas.fused_decode import (
        fused_decode_attention_pallas, fused_decode_attention_q8_pallas)

    from llmq_tpu.ops.pallas.fused_decode import _tile_plan

    B, H, Hkv, D, ps, mp, L, P, q8 = _DECODE_GEOMETRIES[name]
    plan = _tile_plan(B, ps, mp, Hkv * D, 1 if q8 else 2)
    assert plan.rows == 8        # a full step's block is eight rows'

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tables = (arg((B, mp), jnp.int32), arg((B,), jnp.int32),
              arg((B,), jnp.int32), arg((), jnp.int32))
    q = arg((B, H, D), jnp.bfloat16)
    if q8:
        row, scale = arg((B, Hkv, D), jnp.int8), arg((B, Hkv), jnp.bfloat16)
        pools = ((arg((L, P, ps, Hkv * D), jnp.int8),) * 2
                 + (arg((L, P, Hkv, ps), jnp.bfloat16),) * 2)
        lowered = jax.jit(fused_decode_attention_q8_pallas).lower(
            q, row, scale, row, scale, pools, *tables)
    else:
        row = arg((B, Hkv, D), jnp.bfloat16)
        pool = arg((L, P, ps, Hkv * D), jnp.bfloat16)
        lowered = jax.jit(fused_decode_attention_pallas).lower(
            q, row, row, pool, pool, *tables)
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(text) < 100_000       # the v3 kernel's was 154-427 kB


def test_int8_kv_prefill_program_stays_small_for_v5e(one_chip, monkeypatch):
    """The int8-KV prefill program (``forward_prefill(last_only=True)``,
    what ``prefill_b512`` and the benchmark's logits check run) at
    Mistral-7B-v0.3's widths and FULL depth, as ``mistral-7b-v0.3-w8kv8``
    serves it: w8a8 weights, 832 int8 pages of 128 tokens, one 512-token
    row. Its layers are one rolled loop body. Unrolled, the same program
    took 220 s to compile here (126 s on the chip's host), 155 MB of
    code and 568 MB of temporaries, and no run fitted its time limit
    (PERF.md, PR 26): rolled it measured 6-21 s, 8.3 MB and 12 MB. The
    limits below sit between the two, so the old graph cannot come back
    unseen; and the temporaries stay under ONE pool, which is how a
    carried pool that XLA had begun to copy would show. Since PR 33 the
    loop body holds the int8 prefill attention kernel, which only READS
    the carried pools (routed here as on the chip: the backend this
    process sees is the CPU): 7 s, 8.7 MB and 1.4 MB."""
    import time

    from llmq_tpu.models import llama
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)

    cfg = llama.get_config("mistral-7b-v0.3", max_seq_len=2048,
                           pallas_batched_prefill=True)
    pages, page, bucket = 832, 128, 512

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: llama.init_params_quantized(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: llama.init_kv_pages(cfg, pages, page, dtype=jnp.int8)))
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def prefill(params, cache, tokens, positions, lengths, block_tables):
        return llama.forward_prefill(params, cfg, tokens, positions,
                                     lengths, cache, block_tables,
                                     last_only=True)

    t0 = time.perf_counter()
    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, arg(1, bucket), arg(1, bucket), arg(1),
        arg(1, cfg.max_seq_len // page)).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    assert "%paged_prefill_attention_q8_pallas" in compiled.as_text()
    assert seconds < 90.0, seconds
    assert mem.generated_code_size_in_bytes < 40e6
    assert mem.temp_size_in_bytes < pool_bytes / 8, (
        mem.temp_size_in_bytes, pool_bytes)
    # the four pools go in and come out in place
    assert mem.alias_size_in_bytes >= pool_bytes


#: kanana-2-30b-a3b-bf16 as served: 64 rows, 32 heads over one latent
#: "head" of 640 lanes (512 + 64, padded), 128-token pages, 16 a row, 8
#: layers, 832 pages.
#: longcat-flash-chat-bf16-ep32 as served: 128 rows, 64 heads, the same
#: row and pages, 8 cache layers (two attentions in each of 4 layers),
#: 1,664 pages.
_LATENT = {
    "kanana2": dict(B=64, H=32, W=640, rank=512, ps=128, mp=16, L=8, P=832),
    "longcat": dict(B=128, H=64, W=640, rank=512, ps=128, mp=16, L=8,
                    P=1664),
}


@pytest.mark.parametrize("served", sorted(_LATENT))
@pytest.mark.parametrize("kernel", ["decode", "write"])
def test_latent_kernels_compile_for_v5e(one_chip, kernel, served):
    """The latent decode kernel (manual page DMAs into two 512-token
    slots, a heads x 640 by 640 x 512 product a chunk) and the latent
    write at the served geometries: one Mosaic call each."""
    from llmq_tpu.ops.pallas.latent_decode import (
        latent_decode_attention_pallas, latent_write_pallas)

    g = _LATENT[served]

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((g["L"], g["P"], g["ps"], g["W"]), jnp.bfloat16)
    if kernel == "decode":
        lowered = jax.jit(latent_decode_attention_pallas,
                          static_argnames=("rank",)).lower(
            arg((g["B"], g["H"], g["W"]), jnp.bfloat16), pool,
            arg((g["B"], g["mp"])), arg((g["B"],)), arg(()),
            rank=g["rank"])
    else:
        lowered = jax.jit(latent_write_pallas).lower(
            pool, arg((g["B"], g["W"]), jnp.bfloat16), arg((g["B"],)),
            arg((g["B"],)), arg(()))
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(text) < 100_000


@pytest.mark.parametrize("served", sorted(_LATENT))
def test_the_latent_write_holds_tiles_not_pages_for_v5e(one_chip, served):
    """The decode write at the served geometries keeps scratch slots of
    ONE sublane tile each (16 rows x 640 of bf16) and no ``(2,
    page_size, W)`` scratch is left: the VMEM the compiled call asks
    for is its tiles', and the pool is written where it lies."""
    import re

    from llmq_tpu.ops.pallas.latent_decode import (
        WRITE_AHEAD, latent_write_pallas, tile_rows)

    g = _LATENT[served]

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg((g["L"], g["P"], g["ps"], g["W"]), jnp.bfloat16),
            arg((g["B"], g["W"]), jnp.bfloat16), arg((g["B"],)),
            arg((g["B"],)), arg(()))
    R, slots = tile_rows(jnp.bfloat16), 2 * WRITE_AHEAD
    assert R == 16
    kernel = str(jax.make_jaxpr(latent_write_pallas)(*args))
    assert f"bf16[{slots},{R},{g['W']}]" in kernel
    assert f"bf16[2,{g['ps']},{g['W']}]" not in kernel
    compiled = jax.jit(latent_write_pallas,
                       donate_argnums=(0,)).lower(*args).compile()
    call, = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    scoped, = re.findall(
        r'"used_scoped_memory_configs":\[\{[^]]*"size":"(\d+)"', call)
    assert int(scoped) == slots * R * g["W"] * 2
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("E,D,F,rows", [
    (128, 2048, 768, 384), (128, 2048, 768, 6528),
    (64, 2304, 896, 256), (64, 2304, 896, 2048)],
    ids=["64-decode-rows", "1088-mixed-tokens",
         "mellum-32-decode-rows", "mellum-live-block"])
def test_grouped_product_compiles_for_v5e(one_chip, E, D, F, rows):
    """The routed layers' grouped product at Kanana's widths (128
    experts, 2,048 -> 2 x 768 and 768 -> 2,048) for a decode step's
    pairs and a mixed step's, and at Mellum's (64 experts, 2,304 -> 2 x
    896 -> 2,304) for a decode step's pairs and a block of live ones:
    two Mosaic calls at the tiles ``moe.gmm_tiling`` cuts to the
    matrices (a tile that does not fit VMEM fails HERE, without a
    chip), each within 1 MiB above what the rule counted for its tiles
    and inside the scoped VMEM, and the expert leaves are not copied
    (temporaries stay a few megabytes)."""
    import re

    from llmq_tpu.ops import moe

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def ffn(xs, w_gu, w_d, counts):
        gu = moe.moe_grouped_matmul_pallas(xs, w_gu, counts)
        return moe.moe_grouped_matmul_pallas(gu[:, :F] * gu[:, F:], w_d,
                                             counts)

    compiled = jax.jit(ffn).lower(
        arg((rows, D)), arg((E, D, 2 * F)), arg((E, F, D)),
        arg((E,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    scoped = [int(size) for line in text.splitlines()
              if "tpu_custom_call" in line for size in re.findall(
                  r'"used_scoped_memory_configs":\[\{[^]]*"size":"(\d+)"',
                  line)]
    assert len(scoped) == 2 and max(scoped) < moe.VMEM_SCOPED
    counted = [moe.gmm_tile_bytes(*moe.gmm_tiling(rows, K, N))
               for K, N in ((D, 2 * F), (F, D))]
    assert all(s <= c + 2 ** 20 for s, c in zip(scoped, counted))
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


@pytest.mark.parametrize("tokens", [128, 2176],
                         ids=["128-decode-rows", "2176-mixed-tokens"])
def test_a_share_s_grouped_product_compiles_for_v5e(one_chip, monkeypatch,
                                                    tokens):
    """A chip's share of a routed layer at LongCat-Flash-Chat's widths
    (16 of 512 experts held, 768 router outputs, 12 a token, 6,144 ->
    2 x 2,048 -> 6,144): the held pairs go through the grouped product
    a block of sorted pairs at a time inside ONE loop, so two Mosaic
    calls whatever the tokens, and no array of tokens x 12 rows of the
    hidden size is made (a mixed step's would be 0.3 GB in bf16 and
    twice that in float32)."""
    from llmq_tpu.ops import attention, moe

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def share(x, experts, gates, w_gu, w_d, live):
        return moe.routed_ffn(x, experts, gates, w_gu, w_d, live,
                              held=(0, 16), n_routed=512)

    compiled = jax.jit(share).lower(
        arg((tokens, 6144)), arg((tokens, 12), jnp.int32),
        arg((tokens, 12), jnp.float32), arg((16, 6144, 4096)),
        arg((16, 2048, 6144)), arg((tokens,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 80e6 + (
        tokens * 6144 * 4 * 2)


def test_the_double_layer_s_decode_step_fits_v5e(one_chip, monkeypatch):
    """One decode step of ``longcat-flash-chat-bf16-ep32`` as served
    (4 double layers, 16 held experts, 16,384 of the vocabulary, 128
    rows over a pool of 1,664 pages): 8 latent writes, 8 latent decode
    calls and 4 routed layers' two grouped products; weights and pool
    are 12.5 GB of arguments, the pool goes in and comes out in place,
    and the step's temporaries stay a small part of what is left."""
    from llmq_tpu.models import longcat_flash as lf
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    cfg = lf.longcat_flash_chat(n_layers=4, vocab_size=16384,
                                held_experts=(0, 16), max_seq_len=2048)
    B, pages, page = 128, 1664, 128

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: lf.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: lf.init_kv_pages(cfg, pages, page)))
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))

    def arg(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(params, cache, tokens, positions, block_tables, active):
        return lf.forward_decode.__wrapped__(
            params, cfg, tokens, positions, cache, block_tables,
            active=active, stats=True)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, arg(B), arg(B), arg(B, cfg.max_seq_len // page),
        arg(B, dtype=jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert compiled.as_text().count("tpu_custom_call") == 8 + 8 + 2 * 4
    assert 12.4e9 < mem.argument_size_in_bytes < 12.7e9
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes


def _mixed_step(fam, cfg, one_chip, *, B, S, T, pages, page, quantized=False,
                cache_dtype=None):
    """``fam.forward_mixed`` compiled for the described chip at B rows
    and S slices of T tokens laid tight (``ops/rows.py``)."""
    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    init = fam.init_params_quantized if quantized else fam.init_params
    params = on_chip(jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: fam.init_kv_pages(cfg, pages, page, dtype=cache_dtype)))

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, cache, tokens, positions, block_tables, *pf):
        return fam.forward_mixed.__wrapped__(
            params, cfg, tokens, positions, cache, block_tables, *pf,
            dec_active=jnp.ones((B,), jnp.bool_))

    mp = cfg.max_seq_len // page
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, arg(B), arg(B), arg(B, mp), arg(S * T), arg(S * T),
        arg(S), arg(S + 1), arg(S, mp)).compile()
    return compiled, params, cache


def _whole_copies(compiled, tree):
    """Copies, in the compiled program, of an array as large as a leaf
    of ``tree`` (a stacked parameter, the pool)."""
    import re
    shapes = {"[" + ",".join(map(str, x.shape)) + "]"
              for x in jax.tree.leaves(tree) if x.size > 1 << 20}
    return [m for m in re.findall(
        r"= \w+(\[[\d,]+\])\S* copy\(", compiled.as_text()) if m in shapes]


def test_the_tight_mixed_step_copies_no_pool_for_v5e(one_chip, monkeypatch):
    """``longcat-flash-chat-bf16-ep32``'s mixed step as served (128
    rows, four 512-token slices laid tight, the row-wise blocks as
    loops over 256-row tiles): the pool goes in and comes out in place
    and is NEVER copied — without the barrier between the slices'
    attention, which reads it inside a loop, and the decode rows'
    aliased write, XLA kept both with two whole copies a step (2.2 GB
    each, 2.9 GB of temporaries: PERF.md, PR 38) — and the temporaries
    stay under the 0.72 GB of the un-looped step."""
    from llmq_tpu.models import longcat_flash as lf
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    cfg = lf.longcat_flash_chat(n_layers=4, vocab_size=16384,
                                held_experts=(0, 16), max_seq_len=2048)
    compiled, _, cache = _mixed_step(lf, cfg, one_chip, B=128, S=4, T=512,
                                     pages=1664, page=128)
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))
    assert not _whole_copies(compiled, cache)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.72e9, mem.temp_size_in_bytes


def test_the_tight_mixed_step_copies_no_stacked_matrix_for_v5e(
        one_chip, monkeypatch):
    """SmolLM2-1.7B's widths at 4 layers, 32 rows, two 512-token slices
    (four row tiles, so ``attn_out`` + ``mlp`` loop; at its served two
    slices of 256 nothing does): a stacked matrix carried into the
    row-tile loop (``wo``, ``w_gate``, ``w_up``, ``w_down``: 4 x
    201-805 MB at full depth) is read there a layer at a time and NEVER
    copied whole — a product inside a loop whose result was split into
    heads wanted its matrix transposed and got a copy of all its layers
    a step (``wq``, ``wk``, ``wv``, while those ran a tile at a time:
    PERF.md, PR 38) — and the loop is there: one ``while`` a layer."""
    from llmq_tpu.models import llama
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    cfg = llama.LlamaConfig(
        name="smollm2-4-layers", vocab_size=49152, dim=2048, n_layers=4,
        n_heads=32, n_kv_heads=32, ffn_dim=8192, max_seq_len=4096,
        rope_theta=130000.0, tie_embeddings=True,
        pallas_batched_prefill=True)
    compiled, params, _ = _mixed_step(llama, cfg, one_chip, B=32, S=2,
                                      T=512, pages=512, page=16)
    assert not _whole_copies(compiled, params)
    assert compiled.as_text().count(" while(") >= cfg.n_layers


def _control_flow(compiled):
    """``while`` and ``conditional`` instructions of a compiled program."""
    import re
    return re.findall(r" (while|conditional)\(", compiled.as_text())


@pytest.mark.parametrize("served", ["smollm2-1.7b-bf16",
                                    "mistral-7b-v0.3-w8kv8"])
def test_llamas_joined_mixed_step_copies_no_pool_and_no_stacked_matrix_for_v5e(
        one_chip, monkeypatch, served):
    """Both guards above for ``llama.forward_mixed`` since its decode
    rows LEAD the tight slice rows through one product a module, at the
    two served shapes and 4 layers. SmolLM2's (32 rows, two 256-token
    slices, bf16): all 544 rows run whole and the program holds NO
    ``while`` and no ``conditional`` — the slice rows alone decide
    (``ops/rows.worth_a_loop``), and control flow a layer cost its
    ``mixed_chunk`` 2-3 s at every start (PERF.md, PR 38). Mistral's
    (64 rows, two 512-token slices, w8a8 over 832 int8 pages):
    ``attn_out`` + ``mlp`` loop over the 256-row tiles of 1,088 rows,
    one ``while`` a layer. In both the pools go in and come out in
    place with no second pool beside the decode rows' aliased write
    (the barrier behind the slices' attention), and no stacked matrix
    is copied or transposed whole (q, k, v run un-looped)."""
    from llmq_tpu.models import llama
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    if served == "smollm2-1.7b-bf16":
        cfg = llama.LlamaConfig(
            name="smollm2-4-layers", vocab_size=49152, dim=2048, n_layers=4,
            n_heads=32, n_kv_heads=32, ffn_dim=8192, max_seq_len=4096,
            rope_theta=130000.0, tie_embeddings=True,
            pallas_batched_prefill=True)
        compiled, params, cache = _mixed_step(
            llama, cfg, one_chip, B=32, S=2, T=256, pages=3328, page=16)
        assert not _control_flow(compiled)
    else:
        cfg = llama.get_config("mistral-7b-v0.3", n_layers=4,
                               max_seq_len=2048, pallas_batched_prefill=True)
        compiled, params, cache = _mixed_step(
            llama, cfg, one_chip, B=64, S=2, T=512, pages=832, page=128,
            quantized=True, cache_dtype=jnp.int8)
        assert _control_flow(compiled).count("while") >= cfg.n_layers
        assert "%paged_prefill_attention_q8_pallas" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))
    assert not _whole_copies(compiled, cache)
    assert not _whole_copies(compiled, params)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 2, (
        mem.temp_size_in_bytes, pool_bytes)


@pytest.mark.parametrize("program", ["decode_chunk", "mixed_chunk"])
def test_smollm2_s_chunk_programs_copy_no_stacked_matrix_as_dispatched_for_v5e(
        one_chip, monkeypatch, program):
    """SmolLM2's two chunk programs AS THE EXECUTOR DISPATCHES THEM —
    the decode loop of 8 steps, and the mixed step (32 rows, two
    256-token slices) with that loop behind it — at 4 layers, lowered
    by a ``JaxExecutor`` built over the DESCRIPTION of the parameters
    (its own ``lay_params`` and ``programs()``: what the warm-up
    compiles): no stacked parameter and no pool is copied whole.

    The guards above compile a mixed step ALONE and could not see this:
    inside the decode LOOP the 32-row q, k and v products, whose
    results are split into 64-wide heads, want their matrices with the
    contracted axis minor; that is a transposition a layer a step, so
    loop-invariant motion lifts it out of the ``while`` — and it
    becomes a transposing copy of the whole stacked ``wq``, ``wk`` and
    ``wv`` at the start of EVERY run (at full depth 3 x 201 MB, 1.89 ms
    of a 52-107 ms chunk: the whole ``device_unscoped_share`` of the
    three SmolLM2 cells, PERF.md PR 47). ``forward_decode`` with no
    loop around it and ``forward_mixed`` alone hold none. The executor
    lays the three leaves transposed once (``llama.DEVICE_LAYOUT``)
    and describes them so to the lowering; with the family's answer
    empty this test finds ``[4,2048,2048]`` three times (CHANGES.md)."""
    from llmq_tpu.engine.executor import JaxExecutor, describe
    from llmq_tpu.models import llama
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    cfg = llama.LlamaConfig(
        name="smollm2-4-layers", vocab_size=49152, dim=2048, n_layers=4,
        n_heads=32, n_kv_heads=32, ffn_dim=8192, max_seq_len=4096,
        rope_theta=130000.0, tie_embeddings=True)
    params = describe(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    ex = JaxExecutor(cfg, params, batch_size=32, page_size=16,
                     num_pages=3328, prefill_buckets=[256, 1024],
                     chunk_size=8, prefill_batch=4, mixed_prefill_slices=2,
                     mixed_slice_tokens=256, telemetry_metrics=False)
    (fn, operands), = [(fn, operands)
                       for name, fn, operands, _ in ex.programs()
                       if name == program]
    compiled = fn.lower(*operands).compile()
    assert "%fused_decode_attention_pallas" in compiled.as_text()
    assert _control_flow(compiled).count("while") == 1
    assert not _whole_copies(compiled, ex.params)
    assert not _whole_copies(compiled, ex.cache)
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(ex.cache))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert ex.relaid == {"leaves": 3, "bytes": 3 * 4 * 2048 * 2048 * 2}


# -- the row state of a hybrid family (granite-4.0-h-micro's sizes) ------------


def _state_leaf(one_chip, rows=65):
    return jax.ShapeDtypeStruct((36, rows, 128, 4096), jnp.float32,
                                sharding=one_chip)


@pytest.mark.parametrize("width", [4096, 8192])
def test_the_state_update_kernel_compiles_for_v5e(one_chip, width):
    """``ops/pallas/ssm_update.py`` at the served sizes: 64 rows of a
    (128, 4,096) float32 state in a leaf of 36 layers and 65 rows (the
    last nobody's), a WHOLE row a step of its walk (three slots of
    2 MiB, the small operands whole beside them: inside the VMEM limit
    the call states, which the compiler would refuse otherwise), the
    live rows named by the scalar prefetch, the leaf aliased in and
    out: 4.9 GB of arguments, no temporary of a layer's size. And at
    twice the row, where the shape rule takes half a row a step (a
    leaf of 4 layers)."""
    from llmq_tpu.ops.pallas import ssm_update as su

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(pool, decay, dtx, bm, cm, rows, n_live):
        return su.ssm_update_pallas(pool, 3, decay, dtx, bm, cm, rows,
                                    n_live)

    layers = 36 if width == 4096 else 4
    assert su._lanes(128, width) == 4096
    assert (su.SLOTS * 128 * 4096 * 4 <= su.STATE_VMEM_BYTES
            < su.VMEM_LIMIT_BYTES)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        arg(layers, 65, 128, width), arg(64, width), arg(64, width),
        arg(64, 128), arg(64, 128), arg(64, dtype=jnp.int32),
        arg(dtype=jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert mem.alias_size_in_bytes >= layers * 65 * 128 * width * 4
    assert mem.temp_size_in_bytes < 16e6, mem.temp_size_in_bytes


@pytest.mark.parametrize("which", ["read", "write"])
def test_the_state_rows_copies_compile_for_v5e(one_chip, which):
    """The two copies a prompt slice makes of its row's state, as
    Mosaic calls (so that the leaf keeps its layout: XLA's gather and
    update made the whole leaf follow the scan's): the write in
    place."""
    from llmq_tpu.ops.pallas import ssm_update as su

    rows = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    new = jax.ShapeDtypeStruct((2, 128, 4096), jnp.float32,
                               sharding=one_chip)
    if which == "read":
        compiled = jax.jit(lambda p, r: su.state_rows_read(p, 3, r)).lower(
            _state_leaf(one_chip), rows).compile()
    else:
        compiled = jax.jit(lambda p, r, n: su.state_rows_write(p, 3, r, n),
                           donate_argnums=(0,)).lower(
            _state_leaf(one_chip), rows, new).compile()
        assert compiled.memory_analysis().alias_size_in_bytes >= (
            36 * 65 * 128 * 4096 * 4)
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 16e6


def test_the_hybrid_prefill_copies_no_row_state_for_v5e(one_chip,
                                                        monkeypatch):
    """``granite-4.0-h-micro``'s prefill program at one period of its
    layers (9 Mamba, 1 attention), every width as published, 64 rows and
    a 512-token bucket: the page pool and both row-state leaves go in
    and come out in place and are NEVER copied. The program has no
    update kernel to hold the state's layout, and with XLA's gather and
    ``dynamic_update_slice`` around the scan it copied the whole state
    leaf in and out (4.9 GB each way at full depth, more than the chip
    has left), and the convolution's window, kept (3, 4,352) a row, as
    often as a layer touched it (PERF.md section 6, PR 39)."""
    from llmq_tpu.models import granitemoehybrid as gm
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    types = tuple(gm.ATTENTION if i == 5 else gm.MAMBA for i in range(10))
    cfg = gm.serving_config(gm.granite_4_0_h_micro(layer_types=types,
                                                   max_seq_len=2048))

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: gm.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: gm.init_kv_pages(cfg, 832, 128)))
    state = on_chip(jax.eval_shape(lambda: gm.init_row_state(cfg, 64)))

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(params, cache, state, tokens, positions, lengths, bts, rows):
        return gm.forward_prefill.__wrapped__(
            params, cfg, tokens, positions, lengths, cache, bts,
            last_only=True, row_state=state, rows=rows)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, cache, state, arg(1, 512), arg(1, 512), arg(1), arg(1, 16),
        arg(1)).compile()
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((cache, state)))
    assert not _whole_copies(compiled, (cache, state))
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 0.25e9, mem.temp_size_in_bytes
    # the scan's two copies of a row's state a Mamba layer, the prefill
    # attention and its write
    assert compiled.as_text().count("tpu_custom_call") == 2 * 9 + 2


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_the_hybrid_decode_rows_copy_no_state_for_v5e(one_chip, monkeypatch,
                                                      program):
    """``granite-4.0-h-micro``'s decode program and its mixed step (two
    512-token slices beside the decode rows) at one period of its layers
    (9 Mamba, 1 attention), every width as published, 64 rows: the page
    pool and both row-state leaves go in and come out in place, NEITHER
    the state leaf NOR a K/V pool is ever copied (the update kernel
    takes the leaf as it lies, a whole row a grid step; in the mixed
    step the barrier still orders the decode rows' aliased writes behind
    the slices' reads), and the update is one Mosaic call a Mamba
    layer."""
    from llmq_tpu.models import granitemoehybrid as gm
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    types = tuple(gm.ATTENTION if i == 5 else gm.MAMBA for i in range(10))
    cfg = gm.serving_config(gm.granite_4_0_h_micro(layer_types=types,
                                                   max_seq_len=2048))
    B, S, T, mp = 64, 2, 512, 16

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: gm.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: gm.init_kv_pages(cfg, 832, 128)))
    state = on_chip(jax.eval_shape(lambda: gm.init_row_state(cfg, B)))

    def arg(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(params, cache, state, tokens, positions, bts, active):
        return gm.forward_decode.__wrapped__(
            params, cfg, tokens, positions, cache, bts, active=active,
            row_state=state)

    def mixed(params, cache, state, tokens, positions, bts, active, *pf):
        return gm.forward_mixed.__wrapped__(
            params, cfg, tokens, positions, cache, bts, *pf[:-1],
            dec_active=active, row_state=state, pf_rows=pf[-1])

    args = [params, cache, state, arg(B), arg(B), arg(B, mp),
            arg(B, dtype=jnp.bool_)]
    if program == "mixed":
        args += [arg(S * T), arg(S * T), arg(S), arg(S + 1), arg(S, mp),
                 arg(S)]
    compiled = jax.jit(decode if program == "decode" else mixed,
                       donate_argnums=(1, 2)).lower(*args).compile()
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((cache, state)))
    assert not _whole_copies(compiled, (cache, state))
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 0.25e9, mem.temp_size_in_bytes
    assert sum("tpu_custom_call" in line and "/ssm_update/" in line
               for line in compiled.as_text().splitlines()) == 9


def _script(name="whole_copies"):
    """``scripts/<name>.py`` as a module."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _top_level(text):
    """``(computation, line)`` of the instructions of a compiled
    program that run by themselves: those of a fused computation are
    part of the fusion that calls it (a slice there is read where it
    lies)."""
    import re
    inside = ""
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$", line)
        if head is not None:
            inside = head[1]
        elif "fused_computation" not in inside:
            yield inside, line


@pytest.mark.parametrize("periods", [
    1, pytest.param(4, marks=pytest.mark.slow, id="4-as-served")])
@pytest.mark.parametrize("program", ["decode_chunk", "mixed_chunk"])
def test_granite_s_chunk_programs_read_in_proj_where_it_lies_as_dispatched_for_v5e(
        one_chip, monkeypatch, program, periods):
    """``granite-4.0-h-micro``'s two chunk programs AS THE EXECUTOR
    DISPATCHES THEM — 64 rows, 16 decode steps, two 512-token slices —
    lowered by a ``JaxExecutor`` over the DESCRIPTION of the parameters
    (its own ``lay_params`` and ``programs()``), at one period of the
    published layer order (9 Mamba, 1 attention; tier-1) and at all
    four (``slow``: 90 s for the mixed step).

    (i) ``in_proj`` is 8,512 = 66.5 lane tiles wide, so the TPU's own
    layout of the leaf is ``{1,2,0}`` and both programs began with a
    transposing copy of all of it into ``{2,1,0}`` for the decode loop
    (1.26 GB a run at full depth, and a second ``in_proj`` resident).
    Laid row-major by the executor (``gm.DEVICE_LAYOUT``) it enters as
    the loop reads it: no whole move of any stacked leaf or of the
    pool, and the attention layers' ``wq``, ``wk``, ``wv`` lie as
    ``llama``'s.

    (ii) the mixed step multiplies by ``in_proj`` ONCE a Mamba layer,
    in two column blocks — ``xBC | dt`` over the 1,024 slice rows,
    ``z`` inside the 256-row live tile — and once more over the decode
    rows; nothing of a product's size or of a layer's matrix is
    rematerialised (the parent held 105 products of ``bf16[1024,8512]``
    at 40 layers, 69 of them ``.remat``), and no layer's matrix or
    column block is copied out of the leaf by an instruction of its own
    (``lp["in_proj"][i][:, :I]``, two slices, stood as a 35 MB copy a
    tile trip at 40 layers; ``[i, :, :I]`` is read in place)."""
    import re

    from llmq_tpu.engine.executor import JaxExecutor, describe
    from llmq_tpu.models import granitemoehybrid as gm
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    n_mamba = 9 * periods
    types = tuple(gm.ATTENTION if i % 10 == 5 else gm.MAMBA
                  for i in range(10 * periods))
    cfg = gm.serving_config(gm.granite_4_0_h_micro(layer_types=types,
                                                   max_seq_len=2048))
    params = describe(jax.eval_shape(
        lambda: gm.init_params(jax.random.PRNGKey(0), cfg)), one_chip)
    ex = JaxExecutor(cfg, params, batch_size=64, page_size=128,
                     num_pages=832, prefill_buckets=[512], chunk_size=16,
                     prefill_batch=1, mixed_prefill_slices=2,
                     mixed_slice_tokens=512, telemetry_metrics=False)
    assert ex.relaid == {"leaves": 4, "bytes": 2 * periods * (
        9 * 2048 * 8512 + 2048 * 2048 + 2 * 2048 * 512)}
    (fn, operands), = [(fn, operands)
                       for name, fn, operands, _ in ex.programs()
                       if name == program]
    text = fn.lower(*operands).compile().as_text()

    script = _script()
    big = {",".join(map(str, x.shape)): "a leaf"
           for x in jax.tree.leaves((ex.params, ex.cache, ex.row_state))
           if x.size > 1 << 20}
    assert f"{n_mamba},2048,8512" in big
    # (one period's stacks — an attention layer's matrices, nine layers'
    # convolution windows — are small enough to be PREFETCHED whole,
    # ``copy-done`` in and out of ``S(1)``: the served depth's are not)
    assert not [mv for mv in script.whole_moves(text, big)
                if mv["op"] != "copy-done" or periods > 1]
    (enters,) = set(re.findall(
        r"= bf16\[%d,2048,8512\](\{[^ ]*\}) parameter\(" % n_mamba, text))
    assert enters.startswith("{2,1,0:")
    if program == "decode_chunk":
        return
    # nothing as large as the smaller product's result is computed twice
    # but the row state's in-place update (a name, not a second pass)
    again = script.rematerialised(text, 256 * 4096)
    assert not [r for r in again if not r.startswith((
        f"bf16[{n_mamba},65,", "f32[2,512,4352]", "bf16[1,2048,2048]"))], again
    products = {}
    for _, line in _top_level(text):
        m = re.match(r"\s*(?:ROOT )?%[\w.-]+ = \(?(?:f32\[\d+\]\S*, )?"
                     r"bf16\[(\d+),(\d+)\]\S* (\w[\w-]*)\(", line)
        if m is None:
            continue
        rows, width, op = int(m[1]), int(m[2]), m[3]
        if rows == 2048 and width in (8512, 4096, 4416):
            assert op in ("bitcast", "get-tuple-element", "parameter"), line
        if "/qkv/dot_general" in line and op in ("fusion", "convolution"):
            products[rows, width] = products.get((rows, width), 0) + 1
    # a Mamba layer: xBC | dt of the slice rows, z of a tile, the decode
    # rows' whole width — in the mixed step and in the one period that
    # is the decode loop's body (an attention layer's q, k, v are others)
    for shape, n in (((1024, 4416), n_mamba), ((256, 4096), n_mamba),
                     ((64, 8512), n_mamba + 9)):
        assert products.get(shape) == n, (shape, products)
    assert (1024, 8512) not in products and (1024, 4096) not in products


# -- afmoe: window and full attention over two caches ---------------------------


def _trinity_share(one_chip, monkeypatch):
    """``trinity-large-preview-bf16-ep16`` as served: the configuration
    file's model through the family's adapter, the slabs bound as the
    cell's executor binds them, parameters, pool and slabs as shapes on
    the described chip."""
    import json

    from benchmark.harness import contract
    from llmq_tpu.models import afmoe
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-large-preview-bf16-ep16.json")) as f:
        doc = json.load(f)
    adapter = contract.load_family(
        os.path.join(root, "benchmark", "families", "afmoe"), "adapter")
    name = "trinity-share-compile"
    mcfg = adapter.register(name, doc)
    monkeypatch.delitem(afmoe.MODEL_CONFIGS, name)
    cfg = adapter._bound(mcfg, doc["server"])
    ex = doc["server"]["executor"]
    B, pool_pages = ex["max_batch_size"], ex["kv_pages"]
    S = ex["mixed_batch"]["max_slices"]
    T = ex["mixed_batch"]["prefill_token_budget"] // S

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: afmoe.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: afmoe.init_kv_pages(cfg, pool_pages, cfg.page_size)))
    state = on_chip(jax.eval_shape(lambda: afmoe.init_row_state(cfg, B)))
    return afmoe, cfg, params, cache, state, (B, S, T)


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_the_windowed_kernels_compile_for_v5e(one_chip, kernel, window):
    """The two GQA kernels at Trinity's geometry (48 heads over 8 KV
    heads of 128 in 128-token pages, a table of 112 pages, 64 rows):
    over the full layer's pool without a window, over the sliding
    layers' slabs (37 pages a row behind page 0) with it — the decode
    kernel with its full-step block (PR 43) at both."""
    from llmq_tpu.ops.pallas.fused_decode import fused_decode_attention_pallas
    from llmq_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention_pallas)

    B, H, Hkv, D, ps, mp = 64, 48, 8, 128, 128, 112
    L, P = (1, 4096) if window is None else (4, 1 + 64 * 37)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((L, P, ps, Hkv * D), jnp.bfloat16)
    kw = {} if window is None else {"window": window}
    if kernel == "decode":
        row = arg((B, Hkv, D), jnp.bfloat16)
        lowered = jax.jit(partial(fused_decode_attention_pallas, **kw)).lower(
            arg((B, H, D), jnp.bfloat16), row, row, pool, pool,
            arg((B, mp)), arg((B,)), arg((B,)), arg(()))
    else:
        lowered = jax.jit(partial(paged_prefill_attention_pallas,
                                  **kw)).lower(
            arg((512, H, D), jnp.bfloat16), pool, pool, arg((mp,)),
            arg(()), arg(()))
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(text) < 100_000


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_the_share_s_step_fits_v5e_and_copies_no_cache(one_chip, monkeypatch,
                                                       program):
    """One step of ``trinity-large-preview-bf16-ep16`` as served (5
    layers, 16 held experts, 25,024 of the vocabulary, 64 rows, the
    file's pool beside 64 slabs of 37 pages in 4 layers; the mixed step
    with the file's five 512-token slices): weights, pool and slabs
    are 13.2 GB of arguments, BOTH caches go in and come out in place —
    no copy of a pool or a slab leaf — and the step's temporaries stay
    inside what is left of the chip's 16.9 GB."""
    afmoe, cfg, params, cache, state, (B, S, T) = _trinity_share(
        one_chip, monkeypatch)
    mp = cfg.max_seq_len // cfg.page_size
    assert (B, S, T) == (64, 5, 512)
    assert cfg.slab_pages == 37 and state["wk"].shape == (4, 2369, 128, 1024)

    def arg(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if program == "decode":
        def step(params, cache, state, tokens, positions, tables, active):
            return afmoe.forward_decode.__wrapped__(
                params, cfg, tokens, positions, cache, tables, active=active,
                stats=True, row_state=state)
        args = (arg(B), arg(B), arg(B, mp), arg(B, dtype=jnp.bool_))
        calls = 5 + 2 * 4
    else:
        def step(params, cache, state, tokens, positions, tables, active,
                 *pf):
            return afmoe.forward_mixed.__wrapped__(
                params, cfg, tokens, positions, cache, tables, *pf[:5],
                dec_active=active, stats=True, row_state=state,
                pf_rows=pf[5])
        args = (arg(B), arg(B), arg(B, mp), arg(B, dtype=jnp.bool_),
                arg(S * T), arg(S * T), arg(S), arg(S + 1), arg(S, mp),
                arg(S))
        # a layer: its slices' write and attention, the rows' decode
        calls = 5 * (2 * S + 1) + 2 * 4
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, cache, state, *args).compile()
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((cache, state)))
    assert compiled.as_text().count("tpu_custom_call") == calls
    assert 13.1e9 < mem.argument_size_in_bytes < 13.4e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    assert mem.alias_size_in_bytes >= held
    assert not _whole_copies(compiled, (cache, state))
    assert mem.temp_size_in_bytes < (0.5e9 if program == "decode" else 2.0e9)


def _ling_share(one_chip, monkeypatch):
    """``benchmark/configs/ling-3.0-flash-bf16-ep4.json`` as the
    executor holds it: ``(family, cfg, params, cache, state, (B, S, T))``
    as shapes on the described chip."""
    import json

    from benchmark.harness import contract
    from llmq_tpu.models import ling_hybrid as lh
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    path = os.path.join(contract.ROOT, "benchmark", "configs",
                        "ling-3.0-flash-bf16-ep4.json")
    with open(path, encoding="utf-8") as f:
        config = json.load(f)
    adapter = contract.load_family(
        os.path.join(contract.ROOT, "benchmark", "families", "ling_hybrid"),
        "adapter")
    cfg = adapter.register("ling-compile-check", config)
    ex = config["server"]["executor"]

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: lh.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: lh.init_kv_pages(cfg, ex["kv_pages"], ex["page_size"])))
    state = on_chip(jax.eval_shape(
        lambda: lh.init_row_state(cfg, ex["max_batch_size"])))
    S = ex["mixed_batch"]["max_slices"]
    return lh, cfg, params, cache, state, (
        ex["max_batch_size"], S, ex["mixed_batch"]["prefill_token_budget"]
        // S, ex["page_size"])


@pytest.mark.parametrize("program", ["decode", "mixed", "prefill"])
def test_the_delta_rule_share_s_step_fits_v5e_and_copies_no_cache(
        one_chip, monkeypatch, program):
    """One step of ``ling-3.0-flash-bf16-ep4`` as served (7 layers: 6
    KDA and 1 latent, 128 held experts, a quarter of the vocabulary, 128
    rows of 13 MB of row state beside the latent layer's pool): 13.2 GB
    of arguments, the pool and BOTH row-state leaves go in and come out
    in place — no copy of a leaf — the in-place update kernel is there
    once a KDA layer, and the step's temporaries stay inside what is
    left of the chip's 16.9 GB."""
    lh, cfg, params, cache, state, (B, S, T, page) = _ling_share(
        one_chip, monkeypatch)
    mp = cfg.max_seq_len // page
    assert (cfg.n_kda, cfg.n_latent, cfg.n_held) == (6, 1, 128)
    assert state["kda"].shape == (6, B + 1, 128, 4096)

    def arg(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if program == "decode":
        def step(params, cache, state, tokens, positions, tables, active):
            return lh.forward_decode.__wrapped__(
                params, cfg, tokens, positions, cache, tables, active=active,
                stats=True, row_state=state)
        args = (arg(B), arg(B), arg(B, mp), arg(B, dtype=jnp.bool_))
        # the update a KDA layer, the latent write and attention, two
        # grouped products a routed layer
        calls = 6 + 2 + 2 * 6
    elif program == "mixed":
        # as a mixed CHUNK runs it: the fused step, then decode steps in
        # a loop that carries pool and state (without the barrier behind
        # the slices' attention XLA copied the whole pool twice here, and
        # in no program that held the mixed step alone: PR 45)
        def step(params, cache, state, tokens, positions, tables, active,
                 *pf):
            dec, pf_logits, cache, state, st = lh.forward_mixed.__wrapped__(
                params, cfg, tokens, positions, cache, tables, *pf[:5],
                dec_active=active, stats=True, row_state=state,
                pf_rows=pf[5])

            def body(_, carry):
                tok, pos, cache, state, acc = carry
                logits, cache, state, st = lh.forward_decode.__wrapped__(
                    params, cfg, tok, pos, cache, tables, active=active,
                    stats=True, row_state=state)
                return (jnp.argmax(logits, -1).astype(jnp.int32), pos + 1,
                        cache, state, acc + st)

            tok, _, cache, state, st = jax.lax.fori_loop(0, 3, body, (
                jnp.argmax(dec, -1).astype(jnp.int32), positions + 1, cache,
                state, st))
            return tok, pf_logits, cache, state, st
        args = (arg(B), arg(B), arg(B, mp), arg(B, dtype=jnp.bool_),
                arg(S * T), arg(S * T), arg(S), arg(S + 1), arg(S, mp),
                arg(S))
        # the fused step (a KDA layer: the scan kernel between the two
        # copies of its slices' states, and the update) and the loop's body
        calls = (6 * 4 + 2 + 2 * 6) + (6 + 2 + 2 * 6)
    else:
        def step(params, cache, state, tokens, positions, tables, lengths,
                 rows):
            return lh.forward_prefill.__wrapped__(
                params, cfg, tokens, positions, lengths, cache, tables,
                last_only=True, stats=True, row_state=state, rows=rows)
        args = (arg(1, T), arg(1, T), arg(1, mp), arg(1), arg(1))
        calls = 6 * 3 + 2 * 6
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, cache, state, *args).compile()
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((cache, state)))
    assert compiled.as_text().count("tpu_custom_call") == calls
    assert 13.0e9 < mem.argument_size_in_bytes < 13.4e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    assert mem.alias_size_in_bytes >= held
    assert not _whole_copies(compiled, (cache, state))
    assert mem.temp_size_in_bytes < (0.5e9 if program == "decode" else 2.0e9)


def _solar_share(one_chip, monkeypatch):
    """``benchmark/configs/solar-open2-250b-bf16-ep8.json`` as the
    executor holds it (``_ling_share``'s form)."""
    import json

    from benchmark.harness import contract
    from llmq_tpu.models import solar_open2 as so
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    path = os.path.join(contract.ROOT, "benchmark", "configs",
                        "solar-open2-250b-bf16-ep8.json")
    with open(path, encoding="utf-8") as f:
        config = json.load(f)
    adapter = contract.load_family(
        os.path.join(contract.ROOT, "benchmark", "families", "solar_open2"),
        "adapter")
    cfg = so.serving_config(adapter.register("solar-compile-check", config))
    ex = config["server"]["executor"]

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: so.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: so.init_kv_pages(cfg, ex["kv_pages"], ex["page_size"])))
    state = on_chip(jax.eval_shape(
        lambda: so.init_row_state(cfg, ex["max_batch_size"])))
    S = ex["mixed_batch"]["max_slices"]
    return so, cfg, params, cache, state, (
        ex["max_batch_size"], S, ex["mixed_batch"]["prefill_token_budget"]
        // S, ex["page_size"])


@pytest.mark.parametrize("program", ["decode", "mixed", "prefill"])
def test_the_kimi_form_share_s_step_fits_v5e_and_copies_no_cache(
        one_chip, monkeypatch, program):
    """One step of ``solar-open2-250b-bf16-ep8`` as served (4 layers: 1
    gated GQA and 3 KDA of 64 heads, 40 held experts, an eighth of the
    vocabulary, 32 rows of 13 MB of row state beside 8,704 K/V pages of
    272 a row): 11.6 GB of arguments, the pool's two leaves and BOTH
    row-state leaves go in and come out in place — no copy of a leaf —,
    the in-place update kernel is there once a KDA layer (64 heads: two
    head blocks a row, which the kernel refused before it walked in
    blocks), and the step's temporaries stay inside what is left of the
    chip's 16.9 GB. The mixed step (rows in live tiles since PR 53) holds
    2.66e9 bytes of temporaries, no (8192, 24576) float32 among them, in
    28 loops."""
    so, cfg, params, cache, state, (B, S, T, page) = _solar_share(
        one_chip, monkeypatch)
    mp = cfg.max_seq_len // page
    assert (cfg.n_kda, cfg.n_gqa, cfg.n_held, mp) == (3, 1, 40, 272)
    assert state["kda"].shape == (3, B + 1, 128, 8192)
    assert so.routes(cfg, cache, batch=B, page_size=page, max_pages=mp,
                     decode=True, prefill_rows=S)["ssm_update"] == \
        "pallas:kda_update_pallas(head_blocks=2)"
    assert so.scan_step_tokens(cfg, T) == 64

    def arg(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(params, cache, state, tok, pos, tables, active):
        return so.forward_decode.__wrapped__(
            params, cfg, tok, pos, cache, tables, active=active, stats=True,
            row_state=state)

    if program == "decode":
        step = decode
        args = (arg(B), arg(B), arg(B, mp), arg(B, dtype=jnp.bool_))
    elif program == "mixed":
        # as a mixed CHUNK runs it: the fused step, then decode steps in
        # a loop that carries pool and state
        def step(params, cache, state, tokens, positions, tables, active,
                 *pf):
            dec, pf_logits, cache, state, st = so.forward_mixed.__wrapped__(
                params, cfg, tokens, positions, cache, tables, *pf[:5],
                dec_active=active, stats=True, row_state=state,
                pf_rows=pf[5])

            def body(_, carry):
                tok, pos, cache, state, acc = carry
                logits, cache, state, st = decode(params, cache, state, tok,
                                                  pos, tables, active)
                return (jnp.argmax(logits, -1).astype(jnp.int32), pos + 1,
                        cache, state, acc + st)

            tok, _, cache, state, st = jax.lax.fori_loop(0, 3, body, (
                jnp.argmax(dec, -1).astype(jnp.int32), positions + 1, cache,
                state, st))
            return tok, pf_logits, cache, state, st
        args = (arg(B), arg(B), arg(B, mp), arg(B, dtype=jnp.bool_),
                arg(S * T), arg(S * T), arg(S), arg(S + 1), arg(S, mp),
                arg(S))
    else:
        def step(params, cache, state, tokens, positions, tables, lengths,
                 rows):
            return so.forward_prefill.__wrapped__(
                params, cfg, tokens, positions, lengths, cache, tables,
                last_only=True, stats=True, row_state=state, rows=rows)
        args = (arg(1, T), arg(1, T), arg(1, mp), arg(1), arg(1))
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, cache, state, *args).compile()
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((cache, state)))
    text = compiled.as_text()
    assert text.count("kda_update") >= 3 or program == "prefill"
    assert 11.5e9 < mem.argument_size_in_bytes < 11.7e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    assert mem.alias_size_in_bytes >= held
    assert not _whole_copies(compiled, (cache, state))
    # (the mixed step's temporaries read 2.66e9: the slices' tight rows
    # beside the scan's grid, a tile's or a slice's results at a time)
    assert mem.temp_size_in_bytes < (0.3e9 if program != "mixed" else 2.7e9)
    if program != "mixed":
        return
    # What running the rows that hold a token bought (PR 53): the
    # convolution's result of all sixteen slices at once, 805 MB of
    # float32, stands nowhere (one slice's at a time, inside a loop);
    # no stacked leaf of the KDA mixer or the experts is copied (the GQA
    # layer's wq, wk, wv are, transposed for the decode loop, as before);
    # and the loops are counted, since each costs the program's load
    # (PERF.md section 7 (x)) — 28: a layer's two over the row tiles (8), a
    # KDA layer's over the slices in use (3), and what stood before: the
    # held experts' over their blocks, the kernels' walks and the decode
    # loop with its own.
    import re
    assert not re.search(r"f32\[(8192|16,512),24576\]", text)
    assert not _whole_copies(compiled, (params["kda"], params["moe"]))
    assert len(_whole_copies(compiled, params["gqa"])) <= 3
    assert len(re.findall(r" while\(", text)) == 28


def test_the_update_kernel_in_head_blocks_compiles_for_v5e(one_chip):
    """``ops/pallas/kda_update.py`` at 64 heads of 128 x 128 and 32
    rows: ONE kernel, the leaf aliased in and out, three 2 MiB slots
    (a block of 32 heads) where three whole 4 MiB rows would pass the
    walk's 8 MiB."""
    from llmq_tpu.ops.pallas import kda_update as ku

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, B, H, d = 3, 32, 64, 128
    assert ku.kda_update_viable(d, H, d) and ku.head_blocks(H) == 2

    def step(pool, q, k, v, g, beta, rows, n_live):
        return ku.kda_update_pallas(pool, 1, q, k, v, g, beta, rows, n_live)

    wide = arg(B, H, d)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        arg(L, B + 1, d, H * d), wide, wide, wide, wide, arg(B, H),
        arg(B, dtype=jnp.int32), arg(dtype=jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        L * (B + 1) * d * H * d * 4


def _zaya_stage(one_chip, monkeypatch):
    """``benchmark/configs/zaya1-8b-bf16-pp2.json`` as the executor holds
    it: ``(family, cfg, params, cache, state, (B, T, page))`` as shapes on
    the described chip."""
    import json

    from benchmark.harness import contract
    from llmq_tpu.models import zaya
    from llmq_tpu.ops import attention

    monkeypatch.setattr(attention.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("LLMQ_PALLAS", raising=False)
    path = os.path.join(contract.ROOT, "benchmark", "configs",
                        "zaya1-8b-bf16-pp2.json")
    with open(path, encoding="utf-8") as f:
        config = json.load(f)
    adapter = contract.load_family(
        os.path.join(contract.ROOT, "benchmark", "families", "zaya"),
        "adapter")
    cfg = zaya.serving_config(adapter.register("zaya-compile-check", config))
    ex = config["server"]["executor"]

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: zaya.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: zaya.init_kv_pages(cfg, ex["kv_pages"], ex["page_size"])))
    state = on_chip(jax.eval_shape(
        lambda: zaya.init_row_state(cfg, ex["max_batch_size"])))
    return zaya, cfg, params, cache, state, (
        ex["max_batch_size"], max(ex["prefill_buckets"]), ex["page_size"])


def test_the_compressed_attention_stage_s_decode_step_fits_v5e_and_copies_no_cache(
        one_chip, monkeypatch):
    """One decode step of ``zaya1-8b-bf16-pp2`` as served (20 layers,
    each with K/V pages AND a tail as row state, 16 experts a layer all
    held, the whole 262k vocabulary, 64 rows): 14.8 GB of arguments, the
    pool and the tails go in and come out in place — no copy of a leaf —
    the shared fused decode kernel is there once a layer at 2 KV heads
    of 128 beside two grouped products, and the step's temporaries stay
    inside what is left of the chip's 16.9 GB."""
    zaya, cfg, params, cache, state, (B, _T, page) = _zaya_stage(
        one_chip, monkeypatch)
    mp = cfg.max_seq_len // page
    assert (cfg.n_layers, cfg.n_experts, cfg.tail_width) == (20, 16, 2688)
    assert state["tail"].shape == (20, B + 1, 2688)
    routes = zaya.routes(cfg, cache, batch=B, page_size=page, max_pages=mp,
                         decode=True, prefill_rows=1)
    assert routes["decode_attention"].startswith("pallas:_fused_kernel")
    assert routes["prefill_attention"] == "pallas:_prefill_attn_kernel"
    assert routes["cca_mix"] == "xla"

    def arg(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(params, cache, state, tokens, positions, tables, active):
        return zaya.forward_decode.__wrapped__(
            params, cfg, tokens, positions, cache, tables, active=active,
            stats=True, row_state=state)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, cache, state, arg(B), arg(B), arg(B, mp),
        arg(B, dtype=jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves((cache, state)))
    # the fused decode kernel and two ``gmm`` a layer
    assert compiled.as_text().count("tpu_custom_call") == 20 + 2 * 20
    assert 14.6e9 < mem.argument_size_in_bytes < 14.9e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    assert mem.alias_size_in_bytes >= held
    assert not _whole_copies(compiled, (cache, state))
    assert mem.temp_size_in_bytes < 0.5e9


def test_the_delta_rule_scan_kernel_compiles_for_v5e(one_chip):
    """``ops/pallas/kda_scan.py`` at the served sizes — five slices of
    512 tokens, 32 heads of 128 / 128, blocks of 16 inside steps of 64,
    ``HEADS`` heads a step side by side: one kernel, the slices' states
    aliased in and out, and its blocks and what the body spills inside
    the VMEM the call asks for (32 MiB at most: a quarter of the
    chip's), which the compiler would refuse otherwise."""
    import re

    from llmq_tpu.ops.pallas import kda_scan as ks

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, T, H, d = 5, 512, 32, 128
    assert ks.kda_scan_viable(d, H, d, T, 16) and ks.kda_scan_heads(H) == 4

    def step(state, q, k, v, g, beta, lengths):
        return ks.kda_scan_pallas(state, q, k, v, g, beta, lengths, block=16)

    wide = arg(S, T, H, d)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        arg(S, d, H * d), wide, wide, wide, wide, arg(S, T, H),
        arg(S, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert compiled.memory_analysis().alias_size_in_bytes >= S * d * H * d * 4
    scoped = [int(n) for n in re.findall(
        r'scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+",'
        r'"size":"(\d+)"', text)]
    assert scoped and max(scoped) <= 32 << 20, scoped


# -- scripts/whole_copies.py: what it reads out of a compiled program's text ----


_HLO = """\
HloModule jit_decode_chunk, is_scheduled=true

%body.7 (arg: (s32[], bf16[24,2048,2048])) -> (s32[], bf16[24,2048,2048]) {
  %copy-start.3 = (bf16[1,2048,2048]{2,1,0:T(8,128)(2,1)S(1)}, bf16[1,2048,2048]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%slice.1)
  %copy-done.3 = bf16[3328,16,4096]{2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.3)
}

ENTRY %main.9 (params__layers____wq__.1: bf16[24,2048,2048], tok: s32[32]) -> s32[32,8] {
  %params__layers____wq__.1 = bf16[24,2048,2048]{2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="params[\'layers\'][\'wq\']"}
  %tok = s32[32]{0:T(256)} parameter(1)
  %fusion.7.remat2 = bf16[1024,8512]{0,1:T(8,128)(2,1)S(1)} fusion(%copy.5, %copy.321), kind=kOutput, calls=%fused_computation.7.clone
  %fusion.7.remat = bf16[1024,8512]{0,1:T(8,128)(2,1)S(1)} fusion(%copy.5, %copy.321), kind=kOutput, calls=%fused_computation.7.clone
  %fusion.8.remat = bf16[32,2048]{1,0:T(8,128)(2,1)} fusion(%copy.5), kind=kLoop, calls=%fused_computation.8
  %copy.321 = bf16[24,2048,2048]{1,2,0:T(8,128)(2,1)} copy(%params__layers____wq__.1), backend_config={"flag_configs":[]}
  %transpose.2 = bf16[24,2048,2048]{2,1,0:T(8,128)(2,1)} transpose(%copy.321), dimensions={0,2,1}, metadata={op_name="jit(_decode_chunk)/decode_loop/while/body/qkv/dot_general"}
  %copy.5 = bf16[32,2048]{1,0:T(8,128)(2,1)} copy(%fusion.1)
  ROOT %fusion.9 = s32[32,8]{1,0} fusion(%copy.5), kind=kLoop
}
"""


@pytest.mark.parametrize("name,op,inside,under", [
    ("copy.321", "copy", "the entry computation", "(no name)"),
    ("transpose.2", "transpose", "the entry computation",
     "jit(_decode_chunk)/decode_loop/while/body/qkv/dot_general"),
    ("copy-done.3", "copy-done", "body.7", "(no name)"),
])
def test_whole_copies_script_names_each_move_with_its_place(name, op, inside,
                                                            under):
    """``scripts/whole_copies.whole_moves``: a copy, a transpose and an
    asynchronous copy's end as large as a named leaf, each with the
    computation it stands in and its ``op_name``; a small copy and a
    ``copy-start`` (a tuple) are not listed."""
    moves = _script().whole_moves(_HLO, {"24,2048,2048": "params['layers']['wq']",
                                   "3328,16,4096": "pool['k']"})
    assert [m["name"] for m in moves] == ["copy-done.3", "copy.321",
                                          "transpose.2"]
    (mv,) = [m for m in moves if m["name"] == name]
    assert (mv["op"], mv["inside"], mv["under"]) == (op, inside, under)
    assert mv["like"] == ("pool['k']" if name == "copy-done.3"
                          else "params['layers']['wq']")
    # a move of a parameter says how the parameter ENTERS the program
    assert mv["enters"] == (
        "params__layers____wq__.1 enters as "
        "bf16[24,2048,2048]{2,1,0:T(8,128)(2,1)}" if name == "copy.321"
        else "")


@pytest.mark.parametrize("least,counted", [
    (1 << 20, {"bf16[1024,8512]{0,1:T(8,128)(2,1)S(1)}": 2}),
    (1 << 16, {"bf16[1024,8512]{0,1:T(8,128)(2,1)S(1)}": 2,
               "bf16[32,2048]{1,0:T(8,128)(2,1)}": 1}),
    (1 << 24, {}),
])
def test_whole_copies_script_counts_what_is_computed_again(least, counted):
    """``scripts/whole_copies.rematerialised``: the instructions with
    ``.remat`` in their name whose result holds ``least`` elements or
    more, counted by result (how granite's mixed step showed 69
    products of ``bf16[1024,8512]`` beside the model's 36)."""
    assert _script().rematerialised(_HLO, least) == counted


@pytest.mark.parametrize("which", ["export", "import"])
def test_the_tail_programs_move_pages_in_place_for_v5e(one_chip, which):
    """The two tail programs of a window family at Mellum 2's served
    sizes (9 sliding layers, 32 rows' slabs of 13 pages, 64 tail slots
    of 8 pages): each moves its eight pages a layer where they lie — the
    donated leaf comes back aliased, no temporary of a leaf's size. (A
    gather of the eight pages out of the slabs made XLA copy each 0.49 GB
    leaf in halves: 0.49 GB of temporaries where 19 MB move.)"""
    import numpy as np

    from llmq_tpu.models import get_config, mellum

    cfg = get_config("mellum2-12b-a2.5b", max_seq_len=32768)
    cfg = mellum.bind_cache(mellum.serving_config(cfg), page_size=128,
                            step_tokens=512)

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    state = described(jax.eval_shape(lambda: mellum.init_row_state(cfg, 32)))
    tails = described(jax.eval_shape(lambda: mellum.init_row_tails(cfg, 64)))
    i = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
    if which == "export":
        fn = jax.jit(partial(mellum.export_row_tail, cfg),
                     donate_argnums=(1,))
        moved = tails
    else:
        fn = jax.jit(partial(mellum.import_row_tail, cfg),
                     donate_argnums=(0,))
        moved = state
    compiled = fn.lower(state, tails, i, i, i).compile()
    mem = compiled.memory_analysis()
    leaf = min(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert mem.temp_size_in_bytes < leaf // 100
    assert mem.alias_size_in_bytes == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(moved))
    assert not _whole_copies(compiled, (state, tails))


# -- mellum: the mixed chunk over the rows that hold a token ---------------------


@pytest.fixture(scope="module")
def mellum_served(one_chip):
    """``mellum2-12b-a2.5b-bf16``'s executor as the cell builds it
    (``scripts/whole_copies.py``'s way: the configuration file through
    the family's adapter and the server block through the builder's
    ``executor_geometry`` — 32 rows, four slices of 512, 2,048 pages,
    64 tail slots — over the DESCRIPTION of the parameters), with its
    ``mixed_chunk`` compiled for the described chip."""
    import json
    import tempfile

    from benchmark.harness import contract
    from llmq_tpu.core.config import load_config
    from llmq_tpu.engine.builder import executor_geometry
    from llmq_tpu.engine.executor import JaxExecutor, describe
    from llmq_tpu.models import mellum
    from llmq_tpu.ops import attention

    with open(os.path.join(contract.ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b-bf16.json")) as f:
        doc = json.load(f)
    adapter = contract.load_family(
        os.path.join(contract.ROOT, "benchmark", "families", "mellum"),
        "adapter")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention.jax, "default_backend", lambda: "tpu")
        patch.delenv("LLMQ_PALLAS", raising=False)
        name = "mellum-compile-check"
        mcfg = adapter.register(name, doc)
        patch.delitem(mellum.MODEL_CONFIGS, name)
        with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
            json.dump(doc["server"], f)              # JSON is YAML
            f.flush()
            geometry = executor_geometry(load_config(f.name, env=False))
        params = describe(jax.eval_shape(
            adapter.param_builder(mcfg, doc["server"]["model"]),
            jax.random.PRNGKey(0)), one_chip)
        ex = JaxExecutor(mcfg, params, **geometry, telemetry_metrics=False)
        (fn, operands), = [(fn, operands)
                           for name, fn, operands, _ in ex.programs()
                           if name == "mixed_chunk"]
        yield ex, fn.lower(*operands).compile()


def test_mellum_s_mixed_chunk_holds_no_grid_of_pairs_for_v5e(mellum_served):
    """Mellum 2's ``mixed_chunk`` AS DISPATCHED at the served shape (12
    layers, 32 decode rows leading four tight slices of 512, 2,048
    pages beside 32 rows' slabs, 64 tail slots), since its mixed step
    runs the rows that hold a token (PR 55): the page pool and both
    slab leaves go in and come out in place and none is copied; of the
    16,640 (token, expert) pairs, nine blocks of 2,048, ONE array
    stands, the experts' results in bfloat16 as sorted (85 MB a layer,
    written and gathered where pairs are live: PR 59) and none in
    float32 (PR 55's parent held 768 such arrays up to 2,304 wide, 153
    MB the float32 one); four loops a layer are there (front, close,
    the blocks of live pairs, the tiles of live tokens); the only
    stacked leaves copied whole are ``wq``, ``wk`` and ``wv``, once
    each, transposed for the decode LOOP's 32-row products as in the
    parent's program (the front's head split inside its loop asks for
    the same layout: no copy more); and the temporaries, 0.43 GB (0.37
    before the one array), stay under PR 55's parent's 0.95 GB — the
    cell runs at 92 % of the chip."""
    import re
    ex, compiled = mellum_served
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert (ex.spec.batch_size, ex.mixed_prefill_slices,
            ex.mixed_slice_tokens, ex.spec.num_pages) == (32, 4, 512, 2048)
    held = (ex.cache, ex.row_state)
    assert not _whole_copies(compiled, held)
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(held))
    assert not re.search(r"\[16640,\d\d+\]", text)
    assert set(re.findall(r"\w+\[18432,\d\d+\]", text)) == {
        "bf16[18432,2304]"}
    assert _control_flow(compiled).count("while") >= 4 * 12
    assert sorted(_whole_copies(compiled, ex.params)) == [
        "[12,2304,4096]", "[12,2304,512]", "[12,2304,512]"]
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("tokens,rows", [
    (0, 224), (1, 224), (224, 224), (490, 736), (2016, 2016), (2048, 2048)])
def test_mellum_s_mixed_chunk_reports_the_rows_it_ran(mellum_served, tokens,
                                                      rows):
    """``mixed_slice_live_share``'s denominator
    (``executor.slice_tokens`` -> the ``engine.dispatch`` span's
    ``slice_tokens``) is the family's own rule, which is the loop's
    trip count (``ops/rows.tile_rows`` with the 32 decode rows leading):
    256-row tiles over 32 + ``tokens`` rows, less the 32 — one tile
    even for no token at all, and never more than the 2,048 slice
    rows."""
    from llmq_tpu.models import mellum
    from llmq_tpu.ops.rows import tile_rows
    ex, _ = mellum_served
    assert ex.slice_tokens("mixed_chunk", tokens) == rows
    assert mellum.mixed_live_rows(tokens, 32, 4, 512) == rows
    assert tile_rows(tokens, 256, 2048, lead=32) == rows
