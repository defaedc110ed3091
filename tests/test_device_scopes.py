"""Device scopes (``utils/profiling.scope``, the fixed vocabulary
``SCOPES``): they are metadata alone, every serving program of every
family carries them, they survive the export cache, ``slice_tokens``
rides the dispatch beside ``prefill_tokens``, and
``benchmark/harness/scopes.py`` turns ``op_name`` strings and device
events into seconds by scope path. docs/observability.md "Device
scopes"."""

import contextlib
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.utils.profiling import SCOPES, scope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import scopes as reader  # noqa: E402

MODULES = {"embed", "qkv", "kv_write", "attn", "attn_out", "mlp", "head"}
ROUTED = {"moe_route", "moe_experts", "moe_combine"}
#: a state-space layer's: the convolution's window step, the decode
#: update, the chunked scan of prompt tokens
SSM = {"ssm_conv", "ssm_update", "ssm_scan"}
#: a family with two kinds of attention layer: each kind's call, and a
#: gated attention's gate
WINDOW = {"attn_window", "attn_full", "attn_gate"}
#: a residual of several streams: the sites' two halves
HYPER = {"hc_mix", "hc_project", "hc_apply"}
FAMILIES = {
    "afmoe": MODULES | ROUTED | WINDOW,
    "mellum": MODULES | ROUTED | {"attn_window", "attn_full"},
    "ling_hybrid": (MODULES | ROUTED | SSM
                    | {"latent_prefill_attention", "attn_gate",
                       "kda_gates"}),
    "granitemoehybrid": MODULES | SSM,
    "solar_open2": (MODULES | ROUTED | SSM
                    | {"attn_full", "attn_gate", "kda_gates"}),
    "zaya": MODULES | ROUTED | {"cca_mix"},
    "llama": MODULES,
    "llama-w8kv8": MODULES | {"act_quant"},
    "deepseek_v3": MODULES | ROUTED | {"latent_prefill_attention"},
    "longcat_flash": MODULES | ROUTED | {"latent_prefill_attention"},
    "xing": (MODULES | ROUTED | HYPER
             | {"latent_prefill_attention", "attn_full"}),
}
PROGRAMS = ("prefill_multi_b16", "decode_chunk", "mixed_chunk")


def _tiny(family):
    if family == "llama":
        from llmq_tpu.models.llama import init_params, llama3_tiny
        cfg = llama3_tiny(max_seq_len=128)
        return cfg, init_params(jax.random.PRNGKey(0), cfg), {}
    if family == "llama-w8kv8":
        from llmq_tpu.models.llama import (init_params_quantized,
                                           llama3_tiny)
        cfg = llama3_tiny(max_seq_len=128)
        return (cfg, init_params_quantized(jax.random.PRNGKey(0), cfg),
                {"cache_dtype": jnp.int8})
    if family == "deepseek_v3":
        from llmq_tpu.models import deepseek_v3 as ds
        cfg = ds.deepseek_v3_tiny(dtype=jnp.float32, max_seq_len=128)
        return cfg, ds.init_params(jax.random.PRNGKey(31), cfg), {}
    if family == "afmoe":
        from llmq_tpu.models import afmoe as am
        cfg = am.afmoe_tiny(dtype=jnp.float32, max_seq_len=128,
                            held_experts=(8, 16))
        return cfg, am.init_params(jax.random.PRNGKey(41), cfg), {}
    if family == "mellum":
        from llmq_tpu.models import mellum as ml
        cfg = ml.mellum_tiny(dtype=jnp.float32, max_seq_len=128)
        return cfg, ml.init_params(jax.random.PRNGKey(54), cfg), {}
    if family == "ling_hybrid":
        from llmq_tpu.models import ling_hybrid as lh
        cfg = lh.ling_hybrid_tiny(dtype=jnp.float32, max_seq_len=128,
                                  held_experts=(8, 16))
        return cfg, lh.init_params(jax.random.PRNGKey(45), cfg), {}
    if family == "solar_open2":
        from llmq_tpu.models import solar_open2 as so
        cfg = so.solar_open2_tiny(dtype=jnp.float32, max_seq_len=128,
                                  held_experts=(8, 16), n_layers=4,
                                  gqa_layers=(0,))
        return cfg, so.init_params(jax.random.PRNGKey(52), cfg), {}
    if family == "zaya":
        from llmq_tpu.models import zaya
        cfg = zaya.zaya_tiny(dtype=jnp.float32, max_seq_len=128)
        return cfg, zaya.init_params(jax.random.PRNGKey(48), cfg), {}
    if family == "xing":
        from llmq_tpu.models import xing
        cfg = xing.xing_tiny(dtype=jnp.float32, max_seq_len=128, n_layers=2)
        return cfg, xing.init_params(jax.random.PRNGKey(58), cfg), {}
    if family == "granitemoehybrid":
        from llmq_tpu.models import granitemoehybrid as gm
        cfg = gm.granite4h_tiny(dtype=jnp.float32, max_seq_len=128)
        return cfg, gm.init_params(jax.random.PRNGKey(39), cfg), {}
    from llmq_tpu.models import longcat_flash as lf
    cfg = lf.longcat_flash_tiny(dtype=jnp.float32, max_seq_len=128,
                                held_experts=(8, 16))
    return cfg, lf.init_params(jax.random.PRNGKey(34), cfg), {}


def _executor(family):
    cfg, params, kw = _tiny(family)
    return JaxExecutor(cfg, params, batch_size=3, page_size=16,
                       num_pages=40, prefill_buckets=[16], eos_id=-1,
                       chunk_size=4, prefill_batch=2,
                       mixed_prefill_slices=2, mixed_slice_tokens=8, **kw)


def _jobs(ex):
    """(name, jitted program, abstract arguments) of the three serving
    programs, with the signatures ``JaxExecutor._aot_compile`` gives
    them."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    abstract = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    # ``_pool``: the pages and, of a family that has one, the row state
    # beside them; such a family's programs take their prompt chunks'
    # batch rows last
    p, c, key = abstract(ex.params), abstract(ex._pool), sds((2,), jnp.uint32)
    rows = ((lambda n: (sds((n,), jnp.int32),))
            if ex.row_state is not None else (lambda n: ()))
    B, MP = ex.spec.batch_size, ex.spec.max_pages_per_seq
    N, T = ex.prefill_batch, ex.prefill_buckets[0]
    S, TS = ex.mixed_prefill_slices, ex.mixed_slice_tokens
    i32, f32 = jnp.int32, jnp.float32
    chunk = (p, c, sds((B,), i32), sds((B,), i32), sds((B, MP), i32),
             sds((B,), f32), sds((B,), i32), sds((B,), jnp.bool_))
    return [
        (f"prefill_multi_b{T}", ex._prefill_multi,
         (p, c, sds((N, T), i32), sds((N, T), i32), sds((N,), i32),
          sds((N, MP), i32), sds((N,), f32), key) + rows(N)),
        ("decode_chunk", ex._decode_chunk, chunk + (key,)),
        ("mixed_chunk", ex._mixed_chunk,
         chunk + (sds((S * TS,), i32), sds((S * TS,), i32), sds((S,), i32),
                  sds((S + 1,), i32), sds((S, MP), i32), sds((S,), f32),
                  key) + rows(S)),
    ]


def _paths(text):
    """The scope paths in a compiled program's ``op_name``s (those of
    its instructions: a reduction's own little computation carries a
    name cut short, with no ``jit(`` before it, and never runs as an
    operation of its own)."""
    return {reader.scope_path(n, set(SCOPES))
            for n in re.findall(r'op_name="([^"]*)"', text)
            if n.startswith("jit(")}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    return request.param, _executor(request.param)


# -- (a) metadata alone --------------------------------------------------------


@pytest.mark.parametrize("program", range(3), ids=PROGRAMS)
def test_scopes_are_metadata_alone(family, program, monkeypatch):
    """The three forwards of each family, inside the programs that serve
    them, lower to the same StableHLO (no debug info) with
    ``jax.named_scope`` patched to a null context as with it: a scope
    adds no instruction and moves none."""
    _name, ex = family
    _n, fn, args = _jobs(ex)[program]
    jax.clear_caches()
    with_scopes = fn.lower(*args).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    without = fn.lower(*args).as_text()
    monkeypatch.undo()
    jax.clear_caches()
    assert with_scopes == without
    assert "named_scope" not in with_scopes and len(with_scopes) > 1000


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="not a device scope"):
        scope("my_new_scope")
    assert len(set(SCOPES)) == len(SCOPES)
    # short: they are stored in every instruction's metadata
    assert max(map(len, SCOPES)) <= len("latent_prefill_attention")
    # (176 characters until the family afmoe brought its three:
    # attn_window, attn_full, attn_gate; ling_hybrid one: kda_gates;
    # zaya one: cca_mix; the window families' two tail programs three
    # that no serving program's instruction carries: row_tail, export,
    # import; xing three: hc_mix, hc_project, hc_apply)
    assert sum(map(len, SCOPES)) < 275
    assert SSM | WINDOW | HYPER <= set(SCOPES)


def test_no_scope_string_outside_the_vocabulary():
    """Every ``scope("...")`` in ``llmq_tpu/`` names a member of
    ``SCOPES``, every member is used, and ``jax.named_scope`` is
    reached through ``utils/profiling.scope`` alone."""
    used, direct = set(), []
    for base, _dirs, files in os.walk(os.path.join(ROOT, "llmq_tpu")):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(base, f)
            with open(path, encoding="utf-8") as fh:
                src = fh.read()
            used |= set(re.findall(r'\bscope\(\s*"([^"]+)"\s*\)', src))
            if "named_scope(" in src and not path.endswith(
                    os.path.join("utils", "profiling.py")):
                direct.append(path)
    assert direct == []
    assert used == set(SCOPES)


# -- (b) every serving program carries its vocabulary ----------------------------


@pytest.fixture(scope="module")
def compiled(family):
    name, ex = family
    return name, {n: fn.lower(*args).compile().as_text()
                  for n, fn, args in _jobs(ex)}


def test_the_chunk_programs_carry_the_step_and_row_kind_scopes(compiled):
    _name, text = compiled
    dec, mixed = _paths(text["decode_chunk"]), _paths(text["mixed_chunk"])
    pre = _paths(text["prefill_multi_b16"])
    assert any(p.split("/")[0] == "decode_loop" for p in dec)
    assert not any("mixed_step" in p for p in dec)
    heads = {tuple(p.split("/")[:2]) for p in mixed}
    assert {("mixed_step", "slices"), ("mixed_step", "decode_rows"),
            ("mixed_step", "sample")} <= heads
    assert any(p.split("/")[0] == "decode_loop" for p in mixed)
    assert any(p.startswith("decode_loop/sample") for p in mixed | dec)
    assert {p.split("/")[0] for p in pre} <= {"prefill", "sample",
                                              reader.UNSCOPED}
    assert any(p.startswith("prefill/") for p in pre)
    # no scope stands inside itself (a helper scoped where it is called
    # and again where it is defined)
    for path in dec | mixed | pre:
        parts = path.split("/")
        assert len(set(parts)) == len(parts), path


def test_every_module_of_the_family_is_named(compiled):
    name, text = compiled
    want = FAMILIES[name]
    for prog, body in text.items():
        got = {c for p in _paths(body) for c in p.split("/")}
        # decode writes inside its attention kernel's call on the chip;
        # here it is XLA's scatter, under ``kv_write`` or ``attn``
        lacks = want - got - {"kv_write"}
        if prog == "decode_chunk":
            lacks -= {"latent_prefill_attention", "ssm_scan"}
            if name == "xing":      # ``attn_full`` names its PREFILL
                lacks -= {"attn_full"}      # attention alone
        if prog.startswith("prefill"):
            lacks -= {"ssm_update"}
        assert not lacks, (prog, sorted(lacks))
        others = (ROUTED | SSM | WINDOW | HYPER
                  | {"act_quant", "latent_prefill_attention"}) - want
        assert not (got & others), (prog, sorted(got & others))
    assert "kv_write" in {c for p in _paths(text["mixed_chunk"])
                          for c in p.split("/")}


@pytest.mark.parametrize("name", ["llama", "llama-w8kv8"])
def test_llamas_mixed_step_takes_its_rows_apart_for_attention_alone(name):
    """``llama.forward_mixed`` runs a layer's ``qkv``, ``attn_out`` and
    ``mlp`` once for both kinds of row, directly under ``mixed_step``;
    what is left under ``decode_rows`` is what is the decode rows' own
    (their embedding, their fused write + attention, their head), and
    under ``slices`` the slices' own."""
    _, fn, args = _jobs(_executor(name))[2]
    step = {tuple(p.split("/")[1:3])
            for p in _paths(fn.lower(*args).compile().as_text())
            if p.split("/")[0] == "mixed_step"}
    assert {("qkv",), ("attn_out",), ("mlp",)} <= {k[:1] for k in step}
    assert {k[1] for k in step if k[:1] == ("decode_rows",) and k[1:]} == {
        "embed", "attn", "head"}
    assert {k[1] for k in step if k[:1] == ("slices",) and k[1:]} == {
        "embed", "kv_write", "attn", "head"}


def test_the_decode_loop_holds_the_modules_and_nothing_of_the_slices(compiled):
    _name, text = compiled
    loop = {p for p in _paths(text["mixed_chunk"])
            if p.split("/")[0] == "decode_loop"}
    assert {"decode_loop/qkv", "decode_loop/attn", "decode_loop/attn_out",
            "decode_loop/mlp", "decode_loop/head"} <= {
                "/".join(p.split("/")[:2]) for p in loop}
    assert not any({"slices", "decode_rows", "mixed_step"} &
                   set(p.split("/")) for p in loop)


# -- (c) the export cache's round trip -------------------------------------------


def test_scopes_survive_the_export_cache(tmp_path, monkeypatch):
    """The executor's warm path: the second warm-up deserialises every
    program's StableHLO from the export cache, and the executables it
    compiles from them carry the same scope paths."""
    monkeypatch.setenv("LLMQ_EXPORT_CACHE_DIR", str(tmp_path))
    cold = _executor("llama")
    cold.warmup()
    assert not cold._from_export_cache
    warm = _executor("llama")
    warm.warmup()
    assert warm._from_export_cache == set(warm._aot) == set(cold._aot)
    for name in ("decode_chunk", "mixed_chunk", "prefill_multi_b16"):
        got = _paths(warm._aot[name].as_text())
        assert got == _paths(cold._aot[name].as_text()), name
    got = _paths(warm._aot["mixed_chunk"].as_text())
    assert {"mixed_step/qkv", "mixed_step/slices/attn",
            "mixed_step/decode_rows/attn", "decode_loop/mlp"} <= got


# -- (d) slice_tokens beside prefill_tokens ----------------------------------------


def _drive(eng, prompts, max_new=24):
    """Long decodes first, the rest staggered in behind them, so their
    prompts ride mixed chunks."""
    from llmq_tpu.engine.engine import GenRequest
    hs = [eng.submit(GenRequest(id=f"a{i}", prompt=p, max_new_tokens=max_new))
          for i, p in enumerate(prompts[:2])]
    for _ in range(3):
        eng.step()
    hs += [eng.submit(GenRequest(id=f"b{i}", prompt=p,
                                 max_new_tokens=max_new))
           for i, p in enumerate(prompts[2:])]
    eng.run_until_idle()
    assert all(h.result.finish_reason in ("length", "eos") for h in hs)


def _engine(backend):
    from llmq_tpu.core.config import AsyncPipelineConfig, MixedBatchConfig
    from llmq_tpu.engine import EchoExecutor, InferenceEngine
    from llmq_tpu.engine.tokenizer import ByteTokenizer
    tok = ByteTokenizer()
    if backend == "echo":
        ex = EchoExecutor(batch_size=4, page_size=8, num_pages=256,
                          max_pages_per_seq=16, eos_id=tok.eos_id,
                          chunk_size=4, mixed_prefill_slices=3,
                          mixed_slice_tokens=8, async_chunks=True)
    else:
        from llmq_tpu.models.llama import init_params, llama3_tiny
        cfg = llama3_tiny(max_seq_len=128)
        ex = JaxExecutor(cfg, init_params(jax.random.PRNGKey(0), cfg),
                         batch_size=4, page_size=8, num_pages=128,
                         prefill_buckets=[16, 64], eos_id=tok.eos_id,
                         chunk_size=4, prefill_batch=2,
                         mixed_prefill_slices=3, mixed_slice_tokens=8)
    return InferenceEngine(
        ex, tok, enable_metrics=False, name=f"slices-{backend}",
        max_decode_steps=64,
        async_pipeline=AsyncPipelineConfig(enabled=True),
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=24,
                                     max_slices=3))


@pytest.mark.parametrize("backend", ["echo", "jax"])
def test_slice_tokens_ride_the_dispatch_and_the_stats(backend):
    eng = _engine(backend)
    _drive(eng, ["p" * 30, "q" * 34, "r" * 21, "s" * 13, "t" * 27])
    disp = [s.meta for s in eng._prof.snapshot()
            if s.name == "engine.dispatch"]
    mixed = [m for m in disp if m["program"] == "mixed_chunk"]
    plain = [m for m in disp if m["program"] == "decode_chunk"]
    prefills = [m for m in disp if m["steps"] == 0]
    assert mixed and plain and prefills
    ex = eng.executor
    S, T = ex.mixed_prefill_slices, ex.mixed_slice_tokens
    for m in mixed:
        # the live tiles' rows (8-token slices: a tile is 8 rows, three
        # of them so that they are worth a loop) less the 4 decode rows
        # that lead them through a Llama program's products
        n, lead = m["prefill_tokens"], ex.spec.batch_size
        assert lead == 4
        assert m["slice_tokens"] == min(-(-(lead + n) // 8) * 8,
                                        lead + S * T) - lead
        assert 0 < n <= m["slice_tokens"] <= S * T == 24
    assert all(m["slice_tokens"] == 0 == m["prefill_tokens"] for m in plain)
    for m in prefills:
        assert m["slice_tokens"] >= m["prefill_tokens"] > 0
        if backend == "jax":
            bucket = int(m["program"].rsplit("_b", 1)[1])
            rows = ex.prefill_batch if "multi" in m["program"] else 1
            assert m["slice_tokens"] == bucket * rows
    st = eng.get_stats()["mixed_batch"]
    assert st["steps"] == len(mixed)
    assert st["slice_tokens"] == sum(m["slice_tokens"] for m in mixed)
    assert st["prefill_tokens"] == sum(m["prefill_tokens"] for m in mixed)
    assert 0 < st["prefill_tokens"] < st["slice_tokens"]
    assert any(m["slice_tokens"] < S * T for m in mixed)


# -- (e) the reader, on op_name strings and a small neutral form -------------------

VOCAB = set(SCOPES)


@pytest.mark.parametrize("op_name, path", [
    # a while's body, a nested jit, a primitive name
    ("jit(mixed_chunk)/jit(main)/decode_loop/while/body/"
     "jit(forward_decode)/qkv/dot_general", "decode_loop/qkv"),
    # through the export cache
    ("jit(mixed_chunk)/call_exported/jit(_mixed_chunk)/mixed_step/"
     "jit(forward_mixed)/slices/mlp/mul", "mixed_step/slices/mlp"),
    # a remat name and a transform between scopes
    ("jit(f)/decode_loop/while/body/checkpoint/rematted_computation/"
     "mlp/transpose(jvp(dot_general))", "decode_loop/mlp"),
    # a Pallas custom call under its scopes
    ("jit(decode_chunk)/decode_loop/while/body/jit(forward_decode)/attn/"
     "jit(fused_decode_attention_pallas)/pallas_call", "decode_loop/attn"),
    # the one scope older than the vocabulary, nested under attn
    ("jit(mixed_chunk)/mixed_step/slices/attn/latent_prefill_attention/"
     "while/body/dot_general",
     "mixed_step/slices/attn/latent_prefill_attention"),
    # int8 activations inside a product's scope
    ("jit(decode_chunk)/decode_loop/while/body/mlp/act_quant/round",
     "decode_loop/mlp/act_quant"),
    # a primitive or an argument that merely LOOKS like a scope is kept
    # only where it is a whole component
    ("jit(f)/headroom/mlp_like/add", reader.UNSCOPED),
    ("args[0]", reader.UNSCOPED), ("", reader.UNSCOPED),
    # XLA merged two instructions: the first name is its own
    ("jit(mixed_chunk)/mixed_step/jit(forward_mixed)/slices/kv_write/"
     "reshape;slices/kv_write/squeeze", "mixed_step/slices/kv_write"),
], ids=["while_body", "exported", "remat", "pallas", "nested", "act_quant",
        "lookalike", "argument", "empty", "merged"])
def test_scope_path_of_an_op_name(op_name, path):
    assert reader.scope_path(op_name, VOCAB) == path


def _neutral():
    """One device, four runs of two programs: the first and the last
    cut by the capture's edges (the profiler clips their events to the
    capture: each touches an edge), between them one whole mixed chunk
    and one whole decode chunk. Times in ns, chosen so every share is
    exact arithmetic."""
    mixed, dec = "jit_mixed_chunk(7)", "jit_decode_chunk(9)"
    names = ["fusion.1", "fusion.2", "while.3", "fusion.4", "attn_kernel.5",
             "copy.6", "fusion.7", "copy.8", "fusion.9"]
    at = {n: i for i, n in enumerate(names)}
    ops = [
        # the cut run's tail (run 0, clipped to the capture's start)
        [at["fusion.4"], 0, 1000, 0],
        # run 1: a mixed chunk, 10000..20000
        [at["fusion.1"], 10000, 3000, 1],     # slices/mlp (a fusion that
        #                                       took the norm before it)
        [at["fusion.2"], 13000, 1000, 1],     # decode_rows/attn_out
        [at["copy.8"], 14000, 500, 1],        # no op_name, top level
        [at["while.3"], 15000, 5000, 1],      # the loop: 2 steps inside
        [at["fusion.4"], 15100, 1000, 1],
        [at["attn_kernel.5"], 16100, 900, 1],
        [at["copy.6"], 17000, 400, 1],        # no op_name, in the body
        [at["fusion.4"], 17500, 1000, 1],
        [at["attn_kernel.5"], 18500, 900, 1],
        [at["copy.6"], 19400, 400, 1],
        # run 2: a decode chunk, 21000..25000: one step and its sampling
        [at["while.3"], 21000, 4000, 2],
        [at["fusion.4"], 21000, 1500, 2],
        [at["attn_kernel.5"], 22500, 1000, 2],
        [at["fusion.9"], 23500, 500, 2],
        [at["fusion.7"], 24000, 1000, 2],
        # run 3: a mixed chunk the capture's end cut
        [at["fusion.1"], 26000, 3000, 3],
    ]
    loop = "jit(x)/decode_loop/while"
    body = loop + "/body/jit(forward_decode)/"
    return {
        "vocabulary": list(SCOPES),
        "modules": {
            mixed: {"fusion.1": "jit(x)/mixed_step/jit(forward_mixed)/"
                                "slices/mlp/dot_general",
                    "fusion.2": "jit(x)/mixed_step/jit(forward_mixed)/"
                                "decode_rows/attn_out/add",
                    "while.3": loop, "fusion.4": body + "mlp/dot_general",
                    "attn_kernel.5": body + "attn/pallas_call",
                    "copy.6": "", "copy.8": ""},
            dec: {"while.3": loop, "fusion.4": body + "qkv/dot_general",
                  "attn_kernel.5": body + "attn/pallas_call",
                  "fusion.9": body + "head/dot_general",
                  "fusion.7": loop + "/body/sample/argmax"}},
        "planes": [{"name": "/device:TPU:0", "t0_ns": 0.0, "lo_ns": 0.0,
                    "hi_ns": 29000.0, "names": names,
                    "runs": [[mixed, -5, 1500], [mixed, 10000, 10000],
                             [dec, 21000, 4000], [mixed, 26000, 3003]],
                    "ops": ops}]}


def test_reduce_neutral_self_times_paths_and_whole_runs():
    red = reader.reduce_neutral(_neutral(), r"attn_kernel")
    assert (red["devices"], red["whole_runs"], red["cut_runs"]) == (1, 2, 2)
    ns = {k: round(v[0] * 1e9) for k, v in red["paths"].items()}
    assert ns == {
        "mixed_step/slices/mlp": 3000,
        "mixed_step/decode_rows/attn_out": 1000,
        reader.UNSCOPED: 500,
        # the while's own time (5000 - 4600 inside it) plus the two
        # unnamed copies of its body, which take its path; the decode
        # chunk's loop has no time of its own (its body fills it)
        "decode_loop": 400 + 2 * 400,
        "decode_loop/mlp": 2000,
        "decode_loop/attn": 2 * 900 + 1000,
        "decode_loop/qkv": 1500,
        "decode_loop/head": 500,
        "decode_loop/sample": 1000}
    assert red["paths"]["decode_loop/attn"][1] == 3
    # one instruction name, two programs, two paths: the join is by
    # module (``fusion.4`` is mlp in the mixed chunk, qkv in the other)
    progs = red["programs"]
    assert set(progs) == {"jit_mixed_chunk", "jit_decode_chunk"}
    assert progs["jit_mixed_chunk"]["runs"] == 1
    assert progs["jit_mixed_chunk"]["seconds"] == pytest.approx(10e-6)
    assert progs["jit_mixed_chunk"]["decode_attn_calls"] == 2
    assert progs["jit_decode_chunk"]["decode_attn_calls"] == 1
    assert "decode_loop/qkv" not in progs["jit_mixed_chunk"]["paths"]
    assert red["top"]["decode_loop/attn"][0][0] == "attn_kernel"
    # what the metric files ask
    assert reader.under(red, "mixed_step") == pytest.approx(4e-6)
    assert reader.under(red, "mixed_step", reader.SLICES_DENSE,
                        without="decode_rows") == pytest.approx(3e-6)
    assert reader.under(red, "decode_loop") == pytest.approx(9e-6)
    assert reader.under(red, "decode_loop", reader.DECODE_DENSE
                        ) == pytest.approx(4e-6)
    assert reader.runs_holding(red, "mixed_step") == 1
    assert reader.decode_attn_calls(red) == 3
    assert "decode attention calls" in reader.table(red)


def test_reduce_neutral_conserves_tracereds_self_times():
    """Paths + (unscoped) = the self times ``tracered`` gives the same
    operations of the same whole runs, name by name and in sum."""
    from benchmark.harness import tracered
    tr = _neutral()
    red = reader.reduce_neutral(tr)
    p = tr["planes"][0]
    events = [[p["names"][e[0]], e[1], e[2]] for e in p["ops"]
              if e[3] in (1, 2)]
    want = tracered.self_times(events)
    assert {k: round(v[0] * 1e9) for k, v in red["ops"].items()} == {
        k: round(v[0] * 1e9) for k, v in want.items()}
    assert red["busy_s"] == pytest.approx(sum(v[0] for v in want.values()))
    assert sum(v[0] for v in red["paths"].values()) == pytest.approx(
        red["busy_s"])


@pytest.mark.parametrize("what", ["no_vocabulary", "no_device", "no_runs"])
def test_a_capture_with_nothing_to_name_reads_as_none(what):
    tr = _neutral()
    if what == "no_vocabulary":        # the parent of the PR that named
        tr["vocabulary"] = []
    elif what == "no_device":          # a CPU capture
        tr["planes"] = []
    else:                              # every run cut by the edges
        tr["planes"][0]["runs"] = [["jit_mixed_chunk(7)", -5000, 90000]]
        for e in tr["planes"][0]["ops"]:
            e[3] = 0
    assert reader.reduce_neutral(tr, r"attn_kernel") is None


def test_the_wire_decoder_reads_an_hlo_proto():
    """``hlo_modules`` on bytes put together here by the wire format's
    rules: an XSpace whose ``/host:metadata`` plane holds one module's
    HLO in the stat named ``Hlo Proto``."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def ld(num, payload):
        payload = payload.encode() if isinstance(payload, str) else payload
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    def vi(num, n):
        return varint(num << 3) + varint(n)

    def instruction(name, scope_name):
        return ld(2, ld(1, name) + ld(2, "fusion") + vi(35, 4)
                  + (ld(7, ld(1, "dot") + ld(2, scope_name) + vi(4, 12))
                     if scope_name else b""))

    long_name = "jit(f)/decode_loop/while/body/" + "mlp/" * 40 + "dot"
    hlo = ld(1, ld(1, "jit_f") + ld(3, ld(1, "main")
                                    + instruction("fusion.1", long_name)
                                    + instruction("copy.2", "")))
    plane = (vi(1, 3) + ld(2, "/host:metadata")
             + ld(5, vi(1, 9) + ld(2, vi(1, 9) + ld(2, "Hlo Proto")))
             + ld(4, vi(1, 5) + ld(2, vi(1, 5) + ld(2, "jit_f(5)")
                                   + ld(5, vi(1, 9) + ld(6, hlo)))))
    other = ld(2, "/host:CPU") + ld(3, ld(2, "python3"))
    space = ld(1, other) + ld(1, plane) + ld(4, "host")
    assert reader.hlo_modules(space) == {
        "jit_f(5)": {"fusion.1": long_name, "copy.2": ""}}
    assert reader.instruction_of(
        "%fusion.6066 = bf16[8,128]{1,0} fusion(%p.1), kind=kLoop"
    ) == "fusion.6066"
    assert reader.program_of("jit_mixed_chunk(1812124991)") == (
        "jit_mixed_chunk")
