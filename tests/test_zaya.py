"""The ZAYA1 block (``models/zaya.py``: compressed convolutional attention
whose K and V lie in the page pool AFTER the mix while the mix's tail —
two convolution inputs and a shifted value — rides as ROW STATE on the
same layer; a top-1 routed SwiGLU behind a router network whose state is
carried from layer to layer) held to its family's plain float32
reference (``benchmark/families/zaya/reference.py``, which shares no code
with ``llmq_tpu``, carries nothing and computes the convolutions as
shifts of the whole sequence) at a tiny width, on seeded weights.

Logits, never tokens. The weights here are float32, so the served path
differs from the reference by float32 rounding alone and the comparison
is tight (``TOL``): each of the broken paths below — every mechanism and
every item of the configuration file's ``assumed`` that a program could
drop in silence — moves the logits by ten times that or more.
"""

import dataclasses
import os
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import contract
from llmq_tpu.core.config import MixedBatchConfig, PrefixCacheConfig
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import family_of, get_config, model_names
from llmq_tpu.models import zaya
from llmq_tpu.ops import cca, moe
from llmq_tpu.ops.rows import pack_grid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = os.path.join(REPO, "benchmark", "families", "zaya")
reference = contract.load_family(FAMILY, "reference")

PAGE, BUCKET, ROWS = 8, 32, 3
#: float32 against float32: measured 1e-6 to 4e-6 here; the mildest
#: broken path gives over 1e-3.
TOL = {"clean_quantile": 0.25, "rms_clean": 2e-5, "rms": 2e-5,
       "margin_eps": 1e-7, "margin_decisive": 0.0, "tail_rel": 2e-5,
       "kv_rel": 2e-5, "kv_row": 1e-4}


def hf_model(cfg):
    """The configuration under the public ``config.json``'s keys: what
    the reference reads."""
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.dim,
            "layer_types": list(cfg.layer_types),
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "rope_parameters": {"hybrid": {
                "rope_theta": cfg.rope_theta,
                "partial_rotary_factor": cfg.rotary_dim / cfg.head_dim}},
            "num_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.n_experts_per_tok,
            "moe_intermediate_size": cfg.moe_ffn_dim,
            "router_hidden_size": cfg.router_dim,
            "rms_norm_eps": cfg.norm_eps, "vocab_size": cfg.vocab_size}


@pytest.fixture(scope="module")
def tiny():
    cfg = zaya.zaya_tiny(dtype=jnp.float32, max_seq_len=128)
    params = zaya.init_params(jax.random.PRNGKey(48), cfg)
    seq = np.random.default_rng(48).integers(3, cfg.vocab_size, 100,
                                             dtype=np.int32)
    return cfg, params, seq


def block_table(cfg, n_rows=ROWS):
    mp = cfg.max_seq_len // PAGE
    return (1 + np.arange(n_rows)[:, None] * mp
            + np.arange(mp)[None, :]).astype(np.int32)


def new_cache(cfg, n_rows=ROWS):
    return (zaya.init_kv_pages(cfg, 1 + n_rows * (cfg.max_seq_len // PAGE),
                               PAGE), zaya.init_row_state(cfg, n_rows))


def prefill(fns, cfg, params, cache, state, bt, seq, start, end, row):
    """One bucket-padded prefill of seq[start:end] at its absolute
    positions in batch row ``row``; the last valid position's logits."""
    n = end - start
    toks = np.zeros((1, BUCKET), np.int32)
    toks[0, :n] = seq[start:end]
    pos = start + np.minimum(np.arange(BUCKET, dtype=np.int32), n - 1)[None]
    logits, cache, state = fns.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([n], jnp.int32), cache, jnp.asarray(bt[row:row + 1]),
        last_only=True, row_state=state, rows=jnp.asarray([row], jnp.int32))
    return np.asarray(logits)[0], cache, state


def serve(cfg, params, seq, cuts, fns=zaya, row=1, carry=True, dirty=False):
    """Prefill seq[:cuts[-1]] in the slices ``cuts`` bounds, in batch
    row ``row`` of ``ROWS``, then teacher-forced decode steps through
    the tails and the pool to the end of ``seq`` (the other rows not
    active). ``carry`` False: the tail is NOT handed from slice to slice
    nor to decode (zeros in its place); ``dirty``: the row holds another
    sequence's tail when this one starts at position 0. Returns the
    logits at positions cuts[-1] - 1 .. len(seq) - 1, those positions,
    the row's tails and the row's K and V rows of every position."""
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    if dirty:
        state = jax.tree.map(lambda x: jnp.full_like(x, 0.7), state)
    out, start = [], 0
    for end in cuts:
        logits, cache, state = prefill(fns, cfg, params, cache, state, bt,
                                       seq, start, end, row)
        start = end
        if not carry:
            state = zaya.init_row_state(cfg, ROWS)
    out.append(logits)
    active = jnp.asarray(np.arange(ROWS) == row)
    for p in range(cuts[-1], len(seq)):
        tok, pos = np.zeros(ROWS, np.int32), np.zeros(ROWS, np.int32)
        tok[row], pos[row] = seq[p], p
        logits, cache, state = fns.forward_decode(
            params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
            jnp.asarray(bt), active=active, row_state=state)
        out.append(np.asarray(logits)[row])
    kv = np.concatenate([np.asarray(cache[k][:, bt[row]]).reshape(
        cfg.n_layers, -1, cache[k].shape[-1])[:, :len(seq)]
        for k in ("k", "v")], -1)
    return (np.stack(out), list(range(cuts[-1] - 1, len(seq))),
            np.asarray(state["tail"][:, row]), kv)


def verdict(cfg, params, seq, served, rows, tails=None, kv=None):
    ref = reference.routed_forward(params, seq, hf_model(cfg), rows,
                                   snaps=[len(seq) - 1])
    tail_rel = ([0.0] if tails is None else reference.layer_distances(
        tails, np.asarray(ref.tails[:, 0])))
    kv_rel = (None if kv is None else reference.layer_distances(
        kv, np.asarray(ref.kv)))
    return reference.judge(served, np.asarray(ref.logits), ref.margins,
                           ref.swapped, tail_rel, kv_rel, TOL,
                           None if kv is None else reference.worst_row(
                               kv, np.asarray(ref.kv)))


def test_the_family_is_registered():
    assert model_names()["zaya-tiny"] == "zaya"
    assert model_names()["zaya1-8b"] == "zaya"
    cfg = get_config("zaya1-8b")
    assert family_of(cfg) is zaya
    assert cfg.n_layers == 40 and set(cfg.layer_types) == {zaya.HYBRID}
    assert zaya.param_count_analytic(cfg) == 8_840_475_344
    half = get_config("zaya1-8b", layer_types=(zaya.HYBRID,) * 20)
    assert zaya.param_count_analytic(half) == 4_688_805_224
    assert zaya.kv_bytes_per_token(half) == 20 * 1024
    assert zaya.row_state_bytes_per_row(half) == 20 * 2688 * 4
    assert (zaya.param_count_analytic(half) - zaya.active_param_count(half)
            == 20 * 15 * 3 * 2048 * 2048)
    tiny = get_config("zaya-tiny")
    assert zaya.param_count(zaya.init_params(jax.random.PRNGKey(0), tiny)) \
        == zaya.param_count_analytic(tiny)
    # pages AND row state, of the same layers
    pool, rs = zaya.init_kv_pages(tiny, 5, 8), zaya.init_row_state(tiny, 4)
    assert pool["k"].shape == pool["v"].shape == (3, 5, 8, 64)
    assert rs["tail"].shape == (3, 5, 2 * 192 + 32)
    assert rs["tail"].dtype == jnp.float32


# -- the served path against the reference ------------------------------------


def test_prefill_every_position(tiny):
    cfg, params, seq = tiny
    toks = np.zeros((1, 64), np.int32)
    toks[0, :50] = seq[:50]
    pos = np.minimum(np.arange(64, dtype=np.int32), 49)[None]
    cache, state = new_cache(cfg, 1)
    logits, _, state = zaya.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([50], jnp.int32), cache, jnp.asarray(block_table(cfg, 1)),
        row_state=state, rows=jnp.zeros((1,), jnp.int32))
    got = verdict(cfg, params, seq[:50], np.asarray(logits)[0, :50],
                  list(range(50)), np.asarray(state["tail"][:, 0]))
    assert got["ok"] and got["near_tie_share"] == 0, got


@pytest.mark.parametrize("cuts", [(20,), (32, 64, 70), (13, 45, 46, 75)],
                         ids=["one-slice", "whole-slices", "mid-sequence"])
def test_prefill_in_slices_then_decode_through_pages_and_tails(tiny, cuts):
    """The tail is handed from slice to slice (a slice of ONE token among
    them: both convolutions and the shift reach back across two
    boundaries) and the one-token step continues what the slices left;
    every position's K and V in every layer's pages, and the row's
    tails behind the last token, are the reference's."""
    cfg, params, seq = tiny
    served, rows, tails, kv = serve(cfg, params, seq, cuts)
    got = verdict(cfg, params, seq, served, rows, tails, kv)
    assert got["ok"] and got["near_tie_share"] == 0, got


def test_a_row_restarted_at_position_zero_starts_from_zeros(tiny):
    """A row that held another sequence's tail is zeroed INSIDE the
    program where its new sequence starts."""
    cfg, params, seq = tiny
    served, rows, tails, kv = serve(cfg, params, seq, (32, 64, 70),
                                    dirty=True)
    got = verdict(cfg, params, seq, served, rows, tails, kv)
    assert got["ok"], got
    # ... and a decode step at position 0 starts from zeros too
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    state = jax.tree.map(lambda x: jnp.full_like(x, 0.7), state)
    logits, _, state = zaya.forward_decode(
        params, cfg, jnp.asarray([seq[0], 0, 0]), jnp.zeros((3,), jnp.int32),
        cache, jnp.asarray(bt), active=jnp.asarray([True, False, False]),
        row_state=state)
    got = verdict(cfg, params, seq[:1], np.asarray(logits)[:1], [0],
                  np.asarray(state["tail"][:, 0]))
    assert got["ok"], got
    assert (np.asarray(state["tail"][:, 1:]) == np.float32(0.7)).all()


def _counts(cfg, st):
    layout, st = zaya.step_stats_layout(cfg), np.asarray(st)
    assert st.shape == (zaya.step_stats_size(cfg),)
    out = {k: int(st[i]) for k, i in layout.items() if k != "load"}
    out["load"] = st[slice(*layout["load"])]
    return out


def _mixed(cfg, params, cache, state, bt, dec, slices, T=BUCKET, S=2,
           fns=zaya, **kw):
    """One mixed step: ``dec`` {row: (token, position)} decode rows of
    ``ROWS``, ``slices`` [(row, tokens, start)] on an (S, T) grid."""
    tok, pos = np.zeros(ROWS, np.int32), np.zeros(ROWS, np.int32)
    for r, (t, p) in dec.items():
        tok[r], pos[r] = t, p
    g_t, g_p = np.zeros((S, T), np.int32), np.zeros((S, T), np.int32)
    lens, rows = np.ones((S,), np.int32), np.full((S,), ROWS, np.int32)
    pf_bt = np.zeros((S, bt.shape[1]), np.int32)
    for i, (r, toks, start) in enumerate(slices):
        n = len(toks)
        g_t[i, :n], g_p[i, :n] = toks, start + np.arange(n)
        lens[i], rows[i], pf_bt[i] = n, r, bt[r]
    pf_tok, pf_pos, starts = pack_grid(g_t, g_p, lens, used=len(slices))
    return fns.forward_mixed(
        params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
        jnp.asarray(bt), jnp.asarray(pf_tok), jnp.asarray(pf_pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bt),
        dec_active=jnp.asarray([r in dec for r in range(ROWS)]),
        row_state=state, pf_rows=jnp.asarray(rows), **kw)


def test_mixed_steps_with_a_live_and_an_idle_slice(tiny):
    """Two decode rows and ONE prompt slice of two (the other idle) that
    continues a third row's context mid-sequence, in one fused step,
    against the reference's full forward pass of each; the routed
    counters; then the slice's row decodes from the tail the mixed step
    left, and a row that does not decode keeps its tail to the bit."""
    cfg, params, seq = tiny
    other = np.random.default_rng(7).integers(3, cfg.vocab_size, 90,
                                              dtype=np.int32)
    bt = block_table(cfg)
    cache, state = new_cache(cfg)
    for s, row, upto in ((seq, 0, 70), (other, 1, 45), (other, 2, 50)):
        for a in range(0, upto, BUCKET):
            _, cache, state = prefill(zaya, cfg, params, cache, state, bt, s,
                                      a, min(a + BUCKET, upto), row)
    dec, pf, cache, state, st = _mixed(
        cfg, params, cache, state, bt,
        {0: (seq[70], 70), 1: (other[45], 45)},
        [(2, other[50:79], 50)], stats=True)
    for served, s, row in ((dec[0], seq, 70), (dec[1], other, 45),
                           (pf[0], other, 78)):
        got = verdict(cfg, params, s[:row + 1], np.asarray(served)[None],
                      [row])
        assert got["ok"], got
    got = verdict(cfg, params, other[:79], np.asarray(pf[0])[None], [78],
                  np.asarray(state["tail"][:, 2]))
    assert got["ok"], got
    c = _counts(cfg, st)
    live = 29 + 2 + 1          # and the idle slice's one trash token
    assert c["runs"] == cfg.n_layers == 3
    assert c["load"].sum() == live * c["runs"]
    assert 0 < c["touched"] <= c["runs"] * cfg.n_experts
    # a second step: row 2 decodes from what the slice left, row 1 idles
    before = np.asarray(state["tail"][:, 1])
    dec, _, cache, state = _mixed(cfg, params, cache, state, bt,
                                  {0: (seq[71], 71), 2: (other[79], 79)}, [])
    got = verdict(cfg, params, other[:80], np.asarray(dec[2])[None], [79],
                  np.asarray(state["tail"][:, 2]))
    assert got["ok"], got
    assert (before == np.asarray(state["tail"][:, 1])).all()


def test_the_programs_hand_out_the_experts_they_chose(tiny):
    """``chosen=True``: (layers, rows, 1), the reference's own choice
    where nothing is near a tie; forced the other way the reference
    says which positions and by what margin."""
    cfg, params, seq = tiny
    toks = np.zeros((1, 64), np.int32)
    toks[0, :50] = seq[:50]
    pos = np.minimum(np.arange(64, dtype=np.int32), 49)[None]
    cache, state = new_cache(cfg, 1)
    *_, took = zaya.forward_prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos),
        jnp.asarray([50], jnp.int32), cache, jnp.asarray(block_table(cfg, 1)),
        row_state=state, rows=jnp.zeros((1,), jnp.int32), chosen=True)
    took = np.asarray(took)[:, :50]
    assert took.shape == (cfg.n_layers, 50, 1)
    own = reference.routed_forward(params, seq[:50], hf_model(cfg),
                                   np.arange(50), forced=took)
    assert not np.asarray(own.swapped).any()
    other = (took + 1) % cfg.n_experts
    forced = reference.routed_forward(params, seq[:50], hf_model(cfg),
                                      np.arange(50), forced=other)
    assert np.asarray(forced.swapped)[0].all()      # (deeper: another stream)
    got = reference.judge(np.asarray(forced.logits), np.asarray(own.logits),
                          forced.margins, forced.swapped, [0.0], None, TOL)
    assert not got["ok"] and got["swap_margin"] > 0


def test_the_control_one_precision_down_says_which_part(tiny):
    """``lowp``: each part alone moves what it should — the cache's
    rounding the cached rows, the state's the tails, the router's the
    logits — and an unknown name is refused."""
    cfg, params, seq = tiny
    model, rows = hf_model(cfg), np.arange(40)
    ref = reference.routed_forward(params, seq[:40], model, rows, snaps=[39])
    for part, moved in (("cache", "kv"), ("state", "tails"),
                        ("router", "logits")):
        low = reference.routed_forward(params, seq[:40], model, rows,
                                       lowp=(part,), snaps=[39],
                                       forced=np.asarray(
                                           ref.margins)[:, :, None] * 0 - 1)
        gap = {k: float(np.abs(np.asarray(getattr(low, k))
                               - np.asarray(getattr(ref, k))).max())
               for k in ("kv", "tails", "logits")}
        assert gap[moved] > 1e-4, (part, gap)
        if part == "router":
            assert gap["kv"] < 1e-2 and gap["tails"] > 0
    with pytest.raises(ValueError, match="lowp"):
        reference.routed_forward(params, seq[:8], model, [7], lowp=("x",))


# -- the broken paths, each of which the comparison refuses -------------------


def _retraced():
    """The model's forward functions, each under a NEW function and a
    ``jax.jit`` of its own: a patched helper must be traced again, not
    found in the cache of the function it was traced under."""
    def fresh(fn):
        def call(*args, **kw):
            return fn(*args, **kw)
        return jax.jit(call, static_argnums=(1,),
                       static_argnames=("last_only", "stats"))

    return SimpleNamespace(
        forward_prefill=fresh(zaya.forward_prefill.__wrapped__),
        forward_decode=fresh(zaya.forward_decode.__wrapped__))


def _choose_with(**change):
    def choose(scores, bias, _sound=moe.choose, **kw):
        if change.get("bias_in_gate"):
            _, gates = _sound(scores + bias, jnp.zeros_like(bias), **kw)
            return _sound(scores, bias, **kw)[0], gates
        return _sound(scores, bias, **{**kw, **change})
    return choose


FAULTS = ("tail-not-carried-over-a-slice-boundary",
          "tail-not-zeroed-at-position-0", "value-shift-takes-the-current",
          "q-k-mean-dropped", "temperature-dropped",
          "rope-over-the-whole-head", "depth-carry-dropped",
          "bias-enters-the-gate", "gate-renormalised")


def _broken(name, monkeypatch, cfg, params):
    """(cfg, params, serve's keywords) of the PROGRAM with one path
    broken; the reference stays sound."""
    kw = {}
    layers, router = params["layers"], params["router"]
    if name == "tail-not-carried-over-a-slice-boundary":
        kw["carry"] = False
    elif name == "tail-not-zeroed-at-position-0":
        kw["dirty"] = True
        monkeypatch.setattr(zaya, "_fresh", lambda tail, start: tail)
    elif name == "value-shift-takes-the-current":
        monkeypatch.setattr(cca, "_values", lambda v1, now, before:
                            jnp.concatenate([v1, now], -1))
    elif name == "q-k-mean-dropped":
        monkeypatch.setattr(cca, "_mean", lambda c, H, G, d: (0.0, 0.0))
    elif name == "temperature-dropped":
        params = {**params, "layers": {**layers, "temp": jnp.ones_like(
            layers["temp"])}}
    elif name == "rope-over-the-whole-head":
        cfg = dataclasses.replace(cfg, rotary_dim=cfg.head_dim)
    elif name == "depth-carry-dropped":
        params = {**params, "router": {**router, "gamma": jnp.zeros_like(
            router["gamma"])}}
    elif name == "bias-enters-the-gate":
        monkeypatch.setattr(zaya, "choose", _choose_with(bias_in_gate=True))
    elif name == "gate-renormalised":
        monkeypatch.setattr(zaya, "choose", _choose_with(norm_topk=True))
    else:
        raise AssertionError(name)
    return cfg, params, kw


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_path_is_refused_by_ten_times_the_limit(tiny, monkeypatch,
                                                         fault):
    """Every mechanism, taken out of the PROGRAM: prefill in slices and
    decode through tails and pool read ten times the limit or more
    against the sound reference."""
    cfg, params, seq = tiny
    bad_cfg, bad_params, kw = _broken(fault, monkeypatch, cfg, params)
    served, rows, tails, kv = serve(bad_cfg, bad_params, seq, (32, 64, 70),
                                    fns=_retraced(), **kw)
    got = verdict(cfg, params, seq, served, rows, tails, kv)
    assert not got["ok"] and got["rms_clean"] > 10 * TOL["rms_clean"], got
    if fault.startswith("tail-not"):    # another token's K and V, whole
        assert got["kv_row"] > 0.5, got


def test_other_layer_types_int8_and_a_mesh_are_refused_by_name(tiny):
    cfg, _, _ = tiny
    for kw, word in (({"quantization": "int8"}, "model.quantization"),
                     ({"kv_quantization": "int8"}, "model.kv_quantization"),
                     ({"mesh": True}, "executor.mesh")):
        with pytest.raises(ValueError, match=word):
            zaya.check_serving(cfg, **kw)
    sliding = dataclasses.replace(
        cfg, layer_types=("hybrid", "hybrid_sliding", "hybrid"))
    with pytest.raises(ValueError, match="hybrid_sliding"):
        zaya.check_serving(sliding)
    with pytest.raises(NotImplementedError, match="tensors are called"):
        zaya.import_hf("/nowhere", cfg)
    with pytest.raises(ValueError, match="KV heads"):
        zaya.zaya_tiny(n_kv_heads=1, n_heads=4)


# -- through the executor and the engine --------------------------------------


def make_engine(tiny, batch=2, **kw):
    cfg, params, _ = tiny
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=batch, page_size=PAGE,
                     num_pages=96, prefill_buckets=[16, 32],
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8)
    return InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=64,
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                     max_slices=2), **kw), ex


def generate(eng, rid, prompt, n=12, **kw):
    h = eng.submit(GenRequest(id=rid, prompt=prompt, max_new_tokens=n,
                              temperature=0.0, **kw))
    eng.run_until_idle()
    assert h.done
    return h.result


def test_the_executor_carries_row_state_beside_pages_of_the_same_layers(
        tiny):
    """Nothing in the executor takes row state for the mark of a layer
    WITHOUT pages: here every layer has both."""
    cfg = tiny[0]
    eng, ex = make_engine(tiny, batch=3)
    assert set(ex.cache) == {"k", "v"} and set(ex.row_state) == {"tail"}
    assert ex.cache["k"].shape == (cfg.n_layers, 96, PAGE, 64)
    assert ex.row_state["tail"].shape == (cfg.n_layers, 4, cfg.tail_width)
    per_row = zaya.row_state_bytes_per_row(cfg)
    assert ex.row_state_bytes_per_row == per_row == sum(
        x.nbytes for x in jax.tree.leaves(ex.row_state)) // 4
    assert eng.get_stats()["row_state"]["bytes_per_row"] == per_row
    with pytest.raises(ValueError, match="names its sequence's batch row"):
        ex.prefill_async([1, 2, 3], 0, np.zeros(16, np.int32), 0.0)


def test_served_through_the_engine_as_alone(tiny):
    """Requests through ``InferenceEngine`` over the executor's prefill,
    decode-chunk and mixed-chunk programs: a prompt that joins a running
    batch (its slices ride mixed steps) yields the tokens it yields
    alone, and a row that another sequence left is started from a tail
    of zeros; the routed counters fill by the family's layout."""
    prompt = "a prompt of fifty-odd bytes whose slices ride mixed steps"
    alone, _ = make_engine(tiny)
    want = generate(alone, "a", prompt, n=16)
    eng, _ = make_engine(tiny)
    first = eng.submit(GenRequest(id="long", prompt="x" * 20,
                                  max_new_tokens=40, temperature=0.0))
    for _ in range(3):
        eng.step()
    second = eng.submit(GenRequest(id="b", prompt=prompt, max_new_tokens=16,
                                   temperature=0.0))
    eng.run_until_idle()
    assert first.done and second.done
    assert second.result.tokens == want.tokens and len(want.tokens) == 16
    again = generate(eng, "c", prompt, n=16)
    assert again.tokens == want.tokens
    stats = eng.get_stats()
    assert stats["mixed_batch"]["steps"] > 0
    routed = stats["moe"]
    assert routed["layer_runs"] > 0 and routed["pairs"] > 0
    assert len(routed["load"]) == tiny[0].n_experts


def test_a_prefix_match_and_a_second_turn_are_declined_and_counted(tiny):
    """Pages alone do not hold the tail: nothing is adopted."""
    shared = "the same forty-odd characters of system prompt: "
    plain, _ = make_engine(tiny)
    want = generate(plain, "b", shared + "second question")
    eng, _ = make_engine(tiny, prefix_cache=PrefixCacheConfig(enabled=True))
    generate(eng, "a", shared + "first question", conversation_id="c")
    second = generate(eng, "b", shared + "second question")
    assert second.cached_tokens == 0 and second.tokens == want.tokens
    turn = generate(eng, "a2", " and then?", conversation_id="c",
                    history_text=shared + "first question")
    assert turn.cached_tokens == 0
    declined = eng.get_stats()["row_state"]["declined"]
    assert declined["prefix"] >= 1 and sum(declined.values()) >= 2
