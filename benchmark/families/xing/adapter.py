"""The ``xing4_0`` block in the program: the one file of the family
that imports ``llmq_tpu``. The surface is ``families/llama/adapter.py``'s:

- ``register(name, config)``: the configuration file (the public
  ``config.json``'s keys at its top level, ``num_hidden_layers`` the
  layers THIS CHIP holds and ``dense_layers_held`` the dense ones that
  lead them) as one more entry of the program's registry
  (``llmq_tpu/models/xing.py`` ``MODEL_CONFIGS``) — the program is not
  edited. A multi-token-prediction layer the file does not list under
  ``left_out`` is asked to be SERVED, which the program refuses by name;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served types (bf16 matrices, float32 sites) for
  ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through the latent page pool. For a configuration
  that states a ``tolerance`` it also hands the family's reference
  ``served_many`` (``reference.JUDGED``): a prefill to 16k a bucket at a
  time, mixed steps (prompt slices of other rows beside a decode row at
  16k) and decode steps of rows at eight contexts.

A parent of the PR that brought this family has no such module in the
program: ``register`` then fails at its import, at once.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from types import SimpleNamespace
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
#: The router's selection bias is drawn uniform in (-b, b)
#: (``families/deepseek_v3/adapter.py`` has why).
ROUTER_BIAS = 0.02
#: The fan-in the embedding is drawn by: rows of variance 4, so that the
#: rows of a batch do not all ask the router the same question
#: (``families/mellum/adapter.py`` has the measurement).
EMBED_FAN_IN = 0.25
#: A site's three scalars, and the half-width of the uniform draw added
#: to its biases as training starts them (H_pre 1 / n, H_post 1, H_res
#: the projection of exp(2 I)): every H depends on its token (Phi's
#: products are N(0, 1)) and H_res converges in its 20 steps (entries
#: within e^3.6 of each other), as a trained site's must for the model
#: to be the paper's.
HC_ALPHA, HC_BIAS = 0.1, 0.5
#: ``served_many``: rows of its decode batch, plain decode steps of
#: each, slices of its mixed steps, logits kept of a prefilled bucket.
JUDGED_ROWS, JUDGED_STEPS, JUDGED_SLICES, KEPT = 8, 16, 4, 8
#: Where each row starts decoding, as a share of the longest context.
JUDGED_AT = (1.0, 0.75, 0.5, 0.375, 0.26, 0.18, 0.06, 0.012)
#: name -> the ``tolerance`` of the configuration ``register`` was given.
_TOLERANCE: Dict[str, Dict[str, Any]] = {}


def _part(name: str):
    from benchmark.harness import contract
    return contract.load_family(HERE, name)


def register(name: str, config: Dict[str, Any]):
    """``config`` holds the keys of ``shapes.MODEL_KEYS`` at its top
    level: the whole configuration file, or its ``model`` block."""
    import jax.numpy as jnp

    from llmq_tpu.models import xing
    from llmq_tpu.ops.rope import YarnScaling

    if (config.get("scoring_func", "sigmoid") != "sigmoid"
            or config.get("n_group", 1) != 1
            or config.get("topk_group", 1) != 1
            or config.get("moe_layer_freq", 1) != 1
            or config.get("ep_size", 1) != 1
            or config.get("tie_word_embeddings", False)):
        raise ValueError(f"{name}: the program's xing block has sigmoid "
                         f"scores, no group limit, a routed layer after "
                         f"every dense one, every expert on the chip and an "
                         f"untied head")
    rs, yarn = config.get("rope_scaling"), None
    if rs is not None:
        if rs.get("type") != "yarn":
            raise ValueError(f"{name}: rope_scaling type {rs.get('type')!r}")
        yarn = YarnScaling(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            mscale=float(rs.get("mscale", 1.0)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)))
    nextn = int(config.get("num_nextn_predict_layers", 0))
    left_out = " ".join(map(str, config.get("left_out", ())))
    base = xing.XingConfig(
        name=name, vocab_size=config["vocab_size"],
        dim=config["hidden_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], q_lora_rank=config["q_lora_rank"],
        ffn_dim=config["intermediate_size"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_routed_experts=config["n_routed_experts"],
        n_shared_experts=config["n_shared_experts"],
        n_experts_per_tok=config["num_experts_per_tok"],
        first_k_dense=config.get("dense_layers_held",
                                 config["first_k_dense_replace"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]), rope_scaling=yarn,
        norm_eps=float(config["rms_norm_eps"]),
        hc_mult=int(config["hc_mult"]),
        hc_sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        hc_clamp=(float(config["mhc_h_res_clamp_min"]),
                  float(config["mhc_h_res_clamp_max"])),
        n_nextn_served=(0 if "num_nextn_predict_layers" in left_out
                        else nextn),
        dtype=jnp.bfloat16)
    xing.MODEL_CONFIGS[name] = lambda **kw: dataclasses.replace(base, **kw)
    if "tolerance" in config:
        _TOLERANCE[name] = config["tolerance"]
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` in the program's tree
    (``xing.param_shapes`` / ``hc_shapes`` / ``assemble``). Uniform in
    (-a, a) with a = sqrt(3 / fan_in) (the variance of the program's
    own normal init; the embedding by ``EMBED_FAN_IN``), the hardware
    generator ("rbg"), a leaf drawn one slice of its leading axis at a
    time. RMSNorm weights are ones; the router's selection bias is
    uniform in (-ROUTER_BIAS, ROUTER_BIAS); a site's Phi is drawn like
    a matrix, in float32, its scalars are ``HC_ALPHA`` and its biases
    the training start plus a draw in (-HC_BIAS, HC_BIAS)."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.models import xing

    if server_model.get("quantization") or server_model.get(
            "kv_quantization"):
        xing.check_serving(
            mcfg, quantization=server_model.get("quantization", ""),
            kv_quantization=server_model.get("kv_quantization", ""))
    shapes = xing.param_shapes(mcfg)
    shapes["top"]["embed"] = (shapes["top"]["embed"][0], EMBED_FAN_IN)

    def draw(key, shape, fan_in, dtype=jnp.bfloat16):
        a = (3.0 / fan_in) ** 0.5

        def one(k, shp):
            return jax.random.uniform(k, shp, dtype, -a, a)

        if len(shape) >= 3:
            return jax.lax.map(lambda k: one(k, shape[1:]),
                               jax.random.split(key, shape[0]))
        return one(key, shape)

    def sites(key):
        hc = xing.hc_shapes(mcfg)
        start = xing.hc_init(mcfg)
        k_phi, k_bias = jax.random.split(key)
        L, two, fan_in, m = hc["phi"]
        phi = draw(k_phi, (L * two, fan_in, m), fan_in, jnp.float32)
        return {"phi": phi.reshape(hc["phi"]),
                "alpha": jnp.full(hc["alpha"], HC_ALPHA, jnp.float32),
                "bias": start["bias"] + jax.random.uniform(
                    k_bias, hc["bias"], jnp.float32, -HC_BIAS, HC_BIAS)}

    def build(key):
        names = [(g, n) for g, leaves in shapes.items() for n in leaves]
        keys = jax.random.split(key, len(names) + 2)
        drawn: Dict[str, Dict[str, Any]] = {g: {} for g in shapes}
        for k, (g, n) in zip(keys, names):
            if g == "experts":     # a leaf of its own a routed layer
                drawn[g][n] = [draw(kk, *shapes[g][n]) for kk in
                               jax.random.split(k, mcfg.n_routed_layers)]
            else:
                drawn[g][n] = draw(k, *shapes[g][n])
        params = xing.assemble(
            mcfg, drawn, sites(keys[-2]) if mcfg.hc_mult > 1 else None)
        bias = params["moe"]["router_bias"]
        params["moe"]["router_bias"] = jax.random.uniform(
            keys[-1], bias.shape, bias.dtype, -ROUTER_BIAS, ROUTER_BIAS)
        return params

    return build


def serving_path(mcfg, server: Dict[str, Any]) -> SimpleNamespace:
    """The serving path's model functions at the configuration's
    ``server`` block: ``cache(n)`` a latent page pool of ``n`` pages,
    ``prefill`` (last position's logits) and ``decode`` as the served
    programs call them, ``ident`` the string that identifies what they
    trace, ``vocab_size`` of the logits."""
    from llmq_tpu.models.xing import (forward_decode, forward_prefill,
                                      init_kv_pages)

    page_size = int(server["executor"]["page_size"])

    def cache(n_pages: int):
        return init_kv_pages(mcfg, n_pages, page_size)

    def prefill(params, cache, tokens, positions, lens, bts):
        return forward_prefill(params, mcfg, tokens, positions, lens, cache,
                               bts, last_only=True)

    def decode(params, cache, tokens, positions, bts, active):
        return forward_decode(params, mcfg, tokens, positions, cache, bts,
                              active=active)

    if mcfg.name in _TOLERANCE:
        _part("reference").JUDGED = (_served_many(mcfg, server),
                                     _TOLERANCE[mcfg.name])
    return SimpleNamespace(cache=cache, prefill=prefill, decode=decode,
                           ident=str(mcfg), vocab_size=mcfg.vocab_size)


def judged_plan(n: int, bucket: int, slices: int):
    """How ``n`` judged tokens are spent: ``(starts, mixed_steps)``.
    Row ``r`` is prefilled to ``starts[r]`` (row 0, the longest, by the
    prefill program; the others by the slices of mixed steps, ``slices``
    buckets a step, while row 0 decodes beside them), then all rows
    decode ``JUDGED_STEPS`` steps; ``n = starts[0] + mixed_steps +
    JUDGED_STEPS``."""
    def plan(longest):
        starts = [max(2, int(longest * f)) for f in JUDGED_AT[:JUDGED_ROWS]]
        chunks = sum(-(-s // bucket) for s in starts[1:])
        return starts, -(-chunks // slices)

    longest = n - JUDGED_STEPS
    for _ in range(4):                       # the steps depend on the starts
        starts, steps = plan(longest)
        longest = n - JUDGED_STEPS - steps
    starts, steps = plan(longest)
    if longest < 2 or starts[0] + steps + JUDGED_STEPS > n:
        raise ValueError(f"{n} judged tokens are too few")
    return starts, steps


def _served_many(cfg, server: Dict[str, Any]):
    """``reference.JUDGED``'s ``served_many(params, tokens)`` over the
    serving path, through ONE latent pool and the block tables of the
    served geometry (``max_seq_len // page_size`` pages a row):

    - ``prefill``: row 0's prompt through ``forward_prefill`` a bucket
      at a time, each continuing over the pages before it; ``KEPT``
      positions' logits of every bucket, the last among them;
    - ``mixed``: rows 1-7's prompts (``judged_plan``) as the slices of
      ``forward_mixed``, ``JUDGED_SLICES`` buckets a step (two slices of
      one step may be one row's consecutive buckets), while row 0, at
      the longest context, decodes one teacher-forced token a step
      beside them: each slice's last position, and row 0's;
    - ``decode``: ``JUDGED_STEPS`` teacher-forced steps of all rows in
      one batch through ``forward_decode``, at the eight contexts.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.models.xing import (forward_decode, forward_mixed,
                                      forward_prefill, init_kv_pages)
    from llmq_tpu.ops.rows import pack_grid

    ex = server["executor"]
    ps = int(ex["page_size"])
    bucket = int(min(ex["prefill_buckets"]))
    max_pages = -(-cfg.max_seq_len // ps)
    B, S, T = JUDGED_ROWS, JUDGED_SLICES, bucket

    @partial(jax.jit, donate_argnums=(1,))
    def prefill_kept(params, cache, tokens, start, n, bts, keep):
        positions = start + jnp.minimum(
            jnp.arange(bucket, dtype=jnp.int32)[None], n - 1)
        logits, cache = forward_prefill(params, cfg, tokens, positions,
                                        n[None], cache, bts)
        return logits[0, keep].astype(jnp.float32), cache

    @partial(jax.jit, donate_argnums=(1,))
    def mixed(params, cache, dec_tok, dec_pos, dec_bts, dec_active, pf_tok,
              pf_pos, pf_len, pf_start, pf_bts):
        dec, pf, cache = forward_mixed(
            params, cfg, dec_tok, dec_pos, cache, dec_bts, pf_tok, pf_pos,
            pf_len, pf_start, pf_bts, dec_active=dec_active)
        return dec.astype(jnp.float32), pf.astype(jnp.float32), cache

    @partial(jax.jit, donate_argnums=(1,))
    def step(params, cache, tok, pos, bts, active):
        logits, cache = forward_decode(params, cfg, tok, pos, cache, bts,
                                       active=active)
        return logits.astype(jnp.float32), cache

    def served_many(params, tokens):
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens)
        if n > cfg.max_seq_len:
            raise ValueError(f"{n} judged tokens, {cfg.max_seq_len} "
                             f"positions")
        starts, mixed_steps = judged_plan(n, bucket, S)
        ends = [s + JUDGED_STEPS for s in starts]
        ends[0] += mixed_steps
        pages = [-(-e // ps) for e in ends]
        cache = init_kv_pages(cfg, 1 + sum(pages), ps)
        bts = np.zeros((B, max_pages), np.int32)
        first = 1
        for r, p in enumerate(pages):
            bts[r, :p] = first + np.arange(p, dtype=np.int32)
            first += p
        dev_bts = jnp.asarray(bts)
        out: Dict[str, Any] = {}

        # row 0: its prompt a bucket at a time, KEPT logits of each
        at, got = [], []
        for a in range(0, starts[0], bucket):
            m = min(bucket, starts[0] - a)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :m] = tokens[a:a + m]
            keep = np.unique(((np.arange(KEPT) + 1) * m) // KEPT - 1)
            keep = np.concatenate([keep, np.full(KEPT - len(keep), m - 1)])
            logits, cache = prefill_kept(
                params, cache, jnp.asarray(toks), jnp.int32(a), jnp.int32(m),
                dev_bts[:1], jnp.asarray(keep.astype(np.int32)))
            at.append(a + keep)
            got.append(np.asarray(logits))
        at, idx = np.unique(np.concatenate(at), return_index=True)
        out["prefill"] = (at, np.concatenate(got)[idx])

        # rows 1..: their prompts as slices, S a mixed step, row 0
        # decoding beside them
        chunks = [(r, a, min(T, starts[r] - a))
                  for r in range(1, len(starts))
                  for a in range(0, starts[r], T)]
        only0 = np.arange(B) == 0
        at, got = [], []
        for j in range(mixed_steps):
            now = chunks[j * S:(j + 1) * S]
            g_t, g_p = np.zeros((S, T), np.int32), np.zeros((S, T), np.int32)
            lens = np.ones((S,), np.int32)
            pf_bts = np.zeros((S, max_pages), np.int32)
            for s, (r, a, m) in enumerate(now):
                g_t[s, :m], g_p[s, :m] = tokens[a:a + m], np.arange(a, a + m)
                lens[s], pf_bts[s] = m, bts[r]
            pf_tok, pf_pos, pf_start = pack_grid(g_t, g_p, lens,
                                                 used=len(now))
            tok, pos = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
            tok[0], pos[0] = tokens[starts[0] + j], starts[0] + j
            dec, pf, cache = mixed(
                params, cache, jnp.asarray(tok), jnp.asarray(pos), dev_bts,
                jnp.asarray(only0), jnp.asarray(pf_tok), jnp.asarray(pf_pos),
                jnp.asarray(lens), jnp.asarray(pf_start), jnp.asarray(pf_bts))
            at += [starts[0] + j] + [a + m - 1 for _r, a, m in now]
            got += [np.asarray(dec[:1]), np.asarray(pf[:len(now)])]
        at, idx = np.unique(np.asarray(at), return_index=True)
        out["mixed"] = (at, np.concatenate(got)[idx])

        # all rows: JUDGED_STEPS decode steps in one batch
        first_pos = np.asarray(starts)
        first_pos[0] += mixed_steps
        R = len(starts)
        active = jnp.asarray(np.arange(B) < R)
        at, got = [], []
        for j in range(JUDGED_STEPS):
            tok, pos = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
            tok[:R], pos[:R] = tokens[first_pos + j], first_pos + j
            logits, cache = step(params, cache, jnp.asarray(tok),
                                 jnp.asarray(pos), dev_bts, active)
            at.append(first_pos + j)
            got.append(np.asarray(logits[:R]))
        at, idx = np.unique(np.concatenate(at), return_index=True)
        out["decode"] = (at, np.concatenate(got)[idx])
        return out

    return served_many
