"""The LongCat-Flash block in the program: the one file of the family
that imports ``llmq_tpu``. The surface is ``families/llama/adapter.py``'s,
and the procedure ``families/deepseek_v3/adapter.py``'s:

- ``register(name, config)``: the configuration file (the public
  ``config.json``'s keys at its top level, with ``n_routed_experts`` and
  ``vocab_size`` THIS CHIP'S share and ``router_experts`` /
  ``expert_share`` saying of what) as one more entry of the program's
  registry (``llmq_tpu/models/longcat_flash.py`` ``MODEL_CONFIGS``) —
  the program is not edited;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served type for ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through the latent page pool (both attentions of
  a layer write it), with the kernels the served programs route to. For
  a configuration that states a ``tolerance`` it also hands the
  family's reference ``served_many`` (``reference.JUDGED``): the same
  two functions over every position of a prompt and over 128 decode
  positions.

A parent of the PR that brought this family has no such module in the
program: ``register`` then fails at its import, at once.
"""

from __future__ import annotations

import dataclasses
import os
from types import SimpleNamespace
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
#: The router's selection bias is drawn uniform in (-b, b): not zero (a
#: program that used it in the weights would go unnoticed), small
#: against the scores' spread — a softmax over 768 outputs gives a
#: token's twelve chosen experts 0.004-0.02 each, and a trained balance
#: correction tilts an expert's share of the tokens by under a factor
#: of two.
ROUTER_BIAS = 0.0005
#: Decode positions ``served_many`` drives through the cache: 8 rows (the
#: check's own batch) x 16 teacher-forced steps, as the sibling family.
JUDGED_ROWS, JUDGED_STEPS = 8, 16
#: name -> the ``tolerance`` of the configuration ``register`` was given.
_TOLERANCE: Dict[str, Dict[str, Any]] = {}


def _part(name: str):
    from benchmark.harness import contract
    return contract.load_family(HERE, name)


def register(name: str, config: Dict[str, Any]):
    """``config`` holds the keys of ``shapes.MODEL_KEYS`` at its top
    level: the whole configuration file, or its ``model`` block."""
    import jax.numpy as jnp

    from llmq_tpu.models import longcat_flash

    if (config.get("zero_expert_type", "identity") != "identity"
            or config.get("attention_method", "MLA") != "MLA"
            or config.get("attention_bias", False)):
        raise ValueError(f"{name}: the program's longcat_flash block has "
                         f"latent attention without bias and identity "
                         f"zero-compute experts")
    base = longcat_flash.LongcatFlashConfig(
        name=name, vocab_size=config["vocab_size"],
        dim=config["hidden_size"], n_layers=config["num_layers"],
        n_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        q_lora_rank=config["q_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mla_scale_q_lora=bool(config["mla_scale_q_lora"]),
        mla_scale_kv_lora=bool(config["mla_scale_kv_lora"]),
        ffn_dim=config["ffn_hidden_size"],
        moe_ffn_dim=config["expert_ffn_hidden_size"],
        n_routed_experts=config["router_experts"],
        zero_expert_num=config["zero_expert_num"],
        n_experts_per_tok=config["moe_topk"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        held_experts=_part("shapes").held_experts(config),
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)
    longcat_flash.MODEL_CONFIGS[name] = (
        lambda **kw: dataclasses.replace(base, **kw))
    if "tolerance" in config:
        _TOLERANCE[name] = config["tolerance"]
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` in the program's tree
    (``longcat_flash.param_shapes`` / ``assemble``). Uniform in (-a, a)
    with a = sqrt(3 / fan_in) (the variance of the program's own normal
    init), the hardware generator ("rbg"); a leaf is drawn one slice of
    its leading axis at a time (the stacked dense SwiGLUs are 1.2 GB a
    leaf: their random bits drawn at once are twice that). RMSNorm
    weights are ones; the router's selection bias is uniform in
    (-ROUTER_BIAS, ROUTER_BIAS).

    ``wq_b`` and ``wkv_b`` are drawn s_q and s_kv times NARROWER, so
    that the query and the expanded keys and values come out at unit
    scale AFTER the model's two scale factors, as a trained checkpoint's
    do (the factors exist to undo what a low rank takes from the
    variance). Drawn at 1 / fan_in they came out 2 and 3.46 times too
    large, an attention score had a standard deviation of 5.8, every
    head's softmax was all but one-hot, and a bf16 rounding decided
    WHICH key a head read: the served path then lay 0.34-0.42 RMS from
    the float32 reference and the control 0.82-0.89 (PERF.md section 6,
    PR 34), a comparison that sees nothing finer than a wrong layout."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.models import longcat_flash

    if server_model.get("quantization") or server_model.get(
            "kv_quantization"):
        longcat_flash.check_serving(
            mcfg, quantization=server_model.get("quantization", ""),
            kv_quantization=server_model.get("kv_quantization", ""))
    shapes = longcat_flash.param_shapes(mcfg)
    narrower = {"wq_b": mcfg.q_scale, "wkv_b": mcfg.kv_scale}

    def draw(key, shape, fan_in, by=1.0):
        a = (3.0 / fan_in) ** 0.5 / by

        def one(k, shp):
            return jax.random.uniform(k, shp, jnp.bfloat16, -a, a)

        if len(shape) >= 3:
            return jax.lax.map(lambda k: one(k, shape[1:]),
                               jax.random.split(key, shape[0]))
        return one(key, shape)

    def build(key):
        names = [(g, n) for g, leaves in shapes.items() for n in leaves]
        keys = jax.random.split(key, len(names) + 1)
        drawn: Dict[str, Dict[str, Any]] = {g: {} for g in shapes}
        for k, (g, n) in zip(keys, names):
            if g == "experts":     # a leaf of its own a routed layer
                drawn[g][n] = [draw(kk, *shapes[g][n]) for kk in
                               jax.random.split(k, mcfg.n_layers)]
            else:
                drawn[g][n] = draw(k, *shapes[g][n],
                                   by=narrower.get(n, 1.0))
        params = longcat_flash.assemble(mcfg, drawn)
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
    trace, ``vocab_size`` of the logits (the slice held)."""
    from llmq_tpu.models.longcat_flash import (forward_decode,
                                               forward_prefill,
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
        _part("reference").JUDGED = (_served_many(mcfg, server, cache),
                                     _TOLERANCE[mcfg.name])
    return SimpleNamespace(cache=cache, prefill=prefill, decode=decode,
                           ident=str(mcfg), vocab_size=mcfg.vocab_size)


def _served_many(mcfg, server: Dict[str, Any], new_cache):
    """``reference.JUDGED``'s ``served_many(params, tokens)`` over the
    serving path: ``prefill``, every position of ``tokens`` in one
    prefill through the smallest bucket; ``decode``, the last
    ``JUDGED_ROWS x JUDGED_STEPS`` positions through the latent cache,
    row ``r`` prefilled up to its first one and then teacher-forced
    ``JUDGED_STEPS`` steps, all rows in one batch as the served decode
    program runs them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.models.longcat_flash import forward_decode, forward_prefill

    ex = server["executor"]
    page_size = int(ex["page_size"])
    bucket = int(min(ex["prefill_buckets"]))
    pages = -(-bucket // page_size)
    R = JUDGED_ROWS

    @jax.jit
    def prefill_all(params, cache, tokens, lens, bts):
        positions = jnp.minimum(jnp.arange(bucket, dtype=jnp.int32)[None],
                                lens[:, None] - 1)
        logits, cache = forward_prefill(params, mcfg, tokens, positions, lens,
                                        cache, bts)
        return logits[0].astype(jnp.float32), cache

    def served_many(params, tokens):
        tokens = np.asarray(tokens, np.int32)
        T = len(tokens)
        steps = min(JUDGED_STEPS, (T - 1) // R)
        if T > bucket or steps < 1:
            raise ValueError(f"{T} tokens: the judged sequence fills at "
                             f"least {R + 1} positions and at most the "
                             f"bucket's {bucket}")
        cache = new_cache(1 + R * pages)
        bts = 1 + np.arange(R * pages, dtype=np.int32).reshape(R, pages)

        def prefill(n, r):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = tokens[:n]
            return prefill_all(params, cache, jnp.asarray(toks),
                               jnp.asarray([n], jnp.int32),
                               jnp.asarray(bts[r:r + 1]))

        every, cache = prefill(T, 0)
        first = T - R * steps + steps * np.arange(R)
        for r in range(R):                  # row 0's pages are written anew
            _, cache = prefill(int(first[r]), r)
        active, stepped = jnp.ones((R,), bool), []
        for j in range(steps):
            logits, cache = forward_decode(
                params, mcfg, jnp.asarray(tokens[first + j]),
                jnp.asarray(first + j, jnp.int32), cache, jnp.asarray(bts),
                active=active)
            stepped.append(logits.astype(jnp.float32))
        return {"prefill": (np.arange(T), every[:T]),
                "decode": ((first[None, :] + np.arange(steps)[:, None]
                            ).reshape(-1), jnp.concatenate(stepped))}

    return served_many
