"""The DeepSeek-V3 block in the program: the one file of the family
that imports ``llmq_tpu``. The surface is ``families/llama/adapter.py``'s:

- ``register(name, config)``: the configuration file (Hugging Face keys
  at its top level) as one more entry of the program's registry
  (``llmq_tpu/models/deepseek_v3.py`` ``MODEL_CONFIGS``) — the program
  is not edited;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served type for ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through the latent page pool, with the kernels the
  served programs route to. For a configuration that states a
  ``tolerance`` it also hands the family's reference ``served_many``
  (``reference.JUDGED``): the same two functions over every position of
  a prompt and over 128 decode positions, which the harness's check
  does not drive and a routed model's comparison needs.

A parent of the PR that brought this family has no such module in the
program: ``register`` then fails at its import, at once.
"""

from __future__ import annotations

import dataclasses
import os
from types import SimpleNamespace
from typing import Any, Dict

#: The router's selection bias is drawn uniform in (-b, b): not zero
#: (a program that used it in the gates would go unnoticed), small
#: against the scores' spread (it tilts an expert's share of the tokens
#: by under a factor of two, as a trained balance correction does).
ROUTER_BIAS = 0.02
#: Decode positions ``served_many`` drives through the cache: 8 rows (the
#: check's own batch) x 16 teacher-forced steps. A quantile of fewer
#: than 128 positions no longer tells the control from a sound run
#: (PERF.md section 7 (f)).
JUDGED_ROWS, JUDGED_STEPS = 8, 16
#: name -> the ``tolerance`` of the configuration ``register`` was given.
_TOLERANCE: Dict[str, Dict[str, Any]] = {}


def register(name: str, config: Dict[str, Any]):
    """``config`` holds the keys of ``shapes.MODEL_KEYS`` at its top
    level: the whole configuration file, or its ``model`` block."""
    import jax.numpy as jnp

    from llmq_tpu.models import deepseek_v3

    if (config.get("scoring_func", "sigmoid") != "sigmoid"
            or config.get("n_group", 1) != 1
            or config.get("topk_group", 1) != 1
            or config.get("moe_layer_freq", 1) != 1
            or config.get("rope_scaling") is not None
            or config.get("tie_word_embeddings", False)):
        raise ValueError(f"{name}: the program's deepseek_v3 block has "
                         f"sigmoid scores, no group limit, a routed layer "
                         f"after every dense one, plain RoPE and an untied "
                         f"head")
    base = deepseek_v3.DeepseekV3Config(
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
        first_k_dense=config["first_k_dense_replace"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)
    deepseek_v3.MODEL_CONFIGS[name] = (
        lambda **kw: dataclasses.replace(base, **kw))
    if "tolerance" in config:
        _TOLERANCE[name] = config["tolerance"]
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` in the program's tree
    (``deepseek_v3.param_shapes`` / ``assemble``). Uniform in (-a, a)
    with a = sqrt(3 / fan_in) (the variance of the program's own normal
    init), the hardware generator ("rbg"); a leaf is drawn one slice of
    its leading axis at a time (a routed layer's gate-and-up leaf is
    0.8 GB: its random bits drawn at once are twice that). RMSNorm weights are
    ones; the router's selection bias is uniform in (-ROUTER_BIAS,
    ROUTER_BIAS)."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.models import deepseek_v3

    if server_model.get("quantization") or server_model.get(
            "kv_quantization"):
        deepseek_v3.check_serving(
            mcfg, quantization=server_model.get("quantization", ""),
            kv_quantization=server_model.get("kv_quantization", ""))
    shapes = deepseek_v3.param_shapes(mcfg)

    def draw(key, shape, fan_in):
        a = (3.0 / fan_in) ** 0.5

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
                               jax.random.split(k, mcfg.n_routed_layers)]
            else:
                drawn[g][n] = draw(k, *shapes[g][n])
        params = deepseek_v3.assemble(mcfg, drawn)
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
    from llmq_tpu.models.deepseek_v3 import (forward_decode, forward_prefill,
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
        from benchmark.harness import contract
        contract.load_family(
            os.path.dirname(os.path.abspath(__file__)), "reference"
        ).JUDGED = (_served_many(mcfg, server, cache), _TOLERANCE[mcfg.name])
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

    from llmq_tpu.models.deepseek_v3 import forward_decode, forward_prefill

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
