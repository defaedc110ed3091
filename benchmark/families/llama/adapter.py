"""The Llama block in the program: the one file of the family that
imports ``llmq_tpu``. The harness (``harness/child.py``) asks it for

- ``register(name, config)``: the configuration file (Hugging Face keys
  at its top level) as one more entry of the program's
  ``MODEL_CONFIGS`` — the program is not edited;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served type for ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through a paged cache, with the kernels the served
  programs route to.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace
from typing import Any, Dict


def register(name: str, config: Dict[str, Any]):
    """``config`` holds the keys of ``shapes.MODEL_KEYS`` at its top
    level: the whole configuration file, or its ``model`` block."""
    import jax.numpy as jnp

    from llmq_tpu.models import llama

    hd = config.get("head_dim") or (config["hidden_size"]
                                    // config["num_attention_heads"])
    if hd * config["num_attention_heads"] != config["hidden_size"]:
        raise ValueError("the program derives head_dim as hidden/heads; "
                         f"{name} has head_dim {hd}")
    base = llama.LlamaConfig(
        name=name, vocab_size=config["vocab_size"],
        dim=config["hidden_size"], n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16,
        tie_embeddings=bool(config.get("tie_word_embeddings", False)))
    llama.MODEL_CONFIGS[name] = lambda **kw: dataclasses.replace(base, **kw)
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` for the served type that the
    configuration's ``server.model`` block states.
    Uniform in (-a, a) with a = sqrt(3 / fan_in)
    (the variance of the program's own normal init); the hardware
    generator ("rbg"), because the default counter-based one costs
    tens of seconds at 7 B. int8 leaves are made as int8: q uniform
    bytes in [-127, 127] and one scale per output channel, in the
    program's ``{"q", "s"}`` layout (ops/quant.py)."""
    import jax
    import jax.numpy as jnp

    quantized = server_model.get("quantization") == "int8"
    L, D, H, HKV, F, V = (mcfg.n_layers, mcfg.dim, mcfg.n_heads,
                          mcfg.n_kv_heads, mcfg.ffn_dim, mcfg.vocab_size)
    hd = mcfg.head_dim
    shapes = {"wq": ((L, D, H * hd), D), "wk": ((L, D, HKV * hd), D),
              "wv": ((L, D, HKV * hd), D), "wo": ((L, H * hd, D), H * hd),
              "w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
              "w_down": ((L, F, D), F)}

    def dense(key, shape, fan_in):
        a = (3.0 / fan_in) ** 0.5
        return jax.random.uniform(key, shape, jnp.bfloat16, -a, a)

    def quant(key, shape, fan_in, axis):
        # value = q * s; q uniform int8, so std(q) = 127/sqrt(3) and
        # s = a / 127 gives the same variance as ``dense``. Random
        # BYTES, one layer at a time: a stacked 7 B leaf drawn at once
        # as 32-bit integers does not fit beside the rest (14 GB).
        a = (3.0 / fan_in) ** 0.5

        def one(k, shp):
            bits = jax.random.bits(k, shp, jnp.uint8)
            return jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8),
                               jnp.int8(-127))

        if len(shape) == 3:
            q = jax.lax.map(lambda k: one(k, shape[1:]),
                            jax.random.split(key, shape[0]))
        else:
            q = one(key, shape)
        sshape = list(shape)
        sshape[axis] = 1
        return {"q": q, "s": jnp.full(sshape, a / 127.0, jnp.float32)}

    def build(key):
        keys = jax.random.split(key, len(shapes) + 2)
        mk = ((lambda k, s, f: quant(k, s, f, -2)) if quantized else dense)
        layers = {n: mk(keys[i], s, f)
                  for i, (n, (s, f)) in enumerate(shapes.items())}
        layers["attn_norm"] = jnp.ones((L, D), jnp.bfloat16)
        layers["mlp_norm"] = jnp.ones((L, D), jnp.bfloat16)
        params = {"layers": layers,
                  "final_norm": jnp.ones((D,), jnp.bfloat16)}
        if quantized:
            params["embed"] = quant(keys[-2], (V, D), D, -1)
        else:
            params["embed"] = dense(keys[-2], (V, D), D)
        if not mcfg.tie_embeddings:
            params["lm_head"] = mk(keys[-1], (D, V), D)
        return params

    return build


def serving_path(mcfg, server: Dict[str, Any]) -> SimpleNamespace:
    """The serving path's model functions at the configuration's
    ``server`` block: ``cache(n)`` a paged cache of ``n`` pages in the
    served KV type, ``prefill`` (last position's logits) and ``decode``
    as the served programs call them, ``ident`` the string that
    identifies what they trace, ``vocab_size`` of the logits."""
    import jax.numpy as jnp

    from llmq_tpu.models.llama import (forward_decode, forward_prefill,
                                       init_kv_pages)

    page_size = int(server["executor"]["page_size"])
    kv_int8 = server["model"].get("kv_quantization") == "int8"
    cfg = dataclasses.replace(mcfg, pallas_batched_prefill=True)

    def cache(n_pages: int):
        return init_kv_pages(cfg, n_pages, page_size,
                             dtype=jnp.int8 if kv_int8 else None)

    def prefill(params, cache, tokens, positions, lens, bts):
        return forward_prefill(params, cfg, tokens, positions, lens, cache,
                               bts, last_only=True)

    def decode(params, cache, tokens, positions, bts, active):
        return forward_decode(params, cfg, tokens, positions, cache, bts,
                              active=active)

    return SimpleNamespace(cache=cache, prefill=prefill, decode=decode,
                           ident=str(cfg), vocab_size=cfg.vocab_size)
