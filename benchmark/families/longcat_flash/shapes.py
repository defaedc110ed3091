"""The LongCat-Flash block's shapes (a shortcut-connected double
layer: two latent attentions, two dense SwiGLUs and a routed layer with
zero-compute experts; ``model_type: longcat_flash``), for a chip that
holds a SHARE of each routed layer's experts and of the vocabulary:
what a step or a kernel call MUST move and compute here, from shapes
alone, and what the harness has to know of the family to read a trace.
The surface is ``families/llama/shapes.py``'s. What differs from
``families/deepseek_v3/shapes.py``:

- a layer has TWO attentions, each with a cache row of its own
  (``kv_lora_rank + qk_rope_head_dim`` values, 1,152 B in bf16, read
  once by all heads), so ``attn_calls_per_step`` is twice the layers
  and a cached token costs twice a layer's row. The query is low-rank
  (``q_lora_rank``);
- ``n_routed_experts`` is what this chip HOLDS of the router's
  ``router_experts`` real experts; the router has ``router_experts +
  zero_expert_num`` outputs and a token draws ``moe_topk`` of them, so
  of a step's ``rows * moe_topk`` slots the share held / outputs falls
  on a held expert (uniformly: 32 of 1,536 at 128 rows with 16 of 768),
  a third on zero-compute experts, which cost no bytes, and the rest
  on experts another chip holds, which cost nothing HERE;
- the dense parts are whole: both SwiGLUs, both attentions and the
  router are data-parallel in the deployment, and the head is this
  chip's slice of the vocabulary;
- ``param_count`` is the parameters HELD here; ``active_param_count``
  what one token multiplies with here, in expectation.

Standard library only."""

from __future__ import annotations

from typing import Dict, Tuple

MODEL_KEYS = ("attention_bias", "vocab_size", "hidden_size",
              "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
              "num_attention_heads", "kv_lora_rank", "q_lora_rank",
              "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim",
              "mla_scale_q_lora", "mla_scale_kv_lora",
              "routed_scaling_factor", "n_routed_experts",
              "max_position_embeddings", "rms_norm_eps", "rope_theta",
              "attention_method", "zero_expert_num", "zero_expert_type",
              "moe_topk", "router_experts", "expert_share")
#: The program's kernels by their names in a trace (patterns).
DECODE_ATTN = r"latent_decode_attention"
#: This family's prefill attention runs under XLA: the pattern is for
#: the kernel it does not have yet, and matches nothing.
PREFILL_ATTN = r"latent_prefill_attention_pallas"
#: The grouped product of a routed layer (``moe_ffn_roofline``): JAX's
#: megablox kernel, which a trace names ``gmm`` whatever wraps it.
MOE_FFN = r"^gmm$"


def held_experts(model: Dict) -> Tuple[int, int]:
    """(first, end) of the router's experts this chip holds: share
    ``index`` of ``chips`` equal shares of ``router_experts``."""
    share, n = model["expert_share"], model["n_routed_experts"]
    if share["chips"] * n != model["router_experts"]:
        raise ValueError(f"{share['chips']} shares of {n} experts are not "
                         f"the router's {model['router_experts']}")
    return share["index"] * n, (share["index"] + 1) * n


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: two a layer."""
    return 2 * model["num_layers"]


def _dims(model: Dict) -> Dict[str, int]:
    L = model["num_layers"]
    return {"D": model["hidden_size"], "L": L, "A": 2 * L,
            "H": model["num_attention_heads"], "r": model["kv_lora_rank"],
            "rq": model["q_lora_rank"], "dn": model["qk_nope_head_dim"],
            "dr": model["qk_rope_head_dim"], "dv": model["v_head_dim"],
            "F": model["ffn_hidden_size"],
            "Fe": model["expert_ffn_hidden_size"],
            "Eh": model["n_routed_experts"],
            "R": model["router_experts"] + model["zero_expert_num"],
            "k": model["moe_topk"], "V": model["vocab_size"]}


def attn_params(model: Dict) -> int:
    """One attention's matrices: W_qa, W_qb, W_kva, W_kvb, W_o."""
    d = _dims(model)
    return (d["D"] * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["r"] + d["dr"])
            + d["r"] * d["H"] * (d["dn"] + d["dv"]) + d["H"] * d["dv"] * d["D"])


def expert_params(model: Dict) -> int:
    d = _dims(model)
    return 3 * d["D"] * d["Fe"]


def held_slot_share(model: Dict) -> float:
    """The share of a token's slots that falls on an expert held here,
    under uniform routing."""
    d = _dims(model)
    return d["Eh"] / d["R"]


def experts_touched(model: Dict, rows: float) -> float:
    """Distinct HELD experts of one routed layer that ``rows`` tokens
    touch, in expectation under uniform routing: a token draws k
    distinct of the router's outputs, so it misses a given one with
    1 - k / outputs (13.9 of 16 at 128 rows)."""
    d = _dims(model)
    return d["Eh"] * (1.0 - (1.0 - d["k"] / d["R"]) ** max(rows, 0.0))


def _once_params(model: Dict) -> int:
    """Matrices a decode step reads once whatever its rows: both
    attentions and both dense SwiGLUs of every layer, the routers, and
    the head's slice."""
    d = _dims(model)
    return (d["A"] * (attn_params(model) + 3 * d["D"] * d["F"])
            + d["L"] * d["D"] * d["R"] + d["V"] * d["D"])


def matmul_params(model: Dict) -> int:
    """Parameters of every matrix a decode step can read here: all
    layers with all their HELD experts, and the head's slice."""
    d = _dims(model)
    return _once_params(model) + d["L"] * d["Eh"] * expert_params(model)


def param_count(model: Dict) -> int:
    """Parameters held: every matrix, the embedding's slice, the norms
    (four a layer, one inside each query and each latent, the final
    one) and the routers' selection biases."""
    d = _dims(model)
    norms = d["A"] * (2 * d["D"] + d["r"] + d["rq"]) + d["D"]
    return matmul_params(model) + d["V"] * d["D"] + norms + d["L"] * d["R"]


def active_param_count(model: Dict) -> int:
    """Parameters one token multiplies with here, in expectation: of
    the held experts the k * held / outputs its slots fall on."""
    d = _dims(model)
    idle = d["Eh"] - d["k"] * held_slot_share(model)
    return int(param_count(model) - d["L"] * idle * expert_params(model))


def kv_bytes_per_token(model: Dict, kv_itemsize: int) -> int:
    """One token's latent and RoPE key in both attentions of all
    layers."""
    d = _dims(model)
    return d["A"] * (d["r"] + d["dr"]) * kv_itemsize


def moe_ffn_bytes(model: Dict, weight_itemsize: int,
                  touched: float) -> float:
    """One routed layer's grouped products: the touched held experts'
    three matrices, read once."""
    return touched * expert_params(model) * weight_itemsize


def moe_ffn_flops(model: Dict, pairs: float) -> float:
    """... and their operations for ``pairs`` (token, held expert)
    pairs."""
    return 2.0 * expert_params(model) * pairs


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must read: what is read once, each routed
    layer's touched held experts (in expectation at ``rows``), and the
    cached latents of every token in the batch's contexts."""
    d = _dims(model)
    routed = d["L"] * moe_ffn_bytes(model, weight_itemsize,
                                    experts_touched(model, rows))
    return (_once_params(model) * weight_itemsize + routed
            + kv_bytes_per_token(model, kv_itemsize) * context_tokens)


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    d = _dims(model)
    pairs = rows * d["k"] * held_slot_share(model)
    return (2.0 * _once_params(model) * rows
            + d["L"] * moe_ffn_flops(model, pairs)
            + decode_attn_flops(model, rows, context_tokens))


def decode_attn_bytes(model: Dict, kv_itemsize: int, rows: float,
                      context_tokens: float) -> float:
    """One decode step's attention over both attentions of all layers:
    the cached latent and RoPE key of every context token, read once
    for all heads."""
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens


def decode_attn_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """Absorbed form: a head's score contracts rank + rope values of a
    cached token and its output sums rank values, 2 operations each."""
    d = _dims(model)
    return 2.0 * d["A"] * d["H"] * (2 * d["r"] + d["dr"]) * context_tokens


def prefill_attn_flops(model: Dict, pairs: float) -> float:
    """Unabsorbed QK^T (nope + rope) and PV over ``pairs`` (query,
    visible key) pairs, both attentions of all layers."""
    d = _dims(model)
    return 2.0 * d["A"] * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * pairs


def prefill_attn_bytes(model: Dict, kv_itemsize: int, new_tokens: float,
                       context_tokens: float) -> float:
    """Least traffic of prefill attention: each call reads its
    sequence's cached latents once and its q, and writes its output."""
    d = _dims(model)
    qo = 2 * d["A"] * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * new_tokens
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens + qo
