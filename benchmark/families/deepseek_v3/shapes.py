"""The DeepSeek-V3 block's shapes (latent attention, routed experts
with shared ones; ``model_type: deepseek_v3``): what a step or a kernel
call MUST move and compute, from shapes alone, and what the harness has
to know of the family to read a trace. The surface is
``families/llama/shapes.py``'s. What differs:

- the cache holds ``kv_lora_rank + qk_rope_head_dim`` values a token a
  layer (576: 1,152 B in bf16), read once by ALL heads. The program
  pads a row to 640 lanes; the padding is not counted here, so a share
  of the roofline is against the published bytes;
- a routed layer reads only the experts its batch touches: with
  ``rows`` tokens drawing ``k`` of ``E`` experts each, uniformly,
  E * (1 - (1 - k/E) ** rows) in expectation (122.1 of 128 at 64 rows,
  40.8 at 8), so the least bytes of a step depend on the rows.
  ``moe_ffn_roofline`` uses the program's measured count instead;
- ``param_count`` is the parameters HELD (all experts);
  ``active_param_count`` what one token multiplies with.

Standard library only."""

from __future__ import annotations

from typing import Dict

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "kv_lora_rank", "q_lora_rank", "qk_head_dim",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
              "first_k_dense_replace", "moe_layer_freq", "n_group",
              "topk_group", "topk_method", "scoring_func", "norm_topk_prob",
              "routed_scaling_factor", "hidden_act", "attention_bias",
              "max_position_embeddings", "rope_theta", "rope_interleave",
              "rope_scaling", "rms_norm_eps", "tie_word_embeddings")
#: The program's kernels by their names in a trace (patterns).
DECODE_ATTN = r"latent_decode_attention"
#: This family's prefill attention runs under XLA: the pattern is for
#: the kernel it does not have yet, and matches nothing.
PREFILL_ATTN = r"latent_prefill_attention_pallas"
#: The grouped product of a routed layer (``moe_ffn_roofline``): JAX's
#: megablox kernel, which a trace names ``gmm`` whatever wraps it.
MOE_FFN = r"^gmm$"


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: one a layer."""
    return model["num_hidden_layers"]


def _dims(model: Dict) -> Dict[str, int]:
    L, Ld = model["num_hidden_layers"], model["first_k_dense_replace"]
    return {"D": model["hidden_size"], "L": L, "Ld": Ld, "Lm": L - Ld,
            "H": model["num_attention_heads"], "r": model["kv_lora_rank"],
            "dn": model["qk_nope_head_dim"], "dr": model["qk_rope_head_dim"],
            "dv": model["v_head_dim"], "F": model["intermediate_size"],
            "Fe": model["moe_intermediate_size"],
            "E": model["n_routed_experts"], "k": model["num_experts_per_tok"],
            "Fs": model["n_shared_experts"] * model["moe_intermediate_size"],
            "V": model["vocab_size"]}


def attn_params(model: Dict) -> int:
    """One layer's attention matrices: W_q, W_kva, W_kvb, W_o."""
    d = _dims(model)
    return (d["D"] * d["H"] * (d["dn"] + d["dr"]) + d["D"] * (d["r"] + d["dr"])
            + d["r"] * d["H"] * (d["dn"] + d["dv"]) + d["H"] * d["dv"] * d["D"])


def expert_params(model: Dict) -> int:
    d = _dims(model)
    return 3 * d["D"] * d["Fe"]


def experts_touched(model: Dict, rows: float) -> float:
    """Distinct experts of one routed layer that ``rows`` tokens touch,
    in expectation under uniform routing."""
    d = _dims(model)
    return d["E"] * (1.0 - (1.0 - d["k"] / d["E"]) ** max(rows, 0.0))


def _once_params(model: Dict) -> int:
    """Matrices a decode step reads once whatever its rows: attention,
    the dense layers, shared experts and routers, and the head."""
    d = _dims(model)
    return (d["L"] * attn_params(model) + d["Ld"] * 3 * d["D"] * d["F"]
            + d["Lm"] * (3 * d["D"] * d["Fs"] + d["D"] * d["E"])
            + d["V"] * d["D"])


def matmul_params(model: Dict) -> int:
    """Parameters of every matrix a decode step can read: all layers
    with ALL their experts, and the head (a batch large enough touches
    every expert; ``decode_step_bytes`` counts the touched ones)."""
    d = _dims(model)
    return _once_params(model) + d["Lm"] * d["E"] * expert_params(model)


def param_count(model: Dict) -> int:
    """Parameters held: every matrix, the embedding, the norms and the
    routers' selection biases."""
    d = _dims(model)
    norms = d["L"] * (2 * d["D"] + d["r"]) + d["D"]
    return matmul_params(model) + d["V"] * d["D"] + norms + d["Lm"] * d["E"]


def active_param_count(model: Dict) -> int:
    """Parameters one token multiplies with: ``k`` of the experts."""
    d = _dims(model)
    return (param_count(model)
            - d["Lm"] * (d["E"] - d["k"]) * expert_params(model))


def kv_bytes_per_token(model: Dict, kv_itemsize: int) -> int:
    """One token's latent and RoPE key across all layers."""
    d = _dims(model)
    return d["L"] * (d["r"] + d["dr"]) * kv_itemsize


def moe_ffn_bytes(model: Dict, weight_itemsize: int,
                  touched: float) -> float:
    """One routed layer's grouped products: the touched experts' three
    matrices, read once."""
    return touched * expert_params(model) * weight_itemsize


def moe_ffn_flops(model: Dict, pairs: float) -> float:
    """... and their operations for ``pairs`` (token, expert) pairs."""
    return 2.0 * expert_params(model) * pairs


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must read: what is read once, each routed
    layer's touched experts (in expectation at ``rows``), and the
    cached latents of every token in the batch's contexts."""
    d = _dims(model)
    routed = d["Lm"] * moe_ffn_bytes(model, weight_itemsize,
                                     experts_touched(model, rows))
    return (_once_params(model) * weight_itemsize + routed
            + kv_bytes_per_token(model, kv_itemsize) * context_tokens)


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    d = _dims(model)
    return (2.0 * _once_params(model) * rows
            + d["Lm"] * moe_ffn_flops(model, rows * d["k"])
            + decode_attn_flops(model, rows, context_tokens))


def decode_attn_bytes(model: Dict, kv_itemsize: int, rows: float,
                      context_tokens: float) -> float:
    """One decode step's attention over all layers: the cached latent
    and RoPE key of every context token, read once for all heads."""
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens


def decode_attn_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """Absorbed form: a head's score contracts rank + rope values of a
    cached token and its output sums rank values, 2 operations each."""
    d = _dims(model)
    return 2.0 * d["L"] * d["H"] * (2 * d["r"] + d["dr"]) * context_tokens


def prefill_attn_flops(model: Dict, pairs: float) -> float:
    """Unabsorbed QK^T (nope + rope) and PV over ``pairs`` (query,
    visible key) pairs, all layers."""
    d = _dims(model)
    return 2.0 * d["L"] * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * pairs


def prefill_attn_bytes(model: Dict, kv_itemsize: int, new_tokens: float,
                       context_tokens: float) -> float:
    """Least traffic of prefill attention: each call reads its
    sequence's cached latents once and its q, and writes its output."""
    d = _dims(model)
    qo = 2 * d["L"] * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * new_tokens
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens + qo
