"""The Granite-4.0-H block's shapes (``granitemoehybrid`` with no routed
experts): what a step or a kernel call MUST move and compute, from
shapes alone, and what the harness has to know of the family to read a
trace. Kept with the benchmark so that no PR that claims a gain can
change the yardstick. All functions take the configuration file's
``model`` block (``MODEL_KEYS``, the public ``config.json``'s key names)
and the served dtypes. Standard library only: the parent process and the
metric readers import this and stay off JAX.

Two kinds of layer: ``layer_types`` says which of the L layers are
Mamba-2 mixers and which grouped-query attention; a shared SwiGLU sits
in every one. The attention layers alone cache K and V by token; a Mamba
layer keeps ROW STATE, as large for a row of 10 tokens as for one of
10,000 (``state_bytes_per_row``), and a decode step reads and writes
every live row's state once (``ssm_update_bytes``): at the published
sizes most of a step's bytes."""

from __future__ import annotations

from typing import Dict

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "layer_types", "num_attention_heads", "num_key_value_heads",
              "shared_intermediate_size", "num_local_experts",
              "mamba_n_heads", "mamba_d_head", "mamba_d_state",
              "mamba_n_groups", "mamba_d_conv", "mamba_expand",
              "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias",
              "embedding_multiplier", "residual_multiplier",
              "attention_multiplier", "logits_scaling",
              "position_embedding_type", "max_position_embeddings",
              "rms_norm_eps", "tie_word_embeddings")
#: The attention layers run the Llama block's kernels (their names in a
#: trace).
DECODE_ATTN = r"fused_decode_attention"
PREFILL_ATTN = r"paged_prefill_attention"
#: The recurrent state is held in float32 (the configuration's
#: ``assumed.state_dtype``), the convolution's window in bf16.
STATE_ITEMSIZE, WINDOW_ITEMSIZE = 4, 2


def _dims(model: Dict) -> Dict[str, int]:
    kinds = list(model["layer_types"])
    inner = model["mamba_n_heads"] * model["mamba_d_head"]
    return {"D": model["hidden_size"], "L": len(kinds),
            "Lm": kinds.count("mamba"), "La": kinds.count("attention"),
            "H": model["num_attention_heads"],
            "HKV": model["num_key_value_heads"],
            "hd": model["hidden_size"] // model["num_attention_heads"],
            "F": model["shared_intermediate_size"],
            "V": model["vocab_size"], "I": inner,
            "N": model["mamba_d_state"], "Hm": model["mamba_n_heads"],
            "K": model["mamba_d_conv"],
            "C": inner + 2 * model["mamba_n_groups"] * model["mamba_d_state"]}


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: one an ATTENTION
    layer."""
    return _dims(model)["La"]


def matmul_params(model: Dict) -> int:
    """Parameters every decode step reads as matrices: the SwiGLU of
    every layer, the mixers' two projections, the attention layers'
    four, and the tied head (one V x D matrix)."""
    d = _dims(model)
    mamba = d["D"] * (d["I"] + d["C"] + d["Hm"]) + d["I"] * d["D"]
    attn = 2 * d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["HKV"] * d["hd"]
    return (d["L"] * 3 * d["D"] * d["F"] + d["Lm"] * mamba + d["La"] * attn
            + d["V"] * d["D"])


def param_count(model: Dict) -> int:
    d = _dims(model)
    small = d["C"] * (d["K"] + 1) + 3 * d["Hm"] + d["I"]  # conv, dt/A/D, norm
    n = matmul_params(model) + d["Lm"] * small + d["L"] * 2 * d["D"] + d["D"]
    return n if model.get("tie_word_embeddings", True) else n + d["V"] * d["D"]


def kv_bytes_per_token(model: Dict, kv_itemsize: int) -> int:
    """K and V of one token across the ATTENTION layers: all a token
    adds to the cache."""
    d = _dims(model)
    return 2 * d["La"] * d["HKV"] * d["hd"] * kv_itemsize


def state_bytes_per_row(model: Dict) -> int:
    """What a batch row holds whatever its length: each Mamba layer's
    state (N x heads x head, float32) and its convolution's last K - 1
    inputs (bf16)."""
    d = _dims(model)
    return d["Lm"] * (d["N"] * d["I"] * STATE_ITEMSIZE
                      + (d["K"] - 1) * d["C"] * WINDOW_ITEMSIZE)


def ssm_update_bytes(model: Dict, rows: float) -> float:
    """One decode step's state update: every live row's state of every
    Mamba layer read once and written once."""
    d = _dims(model)
    return rows * d["Lm"] * 2 * d["N"] * d["I"] * STATE_ITEMSIZE


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must move: every matrix once, the cached K
    and V of every token in the batch's contexts, and the live rows'
    state in and out."""
    return (matmul_params(model) * weight_itemsize
            + kv_bytes_per_token(model, kv_itemsize) * context_tokens
            + ssm_update_bytes(model, rows))


def decode_attn_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    d = _dims(model)
    return 4.0 * d["La"] * d["H"] * d["hd"] * context_tokens


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """The products, the attention layers' scores and values, and the
    update's three multiply-adds a state value."""
    d = _dims(model)
    return (2.0 * matmul_params(model) * rows
            + decode_attn_flops(model, rows, context_tokens)
            + 6.0 * d["Lm"] * d["N"] * d["I"] * rows)


def decode_attn_bytes(model: Dict, kv_itemsize: int, rows: float,
                      context_tokens: float) -> float:
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens


def prefill_attn_flops(model: Dict, pairs: float) -> float:
    d = _dims(model)
    return 4.0 * d["La"] * d["H"] * d["hd"] * pairs


def prefill_attn_bytes(model: Dict, kv_itemsize: int, new_tokens: float,
                       context_tokens: float) -> float:
    d = _dims(model)
    qo = 2 * d["La"] * d["H"] * d["hd"] * 2 * new_tokens
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens + qo
