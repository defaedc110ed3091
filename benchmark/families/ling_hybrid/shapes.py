"""The Ling-3.0-flash block's shapes (delta-rule linear attention, KDA,
as ROW STATE beside one latent attention in ``layer_group_size`` over a
latent page pool; a dense SwiGLU in the leading layers and after them a
group-limited sigmoid-routed layer beside a shared expert), for a chip
that holds a SHARE of each routed layer's experts and of the vocabulary:
what a step or a kernel call MUST move and compute here, from shapes
alone, and what the harness has to know of the family to read a trace.
The surface is ``families/llama/shapes.py``'s, with
``families/afmoe``'s share and ``families/granitemoehybrid``'s row state:

- ``num_hidden_layers`` layers are HELD: the first ``dense_layers_held``
  with the dense SwiGLU (``intermediate_size``), the rest routed; layer
  ``l`` is a latent attention if ``(l + 1) % layer_group_size == 0``,
  else KDA;
- ``num_experts`` is what this chip HOLDS of the router's
  ``router_experts``; a token draws ``num_experts_per_tok`` of the
  router's outputs, so of a step's ``rows * k`` slots the share held /
  outputs falls on a held expert (128 of 512 here);
- both mixers, the dense and the shared SwiGLUs and the router are whole
  (data-parallel in the deployment); the head is this chip's slice of
  the vocabulary;
- the latent layers alone cache by token (``kv_bytes_per_token``); a KDA
  layer keeps ROW STATE, as large for a row of 10 tokens as for one of
  10,000 (``state_bytes_per_row``), and a decode step reads and writes
  every live row's state once (``ssm_update_bytes``: the name the
  accepted ``ssm_update_roofline`` asks a family's shapes for — the
  recurrent mixer stands under the scopes' ROLE names).

Standard library only."""

from __future__ import annotations

from typing import Dict, Tuple

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "num_hidden_layers", "first_k_dense_replace",
              "dense_layers_held", "layer_group_size", "num_attention_heads",
              "num_key_value_heads", "num_kv_heads_for_linear_attn",
              "head_dim", "short_conv_kernel_size", "group_norm_size",
              "linear_silu", "no_kda_lora", "use_kda_lora", "kda_safe_gate",
              "kda_lower_bound", "use_qk_norm", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rotary_dim", "partial_rotary_factor", "use_mla_nope",
              "gated_attention_proj_granularity_type", "num_experts",
              "num_experts_per_tok", "n_group", "topk_group",
              "score_function", "moe_router_enable_expert_bias",
              "routed_scaling_factor", "norm_topk_prob", "scale_router_input",
              "expert_swiglu_limit_list", "share_expert_swiglu_limit_list",
              "max_position_embeddings", "rope_theta", "rms_norm_eps",
              "router_experts", "expert_share")
#: The program's kernels by their names in a trace (patterns): the
#: latent layers call ``models/latent.py``'s decode kernel.
DECODE_ATTN = r"latent_decode_attention"
#: The latent layers' prefill attention runs under XLA: the pattern is
#: for the kernel it does not have, and matches nothing.
PREFILL_ATTN = r"latent_prefill_attention_pallas"
#: The grouped product of a routed layer: JAX's megablox kernel.
MOE_FFN = r"^gmm$"
#: The KDA state is held in float32, the convolution's window in bf16.
STATE_ITEMSIZE, WINDOW_ITEMSIZE = 4, 2


def held_experts(model: Dict) -> Tuple[int, int]:
    """(first, end) of the router's experts this chip holds: share
    ``index`` of ``chips`` equal shares of ``router_experts``."""
    share, n = model["expert_share"], model["num_experts"]
    if share["chips"] * n != model["router_experts"]:
        raise ValueError(f"{share['chips']} shares of {n} experts are not "
                         f"the router's {model['router_experts']}")
    return share["index"] * n, (share["index"] + 1) * n


def layer_kinds(model: Dict) -> Tuple[int, int]:
    """(KDA, latent) layers among the layers held."""
    L, g = model["num_hidden_layers"], model["layer_group_size"]
    latent = sum(1 for l in range(L) if (l + 1) % g == 0)
    return L - latent, latent


def dense_layers(model: Dict) -> int:
    """Held layers with the dense SwiGLU (the rest are routed)."""
    return min(model.get("dense_layers_held", model["first_k_dense_replace"]),
               model["num_hidden_layers"])


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: one a LATENT layer."""
    return layer_kinds(model)[1]


def _dims(model: Dict) -> Dict[str, int]:
    L, Ld = model["num_hidden_layers"], dense_layers(model)
    Lk, Ll = layer_kinds(model)
    H, d = model["num_attention_heads"], model["head_dim"]
    return {"D": model["hidden_size"], "L": L, "Ld": Ld, "Lm": L - Ld,
            "Lk": Lk, "Ll": Ll, "H": H, "d": d, "W": H * d,
            "K": model["short_conv_kernel_size"],
            "r": model["kv_lora_rank"], "dn": model["qk_nope_head_dim"],
            "dr": model["qk_rope_head_dim"], "dv": model["v_head_dim"],
            "F": model["intermediate_size"],
            "Fe": model["moe_intermediate_size"],
            "Fs": model["moe_shared_expert_intermediate_size"],
            "Eh": model["num_experts"], "R": model["router_experts"],
            "k": model["num_experts_per_tok"], "V": model["vocab_size"]}


def kda_params(model: Dict) -> int:
    """One KDA layer's matrices: W_q, W_k, W_v, the decay's W_f, W_beta,
    the output gate's W_g, W_o, and the convolution's taps."""
    d = _dims(model)
    return d["D"] * (6 * d["W"] + d["H"]) + 3 * d["W"] * d["K"]


def latent_params(model: Dict) -> int:
    """One latent attention's matrices: W_q, W_kva, W_kvb, the head-wise
    gate, W_o."""
    d = _dims(model)
    return (d["D"] * d["H"] * (d["dn"] + d["dr"]) + d["D"] * (d["r"] + d["dr"])
            + d["r"] * d["H"] * (d["dn"] + d["dv"]) + d["D"] * d["H"]
            + d["H"] * d["dv"] * d["D"])


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
    1 - k / outputs (111 of 128 at 128 rows). The group limit keeps a
    token to half the groups and does not move that expectation."""
    d = _dims(model)
    return d["Eh"] * (1.0 - (1.0 - d["k"] / d["R"]) ** max(rows, 0.0))


def _once_params(model: Dict) -> int:
    """Matrices a decode step reads once whatever its rows: every
    layer's mixer, the dense SwiGLUs, the routed layers' routers and
    shared experts, and the head's slice."""
    d = _dims(model)
    return (d["Lk"] * kda_params(model) + d["Ll"] * latent_params(model)
            + d["Ld"] * 3 * d["D"] * d["F"]
            + d["Lm"] * (d["D"] * d["R"] + 3 * d["D"] * d["Fs"])
            + d["V"] * d["D"])


def matmul_params(model: Dict) -> int:
    """Parameters of every matrix a decode step can read here: all
    layers with all their HELD experts, and the head's slice."""
    d = _dims(model)
    return _once_params(model) + d["Lm"] * d["Eh"] * expert_params(model)


def param_count(model: Dict) -> int:
    """Parameters held: every matrix, the embedding's slice, the norms
    (two a layer, the final one, a KDA layer's over a head, a latent
    layer's over the latent), the decay's ``A_log`` and ``b_f`` and the
    routers' selection biases."""
    d = _dims(model)
    small = (d["L"] * 2 * d["D"] + d["D"]
             + d["Lk"] * (d["d"] + d["H"] + d["W"]) + d["Ll"] * d["r"]
             + d["Lm"] * d["R"])
    return matmul_params(model) + d["V"] * d["D"] + small


def active_param_count(model: Dict) -> int:
    """Parameters one token multiplies with here, in expectation: of
    the held experts the k * held / outputs its slots fall on."""
    d = _dims(model)
    idle = d["Eh"] - d["k"] * held_slot_share(model)
    return int(param_count(model) - d["Lm"] * idle * expert_params(model))


def kv_bytes_per_token(model: Dict, kv_itemsize: int) -> int:
    """What one cached token adds to the page pool: the latent and the
    RoPE key in the LATENT layers (a KDA layer's state is a row's)."""
    d = _dims(model)
    return d["Ll"] * (d["r"] + d["dr"]) * kv_itemsize


def state_bytes_per_row(model: Dict) -> int:
    """What one batch row's KDA layers hold, whatever its context: the
    float32 state and the convolution's window."""
    d = _dims(model)
    return d["Lk"] * (d["d"] * d["W"] * STATE_ITEMSIZE
                      + (d["K"] - 1) * 3 * d["W"] * WINDOW_ITEMSIZE)


def ssm_update_bytes(model: Dict, rows: float) -> float:
    """One decode step's state update: every live row's state of every
    KDA layer read once and written once (the delta rule reads the
    state it writes; both visits are of one copy in fast memory)."""
    d = _dims(model)
    return rows * d["Lk"] * 2 * d["d"] * d["W"] * STATE_ITEMSIZE


def moe_ffn_bytes(model: Dict, weight_itemsize: int,
                  touched: float) -> float:
    """One routed layer's grouped products: the touched held experts'
    three matrices, read once."""
    return touched * expert_params(model) * weight_itemsize


def moe_ffn_flops(model: Dict, pairs: float) -> float:
    """... and their operations for ``pairs`` (token, held expert)
    pairs."""
    return 2.0 * expert_params(model) * pairs


def decode_attn_bytes(model: Dict, kv_itemsize: int, rows: float,
                      context_tokens: float) -> float:
    """One decode step's attention: the latent layers' cached latent and
    RoPE key of every context token, read once for all heads."""
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens


def decode_attn_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """Absorbed form: a head's score contracts rank + rope values of a
    cached token and its output sums rank values, 2 operations each."""
    d = _dims(model)
    return 2.0 * d["Ll"] * d["H"] * (2 * d["r"] + d["dr"]) * context_tokens


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must move: what is read once, each routed
    layer's touched held experts (in expectation at ``rows``), the
    latent layers' cached rows, and the live rows' KDA state in and
    out."""
    d = _dims(model)
    routed = d["Lm"] * moe_ffn_bytes(model, weight_itemsize,
                                     experts_touched(model, rows))
    return (_once_params(model) * weight_itemsize + routed
            + decode_attn_bytes(model, kv_itemsize, rows, context_tokens)
            + ssm_update_bytes(model, rows))


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """The products, the latent layers' scores and values, and the
    delta rule's four multiply-adds a state value."""
    d = _dims(model)
    pairs = rows * d["k"] * held_slot_share(model)
    return (2.0 * _once_params(model) * rows
            + d["Lm"] * moe_ffn_flops(model, pairs)
            + decode_attn_flops(model, rows, context_tokens)
            + 8.0 * d["Lk"] * d["d"] * d["W"] * rows)


def prefill_attn_flops(model: Dict, pairs: float) -> float:
    """Unabsorbed QK^T (nope + rope) and PV over ``pairs`` (query,
    visible key) pairs in the latent layers."""
    d = _dims(model)
    return 2.0 * d["Ll"] * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * pairs


def prefill_attn_bytes(model: Dict, kv_itemsize: int, new_tokens: float,
                       context_tokens: float) -> float:
    """Least traffic of the latent layers' prefill attention: each call
    reads its sequence's cached latents once and its q, and writes its
    output."""
    d = _dims(model)
    qo = 2 * d["Ll"] * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * new_tokens
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens + qo
