"""The ``xing4_0`` block's shapes (Xing4.0-29B-A4B: latent attention
with a low-rank query, routed experts beside a shared one, a residual
of ``hc_mult`` streams mixed by hyper-connections): what a step or a
kernel call MUST move and compute, from shapes alone, and what the
harness has to know of the family to read a trace. The surface is
``families/deepseek_v3/shapes.py``'s. What differs:

- ``num_hidden_layers`` is the layers HELD and ``dense_layers_held``
  the dense ones that lead them (a file that cuts the depth keeps the
  published ``first_k_dense_replace``): ``param_count`` of the block
  with 40 and 2 there is the whole model's 29.5 B;
- a SITE (two a layer) holds ``hc_mult C (2 hc_mult + hc_mult ** 2) +
  3 + 2 hc_mult + hc_mult ** 2`` float32 parameters (344,091), and must
  read a token's float32 streams and write them (``hc_mix_bytes``: the
  least; the program reads them twice);
- ``latent_prefill_bytes`` / ``_flops``: the prefill attention's work
  over LIVE keys (every slice's queries against its own context's
  keys), expanded as the program expands them: K and V of a key from
  its latent, then the scores and the sums. The multi-token-prediction
  layer is not held and not counted.

Standard library only."""

from __future__ import annotations

from typing import Dict

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "dense_layers_held", "num_attention_heads",
              "num_key_value_heads", "kv_lora_rank", "q_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
              "first_k_dense_replace", "moe_layer_freq", "n_group",
              "topk_group", "topk_method", "scoring_func", "norm_topk_prob",
              "routed_scaling_factor", "hidden_act", "attention_bias",
              "max_position_embeddings", "rope_theta", "rope_scaling",
              "rms_norm_eps", "tie_word_embeddings", "ep_size",
              "num_nextn_predict_layers", "hc_mult", "hc_sinkhorn_iters",
              "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
              "model_type")
#: The program's kernels by their names in a trace (patterns).
DECODE_ATTN = r"latent_decode_attention"
#: This family's prefill attention runs under XLA: the pattern is for
#: the kernel it does not have yet, and matches nothing
#: (``latent_prefill_roofline`` reads the SCOPE).
PREFILL_ATTN = r"latent_prefill_attention_pallas"
#: The grouped product of a routed layer (``moe_ffn_roofline``).
MOE_FFN = r"^gmm$"
#: Bytes of a value of the streams and of a site's parameters.
STREAM_ITEMSIZE = 4


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: one a layer."""
    return model["num_hidden_layers"]


def _dims(model: Dict) -> Dict[str, int]:
    L = model["num_hidden_layers"]
    Ld = model.get("dense_layers_held", model["first_k_dense_replace"])
    return {"D": model["hidden_size"], "L": L, "Ld": Ld, "Lm": L - Ld,
            "H": model["num_attention_heads"], "r": model["kv_lora_rank"],
            "rq": model["q_lora_rank"],
            "dn": model["qk_nope_head_dim"], "dr": model["qk_rope_head_dim"],
            "dv": model["v_head_dim"], "F": model["intermediate_size"],
            "Fe": model["moe_intermediate_size"],
            "E": model["n_routed_experts"], "k": model["num_experts_per_tok"],
            "Fs": model["n_shared_experts"] * model["moe_intermediate_size"],
            "V": model["vocab_size"], "n": model["hc_mult"]}


def attn_params(model: Dict) -> int:
    """One layer's attention matrices: W_qa, W_qb, W_kva, W_kvb, W_o."""
    d = _dims(model)
    return (d["D"] * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["r"] + d["dr"])
            + d["r"] * d["H"] * (d["dn"] + d["dv"]) + d["H"] * d["dv"] * d["D"])


def expert_params(model: Dict) -> int:
    d = _dims(model)
    return 3 * d["D"] * d["Fe"]


def site_params(model: Dict) -> int:
    """One hyper-connection site: Phi, three scalars, the biases."""
    d = _dims(model)
    m = 2 * d["n"] + d["n"] ** 2
    return d["n"] * d["D"] * m + 3 + m


def experts_touched(model: Dict, rows: float) -> float:
    """Distinct experts of one routed layer that ``rows`` tokens touch,
    in expectation under uniform routing."""
    d = _dims(model)
    return d["E"] * (1.0 - (1.0 - d["k"] / d["E"]) ** max(rows, 0.0))


def _once_params(model: Dict) -> int:
    """bf16 matrices a decode step reads once whatever its rows:
    attention, the dense layers, shared experts and routers, the head."""
    d = _dims(model)
    return (d["L"] * attn_params(model) + d["Ld"] * 3 * d["D"] * d["F"]
            + d["Lm"] * (3 * d["D"] * d["Fs"] + d["D"] * d["E"])
            + d["V"] * d["D"])


def matmul_params(model: Dict) -> int:
    """Parameters of every matrix a decode step can read: all layers
    with ALL their experts and both sites' Phi, and the head."""
    d = _dims(model)
    return (_once_params(model) + d["Lm"] * d["E"] * expert_params(model)
            + 2 * d["L"] * site_params(model))


def param_count(model: Dict) -> int:
    """Parameters held: every matrix, the sites, the embedding, the
    norms and the routers' selection biases."""
    d = _dims(model)
    norms = d["L"] * (2 * d["D"] + d["r"] + d["rq"]) + d["D"]
    return matmul_params(model) + d["V"] * d["D"] + norms + d["Lm"] * d["E"]


def active_param_count(model: Dict) -> int:
    """Parameters one token multiplies with: ``k`` of the experts."""
    d = _dims(model)
    return (param_count(model)
            - d["Lm"] * (d["E"] - d["k"]) * expert_params(model))


def weight_bytes(model: Dict, weight_itemsize: int) -> int:
    """The held parameters in the served types: the sites float32."""
    sites = 2 * _dims(model)["L"] * site_params(model)
    return ((param_count(model) - sites) * weight_itemsize
            + sites * STREAM_ITEMSIZE)


def kv_bytes_per_token(model: Dict, kv_itemsize: int) -> int:
    """One token's latent and RoPE key across the layers held."""
    d = _dims(model)
    return d["L"] * (d["r"] + d["dr"]) * kv_itemsize


def moe_ffn_bytes(model: Dict, weight_itemsize: int,
                  touched: float) -> float:
    return touched * expert_params(model) * weight_itemsize


def moe_ffn_flops(model: Dict, pairs: float) -> float:
    return 2.0 * expert_params(model) * pairs


def hc_mix_bytes(model: Dict, tokens: float) -> float:
    """Every site of ONE pass over ``tokens`` rows, the LEAST it can
    move: the float32 streams read once and written once a site (the
    program reads them a second time, to project before the sub-layer
    whose output the mix waits for; a site fused with the one before it
    would project from what that one still holds), and Phi once a
    site. The sub-layer's input and output are the sub-layer's."""
    d = _dims(model)
    streams = 2 * d["n"] * d["D"] * STREAM_ITEMSIZE
    return 2 * d["L"] * (tokens * streams
                         + site_params(model) * STREAM_ITEMSIZE)


def hc_mix_flops(model: Dict, tokens: float) -> float:
    """... and its operations: the flattened norm (3 a value), Phi's
    product, the read (2 n a value of C) and the write (2 n n + 2 n);
    the Sinkhorn steps are a few hundred a token and are left out."""
    d = _dims(model)
    n, D = d["n"], d["D"]
    m = 2 * n + n * n
    return 2 * d["L"] * tokens * (3 * n * D + 2 * n * D * m + 2 * n * D
                                  + (2 * n * n + 2 * n) * D)


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must read: what is read once, each routed
    layer's touched experts (in expectation at ``rows``), the sites'
    traffic and the cached latents of the batch's contexts."""
    d = _dims(model)
    routed = d["Lm"] * moe_ffn_bytes(model, weight_itemsize,
                                     experts_touched(model, rows))
    return (_once_params(model) * weight_itemsize + routed
            + hc_mix_bytes(model, rows)
            + kv_bytes_per_token(model, kv_itemsize) * context_tokens)


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    d = _dims(model)
    return (2.0 * _once_params(model) * rows
            + d["Lm"] * moe_ffn_flops(model, rows * d["k"])
            + hc_mix_flops(model, rows)
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


def latent_prefill_flops(model: Dict, keys: float, queries: float,
                         pairs: float) -> float:
    """ONE layer's prefill attention over live work alone, the fewer of
    two forms' operations. EXPANDED, as the program computes it: K and V
    of ``keys`` live keys from their latents (each slice expands its own
    context's), then scores and sums of ``pairs`` (query, visible key)
    pairs. ABSORBED, as decode does: the queries through W_kvb's key
    half and the outputs back through its value half, and ``rank + rope``
    and ``rank`` wide products a pair. (512 queries a slice: expanded is
    the fewer by half.)"""
    d = _dims(model)
    expanded = (2.0 * d["r"] * d["H"] * (d["dn"] + d["dv"]) * keys
                + 2.0 * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * pairs)
    absorbed = (2.0 * d["r"] * d["H"] * (d["dn"] + d["dv"]) * queries
                + 2.0 * d["H"] * (2 * d["r"] + d["dr"]) * pairs)
    return min(expanded, absorbed)


def latent_prefill_bytes(model: Dict, kv_itemsize: int, keys: float,
                         queries: float) -> float:
    """... and its least traffic: the live keys' cached rows once, the
    queries in and the outputs out (2 bytes a value)."""
    d = _dims(model)
    return ((d["r"] + d["dr"]) * kv_itemsize * keys
            + 2 * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * queries)
