"""The ZAYA1 block's shapes (compressed convolutional attention — a plain
grouped-query attention INSIDE a compressed space, K and V in the page
pool after the mix, beside a short tail as row state on the same layer —
and a top-1 routed SwiGLU behind a router network, every layer): what a
step or a kernel call MUST move and compute, from shapes alone, and what
the harness has to know of the family to read a trace. The surface is
``families/llama/shapes.py``'s, with the routed families' additions
(``MOE_FFN``, ``moe_ffn_bytes`` / ``_flops``, ``experts_touched``):

- every layer held is whole on this chip: the four compressed
  projections, the two convolutions, ``W_o``, the router network, all
  ``num_experts`` experts; a token runs ``num_experts_per_tok`` of them
  (1), so a decode step reads the matrices of the experts its rows
  TOUCH, each once;
- a token adds K and V of ``num_key_value_heads * head_dim`` to every
  layer's pages (``kv_bytes_per_token``); the tail is ROW STATE, as
  large for a row of 10 tokens as for one of 10,000
  (``state_bytes_per_row``), read and written once a step a live row;
- the head is the embedding (tied): its matrix is read once a step and
  counted once in ``param_count``.

Standard library only."""

from __future__ import annotations

from typing import Dict

MODEL_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "cca_time0", "cca_time1", "partial_rotary_factor",
              "rope_parameters",
              "num_experts", "num_experts_per_tok", "moe_intermediate_size",
              "router_hidden_size", "rms_norm_eps", "tie_word_embeddings",
              "max_position_embeddings", "sliding_window")
#: The program's kernels by their names in a trace (patterns): after the
#: mix this is the Llama block's attention, through the same kernels.
DECODE_ATTN = r"fused_decode_attention(_q8)?_pallas"
PREFILL_ATTN = r"paged_prefill_attention(_q8)?_pallas"
#: The grouped product of a routed layer: JAX's megablox kernel.
MOE_FFN = r"^gmm$"
#: The tail is held in float32.
STATE_ITEMSIZE = 4


def rope_theta(model: Dict) -> float:
    """The rotation's base for the ``hybrid`` layers (the published
    file gives it a kind of layer, under ``rope_parameters``)."""
    return float(model["rope_parameters"]["hybrid"]["rope_theta"])


def rotary_dim(model: Dict) -> int:
    """Values of a head that are rotated: the FIRST of them."""
    return int(round(model["head_dim"] * float(
        model["rope_parameters"]["hybrid"]["partial_rotary_factor"])))


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: one a layer."""
    return model["num_hidden_layers"]


def _dims(model: Dict) -> Dict[str, int]:
    H, G, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    return {"D": model["hidden_size"], "L": model["num_hidden_layers"],
            "H": H, "G": G, "d": d, "C": (H + G) * d, "W": G * d // 2,
            "R": model["router_hidden_size"], "E": model["num_experts"],
            "k": model["num_experts_per_tok"],
            "F": model["moe_intermediate_size"], "V": model["vocab_size"],
            "t0": model.get("cca_time0", 2), "t1": model.get("cca_time1", 2)}


def cca_params(model: Dict) -> int:
    """One CCA sublayer's matrices: the four compressed projections,
    ``W_o``, the depthwise taps and the full convolution within a head."""
    d = _dims(model)
    return (d["D"] * (d["C"] + 2 * d["W"]) + d["H"] * d["d"] * d["D"]
            + d["C"] * d["t0"] + (d["H"] + d["G"]) * d["t1"] * d["d"] ** 2)


def router_params(model: Dict) -> int:
    """One router network's matrices: down, two hidden, out."""
    d = _dims(model)
    return d["D"] * d["R"] + 2 * d["R"] ** 2 + d["R"] * d["E"]


def expert_params(model: Dict) -> int:
    d = _dims(model)
    return 3 * d["D"] * d["F"]


def experts_touched(model: Dict, rows: float) -> float:
    """Distinct experts of one routed layer that ``rows`` tokens touch,
    in expectation under uniform routing: a token draws k distinct of
    the E, so it misses a given one with 1 - k / E (15.7 of 16 at 64
    rows)."""
    d = _dims(model)
    return d["E"] * (1.0 - (1.0 - d["k"] / d["E"]) ** max(rows, 0.0))


def _once_params(model: Dict) -> int:
    """Matrices a decode step reads once whatever its rows: every
    layer's CCA sublayer and router network, and the head."""
    d = _dims(model)
    return (d["L"] * (cca_params(model) + router_params(model))
            + d["V"] * d["D"])


def matmul_params(model: Dict) -> int:
    """Parameters of every matrix a decode step can read: all layers
    with all their experts, and the head (= the embedding)."""
    d = _dims(model)
    return _once_params(model) + d["L"] * d["E"] * expert_params(model)


def param_count(model: Dict) -> int:
    """Parameters held: every matrix (the tied embedding once), a
    layer's two norms and two residual merges (10 D), the convolutions'
    biases (2 C), the key temperatures (G), the router's biases, carry
    gain and norm (5 R) and selection bias (E); the final norm."""
    d = _dims(model)
    small = 10 * d["D"] + 2 * d["C"] + d["G"] + 5 * d["R"] + d["E"]
    return matmul_params(model) + d["L"] * small + d["D"]


def active_param_count(model: Dict) -> int:
    """Parameters one token multiplies with: of the experts its k."""
    d = _dims(model)
    return (param_count(model)
            - d["L"] * (d["E"] - d["k"]) * expert_params(model))


def kv_bytes_per_token(model: Dict, kv_itemsize: int) -> int:
    """What one cached token adds to the page pool: K and V of the
    compressed space, every layer."""
    d = _dims(model)
    return 2 * d["L"] * d["G"] * d["d"] * kv_itemsize


def state_bytes_per_row(model: Dict) -> int:
    """What one batch row's tails hold, whatever its context: ``[c | a
    | v2]`` a layer."""
    d = _dims(model)
    return d["L"] * (2 * d["C"] + d["W"]) * STATE_ITEMSIZE


def moe_ffn_bytes(model: Dict, weight_itemsize: int,
                  touched: float) -> float:
    """One routed layer's grouped products: the touched experts' three
    matrices, read once."""
    return touched * expert_params(model) * weight_itemsize


def moe_ffn_flops(model: Dict, pairs: float) -> float:
    """... and their operations for ``pairs`` (token, expert) pairs."""
    return 2.0 * expert_params(model) * pairs


def decode_attn_bytes(model: Dict, kv_itemsize: int, rows: float,
                      context_tokens: float) -> float:
    """One decode step's attention: K and V of every context token of
    the batch, read once (a KV head's pages serve its four query
    heads)."""
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens


def decode_attn_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """QK^T and PV: 2 x 2 operations a query head, head value and
    context token, every layer."""
    d = _dims(model)
    return 4.0 * d["L"] * d["H"] * d["d"] * context_tokens


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must move: what is read once, each layer's
    touched experts (in expectation at ``rows``), K and V of the batch's
    contexts, and the live rows' tails in and out."""
    d = _dims(model)
    routed = d["L"] * moe_ffn_bytes(model, weight_itemsize,
                                    experts_touched(model, rows))
    return (_once_params(model) * weight_itemsize + routed
            + decode_attn_bytes(model, kv_itemsize, rows, context_tokens)
            + 2.0 * rows * state_bytes_per_row(model))


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """The products a row runs (its k experts of each layer), and the
    attention."""
    d = _dims(model)
    return (2.0 * _once_params(model) * rows
            + d["L"] * moe_ffn_flops(model, rows * d["k"])
            + decode_attn_flops(model, rows, context_tokens))


def prefill_attn_flops(model: Dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, visible key) pairs, every
    layer."""
    d = _dims(model)
    return 4.0 * d["L"] * d["H"] * d["d"] * pairs


def prefill_attn_bytes(model: Dict, kv_itemsize: int, new_tokens: float,
                       context_tokens: float) -> float:
    """Least traffic of the layers' prefill attention: each call reads
    its sequence's cached K and V once and its q, and writes its
    output."""
    d = _dims(model)
    qo = 2 * 2 * d["L"] * d["H"] * d["d"] * new_tokens
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens + qo
