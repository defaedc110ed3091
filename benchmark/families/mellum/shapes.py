"""The JetBrains ``mellum`` block's shapes (grouped-query attention whose
layers are sliding-window or full, each rotated by the table of its
kind; a softmax-routed feed-forward in EVERY layer, no dense layer, no
shared expert; ``model_type: mellum``), for a chip that holds a
pipeline stage WHOLE — every expert and the whole vocabulary of the
layers it holds: what a step or a kernel call MUST move and compute
here, from shapes alone, and what the harness has to know of the family
to read a trace. The surface is ``families/llama/shapes.py``'s.

**The two kinds of cache, and what the harness can tell of them**
(``families/afmoe/shapes.py`` has the argument): a full layer reads
every cached token of a row, a sliding layer at most ``sliding_window``
of them; ``harness/readers.mean_load`` samples the SUM of a batch's
contexts and nothing of its split, so ``decode_attn_bytes`` /
``decode_step_bytes`` / ``prefill_attn_*`` count the full layers exactly
and for the sliding layers the LEAST any split of that sum over rows of
at most ``max_position_embeddings`` tokens could read (``window_share``).
The accepted rooflines read LOW for this family and can never pass
100 % through this count; the exact share of the sliding layers is
``metrics/attn_window_roofline.py``'s, from the program's own
window-bounded counter (``attn_window_bytes``).

**A tail** (``row_tail_bytes``): what one adoption of a cached prefix
copies — the last ``ceil(window / page)`` pages of every sliding
layer's K and V, read once and written once.

Standard library only."""

from __future__ import annotations

from typing import Dict, Tuple

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers", "layer_types",
              "mlp_layer_types", "sliding_window", "num_attention_heads",
              "num_key_value_heads", "head_dim", "num_experts",
              "num_experts_per_tok", "norm_topk_prob",
              "max_position_embeddings", "rope_parameters", "rms_norm_eps",
              "tie_word_embeddings", "qk_norm")
#: The program's kernels by their names in a trace (patterns): both
#: kinds of layer call the same two kernels, the sliding layers with
#: the window.
DECODE_ATTN = r"fused_decode_attention"
PREFILL_ATTN = r"paged_prefill_attention"
#: The grouped product of a routed layer (``moe_ffn_roofline``).
MOE_FFN = r"^gmm$"
SLIDING = "sliding_attention"


def layer_kinds(model: Dict) -> Tuple[int, int]:
    """(sliding, full) layers among the layers held."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    n = sum(1 for k in kinds if k == SLIDING)
    return n, len(kinds) - n


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: one a layer."""
    return model["num_hidden_layers"]


def _dims(model: Dict) -> Dict[str, int]:
    return {"D": model["hidden_size"], "L": model["num_hidden_layers"],
            "H": model["num_attention_heads"],
            "G": model["num_key_value_heads"], "hd": model["head_dim"],
            "Fe": model["moe_intermediate_size"], "E": model["num_experts"],
            "k": model["num_experts_per_tok"], "V": model["vocab_size"],
            "W": model["sliding_window"],
            "max": model["max_position_embeddings"]}


def attn_params(model: Dict) -> int:
    """One layer's attention matrices: Wq, Wk, Wv, Wo."""
    d = _dims(model)
    return d["D"] * d["hd"] * (2 * d["H"] + 2 * d["G"])


def expert_params(model: Dict) -> int:
    d = _dims(model)
    return 3 * d["D"] * d["Fe"]


def experts_touched(model: Dict, rows: float) -> float:
    """Distinct experts of one layer that ``rows`` tokens touch, in
    expectation under uniform routing: a token draws k distinct of E."""
    d = _dims(model)
    return d["E"] * (1.0 - (1.0 - d["k"] / d["E"]) ** max(rows, 0.0))


def _once_params(model: Dict) -> int:
    """Matrices a decode step reads once whatever its rows: every
    layer's attention and router, and the head."""
    d = _dims(model)
    return (d["L"] * (attn_params(model) + d["D"] * d["E"])
            + d["V"] * d["D"])


def matmul_params(model: Dict) -> int:
    """Parameters of every matrix a decode step can read: all layers
    with all their experts, and the head."""
    d = _dims(model)
    return _once_params(model) + d["L"] * d["E"] * expert_params(model)


def param_count(model: Dict) -> int:
    """Parameters held: every matrix, the embedding and the norms (two
    a layer over the stream, with ``qk_norm`` two over a head, the final
    one)."""
    d = _dims(model)
    heads = 2 * d["hd"] if model.get("qk_norm", True) else 0
    return (matmul_params(model) + d["V"] * d["D"]
            + d["L"] * (2 * d["D"] + heads) + d["D"])


def active_param_count(model: Dict) -> int:
    """Parameters one token multiplies with: all but the E - k experts
    a layer it is not routed to."""
    d = _dims(model)
    return (param_count(model)
            - d["L"] * (d["E"] - d["k"]) * expert_params(model))


def kv_layer_bytes(model: Dict, kv_itemsize: int) -> int:
    """K and V of one token in one layer."""
    d = _dims(model)
    return 2 * d["G"] * d["hd"] * kv_itemsize


def kv_bytes_per_token(model: Dict, kv_itemsize: int) -> int:
    """What one cached token adds to the PAGE POOL: K and V in the full
    layers (a sliding layer's are bounded a row, in its slab)."""
    return layer_kinds(model)[1] * kv_layer_bytes(model, kv_itemsize)


def window_share(model: Dict) -> float:
    """The least share of a batch's summed contexts that a sliding
    layer reads, whatever the split over the rows."""
    d = _dims(model)
    return min(1.0, d["W"] / d["max"])


def attn_window_bytes(model: Dict, kv_itemsize: int,
                      window_tokens: float) -> float:
    """The sliding layers' K and V of one decode step, exactly:
    ``window_tokens`` is the batch's window-bounded contexts summed
    (the program's counter, ``sum min(context, window)``)."""
    return (layer_kinds(model)[0] * kv_layer_bytes(model, kv_itemsize)
            * window_tokens)


def row_tail_bytes(model: Dict, kv_itemsize: int, page_size: int) -> int:
    """One tail: the window in whole pages of every sliding layer's K
    and V. A copy reads it once and writes it once."""
    pages = -(-model["sliding_window"] // page_size)
    return (layer_kinds(model)[0] * kv_layer_bytes(model, kv_itemsize)
            * pages * page_size)


def moe_ffn_bytes(model: Dict, weight_itemsize: int,
                  touched: float) -> float:
    """One routed layer's grouped products: the touched experts' three
    matrices, read once."""
    return touched * expert_params(model) * weight_itemsize


def moe_ffn_flops(model: Dict, pairs: float) -> float:
    """... and their operations for ``pairs`` (token, expert) pairs."""
    return 2.0 * expert_params(model) * pairs


def _attn_layers(model: Dict) -> float:
    sliding, full = layer_kinds(model)
    return full + sliding * window_share(model)


def decode_attn_bytes(model: Dict, kv_itemsize: int, rows: float,
                      context_tokens: float) -> float:
    """One decode step's attention: the full layers' cached K and V of
    every context token, and the LEAST the sliding layers could read of
    that sum (the module's docstring)."""
    return (kv_layer_bytes(model, kv_itemsize) * context_tokens
            * _attn_layers(model))


def decode_attn_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """QK^T and PV over the same tokens: 2 operations a head a value
    each."""
    d = _dims(model)
    return 4.0 * d["H"] * d["hd"] * context_tokens * _attn_layers(model)


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must read: what is read once, each layer's
    touched experts (in expectation at ``rows``), and the attention's
    cached K and V (``decode_attn_bytes``)."""
    d = _dims(model)
    routed = d["L"] * moe_ffn_bytes(model, weight_itemsize,
                                    experts_touched(model, rows))
    return (_once_params(model) * weight_itemsize + routed
            + decode_attn_bytes(model, kv_itemsize, rows, context_tokens))


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    d = _dims(model)
    return (2.0 * _once_params(model) * rows
            + d["L"] * moe_ffn_flops(model, rows * d["k"])
            + decode_attn_flops(model, rows, context_tokens))


def prefill_attn_flops(model: Dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, visible key) pairs in the full
    layers and the least of them a window leaves in the sliding ones."""
    d = _dims(model)
    return 4.0 * d["H"] * d["hd"] * pairs * _attn_layers(model)


def prefill_attn_bytes(model: Dict, kv_itemsize: int, new_tokens: float,
                       context_tokens: float) -> float:
    """Least traffic of prefill attention: each call reads its
    sequence's visible K and V once (bounded as above in a sliding
    layer) and its q, and writes its output."""
    d = _dims(model)
    qo = 2 * d["L"] * d["H"] * d["hd"] * kv_itemsize * new_tokens
    return (kv_layer_bytes(model, kv_itemsize) * context_tokens
            * _attn_layers(model) + qo)
