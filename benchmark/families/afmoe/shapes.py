"""The Arcee ``afmoe`` block's shapes (gated grouped-query attention
whose layers are sliding-window or full, a dense SwiGLU in the leading
layers and a sigmoid-routed layer beside a shared expert after;
``model_type: afmoe``), for a chip that holds a SHARE of each routed
layer's experts and of the vocabulary: what a step or a kernel call MUST
move and compute here, from shapes alone, and what the harness has to
know of the family to read a trace. The surface is
``families/llama/shapes.py``'s, with ``families/longcat_flash``'s share:

- ``num_hidden_layers`` layers are HELD: the first ``dense_layers_held``
  with the dense SwiGLU (``intermediate_size``), the rest routed; layer
  ``l``'s kind is ``layer_types[l]`` (the published list, of which the
  held layers are the first);
- ``num_experts`` is what this chip HOLDS of the router's
  ``router_experts``; a token draws ``num_experts_per_tok`` of the
  router's outputs, so of a step's ``rows * k`` slots the share held /
  outputs falls on a held expert (16 of 256 here), the rest on experts
  another chip holds, which cost nothing HERE;
- attention, the dense and the shared SwiGLUs and the router are whole
  (data-parallel in the deployment); the head is this chip's slice of
  the vocabulary.

**The two kinds of cache, and what the harness can tell of them.** A
full layer reads every cached token of a row, a sliding layer at most
``sliding_window`` of them. ``harness/readers.mean_load`` samples a
batch's rows and the SUM of their contexts and nothing of how that sum
is split, so ``decode_attn_bytes`` / ``decode_step_bytes`` count the
full layers exactly and, for the sliding layers, the LEAST that any
split of that sum over rows of at most ``max_position_embeddings``
tokens could read: ``context_tokens * sliding_window /
max_position_embeddings`` tokens a layer (all the sum in rows as long
as a row may be). The accepted rooflines therefore read LOW for this
family and can never pass 100 % through this count; the exact share of
the sliding layers is ``metrics/attn_window_roofline.py``'s, from the
program's own window-bounded counter. ``prefill_attn_*`` bound the
sliding layers the same way.

Standard library only."""

from __future__ import annotations

from typing import Dict, Tuple

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_dense_layers", "dense_layers_held", "layer_types",
              "sliding_window", "num_attention_heads", "num_key_value_heads",
              "head_dim", "num_experts", "num_experts_per_tok",
              "num_shared_experts", "route_norm", "route_scale",
              "score_func", "mup_enabled", "max_position_embeddings",
              "rope_theta", "rms_norm_eps", "tie_word_embeddings",
              "router_experts", "expert_share")
#: The program's kernels by their names in a trace (patterns): both
#: kinds of layer call the same two kernels, the sliding layers with
#: the window.
DECODE_ATTN = r"fused_decode_attention"
PREFILL_ATTN = r"paged_prefill_attention"
#: The grouped product of a routed layer (``moe_ffn_roofline``): JAX's
#: megablox kernel, which a trace names ``gmm`` whatever wraps it.
MOE_FFN = r"^gmm$"
SLIDING = "sliding_attention"


def held_experts(model: Dict) -> Tuple[int, int]:
    """(first, end) of the router's experts this chip holds: share
    ``index`` of ``chips`` equal shares of ``router_experts``."""
    share, n = model["expert_share"], model["num_experts"]
    if share["chips"] * n != model["router_experts"]:
        raise ValueError(f"{share['chips']} shares of {n} experts are not "
                         f"the router's {model['router_experts']}")
    return share["index"] * n, (share["index"] + 1) * n


def layer_kinds(model: Dict) -> Tuple[int, int]:
    """(sliding, full) layers among the layers held."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    n = sum(1 for k in kinds if k == SLIDING)
    return n, len(kinds) - n


def dense_layers(model: Dict) -> int:
    """Held layers with the dense SwiGLU (the rest are routed)."""
    return min(model.get("dense_layers_held", model["num_dense_layers"]),
               model["num_hidden_layers"])


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: one a layer, of
    either kind."""
    return model["num_hidden_layers"]


def _dims(model: Dict) -> Dict[str, int]:
    L, Ld = model["num_hidden_layers"], dense_layers(model)
    return {"D": model["hidden_size"], "L": L, "Ld": Ld, "Lm": L - Ld,
            "H": model["num_attention_heads"],
            "G": model["num_key_value_heads"], "hd": model["head_dim"],
            "F": model["intermediate_size"],
            "Fe": model["moe_intermediate_size"],
            "Fs": model["num_shared_experts"] * model["moe_intermediate_size"],
            "Eh": model["num_experts"], "R": model["router_experts"],
            "k": model["num_experts_per_tok"], "V": model["vocab_size"],
            "W": model["sliding_window"],
            "max": model["max_position_embeddings"]}


def attn_params(model: Dict) -> int:
    """One layer's attention matrices: Wq, Wk, Wv, the gate, Wo."""
    d = _dims(model)
    return d["D"] * d["hd"] * (3 * d["H"] + 2 * d["G"])


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
    1 - k / outputs (10.2 of 16 at 64 rows)."""
    d = _dims(model)
    return d["Eh"] * (1.0 - (1.0 - d["k"] / d["R"]) ** max(rows, 0.0))


def _once_params(model: Dict) -> int:
    """Matrices a decode step reads once whatever its rows: every
    layer's attention, the dense SwiGLUs, the routed layers' routers
    and shared experts, and the head's slice."""
    d = _dims(model)
    return (d["L"] * attn_params(model) + d["Ld"] * 3 * d["D"] * d["F"]
            + d["Lm"] * (d["D"] * d["R"] + 3 * d["D"] * d["Fs"])
            + d["V"] * d["D"])


def matmul_params(model: Dict) -> int:
    """Parameters of every matrix a decode step can read here: all
    layers with all their HELD experts, and the head's slice."""
    d = _dims(model)
    return _once_params(model) + d["Lm"] * d["Eh"] * expert_params(model)


def param_count(model: Dict) -> int:
    """Parameters held: every matrix, the embedding's slice, the norms
    (four a layer over the stream, two over a head, the final one) and
    the routers' selection biases."""
    d = _dims(model)
    norms = d["L"] * (4 * d["D"] + 2 * d["hd"]) + d["D"]
    return matmul_params(model) + d["V"] * d["D"] + norms + d["Lm"] * d["R"]


def active_param_count(model: Dict) -> int:
    """Parameters one token multiplies with here, in expectation: of
    the held experts the k * held / outputs its slots fall on."""
    d = _dims(model)
    idle = d["Eh"] - d["k"] * held_slot_share(model)
    return int(param_count(model) - d["Lm"] * idle * expert_params(model))


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
    layer reads, whatever the split over the rows (the module's
    docstring)."""
    d = _dims(model)
    return min(1.0, d["W"] / d["max"])


def attn_window_bytes(model: Dict, kv_itemsize: int,
                      window_tokens: float) -> float:
    """The sliding layers' K and V of one decode step, exactly:
    ``window_tokens`` is the batch's window-bounded contexts summed
    (the program's counter, ``sum min(context, window)``)."""
    return (layer_kinds(model)[0] * kv_layer_bytes(model, kv_itemsize)
            * window_tokens)


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
    """One decode step's attention: the full layers' cached K and V of
    every context token, and the LEAST the sliding layers could read of
    that sum (the module's docstring)."""
    sliding, full = layer_kinds(model)
    return (kv_layer_bytes(model, kv_itemsize) * context_tokens
            * (full + sliding * window_share(model)))


def decode_attn_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """QK^T and PV over the same tokens: 2 operations a head a value
    each."""
    d = _dims(model)
    sliding, full = layer_kinds(model)
    return (4.0 * d["H"] * d["hd"] * context_tokens
            * (full + sliding * window_share(model)))


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must read: what is read once, each routed
    layer's touched held experts (in expectation at ``rows``), and the
    attention's cached K and V (``decode_attn_bytes``)."""
    d = _dims(model)
    routed = d["Lm"] * moe_ffn_bytes(model, weight_itemsize,
                                     experts_touched(model, rows))
    return (_once_params(model) * weight_itemsize + routed
            + decode_attn_bytes(model, kv_itemsize, rows, context_tokens))


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    d = _dims(model)
    pairs = rows * d["k"] * held_slot_share(model)
    return (2.0 * _once_params(model) * rows
            + d["Lm"] * moe_ffn_flops(model, pairs)
            + decode_attn_flops(model, rows, context_tokens))


def prefill_attn_flops(model: Dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, visible key) pairs in the full
    layers and the least of them a window leaves in the sliding ones
    (a query at position p sees min(p + 1, W) >= (p + 1) W / max)."""
    d = _dims(model)
    sliding, full = layer_kinds(model)
    return (4.0 * d["H"] * d["hd"] * pairs
            * (full + sliding * window_share(model)))


def prefill_attn_bytes(model: Dict, kv_itemsize: int, new_tokens: float,
                       context_tokens: float) -> float:
    """Least traffic of prefill attention: each call reads its
    sequence's visible K and V once (bounded as above in a sliding
    layer) and its q, and writes its output."""
    d = _dims(model)
    sliding, full = layer_kinds(model)
    qo = 2 * d["L"] * d["H"] * d["hd"] * kv_itemsize * new_tokens
    return (kv_layer_bytes(model, kv_itemsize) * context_tokens
            * (full + sliding * window_share(model)) + qo)
