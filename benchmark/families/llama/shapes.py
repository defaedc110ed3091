"""The Llama block's shapes: what a step or a kernel call MUST move and
compute, from shapes alone, and what the harness has to know of the
family to read a trace. Kept with the benchmark so that no PR that
claims a gain can change the yardstick. All functions take the
configuration file's ``model`` block (``MODEL_KEYS``, Hugging Face key
names) and the served dtypes; the decode functions take the rows of the
batch beside the tokens of context they attend to (this family's least
bytes do not depend on the rows; a routed layer's do). Standard library
only: the parent process and the metric readers import this and stay
off JAX."""

from __future__ import annotations

from typing import Dict

#: The keys of the public ``config.json`` that say what this family's
#: shape is: ``contract.resolve_cell`` copies these into
#: ``config["model"]``.
MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "max_position_embeddings",
              "rope_theta", "rms_norm_eps", "tie_word_embeddings")
#: The program's kernels by their names in a trace (patterns): what the
#: benchmark takes from the program besides spans and counters.
DECODE_ATTN = r"fused_decode_attention"
PREFILL_ATTN = r"paged_prefill_attention"


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: one a layer."""
    return model["num_hidden_layers"]


def _dims(model: Dict) -> Dict[str, int]:
    hd = model.get("head_dim") or model["hidden_size"] // model[
        "num_attention_heads"]
    return {"D": model["hidden_size"], "L": model["num_hidden_layers"],
            "H": model["num_attention_heads"],
            "HKV": model["num_key_value_heads"], "hd": hd,
            "F": model["intermediate_size"], "V": model["vocab_size"],
            "tied": bool(model.get("tie_word_embeddings", False))}


def matmul_params(model: Dict) -> int:
    """Parameters every decode step reads: all layers' matrices and the
    output head (the embedding table is gathered, not streamed; tied
    or not, the head is one V x D matrix)."""
    d = _dims(model)
    per_layer = (d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["HKV"] * d["hd"]
                 + d["H"] * d["hd"] * d["D"] + 3 * d["D"] * d["F"])
    return d["L"] * per_layer + d["V"] * d["D"]


def param_count(model: Dict) -> int:
    d = _dims(model)
    n = matmul_params(model) + d["L"] * 2 * d["D"] + d["D"]
    return n if d["tied"] else n + d["V"] * d["D"]


def kv_bytes_per_token(model: Dict, kv_itemsize: int) -> int:
    """K and V of one token across all layers. int8 KV adds one bf16
    scale per token and KV head for K and for V."""
    d = _dims(model)
    b = 2 * d["L"] * d["HKV"] * d["hd"] * kv_itemsize
    if kv_itemsize == 1:
        b += 2 * d["L"] * d["HKV"] * 2
    return b


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must read: every matrix once, plus the
    cached K and V of every token in the batch's contexts
    (``context_tokens`` = sum of the rows' context lengths)."""
    return (matmul_params(model) * weight_itemsize
            + kv_bytes_per_token(model, kv_itemsize) * context_tokens)


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    d = _dims(model)
    return (2.0 * matmul_params(model) * rows
            + 4.0 * d["L"] * d["H"] * d["hd"] * context_tokens)


def decode_attn_bytes(model: Dict, kv_itemsize: int, rows: float,
                      context_tokens: float) -> float:
    """One decode step's attention over all layers: the cached K and V
    of every context token, read once (q, the new K/V row and the
    output are smaller by the context length and left out)."""
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens


def decode_attn_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    d = _dims(model)
    return 4.0 * d["L"] * d["H"] * d["hd"] * context_tokens


def prefill_attn_flops(model: Dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query token, visible key) pairs, all
    layers: a new token at position p of its sequence sees p + 1 keys."""
    d = _dims(model)
    return 4.0 * d["L"] * d["H"] * d["hd"] * pairs


def prefill_attn_bytes(model: Dict, kv_itemsize: int, new_tokens: float,
                       context_tokens: float) -> float:
    """Least traffic of the prefill attention calls: each call reads
    its sequence's cached K/V once and its q, and writes its output
    (bf16 activations)."""
    d = _dims(model)
    qo = 2 * d["L"] * d["H"] * d["hd"] * 2 * new_tokens
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens + qo
