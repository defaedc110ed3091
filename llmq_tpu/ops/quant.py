"""Weight quantization for the TPU serving path (w8a8 dynamic).

Why this exists: BASELINE config #2 names Llama-3-8B on a single chip,
but 8B of bf16 weights is 16 GB — the whole v5e HBM. int8 weights are
8 GB and leave room for the paged KV pool. Decode is HBM-bandwidth
bound (every step streams the full weight set), so int8 also halves the
per-step bandwidth floor for every model size.

Design (TPU-first, not a torch translation — the reference has no model
layer at all, SURVEY.md §2.2):

- **Symmetric per-output-channel weight scales.** Each matmul weight
  ``W (..., D_in, D_out)`` becomes ``{"q": int8, "s": f32 (..., 1,
  D_out)}``; the embedding table is scaled per ROW (per token id), which
  transposes into per-output-channel for the tied lm_head.
- **Dynamic per-token activation quantization** (w8a8): activations are
  scaled to int8 per row at runtime, and the matmul runs **natively in
  int8 on the MXU** via ``lax.dot_general(..., preferred_element_type=
  int32)`` — v5e's int8 MXU path has 2x the bf16 FLOPs, and weights are
  read from HBM as int8 (the bandwidth win; no bf16 dequant ever hits
  HBM).
- Norm gains stay bf16 (tiny), logits/softmax stay f32 (as before).

The quantized pytree drops into the existing forward functions: the
model's ``_linear`` dispatches on leaf structure, so one model source
serves bf16 and int8 — and ``parallel/sharding.py`` shards ``q`` exactly
like the bf16 weight it replaced (scales are replicated-or-sliced along
the same named axis).
"""

from __future__ import annotations

from typing import Any, Dict, Union

import jax
import jax.numpy as jnp
from jax import lax

from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]

#: Quantized-weight leaf: {"q": int8 weights, "s": f32 scales}.
QuantW = Dict[str, jnp.ndarray]

_QKEYS = frozenset({"q", "s"})


def is_quantized(w: Any) -> bool:
    """True if ``w`` is a quantized-weight leaf produced by this module."""
    return isinstance(w, dict) and _QKEYS.issubset(w.keys())


def quantize_weight(w: jnp.ndarray, axis: int = -2) -> QuantW:
    """Quantize one weight to int8 with symmetric per-channel scales.

    ``axis`` is the CONTRACTION axis (reduced over in the matmul); the
    scale is computed per slice along every other trailing axis. For a
    stacked-layer weight (L, D_in, D_out) with axis=-2 the scale shape
    is (L, 1, D_out) — one scale per output channel per layer.
    """
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def dequantize_weight(w: QuantW, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (w["q"].astype(jnp.float32) * w["s"]).astype(dtype)


def quantize_act(x: jnp.ndarray):
    """Dynamic symmetric per-row (per-token) activation quantization.

    Returns (x_q int8, scale f32 with trailing dim 1). f32 math — bf16
    amax/round loses enough precision to visibly shift logits.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return xq, scale


def qdot(x: jnp.ndarray, w: QuantW) -> jnp.ndarray:
    """``x @ W`` with int8 weights and dynamically-quantized activations.

    The contraction runs int8 x int8 -> int32 on the MXU
    (``preferred_element_type=int32``); the two scales (per-token
    activation, per-channel weight) are applied to the int32 result in
    f32 and the output returns in ``x.dtype``. Weight leading batch dims
    (e.g. none here — layers are indexed before the call) must already
    be sliced away.
    """
    with scope("act_quant"):
        xq, sx = quantize_act(x)
    wq, sw = w["q"], w["s"]
    # Contract the last axis of x with the first axis of wq.
    y = lax.dot_general(
        xq, wq,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    # sx: (..., 1) broadcasts over output channels; sw: (1, D_out)
    # (contraction axis kept as 1) broadcasts over rows.
    return (y * sx * sw.reshape(sw.shape[-1])).astype(x.dtype)


def linear(x: jnp.ndarray, w: Union[jnp.ndarray, QuantW]) -> jnp.ndarray:
    """Quantization-dispatching matmul: bf16 ``jnp.dot`` or int8 ``qdot``."""
    if is_quantized(w):
        return qdot(x, w)
    return jnp.dot(x, w)


def layer_slice(w: Union[jnp.ndarray, QuantW], l) -> Union[jnp.ndarray, QuantW]:
    """Index the stacked-layer leading axis of a (possibly quantized)
    weight: ``w[l]`` for arrays, elementwise for quantized leaves."""
    if is_quantized(w):
        return {"q": w["q"][l], "s": w["s"][l]}
    return w[l]


# -- embedding ----------------------------------------------------------------

def quantize_embedding(embed: jnp.ndarray) -> QuantW:
    """Per-row (per-token-id) scales: gather stays a 1-byte-per-element
    HBM read; the tied lm_head (``embed.T``) sees per-output-channel
    scales, which is exactly the quantization axis `quantize_weight`
    uses for untied heads. (Same formula as quantize_weight, reduced
    over the last axis — keep one implementation.)"""
    return quantize_weight(embed, axis=-1)


def embed_lookup(embed: Union[jnp.ndarray, QuantW], tokens: jnp.ndarray,
                 dtype=jnp.bfloat16) -> jnp.ndarray:
    """Row gather for bf16 or quantized embedding tables."""
    if is_quantized(embed):
        rows = embed["q"][tokens].astype(jnp.float32) * embed["s"][tokens]
        return rows.astype(dtype)
    return embed[tokens].astype(dtype)


def tied_head_logits(embed: QuantW, h: jnp.ndarray) -> jnp.ndarray:
    """``h @ embed.T`` for a per-row-quantized embedding: the row scales
    become per-output-channel scales of the transposed head."""
    with scope("act_quant"):
        xq, sx = quantize_act(h)
    y = lax.dot_general(
        xq, embed["q"],
        # contract h's last axis with embed's LAST axis (i.e. embed.T).
        dimension_numbers=(((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    return y * sx * embed["s"].reshape(embed["s"].shape[0])


# -- pytree transform ---------------------------------------------------------

#: Stacked-layer matmul weights in models/llama.py's param tree.
_LAYER_MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_params(params: Params) -> Params:
    """Quantize a models/llama.py parameter pytree to w8-int8.

    Matmul weights (attention/ffn projections, lm_head, embedding)
    become ``{"q", "s"}`` leaves; norm gains stay in their float dtype.
    Idempotent on already-quantized trees.
    """
    out: Params = {}
    out["embed"] = (params["embed"] if is_quantized(params["embed"])
                    else quantize_embedding(params["embed"]))
    layers_in = params["layers"]
    layers: Dict[str, Any] = {}
    for name, w in layers_in.items():
        if name in _LAYER_MATMULS and not is_quantized(w):
            layers[name] = quantize_weight(w, axis=-2)
        else:
            layers[name] = w
    out["layers"] = layers
    out["final_norm"] = params["final_norm"]
    if "lm_head" in params:
        head = params["lm_head"]
        out["lm_head"] = (head if is_quantized(head)
                          else quantize_weight(head, axis=-2))
    return out


def params_bytes(params: Params) -> int:
    """On-device byte footprint of a (possibly quantized) param tree."""
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))


# -- int8 KV cache ------------------------------------------------------------
#
# Per-token-per-KV-head symmetric int8 (docs/performance.md "int8 KV
# cache is the next lever"): KV reads are ~2 GB of an 8B decode step's
# ~10 GB HBM floor, and the POOL's byte size also bounds how many
# sequences fit resident. Scales are bf16, one per (token, kv-head),
# stored in pools shaped (L, P, H_kv, page_size): for the llama3 family
# H_kv = 8 exactly fills the TPU's minimum sublane tile, so a page's
# scales are one aligned (8, page_size) block — and the (head, position)
# layout is ALSO the logits layout, so kernels apply K scales to logits
# and V scales to probabilities without any transpose.


def quantize_kv_rows(x: jnp.ndarray):
    """Quantize KV rows (..., H_kv, D) → (int8 (..., H_kv, D),
    bf16 scales (..., H_kv)). Symmetric max-abs per (row, head)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    """Inverse of :func:`quantize_kv_rows`: q (..., H_kv, D) int8 ×
    scales (..., H_kv) → (..., H_kv, D) ``dtype``."""
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)
