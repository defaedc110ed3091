"""The plain reference: a decoder-only transformer of the Llama block
(RMSNorm, rotary positions in the split-half layout, grouped-query
causal attention, SwiGLU, optional tied head) as the public
``modeling_llama.py`` / ``modeling_mistral.py`` of Hugging Face
describe it, in straightforward ``jax.numpy`` and float32: no kernels,
no cache, no batching, one sequence at a time,
``jax.default_matmul_precision("highest")``.

It shares no code with ``llmq_tpu`` and none with ``adapter.py``. It
reads the served parameter tree (stacked layers; an int8 leaf is
``{"q", "s"}`` and stands for ``q * s``), upcasting ONE layer at a time
so that 7 B parameters never exist in float32 at once. Departures from the published models: none in
the mathematics; the weights are random, and an int8 leaf is taken at
its dequantised value (the reference has no activation quantisation, so
the served w8a8 model is held to it by an RMS bound, see
``configs/*.json`` ``tolerance``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp


def _f32(leaf: Any) -> jnp.ndarray:
    if isinstance(leaf, dict):
        return leaf["q"].astype(jnp.float32) * leaf["s"].astype(jnp.float32)
    return leaf.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    # x: (T, H, D); rotate the two halves of D by position-dependent angles.
    T, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "theta"))
def _layer(h, layers, l, *, n_heads, n_kv, eps, theta):
    # Layer ``l`` of the stacked tree, taken and upcast here, inside
    # the one compiled program (``l`` is traced: slicing with a Python
    # index outside would compile one tiny program per layer and leaf).
    w = {k: _f32(jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, l, 0, keepdims=False), v))
        for k, v in layers.items()}
    T, D = h.shape
    hd = D // n_heads
    x = _rms(h, w["attn_norm"], eps)
    q = _rope((x @ w["wq"]).reshape(T, n_heads, hd), theta)
    k = _rope((x @ w["wk"]).reshape(T, n_kv, hd), theta)
    v = (x @ w["wv"]).reshape(T, n_kv, hd)
    rep = n_heads // n_kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(hd))
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    h = h + a.reshape(T, D) @ w["wo"]
    x = _rms(h, w["mlp_norm"], eps)
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=("eps",))
def _head(params, tokens_h, rows, *, eps):
    h = _rms(tokens_h[rows], _f32(params["final_norm"]), eps)
    head = params.get("lm_head")
    if head is not None:
        return h @ _f32(head)
    return h @ _f32(params["embed"]).T


@jax.jit
def _embed(emb, tokens):
    if isinstance(emb, dict):
        return (emb["q"][tokens].astype(jnp.float32)
                * emb["s"][tokens].astype(jnp.float32))
    return emb[tokens].astype(jnp.float32)


def logits_by_dims(params: Dict[str, Any], tokens, *, n_layers: int,
                   n_heads: int, n_kv_heads: int, eps: float,
                   theta: float, rows) -> jnp.ndarray:
    """float32 logits ``(len(rows), V)`` of one sequence ``tokens``
    ``(T,)`` at the positions ``rows``."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        h = _embed(params["embed"], tokens)
        for l in range(n_layers):
            h = _layer(h, params["layers"], jnp.int32(l), n_heads=n_heads,
                       n_kv=n_kv_heads, eps=eps, theta=theta)
        top = {k: v for k, v in params.items() if k != "layers"}
        return _head(top, h, jnp.asarray(rows, jnp.int32), eps=eps)


def reference_logits(params: Dict[str, Any], tokens, model: Dict[str, Any],
                     rows) -> jnp.ndarray:
    """The family's surface: ``model`` is the configuration file's
    ``model`` block (``shapes.MODEL_KEYS``). ``logits_by_dims`` is the
    same by keyword, as ``tests/`` call it (``harness/reference.py``)."""
    return logits_by_dims(
        params, tokens, n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
        rows=rows)
