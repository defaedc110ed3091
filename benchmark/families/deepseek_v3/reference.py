"""The plain reference: a decoder-only transformer of the DeepSeek-V3
block (``model_type: deepseek_v3`` as the public
``modeling_deepseek_v3.py`` describes it: RMSNorm, multi-head latent
attention with ``q_lora_rank`` null, a dense SwiGLU in the first
``first_k_dense_replace`` layers and after them a routed SwiGLU —
sigmoid scores, ``noaux_tc`` selection with a correction bias that
chooses only, no group limit, normalised and scaled gates — plus shared
experts, untied head), in straightforward ``jax.numpy`` and float32:
the UNABSORBED equations (K and V expanded from the latent for every
token), no cache, no kernel, no batching, a loop over the experts, one
sequence at a time, ``jax.default_matmul_precision("highest")``.

It shares no code with ``llmq_tpu`` and none with ``adapter.py``. It
reads the served parameter tree (stacked layers; ``layers`` over all,
``dense`` over the leading dense layers, ``moe`` over the routed ones;
an expert's gate and up matrices side by side in ``we_gate_up``, a
routed layer's experts a leaf of their own),
upcasting ONE layer — and of a routed layer one expert — at a time, so
that it fits beside 10 GB of served weights. Departures from the
published model: none in the mathematics. The weights are random. The
tree holds the rotary rows of W_q and W_kva de-interleaved (the
program's loader permutes a published checkpoint once), so the
rotation here is of the two halves, which on those rows is the
published interleaved rotation.

Routing makes the comparison harder than a dense block's: a rounding
difference can swap a token's 6th and 7th expert. The reference routes
for itself and also returns, for every position asked for, the smallest
margin between its 6th and 7th selection score over the routed layers
(``margins``); ``judge`` is the comparison over many positions that
tells a swap from a fault (``configs/*.json`` ``tolerance`` has the
numbers and the reasons). ``harness/child.py`` ``check_logits`` holds
the worst of the 8 positions it drives to ``tolerance.rms``, which
refuses unrelated logits and nothing finer, and it calls no family's
``judge``. So the finer comparison runs inside ``reference_logits``,
the call in which that check hands this module the weights and a
prompt: while ``JUDGED`` is set (``adapter.serving_path`` sets it; this
module imports neither the program nor the adapter), every position of
the prompt and 128 decode positions through the latent cache are held
to ``judge`` (for a prompt of at least ``tolerance.min_positions``
tokens), and a group that fails raises ``NotCorrect``: the run
ends there, before the server is built, and prints no result.

``lowp=True`` is the same reference with the router's product in
bfloat16 and the latent rounded to 8 bits (float8_e4m3): the nearest
precision below what the configuration states, which the comparison
has to refuse.
"""

from __future__ import annotations

import json
import sys
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return x.astype(jnp.float32)


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


def _take(tree, l):
    return {k: _f32(jax.lax.dynamic_index_in_dim(v, l, 0, keepdims=False))
            for k, v in tree.items()}


@partial(jax.jit, static_argnames=("n_heads", "rank", "dn", "dr", "eps",
                                   "theta", "lowp"))
def _attention(h, layers, l, *, n_heads, rank, dn, dr, eps, theta, lowp):
    w = _take({k: layers[k] for k in ("attn_norm", "wq", "wkv_a", "kv_norm",
                                      "wkv_b", "wo")}, l)
    T = h.shape[0]
    x = _rms(h, w["attn_norm"], eps)
    q = (x @ w["wq"]).reshape(T, n_heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    kva = x @ w["wkv_a"]
    c = _rms(kva[:, :rank], w["kv_norm"], eps)
    k_rope = _rope(kva[:, None, rank:], theta)             # (T, 1, dr)
    if lowp:
        c = _f32(c.astype(jnp.float8_e4m3fn))
        k_rope = _f32(k_rope.astype(jnp.float8_e4m3fn))
    kv = (c @ w["wkv_b"]).reshape(T, n_heads, -1)          # [k_nope | v]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (T, n_heads, dr))], -1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(dn + dr))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), kv[..., dn:])
    return h + o.reshape(T, -1) @ w["wo"]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(h, norm, dense, l, *, eps):
    w = _take(dense, l)
    x = _rms(h, _f32(norm[l]), eps)
    return h + _swiglu(x, w["w_gate"], w["w_up"], w["w_down"])


@partial(jax.jit, static_argnames=("top_k", "scale", "norm_topk", "eps",
                                   "lowp"))
def _routed_ffn(h, norm, moe, we_gate_up, we_down, l, i, *, top_k, scale,
                norm_topk, eps, lowp):
    """Layer ``l`` (the ``i``-th routed one; ``we_*``: ITS experts'
    leaves). Returns (h', margin (T,): the 6th selection score minus
    the 7th)."""
    x = _rms(h, _f32(norm[l]), eps)
    w_r = _f32(moe["router"][i])
    if lowp:
        logits = _f32(jnp.dot(x.astype(jnp.bfloat16),
                              w_r.astype(jnp.bfloat16)))
    else:
        logits = x @ w_r
    s = jax.nn.sigmoid(logits)                             # (T, E)
    sel = s + _f32(moe["router_bias"][i])
    order = jnp.argsort(-sel, axis=-1)                     # ties: low index
    chosen = order[:, :top_k]
    ranked = jnp.take_along_axis(sel, order[:, :top_k + 1], -1)
    g = jnp.take_along_axis(s, chosen, -1)
    if norm_topk:
        g = g / jnp.sum(g, -1, keepdims=True)
    T, E = s.shape
    gates = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], chosen].set(g * scale)
    F = we_down.shape[1]

    def one(e, acc):
        gu = _f32(we_gate_up[e])
        y = _swiglu(x, gu[:, :F], gu[:, F:], _f32(we_down[e]))
        return acc + gates[:, e, None] * y

    y = jax.lax.fori_loop(0, E, one, jnp.zeros_like(h))
    y = y + _swiglu(x, _f32(moe["ws_gate"][i]), _f32(moe["ws_up"][i]),
                    _f32(moe["ws_down"][i]))
    return h + y, ranked[:, top_k - 1] - ranked[:, top_k]


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, h, rows, *, eps):
    return _rms(h[rows], _f32(final_norm), eps) @ _f32(lm_head)


def reference_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                      rows, lowp: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(float32 logits ``(len(rows), V)`` of one sequence ``tokens``
    ``(T,)`` at the positions ``rows``, margins ``(len(rows),)``: each
    position's smallest 6th-to-7th selection margin over the routed
    layers)."""
    L, Ld = model["num_hidden_layers"], model["first_k_dense_replace"]
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    if model.get("q_lora_rank") is not None or model.get("n_group", 1) != 1:
        raise ValueError("the reference is written for q_lora_rank null "
                         "and no group limit")
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
        margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
        for l in range(L):
            h = _attention(
                h, params["layers"], jnp.int32(l),
                n_heads=model["num_attention_heads"],
                rank=model["kv_lora_rank"], dn=model["qk_nope_head_dim"],
                dr=model["qk_rope_head_dim"], eps=eps, theta=theta,
                lowp=lowp)
            norm = params["layers"]["mlp_norm"]
            if l < Ld:
                h = _dense_ffn(h, norm, params["dense"], jnp.int32(l),
                               eps=eps)
            else:
                moe = params["moe"]
                h, m = _routed_ffn(
                    h, norm, {k: v for k, v in moe.items()
                              if not k.startswith("we_")},
                    moe["we_gate_up"][l - Ld], moe["we_down"][l - Ld],
                    jnp.int32(l), jnp.int32(l - Ld),
                    top_k=model["num_experts_per_tok"],
                    scale=float(model["routed_scaling_factor"]),
                    norm_topk=bool(model["norm_topk_prob"]), eps=eps,
                    lowp=lowp)
                margin = jnp.minimum(margin, m)
        return (_head(params["final_norm"], params["lm_head"], h, rows,
                      eps=eps), margin[rows])


class NotCorrect(AssertionError):
    """The serving path's logits are not the reference's, by ``judge``."""


#: ``(served_many, tolerance)`` while the family's serving path is under
#: the harness's check, else ``None``. ``served_many(params, tokens) ->
#: {group: (rows, logits (len(rows), V))}``: the serving path's float32
#: logits at MANY positions ``rows`` of the one sequence ``tokens``, a
#: group for each way of getting there (all of a prefill's positions;
#: decode steps through the cache). ``tolerance``: the configuration's.
JUDGED: Optional[Tuple[Callable[..., Dict[str, Any]], Dict[str, Any]]] = None


def reference_logits(params: Dict[str, Any], tokens, model: Dict[str, Any],
                     rows) -> jnp.ndarray:
    """The family's surface: ``model`` is the configuration file's
    ``model`` block (``shapes.MODEL_KEYS``). While ``JUDGED`` is set,
    each of its groups is held to ``judge`` first (one line a group on
    standard error), and ``NotCorrect`` is raised for one that fails.
    A sequence of fewer than ``tolerance.min_positions`` tokens is not
    judged: in a short context a swapped token upstream weighs in every
    later position, too few positions stay clean, and the quantile
    refuses a sound run now and then (PERF.md section 7 (f))."""
    if JUDGED is None or len(tokens) < JUDGED[1].get("min_positions", 0):
        return reference_forward(params, tokens, model, rows)[0]
    served_many, tol = JUDGED
    ref, margins = reference_forward(params, tokens, model,
                                     np.arange(len(tokens)))
    margins = np.asarray(margins)
    for group, (at, served) in served_many(params, tokens).items():
        at = np.asarray(at)
        got = judge(served, ref[at], margins[at], tol)
        sys.stderr.write(json.dumps({"judged": group, **got}) + "\n")
        if not got["ok"]:
            raise NotCorrect(
                f"{group}: the {tol['clean_quantile']} quantile of "
                f"{got['positions']} positions' RMS differences is "
                f"{got['rms_clean']:.4f} (limit rms_clean "
                f"{tol['rms_clean']}), the worst {got['rms']:.4f} "
                f"(limit rms {tol['rms']})")
    return ref[np.asarray(rows)]


def judge(served: np.ndarray, ref: np.ndarray, margins: np.ndarray,
          tol: Dict[str, Any]) -> Dict[str, Any]:
    """The comparison that knows of routing (``tolerance``'s keys),
    over MANY positions (``reference_forward`` gives every position of
    a prompt for the price of one): where a rounding difference swapped
    a token's 6th and 7th expert the logits differ by as much as a
    fault's would, and no margin tells those positions from the rest
    (the served scores' own error is above most margins), so the
    judgement is of the positions' distribution. The
    ``clean_quantile`` of the positions' RMS differences is held to
    ``rms_clean`` (the positions no swap touched: a precision below the
    stated one moves every position, these too) and the worst position
    to ``rms`` (logits that have nothing to do with the reference's).
    The share of positions with a margin under ``margin_eps`` is
    reported."""
    rms = np.asarray(jnp.sqrt(jnp.mean(jnp.square(
        jnp.asarray(served, jnp.float32) - ref), -1)))
    clean = float(np.quantile(rms, tol["clean_quantile"], method="higher"))
    worst = float(rms.max())
    return {"ok": bool(clean <= tol["rms_clean"] and worst <= tol["rms"]),
            "rms_clean": clean, "rms": worst, "positions": int(rms.size),
            "near_tie_share": float(
                (np.asarray(margins) < tol["margin_eps"]).mean())}
