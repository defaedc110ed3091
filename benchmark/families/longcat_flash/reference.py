"""The plain reference: a decoder-only transformer of the LongCat-Flash
block (``model_type: longcat_flash`` as the public
``modeling_longcat_flash.py`` describes it), in straightforward
``jax.numpy`` and float32: the UNABSORBED equations (K and V expanded
from the latent for every token), no cache, no kernel, no batching, a
loop over the experts, one sequence at a time,
``jax.default_matmul_precision("highest")``. One layer, N and N' its
four RMSNorms:

    h1 = x  + MLA_0(N_0(x))            u = N'_0(h1)
    m  = Routed(u)                     # the shortcut: reads what FFN_0
    h2 = h1 + FFN_0(u)                 #   reads, is added at the end
    h3 = h2 + MLA_1(N_1(h2))
    y  = h3 + FFN_1(N'_1(h3)) + m

    MLA_i(x): c_q = RMSNorm(x W_qa);  [q^nope ; q^rope] = s_q c_q W_qb,
              s_q = sqrt(hidden / q_lora_rank)
              [c' ; k'] = x W_kva;  c = RMSNorm(c');  k^rope = RoPE(k'),
              one for all heads, NOT scaled
              [k^nope ; v] = s_kv c W_kvb, s_kv = sqrt(hidden /
              kv_lora_rank);  score / sqrt(dn + dr);  concat_h(o_h) W_o
    Routed(u): p = softmax(u W_r) over all router_experts +
               zero_expert_num outputs;  S = top-k of (p + b), b chooses
               only;  g_e = scale p_e, NOT renormalised;
               m = sum_{e in S, e real} g_e SwiGLU_e(u)
                   + sum_{e in S, e zero-compute} g_e u

**The share.** The configuration gives this chip ``n_routed_experts``
of the router's ``router_experts`` real experts (``expert_share``:
which of the equal shares; ``held`` below) and ``vocab_size`` rows of
the vocabulary. The reference is given the same share: it routes over
ALL outputs for itself, adds the held experts' and the zero-compute
experts' terms and leaves out what the experts held elsewhere would
have added — that partial ``m`` goes on to the next layer, as in the
program. With ``held`` all of the router's experts it is the uncut
layer (``tests/test_moe_share.py`` adds the shares up to it).

It shares no code with ``llmq_tpu`` and none with ``adapter.py``. It
reads the served parameter tree (``layers``: the attentions' leaves
stacked over the 2 L attentions, attention i of layer l at 2 l + i;
``ffn``: the dense SwiGLUs likewise; ``moe``: the routers stacked over
the layers and, a leaf a layer, the HELD experts' matrices, gate and up
side by side in ``we_gate_up``), upcasting one attention, one SwiGLU or
one expert at a time, so that it fits beside 10 GB of served weights.
Departures from the published model: none in the mathematics. The
weights are random. The tree holds the rotary rows of W_qb and W_kva
de-interleaved (the program's loader permutes a published checkpoint
once), so the rotation here is of the two halves.

Routing makes the comparison harder than a dense block's: a rounding
difference can swap a token's 12th and 13th choice. The reference also
returns, for every position asked for, the smallest margin between its
k-th and (k+1)-th selection score over the layers (``margins``), and
``judge`` is the comparison over many positions that tells a swap from
a fault (``configs/*.json`` ``tolerance`` has the numbers and the
reasons). As in ``families/deepseek_v3``: ``harness/child.py``
``check_logits`` holds the worst of the 8 positions it drives to
``tolerance.rms`` and calls no family's ``judge``, so while ``JUDGED``
is set (``adapter.serving_path`` sets it), ``reference_logits`` holds
every position of the prompt and 128 decode positions through the
latent cache to ``judge``, and a group that fails raises
``NotCorrect``: the run ends there and prints no result.

``lowp=True`` is the same reference with the router's product in
bfloat16 and the latents rounded to 8 bits (float8_e4m3): the nearest
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


def _take(tree, a):
    return {k: _f32(jax.lax.dynamic_index_in_dim(v, a, 0, keepdims=False))
            for k, v in tree.items()}


@partial(jax.jit, static_argnames=("n_heads", "rank", "dn", "dr", "s_q",
                                   "s_kv", "eps", "theta", "lowp"))
def _attention(h, layers, a, *, n_heads, rank, dn, dr, s_q, s_kv, eps,
               theta, lowp):
    w = _take(layers, a)
    T = h.shape[0]
    x = _rms(h, w["attn_norm"], eps)
    c_q = _rms(x @ w["wq_a"], w["q_norm"], eps)
    q = s_q * (c_q @ w["wq_b"]).reshape(T, n_heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    kva = x @ w["wkv_a"]
    c = _rms(kva[:, :rank], w["kv_norm"], eps)
    k_rope = _rope(kva[:, None, rank:], theta)             # (T, 1, dr)
    if lowp:
        c = _f32(c.astype(jnp.float8_e4m3fn))
        k_rope = _f32(k_rope.astype(jnp.float8_e4m3fn))
    kv = s_kv * (c @ w["wkv_b"]).reshape(T, n_heads, -1)   # [k_nope | v]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (T, n_heads, dr))], -1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(dn + dr))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), kv[..., dn:])
    return h + o.reshape(T, -1) @ w["wo"]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("eps",))
def _norm(h, ffn, a, *, eps):
    return _rms(h, _f32(ffn["mlp_norm"][a]), eps)


@jax.jit
def _dense(x, ffn, a):
    w = _take({k: ffn[k] for k in ("w_gate", "w_up", "w_down")}, a)
    return _swiglu(x, w["w_gate"], w["w_up"], w["w_down"])


@partial(jax.jit, static_argnames=("first", "n_real", "top_k", "scale",
                                   "lowp"))
def _routed(u, router, bias, we_gate_up, we_down, *, first, n_real, top_k,
            scale, lowp):
    """The routed part of one layer for tokens u (T, D), normalised:
    ``we_*`` hold the experts ``first .. first + len - 1`` of the
    router's ``n_real`` real ones; an output at or above ``n_real`` is
    a zero-compute (identity) expert. Returns (m, margin (T,): the
    k-th selection score minus the (k+1)-th)."""
    w_r = _f32(router)
    if lowp:
        logits = _f32(jnp.dot(u.astype(jnp.bfloat16),
                              w_r.astype(jnp.bfloat16)))
    else:
        logits = u @ w_r
    p = jax.nn.softmax(logits, -1)                         # (T, R)
    sel = p + _f32(bias)
    order = jnp.argsort(-sel, axis=-1)                     # ties: low index
    chosen = order[:, :top_k]
    ranked = jnp.take_along_axis(sel, order[:, :top_k + 1], -1)
    T, R = p.shape
    gates = jnp.zeros((T, R), jnp.float32).at[
        jnp.arange(T)[:, None], chosen].set(
        scale * jnp.take_along_axis(p, chosen, -1))
    F = we_down.shape[1]

    def one(e, acc):
        gu = _f32(we_gate_up[e])
        y = _swiglu(u, gu[:, :F], gu[:, F:], _f32(we_down[e]))
        return acc + jax.lax.dynamic_index_in_dim(
            gates, first + e, 1, keepdims=True) * y

    m = jax.lax.fori_loop(0, we_down.shape[0], one, jnp.zeros_like(u))
    m = m + jnp.sum(gates[:, n_real:], -1, keepdims=True) * u
    return m, ranked[:, top_k - 1] - ranked[:, top_k]


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, h, rows, *, eps):
    return _rms(h[rows], _f32(final_norm), eps) @ _f32(lm_head)


def held_experts(model: Dict[str, Any]) -> Tuple[int, int]:
    """(first, end) of the router's real experts this chip holds."""
    share, n = model["expert_share"], model["n_routed_experts"]
    if share["chips"] * n != model["router_experts"]:
        raise ValueError(f"{share['chips']} shares of {n} experts are not "
                         f"the router's {model['router_experts']}")
    return share["index"] * n, (share["index"] + 1) * n


def reference_layer(params: Dict[str, Any], l: int, h, model: Dict[str, Any],
                    lowp: bool = False):
    """Double layer ``l`` over one sequence's stream h (T, D). Returns
    (y, margin (T,))."""
    if model.get("zero_expert_type", "identity") != "identity":
        raise ValueError("the reference is written for identity "
                         "zero-compute experts")
    eps, D = float(model["rms_norm_eps"]), model["hidden_size"]
    s_q = ((D / model["q_lora_rank"]) ** 0.5
           if model["mla_scale_q_lora"] else 1.0)
    s_kv = ((D / model["kv_lora_rank"]) ** 0.5
            if model["mla_scale_kv_lora"] else 1.0)
    attn = dict(n_heads=model["num_attention_heads"],
                rank=model["kv_lora_rank"], dn=model["qk_nope_head_dim"],
                dr=model["qk_rope_head_dim"], s_q=s_q, s_kv=s_kv, eps=eps,
                theta=float(model["rope_theta"]), lowp=lowp)
    moe, ffn = params["moe"], params["ffn"]
    a0, a1 = jnp.int32(2 * l), jnp.int32(2 * l + 1)
    h1 = _attention(h, params["layers"], a0, **attn)
    u = _norm(h1, ffn, a0, eps=eps)
    m, margin = _routed(
        u, moe["router"][l], moe["router_bias"][l], moe["we_gate_up"][l],
        moe["we_down"][l], first=held_experts(model)[0],
        n_real=model["router_experts"], top_k=model["moe_topk"],
        scale=float(model["routed_scaling_factor"]), lowp=lowp)
    h2 = h1 + _dense(u, ffn, a0)
    h3 = _attention(h2, params["layers"], a1, **attn)
    return h3 + _dense(_norm(h3, ffn, a1, eps=eps), ffn, a1) + m, margin


def reference_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                      rows, lowp: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(float32 logits ``(len(rows), V)`` of one sequence ``tokens``
    ``(T,)`` at the positions ``rows``, margins ``(len(rows),)``: each
    position's smallest k-th-to-(k+1)-th selection margin over the
    layers)."""
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
        margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
        for l in range(model["num_layers"]):
            h, m = reference_layer(params, l, h, model, lowp)
            margin = jnp.minimum(margin, m)
        return (_head(params["final_norm"], params["lm_head"], h, rows,
                      eps=float(model["rms_norm_eps"])), margin[rows])


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
    judged (``families/deepseek_v3/README.md`` has the reason)."""
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
    over MANY positions: where a rounding difference swapped a token's
    k-th and (k+1)-th choice the logits differ by as much as a fault's
    would, so the judgement is of the positions' distribution. The
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
