"""The plain reference: a decoder-only transformer of the ``xing4_0``
block (Xing4.0-29B-A4B, README beside this file) in straightforward
``jax.numpy`` and float32 under ``jax.default_matmul_precision
("highest")``: no cache, no kernel, no batching, one sequence at a
time, the attention UNABSORBED (K and V expanded from the latent for
every token), a loop over the experts, the Sinkhorn projection written
as the steps it is. It imports neither ``llmq_tpu`` nor ``adapter.py``.

A token's residual is ``X`` (n, C), n = ``hc_mult`` streams. The
embedding is copied into every stream. A layer has two SITES (attention,
feed-forward); with ``F`` the sub-layer behind its own RMSNorm::

    x~          = vec(X) / rms(vec(X))       over all n C values, no weight
    [p | q | r] = x~ Phi
    H_pre       = sigmoid(a_pre p + b_pre)
    H_post      = 2 sigmoid(a_post q + b_post)
    A           = clip(a_res mat(r) + b_res, clamp_min, clamp_max)
    M_0 = exp(A);  repeat hc_sinkhorn_iters times:
        M = M / (row sums + hc_eps);  M = M / (column sums + hc_eps)
    u           = sum_i H_pre[i] X[i]
    X'[i]       = sum_j M[i, j] X[j] + H_post[i] F(u)

After the last layer HELD the streams are summed, then the final norm
and the head. ``F`` of the attention site is DeepSeek-V3's latent
attention with a low-rank query, the 64 rotary lanes turned by YaRN's
frequencies (cos and sin times ``mscale / mscale_all_dim`` = 1) and the
scores scaled by ``(dn + dr) ** -0.5 * m * m``, m = 0.1 mscale_all_dim
ln(factor) + 1; ``F`` of the feed-forward site is a dense SwiGLU in the
leading layers held and after them sigmoid scores, the top k of score +
bias (the bias chooses only), the chosen scores renormalised and
scaled, plus the shared expert.

Departures from the paper or the keys, each under ``assumed`` in the
configuration file with a CPU test of its own (``tests/test_xing.py``):
rows before columns in a Sinkhorn step, ``hc_eps`` joining each sum;
the flattened norm carries no weight; the fan-out copies and the
collapse sums; the YaRN temperature squared multiplies the softmax
scale (DeepSeek-V3's reading of the ``rope_scaling`` keys). The
multi-token-prediction layer is not part of the main model's logits and
is left out. The weights are random; the tree holds the rotary rows
de-interleaved, so the rotation here is of the two halves.

It reads the served parameter tree (``llmq_tpu/models/xing.py``: the
DeepSeek-V3 tree plus the group ``hc``, float32, stacked (layer,
site)), upcasting ONE layer — of a routed layer one expert — at a time,
and takes the attention a block of queries at a time, so that a
17k-token sequence at the published widths fits beside 9.6 GB of served
weights.

Routing makes the comparison harder than a dense block's (a rounding
difference swaps a token's 4th and 5th expert): ``judge`` is
``families/deepseek_v3``'s comparison over MANY positions, and while
``JUDGED`` is set (``adapter.serving_path`` sets it) ``reference_logits``
holds the serving path to it over a sequence of its own of
``tolerance.judged_tokens`` tokens — long enough to pass
``original_max_position_embeddings`` (where YaRN's table differs from
the plain one) and to decode at a context of 16k through the latent
pool — before it answers the harness. ``lowp=True`` is the control one
precision down (``LOWP``): the router's product in bfloat16, the streams
rounded to bfloat16 at every site, the Sinkhorn loop cut to 5 steps, the
cached latent and RoPE key rounded to 8 bits (float8_e4m3, as the family
``deepseek_v3``'s control) and every bfloat16 matrix rounded to 8 bits
under one scale a matrix. The limits have to refuse it; what each part
reads alone is in the configuration's ``tolerance.why`` (on the chip the
three float32 parts' lower precision reads UNDER the served path's own
bfloat16 products and, at contexts of thousands of keys, the 8-bit cache
barely above it: the limit refuses the control by its weights, and
``tests/test_xing.py`` refuses each part alone on the CPU at float32).
"""

from __future__ import annotations

import json
import math
import sys
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Queries the attention takes at a time.
Q_BLOCK = 512
#: What the control holds one precision down (``lowp=True``: all of it;
#: a tuple of these names: those alone), and its Sinkhorn steps.
LOWP = ("router", "streams", "sinkhorn", "latent", "weights")
LOWP_ITERS = 5
ATTN, FFN = 0, 1


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_table(model: Dict[str, Any]) -> Tuple[np.ndarray, float, float]:
    """(frequency of each rotary pair, the factor on cos and sin, the
    factor on the softmax scale) from ``rope_theta``, ``qk_rope_head_dim``
    and ``rope_scaling`` (None: the plain table, 1, 1)."""
    dim, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    half = dim // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    rs = model.get("rope_scaling")
    if rs is None:
        return freqs.astype(np.float32), 1.0, 1.0
    if rs.get("type") != "yarn":
        raise ValueError(f"rope_scaling type {rs.get('type')!r}")
    factor = float(rs["factor"])
    orig = rs["original_max_position_embeddings"]

    def pair(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair(rs.get("beta_fast", 32))), 0)
    hi = min(math.ceil(pair(rs.get("beta_slow", 1))), half - 1)
    gamma = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    m_all = mscale(rs.get("mscale_all_dim", 0.0))
    return ((freqs / factor * gamma + freqs * (1 - gamma)).astype(np.float32),
            mscale(rs.get("mscale", 1.0)) / m_all, m_all * m_all)


def _rope(x, freqs, factor):
    # x: (T, H, D); rotate the two halves of D by position-dependent angles.
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
    c, s = jnp.cos(ang)[:, None, :] * factor, jnp.sin(ang)[:, None, :] * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _w8(x, on: bool = True):
    """A matrix as float32 — through 8 bits (float8_e4m3 under ONE scale
    a matrix, its largest magnitude at the format's 448) where ``on``:
    the control's weights, one precision below the bfloat16 the
    configuration states."""
    x = _f32(x)
    if not on or x.ndim < 2:
        return x
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return _f32((x / scale).astype(jnp.float8_e4m3fn)) * scale


def _take(tree, l, names, w8: bool = False):
    return {k: _w8(jax.lax.dynamic_index_in_dim(tree[k], l, 0,
                                                keepdims=False), w8)
            for k in names}


# -- a site ---------------------------------------------------------------------

@partial(jax.jit, static_argnames=("iters", "eps", "clamp", "norm_eps"))
def site_open(x, hc, l, s, *, iters, eps, clamp, norm_eps):
    """Site ``s`` of layer ``l`` over the streams x (T, n, C): (u (T, C),
    H_post (T, n), H_res (T, n, n))."""
    T, n, C = x.shape
    w = {k: hc[k][l, s] for k in ("phi", "alpha", "bias")}
    flat = x.reshape(T, n * C)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + norm_eps)
    pqr = flat @ w["phi"]
    p, q, r = pqr[:, :n], pqr[:, n:2 * n], pqr[:, 2 * n:]
    b_pre, b_post, b_res = (w["bias"][:n], w["bias"][n:2 * n],
                            w["bias"][2 * n:])
    h_pre = jax.nn.sigmoid(w["alpha"][0] * p + b_pre)
    h_post = 2.0 * jax.nn.sigmoid(w["alpha"][1] * q + b_post)
    a = jnp.clip((w["alpha"][2] * r + b_res).reshape(T, n, n), *clamp)
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)      # rows
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)      # columns
    return jnp.einsum("tn,tnc->tc", h_pre, x), h_post, m


@partial(jax.jit, static_argnames=("lowp",))
def site_shut(x, h_post, h_res, y, *, lowp=False):
    out = (jnp.einsum("tij,tjc->tic", h_res, x)
           + h_post[:, :, None] * y[:, None, :])
    return _f32(out.astype(jnp.bfloat16)) if lowp else out


# -- the sub-layers ---------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_heads", "rank", "dn", "dr", "eps",
                                   "scale", "factor", "lowp", "w8"))
def _attention(u, layers, l, freqs, *, n_heads, rank, dn, dr, eps, scale,
               factor, lowp=False, w8=False):
    """F of the attention site: u (T, C) -> (T, C)."""
    w = _take(layers, l, ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a",
                          "kv_norm", "wkv_b", "wo"), w8)
    T = u.shape[0]
    x = _rms(u, w["attn_norm"], eps)
    q = (_rms(x @ w["wq_a"], w["q_norm"], eps) @ w["wq_b"]).reshape(
        T, n_heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], freqs, factor)], -1)
    kva = x @ w["wkv_a"]
    c = _rms(kva[:, :rank], w["kv_norm"], eps)
    k_rope = _rope(kva[:, None, rank:], freqs, factor)          # (T, 1, dr)
    if lowp:                        # what the cache holds, in 8 bits
        c = _f32(c.astype(jnp.float8_e4m3fn))
        k_rope = _f32(k_rope.astype(jnp.float8_e4m3fn))
    kv = (c @ w["wkv_b"]).reshape(T, n_heads, -1)               # [k_nope | v]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (T, n_heads, dr))], -1)
    v = kv[..., dn:]
    blocks = -(-T // Q_BLOCK)
    q = jnp.pad(q, ((0, blocks * Q_BLOCK - T), (0, 0), (0, 0)))

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("thd,shd->hts", qb, k) * scale
        seen = (jnp.arange(T)[None, :]
                <= (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None])
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(one, jnp.arange(blocks)).reshape(blocks * Q_BLOCK, -1)
    return o[:T] @ w["wo"]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("eps", "w8"))
def _dense_ffn(u, norm, dense, l, *, eps, w8=False):
    w = _take(dense, l, ("w_gate", "w_up", "w_down"), w8)
    return _swiglu(_rms(u, _f32(norm[l]), eps), w["w_gate"], w["w_up"],
                   w["w_down"])


@partial(jax.jit, static_argnames=("top_k", "scale", "norm_topk", "eps",
                                   "lowp", "w8"))
def _routed_ffn(u, norm, moe, we_gate_up, we_down, l, i, *, top_k, scale,
                norm_topk, eps, lowp, w8=False):
    """F of layer ``l``'s feed-forward site (the ``i``-th routed one;
    ``we_*``: ITS experts' leaves). Returns (y, margin (T,): the k-th
    selection score minus the (k+1)-th)."""
    x = _rms(u, _f32(norm[l]), eps)
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
        gu = _w8(we_gate_up[e], w8)
        y = _swiglu(x, gu[:, :F], gu[:, F:], _w8(we_down[e], w8))
        return acc + gates[:, e, None] * y

    y = jax.lax.fori_loop(0, E, one, jnp.zeros_like(u))
    y = y + _swiglu(x, _w8(moe["ws_gate"][i], w8), _w8(moe["ws_up"][i], w8),
                    _w8(moe["ws_down"][i], w8))
    return y, ranked[:, top_k - 1] - ranked[:, top_k]


@partial(jax.jit, static_argnames=("eps", "w8"))
def _head(final_norm, lm_head, x, rows, *, eps, w8=False):
    return _rms(jnp.sum(x[rows], axis=1), _f32(final_norm),
                eps) @ _w8(lm_head, w8)


@partial(jax.jit, static_argnames=("w8",))
def _embed(embed, tokens, *, w8=False):
    rows = _f32(embed[tokens])
    if not w8:
        return rows
    scale = _f32(jnp.max(jnp.abs(embed))) / 448.0 + 1e-30
    return _f32((rows / scale).astype(jnp.float8_e4m3fn)) * scale


def layers_held(model: Dict[str, Any]) -> Tuple[int, int]:
    """(layers this chip holds, the dense ones among them, which lead):
    ``dense_layers_held`` where the file cuts the depth, else the
    published ``first_k_dense_replace``."""
    return (model["num_hidden_layers"],
            model.get("dense_layers_held", model["first_k_dense_replace"]))


def reference_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                      rows, lowp=False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(float32 logits ``(len(rows), V)`` of one sequence ``tokens``
    ``(T,)`` at the positions ``rows``, margins ``(len(rows),)``: each
    position's smallest k-th-to-(k+1)-th selection margin over the
    routed layers). ``lowp``: True, or the names of ``LOWP`` to hold one
    precision down."""
    low = set(LOWP if lowp is True else lowp or ())
    if low - set(LOWP):
        raise ValueError(f"lowp {sorted(low - set(LOWP))}: of {LOWP}")
    L, Ld = layers_held(model)
    eps = float(model["rms_norm_eps"])
    if model.get("n_group", 1) != 1 or model.get("q_lora_rank") is None:
        raise ValueError("the reference is written for a low-rank query "
                         "and no group limit")
    n = int(model["hc_mult"])
    freqs, factor, temper = yarn_table(model)
    site = dict(iters=(LOWP_ITERS if "sinkhorn" in low
                       else int(model["hc_sinkhorn_iters"])),
                eps=float(model["hc_eps"]),
                clamp=(float(model["mhc_h_res_clamp_min"]),
                       float(model["mhc_h_res_clamp_max"])), norm_eps=eps)
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision("highest"):
        w8 = "weights" in low
        h = _embed(params["embed"], jnp.asarray(tokens, jnp.int32), w8=w8)
        x = jnp.broadcast_to(h[:, None], (h.shape[0], n, h.shape[1]))
        margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
        norm = params["layers"]["mlp_norm"]
        for l in range(L):
            u, h_post, h_res = site_open(x, params["hc"], l, ATTN, **site)
            y = _attention(
                u, params["layers"], jnp.int32(l), jnp.asarray(freqs),
                n_heads=model["num_attention_heads"],
                rank=model["kv_lora_rank"], dn=dn, dr=dr, eps=eps,
                scale=float((dn + dr) ** -0.5 * temper), factor=factor,
                lowp="latent" in low, w8=w8)
            x = site_shut(x, h_post, h_res, y, lowp="streams" in low)
            u, h_post, h_res = site_open(x, params["hc"], l, FFN, **site)
            if l < Ld:
                y = _dense_ffn(u, norm, params["dense"], jnp.int32(l),
                               eps=eps, w8=w8)
            else:
                moe = params["moe"]
                y, m = _routed_ffn(
                    u, norm, {k: v for k, v in moe.items()
                              if not k.startswith("we_")},
                    moe["we_gate_up"][l - Ld], moe["we_down"][l - Ld],
                    jnp.int32(l), jnp.int32(l - Ld),
                    top_k=model["num_experts_per_tok"],
                    scale=float(model["routed_scaling_factor"]),
                    norm_topk=bool(model["norm_topk_prob"]), eps=eps,
                    lowp="router" in low, w8=w8)
                margin = jnp.minimum(margin, m)
            x = site_shut(x, h_post, h_res, y, lowp="streams" in low)
        return (_head(params["final_norm"], params["lm_head"], x, rows,
                      eps=eps, w8=w8), margin[rows])


class NotCorrect(AssertionError):
    """The serving path's logits are not the reference's, by ``judge``."""


#: ``(served_many, tolerance)`` while the family's serving path is under
#: the harness's check, else ``None``. ``served_many(params, tokens) ->
#: {group: (rows, logits (len(rows), V))}``: the serving path's float32
#: logits at MANY positions ``rows`` of the one sequence ``tokens``, a
#: group for each way of getting there (a prefill's positions; decode
#: steps through the latent pool; mixed steps).
JUDGED: Optional[Tuple[Callable[..., Dict[str, Any]], Dict[str, Any]]] = None
#: Set once the judged sequence of this process has been held.
_HELD = False


def judged_sequence(tokens, n: int, vocab: int) -> np.ndarray:
    """The family's own judged sequence of ``n`` tokens: drawn from the
    harness's prompt ``tokens`` (which the run's seed drew), so the same
    seed judges the same sequence and another seed another."""
    rng = np.random.default_rng(np.asarray(tokens, np.uint32))
    return rng.integers(3, vocab, n, dtype=np.int32)


def judged_groups(params, tokens, model: Dict[str, Any],
                  served: Dict[str, Any], tol: Dict[str, Any], lowp=()):
    """``(group, verdict)`` for each group of ``served``
    (``served_many``'s result over ``tokens``) held to ``judge`` against
    ONE reference pass over the positions of all groups; with ``lowp``
    (names of ``LOWP``) the CONTROL stands in the serving path's place
    at the same positions — the readings ``tolerance`` is written from
    (``scripts/family_logits_probe.py``)."""
    at_all = np.unique(np.concatenate(
        [np.asarray(at) for at, _ in served.values()]))
    ref, margins = reference_forward(params, tokens, model, at_all)
    control = (reference_forward(params, tokens, model, at_all,
                                 lowp=tuple(lowp))[0] if lowp else None)
    margins = np.asarray(margins)
    for group, (at, got) in served.items():
        idx = np.searchsorted(at_all, np.asarray(at))
        yield group, judge(got if control is None else control[idx],
                           ref[idx], margins[idx], tol)


def hold(params, tokens, model: Dict[str, Any]) -> None:
    """Every group of ``JUDGED``'s ``served_many`` over ``tokens`` held
    to ``judge`` (one line a group on standard error); ``NotCorrect``
    for a group that fails."""
    served_many, tol = JUDGED
    for group, got in judged_groups(params, tokens, model,
                                    served_many(params, tokens), tol):
        sys.stderr.write(json.dumps(
            {"judged": group, "tokens": len(tokens), **got}) + "\n")
        if not got["ok"]:
            raise NotCorrect(
                f"{group} of {len(tokens)} tokens: the "
                f"{tol['clean_quantile']} quantile of {got['positions']} "
                f"positions' RMS differences is {got['rms_clean']:.4f} "
                f"(limit rms_clean {tol['rms_clean']}), the worst "
                f"{got['rms']:.4f} (limit rms {tol['rms']})")


def reference_logits(params: Dict[str, Any], tokens, model: Dict[str, Any],
                     rows) -> jnp.ndarray:
    """The family's surface: ``model`` is the configuration file's
    ``model`` block (``shapes.MODEL_KEYS``). While ``JUDGED`` is set, the
    first call first holds the serving path's groups to ``judge`` over
    ``judged_sequence`` (``tolerance.judged_tokens`` tokens) and raises
    ``NotCorrect`` for one that fails: the run ends there, before the
    server is built, and prints no result."""
    global _HELD
    if JUDGED is not None and not _HELD:
        _HELD = True
        n = int(JUDGED[1].get("judged_tokens", 0))
        if n:
            hold(params, judged_sequence(tokens, n, model["vocab_size"]),
                 model)
    return reference_forward(params, tokens, model, rows)[0]


def judge(served: np.ndarray, ref: np.ndarray, margins: np.ndarray,
          tol: Dict[str, Any]) -> Dict[str, Any]:
    """``families/deepseek_v3/reference.judge``: over MANY positions the
    ``clean_quantile`` of the positions' RMS differences is held to
    ``rms_clean`` (the positions no swapped expert touched: a precision
    below the stated one moves these too) and the worst position to
    ``rms`` (logits that have nothing to do with the reference's). The
    share of positions with a margin under ``margin_eps`` is
    reported."""
    rms = np.asarray(jnp.sqrt(jnp.mean(jnp.square(
        jnp.asarray(served, jnp.float32) - jnp.asarray(ref, jnp.float32)),
        -1)))
    clean = float(np.quantile(rms, tol["clean_quantile"], method="higher"))
    worst = float(rms.max())
    return {"ok": bool(clean <= tol["rms_clean"] and worst <= tol["rms"]),
            "rms_clean": clean, "rms": worst, "positions": int(rms.size),
            "median": float(np.median(rms)),
            "near_tie_share": float(
                (np.asarray(margins) < tol["margin_eps"]).mean())}
