"""The plain reference: a decoder-only transformer of the Arcee
``afmoe`` block (``model_type: afmoe`` as the public
``modeling_afmoe.py`` describes it), in straightforward ``jax.numpy``
and float32: full-sequence forward, the window as a MASK, no cache, no
kernel, no batching, a loop over the experts, one sequence at a time,
``jax.default_matmul_precision("highest")``. One layer of kind ``t =
layer_types[l]``::

    h0 = E[token] * sqrt(hidden_size)                      # mup_enabled
    x  = rms(h; g_in)
    q  = rms_head(x Wq; g_q)   k = rms_head(x Wk; g_k)   v = x Wv
    t == sliding_attention:  q, k = rope(q, k)  (rotate-half); full: none
    key s visible to query p:  s <= p, and sliding: s > p - window
    a  = softmax(q k^T / sqrt(head_dim)) v  *  sigmoid(x Wg)
    h  = h + rms(a Wo; g_post_attn)
    y  = rms(h; g_pre_mlp)
    l <  num_dense_layers:  f = (silu(y W1) * (y W3)) W2
    l >= num_dense_layers:  s = sigmoid(y Wr);  S = top-k of (s + b), b
                            chooses only;  g = s[S] / (sum s[S] + 1e-20)
                            * route_scale;
                            f = shared(y) + sum_{e in S} g_e expert_e(y)
    h  = h + rms(f; g_post_mlp)
    logits = rms(h; g_final) W_head

**The share.** The configuration gives this chip ``num_experts`` of the
router's ``router_experts`` experts (``expert_share``: which of the
equal shares; ``held_experts`` below) and ``vocab_size`` rows of the
vocabulary. The reference is given the same share: it routes over ALL
the router's outputs for itself, adds the held experts' terms and the
shared expert's, and leaves out what the experts held elsewhere would
have added — that partial ``f`` goes on, as in the program. With all of
the router's experts held it is the uncut layer
(``tests/test_moe_share.py`` adds the shares up to it).

It shares no code with ``llmq_tpu`` and none with ``adapter.py``. It
reads the served parameter tree (``layers``: the attention's leaves
stacked over the layers; ``dense``: the leading layers' SwiGLUs;
``moe``: router, selection bias and shared expert stacked over the
routed layers and, a leaf a layer, the HELD experts' matrices, gate and
up side by side in ``we_gate_up``), upcasting one layer's or one
expert's matrices at a time, and runs the attention a BLOCK of queries
at a time (``Q_BLOCK``: the scores of 6k+ tokens at once are 48 heads x
T x T float32), each block against the keys it can see.

Departures from the published code, each at its line: none in the
mathematics; the weights are random; the selection bias is the
configuration's draw (a trained buffer outside ``config.json``).

Routing makes the comparison harder than a dense block's: a rounding
difference can swap a token's 4th and 5th choice. ``reference_forward``
also returns each position's smallest margin between its k-th and
(k+1)-th selection score over the routed layers, and ``judge`` is the
comparison over many positions that tells a swap from a fault, as
``families/deepseek_v3``'s (its README has why ``harness/child.py``
``check_logits``' worst-of-8 cannot refuse a lower precision for a
routed model): while ``JUDGED`` is set (``adapter.serving_path`` sets
it), ``reference_logits`` holds its groups to ``judge`` first and
raises ``NotCorrect`` for one that fails — over the harness's own
prompt AND over a sequence of the family's own of
``tolerance.judged_tokens`` tokens (``judged_sequence``), long enough
to pass the window: the harness's prompts end below it, where a sliding
layer is a full layer with rotary positions and a window switched off,
off by one or read through a wrong ring entry would pass.

``lowp=True`` is the same reference with the router's product in
bfloat16 and K and V rounded to 8 bits (float8_e4m3): the nearest
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

SLIDING = "sliding_attention"
#: Queries the attention takes at a time.
Q_BLOCK = 512


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    # x: (T, H, D); rotate the two halves of D by position-dependent
    # angles (rotate-half, as the published code's rotate_half).
    T, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _take(tree, l, names):
    return {k: _f32(jax.lax.dynamic_index_in_dim(tree[k], l, 0,
                                                 keepdims=False))
            for k in names}


_ATTN = ("attn_norm", "post_attn_norm", "q_norm", "k_norm", "wq", "wk",
         "wv", "wg", "wo")


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd", "window",
                                   "theta", "eps", "lowp"))
def _attention(h, layers, l, *, n_heads, n_kv, hd, window, theta, eps,
               lowp):
    """h + rms(gated attention; g_post_attn) over one sequence's stream
    h (T, D). ``window``: 0 for a full layer (no rotary embedding, every
    earlier key), else the sliding layer's (rotary; a query sees
    ``window`` keys, itself counted)."""
    w = _take(layers, l, _ATTN)
    T = h.shape[0]
    x = _rms(h, w["attn_norm"], eps)
    q = _rms((x @ w["wq"]).reshape(T, n_heads, hd), w["q_norm"], eps)
    k = _rms((x @ w["wk"]).reshape(T, n_kv, hd), w["k_norm"], eps)
    v = (x @ w["wv"]).reshape(T, n_kv, hd)
    if window:
        q, k = _rope(q, theta), _rope(k, theta)
    if lowp:
        k = _f32(k.astype(jnp.float8_e4m3fn))
        v = _f32(v.astype(jnp.float8_e4m3fn))
    rep = n_heads // n_kv
    pos = jnp.arange(T)
    outs = []
    for lo in range(0, T, Q_BLOCK):
        hi = min(lo + Q_BLOCK, T)
        first = max(0, lo - window + 1) if window else 0    # keys it sees
        qb = q[lo:hi].reshape(hi - lo, n_kv, rep, hd)
        s = jnp.einsum("tgrd,sgd->grts", qb, k[first:hi]) / jnp.sqrt(
            jnp.float32(hd))
        qp, kp = pos[lo:hi, None], pos[None, first:hi]
        seen = kp <= qp
        if window:
            seen = seen & (kp > qp - window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, -1), v[first:hi])
        outs.append(o.reshape(hi - lo, n_heads * hd))
    a = jnp.concatenate(outs) * jax.nn.sigmoid(x @ w["wg"])
    return h + _rms(a @ w["wo"], w["post_attn_norm"], eps)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("eps",))
def _dense(h, layers, dense, l, *, eps):
    n = _take(layers, l, ("mlp_norm", "post_mlp_norm"))
    w = _take(dense, l, ("w_gate", "w_up", "w_down"))
    f = _swiglu(_rms(h, n["mlp_norm"], eps), w["w_gate"], w["w_up"],
                w["w_down"])
    return h + _rms(f, n["post_mlp_norm"], eps)


@partial(jax.jit, static_argnames=("first", "top_k", "scale", "renorm",
                                   "eps", "lowp"))
def _routed(h, layers, moe, l, i, we_gate_up, we_down, *, first, top_k,
            scale, renorm, eps, lowp):
    """Layer ``l``'s routed feed-forward (routed layer ``i``) between
    its two norms. ``we_*`` hold the experts ``first .. first + len -
    1`` of the router's. Returns (h', margin (T,): the k-th selection
    score minus the (k+1)-th)."""
    n = _take(layers, l, ("mlp_norm", "post_mlp_norm"))
    m = _take(moe, i, ("router", "router_bias", "ws_gate", "ws_up",
                       "ws_down"))
    y = _rms(h, n["mlp_norm"], eps)
    if lowp:
        logits = _f32(jnp.dot(y.astype(jnp.bfloat16),
                              m["router"].astype(jnp.bfloat16)))
    else:
        logits = y @ m["router"]
    s = jax.nn.sigmoid(logits)                             # (T, E)
    sel = s + m["router_bias"]                             # chooses only
    order = jnp.argsort(-sel, axis=-1)                     # ties: low index
    chosen = order[:, :top_k]
    ranked = jnp.take_along_axis(sel, order[:, :top_k + 1], -1)
    g = jnp.take_along_axis(s, chosen, -1)
    if renorm:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    T, E = s.shape
    gates = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], chosen].set(scale * g)
    F = we_down.shape[1]

    def one(e, acc):
        gu = _f32(we_gate_up[e])
        out = _swiglu(y, gu[:, :F], gu[:, F:], _f32(we_down[e]))
        return acc + jax.lax.dynamic_index_in_dim(
            gates, first + e, 1, keepdims=True) * out

    f = jax.lax.fori_loop(0, we_down.shape[0], one, jnp.zeros_like(y))
    f = f + _swiglu(y, m["ws_gate"], m["ws_up"], m["ws_down"])
    return (h + _rms(f, n["post_mlp_norm"], eps),
            ranked[:, top_k - 1] - ranked[:, top_k])


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, h, rows, *, eps):
    return _rms(h[rows], _f32(final_norm), eps) @ _f32(lm_head)


def held_experts(model: Dict[str, Any]) -> Tuple[int, int]:
    """(first, end) of the router's experts this chip holds."""
    share, n = model["expert_share"], model["num_experts"]
    if share["chips"] * n != model["router_experts"]:
        raise ValueError(f"{share['chips']} shares of {n} experts are not "
                         f"the router's {model['router_experts']}")
    return share["index"] * n, (share["index"] + 1) * n


def reference_layer(params: Dict[str, Any], l: int, h, model: Dict[str, Any],
                    lowp: bool = False):
    """Layer ``l`` over one sequence's stream h (T, D). Returns (h',
    margin (T,) — infinite for a dense layer)."""
    eps = float(model["rms_norm_eps"])
    sliding = model["layer_types"][l] == SLIDING
    h = _attention(
        h, params["layers"], jnp.int32(l), n_heads=model["num_attention_heads"],
        n_kv=model["num_key_value_heads"], hd=model["head_dim"],
        window=int(model["sliding_window"]) if sliding else 0,
        theta=float(model["rope_theta"]), eps=eps, lowp=lowp)
    # the held layers' dense ones: the file's ``dense_layers_held`` (the
    # cut holds fewer layers than the published model has dense ones)
    Ld = model.get("dense_layers_held", model["num_dense_layers"])
    if l < Ld:
        return (_dense(h, params["layers"], params["dense"], jnp.int32(l),
                       eps=eps),
                jnp.full((h.shape[0],), jnp.inf, jnp.float32))
    moe = params["moe"]
    stacked = {k: moe[k] for k in ("router", "router_bias", "ws_gate",
                                   "ws_up", "ws_down")}
    return _routed(
        h, params["layers"], stacked, jnp.int32(l), jnp.int32(l - Ld),
        moe["we_gate_up"][l - Ld], moe["we_down"][l - Ld],
        first=held_experts(model)[0], top_k=model["num_experts_per_tok"],
        scale=float(model["route_scale"]), renorm=bool(model["route_norm"]),
        eps=eps, lowp=lowp)


def reference_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                      rows, lowp: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(float32 logits ``(len(rows), V)`` of one sequence ``tokens``
    ``(T,)`` at the positions ``rows``, margins ``(len(rows),)``: each
    position's smallest k-th-to-(k+1)-th selection margin over the
    routed layers)."""
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
        if model.get("mup_enabled", True):
            h = h * jnp.sqrt(jnp.float32(model["hidden_size"]))
        margin = jnp.full((h.shape[0],), jnp.inf, jnp.float32)
        for l in range(model["num_hidden_layers"]):
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
#: group for each way of getting there (a prefill's positions; decode
#: steps through both kinds of cache). ``tolerance``: the
#: configuration's.
JUDGED: Optional[Tuple[Callable[..., Dict[str, Any]], Dict[str, Any]]] = None


def judged_sequence(tokens, n: int, vocab: int) -> np.ndarray:
    """The family's own judged sequence of ``n`` tokens: drawn from the
    harness's prompt ``tokens`` (which the run's seed drew), so the same
    seed judges the same sequence and another seed another."""
    rng = np.random.default_rng(np.asarray(tokens, np.uint32))
    return rng.integers(3, vocab, n, dtype=np.int32)


def _hold(params, tokens, model: Dict[str, Any]) -> jnp.ndarray:
    """Every group of ``JUDGED``'s ``served_many`` over ``tokens`` held
    to ``judge`` (one line a group on standard error). Returns the
    reference's logits at every position."""
    served_many, tol = JUDGED
    ref, margins = reference_forward(params, tokens, model,
                                     np.arange(len(tokens)))
    margins = np.asarray(margins)
    for group, (at, served) in served_many(params, tokens).items():
        at = np.asarray(at)
        got = judge(served, ref[at], margins[at], tol, at)
        sys.stderr.write(json.dumps({"judged": group, "tokens": len(tokens),
                                     **got}) + "\n")
        if not got["ok"]:
            raise NotCorrect(
                f"{group} of {len(tokens)} tokens: the "
                f"{tol['clean_quantile']} quantile of {got['positions']} "
                f"positions' RMS differences is {got['rms_clean']:.4f} "
                f"(limit rms_clean {tol['rms_clean']}; by context "
                f"{got['bands']}), the worst {got['rms']:.4f} (limit rms "
                f"{tol['rms']})")
    return ref


def reference_logits(params: Dict[str, Any], tokens, model: Dict[str, Any],
                     rows) -> jnp.ndarray:
    """The family's surface: ``model`` is the configuration file's
    ``model`` block (``shapes.MODEL_KEYS``). While ``JUDGED`` is set,
    each of its groups is held to ``judge`` first, over ``tokens`` and
    then over ``judged_sequence`` (``tolerance.judged_tokens`` of them,
    where the configuration states it), and ``NotCorrect`` is raised
    for one that fails. A sequence of fewer than
    ``tolerance.min_positions`` tokens is not judged and draws no
    sequence (``families/deepseek_v3/README.md`` has the reason)."""
    if JUDGED is None or len(tokens) < JUDGED[1].get("min_positions", 0):
        return reference_forward(params, tokens, model, rows)[0]
    ref = _hold(params, tokens, model)
    n = int(JUDGED[1].get("judged_tokens", 0))
    if n:
        _hold(params, judged_sequence(tokens, n, model["vocab_size"]), model)
    return ref[np.asarray(rows)]


#: A band of contexts is judged by its own limit where a group has this
#: many positions in it (a handful are no distribution).
BAND_MIN = 32


def judge(served: np.ndarray, ref: np.ndarray, margins: np.ndarray,
          tol: Dict[str, Any], at: Optional[np.ndarray] = None
          ) -> Dict[str, Any]:
    """The comparison that knows of routing (``tolerance``'s keys),
    over MANY positions: where a rounding difference swapped a token's
    k-th and (k+1)-th choice the logits differ by as much as a fault's
    would, so the judgement is of the positions' distribution. The
    ``clean_quantile`` of the positions' RMS differences is held to
    ``rms_clean`` (the positions no swap touched: a precision below the
    stated one moves every position, these too) and the worst position
    to ``rms`` (logits that have nothing to do with the reference's).
    Rounding averages out over the keys a query reads, so both the
    served path and a lower precision read lower behind a longer
    context: given the positions ``at``, each band of
    ``rms_clean_by_context`` (``[first position, limit]``, ascending)
    with ``BAND_MIN`` positions or more is held to its own limit as
    well. The share of positions with a margin under ``margin_eps`` is
    reported."""
    rms = np.asarray(jnp.sqrt(jnp.mean(jnp.square(
        jnp.asarray(served, jnp.float32) - ref), -1)))
    q = tol["clean_quantile"]
    clean = float(np.quantile(rms, q, method="higher"))
    worst = float(rms.max())
    bands: Dict[str, Any] = {}
    edges = tol.get("rms_clean_by_context") if at is not None else None
    for i, (first, limit) in enumerate(edges or ()):
        end = edges[i + 1][0] if i + 1 < len(edges) else np.inf
        inside = (np.asarray(at) >= first) & (np.asarray(at) < end)
        if inside.sum() >= BAND_MIN:
            bands[str(first)] = {
                "rms_clean": float(np.quantile(rms[inside], q,
                                               method="higher")),
                "limit": limit, "positions": int(inside.sum())}
    ok = (clean <= tol["rms_clean"] and worst <= tol["rms"]
          and all(b["rms_clean"] <= b["limit"] for b in bands.values()))
    return {"ok": bool(ok), "rms_clean": clean, "rms": worst,
            "positions": int(rms.size), "bands": bands,
            "near_tie_share": float(
                (np.asarray(margins) < tol["margin_eps"]).mean())}
