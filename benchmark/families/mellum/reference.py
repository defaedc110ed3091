"""The plain reference: a decoder-only transformer of the JetBrains
``mellum`` block (``model_type: mellum``, as its public ``config.json``
describes it), in straightforward ``jax.numpy`` and float32:
full-sequence forward, the window as a MASK, no cache, no kernel, no
batching, a loop over the experts, one sequence at a time,
``jax.default_matmul_precision("highest")``. One layer of kind ``t =
layer_types[l]``::

    x  = rms(h; g_in)
    q  = rms_head(x Wq; g_q)   k = rms_head(x Wk; g_k)   v = x Wv  # assumed
    q, k = rope_t(q, k)   rotate-half over all of head_dim, theta 500,000:
        t == sliding_attention:  f_i = theta^(-2i / head_dim)
        t == full_attention:     YaRN, from rope_parameters.full_attention
            (factor s, original length n0, beta_fast, beta_slow,
            attention_factor a):
            pair(b) = head_dim ln(n0 / (2 pi b)) / (2 ln theta)
            lo, hi  = floor(pair(beta_fast)), ceil(pair(beta_slow)),
                      clipped to [0, head_dim / 2 - 1]
            gamma_i = clip((i - lo) / (hi - lo), 0, 1)
            f'_i    = f_i / s * gamma_i + f_i (1 - gamma_i)
            cos, sin of (position * f'_i), both times a
    key s visible to query p:  s <= p, and sliding: s > p - window
    h  = h + softmax(q k^T / sqrt(head_dim)) v Wo
    y  = rms(h; g_mlp)
    s  = softmax(y Wr) over all num_experts;  S = top-k of s;
    g  = s[S] / sum s[S]                                 # norm_topk_prob
    h  = h + sum_{e in S} g_e (silu(y W_gate_e) * (y W_up_e)) W_down_e
    logits = rms(h; g_final) W_head

It shares no code with ``llmq_tpu`` and none with ``adapter.py``; YaRN
is written out here from the five published numbers. It reads the
served parameter tree (``layers``: the attention's leaves and the
router stacked over the layers; ``moe``: a leaf a layer, every expert's
gate and up side by side in ``we_gate_up``), upcasting one layer's or
one expert's matrices at a time, and runs the attention a BLOCK of
queries at a time (``Q_BLOCK``).

Departures from the published model, each at its line: the per-head
norm on q and k is ASSUMED (``qk_norm``: the config has no key; README);
``intermediate_size`` is read by no layer (every ``mlp_layer_types``
entry is ``sparse``); the "MTP head" of the catalog's ``described_as``
has no key and is left out; the weights are random.

Routing makes the comparison harder than a dense block's: a rounding
difference can swap a token's 8th and 9th choice. ``reference_forward``
also returns each position's smallest margin between its k-th and
(k+1)-th score over the layers, and ``judge`` is the comparison over
many positions that tells a swap from a fault, as
``families/afmoe``'s (and ``families/deepseek_v3/README.md`` has why
the harness's worst-of-8 cannot refuse a lower precision for a routed
model): while ``JUDGED`` is set (``adapter.serving_path`` sets it),
``reference_logits`` holds its groups to ``judge`` first and raises
``NotCorrect`` for one that fails — over the harness's own prompt AND
over a sequence of the family's own of ``tolerance.judged_tokens``
tokens (``judged_sequence``), long enough to pass the window and wrap
the ring, part of it through the ADOPTED path (a tail exported from one
row and imported into another).

``lowp=True`` is the same reference with the router's product in
bfloat16 and K and V rounded to 8 bits (float8_e4m3): the nearest
precision below what the configuration states, which the comparison
has to refuse (``lowp="router"`` / ``"kv"``: one of the two alone).

**The judgement is RELATIVE to that control** (``judge``,
``tolerance.control_ratio``). With top-8 of 64 softmax scores in each
of 12 layers nearly every position carries a swapped 8th choice of its
own or, through the attention, of an earlier position, so the level of
a group's RMS differences is the weights' swap rate before it is a
precision: over three seeds on the chip the served path's medians were
0.010-0.043 and the control's 0.055-0.090, the two ranges a hair apart —
but seed by seed, group by group, the served path read 0.11-0.54 of the
control over nineteen (README). So every judged run computes the control too and
holds the served path's ``clean_quantile`` to ``control_ratio`` of the
control's at the same positions; a path that IS the control reads 1.
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

SLIDING, FULL = "sliding_attention", "full_attention"
#: Queries the attention takes at a time.
Q_BLOCK = 512


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def inv_freq(rope: Dict[str, Any], head_dim: int) -> Tuple[np.ndarray, float]:
    """(the ``head_dim / 2`` rotary frequencies, what cos and sin are
    multiplied by) of one entry of ``rope_parameters``."""
    half = head_dim // 2
    theta = float(rope["rope_theta"])
    f = theta ** (-np.arange(half, dtype=np.float64) / half)
    if rope.get("rope_type", "default") == "default":
        return f.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    n0 = float(rope["original_max_position_embeddings"])

    def pair(turns):
        return head_dim * math.log(n0 / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair(float(rope["beta_fast"]))), 0)
    hi = min(math.ceil(pair(float(rope["beta_slow"]))), half - 1)
    gamma = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    f = f / float(rope["factor"]) * gamma + f * (1.0 - gamma)
    return f.astype(np.float32), float(rope["attention_factor"])


def _rope(x, freqs, factor):
    # x: (T, H, D); rotate the two halves of D (rotate-half).
    T, _, D = x.shape
    half = D // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    c = (jnp.cos(ang) * factor)[:, None, :]
    s = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _take(tree, l, names):
    return {k: _f32(jax.lax.dynamic_index_in_dim(tree[k], l, 0,
                                                 keepdims=False))
            for k in names if k in tree}


_ATTN = ("attn_norm", "q_norm", "k_norm", "wq", "wk", "wv", "wo")


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd", "window",
                                   "factor", "eps", "qk_norm", "lowp"))
def _attention(h, layers, l, freqs, *, n_heads, n_kv, hd, window, factor,
               eps, qk_norm, lowp):
    """h + attention over one sequence's stream h (T, D). ``window``:
    0 for a full layer, else the sliding layer's (a query sees
    ``window`` keys, itself counted); ``freqs`` / ``factor``: the rotary
    table of the layer's kind."""
    w = _take(layers, l, _ATTN)
    T = h.shape[0]
    x = _rms(h, w["attn_norm"], eps)
    q = (x @ w["wq"]).reshape(T, n_heads, hd)
    k = (x @ w["wk"]).reshape(T, n_kv, hd)
    v = (x @ w["wv"]).reshape(T, n_kv, hd)
    if qk_norm:                            # ASSUMED: no key in the config
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = _rope(q, freqs, factor), _rope(k, freqs, factor)
    if lowp in (True, "kv", "router+kv"):
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
    return h + jnp.concatenate(outs) @ w["wo"]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("top_k", "renorm", "eps", "lowp"))
def _routed(h, layers, l, we_gate_up, we_down, *, top_k, renorm, eps, lowp):
    """Layer ``l``'s routed feed-forward. Returns (h', margin (T,): the
    k-th score minus the (k+1)-th)."""
    n = _take(layers, l, ("mlp_norm", "router"))
    y = _rms(h, n["mlp_norm"], eps)
    if lowp in (True, "router", "router+kv"):
        logits = _f32(jnp.dot(y.astype(jnp.bfloat16),
                              n["router"].astype(jnp.bfloat16)))
    else:
        logits = y @ n["router"]
    s = jax.nn.softmax(logits, -1)                         # (T, E): ALL
    order = jnp.argsort(-s, axis=-1)                       # ties: low index
    chosen = order[:, :top_k]
    ranked = jnp.take_along_axis(s, order[:, :top_k + 1], -1)
    g = ranked[:, :top_k]
    if renorm:
        g = g / jnp.sum(g, -1, keepdims=True)
    T, E = s.shape
    gates = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], chosen].set(g)
    F = we_down.shape[1]

    def one(e, acc):
        gu = _f32(we_gate_up[e])
        out = _swiglu(y, gu[:, :F], gu[:, F:], _f32(we_down[e]))
        return acc + jax.lax.dynamic_index_in_dim(
            gates, e, 1, keepdims=True) * out

    f = jax.lax.fori_loop(0, E, one, jnp.zeros_like(y))
    return h + f, ranked[:, top_k - 1] - ranked[:, top_k]


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, h, rows, *, eps):
    return _rms(h[rows], _f32(final_norm), eps) @ _f32(lm_head)


def reference_layer(params: Dict[str, Any], l: int, h, model: Dict[str, Any],
                    lowp: bool = False):
    """Layer ``l`` over one sequence's stream h (T, D). Returns (h',
    margin (T,))."""
    eps = float(model["rms_norm_eps"])
    kind = model["layer_types"][l]
    if model["mlp_layer_types"][l] != "sparse":
        raise ValueError(f"layer {l}: {model['mlp_layer_types'][l]!r} "
                         f"feed-forward is not written (none is published)")
    freqs, factor = inv_freq(model["rope_parameters"][kind],
                             model["head_dim"])
    h = _attention(
        h, params["layers"], jnp.int32(l), jnp.asarray(freqs),
        n_heads=model["num_attention_heads"],
        n_kv=model["num_key_value_heads"], hd=model["head_dim"],
        window=int(model["sliding_window"]) if kind == SLIDING else 0,
        factor=factor, eps=eps, qk_norm=bool(model.get("qk_norm", True)),
        lowp=lowp)
    return _routed(
        h, params["layers"], jnp.int32(l), params["moe"]["we_gate_up"][l],
        params["moe"]["we_down"][l], top_k=model["num_experts_per_tok"],
        renorm=bool(model["norm_topk_prob"]), eps=eps, lowp=lowp)


def reference_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                      rows, lowp: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(float32 logits ``(len(rows), V)`` of one sequence ``tokens``
    ``(T,)`` at the positions ``rows``, margins ``(len(rows),)``: each
    position's smallest k-th-to-(k+1)-th margin over the layers)."""
    rows = jnp.asarray(rows, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
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
#: steps through both kinds of cache; the adopted path).
JUDGED: Optional[Tuple[Callable[..., Dict[str, Any]], Dict[str, Any]]] = None


def judged_sequence(tokens, n: int, vocab: int) -> np.ndarray:
    """The family's own judged sequence of ``n`` tokens: drawn from the
    harness's prompt ``tokens`` (which the run's seed drew), so the same
    seed judges the same sequence and another seed another."""
    rng = np.random.default_rng(np.asarray(tokens, np.uint32))
    return rng.integers(3, vocab, n, dtype=np.int32)


def _hold(params, tokens, model: Dict[str, Any]) -> jnp.ndarray:
    """Every group of ``JUDGED``'s ``served_many`` over ``tokens`` held
    to ``judge`` beside the control at the same positions (one line a
    group on standard error). Returns the reference's logits at every
    position."""
    served_many, tol = JUDGED
    every = np.arange(len(tokens))
    ref, margins = reference_forward(params, tokens, model, every)
    margins = np.asarray(margins)
    control = (np.asarray(reference_forward(params, tokens, model, every,
                                            lowp=True)[0])
               if tol.get("control_ratio") else None)
    for group, (at, served) in served_many(params, tokens).items():
        at = np.asarray(at)
        got = judge(served, ref[at], margins[at], tol,
                    None if control is None else control[at])
        sys.stderr.write(json.dumps({"judged": group, "tokens": len(tokens),
                                     **got}) + "\n")
        if not got["ok"]:
            raise NotCorrect(
                f"{group} of {len(tokens)} tokens: the "
                f"{tol['clean_quantile']} quantile of {got['positions']} "
                f"positions' RMS differences is {got['rms_clean']:.4f} "
                f"(limit rms_clean {tol['rms_clean']}; "
                f"{got.get('ratio')} of the control's, limit "
                f"{tol.get('control_ratio')}), the worst {got['rms']:.4f} "
                f"(limit rms {tol['rms']})")
    return ref


def reference_logits(params: Dict[str, Any], tokens, model: Dict[str, Any],
                     rows) -> jnp.ndarray:
    """The family's surface: ``model`` is the configuration file's
    ``model`` block (``shapes.MODEL_KEYS``). While ``JUDGED`` is set,
    each of its groups is held to ``judge`` first, over ``tokens`` and
    then over ``judged_sequence`` (``tolerance.judged_tokens`` of them),
    and ``NotCorrect`` is raised for one that fails. A sequence of fewer
    than ``tolerance.min_positions`` tokens is not judged and draws no
    sequence (``families/deepseek_v3/README.md`` has the reason)."""
    if JUDGED is None or len(tokens) < JUDGED[1].get("min_positions", 0):
        return reference_forward(params, tokens, model, rows)[0]
    ref = _hold(params, tokens, model)
    n = int(JUDGED[1].get("judged_tokens", 0))
    if n:
        _hold(params, judged_sequence(tokens, n, model["vocab_size"]), model)
    return ref[np.asarray(rows)]


def _rms_diff(a, b) -> np.ndarray:
    return np.asarray(jnp.sqrt(jnp.mean(jnp.square(
        jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)), -1)))


def judge(served: np.ndarray, ref: np.ndarray, margins: np.ndarray,
          tol: Dict[str, Any], control: Optional[np.ndarray] = None
          ) -> Dict[str, Any]:
    """The comparison that knows of routing (``tolerance``'s keys), over
    MANY positions: the ``clean_quantile`` of the positions' RMS
    differences is held to ``rms_clean`` and the worst position to
    ``rms`` (logits that have nothing to do with the reference's), as
    ``families/afmoe/reference.judge``; and, given the ``control``'s
    logits at the same positions (the reference one precision down),
    to ``control_ratio`` of the control's own ``clean_quantile`` — the
    limit that refuses a lower precision whatever the weights' swap
    rate (the module's docstring). The share of positions with a margin
    under ``margin_eps`` is reported."""
    rms = _rms_diff(served, ref)
    q = tol["clean_quantile"]
    clean = float(np.quantile(rms, q, method="higher"))
    worst = float(rms.max())
    out: Dict[str, Any] = {}
    ok = clean <= tol["rms_clean"] and worst <= tol["rms"]
    if control is not None and tol.get("control_ratio"):
        of = float(np.quantile(_rms_diff(control, ref), q, method="higher"))
        out = {"control_clean": of, "ratio": clean / of if of else np.inf}
        ok = ok and out["ratio"] <= tol["control_ratio"]
    return {"ok": bool(ok), "rms_clean": clean, "rms": worst,
            "positions": int(rms.size), **out,
            "near_tie_share": float(
                (np.asarray(margins) < tol["margin_eps"]).mean())}
