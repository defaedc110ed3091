"""The plain reference: the language model of Ling-3.0-flash-VL (delta-rule
linear attention with a decay a channel, KDA, beside one latent
attention in ``layer_group_size``; a dense SwiGLU in the leading layers
and after them a group-limited sigmoid-routed SwiGLU beside a shared
expert; pre-norm, untied head) in straightforward ``jax.numpy`` and
float32: no cache, no kernel, no chunks, no batching, one sequence at a
time, ``jax.default_matmul_precision("highest")``, one layer — and of a
routed layer one expert — upcast at a time. ``README.md`` has the
equations and what is assumed of them.

**The KDA recurrence is computed TOKEN BY TOKEN** (``lax.scan`` over the
positions, the state ``(heads, d_k, d_v)`` as the equations write it):
it shares nothing with the program's chunked scan, its one-token
update, its state layout or its kernel, so it is what those are held
against. The latent attention is UNABSORBED (K and V expanded from the
latent for every token, a full softmax). The router limits its choice
to groups for itself, and is given the same SHARE of the experts as the
chip: ``expert_share`` ``{chips, index}`` of ``router_experts``; what
the experts held elsewhere would have added is left out here as there.

It imports neither ``llmq_tpu`` nor ``adapter.py``; it reads the served
parameter tree by its leaf names.

``lowp`` is the same reference ONE precision down, which the
comparison has to refuse (``LOWP``; ``True`` is all three): ``"state"``
the KDA state rounded to bfloat16 between tokens, ``"router"`` the
router's product in bfloat16, ``"latent"`` the latent rounded to 8 bits
(float8_e4m3's four exponent and three mantissa bits).

``JUDGED``: while the harness's check runs, the adapter leaves here a
function that drives the SERVED path over many positions — a prompt
prefilled slice by slice through the chunked scan, more rows through the
mixed step, then teacher-forced decode steps from the state the scan
left — and ``reference_logits`` holds each group to ``judge`` before it
answers (``families/deepseek_v3/README.md`` has why the harness's
worst-of-8 is not enough for a routed block).

**The reference is ROUTED BY THE SERVED PATH'S CHOICES** while it judges
(``forced``: the experts the program chose, which its forward functions
hand out with ``chosen=True``). With 512 experts, 8 a token and a group
limit the margin at the 8th choice is about 0.006 and the bfloat16
stream moves a router's score by about 0.0025, so nearly every position
has a near-tie that falls the other way in one of the routed layers, and
the recurrent state carries that on to every later position: routed for
itself, the reference's distance from the served path reads the swap
rate and nothing finer — the control one precision down read LOWER than
the served path (``README.md`` has the readings). Given the served
choices the reference computes the gates from its OWN float32 scores of
those experts, so what is left between the two is rounding, and a
precision shows in every layer of the whole model. The router is held
apart: wherever the reference's own choice differs from the served one,
its margin (the k-th selection score over the next, the last kept
group's over the next group's) has to be under ``tolerance.
margin_decisive`` — a program that chooses otherwise where the choice is
not close is a wrong router, not a rounding.
"""

from __future__ import annotations

import json
import sys
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

L2_EPS = 1e-6


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _rope(x, theta):
    # x: (T, H, D); rotate the two halves of D by position-dependent angles.
    T, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _take(tree, names, l):
    return {k: _f32(jax.lax.dynamic_index_in_dim(tree[k], l, 0,
                                                 keepdims=False))
            for k in names}


def is_latent(model: Dict[str, Any], l: int) -> bool:
    return (l + 1) % model["layer_group_size"] == 0


#: What ``lowp`` may name.
LOWP = ("state", "router", "latent")


@partial(jax.jit, static_argnames=("heads", "lower", "eps", "lowp"))
def _kda(h, norm, kda, l, i, snaps, *, heads, lower, eps, lowp):
    """Returns (h', the state ``(len(snaps), H, d_k, d_v)`` behind each
    of the positions ``snaps``)."""
    w = _take(kda, ("wqkv", "conv_w", "wf", "b_f", "a_log", "wb", "wg",
                    "o_norm", "wo"), i)
    T = h.shape[0]
    x = _rms(h, _f32(norm[l]), eps)
    qkv = x @ w["wqkv"]                                     # (T, 3 H d)
    K = w["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1])), qkv])
    qkv = jax.nn.silu(sum(padded[j:j + T] * w["conv_w"][:, j]
                          for j in range(K)))
    q, k, v = (qkv[:, j * qkv.shape[1] // 3:(j + 1) * qkv.shape[1] // 3]
               .reshape(T, heads, -1) for j in range(3))
    d = q.shape[-1]
    q, k = _unit(q) / jnp.sqrt(jnp.float32(d)), _unit(k)
    g = lower * jax.nn.sigmoid(
        (x @ w["wf"] + w["b_f"]).reshape(T, heads, d)
        * jnp.exp(w["a_log"])[:, None])                     # (T, H, d) <= 0
    beta = jax.nn.sigmoid(x @ w["wb"])                      # (T, H)

    def step(carry, t):
        s, kept = carry
        q_t, k_t, v_t, g_t, b_t, at = t
        s = jnp.exp(g_t)[:, :, None] * s                    # (H, d_k, d_v)
        u = jnp.einsum("hk,hkv->hv", k_t, s)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - u)[:, None, :]
        o = jnp.einsum("hk,hkv->hv", q_t, s)
        if "state" in lowp:    # (not two converts: XLA's TPU compiler keeps
            # excess precision and drops those; this it may not drop)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        kept = jnp.where((snaps == at)[:, None, None, None], s, kept)
        return (s, kept), o

    (_, kept), o = jax.lax.scan(
        step, (jnp.zeros((heads, d, d)), jnp.zeros((len(snaps), heads, d, d))),
        (q, k, v, g, beta, jnp.arange(T)))
    o = _rms(o, w["o_norm"], eps).reshape(T, -1)
    return h + (o * jax.nn.sigmoid(x @ w["wg"])) @ w["wo"], kept


@partial(jax.jit, static_argnames=("n_heads", "rank", "dn", "dr", "eps",
                                   "theta", "lowp"))
def _attention(h, norm, lat, l, i, *, n_heads, rank, dn, dr, eps, theta,
               lowp):
    """Returns (h', the rows a cache of latents holds ``(T, rank +
    dr)``: the normed latent beside the rotated RoPE key)."""
    w = _take(lat, ("wq", "wkv_a", "kv_norm", "wkv_b", "w_head_gate", "wo"),
              i)
    T = h.shape[0]
    x = _rms(h, _f32(norm[l]), eps)
    q = (x @ w["wq"]).reshape(T, n_heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    kva = x @ w["wkv_a"]
    c = _rms(kva[:, :rank], w["kv_norm"], eps)
    k_rope = _rope(kva[:, None, rank:], theta)              # (T, 1, dr)
    if "latent" in lowp:   # float8_e4m3's bits (as the state: no converts)
        c, k_rope = (jax.lax.reduce_precision(x, exponent_bits=4,
                                              mantissa_bits=3)
                     for x in (c, k_rope))
    kv = (c @ w["wkv_b"]).reshape(T, n_heads, -1)           # [k_nope | v]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (T, n_heads, dr))], -1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(dn + dr))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), kv[..., dn:])
    o = o * jax.nn.sigmoid(x @ w["w_head_gate"])[:, :, None]
    return (h + o.reshape(T, -1) @ w["wo"],
            jnp.concatenate([c, k_rope[:, 0]], -1))


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(h, norm, dense, l, *, eps):
    w = _take(dense, ("w_gate", "w_up", "w_down"), l)
    x = _rms(h, _f32(norm[l]), eps)
    return h + _swiglu(x, w["w_gate"], w["w_up"], w["w_down"])


@partial(jax.jit, static_argnames=("top_k", "n_group", "topk_group", "scale",
                                   "norm_topk", "first", "eps", "lowp"))
def _routed_ffn(h, norm, moe, we_gate_up, we_down, l, i, forced, *, top_k,
                n_group, topk_group, scale, norm_topk, first, eps, lowp):
    """Layer ``l`` (the ``i``-th routed one; ``we_*``: the HELD experts'
    leaves, ``first`` the router's index of the first of them).
    ``forced`` (T, k) int32: the experts to USE at each position (a
    position whose first is negative uses the reference's own choice).
    Returns (h', margin (T,): the smallest margin of the reference's
    OWN choice — the k-th selection score over the next, and the last
    kept group's over the next group's —, swapped (T,): the own choice
    is not the forced one)."""
    x = _rms(h, _f32(norm[l]), eps)
    w_r = _f32(moe["router"][i])
    if "router" in lowp:
        logits = _f32(jnp.dot(x.astype(jnp.bfloat16),
                              w_r.astype(jnp.bfloat16)))
    else:
        logits = x @ w_r
    s = jax.nn.sigmoid(logits)                              # (T, E)
    sel = s + _f32(moe["router_bias"][i])
    T, E = s.shape
    by_group = sel.reshape(T, n_group, E // n_group)
    score = jnp.sum(-jnp.sort(-by_group, axis=-1)[..., :2], -1)   # (T, G)
    g_order = jnp.argsort(-score, axis=-1)                  # ties: low index
    kept = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], g_order[:, :topk_group]].set(True)
    margin = jnp.full((T,), jnp.inf)
    if topk_group < n_group:
        g_ranked = jnp.take_along_axis(score, g_order, -1)
        margin = g_ranked[:, topk_group - 1] - g_ranked[:, topk_group]
    sel = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(T, E)
    order = jnp.argsort(-sel, axis=-1)
    own = order[:, :top_k]
    ranked = jnp.take_along_axis(sel, order[:, :top_k + 1], -1)
    margin = jnp.minimum(margin, ranked[:, top_k - 1] - ranked[:, top_k])
    given = forced[:, :1] >= 0
    chosen = jnp.where(given, forced, own)
    swapped = given[:, 0] & jnp.any(
        jnp.sort(own, -1) != jnp.sort(chosen, -1), -1)
    g = jnp.take_along_axis(s, chosen, -1)
    if norm_topk:
        g = g / jnp.sum(g, -1, keepdims=True)
    gates = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], chosen].set(g * scale)
    F = we_down.shape[1]

    def one(e, acc):                    # held expert e: the router's first + e
        gu = _f32(we_gate_up[e])
        y = _swiglu(x, gu[:, :F], gu[:, F:], _f32(we_down[e]))
        return acc + jax.lax.dynamic_index_in_dim(
            gates, first + e, 1, keepdims=True) * y

    y = jax.lax.fori_loop(0, we_down.shape[0], one, jnp.zeros_like(h))
    y = y + _swiglu(x, _f32(moe["ws_gate"][i]), _f32(moe["ws_up"][i]),
                    _f32(moe["ws_down"][i]))
    return h + y, margin, swapped


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, h, rows, *, eps):
    return _rms(h[rows], _f32(final_norm), eps) @ _f32(lm_head)


def held_first(model: Dict[str, Any]) -> int:
    share = model["expert_share"]
    if share["chips"] * model["num_experts"] != model["router_experts"]:
        raise ValueError(f"{share['chips']} shares of "
                         f"{model['num_experts']} experts are not the "
                         f"router's {model['router_experts']}")
    return share["index"] * model["num_experts"]


def _lowp(lowp) -> Tuple[str, ...]:
    names = LOWP if lowp is True else tuple(lowp or ())
    if set(names) - set(LOWP):
        raise ValueError(f"lowp names {names}: of {LOWP}")
    return names


class Forward(NamedTuple):
    """``routed_forward``'s: float32 logits ``(len(rows), V)``; margins
    and swapped ``(routed layers, len(rows))`` (``_routed_ffn``'s);
    states ``(KDA layers, len(snaps), H, d_k, d_v)``: each KDA layer's
    state behind each of the positions ``snaps``; latents ``(latent
    layers, T, rank + dr)``: what a cache of latents holds of every
    position (``_attention``)."""
    logits: jnp.ndarray
    margins: jnp.ndarray
    swapped: jnp.ndarray
    states: jnp.ndarray
    latents: jnp.ndarray


def routed_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                   rows, lowp=(), forced=None, snaps=()) -> Forward:
    """One sequence ``tokens`` ``(T,)`` judged at the positions ``rows``.
    ``forced`` (routed layers, T, k) int32, or None: every position
    routed by the reference's own choice."""
    if (model.get("q_lora_rank") is not None
            or model.get("score_function", "sigmoid") != "sigmoid"
            or model.get("num_kv_heads_for_linear_attn", 0)):
        raise ValueError("the reference is written for q_lora_rank null, "
                         "sigmoid scores and as many KDA key heads as "
                         "query heads")
    lowp = _lowp(lowp)
    L = model["num_hidden_layers"]
    Ld = min(model.get("dense_layers_held", model["first_k_dense_replace"]), L)
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    rows = jnp.asarray(rows, jnp.int32)
    T, k = len(tokens), model["num_experts_per_tok"]
    if forced is None:
        forced = jnp.full((L - Ld, T, k), -1, jnp.int32)
    forced = jnp.asarray(forced, jnp.int32)
    snaps = jnp.asarray(snaps, jnp.int32).reshape(-1)
    norms = params["layers"]
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
        margins, swaps, states, latents = [], [], [], []
        seen = {True: 0, False: 0}
        for l in range(L):
            lat = is_latent(model, l)
            i = jnp.int32(seen[lat])
            seen[lat] += 1
            if lat:
                h, held = _attention(
                    h, norms["attn_norm"], params["latent"], jnp.int32(l), i,
                    n_heads=model["num_attention_heads"],
                    rank=model["kv_lora_rank"], dn=model["qk_nope_head_dim"],
                    dr=model["qk_rope_head_dim"], eps=eps, theta=theta,
                    lowp=lowp)
                latents.append(held)
            else:
                h, kept = _kda(
                    h, norms["attn_norm"], params["kda"], jnp.int32(l), i,
                    snaps, heads=model["num_attention_heads"],
                    lower=float(model["kda_lower_bound"]), eps=eps,
                    lowp=lowp)
                states.append(kept)
            if l < Ld:
                h = _dense_ffn(h, norms["mlp_norm"], params["dense"],
                               jnp.int32(l), eps=eps)
            else:
                moe = params["moe"]
                h, m, sw = _routed_ffn(
                    h, norms["mlp_norm"],
                    {k: v for k, v in moe.items()
                     if not k.startswith("we_")},
                    moe["we_gate_up"][l - Ld], moe["we_down"][l - Ld],
                    jnp.int32(l), jnp.int32(l - Ld), forced[l - Ld],
                    top_k=k, n_group=model["n_group"],
                    topk_group=model["topk_group"],
                    scale=float(model["routed_scaling_factor"]),
                    norm_topk=bool(model["norm_topk_prob"]),
                    first=held_first(model), eps=eps, lowp=lowp)
                margins.append(m[rows])
                swaps.append(sw[rows])
        def stacked(xs, dtype=jnp.float32):     # a kind of layer not held
            return jnp.stack(xs) if xs else jnp.zeros((0, len(rows)), dtype)

        return Forward(
            _head(params["final_norm"], params["lm_head"], h, rows, eps=eps),
            stacked(margins), stacked(swaps, bool), stacked(states),
            stacked(latents))


def reference_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                      rows, lowp=()) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(float32 logits ``(len(rows), V)`` of one sequence ``tokens``
    ``(T,)`` at the positions ``rows``, margins ``(len(rows),)``: each
    position's smallest selection margin over the routed layers), every
    position routed by the reference's own choice."""
    got = routed_forward(params, tokens, model, rows, lowp)
    return got.logits, jnp.min(got.margins, 0, initial=jnp.inf)


class NotCorrect(AssertionError):
    """The serving path's logits are not the reference's, by ``judge``."""


#: ``(served_many, tolerance)`` while the family's serving path is under
#: the harness's check, else ``None``. ``served_many(params, tokens) ->
#: (groups, chosen)``. ``groups`` ``{name: group}``, a group for each way
#: of getting to MANY positions of the one sequence ``tokens``: ``row``
#: the batch row that got there, ``at`` the positions, ``logits``
#: ``(len(at), V)`` float32, ``states`` ``(KDA layers, H, d_k, d_v)``
#: what that row's recurrent state held behind ``at[-1]`` and
#: ``latents`` ``(latent layers, at[-1] + 1, rank + dr)`` what the cache
#: held of the row's every position by then (or None: not looked at).
#: ``chosen`` ``(rows, routed layers, len(tokens), k)`` int32: the
#: experts the program chose at every position a row ran (negative
#: where it ran none).
JUDGED: Optional[Tuple[Callable[..., Any], Dict[str, Any]]] = None


def judged_sequence(tokens, n: int, vocab: int) -> np.ndarray:
    """The family's own judged sequence of ``n`` tokens: drawn from the
    harness's prompt ``tokens`` (which the run's seed drew), so the same
    seed judges the same sequence and another seed another."""
    rng = np.random.default_rng(np.asarray(tokens, np.uint32))
    return rng.integers(3, vocab, n, dtype=np.int32)


#: Positions that make a distribution (``judge``).
MANY = 64


def layer_distances(got: np.ndarray, ref: np.ndarray) -> list:
    """``|got - ref| / |ref|`` (Frobenius) of each layer's whole array,
    ``got`` and ``ref`` ``(layers, ...)``, the first layer first."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    axes = tuple(range(1, ref.ndim))
    return [float(x) for x in np.sqrt(np.sum((got - ref) ** 2, axes)
                                      / np.sum(ref ** 2, axes))]


def judge(got: np.ndarray, ref: np.ndarray, margins: np.ndarray,
          swapped: np.ndarray, state_rel: list, latent_rel: Optional[list],
          tol: Dict[str, Any]) -> Dict[str, Any]:
    """One group: ``got`` and ``ref`` ``(positions, V)`` in the order of
    the positions, the reference routed by the served choices;
    ``margins`` and ``swapped`` ``(routed layers, positions)``
    (``Forward``); ``state_rel`` / ``latent_rel`` the
    ``layer_distances`` of the KDA layers' states behind the group's
    last position and of the latent layers' cached rows up to it (None:
    the group does not look at them). Six limits; the two over the
    positions' distribution hold for a group of ``MANY`` positions or
    more (a mixed step's one or two positions are no distribution):

    - ``rms_clean``: the ``clean_quantile`` of the positions' RMS
      differences. What is left between the two is rounding, the same
      in every seed, so the limit stands close above the served
      reading: an error of another source as large as the products'
      rounding (an expert's product in a narrower type) does not pass;
    - ``rms``: the worst position (logits that have nothing to do with
      the reference's);
    - ``state_rel``, a limit a KDA layer: the recurrent state itself.
      The products' rounding reaches a state through its inputs, a
      token at a time and each token's independent of the last; a
      state HELD too narrow is rounded whole at every token, and a
      channel that remembers n tokens gathers sqrt(n) roundings. The
      limit that sees the state's precision, behind the scan and
      behind the one-token update alike (a deeper layer's inputs carry
      more of the stream's rounding: hence a limit a layer);
    - ``latent_rel``: the cached latents themselves. One latent layer
      in seven, attended as a near-even mean over hundreds of keys,
      moves the logits by a twentieth of the products' rounding when
      its cache is held in 8 bits; its rows show it at once;
    - ``growth``: the mean over the group's last quarter of positions
      over the mean over its first (what the products leave is the same
      at every position; what is carried from token to token grows);
    - ``margin_decisive``: the largest margin of the reference's own
      choice where the served path chose otherwise (a near-tie may fall
      either way; a clear choice may not).

    The shares of positions with a margin under ``margin_eps`` and with
    a swapped choice in some layer are reported."""
    rms = np.asarray(jnp.sqrt(jnp.mean(jnp.square(
        jnp.asarray(got, jnp.float32) - ref), -1)))
    margins, swapped = np.asarray(margins), np.asarray(swapped, bool)
    clean = float(np.quantile(rms, tol["clean_quantile"], method="higher"))
    worst = float(rms.max())
    many, q = len(rms) >= MANY, len(rms) // 4
    growth = float(rms[-q:].mean() / rms[:q].mean()) if many else None
    decisive = float(margins[swapped].max()) if swapped.any() else 0.0
    return {"ok": bool((clean <= tol["rms_clean"] or not many)
                       and worst <= tol["rms"]
                       and len(state_rel) == len(tol["state_rel"])
                       and all(x <= y for x, y in zip(state_rel,
                                                      tol["state_rel"]))
                       and (latent_rel is None
                            or max(latent_rel) <= tol["latent_rel"])
                       and (growth is None or growth <= tol["growth"])
                       and decisive <= tol["margin_decisive"]),
            "rms_clean": clean, "rms": worst, "state_rel": state_rel,
            "latent_rel": latent_rel, "growth": growth,
            "swap_margin": decisive, "positions": int(rms.size),
            "swapped_share": float(swapped.any(0).mean()),
            "near_tie_share": float(
                (margins.min(0, initial=np.inf) < tol["margin_eps"]).mean())}


def judged_groups(params: Dict[str, Any], tokens, model: Dict[str, Any],
                  served, tol: Dict[str, Any], lowp=()):
    """``(name, judge's verdict)`` for every group of ``served``
    (``served_many``'s result over ``tokens``), each held against the
    reference routed by ITS row's choices. With ``lowp`` the same
    reference one precision down, routed alike, is judged in the served
    path's place at the same positions: the control."""
    groups, chosen = served
    every = np.arange(len(tokens))
    for row in sorted({g["row"] for g in groups.values()}):
        mine = {n: g for n, g in groups.items() if g["row"] == row}
        snaps = sorted({int(g["at"][-1]) for g in mine.values()})
        ref = routed_forward(params, tokens, model, every,
                             forced=chosen[row], snaps=snaps)
        low = lowp and routed_forward(params, tokens, model, every, lowp,
                                      chosen[row], snaps)
        ref, low = (x and Forward(*map(np.asarray, x)) for x in (ref, low))
        for name, g in mine.items():
            at, n = np.asarray(g["at"]), int(g["at"][-1]) + 1
            snap = snaps.index(n - 1)
            if lowp:
                g = dict(g, logits=low.logits[at],
                         states=low.states[:, snap],
                         latents=None if g["latents"] is None
                         else low.latents[:, :n])
            yield name, judge(
                g["logits"], ref.logits[at], ref.margins[:, at],
                ref.swapped[:, at],
                layer_distances(g["states"], ref.states[:, snap]),
                None if g["latents"] is None else layer_distances(
                    g["latents"], ref.latents[:, :n]), tol)


def reference_logits(params: Dict[str, Any], tokens, model: Dict[str, Any],
                     rows) -> jnp.ndarray:
    """The family's surface: ``model`` is the configuration file's
    ``model`` block (``shapes.MODEL_KEYS``). While ``JUDGED`` is set and
    ``tokens`` is of ``tolerance.min_positions`` or more, the family's
    own sequence (``judged_sequence``, ``tolerance.judged_tokens`` long:
    longer than a prefill slice, so the state is carried from slice to
    slice) goes through ``served_many`` and each of its groups is held
    to ``judge`` (one line a group on standard error); ``NotCorrect`` is
    raised for one that fails."""
    if JUDGED is not None and len(tokens) >= JUDGED[1].get("min_positions",
                                                           0):
        served_many, tol = JUDGED
        own = judged_sequence(tokens, int(tol["judged_tokens"]),
                              model["vocab_size"])
        for group, got in judged_groups(params, own, model,
                                        served_many(params, own), tol):
            sys.stderr.write(json.dumps({"judged": group, **got}) + "\n")
            if not got["ok"]:
                def r(x):
                    return x if x is None else [round(v, 5) for v in x]
                raise NotCorrect(
                    f"{group}: the {tol['clean_quantile']} quantile of "
                    f"{got['positions']} positions' RMS differences is "
                    f"{got['rms_clean']:.4f} (limit rms_clean "
                    f"{tol['rms_clean']} over {MANY} positions or more), "
                    f"the worst {got['rms']:.4f} (limit rms {tol['rms']}), "
                    f"the last quarter's mean over the first's "
                    f"{got['growth']} (limit growth {tol['growth']}), the "
                    f"recurrent states lie {r(got['state_rel'])} of their "
                    f"norms from the reference's (limits state_rel "
                    f"{tol['state_rel']}), the cached latents "
                    f"{r(got['latent_rel'])} (limit latent_rel "
                    f"{tol['latent_rel']}), the clearest choice the served "
                    f"path did not make had a margin of "
                    f"{got['swap_margin']:.4f} (limit margin_decisive "
                    f"{tol['margin_decisive']})")
    return reference_forward(params, tokens, model, rows)[0]
