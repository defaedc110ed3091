"""The plain reference: Solar-Open2 (Kimi Delta Attention — delta-rule
linear attention with a decay a channel through low-rank gates, beta in
(0, 2) — in three layers of four, gated softmax GQA without rotary
positions in every fourth, a sigmoid-routed SwiGLU beside a shared
expert in every layer; pre-norm, untied head) in straightforward
``jax.numpy`` and float32: no cache, no kernel, no chunks, no batching,
one sequence at a time, ``jax.default_matmul_precision("highest")``, one
layer — and of a routed layer one expert — upcast at a time.
``README.md`` has the equations and what is assumed of them.

**The KDA recurrence is computed TOKEN BY TOKEN** (``lax.scan`` over the
positions, the state ``(heads, d_k, d_v)`` as the equations write it):
it shares nothing with the program's chunked scan, its one-token
update, its state layout or its kernels, so it is what those are held
against. The softmax attention is FULL (every key of the sequence,
causal), a block of ``QUERY_BLOCK`` queries at a time so that a row of
ten thousand tokens fits: the scores that exist at once are (heads,
block, T). The router is given the same SHARE of the experts as the
chip: ``expert_share`` ``{chips, index}`` of ``router_experts``; what
the experts held elsewhere would have added is left out here as there.

It imports neither ``llmq_tpu`` nor ``adapter.py``; it reads the served
parameter tree by its leaf names.

``lowp`` is the same reference ONE precision down, which the
comparison has to refuse (``LOWP``; ``True`` is all three): ``"state"``
the KDA state rounded to bfloat16 between tokens, ``"router"`` the
router's product in bfloat16, ``"kv"`` the keys and values rounded to 8
bits (float8_e4m3's four exponent and three mantissa bits).

``JUDGED``, the reference ROUTED BY THE SERVED PATH'S CHOICES and
``judge``'s limits are ``families/ling_hybrid/reference.py``'s method
(it has why: with hundreds of experts nearly every position has a
near-tie that bfloat16 turns, and a recurrent state carries a swap on),
with the K/V rows where that family looks at its latents.
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
#: Queries whose scores against every key exist at once.
QUERY_BLOCK = 256


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _take(tree, names, l):
    return {k: _f32(jax.lax.dynamic_index_in_dim(tree[k], l, 0,
                                                 keepdims=False))
            for k in names}


def is_gqa(model: Dict[str, Any], l: int) -> bool:
    return l in model["gqa_layers"]


#: What ``lowp`` may name.
LOWP = ("state", "router", "kv")


@partial(jax.jit, static_argnames=("heads", "eps", "lowp"))
def _kda(h, norm, kda, l, i, snaps, *, heads, eps, lowp):
    """Returns (h', the state ``(len(snaps), H, d_k, d_v)`` behind each
    of the positions ``snaps``)."""
    w = _take(kda, ("wqkv", "conv_w", "wf_a", "wf_b", "dt_bias", "a_log",
                    "wb", "wg_a", "wg_b", "o_norm", "wo"), i)
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
    f = (x @ w["wf_a"]) @ w["wf_b"] + w["dt_bias"]          # the low-rank pair
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
        f.reshape(T, heads, d))                             # (T, H, d) < 0
    beta = 2.0 * jax.nn.sigmoid(x @ w["wb"])                # (T, H) in (0, 2)

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
    gate = jax.nn.sigmoid((x @ w["wg_a"]) @ w["wg_b"])
    return h + (o * gate) @ w["wo"], kept


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "lowp"))
def _attention(h, norm, gqa, l, i, *, n_heads, n_kv, eps, lowp):
    """Gated softmax attention WITHOUT positions (the causal mask is all
    that orders the keys), every key of the sequence, a block of queries
    at a time. Returns (h', the rows a K/V cache holds ``(T, 2 n_kv
    hd)``: each token's keys beside its values)."""
    w = _take(gqa, ("wq", "wk", "wv", "wg", "wo"), i)
    T = h.shape[0]
    x = _rms(h, _f32(norm[l]), eps)
    q = (x @ w["wq"]).reshape(T, n_heads, -1)
    hd = q.shape[-1]
    k = (x @ w["wk"]).reshape(T, n_kv, hd)
    v = (x @ w["wv"]).reshape(T, n_kv, hd)
    if "kv" in lowp:       # float8_e4m3's bits (as the state: no converts)
        k, v = (jax.lax.reduce_precision(y, exponent_bits=4, mantissa_bits=3)
                for y in (k, v))
    group = n_heads // n_kv
    blocks = -(-T // QUERY_BLOCK)
    qb = jnp.pad(q, ((0, blocks * QUERY_BLOCK - T), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, n_kv, group, hd)

    def block(args):
        qs, start = args                                    # (Q, G, g, hd)
        s = jnp.einsum("qngd,snd->ngqs", qs, k) / jnp.sqrt(jnp.float32(hd))
        at = start + jnp.arange(QUERY_BLOCK)
        s = jnp.where(jnp.arange(T)[None, :] <= at[:, None], s, -jnp.inf)
        return jnp.einsum("ngqs,snd->qngd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (qb, jnp.arange(blocks) * QUERY_BLOCK))
    o = o.reshape(blocks * QUERY_BLOCK, -1)[:T]
    o = o * jax.nn.sigmoid(x @ w["wg"])                     # an element each
    return (h + o @ w["wo"],
            jnp.concatenate([k.reshape(T, -1), v.reshape(T, -1)], -1))


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@partial(jax.jit, static_argnames=("top_k", "scale", "norm_topk", "first",
                                   "eps", "lowp"))
def _routed_ffn(h, norm, moe, we_gate_up, we_down, l, forced, *, top_k,
                scale, norm_topk, first, eps, lowp):
    """Layer ``l`` (``we_*``: the HELD experts' leaves, ``first`` the
    router's index of the first of them). ``forced`` (T, k) int32: the
    experts to USE at each position (a position whose first is negative
    uses the reference's own choice). Returns (h', margin (T,): the
    margin of the reference's OWN choice — the k-th selection score
    over the next —, swapped (T,): the own choice is not the forced
    one, chosen (T, k): the experts used)."""
    x = _rms(h, _f32(norm[l]), eps)
    w_r = _f32(moe["router"][l])
    if "router" in lowp:
        logits = _f32(jnp.dot(x.astype(jnp.bfloat16),
                              w_r.astype(jnp.bfloat16)))
    else:
        logits = x @ w_r
    s = jax.nn.sigmoid(logits)                              # (T, E)
    sel = s + _f32(moe["router_bias"][l])                   # chooses only
    T, E = s.shape
    order = jnp.argsort(-sel, axis=-1)
    own = order[:, :top_k]
    ranked = jnp.take_along_axis(sel, order[:, :top_k + 1], -1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
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
    y = y + _swiglu(x, _f32(moe["ws_gate"][l]), _f32(moe["ws_up"][l]),
                    _f32(moe["ws_down"][l]))
    return h + y, margin, swapped, chosen


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, lm_head, h, rows, *, eps):
    return _rms(h[rows], _f32(final_norm), eps) @ _f32(lm_head)


def held_first(model: Dict[str, Any]) -> int:
    share = model["expert_share"]
    if share["chips"] * model["n_routed_experts"] != model["router_experts"]:
        raise ValueError(f"{share['chips']} shares of "
                         f"{model['n_routed_experts']} experts are not the "
                         f"router's {model['router_experts']}")
    return share["index"] * model["n_routed_experts"]


def _lowp(lowp) -> Tuple[str, ...]:
    names = LOWP if lowp is True else tuple(lowp or ())
    if set(names) - set(LOWP):
        raise ValueError(f"lowp names {names}: of {LOWP}")
    return names


class Forward(NamedTuple):
    """``routed_forward``'s: float32 logits ``(len(rows), V)``; margins
    and swapped ``(layers, len(rows))`` (``_routed_ffn``'s); states
    ``(KDA layers, len(snaps), H, d_k, d_v)``: each KDA layer's state
    behind each of the positions ``snaps``; kv ``(GQA layers, T, 2 n_kv
    hd)``: what a K/V cache holds of every position (``_attention``);
    chosen ``(layers, T, k)``: the experts every position was routed
    to (``forced`` where it was given)."""
    logits: jnp.ndarray
    margins: jnp.ndarray
    swapped: jnp.ndarray
    states: jnp.ndarray
    kv: jnp.ndarray
    chosen: jnp.ndarray


def routed_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                   rows, lowp=(), forced=None, snaps=()) -> Forward:
    """One sequence ``tokens`` ``(T,)`` judged at the positions ``rows``.
    ``forced`` (layers, T, k) int32, or None: every position routed by
    the reference's own choice."""
    lin = model["linear_attn_config"]
    if (model.get("use_rope") or not model.get("use_gqa_gate", True)
            or model.get("kda_use_full_proj")
            or not model.get("kda_allow_neg_eigval", True)
            or model.get("first_k_dense_replace", 0)
            or lin.get("num_kv_heads") not in (None, lin["num_heads"])):
        raise ValueError("the reference is written for no rotary and a gate "
                         "on the GQA layers, KDA with low-rank gates, beta "
                         "in (0, 2) and as many key heads as query heads, "
                         "and no leading dense layer")
    lowp = _lowp(lowp)
    L = model["num_hidden_layers"]
    eps = float(model["rms_norm_eps"])
    rows = jnp.asarray(rows, jnp.int32)
    T, k = len(tokens), model["num_experts_per_tok"]
    if forced is None:
        forced = jnp.full((L, T, k), -1, jnp.int32)
    forced = jnp.asarray(forced, jnp.int32)
    snaps = jnp.asarray(snaps, jnp.int32).reshape(-1)
    norms = params["layers"]
    moe = params["moe"]
    small = {n: v for n, v in moe.items() if not n.startswith("we_")}
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
        margins, swaps, states, kvs, used = [], [], [], [], []
        seen = {True: 0, False: 0}
        for l in range(L):
            gqa = is_gqa(model, l)
            i = jnp.int32(seen[gqa])
            seen[gqa] += 1
            if gqa:
                h, held = _attention(
                    h, norms["attn_norm"], params["gqa"], jnp.int32(l), i,
                    n_heads=model["num_attention_heads"],
                    n_kv=model["num_key_value_heads"], eps=eps, lowp=lowp)
                kvs.append(held)
            else:
                h, kept = _kda(
                    h, norms["attn_norm"], params["kda"], jnp.int32(l), i,
                    snaps, heads=lin["num_heads"], eps=eps, lowp=lowp)
                states.append(kept)
            h, m, sw, took = _routed_ffn(
                h, norms["mlp_norm"], small, moe["we_gate_up"][l],
                moe["we_down"][l], jnp.int32(l), forced[l], top_k=k,
                scale=float(model["routed_scaling_factor"]),
                norm_topk=bool(model["norm_topk_prob"]),
                first=held_first(model), eps=eps, lowp=lowp)
            margins.append(m[rows])
            swaps.append(sw[rows])
            used.append(took)

        def stacked(xs, dtype=jnp.float32):     # a kind of layer not held
            return jnp.stack(xs) if xs else jnp.zeros((0, len(rows)), dtype)

        return Forward(
            _head(params["final_norm"], params["lm_head"], h, rows, eps=eps),
            stacked(margins), stacked(swaps, bool), stacked(states),
            stacked(kvs), jnp.stack(used))


def reference_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                      rows, lowp=()) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(float32 logits ``(len(rows), V)`` of one sequence ``tokens``
    ``(T,)`` at the positions ``rows``, margins ``(len(rows),)``: each
    position's smallest selection margin over the layers), every
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
#: what that row's recurrent state held behind ``at[-1]`` and ``kv``
#: ``(GQA layers, at[-1] + 1, 2 n_kv hd)`` what the pages held of the
#: row's every position by then (or None: not looked at). ``chosen``
#: ``(rows, layers, len(tokens), k)`` int32: the experts the program
#: chose at every position a row ran (negative where it ran none).
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
          swapped: np.ndarray, state_rel: list, kv_rel: Optional[list],
          tol: Dict[str, Any]) -> Dict[str, Any]:
    """One group: ``got`` and ``ref`` ``(positions, V)`` in the order of
    the positions, the reference routed by the served choices;
    ``margins`` and ``swapped`` ``(layers, positions)`` (``Forward``);
    ``state_rel`` / ``kv_rel`` the ``layer_distances`` of the KDA
    layers' states behind the group's last position and of the GQA
    layers' cached rows up to it (None: the group does not look at
    them). Six limits (``families/ling_hybrid/reference.judge`` has the
    reason for each); the two over the positions' distribution hold for
    a group of ``MANY`` positions or more:

    - ``rms_clean``: the ``clean_quantile`` of the positions' RMS
      differences;
    - ``rms_worst``: the worst position. Routed by the served choices
      it reads a precision like the median does, so it lies between
      the served path's largest and the control's smallest reading: a
      fault in a few positions that leaves the median where it was (one
      slot's wrong row) is refused here. (``rms`` is the harness's own
      limit on ITS worst of eight positions, against the reference
      routed for itself, which reads the swap rate: not used here);
    - ``state_rel``, a limit a KDA layer: the recurrent state itself,
      behind the scan and behind the one-token update alike;
    - ``kv_rel``: the cached keys and values themselves — one GQA layer
      in four, attended as a near-even mean over thousands of keys,
      hardly moves the logits when its cache is held in 8 bits; its
      rows show it at once;
    - ``growth``: the mean over the group's last quarter of positions
      over the mean over its first;
    - ``margin_decisive``: the largest margin of the reference's own
      choice where the served path chose otherwise.

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
                       and worst <= tol["rms_worst"]
                       and len(state_rel) == len(tol["state_rel"])
                       and all(x <= y for x, y in zip(state_rel,
                                                      tol["state_rel"]))
                       and (kv_rel is None
                            or max(kv_rel) <= tol["kv_rel"])
                       and (growth is None or growth <= tol["growth"])
                       and decisive <= tol["margin_decisive"]),
            "rms_clean": clean, "rms": worst, "state_rel": state_rel,
            "kv_rel": kv_rel, "growth": growth,
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
    path's place at the same positions: the control. What a control
    routed alike cannot move is ``swap_margin`` (it was given the
    choices): that is read from the control choosing FOR ITSELF, the
    reference routed by those choices — the clearest choice of the
    reference that a path one precision down turns."""
    groups, chosen = served
    for row in sorted({g["row"] for g in groups.values()}):
        mine = {n: g for n, g in groups.items() if g["row"] == row}
        snaps = sorted({int(g["at"][-1]) for g in mine.values()})
        # (the row's own stretch of the sequence: a row that stopped
        # early is not run to the end of the longest)
        n_row = snaps[-1] + 1
        every = np.arange(n_row)
        ref = routed_forward(params, tokens[:n_row], model, every,
                             forced=chosen[row][:, :n_row], snaps=snaps)
        low = lowp and routed_forward(params, tokens[:n_row], model, every,
                                      lowp, chosen[row][:, :n_row], snaps)
        turned = lowp and routed_forward(
            params, tokens[:n_row], model, every, forced=routed_forward(
                params, tokens[:n_row], model, every, lowp).chosen)
        ref, low, turned = (x and Forward(*map(np.asarray, x))
                            for x in (ref, low, turned))
        for name, g in mine.items():
            at, n = np.asarray(g["at"]), int(g["at"][-1]) + 1
            snap = snaps.index(n - 1)
            if lowp:
                g = dict(g, logits=low.logits[at],
                         states=low.states[:, snap],
                         kv=None if g["kv"] is None else low.kv[:, :n])
            routed = turned or ref
            yield name, judge(
                g["logits"], ref.logits[at], routed.margins[:, at],
                routed.swapped[:, at],
                layer_distances(g["states"], ref.states[:, snap]),
                None if g["kv"] is None else layer_distances(
                    g["kv"], ref.kv[:, :n]), tol)


def reference_logits(params: Dict[str, Any], tokens, model: Dict[str, Any],
                     rows) -> jnp.ndarray:
    """The family's surface: ``model`` is the configuration file's
    ``model`` block (``shapes.MODEL_KEYS``). While ``JUDGED`` is set and
    ``tokens`` is of ``tolerance.min_positions`` or more, the family's
    own sequence (``judged_sequence``, ``tolerance.judged_tokens`` long:
    deeper than 8,192 tokens, so the pages far behind a query and a scan
    carried over many slices are judged) goes through ``served_many``
    and each of its groups is held to ``judge`` (one line a group on
    standard error); ``NotCorrect`` is raised for one that fails."""
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
                    f"the worst {got['rms']:.4f} (limit rms_worst "
                    f"{tol['rms_worst']}), "
                    f"the last quarter's mean over the first's "
                    f"{got['growth']} (limit growth {tol['growth']}), the "
                    f"recurrent states lie {r(got['state_rel'])} of their "
                    f"norms from the reference's (limits state_rel "
                    f"{tol['state_rel']}), the cached keys and values "
                    f"{r(got['kv_rel'])} (limit kv_rel {tol['kv_rel']}), "
                    f"the clearest choice the served path did not make had "
                    f"a margin of {got['swap_margin']:.4f} (limit "
                    f"margin_decisive {tol['margin_decisive']})")
    return reference_forward(params, tokens, model, rows)[0]
