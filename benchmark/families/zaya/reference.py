"""The plain reference: ZAYA1 (compressed convolutional attention, CCA,
and a top-1 routed SwiGLU behind a router network that carries its state
from layer to layer, in every layer; pre-norm, a scaled residual merge,
tied head) in straightforward ``jax.numpy`` and float32: no cache, no
tail, no kernel, no batching, one sequence at a time,
``jax.default_matmul_precision("highest")``, one layer — and of a routed
layer one expert — upcast at a time. ``README.md`` has the equations and
what is assumed of them.

**Nothing is carried.** The two convolutions and the value shift are
computed over the WHOLE sequence at once, as shifts of ``(T, ...)``
arrays by one position with zeros in front (each convolution pads its
own input): it shares nothing with the program's tail, its one-token
step, its slices or their boundaries, so it is what those are held
against. Attention is a full causal softmax over keys and values that
exist only as arrays here.

It imports neither ``llmq_tpu`` nor ``adapter.py``; it reads the served
parameter tree by its leaf names (``wqkv`` = ``[W_q | W_k | W_v1 |
W_v2]``; ``conv1_w`` ``(heads, 2 d, d)`` with the older tap's rows
first, applied as ``[a_{t-1} ; a_t] W``).

Departures from the issue's equations, each also in the configuration's
``assumed``: an epsilon of 1e-6 under the square roots of the two L2
norms; exact (erf) GELU.

``lowp`` is the same reference ONE precision down, which the comparison
has to refuse (``LOWP``; ``True`` is all three): ``"router"`` the router
network's products in bfloat16, ``"cache"`` the cached K and V rounded
to 8 bits (float8_e4m3's four exponent and three mantissa bits),
``"state"`` what a token hands the next (``c``, ``a``, ``v2``) rounded
to bfloat16.

``JUDGED``: while the harness's check runs, the adapter leaves here a
function that drives the SERVED path over many positions — a prompt
prefilled slice by slice, more rows through the mixed step, then
teacher-forced decode steps through pages and tails — and
``reference_logits`` holds each group to ``judge`` before it answers
(``families/ling_hybrid/reference.py``'s procedure).

**The reference is ROUTED BY THE SERVED PATH'S CHOICES** while it judges
(``forced``: the expert the program chose, which its forward functions
hand out with ``chosen=True``): with one expert a token a near-tie that
falls the other way in bfloat16 swaps a token's WHOLE feed-forward, and
the carry hands the difference down the layers. Given the served choice
the reference computes the gate from its OWN float32 scores, so what is
left between the two is rounding. The router is held apart: wherever the
reference's own choice differs from the served one, its margin (the
best selection score over the next) has to be under
``tolerance.margin_decisive``.
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
    # x: (T, H, R): rotate the two halves of R by position-dependent angles.
    T, _, R = x.shape
    half = R // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _before(x):
    """``x`` (T, ...) moved on by one position, zeros in front."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]])


def _take(tree, names, l):
    return {k: _f32(jax.lax.dynamic_index_in_dim(tree[k], l, 0,
                                                 keepdims=False))
            for k in names}


def _bf16(x):      # (not two converts: XLA's TPU compiler keeps excess
    # precision and drops those; this it may not drop)
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _merge(x, f, res):
    return (res[0] * x + res[1]) + (res[2] * f + res[3])


#: What ``lowp`` may name.
LOWP = ("router", "cache", "state")


@partial(jax.jit, static_argnames=("H", "G", "d", "rot", "eps", "theta",
                                   "lowp"))
def _cca(h, layers, l, snaps, *, H, G, d, rot, eps, theta, lowp):
    """The CCA sublayer of layer ``l`` over one sequence ``h`` (T, D).
    Returns (h', tails ``(len(snaps), 2 C + W)``: ``[c_t | a_t | v2_t]``
    at each of the positions ``snaps`` — what a token hands the next —,
    kv ``(T, 2 G d)``: the K and V rows a cache holds of every position,
    rotated keys beside shifted values)."""
    w = _take(layers, ("attn_norm", "wqkv", "wo", "conv0_w", "conv0_b",
                       "conv1_w", "conv1_b", "temp", "res_attn"), l)
    T = h.shape[0]
    C = (H + G) * d
    W = G * d // 2
    u = _rms(h, w["attn_norm"], eps)
    p = u @ w["wqkv"]
    c, v1, v2 = p[:, :C], p[:, C:C + W], p[:, C + W:]
    low = "state" in lowp
    hand = _bf16 if low else (lambda x: x)         # what a token hands on
    # the q-k mean, before the convolutions
    qt = c[:, :H * d].reshape(T, G, H // G, d)
    kt = c[:, H * d:].reshape(T, G, 1, d)
    mq = 0.5 * (qt + kt)
    mk = jnp.mean(mq, axis=2)                                  # (T, G, d)
    # two causal convolutions, each padding its own input with zeros
    a = (w["conv0_w"][:, 0] * _before(hand(c)) + w["conv0_w"][:, 1] * c
         + w["conv0_b"])
    both = jnp.concatenate([_before(hand(a)).reshape(T, H + G, d),
                            a.reshape(T, H + G, d)], -1)       # (T, J, 2d)
    z = jnp.einsum("tji,jio->tjo", both, w["conv1_w"]).reshape(T, C)
    z = z + w["conv1_b"]
    q = z[:, :H * d].reshape(T, H, d) + mq.reshape(T, H, d)
    k = z[:, H * d:].reshape(T, G, d) + mk
    root = jnp.sqrt(jnp.float32(d))
    q = _unit(q) * root
    k = _unit(k) * root * w["temp"][:, None]
    q = jnp.concatenate([_rope(q[..., :rot], theta), q[..., rot:]], -1)
    k = jnp.concatenate([_rope(k[..., :rot], theta), k[..., rot:]], -1)
    # the value shift: the second half of the K/V width is the token
    # BEFORE's
    v = jnp.concatenate([v1, _before(hand(v2))], -1).reshape(T, G, d)
    if "cache" in lowp:
        k, v = (jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
                for x in (k, v))
    qg = q.reshape(T, G, H // G, d)
    s = jnp.einsum("tghd,sgd->ghts", qg, k) / root
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("ghts,sgd->tghd", jax.nn.softmax(s, -1), v)
    f = o.reshape(T, H * d) @ w["wo"]
    tails = jnp.concatenate([hand(c), hand(a), hand(v2)], -1)[snaps]
    return (_merge(h, f, w["res_attn"]), tails,
            jnp.concatenate([k.reshape(T, -1), v.reshape(T, -1)], -1))


@partial(jax.jit, static_argnames=("first", "eps", "lowp"))
def _routed(h, r, layers, router, we_gate_up, we_down, l, forced, *, first,
            eps, lowp):
    """The routed sublayer of layer ``l``; ``r`` (T, R) what the layer
    before's router left (``first``: the model's first layer, which
    receives nothing). ``forced`` (T, 1) int32: the expert to USE at
    each position (negative: the reference's own choice). Returns (h',
    r', margin (T,): the best selection score of the reference's OWN
    choice over the next, swapped (T,): the own choice is not the forced
    one)."""
    lw = _take(layers, ("mlp_norm", "res_mlp"), l)
    rt = _take(router, ("w_down", "b_down", "gamma", "norm", "w1", "b1",
                        "w2", "b2", "w3", "bias"), l)
    s = _rms(h, lw["mlp_norm"], eps)

    def lin(x, w):
        if "router" in lowp:
            return _f32(jnp.dot(x.astype(jnp.bfloat16),
                                w.astype(jnp.bfloat16)))
        return x @ w

    here = lin(s, rt["w_down"]) + rt["b_down"]
    r = here if first else here + rt["gamma"] * r
    x = _rms(r, rt["norm"], eps)
    x = jax.nn.gelu(lin(x, rt["w1"]) + rt["b1"], approximate=False)
    x = jax.nn.gelu(lin(x, rt["w2"]) + rt["b2"], approximate=False)
    p = jax.nn.softmax(lin(x, rt["w3"]), -1)                   # (T, E)
    sel = p + rt["bias"]
    order = jnp.argsort(-sel, axis=-1)                  # ties: low index
    own = order[:, :1]
    ranked = jnp.take_along_axis(sel, order[:, :2], -1)
    margin = ranked[:, 0] - ranked[:, 1]
    given = forced[:, :1] >= 0
    chosen = jnp.where(given, forced[:, :1], own)
    swapped = given[:, 0] & (own[:, 0] != chosen[:, 0])
    gate = jnp.take_along_axis(p, chosen, -1)          # NOT renormalised
    F = we_down.shape[1]

    def one(e, acc):
        gu = _f32(we_gate_up[e])
        y = (jax.nn.silu(s @ gu[:, :F]) * (s @ gu[:, F:])) @ _f32(we_down[e])
        return acc + jnp.where(chosen == e, gate, 0.0) * y

    y = jax.lax.fori_loop(0, we_down.shape[0], one, jnp.zeros_like(h))
    return _merge(h, y, lw["res_mlp"]), r, margin, swapped


#: Rows of the vocabulary the head upcasts at a time (the whole of
#: 262,272 x 2,048 in float32 would be 2.1 GB beside the served weights).
HEAD_ROWS = 32768


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, embed, h, rows, *, eps):
    x = _rms(h[rows], _f32(final_norm), eps)
    return jnp.concatenate(
        [x @ _f32(embed[a:a + HEAD_ROWS]).T
         for a in range(0, embed.shape[0], HEAD_ROWS)], -1)


def _lowp(lowp) -> Tuple[str, ...]:
    names = LOWP if lowp is True else tuple(lowp or ())
    if set(names) - set(LOWP):
        raise ValueError(f"lowp names {names}: of {LOWP}")
    return names


class Forward(NamedTuple):
    """``routed_forward``'s: float32 logits ``(len(rows), V)``; margins
    and swapped ``(layers, len(rows))`` (``_routed``'s); tails ``(layers,
    len(snaps), 2 C + W)``: what each layer's token at each of the
    positions ``snaps`` hands the next; kv ``(layers, T, 2 G d)``: what
    a cache holds of every position (``_cca``)."""
    logits: jnp.ndarray
    margins: jnp.ndarray
    swapped: jnp.ndarray
    tails: jnp.ndarray
    kv: jnp.ndarray


def routed_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                   rows, lowp=(), forced=None, snaps=()) -> Forward:
    """One sequence ``tokens`` ``(T,)`` judged at the positions ``rows``.
    ``forced`` (layers, T, 1) int32, or None: every position routed by
    the reference's own choice."""
    if set(model["layer_types"]) != {"hybrid"}:
        raise ValueError("the reference is written for hybrid layers")
    if model["num_experts_per_tok"] != 1:
        raise ValueError("the reference is written for one expert a token")
    lowp = _lowp(lowp)
    L = model["num_hidden_layers"]
    H, G, d = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    eps = float(model["rms_norm_eps"])
    rope = model["rope_parameters"]["hybrid"]
    theta = float(rope["rope_theta"])
    rot = int(round(d * float(rope["partial_rotary_factor"])))
    rows = jnp.asarray(rows, jnp.int32)
    T = len(tokens)
    if forced is None:
        forced = jnp.full((L, T, 1), -1, jnp.int32)
    forced = jnp.asarray(forced, jnp.int32)
    snaps = jnp.asarray(snaps, jnp.int32).reshape(-1)
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][jnp.asarray(tokens, jnp.int32)])
        r = jnp.zeros((T, model["router_hidden_size"]), jnp.float32)
        margins, swaps, tails, kvs = [], [], [], []
        for l in range(L):
            h, tail, kv = _cca(h, params["layers"], jnp.int32(l), snaps,
                               H=H, G=G, d=d, rot=rot, eps=eps, theta=theta,
                               lowp=lowp)
            h, r, m, sw = _routed(
                h, r, params["layers"], params["router"],
                params["moe"]["we_gate_up"][l], params["moe"]["we_down"][l],
                jnp.int32(l), forced[l], first=(l == 0), eps=eps, lowp=lowp)
            tails.append(tail)
            kvs.append(kv)
            margins.append(m[rows])
            swaps.append(sw[rows])
        return Forward(
            _head(params["final_norm"], params["embed"], h, rows, eps=eps),
            jnp.stack(margins), jnp.stack(swaps), jnp.stack(tails),
            jnp.stack(kvs))


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
#: ``(len(at), V)`` float32, ``tails`` ``(layers, 2 C + W)`` what that
#: row's tail held behind ``at[-1]`` and ``kv`` ``(layers, at[-1] + 1,
#: 2 G d)`` what the page pool held of the row's every position by then
#: (or None: not looked at). ``chosen`` ``(rows, layers, len(tokens),
#: 1)`` int32: the expert the program chose at every position a row ran
#: (negative where it ran none).
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


def worst_row(got: np.ndarray, ref: np.ndarray) -> float:
    """The largest ``|got - ref| / |ref|`` of ONE row, ``got`` and
    ``ref`` ``(layers, rows, width)``: a position's cached K and V in a
    layer."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.sqrt(np.sum((got - ref) ** 2, -1)
                         / (np.sum(ref ** 2, -1) + 1e-30)).max())


def judge(got: np.ndarray, ref: np.ndarray, margins: np.ndarray,
          swapped: np.ndarray, tail_rel: list, kv_rel: Optional[list],
          tol: Dict[str, Any], kv_row: Optional[float] = None
          ) -> Dict[str, Any]:
    """One group: ``got`` and ``ref`` ``(positions, V)`` in the order of
    the positions, the reference routed by the served choices;
    ``margins`` and ``swapped`` ``(layers, positions)`` (``Forward``);
    ``tail_rel`` / ``kv_rel`` the ``layer_distances`` of the layers'
    tails behind the group's last position and of their cached K and V
    rows up to it, ``kv_row`` the ``worst_row`` of those (None: the
    group does not look at them). Six limits;
    the one over the positions' distribution holds for a group of
    ``MANY`` positions or more:

    - ``rms_clean``: the ``clean_quantile`` of the positions' RMS
      differences: what the products' rounding leaves, the same in
      every seed;
    - ``rms``: the worst position (logits that have nothing to do with
      the reference's);
    - ``tail_rel``: the worst layer's tail. A tail that was not handed
      on, not zeroed, or moved on by a token too many is another
      token's: it lies its whole norm away;
    - ``kv_rel``: the worst layer's cached rows — K after the mix, the
      norm, the temperature and the rotation, V after the shift. The
      limit that sees the cache's precision: attended as a near-even
      mean over thousands of keys an 8-bit cache moves the logits by
      less than the products' rounding, and its rows show it at once;
    - ``kv_row``: the worst single position's cached row in any layer.
      The limit that sees a tail that was NOT HANDED ON: the first
      token of a slice that was handed zeros (or another sequence's
      tail) writes a K and a V that are another token's — its whole
      norm away — while every later token's differ only through what
      that one did to the stream, which the distances over a whole
      layer (a thousand sound rows to one) hardly show;
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
    many = len(rms) >= MANY
    decisive = float(margins[swapped].max()) if swapped.any() else 0.0
    return {"ok": bool((clean <= tol["rms_clean"] or not many)
                       and worst <= tol["rms"]
                       and max(tail_rel) <= tol["tail_rel"]
                       and (kv_rel is None or max(kv_rel) <= tol["kv_rel"])
                       and (kv_row is None or kv_row <= tol["kv_row"])
                       and decisive <= tol["margin_decisive"]),
            "rms_clean": clean, "rms": worst, "tail_rel": max(tail_rel),
            "kv_rel": None if kv_rel is None else max(kv_rel),
            "kv_row": kv_row, "swap_margin": decisive, "positions": int(rms.size),
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
    for row in sorted({g["row"] for g in groups.values()}):
        mine = {n: g for n, g in groups.items() if g["row"] == row}
        snaps = sorted({int(g["at"][-1]) for g in mine.values()})
        # the head runs the positions some group of the row looks at,
        # and no others (a position's logits are a megabyte here)
        seen = np.unique(np.concatenate([g["at"] for g in mine.values()]))
        ref = routed_forward(params, tokens, model, seen,
                             forced=chosen[row], snaps=snaps)
        low = lowp and routed_forward(params, tokens, model, seen, lowp,
                                      chosen[row], snaps)
        ref, low = (x and Forward(*map(np.asarray, x)) for x in (ref, low))
        for name, g in mine.items():
            at, n = np.searchsorted(seen, g["at"]), int(g["at"][-1]) + 1
            snap = snaps.index(n - 1)
            if lowp:
                g = dict(g, logits=low.logits[at], tails=low.tails[:, snap],
                         kv=None if g["kv"] is None else low.kv[:, :n])
            yield name, judge(
                g["logits"], ref.logits[at], ref.margins[:, at],
                ref.swapped[:, at],
                layer_distances(g["tails"], ref.tails[:, snap]),
                None if g["kv"] is None else layer_distances(
                    g["kv"], ref.kv[:, :n]), tol,
                None if g["kv"] is None else worst_row(g["kv"],
                                                       ref.kv[:, :n]))


def reference_logits(params: Dict[str, Any], tokens, model: Dict[str, Any],
                     rows) -> jnp.ndarray:
    """The family's surface: ``model`` is the configuration file's
    ``model`` block (``shapes.MODEL_KEYS``). While ``JUDGED`` is set and
    ``tokens`` is of ``tolerance.min_positions`` or more, the family's
    own sequence (``judged_sequence``, ``tolerance.judged_tokens`` long:
    longer than a prefill slice, so the tail is handed from slice to
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
                raise NotCorrect(
                    f"{group}: the {tol['clean_quantile']} quantile of "
                    f"{got['positions']} positions' RMS differences is "
                    f"{got['rms_clean']:.4f} (limit rms_clean "
                    f"{tol['rms_clean']} over {MANY} positions or more), "
                    f"the worst {got['rms']:.4f} (limit rms {tol['rms']}), "
                    f"the worst layer's tail lies {got['tail_rel']:.5f} of "
                    f"its norm from the reference's (limit tail_rel "
                    f"{tol['tail_rel']}), its cached K and V rows "
                    f"{got['kv_rel']} (limit kv_rel {tol['kv_rel']}), the "
                    f"worst single row of them {got['kv_row']} (limit "
                    f"kv_row {tol['kv_row']}), the clearest choice the served path did not make had a "
                    f"margin of {got['swap_margin']:.4f} (limit "
                    f"margin_decisive {tol['margin_decisive']})")
    return reference_forward(params, tokens, model, rows)[0]
