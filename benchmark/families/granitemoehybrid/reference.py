"""The plain reference: the Granite-4.0-H block (``granitemoehybrid``
with no routed experts) as the public ``modeling_granitemoehybrid.py``
of Hugging Face describes it, in straightforward ``jax.numpy`` and
float32: no kernels, no cache, no batching, one sequence at a time,
``jax.default_matmul_precision("highest")``, one layer upcast at a
time so that it fits beside the served model.

With N, N' a layer's RMSNorms and r = ``residual_multiplier``::

    x0 = embedding_multiplier * E[token]
    h  = x + r * Mixer_l(N(x));  y = h + r * W_out(silu(g) * u),
                                 [g ; u] = N'(h) W_in
    logits = RMSNorm(x_L) E^T / logits_scaling

    Attn:   causal, grouped queries, NO rotary embedding, scores times
            ``attention_multiplier``.
    Mamba2: [z ; xBC ; dt] = x W_inproj; xBC_t = silu(b + sum_j w[:, j]
            xBC_{t-3+j}); [X ; B ; C] = xBC; dt = softplus(dt + dt_bias);
            H_t = exp(dt_t A) H_{t-1} + dt_t X_t (x) B_t;
            Y_t = H_t C_t + D X_t; out = W_outproj RMSNorm(Y * silu(z)).

**The recurrence is computed TOKEN BY TOKEN** (``lax.scan`` over the
positions, the state ``(heads, head, state)`` as the equations write
it): it shares nothing with the program's chunked scan, its one-token
update, its state layout or its kernel, so it is what those are held
against.

It shares no code with ``llmq_tpu`` and none with ``adapter.py``; it
reads the served parameter tree by its leaf names. Departures from the
published code: none in the mathematics. The published code computes
the convolution and the scan in the checkpoint's type where its fused
kernels run and in float32 on its plain path — this is the plain path;
``time_step_limit`` is the published (0, inf), so nothing is clamped;
``mamba_n_groups`` is 1 (one B and one C for all heads) and nothing
else is written here; the weights are random.

``JUDGED``: while the harness's check runs, the adapter leaves here a
function that drives the SERVED path further than the harness's three
decode steps — a prompt in two slices, the state carried between the
programs, then some hundreds of teacher-forced decode steps at the
served batch width — and ``reference_logits`` holds its logits to
``tolerance.decode_rms`` and ``tolerance.decode_growth`` before it
answers (``judge``; ``README.md`` has why, and the control the limits
were set against).
"""

from __future__ import annotations

import json
import sys
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MAMBA = "mamba"


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _take(layers: Dict[str, Any], names, i):
    return {k: _f32(jax.lax.dynamic_index_in_dim(layers[k], i, 0,
                                                 keepdims=False))
            for k in names}


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "scale", "eps", "r"))
def _attention(h, layers, l, i, *, n_heads, n_kv, scale, eps, r):
    w = {**_take(layers, ("attn_norm",), l),
         **_take(layers, ("wq", "wk", "wv", "wo"), i)}
    T = h.shape[0]
    x = _rms(h, w["attn_norm"], eps)
    q = (x @ w["wq"]).reshape(T, n_heads, -1)
    k = (x @ w["wk"]).reshape(T, n_kv, -1)
    v = (x @ w["wv"]).reshape(T, n_kv, -1)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    return h + r * (a.reshape(T, -1) @ w["wo"])


@partial(jax.jit, static_argnames=("heads", "state", "eps", "r"))
def _mamba(h, layers, l, i, *, heads, state, eps, r):
    w = {**_take(layers, ("attn_norm",), l),
         **_take(layers, ("in_proj", "conv_w", "conv_b", "dt_bias", "a_log",
                          "d_skip", "ssm_norm", "out_proj"), i)}
    T = h.shape[0]
    inner = w["ssm_norm"].shape[0]
    K = w["conv_w"].shape[1]
    zxd = _rms(h, w["attn_norm"], eps) @ w["in_proj"]
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:inner + inner + 2 * state],
                  zxd[:, inner + inner + 2 * state:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(w["conv_b"] + sum(
        padded[j:j + T] * w["conv_w"][:, j] for j in range(K)))
    x = xbc[:, :inner].reshape(T, heads, -1)
    bm, cm = xbc[:, inner:inner + state], xbc[:, inner + state:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # (T, heads)
    a = -jnp.exp(w["a_log"])

    def step(hs, t):
        x_t, b_t, c_t, dt_t = t
        hs = (jnp.exp(dt_t * a)[:, None, None] * hs
              + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return hs, hs @ c_t + w["d_skip"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, x.shape[2], state)),
                        (x, bm, cm, dt))
    y = _rms(y.reshape(T, inner) * jax.nn.silu(z), w["ssm_norm"], eps)
    return h + r * (y @ w["out_proj"])


@partial(jax.jit, static_argnames=("eps", "r"))
def _swiglu(h, layers, l, *, eps, r):
    w = _take(layers, ("mlp_norm", "w_gate", "w_up", "w_down"), l)
    x = _rms(h, w["mlp_norm"], eps)
    return h + r * ((jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"]))
                    @ w["w_down"])


@partial(jax.jit, static_argnames=("eps",))
def _head(final_norm, embed, h, rows, *, eps):
    return _rms(h[rows], _f32(final_norm), eps) @ _f32(embed).T


def reference_forward(params: Dict[str, Any], tokens, model: Dict[str, Any],
                      rows) -> jnp.ndarray:
    """float32 logits ``(len(rows), V)`` of one sequence ``tokens`` at
    the positions ``rows``; ``model`` the configuration's keys
    (``shapes.MODEL_KEYS``)."""
    if model.get("mamba_n_groups", 1) != 1 or model.get(
            "num_local_experts", 0):
        raise ValueError("the reference is written for one B/C group and "
                         "no routed experts")
    eps = float(model["rms_norm_eps"])
    r = float(model["residual_multiplier"])
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        h = _f32(params["embed"][tokens]) * float(
            model["embedding_multiplier"])
        seen = {True: 0, False: 0}
        for l, kind in enumerate(model["layer_types"]):
            is_m = kind == MAMBA
            i = jnp.int32(seen[is_m])
            seen[is_m] += 1
            if is_m:
                h = _mamba(h, params["layers"], jnp.int32(l), i,
                           heads=model["mamba_n_heads"],
                           state=model["mamba_d_state"], eps=eps, r=r)
            else:
                h = _attention(h, params["layers"], jnp.int32(l), i,
                               n_heads=model["num_attention_heads"],
                               n_kv=model["num_key_value_heads"],
                               scale=float(model["attention_multiplier"]),
                               eps=eps, r=r)
            h = _swiglu(h, params["layers"], jnp.int32(l), eps=eps, r=r)
        return _head(params["final_norm"], params["embed"], h,
                     jnp.asarray(rows, jnp.int32),
                     eps=eps) / float(model["logits_scaling"])


class NotCorrect(AssertionError):
    """The serving path's logits, driven through carried state and many
    decode steps, are not the reference's."""


#: ``(served_many, tolerance)`` while the family's serving path is under
#: the harness's check, else ``None``. ``served_many(params, tokens) ->
#: {group: (rows, logits (len(rows), V))}``: the serving path's float32
#: logits at the positions ``rows`` of the one sequence ``tokens``.
JUDGED: Optional[Tuple[Callable[..., Dict[str, Any]], Dict[str, Any]]] = None


def judge(served, ref, tol: Dict[str, Any]) -> Dict[str, Any]:
    """The judged decode positions' RMS differences, in order. Two
    limits, and a run is refused by either: the WORST position is held
    to ``tolerance.decode_rms`` (a wrong program), and the GROWTH over
    the judged steps — the last quarter's mean over the first
    quarter's — to ``tolerance.decode_growth``. What the products'
    bfloat16 rounding leaves is the same at every step; what a state
    held too narrow loses is carried on from token to token, so it
    GROWS with the steps decoded — and the ratio does not move with a
    seed's level of error as the level itself does."""
    rms = np.sqrt(np.mean(np.square(
        np.asarray(served, np.float32) - np.asarray(ref, np.float32)), -1))
    q = max(1, len(rms) // 4)
    first, last = float(rms[:q].mean()), float(rms[-q:].mean())
    growth = last / max(first, 1e-30)
    over = [k for k, v in (("decode_rms", float(rms.max())),
                           ("decode_growth", growth)) if v > tol[k]]
    return {"ok": not over, "over": over, "rms": float(rms.max()),
            "rms_mean": float(rms.mean()), "rms_first_quarter": first,
            "rms_last_quarter": last, "growth": growth,
            "positions": int(rms.size)}


def reference_logits(params: Dict[str, Any], tokens, model: Dict[str, Any],
                     rows) -> jnp.ndarray:
    """The family's surface. While ``JUDGED`` is set, a sequence of at
    least ``tolerance.min_positions`` tokens is first driven through the
    served path's carried state (``served_many``) and each group held
    to ``judge``: one line a group on standard error, ``NotCorrect`` for
    one that is over a limit."""
    if JUDGED is None or len(tokens) < JUDGED[1].get("min_positions", 0):
        return reference_forward(params, tokens, model, rows)
    served_many, tol = JUDGED
    ref = np.asarray(reference_forward(params, tokens, model,
                                       np.arange(len(tokens))))
    for group, (at, served) in served_many(params, tokens).items():
        got = judge(served, ref[np.asarray(at)], tol)
        sys.stderr.write(json.dumps({"judged": group, **got}) + "\n")
        if not got["ok"]:
            raise NotCorrect(
                f"{group}: over {got['positions']} decode positions the "
                f"worst RMS difference is {got['rms']:.6f} (limit "
                f"decode_rms {tol['decode_rms']}) and the last quarter's "
                f"mean is {got['growth']:.4f} of the first's (limit "
                f"decode_growth {tol['decode_growth']}): over "
                f"{', '.join(got['over'])}")
    return jnp.asarray(ref[np.asarray(rows)])
