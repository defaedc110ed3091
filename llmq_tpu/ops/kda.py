"""Delta-rule linear attention with a decay a CHANNEL (Kimi Delta
Attention, arXiv:2510.26692) in plain JAX: the one-token state update
(decode rows) and the chunked scan (prompt slices and the prefill
program), and the routes to their kernels (``ops/pallas/kda_update.py``,
``ops/pallas/kda_scan.py``). The convolution in front of it is
``ops/ssm.py``'s.

The recurrence, a head (``k_t``, ``q_t`` its d_k key values at unit
length, ``v_t`` its d_v values, ``a_t = exp(g_t)`` in (0, 1]^d_k, ``b_t``
in (0, 2))::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T   # (d_k, d_v)
    o_t = S_t^T q_t

**The decay and beta are DATA here**: the update, the scan and both
kernels take ``g_t <= 0`` and ``b_t`` as they come and know nothing of
how a family makes them. Two forms are served:

- the BOUNDED decay (Ling-3.0-flash's safe gate, ``models/ling_hybrid.
  py``): ``g = lower * sigmoid(exp(A_log)_h (x W_f + b_f))`` in
  (``lower``, 0) = (-5, 0) a token, full-rank ``W_f``, ``b = sigmoid(x
  W_b)`` in (0, 1);
- the published Kimi form (:func:`kimi_decay`, :func:`low_rank`;
  ``models/solar_open2.py``): ``g = -exp(A_log)_h softplus(W_f^up
  W_f^down x + dt_bias)``, UNBOUNDED below, through a low-rank pair, and
  ``b = 2 sigmoid(x W_b)`` in (0, 2) (arXiv:2411.12537: ``I - b k k^T``
  then has an eigenvalue in (-1, 1), a NEGATIVE one for b > 1; still a
  contraction, so the state stays bounded).

which, with ``u_t = k_t^T (a_t * S_{t-1})`` (what the decayed state
answers to the key), is ``S_t = a_t * S_{t-1} + b_t k_t (v_t - u_t)^T``:
the state is READ (a reduction over d_k) before it can be written. That
is what sets it apart from ``ops/ssm.py``'s update, a lane-wise decay
and a rank-one add.

**The state's layout** is ``ops/ssm.py``'s: a row's state of one layer
is ``(d_k, H * d_v)`` float32 — the key dimension on the sublanes, heads
and their values side by side on the lanes — and a family's leaf stacks
it ``(layers, rows, d_k, H * d_v)``. ``u`` and ``o`` are sums down the
sublanes, the decay and the key are columns broadcast along the lanes
of their head: nothing crosses lanes (``ops/pallas/kda_update.py`` is
the in-place kernel over this layout; ``state_rows_read`` /
``state_rows_write`` of ``ops/pallas/ssm_update.py`` and ``ops/ssm``'s
``rows_read`` / ``rows_write`` / ``decode_walk`` serve it as it lies).

Everything here computes in float32 whatever the activations' type, and
a product that feeds the state asks for ``Precision.HIGHEST``.

**Inside a chunk** the scan never divides by a cumulative decay: with
``G_i`` the log-decays summed from the chunk's start, token ``i`` reads
token ``j <= i`` through ``exp(G_i - G_j)``, a DIFFERENCE a channel,
always in (0, 1] — ``exp(G_i) / exp(G_j)`` underflows float32 within 18
tokens at a decay of -5 a token (the bounded form's floor), and within
one at the decays the unbounded form reaches. Every factor the scan
does form — ``exp(G_i)`` from a chunk's (the kernel: a block's) start,
``exp(G_C - G_i)`` to its end — is of a sum that only falls, so it lies
in (0, 1] whatever the decay, and one that underflows to 0 is of a term
whose true value underflows as well. The chunk's unit-lower solve is
exact for any ``b``; with ``b`` up to 2 its inverse's entries grow with
the keys' overlap (``|k_i . k_j| b``), not with the decay
(``tests/test_kda.py`` holds both scans to the update a token at a time
with b in (0, 2) and a decay that underflows a block's factor).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from llmq_tpu.ops.ssm import decode_walk

#: ``x / sqrt(sum x^2 + L2_EPS)``: q's and k's normalisation a head.
L2_EPS = 1e-6


def l2_norm(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` (..., d) float32 at unit length over its last axis."""
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def low_rank(x: jnp.ndarray, down: jnp.ndarray, up: jnp.ndarray, *,
             exact: bool = False) -> jnp.ndarray:
    """``(x W_down) W_up`` of a low-rank pair, ``x`` (M, D), ``down``
    (D, r), ``up`` (r, W): two small products, float32 out. ``exact``:
    the second at ``Precision.HIGHEST`` over the float32 intermediate
    (the decay's pair: the r values a token would else be rounded to
    bfloat16 on the way in, and the log-decay carries a logit's error on
    from token to token — ``models/ling_hybrid._kda_in`` has what a
    rounded logit cost)."""
    f32 = jnp.float32
    mid = jnp.dot(x, down, preferred_element_type=f32)
    if exact:
        return jnp.dot(mid, up.astype(f32), precision=lax.Precision.HIGHEST)
    return jnp.dot(mid.astype(x.dtype), up, preferred_element_type=f32)


def kimi_decay(f: jnp.ndarray, a_log: jnp.ndarray,
               dt_bias: jnp.ndarray) -> jnp.ndarray:
    """Kimi Linear's log-decay a channel, unbounded below: ``g =
    -exp(A_log)_h softplus(f + dt_bias)`` for ``f`` (M, H * d) float32
    (the low-rank pair's product, heads side by side on the lanes as
    the state's), ``a_log`` (H,), ``dt_bias`` (H * d,). Returns
    (M, H * d) float32, < 0 — FLAT, as it came: on the TPU a reshape to
    (M, H, d) moves the rows off the sublanes, a copy of the whole array
    each way (``models/solar_open2._unit`` has the same)."""
    f32 = jnp.float32
    a = jnp.repeat(jnp.exp(a_log.astype(f32)), f.shape[-1] // a_log.shape[0])
    return -a * jax.nn.softplus(f.astype(f32) + dt_bias.astype(f32))


def conv_step(pool: jnp.ndarray, layer, x: jnp.ndarray, w: jnp.ndarray,
              active: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the depthwise causal convolution WITHOUT a bias,
    on the first B rows of layer ``layer`` of the window leaf ``pool``
    (L, R, (K-1) * C) — a row's last K-1 inputs laid end to end, oldest
    first; ``x`` (B, C) the new inputs, ``w`` (C, K). Returns ``(silu(
    conv) (B, C) float32, pool with the windows of the ``active`` rows
    moved on by one)``.

    ``ops/ssm.conv_step`` with the leaf's handling inside it, and
    written tap by tap: each tap is read from its C lanes of the leaf, a
    sum of K elementwise products is the convolution, and each tap of
    the new window is written to its own C lanes. Over 12,288 channels
    and 128 rows the other form's ``einsum`` and, after it, its
    concatenation of the taps along the flat axis made XLA hold the
    window rows-minor-most, give the WHOLE leaf that layout and copy it
    in and out a layer (compiled for a described v5e, PR 45)."""
    B, C = x.shape
    K = w.shape[1]
    taps = [pool[layer, :B, j * C:(j + 1) * C] for j in range(K - 1)]
    taps.append(x.astype(pool.dtype))
    wf = w.astype(jnp.float32)
    y = sum(taps[j].astype(jnp.float32) * wf[:, j] for j in range(K))
    moved = jnp.where(active[:, None], jnp.concatenate(taps[1:], axis=1),
                      jnp.concatenate(taps[:-1], axis=1))
    return jax.nn.silu(y), pool.at[layer, :B].set(moved)


def kda_update(state: jnp.ndarray, q: jnp.ndarray, k: jnp.ndarray,
               v: jnp.ndarray, g: jnp.ndarray, beta: jnp.ndarray,
               active: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row. ``state`` (B, d_k, H*d_v); ``q``, ``k``, ``g``
    (B, H, d_k) — ``g`` the log-decay, at most 0, of either form;
    ``v`` (B, H, d_v); ``beta`` (B, H), in (0, 2). Returns ``(o (B, H,
    d_v) float32, the new state in
    ``state.dtype``)``; a row that is not ``active`` keeps its state
    (its ``o`` is of no use). Elementwise products and sums over d_k,
    all float32: no matrix product, so nothing is rounded on the way."""
    B, H, dk = k.shape
    f32 = jnp.float32

    def col(x):                         # (B, H, d_k) -> (B, d_k, H, 1)
        return jnp.swapaxes(x.astype(f32), 1, 2)[..., None]

    s = state.astype(f32).reshape(B, dk, H, -1) * col(jnp.exp(g.astype(f32)))
    u = jnp.sum(s * col(k), axis=1)                            # (B, H, d_v)
    new = s + (col(k) * beta.astype(f32)[:, None, :, None]
               * (v.astype(f32) - u)[:, None])
    o = jnp.sum(new * col(q), axis=1)
    new = new.reshape(state.shape).astype(state.dtype)
    if active is not None:
        new = jnp.where(active[:, None, None], new, state)
    return o, new


def _inverse_unit_lower(m: jnp.ndarray) -> jnp.ndarray:
    """``(I + m)^-1`` for ``m`` (..., C, C) STRICTLY lower triangular:
    the sum of ``(-m)^n``, n < C, as the product of ``I + (-m)^(2^i)``."""
    C = m.shape[-1]
    hi = lax.Precision.HIGHEST
    p = -m
    t = jnp.eye(C, dtype=m.dtype) + p
    for _ in range(max(0, math.ceil(math.log2(max(C, 2))) - 1)):
        p = jnp.matmul(p, p, precision=hi)
        t = t + jnp.matmul(t, p, precision=hi)
    return t


def kda_scan(state: jnp.ndarray, q: jnp.ndarray, k: jnp.ndarray,
             v: jnp.ndarray, g: jnp.ndarray, beta: jnp.ndarray,
             lengths: jnp.ndarray, chunk: int
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over S slices of T tokens, ``chunk`` tokens at a
    time. ``state`` (S, d_k, H*d_v) each slice's state BEFORE its first
    token; ``q``, ``k``, ``g`` (S, T, H, d_k); ``v`` (S, T, H, d_v);
    ``beta`` (S, T, H); ``lengths`` (S,). Returns ``(o (S, T, H, d_v)
    float32, the state behind each slice's last VALID token in
    ``state.dtype``)``: a token past its slice's length has ``g`` 0 and
    ``beta`` 0, so it neither decays the state nor feeds it.

    A chunk of C tokens from the state ``S_0`` (``G`` the log-decays
    summed from its start, ``A_ij = sum_d k_i k_j exp(G_i - G_j)`` for
    j < i, ``B_ij`` the same with ``q_i`` for j <= i)::

        (I + A Diag(b)) W = V - (K * exp(G)) S_0      # W: v_t - u_t
        O   = (Q * exp(G)) S_0 + B Diag(b) W
        S_C = exp(G_C) * S_0 + (K * exp(G_C - G) * b)^T W

    The triangular system is solved once a chunk for all chunks side by
    side (its inverse times ``V`` and times ``K * exp(G)``); what is
    left to the loop over the chunks is four small products with the
    carried state."""
    S, T, H, dk = k.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    hi = lax.Precision.HIGHEST
    Q = min(chunk, T)
    pad = -T % Q
    valid = (jnp.arange(T)[None, :] < lengths[:, None])[:, :, None]
    g = jnp.where(valid[..., None], g.astype(f32), 0.0)
    beta = jnp.where(valid, beta.astype(f32), 0.0)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    if pad:
        g, beta, qf, kf, vf = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (g, beta, qf, kf, vf))
    c = (T + pad) // Q

    def chunks(x):                      # (S, T, H, ...) -> (S, c, H, Q, ...)
        return jnp.moveaxis(x.reshape((S, c, Q) + x.shape[2:]), 3, 2)

    g, qf, kf, vf = chunks(g), chunks(qf), chunks(kf), chunks(vf)
    beta = chunks(beta)                                        # (S,c,H,Q)
    # (lax's: jnp.cumsum is a jit of its own, ``ops/ssm.ssm_scan``)
    gc = lax.cumsum(g, axis=3)                                 # (S,c,H,Q,dk)
    # token i reads token j <= i through exp(G_i - G_j), a channel
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    seen = jnp.exp(jnp.where(tri[..., None],
                             gc[..., :, None, :] - gc[..., None, :, :],
                             -jnp.inf))                        # (..,i,j,dk)
    a = jnp.sum(kf[..., :, None, :] * kf[..., None, :, :] * seen, axis=-1)
    b = jnp.sum(qf[..., :, None, :] * kf[..., None, :, :] * seen, axis=-1)
    a = jnp.where(jnp.tril(jnp.ones((Q, Q), bool), -1), a, 0.0)
    inv = _inverse_unit_lower(a * beta[..., None, :])
    from_start = jnp.exp(gc)
    u0 = jnp.matmul(inv, vf, precision=hi)                     # (..,Q,dv)
    wk = jnp.matmul(inv, kf * from_start, precision=hi)        # (..,Q,dk)
    qg = qf * from_start
    bb = b * beta[..., None, :]
    to_end = kf * jnp.exp(gc[..., -1:, :] - gc) * beta[..., None]
    whole = from_start[..., -1, :]                             # (S,c,H,dk)

    def carry(s0, step):
        u0, wk, qg, bb, to_end, whole = step
        w = u0 - jnp.matmul(wk, s0, precision=hi)
        o = jnp.matmul(qg, s0, precision=hi) + jnp.matmul(bb, w,
                                                          precision=hi)
        s1 = whole[..., None] * s0 + jnp.matmul(
            jnp.swapaxes(to_end, -1, -2), w, precision=hi)
        return s1, o

    first = jnp.moveaxis(state.astype(f32).reshape(S, dk, H, dv), 2, 1)
    last, o = lax.scan(carry, first, tuple(
        jnp.moveaxis(x, 1, 0) for x in (u0, wk, qg, bb, to_end, whole)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)              # (S,c,Q,H,dv)
    return (o.reshape(S, T + pad, H, dv)[:, :T],
            jnp.moveaxis(last, 1, 2).reshape(S, dk, H * dv)
            .astype(state.dtype))


def scan_route(d_k: int, n_heads: int, d_v: int, T: int, chunk: int, *,
               enabled: bool = True) -> Tuple[bool, bool]:
    """``(use the scan kernel, in interpret mode)`` for slices of ``T``
    tokens worked in exact blocks of ``chunk``: ``ops/attention.
    _kernel_route``'s policy, as :func:`update_route`, and the shapes
    ``ops/pallas/kda_scan.py`` is written for."""
    from llmq_tpu.ops.attention import _kernel_route
    from llmq_tpu.ops.pallas.kda_scan import kda_scan_viable
    return _kernel_route(n_heads * d_v, enabled=enabled,
                         extra_ok=kda_scan_viable(d_k, n_heads, d_v, T,
                                                  chunk))


def kda_scan_slices(state: jnp.ndarray, q: jnp.ndarray, k: jnp.ndarray,
                    v: jnp.ndarray, g: jnp.ndarray, beta: jnp.ndarray,
                    lengths: jnp.ndarray, chunk: int, *,
                    enabled: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`kda_scan` of a program's prompt slices: the kernel where
    :func:`scan_route` takes them — ``chunk`` then the tokens of a block
    it works in exact differences, inside steps of 64 —, else XLA's
    scan. An output past its slice's length is of no use either way."""
    S, T, H, dk = k.shape
    use_kernel, interpret = scan_route(dk, H, v.shape[-1], T, chunk,
                                       enabled=enabled)
    if not use_kernel:
        return kda_scan(state, q, k, v, g, beta, lengths, chunk)
    from llmq_tpu.ops.pallas.kda_scan import kda_scan_pallas
    return kda_scan_pallas(state, q, k, v, g, beta, lengths, block=chunk,
                           interpret=interpret)


def update_route(d_k: int, n_heads: int, d_v: int, *,
                 enabled: bool = True) -> Tuple[bool, bool]:
    """``(use the in-place kernel, in interpret mode)`` for a state of
    ``(d_k, n_heads * d_v)`` float32 a row: ``ops/attention.
    _kernel_route``'s policy (``LLMQ_PALLAS``, the backend, the caller's
    ``enabled``) and the shapes the kernel is written for."""
    from llmq_tpu.ops.attention import _kernel_route
    from llmq_tpu.ops.pallas.kda_update import kda_update_viable
    return _kernel_route(n_heads * d_v, enabled=enabled,
                         extra_ok=kda_update_viable(d_k, n_heads, d_v))


def kda_update_layer(pool: jnp.ndarray, layer, q: jnp.ndarray,
                     k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                     beta: jnp.ndarray, active: jnp.ndarray, *, walk=None,
                     enabled: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`kda_update` on the first B rows of layer ``layer`` of the
    stacked leaf ``pool`` (L, R, d_k, H*d_v), R >= B: the in-place
    kernel where :func:`update_route` takes it (over ``walk``, the
    step's ``ops/ssm.decode_walk``; made here if a caller has none),
    else XLA's fusion of the same and a ``dynamic_update_slice`` of the
    rows. Returns ``(o (B, H, d_v) float32, pool)``: a row that is not
    ``active`` keeps its state, and its ``o`` is of no use."""
    B, H, dk = k.shape
    use_kernel, interpret = update_route(dk, H, v.shape[-1],
                                         enabled=enabled)
    if not use_kernel:
        o, new = kda_update(pool[layer, :B], q, k, v, g, beta, active)
        return o, pool.at[layer, :B].set(new)
    from llmq_tpu.ops.pallas.kda_update import kda_update_pallas
    if walk is None:
        walk = decode_walk(active)
    return kda_update_pallas(pool, layer, q, k, v, g, beta, *walk,
                             interpret=interpret)
