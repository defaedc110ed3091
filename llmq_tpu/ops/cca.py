"""Compressed convolutional attention's MIX (CCA, arXiv:2510.04476; the
ZAYA1 report, arXiv:2511.17127) in plain JAX: what turns a token's
compressed projections into the q, K and V of a plain grouped-query
attention. One-token step (decode rows) and slices form (prompt slices
and the prefill program), both over a TAIL a row carries from token to
token, as ``ops/ssm.conv_step`` / ``conv_slices`` are over a window.

With ``c_t = [q~_t ; k~_t]`` the ``C = (H + G) d`` channels of the two
projections (H query heads, G key heads of d), zeros before position 0::

    m^q_h = (q~_h + k~_g(h)) / 2;  m^k_g = mean of its heads' m^q_h
    a_t   = w0[:, 0] c_{t-1} + w0[:, 1] c_t + b0          # depthwise
    z_t[j] = [a_{t-1}[j] ; a_t[j]] W1[j] + b1[j]          # full, a head j
    q = z^q + m^q;  k = z^k + m^k                         # no activation
    q^ = sqrt(d) q / |q|;  k^ = tau_g sqrt(d) k / |k|     # a head
    v_t = [v1_t ; v2_{t-1}]                               # the value SHIFT

Two taps each (``cca_time0`` = ``cca_time1`` = 2), so ``z_t`` sees the
tokens t, t-1 and t-2, and the tail a row carries is what the NEXT token
needs of this one: ``[c_t | a_t | v2_t]``, ``2 C + v2's width`` values
in float32. A row at position 0 starts from a tail of zeros (each
convolution pads its own input: ``c_{-1} = a_{-1} = 0``), which the
CALLER hands in — these functions know no positions. RoPE and the
attention are not here: q^, k^ and v go on to the paged GQA ops.

Elementwise work is float32; the full convolution within a head takes
its operands in the weights' type (bfloat16 where served: one pass of
the matrix unit, accumulated in float32), like every product of the
serving programs.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax import lax

#: Under the square roots of the two L2 norms.
L2_EPS = 1e-6


def tail_width(n_heads: int, n_kv_heads: int, head_dim: int,
               shift_width: int) -> int:
    """Values of one row's tail: ``[c | a | v2]``."""
    return 2 * (n_heads + n_kv_heads) * head_dim + shift_width


def _split_tail(tail: jnp.ndarray, C: int):
    return tail[..., :C], tail[..., C:2 * C], tail[..., 2 * C:]


def _mean(c: jnp.ndarray, H: int, G: int, d: int):
    """The q-k mean of ``c`` (..., C) float32: ``(m^q (..., H, d), m^k
    (..., G, d))``."""
    lead = c.shape[:-1]
    q = c[..., :H * d].reshape(lead + (G, H // G, d))
    k = c[..., H * d:].reshape(lead + (G, 1, d))
    mq = 0.5 * (q + k)
    return mq.reshape(lead + (H, d)), jnp.mean(mq, axis=-2)


def _close(z, c, temp, H: int, G: int, d: int):
    """Convolved channels ``z`` and the raw ones ``c`` (..., C) float32
    -> ``(q^ (..., H, d), k^ (..., G, d))`` float32: the mean added, each
    head normalised to length sqrt(d), the keys tempered."""
    lead = z.shape[:-1]
    mq, mk = _mean(c, H, G, d)
    q = z[..., :H * d].reshape(lead + (H, d)) + mq
    k = z[..., H * d:].reshape(lead + (G, d)) + mk

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    root = jnp.float32(d) ** 0.5
    return (unit(q) * root,
            unit(k) * (root * temp.astype(jnp.float32)[:, None]))


def _values(v1, v2_now, v2_before):
    """``v_t = [v1_t ; v2_{t-1}]``: the first half of the K/V width
    carries the token's own value, the second the token BEFORE's
    (``v2_now`` is what the next token will be handed)."""
    del v2_now
    return jnp.concatenate([v1, v2_before], -1)


def _head_conv(a_prev, a, w1, b1, J: int, d: int):
    """``z[j] = [a_prev[j] ; a[j]] W1[j] + b1[j]``: ``a_prev``, ``a``
    (..., C) float32, ``w1`` (J, 2 d, d) — the older tap's rows first —
    ``b1`` (C,). (..., C) float32."""
    lead = a.shape[:-1]
    both = jnp.concatenate([a_prev.reshape((-1, J, d)),
                            a.reshape((-1, J, d))], axis=-1)
    # heads LEAD the product (a batched matmul as XLA's backends all
    # have it; rows leading, the CPU's refuses bf16 x bf16 = f32)
    z = jnp.einsum("jni,jio->jno",
                   jnp.moveaxis(both, 1, 0).astype(w1.dtype), w1,
                   preferred_element_type=jnp.float32)
    return (jnp.moveaxis(z, 0, 1).reshape(lead + (J * d,))
            + b1.astype(jnp.float32))


def cca_step(tail: jnp.ndarray, qk: jnp.ndarray, v1: jnp.ndarray,
             v2: jnp.ndarray, w0: jnp.ndarray, b0: jnp.ndarray,
             w1: jnp.ndarray, b1: jnp.ndarray, temp: jnp.ndarray, *,
             n_heads: int, n_kv_heads: int
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One token a row. ``tail`` (B, 2 C + W) what each row's last token
    left (zeros for a row at position 0); ``qk`` (B, C) the new token's
    ``[q~ ; k~]``, ``v1``, ``v2`` (B, W) its two value projections (W =
    half the K/V width); ``w0`` (C, 2), ``b0`` (C,), ``w1`` (H + G, 2 d,
    d), ``b1`` (C,), ``temp`` (G,). Returns ``(q^ (B, H, d), k^ (B, G,
    d), v (B, G, d), the tail moved on by one)``, float32, before RoPE."""
    H, G = n_heads, n_kv_heads
    C = qk.shape[-1]
    d = C // (H + G)
    f32 = jnp.float32
    c_prev, a_prev, v_prev = _split_tail(tail.astype(f32), C)
    c = qk.astype(f32)
    w0 = w0.astype(f32)
    a = w0[:, 0] * c_prev + w0[:, 1] * c + b0.astype(f32)
    z = _head_conv(a_prev, a, w1, b1, H + G, d)
    q, k = _close(z, c, temp, H, G, d)
    v2 = v2.astype(f32)
    v = _values(v1.astype(f32), v2, v_prev).reshape(-1, G, d)
    return q, k, v, jnp.concatenate([c, a, v2], -1).astype(tail.dtype)


def cca_slices(tail: jnp.ndarray, qk: jnp.ndarray, v1: jnp.ndarray,
               v2: jnp.ndarray, lengths: jnp.ndarray, w0: jnp.ndarray,
               b0: jnp.ndarray, w1: jnp.ndarray, b1: jnp.ndarray,
               temp: jnp.ndarray, *, n_heads: int, n_kv_heads: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                          jnp.ndarray]:
    """The same over S slices of T tokens that continue their rows'
    tails: ``tail`` (S, 2 C + W), ``qk`` (S, T, C), ``v1``, ``v2`` (S,
    T, W), ``lengths`` (S,) the valid tokens of each slice. Returns
    ``(q^ (S, T, H, d), k^ (S, T, G, d), v (S, T, G, d), the tails
    behind each slice's LAST VALID token)``: what lies past a slice's
    length never enters a tail, and the value shift crosses a slice's
    boundary exactly as the convolutions do."""
    H, G = n_heads, n_kv_heads
    S, T, C = qk.shape
    d = C // (H + G)
    f32 = jnp.float32
    c_prev, a_prev, v_prev = _split_tail(tail.astype(f32), C)

    def behind(prev, x):      # (S, T + 1, ...): index t is token t - 1
        return jnp.concatenate([prev[:, None], x], axis=1)

    c_all = behind(c_prev, qk.astype(f32))
    w0 = w0.astype(f32)
    a = w0[:, 0] * c_all[:, :T] + w0[:, 1] * c_all[:, 1:] + b0.astype(f32)
    a_all = behind(a_prev, a)
    z = _head_conv(a_all[:, :T], a, w1, b1, H + G, d)
    q, k = _close(z, c_all[:, 1:], temp, H, G, d)
    v_all = behind(v_prev, v2.astype(f32))
    v = _values(v1.astype(f32), v_all[:, 1:], v_all[:, :T]).reshape(
        S, T, G, d)
    at = lengths[:, None, None]
    new = jnp.concatenate(
        [jnp.take_along_axis(x, at, axis=1)[:, 0]
         for x in (c_all, a_all, v_all)], -1)
    return q, k, v, new.astype(tail.dtype)
