"""Manifold-constrained hyper-connections (mHC, arXiv 2512.24880): a
residual of ``n`` STREAMS a token, mixed at every sub-layer.

A token's residual is ``X`` (n, C), float32. A SITE wraps one sub-layer
``F`` (an attention or a feed-forward, with its own input RMSNorm) and
has two halves, both a row's own (``ops/rows.live_rows`` can run them)::

    x~          = vec(X) / rms(vec(X))          # all n C values, no weight
    [p | q | r] = x~ Phi                        # Phi (n C, n + n + n n) f32
    H_pre       = sigmoid(a_pre p + b_pre)                      (n,)
    H_post      = 2 sigmoid(a_post q + b_post)                  (n,)
    A           = clip(a_res mat(r) + b_res, lo, hi)            (n, n)
    M_0 = exp(A);  M_t = cols(rows(M_{t-1}))    # ``iters`` times
    rows(M) = M / (M 1 + eps)     cols(M) = M / (1^T M + eps)
    H_res       = M_iters                       # doubly stochastic
    u           = H_pre X                       # READ: F's input (C,)
    X'          = H_res X + H_post^T F(u)       # WRITE: stream i gains
                                                # H_post[i] F(u)

:func:`project` makes the three H of a site (scope ``hc_project``),
:func:`read` and :func:`write` apply them (``hc_apply``); all under
``hc_mix``. Everything is float32: the streams ARE the model's state
between layers, and H_res multiplies them at every site. The ``n x n``
matrices of a batch of rows are kept ``(n, n, rows)``, the rows in the
lanes: a ``(rows, 4, 4)`` array pads each token's sixteen values to a
whole (8, 128) tile.

A site's parameters (``site_shapes``): ``phi`` (n C, n + n + n n),
``alpha`` (3,) = (a_pre, a_post, a_res), ``bias`` (n + n + n n,) =
[b_pre | b_post | vec(b_res)]; a model stacks them over (layer, site).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from llmq_tpu.utils.profiling import scope

#: The sites of a layer, in order.
ATTN, FFN = 0, 1


def site_shapes(n: int, width: int) -> Dict[str, tuple]:
    """Leaf name -> shape of ONE site's parameters (all float32)."""
    m = n + n + n * n
    return {"phi": (n * width, m), "alpha": (3,), "bias": (m,)}


def site_param_count(n: int, width: int) -> int:
    return sum(math.prod(shape) for shape in site_shapes(n, width).values())


def sinkhorn(a: jnp.ndarray, iters: int, eps: float) -> jnp.ndarray:
    """``a`` (n, n, ...) float32, already clipped -> the projection of
    exp(a) towards the doubly stochastic matrices: ``iters`` times the
    rows normalised (axis 1 sums to 1), then the columns (axis 0);
    ``eps`` joins each sum. The steps are unrolled: forty small
    dependent operations XLA fuses, where a ``while`` would pay its
    trip overhead twenty times."""
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def project(x: jnp.ndarray, phi, alpha, bias, *, iters: int, eps: float,
            clamp: Tuple[float, float], norm_eps: float):
    """The three H of one site for the rows ``x`` (R, n, C) float32:
    ``(h_pre (R, n), h_post (R, n), h_res (n, n, R))``; ``h_res[i, j]``
    is what stream i takes of stream j."""
    R, n, C = x.shape
    with scope("hc_mix"), scope("hc_project"):
        flat = x.reshape(R, n * C)
        flat = flat * lax.rsqrt(
            jnp.mean(flat * flat, axis=-1, keepdims=True) + norm_eps)
        pqr = jnp.dot(flat, phi, precision=lax.Precision.HIGHEST)
        h_pre = jax.nn.sigmoid(alpha[0] * pqr[:, :n] + bias[:n])
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[:, n:2 * n]
                                      + bias[n:2 * n])
        a = jnp.clip(alpha[2] * pqr[:, 2 * n:] + bias[2 * n:], *clamp)
        h_res = sinkhorn(a.T.reshape(n, n, R), iters, eps)
        return h_pre, h_post, h_res


def read(x: jnp.ndarray, h_pre: jnp.ndarray) -> jnp.ndarray:
    """u = H_pre X: the sub-layer's input (R, C) of the streams
    ``x`` (R, n, C)."""
    with scope("hc_mix"), scope("hc_apply"):
        return jnp.sum(h_pre[:, :, None] * x, axis=1)


def write(x: jnp.ndarray, h_res: jnp.ndarray, h_post: jnp.ndarray,
          y: jnp.ndarray) -> jnp.ndarray:
    """X' = H_res X + H_post^T y for the sub-layer's output ``y``
    (R, C): the streams mix and each gains its share of ``y``. The mix
    is n n multiply-adds a value on the vector unit, no matrix
    product: n is 4."""
    n = x.shape[1]
    with scope("hc_mix"), scope("hc_apply"):
        y = y.astype(jnp.float32)
        return jnp.stack(
            [sum(h_res[i, j][:, None] * x[:, j] for j in range(n))
             + h_post[:, i, None] * y for i in range(n)], axis=1)


def row_sum_error(h_res: jnp.ndarray) -> jnp.ndarray:
    """Each row's worst ``|row sum - 1|`` of ``h_res`` (n, n, R): (R,)
    float32. The last Sinkhorn step normalises the columns, so what is
    left of the projection's error shows in the rows (a serving step
    counts the worst of them: a site that stops being doubly stochastic
    is a wrong model that still emits tokens)."""
    with scope("hc_mix"), scope("hc_project"):
        return jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0), axis=0)
