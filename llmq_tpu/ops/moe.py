"""Routed feed-forward (DeepSeek-V3's): a float32 sigmoid router with a
selection-only correction bias, the top ``k`` of ``E`` experts a token,
and a GROUPED product that multiplies each token with its own experts
and no others.

``route`` chooses and weighs; ``routed_ffn`` sorts the (token, expert)
pairs by expert, gathers the tokens in that order and runs the three
SwiGLU matrices as grouped products over the sorted rows (one product
a group, the group being the expert's rows), then weighs and adds each
token's ``k`` results. An expert that got no token has an empty group:
nothing is multiplied for it and its matrices are not read. Rows that
must not count (a decode row that is not live, a slice's padding) are
sorted behind every group and multiplied by nothing.

The grouped product is JAX's own Pallas kernel for it on the TPU
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: ``gmm`` in a device
trace, two calls a routed layer) and ``jax.lax.ragged_dot``
elsewhere, by the policy every kernel of this repo follows
(``ops/attention._kernel_route``: ``LLMQ_PALLAS``). Both were measured on the chip at this model's
widths (PERF.md §6, PR 31): both read only the experts a batch
touches, and the kernel is the faster by a quarter at 64 rows.

``stats`` of a call: tokens each expert received and how many experts
received any, which the serving programs sum over their steps and
layers (``engine.get_stats()["moe"]``).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def route(x: jnp.ndarray, w_router: jnp.ndarray, bias: jnp.ndarray, *,
          top_k: int, scale: float, norm_topk: bool = True
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``x`` (N, D) -> (experts (N, k) int32, gates (N, k) float32).

    s = sigmoid(x W_r) in float32 at the highest matmul precision (a
    bf16 pass swaps near-tied experts); the ``k`` experts are the top
    ``k`` of ``s + bias``; the gates are the chosen ``s`` (WITHOUT the
    bias), normalised to sum 1 where ``norm_topk``, times ``scale``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(s + bias.astype(jnp.float32), top_k)
    g = jnp.take_along_axis(s, experts, axis=-1)
    if norm_topk:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), g * scale


#: megablox tiling (rows, contraction, output): the best of those
#: tried at 2,048 x 1,536 and 768 x 2,048 for 384 and 6,528 rows.
GMM_TILING = (128, 768, 2048)


def moe_grouped_matmul_pallas(xs: jnp.ndarray, w: jnp.ndarray,
                              counts: jnp.ndarray, *,
                              interpret: bool = False) -> jnp.ndarray:
    """xs (M, K) sorted by group, w (E, K, N), counts (E,) -> (M, N):
    row i times the matrix of its group. Rows behind the last group
    come out undefined."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m = xs.shape[0]
    pad = -m % GMM_TILING[0]
    out = gmm(jnp.pad(xs, ((0, pad), (0, 0))), w, counts,
              preferred_element_type=xs.dtype, tiling=GMM_TILING,
              interpret=interpret)
    return out[:m]


def _grouped(xs, w, counts):
    from llmq_tpu.ops.attention import _kernel_jit, _kernel_route
    use, interp = _kernel_route(128)
    if not use:
        return lax.ragged_dot(xs, w, counts)
    fn = _kernel_jit("moe_grouped_matmul", lambda: jax.jit(
        moe_grouped_matmul_pallas, static_argnames=("interpret",)))
    return fn(xs, w, counts, interpret=interp)


def routed_ffn(x: jnp.ndarray, experts: jnp.ndarray, gates: jnp.ndarray,
               w_gate_up: jnp.ndarray, w_down: jnp.ndarray,
               live: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """sum_i gates_i SwiGLU_{experts_i}(x) for every token of ``x``
    (N, D). ``w_gate_up`` (E, D, 2F) holds each expert's gate and up
    matrices side by side, ``w_down`` (E, F, D). ``live`` (N,) bool:
    rows that are not live are multiplied with nothing and come out 0.

    Returns (y (N, D) in ``x.dtype``, stats (E + 1,) int32: the tokens
    each expert received, then the number of experts that received
    any)."""
    N, k = experts.shape
    E, _, F2 = w_gate_up.shape
    F = F2 // 2
    flat = experts.reshape(-1)
    if live is not None:
        # Sorted behind the last group: outside every group, so no
        # product touches those rows (they are zeroed below).
        flat = jnp.where(jnp.repeat(live, k), flat, E)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    xs = x[order // k]                                     # (N*k, D)
    gu = _grouped(xs, w_gate_up, counts)
    a = (jax.nn.silu(gu[:, :F].astype(jnp.float32)).astype(x.dtype)
         * gu[:, F:])
    ys = _grouped(a, w_down, counts)                       # (N*k, D)
    w = jnp.where(flat[order] < E, gates.reshape(-1)[order], 0.0)
    ys = jnp.where(w[:, None] != 0, ys.astype(jnp.float32) * w[:, None], 0.0)
    y = jnp.zeros((N * k, x.shape[-1]), jnp.float32).at[order].set(ys)
    y = jnp.sum(y.reshape(N, k, -1), axis=1).astype(x.dtype)
    stats = jnp.concatenate(
        [counts, jnp.sum(counts > 0, dtype=jnp.int32)[None]])
    return y, stats
