"""Ring attention: sequence-parallel causal attention over an "sp" mesh axis.

Long-context scope (task mandate; no reference counterpart — SURVEY.md §5
"Long-context / sequence parallelism: Absent"): shard the SEQUENCE dim
over devices; each device holds a local Q/K/V chunk, computes partial
attention against the chunk it currently holds, and rotates K/V around the
ring with ``lax.ppermute`` over ICI, accumulating with the online-softmax
(flash) recurrence. Peak memory per device is O(T/n) while computing exact
full-sequence attention — the blockwise/RingAttention construction.

Usage: wrap with ``shard_map`` over a mesh with an "sp" axis (see
``ring_attention_sharded``); inside, shapes are per-device chunks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _chunk_attention(q, k, v, q_offset, k_offset, causal):
    """Partial (unnormalised) attention of local q against one k/v chunk.
    Returns (chunk_max (B,H,Tq), exp-sum (B,H,Tq), acc (B,Tq,H,D))."""
    B, Tq, H, D = q.shape
    n_rep = H // k.shape[2]
    k = jnp.repeat(k, n_rep, axis=-2)
    v = jnp.repeat(v, n_rep, axis=-2)
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (D ** -0.5)
    if causal:
        q_pos = q_offset + jnp.arange(Tq)[:, None]
        k_pos = k_offset + jnp.arange(k.shape[1])[None, :]
        logits = jnp.where((k_pos <= q_pos)[None, None], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                       # (B,H,Tq)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)        # fully-masked rows
    l = jnp.sum(p, axis=-1)                            # (B,H,Tq)
    acc = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return m, l, acc


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str = "sp", causal: bool = True) -> jnp.ndarray:
    """Per-device body (call inside shard_map).

    q: (B, T_local, H, D); k/v: (B, T_local, H_kv, D) — the local sequence
    chunk of each. Returns (B, T_local, H, D) exact attention output over
    the GLOBAL sequence.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    q_offset = my * Tq

    m0 = jnp.full((B, H, Tq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    acc0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        m, l, acc, k_cur, v_cur = carry
        src = (my - i) % n                 # owner of the chunk we hold now
        cm, cl, cacc = _chunk_attention(q, k_cur, v_cur, q_offset,
                                        src * Tk, causal)
        new_m = jnp.maximum(m, cm)
        corr_old = jnp.exp(m - new_m)
        corr_new = jnp.exp(cm - new_m)
        l = l * corr_old + cl * corr_new
        acc = (acc * corr_old.transpose(0, 2, 1)[..., None]
               + cacc * corr_new.transpose(0, 2, 1)[..., None])
        # Rotate K/V one step around the ring (ICI neighbour exchange).
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return new_m, l, acc, k_next, v_next

    m, l, acc, _, _ = lax.fori_loop(0, n, body, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(mesh: Mesh, q, k, v, causal: bool = True,
                           axis_name: str = "sp") -> jnp.ndarray:
    """Convenience wrapper: global (B, T, H, D) arrays in, sequence dim
    sharded over ``axis_name``, exact attention out with the same
    sharding."""
    spec = P(None, axis_name, None, None)

    fn = jax.jit(
        jax.shard_map(
            partial(ring_attention, axis_name=axis_name, causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        ))
    q = jax.device_put(q, NamedSharding(mesh, spec))
    k = jax.device_put(k, NamedSharding(mesh, spec))
    v = jax.device_put(v, NamedSharding(mesh, spec))
    return fn(q, k, v)
