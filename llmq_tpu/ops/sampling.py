"""Token sampling: greedy, temperature, top-k, top-p — all static-shape,
jit-safe, batched."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def greedy(logits: jnp.ndarray) -> jnp.ndarray:
    """(B, V) → (B,) argmax token ids."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _filter_logits(
    logits: jnp.ndarray,          # (B, V)
    temperature: jnp.ndarray | float,
    top_k: int,
    top_p: float,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared temperature / top-k / top-p filtering. Returns
    (t (B,), lf (B, V) f32, scaled (B, V) filtered logits)."""
    B, V = logits.shape
    t = jnp.broadcast_to(jnp.asarray(temperature, dtype=jnp.float32), (B,))
    lf = logits.astype(jnp.float32)
    scaled = lf / jnp.maximum(t[:, None], 1e-6)
    if top_k and top_k < V:
        kth = jnp.sort(scaled, axis=-1)[:, V - top_k][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p < 1.0:
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep tokens until cumulative prob exceeds top_p (always >= 1 token).
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff_logit = jnp.take_along_axis(
            sorted_logits, cutoff_idx[:, None], axis=-1)
        scaled = jnp.where(scaled < cutoff_logit, -jnp.inf, scaled)
    return t, lf, scaled


def sample_token(
    logits: jnp.ndarray,          # (B, V)
    key: jax.Array,
    temperature: jnp.ndarray | float = 1.0,   # scalar or (B,)
    top_k: int = 0,               # 0 = disabled (static!)
    top_p: float = 1.0,           # 1.0 = disabled
) -> jnp.ndarray:
    """Temperature / top-k / top-p sampling. ``temperature == 0`` rows fall
    back to greedy. top_k/top_p are static config (bucketed per engine),
    temperature may vary per sequence."""
    t, lf, scaled = _filter_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(t <= 0.0, greedy(lf), sampled)
