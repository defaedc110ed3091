"""Rotary position embeddings (RoPE), Llama-3 convention.

Llama-3 uses theta=500000 and rotates half-dimensions as (x1, x2) pairs
split at head_dim/2 (the "GPT-NeoX" layout used by Meta's checkpoints
after their permutation is undone — equivalent under a fixed basis
change; we standardise on the split-half layout everywhere, including
checkpoint import)."""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax.numpy as jnp


def rope_cos_sin(positions: jnp.ndarray, head_dim: int,
                 theta: float = 500000.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for given positions.

    positions: (..., T) int32 → returns cos, sin of shape (..., T, head_dim//2),
    computed in f32.
    """
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate q or k.

    x: (..., T, H, D); cos/sin: (..., T, D//2) broadcast over the head axis.
    """
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    c = cos[..., None, :]  # (..., T, 1, half) → broadcast across heads
    s = sin[..., None, :]
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def yarn_inv_freq(head_dim: int, theta: float, *, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> jnp.ndarray:
    """YaRN's frequency of each of the ``head_dim // 2`` rotary pairs
    (Peng et al., arXiv 2309.00071, the "NTK-by-parts" interpolation as
    Hugging Face's ``_compute_yarn_parameters`` has it): pair ``i``'s
    plain frequency ``f_i = theta^(-2i / head_dim)`` divided by
    ``factor`` where the pair turns fewer than ``beta_slow`` times in
    ``original_max_position`` positions, kept where it turns more than
    ``beta_fast`` times, and blended linearly between those two pairs::

        pair(b) = head_dim ln(original / (2 pi b)) / (2 ln theta)
        lo, hi  = floor(pair(beta_fast)), ceil(pair(beta_slow)), in [0, half-1]
        gamma_i = clip((i - lo) / (hi - lo), 0, 1)
        f'_i    = f_i / factor * gamma_i + f_i * (1 - gamma_i)

    A model whose layers differ in kind may rotate each kind by a table
    of its own (``models/mellum.py``: this one on its full layers).
    """
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))

    def pair(turns: float) -> float:
        return (head_dim * math.log(original_max_position
                                    / (turns * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    lo = max(math.floor(pair(beta_fast)), 0)
    hi = min(math.ceil(pair(beta_slow)), half - 1)
    gamma = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo)
                     / max(hi - lo, 1e-3), 0.0, 1.0)
    return freqs / factor * gamma + freqs * (1.0 - gamma)


def rope_cos_sin_scaled(positions: jnp.ndarray, inv_freq: jnp.ndarray,
                        attention_factor: float = 1.0
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`rope_cos_sin` over a frequency table of the caller's
    (``yarn_inv_freq``), cos and sin multiplied by ``attention_factor``
    (YaRN scales q and k alike, so the scores by its square)."""
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return (jnp.cos(angles) * attention_factor,
            jnp.sin(angles) * attention_factor)


@dataclass(frozen=True)
class YarnScaling:
    """A ``rope_scaling`` block of ``type: yarn`` in DeepSeek-V3's
    reading (``modeling_deepseek_v3.py``): the rotary pairs turn by
    :func:`yarn_inv_freq`, cos and sin are multiplied by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)`` (1 where the two keys are equal), and the softmax
    scale by ``yarn_mscale(factor, mscale_all_dim) ** 2``
    (``models/latent.LatentDims.softmax_scale``)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def attention_factor(self) -> float:
        return (yarn_mscale(self.factor, self.mscale)
                / yarn_mscale(self.factor, self.mscale_all_dim))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's temperature: ``0.1 mscale ln(factor) + 1`` (1.41589 at
    factor 64), 1 without scaling."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0
