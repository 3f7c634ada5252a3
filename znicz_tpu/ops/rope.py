"""Rotary position embedding: plain frequencies, or YaRN-scaled ones.

Pairs are split by halves: ``(a[..., i], a[..., i + half])`` turn together
(the layout that keeps both operands of the rotation contiguous on the
TPU's lanes; a checkpoint whose pairs interleave maps onto it by a column
permutation of the projection that produces ``a``).
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def plain_inv_freq(dim: int, base: float) -> jnp.ndarray:
    """[dim / 2] float32 frequencies ``base ** (-2i / dim)``: rotary
    positions over a whole head of ``dim``, with no scaling."""
    return 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)


def yarn_inv_freq(
    dim: int, base: float, *, factor: float, original_max: int,
    beta_fast: float, beta_slow: float,
) -> jnp.ndarray:
    """[dim / 2] float32 frequencies: the plain ``base ** (-2i / dim)``
    for pairs that turn more than ``beta_fast`` times within the original
    context, those divided by ``factor`` for pairs that turn fewer than
    ``beta_slow`` times, and a linear ramp between (Peng et al. 2023, as
    the public DeepSeek-V3 inference code computes them)."""

    def correction_dim(rotations):
        return (
            dim * math.log(original_max / (rotations * 2 * math.pi))
            / (2 * math.log(base))
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freqs = plain_inv_freq(dim, base)
    keep = 1 - jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
    )
    return freqs / factor * (1 - keep) + freqs * keep


def yarn_attention_factor(factor: float, mscale: float = 1.0) -> float:
    """``0.1 * mscale * ln(factor) + 1``: what the softmax scale is
    multiplied by, squared, when the context is stretched by ``factor``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rotary(a: jnp.ndarray, positions: jnp.ndarray, inv_freq) -> jnp.ndarray:
    """Rotate the trailing axis of ``a`` [..., n, dim] or [..., dim] at
    ``positions`` (the shape of ``a``'s leading axes), in float32."""
    half = a.shape[-1] // 2
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    while angle.ndim < a.ndim:
        angle = angle[..., None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a = a.astype(jnp.float32)
    lo, hi = a[..., :half], a[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], axis=-1)
