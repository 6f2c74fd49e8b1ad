"""Rotary position embeddings (RoPE), half-rotation convention, with YaRN.

A layer's RoPE is its inverse frequencies ``(head_dim/2,)`` and a factor
that multiplies both cos and sin (1 for default RoPE).  ``yarn_freqs``
gives YaRN's (arXiv:2309.00071, as the published ``transformers`` code
computes it): each frequency is blended between the interpolated
``1 / (factor * base^(2i/d))`` and the extrapolated ``1 / base^(2i/d)`` by
a linear ramp over the dimensions whose wavelengths lie between
``beta_fast`` and ``beta_slow`` rotations of the original context, and
the attention factor scales cos and sin alike, so q and k both carry it.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)  # (head_dim/2,)


def yarn_attention_factor(factor: float) -> float:
    """YaRN's default attention factor, ``0.1 ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, factor: float,
               original_max_position: int, beta_fast: float = 32.0,
               beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's inverse frequencies ``(head_dim/2,)`` in float32."""
    def correction_dim(rotations):
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extrapolation = 1 - ramp  # 1 where a dimension keeps its frequency
    inv = (1.0 / (factor * pos)) * (1 - extrapolation) \
        + (1.0 / pos) * extrapolation
    return inv.astype(np.float32)


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
    *, freqs=None, scale=None,
) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S).

    ``freqs`` (D/2,) and ``scale`` (a scalar multiplying cos and sin) are
    a layer's RoPE, traced where layers differ; without them, default
    RoPE at ``theta``."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]  # (..., S, 1, D/2) — broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scale is not None:
        sin, cos = sin * scale, cos * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
