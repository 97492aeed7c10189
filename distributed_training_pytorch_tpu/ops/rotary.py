"""Rotary position embedding (RoPE, arXiv:2104.09864), interleaved form.

``rotate(x, positions, theta)`` turns each pair ``(x_{2i}, x_{2i+1})`` of the
last axis (``D`` wide, even) at position ``p`` by the angle
``p · theta^{-2i/D}``::

    x'_{2i}   = x_{2i} cos a − x_{2i+1} sin a
    x'_{2i+1} = x_{2i} sin a + x_{2i+1} cos a

The pairs stay where they are. (The ``transformers`` DeepSeek-V3 code with
``rope_interleave`` rotates the same pairs and writes the evens before the
odds; queries and keys are permuted alike, so every ``q · k`` is the same.)
Angles, their sines and cosines and the rotation are float32; the result
comes back in ``x``'s dtype. No scaling of the frequencies (``rope_scaling``
null)."""

from __future__ import annotations

import jax.numpy as jnp


def angles(positions, width: int, theta: float):
    """``[T, width / 2]`` float32 angles ``p · theta^{-2i/width}``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    return positions.astype(jnp.float32)[:, None] * inv_freq[None, :]


def rotate(x, positions, theta: float):
    """``x``: ``[B, T, D]`` or ``[B, T, H, D]``; ``positions``: ``[T]``."""
    width = x.shape[-1]
    a = angles(positions, width, theta)  # [T, D/2]
    if x.ndim == 4:
        a = a[:, None, :]
    cos, sin = jnp.cos(a), jnp.sin(a)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (width // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
