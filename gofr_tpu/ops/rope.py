"""Rotary position embeddings (RoPE): half-rotation layout
(:func:`apply_rope`) and interleaved pairs (:func:`apply_rope_interleaved`).

Table is precomputed once per max length (static under jit) and gathered by
position — decode steps index it with dynamic positions without recompute.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_table(max_len: int, head_dim: int, theta: float = 10000.0) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sin, cos) tables of shape [max_len, head_dim//2], float32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = jnp.arange(max_len, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(
    x: jnp.ndarray,  # [..., seq, heads, head_dim]
    positions: jnp.ndarray,  # [..., seq]
    sin_table: jnp.ndarray,
    cos_table: jnp.ndarray,
) -> jnp.ndarray:
    dtype = x.dtype
    sin = jnp.take(sin_table, positions, axis=0)[..., :, None, :]  # [..., seq, 1, half]
    cos = jnp.take(cos_table, positions, axis=0)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def rope_angles(
    positions: jnp.ndarray, head_dim: int, theta: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sin, cos) [..., head_dim//2] of the positions themselves: the rows
    :func:`rope_table` would hold, without a table as long as the model's
    largest position."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope_interleaved(
    x: jnp.ndarray,  # [..., seq, heads, head_dim]
    sin: jnp.ndarray,  # [..., seq, head_dim//2]
    cos: jnp.ndarray,
) -> jnp.ndarray:
    """The GPT-J layout: pair ``i`` is lanes ``(2i, 2i+1)`` and turns by
    the angle ``position * theta**(-i/half)`` that :func:`apply_rope`
    gives lanes ``(i, i+half)``."""
    dtype = x.dtype
    sin, cos = sin[..., :, None, :], cos[..., :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(dtype)
