"""Rotary position embeddings (RoPE): half-rotation layout
(:func:`apply_rope`) and interleaved pairs (:func:`apply_rope_interleaved`).

Table is precomputed once per max length (static under jit) and gathered by
position — decode steps index it with dynamic positions without recompute.
"""

from __future__ import annotations

import math

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


def apply_rope_halves(
    x: jnp.ndarray,  # [..., seq, heads, head_dim]
    sin: jnp.ndarray,  # [..., seq, head_dim//2]
    cos: jnp.ndarray,
) -> jnp.ndarray:
    """:func:`apply_rope`'s layout (lane ``i`` pairs with ``i + half``)
    from the angles themselves, as :func:`apply_rope_interleaved` takes
    them."""
    dtype = x.dtype
    sin, cos = sin[..., :, None, :], cos[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def yarn_frequencies(
    head_dim: int, theta: float, factor: float, original_max: int,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
) -> jnp.ndarray:
    """YaRN's blend of the rotary frequencies [head_dim//2], float32: with
    ``f_i = theta**(-2i/head_dim)``, pairs that turn more than ``beta_fast``
    times in ``original_max`` positions keep ``f_i``, those that turn fewer
    than ``beta_slow`` times get ``f_i / factor``, and a linear ramp over
    the pair index blends the ones between."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))

    def pair_turning(rotations: float) -> float:
        return head_dim * math.log(original_max / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    return freqs / factor * (1.0 - keep) + freqs * keep


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: the softmax scale is multiplied by its
    square."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def angles(positions: jnp.ndarray, freqs: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(sin, cos) [..., len(freqs)] of the positions under given
    frequencies (:func:`rope_angles` with the frequencies handed in)."""
    a = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(a), jnp.cos(a)
