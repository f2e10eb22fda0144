"""Normalization ops. Accumulate in float32, cast back — the TPU-correct
pattern for bf16 activations (guide: keep VPU elementwise in f32 where
precision matters, MXU inputs in bf16)."""

from __future__ import annotations

import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    return (normed * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(
    x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray | None, eps: float = 1e-12
) -> jnp.ndarray:
    """Mean-subtracting LayerNorm; ``bias=None`` is the weight-only form."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mean) * jnp.reciprocal(jnp.sqrt(var + eps))
    out = normed * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)
