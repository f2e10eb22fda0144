"""The plain reference: the published forward pass of the decoder family
(RMSNorm, RoPE in half-rotation layout, causal grouped/multi-head
attention, SwiGLU, untied head) in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision. No kernels, no cache, no
batching, and nothing imported from the program: it reads the
configuration file's published keys and the benchmark's own weights.

It runs layer by layer and one sequence at a time, so that at 7B widths
one dequantised layer (under 1 GB in f32) and one sequence's activations
are all that live beside the int8 weights.

``weight_bits=4`` is the CONTROL: the same pass with every int8 matrix
re-quantised to int4 per output channel — the nearest precision below the
one the configurations state, the step that would tempt a later PR.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import costs

_HI = jax.lax.Precision.HIGHEST


def _dequant(w: dict, bits: int) -> jnp.ndarray:
    full = w["q"].astype(jnp.float32) * w["s"][..., None, :]
    if bits == 8:
        return full
    levels = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(full), axis=-2, keepdims=True)
    scale = jnp.maximum(amax / levels, 1e-30)
    return jnp.clip(jnp.round(full / scale), -levels, levels) * scale


def _rms(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x [T, heads, Dh]; rotate the two halves of each head by position."""
    T, _, Dh = x.shape
    half = Dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnums=(0, 1))
def _layer(shape: tuple, bits: int, layers: dict, i: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Decoder layer ``i`` over one sequence x [T, D], float32. The layer
    is picked inside the program, so that all layers share one compile."""
    H, Hkv, Dh, theta, eps = shape
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), layers)
    T = x.shape[0]
    h = _rms(x, lp["attn_norm"], eps)
    q = jnp.matmul(h, _dequant(lp["wq"], bits), precision=_HI).reshape(T, H, Dh)
    k = jnp.matmul(h, _dequant(lp["wk"], bits), precision=_HI).reshape(T, Hkv, Dh)
    v = jnp.matmul(h, _dequant(lp["wv"], bits), precision=_HI).reshape(T, Hkv, Dh)
    q, k = _rope(q, theta), _rope(k, theta)
    group = H // Hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k, precision=_HI) / np.sqrt(Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hts,shd->thd", probs, v, precision=_HI).reshape(T, H * Dh)
    x = x + jnp.matmul(attn, _dequant(lp["wo"], bits), precision=_HI)
    h = _rms(x, lp["mlp_norm"], eps)
    gate = jax.nn.silu(jnp.matmul(h, _dequant(lp["w_gate"], bits), precision=_HI))
    up = jnp.matmul(h, _dequant(lp["w_up"], bits), precision=_HI)
    return x + jnp.matmul(gate * up, _dequant(lp["w_down"], bits), precision=_HI)


@partial(jax.jit, static_argnums=(0, 1))
def _head(eps: float, bits: int, final_norm: jnp.ndarray, head: dict, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(_rms(x, final_norm, eps), _dequant(head, bits), precision=_HI)


def logits(config: dict[str, Any], weights: dict, token_ids: np.ndarray,
           weight_bits: int = 8) -> jnp.ndarray:
    """Logits [T, V] at every position of one padded sequence [T]. The
    causal mask makes right padding harmless to the positions before it."""
    shape = (int(config["num_attention_heads"]), int(config["num_key_value_heads"]),
             costs.head_dim(config), float(config["rope_theta"]), float(config["rms_norm_eps"]))
    x = weights["embedding"][jnp.asarray(token_ids)].astype(jnp.float32)
    layers = weights["layers"]
    for i in range(int(config["num_hidden_layers"])):
        x = _layer(shape, weight_bits, layers, jnp.int32(i), x)
    return _head(float(config["rms_norm_eps"]), weight_bits, weights["final_norm"],
                 weights["lm_head"], x)


@jax.jit
def _gaps(ref_logits: jnp.ndarray, chosen: jnp.ndarray) -> jnp.ndarray:
    """How far each chosen token's reference logit lies below the best."""
    picked = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(ref_logits, axis=-1) - picked


def pad_to(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def served_gaps(config: dict[str, Any], weights: dict, prompt: list[int],
                served: list[int], pad_len: int = 0,
                control_bits: int | None = None) -> dict[str, np.ndarray]:
    """Run the reference once over prompt + served tokens. ``served`` is
    the gap of every served token under the reference; with
    ``control_bits`` also ``control``: at the same positions, the gap of
    the token that the lower precision puts first."""
    ids = list(prompt) + list(served)
    n_p, n_s = len(prompt), len(served)
    T = max(int(pad_len), len(ids))
    padded = np.zeros(T, np.int32)
    padded[: len(ids)] = ids
    ref = logits(config, weights, padded)
    # position p-1 predicts the first served token, and so on; the chosen
    # tokens are laid out at full length so that one program serves all
    chosen = np.zeros(T, np.int32)
    chosen[n_p - 1: n_p - 1 + n_s] = served
    rows = slice(n_p - 1, n_p - 1 + n_s)
    out = {"served": np.asarray(_gaps(ref, jnp.asarray(chosen)))[rows]}
    if control_bits is not None:
        low = logits(config, weights, padded, weight_bits=control_bits)
        out["control"] = np.asarray(_gaps(ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))[rows]
    return out
