"""Attention ops: batched multi-head/GQA attention for prefill and decode.

Layout convention everywhere: ``[batch, seq, heads, head_dim]`` — batch and
heads map cleanly onto MXU-tiled matmuls via einsum; XLA fuses the softmax
chain. Float32 softmax accumulation over bf16 inputs.

The Pallas flash-attention kernel (ops/flash_attention.py) replaces the
prefill path for long sequences; this module is the reference/fallback and
the decode path (single-token query against a dense KV cache — an
MXU-friendly [B,H,1,S] matmul where flash tiling buys nothing).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

NEG_INF = -1e30


def gqa_repeat(kv: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """[B, S, n_kv, D] -> [B, S, n_heads, D] by head-group broadcast."""
    n_kv = kv.shape[2]
    if n_kv == n_heads:
        return kv
    reps = n_heads // n_kv
    return jnp.repeat(kv, reps, axis=2)


def attention(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, Hkv, D]
    v: jnp.ndarray,  # [B, Sk, Hkv, D]
    *,
    causal: bool = True,
    q_offset: jnp.ndarray | int = 0,
    kv_len: jnp.ndarray | None = None,  # [B] valid KV length per row
    scale: float | None = None,
    window: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """Dense attention, GQA-native. Queries are grouped as
    ``[B, Sq, Hkv, G, D]`` and contracted against the *unexpanded* KV —
    never ``jnp.repeat`` the cache: at decode batch sizes the materialized
    [B, S, H, D] copies would double-to-quadruple HBM traffic in the hot
    path (the step is bandwidth-bound). ``q_offset`` is the absolute
    position of q[0] (for chunked prefill); ``kv_len`` masks right-padded
    KV. ``window`` (causal only; a traced scalar is fine) keeps the keys
    ``q_pos - window < k_pos <= q_pos``."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, Hkv, G, D)

    # [B, Hkv, G, Sq, Sk] f32
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale

    mask = None
    if causal:
        off = jnp.asarray(q_offset)
        if off.ndim == 0:
            q_pos = jnp.arange(Sq)[:, None] + off  # [Sq, 1]
            k_pos = jnp.arange(Sk)[None, :]
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            mask = mask[None, None, None, :, :]
        else:
            # per-ROW offsets (chunk verify over a shared cache): row b's
            # query i sits at absolute position off[b] + i
            q_pos = off[:, None] + jnp.arange(Sq)[None, :]  # [B, Sq]
            k_pos = jnp.arange(Sk)[None, None, :]
            mask = k_pos <= q_pos[:, :, None]
            if window is not None:
                mask &= k_pos > q_pos[:, :, None] - window
            mask = mask[:, None, None, :, :]  # [B, 1, 1, Sq, Sk]
    if kv_len is not None:
        valid = jnp.arange(Sk)[None, :] < kv_len[:, None]  # [B, Sk]
        valid = valid[:, None, None, None, :]
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)

    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / (jnp.sum(probs, axis=-1, keepdims=True) + 1e-30)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D)


def decode_attention(
    q: jnp.ndarray,  # [B, 1, H, D] — one new token per row
    k_cache: jnp.ndarray,  # [B, S_max, Hkv, D]
    v_cache: jnp.ndarray,  # [B, S_max, Hkv, D]
    cache_len: jnp.ndarray,  # [B] — valid entries (including the new token)
    *,
    scale: float | None = None,
) -> jnp.ndarray:
    """Single-step decode against a dense KV cache with per-row lengths."""
    return attention(
        q, k_cache, v_cache, causal=False, kv_len=cache_len, scale=scale
    )
