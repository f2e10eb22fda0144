"""The plain reference of the ``lfm2_moe`` decoder (LFM2-8B-A1B): the
published forward pass in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision. No kernels, no cache, no batching, no tail
carried from call to call — EVERY layer runs over EVERY position of the
sequence — and nothing imported from the program: it reads the
configuration file's keys and the benchmark's own weights
(``lfm2_moe_family.make_weights``). The int8 de-quantisation with its int4
control, the gap of a chosen token and ``pad_to`` are ``reference.py``'s.

Residual stream x [T, D]; RMSNorm(v; g) = v / sqrt(mean v^2 + norm_eps) * g:

    a = RMSNorm(x; g_op)
    conv (layer_types "conv"):  [B | C | u] = a W_in ;  v = B * u
        z_t = sum_{k=0..L-1} c_k * v_{t-L+1+k}, v = 0 before position 0 (L = conv_L_cache; no bias)
        y = (C * z) W_out
    attention ("full_attention"):  q = a W_q, k = a W_k, v = a W_v, heads of hidden / heads
        q_h <- RMSNorm(q_h; g_q), k_h <- RMSNorm(k_h; g_k), then RoPE (rotate-half, rope_theta)
        causal softmax(q k^T / sqrt(head_dim)), query heads in groups over the KV heads ;  y = o W_o
    h = x + y ;  b = RMSNorm(h; g_ffn)
    layers < num_dense_layers:  f = (SiLU(b W_1) * b W_3) W_2
    the others:  s = sigmoid(b W_r) ;  T = the num_experts_per_tok largest of s + e_bias
                 g_e = s_e / (sum_T s + 1e-6) * routed_scaling_factor   (the published code's 1e-6)
                 f = sum_e g_e (SiLU(b W_1,e) * b W_3,e) W_2,e, every expert over every position, dense
    x <- h + f ;  logits = RMSNorm(x_L; g_final) E^T   (tied)

The conv is a literal sum over the sequence and attention is unpaged,
computed a block of queries at a time; the head a block of positions at a
time, so that one block's scores and one dequantised matrix are what live
beside the int8 weights at the cell's 2,560 positions.

``weight_bits=4`` is the CONTROL: every int8 matrix (W_in, W_out, W_q,
W_k, W_v, W_o, the dense MLPs, the experts) re-quantised to int4 per
output channel — the nearest precision below the one the configuration
states. The router, its bias, the conv taps, the norms and the embedding
are as served.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference
from benchmarks.harness.reference import pad_to  # noqa: F401  (part of a reference module's contract)

_HI = jax.lax.Precision.HIGHEST


def layer_map(config: dict[str, Any]) -> list[tuple[str, int, str, int]]:
    """Layer l -> (mixer kind, its index in the kind's stack, feed-forward
    kind, its index in that stack), in published order."""
    seen = {"conv": 0, "full_attention": 0}
    dense = int(config["num_dense_layers"])
    out = []
    for l, kind in enumerate(config["layer_types"]):
        out.append((kind, seen[kind], "dense" if l < dense else "moe", l if l < dense else l - dense))
        seen[kind] += 1
    return out


def _dequant(w: Any, bits: int) -> jnp.ndarray:
    """A matrix as float32: a plain array as it is (the CPU tests'); an int8
    ``{"q", "s"}`` one by its scales, through ``bits`` levels if fewer than 8."""
    return reference._dequant(w, bits) if isinstance(w, dict) else w.astype(jnp.float32)


def _mm(x: jnp.ndarray, w: Any, bits: int) -> jnp.ndarray:
    return jnp.matmul(x, _dequant(w, bits), precision=_HI)


def _rms(x: jnp.ndarray, g: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _pick(stack: dict, i: jnp.ndarray) -> dict:
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack)


def _swiglu(b: jnp.ndarray, w: dict, bits: int) -> jnp.ndarray:
    return _mm(jax.nn.silu(_mm(b, w["w_gate"], bits)) * _mm(b, w["w_up"], bits), w["w_down"], bits)


@partial(jax.jit, static_argnums=(0, 1))
def _conv(eps: float, bits: int, stack: dict, i: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Conv layer ``i``'s mixer over x [T, D], with its residual."""
    lp = _pick(stack, i)
    T, D = x.shape
    L = lp["conv_w"].shape[0]
    bcu = _mm(_rms(x, lp["norm"], eps), lp["in_proj"], bits)
    v = bcu[:, :D] * bcu[:, 2 * D:]
    padded = jnp.concatenate([jnp.zeros((L - 1, D), jnp.float32), v])  # v = 0 before the sequence
    z = sum(lp["conv_w"][k] * padded[k:k + T] for k in range(L))
    return x + _mm(bcu[:, D:2 * D] * z, lp["out_proj"], bits)


def _rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x [T, heads, Dh]: lane i turns with lane i + Dh/2 by t theta^(-2i/Dh)."""
    T, _, Dh = x.shape
    half = Dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnums=(0, 1))
def _attention(shape: tuple, bits: int, stack: dict, i: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Attention layer ``i``'s mixer over x [T, D], with its residual."""
    H, Hkv, Dh, theta, eps = shape
    lp = _pick(stack, i)
    T = x.shape[0]
    a = _rms(x, lp["norm"], eps)
    q = _rope(_rms(_mm(a, lp["wq"], bits).reshape(T, H, Dh), lp["q_norm"], eps), theta)
    k = _rope(_rms(_mm(a, lp["wk"], bits).reshape(T, Hkv, Dh), lp["k_norm"], eps), theta)
    v = _mm(a, lp["wv"], bits).reshape(T, Hkv, Dh)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    pos = jnp.arange(T)

    def attend(rows: tuple) -> jnp.ndarray:
        """A block of queries over every key: the scores of a long
        sequence a block of rows at a time, so that they fit."""
        qb, qpos = rows
        scores = jnp.einsum("thd,shd->hts", qb, k, precision=_HI) / np.sqrt(Dh)
        probs = jax.nn.softmax(jnp.where((pos[None, :] <= qpos[:, None])[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v, precision=_HI)

    block = next((b for b in (512, 128) if T % b == 0), T)
    o = jax.lax.map(attend, (q.reshape(T // block, block, H, Dh), pos.reshape(T // block, block)))
    return x + _mm(o.reshape(T, H * Dh), lp["wo"], bits)


@partial(jax.jit, static_argnums=(0, 1))
def _dense(eps: float, bits: int, stack: dict, i: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    lp = _pick(stack, i)
    return h + _swiglu(_rms(h, lp["norm"], eps), lp, bits)


@partial(jax.jit, static_argnums=(0, 1))
def _experts(shape: tuple, bits: int, stack: dict, i: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """Expert layer ``i`` over h [T, D], with its residual: every expert
    over every position, weighted by its gate (zero off the chosen ones),
    one expert at a time."""
    top_k, scale, eps = shape
    lp = _pick(stack, i)
    b = _rms(h, lp["norm"], eps)
    s = jax.nn.sigmoid(jnp.matmul(b, lp["w_router"].astype(jnp.float32), precision=_HI))  # [T, E]
    _, chosen = jax.lax.top_k(s + lp["expert_bias"], top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    g = jnp.einsum("tke,tk->te", jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32),
                   picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6) * scale)

    def one(acc: jnp.ndarray, xs: tuple) -> tuple:
        w, gate = xs
        return acc + gate[:, None] * _swiglu(b, w, bits), None

    f, _ = jax.lax.scan(one, jnp.zeros_like(h), (lp["experts"], g.T))
    return h + f


def hidden(config: dict[str, Any], weights: dict, token_ids: np.ndarray, weight_bits: int = 8) -> jnp.ndarray:
    """The final-normed state [T, D] at every position of one padded
    sequence [T]. Causality makes right padding harmless to the positions
    before it."""
    c = config
    eps = float(c["norm_eps"])
    D, H = int(c["hidden_size"]), int(c["num_attention_heads"])
    attn_shape = (H, int(c["num_key_value_heads"]), int(c.get("head_dim") or D // H), float(c["rope_theta"]), eps)
    moe_shape = (int(c["num_experts_per_tok"]), float(c.get("routed_scaling_factor", 1.0)), eps)
    x = weights["embedding"][jnp.asarray(token_ids)].astype(jnp.float32)
    for kind, i, ffn, m in layer_map(c):
        if kind == "conv":
            x = _conv(eps, weight_bits, weights["conv"], jnp.int32(i), x)
        else:
            x = _attention(attn_shape, weight_bits, weights["attn"], jnp.int32(i), x)
        if ffn == "dense":
            x = _dense(eps, weight_bits, weights["dense"], jnp.int32(m), x)
        else:
            x = _experts(moe_shape, weight_bits, weights["moe"], jnp.int32(m), x)
    return _rms(x, weights["final_norm"], eps)


@jax.jit
def _head(embedding: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(x, embedding.astype(jnp.float32).T, precision=_HI)


def logits(config: dict[str, Any], weights: dict, token_ids: np.ndarray, weight_bits: int = 8) -> jnp.ndarray:
    """Logits [T, V] at every position (the tests'; ``served_gaps`` takes
    the head a block of positions at a time)."""
    return _head(weights["embedding"], hidden(config, weights, token_ids, weight_bits))


_HEAD_ROWS = 256  # positions the head is computed for at once: [256, vocabulary] float32


def served_gaps(config: dict[str, Any], weights: dict, prompt: list[int],
                served: list[int], pad_len: int = 0,
                control_bits: int | None = None) -> dict[str, np.ndarray]:
    """Run the reference once over prompt + served tokens. ``served`` is
    the gap of every served token under the reference (how far its
    reference logit lies below the reference's best); with
    ``control_bits`` also ``control``: at the same positions, the gap of
    the token that the lower precision puts first."""
    ids = list(prompt) + list(served)
    n_p, n_s = len(prompt), len(served)
    T = max(int(pad_len), len(ids))
    padded = np.zeros(T, np.int32)
    padded[: len(ids)] = ids
    # position p-1 predicts the first served token, and so on
    rows = slice(n_p - 1, n_p - 1 + n_s)
    ref = hidden(config, weights, padded)[rows]
    low = hidden(config, weights, padded, weight_bits=control_bits)[rows] if control_bits is not None else None
    chosen = jnp.asarray(np.asarray(served, np.int32))
    out: dict[str, list] = {"served": [], "control": []}
    for a in range(0, n_s, _HEAD_ROWS):
        b = min(a + _HEAD_ROWS, n_s)
        ref_logits = _head(weights["embedding"], ref[a:b])
        out["served"].append(np.asarray(reference._gaps(ref_logits, chosen[a:b])))
        if low is not None:
            first = jnp.argmax(_head(weights["embedding"], low[a:b]), axis=-1).astype(jnp.int32)
            out["control"].append(np.asarray(reference._gaps(ref_logits, first)))
    return {k: np.concatenate(v) for k, v in out.items() if v}
