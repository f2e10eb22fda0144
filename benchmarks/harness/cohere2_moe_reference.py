"""The plain reference of the ``cohere2_moe`` decoder: the published
forward pass in straightforward ``jax.numpy`` and float32 at ``highest``
matmul precision. No kernels, no cache, no batching, and nothing imported
from the program: it reads the configuration file's keys and the
benchmark's own weights (``cohere2_moe_family.make_weights``). The int8
de-quantisation with its int4 control, the gap of a chosen token and
``pad_to`` are ``reference.py``'s, the dense decoders' plain reference.

One layer, ``use_parallel_block`` (the text tower; ``config`` is trusted
over ``described_as``):

    h      = LN(x)                  mean-subtracting, weight only, eps layer_norm_eps
    a      = W_o · Attn(W_q h, W_k h, W_v h)        causal, grouped queries, 1/sqrt(head_dim)
             sliding_attention: interleaved-pair RoPE (rope_gptj) on q and k,
                                key j seen from i iff i - sliding_window < j <= i
             full_attention:    no positional embedding, every earlier key
    s      = sigmoid(W_r h), float32, over every PUBLISHED expert
    T      = the num_experts_per_tok largest;  g_e = s_e / sum_T s   (norm_topk_prob)
    routed = sum_{e in T, e held} g_e · W_down,e (silu(W_gate,e h) * W_up,e h)
    shared = mean over the shared experts of the same form  ("average")
    out    = x + a + routed + shared ;   logits = logit_scale · LN_f(x_L) · E^T

The share (section 4 of the model-configs guide): the file's
``num_experts`` are the routed experts HELD, from ``deployment.first_expert``
on, of ``published.num_experts``; the router keeps its published width; what
the absent experts would add is left out, and that partial result goes on
to the next layer. ``vocab_size`` rows of the tied embedding are held: the
logits are over the slice. With nothing reduced this is the whole model.

It runs layer by layer, one sequence at a time, one expert at a time (a
scan) and, in attention, a block of up to 512 queries at a time, so that
one dequantised matrix (64 MB in f32 at published widths) and one block's
scores are all that live beside the int8 weights, at 6,000 positions too.

``weight_bits=4`` is the CONTROL: every int8 matrix (attention, routed and
shared experts) re-quantised to int4 per output channel — the nearest
precision below the one the configuration states. The router (float32)
and the tied embedding (bfloat16) are as served.
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


def _dequant(w: Any, bits: int) -> jnp.ndarray:
    """A matrix as float32: a plain array as it is (the CPU tests'); an int8
    ``{"q", "s"}`` one by its scales, through ``bits`` levels if fewer than 8."""
    return reference._dequant(w, bits) if isinstance(w, dict) else w.astype(jnp.float32)


def _ln(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w


def _rope_pairs(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x [T, heads, Dh]; pair i = lanes (2i, 2i+1) turns by t · theta^(-2i/Dh)."""
    T, _, Dh = x.shape
    half = Dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _ffn_sum(h: jnp.ndarray, stack: dict, weight: jnp.ndarray, bits: int) -> jnp.ndarray:
    """sum_e weight[e, t] · W_down,e (silu(W_gate,e h) * W_up,e h), one
    expert of the stack at a time."""

    def one(acc: jnp.ndarray, xs: tuple) -> tuple:
        w, g = xs
        gate = jax.nn.silu(jnp.matmul(h, _dequant(w["w_gate"], bits), precision=_HI))
        up = jnp.matmul(h, _dequant(w["w_up"], bits), precision=_HI)
        return acc + g[:, None] * jnp.matmul(gate * up, _dequant(w["w_down"], bits), precision=_HI), None

    return jax.lax.scan(one, jnp.zeros_like(h), (stack, weight))[0]


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(shape: tuple, sliding: bool, bits: int, layers: dict, i: jnp.ndarray,
           x: jnp.ndarray) -> jnp.ndarray:
    """Layer ``i`` over one sequence x [T, D], float32. The layer is picked
    inside the program: all layers of one kind share one compile."""
    H, Hkv, Dh, theta, eps, window, top_k, first = shape
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), layers)
    T = x.shape[0]
    h = _ln(x, lp["norm"], eps)
    q = jnp.matmul(h, _dequant(lp["wq"], bits), precision=_HI).reshape(T, H, Dh)
    k = jnp.matmul(h, _dequant(lp["wk"], bits), precision=_HI).reshape(T, Hkv, Dh)
    v = jnp.matmul(h, _dequant(lp["wv"], bits), precision=_HI).reshape(T, Hkv, Dh)
    if sliding:
        q, k = _rope_pairs(q, theta), _rope_pairs(k, theta)
    group = H // Hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    pos = jnp.arange(T)

    def attend(rows: tuple) -> jnp.ndarray:
        """A block of queries over every key: the scores of a long
        sequence are taken a block of rows at a time, so that they fit."""
        qb, qpos = rows
        seen = pos[None, :] <= qpos[:, None]
        if sliding:
            seen &= pos[None, :] > qpos[:, None] - window
        scores = jnp.einsum("thd,shd->hts", qb, k, precision=_HI) / np.sqrt(Dh)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v, precision=_HI)

    block = next((b for b in (512, 128) if T % b == 0), T)
    attn = jax.lax.map(attend, (q.reshape(T // block, block, H, Dh), pos.reshape(T // block, block)))
    attn = attn.reshape(T, H * Dh)
    a = jnp.matmul(attn, _dequant(lp["wo"], bits), precision=_HI)

    s = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"].astype(jnp.float32), precision=_HI))  # [T, published]
    top_s, top_i = jax.lax.top_k(s, top_k)
    g = jnp.einsum("tke,tk->te", jax.nn.one_hot(top_i, s.shape[-1], dtype=jnp.float32),
                   top_s / jnp.sum(top_s, axis=-1, keepdims=True))
    held = jax.tree.leaves(lp["experts"])[0].shape[0]
    n_shared = jax.tree.leaves(lp["shared"])[0].shape[0]
    routed = _ffn_sum(h, lp["experts"], g[:, first:first + held].T, bits)
    shared = _ffn_sum(h, lp["shared"], jnp.full((n_shared, T), 1.0 / n_shared), bits)
    return x + a + routed + shared


@partial(jax.jit, static_argnums=(0, 1))
def _head(eps: float, logit_scale: float, final_norm: jnp.ndarray, embedding: jnp.ndarray,
          x: jnp.ndarray) -> jnp.ndarray:
    return logit_scale * jnp.matmul(_ln(x, final_norm, eps), embedding.astype(jnp.float32).T, precision=_HI)


def logits(config: dict[str, Any], weights: dict, token_ids: np.ndarray,
           weight_bits: int = 8) -> jnp.ndarray:
    """Logits [T, V] at every position of one padded sequence [T]. The
    causal mask makes right padding harmless to the positions before it."""
    eps = float(config["layer_norm_eps"])
    shape = (int(config["num_attention_heads"]), int(config["num_key_value_heads"]),
             int(config["head_dim"]), float(config["rope_theta"]), eps, int(config["sliding_window"]),
             int(config["num_experts_per_tok"]), int((config.get("deployment") or {}).get("first_expert", 0)))
    x = weights["embedding"][jnp.asarray(token_ids)].astype(jnp.float32)
    for i, kind in enumerate(config["layer_types"]):
        x = _layer(shape, kind == "sliding_attention", weight_bits, weights["layers"], jnp.int32(i), x)
    return _head(eps, float(config.get("logit_scale", 1.0)), weights["final_norm"], weights["embedding"], x)


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
    # position p-1 predicts the first served token, and so on
    chosen = np.zeros(T, np.int32)
    chosen[n_p - 1: n_p - 1 + n_s] = served
    rows = slice(n_p - 1, n_p - 1 + n_s)
    out = {"served": np.asarray(reference._gaps(ref, jnp.asarray(chosen)))[rows]}
    if control_bits is not None:
        low = logits(config, weights, padded, weight_bits=control_bits)
        out["control"] = np.asarray(reference._gaps(ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))[rows]
    return out
