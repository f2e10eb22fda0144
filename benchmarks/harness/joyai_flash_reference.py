"""The plain reference of JoyAI-LLM-Flash (``joyai_llm_flash``, the
DeepSeek-V3 layer): the published forward pass in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision. No kernels, no
cache, no batching, the EXPANDED form of latent attention only, and
nothing imported from the program: it reads the configuration file's keys
and the benchmark's own weights (``joyai_flash_family.make_weights``). The
int8 de-quantisation with its int4 control, the gap of a chosen token and
``pad_to`` are ``reference.py``'s; the sigmoid gate, the interleaved RoPE,
the expert sums and the stretch means ``deepseek_v32_reference.py``'s.

One layer over a sequence, pre-norm RMSNorm (``rms_norm_eps``), position t:

    c_q  = RMSNorm(x W_qa);  q = c_q W_qb -> heads x (qk_nope | qk_rope);  RoPE on the rope part
    [c_kv | k_r] = x W_kva (kv_lora_rank | qk_rope);  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r)
    [k_nope,h | v_h] = c_kv W_kvb  per head (qk_nope | v_head)
    s_h(t,u) = (q_nope,h · k_nope,h(u) + q_rope,h · k_r(u)) · scale;  p_h = softmax over EVERY u <= t
    x    = x + concat_h(sum_u p_h v_h(u)) W_o
    x    = x + FFN(RMSNorm(x)):  SwiGLU of intermediate_size in the first_k_dense_replace
           leading layers; after them sigma = sigmoid(x W_g) (float32) over every PUBLISHED
           expert, sigma' = sigma + e_score_correction_bias for the choice only (n_group =
           topk_group = 1: no group limit), the num_experts_per_tok largest sigma' are chosen,
           g_e = routed_scaling_factor · sigma_e / (sum_chosen sigma + 1e-20);
           FFN = sum_{e chosen and held} g_e FFN_e + FFN_shared, SwiGLU of moe_intermediate_size
    logits = RMSNorm(x_L) · W_head   (untied, over the vocabulary rows held)

``scale = (qk_nope + qk_rope)^-1/2``: ``rope_scaling`` is null, so the
rotary frequencies are plain, ``theta^(-2i/qk_rope)``, and YaRN's ``m`` is
1. RoPE is on interleaved pairs (``rope_interleave``).

The share (section 4 of the model-configs guide): ``n_routed_experts`` are
the routed experts HELD, from ``deployment.first_expert`` on, of
``published.n_routed_experts``; the router keeps its published width and
what the absent experts would add is left out — every held expert is run
dense over every position under its gate, zero where it was not chosen.
``vocab_size`` rows of embedding and head are held.

It runs layer by layer, one sequence at a time, one expert at a time (a
scan), a block of queries at a time in attention and a block of positions
at a time in the head, so that one dequantised matrix and one block's
scores or logits are all that live beside the int8 weights.

``weight_bits=4`` is the CONTROL: every int8 matrix re-quantised to int4
per output channel — the nearest precision below the one the
configuration states. Router, correction bias, embedding and head are as
served.

What ``served_gaps`` HOLDS (the harness takes the largest) is the worst
stretch of ``deepseek_v32_reference.STRETCH`` consecutive served tokens by
its MEAN gap, as ``deepseek_v32_reference`` holds it, and for its reason:
the router makes a discrete choice a token and expert layer, and bf16
activations settle a near-tie otherwise than float32 does; one flip moves
ONE token's logits by a step. On the v5e over 8 seeds x 2 requests, each
the larger of a run's two requests as the harness holds it, the largest
single gap of the program read 0.68-1.18 and of the int4 control
1.53-2.04, too close for a limit; the stretch's mean 0.0172-0.0360
against 0.3325-0.6914 (the cell's ``correct.readings``).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference
from benchmarks.harness.deepseek_v32_reference import _ffn, _ffn_sum, _mm, _rope_pairs, gates, stretch_means
from benchmarks.harness.reference import pad_to  # noqa: F401  (part of a reference module's contract)

_HI = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 512  # positions the head is computed for at a time


def softmax_scale(config: dict[str, Any]) -> float:
    if config.get("rope_scaling"):
        raise ValueError("joyai_flash_reference computes plain rotary frequencies: rope_scaling must be null")
    return (int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])) ** -0.5


def frequencies(dim: int, theta: float) -> np.ndarray:
    """theta_i = theta^(-2i/dim), one a pair of lanes."""
    return (1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))).astype(np.float32)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(shape: tuple, sparse: bool, bits: int, layers: dict, i: jnp.ndarray, x: jnp.ndarray,
           freqs: jnp.ndarray) -> jnp.ndarray:
    """Layer ``i`` of its stack over one sequence x [T, D], float32. The
    layer is picked inside the program: all layers of a kind share one
    compile."""
    (H, Dn, Dr, Dv, Rkv, eps, scale, top_k, n_group, topk_group, scaling, first) = shape
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), layers)
    T = x.shape[0]
    pos = jnp.arange(T)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]

    h = reference._rms(x, lp["attn_norm"], eps)
    c_q = reference._rms(_mm(h, lp["wq_a"], bits), lp["q_norm"], eps)
    q = _mm(c_q, lp["wq_b"], bits).reshape(T, H, Dn + Dr)
    q_nope, q_rope = q[..., :Dn], _rope_pairs(q[..., Dn:], ang)
    kv = _mm(h, lp["wkv_a"], bits)
    c_kv = reference._rms(kv[:, :Rkv], lp["kv_norm"], eps)
    k_r = _rope_pairs(kv[:, None, Rkv:], ang)[:, 0]  # [T, Dr], one for all heads
    kvb = _mm(c_kv, lp["wkv_b"], bits).reshape(T, H, Dn + Dv)
    k_nope, v = kvb[..., :Dn], kvb[..., Dn:]

    def attend(rows: tuple) -> jnp.ndarray:
        """A block of queries over every key before it."""
        qn, qr, qpos = rows
        causal = pos[None, :] <= qpos[:, None]
        scores = (jnp.einsum("thd,shd->hts", qn, k_nope, precision=_HI)
                  + jnp.einsum("thd,sd->hts", qr, k_r, precision=_HI)) * scale
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v, precision=_HI)

    block = next((b for b in (256, 128) if T % b == 0), T)
    split = lambda a: a.reshape((T // block, block) + a.shape[1:])  # noqa: E731
    attn = jax.lax.map(attend, (split(q_nope), split(q_rope), split(pos)))
    x = x + _mm(attn.reshape(T, H * Dv), lp["wo"], bits)

    h = reference._rms(x, lp["mlp_norm"], eps)
    if not sparse:
        return x + _ffn(h, lp, bits)
    sigma = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"].astype(jnp.float32), precision=_HI))  # [T, published]
    g = gates(sigma, lp["router_bias"], top_k, n_group, topk_group, scaling)
    held = jax.tree.leaves(lp["experts"])[0].shape[0]
    n_shared = jax.tree.leaves(lp["shared"])[0].shape[0]
    routed = _ffn_sum(h, lp["experts"], g[:, first:first + held].T, bits)
    shared = _ffn_sum(h, lp["shared"], jnp.ones((n_shared, T)), bits)
    return x + routed + shared


@partial(jax.jit, static_argnums=0)
def _head(eps: float, final_norm: jnp.ndarray, head: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """The head over x [T, D], a block of positions at a time."""
    T = x.shape[0]
    block = next((b for b in (HEAD_BLOCK, 128) if T % b == 0), T)
    w = head.astype(jnp.float32)
    out = jax.lax.map(lambda xb: jnp.matmul(reference._rms(xb, final_norm, eps), w, precision=_HI),
                      x.reshape(T // block, block, -1))
    return out.reshape(T, -1)


def logits(config: dict[str, Any], weights: dict, token_ids: np.ndarray, weight_bits: int = 8) -> jnp.ndarray:
    """Logits [T, V] at every position of one padded sequence [T]. The
    causal mask makes right padding harmless to the positions before it."""
    c, eps = config, float(config["rms_norm_eps"])
    shape = (int(c["num_attention_heads"]), int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
             int(c["v_head_dim"]), int(c["kv_lora_rank"]), eps, softmax_scale(c), int(c["num_experts_per_tok"]),
             int(c["n_group"]), int(c["topk_group"]), float(c["routed_scaling_factor"]),
             int((c.get("deployment") or {}).get("first_expert", 0)))
    freqs = jnp.asarray(frequencies(int(c["qk_rope_head_dim"]), float(c["rope_theta"])))
    x = weights["embedding"][jnp.asarray(token_ids)].astype(jnp.float32)
    lead = int(c["first_k_dense_replace"])
    for i in range(int(c["num_hidden_layers"])):
        sparse = i >= lead
        stack = weights["moe" if sparse else "dense"]
        x = _layer(shape, sparse, weight_bits, stack, jnp.int32(i - lead if sparse else i), x, freqs)
    return _head(eps, weights["final_norm"], weights["lm_head"], x)


def served_gaps(config: dict[str, Any], weights: dict, prompt: list[int],
                served: list[int], pad_len: int = 0,
                control_bits: int | None = None) -> dict[str, np.ndarray]:
    """Run the reference once over prompt + served tokens.

    ``served_tokens`` is the gap of every served token under the reference
    (how far its reference logit lies below the reference's best); with
    ``control_bits`` also ``control_tokens``: at the same positions, the
    gap of the token that the lower precision puts first. ``served`` and
    ``control`` are what is held of them: :func:`stretch_means`."""
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
    tokens = np.asarray(reference._gaps(ref, jnp.asarray(chosen)))[rows]
    out = {"served": stretch_means(tokens), "served_tokens": tokens}
    if control_bits is not None:
        low = logits(config, weights, padded, weight_bits=control_bits)
        tokens = np.asarray(reference._gaps(ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))[rows]
        out.update(control=stretch_means(tokens), control_tokens=tokens)
    return out
