"""The plain reference of the ``deepseek_v32`` decoder: the published
forward pass in straightforward ``jax.numpy`` and float32 at ``highest``
matmul precision. No kernels, no cache, no batching, the EXPANDED form of
latent attention only, and nothing imported from the program: it reads the
configuration file's keys and the benchmark's own weights
(``deepseek_v32_family.make_weights``). The int8 de-quantisation with its
int4 control, the gap of a chosen token and ``pad_to`` are
``reference.py``'s, the dense decoders' plain reference.

One layer over a sequence, pre-norm RMSNorm (``rms_norm_eps``), position t:

    c_q  = RMSNorm(x W_qa);  q = c_q W_qb -> heads x (qk_nope | qk_rope);  RoPE on the rope part
    [c_kv | k_r] = x W_kva (kv_lora_rank | qk_rope);  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r)
    [k_nope,h | v_h] = c_kv W_kvb  per head (qk_nope | v_head)
    indexer: q^I = c_q W^I_qb -> index_n_heads x index_head_dim;  k^I = LayerNorm(x W^I_k) (weight, bias)
             RoPE on the first qk_rope dims of each, two-halves layout, the same angles
             w = x W^I_w · index_n_heads^-1/2 · index_head_dim^-1/2
             I(t,u) = sum_j w_j relu(q^I_j(t) · k^I(u)),  u <= t
    S_t  = the min(index_topk, t+1) positions with the largest I(t,·)
    s_h(t,u) = (q_nope,h · k_nope,h(u) + q_rope,h · k_r(u)) · scale;  p_h = softmax over u in S_t
    x    = x + concat_h(sum_u p_h v_h(u)) W_o
    x    = x + FFN(RMSNorm(x)):  SwiGLU of intermediate_size in the first_k_dense_replace
           leading layers; after them sigma = sigmoid(x W_g) (float32) over every PUBLISHED
           expert, sigma' = sigma + e_score_correction_bias for the choice only, a group's
           score the sum of its two largest sigma', the topk_group best of n_group groups
           stay, the num_experts_per_tok largest sigma' among theirs are chosen,
           g_e = routed_scaling_factor · sigma_e / (sum_chosen sigma + 1e-20);
           FFN = sum_{e chosen and held} g_e FFN_e + FFN_shared, SwiGLU of moe_intermediate_size
    logits = RMSNorm(x_L) · W_head   (untied, over the vocabulary rows held)

``scale = (qk_nope + qk_rope)^-1/2 · m^2``, ``m = 0.1 · mscale · ln(factor) + 1``
(YaRN); the rotary frequencies are YaRN's blend (:func:`_yarn_freqs`).
MLA's RoPE is on interleaved pairs, the indexer's on the two halves.

The share (section 4 of the model-configs guide): ``n_routed_experts`` are
the routed experts HELD, from ``deployment.first_expert`` on, of
``published.n_routed_experts``; the router keeps its published width and
what the absent experts would add is left out. ``vocab_size`` rows of
embedding and head are held. With nothing reduced this is the whole model.

It runs layer by layer, one sequence at a time, one expert at a time (a
scan) and a block of queries at a time, so that one dequantised matrix
and one block's scores are all that live beside the int8 weights.

``weight_bits=4`` is the CONTROL: every int8 matrix re-quantised to int4
per output channel — the nearest precision below the one the
configuration states. Router, indexer head weights, embedding and head
are as served.
"""

from __future__ import annotations

import math
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


def _mm(x: jnp.ndarray, w: Any, bits: int) -> jnp.ndarray:
    return jnp.matmul(x, _dequant(w, bits), precision=_HI)


def _yarn_freqs(dim: int, theta: float, scaling: dict[str, Any] | None) -> np.ndarray:
    """theta_i = theta^(-2i/dim), blended by YaRN: pairs that turn more
    than beta_fast times in the original context keep theta_i, those that
    turn fewer than beta_slow times get theta_i / factor, a linear ramp
    over the pair index between."""
    freqs = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if not scaling or float(scaling.get("factor", 1.0)) <= 1.0:
        return freqs.astype(np.float32)
    orig, factor = float(scaling["original_max_position_embeddings"]), float(scaling["factor"])

    def pair(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(pair(float(scaling["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0)
    gamma = 1.0 - ramp
    return (freqs / factor * (1.0 - gamma) + freqs * gamma).astype(np.float32)


def softmax_scale(config: dict[str, Any]) -> float:
    scaling = config.get("rope_scaling") or {}
    factor = float(scaling.get("factor", 1.0))
    # the published inference code reads ``mscale`` here; the issue names
    # ``mscale_all_dim``: the source gives both the same value
    m = 0.1 * float(scaling.get("mscale", scaling.get("mscale_all_dim", 1.0))) * math.log(factor) + 1.0 if factor > 1.0 else 1.0
    return (int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])) ** -0.5 * m * m


def _rope_pairs(x: jnp.ndarray, ang: jnp.ndarray) -> jnp.ndarray:
    """x [T, heads, d]; pair i = lanes (2i, 2i+1) turns by ang[t, i]."""
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _rope_halves(x: jnp.ndarray, ang: jnp.ndarray) -> jnp.ndarray:
    """x [T, heads, d]; lane i pairs with lane i + d/2."""
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, eps: float) -> jnp.ndarray:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _ffn(h: jnp.ndarray, w: dict, bits: int) -> jnp.ndarray:
    return _mm(jax.nn.silu(_mm(h, w["w_gate"], bits)) * _mm(h, w["w_up"], bits), w["w_down"], bits)


def _ffn_sum(h: jnp.ndarray, stack: dict, weight: jnp.ndarray, bits: int) -> jnp.ndarray:
    """sum_e weight[e, t] · FFN_e(h), one expert of the stack at a time."""

    def one(acc: jnp.ndarray, xs: tuple) -> tuple:
        w, g = xs
        return acc + g[:, None] * _ffn(h, w, bits), None

    return jax.lax.scan(one, jnp.zeros_like(h), (stack, weight))[0]


def gates(sigma: jnp.ndarray, bias: jnp.ndarray, top_k: int, n_group: int, topk_group: int,
          scaling: float) -> jnp.ndarray:
    """The group-limited choice over scores sigma [T, E]: gates [T, E],
    zero off the chosen experts."""
    T, E = sigma.shape
    choice = sigma + bias
    if n_group > 1:
        grouped = choice.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, topk_group)
        stays = jnp.zeros((T, n_group), bool).at[jnp.arange(T)[:, None], kept].set(True)
        choice = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(T, E)
    _, chosen = jax.lax.top_k(choice, top_k)
    picked = jnp.take_along_axis(sigma, chosen, axis=-1)
    g = scaling * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros((T, E), jnp.float32).at[jnp.arange(T)[:, None], chosen].set(g)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(shape: tuple, sparse: bool, bits: int, layers: dict, i: jnp.ndarray, x: jnp.ndarray,
           freqs: jnp.ndarray) -> jnp.ndarray:
    """Layer ``i`` of its stack over one sequence x [T, D], float32. The
    layer is picked inside the program: all layers of a kind share one
    compile."""
    (H, Dn, Dr, Dv, Rkv, Hi, Di, topk, eps, scale, top_k, n_group, topk_group, scaling, first, index_dtype) = shape
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

    def index_rope(a: jnp.ndarray) -> jnp.ndarray:  # [T, heads, Di]: the first Dr dims turn
        return jnp.concatenate([_rope_halves(a[..., :Dr], ang), a[..., Dr:]], axis=-1)

    q_i = index_rope(_mm(c_q, lp["idx_wq"], bits).reshape(T, Hi, Di))
    k_i = index_rope(_layer_norm(_mm(h, lp["idx_wk"], bits), lp["idx_norm_w"], lp["idx_norm_b"], eps)[:, None])[:, 0]
    w_i = jnp.matmul(h, lp["idx_w"].astype(jnp.float32), precision=_HI) * (Hi ** -0.5 * Di ** -0.5)
    if index_dtype is not None:  # a builder's probe (tools/gap_study.py): the indexer's operands as the program caches them
        q_i, k_i = (a.astype(index_dtype).astype(jnp.float32) for a in (q_i, k_i))

    def attend(rows: tuple) -> jnp.ndarray:
        """A block of queries over every key: indexer, selection, softmax
        over the selected positions."""
        qn, qr, qi, wi, qpos = rows
        causal = pos[None, :] <= qpos[:, None]
        index = jnp.einsum("tjd,sd->tjs", qi, k_i, precision=_HI)
        index = jnp.einsum("tjs,tj->ts", jax.nn.relu(index), wi, precision=_HI)
        _, best = jax.lax.top_k(jnp.where(causal, index, -jnp.inf), min(topk, T))
        chosen = jnp.zeros(causal.shape, bool).at[jnp.arange(qpos.shape[0])[:, None], best].set(True) & causal
        scores = (jnp.einsum("thd,shd->hts", qn, k_nope, precision=_HI)
                  + jnp.einsum("thd,sd->hts", qr, k_r, precision=_HI)) * scale
        probs = jax.nn.softmax(jnp.where(chosen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v, precision=_HI)

    block = next((b for b in (256, 128) if T % b == 0), T)
    split = lambda a: a.reshape((T // block, block) + a.shape[1:])  # noqa: E731
    attn = jax.lax.map(attend, (split(q_nope), split(q_rope), split(q_i), split(w_i), split(pos)))
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
    return jnp.matmul(reference._rms(x, final_norm, eps), head.astype(jnp.float32), precision=_HI)


def logits(config: dict[str, Any], weights: dict, token_ids: np.ndarray,
           weight_bits: int = 8, index_dtype: Any = None) -> jnp.ndarray:
    """Logits [T, V] at every position of one padded sequence [T]. The
    causal mask makes right padding harmless to the positions before it.
    ``index_dtype`` rounds the indexer's queries and keys to that type
    before they are scored (a probe; the reference itself leaves it None)."""
    c, eps = config, float(config["rms_norm_eps"])
    shape = (int(c["num_attention_heads"]), int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"]),
             int(c["v_head_dim"]), int(c["kv_lora_rank"]), int(c["index_n_heads"]), int(c["index_head_dim"]),
             int(c["index_topk"]), eps, softmax_scale(c), int(c["num_experts_per_tok"]), int(c["n_group"]),
             int(c["topk_group"]), float(c["routed_scaling_factor"]),
             int((c.get("deployment") or {}).get("first_expert", 0)), index_dtype)
    freqs = jnp.asarray(_yarn_freqs(int(c["qk_rope_head_dim"]), float(c["rope_theta"]), c.get("rope_scaling")))
    x = weights["embedding"][jnp.asarray(token_ids)].astype(jnp.float32)
    lead = int(c["first_k_dense_replace"])
    for i in range(int(c["num_hidden_layers"])):
        sparse = i >= lead
        stack = weights["moe" if sparse else "dense"]
        x = _layer(shape, sparse, weight_bits, stack, jnp.int32(i - lead if sparse else i), x, freqs)
    return _head(eps, weights["final_norm"], weights["lm_head"], x)


STRETCH = 64  # served tokens a held mean runs over


def stretch_means(gaps: np.ndarray, width: int = STRETCH) -> np.ndarray:
    """The mean gap over every run of ``width`` consecutive tokens (one
    mean over all of a shorter answer)."""
    gaps = np.asarray(gaps, np.float64)
    if len(gaps) <= width:
        return np.asarray([gaps.mean()] if len(gaps) else [0.0])
    total = np.concatenate([[0.0], np.cumsum(gaps)])
    return (total[width:] - total[:-width]) / width


def served_gaps(config: dict[str, Any], weights: dict, prompt: list[int],
                served: list[int], pad_len: int = 0,
                control_bits: int | None = None) -> dict[str, np.ndarray]:
    """Run the reference once over prompt + served tokens.

    ``served_tokens`` is the gap of every served token under the reference
    (how far its reference logit lies below the reference's best); with
    ``control_bits`` also ``control_tokens``: at the same positions, the
    gap of the token that the lower precision puts first.

    What is HELD (``served``, and ``control`` beside it; the harness takes
    the largest) is :func:`stretch_means` of those: the worst stretch of
    ``STRETCH`` consecutive served tokens, by its mean gap. This model
    makes two discrete choices a token and layer — the indexer's
    ``index_topk`` positions and the router's experts — and the program's
    bf16 activations settle a near-tie otherwise than float32 does. One
    such flip moves ONE token's logits by a step and leaves its
    neighbours alone, so the largest single gap reads the rarest flip of
    the sample and not the program's precision (PERF.md section 6, PR 33,
    has the readings: with both choices taken out of the model the
    largest gap falls to the other cells'). Lost precision, a wrong
    layout or a wrong selection move every token; a stretch's mean reads
    them and is moved by a flip a ``STRETCH``-th of its step."""
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
