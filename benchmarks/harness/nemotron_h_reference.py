"""The plain reference of Nemotron 3 Nano (``nemotron_h``): the published
forward pass in straightforward ``jax.numpy`` and float32 at ``highest``
matmul precision. No kernels, no cache, no batching, and nothing imported
from the program: it reads the configuration file's keys and the
benchmark's own weights (``nemotron_h_family.make_weights``). The int8
de-quantisation with its int4 control, the gap of a chosen token and
``pad_to`` are ``reference.py``'s; the sigmoid gate and the stretch means
``deepseek_v32_reference.py``'s.

Layer l over a sequence, kind ``hybrid_override_pattern[l]``, position t:

    h = RMSNorm(x; g_l)  (norm_eps);  x <- x + Mixer(h)
    M:  [z | xBC | dt] = h W_in (d_inner | d_inner + 2 G N | H)
        xBC_t = SiLU(b_conv + sum_{k<K} w_k xBC_{t-K+1+k})   the literal sum, xBC = 0 before the sequence
        [x | B | C] = xBC;  x_t [H, P], B_t and C_t [G, N];  head h reads group h // (H / G)
        Delta_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
        S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t (x) B_t   ONE POSITION AT A TIME from S = 0,
        y_t = S_t C_t + D x_t                                  the definition, not the chunked form
        out = RMSNorm over groups of d_inner / G (y * SiLU(z)) * w_norm W_out
    *:  q = h W_q, k = h W_k, v = h W_v (no positional encoding);  causal GQA, scale head_dim^-1/2;  o W_o
    E:  sigma = sigmoid(h W_r) over every PUBLISHED expert (float32); the num_experts_per_tok largest of
        sigma + e_score_correction_bias are chosen (n_group = topk_group = 1);
        g_e = routed_scaling_factor * sigma_e / (sum_chosen sigma + 1e-20);
        out = sum_{e chosen and held} g_e relu(h W_up,e)^2 W_down,e + relu(h W_up,s)^2 W_down,s
    logits = RMSNorm(x_L; g_f) W_head   (untied, over the vocabulary rows held)

The share (section 4 of the model-configs guide): ``n_routed_experts``
are the routed experts HELD, from ``deployment.first_expert`` on, of
``published.n_routed_experts``; the router keeps its published width and
what the absent experts would add is left out — every held expert is run
dense over every position under its gate, zero where it was not chosen.
``vocab_size`` rows of embedding and head are held. The experts are read
at their published widths (``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``): what is stored beyond them, the
program's padding to whole lane tiles, is not read.

It runs layer by layer, one sequence at a time, one expert at a time (a
scan), a block of queries at a time in attention and a block of positions
at a time in the head, so that one dequantised matrix and one block's
scores or logits are all that live beside the int8 weights.

``weight_bits=4`` is the CONTROL: every int8 matrix re-quantised to int4
per output channel — the nearest precision below the one the
configuration states. Router, correction bias, embedding and head are as
served. What ``served_gaps`` HOLDS is ``joyai_flash_reference``'s, for its
reason (a router's discrete choice a token and expert layer): the worst
stretch of ``STRETCH`` consecutive served tokens by its mean gap.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference
from benchmarks.harness.deepseek_v32_reference import STRETCH, _mm, gates, stretch_means  # noqa: F401
from benchmarks.harness.reference import pad_to  # noqa: F401  (part of a reference module's contract)

_HI = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 512  # positions the head is computed for at a time
QUERY_BLOCK = 256  # queries attention scores at a time


def _pick(layers: dict, i: jnp.ndarray) -> dict:
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), layers)


def _cols(w: Any, n: int) -> Any:
    """The first ``n`` columns of a matrix [..., D, F], plain or int8."""
    return {"q": w["q"][..., :n], "s": w["s"][..., :n]} if isinstance(w, dict) else w[..., :n]


def _rows(w: Any, n: int) -> Any:
    """The first ``n`` rows of a matrix [..., F, D], plain or int8."""
    return {"q": w["q"][..., :n, :], "s": w["s"]} if isinstance(w, dict) else w[..., :n, :]


@partial(jax.jit, static_argnums=(0, 1))
def _mamba(shape: tuple, bits: int, layers: dict, i: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Mamba layer ``i`` of its stack over one sequence x [T, D], float32."""
    H, P, G, N, K, eps = shape
    lp = _pick(layers, i)
    T = x.shape[0]
    Di = H * P
    Cd = Di + 2 * G * N
    h = reference._rms(x, lp["norm"], eps)
    zxd = _mm(h, lp["in_proj"], bits)
    z, xbc, dt = zxd[:, :Di], zxd[:, Di:Di + Cd], zxd[:, Di + Cd:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Cd), jnp.float32), xbc])
    conv = lp["conv_b"] + sum(lp["conv_w"][k] * padded[k:k + T] for k in range(K))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :Di].reshape(T, H, P)
    bs = jnp.repeat(xbc[:, Di:Di + G * N].reshape(T, G, N), H // G, axis=1)  # [T, H, N]: head h's group
    cs = jnp.repeat(xbc[:, Di + G * N:].reshape(T, G, N), H // G, axis=1)
    delta = jax.nn.softplus(dt + lp["dt_bias"])  # [T, H]
    a = -jnp.exp(lp["A_log"])

    def position(s: jnp.ndarray, inp: tuple) -> tuple:
        x_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t * a)[:, None, None] * s + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(position, jnp.zeros((H, P, N), jnp.float32), (xs, delta, bs, cs))
    y = (y + lp["D"][:, None] * xs).reshape(T, Di) * jax.nn.silu(z)
    grouped = y.reshape(T, G, Di // G)
    y = (grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)).reshape(T, Di)
    return x + _mm(y * lp["gate_norm"], lp["out_proj"], bits)


@partial(jax.jit, static_argnums=(0, 1))
def _attention(shape: tuple, bits: int, layers: dict, i: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Attention layer ``i`` of its stack over one sequence x [T, D], float32."""
    H, Hkv, Dh, eps = shape
    lp = _pick(layers, i)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = reference._rms(x, lp["norm"], eps)
    q = _mm(h, lp["wq"], bits).reshape(T, H, Dh)
    k = jnp.repeat(_mm(h, lp["wk"], bits).reshape(T, Hkv, Dh), H // Hkv, axis=1)
    v = jnp.repeat(_mm(h, lp["wv"], bits).reshape(T, Hkv, Dh), H // Hkv, axis=1)

    def attend(rows: tuple) -> jnp.ndarray:
        """A block of queries over every key before it."""
        qb, qpos = rows
        scores = jnp.einsum("thd,shd->hts", qb, k, precision=_HI) * Dh ** -0.5
        probs = jax.nn.softmax(jnp.where((pos[None, :] <= qpos[:, None])[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", probs, v, precision=_HI)

    block = next((b for b in (QUERY_BLOCK, 128) if T % b == 0), T)
    attn = jax.lax.map(attend, (q.reshape(T // block, block, H, Dh), pos.reshape(T // block, block)))
    return x + _mm(attn.reshape(T, H * Dh), lp["wo"], bits)


def _relu2_sum(h: jnp.ndarray, stack: dict, weight: jnp.ndarray, width: int, bits: int) -> jnp.ndarray:
    """sum_e weight[e, t] relu(h W_up,e)^2 W_down,e over a stack of experts
    [n, ...] at their published ``width``, one expert at a time."""

    def one(acc: jnp.ndarray, xs: tuple) -> tuple:
        w, g = xs
        up = _mm(h, _cols(w["w_up"], width), bits)
        return acc + g[:, None] * _mm(jnp.square(jax.nn.relu(up)), _rows(w["w_down"], width), bits), None

    return jax.lax.scan(one, jnp.zeros_like(h), (stack, weight))[0]


@partial(jax.jit, static_argnums=(0, 1))
def _experts(shape: tuple, bits: int, layers: dict, i: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Expert layer ``i`` of its stack over one sequence x [T, D], float32."""
    top_k, scaling, first, Fe, Fs, eps = shape
    lp = _pick(layers, i)
    T = x.shape[0]
    h = reference._rms(x, lp["norm"], eps)
    sigma = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"].astype(jnp.float32), precision=_HI))  # [T, published]
    g = gates(sigma, lp["router_bias"], top_k, 1, 1, scaling)
    held = jax.tree.leaves(lp["experts"])[0].shape[0]
    n_shared = jax.tree.leaves(lp["shared"])[0].shape[0]
    routed = _relu2_sum(h, lp["experts"], g[:, first:first + held].T, Fe, bits)
    return x + routed + _relu2_sum(h, lp["shared"], jnp.ones((n_shared, T)), Fs, bits)


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
    causal mask and the recurrence make right padding harmless to the
    positions before it."""
    c, eps = config, float(config["norm_eps"])
    mamba = (int(c["mamba_num_heads"]), int(c["mamba_head_dim"]), int(c["n_groups"]), int(c["ssm_state_size"]),
             int(c["conv_kernel"]), eps)
    attn = (int(c["num_attention_heads"]), int(c["num_key_value_heads"]), int(c["head_dim"]), eps)
    experts = (int(c["num_experts_per_tok"]), float(c["routed_scaling_factor"]),
               int((c.get("deployment") or {}).get("first_expert", 0)), int(c["moe_intermediate_size"]),
               int(c["moe_shared_expert_intermediate_size"]), eps)
    if int(c.get("n_group", 1)) != 1 or int(c.get("topk_group", 1)) != 1:
        raise ValueError("nemotron_h_reference routes with no group limit: n_group = topk_group = 1")
    x = weights["embedding"][jnp.asarray(token_ids)].astype(jnp.float32)
    seen = {"M": 0, "*": 0, "E": 0}
    for kind in c["hybrid_override_pattern"]:
        layer, group, shape = {"M": (_mamba, "mamba", mamba), "*": (_attention, "attn", attn),
                               "E": (_experts, "moe", experts)}[kind]
        x = layer(shape, weight_bits, weights[group], jnp.int32(seen[kind]), x)
        seen[kind] += 1
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
