"""The plain reference of the ``phi4flash`` decoder ("SambaY" with
differential attention): the published forward pass in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision. No kernels, no
cache, no batching, no last-position shortcut — EVERY layer runs over EVERY
position — and nothing imported from the program: it reads the
configuration file's keys and the benchmark's own weights
(``phi4flash_family.make_weights``). The int8 de-quantisation with its int4
control, the gap of a chosen token and ``pad_to`` are ``reference.py``'s.

Residual stream x [T, D], LayerNorm with weight and bias (``layer_norm_eps``):

    h = x + Mix_l(LN1_l(x)) ;  x <- h + (u * SiLU(g)) W_2, [g | u] = LN2_l(h) W_1

    l even, l <= n/2   Mamba:  [u | z] = a W_in ;  u~_t = SiLU(sum_k c_k u_{t-3+k} + b_c)
                       [delta | B | C] = u~ W_x ;  Delta = softplus(delta W_dt + b_dt) ;  A = -exp(A_log)
                       S_t = exp(Delta_t A) S_{t-1} + (Delta_t u~_t) (x) B_t ;  y_t = S_t C_t + D u~_t
                       out = (y * SiLU(z)) W_out ;  at l = n/2, m = y feeds the gated memory units
    l odd, l < n/2     differential attention over the last ``sliding_window`` positions (t - u < window)
    l = n/2 + 1        the same, full causal; its K and V are what every cross layer reads
    l even, l > n/2    gated memory unit: (m_t * SiLU(a W_g)) W_o'
    l odd, l > n/2+1   differential cross-attention: own W_q, b_q, W_o, b_o on layer n/2+1's K, V

    differential attention, query pair i = heads (2i, 2i+1), KV pair j = i // (heads / kv heads):
        P1 = softmax(q_2i K_2j^T / sqrt(head_dim) + M) ;  P2 = softmax(q_2i+1 K_2j+1^T / sqrt(head_dim) + M)
        O_i = (P1 - lambda_l P2) [V_2j | V_2j+1] ;  O_i <- RMSNorm(O_i; g_l) (1 - lambda_init_l)
        lambda_l = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init_l ;  lambda_init_l = 0.8 - 0.6 exp(-0.3 l)

    logits = LN_f(x_L) E^T   (tied, no bias, no scaling) ;  no positional encoding anywhere

The two softmaxes of a pair are computed separately on K and V as
published (20 heads of 64), a block of queries at a time; the recurrence
is a plain ``lax.scan`` over positions. The stored orientation of ``A_log``
and of nothing else is the weights' own ([N, Din]); it is turned to the
published [Din, N] here.

``weight_bits=4`` is the CONTROL: every int8 matrix re-quantised to int4
per output channel — the nearest precision below the one the configuration
states. The float32 parameters (conv, W_x, W_dt, A_log, D, lambdas, norms,
biases) and the embedding are as served.
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
MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


def layer_map(n_layers: int, mb_per_layer: int = 2) -> list[tuple[str, str, int | None]]:
    """Layer l -> (kind, the weights' group, its index in the group's
    stack): ``mb_per_layer`` 2 puts a Mamba layer at every second layer of
    the self-decoder (layers 0 .. n/2 + 1), the last of which is the full
    layer; above it gated memory units and cross-attention alternate."""
    if mb_per_layer != 2:
        raise ValueError("the reference follows mb_per_layer = 2")
    half = n_layers // 2
    out: list[tuple[str, str, int | None]] = []
    for l in range(n_layers):
        if l < half:
            out.append((MAMBA if l % 2 == 0 else WINDOW, "pairs", l // 2))
        elif l <= half + 1:
            out.append((MAMBA if l == half else FULL, "mid", None))
        else:
            out.append((GMU if l % 2 == 0 else CROSS, "cross", (l - half - 2) // 2))
    return out


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * float(np.exp(-0.3 * layer))


def _dequant(w: Any, bits: int) -> jnp.ndarray:
    """A matrix as float32: a plain array as it is (the CPU tests'); an int8
    ``{"q", "s"}`` one by its scales, through ``bits`` levels if fewer than 8."""
    return reference._dequant(w, bits) if isinstance(w, dict) else w.astype(jnp.float32)


def _mm(x: jnp.ndarray, w: Any, bits: int) -> jnp.ndarray:
    return jnp.matmul(x, _dequant(w, bits), precision=_HI)


def _ln(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, eps: float) -> jnp.ndarray:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _pick(stack: dict, i: jnp.ndarray | None) -> dict:
    if i is None:
        return stack
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack)


def _mlp(h: jnp.ndarray, lp: dict, eps: float, bits: int) -> jnp.ndarray:
    gu = _mm(_ln(h, lp["ln2_w"], lp["ln2_b"], eps), lp["w1"], bits)
    half = gu.shape[-1] // 2
    return h + _mm(gu[:, half:] * jax.nn.silu(gu[:, :half]), lp["w2"], bits)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _mamba_layer(eps: float, bits: int, stacked: bool, stack: dict, i: jnp.ndarray,
                 x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A Mamba layer over x [T, D]: (x after the layer, y [T, Din] before the gate)."""
    lp = _pick(stack, i if stacked else None)
    T = x.shape[0]
    K, Din = lp["conv_w"].shape
    N = lp["a_log"].shape[0]
    R = lp["dt_w"].shape[0]
    uz = _mm(_ln(x, lp["ln1_w"], lp["ln1_b"], eps), lp["in_proj"], bits)
    u, z = uz[:, :Din], uz[:, Din:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Din), jnp.float32), u])  # u = 0 before the sequence
    conv = sum(lp["conv_w"][k] * padded[k:k + T] for k in range(K)) + lp["conv_b"]
    ut = jax.nn.silu(conv)
    dbc = jnp.matmul(ut, lp["x_proj"].astype(jnp.float32), precision=_HI)
    delta = jax.nn.softplus(jnp.matmul(dbc[:, :R], lp["dt_w"].astype(jnp.float32), precision=_HI) + lp["dt_b"])
    b, c = dbc[:, R:R + N], dbc[:, R + N:R + 2 * N]
    a = -jnp.exp(lp["a_log"].astype(jnp.float32)).T  # [Din, N], as published

    def step(s: jnp.ndarray, xs: tuple) -> tuple:
        u_t, d_t, b_t, c_t = xs
        s = jnp.exp(d_t[:, None] * a) * s + (d_t * u_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=-1) + lp["d"] * u_t

    _, y = jax.lax.scan(step, jnp.zeros((Din, N), jnp.float32), (ut, delta, b, c))
    h = x + _mm(y * jax.nn.silu(z), lp["out_proj"], bits)
    return _mlp(h, lp, eps, bits), y


def _differential(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, window: int | None, lam: jnp.ndarray,
                  lam_init: float, g: jnp.ndarray, eps: float) -> jnp.ndarray:
    """q [T, H, Dh], k and v [T, Hkv, Dh] as published -> [T, H Dh]."""
    T, H, Dh = q.shape
    Hkv = k.shape[1]
    group = (H // 2) // (Hkv // 2)
    pos = jnp.arange(T)
    # KV head of each query head: pair i = h // 2 reads KV pair j = i // group, head 2j + h % 2
    kv_of = 2 * ((jnp.arange(H) // 2) // group) + jnp.arange(H) % 2
    k_h, v_pair = k[:, kv_of], v.reshape(T, Hkv // 2, 2 * Dh)[:, (jnp.arange(H // 2)) // group]

    def attend(rows: tuple) -> jnp.ndarray:
        qb, qpos = rows  # a block of queries over every key
        seen = pos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= qpos[:, None] - pos[None, :] < window
        scores = jnp.einsum("thd,shd->hts", qb, k_h, precision=_HI) / np.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)  # [H, t, S]: each head's own softmax
        p = p.reshape(H // 2, 2, qb.shape[0], T)
        diff = p[:, 0] - lam * p[:, 1]
        o = jnp.einsum("its,sid->tid", diff, v_pair, precision=_HI)  # [t, H/2, 2 Dh]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * g
        return o * (1.0 - lam_init)

    block = next((b for b in (256, 128) if T % b == 0), T)
    out = jax.lax.map(attend, (q.reshape(T // block, block, H, Dh), pos.reshape(T // block, block)))
    return out.reshape(T, H * Dh)


def _lambda(lp: dict, lam_init: float) -> jnp.ndarray:
    return jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"])) - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + lam_init


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _attn_layer(shape: tuple, bits: int, stacked: bool, window: int | None, stack: dict, i: jnp.ndarray,
                lam_init: jnp.ndarray, x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """An attention layer with its own K and V over x [T, D]: (x after the
    layer, k, v [T, Hkv, Dh])."""
    H, Hkv, Dh, eps = shape
    lp = _pick(stack, i if stacked else None)
    T = x.shape[0]
    qkv = _mm(_ln(x, lp["ln1_w"], lp["ln1_b"], eps), lp["wqkv"], bits) + lp["bqkv"]
    q = qkv[:, :H * Dh].reshape(T, H, Dh)
    k = qkv[:, H * Dh:(H + Hkv) * Dh].reshape(T, Hkv, Dh)
    v = qkv[:, (H + Hkv) * Dh:].reshape(T, Hkv, Dh)
    o = _differential(q, k, v, window, _lambda(lp, lam_init), lam_init, lp["sub_norm"], eps)
    h = x + _mm(o, lp["wo"], bits) + lp["bo"]
    return _mlp(h, lp, eps, bits), k, v


@partial(jax.jit, static_argnums=(0, 1))
def _gmu_layer(eps: float, bits: int, stack: dict, i: jnp.ndarray, x: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    lp = _pick(stack, i)
    gate = jax.nn.silu(_mm(_ln(x, lp["ln1_w"], lp["ln1_b"], eps), lp["w_gate"], bits))
    return _mlp(x + _mm(m * gate, lp["w_out"], bits), lp, eps, bits)


@partial(jax.jit, static_argnums=(0, 1))
def _cross_layer(shape: tuple, bits: int, stack: dict, i: jnp.ndarray, lam_init: jnp.ndarray, x: jnp.ndarray,
                 k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    H, _, Dh, eps = shape
    lp = _pick(stack, i)
    q = (_mm(_ln(x, lp["ln1_w"], lp["ln1_b"], eps), lp["wq"], bits) + lp["bq"]).reshape(x.shape[0], H, Dh)
    o = _differential(q, k, v, None, _lambda(lp, lam_init), lam_init, lp["sub_norm"], eps)
    return _mlp(x + _mm(o, lp["wo"], bits) + lp["bo"], lp, eps, bits)


def hidden(config: dict[str, Any], weights: dict, token_ids: np.ndarray, weight_bits: int = 8) -> jnp.ndarray:
    """The final-normed state [T, D] at every position of one padded
    sequence [T]. Causality makes right padding harmless to the positions
    before it."""
    c, eps = config, float(config["layer_norm_eps"])
    shape = (int(c["num_attention_heads"]), int(c["num_key_value_heads"]), int(c["head_dim"]), eps)
    window = int(c["sliding_window"])
    x = weights["embedding"][jnp.asarray(token_ids)].astype(jnp.float32)
    m = k = v = None
    for l, (kind, group, i) in enumerate(layer_map(int(c["num_hidden_layers"]), int(c.get("mb_per_layer", 2)))):
        idx, lam = jnp.int32(0 if i is None else i), jnp.float32(lambda_init(l))
        if kind == MAMBA:
            x, m = _mamba_layer(eps, weight_bits, i is not None, weights[group]["mamba"], idx, x)
        elif kind in (WINDOW, FULL):
            x, k, v = _attn_layer(shape, weight_bits, i is not None, window if kind == WINDOW else None,
                                  weights[group]["attn"], idx, lam, x)
        elif kind == GMU:
            x = _gmu_layer(eps, weight_bits, weights[group]["gmu"], idx, x, m)
        else:
            x = _cross_layer(shape, weight_bits, weights[group]["cross"], idx, lam, x, k, v)
    return _ln(x, weights["final_norm_w"], weights["final_norm_b"], eps)


@jax.jit
def _head(embedding: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(x, embedding.astype(jnp.float32).T, precision=_HI)


def logits(config: dict[str, Any], weights: dict, token_ids: np.ndarray, weight_bits: int = 8) -> jnp.ndarray:
    """Logits [T, V] at every position (the tests'; ``served_gaps`` takes
    the head a block of positions at a time)."""
    return _head(weights["embedding"], hidden(config, weights, token_ids, weight_bits))


_HEAD_ROWS = 256  # positions the head is computed for at once: [256, vocabulary] float32


def _blocks(n: int) -> list[tuple[int, int]]:
    return [(a, min(a + _HEAD_ROWS, n)) for a in range(0, n, _HEAD_ROWS)]


def served_gaps(config: dict[str, Any], weights: dict, prompt: list[int],
                served: list[int], pad_len: int = 0,
                control_bits: int | None = None) -> dict[str, np.ndarray]:
    """Run the reference once over prompt + served tokens. ``served`` is
    the gap of every served token under the reference (how far its
    reference logit lies below the reference's best); with
    ``control_bits`` also ``control``: at the same positions, the gap of
    the token that the lower precision puts first. This model makes no
    discrete choice (no router, no selection), so the largest single gap
    is what the harness holds."""
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
    for a, b in _blocks(n_s):
        ref_logits = _head(weights["embedding"], ref[a:b])
        out["served"].append(np.asarray(reference._gaps(ref_logits, chosen[a:b])))
        if low is not None:
            first = jnp.argmax(_head(weights["embedding"], low[a:b]), axis=-1).astype(jnp.int32)
            out["control"].append(np.asarray(reference._gaps(ref_logits, first)))
    return {k: np.concatenate(v) for k, v in out.items() if v}
