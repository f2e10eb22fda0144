"""Cohere2-MoE decoder (``model_type`` ``cohere2_moe``): a parallel block
— one weight-only LayerNorm feeds attention and the expert layer, and both
add to the residual — with window and full attention in a fixed pattern of
layer types, sparse experts chosen by a sigmoid rule beside shared experts
that every token takes, and a head tied to the embedding.

    h      = LN(x)                                    weight only, mean-subtracting
    a      = W_o · Attn(W_q h, W_k h, W_v h)          causal, grouped queries
             sliding layer: interleaved-pair RoPE on q and k; key j is seen
                            from i iff i - window < j <= i
             full layer:    no positional embedding, every earlier key
    s      = sigmoid(W_r h) in float32;  T = the top_k largest;  g_e = s_e / sum_T s
    routed = sum_{e in T} g_e · W_down,e (silu(W_gate,e h) * W_up,e h)
    shared = mean over the shared experts of the same form
    out    = x + a + routed + shared ;   logits = logit_scale · LN_f(x_L) · E^T

A chip may hold a SHARE of the model (``held_experts`` of ``n_experts``
from ``first_expert`` on, ``vocab_size`` rows of the embedding): the router
scores every published expert, the chip computes its own experts' part and
the shared experts, and what the absent experts would add is left out
(``ops/moe.held_experts``). With every expert held this is the whole model.

Layer kind is DATA: the scan over layers is one program, and a per-layer
flag chooses RoPE-and-window or neither. Weights are plain arrays or the
weight-only int8 ``{"q", "s"}`` form of ``models/llama.py``; the router is
float32.

The engine reaches this module through its config's class
(``serving/batch.model_of``): ``KVCache``, ``prefill``,
``decode_step_paged``, ``decode_chunk_paged``, ``step_stats_len``,
``page_shapes`` and ``unserved``. It serves the paged bf16 layout. Window layers keep all
their pages: releasing pages behind the window is the allocator's job
(ROADMAP R3) and no program here depends on it.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from gofr_tpu.models.llama import (
    KVCache,
    _mm,
    _paged_chunk_targets,
    _paged_gather,
    page_shapes,
    quantize_weight,
)
from gofr_tpu.ops.attention import attention
from gofr_tpu.ops.flash_attention import flash_attention
from gofr_tpu.ops.moe import held_experts, sigmoid_topk_gates
from gofr_tpu.ops.norms import layer_norm
from gofr_tpu.ops.paged_attention import paged_decode_attention, paged_kv_append
from gofr_tpu.ops.rope import apply_rope_interleaved, rope_angles

__all__ = [
    "Cohere2MoeConfig", "KVCache", "init_params", "quantize_params", "prefill",
    "decode_step_paged", "decode_chunk_paged", "step_stats_len", "page_shapes", "unserved",
]

SLIDING, FULL = "sliding_attention", "full_attention"
# a full layer's window: wider than any cache, so one traced scalar serves
# both kinds (and seq_len - window cannot wrap)
NO_WINDOW = 1 << 30


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144  # rows of the tied embedding held here
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128  # stated: not d_model / n_heads
    d_ff: int = 4096  # one expert's width
    n_experts: int = 128  # published: the router's outputs
    top_k: int = 8
    n_shared: int = 4
    held_experts: int = 128  # routed experts this chip holds ...
    first_expert: int = 0  # ... from this one on
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    sliding_window: int = 4096
    max_seq_len: int = 200000
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if len(self.layer_types) != self.n_layers or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {self.n_layers} layers, each {SLIDING} or {FULL}"
            )
        if not 0 <= self.first_expert <= self.n_experts - self.held_experts:
            raise ValueError("the held experts are not among the published ones")

    @classmethod
    def tiny(cls, **kw: Any) -> "Cohere2MoeConfig":
        """Test size: two periods of four layers, window 8."""
        defaults = dict(
            vocab_size=256, d_model=64, n_layers=8, n_heads=8, n_kv_heads=2,
            head_dim=16, d_ff=64, n_experts=16, top_k=4, n_shared=2,
            held_experts=16, sliding_window=8, max_seq_len=256,
            layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 2, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)


def step_stats_len(cfg: Cohere2MoeConfig) -> int:
    """int32 counters a paged decode step returns after the pools: rows
    routed to each held expert, then the held experts whose matrices were
    read (``ops/moe.held_experts``), each summed over the layers."""
    return cfg.held_experts + 1


def unserved(engine_config: Any, lora: Any, cfg: Any = None) -> str | None:
    """What an engine asks for that this model has no program for, in a
    sentence; None if it can be built."""
    if engine_config.kv_layout != "paged":
        return ("cohere2_moe is served from the paged KV layout only: its dense "
                "decode programs wait for the one cache layout (ROADMAP D2)")
    if engine_config.spec_tokens > 0:
        return "cohere2_moe has no speculative verify program: set TPU_SPEC_TOKENS=0"
    if lora is not None:
        return "cohere2_moe serves no LoRA adapters: its tied head is a slice of the vocabulary"
    return None


_MATRICES = ("wq", "wk", "wv", "wo")
_EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


def init_params(cfg: Cohere2MoeConfig, key: jax.Array) -> dict:
    """Random params, layers stacked [L, ...], experts [L, held, ...]."""
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = iter(jax.random.split(key, 12))

    def w(shape: tuple, fan_in: int) -> jnp.ndarray:
        return jax.random.normal(next(ks), shape, cfg.dtype) / math.sqrt(fan_in)

    def ffn(n: int) -> dict:
        return {"w_gate": w((L, n, D, F), D), "w_up": w((L, n, D, F), D),
                "w_down": w((L, n, F, D), F)}

    return {
        "embedding": jax.random.normal(next(ks), (cfg.vocab_size, D), cfg.dtype),
        "layers": {
            "norm": jnp.ones((L, D), jnp.float32),
            "wq": w((L, D, H * Dh), D), "wk": w((L, D, Hkv * Dh), D),
            "wv": w((L, D, Hkv * Dh), D), "wo": w((L, H * Dh, D), H * Dh),
            "w_router": jax.random.normal(next(ks), (L, D, cfg.n_experts), jnp.float32)
            / math.sqrt(D),
            "experts": ffn(cfg.held_experts),
            "shared": ffn(cfg.n_shared),
        },
        "final_norm": jnp.ones((D,), jnp.float32),
    }


def quantize_params(params: dict) -> dict:
    """Every matrix of a plain tree in weight-only int8 (one f32 scale per
    output channel); embedding, norms and router stay as they are."""
    lp = dict(params["layers"])
    for k in _MATRICES:
        lp[k] = quantize_weight(lp[k], axis=-2)
    for group in ("experts", "shared"):
        lp[group] = {k: quantize_weight(lp[group][k], axis=-2) for k in _EXPERT_MATRICES}
    return dict(params, layers=lp)


def _sliding(cfg: Cohere2MoeConfig) -> jnp.ndarray:
    return jnp.asarray([t == SLIDING for t in cfg.layer_types])


def _qkv(
    cfg: Cohere2MoeConfig, h: jnp.ndarray, lp: dict, sliding: jnp.ndarray,
    sin: jnp.ndarray, cos: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Projections of the normed input h [B, S, D], with RoPE on q and k
    where the layer is a sliding one, and the layer's window."""
    B, S, _ = h.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm(h, lp["wq"]).reshape(B, S, H, Dh)
    k = _mm(h, lp["wk"]).reshape(B, S, Hkv, Dh)
    v = _mm(h, lp["wv"]).reshape(B, S, Hkv, Dh)
    q = jnp.where(sliding, apply_rope_interleaved(q, sin, cos), q)
    k = jnp.where(sliding, apply_rope_interleaved(k, sin, cos), k)
    window = jnp.where(sliding, cfg.sliding_window, NO_WINDOW).astype(jnp.int32)
    return q, k, v, window


def _scanned(params: dict) -> tuple[dict, dict]:
    """A tree's layers as (what a scan over layers slices, the expert
    stacks it closes over whole: ``ops/moe.held_experts`` takes each
    matrix out of its stack itself)."""
    lp = dict(params["layers"])
    stacks = {"experts": lp.pop("experts"), "shared": lp.pop("shared")}
    return lp, stacks


def _mix(
    cfg: Cohere2MoeConfig, x: jnp.ndarray, h: jnp.ndarray, attn: jnp.ndarray,
    lp: dict, stacks: dict, layer: jnp.ndarray, live: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The parallel block's sum: x + W_o attn + experts(h). ``live``
    [B, S] marks the rows whose routing counts: no other pulls an expert.
    Returns the new residual and the layer's counters [held + 1] int32:
    the rows each held expert took, then the held experts read."""
    B, S, D = x.shape
    a = _mm(attn.reshape(B, S, cfg.n_heads * cfg.head_dim), lp["wo"])
    flat = h.reshape(B * S, D)
    gates = sigmoid_topk_gates(flat, lp["w_router"], cfg.top_k)
    y, g, read = held_experts(flat, gates, stacks["experts"], stacks["shared"], cfg.first_expert,
                              _mm, layer, top_k=cfg.top_k, rows=live.reshape(B * S))
    rows = jnp.sum((g > 0) & live.reshape(B * S, 1), axis=0, dtype=jnp.int32)
    out = x.astype(jnp.float32) + a.astype(jnp.float32) + y.reshape(B, S, D)
    return out.astype(x.dtype), jnp.append(rows, read)


def _logits(cfg: Cohere2MoeConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The tied head over the rows of the vocabulary held here, float32."""
    x = layer_norm(x, params["final_norm"], None, cfg.norm_eps)
    y = jnp.einsum("bsd,vd->bsv", x, params["embedding"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    return y * cfg.logit_scale


@partial(jax.jit, static_argnums=0, donate_argnums=(3,))
def prefill(
    cfg: Cohere2MoeConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, S] right-padded
    cache: KVCache,  # dense bf16 scratch [L, B, S, Hkv, Dh], donated
    seq_lens: jnp.ndarray,  # [B] true lengths
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill: fill the cache, return last-token logits [B, V]."""
    B, S = tokens.shape
    x = params["embedding"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    live = positions < seq_lens[:, None]
    layers, stacks = _scanned(params)

    def body(carry, xs):
        x, k_all, v_all = carry
        lp, layer, sliding = xs
        h = layer_norm(x, lp["norm"], None, cfg.norm_eps)
        q, k, v, window = _qkv(cfg, h, lp, sliding, sin, cos)
        k_all = jax.lax.dynamic_update_slice(k_all, k[None], (layer, 0, 0, 0, 0))
        v_all = jax.lax.dynamic_update_slice(v_all, v[None], (layer, 0, 0, 0, 0))
        if S % 128 == 0:  # compiled kernel on a TPU, ops.attention on the CPU
            attn = flash_attention(q, k, v, seq_lens, causal=True, window=window)
        else:
            attn = attention(q, k, v, causal=True, kv_len=seq_lens, window=window)
        x, _ = _mix(cfg, x, h, attn, lp, stacks, layer, live)
        return (x, k_all, v_all), None

    (x, k_all, v_all), _ = jax.lax.scan(
        body, (x, cache.k, cache.v),
        (layers, jnp.arange(cfg.n_layers), _sliding(cfg)),
    )
    last_h = jnp.take_along_axis(x, (seq_lens - 1)[:, None, None], axis=1)  # [B, 1, D]
    return _logits(cfg, params, last_h)[:, 0], KVCache(k_all, v_all)


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_step_paged(
    cfg: Cohere2MoeConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B] last sampled token per row
    k_pool: jnp.ndarray,  # [L, N_pages, Hkv, page, Dh] donated
    v_pool: jnp.ndarray,  # donated
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B] length INCLUDING this token's position
    active: jnp.ndarray,  # [B] bool — inactive rows write the trash page
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step over the paged pool, as ``llama.decode_step_paged``
    (same arguments, same trash-page redirect, the pools carried whole
    through the layers and touched by the two kernels alone), and after the pools the
    step's counters: rows of ``active`` each held expert took, summed over
    the layers (:func:`step_stats_len`)."""
    B = tokens.shape[0]
    page = k_pool.shape[3]
    trash_page = k_pool.shape[1] - 1  # reserved by PagedKVCache
    H, Dh = cfg.n_heads, cfg.head_dim
    x = params["embedding"][tokens][:, None, :].astype(cfg.dtype)  # [B, 1, D]
    pos = jnp.maximum(seq_lens - 1, 0)  # [B]
    sin, cos = rope_angles(pos[:, None], Dh, cfg.rope_theta)
    b_idx = jnp.arange(B)
    pages = jnp.where(active, block_tables[b_idx, pos // page], trash_page)
    offsets = jnp.where(active, pos % page, 0)
    layers, stacks = _scanned(params)

    def body(carry, xs):
        x, kp, vp = carry  # kp/vp: the whole pools, written by the append alone
        lp, layer, sliding = xs
        h = layer_norm(x, lp["norm"], None, cfg.norm_eps)
        q, k, v, window = _qkv(cfg, h, lp, sliding, sin, cos)
        # Mosaic kernels on a TPU, scatter and gather references on the CPU
        kp, vp = paged_kv_append(kp, vp, k[:, 0], v[:, 0], layer, pages, offsets)
        attn = paged_decode_attention(
            q[:, 0], kp, vp, block_tables, seq_lens, window=window, layer=layer
        )
        x, rows = _mix(cfg, x, h, attn.reshape(B, 1, H, Dh), lp, stacks, layer, active[:, None])
        return (x, kp, vp), rows

    (x, k_pool, v_pool), rows = jax.lax.scan(
        body, (x, k_pool, v_pool), (layers, jnp.arange(cfg.n_layers), _sliding(cfg))
    )
    return _logits(cfg, params, x)[:, 0], k_pool, v_pool, jnp.sum(rows, axis=0)


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_chunk_paged(
    cfg: Cohere2MoeConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, T] the next prompt tokens of each row (-1 pads)
    k_pool: jnp.ndarray,  # [L, N+1, Hkv, page, Dh] donated
    v_pool: jnp.ndarray,  # donated
    block_tables: jnp.ndarray,  # [B, M]
    start_len: jnp.ndarray,  # [B] resident length BEFORE the chunk
    active: jnp.ndarray,  # [B]
    kv_capacity: jnp.ndarray,  # [B] tokens covered by owned pages
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A chunk of T tokens a row in one dispatch against the page pool, as
    ``llama.decode_chunk_paged``: K/V written through the block tables
    (overflow and inactive rows to the trash page), attention over the
    gathered pages with per-row offsets and the layer's window. Returns
    (logits [B, T, V], k_pool, v_pool)."""
    B, T = tokens.shape
    positions = start_len[:, None] + jnp.arange(T)[None, :]
    pages, offsets = _paged_chunk_targets(k_pool, block_tables, positions, active, kv_capacity)
    x = params["embedding"][jnp.maximum(tokens, 0)].astype(cfg.dtype)
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    live = active[:, None] & (tokens >= 0)
    layers, stacks = _scanned(params)

    def body(x, xs):
        lp, kc, vc, layer, sliding = xs
        h = layer_norm(x, lp["norm"], None, cfg.norm_eps)
        q, k, v, window = _qkv(cfg, h, lp, sliding, sin, cos)
        kc = kc.at[pages, :, offsets].set(k)
        vc = vc.at[pages, :, offsets].set(v)
        attn = attention(
            q, _paged_gather(kc, block_tables), _paged_gather(vc, block_tables),
            causal=True, q_offset=start_len, kv_len=start_len + T, window=window,
        )
        x, _ = _mix(cfg, x, h, attn, lp, stacks, layer, live)
        return x, (kc, vc)

    x, (k_pool, v_pool) = jax.lax.scan(
        body, x, (layers, k_pool, v_pool, jnp.arange(cfg.n_layers), _sliding(cfg))
    )
    return _logits(cfg, params, x), k_pool, v_pool
