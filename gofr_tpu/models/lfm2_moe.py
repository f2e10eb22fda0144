"""LFM2-MoE decoder (``model_type`` ``lfm2_moe``, LiquidAI's LFM2-8B-A1B):
gated short convolutions and grouped-query attention with per-head
QK-norm as the two kinds of mixer, in an irregular order of layer types;
two leading dense layers, then sparse experts chosen by a sigmoid rule
with an expert bias; a head tied to the embedding. RMSNorm everywhere:

    a = RMSNorm(x; g_op)
    conv layer:  [B | C | u] = a W_in ;  v_t = B_t * u_t
                 z_t = sum_{k=0..K-1} c_k * v_{t-K+1+k}     depthwise, no bias, no activation, v = 0 before a prompt
                 y_t = (C_t * z_t) W_out
    attn layer:  q = a W_q, k = a W_k, v = a W_v ;  q_h, k_h <- RMSNorm(.; g_q), RMSNorm(.; g_k) over each head
                 RoPE (rotate-half) on q and k ;  causal GQA, scale head_dim^-1/2 ;  y = o W_o
    h = x + y ;  b = RMSNorm(h; g_ffn)
    dense layer (the first n_dense): f = (SiLU(b W_1) * b W_3) W_2
    expert layer: s = sigmoid(b W_r) in float32 ;  the top_k of s + e_bias are chosen (the bias moves
                  the choice, not the gate) ;  g_e = s_e / sum_chosen s ;  f = sum_chosen g_e FFN_e(b)
    x <- h + f ;  logits = RMSNorm(x_L; g_final) E^T

Each layer runs its own mixer and nothing else: the layers are walked in
published order as a scan over the dense prefix (conv layers with the
dense MLP) and a scan over BLOCKS — an attention layer and the conv
layers that follow it up to the next one, their count data (a loop whose
trip count is the block's), so the irregular last block costs no second
program. No select chooses between two mixers that both ran: the step's
own counters (``conv_rows``, ``attn_kv``) count what ran.

What is stored (``cache_spec``; ``serving/kv_cache.py`` builds it): one
pool of K and V for the attention layers, every position, and a state a
slot that is no page — each conv layer's last ``K - 1`` values of v,
float32. The state rides ``k_pool["state"]``, is written by the prefill
that fills the slot, zeroed by a chunk that starts a prompt, carried from
chunk to chunk at any offset, and advanced by live rows only. The pool
holds two KV heads of 64 as ONE head of 128, ``[k_2p | k_2p+1]`` (a
page's minor axis must be a whole 128-lane tile: the kernels' DMAs cannot
slice a 64-wide one), and a query head is zero-padded on the half its KV
head does not use, so the kernels that exist return exactly
``P [v_2p | v_2p+1]`` and the half that is the head's own is kept —
``phi4flash``'s pairing, for groups of query heads.

The expert layer is ``ops/moe``'s: ``sigmoid_topk_gates`` with the bias
and ``held_experts`` with every expert held (``first = 0``). The engine
reaches this module through its config's class (``serving/batch.model_of``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.models.llama import _mm, _qkv_products, quantize_weight
from gofr_tpu.models.phi4flash import _last, _masked_attention, _targets, _write_rows
from gofr_tpu.ops import ssm
from gofr_tpu.ops.attention import attention
from gofr_tpu.ops.moe import held_experts, sigmoid_topk_gates
from gofr_tpu.ops.norms import rms_norm
from gofr_tpu.ops.paged_attention import paged_decode_attention, paged_kv_append
from gofr_tpu.ops.rope import apply_rope_halves, rope_angles

__all__ = [
    "Lfm2MoeConfig", "KVCache", "init_params", "quantize_params", "prefill", "prefill_slabs",
    "decode_step_paged", "decode_chunk_paged", "step_stats_len", "step_stats", "cache_spec", "unserved", "STEP_STATS",
]

CONV, ATTN = "conv", "full_attention"
# the int32 counters a paged decode step returns after the expert layers'
# (rows each expert took, experts read): row-steps whose conv tails
# advanced, summed over the conv layers that ran; positions the attention
# layers that ran read
STEP_STATS = ("conv_rows", "attn_kv")
# the ops/moe stacks of shared experts: none (``held_experts`` reads their count alone)
_NO_SHARED = {name: np.zeros((1, 0, 1, 1), np.float32) for name in ("w_gate", "w_up", "w_down")}


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    d_model: int = 2048
    n_layers: int = 24
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64  # hidden / heads: the config states none
    d_ff: int = 7168  # a dense layer's width
    d_ff_expert: int = 1792  # one expert's width
    n_experts: int = 32
    top_k: int = 4
    n_dense_layers: int = 2
    conv_kernel: int = 3  # conv_L_cache
    layer_types: tuple[str, ...] = (CONV, CONV, ATTN) + (CONV, CONV, CONV, ATTN) * 4 + (CONV, CONV, ATTN, CONV, CONV)
    routed_scaling: float = 1.0
    max_seq_len: int = 128000
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        kinds = self.layer_types
        if len(kinds) != self.n_layers or set(kinds) - {CONV, ATTN}:
            raise ValueError(f"layer_types must name {self.n_layers} layers, each {CONV} or {ATTN}")
        if not (0 <= self.n_dense_layers < self.n_layers and set(kinds[:self.n_dense_layers]) <= {CONV}
                and kinds[self.n_dense_layers] == ATTN):
            raise ValueError("lfm2_moe serves its dense layers as the conv layers before the first attention layer")
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError("the query heads divide into the KV heads' groups, and the KV heads pair up")

    # what the engine reads of a config with sparse experts
    @property
    def held_experts(self) -> int:
        return self.n_experts  # every expert, on one chip

    @property
    def first_expert(self) -> int:
        return 0

    @property
    def kv_heads(self) -> tuple[int, int]:
        """A cached token's heads and their width: the KV heads in pairs."""
        return self.n_kv_heads // 2, 2 * self.head_dim

    @property
    def n_conv(self) -> int:
        return self.layer_types.count(CONV)

    @property
    def n_attn(self) -> int:
        return self.layer_types.count(ATTN)

    @classmethod
    def tiny(cls, **kw: Any) -> "Lfm2MoeConfig":
        """Test size with the published structure: two dense conv layers,
        a period of four, a last period of three; 8 experts, top 4."""
        defaults = dict(
            vocab_size=256, d_model=64, n_layers=9, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            d_ff_expert=32, n_experts=8, top_k=4, layer_types=(CONV, CONV, ATTN, CONV, CONV, CONV, ATTN, CONV, CONV),
            max_seq_len=256, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)


def _blocks(cfg: Lfm2MoeConfig) -> tuple[np.ndarray, np.ndarray]:
    """(the layer of each attention layer, the conv layers after it up to
    the next one): the blocks the layer walk scans."""
    first = np.asarray([l for l, kind in enumerate(cfg.layer_types) if kind == ATTN], np.int32)
    ends = np.append(first[1:], cfg.n_layers)
    return first, (ends - first - 1).astype(np.int32)


def step_stats_len(cfg: Lfm2MoeConfig) -> int:
    """int32 counters a paged decode step returns after the pools: rows
    each expert took and the experts read (``ops/moe.held_experts``), each
    summed over the expert layers, then :data:`STEP_STATS`."""
    return cfg.n_experts + 1 + len(STEP_STATS)


def step_stats(cfg: Lfm2MoeConfig) -> tuple[str, ...]:
    """Names of the counters after the experts' (:data:`STEP_STATS`)."""
    return STEP_STATS


def cache_spec(cfg: Lfm2MoeConfig, page_size: int) -> tuple[tuple, dict]:
    """What this model stores (``serving/kv_cache.PagedKVCache`` builds
    from it): one pool of every position's K and V for the attention
    layers, and a state a slot — each conv layer's last ``K - 1`` values
    of v, float32. A page holds the KV heads in pairs (:attr:`kv_heads`)."""
    heads, width = cfg.kv_heads
    page = (heads, page_size, width)
    return (("full", cfg.n_attn, page, page, None),), {
        "conv": (cfg.n_conv, (cfg.conv_kernel - 1, cfg.d_model), jnp.float32)}


def unserved(engine_config: Any, lora: Any, cfg: Any = None) -> str | None:
    """What an engine asks for that this model has no program for, in a
    sentence; None if it can be built. A preempted request resumes by
    prefilling its prompt and what it emitted, which needs no snapshot of
    the conv tails and is served."""
    ec = engine_config
    if ec.kv_layout != "paged":
        return ("lfm2_moe is served from the paged KV layout only: a dense cache has no place for "
                "the conv tails (ROADMAP D2)")
    if ec.spec_tokens > 0:
        return ("lfm2_moe has no speculative verify program: a rejected draft would have to roll "
                "the conv tails back; set TPU_SPEC_TOKENS=0")
    if lora is not None:
        return "lfm2_moe serves no LoRA adapters: set no adapter registry"
    if ec.prefix_cache_entries > 0:
        return ("lfm2_moe keeps no prefix cache: a cached prefix would need the conv tails at its "
                "boundary (ROADMAP R2); set TPU_PREFIX_CACHE=0")
    if ec.kv_spill_bytes > 0:
        return ("lfm2_moe spills no KV to the host: a spilled row would need its conv tails; set "
                "TPU_KV_SPILL_BYTES=0 (a preempted row re-prefills)")
    if ec.role != "unified":
        return ("lfm2_moe is served by unified replicas only: a prefill replica hands a decode "
                "replica K/V slabs through the prefix cache, which this model does not keep")
    return None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """What a bucketed prefill returns for one batch of rows: ``k`` holds
    the attention layers' K [n_attn, B, S, Hkv/2, 2 Dh] under ``"full"`` and
    the state ({"conv": [n_conv, B, K-1, D]}); ``v`` the V's. A prefill
    needs no scratch: ``create`` is empty."""

    k: Any
    v: Any

    @classmethod
    def create(cls, cfg: Lfm2MoeConfig, batch: int, max_len: int | None = None) -> "KVCache":
        return cls({}, {})


def prefill_slabs(cache: KVCache) -> tuple[Any, Any]:
    """Row 0 of a prefill's cache, as ``batch.prefill_compute`` returns
    it and ``PagedKVCache.write_prefill`` takes it."""
    return jax.tree.map(lambda a: a[:, 0], (cache.k, cache.v))


# ------------------------------------------------------------------ weights
_QUANT = {"conv": ("in_proj", "out_proj"), "attn": ("wq", "wk", "wv", "wo"),
          "dense": ("w_gate", "w_up", "w_down")}


def init_params(cfg: Lfm2MoeConfig, key: jax.Array) -> dict:
    """Random params, stacked by kind in published order: ``conv`` and
    ``attn`` the mixers (with the norm before each), ``dense`` and ``moe``
    the feed-forward parts (with the norm before each); experts [Lm, E,
    ...]. The router, its bias, the conv taps and the norms are float32."""
    D, F, Fe, E, K = cfg.d_model, cfg.d_ff, cfg.d_ff_expert, cfg.n_experts, cfg.conv_kernel
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Lc, La, Ld = cfg.n_conv, cfg.n_attn, cfg.n_dense_layers
    Lm = cfg.n_layers - Ld
    keys = iter(jax.random.split(key, 32))

    def w(shape: tuple, fan_in: int, dtype: Any = None) -> jnp.ndarray:
        return jax.random.normal(next(keys), shape, dtype or cfg.dtype) / math.sqrt(fan_in)

    def norm(shape: tuple) -> jnp.ndarray:
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    def ffn(lead: tuple, width: int) -> dict:
        return {"w_gate": w(lead + (D, width), D), "w_up": w(lead + (D, width), D), "w_down": w(lead + (width, D), width)}

    bound = K ** -0.5
    return {
        "embedding": jax.random.normal(next(keys), (cfg.vocab_size, D), cfg.dtype) / math.sqrt(D),
        "conv": {"norm": norm((Lc, D)), "in_proj": w((Lc, D, 3 * D), D), "out_proj": w((Lc, D, D), D),
                 "conv_w": jax.random.uniform(next(keys), (Lc, K, D), jnp.float32, -bound, bound)},
        "attn": {"norm": norm((La, D)), "wq": w((La, D, H * Dh), D), "wk": w((La, D, Hkv * Dh), D),
                 "wv": w((La, D, Hkv * Dh), D), "wo": w((La, H * Dh, D), H * Dh),
                 "q_norm": norm((La, Dh)), "k_norm": norm((La, Dh))},
        "dense": {"norm": norm((Ld, D)), **ffn((Ld,), F)},
        "moe": {"norm": norm((Lm, D)), "w_router": w((Lm, D, E), D, jnp.float32),
                # the choice's correction: seeded non-zero, so that it shows
                "expert_bias": 0.1 * jax.random.normal(next(keys), (Lm, E), jnp.float32),
                "experts": ffn((Lm, E), Fe)},
        "final_norm": norm((D,)),
    }


def quantize_params(params: dict) -> dict:
    """Every large matrix of a plain tree in weight-only int8 (one f32
    scale per output channel); embedding, norms, conv taps, router and
    its bias stay as they are."""
    out = {name: (dict(group, **{k: quantize_weight(group[k], axis=-2) for k in _QUANT[name]})
                  if name in _QUANT else group) for name, group in params.items()}
    out["moe"] = dict(params["moe"], experts={k: quantize_weight(v, axis=-2)
                                              for k, v in params["moe"]["experts"].items()})
    return out


# ------------------------------------------------------------------- layers
def _pick(stack: dict, i: Any) -> dict:
    """Layer ``i`` (traced) of a kind's stacks: one dynamic slice a
    matrix, which XLA fuses into the product that reads it."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack)


def _mm32(x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """``llama._mm`` with the float32 result kept (not rounded to x's type)."""
    if isinstance(w, dict):
        return jnp.matmul(x, w["q"].astype(x.dtype), preferred_element_type=jnp.float32) * w["s"]
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def _short_conv(cfg: Lfm2MoeConfig, h: jnp.ndarray, lp: dict, tail: jnp.ndarray, n_new: jnp.ndarray) -> tuple:
    """The gated short convolution over the normed h [B, T, D] from
    ``tail`` [B, K-1, D] (v before position 0). ``n_new`` [B]: how many
    positions the new tail lies behind. Returns (y [B, T, D], the new
    tail), float32: B, C, u and the two gates are not rounded — the cubic
    gate would triple a rounding of its factors. The split of W_in's
    product stays behind a barrier: folded into the product it would
    slice the weight stack instead."""
    D = cfg.d_model
    bcu = jax.lax.optimization_barrier(_mm32(h, lp["in_proj"]))
    b, c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
    z, seen = ssm.causal_conv(b * u, tail, lp["conv_w"], silu=False)
    y = _mm32((c * z).astype(h.dtype), lp["out_proj"])
    return y, ssm.conv_tail(seen, n_new, cfg.conv_kernel - 1)


def _qkv(cfg: Lfm2MoeConfig, h: jnp.ndarray, lp: dict, sin: jnp.ndarray, cos: jnp.ndarray) -> tuple:
    """h [B, T, D] -> q [B, T, H, Dh], k and v [B, T, Hkv, Dh]: each head
    of q and k RMS-normed (one weight for the heads), then turned."""
    B, T, _ = h.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv_products(h, lp)
    q = apply_rope_halves(rms_norm(q.reshape(B, T, H, Dh), lp["q_norm"], cfg.norm_eps), sin, cos)
    k = apply_rope_halves(rms_norm(k.reshape(B, T, Hkv, Dh), lp["k_norm"], cfg.norm_eps), sin, cos)
    return q, k, v.reshape(B, T, Hkv, Dh)


def _pairs(x: jnp.ndarray) -> jnp.ndarray:
    """K or V [..., Hkv, Dh] as the pool holds it: [..., Hkv/2, 2 Dh]."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] // 2, 2 * x.shape[-1]))


def _second(cfg: Lfm2MoeConfig) -> jnp.ndarray:
    """[H, 1]: the query heads whose KV head is the second of its pair."""
    return ((jnp.arange(cfg.n_heads) // (cfg.n_heads // cfg.n_kv_heads)) % 2 == 1)[:, None]


def _pad_queries(cfg: Lfm2MoeConfig, q: jnp.ndarray) -> jnp.ndarray:
    """[..., H, Dh] -> [..., H, 2 Dh]: each head on the half of the cached
    pair that holds its KV head, zeros on the other (a zero times a key
    adds exactly 0)."""
    zero = jnp.zeros_like(q)
    return jnp.where(_second(cfg), jnp.concatenate([zero, q], -1), jnp.concatenate([q, zero], -1))


def _own_half(cfg: Lfm2MoeConfig, o: jnp.ndarray) -> jnp.ndarray:
    """[..., H, 2 Dh] = P [v_2p | v_2p+1] -> [..., H, Dh]: P v of the head's own KV head."""
    Dh = cfg.head_dim
    return jnp.where(_second(cfg), o[..., Dh:], o[..., :Dh])


def _scale(cfg: Lfm2MoeConfig) -> float:
    return 1.0 / math.sqrt(cfg.head_dim)


def _walk(cfg: Lfm2MoeConfig, params: dict, x: jnp.ndarray, carry: Any, attend: Any, convolve: Any,
          live: jnp.ndarray) -> tuple:
    """Every layer over x [B, T, D] float32, in published order. The
    caller's mixers: ``attend(h, lp, j, carry)`` for attention layer j
    and ``convolve(h, lp, i, carry)`` for conv layer i, from the normed
    input (cfg.dtype) to (y [B, T, D], the carry — pools, tails — and an
    int32 count: positions read, rows whose tail advanced). ``live``
    [B, T] marks the rows whose routing counts: no other pulls an expert.
    Returns x, the carry and the counters of :func:`step_stats_len`."""
    B, T, D = x.shape
    E, Ld, eps = cfg.n_experts, cfg.n_dense_layers, cfg.norm_eps
    moe = dict(params["moe"])
    experts = moe.pop("experts")
    rows = live.reshape(B * T)

    def normed(x: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
        return rms_norm(x, g, eps).astype(cfg.dtype)

    def conv_layer(i: Any, c: tuple) -> tuple:
        x, carry, stats = c
        lp = _pick(params["conv"], i)
        y, carry, n = convolve(normed(x, lp["norm"]), lp, i, carry)
        return x + y.astype(jnp.float32), carry, stats.at[E + 1].add(n)

    def expert_layer(x: jnp.ndarray, m: Any, stats: jnp.ndarray) -> tuple:
        lp = _pick(moe, m)
        b = rms_norm(x, lp["norm"], eps).reshape(B * T, D)  # float32: the router's input is not rounded
        gates = sigmoid_topk_gates(b, lp["w_router"], cfg.top_k, bias=lp["expert_bias"], scale=cfg.routed_scaling)
        y, g, read = held_experts(b.astype(cfg.dtype), gates, experts, _NO_SHARED, 0, _mm, m, top_k=cfg.top_k, rows=rows)
        took = jnp.sum((g > 0) & rows[:, None], axis=0, dtype=jnp.int32)
        return x + y.reshape(B, T, D), stats.at[:E].add(took).at[E].add(read)

    def dense(c: tuple, i: Any) -> tuple:
        x, carry, stats = conv_layer(i, c)
        lp = _pick(params["dense"], i)
        b = normed(x, lp["norm"])
        f = _mm(jax.nn.silu(_mm(b, lp["w_gate"]).astype(jnp.float32)).astype(b.dtype) * _mm(b, lp["w_up"]), lp["w_down"])
        return (x + f.astype(jnp.float32), carry, stats), None

    def block(c: tuple, xs: tuple) -> tuple:
        j, layer, n_conv = xs  # attention layer j at ``layer``; conv layers up to the next one
        x, carry, stats = c
        lp = _pick(params["attn"], j)
        y, carry, n = attend(normed(x, lp["norm"]), lp, j, carry)
        x, stats = expert_layer(x + y.astype(jnp.float32), layer - Ld, stats.at[E + 2].add(n))

        def conv_then_experts(t: Any, c: tuple) -> tuple:
            x, carry, stats = conv_layer(layer - j + t, c)  # conv layers before ``layer``: layer - j
            x, stats = expert_layer(x, layer + 1 + t - Ld, stats)
            return x, carry, stats

        return jax.lax.fori_loop(0, n_conv, conv_then_experts, (x, carry, stats)), None

    c = (x, carry, jnp.zeros(step_stats_len(cfg), jnp.int32))
    c, _ = jax.lax.scan(dense, c, jnp.arange(Ld))
    first, n_conv = _blocks(cfg)
    c, _ = jax.lax.scan(block, c, (jnp.arange(cfg.n_attn), jnp.asarray(first), jnp.asarray(n_conv)))
    return c


def _logits(cfg: Lfm2MoeConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The tied head over x [..., D] float32: logits [..., V] float32."""
    h = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
    return jnp.einsum("...d,vd->...v", h, params["embedding"].astype(h.dtype), preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ prefill
@partial(jax.jit, static_argnums=0, donate_argnums=(3,))
def prefill(
    cfg: Lfm2MoeConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, S] right-padded
    cache: KVCache,  # empty: this model's prefill needs no scratch
    seq_lens: jnp.ndarray,  # [B] true lengths
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill from empty tails: last-token logits [B, V] and what the
    slot stores — the attention layers' K and V over the bucket, and the
    tails where ``seq_lens`` (not the bucket's end) leaves them."""
    B, S = tokens.shape
    heads, width = cfg.kv_heads
    x = params["embedding"][tokens].astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    live = positions < seq_lens[:, None]

    def attend(h, lp, j, carry):
        k_all, v_all, tails = carry
        q, k, v = _qkv(cfg, h, lp, sin, cos)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, _pairs(k), j, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, _pairs(v), j, 0)
        o = attention(q, k, v, causal=True, kv_len=seq_lens, scale=_scale(cfg))
        return _mm(o.reshape(B, S, -1), lp["wo"]), (k_all, v_all, tails), jnp.int32(0)

    def convolve(h, lp, i, carry):
        k_all, v_all, tails = carry
        y, tail = _short_conv(cfg, h, lp, jnp.zeros((B, cfg.conv_kernel - 1, cfg.d_model), jnp.float32), seq_lens)
        return y, (k_all, v_all, jax.lax.dynamic_update_index_in_dim(tails, tail, i, 0)), jnp.int32(0)

    kv = jnp.zeros((cfg.n_attn, B, S, heads, width), cfg.dtype)
    tails = jnp.zeros((cfg.n_conv, B, cfg.conv_kernel - 1, cfg.d_model), jnp.float32)
    x, (k_all, v_all, tails), _ = _walk(cfg, params, x, (kv, kv, tails), attend, convolve, live)
    logits = _logits(cfg, params, _last(x, seq_lens))
    return logits, KVCache({"full": k_all, "state": {"conv": tails}}, {"full": v_all})


# ------------------------------------------------------------- paged decode
@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_step_paged(
    cfg: Lfm2MoeConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B] last sampled token per row
    k_pool: dict,  # {"full": [n_attn, N+1, Hkv/2, page, 2 Dh], "state": {"conv": [n_conv, B, K-1, D]}} donated
    v_pool: dict,  # {"full"} donated
    block_tables: dict,  # {"full": [B, M]} int32
    seq_lens: jnp.ndarray,  # [B] length INCLUDING this token's position
    active: jnp.ndarray,  # [B] bool — inactive rows write the trash page and keep their tails
) -> tuple[jnp.ndarray, dict, dict, jnp.ndarray]:
    """One decode step, as ``llama.decode_step_paged`` (same arguments,
    the pool carried whole and touched by the two kernels alone — the
    tails beside it are XLA's, updated in place), and after the pools the
    step's counters (:func:`step_stats_len`)."""
    B = tokens.shape[0]
    H, Dh = cfg.n_heads, cfg.head_dim
    table = block_tables["full"]
    page = k_pool["full"].shape[3]
    x = params["embedding"][tokens][:, None, :].astype(jnp.float32)  # [B, 1, D]
    pos = jnp.maximum(seq_lens - 1, 0)
    sin, cos = rope_angles(pos[:, None], Dh, cfg.rope_theta)
    pages, offsets = _targets(table, pos, active, page, k_pool["full"].shape[1] - 1)
    one = jnp.ones((B,), jnp.int32)

    def attend(h, lp, j, carry):
        kp, vp, tails = carry
        q, k, v = _qkv(cfg, h, lp, sin, cos)
        # Mosaic kernels on a TPU, scatter and gather references on the CPU
        kp, vp = paged_kv_append(kp, vp, _pairs(k[:, 0]), _pairs(v[:, 0]), j, pages, offsets)
        o = paged_decode_attention(_pad_queries(cfg, q[:, 0]), kp, vp, table, seq_lens, scale=_scale(cfg), layer=j)
        o = _own_half(cfg, o)
        read = jnp.sum(jnp.where(active, seq_lens, 0), dtype=jnp.int32)
        return _mm(o.reshape(B, 1, H * Dh), lp["wo"]), (kp, vp, tails), read

    def convolve(h, lp, i, carry):
        kp, vp, tails = carry
        tail = jax.lax.dynamic_index_in_dim(tails, i, 0, keepdims=False)
        y, new = _short_conv(cfg, h, lp, tail, one)
        tail = jnp.where(active[:, None, None], new, tail)
        return y, (kp, vp, jax.lax.dynamic_update_index_in_dim(tails, tail, i, 0)), jnp.sum(active, dtype=jnp.int32)

    carry = (k_pool["full"], v_pool["full"], k_pool["state"]["conv"])
    x, (kp, vp, tails), stats = _walk(cfg, params, x, carry, attend, convolve, active[:, None])
    return _logits(cfg, params, x[:, 0]), {"full": kp, "state": {"conv": tails}}, {"full": vp}, stats


# -------------------------------------------------------------- paged chunk
def _chunk_row(cfg: Lfm2MoeConfig, params: dict, tokens: jnp.ndarray, start: jnp.ndarray, capacity: jnp.ndarray,
               table: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray, tails: jnp.ndarray) -> tuple:
    """ONE row's chunk of T prompt tokens [T] (-1 pads) through every
    layer: its K and V written through the row's ``table`` [M] (beyond
    ``capacity`` to the trash page), each attention layer over the row's
    pages after the write, the row's ``tails`` [n_conv, K-1, D] (zeroed
    here if the chunk starts the prompt) carried. Returns the pools, the
    new tails and the residual stream [D] at the row's last token."""
    T, Dh, page = tokens.shape[0], cfg.head_dim, k_pool.shape[3]
    M = table.shape[0]
    live = (tokens >= 0)[None]  # [1, T]
    n_new = jnp.sum(live, axis=1, dtype=jnp.int32)
    positions = start + jnp.arange(T)
    x = params["embedding"][jnp.maximum(tokens, 0)][None].astype(jnp.float32)
    sin, cos = rope_angles(positions[None], Dh, cfg.rope_theta)
    pages, offsets = _targets(table[None], positions[None], live & (positions < capacity)[None], page,
                              k_pool.shape[1] - 1)
    seen = (jnp.arange(M * page)[None, :] <= positions[:, None])[None]  # [1, T, M page]

    def row_pages(pool: jnp.ndarray, j: Any) -> jnp.ndarray:  # layer j's pages of the row -> [1, M page, Hkv, Dh]
        return pool[j, table].transpose(0, 2, 1, 3).reshape(1, M * page, cfg.n_kv_heads, Dh)

    def attend(h, lp, j, carry):
        kp, vp, tails = carry
        q, k, v = _qkv(cfg, h, lp, sin, cos)
        kp = _write_rows(kp, j, pages[0], offsets[0], _pairs(k[0]))
        vp = _write_rows(vp, j, pages[0], offsets[0], _pairs(v[0]))
        o = _masked_attention(q, row_pages(kp, j), row_pages(vp, j), seen, _scale(cfg))
        return _mm(o.reshape(1, T, -1), lp["wo"]), (kp, vp, tails), jnp.int32(0)

    def convolve(h, lp, i, carry):
        kp, vp, tails = carry
        y, tail = _short_conv(cfg, h, lp, jax.lax.dynamic_index_in_dim(tails, i, 0), n_new)
        return y, (kp, vp, jax.lax.dynamic_update_index_in_dim(tails, tail[0], i, 0)), jnp.int32(0)

    tails = jnp.where(start == 0, 0.0, tails)
    x, (k_pool, v_pool, tails), _ = _walk(cfg, params, x, (k_pool, v_pool, tails), attend, convolve, live)
    return k_pool, v_pool, tails, _last(x, n_new)[0]


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_chunk_paged(
    cfg: Lfm2MoeConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, T] the next prompt tokens of each row (-1 pads)
    k_pool: dict,  # donated
    v_pool: dict,  # donated
    block_tables: dict,  # {"full": [B, M]}
    start_len: jnp.ndarray,  # [B] resident length BEFORE the chunk; 0 starts a prompt from zero tails
    active: jnp.ndarray,  # [B]
    kv_capacity: jnp.ndarray,  # [B] tokens covered by owned pages
) -> tuple[jnp.ndarray, dict, dict]:
    """A chunk of T prompt tokens a row against the pool and the tails,
    with ``llama.decode_chunk_paged``'s arguments, ONE ROW AT A TIME under
    a ``cond`` (:func:`_chunk_row`): a row without a chunk runs nothing
    and keeps its tails, so a dispatch costs its live rows. Returns
    (logits [B, 1, V] at each row's last chunk position — the head runs
    there alone — k_pool, v_pool)."""
    B = tokens.shape[0]

    def row(carry: tuple, xs: tuple) -> tuple:
        kp, vp, tails = carry
        toks, start, act, cap, table, b = xs
        tail = jax.lax.dynamic_index_in_dim(tails, b, 1, keepdims=False)

        def run(kp, vp):
            return _chunk_row(cfg, params, toks, start, cap, table, kp, vp, tail)

        def skip(kp, vp):
            return kp, vp, tail, jnp.zeros((cfg.d_model,), jnp.float32)

        kp, vp, tail, x = jax.lax.cond(act, run, skip, kp, vp)
        return (kp, vp, jax.lax.dynamic_update_index_in_dim(tails, tail, b, 1)), x

    (kp, vp, tails), x = jax.lax.scan(
        row, (k_pool["full"], v_pool["full"], k_pool["state"]["conv"]),
        (tokens, start_len, active, kv_capacity, block_tables["full"], jnp.arange(B)))
    return _logits(cfg, params, x)[:, None], {"full": kp, "state": {"conv": tails}}, {"full": vp}
