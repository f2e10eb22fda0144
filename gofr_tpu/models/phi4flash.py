"""Phi-4-mini-flash decoder (``model_type`` ``phi4flash``, the "SambaY"
architecture): a self-decoder of state-space and window-attention layers,
one full-attention layer whose K and V are the ONE cache of the whole upper
half, and a cross-decoder of gated memory units and cross-attention layers
that read that cache. Differential attention everywhere, no positional
encoding of any kind, LayerNorm with weight and bias, a head tied to the
embedding.

    h = x + Mix_l(LN1_l(x)) ;  x <- h + MLP_l(LN2_l(h)) ;  MLP(a) = (u * SiLU(g)) W_2, [g | u] = a W_1

    Mix_l, with n = n_layers:
      l even, l <= n/2      state-space (``ops/ssm.py``); at l = n/2 its output
                            before the gate, m_t, is kept for this position's GMUs
      l odd,  l <  n/2      differential attention over the last ``sliding_window`` positions
      l = n/2 + 1           the same over every earlier position; its K, V serve every layer above
      l even, l >  n/2      gated memory unit: (m_t * SiLU(a W_g)) W_o'
      l odd,  l >  n/2 + 1  differential cross-attention: own W_q, W_o, layer n/2 + 1's K and V

Differential attention: query heads pair up, (2i, 2i+1), over KV heads
(2j, 2j+1), j = i // (n_heads / n_kv_heads):

    O_i = (softmax(q_2i K_2j^T s) - lambda_l softmax(q_2i+1 K_2j+1^T s)) [V_2j | V_2j+1]
    O_i <- RMSNorm(O_i; g_l) * (1 - lambda_init_l)
    lambda_l = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init_l ;  lambda_init_l = 0.8 - 0.6 exp(-0.3 l)

It needs no attention kernel of its own: the cache holds a KV pair as ONE
head twice as wide, ``[k_2j | k_2j+1]``, and a query head is zero-padded on
the half it does not use, so the kernels that exist return exactly
``P1 [V | V']`` and ``P2 [V | V']`` (a zero times a key adds exactly 0);
the subtraction, the norm and the split are elementwise around the call.

What is stored (``cache_spec``; ``serving/kv_cache.py`` builds it): K and V
of the window layers for a row's last ``sliding_window`` positions and one
page; K and V of the one full layer for the whole context; and a state a
slot — float32 ``S`` and the conv's last inputs of every state-space layer
— that is no page. The state rides the first of the engine's two donated
trees (``k_pool["state"]``), is written by the prefill that fills the slot,
zeroed by a chunk that starts a prompt, and advanced by live rows only.

Prefill is exact with half the model on one position: nothing above layer
n/2 + 1 mixes positions other than through that layer's K and V, and m_t
is per position, so layers up to n/2 and layer n/2 + 1's K and V run over
the prompt and everything above on its last position alone. A chunk that
does not finish a prompt runs no cross-decoder at all (``finish``).

The engine reaches this module through its config's class
(``serving/batch.model_of``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.models.llama import _mm, quantize_weight
from gofr_tpu.ops import ssm
from gofr_tpu.ops.attention import NEG_INF, attention
from gofr_tpu.ops.flash_attention import flash_attention
from gofr_tpu.ops.norms import layer_norm
from gofr_tpu.ops.paged_attention import paged_decode_attention, paged_kv_append

__all__ = [
    "Phi4FlashConfig", "KVCache", "init_params", "quantize_params", "prefill", "prefill_slabs",
    "decode_step_paged", "decode_chunk_paged", "step_stats_len", "step_stats", "cache_spec", "unserved",
    "layer_kinds", "lambda_init", "STEP_STATS", "CHUNK_TAKES_FINISH",
]

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window_attention", "full_attention", "gmu", "cross_attention"
# the int32 counters a paged decode step returns after the pools
STEP_STATS = ("attn_full", "attn_win", "ssm_rows")
# serving/batch.ragged_step_paged hands ``finish`` to a chunk program that asks for it
CHUNK_TAKES_FINISH = True


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    d_model: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64  # stated: hidden / heads here, and the softmax scale's root
    d_ff: int = 10240
    sliding_window: int = 512
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # the recurrent state's type. float32 is the served one; a test sets
    # bfloat16 to show that the comparison with the reference then fails
    state_dtype: Any = jnp.float32

    def __post_init__(self) -> None:
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError("phi4flash alternates four kinds of layer in two halves: n_layers is a multiple of 4, at least 8")
        if self.n_heads % 2 or self.n_kv_heads % 2 or self.n_heads % self.n_kv_heads:
            raise ValueError("differential attention pairs the query heads and the KV heads")

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_pairs(self) -> int:
        """(state-space, window attention) pairs below the middle."""
        return self.n_layers // 4

    @property
    def n_cross(self) -> int:
        """(gated memory unit, cross-attention) pairs above it."""
        return self.n_layers // 4 - 1

    @property
    def n_mamba(self) -> int:
        return self.n_pairs + 1

    @property
    def kv_heads(self) -> tuple[int, int]:
        """A cached token's heads and their width: the KV pairs."""
        return self.n_kv_heads // 2, 2 * self.head_dim

    @classmethod
    def tiny(cls, **kw: Any) -> "Phi4FlashConfig":
        """Test size: two pairs, the middle, one cross pair; window 8."""
        defaults = dict(
            vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            sliding_window=8, d_state=4, d_conv=4, expand=2, dt_rank=4, max_seq_len=256, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)


def layer_kinds(cfg: Phi4FlashConfig) -> tuple[str, ...]:
    """What mixes layer l, for l = 0 .. n_layers - 1."""
    half = cfg.n_layers // 2
    lower = (MAMBA, WINDOW) * (half // 2)
    return lower + (MAMBA, FULL) + (GMU, CROSS) * cfg.n_cross


def lambda_init(layer: int | np.ndarray) -> Any:
    return 0.8 - 0.6 * np.exp(-0.3 * np.asarray(layer, np.float64))


def step_stats_len(cfg: Phi4FlashConfig) -> int:
    """int32 counters a paged decode step returns after the pools
    (:data:`STEP_STATS`): cache positions the full layer and its readers
    read, positions the window layers read, rows whose state advanced."""
    return len(STEP_STATS)


def step_stats(cfg: Phi4FlashConfig) -> tuple[str, ...]:
    """Names of the step's counters (:data:`STEP_STATS`)."""
    return STEP_STATS


def cache_spec(cfg: Phi4FlashConfig, page_size: int) -> tuple[tuple, dict]:
    """What this model stores (``serving/kv_cache.PagedKVCache`` builds
    from it): the pools by layer kind — (name, layers, a K page, a V page
    [heads, page, width], the trailing positions a row needs or None for
    all) — and the per-slot state arrays, name -> (layers, shape a slot,
    dtype), laid out [layers, slots, ...]."""
    heads, width = cfg.kv_heads
    page = (heads, page_size, width)
    pools = (
        ("window", cfg.n_pairs, page, page, cfg.sliding_window),
        ("full", 1, page, page, None),
    )
    state = {
        "ssm": (cfg.n_mamba, (cfg.d_state, cfg.d_inner), cfg.state_dtype),
        "conv": (cfg.n_mamba, (cfg.d_conv - 1, cfg.d_inner), cfg.dtype),
    }
    return pools, state


def unserved(engine_config: Any, lora: Any, cfg: Any = None) -> str | None:
    """What an engine asks for that this model has no program for, in a
    sentence; None if it can be built. A preempted request resumes by
    prefilling its prompt and what it emitted, which needs no snapshot of
    the state and is served."""
    ec = engine_config
    if ec.kv_layout != "paged":
        return ("phi4flash is served from the paged KV layout only: a dense cache has no place for "
                "the recurrent state or the shared layer (ROADMAP D2)")
    if ec.spec_tokens > 0:
        return ("phi4flash has no speculative verify program: a rejected draft would have to roll "
                "the recurrent state back; set TPU_SPEC_TOKENS=0")
    if lora is not None:
        return "phi4flash serves no LoRA adapters: set no adapter registry"
    if ec.prefix_cache_entries > 0:
        return ("phi4flash keeps no prefix cache: a cached prefix would need a snapshot of the "
                "recurrent state at its boundary (ROADMAP R2); set TPU_PREFIX_CACHE=0")
    if ec.kv_spill_bytes > 0:
        return ("phi4flash spills no KV to the host: a spilled row would need its recurrent state "
                "and three kinds of page; set TPU_KV_SPILL_BYTES=0 (a preempted row re-prefills)")
    if ec.role != "unified":
        return ("phi4flash is served by unified replicas only: a prefill replica hands a decode "
                "replica K/V slabs through the prefix cache, which this model does not keep")
    if cfg is not None and ec.prefill_chunk_tokens > cfg.sliding_window:
        return ("phi4flash's chunk program reads a window's old keys before it writes the chunk over "
                f"them: prefill_chunk_tokens may not exceed the sliding window ({cfg.sliding_window})")
    return None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """What a bucketed prefill returns for one batch of rows: ``k`` holds
    the window layers' K [n_pairs, B, S, heads, width], the full layer's
    [1, B, S, heads, width] and the state ({"ssm", "conv"}, [n_mamba, B,
    ...]); ``v`` the two V's. A prefill needs no scratch: ``create`` is
    empty."""

    k: Any
    v: Any

    @classmethod
    def create(cls, cfg: Phi4FlashConfig, batch: int, max_len: int | None = None) -> "KVCache":
        return cls({}, {})


def prefill_slabs(cache: KVCache) -> tuple[Any, Any]:
    """Row 0 of a prefill's cache, as ``batch.prefill_compute`` returns
    it and ``PagedKVCache.write_prefill`` takes it."""
    return jax.tree.map(lambda a: a[:, 0], (cache.k, cache.v))


# ------------------------------------------------------------------ weights
_MLP = ("w1", "w2")
_QUANT = {"mamba": ("in_proj", "out_proj") + _MLP, "attn": ("wqkv", "wo") + _MLP,
          "gmu": ("w_gate", "w_out") + _MLP, "cross": ("wq", "wo") + _MLP}


def init_params(cfg: Phi4FlashConfig, key: jax.Array) -> dict:
    """Random params. ``pairs`` stacks the n_pairs (state-space, window)
    pairs, ``mid`` holds the middle's two layers, ``cross`` stacks the
    (gated memory unit, cross-attention) pairs. ``A_log`` and ``dt_b``
    are Mamba's own initialisation: a random one would make the
    recurrence forget in a step or never."""
    D, F, Din, N, K, R = cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 256))

    def w(shape: tuple, fan_in: int, dtype: Any = None) -> jnp.ndarray:
        return jax.random.normal(next(keys), shape, dtype or cfg.dtype) / math.sqrt(fan_in)

    def vec(shape: tuple, scale: float = 0.1, mean: float = 0.0) -> jnp.ndarray:
        return mean + scale * jax.random.normal(next(keys), shape, jnp.float32)

    def block(n: tuple) -> dict:
        return {"ln1_w": vec(n + (D,), mean=1.0), "ln1_b": vec(n + (D,)), "ln2_w": vec(n + (D,), mean=1.0),
                "ln2_b": vec(n + (D,)), "w1": w(n + (D, 2 * F), D), "w2": w(n + (F, D), F)}

    def mamba(n: tuple) -> dict:
        dt = jnp.exp(jax.random.uniform(next(keys), n + (Din,), jnp.float32)
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return {**block(n), "in_proj": w(n + (D, 2 * Din), D), "out_proj": w(n + (Din, D), Din),
                "conv_w": w(n + (K, Din), K, jnp.float32), "conv_b": vec(n + (Din,)),
                "x_proj": w(n + (Din, R + 2 * N), Din, jnp.float32), "dt_w": w(n + (R, Din), R, jnp.float32),
                "dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], n + (N, Din)),
                "d": jnp.ones(n + (Din,), jnp.float32)}

    def lambdas(n: tuple) -> dict:
        return {name: vec(n + (Dh,)) for name in ("lq1", "lk1", "lq2", "lk2")}

    def attn(n: tuple) -> dict:
        return {**block(n), **lambdas(n), "wqkv": w(n + (D, (H + 2 * Hkv) * Dh), D), "bqkv": vec(n + ((H + 2 * Hkv) * Dh,)),
                "wo": w(n + (H * Dh, D), H * Dh), "bo": vec(n + (D,)), "sub_norm": vec(n + (2 * Dh,), mean=1.0)}

    def gmu(n: tuple) -> dict:
        return {**block(n), "w_gate": w(n + (D, Din), D), "w_out": w(n + (Din, D), Din)}

    def cross(n: tuple) -> dict:
        return {**block(n), **lambdas(n), "wq": w(n + (D, H * Dh), D), "bq": vec(n + (H * Dh,)),
                "wo": w(n + (H * Dh, D), H * Dh), "bo": vec(n + (D,)), "sub_norm": vec(n + (2 * Dh,), mean=1.0)}

    P, C = (cfg.n_pairs,), (cfg.n_cross,)
    return {
        "embedding": jax.random.normal(next(keys), (cfg.vocab_size, D), cfg.dtype),
        "pairs": {"mamba": mamba(P), "attn": attn(P)},
        "mid": {"mamba": mamba(()), "attn": attn(())},
        "cross": {"gmu": gmu(C), "cross": cross(C)},
        "final_norm_w": vec((D,), mean=1.0), "final_norm_b": vec((D,)),
    }


def quantize_params(params: dict) -> dict:
    """Every large matrix of a plain tree in weight-only int8 (one f32
    scale per output channel); embedding, norms, biases, the conv, W_x,
    W_dt, A_log, D and the lambda vectors stay as they are."""
    def group(tree: dict) -> dict:
        return {kind: {k: (quantize_weight(v, axis=-2) if k in _QUANT[kind] else v) for k, v in lp.items()}
                for kind, lp in tree.items()}

    return dict(params, **{name: group(params[name]) for name in ("pairs", "mid", "cross")})


# ------------------------------------------------------------------- layers
def _mlp(cfg: Phi4FlashConfig, x: jnp.ndarray, lp: dict) -> jnp.ndarray:
    gu = _mm(layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps), lp["w1"])
    g, u = gu[..., :cfg.d_ff], gu[..., cfg.d_ff:]
    return x + _mm(u * jax.nn.silu(g), lp["w2"])


def _mamba(cfg: Phi4FlashConfig, x: jnp.ndarray, lp: dict, state: jnp.ndarray, tail: jnp.ndarray,
           live: jnp.ndarray, n_new: jnp.ndarray) -> tuple:
    """A state-space layer over x [B, T, D] from (state [B, N, Din], tail
    [B, K-1, Din]); ``live`` [B, T] marks the positions that advance the
    state, ``n_new`` [B] how many inputs the new tail lies behind.
    Returns (x after the layer, y [B, T, Din] before the gate, state, tail)."""
    Din, T = cfg.d_inner, x.shape[1]
    uz = _mm(layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps), lp["in_proj"])
    u, z = uz[..., :Din], uz[..., Din:]
    ut, seen = ssm.causal_conv(u, tail, lp["conv_w"], lp["conv_b"])
    delta, b, c = ssm.ssm_inputs(ut, lp["x_proj"], lp["dt_w"], lp["dt_b"], cfg.d_state, live)
    if T == 1:
        y, state = ssm.selective_step(ut[:, 0], delta[:, 0], lp["a_log"], b[:, 0], c[:, 0], lp["d"], state)
        y = y[:, None]
    else:
        y, state = ssm.selective_scan(ut, delta, lp["a_log"], b, c, lp["d"], state)
    mix = _mm((y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype), lp["out_proj"])
    tail = ssm.conv_tail(seen, n_new, cfg.d_conv - 1).astype(tail.dtype)
    return _mlp(cfg, x + mix, lp), y, state.astype(cfg.state_dtype), tail


def _pad_queries(q: jnp.ndarray) -> jnp.ndarray:
    """[..., H, Dh] -> [..., H, 2 Dh]: an even head on the first half, an
    odd one on the second — the half of the cached pair it is scored on."""
    zero = jnp.zeros_like(q)
    odd = (jnp.arange(q.shape[-2]) % 2 == 1)[:, None]
    return jnp.where(odd, jnp.concatenate([zero, q], -1), jnp.concatenate([q, zero], -1))


def _lambda(lp: dict, lam_init: jnp.ndarray) -> jnp.ndarray:
    return (jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"])) - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"]))
            + lam_init).astype(jnp.float32)


def _differential(cfg: Phi4FlashConfig, attn: jnp.ndarray, lp: dict, lam_init: jnp.ndarray) -> jnp.ndarray:
    """attn [..., H, 2 Dh], head h being P_h [V | V'] -> the pairs'
    difference, normed, [..., H Dh] (a pair's two halves are its heads')."""
    lead = attn.shape[:-2]
    a = attn.astype(jnp.float32).reshape(lead + (cfg.n_heads // 2, 2, 2 * cfg.head_dim))
    o = a[..., 0, :] - _lambda(lp, lam_init) * a[..., 1, :]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * lp["sub_norm"] * (1.0 - lam_init)
    return o.reshape(lead + (cfg.n_heads * cfg.head_dim,)).astype(attn.dtype)


def _qkv(cfg: Phi4FlashConfig, h: jnp.ndarray, lp: dict) -> tuple:
    """h [..., D] -> (padded queries [..., H, 2 Dh], K and V as the cache
    holds them [..., Hkv/2, 2 Dh])."""
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    heads, width = cfg.kv_heads
    qkv = _mm(h, lp["wqkv"]) + lp["bqkv"].astype(h.dtype)
    lead = h.shape[:-1]
    q = qkv[..., :H * Dh].reshape(lead + (H, Dh))
    k = qkv[..., H * Dh:(H + Hkv) * Dh].reshape(lead + (heads, width))
    v = qkv[..., (H + Hkv) * Dh:].reshape(lead + (heads, width))
    return _pad_queries(q), k, v


def _attn_out(cfg: Phi4FlashConfig, x: jnp.ndarray, attn: jnp.ndarray, lp: dict, lam_init: jnp.ndarray) -> jnp.ndarray:
    mix = _mm(_differential(cfg, attn, lp, lam_init), lp["wo"]) + lp["bo"].astype(x.dtype)
    return _mlp(cfg, x + mix, lp)


def _scale(cfg: Phi4FlashConfig) -> float:
    return 1.0 / math.sqrt(cfg.head_dim)


def _lam_inits(cfg: Phi4FlashConfig) -> tuple[jnp.ndarray, float, jnp.ndarray]:
    """lambda_init of the window layers, the full layer, the cross layers."""
    half = cfg.n_layers // 2
    return (jnp.asarray(lambda_init(np.arange(1, half, 2)), jnp.float32), float(lambda_init(half + 1)),
            jnp.asarray(lambda_init(np.arange(half + 3, cfg.n_layers, 2)), jnp.float32))


def _upper(cfg: Phi4FlashConfig, params: dict, x: jnp.ndarray, m: jnp.ndarray, attend: Any) -> jnp.ndarray:
    """Everything above the full layer's K and V, over x [B, D] (one
    position a row): the full layer's own queries and MLP, the gated
    memory units on ``m`` [B, Din], the cross-attention layers, the final
    norm and the tied head. ``attend(padded queries [B, H, 2 Dh])`` reads
    the one cache. Returns logits [B, V] float32."""
    _, lam_full, lam_cross = _lam_inits(cfg)
    lp = params["mid"]["attn"]
    h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
    q, _, _ = _qkv(cfg, h, lp)  # the K and V of this position are in the cache already
    x = _attn_out(cfg, x, attend(q), lp, jnp.float32(lam_full))

    def body(x: jnp.ndarray, xs: tuple) -> tuple:
        lp, lam_init = xs
        g, c = lp["gmu"], lp["cross"]
        a = layer_norm(x, g["ln1_w"], g["ln1_b"], cfg.norm_eps)
        x = _mlp(cfg, x + ssm.gated_memory(m, a, g["w_gate"], g["w_out"], _mm), g)
        h = layer_norm(x, c["ln1_w"], c["ln1_b"], cfg.norm_eps)
        q = (_mm(h, c["wq"]) + c["bq"].astype(h.dtype)).reshape(h.shape[:-1] + (cfg.n_heads, cfg.head_dim))
        return _attn_out(cfg, x, attend(_pad_queries(q)), c, lam_init), None

    x, _ = jax.lax.scan(body, x, (params["cross"], lam_cross))
    x = layer_norm(x, params["final_norm_w"], params["final_norm_b"], cfg.norm_eps)
    return jnp.einsum("bd,vd->bv", x, params["embedding"].astype(x.dtype), preferred_element_type=jnp.float32)


def _last(x: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """x [B, T, W] at each row's position n - 1 (0 for an empty row): [B, W]."""
    idx = jnp.clip(n - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]


# ------------------------------------------------------------------ prefill
@partial(jax.jit, static_argnums=0, donate_argnums=(3,))
def prefill(
    cfg: Phi4FlashConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, S] right-padded
    cache: KVCache,  # empty: this model's prefill needs no scratch
    seq_lens: jnp.ndarray,  # [B] true lengths
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill from an empty state: last-token logits [B, V] and what the
    slot stores — the window layers' and the full layer's K and V over the
    bucket, and the state where ``seq_lens`` (not the bucket's end) leaves
    it. Layers up to the full layer's K and V run over every position,
    everything above on the last one."""
    B, S = tokens.shape
    x = params["embedding"][tokens].astype(cfg.dtype)
    live = jnp.arange(S)[None, :] < seq_lens[:, None]
    lam_win, _, _ = _lam_inits(cfg)
    state0 = jnp.zeros((B, cfg.d_state, cfg.d_inner), cfg.state_dtype)
    tail0 = jnp.zeros((B, cfg.d_conv - 1, cfg.d_inner), cfg.dtype)
    window = jnp.int32(cfg.sliding_window)

    def body(x: jnp.ndarray, xs: tuple) -> tuple:
        lp, lam_init = xs
        x, _, state, tail = _mamba(cfg, x, lp["mamba"], state0, tail0, live, seq_lens)
        a = lp["attn"]
        q, k, v = _qkv(cfg, layer_norm(x, a["ln1_w"], a["ln1_b"], cfg.norm_eps), a)
        if S % 128 == 0:  # compiled kernel on a TPU, ops.attention on the CPU
            attn = flash_attention(q, k, v, seq_lens, causal=True, scale=_scale(cfg), window=window)
        else:
            attn = attention(q, k, v, causal=True, kv_len=seq_lens, scale=_scale(cfg), window=window)
        return _attn_out(cfg, x, attn, a, lam_init), (k, v, state, tail)

    x, (k_win, v_win, states, tails) = jax.lax.scan(body, x, (params["pairs"], lam_win))
    x, y, state, tail = _mamba(cfg, x, params["mid"]["mamba"], state0, tail0, live, seq_lens)
    lp = params["mid"]["attn"]
    _, k, v = _qkv(cfg, layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps), lp)

    def attend(q: jnp.ndarray) -> jnp.ndarray:  # the last position sees every position before seq_len
        return attention(q[:, None], k, v, causal=False, kv_len=seq_lens, scale=_scale(cfg))[:, 0]

    logits = _upper(cfg, params, _last(x, seq_lens), _last(y, seq_lens), attend)
    stored = {"window": k_win, "full": k[None],
              "state": {"ssm": jnp.concatenate([states, state[None]]), "conv": jnp.concatenate([tails, tail[None]])}}
    return logits, KVCache(stored, {"window": v_win, "full": v[None]})


# ------------------------------------------------------------- paged decode
def _targets(table: jnp.ndarray, positions: jnp.ndarray, valid: jnp.ndarray, page: int, trash: int) -> tuple:
    """(page id, slot in the page) each position writes: through the
    row's table where ``valid``, else the trash page."""
    blk = jnp.minimum(positions // page, table.shape[1] - 1)
    ids = jnp.take_along_axis(table, blk.reshape(table.shape[0], -1), axis=1).reshape(positions.shape)
    return jnp.where(valid, ids, trash), jnp.where(valid, positions % page, 0)


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_step_paged(
    cfg: Phi4FlashConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B] last sampled token per row
    k_pool: dict,  # {"window": [n_pairs, N+1, heads, page, width], "full": [1, N'+1, ...], "state": {...}} donated
    v_pool: dict,  # {"window", "full"} donated
    block_tables: dict,  # {"window": [B, M], "full": [B, M]} int32
    seq_lens: jnp.ndarray,  # [B] length INCLUDING this token's position
    active: jnp.ndarray,  # [B] bool — inactive rows write the trash pages and keep their state
) -> tuple[jnp.ndarray, dict, dict, jnp.ndarray]:
    """One decode step, as ``llama.decode_step_paged`` (same arguments,
    the pools carried whole and touched by the two kernels alone — the
    state arrays beside them are XLA's, updated in place), and after the
    pools the step's counters (:data:`STEP_STATS`)."""
    B = tokens.shape[0]
    page = k_pool["full"].shape[3]
    x = params["embedding"][tokens][:, None, :].astype(cfg.dtype)  # [B, 1, D]
    pos = jnp.maximum(seq_lens - 1, 0)
    lam_win, _, _ = _lam_inits(cfg)
    live = active[:, None]
    one = jnp.ones((B,), jnp.int32)
    window = jnp.int32(cfg.sliding_window)
    win_page, win_off = _targets(block_tables["window"], pos, active, page, k_pool["window"].shape[1] - 1)
    full_page, full_off = _targets(block_tables["full"], pos, active, page, k_pool["full"].shape[1] - 1)

    def mamba(x: jnp.ndarray, lp: dict, states: jnp.ndarray, tails: jnp.ndarray, i: Any) -> tuple:
        state = jax.lax.dynamic_index_in_dim(states, i, 0, keepdims=False)
        tail = jax.lax.dynamic_index_in_dim(tails, i, 0, keepdims=False)
        x, y, state, new_tail = _mamba(cfg, x, lp, state, tail, live, one)
        tail = jnp.where(active[:, None, None], new_tail, tail)
        return (x, y, jax.lax.dynamic_update_index_in_dim(states, state, i, 0),
                jax.lax.dynamic_update_index_in_dim(tails, tail, i, 0))

    def body(carry: tuple, xs: tuple) -> tuple:
        x, kp, vp, states, tails = carry  # kp/vp: the window pools, written by the append alone
        lp, lam_init, i = xs
        x, _, states, tails = mamba(x, lp["mamba"], states, tails, i)
        a = lp["attn"]
        q, k, v = _qkv(cfg, layer_norm(x, a["ln1_w"], a["ln1_b"], cfg.norm_eps), a)
        # Mosaic kernels on a TPU, scatter and gather references on the CPU
        kp, vp = paged_kv_append(kp, vp, k[:, 0], v[:, 0], i, win_page, win_off)
        attn = paged_decode_attention(q[:, 0], kp, vp, block_tables["window"], seq_lens,
                                      scale=_scale(cfg), window=window, layer=i)
        return (_attn_out(cfg, x, attn[:, None], a, lam_init), kp, vp, states, tails), None

    st = k_pool["state"]
    (x, k_win, v_win, states, tails), _ = jax.lax.scan(
        body, (x, k_pool["window"], v_pool["window"], st["ssm"], st["conv"]),
        (params["pairs"], lam_win, jnp.arange(cfg.n_pairs)))
    x, y, states, tails = mamba(x, params["mid"]["mamba"], states, tails, cfg.n_pairs)
    lp = params["mid"]["attn"]
    _, k, v = _qkv(cfg, layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps), lp)
    k_full, v_full = paged_kv_append(k_pool["full"], v_pool["full"], k[:, 0], v[:, 0], 0, full_page, full_off)

    def attend(q: jnp.ndarray) -> jnp.ndarray:
        return paged_decode_attention(q, k_full, v_full, block_tables["full"], seq_lens, scale=_scale(cfg), layer=0)

    logits = _upper(cfg, params, x[:, 0], y[:, 0], attend)
    lens = jnp.where(active, seq_lens, 0)
    readers = cfg.n_cross + 1
    stats = jnp.stack([readers * jnp.sum(lens), cfg.n_pairs * jnp.sum(jnp.minimum(lens, cfg.sliding_window)),
                       jnp.sum(active)]).astype(jnp.int32)
    return (logits, {"window": k_win, "full": k_full, "state": {"ssm": states, "conv": tails}},
            {"window": v_win, "full": v_full}, stats)


# -------------------------------------------------------------- paged chunk
def _masked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, mask: jnp.ndarray, scale: float) -> jnp.ndarray:
    """q [B, T, H, W] over k, v [B, S, Hkv, W] under mask [B, T, S]; a
    query that sees nothing returns zeros."""
    B, T, H, W = q.shape
    Hkv = k.shape[2]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(B, T, Hkv, H // Hkv, W), k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask[:, None, None], p, 0.0)
    p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-30)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v).reshape(B, T, H, W)


def _write_rows(pool: jnp.ndarray, layer: Any, pages: jnp.ndarray, offsets: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """T tokens' K (or V) ``new`` [T, heads, width] into ``pool[layer,
    pages[t], :, offsets[t]]``, as a scatter of ROWS of the pool seen as
    [L N heads page, width] — a bitcast of it, so XLA writes in place and
    gives the pool no layout of its own (an indexed write of the 5-D pool
    makes it swap the head and page axes and copy the pool around the
    write: PERF.md section 6, PR 30)."""
    L, N, heads, page, width = pool.shape
    rows = (((layer * N + pages)[:, None] * heads + jnp.arange(heads)[None, :]) * page + offsets[:, None]).reshape(-1)
    flat = pool.reshape(L * N * heads * page, width).at[rows].set(new.reshape(-1, width).astype(pool.dtype))
    return flat.reshape(pool.shape)


def _chunk_row(cfg: Phi4FlashConfig, params: dict, tokens: jnp.ndarray, start: jnp.ndarray, capacity: jnp.ndarray,
               tables: dict, pools: tuple, state: jnp.ndarray, tail: jnp.ndarray) -> tuple:
    """ONE row's chunk of T prompt tokens [T] (-1 pads) through the
    self-decoder and the full layer's K and V: ``tables`` are the row's
    [M], ``pools`` (k_win, v_win, k_full, v_full) whole, ``state`` [n_mamba,
    N, Din] and ``tail`` [n_mamba, K-1, Din] the row's (zeroed here if the
    chunk starts the prompt). A window layer reads the window's old keys
    out of its pages BEFORE the chunk is written over them. Returns the
    pools, the row's new state and tail, and, at the row's last token, the
    residual stream [D] and the middle state-space layer's output [Din]."""
    k_win, v_win, k_full, v_full = pools
    T, page, M, W = tokens.shape[0], k_full.shape[3], tables["full"].shape[0], cfg.sliding_window
    ring = -(-W // page) + 1  # pages that hold a window wherever it starts
    heads, width = cfg.kv_heads
    live = (tokens >= 0)[None]  # [1, T]
    n_new = jnp.sum(live, axis=1, dtype=jnp.int32)
    positions = start + jnp.arange(T)
    x = params["embedding"][jnp.maximum(tokens, 0)][None].astype(cfg.dtype)
    lam_win, _, _ = _lam_inits(cfg)
    state, tail = jnp.where(start == 0, 0, state), jnp.where(start == 0, 0, tail)
    writes = live & (positions < capacity)[None]
    win_pages, win_offs = _targets(tables["window"][None], positions[None], writes, page, k_win.shape[1] - 1)
    full_pages, full_offs = _targets(tables["full"][None], positions[None], writes, page, k_full.shape[1] - 1)
    # the window's old keys: ``ring`` pages from the one that holds start - W
    blk = jnp.maximum(start - W, 0) // page + jnp.arange(ring)
    old_ids = tables["window"][jnp.minimum(blk, M - 1)]
    old_pos = (blk[:, None] * page + jnp.arange(page)[None, :]).reshape(ring * page)
    k_pos = jnp.concatenate([old_pos, positions])
    k_ok = jnp.concatenate([old_pos < start, live[0]])
    mask = (k_ok[None, :] & (k_pos[None, :] <= positions[:, None]) & (k_pos[None, :] > positions[:, None] - W))[None]

    def old(pool: jnp.ndarray, layer: Any) -> jnp.ndarray:  # the layer's pages -> [1, ring*page, heads, width]
        return pool[layer, old_ids].transpose(0, 2, 1, 3).reshape(1, ring * page, heads, width)

    def body(carry: tuple, xs: tuple) -> tuple:
        x, kp, vp = carry
        lp, lam_init, s, t, i = xs
        x, _, s, t = _mamba(cfg, x, lp["mamba"], s[None], t[None], live, n_new)
        a = lp["attn"]
        q, k, v = _qkv(cfg, layer_norm(x, a["ln1_w"], a["ln1_b"], cfg.norm_eps), a)
        attn = _masked_attention(q, jnp.concatenate([old(kp, i), k], axis=1), jnp.concatenate([old(vp, i), v], axis=1),
                                 mask, _scale(cfg))
        kp = _write_rows(kp, i, win_pages[0], win_offs[0], k[0])
        vp = _write_rows(vp, i, win_pages[0], win_offs[0], v[0])
        return (_attn_out(cfg, x, attn, a, lam_init), kp, vp), (s[0], t[0])

    n = cfg.n_pairs
    (x, k_win, v_win), (states, tails) = jax.lax.scan(
        body, (x, k_win, v_win), (params["pairs"], lam_win, state[:n], tail[:n], jnp.arange(n)))
    x, y, s, t = _mamba(cfg, x, params["mid"]["mamba"], state[n][None], tail[n][None], live, n_new)
    lp = params["mid"]["attn"]
    _, k, v = _qkv(cfg, layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps), lp)
    k_full = _write_rows(k_full, 0, full_pages[0], full_offs[0], k[0])
    v_full = _write_rows(v_full, 0, full_pages[0], full_offs[0], v[0])
    return ((k_win, v_win, k_full, v_full), jnp.concatenate([states, s]), jnp.concatenate([tails, t]),
            _last(x, n_new)[0], _last(y, n_new)[0])


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_chunk_paged(
    cfg: Phi4FlashConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, T] the next prompt tokens of each row (-1 pads)
    k_pool: dict,  # donated
    v_pool: dict,  # donated
    block_tables: dict,  # {"window", "full"}: [B, M]
    start_len: jnp.ndarray,  # [B] resident length BEFORE the chunk; 0 starts a prompt from a zero state
    active: jnp.ndarray,  # [B]
    kv_capacity: jnp.ndarray,  # [B] tokens covered by owned pages
    finish: jnp.ndarray | None = None,  # [B] bool: the chunk completes the row's prompt (None: any may)
) -> tuple[jnp.ndarray, dict, dict]:
    """A chunk of T prompt tokens a row against the pools and the state,
    with ``llama.decode_chunk_paged``'s arguments. The self-decoder and
    the full layer's K and V run ONE ROW AT A TIME under a ``cond``
    (:func:`_chunk_row`): a row without a chunk runs nothing and keeps its
    state, so a dispatch costs its live rows. Everything above runs once,
    on each row's last position, and only if a row of ``finish`` needs it.
    Returns (logits [B, 1, V] at that position — zeros if no row finishes
    — k_pool, v_pool)."""
    B, T = tokens.shape
    st = k_pool["state"]

    def row(carry: tuple, xs: tuple) -> tuple:
        pools, states, tails = carry
        toks, start, act, cap, table, b = xs
        state = jax.lax.dynamic_index_in_dim(states, b, 1, keepdims=False)
        tail = jax.lax.dynamic_index_in_dim(tails, b, 1, keepdims=False)

        def run(pools: tuple) -> tuple:
            return _chunk_row(cfg, params, toks, start, cap, table, pools, state, tail)

        def skip(pools: tuple) -> tuple:
            return (pools, state, tail, jnp.zeros((cfg.d_model,), cfg.dtype), jnp.zeros((cfg.d_inner,), jnp.float32))

        pools, state, tail, x, y = jax.lax.cond(act, run, skip, pools)
        return (pools, jax.lax.dynamic_update_index_in_dim(states, state, b, 1),
                jax.lax.dynamic_update_index_in_dim(tails, tail, b, 1)), (x, y)

    pools = (k_pool["window"], v_pool["window"], k_pool["full"], v_pool["full"])
    ((k_win, v_win, k_full, v_full), states, tails), (x, y) = jax.lax.scan(
        row, (pools, st["ssm"], st["conv"]),
        (tokens, start_len, active, kv_capacity, block_tables, jnp.arange(B)))
    n_new = jnp.sum(active[:, None] & (tokens >= 0), axis=1, dtype=jnp.int32)
    new_len = jnp.maximum(start_len + n_new, 1)  # an idle row is read as one of length 1

    def attend(q: jnp.ndarray) -> jnp.ndarray:
        return paged_decode_attention(q, k_full, v_full, block_tables["full"], new_len, scale=_scale(cfg), layer=0)

    needed = jnp.any(active) if finish is None else jnp.any(finish & active)
    logits = jax.lax.cond(needed, lambda: _upper(cfg, params, x, y, attend),
                          lambda: jnp.zeros((B, cfg.vocab_size), jnp.float32))
    stored = {"window": k_win, "full": k_full, "state": {"ssm": states, "conv": tails}}
    return logits[:, None], stored, {"window": v_win, "full": v_full}
