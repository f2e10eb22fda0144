"""Llama-family decoder-only transformer (flagship model).

Pure-functional JAX, TPU-first:
- stacked layer params scanned with ``lax.scan`` → one compiled layer body,
  flat compile time in depth;
- GQA attention ([B,S,H,D] layout, f32 softmax), RoPE, SwiGLU MLP, RMSNorm;
- bf16 weights/activations, f32 accumulation (``preferred_element_type``);
- dense per-request KV cache (paged cache lives in serving/kv_cache.py);
- sharding-agnostic: weights carry no mesh references — ShardingRules
  (parallel/sharding.py) place them, XLA inserts the ICI collectives.

Shapes follow Llama-3: 8B = 32L/32H/8KV/4096d/14336ff/128256V,
70B = 80L/64H/8KV/8192d/28672ff (BASELINE.json configs[2]/[4]).

The ``donate_argnums`` on every prefill/decode jit here are a contract
with the serving engine: the caller rebinds the donated cache/pool from
the call's results in the same statement. shardcheck enforces that
tree-wide (``use-after-donation``, docs/static-analysis.md).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from gofr_tpu.ops.attention import attention, decode_attention
from gofr_tpu.ops.flash_attention import flash_attention
from gofr_tpu.ops.norms import rms_norm
from gofr_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_kv_append,
)
from gofr_tpu.ops.rope import apply_rope, rope_table


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    # "auto" → Pallas flash-attention for prefill when shapes tile cleanly
    # (seq multiple of 128); "dense" / "flash" force a path; "cp" → context-
    # parallel ring/Ulysses attention under an ambient cp_context(mesh).
    attn_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    # -- presets ---------------------------------------------------------------
    @classmethod
    def llama3_8b(cls, **kw: Any) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama3_70b(cls, **kw: Any) -> "LlamaConfig":
        return cls(
            d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8, d_ff=28672, **kw
        )

    @classmethod
    def tiny(cls, **kw: Any) -> "LlamaConfig":
        """Test-size config: runs on CPU in milliseconds."""
        defaults = dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=128, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)


def init_params(cfg: LlamaConfig, key: jax.Array, quantize: bool = False) -> dict:
    """Random-init params pytree with stacked layers [L, ...].

    ``quantize=True`` emits each matmul weight already in the weight-only
    int8 form (``{"q": int8, "s": f32}``, see :func:`quantize_weight`) so
    peak HBM during init is the int8 total plus ONE dtype-sized leaf
    transient — an 8B-class model inits on a single 16 GB v5e chip where
    a full-bf16 init (16 GB resident before quantizing) cannot.
    """
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def winit(key: jax.Array, shape: tuple, fan_in: int) -> jnp.ndarray:
        # generate directly in target dtype: a f32 intermediate for a
        # [L, D, F] leaf is a 7.5 GB transient at 8B scale
        return jax.random.normal(key, shape, cfg.dtype) / math.sqrt(fan_in)

    def mm_weight(key: jax.Array, shape: tuple, fan_in: int):
        w = winit(key, shape, fan_in)
        return quantize_weight(w, axis=-2, donate=True) if quantize else w

    ks = jax.random.split(k_layers, 7)
    params: dict = {
        "embedding": winit(k_embed, (cfg.vocab_size, D), D),
        "layers": {
            "wq": mm_weight(ks[0], (L, D, H * Dh), D),
            "wk": mm_weight(ks[1], (L, D, Hkv * Dh), D),
            "wv": mm_weight(ks[2], (L, D, Hkv * Dh), D),
            "wo": mm_weight(ks[3], (L, H * Dh, D), H * Dh),
            "w_gate": mm_weight(ks[4], (L, D, F), D),
            "w_up": mm_weight(ks[5], (L, D, F), D),
            "w_down": mm_weight(ks[6], (L, F, D), F),
            "attn_norm": jnp.ones((L, D), jnp.float32),
            "mlp_norm": jnp.ones((L, D), jnp.float32),
        },
        "final_norm": jnp.ones((D,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = mm_weight(k_head, (D, cfg.vocab_size), D)
    return params


def param_count(params: dict) -> int:
    # scales are metadata, not model parameters
    return sum(
        int(p.size)
        for path, p in jax.tree_util.tree_leaves_with_path(params)
        if not (path and getattr(path[-1], "key", None) == "s")
    )


def param_bytes(params: dict) -> int:
    """Resident bytes of the weight pytree (int8 q + f32 s counted as-is)."""
    return sum(int(p.size) * p.dtype.itemsize for p in jax.tree.leaves(params))


# what serving/batch.model_of asks of a served module beside its programs
def step_stats_len(cfg: LlamaConfig) -> int:
    """int32 counters a paged decode step returns after the pools: none."""
    return 0


def page_shapes(cfg: Any, page_size: int) -> tuple[tuple, tuple]:
    """What a page of each of the engine's two pools holds, [heads, page,
    width]: K of every KV head in the first, V in the second."""
    shape = (cfg.n_kv_heads, page_size, cfg.head_dim)
    return shape, shape


def unserved(engine_config: Any, lora: Any, cfg: Any = None) -> str | None:
    """What an engine asks for that this model has no program for: nothing."""
    return None


# ------------------------------------------------------- weight-only int8
def _quantize_body(w: jnp.ndarray, axis: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    # jitted (below) so XLA fuses abs/div/round/clip/convert into one pass
    # that streams w once and writes int8 — the eager version materializes
    # TWO full-leaf f32 transients (15 GB for a [32,4096,14336] leaf),
    # OOMing the 8B init on a 16 GB chip
    amax = jnp.max(jnp.abs(w).astype(jnp.float32), axis=axis, keepdims=True)
    s = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(s, axis)


_quantize_jit = jax.jit(_quantize_body, static_argnums=1)
# init-path variant: the freshly-generated source leaf is a temp, so it is
# donated and XLA reuses its buffer
_quantize_jit_donate = jax.jit(_quantize_body, static_argnums=1, donate_argnums=0)


def quantize_weight(w: jnp.ndarray, axis: int = -2, *, donate: bool = False) -> dict:
    """Symmetric per-output-channel weight-only int8: ``axis`` is the
    contraction (input) axis; returns ``{"q": int8 same-shape, "s": f32
    per-output-channel}``. The matmul dequantizes on the fly (``_mm``) —
    XLA fuses the int8→bf16 convert into the dot read, so HBM streams
    int8 bytes. Accuracy is the standard W8 recipe (per-channel absmax);
    the scale multiply rides the matmul epilogue. ``donate=True``
    invalidates ``w`` (init path: the source leaf is a temp)."""
    fn = _quantize_jit_donate if donate else _quantize_jit
    q, s = fn(w, axis % w.ndim)
    return {"q": q, "s": s}


def quantize_params(params: dict) -> dict:
    """Quantize every matmul weight of an existing (small enough to be
    resident) params tree; embedding and norms stay in model dtype."""
    layers = {
        k: (quantize_weight(v, axis=-2) if k in _QUANT_KEYS and not isinstance(v, dict) else v)
        for k, v in params["layers"].items()
    }
    out = dict(params, layers=layers)
    if "lm_head" in params and not isinstance(params["lm_head"], dict):
        out["lm_head"] = quantize_weight(params["lm_head"], axis=-2)
    return out


_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _mm(x: jnp.ndarray, w) -> jnp.ndarray:
    """Matmul against a maybe-quantized weight (plain array or the
    ``{"q", "s"}`` int8 dict). Dequant is fused into the dot by XLA; the
    per-output-channel scale is applied to the f32-accumulated result."""
    if isinstance(w, dict):
        y = jnp.matmul(x, w["q"].astype(x.dtype), preferred_element_type=jnp.float32)
        return (y * w["s"]).astype(x.dtype)
    return x @ w


# ---------------------------------------------------------------- KV cache
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Dense KV cache: [L, B, S_max, Hkv, Dh] per k/v, in the model's
    ``dtype``. The form ``prefill`` returns its slabs in; the serving
    layer's paged cache (serving/kv_cache.py) converts to/from this layout
    for the model step functions."""

    k: jnp.ndarray
    v: jnp.ndarray

    @classmethod
    def create(cls, cfg: LlamaConfig, batch: int, max_len: int | None = None) -> "KVCache":
        S = max_len or cfg.max_seq_len
        shape = (cfg.n_layers, batch, S, cfg.n_kv_heads, cfg.head_dim)
        return cls(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


# ---------------------------------------------------------------- layer body
def _qkv_products(
    h: jnp.ndarray,  # [..., D] normed hidden state
    lp: dict,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The three projections as plain ``[rows, D] x [D, N]`` products —
    ``[..., H*Dh]``, ``[..., Hkv*Dh]``, ``[..., Hkv*Dh]`` — handed on
    together through an optimization barrier; the caller reshapes to heads
    and applies RoPE after it.

    Without the barrier XLA folds the reshape to ``[B, S, heads, Dh]`` INTO
    the product: the dot's weight operand becomes a view ``s8[heads, Dh,
    D]``, which needs the stack with the contraction axis minor
    (``{1,2,0}``). A program that scans the layers then copies each whole
    ``wq``/``wk``/``wv`` stack to that layout once a dispatch (``copy.30``,
    ``copy.44``: 0.75 and 1.4 GiB of temporaries at the 7B cells' sizes) and
    in every layer first writes the layer's slice out
    (``constant_dynamic-slice_fusion.*``) and only then multiplies it — two
    passes where ``wo``, of ``wq``'s size, streams from HBM into its
    ``convolution`` once through a bitcast of the slice. Behind the barrier
    q, k and v take ``wo``'s form (PERF.md §6, PR 38;
    ``tests/test_paged_append.py`` holds the compiled text)."""
    return jax.lax.optimization_barrier(
        (_mm(h, lp["wq"]), _mm(h, lp["wk"]), _mm(h, lp["wv"]))
    )


def _qkv(
    cfg: LlamaConfig,
    x: jnp.ndarray,  # [B, S, D]
    lp: dict,
    sin: jnp.ndarray,
    cos: jnp.ndarray,
    positions: jnp.ndarray,  # [B, S]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared layer preamble: attn-norm + QKV projections + RoPE.
    Returns (h_normed, q, k, v)."""
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv_products(h, lp)
    q = apply_rope(q.reshape(B, S, H, Dh), positions, sin, cos)
    k = apply_rope(k.reshape(B, S, Hkv, Dh), positions, sin, cos)
    v = v.reshape(B, S, Hkv, Dh)
    return h, q, k, v


def _attn_mlp_epilogue(
    cfg: LlamaConfig, x: jnp.ndarray, lp: dict, attn: jnp.ndarray
) -> jnp.ndarray:
    """Shared layer epilogue: attn output projection + SwiGLU MLP."""
    B, S, _ = x.shape
    x = x + _mm(attn.reshape(B, S, cfg.n_heads * cfg.head_dim), lp["wo"])
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    gate = jax.nn.silu(_mm(h, lp["w_gate"]).astype(jnp.float32)).astype(h.dtype)
    return x + _mm(gate * _mm(h, lp["w_up"]), lp["w_down"])


def _layer(
    cfg: LlamaConfig,
    x: jnp.ndarray,  # [B, S, D]
    lp: dict,  # per-layer params (leading L axis stripped by scan)
    sin: jnp.ndarray,
    cos: jnp.ndarray,
    positions: jnp.ndarray,  # [B, S] absolute positions
) -> jnp.ndarray:
    """Cache-less layer (training/forward path). The cached prefill/decode
    modes live in _layer_cached, which carries the stacked KV cache."""
    _, q, k, v = _qkv(cfg, x, lp, sin, cos, positions)

    if cfg.attn_impl == "cp":
        # long-context path: seq axis sharded on the sp mesh axis, ring
        # or Ulysses attention per the ambient cp_context (§5.7)
        from gofr_tpu.parallel.context_parallel import cp_attention

        attn = cp_attention(q, k, v)
    else:
        attn = attention(q, k, v, causal=True, kv_len=None)
    return _attn_mlp_epilogue(cfg, x, lp, attn)


def _layer_cached(
    cfg: LlamaConfig,
    x: jnp.ndarray,  # [B, S, D]
    lp: dict,  # per-layer params (leading L axis stripped by scan)
    layer: jnp.ndarray,  # scalar layer index (traced)
    sin: jnp.ndarray,
    cos: jnp.ndarray,
    positions: jnp.ndarray,  # [B, S]
    k_all: jnp.ndarray,  # [L, B, S_max, Hkv, Dh] — FULL stacked cache
    v_all: jnp.ndarray,
    cache_len: jnp.ndarray,  # [B] length AFTER writing current tokens
    mode: str,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Layer body for the cached modes, carrying the WHOLE stacked cache.

    Scanning the cache as xs/ys (the obvious formulation) makes XLA slice
    layer caches out, restack them, and take two full-cache copies per
    step — profiled at ~15 ms of a 25 ms decode step at B=256. Keeping
    the stacked cache in the scan *carry* and doing per-layer indexed
    in-place updates leaves it resident in HBM: per step the only cache
    traffic is the attention read plus a one-token scatter."""
    B, S, _ = x.shape
    _, q, k, v = _qkv(cfg, x, lp, sin, cos, positions)

    if mode == "prefill":
        # fill layer `layer`'s slab in place; attention runs on the fresh
        # k/v directly (no cache read-back needed during prefill)
        k_all = jax.lax.dynamic_update_slice(k_all, k[None], (layer, 0, 0, 0, 0))
        v_all = jax.lax.dynamic_update_slice(v_all, v[None], (layer, 0, 0, 0, 0))
        if cfg.attn_impl == "flash" or (cfg.attn_impl == "auto" and S % 128 == 0):
            # compiled kernel on a TPU, ops.attention on the CPU
            # (ops/backend.py)
            attn = flash_attention(q, k, v, cache_len, causal=True)
        else:
            attn = attention(q, k, v, causal=True, kv_len=cache_len)
    else:  # decode: S == 1, one-token scatter at (layer, row, position)
        idx = cache_len - 1  # position just written
        b_idx = jnp.arange(B)
        k_all = k_all.at[layer, b_idx, idx].set(k[:, 0])
        v_all = v_all.at[layer, b_idx, idx].set(v[:, 0])
        kc = jax.lax.dynamic_index_in_dim(k_all, layer, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, layer, 0, keepdims=False)
        attn = decode_attention(q, kc, vc, cache_len)

    return _attn_mlp_epilogue(cfg, x, lp, attn), k_all, v_all


def _run_layers(
    cfg: LlamaConfig,
    params: dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: KVCache | None,
    cache_len: jnp.ndarray | None,
    mode: str,
) -> tuple[jnp.ndarray, KVCache | None]:
    if cfg.attn_impl == "cp" and mode != "prefill_nocache":
        # context-parallel attention covers the no-cache forward path only;
        # failing loudly beats silently serving dense attention when the
        # config asked for O(S/n) memory (serving CP lands with paged KV).
        raise ValueError(
            f"attn_impl='cp' is not supported in mode={mode!r}; "
            "use forward() or a dense/flash attn_impl for prefill/decode"
        )
    sin, cos = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)

    if cache is None:
        def body(h, lp):
            h = _layer(cfg, h, lp, sin, cos, positions)
            return h, None

        x, _ = jax.lax.scan(body, x, params["layers"])
        return x, None

    # cache modes: the stacked cache rides the CARRY (in-place per-layer
    # updates), never the xs/ys path — see _layer_cached's docstring
    def body(carry, xs):
        h, k_all, v_all = carry
        lp, layer = xs
        h, k_all, v_all = _layer_cached(
            cfg, h, lp, layer, sin, cos, positions, k_all, v_all, cache_len, mode
        )
        return (h, k_all, v_all), None

    (x, new_k, new_v), _ = jax.lax.scan(
        body,
        (x, cache.k, cache.v),
        (params["layers"], jnp.arange(cfg.n_layers)),
    )
    return x, KVCache(new_k, new_v)


def _logits(cfg: LlamaConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        head = params["embedding"].T
    else:
        head = params["lm_head"]
        if isinstance(head, dict):
            y = jnp.einsum(
                "bsd,dv->bsv", x, head["q"].astype(x.dtype),
                preferred_element_type=jnp.float32,
            )
            return y * head["s"]
    return jnp.einsum("bsd,dv->bsv", x, head, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- entry points
@partial(jax.jit, static_argnums=(0, 3))
def _forward_jit(
    cfg: LlamaConfig, params: dict, tokens: jnp.ndarray, _cp_key: Any
) -> jnp.ndarray:
    B, S = tokens.shape
    x = params["embedding"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _ = _run_layers(cfg, params, x, positions, None, None, "prefill_nocache")
    return _logits(cfg, params, x)


def forward(cfg: LlamaConfig, params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    """Plain causal forward (no cache): [B, S] -> logits [B, S, V].
    The graft entry / training-style step.

    For attn_impl="cp" the ambient cp_context (mesh, axis, impl) joins the
    jit cache key — a context switch retraces instead of silently reusing
    the collectives compiled for a previous mesh.
    """
    cp_key = None
    if cfg.attn_impl == "cp":
        from gofr_tpu.parallel.context_parallel import current_cp

        cp_key = current_cp()
        if cp_key is None:
            raise RuntimeError("attn_impl='cp' requires an enclosing cp_context(mesh)")
    return _forward_jit(cfg, params, tokens, cp_key)


@partial(jax.jit, static_argnums=0, donate_argnums=(3,))
def prefill(
    cfg: LlamaConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, S] right-padded
    cache: KVCache,
    seq_lens: jnp.ndarray,  # [B] true lengths
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill: fill the cache, return last-token logits [B, V]."""
    B, S = tokens.shape
    x = params["embedding"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, cache = _run_layers(cfg, params, x, positions, cache, seq_lens, "prefill")
    # gather last hidden state BEFORE the lm_head: computing [B, S, V]
    # logits just to slice one position wastes 2·B·S·D·V flops and a
    # B·S·V f32 temp (6.3 GB at B=384, S=128, V=32k — an OOM at serving
    # batch sizes)
    last_h = jnp.take_along_axis(x, (seq_lens - 1)[:, None, None], axis=1)  # [B,1,D]
    last = _logits(cfg, params, last_h)[:, 0]  # [B, V]
    return last, cache


@partial(jax.jit, static_argnums=0, donate_argnums=(3,))
def decode_step(
    cfg: LlamaConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B] last sampled token per row
    cache: KVCache,
    cache_len: jnp.ndarray,  # [B] length including this token's position
) -> tuple[jnp.ndarray, KVCache]:
    """One decode step: [B] -> logits [B, V], cache updated in place
    (donated)."""
    B = tokens.shape[0]
    x = params["embedding"][tokens][:, None, :].astype(cfg.dtype)  # [B, 1, D]
    positions = (cache_len - 1)[:, None]  # [B, 1]
    x, cache = _run_layers(cfg, params, x, positions, cache, cache_len, "decode")
    logits = _logits(cfg, params, x)[:, 0]  # [B, V]
    return logits, cache


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_step_paged(
    cfg: LlamaConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B] last sampled token per row
    k_pool: jnp.ndarray,  # [L, N_pages, Hkv, page, Dh] donated
    v_pool: jnp.ndarray,  # donated
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B] length INCLUDING this token's position
    active: jnp.ndarray,  # [B] bool — inactive rows must not write live pages
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step over the paged KV pool (serving/kv_cache.py):
    appends this step's K/V into each active row's current page slot and
    attends through the block tables (ops/paged_attention.py). Inactive
    rows write into the pool's LAST page (the trash page the cache manager
    reserves) so the append never touches a live page, and their
    attention output is garbage the host ignores.

    The pools ride the layer scan as CARRY and are written only by the
    append kernel aliased over them (``paged_kv_append``) and read whole by
    the attention kernel, each given the layer's index: XLA never slices,
    scatters into or copies a pool. Any XLA op that writes one token into
    a pool makes layout assignment swap the pool's KV-head and page axes,
    and the pool is then transposed on entry, around every kernel call and
    on exit — more than half of a decode step (PERF.md §6, PR 30).

    The q, k and v products come through ``_qkv_products`` and are reshaped
    to heads after its barrier. Written ``_mm(hn, lp["wq"]).reshape(B, 1, H,
    Dh)`` the reshape is folded into the product, and the block then copies
    the three weight stacks to another layout on entry (``copy.*`` of
    ``s8[L, D, N]{1,2,0}``) and reads every layer's slice twice
    (``constant_dynamic-slice_fusion.*``, then the product): a fifth of a
    step at 32 query and 32 KV heads, a ninth at 32 over 8 (PERF.md §6,
    PR 38)."""
    B = tokens.shape[0]
    page = k_pool.shape[3]
    trash_page = k_pool.shape[1] - 1  # reserved by PagedKVCache
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embedding"][tokens][:, None, :].astype(cfg.dtype)  # [B, 1, D]
    pos = jnp.maximum(seq_lens - 1, 0)  # [B]
    positions = pos[:, None]
    sin, cos = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    b_idx = jnp.arange(B)
    # each active row's decode position is a (page, offset) of its own
    pages = jnp.where(active, block_tables[b_idx, pos // page], trash_page)  # [B]
    offsets = jnp.where(active, pos % page, 0)

    def body(carry, xs):
        h, kp, vp = carry  # kp/vp: the whole pools
        lp, layer = xs
        hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv_products(hn, lp)  # [B, 1, heads * Dh], not yet by heads
        q = apply_rope(q.reshape(B, 1, H, Dh), positions, sin, cos)[:, 0]  # [B, H, Dh]
        k = apply_rope(k.reshape(B, 1, Hkv, Dh), positions, sin, cos)[:, 0]  # [B, Hkv, Dh]
        v = v.reshape(B, Hkv, Dh)

        # Mosaic kernels on a TPU, scatter and gather references on the CPU
        kp, vp = paged_kv_append(kp, vp, k, v, layer, pages, offsets)
        attn = paged_decode_attention(q, kp, vp, block_tables, seq_lens, layer=layer)

        h = h + _mm(attn.reshape(B, 1, H * Dh), lp["wo"])
        hn = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(_mm(hn, lp["w_gate"]).astype(jnp.float32)).astype(hn.dtype)
        h = h + _mm(gate * _mm(hn, lp["w_up"]), lp["w_down"])
        return (h, kp, vp), None

    (x, k_pool, v_pool), _ = jax.lax.scan(
        body, (x, k_pool, v_pool), (params["layers"], jnp.arange(cfg.n_layers))
    )
    logits = _logits(cfg, params, x)[:, 0]  # [B, V]
    return logits, k_pool, v_pool


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_step_greedy(
    cfg: LlamaConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B] last sampled token per row
    cache: KVCache,
    cache_len: jnp.ndarray,  # [B] length BEFORE this token's position
) -> tuple[jnp.ndarray, KVCache, jnp.ndarray]:
    """Fused decode step: forward + greedy argmax + length increment in ONE
    dispatch. On hardware where every executable launch pays a host→device
    round trip (PJRT over a proxy; multi-host controllers), folding the
    3-dispatch sequence (len+1, forward, argmax) into one call is worth
    milliseconds per token — this is the serving/bench hot path."""
    cache_len = cache_len + 1
    logits, cache = decode_step.__wrapped__(cfg, params, tokens, cache, cache_len)
    return jnp.argmax(logits, axis=-1), cache, cache_len


@partial(jax.jit, static_argnums=(0, 5), donate_argnums=(3,))
def decode_loop_greedy(
    cfg: LlamaConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B] last sampled token per row
    cache: KVCache,
    cache_len: jnp.ndarray,  # [B] length BEFORE the first new position
    n_steps: int,
) -> tuple[jnp.ndarray, KVCache, jnp.ndarray, jnp.ndarray]:
    """``n_steps`` greedy decode steps fused into ONE dispatch via
    ``lax.scan``. Useful when launches CANNOT be pipelined (e.g. the host
    must observe each token, or a strict one-outstanding-dispatch PJRT
    proxy); when the caller can keep the dispatch queue full, the
    per-step ``decode_step_greedy`` loop measures slightly faster (the
    bench uses that). Returns (last_token, cache, cache_len,
    tokens [B, n_steps])."""

    def body(carry, _):
        tokens, cache, cache_len = carry
        tokens, cache, cache_len = decode_step_greedy.__wrapped__(
            cfg, params, tokens, cache, cache_len
        )
        return (tokens, cache, cache_len), tokens

    (tokens, cache, cache_len), toks = jax.lax.scan(
        body, (tokens, cache, cache_len), None, length=n_steps
    )
    return tokens, cache, cache_len, jnp.transpose(toks)  # [B, n_steps]


@partial(jax.jit, static_argnums=0, donate_argnums=(3,))
def decode_chunk(
    cfg: LlamaConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, T] chunk: (last committed token, drafts...)
    cache: KVCache,  # dense bf16 cache (donated)
    start_len: jnp.ndarray,  # [B] committed length BEFORE the chunk
) -> tuple[jnp.ndarray, KVCache]:
    """Verify-forward for speculative decoding: run T tokens in ONE
    dispatch against the cache, writing their K/V at rows
    [start, start+T) and attending causally over prefix+chunk (per-row
    ``q_offset``). Returns logits [B, T, V]; position i's logits predict
    the token AFTER chunk token i. KV written past the eventually
    accepted prefix is garbage the cache-length gating never reads —
    rejection is just "don't advance cache_len", no rollback."""
    B, T = tokens.shape
    positions = start_len[:, None] + jnp.arange(T)[None, :]  # [B, T]
    # chunk tails may be draft padding (-1): embed/scatter them safely —
    # .at[].set drops out-of-bounds rows, the embedding gather clamps
    safe_tokens = jnp.maximum(tokens, 0)
    x = params["embedding"][safe_tokens].astype(cfg.dtype)
    sin, cos = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    b_rows = jnp.arange(B)[:, None]

    def body(carry, xs):
        h, k_all, v_all = carry
        lp, layer = xs
        _, q, k, v = _qkv(cfg, h, lp, sin, cos, positions)
        k_all = k_all.at[layer, b_rows, positions].set(k)
        v_all = v_all.at[layer, b_rows, positions].set(v)
        kc = jax.lax.dynamic_index_in_dim(k_all, layer, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, layer, 0, keepdims=False)
        attn = attention(
            q, kc, vc, causal=True, q_offset=start_len, kv_len=start_len + T
        )
        h = _attn_mlp_epilogue(cfg, h, lp, attn)
        return (h, k_all, v_all), None

    (x, new_k, new_v), _ = jax.lax.scan(
        body, (x, cache.k, cache.v), (params["layers"], jnp.arange(cfg.n_layers))
    )
    return _logits(cfg, params, x), KVCache(new_k, new_v)


def _paged_chunk_targets(
    k_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, M]
    positions: jnp.ndarray,  # [B, T] absolute write positions
    active: jnp.ndarray,  # [B]
    kv_capacity: jnp.ndarray,  # [B] tokens covered by OWNED pages
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(page, offset) targets for a chunk write. Positions beyond a row's
    owned capacity — or on inactive rows — go to the trash page: table
    entries past the owned prefix read 0, and page 0 is LIVE, so an
    unmasked overflow write would corrupt another sequence's KV."""
    page = k_pool.shape[3]
    trash = k_pool.shape[1] - 1
    M = block_tables.shape[1]
    valid = active[:, None] & (positions < kv_capacity[:, None])
    slot_idx = jnp.minimum(positions // page, M - 1)
    pages = jnp.where(
        valid, jnp.take_along_axis(block_tables, slot_idx, axis=1), trash
    )
    offsets = jnp.where(valid, positions % page, 0)
    return pages, offsets


def _paged_gather(
    pool: jnp.ndarray,  # [N+1, Hkv, page, Dh] one layer's pool
    block_tables: jnp.ndarray,  # [B, M]
) -> jnp.ndarray:
    """Gather a row's pages into contiguous [B, M*page, Hkv, Dh] for the
    chunk-verify attention (XLA-gather reference path: verify chunks are
    a small, latency-tolerant fraction of decode traffic)."""
    g = pool[block_tables]  # [B, M, Hkv, page, Dh]
    B, M, Hkv, page, Dh = g.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(B, M * page, Hkv, Dh)


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_chunk_paged(
    cfg: LlamaConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, T] chunk: (last committed token, drafts...)
    k_pool: jnp.ndarray,  # [L, N+1, Hkv, page, Dh] donated
    v_pool: jnp.ndarray,  # donated
    block_tables: jnp.ndarray,  # [B, M]
    start_len: jnp.ndarray,  # [B] committed length BEFORE the chunk
    active: jnp.ndarray,  # [B]
    kv_capacity: jnp.ndarray,  # [B] tokens covered by owned pages
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paged twin of :func:`decode_chunk`: verify T tokens in one dispatch
    against the page pool, writing chunk K/V through the block tables
    (overflow → trash page) and attending over gathered pages with per-row
    ``q_offset``. Returns (logits [B, T, V], k_pool, v_pool)."""
    B, T = tokens.shape
    positions = start_len[:, None] + jnp.arange(T)[None, :]
    pages, offsets = _paged_chunk_targets(
        k_pool, block_tables, positions, active, kv_capacity
    )
    x = params["embedding"][jnp.maximum(tokens, 0)].astype(cfg.dtype)
    sin, cos = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)

    def body(h, xs):
        lp, kc, vc = xs
        _, q, k, v = _qkv(cfg, h, lp, sin, cos, positions)
        kc = kc.at[pages, :, offsets].set(k)
        vc = vc.at[pages, :, offsets].set(v)
        kg = _paged_gather(kc, block_tables)
        vg = _paged_gather(vc, block_tables)
        attn = attention(
            q, kg, vg, causal=True, q_offset=start_len, kv_len=start_len + T
        )
        h = _attn_mlp_epilogue(cfg, h, lp, attn)
        return h, (kc, vc)

    x, (k_pool, v_pool) = jax.lax.scan(body, x, (params["layers"], k_pool, v_pool))
    return _logits(cfg, params, x), k_pool, v_pool


def _prompt_lookup_draft(context: list[int], ngram: int, draft_len: int) -> list[int]:
    """Prompt-lookup drafting: find the most recent earlier occurrence of
    the context's last ``ngram`` tokens and propose what followed it."""
    if len(context) <= ngram:
        return []
    suffix = context[-ngram:]
    # scan right-to-left, excluding the suffix occurrence itself
    for start in range(len(context) - ngram - 1, -1, -1):
        if context[start : start + ngram] == suffix:
            cont = context[start + ngram : start + ngram + draft_len]
            if cont:
                return cont
    return []


def speculative_generate(
    cfg: LlamaConfig,
    params: dict,
    prompt: jnp.ndarray,  # [B, S] right-padded
    seq_lens: jnp.ndarray,
    max_new_tokens: int,
    *,
    draft_len: int = 8,
    ngram: int = 2,
) -> tuple[jnp.ndarray, dict]:
    """Greedy generation with prompt-lookup speculative decoding
    (assisted generation / PLD): draft tokens by matching the last
    n-gram earlier in the context, verify the whole draft in ONE
    :func:`decode_chunk` dispatch, and commit the longest prefix that
    greedy decoding would have produced — LOSSLESS: the output equals
    plain :func:`greedy_generate` token for token, but repetitive text
    (code, quotes, structured data) commits several tokens per forward.
    Returns ([B, max_new_tokens] ids — exactly max_new_tokens live
    tokens per row, like greedy_generate; EOS handling is the caller's
    concern — and stats {"forwards", "tokens"}). The chunk width is
    static, so exactly one extra executable compiles."""
    import numpy as np

    B, S = prompt.shape
    T = draft_len + 1  # chunk = committed last token + up to draft_len drafts
    cache = KVCache.create(cfg, B, max_len=S + max_new_tokens + T + 1)
    logits, cache = prefill(cfg, params, prompt, cache, seq_lens)
    last = jnp.argmax(logits, axis=-1)

    prompt_np = np.asarray(prompt)
    lens_np = np.asarray(seq_lens)
    context = [list(prompt_np[b, : lens_np[b]]) for b in range(B)]
    out: list[list[int]] = [[] for _ in range(B)]
    last_np = np.asarray(last)
    for b in range(B):
        out[b].append(int(last_np[b]))
        context[b].append(int(last_np[b]))

    cache_len = lens_np.copy()  # committed length (last token NOT yet in cache)
    forwards = 1  # prefill
    while min(len(o) for o in out) < max_new_tokens:
        chunk = np.zeros((B, T), np.int32)
        k_row = np.zeros(B, np.int32)
        for b in range(B):
            chunk[b, 0] = context[b][-1]
            draft = _prompt_lookup_draft(context[b], ngram, draft_len)
            k_row[b] = len(draft)
            for i, d in enumerate(draft):
                chunk[b, 1 + i] = d
        logits, cache = decode_chunk(
            cfg, params, jnp.asarray(chunk), cache, jnp.asarray(cache_len)
        )
        forwards += 1
        greedy = np.asarray(jnp.argmax(logits, axis=-1))  # [B, T]
        for b in range(B):
            if len(out[b]) >= max_new_tokens:
                cache_len[b] += 1  # keep the row's committed token in cache
                continue
            a = 0
            while a < k_row[b] and greedy[b, a] == chunk[b, 1 + a]:
                a += 1
            new_tokens = [int(t) for t in chunk[b, 1 : 1 + a]] + [int(greedy[b, a])]
            room = max_new_tokens - len(out[b])
            new_tokens = new_tokens[:room]
            out[b].extend(new_tokens)
            context[b].extend(new_tokens)
            # chunk wrote KV for (last + a accepted drafts); the bonus
            # token commits NEXT round as that chunk's position 0
            cache_len[b] += a + 1 if len(new_tokens) == a + 1 else len(new_tokens)

    total = sum(len(o) for o in out)
    result = np.asarray([o[:max_new_tokens] for o in out], np.int64)
    return jnp.asarray(result), {"forwards": forwards, "tokens": total}


def greedy_generate(
    cfg: LlamaConfig,
    params: dict,
    prompt: jnp.ndarray,  # [B, S] right-padded
    seq_lens: jnp.ndarray,
    max_new_tokens: int,
) -> jnp.ndarray:
    """Simple generate loop (serving uses the continuous-batching engine;
    this is the library-level convenience + test oracle). Returns
    [B, max_new_tokens]."""
    B, S = prompt.shape
    cache = KVCache.create(cfg, B, max_len=S + max_new_tokens)
    logits, cache = prefill(cfg, params, prompt, cache, seq_lens)
    tokens = jnp.argmax(logits, axis=-1)
    out = [tokens]
    cache_len = seq_lens
    for _ in range(max_new_tokens - 1):
        cache_len = cache_len + 1
        logits, cache = decode_step(cfg, params, tokens, cache, cache_len)
        tokens = jnp.argmax(logits, axis=-1)
        out.append(tokens)
    return jnp.stack(out, axis=1)
