"""DeepSeek-V3.2 decoder (``model_type`` ``deepseek_v32``): latent
attention (MLA) under a learned sparse selection (the lightning indexer),
group-limited sigmoid routing over sparse experts beside a shared one,
leading dense layers, an untied head — and, with no indexer
(``index_topk`` 0) and plain rotary frequencies, the DeepSeek-V3 layer
that ``joyai_llm_flash`` (JoyAI-LLM-Flash) publishes: latent attention
over EVERY position. Pre-norm RMSNorm; for a token ``t`` with hidden ``x``:

    c_q  = RMSNorm(x W_qa);  q = c_q W_qb -> heads x (nope | rope), RoPE on rope
    [c_kv | k_r] = x W_kva;  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r)   (CACHED, one for all heads)
    indexer: q^I = c_q W^I_qb (heads x Di);  k^I = LayerNorm(x W^I_k) (CACHED);
             RoPE on the first rope dims of each, the two-halves layout;
             w = x W^I_w / sqrt(heads_I · Di);  I(t,u) = sum_j w_j relu(q^I_j · k^I(u))
    S_t  = the min(index_topk, t+1) positions u <= t with the largest I(t,u)
    expanded (prefill): [k_nope,h | v_h] = c_kv W_kvb;  s_h = (q_nope,h·k_nope,h + q_rope,h·k_r)·scale
    absorbed (decode, chunks): q_lat,h = q_nope,h W_UK,h^T;  s_h = (q_lat,h·c_kv + q_rope,h·k_r)·scale;
             o_h = (sum_u p_h c_kv(u)) W_UV,h            -- the same numbers (ops/mla.py)
    out  = x + concat_h(o_h) W_o;  out = out + FFN(RMSNorm(out))
    FFN  = SwiGLU (the leading dense layers) or the group-limited sigmoid
           gate over the routed experts beside the shared expert
           (``ops/moe.sigmoid_topk_gates`` with bias, groups and scaling)

``scale = (nope + rope)^-1/2 · m^2`` with YaRN's ``m`` and YaRN's blended
rotary frequencies (``ops/rope.yarn_frequencies``); with ``rope_factor`` 1
(no ``rope_scaling``) the frequencies are plain and ``m`` is 1. MLA's RoPE
is on interleaved pairs, the indexer's on the two halves.

A chip may hold a SHARE (``held_experts`` of ``n_experts`` from
``first_expert`` on, ``vocab_size`` rows of embedding and head), as
``models/cohere2_moe.py``: the router scores every published expert and
what the absent experts would add is left out.

What a token caches, per layer: one latent row ``[c_kv | k_r | 0]`` (576
values padded to 640, ``ops/mla.latent_row_width``) and one indexer key.
The engine's two pools hold them (:func:`page_shapes`): ``k_pool`` the
latent rows, ``v_pool`` the indexer keys, under one block table. A decode
step appends with ``ops/paged_attention.paged_kv_append`` and scores the
whole context with ``ops/mla.paged_index_scores``; then it reads the
selection one of two ways, chosen from the slot's shape
(:func:`sparse_read`). Where the selection is a large part of the slot,
the row's owned pages are read whole by
``ops/latent_attention.paged_latent_attention``, a Mosaic call a layer at
the rate of a page DMA, masked to the selection
(``ops/mla.selection_mask``); where it is a small part, only the
selected rows are read, by ``ops/mla.sparse_decode_attention``'s gather
by token. Both attend to the same positions. A chunk of a
prompt runs one row at a time under a ``cond``: rows without a chunk cost
nothing, and a row's scores ([heads, chunk, context]) fit. Its reads,
index scores, selection and attention stop at the pages up to the
chunk's end: the context is bucketed (the chunk's length doubling up to
the slot, :func:`chunk_contexts`, one branch of a ``switch`` each).

Without an indexer ``S_t`` is every position ``u <= t``, a token caches
the latent row alone and the engine keeps ONE pool (:func:`page_shapes`
answers None for the second; ``v_pool`` is None throughout). A decode
step appends with ``paged_kv_append`` and reads every cached row of the
row through ``ops/latent_attention.paged_latent_attention``, one Mosaic
call a layer; it counts the positions read and the live rows (``mla_kv``,
``mla_rows``: :func:`step_stats`). A chunk runs one row at a time
over its bucket of the context as above, and its head runs at the row's
last token.

Not served, each stated in the benchmark configurations' ``assumed``: the
indexer's Hadamard rotation (orthogonal on both sides of a dot product)
and its FP8 (bf16 here); the multi-token-prediction module.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from gofr_tpu.models.llama import _mm, _paged_chunk_targets, quantize_weight
from gofr_tpu.ops import mla
from gofr_tpu.ops.latent_attention import paged_latent_attention
from gofr_tpu.ops.moe import held_experts, sigmoid_topk_gates
from gofr_tpu.ops.norms import layer_norm, rms_norm
from gofr_tpu.ops.paged_attention import paged_kv_append
from gofr_tpu.ops.rope import (
    angles, apply_rope_halves, apply_rope_interleaved, rope_angles, yarn_frequencies, yarn_mscale,
)

__all__ = [
    "DeepseekV32Config", "KVCache", "init_params", "quantize_params", "prefill",
    "decode_step_paged", "decode_chunk_paged", "step_stats_len", "step_stats", "page_shapes", "unserved", "prefill_slabs",
    "chunk_contexts", "sparse_read",
]


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config:
    vocab_size: int = 129280  # rows of embedding and head held here
    d_model: int = 7168
    n_layers: int = 61
    n_dense_layers: int = 3  # leading layers with a dense MLP (first_k_dense_replace)
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64  # the indexer's: all three 0 for a model without one
    index_head_dim: int = 128
    index_topk: int = 2048
    d_ff: int = 18432  # a dense layer's width
    d_ff_expert: int = 2048  # one expert's width
    n_experts: int = 256  # published: the router's outputs
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    n_shared: int = 1
    held_experts: int = 256  # routed experts this chip holds ...
    first_expert: int = 0  # ... from this one on
    max_seq_len: int = 163840
    rope_theta: float = 10000.0
    rope_factor: float = 40.0  # YaRN's; 1 for plain frequencies (no rope_scaling)
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("the leading dense layers are not among the layers")
        if not 0 <= self.first_expert <= self.n_experts - self.held_experts:
            raise ValueError("the held experts are not among the published ones")
        if self.n_experts % self.n_group or (self.index_topk and self.index_head_dim < self.qk_rope_head_dim):
            raise ValueError("the experts do not divide into n_group groups, or the indexer's "
                             "head is narrower than the rotary part")

    # what the pager and the handlers read of any served config
    @property
    def n_kv_heads(self) -> int:
        return 1  # one latent row for all heads

    @property
    def head_dim(self) -> int:
        return self.qk_rope_head_dim  # the family's convention

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.mscale)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @property
    def row_width(self) -> int:
        return mla.latent_row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @classmethod
    def tiny(cls, **kw: Any) -> "DeepseekV32Config":
        """Test size: one dense layer, two expert layers, a selection of
        8 positions."""
        defaults = dict(
            vocab_size=256, d_model=64, n_layers=3, n_dense_layers=1, n_heads=4,
            q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=8, d_ff=128,
            d_ff_expert=32, n_experts=16, top_k=4, n_group=4, topk_group=2, held_experts=16,
            max_seq_len=256, rope_original_max=32, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)


def step_stats(cfg: DeepseekV32Config) -> tuple[str, ...]:
    """Names of the two counters a paged step returns after the experts',
    each summed over rows and layers: the positions the indexer scored and
    the positions attention read; without an indexer the positions
    attention read and the live rows it read them for."""
    return ("dsa_scored", "dsa_selected") if cfg.index_topk else ("mla_kv", "mla_rows")


def step_stats_len(cfg: DeepseekV32Config) -> int:
    """int32 counters a paged decode step returns after the pools: rows
    routed to each held expert, the held experts whose matrices were read
    (``ops/moe.held_experts``), then :func:`step_stats`."""
    return cfg.held_experts + 1 + len(step_stats(cfg))


# the masked read costs each resident position of a row, the gather each
# of index_topk selected ones: on the v5e at 128 heads about 4.4 ns against
# 17.6 (tools/masked_probe.py), so they break even near 4 x index_topk
# resident positions, which no row of a slot of 4 x index_topk passes
_MASKED_READ_SLOTS = 4


def sparse_read(cfg: DeepseekV32Config, slot_positions: int) -> str | None:
    """How a decode step reads the selection from a slot of
    ``slot_positions``: ``masked`` — the row's owned pages whole, through
    the latent kernel under the selection's mask — where the slot holds at
    most 4 x ``index_topk`` positions, else ``gather`` — the selected rows
    alone, a gather by token. None without an indexer. The engine sets it
    on every block's dispatch span as ``dsa_read``."""
    if not cfg.index_topk:
        return None
    return "masked" if slot_positions <= _MASKED_READ_SLOTS * cfg.index_topk else "gather"


def page_shapes(cfg: DeepseekV32Config, page_size: int) -> tuple[tuple, tuple | None]:
    """What a page of each of the engine's two pools holds, [heads, page,
    width]: latent rows in the first, the indexer's keys in the second —
    None, no second pool, without an indexer."""
    return (1, page_size, cfg.row_width), ((1, page_size, cfg.index_head_dim) if cfg.index_topk else None)


def unserved(engine_config: Any, lora: Any, cfg: Any = None) -> str | None:
    """What an engine asks for that this model has no program for, in a
    sentence; None if it can be built."""
    if engine_config.spec_tokens > 0:
        return ("deepseek_v32 has no speculative verify program (its multi-token-prediction "
                "module is not served): set TPU_SPEC_TOKENS=0")
    if lora is not None:
        return "deepseek_v32 serves no LoRA adapters: its head is a slice of the vocabulary"
    if cfg is not None and not cfg.index_topk:
        # one pool: a cached prefix is (logits, latent slab, None), which the
        # host tier and the HTTP handoff carry as arrays alone
        if engine_config.kv_spill_bytes > 0:
            return ("deepseek_v32 without an indexer keeps one pool, and the host spill tier moves K and V "
                    "slab pairs: set TPU_KV_SPILL_BYTES=0")
        if engine_config.role != "unified":
            return ("deepseek_v32 without an indexer keeps one pool, and a prefill replica hands a decode "
                    "replica K and V slab pairs: serve it from unified replicas")
    return None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """The dense form ``prefill`` fills and a bucketed prefill scatters
    into pages: latent rows [L, B, S, 1, W] in ``k``, the indexer's keys
    [L, B, S, 1, Di] in ``v`` (None without an indexer)."""

    k: jnp.ndarray
    v: jnp.ndarray | None

    @classmethod
    def create(cls, cfg: DeepseekV32Config, batch: int, max_len: int | None = None) -> "KVCache":
        S = max_len or cfg.max_seq_len
        return cls(jnp.zeros((cfg.n_layers, batch, S, 1, cfg.row_width), cfg.dtype),
                   jnp.zeros((cfg.n_layers, batch, S, 1, cfg.index_head_dim), cfg.dtype) if cfg.index_topk else None)

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


def prefill_slabs(cache: KVCache) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Row 0 of a prefill's cache, as ``batch.prefill_compute`` returns it
    and the pager's ``write_prefill`` takes it (a V slab of None without
    an indexer)."""
    return jax.tree.map(lambda a: a[:, 0], (cache.k, cache.v))


_ATTN_MATRICES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "idx_wq", "idx_wk")
_FFN_MATRICES = ("w_gate", "w_up", "w_down")


def init_params(cfg: DeepseekV32Config, key: jax.Array) -> dict:
    """Random params: the dense layers stacked [Ld, ...] under ``dense``,
    the expert layers [Lm, ...] under ``moe`` (experts [Lm, held, ...])."""
    D, H = cfg.d_model, cfg.n_heads
    Rq, Rkv, Dn, Dr, Dv = (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                           cfg.qk_rope_head_dim, cfg.v_head_dim)
    Hi, Di = cfg.index_n_heads, cfg.index_head_dim
    ks = iter(jax.random.split(key, 64))

    def w(shape: tuple, fan_in: int, dtype: Any = None) -> jnp.ndarray:
        return jax.random.normal(next(ks), shape, dtype or cfg.dtype) / math.sqrt(fan_in)

    def attention(L: int) -> dict:
        out = {
            "attn_norm": jnp.ones((L, D), jnp.float32), "mlp_norm": jnp.ones((L, D), jnp.float32),
            "wq_a": w((L, D, Rq), D), "q_norm": jnp.ones((L, Rq), jnp.float32),
            "wq_b": w((L, Rq, H * (Dn + Dr)), Rq),
            "wkv_a": w((L, D, Rkv + Dr), D), "kv_norm": jnp.ones((L, Rkv), jnp.float32),
            "wkv_b": w((L, Rkv, H * (Dn + Dv)), Rkv), "wo": w((L, H * Dv, D), H * Dv),
        }
        if cfg.index_topk:
            out.update({
                "idx_wq": w((L, Rq, Hi * Di), Rq), "idx_wk": w((L, D, Di), D),
                "idx_norm_w": jnp.ones((L, Di), jnp.float32),
                "idx_norm_b": 0.1 * jax.random.normal(next(ks), (L, Di), jnp.float32),
                "idx_w": w((L, D, Hi), D, jnp.float32),
            })
        return out

    def ffn(lead: tuple, F: int) -> dict:
        return {"w_gate": w(lead + (D, F), D), "w_up": w(lead + (D, F), D), "w_down": w(lead + (F, D), F)}

    Ld, Lm = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    return {
        "embedding": jax.random.normal(next(ks), (cfg.vocab_size, D), cfg.dtype),
        "dense": {**attention(Ld), **ffn((Ld,), cfg.d_ff)},
        "moe": {
            **attention(Lm),
            "w_router": w((Lm, D, cfg.n_experts), D, jnp.float32),
            # the choice's correction: seeded non-zero, so that it shows
            "router_bias": 0.1 * jax.random.normal(next(ks), (Lm, cfg.n_experts), jnp.float32),
            "experts": ffn((Lm, cfg.held_experts), cfg.d_ff_expert),
            "shared": ffn((Lm, cfg.n_shared), cfg.d_ff_expert),
        },
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": w((D, cfg.vocab_size), D),
    }


def quantize_params(params: dict) -> dict:
    """Every matrix of a plain tree in weight-only int8 (one f32 scale per
    output channel); embedding, head, norms, the router and the indexer's
    head weights stay as they are."""
    out = dict(params)
    for group, ffn in (("dense", _FFN_MATRICES), ("moe", ())):
        lp = dict(params[group])
        for k in _ATTN_MATRICES + ffn:
            if k in lp:  # a model without an indexer has no idx_* matrices
                lp[k] = quantize_weight(lp[k], axis=-2)
        out[group] = lp
    for stack in ("experts", "shared"):
        out["moe"][stack] = {k: quantize_weight(params["moe"][stack][k], axis=-2) for k in _FFN_MATRICES}
    return out


# ------------------------------------------------------------- one layer
def _angles(cfg: DeepseekV32Config, positions: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.rope_factor <= 1.0:  # no rope_scaling: theta^(-2i/d)
        return rope_angles(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    return angles(positions, yarn_frequencies(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max,
        cfg.beta_fast, cfg.beta_slow))


def _project(cfg: DeepseekV32Config, h: jnp.ndarray, lp: dict, sin: jnp.ndarray, cos: jnp.ndarray) -> tuple:
    """The normed input h [B, S, D] to what attention and the indexer take:
    q_nope [B,S,H,Dn], q_rope [B,S,H,Dr], the latent row to cache [B,S,W],
    the indexer's queries [B,S,Hi,Di], its key to cache [B,S,Di] and its
    head weights [B,S,Hi] float32 (the last three None without an
    indexer)."""
    B, S, _ = h.shape
    H, Dn, Dr, Rkv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    Hi, Di = cfg.index_n_heads, cfg.index_head_dim
    c_q = rms_norm(_mm(h, lp["wq_a"]), lp["q_norm"], cfg.norm_eps)
    q = _mm(c_q, lp["wq_b"])
    if not cfg.index_topk:
        # the product handed on whole, as llama._qkv_products hands q, k
        # and v on: folded into it, the reshape to heads makes the dot's
        # weight a view [heads, Dn+Dr, Rq] and the stack is copied to that
        # layout once a dispatch. Keyed on the indexer only so that V3.2's
        # lowered programs keep their digests (ROADMAP S16 applies it there)
        q = jax.lax.optimization_barrier(q)
    q = q.reshape(B, S, H, Dn + Dr)
    q_nope, q_rope = q[..., :Dn], apply_rope_interleaved(q[..., Dn:], sin, cos)
    kv = _mm(h, lp["wkv_a"])
    c_kv = rms_norm(kv[..., :Rkv], lp["kv_norm"], cfg.norm_eps)
    k_r = apply_rope_interleaved(kv[..., None, Rkv:], sin, cos)[..., 0, :]
    row = jnp.concatenate(
        [c_kv, k_r, jnp.zeros((B, S, cfg.row_width - Rkv - Dr), c_kv.dtype)], axis=-1)
    if not cfg.index_topk:
        return q_nope, q_rope, row, None, None, None

    def index_rope(x: jnp.ndarray) -> jnp.ndarray:  # [B, S, heads, Di]: the first Dr dims turn
        return jnp.concatenate([apply_rope_halves(x[..., :Dr], sin, cos), x[..., Dr:]], axis=-1)

    qi = index_rope(_mm(c_q, lp["idx_wq"]).reshape(B, S, Hi, Di))
    ki = layer_norm(_mm(h, lp["idx_wk"]), lp["idx_norm_w"], lp["idx_norm_b"], cfg.norm_eps)
    ki = index_rope(ki[..., None, :])[..., 0, :]
    wi = jnp.matmul(h.astype(jnp.float32), lp["idx_w"], precision=jax.lax.Precision.HIGHEST) * (Hi ** -0.5 * Di ** -0.5)
    return q_nope, q_rope, row, qi, ki, wi


def _wkv_b(cfg: DeepseekV32Config, w: Any) -> tuple:
    """W_kvb as the absorbed form takes it: the key half [Rkv, H, Dn] and
    the value half [Rkv, H, Dv], each with its per-output-channel scale
    ([H, D*], or None for a plain matrix)."""
    H, Dn, Dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    if isinstance(w, dict):
        q = w["q"].reshape(-1, H, Dn + Dv)
        s = w["s"].reshape(H, Dn + Dv)
        return q[..., :Dn], s[:, :Dn], q[..., Dn:], s[:, Dn:]
    w = w.reshape(-1, H, Dn + Dv)
    return w[..., :Dn], None, w[..., Dn:], None


def _absorb_query(cfg: DeepseekV32Config, q_nope: jnp.ndarray, q_rope: jnp.ndarray, w: Any) -> jnp.ndarray:
    """The queries in the latent row's layout [..., H, W]: q_nope W_UK^T |
    q_rope | 0."""
    wk, sk, _, _ = _wkv_b(cfg, w)
    if sk is not None:
        q_nope = (q_nope.astype(jnp.float32) * sk).astype(q_nope.dtype)
    q_lat = jnp.einsum("...hd,chd->...hc", q_nope, wk.astype(q_nope.dtype),
                       preferred_element_type=jnp.float32).astype(q_nope.dtype)
    pad = jnp.zeros(q_rope.shape[:-1] + (cfg.row_width - cfg.kv_lora_rank - cfg.qk_rope_head_dim,), q_rope.dtype)
    return jnp.concatenate([q_lat, q_rope, pad], axis=-1)


def _absorb_output(cfg: DeepseekV32Config, o_lat: jnp.ndarray, w: Any, dtype: Any) -> jnp.ndarray:
    """o_lat [..., H, Rkv] float32 through W_UV to [..., H * Dv]."""
    _, _, wv, sv = _wkv_b(cfg, w)
    o = jnp.einsum("...hc,chd->...hd", o_lat.astype(dtype), wv.astype(dtype),
                   preferred_element_type=jnp.float32)
    if sv is not None:
        o = o * sv
    return o.astype(dtype).reshape(o.shape[:-2] + (cfg.n_heads * cfg.v_head_dim,))


def _dense_ffn(h: jnp.ndarray, lp: dict) -> jnp.ndarray:
    gate = jax.nn.silu(_mm(h, lp["w_gate"]).astype(jnp.float32)).astype(h.dtype)
    return _mm(gate * _mm(h, lp["w_up"]), lp["w_down"])


def _run_layers(cfg: DeepseekV32Config, params: dict, x: jnp.ndarray, carry: Any, attend: Any,
                live: jnp.ndarray) -> tuple:
    """Both stacks of layers over x [B, S, D] float32: the leading dense ones, then
    the expert ones. ``attend(lp, layer, h, carry)`` is the caller's
    attention: from the normed input to (heads' outputs [B, S, H*Dv], the
    carry — a cache or the pools — and its two int32 counters, :func:`step_stats`).
    ``live`` [B, S] marks the rows whose routing counts: no other pulls an
    expert. Returns x, the carry and the counters of :func:`step_stats_len`."""
    B, S, D = x.shape
    moe = dict(params["moe"])
    stacks = {"experts": moe.pop("experts"), "shared": moe.pop("shared")}

    def block(x, carry, lp, layer, ffn):
        # the residual stream is float32; what enters a product is cfg.dtype
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps).astype(cfg.dtype)
        a, carry, counts = attend(lp, layer, h, carry)
        x = x + _mm(a, lp["wo"]).astype(jnp.float32)
        y, rows = ffn(rms_norm(x, lp["mlp_norm"], cfg.norm_eps).astype(cfg.dtype), lp)
        return x + y.astype(jnp.float32), carry, rows, counts

    def dense_body(c, xs):
        lp, i = xs
        x, carry, _, counts = block(*c, lp, i, lambda h, lp: (_dense_ffn(h, lp), None))
        return (x, carry), counts

    def moe_body(c, xs):
        lp, i = xs

        def ffn(h, lp):
            flat = h.reshape(B * S, D)
            gates = sigmoid_topk_gates(
                flat, lp["w_router"], cfg.top_k, bias=lp["router_bias"], n_group=cfg.n_group,
                topk_group=cfg.topk_group, scale=cfg.routed_scaling)
            y, g, read = held_experts(flat, gates, stacks["experts"], stacks["shared"], cfg.first_expert,
                                      _mm, i, top_k=cfg.top_k, rows=live.reshape(B * S))
            took = jnp.sum((g > 0) & live.reshape(B * S, 1), axis=0, dtype=jnp.int32)
            return y.reshape(B, S, D), jnp.append(took, read)

        x, carry, rows, counts = block(*c, lp, cfg.n_dense_layers + i, ffn)
        return (x, carry), (rows, counts)

    Ld, Lm = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    c, counts_d = jax.lax.scan(dense_body, (x, carry), (params["dense"], jnp.arange(Ld)))
    (x, carry), (rows, counts_m) = jax.lax.scan(moe_body, c, (moe, jnp.arange(Lm)))
    stats = jnp.concatenate([jnp.sum(rows, axis=0), jnp.sum(counts_d, axis=0) + jnp.sum(counts_m, axis=0)])
    return x, carry, stats


def _logits(cfg: DeepseekV32Config, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The untied head over the rows of the vocabulary held here, float32."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
    return jnp.matmul(x, params["lm_head"].astype(x.dtype), preferred_element_type=jnp.float32)


def _count(scored: jnp.ndarray, read: jnp.ndarray) -> jnp.ndarray:
    return jnp.stack([jnp.sum(scored, dtype=jnp.int32), jnp.sum(read, dtype=jnp.int32)])


# ------------------------------------------------------------ the programs
@partial(jax.jit, static_argnums=0, donate_argnums=(3,))
def prefill(
    cfg: DeepseekV32Config,
    params: dict,
    tokens: jnp.ndarray,  # [B, S] right-padded
    cache: KVCache,  # dense scratch, donated
    seq_lens: jnp.ndarray,  # [B] true lengths
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill in the expanded form: fill the cache, return last-token
    logits [B, V]."""
    B, S = tokens.shape
    H, Dn = cfg.n_heads, cfg.qk_nope_head_dim
    x = params["embedding"][tokens].astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    sin, cos = _angles(cfg, positions)
    live = positions < seq_lens[:, None]
    seen = (positions[:, None, :] <= positions[:, :, None]) & live[:, None, :]  # [B, T, S]

    def attend(lp, layer, h, cache):
        q_nope, q_rope, row, qi, ki, wi = _project(cfg, h, lp, sin, cos)
        k_all = jax.lax.dynamic_update_slice(cache.k, row[None, :, :, None], (layer, 0, 0, 0, 0))
        if cfg.index_topk:
            v_all = jax.lax.dynamic_update_slice(cache.v, ki[None, :, :, None], (layer, 0, 0, 0, 0))
            keep = mla.selection_mask(mla.index_scores(qi, ki, wi), seen, cfg.index_topk)
        else:
            v_all, keep = None, seen
        kvb = _mm(row[..., :cfg.kv_lora_rank], lp["wkv_b"]).reshape(B, S, H, -1)
        o = mla.expanded_attention(
            q_nope, q_rope, kvb[..., :Dn], row[..., cfg.kv_lora_rank:cfg.kv_lora_rank + cfg.qk_rope_head_dim],
            kvb[..., Dn:], keep, cfg.softmax_scale)
        counts = _count(seen & live[:, :, None], keep & live[:, :, None])
        return o.astype(h.dtype).reshape(B, S, -1), KVCache(k_all, v_all), counts

    x, cache, _ = _run_layers(cfg, params, x, cache, attend, live)
    last_h = jnp.take_along_axis(x, (seq_lens - 1)[:, None, None], axis=1)  # [B, 1, D]
    return _logits(cfg, params, last_h)[:, 0], cache


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_step_paged(
    cfg: DeepseekV32Config,
    params: dict,
    tokens: jnp.ndarray,  # [B] last sampled token per row
    k_pool: jnp.ndarray,  # [L, N+1, 1, page, W] latent rows, donated
    v_pool: jnp.ndarray,  # [L, N+1, 1, page, Di] the indexer's keys, donated
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B] length INCLUDING this token's position
    active: jnp.ndarray,  # [B] bool — inactive rows write the trash page
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step over the paged pools, with ``llama.decode_step_paged``'s
    arguments: the pools ride the layers whole, written by the append's
    kernel alone. The indexer reads every page of a row's keys; the latent
    rows are read as :func:`sparse_read` says for the tables' slot — the
    row's owned pages through ``paged_latent_attention`` under the
    selection's mask, or the selected rows alone by a gather. Without an
    indexer ``v_pool`` is None and every cached latent row of a row is read
    by ``paged_latent_attention``, a Mosaic call a layer. After the pools,
    the step's counters (:func:`step_stats_len`)."""
    B = tokens.shape[0]
    page = k_pool.shape[3]
    masked = sparse_read(cfg, block_tables.shape[1] * page) == "masked"
    trash_page = k_pool.shape[1] - 1  # reserved by PagedKVCache
    x = params["embedding"][tokens][:, None, :].astype(jnp.float32)  # [B, 1, D]
    pos = jnp.maximum(seq_lens - 1, 0)
    sin, cos = _angles(cfg, pos[:, None])
    b_idx = jnp.arange(B)
    pages = jnp.where(active, block_tables[b_idx, pos // page], trash_page)
    offsets = jnp.where(active, pos % page, 0)

    def attend(lp, layer, h, pools):
        kp, vp = pools
        q_nope, q_rope, row, qi, ki, wi = _project(cfg, h, lp, sin, cos)
        if not cfg.index_topk:
            kp, _ = paged_kv_append(kp, None, row[:, 0, None], None, layer, pages, offsets)
            q = _absorb_query(cfg, q_nope[:, 0], q_rope[:, 0], lp["wkv_b"])
            o_lat = paged_latent_attention(q, kp, block_tables, seq_lens, layer, scale=cfg.softmax_scale,
                                           kv_lora_rank=cfg.kv_lora_rank)
            read = jnp.stack([jnp.sum(jnp.where(active, seq_lens, 0), dtype=jnp.int32),
                              jnp.sum(active, dtype=jnp.int32)])
            return _absorb_output(cfg, o_lat, lp["wkv_b"], h.dtype)[:, None], (kp, vp), read
        kp, vp = paged_kv_append(kp, vp, row[:, 0, None], ki[:, 0, None], layer, pages, offsets)
        scores, seen = mla.paged_index_scores(qi[:, 0], wi[:, 0], vp, block_tables, seq_lens, layer)
        if masked:
            keep = mla.selection_mask(scores, seen, cfg.index_topk)
            q = _absorb_query(cfg, q_nope[:, 0], q_rope[:, 0], lp["wkv_b"])
            o_lat = paged_latent_attention(q, kp, block_tables, seq_lens, layer, scale=cfg.softmax_scale,
                                           kv_lora_rank=cfg.kv_lora_rank, keep=keep)
        else:
            rows, keep = mla.select_topk(
                scores, seen, cfg.index_topk, mla.pool_rows(block_tables, kp.shape[1], page, layer))
            q = _absorb_query(cfg, q_nope[:, 0], q_rope[:, 0], lp["wkv_b"])
            o_lat = mla.sparse_decode_attention(
                q, kp, rows, keep, scale=cfg.softmax_scale, kv_lora_rank=cfg.kv_lora_rank)
        counts = _count(seen & active[:, None], keep & active[:, None])
        return _absorb_output(cfg, o_lat, lp["wkv_b"], h.dtype)[:, None], (kp, vp), counts

    x, (k_pool, v_pool), stats = _run_layers(cfg, params, x, (k_pool, v_pool), attend, active[:, None])
    return _logits(cfg, params, x)[:, 0], k_pool, v_pool, stats


def _chunk_row(cfg: DeepseekV32Config, params: dict, tokens: jnp.ndarray, positions: jnp.ndarray,
               pages: jnp.ndarray, offsets: jnp.ndarray, table: jnp.ndarray, start: jnp.ndarray,
               k_pool: jnp.ndarray, v_pool: jnp.ndarray) -> tuple:
    """One row's chunk of T tokens in the absorbed form: its rows and keys
    written through its table, then every chunk position against the
    row's pages up to the chunk's end (:func:`_bounded_attention`), under
    the selection's mask where the model has an indexer."""
    T = tokens.shape[0]
    x = params["embedding"][jnp.maximum(tokens, 0)][None].astype(jnp.float32)  # [1, T, D]
    sin, cos = _angles(cfg, positions[None])

    def attend(lp, layer, h, pools):
        kp, vp = pools
        q_nope, q_rope, row, qi, ki, wi = _project(cfg, h, lp, sin, cos)
        kp = kp.at[layer, pages, 0, offsets].set(row[0])
        index = None
        if cfg.index_topk:
            vp = vp.at[layer, pages, 0, offsets].set(ki[0])
            index = (vp, qi[0], wi[0])
        q = _absorb_query(cfg, q_nope[0], q_rope[0], lp["wkv_b"])
        o_lat = _bounded_attention(cfg, q, kp, table, layer, positions, start + T, index)
        return _absorb_output(cfg, o_lat, lp["wkv_b"], h.dtype)[None], (kp, vp), jnp.zeros(2, jnp.int32)

    x, (k_pool, v_pool), _ = _run_layers(cfg, params, x, (k_pool, v_pool), attend, (tokens >= 0)[None])
    # without an indexer the head runs at the row's last token alone: [1, V];
    # V3.2's chunk still runs it at every position (ROADMAP S16)
    if not cfg.index_topk:
        last = jnp.maximum(jnp.sum(tokens >= 0) - 1, 0)
        return _logits(cfg, params, jax.lax.dynamic_index_in_dim(x[0], last, 0)), k_pool, v_pool
    return _logits(cfg, params, x)[0], k_pool, v_pool


def chunk_contexts(T: int, page: int, slot: int) -> tuple[int, ...]:
    """The contexts a chunk of T tokens may read in a slot of ``slot``
    positions: T in whole pages, doubling, the last the slot. The chunk
    reads the first that holds its end (``_bounded_attention``'s
    branches); the engine mirrors that choice as ``chunk_ctx`` on the
    ragged dispatch's span."""
    bounds, n = [], -(-T // page) * page
    while n < slot:
        bounds.append(n)
        n *= 2
    return (*bounds, slot)


def _chunk_keep(cfg: DeepseekV32Config, positions: jnp.ndarray, end: jnp.ndarray, n: int,
                scores: jnp.ndarray | None = None) -> jnp.ndarray:
    """Which of the first n positions each chunk query attends [T, n]:
    those at or before it — under an indexer, of those before the chunk's
    ``end`` the ``index_topk`` best by ``scores`` [T, n]. Positions past
    ``end`` are never seen, so this is the selection over the whole slot
    cut to its first n positions, bit for bit."""
    keep = jnp.arange(n)[None, :] <= positions[:, None]
    if not cfg.index_topk:
        return keep
    seen = keep & (jnp.arange(n)[None, :] < end)
    return mla.selection_mask(scores, seen, cfg.index_topk)


def _bounded_attention(cfg: DeepseekV32Config, q: jnp.ndarray, k_pool: jnp.ndarray, table: jnp.ndarray,
                       layer: jnp.ndarray, positions: jnp.ndarray, end: jnp.ndarray,
                       index: tuple | None = None) -> jnp.ndarray:
    """A chunk's queries q [T, H, W] against the row's latent rows up to
    ``end``, the chunk's end, under the causal mask: the context read is
    the first of :func:`chunk_contexts` that holds ``end``, one branch of
    a ``switch`` each, so a chunk near the start of a long slot reads and
    scores its own few pages and not the slot. With ``index`` (the key
    pool and the chunk's indexer queries [T, Hi, Di] and head weights
    [T, Hi]) the branch also reads that many positions of indexer keys,
    scores them and attends to the selection alone. Returns o_lat [T, H,
    kv_lora_rank] float32."""
    page = k_pool.shape[3]
    bounds = chunk_contexts(q.shape[0], page, table.shape[0] * page)

    def over(n: int) -> Any:
        def attend() -> jnp.ndarray:
            rows = mla.row_pages(k_pool, table[None, :n // page], layer)[0]  # [n, W]
            scores = None
            if index is not None:
                v_pool, qi, wi = index
                keys = mla.row_pages(v_pool, table[None, :n // page], layer)[0]  # [n, Di]
                scores = mla.index_scores(qi, keys, wi)
            keep = _chunk_keep(cfg, positions, end, n, scores)
            return mla.latent_attention(q, rows, keep, cfg.softmax_scale, cfg.kv_lora_rank)
        return attend

    which = jnp.sum(jnp.asarray(bounds[:-1], jnp.int32) < end, dtype=jnp.int32)
    return jax.lax.switch(which, [over(n) for n in bounds])


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_chunk_paged(
    cfg: DeepseekV32Config,
    params: dict,
    tokens: jnp.ndarray,  # [B, T] the next prompt tokens of each row (-1 pads)
    k_pool: jnp.ndarray,  # donated
    v_pool: jnp.ndarray,  # donated
    block_tables: jnp.ndarray,  # [B, M]
    start_len: jnp.ndarray,  # [B] resident length BEFORE the chunk
    active: jnp.ndarray,  # [B]
    kv_capacity: jnp.ndarray,  # [B] tokens covered by owned pages
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A chunk of T tokens a row against the page pools, with
    ``llama.decode_chunk_paged``'s arguments (overflow to the trash page),
    ONE ROW AT A TIME under a ``cond``: a row without a chunk runs nothing
    and returns zeros, so a dispatch costs its live rows, and one row's
    scores over its context are all that is held at once. Returns (logits
    [B, T, V] — without an indexer [B, 1, V] at each row's last chunk
    position, where the head runs alone, as ``lfm2_moe``'s — k_pool,
    v_pool)."""
    B, T = tokens.shape
    positions = start_len[:, None] + jnp.arange(T)[None, :]
    pages, offsets = _paged_chunk_targets(k_pool, block_tables, positions, active, kv_capacity)

    def row(pools, xs):
        toks, pos, pg, off, table, start, act = xs

        def run(kp, vp):
            return _chunk_row(cfg, params, toks, pos, pg, off, table, start, kp, vp)

        def skip(kp, vp):
            return jnp.zeros((T if cfg.index_topk else 1, cfg.vocab_size), jnp.float32), kp, vp

        logits, kp, vp = jax.lax.cond(act, run, skip, *pools)
        return (kp, vp), logits

    (k_pool, v_pool), logits = jax.lax.scan(
        row, (k_pool, v_pool), (tokens, positions, pages, offsets, block_tables, start_len, active))
    return logits, k_pool, v_pool
