"""Nemotron-H decoder (``model_type`` ``nemotron_h``, NVIDIA's Nemotron 3
Nano 30B-A3B): a hybrid of three kinds of layer in the published order of
``hybrid_override_pattern`` — Mamba-2 mixers (M), sparse relu² experts
beside a shared one (E) and grouped-query attention with no positional
encoding (*). Each layer is ONE mixer behind a pre-norm, nothing else:

    x <- x + Mixer_l(RMSNorm(x; g_l))            eps = norm_eps, the residual stream float32
    M:  [z | xBC | dt] = h W_in                   widths d_inner | d_inner + 2 G N | H
        xBC <- SiLU(causal_conv_K(xBC) + b_conv); [x | B | C] = xBC, x [H, P], B and C [G, N]
        Delta_h = softplus(dt_h + dt_bias_h)      (no clamp: time_step_limit is (0, inf))
        A_h = -exp(A_log_h)                       head h reads group h // (H / G)
        S_h,t = exp(Delta_h,t A_h) S_h,t-1 + Delta_h,t x_h,t (x) B_g,t      S_h [P, N] float32
        y_h,t = S_h,t C_g,t + D_h x_h,t
        out = RMSNorm_groups(y * SiLU(z)) w_norm W_out   the norm over n_groups groups of channels
    *:  q = h W_q, k = h W_k, v = h W_v; causal GQA over every position, scale head_dim^-1/2, o W_o
    E:  s = sigmoid(h W_r) over every published expert, float32 at highest precision; the top_k of
        s + e_bias are chosen (no group limit); g_e = routed_scaling * s_e / sum_chosen s;
        out = sum_chosen,held g_e relu(h W_up,e)² W_down,e + relu(h W_up,s)² W_down,s
    logits = RMSNorm(x_L; g_f) W_head             untied, over the vocabulary rows held

The layers are walked in published order as a first Mamba layer, then
pairs (E, M), an attention layer before the pair that follows it, and a
last E: the pattern is a string of units ``M E`` or ``M * E``
(:func:`_units`). The pairs before the first attention layer are one
``fori_loop``; each attention layer and the pairs up to the next are one
step of a ``scan`` over the attention layers, their count data. Nothing
is unrolled over the 52 layers, and no select chooses between mixers
that both ran: the step's own counters (``ssm_rows``, ``attn_full``)
count what ran.

What is stored (``cache_spec``; ``serving/kv_cache.py`` builds it): one
pool of K and V for the attention layers, every position, a page
``(n_kv_heads, page, head_dim)``; and a state a slot that is no page —
each Mamba layer's SSD state ``[H, P, N]`` (float32: ``state_dtype``) and
its conv's last ``K - 1`` inputs (``cfg.dtype``, the type ``xBC`` is
rounded to before the conv everywhere). The state rides
``k_pool["state"]``: written by the prefill that fills the slot, zeroed by
a chunk that starts a prompt, carried from chunk to chunk at any offset
(``ops/ssd.ssd_chunk_scan`` takes the start state and returns the end
one), and advanced by live rows only — in a decode step by
``ops/ssd.ssd_state_step``, ONE Pallas call a layer aliased over the whole
state pool, which reads and writes each live row's state once.

The expert layer is ``ops/moe``'s: ``sigmoid_topk_gates`` with the
correction bias and the routed scaling, and ``held_experts`` over the
held share (``first_expert`` on) with the shared expert beside it. The
experts are relu² (no ``w_gate``); stored as served (:func:`quantize_params`)
their width is zero-padded to whole lane tiles (:func:`pad_experts`),
exact: relu(0)² = 0 and a zero row of W_down adds nothing. The engine
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
# a prefill needs no scratch and returns its slabs and state as lfm2_moe's does: its KVCache is this model's
from gofr_tpu.models.lfm2_moe import KVCache, _mm32, _pick, prefill_slabs
from gofr_tpu.models.phi4flash import _last, _masked_attention, _targets, _write_rows
from gofr_tpu.ops import ssd, ssm
from gofr_tpu.ops.attention import attention
from gofr_tpu.ops.moe import held_experts, sigmoid_topk_gates
from gofr_tpu.ops.norms import rms_norm
from gofr_tpu.ops.paged_attention import paged_decode_attention, paged_kv_append

__all__ = [
    "NemotronHConfig", "KVCache", "init_params", "quantize_params", "pad_experts", "prefill", "prefill_slabs",
    "decode_step_paged", "decode_chunk_paged", "step_stats_len", "step_stats", "cache_spec", "unserved",
    "layer_kinds", "STEP_STATS", "SCAN_POSITIONS",
]

MAMBA, EXPERT, ATTN = "M", "E", "*"
# the int32 counters a paged decode step returns after the expert layers'
# (rows each held expert took, held experts read): positions the attention
# layers read; row-steps whose SSD state advanced, summed over the Mamba layers
STEP_STATS = ("attn_full", "ssm_rows")
# the span attribute a prefill or chunk dispatch carries: prompt positions
# the SSD scan advanced (serving/engine.py sets it)
SCAN_POSITIONS = "ssd_positions"
LANES = 128


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    d_model: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"  # hybrid_override_pattern
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    d_state: int = 128  # ssm_state_size
    conv_kernel: int = 4
    chunk_size: int = 128  # the SSD scan's sub-chunk
    d_ff_expert: int = 1856  # moe_intermediate_size
    d_ff_shared: int = 3712  # moe_shared_expert_intermediate_size
    n_experts: int = 128  # published: the router's width
    top_k: int = 6
    routed_scaling: float = 2.5
    n_held: int = 128  # experts this chip holds ...
    first_held: int = 0  # ... from this one on
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32  # the SSD state's type in the pool

    def __post_init__(self) -> None:
        _units(self.pattern)  # raises on a pattern the walk does not take
        if self.n_heads % self.n_kv_heads or self.mamba_heads % self.n_groups:
            raise ValueError("query heads divide into KV heads' groups, Mamba heads into B/C groups")
        if not 0 <= self.first_held <= self.n_experts - self.n_held:
            raise ValueError("the held experts lie among the published ones")

    # what the engine reads of a config with sparse experts
    @property
    def held_experts(self) -> int:
        return self.n_held

    @property
    def first_expert(self) -> int:
        return self.first_held

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def n_mamba(self) -> int:
        return self.pattern.count(MAMBA)

    @property
    def n_moe(self) -> int:
        return self.pattern.count(EXPERT)

    @property
    def n_attn(self) -> int:
        return self.pattern.count(ATTN)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @classmethod
    def tiny(cls, **kw: Any) -> "NemotronHConfig":
        """Test size with the published structure: units ``M E`` and ``M * E``
        with attention in two of six, 8 Mamba heads in 2 groups, 16
        experts (top 4) and a shared one, relu² widths that are no whole
        lane tile."""
        defaults = dict(
            vocab_size=256, d_model=64, pattern="MEM*EMEMEM*EME", n_heads=4, n_kv_heads=2, head_dim=16,
            mamba_heads=8, mamba_head_dim=8, n_groups=2, d_state=16, chunk_size=8, d_ff_expert=24,
            d_ff_shared=48, n_experts=16, top_k=4, n_held=16, max_seq_len=256, dtype=jnp.float32,
        )
        defaults.update(kw)
        return cls(**defaults)


def layer_kinds(pattern: str) -> tuple[str, ...]:
    """The kind of each layer, in published order: ``M``, ``E`` or ``*``."""
    return tuple(pattern)


def _units(pattern: str) -> np.ndarray:
    """Whether each unit (``M E`` or ``M * E``) of the pattern holds an
    attention layer; raises on a pattern that is no string of such units."""
    units, rest = [], pattern
    while rest:
        for unit in (MAMBA + EXPERT, MAMBA + ATTN + EXPERT):
            if rest.startswith(unit):
                units.append(len(unit) == 3)
                rest = rest[len(unit):]
                break
        else:
            raise ValueError(f"nemotron_h walks patterns of units 'ME' and 'M*E'; {pattern!r} is not one")
    if not any(units):
        raise ValueError("nemotron_h keeps one KV pool: the pattern has an attention layer")
    return np.asarray(units)


def _blocks(cfg: NemotronHConfig) -> tuple[int, np.ndarray, np.ndarray]:
    """(pairs before the first attention layer; the first pair after each
    attention layer and how many pairs follow it up to the next, the last
    block's final expert layer not counted): the blocks the walk scans.
    Pair u is (expert layer u, Mamba layer u + 1)."""
    starts = np.flatnonzero(_units(cfg.pattern)).astype(np.int32)
    ends = np.append(starts[1:], len(_units(cfg.pattern)) - 1)
    return int(starts[0]), starts, (ends - starts).astype(np.int32)


def step_stats_len(cfg: NemotronHConfig) -> int:
    """int32 counters a paged decode step returns after the pools: rows
    each held expert took and the held experts read
    (``ops/moe.held_experts``), each summed over the expert layers, then
    :data:`STEP_STATS`."""
    return cfg.n_held + 1 + len(STEP_STATS)


def step_stats(cfg: NemotronHConfig) -> tuple[str, ...]:
    """Names of the counters after the experts' (:data:`STEP_STATS`)."""
    return STEP_STATS


def cache_spec(cfg: NemotronHConfig, page_size: int) -> tuple[tuple, dict]:
    """What this model stores (``serving/kv_cache.PagedKVCache`` builds
    from it): one pool of every position's K and V for the attention
    layers, and a state a slot — each Mamba layer's SSD state ``[H, P,
    N]`` in ``state_dtype`` and its conv's last ``K - 1`` inputs."""
    page = (cfg.n_kv_heads, page_size, cfg.head_dim)
    return (("full", cfg.n_attn, page, page, None),), {
        "ssm": (cfg.n_mamba, (cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state), cfg.state_dtype),
        "conv": (cfg.n_mamba, (cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype)}


def unserved(engine_config: Any, lora: Any, cfg: Any = None) -> str | None:
    """What an engine asks for that this model has no program for, in a
    sentence; None if it can be built. A preempted request resumes by
    prefilling its prompt and what it emitted, which needs no snapshot of
    the state and is served."""
    ec = engine_config
    if ec.spec_tokens > 0:
        return ("nemotron_h has no speculative verify program: a rejected draft would have to roll "
                "the SSD states and conv tails back; set TPU_SPEC_TOKENS=0")
    if lora is not None:
        return "nemotron_h serves no LoRA adapters: set no adapter registry"
    if ec.prefix_cache_entries > 0:
        return ("nemotron_h keeps no prefix cache: a cached prefix would need a snapshot of every "
                "Mamba layer's state at its boundary, 46 MiB a slot at the published widths "
                "(ROADMAP R2); set TPU_PREFIX_CACHE=0")
    if ec.kv_spill_bytes > 0:
        return ("nemotron_h spills no KV to the host: a spilled row would need its SSD states and "
                "conv tails; set TPU_KV_SPILL_BYTES=0 (a preempted row re-prefills)")
    if ec.role != "unified":
        return ("nemotron_h is served by unified replicas only: a prefill replica hands a decode "
                "replica K/V slabs through the prefix cache, which this model does not keep")
    return None


# ------------------------------------------------------------------ weights
_QUANT = {"mamba": ("in_proj", "out_proj"), "attn": ("wq", "wk", "wv", "wo")}


def init_params(cfg: NemotronHConfig, key: jax.Array) -> dict:
    """Random params, stacked by kind in published order: ``mamba``,
    ``attn`` and ``moe``, each with the norm before its layers; routed
    experts [n_moe, held, ...] and the shared one [n_moe, 1, ...], relu²
    (``w_up``, ``w_down``) at their published widths. Router, correction
    bias, conv taps and bias, ``A_log``, ``D``, ``dt_bias`` and the norms
    are float32."""
    D, Fe, Fs, E = cfg.d_model, cfg.d_ff_expert, cfg.d_ff_shared, cfg.n_experts
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hm, Di, Cd, K = cfg.mamba_heads, cfg.d_inner, cfg.conv_dim, cfg.conv_kernel
    Lm, La, Le = cfg.n_mamba, cfg.n_attn, cfg.n_moe
    keys = iter(jax.random.split(key, 32))

    def w(shape: tuple, fan_in: int, dtype: Any = None, gain: float = 1.0) -> jnp.ndarray:
        return gain * jax.random.normal(next(keys), shape, dtype or cfg.dtype) / math.sqrt(fan_in)

    def norm(shape: tuple) -> jnp.ndarray:
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    def relu2(lead: tuple, width: int) -> dict:
        # the output matrix at a tenth: relu² of a unit product is a
        # squared normal, and a layer of them would swamp the stream
        return {"w_up": w(lead + (D, width), D), "w_down": w(lead + (width, D), width, gain=0.1)}

    return {
        "embedding": jax.random.normal(next(keys), (cfg.vocab_size, D), cfg.dtype),
        "mamba": {"norm": norm((Lm, D)), "in_proj": w((Lm, D, Di + Cd + Hm), D), "out_proj": w((Lm, Di, D), Di),
                  "conv_w": jax.random.uniform(next(keys), (Lm, K, Cd), jnp.float32, -K ** -0.5, K ** -0.5),
                  "conv_b": 0.1 * jax.random.normal(next(keys), (Lm, Cd), jnp.float32),
                  "dt_bias": jax.random.uniform(next(keys), (Lm, Hm), jnp.float32, -4.0, -1.0),
                  "A_log": jnp.log(jax.random.uniform(next(keys), (Lm, Hm), jnp.float32, 1.0, 16.0)),
                  "D": 1.0 + 0.1 * jax.random.normal(next(keys), (Lm, Hm), jnp.float32),
                  "gate_norm": norm((Lm, Di))},
        "attn": {"norm": norm((La, D)), "wq": w((La, D, H * Dh), D), "wk": w((La, D, Hkv * Dh), D),
                 "wv": w((La, D, Hkv * Dh), D), "wo": w((La, H * Dh, D), H * Dh)},
        "moe": {"norm": norm((Le, D)), "w_router": w((Le, D, E), D, jnp.float32),
                # the choice's correction: seeded non-zero, so that it shows
                "router_bias": 0.1 * jax.random.normal(next(keys), (Le, E), jnp.float32),
                "experts": relu2((Le, cfg.n_held), Fe), "shared": relu2((Le, 1), Fs)},
        "final_norm": norm((D,)),
        "lm_head": w((D, cfg.vocab_size), D),
    }


def pad_experts(stacks: dict, multiple: int = LANES) -> dict:
    """relu² stacks (``w_up`` [..., D, F], ``w_down`` [..., F, D], plain or
    int8 ``{"q", "s"}``) with F zero-padded to a multiple of ``multiple``:
    the Mosaic expert kernel takes whole lane tiles. Exact — relu(0)² = 0
    and a zero row of W_down adds nothing; a padded column's scale is 1."""
    F = jax.tree.leaves(stacks["w_up"])[0].shape[-1]
    pad = -F % multiple
    if not pad:
        return stacks

    def widen(w: Any, axis: int, scale_axis: int | None) -> Any:
        def zeros(a: jnp.ndarray, ax: int, value: float = 0.0) -> jnp.ndarray:
            spec = [(0, 0)] * a.ndim
            spec[ax % a.ndim] = (0, pad)
            return jnp.pad(a, spec, constant_values=value)

        if not isinstance(w, dict):
            return zeros(w, axis)
        return {"q": zeros(w["q"], axis), "s": w["s"] if scale_axis is None else zeros(w["s"], scale_axis, 1.0)}

    return {"w_up": widen(stacks["w_up"], -1, -1), "w_down": widen(stacks["w_down"], -2, None)}


def quantize_params(params: dict) -> dict:
    """Every large matrix of a plain tree in weight-only int8 (one f32
    scale per output channel), the experts' widths padded to whole lane
    tiles (:func:`pad_experts`); embedding, head, norms, conv, router and
    its bias, and the Mamba scalars stay as they are."""
    out = {name: (dict(group, **{k: quantize_weight(group[k], axis=-2) for k in _QUANT[name]})
                  if name in _QUANT else group) for name, group in params.items()}
    out["moe"] = dict(params["moe"], **{
        kind: pad_experts({k: quantize_weight(v, axis=-2) for k, v in params["moe"][kind].items()})
        for kind in ("experts", "shared")})
    return out


# ------------------------------------------------------------------- layers
def _mamba_inputs(cfg: NemotronHConfig, h: jnp.ndarray, lp: dict) -> tuple:
    """W_in's product over the normed h [B, T, D]: (z [B, T, Di] float32,
    xBC [B, T, conv_dim] rounded to cfg.dtype — the type its conv tail is
    stored in — and Delta [B, T, H] float32 after the softplus). The split
    stays behind a barrier: folded into the product it would slice the
    weight stack instead."""
    Di, Cd = cfg.d_inner, cfg.conv_dim
    zxd = jax.lax.optimization_barrier(_mm32(h, lp["in_proj"]))
    z, xbc, dt = zxd[..., :Di], zxd[..., Di:Di + Cd], zxd[..., Di + Cd:]
    return z, xbc.astype(cfg.dtype), jax.nn.softplus(dt + lp["dt_bias"])


def _split_xbc(cfg: NemotronHConfig, xbc: jnp.ndarray) -> tuple:
    """The conv's output [..., conv_dim] as x [..., H, P], B and C [..., G, N]."""
    Di, GN = cfg.d_inner, cfg.n_groups * cfg.d_state
    lead = xbc.shape[:-1]
    return (xbc[..., :Di].reshape(lead + (cfg.mamba_heads, cfg.mamba_head_dim)),
            xbc[..., Di:Di + GN].reshape(lead + (cfg.n_groups, cfg.d_state)),
            xbc[..., Di + GN:].reshape(lead + (cfg.n_groups, cfg.d_state)))


def _mamba_out(cfg: NemotronHConfig, y: jnp.ndarray, x: jnp.ndarray, z: jnp.ndarray, lp: dict,
               dtype: Any) -> jnp.ndarray:
    """y [..., H, P] (the scan's, without D) -> the layer's output [..., D]:
    the D skip, the gate, the grouped norm, W_out."""
    y = y + lp["D"][:, None] * x
    g = ssd.gated_rms_norm(y.reshape(z.shape), z, lp["gate_norm"], cfg.d_inner // cfg.n_groups, cfg.norm_eps)
    return _mm32(g.astype(dtype), lp["out_proj"])


def _mamba_span(cfg: NemotronHConfig, h: jnp.ndarray, lp: dict, state: jnp.ndarray, tail: jnp.ndarray,
                live: jnp.ndarray, n_new: jnp.ndarray) -> tuple:
    """A Mamba layer over positions h [B, T, D] from ``state`` [B, H, P, N]
    and ``tail`` [B, K-1, conv_dim]: (y [B, T, D] float32, the state after
    the row's last live position, the tail that ends there). ``live`` [B,
    T] marks the positions that advance the state (Delta = 0 elsewhere);
    ``n_new`` [B] how many there are."""
    z, xbc, dt = _mamba_inputs(cfg, h, lp)
    conv, seen = ssm.causal_conv(xbc, tail, lp["conv_w"], lp["conv_b"])
    x, b, c = _split_xbc(cfg, conv)
    dt = jnp.where(live[..., None], dt, 0.0)
    y, state = ssd.ssd_chunk_scan(x, dt, -jnp.exp(lp["A_log"]), b, c, state, chunk=cfg.chunk_size)
    return _mamba_out(cfg, y, x, z, lp, h.dtype), state, ssm.conv_tail(seen, n_new, cfg.conv_kernel - 1)


def _scale(cfg: NemotronHConfig) -> float:
    return 1.0 / math.sqrt(cfg.head_dim)


def _qkv(cfg: NemotronHConfig, h: jnp.ndarray, lp: dict) -> tuple:
    """h [B, T, D] -> q [B, T, H, Dh], k and v [B, T, Hkv, Dh]: no
    positional encoding."""
    B, T, _ = h.shape
    q, k, v = _qkv_products(h, lp)
    return (q.reshape(B, T, cfg.n_heads, cfg.head_dim), k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim))


def _walk(cfg: NemotronHConfig, params: dict, x: jnp.ndarray, carry: Any, attend: Any, mix: Any,
          live: jnp.ndarray) -> tuple:
    """Every layer over x [B, T, D] float32, in published order. The
    caller's mixers: ``mix(h, lp, i, carry)`` for Mamba layer i and
    ``attend(h, lp, j, carry)`` for attention layer j, from the normed
    input (cfg.dtype) to (y [B, T, D], the carry — pools, states — and an
    int32 count: rows whose state advanced, positions read). ``live`` [B,
    T] marks the rows whose routing counts: no other pulls an expert.
    Returns x, the carry and the counters of :func:`step_stats_len`."""
    B, T, D = x.shape
    E, eps = cfg.n_held, cfg.norm_eps
    moe = dict(params["moe"])
    experts, shared = moe.pop("experts"), moe.pop("shared")
    rows = live.reshape(B * T)

    def normed(x: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
        return rms_norm(x, g, eps).astype(cfg.dtype)

    def mamba_layer(i: Any, c: tuple) -> tuple:
        x, carry, stats = c
        lp = _pick(params["mamba"], i)
        y, carry, n = mix(normed(x, lp["norm"]), lp, i, carry)
        return x + y.astype(jnp.float32), carry, stats.at[E + 2].add(n)

    def expert_layer(m: Any, c: tuple) -> tuple:
        x, carry, stats = c
        lp = _pick(moe, m)
        b = rms_norm(x, lp["norm"], eps).reshape(B * T, D)  # float32: the router's input is not rounded
        gates = sigmoid_topk_gates(b, lp["w_router"], cfg.top_k, bias=lp["router_bias"], scale=cfg.routed_scaling)
        y, g, read = held_experts(b.astype(cfg.dtype), gates, experts, shared, cfg.first_held, _mm, m,
                                  top_k=cfg.top_k, rows=rows)
        took = jnp.sum((g > 0) & rows[:, None], axis=0, dtype=jnp.int32)
        return x + y.reshape(B, T, D), carry, stats.at[:E].add(took).at[E].add(read)

    def pair(u: Any, c: tuple) -> tuple:  # expert layer u, then Mamba layer u + 1
        return mamba_layer(u + 1, expert_layer(u, c))

    def block(c: tuple, xs: tuple) -> tuple:
        j, first, n_pairs = xs  # attention layer j, then pairs first .. first + n_pairs - 1
        x, carry, stats = c
        lp = _pick(params["attn"], j)
        y, carry, n = attend(normed(x, lp["norm"]), lp, j, carry)
        c = (x + y.astype(jnp.float32), carry, stats.at[E + 1].add(n))
        return jax.lax.fori_loop(first, first + n_pairs, pair, c), None

    lead, starts, n_pairs = _blocks(cfg)
    c = mamba_layer(0, (x, carry, jnp.zeros(step_stats_len(cfg), jnp.int32)))
    c = jax.lax.fori_loop(0, lead, pair, c)
    c, _ = jax.lax.scan(block, c, (jnp.arange(cfg.n_attn), jnp.asarray(starts), jnp.asarray(n_pairs)))
    return expert_layer(cfg.n_moe - 1, c)


def _logits(cfg: NemotronHConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The untied head over x [..., D] float32: logits [..., V] float32."""
    h = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
    return jnp.einsum("...d,dv->...v", h, params["lm_head"].astype(h.dtype), preferred_element_type=jnp.float32)


def _zero_state(cfg: NemotronHConfig, B: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    return (jnp.zeros((B, cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state), jnp.float32),
            jnp.zeros((B, cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype))


# ------------------------------------------------------------------ prefill
@partial(jax.jit, static_argnums=0, donate_argnums=(3,))
def prefill(
    cfg: NemotronHConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, S] right-padded
    cache: KVCache,  # empty: this model's prefill needs no scratch
    seq_lens: jnp.ndarray,  # [B] true lengths
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill from a zero state: last-token logits [B, V] and what the
    slot stores — the attention layers' K and V over the bucket, and the
    SSD states and conv tails where ``seq_lens`` (not the bucket's end)
    leaves them."""
    B, S = tokens.shape
    x = params["embedding"][tokens].astype(jnp.float32)
    live = jnp.arange(S)[None, :] < seq_lens[:, None]
    state0, tail0 = _zero_state(cfg, B)

    def attend(h, lp, j, carry):
        k_all, v_all, states, tails = carry
        q, k, v = _qkv(cfg, h, lp)
        k_all = jax.lax.dynamic_update_index_in_dim(k_all, k, j, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(v_all, v, j, 0)
        o = attention(q, k, v, causal=True, kv_len=seq_lens, scale=_scale(cfg))
        return _mm(o.reshape(B, S, -1), lp["wo"]), (k_all, v_all, states, tails), jnp.int32(0)

    def mix(h, lp, i, carry):
        k_all, v_all, states, tails = carry
        y, state, tail = _mamba_span(cfg, h, lp, state0, tail0, live, seq_lens)
        states = jax.lax.dynamic_update_index_in_dim(states, state.astype(states.dtype), i, 0)
        tails = jax.lax.dynamic_update_index_in_dim(tails, tail, i, 0)
        return y, (k_all, v_all, states, tails), jnp.int32(0)

    kv = jnp.zeros((cfg.n_attn, B, S, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    states = jnp.zeros((cfg.n_mamba,) + state0.shape, cfg.state_dtype)
    tails = jnp.zeros((cfg.n_mamba,) + tail0.shape, cfg.dtype)
    x, (k_all, v_all, states, tails), _ = _walk(cfg, params, x, (kv, kv, states, tails), attend, mix, live)
    logits = _logits(cfg, params, _last(x, seq_lens))
    return logits, KVCache({"full": k_all, "state": {"ssm": states, "conv": tails}}, {"full": v_all})


# ------------------------------------------------------------- paged decode
@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_step_paged(
    cfg: NemotronHConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B] last sampled token per row
    k_pool: dict,  # {"full": [n_attn, N+1, Hkv, page, Dh], "state": {"ssm", "conv"}} donated
    v_pool: dict,  # {"full"} donated
    block_tables: dict,  # {"full": [B, M]} int32
    seq_lens: jnp.ndarray,  # [B] length INCLUDING this token's position
    active: jnp.ndarray,  # [B] bool — inactive rows write the trash page and keep their state
) -> tuple[jnp.ndarray, dict, dict, jnp.ndarray]:
    """One decode step, as ``llama.decode_step_paged`` (same arguments),
    and after the pools the step's counters (:func:`step_stats_len`). The
    KV pool is touched by the two attention kernels alone, the SSD states
    by ``ssd_state_step`` alone (each call aliased over its whole pool);
    the conv tails beside them are XLA's, updated in place."""
    B = tokens.shape[0]
    H, Dh, K = cfg.n_heads, cfg.head_dim, cfg.conv_kernel
    table = block_tables["full"]
    page = k_pool["full"].shape[3]
    x = params["embedding"][tokens][:, None, :].astype(jnp.float32)  # [B, 1, D]
    pos = jnp.maximum(seq_lens - 1, 0)
    pages, offsets = _targets(table, pos, active, page, k_pool["full"].shape[1] - 1)
    one = jnp.ones((B,), jnp.int32)

    def attend(h, lp, j, carry):
        kp, vp, states, tails = carry
        q, k, v = _qkv(cfg, h, lp)
        # Mosaic kernels on a TPU, scatter and gather references on the CPU
        kp, vp = paged_kv_append(kp, vp, k[:, 0], v[:, 0], j, pages, offsets)
        o = paged_decode_attention(q[:, 0], kp, vp, table, seq_lens, scale=_scale(cfg), layer=j)
        read = jnp.sum(jnp.where(active, seq_lens, 0), dtype=jnp.int32)
        return _mm(o.reshape(B, 1, H * Dh), lp["wo"]), (kp, vp, states, tails), read

    def mix(h, lp, i, carry):
        kp, vp, states, tails = carry
        z, xbc, dt = _mamba_inputs(cfg, h, lp)
        tail = jax.lax.dynamic_index_in_dim(tails, i, 0, keepdims=False)
        conv, seen = ssm.causal_conv(xbc, tail, lp["conv_w"], lp["conv_b"])
        new_tail = jnp.where(active[:, None, None], ssm.conv_tail(seen, one, K - 1), tail)
        x, b, c = _split_xbc(cfg, conv[:, 0])
        dt = dt[:, 0]
        y, states = ssd.ssd_state_step(states, i, active, dt[..., None] * x, jnp.exp(dt * -jnp.exp(lp["A_log"])), b, c)
        y = _mamba_out(cfg, y, x, z[:, 0], lp, h.dtype)[:, None]
        tails = jax.lax.dynamic_update_index_in_dim(tails, new_tail, i, 0)
        return y, (kp, vp, states, tails), jnp.sum(active, dtype=jnp.int32)

    carry = (k_pool["full"], v_pool["full"], k_pool["state"]["ssm"], k_pool["state"]["conv"])
    x, (kp, vp, states, tails), stats = _walk(cfg, params, x, carry, attend, mix, active[:, None])
    return (_logits(cfg, params, x[:, 0]), {"full": kp, "state": {"ssm": states, "conv": tails}},
            {"full": vp}, stats)


# -------------------------------------------------------------- paged chunk
def _chunk_row(cfg: NemotronHConfig, params: dict, tokens: jnp.ndarray, start: jnp.ndarray, capacity: jnp.ndarray,
               table: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray, states: jnp.ndarray,
               tails: jnp.ndarray) -> tuple:
    """ONE row's chunk of T prompt tokens [T] (-1 pads) through every
    layer: its K and V written through the row's ``table`` [M] (beyond
    ``capacity`` to the trash page), each attention layer over the row's
    pages after the write, the row's ``states`` [n_mamba, H, P, N] and
    ``tails`` [n_mamba, K-1, conv_dim] (zeroed here if the chunk starts the
    prompt) carried through the SSD scan. Returns the pools, the new
    states and tails, and the residual stream [D] at the row's last token."""
    T, Dh, page = tokens.shape[0], cfg.head_dim, k_pool.shape[3]
    M = table.shape[0]
    live = (tokens >= 0)[None]  # [1, T]
    n_new = jnp.sum(live, axis=1, dtype=jnp.int32)
    positions = start + jnp.arange(T)
    x = params["embedding"][jnp.maximum(tokens, 0)][None].astype(jnp.float32)
    pages, offsets = _targets(table[None], positions[None], live & (positions < capacity)[None], page,
                              k_pool.shape[1] - 1)
    seen = (jnp.arange(M * page)[None, :] <= positions[:, None])[None]  # [1, T, M page]

    def row_pages(pool: jnp.ndarray, j: Any) -> jnp.ndarray:  # layer j's pages of the row -> [1, M page, Hkv, Dh]
        return pool[j, table].transpose(0, 2, 1, 3).reshape(1, M * page, cfg.n_kv_heads, Dh)

    def attend(h, lp, j, carry):
        kp, vp, states, tails = carry
        q, k, v = _qkv(cfg, h, lp)
        kp = _write_rows(kp, j, pages[0], offsets[0], k[0])
        vp = _write_rows(vp, j, pages[0], offsets[0], v[0])
        o = _masked_attention(q, row_pages(kp, j), row_pages(vp, j), seen, _scale(cfg))
        return _mm(o.reshape(1, T, -1), lp["wo"]), (kp, vp, states, tails), jnp.int32(0)

    def mix(h, lp, i, carry):
        kp, vp, states, tails = carry
        state = jax.lax.dynamic_index_in_dim(states, i, 0).astype(jnp.float32)
        y, state, tail = _mamba_span(cfg, h, lp, state, jax.lax.dynamic_index_in_dim(tails, i, 0), live, n_new)
        states = jax.lax.dynamic_update_index_in_dim(states, state.astype(states.dtype), i, 0)
        return y, (kp, vp, states, jax.lax.dynamic_update_index_in_dim(tails, tail, i, 0)), jnp.int32(0)

    fresh = start == 0
    states, tails = jnp.where(fresh, jnp.zeros_like(states), states), jnp.where(fresh, jnp.zeros_like(tails), tails)
    x, (k_pool, v_pool, states, tails), _ = _walk(cfg, params, x, (k_pool, v_pool, states, tails), attend, mix, live)
    return k_pool, v_pool, states, tails, _last(x, n_new)[0]


@partial(jax.jit, static_argnums=0, donate_argnums=(3, 4))
def decode_chunk_paged(
    cfg: NemotronHConfig,
    params: dict,
    tokens: jnp.ndarray,  # [B, T] the next prompt tokens of each row (-1 pads)
    k_pool: dict,  # donated
    v_pool: dict,  # donated
    block_tables: dict,  # {"full": [B, M]}
    start_len: jnp.ndarray,  # [B] resident length BEFORE the chunk; 0 starts a prompt from a zero state
    active: jnp.ndarray,  # [B]
    kv_capacity: jnp.ndarray,  # [B] tokens covered by owned pages
) -> tuple[jnp.ndarray, dict, dict]:
    """A chunk of T prompt tokens a row against the pool and the states,
    with ``llama.decode_chunk_paged``'s arguments, ONE ROW AT A TIME under
    a ``cond`` (:func:`_chunk_row`): a row without a chunk runs nothing
    and keeps its state, so a dispatch costs its live rows. The row's
    state is taken out of the pool and put back outside the ``cond``,
    which carries the KV pools and the row's own 48 MiB alone. Returns
    (logits [B, 1, V] at each row's last chunk position — the head runs
    there alone — k_pool, v_pool)."""
    B = tokens.shape[0]

    def row(carry: tuple, xs: tuple) -> tuple:
        kp, vp, states, tails = carry
        toks, start, act, cap, table, b = xs
        state = jax.lax.dynamic_index_in_dim(states, b, 1, keepdims=False)
        tail = jax.lax.dynamic_index_in_dim(tails, b, 1, keepdims=False)

        def run(kp, vp):
            return _chunk_row(cfg, params, toks, start, cap, table, kp, vp, state, tail)

        def skip(kp, vp):
            return kp, vp, state, tail, jnp.zeros((cfg.d_model,), jnp.float32)

        kp, vp, state, tail, x = jax.lax.cond(act, run, skip, kp, vp)
        states = jax.lax.dynamic_update_index_in_dim(states, state, b, 1)
        return (kp, vp, states, jax.lax.dynamic_update_index_in_dim(tails, tail, b, 1)), x

    (kp, vp, states, tails), x = jax.lax.scan(
        row, (k_pool["full"], v_pool["full"], k_pool["state"]["ssm"], k_pool["state"]["conv"]),
        (tokens, start_len, active, kv_capacity, block_tables["full"], jnp.arange(B)))
    return _logits(cfg, params, x)[:, None], {"full": kp, "state": {"ssm": states, "conv": tails}}, {"full": vp}
