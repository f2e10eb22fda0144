"""What Nemotron 3 Nano's mathematics needs on ONE CHIP'S SHARE, from the
configuration's keys alone (``benchmarks/configs/nemotron-3-nano-30b-a3b-ep8-int8.json``):
parameters, cache and state bytes, operations and bytes for the tokens
that were served — exact contexts, no page rounding, no padded or masked
positions, the experts at their published widths (what the program stores
beyond them, its padding to whole lane tiles, counts against whoever reads
it) — and nothing read from the program.

The share: ``n_routed_experts`` routed experts are held of
``published.n_routed_experts``; a token takes ``num_experts_per_tok`` of
the published ones, so of the held ones ``num_experts_per_tok x held /
published`` on average. The Mamba mixers, attention, router and the shared
expert are whole; the head is over the ``vocab_size`` rows held. Layer
kinds come from ``hybrid_override_pattern``: ``M`` Mamba-2, ``E``
experts, ``*`` attention.
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness.deepseek_v32_costs import _i, published_experts, segment_contexts

MIB = 1 << 20


def layer_counts(c: dict[str, Any]) -> dict[str, int]:
    """Layers of each kind: ``mamba``, ``moe``, ``attn``."""
    pattern = c["hybrid_override_pattern"]
    return {"mamba": pattern.count("M"), "moe": pattern.count("E"), "attn": pattern.count("*")}


def d_inner(c: dict[str, Any]) -> int:
    return _i(c, "mamba_num_heads") * _i(c, "mamba_head_dim")


def conv_dim(c: dict[str, Any]) -> int:
    return d_inner(c) + 2 * _i(c, "n_groups") * _i(c, "ssm_state_size")


def mamba_params(c: dict[str, Any]) -> int:
    """W_in (z, xBC, dt) and W_out of one Mamba layer."""
    d = _i(c, "hidden_size")
    return d * (d_inner(c) + conv_dim(c) + _i(c, "mamba_num_heads")) + d_inner(c) * d


def attention_params(c: dict[str, Any]) -> int:
    d, q, kv = _i(c, "hidden_size"), _i(c, "num_attention_heads") * _i(c, "head_dim"), \
        _i(c, "num_key_value_heads") * _i(c, "head_dim")
    return 2 * d * q + 2 * d * kv


def expert_params(c: dict[str, Any], shared: bool = False) -> int:
    """W_up and W_down of one relu² expert at its published width."""
    width = _i(c, "moe_shared_expert_intermediate_size" if shared else "moe_intermediate_size")
    return 2 * _i(c, "hidden_size") * width


def router_params(c: dict[str, Any]) -> int:
    return _i(c, "hidden_size") * published_experts(c)


def expert_layer_params_held(c: dict[str, Any]) -> int:
    """Matrix parameters of one expert layer as this chip holds them: the
    shared expert and the held routed experts (the float32 router apart)."""
    return _i(c, "n_shared_experts") * expert_params(c, shared=True) + _i(c, "n_routed_experts") * expert_params(c)


def params_held(c: dict[str, Any]) -> int:
    """int8 matrix parameters of all layers resident on this chip."""
    n = layer_counts(c)
    return n["mamba"] * mamba_params(c) + n["attn"] * attention_params(c) + n["moe"] * expert_layer_params_held(c)


def routed_pairs_per_token(c: dict[str, Any]) -> float:
    """Held experts a token takes on average."""
    return _i(c, "num_experts_per_tok") * _i(c, "n_routed_experts") / published_experts(c)


def params_per_token(c: dict[str, Any]) -> float:
    """Parameters that take part in a product for one token on this chip,
    over all layers: Mamba and attention projections, the router and the
    shared expert whole, the held share of its routed experts."""
    n = layer_counts(c)
    ffn = router_params(c) + _i(c, "n_shared_experts") * expert_params(c, shared=True) \
        + routed_pairs_per_token(c) * expert_params(c)
    return n["mamba"] * mamba_params(c) + n["attn"] * attention_params(c) + n["moe"] * ffn


def head_params(c: dict[str, Any]) -> int:
    return _i(c, "hidden_size") * _i(c, "vocab_size")


def weight_bytes(c: dict[str, Any]) -> int:
    """Resident weights as served: int8 matrices (their f32 scales, the conv
    and the Mamba scalars are under a thousandth and left out), a float32
    router, bf16 embedding and head."""
    return params_held(c) + 4 * layer_counts(c)["moe"] * router_params(c) + 2 * 2 * head_params(c)


def state_bytes_per_layer(c: dict[str, Any]) -> int:
    """One Mamba layer's float32 SSD state of one slot: [H, P, N]."""
    return 4 * _i(c, "mamba_num_heads") * _i(c, "mamba_head_dim") * _i(c, "ssm_state_size")


def state_bytes_per_slot(c: dict[str, Any]) -> int:
    return layer_counts(c)["mamba"] * state_bytes_per_layer(c)


def conv_tail_bytes_per_slot(c: dict[str, Any]) -> int:
    """The bf16 conv tails of one slot: the last K - 1 inputs of xBC a Mamba layer."""
    return layer_counts(c)["mamba"] * (_i(c, "conv_kernel") - 1) * conv_dim(c) * 2


def kv_bytes_per_token(c: dict[str, Any]) -> int:
    """bf16 K and V a token keeps in the attention layers."""
    return layer_counts(c)["attn"] * 2 * _i(c, "num_key_value_heads") * _i(c, "head_dim") * 2


def cache_bytes(c: dict[str, Any], slots: int, slot_len: int) -> int:
    """The whole cache of ``slots`` slots of ``slot_len`` positions: KV and
    state."""
    return int(slots) * (int(slot_len) * kv_bytes_per_token(c) + state_bytes_per_slot(c)
                         + conv_tail_bytes_per_slot(c))


def state_step_bytes(c: dict[str, Any], layer_rows: float) -> float:
    """What ``ssd_state_step`` must move for ``layer_rows`` live row-layers
    (the device's ``ssm_rows``): each row's state of the layer read and
    written, its Delta x [H, P] and exp(Delta A) [H] in, B and C [G, N] in,
    y [H, P] out, all float32."""
    h, p, g, n = (_i(c, k) for k in ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size"))
    vectors = 4 * (2 * h * p + h + 2 * g * n)
    return float(layer_rows) * (2 * state_bytes_per_layer(c) + vectors)


def recurrence_flops(c: dict[str, Any], layer_positions: float) -> float:
    """The recurrence for ``layer_positions`` positions of Mamba layers:
    the state's decay and update (2 H P N) and its read by C (2 H P N)."""
    return 4.0 * _i(c, "mamba_num_heads") * _i(c, "mamba_head_dim") * _i(c, "ssm_state_size") * float(layer_positions)


def attention_flops(c: dict[str, Any], layer_positions: float) -> float:
    """Scores and sums of every query head over ``layer_positions``
    positions read."""
    return 4.0 * _i(c, "num_attention_heads") * _i(c, "head_dim") * float(layer_positions)


def served_flops(c: dict[str, Any], prefill_segments: list[tuple[int, int]], row_steps: int,
                 layer_positions: int) -> float:
    """FLOPs the model needs on this chip for the tokens of ``tok_s``'s
    numerator: 2 x the parameters a token meets for every prompt token
    admitted and every one of ``row_steps`` decoded; the head for every
    decoded token and once a prompt (its last position); the recurrence of
    every Mamba layer at every one of those positions; attention over
    ``layer_positions`` (the device's ``attn_full``) in decode and over
    exactly the positions each prompt token sees, in every attention layer."""
    tokens = sum(int(n) for _, n in prefill_segments)
    prompts = sum(1 for start, _ in prefill_segments if start == 0)
    n = layer_counts(c)
    flops = 2.0 * params_per_token(c) * (tokens + int(row_steps)) + 2.0 * head_params(c) * (int(row_steps) + prompts)
    flops += recurrence_flops(c, n["mamba"] * (tokens + int(row_steps)))
    seen = sum(sum(segment_contexts(s, k)) for s, k in prefill_segments)
    return flops + attention_flops(c, n["attn"] * seen + int(layer_positions))


def expert_bytes(c: dict[str, Any], shared: bool = False) -> int:
    """One relu² expert's int8 matrices at its published width and their
    f32 scales (W_up: one a column of F; W_down: one a column of D)."""
    width = _i(c, "moe_shared_expert_intermediate_size" if shared else "moe_intermediate_size")
    return expert_params(c, shared) + 4 * (width + _i(c, "hidden_size"))


def expert_flops(c: dict[str, Any], row_experts: float, shared_rows: float) -> float:
    """2 x an expert's parameters for every row-expert pair routed, and the
    shared expert's for every live row."""
    return 2.0 * (expert_params(c) * float(row_experts) + expert_params(c, shared=True) * float(shared_rows))
