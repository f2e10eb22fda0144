"""What JoyAI-LLM-Flash's mathematics needs on ONE CHIP'S SHARE, from the
configuration's keys alone (``benchmarks/configs/joyai-llm-flash-ep8-int8.json``):
operations and bytes for the tokens that were served, exact contexts, no
page rounding, no padded or masked positions, and nothing read from the
program.

The share: ``n_routed_experts`` routed experts are held of
``published.n_routed_experts``; a token takes ``num_experts_per_tok`` of
the published ones, so of the held ones ``num_experts_per_tok x held /
published`` on average. Attention, router and the shared expert are whole;
the head is over the ``vocab_size`` rows held. ``first_k_dense_replace``
leading layers have a dense MLP, the rest experts. There is no indexer:
every position a token sees is read by attention.

Decode counts the absorbed form (every head against the latent row: the
576 values ``kv_lora_rank + qk_rope_head_dim`` a position needs, 1,152 B in
bf16; the row is STORED padded to whole lane tiles, 640, and the pad counts
against whoever reads it); a prompt's tokens the expanded form.
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness.deepseek_v32_costs import (  # noqa: F401  (this family's cost functions too)
    _i, attention_params, dense_ffn_params, expert_call_bytes, expert_call_flops, expert_params, head_params,
    layer_counts, published_experts, routed_pairs_per_token, router_params, segment_contexts,
)

LANES = 128


def expert_layer_params_held(c: dict[str, Any]) -> int:
    """Matrix parameters of one expert layer as this chip holds it:
    attention, router, the shared expert and the held routed experts."""
    experts = (_i(c, "n_routed_experts") + _i(c, "n_shared_experts")) * expert_params(c)
    return attention_params(c) + router_params(c) + experts


def params_held(c: dict[str, Any]) -> int:
    """Matrix parameters of all layers resident on this chip (without the
    embedding and the head)."""
    dense, sparse = layer_counts(c)
    return dense * (attention_params(c) + dense_ffn_params(c)) + sparse * expert_layer_params_held(c)


def params_per_token(c: dict[str, Any]) -> float:
    """Parameters that take part in a product for one token on this chip,
    over all layers: attention, the dense MLP, router and shared expert
    whole, the held share of its routed experts."""
    dense, sparse = layer_counts(c)
    ffn = router_params(c) + (_i(c, "n_shared_experts") + routed_pairs_per_token(c)) * expert_params(c)
    return dense * (attention_params(c) + dense_ffn_params(c)) + sparse * (attention_params(c) + ffn)


def weight_bytes(c: dict[str, Any]) -> int:
    """Resident weights as served: int8 matrices (the f32 scales and norms
    are under a thousandth of them and left out), a float32 router, bf16
    embedding and head."""
    _, sparse = layer_counts(c)
    return params_held(c) + 3 * sparse * router_params(c) + 2 * 2 * head_params(c)


def latent_values(c: dict[str, Any]) -> int:
    """What a position's latent row holds that attention needs: the latent
    and the rope key, 576."""
    return _i(c, "kv_lora_rank") + _i(c, "qk_rope_head_dim")


def latent_row_bytes(c: dict[str, Any], itemsize: int = 2) -> tuple[int, int]:
    """(bytes a position and layer NEEDS, bytes it is STORED in): 1,152 and
    1,280 (padded to whole lane tiles)."""
    n = latent_values(c)
    return n * itemsize, -(-n // LANES) * LANES * itemsize


def kv_bytes_per_token(c: dict[str, Any]) -> int:
    """Cache a token keeps over all layers, as stored."""
    return _i(c, "num_hidden_layers") * latent_row_bytes(c)[1]


def latent_read_bytes(c: dict[str, Any], layer_positions: int) -> int:
    """Bytes decode attention needs for ``layer_positions`` positions read
    (summed over rows and layers: the device's ``mla_kv``): the latent and
    rope key of each, as bf16."""
    return latent_row_bytes(c)[0] * int(layer_positions)


def latent_attention_flops(c: dict[str, Any], layer_positions: int) -> int:
    """Absorbed decode attention over ``layer_positions`` positions: every
    head scores the 576 values and sums the 512 latents of each."""
    return 2 * _i(c, "num_attention_heads") * (latent_values(c) + _i(c, "kv_lora_rank")) * int(layer_positions)


def expanded_attention_flops(c: dict[str, Any], layer_positions: int) -> int:
    """Prefill attention in the published form over ``layer_positions``
    query-position pairs: per head 192 to score and 128 to sum."""
    per_pair = _i(c, "qk_nope_head_dim") + _i(c, "qk_rope_head_dim") + _i(c, "v_head_dim")
    return 2 * _i(c, "num_attention_heads") * per_pair * int(layer_positions)


def served_flops(c: dict[str, Any], prefill_segments: list[tuple[int, int]], row_steps: int,
                 layer_positions: int) -> float:
    """FLOPs the model needs on this chip for the tokens of ``tok_s``'s
    numerator: 2 x the parameters a token meets for every prompt token
    admitted and every one of ``row_steps`` decoded; the head for every
    decoded token and once a prompt (its last position); attention over
    ``layer_positions`` (the device's ``mla_kv``) in decode and over exactly
    the positions each prompt token sees, in every layer."""
    tokens = sum(int(n) for _, n in prefill_segments)
    prompts = sum(1 for start, _ in prefill_segments if start == 0)
    flops = 2.0 * params_per_token(c) * (tokens + int(row_steps)) + 2.0 * head_params(c) * (int(row_steps) + prompts)
    seen = sum(sum(segment_contexts(s, k)) for s, k in prefill_segments)
    flops += expanded_attention_flops(c, _i(c, "num_hidden_layers") * seen)
    return flops + latent_attention_flops(c, layer_positions)


def expert_bytes(c: dict[str, Any]) -> int:
    """One expert's int8 matrices and their f32 scales (gate and up: one a
    column of F; down: one a column of D)."""
    d, f = _i(c, "hidden_size"), _i(c, "moe_intermediate_size")
    return expert_params(c) + 4 * (2 * f + d)


def expert_flops(c: dict[str, Any], row_experts: float) -> float:
    """2 x an expert's parameters for every row-expert pair routed."""
    return 2.0 * expert_params(c) * float(row_experts)


def cache_bytes(c: dict[str, Any], slots: int, slot_len: int) -> int:
    return int(slots) * int(slot_len) * kv_bytes_per_token(c)
