"""What the ``lfm2_moe`` mathematics needs, from the configuration's keys
alone (``benchmarks/configs/lfm2-8b-a1b-int8.json``): parameters, bytes
and operations for the tokens that were served, exact contexts, no page
rounding, no masked or padded positions, and nothing read from the
program. Every function counts the WORK THE MODEL NEEDED, never the work
a path performed: an expert product that runs every expert over every row
is measured against the row-experts chosen and the experts reached.

Layers, from ``layer_types``: 18 conv mixers and 6 attention mixers; the
first ``num_dense_layers`` have a dense SwiGLU of ``intermediate_size``,
the other 22 an expert layer of ``num_experts`` experts of
``moe_intermediate_size`` of which a token takes ``num_experts_per_tok``.
The head is tied to the embedding and counted once.
"""

from __future__ import annotations

from typing import Any


def _i(c: dict[str, Any], key: str) -> int:
    return int(c[key])


def head_dim(c: dict[str, Any]) -> int:
    return int(c.get("head_dim") or _i(c, "hidden_size") // _i(c, "num_attention_heads"))


def layer_counts(c: dict[str, Any]) -> dict[str, int]:
    kinds, dense = list(c["layer_types"]), _i(c, "num_dense_layers")
    return {"conv": kinds.count("conv"), "attn": kinds.count("full_attention"), "dense": dense,
            "moe": len(kinds) - dense}


def conv_params(c: dict[str, Any]) -> int:
    """One conv mixer's matrices: W_in (hidden x 3 hidden) and W_out."""
    d = _i(c, "hidden_size")
    return d * 3 * d + d * d


def attention_params(c: dict[str, Any]) -> int:
    d, dh = _i(c, "hidden_size"), head_dim(c)
    q, kv = _i(c, "num_attention_heads") * dh, _i(c, "num_key_value_heads") * dh
    return d * q + 2 * d * kv + q * d


def dense_params(c: dict[str, Any]) -> int:
    return 3 * _i(c, "hidden_size") * _i(c, "intermediate_size")


def expert_params(c: dict[str, Any]) -> int:
    """One expert: gate, up and down, each hidden x moe_intermediate_size."""
    return 3 * _i(c, "hidden_size") * _i(c, "moe_intermediate_size")


def router_params(c: dict[str, Any]) -> int:
    return _i(c, "hidden_size") * _i(c, "num_experts")


def embedding_params(c: dict[str, Any]) -> int:
    return _i(c, "vocab_size") * _i(c, "hidden_size")


def small_params(c: dict[str, Any]) -> int:
    """What is no large matrix: two norms a layer and the final, the
    per-head q and k norms, the conv taps, the expert biases."""
    d, n = _i(c, "hidden_size"), layer_counts(c)
    return ((2 * _i(c, "num_hidden_layers") + 1) * d + n["attn"] * 2 * head_dim(c)
            + n["conv"] * _i(c, "conv_L_cache") * d + n["moe"] * _i(c, "num_experts"))


def matrix_params(c: dict[str, Any]) -> dict[str, int]:
    """The int8 matrices by kind, summed over the layers, and the float32
    routers."""
    n = layer_counts(c)
    return {"experts": n["moe"] * _i(c, "num_experts") * expert_params(c), "conv": n["conv"] * conv_params(c),
            "attn": n["attn"] * attention_params(c), "dense": n["dense"] * dense_params(c),
            "router": n["moe"] * router_params(c)}


def total_params(c: dict[str, Any]) -> int:
    """All parameters; the tied embedding counts once."""
    return sum(matrix_params(c).values()) + embedding_params(c) + small_params(c)


def active_params(c: dict[str, Any]) -> int:
    """Parameters in a matrix product for one decoded token: every mixer,
    the dense MLPs, the routers, ``num_experts_per_tok`` experts of each
    expert layer, and the tied head."""
    m, n = matrix_params(c), layer_counts(c)
    chosen = n["moe"] * _i(c, "num_experts_per_tok") * expert_params(c)
    return m["conv"] + m["attn"] + m["dense"] + m["router"] + chosen + embedding_params(c)


def expert_bytes(c: dict[str, Any]) -> int:
    """One expert as served: int8 gate, up and down, and their f32 scales
    (one an output channel)."""
    d, f = _i(c, "hidden_size"), _i(c, "moe_intermediate_size")
    return expert_params(c) + 4 * (2 * f + d)


def weight_bytes(c: dict[str, Any]) -> int:
    """Resident weights as served: int8 matrices with one f32 scale per
    output channel, float32 routers and small parameters, the bf16 tied
    embedding."""
    d, f, dh = _i(c, "hidden_size"), _i(c, "intermediate_size"), head_dim(c)
    m, n = matrix_params(c), layer_counts(c)
    q, kv = _i(c, "num_attention_heads") * dh, _i(c, "num_key_value_heads") * dh
    channels = n["conv"] * (3 * d + d) + n["attn"] * (q + 2 * kv + d) + n["dense"] * (2 * f + d)
    int8 = m["conv"] + m["attn"] + m["dense"] + 4 * channels
    experts = n["moe"] * _i(c, "num_experts") * expert_bytes(c)
    return int8 + experts + 4 * (m["router"] + small_params(c)) + 2 * embedding_params(c)


def kv_bytes_per_position(c: dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one position in ONE attention layer."""
    return 2 * _i(c, "num_key_value_heads") * head_dim(c) * itemsize


def kv_bytes_per_token(c: dict[str, Any]) -> int:
    return layer_counts(c)["attn"] * kv_bytes_per_position(c)


def tail_bytes_per_layer(c: dict[str, Any]) -> int:
    """A slot's conv tail in one conv layer: the last conv_L_cache - 1
    values of v, float32."""
    return (_i(c, "conv_L_cache") - 1) * _i(c, "hidden_size") * 4


def slot_state_bytes(c: dict[str, Any]) -> int:
    return layer_counts(c)["conv"] * tail_bytes_per_layer(c)


def cache_bytes(c: dict[str, Any], slots: int, max_seq_len: int) -> dict[str, int]:
    """The cell's cache as the pager builds it: every position of the
    attention layers' pool, the tails of every slot."""
    return {"kv": slots * max_seq_len * kv_bytes_per_token(c), "state": slots * slot_state_bytes(c)}


def attention_flops(c: dict[str, Any], positions: int) -> int:
    """QK^T and PV over ``positions`` layer-positions (the sum, over the
    attention layers and the query tokens, of the context each read):
    4 x heads x head_dim each."""
    return 4 * _i(c, "num_attention_heads") * head_dim(c) * int(positions)


def prompt_positions(c: dict[str, Any], prefill_segments: list[tuple[int, int]]) -> int:
    """Layer-positions the attention layers read for pieces of prompt
    (start, tokens): token i of a piece sees start + i positions."""
    return layer_counts(c)["attn"] * sum(n * s + n * (n + 1) // 2 for s, n in prefill_segments)


def served_flops(c: dict[str, Any], prefill_segments: list[tuple[int, int]], row_steps: int,
                 attention_layer_positions: int) -> int:
    """FLOPs the model needs for the tokens of ``tok_s``'s numerator:
    ``row_steps`` decoded tokens at 2 x the active parameters each and
    attention over ``attention_layer_positions`` (what the attention
    layers read, the device's ``attn_kv``); every prompt token at 2 x the
    active parameters but the head, the head once a prompt (at its last
    position), attention over exactly the positions each token sees."""
    per_token, head = 2 * active_params(c), 2 * embedding_params(c)
    tokens = sum(int(n) for _, n in prefill_segments)
    prompts = sum(1 for start, _ in prefill_segments if start == 0)
    return (per_token * (int(row_steps) + tokens) - head * (tokens - prompts)
            + attention_flops(c, int(attention_layer_positions) + prompt_positions(c, prefill_segments)))


def expert_flops(c: dict[str, Any], row_experts: float) -> float:
    """2 x an expert's parameters for every row-expert pair routed."""
    return 2.0 * expert_params(c) * float(row_experts)


def conv_bytes(c: dict[str, Any], live_row_steps: int, layer_steps: int) -> int:
    """Bytes the conv mixers had to move: W_in and W_out with their
    scales once a layer and step (``layer_steps`` conv layer-steps), and
    for every live row of each (``live_row_steps`` = the device's
    ``conv_rows``) its tail read and written and its activation in and out
    (bf16)."""
    d = _i(c, "hidden_size")
    weights = conv_params(c) + 4 * (3 * d + d)
    per_row = 2 * tail_bytes_per_layer(c) + 2 * d * 2
    return int(layer_steps) * weights + int(live_row_steps) * per_row
