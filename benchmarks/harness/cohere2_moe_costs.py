"""What the ``cohere2_moe`` mathematics needs on ONE CHIP'S SHARE, from the
configuration's keys alone (``benchmarks/configs/command-a-plus-ep8-int8.json``):
operations and bytes for the tokens that were served, exact lengths, no
page rounding, no masked or recomputed positions, and nothing read from
the program.

The share: ``num_experts`` routed experts are held of
``published.num_experts``; a token takes ``num_experts_per_tok`` of the
published ones, so of the held ones ``num_experts_per_tok x held /
published`` on average. Attention, router and shared experts are whole;
the head is over the ``vocab_size`` rows held.
"""

from __future__ import annotations

from typing import Any


def published_experts(c: dict[str, Any]) -> int:
    return int((c.get("published") or {}).get("num_experts", c["num_experts"]))


def attention_params(c: dict[str, Any]) -> int:
    d, dh = int(c["hidden_size"]), int(c["head_dim"])
    q, kv = int(c["num_attention_heads"]) * dh, int(c["num_key_value_heads"]) * dh
    return d * q + 2 * d * kv + q * d


def expert_params(c: dict[str, Any]) -> int:
    """One expert: gate, up and down, each hidden x intermediate."""
    return 3 * int(c["hidden_size"]) * int(c["intermediate_size"])


def router_params(c: dict[str, Any]) -> int:
    return int(c["hidden_size"]) * published_experts(c)


def layer_params_held(c: dict[str, Any]) -> int:
    """Parameters of one layer resident on this chip."""
    return (attention_params(c) + router_params(c)
            + (int(c["num_experts"]) + int(c["num_shared_experts"])) * expert_params(c))


def routed_pairs_per_token(c: dict[str, Any]) -> float:
    """Held experts a token takes in one layer, on average."""
    return int(c["num_experts_per_tok"]) * int(c["num_experts"]) / published_experts(c)


def layer_params_per_token(c: dict[str, Any]) -> float:
    """Parameters of one layer that take part in a product for one token
    on this chip: attention, router and shared experts whole, the held
    share of its routed experts."""
    return (attention_params(c) + router_params(c)
            + (int(c["num_shared_experts"]) + routed_pairs_per_token(c)) * expert_params(c))


def head_params(c: dict[str, Any]) -> int:
    return int(c["hidden_size"]) * int(c["vocab_size"])


def weight_bytes(c: dict[str, Any]) -> int:
    """Resident weights as served: int8 matrices with one f32 scale per
    output channel, float32 router, bf16 tied embedding, f32 norms."""
    d, f, L = int(c["hidden_size"]), int(c["intermediate_size"]), int(c["num_hidden_layers"])
    dh = int(c["head_dim"])
    q, kv = int(c["num_attention_heads"]) * dh, int(c["num_key_value_heads"]) * dh
    n = int(c["num_experts"]) + int(c["num_shared_experts"])
    matrices = attention_params(c) + n * expert_params(c)
    out_channels = q + 2 * kv + d + n * (2 * f + d)
    return L * (matrices + 4 * out_channels + 4 * router_params(c) + 4 * d) + 2 * head_params(c) + 4 * d


def kv_bytes_per_token(c: dict[str, Any], kv_itemsize: int = 2) -> int:
    return 2 * int(c["num_hidden_layers"]) * int(c["num_key_value_heads"]) * int(c["head_dim"]) * kv_itemsize


def attention_flops(c: dict[str, Any], positions: int, longest_context: int) -> int:
    """QK^T and PV for query tokens that attend to ``positions`` positions
    in all (summed over the tokens), over all layers and heads. A sliding
    layer sees at most ``sliding_window`` of a token's context, which a
    sum over tokens cannot tell: exact while no context passes the window,
    and refused beyond it."""
    if int(longest_context) > int(c["sliding_window"]):
        raise ValueError("a context passes the window: attention over exact positions needs each token's context")
    return 4 * int(c["num_hidden_layers"]) * int(c["num_attention_heads"]) * int(c["head_dim"]) * int(positions)


def served_flops(c: dict[str, Any], prefill_segments: list[tuple[int, int]], decode_tokens: int,
                 resident_positions: int, longest_context: int) -> float:
    """FLOPs the model needs on this chip for the tokens of ``tok_s``'s
    numerator: 2 x the layer parameters a token meets, for every prompt
    token admitted and every token decoded; the head for every decoded
    token and once a prompt (its last position); attention over exactly
    the positions each token sees. ``prefill_segments`` are (start,
    tokens) pieces of prompts; ``resident_positions`` the sum, over
    decoded tokens, of the context each read."""
    tokens = sum(n for _, n in prefill_segments) + int(decode_tokens)
    prompts = sum(1 for start, _ in prefill_segments if start == 0)
    flops = 2.0 * int(c["num_hidden_layers"]) * layer_params_per_token(c) * tokens
    flops += 2.0 * head_params(c) * (int(decode_tokens) + prompts)
    seen = sum(n * s + n * (n + 1) // 2 for s, n in prefill_segments) + int(resident_positions)
    return flops + attention_flops(c, seen, longest_context)


def expert_call_bytes(c: dict[str, Any], rows: float) -> float:
    """int8 bytes one layer's expert products have to read for ``rows``
    tokens: the held experts a token of the batch is expected to reach,
    ``held x (1 - (1 - k/published)^rows)``, and the shared experts."""
    k, e = int(c["num_experts_per_tok"]), published_experts(c)
    touched = int(c["num_experts"]) * (1.0 - (1.0 - k / e) ** float(rows))
    return (touched + int(c["num_shared_experts"])) * expert_params(c)


def expert_call_flops(c: dict[str, Any], rows: float) -> float:
    """2 x an expert's parameters for every row-expert pair of one layer:
    the held share of the routed pairs and every row's shared experts."""
    pairs = float(rows) * (routed_pairs_per_token(c) + int(c["num_shared_experts"]))
    return 2.0 * expert_params(c) * pairs
