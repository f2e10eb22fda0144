"""What the algorithm needs, from the configuration's shapes alone.

These count the operations and bytes the model's mathematics requires for
the tokens that were served: exact lengths, no page rounding, no masked or
recomputed positions. They take the published config keys (the HF names in
``benchmarks/configs/*.json``), never a shape read from the program.
"""

from __future__ import annotations

from typing import Any


def head_dim(c: dict[str, Any]) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def layer_matrix_params(c: dict[str, Any]) -> int:
    d, f = int(c["hidden_size"]), int(c["intermediate_size"])
    q = int(c["num_attention_heads"]) * head_dim(c)
    kv = int(c["num_key_value_heads"]) * head_dim(c)
    return d * q + 2 * d * kv + q * d + 3 * d * f


def matrix_params(c: dict[str, Any]) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's seven matrices and the output head (the embedding is a lookup)."""
    return int(c["num_hidden_layers"]) * layer_matrix_params(c) + int(c["hidden_size"]) * int(c["vocab_size"])


def total_params(c: dict[str, Any]) -> int:
    """All parameters: matrices, the embedding table, the norm vectors."""
    d, L = int(c["hidden_size"]), int(c["num_hidden_layers"])
    embed = int(c["vocab_size"]) * d
    tied = bool(c.get("tie_word_embeddings", False))
    return matrix_params(c) + (0 if tied else embed) + (2 * L + 1) * d


def kv_bytes_per_token(c: dict[str, Any], kv_itemsize: int = 2) -> int:
    """Keys and values one position adds, over all layers."""
    return 2 * int(c["num_hidden_layers"]) * int(c["num_key_value_heads"]) * head_dim(c) * kv_itemsize


def weight_bytes(c: dict[str, Any]) -> int:
    """Resident weights as served: int8 matrices with one f32 scale per
    output channel, bf16 embedding, f32 norms."""
    d, f, L, v = (int(c["hidden_size"]), int(c["intermediate_size"]),
                  int(c["num_hidden_layers"]), int(c["vocab_size"]))
    q = int(c["num_attention_heads"]) * head_dim(c)
    kv = int(c["num_key_value_heads"]) * head_dim(c)
    out_channels = L * (q + 2 * kv + d + 2 * f + d) + v
    return matrix_params(c) + 4 * out_channels + 2 * v * d + 4 * (2 * L + 1) * d


def attention_flops(c: dict[str, Any], context_positions: int) -> int:
    """QK^T and PV for ONE query token that attends to
    ``context_positions`` positions, over all layers and heads."""
    return 4 * int(c["num_hidden_layers"]) * int(c["num_attention_heads"]) * head_dim(c) * int(context_positions)


def prefill_attention_flops(c: dict[str, Any], prompt_tokens: int) -> int:
    """Causal attention over a prompt: token i attends to i positions."""
    n = int(prompt_tokens)
    return attention_flops(c, n * (n + 1) // 2)


def served_flops(c: dict[str, Any], prefill_segments: list[tuple[int, int]],
                 decode_tokens: int, resident_positions: int) -> int:
    """FLOPs the model needs for the tokens of ``tok_s``'s numerator:
    2 x matrix parameters for every prompt token admitted and every token
    decoded, plus attention over exactly the positions each one sees.
    ``prefill_segments`` are (start, tokens) pieces of prompts — a whole
    prompt is (0, n), a chunk starts where the last one ended: token i of
    a piece attends to start + i positions. ``resident_positions`` is the
    sum, over decoded tokens, of the context each read
    (stats.window_tokens)."""
    tokens = sum(n for _, n in prefill_segments) + int(decode_tokens)
    flops = 2 * matrix_params(c) * tokens
    flops += sum(attention_flops(c, n * s + n * (n + 1) // 2) for s, n in prefill_segments)
    flops += attention_flops(c, resident_positions)
    return flops


def paged_attention_bytes(c: dict[str, Any], resident_positions: int,
                          kv_itemsize: int = 2) -> int:
    """Bytes the decode-attention calls had to read: keys and values of
    every resident position of every decoding row, over all layers. The
    queries and outputs are some KB a call and are left out, which only
    lowers the share."""
    return kv_bytes_per_token(c, kv_itemsize) * int(resident_positions)


def paged_attention_flops(c: dict[str, Any], resident_positions: int) -> int:
    return attention_flops(c, resident_positions)
