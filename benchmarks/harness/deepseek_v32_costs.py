"""What the ``deepseek_v32`` mathematics needs on ONE CHIP'S SHARE, from the
configuration's keys alone
(``benchmarks/configs/deepseek-v3.2-exp-ep8-int8.json``): operations and
bytes for the tokens that were served, exact contexts, no page rounding, no
masked or recomputed positions, and nothing read from the program.

The share: ``n_routed_experts`` routed experts are held of
``published.n_routed_experts``; a token takes ``num_experts_per_tok`` of the
published ones, so of the held ones ``num_experts_per_tok x held /
published`` on average (the groups are symmetric). Attention, indexer,
router and the shared expert are whole; the head is over the
``vocab_size`` rows held. ``first_k_dense_replace`` leading layers have a
dense MLP, the rest experts.

A token whose query sees ``n`` positions has all ``n`` scored by the
indexer and ``min(n, index_topk)`` read by attention. Decode counts the
absorbed form (every head against the latent row: what a latent cache
allows), a prompt's tokens the expanded one.
"""

from __future__ import annotations

from typing import Any, Iterable


def _i(c: dict[str, Any], key: str) -> int:
    return int(c[key])


def published_experts(c: dict[str, Any]) -> int:
    return int((c.get("published") or {}).get("n_routed_experts", c["n_routed_experts"]))


def attention_params(c: dict[str, Any]) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o."""
    d, h, rq, rkv = _i(c, "hidden_size"), _i(c, "num_attention_heads"), _i(c, "q_lora_rank"), _i(c, "kv_lora_rank")
    dn, dr, dv = _i(c, "qk_nope_head_dim"), _i(c, "qk_rope_head_dim"), _i(c, "v_head_dim")
    return d * rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) + h * dv * d


def indexer_params(c: dict[str, Any]) -> int:
    """W^I_qb, W^I_k and the head weights W^I_w."""
    d, hi, di = _i(c, "hidden_size"), _i(c, "index_n_heads"), _i(c, "index_head_dim")
    return _i(c, "q_lora_rank") * hi * di + d * di + d * hi


def dense_ffn_params(c: dict[str, Any]) -> int:
    return 3 * _i(c, "hidden_size") * _i(c, "intermediate_size")


def expert_params(c: dict[str, Any]) -> int:
    """One expert: gate, up and down, each hidden x moe_intermediate_size."""
    return 3 * _i(c, "hidden_size") * _i(c, "moe_intermediate_size")


def router_params(c: dict[str, Any]) -> int:
    return _i(c, "hidden_size") * published_experts(c)


def layer_counts(c: dict[str, Any]) -> tuple[int, int]:
    """(dense layers, expert layers) held here."""
    lead = min(_i(c, "first_k_dense_replace"), _i(c, "num_hidden_layers"))
    return lead, _i(c, "num_hidden_layers") - lead


def routed_pairs_per_token(c: dict[str, Any]) -> float:
    """Held experts a token takes in one expert layer, on average."""
    return _i(c, "num_experts_per_tok") * _i(c, "n_routed_experts") / published_experts(c)


def params_held(c: dict[str, Any]) -> int:
    """Matrix parameters of all layers resident on this chip (without the
    embedding and the head)."""
    dense, sparse = layer_counts(c)
    per_layer = attention_params(c) + indexer_params(c)
    experts = (_i(c, "n_routed_experts") + _i(c, "n_shared_experts")) * expert_params(c)
    return dense * (per_layer + dense_ffn_params(c)) + sparse * (per_layer + router_params(c) + experts)


def params_per_token(c: dict[str, Any]) -> float:
    """Parameters that take part in a product for one token on this chip,
    over all layers: attention, indexer, the dense MLPs, router and shared
    expert whole, the held share of its routed experts."""
    dense, sparse = layer_counts(c)
    per_layer = attention_params(c) + indexer_params(c)
    ffn = router_params(c) + (_i(c, "n_shared_experts") + routed_pairs_per_token(c)) * expert_params(c)
    return dense * (per_layer + dense_ffn_params(c)) + sparse * (per_layer + ffn)


def head_params(c: dict[str, Any]) -> int:
    return _i(c, "hidden_size") * _i(c, "vocab_size")


def weight_bytes(c: dict[str, Any]) -> int:
    """Resident weights as served: int8 matrices (the f32 scales and norms
    are under a thousandth of them and left out), float32 router and
    indexer head weights, bf16 embedding and head."""
    _, sparse = layer_counts(c)
    f32_extra = 3 * (sparse * router_params(c) + _i(c, "num_hidden_layers") * _i(c, "hidden_size") * _i(c, "index_n_heads"))
    return params_held(c) + f32_extra + 2 * 2 * head_params(c)


def cache_row_bytes(c: dict[str, Any], itemsize: int = 2, lanes: int = 128) -> tuple[int, int]:
    """Bytes a token keeps in one layer AS STORED: the latent row (latent
    and rope key, padded to whole lane tiles) and the indexer's key."""
    row = -(-(_i(c, "kv_lora_rank") + _i(c, "qk_rope_head_dim")) // lanes) * lanes
    return row * itemsize, _i(c, "index_head_dim") * itemsize


def kv_bytes_per_token(c: dict[str, Any]) -> int:
    return _i(c, "num_hidden_layers") * sum(cache_row_bytes(c))


def selected(c: dict[str, Any], contexts: Iterable[int]) -> int:
    """Positions attention reads, summed over queries that see ``contexts``
    positions each."""
    topk = _i(c, "index_topk")
    return sum(min(int(n), topk) for n in contexts)


def segment_contexts(start: int, tokens: int) -> range:
    """What each token of a piece of prompt sees: itself and all before."""
    return range(int(start) + 1, int(start) + int(tokens) + 1)


def indexer_flops(c: dict[str, Any], scored: int) -> int:
    """q^I · k^I for every head and the weighted sum, for ``scored``
    query-position pairs, over all layers."""
    hi, di = _i(c, "index_n_heads"), _i(c, "index_head_dim")
    return _i(c, "num_hidden_layers") * (2 * hi * di + 2 * hi) * int(scored)


def indexer_bytes(c: dict[str, Any], scored: int) -> int:
    """The indexer reads one key a scored position and layer."""
    return _i(c, "num_hidden_layers") * cache_row_bytes(c)[1] * int(scored)


def sparse_attention_flops(c: dict[str, Any], read: int, absorbed: bool = True) -> int:
    """Scores and weighted sums over ``read`` selected query-position
    pairs, all heads and layers: against the latent row (absorbed: 576 to
    score, 512 to sum) or per-head keys and values (expanded: 192 and 128)."""
    h, rkv, dr = _i(c, "num_attention_heads"), _i(c, "kv_lora_rank"), _i(c, "qk_rope_head_dim")
    per_pair = (rkv + dr) + rkv if absorbed else _i(c, "qk_nope_head_dim") + dr + _i(c, "v_head_dim")
    return _i(c, "num_hidden_layers") * 2 * h * per_pair * int(read)


def sparse_attention_bytes(c: dict[str, Any], read: int) -> int:
    """Decode attention reads one latent row, as stored, a selected
    position and layer."""
    return _i(c, "num_hidden_layers") * cache_row_bytes(c)[0] * int(read)


def served_flops(c: dict[str, Any], prefill_segments: list[tuple[int, int]], decode_contexts: list[int]) -> float:
    """FLOPs the model needs on this chip for the tokens of ``tok_s``'s
    numerator: 2 x the parameters a token meets, for every prompt token
    admitted and every token decoded; the head for every decoded token and
    once a prompt (its last position); the indexer over every position a
    token sees and attention over the positions it selects.
    ``prefill_segments`` are (start, tokens) pieces of prompts;
    ``decode_contexts`` the positions each decoded token's query saw."""
    tokens = sum(n for _, n in prefill_segments) + len(decode_contexts)
    prompts = sum(1 for start, _ in prefill_segments if start == 0)
    flops = 2.0 * params_per_token(c) * tokens + 2.0 * head_params(c) * (len(decode_contexts) + prompts)
    prompt_seen = [n for s, k in prefill_segments for n in segment_contexts(s, k)]
    flops += indexer_flops(c, sum(prompt_seen) + sum(decode_contexts))
    flops += sparse_attention_flops(c, selected(c, prompt_seen), absorbed=False)
    return flops + sparse_attention_flops(c, selected(c, decode_contexts), absorbed=True)


def expert_call_bytes(c: dict[str, Any], rows: float) -> float:
    """int8 bytes one layer's expert products have to read for ``rows``
    tokens: the held experts a token of the batch is expected to reach,
    ``held x (1 - (1 - k/published)^rows)``, and the shared expert."""
    k, e = _i(c, "num_experts_per_tok"), published_experts(c)
    touched = _i(c, "n_routed_experts") * (1.0 - (1.0 - k / e) ** float(rows))
    return (touched + _i(c, "n_shared_experts")) * expert_params(c)


def expert_call_flops(c: dict[str, Any], rows: float) -> float:
    """2 x an expert's parameters for every ROUTED row-expert pair of one
    layer — the held share of the pairs, not every held expert over every
    row — and every row's shared expert."""
    pairs = float(rows) * (routed_pairs_per_token(c) + _i(c, "n_shared_experts"))
    return 2.0 * expert_params(c) * pairs
