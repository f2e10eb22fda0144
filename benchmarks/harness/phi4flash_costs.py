"""What the ``phi4flash`` mathematics needs, from the configuration's keys
alone (``benchmarks/configs/phi-4-mini-flash-reasoning-int8.json``):
operations and bytes for the tokens that were served, exact contexts, no
page rounding, no masked or recomputed positions, and nothing read from the
program. FLOPs are the model's — 40 query heads of 64 — never the padded
queries' of whatever implements the pairs.

Layers, with n = ``num_hidden_layers`` and ``mb_per_layer`` 2: n/4 + 1
Mamba (l even, l <= n/2), n/4 window attention (l odd, l < n/2), one full
attention (l = n/2 + 1), n/4 - 1 gated memory units and as many
cross-attention layers above it. Each has an MLP of 3 x hidden x
intermediate.

A decode step streams every weight and the tied head once. Its attention
reads, a live row of context c: the ONE cached layer (n/4 - 1) + 1 times —
its own layer and every cross-attention layer — and the last min(c, window)
positions of each of the n/4 window layers, at 2 x kv heads x head_dim x 2 B
a position and layer. Its recurrences read and write a live row's state
S (d_inner x d_state float32) in each Mamba layer.

A prompt of T tokens needs the layers up to the full layer's K and V over
T positions and everything above on one (the program and the reference
agree on the result; the work above is not the mathematics' need).
"""

from __future__ import annotations

from typing import Any, Iterable


def _i(c: dict[str, Any], key: str) -> int:
    return int(c[key])


def layer_counts(c: dict[str, Any]) -> dict[str, int]:
    n = _i(c, "num_hidden_layers")
    return {"mamba": n // 4 + 1, "window": n // 4, "full": 1, "gmu": n // 4 - 1, "cross": n // 4 - 1}


def d_inner(c: dict[str, Any]) -> int:
    return _i(c, "mamba_expand") * _i(c, "hidden_size")


def mlp_params(c: dict[str, Any]) -> int:
    return 3 * _i(c, "hidden_size") * _i(c, "intermediate_size")


def mixer_params(c: dict[str, Any]) -> dict[str, int]:
    """The matrices of one layer's mixer, by kind (what a token's product
    runs through; the conv, A_log, D, biases and norms are beside them)."""
    d, h, hkv, dh = _i(c, "hidden_size"), _i(c, "num_attention_heads"), _i(c, "num_key_value_heads"), _i(c, "head_dim")
    din, n, r = d_inner(c), _i(c, "mamba_d_state"), _i(c, "mamba_dt_rank")
    attn = d * (h + 2 * hkv) * dh + h * dh * d
    return {"mamba": d * 2 * din + din * d + din * (r + 2 * n) + r * din, "window": attn, "full": attn,
            "gmu": 2 * d * din, "cross": 2 * d * h * dh}


def small_params(c: dict[str, Any]) -> int:
    """What is no large matrix: the conv with its bias, dt's bias, A_log
    and D of each Mamba layer; the biases, lambda vectors and sub-layer
    norm of each attention layer; two LayerNorms a layer and the final."""
    d, h, hkv, dh = _i(c, "hidden_size"), _i(c, "num_attention_heads"), _i(c, "num_key_value_heads"), _i(c, "head_dim")
    din, n, k = d_inner(c), _i(c, "mamba_d_state"), _i(c, "mamba_d_conv")
    counts = layer_counts(c)
    mamba = k * din + din + din + n * din + din
    own_kv = (h + 2 * hkv) * dh + d + 4 * dh + 2 * dh
    cross = h * dh + d + 4 * dh + 2 * dh
    return (counts["mamba"] * mamba + (counts["window"] + counts["full"]) * own_kv + counts["cross"] * cross
            + (2 * _i(c, "num_hidden_layers") + 1) * 2 * d)


def self_matrix_params(c: dict[str, Any]) -> int:
    """Matrices of the layers that run over every position of a prompt:
    the Mamba and window layers with their MLPs, and the full layer's
    K and V projections."""
    d, hkv, dh = _i(c, "hidden_size"), _i(c, "num_key_value_heads"), _i(c, "head_dim")
    counts, mix = layer_counts(c), mixer_params(c)
    return (counts["mamba"] * (mix["mamba"] + mlp_params(c)) + counts["window"] * (mix["window"] + mlp_params(c))
            + d * 2 * hkv * dh)


def layer_matrix_params(c: dict[str, Any]) -> int:
    counts, mix = layer_counts(c), mixer_params(c)
    return sum(counts[kind] * (mix[kind] + mlp_params(c)) for kind in counts)


def matrix_params(c: dict[str, Any]) -> int:
    """Parameters in a matrix product per decoded token: every layer's
    and the tied head."""
    return layer_matrix_params(c) + _i(c, "hidden_size") * _i(c, "vocab_size")


def total_params(c: dict[str, Any]) -> int:
    """All parameters; the tied embedding counts once."""
    return matrix_params(c) + small_params(c)


def weight_bytes(c: dict[str, Any]) -> int:
    """Resident weights as served: int8 large matrices (all but W_x and
    W_dt) with one f32 scale per output channel, float32 W_x, W_dt and
    small parameters, the bf16 embedding."""
    d, f, h, hkv, dh = (_i(c, "hidden_size"), _i(c, "intermediate_size"), _i(c, "num_attention_heads"),
                        _i(c, "num_key_value_heads"), _i(c, "head_dim"))
    din, n, r = d_inner(c), _i(c, "mamba_d_state"), _i(c, "mamba_dt_rank")
    counts = layer_counts(c)
    f32_matrices = counts["mamba"] * (din * (r + 2 * n) + r * din)
    int8 = layer_matrix_params(c) - f32_matrices
    channels = (_i(c, "num_hidden_layers") * (2 * f + d) + counts["mamba"] * (2 * din + d)
                + (counts["window"] + counts["full"]) * ((h + 2 * hkv) * dh + d) + counts["gmu"] * (din + d)
                + counts["cross"] * (h * dh + d))
    return int8 + 4 * channels + 4 * (f32_matrices + small_params(c)) + 2 * _i(c, "vocab_size") * d


def kv_bytes_per_position(c: dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one position in ONE layer that stores them."""
    return 2 * _i(c, "num_key_value_heads") * _i(c, "head_dim") * itemsize


def state_bytes_per_layer(c: dict[str, Any]) -> int:
    """A slot's recurrent state S in one Mamba layer, float32."""
    return d_inner(c) * _i(c, "mamba_d_state") * 4


def slot_state_bytes(c: dict[str, Any]) -> int:
    """What a slot holds that is no page: S and the bf16 conv tail of every Mamba layer."""
    tail = (_i(c, "mamba_d_conv") - 1) * d_inner(c) * 2
    return layer_counts(c)["mamba"] * (state_bytes_per_layer(c) + tail)


def cache_bytes(c: dict[str, Any], slots: int, max_seq_len: int, page: int = 16) -> dict[str, int]:
    """The cell's cache as the pager builds it: the full layer's pool for
    every position, a ring of ceil(window / page) + 1 pages a slot in each
    window layer, the state."""
    counts = layer_counts(c)
    ring = -(-_i(c, "sliding_window") // page) + 1
    return {"full": slots * max_seq_len * kv_bytes_per_position(c),
            "window": slots * ring * page * counts["window"] * kv_bytes_per_position(c),
            "state": slots * slot_state_bytes(c)}


def attention_positions(c: dict[str, Any], contexts: Iterable[int]) -> tuple[int, int]:
    """(positions of the one cached layer read — by its own layer and
    every cross-attention layer — positions of the window layers read), over
    decoded tokens whose queries saw ``contexts`` positions."""
    counts, window = layer_counts(c), _i(c, "sliding_window")
    contexts = [int(n) for n in contexts]
    return ((counts["cross"] + counts["full"]) * sum(contexts),
            counts["window"] * sum(min(n, window) for n in contexts))


def attention_bytes(c: dict[str, Any], contexts: Iterable[int]) -> int:
    """Bytes the decode steps' attention had to read: a row and step,
    ``(8 x context + 8 x min(context, 512)) x 5,120`` at the published sizes."""
    full, win = attention_positions(c, contexts)
    return (full + win) * kv_bytes_per_position(c)


def attention_flops(c: dict[str, Any], positions: int) -> int:
    """QK^T over head_dim for every query head and the pair's difference
    times [V | V'] for every pair, of ``positions`` layer-positions:
    4 x heads x head_dim each, as any attention."""
    return 4 * _i(c, "num_attention_heads") * _i(c, "head_dim") * int(positions)


def state_bytes(c: dict[str, Any], live_row_steps: int) -> int:
    """Bytes the decode steps' recurrences had to move: S read and
    written in each Mamba layer, a live row and step."""
    return layer_counts(c)["mamba"] * 2 * state_bytes_per_layer(c) * int(live_row_steps)


def scan_flops(c: dict[str, Any], tokens: int) -> int:
    """The recurrence's elementwise work a token: decay times state, the
    drive, their sum, and the contraction with C — 6 a state element and
    Mamba layer (exp not counted)."""
    return 6 * layer_counts(c)["mamba"] * d_inner(c) * _i(c, "mamba_d_state") * int(tokens)


def step_bytes(c: dict[str, Any], contexts: Iterable[int]) -> dict[str, int]:
    """One decode step over live rows of ``contexts``: weights and head
    streamed once, attention, state."""
    contexts = list(contexts)
    return {"weights": weight_bytes(c), "attention": attention_bytes(c, contexts),
            "state": state_bytes(c, len(contexts))}


def served_flops(c: dict[str, Any], prefill_segments: list[tuple[int, int]], row_steps: int,
                 attention_layer_positions: int) -> int:
    """FLOPs the model needs for the tokens of ``tok_s``'s numerator.
    ``row_steps`` decoded tokens: 2 x every matrix and the recurrences
    each, and attention over ``attention_layer_positions`` layer-positions
    in all (:func:`attention_positions` of their contexts, summed: n in
    the cached layer's readers, min(n, window) in the window layers). A
    piece of prompt (start, tokens): the layers up to the full layer's K
    and V over its tokens (window attention of token i over min(start + i,
    window) positions), and — once a prompt, with the piece that starts
    it — everything above on one position."""
    counts, window = layer_counts(c), _i(c, "sliding_window")
    flops = (2 * matrix_params(c) * int(row_steps) + attention_flops(c, attention_layer_positions)
             + scan_flops(c, row_steps))
    upper = matrix_params(c) - self_matrix_params(c)
    for start, n in prefill_segments:
        seen = sum(min(start + i, window) for i in range(1, int(n) + 1))
        flops += 2 * self_matrix_params(c) * int(n) + attention_flops(c, counts["window"] * seen) + scan_flops(c, n)
        if start == 0:
            flops += 2 * upper
    return flops
