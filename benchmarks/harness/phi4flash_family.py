"""Factory for the ``phi4flash`` decoder the engine serves through
``gofr_tpu.models.phi4flash``: state-space and window-attention layers, one
full-attention layer whose K and V the whole upper half reads, gated memory
units and cross-attention above it, differential attention everywhere, a
head tied to the embedding.

``build(config, seed)`` turns a configuration file into the program's
``(Phi4FlashConfig, params)`` — the WHOLE model, nothing cut. The weights
are the BENCHMARK's: made here from the seed, on the device, in one jitted
call, directly in the types they are served in (int8 matrices with one f32
scale per output channel that differs by channel; float32 conv, W_x, W_dt,
A_log, D, lambda vectors, norms and biases; bf16 embedding). The plain
reference (``phi4flash_reference.py``) reads the same arrays and nothing
the program made.

What a random draw must not decide is set as the architecture's own
initialisation does (the file's ``assumed``): ``A_log = log(1..N)`` a
channel and ``b_dt`` the inverse softplus of a step in 0.001-0.1, so that
the recurrence neither forgets in one step nor never; the lambda vectors
normal with deviation 0.1, so that ``lambda_l`` stays near
``lambda_init_l``; the embedding with deviation ``hidden^-1/2``, so that the
tied head's logits have deviation 1 over a normed state.

``lowered_programs`` is the family's lowering: the engine's own programs
of ``serving/batch.py`` at the shapes the warm-up uses, the pools, the
state and the block tables as this family's pager holds them (dicts by
pool name).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from benchmarks.harness.llama_family import _INT8_STD, seed_key

_DIMS = ("num_hidden_layers", "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "vocab_size", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")


def _dims(c: dict[str, Any]) -> tuple[int, ...]:
    return tuple(int(c[k]) for k in _DIMS)


@partial(jax.jit, static_argnums=0)
def _make_weights(dims: tuple[int, ...], key: jax.Array) -> dict:
    L, D, F, H, Hkv, Dh, V, N, K, expand, R = dims
    Din, P, C = expand * D, L // 4, L // 4 - 1
    keys = iter(jax.random.split(key, 512))

    def int8(shape: tuple[int, ...]) -> jnp.ndarray:
        return jax.lax.bitcast_convert_type(jax.random.bits(next(keys), shape, jnp.uint8), jnp.int8)

    def matrix(shape: tuple[int, ...]) -> dict:
        fan_in = shape[-2]
        # per-output-channel scales that differ, so a dropped or misplaced
        # scale shows; the product has std 1/sqrt(fan_in) on average
        spread = jax.random.uniform(next(keys), shape[:-2] + shape[-1:], jnp.float32, 0.75, 1.25)
        return {"q": int8(shape), "s": spread / (_INT8_STD * math.sqrt(fan_in))}

    def normal(shape: tuple[int, ...], std: float, mean: float = 0.0) -> jnp.ndarray:
        return mean + std * jax.random.normal(next(keys), shape, jnp.float32)

    def block(n: tuple) -> dict:
        return {"ln1_w": normal(n + (D,), 0.1, 1.0), "ln1_b": normal(n + (D,), 0.1),
                "ln2_w": normal(n + (D,), 0.1, 1.0), "ln2_b": normal(n + (D,), 0.1),
                "w1": matrix(n + (D, 2 * F)), "w2": matrix(n + (F, D))}

    def mamba(n: tuple) -> dict:
        step = jnp.exp(jax.random.uniform(next(keys), n + (Din,), jnp.float32, math.log(0.001), math.log(0.1)))
        bound = R ** -0.5
        return {**block(n), "in_proj": matrix(n + (D, 2 * Din)), "out_proj": matrix(n + (Din, D)),
                "conv_w": normal(n + (K, Din), K ** -0.5), "conv_b": normal(n + (Din,), 0.1),
                "x_proj": normal(n + (Din, R + 2 * N), Din ** -0.5),
                "dt_w": jax.random.uniform(next(keys), n + (R, Din), jnp.float32, -bound, bound),
                "dt_b": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1(step)
                "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], n + (N, Din)),
                "d": normal(n + (Din,), 0.1, 1.0)}

    def lambdas(n: tuple) -> dict:
        return {name: normal(n + (Dh,), 0.1) for name in ("lq1", "lk1", "lq2", "lk2")}

    def attn(n: tuple) -> dict:
        return {**block(n), **lambdas(n), "wqkv": matrix(n + (D, (H + 2 * Hkv) * Dh)),
                "bqkv": normal(n + ((H + 2 * Hkv) * Dh,), 0.1), "wo": matrix(n + (H * Dh, D)),
                "bo": normal(n + (D,), 0.1), "sub_norm": normal(n + (2 * Dh,), 0.1, 1.0)}

    def gmu(n: tuple) -> dict:
        return {**block(n), "w_gate": matrix(n + (D, Din)), "w_out": matrix(n + (Din, D))}

    def cross(n: tuple) -> dict:
        return {**block(n), **lambdas(n), "wq": matrix(n + (D, H * Dh)), "bq": normal(n + (H * Dh,), 0.1),
                "wo": matrix(n + (H * Dh, D)), "bo": normal(n + (D,), 0.1),
                "sub_norm": normal(n + (2 * Dh,), 0.1, 1.0)}

    return {
        # tied: the head's logits have deviation 1 over a normed state
        "embedding": (int8((V, D)).astype(jnp.float32) / (_INT8_STD * math.sqrt(D))).astype(jnp.bfloat16),
        "pairs": {"mamba": mamba((P,)), "attn": attn((P,))},
        "mid": {"mamba": mamba(()), "attn": attn(())},
        "cross": {"gmu": gmu((C,)), "cross": cross((C,))},
        "final_norm_w": normal((D,), 0.1, 1.0), "final_norm_b": normal((D,), 0.1),
    }


def make_weights(config: dict[str, Any], seed: int) -> dict:
    return _make_weights(_dims(config), seed_key(seed))


def program_config(config: dict[str, Any]) -> Any:
    """The file's keys as the program's config: published widths and
    depth, bf16 activations, float32 state."""
    from gofr_tpu.models import phi4flash

    served = {"mb_per_layer": 2, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
              "hidden_act": "silu", "model_type": "phi4flash"}
    for key, value in served.items():
        if config.get(key, value) != value:
            raise ValueError(f"phi4flash_family serves {key}={value!r}; the file says {config[key]!r}")
    if int(config["mamba_dt_rank"]) != -(-int(config["hidden_size"]) // 16):
        raise ValueError("phi4flash_family serves mamba_dt_rank = ceil(hidden_size / 16)")
    return phi4flash.Phi4FlashConfig(
        vocab_size=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]), sliding_window=int(config["sliding_window"]),
        d_state=int(config["mamba_d_state"]), d_conv=int(config["mamba_d_conv"]),
        expand=int(config["mamba_expand"]), dt_rank=int(config["mamba_dt_rank"]),
        max_seq_len=int(config["max_position_embeddings"]), norm_eps=float(config["layer_norm_eps"]),
        dtype=jnp.bfloat16,
    )


def build(config: dict[str, Any], seed: int) -> tuple[Any, dict]:
    """(Phi4FlashConfig, params) for the engine."""
    return program_config(config), make_weights(config, seed)


def lowered_programs(engine: Any, prompt_sizes: list[int]) -> tuple[dict[str, str], tuple[str, ...]]:
    """The family's lowering (the harness finds it by this name beside
    ``build``): the engine's own jitted programs at the shapes the warm-up
    uses — ``llama_family.lowered_programs`` with this family's pools,
    state and tables — and ``decode_block_paged`` as the one that must
    hold a compiled kernel: the append and the attention in every window
    layer, the full layer and its seven readers."""
    from gofr_tpu.serving import batch as batch_ops

    if engine.paged_cache is None:
        raise ValueError("this family lowers the paged KV layout; set kv_layout to paged in the cell")
    cfg, ec = engine.model_cfg, engine.config
    B, C, steps = ec.max_slots, engine._chunk_tokens, engine._block_steps

    def ab(tree: Any) -> Any:
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    def vec(dtype: Any, *shape: int) -> Any:
        return jax.ShapeDtypeStruct(shape or (B,), dtype)

    i32, f32 = jnp.int32, jnp.float32
    params, key = ab(engine.params), ab(engine._rng_root)
    state = batch_ops.DecodeState(
        vec(i32), vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(f32),
        vec(i32), vec(f32), key, vec(i32),
    )
    texts: dict[str, str] = {}
    chunked = False
    for n in prompt_sizes:
        if engine._route_chunked(n):
            chunked = True
            continue
        b = batch_ops.pad_bucket(n, engine._buckets())
        texts[f"prefill_compute[{b}]"] = batch_ops.prefill_compute.lower(
            cfg, params, vec(i32, 1, b), vec(i32, 1)).as_text()
    pc = engine.paged_cache
    kp, vp = ab(pc.k_pool), ab(pc.v_pool)
    tables = ab(pc.tables_device())
    texts["decode_block_paged"] = batch_ops.decode_block_paged.lower(
        cfg, params, kp, vp, state, tables, vec(jnp.bool_), steps).as_text()
    if chunked:
        row = (vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(i32), vec(f32),
               vec(i32), vec(f32), vec(i32), key, vec(jnp.bool_), steps)
        texts["ragged_step_paged"] = batch_ops.ragged_step_paged.lower(
            cfg, params, kp, vp, state, tables, vec(i32, B, C), vec(i32),
            vec(jnp.bool_), *row).as_text()
    return texts, ("decode_block_paged",)
