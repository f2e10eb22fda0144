"""Factory for the ``lfm2_moe`` decoder the engine serves through
``gofr_tpu.models.lfm2_moe`` (LFM2-8B-A1B): gated short convolutions and
QK-normed grouped-query attention in the file's order of ``layer_types``,
two dense layers, then sparse experts under a sigmoid rule with an expert
bias, a head tied to the embedding.

``build(config, seed)`` turns a configuration file into the program's
``(Lfm2MoeConfig, params)`` — the WHOLE model, nothing cut. The weights
are the BENCHMARK's: made here from the seed, on the device, in one jitted
call, directly in the types they are served in (int8 matrices with one
f32 scale per output channel that differs by channel; float32 router,
expert bias, conv taps and norms; bf16 embedding). The plain reference
(``lfm2_moe_reference.py``) reads the same arrays and nothing the program
made. The draws the file's ``assumed`` states: the embedding with
deviation ``hidden^-1/2`` (logits of deviation about 1 over a normed
state), the expert bias with deviation 0.02 (it changes some choices),
the conv taps uniform in ``+-3^-1/2``, the feed-forward layers' output
matrices at a tenth of a unit product's deviation (:data:`FFN_OUT_GAIN`).

``lowered_programs`` is ``phi4flash_family``'s: the engine's own programs
of ``serving/batch.py`` at the shapes the warm-up uses, with the pools,
the state and the block tables as the pager holds them for a model with a
``cache_spec`` (dicts by pool name), and ``decode_block_paged`` as the one
that must hold a compiled kernel — the append and the attention of every
attention layer.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from benchmarks.harness.llama_family import _INT8_STD, seed_key
from benchmarks.harness.phi4flash_family import lowered_programs  # noqa: F401  (the family's lowering)

BIAS_STD = 0.02  # the expert bias's deviation (the file's ``assumed``)
# the feed-forward layers' output (W_2 of the dense MLPs and of every
# expert) at a tenth of a unit product's deviation (the file's ``assumed``):
# at unit scale the seeded model is chaotic in bfloat16 — a routing choice
# that rounding flips moves a residual stream by a step that the layers
# above amplify, and the served logits leave the reference's by 2-4 at
# hidden 256-512 (CPU, PERF.md section 6, PR 39); at a tenth, by 0.2-0.5
FFN_OUT_GAIN = 0.1


def _dims(c: dict[str, Any]) -> tuple:
    kinds = tuple(c["layer_types"])
    return (kinds, int(c["num_dense_layers"]), int(c["hidden_size"]), int(c["intermediate_size"]),
            int(c["moe_intermediate_size"]), int(c["num_experts"]), int(c["num_attention_heads"]),
            int(c["num_key_value_heads"]), head_dim(c), int(c["vocab_size"]), int(c["conv_L_cache"]))


def head_dim(c: dict[str, Any]) -> int:
    return int(c.get("head_dim") or int(c["hidden_size"]) // int(c["num_attention_heads"]))


@partial(jax.jit, static_argnums=0)
def _make_weights(dims: tuple, key: jax.Array) -> dict:
    kinds, Ld, D, F, Fe, E, H, Hkv, Dh, V, K = dims
    Lc, La, Lm = kinds.count("conv"), kinds.count("full_attention"), len(kinds) - Ld
    keys = iter(jax.random.split(key, 64))

    def int8(shape: tuple[int, ...]) -> jnp.ndarray:
        return jax.lax.bitcast_convert_type(jax.random.bits(next(keys), shape, jnp.uint8), jnp.int8)

    def matrix(shape: tuple[int, ...], gain: float = 1.0) -> dict:
        fan_in = shape[-2]
        # per-output-channel scales that differ, so a dropped or misplaced
        # scale shows; the product has std gain/sqrt(fan_in) on average
        spread = jax.random.uniform(next(keys), shape[:-2] + shape[-1:], jnp.float32, 0.75, 1.25)
        return {"q": int8(shape), "s": gain * spread / (_INT8_STD * math.sqrt(fan_in))}

    def normal(shape: tuple[int, ...], std: float, mean: float = 0.0) -> jnp.ndarray:
        return mean + std * jax.random.normal(next(keys), shape, jnp.float32)

    def ffn(lead: tuple, width: int) -> dict:
        return {"w_gate": matrix(lead + (D, width)), "w_up": matrix(lead + (D, width)),
                "w_down": matrix(lead + (width, D), FFN_OUT_GAIN)}

    bound = K ** -0.5
    return {
        # tied: the head's logits have deviation 1 over a normed state
        "embedding": (int8((V, D)).astype(jnp.float32) / (_INT8_STD * math.sqrt(D))).astype(jnp.bfloat16),
        "conv": {"norm": normal((Lc, D), 0.1, 1.0), "in_proj": matrix((Lc, D, 3 * D)),
                 "out_proj": matrix((Lc, D, D)),
                 "conv_w": jax.random.uniform(next(keys), (Lc, K, D), jnp.float32, -bound, bound)},
        "attn": {"norm": normal((La, D), 0.1, 1.0), "wq": matrix((La, D, H * Dh)), "wk": matrix((La, D, Hkv * Dh)),
                 "wv": matrix((La, D, Hkv * Dh)), "wo": matrix((La, H * Dh, D)),
                 "q_norm": normal((La, Dh), 0.1, 1.0), "k_norm": normal((La, Dh), 0.1, 1.0)},
        "dense": {"norm": normal((Ld, D), 0.1, 1.0), **ffn((Ld,), F)},
        "moe": {"norm": normal((Lm, D), 0.1, 1.0), "w_router": normal((Lm, D, E), D ** -0.5),
                "expert_bias": normal((Lm, E), BIAS_STD), "experts": ffn((Lm, E), Fe)},
        "final_norm": normal((D,), 0.1, 1.0),
    }


def make_weights(config: dict[str, Any], seed: int) -> dict:
    return _make_weights(_dims(config), seed_key(seed))


def program_config(config: dict[str, Any]) -> Any:
    """The file's keys as the program's config: published widths, depth
    and layer order, bf16 activations."""
    from gofr_tpu.models import lfm2_moe

    served = {"model_type": "lfm2_moe", "conv_bias": False, "use_expert_bias": True, "norm_topk_prob": True}
    for key, value in served.items():
        if config.get(key, value) != value:
            raise ValueError(f"lfm2_moe_family serves {key}={value!r}; the file says {config[key]!r}")
    return lfm2_moe.Lfm2MoeConfig(
        vocab_size=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=head_dim(config),
        d_ff=int(config["intermediate_size"]), d_ff_expert=int(config["moe_intermediate_size"]),
        n_experts=int(config["num_experts"]), top_k=int(config["num_experts_per_tok"]),
        n_dense_layers=int(config["num_dense_layers"]), conv_kernel=int(config["conv_L_cache"]),
        layer_types=tuple(config["layer_types"]), routed_scaling=float(config["routed_scaling_factor"]),
        max_seq_len=int(config["max_position_embeddings"]), rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]), dtype=jnp.bfloat16,
    )


def build(config: dict[str, Any], seed: int) -> tuple[Any, dict]:
    """(Lfm2MoeConfig, params) for the engine."""
    return program_config(config), make_weights(config, seed)
