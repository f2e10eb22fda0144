"""Factory for Nemotron 3 Nano (``model_type`` ``nemotron_h``), the hybrid
the engine serves through ``gofr_tpu.models.nemotron_h``: Mamba-2 mixers,
sparse relu² experts beside a shared one and attention with no positional
encoding, one mixer a layer in the published ``hybrid_override_pattern``,
an untied head.

``build(config, seed)`` turns a configuration file into the program's
``(NemotronHConfig, params)`` for ONE CHIP'S SHARE of the deployment the
file states: ``n_routed_experts`` routed experts held (of
``published.n_routed_experts``, from ``deployment.first_expert`` on), the
Mamba mixers, attention, router and the shared expert whole, and
``vocab_size`` rows of embedding and head; all 52 layers. The weights are
the BENCHMARK's: made here from the seed, on the device, in one jitted
call, directly in the types they are served in (int8 matrices with one
f32 scale per output channel that differs by channel; float32 router,
correction bias, conv, Mamba scalars and norms; bf16 embedding and head),
the experts' width stored zero-padded to whole lane tiles as the program
stores it (``models/nemotron_h.pad_experts``). The plain reference
(``nemotron_h_reference.py``) reads the same arrays, at the published
width, and nothing the program made. The draws are the file's ``assumed``;
the head's EOS column is zero, as ``joyai_flash_family``'s and for its
reason: every request runs to the ``max_tokens`` the mix drew.

This module imports the SSD ops at the top: a program that has no Mamba-2
mixer fails here, at once.

``lowered_programs`` is ``phi4flash_family``'s: the engine's own programs
at the shapes the warm-up uses, the pools, the state and the tables as the
pager holds them for a model with a ``cache_spec``, and
``decode_block_paged`` as the one that must hold a compiled kernel — the
append and the attention of every attention layer, the state step of every
Mamba layer, the every-row expert sums.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from benchmarks.harness.deepseek_v32_family import published
from benchmarks.harness.llama_family import _INT8_STD, seed_key
from benchmarks.harness.phi4flash_family import lowered_programs  # noqa: F401  (the family's lowering)
from benchmarks.harness.tokens import EOS_ID
from gofr_tpu.ops import ssd  # noqa: F401  (the mixer this family needs: see above)

BIAS_STD = 0.02  # e_score_correction_bias's deviation (the file's ``assumed``)
# the experts' W_down at a tenth of a unit product's deviation (the file's
# ``assumed``): relu² of a unit product is a squared normal, and 23 layers
# of six experts and a shared one at unit scale swamp the residual stream
FFN_OUT_GAIN = 0.1
LANES = 128
_KEYS = ("hybrid_override_pattern", "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
         "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel", "moe_intermediate_size",
         "moe_shared_expert_intermediate_size", "n_routed_experts", "n_shared_experts", "vocab_size",
         "time_step_min", "time_step_max", "time_step_floor")


def _dims(c: dict[str, Any]) -> tuple:
    return tuple(c[k] for k in _KEYS) + (int(published(c, "n_routed_experts")),)


@partial(jax.jit, static_argnums=0)
def _make_weights(dims: tuple, key: jax.Array) -> dict:
    (pattern, D, H, Hkv, Dh, Hm, P, G, N, K, Fe, Fs, held, n_shared, V, dt_min, dt_max, dt_floor, E) = dims
    Lm, La, Le = pattern.count("M"), pattern.count("*"), pattern.count("E")
    Di = Hm * P
    Cd = Di + 2 * G * N
    keys = iter(jax.random.split(key, 64))

    def int8(shape: tuple[int, ...]) -> jnp.ndarray:
        return jax.lax.bitcast_convert_type(jax.random.bits(next(keys), shape, jnp.uint8), jnp.int8)

    def matrix(shape: tuple[int, ...], gain: float = 1.0) -> dict:
        fan_in = shape[-2]
        # per-output-channel scales that differ, so a dropped or misplaced
        # scale shows; the product has std gain/sqrt(fan_in) on average
        spread = jax.random.uniform(next(keys), shape[:-2] + shape[-1:], jnp.float32, 0.75, 1.25)
        return {"q": int8(shape), "s": gain * spread / (_INT8_STD * math.sqrt(fan_in))}

    def relu2(lead: tuple[int, ...], width: int) -> dict:
        """W_up and W_down of relu² experts, stored at the width padded to
        whole lane tiles: the padded columns of W_up and rows of W_down
        are zero (their scales 1), so they add exactly nothing."""
        wide = -(-width // LANES) * LANES
        up, down = matrix(lead + (D, wide)), matrix(lead + (wide, D), FFN_OUT_GAIN)
        down["s"] = down["s"] * math.sqrt(wide / width)  # the deviation of the published width
        kept = jnp.arange(wide) < width
        return {"w_up": {"q": jnp.where(kept, up["q"], 0), "s": jnp.where(kept, up["s"], 1.0)},
                "w_down": {"q": jnp.where(kept[:, None], down["q"], 0), "s": down["s"]}}

    def normal(shape: tuple[int, ...], std: float, mean: float = 0.0) -> jnp.ndarray:
        return mean + std * jax.random.normal(next(keys), shape, jnp.float32)

    # the published initialisation of dt_bias: softplus^-1 of a log-uniform
    # draw in [time_step_min, time_step_max], floored
    dt = jnp.exp(jax.random.uniform(next(keys), (Lm, Hm), jnp.float32, math.log(dt_min), math.log(dt_max)))
    dt = jnp.maximum(dt, dt_floor)
    bound = K ** -0.5
    return {
        "embedding": (int8((V, D)).astype(jnp.float32) / _INT8_STD).astype(jnp.bfloat16),
        "mamba": {"norm": normal((Lm, D), 0.1, 1.0), "in_proj": matrix((Lm, D, Di + Cd + Hm)),
                  "out_proj": matrix((Lm, Di, D)),
                  "conv_w": jax.random.uniform(next(keys), (Lm, K, Cd), jnp.float32, -bound, bound),
                  "conv_b": jax.random.uniform(next(keys), (Lm, Cd), jnp.float32, -bound, bound),
                  "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                  "A_log": jnp.log(jax.random.uniform(next(keys), (Lm, Hm), jnp.float32, 1.0, 16.0)),
                  "D": normal((Lm, Hm), 0.1, 1.0), "gate_norm": normal((Lm, Di), 0.1, 1.0)},
        "attn": {"norm": normal((La, D), 0.1, 1.0), "wq": matrix((La, D, H * Dh)), "wk": matrix((La, D, Hkv * Dh)),
                 "wv": matrix((La, D, Hkv * Dh)), "wo": matrix((La, H * Dh, D))},
        "moe": {"norm": normal((Le, D), 0.1, 1.0), "w_router": normal((Le, D, E), D ** -0.5),
                "router_bias": normal((Le, E), BIAS_STD),
                "experts": relu2((Le, held), Fe), "shared": relu2((Le, n_shared), Fs)},
        "final_norm": normal((D,), 0.1, 1.0),
        # the head's logits have deviation 1 over a normed state; EOS's is 0
        # (the docstring)
        "lm_head": (int8((D, V)).astype(jnp.float32) / (_INT8_STD * math.sqrt(D))).astype(
            jnp.bfloat16).at[:, EOS_ID].set(0),
    }


def make_weights(config: dict[str, Any], seed: int) -> dict:
    return _make_weights(_dims(config), seed_key(seed))


def program_config(config: dict[str, Any]) -> Any:
    """The file's keys as the program's config: published widths, depth
    and pattern, the chip's share of the experts and of the vocabulary,
    bf16 activations, a float32 state."""
    from gofr_tpu.models import nemotron_h

    served = {"model_type": "nemotron_h", "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
              "mamba_proj_bias": False, "use_conv_bias": True, "attention_bias": False, "mlp_bias": False,
              "use_bias": False, "tie_word_embeddings": False, "norm_topk_prob": True, "n_group": 1,
              "topk_group": 1, "n_shared_experts": 1, "sliding_window": None}
    for key, value in served.items():
        if config.get(key, value) != value:
            raise ValueError(f"nemotron_h_family serves {key}={value!r}; the file says {config[key]!r}")
    if float(config["norm_eps"]) != float(config["layer_norm_epsilon"]):
        raise ValueError("norm_eps and layer_norm_epsilon differ: the family has one eps")
    return nemotron_h.NemotronHConfig(
        vocab_size=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        pattern=config["hybrid_override_pattern"], n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        mamba_heads=int(config["mamba_num_heads"]), mamba_head_dim=int(config["mamba_head_dim"]),
        n_groups=int(config["n_groups"]), d_state=int(config["ssm_state_size"]),
        conv_kernel=int(config["conv_kernel"]), chunk_size=int(config["chunk_size"]),
        d_ff_expert=int(config["moe_intermediate_size"]),
        d_ff_shared=int(config["moe_shared_expert_intermediate_size"]),
        n_experts=int(published(config, "n_routed_experts")), top_k=int(config["num_experts_per_tok"]),
        routed_scaling=float(config["routed_scaling_factor"]), n_held=int(config["n_routed_experts"]),
        first_held=int((config.get("deployment") or {}).get("first_expert", 0)),
        max_seq_len=int(config["max_position_embeddings"]), norm_eps=float(config["norm_eps"]),
        dtype=jnp.bfloat16, state_dtype=jnp.float32,
    )


def build(config: dict[str, Any], seed: int) -> tuple[Any, dict]:
    """(NemotronHConfig, params) for the engine."""
    return program_config(config), make_weights(config, seed)
