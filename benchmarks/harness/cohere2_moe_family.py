"""Factory for the ``cohere2_moe`` decoder the engine serves through
``gofr_tpu.models.cohere2_moe``: a parallel block behind one weight-only
LayerNorm, window and full attention by layer type, sparse experts chosen
by a sigmoid rule beside shared experts, a head tied to the embedding.

``build(config, seed)`` turns a configuration file into the program's
``(Cohere2MoeConfig, params)`` for ONE CHIP'S SHARE of the deployment the
file states: ``num_experts`` routed experts held (of
``published.num_experts``, from ``deployment.first_expert`` on), the
shared experts, attention and router whole, and ``vocab_size`` rows of the
tied embedding. The weights are the BENCHMARK's: made here from the seed,
on the device, in one jitted call, directly in the types they are served
in (int8 matrices with one f32 scale per output channel that differs by
channel, float32 router, bf16 embedding, f32 norms). The plain reference
(``cohere2_moe_reference.py``) reads the same arrays and nothing the
program made.

``lowered_programs`` is the family's lowering. The engine's programs are
those of ``serving/batch.py`` under the names every family's are
(``prefill_compute``, ``decode_block_paged``, ``ragged_step_paged``) and
take the model's config as their static argument, so the lowering is
``llama_family``'s, given this family's engine.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from benchmarks.harness import llama_family
from benchmarks.harness.llama_family import _INT8_STD, seed_key


def published(c: dict[str, Any], key: str) -> Any:
    """The source's value of a key: the file's own unless it is reduced."""
    return (c.get("published") or {}).get(key, c[key])


def _dims(c: dict[str, Any]) -> tuple[int, ...]:
    return (int(c["num_hidden_layers"]), int(c["hidden_size"]), int(c["intermediate_size"]),
            int(c["num_attention_heads"]), int(c["num_key_value_heads"]), int(c["head_dim"]),
            int(c["vocab_size"]), int(c["num_experts"]), int(c["num_shared_experts"]),
            int(published(c, "num_experts")))


@partial(jax.jit, static_argnums=0)
def _make_weights(dims: tuple[int, ...], key: jax.Array) -> dict:
    L, D, F, H, Hkv, Dh, V, held, n_shared, n_published = dims
    names = ("wq", "wk", "wv", "wo", "w_router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
             "s_down", "embedding", "norm", "final_norm")
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def int8(k: jax.Array, shape: tuple[int, ...]) -> jnp.ndarray:
        return jax.lax.bitcast_convert_type(jax.random.bits(k, shape, jnp.uint8), jnp.int8)

    def matrix(name: str, shape: tuple[int, ...]) -> dict:
        kq, ks = jax.random.split(keys[name])
        fan_in = shape[-2]
        # per-output-channel scales that differ, so a dropped or misplaced
        # scale shows; the product has std 1/sqrt(fan_in) on average
        spread = jax.random.uniform(ks, shape[:-2] + shape[-1:], jnp.float32, 0.75, 1.25)
        return {"q": int8(kq, shape), "s": spread / (_INT8_STD * math.sqrt(fan_in))}

    def norm(name: str, shape: tuple[int, ...]) -> jnp.ndarray:
        return 1.0 + 0.1 * jax.random.normal(keys[name], shape, jnp.float32)

    def experts(prefix: str, n: int) -> dict:
        return {"w_gate": matrix(prefix + "_gate", (L, n, D, F)), "w_up": matrix(prefix + "_up", (L, n, D, F)),
                "w_down": matrix(prefix + "_down", (L, n, F, D))}

    return {
        # tied: the head's logits have deviation 1 over a normed state
        "embedding": (int8(keys["embedding"], (V, D)).astype(jnp.float32)
                      / (_INT8_STD * math.sqrt(D))).astype(jnp.bfloat16),
        "layers": {
            "norm": norm("norm", (L, D)),
            "wq": matrix("wq", (L, D, H * Dh)),
            "wk": matrix("wk", (L, D, Hkv * Dh)),
            "wv": matrix("wv", (L, D, Hkv * Dh)),
            "wo": matrix("wo", (L, H * Dh, D)),
            # every published expert is scored, held here or not
            "w_router": jax.random.normal(keys["w_router"], (L, D, n_published), jnp.float32) / math.sqrt(D),
            "experts": experts("e", held),
            "shared": experts("s", n_shared),
        },
        "final_norm": norm("final_norm", (D,)),
    }


def make_weights(config: dict[str, Any], seed: int) -> dict:
    return _make_weights(_dims(config), seed_key(seed))


def build(config: dict[str, Any], seed: int) -> tuple[Any, dict]:
    """(Cohere2MoeConfig, params) for the engine: published widths, the
    chip's share of the experts and of the vocabulary, bf16 activations."""
    from gofr_tpu.models import cohere2_moe

    served = {"position_embedding_type": "rope_gptj", "expert_selection_fn": "sigmoid",
                   "shared_expert_combination_strategy": "average", "use_parallel_block": True,
                   "norm_topk_prob": True, "tie_word_embeddings": True, "use_qk_norm": False,
                   "attention_bias": False, "first_k_dense_replace": 0, "hidden_act": "silu"}
    for key, value in served.items():
        if config.get(key, value) != value:
            raise ValueError(f"cohere2_moe_family serves {key}={value!r}; the file says {config[key]!r}")
    cfg = cohere2_moe.Cohere2MoeConfig(
        vocab_size=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]), n_experts=int(published(config, "num_experts")),
        top_k=int(config["num_experts_per_tok"]), n_shared=int(config["num_shared_experts"]),
        held_experts=int(config["num_experts"]),
        first_expert=int((config.get("deployment") or {}).get("first_expert", 0)),
        layer_types=tuple(config["layer_types"]), sliding_window=int(config["sliding_window"]),
        max_seq_len=int(config["max_position_embeddings"]), rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["layer_norm_eps"]), logit_scale=float(config.get("logit_scale", 1.0)),
        dtype=jnp.bfloat16,
    )
    return cfg, make_weights(config, seed)


def lowered_programs(engine: Any, prompt_sizes: list[int]) -> tuple[dict[str, str], tuple[str, ...]]:
    """The family's lowering (the harness finds it by this name beside
    ``build``): the engine's own jitted programs at the shapes the warm-up
    uses, and ``decode_block_paged`` as the one that must hold a compiled
    kernel — the paged kernel, with its window argument, in every layer."""
    return llama_family.lowered_programs(engine, prompt_sizes)
