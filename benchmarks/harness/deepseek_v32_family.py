"""Factory for the ``deepseek_v32`` decoder the engine serves through
``gofr_tpu.models.deepseek_v32``: latent attention under a learned sparse
selection, leading dense layers, group-limited sigmoid routing over sparse
experts beside a shared one, an untied head.

``build(config, seed)`` turns a configuration file into the program's
``(DeepseekV32Config, params)`` for ONE CHIP'S SHARE of the deployment the
file states: ``n_routed_experts`` routed experts held (of
``published.n_routed_experts``, from ``deployment.first_expert`` on), the
shared expert, attention, indexer and router whole, and ``vocab_size``
rows of embedding and head. The weights are the BENCHMARK's: made here
from the seed, on the device, in one jitted call, directly in the types
they are served in (int8 matrices with one f32 scale per output channel
that differs by channel; float32 router, correction bias and indexer head
weights; bf16 embedding and head; f32 norms). The plain reference
(``deepseek_v32_reference.py``) reads the same arrays and nothing the
program made.

``lowered_programs`` is the family's lowering. The engine's programs are
those of ``serving/batch.py`` under the names every family's are and take
the model's config as their static argument, so the lowering is
``llama_family``'s, given this family's engine (it reads the pools'
shapes from the engine's pager).
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


_DIMS = ("num_hidden_layers", "first_k_dense_replace", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim", "vocab_size",
         "n_routed_experts", "n_shared_experts")


def _dims(c: dict[str, Any]) -> tuple[int, ...]:
    return tuple(int(c[k]) for k in _DIMS) + (int(published(c, "n_routed_experts")),)


@partial(jax.jit, static_argnums=0)
def _make_weights(dims: tuple[int, ...], key: jax.Array) -> dict:
    L, Ld, D, F, Fe, H, Rq, Rkv, Dn, Dr, Dv, Hi, Di, V, held, n_shared, n_published = dims
    Lm = L - Ld

    def int8(k: jax.Array, shape: tuple[int, ...]) -> jnp.ndarray:
        return jax.lax.bitcast_convert_type(jax.random.bits(k, shape, jnp.uint8), jnp.int8)

    def matrix(k: jax.Array, shape: tuple[int, ...]) -> dict:
        kq, ks = jax.random.split(k)
        fan_in = shape[-2]
        # per-output-channel scales that differ, so a dropped or misplaced
        # scale shows; the product has std 1/sqrt(fan_in) on average
        spread = jax.random.uniform(ks, shape[:-2] + shape[-1:], jnp.float32, 0.75, 1.25)
        return {"q": int8(kq, shape), "s": spread / (_INT8_STD * math.sqrt(fan_in))}

    def norm(k: jax.Array, shape: tuple[int, ...]) -> jnp.ndarray:
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)

    def ffn(k: jax.Array, lead: tuple[int, ...], width: int) -> dict:
        kg, ku, kd = jax.random.split(k, 3)
        return {"w_gate": matrix(kg, lead + (D, width)), "w_up": matrix(ku, lead + (D, width)),
                "w_down": matrix(kd, lead + (width, D))}

    def attention(k: jax.Array, n: int) -> dict:
        ks = jax.random.split(k, 14)
        return {
            "attn_norm": norm(ks[0], (n, D)), "mlp_norm": norm(ks[1], (n, D)),
            "wq_a": matrix(ks[2], (n, D, Rq)), "q_norm": norm(ks[3], (n, Rq)),
            "wq_b": matrix(ks[4], (n, Rq, H * (Dn + Dr))),
            "wkv_a": matrix(ks[5], (n, D, Rkv + Dr)), "kv_norm": norm(ks[6], (n, Rkv)),
            "wkv_b": matrix(ks[7], (n, Rkv, H * (Dn + Dv))), "wo": matrix(ks[8], (n, H * Dv, D)),
            "idx_wq": matrix(ks[9], (n, Rq, Hi * Di)), "idx_wk": matrix(ks[10], (n, D, Di)),
            "idx_norm_w": norm(ks[11], (n, Di)),
            "idx_norm_b": 0.1 * jax.random.normal(ks[12], (n, Di), jnp.float32),
            "idx_w": jax.random.normal(ks[13], (n, D, Hi), jnp.float32) / math.sqrt(D),
        }

    k = dict(zip(("dense", "dense_ffn", "moe", "router", "bias", "experts", "shared", "embedding",
                  "final_norm", "lm_head"), jax.random.split(key, 10)))
    return {
        "embedding": (int8(k["embedding"], (V, D)).astype(jnp.float32) / _INT8_STD).astype(jnp.bfloat16),
        "dense": {**attention(k["dense"], Ld), **ffn(k["dense_ffn"], (Ld,), F)},
        "moe": {
            **attention(k["moe"], Lm),
            # every published expert is scored, held here or not
            "w_router": jax.random.normal(k["router"], (Lm, D, n_published), jnp.float32) / math.sqrt(D),
            # e_score_correction_bias: non-zero, so that the correction decides some choices
            "router_bias": 0.1 * jax.random.normal(k["bias"], (Lm, n_published), jnp.float32),
            "experts": ffn(k["experts"], (Lm, held), Fe),
            "shared": ffn(k["shared"], (Lm, n_shared), Fe),
        },
        "final_norm": norm(k["final_norm"], (D,)),
        # the head's logits have deviation 1 over a normed state
        "lm_head": (int8(k["lm_head"], (D, V)).astype(jnp.float32) / (_INT8_STD * math.sqrt(D))).astype(jnp.bfloat16),
    }


def make_weights(config: dict[str, Any], seed: int) -> dict:
    return _make_weights(_dims(config), seed_key(seed))


def program_config(config: dict[str, Any]) -> Any:
    """The file's keys as the program's config: published widths, the
    chip's share of the experts and of the vocabulary, bf16 activations."""
    from gofr_tpu.models import deepseek_v32

    served = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
              "tie_word_embeddings": False, "attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1}
    for key, value in served.items():
        if config.get(key, value) != value:
            raise ValueError(f"deepseek_v32_family serves {key}={value!r}; the file says {config[key]!r}")
    scaling = config["rope_scaling"]
    if scaling.get("type") != "yarn" or scaling.get("mscale") != scaling.get("mscale_all_dim"):
        raise ValueError("deepseek_v32_family serves YaRN with mscale = mscale_all_dim")
    return deepseek_v32.DeepseekV32Config(
        vocab_size=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_dense_layers=int(config["first_k_dense_replace"]),
        n_heads=int(config["num_attention_heads"]), q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]), qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]), v_head_dim=int(config["v_head_dim"]),
        index_n_heads=int(config["index_n_heads"]), index_head_dim=int(config["index_head_dim"]),
        index_topk=int(config["index_topk"]), d_ff=int(config["intermediate_size"]),
        d_ff_expert=int(config["moe_intermediate_size"]), n_experts=int(published(config, "n_routed_experts")),
        top_k=int(config["num_experts_per_tok"]), n_group=int(config["n_group"]),
        topk_group=int(config["topk_group"]), routed_scaling=float(config["routed_scaling_factor"]),
        n_shared=int(config["n_shared_experts"]), held_experts=int(config["n_routed_experts"]),
        first_expert=int((config.get("deployment") or {}).get("first_expert", 0)),
        max_seq_len=int(config["max_position_embeddings"]), rope_theta=float(config["rope_theta"]),
        rope_factor=float(scaling["factor"]), rope_original_max=int(scaling["original_max_position_embeddings"]),
        beta_fast=float(scaling["beta_fast"]), beta_slow=float(scaling["beta_slow"]),
        mscale=float(scaling["mscale"]), norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16,
    )


def build(config: dict[str, Any], seed: int) -> tuple[Any, dict]:
    """(DeepseekV32Config, params) for the engine."""
    return program_config(config), make_weights(config, seed)


def lowered_programs(engine: Any, prompt_sizes: list[int]) -> tuple[dict[str, str], tuple[str, ...]]:
    """The family's lowering (the harness finds it by this name beside
    ``build``): the engine's own jitted programs at the shapes the warm-up
    uses, and ``decode_block_paged`` as the one that must hold a compiled
    kernel — the append aliased over both pools, in every layer."""
    return llama_family.lowered_programs(engine, prompt_sizes)
