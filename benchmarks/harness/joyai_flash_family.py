"""Factory for JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``), the
DeepSeek-V3 layer the engine serves through ``gofr_tpu.models.deepseek_v32``
with no indexer: latent attention over EVERY cached position (decode
through ``gofr_tpu.ops.latent_attention``, a Mosaic call a layer), one
leading dense layer, sigmoid routing with a correction bias over 256
experts beside a shared one, an untied head.

``build(config, seed)`` turns a configuration file into the program's
``(DeepseekV32Config, params)`` for ONE CHIP'S SHARE of the deployment the
file states: ``n_routed_experts`` routed experts held (of
``published.n_routed_experts``, from ``deployment.first_expert`` on), the
shared expert, attention and router whole, and ``vocab_size`` rows of
embedding and head. The weights are the BENCHMARK's: made here from the
seed, on the device, in one jitted call, directly in the types they are
served in (int8 matrices with one f32 scale per output channel that
differs by channel; float32 router and correction bias; bf16 embedding
and head; f32 norms). The plain reference (``joyai_flash_reference.py``)
reads the same arrays and nothing the program made. The head's EOS column
is zero: a seeded model's greedy stream otherwise ends at EOS early in a
share of requests that differs from seed to seed (an answer of 78 tokens
where the mix asked for 768 or more), and each early end admits one more
prompt, credited whole, into the window; with it zero every request runs
to the ``max_tokens`` the mix drew.

This module imports the decode kernel's module at the top: a program that
has no such kernel fails here, at once, and never builds a latent model
without an indexer it cannot serve.

``lowered_programs`` is ``llama_family``'s: the engine's own programs at
the shapes the warm-up uses, the pools as the pager holds them (one
latent pool: the second is None), and ``decode_block_paged`` as the one
that must hold a compiled kernel — the append and the latent attention of
every layer.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from benchmarks.harness.deepseek_v32_family import published
from benchmarks.harness.llama_family import _INT8_STD, lowered_programs, seed_key  # noqa: F401  (the family's lowering)
from benchmarks.harness.tokens import EOS_ID
from gofr_tpu.ops import latent_attention  # noqa: F401  (the decode kernel this family needs: see above)

_DIMS = ("num_hidden_layers", "first_k_dense_replace", "hidden_size", "intermediate_size",
         "moe_intermediate_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "vocab_size", "n_routed_experts", "n_shared_experts")


def _dims(c: dict[str, Any]) -> tuple[int, ...]:
    return tuple(int(c[k]) for k in _DIMS) + (int(published(c, "n_routed_experts")),)


@partial(jax.jit, static_argnums=0)
def _make_weights(dims: tuple[int, ...], key: jax.Array) -> dict:
    L, Ld, D, F, Fe, H, Rq, Rkv, Dn, Dr, Dv, V, held, n_shared, n_published = dims
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
        ks = jax.random.split(k, 9)
        return {
            "attn_norm": norm(ks[0], (n, D)), "mlp_norm": norm(ks[1], (n, D)),
            "wq_a": matrix(ks[2], (n, D, Rq)), "q_norm": norm(ks[3], (n, Rq)),
            "wq_b": matrix(ks[4], (n, Rq, H * (Dn + Dr))),
            "wkv_a": matrix(ks[5], (n, D, Rkv + Dr)), "kv_norm": norm(ks[6], (n, Rkv)),
            "wkv_b": matrix(ks[7], (n, Rkv, H * (Dn + Dv))), "wo": matrix(ks[8], (n, H * Dv, D)),
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
        # the head's logits have deviation 1 over a normed state; EOS's is 0,
        # under the largest of the others in every state (the docstring)
        "lm_head": (int8(k["lm_head"], (D, V)).astype(jnp.float32) / (_INT8_STD * math.sqrt(D))).astype(
            jnp.bfloat16).at[:, EOS_ID].set(0),
    }


def make_weights(config: dict[str, Any], seed: int) -> dict:
    return _make_weights(_dims(config), seed_key(seed))


def program_config(config: dict[str, Any]) -> Any:
    """The file's keys as the program's config: published widths, the
    chip's share of the experts and of the vocabulary, no indexer, plain
    rotary frequencies, bf16 activations."""
    from gofr_tpu.models import deepseek_v32

    served = {"model_type": "joyai_llm_flash", "scoring_func": "sigmoid", "topk_method": "noaux_tc",
              "norm_topk_prob": True, "tie_word_embeddings": False, "attention_bias": False,
              "hidden_act": "silu", "moe_layer_freq": 1, "rope_interleave": True, "rope_scaling": None}
    for key, value in served.items():
        if config.get(key, value) != value:
            raise ValueError(f"joyai_flash_family serves {key}={value!r}; the file says {config[key]!r}")
    if int(config["qk_head_dim"]) != int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    return deepseek_v32.DeepseekV32Config(
        vocab_size=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_dense_layers=int(config["first_k_dense_replace"]),
        n_heads=int(config["num_attention_heads"]), q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]), qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]), v_head_dim=int(config["v_head_dim"]),
        index_n_heads=0, index_head_dim=0, index_topk=0, d_ff=int(config["intermediate_size"]),
        d_ff_expert=int(config["moe_intermediate_size"]), n_experts=int(published(config, "n_routed_experts")),
        top_k=int(config["num_experts_per_tok"]), n_group=int(config["n_group"]),
        topk_group=int(config["topk_group"]), routed_scaling=float(config["routed_scaling_factor"]),
        n_shared=int(config["n_shared_experts"]), held_experts=int(config["n_routed_experts"]),
        first_expert=int((config.get("deployment") or {}).get("first_expert", 0)),
        max_seq_len=int(config["max_position_embeddings"]), rope_theta=float(config["rope_theta"]),
        rope_factor=1.0, norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16,
    )


def build(config: dict[str, Any], seed: int) -> tuple[Any, dict]:
    """(DeepseekV32Config, params) for the engine."""
    return program_config(config), make_weights(config, seed)
