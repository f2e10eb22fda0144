"""Factory for the decoder family the engine serves through
``gofr_tpu.models.llama``: RMSNorm, RoPE (half rotation), grouped or full
multi-head attention, SwiGLU, no biases, untied head.

``build(config, seed)`` turns a configuration file into the program's
``(LlamaConfig, params)``. The weights are the BENCHMARK's: made here from
the seed, on the device, in one jitted call, directly in the types they
are served in (int8 matrices with one f32 scale per output channel, bf16
embedding, f32 norms) — the program's ``init_params`` makes every leaf in
bf16 first and peaks 7 GB over the settled size. The plain reference
(``reference.py``) reads the same arrays and nothing the program made.

A later configuration of another architecture brings a factory of its
own and names it in its file's ``factory`` key.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from benchmarks.harness import costs

_INT8_STD = 73.9  # std of a uniform draw from -128..127


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def _dims(c: dict[str, Any]) -> tuple[int, ...]:
    return (int(c["num_hidden_layers"]), int(c["hidden_size"]), int(c["intermediate_size"]),
            int(c["num_attention_heads"]), int(c["num_key_value_heads"]), costs.head_dim(c),
            int(c["vocab_size"]))


@partial(jax.jit, static_argnums=0)
def _make_weights(dims: tuple[int, ...], key: jax.Array) -> dict:
    L, D, F, H, Hkv, Dh, V = dims
    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
             "embedding", "attn_norm", "mlp_norm", "final_norm")
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

    return {
        "embedding": (int8(keys["embedding"], (V, D)).astype(jnp.float32) / _INT8_STD).astype(jnp.bfloat16),
        "layers": {
            "wq": matrix("wq", (L, D, H * Dh)),
            "wk": matrix("wk", (L, D, Hkv * Dh)),
            "wv": matrix("wv", (L, D, Hkv * Dh)),
            "wo": matrix("wo", (L, H * Dh, D)),
            "w_gate": matrix("w_gate", (L, D, F)),
            "w_up": matrix("w_up", (L, D, F)),
            "w_down": matrix("w_down", (L, F, D)),
            "attn_norm": norm("attn_norm", (L, D)),
            "mlp_norm": norm("mlp_norm", (L, D)),
        },
        "final_norm": norm("final_norm", (D,)),
        "lm_head": matrix("lm_head", (D, V)),
    }


def make_weights(config: dict[str, Any], seed: int) -> dict:
    return _make_weights(_dims(config), seed_key(seed))


def build(config: dict[str, Any], seed: int) -> tuple[Any, dict]:
    """(LlamaConfig, params) for the engine: published widths, bf16
    activations, the compiled kernels where a shape routes to them."""
    from gofr_tpu.models import llama

    if config.get("sliding_window"):
        raise ValueError("llama_family has no sliding-window attention")
    cfg = llama.LlamaConfig(
        vocab_size=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]), n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]), d_ff=int(config["intermediate_size"]),
        max_seq_len=int(config["max_position_embeddings"]), rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16,
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
    )
    if cfg.head_dim != costs.head_dim(config):
        raise ValueError("LlamaConfig derives head_dim = hidden/heads; the file disagrees")
    return cfg, make_weights(config, seed)


# the engine's ByteTokenizer, restated so that the reference needs nothing
# of the program: BOS, then one id per UTF-8 byte offset by the specials
BOS_ID, EOS_ID, BYTE_OFFSET = 1, 2, 3


def prompt_ids(prompt: str) -> list[int]:
    return [BOS_ID] + [b + BYTE_OFFSET for b in prompt.encode("utf-8")]
