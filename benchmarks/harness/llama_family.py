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

``lowered_programs(engine, prompt_sizes)`` is the family's lowering: the
programs of ``serving/batch.py`` this family's cells warm up, as text.

A later configuration of another architecture brings a module of its own
with its factory and its ``lowered_programs``, names the factory in its
file's ``factory`` key, and a plain reference under ``reference``.
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


def lowered_programs(engine: Any, prompt_sizes: list[int]) -> tuple[dict[str, str], tuple[str, ...]]:
    """The family's lowering: the harness finds it by this name in the
    module of the configuration's ``factory``. Lower (not compile) the
    engine's own jitted programs at the argument shapes the cell's
    warm-up uses: program name -> lowered text, and the names of those
    that must hold a compiled kernel on the chip. The runner counts the
    Mosaic custom calls in each — which attention path each of the cell's
    programs takes.

    ``chip_smoke.py:attention_paths`` keeps a copy of these signatures
    (the benchmark may not import the program's scripts): when a
    signature of ``serving/batch.py`` moves, change the two together."""
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
    tables = vec(i32, B, pc.max_pages_per_seq)
    texts["decode_block_paged"] = batch_ops.decode_block_paged.lower(
        cfg, params, kp, vp, state, tables, vec(jnp.bool_), steps).as_text()
    if chunked:
        row = (vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(i32), vec(f32),
               vec(i32), vec(f32), vec(i32), key, vec(jnp.bool_), steps)
        texts["ragged_step_paged"] = batch_ops.ragged_step_paged.lower(
            cfg, params, kp, vp, state, tables, vec(i32, B, C), vec(i32),
            vec(jnp.bool_), *row).as_text()
    return texts, ("decode_block_paged",)
