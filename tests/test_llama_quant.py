"""Weight-only int8 quantization (models/llama.py quantize_weight/_mm).

The memory-honest bench config (bench.py) runs the Llama-3-8B shape with
W8 matmul weights on one 16 GB v5e chip; these tests pin the numerics
and the byte accounting of that path at tiny scale on CPU.
"""

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models import llama


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_quantized_forward_close(setup):
    cfg, params = setup
    qp = llama.quantize_params(params)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    lo = llama.forward(cfg, params, tok)
    lq = llama.forward(cfg, qp, tok)
    rel = float(jnp.abs(lo - lq).max() / jnp.abs(lo).max())
    assert rel < 0.1, f"W8 relative error too large: {rel}"
    agree = float((lo.argmax(-1) == lq.argmax(-1)).mean())
    assert agree > 0.85, f"argmax agreement too low: {agree}"


def test_quantized_weight_shapes(setup):
    _, params = setup
    w = params["layers"]["wq"]  # [L, D, H*Dh]
    q = llama.quantize_weight(w, axis=-2)
    assert q["q"].shape == w.shape and q["q"].dtype == jnp.int8
    assert q["s"].shape == (w.shape[0], w.shape[2])
    # int8 payload + f32 scales strictly smaller than the f32 original
    assert llama.param_bytes({"w": q}) < llama.param_bytes({"w": w})


def test_quantize_params_idempotent(setup):
    _, params = setup
    qp = llama.quantize_params(params)
    qp2 = llama.quantize_params(qp)  # already-quantized leaves pass through
    assert qp2["layers"]["wq"]["q"] is qp["layers"]["wq"]["q"]


def test_init_params_quantized_generates(setup):
    cfg, _ = setup
    qp = llama.init_params(cfg, jax.random.PRNGKey(0), quantize=True)
    assert isinstance(qp["layers"]["w_down"], dict)
    assert qp["layers"]["w_down"]["q"].dtype == jnp.int8
    tok = jnp.ones((2, 8), jnp.int32)
    out = llama.greedy_generate(cfg, qp, tok, jnp.full((2,), 8, jnp.int32), 4)
    assert out.shape == (2, 4)


def test_param_count_excludes_scales(setup):
    _, params = setup
    assert llama.param_count(llama.quantize_params(params)) == llama.param_count(params)


def test_quantized_decode_matches_generate(setup):
    """Paged/engine path smoke: decode_step with quantized params."""
    cfg, params = setup
    qp = llama.quantize_params(params)
    B, S = 2, 8
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    lens = jnp.full((B,), S, jnp.int32)
    ref = llama.greedy_generate(cfg, qp, prompt, lens, 6)
    # re-run through prefill + decode_step_greedy, must agree exactly
    cache = llama.KVCache.create(cfg, B, max_len=S + 8)
    logits, cache = llama.prefill(cfg, qp, prompt, cache, lens)
    tok = jnp.argmax(logits, axis=-1)
    toks = [tok]
    cache_len = lens
    for _ in range(5):
        tok, cache, cache_len = llama.decode_step_greedy(cfg, qp, tok, cache, cache_len)
        toks.append(tok)
    assert (jnp.stack(toks, 1) == ref).all()
