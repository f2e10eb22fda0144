"""Flash-attention kernel vs the dense reference (ops/attention.py).

Runs the Pallas kernel in interpret mode on CPU (tests/conftest.py pins the
platform), mirroring the reference's strategy of testing transport logic
against single-node fakes (SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops.attention import attention
from gofr_tpu.ops.flash_attention import flash_attention


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("gqa", [1, 2, 4])
def test_matches_dense(causal, gqa):
    B, S, H, D = 2, 256, 4, 64
    q = _rand((B, S, H, D), 0)
    k = _rand((B, S, H // gqa, D), 1)
    v = _rand((B, S, H // gqa, D), 2)
    kv_len = jnp.array([S, S - 37], jnp.int32)

    ref = attention(q, k, v, causal=causal, kv_len=kv_len)
    out = flash_attention(q, k, v, kv_len, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blocks_smaller_than_seq():
    B, S, H, D = 1, 512, 2, 64
    q, k, v = _rand((B, S, H, D), 0), _rand((B, S, H, D), 1), _rand((B, S, H, D), 2)
    ref = attention(q, k, v, causal=True)
    out = flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_bf16_inputs():
    B, S, H, D = 2, 128, 4, 64
    q = _rand((B, S, H, D), 0, jnp.bfloat16)
    k = _rand((B, S, H, D), 1, jnp.bfloat16)
    v = _rand((B, S, H, D), 2, jnp.bfloat16)
    ref = attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )


def test_fully_masked_row_is_zero():
    """kv_len == 0 rows (padding slots in a serving batch) must yield zeros,
    not NaN (the engine relies on this to keep dead slots inert)."""
    B, S, H, D = 2, 128, 2, 64
    q, k, v = _rand((B, S, H, D), 0), _rand((B, S, H, D), 1), _rand((B, S, H, D), 2)
    kv_len = jnp.array([S, 0], jnp.int32)
    out = flash_attention(q, k, v, kv_len, causal=True, interpret=True)
    assert not np.any(np.isnan(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)


def test_rejects_ragged_blocks():
    q = _rand((1, 100, 2, 64), 0)
    with pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=64, block_k=64, interpret=True)


def test_llama_prefill_flash_matches_dense(monkeypatch):
    """End-to-end: the flagship model's prefill with the flash path vs the
    dense path (cfg.attn_impl toggles; SURVEY §7 phase 4 hot path). On the
    CPU the model's flash call computes the dense reference
    (ops/backend.py), so the kernel is put under the interpreter here."""
    import functools

    from gofr_tpu.models import llama

    monkeypatch.setattr(
        llama, "flash_attention",
        functools.partial(flash_attention, interpret=True),
    )

    base = dict(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, dtype=jnp.float32,
    )
    cfg_d = llama.LlamaConfig.tiny(**base, attn_impl="dense")
    cfg_f = llama.LlamaConfig.tiny(**base, attn_impl="flash")
    params = llama.init_params(cfg_d, jax.random.PRNGKey(0))

    B, S = 2, 128
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 128)
    seq_lens = jnp.array([S, S - 17], jnp.int32)

    cache_d = llama.KVCache.create(cfg_d, B, max_len=S)
    cache_f = llama.KVCache.create(cfg_f, B, max_len=S)
    last_d, _ = llama.prefill(cfg_d, params, tokens, cache_d, seq_lens)
    last_f, _ = llama.prefill(cfg_f, params, tokens, cache_f, seq_lens)
    np.testing.assert_allclose(
        np.asarray(last_f), np.asarray(last_d), atol=5e-4, rtol=1e-4
    )
