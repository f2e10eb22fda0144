"""Model correctness: llama forward/prefill/decode consistency, bert embed.
Tiny configs on CPU (conftest forces JAX_PLATFORMS=cpu, 8 virtual devices).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import bert, llama


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_forward_shapes(tiny_llama):
    cfg, params = tiny_llama
    tokens = jnp.ones((2, 8), jnp.int32)
    logits = llama.forward(cfg, params, tokens)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_prefill_matches_forward(tiny_llama):
    """Cache-path prefill must produce the same last-token logits as the
    no-cache forward."""
    cfg, params = tiny_llama
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab_size)
    seq_lens = jnp.array([6, 4])
    cache = llama.KVCache.create(cfg, 2, max_len=16)
    last, cache = llama.prefill(cfg, params, tokens, cache, seq_lens)

    full = llama.forward(cfg, params, tokens)  # [B, S, V]
    np.testing.assert_allclose(last[0], full[0, 5], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(last[1], full[1, 3], rtol=2e-4, atol=2e-4)


def test_decode_matches_forward(tiny_llama):
    """Prefill + N decode steps must equal a full forward over the whole
    sequence (the KV-cache correctness invariant)."""
    cfg, params = tiny_llama
    B, S, N = 1, 4, 3
    full_tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S + N), 0, cfg.vocab_size)

    cache = llama.KVCache.create(cfg, B, max_len=16)
    last, cache = llama.prefill(cfg, params, full_tokens[:, :S], cache, jnp.array([S]))
    cache_len = jnp.array([S])
    decode_logits = []
    for i in range(N):
        cache_len = cache_len + 1
        last, cache = llama.decode_step(cfg, params, full_tokens[:, S + i], cache, cache_len)
        decode_logits.append(last)

    full = llama.forward(cfg, params, full_tokens)
    for i in range(N):
        np.testing.assert_allclose(
            decode_logits[i][0], full[0, S + i], rtol=2e-3, atol=2e-3
        )


def test_greedy_generate_deterministic(tiny_llama):
    cfg, params = tiny_llama
    prompt = jnp.array([[1, 2, 3, 0]], jnp.int32)
    out1 = llama.greedy_generate(cfg, params, prompt, jnp.array([3]), 4)
    out2 = llama.greedy_generate(cfg, params, prompt, jnp.array([3]), 4)
    assert out1.shape == (1, 4)
    np.testing.assert_array_equal(out1, out2)


def test_padding_does_not_change_result(tiny_llama):
    """Right-padding must not leak into valid positions (mask check)."""
    cfg, params = tiny_llama
    tokens = jnp.array([[5, 6, 7]], jnp.int32)
    padded = jnp.array([[5, 6, 7, 99, 123]], jnp.int32)
    cache1 = llama.KVCache.create(cfg, 1, max_len=8)
    cache2 = llama.KVCache.create(cfg, 1, max_len=8)
    last1, _ = llama.prefill(cfg, params, tokens, cache1, jnp.array([3]))
    last2, _ = llama.prefill(cfg, params, padded, cache2, jnp.array([3]))
    np.testing.assert_allclose(last1, last2, rtol=1e-5, atol=1e-5)


def test_param_count_llama8b_shape():
    """Sanity: the 8B preset's parameter count is ~8.0B."""
    cfg = llama.LlamaConfig.llama3_8b()
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    count = (
        V * D  # embedding
        + L * (D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D)  # attn
        + L * (3 * D * F)  # mlp
        + L * 2 * D + D  # norms
        + D * V  # head
    )
    assert 7.9e9 < count < 8.1e9


def test_bert_embed():
    cfg = bert.BertConfig.tiny()
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.ones((2, 10), jnp.int32)
    lens = jnp.array([10, 5])
    emb = bert.embed(cfg, params, tokens, lens)
    assert emb.shape == (2, cfg.d_model)
    norms = jnp.linalg.norm(emb, axis=-1)
    np.testing.assert_allclose(norms, jnp.ones(2), rtol=1e-5)


def test_bert_padding_invariance():
    cfg = bert.BertConfig.tiny()
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    short = jnp.array([[4, 5, 6]], jnp.int32)
    padded = jnp.array([[4, 5, 6, 77, 88]], jnp.int32)
    e1 = bert.embed(cfg, params, short, jnp.array([3]))
    e2 = bert.embed(cfg, params, padded, jnp.array([3]))
    np.testing.assert_allclose(e1, e2, rtol=1e-4, atol=1e-5)


def test_decode_loop_matches_stepwise(tiny_llama):
    """The scan-fused decode loop must emit exactly the tokens the
    stepwise decode_step_greedy path does."""
    cfg, params = tiny_llama
    prompt = jnp.array([[5, 9, 2]])
    seq_lens = jnp.array([3])
    n = 5
    oracle = llama.greedy_generate(cfg, params, prompt, seq_lens, n + 1)

    cache = llama.KVCache.create(cfg, 1, max_len=16)
    logits, cache = llama.prefill(cfg, params, prompt, cache, seq_lens)
    first = jnp.argmax(logits, axis=-1)
    _, _, _, toks = llama.decode_loop_greedy(
        cfg, params, first, cache, seq_lens, n
    )
    got = jnp.concatenate([first[:, None], toks], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


def test_kv_cache_is_a_two_leaf_pytree_that_survives_a_donated_jit():
    """``KVCache`` is registered as a plain dataclass pytree: k and v, in
    that order, nothing else — flatten/unflatten rebuild it, and a jit
    that donates it returns the same class with shapes and dtype kept."""
    cfg = llama.LlamaConfig.tiny()
    cache = llama.KVCache.create(cfg, 2, max_len=16)
    shape = (cfg.n_layers, 2, 16, cfg.n_kv_heads, cfg.head_dim)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    assert [f.name for f in dataclasses.fields(cache)] == ["k", "v"]
    assert len(leaves) == 2 and leaves[0] is cache.k and leaves[1] is cache.v
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back, llama.KVCache) and back.k is cache.k and back.v is cache.v
    assert cache.max_len == 16

    bump = jax.jit(lambda c: jax.tree.map(lambda x: x + 1, c), donate_argnums=0)
    out = bump(cache)
    assert isinstance(out, llama.KVCache)
    assert out.k.shape == out.v.shape == shape and out.k.dtype == out.v.dtype == cfg.dtype
    assert cache.k.is_deleted() and cache.v.is_deleted()  # donated, both leaves
    assert float(out.k[0, 0, 0, 0, 0]) == 1.0 and float(out.v[-1, -1, -1, -1, -1]) == 1.0
