"""Pipeline parallelism: GPipe schedule over pp mesh axis vs dense
reference; composition with tp/dp via partial manual mapping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import llama
from gofr_tpu.models.train import make_pp_train_step, sharded_train_step
from gofr_tpu.parallel import build_mesh
from gofr_tpu.parallel.mesh import MeshSpec
from gofr_tpu.parallel.pipeline import pipeline_apply, pp_forward
from gofr_tpu.parallel.sharding import llama_sharding_rules, shard_params


@pytest.fixture(scope="module")
def pp_mesh():
    return build_mesh(MeshSpec(pp=4, dp=2))


@pytest.fixture(scope="module")
def mixed_mesh():
    return build_mesh(MeshSpec(pp=2, tp=2, dp=2))


def test_pipeline_apply_identity_chain(pp_mesh):
    """Each stage adds its stage param; result = x + sum(all stages)."""
    stage_params = jnp.arange(4.0)  # one scalar per stage

    def stage_fn(p, x):
        return x + p[0]  # local stage slice is [1]

    x_mb = jnp.ones((8, 2, 3))  # M=8 microbatches
    out = pipeline_apply(stage_fn, stage_params[:, None], x_mb, pp_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x_mb) + 6.0)


def test_pp_forward_matches_dense(pp_mesh):
    cfg = llama.LlamaConfig.tiny(n_layers=4, attn_impl="dense")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)
    ref = llama.forward(cfg, params, tokens)
    out = jax.jit(lambda p, t: pp_forward(cfg, p, t, pp_mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4)


def test_pp_forward_rejects_bad_layer_split(pp_mesh):
    cfg = llama.LlamaConfig.tiny(n_layers=3)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.ones((4, 8), jnp.int32)
    with pytest.raises(ValueError):
        pp_forward(cfg, params, tokens, pp_mesh)


def test_pp_train_step_decreases_loss(mixed_mesh):
    """Two steps of pp+tp+dp training on one repeated batch reduce loss."""
    cfg = llama.LlamaConfig.tiny(n_layers=4, n_heads=4, n_kv_heads=2, attn_impl="dense")
    rules = llama_sharding_rules(pp=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    params = shard_params(params, mixed_mesh, rules)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)

    init_opt, compile_for = sharded_train_step(cfg, mixed_mesh, rules)
    opt_state = init_opt(params)
    step = compile_for(params, opt_state, tokens)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


def test_pp_grads_match_dense():
    """Gradients through the ppermute ring equal single-device grads."""
    mesh = build_mesh(MeshSpec(pp=4, dp=2))
    cfg = llama.LlamaConfig.tiny(n_layers=4, attn_impl="dense")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)

    def dense_loss(p):
        logits = llama.forward(cfg, p, tokens)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    def pp_loss(p):
        logits = pp_forward(cfg, p, tokens, mesh)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    g_ref = jax.grad(dense_loss)(params)
    g_pp = jax.jit(jax.grad(pp_loss))(params)
    flat_ref = jax.tree.leaves(g_ref)
    flat_pp = jax.tree.leaves(g_pp)
    for a, b in zip(flat_ref, flat_pp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-4)
