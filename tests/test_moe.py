"""MoE + expert parallelism: GShard dispatch vs dense reference on the
8-virtual-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import moe
from gofr_tpu.ops import moe as moe_ops
from gofr_tpu.parallel import build_mesh
from gofr_tpu.parallel.mesh import MeshSpec


@pytest.fixture(scope="module")
def ep_mesh():
    return build_mesh(MeshSpec(ep=4, dp=2))


def _weights(key, D=16, F=32, E=4):
    ks = jax.random.split(key, 4)
    wr = jax.random.normal(ks[0], (D, E)) * 0.5
    wg = jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D)
    wu = jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D)
    wd = jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)
    return wr, wg, wu, wd


def test_ep_matches_reference_with_full_capacity(ep_mesh):
    """Capacity ≥ tokens-per-group ⇒ no drops ⇒ exact match with dense."""
    wr, wg, wu, wd = _weights(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    ref = moe_ops.moe_ffn_reference(x, wr, wg, wu, wd, top_k=2)
    out = moe_ops.moe_ffn_ep(x, wr, wg, wu, wd, ep_mesh, top_k=2, capacity=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ep_capacity_drops_are_graceful(ep_mesh):
    """Tiny capacity drops tokens but output stays finite and bounded."""
    wr, wg, wu, wd = _weights(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 16))
    out = moe_ops.moe_ffn_ep(x, wr, wg, wu, wd, ep_mesh, top_k=2, capacity=1)
    assert np.all(np.isfinite(np.asarray(out)))


def test_ep_rejects_bad_divisibility(ep_mesh):
    wr, wg, wu, wd = _weights(jax.random.PRNGKey(4), E=6)
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    with pytest.raises(ValueError):
        moe_ops.moe_ffn_ep(x, wr, wg, wu, wd, ep_mesh)  # 6 experts vs ep=4


def test_moe_forward_ep_matches_dense(ep_mesh):
    cfg = moe.MoeConfig.tiny(capacity_factor=8.0)  # high capacity: no drops
    params = moe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    ref = moe.forward(cfg, params, tokens, mesh=None)
    out = moe.forward(cfg, params, tokens, mesh=ep_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4)


def test_load_balance_loss_finite_and_positive():
    cfg = moe.MoeConfig.tiny()
    params = moe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    aux = moe.load_balance_loss(cfg, params, tokens)
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_aux_stats_use_per_layer_hidden_states():
    """Layer-1 router stats must come from the residual stream it actually
    routes on, not the embeddings (regression: aux loss previously fed every
    layer's router the embedding output)."""
    cfg = moe.MoeConfig.tiny()
    params = moe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    _, (f, p) = moe.forward(cfg, params, tokens, return_aux=True)
    assert f.shape == (cfg.n_layers, cfg.n_experts)
    # what the (buggy) embedding-based computation would produce for layer 1
    from gofr_tpu.ops.moe import router_topk, switch_aux_stats
    from gofr_tpu.ops.norms import rms_norm

    x = params["embedding"][tokens].astype(cfg.dtype).reshape(-1, cfg.d_model)
    x = rms_norm(x, params["layers"]["mlp_norm"][1], cfg.norm_eps)
    ti, _, probs = router_topk(x, params["layers"]["w_router"][1], cfg.top_k)
    _, p_embed = switch_aux_stats(ti, probs)
    assert not np.allclose(np.asarray(p[1]), np.asarray(p_embed), atol=1e-5)
    # each layer's P_e sums to 1 (true softmax means)
    np.testing.assert_allclose(np.asarray(p).sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f).sum(-1), 1.0, atol=1e-5)


def test_moe_grads_flow_through_ep(ep_mesh):
    """value_and_grad through the all_to_all dispatch produces finite,
    nonzero expert grads."""
    cfg = moe.MoeConfig.tiny(capacity_factor=4.0)
    params = moe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)

    def loss(p):
        logits, _ = moe._forward_jit(cfg, p, tokens, ep_mesh)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    val, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(val))
    g = grads["layers"]["w_gate"]
    assert np.all(np.isfinite(np.asarray(g)))
    assert float(jnp.abs(g).sum()) > 0
