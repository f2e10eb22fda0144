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


# ------------------------------------------- dropless, for serving: held_experts
def _stacks(key, lead, n, D=16, F=24, int8=False):
    """Three stacks [*lead, n, ...] of one SwiGLU expert each, plain or
    weight-only int8 as the served trees hold them."""
    from gofr_tpu.models.llama import quantize_weight

    ks = jax.random.split(key, 3)
    w = {"w_gate": jax.random.normal(ks[0], lead + (n, D, F)) / np.sqrt(D),
         "w_up": jax.random.normal(ks[1], lead + (n, D, F)) / np.sqrt(D),
         "w_down": jax.random.normal(ks[2], lead + (n, F, D)) / np.sqrt(F)}
    return {k: quantize_weight(v, axis=-2) for k, v in w.items()} if int8 else w


def _gates(T, E, chosen, seed=0):
    """Gates [T, E] with row t's weight on the experts ``chosen(t)`` names."""
    rng = np.random.default_rng(seed)
    g = np.zeros((T, E), np.float32)
    for t in range(T):
        picks = list(chosen(t))
        g[t, picks] = rng.uniform(0.2, 1.0, len(picks))
    return jnp.asarray(g)


FIRST, HELD, PUBLISHED = 8, 6, 64  # experts 8..13 of 64 are held

# name -> (rows T, experts a row chooses, which ones row t chose, the rows that count)
GROUPED_CASES = {
    # experts 9 and 12 are nobody's; an expert's 18 or 19 rows are one tile, not a whole one
    "an-expert-nobody-chose": (37, 2, lambda t: (8 + (t % 2) * 2, 11 + (t % 2) * 2), None),
    "every-row-on-one-expert": (37, 1, lambda t: (10,), None),  # a tile of 32 and a second of 5
    # row 3 alone chose expert 13, and is left out: 13 is read by nobody
    "a-mask-removes-an-expert-s-only-row": (21, 2, lambda t: (13, 9) if t == 3 else (8 + t % 3, 40), lambda t: t != 3),
    "some-rows-choose-no-held-expert": (9, 2, lambda t: (20, 30) if t % 2 else (8, 13), lambda t: t % 4 != 2),
    "past-the-ridge": (131, 2, lambda t: (8 + t % 4, 9 + t % 4), lambda t: t % 7 != 0),
}


@pytest.mark.parametrize("layer", [None, 1], ids=["a-layer-s-stacks", "the-stacks-whole-and-a-layer"])
@pytest.mark.parametrize("int8", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("case", list(GROUPED_CASES))
def test_the_grouped_product_is_the_loop_over_every_row(case, int8, layer):
    """``held_experts`` where it multiplies each held expert by its own
    rows, against the loop over every row with the gate as the weight: the
    same float32 sum on every row that counts, and the experts it read
    counted as the host counts them."""
    from gofr_tpu.models.llama import _mm

    T, k, chosen, counts = GROUPED_CASES[case]
    lead = () if layer is None else (3,)
    experts = _stacks(jax.random.PRNGKey(1), lead, HELD, int8=int8)
    shared = _stacks(jax.random.PRNGKey(2), lead, 2, int8=int8)
    h = jax.random.normal(jax.random.PRNGKey(3), (T, 16), jnp.float32)
    gates = _gates(T, PUBLISHED, chosen)
    rows = None if counts is None else jnp.asarray([bool(counts(t)) for t in range(T)])
    mm = _mm if int8 else jnp.matmul
    assert moe_ops.groups_rows(T, PUBLISHED, k)
    at = None if layer is None else jnp.int32(layer)
    y, g, read = jax.jit(lambda h, gates: moe_ops.held_experts(
        h, gates, experts, shared, FIRST, mm, at, top_k=k, rows=rows))(h, gates)
    assert (np.asarray(g) == np.asarray(gates)[:, FIRST:FIRST + HELD]).all()
    want = np.asarray(moe_ops._over_every_row(h, g, experts, shared, mm, at))
    keep = np.ones(T, bool) if rows is None else np.asarray(rows)
    assert np.abs(want[keep]).max() > 0.1 and np.abs(np.asarray(y)[keep] - want[keep]).max() < 1e-5
    took = (np.asarray(g) > 0) & keep[:, None]
    assert int(read) == took.any(axis=0).sum() < HELD


def test_the_loop_over_every_row_reads_every_held_expert_and_takes_no_mask():
    """Under the ridge with two rows or more an expert the product is the
    loop: ``rows`` changes nothing, and the count that comes back is every
    held expert, whatever the routing."""
    experts, shared = _stacks(jax.random.PRNGKey(1), (), HELD), _stacks(jax.random.PRNGKey(2), (), 2)
    h = jax.random.normal(jax.random.PRNGKey(3), (40, 16), jnp.float32)
    gates = _gates(40, PUBLISHED, lambda t: (8 + t % 6, 20, 30, 40))
    assert not moe_ops.groups_rows(40, PUBLISHED, 4)
    y, g, read = moe_ops.held_experts(h, gates, experts, shared, FIRST, top_k=4, rows=jnp.arange(40) % 2 == 0)
    assert int(read) == HELD
    assert (np.asarray(y) == np.asarray(moe_ops._over_every_row(h, g, experts, shared, jnp.matmul, None))).all()
    # a caller that tells no top_k is taken to choose any number: grouped past the ridge alone
    assert int(moe_ops.held_experts(h[:5], gates[:5], experts, shared, FIRST)[2]) == HELD
    assert moe_ops.groups_rows(129, PUBLISHED, None) and not moe_ops.groups_rows(128, PUBLISHED, None)


# the four shapes the cells run: (rows, published experts, experts a row, held) -> grouped?
@pytest.mark.parametrize("T, E, k, held, grouped", [
    (64, 128, 8, 16, False),   # commandaplus.wide, a decode step: 4 rows an expert, under the ridge
    (256, 128, 8, 16, True),   # commandaplus.wide, its largest prefill bucket: past the ridge
    (32, 256, 8, 32, True),    # deepseekv32.long, a decode step: 1 row an expert
    (256, 256, 8, 32, True),   # deepseekv32.long, a chunk: past the ridge
], ids=["wide-decode", "wide-bucket-256", "long-decode", "long-chunk"])
def test_the_shapes_choose_between_the_loop_and_the_grouped_product(T, E, k, held, grouped):
    assert moe_ops.groups_rows(T, E, k) is grouped
    experts, shared = _stacks(jax.random.PRNGKey(1), (), held, D=8, F=8), _stacks(jax.random.PRNGKey(2), (), 1, D=8, F=8)
    traced = jax.make_jaxpr(lambda h, gates: moe_ops.held_experts(h, gates, experts, shared, 0, top_k=k))(
        jax.ShapeDtypeStruct((T, 8), jnp.float32), jax.ShapeDtypeStruct((T, E), jnp.float32))
    assert ("while[" in str(traced)) is grouped  # the loop over the tiles that hold a row, and no other
    assert [v.aval.shape for v in traced.jaxpr.outvars] == [(T, 8), (T, held), ()]
    # the other buckets of wide's prefill stay the loop: at 128 rows and fewer a matrix's read hides the products
    assert [moe_ops.groups_rows(b, 128, 8) for b in (32, 64, 128)] == [False] * 3
