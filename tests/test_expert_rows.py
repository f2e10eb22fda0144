"""``ops/expert_rows``: the every-row expert sum of ``ops/moe.held_experts``
as one Pallas call a layer, under the Pallas interpreter on the CPU, held
to the loop of XLA products it replaces on the chip; and the branch the
engine names (``moe.path``) held to the branch ``held_experts`` takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import cohere2_moe as cm
from gofr_tpu.models import lfm2_moe as lm
from gofr_tpu.models import nemotron_h as nh
from gofr_tpu.models.llama import _mm, quantize_weight
from gofr_tpu.ops import backend, expert_rows
from gofr_tpu.ops import moe as moe_ops

D, F = 128, 384  # whole lane tiles, as the kernel serves them; F is three tiles of 128 under a small budget
BF16, F32 = jnp.bfloat16, jnp.float32


def _stacks(key, lead, n, d=D, f=F, int8=True):
    """Three stacks [*lead, n, ...] of SwiGLU experts, int8 as the served trees hold them."""
    ks = jax.random.split(key, 3)
    w = {"w_gate": jax.random.normal(ks[0], lead + (n, d, f)) / np.sqrt(d),
         "w_up": jax.random.normal(ks[1], lead + (n, d, f)) / np.sqrt(d),
         "w_down": jax.random.normal(ks[2], lead + (n, f, d)) / np.sqrt(f)}
    return {k: quantize_weight(v, axis=-2) for k, v in w.items()} if int8 else w


def _gates(T, n, seed=0):
    """Gates [T, n]: up to four experts a row, expert 2 chosen by no row,
    every third row choosing none."""
    rng = np.random.default_rng(seed)
    g = np.zeros((T, n), np.float32)
    k = min(4, n - 1)
    for t in range(T):
        if t % 3 != 1:
            picks = rng.choice([e for e in range(n) if e != 2], k, replace=False)
            g[t, picks] = rng.uniform(0.1, 1.0, k)
    return jnp.asarray(g)


def _kernel_rounding(x, g, stacks, layer):
    """The sum as the kernel rounds it: ``moe._ffn``'s gate and up, the down
    product left float32 before the gate weighs it. Over the rows padded
    to 16: XLA's product of ONE row on the CPU sums otherwise than the
    interpreter's (and than its own of several), and the bf16 roundings
    after it carry the difference."""
    T = x.shape[0]
    x, g = jnp.pad(x, ((0, -T % 16), (0, 0))), jnp.pad(g, ((0, -T % 16), (0, 0)))
    y = jnp.zeros(x.shape, F32)
    for e in range(g.shape[1]):
        w = {k: moe_ops._at(v, e, layer) for k, v in stacks.items()}
        h = jax.nn.silu(_mm(x, w["w_gate"]).astype(F32)).astype(x.dtype) * _mm(x, w["w_up"])
        down = jnp.matmul(h, w["w_down"]["q"].astype(x.dtype), preferred_element_type=F32) * w["w_down"]["s"]
        y = y + g[:, e:e + 1] * down
    return y[:T]


@pytest.fixture
def in_the_interpreter(monkeypatch):
    """What runs where a test does not say: the Pallas interpreter, as the
    chip would run Mosaic; a tile of F is 128 columns."""
    monkeypatch.setattr(expert_rows, "kernel_mode",
                        lambda interpret=None: backend.INTERPRET if interpret is None else backend.kernel_mode(interpret))
    monkeypatch.setattr(expert_rows, "_WEIGHT_VMEM", 2 * 3 * D * 128)


@pytest.mark.parametrize("T", [1, 5, 32, 64, 128])
@pytest.mark.parametrize("whole", [False, True], ids=["a-layer-s-stacks", "the-stacks-whole-in-a-scan"])
def test_the_kernel_is_the_loop_over_every_row(T, whole, in_the_interpreter):
    """The kernel against ``_loop_over_every_row``, the shared experts
    through it at 1/n over every row: within bf16's rounding of the down
    product (the loop rounds it once an expert, the kernel never), and to
    float32's sums against that rounding written out; a row that chose no
    expert gets the shared experts' mean alone."""
    n, L = 6, 3
    lead = (L,) if whole else ()
    experts, shared = _stacks(jax.random.PRNGKey(1), lead, n), _stacks(jax.random.PRNGKey(2), lead, 2)
    h = jax.random.normal(jax.random.PRNGKey(3), (T, D), F32).astype(BF16)
    g = _gates(T, n)
    assert expert_rows.f_tile(D, F, expert_rows._WEIGHT_VMEM) == 128  # three tiles of F
    assert expert_rows.serves(experts) and expert_rows.serves(shared)

    def each(fn):
        if not whole:
            return jax.jit(lambda h, g: fn(h, g, None))(h, g)[None]
        # the layer a traced scan index, as the models' layer loops hand it over
        return jax.jit(lambda h, g: jax.lax.scan(lambda c, i: (c, fn(h, g, i)), 0, jnp.arange(L))[1])(h, g)

    got = each(lambda h, g, i: moe_ops._over_every_row(h, g, experts, shared, _mm, i))
    loop = each(lambda h, g, i: moe_ops._loop_over_every_row(h, g, experts, shared, _mm, i))
    mine = each(lambda h, g, i: _kernel_rounding(h, g, experts, i) + _kernel_rounding(
        h, jnp.full((T, 2), 0.5, F32), shared, i))
    got, loop, mine = (np.asarray(a) for a in (got, loop, mine))
    scale = np.abs(loop).max()
    assert got.shape == (1 if not whole else L, T, D) and scale > 0.1
    assert np.abs(got - loop).max() <= 1e-2 * scale
    assert np.abs(got - mine).max() <= 1e-5 * scale
    # a row with no routed expert: the routed kernel adds exactly zero to it
    routed = np.asarray(expert_rows.expert_rows(h, g, jax.tree.map(lambda a: a[0], experts) if whole else experts))
    assert (routed[np.asarray(g).sum(axis=1) == 0] == 0).all()


def _relu2_stacks(key, lead, n, f):
    """Two stacks [*lead, n, ...] of relu² experts of width ``f``, int8 and
    zero-padded to whole lane tiles as ``models/nemotron_h`` stores them."""
    ks = jax.random.split(key, 2)
    w = {"w_up": jax.random.normal(ks[0], lead + (n, D, f)) / np.sqrt(D),
         "w_down": jax.random.normal(ks[1], lead + (n, f, D)) / np.sqrt(f)}
    return nh.pad_experts({k: quantize_weight(v, axis=-2) for k, v in w.items()})


@pytest.mark.parametrize("T", [1, 5, 64])
@pytest.mark.parametrize("whole", [False, True], ids=["a-layer-s-stacks", "the-stacks-whole-in-a-scan"])
def test_the_relu2_kernel_is_the_loop_over_every_row(T, whole, in_the_interpreter):
    """Stacks without a W_gate — relu² experts of width 200, stored padded
    to 256, and a shared one of 128 — through the kernel's two-matrix form
    against ``_loop_over_every_row``: within bf16's rounding of the down
    product, as the SwiGLU form; the padding adds exactly nothing, so
    the kernel equals the loop over the published width's columns."""
    n, L = 6, 3
    lead = (L,) if whole else ()
    experts = _relu2_stacks(jax.random.PRNGKey(7), lead, n, 200)
    shared = _relu2_stacks(jax.random.PRNGKey(8), lead, 1, 128)
    assert experts["w_up"]["q"].shape[-1] == 256 and expert_rows.serves(experts) and expert_rows.serves(shared)
    assert expert_rows.f_tile(D, 256, expert_rows._WEIGHT_VMEM, mats=2) == 128
    h = jax.random.normal(jax.random.PRNGKey(9), (T, D), F32).astype(BF16)
    g = _gates(T, n)

    def each(fn):
        if not whole:
            return jax.jit(lambda h, g: fn(h, g, None))(h, g)[None]
        return jax.jit(lambda h, g: jax.lax.scan(lambda c, i: (c, fn(h, g, i)), 0, jnp.arange(L))[1])(h, g)

    got = np.asarray(each(lambda h, g, i: moe_ops._over_every_row(h, g, experts, shared, _mm, i)))
    loop = np.asarray(each(lambda h, g, i: moe_ops._loop_over_every_row(h, g, experts, shared, _mm, i)))
    unpadded = {k: {"q": v["q"][..., :200], "s": v["s"][..., :200]} if k == "w_up" else
                {"q": v["q"][..., :200, :], "s": v["s"]} for k, v in experts.items()}
    narrow = np.asarray(each(lambda h, g, i: moe_ops._loop_over_every_row(h, g, unpadded, shared, _mm, i)))
    scale = np.abs(loop).max()
    assert scale > 0.1 and np.abs(got - loop).max() <= 1e-2 * scale
    assert np.abs(narrow - loop).max() <= 1e-6 * scale


def test_one_tile_and_many_give_the_same_sum(in_the_interpreter, monkeypatch):
    """Splitting F is exact up to the order of float32 sums: the down
    scale is per output channel."""
    experts = _stacks(jax.random.PRNGKey(4), (), 3)
    h = jax.random.normal(jax.random.PRNGKey(5), (16, D), F32).astype(BF16)
    g = _gates(16, 3)
    tiled = np.asarray(expert_rows.expert_rows(h, g, experts))
    monkeypatch.setattr(expert_rows, "_WEIGHT_VMEM", 2 * 3 * D * F)
    whole = np.asarray(expert_rows.expert_rows(h, g, experts))
    assert np.abs(tiled - whole).max() <= 1e-6 * np.abs(whole).max()


def test_tiles_come_from_the_shapes():
    """The widest multiple of 128 that divides F and fits the budget with
    both slots of three int8 tiles: at the cells' shapes 256 columns."""
    budget = expert_rows._WEIGHT_VMEM
    assert expert_rows.f_tile(2048, 1792, budget) == 256  # lfm2.tools: 7 tiles an expert
    assert expert_rows.f_tile(4096, 4096, budget) == 256  # commandaplus.wide: 16
    assert expert_rows.f_tile(2048, 1792, 24 << 20) == 1792  # the whole of F where it fits
    assert expert_rows.f_tile(8192, 1920, 1 << 20) == 128  # none fits: the narrowest
    # relu² experts, two matrices a step: nemotron3nano.solve's routed experts (1856 stored as 1920) and shared one
    assert expert_rows.f_tile(2688, 1920, budget, mats=2) == 640  # 3 tiles an expert
    assert expert_rows.f_tile(2688, 3712, budget, mats=2) == 128  # 29 tiles: 3712 is 29 x 128


def _held(cfg, params, stacks, T):
    """The jaxpr of one expert layer's ``held_experts`` at T rows over the model's own stacks."""
    return str(jax.make_jaxpr(lambda h, gates: moe_ops.held_experts(
        h, gates, stacks["experts"], stacks["shared"], cfg.first_expert, _mm, jnp.int32(0),
        top_k=cfg.top_k))(jax.ShapeDtypeStruct((T, cfg.d_model), BF16),
                          jax.ShapeDtypeStruct((T, cfg.n_experts), F32)))


MODELS = {
    # family -> (tiny config at whole lane tiles, its expert stacks from its params)
    "lfm2_moe": (lambda: lm.Lfm2MoeConfig.tiny(d_model=128, d_ff_expert=128, dtype=BF16),
                 lambda p: {"experts": p["moe"]["experts"], "shared": lm._NO_SHARED}, lm),
    "cohere2_moe": (lambda: cm.Cohere2MoeConfig.tiny(d_model=128, d_ff=128, dtype=BF16),
                    lambda p: {"experts": p["layers"]["experts"], "shared": p["layers"]["shared"]}, cm),
}


@pytest.mark.parametrize("int8", [True, False], ids=["int8", "plain"])
@pytest.mark.parametrize("family", list(MODELS))
def test_the_branch_the_engine_names_is_the_branch_taken(family, int8, in_the_interpreter):
    """``moe.path`` — what the engine sets as ``moe_path`` on a dispatch
    span — against what ``held_experts`` traces at the same rows: a
    ``pallas_call`` where it says ``kernel``, the grouped product's
    ``while`` where it says ``grouped``, neither where it says ``loop``;
    plain stacks keep the loop."""
    make_cfg, stacks_of, module = MODELS[family]
    cfg = make_cfg()
    params = module.init_params(cfg, jax.random.PRNGKey(0))
    if int8:
        params = module.quantize_params(params)
    stacks = stacks_of(params)
    under = cfg.n_experts * 2 // cfg.top_k  # the fewest rows at which each expert expects two
    for T, grouped in ((under, False), (under - 1, True), (moe_ops.RIDGE_ROWS + 1, True)):
        said = moe_ops.path(T, cfg.n_experts, cfg.top_k, stacks["experts"])
        traced = _held(cfg, params, stacks, T)
        assert said == ("grouped" if grouped else "kernel" if int8 else "loop")
        assert ("pallas_call" in traced) is (said == "kernel")
        assert ("while[" in traced) is grouped


def test_on_the_cpu_the_reference_path_is_the_loop():
    """Without the interpreter asked for, the CPU runs the loop: no
    ``pallas_call`` in ``held_experts`` and ``moe.path`` says ``loop``."""
    experts, shared = _stacks(jax.random.PRNGKey(1), (), 4), _stacks(jax.random.PRNGKey(2), (), 1)
    assert not expert_rows.serves(experts) and moe_ops.path(8, 4, 2, experts) == "loop"
    traced = jax.make_jaxpr(lambda h, g: moe_ops.held_experts(h, g, experts, shared, 0, _mm, top_k=2))(
        jnp.zeros((8, D), BF16), jnp.zeros((8, 4), F32))
    assert "pallas_call" not in str(traced)
    with pytest.raises(ValueError, match="no reference of its own"):
        expert_rows.expert_rows(jnp.zeros((8, D), BF16), jnp.zeros((8, 4), F32), experts)
