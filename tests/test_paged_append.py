"""The decode step's append and the layer-indexed paged kernel: the pools
ride the layer scan as carry and are touched only by the two Pallas calls
(``ops/paged_attention.paged_kv_append`` aliased over both pools,
``paged_decode_attention`` given the whole pools and a layer index).

On the CPU: the append's kernel (``interpret=True``) against the scatter it
replaced, and whole decode blocks of both served families through the
kernels against the references and the dense path. For the chip: the
compiled ``decode_block_paged`` holds no XLA op that makes, slices or
updates a pool — what keeps a later edit from bringing the transposes
around the kernel back (PERF.md §6, PR 30).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import re

import hlo_text
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import cohere2_moe as cm
from gofr_tpu.models import deepseek_v32 as ds
from gofr_tpu.models import lfm2_moe as lm
from gofr_tpu.models import llama
from gofr_tpu.models import phi4flash as phi
from gofr_tpu.ops import paged_attention as pa
from gofr_tpu.serving import batch as batch_ops

PAGE = 4
STEPS = 4
SLOT_PAGES = 4  # a row's table: 16 positions


# ------------------------------------------------------ the append alone
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("rows", [1, 5, 11], ids=["one-row", "one-chunk", "chunks-with-a-ragged-last"])
def test_the_append_kernel_writes_what_the_scatter_wrote(rows, dtype, monkeypatch):
    """Every live page bit for bit as ``pool.at[layer, pages, :, offsets]
    .set``; rows sent to the trash page (the last) leave garbage there and
    nowhere else; the other layers are untouched."""
    L, N, Hkv, Dh = 3, 14, 2, 16
    if rows == 11:  # four rows a chunk: three programs, the last with three rows
        monkeypatch.setattr(pa, "_APPEND_VMEM_BUDGET", 4 * 2 * Hkv * PAGE * Dh * jnp.dtype(dtype).itemsize)
    keys = jax.random.split(jax.random.PRNGKey(rows), 4)
    k_pool = jax.random.normal(keys[0], (L, N, Hkv, PAGE, Dh), dtype)
    v_pool = jax.random.normal(keys[1], (L, N, Hkv, PAGE, Dh), dtype)
    k_new = jax.random.normal(keys[2], (rows, Hkv, Dh), dtype)
    v_new = jax.random.normal(keys[3], (rows, Hkv, Dh), dtype)
    rng = np.random.default_rng(rows)
    pages = rng.permutation(N - 1)[:rows].astype(np.int32)
    pages[1::4] = N - 1  # inactive rows: all on the trash page
    offsets = rng.integers(0, PAGE, rows).astype(np.int32)
    offsets[0] = PAGE - 1
    args = (k_new, v_new, jnp.int32(1), jnp.asarray(pages), jnp.asarray(offsets))
    got = pa.paged_kv_append(k_pool, v_pool, *args, interpret=True)
    want = pa.paged_kv_append_ref(k_pool, v_pool, *args)
    for g, w, before in zip(got, want, (k_pool, v_pool)):
        g, w, before = (np.asarray(a.astype(jnp.float32)) for a in (g, w, before))
        assert (g[:, :N - 1] == w[:, :N - 1]).all()
        assert (g[0] == before[0]).all() and (g[2] == before[2]).all()
        assert not (g[1] == before[1]).all()


@pytest.mark.parametrize("second", [128, None], ids=["an-indexer-key", "no-second-pool"])
def test_the_append_kernel_takes_pools_of_two_page_shapes_or_one_pool(second):
    """A latent row and an indexer key a token (``deepseek_v32``): one
    head, widths that differ, one set of pages and offsets for both; or
    the latent row alone (no indexer: the second pool is None, and stays
    None)."""
    L, N, rows = 2, 9, 5
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    k_pool = jax.random.normal(keys[0], (L, N, 1, PAGE, 256), jnp.bfloat16)
    k_new = jax.random.normal(keys[2], (rows, 1, 256), jnp.bfloat16)
    v_pool = v_new = None
    if second:
        v_pool = jax.random.normal(keys[1], (L, N, 1, PAGE, second), jnp.bfloat16)
        v_new = jax.random.normal(keys[3], (rows, 1, second), jnp.bfloat16)
    pages, offsets = jnp.asarray([4, 0, 7, 2, 5]), jnp.asarray([0, 3, 1, 2, 3])
    args = (k_new, v_new, jnp.int32(1), pages, offsets)
    got = pa.paged_kv_append(k_pool, v_pool, *args, interpret=True)
    want = pa.paged_kv_append_ref(k_pool, v_pool, *args)
    assert (got[1] is None) is (second is None) and not bool(jnp.all(got[0] == k_pool))
    for g, w in zip(got, want):
        assert (g is None and w is None) or (g.shape == w.shape and bool(jnp.all(g == w)))


def test_on_the_cpu_the_append_is_the_scatter():
    pool = jnp.zeros((2, 3, 1, PAGE, 8), jnp.float32)
    new = jnp.ones((1, 1, 8), jnp.float32)
    k, v = pa.paged_kv_append(pool, pool, new, 2 * new, 1, jnp.asarray([2]), jnp.asarray([3]))
    assert float(k.sum()) == 8 and float(v.sum()) == 16 and (np.asarray(k[1, 2, 0, 3]) == 1).all()


@pytest.mark.parametrize("window", [None, 6], ids=["full", "window"])
def test_the_kernel_reads_the_layer_it_is_given(window):
    """The whole pools and a traced layer index against the reference on
    that layer's slice; the other layers hold NaN."""
    L, B, H, Hkv, Dh = 3, 3, 4, 2, 16
    N = B * SLOT_PAGES + 1
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (B, H, Dh), jnp.float32)
    layer_k = jax.random.normal(keys[1], (N, Hkv, PAGE, Dh), jnp.float32)
    layer_v = jax.random.normal(keys[2], (N, Hkv, PAGE, Dh), jnp.float32)
    nan = jnp.full_like(layer_k, jnp.nan)
    k_pool, v_pool = jnp.stack([nan, nan, layer_k]), jnp.stack([nan, nan, layer_v])
    tables = jnp.asarray(np.random.default_rng(2).permutation(N - 1).reshape(B, SLOT_PAGES), jnp.int32)
    seq_lens = jnp.asarray([1, 9, 16], jnp.int32)
    kw = {} if window is None else {"window": jnp.int32(window)}
    want = pa.paged_decode_attention_ref(q, layer_k, layer_v, tables, seq_lens, **kw)
    by_index = jax.jit(lambda layer: pa.paged_decode_attention(
        q, k_pool, v_pool, tables, seq_lens, interpret=True, layer=layer, **kw))(jnp.int32(2))
    np.testing.assert_allclose(np.asarray(by_index), np.asarray(want), rtol=2e-5, atol=2e-5)
    ref_by_index = pa.paged_decode_attention_ref(q, k_pool, v_pool, tables, seq_lens, layer=2, **kw)
    assert (np.asarray(ref_by_index) == np.asarray(want)).all()


# --------------------------------------- whole decode blocks, both families
FAMILIES = {
    "llama": (llama, llama.LlamaConfig.tiny(vocab_size=300)),
    # two periods of three window layers (8 positions) and a full one
    "cohere2_moe": (cm, cm.Cohere2MoeConfig.tiny(vocab_size=300)),
    # a dense layer and two expert layers; a selection of 8 positions, which binds from the ninth on
    "deepseek_v32": (ds, ds.DeepseekV32Config.tiny(vocab_size=300)),
}
# (resident prompt length, active, budget) a row. Row 0 always starts with 6
# resident positions, so its four steps write positions 6..9: slot PAGE - 1
# of its second page, then slot 0 of its third, and past cohere2's window
ROWS = {
    "a stopped and an inactive row": ((6, True, 9), (3, True, 2), (5, False, 9)),
    "one row live": ((6, True, 9), (3, False, 9), (5, False, 9)),
    "all rows live": ((6, True, 9), (3, True, 9), (7, True, 9)),
}
MODES = ("scatter", "append-kernel", "both-kernels")


def _prompt(row: int, n: int) -> np.ndarray:
    return np.random.default_rng(10 + row).integers(3, 259, n).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _params(family: str):
    model, cfg = FAMILIES[family]
    return model.init_params(cfg, jax.random.PRNGKey(7))


def _run_block(family: str, rows: tuple, mode: str, monkeypatch):
    """Prefill each row's prompt into pages of its own, then one
    ``decode_block_paged`` of four steps. ``mode`` says what the model's
    step calls: the CPU references (the scatter this PR replaced and the
    gather), the append's kernel under the interpreter with the gather, or
    both kernels. Each mode runs under a config of its own (a static
    argument of every jitted program), so none reuses another's trace."""
    model, cfg = FAMILIES[family]
    params = _params(family)
    cfg = dataclasses.replace(cfg, max_seq_len=cfg.max_seq_len + 1 + MODES.index(mode))
    if mode != "scatter":
        monkeypatch.setattr(model, "paged_kv_append", functools.partial(pa.paged_kv_append, interpret=True))
    if mode == "both-kernels":
        monkeypatch.setattr(model, "paged_decode_attention",
                            functools.partial(pa.paged_decode_attention, interpret=True))
    B = len(rows)
    n_pages = B * SLOT_PAGES
    k_page, v_page = model.page_shapes(cfg, PAGE)
    # pages nobody wrote hold noise, not zeros: a page fetched from the
    # wrong place, or written where it should not be, shows
    k_pool = jax.random.normal(jax.random.PRNGKey(1), (cfg.n_layers, n_pages + 1) + k_page, cfg.dtype)
    v_pool = jax.random.normal(jax.random.PRNGKey(2), (cfg.n_layers, n_pages + 1) + v_page, cfg.dtype)
    tables = np.random.default_rng(1).permutation(n_pages).reshape(B, SLOT_PAGES).astype(np.int32)
    first = []
    for b, (n, _, _) in enumerate(rows):
        tokens = np.zeros((1, 8), np.int32)
        tokens[0, :n] = _prompt(b, n)
        last, k_slab, v_slab = batch_ops.prefill_compute(cfg, params, jnp.asarray(tokens), jnp.asarray([n]))
        for t in range(n):
            k_pool = k_pool.at[:, tables[b, t // PAGE], :, t % PAGE].set(k_slab[:, t])
            v_pool = v_pool.at[:, tables[b, t // PAGE], :, t % PAGE].set(v_slab[:, t])
        first.append(int(jnp.argmax(last[0])))
    zeros = np.zeros(B, np.int32)
    state = batch_ops.make_decode_state(
        first, [n for n, _, _ in rows], np.zeros(B, bool), [budget for _, _, budget in rows],
        zeros - 1, np.zeros(B, np.float32), zeros, np.ones(B, np.float32), jax.random.PRNGKey(0),
    )
    before = (np.asarray(k_pool), np.asarray(v_pool))
    active = jnp.asarray([a for _, a, _ in rows])
    packed, k_pool, v_pool, _ = batch_ops.decode_block_paged(
        cfg, params, k_pool, v_pool, state, jnp.asarray(tables), active, STEPS)
    return np.asarray(packed)[:B, :STEPS], (np.asarray(k_pool), np.asarray(v_pool)), before, tables, first


@pytest.fixture(scope="module")
def scatter_runs():
    return {}


# deepseek_v32 reads its pools by gathers, not by the paged kernel: its one kernel is the append
BLOCK_CASES = [(family, mode) for family in FAMILIES for mode in MODES[1:]
               if (family, mode) != ("deepseek_v32", "both-kernels")]


@pytest.mark.parametrize("rows", list(ROWS), ids=[r.replace(" ", "-") for r in ROWS])
@pytest.mark.parametrize("family, mode", BLOCK_CASES, ids=[f"{f}-{m}" for f, m in BLOCK_CASES])
def test_a_block_through_the_kernels_is_the_block_through_the_scatter(family, rows, mode, scatter_runs, monkeypatch):
    key = (family, rows)
    if key not in scatter_runs:
        scatter_runs[key] = _run_block(family, ROWS[rows], "scatter", monkeypatch)
    want_tokens, want_pools, before, tables, _ = scatter_runs[key]
    tokens, pools, _, _, _ = _run_block(family, ROWS[rows], mode, monkeypatch)
    assert (tokens == want_tokens).all()
    for got, want, was in zip(pools, want_pools, before):
        live, trash = slice(0, got.shape[1] - 1), got.shape[1] - 1
        if mode == "append-kernel":  # the same arithmetic around it: bit for bit
            assert (got[:, live] == want[:, live]).all()
        else:
            np.testing.assert_allclose(got[:, live], want[:, live], rtol=1e-5, atol=1e-5)
        # written: the positions the live rows decoded, and nothing else
        changed = np.argwhere((got[0, live] != was[0, live]).any(axis=(1, 3)))  # (page, slot)
        expect = set()
        for b, (n, active, budget) in enumerate(ROWS[rows]):
            for pos in range(n, n + min(STEPS, budget) if active else n):
                expect.add((int(tables[b, pos // PAGE]), pos % PAGE))
        assert {(int(p), int(s)) for p, s in changed} == expect
        if len(expect) < STEPS * len(ROWS[rows]):  # some row was redirected
            assert (got[:, trash] != was[:, trash]).any()


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_block_s_tokens_are_the_dense_path_s(family, monkeypatch):
    """Row 0's four tokens through both kernels, against the model's
    ``prefill`` over prompt and tokens with a dense cache: greedy at every
    position (for cohere2_moe the last two lie past the window)."""
    model, cfg = FAMILIES[family]
    mode = "both-kernels" if (family, "both-kernels") in BLOCK_CASES else "append-kernel"
    tokens, _, _, _, first = _run_block(family, ROWS["all rows live"], mode, monkeypatch)
    served = [first[0]] + [int(t) for t in tokens[0]]
    ids = np.concatenate([_prompt(0, 6), served[:-1]]).astype(np.int32)
    for n in range(6, len(ids) + 1):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :n] = ids[:n]
        cache = model.KVCache.create(cfg, 1, max_len=16)
        last, _ = model.prefill(cfg, _params(family), jnp.asarray(padded), cache, jnp.asarray([n]))
        assert int(jnp.argmax(last[0])) == served[n - 6]


# ------------------------------------------------ compiled for the chip
@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip (no chip attached): the TPU compiler is
    installed here and compiles for it. Only inside this fixture, never at
    import: a process keeps libtpu once it has loaded it."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# tile-legal and narrow: Dh 128, page 16, two layers (cohere2_moe: a window
# layer and a full one). The pools are the shapes of 32 slots x 1024
# positions (32 MiB each; shapes only, nothing is allocated): a pool of a
# few hundred KiB the compiler prefetches whole into fast memory, with
# copies of its own
CHIP_CONFIGS = {
    "llama": llama.LlamaConfig(
        vocab_size=512, d_model=256, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=512,
        max_seq_len=131, dtype=jnp.bfloat16),
    "cohere2_moe": cm.Cohere2MoeConfig.tiny(
        vocab_size=512, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=128,
        d_ff=256, layer_types=(cm.SLIDING, cm.FULL), sliding_window=32, max_seq_len=131,
        dtype=jnp.bfloat16),
    # a latent row of 96 + 32 = one lane tile, an indexer key of 128; two dense and two expert
    # layers (a scan of one layer is no loop: its layer index is a constant, and XLA slices the pool by it)
    "deepseek_v32": ds.DeepseekV32Config.tiny(
        vocab_size=512, d_model=256, n_layers=4, n_dense_layers=2, n_heads=4, q_lora_rank=64,
        kv_lora_rank=96, qk_nope_head_dim=32, qk_rope_head_dim=32, v_head_dim=32, index_n_heads=4,
        index_head_dim=128, index_topk=64, d_ff=256, d_ff_expert=128, n_experts=8, held_experts=8,
        n_group=2, topk_group=1, top_k=2, max_seq_len=131, dtype=jnp.bfloat16),
}
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
# ops that hand a buffer on as it is
_PASSES_ON = {"parameter", "get-tuple-element", "tuple", "while", "bitcast", "custom-call",
              "conditional", "call", "opt-barrier"}


@pytest.mark.parametrize("family", list(CHIP_CONFIGS))
def test_the_compiled_decode_block_leaves_the_pools_to_the_kernels(family, one_chip, no_compile_cache, monkeypatch):
    """``decode_block_paged`` by the chip's compiler: no copy, fusion,
    slice, update or scatter whose result has the shape of a pool or of a
    layer's slice of one — the pools enter, go round both scans and leave
    as the buffers they came in, written by the append's custom call alone
    — and the program's temporaries stay under one pool."""
    from gofr_tpu.ops.backend import COMPILED

    # the model's steps ask the backend which mode to take, and see the CPU
    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    cfg = CHIP_CONFIGS[family]
    model = batch_ops.model_of(cfg)
    B, page, M = 32, 16, 64
    N = B * M

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one_chip)

    i32, f32 = jnp.int32, jnp.float32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda k: model.init_params(cfg, k), key))
    pool_shapes = [(cfg.n_layers, N + 1) + shape for shape in model.page_shapes(cfg, page)]
    k_pool, v_pool = (jax.ShapeDtypeStruct(shape, cfg.dtype, sharding=one_chip) for shape in pool_shapes)
    state = batch_ops.DecodeState(vec(i32), vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(f32),
                                  vec(i32), vec(f32), on_chip(key), vec(i32))
    # conftest.py asks for float32 products everywhere; the served program
    # does not, and Mosaic takes bf16 operands at the default precision only
    with jax.default_matmul_precision("default"):
        compiled = batch_ops.decode_block_paged.lower(
            cfg, params, k_pool, v_pool, state, vec(i32, B, M), vec(jnp.bool_), STEPS).compile()

    text = compiled.as_text()
    # the append and the attention kernel a layer body (deepseek_v32: two bodies, the append alone in each)
    assert text.count("tpu_custom_call") >= 2
    pool_like = set()
    for pool_shape in pool_shapes:
        dims = ",".join(str(d) for d in pool_shape)
        pool_like |= {f"bf16[{dims}]", f"bf16[{dims.split(',', 1)[1]}]", f"bf16[1,{dims.split(',', 1)[1]}]"}
    dims = ",".join(str(d) for d in pool_shapes[0])
    made = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) not in _PASSES_ON and any(shape in m.group(2) for shape in pool_like):
            made.append(f"{m.group(1)} = {m.group(3)}")
    assert not made, f"XLA ops that make, slice or update a pool: {made}"
    appends = [line for line in text.splitlines() if "custom-call(" in line and f"bf16[{dims}]" in line.split("custom-call(")[0]]
    assert appends and all("paged_kv_append" in line for line in appends)
    pool_bytes = 2 * int(np.prod(pool_shapes[0]))
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
    # the chip's compiler left the sampler's sorts where the program put
    # them: inside a branch of the conditional that greedy rows do not
    # take, not hoisted into the step (PERF.md §6, PR 32)
    comps, entry = hlo_text.computations(text)
    sorts = hlo_text.holds(comps, "sort", f"f32[{B},{cfg.vocab_size}]")  # not the router's top-k
    assert sorts and not sorts & hlo_text.reached_outside_a_branch(comps, entry)


def test_the_compiled_ragged_step_writes_a_chunk_into_the_pools_in_place(one_chip, no_compile_cache, monkeypatch):
    """``ragged_step_paged`` of ``deepseek_v32`` by the chip's compiler —
    the program ``deepseekv32.long`` runs in every iteration. Its chunk
    writes T rows a pool by an XLA scatter beside the decode steps'
    Mosaic append (the append's kernel takes one token a row): the
    hazard of PR 30, where an XLA write of a pool gave it another layout
    than Mosaic's and a copy around every call. Held here: the only ops
    whose result is a pool are those two scatters a stack of layers, each
    over the pool it was handed (a bitcast of it), and nothing copies,
    transposes, slices or pads one."""
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    cfg = CHIP_CONFIGS["deepseek_v32"]
    model = batch_ops.model_of(cfg)
    B, page, M, C = 32, 16, 64, 32
    N = B * M

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one_chip)

    i32, f32, flag = jnp.int32, jnp.float32, jnp.bool_
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda k: model.init_params(cfg, k), key))
    pool_shapes = [(cfg.n_layers, N + 1) + shape for shape in model.page_shapes(cfg, page)]
    k_pool, v_pool = (jax.ShapeDtypeStruct(shape, cfg.dtype, sharding=one_chip) for shape in pool_shapes)
    state = batch_ops.DecodeState(vec(i32), vec(i32), vec(flag), vec(i32), vec(i32), vec(f32),
                                  vec(i32), vec(f32), on_chip(key), vec(i32))
    with jax.default_matmul_precision("default"):
        compiled = batch_ops.ragged_step_paged.lower(
            cfg, params, k_pool, v_pool, state, vec(i32, B, M), vec(i32, B, C), vec(i32), vec(flag), vec(i32),
            vec(flag), vec(i32), vec(i32), vec(i32), vec(f32), vec(i32), vec(f32), vec(i32), on_chip(key),
            vec(flag), STEPS).compile()

    text = compiled.as_text()
    sizes = {int(np.prod(shape)) for shape in pool_shapes}
    made = collections.Counter()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        shape = re.match(r"bf16\[([\d,]+)\]", m.group(2)) if m else None
        if shape and m.group(3) not in _PASSES_ON and int(np.prod([int(d) for d in shape.group(1).split(",")])) in sizes:
            made[m.group(3) if "/scatter" in line else f"{m.group(1)} = {m.group(3)}"] += 1
    # the scatter itself and the fusion that wraps it, for each pool, in the dense stack's loop and in the expert stack's
    assert set(made) <= {"scatter", "fusion"} and made["scatter"] == 4, made
    appends = [line for line in text.splitlines() if "custom-call(" in line and "paged_kv_append" in line]
    assert len(appends) == 2  # the decode steps' append, once a stack of layers


# ------------------- several pools and a state a slot (phi4flash, PR 35)
# tile-legal and narrow: a KV pair of 2 x 64 = one lane tile, a state of
# 16 x 512; two window layers, the full layer, one cross pair. The window
# is the published 512 and a slot 4,096 positions, so that the pools (8 and
# 32 MiB; shapes only) are too large to be prefetched whole into fast memory
PHI_CHIP = phi.Phi4FlashConfig(
    vocab_size=512, d_model=256, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=64, d_ff=512,
    sliding_window=512, d_state=16, d_conv=4, expand=2, dt_rank=16, max_seq_len=4096, dtype=jnp.bfloat16)


def _phi_arguments(one_chip, B, M, page=16):
    """Shapes, on the described chip, of what the two paged programs take
    first for ``PHI_CHIP``: params, the pools with the state, the decode
    state, the tables — as ``PagedKVCache`` builds them from ``cache_spec``."""
    cfg = PHI_CHIP

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one_chip)

    i32, f32 = jnp.int32, jnp.float32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda k: phi.quantize_params(phi.init_params(cfg, k)), key))
    pools, state = phi.cache_spec(cfg, page)
    ring = -(-cfg.sliding_window // page) + 1
    k_pool, v_pool = {}, {}
    for name, layers, k_page, v_page, window in pools:
        lead = (layers, (B * ring if window else B * M) + 1)
        k_pool[name], v_pool[name] = vec(cfg.dtype, *lead, *k_page), vec(cfg.dtype, *lead, *v_page)
    k_pool["state"] = {name: vec(dtype, layers, B, *shape) for name, (layers, shape, dtype) in state.items()}
    dec = batch_ops.DecodeState(vec(i32), vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(f32),
                                vec(i32), vec(f32), on_chip(key), vec(i32))
    return cfg, params, k_pool, v_pool, dec, {"window": vec(i32, B, M), "full": vec(i32, B, M)}, vec, on_chip(key)


def _pool_like(k_pool):
    shapes = set()
    for name in ("window", "full"):
        dims = ",".join(str(d) for d in k_pool[name].shape)
        shapes |= {f"bf16[{dims}]", f"bf16[{dims.split(',', 1)[1]}]", f"bf16[1,{dims.split(',', 1)[1]}]"}
    return shapes


def test_phi4flash_compiled_decode_block_leaves_its_pools_to_the_kernels(one_chip, no_compile_cache, monkeypatch):
    """``decode_block_paged`` of ``phi4flash`` by the chip's compiler: two
    pool pairs (a window ring of two layers, ONE full layer read by its own
    layer and by the cross-attention layer) and a state a slot. No copy,
    fusion, slice, update or transpose has the shape of a pool or of a
    layer's slice of one; the pools are written by the append's custom call
    alone; the state arrays are XLA's, updated in place (their only writers
    are dynamic-update-slices over the buffer they were handed) and the
    float32 state is never rounded."""
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    B, M = 32, 256
    cfg, params, k_pool, v_pool, dec, tables, vec, _ = _phi_arguments(one_chip, B, M)
    with jax.default_matmul_precision("default"):
        compiled = batch_ops.decode_block_paged.lower(
            cfg, params, k_pool, v_pool, dec, tables, vec(jnp.bool_), STEPS).compile()
    text = compiled.as_text()
    # the window layers' append and attention, the full layer's, the cross layers' attention
    assert text.count("tpu_custom_call") >= 5
    made = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) not in _PASSES_ON and any(shape in m.group(2) for shape in _pool_like(k_pool)):
            made.append(f"{m.group(1)} = {m.group(3)}")
    assert not made, f"XLA ops that make, slice or update a pool: {made}"
    appends = [line for line in text.splitlines() if "custom-call(" in line and "paged_kv_append" in line]
    assert len(appends) == 2  # the window layers' (in their loop) and the full layer's
    state_shape = "f32[{}]".format(",".join(str(d) for d in k_pool["state"]["ssm"].shape))
    writers = set()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) not in _PASSES_ON and m.group(2).startswith(state_shape):
            writers.add(m.group(3) if m.group(3) != "fusion" else "fusion:" + ("dynamic-update-slice" if "dynamic-update-slice" in m.group(1) or "dynamic_update_slice" in m.group(1) else m.group(1)))
    assert writers and all("dynamic-update-slice" in w for w in writers), writers
    assert "bf16[{}]".format(state_shape[4:-1]) not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * int(np.prod(k_pool["full"].shape))


def test_phi4flash_compiled_ragged_step_writes_a_chunk_into_its_pools_in_place(one_chip, no_compile_cache, monkeypatch):
    """``ragged_step_paged`` of ``phi4flash`` by the chip's compiler. A
    chunk's K and V go into pools of SEVERAL heads a page, where an indexed
    write makes XLA swap the head and page axes and copy the pool around
    the write (4.2 GB of temporaries at the cell's size before
    ``_write_rows``, 105 MB after; my compile, PR 35). Held here: the
    only ops whose result has a pool's size are scatters of rows over the
    pool they were handed, one a pool a row's chunk."""
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    B, M, C = 32, 256, 32
    cfg, params, k_pool, v_pool, dec, tables, vec, key = _phi_arguments(one_chip, B, M)
    i32, f32, flag = jnp.int32, jnp.float32, jnp.bool_
    with jax.default_matmul_precision("default"):
        compiled = batch_ops.ragged_step_paged.lower(
            cfg, params, k_pool, v_pool, dec, tables, vec(i32, B, C), vec(i32), vec(flag), vec(i32),
            vec(flag), vec(i32), vec(i32), vec(i32), vec(f32), vec(i32), vec(f32), vec(i32), key,
            vec(flag), STEPS).compile()
    text = compiled.as_text()
    sizes = {int(np.prod(k_pool[name].shape)) for name in ("window", "full")}
    made = collections.Counter()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        shape = re.match(r"bf16\[([\d,]+)\]", m.group(2)) if m else None
        if shape and m.group(3) not in _PASSES_ON and int(np.prod([int(d) for d in shape.group(1).split(",")])) in sizes:
            made[m.group(3) if "/scatter" in line else f"{m.group(1)} = {m.group(3)}"] += 1
    assert set(made) <= {"scatter", "fusion"} and made["scatter"] == 4, made  # K and V of the window pool and of the full pool
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * int(np.prod(k_pool["full"].shape))


# ------------------------------------- the expert product a program holds (PR 34)
def _program_arguments(cfg, one_chip, B, M, page=16, init_params=None):
    """Shapes, on the described chip, of what the two paged programs take
    first: params (the model's ``init_params``, or the one given), the two
    pools, the decode state, the block tables."""
    model = batch_ops.model_of(cfg)
    N = B * M

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one_chip)

    i32, f32, flag = jnp.int32, jnp.float32, jnp.bool_
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    on_chip = functools.partial(jax.tree.map, lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip))
    params = on_chip(jax.eval_shape(lambda k: (init_params or model.init_params)(cfg, k), key))
    pools = [jax.ShapeDtypeStruct((cfg.n_layers, N + 1) + shape, cfg.dtype, sharding=one_chip)
             for shape in model.page_shapes(cfg, page)]
    state = batch_ops.DecodeState(vec(i32), vec(i32), vec(flag), vec(i32), vec(i32), vec(f32),
                                  vec(i32), vec(f32), on_chip(key), vec(i32))
    return vec, on_chip(key), (params, *pools, state, vec(i32, B, M))


def test_wide_s_decode_block_lowers_to_the_sum_over_every_row_and_nothing_else(one_chip, monkeypatch):
    """``cohere2_moe``'s ``decode_block_paged`` at the shapes that decide
    (``commandaplus.wide``: 64 rows, 8 of 128 experts a row, 16 held),
    lowered for a described v5e, is line for line the text of the program
    whose expert layer is the every-row sum — every held expert over every
    row, no mask — with the constant count of its held experts beside it:
    at four rows an expert under the ridge ``held_experts`` adds nothing
    else to it (PERF.md §6, PR 34). With int8 stacks, as served, that sum
    is two ``expert_rows`` calls a layer on the chip, the routed experts'
    and the shared ones' (PR 40)."""
    from gofr_tpu.ops import expert_rows, moe
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    monkeypatch.setattr(expert_rows, "kernel_mode", lambda interpret=None: COMPILED)
    cfg = cm.Cohere2MoeConfig.tiny(
        vocab_size=512, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=128, d_ff=256,
        n_experts=128, top_k=8, held_experts=16, n_shared=4, layer_types=(cm.SLIDING, cm.FULL),
        sliding_window=32, max_seq_len=131, dtype=jnp.bfloat16)

    def lowered(B):
        jax.clear_caches()  # the trace is cached by the arguments, and the function under it changes
        vec, _, first = _program_arguments(cfg, one_chip, B, 64,
                                           init_params=lambda c, k: cm.quantize_params(cm.init_params(c, k)))
        with jax.default_matmul_precision("default"):
            text = batch_ops.decode_block_paged.lower(cfg, *first, vec(jnp.bool_), STEPS).as_text()
        # a Mosaic call's payload is its kernel's serialized body, which carries where it was traced from
        return re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', "backend_config = <kernel>", text).splitlines()

    def parents(h, gates, experts, shared, first, mm=jnp.matmul, layer=None, **told):
        g = jax.lax.dynamic_slice_in_dim(gates, first, 16, axis=1)
        return moe._over_every_row(h, g, experts, shared, mm, layer), g, jnp.int32(16)

    ours = lowered(64)
    monkeypatch.setattr(cm, "held_experts", parents)
    theirs = lowered(64)
    jax.clear_caches()
    assert len(ours) > 500 and ours == theirs
    assert sum('kernel_name = "expert_rows"' in line for line in ours) == 2  # one layer body: routed, shared


def test_the_compiled_ragged_step_multiplies_a_held_expert_by_tiles_of_its_own_rows(one_chip, no_compile_cache, monkeypatch):
    """``ragged_step_paged`` of ``deepseek_v32`` by the chip's compiler at
    the shapes that decide in ``deepseekv32.long`` (32 rows a decode step
    and a chunk of 256, 8 of 256 experts a row, 32 held; narrow widths):
    no product with a held expert's matrix takes a chunk's block of 256
    rows — every one takes a tile, inside the ``while`` over the tiles
    that hold a row — and the shared expert's product over the whole
    chunk is there beside it."""
    from gofr_tpu.ops import expert_rows, moe
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    monkeypatch.setattr(expert_rows, "kernel_mode", lambda interpret=None: COMPILED)
    D, F, B, C, held = 384, 640, 32, 256, 32  # widths no other matrix of the model has
    cfg = ds.DeepseekV32Config.tiny(
        vocab_size=512, d_model=D, n_layers=4, n_dense_layers=2, n_heads=4, q_lora_rank=64,
        kv_lora_rank=96, qk_nope_head_dim=32, qk_rope_head_dim=32, v_head_dim=32, index_n_heads=4,
        index_head_dim=128, index_topk=64, d_ff=256, d_ff_expert=F, n_experts=256, held_experts=held,
        n_group=8, topk_group=4, top_k=8, max_seq_len=1024, dtype=jnp.bfloat16)
    vec, key, first = _program_arguments(cfg, one_chip, B, 64)
    i32, f32, flag = jnp.int32, jnp.float32, jnp.bool_
    jax.clear_caches()  # traced afresh with the every-row kernel on
    with jax.default_matmul_precision("default"):
        text = batch_ops.ragged_step_paged.lower(
            cfg, *first, vec(i32, B, C), vec(i32), vec(flag), vec(i32), vec(flag), vec(i32), vec(i32), vec(i32),
            vec(f32), vec(i32), vec(f32), vec(i32), key, vec(flag), STEPS).compile().as_text()

    def products(experts):
        """The fused computations that take a matrix of a stack of so many
        experts a layer AND a block of activations, as (name, the rows of its blocks)."""
        found = {}
        for line in text.splitlines():
            m = re.match(r"%(fused_computation[\w.\-]*) \((.*)\) -> ", line)
            if not m:
                continue
            shapes = re.findall(r"\w+\[([\d,]*)\]", m.group(2))
            dims = [tuple(int(d) for d in shape.split(",") if d) for shape in shapes]
            stacks = [d for d in dims if d[-2:] in ((D, F), (F, D)) and int(np.prod(d[:-2])) in (experts, 2 * experts)]
            blocks = {d[-2] for d in dims if len(d) >= 2 and d[-1] in (D, F) and d not in stacks and d[-2:] not in ((D, F), (F, D))}
            if stacks and blocks:
                found[m.group(1)] = blocks
        return found

    routed, shared = products(held), products(1)
    assert routed and all(rows == {moe.TILE_ROWS} for rows in routed.values()), routed  # never the chunk's 256
    assert {C} in shared.values()  # the shared expert is one product over every row of the chunk
    comps, entry = hlo_text.computations(text)
    calls = {name for name, lines in comps.items() if any(f"calls=%{r}" in line or f"calls={r}" in line
                                                           for line in lines for r in routed)}
    assert calls and entry not in calls  # inside the loop over the tiles that hold a row, not at the top
    # the grouped branch everywhere: neither the chunk nor the decode steps call the every-row kernel
    # (PR 40), in this program or in the decode block
    with jax.default_matmul_precision("default"):
        block = batch_ops.decode_block_paged.lower(cfg, *first, vec(flag), STEPS).as_text()
    jax.clear_caches()
    assert "expert_rows" not in text and "expert_rows" not in block and "tpu_custom_call" in block


# ------------------------ the q/k/v products stay two-dimensional (PR 38)
def _folded_products(h, lp):
    """The parent's expression: the three products with nothing between
    them and the caller's reshape to heads, which XLA folds into them."""
    return llama._mm(h, lp["wq"]), llama._mm(h, lp["wk"]), llama._mm(h, lp["wv"])


def _logits_of_three_programs(cfg, params):
    """``forward`` over two prompts, ``prefill`` of both (6 and 8 tokens)
    into a dense cache, and one ``decode_step_paged`` over pools that hold
    what the prefill wrote: the three programs' logits."""
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 3, cfg.vocab_size)
    lens = np.asarray([6, 8], np.int32)
    whole = llama.forward(cfg, params, tokens)
    last, cache = llama.prefill(cfg, params, tokens, llama.KVCache.create(cfg, B, max_len=S), jnp.asarray(lens))
    k_page, v_page = llama.page_shapes(cfg, PAGE)
    n_pages = B * SLOT_PAGES
    k_pool = jax.random.normal(jax.random.PRNGKey(2), (cfg.n_layers, n_pages + 1) + k_page, cfg.dtype)
    v_pool = jax.random.normal(jax.random.PRNGKey(3), (cfg.n_layers, n_pages + 1) + v_page, cfg.dtype)
    tables = np.random.default_rng(1).permutation(n_pages).reshape(B, SLOT_PAGES).astype(np.int32)
    for b in range(B):
        for t in range(int(lens[b])):
            k_pool = k_pool.at[:, tables[b, t // PAGE], :, t % PAGE].set(cache.k[:, b, t])
            v_pool = v_pool.at[:, tables[b, t // PAGE], :, t % PAGE].set(cache.v[:, b, t])
    step, _, _ = llama.decode_step_paged(
        cfg, params, jnp.argmax(last, axis=-1).astype(jnp.int32), k_pool, v_pool, jnp.asarray(tables),
        jnp.asarray(lens + 1), jnp.ones(B, bool))
    return {"forward": whole, "prefill": last, "decode_step_paged": step}


@pytest.fixture(scope="module")
def qkv_runs():
    return {}


@pytest.mark.parametrize("program", ["forward", "prefill", "decode_step_paged"])
@pytest.mark.parametrize("weights", ["bf16", "int8"])
def test_the_barrier_after_the_q_k_v_products_changes_no_value(weights, program, qkv_runs, monkeypatch):
    """``_qkv_products`` against the expression it replaced
    (``_mm(...).reshape`` with no barrier between), for weights in bf16 and
    in int8: the same logits from all three programs that reach it."""
    cfg = llama.LlamaConfig.tiny(vocab_size=300, dtype=jnp.bfloat16)
    if weights not in qkv_runs:
        params = llama.init_params(cfg, jax.random.PRNGKey(7), quantize=weights == "int8")
        ours = _logits_of_three_programs(cfg, params)
        # a config of its own (a static argument of every jitted program), so no trace of ours is reused
        monkeypatch.setattr(llama, "_qkv_products", _folded_products)
        theirs = _logits_of_three_programs(dataclasses.replace(cfg, max_seq_len=cfg.max_seq_len + 1), params)
        qkv_runs[weights] = ours, theirs
    ours, theirs = (np.asarray(run[program], np.float32) for run in qkv_runs[weights])
    assert np.isfinite(ours).all() and ours.std() > 0
    np.testing.assert_allclose(ours, theirs, rtol=2 ** -7, atol=2 ** -7 * float(np.abs(theirs).max()))


def test_a_gradient_passes_the_barrier_after_the_q_k_v_products(monkeypatch):
    """``jax.grad`` of a loss through ``forward``: every leaf finite, the
    three projections' among them and not zero, and the gradient the
    parent's expression gives."""
    cfg = llama.LlamaConfig.tiny(vocab_size=300, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 3, cfg.vocab_size)

    def grads(cfg):
        def loss(p):
            logp = jax.nn.log_softmax(llama.forward(cfg, p, tokens[:, :-1]))
            return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
        return jax.grad(loss)(params)

    ours = grads(cfg)
    monkeypatch.setattr(llama, "_qkv_products", _folded_products)
    theirs = grads(dataclasses.replace(cfg, max_seq_len=cfg.max_seq_len + 1))
    for name in ("wq", "wk", "wv"):
        assert float(jnp.abs(ours["layers"][name]).max()) > 0
    for got, want in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6)


# tile-legal and narrow, int8 as the cells serve it: heads of 128, 8 query
# heads over 2 KV heads (mistral7b.chat's grouping) and over 8
# (deepseek7b.gen's), eight layers so that the smallest stack (K of the
# grouped layout, 2 MiB) is more than the sampler and the logits take.
# The cells' own shapes beside them, 20 s a compile: (layers, hidden,
# query heads, KV heads, ff, vocabulary, rows, pages a row)
QKV_SHAPES = {
    "grouped-8-over-2": (8, 1024, 8, 2, 2048, 512, 32, 16),
    "one-kv-head-a-query-head": (8, 1024, 8, 8, 2048, 512, 32, 16),
    "mistral7b.chat": (32, 4096, 32, 8, 14336, 32768, 32, 48),
    "deepseek7b.gen": (30, 4096, 32, 32, 11008, 102400, 6, 64),
}
QKV_CASES = [pytest.param(name, marks=pytest.mark.slow) if "." in name else name for name in QKV_SHAPES]
# what yields an int8 array and moves no byte; a dynamic-slice is judged by the fusion that holds it
_NO_BYTES = _PASSES_ON - {"custom-call"} | {"dynamic-slice"}


def _materialized_weights(text: str, sizes: set[int]) -> list[str]:
    """The instructions of a compiled program that write an int8 array of
    one of ``sizes`` elements: everything but a fusion that only slices its
    stack by the layer's index and bitcasts the slice, INSIDE the fusion
    whose ``convolution`` takes it — the form in which a weight goes from
    HBM into its product once."""
    comps, _ = hlo_text.computations(text)
    slices_only = {name for name, lines in comps.items()
                   if {m.group(3) for m in map(_INSTRUCTION.match, lines) if m}
                   <= {"parameter", "constant", "dynamic-slice", "bitcast"}}
    made = []
    for lines in comps.values():
        for line in lines:
            m = _INSTRUCTION.match(line)
            shape = re.match(r"s8\[([\d,]+)\]", m.group(2)) if m else None
            if not shape or m.group(3) in _NO_BYTES or int(np.prod([int(d) for d in shape.group(1).split(",")])) not in sizes:
                continue
            name, _, op = m.groups()
            callee = re.search(r"calls=%?([\w.\-]+)", line)
            feeds_a_product = any("convolution(" in other and f"%{name}" in other.split("convolution(")[1]
                                  for other in lines)
            if not (op == "fusion" and callee and callee.group(1) in slices_only and feeds_a_product):
                made.append(f"{name} = {shape.group(0)} {op}")
    return made


@pytest.mark.parametrize("program", ["decode_block_paged", "prefill_compute"])
@pytest.mark.parametrize("shapes", QKV_CASES)
def test_the_compiled_llama_step_streams_q_k_v_weights_into_their_products_once(shapes, program, one_chip, no_compile_cache, monkeypatch):
    """``decode_block_paged`` and ``prefill_compute`` of a Llama model with
    int8 weights by the chip's compiler: no ``copy`` and no fusion of its
    own yields an int8 array the size of a ``wq``/``wk``/``wv`` stack or of
    one layer's slice of one. With the reshape to heads folded into the
    product (the parent) XLA wants each stack with the contraction axis
    minor: it copies the whole stack to ``{1,2,0}`` once a program
    (``copy.36``-``.38`` here, ``copy.30`` / ``copy.44`` in the cells'
    traces), and in every layer a ``constant_dynamic-slice_fusion`` first
    writes the slice out and the product then reads it — two passes where
    ``wo`` takes one (PERF.md §6, PR 38). ``wo`` has ``wq``'s size, so its
    path is held with them."""
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    L, D, H, Hkv, F, V, B, M = QKV_SHAPES[shapes]
    cfg = llama.LlamaConfig(vocab_size=V, d_model=D, n_layers=L, n_heads=H, n_kv_heads=Hkv, d_ff=F,
                            max_seq_len=16 * M, dtype=jnp.bfloat16)
    vec, _, first = _program_arguments(cfg, one_chip, B, M, init_params=functools.partial(llama.init_params, quantize=True))
    assert first[0]["layers"]["wq"]["q"].dtype == jnp.int8
    with jax.default_matmul_precision("default"):
        if program == "decode_block_paged":
            compiled = batch_ops.decode_block_paged.lower(cfg, *first, vec(jnp.bool_), STEPS).compile()
        else:
            compiled = batch_ops.prefill_compute.lower(cfg, first[0], vec(jnp.int32, 1, 256), vec(jnp.int32, 1)).compile()

    text = compiled.as_text()
    slices = {D * H * cfg.head_dim, D * Hkv * cfg.head_dim}
    made = _materialized_weights(text, slices | {L * n for n in slices})
    assert not made, f"XLA ops that write a q/k/v weight out again: {made}"
    # and the products are there, each taking its weight as [D, N]: q, k, v (and wo, of wq's shape)
    straight = [line for line in text.splitlines() if "convolution(" in line and "dim_labels=bf_io->bf" in line]
    assert len(straight) >= 7  # the seven matrices of a layer
    assert compiled.memory_analysis().temp_size_in_bytes < L * min(slices)


# --------------------- conv tails a slot beside one pool (lfm2_moe, PR 39)
# tile-legal and narrow: two KV heads of 64 = one cached head of 128, the
# published structure (two dense conv layers, a period of four, a last of
# three), 8 experts of 128; slots of 4,096 positions, so that the pool is
# too large to be prefetched whole into fast memory
LFM2_CHIP = lm.Lfm2MoeConfig(
    vocab_size=512, d_model=256, n_layers=9, n_heads=4, n_kv_heads=2, head_dim=64, d_ff=512, d_ff_expert=128,
    n_experts=8, top_k=4, layer_types=lm.Lfm2MoeConfig.tiny().layer_types, max_seq_len=4096, dtype=jnp.bfloat16)


def _lfm2_arguments(one_chip, B, M, page=16):
    """Shapes, on the described chip, of what the two paged programs take
    first for ``LFM2_CHIP``: params (int8), the pool with the conv tails,
    the decode state, the table — as ``PagedKVCache`` builds them from
    ``cache_spec``."""
    cfg = LFM2_CHIP

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one_chip)

    i32, f32 = jnp.int32, jnp.float32
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(lambda k: lm.quantize_params(lm.init_params(cfg, k)), key))
    ((name, layers, k_page, v_page, _),), state = lm.cache_spec(cfg, page)
    k_pool = {name: vec(cfg.dtype, layers, B * M + 1, *k_page),
              "state": {key_: vec(dtype, n, B, *shape) for key_, (n, shape, dtype) in state.items()}}
    v_pool = {name: vec(cfg.dtype, layers, B * M + 1, *v_page)}
    dec = batch_ops.DecodeState(vec(i32), vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(f32),
                                vec(i32), vec(f32), on_chip(key), vec(i32))
    return cfg, params, k_pool, v_pool, dec, {name: vec(i32, B, M)}, vec, on_chip(key)


def test_lfm2_compiled_decode_block_leaves_its_pool_to_the_kernels(one_chip, no_compile_cache, monkeypatch):
    """``decode_block_paged`` of ``lfm2_moe`` by the chip's compiler: the
    pool is written by the append's custom call alone (one a block of
    layers: the attention layer of each block), no XLA op makes, slices or
    updates it; the conv tails are XLA's, updated in place (their only
    writers are dynamic-update-slices) and never rounded. (At these widths
    the compiler prefetches whole weight stacks into fast memory; at the
    cell's, ``decode_block_paged`` and ``ragged_step_paged`` write no
    weight out before its product — ``_materialized_weights`` finds none:
    my compile, PR 39.)"""
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    B, M = 32, 256
    cfg, params, k_pool, v_pool, dec, tables, vec, _ = _lfm2_arguments(one_chip, B, M)
    with jax.default_matmul_precision("default"):
        compiled = batch_ops.decode_block_paged.lower(
            cfg, params, k_pool, v_pool, dec, tables, vec(jnp.bool_), STEPS).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    dims = ",".join(str(d) for d in k_pool["full"].shape)
    pool_like = {f"bf16[{dims}]", f"bf16[{dims.split(',', 1)[1]}]", f"bf16[1,{dims.split(',', 1)[1]}]"}
    made = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) not in _PASSES_ON and any(shape in m.group(2) for shape in pool_like):
            made.append(f"{m.group(1)} = {m.group(3)}")
    assert not made, f"XLA ops that make, slice or update the pool: {made}"
    appends = [line for line in text.splitlines() if "custom-call(" in line and "paged_kv_append" in line]
    assert len(appends) == 1  # the attention layer of the block loop
    tails = "f32[{}]".format(",".join(str(d) for d in k_pool["state"]["conv"].shape))
    comps, _ = hlo_text.computations(text)
    roots = {name: next((l for l in lines if l.lstrip().startswith("ROOT")), "") for name, lines in comps.items()}
    writers = collections.Counter()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) not in _PASSES_ON and m.group(2).startswith(tails):
            callee = re.search(r"calls=%?([\w.\-]+)", line)
            fused_update = m.group(3) == "fusion" and callee and " dynamic-update-slice(" in roots.get(callee.group(1), "")
            writers["dynamic-update-slice" if fused_update else m.group(3)] += 1
    # a conv layer's tail written in place; the layout of the whole array changed on entry and back on exit
    # (19 MB twice a block at the cell's size, about 0.1 ms of a 4-step block)
    assert writers["dynamic-update-slice"] >= 2 and set(writers) <= {"dynamic-update-slice", "copy"}, writers
    assert writers["copy"] <= 2, writers
    assert "bf16[{}]".format(tails[4:-1]) not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * int(np.prod(k_pool["full"].shape))


def test_lfm2_compiled_decode_block_reads_the_experts_through_the_kernel_alone(one_chip, no_compile_cache, monkeypatch):
    """``decode_block_paged`` of ``lfm2_moe`` by the chip's compiler at
    rows under the ridge with two or more an expert (32 rows, 4 of 8
    experts a row): each expert layer is one ``expert_rows`` call over the
    stacks whole (two sites: the first expert layer of a block and the
    loop over the rest), and no other op carries a stack's shape or its
    scales' — the calls are all ``moe.experts_roofline.tools`` times, and
    no slice of a stack is left beside them (PR 40)."""
    from gofr_tpu.ops import expert_rows
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    monkeypatch.setattr(expert_rows, "kernel_mode", lambda interpret=None: COMPILED)
    B, M = 32, 256
    cfg, params, k_pool, v_pool, dec, tables, vec, _ = _lfm2_arguments(one_chip, B, M)
    assert moe_path_of(cfg, B, params) == "kernel"
    jax.clear_caches()  # the trace is cached by the arguments, and the branch under it changes
    with jax.default_matmul_precision("default"):
        text = batch_ops.decode_block_paged.lower(
            cfg, params, k_pool, v_pool, dec, tables, vec(jnp.bool_), STEPS).compile().as_text()
    jax.clear_caches()
    Lm, E, D, F = cfg.n_layers - cfg.n_dense_layers, cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    marks = [f"s8[{a},{b},{c}]" for a in (f"{Lm},{E}", f"{Lm * E}") for b, c in ((D, F), (F, D))]
    marks += [f"f32[{Lm * E},{F}]", f"f32[{Lm * E},{D}]"]
    carriers = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) not in _PASSES_ON - {"custom-call"} and any(mark in line for mark in marks):
            carriers.append((m.group(1).split(".")[0], m.group(3)))
    assert carriers == [("expert_rows", "custom-call")] * 2, carriers


def moe_path_of(cfg, rows, params):
    from gofr_tpu.ops import moe

    return moe.path(rows, cfg.n_experts, cfg.top_k, params["moe"]["experts"])


def test_lfm2_compiled_ragged_step_writes_a_chunk_into_its_pool_in_place(one_chip, no_compile_cache, monkeypatch):
    """``ragged_step_paged`` of ``lfm2_moe`` by the chip's compiler: a
    chunk's K and V go into the pool through ``phi4flash._write_rows``'s
    row scatter — the only ops whose result has the pool's size are those
    scatters (K and V, in the attention layer of the chunk's block loop)
    and the fusions that wrap them."""
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    B, M, C = 32, 256, 32
    cfg, params, k_pool, v_pool, dec, tables, vec, key = _lfm2_arguments(one_chip, B, M)
    i32, f32, flag = jnp.int32, jnp.float32, jnp.bool_
    with jax.default_matmul_precision("default"):
        compiled = batch_ops.ragged_step_paged.lower(
            cfg, params, k_pool, v_pool, dec, tables, vec(i32, B, C), vec(i32), vec(flag), vec(i32),
            vec(flag), vec(i32), vec(i32), vec(i32), vec(f32), vec(i32), vec(f32), vec(i32), key,
            vec(flag), STEPS).compile()
    text = compiled.as_text()
    size = int(np.prod(k_pool["full"].shape))
    made = collections.Counter()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        shape = re.match(r"bf16\[([\d,]+)\]", m.group(2)) if m else None
        if shape and m.group(3) not in _PASSES_ON and int(np.prod([int(d) for d in shape.group(1).split(",")])) == size:
            made[m.group(3) if "/scatter" in line else f"{m.group(1)} = {m.group(3)}"] += 1
    assert set(made) <= {"scatter", "fusion"} and made["scatter"] == 2, made
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * size


# ------------------------------------------ JoyAI-LLM-Flash, compiled for the chip
def _joyai_arguments(one_chip, n_layers, B, M, page=16):
    """The cell's configuration at ``n_layers`` (the first dense), its
    weights' and pool's shapes on the described chip: nothing is made."""
    from benchmarks.harness import joyai_flash_family
    from benchmarks.harness.manifest import Manifest

    config = dict(Manifest(joyai_flash_family.__file__.rsplit("/benchmarks/", 1)[0]).config("joyai-llm-flash-ep8-int8"),
                  num_hidden_layers=n_layers)
    cfg = joyai_flash_family.program_config(config)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(lambda: joyai_flash_family.make_weights(config, 0)))
    page_shape, second = ds.page_shapes(cfg, page)
    assert second is None  # one latent pool
    pool = jax.ShapeDtypeStruct((cfg.n_layers, B * M + 1) + page_shape, cfg.dtype, sharding=one_chip)
    i32, f32 = jnp.int32, jnp.float32
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    dec = batch_ops.DecodeState(vec(i32), vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(f32),
                                vec(i32), vec(f32), key, vec(i32))
    return cfg, params, pool, dec, vec


def test_joyai_latent_kernel_compiles_at_the_cell_s_widths(one_chip, no_compile_cache, monkeypatch):
    """``paged_latent_attention`` by the chip's compiler at the cell's
    shapes: 64 rows of 32 heads over 640-wide latent rows, 512 pages of 16
    a row, the whole pool of 13 layers in HBM — one Mosaic call and no
    other op, nothing copied."""
    from gofr_tpu.ops import latent_attention as la
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(la, "kernel_mode", lambda interpret=None: COMPILED)
    B, M, page = 64, 512, 16
    pool = jax.ShapeDtypeStruct((13, B * M + 1, 1, page, 640), jnp.bfloat16, sharding=one_chip)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one_chip)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(lambda q, p, t, n, layer: la.paged_latent_attention(
            q, p, t, n, layer, scale=192 ** -0.5, kv_lora_rank=512)).lower(
            vec(jnp.bfloat16, B, 32, 640), pool, vec(jnp.int32, B, M), vec(jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "paged_latent_attention" in text
    copies = [line for line in text.splitlines() if " copy(" in line]
    assert not any("bf16[" in line.split(" copy(")[0] for line in copies), copies  # the pool and queries go in as they are
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("rows,heads,kv_heads", [(64, 32, 2), (32, 40, 10)], ids=["solve", "reason-full"])
def test_the_paged_kernel_lands_a_full_block_with_one_wait_a_stream(rows, heads, kv_heads, one_chip, no_compile_cache,
                                                                  monkeypatch):
    """``paged_decode_attention`` by the chip's compiler at the shapes of
    ``nemotron3nano.solve`` (2 KV heads of 128, 8 KiB a page) and of
    ``phi4flash.reason``'s full layer (10 KV pairs of 128, 40 KiB), 512
    pages of 16 a row, 8 pages a block: one Mosaic call, whose only loop
    is the one over a row's blocks — the fetch's starts and waits are
    unrolled — and whose waits are a full block's two, each described as
    one stream's whole slot, beside a partial block's one a page a
    stream."""
    from jax._src import tpu_custom_call

    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(pa, "kernel_mode", lambda interpret=None: COMPILED)
    texts = []
    real = tpu_custom_call._lower_mosaic_module_to_asm

    def spy(module, **kw):
        texts.append(module.operation.get_asm(enable_debug_info=False))
        return real(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", spy)
    M, page, Dh, ppb = 512, 16, 128, 8
    pool = jax.ShapeDtypeStruct((6, rows * M + 1, kv_heads, page, Dh), jnp.bfloat16, sharding=one_chip)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (rows,), dtype, sharding=one_chip)

    jax.clear_caches()
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(lambda q, k, v, t, n, layer: pa.paged_decode_attention(q, k, v, t, n, layer=layer)).lower(
            vec(jnp.bfloat16, rows, heads, Dh), pool, pool, vec(jnp.int32, rows, M), vec(jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    jax.clear_caches()
    assert compiled.as_text().count("tpu_custom_call") == 1
    (text,) = texts
    assert text.count("scf.for") == 1 and "scf.while" not in text
    waits = re.findall(r"tpu\.wait_dma2 .* src\(%\w+ : memref<([0-9x]+)xbf16", text)
    whole, page_dma = f"{ppb}x{kv_heads}x{page}x{Dh}", f"{kv_heads}x{page}x{Dh}"
    assert sorted(waits) == sorted([whole] * 2 + [page_dma] * 2 * ppb), waits


# the Mosaic module of the unmasked latent kernel at joyai.think's shapes, its
# text without debug locations, as the tree before the mask was added lowered it
UNMASKED_MOSAIC_SHA256 = "b753c356cdc3b6c57b6a4380a6ad92cda5894d1af8c3f81620bd5d575369f55d"


def test_the_latent_kernel_without_a_mask_lowers_as_it_did_before_the_mask(one_chip, monkeypatch):
    """``paged_latent_attention`` without ``keep`` — JoyAI's decode read,
    and the index-free V3 layer's — lowers to the Mosaic module it lowered
    to before the masked form was added, op for op: the text of the module
    (debug locations stripped, so that lines moved in the source do not
    count) hashes as recorded. With ``keep`` it is another module, which
    takes the mask as one more operand."""
    from jax._src import tpu_custom_call

    from gofr_tpu.ops import latent_attention as la
    from gofr_tpu.ops.backend import COMPILED

    monkeypatch.setattr(la, "kernel_mode", lambda interpret=None: COMPILED)
    texts = []
    real = tpu_custom_call._lower_mosaic_module_to_asm

    def spy(module, **kw):
        texts.append(module.operation.get_asm(enable_debug_info=False))
        return real(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", spy)
    B, M, page = 64, 512, 16
    pool = jax.ShapeDtypeStruct((13, B * M + 1, 1, page, 640), jnp.bfloat16, sharding=one_chip)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one_chip)

    args = (vec(jnp.bfloat16, B, 32, 640), pool, vec(jnp.int32, B, M), vec(jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    jax.clear_caches()
    with jax.default_matmul_precision("default"):
        jax.jit(lambda *a: la.paged_latent_attention(*a, scale=192 ** -0.5, kv_lora_rank=512)).lower(*args)
        jax.jit(lambda *a: la.paged_latent_attention(*a[:5], scale=192 ** -0.5, kv_lora_rank=512, keep=a[5])).lower(
            *args, vec(jnp.bool_, B, M * page))
    jax.clear_caches()
    assert len(texts) == 2 and hashlib.sha256(texts[0].encode()).hexdigest() == UNMASKED_MOSAIC_SHA256
    assert "memref<1x1x8192xi32" in texts[1] and "memref<1x1x8192xi32" not in texts[0]


def test_v32_compiled_decode_block_reads_the_selection_through_the_latent_kernel(one_chip, no_compile_cache, monkeypatch):
    """``decode_block_paged`` of ``deepseekv32.long``'s configuration (7
    layers, 128 heads, a selection of 2,048) at the cell's shapes — 32
    rows of 256 pages of 16 — by the chip's compiler: the slot's 4,096
    positions are at most 4 x 2,048, so each layer body reads its latent
    rows by ONE ``paged_latent_attention`` call under the selection's mask
    and nothing gathers the 65,536 selected rows (``[65536,640]``) or
    holds them (``[32,2048,640]``). The kernel takes the mask as
    ``[32,1,4096]``: no operand of its call has the indexer's
    ``[32,4096]``."""
    from gofr_tpu.ops import expert_rows
    from gofr_tpu.ops import latent_attention as la
    from gofr_tpu.ops.backend import COMPILED
    from benchmarks.harness import deepseek_v32_family
    from benchmarks.harness.manifest import Manifest

    for module in (pa, la, expert_rows):
        monkeypatch.setattr(module, "kernel_mode", lambda interpret=None: COMPILED)
    config = Manifest(deepseek_v32_family.__file__.rsplit("/benchmarks/", 1)[0]).config("deepseek-v3.2-exp-ep8-int8")
    cfg = deepseek_v32_family.program_config(config)
    B, M, page = 32, 256, 16
    assert ds.sparse_read(cfg, M * page) == "masked" and ds.sparse_read(cfg, 2 * M * page + page) == "gather"

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(lambda: deepseek_v32_family.make_weights(config, 0)))
    k_pool, v_pool = (jax.ShapeDtypeStruct((cfg.n_layers, B * M + 1) + shape, cfg.dtype, sharding=one_chip)
                      for shape in ds.page_shapes(cfg, page))
    i32, f32 = jnp.int32, jnp.float32
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    dec = batch_ops.DecodeState(vec(i32), vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(f32),
                                vec(i32), vec(f32), key, vec(i32))
    jax.clear_caches()
    with jax.default_matmul_precision("default"):
        text = batch_ops.decode_block_paged.lower(
            cfg, params, k_pool, v_pool, dec, vec(i32, B, M), vec(jnp.bool_), STEPS).compile().as_text()
    jax.clear_caches()
    calls = [line for line in text.splitlines() if re.match(r"\s*(ROOT )?%paged_latent_attention[\w.\-]* = ", line)]
    # a layer body of each stack: the dense layers' and the expert layers'
    assert len(calls) == 2 and all("tpu_custom_call" in line for line in calls)
    assert "[65536,640]" not in text and "[32,2048,640]" not in text
    shapes = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\])", text, re.M))
    masks = [shapes[name] for line in calls for name in re.findall(r"%[\w.\-]+", line.split("custom-call(", 1)[1].split(")")[0])]
    assert masks.count("s32[32,1,4096]") == 2 and "s32[32,4096]" not in masks


def test_joyai_compiled_decode_block_leaves_its_pool_to_the_kernels(one_chip, no_compile_cache, monkeypatch):
    """``decode_block_paged`` of the cell's configuration at 3 layers (one
    dense, two expert) by the chip's compiler: the latent pool is written
    by the append's custom call and read by the latent kernel's alone, no
    XLA op makes, slices or updates it; and of the weight stacks only
    W_kvb's is copied once a dispatch (both absorptions read it, over two
    different axes: the one layout change a block can hold) — W_qb's
    product takes the stack as stored (the barrier after it)."""
    from gofr_tpu.ops import expert_rows
    from gofr_tpu.ops import latent_attention as la
    from gofr_tpu.ops.backend import COMPILED

    for module in (pa, la, expert_rows):
        monkeypatch.setattr(module, "kernel_mode", lambda interpret=None: COMPILED)
    B, M = 64, 512
    cfg, params, pool, dec, vec = _joyai_arguments(one_chip, 3, B, M)
    with jax.default_matmul_precision("default"):
        compiled = batch_ops.decode_block_paged.lower(
            cfg, params, pool, None, dec, vec(jnp.int32, B, M), vec(jnp.bool_), STEPS).compile()
    text = compiled.as_text()
    calls = collections.Counter(m.group(1) for m in re.finditer(r"%(paged_latent_attention|paged_kv_append|expert_rows)[\w.\-]* = ", text))
    # a layer body of each stack: the append and the attention; the expert layers' two expert_rows calls
    assert calls["paged_latent_attention"] == 2 and calls["paged_kv_append"] == 2 and calls["expert_rows"] == 2
    dims = ",".join(str(d) for d in pool.shape)
    pool_like = {f"bf16[{dims}]", f"bf16[{dims.split(',', 1)[1]}]", f"bf16[1,{dims.split(',', 1)[1]}]"}
    made = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m.group(3) not in _PASSES_ON and any(shape in m.group(2) for shape in pool_like):
            made.append(f"{m.group(1)} = {m.group(3)}")
    assert not made, f"XLA ops that make, slice or update the pool: {made}"
    copied = set(re.findall(r"= (s8\[\d+,\d+,\d+\])\{[^}]*\} copy\(", text))
    assert copied <= {"s8[2,512,8192]", "s8[1,512,8192]", "s8[2,2048,576]", "s8[1,2048,576]"}, copied
    assert "s8[2,1536,6144]" not in " ".join(copied)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
