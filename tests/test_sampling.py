"""``ops/sampling.sample_logits`` does the work its live rows ask for: the
argmax alone for greedy rows, ``categorical`` without a sort when nobody
filters, the whole pipeline otherwise — chosen on the device from the rows
that count. Each path is held to the pipeline as it was before the choice
existed, kept here as a plain reference; and the lowered decode block and
ragged step hold their sorts inside a branch of a ``conditional``."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hlo_text import branches, computations, holds, operand_closure, reached_outside_a_branch

from gofr_tpu.ops.sampling import NEG_INF, SAMPLER_PATHS, sample_logits, sampler_path
from gofr_tpu.serving import batch as batch_ops

SHAPES = [(4, 512), (6, 1024)]


def pipeline_ref(logits, key, temperature, top_k, top_p):
    """The sampler before it chose: argmax, top-k by a sort, top-p by a
    second sort, ``categorical`` over what is left, then the greedy pick."""
    logits = jnp.asarray(logits, jnp.float32)
    B, V = logits.shape
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    greedy_ids = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.where(temperature > 0, temperature, 1.0)[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    k_idx = jnp.clip(jnp.where(top_k > 0, top_k, V) - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    scaled = jnp.where(scaled >= kth, scaled, NEG_INF)
    sorted_scaled = jnp.sort(scaled, axis=-1)[..., ::-1]
    probs_sorted = jax.nn.softmax(sorted_scaled, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_mask = cum - probs_sorted < top_p[:, None]
    threshold = jnp.min(jnp.where(cutoff_mask, sorted_scaled, jnp.inf), axis=-1, keepdims=True)
    scaled = jnp.where(scaled >= threshold, scaled, NEG_INF)
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return np.asarray(jnp.where(temperature <= 0, greedy_ids, sampled))


def _logits(shape):
    return 3.0 * jax.random.normal(jax.random.PRNGKey(shape[1]), shape, jnp.float32)


KEY = jax.random.PRNGKey(11)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_greedy_rows_get_the_argmax_and_nothing_else(shape):
    logits = _logits(shape)
    zeros = jnp.zeros(shape[0], jnp.float32)
    got = sample_logits(logits, KEY, temperature=zeros, top_k=40, top_p=0.9)
    assert int(sampler_path(zeros, jnp.int32(40), jnp.float32(0.9))) == 0
    assert (np.asarray(got) == np.asarray(jnp.argmax(logits, axis=-1))).all()
    assert (np.asarray(got) == pipeline_ref(logits, KEY, 0.0, 40, 0.9)).all()


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_a_mixed_batch_is_the_whole_pipeline_row_by_row(shape):
    """One live row that filters puts the batch on the old path: every
    row's token is the old sampler's for the same key."""
    B = shape[0]
    logits = _logits(shape)
    t = jnp.asarray([0.0, 0.7, 1.0, 1.3, 0.0, 0.9][:B], jnp.float32)
    k = jnp.asarray([0, 0, 5, 40, 3, 0][:B], jnp.int32)
    p = jnp.asarray([1.0, 0.9, 1.0, 0.8, 0.5, 1.0][:B], jnp.float32)
    assert SAMPLER_PATHS[int(sampler_path(t, k, p))] == "filter"
    for key in jax.random.split(KEY, 4):
        got = sample_logits(logits, key, temperature=t, top_k=k, top_p=p)
        assert (np.asarray(got) == pipeline_ref(logits, key, t, k, p)).all()
    # a top-k below zero or a top-p above one mean "off", as zero and one do
    off = sample_logits(logits, KEY, temperature=t, top_k=-k, top_p=2.0 - p)
    assert (np.asarray(off) == np.asarray(sample_logits(logits, KEY, temperature=t))).all()


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_rows_that_do_not_count_do_not_choose_the_path(shape):
    """A slot that never held a request carries temperature 1.0, a retired
    one its last request's top-p: masked off by ``rows`` they leave the
    batch on the greedy path — every row gets the argmax, theirs too, where
    the pipeline would have sampled them."""
    B = shape[0]
    logits = _logits(shape)
    rows = jnp.arange(B) < 2
    t = jnp.where(rows, 0.0, 1.0)
    p = jnp.where(rows, 1.0, 0.9)
    assert int(sampler_path(t, jnp.int32(0), p, rows)) == 0 and int(sampler_path(t, jnp.int32(0), p)) == 2
    logits = logits / 3.0  # flat enough that a sampled row seldom draws its argmax
    got = np.asarray(sample_logits(logits, KEY, temperature=t, top_p=p, rows=rows))
    assert (got == np.asarray(jnp.argmax(logits, axis=-1))).all()
    assert (pipeline_ref(logits, KEY, t, 0, p)[2:] != got[2:]).any()
    # and without the mask the same batch is the pipeline's
    assert (np.asarray(sample_logits(logits, KEY, temperature=t, top_p=p)) == pipeline_ref(logits, KEY, t, 0, p)).all()


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_sampling_without_a_filter_is_categorical_without_a_sort(shape):
    B = shape[0]
    logits = _logits(shape)
    t = jnp.asarray([0.0, 0.7, 1.0, 1.3, 0.0, 0.9][:B], jnp.float32)
    assert SAMPLER_PATHS[int(sampler_path(t, jnp.int32(0), jnp.float32(1.0)))] == "sample"
    got = np.asarray(sample_logits(logits, KEY, temperature=t))
    want = np.asarray(jax.random.categorical(KEY, logits / jnp.where(t > 0, t, 1.0)[:, None], axis=-1))
    greedy = np.asarray(t) <= 0
    assert (got[~greedy] == want[~greedy]).all()
    assert (got[greedy] == np.asarray(jnp.argmax(logits, axis=-1))[greedy]).all()
    # rows whose filter is live elsewhere but masked off here: still no sort
    live = jnp.arange(B) != 2
    k = jnp.where(live, 0, 5)
    assert int(sampler_path(t, k, jnp.float32(1.0), live)) == 1
    masked = np.asarray(sample_logits(logits, KEY, temperature=t, top_k=k, rows=live))
    assert (masked == got).all()


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_top_k_of_one_is_the_argmax_at_any_temperature(shape):
    logits = _logits(shape)
    t = jnp.linspace(0.2, 5.0, shape[0])
    got = sample_logits(logits, KEY, temperature=t, top_k=1)
    assert (np.asarray(got) == np.asarray(jnp.argmax(logits, axis=-1))).all()


@pytest.mark.parametrize("params", [(0.0, 0, 1.0), (0.8, 40, 0.9)], ids=["greedy", "t0.8-k40-p0.9"])
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_the_first_token_program_is_the_eager_call(shape, params):
    """``batch.sample_first_token`` with the request's three scalars as the
    engine passes them (numpy scalars) against the eager call it replaced,
    and against the old pipeline."""
    t, k, p = params
    logits = _logits(shape)[1:2]
    for rid in (1, 77, 4096):
        key = jax.random.fold_in(KEY, rid)
        got = batch_ops.sample_first_token(logits, key, np.float32(t), np.int32(k), np.float32(p))
        eager = sample_logits(logits, key, temperature=jnp.float32(t), top_k=jnp.int32(k), top_p=jnp.float32(p))
        assert got.shape == (1,) and int(got[0]) == int(eager[0]) == int(pipeline_ref(logits, key, t, k, p)[0])


# ------------------------------------------------------ the chunk path's fold
def _fold(finish, temps, topks, topps, B=4, C=8, V=320):
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    logits_c = 3.0 * jax.random.normal(keys[0], (B, C, V), jnp.float32)
    chunk = jax.random.randint(keys[1], (B, C), 3, V)
    zeros = np.zeros(B, np.int32)
    st = batch_ops.make_decode_state(
        zeros, zeros, np.ones(B, bool), zeros, zeros - 1, np.ones(B, np.float32), zeros, np.ones(B, np.float32),
        jax.random.PRNGKey(0))
    new_len = jnp.asarray([5, 8, 3, 6], jnp.int32)
    rids = jnp.asarray([7, 8, 9, 10], jnp.int32)
    st, first, last = batch_ops._fold_finished_prefill(
        st, logits_c, chunk, jnp.zeros(B, jnp.int32), jnp.asarray(finish), new_len, jnp.full(B, 9, jnp.int32),
        jnp.full(B, -1, jnp.int32), jnp.asarray(temps, jnp.float32), jnp.asarray(topks, jnp.int32),
        jnp.asarray(topps, jnp.float32), rids, KEY)
    want = [int(pipeline_ref(last[b:b + 1], jax.random.fold_in(KEY, int(rids[b])), temps[b], topks[b], topps[b])[0])
            for b in range(B)]
    return np.asarray(first), np.asarray(want), np.asarray(jnp.argmax(last, axis=-1)), st


def test_a_finished_prefill_samples_its_first_token_as_the_host_path_does():
    """A finishing row that samples: every finishing row gets the token the
    old per-row pipeline gave under ``fold_in(root, request id)``."""
    finish = [True, True, False, True]
    first, want, greedy, st = _fold(finish, [0.0, 0.9, 1.0, 1.2], [0, 20, 0, 0], [1.0, 0.9, 1.0, 0.7])
    assert (first[finish] == want[finish]).all() and first[2] == -1 and first[0] == greedy[0]
    assert (np.asarray(st.last_token)[finish] == want[finish]).all()


def test_finished_prefills_that_are_all_greedy_take_the_argmax():
    """The rows that do not finish carry another request's sampling
    parameters and do not choose: the finishing rows get the argmax."""
    finish = [True, False, False, True]
    first, want, greedy, _ = _fold(finish, [0.0, 1.0, 0.8, 0.0], [0, 5, 0, 40], [1.0, 0.9, 0.5, 0.9])
    assert (first[finish] == greedy[finish]).all() and (first[finish] == want[finish]).all()
    assert (first[[1, 2]] == -1).all()


# ---------------------------------------------------- where the sorts are
@pytest.fixture(scope="module")
def tiny_programs():
    """``decode_block_paged`` and ``ragged_step_paged`` lowered (not
    compiled) at ``tests/benchmark/cellbench_tiny.py``'s shapes, as HLO text."""
    from benchmarks.harness import llama_family
    from tests.benchmark import cellbench_tiny

    cfg, params = llama_family.build(cellbench_tiny.TINY_CONFIG, 3)
    ec = cellbench_tiny.ENGINE
    B, page, C, steps = ec["max_slots"], ec["kv_page_size"], ec["prefill_chunk_tokens"], 4
    M = ec["max_seq_len"] // page

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (B,), dtype)

    def ab(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    i32, f32 = jnp.int32, jnp.float32
    params, key = ab(params), ab(jax.random.PRNGKey(0))
    state = batch_ops.DecodeState(vec(i32), vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(f32),
                                  vec(i32), vec(f32), key, vec(i32))
    pool = jax.ShapeDtypeStruct((cfg.n_layers, B * M + 1, cfg.n_kv_heads, page, cfg.head_dim), cfg.dtype)
    tables = vec(i32, B, M)
    row = (vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(i32), vec(f32),
           vec(i32), vec(f32), vec(i32), key, vec(jnp.bool_), steps)
    lowered = {
        "decode_block_paged": batch_ops.decode_block_paged.lower(
            cfg, params, pool, pool, state, tables, vec(jnp.bool_), steps),
        "ragged_step_paged": batch_ops.ragged_step_paged.lower(
            cfg, params, pool, pool, state, tables, vec(i32, B, C), vec(i32), vec(jnp.bool_), *row),
    }
    return {name: computations(low.compiler_ir(dialect="hlo").as_hlo_text()) for name, low in lowered.items()}


def test_the_decode_block_sorts_only_inside_a_branch(tiny_programs):
    """Every ``sort`` of the lowered decode block sits in a computation
    reached only through a branch of a ``conditional``, none in the scan's
    body itself; and the branch index is reduced from the live mask — an
    ``and`` with ``not done`` comes before the reduce."""
    comps, entry = tiny_programs["decode_block_paged"]
    always = reached_outside_a_branch(comps, entry)
    sorts, conds = holds(comps, "sort"), holds(comps, "conditional")
    assert sorts and not sorts & always, sorted(sorts & always)
    assert len(conds) == 1 and conds <= always
    (line,) = [ln for ln in comps[conds.pop()] if "conditional(" in ln]
    assert len(branches(line)) == len(SAMPLER_PATHS)
    body = next(name for name, lines in comps.items() if line in lines)
    index = re.search(r"conditional\(%?([\w.\-]+)", line).group(1)
    ops = operand_closure(comps[body], index)
    # any(live & samples) + any(live & samples & filters), live = active & ~done
    assert ops.count("reduce") == 2 and ops.count("and") >= 3 and "not" in ops and "compare" in ops


def test_the_ragged_step_chooses_its_first_token_sampler_by_a_conditional(tiny_programs):
    """``_fold_finished_prefill`` under ``vmap`` would turn the sampler's
    branches into selects that all run: the lowered ragged step holds one
    ``conditional`` around the vmapped pipeline instead, and its sorts (the
    fold's and the decode steps') are all inside branches."""
    comps, entry = tiny_programs["ragged_step_paged"]
    always = reached_outside_a_branch(comps, entry)
    sorts, conds = holds(comps, "sort"), holds(comps, "conditional")
    assert sorts and not sorts & always, sorted(sorts & always)
    lines = [ln for name in conds for ln in comps[name] if "conditional(" in ln]
    assert sorted(len(branches(ln)) for ln in lines) == [2, len(SAMPLER_PATHS)]
    assert conds <= always  # neither sits inside the other: the fold's is outside the scan
