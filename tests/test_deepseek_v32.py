"""``models/deepseek_v32.py`` on the serving path, at small widths with
seeded random weights (hidden 64, 4 heads of 16 nope + 8 rope over a latent
of 32, an indexer of 4 heads of 16 selecting 8 positions, one dense layer
and two expert layers of 16 experts in 4 groups with 4 a token and one
shared), against the plain reference the benchmark decides ``correct``
with (``benchmarks/harness/deepseek_v32_reference.py``: float32,
``highest``, the expanded form, nothing of the program).

The tolerance of the logit comparisons, ``TOL`` = 2e-3: program and
reference compute the same float32 mathematics in another order (scans
over stacked layers, the absorbed form of latent attention in decode and
chunks against the expanded one, a gather of selected rows against a
mask; logits have deviation about 1 and reach 5 at these widths), which
reads under 2e-4 here. One step of lower precision — the same weights in
int4 — moves logits by more than 0.1 and fails it. With 4 index heads an
indexer score is exactly zero where every head's ReLU is (one pair in
sixteen), so ties at the selection's edge are common here: program and
reference break them alike, the earlier position first
(``ops/mla.select_topk``, ``selection_mask``).
"""

import dataclasses
import functools
import json
import math
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import deepseek_v32_reference as reference
from gofr_tpu.models import cohere2_moe, llama
from gofr_tpu.models import deepseek_v32 as ds
from gofr_tpu.ops import mla
from gofr_tpu.ops import moe as moe_ops
from gofr_tpu.ops.rope import angles, apply_rope_halves, yarn_frequencies, yarn_mscale
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving import batch as batch_ops
from gofr_tpu.serving.kv_cache import PagedKVCache

TOL = 2e-3
PAGE = 4
CFG = ds.DeepseekV32Config.tiny(vocab_size=300)


def as_file(cfg, first=0):
    """The configuration file's keys for a program config: what the
    reference reads."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads, "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_dense_layers, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "index_n_heads": cfg.index_n_heads, "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk, "num_experts_per_tok": cfg.top_k, "n_group": cfg.n_group,
        "topk_group": cfg.topk_group, "routed_scaling_factor": cfg.routed_scaling,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": {"type": "yarn", "factor": cfg.rope_factor, "beta_fast": cfg.beta_fast,
                         "beta_slow": cfg.beta_slow, "mscale": cfg.mscale, "mscale_all_dim": cfg.mscale,
                         "original_max_position_embeddings": cfg.rope_original_max},
        "deployment": {"first_expert": first},
    }


@pytest.fixture(scope="module")
def plain():
    return ds.init_params(CFG, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def int8(plain):
    return ds.quantize_params(plain)


def hold_share(params, first, held, vocab_rows):
    """The share of a whole tree that one chip holds: the routed experts
    ``first .. first + held`` of every expert layer, the shared expert,
    attention, indexer and router whole, and a slice of embedding and head."""
    moe = dict(params["moe"])
    moe["experts"] = jax.tree.map(lambda a: a[:, first:first + held], moe["experts"])
    return dict(params, moe=moe, embedding=params["embedding"][vocab_rows], lm_head=params["lm_head"][:, vocab_rows])


def ids_of(n, seed=3):
    return np.asarray([1] + list(np.random.default_rng(seed).integers(3, 259, n - 1)), np.int32)


def paged(cfg, slots, pages_per_slot):
    """Empty pools of the model's two page shapes and block tables that
    give every slot its own pages, in an order that is not the identity."""
    n = slots * pages_per_slot
    k_page, v_page = ds.page_shapes(cfg, PAGE)
    tables = np.random.default_rng(1).permutation(n).reshape(slots, pages_per_slot).astype(np.int32)
    return (jnp.zeros((cfg.n_layers, n + 1) + k_page, cfg.dtype),
            jnp.zeros((cfg.n_layers, n + 1) + v_page, cfg.dtype), jnp.asarray(tables))


def write_slab(pool, slab, table, start=0):
    """A prefill slab [L, S, 1, W] into a row's pages from ``start``."""
    for t in range(slab.shape[1]):
        pos = start + t
        pool = pool.at[:, table[pos // PAGE], :, pos % PAGE].set(slab[:, t])
    return pool


def serve_through_the_cache(cfg, params, ids, n_prompt, bucket, pages=16):
    """Bucketed prefill of the first ``n_prompt`` tokens, then the rest one
    decode step at a time through the paged pools of ``pages`` a slot
    (teacher-forced): the logits at positions n_prompt-1 .. len(ids)-1, and
    each step's counters."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :n_prompt] = ids[:n_prompt]
    last, k_slab, v_slab = batch_ops.prefill_compute(cfg, params, jnp.asarray(tokens), jnp.asarray([n_prompt]))
    kp, vp, tables = paged(cfg, 2, pages)
    kp, vp = write_slab(kp, k_slab[:, :n_prompt], tables[0]), write_slab(vp, v_slab[:, :n_prompt], tables[0])
    out, counted = [np.asarray(last[0])], []
    for pos in range(n_prompt, len(ids)):
        logits, kp, vp, stats = ds.decode_step_paged(
            cfg, params, jnp.asarray([ids[pos], 0]), kp, vp, tables,
            jnp.asarray([pos + 1, 1]), jnp.asarray([True, False]))
        out.append(np.asarray(logits[0]))
        counted.append(np.asarray(stats))
    return np.stack(out), np.stack(counted)


def chunked(cfg, params, ids, chunk):
    """The whole sequence through ``decode_chunk_paged``, ``chunk`` tokens
    a dispatch, in row 1 of three (rows 0 and 2 have no chunk): logits at
    every position."""
    kp, vp, tables = paged(cfg, 3, 16)
    out = []
    for start in range(0, len(ids), chunk):
        piece = np.full((3, chunk), -1, np.int32)
        n = min(chunk, len(ids) - start)
        piece[1, :n] = ids[start:start + n]
        logits, kp, vp = ds.decode_chunk_paged(
            cfg, params, jnp.asarray(piece), kp, vp, tables, jnp.asarray([64, start, 64]),
            jnp.asarray([False, True, False]), jnp.asarray([0, 64, 0]))
        assert not np.asarray(logits[0]).any() and not np.asarray(logits[2]).any()
        out.append(np.asarray(logits[1, :n]))
    return np.concatenate(out)


# ---------------------------------------------------- against the reference
@pytest.mark.parametrize("weights", ["plain", "int8"])
def test_prefill_then_decode_with_a_binding_selection_agrees_with_the_reference(weights, request):
    params = request.getfixturevalue(weights)
    ids = ids_of(40)
    want = np.asarray(reference.logits(as_file(CFG), params, ids))
    got, counted = serve_through_the_cache(CFG, params, ids, n_prompt=12, bucket=16)
    # 28 decoded positions, the last at 39: a selection of 8 binds from position 8 on
    assert got.shape == (29, 300) and np.abs(want).max() > 3
    assert np.abs(got - want[11:]).max() < TOL
    # every step, each of the 2 expert layers routes the one live row to 4 of the 16 experts, all held;
    # each of the 3 layers scores the row's whole context and reads 8 positions of it
    # (2 rows x 4 of 16 experts: the product groups the rows, and counts the experts it read — the live row's 4 a layer)
    assert counted.shape == (28, 19) and (counted[:, :16].sum(axis=1) == 2 * 4).all() and (counted[:, 16] == 2 * 4).all()
    assert (counted[:, 17] == 3 * np.arange(13, 41)).all() and (counted[:, 18] == 3 * 8).all()


@pytest.mark.parametrize("chunk, n", [
    pytest.param(12, 40, id="chunks-of-12-the-loop-over-every-row"),
    pytest.param(6, 40, id="chunks-of-6-the-grouped-product"),
    pytest.param(12, 60, id="chunks-of-12-to-the-slot"),
    pytest.param(8, 60, id="chunks-of-8-to-the-slot"),
])
@pytest.mark.parametrize("weights", ["plain", "int8"])
def test_chunked_prefill_agrees_with_the_reference(weights, chunk, n, request):
    """A chunk reads the first of its contexts (``ds.chunk_contexts``:
    12, 24, 48, 64 for chunks of 12; 8, 16, 32, 64 for 6 and 8) that holds
    its end. Every one is taken by some case, the 8-position one no wider
    than ``index_topk`` (the selection keeps every seen position), the
    others with a selection that binds."""
    params = request.getfixturevalue(weights)
    ids = ids_of(n, seed=4)
    want = np.asarray(reference.logits(as_file(CFG), params, ids))
    # 12 rows x 4 of 16 experts is 3 rows an expert: the loop; 6 rows is 1.5: each expert takes its own rows
    assert moe_ops.groups_rows(chunk, CFG.n_experts, CFG.top_k) is (chunk == 6)
    contexts = ds.chunk_contexts(chunk, PAGE, 64)
    read = {next(c for c in contexts if c >= start + chunk) for start in range(0, n, chunk)}
    assert n < 60 or read == set(contexts)  # 60 tokens reach the slot: every context is read
    got = chunked(CFG, params, ids, chunk)  # chunks at 0, 12, 24, 36 (or every 6): selections reach across them
    assert np.abs(got - want).max() < TOL


def test_the_selection_over_a_chunks_context_is_the_one_over_the_slot():
    """For a chunk at every start, the selection over the context it reads
    is the selection over the whole slot (the form a chunk had before it
    read a context, kept here as the oracle) cut to that context, bit for
    bit: nothing past the chunk's end is seen, and ties (scores drawn from
    seven values, zero among them) break alike."""
    T, slot = 12, 64
    contexts = ds.chunk_contexts(T, PAGE, slot)
    scores = jnp.asarray(np.random.default_rng(5).integers(-3, 4, (T, slot)).astype(np.float32))
    ctx = jnp.arange(slot)
    for start in range(0, slot, T):
        positions, end = start + jnp.arange(T), jnp.asarray(start + T)
        seen = (ctx[None, :] <= positions[:, None]) & (ctx[None, :] < end)
        oracle = np.asarray(mla.selection_mask(scores, seen, CFG.index_topk))
        for n in (c for c in contexts if c >= min(start + T, slot)):
            got = np.asarray(ds._chunk_keep(CFG, positions, end, n, scores[:, :n]))
            assert (got == oracle[:, :n]).all() and not oracle[:, n:].any(), (start, n)
        assert oracle.sum(axis=1).max() == min(CFG.index_topk, start + T)


def poisoned(pool, table, first, last=None):
    """The pool with a row's pages from position ``first`` (to ``last``) NaN."""
    return pool.at[:, np.asarray(table)[first // PAGE:None if last is None else last // PAGE]].set(jnp.nan)


def one_chunk(params, ids, start, kp, vp, tables):
    """``ids[start:start+12]`` as row 1's chunk over the given pools."""
    piece = np.full((3, 12), -1, np.int32)
    piece[1] = ids[start:start + 12]
    logits, kp, vp = ds.decode_chunk_paged(
        CFG, params, jnp.asarray(piece), kp, vp, tables, jnp.asarray([64, start, 64]),
        jnp.asarray([False, True, False]), jnp.asarray([0, 64, 0]))
    return np.asarray(logits[1]), kp, vp


def filled(seed=9):
    """Both pools of three rows filled with finite values: a context."""
    kp, vp, tables = paged(CFG, 3, 16)
    kp = jax.random.normal(jax.random.PRNGKey(seed), kp.shape, kp.dtype)
    vp = jax.random.normal(jax.random.PRNGKey(seed + 1), vp.shape, vp.dtype)
    return kp, vp, tables


@pytest.mark.parametrize("start", [0, 12, 24, 36])
def test_a_chunk_reads_the_pages_up_to_its_end_alone(plain, start):
    """Both pools' pages of the row past the context that holds the
    chunk's end (12, 24, 48, 48 positions) are NaN: the chunk's logits and
    every page it may read or write come out as with clean pools."""
    n = next(c for c in ds.chunk_contexts(12, PAGE, 64) if c >= start + 12)
    ids = ids_of(48)
    kp, vp, tables = filled()
    got, kg, vg = one_chunk(plain, ids, start, poisoned(kp, tables[1], n), poisoned(vp, tables[1], n), tables)
    clean, kc, vc = one_chunk(plain, ids, start, kp, vp, tables)  # the pools are donated: clean last
    assert np.isfinite(got).all() and np.abs(got - clean).max() == 0.0
    held = np.asarray(tables[1])[:n // PAGE]
    for a, b in ((kc, kg), (vc, vg)):
        assert np.array_equal(np.asarray(a[:, held]), np.asarray(b[:, held]))


def test_the_int4_control_fails_the_same_tolerance(int8):
    ids = ids_of(40)
    got, _ = serve_through_the_cache(CFG, int8, ids, n_prompt=12, bucket=16)
    control = np.asarray(reference.logits(as_file(CFG), int8, ids, weight_bits=4))
    assert np.abs(got - control[11:]).max() > 50 * TOL


def test_a_selection_wider_than_the_context_is_attention_without_an_indexer(plain):
    """``index_topk`` >= the context keeps every seen position, whatever
    the indexer scores: the logits are those of a model whose indexer is
    zeroed, and differ from the binding selection's."""
    ids = ids_of(24)
    wide = dataclasses.replace(CFG, index_topk=64)
    binding, _ = serve_through_the_cache(CFG, plain, ids, 12, 16)
    got, counted = serve_through_the_cache(wide, plain, ids, 12, 16)
    assert np.abs(binding - got).max() > 10 * TOL
    assert (counted[:, 18] == counted[:, 17]).all()  # every scored position is read
    blind = dict(plain)
    for group in ("dense", "moe"):
        blind[group] = dict(plain[group], idx_w=jnp.zeros_like(plain[group]["idx_w"]))
    same, _ = serve_through_the_cache(wide, blind, ids, 12, 16)
    assert np.abs(got - same).max() < 1e-5
    assert np.abs(got - np.asarray(reference.logits(as_file(wide), plain, ids))[11:]).max() < TOL
    assert np.abs(chunked(wide, plain, ids, 12) - chunked(wide, blind, ids, 12)).max() < 1e-5


def test_the_absorbed_form_is_the_expanded_form():
    """Latent rows scored and summed by every head (queries through
    W_UK, outputs through W_UV) against per-head keys and values expanded
    from the same latents, under the same mask, plain and int8."""
    cfg = CFG
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    T, S, H = 5, 11, cfg.n_heads
    q_nope = jax.random.normal(ks[0], (T, H, cfg.qk_nope_head_dim), jnp.float32)
    q_rope = jax.random.normal(ks[1], (T, H, cfg.qk_rope_head_dim), jnp.float32)
    c_kv = jax.random.normal(ks[2], (S, cfg.kv_lora_rank), jnp.float32)
    k_r = jax.random.normal(ks[3], (S, cfg.qk_rope_head_dim), jnp.float32)
    w = jax.random.normal(ks[4], (cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), jnp.float32) / 6
    keep = jax.random.bernoulli(ks[5], 0.6, (T, S)).at[:, 0].set(True)
    rows = jnp.concatenate([c_kv, k_r, jnp.zeros((S, cfg.row_width - cfg.kv_lora_rank - cfg.qk_rope_head_dim))], axis=-1)
    assert cfg.row_width == 128 and mla.latent_row_width(512, 64) == 640
    for weight in (w, llama.quantize_weight(w, axis=-2)):
        kvb = llama._mm(c_kv, weight).reshape(S, H, -1)
        want = mla.expanded_attention(q_nope, q_rope, kvb[..., :cfg.qk_nope_head_dim], k_r,
                                      kvb[..., cfg.qk_nope_head_dim:], keep, cfg.softmax_scale)
        o_lat = mla.latent_attention(ds._absorb_query(cfg, q_nope, q_rope, weight), rows, keep,
                                     cfg.softmax_scale, cfg.kv_lora_rank)
        got = ds._absorb_output(cfg, o_lat, weight, jnp.float32)
        assert np.abs(np.asarray(got) - np.asarray(want).reshape(T, -1)).max() < 1e-5 and np.abs(want).max() > 0.5


def test_the_sparse_read_gathers_the_selected_rows_alone():
    """Rows nobody selected hold NaN: the decode attention does not read
    them. Its result is dense latent attention under the selection's mask."""
    L, B, H, W, R, M, K = 2, 3, 4, 128, 32, 6, 5
    n = B * M
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    pool = jax.random.normal(ks[0], (L, n + 1, 1, PAGE, W), jnp.float32)
    q = jax.random.normal(ks[1], (B, H, W), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(3).permutation(n).reshape(B, M), jnp.int32)
    lens = np.asarray([24, 3, 17])
    scores = jax.random.normal(ks[2], (B, M * PAGE), jnp.float32)
    seen = jnp.arange(M * PAGE)[None] < lens[:, None]
    selected, valid = mla.select_topk(scores, seen, K)
    assert (np.asarray(valid).sum(axis=1) == np.minimum(lens, K)).all()
    # the sort that selects is top_k, earlier positions first among equal scores, and carries a payload
    top, idx = jax.lax.top_k(jnp.where(seen, scores, mla.NEG_INF), K)
    assert (np.asarray(selected) == np.asarray(idx)).all()
    tied, _ = mla.select_topk(jnp.zeros((1, 9)), jnp.ones((1, 9), bool), 4)
    assert np.asarray(tied).tolist() == [[0, 1, 2, 3]]
    # the mask the dense paths take settles ties the same way
    assert np.asarray(mla.selection_mask(jnp.zeros((1, 9)), jnp.ones((1, 9), bool), 4)).tolist() == [[True] * 4 + [False] * 5]
    where = mla.pool_rows(tables, n + 1, PAGE, jnp.int32(1))  # [B, S]: each position's row in the flat pool
    in_pool, valid_too = mla.select_topk(scores, seen, K, where)
    assert (np.asarray(valid_too) == np.asarray(valid)).all()
    assert (np.asarray(in_pool) == np.take_along_axis(np.asarray(where), np.asarray(selected), axis=1)).all()
    rows = np.asarray(mla.row_pages(pool, tables, 1))  # [B, S, W] of layer 1
    assert (np.asarray(pool).reshape(-1, W)[np.asarray(where)] == rows).all()
    keep = np.zeros((B, M * PAGE), bool)
    for b in range(B):
        keep[b, np.asarray(selected[b])[np.asarray(valid[b])]] = True
    assert (keep <= np.asarray(seen)).all()
    want = mla.latent_attention(q[:, None], jnp.asarray(rows), jnp.asarray(keep)[:, None], 0.3, R)[:, 0]
    poisoned = np.full(pool.shape, np.nan, np.float32)
    for b in range(B):
        for pos in np.flatnonzero(keep[b]):
            poisoned[1, tables[b, pos // PAGE], 0, pos % PAGE] = rows[b, pos]
    poisoned[0, 0, 0, 0] = 0.0  # where a list's unused entries point: the pool's first row
    got = mla.sparse_decode_attention(q, jnp.asarray(poisoned), in_pool, valid, scale=0.3, kv_lora_rank=R)
    assert np.isfinite(np.asarray(got)).all() and np.abs(np.asarray(got - want)).max() < 1e-5
    assert (np.asarray(mla.selection_mask(scores, seen, K)) == keep).all()


# ------------------------------------------------------------ closed forms
@pytest.mark.parametrize("pages, read, interpret", [
    pytest.param(8, "masked", False, id="32-positions-masked"),
    pytest.param(8, "masked", True, id="32-positions-masked-through-the-kernel"),
    pytest.param(9, "gather", False, id="36-positions-gather"),
    pytest.param(16, "gather", False, id="64-positions-gather"),
])
def test_the_masked_read_and_the_gather_attend_to_the_same_selection(plain, pages, read, interpret, monkeypatch):
    """The read a decode step takes comes from the slot's shape alone
    (``ds.sparse_read``: the row's pages whole under the selection's mask
    up to 4 x ``index_topk`` = 32 positions, the gather by token past it).
    Over the same tokens and positions — a selection of 8 that binds from
    position 8 on, ties at its edge — each form's logits are the gather's
    at 16 pages a slot and the reference's, and each counts the same
    positions selected; the masked form also through the latent kernel in
    the interpreter."""
    from gofr_tpu.ops import latent_attention as la

    assert ds.sparse_read(CFG, pages * PAGE) == read and ds.sparse_read(ds.DeepseekV32Config.tiny(index_topk=0), 8) is None
    cfg = CFG
    if interpret:  # a config of its own: the jitted step traces afresh with the kernel in the interpreter
        cfg = dataclasses.replace(CFG, max_seq_len=CFG.max_seq_len + 1)
        monkeypatch.setattr(ds, "paged_latent_attention", functools.partial(la.paged_latent_attention, interpret=True))
    ids = ids_of(32)
    got, counted = serve_through_the_cache(cfg, plain, ids, n_prompt=12, bucket=16, pages=pages)
    gathered, counted_by_gather = serve_through_the_cache(CFG, plain, ids, n_prompt=12, bucket=16)
    want = np.asarray(reference.logits(as_file(CFG), plain, ids))
    assert np.abs(got - gathered).max() < TOL and np.abs(got - want[11:]).max() < TOL
    assert (counted[:, -2:] == counted_by_gather[:, -2:]).all() and (counted[:, -1] == 3 * 8).all()


def test_yarn_angles_and_temperature_against_closed_forms():
    """The published shapes: 64 rotary dims, base 10000, factor 40 over
    4096. Pairs 0..10 turn more than 32 times in 4096 positions and keep
    their frequency, pairs 23..31 turn less than once and get a fortieth,
    the ramp between is linear in the pair index."""
    f = np.asarray(yarn_frequencies(64, 10000.0, 40.0, 4096, 32.0, 1.0))
    base = 10000.0 ** (-np.arange(32) / 32)
    turns = 4096 * base / (2 * math.pi)
    assert (turns[:11] > 32).all() and (turns[23:] < 1).all()
    assert np.allclose(f[:11], base[:11], rtol=1e-6) and np.allclose(f[23:], base[23:] / 40, rtol=1e-6)
    gamma = 1 - (np.arange(11, 23) - 10) / 13
    assert np.allclose(f[11:23], base[11:23] / 40 * (1 - gamma) + base[11:23] * gamma, rtol=1e-6)
    assert np.allclose(f, reference._yarn_freqs(64, 10000.0, {
        "factor": 40, "beta_fast": 32, "beta_slow": 1, "original_max_position_embeddings": 4096}), rtol=1e-6)
    m = 0.1 * math.log(40) + 1
    assert abs(yarn_mscale(40.0, 1.0) - m) < 1e-12 and yarn_mscale(1.0) == 1.0
    cfg = ds.DeepseekV32Config()
    assert abs(cfg.softmax_scale - 192 ** -0.5 * m * m) < 1e-12
    assert abs(reference.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_scaling": {
        "factor": 40, "mscale": 1.0, "mscale_all_dim": 1.0}}) - cfg.softmax_scale) < 1e-12
    # the two-halves layout: lane i pairs with lane i + half
    x = jnp.asarray([1.0, 0.0, 0.0, 0.0, 0.0, 2.0, 0.0, 0.0]).reshape(1, 1, 1, 8)
    sin, cos = angles(jnp.asarray([[3]]), jnp.asarray([1.0, 0.5, 0.25, 0.125]))
    out = np.asarray(apply_rope_halves(x, sin, cos))[0, 0, 0]
    assert np.allclose(out, [np.cos(3), -2 * np.sin(1.5), 0, 0, np.sin(3), 2 * np.cos(1.5), 0, 0], atol=1e-6)


def test_the_group_limited_gate_on_a_hand_worked_example():
    """8 experts in 4 groups of 2, 2 groups kept, 2 experts chosen, scaling
    2.5. Logits through an identity router; s = sigmoid(logit)."""
    logit = jnp.asarray([[2.0, -1.0, 1.5, 1.4, 0.0, 0.1, -2.0, 3.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.9, 0.9, 0.0, -0.5])
    s = 1 / (1 + np.exp(-np.asarray(logit[0])))
    # corrected scores: group sums (of both members) decide the groups
    c = s + np.asarray(bias)
    group = c.reshape(4, 2).sum(axis=1)
    assert list(np.argsort(-group)[:2]) == [2, 1]  # the corrected group (experts 4, 5), then group 1: NOT group 0 or 3
    # among experts 2..5 the two largest corrected scores are 5 and 4; the gates are the UNcorrected ones
    want = np.zeros(8)
    want[[4, 5]] = 2.5 * s[[4, 5]] / (s[4] + s[5] + 1e-20)
    got = moe_ops.sigmoid_topk_gates(logit, jnp.eye(8), 2, bias=bias, n_group=4, topk_group=2, scale=2.5)
    assert np.allclose(np.asarray(got[0]), want, atol=1e-6)
    assert np.allclose(np.asarray(reference.gates(jnp.asarray(s)[None], bias, 2, 4, 2, 2.5))[0], want, atol=1e-6)
    # without the correction groups 1 and 0 stay (the sums of their two scores) and experts 0 and 2 are
    # chosen: expert 7, the largest score of all, is in a group that does not stay; without groups it is chosen
    limited = moe_ops.sigmoid_topk_gates(logit, jnp.eye(8), 2, n_group=4, topk_group=2)
    assert set(np.flatnonzero(np.asarray(limited[0]))) == {0, 2}
    assert set(np.flatnonzero(np.asarray(moe_ops.sigmoid_topk_gates(logit, jnp.eye(8), 2)[0]))) == {0, 7}


def test_neutral_arguments_give_the_plain_sigmoid_gates_bit_for_bit():
    h = jax.random.normal(jax.random.PRNGKey(1), (33, 24), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (24, 16), jnp.float32)
    scores = jax.nn.sigmoid(jnp.matmul(h, w, precision=jax.lax.Precision.HIGHEST))
    top_s, top_i = jax.lax.top_k(scores, 4)  # the rule as PR 29 wrote it
    want = jnp.einsum("tke,tk->te", jax.nn.one_hot(top_i, 16, dtype=jnp.float32),
                      top_s / jnp.sum(top_s, axis=-1, keepdims=True))
    assert bool(jnp.all(moe_ops.sigmoid_topk_gates(h, w, 4) == want))
    assert bool(jnp.all(moe_ops.sigmoid_topk_gates(h, w, 4, bias=None, n_group=1, topk_group=1, scale=1.0) == want))


# ---------------------------------------------------------------- the share
def test_the_eight_shares_add_up_to_the_uncut_layer(plain):
    """Eight chips hold 2 of the 16 experts each (half a routing group):
    their parts, with the shared expert counted once, are the whole
    layer's routed + shared sum, as the reference computes it uncut."""
    lp = jax.tree.map(lambda a: a[1], plain["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.d_model), jnp.float32)
    gates = moe_ops.sigmoid_topk_gates(h, lp["w_router"], CFG.top_k, bias=lp["router_bias"], n_group=CFG.n_group,
                                       topk_group=CFG.topk_group, scale=CFG.routed_scaling)
    chosen = np.asarray(gates > 0)
    assert (chosen.sum(axis=1) == CFG.top_k).all() and np.allclose(gates.sum(axis=1), CFG.routed_scaling, atol=1e-5)
    assert (chosen.reshape(24, CFG.n_group, -1).any(axis=2).sum(axis=1) <= CFG.topk_group).all()
    none_held = jax.tree.map(lambda a: a[:0], lp["experts"])
    shared, *_ = moe_ops.held_experts(h, gates, none_held, lp["shared"], 0)
    total, counted = jnp.zeros_like(shared), []
    for first in range(0, 16, 2):
        share = jax.tree.map(lambda a: a[first:first + 2], lp["experts"])
        part, g, _ = moe_ops.held_experts(h, gates, share, lp["shared"], first)
        total += part - shared
        counted.append(int((g > 0).sum()))
    whole, *_ = moe_ops.held_experts(h, gates, lp["experts"], lp["shared"], 0)
    assert sum(counted) == 24 * CFG.top_k
    assert np.abs(total + shared - whole).max() < 1e-5
    sigma = jax.nn.sigmoid(jnp.matmul(h, lp["w_router"], precision=jax.lax.Precision.HIGHEST))
    g_ref = reference.gates(sigma, lp["router_bias"], CFG.top_k, CFG.n_group, CFG.topk_group, CFG.routed_scaling)
    uncut = reference._ffn_sum(h, lp["experts"], g_ref.T, 8) + reference._ffn_sum(
        h, lp["shared"], jnp.ones((CFG.n_shared, 24)), 8)
    assert np.abs(whole - uncut).max() < 1e-4 and np.abs(uncut).max() > 0.1


def test_a_share_of_the_model_is_the_reference_given_the_same_share(plain):
    """Experts 8..11 (one routing group) held and rows 0..199 of the
    vocabulary: program and reference leave out the same part, and differ
    from the whole model."""
    cfg = ds.DeepseekV32Config.tiny(vocab_size=200, held_experts=4, first_expert=8)
    share = hold_share(plain, 8, 4, slice(0, 200))
    ids = np.minimum(ids_of(24), 199)
    got, counted = serve_through_the_cache(cfg, share, ids, 12, 16)
    want = np.asarray(reference.logits(as_file(cfg, first=8), share, ids))
    assert got.shape[1] == 200 and np.abs(got - want[11:]).max() < TOL
    whole = np.asarray(reference.logits(as_file(CFG), plain, ids))[11:, :200]
    assert np.abs(got - whole).max() > 10 * TOL
    # one group of four: some rows, not all; then the experts the grouped product read, then the indexer's two
    assert counted.shape[1] == 4 + 1 + 2 and 0 < counted[:, :4].sum() < 12 * 2 * 4
    assert (counted[:, 4] == counted[:, :4].sum(axis=1)).all()  # one live row: each expert it chose, once a layer


# ----------------------------------------------------------------- the pager
def test_the_pager_is_told_by_the_model_what_its_pools_hold():
    """``llama`` and ``cohere2_moe`` answer [Hkv, page, Dh] twice, as
    before this model (and as a pager that is told nothing assumes);
    ``deepseek_v32`` a latent row and an indexer key a token, under one
    block table. Slabs of both shapes go in and come out."""
    for module, cfg in ((llama, llama.LlamaConfig.tiny()), (cohere2_moe, cohere2_moe.Cohere2MoeConfig.tiny())):
        shape = (cfg.n_layers, 7, cfg.n_kv_heads, 8, cfg.head_dim)
        for told in (None, module.page_shapes(cfg, 8)):
            cache = PagedKVCache(cfg, num_pages=6, page_size=8, max_slots=2, max_seq_len=24, page_shapes=told)
            assert cache.k_pool.shape == cache.v_pool.shape == shape
            cache.close()
    cache = PagedKVCache(CFG, num_pages=6, page_size=8, max_slots=2, max_seq_len=24,
                         page_shapes=ds.page_shapes(CFG, 8))
    assert cache.k_pool.shape == (3, 7, 1, 8, 128) and cache.v_pool.shape == (3, 7, 1, 8, 16)
    k = jax.random.normal(jax.random.PRNGKey(0), (3, 13, 1, 128), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(1), (3, 13, 1, 16), jnp.float32)
    cache.alloc_slot(1, seq_id=5, prompt_len=13)
    cache.write_prefill(1, k, v)
    back_k, back_v = cache.read_span(1, 0, 13)
    assert bool(jnp.all(back_k == k)) and bool(jnp.all(back_v == v))
    cache.write_span(1, 8, k[:, :5], v[:, :5])
    back_k, back_v = cache.read_span(1, 8, 13)
    assert bool(jnp.all(back_k == k[:, :5])) and bool(jnp.all(back_v == v[:, :5]))
    cache.close()


# -------------------------------------------------- the engine and the App
def engine_settings(**kw):
    settings = dict(max_slots=3, max_seq_len=64, prefill_buckets=(16,), multi_step=4,
                    kv_page_size=8, prefill_chunk_tokens=16)
    settings.update(kw)
    return EngineConfig(**settings)


@pytest.mark.parametrize("settings, lora, sentence", [
    (dict(spec_tokens=2, multi_step=None), None, "no speculative verify program"),
    (dict(), object(), "serves no LoRA adapters"),
], ids=["speculative", "lora"])
def test_engines_the_model_has_no_program_for_are_refused_at_construction(plain, settings, lora, sentence):
    with pytest.raises(ValueError, match=sentence):
        ServingEngine(CFG, plain, engine_settings(**settings), ByteTokenizer(300), lora=lora)


@pytest.mark.parametrize("start", [0, 12, 24, 36, 48])
def test_the_engine_mirrors_the_context_a_chunk_reads(plain, start):
    """The engine's ``chunk_ctx`` for a chunk row is the context the
    program read: NaN from there on leaves the chunk's logits finite, and
    NaN between the chunk's end and there (where they differ) reaches
    them, so the program read that far and no further."""
    engine = ServingEngine(CFG, plain, engine_settings(kv_page_size=PAGE, prefill_chunk_tokens=12), ByteTokenizer(300))
    try:
        n = engine._chunk_ctx(engine.paged_cache, [(1, None, None, start, 12)])
    finally:
        engine.stop()
    assert n in ds.chunk_contexts(12, PAGE, 64) and n >= min(start + 12, 64)
    ids = ids_of(60)
    kp, vp, tables = filled()  # the pools are donated: each call takes its own
    beyond, *_ = one_chunk(plain, ids, start, poisoned(kp, tables[1], n), poisoned(vp, tables[1], n), tables)
    assert np.isfinite(beyond).all()
    if start + 12 < n:
        kp, vp, tables = filled()
        inside, *_ = one_chunk(plain, ids, start, poisoned(kp, tables[1], start + 12, n), vp, tables)
        assert np.isnan(inside).any()


def test_the_seam_finds_the_module_and_its_counters():
    # 16 held experts' rows, the held experts read, the indexer's two
    assert batch_ops.model_of(CFG) is ds and ds.step_stats_len(CFG) == 16 + 1 + 2
    assert CFG.n_kv_heads == 1 and CFG.head_dim == CFG.qk_rope_head_dim


@pytest.mark.parametrize("slot, read", [(32, "masked"), (64, "gather")])
def test_every_block_names_the_read_its_decode_steps_take(plain, slot, read, monkeypatch):
    """The engine asks the model once how a decode step reads the
    selection from its slot (``ds.sparse_read``: 32 positions are 4 x
    ``index_topk``, 64 are more) and names it as ``dsa_read`` on every
    block's dispatch span, decode and ragged alike; /metrics counts the
    blocks under that path in ``app_dsa_read_blocks_total`` and under no
    other. A model without an indexer sets neither."""
    from gofr_tpu.config import MapConfig
    from gofr_tpu.container.container import Container
    from gofr_tpu.serving import engine as engine_mod

    container = Container(MapConfig({"APP_NAME": "dsa-read", "LOG_LEVEL": "WARN"}, use_env=False))
    seen = []
    real = engine_mod._StepPhase.set
    monkeypatch.setattr(engine_mod._StepPhase, "set", lambda self, **kw: (seen.append((self._phase, kw)), real(self, **kw))[1])
    engine = ServingEngine(CFG, plain, engine_settings(max_seq_len=slot), ByteTokenizer(300),
                           metrics=container.metrics_manager, logger=container.logger)
    try:
        engine.start()
        # a bucketed prompt and one that chunks (20 tokens with BOS, chunks of 16): decode and ragged blocks
        for prompt in ("short", "a prompt that chunks"):
            assert len(engine.submit(prompt, max_new_tokens=10, temperature=0.0).result(timeout=300).token_ids) == 10
        metrics = container.metrics_manager.expose_prometheus()
    finally:
        engine.stop()
        container.close()
    blocks = [kw for phase, kw in seen if phase == "dispatch" and "blk" in kw]
    assert {kw["kind"] for kw in blocks} == {"decode", "ragged"}
    assert [kw["dsa_read"] for phase, kw in seen if phase == "dispatch" and "dsa_read" in kw] == [read] * len(blocks)
    counted = {line.split("{", 1)[1].split("}")[0]: int(float(line.rsplit(" ", 1)[1]))
               for line in metrics.splitlines() if line.startswith("app_dsa_read_blocks_total{")}
    assert counted == {f'path="{read}"': len(blocks)}
    index_free = dataclasses.replace(CFG, index_n_heads=0, index_head_dim=0, index_topk=0)
    engine = ServingEngine(index_free, ds.init_params(index_free, jax.random.PRNGKey(7)),
                           engine_settings(max_seq_len=slot), ByteTokenizer(300))
    try:
        assert engine._dsa_read is None
    finally:
        engine.stop()


def test_the_model_is_served_behind_an_app_over_http_with_its_spans_and_counters(plain, monkeypatch):
    """POST /generate and the SSE route through a real App, a bucketed and
    a chunked prompt: the tokens are the reference's greedy choice, the
    commit spans carry ``dsa_scored``, ``dsa_selected``, ``moe_rows``,
    ``moe_max`` and ``moe_reached``, the dispatch spans ``dsa_rows`` and, on
    a chunk's, ``chunk_ctx``, and /metrics counts the
    positions by kind and the rows by expert."""
    import gofr_tpu
    from gofr_tpu.config import MapConfig
    from gofr_tpu.serving import engine as engine_mod
    from gofr_tpu.serving.handlers import register_generation_routes
    from gofr_tpu.testutil import get_free_port

    http_port, metrics_port = get_free_port(), get_free_port()
    app = gofr_tpu.App(MapConfig({"HTTP_PORT": str(http_port), "METRICS_PORT": str(metrics_port),
                                  "APP_NAME": "deepseek-v32-test", "LOG_LEVEL": "WARN"}, use_env=False))
    tokenizer = ByteTokenizer(300)
    engine = ServingEngine(CFG, plain, engine_settings(), tokenizer, metrics=app.container.metrics_manager,
                           logger=app.container.logger)
    seen = []
    real = engine_mod._StepPhase.set
    monkeypatch.setattr(engine_mod._StepPhase, "set", lambda self, **kw: (seen.append((self._phase, kw)), real(self, **kw))[1])
    register_generation_routes(app, engine)
    thread = threading.Thread(target=app.run, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{http_port}"

    def post(path, body):
        req = urllib.request.Request(base + path, data=json.dumps(body).encode(), method="POST",
                                     headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=300)

    try:
        deadline = time.monotonic() + 60
        while True:
            try:
                urllib.request.urlopen(base + "/.well-known/alive", timeout=1).close()
                break
            except OSError:
                assert time.monotonic() < deadline and thread.is_alive()
                time.sleep(0.05)
        short = "a short one"          # bucketed prefill
        long = "a prompt of three chunks, and a tail "  # 38 bytes + BOS: chunked at 16
        answers, texts = {}, {}
        for prompt in (short, long):
            with post("/generate/stream", {"prompt": prompt, "max_tokens": 14, "temperature": 0.0}) as resp:
                frames = [json.loads(line[6:]) for line in resp.read().decode().splitlines()
                          if line.startswith("data: {")]
            answers[prompt] = [f["token"] for f in frames if "token" in f]
            with post("/generate", {"prompt": prompt, "max_tokens": 14, "temperature": 0.0}) as resp:
                texts[prompt] = json.loads(resp.read())["data"]
        metrics = urllib.request.urlopen(f"http://127.0.0.1:{metrics_port}/metrics", timeout=10).read().decode()
    finally:
        app.stop()
        thread.join(timeout=60)

    for prompt, served in answers.items():
        ids = tokenizer.encode(prompt)
        assert len(served) == 14 and len(ids) + 14 > CFG.index_topk
        gaps = reference.served_gaps(as_file(CFG), plain, ids, served)["served_tokens"]
        assert gaps.max() < TOL, (prompt, gaps)
        # the JSON route serves the same greedy tokens
        assert texts[prompt]["usage"]["completion_tokens"] == 14 and texts[prompt]["text"] == tokenizer.decode(served)
    commits = [kw for phase, kw in seen if phase == "commit" and "dsa_scored" in kw]
    assert any(kw["moe_rows"] for kw in commits) and all(0 <= kw["moe_max"] <= kw["moe_rows"] for kw in commits)
    # a live row's step routes it to top_k experts in each of the 2 expert layers, all held here,
    # and reads at most index_topk positions in each of the 3 layers, of those it scored
    assert all(kw["moe_rows"] % (2 * CFG.top_k) == 0 for kw in commits)
    # 3 slots x 4 of 16 experts: the product groups the rows and counts the experts it read on the device —
    # one request at a time, so a live row's 4 experts a layer and no other
    assert all(kw["moe_reached"] == kw["moe_rows"] for kw in commits)
    assert all(0 <= kw["dsa_selected"] <= kw["dsa_scored"] for kw in commits)
    assert any(0 < kw["dsa_selected"] < kw["dsa_scored"] for kw in commits)  # the selection binds
    assert all(kw["dsa_selected"] * 2 * CFG.top_k <= kw["moe_rows"] * 3 * CFG.index_topk for kw in commits)
    bound = [kw["dsa_rows"] for phase, kw in seen if phase == "dispatch" and "dsa_rows" in kw]
    assert bound and max(bound) >= 1  # rows decode past 8 positions
    # the chunked prompt's three chunks (16 tokens, 8 a page, 64 a slot) end at 16, 32, 48: they read 16, 32, 64
    ctx = [kw["chunk_ctx"] for phase, kw in seen if phase == "dispatch" and "chunk_ctx" in kw]
    assert ds.chunk_contexts(16, 8, 64) == (16, 32, 64) and ctx and sorted(set(ctx)) == [16, 32, 64], ctx
    for name, key, label in (("app_moe_expert_rows_total", "moe_rows", 'expert="'),
                             ("app_moe_experts_read_total", "moe_reached", ""),
                             ("app_dsa_positions_total", None, 'kind="')):
        counted = [line for line in metrics.splitlines() if line.startswith((name + "{", name + " "))]
        assert counted and all(label in line for line in counted)
        total = sum(float(line.rsplit(" ", 1)[1]) for line in counted)
        assert total == sum(kw[key] if key else kw["dsa_scored"] + kw["dsa_selected"] for kw in commits)
