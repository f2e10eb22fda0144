"""JoyAI-LLM-Flash (the DeepSeek-V3 layer) on the serving path:
``models/deepseek_v32.py`` with no indexer and plain rotary frequencies, at
small widths with seeded random weights (hidden 64, 4 heads of 16 nope + 8
rope over a latent of 32, one dense layer and two expert layers of 16
experts with 4 a token, no group limit, one shared), against the plain
reference the benchmark decides ``correct`` with
(``benchmarks/harness/joyai_flash_reference.py``: float32, ``highest``, the
expanded form over every position, nothing of the program); and its decode
kernel, ``ops/latent_attention.paged_latent_attention``, in the Pallas
interpreter against ``mla.latent_attention`` over ``mla.row_pages``.

``TOL`` = 2e-3 as in ``tests/test_deepseek_v32.py``: program and reference
compute the same float32 mathematics in another order (the absorbed form in
decode and chunks against the expanded one), which reads under 1e-5 here;
the same weights in int4 move logits by more than 0.1.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import lowered_digests
from benchmarks.harness import joyai_flash_reference as reference
from gofr_tpu.models import deepseek_v32 as ds
from gofr_tpu.ops import latent_attention as la
from gofr_tpu.ops import mla
from gofr_tpu.ops import moe as moe_ops
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving import batch as batch_ops
from gofr_tpu.serving.kv_cache import PagedKVCache

TOL = 2e-3
PAGE = 4
CFG = ds.DeepseekV32Config.tiny(vocab_size=300, index_n_heads=0, index_head_dim=0, index_topk=0, n_group=1,
                                topk_group=1, rope_factor=1.0, rope_theta=3.2e7)


def as_file(cfg, first=0):
    """The configuration file's keys for a program config: what the
    reference reads."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads, "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_dense_layers, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "num_experts_per_tok": cfg.top_k, "n_group": cfg.n_group, "topk_group": cfg.topk_group,
        "routed_scaling_factor": cfg.routed_scaling, "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "rope_scaling": None, "deployment": {"first_expert": first},
    }


@pytest.fixture(scope="module")
def plain():
    return ds.init_params(CFG, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def int8(plain):
    return ds.quantize_params(plain)


def ids_of(n, seed=3):
    return np.asarray([1] + list(np.random.default_rng(seed).integers(3, 259, n - 1)), np.int32)


def pool_and_tables(cfg, slots, pages_per_slot):
    """An empty latent pool and block tables that give every slot its own
    pages, in an order that is not the identity."""
    n = slots * pages_per_slot
    page, second = ds.page_shapes(cfg, PAGE)
    assert second is None
    tables = np.random.default_rng(1).permutation(n).reshape(slots, pages_per_slot).astype(np.int32)
    return jnp.zeros((cfg.n_layers, n + 1) + page, cfg.dtype), jnp.asarray(tables)


def serve_through_the_cache(cfg, params, ids, n_prompt, bucket):
    """Bucketed prefill of the first ``n_prompt`` tokens, then the rest one
    decode step at a time through the pool (teacher-forced, row 0 of two,
    row 1 idle): the logits at positions n_prompt-1 .. len(ids)-1, and each
    step's counters."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :n_prompt] = ids[:n_prompt]
    last, k_slab, v_slab = batch_ops.prefill_compute(cfg, params, jnp.asarray(tokens), jnp.asarray([n_prompt]))
    assert v_slab is None
    kp, tables = pool_and_tables(cfg, 2, 16)
    for t in range(n_prompt):
        kp = kp.at[:, tables[0, t // PAGE], :, t % PAGE].set(k_slab[:, t])
    out, counted = [np.asarray(last[0])], []
    for pos in range(n_prompt, len(ids)):
        logits, kp, vp, stats = ds.decode_step_paged(
            cfg, params, jnp.asarray([ids[pos], 0]), kp, None, tables,
            jnp.asarray([pos + 1, 1]), jnp.asarray([True, False]))
        assert vp is None
        out.append(np.asarray(logits[0]))
        counted.append(np.asarray(stats))
    return np.stack(out), np.stack(counted)


def chunked(cfg, params, ids, chunk):
    """The whole sequence through ``decode_chunk_paged``, ``chunk`` tokens
    a dispatch, in row 1 of three (rows 0 and 2 have no chunk): the logits
    the program returns, each chunk's at its last token, and where those
    tokens stand."""
    kp, tables = pool_and_tables(cfg, 3, 16)
    out, at = [], []
    for start in range(0, len(ids), chunk):
        piece = np.full((3, chunk), -1, np.int32)
        n = min(chunk, len(ids) - start)
        piece[1, :n] = ids[start:start + n]
        logits, kp, vp = ds.decode_chunk_paged(
            cfg, params, jnp.asarray(piece), kp, None, tables, jnp.asarray([64, start, 64]),
            jnp.asarray([False, True, False]), jnp.asarray([0, 64, 0]))
        assert logits.shape == (3, 1, cfg.vocab_size)  # the head at each row's last token alone
        assert vp is None and not np.asarray(logits[0]).any() and not np.asarray(logits[2]).any()
        out.append(np.asarray(logits[1, 0]))
        at.append(start + n - 1)
    return np.stack(out), at


# ------------------------------------------------------------- the kernel
# lengths a case of six rows holds: 8 positions a block at two pages, and the
# entry's block here is the whole table (6 pages, 24 positions)
LENGTHS = {
    # an empty slot, one position, a partial last page, a block's end, across a block boundary, the whole table
    "ragged": [0, 1, 9, 16, 17, 24],
    "ends-at-a-block": [8, 16, 24, 24, 16, 8],  # full blocks alone at two pages a block
    "one-into-a-block": [9, 17, 1, 9, 17, 1],
    "whole-tables": [24] * 6,  # every block full at both sizes
    "empty-and-one": [0, 1, 0, 1, 1, 0],
}
# the TPU interpreter that lands a DMA only when a wait on its semaphore
# needs its bytes, into buffers that start as NaN, and prints a semaphore
# left with a count at the kernel's exit
ON_WAIT = pltpu.InterpretParams(dma_execution_mode="on_wait", uninitialized_memory="nan")


def kernel_case(dtype=jnp.bfloat16, lengths="ragged"):
    L, B, H, W, R, M = 2, 6, 4, 128, 96, 6
    n = B * M
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = jax.random.normal(ks[0], (L, n + 1, 1, PAGE, W), jnp.float32).astype(dtype)
    q = jax.random.normal(ks[1], (B, H, W), jnp.float32).astype(dtype)
    tables = jnp.asarray(np.random.default_rng(3).permutation(n).reshape(B, M), jnp.int32)
    return pool, q, tables, jnp.asarray(LENGTHS[lengths]), R


def interpreted(q, pool, tables, lens, layer, R, interpret=True):
    """The kernel's call, un-jitted, in the Pallas interpreter."""
    return la._call(q, pool, tables, lens, layer, 0.3, R, interpret)


def poisoned(pool, tables, lens):
    """The pool with NaN in every page no row holds below its length, and
    in the other layer."""
    out = np.full(pool.shape, np.nan, np.asarray(pool).dtype)
    for b, n in enumerate(np.asarray(lens)):
        for j in range(max(1, -(-int(n) // PAGE))):  # a row of length 0 is read as one of length 1
            page = int(tables[b, j])
            out[1, page] = np.asarray(pool)[1, page]
    return jnp.asarray(out)


def committed_start(pool_hbm, tables_ref, buf, sem, layer, row, first, n, slot):
    """The fetch the kernel had before one wait a block, kept as the
    oracle of the fetch: a loop of dynamic length starts a DMA a page ..."""
    def one(j, _):
        pid = tables_ref[row, first + j]
        pltpu.make_async_copy(pool_hbm.at[layer, pid], buf.at[slot, j], sem.at[slot]).start()
        return _
    jax.lax.fori_loop(0, n, one, None)


def committed_land(pool_hbm, buf, sem, n, slot):
    """... another waits for them page by page, a third zeroes the rest."""
    def wait(j, _):
        pltpu.make_async_copy(pool_hbm.at[0, 0], buf.at[slot, j], sem.at[slot]).wait()
        return _
    jax.lax.fori_loop(0, n, wait, None)

    def clear(j, _):
        buf[slot, j] = jnp.zeros(buf.shape[2:], buf.dtype)
        return _
    jax.lax.fori_loop(n, buf.shape[1], clear, None)


@pytest.fixture
def blocks_of_two_pages(monkeypatch):
    """The kernel's block at two pages: the entry's 512 positions would
    hold every row here in one block."""
    monkeypatch.setattr(la, "_BLOCK_TOKENS", 2 * PAGE)


@pytest.mark.parametrize("blocks,lengths", [
    pytest.param(blocks, lengths, id=blocks if lengths == "ragged" else f"{blocks}-{lengths}")
    for lengths in LENGTHS for blocks in ("blocks-of-two-pages", "the-entry-s-block")])
def test_the_kernel_is_latent_attention_over_the_row_s_pages_at_ragged_lengths(blocks, lengths, request,
                                                                               monkeypatch, capsys):
    """In the interpreter: every length against the oracle computed in
    float32 (the kernel's P·V is a float32 product; the oracle's bf16
    weights are not); and in ``ON_WAIT`` bit for bit the committed
    fetch's output (``committed_start``, ``committed_land``), over the
    pool as it is and over the pool with every page the row does not own
    up to its length poisoned. ``ON_WAIT`` honours a wait described as a
    whole slot after a DMA a page: it lands the DMAs queued on the slot's
    semaphore until the wait's bytes are counted, so a wait that covered
    fewer pages than were started would leave a page NaN, or another
    block's rows, when the block is computed; and it counts no semaphore
    left over at the kernel's exit."""
    if blocks == "blocks-of-two-pages":
        request.getfixturevalue("blocks_of_two_pages")
    pool, q, tables, lens, R = kernel_case(lengths=lengths)
    want = la.paged_latent_attention_ref(q.astype(jnp.float32), pool.astype(jnp.float32), tables, lens, jnp.int32(1),
                                         scale=0.3, kv_lora_rank=R)
    got = interpreted(q, pool, tables, lens, jnp.int32(1), R)
    assert got.shape == (6, 4, R) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got - want)).max() < 2e-5 and np.abs(np.asarray(want)).max() > 0.5
    assert not np.asarray(got)[np.asarray(lens) == 0].any()  # a row of length 0 sums nothing
    # the other layer's pages are other numbers
    assert np.abs(np.asarray(interpreted(q, pool, tables, lens, jnp.int32(0), R) - got)).max() > 0.1
    if blocks == "the-entry-s-block":  # the jitted entry in the interpreter is the same call
        entry = la.paged_latent_attention(q, pool, tables, lens, jnp.int32(1), scale=0.3, kv_lora_rank=R, interpret=True)
        assert np.abs(np.asarray(entry - want)).max() < 2e-5

    with monkeypatch.context() as m:
        m.setattr(la, "_start_pages", committed_start)
        m.setattr(la, "_land_pages", committed_land)
        committed = np.asarray(interpreted(q, pool, tables, lens, jnp.int32(1), R, ON_WAIT))
    assert np.array_equal(np.asarray(interpreted(q, pool, tables, lens, jnp.int32(1), R, ON_WAIT)), committed)
    unowned = np.asarray(interpreted(q, poisoned(pool, tables, lens), tables, lens, jnp.int32(1), R, ON_WAIT))
    assert np.array_equal(unowned, committed)
    assert "non-zero count" not in capsys.readouterr().out


def test_a_pool_of_fewer_pages_than_a_table_holds_is_read_in_blocks_of_the_pool_s_pages():
    """A pool of 4 pages and the trash page under tables of 6 entries
    (rows share pages), at the entry's block: a block holds no more pages
    than the pool (5 here), since a run of the pool's pages describes the
    whole slot's wait — a bound that neither the interpreter nor the
    chip's compiler checks for a wait."""
    pool, q, tables, lens, R = kernel_case()
    pool = pool[:, :5]
    tables = jnp.asarray(np.random.default_rng(5).integers(0, 4, tables.shape), jnp.int32)
    want = la.paged_latent_attention_ref(q.astype(jnp.float32), pool.astype(jnp.float32), tables, lens, jnp.int32(1),
                                         scale=0.3, kv_lora_rank=R)
    got = interpreted(q, pool, tables, lens, jnp.int32(1), R, ON_WAIT)
    assert np.abs(np.asarray(got - want)).max() < 2e-5 and np.abs(np.asarray(want)).max() > 0.5


def test_the_kernel_reads_the_pages_a_row_owns_up_to_its_length_alone(blocks_of_two_pages):
    """Every page of the pool that no row holds below its length is NaN,
    and the pool's other layer too: the kernel never reads them (a stale
    NaN in a page past the row's last would poison the sum even under a
    zero weight). The CPU entry is the oracle."""
    pool, q, tables, lens, R = kernel_case(jnp.float32)
    got = interpreted(q, poisoned(pool, tables, lens), tables, lens, jnp.int32(1), R)
    want = la.paged_latent_attention(q, pool, tables, lens, jnp.int32(1), scale=0.3, kv_lora_rank=R)
    assert np.isfinite(np.asarray(got)).all() and np.abs(np.asarray(got - want)).max() < 2e-5
    # on the CPU the entry is mla.latent_attention over row_pages under the length's mask
    rows = mla.row_pages(pool, tables, jnp.int32(1))
    keep = (jnp.arange(rows.shape[1])[None] < lens[:, None])[:, None]
    direct = mla.latent_attention(q[:, None], rows, keep, 0.3, R)[:, 0]
    assert np.abs(np.asarray(want - direct)).max() == 0.0


# ---------------------------------------------------- against the reference
@pytest.mark.parametrize("weights", ["plain", "int8"])
def test_prefill_then_decode_over_every_position_agrees_with_the_reference(weights, request):
    params = request.getfixturevalue(weights)
    ids = ids_of(40)
    want = np.asarray(reference.logits(as_file(CFG), params, ids))
    got, counted = serve_through_the_cache(CFG, params, ids, n_prompt=12, bucket=16)
    assert got.shape == (29, 300) and np.abs(want).max() > 2
    assert np.abs(got - want[11:]).max() < TOL
    # each step: the live row routed to 4 of the 16 experts in each of 2 expert layers (all held: 2 rows x 4 of
    # 16 is the grouped product, which reads the 4 a layer); attention read the row's whole context in each of
    # the 3 layers (mla_kv), for one live row a layer (mla_rows)
    assert counted.shape == (28, 16 + 1 + 2)
    assert (counted[:, :16].sum(axis=1) == 2 * 4).all() and (counted[:, 16] == 2 * 4).all()
    assert (counted[:, 17] == 3 * np.arange(13, 41)).all() and (counted[:, 18] == 3).all()


@pytest.mark.parametrize("chunk", [12, 6], ids=["chunks-of-12-the-loop-over-every-row", "chunks-of-6-the-grouped-product"])
@pytest.mark.parametrize("weights", ["plain", "int8"])
def test_chunked_prefill_over_bucketed_contexts_agrees_with_the_reference(weights, chunk, request):
    """Chunks end at 12, 24, 36, 40 (or every 6): the context a chunk reads
    is the smallest of 12, 24, 48, 64 (or 8, 16, 32, 64) that holds its
    end — every bucket is taken, and each agrees."""
    params = request.getfixturevalue(weights)
    ids = ids_of(40, seed=4)
    want = np.asarray(reference.logits(as_file(CFG), params, ids))
    assert moe_ops.groups_rows(chunk, CFG.n_experts, CFG.top_k) is (chunk == 6)
    got, at = chunked(CFG, params, ids, chunk)
    assert at == list(range(chunk - 1, 40, chunk)) + ([39] if 40 % chunk else [])
    assert np.abs(got - want[at]).max() < TOL
    # a prompt chunked, then decoded from the pool the chunks wrote: every position after the prompt
    kp, tables = pool_and_tables(CFG, 1, 16)
    for start in (0, 12):
        _, kp, _ = ds.decode_chunk_paged(CFG, params, jnp.asarray(ids[None, start:start + 12]), kp, None, tables,
                                         jnp.asarray([start]), jnp.asarray([True]), jnp.asarray([64]))
    for pos in range(24, 40):
        logits, kp, _, _ = ds.decode_step_paged(CFG, params, jnp.asarray([ids[pos]]), kp, None, tables,
                                                jnp.asarray([pos + 1]), jnp.asarray([True]))
        assert np.abs(np.asarray(logits[0]) - want[pos]).max() < TOL


def test_a_chunk_reads_the_pages_up_to_its_end_alone():
    """Pages of the row's table past the bucket that holds the chunk's end
    are NaN: the chunk's attention does not gather them."""
    ids = ids_of(12)
    kp, tables = pool_and_tables(CFG, 1, 16)
    owned = np.asarray(tables[0])
    poisoned = kp.at[:, owned[4:]].set(jnp.nan)  # positions 16 on: beyond the 12-position bucket
    piece = jnp.asarray(ids[None])
    args = (tables, jnp.asarray([0]), jnp.asarray([True]), jnp.asarray([64]))
    params = ds.init_params(CFG, jax.random.PRNGKey(7))
    clean, *_ = ds.decode_chunk_paged(CFG, params, piece, kp, None, *args)
    got, *_ = ds.decode_chunk_paged(CFG, params, piece, poisoned, None, *args)
    assert np.isfinite(np.asarray(got)).all() and np.abs(np.asarray(got - clean)).max() == 0.0


def test_the_int4_control_fails_the_same_tolerance(int8):
    ids = ids_of(40)
    got, _ = serve_through_the_cache(CFG, int8, ids, n_prompt=12, bucket=16)
    control = np.asarray(reference.logits(as_file(CFG), int8, ids, weight_bits=4))
    assert np.abs(got - control[11:]).max() > 50 * TOL


def test_plain_frequencies_and_the_scale_without_rope_scaling():
    """No rope_scaling: theta^(-2i/d) and the scale (nope + rope)^-1/2."""
    from gofr_tpu.ops.rope import rope_angles

    freqs = reference.frequencies(64, 3.2e7)
    assert np.allclose(freqs, 3.2e7 ** (-np.arange(32) / 32), rtol=1e-6)
    sin, _ = rope_angles(jnp.asarray([[5]]), 64, 3.2e7)
    assert np.allclose(np.asarray(sin)[0, 0], np.sin(5 * freqs), atol=1e-6)
    cfg = ds.DeepseekV32Config(index_n_heads=0, index_head_dim=0, index_topk=0, rope_factor=1.0)
    assert cfg.softmax_scale == 192 ** -0.5 == reference.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64})
    with pytest.raises(ValueError, match="rope_scaling"):
        reference.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_scaling": {"factor": 40}})


# ---------------------------------------------------------------- the share
def test_the_eight_shares_add_up_to_the_uncut_layer(plain):
    """Eight chips hold 2 of the 16 experts each: their parts, with the
    shared expert counted once, are the whole layer's routed + shared sum,
    as the reference computes it uncut. The choice has no group limit."""
    lp = jax.tree.map(lambda a: a[1], plain["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.d_model), jnp.float32)
    gates = moe_ops.sigmoid_topk_gates(h, lp["w_router"], CFG.top_k, bias=lp["router_bias"], n_group=1,
                                       topk_group=1, scale=CFG.routed_scaling)
    chosen = np.asarray(gates > 0)
    assert (chosen.sum(axis=1) == CFG.top_k).all() and np.allclose(gates.sum(axis=1), CFG.routed_scaling, atol=1e-5)
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
    g_ref = reference.gates(sigma, lp["router_bias"], CFG.top_k, 1, 1, CFG.routed_scaling)
    uncut = reference._ffn_sum(h, lp["experts"], g_ref.T, 8) + reference._ffn_sum(
        h, lp["shared"], jnp.ones((CFG.n_shared, 24)), 8)
    assert np.abs(whole - uncut).max() < 1e-4 and np.abs(uncut).max() > 0.1


def test_a_share_of_the_model_is_the_reference_given_the_same_share(plain):
    """Experts 8..11 held and rows 0..199 of the vocabulary: program and
    reference leave out the same part, and differ from the whole model."""
    cfg = ds.DeepseekV32Config.tiny(vocab_size=200, held_experts=4, first_expert=8, index_n_heads=0, index_head_dim=0,
                                    index_topk=0, n_group=1, topk_group=1, rope_factor=1.0, rope_theta=3.2e7)
    moe = dict(plain["moe"], experts=jax.tree.map(lambda a: a[:, 8:12], plain["moe"]["experts"]))
    share = dict(plain, moe=moe, embedding=plain["embedding"][:200], lm_head=plain["lm_head"][:, :200])
    ids = np.minimum(ids_of(24), 199)
    got, counted = serve_through_the_cache(cfg, share, ids, 12, 16)
    want = np.asarray(reference.logits(as_file(cfg, first=8), share, ids))
    assert got.shape[1] == 200 and np.abs(got - want[11:]).max() < TOL
    whole = np.asarray(reference.logits(as_file(CFG), plain, ids))[11:, :200]
    assert np.abs(got - whole).max() > 10 * TOL
    assert counted.shape[1] == 4 + 1 + 2 and 0 < counted[:, :4].sum() < 12 * 2 * 4


# ----------------------------------------------------------------- the pager
def test_the_pager_keeps_one_latent_pool_and_no_second():
    """``page_shapes`` answers None for the second pool: the pager holds
    none, and a slab goes in and comes out with a V slab of None."""
    cache = PagedKVCache(CFG, num_pages=6, page_size=8, max_slots=2, max_seq_len=24,
                         page_shapes=ds.page_shapes(CFG, 8))
    assert cache.k_pool.shape == (3, 7, 1, 8, 128) and cache.v_pool is None
    k = jax.random.normal(jax.random.PRNGKey(0), (3, 13, 1, 128), jnp.float32)
    cache.alloc_slot(1, seq_id=5, prompt_len=13)
    cache.write_prefill(1, k, None)
    back_k, back_v = cache.read_span(1, 0, 13)
    assert bool(jnp.all(back_k == k)) and back_v is None
    cache.write_span(1, 8, k[:, :5], None)
    back_k, _ = cache.read_span(1, 8, 13)
    assert bool(jnp.all(back_k == k[:, :5]))
    cache.close()
    assert ds.KVCache.create(CFG, 2, 16).v is None and ds.step_stats_len(CFG) == 16 + 1 + 2


def engine_settings(**kw):
    settings = dict(max_slots=3, max_seq_len=64, prefill_buckets=(16,), multi_step=4,
                    kv_layout="paged", kv_page_size=8, prefill_chunk_tokens=16, prefix_cache_entries=0)
    settings.update(kw)
    return EngineConfig(**settings)


@pytest.mark.parametrize("settings, lora, sentence", [
    (dict(kv_layout="dense"), None, "paged KV layout only"),
    (dict(spec_tokens=2, multi_step=None), None, "multi-token-prediction module is not served"),
    (dict(), object(), "serves no LoRA adapters"),
    (dict(kv_spill_bytes=1 << 20), None, "TPU_KV_SPILL_BYTES=0"),
    (dict(role="prefill"), None, "unified replicas"),
], ids=["dense", "speculative", "lora", "spill", "disaggregated"])
def test_engines_the_model_has_no_program_for_are_refused_at_construction(plain, settings, lora, sentence):
    with pytest.raises(ValueError, match=sentence):
        ServingEngine(CFG, plain, engine_settings(**settings), ByteTokenizer(300), lora=lora)


def test_the_five_families_keep_their_programs_and_the_new_one_lowers_as_recorded():
    """Each served family's lowering at tiny widths hashes as it did before
    this model came (``tests/test_lfm2_moe.py`` holds the five, V3.2's
    among them, to the same digests); the new one's as recorded here. A PR
    that changes them on purpose records them again
    (``tests/lowered_digests.py``)."""
    assert lowered_digests.digests("joyai_llm_flash") == JOYAI_DIGESTS


def test_a_repeated_prompt_is_served_from_the_prefix_cache_of_one_pool(plain):
    """The prefix cache holds (logits, latent slab, None) for a prompt of
    one pool: the second request skips its prefill and serves the same
    greedy tokens, both the reference's."""
    engine = ServingEngine(CFG, plain, engine_settings(prefix_cache_entries=4), ByteTokenizer(300))
    engine.start()
    try:
        first = engine.submit("the same prompt", max_new_tokens=10, temperature=0.0).result(timeout=300)
        second = engine.submit("the same prompt", max_new_tokens=10, temperature=0.0).result(timeout=300)
        stats = engine._prefix_cache.stats()
    finally:
        engine.stop()
    assert first.token_ids == second.token_ids and len(first.token_ids) == 10
    assert stats["hits"] >= 1
    ids = ByteTokenizer(300).encode("the same prompt")
    assert reference.served_gaps(as_file(CFG), plain, ids, list(first.token_ids))["served_tokens"].max() < TOL


# -------------------------------------------------- the engine and the App
def test_the_model_is_served_behind_an_app_over_http_with_its_counters(plain, monkeypatch):
    """POST /generate/stream through a real App, a bucketed and a chunked
    prompt: the tokens are the reference's greedy choice, and the commit
    spans carry ``mla_kv`` and ``mla_rows`` (the positions attention read
    and the live rows, over the 3 layers) beside ``moe_rows``, ``moe_max``
    and ``moe_reached``, and no indexer's counters; the chunks' dispatch
    spans carry ``chunk_ctx``, the context each chunk read."""
    import gofr_tpu
    from gofr_tpu.config import MapConfig
    from gofr_tpu.serving import engine as engine_mod
    from gofr_tpu.serving.handlers import register_generation_routes
    from gofr_tpu.testutil import get_free_port

    http_port, metrics_port = get_free_port(), get_free_port()
    app = gofr_tpu.App(MapConfig({"HTTP_PORT": str(http_port), "METRICS_PORT": str(metrics_port),
                                  "APP_NAME": "joyai-test", "LOG_LEVEL": "WARN"}, use_env=False))
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
    try:
        deadline = time.monotonic() + 60
        while True:
            try:
                urllib.request.urlopen(base + "/.well-known/alive", timeout=1).close()
                break
            except OSError:
                assert time.monotonic() < deadline and thread.is_alive()
                time.sleep(0.05)
        answers = {}
        for prompt in ("a short one", "a prompt of three chunks, and a tail "):
            req = urllib.request.Request(base + "/generate/stream", method="POST",
                                         data=json.dumps({"prompt": prompt, "max_tokens": 14, "temperature": 0.0}).encode(),
                                         headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                frames = [json.loads(line[6:]) for line in resp.read().decode().splitlines() if line.startswith("data: {")]
            answers[prompt] = [f["token"] for f in frames if "token" in f]
    finally:
        app.stop()
        thread.join(timeout=60)

    for prompt, served in answers.items():
        ids = tokenizer.encode(prompt)
        assert len(served) == 14
        gaps = reference.served_gaps(as_file(CFG), plain, ids, served)["served_tokens"]
        assert gaps.max() < TOL, (prompt, gaps)
    commits = [kw for phase, kw in seen if phase == "commit" and "mla_kv" in kw]
    assert commits and not any("dsa_scored" in kw for phase, kw in seen)
    assert any(kw["moe_rows"] for kw in commits) and all(0 <= kw["moe_max"] <= kw["moe_rows"] for kw in commits)
    # a live row-step is 3 layers' rows, routed to top_k experts in each of the 2 expert layers, and reads
    # its context (at least one position, at most the slot) in each of the 3 layers
    assert all(kw["mla_rows"] % 3 == 0 and kw["moe_rows"] == kw["mla_rows"] // 3 * 2 * CFG.top_k for kw in commits)
    assert all(kw["mla_rows"] <= kw["mla_kv"] <= kw["mla_rows"] * 64 for kw in commits)
    assert sum(kw["mla_rows"] for kw in commits) > 0
    # the chunked prompt's three chunks (16 tokens, 8 a page, 64 a slot) read 16, 32 and 64 positions
    ctx = [kw["chunk_ctx"] for phase, kw in seen if phase == "dispatch" and "chunk_ctx" in kw]
    assert sorted(ctx) == [16, 32, 64], ctx


JOYAI_DIGESTS = {
    "decode_block_paged": "8d5ea1972633b5f37cb4f6d9adb560290679ece923b217e0ca2d62486ba32a12",
    "prefill_compute[16]": "a1197d3f284ffacb8e9076469d5e88b30d359b39e1557dfae86c50e8dd4c834e",
    "ragged_step_paged": "cfdee0e4a6c87dc4d9189b21c7ffa3f00f54eba64ba9c3dda97f5cd25a4f9d38",
}
