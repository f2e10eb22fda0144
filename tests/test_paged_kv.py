"""Paged KV cache + paged decode attention: the paged path must produce
bit-comparable results to the dense KVCache path it replaces, with the
Pallas kernel (interpret mode on CPU) matching the XLA reference."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from committed_fetch import ON_WAIT, committed_land, committed_start
from gofr_tpu.models import llama
from gofr_tpu.ops import paged_attention as pa
from gofr_tpu.ops.attention import decode_attention
from gofr_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_ref,
)
from gofr_tpu.serving.kv_cache import OutOfBlocks, PagedKVCache


def _random_pool(key, B, S, H, Hkv, Dh, page):
    """Build dense K/V plus the equivalent paged pool + tables."""
    kk, kv, kq = jax.random.split(key, 3)
    k = jax.random.normal(kk, (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(kv, (B, S, Hkv, Dh), jnp.float32)
    q = jax.random.normal(kq, (B, H, Dh), jnp.float32)
    M = S // page
    n_pages = B * M + 1  # page 0 reserved/garbage to catch off-by-one
    k_pool = np.zeros((n_pages, Hkv, page, Dh), np.float32)
    v_pool = np.zeros((n_pages, Hkv, page, Dh), np.float32)
    tables = np.zeros((B, M), np.int32)
    nxt = 1
    for b in range(B):
        for m in range(M):
            k_pool[nxt] = np.asarray(k[b, m * page:(m + 1) * page]).transpose(1, 0, 2)
            v_pool[nxt] = np.asarray(v[b, m * page:(m + 1) * page]).transpose(1, 0, 2)
            tables[b, m] = nxt
            nxt += 1
    return q, k, v, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(tables)


class TestPagedAttentionOps:
    def test_ref_matches_dense_decode_attention(self):
        B, S, H, Hkv, Dh, page = 3, 32, 4, 2, 16, 8
        q, k, v, k_pool, v_pool, tables = _random_pool(
            jax.random.PRNGKey(0), B, S, H, Hkv, Dh, page
        )
        seq_lens = jnp.array([5, 32, 17], jnp.int32)
        out_ref = paged_decode_attention_ref(q, k_pool, v_pool, tables, seq_lens)
        dense = decode_attention(q[:, None], k, v, seq_lens)[:, 0]
        np.testing.assert_allclose(np.asarray(out_ref), np.asarray(dense),
                                   rtol=2e-5, atol=2e-5)

    def test_kernel_matches_ref(self):
        B, S, H, Hkv, Dh, page = 2, 64, 8, 4, 32, 16
        q, _, _, k_pool, v_pool, tables = _random_pool(
            jax.random.PRNGKey(1), B, S, H, Hkv, Dh, page
        )
        seq_lens = jnp.array([64, 23], jnp.int32)
        ref = paged_decode_attention_ref(q, k_pool, v_pool, tables, seq_lens)
        out = paged_decode_attention(q, k_pool, v_pool, tables, seq_lens,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_kernel_single_token_sequence(self):
        B, S, H, Hkv, Dh, page = 2, 16, 4, 4, 16, 8
        q, _, _, k_pool, v_pool, tables = _random_pool(
            jax.random.PRNGKey(2), B, S, H, Hkv, Dh, page
        )
        seq_lens = jnp.array([1, 2], jnp.int32)
        ref = paged_decode_attention_ref(q, k_pool, v_pool, tables, seq_lens)
        out = paged_decode_attention(q, k_pool, v_pool, tables, seq_lens,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def _paged_case(heads, max_len, seed=0):
    """Served types at a small width: bf16 queries over a bf16 pool of
    16-token pages, tables ``max_len`` tokens wide. Returns (page, q,
    one layer's k and v pools, tables)."""
    Hkv, group = heads
    page = 16
    Dh, B, M = 32, 8, max_len // page
    N = B * M + 1
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, Hkv * group, Dh), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (N, Hkv, page, Dh), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (N, Hkv, page, Dh), jnp.bfloat16)
    tables = jnp.asarray(
        np.random.default_rng(seed).permutation(N - 1).reshape(B, M), jnp.int32
    )
    return page, q, k_pool, v_pool, tables


class TestPagedKernelShapes:
    """The loop kernel against the gather reference over both head classes
    (grouped: Hkv 8 x 4 queries; full multi-head: Hkv 4 x 1) and both forms
    of the call, each with rows that end everywhere a block can end."""

    @pytest.mark.parametrize("form", ["one_layer", "whole_pools"])
    @pytest.mark.parametrize("heads", [(8, 4), (4, 1)], ids=["gqa8x4", "mha4x1"])
    def test_kernel_matches_ref_ragged(self, heads, form):
        """``one_layer``: a layer's pool and no layer index. ``whole_pools``:
        as the decode step calls it — the pools of two layers, the other
        one NaN, and a traced layer index."""
        from gofr_tpu.ops.paged_attention import _pages_per_block

        page, q, k_pool, v_pool, tables = _paged_case(heads, max_len=320)
        M = tables.shape[1]
        block = page * _pages_per_block(heads[0], page, 32, k_pool.dtype.itemsize, M)
        assert block == 128 and M * page > 2 * block  # several blocks a row
        # an empty slot, one page exactly, a page boundary - 1 and + 1, a
        # block boundary and + 1, a length no multiple of the block, the
        # whole table
        seq_lens = jnp.array(
            [1, page, page - 1, page + 1, block, block + 1, 200, M * page], jnp.int32
        )
        ref = paged_decode_attention_ref(q, k_pool, v_pool, tables, seq_lens)
        if form == "one_layer":
            out = paged_decode_attention(q, k_pool, v_pool, tables, seq_lens, interpret=True)
        else:
            kw = jnp.stack([jnp.full_like(k_pool, jnp.nan), k_pool])
            vw = jnp.stack([jnp.full_like(v_pool, jnp.nan), v_pool])
            out = jax.jit(
                lambda layer: paged_decode_attention(
                    q, kw, vw, tables, seq_lens, interpret=True, layer=layer)
            )(jnp.int32(1))
            by_ref = paged_decode_attention_ref(q, kw, vw, tables, seq_lens, layer=1)
            assert jnp.array_equal(by_ref, ref)  # the reference reads that layer too
        assert out.dtype == ref.dtype == jnp.bfloat16
        # two roundings of a bf16 result (8 bits) of magnitude < 4
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
        )

    def test_unowned_pages_are_never_read(self):
        """NaN in every page no row owns, the pages that unused block-table
        entries point at among them, and (TPU interpret mode) in VMEM the
        kernel has not written: the result is the clean pool's."""
        from jax.experimental.pallas import tpu as pltpu

        from gofr_tpu.ops.paged_attention import _paged_attention_call

        B, S, H, Hkv, Dh, page = 3, 192, 4, 2, 16, 8
        q, _, _, k_pool, v_pool, tables = _random_pool(
            jax.random.PRNGKey(3), B, S, H, Hkv, Dh, page
        )
        lens = [1, 70, 129]  # 1, 9 and 17 of 24 pages owned
        seq_lens = jnp.array(lens, jnp.int32)
        ref = paged_decode_attention_ref(q, k_pool, v_pool, tables, seq_lens)

        owned = np.zeros(k_pool.shape[0], bool)
        for b, n in enumerate(lens):
            owned[np.asarray(tables)[b, : -(-n // page)]] = True
        assert not owned[0] and owned.sum() == 1 + 9 + 17
        poison = jnp.asarray(~owned)[:, None, None, None]
        k_bad = jnp.where(poison, jnp.nan, k_pool)
        v_bad = jnp.where(poison, jnp.nan, v_pool)
        out = _paged_attention_call(
            q, k_bad, v_bad, tables, seq_lens, 1.0 / np.sqrt(Dh),
            pltpu.InterpretParams(),
        )
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grid_has_no_page_axis(self):
        """One program a row: the page loop is inside the kernel, so the
        grid does not grow with the block table's width."""
        B, M, page = 3, 24, 8
        q, _, _, k_pool, v_pool, tables = _random_pool(
            jax.random.PRNGKey(4), B, M * page, 4, 2, 16, page
        )
        jaxpr = jax.make_jaxpr(
            lambda *a: paged_decode_attention(*a, interpret=True)
        )(q, k_pool, v_pool, tables, jnp.full((B,), 5, jnp.int32))

        def calls(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from calls(sub)

        (call,) = calls(jaxpr.jaxpr)
        grid = tuple(call.params["grid_mapping"].grid)
        assert grid == (B,) and M not in grid


# ------------------------------------------------------------- the fetch
PAGE = 4
# six rows over tables of six pages (24 positions): a block holds 8
# positions at two pages, and the whole table at the entry's block
FETCH_LENGTHS = {
    "0": lambda bk: [0] * 6,
    "1": lambda bk: [1] * 6,
    "a-block": lambda bk: [bk] * 6,  # every block whole
    "one-past-a-block": lambda bk: [bk + 1] * 6,  # a whole block, then one of one page (past the table at 24)
    "ragged": lambda bk: [0, 1, 9, 16, 17, 24],
}
WINDOW = 6  # at two pages a block, the rows of 16, 17 and 24 start reading at their second or third block


def fetch_case(lengths, bk):
    """Two layers of bf16 pools of 4-position pages, 2 KV heads of 2
    queries each, under permuted tables; the kernel reads layer 1."""
    B, M, Hkv, group, Dh = 6, 6, 2, 2, 32
    N = B * M
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    k_pool, v_pool = (jax.random.normal(key, (2, N + 1, Hkv, PAGE, Dh), jnp.float32).astype(jnp.bfloat16)
                      for key in ks[:2])
    q = jax.random.normal(ks[2], (B, Hkv * group, Dh), jnp.float32).astype(jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(11).permutation(N).reshape(B, M), jnp.int32)
    return q, k_pool, v_pool, tables, jnp.asarray(FETCH_LENGTHS[lengths](bk), jnp.int32)


def unowned_poisoned(pool, tables, lens):
    """The pool with NaN in every page no row holds below its length, and
    in the other layer."""
    out = np.full(pool.shape, np.nan, np.float32)
    for b, n in enumerate(np.asarray(lens)):
        for j in range(max(1, -(-int(n) // PAGE))):  # a row of length 0 is read as one of length 1
            out[1, int(tables[b, j])] = np.asarray(pool[1, int(tables[b, j])], np.float32)
    return jnp.asarray(out, pool.dtype)


@pytest.mark.parametrize("window", [None, WINDOW], ids=["whole-rows", "windowed"])
@pytest.mark.parametrize("lengths", FETCH_LENGTHS)
@pytest.mark.parametrize("block", ["blocks-of-two-pages", "the-entry-s-block"])
def test_a_block_lands_as_the_committed_fetch_landed_it(block, lengths, window, monkeypatch, capsys):
    """In the interpreter's ``ON_WAIT`` mode the kernel's output equals,
    bit for bit, the committed fetch's (``tests/committed_fetch.py``: a
    loop starts a DMA a page a stream, another waits page by page), over
    the pools as they are and over pools with every page no row owns up
    to its length poisoned. ``ON_WAIT`` lands the DMAs queued on a slot's
    semaphore only until a wait's bytes are counted, into buffers that
    start as NaN: if a full block's two waits, each described as one
    stream's whole slot, counted fewer bytes than both streams' pages,
    a page would be read before it landed. No semaphore is left with a
    count at the kernel's exit. The reference agrees to a bf16 rounding
    on every row that sees a position."""
    if block == "blocks-of-two-pages":
        monkeypatch.setattr(pa, "_BLOCK_TOKENS", 2 * PAGE)
    bk = PAGE * pa._pages_per_block(2, PAGE, 32, 2, 6)
    assert bk == (8 if block == "blocks-of-two-pages" else 24)
    q, k_pool, v_pool, tables, lens = fetch_case(lengths, bk)
    win = None if window is None else jnp.int32(window)

    def call(k, v, interpret):
        return np.asarray(pa._paged_attention_call(q, k, v, tables, lens, 0.3, interpret, window=win,
                                                   layer=jnp.int32(1)), np.float32)

    got = call(k_pool, v_pool, ON_WAIT)
    ref = pa.paged_decode_attention_ref(q, k_pool, v_pool, tables, lens, scale=0.3, window=win, layer=1)
    seen = np.asarray(lens) > 0  # a row of length 0 is an empty slot, whose output nothing reads
    np.testing.assert_allclose(got[seen], np.asarray(ref, np.float32)[seen], atol=2e-2)
    with monkeypatch.context() as m:
        m.setattr(pa, "_start_pages", committed_start)
        m.setattr(pa, "_land_pages", committed_land)
        committed = call(k_pool, v_pool, ON_WAIT)
    assert np.array_equal(got, committed, equal_nan=True)
    unowned = call(unowned_poisoned(k_pool, tables, lens), unowned_poisoned(v_pool, tables, lens), ON_WAIT)
    assert np.array_equal(unowned, committed, equal_nan=True)
    assert "non-zero count" not in capsys.readouterr().out


def test_a_pool_of_fewer_pages_than_a_block_holds_is_read_in_blocks_of_the_pool_s_pages(monkeypatch):
    """Five pages in the pool (four and the trash page) under tables of six
    entries, rows sharing pages, at the entry's block (the table's six
    pages): a block holds the pool's five, since a run of the pool's pages
    describes a whole slot's wait — a bound that neither the interpreter
    nor the chip's compiler checks for a wait."""
    q, k_pool, v_pool, _, lens = fetch_case("ragged", 24)
    k_pool, v_pool = k_pool[:, :5], v_pool[:, :5]
    tables = jnp.asarray(np.random.default_rng(5).integers(0, 4, (6, 6)), jnp.int32)
    slots = []

    real_land = pa._land_pages

    def land(streams, *args):
        slots.append(streams[0][1].shape[1])
        return real_land(streams, *args)

    monkeypatch.setattr(pa, "_land_pages", land)
    got = pa._paged_attention_call(q, k_pool, v_pool, tables, lens, 0.3, ON_WAIT, layer=jnp.int32(1))
    ref = pa.paged_decode_attention_ref(q, k_pool, v_pool, tables, lens, scale=0.3, layer=1)
    assert set(slots) == {5}
    seen = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[seen], np.asarray(ref, np.float32)[seen], atol=2e-2)


class TestPagedKVCache:
    def test_accounting_roundtrip(self):
        cfg = llama.LlamaConfig.tiny()
        cache = PagedKVCache(cfg, num_pages=16, page_size=8, max_slots=2,
                             max_seq_len=64)
        cache.alloc_slot(0, seq_id=100, prompt_len=10)  # 2 pages
        assert cache.stats()["free_blocks"] == 14
        assert cache.seq_lens[0] == 10
        for _ in range(6):
            cache.extend_slot(0)  # 10 -> 16, stays in 2 pages
        assert cache.stats()["free_blocks"] == 14
        cache.extend_slot(0)  # 17 -> 3rd page
        assert cache.stats()["free_blocks"] == 13
        cache.free_slot(0)
        assert cache.stats()["free_blocks"] == 16
        cache.close()

    def test_out_of_blocks_keeps_state_clean(self):
        cfg = llama.LlamaConfig.tiny()
        cache = PagedKVCache(cfg, num_pages=4, page_size=8, max_slots=2,
                             max_seq_len=64)
        cache.alloc_slot(0, seq_id=1, prompt_len=24)  # 3 pages
        with pytest.raises(OutOfBlocks):
            cache.alloc_slot(1, seq_id=2, prompt_len=24)
        assert cache._slot_seq[1] is None
        cache.alloc_slot(1, seq_id=2, prompt_len=8)  # 1 page fits
        cache.close()

    def test_bucket_reservation(self):
        cfg = llama.LlamaConfig.tiny()
        cache = PagedKVCache(cfg, num_pages=16, page_size=8, max_slots=2,
                             max_seq_len=64)
        # prompt 10, bucket 32 -> reserve 4 pages up front
        cache.alloc_slot(0, seq_id=1, prompt_len=10, reserve_tokens=32)
        assert cache.stats()["free_blocks"] == 12
        for _ in range(22):
            cache.extend_slot(0)  # grows to 32 without new pages
        assert cache.stats()["free_blocks"] == 12
        cache.extend_slot(0)  # 33rd token -> 5th page
        assert cache.stats()["free_blocks"] == 11
        cache.close()

    def test_a_block_s_reservation_copies_the_table_only_when_it_takes_a_page(self, monkeypatch):
        """The decode loop reserves a row's block ahead every block: a
        reservation inside the pages the row owns leaves the host table as
        it was and reads no table from the allocator; one that takes a
        page copies the table once, and it is the allocator's."""
        cfg = llama.LlamaConfig.tiny()
        cache = PagedKVCache(cfg, num_pages=16, page_size=8, max_slots=2, max_seq_len=64)
        cache.alloc_slot(1, seq_id=7, prompt_len=3)  # 1 page
        reads = []
        real = cache.allocator.block_table
        monkeypatch.setattr(cache.allocator, "block_table", lambda seq: reads.append(seq) or real(seq))
        for _ in range(6):  # 4 positions a block, reserved 2 blocks ahead
            assert cache.try_reserve_slot(1, 8)
            owned = real(7)
            assert list(cache.tables[1, :len(owned)]) == owned and cache.owned_capacity(1) == len(owned) * 8
            assert len(reads) == len(owned) - 1  # one read a page taken, none between
            cache.advance_slot(1, 4)
        assert cache.stats()["free_blocks"] == 12  # the last reservation reached position 31: 4 pages
        cache.close()


class TestPagedDecodeParity:
    def test_paged_decode_matches_dense_path(self):
        """Generate 8 tokens for 2 ragged rows through (a) the dense KVCache
        decode_step and (b) prefill-into-pages + decode_step_paged; logits
        must agree at every step."""
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        B, page = 2, 8
        prompts = jnp.array(
            [[5, 6, 7, 8, 9, 0, 0, 0], [11, 12, 13, 14, 15, 16, 17, 18]],
            jnp.int32,
        )
        seq_lens = jnp.array([5, 8], jnp.int32)

        # dense oracle
        dense_cache = llama.KVCache.create(cfg, B, max_len=32)
        last_d, dense_cache = llama.prefill(cfg, params, prompts, dense_cache, seq_lens)
        # paged path: prefill computes the slab, cache scatters it
        from gofr_tpu.serving.batch import prefill_compute

        cache = PagedKVCache(cfg, num_pages=12, page_size=page, max_slots=B,
                             max_seq_len=32, dtype=cfg.dtype)
        last_p = []
        for b in range(B):
            logits_b, k_slab, v_slab = prefill_compute(
                cfg, params, prompts[b:b + 1], seq_lens[b:b + 1]
            )
            cache.alloc_slot(b, seq_id=b + 1, prompt_len=int(seq_lens[b]),
                             reserve_tokens=prompts.shape[1])
            cache.write_prefill(b, k_slab, v_slab)
            last_p.append(logits_b[0])
        np.testing.assert_allclose(
            np.asarray(jnp.stack(last_p)), np.asarray(last_d), rtol=2e-4, atol=2e-4
        )

        tok_d = jnp.argmax(last_d, axis=-1)
        tok_p = jnp.argmax(jnp.stack(last_p), axis=-1)
        np.testing.assert_array_equal(np.asarray(tok_d), np.asarray(tok_p))

        cache_len = seq_lens
        active = jnp.ones((B,), bool)
        for step in range(8):
            cache_len = cache_len + 1
            logits_d, dense_cache = llama.decode_step(
                cfg, params, tok_d, dense_cache, cache_len
            )
            for b in range(B):
                cache.extend_slot(b)
            logits_p, cache.k_pool, cache.v_pool = llama.decode_step_paged(
                cfg, params, tok_p, cache.k_pool, cache.v_pool,
                cache.tables_device(), cache.seq_lens_device(), active,
            )
            np.testing.assert_allclose(
                np.asarray(logits_p), np.asarray(logits_d), rtol=2e-4, atol=2e-4,
                err_msg=f"step {step}",
            )
            tok_d = jnp.argmax(logits_d, axis=-1)
            tok_p = jnp.argmax(logits_p, axis=-1)
            np.testing.assert_array_equal(np.asarray(tok_d), np.asarray(tok_p))
        cache.close()

    def test_inactive_rows_do_not_corrupt_pool(self):
        """An inactive row pointing at page 0 must not clobber it."""
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        B, page = 2, 8
        cache = PagedKVCache(cfg, num_pages=8, page_size=page, max_slots=B,
                             max_seq_len=32, dtype=cfg.dtype)
        from gofr_tpu.serving.batch import prefill_compute

        prompt = jnp.array([[3, 4, 5, 6, 0, 0, 0, 0]], jnp.int32)
        slen = jnp.array([4], jnp.int32)
        logits0, k_slab, v_slab = prefill_compute(cfg, params, prompt, slen)
        cache.alloc_slot(0, seq_id=1, prompt_len=4, reserve_tokens=8)
        cache.write_prefill(0, k_slab, v_slab)
        pool_before = np.asarray(cache.k_pool).copy()

        # slot 1 inactive: table all zeros, seq_len 0
        active = jnp.array([True, False])
        tok = jnp.array([7, 0], jnp.int32)
        cache.extend_slot(0)
        _, cache.k_pool, cache.v_pool = llama.decode_step_paged(
            cfg, params, tok, cache.k_pool, cache.v_pool,
            cache.tables_device(), cache.seq_lens_device(), active,
        )
        pool_after = np.asarray(cache.k_pool)
        # The inactive row's table points at page 0 offset 0 (page 0 is also
        # legitimately owned by slot 0, which wrote offset 4 this step) —
        # the masked append must leave offset 0 untouched.
        np.testing.assert_array_equal(
            pool_after[:, 0, :, 0], pool_before[:, 0, :, 0]
        )
        assert not np.array_equal(pool_after[:, 0, :, 4], pool_before[:, 0, :, 4]), (
            "active row's append should have written offset 4"
        )
        cache.close()


# ------------------------------------------------------ the device pools
def test_cache_holds_exactly_two_device_pools_and_leaves_none_behind():
    """A ``PagedKVCache`` keeps two device arrays, ``k_pool`` and ``v_pool``
    in the model's dtype, and nothing else on the device: ``reset_pools()``
    replaces the pair (the old one is freed), and once the cache is closed
    and dropped no pool-shaped array is live."""
    import gc

    cfg = llama.LlamaConfig.tiny()
    # a shape no other test of this file makes, so live_arrays() can be read
    shape = (cfg.n_layers, 13 + 1, cfg.n_kv_heads, 8, cfg.head_dim)

    def live_pools():
        gc.collect()
        return [a for a in jax.live_arrays() if a.shape == shape]

    assert live_pools() == []
    cache = PagedKVCache(cfg, num_pages=13, page_size=8, max_slots=2, max_seq_len=64)
    on_device = {n for n, v in vars(cache).items() if isinstance(v, jax.Array)}
    assert on_device == {"k_pool", "v_pool"}
    assert cache.k_pool.shape == cache.v_pool.shape == shape
    assert cache.k_pool.dtype == cache.v_pool.dtype == cfg.dtype
    assert len(live_pools()) == 2
    old = (cache.k_pool, cache.v_pool)
    cache.reset_pools()
    assert cache.k_pool is not old[0] and cache.v_pool is not old[1]
    del old
    assert len(live_pools()) == 2  # the pair it replaced is gone
    cache.close()
    del cache
    assert live_pools() == []
