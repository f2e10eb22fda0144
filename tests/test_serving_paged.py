"""Serving engine in paged-KV mode: same correctness contract as the dense
layout (outputs must match the dense engine greedily), plus page-pool
behaviors the dense layout cannot express — token-level admission, pool
exhaustion requeue, and early retirement when decode outgrows the pool."""

import time

import jax
import pytest

from gofr_tpu.models import llama
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def make_engine(cfg, params, **kw):
    defaults = dict(
        max_slots=4, max_seq_len=64, prefill_buckets=(16, 32), max_queue=64,
        kv_layout="paged", kv_page_size=8,
    )
    defaults.update(kw)
    return ServingEngine(cfg, params, EngineConfig(**defaults), ByteTokenizer())


def test_paged_matches_dense_outputs(setup):
    cfg, params = setup
    dense = ServingEngine(
        cfg, params,
        EngineConfig(max_slots=4, max_seq_len=64, prefill_buckets=(16, 32)),
        ByteTokenizer(),
    )
    paged = make_engine(cfg, params)
    prompts = ["hello paged world", "a", "the quick brown fox jumps"]
    try:
        dense.start()
        paged.start()
        futs_d = [dense.submit(p, max_new_tokens=12) for p in prompts]
        futs_p = [paged.submit(p, max_new_tokens=12) for p in prompts]
        for fd, fp in zip(futs_d, futs_p):
            rd = fd.result(timeout=120)
            rp = fp.result(timeout=120)
            assert rp.token_ids == rd.token_ids, (rp.text, rd.text)
            assert rp.finish_reason == rd.finish_reason
    finally:
        dense.stop()
        paged.stop()


def test_health_reports_page_stats(setup):
    cfg, params = setup
    engine = make_engine(cfg, params)
    engine.start()
    try:
        details = engine.health_check()["details"]
        assert details["kv_layout"] == "paged"
        assert details["kv_pages"]["total_blocks"] == 4 * 64 // 8
        assert details["kv_pages"]["page_size"] == 8
    finally:
        engine.stop()


def test_pool_exhaustion_requeues_and_recovers(setup):
    """A pool sized for ~1.5 requests forces later prompts to wait for
    pages; everyone still completes."""
    cfg, params = setup
    engine = make_engine(cfg, params, kv_num_pages=8, max_slots=4)
    engine.start()
    try:
        # each request: bucket 16 -> 2 pages reserved, +growth
        futs = [engine.submit("abcdefghij", max_new_tokens=6) for _ in range(5)]
        results = [f.result(timeout=180) for f in futs]
        for r in results:
            assert r.finish_reason in ("stop", "length", "kv_exhausted")
            assert r.completion_tokens > 0
        stats = engine.paged_cache.stats()
        assert stats["free_blocks"] == stats["total_blocks"]  # all freed
    finally:
        engine.stop()


def test_decode_outgrowing_pool_retires_early(setup):
    """One request whose decode would exceed the pool retires with a
    partial result instead of wedging the engine."""
    cfg, params = setup
    engine = make_engine(cfg, params, kv_num_pages=3, max_slots=1)
    engine.start()
    try:
        # bucket 16 -> 2 pages; decode grows past 24 tokens -> needs a 4th page
        fut = engine.submit("abcdefghijklmn", max_new_tokens=40)
        res = fut.result(timeout=120)
        # pool pressure reports its OWN reason — "length" would be
        # indistinguishable from a legitimate max-tokens stop
        assert res.finish_reason == "kv_exhausted"
        assert 0 < res.completion_tokens < 40
        # engine still serves after the early retirement
        res2 = engine.submit("ok", max_new_tokens=3).result(timeout=120)
        assert res2.completion_tokens > 0
    finally:
        engine.stop()


def test_cancellation_frees_pages(setup):
    cfg, params = setup
    engine = make_engine(cfg, params)
    engine.start()
    try:
        fut = engine.submit("cancel me please", max_new_tokens=50)
        deadline = time.time() + 60
        while time.time() < deadline and not any(engine.slots):
            time.sleep(0.01)
        assert any(engine.slots)
        engine.cancel(fut.request_id)
        res = fut.result(timeout=120)
        assert res.finish_reason == "cancel"
        deadline = time.time() + 30
        while time.time() < deadline and any(engine.slots):
            time.sleep(0.01)
        stats = engine.paged_cache.stats()
        assert stats["free_blocks"] == stats["total_blocks"]
    finally:
        engine.stop()


def test_paged_multi_step_matches_single(setup):
    """Chunked paged decode equals single-step greedy."""
    cfg, params = setup
    single = make_engine(cfg, params, multi_step=1)
    chunked = make_engine(cfg, params, multi_step=4)
    single.start(), chunked.start()
    try:
        for prompt, n in (("chunk paged", 11), ("q", 6)):
            a = single.submit(prompt, max_new_tokens=n, temperature=0.0).result(timeout=120)
            b = chunked.submit(prompt, max_new_tokens=n, temperature=0.0).result(timeout=120)
            assert b.token_ids == a.token_ids, prompt
    finally:
        single.stop(), chunked.stop()


def test_paged_multi_step_pool_pressure_falls_back(setup):
    """When the pool cannot cover a whole chunk, dispatch falls back to
    single steps (with the per-row OutOfBlocks handling) instead of
    corrupting the chunk accounting; everyone still completes."""
    cfg, params = setup
    engine = make_engine(cfg, params, kv_num_pages=8, max_slots=4, multi_step=4)
    engine.start()
    try:
        futs = [engine.submit("abcdefghij", max_new_tokens=6) for _ in range(5)]
        results = [f.result(timeout=180) for f in futs]
        for r in results:
            assert r.finish_reason in ("stop", "length", "kv_exhausted")
            assert r.completion_tokens > 0
        stats = engine.paged_cache.stats()
        assert stats["free_blocks"] == stats["total_blocks"]
    finally:
        engine.stop()
