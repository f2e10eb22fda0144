"""Continuous batching: the token-budget step planner + unified ragged
prefill/decode dispatch (ROADMAP item 1, Ragged Paged Attention
arXiv:2604.15464).

The acceptance lens is the one head-of-line blocking used to destroy:
under a mixed load (a long prompt chunking through admission while rows
decode), decode rows keep emitting BETWEEN the long prompt's chunks, and
short-prompt TTFT under load stays within a small factor of its unloaded
value — measured straight off the PR 9 timeline recorder, no TPU needed.
Chunked prefill must also be a pure scheduling change: greedy outputs
match the monolithic path token-for-token on every KV layout.
"""

import threading
import time

import jax
import numpy as np
import pytest

from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving.stepplan import ChunkCursor, StepPlanner
from gofr_tpu.models import llama


@pytest.fixture(scope="module")
def engine_setup():
    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def make_engine(cfg, params, **kw):
    defaults = dict(
        max_slots=6, max_seq_len=128, prefill_buckets=(16,), max_queue=64,
        prefill_chunk_tokens=16,
    )
    defaults.update(kw)
    return ServingEngine(cfg, params, EngineConfig(**defaults), ByteTokenizer())


# -- step planner policy ------------------------------------------------------

def _cursor(slot, total, seq, dispatched=0, blocked=False):
    cur = ChunkCursor(req=None, slot=slot, total=total, seq=seq)
    cur.dispatched = cur.committed = dispatched
    cur.blocked = blocked
    return cur


def test_planner_reserves_decode_first_under_explicit_budget():
    p = StepPlanner(chunk_tokens=16, block_steps=4, step_token_budget=48)
    plan = p.plan(decode_rows=8, cursors=[_cursor(0, 100, 0)],
                  free_slots=0, queue_depth=0)
    # 8 rows * 4 steps = 32 reserved; 16 left for prefill = one chunk
    assert plan.decode_tokens == 32
    assert plan.prefill_budget == 16
    assert plan.grants == [(0, 16)]
    # decode saturating the budget starves prefill, never the reverse
    plan = p.plan(decode_rows=12, cursors=[_cursor(0, 100, 0)],
                  free_slots=0, queue_depth=0)
    assert plan.prefill_budget == 0 and plan.grants == []


def test_planner_never_splits_a_chunk_across_the_budget():
    """Grants are whole chunks (or the final ragged tail) — a budget
    leftover smaller than the next chunk defers the cursor instead of
    fragmenting chunk boundaries (they double as page-grid write
    boundaries and chunk-prefix cache keys)."""
    p = StepPlanner(chunk_tokens=32, block_steps=4, step_token_budget=48)
    plan = p.plan(decode_rows=8, cursors=[_cursor(0, 100, 0)],
                  free_slots=0, queue_depth=0)
    assert plan.prefill_budget == 16  # < one chunk
    assert plan.grants == []
    # two cursors, budget for one and a half chunks: the second waits
    p2 = StepPlanner(chunk_tokens=32, block_steps=4, step_token_budget=48)
    plan = p2.plan(decode_rows=0,
                   cursors=[_cursor(0, 100, 0), _cursor(1, 100, 1)],
                   free_slots=0, queue_depth=0)
    assert plan.grants == [(0, 32)]
    # but a FINAL ragged tail that fits the leftover still lands
    p3 = StepPlanner(chunk_tokens=32, block_steps=4, step_token_budget=44)
    plan = p3.plan(decode_rows=0,
                   cursors=[_cursor(0, 100, 0), _cursor(1, 70, 1, dispatched=64)],
                   free_slots=0, queue_depth=0)
    assert plan.grants == [(0, 32), (1, 6)]


def _parent_cursor_free_plan(prefill_budget, free_slots, queue_depth, max_admissions=4):
    """What the one-chunk-an-iteration planner (the policy until PR 36)
    gave a plan WITHOUT cursors — one chunk of budget in auto mode, what
    decode left of an explicit one: the fields a change to the auto
    budget must leave alone for traffic that never chunks."""
    admit_cap = 0
    if queue_depth > 0:
        admit_cap = 1
        if free_slots > 0 and prefill_budget > 0:
            admit_cap = min(max_admissions, free_slots)
    return dict(prefill_budget=prefill_budget, admit_cap=admit_cap,
                budget_left=prefill_budget, grants=[])


# block_steps=4 is the bound on chunk rows an iteration
AUTO_CASES = {
    # name: (budget, cursors as (slot, total, seq, dispatched, blocked),
    #        expected grants, expected prefill_budget)
    "no_cursor": (0, [], [], 16),
    "one_cursor": (0, [(0, 100, 1, 32, False)], [(0, 16)], 16),
    "k_cursors_under_the_bound_oldest_first": (
        0, [(3, 64, 2, 0, False), (2, 64, 1, 16, False)],
        [(2, 16), (3, 16)], 32),
    # slot s admitted at seq 10 - s: the four OLDEST are the highest slots
    "more_cursors_than_the_bound_the_oldest_get_it": (
        0, [(s, 64, 10 - s, 0, False) for s in range(6)],
        [(5, 16), (4, 16), (3, 16), (2, 16)], 64),
    "blocked_and_finished_not_counted": (
        0, [(0, 64, 1, 0, True), (1, 32, 2, 32, False), (2, 64, 3, 0, False),
            (3, 64, 4, 16, False)],
        [(2, 16), (3, 16)], 32),
    "ragged_tail_beside_whole_chunks": (
        0, [(0, 64, 1, 0, False), (1, 37, 2, 32, False)],
        [(0, 16), (1, 5)], 32),
    # 6 decode rows x 4 steps reserved first: 32 of the 56 are prefill's
    "explicit_budget_as_today": (
        56, [(3, 64, 2, 0, False), (2, 64, 1, 0, False), (4, 64, 3, 0, False)],
        [(2, 16), (3, 16)], 32),
}


@pytest.mark.parametrize("case", sorted(AUTO_CASES))
def test_planner_auto_budget_grants_a_chunk_to_every_waiting_cursor(case):
    """Auto budget (ISSUE 36): one whole chunk (or the ragged tail) for
    each cursor that has work, oldest first, at most ``block_steps`` an
    iteration; a cursor-free plan is the one-chunk plan field for
    field; an explicit budget plans as it always did."""
    budget, cursors, grants, prefill_budget = AUTO_CASES[case]
    p = StepPlanner(chunk_tokens=16, block_steps=4, step_token_budget=budget)
    plan = p.plan(decode_rows=6, cursors=[_cursor(*c) for c in cursors],
                  free_slots=2, queue_depth=3)
    assert plan.grants == grants
    assert plan.prefill_budget == prefill_budget
    assert plan.budget_left == prefill_budget - sum(n for _, n in grants)
    assert plan.decode_tokens == 24  # reserved first, whatever is granted
    assert plan.admit_cap >= 1  # never zero while the queue holds work
    # traffic that never chunks: the parent's plan, field for field
    for free_slots, queue_depth in ((0, 0), (0, 5), (3, 0), (3, 5), (9, 2)):
        bare = p.plan(decode_rows=6, cursors=[], free_slots=free_slots,
                      queue_depth=queue_depth)
        expect = _parent_cursor_free_plan(budget - 24 if budget else 16,
                                          free_slots, queue_depth)
        assert {k: getattr(bare, k) for k in expect} == expect


@pytest.mark.parametrize("steps", [1, 2, 4, 16])
def test_planner_auto_bound_follows_the_block(steps):
    """The bound is derived: as many chunk rows as the block has decode
    steps (a one-step block carries one chunk, as before PR 36)."""
    p = StepPlanner(chunk_tokens=16, block_steps=steps)
    plan = p.plan(decode_rows=0,
                  cursors=[_cursor(s, 64, s) for s in range(20)],
                  free_slots=0, queue_depth=1)
    assert plan.grants == [(s, 16) for s in range(steps)]
    assert plan.budget_left == 0 and plan.admit_cap == 1


def test_planner_grants_fifo_oldest_cursor_first():
    p = StepPlanner(chunk_tokens=16, block_steps=4)
    old = _cursor(2, 64, seq=1)
    new = _cursor(3, 64, seq=2)
    plan = p.plan(decode_rows=0, cursors=[new, old], free_slots=0,
                  queue_depth=0)
    # a chunk for each waiting cursor, the OLDEST first
    assert plan.grants == [(2, 16), (3, 16)]
    # past the bound it is the NEWEST that wait
    more = [_cursor(10 + i, 64, seq=3 + i) for i in range(4)]
    plan = p.plan(decode_rows=0, cursors=more[::-1] + [new, old],
                  free_slots=0, queue_depth=0)
    assert plan.grants == [(2, 16), (3, 16), (10, 16), (11, 16)]
    # an explicit budget splits across cursors in admission order
    p2 = StepPlanner(chunk_tokens=16, block_steps=4, step_token_budget=32)
    plan = p2.plan(decode_rows=0, cursors=[new, old], free_slots=0,
                   queue_depth=0)
    assert plan.grants == [(2, 16), (3, 16)]


def test_planner_skips_blocked_and_finished_cursors():
    p = StepPlanner(chunk_tokens=16, block_steps=4)
    blocked = _cursor(0, 64, seq=1, blocked=True)
    done = _cursor(1, 32, seq=2, dispatched=32)
    live = _cursor(2, 64, seq=3)
    plan = p.plan(decode_rows=0, cursors=[blocked, done, live],
                  free_slots=0, queue_depth=0)
    assert plan.grants == [(2, 16)]


def test_planner_admission_quota_never_zero_with_queue():
    """Canceled-but-queued requests settle only through an admit delivery:
    the quota floor is 1 whenever the queue is non-empty, even with zero
    budget or zero free slots."""
    p = StepPlanner(chunk_tokens=16, block_steps=4, step_token_budget=8)
    plan = p.plan(decode_rows=4, cursors=[], free_slots=0, queue_depth=5)
    assert plan.prefill_budget == 0
    assert plan.admit_cap == 1
    plan = p.plan(decode_rows=0, cursors=[], free_slots=3, queue_depth=5)
    assert plan.admit_cap >= 1


def test_planner_final_ragged_chunk_grant():
    p = StepPlanner(chunk_tokens=16, block_steps=4)
    plan = p.plan(decode_rows=0, cursors=[_cursor(0, 37, 1, dispatched=32)],
                  free_slots=0, queue_depth=0)
    assert plan.grants == [(0, 5)]


# -- chunked prefill correctness ---------------------------------------------

@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_chunked_prefill_matches_monolithic_greedy(engine_setup, kv_layout):
    """Chunked prefill is a SCHEDULING change: greedy tokens must match
    the monolithic bucketed path exactly (the on-device first-token
    sample uses the same fold_in(root, request_id) key)."""
    cfg, params = engine_setup
    kw = {} if kv_layout == "dense" else dict(kv_layout="paged", kv_page_size=8)
    mono = make_engine(cfg, params, prefill_chunk_tokens=128,
                       prefill_buckets=(64,), **kw)
    chunked = make_engine(cfg, params, prefill_chunk_tokens=16,
                          prefill_buckets=(64,), **kw)
    mono.start(), chunked.start()
    try:
        prompt = "the quick brown fox jumps over the lazy dog " * 1
        a = mono.submit(prompt, max_new_tokens=8, temperature=0.0).result(timeout=120)
        b = chunked.submit(prompt, max_new_tokens=8, temperature=0.0).result(timeout=120)
        assert a.token_ids == b.token_ids
        assert a.finish_reason == b.finish_reason
        tl = chunked.timeline.get(b.request_id)
        assert len(tl.prefill_chunks) == 3  # 45 tokens / 16-token chunks
        assert sum(c["tokens"] for c in tl.prefill_chunks) == b.prompt_tokens
    finally:
        mono.stop(), chunked.stop()


def test_waiting_prompts_share_an_iteration_up_to_the_bound(engine_setup, tmp_path):
    """ISSUE 36: several long prompts submitted together to a paged
    engine whose rows are decoding. Read off the engine's own spans
    (a profiler trace on the CPU, the benchmark's reader): a ragged
    dispatch carries MORE THAN ONE chunk row and never more than the
    planner's bound, a plan never grants more than the bound however
    many cursors ask, the decode block rides EVERY dispatch (the rows
    that were decoding are in each one, for a whole block of steps),
    every prompt's greedy tokens are the monolithic prefill's, and chunk
    commits stay monotonic and cover each prompt."""
    from benchmarks.harness import host_spans, trace_reduce

    cfg, params = engine_setup
    bound = 4  # the block's decode steps: chunk rows an iteration
    kw = dict(kv_layout="paged", kv_page_size=8, max_slots=8, max_seq_len=192,
              prefill_buckets=(128,), multi_step=bound)
    prompts = [chr(ord("a") + i) * (97 + i) for i in range(6)]  # 7 chunks of 16 each
    mono = make_engine(cfg, params, prefill_chunk_tokens=128, **kw)
    mono.start()
    try:
        want = [mono.submit(p, max_new_tokens=4, temperature=0.0).result(timeout=300).token_ids
                for p in prompts]
    finally:
        mono.stop()
    engine = make_engine(cfg, params, prefill_chunk_tokens=16, **kw)
    engine.start()
    try:
        # compile the programs off the trace
        engine.submit("warm", max_new_tokens=4, temperature=0.0).result(timeout=300)
        engine.submit("w" * 40, max_new_tokens=4, temperature=0.0).result(timeout=300)
        with jax.profiler.trace(str(tmp_path)):
            streaming = [threading.Event(), threading.Event()]
            decoding = [
                engine.submit(f"decode row {i}", max_new_tokens=150, temperature=0.0,
                              stream_cb=lambda tid, piece, done, ev=ev: ev.set())
                for i, ev in enumerate(streaming)
            ]
            assert all(ev.wait(120) for ev in streaming)
            longs = [engine.submit(p, max_new_tokens=4, temperature=0.0) for p in prompts]
            got = [f.result(timeout=300) for f in longs]
            still_decoding = [not f.done() for f in decoding]
            assert all(f.result(timeout=300).completion_tokens == 150 for f in decoding)
            time.sleep(0.1)
    finally:
        engine.stop()
    assert [r.token_ids for r in got] == want
    for r in got:
        tl = engine.timeline.get(r.request_id)
        assert len(tl.prefill_chunks) == 7
        _assert_chunks_cover(tl, r.prompt_tokens)
    assert all(still_decoding)  # the two rows outlived every prompt's prefill
    spans = [host_spans.parse(e) for e in
             host_spans.load_host_events(trace_reduce.find_xplane(str(tmp_path)))]
    ragged = [s.kw for s in spans if s.phase == "dispatch" and s.kw.get("kind") == "ragged"]
    assert ragged and max(kw["chunk_rows"] for kw in ragged) > 1
    assert all(1 <= kw["chunk_rows"] <= bound for kw in ragged)
    # the decode block is in every one of them: both decoding rows, a whole block
    assert all(kw["rows"] >= 2 and kw["steps"] == bound for kw in ragged)
    plans = [s.kw for s in spans if s.phase == "plan"]
    assert all(kw["grants"] <= min(bound, kw["cursors"]) for kw in plans)
    # more cursors asked than the bound at some plan, and the bound held
    assert any(kw["cursors"] > bound and kw["grants"] == bound for kw in plans)
    assert sum(kw["chunk_rows"] for kw in ragged) == 6 * 7


def test_prompt_longer_than_every_bucket_now_chunks_instead_of_truncating(
    engine_setup,
):
    """Monolithic prefill had to truncate a prompt to its largest bucket;
    the chunked path serves the WHOLE prompt up to the sequence cap."""
    cfg, params = engine_setup
    engine = make_engine(cfg, params)  # buckets (16,), chunk 16
    engine.start()
    try:
        r = engine.submit("x" * 80, max_new_tokens=3, temperature=0.0).result(timeout=120)
        assert r.prompt_tokens > 16  # not truncated to the bucket anymore
        tl = engine.timeline.get(r.request_id)
        assert len(tl.prefill_chunks) >= 5
    finally:
        engine.stop()


def test_chunked_sampled_rows_are_deterministic_per_request(engine_setup):
    """The on-device first-token sample is keyed fold_in(root, rid): the
    same submit order gives identical tokens, chunked or not."""
    cfg, params = engine_setup
    a = make_engine(cfg, params)
    b = make_engine(cfg, params)
    a.start(), b.start()
    try:
        prompt = "sample me " * 5  # 50 tokens -> chunked
        ra = a.submit(prompt, max_new_tokens=6, temperature=0.7, top_k=20).result(timeout=120)
        rb = b.submit(prompt, max_new_tokens=6, temperature=0.7, top_k=20).result(timeout=120)
        assert ra.token_ids == rb.token_ids
    finally:
        a.stop(), b.stop()


# -- the acceptance test: head-of-line blocking is gone -----------------------

def test_mixed_load_decode_not_starved_and_ttft_bounded(engine_setup):
    """One long prompt chunks through admission while 4 rows decode:

    - decode rows keep emitting tokens BETWEEN the long prompt's chunks
      (the old monolithic path emitted nothing until the prefill finished),
    - the long prompt actually split into chunks,
    - short-prompt TTFT under load stays within a small factor of its
      unloaded value (timeline-measured, same data /requestz serves)."""
    cfg, params = engine_setup
    engine = make_engine(cfg, params, max_slots=8)
    engine.start()
    try:
        # warm every executable off the clock
        engine.submit("warm", max_new_tokens=4, temperature=0.0).result(timeout=300)
        engine.submit("w" * 48, max_new_tokens=4, temperature=0.0).result(timeout=300)

        unloaded = []
        for i in range(4):
            r = engine.submit(f"b{i}", max_new_tokens=2, temperature=0.0).result(timeout=300)
            tl = engine.timeline.get(r.request_id)
            unloaded.append(tl.ttft_s())
        unloaded_p50 = sorted(unloaded)[len(unloaded) // 2]

        # 4 decoding rows, their per-token emission times recorded
        emissions: dict[int, list[float]] = {}
        mu = threading.Lock()

        def cb_for(i):
            def cb(token_id, piece, done):
                with mu:
                    emissions.setdefault(i, []).append(time.perf_counter())
            return cb

        decode_futs = [
            engine.submit(f"decode row {i}", max_new_tokens=48,
                          temperature=0.0, stream_cb=cb_for(i))
            for i in range(4)
        ]
        # let the rows reach steady decode
        deadline = time.time() + 60
        while time.time() < deadline:
            with mu:
                if sum(len(v) for v in emissions.values()) >= 8:
                    break
            time.sleep(0.01)

        long_submitted = time.perf_counter()
        long_fut = engine.submit("L" * 100, max_new_tokens=4, temperature=0.0)
        short_futs = []
        for i in range(4):
            short_futs.append(
                engine.submit(f"s{i}", max_new_tokens=2, temperature=0.0)
            )
            time.sleep(0.02)

        long_res = long_fut.result(timeout=300)
        long_tl = engine.timeline.get(long_res.request_id)
        shorts = [f.result(timeout=300) for f in short_futs]
        for f in decode_futs:
            assert f.result(timeout=300).completion_tokens > 0

        # (1) the long prompt chunked (100 tokens / 16-token chunks)
        assert len(long_tl.prefill_chunks) >= 5, long_tl.prefill_chunks
        # (2) decode rows emitted DURING the long prefill window
        long_first_token = long_submitted + long_tl.ttft_s()
        with mu:
            during = sum(
                1 for times in emissions.values() for t in times
                if long_submitted < t < long_first_token
            )
        assert during > 0, (
            "no decode tokens emitted while the long prompt prefilled — "
            "head-of-line blocking is back"
        )
        # (3) short-prompt TTFT under load within a small factor of the
        # unloaded value (generous bound: CI boxes jitter, but the old
        # head-of-line path blew past this by the full prefill time)
        loaded = sorted(
            engine.timeline.get(r.request_id).ttft_s() for r in shorts
        )
        loaded_p50 = loaded[len(loaded) // 2]
        assert loaded_p50 <= unloaded_p50 * 10 + 0.75, (
            f"short TTFT p50 under load {loaded_p50:.3f}s vs unloaded "
            f"{unloaded_p50:.3f}s"
        )
    finally:
        engine.stop()


# -- lifecycle: cancel / deadline / warm restart / pool pressure --------------

def test_cancel_mid_chunked_prefill_reclaims_slot(engine_setup):
    cfg, params = engine_setup
    engine = make_engine(cfg, params, kv_layout="paged", kv_page_size=8)
    engine.start()
    try:
        # warm so the cancel window is not dominated by compiles
        engine.submit("w" * 48, max_new_tokens=2, temperature=0.0).result(timeout=300)
        fut = engine.submit("c" * 100, max_new_tokens=8, temperature=0.0)
        # cancel as soon as the cursor starts (slot claimed, chunks pending)
        deadline = time.time() + 30
        while time.time() < deadline and not engine._cursors:
            time.sleep(0.001)
        engine.cancel(fut.request_id)
        res = fut.result(timeout=120)
        assert res.finish_reason in ("cancel", "stop", "length")
        deadline = time.time() + 30
        while time.time() < deadline and any(s is not None for s in engine.slots):
            time.sleep(0.01)
        assert all(s is None for s in engine.slots)
        stats = engine.paged_cache.stats()
        assert stats["free_blocks"] == stats["total_blocks"], stats
    finally:
        engine.stop()


def test_warm_restart_requeues_partially_prefilled_from_chunk_zero(
    engine_setup, monkeypatch,
):
    """A request mid-chunked-prefill at restart time has emitted nothing:
    it must requeue and COMPLETE on the rebuilt engine, re-prefilling
    from chunk 0 (its committed KV died with the pools)."""
    from gofr_tpu.serving import batch as batch_ops

    cfg, params = engine_setup
    engine = make_engine(cfg, params)
    hold = threading.Event()
    seen = threading.Event()
    real = batch_ops.ragged_step

    def stalling(*args, **kw):
        if not seen.is_set():
            seen.set()
            hold.wait(20)
        return real(*args, **kw)

    monkeypatch.setattr(batch_ops, "ragged_step", stalling)
    engine.start()
    try:
        engine.submit("warm", max_new_tokens=2, temperature=0.0).result(timeout=300)
        fut = engine.submit("R" * 60, max_new_tokens=4, temperature=0.0)
        assert seen.wait(60)  # first chunk dispatched; cursor is live
        hold.set()
        assert engine.warm_restart(join_timeout=10.0) is True
        res = fut.result(timeout=300)
        assert res.finish_reason in ("stop", "length")
        assert res.completion_tokens > 0
        tl = engine.timeline.get(res.request_id)
        # re-prefilled from chunk 0 on the rebuilt engine: the timeline
        # shows a restarted chunk sequence, never a continuation of
        # committed-then-lost KV
        restarts = [c for c in tl.prefill_chunks if c["index"] == 0]
        assert restarts, tl.prefill_chunks
    finally:
        engine.stop()


def test_kv_pool_pressure_requeues_cursor_from_chunk_zero(engine_setup):
    """Chunked prefill against a pool too small for two long prompts at
    once: the second cursor hits pool pressure, requeues from chunk 0,
    and completes once the first row retires — pool pressure is a
    transient, not an error, and no pages leak."""
    cfg, params = engine_setup
    engine = make_engine(
        cfg, params, max_slots=2, kv_layout="paged", kv_page_size=8,
        kv_num_pages=24,  # 192 tokens of pool: two 80-token prompts contend
    )
    engine.start()
    try:
        futs = [
            engine.submit("K" * 80, max_new_tokens=3, temperature=0.0)
            for _ in range(3)
        ]
        for f in futs:
            r = f.result(timeout=600)
            assert r.finish_reason in ("stop", "length", "kv_exhausted")
        stats = engine.paged_cache.stats()
        assert stats["free_blocks"] == stats["total_blocks"], stats
        assert stats["sequences"] == 0
    finally:
        engine.stop()


# -- chunk-prefix cache -------------------------------------------------------

@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_chunk_prefix_cache_skips_cached_chunks(engine_setup, kv_layout):
    cfg, params = engine_setup
    kw = {} if kv_layout == "dense" else dict(kv_layout="paged", kv_page_size=8)
    engine = make_engine(cfg, params, prefix_cache_entries=64, **kw)
    engine.start()
    try:
        prompt = "shared prefix " * 5  # 70 tokens -> 5 chunks
        r1 = engine.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        t1 = engine.timeline.get(r1.request_id)
        assert all(not c["prefix_hit"] for c in t1.prefill_chunks)
        r2 = engine.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        t2 = engine.timeline.get(r2.request_id)
        assert r2.token_ids == r1.token_ids
        hits = [c for c in t2.prefill_chunks if c["prefix_hit"]]
        assert hits and hits[0]["tokens"] == r2.prompt_tokens, t2.prefill_chunks
        # and a prompt EXTENDING the cached prefix skips the shared chunks
        r3 = engine.submit(prompt + "tail " * 4, max_new_tokens=4,
                           temperature=0.0).result(timeout=300)
        t3 = engine.timeline.get(r3.request_id)
        hits3 = [c for c in t3.prefill_chunks if c["prefix_hit"]]
        computed3 = [c for c in t3.prefill_chunks if not c["prefix_hit"]]
        assert hits3 and hits3[0]["tokens"] >= 64  # whole-chunk prefixes
        assert computed3  # only the tail was computed
    finally:
        engine.stop()


# -- config knobs -------------------------------------------------------------

def test_continuous_batching_knobs_from_config():
    from gofr_tpu.config import MapConfig

    cfg = EngineConfig.from_config(MapConfig({
        "TPU_PREFILL_CHUNK_TOKENS": "24",
        "TPU_STEP_TOKEN_BUDGET": "512",
        # deprecated aliases still parse and feed the new policy
        "TPU_BATCH_ADMISSION_PER_STEP": "7",
        "TPU_BATCH_PREFILL_BUDGET": "2048",
    }, use_env=False))
    assert cfg.prefill_chunk_tokens == 24
    assert cfg.step_token_budget == 512
    assert cfg.admission_per_step == 7
    assert cfg.prefill_token_budget == 2048
    defaults = EngineConfig.from_config(MapConfig({}, use_env=False))
    assert defaults.prefill_chunk_tokens == 256
    assert defaults.step_token_budget == 0


def test_deprecated_knobs_feed_the_planner(engine_setup):
    """admission_per_step is the planner's admission cap now; the chunk
    size aligns down to the page grid on the paged layout."""
    cfg, params = engine_setup
    eng = make_engine(cfg, params, admission_per_step=3,
                      prefill_chunk_tokens=30, kv_layout="paged",
                      kv_page_size=8)
    assert eng._planner.max_admissions == 3
    assert eng._chunk_tokens == 24  # 30 aligned down to page 8
    eng2 = make_engine(cfg, params, spec_tokens=2, multi_step=None)
    assert eng2._chunk_enabled is False  # spec mode keeps monolithic prefill


def _assert_chunks_cover(tl, prompt_tokens):
    """Within one slot tenancy, committed chunk spans are contiguous and
    strictly increasing; a requeue restarts at 0; the final run covers
    the whole prompt exactly once."""
    runs = [[]]
    for c in tl.prefill_chunks:
        if c["start"] == 0 and runs[-1]:
            runs.append([])
        runs[-1].append(c)
    for run in runs:
        pos = 0
        for c in run:
            assert c["start"] == pos, tl.prefill_chunks
            pos = c["start"] + c["tokens"]
    assert sum(c["tokens"] for c in runs[-1]) == prompt_tokens


def test_chunk_commits_are_monotonic_and_cover_the_prompt(engine_setup):
    """The double-prefill guard: within one slot tenancy, committed chunk
    spans are contiguous and strictly increasing; a requeue restarts at
    0. The final run covers the whole prompt exactly once."""
    cfg, params = engine_setup
    engine = make_engine(cfg, params)
    engine.start()
    try:
        r = engine.submit("m" * 70, max_new_tokens=3, temperature=0.0).result(timeout=300)
        _assert_chunks_cover(engine.timeline.get(r.request_id), r.prompt_tokens)
    finally:
        engine.stop()
