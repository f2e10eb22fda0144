"""Cluster-wide KV reuse: host-RAM spill tier, distributed prefix
index, and warm KV page migration (ROADMAP item 3, AIBrix multi-tier KV
pooling arXiv:2504.03648).

The acceptance lens: a request whose prefix is cached ONLY on another
replica admits via migration with zero prefill-compute dispatches, and
every failure mode of the new tiers — a dropped spill, a stale
advertisement, a source dying mid-transfer — degrades to a compute
miss, token-identical to the cold path.
"""

import jax
import numpy as np
import pytest

from gofr_tpu.models import llama
from gofr_tpu.serving import (
    ByteTokenizer,
    EngineConfig,
    KVMigrator,
    PrefixIndex,
    ServingEngine,
    TieredPrefixCache,
    local_engine_fetcher,
)
from gofr_tpu.serving.membership import Heartbeat, ReplicaAnnouncer
from gofr_tpu.serving.prefix_index import decode_entry, encode_entry
from gofr_tpu.serving.router import Router, RouterConfig


@pytest.fixture(scope="module")
def engine_setup():
    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def make_engine(cfg, params, **kw):
    defaults = dict(
        max_slots=6, max_seq_len=128, prefill_buckets=(16,), max_queue=64,
        prefill_chunk_tokens=16, prefix_cache_entries=64,
    )
    defaults.update(kw)
    return ServingEngine(
        cfg, params, EngineConfig(**{
            k: v for k, v in defaults.items() if k != "kv_migrator"
        }),
        ByteTokenizer(), kv_migrator=defaults.get("kv_migrator"),
    )


# -- spill tier (unit) ---------------------------------------------------------

def test_tiered_cache_spill_and_reupload_round_trip():
    import jax.numpy as jnp

    cache = TieredPrefixCache(max_entries=2, spill_bytes=1 << 24)
    originals = {}
    for i in range(5):
        value = (
            jnp.full((1, 8), float(i)),
            jnp.full((2, 4, 2, 2), float(i) + 0.5),
            jnp.full((2, 4, 2, 2), float(i) + 0.25),
        )
        originals[f"k{i}"] = value
        cache.put(f"k{i}", value)
    assert cache.flush(5.0)
    stats = cache.stats()
    assert stats["entries"] == 2            # device LRU holds the newest
    assert stats["host"]["entries"] == 3    # the rest spilled, not dropped
    assert stats["spilled_total"] == 3
    # host hit: byte-identical after the spill → re-upload round trip
    value, tier = cache.get_with_tier("k0")
    assert tier == "host"
    for got, want in zip(value, originals["k0"]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the promotion moved it back to the device tier
    _, tier2 = cache.get_with_tier("k0")
    assert tier2 == "device"
    assert cache.get("missing") is None
    cache.close()


def test_spill_tier_byte_bound_evicts_lru():
    import jax.numpy as jnp

    cache = TieredPrefixCache(max_entries=1, spill_bytes=3000)
    for i in range(4):
        cache.put(f"k{i}", (jnp.zeros((256,), jnp.float32),))  # 1 KiB each
    assert cache.flush(5.0)
    host = cache.stats()["host"]
    assert host["entries"] == 2  # 3000 B bound: only the newest two fit
    assert host["bytes"] <= 3000
    cache.close()


def test_spill_chaos_fault_drops_entry_degrades_to_miss():
    import jax.numpy as jnp

    from gofr_tpu import chaos
    from gofr_tpu.chaos.injector import ChaosInjector

    cache = TieredPrefixCache(max_entries=1, spill_bytes=1 << 20)
    with chaos.active(ChaosInjector(101, {"kv.spill": 1.0})):
        cache.put("a", (jnp.zeros((4,)),))
        cache.put("b", (jnp.zeros((4,)),))  # evicts "a" → spill faulted
        assert cache.flush(5.0)
    assert cache.stats()["host"]["entries"] == 0
    assert cache.stats()["spill_dropped_total"] == 1
    value, tier = cache.get_with_tier("a")
    assert value is None and tier == "miss"
    cache.close()


# -- spill tier (engine round trip: evict → host → re-upload) ------------------

@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_engine_spill_round_trip_serves_from_host_tier(engine_setup, kv_layout):
    cfg, params = engine_setup
    kw = {} if kv_layout == "dense" else dict(kv_layout="paged", kv_page_size=8)
    # device tier: 4 entries — one chunked prompt's chain exactly; the
    # flood prompt's chain evicts it into the host tier
    engine = make_engine(cfg, params, prefix_cache_entries=4,
                         kv_spill_bytes=1 << 24, **kw)
    engine.start()
    try:
        prompt = "spill me to host ram " * 3  # >3 chunks of 16
        r1 = engine.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        flood = "completely different x" * 3
        engine.submit(flood, max_new_tokens=2, temperature=0.0).result(timeout=300)
        assert engine._prefix_cache.flush(10.0)
        assert engine._prefix_cache.stats()["host"]["entries"] > 0
        r2 = engine.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        assert r2.token_ids == r1.token_ids
        t2 = engine.timeline.get(r2.request_id)
        assert t2.prefix_tier == "host", t2.prefix_tier
        assert any(c["prefix_hit"] for c in t2.prefill_chunks)
    finally:
        engine.stop()


# -- distributed index: gossip idempotency -------------------------------------

def test_index_observe_is_seq_idempotent_under_redelivery_and_reorder():
    idx = PrefixIndex()
    assert idx.observe("rep-a", 3, [["k1", "device"], ["k2", "host"]])
    # redelivery (same seq) and reorder (older seq) are both dropped
    assert not idx.observe("rep-a", 3, [["k9", "device"]])
    assert not idx.observe("rep-a", 1, [["k9", "device"]])
    assert idx.locate("k1") == [("rep-a", "device")]
    assert idx.locate("k9") == []
    # a NEWER advertisement replaces the set (not a merge): keys the
    # replica no longer advertises disappear
    assert idx.observe("rep-a", 4, [["k2", "device"]])
    assert idx.locate("k1") == []
    assert idx.locate("k2") == [("rep-a", "device")]
    # malformed rows are dropped, not fatal; None advertises nothing
    assert idx.observe("rep-b", 1, [["ok", "device"], "garbage", []])
    assert idx.locate("ok") == [("rep-b", "device")]
    assert not idx.observe("rep-c", 1, None)


def test_index_longest_chain_and_drop_replica():
    idx = PrefixIndex()
    idx.observe("rep-a", 1, [["c0", "device"], ["c1", "device"]])
    idx.observe("rep-b", 1, [["c0", "host"], ["c1", "host"], ["c2", "host"]])
    rid, n = idx.longest_chain(["c0", "c1", "c2", "c3"])
    assert (rid, n) == ("rep-b", 3)
    # exclude self: the admitting replica never migrates from itself
    rid, n = idx.longest_chain(["c0", "c1", "c2"], exclude="rep-b")
    assert (rid, n) == ("rep-a", 2)
    idx.drop_replica("rep-b")
    assert idx.longest_chain(["c0", "c1", "c2"]) == ("rep-a", 2)


def test_heartbeat_carries_advertisement_into_router_index(engine_setup):
    """The gossip path end-to-end minus the broker: the announcer's
    composed beat carries the engine's advertisement, and the router's
    observe_heartbeat files it in its PrefixIndex — same seq discipline
    as membership."""
    cfg, params = engine_setup
    engine = make_engine(cfg, params)
    engine.start()
    try:
        engine.submit("adv " * 10, max_new_tokens=2, temperature=0.0).result(timeout=300)
        announcer = ReplicaAnnouncer("rep-a", engine, publisher=None)
        hb = announcer.compose()
        assert hb.prefix_keys, "beat must carry the prefix advertisement"
        # wire round trip: to_json → from_json preserves the field
        hb2 = Heartbeat.from_json(hb.to_json())
        assert hb2.prefix_keys == hb.prefix_keys
        router = Router(RouterConfig(heartbeat_s=0.05))
        router.observe_heartbeat(hb2)
        key = hb.prefix_keys[0][0]
        assert router.prefix_index.locate(key) == [("rep-a", hb.prefix_keys[0][1])]
        # a replayed (stale-seq) beat cannot regress the index
        assert not router.prefix_index.observe("rep-a", hb2.seq, [["zz", "device"]])
        assert "rep-a" in router.routerz()["prefix_index"]
    finally:
        engine.stop()


# -- wire codec ----------------------------------------------------------------

def test_entry_codec_round_trips_bf16_slabs():
    import jax.numpy as jnp

    value = (
        jnp.linspace(0, 1, 16, dtype=jnp.bfloat16).reshape(1, 16),
        jnp.arange(24, dtype=jnp.float32).reshape(2, 3, 4),
        jnp.arange(24, dtype=jnp.bfloat16).reshape(2, 3, 4),
    )
    decoded = decode_entry(encode_entry(value))
    for got, want in zip(decoded, value):
        assert got.dtype == np.asarray(want).dtype
        np.testing.assert_array_equal(got, np.asarray(want))


# -- migration -----------------------------------------------------------------

def _wire_pair(cfg, params, **kw):
    """Two engines A/B sharing one PrefixIndex; B can migrate from A."""
    index = PrefixIndex()
    a = make_engine(cfg, params, **kw)
    migrator = KVMigrator("B", index)
    b = make_engine(cfg, params, kv_migrator=migrator, **kw)
    migrator.add_peer("A", local_engine_fetcher(a))
    return index, a, b, migrator


def _advertise(index, engine, replica_id="A", seq=1):
    adv = engine.prefix_advertisement()
    assert adv
    assert index.observe(replica_id, seq, adv)


@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_acceptance_second_replica_serves_migrated_prefix_zero_prefill_dispatches(
        engine_setup, kv_layout):
    """THE acceptance test (ISSUE 12): with two in-process replicas, a
    request whose prefix is cached only on the first admits on the
    second via warm migration with ZERO prefill-compute dispatches —
    token-identical to the source replica's output."""
    cfg, params = engine_setup
    kw = {} if kv_layout == "dense" else dict(kv_layout="paged", kv_page_size=8)
    index, a, b, migrator = _wire_pair(cfg, params, **kw)
    a.start()
    b.start()
    try:
        prompt = "the shared system prompt " * 3  # 4+ chunks of 16
        r1 = a.submit(prompt, max_new_tokens=5, temperature=0.0).result(timeout=300)
        _advertise(index, a)
        # B must not run ANY prefill compute for this admission: both
        # the monolithic prefill and the ragged chunk dispatch trip this
        compute_calls = []
        from gofr_tpu.serving import batch as batch_ops
        orig_prefill = batch_ops.prefill_compute
        orig_ragged = b._dispatch_ragged

        def counting_prefill(*args, **kwargs):
            compute_calls.append("prefill_compute")
            return orig_prefill(*args, **kwargs)

        def counting_ragged(*args, **kwargs):
            compute_calls.append("ragged")
            return orig_ragged(*args, **kwargs)

        batch_ops.prefill_compute = counting_prefill
        b._dispatch_ragged = counting_ragged
        try:
            r2 = b.submit(prompt, max_new_tokens=5, temperature=0.0).result(timeout=300)
        finally:
            batch_ops.prefill_compute = orig_prefill
            b._dispatch_ragged = orig_ragged
        assert r2.token_ids == r1.token_ids
        assert compute_calls == [], compute_calls
        t2 = b.timeline.get(r2.request_id)
        assert t2.prefix_tier == "remote"
        assert all(c["prefix_hit"] for c in t2.prefill_chunks)
        assert migrator.migrations_total == 1
        # the transfer was paid ONCE: a third request hits B locally
        r3 = b.submit(prompt, max_new_tokens=5, temperature=0.0).result(timeout=300)
        assert r3.token_ids == r1.token_ids
        assert b.timeline.get(r3.request_id).prefix_tier == "device"
        assert migrator.migrations_total == 1
    finally:
        a.stop()
        b.stop()


def test_monolithic_prompt_migrates_whole_prefill(engine_setup):
    """Short prompts (≤ one chunk) migrate through the whole-prompt
    prefill cache key — the monolithic admission path's twin."""
    cfg, params = engine_setup
    index, a, b, migrator = _wire_pair(cfg, params)
    a.start()
    b.start()
    try:
        prompt = "short sys"  # < 16 tokens: monolithic bucketed prefill
        r1 = a.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        _advertise(index, a)
        from gofr_tpu.serving import batch as batch_ops
        calls = []
        orig = batch_ops.prefill_compute
        batch_ops.prefill_compute = lambda *a_, **k_: (
            calls.append(1) or orig(*a_, **k_)
        )
        try:
            r2 = b.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        finally:
            batch_ops.prefill_compute = orig
        assert r2.token_ids == r1.token_ids
        assert calls == []
        assert b.timeline.get(r2.request_id).prefix_tier == "remote"
        assert migrator.migrations_total == 1
    finally:
        a.stop()
        b.stop()


def test_stale_advertisement_degrades_to_compute_miss(engine_setup):
    """An advertisement naming entries the source no longer holds (or a
    source with no transport) must degrade to a plain compute miss —
    same tokens, no error, no partial corruption."""
    cfg, params = engine_setup
    index, a, b, migrator = _wire_pair(cfg, params)
    # poison the index: advertise keys A never cached
    index.observe("A", 99, [["chunkpfx:16:0:16:deadbeef", "device"]])
    a.start()
    b.start()
    try:
        prompt = "never cached anywhere " * 3
        cold = a.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        r = b.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        assert r.token_ids == cold.token_ids
        assert b.timeline.get(r.request_id).prefix_tier == "miss"
        assert migrator.migrations_total == 0
        # now a REAL advertisement, but the source forgot the entries
        # (evicted between the beat and the fetch): contiguous-prefix
        # contract keeps whatever was fetched, computes the rest
        _advertise(index, a, seq=100)
        a._prefix_cache.clear()
        r2 = b.submit(prompt + "x", max_new_tokens=4, temperature=0.0).result(timeout=300)
        assert r2.finish_reason in ("stop", "length")
    finally:
        a.stop()
        b.stop()


def test_migration_fetch_failure_degrades_to_reprefill(engine_setup):
    """The source replica dying mid-transfer (fetcher raises) is a clean
    degrade: the admitting replica re-prefills, token-identical."""
    cfg, params = engine_setup
    index = PrefixIndex()
    a = make_engine(cfg, params)
    migrator = KVMigrator("B", index)
    b = make_engine(cfg, params, kv_migrator=migrator)

    def dead_fetch(keys):
        raise ConnectionError("source replica died mid-transfer")

    migrator.add_peer("A", dead_fetch)
    a.start()
    b.start()
    try:
        prompt = "prefix on a dead source " * 3
        r1 = a.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        _advertise(index, a)
        r2 = b.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        assert r2.token_ids == r1.token_ids
        assert migrator.migrations_total == 0
        assert migrator.failed_fetches_total == 1
        t2 = b.timeline.get(r2.request_id)
        # committed chunk spans stay contiguous and cover the prompt
        # exactly once — the double-prefill audit's invariant
        spans = sorted(
            (c["start"], c["start"] + c["tokens"]) for c in t2.prefill_chunks
        )
        pos = 0
        for start, end in spans:
            assert start == pos, t2.prefill_chunks
            pos = end
        assert pos == r2.prompt_tokens
    finally:
        a.stop()
        b.stop()


def test_warm_ttft_beats_cold_by_2x(engine_setup):
    """The perf claim on the CPU-verifiable axis: a fully-migrated
    warm-prefix admission (zero prefill dispatches) reaches its first
    token ≥2x faster than the cold prefill of the same prompt."""
    cfg, params = engine_setup
    index, a, b, _ = _wire_pair(cfg, params)
    a.start()
    b.start()
    try:
        # warm every executable on BOTH engines off the clock
        for eng in (a, b):
            eng.submit("w" * 70, max_new_tokens=2, temperature=0.0).result(timeout=300)
        prompt = "repeated system prompt under test " * 2  # 68 tokens
        cold = [
            a.submit(prompt + "", max_new_tokens=2, temperature=0.0)
            .result(timeout=300).ttft_s
            for _ in range(5)
        ][0]  # first submit is the only true cold one
        _advertise(index, a, seq=2)
        warm = sorted(
            b.submit(prompt, max_new_tokens=2, temperature=0.0)
            .result(timeout=300).ttft_s
            for _ in range(5)
        )[2]  # p50 of the warm path (first pays the one-time transfer)
        assert warm * 2 <= cold, (warm, cold)
    finally:
        a.stop()
        b.stop()


# -- serialized page transfer over the real HTTP surface -----------------------

def test_http_kv_fetch_serves_migration_over_the_wire(engine_setup):
    """End-to-end remote half: replica A behind a real HTTP app serves
    ``/kv/fetch``; replica B's migrator, wired through
    ``HTTPReplica.fetch_kv``, admits A's prefix over the serialized page
    transfer — token-identical, remote-tier attributed."""
    import threading as _threading
    import time as _time
    import urllib.request

    import gofr_tpu
    from gofr_tpu.config import MapConfig
    from gofr_tpu.serving.handlers import register_generation_routes
    from gofr_tpu.serving.router import HTTPReplica
    from gofr_tpu.testutil import new_server_configs

    cfg, params = engine_setup
    a = make_engine(cfg, params)
    ports = new_server_configs(set_env=False)
    config = MapConfig(
        {"HTTP_PORT": str(ports.http_port), "GRPC_PORT": str(ports.grpc_port),
         "METRICS_PORT": str(ports.metrics_port), "APP_NAME": "kv-fetch-a",
         "LOG_LEVEL": "ERROR"},
        use_env=False,
    )
    app = gofr_tpu.App(config)
    register_generation_routes(app, a)
    thread = _threading.Thread(target=app.run, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{ports.http_port}"
    deadline = _time.time() + 15
    while _time.time() < deadline:
        try:
            urllib.request.urlopen(base + "/.well-known/alive", timeout=1)
            break
        except OSError:
            _time.sleep(0.05)

    index = PrefixIndex()
    migrator = KVMigrator("B", index)
    b = make_engine(cfg, params, kv_migrator=migrator)
    remote = HTTPReplica("A", base)
    migrator.add_peer("A", remote.fetch_kv)
    b.start()
    try:
        prompt = "wire transfer prefix " * 3
        r1 = a.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        _advertise(index, a)
        # raw endpoint contract: present keys encoded, absent keys omitted
        keys = [row[0] for row in a.prefix_advertisement()][:3]
        fetched = remote.fetch_kv(keys + ["chunkpfx:16:0:16:absent"])
        assert set(fetched) == set(keys)
        for value in fetched.values():
            assert len(value) == 3  # (last_logits, k_slab, v_slab)
        # and the full migration path over the wire
        r2 = b.submit(prompt, max_new_tokens=4, temperature=0.0).result(timeout=300)
        assert r2.token_ids == r1.token_ids
        assert b.timeline.get(r2.request_id).prefix_tier == "remote"
        assert migrator.migrations_total >= 1
    finally:
        b.stop()
        remote.close()
        app.stop()
        a.stop()
        thread.join(timeout=15)


# -- review-pass regressions ---------------------------------------------------

def test_peer_reads_are_non_mutating_peeks():
    """Serving a peer fetch must not promote host-tier entries into the
    owner's device LRU or destructively pop its only host copy."""
    import jax.numpy as jnp

    cache = TieredPrefixCache(max_entries=1, spill_bytes=1 << 20)
    cache.put("old", (jnp.full((4,), 1.0),))
    cache.put("new", (jnp.full((4,), 2.0),))  # evicts "old" → host tier
    assert cache.flush(5.0)
    assert cache.stats()["host"]["entries"] == 1

    class Owner:
        _prefix_cache = cache

    fetch = local_engine_fetcher(Owner())
    got = fetch(["old", "new", "absent"])
    assert set(got) == {"old", "new"}
    # the host copy survived and the device LRU was not reshuffled
    assert cache.stats()["host"]["entries"] == 1
    assert cache._device.keys() == ["new"]
    # a direct peek of a host entry returns HOST arrays (no promotion)
    assert isinstance(cache.peek("old")[0], np.ndarray)
    cache.close()


def test_migrator_backs_off_a_failing_peer():
    """A failed peer fetch suppresses that peer for failure_backoff_s —
    a dead replica's stale advertisements must not stall every
    admission behind its transport timeout."""
    idx = PrefixIndex()
    idx.observe("A", 1, [["c0", "device"]])
    migrator = KVMigrator("B", idx, failure_backoff_s=30.0)
    calls = []

    def failing(keys):
        calls.append(list(keys))
        raise ConnectionError("peer down")

    migrator.add_peer("A", failing)
    assert migrator.fetch_chain([(0, 16, "c0")]) == []
    assert migrator.fetch_chain([(0, 16, "c0")]) == []  # suppressed
    assert len(calls) == 1
    assert migrator.failed_fetches_total == 1
    # recovery: backoff elapsed → the peer is probed again
    migrator._suppressed_until["A"] = 0.0
    migrator.add_peer("A", lambda keys: {})
    migrator.fetch_chain([(0, 16, "c0")])
    assert "A" not in migrator._suppressed_until


def test_reuse_scored_demotion_hot_prefix_outlives_cold():
    """ISSUE 14 satellite: spill-tier demotion orders by the
    timeline-observed reuse score, not raw LRU — under byte pressure a
    hot prefix's slabs outlive a one-shot prefix's even when the
    one-shot was touched more recently."""
    from gofr_tpu.serving.kv_spill import HostSpillTier
    from gofr_tpu.serving.timeline import TimelineRecorder

    rec = TimelineRecorder()
    for _ in range(5):
        rec.observe_prefix_reuse("hot")
    assert rec.reuse_count("hot") == 5 and rec.reuse_count("cold") == 0

    def val(x):
        return (np.full((10, 10), float(x)),)  # 800 bytes/entry

    scored = HostSpillTier(max_bytes=3 * 800, score=rec.reuse_count)
    scored.put("hot", val(1))       # oldest in raw LRU order
    scored.put("cold1", val(2))
    scored.put("cold2", val(3))
    scored.put("cold3", val(4))     # byte pressure: one entry must go
    assert "hot" in scored.keys()   # the hot prefix survived
    assert len(scored.keys()) == 3
    # control: an unscored tier evicts by raw LRU and loses the hot one
    lru = HostSpillTier(max_bytes=3 * 800)
    lru.put("hot", val(1))
    lru.put("cold1", val(2))
    lru.put("cold2", val(3))
    lru.put("cold3", val(4))
    assert "hot" not in lru.keys()


def test_tiered_cache_wires_reuse_score_through(engine_setup):
    """The engine wires the recorder's reuse counts into the tiered
    cache: admission-time hits feed the scorer."""
    cfg, params = engine_setup
    eng = make_engine(cfg, params, kv_spill_bytes=1 << 22)
    eng.start()
    try:
        prompt = "reuse scored prompt " * 3
        eng.submit(prompt, max_new_tokens=2, temperature=0.0).result(timeout=300)
        assert all(
            eng.timeline.reuse_count(k) == 0
            for k, _t in eng.prefix_advertisement()
        )
        eng.submit(prompt, max_new_tokens=2, temperature=0.0).result(timeout=300)
        # the second admission HIT the cached chunk chain: every boundary
        # key it walked is now observed as reused
        assert any(
            eng.timeline.reuse_count(k) > 0
            for k, _t in eng.prefix_advertisement()
        )
    finally:
        eng.stop()
