"""Disaggregated prefill/decode serving (ROADMAP item 2, ISSUE 14):
crash-safe KV handoff, token-streaming remote transport, and the
role-split router policy.

The acceptance lens:

- a prefill replica's ``prefill_only`` admission leaves the prompt KV in
  its prefix cache and retires with finish_reason ``handoff`` — and a
  decode replica admitting with ``handoff_from`` pulls the chain under
  the ``kv.handoff`` two-phase-commit discipline with ZERO prefill
  compute, token-identical to a unified replica;
- every interruption — the source dying, the destination dying
  mid-handoff (warm restart with the fetch in flight), a transport
  fault at ``kv.handoff`` — degrades to re-prefill: token-identity with
  the unified path, exactly one terminal state, chunk-span contiguity
  audit clean, leaktrace balanced after drain (seeds 101/202/303);
- a remote (HTTP) replica STREAMS tokens: TTFT decoupled from
  completion, mid-stream cancel stops the remote decode within one
  block, and a ``stream.remote`` tear maps to the typed-retriable set.
"""

from __future__ import annotations

import threading
import time

import jax
import pytest

from gofr_tpu import chaos
from gofr_tpu.chaos.injector import ChaosInjector
from gofr_tpu.http.errors import (
    ErrorDeadlineExceeded,
    ErrorServiceUnavailable,
)
from gofr_tpu.models import llama
from gofr_tpu.serving import (
    ByteTokenizer,
    EngineConfig,
    KVMigrator,
    PrefixIndex,
    ServingEngine,
    local_engine_fetcher,
)
from gofr_tpu.serving.membership import Heartbeat
from gofr_tpu.serving.router import LocalReplica, Router, RouterConfig

CHAOS_SEEDS = (101, 202, 303)

# a prompt long enough to chunk (4+ chunks of 16) — the handoff moves a
# real chunk-boundary chain, not one monolithic entry
CHUNKED_PROMPT = "the disaggregated system prompt " * 3
SHORT_PROMPT = "short sys"


@pytest.fixture(scope="module")
def engine_setup():
    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def mk(cfg, params, role="unified", migrator=None, **kw):
    defaults = dict(
        max_slots=6, max_seq_len=128, prefill_buckets=(16,), max_queue=64,
        prefill_chunk_tokens=16, prefix_cache_entries=64, role=role,
    )
    defaults.update(kw)
    return ServingEngine(
        cfg, params, EngineConfig(**defaults), ByteTokenizer(),
        kv_migrator=migrator,
    )


def wire_pair(cfg, params, **kw):
    """A prefill replica + a decode replica whose migrator holds a
    direct (colocated) transport to it."""
    index = PrefixIndex()
    source = mk(cfg, params, role="prefill", **kw)
    migrator = KVMigrator("B", index)
    sink = mk(cfg, params, role="decode", migrator=migrator, **kw)
    migrator.add_peer("A", local_engine_fetcher(source))
    return index, source, sink, migrator


def assert_contiguous_chunks(tl, prompt_tokens):
    """The chunk-span contiguity audit: within each tenancy run the
    committed spans abut, and the final run covers the prompt once."""
    runs: list[list] = [[]]
    for c in tl.prefill_chunks:
        if c["start"] == 0 and runs[-1]:
            runs.append([])
        runs[-1].append(c)
    for run in runs:
        pos = 0
        for c in run:
            assert c["start"] == pos, (tl.request_id, tl.prefill_chunks)
            pos = c["start"] + c["tokens"]
    if tl.prefill_chunks and (tl.decode_tokens or "first_token" in tl.phases):
        assert sum(c["tokens"] for c in runs[-1]) == prompt_tokens, (
            tl.request_id, tl.prefill_chunks, prompt_tokens,
        )


# ---------------------------------------------------------- prefill_only


def test_prefill_only_retires_with_handoff_and_emits_nothing(engine_setup):
    cfg, params = engine_setup
    eng = mk(cfg, params, role="prefill")
    eng.start()
    try:
        frames: list = []
        r = eng.submit(
            CHUNKED_PROMPT, max_new_tokens=1, temperature=0.0,
            prefill_only=True,
            stream_cb=lambda t, p, d: frames.append((t, d)),
        ).result(timeout=300)
        assert r.finish_reason == "handoff"
        assert r.token_ids == [] and r.completion_tokens == 0
        # the DECODE replica owns the stream: a prefill phase must not
        # double-serve the first token (only the terminal frame fires)
        assert [f for f in frames if not f[1]] == []
        tl = eng.timeline.get(r.request_id)
        assert tl.terminal_marks == 1 and tl.finish_reason == "handoff"
        # the handoff payload is in the cache, advertised
        assert eng.prefix_advertisement()
    finally:
        eng.stop()


def test_handoff_token_identity_zero_prefill_compute(engine_setup):
    """THE handoff acceptance: the decode replica admits the handed-off
    chain with zero prefill-compute dispatches, token-identical to a
    unified replica serving the same prompt."""
    cfg, params = engine_setup
    _index, a, b, migrator = wire_pair(cfg, params, kv_page_size=8)
    ref = mk(cfg, params, kv_page_size=8)
    a.start(); b.start(); ref.start()
    try:
        for prompt, max_new in ((CHUNKED_PROMPT, 5), (SHORT_PROMPT, 4)):
            r0 = ref.submit(
                prompt, max_new_tokens=max_new, temperature=0.0
            ).result(timeout=300)
            rp = a.submit(
                prompt, max_new_tokens=1, temperature=0.0, prefill_only=True,
            ).result(timeout=300)
            assert rp.finish_reason == "handoff"
            from gofr_tpu.serving import batch as batch_ops

            calls: list = []
            orig_prefill = batch_ops.prefill_compute
            orig_ragged = b._dispatch_ragged
            batch_ops.prefill_compute = lambda *a_, **k_: (
                calls.append("prefill") or orig_prefill(*a_, **k_)
            )
            b._dispatch_ragged = lambda *a_, **k_: (
                calls.append("ragged") or orig_ragged(*a_, **k_)
            )
            try:
                r1 = b.submit(
                    prompt, max_new_tokens=max_new, temperature=0.0,
                    handoff_from="A",
                ).result(timeout=300)
            finally:
                batch_ops.prefill_compute = orig_prefill
                b._dispatch_ragged = orig_ragged
            assert r1.token_ids == r0.token_ids
            assert calls == [], calls
            tl = b.timeline.get(r1.request_id)
            assert tl.prefix_tier == "remote"
            assert tl.terminal_marks == 1
            assert_contiguous_chunks(tl, r1.prompt_tokens)
        assert migrator.handoffs_total == 2
    finally:
        a.stop(); b.stop(); ref.stop()


def test_incomplete_chain_fails_whole_handoff_then_reprefills(engine_setup):
    """The 2PC audit: a source that lost part of the chain mid-handoff
    (device LRU eviction between advertisement and fetch) fails the
    WHOLE handoff — the decode replica re-prefills from the prompt, and
    never commits the partial chain the handoff believed complete."""
    cfg, params = engine_setup
    _index, a, b, migrator = wire_pair(cfg, params)
    ref = mk(cfg, params)
    a.start(); b.start(); ref.start()
    try:
        r0 = ref.submit(
            CHUNKED_PROMPT, max_new_tokens=5, temperature=0.0
        ).result(timeout=300)
        a.submit(
            CHUNKED_PROMPT, max_new_tokens=1, temperature=0.0,
            prefill_only=True,
        ).result(timeout=300)
        # the source loses a MIDDLE chunk: evict one chunk-boundary key
        keys = [k for k, _t in a.prefix_advertisement(128)
                if k.startswith("chunkpfx:")]
        assert len(keys) >= 3
        victim = sorted(keys, key=lambda k: int(k.split(":")[2]))[1]
        a._prefix_cache.evict(victim)
        before = migrator.handoffs_total
        r1 = b.submit(
            CHUNKED_PROMPT, max_new_tokens=5, temperature=0.0,
            handoff_from="A",
        ).result(timeout=300)
        assert r1.token_ids == r0.token_ids  # degraded, never corrupted
        assert migrator.handoffs_total == before  # no partial admit
        tl = b.timeline.get(r1.request_id)
        assert tl.terminal_marks == 1
        assert_contiguous_chunks(tl, r1.prompt_tokens)
    finally:
        a.stop(); b.stop(); ref.stop()


# ------------------------------------------------- handoff-interrupted chaos


@pytest.mark.chaos
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_handoff_interrupted_chaos(seed):
    """Handoff-interrupted seeds (ISSUE 14 acceptance): transport faults
    at ``kv.handoff`` plus the source dying for good mid-run. Every
    admission — handed off, torn, or fully re-prefilled — must be
    token-identical to the unified path, reach exactly one terminal
    state, keep its committed chunk spans contiguous, and leave the
    reclaim ledger balanced after drain."""
    from gofr_tpu.analysis import leaktrace

    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    leak_mon = leaktrace.install()
    try:
        index, a, b, migrator = wire_pair(
            cfg, params, prefix_cache_entries=8, kv_spill_bytes=1 << 22,
        )
        source_dead = threading.Event()
        inner = local_engine_fetcher(a)

        def dying_fetch(keys):
            if source_dead.is_set():
                raise ConnectionError("prefill source died mid-handoff")
            return inner(keys)

        migrator._peers["A"] = dying_fetch
        migrator.failure_backoff_s = 0.0  # every admission re-probes
        ref = mk(cfg, params)
        a.start(); b.start(); ref.start()
        try:
            reference = ref.submit(
                CHUNKED_PROMPT, max_new_tokens=4, temperature=0.0
            ).result(timeout=300)
            a.submit(
                CHUNKED_PROMPT, max_new_tokens=1, temperature=0.0,
                prefill_only=True,
            ).result(timeout=300)
            results = []
            with chaos.active(ChaosInjector(
                seed, {"kv.handoff": 0.6, "kv.spill": 0.3}, max_faults=4,
            )):
                for _ in range(4):
                    results.append(b.submit(
                        CHUNKED_PROMPT, max_new_tokens=4, temperature=0.0,
                        handoff_from="A",
                    ).result(timeout=300))
                    b._prefix_cache.clear()  # every admission re-fetches
                source_dead.set()  # the source dies for good
                for _ in range(4):
                    results.append(b.submit(
                        CHUNKED_PROMPT, max_new_tokens=4, temperature=0.0,
                        handoff_from="A",
                    ).result(timeout=300))
                    b._prefix_cache.clear()
            for r in results:
                # never corrupt KV, never double-serve
                assert r.token_ids == reference.token_ids
                tl = b.timeline.get(r.request_id)
                assert tl is not None and tl.terminal_marks == 1
                assert_contiguous_chunks(tl, r.prompt_tokens)
            assert b.drain(deadline_s=60) is True
        finally:
            for eng in (a, b, ref):
                if eng._running:
                    eng.stop()
    finally:
        leaktrace.uninstall()
    leak_mon.check()  # no leaked pages/slots/timelines after drain


@pytest.mark.chaos
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_destination_death_mid_handoff_requeues_and_reprefills(seed):
    """The DESTINATION dying mid-handoff: a warm restart fires while the
    decode replica's admission thread is blocked inside the handoff
    fetch. The quarantined thread must commit NOTHING when it thaws
    (retired-thread gate after the fetch), and the requeued request
    re-admits on the rebuilt engine — token-identical, exactly one
    terminal state."""
    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed % 7))
    index, a, b, migrator = wire_pair(cfg, params)
    ref = mk(cfg, params)
    fetch_started = threading.Event()
    release = threading.Event()
    inner = local_engine_fetcher(a)

    def gated_fetch(keys):
        fetch_started.set()
        release.wait(timeout=30)
        return inner(keys)

    migrator._peers["A"] = gated_fetch
    a.start(); b.start(); ref.start()
    try:
        reference = ref.submit(
            CHUNKED_PROMPT, max_new_tokens=4, temperature=0.0
        ).result(timeout=300)
        a.submit(
            CHUNKED_PROMPT, max_new_tokens=1, temperature=0.0,
            prefill_only=True,
        ).result(timeout=300)
        fut = b.submit(
            CHUNKED_PROMPT, max_new_tokens=4, temperature=0.0,
            handoff_from="A",
        )
        assert fetch_started.wait(timeout=60)
        # the destination dies mid-handoff: the engine thread is inside
        # the fetch, so the restart quarantine-leaks it and requeues the
        # token-less request on the rebuilt engine
        assert b.warm_restart(join_timeout=0.3) is True
        release.set()  # the old thread thaws — and must retire silently
        r = fut.result(timeout=300)
        assert r.token_ids == reference.token_ids
        tl = b.timeline.get(r.request_id)
        assert tl is not None and tl.terminal_marks == 1
        assert_contiguous_chunks(tl, r.prompt_tokens)
        # still servable after the quarantine
        probe = b.submit("probe", max_new_tokens=2).result(timeout=60)
        assert probe.finish_reason in ("stop", "length")
    finally:
        a.stop(); b.stop(); ref.stop()


# ------------------------------------------------- role-split router e2e


def test_router_splits_prefill_and_decode_roles(engine_setup):
    """End-to-end role-split routing: the router runs the prefill phase
    on the prefill pool, the decode phase (with the handoff hint) on the
    decode pool, and the client stream comes off the decode replica."""
    cfg, params = engine_setup
    index, a, b, migrator = wire_pair(cfg, params)
    # wide liveness windows: these are routing-policy tests, and a
    # cold jit compile during the prefill phase must not age the single
    # observed beat past the down timer mid-test
    router = Router(RouterConfig(
        heartbeat_s=0.05, suspect_after_s=60.0, down_after_s=120.0,
    ))
    router.add_replica(LocalReplica("A", a, role="prefill"))
    router.add_replica(LocalReplica("B", b, role="decode"))
    router.membership.observe(Heartbeat("A", 1, role="prefill"))
    router.membership.observe(Heartbeat("B", 1, role="decode"))
    a.start(); b.start()
    try:
        tokens: list = []
        fut = router.submit(
            CHUNKED_PROMPT, max_new_tokens=5, temperature=0.0,
            stream_cb=lambda t, p, d: tokens.append((t, d)),
        )
        r = fut.result(timeout=300)
        assert getattr(r, "replica_id", None) == "B"
        assert router.handoffs_total == 1
        assert len([t for t, d in tokens if not d]) == len(r.token_ids)
        assert b.timeline.get(r.request_id).prefix_tier == "remote"
    finally:
        router.stop(); a.stop(); b.stop()


def test_router_degrades_when_prefill_pool_refuses(engine_setup):
    """Crash-safety degrade: every prefill replica refusing admission
    (draining) must not lose the request — the decode pool re-prefills
    and serves it whole."""
    cfg, params = engine_setup
    index, a, b, migrator = wire_pair(cfg, params)
    # wide liveness windows: these are routing-policy tests, and a
    # cold jit compile during the prefill phase must not age the single
    # observed beat past the down timer mid-test
    router = Router(RouterConfig(
        heartbeat_s=0.05, suspect_after_s=60.0, down_after_s=120.0,
    ))
    router.add_replica(LocalReplica("A", a, role="prefill"))
    router.add_replica(LocalReplica("B", b, role="decode"))
    router.membership.observe(Heartbeat("A", 1, role="prefill"))
    router.membership.observe(Heartbeat("B", 1, role="decode"))
    b.start()  # A never starts: its submit raises retriable (draining)
    a._draining = True
    try:
        r = router.submit(
            CHUNKED_PROMPT, max_new_tokens=4, temperature=0.0,
        ).result(timeout=300)
        assert r.finish_reason in ("stop", "length")
        assert getattr(r, "replica_id", None) == "B"
        assert router.handoff_degraded_total >= 1
        assert router.handoffs_total == 0
    finally:
        router.stop(); b.stop(); a.stop()


@pytest.mark.chaos
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_expired_request_never_crosses_disagg_boundary(engine_setup, seed):
    """The deadline-propagation acceptance for the role-split tier
    (docs/static-analysis.md#deadlinecheck): an already-expired request
    submitted through the disagg router 504s at the router's deadline
    gate WITHOUT crossing the prefill→decode boundary or opening a
    remote stream — under the runtime deadline tracer with zero budget
    violations, and every crossing it DOES observe is a site the static
    boundary table knows."""
    from gofr_tpu.analysis import deadlinetrace
    from gofr_tpu.analysis.deadlinecheck import (
        build_boundary_table,
        check_deadline_coverage,
    )

    cfg, params = engine_setup
    index, a, b, migrator = wire_pair(cfg, params)
    router = Router(RouterConfig(
        heartbeat_s=0.05, suspect_after_s=60.0, down_after_s=120.0,
    ))
    router.add_replica(LocalReplica("A", a, role="prefill"))
    router.add_replica(LocalReplica("B", b, role="decode"))
    router.membership.observe(Heartbeat("A", 1, role="prefill"))
    router.membership.observe(Heartbeat("B", 1, role="decode"))
    a.start(); b.start()
    mon = deadlinetrace.install()
    try:
        with chaos.active(ChaosInjector(
            seed, {"router.route": 0.5}, max_faults=2,
        )):
            # the deadline gate sits BEFORE the router.route chaos seam:
            # an expired request must 504, never fault-and-retry onward
            with pytest.raises(ErrorDeadlineExceeded):
                res = router.submit(
                    CHUNKED_PROMPT, max_new_tokens=4, temperature=0.0,
                    deadline=1e-9,
                )
                if hasattr(res, "result"):
                    res.result(timeout=60)
    finally:
        deadlinetrace.uninstall()
        router.stop(); a.stop(); b.stop()
    mon.check()  # zero budget violations
    crossed = mon.observed_sites()
    assert "Router.submit" in crossed
    # the 504 settles at the router: the request never reaches a
    # replica, the engine admission, or the remote stream transport
    assert crossed.isdisjoint({
        "LocalReplica.submit", "ServingEngine.submit", "HTTPReplica.submit",
        "remote.run_stream", "KVMigrator.fetch_handoff",
    }), crossed
    import os as _os
    table = build_boundary_table(
        [_os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), "gofr_tpu")]
    )
    assert check_deadline_coverage(mon.export(), table) == []


# ------------------------------------------------- remote token streaming


@pytest.fixture(scope="module")
def http_replica(engine_setup):
    """One real engine behind a real HTTP app + an HTTPReplica handle,
    warmed so jit compiles don't masquerade as TTFT."""
    import urllib.request

    import gofr_tpu
    from gofr_tpu.config import MapConfig
    from gofr_tpu.serving.handlers import register_generation_routes
    from gofr_tpu.serving.router import HTTPReplica
    from gofr_tpu.testutil import new_server_configs

    cfg, params = engine_setup
    eng = mk(cfg, params, max_seq_len=256)
    ports = new_server_configs(set_env=False)
    config = MapConfig(
        {"HTTP_PORT": str(ports.http_port), "GRPC_PORT": str(ports.grpc_port),
         "METRICS_PORT": str(ports.metrics_port), "APP_NAME": "disagg-stream",
         "LOG_LEVEL": "ERROR"},
        use_env=False,
    )
    app = gofr_tpu.App(config)
    register_generation_routes(app, eng)
    thread = threading.Thread(target=app.run, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{ports.http_port}"
    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            urllib.request.urlopen(base + "/.well-known/alive", timeout=1)
            break
        except OSError:
            time.sleep(0.05)
    replica = HTTPReplica("A", base)
    # warm both admission shapes (monolithic bucket + chunked route)
    replica.submit("warm here now", max_new_tokens=64,
                   temperature=0.0).result(timeout=300)
    replica.submit(CHUNKED_PROMPT, max_new_tokens=8,
                   temperature=0.0).result(timeout=300)
    yield replica, eng
    replica.close()
    app.stop()
    eng.stop()
    thread.join(timeout=15)


def test_remote_stream_ttft_decoupled_from_completion(http_replica):
    """THE streaming acceptance: a remote replica's first token reaches
    the router while the generation is still running — remote TTFT is no
    longer capped at full-completion latency."""
    replica, _eng = http_replica
    events: list = []
    t0 = time.monotonic()
    fut = replica.submit(
        "tell a story", max_new_tokens=60, temperature=0.0,
        stream_cb=lambda t, p, d: events.append((time.monotonic() - t0, t, d)),
    )
    r = fut.result(timeout=300)
    e2e = time.monotonic() - t0
    token_times = [e[0] for e in events if not e[2]]
    assert len(token_times) == len(r.token_ids) == 60
    # decoupled: the first token lands in the first half of the stream's
    # wall time (unary transport put it AT completion, by construction)
    assert token_times[0] < e2e * 0.5, (token_times[0], e2e)
    assert r.ttft_s < e2e * 0.5
    assert events[-1][2] is True  # terminal frame after the tokens


def test_remote_stream_cancel_stops_decode_within_a_block(http_replica):
    """Mid-stream client cancel crosses the cancel wire and retires the
    remote row at the next block sync — a canceled hedge twin stops
    burning decode steps instead of running 200 tokens to the end."""
    replica, eng = http_replica
    got: list = []
    fut = replica.submit(
        "cancel target xy", max_new_tokens=200, temperature=0.0,
        stream_cb=lambda t, p, d: got.append((t, d)),
    )
    while len([g for g in got if not g[1]]) < 3:
        time.sleep(0.002)
    replica.cancel(fut.request_id)
    r = fut.result(timeout=300)
    streamed = len([g for g in got if not g[1]])
    assert r.finish_reason == "cancel"
    # "within one block": the engine retires at the next sync — bound by
    # what was already decoded when the cancel landed plus the in-flight
    # blocks (block size x sync depth), far below the 200-token budget
    assert streamed <= 3 + 4 * eng._block_steps * (eng._sync_every + 2), streamed
    # the engine resolves the future BEFORE ringing the timeline
    # (_try_resolve order): poll briefly for the completed record
    deadline = time.monotonic() + 5.0
    canceled_tls: list = []
    while time.monotonic() < deadline and not canceled_tls:
        canceled_tls = [
            t for t in eng.timeline.completed()
            if t.finish_reason == "cancel"
        ]
        time.sleep(0.01)
    assert canceled_tls, "no cancel timeline ringed"
    assert canceled_tls[-1].terminal_marks == 1


def test_stream_remote_tear_maps_to_typed_retriable(http_replica):
    """A transport tear mid-stream (the stream.remote chaos point) must
    surface as a RETRIABLE_ERRORS member — the router's failover/claim
    machinery treats remote streams exactly like local ones."""
    from gofr_tpu.serving.router import RETRIABLE_ERRORS

    replica, _eng = http_replica
    with chaos.active(ChaosInjector(
        101, {"stream.remote": 1.0}, max_faults=1,
    )):
        fut = replica.submit(
            "tear this stream", max_new_tokens=20, temperature=0.0,
            stream_cb=lambda t, p, d: None,
        )
        exc = fut.exception(timeout=300)
    assert exc is not None and isinstance(exc, RETRIABLE_ERRORS), exc


def test_stream_wire_format_id_frame_first(http_replica):
    """The wire contract (docs/serving.md): id frame, token frames,
    terminal frame with finish_reason + usage, [DONE]."""
    import json as json_mod
    import urllib.request

    replica, _eng = http_replica
    req = urllib.request.Request(
        replica.address + "/generate/stream",
        data=json_mod.dumps(
            {"prompt": "wire format probe", "max_tokens": 3,
             "temperature": 0}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    frames = []
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("data:"):
                frames.append(line[5:].strip())
    assert frames[-1] == "[DONE]"
    events = [json_mod.loads(f) for f in frames[:-1]]
    assert "id" in events[0]
    tokens = [e for e in events if "token" in e]
    assert len(tokens) == 3 and all("text" in e for e in tokens)
    terminal = events[-1]
    assert terminal["finish_reason"] in ("stop", "length")
    assert "usage" in terminal


# ---------------------------------------------------------- autoscaler


class _ScalerHarness:
    """Router + simulated pool over stub replicas, membership fed
    directly (no broker: deterministic)."""

    def __init__(self, **cfg_kw):
        from gofr_tpu.serving.autoscaler import (
            Autoscaler,
            AutoscalerConfig,
            SimulatedPoolDriver,
        )
        from gofr_tpu.testutil.replica import StubReplicaEngine

        self.router = Router(RouterConfig(heartbeat_s=0.05))
        self.stubs = {}
        self._seq = {}

        def factory(role, rid):
            stub = StubReplicaEngine(rid, tokens=3, token_interval_s=0.002)
            self.stubs[rid] = stub
            return LocalReplica(rid, stub, role=role)

        self.driver = SimulatedPoolDriver(self.router, factory)
        defaults = dict(
            interval_s=0.02, min_replicas=1, max_replicas=4,
            scale_up_wait_s=0.5, scale_down_wait_s=0.05,
            up_stable_s=0.05, down_stable_s=0.1, cooldown_s=0.08,
        )
        defaults.update(cfg_kw)
        self.scaler = Autoscaler(
            self.router, self.driver, AutoscalerConfig(**defaults),
            roles=("unified",),
        )

    def beat(self, wait=0.0, hbm=None):
        for rid in self.driver.replica_ids("unified"):
            self._seq[rid] = self._seq.get(rid, 0) + 1
            self.router.membership.observe(Heartbeat(
                rid, self._seq[rid], queue_wait_s=wait, hbm_free_frac=hbm,
            ))

    def run_until(self, cond, wait=0.0, hbm=None, timeout=8.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.beat(wait=wait, hbm=hbm)
            self.scaler.tick()
            if cond():
                return True
            time.sleep(0.02)
        return False

    def pool(self):
        return self.driver.replica_ids("unified")


def test_autoscaler_scales_up_under_queue_wait_ramp_and_down_at_idle():
    h = _ScalerHarness()
    h.driver.scale_up("unified", 1)
    h.beat()
    assert len(h.pool()) == 1
    # ramp: sustained queue-wait pressure grows the pool (hysteresis:
    # one step per cooldown, never a jump)
    assert h.run_until(lambda: len(h.pool()) >= 3, wait=2.0)
    assert h.scaler.scale_ups_total >= 2
    # idle: sustained zero wait drains it back to the floor
    assert h.run_until(lambda: len(h.pool()) == 1, wait=0.0)
    assert h.scaler.scale_downs_total >= 2
    assert h.scaler.snapshot()["roles"]["unified"]["replicas"] == h.pool()


def test_autoscaler_hbm_pressure_triggers_scale_up():
    h = _ScalerHarness()
    h.driver.scale_up("unified", 1)
    assert h.run_until(lambda: len(h.pool()) >= 2, wait=0.0, hbm=0.01)
    assert h.scaler.scale_ups_total >= 1


def test_autoscaler_hysteresis_ignores_transient_blips():
    """A single pressured tick (below up_stable_s) must not scale."""
    h = _ScalerHarness(up_stable_s=60.0, down_stable_s=60.0)
    h.driver.scale_up("unified", 1)
    for _ in range(10):
        h.beat(wait=5.0)
        h.scaler.tick()
    assert len(h.pool()) == 1 and h.scaler.scale_ups_total == 0


def test_autoscaler_respects_min_max_bounds():
    h = _ScalerHarness(max_replicas=2)
    h.driver.scale_up("unified", 1)
    assert h.run_until(lambda: len(h.pool()) == 2, wait=3.0)
    for _ in range(20):  # pressure continues: the cap holds
        h.beat(wait=3.0)
        h.scaler.tick()
        time.sleep(0.01)
    assert len(h.pool()) == 2
    # and the floor holds at idle
    assert h.run_until(lambda: len(h.pool()) == 1, wait=0.0)
    for _ in range(20):
        h.beat(wait=0.0)
        h.scaler.tick()
        time.sleep(0.01)
    assert len(h.pool()) == 1


def test_scale_decision_chaos_fault_skips_round_never_kills():
    """A faulted scale.decision round leaves the pool exactly as it was
    — the control plane misfiring degrades to no-op, never a kill."""
    h = _ScalerHarness()
    h.driver.scale_up("unified", 2)
    h.beat(wait=3.0)
    with chaos.active(ChaosInjector(
        202, {"scale.decision": 1.0}, max_faults=100,
    )):
        for _ in range(10):
            h.beat(wait=3.0)
            h.scaler.tick()
    assert len(h.pool()) == 2
    assert h.scaler.scale_ups_total == 0
    assert h.scaler.decisions_skipped_total == 10


def test_cancel_during_prefill_phase_never_runs_decode(engine_setup):
    """Review regression (ISSUE 14): a request canceled while its
    prefill phase runs must settle with the cancel result and NEVER run
    the decode phase — and a result still labeled "handoff" (cancel
    raced the prefill's completion) is relabeled before reaching the
    client."""
    import concurrent.futures

    from gofr_tpu.serving.membership import (
        ROLE_DECODE,
        ROLE_PREFILL,
    )

    class ManualHandle:
        def __init__(self, rid):
            self.replica_id = rid
            self.futures: list = []
            self.cancels: list = []

        def submit(self, prompt, **kw):
            fut = concurrent.futures.Future()
            fut.request_id = len(self.futures) + 1
            self.futures.append((fut, kw))
            return fut

        def cancel(self, request_id):
            self.cancels.append(request_id)

        def health_check(self):
            return {"status": "UP", "details": {}}

    router = Router(RouterConfig(
        heartbeat_s=0.05, suspect_after_s=60.0, down_after_s=120.0,
    ))
    p, d = ManualHandle("p"), ManualHandle("d")
    router.add_replica(p, role=ROLE_PREFILL)
    router.add_replica(d, role=ROLE_DECODE)
    router.membership.observe(Heartbeat("p", 1, role=ROLE_PREFILL))
    router.membership.observe(Heartbeat("d", 1, role=ROLE_DECODE))
    try:
        fut = router.submit("disagg cancel race", max_new_tokens=8)
        assert len(p.futures) == 1 and d.futures == []
        router.cancel(fut.request_id)
        assert p.cancels, "the in-flight prefill attempt must be canceled"

        class _R:  # the prefill completing anyway (cancel raced it)
            finish_reason = "handoff"
            token_ids: list = []

        p.futures[0][0].set_result(_R())
        result = fut.result(timeout=5)
        assert result.finish_reason == "cancel"  # never leaks "handoff"
        time.sleep(0.1)  # any (wrong) decode phase would submit async
        assert d.futures == [], "decode phase ran for a canceled request"
        # and the cancel-in-the-gap race: a decode attempt registering
        # after cancel() ran must be canceled at registration
        fut2 = router.submit("disagg cancel race two", max_new_tokens=8)
        router.cancel(fut2.request_id)
        p.futures[1][0].set_exception(
            ErrorServiceUnavailable("prefill died", retry_after=0.1)
        )
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and fut2.done() is False:
            time.sleep(0.01)
        assert fut2.done()
    finally:
        router.stop()
