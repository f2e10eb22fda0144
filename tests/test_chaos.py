"""The deterministic chaos tier (``make chaos``).

Unit tests pin the injector contract (seeded determinism, per-point
streams, fault budgets, default fault types). The ``chaos``-marked
invariant tests run the serving engine under fixed-seed fault schedules at
every registered injection point and assert the lifecycle invariant the
whole robustness layer exists for:

    every submitted request reaches EXACTLY ONE terminal state
    (completed / canceled / deadline_exceeded / shed / failed-retriable),
    its slot and KV pages are reclaimed, expired requests are never
    prefilled, drain completes within its deadline, and the engine thread
    exits cleanly — no wedge, no deadlock.

Seeds are FIXED (the point of deterministic chaos): a failure reproduces
with ``pytest tests/test_chaos.py -k <seed>`` every time. Add seeds, never
rotate them — a seed that once caught a bug is a regression test.
"""

import concurrent.futures as cf
import os
import threading
import time

import jax
import pytest

from gofr_tpu import chaos
from gofr_tpu.http.errors import (
    ErrorDeadlineExceeded,
    ErrorServiceUnavailable,
    ErrorTooManyRequests,
)
from gofr_tpu.models import llama
from gofr_tpu.native.fallback import OutOfBlocks, QueueFull
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine

CHAOS_SEEDS = (101, 202, 303)

# exceptions that count as a terminal state: shed (429), drain (503),
# queued expiry (504), and the injected transient itself (failed-retriable)
TERMINAL_ERRORS = (
    ErrorTooManyRequests,
    ErrorServiceUnavailable,
    ErrorDeadlineExceeded,
    chaos.ChaosFault,
)
TERMINAL_REASONS = {"stop", "length", "kv_exhausted", "cancel",
                    "deadline_exceeded"}


def tiny_cfg(max_seq: int = 64) -> llama.LlamaConfig:
    return llama.LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=max_seq,
    )


def make_engine(tracer=None, **cfg_kw) -> ServingEngine:
    cfg = tiny_cfg(cfg_kw.get("max_seq_len", 64))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_slots=2, max_seq_len=64, prefill_buckets=(16,),
        admission_per_step=2, max_queue=32,
    )
    defaults.update(cfg_kw)
    return ServingEngine(
        cfg, params, EngineConfig(**defaults), ByteTokenizer(cfg.vocab_size),
        tracer=tracer,
    )


# -- injector contract --------------------------------------------------------

def test_injector_is_deterministic_per_seed():
    def schedule(seed):
        inj = chaos.ChaosInjector(seed, {"decode.dispatch": 0.3})
        fired = []
        for i in range(200):
            try:
                inj.fire("decode.dispatch")
                fired.append(False)
            except chaos.ChaosFault:
                fired.append(True)
        return fired

    assert schedule(7) == schedule(7)
    assert schedule(7) != schedule(8)
    assert any(schedule(7))


def test_injector_streams_are_independent_per_point():
    inj = chaos.ChaosInjector(7, {"decode.dispatch": 1.0, "kv.alloc": 0.0})
    with pytest.raises(chaos.ChaosFault):
        inj.fire("decode.dispatch")
    inj.fire("kv.alloc")  # rate 0: never fires
    stats = inj.stats()
    assert stats["decode.dispatch"] == {"calls": 1, "faults": 1}
    assert stats["kv.alloc"] == {"calls": 1, "faults": 0}


def test_injector_rejects_unknown_points_and_caps_faults():
    with pytest.raises(ValueError):
        chaos.ChaosInjector(1, {"not.a.point": 1.0})
    inj = chaos.ChaosInjector(1, {"decode.dispatch": 1.0}, max_faults=2)
    fired = 0
    for _ in range(10):
        try:
            inj.fire("decode.dispatch")
        except chaos.ChaosFault:
            fired += 1
    assert fired == 2  # budget spent → the point goes quiet


def test_default_fault_types_match_the_seam():
    inj = chaos.ChaosInjector(1, {"kv.alloc": 1.0, "sched.submit": 1.0})
    with pytest.raises(OutOfBlocks):
        inj.fire("kv.alloc")
    with pytest.raises(QueueFull):
        inj.fire("sched.submit")


def test_install_is_exclusive_and_context_managed():
    inj = chaos.ChaosInjector(1, {})
    with chaos.active(inj):
        with pytest.raises(RuntimeError):
            chaos.install(chaos.ChaosInjector(2, {}))
    chaos.maybe_fail("decode.dispatch")  # uninstalled: plain no-op


def test_service_client_injection_point():
    """The service.request point fires inside HTTPService.request, BEFORE
    the socket — and the Retry option ladder absorbs it."""
    import http.server
    import threading as th

    from gofr_tpu.service import new_http_service
    from gofr_tpu.service.options import RetryConfig

    class Ok(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"ok")

        def log_message(self, *a):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Ok)
    th.Thread(target=server.serve_forever, daemon=True).start()
    try:
        svc = new_http_service(
            f"http://127.0.0.1:{server.server_port}", None, None, None,
            RetryConfig(max_retries=3, backoff=0.001),
        )
        inj = chaos.ChaosInjector(5, {"service.request": 1.0}, max_faults=2)
        with chaos.active(inj):
            resp = svc.get("x")  # 2 injected transport faults, then through
        assert resp.ok
        assert inj.stats()["service.request"]["faults"] == 2
    finally:
        server.shutdown()


def test_pubsub_publish_injection_point():
    from gofr_tpu.datasource.pubsub.memory import InMemoryBroker

    broker = InMemoryBroker()
    inj = chaos.ChaosInjector(5, {"pubsub.publish": 1.0}, max_faults=1)
    with chaos.active(inj):
        with pytest.raises(chaos.ChaosFault):
            broker.publish("t", b"lost")
        broker.publish("t", b"delivered")  # budget spent: goes through
    msg = broker.subscribe("t")
    assert msg is not None and msg.value == b"delivered"
    # the faulted publish never entered the log
    assert len(broker._topics["t"]) == 1


# -- the lifecycle invariant under injected faults ----------------------------

def _run_workload(eng: ServingEngine, n_requests: int = 18) -> list:
    """Mixed-traffic workload: plain, deadline-carrying, born-expired and
    canceled requests, submitted from several threads. Returns
    (kind, future-or-exception) pairs."""
    outcomes: list = []
    lock = threading.Lock()

    def submit_one(i: int) -> None:
        kind = ("plain", "deadline", "expired", "cancel")[i % 4]
        deadline = {"plain": None, "deadline": 30.0,
                    "expired": 1e-9, "cancel": None}[kind]
        try:
            fut = eng.submit(
                f"req {i} pad"[:10],
                max_new_tokens=(2, 5, 8)[i % 3],
                temperature=0.5 if i % 2 else 0.0,
                deadline=deadline,
            )
        except TERMINAL_ERRORS as exc:
            with lock:
                outcomes.append((kind, exc))
            return
        if kind == "cancel":
            eng.cancel(fut.request_id)
        with lock:
            outcomes.append((kind, fut))

    with cf.ThreadPoolExecutor(4) as ex:
        list(ex.map(submit_one, range(n_requests)))
    return outcomes


def _assert_terminal(outcomes: list, timeout: float = 120.0) -> dict:
    """Every submitted request reached exactly one terminal state."""
    counts: dict[str, int] = {}
    for kind, item in outcomes:
        if isinstance(item, BaseException):
            assert isinstance(item, TERMINAL_ERRORS), item
            counts[type(item).__name__] = counts.get(type(item).__name__, 0) + 1
            continue
        try:
            result = item.result(timeout=timeout)
            assert result.finish_reason in TERMINAL_REASONS, result.finish_reason
            counts[result.finish_reason] = counts.get(result.finish_reason, 0) + 1
        except TERMINAL_ERRORS as exc:
            counts[type(exc).__name__] = counts.get(type(exc).__name__, 0) + 1
    assert sum(counts.values()) == len(outcomes)
    return counts


def _assert_timelines_terminal(eng: ServingEngine) -> None:
    """The flight-recorder invariant (docs/observability.md): after the
    engine drains, every recorded request left a COMPLETE timeline with
    exactly one terminal phase — two marks would mean two settlement
    paths both thought they won; zero means a request vanished without
    its terminal ever being recorded."""
    timelines = eng.timeline.all()
    assert timelines, "no timelines recorded for a workload that ran"
    stale = [tl.request_id for tl in timelines if not tl.terminal]
    assert not stale, f"non-terminal timelines after drain: {stale}"
    bad_marks = {
        tl.request_id: tl.terminal_marks
        for tl in timelines if tl.terminal_marks != 1
    }
    assert not bad_marks, f"terminal marked != once: {bad_marks}"
    for tl in timelines:
        assert "submitted" in tl.phases, tl.to_dict()
        assert "terminal" in tl.phases, tl.to_dict()
        # a request that produced tokens must carry the full phase chain
        if tl.decode_tokens or "first_token" in tl.phases:
            assert "admitted" in tl.phases, tl.to_dict()
            assert "prefill_start" in tl.phases, tl.to_dict()
            assert "prefill_end" in tl.phases, tl.to_dict()


def _assert_reclaimed(eng: ServingEngine) -> None:
    deadline = time.time() + 30
    while time.time() < deadline:
        with eng._count_lock:
            live = len(eng._by_id)
        if live == 0 and all(s is None for s in eng.slots):
            break
        time.sleep(0.02)
    assert all(s is None for s in eng.slots)
    stats = eng.paged_cache.stats()
    assert stats["free_blocks"] == stats["total_blocks"], stats
    assert stats["sequences"] == 0


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_lifecycle_invariant_under_faults(seed, monkeypatch):
    from gofr_tpu.analysis import leaktrace
    from gofr_tpu.tracing import Tracer

    tracer = Tracer("chaos")  # no processor: pure open/close accounting
    # the reclaim audit, observed directly at the acquire/release sites:
    # leaktrace instruments allocator/scheduler/paged-slot/timeline
    # lifecycles for this storm; after the drain the live ledger must be
    # empty, and the observed pairs export for the static cross-check
    # (GOFR_LEAK_EXPORT, docs/static-analysis.md#leakcheck)
    leak_mon = leaktrace.install()
    try:
        eng = make_engine(tracer=tracer, kv_page_size=8)

        # pin "expired requests are never prefilled": track born-dead
        # requests
        born_dead: set[int] = set()
        real_submit = eng.submit

        def tracking_submit(prompt, **skw):
            fut = real_submit(prompt, **skw)
            if skw.get("deadline") == 1e-9:
                born_dead.add(fut.request_id)
            return fut

        monkeypatch.setattr(eng, "submit", tracking_submit)
        real_prefill = eng._prefill_into
        prefilled: set[int] = set()
        monkeypatch.setattr(
            eng, "_prefill_into",
            lambda slot, req: (
                prefilled.add(req.id), real_prefill(slot, req)
            )[1],
        )

        rates = {
            "sched.submit": 0.08,
            "sched.admit": 0.04,
            "decode.dispatch": 0.04,
            "kv.alloc": 0.10,
        }
        inj = chaos.ChaosInjector(seed, rates, max_faults=3)

        eng.start()
        try:
            with chaos.active(inj):
                outcomes = _run_workload(eng)
                counts = _assert_terminal(outcomes)
            assert counts, counts
            assert not (born_dead & prefilled), \
                "expired requests were prefilled"
            # still servable after the storm
            probe = eng.submit("probe", max_new_tokens=2).result(timeout=60)
            assert probe.finish_reason in ("stop", "length")
            _assert_reclaimed(eng)
            # drain completes within its deadline, thread exits cleanly
            assert eng.drain(deadline_s=60) is True
            assert eng._thread is None or not eng._thread.is_alive()
            assert eng.health_check()["status"] == "DOWN"  # no wedge
            # observability invariants ride the same storm: every request
            # left exactly one terminal timeline phase, and no lifecycle
            # span leaked across a single fault path
            _assert_timelines_terminal(eng)
            assert tracer.open_spans() == 0, (
                f"{tracer.open_spans()} span(s) leaked across the chaos run"
            )
        finally:
            if eng._running:
                eng.stop()
    finally:
        # the uninstall covers SETUP failures too (make_engine, injector
        # construction, start) — a failed cell must not leave the global
        # instrumentation installed, or every later parametrized cell
        # dies on the install() guard instead of its real assertion
        leaktrace.uninstall()
    # the dynamic reclaim invariant at the resource sites themselves:
    # every acquired allocator/scheduler/slot/timeline was released
    leak_mon.check()
    export_path = os.environ.get("GOFR_LEAK_EXPORT")
    if export_path:
        leaktrace.export_to(leak_mon, export_path)


def _assert_chunk_spans_never_double_prefill(eng: ServingEngine) -> None:
    """The chunked-prefill invariant: within one slot tenancy, committed
    chunk spans are contiguous and strictly increasing — a chunk cursor
    never re-commits (double-prefills) KV it already committed. A requeue
    (pool pressure, warm restart) legitimately restarts a NEW run at
    start 0; overlap or regression WITHIN a run is the bug class."""
    for tl in eng.timeline.all():
        runs: list[list] = [[]]
        for c in tl.prefill_chunks:
            if c["start"] == 0 and runs[-1]:
                runs.append([])
            runs[-1].append(c)
        for run in runs:
            pos = 0
            for c in run:
                assert c["start"] == pos, (
                    f"request {tl.request_id}: chunk committed at "
                    f"{c['start']}, expected {pos}: {tl.prefill_chunks}"
                )
                pos = c["start"] + c["tokens"]
        # a request that produced tokens finished its prefill: the final
        # run covers the whole prompt exactly once. A PREEMPTED request's
        # resume run covers prompt + already-emitted tokens (serve_ids) —
        # at least the prompt, still contiguous, never less.
        if tl.prefill_chunks and (
            tl.decode_tokens or "first_token" in tl.phases
        ):
            preempted = any(p.startswith("preempted") for p in tl.phases)
            covered = sum(c["tokens"] for c in runs[-1])
            if preempted:
                assert covered >= tl.prompt_tokens, (
                    tl.request_id, tl.prefill_chunks, tl.prompt_tokens,
                )
            else:
                assert covered == tl.prompt_tokens, (
                    tl.request_id, tl.prefill_chunks, tl.prompt_tokens,
                )


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_mid_chunk_faults_preserve_lifecycle(seed):
    """Faults landing MID-CHUNKED-PREFILL — while a step plan is being
    assembled (the sched.plan point) and while the paged pool is under
    pressure — must preserve the lifecycle invariant for partially-
    prefilled requests: slots+pages reclaimed, exactly one terminal per
    request, and a chunk cursor never double-prefills committed KV."""
    eng = make_engine(
        max_seq_len=128, prefill_buckets=(16,), prefill_chunk_tokens=8,
        max_slots=2, kv_page_size=8, kv_num_pages=20,  # tight: real pressure
    )

    rates = {
        "sched.plan": 0.05,
        "sched.admit": 0.04,
        "decode.dispatch": 0.04,
        "kv.alloc": 0.10,
    }
    inj = chaos.ChaosInjector(seed, rates, max_faults=3)

    outcomes: list = []
    lock = threading.Lock()

    def submit_one(i: int) -> None:
        # every other request is LONG (4+ chunks at chunk=8); the rest are
        # the usual short/deadline/cancel mix
        kind = ("long", "short", "long_cancel", "deadline")[i % 4]
        prompt = ("Z" * 40) if kind.startswith("long") else f"req {i}"[:8]
        deadline = 30.0 if kind == "deadline" else None
        try:
            fut = eng.submit(prompt, max_new_tokens=(2, 4)[i % 2],
                             temperature=0.0, deadline=deadline)
        except TERMINAL_ERRORS as exc:
            with lock:
                outcomes.append((kind, exc))
            return
        if kind == "long_cancel":
            eng.cancel(fut.request_id)
        with lock:
            outcomes.append((kind, fut))

    eng.start()
    try:
        with chaos.active(inj):
            with cf.ThreadPoolExecutor(4) as ex:
                list(ex.map(submit_one, range(12)))
            _assert_terminal(outcomes)
        # still servable after the storm, then drain clean
        probe = eng.submit("probe", max_new_tokens=2).result(timeout=60)
        assert probe.finish_reason in ("stop", "length")
        _assert_reclaimed(eng)
        assert eng.drain(deadline_s=60) is True
        assert eng.health_check()["status"] == "DOWN"
        _assert_timelines_terminal(eng)
        _assert_chunk_spans_never_double_prefill(eng)
    finally:
        if eng._running:
            eng.stop()


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_drain_under_decode_faults(seed):
    """Drain while faults are still firing: the remainder must fail
    retriable, slots/pages reclaimed, no deadlock on exit."""
    eng = make_engine()
    inj = chaos.ChaosInjector(seed, {"decode.dispatch": 0.1}, max_faults=5)
    eng.start()
    try:
        with chaos.active(inj):
            outcomes = _run_workload(eng, n_requests=10)
            eng.drain(deadline_s=30)
            _assert_terminal(outcomes, timeout=30)
        assert all(s is None for s in eng.slots)
        assert eng._thread is None or not eng._thread.is_alive()
    finally:
        if eng._running:
            eng.stop()


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_tenant_storm_preemption_preserves_lifecycle(seed):
    """The tenant-storm seed (docs/serving.md "Multi-tenancy"): a
    low-priority flood saturates the batch while high-priority requests
    arrive, with faults firing at the NEW seams — ``tenant.preempt``
    (a faulted preemption is a SKIPPED one, advisory by construction)
    and ``lora.upload`` (a faulted adapter upload requeues the request
    like KV-pool pressure). Asserts the lifecycle invariant over every
    request, zero high-priority deadline misses while the flood runs
    (preemption keeps the higher class inside its SLO even as faults
    thin it out), and clean reclamation after drain."""
    from gofr_tpu.serving.lora import AdapterRegistry, make_adapter
    from gofr_tpu.serving.tenancy import TenantPolicy, TenantRegistry

    cfg = tiny_cfg(64)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    lora = AdapterRegistry(max_active=3)
    lora.register(make_adapter(cfg, "bulk-lora", rank=2, seed=3, scale=4.0))
    tenants = TenantRegistry()
    tenants.set_policy(TenantPolicy(name="gold", deadline_class="interactive",
                                    deadline_s=60.0))
    tenants.set_policy(TenantPolicy(name="bulk", deadline_class="batch",
                                    deadline_s=120.0))
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_slots=2, max_seq_len=64, prefill_buckets=(16,),
                     admission_per_step=2, max_queue=64,
                     prefix_cache_entries=16, prefill_chunk_tokens=8),
        ByteTokenizer(cfg.vocab_size), lora=lora, tenants=tenants,
    )
    inj = chaos.ChaosInjector(
        seed, {"tenant.preempt": 0.3, "lora.upload": 0.3}, max_faults=3
    )
    eng.start()
    try:
        # warm the executables OUTSIDE the storm: a first-compile stall
        # must not masquerade as a deadline miss
        eng.submit("warm", max_new_tokens=2).result(timeout=120)
        eng.submit("warm-lora", max_new_tokens=2,
                   adapter_id="bulk-lora").result(timeout=120)
        with chaos.active(inj):
            low: list = []
            hi: list = []
            # the flood: ≥4x decode capacity of batch-class traffic,
            # half of it through the LoRA adapter (exercises the upload
            # seam under fault)
            for i in range(8):
                low.append(eng.submit(
                    f"low {i} xxxxxxxx"[:12], max_new_tokens=24,
                    tenant="bulk",
                    adapter_id="bulk-lora" if i % 2 else None,
                ))
            time.sleep(0.05)
            for i in range(4):
                hi.append(eng.submit(
                    f"hi {i}", max_new_tokens=3, tenant="gold",
                ))
            for fut in hi:
                result = fut.result(timeout=120)
                # ZERO high-priority deadline misses while the flood runs
                assert result.finish_reason in ("stop", "length"), (
                    f"high-priority request missed: {result.finish_reason}"
                )
            outcomes = [("plain", f) for f in low + hi]
            _assert_terminal(outcomes)
        _assert_reclaimed(eng)
        assert eng.drain(deadline_s=60) is True
        assert eng._thread is None or not eng._thread.is_alive()
        assert eng.health_check()["status"] == "DOWN"
        _assert_timelines_terminal(eng)
        _assert_chunk_spans_never_double_prefill(eng)
    finally:
        if eng._running:
            eng.stop()
        lora.close()
