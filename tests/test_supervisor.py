"""Engine supervision (serving/supervisor.py): watchdog detection,
self-healing warm restart, restart budget → WEDGED parking, and the
supervision invariant under fixed-seed chaos.

The invariant (docs/robustness.md "The engine plane"): every submitted
request still reaches EXACTLY ONE terminal state across a warm restart,
queued never-prefilled requests survive it (original deadlines intact),
slots and KV pages are re-founded cleanly, and a budget-exhausted engine
parks WEDGED instead of flapping.

Seeds are FIXED (same contract as tests/test_chaos.py): add seeds, never
rotate them.
"""

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
from gofr_tpu.serving import (
    ByteTokenizer,
    EngineConfig,
    EngineSupervisor,
    ServingEngine,
)

CHAOS_SEEDS = (101, 202, 303)

TERMINAL_ERRORS = (
    ErrorTooManyRequests,
    ErrorServiceUnavailable,
    ErrorDeadlineExceeded,
    chaos.ChaosFault,  # DeviceLost subclasses it
)
TERMINAL_REASONS = {"stop", "length", "kv_exhausted", "cancel",
                    "deadline_exceeded"}


def tiny_cfg(max_seq: int = 64) -> llama.LlamaConfig:
    return llama.LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=max_seq,
    )


class RecordingMetrics:
    def __init__(self) -> None:
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}

    def increment_counter(self, name, *labels, **kw) -> None:
        self.counters[name] = self.counters.get(name, 0) + 1

    def add_counter(self, name, value, *labels, **kw) -> None:
        self.counters[name] = self.counters.get(name, 0) + value

    def set_gauge(self, name, value, *labels, **kw) -> None:
        self.gauges[name] = value

    def record_histogram(self, name, value, *labels, **kw) -> None:
        pass


def make_engine(metrics=None, **cfg_kw) -> ServingEngine:
    cfg = tiny_cfg(cfg_kw.get("max_seq_len", 64))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_slots=2, max_seq_len=64, prefill_buckets=(16,),
        admission_per_step=2, max_queue=32,
    )
    defaults.update(cfg_kw)
    return ServingEngine(
        cfg, params, EngineConfig(**defaults), ByteTokenizer(cfg.vocab_size),
        metrics=metrics,
    )


def make_supervisor(eng, **kw) -> EngineSupervisor:
    defaults = dict(stall_s=0.25, poll_s=0.03, restart_budget=3,
                    restart_reset_s=60.0, join_timeout=0.4)
    defaults.update(kw)
    return EngineSupervisor(eng, **defaults)


def wait_for(cond, timeout: float = 30.0, msg: str = "") -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(msg or "condition not reached in time")


def probe_until_served(eng: ServingEngine, timeout: float = 120.0):
    """Submit a probe until one is actually served: a probe landing inside
    a RESTARTING window (503) or eating a leftover injected fault is part
    of the storm, not a verdict on the healed engine."""
    deadline = time.time() + timeout
    while True:
        try:
            res = eng.submit("probe", max_new_tokens=2).result(timeout=timeout)
            assert res.finish_reason in TERMINAL_REASONS
            return res
        except (*TERMINAL_ERRORS, RuntimeError):
            if time.time() > deadline:
                raise
            time.sleep(0.05)


def assert_reclaimed(eng: ServingEngine) -> None:
    wait_for(
        lambda: all(s is None for s in eng.slots) and not eng._by_id,
        msg="slots/requests not reclaimed",
    )
    if eng.paged_cache is not None:
        stats = eng.paged_cache.stats()
        assert stats["free_blocks"] == stats["total_blocks"], stats
        assert stats["sequences"] == 0


# -- warm restart mechanics ---------------------------------------------------

def test_warm_restart_requeues_queued_requests():
    """Queued, never-prefilled requests survive the restart and complete
    on the rebuilt engine — the engine was never even started, so nothing
    is in flight."""
    eng = make_engine()
    try:
        futs = [eng.submit(f"queued {i}", max_new_tokens=3) for i in range(3)]
        assert eng.warm_restart() is True
        for f in futs:
            assert f.result(timeout=60).finish_reason in TERMINAL_REASONS
        assert_reclaimed(eng)
    finally:
        eng.stop()


def test_warm_restart_quarantines_hung_thread_and_fails_inflight(monkeypatch):
    """An engine thread that cannot join: the in-flight stream fails
    RETRIABLE, the native scheduler/pool are quarantine-leaked (never
    destroyed under a live thread), and the thawed old thread retires
    itself via the identity guard instead of racing the replacement.

    The pin lives in the DECODE DISPATCH — the realistic hang shape (a
    device call that never returns). A blocking stream_cb no longer pins
    the engine thread at all: emission runs on the detok executor
    (docs/performance.md), which is exactly why the old version of this
    test stopped hanging anything."""
    from gofr_tpu.serving import batch as batch_ops

    eng = make_engine(kv_layout="paged", kv_page_size=8)
    hold = threading.Event()
    pinned = threading.Event()
    real_block = batch_ops.decode_block_paged

    def hanging_block(*args, **kw):
        if not pinned.is_set():
            pinned.set()
            hold.wait(30)  # pins the ENGINE THREAD mid-dispatch
        return real_block(*args, **kw)

    monkeypatch.setattr(batch_ops, "decode_block_paged", hanging_block)
    eng.start()
    try:
        fut = eng.submit("held in flight", max_new_tokens=40)
        assert pinned.wait(60)
        old_thread = eng._thread
        old_sched = eng._sched
        assert eng.warm_restart(join_timeout=0.2) is True
        assert old_thread.is_alive()  # hung: quarantined, not joined
        assert old_sched._closed  # leaked — marked closed, never destroyed
        with pytest.raises(ErrorServiceUnavailable) as exc_info:
            fut.result(timeout=10)
        assert exc_info.value.retry_after is not None
        # the rebuilt engine serves
        res = eng.submit("fresh", max_new_tokens=3).result(timeout=60)
        assert res.finish_reason in TERMINAL_REASONS
        hold.set()  # thaw: the identity guard must retire the old thread
        old_thread.join(timeout=30)
        assert not old_thread.is_alive()
        assert eng._thread is not old_thread and eng._thread.is_alive()
        assert_reclaimed(eng)
    finally:
        hold.set()
        eng.stop()


def test_warm_restart_stands_down_for_drain():
    """drain() racing a restart resolves to exactly one winner."""
    eng = make_engine()
    eng.start()
    stop_flag = threading.Event()
    restart_results = []

    def restart_loop():
        while not stop_flag.is_set():
            try:
                restart_results.append(eng.warm_restart(join_timeout=2.0))
            except Exception as exc:  # pragma: no cover - would fail below
                restart_results.append(exc)
            time.sleep(0.01)

    t = threading.Thread(target=restart_loop, daemon=True)
    t.start()
    try:
        time.sleep(0.05)  # let at least one restart interleave
        assert eng.drain(deadline_s=30) is True
        stop_flag.set()
        t.join(timeout=30)
        assert not any(isinstance(r, Exception) for r in restart_results)
        # after the drain won, every further restart stands down
        assert eng.warm_restart() is False
        assert eng.health_check()["status"] == "DOWN"
        assert eng._thread is None or not eng._thread.is_alive()
        with pytest.raises(ErrorServiceUnavailable):
            eng.submit("late", max_new_tokens=2)
    finally:
        stop_flag.set()
        if eng._running:
            eng.stop()


def test_warm_restart_rebuild_failure_settles_requeued():
    """The rebuild itself can fail (a real device loss may leave the
    allocator refusing pools for a while): the requeued requests live only
    in warm_restart's local list at that point — they must be settled
    retriable before the failure escapes, never stranded on futures the
    supervisor's retry can no longer see."""
    eng = make_engine()
    futs = [eng.submit(f"queued {i}", max_new_tokens=3) for i in range(2)]

    def broken_rebuild():
        raise RuntimeError("device still refusing allocations")

    eng._make_dense_cache = broken_rebuild
    with pytest.raises(RuntimeError):
        eng.warm_restart()
    for f in futs:
        with pytest.raises(ErrorServiceUnavailable) as exc_info:
            f.result(timeout=10)
        assert exc_info.value.retry_after is not None


def test_stand_down_clears_stale_restarting_state():
    """drain() winning the race mid-restart must not leave the supervisor
    pinned at RESTARTING: health ranks that above the engine's own DOWN,
    so a cleanly drained engine would report RESTARTING forever."""
    eng = make_engine()
    eng.start()
    sup = make_supervisor(eng)
    assert eng.drain(deadline_s=30) is True  # drain wins before the restart
    sup._transition("RESTARTING")  # the watchdog had already claimed one
    sup._restart("stall detected just before the drain")
    assert sup.state == "UP"  # the claim is dropped, not left dangling
    assert eng.health_check()["status"] == "DOWN"
    assert sup._stop.is_set()  # and the watchdog stands down


def test_supervisor_states_surface_in_health():
    eng = make_engine()
    sup = make_supervisor(eng)
    assert eng.health_check()["details"]["supervisor"]["state"] == "UP"
    for state, expected in (("SUSPECT", "SUSPECT"), ("RESTARTING", "RESTARTING"),
                            ("WEDGED", "WEDGED")):
        sup.state = state
        eng._running = True  # pretend-live so the state alone decides
        assert eng.health_check()["status"] == expected
    eng._running = False
    sup.state = "UP"
    eng.stop()


def test_wedged_outranks_drain_in_aggregate_health():
    from gofr_tpu.container.health import aggregate_health

    class WedgedServing:
        def health_check(self):
            return {"status": "WEDGED", "details": {}}

    class StubContainer:
        app_name = "t"
        app_version = "v"
        draining = True
        services: dict = {}
        serving = WedgedServing()
        logger = None

        def datasource_pairs(self):
            return []

    # a wedged engine is an incident even mid-drain: DEGRADED, not a
    # soothing DRAINING
    assert aggregate_health(StubContainer())["status"] == "DEGRADED"


def test_earn_back_resets_consecutive_restarts():
    eng = make_engine()
    eng.start()
    sup = make_supervisor(eng, stall_s=5.0, restart_reset_s=0.05)
    sup._consecutive = 2
    sup._last_restart_t = time.monotonic()
    sup.start()
    try:
        wait_for(lambda: sup._consecutive == 0, timeout=10,
                 msg="healthy run never earned the restart budget back")
    finally:
        sup.drain(deadline_s=30)


# -- watchdog detection under fixed-seed chaos --------------------------------

@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_hang_is_detected_and_healed(seed):
    """The acceptance scenario: an injected engine.step HANG at a fixed
    seed. The supervisor detects the stall within TPU_ENGINE_STALL_S,
    warm-restarts (quarantining the hung thread), requeued requests
    complete, and app_engine_restarts_total increments."""
    metrics = RecordingMetrics()
    eng = make_engine(metrics=metrics)
    sup = make_supervisor(eng, stall_s=0.3, poll_s=0.03, join_timeout=0.3)
    # warm every executable FIRST: a first-call jit compile is slow-but-
    # alive, and this test is about a hang, not about compile time
    eng.start()
    eng.submit("warmup", max_new_tokens=3).result(timeout=120)
    inj = chaos.ChaosInjector(
        seed, {"engine.step": 1.0}, max_faults=1,
        fault_factories={"engine.step": chaos.hang_factory(2.0)},
    )
    with chaos.active(inj):
        sup.start()  # the next loop iteration hangs 2s > stall_s
        futs = [eng.submit(f"pre-hang {i}", max_new_tokens=3) for i in range(4)]
        try:
            # every queued request survives the restart and completes
            for f in futs:
                assert f.result(timeout=120).finish_reason in TERMINAL_REASONS
            wait_for(lambda: sup.restarts >= 1, timeout=60,
                     msg="watchdog never restarted the hung engine")
        finally:
            sup.drain(deadline_s=60)
    assert metrics.counters.get("app_engine_restarts_total", 0) >= 1
    assert inj.stats()["engine.step"]["faults"] == 1
    assert sup.state in ("UP", "RESTARTING") or eng.health_check()["status"] == "DOWN"
    assert_reclaimed(eng)


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_crash_is_detected_and_healed(seed):
    """The RAISE variant: engine.step kills the loop thread outright (an
    unhandled loop exit — past the per-step recovery). The watchdog reads
    loop_crashed and restarts; queued requests complete."""
    eng = make_engine()
    sup = make_supervisor(eng, stall_s=5.0, poll_s=0.03)  # crash flag, not stall
    futs = [eng.submit(f"pre-crash {i}", max_new_tokens=3) for i in range(3)]
    inj = chaos.ChaosInjector(seed, {"engine.step": 1.0}, max_faults=1)
    with chaos.active(inj):
        sup.start()
        try:
            for f in futs:
                assert f.result(timeout=120).finish_reason in TERMINAL_REASONS
            wait_for(lambda: sup.restarts >= 1, timeout=60,
                     msg="watchdog never restarted the crashed engine")
            assert not eng.loop_crashed  # cleared by the restart
        finally:
            sup.drain(deadline_s=60)
    assert_reclaimed(eng)


@pytest.mark.chaos
def test_hung_thread_wedge_settles_queued_futures():
    """Budget exhaustion on a TRUE hang — the loop thread never joins, so
    stop() takes the wedge branch. It must still settle every registered
    future retriable: the hung thread never will, and before the
    code-review fix the early return stranded them forever (a caller with
    no deadline blocked on fut.result() indefinitely)."""
    eng = make_engine()
    sup = make_supervisor(eng, stall_s=0.2, poll_s=0.05, restart_budget=1,
                          join_timeout=0.3)
    inj = chaos.ChaosInjector(
        11, {"engine.step": 1.0},
        fault_factories={"engine.step": chaos.hang_factory(30.0)},
    )
    with chaos.active(inj):
        sup.start()
        try:
            fut = eng.submit("queued behind the hang", max_new_tokens=2)
        except TERMINAL_ERRORS:
            fut = None  # raced a restart window: already terminal
        wait_for(lambda: sup.state == "WEDGED", timeout=60,
                 msg="supervisor did not park on a true hang")
        assert eng.health_check()["status"] == "WEDGED"
        if fut is not None:
            with pytest.raises(ErrorServiceUnavailable):
                fut.result(timeout=10)
        assert not eng._by_id, "wedge left requests registered forever"


@pytest.mark.chaos
@pytest.mark.slow
def test_budget_exhaustion_parks_wedged():
    """Every restarted thread dies again: after the budget is spent the
    supervisor parks WEDGED — loud in health, never flapping — instead of
    burning CPU on restarts that stop helping."""
    metrics = RecordingMetrics()
    eng = make_engine(metrics=metrics)
    sup = make_supervisor(eng, stall_s=5.0, poll_s=0.03, restart_budget=2)
    fut = eng.submit("doomed", max_new_tokens=3)
    inj = chaos.ChaosInjector(7, {"engine.step": 1.0})  # unbounded faults
    with chaos.active(inj):
        sup.start()
        try:
            wait_for(lambda: sup.state == "WEDGED", timeout=120,
                     msg="budget exhaustion never parked the engine")
            # exactly-one-terminal-state: the queued request was settled
            # retriable by the park's stop sweep
            with pytest.raises(ErrorServiceUnavailable):
                fut.result(timeout=30)
            assert sup.restarts == 2  # the budget, no more
            assert eng.health_check()["status"] == "WEDGED"
            assert metrics.gauges.get("app_engine_supervisor_state") == 3.0
            # never flaps: parked means parked
            time.sleep(0.3)
            assert sup.state == "WEDGED"
            assert sup.restarts == 2
            assert sup._thread is not None and not sup._thread.is_alive()
        finally:
            sup._stop.set()
            eng._wedged = False  # allow the cleanup stop to run
            if eng._running:
                eng.stop()


def test_isolated_poisonings_decay_instead_of_restarting():
    """Only a poison STORM (repeated poisonings with no quiet window)
    escalates to a warm restart. Isolated, fully-healed poisonings spread
    out in time rebase the mark after restart_reset_s of quiet — they must
    never accumulate into a spurious restart of a healthy engine."""
    eng = make_engine()
    sup = make_supervisor(eng, stall_s=30.0, poll_s=0.02,
                          restart_reset_s=0.15, poison_threshold=2)
    sup.start()
    try:
        eng.device_poisonings += 1  # healed in place; engine stays healthy
        time.sleep(0.4)  # quiet window > restart_reset_s: mark rebases
        eng.device_poisonings += 1  # another isolated, healed fault
        time.sleep(0.1)  # below the quiet window: detection still possible
        assert sup.restarts == 0, "isolated poisonings must not restart"
        assert sup.state == "UP"
    finally:
        sup.stop()


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_device_poisoning_escalates_to_restart(seed):
    """Repeated device.loss poisonings (the executable keeps dying and
    taking the KV buffers with it) escalate past the in-place _fail_all
    rebuild to a full warm restart."""
    eng = make_engine()
    sup = make_supervisor(eng, stall_s=30.0, poll_s=0.03, poison_threshold=2,
                          restart_budget=5)
    inj = chaos.ChaosInjector(seed, {"device.loss": 1.0}, max_faults=3)
    with chaos.active(inj):
        sup.start()
        try:
            outcomes = []
            for i in range(6):
                try:
                    outcomes.append(eng.submit(f"poison {i}", max_new_tokens=3))
                except TERMINAL_ERRORS as exc:
                    outcomes.append(exc)
                time.sleep(0.05)
            wait_for(lambda: eng.device_poisonings >= 2, timeout=60,
                     msg="device.loss never poisoned the engine")
            wait_for(lambda: sup.restarts >= 1, timeout=60,
                     msg="poison storm never escalated to a restart")
            # every submission reached exactly one terminal state
            for item in outcomes:
                if isinstance(item, BaseException):
                    continue
                try:
                    res = item.result(timeout=120)
                    assert res.finish_reason in TERMINAL_REASONS
                except TERMINAL_ERRORS:
                    pass
                except RuntimeError:
                    pass  # the poisoning dispatch's own error is terminal too
            # faults exhausted: the healed engine serves
            probe_until_served(eng)
        finally:
            sup.drain(deadline_s=60)
    assert_reclaimed(eng)


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_lifecycle_invariant_across_restart(seed, kv_layout):
    """PR 3's lifecycle invariant stays green when a warm restart lands in
    the middle of a mixed workload: every request reaches exactly one
    terminal state, slots and pages are re-founded cleanly, the engine
    drains within its deadline."""
    kw = dict(kv_layout=kv_layout)
    if kv_layout == "paged":
        kw.update(kv_page_size=8)
    eng = make_engine(**kw)
    sup = make_supervisor(eng, stall_s=0.3, poll_s=0.03, join_timeout=0.3)
    # compile everything before the storm: stall detection is for hangs,
    # not first-call jit compiles
    eng.start()
    eng.submit("warmup", max_new_tokens=3).result(timeout=120)
    inj = chaos.ChaosInjector(
        seed, {"engine.step": 0.02}, max_faults=2,
        fault_factories={"engine.step": chaos.hang_factory(1.0)},
    )
    outcomes = []
    with chaos.active(inj):
        sup.start()
        try:
            for i in range(16):
                kind = ("plain", "deadline", "cancel")[i % 3]
                try:
                    fut = eng.submit(
                        f"req {i} pad"[:10], max_new_tokens=(2, 5, 8)[i % 3],
                        deadline=30.0 if kind == "deadline" else None,
                    )
                except TERMINAL_ERRORS as exc:
                    outcomes.append(exc)
                    continue
                if kind == "cancel":
                    eng.cancel(fut.request_id)
                outcomes.append(fut)
                time.sleep(0.01)
            settled = 0
            for item in outcomes:
                if isinstance(item, BaseException):
                    assert isinstance(item, TERMINAL_ERRORS), item
                    settled += 1
                    continue
                try:
                    res = item.result(timeout=120)
                    assert res.finish_reason in TERMINAL_REASONS, res.finish_reason
                except TERMINAL_ERRORS:
                    pass
                settled += 1
            assert settled == len(outcomes)
            # still servable after the storm + restart(s)
            probe_until_served(eng)
            assert_reclaimed(eng)
        finally:
            assert sup.drain(deadline_s=60) is True
    assert eng.health_check()["status"] == "DOWN"  # no wedge
    assert eng._thread is None or not eng._thread.is_alive()


# -- compile grace & retired-thread containment -------------------------------

def test_cold_dispatch_marks_warmed_only_on_success():
    """The _cold_dispatch section flags in_cold_dispatch while a
    never-seen signature runs, clears it either way, and warms the key
    only when the section completes — a faulted dispatch keeps its
    grace."""
    eng = make_engine()
    assert not eng.in_cold_dispatch
    with pytest.raises(RuntimeError):
        with eng._cold_dispatch("probe", 1):
            assert eng.in_cold_dispatch
            raise RuntimeError("faulted dispatch")
    assert not eng.in_cold_dispatch
    assert ("probe", 1) not in eng._warmed
    with eng._cold_dispatch("probe", 1):
        assert eng.in_cold_dispatch
    assert ("probe", 1) in eng._warmed
    with eng._cold_dispatch("probe", 1):  # warmed: no cold flag
        assert not eng.in_cold_dispatch


def test_first_compile_is_not_a_stall():
    """A first-call dispatch that outlasts TPU_ENGINE_STALL_S is a jit
    compile, not a hang: the watchdog widens its threshold to
    TPU_ENGINE_COMPILE_GRACE_S while the engine reports in_cold_dispatch,
    and the request completes with ZERO restarts. (Before this guard a
    cold engine with a multi-second compile warm-restarted in a loop
    until it parked WEDGED.)"""
    from gofr_tpu.serving import batch as batch_ops

    eng = make_engine()
    sup = make_supervisor(eng, stall_s=0.2, poll_s=0.03)
    assert sup.compile_grace_s > sup.stall_s
    assert sup.snapshot()["compile_grace_s"] == sup.compile_grace_s
    real = batch_ops.prefill_compute

    def slow_compile(*args, **kw):
        time.sleep(0.8)  # "compiling": > stall_s, < compile_grace_s
        return real(*args, **kw)

    batch_ops.prefill_compute = slow_compile
    try:
        sup.start()
        res = eng.submit("cold start", max_new_tokens=3).result(timeout=120)
        assert res.finish_reason in TERMINAL_REASONS
        assert sup.restarts == 0, "compile was misread as a stall"
        assert sup.state == "UP"
    finally:
        batch_ops.prefill_compute = real
        sup.stop()


def test_stall_inside_warmed_dispatch_heals_without_corruption():
    """A true mid-dispatch stall on a WARMED signature: the watchdog
    restarts once; the stalled request (still queued from the restart's
    point of view — its prefill never committed) is requeued and
    COMPLETES; and when the quarantined thread thaws inside the dispatch
    it unwinds via _check_retired instead of donating the rebuilt
    engine's pools or settling the requeued future with an internal
    error."""
    from gofr_tpu.serving import batch as batch_ops

    eng = make_engine(kv_layout="paged", kv_page_size=8)
    sup = make_supervisor(eng, stall_s=0.3, poll_s=0.03, join_timeout=0.2)
    sup.start()
    eng.submit("warmup", max_new_tokens=3).result(timeout=120)

    real = batch_ops.prefill_compute
    stalled = threading.Event()

    def stall_once(*args, **kw):
        if not stalled.is_set():
            stalled.set()
            time.sleep(1.5)  # > stall_s, > join_timeout: quarantine path
        return real(*args, **kw)

    batch_ops.prefill_compute = stall_once
    try:
        old_thread = eng._thread
        # prompt must fit the 16-token bucket: the stall is pinned INSIDE
        # the monolithic prefill_compute dispatch (a longer prompt would
        # route through chunked prefill and never reach the patched stall)
        res = eng.submit("stalls mid-pre", max_new_tokens=4).result(
            timeout=120
        )
        # the request survived the restart and finished NORMALLY — before
        # the containment fix the thawed thread wrote into the rebuilt
        # pools and crashed the replacement loop
        assert res.finish_reason in TERMINAL_REASONS
        wait_for(lambda: sup.restarts >= 1, timeout=60,
                 msg="watchdog never saw the warmed-dispatch stall")
        old_thread.join(timeout=30)
        assert not old_thread.is_alive()
        assert sup.restarts == 1, "containment failed: restart cascaded"
        probe_until_served(eng)
        assert sup.state == "UP"
        assert_reclaimed(eng)
    finally:
        batch_ops.prefill_compute = real
        sup.stop()


@pytest.mark.chaos
def test_thawed_thread_skips_doomed_iteration():
    """A hang that thaws WHILE warm_restart waits in join(): the old
    thread must re-check _running before admitting — one more iteration
    would prefill a request the restart is about to sweep, downgrading a
    clean requeue-and-complete into a retriable failure."""
    metrics = RecordingMetrics()
    eng = make_engine(metrics=metrics)
    sup = make_supervisor(eng, stall_s=0.25, poll_s=0.03, join_timeout=5.0)
    eng.start()
    eng.submit("warmup", max_new_tokens=3).result(timeout=120)
    inj = chaos.ChaosInjector(
        101, {"engine.step": 1.0}, max_faults=1,
        fault_factories={"engine.step": chaos.hang_factory(1.2)},
    )
    with chaos.active(inj):
        sup.start()
        fut = eng.submit("queued through the hang", max_new_tokens=3)
        try:
            # join_timeout (5s) outlasts the hang (1.2s): the thaw races
            # warm_restart's join and MUST lose — the request completes
            res = fut.result(timeout=120)
            assert res.finish_reason in TERMINAL_REASONS
            wait_for(lambda: sup.restarts >= 1, timeout=60,
                     msg="hang never detected")
        finally:
            sup.drain(deadline_s=60)
    assert metrics.counters.get("app_engine_restarts_total", 0) >= 1
    assert_reclaimed(eng)
