"""Request-lifecycle hardening: deadline propagation, load shedding,
graceful drain, and the UP → DRAINING → DOWN/WEDGED health states.

Engine-level twins of the transport behaviors documented in
docs/robustness.md: a deadline is the caller's remaining budget in seconds;
an expired-while-queued request 504s without ever prefilling; a mid-stream
expiry retires with finish reason ``deadline_exceeded`` and its partial
tokens; shedding rejects in microseconds with 429 + Retry-After when the
EWMA queue-wait estimate says the request cannot make it."""

import threading
import time

import jax
import pytest

from gofr_tpu.container.health import aggregate_health
from gofr_tpu.http.errors import (
    ErrorDeadlineExceeded,
    ErrorServiceUnavailable,
    ErrorTooManyRequests,
)
from gofr_tpu.models import llama
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving.shed import QueueWaitEstimator


def tiny_cfg(max_seq: int = 64) -> llama.LlamaConfig:
    return llama.LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=max_seq,
    )


def make_engine(**cfg_kw) -> ServingEngine:
    cfg = tiny_cfg(cfg_kw.get("max_seq_len", 64))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_slots=2, max_seq_len=64, prefill_buckets=(16,),
        admission_per_step=2, max_queue=16,
    )
    defaults.update(cfg_kw)
    return ServingEngine(
        cfg, params, EngineConfig(**defaults), ByteTokenizer(cfg.vocab_size)
    )


# -- shed estimator -----------------------------------------------------------

def test_estimator_cold_and_idle_never_shed():
    est = QueueWaitEstimator()
    assert est.estimate_wait(100, 4) == 0.0  # cold: no observations yet
    est.observe_request(2.0)
    assert est.estimate_wait(0, 4) == 0.0  # idle queue: nothing to wait behind


def test_estimator_scales_with_queue_depth():
    est = QueueWaitEstimator(alpha=0.5)
    est.observe_request(4.0)
    assert est.estimate_wait(4, 4) == pytest.approx(4.0)
    assert est.estimate_wait(8, 4) == pytest.approx(8.0)
    est.observe_request(2.0)  # EWMA: 4 + 0.5*(2-4) = 3
    assert est.estimate_wait(4, 4) == pytest.approx(3.0)
    snap = est.snapshot()
    assert snap["ewma_request_s"] == pytest.approx(3.0)


def test_shed_on_deadline_rejects_with_retry_after():
    eng = make_engine()  # not started: submissions stay queued
    eng._shed.observe_request(10.0)
    eng.submit("first", max_new_tokens=2)  # queue_depth becomes 1
    with pytest.raises(ErrorTooManyRequests) as err:
        eng.submit("doomed", max_new_tokens=2, deadline=0.01)
    assert err.value.status_code == 429
    assert err.value.retry_after and err.value.retry_after > 0
    assert "Retry-After" in err.value.response_headers()
    assert err.value.response_fields()["retry_after_s"] > 0
    # no deadline → not shed (threshold disabled by default)
    eng.submit("patient", max_new_tokens=2)


def test_shed_threshold_without_deadline():
    eng = make_engine(shed_max_wait_s=0.5)
    eng._shed.observe_request(10.0)
    eng.submit("first", max_new_tokens=2)
    with pytest.raises(ErrorTooManyRequests):
        eng.submit("over threshold", max_new_tokens=2)


# -- deadlines ----------------------------------------------------------------

def test_queued_expiry_is_504_and_never_prefills(monkeypatch):
    eng = make_engine()
    prefilled: list[int] = []
    real = eng._prefill_into
    monkeypatch.setattr(
        eng, "_prefill_into",
        lambda slot, req: (prefilled.append(req.id), real(slot, req))[1],
    )
    eng.start()
    try:
        f = eng.submit("born dead", max_new_tokens=4, deadline=1e-9)
        with pytest.raises(ErrorDeadlineExceeded) as err:
            f.result(timeout=60)
        assert err.value.status_code == 504
        assert f.request_id not in prefilled
        # the engine stays servable
        res = eng.submit("alive", max_new_tokens=2).result(timeout=60)
        assert res.finish_reason in ("stop", "length")
    finally:
        eng.stop()


def test_mid_stream_deadline_keeps_partial_tokens():
    eng = make_engine()
    got_token = threading.Event()

    def cb(token_id, piece, done):
        if not done:
            got_token.set()

    eng.start()
    try:
        f = eng.submit("stream me", max_new_tokens=50, deadline=30.0,
                       stream_cb=cb)
        assert got_token.wait(timeout=60)
        # force the deadline into the past mid-stream (white-box: exact
        # timing of a real expiry is load-dependent)
        with eng._count_lock:
            req = eng._by_id.get(f.request_id)
        if req is not None:  # may have finished already on a fast box
            req.deadline = time.perf_counter() - 1.0
        res = f.result(timeout=60)
        assert res.finish_reason in ("deadline_exceeded", "stop", "length")
        if res.finish_reason == "deadline_exceeded":
            assert res.completion_tokens >= 0
        # slot reclaimed either way
        deadline = time.time() + 30
        while any(s is not None for s in eng.slots) and time.time() < deadline:
            time.sleep(0.01)
        assert all(s is None for s in eng.slots)
    finally:
        eng.stop()


def test_deadline_from_ctx_parses_and_rejects():
    from gofr_tpu.http.errors import ErrorInvalidParam
    from gofr_tpu.serving.handlers import deadline_from_ctx

    class Ctx:
        def __init__(self, headers):
            self._h = {k.lower(): v for k, v in headers.items()}

        def header(self, key):
            return self._h.get(key.lower(), "")

    assert deadline_from_ctx(Ctx({})) is None
    assert deadline_from_ctx(Ctx({"X-Request-Timeout": "2.5"})) == 2.5
    assert deadline_from_ctx(Ctx({"Request-Timeout": "3"})) == 3.0
    assert deadline_from_ctx(Ctx({"X-Request-Timeout": "-1"})) is None
    with pytest.raises(ErrorInvalidParam):
        deadline_from_ctx(Ctx({"X-Request-Timeout": "soon"}))


# -- drain --------------------------------------------------------------------

def test_drain_lets_inflight_finish():
    eng = make_engine()
    eng.start()
    futs = [eng.submit(f"req {i}", max_new_tokens=4) for i in range(4)]
    assert eng.drain(deadline_s=60) is True
    for f in futs:
        assert f.result(timeout=1).finish_reason in ("stop", "length")
    assert eng.health_check()["status"] == "DOWN"
    assert all(s is None for s in eng.slots)
    with pytest.raises(ErrorServiceUnavailable) as err:
        eng.submit("after drain")
    assert err.value.status_code == 503
    assert "Retry-After" in err.value.response_headers()


def test_drain_deadline_fails_remainder_retriable():
    eng = make_engine()
    eng.start()
    futs = [eng.submit(f"req {i}", max_new_tokens=40) for i in range(6)]
    assert eng.drain(deadline_s=0.0) is False
    outcomes = []
    for f in futs:
        try:
            outcomes.append(f.result(timeout=30).finish_reason)
        except ErrorServiceUnavailable as exc:
            assert exc.status_code == 503  # retriable
            outcomes.append("drained")
        except ErrorDeadlineExceeded:
            outcomes.append("deadline")
    assert len(outcomes) == len(futs)  # every request reached a terminal state
    assert all(s is None for s in eng.slots)
    assert not eng._thread or not eng._thread.is_alive()


def test_draining_health_state():
    eng = make_engine()
    eng.start()
    try:
        assert eng.health_check()["status"] == "UP"
        done = threading.Event()
        t = threading.Thread(
            target=lambda: (eng.drain(deadline_s=30), done.set()), daemon=True
        )
        # hold a request in flight so DRAINING is observable
        eng.submit("hold", max_new_tokens=30)
        t.start()
        deadline = time.time() + 10
        seen_draining = False
        while time.time() < deadline and not done.is_set():
            if eng.health_check()["status"] == "DRAINING":
                seen_draining = True
                break
            time.sleep(0.005)
        assert seen_draining or done.is_set()
        assert done.wait(timeout=60)
        assert eng.health_check()["status"] == "DOWN"
    finally:
        if eng._running:
            eng.stop()


def test_stop_wedged_thread_reports_wedged():
    eng = make_engine()
    release = threading.Event()
    # a loop that ignores _running until released: the wedge scenario
    eng._loop = lambda: release.wait(60)  # type: ignore[method-assign]
    eng.start()
    eng.stop(join_timeout=0.2)
    assert eng.health_check()["status"] == "WEDGED"
    assert eng._thread is not None  # the wedged thread is not forgotten
    release.set()
    eng._thread.join(timeout=10)
    eng.stop(join_timeout=5)  # second stop joins clean and releases resources
    assert eng.health_check()["status"] == "DOWN"


def test_container_drain_flag_aggregates_and_rejects():
    class StubContainer:
        app_name = "t"
        app_version = "v"
        draining = True
        services: dict = {}
        serving = None
        logger = None

        def datasource_pairs(self):
            return []

    assert aggregate_health(StubContainer())["status"] == "DRAINING"

    import asyncio

    from gofr_tpu.http.dispatch import Dispatcher
    from gofr_tpu.http.request import Request
    from gofr_tpu.http.router import Router

    disp = Dispatcher(Router(), StubContainer())
    resp = asyncio.run(disp(Request("POST", "/generate", {}, {}, b"{}")))
    assert resp.status == 503
    assert resp.headers.get("Retry-After") == "1"
    # probes stay served so the LB can see the DRAINING state
    health = asyncio.run(
        disp(Request("GET", "/.well-known/alive", {}, {}, b""))
    )
    assert health.status != 503


# -- permanent rejections & KV-exhaustion honesty -----------------------------

class _RecMetrics:
    def __init__(self):
        self.counters: dict = {}

    def increment_counter(self, name, *a, **kw):
        self.counters[name] = self.counters.get(name, 0) + 1

    def add_counter(self, *a, **kw):
        pass

    def set_gauge(self, *a, **kw):
        pass

    def record_histogram(self, *a, **kw):
        pass


def test_never_fit_prompt_is_413_not_429():
    """A prompt needing more KV pages than the whole pool HOLDS is a
    permanent condition: 413 (non-retriable, no Retry-After), never a 429
    that invites clients to retry forever."""
    from gofr_tpu.http.errors import ErrorRequestEntityTooLarge

    # bucket 32 -> 4 pages of 8; the pool holds 3 in total
    eng = make_engine(kv_page_size=8, kv_num_pages=3,
                      prefill_buckets=(16, 32))
    eng.start()
    try:
        fut = eng.submit("x" * 20, max_new_tokens=4)  # bucket 32
        with pytest.raises(ErrorRequestEntityTooLarge) as exc_info:
            fut.result(timeout=60)
        assert exc_info.value.status_code == 413
        assert exc_info.value.retry_after is None
        assert exc_info.value.response_headers() == {}  # no Retry-After
        # the engine is unharmed: a fitting prompt serves right after
        res = eng.submit("ok", max_new_tokens=3).result(timeout=60)
        assert res.finish_reason in ("stop", "length")
    finally:
        eng.stop()


def test_grpc_maps_413_to_failed_precondition():
    import asyncio

    grpc = pytest.importorskip("grpc")
    from gofr_tpu.grpcx.inference import _abort_lifecycle
    from gofr_tpu.http.errors import ErrorRequestEntityTooLarge

    class AbortCalled(Exception):
        pass

    class Ctx:
        code = None

        async def abort(self, code, message):
            self.code = code
            raise AbortCalled()

        def set_trailing_metadata(self, md):
            pass

    ctx = Ctx()
    with pytest.raises(AbortCalled):
        asyncio.run(_abort_lifecycle(ctx, ErrorRequestEntityTooLarge("too big")))
    assert ctx.code == grpc.StatusCode.FAILED_PRECONDITION


def test_kv_exhaustion_reports_its_own_reason_and_metric():
    """Mid-decode pool exhaustion used to retire rows as "length" —
    indistinguishable from a legitimate max-tokens stop. It now reports
    finish_reason "kv_exhausted" and counts in
    app_requests_kv_exhausted_total."""
    metrics = _RecMetrics()
    cfg = tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_slots=1, max_seq_len=64, prefill_buckets=(16,),
                     kv_page_size=8, kv_num_pages=3),
        ByteTokenizer(cfg.vocab_size), metrics=metrics,
    )
    eng.start()
    try:
        # bucket 16 -> 2 pages; decode grows past 24 tokens -> needs a 4th
        res = eng.submit("abcdefghijklmn", max_new_tokens=40).result(timeout=120)
        assert res.finish_reason == "kv_exhausted"
        assert 0 < res.completion_tokens < 40
        assert metrics.counters.get("app_requests_kv_exhausted_total") == 1
    finally:
        eng.stop()


def test_kv_exhaustion_reaches_stream_consumers():
    """The transport contract: SSE's terminal event, the gRPC done frame
    and the WS summary all read the stream's final GenerationResult via
    on_result — kv_exhausted must arrive there, end-to-end."""
    import asyncio

    eng = make_engine(kv_page_size=8, kv_num_pages=3,
                      max_slots=1)
    eng.start()
    try:
        final: dict = {}

        async def consume():
            tokens = []
            async for token_id, piece in eng.stream(
                "abcdefghijklmn", max_new_tokens=40,
                on_result=lambda r: final.setdefault("result", r),
            ):
                tokens.append(token_id)
            return tokens

        tokens = asyncio.run(consume())
        assert tokens  # partial output was delivered before the pool dried up
        assert final["result"].finish_reason == "kv_exhausted"
    finally:
        eng.stop()
