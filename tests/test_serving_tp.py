"""TP serving (VERDICT r1 item 4): the ServingEngine running with
tensor-parallel sharded weights on the 8-virtual-device CPU mesh — the
single-host slice of BASELINE.json configs[2]/[4] — plus concurrent
HTTP + gRPC load with TTFT/req-rate read back from the engine's own
histograms (SURVEY §5.5).

The engine itself is sharding-agnostic: its jitted step functions
(serving/batch.py) compile against whatever shardings the param leaves
carry, and GSPMD inserts the tp collectives. These tests pin that down:
same tokens sharded vs unsharded, and the full HTTP/gRPC stack on top.
"""

import concurrent.futures
import json
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import gofr_tpu
from gofr_tpu.config import MapConfig
from gofr_tpu.grpcx import InferenceClient, InferenceService
from gofr_tpu.models import llama
from gofr_tpu.parallel.sharding import llama_sharding_rules, shard_params
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving.handlers import register_generation_routes
from gofr_tpu.testutil import new_server_configs

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


@pytest.fixture(scope="module")
def tp_setup():
    # dims divisible by tp=4 and fsdp=2: vocab 320, d_model 64, kv-proj 32
    cfg = llama.LlamaConfig.tiny(vocab_size=320)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("fsdp", "tp"))
    sharded = shard_params(params, mesh, llama_sharding_rules())
    return cfg, params, sharded, mesh


def _make_engine(cfg, params, **kw):
    defaults = dict(max_slots=4, max_seq_len=64, prefill_buckets=(16, 32))
    defaults.update(kw)
    return ServingEngine(cfg, params, EngineConfig(**defaults), ByteTokenizer())


def _greedy_tokens(engine, prompt, n=6):
    return engine.submit(prompt, max_new_tokens=n, temperature=0.0).result(
        timeout=120
    ).token_ids


def test_sharded_params_actually_sharded(tp_setup):
    cfg, _, sharded, mesh = tp_setup
    wq = sharded["layers"]["wq"]
    assert len(wq.sharding.device_set) == 8
    # column-parallel: head axis split 4-way, d_model split 2-way
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape == (cfg.n_layers, cfg.d_model // 2, cfg.d_model // 4)


def test_tp_engine_matches_unsharded(tp_setup):
    cfg, params, sharded, _ = tp_setup
    ref = _make_engine(cfg, params)
    tp = _make_engine(cfg, sharded)
    ref.start(), tp.start()
    try:
        for prompt in ("hello tp", "b", "a longer prompt than the others"):
            assert _greedy_tokens(tp, prompt) == _greedy_tokens(ref, prompt)
    finally:
        ref.stop(), tp.stop()


def test_tp_engine_paged_layout(tp_setup):
    """Paged KV on top of tp-sharded weights: same greedy tokens."""
    cfg, params, sharded, _ = tp_setup
    ref = _make_engine(cfg, params)
    tp = _make_engine(cfg, sharded, kv_layout="paged", kv_page_size=8)
    ref.start(), tp.start()
    try:
        assert _greedy_tokens(tp, "paged tp") == _greedy_tokens(ref, "paged tp")
    finally:
        ref.stop(), tp.stop()


def test_tp_engine_http_grpc_load(tp_setup, run_async):
    """Full stack under load: boot the app (HTTP + gRPC) on the tp-sharded
    engine, fire concurrent requests through both fronts, then read p50
    TTFT and request rate out of the engine's histograms — the numbers
    VERDICT r1 said had never been read."""
    cfg, _, sharded, _ = tp_setup
    ports = new_server_configs(set_env=False)
    http_port, grpc_port, metrics_port = (
        ports.http_port, ports.grpc_port, ports.metrics_port,
    )
    config = MapConfig(
        {
            "HTTP_PORT": str(http_port),
            "GRPC_PORT": str(grpc_port),
            "METRICS_PORT": str(metrics_port),
            "APP_NAME": "tp-serving-test",
            "LOG_LEVEL": "ERROR",
        },
        use_env=False,
    )
    app = gofr_tpu.App(config)
    engine = ServingEngine(
        cfg,
        sharded,
        EngineConfig(max_slots=4, max_seq_len=64, prefill_buckets=(16, 32)),
        ByteTokenizer(),
        metrics=app.container.metrics_manager,
    )
    register_generation_routes(app, engine)
    app.register_grpc_service(InferenceService(engine))
    thread = threading.Thread(target=app.run, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{http_port}"
    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            urllib.request.urlopen(base + "/.well-known/alive", timeout=1)
            break
        except OSError:
            time.sleep(0.05)
    else:
        pytest.fail("app did not come up")

    N_HTTP, N_GRPC = 8, 4
    t0 = time.perf_counter()

    def http_gen(i):
        body = json.dumps(
            {"prompt": f"load {i}", "max_tokens": 5, "temperature": 0.0}
        ).encode()
        req = urllib.request.Request(
            base + "/generate", data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status in (200, 201)  # framework maps POST → 201
            return json.loads(resp.read())["data"]

    async def grpc_gen():
        client = InferenceClient(f"127.0.0.1:{grpc_port}")
        try:
            return await asyncio_gather(
                *[client.generate(f"grpc {i}", max_tokens=5) for i in range(N_GRPC)]
            )
        finally:
            await client.close()

    from asyncio import gather as asyncio_gather

    try:
        with concurrent.futures.ThreadPoolExecutor(N_HTTP) as pool:
            http_futures = [pool.submit(http_gen, i) for i in range(N_HTTP)]
            grpc_results = run_async(grpc_gen())
            http_results = [f.result(timeout=120) for f in http_futures]
        elapsed = time.perf_counter() - t0

        assert len(http_results) == N_HTTP and len(grpc_results) == N_GRPC
        for r in http_results:
            assert r["usage"]["completion_tokens"] >= 1
            assert r["usage"]["ttft_ms"] > 0
        for r in grpc_results:
            assert r["finish_reason"] in ("length", "stop")

        m = app.container.metrics_manager
        ttft = m.get("app_ttft_seconds")
        _, ttft_count = ttft.snapshot()
        assert ttft_count == N_HTTP + N_GRPC
        p50 = ttft.percentile(0.5)
        assert 0 < p50 < 120
        req_per_s = (N_HTTP + N_GRPC) / elapsed
        assert req_per_s > 0
        _, tpot_count = m.get("app_tpot_seconds").snapshot()
        assert tpot_count >= 1
    finally:
        app.stop()
        thread.join(timeout=15)
