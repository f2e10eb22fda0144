"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                  one TPU chip, Llama-3-8B-int8
    python chip_smoke.py --replicas 4     four one-chip replicas behind the Router
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-self-test [--replicas N]

One process (a chip belongs to one process), no network, weights from a
seed. It drives the main path once through the entry points a user calls —
``gofr_tpu.App`` -> ``register_generation_routes`` / ``InferenceService`` ->
``ServingEngine`` -> ``StepPlanner`` -> ``serving/batch.py`` -> ``ops/`` — at
the full published widths of Llama-3-8B (``LlamaConfig()``: 32 layers,
d 4096, 32/8 heads x 128, ff 14336, vocab 128256; int8 weights, 8.56 GB),
once in the default engine configuration (dense bf16 KV) and once paged,
over HTTP, SSE and gRPC, with prompts that take all three prefill routes.
Every Pallas kernel is compiled by Mosaic at those head shapes and compared
with its reference. Any failed check raises: the run ends non-zero and
prints no result line. Without a TPU it fails at once.

``--cpu-self-test`` runs the same command at ``LlamaConfig.tiny`` widths
with the kernels under the Pallas interpreter, so the script is debugged
before chip time is spent on it. Its output is labelled a CPU self-test
and says nothing about a device.

What it prints (set-up against request time, peak HBM, which attention
path each route took) are observations, not metrics: no number here is a
benchmark result.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import gc
import json
import sys
import threading
import time
import urllib.request
from typing import Any

T0 = time.monotonic()
SEED = 0
NEW_TOKENS = 12
# prompt lengths in characters; ByteTokenizer adds one BOS token. They hit
# the three prefill routes of engine._route_chunked with EngineConfig()'s
# buckets (32..1024) and 256-token chunks:
PROMPTS = {
    "short": "hello chip",       # 11 tokens -> bucket 32: dense attention
    "mid": "m" * 200,            # 201 tokens -> bucket 256: flash kernel
    "long": "l" * 400,           # 401 tokens > 256: chunk cursors + ragged_step*
}
MOSAIC_CALL = "tpu_custom_call"  # what a Mosaic-compiled pallas_call lowers to


def say(msg: str) -> None:
    print(f"[{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


def check(cond: Any, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


# ------------------------------------------------------------------- kernels
def check_kernels(self_test: bool) -> None:
    """Compile every Pallas kernel (Mosaic on the chip, the interpreter in
    the self-test) and compare it with its reference. Tolerance: outputs
    are weighted means of unit-normal values, |out| <~ 4; bf16 keeps 8
    bits, so one rounding of the result is <= 2**-7 ~ 0.016 and 0.03
    allows two. The f32 self-test must agree to 1e-4."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.ops.attention import attention
    from gofr_tpu.ops.flash_attention import flash_attention
    from gofr_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_ref,
        paged_kv_append,
        paged_kv_append_ref,
    )

    interpret = self_test
    if self_test:
        H, Hkv, D, B, S, dtype, tol = 4, 2, 32, 2, 128, jnp.float32, 1e-4
    else:  # Llama-3-8B heads, the engine's 8 slots x 1024
        H, Hkv, D, B, S, dtype, tol = 32, 8, 128, 8, 1024, jnp.bfloat16, 3e-2
    keys = jax.random.split(jax.random.PRNGKey(SEED), 6)

    def err(a: Any, b: Any) -> float:
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))

    fs = 256  # the prefill bucket that routes to flash
    q = jax.random.normal(keys[0], (1, fs, H, D), dtype)
    k = jax.random.normal(keys[1], (1, fs, Hkv, D), dtype)
    v = jax.random.normal(keys[2], (1, fs, Hkv, D), dtype)
    n = fs - 55  # a right-padded prompt; rows past kv_len are padding
    kv_len = jnp.array([n], jnp.int32)
    e = err(flash_attention(q, k, v, kv_len, causal=True, interpret=interpret)[:, :n],
            attention(q, k, v, causal=True, kv_len=kv_len)[:, :n])
    say(f"kernel flash_attention S={fs} H={H}/{Hkv}x{D}: max|err|={e:.4g} (tol {tol})")
    check(e <= tol, f"flash_attention disagrees with ops.attention ({e} > {tol})")
    # with a window (a traced scalar, a scanned layer's own): one that
    # leaves whole blocks of 128 keys below it and cuts inside a block, at
    # the heads of the window layers served (command-a-plus: 128 / 8)
    wH, wHkv, ws, window = (H, Hkv, 256, 40) if self_test else (128, 8, 512, 200)
    q = jax.random.normal(keys[0], (1, ws, wH, D), dtype)
    k = jax.random.normal(keys[1], (1, ws, wHkv, D), dtype)
    v = jax.random.normal(keys[2], (1, ws, wHkv, D), dtype)
    n = ws - 55
    kv_len = jnp.array([n], jnp.int32)
    w = jnp.int32(window)
    e = err(flash_attention(q, k, v, kv_len, causal=True, interpret=interpret, window=w)[:, :n],
            attention(q, k, v, causal=True, kv_len=kv_len, window=w)[:, :n])
    say(f"kernel flash_attention window={window} S={ws} H={wH}/{wHkv}x{D}: max|err|={e:.4g} (tol {tol})")
    check(e <= tol, f"flash_attention with a window disagrees with ops.attention ({e} > {tol})")

    # the paged decode kernel sizes its blocks from the shapes it sees, so
    # it is compiled at every shape class served: Llama-3-8B under the
    # engine's defaults, and the two benchmark configurations' heads,
    # slots and table widths (benchmarks/cells/) — grouped-query with many
    # short rows, full multi-head with few long ones
    if self_test:
        paged_cases = [("grouped", H, Hkv, B, S), ("multi-head", H, H, 3, S)]
    else:
        paged_cases = [
            ("8B heads, 8 x 1024", H, Hkv, B, S),
            ("mistral7b.chat, 32 x 768", 32, 8, 32, 768),
            ("deepseek7b.gen, 6 x 1024", 32, 32, 6, 1024),
            # 16 queries a KV head; its window layers pass a window (below)
            ("commandaplus.wide, 64 x 1024", 128, 8, 64, 1024),
        ]
    page = 16  # the engine's default, and every cell's
    for label, pH, pHkv, pB, pS in paged_cases:
        qd = jax.random.normal(keys[3], (pB, pH, D), dtype)
        # empty slots (length 1), a page boundary + 1, ragged, the full table
        ragged = [17, pS // 3, pS // 2 - 1, pS - pS // 4 + 9, pS - 24, pS - 3, pS]
        k_live = min(len(ragged), pB - 1)
        seq_lens = jnp.asarray([1] * (pB - k_live) + ragged[-k_live:], jnp.int32)
        M = pS // page
        N = pB * M + 1
        kf = jax.random.normal(keys[4], (N, pHkv, page, D), dtype)
        vf = jax.random.normal(keys[5], (N, pHkv, page, D), dtype)
        tables = jnp.asarray(
            np.random.default_rng(SEED).permutation(N - 1).reshape(pB, M), jnp.int32
        )
        # as the decode step calls the two kernels: the whole pools (two
        # layers; the other holds NaN, so a page read from the wrong layer
        # shows) and a traced layer index
        layer = jnp.int32(1)
        kw = jnp.stack([jnp.full_like(kf, jnp.nan), kf])
        vw = jnp.stack([jnp.full_like(vf, jnp.nan), vf])
        # the append: every row writes the slot of its last position
        # (an empty slot the trash page N - 1, as an inactive row)
        pos = seq_lens - 1
        live = seq_lens > 1
        pages = jnp.where(live, tables[jnp.arange(pB), pos // page], N - 1)
        offsets = jnp.where(live, pos % page, 0)
        k_new = jax.random.normal(keys[1], (pB, pHkv, D), dtype)
        v_new = jax.random.normal(keys[2], (pB, pHkv, D), dtype)
        want = paged_kv_append_ref(kw, vw, k_new, v_new, layer, pages, offsets)
        kw, vw = paged_kv_append(kw, vw, k_new, v_new, layer, pages, offsets,
                                 interpret=interpret)
        # bit for bit on every page but the trash page (garbage by contract)
        same = all(bool(jnp.array_equal(got[1, :N - 1], exp[1, :N - 1]))
                   and bool(jnp.all(jnp.isnan(got[0])))
                   for got, exp in zip((kw, vw), want))
        say(f"kernel paged_kv_append bf16 [{label}] Hkv={pHkv} B={pB} page={page}: "
            f"{'equal to' if same else 'DIFFERS from'} .at[].set")
        check(same, f"paged_kv_append [{label}] page={page} differs from its reference")
        kf, vf = kw[1], vw[1]  # the references read the appended layer
        out = paged_decode_attention(
            qd, kw, vw, tables, seq_lens, interpret=interpret, layer=layer
        )
        ref = paged_decode_attention_ref(qd, kf, vf, tables, seq_lens)
        # the same pools through the kernel with a window: one that starts
        # long rows past their first block and cuts inside a page, and one
        # wider than every row (a full-attention layer)
        for window in (pS // 4 + 5, 1 << 30):
            w = jnp.int32(window)
            e = err(paged_decode_attention(qd, kw, vw, tables, seq_lens,
                                           interpret=interpret, window=w, layer=layer),
                    paged_decode_attention_ref(qd, kf, vf, tables, seq_lens, window=w))
            say(f"kernel paged_decode_attention bf16 window={window} [{label}] "
                f"H={pH}/{pHkv} B={pB} M={M} page={page}: max|err|={e:.4g} (tol {tol})")
            check(e <= tol, f"paged_decode_attention window={window} [{label}] "
                            f"disagrees with its reference ({e} > {tol})")
        e = err(out, ref)
        say(f"kernel paged_decode_attention bf16 [{label}] H={pH}/{pHkv} B={pB} M={M} page={page}: "
            f"max|err|={e:.4g} (tol {tol})")
        check(e <= tol,
              f"paged_decode_attention [{label}] page={page} disagrees with its reference ({e} > {tol})")


# ------------------------------------------------- which attention path ran
def attention_paths(engine: Any, self_test: bool) -> dict[str, int]:
    """Lower (not compile) the engine's own jitted programs at its real
    argument shapes and count Mosaic custom calls in each. On the chip the
    flash bucket and every paged decode program must carry one; the
    programs that by design take XLA attention must carry none."""
    import jax
    import jax.numpy as jnp

    from gofr_tpu.serving import batch as batch_ops

    cfg, ec = engine.model_cfg, engine.config
    B, C, steps = ec.max_slots, engine._chunk_tokens, engine._block_steps

    def ab(tree: Any) -> Any:
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    def vec(dtype: Any, *shape: int) -> Any:
        return jax.ShapeDtypeStruct(shape or (B,), dtype)

    i32, f32 = jnp.int32, jnp.float32
    params = ab(engine.params)
    key = ab(engine._rng_root)
    state = batch_ops.DecodeState(
        vec(i32), vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(f32),
        vec(i32), vec(f32), key, vec(i32),
    )

    def calls(lowered: Any) -> int:
        return lowered.as_text().count(MOSAIC_CALL)

    paths = {
        f"prefill bucket {b}": calls(batch_ops.prefill_compute.lower(
            cfg, params, vec(i32, 1, b), vec(i32, 1)))
        for b in (32, 256)
    }
    # the per-row tail both ragged entries share: (chunk_start | kv_capacity),
    # finish, new_len, budgets, stops, temps, topks, topps, rids, rng_root,
    # decode_active, steps
    row = (vec(i32), vec(jnp.bool_), vec(i32), vec(i32), vec(i32), vec(f32),
           vec(i32), vec(f32), vec(i32), key, vec(jnp.bool_), steps)
    if engine.cache is not None:
        cache = ab(engine.cache)
        paths["decode_block"] = calls(batch_ops.decode_block.lower(
            cfg, params, cache, state, vec(jnp.bool_), steps))
        paths["ragged_step"] = calls(batch_ops.ragged_step.lower(
            cfg, params, cache, state, vec(i32, B, C), *row))
    else:
        pc = engine.paged_cache
        kp, vp = ab(pc.k_pool), ab(pc.v_pool)
        tables = vec(i32, B, pc.max_pages_per_seq)
        paths["decode_block_paged"] = calls(batch_ops.decode_block_paged.lower(
            cfg, params, kp, vp, state, tables, vec(jnp.bool_), steps))
        paths["ragged_step_paged"] = calls(batch_ops.ragged_step_paged.lower(
            cfg, params, kp, vp, state, tables, vec(i32, B, C), vec(i32),
            vec(jnp.bool_), *row))
    for name, n in paths.items():
        say(f"  attention path: {name}: "
            + (f"Mosaic kernel ({n} custom call)" if n else "XLA attention (no kernel)"))
    if not self_test:
        check(paths["prefill bucket 256"] > 0, "flash bucket lowered without the Mosaic kernel")
        check(paths["prefill bucket 32"] == 0, "bucket 32 should take dense attention")
        for name in ("decode_block_paged", "ragged_step_paged"):
            if name in paths:
                check(paths[name] > 0,
                      f"{name} lowered to the gather reference, not the Mosaic kernel")
    else:
        check(not any(paths.values()), "a CPU program carries a TPU custom call")
    return paths


# --------------------------------------------------------------- the clients
def http_json(url: str, body: dict | None = None, timeout: float = 900) -> tuple[int, Any]:
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method="POST" if body is not None else "GET",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def call_http(base: str, name: str, vocab: int) -> dict:
    t = time.monotonic()
    status, out = http_json(
        base + "/generate", {"prompt": PROMPTS[name], "max_tokens": NEW_TOKENS}
    )
    wall = time.monotonic() - t
    data = out["data"]
    check(status in (200, 201), f"POST /generate [{name}] status {status}")
    check(data["finish_reason"] == "length", f"/generate [{name}] finish_reason {data['finish_reason']!r}")
    usage = data["usage"]
    check(usage["completion_tokens"] == NEW_TOKENS, f"/generate [{name}] {usage}")
    check(usage["prompt_tokens"] == len(PROMPTS[name]) + 1, f"/generate [{name}] {usage}")
    check(0 < usage["ttft_ms"] <= wall * 1000, f"/generate [{name}] ttft {usage['ttft_ms']}")
    check(isinstance(data["text"], str), f"/generate [{name}] text")
    # the flight recorder says which prefill route the request took
    _, timeline = http_json(base + f"/requestz/{data['id']}")
    chunks = len(timeline["data"].get("prefill_chunks", []))
    check(chunks == (2 if name == "long" else 0),
          f"/generate [{name}] took {chunks} prefill chunks: wrong prefill route")
    return {"wire": "http", "prompt": name, "ttft_ms": usage["ttft_ms"],
            "wall_ms": round(wall * 1000, 1)}


def call_sse(base: str, name: str, vocab: int) -> dict:
    t = time.monotonic()
    req = urllib.request.Request(
        base + "/generate/stream",
        data=json.dumps({"prompt": PROMPTS[name], "max_tokens": NEW_TOKENS}).encode(),
        method="POST", headers={"Content-Type": "application/json"},
    )
    frames: list[tuple[int | None, Any]] = []
    ttft = None
    with urllib.request.urlopen(req, timeout=900) as resp:
        check(resp.status == 200, f"POST /generate/stream [{name}] status {resp.status}")
        event_id = None
        for raw in resp:
            line = raw.decode().rstrip("\n")
            if line.startswith("id: "):
                event_id = int(line[4:])
            elif line.startswith("data: "):
                payload = line[6:]
                frames.append((event_id, payload if payload == "[DONE]" else json.loads(payload)))
                if ttft is None and len(frames) == 2:
                    ttft = time.monotonic() - t
                event_id = None
    wall = time.monotonic() - t
    # id frame, NEW_TOKENS token frames, one terminal, [DONE] — ids in order
    check(len(frames) == NEW_TOKENS + 3, f"SSE [{name}] {len(frames)} frames")
    check([i for i, _ in frames[:-1]] == list(range(NEW_TOKENS + 2)), f"SSE [{name}] ids out of order")
    check("id" in frames[0][1], f"SSE [{name}] first frame is not the id frame")
    for _, tok in frames[1:-2]:
        check(0 <= tok["token"] < vocab, f"SSE [{name}] token {tok['token']} outside the vocabulary")
    terminal = frames[-2][1]
    check(terminal.get("finish_reason") == "length", f"SSE [{name}] terminal {terminal}")
    check(terminal["usage"]["completion_tokens"] == NEW_TOKENS, f"SSE [{name}] terminal {terminal}")
    check(frames[-1] == (None, "[DONE]"), f"SSE [{name}] missing [DONE]")
    check(sum("finish_reason" in f for _, f in frames if isinstance(f, dict)) == 1,
          f"SSE [{name}] more than one terminal")
    return {"wire": "sse", "prompt": name, "ttft_ms": round(ttft * 1000, 1),
            "wall_ms": round(wall * 1000, 1)}


def call_grpc(target: str, name: str, vocab: int) -> dict:
    """Server-streaming GenerateStream through grpc's blocking client: the
    App's grpc.aio server already owns this process's aio poller, and a
    second event loop beside it trips over the shared completion queue."""
    import grpc

    from gofr_tpu.grpcx.inference import SERVICE_NAME

    t = time.monotonic()
    ttft = None
    frames = []
    with grpc.insecure_channel(target) as channel:
        stream = channel.unary_stream(f"/{SERVICE_NAME}/GenerateStream")
        request = json.dumps({"prompt": PROMPTS[name], "max_tokens": NEW_TOKENS}).encode()
        for raw in stream(request, timeout=900):
            if ttft is None:
                ttft = time.monotonic() - t
            frames.append(json.loads(raw))
    wall = time.monotonic() - t
    check(len(frames) == NEW_TOKENS + 1, f"gRPC [{name}] {len(frames)} frames")
    for tok in frames[:-1]:
        check(0 <= tok["token"] < vocab, f"gRPC [{name}] token {tok} outside the vocabulary")
    check(frames[-1] == {"done": True, "finish_reason": "length"}, f"gRPC [{name}] terminal {frames[-1]}")
    check(sum("done" in f for f in frames) == 1, f"gRPC [{name}] more than one terminal")
    return {"wire": "grpc", "prompt": name, "ttft_ms": round(ttft * 1000, 1),
            "wall_ms": round(wall * 1000, 1)}


# ------------------------------------------------------------ one App, served
def serve_and_check(label: str, cfg: Any, params: Any, engine_config: Any,
                    self_test: bool) -> None:
    """Boot a real App exactly as examples/serving-llama/main.py does (plus
    the gRPC service and the tpu datasource), answer requests over all
    three wires, read health and metrics, shut it down."""
    import jax

    import gofr_tpu
    from gofr_tpu.config import MapConfig
    from gofr_tpu.datasource.tpu import TPUClient
    from gofr_tpu.grpcx import InferenceService
    from gofr_tpu.serving import ByteTokenizer, DeviceTelemetry, ServingEngine
    from gofr_tpu.serving.handlers import register_generation_routes
    from gofr_tpu.testutil import get_free_port

    platform = jax.devices()[0].platform
    t_setup = time.monotonic()
    http_port, metrics_port, grpc_port = (get_free_port() for _ in range(3))
    config = MapConfig({
        "HTTP_PORT": str(http_port), "METRICS_PORT": str(metrics_port),
        "GRPC_PORT": str(grpc_port), "APP_NAME": f"chip-smoke-{label}",
        "LOG_LEVEL": "WARN",
    }, use_env=False)
    app = gofr_tpu.App(config)
    app.add_tpu(TPUClient.from_config(config))
    engine = ServingEngine(
        cfg, params, engine_config, ByteTokenizer(cfg.vocab_size),
        metrics=app.container.metrics_manager, logger=app.container.logger,
        tracer=app.container.tracer, seed=SEED,
    )
    register_generation_routes(app, engine)
    app.register_grpc_service(InferenceService(engine))
    telemetry = DeviceTelemetry(
        engine, metrics=app.container.metrics_manager,
        logger=app.container.logger, interval_s=1.0,
    )
    app.on_start(lambda ctx: telemetry.start())
    app.on_shutdown(telemetry.stop)
    paths = attention_paths(engine, self_test)

    thread = threading.Thread(target=app.run, name="chip-smoke-app", daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{http_port}"
    try:
        deadline = time.monotonic() + 30
        while True:
            try:
                urllib.request.urlopen(base + "/.well-known/alive", timeout=1)
                break
            except OSError:
                check(time.monotonic() < deadline and thread.is_alive(), "the App never came up")
                time.sleep(0.05)

        # set-up: the first request of each route compiles its programs
        vocab = cfg.vocab_size
        for name in PROMPTS:
            t = time.monotonic()
            call_http(base, name, vocab)
            say(f"  [{label}] warm-up {name:5s} ({len(PROMPTS[name]) + 1} tokens): "
                f"{time.monotonic() - t:.1f}s (compilation included)")
        setup_s = time.monotonic() - t_setup

        # the requests: seven at once over the three wires, so the decode
        # block carries several rows and the long prompts chunk beside them
        calls = [(call_http, base, n) for n in PROMPTS] + \
                [(call_sse, base, n) for n in ("short", "long")] + \
                [(call_grpc, f"127.0.0.1:{grpc_port}", n) for n in ("short", "mid")]
        t = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(len(calls)) as pool:
            futures = [pool.submit(fn, where, name, vocab) for fn, where, name in calls]
            results = [f.result(timeout=900) for f in futures]
        request_s = time.monotonic() - t
        for r in results:
            say(f"  [{label}] {r['wire']:4s} {r['prompt']:5s} ttft {r['ttft_ms']:.0f} ms, "
                f"done in {r['wall_ms']:.0f} ms")

        # health and metrics, through the App's own endpoints (the tpu
        # datasource publishes its HBM gauges when its health is read)
        _, health = http_json(base + "/.well-known/health")
        details = health["data"]["details"]
        tpu, serving = details["tpu"]["details"], details["serving"]["details"]
        check(health["data"]["status"] == "UP", f"health {health['data']['status']}: {details}")
        check(tpu["platform"] == platform, f"tpu datasource platform {tpu['platform']!r}")
        check(serving["scheduler_backend"] == "native",
              "the engine runs the Python twin (gofr_tpu/native/fallback.py), not the C++ runtime")
        check(serving["total_admitted"] == len(PROMPTS) + len(results), f"admitted {serving['total_admitted']}")
        with urllib.request.urlopen(f"http://127.0.0.1:{metrics_port}/metrics", timeout=30) as resp:
            metrics = resp.read().decode()
        check("app_ttft_seconds" in metrics or "app_request_ttft_seconds" in metrics, "no TTFT histogram in /metrics")
        stats = jax.devices()[0].memory_stats() or {}
        if not self_test:
            check(tpu["hbm"][0]["bytes_in_use"] > 0, f"health reports no HBM in use: {tpu['hbm']}")
            used = [ln for ln in metrics.splitlines()
                    if ln.startswith("app_tpu_hbm_used_bytes{") and float(ln.rsplit(" ", 1)[1]) > 0]
            check(used, "no app_tpu_hbm_used_bytes > 0 in /metrics")
            check(tpu["native_pjrt"] and "pjrt_c_api" in tpu["native_pjrt"],
                  f"native PJRT binding did not load: {tpu['native_pjrt']}")
        say(f"[{label}] platform={platform} kv_layout={engine_config.kv_layout} "
            f"native_runtime={serving['scheduler_backend']} set-up {setup_s:.1f}s "
            f"requests {request_s:.2f}s "
            + ("(CPU self-test: no device numbers)" if self_test else
               f"hbm_in_use={stats.get('bytes_in_use', 0) / 1e9:.2f}GB "
               f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 0) / 1e9:.2f}GB "
               "(peak is the process's, weight init included)")
            + f" paths={paths}")
    finally:
        app.stop()
        thread.join(timeout=120)
    check(not thread.is_alive(), "the App did not shut down")
    check(engine.health_check()["status"] == "DOWN", "the engine outlived the App")


# ------------------------------------------- N one-chip replicas, one Router
def replicas_and_check(cfg: Any, params: Any, n: int, self_test: bool) -> None:
    """The same engines behind serving.router.Router, assembled by
    loadlab.stack.ServingStack: one replica per device, each with its own
    copy of the weights. Every chip must hold weights + KV and every
    replica must serve."""
    import jax

    from gofr_tpu.loadlab.stack import ServingStack, StackConfig
    from gofr_tpu.models import llama
    from gofr_tpu.serving import EngineConfig

    devices = jax.local_devices()
    check(len(devices) >= n, f"--replicas {n} needs {n} devices; jax sees {len(devices)}")
    defaults = EngineConfig()
    t = time.monotonic()
    stack = ServingStack(cfg, params, StackConfig(
        roles=("unified",) * n, autoscale=False, warmup=False,
        max_slots=defaults.max_slots, max_seq_len=defaults.max_seq_len,
        prefill_buckets=defaults.prefill_buckets,
        prefill_chunk_tokens=defaults.prefill_chunk_tokens,
    ))
    weight_bytes = llama.param_bytes(params)
    with stack:
        placed = {rid: {d for leaf in jax.tree.leaves(e.params) for d in leaf.devices()}
                  for rid, e in stack.engines.items()}
        check(all(len(p) == 1 for p in placed.values()), f"a replica spans devices: {placed}")
        check(len(set().union(*placed.values())) == n, f"replicas share devices: {placed}")
        # every replica compiles its own programs (an executable is bound
        # to its device): warm each route on all replicas at once — their
        # engine threads compile side by side — then a routed wave
        for name, prompt in PROMPTS.items():
            warm = {rid: e.submit(prompt, max_new_tokens=NEW_TOKENS, temperature=0.0)
                    for rid, e in stack.engines.items()}
            for rid, fut in warm.items():
                out = fut.result(timeout=900)
                check(out.completion_tokens == NEW_TOKENS and out.finish_reason == "length",
                      f"replica {rid} [{name}] {out.finish_reason} {out.completion_tokens}")
        say(f"  [replicas] {n} replicas up and warm in {time.monotonic() - t:.1f}s")
        before = {rid: e.health_check()["details"]["total_admitted"] for rid, e in stack.engines.items()}
        t = time.monotonic()
        futures = [
            stack.router.submit(f"request {i}: " + PROMPTS[name], max_new_tokens=NEW_TOKENS, temperature=0.0)
            for i in range(4 * n) for name in ("short", "mid")
        ]
        for fut in futures:
            out = fut.result(timeout=900)
            check(out.finish_reason == "length" and out.completion_tokens == NEW_TOKENS,
                  f"routed request: {out.finish_reason} {out.completion_tokens}")
            check(all(0 <= tok < cfg.vocab_size for tok in out.token_ids), "token outside the vocabulary")
            check(out.ttft_s > 0, "no TTFT")
        say(f"  [replicas] {len(futures)} routed requests in {time.monotonic() - t:.2f}s")
        for rid, engine in stack.engines.items():
            served = engine.health_check()["details"]["total_admitted"] - before[rid]
            (dev,) = placed[rid]
            stats = dev.memory_stats() or {}
            say(f"  [replicas] {rid} on {dev}: served {served} routed request(s)"
                + ("" if self_test else
                   f", hbm_in_use={stats.get('bytes_in_use', 0) / 1e9:.2f}GB"
                   f" peak={stats.get('peak_bytes_in_use', 0) / 1e9:.2f}GB"))
            check(served >= 1, f"replica {rid} served nothing")
            if not self_test:  # weights AND cache resident on this chip
                check(stats.get("bytes_in_use", 0) > weight_bytes,
                      f"{dev} holds {stats.get('bytes_in_use')} bytes, less than the weights ({weight_bytes})")


# ---------------------------------------------------------------------- main
def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--replicas", type=int, default=1,
                    help="N > 1: N one-chip engines behind the Router instead of the App phases")
    ap.add_argument("--cpu-self-test", action="store_true",
                    help="debug the script on the CPU at tiny widths (needs JAX_PLATFORMS=cpu)")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    if args.cpu_self_test and args.replicas > 1:
        jax.config.update("jax_num_cpu_devices", args.replicas)

    from gofr_tpu.models import llama
    from gofr_tpu.ops.backend import configure_compile_cache
    from gofr_tpu.serving import EngineConfig

    cache_dir = configure_compile_cache()
    device = jax.devices()[0]
    wanted = "cpu" if args.cpu_self_test else "tpu"
    if device.platform != wanted:
        print(f"chip_smoke: needs platform {wanted!r}, jax found {device.platform!r} "
              f"({device.device_kind}); no result", file=sys.stderr)
        return 2
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    if args.cpu_self_test:
        say("CPU SELF-TEST — tiny widths, Pallas interpreter; nothing below is a device number")
    say(f"platform={device.platform} device_kind={device.device_kind} count={len(jax.devices())} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu_version} "
        f"compile_cache={cache_dir}")

    t = time.monotonic()
    check_kernels(args.cpu_self_test)
    say(f"kernels: {time.monotonic() - t:.1f}s")

    t = time.monotonic()
    if args.cpu_self_test:
        cfg = llama.LlamaConfig.tiny(vocab_size=320, max_seq_len=EngineConfig().max_seq_len)
    else:
        cfg = llama.LlamaConfig()  # Llama-3-8B, published widths, no cut
    params = llama.init_params(cfg, jax.random.PRNGKey(SEED), quantize=True)
    jax.block_until_ready(params)
    say(f"weights: {cfg.n_layers} layers d={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"ff={cfg.d_ff} vocab={cfg.vocab_size} int8, {llama.param_bytes(params) / 1e9:.2f} GB, "
        f"init {time.monotonic() - t:.1f}s")

    if args.replicas > 1:
        replicas_and_check(cfg, params, args.replicas, args.cpu_self_test)
    else:
        # the default engine configuration, and the paged layout — dense
        # never reaches ops/paged_attention.py
        for label, engine_config in (
            ("dense", EngineConfig()),
            ("paged", EngineConfig(kv_layout="paged")),
        ):
            serve_and_check(label, cfg, params, engine_config, args.cpu_self_test)
            gc.collect()  # the stopped engine's KV leaves the chip before the next one

    result: dict[str, Any] = {"ok": True}
    if args.cpu_self_test:
        result["cpu_self_test"] = True
    result["device"] = {"platform": device.platform, "kind": device.device_kind,
                        "count": len(jax.devices())}
    say(f"total {time.monotonic() - T0:.1f}s")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
