"""One run of one cell: build the served system, warm it, drive a timed
window from a child process, check what the window served against the
plain reference, print the result line.

The entry the window drives is the one a user calls: a real
``gofr_tpu.App`` with ``register_generation_routes`` (assembled as
``chip_smoke.py:serve_and_check`` does), answering ``POST
/generate/stream`` over loopback. One process holds the chip; the load
generator is a child that never imports JAX.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Any

from benchmarks.harness import loadgen, stats, tokens, traffic
from benchmarks.harness.manifest import Manifest, lowering, reference_module, resolve

MOSAIC_CALL = "tpu_custom_call"  # what a Mosaic-compiled pallas_call lowers to
TRACE_DIR = ".bench_trace"       # inside the checkout, git-ignored, removed after the run


def say(t_start: float, msg: str) -> None:
    print(f"[{time.monotonic() - t_start:7.1f}s] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class RunData:
    """What a per-layer reader may look at."""
    workload: dict[str, Any]
    config: dict[str, Any]
    cell: dict[str, Any]
    records: list[dict[str, Any]]
    window: tuple[float, float]            # monotonic seconds
    traced: tuple[float, float] | None     # monotonic seconds of the profiled sub-window
    events: list[Any]                      # trace_reduce.Event, trace clock
    trace_offset_ns: int | None            # trace clock = monotonic_ns + offset
    requestz: dict[int, dict[str, Any]]
    health_polls: list[dict[str, Any]]
    device_kind: str
    wall_minus_mono: float | None = None   # time.time() - time.monotonic() in the serving process
    cache: dict[str, Any] = dataclasses.field(default_factory=dict)  # the readers' shared reductions

    def traced_ns(self) -> tuple[int, int] | None:
        if self.traced is None or self.trace_offset_ns is None:
            return None
        return (int(self.traced[0] * 1e9) + self.trace_offset_ns,
                int(self.traced[1] * 1e9) + self.trace_offset_ns)


# ------------------------------------------------------------------ pieces
def start_loadgen() -> subprocess.Popen:
    """Start the child before this process touches JAX. Its environment
    names no accelerator: it must never load the TPU's library."""
    env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    return subprocess.Popen(
        [sys.executable, "-S", os.path.abspath(loadgen.__file__)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True,
    )


def stop_loadgen(child: subprocess.Popen) -> None:
    for pipe in (child.stdin, child.stdout):
        try:
            if pipe:
                pipe.close()
        except OSError:
            pass
    if child.poll() is None:
        child.terminate()
        try:
            child.wait(timeout=10)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait(timeout=10)


class CompileCounter:
    """Counts XLA compilations through jax.monitoring: a backend compile
    and a persistent-cache hit each leave one duration event."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self) -> None:
        self.events: list[tuple[float, str]] = []
        self.names: list[tuple[float, str]] = []
        import logging

        import jax
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        # jax names what it compiles only in its log: catch the records,
        # keep them off standard error
        jax.config.update("jax_log_compiles", True)
        counter = self

        class Catch(logging.Handler):
            def emit(self, record: logging.LogRecord) -> None:
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    counter.names.append((time.monotonic(), msg.split(" with global shapes")[0][10:]))

        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch", "jax._src.compiler"):
            logger = logging.getLogger(name)
            logger.addHandler(Catch())
            logger.propagate = False

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event in (self.COMPILE, self.CACHE_HIT):
            self.events.append((time.monotonic(), event))

    def between(self, t0: float, t1: float) -> dict[str, int]:
        inside = [e for t, e in self.events if t0 <= t < t1]
        return {"compiled": inside.count(self.COMPILE), "from_cache": inside.count(self.CACHE_HIT),
                "names": [n for t, n in self.names if t0 <= t < t1]}


def engine_config(cell: dict[str, Any]) -> Any:
    from gofr_tpu.serving import EngineConfig

    settings = dict(cell["engine"])
    known = {f.name for f in dataclasses.fields(EngineConfig)}
    unknown = set(settings) - known
    if unknown:
        raise ValueError(f"cell engine settings name no EngineConfig field: {sorted(unknown)}")
    if "prefill_buckets" in settings:
        settings["prefill_buckets"] = tuple(int(b) for b in settings["prefill_buckets"])
    return EngineConfig(**settings)


def mosaic_calls(config: dict[str, Any], engine: Any, prompt_sizes: list[int]) -> tuple[dict[str, int], list[str]]:
    """Which attention path each of the cell's programs takes: the
    configuration's lowering gives the engine's own jitted programs as
    lowered text, at the shapes the warm-up uses; counted here are the
    Mosaic custom calls in each. Also returned: the programs that must
    hold a compiled kernel and lowered without one."""
    texts, must_hold = lowering(config)(engine, prompt_sizes)
    paths = {name: text.count(MOSAIC_CALL) for name, text in texts.items()}
    return paths, [name for name in must_hold if not paths.get(name)]


def http_json(url: str, timeout: float = 30.0) -> Any:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def warmup_requests(engine: Any, spec: dict[str, Any]) -> list[dict[str, Any]]:
    """One request per shape this cell's traffic uses and no others: each
    prefill bucket its prompt lengths reach, and the chunked path when a
    prompt can exceed a chunk. Each decodes two blocks and a tail."""
    from gofr_tpu.serving import batch as batch_ops

    shapes = traffic.longest_shapes(spec)
    lo, hi = shapes["prompt_min"], shapes["prompt_max"]
    sizes: list[int] = []
    for b in engine._buckets():
        n = min(b, hi)
        if n >= lo and not engine._route_chunked(n) and batch_ops.pad_bucket(n, engine._buckets()) == b:
            sizes.append(n)
        if b >= hi:
            break
    if engine._route_chunked(hi):
        # two full chunks and a ragged tail, or the longest prompt if shorter
        sizes.append(max(lo, min(hi, 2 * engine._chunk_tokens + 17)))
    rng = random.Random("bench:warmup")
    steps = 2 * engine._block_steps + 2
    return [{"index": -1 - i, "prompt": traffic._prompt_text(rng, n), "prompt_tokens": n,
             "max_tokens": steps, "chunked": engine._route_chunked(n)} for i, n in enumerate(sizes)]


def run_warmup(base: str, reqs: list[dict[str, Any]], t_start: float,
               max_admissions: int, hold_tokens: int) -> None:
    from urllib.parse import urlparse

    url = urlparse(base)
    # a cold compile of one 32-layer program takes a minute or two; a
    # request that has not answered in seven has met a dead engine
    deadline = time.monotonic() + 420

    def one(req: dict[str, Any]) -> dict[str, Any]:
        rec = loadgen.stream_one(url.hostname, url.port, req, time.monotonic(), deadline)
        if not stats.succeeded(rec):
            raise RuntimeError(f"warm-up request of {req['prompt_tokens']} tokens failed: {rec}")
        return rec

    for req in reqs:  # one at a time: each compiles its own programs
        t = time.monotonic()
        one(req)
        say(t_start, f"warm-up {req['prompt_tokens']} prompt tokens: {time.monotonic() - t:.2f}s")
    # then beside a decoding row: the engine folds the K requests it
    # admitted between two decode blocks into the device's decode state in
    # one program per K (batch.admit_decode_state), K up to
    # admission_per_step. A held row keeps the decode loop turning while
    # waves of K short requests land together, each K tried until its
    # program exists (or three times, where jax does not say)
    from gofr_tpu.serving import batch as batch_ops

    def folded() -> int | None:
        size = getattr(batch_ops.admit_decode_state, "_cache_size", None)
        return size() if callable(size) else None

    t = time.monotonic()
    errors: list[BaseException] = []

    def guarded(req: dict[str, Any]) -> None:
        try:
            one(req)
        except BaseException as exc:  # re-raised below, in the caller's thread
            errors.append(exc)

    bucketed = [r for r in reqs if not r["chunked"]]
    if not bucketed:
        return  # every prompt is chunked: the ragged dispatch folds its own rows in
    # the shortest prompt, one decode block: K of them are admitted together
    short = dict(min(bucketed, key=lambda r: r["prompt_tokens"]), max_tokens=2)
    held = hold_row(base, short["prompt"], hold_tokens)
    try:
        for k in range(1, max_admissions + 1):
            for _ in range(3):
                before = folded()
                threads = [threading.Thread(target=guarded, args=(short,)) for _ in range(k)]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                if errors:
                    raise errors[0]
                if before is not None and folded() > before:
                    break
    finally:
        held()
    say(t_start, f"warm-up waves of 1..{max_admissions} beside a decoding row: {time.monotonic() - t:.2f}s "
                 f"(admit programs: {folded()})")


def hold_row(base: str, prompt: str, max_tokens: int) -> Any:
    """Start one long greedy request and read it in the background;
    returns a function that cancels it (POST /generate/cancel) and waits."""
    import http.client
    from urllib.parse import urlparse

    url = urlparse(base)
    conn = http.client.HTTPConnection(url.hostname, url.port, timeout=600)
    conn.request("POST", "/generate/stream",
                 body=json.dumps({"prompt": prompt, "max_tokens": max_tokens, "temperature": 0.0}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    if resp.status != 200:
        raise RuntimeError(f"the held warm-up row was refused: {resp.status}")
    rid = None
    for raw in resp:
        if raw.startswith(b"data: "):
            rid = json.loads(raw[6:])["id"]  # the head frame comes first
            break

    def drain() -> None:
        try:
            for _ in resp:
                pass
        except (OSError, http.client.HTTPException):
            pass

    reader = threading.Thread(target=drain, daemon=True)
    reader.start()

    def release() -> None:
        req = urllib.request.Request(base + "/generate/cancel", data=json.dumps({"id": rid}).encode(),
                                     method="POST", headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=30).close()
        except OSError:
            pass  # it had finished by itself
        reader.join(timeout=60)
        conn.close()

    return release


# ------------------------------------------------------------- correctness
def send_window(child: subprocess.Popen, base: str, schedule: dict[str, Any], seconds: float,
                drain_s: float, t0: float) -> None:
    """Hand the child its window; it starts sending at ``t0``."""
    child.stdin.write(json.dumps({"base": base, "t0": t0, "seconds": seconds,
                                  "drain_s": drain_s, "schedule": schedule}) + "\n")
    child.stdin.flush()


def collect(child: subprocess.Popen, t1: float) -> dict[str, Any]:
    """Wait for the window to close and for every record."""
    _sleep_until(t1)
    line = child.stdout.readline()
    if not line:
        raise RuntimeError(f"the load generator died (exit {child.poll()})")
    return json.loads(line)


def sweep_rates(base: str, spec: dict[str, Any], rates: list[float], seed: int, seconds: float,
                drain_s: float, t_start: float) -> None:
    """The knee sweep (a builder's tool, tools/sweep.py): the same mix at
    several fixed rates against one warm server, one child each."""
    for rate in rates:
        schedule = traffic.generate(dict(spec, rate_per_s=rate), seed, seconds)
        child = start_loadgen()
        try:
            t0 = time.monotonic() + 0.25
            send_window(child, base, schedule, seconds, drain_s, t0)
            got = collect(child, t0 + seconds)
        finally:
            stop_loadgen(child)
        records, until = got["records"], float(got["observed_until"])
        s = stats.summarize(records, t0, t0 + seconds, until)
        half = t0 + seconds / 2
        early = [stats.ttft_ms(r, until) for r in records if r["due"] < half]
        late = [stats.ttft_ms(r, until) for r in records if r["due"] >= half]
        say(t_start, f"sweep rate {rate:g}/s: due {s['attempted']} failed {s['failed']} "
                     f"ttft p50 {s.get('ttft_p50_ms', 0):.0f} p90 {s.get('ttft_p90_ms', 0):.0f} ms "
                     f"(first half p50 {stats.percentile(early, .5):.0f}, second half p50 {stats.percentile(late, .5):.0f}) "
                     f"tpot p90 {s.get('tpot_p90_ms', 0):.1f} ms tok_s {s['tok_s']:.0f} "
                     f"drain {until - t0 - seconds:.1f}s")


# ------------------------------------------------------------- correctness
def pick_sample(records: list[dict[str, Any]], seed: int, k: int) -> list[dict[str, Any]]:
    """The longest served request and k-1 others drawn from the seed."""
    done = [r for r in records if stats.succeeded(r) and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_tokens"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    random.Random(f"bench:sample:{seed}").shuffle(rest)
    return [longest] + rest[: max(k - 1, 0)]


def served_tokens(rec: dict[str, Any], eos_id: int) -> list[int]:
    """The tokens the engine chose for a request: the streamed ones, and
    the EOS a natural stop ended on (chosen, but neither streamed nor
    counted in the terminal frame's usage)."""
    return list(rec["tokens"]) + ([eos_id] if rec["finish_reason"] == "stop" else [])


def structural_faults(records: list[dict[str, Any]], vocab: int, eos_id: int) -> list[str]:
    """What every answer must say whatever its tokens are."""
    faults = []
    for r in records:
        if not stats.succeeded(r):
            continue
        toks, n = served_tokens(r, eos_id), len(r["tokens"])
        if n != len(r["token_ts"]) or (r["completion_tokens"] is not None and n != r["completion_tokens"]):
            faults.append(f"request {r['index']}: {n} token frames ({r['finish_reason']}), terminal says {r['completion_tokens']}")
        elif len(toks) > r["max_tokens"] or (r["finish_reason"] == "length" and n != r["max_tokens"]):
            faults.append(f"request {r['index']}: {n} tokens for max_tokens {r['max_tokens']} ({r['finish_reason']})")
        elif any(not 0 <= t < vocab for t in toks) or eos_id in r["tokens"]:
            faults.append(f"request {r['index']}: a token outside the vocabulary, or a streamed EOS")
    return faults


def check_outputs(config: dict[str, Any], weights: Any, schedule: dict[str, Any],
                  records: list[dict[str, Any]], seed: int, limits: dict[str, Any],
                  eos_id: int, control_bits: int | None = None) -> dict[str, Any]:
    """Compare a seeded sample of what the window served with the plain
    reference: the widest gap by which a served token's reference logit
    lies below the reference's best. Greedy tokens only — all are. The
    reference is the module the configuration's file names."""
    reference = reference_module(config)
    by_index = {r["index"]: r for r in schedule["requests"]}
    sample = pick_sample(records, seed, int(limits.get("sample_requests", 4)))
    # one padded length for the whole mix: the reference compiles once
    longest = max(r["prompt_tokens"] + r["max_tokens"] for r in schedule["requests"])
    padded = reference.pad_to(longest, 128)
    worst, worst_control, n_tokens = 0.0, None, 0
    per_request = []
    for rec in sample:
        prompt = tokens.prompt_ids(by_index[rec["index"]]["prompt"])
        served = served_tokens(rec, eos_id)
        gaps = reference.served_gaps(config, weights, prompt, served, pad_len=padded,
                                     control_bits=control_bits)
        n_tokens += len(served)
        worst = max(worst, float(gaps["served"].max()))
        entry = {"index": rec["index"], "prompt_tokens": len(prompt), "served": len(served),
                 "gap_max": float(gaps["served"].max()),
                 "mismatch": int((gaps["served"] > 0).sum())}
        if control_bits is not None:
            entry["control_gap_max"] = float(gaps["control"].max())
            worst_control = max(worst_control or 0.0, entry["control_gap_max"])
        per_request.append(entry)
    out = {"reference": f"{config['reference']} ({reference.__name__})", "sampled_requests": len(sample),
           "sampled_tokens": n_tokens, "gap_max": worst, "per_request": per_request}
    if control_bits is not None:
        out["control_gap_max"] = worst_control
    return out


# ------------------------------------------------------------------ the run
def run_cell(root: str, workload_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, platform: str = "tpu", control_bits: int | None = None,
             fault: Any = None, keep_events: str | None = None,
             sweep: list[float] | None = None) -> tuple[int, dict[str, Any] | None]:
    """Returns (exit code, result object). ``platform`` is what JAX must
    report; the command always asks for ``tpu`` — only a test steers it.
    ``fault`` (tests only) is called with the engine before it starts, to
    break the timed path underneath."""
    manifest = Manifest(root)
    workload = manifest.workload(workload_name)
    config = manifest.config(workload["config"])
    spec = manifest.traffic(workload["traffic"])
    cell = manifest.cell(workload_name)
    schedule = traffic.generate(spec, seed, seconds)
    child = start_loadgen()
    trace_dir = os.path.join(manifest.root, TRACE_DIR)
    try:
        return _run(manifest, workload, config, spec, cell, schedule, child, seed, seconds,
                    trace, t_start, platform, control_bits, fault, trace_dir, keep_events, sweep)
    finally:
        stop_loadgen(child)
        shutil.rmtree(trace_dir, ignore_errors=True)


def _run(manifest: Manifest, workload: dict, config: dict, spec: dict, cell: dict,
         schedule: dict, child: subprocess.Popen, seed: int, seconds: float, trace: bool,
         t_start: float, platform: str, control_bits: int | None, fault: Any,
         trace_dir: str, keep_events: str | None,
         sweep: list[float] | None) -> tuple[int, dict[str, Any] | None]:
    import jax

    devices = jax.devices()
    device = devices[0]
    older = jax.live_arrays()  # not this run's to free
    if device.platform != platform or len(devices) < int(workload["chips"]):
        print(f"benchmark: cell {workload['name']} needs {workload['chips']} {platform} device(s); "
              f"jax found {len(devices)} x {device.platform} ({device.device_kind}); no result",
              file=sys.stderr)
        return 3, None

    import gofr_tpu
    from gofr_tpu.config import MapConfig
    from gofr_tpu.datasource.tpu import TPUClient
    from gofr_tpu.ops.backend import configure_compile_cache, kernel_mode
    from gofr_tpu.serving import ByteTokenizer, DeviceTelemetry, ServingEngine
    from gofr_tpu.serving.handlers import register_generation_routes
    from gofr_tpu.testutil import get_free_port

    from benchmarks.harness import peaks

    cache_dir = configure_compile_cache()
    # small programs (slot inserts, samplers) are cached too: every run is
    # a new process and would compile each of them again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()
    if platform == "tpu":
        peaks.peaks_for(device.device_kind)  # an unknown device is an error
    say(t_start, f"device platform={device.platform} kind={device.device_kind} count={len(devices)} "
                 f"jax={jax.__version__} kernels={kernel_mode()} compile_cache={cache_dir}")

    t = time.monotonic()
    factory = resolve(config["factory"])
    cfg, params = factory(config, seed)
    jax.block_until_ready(params)
    say(t_start, f"weights from seed {seed}: {time.monotonic() - t:.2f}s, "
                 f"hbm_in_use={_mem(device).get('bytes_in_use', 0) / 1e9:.2f}GB")

    http_port, metrics_port = get_free_port(), get_free_port()
    app_config = MapConfig({
        "HTTP_PORT": str(http_port), "METRICS_PORT": str(metrics_port),
        "APP_NAME": "bench-" + workload["name"], "LOG_LEVEL": "WARN",
    }, use_env=False)
    app = gofr_tpu.App(app_config)
    app.add_tpu(TPUClient.from_config(app_config))
    tokenizer = ByteTokenizer(cfg.vocab_size)
    engine = ServingEngine(
        cfg, params, engine_config(cell), tokenizer,
        metrics=app.container.metrics_manager, logger=app.container.logger,
        tracer=app.container.tracer, seed=seed & 0x7FFFFFFF,
    )
    register_generation_routes(app, engine)
    telemetry = DeviceTelemetry(engine, metrics=app.container.metrics_manager,
                                logger=app.container.logger, interval_s=1.0)
    app.on_start(lambda ctx: telemetry.start())
    app.on_shutdown(telemetry.stop)
    if fault is not None:
        fault(engine)

    warm = warmup_requests(engine, spec)
    t = time.monotonic()
    paths, bare = mosaic_calls(config, engine, [r["prompt_tokens"] for r in warm])
    say(t_start, "attention paths (Mosaic custom calls per program): "
                 + ", ".join(f"{k}={v}" for k, v in paths.items())
                 + f" [{time.monotonic() - t:.2f}s]")
    if platform == "tpu" and bare:
        raise RuntimeError(f"{', '.join(bare)} lowered without the Mosaic kernel its configuration says it holds")

    thread = threading.Thread(target=app.run, name="bench-app", daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{http_port}"
    try:
        deadline = time.monotonic() + 60
        while True:
            try:
                urllib.request.urlopen(base + "/.well-known/alive", timeout=1).close()
                break
            except OSError:
                if time.monotonic() > deadline or not thread.is_alive():
                    raise RuntimeError("the App never came up") from None
                time.sleep(0.05)
        run_warmup(base, warm, t_start, int(engine.config.admission_per_step),
                   traffic.longest_shapes(spec)["output_max"])
        if sweep:
            sweep_rates(base, spec, sweep, seed, seconds, float(cell["drain_s"]), t_start)

        # ---------------------------------------------------- the window
        drain_s = float(cell["drain_s"])
        t0 = time.monotonic() + 0.25
        t1 = t0 + seconds
        setup_s = t0 - t_start
        send_window(child, base, schedule, seconds, drain_s, t0)
        traced = None
        polls: list[dict[str, Any]] = []
        if trace:
            tr = cell.get("trace", {})
            start = t0 + min(float(tr.get("start_s", 3.0)), max(seconds - 1.0, 0.0))
            length = min(float(tr.get("seconds", 3.0)), max(t1 - start, 0.5))
            traced = _profile(start, length, trace_dir, base, polls, t1)
        got = collect(child, t1)
        records, observed_until = got["records"], float(got["observed_until"])
        in_window = compiles.between(t0, t1)
        mem = _mem(device)
        health = http_json(base + "/.well-known/health")["data"]["details"]["serving"]["details"]
        requestz: dict[int, dict[str, Any]] = {}
        if trace:
            for r in records:
                if r.get("request_id") is not None:
                    try:
                        requestz[r["request_id"]] = http_json(f"{base}/requestz/{r['request_id']}")["data"]
                    except OSError:
                        pass  # fell out of the ring: the reader sees fewer samples
    finally:
        app.stop()
        thread.join(timeout=120)
    if thread.is_alive():
        raise RuntimeError("the App did not shut down")

    summary = stats.summarize(records, t0, t1, observed_until, closed_loop=schedule["loop"] == "closed")
    say(t_start, f"window {seconds:g}s: due {summary['attempted']} failed {summary['failed']} "
                 f"cut at the end of observation {summary['cut']} records {len(records)}; "
                 f"compilations inside the window: {in_window['compiled']} "
                 f"(+{in_window['from_cache']} loaded from the cache) {in_window['names'] or ''}")
    say(t_start, "generator lateness: "
                 f"p50 {summary.get('generator_late_p50_ms', 0.0):.3f} ms, "
                 f"max {summary.get('generator_late_max_ms', 0.0):.3f} ms; ttft mean "
                 f"{summary.get('ttft_mean_ms', 0.0):.1f} p50 {summary.get('ttft_p50_ms', 0.0):.1f} "
                 f"p90 {summary.get('ttft_p90_ms', 0.0):.1f} ms, tpot p90 {summary.get('tpot_p90_ms', 0.0):.2f} ms, "
                 f"tok_s {summary['tok_s']:.1f}; samples: "
                 f"ttft {summary.get('ttft_samples', 0)}, tpot {summary.get('tpot_samples', 0)}")
    # ------------------------------------- free the program's state, then check
    vocab, eos_id = cfg.vocab_size, tokenizer.eos_id
    freed = free_device_state(params, older)
    del app, engine, telemetry
    gc.collect()
    say(t_start, f"hbm in use {mem.get('bytes_in_use', 0) / 1e9:.2f}GB peak {mem.get('peak_bytes_in_use', 0) / 1e9:.2f}GB "
                 f"of {mem.get('bytes_limit', 0) / 1e9:.2f}GB, after freeing every device array beside the weights "
                 f"({freed / 1e9:.2f}GB) {_mem(device).get('bytes_in_use', 0) / 1e9:.2f}GB; "
                 f"kv pages {health.get('kv_pages')}; scheduler {health.get('scheduler_backend')}")
    t = time.monotonic()
    limits = cell["correct"]
    due = stats.due_in_window(records, t0, t1)
    faults = structural_faults(due, vocab, eos_id)
    check = check_outputs(config, params, schedule, due, seed, limits, eos_id, control_bits)
    say(t_start, f"reference {check['reference']} over {check['sampled_requests']} requests, "
                 f"{check['sampled_tokens']} served tokens: {time.monotonic() - t:.2f}s")
    checks = {
        "gap_max": {"value": check["gap_max"], "limit": float(limits["gap_max"])},
        "failed": {"value": summary["failed"], "limit": 0},
        "wrong_answers": {"value": len(faults), "limit": 0},
        "sampled_tokens": {"value": check["sampled_tokens"], "limit_min": int(limits.get("min_tokens", 1))},
    }
    correct = (check["gap_max"] <= float(limits["gap_max"]) and summary["failed"] == 0
               and not faults and check["sampled_tokens"] >= int(limits.get("min_tokens", 1))
               and summary["attempted"] > 0)
    if control_bits is not None:
        checks["control_gap_max"] = {"value": check["control_gap_max"], "limit": float(limits["gap_max"])}

    metrics: dict[str, dict[str, Any]] = {}
    device_out: dict[str, Any] = {
        "platform": device.platform, "kind": device.device_kind,
        "count": int(workload["chips"]), "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
    }
    breakdown = None
    if not trace:
        for m in manifest.metrics_for("end_to_end", workload["name"]):
            value = setup_s if m["name"] == "setup_s" else summary.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        run = _load_trace(RunData(workload, config, cell, records, (t0, t1), traced, [], None,
                                  requestz, polls, device.device_kind,
                                  time.time() - time.monotonic()),
                          trace_dir, t_start, keep_events)
        from benchmarks.harness import trace_reduce

        span = run.traced_ns()
        if span is not None:
            device_out["busy_s"] = trace_reduce.busy_seconds(run.events, *span)
            device_out["window_s"] = (span[1] - span[0]) / 1e9
            ops = trace_reduce.leaf_op_times(run.events, *span)
            top = sorted(ops.items(), key=lambda kv: -kv[1]["seconds"])[:10]
            breakdown = {"device_ops": [[k, v["seconds"]] for k, v in top],
                         "idle_gaps": [[k, v] for k, v in trace_reduce.idle_gaps(run.events, *span)]}
            progs = trace_reduce.program_times(run.events, *span)
            say(t_start, "programs in the traced sub-window: "
                         + ", ".join(f"{k} x{int(v['count'])} {v['seconds']:.3f}s" for k, v in
                                     sorted(progs.items(), key=lambda kv: -kv[1]["seconds"])[:8]))
        for m in manifest.metrics_for("per_layer", workload["name"]):
            value = manifest.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for fault_line in faults[:5]:
        say(t_start, "wrong answer: " + fault_line)
    for entry in check["per_request"]:
        say(t_start, f"checked request {entry}")
    print("compared: " + ", ".join(
        f"{k}={v['value']} ({'>=' if 'limit_min' in v else '<='} {v.get('limit', v.get('limit_min'))})"
        for k, v in checks.items()) + f" -> correct={correct}", file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": summary["attempted"], "failed": summary["failed"],
              "metrics": metrics, "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return 0, result


def _mem(device: Any) -> dict[str, Any]:
    return device.memory_stats() or {}


def free_device_state(keep: Any, older: Any = ()) -> int:
    """Delete every device array the process holds whose buffer is none
    of ``keep``'s (the weights), but for the arrays of ``older`` (those
    alive before the run began: in a run of the command none that matter,
    in a test process other tests'). The runtime knows every array, so a
    cache of any layout goes — one pool or one a layer type, a latent pool,
    recurrent state — whatever holds it. One device: an array is one
    buffer. Returns the bytes deleted."""
    import jax

    kept = {leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(keep)}
    spared = {id(array) for array in older}
    freed = 0
    for array in jax.live_arrays():
        if id(array) in spared or array.is_deleted() or array.unsafe_buffer_pointer() in kept:
            continue
        freed += int(array.nbytes)
        array.delete()
    return freed


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.5))


def _profile(start: float, length: float, trace_dir: str, base: str,
             polls: list[dict[str, Any]], t1: float) -> tuple[float, float]:
    """Poll the engine's health once a second through the window, and
    profile ``length`` seconds of steady state from ``start``. The
    harness's own TraceAnnotations put the host's monotonic stamps on the
    trace's clock."""
    import jax.profiler

    def poll() -> None:
        try:
            d = http_json(base + "/.well-known/health", timeout=5)["data"]["details"]["serving"]["details"]
            polls.append({"t": time.monotonic(), "kv_pages": d.get("kv_pages"),
                          "slots_active": d.get("slots_active"), "queue_depth": d.get("queue_depth")})
        except OSError:
            pass

    def poll_until(t: float) -> None:
        while time.monotonic() < t:
            poll()
            _sleep_until(min(t, time.monotonic() + 1.0))

    poll_until(start)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    a = time.monotonic()
    with jax.profiler.TraceAnnotation(f"bench.mark:{int(a * 1e9)}"):
        pass
    poll_until(a + length)
    b = time.monotonic()
    with jax.profiler.TraceAnnotation(f"bench.mark:{int(b * 1e9)}"):
        pass
    jax.profiler.stop_trace()
    poll_until(t1)
    return a, b


def _load_trace(run: RunData, trace_dir: str, t_start: float, keep: str | None) -> RunData:
    from benchmarks.harness import trace_reduce

    t = time.monotonic()
    run.events = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    # each marker's name carries the host's monotonic stamp at its start
    offsets = []
    for e in run.events:
        if e.name.startswith("bench.mark:"):
            offsets.append(e.start_ns - int(e.name.split(":", 1)[1]))
    if offsets:
        run.trace_offset_ns = int(sum(offsets) / len(offsets))
    if keep:  # a builder's tool (benchmarks/tools/), never the command
        trace_reduce.save_events(run.events, keep)
    say(t_start, f"trace: {len(run.events)} events, clock offset from {len(offsets)} markers, "
                 f"read in {time.monotonic() - t:.2f}s")
    return run
