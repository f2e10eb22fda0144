"""The engine's step spans (docs/observability.md "Engine step spans"): every
phase of the step loop as a ``gofr.step[.<phase>]`` event in a profiler
trace, the one phase account behind ``busy_seconds()`` and
``app_engine_phase_seconds_total``, and the block numbers that join a
request to its blocks. Tiny widths on the CPU; the traces are read with the
benchmark's own reader (``benchmarks/harness/host_spans.py``), so that what
the engine writes and what the benchmark parses are held to each other."""

import threading
import time
from types import SimpleNamespace

import jax
import pytest

from benchmarks.harness import host_spans, trace_reduce
from gofr_tpu.config import MapConfig
from gofr_tpu.container.container import Container
from gofr_tpu.models import llama
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving import batch as batch_ops
from gofr_tpu.serving import engine as engine_mod

KINDS = {
    # dispatch kind -> engine settings, the open request's prompt, the
    # phases that kind of engine can show
    "decode": (dict(), "open", set(engine_mod.STEP_PHASES) - {"commit.chunks"}),
    # a prompt longer than a chunk prefills through ragged dispatches
    "ragged": (dict(prefill_chunk_tokens=16), "a long open prompt of three chunks",
               set(engine_mod.STEP_PHASES)),
    # speculative decoding keeps its decode state on the host (no fold) and
    # commits a chunk in one piece
    "spec": (dict(spec_tokens=2, multi_step=None), "open",
             set(engine_mod.STEP_PHASES) - {"fold", "commit.rows", "commit.chunks", "commit.stats"}),
}
STEPS = 4
PARTS = {"dispatch.rows", "dispatch.launch", "dispatch.count", "commit.rows", "commit.chunks", "commit.stats"}


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def make_engine(model, metrics=None, **kw):
    settings = dict(max_slots=3, max_seq_len=128, prefill_buckets=(16,), multi_step=STEPS,
                    kv_page_size=8)
    settings.update(kw)
    cfg, params = model
    return ServingEngine(cfg, params, EngineConfig(**settings), ByteTokenizer(), metrics=metrics)


def drive(engine, open_prompt):
    """One open request, and one admitted while the first is mid-block."""
    decoding = threading.Event()
    first = engine.submit(open_prompt, max_new_tokens=22, temperature=0.0,
                          stream_cb=lambda tid, piece, done: len(piece) >= 0 and decoding.set())
    assert decoding.wait(120)
    second = engine.submit("late", max_new_tokens=7, temperature=0.0)
    return [first.result(timeout=120), second.result(timeout=120)], [first.request_id, second.request_id]


def settles_in(engine, phase, timeout=30.0):
    """Bounded poll: the loop thread reaches ``phase`` (and stays there
    for an idle engine) within ``timeout`` seconds, however many workers
    share the host."""
    deadline = time.monotonic() + timeout
    while engine._phase_state[0] != phase and time.monotonic() < deadline:
        time.sleep(0.01)
    return engine._phase_state[0] == phase


def read_spans(trace_dir):
    """The trace's engine spans by start, without the iteration the trace's
    end cut: a span still open when the session stops is not written, so
    that iteration's inner spans come without their gofr.step."""
    events = host_spans.load_host_events(trace_reduce.find_xplane(str(trace_dir)))
    spans = sorted((host_spans.parse(e) for e in events), key=lambda s: (s.start_ns, -s.dur_ns))
    whole = max(s.end_ns for s in spans if s.phase == "step")
    return [s for s in spans if s.end_ns <= whole]


def run_over(spans, fold_parts=False):
    """The benchmark's view of a list of spans: a RunData whose traced
    sub-window holds them all, with the parts of dispatch and commit
    left in or taken out (their time then falls to their parents)."""
    from benchmarks.harness.runner import RunData

    events = [trace_reduce.Event(*s.thread.rsplit("/", 1), "gofr.step" + ("" if s.phase == "step" else "." + s.phase)
                                 + "#" + ",".join(f"{k}={v}" for k, v in s.kw.items()) + "#", s.start_ns, s.dur_ns)
              for s in spans if not (fold_parts and s.phase in PARTS)]
    a, b = min(s.start_ns for s in spans), max(s.end_ns for s in spans)
    return RunData({"name": "x"}, {}, {"engine": {"max_slots": 3}}, [], (0.0, 1.0), (a / 1e9, b / 1e9),
                   events, 0, {}, [], "cpu")


def depths(spans):
    """Nesting depth of every span, thread by thread; raises if two spans
    of a thread overlap without one holding the other."""
    out = []
    threads = {}
    for s in spans:
        threads.setdefault(s.thread, []).append(s)
    for group in threads.values():
        stack = []
        for s in group:
            while stack and stack[-1].end_ns <= s.start_ns:
                stack.pop()
            assert not stack or s.end_ns <= stack[-1].end_ns, (s, stack[-1])
            out.append((s, len(stack), stack[-1].phase if stack else None))
            stack.append(s)
    return out


@pytest.fixture(scope="module", params=list(KINDS))
def traced(request, model, tmp_path_factory):
    """One engine of each dispatch kind, driven once to compile and once
    under ``jax.profiler.trace``."""
    kind = request.param
    settings, prompt, phases = KINDS[kind]
    engine = make_engine(model, **settings)
    finished = []
    finish = engine._finish
    engine._finish = lambda req, reason: (finished.append(req), finish(req, reason))[1]
    syncs = []
    patch = pytest.MonkeyPatch()
    block_sync = engine_mod._block_sync
    patch.setattr(engine_mod, "_block_sync", lambda value: (syncs.append(1), block_sync(value))[1])
    engine.start()
    try:
        drive(engine, prompt)
        deadline = time.monotonic() + 30
        while (engine._inflight_q or engine._phase_state[0] != "wait") and time.monotonic() < deadline:
            time.sleep(0.02)  # the pipeline drains its last block: a span cut by the trace's start has no parent
        del finished[:], syncs[:]
        launched = dict(engine._launched)
        trace_dir = tmp_path_factory.mktemp(f"trace-{kind}")
        with jax.profiler.trace(str(trace_dir)):
            time.sleep(0.12)  # an idle engine waits
            results, ids = drive(engine, prompt)
            time.sleep(0.06)
        views = [engine.timeline.get(i).to_dict() for i in ids]
    finally:
        engine.stop()
        patch.undo()
    return SimpleNamespace(kind=kind, phases=phases, spans=read_spans(trace_dir), results=results,
                           views=views, requests=list(finished), syncs=len(syncs),
                           launched={k: v - launched[k] for k, v in engine._launched.items()})


def test_every_phase_is_a_span_nested_under_a_step(traced):
    seen = depths(traced.spans)
    assert {s.phase for s, _, _ in seen} == traced.phases
    for s, depth, parent in seen:
        if s.phase == "step":
            assert depth == 0 and s.kw["iter"] > 0 and s.kw["mono_ns"] > 0
        else:
            assert depth >= 1, s
    parents = {(s.phase, parent) for s, _, parent in seen}
    assert ("prefill_sync", "prefill") in parents and ("prefill", "admit") in parents
    if "fold" in traced.phases:
        assert ("fold", "dispatch") in parents
    # a part of dispatch or of commit lies inside a span of that name, and nowhere else
    assert {(p, parent) for p, parent in parents if p in PARTS} == {
        (p, p.split(".")[0]) for p in PARTS & traced.phases}
    # the iteration's number and the host's clock ride every gofr.step
    iters = [s.kw["iter"] for s in traced.spans if s.phase == "step"]
    assert iters == sorted(set(iters))


def test_dispatch_kind_and_keywords(traced):
    blocks = [s for s in traced.spans if s.phase == "dispatch" and "blk" in s.kw]
    assert traced.kind in {s.kw["kind"] for s in blocks}
    keywords = {"blk", "kind", "rows", "steps", "kv_tokens", "chunk_rows", "chunk_tokens", "cold", "dev_idle", "cpu_us"}
    for s in blocks:
        # the sampler's path rides the blocks whose steps end in ops/sampling.sample_logits
        assert set(s.kw) == keywords | ({"sampler"} if traced.kind != "spec" else set())
        assert s.kw["cold"] == 0  # the same traffic ran once before the trace
        assert s.kw["steps"] == (3 if traced.kind == "spec" else STEPS)
        assert (s.kw["chunk_rows"] > 0) == (s.kw["kind"] == "ragged")
    assert any(s.kw["rows"] == 2 and s.kw["kv_tokens"] > 0 for s in blocks)  # both rows in one block
    routes = {s.kw.get("route") for s in traced.spans if s.phase == "prefill"}
    assert routes == ({"chunked", "bucketed"} if traced.kind == "ragged" else {"bucketed"})


def test_self_time_adds_up_to_the_iterations_and_the_parts_change_no_sum(traced):
    """Every instant of an iteration belongs to one phase, and what the
    parts of dispatch and commit hold is what their parents held before:
    the benchmark's sums by prefix read the same with the parts folded
    back into them."""
    run, folded = run_over(traced.spans), run_over(traced.spans, fold_parts=True)
    by_phase, before = host_spans.self_seconds_by_phase(run), host_spans.self_seconds_by_phase(folded)
    iterations = sum(s.dur_ns for s in traced.spans if s.phase == "step") / 1e9
    assert sum(by_phase.values()) == pytest.approx(iterations, abs=1e-9)
    for parent in ("dispatch", "commit"):
        parts = sum(v for p, v in by_phase.items() if p.split(".")[0] == parent)
        assert parts == pytest.approx(before[parent], abs=1e-9)
    assert PARTS & set(by_phase) and not PARTS & set(before)
    assert [s.kw["blk"] for s in host_spans.blocks(run)] == [s.kw["blk"] for s in host_spans.blocks(folded)]
    assert host_spans.host_ms_per_block(run) == pytest.approx(host_spans.host_ms_per_block(folded), abs=1e-6)
    assert host_spans.slot_use_pct(run) == host_spans.slot_use_pct(folded)


def test_every_span_carries_its_cpu_time_and_a_block_says_whether_the_device_waited(traced):
    for s in traced.spans:
        # its own thread's clock: never more than its duration, but for a tick of that clock (10 ms on some hosts)
        assert 0 <= s.kw["cpu_us"] <= s.dur_ns / 1e3 + 11_000, s
    blocks = [s for s in traced.spans if s.phase == "dispatch" and "blk" in s.kw]
    # the loop's count of launches is the spans'
    assert traced.launched == {launch: sum(s.kw["dev_idle"] == said for s in blocks)
                               for said, launch in enumerate(engine_mod.LAUNCHES)}
    if traced.kind == "spec":
        assert {s.kw["dev_idle"] for s in blocks} == {2}  # a chunk is read before the next is built
    else:
        # a block with none in flight follows a wait or an iteration that dispatched nothing
        assert blocks[0].kw["dev_idle"] == 2 and {s.kw["dev_idle"] for s in blocks} <= {0, 1, 2}
        assert traced.launched["none"] <= len(blocks) // 2


def test_a_blocks_dispatch_sync_and_commit_carry_one_number(traced):
    by_phase = {p: [s.kw["blk"] for s in traced.spans if s.phase == p and "blk" in s.kw]
                for p in ("dispatch", "sync", "commit")}
    assert by_phase["dispatch"] == sorted(set(by_phase["dispatch"]))  # one span a block, in order
    assert by_phase["sync"] == by_phase["commit"] == by_phase["dispatch"]
    assert traced.syncs == len(by_phase["sync"])  # _block_sync: still once a block, inside its span


def test_rows_times_steps_is_what_the_requests_were_dispatched(traced):
    blocks = [s for s in traced.spans if s.phase == "dispatch" and "blk" in s.kw]
    commits = [s for s in traced.spans if s.phase == "commit"]
    committed = sum(len(r.tokens) - 1 for r in traced.requests)  # the first token is the prefill's
    assert sum(s.kw["tokens"] for s in commits) == committed
    assert sum(s.kw["retired"] for s in commits) == len(traced.requests) == 2
    if traced.kind == "spec":
        # a verify chunk computes rows x (drafts + 1) positions and keeps a prefix of each
        assert sum(s.kw["rows"] * s.kw["steps"] for s in blocks) >= committed
    else:
        # committed tokens plus those discarded at retire: every step a row was dispatched for
        assert sum(s.kw["rows"] * s.kw["steps"] for s in blocks) == sum(r.dispatched for r in traced.requests)
        assert sum(r.dispatched for r in traced.requests) >= committed


def test_requestz_joins_a_request_to_its_blocks(traced):
    seen = {s.kw["blk"] for s in traced.spans if s.phase == "commit"}
    for view in traced.views:
        decode = view["decode"]
        assert decode["first_blk"] <= decode["last_blk"]
        assert {decode["first_blk"], decode["last_blk"]} <= seen
        assert decode["last_blk"] - decode["first_blk"] + 1 >= decode["blocks"] >= 1


def test_requestz_carries_the_loops_account_at_admission_and_at_the_end(traced):
    for view in traced.views:
        admit, end, during = (view["loop"][k] for k in ("at_admit", "at_end", "during"))
        assert admit["ms"] == view["phases_ms"]["admitted"] or admit["ms"] >= view["phases_ms"]["admitted"]
        for key in ("ms", "blocks", "launched_idle", "launched_queued"):
            assert admit[key] <= end[key], key
        for key in ("phase_ms", "cpu_ms"):
            assert set(admit[key]) == set(end[key]) == set(engine_mod.STEP_PHASES)
            assert all(admit[key][p] <= end[key][p] for p in admit[key]), key
        assert during["blocks"] == end["blocks"] - admit["blocks"] >= view["decode"]["blocks"] >= 1
        assert during["host_ms_per_block"] > 0
        launched = (end["launched_idle"] - admit["launched_idle"]) + (end["launched_queued"] - admit["launched_queued"])
        assert (during["launched_idle_share"] is None) == (launched == 0)
        assert during["launched_idle_share"] is None or 0.0 <= during["launched_idle_share"] <= 1.0


# ------------------------------------------------------- the sampler's path
def test_the_sampler_s_path_rides_the_spans_and_the_first_token_is_one_program(model, tmp_path, monkeypatch):
    """A bucketed admission and a whole-prompt chunk-cache hit each take
    their first token from ``batch.sample_first_token`` once and never call
    ``sample_logits`` outside a trace; ``gofr.step.dispatch`` says
    ``sampler=greedy`` until a live row sets top-p, then ``sampler=filter``;
    ``app_sampler_steps_total{path}`` is the spans' steps, and one a first
    token."""
    from gofr_tpu.ops import sampling

    container = Container(MapConfig({"LOG_LEVEL": "ERROR"}, use_env=False))
    metrics = container.metrics_manager
    engine = make_engine(model, metrics=metrics, prefill_chunk_tokens=16, prefix_cache_entries=16)
    first_tokens, eager = [], []
    entry, sampler = batch_ops.sample_first_token, sampling.sample_logits

    def spy_entry(logits, *a):
        first_tokens.append(logits.shape)
        return entry(logits, *a)

    def spy_sampler(logits, *a, **kw):
        if not isinstance(logits, jax.core.Tracer):
            eager.append(logits.shape)
        return sampler(logits, *a, **kw)

    monkeypatch.setattr(batch_ops, "sample_first_token", spy_entry)
    monkeypatch.setattr(batch_ops, "sample_logits", spy_sampler)
    monkeypatch.setattr(sampling, "sample_logits", spy_sampler)
    long_prompt = "a prompt of three chunks and a bit"  # 34 tokens: chunked, cached at its boundaries
    engine.start()
    try:
        with jax.profiler.trace(str(tmp_path)):
            engine.submit("open", max_new_tokens=6, temperature=0.0).result(timeout=120)
            cold = engine.submit(long_prompt, max_new_tokens=5, temperature=0.0).result(timeout=120)
            assert first_tokens == [(1, 300)]  # the bucketed one; the chunk path samples inside its dispatch
            hit = engine.submit(long_prompt, max_new_tokens=5, temperature=0.0).result(timeout=120)
            assert hit.token_ids == cold.token_ids and first_tokens == [(1, 300)] * 2
            decoding = threading.Event()
            greedy = engine.submit("greedy row", max_new_tokens=30, temperature=0.0,
                                   stream_cb=lambda tid, piece, done: decoding.set())
            assert decoding.wait(120)
            sampled = engine.submit("sampled", max_new_tokens=6, temperature=0.8, top_p=0.9)
            assert sampled.result(timeout=120).completion_tokens >= 1 and greedy.result(timeout=120)
            assert settles_in(engine, "wait")
            time.sleep(0.06)
    finally:
        engine.stop()
    assert first_tokens == [(1, 300)] * 4 and eager == []
    spans = read_spans(tmp_path)
    prefills = [(s.kw["route"], s.kw.get("sampler")) for s in spans if s.phase == "prefill"]
    assert prefills == [("bucketed", "greedy"), ("chunked", None), ("prefix_hit", "greedy"),
                        ("bucketed", "greedy"), ("bucketed", "filter")]
    blocks = [s.kw for s in spans if s.phase == "dispatch" and "blk" in s.kw]
    paths = [kw["sampler"] for kw in blocks]
    assert "sample" not in paths and paths.index("filter") > 0
    # greedy until the row with a top-p decodes, greedy again once it has retired
    assert [kw["sampler"] for kw in blocks if kw["rows"] == 2] == ["filter"] * paths.count("filter")
    assert paths[-1] == "greedy"
    counter = metrics.get("app_sampler_steps_total")
    for path in ("greedy", "filter"):
        steps = sum(kw["steps"] for kw in blocks if kw["sampler"] == path)
        assert counter.value({"path": path}) == steps + sum(1 for _, p in prefills if p == path) > 0
    assert counter.value({"path": "sample"}) == 0
    container.close()


# ------------------------------------------------------------ the account
def test_phase_account_covers_the_loop_and_busy_leaves_wait_out(model):
    container = Container(MapConfig({"LOG_LEVEL": "ERROR"}, use_env=False))
    metrics = container.metrics_manager
    engine = make_engine(model, metrics=metrics)
    t0 = time.monotonic()
    engine.start()
    try:
        drive(engine, "open")
        time.sleep(0.8)  # and an idle stretch (long beside what start() and stop() take outside the account)
    finally:
        engine.stop()
    wall = time.monotonic() - t0
    account = dict(engine._phase_s)
    assert set(account) == set(engine_mod.STEP_PHASES)
    assert sum(account.values()) >= 0.95 * wall
    assert sum(account.values()) <= wall
    assert account["wait"] >= 0.25
    assert engine.busy_seconds() == pytest.approx(sum(account.values()) - account["wait"])
    assert engine._phase_state[0] is None  # nothing left open
    # the counter is the account, carried over once an iteration
    counter = metrics.get("app_engine_phase_seconds_total")
    for phase in ("wait", "sync", "prefill", "dispatch", "commit"):
        assert counter.value({"phase": phase}) == pytest.approx(account[phase], rel=0.05), phase
    tokens = metrics.get("app_step_tokens_total")
    decode = tokens.value({"kind": "decode"})
    assert decode >= 22 + 7 - 2 and decode % STEPS == 0
    assert tokens.value({"kind": "padding"}) > 0 and tokens.value({"kind": "prefill"}) == 0
    # the host's share of a block is a rate of the two counters, by phase:
    # the CPU one never passes the wall one, and every phase has its series
    cpu = metrics.get("app_engine_phase_cpu_seconds_total")
    for phase in engine_mod.STEP_PHASES:
        assert engine._phase_cpu_s[phase] <= account[phase] + 0.011 + 0.01 * account[phase], phase  # a tick of room
        assert cpu.value({"phase": phase}) == pytest.approx(engine._phase_cpu_s[phase], rel=0.05, abs=1e-6), phase
        assert f'app_engine_phase_cpu_seconds_total{{phase="{phase}"}}' in metrics.expose_prometheus()
    host = sum(account[p] for p in ("fold", "dispatch", "commit") + tuple(PARTS))
    assert 0 < host / engine._blk_seq < 1.0 and cpu.value({"phase": "wait"}) < 0.1 * account["wait"]
    blocks = metrics.get("app_engine_blocks_total")
    assert {k: blocks.value({"launch": k}) for k in engine_mod.LAUNCHES} == engine._launched
    assert sum(engine._launched.values()) == engine._blk_seq  # every block, once
    assert 0 < engine._launched["none"] < engine._blk_seq  # the first block found none in flight
    assert metrics.get("app_decode_host_ms_per_step") is None  # gone: the rate of the counters replaces it
    container.close()


def test_a_busy_phase_reads_cpu_as_wall_and_a_sleeping_one_reads_none(model):
    engine = make_engine(model)  # never started: the caller owns the account
    with engine._phase("plan"):
        # busy until the thread itself has burnt 0.2 s of CPU: beside other
        # processes' compiles a wall-clock loop may get a third of a core,
        # and what is checked is the thread's work, not the machine's share
        cpu0 = time.thread_time()
        while time.thread_time() - cpu0 < 0.2:
            pass
        burnt = time.thread_time() - cpu0
    with engine._phase("wait"):
        time.sleep(0.2)
    wall, cpu = engine._phase_s, engine._phase_cpu_s
    assert wall["plan"] >= 0.2 and wall["wait"] >= 0.2
    # a thread's CPU clock may tick in steps of 10 ms: a tick of room on either side
    assert burnt - 0.011 <= cpu["plan"] <= wall["plan"] + 0.011
    assert cpu["wait"] <= 0.011 + 0.05 * wall["wait"]
    account = engine.loop_account()
    assert account["phase_s"] == wall and account["cpu_s"] == cpu and account["phase_s"] is not wall
    assert account["blocks"] == 0 and account["t"] <= time.monotonic()


class _Clocks:
    """The two clocks the loop's account reads (``time.monotonic``, the
    thread's ``time.thread_time_ns``), advanced by the test: a busy
    second moves both, a sleeping one the wall clock alone. Every other
    name is the real ``time`` module's."""

    def __init__(self) -> None:
        self.wall, self.cpu_ns = 1000.0, 0

    def __getattr__(self, name):
        return getattr(time, name)

    def monotonic(self) -> float:
        return self.wall

    def thread_time_ns(self) -> int:
        return self.cpu_ns

    def busy(self, seconds: float) -> None:
        self.wall += seconds
        self.cpu_ns += int(seconds * 1e9)

    def sleep(self, seconds: float) -> None:
        self.wall += seconds


def test_the_account_charges_each_phase_the_two_clocks_it_spanned(model, monkeypatch):
    # the arithmetic alone, on scripted clocks: a busy phase is charged its
    # CPU seconds in full, a sleeping one none, and both their wall seconds
    clock = _Clocks()
    monkeypatch.setattr(engine_mod, "time", clock)
    engine = make_engine(model)
    with engine._phase("plan"):
        clock.busy(0.2)
    with engine._phase("wait"):
        clock.sleep(0.3)
    wall, cpu = engine._phase_s, engine._phase_cpu_s
    assert wall["plan"] == pytest.approx(0.2) and cpu["plan"] == pytest.approx(0.2)
    assert wall["wait"] == pytest.approx(0.3) and cpu["wait"] == 0
    assert sum(wall.values()) == pytest.approx(0.5) and sum(cpu.values()) == pytest.approx(0.2)
    account = engine.loop_account()
    assert account["phase_s"] == wall and account["cpu_s"] == cpu
    assert account["t"] == clock.monotonic()


@pytest.mark.parametrize("newest, said, counted", [("ready", 1, "idle"), ("running", 0, "queued"), (None, 2, "none")])
def test_a_launch_is_onto_an_idle_device_when_the_newest_block_in_flight_is_ready(model, newest, said, counted):
    container = Container(MapConfig({"LOG_LEVEL": "ERROR"}, use_env=False))
    metrics = container.metrics_manager
    engine = make_engine(model, metrics=metrics)
    asked = []
    if newest is not None:
        # an older block still running does not matter: the newest is what was queued last
        older = SimpleNamespace(is_ready=lambda: asked.append("older") or False)
        packed = SimpleNamespace(is_ready=lambda: asked.append("newest") or newest == "ready")
        engine._inflight_q.extend(engine_mod._Inflight(p, [], 0.0) for p in (older, packed))
    with engine._phase("dispatch") as span:
        dev_idle = engine._launch_idle()
        engine._count_launch(span, dev_idle)
    assert dev_idle == said and asked == ([] if newest is None else ["newest"])
    assert engine._launched == {k: int(k == counted) for k in engine_mod.LAUNCHES}
    blocks = metrics.get("app_engine_blocks_total")
    assert {k: blocks.value({"launch": k}) for k in engine_mod.LAUNCHES} == engine._launched
    account = engine.loop_account()
    assert (account["launched_idle"], account["launched_queued"]) == (engine._launched["idle"], engine._launched["queued"])
    engine._inflight_q.clear()
    container.close()


def test_kv_pages_gauge_counts_pages_not_tokens(model):
    container = Container(MapConfig({"LOG_LEVEL": "ERROR"}, use_env=False))
    metrics = container.metrics_manager
    engine = make_engine(model, metrics=metrics, kv_num_pages=64)
    seen = []
    consume = engine._consume_block

    def watching(rec):
        consume(rec)
        kv = engine.paged_cache.stats()
        seen.append((metrics.get("app_kv_cache_pages_used").value(), kv["total_blocks"] - kv["free_blocks"],
                     int(engine.cache_len.sum())))

    engine._consume_block = watching
    engine.start()
    try:
        engine.submit("a prompt of some length", max_new_tokens=12, temperature=0.0).result(timeout=120)
    finally:
        engine.stop()
    assert seen and all(gauge == pages for gauge, pages, _ in seen)
    assert any(tokens > pages > 0 for _, pages, tokens in seen)  # 8 tokens a page: never the same number
    container.close()


def test_busy_seconds_counts_the_open_phase(model):
    """A caller that saw its request complete sees the work in
    busy_seconds() already, with no flush by the engine."""
    engine = make_engine(model)
    engine.start()
    try:
        engine.submit("busy", max_new_tokens=2).result(timeout=120)
        assert engine.busy_seconds() > 0.0
        time.sleep(0.2)  # idle now: only the closed phases but wait
        idle = engine.busy_seconds()
        time.sleep(0.2)
        assert engine.busy_seconds() - idle < 0.05
    finally:
        engine.stop()


# ------------------------------------------------------------- unwinding
def test_a_step_that_raises_leaves_no_span_open(model, tmp_path, monkeypatch):
    engine = make_engine(model)
    real = batch_ops.decode_block_paged
    raised = []

    def failing(*a, **kw):
        if not raised:
            raised.append(1)
            raise RuntimeError("injected dispatch failure")
        return real(*a, **kw)

    engine.start()
    try:
        engine.submit("warm", max_new_tokens=6).result(timeout=120)
        monkeypatch.setattr(batch_ops, "decode_block_paged", failing)
        with jax.profiler.trace(str(tmp_path)):
            with pytest.raises(RuntimeError, match="injected"):
                engine.submit("doomed", max_new_tokens=6).result(timeout=120)
            assert engine.submit("after", max_new_tokens=6).result(timeout=120).completion_tokens == 6
    finally:
        engine.stop()
    seen = depths(read_spans(tmp_path))  # raises on a span left open across its parent's end
    assert raised and all(depth == 0 for s, depth, _ in seen if s.phase == "step")
    failed = [s for s, _, _ in seen if s.phase == "dispatch" and "blk" not in s.kw]
    assert failed  # the span of the dispatch that raised closed without a block
    assert engine._phase_state[0] is None


def test_a_retired_thread_unwinds_without_touching_the_account(model, monkeypatch):
    """A quarantined loop thread thaws inside its dispatch span after a
    warm restart: its spans close, the replacement's account is not
    written by it, and the replacement's next gofr.step is a root."""
    engine = make_engine(model)
    hold, pinned = threading.Event(), threading.Event()
    real = batch_ops.decode_block_paged

    def hanging(*a, **kw):
        if not pinned.is_set():
            pinned.set()
            hold.wait(60)
        return real(*a, **kw)

    engine.start()
    try:
        engine.submit("warm", max_new_tokens=6).result(timeout=120)
        monkeypatch.setattr(batch_ops, "decode_block_paged", hanging)
        doomed = engine.submit("held in flight", max_new_tokens=30)
        assert pinned.wait(60)
        old = engine._thread
        assert engine.warm_restart(join_timeout=0.2) is True
        with pytest.raises(Exception):
            doomed.result(timeout=30)
        assert engine.submit("fresh", max_new_tokens=3).result(timeout=120).completion_tokens >= 1
        assert settles_in(engine, "wait")  # the replacement idles in its own wait
        before = dict(engine._phase_s), dict(engine._phase_cpu_s)
        hold.set()
        old.join(timeout=60)
        assert not old.is_alive()
        after = dict(engine._phase_s), dict(engine._phase_cpu_s)
        # the old thread left dispatch.launch, dispatch and step behind it: its unwind charged
        # none of them, in neither account
        for phase in ("dispatch.launch", "dispatch", "step"):
            assert after[0][phase] == before[0][phase] and after[1][phase] == before[1][phase], phase
        assert settles_in(engine, "wait")
        assert engine.submit("again", max_new_tokens=3).result(timeout=120).completion_tokens >= 1
    finally:
        hold.set()
        engine.stop()
    assert engine._phase_state[0] is None
