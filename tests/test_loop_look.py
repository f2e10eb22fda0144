"""``tools/loop_look.py`` on hand-made events: the traced table (wall from
self time, CPU from ``cpu_us`` less the children's) and the check of
``dev_idle`` against the gaps on ``XLA Ops``; and the wrapper that starts a
profiler session without the Python tracer."""

import importlib.util
import io
import os

import jax
import pytest

from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.runner import RunData

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEV, HOST, MS = "/device:TPU:0", "/host:CPU", 1_000_000


@pytest.fixture(scope="module")
def loop_look():
    spec = importlib.util.spec_from_file_location("loop_look", os.path.join(REPO, "tools", "loop_look.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def dev(line, name, start_ms, dur_ms):
    return tr.Event(DEV, line, name, int(start_ms * MS), int(dur_ms * MS))


def span(name, start_ms, dur_ms):
    return tr.Event(HOST, "python3#4", name, int(start_ms * MS), int(dur_ms * MS))


def block(n, at, dev_idle):
    """A dispatch of 6 ms from ``at``: rows 2 ms (1.5 on the CPU), launch 3 ms
    (1 on the CPU: the rest a call blocked in the runtime), 1 ms of its own."""
    return [span(f"gofr.step.dispatch#blk={n},rows=2,steps=4,dev_idle={dev_idle},cpu_us=3500#", at, 6),
            span("gofr.step.dispatch.rows#cpu_us=1500#", at, 2),
            span("gofr.step.dispatch.launch#cpu_us=1000#", at + 2.5, 3)]


# three iterations of 20 ms; block 1 runs 0-18 on the device, block 2 is
# launched behind it (asked at 12: still running) and starts at once, block 3
# finds block 2 finished (asked at 42, block 2 ended at 38) and lands at 45
EVENTS = [
    span("gofr.step#iter=1,mono_ns=1,cpu_us=9000#", 0, 20), *block(2, 10, 0),
    span("gofr.step#iter=2,mono_ns=2,cpu_us=9000#", 20, 20), span("gofr.step.dispatch#cpu_us=10#", 21, 1),
    span("gofr.step#iter=3,mono_ns=3,cpu_us=9000#", 40, 20), *block(3, 40, 1),
    dev(tr.MODULE_LINE, "jit_decode_block_paged(1)", 0, 18), dev(tr.OPS_LINE, "%while.1 = while()", 0, 18),
    dev(tr.MODULE_LINE, "jit_decode_block_paged(1)", 18, 20), dev(tr.OPS_LINE, "%while.1 = while()", 18.01, 19.99),
    dev(tr.MODULE_LINE, "jit_decode_block_paged(1)", 45, 14), dev(tr.OPS_LINE, "%while.1 = while()", 45, 14),
]


def run_over(events):
    return RunData({"name": "x"}, {}, {"engine": {"max_slots": 4}}, [], (0.0, 1.0), (0.0, 0.06), events, 0, {}, [], "")


def test_the_traced_table_takes_children_out_of_wall_and_cpu(loop_look):
    out = io.StringIO()
    loop_look.traced_table(run_over(EVENTS), out)
    said = out.getvalue()
    assert "loop account, traced: 2 blocks in 0.060s of whole iterations" in said
    # per block: dispatch 2 x 1 ms of its own wall (and 1 of the empty one), 2 x (3.5 - 1.5 - 1.0) of CPU
    assert "dispatch.rows 2.000 (1.500)" in said and "dispatch.launch 3.000 (1.000)" in said
    assert "dispatch 1.500 (1.005)" in said
    assert "step 23.500 (9.995)" in said  # 60 - 13 ms of spans; 27 - 7.01 ms of CPU
    assert "host ms a block 30.000 (cpu 13.500)" in said


def test_dev_idle_is_held_to_the_gap_before_the_next_program(loop_look):
    out = io.StringIO()
    loop_look.check_dev_idle(run_over(EVENTS), out)
    said = out.getvalue()
    assert "dev_idle=1: 1 blocks, 1 (100.0%) with a gap" in said and "median 7.000 ms" in said
    assert "dev_idle=0: 1 blocks, 1 (100.0%) without a gap" in said and "median 0.010 ms" in said
    # a span that says the device was busy where it had run dry is counted against the counter
    lying = [e._replace(name=e.name.replace("dev_idle=1", "dev_idle=0")) for e in EVENTS]
    out = io.StringIO()
    loop_look.check_dev_idle(run_over(lying), out)
    assert "dev_idle=0: 2 blocks, 1 (50.0%) without a gap" in out.getvalue()
    out = io.StringIO()
    loop_look.check_dev_idle(run_over([e for e in EVENTS if e.plane != DEV]), out)
    assert "no device plane" in out.getvalue()


def test_the_wrapper_starts_the_session_without_the_python_tracer(loop_look, monkeypatch, tmp_path):
    seen = {}
    monkeypatch.setattr(jax.profiler, "start_trace", lambda log_dir, **kw: seen.update(dir=log_dir, **kw))
    loop_look.start_trace_with(0)
    jax.profiler.start_trace(str(tmp_path))  # as benchmarks/harness/runner.py calls it
    assert seen["dir"] == str(tmp_path) and seen["profiler_options"].python_tracer_level == 0
    assert seen["profiler_options"].host_tracer_level == jax.profiler.ProfileOptions().host_tracer_level
