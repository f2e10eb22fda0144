"""The readers of the engine's step spans (``benchmarks/harness/host_spans.py``):
exact arithmetic on hand-made events, the same functions on a second of
``mistral7b.chat`` cut from a real v5e trace with the spans in it (my chip
run, PR 26), and None, never 0, on the older recording, whose program had no
such span."""

import os

import pytest

from benchmarks.harness import host_spans as hs, trace_reduce as tr
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.runner import RunData

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TESTDATA = os.path.join(REPO, "benchmarks", "testdata")
DEV, HOST, MS = "/device:TPU:0", "/host:CPU", 1_000_000
NEW_READERS = [m["name"] for m in Manifest(REPO).data["per_layer"] if m["source"] == "program_span"]


def dev(line, name, start_ms, dur_ms):
    return tr.Event(DEV, line, name, int(start_ms * MS), int(dur_ms * MS))


def span(name, start_ms, dur_ms, line="python3#4"):
    return tr.Event(HOST, line, name, int(start_ms * MS), int(dur_ms * MS))


def run_over(events, window_ms, slots=4):
    """A RunData whose traced sub-window is ``window_ms`` on the trace's
    own clock (offset 0)."""
    a, b = window_ms
    return RunData({"name": "x"}, {}, {"engine": {"max_slots": slots}}, [], (0.0, 1.0), (a / 1e3, b / 1e3),
                   events, 0, {}, [], "TPU v5 lite")


# one iteration that admits a request (the device drains block 1 and idles
# while the host samples), dispatches block 2, waits for block 1 and commits
# it; then an idle iteration
HAND = [
    dev(tr.OPS_LINE, "%while.1 = (s32[]) while(...)", 0, 30),      # block 1
    dev(tr.OPS_LINE, "%fusion.9 = f32[8] fusion(%y)", 34, 4),      # the prefill
    dev(tr.OPS_LINE, "%while.1 = (s32[]) while(...)", 50, 48),     # block 2
    span("gofr.step#iter=5,mono_ns=123#", 10, 50),
    span("gofr.step.plan#decode_rows=1,cursors=0,queue=1#", 11, 1),
    span("gofr.step.admit#admitted=1#", 12, 30),
    span("gofr.step.prefill#rid=9,bucket=32,tokens=20,route=bucketed#", 13, 28),
    span("gofr.step.prefill_sync#rid=9#", 20, 20),
    span("gofr.step.dispatch#blk=2,kind=decode,rows=2,steps=4,kv_tokens=70,chunk_rows=0,chunk_tokens=0,cold=0#", 43, 8),
    span("gofr.step.fold#n=1#", 44, 2),
    span("gofr.step.sync#blk=1#", 52, 1),
    span("gofr.step.commit#blk=1,tokens=4,retired=0#", 54, 4),
    span("gofr.step#iter=6,mono_ns=456#", 61, 30),
    span("gofr.step.dispatch#", 62, 1),                            # found no row: no block
    span("gofr.step.wait", 64, 27),
    span("bench.mark:0", 0, 0, line="python3#1"),
]


def test_keywords_come_back_from_the_name():
    s = hs.parse(HAND[8])
    assert (s.phase, s.thread) == ("dispatch", "/host:CPU/python3#4")
    assert s.kw == {"blk": 2, "kind": "decode", "rows": 2, "steps": 4, "kv_tokens": 70,
                    "chunk_rows": 0, "chunk_tokens": 0, "cold": 0}
    assert hs.parse(HAND[3]).phase == "step" and hs.parse(HAND[3]).kw == {"iter": 5, "mono_ns": 123}
    assert hs.parse(HAND[14]).kw == {} and hs.parse(HAND[13]).kw == {}


def test_self_time_goes_to_the_innermost_span_and_adds_up():
    run = run_over(HAND, (0, 100))
    by_phase = hs.self_seconds_by_phase(run)
    assert by_phase == pytest.approx({
        "step": 0.001 + 0.001 + 0.001 + 0.001 + 0.002 + 0.001 + 0.001, "plan": 0.001, "admit": 0.001 + 0.001,
        "prefill": 0.007 + 0.001, "prefill_sync": 0.020, "dispatch": 0.001 + 0.005 + 0.001, "fold": 0.002,
        "sync": 0.001, "commit": 0.004, "wait": 0.027})
    assert sum(by_phase.values()) == pytest.approx(0.050 + 0.030)  # the two iterations, whole
    # cut by the window's edges like everything else
    assert sum(hs.self_seconds_by_phase(run_over(HAND, (30, 70))).values()) == pytest.approx(0.030 + 0.009)


def test_blocks_are_the_dispatch_spans_that_dispatched_one():
    run = run_over(HAND, (0, 100))
    assert [s.kw["blk"] for s in hs.blocks(run)] == [2]
    assert hs.blocks(run_over(HAND, (44, 100))) == []  # it started before the window
    assert hs.slot_use_pct(run) == pytest.approx(100.0 * 2 * 4 / (4 * 4))
    # every span's self time but the waits', over the one block
    assert hs.host_ms_per_block(run) == pytest.approx(80.0 - 20.0 - 1.0 - 27.0)
    # what one admission holds the loop thread for: the prefill span, its first-token read inside it
    assert hs.admit_blocked_ms(run) == pytest.approx(28.0)
    assert hs.admit_blocked_ms(run_over(HAND, (25, 100))) == pytest.approx(16.0)  # cut like everything else
    assert hs.admit_blocked_ms(run_over(HAND, (45, 100))) is None


def test_the_spans_speak_for_whole_iterations_only():
    """The iteration under way at an edge of a trace leaves no gofr.step:
    the readers look from the first one's start to the last one's end."""
    assert hs.whole_iterations(run_over(HAND, (0, 100))) == (10 * MS, 91 * MS)
    assert hs.whole_iterations(run_over(HAND, (30, 70))) == (30 * MS, 70 * MS)
    # the first iteration's gofr.step was cut by the trace's start: its inner spans are orphans
    orphans = [e for e in HAND if not e.name.startswith("gofr.step#iter=5")]
    assert hs.whole_iterations(run_over(orphans, (0, 100))) == (61 * MS, 91 * MS)
    assert hs.blocks(run_over(orphans, (0, 100))) == []
    assert set(hs.self_seconds_by_phase(run_over(orphans, (0, 100)))) == {"step", "dispatch", "wait"}


def test_idle_is_charged_to_the_span_open_over_it_and_split_where_spans_change():
    run = run_over(HAND, (0, 100))
    idle = hs.idle_by_span(run)
    # gaps inside the whole iterations (10-91): 30-34 and 38-50
    assert idle == pytest.approx({"prefill_sync": 0.004 + 0.002, "prefill": 0.001, "admit": 0.001,
                                  "step": 0.001, "dispatch": 0.001 + 0.004, "fold": 0.002})
    assert sum(idle.values()) == pytest.approx(0.081 - tr.busy_seconds(HAND, 10 * MS, 91 * MS))
    # a share of the whole sub-window, so that it cannot pass the device's idle share
    assert hs.idle_host_bound_pct(run) == pytest.approx(100.0 * 0.016 / 0.100)
    assert hs.idle_host_bound_pct(run) <= 100.0 * (1 - tr.busy_seconds(HAND, 0, 100 * MS) / 0.100)
    # idle under the engine's wait is the device waiting for work, not for the host;
    # between two iterations no span is open
    waiting = run_over([e for e in HAND if not e.name.startswith("%while.1") or e.start_ns < 40 * MS], (50, 100))
    assert hs.idle_by_span(waiting) == pytest.approx({
        "dispatch": 0.001 + 0.001, "step": 0.001 + 0.001 + 0.002 + 0.001 + 0.001, "sync": 0.001, "commit": 0.004,
        hs.NO_SPAN: 0.001, "wait": 0.027})
    assert hs.idle_host_bound_pct(waiting) == pytest.approx(100.0 * 0.013 / 0.050)


def test_no_span_no_number():
    bare = run_over([e for e in HAND if not e.name.startswith(hs.PREFIX)], (0, 100))
    for fn in (hs.spans, hs.self_seconds_by_phase, hs.blocks, hs.idle_by_span, hs.host_ms_per_block,
               hs.admit_blocked_ms, hs.slot_use_pct, hs.idle_host_bound_pct):
        assert fn(bare) is None, fn.__name__
    untraced = run_over(HAND, (0, 100))
    untraced.traced = None
    assert hs.host_ms_per_block(untraced) is None and hs.idle_host_bound_pct(untraced) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_on_the_recording_without_spans(name):
    """``trace_v5e_chat_600ms.json.gz`` was cut from PR 25's program: what
    the parent commit gives the driver's traced runs."""
    events = tr.load_events(os.path.join(TESTDATA, "trace_v5e_chat_600ms.json.gz"))
    m0 = min(e.start_ns for e in events if e.name.startswith("bench.mark:"))
    run = run_over(events, ((m0 + 2400 * MS) / MS, (m0 + 3000 * MS) / MS), slots=32)
    assert Manifest(REPO).reader(name)(run) is None


# ------------------------------------------------------------- the recording
# 1000 ms of mistral7b.chat on the v5e from 1900 ms after the first
# bench.mark (my chip run, PR 26; cut by benchmarks/tools/span_look.py):
# the end of iteration 91, iteration 92 with one bucketed prefill (request
# 26, bucket 128) and the dispatch of block 55, iteration 93 with block 56,
# and the first inner spans of iteration 94, whose gofr.step the trace's end cut
@pytest.fixture(scope="module")
def recorded():
    events = tr.load_events(os.path.join(TESTDATA, "trace_v5e_chat_spans.json.gz"))
    m0 = min(e.start_ns for e in events if e.name.startswith("bench.mark:"))
    return run_over(events, ((m0 + 1900 * MS) / MS, (m0 + 2900 * MS) / MS), slots=32), m0


def test_recording_spans_as_the_v5e_trace_shows_them(recorded):
    run, m0 = recorded
    seen = hs._all(run)
    assert {s.thread for s in seen} == {"/host:CPU/python#6"}  # one line: the engine's loop thread
    assert [s.kw["iter"] for s in seen if s.phase == "step"] == [91, 92, 93]
    prefill, = [s for s in seen if s.phase == "prefill"]
    assert prefill.kw == {"rid": 26, "bucket": 128, "tokens": 128, "route": "bucketed"}
    assert hs.whole_iterations(run) == (m0 + 1900 * MS, m0 + 2820275346)
    # the keywords join a block's three spans
    by_phase = {p: [s.kw["blk"] for s in seen if s.phase == p and "blk" in s.kw] for p in ("dispatch", "sync", "commit")}
    assert by_phase == {"dispatch": [55, 56], "sync": [53, 54, 55], "commit": [53, 54, 55]}


def test_recording_self_time_adds_up_to_the_covered_time(recorded):
    run, _ = recorded
    by_phase = hs.self_seconds_by_phase(run)
    whole = hs.whole_iterations(run)
    covered = tr.union_ns((s.start_ns, s.end_ns) for s in hs.spans(run)) / 1e9
    assert sum(by_phase.values()) == pytest.approx(covered, rel=1e-12) == pytest.approx(0.920036056, rel=1e-9)
    assert covered / ((whole[1] - whole[0]) / 1e9) > 0.9995  # the loop is outside a gofr.step for microseconds
    # the prefill's wait for the block in flight is inside `prefill`, not in its first-token read
    assert by_phase["prefill"] == pytest.approx(0.450101237, rel=1e-9)
    assert by_phase["prefill_sync"] == pytest.approx(0.00153872, rel=1e-9)
    assert by_phase["sync"] == pytest.approx(0.450104868, rel=1e-9)


def test_recording_idle_by_span_is_the_window_less_busy(recorded):
    run, _ = recorded
    idle, whole = hs.idle_by_span(run), hs.whole_iterations(run)
    assert sum(idle.values()) == pytest.approx((whole[1] - whole[0]) / 1e9 - tr.busy_seconds(run.events, *whole), rel=1e-9)
    assert sum(idle.values()) == pytest.approx(0.015256492, rel=1e-9)
    assert hs.NO_SPAN not in idle and max(idle, key=idle.get) == "prefill"
    assert hs.idle_host_bound_pct(run) == pytest.approx(1.5256492, rel=1e-9)
    sub = run.traced_ns()
    assert hs.idle_host_bound_pct(run) <= 100.0 * (1 - tr.busy_seconds(run.events, *sub) / ((sub[1] - sub[0]) / 1e9))


def test_recording_slot_use_against_a_hand_count(recorded):
    run, _ = recorded
    # blocks 55 and 56 were dispatched in the whole iterations, six rows of 32 slots each, four steps
    assert [(s.kw["blk"], s.kw["rows"], s.kw["steps"], s.kw["kv_tokens"]) for s in hs.blocks(run)] == \
        [(55, 6, 4, 826), (56, 6, 4, 846)]
    assert hs.slot_use_pct(run) == pytest.approx(100.0 * (6 * 4 + 6 * 4) / (32 * 4 + 32 * 4))
    assert hs.admit_blocked_ms(run) == pytest.approx(0.450101237e3 + 1.53872, rel=1e-9)  # prefill's self time + its read
    assert hs.host_ms_per_block(run) == pytest.approx(1e3 * (0.920036056 - 0.450104868 - 0.00153872) / 2, rel=1e-9)
