"""The readers of the step loop's own account (``benchmarks/harness/loop_account.py``):
the quiet stretches from hand-made polls, exact arithmetic on hand-made
``/requestz`` timelines and dispatch spans, None — never 0 — where the program
has no account (the parent of PR 37) or the stretch holds too little, and a
traced rehearsal on the CPU at tiny widths in which the engine's real
timelines reach the readers."""

import time

import pytest

import cellbench_tiny
from benchmarks.harness import loop_account as la, runner, trace_reduce as tr
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.runner import RunData

REPO = cellbench_tiny.REPO
NEW = [m["name"] for m in Manifest(REPO).data["per_layer"]
       if "_untraced." in m["name"] or m["name"].startswith("engine.launch_idle_share.")]
WALL_MINUS_MONO = 1_000_000.0
PHASES = ("step", "dispatch", "dispatch.rows", "dispatch.launch", "commit", "commit.rows", "sync", "prefill_sync", "wait")


def snap(ms, blocks, idle, queued, wall, cpu):
    """A ``loop`` snapshot as ``/requestz`` renders it: ``wall`` and
    ``cpu`` give every phase's milliseconds in the order of PHASES."""
    return {"ms": ms, "blocks": blocks, "launched_idle": idle, "launched_queued": queued,
            "phase_ms": dict(zip(PHASES, wall)), "cpu_ms": dict(zip(PHASES, cpu))}


def timeline(born_mono, at_admit=None, at_end=None):
    loop = {k: v for k, v in (("at_admit", at_admit), ("at_end", at_end)) if v is not None}
    return {"created_unix": born_mono + WALL_MINUS_MONO, "phases_ms": {}, **({"loop": loop} if loop else {})}


def run_over(timelines, polls=(9.0, 10.0, 13.5, 14.5), window=(0.0, 51.0), traced=(10.2, 13.2), events=()):
    return RunData({"name": "x"}, {}, {"engine": {"max_slots": 4}}, [], window, traced, list(events), 0,
                   dict(enumerate(timelines)), [{"t": t} for t in polls], "TPU v5 lite", WALL_MINUS_MONO)


# the loop's account at five instants: before the session (2 s and 8 s: 60
# blocks of 10 ms of host work, 5 of it on the CPU), inside the traced
# sub-window (12 s), in the settling second (14 s), and at 20 s and 40 s of the
# stretch behind the session. Per block between 20 s and 40 s: step 1, dispatch
# 2 + 3 + 4, commit 1 + 5 of wall; 10 ms of it on the CPU
AT_2 = snap(100.0, 10, 1, 8, (10, 20, 30, 40, 10, 50, 300, 5, 1), (5, 10, 15, 20, 5, 25, 1, 0, 0))
AT_8 = snap(6100.0, 70, 7, 62, (20, 80, 130, 190, 30, 310, 800, 10, 2), (10, 40, 65, 95, 15, 155, 2, 0, 0))
AT_12 = snap(2000.0, 100, 90, 9, (100, 200, 300, 400, 100, 500, 3000, 50, 10), (100, 200, 300, 400, 100, 500, 10, 1, 1))
AT_20 = snap(500.0, 200, 95, 104, (200, 400, 600, 800, 200, 1000, 9000, 100, 20), (150, 300, 500, 700, 150, 800, 20, 2, 2))
AT_30 = snap(4000.0, 300, 99, 200, (300, 600, 900, 1200, 300, 1500, 14000, 150, 30), (200, 400, 700, 1000, 200, 1100, 30, 3, 3))
AT_40 = snap(15000.0, 400, 105, 294, (400, 800, 1200, 1600, 400, 2000, 19000, 200, 40), (250, 500, 900, 1300, 250, 1400, 40, 4, 4))
BEHIND = [timeline(10.0, at_admit=AT_12, at_end=AT_30),         # 12 s (traced) and 10 + 4 = 14 s (the settling second)
          timeline(19.5, at_admit=AT_20),                        # 19.5 + 0.5 = 20 s, still decoding at the window's end
          timeline(25.0, at_admit=None, at_end=AT_40)]           # 25 + 15 = 40 s
BEFORE = [timeline(1.9, at_admit=AT_2, at_end=AT_8)]             # 2 s and 1.9 + 6.1 = 8 s
TIMELINES = BEFORE + BEHIND


def test_the_quiet_stretches_are_the_window_less_the_session_and_its_settling_second():
    assert la.quiet_stretches(run_over([])) == [(0.0, 10.2), (14.5, 51.0)]
    # the poller sat in stop_trace for six seconds: the second stretch follows the session, not the cell's 3 s
    assert la.quiet_stretches(run_over([], polls=(9.0, 10.0, 19.7, 20.7))) == [(0.0, 10.2), (20.7, 51.0)]
    assert la.quiet_stretches(run_over([], polls=(9.0, 10.0))) == [(0.0, 10.2)]   # no poll after the trace
    assert la.quiet_stretches(run_over([], polls=(9.0, 50.5))) == [(0.0, 10.2)]   # nothing is left behind the session
    assert la.quiet_stretches(run_over([], traced=None)) == []                    # an untraced run
    assert la.quiet_stretches(run_over([], traced=(0.0, 3.0))) == [(10.0, 51.0)]  # a session from the window's start


def test_the_four_numbers_from_hand_made_timelines(capsys):
    run = run_over(BEHIND)
    assert [t for t, _ in la.snapshots(run)] == pytest.approx([12.0, 14.0, 20.0, 40.0])
    found = la.between(run)
    assert found["blocks"] == 200 and found["seconds"] == pytest.approx(20.0)
    assert (found["launched_idle"], found["launched_queued"]) == (10, 190)
    # step 200 + dispatch 400 + 600 + 800 + commit 200 + 1000 over 200 blocks; the waits stay out
    assert la.host_ms_per_block_untraced(run) == pytest.approx(3200 / 200)
    assert la.launch_idle_share_untraced_pct(run) == pytest.approx(100 * 10 / 200)
    # the same phases on the thread's clock: 100 + 200 + 400 + 600 + 100 + 600 = 2000 of the 3200
    assert la.host_offcpu_share_untraced_pct(run) == pytest.approx(100 * (1 - 2000 / 3200))
    err = capsys.readouterr().err
    assert err.count("loop account, profiler off: 200 blocks in 20.000s") == 1  # once a run, whoever asks
    assert "commit.rows 5.000 (3.000)" in err and "sync 50.000 (0.100)" in err


def test_the_stretch_before_the_session_counts_beside_the_one_behind_it():
    """A cell whose trace starts late (30 s of 51, and 11-17 s of
    ``stop_trace``) has its reading before the session: each stretch gives
    the difference of its own two snapshots, and the sums are taken."""
    both, before = la.between(run_over(TIMELINES)), la.between(run_over(BEFORE))
    assert (before["blocks"], before["seconds"]) == (60, pytest.approx(6.0))
    assert both["blocks"] == 260 and both["seconds"] == pytest.approx(26.0)
    assert (both["launched_idle"], both["launched_queued"]) == (10 + 6, 190 + 54)
    assert la.host_ms_per_block_untraced(run_over(BEFORE)) == pytest.approx(600 / 60)
    assert la.host_ms_per_block_untraced(run_over(TIMELINES)) == pytest.approx((3200 + 600) / 260)
    assert la.host_offcpu_share_untraced_pct(run_over(TIMELINES)) == pytest.approx(100 * (1 - (2000 + 300) / 3800))
    # no poll behind the session: the stretch before it still reads
    assert la.between(run_over(TIMELINES, polls=(9.0, 10.0)))["blocks"] == 60


def test_a_snapshot_inside_the_traced_sub_window_or_its_settling_second_is_not_used():
    # only the two snapshots of the session and one behind it: the one alone is no pair
    early = [timeline(10.0, at_admit=AT_12, at_end=AT_30), timeline(25.0, at_end=AT_40)]
    assert la.between(run_over(early)) is None
    # with the stretch opened to the whole window they would be used
    whole = run_over(early, polls=(9.0, 10.25), traced=(10.2, 10.21))
    assert la.between(whole)["blocks"] == 300


@pytest.mark.parametrize("case, run", [
    ("the parent's timelines carry no loop", run_over([timeline(19.0), timeline(30.0)])),
    ("under twenty blocks between the snapshots",
     run_over([timeline(19.5, at_admit=AT_20), timeline(25.0, at_end=dict(AT_40, blocks=219))])),
    ("no poll after the trace and nothing before it", run_over(BEHIND, polls=(9.0, 10.0))),
    ("an untraced run", run_over(TIMELINES, traced=None)),
    ("the serving process's clocks are unknown",
     RunData({"name": "x"}, {}, {}, [], (0.0, 51.0), (10.2, 13.2), [], 0, dict(enumerate(TIMELINES)),
             [{"t": 13.5}], "TPU v5 lite", None)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_nothing_to_read_is_none_never_zero(case, run):
    assert la.between(run) is None
    assert la.host_ms_per_block_untraced(run) is None and la.launch_idle_share_untraced_pct(run) is None
    assert la.host_offcpu_share_untraced_pct(run) is None


def span(name, start_ms, dur_ms):
    return tr.Event("/host:CPU", "python3#4", name, int(start_ms * 1e6), int(dur_ms * 1e6))


def test_the_traced_share_counts_the_dispatch_spans_that_say_so():
    blocks = [span(f"gofr.step.dispatch#blk={n},rows=2,steps=4,dev_idle={idle},cpu_us=900#", 10 + 10 * n, 2)
              for n, idle in enumerate((2, 1, 0, 0, 1, 0))]
    events = [span("gofr.step#iter=1,mono_ns=1#", 5, 80), span("gofr.step.dispatch#cpu_us=3#", 6, 1)] + blocks
    run = run_over([], traced=(0.0, 0.1), events=events)
    assert la.launch_idle_share_pct(run) == pytest.approx(100 * 2 / 5)  # the block with none in flight counts in neither
    # the parent's spans say nothing of the device: None, never 0
    parent = [e._replace(name=e.name.replace("dev_idle=1,", "").replace("dev_idle=0,", "").replace("dev_idle=2,", ""))
              for e in events]
    assert la.launch_idle_share_pct(run_over([], traced=(0.0, 0.1), events=parent)) is None
    assert la.launch_idle_share_pct(run_over([], traced=(0.0, 0.1), events=[])) is None


def test_every_new_reader_is_a_file_that_returns_none_on_an_empty_run():
    assert len(NEW) == 7
    manifest = Manifest(REPO)
    for name in NEW:
        assert manifest.reader(name)(run_over([timeline(19.0)])) is None


def test_a_traced_rehearsal_on_the_cpu_reads_the_engines_own_timelines(tmp_path, monkeypatch):
    """The real App at tiny widths under ``--trace 1``: every finished
    request's ``/requestz`` carries ``loop``, the quiet stretches are found
    from the run's own polls, and each new metric is a number in its range
    or left out of the line — never an exception. (No device plane and no
    span file under this root on the CPU: the traced twin reads None.)"""
    root = cellbench_tiny.make_root(str(tmp_path / "tinybench"))
    seen = {}
    load_trace = runner._load_trace

    def keeping(run, *a, **kw):
        seen["run"] = run
        return load_trace(run, *a, **kw)

    monkeypatch.setattr(runner, "_load_trace", keeping)
    code, result = runner.run_cell(root, "tiny.open", 2**31 + 37, 6.0, True, time.monotonic(), platform="cpu")
    assert code == 0 and result["correct"] is True
    run = seen["run"]
    finished = [z for z in run.requestz.values() if z.get("terminal")]
    assert finished and all({"at_admit", "at_end", "during"} <= set(z["loop"]) for z in finished)
    before, behind = la.quiet_stretches(run)
    assert before == (run.window[0], run.traced[0])
    assert run.traced[1] + la.SETTLE_S < behind[0] < behind[1] == run.window[1]
    assert all(run.window[0] - 1.0 < t < run.window[1] + 60.0 for t, _ in la.snapshots(run))
    found = la.between(run)
    reported = {k: v["value"] for k, v in result["metrics"].items() if k in NEW}
    listed = {m["name"] for m in Manifest(root).metrics_for("per_layer", "tiny.open")} & set(NEW)
    assert set(reported) <= listed and len(listed) == 7
    if found is None:
        assert not reported
    else:
        assert found["blocks"] >= la.MIN_BLOCKS
        assert 0 < reported["engine.host_ms_per_block_untraced.chat"] < 1e3
        assert 0 <= reported["engine.host_offcpu_share_untraced.batch"] <= 100
        assert all(0 <= v <= 100 for k, v in reported.items() if "share" in k)
