"""A builder's tool, not the command: run one cell with --trace 1, print the
engine's gofr.step spans by phase (count, seconds, the keywords the trace
kept) and what ISSUE 26 asks of a trace, and save the device events plus the
engine's host events as a recording. A second form cuts a recording down to
a piece for the tests.

    python3 benchmarks/tools/span_look.py <workload> <seed> <seconds> [out.json.gz]
    python3 benchmarks/tools/span_look.py cut <in.json.gz> <out.json.gz> <from_ms> <ms>

The cut is counted from the first ``bench.mark``; names are clipped to 96
characters to keep the file small (the markers stay whole, and so do the
engine's spans, whose names carry their keywords).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks.harness import host_spans, runner, trace_reduce

    if argv[0] == "cut":
        events = trace_reduce.load_events(argv[1])
        marks = [e for e in events if e.name.startswith("bench.mark:")]
        a = min(e.start_ns for e in marks) + int(float(argv[3]) * 1e6)
        cut = trace_reduce.clip((e for e in events if e not in marks), a, a + int(float(argv[4]) * 1e6))
        keep = marks + [e if e.name.startswith(host_spans.PREFIX) else e._replace(name=e.name[:96]) for e in cut]
        trace_reduce.save_events(keep, argv[2])
        print(f"{len(keep)} of {len(events)} events saved to {argv[2]}", file=sys.stderr)
        return 0
    workload, seed, seconds = argv[0], int(argv[1]), float(argv[2])
    out = argv[3] if len(argv) > 3 else os.path.join(ROOT, "chiprun_out", f"spans.{workload}.json.gz")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # the run's xplane is gone when run_cell returns: keep what the
    # readers load from it
    kept: list[trace_reduce.Event] = []
    load = host_spans.load_host_events

    def keeping(path: str) -> list[trace_reduce.Event]:
        found = load(path)
        kept.extend(found)
        return found

    host_spans.load_host_events = keeping
    code, result = runner.run_cell(ROOT, workload, seed, seconds, True, T_START, keep_events=out)
    if result is None:
        return code
    events = trace_reduce.load_events(out) + kept
    trace_reduce.save_events(events, out)
    spans = [host_spans.parse(e) for e in kept]
    print(f"== {len(kept)} gofr.step events on {sorted({s.thread for s in spans})}; "
          f"{len(events)} events saved to {out}", file=sys.stderr)
    by_phase: dict[str, list] = collections.defaultdict(list)
    for s in spans:
        by_phase[s.phase].append(s)
    for phase, found in sorted(by_phase.items(), key=lambda kv: -sum(s.dur_ns for s in kv[1])):
        print(f"   {phase:13s} x{len(found):5d} {sum(s.dur_ns for s in found) / 1e9:9.4f}s  "
              f"e.g. {found[len(found) // 2].kw}", file=sys.stderr)
    report(look_again(events, workload), sys.stderr)
    print(json.dumps(result))
    return code


def look_again(events: list, workload: str):
    """A RunData over saved events: the sub-window is between the two
    ``bench.mark`` stamps, whose names carry the host's monotonic clock."""
    from benchmarks.harness import runner
    from benchmarks.harness.manifest import Manifest

    marks = sorted((e for e in events if e.name.startswith("bench.mark:")), key=lambda e: e.start_ns)
    mono = [int(e.name.split(":", 1)[1]) for e in marks]
    offset = sum(e.start_ns - m for e, m in zip(marks, mono)) // len(marks)
    return runner.RunData({"name": workload}, {}, Manifest(ROOT).cell(workload), [], (0.0, 0.0),
                          (mono[0] / 1e9, mono[-1] / 1e9), events, offset, {}, [], "")


def report(run, fh) -> None:
    """What ISSUE 26 asks of a trace: how much of the engine thread's time
    and of the device's idle time the spans name, and whether every
    dispatched block has its sync and its commit."""
    from benchmarks.harness import host_spans

    sub, whole = run.traced_ns(), host_spans.whole_iterations(run)
    by_phase, idle = host_spans.self_seconds_by_phase(run), host_spans.idle_by_span(run)
    if by_phase is None or idle is None:
        print("== no gofr.step span in the traced sub-window, or no device plane", file=fh)
        return
    seconds, inside = (sub[1] - sub[0]) / 1e9, (whole[1] - whole[0]) / 1e9
    print(f"== sub-window {seconds:.4f}s, its whole iterations {inside:.4f}s: engine thread inside a span "
          f"{sum(by_phase.values()):.4f}s ({100 * sum(by_phase.values()) / inside:.2f}% of the whole iterations, "
          f"{100 * sum(by_phase.values()) / seconds:.2f}% of the sub-window)", file=fh)
    for phase, s in sorted(by_phase.items(), key=lambda kv: -kv[1]):
        print(f"   self {phase:13s} {s:9.4f}s", file=fh)
    total = sum(idle.values())
    named = total - idle.get(host_spans.NO_SPAN, 0.0)
    print(f"== device idle in the whole iterations {total:.4f}s, charged to a span {named:.4f}s "
          f"({100 * named / total if total else 100.0:.2f}%)", file=fh)
    for phase, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"   idle {phase:13s} {s:9.4f}s", file=fh)
    blks = {p: {s.kw.get("blk") for s in host_spans.spans(run) if s.phase == p} for p in ("dispatch", "sync", "commit")}
    dispatched = sorted(s.kw["blk"] for s in host_spans.blocks(run))
    missing = [b for b in dispatched[:-2] if b not in blks["sync"] or b not in blks["commit"]]
    print(f"== blocks dispatched in the sub-window: {dispatched}; without a sync or a commit "
          f"(the last two may still be in flight): {missing}", file=fh)
    for name, fn in (("host_ms_per_block", host_spans.host_ms_per_block), ("admit_blocked_ms", host_spans.admit_blocked_ms),
                     ("slot_use_pct", host_spans.slot_use_pct), ("idle_host_bound_pct", host_spans.idle_host_bound_pct)):
        print(f"   {name} = {fn(run)}", file=fh)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
