"""A builder's tool, not the benchmark: one traced run of a cell, with the
profiler's Python tracer on (the harness's way: ``start_trace``'s defaults)
or off, and what the engine's own account says beside what the trace says.

    python3 tools/loop_look.py <workload> <seed> <seconds> [--python-tracer 0|1] [--keep out.json.gz]
    python3 tools/loop_look.py check <recording.json.gz> <workload>

``--python-tracer 0`` wraps ``jax.profiler.start_trace`` for this process so
that the session starts under ``ProfileOptions(python_tracer_level=0)``: the
harness's files are not touched, and what the Python tracer alone costs the
traced readings is this run against one without the flag. The run itself
is ``benchmarks/tools/span_look.py``'s (the command's run with ``--trace
1``, the engine's spans kept): its result line is this tool's standard
output. After it, the loop's readings with the profiler off
(``benchmarks/harness/loop_account.py`` over the run's own timelines and
polls: the table of the quiet stretches and the four numbers), whether
``BENCHMARK.json`` lists them for the cell or not; and from the recording:

* the traced sub-window's **ms a block by phase, wall (cpu)** — self time of
  the ``gofr.step*`` spans and their ``cpu_us`` less their children's —, the
  twin of the table ``benchmarks/harness/loop_account.py`` prints for the
  untraced part of the same run;
* the check of ``dev_idle`` against the device: for every dispatch span
  that says 1 or 0, the gap on ``XLA Ops`` before the first program that
  starts on the device after the engine asked (the end of the span's
  ``dispatch.rows``) — idle launches should show one of more than 0.5 ms,
  queued ones none.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import bisect  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
GAP_MS = 0.5


def start_trace_with(python_tracer_level: int) -> None:
    import jax.profiler

    start = jax.profiler.start_trace

    def start_trace(log_dir, *args, **kw):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = python_tracer_level
        kw.setdefault("profiler_options", options)
        print(f"== start_trace under python_tracer_level={python_tracer_level} "
              f"(host_tracer_level={options.host_tracer_level})", file=sys.stderr, flush=True)
        return start(log_dir, *args, **kw)

    jax.profiler.start_trace = start_trace


def traced_table(run, fh) -> None:
    """ms a block by phase over the whole iterations: wall from the spans'
    self time, cpu from ``cpu_us`` less the children's."""
    from benchmarks.harness import host_spans

    by_phase, blocks, found = host_spans.self_seconds_by_phase(run), host_spans.blocks(run), host_spans.spans(run)
    if not by_phase or not blocks:
        print("== no whole iteration with a block in the sub-window", file=fh)
        return
    window = host_spans.whole_iterations(run)
    cpu: dict[str, float] = {}
    threads: dict[str, list] = {}
    for s in host_spans._all(run):  # uncut: a span's cpu_us is of its whole duration
        if window[0] <= s.start_ns and s.end_ns <= window[1] and "cpu_us" in s.kw:
            threads.setdefault(s.thread, []).append(s)
    for group in threads.values():
        stack: list = []
        for s in sorted(group, key=lambda s: (s.start_ns, -s.dur_ns)):
            while stack and stack[-1].end_ns <= s.start_ns:
                stack.pop()
            cpu[s.phase] = cpu.get(s.phase, 0.0) + s.kw["cpu_us"] / 1e3
            if stack:
                cpu[stack[-1].phase] = cpu.get(stack[-1].phase, 0.0) - s.kw["cpu_us"] / 1e3
            stack.append(s)
    n = len(blocks)
    waits = host_spans.WAITS
    print(f"loop account, traced: {n} blocks in {(window[1] - window[0]) / 1e9:.3f}s of whole iterations "
          f"({len(found)} spans); host ms a block {1e3 * sum(v for p, v in by_phase.items() if p not in waits) / n:.3f} "
          f"(cpu {sum(v for p, v in cpu.items() if p not in waits) / n:.3f}); ms a block by phase, wall (cpu): "
          + ", ".join(f"{p} {1e3 * v / n:.3f} ({cpu.get(p, 0.0) / n:.3f})"
                      for p, v in sorted(by_phase.items(), key=lambda kv: -kv[1])), file=fh, flush=True)


def check_dev_idle(run, fh) -> None:
    from benchmarks.harness import host_spans, trace_reduce as tr

    planes = tr.device_planes(run.events)
    blocks = [s for s in host_spans.blocks(run) or () if s.kw.get("dev_idle") in (0, 1)]
    if not planes or not blocks:
        print("== dev_idle check: no device plane, or no dispatch span that says 0 or 1", file=fh)
        return
    device = [e for e in run.events if e.plane == planes[0]]
    modules = sorted(e.start_ns for e in device if e.line == tr.MODULE_LINE)
    ops = sorted((e for e in device if e.line == tr.OPS_LINE), key=lambda e: e.start_ns)
    op_starts = [e.start_ns for e in ops]
    ends, latest = [], 0
    for e in ops:  # the device's latest end over the ops started so far
        latest = max(latest, e.start_ns + e.dur_ns)
        ends.append(latest)
    rows_end = {}
    for s in host_spans._all(run):
        if s.phase == "dispatch.rows":
            rows_end[s.thread, s.start_ns] = s.end_ns
    asked_at = sorted(rows_end.items())
    gaps: dict[int, list[float]] = {0: [], 1: []}
    for d in blocks:
        # the engine asked as this span's dispatch.rows closed
        asked = next((end for (thread, start), end in asked_at
                      if thread == d.thread and d.start_ns <= start < d.end_ns), d.start_ns)
        m = bisect.bisect_left(modules, asked)
        if m == len(modules):
            continue
        first = bisect.bisect_left(op_starts, modules[m])
        if first == len(ops) or first == 0:
            continue
        gaps[d.kw["dev_idle"]].append(max(0.0, (op_starts[first] - ends[first - 1]) / 1e6))
    for said, want_gap in ((1, True), (0, False)):
        found = gaps[said]
        if not found:
            print(f"== dev_idle={said}: no block in the whole iterations", file=fh)
            continue
        agree = sum((g > GAP_MS) == want_gap for g in found)
        print(f"== dev_idle={said}: {len(found)} blocks, {agree} ({100 * agree / len(found):.1f}%) "
              f"{'with' if want_gap else 'without'} a gap of more than {GAP_MS} ms on XLA Ops before the next "
              f"program; the gap's median {statistics.median(found):.3f} ms, "
              f"p10 {sorted(found)[len(found) // 10]:.3f}, p90 {sorted(found)[(9 * len(found)) // 10]:.3f}, "
              f"max {max(found):.3f}", file=fh, flush=True)


def look(path: str, workload: str) -> None:
    from benchmarks.harness import trace_reduce
    from benchmarks.tools import span_look

    run = span_look.look_again(trace_reduce.load_events(path), workload)
    traced_table(run, sys.stderr)
    check_dev_idle(run, sys.stderr)


def main(argv: list[str]) -> int:
    if argv[0] == "check":
        look(argv[1], argv[2])
        return 0
    workload, seed, seconds, *rest = argv
    keep = None
    while rest:
        flag, value, *rest = rest
        if flag == "--python-tracer":
            start_trace_with(int(value))
        elif flag == "--keep":
            keep = value
        else:
            raise SystemExit(f"unknown option {flag}")
    from benchmarks.harness import loop_account, runner
    from benchmarks.tools import span_look

    runs: list = []
    load_trace = runner._load_trace
    runner._load_trace = lambda run, *a, **kw: runs.append(run) or load_trace(run, *a, **kw)
    span_look.T_START = T_START
    out = keep or os.path.join(ROOT, "chiprun_out", f"loop_look.{workload}.{seed}.json.gz")
    code = span_look.main([workload, seed, seconds, out])
    for run in runs:
        print("== profiler off, " + ", ".join(f"{fn.__name__} = {fn(run)!r}" for fn in (
            loop_account.host_ms_per_block_untraced, loop_account.launch_idle_share_untraced_pct,
            loop_account.host_offcpu_share_untraced_pct))
            + f"; under it, launch_idle_share_pct = {loop_account.launch_idle_share_pct(run)!r}; "
            f"quiet stretches {loop_account.quiet_stretches(run)} of the window {run.window}, traced {run.traced}",
            file=sys.stderr, flush=True)
    if os.path.exists(out):
        look(out, workload)
        if keep is None:
            os.remove(out)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
