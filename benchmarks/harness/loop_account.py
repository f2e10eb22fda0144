"""The step loop's own account, read where no profiler is on.

Every other per-layer metric of the engine's host layer comes from the
``gofr.step*`` spans of the traced sub-window, and the harness starts that
trace with the profiler's defaults: on jax 0.9 they hook every Python call
and return of every thread (``python_tracer_level`` 1), so the loop that
those spans time runs several times slower than the one the untraced
windows measure. The engine keeps the same account always on
(``ServingEngine.loop_account``: blocks dispatched, blocks launched onto an
idle device, wall and CPU seconds of the loop thread by phase) and stamps
it into each request's timeline as it admits the request and as it retires
it; ``/requestz/<id>`` renders the two under ``loop``, in milliseconds, and
the runner fetches every request's timeline after the window. This module
takes the snapshots that fall in the **quiet stretches** — the parts of the
window in which no profiler session is on — and differences the earliest
and the latest of each.

The quiet stretches: from the window's start to ``run.traced[0]``, where
the session starts; and from one second after the session's end to the
window's end. The harness's poller sits inside ``stop_trace`` until it
returns, so the first ``health_polls`` entry stamped after ``run.traced[1]``
marks that end — 11 to 17 s after ``traced[1]`` on the v5e machine (my chip
runs, PR 37), so that a cell whose trace starts 30 s into a window of 51
has next to nothing behind its session, and its reading is the stretch
before it. Together they are the window ``tok_s`` and ``tpot_p90_ms`` are
taken over, less what the profiler slowed. A snapshot's host time is the
timeline's wall-clock birth on the serving process's monotonic clock plus
the snapshot's ``ms``, the way ``layers.prefill_segments`` places a chunk.

A program without the account (the parent of PR 37: no ``loop`` in its
timelines, no ``dev_idle`` on its spans), an untraced run, or fewer than
``MIN_BLOCKS`` blocks between the snapshots give None, never 0.
"""

from __future__ import annotations

import sys
from typing import Any

from benchmarks.harness import host_spans

# the phases in which the loop thread waits, as host_spans leaves them out
WAITS = host_spans.WAITS
SETTLE_S = 1.0    # after the first poll past the trace: the session's buffers are gone
MIN_BLOCKS = 20   # fewer blocks between the snapshots say nothing of a block


def quiet_stretches(run: Any) -> list[tuple[float, float]]:
    """(start, end) of each, on the serving process's monotonic clock."""
    if run.traced is None:
        return []
    out = [(run.window[0], run.traced[0])] if run.traced[0] > run.window[0] else []
    after = [p["t"] for p in run.health_polls if p["t"] > run.traced[1]]
    if after and run.window[1] > min(after) + SETTLE_S:
        out.append((min(after) + SETTLE_S, run.window[1]))
    return out


def snapshots(run: Any) -> list[tuple[float, dict[str, Any]]]:
    """Every ``loop`` snapshot of the run's timelines with its host time,
    by that time."""
    if run.wall_minus_mono is None:
        return []
    out = []
    for z in run.requestz.values():
        loop = (z or {}).get("loop")
        if not loop:
            continue
        born = z["created_unix"] - run.wall_minus_mono
        for key in ("at_admit", "at_end"):
            if loop.get(key):
                out.append((born + loop[key]["ms"] / 1e3, loop[key]))
    return sorted(out, key=lambda pair: pair[0])


def between(run: Any) -> dict[str, Any] | None:
    """What the loop did between the earliest and the latest snapshot of
    each quiet stretch, summed over the stretches: seconds spanned,
    blocks, blocks launched onto an idle and onto a busy device, wall and
    CPU milliseconds by phase."""
    if "loop_account" not in run.cache:
        run.cache["loop_account"] = _between(run)
    return run.cache["loop_account"]


def _between(run: Any) -> dict[str, Any] | None:
    found = snapshots(run)
    out: dict[str, Any] = {"seconds": 0.0, "blocks": 0, "launched_idle": 0, "launched_queued": 0,
                           "phase_ms": {}, "cpu_ms": {}}
    for a, b in quiet_stretches(run):
        inside = [(t, snap) for t, snap in found if a <= t < b]
        if len(inside) < 2:
            continue
        (t0, first), (t1, last) = inside[0], inside[-1]
        out["seconds"] += t1 - t0
        for key in ("blocks", "launched_idle", "launched_queued"):
            out[key] += last[key] - first[key]
        for key in ("phase_ms", "cpu_ms"):
            for phase, ms in last[key].items():
                out[key][phase] = out[key].get(phase, 0.0) + ms - first[key].get(phase, 0.0)
    if out["blocks"] < MIN_BLOCKS:
        return None
    _say(out)
    return out


def _say(found: dict[str, Any]) -> None:
    """The quiet stretches' table on standard error: the untraced twin of
    host_spans' "engine thread seconds by phase"."""
    n = found["blocks"]
    rows = sorted(found["phase_ms"].items(), key=lambda kv: -kv[1])
    print(f"loop account, profiler off: {n} blocks in {found['seconds']:.3f}s "
          f"({found['launched_idle']} launched onto an idle device, {found['launched_queued']} behind a running block); "
          "ms a block by phase, wall (cpu): "
          + ", ".join(f"{p} {ms / n:.3f} ({found['cpu_ms'].get(p, 0.0) / n:.3f})" for p, ms in rows if ms > 0),
          file=sys.stderr, flush=True)


def _host(found: dict[str, Any], account: str) -> float:
    return sum(ms for p, ms in found[account].items() if p not in WAITS)


# ------------------------------------------------------- the readers' numbers
def host_ms_per_block_untraced(run: Any) -> float | None:
    """Host work of the step loop per dispatched block with no profiler
    session on: every phase's milliseconds but the waits', over the blocks
    dispatched, between the snapshots."""
    found = between(run)
    return None if found is None else _host(found, "phase_ms") / found["blocks"]


def launch_idle_share_untraced_pct(run: Any) -> float | None:
    """Share of the blocks launched with a block in flight that found it
    finished: the device had run dry and waited for the host."""
    found = between(run)
    if found is None or not found["launched_idle"] + found["launched_queued"]:
        return None
    return 100.0 * found["launched_idle"] / (found["launched_idle"] + found["launched_queued"])


def host_offcpu_share_untraced_pct(run: Any) -> float | None:
    """Share of the loop's host time a block in which its thread was not
    on a CPU: the wait for the GIL, for a lock, or inside a call blocked in
    the runtime."""
    found = between(run)
    if found is None or _host(found, "phase_ms") <= 0.0:
        return None
    return 100.0 * max(0.0, 1.0 - _host(found, "cpu_ms") / _host(found, "phase_ms"))


def launch_idle_share_pct(run: Any) -> float | None:
    """The same share under the profiler, from the dispatch spans of the
    traced sub-window's whole iterations: ``dev_idle`` = 1 over ``dev_idle``
    in (0, 1); a block launched with none in flight (2) counts in neither."""
    found = [s.kw["dev_idle"] for s in host_spans.blocks(run) or () if s.kw.get("dev_idle") in (0, 1)]
    return 100.0 * sum(found) / len(found) if found else None
