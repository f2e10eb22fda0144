"""Step planner: the step loop's host work (every phase of the engine's own account but sync, prefill_sync, wait) per block dispatched in the quiet stretches of the window (before the profiler's session and after it), from the /requestz loop snapshots, ms."""

from benchmarks.harness import loop_account


def read(run):
    return loop_account.host_ms_per_block_untraced(run)
