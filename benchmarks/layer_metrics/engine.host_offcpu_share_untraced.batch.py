"""Step planner: share of the step loop's host time (waits left out) in which its thread was off the CPU (wall less thread-CPU: the GIL, a lock, a call blocked in the runtime), in the quiet stretches of the window (before the profiler's session and after it), from the /requestz loop snapshots, %."""

from benchmarks.harness import loop_account


def read(run):
    return loop_account.host_offcpu_share_untraced_pct(run)
