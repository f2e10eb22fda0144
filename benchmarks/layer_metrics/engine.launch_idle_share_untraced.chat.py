"""Device: blocks launched while the newest block in flight had its result ready (the device waited for the host) over blocks launched with one in flight, in the quiet stretches of the window (before the profiler's session and after it), from the /requestz loop snapshots, %."""

from benchmarks.harness import loop_account


def read(run):
    return loop_account.launch_idle_share_untraced_pct(run)
