"""Device: dispatch spans with dev_idle=1 (the newest block in flight was ready: the device waited for the host) over those with dev_idle 0 or 1, in the traced sub-window's whole iterations, %."""

from benchmarks.harness import loop_account


def read(run):
    return loop_account.launch_idle_share_pct(run)
