"""Device: 1 - busy union / traced sub-window, %."""

from benchmarks.harness import layers


def read(run):
    return layers.idle_share_pct(run)
