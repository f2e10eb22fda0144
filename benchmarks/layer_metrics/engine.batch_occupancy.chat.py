"""Step planner: decoded tokens / (decode steps x slots) in the traced sub-window, %."""

from benchmarks.harness import layers


def read(run):
    return layers.batch_occupancy_pct(run)
