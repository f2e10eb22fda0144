"""Model step: needed FLOPs of the served tokens over the sub-window at the bf16 peak, %."""

from benchmarks.harness import layers


def read(run):
    return layers.step_mfu_pct(run)
