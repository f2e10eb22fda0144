"""Kernels: least time for the decode steps' held routed experts (bytes of the experts reached, FLOPs of the row-experts routed) over the device time of the ops that read their stacks, %."""

from benchmarks.harness import joyai_flash_layers


def read(run):
    return joyai_flash_layers.experts_roofline_pct(run)
