"""Kernels: least time to move the live rows' recurrent state (float32 S read and written in nine layers a row-step the device counted) over the device time of the decode steps' recurrence ops, %."""

from benchmarks.harness import phi4flash_layers


def read(run):
    return phi4flash_layers.state_roofline_pct(run)
