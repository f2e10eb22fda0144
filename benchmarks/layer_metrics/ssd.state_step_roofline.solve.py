"""Kernels: least time for what the decode steps' SSD state step had to move (each live row's float32 state of each Mamba layer read and written, its vectors in and out) at the HBM rate, over the device time of the ssd_state_step calls, %."""

from benchmarks.harness import nemotron_h_layers


def read(run):
    return nemotron_h_layers.state_step_roofline_pct(run)
