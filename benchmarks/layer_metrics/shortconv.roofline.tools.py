"""Kernels: least time for what the 18 conv mixers of the decode steps move (W_in, W_out and scales, live rows' tails and activations) over the device time of their ops, %."""

from benchmarks.harness import lfm2_moe_layers


def read(run):
    return lfm2_moe_layers.conv_roofline_pct(run)
