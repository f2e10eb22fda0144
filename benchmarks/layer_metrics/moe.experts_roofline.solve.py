"""Kernels: least time for the decode steps' relu² expert products (bytes of the held experts reached and of the shared expert at their published widths, FLOPs of the row-experts routed and of the shared expert) over the device time of the expert_rows calls, routed and shared, %."""

from benchmarks.harness import nemotron_h_layers


def read(run):
    return nemotron_h_layers.experts_roofline_pct(run)
