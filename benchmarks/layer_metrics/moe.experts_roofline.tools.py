"""Kernels: least time for the decode steps' expert products (bytes of the experts reached, FLOPs of the row-experts routed) over the device time of the ops that read an expert stack, %."""

from benchmarks.harness import lfm2_moe_layers


def read(run):
    return lfm2_moe_layers.experts_roofline_pct(run)
