"""Kernels: least time for the decode steps' expert products (int8 bytes of the held experts reached and of the shared ones) over their device time, %."""

from benchmarks.harness import cohere2_moe_layers


def read(run):
    return cohere2_moe_layers.experts_roofline_pct(run)
