"""Kernels: least time for the decode steps' expert products at this model's shapes (int8 bytes of the held experts reached and of the shared one) over their device time, %."""

from benchmarks.harness import deepseek_v32_layers


def read(run):
    return deepseek_v32_layers.experts_roofline_pct(run)
