"""Kernels: least time for the paged kernel's bytes over its device time, %."""

from benchmarks.harness import layers


def read(run):
    return layers.paged_attention_roofline_pct(run)
