"""Kernels: least time for the decode steps' indexer (one key a context position, every index head's score) over the device time of its scores and of the selection's sort, %."""

from benchmarks.harness import deepseek_v32_layers


def read(run):
    return deepseek_v32_layers.indexer_roofline_pct(run)
