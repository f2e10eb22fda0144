"""Kernels: least time for the decode steps' attention over the selected latent rows (bytes as stored, every head's scores and sums) over the device time of what implements it, %."""

from benchmarks.harness import deepseek_v32_layers


def read(run):
    return deepseek_v32_layers.sparse_attention_roofline_pct(run)
