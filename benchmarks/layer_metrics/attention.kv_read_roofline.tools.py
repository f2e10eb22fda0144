"""Kernels: least time to read the K and V the device counted (attn_kv positions of the 6 attention layers) over the paged kernel's device time, %."""

from benchmarks.harness import lfm2_moe_layers


def read(run):
    return lfm2_moe_layers.kv_read_roofline_pct(run)
