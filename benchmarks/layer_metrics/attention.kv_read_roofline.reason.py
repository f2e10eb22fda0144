"""Kernels: least time to read what the decode steps' attention had to (the device's count: one cached layer read eight times, eight window layers' last 512 positions) over the device time of the paged_decode_attention calls, %."""

from benchmarks.harness import phi4flash_layers


def read(run):
    return phi4flash_layers.kv_read_roofline_pct(run)
