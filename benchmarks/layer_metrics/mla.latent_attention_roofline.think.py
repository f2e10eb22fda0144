"""Kernels: least time for the latent rows the decode steps' attention read (the device's mla_kv positions x 1,152 B at the HBM rate) over the device time of the paged_latent_attention calls, %."""

from benchmarks.harness import joyai_flash_layers


def read(run):
    return joyai_flash_layers.latent_attention_roofline_pct(run)
