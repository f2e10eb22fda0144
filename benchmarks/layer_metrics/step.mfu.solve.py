"""Model step: FLOPs the served tokens need on this chip's share (Mamba-2 mixers, attention over every position, the held share of the experts and of the vocabulary) from the device's counts, over the whole iterations at the bf16 peak, %."""

from benchmarks.harness import nemotron_h_layers


def read(run):
    return nemotron_h_layers.step_mfu_pct(run)
