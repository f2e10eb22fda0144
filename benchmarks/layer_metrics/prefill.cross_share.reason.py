"""Model step: prompt positions the layers above the shared cache ran over those the layers up to it ran, %."""

from benchmarks.harness import phi4flash_layers


def read(run):
    return phi4flash_layers.cross_share_pct(run)
