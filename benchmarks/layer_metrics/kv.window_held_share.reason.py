"""KV manager: window-pool pages the dispatched rows hold over the pages their whole contexts fill, %."""

from benchmarks.harness import phi4flash_layers


def read(run):
    return phi4flash_layers.window_held_share_pct(run)
