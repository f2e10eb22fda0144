"""KV manager: highest share of the page pool in use, polled once a second, %."""

from benchmarks.harness import layers


def read(run):
    return layers.page_fill_pct(run)
