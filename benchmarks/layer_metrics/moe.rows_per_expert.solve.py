"""Model step: mean rows a held expert takes in one decode step of one expert layer, from the device's counts."""

from benchmarks.harness import nemotron_h_layers


def read(run):
    return nemotron_h_layers.rows_per_expert(run)
