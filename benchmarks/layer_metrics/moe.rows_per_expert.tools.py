"""Model step: mean rows an expert takes in one decode step of one expert layer (the commit spans' moe_rows), rows."""

from benchmarks.harness import lfm2_moe_layers


def read(run):
    return lfm2_moe_layers.rows_per_expert(run)
