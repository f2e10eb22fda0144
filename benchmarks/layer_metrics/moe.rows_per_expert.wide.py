"""Model step: mean rows a held expert takes in one decode step of one layer (the commit spans' moe_rows), rows."""

from benchmarks.harness import cohere2_moe_layers


def read(run):
    return cohere2_moe_layers.rows_per_expert(run)
