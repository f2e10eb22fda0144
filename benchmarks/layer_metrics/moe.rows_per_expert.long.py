"""Model step: mean rows a held expert takes in one decode step of one expert layer of deepseek_v32 (the commit spans' moe_rows), rows."""

from benchmarks.harness import deepseek_v32_layers


def read(run):
    return deepseek_v32_layers.rows_per_expert(run)
