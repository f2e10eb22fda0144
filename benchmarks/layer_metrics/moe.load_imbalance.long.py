"""Model step: the fullest held expert's rows over the mean held expert's in deepseek_v32, from the commit spans' moe_max and moe_rows, x."""

from benchmarks.harness import deepseek_v32_layers


def read(run):
    return deepseek_v32_layers.load_imbalance(run)
