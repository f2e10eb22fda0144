"""Model step: the fullest held expert's rows over the mean held expert's, from the commit spans' moe_max and moe_rows, x."""

from benchmarks.harness import cohere2_moe_layers


def read(run):
    return cohere2_moe_layers.load_imbalance(run)
