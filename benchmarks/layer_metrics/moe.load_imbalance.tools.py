"""Model step: the fullest expert's rows over the mean expert's, from the commit spans' moe_max and moe_rows, x."""

from benchmarks.harness import lfm2_moe_layers


def read(run):
    return lfm2_moe_layers.load_imbalance(run)
