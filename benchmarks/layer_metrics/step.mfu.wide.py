"""Model step: FLOPs the served tokens need on this chip's share of the experts and the vocabulary, over the sub-window at the bf16 peak, %."""

from benchmarks.harness import cohere2_moe_layers


def read(run):
    return cohere2_moe_layers.step_mfu_pct(run)
