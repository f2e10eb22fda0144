"""Model step: FLOPs the served tokens need on this chip's share (latent attention over the selected positions, the indexer over every position, the share of the experts and of the vocabulary), over the sub-window at the bf16 peak, %."""

from benchmarks.harness import deepseek_v32_layers


def read(run):
    return deepseek_v32_layers.step_mfu_pct(run)
