"""Model step: of the positions the indexer scored, the share attention read (the commit spans' dsa_selected over dsa_scored), %."""

from benchmarks.harness import deepseek_v32_layers


def read(run):
    return deepseek_v32_layers.selected_share_pct(run)
