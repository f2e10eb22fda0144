"""Model step: FLOPs the served tokens need (2 x 1.56 B active parameters a token and attention over the positions the device counted) over the whole iterations at the bf16 peak, %."""

from benchmarks.harness import lfm2_moe_layers


def read(run):
    return lfm2_moe_layers.step_mfu_pct(run)
