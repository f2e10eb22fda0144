"""Model step: device time of one decode step, the loop around the appends in decode_block_paged and ragged_step_paged over its steps, ms."""

from benchmarks.harness import lfm2_moe_layers


def read(run):
    return lfm2_moe_layers.decode_step_ms(run)
