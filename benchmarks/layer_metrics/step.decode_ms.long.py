"""Model step: device time of one decode step of deepseek_v32 in whichever program holds it (the loop around the steps' paged_kv_append, in decode_block_paged and in ragged_step_paged), ms."""

from benchmarks.harness import deepseek_v32_layers


def read(run):
    return deepseek_v32_layers.decode_step_ms(run)
