"""Model step: device time of one decode step, the loop around its appends in either program that holds decode steps, ms."""

from benchmarks.harness import joyai_flash_layers


def read(run):
    return joyai_flash_layers.decode_step_ms(run)
