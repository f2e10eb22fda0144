"""Model step: mean rows a held expert takes in one decode step of one expert layer, from the device's moe_rows, rows."""

from benchmarks.harness import joyai_flash_layers


def read(run):
    return joyai_flash_layers.rows_per_expert(run)
