"""Model step: device time of one decode step, in the decode-block and the ragged program alike (the ops around the step's cache appends), ms."""

from benchmarks.harness import phi4flash_layers


def read(run):
    return phi4flash_layers.decode_step_ms(run)
