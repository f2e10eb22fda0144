"""Model step: device time of one decode step, in whichever program holds it, ms."""

from benchmarks.harness import nemotron_h_layers


def read(run):
    return nemotron_h_layers.decode_step_ms(run)
