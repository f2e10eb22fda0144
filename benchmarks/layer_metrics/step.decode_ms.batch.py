"""Model step: device time of the decode-block program per decode step, ms."""

from benchmarks.harness import layers


def read(run):
    return layers.decode_ms(run)
