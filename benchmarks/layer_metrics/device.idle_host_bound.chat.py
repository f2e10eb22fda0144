"""Device: share of the traced sub-window in which no operation ran while the engine thread was inside a gofr.step span other than wait, %."""

from benchmarks.harness import host_spans


def read(run):
    return host_spans.idle_host_bound_pct(run)
