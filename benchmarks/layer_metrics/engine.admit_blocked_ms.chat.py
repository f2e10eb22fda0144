"""Step planner: mean time one admission holds the engine thread (gofr.step.prefill, its first-token read inside it) in the traced sub-window, ms."""

from benchmarks.harness import host_spans


def read(run):
    return host_spans.admit_blocked_ms(run)
