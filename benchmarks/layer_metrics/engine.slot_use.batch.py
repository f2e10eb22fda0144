"""Step planner: rows x steps over slots x steps of the blocks dispatched in the traced sub-window (the dispatch spans' own counts), %."""

from benchmarks.harness import host_spans


def read(run):
    return host_spans.slot_use_pct(run)
