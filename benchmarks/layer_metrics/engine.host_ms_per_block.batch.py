"""Step planner: the step loop's host work (self time of every gofr.step span but sync, prefill_sync, wait) per block dispatched in the traced sub-window, ms."""

from benchmarks.harness import host_spans


def read(run):
    return host_spans.host_ms_per_block(run)
