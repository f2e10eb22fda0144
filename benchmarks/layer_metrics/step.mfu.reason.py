"""Model step: FLOPs the served tokens need (every layer a decoded token, attention over the positions the device counted — one cached layer's eight readers, the window layers' last 512 —, the recurrences; the self-decoder over a prompt and everything above on its last position), over the sub-window's whole iterations at the bf16 peak, %."""

from benchmarks.harness import phi4flash_layers


def read(run):
    return phi4flash_layers.step_mfu_pct(run)
