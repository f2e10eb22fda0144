"""The reductions the ``phi4flash`` cell's per-layer readers share: from a
run's records, the engine's spans and the device trace to one number. Each
reader under ``benchmarks/layer_metrics/`` is a few lines over these. A
function that finds nothing to read — another architecture's configuration,
a program without the spans, no device plane — returns None, never 0, and
does not raise.

What the program writes, and what is read here (docs/observability.md):
``gofr.step.commit`` carries ``attn_full``, ``attn_win`` and ``ssm_rows``
— cache positions the full layer's readers and the window layers read, and
row-steps whose state advanced, over a block's decode steps and live rows,
COUNTED ON THE DEVICE from the lengths the steps used. The work of the
three device shares is taken from these and not from the client's token
stamps: under the profiler the server's 2,500 SSE frames a second reach the
client late, and a count of stamped tokens in the sub-window reads a
quarter low (PERF.md section 6, PR 35). Work and device time are both taken
over the sub-window's whole loop iterations (``host_spans``).
``gofr.step.dispatch`` carries ``win_pages_held`` (ring pages of the window
pool the dispatched rows' tables address) beside ``kv_tokens`` and ``rows``;
``gofr.step.prefill`` and a ragged ``gofr.step.dispatch`` carry
``self_tokens`` and ``cross_tokens`` (positions the layers up to the shared
cache ran, positions the layers above ran). On the device trace the Mosaic
attention kernel is named after its jitted wrapper
(``layers.PAGED_KERNEL``); an XLA fusion is named by its HLO instruction's
text only (``deepseek_v32_layers``' record, PR 33), so the recurrence is
found by the one shape only the state has among an instruction's result and
operands: ``[rows, d_state, d_inner]`` float32 and the stack of it. The
warning of PERF.md section 7 holds here too: a fusion that carries the
state's shape beside other work is counted whole, so the share reads low
rather than high.
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness import host_spans, layers, peaks, trace_reduce
from benchmarks.harness import phi4flash_costs as costs

_KEYS = ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "mb_per_layer", "sliding_window")
APPEND_KERNEL = "paged_kv_append"  # the Mosaic call a decode step makes in every caching layer, and a chunk never


def _is_phi(run: Any) -> bool:
    return all(k in run.config for k in _KEYS)


def step_counts(run: Any) -> dict[str, int] | None:
    """The device-counted work of the blocks committed in the sub-window's
    whole iterations: ``attn_full``, ``attn_win``, ``ssm_rows`` summed over
    the commit spans."""
    found = [s for s in host_spans.spans(run) or () if s.phase == "commit" and "ssm_rows" in s.kw]
    if not _is_phi(run) or not found:
        return None
    return {k: sum(int(s.kw[k]) for s in found) for k in ("attn_full", "attn_win", "ssm_rows")}


def _whole(run: Any) -> tuple[int, int] | None:
    """The whole iterations of the traced sub-window, if the run has a
    device plane to read beside them."""
    if not _is_phi(run) or not trace_reduce.device_planes(run.events):
        return None
    return host_spans.whole_iterations(run)


def step_mfu_pct(run: Any) -> float | None:
    """FLOPs the served tokens need (``phi4flash_costs.served_flops``: the
    decode steps' from the device's counts, the prompts' from the pieces
    the engine committed) over the whole iterations at the chip's bf16 peak
    (int8 weights are dequantised into bf16 products): the share of the
    whole step."""
    segs, counts, whole = layers.prefill_segments(run), step_counts(run), _whole(run)
    if counts is None or segs is None or whole is None:
        return None
    flops = costs.served_flops(run.config, segs, counts["ssm_rows"], counts["attn_full"] + counts["attn_win"])
    return 100.0 * flops / ((whole[1] - whole[0]) / 1e9 * peaks.peaks_for(run.device_kind)["bf16_flops_per_s"])


def _device_ops(run: Any) -> list[Any]:
    return sorted((e for e in run.events if trace_reduce.is_device_plane(e.plane) and e.line == trace_reduce.OPS_LINE),
                  key=lambda e: (e.start_ns, -e.dur_ns))


def _executions(run: Any, whole: bool) -> list[tuple[int, int]]:
    """(start, end) of the executions of the programs that hold decode
    steps: those that lie whole in the traced sub-window, or all."""
    span = run.traced_ns()
    return sorted((e.start_ns, e.start_ns + e.dur_ns) for e in run.events
                  if trace_reduce.is_device_plane(e.plane) and e.line == trace_reduce.MODULE_LINE
                  and trace_reduce.program_name(e.name) in (layers.DECODE_PROGRAM, layers.RAGGED_PROGRAM)
                  and (not whole or (span[0] <= e.start_ns and e.start_ns + e.dur_ns <= span[1])))


def decode_step_ms(run: Any) -> float | None:
    """Device time of one decode step, in whichever program holds it (as
    ``step.decode_ms.long`` reads it): in every execution of
    ``decode_block_paged`` or ``ragged_step_paged`` that lies whole in the
    traced sub-window, the outermost ops around a ``paged_kv_append`` (the
    loop over the block's steps; a ragged dispatch's chunk is beside it,
    not in it), over the steps."""
    if run.traced_ns() is None or not _is_phi(run) or not trace_reduce.device_planes(run.events):
        return None
    ops = _device_ops(run)
    total, executions = 0, 0
    for a, b in _executions(run, whole=True):
        outer, found = None, set()
        for e in ops:
            if not a <= e.start_ns < b:
                continue
            if outer is None or e.start_ns >= outer.start_ns + outer.dur_ns:
                outer = e
            if trace_reduce.op_name(e.name).startswith(APPEND_KERNEL):
                found.add(outer)
        if found:
            total, executions = total + sum(e.dur_ns for e in found), executions + 1
    if not executions:
        return None
    return total / 1e6 / (executions * layers.block_steps(run))


def kv_read_roofline_pct(run: Any) -> float | None:
    """Least time the chip could take to read what the decode steps'
    attention had to — the one cached layer once for itself and once for
    every cross-attention layer, the window layers' last ``sliding_window``
    positions, from the lengths the steps really had (``attn_full`` +
    ``attn_win`` layer-positions) — over the device time of the events
    named ``paged_decode_attention.<n>``: whatever implements the pairs is
    measured against the same bytes. The calls a finishing chunk makes are
    in the time and not in the bytes."""
    counts, whole = step_counts(run), _whole(run)
    if counts is None or whole is None:
        return None
    kernel_s = sum(v["seconds"] for k, v in trace_reduce.op_times(run.events, *whole).items()
                   if k.split(".")[0] == layers.PAGED_KERNEL)
    read = (counts["attn_full"] + counts["attn_win"]) * costs.kv_bytes_per_position(run.config)
    if not kernel_s or not read:
        return None
    return 100.0 * read / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"] / kernel_s


def state_marks(run: Any) -> tuple[str, ...]:
    """How the recurrent state reads among an instruction's result and
    operands: one layer's [rows, d_state, d_inner] float32 (also with a
    unit axis), and the stack of the Mamba layers'."""
    c = run.config
    rows, n, din = int(run.cell["engine"]["max_slots"]), int(c["mamba_d_state"]), costs.d_inner(c)
    stack = costs.layer_counts(c)["mamba"]
    return (f"f32[{rows},{n},{din}]", f"f32[1,{rows},{n},{din}]", f"f32[{stack},{rows},{n},{din}]")


def recurrence_events(run: Any) -> list[Any] | None:
    """Leaf events of the ``XLA Ops`` line in the sub-window's whole
    iterations that started inside an execution of a program that holds
    decode steps and carry the state's shape (a ``while`` around the layers
    names every shape in its tuple and is no leaf)."""
    span = _whole(run)
    if span is None:
        return None
    inside, marks = _executions(run, whole=False), state_marks(run)
    out = []
    for e in trace_reduce.clip(_device_ops(run), *span):
        op = e.name.split(" = ", 1)[-1]
        if any(f" {kind}(" in op for kind in ("while", "conditional", "call")):
            continue
        if any(m in e.name for m in marks) and any(a <= e.start_ns < b for a, b in inside):
            out.append(e)
    return out


def state_roofline_pct(run: Any) -> float | None:
    """Least time for the state's bytes of the live rows — S read and
    written in every Mamba layer, a row-step whose state advanced
    (``ssm_rows``) — over the device time of the decode steps' recurrence
    ops."""
    events, counts = recurrence_events(run), step_counts(run)
    if not events or counts is None or not counts["ssm_rows"]:
        return None
    least = costs.state_bytes(run.config, counts["ssm_rows"]) / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (sum(e.dur_ns for e in events) / 1e9)


def window_held_share_pct(run: Any) -> float | None:
    """Ring pages of the window pool the dispatched rows hold over the
    pages their whole contexts fill (``kv_tokens`` / page): 100 % where
    every page is kept, about 528 / context where pages behind the window
    are returned."""
    found = [s for s in host_spans.blocks(run) or () if "win_pages_held" in s.kw and s.kw.get("kv_tokens")]
    if not _is_phi(run) or not found:
        return None
    page = int(run.cell["engine"].get("kv_page_size", 16))
    return 100.0 * sum(s.kw["win_pages_held"] for s in found) / (sum(s.kw["kv_tokens"] for s in found) / page)


def cross_share_pct(run: Any) -> float | None:
    """Of the prompt positions the layers up to the shared cache ran, the
    share the layers above ran: ``cross_tokens`` over ``self_tokens`` of
    the prefill spans and ragged dispatches of the whole iterations."""
    found = [s for s in host_spans.spans(run) or () if s.kw.get("self_tokens")]
    if not _is_phi(run) or not found:
        return None
    return 100.0 * sum(s.kw.get("cross_tokens", 0) for s in found) / sum(s.kw["self_tokens"] for s in found)
