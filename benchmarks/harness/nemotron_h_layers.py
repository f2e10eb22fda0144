"""The reductions the ``nemotron3nano.solve`` cell's per-layer readers share:
from a run's records, the engine's spans and the device trace to one
number. Each reader under ``benchmarks/layer_metrics/`` is a few lines over
these. A function that finds nothing to read — another architecture's
configuration, a program without the counters, no device plane — returns
None, never 0, and does not raise.

What the program writes, and what is read here (docs/observability.md):
``gofr.step.commit`` carries, over a block's decode steps, COUNTED ON THE
DEVICE: ``ssm_rows`` (row-steps whose SSD state advanced, summed over the
23 Mamba layers: 23 x the live row-steps), ``attn_full`` (positions the 6
attention layers read), ``moe_rows`` (row-expert pairs routed to the held
experts), ``moe_max`` (the fullest expert's) and ``moe_reached`` (held
experts whose matrices were read, summed over the expert layers). The work
of the shares is taken from these, over the sub-window's whole loop
iterations (``host_spans``), as ``joyai_flash_layers`` takes its own.

On the device trace a Mosaic call is named after its ``pallas_call``: the
decode state step is ``%ssd_state_step.<n>`` (one call a Mamba layer and
step; a chunk scans in XLA, not the kernel), the every-row expert sum
``%expert_rows.<n>`` (one call for the held experts' stacks and one for
the shared expert's, a layer and step). The shares are taken in the
executions of ``decode_block_paged`` that lie whole in the whole
iterations: a ragged dispatch holds a chunk's scan and grouped products
beside its steps.
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness import host_spans, layers, peaks, trace_reduce
from benchmarks.harness import nemotron_h_costs as costs
from benchmarks.harness.lfm2_moe_layers import _decode_executions
from benchmarks.harness.phi4flash_layers import APPEND_KERNEL, _device_ops, _executions

COUNTERS = ("ssm_rows", "attn_full", "moe_rows", "moe_max", "moe_reached")
STATE_KERNEL = "ssd_state_step"  # the decode state step's Mosaic call
EXPERT_KERNEL = "expert_rows"  # the every-row expert sum's Mosaic call


def _is_nemotron(run: Any) -> bool:
    return run.config.get("model_type") == "nemotron_h"


def _commits(run: Any) -> list[Any]:
    found = host_spans.spans(run) if _is_nemotron(run) else None
    return [s for s in found or () if s.phase == "commit" and "ssm_rows" in s.kw and "moe_rows" in s.kw]


def step_counts(run: Any) -> dict[str, int] | None:
    """The device-counted work of the blocks committed in the sub-window's
    whole iterations, summed over the commit spans, and ``blocks``: how
    many."""
    found = _commits(run)
    if not found:
        return None
    out = {k: sum(int(s.kw.get(k, 0)) for s in found) for k in COUNTERS}
    out["blocks"] = len(found)
    return out


def _whole(run: Any) -> tuple[int, int] | None:
    if not _is_nemotron(run) or not trace_reduce.device_planes(run.events):
        return None
    return host_spans.whole_iterations(run)


def _kernel_events(run: Any, name: str) -> tuple[list[Any], int] | None:
    """The events of the Mosaic call ``name`` inside the executions of
    ``decode_block_paged`` that lie whole in the whole iterations, and how
    many executions they came from."""
    span = _whole(run)
    if span is None:
        return None
    inside = _decode_executions(run, span)
    found = [e for e in _device_ops(run) if trace_reduce.op_name(e.name).split(".")[0] == name
             and any(a <= e.start_ns < b for a, b in inside)]
    return found, len(inside)


def state_step_roofline_pct(run: Any) -> float | None:
    """Least time for what the decode steps' state step had to move — per
    live row and Mamba layer the row's float32 state read and written and
    its vectors in and out (``nemotron_h_costs.state_step_bytes``) — at the
    HBM rate, over the device time of the ``ssd_state_step.<n>`` calls. The
    live row-layers are the committed blocks' mean ``ssm_rows`` a block,
    applied to the ``decode_block_paged`` executions timed."""
    found, counts = _kernel_events(run, STATE_KERNEL), step_counts(run)
    if not found or not found[0] or not found[1] or counts is None or not counts["ssm_rows"]:
        return None
    events, executions = found
    moved = costs.state_step_bytes(run.config, executions * counts["ssm_rows"] / counts["blocks"])
    return 100.0 * moved / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"] / (sum(e.dur_ns for e in events) / 1e9)


def experts_roofline_pct(run: Any) -> float | None:
    """Least time for the expert products of the decode steps timed — per
    layer and step the larger of the bytes of the held experts REACHED
    (``moe_reached`` a layer-step) and of the shared expert, at their
    published widths, over the HBM rate, and the FLOPs of the row-experts
    ROUTED (``moe_rows``) and of the shared expert over the live rows, over
    the bf16 peak — over the device time of the ``expert_rows.<n>`` calls
    (routed and shared). The counts are the committed blocks' mean a
    layer-step, applied to the ``decode_block_paged`` executions timed."""
    found, counts = _kernel_events(run, EXPERT_KERNEL), step_counts(run)
    if not found or not found[0] or not found[1] or counts is None:
        return None
    events, executions = found
    c = run.config
    n = costs.layer_counts(c)
    per_block = n["moe"] * layers.block_steps(run)
    reached = counts["moe_reached"] / (counts["blocks"] * per_block)
    rows = counts["moe_rows"] / (counts["blocks"] * per_block)
    live = counts["ssm_rows"] / (counts["blocks"] * n["mamba"] * layers.block_steps(run))
    pk = peaks.peaks_for(run.device_kind)
    moved = reached * costs.expert_bytes(c) + int(c["n_shared_experts"]) * costs.expert_bytes(c, shared=True)
    least = max(moved / pk["hbm_bytes_per_s"], costs.expert_flops(c, rows, live) / pk["bf16_flops_per_s"])
    return 100.0 * executions * per_block * least / (sum(e.dur_ns for e in events) / 1e9)


def step_mfu_pct(run: Any) -> float | None:
    """FLOPs the served tokens need (``nemotron_h_costs.served_flops``: the
    decode steps' from the device's counts — live row-steps are
    ``ssm_rows`` over the Mamba layers, attention's positions
    ``attn_full`` — the prompts' from the pieces the engine committed) over
    the whole iterations at the chip's bf16 peak: the share of the whole
    step."""
    segs, counts, whole = layers.prefill_segments(run), step_counts(run), _whole(run)
    if counts is None or segs is None or whole is None:
        return None
    row_steps = counts["ssm_rows"] // costs.layer_counts(run.config)["mamba"]
    flops = costs.served_flops(run.config, segs, row_steps, counts["attn_full"])
    return 100.0 * flops / ((whole[1] - whole[0]) / 1e9 * peaks.peaks_for(run.device_kind)["bf16_flops_per_s"])


def decode_step_ms(run: Any) -> float | None:
    """Device time of one decode step, in whichever program holds it (as
    ``step.decode_ms.think`` reads it): in every execution of
    ``decode_block_paged`` or ``ragged_step_paged`` that lies whole in the
    traced sub-window, the outermost ops around a ``paged_kv_append`` (the
    loop over the block's steps; a ragged dispatch's chunk is beside it,
    not in it), over the steps."""
    if run.traced_ns() is None or not _is_nemotron(run) or not trace_reduce.device_planes(run.events):
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


def rows_per_expert(run: Any) -> float | None:
    """Mean rows a held expert takes in one decode step of one expert
    layer, over the blocks committed in the sub-window's whole iterations."""
    counts = step_counts(run)
    if counts is None:
        return None
    calls = counts["blocks"] * layers.block_steps(run) * costs.layer_counts(run.config)["moe"]
    return counts["moe_rows"] / (calls * int(run.config["n_routed_experts"]))
