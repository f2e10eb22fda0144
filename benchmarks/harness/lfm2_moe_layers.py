"""The reductions the ``lfm2_moe`` cell's per-layer readers share: from a
run's records, the engine's spans and the device trace to one number. Each
reader under ``benchmarks/layer_metrics/`` is a few lines over these. A
function that finds nothing to read — another architecture's
configuration, a program without the counters, no device plane — returns
None, never 0, and does not raise.

What the program writes, and what is read here (docs/observability.md):
``gofr.step.commit`` carries, over a block's decode steps, COUNTED ON THE
DEVICE: ``conv_rows`` (row-steps whose conv tails advanced, summed over
the 18 conv layers that ran: 18 x the live row-steps), ``attn_kv``
(positions the 6 attention layers read), ``moe_rows`` (row-expert pairs
routed), ``moe_max`` (the fullest expert's) and ``moe_reached`` (experts
whose matrices were read, summed over the expert layers). The work of the
shares is taken from these and not from the client's token stamps (PR 35's
lesson: under the profiler the client's stamps come late), over the
sub-window's whole loop iterations (``host_spans``).

On the device trace an XLA fusion is named by its HLO instruction's text
only, so a layer's ops are found by shapes only that layer has among an
instruction's result and operands: the stacked int8 experts
(``s8[22*32,2048,1792]`` and the down matrices' ``s8[22*32,1792,2048]``,
one dynamic slice a matrix — ``ops/moe.held_experts`` — and their scales
``f32[22*32,1792]``, ``f32[22*32,2048]``), the conv mixers' W_in and W_out
stacks (``s8[18,2048,6144]``, ``s8[18,2048,2048]``), their tails
(``f32[18,rows,2,2048]`` and one layer's), what a step's conv sees
(``f32[rows,3,2048]``) and W_in's product (``f32[rows,1,6144]``; looked
at in the v5e's trace, PR 39). A ``while`` or ``conditional`` around the layers
names every shape in its tuple and is no leaf. An op that carries such a
shape beside other work is counted whole, so a share reads low rather
than high. The expert and conv shares are taken in the executions of
``decode_block_paged`` alone: a ragged dispatch holds a chunk's products
beside its steps.
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness import host_spans, layers, peaks, trace_reduce
from benchmarks.harness import lfm2_moe_costs as costs
from benchmarks.harness.phi4flash_layers import APPEND_KERNEL, _device_ops, _executions

COUNTERS = ("conv_rows", "attn_kv", "moe_rows", "moe_max", "moe_reached")


def _is_lfm2(run: Any) -> bool:
    return run.config.get("model_type") == "lfm2_moe"


def _commits(run: Any) -> list[Any]:
    found = host_spans.spans(run) if _is_lfm2(run) else None
    return [s for s in found or () if s.phase == "commit" and "conv_rows" in s.kw]


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
    if not _is_lfm2(run) or not trace_reduce.device_planes(run.events):
        return None
    return host_spans.whole_iterations(run)


def step_mfu_pct(run: Any) -> float | None:
    """FLOPs the served tokens need (``lfm2_moe_costs.served_flops``: the
    decode steps' from the device's counts — live row-steps are
    ``conv_rows`` over the conv layers — the prompts' from the pieces the
    engine committed) over the whole iterations at the chip's bf16 peak
    (int8 weights are dequantised into bf16 products): the share of the
    whole step."""
    segs, counts, whole = layers.prefill_segments(run), step_counts(run), _whole(run)
    if counts is None or segs is None or whole is None:
        return None
    row_steps = counts["conv_rows"] // costs.layer_counts(run.config)["conv"]
    flops = costs.served_flops(run.config, segs, row_steps, counts["attn_kv"])
    return 100.0 * flops / ((whole[1] - whole[0]) / 1e9 * peaks.peaks_for(run.device_kind)["bf16_flops_per_s"])


def decode_step_ms(run: Any) -> float | None:
    """Device time of one decode step, in whichever program holds it (as
    ``step.decode_ms.reason`` reads it): in every execution of
    ``decode_block_paged`` or ``ragged_step_paged`` that lies whole in the
    traced sub-window, the outermost ops around a ``paged_kv_append`` (the
    loop over the block's steps; a ragged dispatch's chunk is beside it,
    not in it), over the steps."""
    if run.traced_ns() is None or not _is_lfm2(run) or not trace_reduce.device_planes(run.events):
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
    attention had to — ``attn_kv`` layer-positions of K and V, 2 x 8 heads
    x 64 x 2 B each — over the device time of the events named
    ``paged_decode_attention.<n>`` in the whole iterations (a chunk reads
    its row's pages through XLA, not the kernel)."""
    counts, whole = step_counts(run), _whole(run)
    if counts is None or whole is None:
        return None
    kernel_s = sum(v["seconds"] for k, v in trace_reduce.op_times(run.events, *whole).items()
                   if k.split(".")[0] == layers.PAGED_KERNEL)
    read = counts["attn_kv"] * costs.kv_bytes_per_position(run.config)
    if not kernel_s or not read:
        return None
    return 100.0 * read / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"] / kernel_s


def expert_marks(run: Any) -> tuple[str, ...]:
    """How the stacked experts read among an instruction's shapes: each
    int8 matrix kind flat over the layers (``held_experts``' one dynamic
    slice) or as [layers, experts, ...], and their scales flat (the
    slices of a step's scale vectors, 18 at a time on the v5e)."""
    c = run.config
    n, e, d, f = costs.layer_counts(c)["moe"], int(c["num_experts"]), int(c["hidden_size"]), int(c["moe_intermediate_size"])
    return (tuple(m for a, b in ((d, f), (f, d)) for m in (f"s8[{n * e},{a},{b}]", f"s8[{n},{e},{a},{b}]"))
            + (f"f32[{n * e},{f}]", f"f32[{n * e},{d}]"))


def conv_marks(run: Any) -> tuple[str, ...]:
    """How the conv mixers read among an instruction's shapes: their W_in
    and W_out stacks, their tails (all layers', one layer's), the inputs a
    step's conv sees (the tail and the new value) and W_in's product."""
    c = run.config
    n, d, w = costs.layer_counts(c)["conv"], int(c["hidden_size"]), int(c["conv_L_cache"]) - 1
    rows = int(run.cell["engine"]["max_slots"])
    return (f"s8[{n},{d},{3 * d}]", f"s8[{n},{d},{d}]", f"f32[{n},{rows},{w},{d}]", f"f32[{rows},{w},{d}]",
            f"f32[1,{rows},{w},{d}]", f"f32[{rows},{w + 1},{d}]", f"f32[{rows},1,{3 * d}]")


def _decode_executions(run: Any, span: tuple[int, int]) -> list[tuple[int, int]]:
    """(start, end) of the executions of ``decode_block_paged`` that lie
    whole in ``span``."""
    return sorted((e.start_ns, e.start_ns + e.dur_ns) for e in run.events
                  if trace_reduce.is_device_plane(e.plane) and e.line == trace_reduce.MODULE_LINE
                  and trace_reduce.program_name(e.name) == layers.DECODE_PROGRAM
                  and span[0] <= e.start_ns and e.start_ns + e.dur_ns <= span[1])


def marked_events(run: Any, marks: tuple[str, ...]) -> tuple[list[Any], int] | None:
    """Leaf ops inside the executions of ``decode_block_paged`` that lie
    whole in the whole iterations and carry one of ``marks`` in their
    result or operands; and how many executions they came from."""
    span = _whole(run)
    if span is None:
        return None
    inside = _decode_executions(run, span)
    out = []
    for e in _device_ops(run):
        op = e.name.split(" = ", 1)[-1]
        if any(f" {kind}(" in op for kind in ("while", "conditional", "call")):
            continue
        if any(m in e.name for m in marks) and any(a <= e.start_ns < b for a, b in inside):
            out.append(e)
    return out, len(inside)


def experts_roofline_pct(run: Any) -> float | None:
    """Least time for the expert products of the decode steps timed — per
    layer and step the larger of the bytes of the experts REACHED (the
    commit spans' ``moe_reached`` a layer-step: int8 matrices and their
    scales) over the HBM rate and the FLOPs of the row-experts ROUTED
    (``moe_rows``) over the bf16 peak — over the device time of the ops
    that read an expert stack. The counts are the committed blocks' mean
    a layer-step, applied to the ``decode_block_paged`` executions timed."""
    found, counts = marked_events(run, expert_marks(run)) if _is_lfm2(run) else None, step_counts(run)
    if not found or not found[0] or not found[1] or counts is None:
        return None
    events, executions = found
    steps = layers.block_steps(run)
    per_block = costs.layer_counts(run.config)["moe"] * steps
    calls = executions * per_block
    reached, rows = counts["moe_reached"] / (counts["blocks"] * per_block), counts["moe_rows"] / (counts["blocks"] * per_block)
    pk = peaks.peaks_for(run.device_kind)
    least = max(reached * costs.expert_bytes(run.config) / pk["hbm_bytes_per_s"],
                costs.expert_flops(run.config, rows) / pk["bf16_flops_per_s"])
    return 100.0 * calls * least / (sum(e.dur_ns for e in events) / 1e9)


def conv_roofline_pct(run: Any) -> float | None:
    """Least time for what the conv mixers of the decode steps timed had
    to move — W_in and W_out with their scales once a layer and step, a
    live row's tail read and written and its activation in and out — over
    the device time of the ops that carry a conv mixer's shape. The live
    rows are the committed blocks' mean ``conv_rows`` a block, applied to
    the ``decode_block_paged`` executions timed."""
    found, counts = marked_events(run, conv_marks(run)) if _is_lfm2(run) else None, step_counts(run)
    if not found or not found[0] or not found[1] or counts is None:
        return None
    events, executions = found
    layer_steps = executions * layers.block_steps(run) * costs.layer_counts(run.config)["conv"]
    live = executions * counts["conv_rows"] / counts["blocks"]
    least = costs.conv_bytes(run.config, live, layer_steps) / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (sum(e.dur_ns for e in events) / 1e9)


def rows_per_expert(run: Any) -> float | None:
    """Mean rows an expert takes in one decode step of one expert layer,
    over the blocks committed in the sub-window's whole iterations."""
    counts = step_counts(run)
    if counts is None:
        return None
    calls = counts["blocks"] * layers.block_steps(run) * costs.layer_counts(run.config)["moe"]
    return counts["moe_rows"] / (calls * int(run.config["num_experts"]))


def load_imbalance(run: Any) -> float | None:
    """The fullest expert's rows over the mean expert's, block by block
    and weighted by the blocks' rows: 1 is even."""
    found = [s for s in _commits(run) if s.kw.get("moe_rows")]
    if not found:
        return None
    n = int(run.config["num_experts"])
    return sum(s.kw["moe_max"] for s in found) / (sum(s.kw["moe_rows"] for s in found) / n)
