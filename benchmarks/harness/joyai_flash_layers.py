"""The reductions the ``joyai.think`` cell's per-layer readers share: from a
run's records, the engine's spans and the device trace to one number. Each
reader under ``benchmarks/layer_metrics/`` is a few lines over these. A
function that finds nothing to read — another architecture's
configuration, a program without the counters, no device plane — returns
None, never 0, and does not raise.

What the program writes, and what is read here (docs/observability.md):
``gofr.step.commit`` carries, over a block's decode steps, COUNTED ON THE
DEVICE: ``mla_kv`` (latent positions the decode attention read, summed
over the live rows and the 13 layers), ``mla_rows`` (the live row-steps,
summed over the layers), ``moe_rows`` (row-expert pairs routed to the held
experts), ``moe_max`` (the fullest expert's) and ``moe_reached`` (held
experts whose matrices were read, summed over the expert layers). The work
of the shares is taken from these, over the sub-window's whole loop
iterations (``host_spans``), as ``lfm2_moe_layers`` takes its own.

On the device trace a Mosaic call is named after its jitted wrapper: the
decode attention is ``%paged_latent_attention.<n>`` (a chunk reads its
row's pages through XLA, not the kernel) and the every-row expert sum
``%expert_rows.<n>``, one call for the 32 held experts' stacks
(``s8[12,32,2048,768]``, ``s8[12,32,768,2048]``) and one for the shared
expert's (``s8[12,1,...]``); the routed call is found by its stacks'
shapes. An XLA fusion is named by its instruction's text only, so a shape
is the only mark it has; the expert share is taken in the executions of
``decode_block_paged`` alone, as ``lfm2_moe_layers`` takes it: a ragged
dispatch holds a chunk's grouped products beside its steps.
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness import host_spans, layers, peaks, trace_reduce
from benchmarks.harness import joyai_flash_costs as costs
from benchmarks.harness.lfm2_moe_layers import _decode_executions
from benchmarks.harness.phi4flash_layers import APPEND_KERNEL, _device_ops, _executions

COUNTERS = ("mla_kv", "mla_rows", "moe_rows", "moe_max", "moe_reached")
LATENT_KERNEL = "paged_latent_attention"  # the decode attention's Mosaic call


def _is_joyai(run: Any) -> bool:
    return run.config.get("model_type") == "joyai_llm_flash"


def _commits(run: Any) -> list[Any]:
    found = host_spans.spans(run) if _is_joyai(run) else None
    return [s for s in found or () if s.phase == "commit" and "mla_kv" in s.kw]


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
    if not _is_joyai(run) or not trace_reduce.device_planes(run.events):
        return None
    return host_spans.whole_iterations(run)


def latent_attention_roofline_pct(run: Any) -> float | None:
    """Least time the chip could take to read what the decode steps'
    attention had to — ``mla_kv`` layer-positions of 576 bf16 values,
    1,152 B each (the pad of the stored row to 640 counts against the
    kernel) — at the HBM rate, over the device time of the
    ``paged_latent_attention.<n>`` calls in the whole iterations."""
    counts, whole = step_counts(run), _whole(run)
    if counts is None or whole is None:
        return None
    kernel_s = sum(v["seconds"] for k, v in trace_reduce.op_times(run.events, *whole).items()
                   if k.split(".")[0] == LATENT_KERNEL)
    read = costs.latent_read_bytes(run.config, counts["mla_kv"])
    if not kernel_s or not read:
        return None
    return 100.0 * read / peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"] / kernel_s


def step_mfu_pct(run: Any) -> float | None:
    """FLOPs the served tokens need (``joyai_flash_costs.served_flops``:
    the decode steps' from the device's counts — live row-steps are
    ``mla_rows`` over the layers, attention's positions ``mla_kv`` — the
    prompts' from the pieces the engine committed) over the whole
    iterations at the chip's bf16 peak: the share of the whole step."""
    segs, counts, whole = layers.prefill_segments(run), step_counts(run), _whole(run)
    if counts is None or segs is None or whole is None:
        return None
    row_steps = counts["mla_rows"] // int(run.config["num_hidden_layers"])
    flops = costs.served_flops(run.config, segs, row_steps, counts["mla_kv"])
    return 100.0 * flops / ((whole[1] - whole[0]) / 1e9 * peaks.peaks_for(run.device_kind)["bf16_flops_per_s"])


def decode_step_ms(run: Any) -> float | None:
    """Device time of one decode step, in whichever program holds it (as
    ``step.decode_ms.long`` reads it): in every execution of
    ``decode_block_paged`` or ``ragged_step_paged`` that lies whole in the
    traced sub-window, the outermost ops around a ``paged_kv_append`` (the
    loop over the block's steps; a ragged dispatch's chunk is beside it,
    not in it), over the steps."""
    if run.traced_ns() is None or not _is_joyai(run) or not trace_reduce.device_planes(run.events):
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


def expert_marks(run: Any) -> tuple[str, ...]:
    """How the held routed experts' int8 stacks read among an
    instruction's operands: each matrix kind as [layers, experts, ...] (the
    kernel's operands) or flat over the layers (one dynamic slice a
    matrix, the loop's)."""
    c = run.config
    _, n = costs.layer_counts(c)
    e, d, f = int(c["n_routed_experts"]), int(c["hidden_size"]), int(c["moe_intermediate_size"])
    return tuple(m for a, b in ((d, f), (f, d)) for m in (f"s8[{n},{e},{a},{b}]", f"s8[{n * e},{a},{b}]"))


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
    """Least time for the held routed experts' products of the decode
    steps timed — per layer and step the larger of the bytes of the
    experts REACHED (``moe_reached`` a layer-step: int8 matrices and their
    scales) over the HBM rate and the FLOPs of the row-experts ROUTED
    (``moe_rows``) over the bf16 peak — over the device time of the ops
    that carry the held experts' stacks. The counts are the committed
    blocks' mean a layer-step, applied to the ``decode_block_paged``
    executions timed."""
    found, counts = marked_events(run, expert_marks(run)) if _is_joyai(run) else None, step_counts(run)
    if not found or not found[0] or not found[1] or counts is None:
        return None
    events, executions = found
    per_block = costs.layer_counts(run.config)[1] * layers.block_steps(run)
    reached = counts["moe_reached"] / (counts["blocks"] * per_block)
    rows = counts["moe_rows"] / (counts["blocks"] * per_block)
    pk = peaks.peaks_for(run.device_kind)
    least = max(reached * costs.expert_bytes(run.config) / pk["hbm_bytes_per_s"],
                costs.expert_flops(run.config, rows) / pk["bf16_flops_per_s"])
    return 100.0 * executions * per_block * least / (sum(e.dur_ns for e in events) / 1e9)


def rows_per_expert(run: Any) -> float | None:
    """Mean rows a held expert takes in one decode step of one expert
    layer, over the blocks committed in the sub-window's whole iterations."""
    counts = step_counts(run)
    if counts is None:
        return None
    calls = counts["blocks"] * layers.block_steps(run) * costs.layer_counts(run.config)[1]
    return counts["moe_rows"] / (calls * int(run.config["n_routed_experts"]))
