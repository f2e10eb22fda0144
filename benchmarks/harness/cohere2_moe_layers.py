"""The reductions the ``cohere2_moe`` cells' per-layer readers share: from
a run's records, the engine's spans and the device trace to one number.
Each reader under ``benchmarks/layer_metrics/`` is a few lines over these.
A function that finds nothing to read — another architecture's
configuration, a program without the counters, no device plane — returns
None, never 0, and does not raise.

What the program writes, and what is read here (docs/observability.md):
``gofr.step.commit`` carries ``moe_rows`` (row-expert pairs the held
experts took over the block's decode steps and layers) and ``moe_max``
(the fullest held expert's). On the device trace the expert products are
the fusions whose instruction text has the stacked int8 experts among its
operands: ``s8[L*held,D,F]`` (or ``s8[L,held,D,F]``) for the routed ones,
``s8[L*shared,D,F]`` for the shared ones — one fusion a matrix, three an
expert (looked at in the compiled program's text and on the v5e's trace,
PR 29: `%fusion.1575 = bf16[64,4096] fusion(s8[128,4096,4096] %bitcast…`,
24 µs a call; the `while` ops around the layers name the stacks too and
are left out).
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness import cohere2_moe_costs as costs
from benchmarks.harness import host_spans, layers, peaks, stats, trace_reduce


def _is_moe(run: Any) -> bool:
    return all(k in run.config for k in ("num_experts", "num_experts_per_tok", "num_shared_experts", "layer_types"))


def step_mfu_pct(run: Any) -> float | None:
    """FLOPs the served tokens need on this chip's share over the
    sub-window at the chip's bf16 peak (int8 weights are dequantised into
    bf16 products)."""
    segs, seconds = layers.prefill_segments(run), layers.traced_seconds(run)
    if not _is_moe(run) or segs is None or not seconds or not trace_reduce.device_planes(run.events):
        return None
    counts = stats.window_tokens(run.records, *run.traced)
    flops = costs.served_flops(run.config, segs, counts["decode_tokens"], counts["resident_positions"],
                               int(run.cell["engine"]["max_seq_len"]))
    return 100.0 * flops / (seconds * peaks.peaks_for(run.device_kind)["bf16_flops_per_s"])


def _commits(run: Any) -> list[Any]:
    return [s for s in host_spans.spans(run) or () if s.phase == "commit" and "moe_rows" in s.kw]


def rows_per_expert(run: Any) -> float | None:
    """Mean rows a held expert takes in one decode step of one layer,
    over the blocks committed in the sub-window's whole iterations."""
    found = _commits(run) if _is_moe(run) else []
    if not found:
        return None
    calls = len(found) * layers.block_steps(run) * int(run.config["num_hidden_layers"])
    return sum(s.kw["moe_rows"] for s in found) / (calls * int(run.config["num_experts"]))


def load_imbalance(run: Any) -> float | None:
    """The fullest held expert's rows over the mean held expert's, block
    by block and weighted by the blocks' rows: 1 is even."""
    found = [s for s in (_commits(run) if _is_moe(run) else []) if s.kw["moe_rows"]]
    if not found:
        return None
    held = int(run.config["num_experts"])
    return sum(s.kw["moe_max"] for s in found) / (sum(s.kw["moe_rows"] for s in found) / held)


def expert_operand_marks(c: dict[str, Any]) -> tuple[str, ...]:
    """How the stacked int8 experts read among a fusion's operands."""
    L, d, f = int(c["num_hidden_layers"]), int(c["hidden_size"]), int(c["intermediate_size"])
    marks = []
    for n in (int(c["num_experts"]), int(c["num_shared_experts"])):
        for a, b in ((d, f), (f, d)):
            marks += [f"s8[{L * n},{a},{b}]", f"s8[{L},{n},{a},{b}]"]
    return tuple(dict.fromkeys(marks))


def expert_product_events(run: Any) -> list[Any] | None:
    """The expert products of the decode program in the traced
    sub-window: events of the ``XLA Ops`` line that started inside an
    execution of ``decode_block_paged`` and read a stack of experts."""
    span = run.traced_ns()
    if span is None or not _is_moe(run):
        return None
    marks = expert_operand_marks(run.config)
    inside = sorted((e.start_ns, e.start_ns + e.dur_ns) for e in run.events
                    if e.line == trace_reduce.MODULE_LINE and trace_reduce.is_device_plane(e.plane)
                    and trace_reduce.program_name(e.name) == layers.DECODE_PROGRAM)
    out = []
    for e in trace_reduce.clip((e for e in run.events if e.line == trace_reduce.OPS_LINE
                                and trace_reduce.is_device_plane(e.plane)), *span):
        # a fusion's own operands: the while around the layers carries the
        # stacks in its tuple too, and is no product
        operands = e.name.split(" = ", 1)[-1]
        if " fusion(" in operands and any(m in operands for m in marks) and any(a <= e.start_ns < b for a, b in inside):
            out.append(e)
    return out


def experts_roofline_pct(run: Any) -> float | None:
    """Least time the chip could take for the decode steps' expert
    products — per layer and step the int8 bytes of the held experts the
    batch is expected to reach and of the shared experts, against the
    FLOPs of its row-expert pairs; bandwidth-bound at serving batch sizes
    — over their device time. A call (one layer of one step) is three
    products an expert, held and shared."""
    events, found = expert_product_events(run), host_spans.blocks(run)
    if not events or not found:
        return None
    c = run.config
    per_call = 3 * (int(c["num_experts"]) + int(c["num_shared_experts"]))
    calls = len(events) / per_call
    rows = sum(s.kw["rows"] * s.kw["steps"] for s in found) / sum(s.kw["steps"] for s in found)
    pk = peaks.peaks_for(run.device_kind)
    least = max(costs.expert_call_bytes(c, rows) / pk["hbm_bytes_per_s"],
                costs.expert_call_flops(c, rows) / pk["bf16_flops_per_s"])
    return 100.0 * calls * least / (sum(e.dur_ns for e in events) / 1e9)
