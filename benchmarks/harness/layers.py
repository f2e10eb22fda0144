"""The reductions the per-layer readers share: from a run's records,
spans, counters and trace to one number. Each reader under
``benchmarks/layer_metrics/`` is a few lines over these. A function that
finds nothing to read returns None, never 0.
"""

from __future__ import annotations

from typing import Any

from benchmarks.harness import costs, peaks, stats, trace_reduce

DECODE_PROGRAM = "decode_block_paged"
RAGGED_PROGRAM = "ragged_step_paged"
# how the Mosaic paged-attention kernel appears on the trace's XLA Ops
# line (looked at by hand, PR 25): a custom-call instruction named
# paged_decode_attention.<n> after the jitted function that wraps the
# pallas_call, which carries no name= of its own
PAGED_KERNEL = "paged_decode_attention"


def block_steps(run: Any) -> int:
    return int(run.cell["engine"].get("multi_step") or 4)


def _programs(run: Any) -> dict[str, dict[str, float]] | None:
    span = run.traced_ns()
    if span is None or not trace_reduce.device_planes(run.events):
        return None
    if "programs" not in run.cache:
        run.cache["programs"] = trace_reduce.program_times(run.events, *span)
    return run.cache["programs"]


def _counts(run: Any) -> dict[str, int] | None:
    if run.traced is None:
        return None
    if "counts" not in run.cache:
        run.cache["counts"] = stats.window_tokens(run.records, *run.traced)
    return run.cache["counts"]


def traced_seconds(run: Any) -> float | None:
    span = run.traced_ns()
    return None if span is None else (span[1] - span[0]) / 1e9


def decode_steps(run: Any) -> int | None:
    """Decode steps the device ran in the traced sub-window: executions of
    the decode-block and ragged programs times the block's step count."""
    progs = _programs(run)
    if progs is None:
        return None
    n = sum(int(progs.get(p, {}).get("count", 0)) for p in (DECODE_PROGRAM, RAGGED_PROGRAM))
    return n * block_steps(run) or None


def batch_occupancy_pct(run: Any) -> float | None:
    steps, counts = decode_steps(run), _counts(run)
    if not steps or counts is None:
        return None
    return 100.0 * counts["decode_tokens"] / (steps * int(run.cell["engine"]["max_slots"]))


def page_fill_pct(run: Any) -> float | None:
    fills = []
    for p in run.health_polls:
        kv = p.get("kv_pages")
        if kv and kv.get("total_blocks") and run.window[0] <= p["t"] < run.window[1]:
            fills.append(100.0 * (kv["total_blocks"] - kv["free_blocks"]) / kv["total_blocks"])
    return max(fills) if fills else None


def decode_ms(run: Any) -> float | None:
    progs = _programs(run)
    if progs is None or not progs.get(DECODE_PROGRAM, {}).get("count"):
        return None
    p = progs[DECODE_PROGRAM]
    return 1e3 * p["seconds"] / (p["count"] * block_steps(run))


def prefill_segments(run: Any) -> list[tuple[int, int]] | None:
    """(start, tokens) of every piece of prompt the engine committed in
    the traced sub-window: the chunks the flight recorder stamped (on the
    host's clock, through each timeline's wall-clock birth), or, for a
    bucketed prefill, the whole prompt at its first token."""
    if run.traced is None:
        return None
    a, b = run.traced
    wall_minus_mono = run.wall_minus_mono
    out: list[tuple[int, int]] = []
    for r in run.records:
        z = run.requestz.get(r.get("request_id"))
        chunks = (z or {}).get("prefill_chunks")
        if chunks and wall_minus_mono is not None:
            born = z["created_unix"] - wall_minus_mono
            for c in chunks:
                if not c.get("prefix_hit") and a <= born + c["ms"] / 1e3 < b:
                    out.append((int(c["start"]), int(c["tokens"])))
        else:
            ts = r.get("token_ts") or ()
            if ts and a <= ts[0] < b:
                out.append((0, int(r["prompt_tokens"])))
    return out


def step_mfu_pct(run: Any) -> float | None:
    """FLOPs the model needs for the tokens the sub-window served, over
    the sub-window at the chip's bf16 peak (int8 weights are dequantised
    into bf16 products). Masked or recomputed positions do not count."""
    counts, segs, seconds = _counts(run), prefill_segments(run), traced_seconds(run)
    if counts is None or segs is None or not seconds or _programs(run) is None:
        return None
    flops = costs.served_flops(run.config, segs, counts["decode_tokens"], counts["resident_positions"])
    return 100.0 * flops / (seconds * peaks.peaks_for(run.device_kind)["bf16_flops_per_s"])


def paged_kernel_seconds(run: Any) -> float | None:
    span = run.traced_ns()
    if span is None:
        return None
    if "ops" not in run.cache:
        run.cache["ops"] = trace_reduce.op_times(run.events, *span)
    total = sum(v["seconds"] for k, v in run.cache["ops"].items()
                if k.split(".")[0] == PAGED_KERNEL)
    return total or None


def paged_attention_roofline_pct(run: Any) -> float | None:
    """Least time the chip could take for what the kernel's calls had to
    read and compute (exact resident lengths), over the kernel's device
    time. Bandwidth-bound at these shapes: 4 (Mistral) or 1 (DeepSeek)
    FLOP a byte against the chip's 240."""
    counts, kernel_s = _counts(run), paged_kernel_seconds(run)
    if counts is None or not kernel_s or not counts["resident_positions"]:
        return None
    pk = peaks.peaks_for(run.device_kind)
    least = max(costs.paged_attention_bytes(run.config, counts["resident_positions"]) / pk["hbm_bytes_per_s"],
                costs.paged_attention_flops(run.config, counts["resident_positions"]) / pk["bf16_flops_per_s"])
    return 100.0 * least / kernel_s


def idle_share_pct(run: Any) -> float | None:
    span = run.traced_ns()
    if span is None or not trace_reduce.device_planes(run.events):
        return None
    busy = trace_reduce.busy_seconds(run.events, *span)
    return 100.0 * (1.0 - busy / ((span[1] - span[0]) / 1e9))
