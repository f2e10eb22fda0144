"""The reductions the ``deepseek_v32`` cell's per-layer readers share: from
a run's records, the engine's spans and the device trace to one number.
Each reader under ``benchmarks/layer_metrics/`` is a few lines over these.
A function that finds nothing to read — another architecture's
configuration, a program without the counters, no device plane — returns
None, never 0, and does not raise.

What the program writes, and what is read here (docs/observability.md):
``gofr.step.commit`` carries ``dsa_scored`` and ``dsa_selected``
(positions the indexer scored and positions attention read, over the
block's decode steps, rows and layers) beside ``moe_rows`` and ``moe_max``
(row-expert pairs the held experts took, and the fullest expert's). On
the device trace an ``XLA Ops`` event is named by its HLO instruction's
text and carries three stats — ``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier`` (dumped on the v5e, PR
33) — so neither a named scope nor a jitted wrapper's name reaches a
reader: only a Mosaic call is named after its wrapper. The decode STEPS
are therefore found by the one kernel they run and a chunk does not
(``paged_kv_append``: the outermost op around it is the loop over the
steps), and the XLA fusions inside them by the shapes only they have
among an instruction's result and operands (looked at in the program
compiled for the v5e, PR 33, at 32 rows x 4,096 positions):

- sparse attention (``ops/mla.sparse_decode_attention``): the gather of
  the selected latent rows ``[rows*topk, 640]``, the scores ``[rows,
  heads, topk]`` and the products around them;
- the indexer and the selection (``ops/mla.paged_index_scores``, the
  top-k): the gather of a row's pages of keys ``[rows*pages, page, 128]``,
  the scores ``[rows, index heads, context]`` and ``[rows, context]``, the
  sort of the latter;
- the expert products: fusions that read a stack of int8 experts
  ``s8[layers*held, D, F]`` and a block of ``rows`` activations.

A chunk's work in the ragged program has the chunk's length where a decode
step has ``rows``, so none of it is counted.
"""

from __future__ import annotations

from typing import Any, Iterable

from benchmarks.harness import deepseek_v32_costs as costs
from benchmarks.harness import host_spans, layers, peaks, trace_reduce

_KEYS = ("index_topk", "index_n_heads", "kv_lora_rank", "n_routed_experts", "n_shared_experts", "n_group")


def _is_dsa(run: Any) -> bool:
    return all(k in run.config for k in _KEYS)


def decode_contexts(run: Any) -> list[int] | None:
    """Positions the query of each token decoded in the traced sub-window
    saw: the j-th output (j from 2) of a prompt of P tokens came from a
    step whose row held P + j - 1 positions."""
    if run.traced is None:
        return None
    a, b = run.traced
    out = []
    for r in run.records:
        p = int(r["prompt_tokens"])
        out += [p + j - 1 for j, t in enumerate(r.get("token_ts") or (), start=1) if j > 1 and a <= t < b]
    return out


def step_mfu_pct(run: Any) -> float | None:
    """FLOPs the served tokens need on this chip's share over the
    sub-window at the chip's bf16 peak (int8 weights are dequantised into
    bf16 products)."""
    segs, seconds = layers.prefill_segments(run), layers.traced_seconds(run)
    if not _is_dsa(run) or segs is None or not seconds or not trace_reduce.device_planes(run.events):
        return None
    flops = costs.served_flops(run.config, segs, decode_contexts(run) or [])
    return 100.0 * flops / (seconds * peaks.peaks_for(run.device_kind)["bf16_flops_per_s"])


def _moe_commits(run: Any) -> list[Any]:
    if not _is_dsa(run):
        return []
    return [s for s in host_spans.spans(run) or () if s.phase == "commit" and "moe_rows" in s.kw]


def rows_per_expert(run: Any) -> float | None:
    """Mean rows a held expert takes in one decode step of one expert
    layer, over the blocks committed in the sub-window's whole
    iterations (``cohere2_moe_layers.rows_per_expert`` by this family's
    keys)."""
    found = _moe_commits(run)
    if not found:
        return None
    calls = len(found) * layers.block_steps(run) * costs.layer_counts(run.config)[1]
    return sum(s.kw["moe_rows"] for s in found) / (calls * int(run.config["n_routed_experts"]))


def load_imbalance(run: Any) -> float | None:
    """The fullest held expert's rows over the mean held expert's, block
    by block and weighted by the blocks' rows: 1 is even."""
    found = [s for s in _moe_commits(run) if s.kw["moe_rows"]]
    if not found:
        return None
    return sum(s.kw["moe_max"] for s in found) / (sum(s.kw["moe_rows"] for s in found) / int(run.config["n_routed_experts"]))


APPEND_KERNEL = "paged_kv_append"  # the Mosaic call a decode step makes in every layer, and a chunk never


def decode_step_ms(run: Any) -> float | None:
    """Device time of one decode step, in whichever program holds it: in
    every execution of ``decode_block_paged`` or ``ragged_step_paged``
    that lies whole in the traced sub-window, the outermost ops around a
    ``paged_kv_append`` (the loop over the block's steps; a ragged
    dispatch's chunk is beside it, not in it), over the steps."""
    span = run.traced_ns()
    if span is None or not _is_dsa(run) or not trace_reduce.device_planes(run.events):
        return None
    device = [e for e in run.events if trace_reduce.is_device_plane(e.plane)]
    whole = sorted((e.start_ns, e.start_ns + e.dur_ns) for e in device
                   if e.line == trace_reduce.MODULE_LINE and span[0] <= e.start_ns and e.start_ns + e.dur_ns <= span[1]
                   and trace_reduce.program_name(e.name) in (layers.DECODE_PROGRAM, layers.RAGGED_PROGRAM))
    ops = sorted((e for e in device if e.line == trace_reduce.OPS_LINE), key=lambda e: (e.start_ns, -e.dur_ns))
    total, executions = 0, 0
    for a, b in whole:
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


def selected_share_pct(run: Any) -> float | None:
    """Of the positions the indexer scored, the share attention read: the
    commit spans' ``dsa_selected`` over ``dsa_scored``."""
    found = [s for s in host_spans.spans(run) or () if s.phase == "commit" and s.kw.get("dsa_scored")]
    if not _is_dsa(run) or not found:
        return None
    return 100.0 * sum(s.kw["dsa_selected"] for s in found) / sum(s.kw["dsa_scored"] for s in found)


# ------------------------------------------------------------ device events
def _shapes(run: Any) -> dict[str, int]:
    c, e = run.config, run.cell["engine"]
    rows, context, page = int(e["max_slots"]), int(e["max_seq_len"]), int(e.get("kv_page_size", 16))
    context = -(-context // page) * page
    row_width = costs.cache_row_bytes(c)[0] // 2
    return {"B": rows, "S": context, "page": page, "M": context // page, "K": min(int(c["index_topk"]), context),
            "W": row_width, "H": int(c["num_attention_heads"]), "Rkv": int(c["kv_lora_rank"]),
            "Hi": int(c["index_n_heads"]), "Di": int(c["index_head_dim"])}


def sparse_attention_marks(run: Any) -> tuple[str, ...]:
    s = _shapes(run)
    return (f"[{s['B'] * s['K']},{s['W']}]", f"[{s['B']},{s['K']},{s['W']}]", f"[{s['B']},{s['H']},{s['K']}]",
            f"[{s['B']},1,{s['H']},{s['K']}]", f"[{s['B']},{s['K']},{s['Rkv']}]", f"s32[{s['B'] * s['K']}]")


def indexer_marks(run: Any) -> tuple[str, ...]:
    s = _shapes(run)
    return (f"[{s['B'] * s['M']},{s['page']},{s['Di']}]", f"[{s['B']},{s['S']},{s['Di']}]",
            f"[{s['B']},{s['Hi']},{s['S']}]", f"[{s['B']},1,{s['Hi']},{s['S']}]", f"[{s['B']},{s['S']}]")


def expert_marks(run: Any) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(how a stack of int8 experts reads among a fusion's operands, how
    a decode step's block of activations does)."""
    c = run.config
    _, sparse = costs.layer_counts(c)
    d, f, rows = int(c["hidden_size"]), int(c["moe_intermediate_size"]), int(run.cell["engine"]["max_slots"])
    stacks = []
    for n in (int(c["n_routed_experts"]), int(c["n_shared_experts"])):
        for a, b in ((d, f), (f, d)):
            stacks += [f"s8[{sparse * n},{a},{b}]", f"s8[{sparse},{n},{a},{b}]"]
    return tuple(dict.fromkeys(stacks)), (f"[{rows},{d}]", f"[{rows},{f}]")


def _step_events(run: Any) -> list[Any] | None:
    """Leaf events of the ``XLA Ops`` line in the traced sub-window that
    started inside an execution of a program that holds decode steps. A
    ``while`` around the layers names every shape in its tuple and is no
    leaf."""
    span = run.traced_ns()
    if span is None or not _is_dsa(run) or not trace_reduce.device_planes(run.events):
        return None
    if "dsa_step_events" not in run.cache:
        inside = sorted((e.start_ns, e.start_ns + e.dur_ns) for e in run.events
                        if e.line == trace_reduce.MODULE_LINE and trace_reduce.is_device_plane(e.plane)
                        and trace_reduce.program_name(e.name) in (layers.DECODE_PROGRAM, layers.RAGGED_PROGRAM))
        out = []
        for e in trace_reduce.clip((e for e in run.events if e.line == trace_reduce.OPS_LINE
                                    and trace_reduce.is_device_plane(e.plane)), *span):
            op = e.name.split(" = ", 1)[-1]
            if any(f" {kind}(" in op for kind in ("while", "conditional", "call")):
                continue
            if any(a <= e.start_ns < b for a, b in inside):
                out.append(e)
        run.cache["dsa_step_events"] = out
    return run.cache["dsa_step_events"]


def marked_events(run: Any, marks: Iterable[str]) -> list[Any] | None:
    events = _step_events(run)
    if events is None:
        return None
    marks = tuple(marks)
    return [e for e in events if any(m in e.name for m in marks)]


def _roofline(run: Any, events: list[Any] | None, needed_bytes: float, needed_flops: float) -> float | None:
    if not events or not (needed_bytes or needed_flops):
        return None
    pk = peaks.peaks_for(run.device_kind)
    least = max(needed_bytes / pk["hbm_bytes_per_s"], needed_flops / pk["bf16_flops_per_s"])
    return 100.0 * least / (sum(e.dur_ns for e in events) / 1e9)


def sparse_attention_roofline_pct(run: Any) -> float | None:
    """Least time the chip could take for the decode steps' attention —
    per decoded token and layer the selected latent rows as stored against
    every head's scores and sums over them — over the device time of
    whatever implements it."""
    contexts = decode_contexts(run) if _is_dsa(run) else None
    if not contexts:
        return None
    read = costs.selected(run.config, contexts)
    return _roofline(run, marked_events(run, sparse_attention_marks(run)),
                     costs.sparse_attention_bytes(run.config, read), costs.sparse_attention_flops(run.config, read))


def indexer_roofline_pct(run: Any) -> float | None:
    """Least time for the decode steps' indexer — one key a position of
    the context, every index head's score of it — over the device time of
    the scores and of the selection (the top-k's sort is in the time and
    not in the work)."""
    contexts = decode_contexts(run) if _is_dsa(run) else None
    if not contexts:
        return None
    scored = sum(contexts)
    return _roofline(run, marked_events(run, indexer_marks(run)),
                     costs.indexer_bytes(run.config, scored), costs.indexer_flops(run.config, scored))


def expert_product_events(run: Any) -> list[Any] | None:
    events = _step_events(run)
    if events is None:
        return None
    stacks, blocks = expert_marks(run)
    return [e for e in events if " fusion(" in e.name and any(m in e.name for m in stacks)
            and any(m in e.name for m in blocks)]


def experts_roofline_pct(run: Any) -> float | None:
    """Least time for the decode steps' expert products — per layer and
    step the int8 bytes of the held experts the batch is expected to reach
    and of the shared expert, against the FLOPs of its routed row-expert
    pairs — over their device time. A call (one expert layer of one step)
    is three products an expert, held and shared."""
    events, found = expert_product_events(run), host_spans.blocks(run)
    if not events or not found:
        return None
    c = run.config
    calls = len(events) / (3 * (int(c["n_routed_experts"]) + int(c["n_shared_experts"])))
    rows = sum(s.kw["rows"] * s.kw["steps"] for s in found) / sum(s.kw["steps"] for s in found)
    return _roofline(run, events, calls * costs.expert_call_bytes(c, rows), calls * costs.expert_call_flops(c, rows))
