"""A builder's tool, not the benchmark: where one iteration of
``deepseekv32.long`` goes, from the device events a traced run kept
(``benchmarks/tools/trace_look.py`` writes them). For every whole
execution of ``ragged_step_paged``: its time, its top-level ops (the loop
over the block's decode steps is the ``while`` that holds the
``paged_kv_append`` calls, the scan over the rows' chunks the other large
one), and inside each of the two the time of the leaf ops that read a
stack of experts (an ``s8[...]`` operand of the experts' two shapes).

From a recording that also holds the engine's spans
(``benchmarks/tools/span_look.py`` writes those), the commit spans'
``moe_reached`` and ``moe_rows`` a decode step and expert layer: held
experts read, and row-expert pairs.

    python3 tools/iteration_look.py chiprun_out/events.deepseekv32.long.json.gz [D F [steps expert_layers]]
"""

from __future__ import annotations

import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import host_spans  # noqa: E402
from benchmarks.harness import trace_reduce as tr  # noqa: E402


def main(argv: list[str]) -> int:
    events = tr.load_events(argv[0])
    D, F = (int(argv[1]), int(argv[2])) if len(argv) > 2 else (7168, 2048)
    marks = (f",{D},{F}]", f",{F},{D}]")
    grouped_loop = re.compile(rf"= \(s32\[\][^,]*, f32\[\d+,{D}\].* while\(")
    device = [e for e in events if tr.is_device_plane(e.plane)]
    ops = sorted((e for e in device if e.line == tr.OPS_LINE), key=lambda e: (e.start_ns, -e.dur_ns))
    rows = []
    for m in (e for e in device if e.line == tr.MODULE_LINE and "ragged_step_paged" in e.name):
        inside = [e for e in ops if m.start_ns <= e.start_ns < m.start_ns + m.dur_ns]
        top, end = [], 0
        for e in inside:
            if e.start_ns >= end:
                top.append(e)
                end = e.start_ns + e.dur_ns
        whiles = sorted((e for e in top if " while(" in e.name), key=lambda e: -e.dur_ns)[:2]
        if len(whiles) < 2:
            continue
        appends = [e for e in inside if tr.op_name(e.name).startswith("paged_kv_append")]
        steps = next((w for w in whiles if any(w.start_ns <= a.start_ns < w.start_ns + w.dur_ns for a in appends)), None)
        if steps is None:
            continue
        chunk = next(w for w in whiles if w is not steps)
        row = {"iteration_ms": m.dur_ns / 1e6, "steps_ms": steps.dur_ns / 1e6, "chunk_ms": chunk.dur_ns / 1e6,
               "outside_ms": (m.dur_ns - steps.dur_ns - chunk.dur_ns) / 1e6}
        for name, w in (("steps", steps), ("chunk", chunk)):
            leaves = [e for e in inside if w.start_ns <= e.start_ns < w.start_ns + w.dur_ns and e is not w
                      and not any(f" {kind}(" in e.name.split(" = ", 1)[-1] for kind in ("while", "conditional", "call"))]
            experts = [e for e in leaves if "s8[" in e.name and any(mk in e.name for mk in marks)]
            row[f"{name}_expert_ms"] = sum(e.dur_ns for e in experts) / 1e6
            row[f"{name}_expert_ops"] = len(experts)
            # the grouped product's own loops (PR 34): a ``while`` that carries the sum [rows, D] after its counter
            loops = [e for e in inside if w.start_ns <= e.start_ns < w.start_ns + w.dur_ns and grouped_loop.search(e.name)]
            row[f"{name}_grouped_loops_ms"] = sum(e.dur_ns for e in loops) / 1e6
            row[f"{name}_grouped_loops"] = len(loops)
        rows.append(row)
    if not rows:
        print("no whole execution of ragged_step_paged in these events")
        return 1
    print(f"{len(rows)} whole executions of ragged_step_paged")
    for key in rows[0]:
        vals = [r[key] for r in rows]
        print(f"  {key:18s} median {statistics.median(vals):9.3f}  min {min(vals):9.3f}  max {max(vals):9.3f}")
    calls = (int(argv[3]) * int(argv[4])) if len(argv) > 4 else 4 * 4  # decode steps a block x expert layers
    commits = [s.kw for s in (host_spans.parse(e) for e in events if e.name.startswith(host_spans.PREFIX))
               if s.phase == "commit" and s.kw.get("moe_rows")]
    for key in ("moe_reached", "moe_rows", "moe_max"):
        vals = [kw[key] / calls for kw in commits if key in kw]
        if vals:
            print(f"  {key + ' a step and layer':30s} over {len(vals)} commits: mean {statistics.mean(vals):7.3f}  "
                  f"min {min(vals):7.3f}  max {max(vals):7.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
