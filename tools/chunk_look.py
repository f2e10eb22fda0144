"""A developer's tool, not the benchmark: where a chunk row of
``deepseekv32.long`` goes, op by op, from the device events a traced run
kept (``benchmarks/tools/span_look.py`` writes them). Every whole
execution of ``ragged_step_paged`` is split as ``tools/iteration_look.py``
splits it; the leaf ops inside the scan over the rows' chunks are sorted
into the parts of a chunk row by their shapes (T chunk positions, H heads,
the latent row W and the context N it reads), and each part's time is
given a chunk row: the scan's ``conditional``s of a millisecond or more
are the rows that ran.

    python3 tools/chunk_look.py chiprun_out/spans.deepseekv32.long.json.gz [T H W Hi V]
"""

from __future__ import annotations

import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import trace_reduce as tr  # noqa: E402


def parts(T: int, H: int, W: int, Hi: int, V: int) -> list[tuple[str, re.Pattern]]:
    """(part, pattern over an op's instruction text), the first that matches
    names the op's part."""
    return [
        ("latent scores (q . rows)", re.compile(rf"= f32\[{T},{H},\d+\][^ ]* fusion\(bf16\[{T},{H},{W}\]")),
        ("latent softmax", re.compile(rf"= \(f32\[{T},{H}\][^ ]*, f32\[{T},{H},\d+\]")),
        ("latent sums (p . rows)", re.compile(rf"= bf16\[{T},{H},\d+\][^ ]* fusion\(f32\[{T},{H},\d+\]")),
        ("index scores", re.compile(rf"= f32\[{T},\d+\][^ ]* fusion\(bf16\[\d+,\d+,1\]")),
        ("selection sort (top_k)", re.compile(rf"= \(f32\[{T},\d{{4,}}\][^ ]*, s32\[{T},\d+\][^ ]*\) sort\(")),
        ("gathers of pages (rows, keys)", re.compile(r"= bf16\[\d+,\d+,\d+\][^ ]* fusion\(bf16\[\d{4,},\d+,\d+\]")),
        ("pool writes", re.compile(r"= bf16\[\d+,\d+,\d+,\d+\][^ ]* fusion\(bf16\[\d+,\d{4,},\d+,\d+\]")),
        ("routed experts", re.compile(r"s8\[\d+,\d+,2048\]|s8\[\d+,2048,\d+\]|= \S+ convolution_add_fusion|"
                                      r"= bf16\[32,\d+\]|f32\[1,\d+\][^ ]* fusion\(f32\[\d+,\d+\]")),
        ("dense FFNs", re.compile(r"s8\[3,\d+,18432\]|s8\[3,18432,\d+\]")),
        ("head and logits", re.compile(rf"{V}\]")),
        ("other int8 products", re.compile(r"s8\[")),
        ("sorts of the router", re.compile(r" sort\(")),
    ]


def main(argv: list[str]) -> int:
    events = tr.load_events(argv[0])
    T, H, W, Hi, V = (int(a) for a in argv[1:6]) if len(argv) > 5 else (256, 128, 640, 64, 16160)
    table = parts(T, H, W, Hi, V)
    device = [e for e in events if tr.is_device_plane(e.plane)]
    ops = sorted((e for e in device if e.line == tr.OPS_LINE), key=lambda e: (e.start_ns, -e.dur_ns))
    ms: dict[str, float] = collections.defaultdict(float)
    widths: dict[str, collections.Counter] = collections.defaultdict(collections.Counter)
    rows = scans = 0
    scan_ns = 0
    for m in (e for e in device if e.line == tr.MODULE_LINE and "ragged_step_paged" in e.name):
        inside = [e for e in ops if m.start_ns <= e.start_ns < m.start_ns + m.dur_ns]
        top, end = [], 0
        for e in inside:
            if e.start_ns >= end:
                top.append(e)
                end = e.start_ns + e.dur_ns
        whiles = sorted((e for e in top if " while(" in e.name), key=lambda e: -e.dur_ns)[:2]
        appends = [e for e in inside if tr.op_name(e.name).startswith("paged_kv_append")]
        steps = next((w for w in whiles if any(w.start_ns <= a.start_ns < w.start_ns + w.dur_ns for a in appends)), None)
        if len(whiles) < 2 or steps is None:
            continue
        chunk = next(w for w in whiles if w is not steps)
        within = [e for e in inside if chunk.start_ns <= e.start_ns < chunk.start_ns + chunk.dur_ns and e is not chunk]
        rows += sum(1 for e in within if " conditional(" in e.name and e.dur_ns >= 1e6
                    and not any(o is not e and o.start_ns <= e.start_ns and e.start_ns + e.dur_ns <= o.start_ns + o.dur_ns
                                and " conditional(" in o.name for o in within))
        scans += 1
        scan_ns += chunk.dur_ns
        for e in within:
            body = e.name.split(" = ", 1)[-1]
            if any(f" {kind}(" in " " + body for kind in ("while", "conditional", "call")):
                continue
            part = next((p for p, pat in table if pat.search(e.name)), "the rest")
            ms[part] += e.dur_ns / 1e6
            ctx = re.search(rf"f32\[{T},{H},(\d+)\]", e.name) or re.search(rf"f32\[{T},(\d+)\]", e.name)
            if part.startswith(("latent", "index", "selection")) and ctx:
                widths[part][int(ctx.group(1))] += 1
    if not rows:
        print("no chunk row in these events")
        return 1
    print(f"{scans} scans over the rows' chunks, {rows} chunk rows, {scan_ns / 1e6 / rows:.2f} ms a row")
    for part, t in sorted(ms.items(), key=lambda kv: -kv[1]):
        seen = " ".join(f"{n}x{c}" for n, c in sorted(widths[part].items())) if widths[part] else ""
        print(f"  {part:32s} {t / rows:8.3f} ms a row  {seen}")
    print(f"  {'(leaf ops, all)':32s} {sum(ms.values()) / rows:8.3f} ms a row")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
