"""A builder's tool, not the command: run one cell with --trace 1, keep the
reduced event list under chiprun_out/, and print what the trace calls
things — planes, lines, and the names that took most time on each line.

    python3 benchmarks/tools/trace_look.py <workload> <seed> <seconds> [out.json.gz]
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks.harness import runner, trace_reduce

    workload, seed, seconds = argv[0], int(argv[1]), float(argv[2])
    out = argv[3] if len(argv) > 3 else os.path.join(ROOT, "chiprun_out", f"events.{workload}.json.gz")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    code, result = runner.run_cell(ROOT, workload, seed, seconds, True, T_START, keep_events=out)
    if result is None:
        return code
    events = trace_reduce.load_events(out)
    by_line: dict[tuple[str, str], collections.Counter] = {}
    for e in events:
        by_line.setdefault((e.plane, e.line), collections.Counter())[e.name] += e.dur_ns
    for (plane, line), names in sorted(by_line.items()):
        print(f"== {plane} / {line}: {len(names)} names, {sum(names.values()) / 1e9:.3f}s", file=sys.stderr)
        for name, ns in names.most_common(25):
            print(f"     {ns / 1e9:9.4f}s  {name[:150]}", file=sys.stderr)
    print(json.dumps(result))
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
