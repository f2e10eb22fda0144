"""A builder's tool, not the command: read the program's gap and the
control's on many seeds in one process (one import of JAX, one compile),
each seed a whole run of the cell — new weights, new engine, a window at
the cell's own load. One JSON line a seed on standard output, also
appended to chiprun_out/seeds.<workload>.jsonl.

    python3 benchmarks/tools/seeds.py <workload> <seconds> <control bits|0> <seed> [<seed> ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks.harness import runner

    workload, seconds, bits = argv[0], float(argv[1]), int(argv[2]) or None
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    worst = 0
    for seed in (int(s) for s in argv[3:]):
        code, result = runner.run_cell(ROOT, workload, seed, seconds, False, time.monotonic(),
                                       control_bits=bits)
        if result is None:
            return code
        line = {"workload": workload, "seed": seed, "correct": result["correct"],
                "attempted": result["attempted"], "failed": result["failed"],
                "checks": {k: v["value"] for k, v in result["checks"].items()},
                "metrics": {k: v["value"] for k, v in result["metrics"].items()}}
        print(json.dumps(line), flush=True)
        with open(os.path.join(ROOT, "chiprun_out", f"seeds.{workload}.jsonl"), "a", encoding="utf-8") as fh:
            fh.write(json.dumps(line) + "\n")
        worst = max(worst, code)
    return worst


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
