"""A builder's tool, not the command: the knee sweep. One set-up, then the
cell's mix at each of several fixed rates (a child load generator each),
then the cell's own window. Reads go to standard error.

    python3 benchmarks/tools/sweep.py <workload> <seed> <seconds> <rate> [<rate> ...]
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks.harness import runner

    workload, seed, seconds = argv[0], int(argv[1]), float(argv[2])
    code, result = runner.run_cell(ROOT, workload, seed, seconds, False, T_START,
                                   sweep=[float(r) for r in argv[3:]])
    if result is not None:
        print(json.dumps(result))
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
