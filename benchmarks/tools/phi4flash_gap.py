"""A builder's tool, not the command: what ``phi4flash.reason``'s
``gap_max`` is set from. In one process (one import of JAX, one compile):
the program's gap and the int4 control's on each seed, a whole run of the
cell each (``tools/seeds.py``'s loop), and then, on the seeds after
``--bf16-state``, the same run of a program whose recurrent state is
bfloat16 — the fault the limit must catch: its gap has to come out over
the limit.

    python3 benchmarks/tools/phi4flash_gap.py <seconds> <seed> [<seed> ...] [--bf16-state <seed> ...]

One JSON line a run on standard output, also appended to
chiprun_out/phi4flash_gap.jsonl.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "phi4flash.reason"


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp

    from benchmarks.harness import phi4flash_family as family
    from benchmarks.harness import runner

    seconds = float(argv[0])
    split = argv.index("--bf16-state") if "--bf16-state" in argv else len(argv)
    runs = [(int(s), "served") for s in argv[1:split]] + [(int(s), "bf16_state") for s in argv[split + 1:]]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    served_config = family.program_config
    worst = 0
    for seed, what in runs:
        rounded = what == "bf16_state"
        family.program_config = (lambda c: dataclasses.replace(served_config(c), state_dtype=jnp.bfloat16)) if rounded else served_config
        code, result = runner.run_cell(ROOT, CELL, seed, seconds, False, time.monotonic(),
                                       control_bits=None if rounded else 4)
        if result is None:
            return code
        line = {"workload": CELL, "program": what, "seed": seed, "correct": result["correct"],
                "attempted": result["attempted"], "failed": result["failed"],
                "checks": {k: v["value"] for k, v in result["checks"].items()},
                "metrics": {k: v["value"] for k, v in result["metrics"].items()}}
        print(json.dumps(line), flush=True)
        with open(os.path.join(ROOT, "chiprun_out", "phi4flash_gap.jsonl"), "a", encoding="utf-8") as fh:
            fh.write(json.dumps(line) + "\n")
        worst = max(worst, code)
    return worst


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
