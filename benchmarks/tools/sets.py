"""A builder's tool, not the command: the measurement the bounds are set
from. Runs the benchmark's command as the driver does — a fresh process a
run — for two sets of the same seeds, keeps every result line under
chiprun_out/, and prints each metric's spread per set (distance between
the quartiles of ``statistics.quantiles(n=4)`` over the median).

    python3 benchmarks/tools/sets.py <workload> <seconds> <trace 0|1> <sets> <seed> [<seed> ...]

This process never imports JAX: each run is a child that holds the chip.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks.harness import stats  # pure Python: no JAX in this process

    workload, seconds, trace, sets = argv[0], argv[1], argv[2], int(argv[3])
    seeds = argv[4:]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", f"sets.{workload}.trace{trace}.jsonl")
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        command = json.load(fh)["command"]
    per_set: list[list[dict]] = []
    for s in range(sets):
        rows = []
        for seed in seeds:
            run = subprocess.run(
                command + ["--workload", workload, "--seed", seed, "--seconds", seconds, "--trace", trace],
                cwd=ROOT, capture_output=True, text=True, timeout=1500)
            tail = [ln for ln in run.stderr.splitlines() if "warnings.warn" not in ln and "Transparent" not in ln][-14:]
            if run.returncode != 0 or not run.stdout.strip():
                print(f"set {s} seed {seed}: exit {run.returncode}\n" + "\n".join(tail), flush=True)
                continue
            line = json.loads(run.stdout.strip().splitlines()[-1])
            row = {"set": s, "seed": int(seed), **line}
            with open(out_path, "a", encoding="utf-8") as fh:
                fh.write(json.dumps(row) + "\n")
            rows.append(row)
            print(f"set {s} seed {seed}: correct={line['correct']} attempted={line['attempted']} "
                  f"failed={line['failed']} gap={line['checks']['gap_max']['value']:.4f} "
                  + " ".join(f"{k}={v['value']:.4f}" for k, v in line["metrics"].items()), flush=True)
            if not line["correct"]:
                print("\n".join(tail), flush=True)
        per_set.append(rows)
    for s, rows in enumerate(per_set):
        names = sorted({k for r in rows for k in r["metrics"]})
        for name in names:
            vals = [r["metrics"][name]["value"] for r in rows if name in r["metrics"]]
            if len(vals) >= 3:
                print(f"set {s} {name}: median {statistics.median(vals):.4f} "
                      f"spread {stats.iqr_share(vals):.4%} over {len(vals)} runs "
                      f"(min {min(vals):.4f} max {max(vals):.4f})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
