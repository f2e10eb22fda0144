"""A builder's tool, not the benchmark: the parent commit beside the
working tree on the same chip, in one call. Runs the benchmark's command
as the driver does — a fresh process a run — in the order parent,
change, change, parent over pairs of seeds, keeps every result line under
chiprun_out/pairs.<workload>.jsonl and prints each seed's two values of
every end-to-end metric with their ratio.

    git archive <parent> | tar -x -C .bench_checkout/parent      # a directory .gitignore lists
    python3 tools/pairs.py <workload> <seconds> .bench_checkout/parent <seed> [<seed> ...]

A further candidate rides the same seeds as ``<name>=<checkout>`` after
the parent's (``.bench_checkout/parent,bound2=.bench_checkout/bound2``):
the order then rotates by one side a seed, and every side is printed
against the parent.

This process never imports JAX: each run is a child that holds the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(checkout: str, command: list[str], workload: str, seed: str, seconds: str) -> dict | None:
    done = subprocess.run(command + ["--workload", workload, "--seed", seed, "--seconds", seconds, "--trace", "0"],
                          cwd=checkout, capture_output=True, text=True, timeout=1500)
    if done.returncode != 0 or not done.stdout.strip():
        tail = [ln for ln in done.stderr.splitlines() if "warn" not in ln.lower()][-12:]
        print(f"{checkout} seed {seed}: exit {done.returncode}\n" + "\n".join(tail), flush=True)
        return None
    return json.loads(done.stdout.strip().splitlines()[-1])


def main(argv: list[str]) -> int:
    workload, seconds = argv[0], argv[1]
    parent, *others = argv[2].split(",")
    seeds = argv[3:]
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        command = json.load(fh)["command"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(ROOT, "chiprun_out", f"pairs.{workload}.jsonl")
    sides = {"parent": os.path.abspath(parent), "change": ROOT}
    sides.update((name, os.path.abspath(path)) for name, path in (o.split("=", 1) for o in others))
    order = list(sides)
    lines: dict[tuple[str, str], dict] = {}
    for i, seed in enumerate(seeds):
        for side in order[i % len(order):] + order[:i % len(order)]:
            line = run(sides[side], command, workload, seed, seconds)
            if line is None:
                continue
            lines[side, seed] = line
            with open(out, "a", encoding="utf-8") as fh:
                fh.write(json.dumps({"side": side, "seed": int(seed), **line}) + "\n")
            print(f"{side} seed {seed}: correct={line['correct']} failed={line['failed']} "
                  f"gap={line['checks']['gap_max']['value']:.4f} "
                  + " ".join(f"{k}={v['value']!r}" for k, v in line["metrics"].items())
                  + f" peak={line['device'].get('memory_peak_bytes')}", flush=True)
    for seed in seeds:
        for side in order[1:]:
            if ("parent", seed) in lines and (side, seed) in lines:
                a, b = lines["parent", seed]["metrics"], lines[side, seed]["metrics"]
                print(f"seed {seed} parent -> {side}: " + ", ".join(
                    f"{k} {a[k]['value']!r} -> {b[k]['value']!r} (x {b[k]['value'] / a[k]['value']:.4f})"
                    for k in a if k in b and a[k]["value"]), flush=True)
    return 0 if len(lines) == len(sides) * len(seeds) and all(v["correct"] for v in lines.values()) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
