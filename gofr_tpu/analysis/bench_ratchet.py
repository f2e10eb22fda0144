"""Ratcheted perf gate over bench.py's contract JSONL (ROADMAP item 1).

The static-analysis suite has ``analysis/baseline.json`` so lint findings
can only go DOWN; this is the same ratchet for performance numbers:
``analysis/bench_floors.json`` commits a per-metric floor (with a
tolerance band for run-to-run noise), and ``bench.py --check`` fails when
the best committed/observed value for a floored metric regresses below
``floor * (1 - tolerance)``. ``BENCH_LOCAL.jsonl`` is a run-time file
(``bench.py --loadlab`` appends to it; git-ignored); this module's unit
tests run the comparison logic against canned fixtures.

Matching is by exact metric name. A floor with NO matching record is a
warning, not a failure — a checkout that has not run the bench yet has
nothing to gate.

Workflow (docs/performance.md):
- ``python bench.py --check``           gate against BENCH_LOCAL.jsonl
- ``python bench.py --check run.jsonl`` gate a specific run's output
- ``python bench.py --update-floors``   ratchet floors up to the best
  committed values (commit the diff)
"""

from __future__ import annotations

import json
import os
from typing import Any, Iterable

DEFAULT_TOLERANCE = 0.10

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FLOORS_PATH = os.path.join(_REPO, "gofr_tpu", "analysis", "bench_floors.json")


def load_floors(path: str | None = None) -> dict[str, dict[str, float]]:
    """{metric: {"floor": value, "tolerance": fraction, "direction":
    "max"|"min"}} from the committed floors file. ``direction`` defaults
    to "max" (throughput-style: higher is better, the floor is a lower
    bound). ``"min"`` inverts the gate for latency-style metrics (TTFT
    under load): the best value is the LOWEST, a regression is exceeding
    floor*(1+tolerance), and the ratchet moves the floor DOWN."""
    with open(path or FLOORS_PATH) as f:
        raw = json.load(f)
    floors: dict[str, dict[str, float]] = {}
    for metric, entry in raw.get("floors", {}).items():
        if isinstance(entry, (int, float)):  # shorthand: bare floor value
            entry = {"floor": entry}
        direction = str(entry.get("direction", "max"))
        if direction not in ("max", "min"):
            raise ValueError(
                f"floor {metric}: direction must be 'max' or 'min', "
                f"got {direction!r}"
            )
        floors[metric] = {
            "floor": float(entry["floor"]),
            "tolerance": float(entry.get("tolerance", DEFAULT_TOLERANCE)),
        }
        if direction == "min":  # "max" stays implicit: entry shape is stable
            floors[metric]["direction"] = "min"
    return floors


def parse_records(lines: Iterable[str]) -> list[dict]:
    """Contract-shaped records from JSONL text lines. Malformed lines are
    skipped — a truncated append from a dying bench run must not wedge the
    gate that guards everything else."""
    records: list[dict] = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and isinstance(rec.get("metric"), str):
            records.append(rec)
    return records


def best_values(records: Iterable[dict],
                floors: dict[str, dict]) -> dict[str, float]:
    """Best numeric value per floored metric (max for throughput-style
    floors, min for direction:"min" latency-style ones)."""
    best: dict[str, float] = {}
    for rec in records:
        metric = rec["metric"]
        if metric not in floors:
            continue
        value = rec.get("value")
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            continue
        lower_better = floors[metric].get("direction") == "min"
        if metric not in best or (
            value < best[metric] if lower_better else value > best[metric]
        ):
            best[metric] = float(value)
    return best


def check_records(
    records: Iterable[dict], floors: dict[str, dict]
) -> tuple[list[str], list[str]]:
    """Returns (violations, warnings). A violation is a floored metric
    whose best value fell below floor*(1-tolerance); a warning is a
    floored metric with no usable record at all."""
    best = best_values(records, floors)
    violations: list[str] = []
    warnings: list[str] = []
    for metric, entry in sorted(floors.items()):
        if metric not in best:
            warnings.append(
                f"{metric}: no record to check (floor {entry['floor']:g} "
                "carried)"
            )
            continue
        if entry.get("direction") == "min":
            allowed = entry["floor"] * (1.0 + entry["tolerance"])
            if best[metric] > allowed:
                violations.append(
                    f"{metric}: best value {best[metric]:g} is above the "
                    f"ratcheted ceiling {entry['floor']:g} "
                    f"(+{entry['tolerance']:.0%} tolerance = {allowed:g}) "
                    "— a latency regression; fix it, or consciously raise "
                    "the floor in analysis/bench_floors.json with a "
                    "justification"
                )
            continue
        allowed = entry["floor"] * (1.0 - entry["tolerance"])
        if best[metric] < allowed:
            violations.append(
                f"{metric}: best value {best[metric]:g} is below the "
                f"ratcheted floor {entry['floor']:g} "
                f"(-{entry['tolerance']:.0%} tolerance = {allowed:g}) — a "
                "perf regression; fix it, or consciously lower the floor "
                "in analysis/bench_floors.json with a justification"
            )
    return violations, warnings


def update_floors(
    records: Iterable[dict], floors: dict[str, dict]
) -> dict[str, dict[str, float]]:
    """Ratchet: floors only move UP (to the best observed value). Returns
    the new floors mapping; the caller persists it."""
    best = best_values(records, floors)
    out: dict[str, dict[str, float]] = {}
    for metric, entry in floors.items():
        floor = entry["floor"]
        lower_better = entry.get("direction") == "min"
        if metric in best and (
            best[metric] < floor if lower_better else best[metric] > floor
        ):
            floor = round(best[metric], 4)
        out[metric] = {"floor": floor, "tolerance": entry["tolerance"]}
        if lower_better:
            out[metric]["direction"] = "min"
    return out


def save_floors(floors: dict[str, dict], path: str | None = None) -> None:
    payload = {
        "_comment": (
            "Ratcheted perf floors for bench.py --check (make bench-check). "
            "Floors only move up (bench.py --update-floors); lowering one "
            "requires a justification in the commit. Tolerance absorbs "
            "run-to-run noise. docs/performance.md#bench-ratchet."
        ),
        "floors": floors,
    }
    with open(path or FLOORS_PATH, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def run_check(jsonl_paths: list[str], *, update: bool = False,
              floors_path: str | None = None, out: Any = None) -> int:
    """CLI driver for ``bench.py --check`` / ``--update-floors``.
    Returns a process exit code."""
    import sys

    out = out or sys.stdout
    floors = load_floors(floors_path)
    records: list[dict] = []
    for path in jsonl_paths:
        try:
            with open(path) as f:
                records.extend(parse_records(f))
        except OSError as exc:
            print(f"bench-check: cannot read {path}: {exc}", file=out)
            return 2
    if update:
        save_floors(update_floors(records, floors), floors_path)
        print(f"bench-check: floors ratcheted over {len(records)} record(s)",
              file=out)
        return 0
    violations, warnings = check_records(records, floors)
    for w in warnings:
        print(f"bench-check: WARN {w}", file=out)
    for v in violations:
        print(f"bench-check: FAIL {v}", file=out)
    if violations:
        return 1
    print(
        f"bench-check: OK ({len(floors)} floor(s), {len(records)} record(s), "
        f"{len(warnings)} unchecked)",
        file=out,
    )
    return 0
