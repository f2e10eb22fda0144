"""The ratcheted perf gate (bench.py --check / make bench-check): floor
comparison logic over canned contract JSONL, so CI enforces the gate's
semantics without a TPU. docs/performance.md#bench-ratchet."""

import io
import json
import subprocess
import sys

from gofr_tpu.analysis.bench_ratchet import (
    check_records,
    load_floors,
    parse_records,
    run_check,
    save_floors,
    update_floors,
)

FLOORS = {
    "llama_decode_tokens_per_sec_8b-int8_bs128_tpu": {
        "floor": 5509.26, "tolerance": 0.10,
    },
}


def rec(metric, value, **details):
    return {"metric": metric, "value": value, "unit": "tokens/s",
            "vs_baseline": None, "details": details}


def test_passing_record_clears_the_floor():
    records = [rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 5600.0)]
    violations, warnings = check_records(records, FLOORS)
    assert violations == [] and warnings == []


def test_synthetic_regression_fails():
    records = [rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 4000.0)]
    violations, _ = check_records(records, FLOORS)
    assert len(violations) == 1
    assert "below the ratcheted floor" in violations[0]


def test_tolerance_band_absorbs_noise():
    # floor 5509.26 with 10% tolerance → anything >= 4958.334 passes
    ok = [rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 4960.0)]
    bad = [rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 4950.0)]
    assert check_records(ok, FLOORS)[0] == []
    assert len(check_records(bad, FLOORS)[0]) == 1


def test_best_value_wins_over_an_errored_line():
    records = [
        rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", None,
            error="chip lost"),
        rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 5700.0),
        rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 4000.0),
    ]
    violations, warnings = check_records(records, FLOORS)
    assert violations == [] and warnings == []


def test_missing_metric_warns_but_does_not_fail():
    violations, warnings = check_records([], FLOORS)
    assert violations == []
    assert len(warnings) == 1 and "no record to check" in warnings[0]


def test_malformed_lines_are_skipped():
    lines = [
        "not json at all {",
        json.dumps(["a", "list"]),
        json.dumps({"value": 1}),  # no metric name
        json.dumps(rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 5600.0)),
        "",
    ]
    records = parse_records(lines)
    assert len(records) == 1  # only the well-formed contract line survives
    assert check_records(records, FLOORS)[0] == []


def test_update_ratchets_up_never_down():
    higher = [rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 9000.0)]
    lower = [rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 1000.0)]
    up = update_floors(higher, FLOORS)
    assert up["llama_decode_tokens_per_sec_8b-int8_bs128_tpu"]["floor"] == 9000.0
    down = update_floors(lower, FLOORS)
    assert down["llama_decode_tokens_per_sec_8b-int8_bs128_tpu"]["floor"] == 5509.26


def test_floors_file_round_trip(tmp_path):
    path = str(tmp_path / "floors.json")
    save_floors(FLOORS, path)
    loaded = load_floors(path)
    assert loaded == FLOORS


def test_run_check_cli_pass_and_fail(tmp_path):
    floors_path = str(tmp_path / "floors.json")
    save_floors(FLOORS, floors_path)
    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps(
        rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 6000.0)) + "\n")
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps(
        rec("llama_decode_tokens_per_sec_8b-int8_bs128_tpu", 100.0)) + "\n")
    buf = io.StringIO()
    assert run_check([str(good)], floors_path=floors_path, out=buf) == 0
    assert "OK" in buf.getvalue()
    buf = io.StringIO()
    assert run_check([str(bad)], floors_path=floors_path, out=buf) == 1
    assert "FAIL" in buf.getvalue()
    assert run_check([str(tmp_path / "absent.jsonl")],
                     floors_path=floors_path, out=io.StringIO()) == 2


MIN_FLOORS = {
    "engine_mixed_ttft_ms_p50_tiny_cpu": {
        "floor": 100.0, "tolerance": 0.50, "direction": "min",
    },
}


def test_min_direction_floor_gates_latency_regressions():
    """direction:"min" inverts the gate for latency-style metrics (TTFT
    under load): lower is better, the violation is EXCEEDING the floor
    plus tolerance."""
    ok = [rec("engine_mixed_ttft_ms_p50_tiny_cpu", 140.0)]  # <= 150 allowed
    bad = [rec("engine_mixed_ttft_ms_p50_tiny_cpu", 160.0)]
    assert check_records(ok, MIN_FLOORS)[0] == []
    violations, _ = check_records(bad, MIN_FLOORS)
    assert len(violations) == 1
    assert "above the ratcheted ceiling" in violations[0]


def test_min_direction_best_value_is_the_lowest():
    records = [
        rec("engine_mixed_ttft_ms_p50_tiny_cpu", 400.0),
        rec("engine_mixed_ttft_ms_p50_tiny_cpu", 90.0),  # best (lowest)
        rec("engine_mixed_ttft_ms_p50_tiny_cpu", 200.0),
    ]
    assert check_records(records, MIN_FLOORS)[0] == []


def test_min_direction_update_ratchets_down_never_up():
    records = [rec("engine_mixed_ttft_ms_p50_tiny_cpu", 80.0)]
    updated = update_floors(records, MIN_FLOORS)
    entry = updated["engine_mixed_ttft_ms_p50_tiny_cpu"]
    assert entry["floor"] == 80.0 and entry["direction"] == "min"
    # a worse run never loosens the committed floor
    worse = update_floors(
        [rec("engine_mixed_ttft_ms_p50_tiny_cpu", 500.0)], MIN_FLOORS
    )
    assert worse["engine_mixed_ttft_ms_p50_tiny_cpu"]["floor"] == 100.0


def test_min_direction_round_trips_through_the_floors_file(tmp_path):
    path = str(tmp_path / "floors.json")
    save_floors(MIN_FLOORS, path)
    loaded = load_floors(path)
    entry = loaded["engine_mixed_ttft_ms_p50_tiny_cpu"]
    assert entry["direction"] == "min" and entry["floor"] == 100.0


def test_bench_py_check_entrypoint_needs_no_backend():
    """`bench.py --check` is the CI gate: it must run without initializing
    any jax backend — JAX_PLATFORMS deliberately unset here — and a
    checkout with no BENCH_LOCAL.jsonl (a run-time file) is zero records:
    warnings, exit 0."""
    import os

    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "bench.py", "--check"],
        capture_output=True, text=True, timeout=120, cwd=repo, env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "bench-check: OK" in r.stdout
