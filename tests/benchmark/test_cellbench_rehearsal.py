"""End-to-end rehearsals of the harness on the CPU at tiny widths: the
served App, the child load generator, SSE parsing, the reference and the
result line. The tests steer the platform check themselves; nothing here
is printed under a device metric's name by the command, which only ever
asks for a TPU."""

import json
import os
import subprocess
import sys
import time

import pytest

import cellbench_tiny
from benchmarks.harness import runner

REPO = cellbench_tiny.REPO


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cellbench_tiny.make_root(str(tmp_path_factory.mktemp("tinybench")))


@pytest.fixture(scope="module")
def open_run(root):
    code, result = runner.run_cell(root, "tiny.open", 2**31 + 11, 2.0, False, time.monotonic(),
                                   platform="cpu", control_bits=4)
    assert code == 0
    return result


def test_result_line_has_the_contracts_keys_in_order(open_run):
    line = json.loads(json.dumps(open_run))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"  # named for what it is, never a device number


def test_open_loop_reports_its_cells_end_to_end_metrics(open_run, root):
    from benchmarks.harness.manifest import Manifest

    listed = {m["name"] for m in Manifest(root).metrics_for("end_to_end", "tiny.open")}
    assert set(open_run["metrics"]) == listed >= {"tpot_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in open_run["metrics"].values())
    assert open_run["attempted"] == 12 and open_run["failed"] == 0  # round(6/s x 2 s), exactly


def test_served_tokens_agree_with_the_plain_reference(open_run):
    checks = open_run["checks"]
    assert open_run["correct"] is True
    assert checks["gap_max"]["value"] <= checks["gap_max"]["limit"]
    assert checks["sampled_tokens"]["value"] >= checks["sampled_tokens"]["limit_min"]


def test_the_run_names_its_reference_and_frees_what_the_engine_held(root, monkeypatch, capsys):
    """Before the reference runs, every device array of the run but the
    weights is gone — the runtime's list of live arrays, no field's name —
    and the weights, and the arrays older than the run, are whole."""
    import jax

    held, seen = {}, {}
    check_outputs = runner.check_outputs
    bystander = jax.numpy.arange(7.0)  # another test's array, alive before the run
    older = jax.live_arrays()

    def spy(config, weights, *args, **kwargs):
        engine = held["engine"]
        pools = [x for x in vars(engine.paged_cache).values() if isinstance(x, jax.Array)]
        seen["pools_deleted"] = len(pools) >= 2 and all(x.is_deleted() for x in pools)
        seen["left_to_free"] = runner.free_device_state(weights, older)
        seen["weights_live"] = not any(x.is_deleted() for x in jax.tree.leaves(weights))
        return check_outputs(config, weights, *args, **kwargs)

    monkeypatch.setattr(runner, "check_outputs", spy)
    code, result = runner.run_cell(root, "tiny.open", 13, 1.0, False, time.monotonic(), platform="cpu",
                                   fault=lambda engine: held.update(engine=engine))
    assert code == 0 and result["correct"] is True
    assert seen == {"pools_deleted": True, "left_to_free": 0, "weights_live": True}
    assert float(bystander.sum()) == 21.0
    err = capsys.readouterr().err
    assert "reference benchmarks/harness/reference.py (benchmarks.harness.reference) over" in err
    assert "after freeing every device array beside the weights" in err


def test_closed_loop_traced_run_goes_through_the_chunked_path(root):
    code, result = runner.run_cell(root, "tiny.closed", 7, 2.0, True, time.monotonic(), platform="cpu")
    assert code == 0 and result["correct"] is True and result["attempted"] >= 3
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result
    # no device plane on the CPU: a reader that finds nothing returns nothing
    assert not any("mfu" in k or "roofline" in k or "idle" in k for k in result["metrics"])


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "mistral7b.chat", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no result" in out.stderr


def test_a_closed_loop_cuts_what_is_in_flight_or_queued_and_fails_nothing(root, tmp_path):
    """More clients than slots and a drain too short for the queue: the
    requests left at the end of observation are cut, not failed."""
    import shutil

    tight = str(tmp_path / "tight")
    shutil.copytree(root, tight)
    cell_path = os.path.join(tight, "benchmarks", "cells", "tiny.closed.json")
    cell = json.load(open(cell_path, encoding="utf-8"))
    cell["drain_s"] = 0.0
    cell["engine"] = dict(cell["engine"], max_slots=1)
    json.dump(cell, open(cell_path, "w", encoding="utf-8"))
    code, result = runner.run_cell(tight, "tiny.closed", 9, 1.0, False, time.monotonic(), platform="cpu")
    assert code == 0 and result["failed"] == 0 and result["correct"] is True
    assert result["metrics"]["tok_s"]["value"] > 0
