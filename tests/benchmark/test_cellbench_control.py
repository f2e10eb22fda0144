"""What has to come out as NOT correct, at a size a test run can hold:

* the control — the reference put in the program's place with its int8
  matrices re-quantised to int4 (the nearest precision below the one the
  configurations state): its widest gap must pass a limit that the
  program's own gap stays under;
* a token altered where it is produced: the harness drives a whole run
  with the timed path broken underneath and ``correct`` reads false — in
  a cell of each tiny configuration, so also through a reference that the
  configuration's file names and ``benchmarks/harness/`` does not.
"""

import importlib
import time

import numpy as np
import pytest

import cellbench_tiny
from benchmarks.harness import llama_family, reference, runner


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # at tiny widths logits have std ~1 like the real cells'; the program
    # (f32 here) sits within rounding of the reference
    return cellbench_tiny.make_root(str(tmp_path_factory.mktemp("tinyctl")), gap_max=0.02)


@pytest.mark.parametrize("seed", [101, 202, 2**31 + 303])
def test_int4_control_fails_where_the_exact_weights_pass(seed):
    cfg = cellbench_tiny.TINY_CONFIG
    weights = llama_family.make_weights(cfg, seed)
    rng = np.random.default_rng(seed)
    prompt = [1] + list(rng.integers(3, 259, 40))
    # tokens the reference itself would serve greedily, teacher-forced
    served = []
    for _ in range(24):
        ids = np.asarray(prompt + served, np.int32)
        served.append(int(np.argmax(np.asarray(reference.logits(cfg, weights, ids))[-1])))
    gaps = reference.served_gaps(cfg, weights, prompt, served, pad_len=80, control_bits=4)
    assert float(gaps["served"].max()) == 0.0
    assert float(gaps["control"].max()) > 0.02


def _alter_tokens(engine):
    """Break the timed path where a token is produced: every committed
    token but the first of a request comes out one id higher."""
    emit = engine._emit_token

    def altered(req, token_id):
        emit(req, (token_id + 1) % engine.model_cfg.vocab_size if req.tokens else token_id)

    engine._emit_token = altered


def test_an_altered_token_reads_not_correct(root):
    code, result = runner.run_cell(root, "tiny.open", 5, 2.0, False, time.monotonic(),
                                   platform="cpu", fault=_alter_tokens)
    assert code == 0
    assert result["correct"] is False
    assert result["checks"]["gap_max"]["value"] > result["checks"]["gap_max"]["limit"]


def test_the_same_run_unbroken_reads_correct(root):
    code, result = runner.run_cell(root, "tiny.open", 5, 2.0, False, time.monotonic(), platform="cpu")
    assert code == 0 and result["correct"] is True


@pytest.fixture
def named_only(monkeypatch):
    """The second tiny configuration's modules, with the harness's own
    reference set to fail if anything asks it."""
    def asked(*args, **kwargs):
        raise AssertionError("benchmarks.harness.reference was asked")

    for name in ("served_gaps", "logits", "pad_to"):
        monkeypatch.setattr(reference, name, asked)
    family = importlib.import_module("tests.benchmark.tiny_family")
    del family.CALLS[:]
    return family


@pytest.mark.parametrize("fault, correct", [(None, True), (_alter_tokens, False)], ids=["unbroken", "a_token_altered"])
def test_a_cell_is_decided_by_the_reference_its_file_names_and_by_no_other(root, named_only, capsys, fault, correct):
    code, result = runner.run_cell(root, "tiny2.open", 2**31 + 21, 2.0, False, time.monotonic(),
                                   platform="cpu", fault=fault)
    assert code == 0 and result["attempted"] == 12 and result["failed"] == 0
    assert result["correct"] is correct
    assert (result["checks"]["gap_max"]["value"] <= result["checks"]["gap_max"]["limit"]) is correct
    # the factory and the lowering were the file's too, and the reference ran once a sampled request
    assert named_only.CALLS[:2] == ["factory", "lowering"] and named_only.CALLS.count("served_gaps") == 3
    assert "reference tests/benchmark/tiny_family.py (tests.benchmark.tiny_family) over 3 requests" in capsys.readouterr().err
