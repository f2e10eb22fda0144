"""BENCHMARK.json against the files it names, and the contract's shapes."""

import json
import os
import re

import pytest

from benchmarks.harness.manifest import Manifest, resolve

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
M = Manifest(REPO)
B = M.data
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in B["workloads"]]
E2E = {m["name"]: m for m in B["end_to_end"]}


def test_top_level_keys_are_exactly_the_contracts():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for word in B["command"]:
        assert not word.startswith("/") and ".." not in word
    for p in B["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))


@pytest.mark.parametrize("cfg", B["configs"], ids=lambda c: c["name"])
def test_configuration_file_states_source_widths_precision_and_factory(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"].startswith("benchmarks/") and any(w["config"] == cfg["name"] for w in B["workloads"])
    data = M.config(cfg["name"])
    assert data["source"] == cfg["source"] and data["reduced"] == cfg["reduced"] == []
    assert "int8" in data["precision"]["weights"] and data["precision"]["kv_cache"] == "bfloat16"
    assert data["hidden_size"] // data["num_attention_heads"] == data["head_dim"] == 128
    assert callable(resolve(data["factory"])) and os.path.exists(os.path.join(REPO, data["reference"]))
    assert not any(f in cfg["name"].lower() for f in ("llama", "gemma", "gpt-oss", "qwen3.5"))


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda w: w["name"])
def test_cell_has_its_config_traffic_and_settings_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in B["configs"]}
    spec, settings = M.traffic(cell["traffic"]), M.cell(cell["name"])
    assert spec["loop"] in ("open", "closed")
    assert ("rate_per_s" in spec) if spec["loop"] == "open" else (spec["clients"] >= 1)
    engine = settings["engine"]
    assert engine["kv_layout"] == "paged" and engine["prefix_cache_entries"] == 0
    # the longest request of the mix fits a slot
    assert spec["prompt_tokens"]["max"] + spec["output_tokens"]["max"] <= engine["max_seq_len"]
    assert settings["correct"]["gap_max"] > 0
    # every cell reports setup_s, another end-to-end metric and a per-layer metric
    assert {m["name"] for m in M.metrics_for("end_to_end", cell["name"])} > {"setup_s"}
    assert M.metrics_for("per_layer", cell["name"])


def test_cells_and_pairs_are_unique():
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("m", B["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric_shape(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_setup_s_is_reported_everywhere():
    assert "workloads" not in E2E["setup_s"] and E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_a_layer_and_moves_a_metric_its_cells_report(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["source"] in SOURCES
    assert os.path.exists(M.reader_path(m["name"])) and callable(M.reader(m["name"]))
    moved = E2E[m["moves"]]
    for cell in m["workloads"]:
        assert cell in CELLS and cell in moved.get("workloads", CELLS)


def test_names_are_unique_and_layers_are_in_perf_md():
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(set(names)) == len(names)
    with open(os.path.join(REPO, "PERF.md"), encoding="utf-8") as fh:
        perf = fh.read()
    for layer in {m["layer"] for m in B["per_layer"]}:
        assert layer in perf


def test_a_roofline_and_the_whole_steps_mfu_move_the_same_metrics():
    moved_by = lambda part: {m["moves"] for m in B["per_layer"] if part in re.split(r"[._]", m["name"])}  # noqa: E731
    assert moved_by("roofline") and moved_by("roofline") <= moved_by("mfu")
    for m in B["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"


def test_every_reader_file_is_named_in_the_manifest():
    files = {f[:-3] for f in os.listdir(os.path.join(REPO, "benchmarks", "layer_metrics")) if f.endswith(".py")}
    assert files == {m["name"] for m in B["per_layer"]}


def test_data_files_are_json_and_named_from_name_characters():
    for sub in ("configs", "traffic", "cells"):
        for f in os.listdir(os.path.join(REPO, "benchmarks", sub)):
            assert re.match(r"^[A-Za-z0-9_.\-]+\.json$", f)
            with open(os.path.join(REPO, "benchmarks", sub, f), encoding="utf-8") as fh:
                json.load(fh)
