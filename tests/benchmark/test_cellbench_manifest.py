"""BENCHMARK.json against the files it names, and the contract's shapes."""

import json
import os
import re

import pytest

from benchmarks.harness import costs
from benchmarks.harness.manifest import Manifest, lowering, reference_module, resolve

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


# ------------------------------------------------- a configuration's file
# What section 4 of the model-configs guide lets a chip hold a share of: a
# key's whole name says which count it is, so that a key which only holds
# such a word (``moe_layer_freq``, ``num_experts_per_tok``, the shared
# experts) is none of them. Everything else stays as published, a width
# above all; WIDTH only words the refusal.
COUNTS = {
    "layers": re.compile(r"^((num|n)_(hidden_|decoder_)?layers?|layer_types)$"),
    "experts": re.compile(r"^(num|n|moe_num)_(routed_|local_)?experts$"),
    "vocabulary": re.compile(r"^(padded_)?vocab(ulary)?_size$"),
    "heads": re.compile(r"^(num|n)_(attention_|query_|q_|key_value_|kv_)?heads?$"),
}
WIDTH = re.compile(r"hidden_size|intermediate|latent|state|proj|head_dim|head_size|_dim$|_rank$|expan|per_tok|top_?k|"
                   r"window|width|d_model|d_ff")
PRECISION = ("weights", "activations", "kv_cache", "embedding", "norms")
BANNED = ("llama", "gemma", "gpt-oss", "qwen3.5")


def kind_of(key):
    """Which of the four counts a reduced key is; a width, a shared
    expert or anything else is not the chip's share of a deployment."""
    kinds = [kind for kind, pattern in COUNTS.items() if pattern.match(key)]
    assert kinds or not WIDTH.search(key), f"reduced names {key}, which is a width: no width is ever cut"
    assert kinds, f"reduced names {key}: neither layers, routed experts held, heads held nor rows of the vocabulary"
    return kinds[0]


def count(value):
    return len(value) if isinstance(value, list) else int(value)


def check_configuration(entry, data):
    """Hold a configuration's file (``data``) and its entry in
    BENCHMARK.json to the guide: the source, a cut that lists counts only
    with their published values, the deployment and the floors beside it,
    the stated head size and precision, and the three modules it names."""
    assert data["source"] == entry["source"]
    assert data["reduced"] == entry["reduced"], "reduced in the file is not reduced in BENCHMARK.json"
    assert not any(f in entry["name"].lower() for f in BANNED), "a banned family"
    # the head size is stated, and the cost functions read that statement
    assert isinstance(data.get("head_dim"), int) and costs.head_dim(data) == data["head_dim"], "head_dim is not stated"
    missing = [k for k in PRECISION if not (isinstance(data.get("precision", {}).get(k), str) and data["precision"][k])]
    assert not missing, f"precision does not state {missing}"
    assert callable(resolve(data["factory"])) and callable(lowering(data))
    assert os.path.isfile(os.path.join(REPO, data["reference"]))
    module = reference_module(data)
    assert os.path.samefile(module.__file__, os.path.join(REPO, data["reference"]))
    check_cut(data)


def check_cut(data):
    reduced, published = data["reduced"], data.get("published", {})
    kinds = {key: kind_of(key) for key in reduced}
    for key in reduced:
        assert key in published, f"{key} is reduced and the file lacks its published value"
        assert key in data and count(data[key]) < count(published[key]), f"{key} is listed and not cut"
    if "layers" in kinds.values():
        pattern = data.get("layer_pattern") or {}
        period, lead = int(pattern.get("period", 0)), int(pattern.get("leading_dense", -1))
        assert period >= 1 and lead >= 0, "layers are cut and layer_pattern lacks period or leading_dense"
        for key in (k for k, kind in kinds.items() if kind == "layers"):
            kept = count(data[key]) - lead
            assert kept >= period and kept % period == 0, f"{key}: {kept} layers are no whole period of {period}"
            assert kept >= 4, f"{key}: {kept} layers after the leading dense ones, under the floor of four"
            if isinstance(data[key], list):  # the kinds that are kept are the published ones, in order
                assert data[key] == published[key][: len(data[key])]
                assert all(t == data[key][lead + i % period] for i, t in enumerate(data[key][lead:]))
    shares = [key for key, kind in kinds.items() if kind != "layers"]
    if shares:
        deployment = data.get("deployment") or {}
        chips = int(deployment.get("chips_per_layer", 0))
        assert chips >= 1 and deployment.get("stands_for"), "a share is held and deployment lacks chips_per_layer or stands_for"
        for key in shares:
            assert count(data[key]) == -(-count(published[key]) // chips), \
                f"{key}: {data[key]} held is not one of {chips} chips' share of {published[key]}"
            if kinds[key] == "experts":
                assert count(data[key]) >= 8, f"{key}: {data[key]} held, under the floor of at least 8 routed experts"
            elif kinds[key] == "vocabulary":
                assert 8 * count(data[key]) >= count(published[key]), f"{key}: {data[key]} rows are under an eighth of the vocabulary"


@pytest.mark.parametrize("cfg", B["configs"], ids=lambda c: c["name"])
def test_configuration_file_states_source_widths_precision_and_factory(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"].startswith("benchmarks/") and any(w["config"] == cfg["name"] for w in B["workloads"])
    check_configuration(cfg, M.config(cfg["name"]))


@pytest.mark.parametrize("name", ["mistral-7b-v0.3-int8", "deepseek-llm-7b-int8"])
def test_the_first_two_configurations_are_served_as_they_were(name):
    """What was asserted of every configuration while these two were all
    there were, still asserted of these two."""
    cfg = next(c for c in B["configs"] if c["name"] == name)
    data = M.config(name)
    assert data["source"] == cfg["source"] and data["reduced"] == cfg["reduced"] == []
    assert "int8" in data["precision"]["weights"] and data["precision"]["kv_cache"] == "bfloat16"
    assert data["hidden_size"] // data["num_attention_heads"] == data["head_dim"] == 128
    assert callable(resolve(data["factory"])) and os.path.exists(os.path.join(REPO, data["reference"]))
    assert reference_module(data).__name__ == "benchmarks.harness.reference"
    assert lowering(data).__module__ == "benchmarks.harness.llama_family"


def cut_fixture(entry_reduced=None, **changes):
    """The cut configuration that passes, with ``changes`` to its file
    (``group__key`` reaches into a group, ``None`` removes a key) and, with
    ``entry_reduced``, another list in its BENCHMARK.json entry."""
    with open(os.path.join(REPO, "tests", "benchmark", "fixtures", "cut-moe.json"), encoding="utf-8") as fh:
        data = json.load(fh)
    for key, value in changes.items():
        group, _, inner = key.partition("__")
        if inner:
            data[group] = {k: v for k, v in dict(data[group], **{inner: value}).items() if v is not None}
        elif value is None:
            del data[key]
        else:
            data[key] = value
    entry = {"name": data["name"], "source": data["source"], "file": "tests/benchmark/fixtures/cut-moe.json",
             "reduced": data["reduced"] if entry_reduced is None else entry_reduced, "why": "fixture"}
    return entry, data


_CUT = ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
_HEADS = dict(reduced=_CUT + ["num_attention_heads", "num_key_value_heads"], num_attention_heads=16, num_key_value_heads=1,
              published__num_attention_heads=128, published__num_key_value_heads=8)
PASSING = {
    "layers, experts held and vocabulary": ({}, ["experts", "layers", "layers", "vocabulary"]),
    "and a share of the heads": (_HEADS, ["experts", "heads", "heads", "layers", "layers", "vocabulary"]),
}


@pytest.mark.parametrize("case", PASSING, ids=lambda c: c.replace(" ", "_").replace(",", ""))
def test_a_cut_configuration_passes(case):
    """Layers, experts held and vocabulary reduced with their published
    values beside, and with them one chip's heads of the eight chips that
    share a layer; heads of 128 at hidden 4096, so head_dim is no quotient
    of hidden size and heads."""
    changes, kinds = PASSING[case]
    entry, data = cut_fixture(**changes)
    assert data["hidden_size"] // data["num_attention_heads"] != data["head_dim"] == 128
    assert sorted(kind_of(k) for k in data["reduced"]) == kinds
    check_configuration(entry, data)


@pytest.mark.parametrize("key, kind", [
    ("num_hidden_layers", "layers"), ("n_layer", "layers"), ("layer_types", "layers"), ("num_experts", "experts"),
    ("n_routed_experts", "experts"), ("num_local_experts", "experts"), ("vocab_size", "vocabulary"),
    ("num_attention_heads", "heads"), ("num_key_value_heads", "heads"), ("n_head", "heads"),
    ("moe_layer_freq", None), ("first_k_dense_replace", None), ("num_shared_experts", None), ("n_shared_experts", None),
    ("num_experts_per_tok", None), ("head_dim", None), ("hidden_size", None), ("ssm_state_size", None),
    ("kv_lora_rank", None), ("sliding_window", None), ("n_embd_per_layer", None), ("state_layers_total", None),
])
def test_a_reduced_key_is_a_count_by_its_whole_name(key, kind):
    if kind is None:
        with pytest.raises(AssertionError, match="reduced names"):
            kind_of(key)
    else:
        assert kind_of(key) == kind


_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
BROKEN = {
    "a width in reduced": (dict(reduced=["num_hidden_layers", "layer_types", "num_experts", "vocab_size", "intermediate_size"],
                                intermediate_size=2048, published__intermediate_size=4096), "is a width"),
    "the experts per token in reduced": (dict(reduced=["num_experts_per_tok"], num_experts_per_tok=2,
                                              published__num_experts_per_tok=8), "is a width"),
    "the shared experts in reduced": (dict(reduced=["num_shared_experts"], num_shared_experts=1,
                                           published__num_shared_experts=4), "neither layers"),
    "the head size in reduced": (dict(reduced=["head_dim"], head_dim=64, published__head_dim=128), "is a width"),
    "a key that only holds the word layer": (dict(reduced=["moe_layer_freq"], moe_layer_freq=1, published__moe_layer_freq=2),
                                             "neither layers"),
    "heads that are not the deployment's share": (dict(_HEADS, num_attention_heads=32), "is not one of 8 chips' share of 128"),
    "heads cut without their published count": (dict(_HEADS, published__num_key_value_heads=None), "lacks its published value"),
    "a reduced key without its published value": (dict(published={"num_hidden_layers": 32, "layer_types": _PERIOD * 8,
                                                                   "vocab_size": 262144}), "lacks its published value"),
    "four experts held": (dict(num_experts=4, deployment__chips_per_layer=32, vocab_size=8192, published__vocab_size=262144),
                          "at least 8 routed experts"),
    "a sixteenth of the vocabulary": (dict(vocab_size=16384, num_experts=8, deployment__chips_per_layer=16),
                                      "an eighth of the vocabulary"),
    "three layers of a four-layer period": (dict(num_hidden_layers=3, layer_types=_PERIOD[:3]), "no whole period of 4"),
    "four layers that are all leading dense but one": (dict(num_hidden_layers=4, layer_types=_PERIOD,
                                                            layer_pattern={"period": 1, "leading_dense": 1}), "under the floor of four"),
    "a reference that lacks served_gaps": (dict(reference="tests/benchmark/fixtures/no_gaps_reference.py"), "lacks served_gaps"),
    "no layer pattern beside cut layers": (dict(layer_pattern=None), "layer_pattern lacks"),
    "no deployment beside a share": (dict(deployment=None), "deployment lacks"),
    "a share that is not the deployment's": (dict(deployment__chips_per_layer=4), "is not one of 4 chips' share"),
    "a key listed and not cut": (dict(num_experts=128, deployment__chips_per_layer=1, vocab_size=262144), "listed and not cut"),
    "reduced differs from BENCHMARK.json": (dict(entry_reduced=["num_hidden_layers"]), "not reduced in BENCHMARK.json"),
    "head_dim left to a quotient": (dict(head_dim=None), "head_dim is not stated"),
    "precision without the cache's type": (dict(precision__kv_cache=""), "precision does not state"),
    "a banned family": (dict(name="llama-cut"), "a banned family"),
}


@pytest.mark.parametrize("case", BROKEN, ids=lambda c: c.replace(" ", "_"))
def test_a_broken_cut_configuration_fails_for_its_reason(case):
    changes, reason = BROKEN[case]
    entry, data = cut_fixture(**changes)
    with pytest.raises((AssertionError, AttributeError), match=reason):
        check_configuration(entry, data)


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
