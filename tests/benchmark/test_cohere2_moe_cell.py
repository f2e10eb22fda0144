"""The ``cohere2_moe`` family under the benchmark: its cost functions from
the configuration's keys against the issue's arithmetic, its readers on
hand-made events (exact arithmetic; None, never 0, on another
architecture's run or a program without the counters), the manifest
holding the new files, and a two-second rehearsal on the CPU at tiny
widths through the family's own factory, ``lowered_programs`` and
reference. Nothing here is a device number."""

import json
import os
import time

import pytest

import cellbench_tiny
from benchmarks.harness import cohere2_moe_costs as costs
from benchmarks.harness import cohere2_moe_family as family
from benchmarks.harness import cohere2_moe_layers as moe_layers
from benchmarks.harness import manifest, runner, trace_reduce as tr
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.runner import RunData

REPO = cellbench_tiny.REPO
M = Manifest(REPO)
FILE = M.config("command-a-plus-ep8-int8")
CELL = "commandaplus.wide"
DEV, HOST, MS = "/device:TPU:0", "/host:CPU", 1_000_000
NEW = ["step.mfu.wide", "moe.experts_roofline.wide", "moe.rows_per_expert.wide", "moe.load_imbalance.wide"]


# ------------------------------------------------------------ the file
def test_the_file_is_the_catalog_row_cut_as_the_issue_cuts_it():
    assert FILE["reduced"] == ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    assert (FILE["num_hidden_layers"], FILE["num_experts"], FILE["vocab_size"]) == (8, 16, 32768)
    assert FILE["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert FILE["published"]["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert (FILE["published"]["num_experts"], FILE["published"]["vocab_size"], FILE["published"]["num_hidden_layers"]) == (128, 262144, 32)
    # every width, the experts a token, the shared experts and the window as published
    assert (FILE["hidden_size"], FILE["intermediate_size"], FILE["head_dim"], FILE["num_attention_heads"],
            FILE["num_key_value_heads"], FILE["num_experts_per_tok"], FILE["num_shared_experts"],
            FILE["sliding_window"]) == (4096, 4096, 128, 128, 8, 8, 4, 4096)
    assert FILE["layer_pattern"] == {"period": 4, "leading_dense": 0} and FILE["deployment"]["chips_per_layer"] == 8
    assert "float32" in FILE["precision"]["router"] and "tied" in FILE["precision"]["embedding"]
    assert len(FILE["assumed"]) >= 6 and manifest.lowering(FILE) is family.lowered_programs
    assert manifest.reference_module(FILE).__name__ == "benchmarks.harness.cohere2_moe_reference"


def test_the_cell_and_its_mix_are_the_issue_s():
    spec, cell = M.traffic("gen-wide"), M.cell(CELL)
    assert (spec["loop"], spec["clients"], spec["block"], spec["max_requests_per_s"]) == ("closed", 96, 96, 16.0)
    assert spec["prompt_tokens"] == {"dist": "lognormal", "median": 64, "sigma": 0.7, "min": 16, "max": 256}
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 384, "sigma": 0.5, "min": 128, "max": 768}
    assert spec["pool_seed"] not in (M.traffic("gen-batch")["pool_seed"], M.traffic("chat-short")["pool_seed"])
    engine = cell["engine"]
    assert (engine["max_slots"], engine["max_seq_len"], engine["kv_page_size"], engine["kv_dtype"]) in (
        (64, 1024, 16, "bf16"), (48, 1024, 16, "bf16"))
    assert engine["prefill_buckets"] == [32, 64, 128, 256] and engine["prefill_chunk_tokens"] == 256
    assert cell["trace"] == {"start_s": 12.0, "seconds": 3.0} and cell["drain_s"] == 30.0
    assert cell["correct"]["sample_requests"] == 3 and cell["correct"]["min_tokens"] == 100
    reported = {m["name"] for m in M.metrics_for("per_layer", CELL)}
    assert reported == set(NEW) | {
        "engine.batch_occupancy.batch", "kv.page_fill.batch", "step.decode_ms.batch", "paged_attention_roofline.batch",
        "device.idle_share.batch", "engine.host_ms_per_block.batch", "engine.slot_use.batch", "device.idle_host_bound.batch"}
    assert {m["name"] for m in M.metrics_for("end_to_end", CELL)} == {"tok_s", "setup_s"}
    why = M.workload(CELL)["why"]
    assert len(why) <= 200 and "eight times its share" in why and "window never binds" in why


# ------------------------------------------------------- the cost functions
def test_costs_are_the_issue_s_arithmetic_from_the_file_alone():
    assert costs.attention_params(FILE) == 2 * 4096 * 16384 + 2 * 4096 * 1024 == 142_606_336
    assert costs.expert_params(FILE) == 3 * 4096 * 4096 and costs.router_params(FILE) == 4096 * 128
    assert costs.layer_params_held(FILE) == 142_606_336 + 524_288 + 20 * 50_331_648 == 1_149_763_584
    assert costs.weight_bytes(FILE) / 2**30 == pytest.approx(8.84, abs=0.01)   # 9.49 GB resident
    assert costs.kv_bytes_per_token(FILE) == 32 * 1024
    assert costs.routed_pairs_per_token(FILE) == 1.0                             # 8 of 128, 16 held
    assert costs.layer_params_per_token(FILE) == 142_606_336 + 524_288 + 5 * 50_331_648
    # at 64 rows nearly every held expert is reached: 16 x (1 - (15/16)^64) = 15.74 of them, and the 4 shared
    assert costs.expert_call_bytes(FILE, 64) == pytest.approx((16 * (1 - (15 / 16) ** 64) + 4) * 50_331_648)
    assert costs.expert_call_bytes(FILE, 1) == pytest.approx((1 + 4) * 50_331_648)
    assert costs.expert_call_flops(FILE, 64) == 2 * 50_331_648 * 64 * 5
    # bandwidth-bound at serving batch sizes: 1.21 ms of bytes against 0.16 ms of products
    assert costs.expert_call_bytes(FILE, 64) / 819e9 > 7 * costs.expert_call_flops(FILE, 64) / 197e12


def test_served_flops_count_the_share_and_exact_positions():
    one_decode = costs.served_flops(FILE, [], 1, 100, 1024)
    assert one_decode == 2 * 8 * costs.layer_params_per_token(FILE) + 2 * 4096 * 32768 + 4 * 8 * 128 * 128 * 100
    prompt = costs.served_flops(FILE, [(0, 10)], 0, 0, 1024)
    assert prompt == 10 * 2 * 8 * costs.layer_params_per_token(FILE) + 2 * 4096 * 32768 + 4 * 8 * 128 * 128 * 55
    # a later chunk brings no head of its own; its tokens see what came before them
    chunk = costs.served_flops(FILE, [(10, 4)], 0, 0, 1024)
    assert chunk == 4 * 2 * 8 * costs.layer_params_per_token(FILE) + 4 * 8 * 128 * 128 * (40 + 10)
    with pytest.raises(ValueError, match="passes the window"):
        costs.served_flops(FILE, [], 1, 5000, 8192)


# ------------------------------------------------------------- the readers
def dev(line, name, start_ms, dur_ms):
    return tr.Event(DEV, line, name, int(start_ms * MS), int(dur_ms * MS))


def span(name, start_ms, dur_ms):
    return tr.Event(HOST, "python3#4", name, int(start_ms * MS), int(dur_ms * MS))


def run_over(events, config, records=(), window_ms=(0, 100)):
    a, b = window_ms
    return RunData({"name": "x"}, config, {"engine": {"max_slots": 64, "max_seq_len": 1024}}, list(records),
                   (0.0, 1.0), (a / 1e3, b / 1e3), events, 0, {}, [], "TPU v5 lite")


ROUTED = "%fusion.7 = bf16[64,4096]{1,0} fusion(s8[128,4096,4096]{2,1,0} %get-tuple-element.9, s32[] %gte.2, bf16[64,4096]{1,0} %h), kind=kOutput"
SHARED = "%fusion.8 = bf16[64,4096]{1,0} fusion(s8[32,4096,4096]{2,1,0} %get-tuple-element.10, s32[] %gte.2, bf16[64,4096]{1,0} %h), kind=kOutput"
ATTN = "%fusion.9 = bf16[64,16384]{1,0} fusion(s8[8,4096,16384]{2,1,0} %get-tuple-element.3, s32[] %gte.2), kind=kOutput"
HAND = (
    [dev(tr.MODULE_LINE, "jit_decode_block_paged(17)", 10, 70), dev(tr.MODULE_LINE, "jit_prefill_compute(3)", 82, 10)]
    # two layer-calls of the decode program: 48 routed and 12 shared products each, 0.02 ms a product
    + [dev(tr.OPS_LINE, ROUTED, 11 + 0.02 * i, 0.02) for i in range(96)]
    + [dev(tr.OPS_LINE, SHARED, 20 + 0.02 * i, 0.02) for i in range(24)]
    + [dev(tr.OPS_LINE, ATTN, 30, 1.0)]
    # the loop around the layers carries the stacks in its tuple: not a product
    + [dev(tr.OPS_LINE, "%while.56 = (s32[], bf16[64,1,4096], s8[128,4096,4096]{2,1,0}, s8[32,4096,4096]{2,1,0}) while(%tuple.9), condition=%c, body=%b", 10.5, 60)]
    # the prefill's expert products are not the decode steps'
    + [dev(tr.OPS_LINE, ROUTED, 83, 0.5)]
    + [span("gofr.step#iter=1,mono_ns=1#", 5, 90),
       span("gofr.step.dispatch#blk=3,kind=decode,rows=48,steps=4,kv_tokens=900,chunk_rows=0,chunk_tokens=0,cold=0,win_rows=0#", 6, 2),
       span("gofr.step.commit#blk=2,tokens=192,retired=0,moe_rows=6144,moe_max=480#", 60, 2),
       span("gofr.step.commit#blk=3,tokens=0,retired=0,moe_rows=0,moe_max=0#", 70, 1),
       span("bench.mark:0", 0, 0)]
)


def test_counters_read_from_the_commit_spans():
    run = run_over(HAND, FILE)
    # two blocks of 4 steps over 8 layers and 16 held experts: 6144 pairs / 1024 expert-calls
    assert moe_layers.rows_per_expert(run) == pytest.approx(6144 / (2 * 4 * 8 * 16))
    # the fullest expert took 480 of 6144, the mean expert 384: the empty block weighs nothing
    assert moe_layers.load_imbalance(run) == pytest.approx(480 / 384)


def test_expert_products_are_found_by_the_stacks_among_the_operands_inside_the_decode_program():
    run = run_over(HAND, FILE)
    assert set(moe_layers.expert_operand_marks(FILE)) == {
        "s8[128,4096,4096]", "s8[8,16,4096,4096]", "s8[32,4096,4096]", "s8[8,4,4096,4096]"}
    found = moe_layers.expert_product_events(run)
    assert len(found) == 120 and sum(e.dur_ns for e in found) == pytest.approx(120 * 0.02 * MS)
    # 120 products = 2 calls of 3 x (16 + 4); each at least its bytes at 819 GB/s, at the dispatches' 48 rows
    least = 2 * costs.expert_call_bytes(FILE, 48) / 819e9
    assert moe_layers.experts_roofline_pct(run) == pytest.approx(100 * least / 0.0024)


def test_step_mfu_counts_this_chips_share():
    records = [{"prompt_tokens": 20, "token_ts": [0.010 + 0.001 * i for i in range(30)], "request_id": 1}]
    run = run_over(HAND, FILE, records)
    flops = costs.served_flops(FILE, [(0, 20)], 29, sum(20 + j - 1 for j in range(2, 31)), 1024)
    assert moe_layers.step_mfu_pct(run) == pytest.approx(100 * flops / (0.1 * 197e12))


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none_and_does_not_raise(name):
    read = M.reader(name)
    mistral = M.config("mistral-7b-v0.3-int8")
    plain = [e for e in HAND if "moe_rows" not in e.name]
    plain.append(span("gofr.step.commit#blk=2,tokens=192,retired=0#", 60, 2))   # the parent's commit span
    assert read(run_over(HAND, mistral)) is None                   # another architecture's configuration
    assert read(run_over([], FILE)) is None                        # no trace at all
    if name.startswith("moe.r") or name.startswith("moe.l"):
        assert read(run_over(plain, FILE)) is None                 # a program without the counters
    if "roofline" in name:
        assert read(run_over([e for e in HAND if e.name not in (ROUTED, SHARED)], FILE)) is None


# ------------------------------------------------------------ the rehearsal
TINY_MOE = {
    "name": "tiny-moe", "source": "tests/benchmark (not a published model)", "model_type": "cohere2_moe",
    "hidden_size": 64, "intermediate_size": 64, "num_hidden_layers": 8,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 320,
    "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 2, "sliding_window": 8,
    "max_position_embeddings": 256, "rope_theta": 50000.0, "layer_norm_eps": 1e-5, "logit_scale": 1,
    "tie_word_embeddings": True, "reduced": ["num_experts"], "published": {"num_experts": 16},
    "deployment": {"chips_per_layer": 4, "first_expert": 8, "stands_for": "four chips share each layer"},
    "assumed": [], "factory": "benchmarks.harness.cohere2_moe_family:build",
    "reference": "benchmarks/harness/cohere2_moe_reference.py",
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """cellbench_tiny's root, and in it a cell of the new family: a share
    (experts 8..11 of 16) of a model with a window of 8, so every request
    decodes past it."""
    path = cellbench_tiny.make_root(str(tmp_path_factory.mktemp("tinymoe")), gap_max=0.2)

    def w(rel, obj):
        with open(os.path.join(path, rel), "w", encoding="utf-8") as fh:
            json.dump(obj, fh)

    w("benchmarks/configs/tiny-moe.json", TINY_MOE)
    w("benchmarks/cells/tinymoe.closed.json", json.load(open(os.path.join(path, "benchmarks/cells/tiny.open.json"))))
    w("benchmarks/traffic/tinymoe-closed.json", dict(cellbench_tiny.LENGTHS, name="tinymoe-closed", loop="closed",
                                                     clients=5, block=8, pool_seed=4))
    data = json.load(open(os.path.join(path, "BENCHMARK.json")))
    data["configs"].append({"name": "tiny-moe", "source": TINY_MOE["source"], "file": "benchmarks/configs/tiny-moe.json",
                            "reduced": ["num_experts"], "why": "CPU test"})
    data["workloads"].append({"name": "tinymoe.closed", "config": "tiny-moe", "traffic": "tinymoe-closed",
                              "chips": 1, "why": "CPU test"})
    for m in data["end_to_end"] + data["per_layer"]:
        if m["name"] == "tok_s" or m["name"] in NEW:
            m["workloads"] = m["workloads"] + ["tinymoe.closed"]
    w("BENCHMARK.json", data)
    return path


def test_the_rehearsal_serves_the_new_family_and_its_reference_agrees(root, capsys):
    code, result = runner.run_cell(root, "tinymoe.closed", 2**31 + 29, 2.0, False, time.monotonic(),
                                   platform="cpu", control_bits=4)
    err = capsys.readouterr().err
    assert code == 0 and result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 5
    checks = result["checks"]
    # the family serves bf16 activations at any width: six seeds here read 0-0.04 for the
    # program and 0.60-1.10 for the int4 control, and 0.2 parts them
    assert checks["gap_max"]["value"] <= checks["gap_max"]["limit"] == 0.2 < checks["control_gap_max"]["value"]
    assert set(result["metrics"]) == {"tok_s", "setup_s"} and result["metrics"]["tok_s"]["value"] > 0
    assert "reference benchmarks/harness/cohere2_moe_reference.py (benchmarks.harness.cohere2_moe_reference) over" in err
    # its own lowering named the programs the warm-up uses; on the CPU none holds a Mosaic call
    assert "prefill_compute[32]=0" in err and "decode_block_paged=0" in err


def test_the_rehearsal_traced_reports_what_the_cpu_can_and_no_device_number(root):
    code, result = runner.run_cell(root, "tinymoe.closed", 31, 2.0, True, time.monotonic(), platform="cpu")
    assert code == 0 and result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result
    # the four new readers ran and found nothing to read: no device plane on the CPU, and the
    # trace of a root that is not the checkout's is not where the span reader looks
    assert not set(result["metrics"]) & set(NEW)


def test_the_parent_s_program_fails_the_new_cell_at_once(root, monkeypatch):
    """A checkout without ``models/cohere2_moe.py`` cannot build the
    configuration: the factory raises before a weight is made, and the run
    ends with an error, not a hang."""
    import builtins

    real = builtins.__import__

    def no_model(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "gofr_tpu.models" and "cohere2_moe" in (fromlist or ()):
            raise ImportError("cannot import name 'cohere2_moe' from 'gofr_tpu.models'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_model)
    t = time.monotonic()
    with pytest.raises(ImportError, match="cohere2_moe"):
        runner.run_cell(root, "tinymoe.closed", 5, 2.0, False, time.monotonic(), platform="cpu")
    assert time.monotonic() - t < 60
