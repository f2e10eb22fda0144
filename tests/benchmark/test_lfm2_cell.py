"""The ``lfm2_moe`` family under the benchmark: the file and the cell
against the issue's numbers, its cost functions from the configuration's
keys against hand counts, its readers on hand-made events (exact
arithmetic; None, never 0, on another architecture's run or a program
without the counters), and a two-second rehearsal on the CPU at tiny
widths through the family's own factory, ``lowered_programs`` and
reference. Nothing here is a device number."""

import json
import os
import time

import pytest

import cellbench_tiny
from benchmarks.harness import lfm2_moe_costs as costs
from benchmarks.harness import lfm2_moe_family as family
from benchmarks.harness import lfm2_moe_layers as lfm2_layers
from benchmarks.harness import manifest, runner, traffic
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.runner import RunData

REPO = cellbench_tiny.REPO
M = Manifest(REPO)
FILE = M.config("lfm2-8b-a1b-int8")
CELL = "lfm2.tools"
DEV, HOST, MS = "/device:TPU:0", "/host:CPU", 1_000_000
NEW = ["step.mfu.tools", "step.decode_ms.tools", "moe.experts_roofline.tools", "shortconv.roofline.tools",
       "attention.kv_read_roofline.tools", "moe.rows_per_expert.tools", "moe.load_imbalance.tools"]
# not engine.batch_occupancy.batch: it counts the client's token stamps, which the profiler delays at this cell's rate
ACCEPTED = ["kv.page_fill.batch", "device.idle_share.batch", "engine.host_ms_per_block.batch", "engine.slot_use.batch",
            "device.idle_host_bound.batch"]
KINDS = ["conv", "conv", "full_attention"] + ["conv", "conv", "conv", "full_attention"] * 4 + [
    "conv", "conv", "full_attention", "conv", "conv"]
# the catalog row's ``config``, every key and value (model-configs guide, architectures.jsonl)
CATALOG = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168, "layer_types": KINDS,
           "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05,
           "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
           "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000,
           "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


# ------------------------------------------------------------ the file
def test_the_file_is_the_catalog_row_with_nothing_cut():
    assert {k: FILE[k] for k in CATALOG} == CATALOG
    assert FILE["reduced"] == [] and "published" not in FILE and "deployment" not in FILE
    assert FILE["head_dim"] == 64 == FILE["hidden_size"] // FILE["num_attention_heads"]
    assert "12,288 B a token" in FILE["precision"]["kv_cache"] and "float32 conv tails" in FILE["precision"]["state"]
    assert "int8" in FILE["precision"]["weights"] and "float32" in FILE["precision"]["router"]
    assumed = " ".join(FILE["assumed"])
    for word in ("8.34 B parameters tied against 8.47 B untied", "2048 / 32 = 64", "[B | C | u]", "last two positions",
                 "a tenth of it", "PLUS 1e-6", "deviation 0.02", "+-3^-1/2", "deviation 2048^-1/2", "not the source's"):
        assert word in assumed, word
    assert manifest.lowering(FILE) is family.lowered_programs
    assert manifest.reference_module(FILE).__name__ == "benchmarks.harness.lfm2_moe_reference"
    cfg = family.program_config(FILE)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size) == (24, 2048, 32, 8, 64, 65536)
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.n_experts, cfg.top_k, cfg.n_dense_layers, cfg.conv_kernel) == (7168, 1792, 32, 4, 2, 3)
    assert list(cfg.layer_types) == KINDS and (cfg.rope_theta, cfg.norm_eps, cfg.routed_scaling) == (1e6, 1e-5, 1.0)
    with pytest.raises(ValueError, match="conv_bias"):
        family.program_config(dict(FILE, conv_bias=True))


def test_the_cell_and_its_mix_are_the_issue_s():
    spec, cell = M.traffic("tool-turns"), M.cell(CELL)
    assert (spec["loop"], spec["clients"], spec["block"]) == ("closed", 96, 96)
    assert spec["prompt_tokens"] in ({"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 64, "max": 1024},
                                     {"dist": "constant", "value": 320, "min": 64, "max": 1024})  # the named fallback
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.5, "min": 192, "max": 1536}
    assert spec["pool_seed"] == 17 and spec["pool_seed"] not in {
        M.traffic(t)["pool_seed"] for t in ("gen-batch", "chat-short", "gen-wide", "gen-long", "reason-long")}
    engine = cell["engine"]
    assert (engine["max_slots"], engine["max_seq_len"], engine["kv_page_size"], engine["kv_dtype"]) == (64, 2560, 16, "bf16")
    assert engine["prefill_buckets"] == [32, 64, 128, 256] and engine["prefill_chunk_tokens"] == 256
    assert engine["prefix_cache_entries"] == 0 and "multi_step" not in engine  # the default block of 4 steps
    assert cell["correct"]["sample_requests"] == 3 and cell["correct"]["min_tokens"] == 100
    assert spec["prompt_tokens"]["max"] + spec["output_tokens"]["max"] == engine["max_seq_len"]
    assert {m["name"] for m in M.metrics_for("end_to_end", CELL)} == {"tok_s", "setup_s"}
    why = M.workload(CELL)["why"]
    assert len(why) <= 200 and "96 clients" in why and "64 slots x 2560" in why and "8 rows" in why
    assert M.workload(CELL)["chips"] == 1 and M.workload(CELL)["config"] == "lfm2-8b-a1b-int8"
    # about 31 % of the prompts pass the largest bucket and prefill in 2-4 chunks
    prompts = traffic.stratified_sizes(spec["prompt_tokens"], 96)
    assert 0.25 < sum(p > 256 for p in prompts) / 96 < 0.37 and max(prompts) <= 1024 == 4 * 256
    # each expert sees 64 x 4 / 32 = 8 rows a step: held_experts' every-row path
    from gofr_tpu.ops import moe
    assert not moe.groups_rows(64, 32, 4) and 64 * 4 // 32 == 8


def test_the_cell_is_on_exactly_the_five_batch_lists_and_its_seven():
    reported = {m["name"] for m in M.metrics_for("per_layer", CELL)}
    assert reported == set(NEW) | set(ACCEPTED)
    assert CELL not in next(m for m in M.data["per_layer"] if m["name"] == "engine.batch_occupancy.batch")["workloads"]
    for m in M.data["per_layer"]:
        if m["name"] in NEW:
            assert m["moves"] == "tok_s" and m["workloads"] == [CELL] and m["name"].endswith(".tools")
        elif CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL  # appended, nothing else changed
    assert sum(1 for w in M.data["workloads"] if w["chips"] == 4) == 0


# ------------------------------------------------------- the cost functions
def test_costs_are_the_issue_s_arithmetic_from_the_file_alone():
    assert costs.layer_counts(FILE) == {"conv": 18, "attn": 6, "dense": 2, "moe": 22}
    assert costs.expert_params(FILE) == 3 * 2048 * 1792 == 11_010_048  # 11.01 M an expert
    assert costs.conv_params(FILE) == 2048 * 6144 + 2048 * 2048 == 16_777_216
    assert costs.attention_params(FILE) == 4_194_304 + 2 * 1_048_576 + 4_194_304 == 10_485_760
    assert costs.dense_params(FILE) == 44_040_192
    m = costs.matrix_params(FILE)
    assert m == {"experts": 22 * 32 * 11_010_048, "conv": 18 * 16_777_216, "attn": 6 * 10_485_760,
                 "dense": 2 * 44_040_192, "router": 22 * 2048 * 32}
    assert m["experts"] / 1e6 == pytest.approx(7751.1, abs=0.1) and m["conv"] / 1e6 == pytest.approx(302.0, abs=0.1)
    assert costs.total_params(FILE) / 1e9 == pytest.approx(8.34, abs=0.005)  # the published 8.3 B, tied
    assert (costs.total_params(FILE) + costs.embedding_params(FILE)) / 1e9 == pytest.approx(8.47, abs=0.005)  # untied
    assert costs.active_params(FILE) / 1e9 == pytest.approx(1.56, abs=0.005)
    assert costs.weight_bytes(FILE) / 1e9 == pytest.approx(8.50, abs=0.005)  # 8.20 int8, 0.27 embedding, scales
    assert costs.expert_bytes(FILE) == 11_010_048 + 4 * (2 * 1792 + 2048)
    # 6 layers x 2 x 8 heads x 64 x 2 B a token; 18 layers x 2 positions x 2048 x 4 B a slot
    assert costs.kv_bytes_per_token(FILE) == 12_288 and costs.slot_state_bytes(FILE) == 294_912
    cache = costs.cache_bytes(FILE, 64, 2560)
    assert cache == {"kv": 64 * 2560 * 12_288, "state": 64 * 294_912}
    assert (sum(cache.values()) + costs.weight_bytes(FILE)) / 1e9 == pytest.approx(10.53, abs=0.01)  # 62 % of 17.2 GB
    # a step of 64 rows reaches all 32 experts of every layer: 91 % of its bytes are experts
    experts = 22 * 32 * costs.expert_bytes(FILE)
    assert 0.90 < experts / costs.weight_bytes(FILE) < 0.92


def test_served_flops_count_the_model_s_need():
    active, head = costs.active_params(FILE), costs.embedding_params(FILE)
    assert costs.served_flops(FILE, [], 1, 6 * 3000) == 2 * active + 4 * 32 * 64 * 6 * 3000
    # a prompt of 10: 10 positions through every layer, the head once, token i sees i positions
    assert costs.served_flops(FILE, [(0, 10)], 0, 0) == 10 * 2 * active - 9 * 2 * head + 4 * 32 * 64 * 6 * 55
    # a later chunk brings no head
    assert costs.served_flops(FILE, [(512, 4)], 0, 0) == 4 * 2 * (active - head) + 4 * 32 * 64 * 6 * (4 * 512 + 10)
    assert costs.conv_bytes(FILE, 64, 1) == 16_777_216 + 4 * 8192 + 64 * (2 * 2 * 2048 * 4 + 2 * 2048 * 2)


# ------------------------------------------------------------- the readers
def dev(line, name, start_ms, dur_ms):
    return tr.Event(DEV, line, name, int(start_ms * MS), int(dur_ms * MS))


def span(name, start_ms, dur_ms):
    return tr.Event(HOST, "python3#4", name, int(start_ms * MS), int(dur_ms * MS))


def run_over(events, config, records=(), window_ms=(0, 100)):
    a, b = window_ms
    cell = {"engine": {"max_slots": 64, "max_seq_len": 2560, "kv_page_size": 16}}
    return RunData({"name": "x"}, config, cell, list(records), (0.0, 1.0), (a / 1e3, b / 1e3), events, 0, {}, [],
                   "TPU v5 lite")


EXPERT = "%fusion.101 = bf16[64,1792]{1,0} fusion(bf16[64,2048]{1,0} %x, s8[704,2048,1792]{2,1,0} %gate, s32[] %e), kind=kOutput"
DOWN = "%fusion.102 = f32[64,2048]{1,0} fusion(bf16[64,1792]{1,0} %a, s8[704,1792,2048]{2,1,0} %down, s32[] %e), kind=kOutput"
CONV_IN = "%fusion.7 = bf16[64,1,6144]{2,1,0} fusion(bf16[64,1,2048]{2,1,0} %a, s8[18,2048,6144]{2,1,0} %w, s32[] %i), kind=kOutput"
CONV_TAIL = "%dynamic-update-slice_fusion.3 = f32[18,64,2,2048]{3,2,1,0} fusion(f32[18,64,2,2048]{3,2,1,0} %t, f32[64,2,2048]{2,1,0} %n), kind=kLoop"
KERNEL = "%paged_decode_attention.5 = bf16[64,32,128]{2,1,0} custom-call(%q, %k, %v), custom_call_target=\"tpu_custom_call\""
APPEND = "%paged_kv_append.3 = (bf16[6,10241,4,16,128]{4,3,2,1,0}, bf16[6,10241,4,16,128]{4,3,2,1,0}) custom-call(%a, %b), custom_call_target=\"tpu_custom_call\""
LOOP = "%while.{} = (s32[], s8[704,2048,1792]{{2,1,0}}, f32[18,64,2,2048]{{3,2,1,0}}) while(%tuple.{}), condition=%c, body=%b"
COMMIT = "gofr.step.commit#blk={},tokens=256,retired=0,moe_rows=22528,moe_max={},moe_reached=2816,conv_rows=4608,attn_kv=1536000#"
HAND = (
    [dev(tr.MODULE_LINE, "jit_decode_block_paged(17)", 10, 30), dev(tr.MODULE_LINE, "jit_ragged_step_paged(18)", 45, 40),
     dev(tr.MODULE_LINE, "jit_prefill_compute(3)", 90, 5)]
    # the loop over the block's steps around the appends, in both programs
    + [dev(tr.OPS_LINE, LOOP.format(56, 1), 10.5, 29), dev(tr.OPS_LINE, APPEND, 11, 0.01)]
    + [dev(tr.OPS_LINE, LOOP.format(61, 3), 66.5, 18), dev(tr.OPS_LINE, APPEND, 67, 0.01)]
    # in the decode block: the experts' products, the conv mixers, the attention kernel
    + [dev(tr.OPS_LINE, EXPERT, 12, 15), dev(tr.OPS_LINE, EXPERT, 13, 15), dev(tr.OPS_LINE, DOWN, 14, 15)]
    + [dev(tr.OPS_LINE, CONV_IN, 15, 1.5), dev(tr.OPS_LINE, CONV_TAIL, 16, 0.5)]
    + [dev(tr.OPS_LINE, KERNEL, 17 + i, 0.5) for i in range(4)] + [dev(tr.OPS_LINE, KERNEL, 68, 0.5)]
    # the same shapes in a ragged dispatch (its chunk's products beside its steps) are not counted
    + [dev(tr.OPS_LINE, EXPERT, 50, 9), dev(tr.OPS_LINE, CONV_IN, 60, 3)]
    + [span("gofr.step#iter=1,mono_ns=1#", 5, 90),
       span("gofr.step.prefill#rid=4,bucket=256,tokens=200,route=bucketed#", 5.5, 0.4),
       span("gofr.step.dispatch#blk=3,kind=decode,rows=64,steps=4,kv_tokens=64000,chunk_rows=0,chunk_tokens=0,cold=0#", 6, 2),
       span("gofr.step.dispatch#blk=4,kind=ragged,rows=64,steps=4,kv_tokens=64256,chunk_rows=1,chunk_tokens=256,cold=0#", 40, 2),
       span(COMMIT.format(3, 900), 60, 2), span(COMMIT.format(4, 1100), 88, 2),
       span("bench.mark:0", 0, 0)]
)
RECORDS = [{"prompt_tokens": 300, "token_ts": [0.010 + 0.001 * i for i in range(30)], "request_id": 1}]


def test_the_counters_are_summed_over_the_commits_of_the_whole_iterations():
    run = run_over(HAND, FILE, RECORDS)
    assert lfm2_layers.step_counts(run) == {"conv_rows": 2 * 4608, "attn_kv": 2 * 1_536_000, "moe_rows": 2 * 22528,
                                            "moe_max": 2000, "moe_reached": 2 * 2816, "blocks": 2}
    calls = 2 * 4 * 22
    assert lfm2_layers.rows_per_expert(run) == pytest.approx(2 * 22528 / (calls * 32))  # 8 rows: 64 x 4 / 32
    assert lfm2_layers.load_imbalance(run) == pytest.approx(2000 / (2 * 22528 / 32))


def test_a_decode_step_is_timed_by_the_loop_around_its_appends_in_either_program():
    run = run_over(HAND, FILE, RECORDS)
    assert lfm2_layers.decode_step_ms(run) == pytest.approx((29 + 18) / (2 * 4))


def test_each_roofline_divides_the_needed_work_by_the_device_time():
    """The work is the device's own count over the committed blocks, the
    time the marked ops of the decode block's executions (the ragged
    dispatch's are beside a chunk and not counted) — or, for attention,
    the kernel's events in the whole iterations."""
    run = run_over(HAND, FILE, RECORDS)
    calls = 1 * 4 * 22  # one decode execution whole in the iterations, four steps, 22 expert layers
    reached, rows = 2 * 2816 / (2 * calls), 2 * 22528 / (2 * calls)
    least = max(reached * costs.expert_bytes(FILE) / 819e9, 2 * costs.expert_params(FILE) * rows / 197e12)
    assert lfm2_layers.experts_roofline_pct(run) == pytest.approx(100 * calls * least / 45e-3)
    conv = costs.conv_bytes(FILE, 4608, 4 * 18) / 819e9
    assert lfm2_layers.conv_roofline_pct(run) == pytest.approx(100 * conv / 2e-3)
    assert lfm2_layers.kv_read_roofline_pct(run) == pytest.approx(100 * 2 * 1_536_000 * 2048 / 819e9 / 2.5e-3)
    flops = costs.served_flops(FILE, [(0, 300)], 2 * 4608 // 18, 2 * 1_536_000)
    assert lfm2_layers.step_mfu_pct(run) == pytest.approx(100 * flops / (0.09 * 197e12))  # the whole iterations: 5 to 95 ms
    # the marks are the file's shapes
    assert lfm2_layers.expert_marks(run) == ("s8[704,2048,1792]", "s8[22,32,2048,1792]", "s8[704,1792,2048]",
                                             "s8[22,32,1792,2048]", "f32[704,1792]", "f32[704,2048]")
    assert lfm2_layers.conv_marks(run)[:3] == ("s8[18,2048,6144]", "s8[18,2048,2048]", "f32[18,64,2,2048]")


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none_and_does_not_raise(name):
    read = M.reader(name)
    for other in ("mistral-7b-v0.3-int8", "command-a-plus-ep8-int8", "deepseek-v3.2-exp-ep8-int8",
                  "phi-4-mini-flash-reasoning-int8"):
        assert read(run_over(HAND, M.config(other), RECORDS)) is None   # another architecture's configuration
    assert read(run_over([], FILE, RECORDS)) is None                     # no trace at all
    # the parent's program: no conv_rows on the commit spans
    bare = [tr.Event(e.plane, e.line, e.name.split(",moe_rows")[0] + "#" if e.name.startswith("gofr.step.commit") else e.name,
                     e.start_ns, e.dur_ns) for e in HAND]
    if name != "step.decode_ms.tools":  # (that one reads the device alone)
        assert read(run_over(bare, FILE, RECORDS)) is None


# ------------------------------------------------------------ the rehearsal
TINY_LFM2 = {
    "name": "tiny-lfm2", "source": "tests/benchmark (not a published model)", "model_type": "lfm2_moe",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 9,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv"],
    "num_dense_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
    "num_experts_per_tok": 4, "vocab_size": 320, "max_position_embeddings": 256, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-5, "rope_theta": 1e6, "routed_scaling_factor": 1, "use_expert_bias": True,
    "norm_topk_prob": True, "reduced": [], "assumed": [], "factory": "benchmarks.harness.lfm2_moe_family:build",
    "reference": "benchmarks/harness/lfm2_moe_reference.py",
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """cellbench_tiny's root, and in it a cell of the new family: prompts
    of 24-100 tokens through buckets of 32 and chunks of 32 (boundaries at
    32, 64, 96: every offset mod 3, the tails carried), closed loop. The
    largest single gap is held, as in the cell: the program (bf16
    activations, int8 weights) reads 0-0.044 here over six seeds and the
    int4 control 1.6-3.3; the limit 0.3 lies seven times above the one and
    five times below the other."""
    path = cellbench_tiny.make_root(str(tmp_path_factory.mktemp("tinylfm2")), gap_max=GAP_MAX)

    def w(rel, obj):
        with open(os.path.join(path, rel), "w", encoding="utf-8") as fh:
            json.dump(obj, fh)

    cell = json.load(open(os.path.join(path, "benchmarks/cells/tiny.open.json")))
    cell["engine"] = dict(cell["engine"], prefill_buckets=[32], prefill_chunk_tokens=32)
    w("benchmarks/configs/tiny-lfm2.json", TINY_LFM2)
    w("benchmarks/cells/tinylfm2.closed.json", cell)
    w("benchmarks/traffic/tinylfm2-closed.json", dict(
        cellbench_tiny.LENGTHS, name="tinylfm2-closed", loop="closed", clients=5, block=8, pool_seed=7,
        prompt_tokens={"dist": "lognormal", "median": 50, "sigma": 0.5, "min": 24, "max": 100}))
    data = json.load(open(os.path.join(path, "BENCHMARK.json")))
    data["configs"].append({"name": "tiny-lfm2", "source": TINY_LFM2["source"], "file": "benchmarks/configs/tiny-lfm2.json",
                            "reduced": [], "why": "CPU test"})
    data["workloads"].append({"name": "tinylfm2.closed", "config": "tiny-lfm2", "traffic": "tinylfm2-closed",
                              "chips": 1, "why": "CPU test"})
    for m in data["end_to_end"] + data["per_layer"]:
        if m["name"] == "tok_s" or m["name"] in NEW:
            m["workloads"] = m["workloads"] + ["tinylfm2.closed"]
    w("BENCHMARK.json", data)
    return path


GAP_MAX = 0.3


def test_the_rehearsal_serves_the_new_family_and_its_reference_agrees(root, capsys):
    code, result = runner.run_cell(root, "tinylfm2.closed", 2**31 + 39, 2.0, False, time.monotonic(),
                                   platform="cpu", control_bits=4)
    err = capsys.readouterr().err
    assert code == 0 and result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 5
    checks = result["checks"]
    assert 3 * checks["gap_max"]["value"] <= checks["gap_max"]["limit"] == GAP_MAX <= checks["control_gap_max"]["value"] / 3
    assert set(result["metrics"]) == {"tok_s", "setup_s"} and result["metrics"]["tok_s"]["value"] > 0
    assert "reference benchmarks/harness/lfm2_moe_reference.py (benchmarks.harness.lfm2_moe_reference) over" in err
    # its lowering named the programs the warm-up uses, the chunked one among them; on the CPU none holds a Mosaic call
    assert "prefill_compute[32]=0" in err and "decode_block_paged=0" in err and "ragged_step_paged=0" in err
    assert "'pools': {'full':" in err  # health's kv_pages names the pool


def test_the_rehearsal_traced_reports_what_the_cpu_can_and_no_device_number(root):
    code, result = runner.run_cell(root, "tinylfm2.closed", 41, 2.0, True, time.monotonic(), platform="cpu")
    assert code == 0 and result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result
    # the device readers found no device plane on the CPU
    assert not {"step.mfu.tools", "step.decode_ms.tools", "moe.experts_roofline.tools", "shortconv.roofline.tools",
                "attention.kv_read_roofline.tools"} & set(result["metrics"])


def test_the_parent_s_program_fails_the_new_cell_at_once(root, monkeypatch):
    """A checkout without ``models/lfm2_moe.py`` cannot build the
    configuration: the factory raises before a weight is made, and the run
    ends with an error, not a hang."""
    import builtins

    real = builtins.__import__

    def no_model(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "gofr_tpu.models" and "lfm2_moe" in (fromlist or ()):
            raise ImportError("cannot import name 'lfm2_moe' from 'gofr_tpu.models'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_model)
    t = time.monotonic()
    with pytest.raises(ImportError, match="lfm2_moe"):
        runner.run_cell(root, "tinylfm2.closed", 5, 2.0, False, time.monotonic(), platform="cpu")
    assert time.monotonic() - t < 60
