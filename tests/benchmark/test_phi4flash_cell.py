"""The ``phi4flash`` family under the benchmark: the file and the cell
against the issue's numbers, its cost functions from the configuration's
keys against hand counts, its readers on hand-made events (exact
arithmetic; None, never 0, on another architecture's run or a program
without the spans), and a two-second rehearsal on the CPU at tiny widths
through the family's own factory, ``lowered_programs`` and reference.
Nothing here is a device number."""

import json
import os
import time

import pytest

import cellbench_tiny
from benchmarks.harness import manifest, runner, trace_reduce as tr
from benchmarks.harness import phi4flash_costs as costs
from benchmarks.harness import phi4flash_family as family
from benchmarks.harness import phi4flash_layers as phi_layers
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.runner import RunData

REPO = cellbench_tiny.REPO
M = Manifest(REPO)
FILE = M.config("phi-4-mini-flash-reasoning-int8")
CELL = "phi4flash.reason"
DEV, HOST, MS = "/device:TPU:0", "/host:CPU", 1_000_000
NEW = ["step.mfu.reason", "step.decode_ms.reason", "attention.kv_read_roofline.reason", "ssm.state_roofline.reason",
       "kv.window_held_share.reason", "prefill.cross_share.reason"]
# not engine.batch_occupancy.batch: it counts the client's token stamps, which the profiler delays here (PERF.md section 7)
ACCEPTED = ["kv.page_fill.batch", "device.idle_share.batch", "engine.host_ms_per_block.batch", "engine.slot_use.batch",
            "device.idle_host_bound.batch"]
# the catalog row's ``config``, every key and value (model-configs guide, architectures.jsonl)
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
           "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
           "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
           "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
           "vocab_size": 200064}


# ------------------------------------------------------------ the file
def test_the_file_is_the_catalog_row_with_nothing_cut():
    assert {k: FILE[k] for k in CATALOG} == CATALOG
    assert FILE["reduced"] == [] and "published" not in FILE and "deployment" not in FILE
    assert FILE["head_dim"] == 64 == FILE["hidden_size"] // FILE["num_attention_heads"]
    assert (FILE["mamba_d_state"], FILE["mamba_d_conv"], FILE["mamba_expand"], FILE["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert "one layer for the whole context, eight layers for the last 512 positions" in FILE["precision"]["kv_cache"]
    assert "float32 S 5120 x 16" in FILE["precision"]["state"] and "int8" in FILE["precision"]["weights"]
    assumed = " ".join(FILE["assumed"])
    for word in ("mamba_d_state 16", "[g | u]", "arXiv:2410.05258", "t - u < 512", "mb_per_layer 2", "head_dim",
                 "includes the D term", "int8 weight-only", "deviation 0.1", "log(1..16)", "No positional".lower()):
        assert word in assumed, word
    assert manifest.lowering(FILE) is family.lowered_programs
    assert manifest.reference_module(FILE).__name__ == "benchmarks.harness.phi4flash_reference"
    cfg = family.program_config(FILE)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        32, 2560, 40, 20, 64, 10240, 200064)
    assert (cfg.sliding_window, cfg.d_state, cfg.d_conv, cfg.d_inner, cfg.dt_rank, cfg.norm_eps) == (512, 16, 4, 5120, 160, 1e-5)
    with pytest.raises(ValueError, match="mb_per_layer"):
        family.program_config(dict(FILE, mb_per_layer=4))


def test_the_cell_and_its_mix_are_the_issue_s():
    spec, cell = M.traffic("reason-long"), M.cell(CELL)
    assert (spec["loop"], spec["clients"], spec["block"]) == ("closed", 48, 48)
    assert spec["prompt_tokens"] in ({"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 64, "max": 512},
                                     {"dist": "constant", "value": 192, "min": 64, "max": 512})  # the named fallback
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 3072, "sigma": 0.4, "min": 1536, "max": 7680}
    assert spec["pool_seed"] not in {M.traffic(t)["pool_seed"] for t in ("gen-batch", "chat-short", "gen-wide", "gen-long")}
    engine = cell["engine"]
    assert (engine["max_slots"], engine["max_seq_len"], engine["kv_page_size"], engine["kv_dtype"]) in (
        (32, 8192, 16, "bf16"), (24, 8192, 16, "bf16"))
    assert engine["prefill_buckets"] == [32, 64, 128, 256] and engine["prefill_chunk_tokens"] == 256
    assert "multi_step" not in engine  # the default block of 4 steps
    assert cell["trace"] == {"start_s": 30.0, "seconds": 3.0} and cell["drain_s"] == 45.0
    assert cell["correct"]["sample_requests"] == 2 and cell["correct"]["min_tokens"] == 100
    reported = {m["name"] for m in M.metrics_for("per_layer", CELL)}
    assert reported == set(NEW) | set(ACCEPTED)
    assert {m["name"] for m in M.metrics_for("end_to_end", CELL)} == {"tok_s", "setup_s"}
    for m in M.data["per_layer"]:
        if m["name"] in NEW:
            assert m["moves"] == "tok_s" and m["workloads"] == [CELL]
    why = M.workload(CELL)["why"]
    assert len(why) <= 200 and "48 clients" in why and "32 slots x 8192" in why and "8x" in why
    assert len(M.data["workloads"]) == 5 and all(w["chips"] == 1 for w in M.data["workloads"])
    # about a fifth of the prompts pass the largest bucket and are chunked
    from benchmarks.harness import traffic
    prompts = traffic.stratified_sizes(spec["prompt_tokens"], 48)
    assert 0.15 < sum(p > 256 for p in prompts) / 48 < 0.4 and max(prompts) <= 512 < 2 * 256 + 1


# ------------------------------------------------------- the cost functions
def test_costs_are_the_issue_s_arithmetic_from_the_file_alone():
    assert costs.layer_counts(FILE) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert costs.mlp_params(FILE) == 3 * 2560 * 10240 == 78_643_200
    mix = costs.mixer_params(FILE)
    assert mix["window"] == mix["full"] == 2560 * 5120 + 2560 * 2560 == 19_660_800
    assert mix["cross"] == 2 * 2560 * 2560 == 13_107_200 and mix["gmu"] == 2 * 2560 * 5120 == 26_214_400
    assert mix["mamba"] == 2560 * 10240 + 5120 * 2560 + 5120 * 192 + 160 * 5120 == 41_123_840
    # 9 x 119.8 + 9 x 98.3 + 7 x 104.9 + 7 x 91.8 M = 3,339 M; the tied embedding 512 M: the published 3.8 B
    assert costs.layer_matrix_params(FILE) == 9 * 119_767_040 + 9 * 98_304_000 + 7 * 104_857_600 + 7 * 91_750_400
    assert costs.matrix_params(FILE) == costs.layer_matrix_params(FILE) + 200064 * 2560
    assert costs.total_params(FILE) / 1e9 == pytest.approx(3.85, abs=0.005)
    assert costs.weight_bytes(FILE) / 1e9 == pytest.approx(4.42, abs=0.01)   # 3.34 GB int8, 1.02 GB bf16 embedding, scales
    # a token costs 5,120 B in each layer that stores it; a slot's state is 3.2 MB
    assert costs.kv_bytes_per_position(FILE) == 20 * 64 * 2 * 2 == 5120
    assert costs.state_bytes_per_layer(FILE) == 5120 * 16 * 4 == 327_680
    assert costs.slot_state_bytes(FILE) == 9 * (327_680 + 3 * 5120 * 2) == 3_225_600
    cache = costs.cache_bytes(FILE, 32, 8192)
    assert cache == {"full": 32 * 8192 * 5120, "window": 32 * 528 * 8 * 5120, "state": 32 * 3_225_600}
    assert sum(cache.values()) + costs.weight_bytes(FILE) == pytest.approx(6.56e9, rel=0.01)  # 41 % of the chip
    # a row and step: (8 x context + 8 x min(context, 512)) x 5,120 B — the one cached layer is read eight times
    assert costs.attention_positions(FILE, [2500]) == (8 * 2500, 8 * 512)
    assert costs.attention_positions(FILE, [100, 7000]) == (8 * 7100, 8 * (100 + 512))
    assert costs.attention_bytes(FILE, [2500] * 32) == 32 * (8 * 2500 + 8 * 512) * 5120
    assert costs.state_bytes(FILE, 32) == 32 * 9 * 2 * 327_680
    step = costs.step_bytes(FILE, [2500] * 32)
    assert step == {"weights": costs.weight_bytes(FILE), "attention": 3_947_888_640, "state": 188_743_680}
    # the model's FLOPs, 40 query heads of 64: 4 x 40 x 64 a layer-position, never the padded queries' 128
    assert costs.attention_flops(FILE, 10) == 4 * 40 * 64 * 10


def test_served_flops_run_the_upper_half_once_a_prompt():
    every, lower = costs.matrix_params(FILE), costs.self_matrix_params(FILE)
    assert lower == 9 * 119_767_040 + 8 * 98_304_000 + 2560 * 2560
    one = costs.served_flops(FILE, [], 1, sum(costs.attention_positions(FILE, [3000])))
    assert one == 2 * every + costs.attention_flops(FILE, 8 * 3000 + 8 * 512) + costs.scan_flops(FILE, 1)
    # a prompt of 10: the self-decoder over 10 positions (window attention over 1 + 2 + ... + 10), the rest on one
    prompt = costs.served_flops(FILE, [(0, 10)], 0, 0)
    assert prompt == 10 * 2 * lower + 2 * (every - lower) + costs.attention_flops(FILE, 8 * 55) + costs.scan_flops(FILE, 10)
    # a later chunk brings no upper half; its tokens see a full window
    chunk = costs.served_flops(FILE, [(600, 4)], 0, 0)
    assert chunk == 4 * 2 * lower + costs.attention_flops(FILE, 8 * 4 * 512) + costs.scan_flops(FILE, 4)


# ------------------------------------------------------------- the readers
def dev(line, name, start_ms, dur_ms):
    return tr.Event(DEV, line, name, int(start_ms * MS), int(dur_ms * MS))


def span(name, start_ms, dur_ms):
    return tr.Event(HOST, "python3#4", name, int(start_ms * MS), int(dur_ms * MS))


def run_over(events, config, records=(), window_ms=(0, 100)):
    a, b = window_ms
    cell = {"engine": {"max_slots": 32, "max_seq_len": 8192, "kv_page_size": 16}}
    return RunData({"name": "x"}, config, cell, list(records), (0.0, 1.0), (a / 1e3, b / 1e3), events, 0, {}, [],
                   "TPU v5 lite")


SCAN_ONE = "%add_dynamic-update-slice_fusion.5 = f32[9,32,16,5120]{3,2,1,0:T(8,128)} fusion(f32[9,32,16,5120]{3,2,1,0} %gte.5307, f32[32,5120]{1,0} %delta), kind=kLoop"
SCAN_LAYER = "%fusion.40 = f32[32,5120]{1,0} fusion(f32[32,16,5120]{2,1,0} %state, f32[32,16]{1,0} %c), kind=kLoop"
KERNEL = "%paged_decode_attention.22 = bf16[32,10,4,128]{3,2,1,0} custom-call(%a, %b), custom_call_target=\"tpu_custom_call\""
APPEND = "%paged_kv_append.3 = (bf16[8,1057,10,16,128]{4,3,2,1,0}, bf16[8,1057,10,16,128]{4,3,2,1,0}) custom-call(%a, %b), custom_call_target=\"tpu_custom_call\""
LOOP = "%while.{} = (s32[], bf16[8,1057,10,16,128]{{4,3,2,1,0}}, f32[9,32,16,5120]{{3,2,1,0}}) while(%tuple.{}), condition=%c, body=%b"
CHUNK_SCAN = "%fusion.90 = f32[16,5120]{1,0} fusion(f32[1,16,5120]{2,1,0} %state, f32[1,5120]{1,0} %delta), kind=kLoop"
HAND = (
    [dev(tr.MODULE_LINE, "jit_decode_block_paged(17)", 10, 30), dev(tr.MODULE_LINE, "jit_ragged_step_paged(18)", 45, 40),
     dev(tr.MODULE_LINE, "jit_prefill_compute(3)", 90, 5)]
    # the loop over the block's steps, around the appends, in both programs; a ragged dispatch's chunk is beside it
    + [dev(tr.OPS_LINE, LOOP.format(56, 1), 10.5, 29), dev(tr.OPS_LINE, APPEND, 11, 0.01), dev(tr.OPS_LINE, APPEND, 20, 0.01)]
    + [dev(tr.OPS_LINE, LOOP.format(60, 2), 46, 20), dev(tr.OPS_LINE, CHUNK_SCAN, 50, 2.0),
       dev(tr.OPS_LINE, LOOP.format(61, 3), 66.5, 18), dev(tr.OPS_LINE, APPEND, 67, 0.01)]
    # the attention kernel and the recurrence inside the decode steps
    + [dev(tr.OPS_LINE, KERNEL, 12 + i, 0.5) for i in range(4)] + [dev(tr.OPS_LINE, KERNEL, 68 + i, 0.5) for i in range(2)]
    + [dev(tr.OPS_LINE, SCAN_ONE, 13, 0.2), dev(tr.OPS_LINE, SCAN_LAYER, 14, 0.1), dev(tr.OPS_LINE, SCAN_ONE, 70, 0.2)]
    # the same shape outside a program that decodes is not counted
    + [dev(tr.OPS_LINE, SCAN_LAYER, 91, 0.5)]
    + [span("gofr.step#iter=1,mono_ns=1#", 5, 90),
       span("gofr.step.prefill#rid=4,bucket=256,tokens=200,route=bucketed,self_tokens=200,cross_tokens=1#", 5.5, 0.4),
       span("gofr.step.dispatch#blk=3,kind=decode,rows=24,steps=4,kv_tokens=72000,chunk_rows=0,chunk_tokens=0,cold=0,win_rows=24,win_pages_held=790,win_pages_freed=6#", 6, 2),
       span("gofr.step.dispatch#blk=4,kind=ragged,rows=24,steps=4,kv_tokens=72096,chunk_rows=1,chunk_tokens=256,cold=0,win_rows=24,win_pages_held=791,win_pages_freed=5,self_tokens=256,cross_tokens=0#", 40, 2),
       span("gofr.step.commit#blk=3,tokens=96,retired=0,attn_full=2304000,attn_win=393216,ssm_rows=96#", 60, 2),
       span("bench.mark:0", 0, 0)]
)
# one request: a prompt of 2,500, thirty tokens in the sub-window (29 decoded, contexts 2501..2529)
RECORDS = [{"prompt_tokens": 2500, "token_ts": [0.010 + 0.001 * i for i in range(30)], "request_id": 1}]


def test_a_decode_step_is_timed_by_the_loop_around_its_appends_in_either_program():
    run = run_over(HAND, FILE, RECORDS)
    assert phi_layers.decode_step_ms(run) == pytest.approx((29 + 18) / (2 * 4))
    assert phi_layers.decode_step_ms(run_over([e for e in HAND if "paged_kv_append" not in e.name], FILE)) is None


def test_the_attention_roofline_is_the_bytes_the_steps_had_to_read_over_the_kernels_time():
    """The work is the device's own count (the commit span's ``attn_full``
    and ``attn_win``), the time the kernel's events in the whole iterations
    (5 to 95 ms here): not the client's token stamps."""
    run = run_over(HAND, FILE, RECORDS)
    assert phi_layers.step_counts(run) == {"attn_full": 2304000, "attn_win": 393216, "ssm_rows": 96}
    bytes_read = 5120 * (2304000 + 393216)
    assert phi_layers.kv_read_roofline_pct(run) == pytest.approx(100 * bytes_read / 819e9 / (6 * 0.5e-3))
    assert phi_layers.kv_read_roofline_pct(run_over(HAND, FILE, [])) == phi_layers.kv_read_roofline_pct(run)


def test_the_recurrence_is_found_by_the_states_shape_inside_the_programs_that_decode():
    run = run_over(HAND, FILE, RECORDS)
    assert phi_layers.state_marks(run) == ("f32[32,16,5120]", "f32[1,32,16,5120]", "f32[9,32,16,5120]")
    events = phi_layers.recurrence_events(run)
    assert len(events) == 3 and sum(e.dur_ns for e in events) == pytest.approx(0.5 * MS)  # not the loops, not the chunk's row
    assert phi_layers.state_roofline_pct(run) == pytest.approx(100 * 96 * 9 * 2 * 327_680 / 819e9 / 0.5e-3)


def test_the_window_and_prefill_shares_read_the_spans():
    run = run_over(HAND, FILE, RECORDS)
    assert phi_layers.window_held_share_pct(run) == pytest.approx(100 * (790 + 791) / ((72000 + 72096) / 16))
    assert phi_layers.cross_share_pct(run) == pytest.approx(100 * 1 / 456)


def test_step_mfu_counts_the_models_flops():
    run = run_over(HAND, FILE, RECORDS)
    flops = costs.served_flops(FILE, [(0, 2500)], 96, 2304000 + 393216)
    assert phi_layers.step_mfu_pct(run) == pytest.approx(100 * flops / (0.09 * 197e12))  # the whole iterations: 5 to 95 ms


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none_and_does_not_raise(name):
    read = M.reader(name)
    for other in ("mistral-7b-v0.3-int8", "command-a-plus-ep8-int8", "deepseek-v3.2-exp-ep8-int8"):
        assert read(run_over(HAND, M.config(other), RECORDS)) is None   # another architecture's configuration
    assert read(run_over([], FILE, RECORDS)) is None                     # no trace at all
    # the parent's program: no new span keyword, no recurrence, no state
    bare = [tr.Event(e.plane, e.line, e.name.split(",win_pages_held")[0].split(",self_tokens")[0] + ("#" if "#" in e.name else ""),
                     e.start_ns, e.dur_ns) for e in HAND if e.name not in (SCAN_ONE, SCAN_LAYER, KERNEL)]
    bare = [e for e in bare if "attn_full" not in e.name]
    if name != "step.decode_ms.reason":  # (that one reads the device alone)
        assert read(run_over(bare, FILE, RECORDS)) is None


@pytest.mark.parametrize("name", ["paged_attention_roofline.batch", "step.mfu.batch", "step.decode_ms.batch", "step.mfu.wide",
                                  "step.mfu.long", "moe.rows_per_expert.long"])
def test_the_cell_is_not_on_the_lists_of_readers_that_count_one_cache_a_layer(name):
    assert CELL not in next(m for m in M.data["per_layer"] if m["name"] == name)["workloads"]


# ------------------------------------------------------------ the rehearsal
TINY_PHI = {
    "name": "tiny-phi", "source": "tests/benchmark (not a published model)", "model_type": "phi4flash",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 32, "mb_per_layer": 2, "layer_norm_eps": 1e-5,
    "vocab_size": 320, "max_position_embeddings": 256, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "hidden_act": "silu", "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 4, "reduced": [], "assumed": [], "factory": "benchmarks.harness.phi4flash_family:build",
    "reference": "benchmarks/harness/phi4flash_reference.py",
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """cellbench_tiny's root, and in it a cell of the new family: window
    32 under slots of 128, prompts of 40-100 tokens through chunks of 32
    (the chunk may not pass the window) and buckets, so that every request
    decodes past the window and most prompts carry their state from chunk
    to chunk. The largest single gap is held, as in the cell: the program
    (bf16 activations, int8 weights) reads 0.02-0.06 here and the int4
    control 0.6-1.5; the limit 0.2 lies three times from each."""
    path = cellbench_tiny.make_root(str(tmp_path_factory.mktemp("tinyphi")), gap_max=0.2)

    def w(rel, obj):
        with open(os.path.join(path, rel), "w", encoding="utf-8") as fh:
            json.dump(obj, fh)

    cell = json.load(open(os.path.join(path, "benchmarks/cells/tiny.open.json")))
    cell["engine"] = dict(cell["engine"], prefill_buckets=[32], prefill_chunk_tokens=32)
    w("benchmarks/configs/tiny-phi.json", TINY_PHI)
    w("benchmarks/cells/tinyphi.closed.json", cell)
    w("benchmarks/traffic/tinyphi-closed.json", dict(
        cellbench_tiny.LENGTHS, name="tinyphi-closed", loop="closed", clients=5, block=8, pool_seed=7,
        prompt_tokens={"dist": "lognormal", "median": 60, "sigma": 0.4, "min": 24, "max": 100}))
    data = json.load(open(os.path.join(path, "BENCHMARK.json")))
    data["configs"].append({"name": "tiny-phi", "source": TINY_PHI["source"], "file": "benchmarks/configs/tiny-phi.json",
                            "reduced": [], "why": "CPU test"})
    data["workloads"].append({"name": "tinyphi.closed", "config": "tiny-phi", "traffic": "tinyphi-closed",
                              "chips": 1, "why": "CPU test"})
    for m in data["end_to_end"] + data["per_layer"]:
        if m["name"] == "tok_s" or m["name"] in NEW:
            m["workloads"] = m["workloads"] + ["tinyphi.closed"]
    w("BENCHMARK.json", data)
    return path


def test_the_rehearsal_serves_the_new_family_and_its_reference_agrees(root, capsys):
    code, result = runner.run_cell(root, "tinyphi.closed", 2**31 + 35, 2.0, False, time.monotonic(),
                                   platform="cpu", control_bits=4)
    err = capsys.readouterr().err
    assert code == 0 and result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 5
    checks = result["checks"]
    assert 3 * checks["gap_max"]["value"] <= checks["gap_max"]["limit"] == 0.2 <= checks["control_gap_max"]["value"] / 3
    assert set(result["metrics"]) == {"tok_s", "setup_s"} and result["metrics"]["tok_s"]["value"] > 0
    assert "reference benchmarks/harness/phi4flash_reference.py (benchmarks.harness.phi4flash_reference) over" in err
    # its own lowering named the programs the warm-up uses, the chunked one among them; on the CPU none holds a Mosaic call
    assert "prefill_compute[32]=0" in err and "decode_block_paged=0" in err and "ragged_step_paged=0" in err
    assert "'pools': {'window':" in err  # health's kv_pages names the pools


def test_the_rehearsal_traced_reports_what_the_cpu_can_and_no_device_number(root):
    code, result = runner.run_cell(root, "tinyphi.closed", 37, 2.0, True, time.monotonic(), platform="cpu")
    assert code == 0 and result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result
    # the new readers ran and found nothing to read: no device plane on the CPU, and the
    # trace of a root that is not the checkout's is not where the span reader looks
    assert not set(result["metrics"]) & set(NEW)


def test_the_parent_s_program_fails_the_new_cell_at_once(root, monkeypatch):
    """A checkout without ``models/phi4flash.py`` cannot build the
    configuration: the factory raises before a weight is made, and the run
    ends with an error, not a hang."""
    import builtins

    real = builtins.__import__

    def no_model(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "gofr_tpu.models" and "phi4flash" in (fromlist or ()):
            raise ImportError("cannot import name 'phi4flash' from 'gofr_tpu.models'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_model)
    t = time.monotonic()
    with pytest.raises(ImportError, match="phi4flash"):
        runner.run_cell(root, "tinyphi.closed", 5, 2.0, False, time.monotonic(), platform="cpu")
    assert time.monotonic() - t < 60
