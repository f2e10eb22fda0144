"""The ``deepseek_v32`` family under the benchmark: the file and the cell
against the issue's numbers, its cost functions from the configuration's
keys against hand counts, its readers on hand-made events (exact
arithmetic; None, never 0, on another architecture's run or a program
without the counters), and a two-second rehearsal on the CPU at tiny widths
through the family's own factory, ``lowered_programs`` and reference.
Nothing here is a device number."""

import json
import os
import time

import pytest

import cellbench_tiny
from benchmarks.harness import deepseek_v32_costs as costs
from benchmarks.harness import deepseek_v32_family as family
from benchmarks.harness import deepseek_v32_layers as dsa_layers
from benchmarks.harness import manifest, runner, trace_reduce as tr
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.runner import RunData

REPO = cellbench_tiny.REPO
M = Manifest(REPO)
FILE = M.config("deepseek-v3.2-exp-ep8-int8")
CELL = "deepseekv32.long"
DEV, HOST, MS = "/device:TPU:0", "/host:CPU", 1_000_000
NEW = ["step.mfu.long", "mla.sparse_attention_roofline.long", "dsa.indexer_roofline.long",
       "moe.experts_roofline.long", "dsa.selected_share.long", "step.decode_ms.long",
       "moe.rows_per_expert.long", "moe.load_imbalance.long"]


# ------------------------------------------------------------ the file
def test_the_file_is_the_catalog_row_cut_as_the_issue_cuts_it():
    assert FILE["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (FILE["num_hidden_layers"], FILE["n_routed_experts"], FILE["vocab_size"]) == (7, 32, 16160)
    assert FILE["published"] == {"num_hidden_layers": 61, "n_routed_experts": 256, "vocab_size": 129280}
    # every width, the experts a token, the groups, the shared expert and the selection as published
    assert (FILE["hidden_size"], FILE["intermediate_size"], FILE["moe_intermediate_size"], FILE["num_attention_heads"],
            FILE["q_lora_rank"], FILE["kv_lora_rank"], FILE["qk_nope_head_dim"], FILE["qk_rope_head_dim"],
            FILE["v_head_dim"]) == (7168, 18432, 2048, 128, 1536, 512, 128, 64, 128)
    assert (FILE["index_n_heads"], FILE["index_head_dim"], FILE["index_topk"], FILE["num_experts_per_tok"],
            FILE["n_group"], FILE["topk_group"], FILE["n_shared_experts"], FILE["routed_scaling_factor"],
            FILE["first_k_dense_replace"]) == (64, 128, 2048, 8, 8, 4, 1, 2.5, 3)
    assert FILE["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
                                    "original_max_position_embeddings": 4096, "type": "yarn"}
    assert FILE["head_dim"] == 64 and FILE["model_type"] == "deepseek_v32" and FILE["num_nextn_predict_layers"] == 1
    assert FILE["layer_pattern"] == {"period": 1, "leading_dense": 3}
    assert FILE["deployment"]["chips_per_layer"] == 8 and FILE["deployment"]["first_expert"] == 0
    assert "float32" in FILE["precision"]["router"] and "latent 512, rope key 64, indexer key 128" in FILE["precision"]["kv_cache"]
    assumed = " ".join(FILE["assumed"])
    for word in ("Hadamard", "FP8", "multi-token-prediction", "head_dim", "two-halves", "int8 weight-only"):
        assert word in assumed
    assert manifest.lowering(FILE) is family.lowered_programs
    assert manifest.reference_module(FILE).__name__ == "benchmarks.harness.deepseek_v32_reference"
    cfg = family.program_config(FILE)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.held_experts, cfg.n_experts, cfg.vocab_size) == (7, 3, 32, 256, 16160)
    assert cfg.row_width == 640 and abs(cfg.softmax_scale - 192 ** -0.5 * (0.1 * 3.6888794541 + 1) ** 2) < 1e-9


def test_the_cell_and_its_mix_are_the_issue_s():
    spec, cell = M.traffic("gen-long"), M.cell(CELL)
    assert (spec["loop"], spec["clients"], spec["block"]) == ("closed", 48, 48)
    assert spec["prompt_tokens"] in ({"dist": "lognormal", "median": 2304, "sigma": 0.15, "min": 2048, "max": 3072},
                                     {"dist": "constant", "value": 2304, "min": 2048, "max": 3072})  # the named fallback
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 768, "sigma": 0.3, "min": 512, "max": 1024}
    assert spec["pool_seed"] not in {M.traffic(t)["pool_seed"] for t in ("gen-batch", "chat-short", "gen-wide")}
    # every decoded token has more than index_topk positions behind it
    assert spec["prompt_tokens"]["min"] >= FILE["index_topk"]
    engine = cell["engine"]
    assert (engine["max_slots"], engine["max_seq_len"], engine["kv_page_size"], engine["kv_dtype"]) in (
        (32, 4096, 16, "bf16"), (24, 4096, 16, "bf16"))
    assert engine["prefill_buckets"] == [32, 64, 128, 256] and engine["prefill_chunk_tokens"] == 256
    assert cell["trace"] == {"start_s": 30.0, "seconds": 3.0} and cell["drain_s"] == 45.0
    assert cell["correct"]["sample_requests"] == 2
    reported = {m["name"] for m in M.metrics_for("per_layer", CELL)}
    assert set(NEW) <= reported and "paged_attention_roofline.batch" not in reported
    assert {m["name"] for m in M.metrics_for("end_to_end", CELL)} == {"tok_s", "setup_s"}
    why = M.workload(CELL)["why"]
    # what the cell runs, as committed: constant prompts, prefill-bound
    assert len(why) <= 200 and "2,048 positions behind" in why and "<1 row an expert" in why
    assert ("constant 2304" in why) == (spec["prompt_tokens"]["dist"] == "constant")


# ------------------------------------------------------- the cost functions
def test_costs_are_the_issue_s_arithmetic_from_the_file_alone():
    assert costs.attention_params(FILE) == (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256
                                            + 128 * 128 * 7168) == 187_105_280
    assert costs.indexer_params(FILE) == 1536 * 64 * 128 + 7168 * 128 + 7168 * 64 == 13_959_168
    assert costs.dense_ffn_params(FILE) == 3 * 7168 * 18432 and costs.expert_params(FILE) == 3 * 7168 * 2048
    assert costs.router_params(FILE) == 7168 * 256 and costs.layer_counts(FILE) == (3, 4)
    dense, sparse = 201_064_448 + 396_361_728, 201_064_448 + 1_835_008 + 33 * 44_040_192
    assert costs.params_held(FILE) == 3 * dense + 4 * sparse == 8_417_181_696       # 8.42 GB in int8
    assert costs.weight_bytes(FILE) / 1e9 == pytest.approx(8.91, abs=0.01)          # with 232 MB each of embedding and head
    assert costs.cache_row_bytes(FILE) == (1280, 256)                                # 576 values stored as 640; 128
    assert costs.kv_bytes_per_token(FILE) == 7 * 1536                                # 9,856 B unpadded
    assert costs.routed_pairs_per_token(FILE) == 1.0                                 # 8 of 256, 32 held
    assert costs.params_per_token(FILE) == 3 * dense + 4 * (201_064_448 + 1_835_008 + 2 * 44_040_192)
    # a top-2048 binds past 2,048 positions
    assert costs.selected(FILE, [1, 2048, 2049, 3000]) == 1 + 2048 + 2048 + 2048
    assert list(costs.segment_contexts(256, 3)) == [257, 258, 259]
    # 32 rows at 3,000 positions, one step: 128 GFLOP and 0.59 GB of selected rows, 0.17 GB of keys
    assert costs.sparse_attention_flops(FILE, 32 * 2048) == 7 * 2 * 128 * (576 + 512) * 65536
    assert costs.sparse_attention_flops(FILE, 10, absorbed=False) == 7 * 2 * 128 * 320 * 10
    assert costs.sparse_attention_bytes(FILE, 32 * 2048) == 7 * 1280 * 65536
    assert costs.indexer_bytes(FILE, 32 * 3000) == 7 * 256 * 96000
    assert costs.indexer_flops(FILE, 5) == 7 * (2 * 64 * 128 + 2 * 64) * 5
    # the experts' count is the routed rows' products, not every held expert over every row
    assert costs.expert_call_flops(FILE, 32) == 2 * 44_040_192 * 32 * 2
    assert costs.expert_call_bytes(FILE, 32) == pytest.approx((32 * (1 - (31 / 32) ** 32) + 1) * 44_040_192)
    assert costs.expert_call_bytes(FILE, 32) / 819e9 > 20 * costs.expert_call_flops(FILE, 32) / 197e12


def test_served_flops_count_the_share_the_selection_and_every_scored_position():
    per_token = 2 * costs.params_per_token(FILE)
    one = costs.served_flops(FILE, [], [3000])
    assert one == per_token + 2 * 7168 * 16160 + costs.indexer_flops(FILE, 3000) + costs.sparse_attention_flops(FILE, 2048)
    prompt = costs.served_flops(FILE, [(0, 10)], [])
    assert prompt == (10 * per_token + 2 * 7168 * 16160 + costs.indexer_flops(FILE, 55)
                      + costs.sparse_attention_flops(FILE, 55, absorbed=False))
    # a later chunk brings no head of its own; its tokens see what came before them, and select 2,048 of it
    chunk = costs.served_flops(FILE, [(2560, 4)], [])
    assert chunk == (4 * per_token + costs.indexer_flops(FILE, 2561 + 2562 + 2563 + 2564)
                     + costs.sparse_attention_flops(FILE, 4 * 2048, absorbed=False))


# ------------------------------------------------------------- the readers
def dev(line, name, start_ms, dur_ms):
    return tr.Event(DEV, line, name, int(start_ms * MS), int(dur_ms * MS))


def span(name, start_ms, dur_ms):
    return tr.Event(HOST, "python3#4", name, int(start_ms * MS), int(dur_ms * MS))


def run_over(events, config, records=(), window_ms=(0, 100)):
    a, b = window_ms
    cell = {"engine": {"max_slots": 32, "max_seq_len": 4096, "kv_page_size": 16}}
    return RunData({"name": "x"}, config, cell, list(records), (0.0, 1.0), (a / 1e3, b / 1e3), events, 0, {}, [],
                   "TPU v5 lite")


GATHER = "%fusion.1958 = bf16[65536,640]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[7,8193,1,16,640]{4,3,2,1,0} %gte.1, s32[65536]{0} %fusion.1956), kind=kLoop"
SCORES = "%fusion.1963 = f32[32,128,2048]{2,1,0} fusion(bf16[32,128,640]{2,1,0} %q, bf16[65536,640]{1,0} %fusion.1958), kind=kOutput"
KEYS = "%fusion.1946 = bf16[8192,16,128]{2,1,0} fusion(bf16[7,8193,1,16,128]{4,3,2,1,0} %gte.2, s32[32,256]{1,0} %tables), kind=kLoop"
INDEX = "%fusion.1953 = f32[32,4096]{1,0} fusion(f32[32,64,4096]{2,1,0} %conv.66, f32[32,64]{1,0} %w), kind=kLoop"
SORT = "%sort.43 = (f32[32,4096]{1,0}, s32[32,4096]{1,0}) sort(f32[32,4096]{1,0} %fusion.1953, s32[32,4096]{1,0} %iota), dimensions={1}"
ROUTED = "%fusion.7 = bf16[32,2048]{1,0} fusion(s8[128,7168,2048]{2,1,0} %get-tuple-element.9, s32[] %gte.2, bf16[32,7168]{1,0} %h), kind=kOutput"
SHARED = "%fusion.8 = bf16[32,7168]{1,0} fusion(s8[4,2048,7168]{2,1,0} %get-tuple-element.10, s32[] %gte.2, bf16[32,2048]{1,0} %a), kind=kOutput"
CHUNK_EXPERT = "%fusion.70 = bf16[256,2048]{1,0} fusion(s8[128,7168,2048]{2,1,0} %get-tuple-element.9, s32[] %gte.2, bf16[256,7168]{1,0} %h), kind=kOutput"
CHUNK_SCORES = "%fusion.71 = f32[256,128,4096]{2,1,0} fusion(bf16[256,128,640]{2,1,0} %q, bf16[4096,640]{1,0} %rows), kind=kOutput"
HAND = (
    [dev(tr.MODULE_LINE, "jit_decode_block_paged(17)", 10, 30), dev(tr.MODULE_LINE, "jit_ragged_step_paged(18)", 45, 40),
     dev(tr.MODULE_LINE, "jit_prefill_compute(3)", 90, 5)]
    # decode steps inside both programs: the sparse read, the indexer and its sort, the expert products
    + [dev(tr.OPS_LINE, GATHER, 11, 0.3), dev(tr.OPS_LINE, SCORES, 12, 0.2), dev(tr.OPS_LINE, GATHER, 50, 0.3),
       dev(tr.OPS_LINE, SCORES, 51, 0.2)]
    + [dev(tr.OPS_LINE, KEYS, 13, 0.1), dev(tr.OPS_LINE, INDEX, 14, 0.05), dev(tr.OPS_LINE, SORT, 15, 0.1)]
    + [dev(tr.OPS_LINE, ROUTED, 16 + 0.02 * i, 0.02) for i in range(96)]
    + [dev(tr.OPS_LINE, SHARED, 20 + 0.02 * i, 0.02) for i in range(3)]
    # a chunk's work in the ragged program is no decode step's
    + [dev(tr.OPS_LINE, CHUNK_EXPERT, 60, 2.0), dev(tr.OPS_LINE, CHUNK_SCORES, 63, 2.0)]
    # the loop around the layers names every shape in its tuple: not a leaf
    + [dev(tr.OPS_LINE, "%while.56 = (s32[], bf16[32,1,7168], bf16[65536,640], f32[32,4096], s8[128,7168,2048]{2,1,0}) while(%tuple.9), condition=%c, body=%b", 10.5, 29)]
    # the same shapes outside a program that decodes are not counted
    + [dev(tr.OPS_LINE, SCORES, 91, 0.5)]
    + [span("gofr.step#iter=1,mono_ns=1#", 5, 90),
       span("gofr.step.dispatch#blk=3,kind=decode,rows=24,steps=4,kv_tokens=72000,chunk_rows=0,chunk_tokens=0,cold=0,dsa_rows=24#", 6, 2),
       span("gofr.step.commit#blk=2,tokens=96,retired=0,moe_rows=768,moe_max=40,dsa_scored=2016000,dsa_selected=1376256#", 60, 2),
       span("gofr.step.commit#blk=3,tokens=0,retired=0,moe_rows=0,moe_max=0,dsa_scored=0,dsa_selected=0#", 70, 1),
       span("bench.mark:0", 0, 0)]
)
# one request: a prompt of 2,500, thirty tokens in the sub-window (29 decoded, contexts 2501..2529)
RECORDS = [{"prompt_tokens": 2500, "token_ts": [0.010 + 0.001 * i for i in range(30)], "request_id": 1}]


def test_the_selected_share_reads_the_commit_spans():
    assert dsa_layers.selected_share_pct(run_over(HAND, FILE)) == pytest.approx(100 * 1376256 / 2016000)


def test_the_routing_counters_are_read_by_this_family_s_keys():
    run = run_over(HAND, FILE)
    # two commits of 4 steps over 4 expert layers and 32 held experts; the second block routed nothing
    assert dsa_layers.rows_per_expert(run) == pytest.approx(768 / (2 * 4 * 4 * 32))
    assert dsa_layers.load_imbalance(run) == pytest.approx(40 / (768 / 32))


def test_a_decode_step_is_timed_by_the_loop_around_its_append_in_either_program():
    append = "%paged_kv_append.3 = (bf16[7,8193,1,16,640]{4,3,2,1,0}, bf16[7,8193,1,16,128]{4,3,2,1,0}) custom-call(%a, %b), custom_call_target=\"tpu_custom_call\""
    loop = "%while.{} = (s32[], bf16[7,8193,1,16,640]{{4,3,2,1,0}}) while(%tuple.{}), condition=%c, body=%b"
    events = [
        dev(tr.MODULE_LINE, "jit_decode_block_paged(17)", 10, 30), dev(tr.OPS_LINE, loop.format(56, 1), 10.5, 29),
        dev(tr.OPS_LINE, append, 11.5, 0.01), dev(tr.OPS_LINE, append, 20, 0.01), dev(tr.OPS_LINE, "%copy.9 = f32[32] copy(%x)", 39.6, 0.2),
        # a ragged dispatch: the chunk's loop (no append in it) beside the steps'
        dev(tr.MODULE_LINE, "jit_ragged_step_paged(18)", 45, 40), dev(tr.OPS_LINE, loop.format(60, 2), 46, 20),
        dev(tr.OPS_LINE, CHUNK_EXPERT, 50, 2.0), dev(tr.OPS_LINE, loop.format(61, 3), 66.5, 18), dev(tr.OPS_LINE, append, 67, 0.01),
        # an execution cut by the sub-window's edge is left out, and so is a program that decodes nothing
        dev(tr.MODULE_LINE, "jit_ragged_step_paged(18)", 95, 10), dev(tr.OPS_LINE, loop.format(61, 3), 96, 8), dev(tr.OPS_LINE, append, 97, 0.01),
        dev(tr.MODULE_LINE, "jit_prefill_compute(3)", 88, 5), dev(tr.OPS_LINE, loop.format(70, 4), 88.5, 4),
    ]
    assert dsa_layers.decode_step_ms(run_over(events, FILE)) == pytest.approx((29 + 18) / (2 * 4))
    assert dsa_layers.decode_step_ms(run_over([e for e in events if "paged_kv_append" not in e.name], FILE)) is None


def test_decode_work_is_found_by_its_shapes_inside_the_programs_that_decode():
    run = run_over(HAND, FILE, RECORDS)
    assert "[65536,640]" in dsa_layers.sparse_attention_marks(run) and "[32,128,2048]" in dsa_layers.sparse_attention_marks(run)
    assert "[8192,16,128]" in dsa_layers.indexer_marks(run) and "[32,4096]" in dsa_layers.indexer_marks(run)
    sparse = dsa_layers.marked_events(run, dsa_layers.sparse_attention_marks(run))
    assert len(sparse) == 4 and sum(e.dur_ns for e in sparse) == pytest.approx(1.0 * MS)
    index = dsa_layers.marked_events(run, dsa_layers.indexer_marks(run))
    assert len(index) == 3 and sum(e.dur_ns for e in index) == pytest.approx(0.25 * MS)
    experts = dsa_layers.expert_product_events(run)
    assert len(experts) == 99 and sum(e.dur_ns for e in experts) == pytest.approx(99 * 0.02 * MS)
    assert dsa_layers.decode_contexts(run) == list(range(2501, 2530))


def test_the_rooflines_are_least_time_over_device_time():
    run = run_over(HAND, FILE, RECORDS)
    read, scored = 29 * 2048, sum(range(2501, 2530))
    least = max(costs.sparse_attention_bytes(FILE, read) / 819e9, costs.sparse_attention_flops(FILE, read) / 197e12)
    assert dsa_layers.sparse_attention_roofline_pct(run) == pytest.approx(100 * least / 1.0e-3)
    assert costs.indexer_bytes(FILE, scored) / 819e9 > costs.indexer_flops(FILE, scored) / 197e12  # bandwidth-bound
    assert dsa_layers.indexer_roofline_pct(run) == pytest.approx(100 * costs.indexer_bytes(FILE, scored) / 819e9 / 0.25e-3)
    # 99 products = one call of 3 x (32 + 1), at the dispatch's 24 rows
    assert dsa_layers.experts_roofline_pct(run) == pytest.approx(100 * costs.expert_call_bytes(FILE, 24) / 819e9 / (99 * 0.02e-3))


def test_step_mfu_counts_this_chips_share():
    run = run_over(HAND, FILE, RECORDS)
    flops = costs.served_flops(FILE, [(0, 2500)], list(range(2501, 2530)))
    assert dsa_layers.step_mfu_pct(run) == pytest.approx(100 * flops / (0.1 * 197e12))


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none_and_does_not_raise(name):
    read = M.reader(name)
    plain = [e for e in HAND if "dsa_scored" not in e.name]
    plain.append(span("gofr.step.commit#blk=2,tokens=192,retired=0,moe_rows=6144,moe_max=480#", 60, 2))  # cohere2's span
    for other in ("mistral-7b-v0.3-int8", "command-a-plus-ep8-int8"):
        assert read(run_over(HAND, M.config(other), RECORDS)) is None   # another architecture's configuration
    assert read(run_over([], FILE, RECORDS)) is None                     # no trace at all
    if name == "dsa.selected_share.long":
        assert read(run_over(plain, FILE, RECORDS)) is None              # a program without the counters
    if "roofline" in name:
        bare = [e for e in HAND if e.name not in (GATHER, SCORES, KEYS, INDEX, SORT, ROUTED, SHARED)]
        assert read(run_over(bare, FILE, RECORDS)) is None               # nothing that implements it
        if not name.startswith("moe."):
            assert read(run_over(HAND, FILE, [])) is None                # no token decoded in the sub-window


@pytest.mark.parametrize("name", ["moe.rows_per_expert.wide", "moe.load_imbalance.wide", "step.mfu.wide",
                                  "moe.experts_roofline.wide"])
def test_the_other_sparse_families_readers_do_not_read_this_configuration(name):
    """They read ``num_experts`` and ``layer_types``, keys this family's
    file does not have: None, and the cell is not on their lists."""
    assert M.reader(name)(run_over(HAND, FILE, RECORDS)) is None
    assert CELL not in next(m for m in M.data["per_layer"] if m["name"] == name)["workloads"]


# ------------------------------------------------------------ the rehearsal
TINY_DSA = {
    "name": "tiny-dsa", "source": "tests/benchmark (not a published model)", "model_type": "deepseek_v32",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 48, "vocab_size": 320,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
    "max_position_embeddings": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
                     "mscale_all_dim": 1.0, "original_max_position_embeddings": 32},
    "reduced": ["n_routed_experts"], "published": {"n_routed_experts": 16},
    "deployment": {"chips_per_layer": 4, "first_expert": 8, "stands_for": "four chips share each layer"},
    "assumed": [], "factory": "benchmarks.harness.deepseek_v32_family:build",
    "reference": "benchmarks/harness/deepseek_v32_reference.py",
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """cellbench_tiny's root, and in it a cell of the new family: a share
    (experts 8..11 of 16, one routing group) of a model that selects 48
    positions, with prompts of 40-100 tokens through chunks of 64 and
    buckets: most requests decode with the selection binding. (At 16 of
    100 positions one flip of the selection between bf16 neighbours moves
    a logit by 0.5-2 at these widths — the program reads 0.47-2.1 over
    four seeds, the control 1.4-2.3, and nothing parts them; at 48 the
    program's largest single gap reads 0.004-0.19 and the control's
    0.75-1.5.) Held, as in the cell, is the worst stretch's mean gap:
    0.025 against the control's 0.28 on the rehearsal's seed, the limit
    at their geometric middle."""
    path = cellbench_tiny.make_root(str(tmp_path_factory.mktemp("tinydsa")), gap_max=0.085)

    def w(rel, obj):
        with open(os.path.join(path, rel), "w", encoding="utf-8") as fh:
            json.dump(obj, fh)

    w("benchmarks/configs/tiny-dsa.json", TINY_DSA)
    w("benchmarks/cells/tinydsa.closed.json", json.load(open(os.path.join(path, "benchmarks/cells/tiny.open.json"))))
    w("benchmarks/traffic/tinydsa-closed.json", dict(
        cellbench_tiny.LENGTHS, name="tinydsa-closed", loop="closed", clients=5, block=8, pool_seed=6,
        prompt_tokens={"dist": "lognormal", "median": 70, "sigma": 0.3, "min": 40, "max": 100}))
    data = json.load(open(os.path.join(path, "BENCHMARK.json")))
    data["configs"].append({"name": "tiny-dsa", "source": TINY_DSA["source"], "file": "benchmarks/configs/tiny-dsa.json",
                            "reduced": ["n_routed_experts"], "why": "CPU test"})
    data["workloads"].append({"name": "tinydsa.closed", "config": "tiny-dsa", "traffic": "tinydsa-closed",
                              "chips": 1, "why": "CPU test"})
    for m in data["end_to_end"] + data["per_layer"]:
        if m["name"] == "tok_s" or m["name"] in NEW:
            m["workloads"] = m["workloads"] + ["tinydsa.closed"]
    w("BENCHMARK.json", data)
    return path


def test_the_rehearsal_serves_the_new_family_and_its_reference_agrees(root, capsys):
    code, result = runner.run_cell(root, "tinydsa.closed", 2**31 + 33, 2.0, False, time.monotonic(),
                                   platform="cpu", control_bits=4)
    err = capsys.readouterr().err
    assert code == 0 and result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 5
    checks = result["checks"]
    # what is held is the worst stretch's MEAN gap (the answers here are shorter than a stretch: one mean
    # each): the family serves bf16 activations at any width
    assert 3 * checks["gap_max"]["value"] <= checks["gap_max"]["limit"] == 0.085 <= checks["control_gap_max"]["value"] / 3
    assert set(result["metrics"]) == {"tok_s", "setup_s"} and result["metrics"]["tok_s"]["value"] > 0
    assert "reference benchmarks/harness/deepseek_v32_reference.py (benchmarks.harness.deepseek_v32_reference) over" in err
    # its own lowering named the programs the warm-up uses, the chunked one among them; on the CPU none holds a Mosaic call
    assert "prefill_compute[64]=0" in err and "decode_block_paged=0" in err and "ragged_step_paged=0" in err


def test_the_rehearsal_traced_reports_what_the_cpu_can_and_no_device_number(root):
    code, result = runner.run_cell(root, "tinydsa.closed", 35, 2.0, True, time.monotonic(), platform="cpu")
    assert code == 0 and result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result
    # the new readers ran and found nothing to read: no device plane on the CPU, and the
    # trace of a root that is not the checkout's is not where the span reader looks
    assert not set(result["metrics"]) & set(NEW)


def test_the_parent_s_program_fails_the_new_cell_at_once(root, monkeypatch):
    """A checkout without ``models/deepseek_v32.py`` cannot build the
    configuration: the factory raises before a weight is made, and the run
    ends with an error, not a hang."""
    import builtins

    real = builtins.__import__

    def no_model(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "gofr_tpu.models" and "deepseek_v32" in (fromlist or ()):
            raise ImportError("cannot import name 'deepseek_v32' from 'gofr_tpu.models'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_model)
    t = time.monotonic()
    with pytest.raises(ImportError, match="deepseek_v32"):
        runner.run_cell(root, "tinydsa.closed", 5, 2.0, False, time.monotonic(), platform="cpu")
    assert time.monotonic() - t < 60
