"""JoyAI-LLM-Flash under the benchmark: the file and the cell against the
arithmetic of the cut, its cost functions from the configuration's keys against
hand counts, its readers on hand-made events (exact arithmetic; None,
never 0, on another architecture's run or a program without the
counters), and a rehearsal on the CPU at tiny widths through the family's
own factory, ``lowered_programs`` and reference. Nothing here is a device
number."""

import json
import os
import time

import pytest

import cellbench_tiny
from benchmarks.harness import joyai_flash_costs as costs
from benchmarks.harness import joyai_flash_family as family
from benchmarks.harness import joyai_flash_layers as joy_layers
from benchmarks.harness import manifest, runner, traffic
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.runner import RunData
from gofr_tpu.models import deepseek_v32

REPO = cellbench_tiny.REPO
M = Manifest(REPO)
FILE = M.config("joyai-llm-flash-ep8-int8")
CELL = "joyai.think"
DEV, HOST, MS = "/device:TPU:0", "/host:CPU", 1_000_000
NEW = ["mla.latent_attention_roofline.think", "step.mfu.think", "step.decode_ms.think", "moe.experts_roofline.think",
       "moe.rows_per_expert.think"]
# the catalog row's ``config``, every key and value (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "joyai_llm_flash", "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 8, "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
CUT = {"num_hidden_layers": 13, "n_routed_experts": 32, "vocab_size": 16160}


# ------------------------------------------------------------ the file
def test_the_file_is_the_catalog_row_with_the_three_cuts():
    assert {k: FILE[k] for k in CATALOG if k not in CUT} == {k: v for k, v in CATALOG.items() if k not in CUT}
    assert {k: FILE[k] for k in CUT} == CUT and FILE["reduced"] == list(CUT)
    assert FILE["published"] == {k: CATALOG[k] for k in CUT}
    assert FILE["layer_pattern"] == {"period": 1, "leading_dense": 1}
    assert (FILE["deployment"]["chips_per_layer"], FILE["deployment"]["first_expert"]) == (8, 0)
    assert 8 * 16160 == 129280 and 8 * 32 == 256  # an eighth of the vocabulary and of the experts
    assumed = " ".join(FILE["assumed"])
    for word in ("multi-token-prediction", "not served", "once in 16160", "int8 weight-only", "seeded non-zero",
                 "head_dim is stated as 64 = qk_rope_head_dim"):
        assert word in assumed, word
    assert "int8" in FILE["precision"]["weights"] and "latent 512 and rope key 64" in FILE["precision"]["kv_cache"]
    assert manifest.lowering(FILE) is family.lowered_programs
    assert manifest.reference_module(FILE).__name__ == "benchmarks.harness.joyai_flash_reference"
    cfg = family.program_config(FILE)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.d_model, cfg.n_heads, cfg.vocab_size) == (13, 1, 2048, 32, 16160)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        1536, 512, 128, 64, 128)
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.n_experts, cfg.held_experts, cfg.top_k) == (7168, 768, 256, 32, 8)
    assert (cfg.n_group, cfg.topk_group, cfg.routed_scaling, cfg.n_shared, cfg.first_expert) == (1, 1, 2.5, 1, 0)
    assert cfg.index_topk == 0 and deepseek_v32.step_stats(cfg) == ("mla_kv", "mla_rows") and cfg.rope_theta == 3.2e7
    assert cfg.softmax_scale == 192 ** -0.5 and cfg.row_width == 640
    for key, wrong in (("rope_scaling", {"type": "yarn", "factor": 40}), ("scoring_func", "softmax"),
                       ("rope_interleave", False)):
        with pytest.raises(ValueError, match=key):
            family.program_config(dict(FILE, **{key: wrong}))


def test_the_cell_and_its_mix_are_as_specified():
    spec, cell = M.traffic("think-long"), M.cell(CELL)
    assert (spec["loop"], spec["clients"], spec["block"]) == ("closed", 96, 96)
    assert spec["prompt_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.7, "min": 128, "max": 2048}
    assert spec["output_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.5, "min": 768, "max": 6144}
    others = {name[:-5] for name in os.listdir(os.path.join(REPO, "benchmarks", "traffic"))} - {"think-long"}
    assert spec["pool_seed"] not in {M.traffic(t)["pool_seed"] for t in others}
    engine = cell["engine"]
    assert (engine["max_slots"], engine["max_seq_len"], engine["kv_page_size"], engine["kv_dtype"]) == (64, 8192, 16, "bf16")
    assert engine["prefill_buckets"] == [32, 64, 128, 256] and engine["prefill_chunk_tokens"] == 256
    assert engine["prefix_cache_entries"] == 0 and "multi_step" not in engine  # the default block
    assert cell["trace"] == {"start_s": 30.0, "seconds": 3.0} and cell["correct"]["min_tokens"] >= 100
    assert spec["prompt_tokens"]["max"] + spec["output_tokens"]["max"] == engine["max_seq_len"]
    assert {m["name"] for m in M.metrics_for("end_to_end", CELL)} == {"tok_s", "setup_s"}
    why = M.workload(CELL)["why"]
    assert len(why) <= 200 and "96 clients" in why and "64 slots x 8192" in why and "16" in why
    assert M.workload(CELL)["chips"] == 1 and M.workload(CELL)["config"] == "joyai-llm-flash-ep8-int8"
    # about 84 % of the prompts pass the largest bucket and prefill in 2-8 chunks of 256
    prompts = traffic.stratified_sizes(spec["prompt_tokens"], 96)
    assert 0.80 < sum(p > 256 for p in prompts) / 96 < 0.88 and max(prompts) <= 2048 == 8 * 256
    # each held expert sees 64 x 8 / 256 = 2 rows a step: held_experts' every-row path
    from gofr_tpu.ops import moe
    assert not moe.groups_rows(64, 256, 8) and 64 * 8 // 256 == 2


def test_the_cell_reports_its_five_and_tok_s():
    reported = {m["name"] for m in M.metrics_for("per_layer", CELL)}
    assert set(NEW) <= reported
    for m in M.data["per_layer"]:
        if m["name"] in NEW:
            assert m["moves"] == "tok_s" and m["workloads"] == [CELL] and m["name"].endswith(".think")
    assert CELL in next(m for m in M.data["end_to_end"] if m["name"] == "tok_s")["workloads"]


# ------------------------------------------------------- the cost functions
def test_costs_are_the_cut_s_arithmetic_from_the_file_alone():
    assert costs.attention_params(FILE) == 26_345_472  # wq_a, wq_b, wkv_a, wkv_b, wo
    assert (2048 * 1536, 1536 * 32 * 192, 2048 * 576, 512 * 32 * 256, 32 * 128 * 2048) == (
        3_145_728, 9_437_184, 1_179_648, 4_194_304, 8_388_608)
    assert costs.dense_ffn_params(FILE) == 44_040_192 and costs.router_params(FILE) == 524_288
    assert costs.expert_params(FILE) == 3 * 2048 * 768 == 4_718_592
    assert costs.expert_layer_params_held(FILE) == 31_588_352 + 32 * 4_718_592 == 182_583_296
    assert costs.layer_counts(FILE) == (1, 12)
    assert costs.params_held(FILE) == 26_345_472 + 44_040_192 + 12 * 182_583_296 == 2_261_385_216
    assert costs.head_params(FILE) == 16160 * 2048
    assert (costs.params_held(FILE) + 2 * 2 * 16160 * 2048) / 1e9 == pytest.approx(2.39, abs=0.005)
    # 576 values needed (1,152 B), 640 stored (1,280 B); 13 layers
    assert costs.latent_row_bytes(FILE) == (1152, 1280) and costs.kv_bytes_per_token(FILE) == 16_640
    assert costs.cache_bytes(FILE, 64, 8192) == 64 * 8192 * 16_640 and costs.cache_bytes(FILE, 64, 8192) / 1e9 == pytest.approx(8.72, abs=0.005)
    assert (costs.cache_bytes(FILE, 64, 8192) + costs.weight_bytes(FILE)) / 1e9 == pytest.approx(11.1, abs=0.05)
    # the held experts a step reaches: about 27.8 of 32 a layer at 64 rows, 8 of 256 each
    assert 32 * (1 - (1 - 8 / 256) ** 64) == pytest.approx(27.8, abs=0.05)
    # the latent read at 32 heads: 2 x 32 x 1,088 FLOPs against 1,152 B a position, about 60 FLOP/B
    assert costs.latent_attention_flops(FILE, 1) == 2 * 32 * 1088 and 2 * 32 * 1088 / 1152 == pytest.approx(60.4, abs=0.1)
    assert costs.latent_read_bytes(FILE, 10) == 11_520


def test_served_flops_count_the_model_s_need():
    per_token, head = costs.params_per_token(FILE), costs.head_params(FILE)
    assert per_token == 26_345_472 + 44_040_192 + 12 * (26_345_472 + 524_288 + (1 + 1) * 4_718_592)
    # one decoded token that read 3,000 positions in each of 13 layers
    assert costs.served_flops(FILE, [], 1, 13 * 3000) == 2 * per_token + 2 * head + 2 * 32 * 1088 * 13 * 3000
    # a prompt of 10: every position through every layer, the head once, token i sees i positions
    assert costs.served_flops(FILE, [(0, 10)], 0, 0) == 10 * 2 * per_token + 2 * head + 2 * 32 * 320 * 13 * 55
    # a later chunk brings no head
    assert costs.served_flops(FILE, [(512, 4)], 0, 0) == 4 * 2 * per_token + 2 * 32 * 320 * 13 * (4 * 512 + 10)


# ------------------------------------------------------------- the readers
def dev(line, name, start_ms, dur_ms):
    return tr.Event(DEV, line, name, int(start_ms * MS), int(dur_ms * MS))


def span(name, start_ms, dur_ms):
    return tr.Event(HOST, "python3#4", name, int(start_ms * MS), int(dur_ms * MS))


def run_over(events, config, records=(), window_ms=(0, 100)):
    a, b = window_ms
    cell = {"engine": {"max_slots": 64, "max_seq_len": 8192, "kv_page_size": 16}}
    return RunData({"name": "x"}, config, cell, list(records), (0.0, 1.0), (a / 1e3, b / 1e3), events, 0, {}, [],
                   "TPU v5 lite")


ROUTED = ("%expert_rows.7 = f32[64,2048]{1,0} custom-call(s32[1]{0} %l, bf16[64,2048]{1,0} %x, f32[64,32]{1,0} %g, "
          "s8[12,32,2048,768]{3,2,1,0} %wg, f32[12,32,768]{2,1,0} %sg), custom_call_target=\"tpu_custom_call\"")
SHARED = ("%expert_rows.8 = f32[64,2048]{1,0} custom-call(s32[1]{0} %l, bf16[64,2048]{1,0} %x, f32[64,1]{1,0} %g, "
          "s8[12,1,2048,768]{3,2,1,0} %wg), custom_call_target=\"tpu_custom_call\"")
KERNEL = ("%paged_latent_attention.5 = f32[64,32,512]{2,1,0} custom-call(s32[64]{0} %n, bf16[64,32,640]{2,1,0} %q, "
          "bf16[13,32769,1,16,640]{4,3,2,1,0} %pool), custom_call_target=\"tpu_custom_call\"")
APPEND = ("%paged_kv_append.3 = bf16[13,32769,1,16,640]{4,3,2,1,0} custom-call(%a, %b), "
          "custom_call_target=\"tpu_custom_call\"")
LOOP = "%while.{} = (s32[], s8[12,32,2048,768]{{3,2,1,0}}) while(%tuple.{}), condition=%c, body=%b"
# a block of 4 steps, 64 live rows at 3,000 positions: 13 layers read 64 x 3,000 each; 2 rows a held expert
COMMIT = ("gofr.step.commit#blk={},tokens=256,retired=0,moe_rows=3072,moe_max={},moe_reached=1536,"
          "mla_kv=9984000,mla_rows=3328#")
HAND = (
    [dev(tr.MODULE_LINE, "jit_decode_block_paged(17)", 10, 30), dev(tr.MODULE_LINE, "jit_ragged_step_paged(18)", 45, 40),
     dev(tr.MODULE_LINE, "jit_prefill_compute(3)", 90, 5)]
    # the loop over the block's steps around the appends, in both programs
    + [dev(tr.OPS_LINE, LOOP.format(56, 1), 10.5, 29), dev(tr.OPS_LINE, APPEND, 11, 0.01)]
    + [dev(tr.OPS_LINE, LOOP.format(61, 3), 66.5, 18), dev(tr.OPS_LINE, APPEND, 67, 0.01)]
    # in the decode block: the routed and the shared experts' calls, the attention kernel
    + [dev(tr.OPS_LINE, ROUTED, 12, 6), dev(tr.OPS_LINE, SHARED, 18, 1), dev(tr.OPS_LINE, ROUTED, 19, 6)]
    + [dev(tr.OPS_LINE, KERNEL, 26 + i, 0.5) for i in range(4)] + [dev(tr.OPS_LINE, KERNEL, 68, 0.5)]
    # the routed stack in a ragged dispatch (beside its chunk) is not counted
    + [dev(tr.OPS_LINE, ROUTED, 50, 9)]
    + [span("gofr.step#iter=1,mono_ns=1#", 5, 90),
       span("gofr.step.prefill#rid=4,bucket=256,tokens=200,route=bucketed#", 5.5, 0.4),
       span("gofr.step.dispatch#blk=3,kind=decode,rows=64,steps=4,kv_tokens=64000,chunk_rows=0,chunk_tokens=0,cold=0#", 6, 2),
       span("gofr.step.dispatch#blk=4,kind=ragged,rows=64,steps=4,kv_tokens=64256,chunk_rows=1,chunk_tokens=256,cold=0#", 40, 2),
       span(COMMIT.format(3, 40), 60, 2), span(COMMIT.format(4, 44), 88, 2),
       span("bench.mark:0", 0, 0)]
)
RECORDS = [{"prompt_tokens": 300, "token_ts": [0.010 + 0.001 * i for i in range(30)], "request_id": 1}]


def test_the_counters_are_summed_over_the_commits_of_the_whole_iterations():
    run = run_over(HAND, FILE, RECORDS)
    assert joy_layers.step_counts(run) == {"mla_kv": 2 * 9_984_000, "mla_rows": 2 * 3328, "moe_rows": 2 * 3072,
                                           "moe_max": 84, "moe_reached": 2 * 1536, "blocks": 2}
    assert joy_layers.rows_per_expert(run) == pytest.approx(2.0)  # 64 x 8 / 256
    assert joy_layers.decode_step_ms(run) == pytest.approx((29 + 18) / (2 * 4))


def test_each_roofline_divides_the_needed_work_by_the_device_time():
    """The work is the device's own count over the committed blocks; the
    time the kernel's events in the whole iterations (attention) or the
    routed stacks' calls of the decode block's executions (experts: the
    shared expert's call and the ragged dispatch's are not counted)."""
    run = run_over(HAND, FILE, RECORDS)
    read = 2 * 9_984_000 * 1152
    assert joy_layers.latent_attention_roofline_pct(run) == pytest.approx(100 * read / 819e9 / 2.5e-3)
    calls = 1 * 4 * 12  # one decode execution whole in the iterations, four steps, 12 expert layers
    least = max(32 * costs.expert_bytes(FILE) / 819e9, 2 * costs.expert_params(FILE) * 3072 / 48 / 197e12)
    assert joy_layers.experts_roofline_pct(run) == pytest.approx(100 * calls * least / 12e-3)
    flops = costs.served_flops(FILE, [(0, 300)], 2 * 3328 // 13, 2 * 9_984_000)
    assert joy_layers.step_mfu_pct(run) == pytest.approx(100 * flops / (0.09 * 197e12))  # the whole iterations: 5 to 95 ms
    assert joy_layers.expert_marks(run) == ("s8[12,32,2048,768]", "s8[384,2048,768]", "s8[12,32,768,2048]",
                                            "s8[384,768,2048]")


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none_and_does_not_raise(name):
    read = M.reader(name)
    for other in ("mistral-7b-v0.3-int8", "command-a-plus-ep8-int8", "deepseek-v3.2-exp-ep8-int8",
                  "phi-4-mini-flash-reasoning-int8", "lfm2-8b-a1b-int8"):
        assert read(run_over(HAND, M.config(other), RECORDS)) is None   # another architecture's configuration
    assert read(run_over([], FILE, RECORDS)) is None                     # no trace at all
    # the parent's program: no mla_kv on the commit spans
    bare = [tr.Event(e.plane, e.line, e.name.split(",moe_rows")[0] + "#" if e.name.startswith("gofr.step.commit") else e.name,
                     e.start_ns, e.dur_ns) for e in HAND]
    if name != "step.decode_ms.think":  # (that one reads the device alone)
        assert read(run_over(bare, FILE, RECORDS)) is None


# ------------------------------------------------------------ the rehearsal
TINY_JOYAI = {
    "name": "tiny-joyai", "source": "tests/benchmark (not a published model)", "model_type": "joyai_llm_flash",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24, "v_head_dim": 16, "vocab_size": 320,
    "n_routed_experts": 8, "published": {"n_routed_experts": 16}, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.5, "rope_theta": 32000000, "rope_scaling": None,
    "rope_interleave": True, "max_position_embeddings": 256, "rms_norm_eps": 1e-6, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "tie_word_embeddings": False, "reduced": ["n_routed_experts"],
    "deployment": {"first_expert": 0}, "assumed": [], "factory": "benchmarks.harness.joyai_flash_family:build",
    "reference": "benchmarks/harness/joyai_flash_reference.py",
}
GAP_MAX = 0.02


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """cellbench_tiny's root, and in it a cell of the new family: prompts
    of 24-100 tokens through buckets of 32 and chunks of 32, closed loop,
    half the published experts held. The worst stretch's mean is held, as
    in the cell: over six seeds here the program reads 0-0.0084 and the
    int4 control 0.066-0.225; the limit 0.02 lies between them."""
    path = cellbench_tiny.make_root(str(tmp_path_factory.mktemp("tinyjoyai")), gap_max=GAP_MAX)

    def w(rel, obj):
        with open(os.path.join(path, rel), "w", encoding="utf-8") as fh:
            json.dump(obj, fh)

    cell = json.load(open(os.path.join(path, "benchmarks/cells/tiny.open.json")))
    cell["engine"] = dict(cell["engine"], prefill_buckets=[32], prefill_chunk_tokens=32, prefix_cache_entries=0)
    w("benchmarks/configs/tiny-joyai.json", TINY_JOYAI)
    w("benchmarks/cells/tinyjoyai.closed.json", cell)
    w("benchmarks/traffic/tinyjoyai-closed.json", dict(
        cellbench_tiny.LENGTHS, name="tinyjoyai-closed", loop="closed", clients=5, block=8, pool_seed=7,
        prompt_tokens={"dist": "lognormal", "median": 50, "sigma": 0.5, "min": 24, "max": 100}))
    data = json.load(open(os.path.join(path, "BENCHMARK.json")))
    data["configs"].append({"name": "tiny-joyai", "source": TINY_JOYAI["source"], "file": "benchmarks/configs/tiny-joyai.json",
                            "reduced": ["n_routed_experts"], "why": "CPU test"})
    data["workloads"].append({"name": "tinyjoyai.closed", "config": "tiny-joyai", "traffic": "tinyjoyai-closed",
                              "chips": 1, "why": "CPU test"})
    for m in data["end_to_end"] + data["per_layer"]:
        if m["name"] == "tok_s" or m["name"] in NEW:
            m["workloads"] = m["workloads"] + ["tinyjoyai.closed"]
    w("BENCHMARK.json", data)
    return path


def test_the_rehearsal_serves_the_new_family_and_its_reference_agrees(root, capsys):
    code, result = runner.run_cell(root, "tinyjoyai.closed", 2**31 + 41, 2.0, False, time.monotonic(),
                                   platform="cpu", control_bits=4)
    err = capsys.readouterr().err
    assert code == 0 and result["correct"] is True and result["failed"] == 0 and result["attempted"] >= 5
    checks = result["checks"]
    assert 3 * checks["gap_max"]["value"] <= checks["gap_max"]["limit"] == GAP_MAX <= checks["control_gap_max"]["value"] / 3
    assert set(result["metrics"]) == {"tok_s", "setup_s"} and result["metrics"]["tok_s"]["value"] > 0
    assert "reference benchmarks/harness/joyai_flash_reference.py (benchmarks.harness.joyai_flash_reference) over" in err
    # its lowering named the programs the warm-up uses, the chunked one among them; on the CPU none holds a Mosaic call
    assert "prefill_compute[32]=0" in err and "decode_block_paged=0" in err and "ragged_step_paged=0" in err


def test_the_seeded_head_never_chooses_eos():
    """EOS's logit is 0 in every state, under the largest of the others:
    every request runs to the max_tokens its traffic drew."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.tokens import EOS_ID

    head = family.make_weights(TINY_JOYAI, 2**31 + 7)["lm_head"]
    assert head.shape == (64, 320) and not bool(jnp.any(head[:, EOS_ID])) and bool(jnp.all(jnp.any(head, axis=0).at[EOS_ID].set(True)))
    states = jax.random.normal(jax.random.PRNGKey(1), (256, 64), jnp.float32).astype(jnp.bfloat16)
    assert not bool(jnp.any(jnp.argmax(states @ head, axis=-1) == EOS_ID))


def test_the_rehearsal_traced_reports_what_the_cpu_can_and_no_device_number(root):
    code, result = runner.run_cell(root, "tinyjoyai.closed", 43, 2.0, True, time.monotonic(), platform="cpu")
    assert code == 0 and result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"]) and "breakdown" in result
    # the device readers found no device plane on the CPU; the span reader read the device's counters
    assert not set(NEW[:4]) & set(result["metrics"])


def test_the_parent_s_program_fails_the_new_cell_at_once(root, monkeypatch):
    """A checkout without ``ops/latent_attention.py`` cannot import the
    family: the factory's module raises before a weight is made, and the
    run ends with an error, not a hang."""
    import builtins
    import sys

    real = builtins.__import__

    def no_kernel(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "gofr_tpu.ops" and "latent_attention" in (fromlist or ()):
            raise ImportError("cannot import name 'latent_attention' from 'gofr_tpu.ops'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.delitem(sys.modules, "benchmarks.harness.joyai_flash_family")
    monkeypatch.setattr(builtins, "__import__", no_kernel)
    t = time.monotonic()
    with pytest.raises(ImportError, match="latent_attention"):
        runner.run_cell(root, "tinyjoyai.closed", 5, 2.0, False, time.monotonic(), platform="cpu")
    assert time.monotonic() - t < 60
