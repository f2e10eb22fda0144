"""FLOP and byte functions against hand-worked numbers for both configs."""

import json
import os

import pytest

from benchmarks.harness import costs, peaks

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


MISTRAL, DEEPSEEK = config("mistral-7b-v0.3-int8"), config("deepseek-llm-7b-int8")


@pytest.mark.parametrize("cfg,total,matrix", [
    # layer: 4096*4096*2 (q, o) + 2*4096*1024 (k, v) + 3*4096*14336 = 218,103,808; x32 + head 134,217,728
    (MISTRAL, 7_248_023_552, 32 * 218_103_808 + 4096 * 32768),
    # layer: 4*4096*4096 + 3*4096*11008 = 202,375,168; x30 + head 419,430,400
    (DEEPSEEK, 6_910_365_696, 30 * 202_375_168 + 4096 * 102400),
])
def test_parameter_counts(cfg, total, matrix):
    assert costs.matrix_params(cfg) == matrix
    assert costs.total_params(cfg) == total


@pytest.mark.parametrize("cfg,kib", [(MISTRAL, 128), (DEEPSEEK, 480)])
def test_kv_bytes_per_token(cfg, kib):
    assert costs.kv_bytes_per_token(cfg) == kib * 1024


@pytest.mark.parametrize("cfg,gb", [(MISTRAL, 7.39), (DEEPSEEK, 7.34)])
def test_resident_weight_bytes(cfg, gb):
    assert costs.weight_bytes(cfg) / 1e9 == pytest.approx(gb, abs=0.01)


def test_attention_flops_by_hand():
    # one query over 1000 positions: QK^T and PV, 2 FLOPs a multiply-add, 32 heads x 128, 32 layers
    assert costs.attention_flops(MISTRAL, 1000) == 2 * 2 * 1000 * 32 * 128 * 32
    # a causal prompt of 4 tokens attends 1+2+3+4 positions
    assert costs.prefill_attention_flops(DEEPSEEK, 4) == costs.attention_flops(DEEPSEEK, 10)


def test_served_flops_counts_needed_work_only():
    got = costs.served_flops(MISTRAL, [(0, 100), (0, 200)], decode_tokens=50, resident_positions=7000)
    want = 2 * costs.matrix_params(MISTRAL) * 350
    want += costs.attention_flops(MISTRAL, 100 * 101 // 2 + 200 * 201 // 2 + 7000)
    assert got == want
    # a prompt in two chunks costs what it costs whole
    whole = costs.served_flops(DEEPSEEK, [(0, 600)], 0, 0)
    assert costs.served_flops(DEEPSEEK, [(0, 256), (256, 256), (512, 88)], 0, 0) == whole


def test_paged_attention_bytes_are_exact_resident_lengths_not_pages():
    # 3 rows resident at 17, 33, 1000 positions: no rounding to the 16-token page
    assert costs.paged_attention_bytes(DEEPSEEK, 17 + 33 + 1000) == 1050 * 480 * 1024
    assert costs.paged_attention_flops(MISTRAL, 1050) == costs.attention_flops(MISTRAL, 1050)


def test_peaks_table_is_keyed_by_device_kind_and_refuses_strangers():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
