"""The trace reduction: exact arithmetic on hand-made events, and the same
functions on 600 ms cut from a real v5e trace of ``mistral7b.chat`` (my
chip run, PR 25; names clipped to 96 characters to keep the file small)."""

import os

import pytest

from benchmarks.harness import layers, trace_reduce as tr
from benchmarks.harness.runner import RunData

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RECORDING = os.path.join(REPO, "benchmarks", "testdata", "trace_v5e_chat_600ms.json.gz")
DEV, MS = "/device:TPU:0", 1_000_000


def ev(line, name, start_ms, dur_ms, plane=DEV):
    return tr.Event(plane, line, name, int(start_ms * MS), int(dur_ms * MS))


HAND = [
    ev(tr.MODULE_LINE, "jit_decode_block_paged(1)", 0, 40),
    ev(tr.MODULE_LINE, "jit_decode_block_paged(1)", 50, 40),
    ev(tr.MODULE_LINE, "jit_prefill_compute(2)", 95, 10),
    ev(tr.OPS_LINE, "%while.1 = (s32[]) while(...)", 0, 40),
    ev(tr.OPS_LINE, "%paged_decode_attention.5 = bf16[8] custom-call(%x)", 5, 10),
    ev(tr.OPS_LINE, "%fusion.2 = bf16[8] fusion(%paged_decode_attention.5)", 15, 20),
    ev(tr.OPS_LINE, "%while.1 = (s32[]) while(...)", 50, 40),
    ev(tr.OPS_LINE, "%paged_decode_attention.5 = bf16[8] custom-call(%x)", 55, 10),
    ev(tr.OPS_LINE, "%fusion.9 = f32[8] fusion(%y)", 95, 10),
    ev("Async XLA Ops", "%copy-start.1 = ...", 0, 100),
    ev("python3", "bench.mark:1000", 0, 0, plane="/host:CPU"),
]


def test_busy_is_the_union_of_op_intervals_not_their_sum():
    assert tr.busy_seconds(HAND, 0, 110 * MS) == pytest.approx(0.090)
    assert tr.busy_seconds(HAND, 20 * MS, 60 * MS) == pytest.approx(0.030)  # clipped at both edges
    assert tr.busy_seconds([e for e in HAND if e.plane != DEV], 0, 110 * MS) == 0.0


def test_program_times_count_starts_inside_and_clip_durations():
    p = tr.program_times(HAND, 20 * MS, 100 * MS)
    assert p["decode_block_paged"] == {"count": 1, "seconds": pytest.approx(0.060)}
    assert p["prefill_compute"] == {"count": 1, "seconds": pytest.approx(0.005)}


def test_an_operands_name_is_not_the_operations_name():
    ops = tr.op_times(HAND, 0, 110 * MS)
    assert ops["paged_decode_attention.5"] == {"count": 2, "seconds": pytest.approx(0.020)}
    assert tr.op_name(HAND[5].name) == "fusion.2"


def test_leaf_times_charge_a_while_only_what_its_children_leave():
    leaf = tr.leaf_op_times(HAND, 0, 110 * MS)
    assert leaf["while.1"]["seconds"] == pytest.approx(0.010 + 0.030)
    assert sum(v["seconds"] for v in leaf.values()) == pytest.approx(tr.busy_seconds(HAND, 0, 110 * MS))


def test_idle_gaps_are_named_after_what_ran_next():
    gaps = dict(tr.idle_gaps(HAND, 0, 110 * MS))
    assert gaps["before decode_block_paged"] == pytest.approx(0.010)
    assert gaps["before prefill_compute"] == pytest.approx(0.005)
    assert gaps["before end of window"] == pytest.approx(0.005)


def test_program_name_strips_jit_and_the_id():
    assert tr.program_name("jit_decode_block_paged(12305427837327443266)") == "decode_block_paged"
    assert tr.program_name("jit__write_pages(5)") == "_write_pages"


# ------------------------------------------------------------- the recording
@pytest.fixture(scope="module")
def recorded():
    events = tr.load_events(RECORDING)
    m0 = min(e.start_ns for e in events if e.name.startswith("bench.mark:"))
    return events, (m0 + 2400 * MS, m0 + 3000 * MS)


def test_recording_busy_programs_and_kernel(recorded):
    events, span = recorded
    assert tr.device_planes(events) == [DEV]
    assert tr.busy_seconds(events, *span) == pytest.approx(0.5900342, rel=1e-6)
    progs = tr.program_times(events, *span)
    assert progs["decode_block_paged"]["count"] == 1
    assert progs["decode_block_paged"]["seconds"] == pytest.approx(0.559501391, rel=1e-6)
    assert progs["prefill_compute"]["count"] == 2
    kernel = tr.op_times(events, *span)["paged_decode_attention.5"]
    assert kernel["count"] == 146 and kernel["seconds"] == pytest.approx(0.348670714, rel=1e-6)


def test_recording_leaf_times_add_up_to_busy(recorded):
    events, span = recorded
    leaf = tr.leaf_op_times(events, *span)
    assert sum(v["seconds"] for v in leaf.values()) == pytest.approx(tr.busy_seconds(events, *span), rel=1e-9)
    assert max(leaf, key=lambda k: leaf[k]["seconds"]) == "paged_decode_attention.5"


def test_readers_on_the_recording(recorded):
    """The per-layer reductions end to end, on the recording plus two
    hand-made requests decoding through it."""
    events, span = recorded
    offset = 5_000_000_000  # trace clock = monotonic + 5 s
    a, b = (span[0] - offset) / 1e9, (span[1] - offset) / 1e9
    config = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 32,
              "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768}

    def rec(i, prompt, first, n):
        return {"index": i, "request_id": i, "due": first - 0.1, "sent": first - 0.1, "prompt_tokens": prompt,
                "token_ts": [first + 0.12 * j for j in range(n)], "tokens": [7] * n, "status": 200,
                "error": None, "finish_reason": "length", "max_tokens": n, "completion_tokens": n}

    records = [rec(0, 100, a - 1.0, 20), rec(1, 200, a + 0.3, 2)]
    run = RunData({"name": "x"}, config, {"engine": {"max_slots": 32}}, records, (a - 5, b + 5), (a, b),
                  events, offset, {}, [], "TPU v5 lite", 0.0)
    # request 0: tokens 10..14 fall inside (5 decode tokens at context 109..113);
    # request 1: its first token (prefill of 200) and one decode token at context 201
    assert layers.decode_steps(run) == 4
    assert layers.batch_occupancy_pct(run) == pytest.approx(100.0 * 6 / (4 * 32))
    assert layers.decode_ms(run) == pytest.approx(559.501391 / 4, rel=1e-6)
    assert layers.idle_share_pct(run) == pytest.approx(100.0 * (1 - 0.5900342 / 0.6), rel=1e-6)
    resident = 109 + 110 + 111 + 112 + 113 + 201
    least = resident * 128 * 1024 / 819e9
    assert layers.paged_attention_roofline_pct(run) == pytest.approx(100.0 * least / 0.348670714, rel=1e-6)
    flops = 2 * 7_113_539_584 * (200 + 6) + 4 * 32 * 32 * 128 * (200 * 201 // 2 + resident)
    assert layers.step_mfu_pct(run) == pytest.approx(100.0 * flops / (0.6 * 197e12), rel=1e-6)


def test_a_reader_that_finds_nothing_returns_nothing():
    run = RunData({"name": "x"}, {}, {"engine": {"max_slots": 4}}, [], (0.0, 1.0), None, [], None,
                  {}, [], "cpu")
    for fn in (layers.batch_occupancy_pct, layers.page_fill_pct, layers.decode_ms, layers.step_mfu_pct,
               layers.paged_attention_roofline_pct, layers.idle_share_pct):
        assert fn(run) is None


def test_page_fill_is_the_highest_poll_inside_the_window():
    polls = [{"t": 0.5, "kv_pages": {"total_blocks": 100, "free_blocks": 40}},
             {"t": 1.5, "kv_pages": {"total_blocks": 100, "free_blocks": 70}},
             {"t": 2.5, "kv_pages": {"total_blocks": 100, "free_blocks": 0}}]
    run = RunData({"name": "x"}, {}, {"engine": {"max_slots": 4}}, [], (0.0, 2.0), None, [], None,
                  {}, polls, "cpu")
    assert layers.page_fill_pct(run) == 60.0
