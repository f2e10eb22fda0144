"""Percentile, latency and tok_s arithmetic on hand-made records."""

import pytest

from benchmarks.harness import stats


def rec(index, due, first, n, gap, *, prompt=10, ok=True, sent=None):
    ts = [first + i * gap for i in range(n)]
    return {"index": index, "due": due, "sent": due if sent is None else sent, "head": due,
            "token_ts": ts, "tokens": [5] * n, "end": ts[-1] if ts else due,
            "finish_reason": "length" if ok else None, "status": 200 if ok else 503,
            "error": None if ok else "status 503", "request_id": index,
            "prompt_tokens": prompt, "max_tokens": n, "completion_tokens": n}


@pytest.mark.parametrize("q,want", [(0.0, 1), (0.5, 6), (0.9, 10), (0.99, 10)])
def test_percentile_is_an_observed_value(q, want):
    assert stats.percentile([10, 9, 8, 7, 6, 5, 4, 3, 2, 1], q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_ttft_counts_from_due_not_from_sent():
    r = rec(0, due=1.0, first=1.5, n=3, gap=0.1, sent=1.3)
    assert stats.ttft_ms(r, 99.0) == pytest.approx(500.0)


def test_tpot_sees_a_stall_anywhere_in_the_decode():
    smooth = rec(0, 0.0, 0.1, 11, 0.02)
    stalled = rec(1, 0.0, 0.1, 11, 0.02)
    stalled["token_ts"] = stalled["token_ts"][:5] + [t + 1.0 for t in stalled["token_ts"][5:]]
    assert stats.tpot_ms(smooth, 9.0) == pytest.approx(20.0)
    assert stats.tpot_ms(stalled, 9.0) == pytest.approx(120.0)
    assert stats.tpot_ms(rec(2, 0.0, 0.1, 1, 0.02), 9.0) is None


def test_a_failed_request_is_a_miss_of_every_latency_and_counts_in_failed():
    good = [rec(i, due=i * 0.1, first=i * 0.1 + 0.05, n=5, gap=0.01) for i in range(9)]
    bad = rec(9, due=0.95, first=0.0, n=0, gap=0.0, ok=False)
    s = stats.summarize(good + [bad], 0.0, 1.0, observed_until=3.0)
    assert (s["attempted"], s["failed"], s["ttft_samples"]) == (10, 1, 10)
    assert s["ttft_p50_ms"] == pytest.approx(50.0)
    assert s["ttft_p90_ms"] == pytest.approx((3.0 - 0.95) * 1e3)
    assert s["tpot_p90_ms"] == pytest.approx((3.0 - 0.95) * 1e3)


def test_only_requests_due_in_the_window_are_judged():
    records = [rec(0, -0.5, 0.1, 4, 0.01), rec(1, 0.2, 0.3, 4, 0.01), rec(2, 1.2, 1.3, 4, 0.01)]
    assert [r["index"] for r in stats.due_in_window(records, 0.0, 1.0)] == [1]


def test_tok_s_is_all_the_work_over_all_the_time():
    # a 2 s window: request 0 started before it (its prompt does not count, 3 of
    # its tokens do), request 1 lies inside, request 2's tail falls outside
    records = [
        rec(0, -1.0, -0.5, 6, 0.2, prompt=100),   # tokens at -0.5 .. 0.5: 3 inside (0.1, 0.3, 0.5)
        rec(1, 0.0, 0.5, 4, 0.25, prompt=50),     # all inside: 50 + 4
        rec(2, 1.0, 1.5, 5, 0.2, prompt=30),      # 1.5, 1.7, 1.9 inside: 30 + 3
    ]
    assert stats.tokens_per_s(records, 0.0, 2.0) == pytest.approx((3 + 54 + 33) / 2.0)
    c = stats.window_tokens(records, 0.0, 2.0)
    assert (c["prompt_tokens"], c["output_tokens"]) == (80, 10)
    # decode tokens: the j-th output (j >= 2) read prompt + j - 1 positions
    assert c["decode_tokens"] == 3 + 3 + 2
    assert c["resident_positions"] == (100 + 3) + (100 + 4) + (100 + 5) + (50 + 1) + (50 + 2) + (50 + 3) + (30 + 1) + (30 + 2)


def test_a_stall_in_the_window_lowers_tok_s_and_nothing_hides_it():
    steady = [rec(0, 0.0, 0.1, 19, 0.1, prompt=0)]
    stalled = [dict(steady[0], token_ts=[t if t < 0.95 else t + 5.0 for t in steady[0]["token_ts"]])]
    assert stats.tokens_per_s(steady, 0.0, 2.0) == pytest.approx(9.5)
    assert stats.tokens_per_s(stalled, 0.0, 2.0) == pytest.approx(4.5)


def test_generator_lateness_is_reported():
    s = stats.summarize([rec(0, 0.0, 0.2, 3, 0.1, sent=0.004)], 0.0, 1.0, 2.0)
    assert s["generator_late_max_ms"] == pytest.approx(4.0)


def test_iqr_share_matches_the_drivers_rule():
    assert stats.iqr_share([100, 101, 102, 103, 104, 105]) == pytest.approx((104.25 - 100.75) / 102.5)


def test_a_cut_request_is_a_failure_in_an_open_loop_and_neither_in_a_closed_one():
    cut = dict(rec(0, 0.1, 0.3, 5, 0.1), finish_reason=None, cut=True)
    done = rec(1, 0.2, 0.4, 5, 0.1)
    assert stats.summarize([cut, done], 0.0, 1.0, 3.0)["failed"] == 1
    closed = stats.summarize([cut, done], 0.0, 1.0, 3.0, closed_loop=True)
    assert (closed["attempted"], closed["failed"], closed["cut"]) == (2, 0, 1)
    # its tokens were still work the device did in the window
    assert closed["tok_s"] == pytest.approx((10 + 5 + 10 + 5) / 1.0)
    errored = dict(cut, error="ConnectionResetError", cut=False)
    assert stats.summarize([errored], 0.0, 1.0, 3.0, closed_loop=True)["failed"] == 1
