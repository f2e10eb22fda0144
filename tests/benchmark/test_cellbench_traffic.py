"""The traffic generator: exact repeat from a seed, same work across seeds."""

import json
import os

import pytest

from benchmarks.harness import traffic
from benchmarks.harness.manifest import Manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(REPO, "benchmarks", "traffic")) if f.endswith(".json"))


def load(mix):
    return Manifest(REPO).traffic(mix)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_repeats_exactly(mix):
    spec = load(mix)
    a = traffic.generate(spec, 2**31 + 7, 20.0)
    b = traffic.generate(spec, 2**31 + 7, 20.0)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_differ_in_order_and_text_not_in_work(mix):
    spec = load(mix)
    a, b = traffic.generate(spec, 1, 20.0)["requests"], traffic.generate(spec, 2, 20.0)["requests"]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert [(r["prompt_tokens"], r["max_tokens"]) for r in a] != [(r["prompt_tokens"], r["max_tokens"]) for r in b]
    block = len(a) if spec["loop"] == "open" else int(spec["block"])
    sizes = lambda rs: sorted((r["prompt_tokens"], r["max_tokens"]) for r in rs[:block])  # noqa: E731
    assert sizes(a) == sizes(b)
    if spec["loop"] == "open":
        gaps = lambda rs: sorted(round(y["due"] - x["due"], 9) for x, y in zip(rs, rs[1:]))  # noqa: E731
        assert len(a) == len(b) == round(spec["rate_per_s"] * 20.0)
        assert sum(gaps(a)) == pytest.approx(sum(gaps(b)), rel=0.2)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_inside_the_files_clip_and_prompt_is_one_byte_a_token(mix):
    spec = load(mix)
    for r in traffic.generate(spec, 3, 10.0)["requests"]:
        assert spec["prompt_tokens"]["min"] <= r["prompt_tokens"] <= spec["prompt_tokens"]["max"]
        assert spec["output_tokens"]["min"] <= r["max_tokens"] <= spec["output_tokens"]["max"]
        assert len(r["prompt"].encode("utf-8")) == r["prompt_tokens"] - 1
        assert json.loads(json.dumps(r["prompt"])) == r["prompt"]


def test_stratified_sizes_hit_the_median_and_the_clips():
    sizes = traffic.stratified_sizes({"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16, "max": 256}, 101)
    assert sizes == sorted(sizes) and sizes[50] == 96 and sizes[0] >= 16 and sizes[-1] == 256


def test_open_loop_arrivals_are_sorted_inside_the_window_and_poisson_in_shape():
    spec = {"rate_per_s": 5.0}
    offs = traffic.arrival_offsets(spec, 40.0, 9)
    assert len(offs) == 200 and offs == sorted(offs) and 0 < offs[0] and offs[-1] < 40.0
    gaps = [b - a for a, b in zip(offs, offs[1:])]
    mean = sum(gaps) / len(gaps)
    var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
    assert 0.7 < var ** 0.5 / mean < 1.3  # an exponential's cv is 1


def test_burst_windows_move_arrivals_into_the_burst_keeping_the_count():
    flat = traffic.arrival_offsets({"rate_per_s": 5.0}, 20.0, 4)
    burst = traffic.arrival_offsets({"rate_per_s": 5.0, "burst_windows": [[5.0, 2.0, 3.0]]}, 20.0, 4)
    inside = lambda offs: sum(5.0 <= t < 7.0 for t in offs)  # noqa: E731
    assert len(burst) == len(flat) == 100
    assert inside(burst) > 1.8 * inside(flat)


def test_closed_loop_blocks_are_each_the_same_set():
    spec = load("gen-batch")
    reqs = traffic.generate(spec, 5, 30.0)["requests"]
    block = spec["block"]
    sets = [sorted((r["prompt_tokens"], r["max_tokens"]) for r in reqs[i:i + block])
            for i in range(0, len(reqs), block)]
    assert len(sets) >= 2 and all(s == sets[0] for s in sets)
    assert [r["index"] for r in reqs] == list(range(len(reqs)))
