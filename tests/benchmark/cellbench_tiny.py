"""A tiny benchmark root for the CPU tests: the real harness and readers,
a configuration at ``LlamaConfig.tiny``-like widths with one open-loop and
one closed-loop cell, and a second configuration whose file names a
factory and a reference of its own, the lowering beside the factory
(``tiny_family.py``), with
one open-loop cell. Nothing here is a device number."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny", "source": "tests/benchmark (not a published model)",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 320,
    "max_position_embeddings": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "sliding_window": None, "tie_word_embeddings": False, "reduced": [], "assumed": [],
    "factory": "benchmarks.harness.llama_family:build",
    "reference": "benchmarks/harness/reference.py",
}
# what a dense decoder may differ in: depth, widths, full multi-head
# attention, vocabulary, theta, epsilon — and every module its file names
TINY2_CONFIG = {
    "name": "tiny2", "source": "tests/benchmark (not a published model)",
    "hidden_size": 96, "intermediate_size": 160, "num_hidden_layers": 3,
    "num_attention_heads": 6, "num_key_value_heads": 6, "head_dim": 16, "vocab_size": 384,
    "max_position_embeddings": 256, "rope_theta": 50000.0, "rms_norm_eps": 1e-6,
    "sliding_window": None, "tie_word_embeddings": False, "reduced": [], "assumed": [],
    "factory": "tests.benchmark.tiny_family:build",
    "reference": "tests/benchmark/tiny_family.py",
}
ENGINE = {"kv_layout": "paged", "kv_page_size": 16, "max_slots": 4, "max_seq_len": 128,
          "prefill_buckets": [32, 64], "prefill_chunk_tokens": 64, "requestz_capacity": 1024}
LENGTHS = {"prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 60},
           "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.4, "min": 4, "max": 16}}


def make_root(path: str, gap_max: float = 1.0) -> str:
    """Write BENCHMARK.json and the data files of three tiny cells under
    ``path``; the readers are the repo's own, copied in."""
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(path, "benchmarks", sub), exist_ok=True)
    link = os.path.join(path, "benchmarks", "layer_metrics")
    if not os.path.exists(link):
        shutil.copytree(os.path.join(REPO, "benchmarks", "layer_metrics"), link)

    def w(rel: str, obj: dict) -> None:
        with open(os.path.join(path, rel), "w", encoding="utf-8") as fh:
            json.dump(obj, fh)

    w("benchmarks/configs/tiny.json", TINY_CONFIG)
    w("benchmarks/configs/tiny2.json", TINY2_CONFIG)
    w("benchmarks/traffic/tiny-open.json", dict(LENGTHS, name="tiny-open", loop="open", rate_per_s=6.0, pool_seed=1))
    w("benchmarks/traffic/tiny-closed.json", dict(
        LENGTHS, name="tiny-closed", loop="closed", clients=3, block=8, pool_seed=2,
        prompt_tokens={"dist": "lognormal", "median": 80, "sigma": 0.3, "min": 40, "max": 100}))
    cell = {"engine": ENGINE, "trace": {"start_s": 0.5, "seconds": 0.5}, "drain_s": 30.0,
            "correct": {"gap_max": gap_max, "sample_requests": 3, "min_tokens": 8}}
    w("benchmarks/cells/tiny.open.json", cell)
    w("benchmarks/cells/tiny.closed.json", cell)
    w("benchmarks/cells/tiny2.open.json", cell)
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8"))
    manifest = dict(real)
    manifest["configs"] = [{"name": c["name"], "source": c["source"], "file": f"benchmarks/configs/{c['name']}.json",
                            "reduced": [], "why": "CPU test"} for c in (TINY_CONFIG, TINY2_CONFIG)]
    manifest["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny-open", "chips": 1, "why": "CPU test"},
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny-closed", "chips": 1, "why": "CPU test"},
        {"name": "tiny2.open", "config": "tiny2", "traffic": "tiny-open", "chips": 1, "why": "CPU test"}]
    for kind, cells in (("end_to_end", {"tok_s": ["tiny.closed"]}), ("per_layer", {})):
        entries = []
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                m["workloads"] = cells.get(m["name"], ["tiny.open", "tiny2.open"])
            entries.append(m)
        manifest[kind] = entries
    w("BENCHMARK.json", manifest)
    return path
