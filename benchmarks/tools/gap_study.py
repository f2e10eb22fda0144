"""A builder's tool, not the command: where ``deepseekv32.long``'s gap comes
from, and what a held number reads on many seeds. Needs a TPU.

    python3 benchmarks/tools/gap_study.py causes <workload> <seed>
    python3 benchmarks/tools/gap_study.py seeds <workload> <control bits|0> <requests> <seed> [<seed> ...]

``causes``: one sequence of 13 chunks of 256 tokens through the program's
own chunk path (``decode_chunk_paged``, one row, the absorbed form, its own
selection and routing) and through the plain reference, teacher-forced,
so that both give logits at EVERY position. Per position: the deviation of
the program's logits from the reference's (rms over the vocabulary) and
the gap of the program's first token. Three models on the same weights —
as served; the selection unbound (``index_topk`` = the slot); that and the
routing without a choice (every expert chosen, in every group) — say what
the two discrete choices add to the deviation. Beside them the reference
against ITSELF with the indexer's queries and keys rounded to bf16, as
the program caches them: the gap of that model's first token under the
float32 reference.

``seeds``: per seed new weights and a new engine with the cell's settings,
``requests`` requests of the seed's schedule served at once through
``ServingEngine.submit`` (no HTTP, no window: a row's numbers do not
depend on its neighbours), then the reference and its control over each.
Every token's gap is kept (``chiprun_out/gap_study/<seed>.npz``), so any
held number can be read from the same runs afterwards.

One JSON line a model or seed on standard output, also appended to
chiprun_out/gap_study.jsonl.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import os
import random
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "chiprun_out")


def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "gap_study.jsonl"), "a", encoding="utf-8") as fh:
        fh.write(json.dumps(line) + "\n")


def _quantiles(a) -> dict:
    import numpy as np

    a = np.asarray(a, np.float64)
    return {k: float(np.quantile(a, q)) for k, q in (("p50", .5), ("p90", .9), ("p99", .99), ("max", 1.0))}


def _gap_summary(gaps, reference) -> dict:
    import numpy as np

    gaps = np.asarray(gaps, np.float64)
    return {"tokens": int(len(gaps)), "mismatch": int((gaps > 0).sum()), "max": float(gaps.max()),
            "mean": float(gaps.mean()), "over_0.3": int((gaps > 0.3).sum()),
            "stretch": float(reference.stretch_means(gaps).max()),
            "stretch128": float(reference.stretch_means(gaps, 128).max()),
            "stretch256": float(reference.stretch_means(gaps, 256).max())}


def causes(workload: str, seed: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import tokens, traffic
    from benchmarks.harness.manifest import Manifest, reference_module, resolve
    from gofr_tpu.serving import batch

    manifest = Manifest(ROOT)
    config, cell = manifest.config(manifest.workload(workload)["config"]), manifest.cell(workload)
    reference = reference_module(config)
    page, chunk = int(cell["engine"]["kv_page_size"]), int(cell["engine"]["prefill_chunk_tokens"])
    slot = int(cell["engine"]["max_seq_len"])
    n = min(13 * chunk, slot) // chunk * chunk
    ids = np.asarray(tokens.prompt_ids(traffic._prompt_text(random.Random(f"bench:causes:{seed}"), n))[:n], np.int32)
    assert len(ids) == n, len(ids)
    cfg0, params = resolve(config["factory"])(config, seed)
    jax.block_until_ready(params)
    model = batch.model_of(cfg0)
    every = int(cfg0.n_experts)
    models = (("as served", {}, {}),
              ("selection unbound", {"index_topk": slot}, {"index_topk": slot}),
              ("selection unbound, routing without a choice",
               {"index_topk": slot, "top_k": every, "topk_group": int(cfg0.n_group)},
               {"index_topk": slot, "num_experts_per_tok": every, "topk_group": int(cfg0.n_group)}))
    M = slot // page
    tables = jnp.arange(M, dtype=jnp.int32)[None]
    base = None
    for name, cfg_kw, file_kw in models:
        cfg = dataclasses.replace(cfg0, **cfg_kw)
        file = dict(copy.deepcopy(config), **file_kw)
        t = time.monotonic()
        pools = [jnp.zeros((cfg.n_layers, M + 1) + shape, cfg.dtype) for shape in model.page_shapes(cfg, page)]
        rows = []
        for start in range(0, n, chunk):
            out, *pools = model.decode_chunk_paged(
                cfg, params, jnp.asarray(ids[None, start:start + chunk]), *pools, tables,
                jnp.asarray([start], jnp.int32), jnp.asarray([True]), jnp.asarray([slot], jnp.int32))
            rows.append(out[0])
        served = jnp.concatenate(rows)  # [n, V] float32
        del pools, rows
        ref = reference.logits(file, params, ids)
        dev = np.asarray(jnp.sqrt(jnp.mean((served - ref) ** 2, axis=-1)))
        first = jnp.argmax(served, axis=-1).astype(jnp.int32)
        gaps = np.asarray(jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0])
        late = slice(int(cfg0.index_topk), n)
        line = {"mode": "causes", "model": name, "seed": seed, "positions": n,
                "logit_deviation": _quantiles(dev), "logit_deviation_past_topk": _quantiles(dev[late]),
                "gap": _gap_summary(gaps, reference), "gap_past_topk": _gap_summary(gaps[late], reference),
                "seconds": time.monotonic() - t}
        if base is None:
            base = ref
            # the reference against itself, the indexer's operands in bf16
            low = reference.logits(file, params, ids, index_dtype=jnp.bfloat16)
            own = jnp.argmax(low, axis=-1).astype(jnp.int32)
            g = np.asarray(jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, own[:, None], axis=-1)[:, 0])
            d = np.asarray(jnp.sqrt(jnp.mean((low - ref) ** 2, axis=-1)))
            line["reference_with_bf16_indexer"] = {
                "logit_deviation_past_topk": _quantiles(d[late]), "gap_past_topk": _gap_summary(g[late], reference)}
            np.savez_compressed(os.path.join(OUT, f"gap_causes.{seed}.bf16_indexer.npz"), deviation=d, gaps=g)
            del low
        os.makedirs(OUT, exist_ok=True)
        np.savez_compressed(os.path.join(OUT, f"gap_causes.{seed}.{models.index((name, cfg_kw, file_kw))}.npz"),
                            deviation=dev, gaps=gaps)
        _emit(line)
        del served, ref
    return 0


def seeds(workload: str, bits: int | None, n_requests: int, seed_list: list[int]) -> int:
    import jax
    import numpy as np

    from benchmarks.harness import runner, tokens, traffic
    from benchmarks.harness.manifest import Manifest, reference_module, resolve
    from gofr_tpu.serving import ByteTokenizer, ServingEngine

    manifest = Manifest(ROOT)
    entry = manifest.workload(workload)
    config, cell, spec = manifest.config(entry["config"]), manifest.cell(workload), manifest.traffic(entry["traffic"])
    reference = reference_module(config)
    os.makedirs(os.path.join(OUT, "gap_study"), exist_ok=True)
    for seed in seed_list:
        t0 = time.monotonic()
        older = jax.live_arrays()
        cfg, params = resolve(config["factory"])(config, seed)
        jax.block_until_ready(params)
        tokenizer = ByteTokenizer(cfg.vocab_size)
        engine = ServingEngine(cfg, params, runner.engine_config(cell), tokenizer, seed=seed & 0x7FFFFFFF)
        requests = traffic.generate(spec, seed, 51.0)["requests"][:n_requests]
        served: list[list[int]] = [[] for _ in requests]

        def on_token(i: int):
            def cb(token_id: int, piece: str, last: bool) -> None:
                if token_id is not None and token_id >= 0 and not last:
                    served[i].append(int(token_id))
            return cb

        engine.start()
        try:
            t = time.monotonic()
            futures = [engine.submit(r["prompt"], max_new_tokens=r["max_tokens"], temperature=0.0,
                                     stream_cb=on_token(i)) for i, r in enumerate(requests)]
            results = [f.result(timeout=600) for f in futures]
            threading.Event().wait(0.2)  # the last callbacks
            serve_s = time.monotonic() - t
        finally:
            engine.stop()
        runner.free_device_state(params, older)
        del engine
        longest = max(r["prompt_tokens"] + r["max_tokens"] for r in requests)
        pad = reference.pad_to(int(spec["prompt_tokens"]["max"]) + int(spec["output_tokens"]["max"]), 128)
        pad = max(pad, reference.pad_to(longest, 128))
        kept, program, control = {}, [], []
        for i, (r, res) in enumerate(zip(requests, results)):
            ids = tokens.prompt_ids(r["prompt"])
            toks = served[i] + ([tokenizer.eos_id] if res.finish_reason == "stop" else [])
            gaps = reference.served_gaps(config, params, ids, toks, pad_len=pad, control_bits=bits)
            kept[f"served_{i}"] = gaps["served_tokens"]
            program.append(_gap_summary(gaps["served_tokens"], reference))
            if bits:
                kept[f"control_{i}"] = gaps["control_tokens"]
                control.append(_gap_summary(gaps["control_tokens"], reference))
        np.savez_compressed(os.path.join(OUT, "gap_study", f"{seed}.npz"), **kept)
        _emit({"mode": "seeds", "workload": workload, "seed": seed, "requests": len(requests),
               "finish": [r.finish_reason for r in results], "program": program, "control": control,
               "serve_s": serve_s, "seconds": time.monotonic() - t0})
        for leaf in jax.tree.leaves(params):  # the next seed's weights need the room
            leaf.delete()
        del params, gaps, kept
    return 0


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu" and not os.environ.get("GAP_STUDY_REHEARSAL"):
        print(f"gap_study: needs a TPU, jax found {device.platform}; no result", file=sys.stderr)
        return 3
    from gofr_tpu.ops.backend import configure_compile_cache

    configure_compile_cache()
    if argv[0] == "causes":
        return causes(argv[1], int(argv[2]))
    if argv[0] == "seeds":
        return seeds(argv[1], int(argv[2]) or None, int(argv[3]), [int(s) for s in argv[4:]])
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
