"""A builder's tool, not the command: ``long_context.py`` for a cache of
several pools. An engine of ``phi-4-mini-flash-reasoning-int8`` with a few
long slots serves ONE prompt through chunked prefill and then decodes far
past the window; the served tokens are compared with the plain reference
(and its int4 control) as a cell's are, over all of them and over the last
256, and the pager's pools are polled all the while: the window pool's
pages in use must stay flat once the row has passed ``window + page``
positions, whatever its length, while the full pool's grow with it.

    python3 benchmarks/tools/phi4flash_long.py <seed> <prompt tokens> <new tokens> [slots] [slot length] [chunk]

One JSON line on standard output, also appended to
chiprun_out/phi4flash_long.jsonl. Needs a TPU.
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "phi-4-mini-flash-reasoning-int8"


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    import jax

    from benchmarks.harness import runner, tokens, traffic
    from benchmarks.harness.manifest import Manifest, reference_module, resolve
    from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine

    seed, n_prompt, n_new = int(argv[0]), int(argv[1]), int(argv[2])
    slots, length, chunk = (int(a) for a in (argv[3:6] + ["4", "32768", "256"][len(argv[3:6]):]))
    t_start = time.monotonic()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"phi4flash_long: needs a TPU, jax found {device.platform}; no result", file=sys.stderr)
        return 3
    config = Manifest(ROOT).config(NAME)
    older = jax.live_arrays()
    cfg, params = resolve(config["factory"])(config, seed)
    jax.block_until_ready(params)
    engine = ServingEngine(cfg, params, EngineConfig(
        kv_layout="paged", kv_page_size=16, kv_dtype="bf16", prefix_cache_entries=0, max_slots=slots,
        max_seq_len=length, prefill_buckets=(32, 64, 128, 256), prefill_chunk_tokens=chunk,
    ), ByteTokenizer(cfg.vocab_size), seed=seed & 0x7FFFFFFF)
    prompt = traffic._prompt_text(random.Random(f"bench:long:{seed}"), n_prompt)
    served: list[int] = []
    polls: list[tuple[int, int, int]] = []  # (tokens served, window pages used, full pages used)
    done = threading.Event()

    def on_token(token_id: int, piece: str, last: bool) -> None:
        if token_id is not None and token_id >= 0 and not last:
            served.append(int(token_id))
        if last:
            done.set()

    def poll() -> None:
        while not done.wait(0.25):
            pools = engine.paged_cache.stats()["pools"]
            polls.append((len(served), pools["window"]["used"], pools["full"]["used"]))

    engine.start()
    try:
        t = time.monotonic()
        future = engine.submit(prompt, max_new_tokens=n_new, temperature=0.0, stream_cb=on_token)
        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        result = future.result(timeout=3000)
        done.wait(60)
        seconds = time.monotonic() - t
    finally:
        engine.stop()
    runner.say(t_start, f"served {result.completion_tokens} tokens after a prompt of {result.prompt_tokens} "
                        f"in {seconds:.1f}s ({result.finish_reason}); streamed {len(served)}")
    ids = tokens.prompt_ids(prompt)
    if result.finish_reason == "stop":
        served.append(ByteTokenizer(cfg.vocab_size).eos_id)
    freed = runner.free_device_state(params, older)
    del engine
    reference = reference_module(config)
    t = time.monotonic()
    gaps = reference.served_gaps(config, params, ids, served, pad_len=reference.pad_to(len(ids) + len(served), 512),
                                 control_bits=4)
    window, page = int(config["sliding_window"]), 16
    past = [p for p in polls if len(ids) + p[0] > window + page]
    line = {"config": NAME, "seed": seed, "prompt_tokens": len(ids), "served": len(served), "window": window,
            "slots": slots, "slot_length": length, "chunk": chunk,
            "gap_max": float(gaps["served"].max()), "gap_max_last_256": float(gaps["served"][-256:].max()),
            "mismatch": int((gaps["served"] > 0).sum()), "control_gap_max": float(gaps["control"].max()),
            "control_gap_max_last_256": float(gaps["control"][-256:].max()),
            "window_pages_used_past_the_window": sorted({p[1] for p in past}),
            "full_pages_used_first_last": [polls[0][2], polls[-1][2]] if polls else None, "polls": len(polls),
            "freed_gb": freed / 1e9, "serve_s": seconds, "tok_s_one_row": len(served) / seconds,
            "reference_s": time.monotonic() - t}
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "phi4flash_long.jsonl"), "a", encoding="utf-8") as fh:
        fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
