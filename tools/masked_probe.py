"""Probe of ``deepseekv32.long``'s decode read, one layer of one step at the
cell's shapes: 32 rows of 128 heads over 640-wide latent rows
(``kv_lora_rank`` 512), a slot of 256 pages of 16 positions, rows resident
at 2,304–3,328 positions, a selection of 2,048 by indexer scores. The two
reads ``models/deepseek_v32.sparse_read`` chooses between:

* ``gather``  the selection as a list (``mla.select_topk``: one sort that
              carries each position's row in the pool), then
              ``mla.sparse_decode_attention``: a gather by token of the
              65,536 listed rows and one dense product a row;
* ``masked``  the selection as a mask (``mla.selection_mask``), then
              ``latent_attention.paged_latent_attention`` under it: every
              page the row owns up to its length, a DMA a page.

Also timed alone: each read's attention given its selection
(``gather_attention``, ``masked_kernel``), the kernel without a mask at
128 and at 32 heads over the same pages (what the heads cost: a kernel
bound by its bytes takes the same time at both), and each selection alone.
Both reads are held to the float32 oracle (dense latent attention over the
row's pages under the selection's mask) and to each other.

    python tools/masked_probe.py           # on the chip: ms a call
    python tools/masked_probe.py --cpu     # rehearsal: tiny shapes, the kernel in the Pallas interpreter

The numbers go to standard output and ``chiprun_out/masked_probe.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.ops import latent_attention as la
from gofr_tpu.ops import mla

ROW_BYTES = 1280  # a latent row as the pool stores it (576 bf16 values padded to 640)


def case(B, H, W, page, M, lo, hi, seed=0):
    """Queries, a two-layer pool under a permuted block table, lengths
    drawn from [lo, hi], and indexer scores over the slot."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, W), jnp.float32).astype(jnp.bfloat16)
    pool = jax.random.normal(ks[1], (2, B * M + 1, 1, page, W), jnp.float32).astype(jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(B * M).reshape(B, M), jnp.int32)
    lens = jnp.asarray(rng.integers(lo, hi + 1, B), jnp.int32)
    scores = jax.random.normal(ks[2], (B, M * page), jnp.float32)
    return q, pool, tables, lens, scores


def _time(fn, *args, n):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / n * 1e3)
    return best, out


def probe(cpu: bool) -> list[dict]:
    if cpu:
        B, H, W, R, page, M, K, lo, hi, n = 4, 8, 128, 96, 4, 16, 24, 30, 60, 1
        la._BLOCK_TOKENS = 16
    else:
        B, H, W, R, page, M, K, lo, hi, n = 32, 128, 640, 512, 16, 256, 2048, 2304, 3328, 30
    scale = 192 ** -0.5
    interpret = True if cpu else None
    q, pool, tables, lens, scores = case(B, H, W, page, M, lo, hi)
    layer = jnp.int32(1)
    seen = jnp.arange(M * page)[None] < lens[:, None]
    positions = int(np.asarray(lens).sum())
    head = {"device": jax.devices()[0].device_kind, "rows": B, "heads": H, "slot": M * page, "topk": K,
            "positions": positions, "selected": int(np.minimum(np.asarray(lens), K).sum())}
    print(json.dumps(head), flush=True)

    def gather_read(q, pool, tables, lens, scores):
        rows, valid = mla.select_topk(scores, seen, K, mla.pool_rows(tables, pool.shape[1], page, layer))
        return mla.sparse_decode_attention(q, pool, rows, valid, scale=scale, kv_lora_rank=R)

    def masked_read(q, pool, tables, lens, scores):
        keep = mla.selection_mask(scores, seen, K)
        return la.paged_latent_attention(q, pool, tables, lens, layer, scale=scale, kv_lora_rank=R,
                                         keep=keep, interpret=interpret)

    keep = jax.jit(lambda s: mla.selection_mask(s, seen, K))(scores)
    listed = jax.jit(lambda s, t: mla.select_topk(s, seen, K, mla.pool_rows(t, pool.shape[1], page, layer)))(
        scores, tables)
    with jax.default_matmul_precision("highest"):
        rows = mla.row_pages(pool.astype(jnp.float32), tables, layer)
        oracle = np.asarray(jax.jit(lambda q, r, k: mla.latent_attention(
            q.astype(jnp.float32)[:, None], r, k[:, None], scale, R)[:, 0])(q, rows, keep))
    forms = {
        "gather": (jax.jit(gather_read), (q, pool, tables, lens, scores), head["selected"]),
        "masked": (jax.jit(masked_read), (q, pool, tables, lens, scores), positions),
        "gather_attention": (jax.jit(lambda q, p, r, v: mla.sparse_decode_attention(
            q, p, r, v, scale=scale, kv_lora_rank=R)), (q, pool, *listed), head["selected"]),
        "masked_kernel": (jax.jit(lambda q, p, t, n, k: la.paged_latent_attention(
            q, p, t, n, layer, scale=scale, kv_lora_rank=R, keep=k, interpret=interpret)),
            (q, pool, tables, lens, keep), positions),
        "kernel_no_mask": (jax.jit(lambda q, p, t, n: la.paged_latent_attention(
            q, p, t, n, layer, scale=scale, kv_lora_rank=R, interpret=interpret)), (q, pool, tables, lens), positions),
        "kernel_no_mask_32_heads": (jax.jit(lambda q, p, t, n: la.paged_latent_attention(
            q[:, :32], p, t, n, layer, scale=scale, kv_lora_rank=R, interpret=interpret)),
            (q, pool, tables, lens), positions),
        "select_topk": (jax.jit(lambda s, t: mla.select_topk(s, seen, K, mla.pool_rows(t, pool.shape[1], page, layer))),
                        (scores, tables), 0),
        "selection_mask": (jax.jit(lambda s: mla.selection_mask(s, seen, K)), (scores,), 0),
    }
    results, outs = [head], {}
    for name, (fn, args, read) in forms.items():
        line = {"form": name}
        try:
            ms, out = _time(fn, *args, n=n)
        except Exception as e:  # a form the compiler refuses is a reading too
            line["error"] = str(e).splitlines()[0][:300]
            print(json.dumps(line), flush=True)
            results.append(line)
            continue
        line["ms"] = ms
        if read:
            line["gb_s"] = read * ROW_BYTES / ms / 1e6
        if name in ("gather", "masked"):
            outs[name] = np.asarray(out)
            line.update(oracle_err=float(np.abs(outs[name] - oracle).max()), oracle_max=float(np.abs(oracle).max()))
        print(json.dumps(line), flush=True)
        results.append(line)
    if len(outs) == 2:
        agree = {"form": "masked_vs_gather", "max_abs": float(np.abs(outs["masked"] - outs["gather"]).max())}
        print(json.dumps(agree), flush=True)
        results.append(agree)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/masked_probe.json", "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true", help="tiny shapes, the kernel in the Pallas interpreter: a rehearsal, no time")
    results = probe(ap.parse_args().cpu)
    sys.exit(0 if all("error" not in r for r in results) else 1)
