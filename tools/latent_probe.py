"""Probe of ``ops/latent_attention.paged_latent_attention``'s fetch at the
shapes ``joyai.think`` runs: 64 rows of 32 heads, latent rows 640 wide
(``kv_lora_rank`` 512), pages of 16 positions, lengths 1–8,192 (189,397
positions), blocks of 512 positions. The forms differ in how a block's
pages come into VMEM and nothing else — the same online softmax, the same
products — so each must equal the first bit for bit:

* ``a``       the loops: a ``fori_loop`` of dynamic length starts a DMA a
              page, another waits for them page by page, a third zeroes the
              pages past a row's last;
* ``b``       the starts unrolled over the slot's static pages, each under
              ``pl.when``; a full block ONE wait described as the whole
              slot, a row's partial last block a wait a page (unrolled
              under ``pl.when``);
* ``b_full``  the module as it stands: ``b`` with a full block's starts
              under no predicate;
* ``a64``, ``c``  ``a`` and ``b`` over a pool of 64-position pages holding
              the same rows: the same bytes in a quarter of the DMAs. A
              bound, never shipped — it would have the pager hand out pages
              of 64.

    python tools/latent_probe.py           # on the chip: ms a call, GB/s, share of 819 GB/s
    python tools/latent_probe.py --cpu     # rehearsal: tiny shapes in the Pallas interpreter

Read on one v5e chip (best of three means of 30 calls; the share counts the
1,152 B of a position's 576 values at 819 GB/s, as
``mla.latent_attention_roofline.think`` does): ``a`` 0.813 ms (32.8 %),
``b`` 0.614 (43.4 %), ``b_full`` 0.579 (46.0 %), ``a64`` 0.470 (56.7 %),
``c`` 0.460 (57.9 %); every form equal to ``a`` bit for bit, 3.3e-3 from the
float32 oracle on outputs of up to 3.8.

Nothing of the benchmark is read or written; the numbers go to standard output and
``chiprun_out/latent_probe.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from committed_fetch import committed_land, committed_start
from gofr_tpu.ops import latent_attention as la

ROW_BYTES = 1152  # a position's 576 bf16 values as the roofline metric counts them (the pad to 640 is the kernel's)
HBM_BPS = 819e9


# ------------------------------------------------------------------ the fetches
# ``a``: the committed loops, the tests' oracle of the fetch
loop_start, loop_land = committed_start, committed_land


def when_start(streams, tables_ref, sem, layer, row, first, n, slot):
    for j in range(streams[0][1].shape[1]):
        @pl.when(j < n)
        def _start():
            pid = tables_ref[row, first + j]
            for hbm, buf in streams:
                pltpu.make_async_copy(hbm.at[layer, pid], buf.at[slot, j], sem.at[slot]).start()


SHIPPED = (la._start_pages, la._land_pages)


FORMS = {"a": (loop_start, loop_land), "b": (when_start, SHIPPED[1]), "b_full": SHIPPED}


# ------------------------------------------------------------------ the probe
def case(B, H, W, R, page, max_len, big_page, seed=0):
    """Queries, lengths, and the same rows in two pools: pages of ``page``
    and of ``big_page`` positions, each under a permuted block table."""
    rng = np.random.default_rng(seed)
    lens = 1 + (max_len - 1) * rng.random(B) ** 1.8
    lens = lens.astype(np.int32)
    lens[0], lens[1] = 1, max_len
    L = 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(ks[0], (B, H, W), jnp.float32).astype(jnp.bfloat16)
    rows = jax.random.normal(ks[1], (L, B, max_len, W), jnp.float32).astype(jnp.bfloat16)
    pools = {}
    for p in (page, big_page):
        M = max_len // p
        perm = rng.permutation(B * M)
        pages = rows.reshape(L, B * M, 1, p, W)
        pool = jnp.zeros((L, B * M + 1, 1, p, W), jnp.bfloat16).at[:, perm].set(pages)
        pools[p] = (pool, jnp.asarray(perm.reshape(B, M), jnp.int32))
    return q, jnp.asarray(lens), pools


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


def probe(cpu: bool):
    if cpu:
        B, H, W, R, page, max_len, big, block, n = 6, 4, 128, 96, 4, 64, 16, 32, 1
    else:
        B, H, W, R, page, max_len, big, block, n = 64, 32, 640, 512, 16, 8192, 64, 512, 30
    la._BLOCK_TOKENS = block
    scale = 192 ** -0.5
    q, lens, pools = case(B, H, W, R, page, max_len, big)
    layer = jnp.int32(1)
    positions = int(np.asarray(lens).sum())
    blocks = [-(-int(x) // block) for x in np.asarray(lens)]
    head = {"device": jax.devices()[0].device_kind, "rows": B, "heads": H, "W": W, "R": R, "page": page,
            "block": block, "positions": positions, "blocks": sum(blocks),
            "full_blocks": int(sum(int(x) // block for x in np.asarray(lens)))}
    print(json.dumps(head), flush=True)
    pool, tables = pools[page]
    with jax.default_matmul_precision("highest"):
        oracle = np.asarray(jax.jit(lambda q, p, t, n: la.paged_latent_attention_ref(
            q.astype(jnp.float32), p.astype(jnp.float32), t, n, layer, scale=scale, kv_lora_rank=R))(q, pool, tables, lens))
    results, first = [head], None
    for name, p, fetch in (("a", page, "a"), ("b", page, "b"), ("b_full", page, "b_full"),
                           ("a64", big, "a"), ("c", big, "b")):
        la._start_pages, la._land_pages = FORMS[fetch]
        pool, tables = pools[p]
        run = jax.jit(lambda q, p, t, n, layer: la._call(q, p, t, n, layer, scale, R, cpu))
        line = {"form": name, "page": p}
        try:
            ms, out = _time(run, q, pool, tables, lens, layer, n=n)
        except Exception as e:  # a form the compiler refuses is a reading too
            line["error"] = str(e).splitlines()[0][:300]
            print(json.dumps(line), flush=True)
            results.append(line)
            continue
        out = np.asarray(out)
        first = out if first is None else first
        line.update(ms=ms, gb_s=positions * ROW_BYTES / ms / 1e6,
                    roofline_pct=100.0 * positions * ROW_BYTES / HBM_BPS / (ms / 1e3),
                    equal_to_a=bool(np.array_equal(out, first)),
                    oracle_err=float(np.abs(out - oracle).max()), oracle_max=float(np.abs(oracle).max()))
        print(json.dumps(line), flush=True)
        results.append(line)
    la._start_pages, la._land_pages = SHIPPED
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/latent_probe.json", "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true", help="tiny shapes in the Pallas interpreter: a rehearsal, no time")
    results = probe(ap.parse_args().cpu)
    sys.exit(0 if all(r.get("equal_to_a", True) and "error" not in r for r in results) else 1)
