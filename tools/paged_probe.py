"""Probe of ``ops/paged_attention.paged_decode_attention``'s fetch, the
committed loops against the module as it stands, at the shapes the paged
cells run. The two forms differ in how a block's pages come into VMEM
and nothing else — the same online softmax, the same products — so the
module's output must equal the loops' bit for bit:

* ``loops``    a ``fori_loop`` of dynamic length starts a DMA a page a
               stream, another waits for them page by page, a third zeroes
               V's pages past a row's last (``latent_probe.loop_start``,
               ``loop_land``);
* ``shipped``  the starts unrolled over the slot's static pages, a full
               block under no predicate; a full block lands with two waits,
               one a stream, each described as the stream's whole slot.

Shapes (one layer of the whole pools read, as the decode step reads it;
pages of 16 positions, 8 pages a block; rows of 1 to 8,192 positions,
about 2,100 on average; bf16):

* ``solve``   64 rows, 32 query heads over 2 KV heads of 128: 8 KiB a page-DMA
* ``tools``   64 rows, 32 query heads over 4 KV pairs of 128: 16 KiB
* ``reason``  32 rows, 40 query heads over 10 KV pairs of 128 (the full layer): 40 KiB

    python tools/paged_probe.py           # on the chip: ms a call, GB/s, share of 819 GB/s, ns a page-DMA
    python tools/paged_probe.py --cpu     # rehearsal: tiny shapes in the Pallas interpreter

The share counts K's and V's bytes of the positions below each row's
length at 819 GB/s. Nothing of the benchmark is read or written; the
numbers go to standard output and ``chiprun_out/paged_probe.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.ops import paged_attention as pa
from latent_probe import HBM_BPS, _time, loop_land, loop_start

SHIPPED = (pa._start_pages, pa._land_pages)
FORMS = {"loops": (loop_start, loop_land), "shipped": SHIPPED}
# name: rows, query heads, KV heads (pairs) of 128
SHAPES = {"solve": (64, 32, 2), "tools": (64, 32, 4), "reason": (32, 40, 10)}


def case(B, H, Hkv, Dh, page, max_len, seed=0):
    """Queries, lengths (a power law with mean ≈ 2,100 at 8,192) and two
    layers of K and V pools under a permuted block table."""
    rng = np.random.default_rng(seed)
    lens = (1 + (max_len - 1) * rng.random(B) ** 2.9).astype(np.int32)
    lens[0], lens[1] = 1, max_len
    M = max_len // page
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32).astype(jnp.bfloat16)
    k_pool, v_pool = (jax.random.normal(key, (2, B * M + 1, Hkv, page, Dh), jnp.float32).astype(jnp.bfloat16)
                      for key in ks[1:])
    tables = jnp.asarray(rng.permutation(B * M).reshape(B, M), jnp.int32)
    return q, k_pool, v_pool, tables, jnp.asarray(lens)


def probe(cpu: bool):
    page, Dh = (4, 32) if cpu else (16, 128)
    max_len, n = (64, 1) if cpu else (8192, 30)
    if cpu:
        pa._BLOCK_TOKENS = 2 * page
    results = []
    for name, (B, H, Hkv) in SHAPES.items():
        if cpu:
            B, H, Hkv = 6, 4, 2
        q, k_pool, v_pool, tables, lens = case(B, H, Hkv, Dh, page, max_len)
        layer = jnp.int32(1)
        ppb = pa._pages_per_block(Hkv, page, Dh, 2, tables.shape[1])
        pages = [max(1, -(-int(x) // page)) for x in np.asarray(lens)]
        positions = int(np.asarray(lens).sum())
        kv_bytes = 2 * positions * Hkv * Dh * 2
        head = {"shape": name, "device": jax.devices()[0].device_kind, "rows": B, "heads": H, "kv_heads": Hkv,
                "Dh": Dh, "page": page, "page_dma_bytes": Hkv * page * Dh * 2, "ppb": ppb, "positions": positions,
                "page_dmas": 2 * sum(pages), "blocks": sum(-(-p // ppb) for p in pages),
                "full_blocks": sum(p // ppb for p in pages)}
        print(json.dumps(head), flush=True)
        results.append(head)
        with jax.default_matmul_precision("highest"):
            oracle = np.asarray(jax.jit(lambda q, k, v, t, s: pa.paged_decode_attention_ref(
                q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), t, s, layer=1))(
                q, k_pool, v_pool, tables, lens), np.float32)
        first = None
        for form, (start, land) in FORMS.items():
            pa._start_pages, pa._land_pages = start, land
            run = jax.jit(lambda q, k, v, t, s, layer: pa._paged_attention_call(
                q, k, v, t, s, Dh ** -0.5, cpu, layer=layer))
            line = {"shape": name, "form": form}
            try:
                ms, out = _time(run, q, k_pool, v_pool, tables, lens, layer, n=n)
            except Exception as e:  # a form the compiler refuses is a reading too
                line["error"] = str(e).splitlines()[0][:300]
                print(json.dumps(line), flush=True)
                results.append(line)
                continue
            out = np.asarray(out, np.float32)
            first = out if first is None else first
            line.update(ms=ms, gb_s=kv_bytes / ms / 1e6, roofline_pct=100.0 * kv_bytes / HBM_BPS / (ms / 1e3),
                        ns_a_page_dma=ms * 1e6 / head["page_dmas"], equal_to_loops=bool(np.array_equal(out, first)),
                        oracle_err=float(np.abs(out - oracle).max()), oracle_max=float(np.abs(oracle).max()))
            print(json.dumps(line), flush=True)
            results.append(line)
        pa._start_pages, pa._land_pages = SHIPPED
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/paged_probe.json", "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true", help="tiny shapes in the Pallas interpreter: a rehearsal, no time")
    results = probe(ap.parse_args().cpu)
    sys.exit(0 if all(r.get("equal_to_loops", True) and "error" not in r for r in results) else 1)
