"""A builder's tool, not the command: the device work ``deepseek_v32`` adds,
alone on the chip at the published shapes — each against its reference,
and timed where the question is what grows with the context.

    python3 benchmarks/tools/dsa_kernels.py [rows] [seed]

1. ``paged_kv_append`` with the model's two page shapes ([1, 16, 640] and
   [1, 16, 128]) compiled by Mosaic, against the scatter it stands for.
2. ``sparse_decode_attention`` (the gather of the selected rows and one
   dense product a row) against dense latent attention under the
   selection's mask, and ``paged_index_scores`` against the same scores
   from a contiguous copy of the keys.
3. Timings, the median of 20 calls each after a warm-up, for slots of
   4,096 and of 16,384 positions and rows resident at 3,000 and 12,000:
   the sparse read (2,048 rows whatever the context) and the indexer with
   the top-k (the whole slot: its shapes are static).

4. The other side of "a kernel given the row's list, or an XLA gather":
   :func:`row_dma_read`, a Mosaic kernel that starts one DMA a selected
   position (its latent row, HBM to VMEM) and scores all heads against
   what arrived. Tried on three layouts of the pool — rows of bf16 as the
   pool stores them, one token a tile in bf16, one token a tile in 32-bit
   words — with whatever Mosaic says to each, and timed where it compiles
   (the whole read, and the DMAs alone) beside the XLA gather alone.

One JSON line on standard output, also appended to
chiprun_out/dsa_kernels.jsonl. Needs a TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def row_dma_read(q, pool, rows, n_valid, *, scale: float, kv_lora_rank: int, gather_only: bool = False,
                 interpret: bool = False):
    """``ops/mla.sparse_decode_attention`` as one Mosaic program a row:
    q [B, H, W], pool [R, W] or [R, 1, W] (a token a tile), rows [B, K]
    int32 with the first ``n_valid`` [B] of each meant. One DMA a listed
    position into a [K, ...] buffer, then the dense product. With
    ``gather_only`` the products are left out (the first H rows that
    arrived are returned): the time of the DMAs alone."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    K = rows.shape[1]

    def kernel(rows_ref, n_ref, q_ref, pool_ref, o_ref, buf, sem):
        b = pl.program_id(0)

        def start(k, carry):
            pltpu.make_async_copy(pool_ref.at[pl.ds(rows_ref[b, k], 1)], buf.at[pl.ds(k, 1)], sem).start()
            return carry

        def wait(k, carry):
            pltpu.make_async_copy(pool_ref.at[pl.ds(0, 1)], buf.at[pl.ds(k, 1)], sem).wait()
            return carry

        jax.lax.fori_loop(0, K, start, 0)
        jax.lax.fori_loop(0, K, wait, 0)
        got = buf[...].reshape(K, W)
        if gather_only:
            o_ref[0] = got[:H, :kv_lora_rank].astype(jnp.float32)
            return
        s = jax.lax.dot_general(q_ref[0], got, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        keep = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < n_ref[b]
        p = jnp.where(keep, jnp.exp(jnp.where(keep, s, -1e30) - jnp.max(jnp.where(keep, s, -1e30), axis=-1, keepdims=True)), 0.0)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        o_ref[0] = jnp.dot(p.astype(got.dtype), got[:, :kv_lora_rank], preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)), pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((1, H, kv_lora_rank), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((K,) + pool.shape[1:], pool.dtype), pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((B, H, kv_lora_rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="row_dma_read", interpret=interpret,
    )(rows, n_valid, q, pool)


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.ops import mla
    from gofr_tpu.ops import paged_attention as pa

    rows, seed = int(argv[0]) if argv else 32, int(argv[1]) if len(argv) > 1 else 0
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"dsa_kernels: needs a TPU, jax found {device.platform}; no result", file=sys.stderr)
        return 3
    L, page, W, Di, H, Hi, R, K = 7, 16, 640, 128, 128, 64, 512, 2048
    scale = 192 ** -0.5
    out: dict = {"device": device.device_kind, "rows": rows, "seed": seed}

    def timed(fn, *args) -> float:
        jax.block_until_ready(fn(*args))
        ms = []
        for _ in range(20):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ms.append(1e3 * (time.perf_counter() - t))
        return statistics.median(ms)

    for slot_len, resident, B in ((4096, 3000, rows), (16384, 3000, 4), (16384, 12000, 4)):
        M = slot_len // page
        n = B * M
        ks = jax.random.split(jax.random.PRNGKey(seed + slot_len + resident), 8)
        latent = jax.random.normal(ks[0], (L, n + 1, 1, page, W), jnp.bfloat16)
        keys = jax.random.normal(ks[1], (L, n + 1, 1, page, Di), jnp.bfloat16)
        tables = jnp.asarray(np.random.default_rng(seed).permutation(n).reshape(B, M), jnp.int32)
        lens = jnp.full((B,), resident, jnp.int32)
        layer = jnp.int32(3)
        tag = f"slot{slot_len}.ctx{resident}"

        if slot_len == 4096:  # 1. the append, both page shapes
            new_k = jax.random.normal(ks[2], (B, 1, W), jnp.bfloat16)
            new_v = jax.random.normal(ks[3], (B, 1, Di), jnp.bfloat16)
            pages = tables[jnp.arange(B), (lens - 1) // page]
            offsets = (lens - 1) % page
            want = pa.paged_kv_append_ref(latent, keys, new_k, new_v, layer, pages, offsets)
            got = jax.jit(pa.paged_kv_append)(latent + 0, keys + 0, new_k, new_v, layer, pages, offsets)
            out["append_equal"] = bool(all(bool(jnp.all(g == w)) for g, w in zip(got, want)))
            del want, got

        # 2. the reads against their references
        q_i = jax.random.normal(ks[4], (B, Hi, Di), jnp.bfloat16)
        w_i = jax.random.normal(ks[5], (B, Hi), jnp.float32) / 90.0
        scores, seen = mla.paged_index_scores(q_i, w_i, keys, tables, lens, layer)
        flat_keys = keys[3][tables].reshape(B, M * page, Di)
        ref_scores = mla.index_scores(q_i[:, None], flat_keys, w_i[:, None])[:, 0]
        out[f"index_scores_diff.{tag}"] = float(jnp.max(jnp.abs(scores - ref_scores)))
        where = mla.pool_rows(tables, n + 1, page, layer)
        selected, valid = mla.select_topk(scores, seen, K, where)
        q = jax.random.normal(ks[6], (B, H, W), jnp.bfloat16)
        got = mla.sparse_decode_attention(q, latent, selected, valid, scale=scale, kv_lora_rank=R)
        flat_rows = latent[3][tables].reshape(B, M * page, W)
        keep = mla.selection_mask(scores, seen, K)
        want = mla.latent_attention(q[:, None], flat_rows, keep[:, None], scale, R)[:, 0]
        out[f"sparse_attention_diff.{tag}"] = float(jnp.max(jnp.abs(got - want)))
        out[f"sparse_attention_max.{tag}"] = float(jnp.max(jnp.abs(want)))
        out[f"selected.{tag}"] = int(jnp.sum(valid)) // B
        if slot_len == 4096:  # 4. the kernel given the row's list, on three layouts of the pool
            import functools

            n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)
            flat = latent.reshape(-1, W)
            out["xla_gather_alone_ms"] = timed(jax.jit(lambda: flat[jnp.where(valid, selected, 0)]))
            for name, pool in (("bf16_rows", flat), ("bf16_token_tiles", flat[:, None]),
                               ("word_token_tiles", flat[:, None].astype(jnp.float32))):
                for only in (False, True):
                    fn = jax.jit(functools.partial(row_dma_read, scale=scale, kv_lora_rank=R, gather_only=only))
                    key = f"row_dma.{name}" + (".dmas_alone" if only else "")
                    try:
                        res = fn(q.astype(pool.dtype), pool, selected, n_valid)
                        if not only:
                            out[key + ".diff"] = float(jnp.max(jnp.abs(res - want)))
                        out[key + "_ms"] = timed(lambda: fn(q.astype(pool.dtype), pool, selected, n_valid))
                    except Exception as e:  # noqa: BLE001 - what Mosaic says is the finding
                        said = [ln for ln in str(e).splitlines() if "must be" in ln or "rror" in ln]
                        out[key + ".refused"] = (said[0] if said else str(e))[:300]
                        break
                del pool
            del flat
        del flat_keys, flat_rows, want, keep

        # 3. what grows with the context
        out[f"sparse_read_ms.{tag}.rows{B}"] = timed(
            lambda: mla.sparse_decode_attention(q, latent, selected, valid, scale=scale, kv_lora_rank=R))
        indexer = jax.jit(lambda: mla.select_topk(
            *mla.paged_index_scores(q_i, w_i, keys, tables, lens, layer), K, mla.pool_rows(tables, n + 1, page, layer)))
        out[f"indexer_and_topk_ms.{tag}.rows{B}"] = timed(indexer)
        out[f"indexer_scores_ms.{tag}.rows{B}"] = timed(
            lambda: mla.paged_index_scores(q_i, w_i, keys, tables, lens, layer))
        del latent, keys

    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "dsa_kernels.jsonl"), "a", encoding="utf-8") as fh:
        fh.write(json.dumps(out) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
