"""A builder's tool, not the command: the device work ``phi4flash`` adds,
alone on the chip at the published shapes — each against its plain form,
and timed.

    python3 benchmarks/tools/phi4flash_kernels.py [rows] [seed]

1. The recurrence over a prompt: ``ops/ssm.selective_scan`` (a ``lax.scan``
   a position, the form the program keeps) and :func:`blocks_scan` (an
   associative scan inside blocks of 16 positions, the form it left), for
   one row of 256 positions (a bucketed prefill) and for ``rows`` rows of
   256 (a chunk), each against the other, and the one-step form a decode
   step runs (one dispatch and its wait: about 0.9 ms whatever it holds).
2. ``paged_decode_attention`` under pairs — queries zero-padded to the
   pair's width over a cache of 10 heads of 128 — compiled by Mosaic,
   against differential attention's two softmaxes on K and V as published
   (20 heads of 64): on a window pool of 8 layers (a ring of 33 pages a
   slot, window 512) and on a pool of ONE layer read at a context of 2,500
   and of 7,000, with the bytes each call had to read over its time.

One JSON line on standard output, also appended to
chiprun_out/phi4flash_kernels.jsonl. Needs a TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def blocks_scan(u, delta, a_log, b, c, d, state, block: int = 16):
    """``ops/ssm.selective_scan``'s contract by a ``lax.scan`` over blocks
    of positions with an associative scan inside each: fewer, larger
    steps, ``[B, block, N, Din]`` live at once."""
    import jax
    import jax.numpy as jnp

    B, T, Din = u.shape
    Q = next(q for q in (block, 8, 4, 2, 1) if T % q == 0)
    a = -jnp.exp(a_log)

    def combine(left, right):  # two stretches of S -> a S + b, the left one first
        (a1, b1), (a2, b2) = left, right
        return a1 * a2, a2 * b1 + b2

    def one(s, xs):
        ub, db, bb, cb = xs  # [B, Q, ...]
        decay = jnp.exp(db[:, :, None, :] * a[None, None])  # [B, Q, N, Din]
        drive = (db * ub)[:, :, None, :] * bb[:, :, :, None]
        mult, add = jax.lax.associative_scan(combine, (decay, drive), axis=1)
        states = mult * s[:, None] + add
        return states[:, -1], jnp.sum(states * cb[:, :, :, None], axis=2) + d * ub

    def blocks(x):  # [B, T, W] -> [T/Q, B, Q, W]
        return jnp.swapaxes(x.reshape(B, T // Q, Q, x.shape[-1]), 0, 1)

    state, y = jax.lax.scan(one, state, tuple(blocks(x) for x in (u, delta, b, c)))
    return jnp.swapaxes(y, 0, 1).reshape(B, T, Din), state


def main() -> int:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.ops import ssm
    from gofr_tpu.ops.paged_attention import paged_decode_attention

    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 0
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"phi4flash_kernels needs a TPU; jax found {device.platform}", file=sys.stderr)
        return 3
    out: dict = {"device": device.device_kind, "rows": rows, "seed": seed}
    Din, N, R, T = 5120, 16, 160, 256
    key = jax.random.PRNGKey(seed)

    def timed(fn, *args, n: int = 20) -> float:
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(n):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t)
        return statistics.median(times)

    # ------------------------------------------------------------ the scan
    a_log = jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], (N, Din))
    d = jnp.ones((Din,), jnp.float32)
    for B in (1, rows):
        ks = jax.random.split(jax.random.fold_in(key, B), 5)
        u = jax.random.normal(ks[0], (B, T, Din), jnp.float32)
        delta = jnp.exp(jax.random.uniform(ks[1], (B, T, Din), jnp.float32, np.log(0.001), np.log(0.1)))
        b = jax.random.normal(ks[2], (B, T, N), jnp.float32)
        c = jax.random.normal(ks[3], (B, T, N), jnp.float32)
        s0 = jax.random.normal(ks[4], (B, N, Din), jnp.float32)
        got = {}
        for form, scan in (("scan", ssm.selective_scan), ("blocks", blocks_scan)):
            fn = jax.jit(lambda u, delta, b, c, s0, scan=scan: scan(u, delta, a_log, b, c, d, s0))
            try:
                got[form] = jax.block_until_ready(fn(u, delta, b, c, s0))
                out[f"scan.{form}.b{B}.ms"] = 1e3 * timed(fn, u, delta, b, c, s0, n=10)
            except Exception as exc:  # noqa: BLE001 - what the compiler or the memory says
                out[f"scan.{form}.b{B}.error"] = str(exc)[:300]
        if len(got) == 2:
            (y1, s1), (y2, s2) = got["scan"], got["blocks"]
            out[f"scan.forms_differ.b{B}"] = float(max(jnp.max(jnp.abs(y1 - y2)), jnp.max(jnp.abs(s1 - s2))))
        step = jax.jit(lambda u, delta, b, c, s: ssm.selective_step(u, delta, a_log, b, c, d, s))
        seconds = timed(step, u[:, 0], delta[:, 0], b[:, 0], c[:, 0], s0)
        out[f"step.b{B}.us"] = 1e6 * seconds
        out[f"step.b{B}.state_gb_s"] = 2 * B * N * Din * 4 / seconds / 1e9

    # ---------------------------------------------- the kernel under pairs
    H, Hkv, Dh, page, W = 40, 20, 64, 16, 512
    ring = W // page + 1

    def pad(q):
        zero = jnp.zeros_like(q)
        odd = (jnp.arange(H) % 2 == 1)[:, None]
        return jnp.where(odd, jnp.concatenate([zero, q], -1), jnp.concatenate([q, zero], -1))

    def two_softmaxes(q, k, v, lens, window):
        """q [B, H, Dh]; k, v [B, S, Hkv, Dh] contiguous; -> P_h [V | V'] as [B, H, 2 Dh], float32 highest."""
        S = k.shape[1]
        pos = jnp.arange(S)[None, :]
        seen = pos < lens[:, None]
        if window is not None:
            seen &= pos >= lens[:, None] - window
        kv_of = 2 * ((jnp.arange(H) // 2) // 2) + jnp.arange(H) % 2
        s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k.astype(jnp.float32)[:, :, kv_of],
                       precision="highest") / 8.0
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
        pairs = v.astype(jnp.float32).reshape(v.shape[0], S, Hkv // 2, 2 * Dh)[:, :, jnp.arange(H) // 4]
        return jnp.einsum("bhs,bshd->bhd", p, pairs, precision="highest")

    cases = {"window": (8, rows * ring, 3, [2500] * rows, W), "full.2500": (1, rows * 160, 0, [2500] * rows, None),
             "full.7000": (1, rows * 440, 0, [7000] * rows, None)}
    for case, (name, (L, n_pages, layer, lens, window)) in enumerate(cases.items()):
        ks = jax.random.split(jax.random.fold_in(key, 1000 + case), 3)
        k_pool = jax.random.normal(ks[0], (L, n_pages + 1, Hkv // 2, page, 2 * Dh), jnp.bfloat16)
        v_pool = jax.random.normal(ks[1], (L, n_pages + 1, Hkv // 2, page, 2 * Dh), jnp.bfloat16)
        q = jax.random.normal(ks[2], (rows, H, Dh), jnp.bfloat16)
        lens = jnp.asarray(lens, jnp.int32)
        per_row = n_pages // rows
        M = -(-int(lens.max()) // page)
        if window is None:
            tables = (jnp.arange(rows)[:, None] * per_row + jnp.arange(M)[None, :]).astype(jnp.int32)
        else:
            tables = (jnp.arange(rows)[:, None] * ring + jnp.arange(M)[None, :] % ring).astype(jnp.int32)
        fn = jax.jit(lambda q, kp, vp, t, n, window=window, layer=layer: paged_decode_attention(
            pad(q), kp, vp, t, n, scale=0.125, layer=layer,
            **({} if window is None else {"window": jnp.int32(window)})))
        got = fn(q, k_pool, v_pool, tables, lens).astype(jnp.float32)
        # the plain form over the positions a query may see, gathered contiguous as published heads
        lo = 0 if window is None else int(lens.max()) - window
        blocks = jnp.arange(lo // page, M)
        k_c = k_pool[layer][tables[:, blocks]].transpose(0, 1, 3, 2, 4).reshape(rows, -1, Hkv, Dh)
        v_c = v_pool[layer][tables[:, blocks]].transpose(0, 1, 3, 2, 4).reshape(rows, -1, Hkv, Dh)
        want = two_softmaxes(q, k_c, v_c, lens - (lo // page) * page, window)
        out[f"pairs.{name}.max_diff"] = float(jnp.max(jnp.abs(got - want)))
        seconds = timed(fn, q, k_pool, v_pool, tables, lens)
        read = sum(min(int(n), window or int(n)) for n in lens) * 2 * Hkv * Dh * 2
        out[f"pairs.{name}.us"] = 1e6 * seconds
        out[f"pairs.{name}.read_gb_s"] = read / seconds / 1e9

    line = json.dumps(out)
    print(line)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "phi4flash_kernels.jsonl"), "a", encoding="utf-8") as fh:
        fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
