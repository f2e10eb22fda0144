"""Probe of ``ops/moe.held_experts`` at the shapes the cells run (PR 34):
one call of the loop over every row and of the grouped product at three
tile sizes, timed on the chip and held to each other; and the every-row
sum as one Mosaic call a layer (``ops/expert_rows``) beside the XLA loop
it replaced, over a program of several layers, at three budgets of its
weight tiles.

    python tools/moe_probe.py              # on the chip: times, ms a call
    python tools/moe_probe.py --only wide  # the 256-row bucket of commandaplus.wide alone
    python tools/moe_probe.py --only rows  # the every-row cases alone: the kernel beside the loop

Nothing of the benchmark is read or written; the numbers go to standard
output and ``chiprun_out/moe_probe.json``. (PR 34 also timed a Mosaic
grouped product here, 9-16 % faster a call and not taken: PERF.md §6 has
its readings; the kernel comes back with the PR that adopts it.)
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

from gofr_tpu.models.llama import _mm
from gofr_tpu.ops import expert_rows, moe

F32, BF16 = jnp.float32, jnp.bfloat16


# ------------------------------------------------------------------ the probe
def _int8_stack(key, shape):
    """A stack of int8 matrices with per-output-channel scales, made on
    the device piece by piece (a float32 stack of published size does not fit)."""
    L, n, a, b = shape
    q = jax.random.randint(key, shape, -127, 128, jnp.int8)
    s = jnp.full((L, n, b), 1.0 / (127.0 * np.sqrt(a) * 0.58), F32)  # unit-variance inputs give unit-variance outputs
    return {"q": q, "s": s}


def _stacks(key, L, n, D, F):
    ks = jax.random.split(key, 3)
    return {"w_gate": _int8_stack(ks[0], (L, n, D, F)), "w_up": _int8_stack(ks[1], (L, n, D, F)),
            "w_down": _int8_stack(ks[2], (L, n, F, D))}


def _time(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


def _case(name, T, live, D, F, E, held, k, n_shared, router, tiles, results):
    L, layer = 2, jnp.int32(1)
    key = jax.random.PRNGKey(T + held)
    experts, shared = _stacks(jax.random.fold_in(key, 1), L, held, D, F), _stacks(jax.random.fold_in(key, 2), L, n_shared, D, F)
    h = jax.random.normal(jax.random.fold_in(key, 3), (T, D), BF16)
    gates = moe.sigmoid_topk_gates(h, jax.random.normal(jax.random.fold_in(key, 4), (D, E), F32) / np.sqrt(D), k, **router)
    rows = jnp.arange(T) < live
    took = np.asarray((gates[:, :held] > 0) & rows[:, None])
    line = {"case": name, "T": T, "live": live, "pairs": int(took.sum()), "reached": int(took.any(0).sum()),
            "fullest": int(took.sum(0).max())}

    # the stacks are arguments: closed over they would be constants of the program (2.9 GB of them)
    @jax.jit
    def loop(h, gates, layer, experts, shared):
        g = jax.lax.dynamic_slice_in_dim(gates, 0, held, axis=1)
        return moe._over_every_row(h, g, experts, shared, _mm, layer)

    line["loop_ms"], ref = _time(loop, h, gates, layer, experts, shared)
    keep = np.asarray(rows)[:, None]
    scale = float(np.abs(np.asarray(ref) * keep).max())
    for tile in tiles:
        @jax.jit
        def grouped(h, gates, rows, layer, experts, shared, tile=tile):
            g = jax.lax.dynamic_slice_in_dim(gates, 0, held, axis=1)
            y, read = moe._over_own_rows(h, g, experts, _mm, layer, rows, tile)
            return moe._add_shared(y, h, shared, _mm, layer), read

        ms, (y, read) = _time(grouped, h, gates, rows, layer, experts, shared)
        line[f"xla_tile{tile}_ms"] = ms
        line[f"xla_tile{tile}_err"] = float(np.abs((np.asarray(y) - np.asarray(ref)) * keep).max()) / scale
        line["read"] = int(read)
    print(json.dumps(line), flush=True)
    results.append(line)


def _rows_case(name, T, L, D, F, E, held, k, n_shared, router, results):
    """The every-row sum over ``L`` layers in one program (a ``fori_loop``,
    as the models' layer loops hand a traced layer over): the XLA loop,
    then the kernel at the weight-tile budgets ``BUDGETS`` (MiB); ms a
    layer, the share of the bytes' bound at 819 GB/s, and the largest
    difference from the loop over its largest value."""
    key = jax.random.PRNGKey(T + held + L)
    experts, shared = _stacks(jax.random.fold_in(key, 1), L, held, D, F), _stacks(jax.random.fold_in(key, 2), L, n_shared, D, F)
    h = jax.random.normal(jax.random.fold_in(key, 3), (T, D), BF16)
    gates = moe.sigmoid_topk_gates(h, jax.random.normal(jax.random.fold_in(key, 4), (D, E), F32) / np.sqrt(D), k, **router)
    g = gates[:, :held]
    line = {"case": name, "T": T, "layers": L, "held": held, "shared": n_shared, "D": D, "F": F}
    layer_bytes = 3 * (held + n_shared) * D * F

    def layers(fn):
        @jax.jit
        def run(h, g, experts, shared):
            return jax.lax.fori_loop(0, L, lambda i, y: y + fn(h, g, experts, shared, i), jnp.zeros(h.shape, F32))
        return run

    loop_ms, ref = _time(layers(lambda h, g, ex, sh, i: moe._loop_over_every_row(h, g, ex, sh, _mm, i)), h, g, experts, shared, n=5)
    line["loop_ms_a_layer"] = loop_ms / L
    line["loop_bound_pct"] = 100.0 * layer_bytes / 819e9 / (loop_ms / L / 1e3)
    scale = float(np.abs(np.asarray(ref)).max())
    for mib in BUDGETS:
        expert_rows._WEIGHT_VMEM = mib << 20  # read as the call is traced
        line[f"tile_{mib}MiB"] = expert_rows.f_tile(D, F, mib << 20)
        try:
            ms, y = _time(layers(lambda h, g, ex, sh, i: moe._over_every_row(h, g, ex, sh, _mm, i)), h, g, experts, shared, n=5)
        except Exception as e:  # a tile the compiler refuses is a reading too
            line[f"kernel_{mib}MiB_error"] = str(e).splitlines()[0][:200]
            continue
        line[f"kernel_{mib}MiB_ms_a_layer"] = ms / L
        line[f"kernel_{mib}MiB_bound_pct"] = 100.0 * layer_bytes / 819e9 / (ms / L / 1e3)
        line[f"kernel_{mib}MiB_err"] = float(np.abs(np.asarray(y) - np.asarray(ref)).max()) / scale
    expert_rows._WEIGHT_VMEM = BUDGETS[0] << 20
    print(json.dumps(line), flush=True)
    results.append(line)


BUDGETS = (8, 16, 24)  # MiB of weight tiles, both slots: the first is the module's own
LFM2 = dict(D=2048, F=1792, E=32, held=32, k=4, n_shared=0, router={})
V32 = dict(D=7168, F=2048, E=256, held=32, k=8, n_shared=1, router=dict(n_group=8, topk_group=4, scale=2.5))
WIDE = dict(D=4096, F=4096, E=128, held=16, k=8, n_shared=4, router={})


def on_chip(only):
    results = []
    print(json.dumps({"device": jax.devices()[0].device_kind, "platform": jax.devices()[0].platform}), flush=True)
    if only == "wide":
        _case("wide.bucket256", 256, 200, tiles=(16, 32, 64), results=results, **WIDE)
        return
    # the every-row cases: lfm2.tools' decode (64 rows, 8 an expert) and its
    # bucket-128 prefill; commandaplus.wide's decode and bucket-128 prefill
    _rows_case("lfm2.decode", 64, 4, results=results, **LFM2)
    _rows_case("lfm2.bucket128", 128, 4, results=results, **LFM2)
    _rows_case("wide.decode", 64, 2, results=results, **WIDE)
    _rows_case("wide.bucket128", 128, 2, results=results, **WIDE)
    if only == "rows":
        _save(results)
        return
    _case("v32.decode", 32, 18, tiles=(16, 32), results=results, **V32)
    _case("v32.decode.full", 32, 32, tiles=(16, 32), results=results, **V32)
    _case("v32.chunk", 256, 256, tiles=(16, 32, 64), results=results, **V32)
    _case("wide.bucket256", 256, 200, tiles=(16, 32, 64), results=results, **WIDE)
    _case("wide.decode", 64, 64, tiles=(16, 32), results=results, **WIDE)
    _save(results)


def _save(results):
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_probe.json", "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="'wide': the 256-row bucket of commandaplus.wide alone, at three tiles; "
                    "'rows': the every-row sum, the kernel beside the loop")
    on_chip(ap.parse_args().only)
