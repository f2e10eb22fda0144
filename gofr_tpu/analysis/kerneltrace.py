"""kerneltrace — the runtime twin of the kernel contract table.

Two producers, one consumer:

- :func:`run_matrix` ``jax.eval_shape``\\ s EVERY contract-table entry
  across the config matrix (dense / paged caches x
  base / LoRA x plain / ragged / speculative x B,N variants) and exports
  the observed (pytree, shape, dtype) signatures. Everything abstract is
  passed as an eval_shape ARGUMENT (``ShapeDtypeStruct`` pytrees); only
  true statics (the config dataclass, ``steps`` ints) are bound by
  closure — so the whole matrix runs on CPU with ZERO device execution
  and zero jit-cache growth (the tier-1 test pins ``_cache_size()``
  deltas to 0 by calling each kernel's ``__wrapped__``).
- :class:`KernelObserver` wraps the host-dispatch kernel entries
  (``serving.batch`` + ``serving.kv_cache``) on a LIVE engine and
  records the same signatures per unique call shape. Input signatures
  are recorded BEFORE the dispatch — shape/dtype metadata reads, safe
  against donation.

Both exports feed ``gofr_tpu.analysis --check-kernel-table`` /
:func:`gofr_tpu.analysis.kernelcheck.check_kernel_table`, which replays
them against the static table: packed widths, symbolic return shapes,
dtypes, and the ``like=`` carry passthroughs (donated-carry drift).
"""

from __future__ import annotations

import functools
import json
from typing import Any

import jax
import jax.numpy as jnp

from gofr_tpu.analysis import kernel_contracts as kc


def signature(x: Any) -> dict:
    """Portable (pytree, shape, dtype) signature of a value — identical
    for a concrete array pytree and its eval_shape twin."""
    leaves = jax.tree_util.tree_leaves(x)
    return {
        "tree": str(jax.tree_util.tree_structure(x)),
        "leaves": [
            [list(int(d) for d in getattr(l, "shape", ())),
             str(getattr(l, "dtype", type(l).__name__))]
            for l in leaves
        ],
    }


def _referenced(c: kc.KernelContract) -> set[str]:
    return {r.like for r in c.returns if r.like} | {
        p for p, _ in c.arg_shapes
    }


def _case(c: kc.KernelContract, variant: str, bound: dict,
          outs: Any) -> dict:
    if outs is None:  # observer records inputs first, outputs post-call
        out_list: list[Any] = []
    else:
        out_list = [outs] if len(c.returns) == 1 else list(outs)
    return {
        "kernel": c.name,
        "variant": variant,
        "inputs": {
            p: signature(bound[p]) for p in _referenced(c) if p in bound
        },
        "statics": {
            p: bound[p]
            for p in c.static
            if isinstance(bound.get(p), int)
            and not isinstance(bound.get(p), bool)
        },
        "outputs": [signature(o) for o in out_list],
    }


# ----------------------------------------------------- eval_shape matrix


def _eval_case(fn_raw, c: kc.KernelContract, variant: str,
               bound: dict) -> dict:
    """eval_shape one kernel entry. ``bound`` maps every contract param
    to either an abstract value (ShapeDtypeStruct pytree / None) or, for
    the params in ``c.static``, a concrete Python value."""
    dyn = [p for p in c.params if p not in c.static]
    statics = {p: bound[p] for p in c.static}

    def call(*dyn_vals):
        kw = dict(zip(dyn, dyn_vals))
        kw.update(statics)
        return fn_raw(**kw)

    outs = jax.eval_shape(call, *(bound[p] for p in dyn))
    return _case(c, variant, bound, outs)


def run_matrix() -> dict:
    """The full abstract-eval matrix. Imports the serving layer lazily
    (this module must stay importable from the no-jax lint path)."""
    from gofr_tpu.models import llama
    from gofr_tpu.ops import flash_attention as flash_mod
    from gofr_tpu.ops import paged_attention as pa_mod
    from gofr_tpu.serving import batch
    from gofr_tpu.serving import kv_cache as kvc_mod

    cfg = llama.LlamaConfig.tiny()
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    V, D = cfg.vocab_size, cfg.d_model
    S_MAX, S_BUCKET, PAGE, N_PAGES, M = 32, 8, 4, 6, 4
    RANK, ADAPTERS = 4, 2

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(
        lambda k: llama.init_params(cfg, k), key
    )
    lora_tabs = (
        sds((ADAPTERS, D, RANK), jnp.float32),
        sds((ADAPTERS, RANK, V), jnp.float32),
    )

    def dense_cache(B):
        shape = (L, B, S_MAX, Hkv, Dh)
        return llama.KVCache(sds(shape, cfg.dtype), sds(shape, cfg.dtype))

    kp = vp = sds((L, N_PAGES + 1, Hkv, PAGE, Dh), cfg.dtype)  # the pools

    def state(B):
        i = sds((B,), jnp.int32)
        f = sds((B,), jnp.float32)
        return batch.DecodeState(
            i, i, sds((B,), jnp.bool_), i, i, f, i, f, key, i,
        )

    def vec(B, dtype=jnp.int32):
        return sds((B,), dtype)

    def ragged_tail(B, steps, lora):
        return {
            "finish": vec(B, jnp.bool_), "new_len": vec(B),
            "budgets": vec(B), "stops": vec(B),
            "temps": vec(B, jnp.float32), "topks": vec(B),
            "topps": vec(B, jnp.float32), "rids": vec(B),
            "rng_root": key, "decode_active": vec(B, jnp.bool_),
            "steps": steps, "adapters": vec(B), "lora": lora,
        }

    def spec_tail(B):
        return {
            "temperature": vec(B, jnp.float32), "top_k": vec(B),
            "top_p": vec(B, jnp.float32), "rng": key,
        }

    C = kc.CONTRACTS
    cases: list[dict] = []

    def add(name, variant, fn, **bound):
        cases.append(_eval_case(fn, C[name], variant, bound))

    raw = {k.name: getattr(batch, k.name) for k in kc.KERNELS
           if k.file == kc.CARRY_FILE}

    def unwrap(name):
        fn = raw[name]
        return getattr(fn, "__wrapped__", fn)

    add("prefill_compute", "dense", unwrap("prefill_compute"),
        cfg=cfg, params=params,
        tokens=sds((1, S_BUCKET), jnp.int32), seq_len=vec(1))
    cache = dense_cache(1)
    add("insert_slot", "dense", unwrap("insert_slot"),
        k_cache=cache.k, v_cache=cache.v,
        k_slab=sds((L, S_BUCKET, Hkv, Dh), cfg.dtype),
        v_slab=sds((L, S_BUCKET, Hkv, Dh), cfg.dtype),
        slot=sds((), jnp.int32))
    add("insert_chunk", "dense", unwrap("insert_chunk"),
        k_cache=cache.k, v_cache=cache.v,
        k_slab=sds((L, 4, Hkv, Dh), cfg.dtype),
        v_slab=sds((L, 4, Hkv, Dh), cfg.dtype),
        slot=sds((), jnp.int32), start=sds((), jnp.int32))
    add("admit_decode_state", "dense", unwrap("admit_decode_state"),
        state=state(3), slots=vec(2), tokens=vec(2), lens=vec(2),
        budgets=vec(2), stops=vec(2), temps=vec(2, jnp.float32),
        topks=vec(2), topps=vec(2, jnp.float32), adapters=vec(2))

    for variant, B, steps, lora in (
        ("dense.b3n4", 3, 4, None),
        ("dense.b2n2", 2, 2, None),
        ("dense.lora", 3, 4, lora_tabs),
    ):
        add("decode_block", variant, unwrap("decode_block"),
            cfg=cfg, params=params, cache=dense_cache(B),
            state=state(B), active=vec(B, jnp.bool_), steps=steps,
            lora=lora)
    for variant, lora in (("paged", None), ("paged.lora", lora_tabs)):
        add("decode_block_paged", variant, unwrap("decode_block_paged"),
            cfg=cfg, params=params, k_pool=kp, v_pool=vp, state=state(3),
            block_tables=sds((3, M), jnp.int32),
            active=vec(3, jnp.bool_), steps=4, lora=lora)

    for variant, B, chunk_c, steps, lora in (
        ("dense.b3n4", 3, 4, 4, None),
        ("dense.b2n2", 2, 2, 2, None),
        ("dense.lora", 3, 4, 4, lora_tabs),
    ):
        add("ragged_step", variant, unwrap("ragged_step"),
            cfg=cfg, params=params, cache=dense_cache(B), state=state(B),
            chunk=sds((B, chunk_c), jnp.int32), chunk_start=vec(B),
            **ragged_tail(B, steps, lora))
    add("ragged_step_paged", "paged", unwrap("ragged_step_paged"),
        cfg=cfg, params=params, k_pool=kp, v_pool=vp, state=state(3),
        block_tables=sds((3, M), jnp.int32),
        chunk=sds((3, 4), jnp.int32), chunk_start=vec(3),
        chunk_active=vec(3, jnp.bool_), kv_capacity=vec(3),
        **ragged_tail(3, 4, None))

    add("verify_and_sample", "spec.dense", unwrap("verify_and_sample"),
        cfg=cfg, params=params, cache=dense_cache(3),
        chunk=sds((3, 3), jnp.int32), start_len=vec(3), **spec_tail(3))
    add("verify_and_sample_paged", "spec.paged",
        unwrap("verify_and_sample_paged"),
        cfg=cfg, params=params, k_pool=kp, v_pool=vp,
        block_tables=sds((3, M), jnp.int32),
        chunk=sds((3, 3), jnp.int32), start_len=vec(3),
        active=vec(3, jnp.bool_), kv_capacity=vec(3), **spec_tail(3))

    add("lora_adjust_logits", "lora", unwrap("lora_adjust_logits"),
        embedding=sds((V, D), cfg.dtype),
        a_row=sds((D, RANK), jnp.float32),
        b_row=sds((RANK, V), jnp.float32),
        token=sds((), jnp.int32), logits=sds((1, V), jnp.float32))

    add("sample_first_token", "first", unwrap("sample_first_token"),
        logits=sds((1, V), jnp.float32), key=key,
        temperature=sds((), jnp.float32), top_k=sds((), jnp.int32),
        top_p=sds((), jnp.float32))

    cases.append(_eval_case(
        kvc_mod._write_pages.__wrapped__, C["_write_pages"], "paged",
        {
            "k_pool": kp, "v_pool": vp,
            "k_slab": sds((L, 2 * PAGE, Hkv, Dh), cfg.dtype),
            "v_slab": sds((L, 2 * PAGE, Hkv, Dh), cfg.dtype),
            "page_ids": vec(2),
        },
    ))

    # the same write into a cache of several pools and a per-slot state: a
    # window pool of two layers, a full pool of one, a state array
    pools = {"window": sds((2, N_PAGES + 1, Hkv, PAGE, Dh), cfg.dtype),
             "full": sds((1, N_PAGES + 1, Hkv, PAGE, Dh), cfg.dtype)}
    slabs = {name: sds((pool.shape[0], 2 * PAGE, Hkv, Dh), cfg.dtype) for name, pool in pools.items()}
    cases.append(_eval_case(
        kvc_mod._write_slot.__wrapped__, C["_write_slot"], "pools",
        {
            "k_pool": {**pools, "state": {"ssm": sds((3, 3, 4, D), jnp.float32)}}, "v_pool": pools,
            "k_slab": {**slabs, "state": {"ssm": sds((3, 4, D), jnp.float32)}}, "v_slab": slabs,
            "page_ids": {"window": vec(2), "full": vec(2)}, "slot": sds((), jnp.int32),
        },
    ))

    # ops-level attention sees ONE layer's pool ([N+1, Hkv, page, Dh]) or,
    # given a layer index, the whole pools as the decode step passes them
    lp = sds((N_PAGES + 1, Hkv, PAGE, Dh), cfg.dtype)
    scalar = sds((), jnp.int32)
    # the window is None (the kernel without the argument) or a scalar
    for variant, pool_k, pool_v, window, layer in (
        ("paged", lp, lp, None, None),
        ("paged.window", lp, lp, scalar, None),
        ("paged.layer", kp, vp, None, scalar),
        ("paged.layer.window", kp, vp, scalar, scalar),
    ):
        cases.append(_eval_case(
            pa_mod.paged_decode_attention.__wrapped__,
            C["paged_decode_attention"], variant,
            {
                "q": sds((3, cfg.n_heads, Dh), cfg.dtype),
                "k_pool": pool_k, "v_pool": pool_v,
                "block_tables": sds((3, M), jnp.int32), "seq_lens": vec(3),
                "scale": None, "interpret": True, "window": window,
                "layer": layer,
            },
        ))
    cases.append(_eval_case(
        pa_mod.paged_kv_append.__wrapped__, C["paged_kv_append"], "paged",
        {
            "k_pool": kp, "v_pool": vp,
            "k_new": sds((3, Hkv, Dh), cfg.dtype),
            "v_new": sds((3, Hkv, Dh), cfg.dtype),
            "layer": scalar, "pages": vec(3), "offsets": vec(3),
            "interpret": True,
        },
    ))
    for variant, window in (("flash", None), ("flash.window", sds((), jnp.int32))):
        cases.append(_eval_case(
            flash_mod.flash_attention.__wrapped__, C["flash_attention"],
            variant,
            {
                "q": sds((2, 8, cfg.n_heads, Dh), cfg.dtype),
                "k": sds((2, 8, cfg.n_heads, Dh), cfg.dtype),
                "v": sds((2, 8, cfg.n_heads, Dh), cfg.dtype),
                "kv_len": None, "causal": True, "scale": None,
                "block_q": 128, "block_k": 128, "interpret": True,
                "window": window,
            },
        ))

    return {"mode": "matrix", "cases": cases, "violations": []}


def export_matrix(path: str) -> dict:
    payload = run_matrix()
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
    return payload


# --------------------------------------------------------- live observer


class KernelObserver:
    """Record device-contract signatures from a LIVE engine: wraps the
    host-dispatch kernel entries (``serving.batch``, ``serving.kv_cache``)
    so every unique call shape becomes an ``observed``-mode case for
    ``--check-kernel-table``. Input signatures are captured before the
    dispatch (metadata only — donation-safe); passthrough semantics stay
    untouched, so an installed observer changes nothing about the run."""

    def __init__(self) -> None:
        self.cases: list[dict] = []
        self.violations: list[str] = []
        self._seen: set[str] = set()
        self._orig: list[tuple[Any, str, Any]] = []

    def _recorder(self, c: kc.KernelContract, fn):
        @functools.wraps(fn)
        def recorded(*args, **kwargs):
            bound = dict(zip(c.params, args))
            for k, v in kwargs.items():
                if k not in c.params:
                    self.violations.append(
                        f"{c.name}: dispatched with undeclared "
                        f"keyword '{k}'"
                    )
                bound[k] = v
            if len(args) > len(c.params):
                self.violations.append(
                    f"{c.name}: dispatched with {len(args)} positional "
                    f"args; the contract declares {len(c.params)}"
                )
            case = None
            try:
                case = _case(c, "", bound, None)
            except Exception as exc:  # never perturb the engine
                self.violations.append(
                    f"{c.name}: could not record inputs ({exc})"
                )
            out = fn(*args, **kwargs)
            if case is not None:
                try:
                    out_list = [out] if len(c.returns) == 1 else list(out)
                    case["outputs"] = [signature(o) for o in out_list]
                except Exception as exc:
                    self.violations.append(
                        f"{c.name}: could not record outputs ({exc})"
                    )
                    return out
                dedup = json.dumps(
                    {k: v for k, v in case.items() if k != "variant"},
                    sort_keys=True,
                )
                if dedup not in self._seen:
                    self._seen.add(dedup)
                    case["variant"] = f"obs{len(self.cases)}"
                    self.cases.append(case)
            return out

        recorded.__kerneltrace_wrapped__ = fn
        return recorded

    def install(self) -> "KernelObserver":
        from gofr_tpu.serving import batch
        from gofr_tpu.serving import kv_cache as kvc_mod

        mods = {
            "gofr_tpu/serving/batch.py": batch,
            "gofr_tpu/serving/kv_cache.py": kvc_mod,
        }
        for c in kc.KERNELS:
            mod = mods.get(c.file)
            if mod is None:
                continue
            fn = getattr(mod, c.name)
            self._orig.append((mod, c.name, fn))
            setattr(mod, c.name, self._recorder(c, fn))
        return self

    def uninstall(self) -> None:
        for mod, name, fn in reversed(self._orig):
            setattr(mod, name, fn)
        self._orig.clear()

    def export(self, path: str | None = None) -> dict:
        payload = {
            "mode": "observed",
            "cases": self.cases,
            "violations": self.violations,
        }
        if path is not None:
            with open(path, "w", encoding="utf-8") as fh:
                json.dump(payload, fh, indent=2, sort_keys=True)
        return payload


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="export the eval_shape kernel-contract matrix"
    )
    ap.add_argument("--out", required=True)
    ns = ap.parse_args(argv)
    payload = export_matrix(ns.out)
    print(
        f"kerneltrace: {len(payload['cases'])} matrix case(s) -> {ns.out}"
    )
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
