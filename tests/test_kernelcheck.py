"""kernelcheck (gofr_tpu/analysis/kernelcheck.py): the device-contract
analyzer over the committed kernel contract table
(gofr_tpu/analysis/kernel_contracts.py) — pack-layout-drift,
dtype-discipline, carry-field-drift, spec-rank-mismatch, the
kernel-contract-coverage audit, the static<->runtime ``check_kernel_table``
verifier, suppressions, and the unified ``--all`` wiring.
docs/static-analysis.md#kernelcheck documents the catalog these pin down.

Pure-AST + pure-data tests: no jax import, no engine. The eval_shape
matrix and the live-engine observer live in tests/test_kerneltrace.py.
"""

from __future__ import annotations

import json
import os

from gofr_tpu.analysis import baseline_io
from gofr_tpu.analysis import kernel_contracts as kc
from gofr_tpu.analysis.core import run_rules, run_unified
from gofr_tpu.analysis.kernelcheck import (
    CarryFieldDriftRule,
    DtypeDisciplineRule,
    KernelContractCoverageRule,
    PackLayoutRule,
    SpecRankRule,
    check_kernel_table,
    kernelcheck_rules,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_tree(tmp_path, files: dict[str, str], rules=None):
    """Materialize {relpath: source} under tmp_path and lint the top dir
    with the given kernelcheck families (fixture isolation from the
    other rule sets)."""
    for rel, source in files.items():
        full = tmp_path / rel
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(source)
    top = tmp_path / sorted(files)[0].split("/")[0]
    return run_rules([str(top)], rules if rules is not None
                     else kernelcheck_rules())


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------- pack-layout-drift
# Fixtures land on the REAL contract-table rel-paths (the table is keyed
# by gofr_tpu/serving/... anchors), so the rule checks them against the
# committed layouts.

_GOOD_CONSUME = (
    "def _block_sync(x):\n"
    "    return x\n"
    "\n"
    "def _consume_block(self, rec, slot):\n"
    "    packed = _block_sync(rec.packed)\n"
    "    device_done = bool(packed[slot, rec.steps])\n"
    "    n_valid = int(packed[slot, rec.steps + 1])\n"
    "    first_id = int(packed[slot, rec.steps + 2])\n"
    "    toks = [int(packed[slot, i]) for i in range(n_valid)]\n"
    "    return toks, device_done, first_id\n"
)


def test_unpack_offset_past_layout_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": _GOOD_CONSUME.replace(
            "rec.steps + 2", "rec.steps + 7"
        ),
    }, rules=[PackLayoutRule()])
    assert any(
        f.rule == "pack-layout-drift" and "past layout 'ragged'" in f.message
        for f in findings
    ), rules_of(findings)
    # and the first column is now never consumed
    assert any("never consumes" in f.message and "'first'" in f.message
               for f in findings)


def test_unpack_binding_misbind_flagged(tmp_path):
    # n_valid read from the DONE column: the classic silent mis-bind
    src = _GOOD_CONSUME.replace(
        "    device_done = bool(packed[slot, rec.steps])\n"
        "    n_valid = int(packed[slot, rec.steps + 1])\n",
        "    device_done = bool(packed[slot, rec.steps + 1])\n"
        "    n_valid = int(packed[slot, rec.steps])\n",
    )
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": src,
    }, rules=[PackLayoutRule()])
    assert any(
        "binding 'n_valid' reads packed column 'done'" in f.message
        for f in findings
    ), [f.message for f in findings]


def test_unpack_clean_consume_block(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": _GOOD_CONSUME,
    }, rules=[PackLayoutRule()])
    assert findings == [], rules_of(findings)


def test_unpack_clean_spec_negative_slices(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "def _block_sync(x):\n"
            "    return x\n"
            "\n"
            "def _spec_step(self):\n"
            "    packed_np = _block_sync(self.packed)\n"
            "    out_np = packed_np[:, :-1]\n"
            "    na_np = packed_np[:, -1]\n"
            "    return out_np, na_np\n"
        ),
    }, rules=[PackLayoutRule()])
    assert findings == [], rules_of(findings)


def test_pack_helper_column_swap_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "import jax.numpy as jnp\n"
            "\n"
            "def _pack_block(toks, done, active):\n"
            "    n_valid = jnp.sum(toks >= 0, axis=1, dtype=jnp.int32)\n"
            "    return jnp.concatenate(\n"
            "        [toks.astype(jnp.int32), n_valid[:, None],\n"
            "         (done & active)[:, None].astype(jnp.int32)],\n"
            "        axis=1)\n"
        ),
    }, rules=[PackLayoutRule()])
    msgs = [f.message for f in findings]
    assert any("should carry 'done'" in m for m in msgs), msgs
    assert any("should carry 'n_valid'" in m for m in msgs), msgs


def test_spec_kernel_missing_scalar_column_flagged(tmp_path):
    # verify_and_sample that forgets the n_accept tail column
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "from functools import partial\n"
            "\n"
            "@partial(jax.jit, static_argnums=0, donate_argnums=(2,))\n"
            "def verify_and_sample(cfg, params, cache, chunk, start_len,\n"
            "                      temperature, top_k, top_p, rng):\n"
            "    out = chunk\n"
            "    packed = jnp.concatenate([out.astype(jnp.int32)], axis=1)\n"
            "    return packed, cache, rng\n"
        ),
    }, rules=[PackLayoutRule()])
    assert any(
        "packs 0 scalar column(s)" in f.message
        and "layout 'spec'" in f.message
        for f in findings
    ), [f.message for f in findings]


def test_decode_block_wrong_helper_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "import jax\n"
            "from functools import partial\n"
            "\n"
            "@partial(jax.jit, static_argnums=(0, 5), donate_argnums=(2, 3))\n"
            "def decode_block(cfg, params, cache, state, active, steps,\n"
            "                 lora=None):\n"
            "    return _pack_ragged(None, None, active, None), cache, state\n"
        ),
    }, rules=[PackLayoutRule()])
    msgs = [f.message for f in findings]
    assert any("never calls its pack helper _pack_block()" in m
               for m in msgs), msgs
    assert any("calls _pack_ragged() which packs layout 'ragged'" in m
               for m in msgs), msgs


def test_decode_block_declared_helper_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "import jax\n"
            "from functools import partial\n"
            "\n"
            "@partial(jax.jit, static_argnums=(0, 5), donate_argnums=(2, 3))\n"
            "def decode_block(cfg, params, cache, state, active, steps,\n"
            "                 lora=None):\n"
            "    return _pack_block(None, None, active), cache, state\n"
        ),
    }, rules=[PackLayoutRule()])
    assert findings == [], rules_of(findings)


# ----------------------------------------------------- dtype-discipline
def test_dtypeless_asarray_of_literal_in_hot_zone_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/ops/sampling.py": (
            "import jax.numpy as jnp\n"
            "def sample(logits):\n"
            "    t = jnp.asarray(1.0)\n"
            "    return logits / t\n"
        ),
    }, rules=[DtypeDisciplineRule()])
    assert any("dtype-less jnp.asarray()" in f.message for f in findings)


def test_64bit_dtype_in_engine_hot_func_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "import jax.numpy as jnp\n"
            "def _dispatch_decode(self):\n"
            "    ids = jnp.asarray(self.ids, jnp.int64)\n"
            "    return ids\n"
        ),
    }, rules=[DtypeDisciplineRule()])
    assert any("64-bit dtype jnp.int64" in f.message for f in findings)


def test_float_index_arange_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "import jax.numpy as jnp\n"
            "def k(x):\n"
            "    idx = jnp.arange(8, dtype=jnp.float32)\n"
            "    return x[idx]\n"
        ),
    }, rules=[DtypeDisciplineRule()])
    assert any("non-int32 dtype" in f.message for f in findings)


def test_engine_cold_function_not_in_dtype_zone(tmp_path):
    # same literal promotion OUTSIDE the hot funcs: not this rule's zone
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "import jax.numpy as jnp\n"
            "def warmup(self):\n"
            "    t = jnp.asarray(1.0)\n"
            "    return t\n"
        ),
    }, rules=[DtypeDisciplineRule()])
    assert findings == [], rules_of(findings)


def test_explicit_dtype_asarray_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/ops/sampling.py": (
            "import jax.numpy as jnp\n"
            "def sample(logits):\n"
            "    t = jnp.asarray(1.0, jnp.float32)\n"
            "    idx = jnp.arange(8)\n"
            "    return logits / t + idx\n"
        ),
    }, rules=[DtypeDisciplineRule()])
    assert findings == [], rules_of(findings)


# ---------------------------------------------------- carry-field-drift
_FIELD_LINES = "".join(
    f"    {n}: int\n" for n, _ in kc.DECODE_STATE_FIELDS
)


def test_decode_state_missing_field_flagged(tmp_path):
    body = "".join(
        f"    {n}: int\n" for n, _ in kc.DECODE_STATE_FIELDS[:-1]
    )
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "class DecodeState:\n" + body
        ),
    }, rules=[CarryFieldDriftRule()])
    assert any("!= declared carry spec" in f.message for f in findings)


def test_decode_state_ctor_arity_drift_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "class DecodeState:\n" + _FIELD_LINES +
            "\n"
            "def _block_step(st):\n"
            "    return DecodeState(1, 2, 3, 4, 5, 6, 7, 8, 9)\n"
        ),
    }, rules=[CarryFieldDriftRule()])
    assert any("constructed with 9 of 10" in f.message for f in findings)


def test_make_decode_state_wrong_dtype_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "import jax.numpy as jnp\n"
            "class DecodeState:\n" + _FIELD_LINES +
            "\n"
            "def make_decode_state(last_token, seq_len, done, budget,\n"
            "                      stop_tok, temperature, top_k, top_p,\n"
            "                      rng, adapter):\n"
            "    return DecodeState(\n"
            "        jnp.asarray(last_token, jnp.int32),\n"
            "        jnp.asarray(seq_len, jnp.int32),\n"
            "        jnp.asarray(done, bool),\n"
            "        jnp.asarray(budget, jnp.int32),\n"
            "        jnp.asarray(stop_tok, jnp.int32),\n"
            "        jnp.asarray(temperature, jnp.int32),\n"  # drifted
            "        jnp.asarray(top_k, jnp.int32),\n"
            "        jnp.asarray(top_p, jnp.float32),\n"
            "        rng,\n"
            "        jnp.asarray(adapter, jnp.int32),\n"
            "    )\n"
        ),
    }, rules=[CarryFieldDriftRule()])
    assert any(
        "'temperature' uploaded as int32" in f.message for f in findings
    ), [f.message for f in findings]


def test_admit_dropping_field_flagged(tmp_path):
    sets = "".join(
        f"        state.{n}.at[slots].set({n}s),\n"
        for n, _ in kc.DECODE_STATE_FIELDS if n not in ("rng", "adapter")
    )
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "class DecodeState:\n" + _FIELD_LINES +
            "\n"
            "def admit_decode_state(state, slots, *vals):\n"
            "    return DecodeState(\n" + sets +
            "        state.rng,\n"
            "        slots,\n"  # adapter never sourced from the carry
            "    )\n"
        ),
    }, rules=[CarryFieldDriftRule()])
    assert any(
        "never references carry field(s) ['adapter']" in f.message
        for f in findings
    ), [f.message for f in findings]


def test_tree_unflatten_starred_ctor_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "class DecodeState:\n" + _FIELD_LINES +
            "    @classmethod\n"
            "    def tree_unflatten(cls, _aux, children):\n"
            "        return cls(*children)\n"
        ),
    }, rules=[CarryFieldDriftRule()])
    assert findings == [], rules_of(findings)


def test_pending_admit_tuple_arity_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "class Engine:\n"
            "    def admit(self, slot, first_id, resident, budget):\n"
            "        self._pending_admit[slot] = (first_id, resident,\n"
            "                                     budget)\n"
        ),
    }, rules=[CarryFieldDriftRule()])
    assert any("built with 3 element(s)" in f.message for f in findings)


def test_pending_admit_annotation_arity_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "class Engine:\n"
            "    def __init__(self):\n"
            "        self._pending_admit: dict[int, tuple[int, int, int,"
            " int]] = {}\n"
        ),
    }, rules=[CarryFieldDriftRule()])
    assert any("annotated as a 4-tuple" in f.message for f in findings)


def test_pending_admit_correct_arity_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "class Engine:\n"
            "    def __init__(self):\n"
            "        self._pending_admit: dict[int, tuple[int, int, int,"
            " int, int]] = {}\n"
            "    def admit(self, slot, a, b, c, d, e):\n"
            "        self._pending_admit[slot] = (a, b, c, d, e)\n"
        ),
    }, rules=[CarryFieldDriftRule()])
    assert findings == [], rules_of(findings)


# --------------------------------------------------- spec-rank-mismatch
def test_shard_map_in_specs_arity_mismatch_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/x.py": (
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n"
            "def body(a, b):\n"
            "    return a, b\n"
            "def wrap(mesh, x, y, z):\n"
            "    spec = P('x', None)\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(spec, spec, P()),\n"
            "                     out_specs=(P(), P()))(x, y, z)\n"
        ),
    }, rules=[SpecRankRule()])
    assert any("has 3 spec(s) but 'body' takes 2" in f.message
               for f in findings)


def test_partition_spec_arity_exceeds_declared_rank_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/x.py": (
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n"
            "def body(a,  # [B, S, D]\n"
            "         b):  # [B, D]\n"
            "    return a, b\n"
            "def wrap(mesh, x, y):\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(P('x'), P('x', None, None)),\n"
            "                     out_specs=(P(), P()))(x, y)\n"
        ),
    }, rules=[SpecRankRule()])
    assert any("PartitionSpec arity exceeds the array rank" in f.message
               for f in findings)


def test_out_specs_vs_returned_tuple_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/x.py": (
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n"
            "def body(a, b):\n"
            "    return a, b\n"
            "def wrap(mesh, x, y):\n"
            "    return shard_map(body, mesh=mesh, in_specs=(P(), P()),\n"
            "                     out_specs=P())(x, y)\n"
        ),
    }, rules=[SpecRankRule()])
    assert any("returns 2 value(s)" in f.message for f in findings)


def test_call_arity_vs_in_specs_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/x.py": (
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n"
            "def wrap(mesh, fn, x, y, z):\n"
            "    return shard_map(fn, mesh=mesh, in_specs=(P(), P()),\n"
            "                     out_specs=P())(x, y, z)\n"
        ),
    }, rules=[SpecRankRule()])
    assert any("called with 3 array(s)" in f.message for f in findings)


def test_partial_bound_inner_and_trailing_spec_clean(tmp_path):
    # the real context_parallel idiom: kwonly partial + spec shorter
    # than rank (legal: trailing dims replicate)
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/x.py": (
            "import functools\n"
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n"
            "def inner(q,  # [B, S, H, D]\n"
            "          k,  # [B, S, H, D]\n"
            "          v,  # [B, S, H, D]\n"
            "          *, axis_name, axis_size):\n"
            "    return q\n"
            "def wrap(mesh, q, k, v, n):\n"
            "    spec = P(None, 'x', None, None)\n"
            "    fn = functools.partial(inner, axis_name='x',"
            " axis_size=n)\n"
            "    return shard_map(fn, mesh=mesh,\n"
            "                     in_specs=(spec, spec, spec),\n"
            "                     out_specs=spec)(q, k, v)\n"
        ),
    }, rules=[SpecRankRule()])
    assert findings == [], rules_of(findings)


def test_unresolvable_spec_pytree_skipped(tmp_path):
    # the pipeline.py idiom: param_specs is a tree-mapped pytree the
    # AST cannot resolve — must not false-positive
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/x.py": (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "from jax import shard_map\n"
            "def wrap(mesh, stage_params, x_mb, axis):\n"
            "    def body(stage_local, x):\n"
            "        return x\n"
            "    param_specs = jax.tree.map(lambda _: P(axis),"
            " stage_params)\n"
            "    return shard_map(body, mesh=mesh,\n"
            "                     in_specs=(param_specs, P()),\n"
            "                     out_specs=P())(stage_params, x_mb)\n"
        ),
    }, rules=[SpecRankRule()])
    assert findings == [], rules_of(findings)


# --------------------------------------------- kernel-contract-coverage
def test_new_jitted_kernel_without_contract_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/ops/flash_attention.py": (
            "import functools\n"
            "import jax\n"
            "@functools.partial(jax.jit, static_argnames=('causal',"
            " 'scale', 'block_q', 'block_k', 'interpret'))\n"
            "def flash_attention(q, k, v, kv_len=None, *, causal=True,\n"
            "                    scale=None, block_q=128, block_k=128,\n"
            "                    interpret=None, window=None):\n"
            "    return q\n"
            "\n"
            "@jax.jit\n"
            "def brand_new_kernel(x):\n"
            "    return x\n"
        ),
    }, rules=[KernelContractCoverageRule(anchor=None)])
    assert any(
        "'brand_new_kernel' has no declared contract" in f.message
        for f in findings
    ), [f.message for f in findings]


def test_donation_drift_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/kv_cache.py": (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, donate_argnums=(0,))\n"  # contract: (0, 1)
            "def _write_pages(k_pool, v_pool, k_slab, v_slab, page_ids):\n"
            "    return k_pool, v_pool\n"
        ),
    }, rules=[KernelContractCoverageRule(anchor=None)])
    assert any(
        "donates ['k_pool'] but the contract declares"
        " ['k_pool', 'v_pool']" in f.message
        for f in findings
    ), [f.message for f in findings]


def test_signature_drift_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/kv_cache.py": (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, donate_argnums=(0, 1))\n"
            "def _write_pages(k_pool, v_pool, slab, page_ids):\n"
            "    return k_pool, v_pool\n"
        ),
    }, rules=[KernelContractCoverageRule(anchor=None)])
    assert any("signature" in f.message and "declared contract params"
               in f.message for f in findings)


def test_stale_contract_flagged(tmp_path):
    # file walked, declared kernel vanished -> stale table entry
    findings = lint_tree(tmp_path, {
        "gofr_tpu/ops/flash_attention.py": "X = 1\n",
    }, rules=[KernelContractCoverageRule(anchor=None)])
    assert any(
        "'flash_attention' matches no jitted def" in f.message
        for f in findings
    ), [f.message for f in findings]


def test_vanished_unpack_site_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "def _consume_block(self):\n"
            "    pass\n"
        ),
    }, rules=[KernelContractCoverageRule(anchor=None)])
    assert any(
        "'_spec_step' no longer exists" in f.message for f in findings
    ), [f.message for f in findings]


def test_matching_kernel_file_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/ops/flash_attention.py": (
            "import functools\n"
            "import jax\n"
            "@functools.partial(jax.jit, static_argnames=('causal',"
            " 'scale', 'block_q', 'block_k', 'interpret'))\n"
            "def flash_attention(q, k, v, kv_len=None, *, causal=True,\n"
            "                    scale=None, block_q=128, block_k=128,\n"
            "                    interpret=None, window=None):\n"
            "    return q\n"
        ),
    }, rules=[KernelContractCoverageRule(anchor=None)])
    assert findings == [], rules_of(findings)


def test_coverage_rule_inert_without_real_tree_anchor(tmp_path):
    # fixture trees (other analyzers' suites) materialize files NAMED
    # like the kernel files; without engine.py defining ServingEngine
    # the default-anchored rule must stay silent
    findings = lint_tree(tmp_path, {
        "gofr_tpu/ops/flash_attention.py": "X = 1\n",
        "gofr_tpu/serving/engine.py": "def _consume_block(self):\n"
                                      "    pass\n",
    }, rules=[KernelContractCoverageRule()])
    assert findings == [], rules_of(findings)


def test_non_kernel_file_ignored(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/other/tool.py": (
            "import jax\n"
            "@jax.jit\n"
            "def helper(x):\n"
            "    return x\n"
        ),
    }, rules=[KernelContractCoverageRule(anchor=None)])
    assert findings == [], rules_of(findings)


# ----------------------------------------------- static <-> runtime twin
def _sig(shape, dtype, tree="*"):
    return {"tree": tree, "leaves": [[list(shape), dtype]]}


def _decode_block_case(**over):
    state = {
        "tree": "DecodeState",
        "leaves": [[[3], "int32"]] * 2 + [[[3], "bool"]] + [[[3], "int32"]]
        * 2 + [[[3], "float32"]] + [[[3], "int32"]] + [[[3], "float32"]]
        + [[[2], "uint32"]] + [[[3], "int32"]],
    }
    cache = {"tree": "KVCache", "leaves": [[[2, 3, 32, 2, 16],
                                            "float32"]] * 2}
    case = {
        "kernel": "decode_block",
        "variant": "t",
        "inputs": {"active": _sig((3,), "bool"), "cache": cache,
                   "state": state},
        "statics": {"steps": 4},
        "outputs": [_sig((3, 6), "int32"), cache, state],
    }
    case.update(over)
    return case


def test_check_kernel_table_clean_case():
    assert check_kernel_table(
        {"mode": "observed", "cases": [_decode_block_case()]}
    ) == []


def test_check_kernel_table_packed_width_drift():
    bad = _decode_block_case()
    bad["outputs"][0] = _sig((3, 7), "int32")
    div = check_kernel_table({"mode": "observed", "cases": [bad]})
    assert any("dim 'steps+2' = 6 by the contract, observed 7" in d
               for d in div), div


def test_check_kernel_table_packed_dtype_drift():
    bad = _decode_block_case()
    bad["outputs"][0] = _sig((3, 6), "int64")
    div = check_kernel_table({"mode": "observed", "cases": [bad]})
    assert any("dtype int64" in d and "declares int32" in d for d in div)


def test_check_kernel_table_donated_carry_drift():
    bad = _decode_block_case()
    drifted = dict(bad["outputs"][2])
    drifted["leaves"] = drifted["leaves"][:-1]  # adapter leaf dropped
    bad["outputs"][2] = drifted
    div = check_kernel_table({"mode": "observed", "cases": [bad]})
    assert any("donated-carry drift" in d for d in div), div


def test_check_kernel_table_output_arity_drift():
    bad = _decode_block_case()
    bad["outputs"] = bad["outputs"][:2]
    div = check_kernel_table({"mode": "observed", "cases": [bad]})
    assert any("returned 2 output(s); the contract declares 3" in d
               for d in div)


def test_check_kernel_table_unknown_kernel_and_violations():
    div = check_kernel_table({
        "mode": "observed",
        "cases": [{"kernel": "mystery_kernel", "variant": "x",
                   "inputs": {}, "statics": {}, "outputs": []}],
        "violations": ["decode_block: dispatched with undeclared kw"],
    })
    assert any("no declared contract" in d for d in div)
    assert any(d.startswith("runtime violation:") for d in div)


def test_check_kernel_table_matrix_requires_full_batch_coverage():
    div = check_kernel_table(
        {"mode": "matrix", "cases": [_decode_block_case()]}
    )
    assert any("'ragged_step' was never exercised" in d for d in div)
    # observed mode is a real workload: partial coverage is fine
    assert check_kernel_table(
        {"mode": "observed", "cases": [_decode_block_case()]}
    ) == []


def test_contract_table_json_stable():
    blob = json.loads(kc.render_table_json())
    assert {k["name"] for k in blob["kernels"]} == set(kc.CONTRACTS)
    assert blob["carry"]["fields"][0] == ["last_token", "int32"]
    assert blob["layouts"]["ragged"]["scalars"] == [
        "done", "n_valid", "first"
    ]


def test_every_batch_kernel_has_contract_and_layouts_agree():
    # the committed table itself stays self-consistent
    for k in kc.KERNELS:
        if k.packed is not None:
            assert k.packed in kc.PACK_LAYOUTS, k.name
            assert k.returns and k.returns[0].dtype == "int32", k.name
        for r in k.returns:
            assert (r.shape is None) != (r.like is None), (k.name, r.name)
            if r.like:
                assert r.like in k.params, (k.name, r.like)
        for p in k.donated + k.static:
            assert p in k.params, (k.name, p)


# ------------------------------------------------- real tree & the gate
def test_real_tree_clean():
    """The acceptance bar: the repo itself is kernelcheck-clean — every
    batch.py/ops kernel entry matches its declared contract, the unpack
    sites slice the declared columns, and the carry sites agree."""
    findings = run_rules(
        [os.path.join(REPO_ROOT, "gofr_tpu")], kernelcheck_rules()
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_unified_pass_includes_kernelcheck_rules():
    from gofr_tpu.analysis.rules import default_rules

    names = {r.name for r in default_rules()}
    assert {
        "pack-layout-drift", "dtype-discipline", "carry-field-drift",
        "spec-rank-mismatch", "kernel-contract-coverage",
    } <= names


def test_unified_run_keeps_kernelcheck_suppressions_live(tmp_path):
    for rel, source in {
        "gofr_tpu/ops/sampling.py": (
            "import jax.numpy as jnp\n"
            "def sample(logits):\n"
            "    # gofrlint: disable=dtype-discipline -- deliberate weak\n"
            "    t = jnp.asarray(1.0)\n"
            "    return logits / t\n"
        ),
    }.items():
        full = tmp_path / rel
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(source)
    live, stale = run_unified(
        [str(tmp_path / "gofr_tpu")], [DtypeDisciplineRule()]
    )
    assert [f for f in live if f.rule == "dtype-discipline"] == []
    assert stale == [], "\n".join(f.render() for f in stale)


def test_findings_roundtrip_json_and_sarif(tmp_path):
    from gofr_tpu.analysis.sarif import render_sarif

    findings = lint_tree(tmp_path, {
        "gofr_tpu/ops/sampling.py": (
            "import jax.numpy as jnp\n"
            "def sample(logits):\n"
            "    t = jnp.asarray(1.0)\n"
            "    return logits / t\n"
        ),
    }, rules=[DtypeDisciplineRule()])
    assert findings
    blob = json.loads(baseline_io.render_json(findings))
    assert any(e["rule"] == "dtype-discipline" for e in blob["findings"])
    sarif = json.loads(render_sarif(findings))
    results = sarif["runs"][0]["results"]
    assert any(r["ruleId"] == "dtype-discipline" for r in results)
    rules = sarif["runs"][0]["tool"]["driver"]["rules"]
    assert any(r["id"] == "pack-layout-drift" for r in rules)


def test_baseline_covers_kernelcheck_findings(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/ops/sampling.py": (
            "import jax.numpy as jnp\n"
            "def sample(logits):\n"
            "    t = jnp.asarray(1.0)\n"
            "    return logits / t\n"
        ),
    }, rules=[DtypeDisciplineRule()])
    assert findings
    path = str(tmp_path / "baseline.json")
    baseline_io.write_baseline(path, findings)
    left, covered = baseline_io.apply_baseline(
        findings, baseline_io.load_baseline(path)
    )
    assert left == [] and covered == len(findings)


def test_cli_check_kernel_table_exit_codes(tmp_path):
    from gofr_tpu.analysis.__main__ import main

    clean = tmp_path / "clean.json"
    clean.write_text(json.dumps(
        {"mode": "observed", "cases": [_decode_block_case()]}
    ))
    assert main(["--check-kernel-table", str(clean)]) == 0

    bad_case = _decode_block_case()
    bad_case["outputs"][0] = _sig((3, 9), "int32")
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"mode": "observed", "cases": [bad_case]}))
    assert main(["--check-kernel-table", str(bad)]) == 1

    assert main(
        ["--check-kernel-table", str(tmp_path / "missing.json")]
    ) == 2


def test_cli_kernel_table_emits_table(capsys):
    from gofr_tpu.analysis.__main__ import main

    assert main(["--kernel-table"]) == 0
    blob = json.loads(capsys.readouterr().out)
    assert "decode_block" in {k["name"] for k in blob["kernels"]}
