"""kerneltrace (gofr_tpu/analysis/kerneltrace.py): the runtime twin of
the kernel contract table.

Tier-1 pins the two acceptance properties of the eval_shape matrix:

- ZERO device execution: every kernel is abstract-evaled through its
  ``__wrapped__`` raw function, so the jit caches of all contract-table
  kernels must not grow by a single entry across the full matrix.
- ZERO static<->runtime divergence: ``check_kernel_table`` replays the
  matrix (and a live-engine observer export) against the committed
  contract table and must come back empty.

The live-engine observer test runs a real ServingEngine workload; the
``make ci`` fixture lane deselects it (engine-running), tier-1 runs it.
"""

from __future__ import annotations

import json

import jax
import pytest

from gofr_tpu.analysis import kernel_contracts as kc
from gofr_tpu.analysis import kerneltrace
from gofr_tpu.analysis.kernelcheck import check_kernel_table

jax.config.update("jax_platforms", "cpu")


def _jitted_kernels():
    """Every jitted entry the contract table covers, by live module
    attribute (the objects whose caches must stay frozen)."""
    from gofr_tpu.ops import flash_attention as flash_mod
    from gofr_tpu.ops import paged_attention as pa_mod
    from gofr_tpu.serving import batch
    from gofr_tpu.serving import kv_cache as kvc_mod

    mods = {
        "gofr_tpu/serving/batch.py": batch,
        "gofr_tpu/serving/kv_cache.py": kvc_mod,
        "gofr_tpu/ops/paged_attention.py": pa_mod,
        "gofr_tpu/ops/flash_attention.py": flash_mod,
    }
    out = {}
    for c in kc.KERNELS:
        fn = getattr(mods[c.file], c.name)
        if hasattr(fn, "_cache_size"):
            out[c.name] = fn
    return out


def _cache_sizes(kernels):
    return {name: fn._cache_size() for name, fn in kernels.items()}


@pytest.fixture(scope="module")
def matrix_payload():
    """Run the matrix ONCE per module, guarded by the zero-compilation
    assertion — every test that consumes the payload also re-proves the
    no-device-execution property."""
    kernels = _jitted_kernels()
    before = _cache_sizes(kernels)
    payload = kerneltrace.run_matrix()
    after = _cache_sizes(kernels)
    grew = {n: (before[n], after[n]) for n in before
            if after[n] != before[n]}
    assert grew == {}, f"eval_shape matrix compiled kernels: {grew}"
    return payload


def test_matrix_runs_with_zero_compilation(matrix_payload):
    # the fixture itself asserts the zero jit-cache-growth property;
    # here we pin the payload shape
    assert matrix_payload["mode"] == "matrix"
    assert matrix_payload["violations"] == []
    assert len(matrix_payload["cases"]) >= 20


def test_matrix_zero_divergence_against_contract_table(matrix_payload):
    divergences = check_kernel_table(matrix_payload)
    assert divergences == [], "\n".join(divergences)


def test_matrix_covers_every_batch_kernel(matrix_payload):
    exercised = {c["kernel"] for c in matrix_payload["cases"]}
    declared = {k.name for k in kc.KERNELS if k.file == kc.CARRY_FILE}
    assert declared <= exercised, declared - exercised
    # and the config matrix axes actually vary
    variants = {c["variant"] for c in matrix_payload["cases"]
                if c["kernel"] == "decode_block_paged"}
    assert {"paged.b3n4", "paged.b2n2", "paged.lora"} <= variants


def test_table_and_live_jitted_entries_are_the_same_set(matrix_payload):
    """The table cannot keep a dead row or miss a live one: in every file
    it covers, the names it declares are exactly the module-level
    ``jax.jit``-wrapped functions defined there (an import of another
    module's jitted function does not count), and the matrix evaluates
    every one of them."""
    import importlib

    assert len(kc.KERNELS) == len(kc.CONTRACTS) == 12
    for rel in kc.KERNEL_FILES:
        mod = importlib.import_module(rel[:-3].replace("/", "."))
        live = {
            name for name, fn in vars(mod).items()
            if hasattr(fn, "_cache_size") and hasattr(fn, "lower")
            and getattr(fn, "__module__", None) == mod.__name__
        }
        assert live == set(kc.contracts_for_file(rel)), (rel, live)
    exercised = {c["kernel"] for c in matrix_payload["cases"]}
    assert exercised == set(kc.CONTRACTS)


def test_matrix_case_signatures_are_portable(matrix_payload):
    # every signature is plain JSON data: [shape-ints, dtype-str]
    blob = json.loads(json.dumps(matrix_payload))
    for case in blob["cases"]:
        for sig in list(case["inputs"].values()) + case["outputs"]:
            assert isinstance(sig["tree"], str)
            for shape, dtype in sig["leaves"]:
                assert all(isinstance(d, int) for d in shape)
                assert isinstance(dtype, str)


def test_export_matrix_cli_round_trip(tmp_path):
    from gofr_tpu.analysis.__main__ import main as analysis_main

    out = str(tmp_path / "matrix.json")
    assert kerneltrace.main(["--out", out]) == 0
    with open(out, encoding="utf-8") as fh:
        blob = json.load(fh)
    assert blob["mode"] == "matrix"
    assert analysis_main(["--check-kernel-table", out]) == 0


def test_check_kernel_table_flags_a_doctored_export(tmp_path):
    payload = kerneltrace.run_matrix()
    doctored = json.loads(json.dumps(payload))
    for case in doctored["cases"]:
        if case["kernel"] == "decode_block_paged":
            # widen the packed block by one column
            shape = case["outputs"][0]["leaves"][0][0]
            shape[-1] += 1
            break
    divergences = check_kernel_table(doctored)
    assert any("decode_block_paged" in d and "by the contract" in d
               for d in divergences), divergences

    from gofr_tpu.analysis.__main__ import main as analysis_main

    bad = tmp_path / "doctored.json"
    bad.write_text(json.dumps(doctored))
    assert analysis_main(["--check-kernel-table", str(bad)]) == 1


def test_observer_live_engine_matches_contract_table():
    """The acceptance run: wrap the kernel dispatch surface of a REAL
    engine, serve a small workload, and assert every observed dispatch
    signature matches the committed contract table — zero divergences.
    (Deselected in the `make ci` fixture lane; tier-1 runs it.)"""
    from gofr_tpu.models import llama
    from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
    from gofr_tpu.serving import batch

    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(
        cfg, params,
        EngineConfig(max_slots=4, max_seq_len=64,
                     prefill_buckets=(16, 32), max_queue=64),
        ByteTokenizer(),
    )

    obs = kerneltrace.KernelObserver().install()
    try:
        assert getattr(batch.decode_block_paged, "__kerneltrace_wrapped__",
                       None) is not None
        engine.start()
        try:
            futures = [
                engine.submit("hello", max_new_tokens=6, temperature=0.0),
                engine.submit("another prompt here", max_new_tokens=4,
                              temperature=0.0),
            ]
            for f in futures:
                f.result(timeout=60)
        finally:
            engine.stop()
    finally:
        obs.uninstall()

    # passthrough restored
    assert getattr(batch.decode_block_paged, "__kerneltrace_wrapped__",
                   None) is None

    payload = obs.export()
    assert payload["violations"] == []
    exercised = {c["kernel"] for c in payload["cases"]}
    assert "prefill_compute" in exercised
    assert "decode_block_paged" in exercised
    divergences = check_kernel_table(payload)
    assert divergences == [], "\n".join(divergences)


def test_observer_uninstall_is_exact():
    from gofr_tpu.serving import batch

    before = {k.name: getattr(batch, k.name) for k in kc.KERNELS
              if k.file == kc.CARRY_FILE}
    obs = kerneltrace.KernelObserver().install()
    obs.uninstall()
    after = {k.name: getattr(batch, k.name) for k in kc.KERNELS
             if k.file == kc.CARRY_FILE}
    assert before == after


def test_signature_matches_eval_shape_twin():
    # a concrete array and its ShapeDtypeStruct twin must sign identically
    import jax.numpy as jnp

    concrete = {"a": jnp.zeros((2, 3), jnp.int32),
                "b": (jnp.ones((4,), jnp.float32),)}
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), concrete
    )
    assert kerneltrace.signature(concrete) == \
        kerneltrace.signature(abstract)
