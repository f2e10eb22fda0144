"""Test configuration.

Per SURVEY §4's implication: CI never needs TPU hardware — JAX runs on CPU
with 8 virtual devices so multi-chip sharding paths (TP/DP/SP meshes) are
exercised for real, the way the reference tests multi-node behavior against
single-node service containers (.github/workflows/go.yml:38-77).

The platform is forced through jax.config (it beats the JAX_PLATFORMS env
var, whatever the machine exports). Set GOFR_TEST_TPU=1 to run the suite
against the real chip instead.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import asyncio  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

if os.environ.get("GOFR_TEST_TPU") != "1":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

# Exact f32 matmuls in tests: the platform default uses fast bf16 passes,
# which makes sliced-vs-full einsums differ by ~1e-2 and breaks
# decode-vs-forward equivalence checks. Production TPU paths keep the fast
# default (bf16 inputs are the design point).
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture
def run_async():
    """Run a coroutine to completion on a fresh event loop."""

    def runner(coro):
        return asyncio.run(coro)

    return runner


# shared environment-capability skips (import from conftest, keep one copy)
import importlib.util  # noqa: E402

requires_websockets = pytest.mark.skipif(
    importlib.util.find_spec("websockets") is None,
    reason="needs the websockets client library",
)


# -- lock-order tier (docs/static-analysis.md) --------------------------------
# GOFR_LOCK_ORDER=1 (set by `make lock-order`) instruments every
# threading.Lock/RLock created during the session and fails the run on any
# lock-order cycle — Python-side deadlock detection complementing the
# C++-only `make native-tsan` tier. GOFR_LOCK_ORDER_EXPORT=<path> also
# dumps the observed acquisition graph as JSON for the static-vs-runtime
# cross-check (lockcheck.check_subgraph; `make lock-order` sets it).
@pytest.fixture(autouse=True, scope="session")
def _lock_order_tier():
    if os.environ.get("GOFR_LOCK_ORDER") != "1":
        yield
        return
    from gofr_tpu.analysis import lockorder

    mon = lockorder.install()
    try:
        yield
    finally:
        lockorder.uninstall()
        export = os.environ.get("GOFR_LOCK_ORDER_EXPORT")
        if export:
            import json as _json

            with open(export, "w", encoding="utf-8") as fp:
                _json.dump(mon.export_graph(), fp, indent=2)
    mon.check()  # raises LockOrderError on any cycle
