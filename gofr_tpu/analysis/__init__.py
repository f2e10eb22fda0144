"""gofrlint — framework-invariant static analysis for gofr-tpu.

The north-star serving numbers die by a thousand cuts: a stray
``time.sleep`` in handler dispatch, a host-device sync in the decode hot
loop, a ctypes binding that drifts from the ``extern "C"`` surface of the
native layer, or an unordered lock pair in the batching scheduler. The
C++ TUs already run under ASan/UBSan/TSan (``make native-asan`` /
``native-tsan``); this package is the equivalent enforcement tier for the
~170 Python files and the Python↔C boundary:

- :mod:`gofr_tpu.analysis.rules` — AST lints: no blocking calls in
  HTTP/gRPC dispatch or the engine decode loop, no host-device syncs in
  the serving hot path outside annotated sync points, registered and
  bounded-cardinality metrics, status-checked ctypes calls.
- :mod:`gofr_tpu.analysis.shardcheck` — the SPMD rule family:
  mesh/collective axis-name consistency (``mesh-axis-unknown``,
  ``collective-unmapped``), donated-buffer discipline
  (``use-after-donation``), and per-request recompile hazards in the
  decode hot path (``retrace-hazard``).
- :mod:`gofr_tpu.analysis.baseline_io` — ``--format json`` stable
  finding ids and the ratchet baseline (pre-existing findings don't
  block, new ones do; ``--update-baseline``).
- :mod:`gofr_tpu.analysis.ffi` — cross-checks every ``extern "C"``
  symbol in ``native/`` against the ctypes ``argtypes``/``restype``
  declarations (drift here is a memory-corruption bug ASan only catches
  at runtime).
- :mod:`gofr_tpu.analysis.lockcheck` — whole-program concurrency
  analysis over the threaded control plane: the static lock-acquisition
  graph with cycle detection (``lock-order-static``), blocking ops under
  a held lock (``hold-and-block``), and guarded-by inference for
  cross-thread attribute writes (``guarded-by``); exports the static
  graph (``--lock-graph``) that the runtime tier's observed graph is
  asserted a subgraph of.
- :mod:`gofr_tpu.analysis.leakcheck` — whole-program resource-lifecycle
  analysis: acquire/release pairing over a table of paired resources
  with cross-file factory resolution and ``# leakcheck:
  transfer(<recipient>)`` ownership annotations (``leak-unreleased``,
  ``leak-exception-path``), settlement-reachability of raise edges
  after a future/timeline registration (``settle-on-raise``), and
  retirement gates between blocking fetches and state commits
  (``retire-gate-missing``); exports the static resource table
  (``--leak-table``) the runtime reclaim tracer's observed pairs are
  asserted a subset of (``--check-leak-table``).
- :mod:`gofr_tpu.analysis.leaktrace` — the runtime reclaim tracer:
  instruments the allocator/scheduler/paged-slot/timeline lifecycles
  during the chaos tier, fails on anything left live after drain, and
  exports observed acquire/release pairs (``GOFR_LEAK_EXPORT``) for
  the static coverage cross-check.
- :mod:`gofr_tpu.analysis.deadlinecheck` — whole-program deadline-
  propagation and bounded-wait analysis over a call graph rooted at the
  request-serving entry points: a request-scoped deadline must bound
  every blocking call on its path (``deadline-dropped``), transport
  sites reachable from a serving entry must carry a finite bound
  (``unbounded-wire-call``), retry/requeue loops must be governed by a
  max-elapsed budget (``retry-unbudgeted``), waits on the cancel/drain
  surface must be stop-Event-gated or bounded (``cancel-unreachable``),
  and analyzer zone tables must not drift from the tree
  (``zone-drift``); exports the static boundary table
  (``--deadline-table``) the runtime tracer's observed crossings are
  asserted a subset of (``--check-deadline-table``).
- :mod:`gofr_tpu.analysis.deadlinetrace` — the runtime deadline tracer:
  instruments budget crossings (router→replica, engine admission,
  migrator fetch, LoRA acquire, SSE stream open) during the chaos tier,
  fails on a widened budget or an expired request crossing a new
  boundary, and exports observed sites for the static coverage
  cross-check.
- :mod:`gofr_tpu.analysis.kernelcheck` — device-contract analysis over
  the committed kernel contract table
  (:mod:`gofr_tpu.analysis.kernel_contracts`): host unpack sites must
  slice packed kernel outputs by the declared column order
  (``pack-layout-drift``), hot-zone dtype hygiene
  (``dtype-discipline``), every DecodeState construction site must
  agree with the declared carry spec (``carry-field-drift``),
  shard_map/PartitionSpec plumbing must match the wrapped function and
  its array ranks (``spec-rank-mismatch``), and every jitted kernel
  entry must carry a declared contract
  (``kernel-contract-coverage``); ``--kernel-table`` emits the table,
  ``--check-kernel-table`` verifies a runtime export against it.
- :mod:`gofr_tpu.analysis.kerneltrace` — the runtime twin:
  ``jax.eval_shape``\\ s every contract entry across the config matrix
  (base/LoRA x plain/ragged/spec over the page pools) with zero
  device execution, and a live-engine observer that records real
  dispatch signatures — both exports feed ``--check-kernel-table``.
- :mod:`gofr_tpu.analysis.sarif` — SARIF 2.1.0 output for the unified
  ``--all`` front door (``--format sarif``), for CI annotation.
- :mod:`gofr_tpu.analysis.audit` — the stale-suppression audit
  (``--check-suppressions``, folded into the ``--all`` pass): inline
  suppressions that match no raw finding fail CI instead of silently
  swallowing the next real one.
- :mod:`gofr_tpu.analysis.chaoscov` — chaos-coverage check
  (``--chaos-coverage``): every injection point registered in
  ``gofr_tpu/chaos/injector.py`` must be exercised by a ``make chaos``
  test file.
- :mod:`gofr_tpu.analysis.lockorder` — a runtime shim that records
  Python-side lock-acquisition ordering during the concurrency tests and
  fails on cycles (``make lock-order``), complementing the C++-only TSan
  tier; exports the observed graph for the static cross-check.

Run ``python -m gofr_tpu.analysis`` (or ``make lint``); it exits non-zero
on any unsuppressed finding. Suppress with
``# gofrlint: disable=<rule> -- <reason>`` — the reason is mandatory.
"""

from __future__ import annotations

from gofr_tpu.analysis.core import (
    Finding,
    SourceFile,
    parse_suppressions,
    run_rules,
)

__all__ = ["Finding", "SourceFile", "parse_suppressions", "run_rules"]
