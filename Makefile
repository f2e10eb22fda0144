# Pre-commit gate (round-1 post-mortem: HEAD shipped with a SyntaxError
# because nothing ran before the final commit). `make check` MUST pass
# before every commit.

PY ?= python
ASAN_RT := $(shell g++ -print-file-name=libasan.so 2>/dev/null)

.PHONY: check ci import-check lint lock-order test bench-smoke bench-check native native-asan chaos loadcheck

check: import-check lint test native-asan bench-smoke
	@echo "CHECK OK"

# pre-merge gate (docs/static-analysis.md): gofrlint + shardcheck over the
# tree, the analyzer's own fixture suites, the fixed-seed chaos tier
# (docs/robustness.md), then the full tier-1 pytest run. The fixture suites
# DO run again inside tier-1; the explicit first pass is a deliberate
# fail-fast — a broken analyzer surfaces in ~30 s, not after the ~15 min
# full suite.
ci: lint bench-check
	$(PY) -m gofr_tpu.analysis --chaos-coverage
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_analysis.py tests/test_shardcheck.py tests/test_lockcheck.py tests/test_leakcheck.py tests/test_deadlinecheck.py tests/test_deadlinetrace.py tests/test_kernelcheck.py tests/test_kerneltrace.py -q -m 'not slow' \
	  --deselect tests/test_lockcheck.py::test_runtime_graph_is_subgraph_of_static \
	  --deselect tests/test_leakcheck.py::test_runtime_pairs_covered_by_static_table \
	  --deselect tests/test_deadlinetrace.py::test_runtime_crossings_covered_by_static_table \
	  --deselect tests/test_deadlinetrace.py::test_lora_acquire_timeout_clamped_to_request_deadline \
	  --deselect tests/test_kerneltrace.py::test_observer_live_engine_matches_contract_table
	$(MAKE) chaos
	$(MAKE) loadcheck
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'
	@echo "CI OK"

# chaos tier (docs/robustness.md): the serving/engine suites under
# FIXED-SEED fault schedules at every registered injection point, asserting
# the request-lifecycle invariant — every submitted request reaches exactly
# one terminal state with its slot + KV pages reclaimed, and the engine
# thread exits cleanly — plus the engine-supervision invariant (an injected
# engine.step hang/crash or device.loss poisoning is detected by the
# watchdog, warm-restarted under budget, queued requests survive the
# restart, a budget-exhausted engine parks WEDGED instead of flapping;
# tests/test_supervisor.py), plus the pubsub delivery invariant (every
# published message handled-and-committed or dead-lettered with history;
# never lost, never looping) over the memory + kafka-wire drivers.
# Deterministic: a red run reproduces with the same seed every time (seeds
# live in tests/test_chaos.py::CHAOS_SEEDS,
# tests/test_supervisor.py::CHAOS_SEEDS,
# tests/test_pubsub_chaos.py::CHAOS_SEEDS,
# tests/test_router_chaos.py::CHAOS_SEEDS and
# tests/test_disagg.py::CHAOS_SEEDS), plus the router-plane replica
# tier (kill / wedge / heartbeat-partition over ≥2 in-process replicas,
# asserting exactly-one-terminal-state-on-exactly-one-replica) and the
# disaggregation plane (handoff-interrupted seeds: source death,
# destination death, kv.handoff transport faults; autoscaler scale-down
# drains under scale.decision faults), and the goodput-under-load tier
# (docs/robustness.md "Goodput under production load"): the full stack
# replays a seeded production trace while a wall-clock FaultSchedule
# fires a mid-run replica kill + tenant storm + heartbeat partition,
# asserting zero lost requests, exactly-one terminal per request, and
# interactive-class goodput strictly above batch inside the fault
# window (seeds in tests/test_loadlab.py::CHAOS_SEEDS), and the HA
# plane (docs/robustness.md "The HA plane"): router death mid-stream
# with a keyed Last-Event-ID re-attach on the survivor router
# (token-identical suffix), duplicate keyed submits across a two-router
# split brain (exactly one admission tier-wide), and stale-epoch
# fencing at the engine wire, under router.claim / stream.resume fault
# schedules (seeds in tests/test_ha.py::CHAOS_SEEDS).
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py tests/test_supervisor.py tests/test_pubsub_chaos.py tests/test_router_chaos.py tests/test_disagg.py tests/test_loadlab.py tests/test_reclaim.py tests/test_ha.py -q -m chaos

# goodput ratchet gate (docs/robustness.md, docs/performance.md#bench-ratchet):
# one deterministic chaos-under-load trace (seed 101) through the full
# stack via bench.py --loadlab, then the floor check — goodput under
# chaos (direction max) plus TTFT/e2e p99 ceilings must stay inside
# analysis/bench_floors.json.
loadcheck:
	JAX_PLATFORMS=cpu $(PY) bench.py --loadlab
	$(PY) bench.py --check

# gofrlint (docs/static-analysis.md): the unified front door — the
# framework-invariant AST lints, the shardcheck SPMD family, the
# lockcheck concurrency families, the leakcheck resource-lifecycle
# families, the extern-C vs ctypes FFI signature cross-check, AND the
# stale-suppression audit, in ONE shared SourceFile walk with one
# baseline load (`--format sarif` emits SARIF 2.1.0 for CI annotation).
# Exits non-zero on any unsuppressed finding — or when the unified pass
# blows its wall-clock budget: the lint gate is the pre-commit fast
# path, and an analyzer that quietly grows past $(LINT_BUDGET_S)s stops
# being one (a new whole-program family must pay for itself in the
# shared walk, not with a second tree scan).
LINT_BUDGET_S ?= 30
lint:
	@start=$$(date +%s); \
	$(PY) -m gofr_tpu.analysis --all || exit $$?; \
	end=$$(date +%s); elapsed=$$((end - start)); \
	if [ $$elapsed -gt $(LINT_BUDGET_S) ]; then \
	  echo "lint: unified pass took $${elapsed}s, over the $(LINT_BUDGET_S)s budget" >&2; \
	  exit 1; \
	fi; \
	echo "lint: unified pass in $${elapsed}s (budget $(LINT_BUDGET_S)s)"

# lock-order tier: run the concurrency tests with every Python lock
# instrumented; any cyclic acquisition order (potential deadlock) fails.
# The observed acquisition graph is exported for the static cross-check
# (docs/static-analysis.md "Static ↔ runtime cross-check"): every
# runtime edge must already be in `python -m gofr_tpu.analysis
# --lock-graph`'s static graph.
lock-order:
	GOFR_LOCK_ORDER=1 GOFR_LOCK_ORDER_EXPORT=$(CURDIR)/.gofr_lock_graph.json \
	JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_native_concurrency.py tests/test_engine_recovery.py -q -x
	$(PY) -m gofr_tpu.analysis --check-lock-graph $(CURDIR)/.gofr_lock_graph.json

import-check:
	$(PY) -c "import compileall,sys; sys.exit(0 if compileall.compile_dir('gofr_tpu', quiet=2) else 1)"
	$(PY) -c "import gofr_tpu; import __graft_entry__; print('import ok')"

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -x

bench-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py

# ratcheted perf gate (docs/performance.md#bench-ratchet): the records
# in BENCH_LOCAL.jsonl (a run-time file `bench.py --loadlab` appends to;
# none yet = zero records, warnings only) must stay inside the floors in
# analysis/bench_floors.json. Pure JSONL comparison — no jax import, no
# TPU; `bench.py --update-floors` ratchets the floors.
bench-check:
	$(PY) bench.py --check

native:
	$(MAKE) -C native

# sanitizer tier for the C++ layer (SURVEY §5.2, VERDICT r2 item 8): the
# same native tests run against ASan+UBSan builds of gofr_runtime.cc /
# pjrt_dl.cc / stub_plugin.cc. The loader rebuilds with the extra flags
# into distinct cache entries; libasan must be preloaded before python.
native-asan:
	GOFR_NATIVE_EXTRA_CXXFLAGS="-fsanitize=address,undefined -fno-sanitize-recover=all -g -O1" \
	GOFR_PJRT_INCLUDE_DIRS="$$($(PY) -c 'from gofr_tpu.native import pjrt_include_dirs; print(":".join(pjrt_include_dirs()))')" \
	LD_PRELOAD=$(ASAN_RT) \
	ASAN_OPTIONS="detect_leaks=0 suppressions=native/asan.supp" \
	UBSAN_OPTIONS="print_stacktrace=1" \
	JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_native_runtime.py tests/test_native_pjrt.py -q -x

# regenerate the committed descriptor sets for the built-in services
protos:
	cd gofr_tpu/grpcx/protos && \
	protoc -I. --descriptor_set_out=reflection.binpb reflection.proto && \
	protoc -I. --descriptor_set_out=health.binpb health.proto
	cd gofr_tpu/datasource/pubsub/protos && \
	protoc -I. --descriptor_set_out=pubsub_v1.binpb pubsub_v1.proto
	python -m gofr_tpu.grpcx.codegen gofr_tpu/distributed/coordination.proto \
	  -o gofr_tpu/distributed/

# thread-sanitizer tier (SURVEY §5.2, VERDICT r4 item 9): the allocator/
# scheduler concurrency stress AND the PJRT binding (pjrt_dl.cc +
# stub_plugin.cc, rebuilt with -fsanitize=thread through the loader's
# GOFR_NATIVE_EXTRA_CXXFLAGS hook) run against TSan builds — any data race
# in the C++ layer becomes a hard failure. GOFR_PJRT_INCLUDE_DIRS skips
# the tensorflow import (same reason as native-asan).
TSAN_RT := $(shell g++ -print-file-name=libtsan.so 2>/dev/null)

.PHONY: native-tsan
native-tsan:
	GOFR_NATIVE_EXTRA_CXXFLAGS="-fsanitize=thread -g -O1" \
	GOFR_PJRT_INCLUDE_DIRS="$$($(PY) -c 'from gofr_tpu.native import pjrt_include_dirs; print(":".join(pjrt_include_dirs()))')" \
	LD_PRELOAD=$(TSAN_RT) \
	TSAN_OPTIONS="halt_on_error=1 suppressions=native/tsan.supp" \
	JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_native_concurrency.py tests/test_native_runtime.py tests/test_native_pjrt.py -q -x
