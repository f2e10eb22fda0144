"""gofrlint (gofr_tpu/analysis/): rule fixtures, suppression mechanics,
the FFI cross-checker against mutated C signatures, and the lock-order
monitor. docs/static-analysis.md describes the tiers these enforce."""

from __future__ import annotations

import os
import shutil
import threading

import pytest

from gofr_tpu.analysis import lockorder
from gofr_tpu.analysis.core import run_rules
from gofr_tpu.analysis.ffi import check_ffi
from gofr_tpu.analysis.rules import default_rules

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_tree(tmp_path, files: dict[str, str]):
    """Materialize {relpath: source} under tmp_path and lint the top dir."""
    for rel, source in files.items():
        full = tmp_path / rel
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(source)
    top = tmp_path / sorted(files)[0].split("/")[0]
    return run_rules([str(top)], default_rules())


# ---------------------------------------------------------------- blocking
def test_blocking_call_positive(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/http/dispatch.py": (
            "import time\n\ndef handle():\n    time.sleep(1)\n"
        ),
    })
    assert [f.rule for f in findings] == ["blocking-call"]
    assert findings[0].line == 4


def test_blocking_call_clean_pass(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/http/dispatch.py": "def handle():\n    return 1\n",
    })
    assert findings == []


def test_blocking_call_suppression_honored(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/http/dispatch.py": (
            "import time\n\ndef handle():\n"
            "    time.sleep(1)  # gofrlint: disable=blocking-call -- test fixture\n"
        ),
    })
    assert findings == []


def test_standalone_suppression_covers_next_code_line(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/http/dispatch.py": (
            "import time\n\ndef handle():\n"
            "    # gofrlint: disable=blocking-call -- reason spanning the\n"
            "    # next comment line too\n"
            "    time.sleep(1)\n"
        ),
    })
    assert findings == []


def test_suppression_without_reason_is_a_finding(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/http/dispatch.py": (
            "import time\n\ndef handle():\n"
            "    time.sleep(1)  # gofrlint: disable=blocking-call\n"
        ),
    })
    rules = sorted(f.rule for f in findings)
    assert rules == ["bad-suppression", "blocking-call"]  # suppresses nothing


def test_closures_are_exempt(tmp_path):
    # deferred work (thread targets, run_in_executor payloads) is exactly
    # how blocking calls are SUPPOSED to leave the hot path
    findings = lint_tree(tmp_path, {
        "gofr_tpu/http/dispatch.py": (
            "import time\n\ndef handle():\n"
            "    def worker():\n        time.sleep(1)\n"
            "    return worker\n"
        ),
    })
    assert findings == []


def test_backoff_zone_flags_only_sleep(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/service/options.py": (
            "import time, urllib.request\n\ndef retry():\n"
            "    urllib.request.urlopen('http://x')\n    time.sleep(2)\n"
        ),
    })
    assert [f.rule for f in findings] == ["blocking-call"]
    assert "time.sleep" in findings[0].message


# ---------------------------------------------------------------- host-sync
def test_host_sync_positive_and_clean(tmp_path):
    findings = lint_tree(tmp_path / "hit", {
        "gofr_tpu/serving/batch.py": (
            "import numpy as np\n\ndef decode_step(x):\n"
            "    return np.asarray(x)\n"
        ),
    })
    assert [f.rule for f in findings] == ["host-sync"]
    findings = lint_tree(tmp_path / "clean", {
        "gofr_tpu/serving/batch2.py": (  # not a hot-zone file
            "import numpy as np\n\ndef decode_step(x):\n"
            "    return np.asarray(x)\n"
        ),
    })
    assert findings == []


def test_host_sync_block_until_ready_method(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "def decode_step(x):\n    return x.block_until_ready()\n"
        ),
    })
    assert [f.rule for f in findings] == ["host-sync"]


def test_host_sync_int_on_device_producer_result(tmp_path):
    """int() on a value produced by a jnp/batch_ops call is a hidden sync
    (jax __int__ blocks): flagged like an explicit np.asarray."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "import jax.numpy as jnp\n\n"
            "def _dispatch_decode(self):\n"
            "    toks = jnp.argmax(self.logits, axis=-1)\n"
            "    return int(toks[0])\n"
        ),
    })
    assert [f.rule for f in findings] == ["host-sync"]
    assert "hidden" in findings[0].message


def test_host_sync_float_on_device_suffix_attr(tmp_path):
    """Device-marker suffixes (_dev/_device) taint without an assignment
    in scope — the engine's persistent device attributes."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "def _consume_block(self):\n"
            "    return float(self._last_tok_dev[0])\n"
        ),
    })
    assert [f.rule for f in findings] == ["host-sync"]


def test_host_sync_int_propagates_through_unpack_and_copy(tmp_path):
    """Tuple-unpack from a batch_ops call taints every target, and a
    plain local copy carries the taint one hop."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving import batch as batch_ops\n\n"
            "def _dispatch_decode(self):\n"
            "    packed, k_pool, v_pool, state = batch_ops.decode_block_paged(self.p)\n"
            "    alias = packed\n"
            "    return bool(alias[0, 0])\n"
        ),
    })
    assert [f.rule for f in findings] == ["host-sync"]


def test_host_sync_int_on_materialized_numpy_is_clean(tmp_path):
    """np.asarray IS the sanctioned (suppressable) sync; int() on its
    result is a host read, not a second sync."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "import numpy as np\n\n"
            "def _consume_block(self, rec):\n"
            "    ids = np.asarray(rec.packed)"
            "  # gofrlint: disable=host-sync -- fixture sync point\n"
            "    return int(ids[0])\n"
        ),
    })
    assert findings == []


def test_host_sync_metadata_reads_are_clean(tmp_path):
    """.shape/.dtype inspection of a device value is static metadata —
    no sync, no finding; host-side bookkeeping ints stay clean too."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "import jax.numpy as jnp\n\n"
            "def _dispatch_decode(self):\n"
            "    toks = jnp.zeros(4, jnp.int32)\n"
            "    n = int(toks.shape[0])\n"
            "    return n + int(self.cache_len[0])\n"
        ),
    })
    assert findings == []


# ---------------------------------------------------------------- ctypes
def test_ctypes_unchecked_positive(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/native/binding.py": (
            "def close(lib, h):\n    lib.gofr_thing_destroy(h)\n"
        ),
    })
    assert [f.rule for f in findings] == ["ctypes-unchecked"]


def test_ctypes_checked_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/native/binding.py": (
            "def _check(c):\n    assert c >= 0\n\n"
            "def close(lib, h):\n    _check(lib.gofr_thing_destroy(h))\n"
        ),
    })
    assert findings == []


# ---------------------------------------------------------------- metrics
def test_metric_unregistered_cross_file(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/a.py": 'def reg(m):\n    m.new_counter("app_good", "d")\n',
        "gofr_tpu/b.py": (
            "def use(m):\n"
            '    m.increment_counter("app_good")\n'
            '    m.increment_counter("app_typoed")\n'
        ),
    })
    assert [f.rule for f in findings] == ["metric-unregistered"]
    assert "app_typoed" in findings[0].message


def test_metric_label_cardinality(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/a.py": (
            'def use(m, rid):\n'
            '    m.new_histogram("app_h", "d")\n'
            '    m.record_histogram("app_h", 1.0, request=f"id-{rid}")\n'
        ),
    })
    assert [f.rule for f in findings] == ["metric-label-cardinality"]


def test_metric_dynamic_name(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/a.py": (
            'def use(m, n):\n    m.increment_counter(f"app_{n}")\n'
        ),
    })
    assert [f.rule for f in findings] == ["metric-dynamic-name"]


def test_metric_unregistered_via_set_and_record(tmp_path):
    """Every facade verb is covered — set_gauge and record_histogram of a
    never-registered name are the PR 1 bug class, not just counters."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/a.py": (
            "def use(m):\n"
            '    m.set_gauge("app_never_gauge", 1.0)\n'
            '    m.record_histogram("app_never_hist", 0.5)\n'
        ),
    })
    assert [f.rule for f in findings] == [
        "metric-unregistered", "metric-unregistered",
    ]


def test_metric_register_site_enforced_with_container(tmp_path):
    """Full-tree runs (container/container.py present): a metric used in
    one subsystem but registered only in an UNRELATED module is flagged —
    a process that never imports the registering module silently loses
    the series."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/container/container.py": (
            "def reg(m):\n"
            '    m.new_gauge("app_info", "d")\n'
            '    m.set_gauge("app_info", 1)\n'
        ),
        "gofr_tpu/datasource/redis/client.py": (
            'def reg(m):\n    m.new_histogram("app_far_away", "d")\n'
        ),
        "gofr_tpu/serving/engine.py": (
            'def use(m):\n    m.record_histogram("app_far_away", 1.0)\n'
        ),
    })
    assert [f.rule for f in findings] == ["metric-register-site"]
    assert "app_far_away" in findings[0].message


def test_metric_register_site_clean_for_container_and_same_dir(tmp_path):
    """Negative: registration in container/container.py or in the using
    file's own directory (self-registering subsystems) is clean."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/container/container.py": (
            'def reg(m):\n    m.new_histogram("app_catalogued", "d")\n'
        ),
        "gofr_tpu/grpcx/server.py": (
            'def reg(m):\n    m.new_histogram("app_grpc_local", "d")\n'
        ),
        "gofr_tpu/grpcx/runtime.py": (
            "def use(m):\n"
            '    m.record_histogram("app_catalogued", 1.0)\n'
            '    m.record_histogram("app_grpc_local", 1.0)\n'
        ),
    })
    assert findings == []


def test_metric_never_emitted_flags_dead_catalog_series(tmp_path):
    """The inverse rule (full-tree runs only, mirrors
    metric-register-site): a name registered in container/container.py
    with zero .increment/.set/.record sites tree-wide — and no
    observe_with-wired callback gauge — is a dead series."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/container/container.py": (
            "def reg(m):\n"
            '    m.new_gauge("app_dead_series", "d")\n'
            '    m.new_gauge("app_live_series", "d")\n'
        ),
        "gofr_tpu/serving/engine.py": (
            'def use(m):\n    m.set_gauge("app_live_series", 1.0)\n'
        ),
    })
    assert [f.rule for f in findings] == ["metric-never-emitted"]
    assert "app_dead_series" in findings[0].message
    assert findings[0].path.endswith("container/container.py")
    assert findings[0].line == 2


def test_metric_never_emitted_observe_with_wiring_counts(tmp_path):
    """Negative: a callback gauge (`g = m.get(name)` +
    `g.observe_with(...)`, or the chained form) emits on every scrape —
    not a dead series. Names registered OUTSIDE the container catalog
    are out of the rule's scope either way."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/container/container.py": (
            "def reg(m):\n"
            '    m.new_gauge("app_threads", "d")\n'
            '    m.new_gauge("app_rss", "d")\n'
            '    g = m.get("app_threads")\n'
            "    g.observe_with(lambda: {})\n"
            '    m.get("app_rss").observe_with(lambda: {})\n'
        ),
        "gofr_tpu/grpcx/server.py": (
            'def reg(m):\n    m.new_histogram("app_subsystem_local", "d")\n'
        ),
    })
    assert findings == []


def test_metric_never_emitted_same_var_name_in_two_functions(tmp_path):
    """Negative: two callback gauges wired through the same idiomatic
    local name (`g`) in different functions must both count as emitted —
    the binding join is per enclosing function, not file-wide."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/container/container.py": (
            "def reg_a(m):\n"
            '    m.new_gauge("app_aaa", "d")\n'
            '    g = m.get("app_aaa")\n'
            "    g.observe_with(lambda: {})\n"
            "def reg_b(m):\n"
            '    m.new_gauge("app_bbb", "d")\n'
            '    g = m.get("app_bbb")\n'
            "    g.observe_with(lambda: {})\n"
        ),
    })
    assert findings == []


def test_metric_register_site_dormant_without_container(tmp_path):
    """Negative: on a tree without container/container.py (file subsets,
    fixtures) the site check stays dormant — registration anywhere
    suffices, as before."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/a.py": 'def reg(m):\n    m.new_counter("app_x", "d")\n',
        "gofr_tpu/b.py": 'def use(m):\n    m.increment_counter("app_x")\n',
    })
    assert findings == []


def test_metric_label_cardinality_format_call(tmp_path):
    """.format()-built label values are as unbounded as f-strings."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/a.py": (
            "def use(m, rid):\n"
            '    m.new_histogram("app_h", "d")\n'
            '    m.record_histogram("app_h", 1.0, "req",\n'
            '                       "id-{}".format(rid))\n'
        ),
    })
    assert [f.rule for f in findings] == ["metric-label-cardinality"]


def test_metric_label_cardinality_bounded_values_clean(tmp_path):
    """Negative: literal values and bare names (bounded enums) stay
    clean — only call-site string BUILDING is the cardinality smell."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/a.py": (
            "def use(m, status):\n"
            '    m.new_counter("app_c", "d")\n'
            '    m.increment_counter("app_c", "method", "GET")\n'
            '    m.increment_counter("app_c", status=status)\n'
        ),
    })
    assert findings == []


# ---------------------------------------------------------------- FFI
def _copy_ffi_fixture(tmp_path) -> str:
    root = tmp_path / "repo"
    for rel in ("native/runtime", "native/pjrt", "gofr_tpu/native"):
        (root / rel).mkdir(parents=True)
    for rel in (
        "native/runtime/gofr_runtime.cc",
        "native/pjrt/pjrt_dl.cc",
        "native/pjrt/stub_plugin.cc",
        "gofr_tpu/native/__init__.py",
    ):
        shutil.copy(os.path.join(REPO_ROOT, rel), root / rel)
    return str(root)


def test_ffi_clean_on_pristine_copy(tmp_path):
    assert check_ffi(_copy_ffi_fixture(tmp_path)) == []


def test_ffi_detects_mutated_c_signature(tmp_path):
    root = _copy_ffi_fixture(tmp_path)
    cc = os.path.join(root, "native/runtime/gofr_runtime.cc")
    with open(cc) as f:
        src = f.read()
    mutated = src.replace(
        "int32_t gofr_ba_alloc(int64_t h, int64_t seq_id, int64_t tokens)",
        "int32_t gofr_ba_alloc(int64_t h, int32_t seq_id, int64_t tokens)",
    )
    assert mutated != src, "fixture drifted: gofr_ba_alloc signature not found"
    with open(cc, "w") as f:
        f.write(mutated)
    findings = check_ffi(root)
    assert [f.rule for f in findings] == ["ffi-mismatch"]
    assert "gofr_ba_alloc" in findings[0].message


def test_ffi_detects_unbound_export(tmp_path):
    root = _copy_ffi_fixture(tmp_path)
    cc = os.path.join(root, "native/runtime/gofr_runtime.cc")
    with open(cc, "a") as f:
        f.write("\nGOFR_API int32_t gofr_ba_new_export(int64_t h) { return 0; }\n")
    findings = check_ffi(root)
    assert [f.rule for f in findings] == ["ffi-unbound"]
    assert "gofr_ba_new_export" in findings[0].message


def test_ffi_detects_stale_binding(tmp_path):
    root = _copy_ffi_fixture(tmp_path)
    cc = os.path.join(root, "native/runtime/gofr_runtime.cc")
    with open(cc) as f:
        src = f.read()
    # comment out one export: the Python declaration goes stale
    mutated = src.replace(
        "GOFR_API const char* gofr_runtime_version()",
        "static const char* gofr_runtime_version_hidden()",
    )
    assert mutated != src
    with open(cc, "w") as f:
        f.write(mutated)
    findings = check_ffi(root)
    assert [f.rule for f in findings] == ["ffi-stale"]


# ------------------------------------------------------ pubsub manual settle
def test_manual_settle_in_registered_handler_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/worker.py": (
            "def on_job(ctx):\n"
            "    ctx.request.commit()\n",
        )[0],
        "gofr_tpu/wiring.py": (
            "def wire(app):\n"
            "    app.subscribe('jobs', on_job)\n"
        ),
    })
    assert [f.rule for f in findings] == ["pubsub-manual-settle"]
    assert findings[0].path.endswith("worker.py") and findings[0].line == 2


def test_manual_nack_flagged_on_any_receiver(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/worker.py": (
            "def on_job(ctx):\n"
            "    thing = ctx.request\n"
            "    thing.nack(True)\n"
            "def wire(mgr):\n"
            "    mgr.register('jobs', on_job)\n"
        ),
    })
    assert [f.rule for f in findings] == ["pubsub-manual-settle"]


def test_settle_outside_registered_handler_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/worker.py": (
            "def framework_loop(msg):\n"
            "    msg.commit()\n"  # the loop itself settles — not a handler
        ),
    })
    assert findings == []


def test_sql_commit_in_handler_is_not_a_settle(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/worker.py": (
            "def on_job(ctx):\n"
            "    ctx.sql.commit()\n"  # transaction commit, not message settle
            "def wire(app):\n"
            "    app.subscribe('jobs', on_job)\n"
        ),
    })
    assert findings == []


def test_manual_settle_suppressible_with_reason(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/worker.py": (
            "def on_job(ctx):\n"
            "    ctx.request.commit()  # gofrlint: disable=pubsub-manual-settle"
            " -- commit-before-side-effect wanted here\n"
            "def wire(app):\n"
            "    app.subscribe('jobs', on_job)\n"
        ),
    })
    assert findings == []


def test_method_reference_handler_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/worker.py": (
            "class Worker:\n"
            "    def handle(self, ctx):\n"
            "        ctx.request.nack(False)\n"
            "def wire(app, w):\n"
            "    app.subscribe('jobs', w.handle)\n"
        ),
    })
    assert [f.rule for f in findings] == ["pubsub-manual-settle"]


# -------------------------------------------------- router retry typing
def test_router_retry_broad_except_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/router.py": (
            "def _failover(self, req):\n"
            "    try:\n"
            "        self._submit_attempt(req, 'r2')\n"
            "    except Exception:\n"
            "        pass\n"
        ),
    })
    assert [f.rule for f in findings] == ["router-retry-untyped"]
    assert "Exception" in findings[0].message


def test_router_retry_bare_except_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/router.py": (
            "def submit(self, prompt):\n"
            "    for rid in ('a', 'b'):\n"
            "        try:\n"
            "            return self._submit_attempt(prompt, rid)\n"
            "        except:\n"
            "            continue\n"
        ),
    })
    assert [f.rule for f in findings] == ["router-retry-untyped"]


def test_router_retry_unlisted_type_in_tuple_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/router.py": (
            "def _hedge(self, req):\n"
            "    try:\n"
            "        self._submit_attempt(req, 'r2')\n"
            "    except (ErrorServiceUnavailable, ValueError):\n"
            "        pass\n"
        ),
    })
    assert [f.rule for f in findings] == ["router-retry-untyped"]
    assert "ValueError" in findings[0].message


def test_router_retry_typed_set_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/router.py": (
            "def submit(self, prompt):\n"
            "    try:\n"
            "        return self._submit_attempt(prompt, 'a')\n"
            "    except RETRIABLE_ERRORS as exc:\n"
            "        raise exc\n"
            "def _failover(self, req):\n"
            "    try:\n"
            "        self._submit_attempt(req, 'b')\n"
            "    except (ErrorServiceUnavailable, ChaosFault):\n"
            "        pass\n"
            "    except ErrorDeadlineExceeded:\n"
            "        pass\n"
        ),
    })
    assert findings == []


def test_router_retry_rule_scopes_to_zone_functions(tmp_path):
    """A broad catch OUTSIDE the retry-zone functions (settlement,
    membership loops) is legitimate defensive code — not flagged."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/router.py": (
            "def _settle(self, req):\n"
            "    try:\n"
            "        req.future.set_result(1)\n"
            "    except Exception:\n"
            "        pass\n"
        ),
        "gofr_tpu/serving/other.py": (
            "def submit(self, prompt):\n"
            "    try:\n"
            "        return 1\n"
            "    except Exception:\n"
            "        pass\n"
        ),
    })
    assert findings == []


# ------------------------------------------------- daemon loop heartbeat
def test_daemon_while_true_without_check_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/poller.py": (
            "import threading\n"
            "def worker():\n"
            "    while True:\n"
            "        poll()\n"
            "def start():\n"
            "    threading.Thread(target=worker, daemon=True).start()\n"
        ),
    })
    assert [f.rule for f in findings] == ["daemon-loop-no-heartbeat"]
    assert findings[0].line == 3


def test_daemon_method_target_while_true_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/poller.py": (
            "import threading\n"
            "class P:\n"
            "    def _loop(self):\n"
            "        while True:\n"
            "            self.step()\n"
            "    def start(self):\n"
            "        self._t = threading.Thread(target=self._loop, daemon=True)\n"
            "        self._t.start()\n"
        ),
    })
    assert [f.rule for f in findings] == ["daemon-loop-no-heartbeat"]


def test_daemon_loop_with_stop_event_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/poller.py": (
            "import threading\n"
            "class P:\n"
            "    def _loop(self):\n"
            "        while True:\n"
            "            if self._stop.is_set():\n"
            "                return\n"
            "            self.step()\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._loop, daemon=True).start()\n"
        ),
    })
    assert findings == []


def test_daemon_loop_with_wake_throttle_wait_still_flagged(tmp_path):
    """A throttling wait on a non-lifecycle event (`self._wake.wait(0.05)`)
    must NOT count as supervision: the loop is still unstoppable and
    unwatchable — the rule's primary target pattern."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/poller.py": (
            "import threading\n"
            "class P:\n"
            "    def _loop(self):\n"
            "        while True:\n"
            "            self.step()\n"
            "            self._wake.wait(0.05)\n"
            "            self._wake.clear()\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._loop, daemon=True).start()\n"
        ),
    })
    assert [f.rule for f in findings] == ["daemon-loop-no-heartbeat"]


def test_daemon_loop_sibling_class_same_name_not_flagged(tmp_path):
    """A `self.<m>` registration scopes to its class: an unrelated
    same-named method of a sibling class (never run on a daemon thread)
    must not be cross-flagged."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/poller.py": (
            "import threading\n"
            "class A:\n"
            "    def _loop(self):\n"
            "        while True:\n"
            "            if self._stop.is_set():\n"
            "                return\n"
            "            self.step()\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._loop, daemon=True).start()\n"
            "class B:\n"
            "    def _loop(self):  # plain iterator helper, never a thread\n"
            "        while True:\n"
            "            if self.advance():\n"
            "                break\n"
        ),
    })
    assert findings == []


def test_daemon_loop_with_heartbeat_stamp_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/poller.py": (
            "import threading, time\n"
            "class P:\n"
            "    def _loop(self):\n"
            "        while True:\n"
            "            self.heartbeat = time.monotonic()\n"
            "            self.step()\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._loop, daemon=True).start()\n"
        ),
    })
    assert findings == []


def test_non_daemon_while_true_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/poller.py": (
            "import threading\n"
            "def worker():\n"
            "    while True:\n"
            "        poll()\n"
            "def start():\n"
            "    threading.Thread(target=worker).start()\n"  # not daemon
        ),
    })
    assert findings == []


def test_daemon_loop_testutil_exempt(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/testutil/fake_server.py": (
            "import threading\n"
            "def _accept_loop():\n"
            "    while True:\n"
            "        accept()\n"
            "def start():\n"
            "    threading.Thread(target=_accept_loop, daemon=True).start()\n"
        ),
    })
    assert findings == []


# ---------------------------------------------------------------- real tree
def test_real_tree_is_clean():
    """The acceptance bar: gofrlint exits 0 on the repo itself."""
    findings = run_rules([os.path.join(REPO_ROOT, "gofr_tpu")], default_rules())
    findings += check_ffi(REPO_ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_exit_codes(tmp_path):
    from gofr_tpu.analysis.__main__ import main

    bad = tmp_path / "gofr_tpu" / "http"
    bad.mkdir(parents=True)
    (bad / "dispatch.py").write_text(
        "import time\n\ndef handle():\n    time.sleep(1)\n"
    )
    assert main([str(tmp_path / "gofr_tpu"), "--no-ffi"]) == 1
    (bad / "dispatch.py").write_text("def handle():\n    return 1\n")
    assert main([str(tmp_path / "gofr_tpu"), "--no-ffi"]) == 0
    assert main(["--ffi-only", "--repo-root", REPO_ROOT]) == 0


# ---------------------------------------------------------------- lock order
@pytest.mark.lockorder
def test_lock_order_cycle_detected():
    # private monitor: synthetic cycles must not touch the global
    # factories (a session-tier monitor would record them as real)
    mon = lockorder.LockOrderMonitor()
    a, b = mon.make_lock(), mon.make_lock()
    with a:
        with b:
            pass
    with b:
        with a:  # AB/BA inversion
            pass
    assert mon.cycles()
    with pytest.raises(lockorder.LockOrderError):
        mon.check()


@pytest.mark.lockorder
def test_lock_order_consistent_is_clean():
    mon = lockorder.LockOrderMonitor()
    a, b, c = mon.make_lock(), mon.make_lock(), mon.make_lock()
    for _ in range(3):
        with a:
            with b:
                with c:
                    pass
    assert mon.cycles() == []
    mon.check()


@pytest.mark.lockorder
def test_lock_order_cross_thread_edges():
    """The monitor aggregates edges across threads — that is the point:
    thread 1 taking A->B while thread 2 takes B->A is the deadlock."""
    mon = lockorder.LockOrderMonitor()
    a, b = mon.make_lock(), mon.make_lock()

    def t1():
        with a:
            with b:
                pass

    def t2():
        with b:
            with a:
                pass

    th1 = threading.Thread(target=t1)
    th1.start()
    th1.join()
    th2 = threading.Thread(target=t2)
    th2.start()
    th2.join()
    assert mon.cycles()


@pytest.mark.lockorder
def test_rlock_reentrancy_no_self_cycle():
    mon = lockorder.LockOrderMonitor()
    r = mon.make_rlock()
    with r:
        with r:  # reentrant acquire must not record a self-edge
            pass
    assert mon.cycles() == []


@pytest.mark.lockorder
@pytest.mark.skipif(os.environ.get("GOFR_LOCK_ORDER") == "1",
                    reason="session lock-order tier already installed")
def test_stdlib_primitives_survive_instrumentation():
    """Event/Condition are built on Lock/RLock; the wrappers must keep
    their protocols (incl. _release_save/_acquire_restore) intact."""
    mon = lockorder.install()
    try:
        ev = threading.Event()
        results = []

        def setter():
            ev.set()

        t = threading.Thread(target=setter)
        t.start()
        assert ev.wait(timeout=5)
        t.join()

        cond = threading.Condition()

        def producer():
            with cond:
                results.append(1)
                cond.notify()

        t2 = threading.Thread(target=producer)
        with cond:
            t2.start()
            assert cond.wait_for(lambda: results, timeout=5)
        t2.join()
    finally:
        lockorder.uninstall()
    assert mon.locks_created >= 2
    mon.check()


@pytest.mark.lockorder
@pytest.mark.skipif(os.environ.get("GOFR_LOCK_ORDER") == "1",
                    reason="session lock-order tier already installed")
def test_engine_locks_under_monitor():
    """A slice of the real target: allocator + scheduler wrappers used
    concurrently under instrumentation record a clean (acyclic) order."""
    mon = lockorder.install()
    try:
        from gofr_tpu.native.runtime import BlockAllocator, Scheduler

        ba = BlockAllocator(32, 4, force_python=True)
        sched = Scheduler(4, 16, 1024, force_python=True)

        def worker(wid: int) -> None:
            for i in range(20):
                sid = wid * 100 + i
                ba.alloc(sid, 3)
                ba.stats()
                ba.free(sid)
                sched.submit(sid, 8, 4)
                sched.stats()
                sched.cancel(sid)
                sched.admit(4)

        threads = [threading.Thread(target=worker, args=(w,)) for w in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        ba.close()
        sched.close()
    finally:
        lockorder.uninstall()
    mon.check()
