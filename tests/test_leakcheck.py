"""leakcheck (gofr_tpu/analysis/leakcheck.py): the whole-program
resource-lifecycle analyzer — acquire/release pairing (incl. cross-file
factory-return resolution and ownership-transfer annotations),
exception-path escapes, settlement-reachability, retirement gates — plus
the runtime reclaim tracer (gofr_tpu/analysis/leaktrace.py), the
static↔runtime coverage cross-check on a REAL engine workload, the
unified ``--all`` front door, and SARIF output.
docs/static-analysis.md#leakcheck documents the catalog these pin down.
"""

from __future__ import annotations

import json
import os
import threading

import pytest

from gofr_tpu.analysis import baseline_io
from gofr_tpu.analysis.core import run_rules, run_unified
from gofr_tpu.analysis.leakcheck import (
    build_resource_table,
    check_coverage,
    leakcheck_rules,
    parse_transfer_annotations,
)
from gofr_tpu.analysis.rules import default_rules

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_tree(tmp_path, files: dict[str, str]):
    """Materialize {relpath: source} under tmp_path and lint the top dir
    with the leakcheck families only (fixture isolation from the other
    rule sets)."""
    for rel, source in files.items():
        full = tmp_path / rel
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(source)
    top = tmp_path / sorted(files)[0].split("/")[0]
    return run_rules([str(top)], leakcheck_rules())


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ------------------------------------------------------- leak-unreleased
def test_executor_never_shutdown(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "import concurrent.futures\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self._pool = concurrent.futures.ThreadPoolExecutor(\n"
            "            max_workers=1)\n"
            "    def go(self):\n"
            "        self._pool.submit(print)\n"
        ),
    })
    assert rules_of(findings) == ["leak-unreleased"]
    assert "executor" in findings[0].message


def test_executor_shutdown_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "import concurrent.futures\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self._pool = concurrent.futures.ThreadPoolExecutor(\n"
            "            max_workers=1)\n"
            "    def close(self):\n"
            "        self._pool.shutdown(wait=False)\n"
        ),
    })
    assert findings == []


def test_discarded_span_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class H:\n"
            "    def __init__(self, tracer):\n"
            "        self._tracer = tracer\n"
            "    def handle(self):\n"
            "        self._tracer.start_span('x')\n"
        ),
    })
    assert rules_of(findings) == ["leak-unreleased"]
    assert "discarded" in findings[0].message


def test_local_span_leaked_vs_released(tmp_path):
    """A bound span with no disposition is flagged; `with`, `.end()`,
    return, and the open_span ownership sink are all clean."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class H:\n"
            "    def __init__(self, tracer):\n"
            "        self._tracer = tracer\n"
            "    def bad(self):\n"
            "        span = self._tracer.start_span('x')\n"
            "        do_work()\n"
            "    def good_with(self):\n"
            "        span = self._tracer.start_span('x')\n"
            "        with span:\n"
            "            do_work()\n"
            "    def good_end(self):\n"
            "        span = self._tracer.start_span('x')\n"
            "        try:\n"
            "            do_work()\n"
            "        finally:\n"
            "            span.end()\n"
            "    def good_factory(self):\n"
            "        return self._tracer.start_span('x')\n"
            "    def good_sink(self, tl):\n"
            "        span = self._tracer.start_span('x')\n"
            "        tl.open_span('phase', span)\n"
        ),
    })
    assert rules_of(findings) == ["leak-unreleased"]
    assert "'span'" in findings[0].message and findings[0].line == 5


def test_cross_file_factory_return_resolution(tmp_path):
    """A function whose return value is an acquisition makes its CALL
    SITES the acquisitions: the factory itself is clean (ownership
    transferred to the caller), the leaking caller is flagged, and a
    caller that releases is clean."""
    files = {
        "gofr_tpu/svc/factory.py": (
            "from gofr_tpu.native.runtime import Scheduler\n"
            "def make_sched():\n"
            "    return Scheduler(1, 1, 1)\n"
        ),
        "gofr_tpu/svc/leaker.py": (
            "from gofr_tpu.svc.factory import make_sched\n"
            "class Owner:\n"
            "    def __init__(self):\n"
            "        self._s = make_sched()\n"
        ),
        "gofr_tpu/svc/clean.py": (
            "from gofr_tpu.svc.factory import make_sched\n"
            "class CleanOwner:\n"
            "    def __init__(self):\n"
            "        self._s = make_sched()\n"
            "    def stop(self):\n"
            "        self._s.close()\n"
        ),
    }
    findings = lint_tree(tmp_path, files)
    assert rules_of(findings) == ["leak-unreleased"]
    assert findings[0].path.endswith("leaker.py")
    assert "native-wrapper" in findings[0].message


def test_nondaemon_thread_requires_join_daemon_exempt(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "import threading\n"
            "class T:\n"
            "    def start(self):\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "        self._t.start()\n"
            "class D:\n"
            "    def start(self):\n"
            "        self._t = threading.Thread(target=self._run,\n"
            "                                   daemon=True)\n"
            "        self._t.start()\n"
        ),
    })
    assert rules_of(findings) == ["leak-unreleased"]
    assert findings[0].line == 4  # the non-daemon one


def test_receiver_state_acquire_pairing(tmp_path):
    """alloc_slot without a free_slot anywhere in the class is a leak;
    with one it is clean (the whole-class pairing, not per-function)."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class BadEngine:\n"
            "    def admit(self, slot, rid, n):\n"
            "        self.pc.alloc_slot(slot, seq_id=rid, prompt_len=n)\n"
            "class GoodEngine:\n"
            "    def admit(self, slot, rid, n):\n"
            "        self.pc.alloc_slot(slot, seq_id=rid, prompt_len=n)\n"
            "    def retire(self, slot):\n"
            "        self.pc.free_slot(slot)\n"
        ),
    })
    assert rules_of(findings) == ["leak-unreleased"]
    assert "BadEngine" in findings[0].message


# -------------------------------------------------- transfer annotations
def test_transfer_annotation_declares_deliberate_leak(tmp_path):
    """The quarantine-leak shape: a `leak()` method annotated
    `transfer(quarantine)` counts as the release for its class's kinds —
    without the annotation the same code is flagged."""
    annotated = (
        "import concurrent.futures\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._pool = concurrent.futures.ThreadPoolExecutor(\n"
        "            max_workers=1)\n"
        "    def leak_pool(self):  # leakcheck: transfer(quarantine)\n"
        "        self._pool = None\n"
        "class User:\n"
        "    def __init__(self):\n"
        "        self._q = Q()\n"
    )
    findings = lint_tree(tmp_path, {"gofr_tpu/svc/a.py": annotated})
    assert findings == []
    bare = annotated.replace("  # leakcheck: transfer(quarantine)", "")
    findings = lint_tree(tmp_path / "x", {"gofr_tpu/svc/a.py": bare})
    assert rules_of(findings) == ["leak-unreleased"]


def test_bad_transfer_annotation_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "def f():\n"
            "    pass  # leakcheck: transfer()\n"
        ),
    })
    assert rules_of(findings) == ["bad-transfer-annotation"]


def test_transfer_annotation_parser():
    ann, bad = parse_transfer_annotations(
        "# leakcheck: transfer(quarantine)\n"
        "x = acquire()\n"
        "y = acquire()  # leakcheck: transfer(caller)\n"
        "z = 1  # leakcheck: nonsense\n",
        "f.py",
    )
    assert ann[2] == "quarantine"  # standalone covers the next code line
    assert ann[3] == "caller"
    assert len(bad) == 1 and bad[0].rule == "bad-transfer-annotation"


def test_real_tree_leak_annotations_present():
    """The three quarantine-leak methods carry transfer(quarantine) —
    lint-clean by declaration, not by suppression sprawl."""
    table = build_resource_table([os.path.join(REPO_ROOT, "gofr_tpu")])
    assert table["transfer_methods"] == {"leak": "quarantine"}


# ---------------------------------------------------- leak-exception-path
def test_raise_between_acquire_and_release(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class P:\n"
            "    def step(self, slot, n):\n"
            "        self.pc.alloc_slot(slot, seq_id=1, prompt_len=n)\n"
            "        if n > 100:\n"
            "            raise ValueError('too big')\n"
            "        self.pc.free_slot(slot)\n"
        ),
    })
    assert "leak-exception-path" in rules_of(findings)
    assert any(f.line == 5 for f in findings)


def test_finally_release_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class P:\n"
            "    def step(self, slot, n):\n"
            "        self.pc.alloc_slot(slot, seq_id=1, prompt_len=n)\n"
            "        try:\n"
            "            if n > 100:\n"
            "                raise ValueError('too big')\n"
            "        finally:\n"
            "            self.pc.free_slot(slot)\n"
        ),
    })
    assert "leak-exception-path" not in rules_of(findings)


def test_release_on_error_path_before_raise_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class P:\n"
            "    def step(self, slot, n):\n"
            "        self.pc.alloc_slot(slot, seq_id=1, prompt_len=n)\n"
            "        if n > 100:\n"
            "            self.pc.free_slot(slot)\n"
            "            raise ValueError('too big')\n"
            "        self.pc.free_slot(slot)\n"
        ),
    })
    assert "leak-exception-path" not in rules_of(findings)


def test_return_between_acquire_and_release_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class P:\n"
            "    def step(self, slot, n):\n"
            "        self.pc.alloc_slot(slot, seq_id=1, prompt_len=n)\n"
            "        if n == 0:\n"
            "            return None\n"
            "        self.pc.free_slot(slot)\n"
        ),
    })
    assert "leak-exception-path" in rules_of(findings)


def test_sibling_release_does_not_mask_exception_edge(tmp_path):
    """Two resources of one kind in one function: releasing the FIRST
    must not shrink the second's checked window (the review repro — the
    raise strands span b even though a.end() ran)."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class H:\n"
            "    def two(self, tracer, cond):\n"
            "        a = tracer.start_span('a')\n"
            "        b = tracer.start_span('b')\n"
            "        a.end()\n"
            "        if cond:\n"
            "            raise ValueError('strands b')\n"
            "        b.end()\n"
        ),
    })
    hits = [f for f in findings if f.rule == "leak-exception-path"]
    assert len(hits) == 1 and hits[0].line == 7


# ------------------------------------------------------- settle-on-raise
def test_raise_after_registration_unsettled(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class Eng:\n"
            "    def submit(self, rid, req):\n"
            "        self._by_id[rid] = req\n"
            "        if req.bad:\n"
            "            raise ValueError('nope')\n"
        ),
    })
    assert rules_of(findings) == ["settle-on-raise"]
    assert findings[0].line == 5


def test_raise_inside_settling_try_clean(tmp_path):
    """The canonical engine.submit shape: registration + raises inside a
    try whose broad except settles (then re-raises)."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class Eng:\n"
            "    def submit(self, rid, req):\n"
            "        try:\n"
            "            self._by_id[rid] = req\n"
            "            if req.bad:\n"
            "                raise ValueError('nope')\n"
            "        except Exception as exc:\n"
            "            self._try_resolve(req, exc=exc)\n"
            "            raise\n"
        ),
    })
    assert findings == []


def test_settle_before_raise_on_same_path_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class Eng:\n"
            "    def submit(self, rid, req):\n"
            "        self._by_id[rid] = req\n"
            "        if req.bad:\n"
            "            self._settle_future(req, ValueError('nope'))\n"
            "            raise ValueError('nope')\n"
        ),
    })
    assert findings == []


def test_timeline_begin_registers_but_sql_begin_does_not(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class Eng:\n"
            "    def submit(self, rid, req, n):\n"
            "        tl = self.timeline.begin(rid, prompt_tokens=n)\n"
            "        req.timeline = tl\n"
            "        if n > 100:\n"
            "            raise ValueError('nope')\n"
            "class Tx:\n"
            "    def run(self, n):\n"
            "        tx = self.sql.begin()\n"
            "        tx.commit()\n"
            "        if n > 100:\n"
            "            raise ValueError('nope')\n"
        ),
    })
    assert rules_of(findings) == ["settle-on-raise"]
    assert findings[0].line == 6  # the timeline one, never the sql tx


def test_settle_in_sibling_handler_does_not_mask(tmp_path):
    """A settle in ONE except handler must not protect an unsettled
    raise in a SIBLING handler — they are distinct paths (the review
    repro: the KeyError re-raise strands the registered future exactly
    like the PR 7 bug class)."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class Eng:\n"
            "    def submit(self, rid, req):\n"
            "        self._by_id[rid] = req\n"
            "        try:\n"
            "            self.admit(req)\n"
            "        except ValueError:\n"
            "            self._settle_future(req, None)\n"
            "        except KeyError:\n"
            "            raise\n"
        ),
    })
    assert rules_of(findings) == ["settle-on-raise"]
    assert findings[0].line == 9


def test_raise_in_orelse_not_protected_by_handler_settle(tmp_path):
    """Python never routes an else-block raise through the try's
    handlers: a settling except must not protect it (a settling
    finally still does)."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class Eng:\n"
            "    def bad(self, rid, req):\n"
            "        self._by_id[rid] = req\n"
            "        try:\n"
            "            self.probe(req)\n"
            "        except ValueError:\n"
            "            self._try_resolve(req)\n"
            "        else:\n"
            "            raise RuntimeError('strands')\n"
            "    def good(self, rid, req):\n"
            "        self._by_id[rid] = req\n"
            "        try:\n"
            "            self.probe(req)\n"
            "        except ValueError:\n"
            "            pass\n"
            "        else:\n"
            "            raise RuntimeError('covered')\n"
            "        finally:\n"
            "            self._try_resolve(req)\n"
        ),
    })
    assert rules_of(findings) == ["settle-on-raise"]
    assert findings[0].line == 9


def test_settle_earlier_in_same_handler_still_protects(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class Eng:\n"
            "    def submit(self, rid, req):\n"
            "        self._by_id[rid] = req\n"
            "        try:\n"
            "            self.admit(req)\n"
            "        except KeyError:\n"
            "            self._settle_future(req, None)\n"
            "            raise\n"
        ),
    })
    assert findings == []


def test_exception_path_unrelated_handler_raise_not_exempt(tmp_path):
    """A re-raise from a handler of a try that does NOT contain the
    acquire is a real escape edge (the review repro): only the handler
    of the try whose body holds the acquire is the acquisition's own
    failure path."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class P:\n"
            "    def step(self, slot, n):\n"
            "        self.pc.alloc_slot(slot, seq_id=1, prompt_len=n)\n"
            "        try:\n"
            "            self.risky(n)\n"
            "        except Exception:\n"
            "            self.log(n)\n"
            "            raise\n"
            "        self.pc.free_slot(slot)\n"
            "    def own_failure_edge(self, slot, n):\n"
            "        try:\n"
            "            self.pc.alloc_slot(slot, seq_id=1, prompt_len=n)\n"
            "        except KeyError:\n"
            "            raise ValueError('busy')\n"
            "        self.pc.free_slot(slot)\n"
        ),
    })
    hits = [f for f in findings if f.rule == "leak-exception-path"]
    assert len(hits) == 1 and hits[0].line == 8


# --------------------------------------------------- retire-gate-missing
def test_commit_after_fetch_without_gate(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "class E:\n"
            "    def admit(self, key):\n"
            "        fetched = self._kv_migrator.fetch_one(key)\n"
            "        if fetched is not None:\n"
            "            self._prefix_cache.put(key, fetched)\n"
        ),
    })
    assert "retire-gate-missing" in rules_of(findings)


def test_gate_between_fetch_and_commit_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "class E:\n"
            "    def admit(self, key):\n"
            "        fetched = self._kv_migrator.fetch_one(key)\n"
            "        self._check_retired()\n"
            "        if fetched is not None:\n"
            "            self._prefix_cache.put(key, fetched)\n"
        ),
    })
    assert "retire-gate-missing" not in rules_of(findings)


def test_second_unguarded_fetch_flagged(tmp_path):
    """A gate covers only the fetch before it: a LATER blocking call
    needs its own re-check before the next commit."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "class E:\n"
            "    def admit(self, key):\n"
            "        a = self._kv_migrator.fetch_one(key)\n"
            "        self._check_retired()\n"
            "        self._prefix_cache.put(key, a)\n"
            "        b = self._kv_migrator.fetch_chain([key])\n"
            "        self._prefix_cache.put(key, b)\n"
        ),
    })
    hits = [f for f in findings if f.rule == "retire-gate-missing"]
    assert len(hits) == 1 and hits[0].line == 7


def test_fetch_outside_engine_zone_not_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/other.py": (
            "class E:\n"
            "    def admit(self, key):\n"
            "        fetched = self._kv_migrator.fetch_one(key)\n"
            "        self._prefix_cache.put(key, fetched)\n"
        ),
    })
    assert "retire-gate-missing" not in rules_of(findings)


# ------------------------------------------ ids / baseline / round trips
def test_json_and_stable_ids_round_trip(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class H:\n"
            "    def __init__(self, tracer):\n"
            "        self._tracer = tracer\n"
            "    def handle(self):\n"
            "        self._tracer.start_span('x')\n"
        ),
    })
    blob = json.loads(baseline_io.render_json(findings))
    assert blob["findings"][0]["rule"] == "leak-unreleased"
    again = lint_tree(tmp_path / "again", {
        "gofr_tpu/svc/a.py": (
            "class H:\n"
            "    def __init__(self, tracer):\n"
            "        self._tracer = tracer\n"
            "    def handle(self):\n"
            "        self._tracer.start_span('x')\n"
        ),
    })
    assert baseline_io.finding_id(findings[0]) == baseline_io.finding_id(
        again[0]
    )


def test_baseline_round_trip(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class H:\n"
            "    def __init__(self, tracer):\n"
            "        self._tracer = tracer\n"
            "    def handle(self):\n"
            "        self._tracer.start_span('x')\n"
        ),
    })
    path = str(tmp_path / "baseline.json")
    n = baseline_io.write_baseline(path, findings)
    assert n == len(findings)
    blocking, baselined = baseline_io.apply_baseline(
        findings, baseline_io.load_baseline(path)
    )
    assert blocking == [] and baselined == len(findings)


def test_suppression_silences_leak_finding(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/svc/a.py": (
            "class H:\n"
            "    def __init__(self, tracer):\n"
            "        self._tracer = tracer\n"
            "    def handle(self):\n"
            "        # gofrlint: disable=leak-unreleased -- exporter owns it\n"
            "        self._tracer.start_span('x')\n"
        ),
    })
    assert findings == []


# ----------------------------------------------------- real-tree gates
def test_real_tree_clean():
    """The acceptance bar: the repo itself is leakcheck-clean (the
    wedged-stop executor strand is fixed, the quarantine leaks are
    declared by annotation)."""
    findings = run_rules(
        [os.path.join(REPO_ROOT, "gofr_tpu")], leakcheck_rules()
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_resource_table_contains_known_sites():
    table = build_resource_table([os.path.join(REPO_ROOT, "gofr_tpu")])
    kv = table["kinds"]["kv-slot"]
    assert any(
        s.startswith("gofr_tpu/serving/engine.py:") for s in kv["acquire_sites"]
    )
    assert any(
        s.startswith("gofr_tpu/serving/engine.py:") for s in kv["release_sites"]
    )
    assert "alloc_slot" in kv["acquire_methods"]
    wrappers = table["kinds"]["native-wrapper"]
    assert "BlockAllocator" in wrappers["acquire_methods"]


def test_check_coverage_divergences():
    table = build_resource_table([os.path.join(REPO_ROOT, "gofr_tpu")])
    ok = {"events": [
        {"kind": "kv-slot", "op": "acquire", "name": "alloc_slot"},
        {"kind": "kv-slot", "op": "release", "name": "free_slot"},
        {"kind": "native-wrapper", "op": "release", "name": "leak"},
    ]}
    assert check_coverage(ok, table) == []
    bad = {"events": [
        {"kind": "kv-slot", "op": "release", "name": "mystery_free"},
        {"kind": "unknown-kind", "op": "acquire", "name": "x"},
    ]}
    divs = check_coverage(bad, table)
    assert len(divs) == 2
    assert any("mystery_free" in d for d in divs)
    assert any("unknown-kind" in d for d in divs)


# ------------------------------------------------- runtime reclaim tracer
def test_leaktrace_install_guard_and_uninstall():
    from gofr_tpu.analysis import leaktrace
    from gofr_tpu.native.runtime import BlockAllocator

    original = BlockAllocator.close
    mon = leaktrace.install()
    try:
        with pytest.raises(leaktrace.LeakTraceError):
            leaktrace.install()
        assert BlockAllocator.close is not original
    finally:
        assert leaktrace.uninstall() is mon
    assert BlockAllocator.close is original


def test_leaktrace_balance_and_leak_detection():
    from gofr_tpu.analysis import leaktrace
    from gofr_tpu.native.runtime import BlockAllocator

    mon = leaktrace.install()
    try:
        ba = BlockAllocator(8, 4, force_python=True)
        ba.alloc(7, 4)
        # a live kv-seq + wrapper: the ledger must name both
        assert len(mon.unreclaimed()) == 2
        with pytest.raises(leaktrace.LeakTraceError):
            mon.check()
        ba.free(7)
        ba.close()
    finally:
        leaktrace.uninstall()
    mon.check()  # balanced now
    events = {(e["kind"], e["op"]) for e in mon.events()}
    assert ("kv-seq", "acquire") in events
    assert ("native-wrapper", "release") in events


def test_runtime_pairs_covered_by_static_table():
    """THE tier-1 cross-check: a real engine workload's observed
    acquire/release pairs are a subset of the static table (zero
    divergences), and the dynamic reclaim ledger drains to empty —
    leakcheck has no blind spot for a resource the runtime actually
    cycles."""
    import jax

    from gofr_tpu.analysis import leaktrace
    from gofr_tpu.models import llama
    from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine

    try:
        mon = leaktrace.install()
    except leaktrace.LeakTraceError:
        pytest.skip("leaktrace already installed by an outer tier")
    try:
        cfg = llama.LlamaConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=64, max_seq_len=64,
        )
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServingEngine(
            cfg, params,
            EngineConfig(max_slots=2, max_seq_len=64, prefill_buckets=(16,),
                         admission_per_step=2, max_queue=16,
                         kv_page_size=8, kv_num_pages=64),
            ByteTokenizer(cfg.vocab_size),
        )
        eng.start()
        try:
            futs = [
                eng.submit(f"hello {i}", max_new_tokens=4) for i in range(3)
            ]
            for fut in futs:
                fut.result(timeout=120)
        finally:
            eng.stop()
    finally:
        leaktrace.uninstall()
    mon.check()  # dynamic reclaim invariant: nothing live after stop
    observed = {(e["kind"], e["op"]) for e in mon.events()}
    assert ("kv-slot", "acquire") in observed
    assert ("timeline", "release") in observed
    table = build_resource_table([os.path.join(REPO_ROOT, "gofr_tpu")])
    divergences = check_coverage(mon.export(), table)
    assert divergences == [], "\n".join(divergences)


def test_leaktrace_export_merges(tmp_path):
    from gofr_tpu.analysis import leaktrace

    path = str(tmp_path / "leaks.json")
    mon = leaktrace.LeakTraceMonitor()
    mon.on_acquire("kv-slot", "alloc_slot", 1)
    mon.on_release("kv-slot", "free_slot", 1)
    leaktrace.export_to(mon, path)
    mon2 = leaktrace.LeakTraceMonitor()
    mon2.on_acquire("timeline", "begin", 2)
    mon2.on_release("timeline", "finish", 2)
    leaktrace.export_to(mon2, path)
    with open(path, encoding="utf-8") as fp:
        merged = json.load(fp)
    kinds = {e["kind"] for e in merged["events"]}
    assert kinds == {"kv-slot", "timeline"}
    assert merged["unreclaimed"] == []


# --------------------------------------- the sweep's regression test (TP)
def test_wedged_stop_shuts_down_host_side_executors():
    """The true positive the leakcheck sweep found: stop() on a WEDGED
    engine (loop thread failed to join) used to return with the detok
    executor and the spill tier's worker still accepting work — a
    stranded thread for the life of the process. Host-side executors
    are ours even under a hung engine thread; only the native
    scheduler/pools stay quarantined."""
    import jax

    from gofr_tpu.models import llama
    from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
    from gofr_tpu.serving.kv_spill import TieredPrefixCache

    cfg = llama.LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    spill = TieredPrefixCache(4, spill_bytes=1 << 20)
    eng = ServingEngine(
        cfg, params,
        EngineConfig(max_slots=2, max_seq_len=64, prefill_buckets=(16,),
                     admission_per_step=2, max_queue=16),
        ByteTokenizer(cfg.vocab_size),
        prefix_cache=spill,
    )
    # simulate the wedge: a loop thread that will not join in time
    release = threading.Event()
    hung = threading.Thread(target=release.wait, daemon=True)
    hung.start()
    eng._thread = hung
    eng._running = True
    try:
        eng.stop(join_timeout=0.05)
        assert eng._wedged
        assert eng.health_check()["status"] == "WEDGED"
        # the host-side executors stopped accepting work
        assert eng._detok._shutdown
        assert spill._exec._shutdown
        # the native scheduler was NOT destroyed (quarantine intact):
        # stats() still serves (a destroyed handle could not)
        assert "queue_depth" in eng._sched.stats()
    finally:
        release.set()
        hung.join(timeout=5)


# ----------------------------------------- unified front door + SARIF
def test_run_unified_matches_classic_pass(tmp_path):
    """The --all shared walk returns exactly what run_rules plus the
    stale-suppression audit return — one implementation, two doors."""
    from gofr_tpu.analysis.audit import stale_suppressions

    files = {
        "gofr_tpu/svc/a.py": (
            "class H:\n"
            "    def __init__(self, tracer):\n"
            "        self._tracer = tracer\n"
            "    def handle(self):\n"
            "        self._tracer.start_span('x')\n"
            "    def quiet(self):\n"
            "        # gofrlint: disable=leak-unreleased -- stale on purpose\n"
            "        pass\n"
        ),
    }
    for rel, source in files.items():
        full = tmp_path / rel
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(source)
    top = str(tmp_path / "gofr_tpu")
    live, stale = run_unified([top], default_rules())
    classic = run_rules([top], default_rules())
    assert [f.render() for f in live] == [f.render() for f in classic]
    audit = stale_suppressions([top])
    assert [f.render() for f in stale] == [f.render() for f in audit]
    assert [f.rule for f in stale] == ["stale-suppression"]


def test_all_front_door_cli(tmp_path, capsys):
    from gofr_tpu.analysis.__main__ import main

    full = tmp_path / "gofr_tpu" / "svc" / "a.py"
    full.parent.mkdir(parents=True)
    full.write_text(
        "class H:\n"
        "    def __init__(self, tracer):\n"
        "        self._tracer = tracer\n"
        "    def handle(self):\n"
        "        self._tracer.start_span('x')\n"
    )
    rc = main([
        "--all", "--no-ffi", "--no-baseline", "--format", "sarif",
        str(tmp_path / "gofr_tpu"),
    ])
    out = capsys.readouterr().out
    doc = json.loads(out)
    assert rc == 1
    assert doc["version"] == "2.1.0"
    results = doc["runs"][0]["results"]
    assert any(r["ruleId"] == "leak-unreleased" for r in results)
    loc = results[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("a.py")
    assert loc["region"]["startLine"] >= 1
    rules = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert {"leak-unreleased", "settle-on-raise", "lock-order-static"} <= rules


def test_all_front_door_clean_exit(tmp_path, capsys):
    from gofr_tpu.analysis.__main__ import main

    full = tmp_path / "gofr_tpu" / "svc" / "a.py"
    full.parent.mkdir(parents=True)
    full.write_text("x = 1\n")
    rc = main(["--all", "--no-ffi", str(tmp_path / "gofr_tpu")])
    assert rc == 0
    assert "clean" in capsys.readouterr().out


def test_check_leak_table_cli(tmp_path, capsys):
    from gofr_tpu.analysis.__main__ import main

    export = tmp_path / "leaks.json"
    export.write_text(json.dumps({
        "version": 1,
        "events": [
            {"kind": "kv-slot", "op": "acquire", "name": "alloc_slot"},
        ],
        "unreclaimed": [],
    }))
    rc = main(["--check-leak-table", str(export)])
    assert rc == 0
    export.write_text(json.dumps({
        "version": 1,
        "events": [
            {"kind": "kv-slot", "op": "acquire", "name": "mystery"},
        ],
        "unreclaimed": ["kv-slot acquired via mystery (key 1) never released"],
    }))
    rc = main(["--check-leak-table", str(export)])
    assert rc == 1
