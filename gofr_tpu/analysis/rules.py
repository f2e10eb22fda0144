"""gofrlint rules: the framework invariants, as AST lints.

Rules
-----
``blocking-call``
    No blocking primitives (``time.sleep``, subprocess, sync socket/HTTP,
    sync ``open``) inside HTTP/gRPC handler dispatch or the engine decode
    loop — those run on the event loop or the step thread, where one
    blocked millisecond is a missed decode step for every active slot.
    In retry/backoff paths (service client, pubsub reconnect, pool ping)
    only ``time.sleep`` is flagged: a sleep there must be an
    interruptible ``Event.wait`` so shutdown is never held hostage.
``host-sync``
    No host-device synchronization (``np.asarray``/``np.array`` on
    device values, ``jax.device_get``, ``.block_until_ready()``,
    ``.item()``) inside the decode hot path except at explicitly
    annotated sync points. The depth-1 pipelined decode is built around
    ONE sync per step; an accidental second one serializes host and
    device again (the ~14x regression VERDICT r3 measured).
``metric-unregistered`` / ``metric-dynamic-name`` / ``metric-label-cardinality``
    Metric names used at call sites must be registered (the Manager
    silently drops unknown names — a typo loses the series, it does not
    crash), must be literals (dynamic names defeat registration), and
    label keys/values must be bounded (an f-string label value such as a
    request id explodes Prometheus cardinality).
``ctypes-unchecked``
    Every ctypes call into the native layer returns a status code;
    discarding it turns a C-side failure (bad handle, OOM) into silent
    corruption. Calls whose result is not consumed are flagged.
``daemon-loop-no-heartbeat``
    A ``while True`` loop running as a daemon-thread target must either
    check a stop ``Event`` or stamp a heartbeat — otherwise it can
    neither be shut down deliberately nor watched for hangs
    (``gofr_tpu/testutil/`` scaffolding is exempt).
``pubsub-manual-settle``
    Subscriber handlers registered via ``app.subscribe(topic, handler)``
    are settled by the framework loop (commit on success, nack/DLQ on
    failure — subscriber.py). A handler that ALSO calls ``commit()``/
    ``nack()`` on its message rides on settle idempotency at best and
    fights the delivery policy at worst (a handler-committed message can
    no longer be nacked into the retry/DLQ ladder). Cross-file: handler
    registrations are collected everywhere, settle calls inside those
    functions are flagged.
``router-retry-untyped``
    The router's retry/failover paths (serving/router.py ``submit`` /
    ``_failover`` / ``_hedge``) may catch ONLY the typed-retriable error
    set (``RETRIABLE_ERRORS``: 503 warm-restart, 429 shed, breaker-open,
    chaos transient, transport reset) plus the terminal
    ``ErrorDeadlineExceeded``. A broad ``except Exception`` there would
    re-route requests that failed for non-retriable reasons — silently
    duplicating work, or worse, a non-idempotent stream.

Blocking/host-sync checks skip nested (closure) functions: closures in
these zones are deferred work — thread targets and
``run_in_executor`` payloads — which is exactly how blocking work is
*supposed* to leave the hot path.
"""

from __future__ import annotations

import ast

from gofr_tpu.analysis.core import Finding, Rule, SourceFile

# -- zone tables --------------------------------------------------------------

# event-loop / decode-thread dispatch surfaces: full blocking-call set.
# "*" = every function in the file; a set restricts to named functions.
DISPATCH_ZONES: dict[str, set[str] | str] = {
    "gofr_tpu/http/dispatch.py": "*",
    "gofr_tpu/http/server.py": "*",
    "gofr_tpu/handler.py": "*",
    "gofr_tpu/grpcx/server.py": "*",
    "gofr_tpu/websocket.py": "*",
    "gofr_tpu/serving/handlers.py": "*",
    "gofr_tpu/serving/engine.py": "*",
    "gofr_tpu/serving/batch.py": "*",
    "gofr_tpu/serving/stepplan.py": "*",
    "gofr_tpu/serving/native_embed.py": "*",
    "gofr_tpu/serving/router.py": "*",
    # KV reuse tier: engine-thread-facing surfaces only — the spill
    # worker (_spill_task/_to_host) and the wire codec (encode_entry)
    # run off-thread BY DESIGN and stay out of the zone
    "gofr_tpu/serving/kv_spill.py": {
        "get", "get_with_tier", "put", "peek", "evict", "_offer",
        "_to_device", "advertised",
    },
    "gofr_tpu/serving/prefix_index.py": {
        "fetch_chain", "fetch_one", "fetch_handoff", "fetch_one_handoff",
        "evacuate_chain", "locate", "longest_chain", "observe",
    },
    # disaggregation plane: the autoscaler's control loop must stay on
    # interruptible Event.wait pacing, and the remote-stream transport's
    # event parsing must never grow a named blocking call — the frame
    # READS block by design (pool worker threads), but through the
    # already-open streaming response, never a fresh urlopen/sleep
    "gofr_tpu/serving/autoscaler.py": "*",
    "gofr_tpu/serving/remote.py": "*",
    # multi-tenant plane: tenancy policy runs on the submit path; the
    # adapter registry's engine/submit-facing surface must never block
    # unbounded (the lora-upload WORKER — _upload — is off-thread by
    # design, like the kv-spill worker, and stays out of the zone)
    "gofr_tpu/serving/tenancy.py": "*",
    # HA plane: the idempotency registry + replay ring sit directly on
    # the submit/admission path (engine thread + handler threads) — pure
    # lock-guarded data structures, and they must stay that way
    "gofr_tpu/serving/dedup.py": "*",
    "gofr_tpu/serving/lora.py": {
        "acquire", "release", "tables", "slot_factors", "prefetch",
        "register", "deregister", "known", "residency",
    },
}

# retry/backoff paths reachable from handlers: uninterruptible sleeps only
BACKOFF_ZONES: dict[str, set[str] | str] = {
    "gofr_tpu/service/options.py": "*",
    "gofr_tpu/datasource/pubsub/mqtt.py": "*",
    "gofr_tpu/datasource/sql/pool.py": "*",
}

# router failover/hedge paths: except clauses here may name ONLY the
# typed-retriable set (plus the terminal deadline error) — a broad catch
# would re-route non-retriable failures (serving/router.py)
ROUTER_RETRY_ZONES: dict[str, set[str] | str] = {
    "gofr_tpu/serving/router.py": {
        "submit", "_submit_attempt", "_failover", "_hedge",
        # the disaggregated two-phase path walks candidates exactly like
        # submit does — its except clauses are pinned to the same set
        "_submit_disagg", "_prefill_attempt", "_decode_phase",
        # the remote transport workers settle the replica future: their
        # deliberately-broad settle-on-anything catches carry reasoned
        # suppressions (a narrow catch would strand the future)
        "_run_unary", "_run_stream",
        # HA plane: the keyed re-attach walk classifies per-replica
        # outcomes exactly like submit's candidate walk, and the resume
        # transport worker settles the future like _run_stream
        "resume", "_run_resume",
    },
}
ROUTER_RETRIABLE_NAMES = {
    "RETRIABLE_ERRORS",        # the canonical tuple (serving/router.py)
    "ErrorServiceUnavailable", "ErrorTooManyRequests",
    "CircuitBreakerError", "ChaosFault", "ConnectionError",
    "ErrorDeadlineExceeded",   # terminal: settles the request, never retried
    "ErrorStaleEpoch",         # fence rejection: router re-stamps and fails over
    "ErrorEntityNotFound",     # resume walk: replica doesn't hold the key — try the next
}

# decode hot path: ONE annotated sync point per N-step block (engine.py
# _block_sync), nothing else — the dispatch, spec, and commit functions
# are all in the zone
HOT_SYNC_ZONES: dict[str, set[str] | str] = {
    "gofr_tpu/serving/engine.py": {
        "_loop", "_loop_body", "_decode_step", "_spec_step",
        "_dispatch_decode", "_dispatch_rows", "_dispatch_ragged",
        "_launch_idle", "_count_launch", "_consume_block",
        "_commit_token", "_commit_first_token", "_emit_token",
        "_emit_async", "_block_sync", "_slot_in_flight",
        "_make_device_state", "_retire", "_plan_step", "_cursor_health",
        "_cache_lookup", "_record_prefix_tier",
        # multi-tenant plane: the preemption ladder and the adapter
        # plumbing all run on the engine thread — the KV page-out in
        # _preempt must stay pure device reads (read_span/slices), and
        # the adapter delta must never materialize anything host-side
        "_maybe_preempt", "_preempt", "_lora_adjusted", "_lora_release",
    },
    "gofr_tpu/serving/batch.py": "*",
    "gofr_tpu/serving/stepplan.py": "*",
    # adapter registry: engine-thread-facing surface only — the
    # lora-upload worker (_upload) materializes host arrays on its own
    # thread by design, mirroring the kv-spill worker
    "gofr_tpu/serving/lora.py": {
        "acquire", "release", "tables", "slot_factors",
    },
    # migration/upload paths that run on the engine thread: a host sync
    # sneaking in here would stall admission behind a device round-trip.
    # The spill worker's np.asarray (device→host, its own thread) and
    # the /kv/fetch codec (HTTP worker) are deliberately OUTSIDE.
    "gofr_tpu/serving/kv_spill.py": {
        "get", "get_with_tier", "put", "peek", "_offer", "_to_device",
    },
    "gofr_tpu/serving/prefix_index.py": {
        "fetch_chain", "fetch_one", "locate", "longest_chain",
    },
}

BLOCKING_CALLS = {
    "time.sleep",
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output", "subprocess.Popen",
    "os.system", "os.popen",
    "socket.create_connection",
    "urllib.request.urlopen",
    "requests.get", "requests.post", "requests.put", "requests.delete",
    "requests.request",
    "open",
}

SLEEP_CALLS = {"time.sleep"}

HOST_SYNC_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get",
}
HOST_SYNC_METHODS = {"block_until_ready", "item"}
# int()/float()/bool() on a DEVICE value is a hidden sync (jax __int__
# blocks until the array materializes). An AST lint cannot type-infer, so
# taint heuristically: names assigned (incl. tuple unpacks) from calls
# rooted in these modules / with these terminal names produce device
# values, and so do dotted names with a device-marker suffix. np.asarray
# results are HOST values — materialization is the flagged sync itself,
# so converting them afterwards is clean.
DEVICE_PRODUCER_ROOTS = {"jnp", "jax", "batch_ops"}
DEVICE_PRODUCER_NAMES = {"sample_logits", "sample_first_token", "prefill_compute"}
DEVICE_NAME_SUFFIXES = ("_dev", "_device")
HOST_CONVERT_CALLS = {"int", "float", "bool"}

# native-layer status codes: functions WITHOUT a status return (string
# accessors) are exempt from ctypes-unchecked
CTYPES_NO_STATUS = {"gofr_runtime_version", "gofr_pjrt_last_error"}

METRIC_REGISTER_METHODS = {
    "new_counter", "new_updown_counter", "new_gauge", "new_histogram",
}
# method -> index of the first label argument (k, v alternating)
METRIC_USE_METHODS = {
    "increment_counter": 1,
    "add_counter": 2,
    "delta_updown_counter": 2,
    "record_histogram": 2,
    "set_gauge": 2,
    "delete_gauge": 1,
}


def _dotted(node: ast.expr) -> str | None:
    """'time.sleep' for Name/Attribute chains; None for computed funcs."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _zone_functions(
    zones: dict[str, set[str] | str], rel_path: str
) -> set[str] | str | None:
    for suffix, funcs in zones.items():
        if rel_path.endswith(suffix):
            return funcs
    return None


class _FunctionCalls(ast.NodeVisitor):
    """Collect (call, enclosing-function-name, closure-depth) triples."""

    def __init__(self) -> None:
        self.calls: list[tuple[ast.Call, str | None, int]] = []
        self._stack: list[str] = []

    def _visit_func(self, node: ast.AST) -> None:
        self._stack.append(node.name)  # type: ignore[attr-defined]
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Call(self, node: ast.Call) -> None:
        name = self._stack[0] if self._stack else None
        self.calls.append((node, name, len(self._stack)))
        self.generic_visit(node)


class BlockingCallRule(Rule):
    name = "blocking-call"

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        funcs = _zone_functions(DISPATCH_ZONES, sf.rel_path)
        flagged = BLOCKING_CALLS
        if funcs is None:
            funcs = _zone_functions(BACKOFF_ZONES, sf.rel_path)
            flagged = SLEEP_CALLS
        if funcs is None:
            return []
        visitor = _FunctionCalls()
        visitor.visit(sf.tree)
        out: list[Finding] = []
        for call, func_name, depth in visitor.calls:
            if depth > 1:  # closures are deferred work, off the hot path
                continue
            if funcs != "*" and func_name not in funcs:
                continue
            dotted = _dotted(call.func)
            if dotted in flagged:
                what = (
                    "uninterruptible sleep in a retry/backoff path — use an "
                    "Event.wait so close() can interrupt it"
                    if flagged is SLEEP_CALLS
                    else "blocking call in a handler-dispatch/decode-loop zone"
                )
                out.append(
                    Finding(self.name, sf.rel_path, call.lineno,
                            f"{dotted}(): {what}")
                )
        return out


class HostSyncRule(Rule):
    """``host-sync``: flags explicit materializations (np.asarray,
    jax.device_get, .item(), .block_until_ready()) AND the hidden ones —
    ``int()``/``float()``/``bool()`` on a device value blocks exactly like
    np.asarray does. Device values are tracked heuristically per function:
    names assigned from calls rooted in jnp/jax/batch_ops (or known
    producer names like sample_logits), names copied from tainted names,
    and dotted names carrying a device-marker suffix (``_dev``,
    ``_device``). Results of np.asarray/np.array are HOST values — the
    materialization itself is the (annotatable) sync, so converting them
    afterwards is clean. ``.shape``/``.dtype``-style metadata reads never
    taint a conversion."""

    name = "host-sync"

    _BENIGN_META = {"shape", "ndim", "dtype", "size"}

    def _tainted_names(self, func: ast.AST) -> set[str]:
        """Device-valued dotted names assigned inside ``func`` (top-level
        statements only — closures are deferred work, off the hot path).
        Two passes give one-hop propagation through local copies."""
        tainted: set[str] = set()

        def value_is_device(expr: ast.expr) -> bool:
            if isinstance(expr, ast.Call):
                d = _dotted(expr.func) or ""
                if d == "jax.device_get":
                    return False  # a sync, flagged on its own; result is host
                return (
                    d.split(".")[0] in DEVICE_PRODUCER_ROOTS
                    or d.split(".")[-1] in DEVICE_PRODUCER_NAMES
                )
            if isinstance(expr, (ast.Name, ast.Attribute)):
                d = _dotted(expr)
                return d is not None and (
                    d in tainted or d.endswith(DEVICE_NAME_SUFFIXES)
                )
            if isinstance(expr, (ast.Tuple, ast.List)):
                return any(value_is_device(e) for e in expr.elts)
            return False

        def scan(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                    continue
                if isinstance(child, ast.Assign) and value_is_device(child.value):
                    targets: list[ast.expr] = list(child.targets)
                    while targets:
                        t = targets.pop()
                        if isinstance(t, (ast.Tuple, ast.List)):
                            targets.extend(t.elts)
                        else:
                            d = _dotted(t)
                            if d:
                                tainted.add(d)
                scan(child)

        scan(func)
        scan(func)  # second pass: one-hop propagation through copies
        return tainted

    def _convert_arg_tainted(self, call: ast.Call, tainted: set[str]) -> bool:
        """True when any (non-metadata) name inside the conversion's
        argument expression is a device value."""
        if not call.args:
            return False

        hit = False

        def walk(n: ast.AST) -> None:
            nonlocal hit
            if hit:
                return
            if isinstance(n, ast.Attribute) and n.attr in self._BENIGN_META:
                return  # .shape/.dtype reads are static metadata, not syncs
            if isinstance(n, (ast.Name, ast.Attribute)):
                d = _dotted(n)
                if d is not None and (
                    d in tainted or d.endswith(DEVICE_NAME_SUFFIXES)
                ):
                    hit = True
                    return
            for child in ast.iter_child_nodes(n):
                walk(child)

        walk(call.args[0])
        return hit

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        funcs = _zone_functions(HOT_SYNC_ZONES, sf.rel_path)
        if funcs is None:
            return []
        visitor = _FunctionCalls()
        visitor.visit(sf.tree)
        taint_cache: dict[str, set[str]] = {}
        func_nodes = {
            n.name: n
            for n in sf.tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ClassDef):
                for n in node.body:
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        func_nodes.setdefault(n.name, n)
        out: list[Finding] = []
        for call, func_name, depth in visitor.calls:
            if depth > 1:
                continue
            if funcs != "*" and func_name not in funcs:
                continue
            dotted = _dotted(call.func)
            method = (
                call.func.attr if isinstance(call.func, ast.Attribute) else None
            )
            if dotted in HOST_SYNC_CALLS or method in HOST_SYNC_METHODS:
                out.append(
                    Finding(
                        self.name, sf.rel_path, call.lineno,
                        f"{dotted or '.' + str(method)}(): host-device sync in "
                        "the decode hot path — annotate deliberate sync points "
                        "with '# gofrlint: disable=host-sync -- <why>'",
                    )
                )
                continue
            if dotted in HOST_CONVERT_CALLS and func_name in func_nodes:
                if func_name not in taint_cache:
                    taint_cache[func_name] = self._tainted_names(
                        func_nodes[func_name]
                    )
                if self._convert_arg_tainted(call, taint_cache[func_name]):
                    out.append(
                        Finding(
                            self.name, sf.rel_path, call.lineno,
                            f"{dotted}() on a device value: a hidden "
                            "host-device sync in the decode hot path — read "
                            "it through the block's one sanctioned "
                            "materialization instead (or annotate with "
                            "'# gofrlint: disable=host-sync -- <why>')",
                        )
                    )
        return out


class CtypesCheckedRule(Rule):
    name = "ctypes-unchecked"

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        if "gofr_tpu/native/" not in sf.rel_path + "/":
            return []
        out: list[Finding] = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Expr) or not isinstance(node.value, ast.Call):
                continue
            func = node.value.func
            if isinstance(func, ast.Attribute) and func.attr.startswith("gofr_"):
                if func.attr in CTYPES_NO_STATUS:
                    continue
                out.append(
                    Finding(
                        self.name, sf.rel_path, node.lineno,
                        f"{func.attr}(): native status code discarded — wrap "
                        "in _check() (a C-side failure must not pass silently)",
                    )
                )
        return out


class MetricsRule(Rule):
    """Cross-file: registrations collected everywhere, usages checked in
    finalize. Dynamic names / unbounded labels are flagged in place.

    Beyond never-registered names (``metric-unregistered`` — the Manager
    silently drops them), full-tree runs enforce the REGISTRATION SITE
    (``metric-register-site``): a name used anywhere in ``gofr_tpu/``
    must be registered in ``container/container.py`` (the framework
    metric catalog every deployment gets) or in the using file's own
    directory (self-registering subsystems: datasource drivers, the gRPC
    server). Registration at an arbitrary distance means the series
    silently vanishes in any process that never imports the registering
    module — the PR 1 ``app_spec_accept_rate`` bug class. Only enforced
    when ``container/container.py`` is part of the scanned tree, so
    file-subset runs and fixture trees are unaffected."""

    name = "metric-unregistered"
    cross_file = True

    def __init__(self) -> None:
        self._registered: set[str] = set()
        self._register_sites: dict[str, set[str]] = {}  # name -> rel paths
        # name -> first container-catalog registration (path, line): the
        # anchor for the inverse metric-never-emitted finding
        self._catalog_lines: dict[str, tuple[str, int]] = {}
        self._container_seen = False
        self._usages: list[tuple[str, str, int]] = []  # (name, path, line)
        # names wired to a callback gauge: `g = m.get("name")` +
        # `g.observe_with(...)` — emitted every scrape, no .set site
        self._observed: set[str] = set()

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        in_container = sf.rel_path.endswith("container/container.py")
        if in_container:
            self._container_seen = True
        inline: list[Finding] = []
        # (scope, var) -> metric name from `var = m.get("x")`, joined
        # against observe_with receivers AFTER the walk (ast order does
        # not guarantee the Assign is visited first). Keyed per
        # enclosing function: two callback gauges wired through the
        # same idiomatic local name (`g`) in different functions must
        # not collide
        get_bound: dict[tuple[int, str], str] = {}
        observe_vars: set[tuple[int, str]] = set()

        def scoped_nodes(root, scope):
            for child in ast.iter_child_nodes(root):
                child_scope = (
                    id(child)
                    if isinstance(
                        child,
                        (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda),
                    )
                    else scope
                )
                yield child, child_scope
                yield from scoped_nodes(child, child_scope)

        for node, scope in scoped_nodes(sf.tree, 0):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
                and isinstance(node.value.func, ast.Attribute)
                and node.value.func.attr == "get"
                and node.value.args
                and isinstance(node.value.args[0], ast.Constant)
                and isinstance(node.value.args[0].value, str)
            ):
                get_bound[(scope, node.targets[0].id)] = (
                    node.value.args[0].value
                )
                continue
            if not isinstance(node, ast.Call) or not isinstance(
                node.func, ast.Attribute
            ):
                continue
            method = node.func.attr
            if method in METRIC_REGISTER_METHODS and node.args:
                first = node.args[0]
                if isinstance(first, ast.Constant) and isinstance(first.value, str):
                    self._registered.add(first.value)
                    self._register_sites.setdefault(first.value, set()).add(
                        sf.rel_path
                    )
                    if in_container:
                        self._catalog_lines.setdefault(
                            first.value, (sf.rel_path, node.lineno)
                        )
            elif method in METRIC_USE_METHODS:
                inline.extend(
                    self._check_usage(sf, node, METRIC_USE_METHODS[method])
                )
            elif method == "observe_with":
                recv = node.func.value
                if isinstance(recv, ast.Name):
                    observe_vars.add((scope, recv.id))
                elif isinstance(recv, ast.Call):
                    # chained m.get("x").observe_with(...)
                    f = recv.func
                    args = recv.args
                    if (
                        isinstance(f, ast.Attribute) and f.attr == "get"
                        and args
                        and isinstance(args[0], ast.Constant)
                        and isinstance(args[0].value, str)
                    ):
                        self._observed.add(args[0].value)
        for key in observe_vars:
            name = get_bound.get(key)
            if name is not None:
                self._observed.add(name)
        return [f for f in inline if not sf.is_suppressed(f.rule, f.line)]

    @staticmethod
    def _unbounded_value(expr: ast.expr) -> bool:
        """True for label-value expressions that smell unbounded: any
        string-building form — f-strings, ``+``/``%`` concatenation,
        ``.format()``/``.join()`` calls. A bare Name may be a bounded
        enum, so it stays clean; building a string at the call site is
        the per-request-id pattern that explodes series cardinality."""
        if isinstance(expr, (ast.JoinedStr, ast.BinOp)):
            return True
        return (
            isinstance(expr, ast.Call)
            and isinstance(expr.func, ast.Attribute)
            and expr.func.attr in ("format", "join")
        )

    def _check_usage(
        self, sf: SourceFile, node: ast.Call, label_start: int
    ) -> list[Finding]:
        out: list[Finding] = []
        if not node.args:
            return out
        first = node.args[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            self._usages.append((first.value, sf.rel_path, node.lineno))
        elif isinstance(first, (ast.JoinedStr, ast.BinOp, ast.Call)):
            out.append(
                Finding(
                    "metric-dynamic-name", sf.rel_path, node.lineno,
                    "computed metric name defeats registration checking — "
                    "use a literal (or a variable bound to one)",
                )
            )
        labels = node.args[label_start:]
        for i, arg in enumerate(labels):
            if i % 2 == 0:  # label KEY
                if not (
                    isinstance(arg, ast.Constant) and isinstance(arg.value, str)
                ) and not isinstance(arg, ast.Starred):
                    out.append(
                        Finding(
                            "metric-label-cardinality", sf.rel_path, arg.lineno,
                            "label KEY must be a string literal",
                        )
                    )
            elif self._unbounded_value(arg):
                out.append(
                    Finding(
                        "metric-label-cardinality", sf.rel_path, arg.lineno,
                        "computed label value — unbounded label cardinality "
                        "(per-request values explode the series space)",
                    )
                )
        for kw in node.keywords:
            if kw.arg is not None and self._unbounded_value(kw.value):
                out.append(
                    Finding(
                        "metric-label-cardinality", sf.rel_path, kw.value.lineno,
                        f"computed value for label '{kw.arg}' — unbounded "
                        "label cardinality",
                    )
                )
        return out

    def finalize(self) -> list[Finding]:
        import posixpath

        out: list[Finding] = []
        # the inverse rule (full-tree runs only, mirrors
        # metric-register-site): a name in the container catalog with
        # zero emission sites tree-wide — no .increment/.set/.record
        # call, no observe_with-wired callback gauge — is a dead series
        # every deployment registers and nobody ever feeds
        if self._container_seen:
            used_names = {name for name, _p, _l in self._usages}
            for name, (path, line) in sorted(self._catalog_lines.items()):
                if name in used_names or name in self._observed:
                    continue
                out.append(
                    Finding(
                        "metric-never-emitted", path, line,
                        f"metric '{name}' is registered in the framework "
                        "catalog but has zero emission sites tree-wide "
                        "(no increment/set/record call, no observe_with "
                        "wiring) — a dead series; delete the "
                        "registration or wire the emitter",
                    )
                )
        for name, path, line in self._usages:
            if name not in self._registered:
                out.append(
                    Finding(
                        "metric-unregistered", path, line,
                        f"metric '{name}' is never registered — the Manager "
                        "silently drops it (typo loses the series)",
                    )
                )
                continue
            if not self._container_seen:
                continue  # file-subset / fixture run: site check is moot
            sites = self._register_sites.get(name, set())
            use_dir = posixpath.dirname(path)
            if not any(
                site.endswith("container/container.py")
                or posixpath.dirname(site) == use_dir
                for site in sites
            ):
                out.append(
                    Finding(
                        "metric-register-site", path, line,
                        f"metric '{name}' is registered only in "
                        f"{sorted(sites)} — register it in container/"
                        "container.py (the framework catalog) or in this "
                        "file's own subsystem: a process that never imports "
                        "the registering module silently loses the series",
                    )
                )
        return out


class DaemonLoopHeartbeatRule(Rule):
    """``daemon-loop-no-heartbeat``: a ``while True`` loop running on a
    daemon thread must either check a stop ``Event`` (``.wait()`` /
    ``.is_set()``) or stamp a heartbeat. A daemon loop with neither is
    invisible: it cannot be shut down deliberately, and when it hangs
    nothing — no supervisor, no watchdog — can tell. The engine loop and
    the supervisor watchdog are the template (serving/engine.py stamps
    ``self.heartbeat`` per iteration; supervisor.py gates on
    ``self._stop.wait``).

    Matching is per-file: ``threading.Thread(target=<fn>, daemon=True)``
    registrations are collected, and ``while True:`` loops inside
    same-file functions of that name are checked — ``self.<m>`` targets
    scope to the registering class, so a sibling class's same-named
    method is not cross-flagged. A ``.wait()``/
    ``.is_set()`` counts only when its receiver is recognizably a
    lifecycle event (name contains stop/shutdown/halt/...): a throttling
    ``self._wake.wait(0.05)`` leaves the loop exactly as unstoppable as
    no wait at all. ``gofr_tpu/testutil/`` is exempt — test scaffolding
    threads live exactly as long as the process by design."""

    name = "daemon-loop-no-heartbeat"

    _STOP_METHODS = {"wait", "is_set"}
    # a .wait()/.is_set() only counts as supervision when its receiver is
    # recognizably a LIFECYCLE event: `self._wake.wait(0.05)` is a
    # throttle, not a stop check — a loop gated on nothing but that is
    # still unstoppable and unwatchable, the exact defect this rule exists
    # to flag
    _STOP_NAME_TOKENS = (
        "stop", "shutdown", "shut_down", "halt", "quit", "exit", "done",
        "closed", "closing", "cancel", "term", "finished",
    )

    @staticmethod
    def _target_name(node: ast.expr) -> str | None:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            return node.attr  # self._loop → "_loop"
        return None

    @staticmethod
    def _scoped_walk(tree: ast.AST):
        """Yield (node, enclosing ClassDef | None) over the whole tree."""

        def walk(node: ast.AST, cls: ast.ClassDef | None):
            for child in ast.iter_child_nodes(node):
                child_cls = child if isinstance(child, ast.ClassDef) else cls
                yield child, child_cls
                yield from walk(child, child_cls)

        yield from walk(tree, None)

    def _daemon_targets(
        self, tree: ast.AST
    ) -> tuple[set[str], dict[int, set[str]]]:
        """Collect daemon-thread target names. ``self.<m>`` registrations
        scope to their enclosing class — an unrelated same-named method of
        a sibling class in the same file must not be flagged (same
        rationale as use-after-donation's scope-awareness). Plain-name and
        non-self attribute targets stay file-wide."""
        loose: set[str] = set()
        by_class: dict[int, set[str]] = {}
        for node, cls in self._scoped_walk(tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func) or ""
            if not dotted.split(".")[-1] == "Thread":
                continue
            kw = {k.arg: k.value for k in node.keywords if k.arg}
            daemon = kw.get("daemon")
            if not (isinstance(daemon, ast.Constant) and daemon.value is True):
                continue
            target = kw.get("target")
            if target is None:
                continue
            name = self._target_name(target)
            if not name:
                continue
            if (
                cls is not None
                and isinstance(target, ast.Attribute)
                and isinstance(target.value, ast.Name)
                and target.value.id == "self"
            ):
                by_class.setdefault(id(cls), set()).add(name)
            else:
                loose.add(name)
        return loose, by_class

    def _loop_is_supervised(self, loop: ast.While) -> bool:
        for node in ast.walk(loop):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                if (
                    node.func.attr in self._STOP_METHODS
                    and self._is_stop_receiver(node.func.value)
                ):
                    return True  # stop-Event check gates the loop
                if "heartbeat" in node.func.attr.lower():
                    return True  # e.g. self._stamp_heartbeat()
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign) else [node.target]
                )
                for t in targets:
                    name = (
                        t.attr if isinstance(t, ast.Attribute)
                        else t.id if isinstance(t, ast.Name) else ""
                    )
                    if "heartbeat" in name.lower():
                        return True  # heartbeat stamp
        return False

    def _is_stop_receiver(self, node: ast.expr) -> bool:
        dotted = (_dotted(node) or "").lower()
        return any(tok in dotted for tok in self._STOP_NAME_TOKENS)

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        if "gofr_tpu/testutil/" in sf.rel_path:
            return []
        loose, by_class = self._daemon_targets(sf.tree)
        if not loose and not by_class:
            return []
        out: list[Finding] = []
        for node, cls in self._scoped_walk(sf.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            allowed = loose if cls is None else (
                loose | by_class.get(id(cls), set())
            )
            if node.name not in allowed:
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.While):
                    continue
                test = sub.test
                if not (isinstance(test, ast.Constant) and test.value is True):
                    continue
                if self._loop_is_supervised(sub):
                    continue
                out.append(
                    Finding(
                        self.name, sf.rel_path, sub.lineno,
                        f"'while True' in daemon-thread target '{node.name}' "
                        "checks no stop Event and stamps no heartbeat — "
                        "unstoppable AND unwatchable; gate on an Event.wait/"
                        "is_set or stamp a heartbeat each iteration",
                    )
                )
        return out


class PubSubManualSettleRule(Rule):
    """Cross-file: collect subscriber-handler registrations
    (``*.subscribe(topic, handler)`` and
    ``*subscription_manager.register(topic, handler)``) everywhere, flag
    ``commit()``/``nack()`` calls inside those handler functions in
    finalize. The commit check is receiver-filtered (``ctx.request`` /
    ``msg``-ish names) so ``ctx.sql.commit()`` stays clean; ``nack`` is
    pubsub-only vocabulary and flags on any receiver.

    Handlers are matched by bare function/attribute name (an AST lint
    cannot resolve cross-module references) — an unrelated function that
    shares a registered handler's name and settles messages legitimately
    is a known false positive; suppress it with a reason, like every
    other finding in this suite (fix-or-justify)."""

    name = "pubsub-manual-settle"
    cross_file = True

    _MSGISH = {"msg", "message", "request"}

    def __init__(self) -> None:
        self._handlers: set[str] = set()
        # (enclosing function, path, line, method)
        self._sites: list[tuple[str, str, int, str]] = []

    @staticmethod
    def _handler_name(node: ast.expr) -> str | None:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            return node.attr  # e.g. worker.handler → "handler"
        return None

    def _is_registration(self, call: ast.Call) -> bool:
        func = call.func
        if not isinstance(func, ast.Attribute) or len(call.args) < 2:
            return False
        if func.attr == "subscribe":
            # registration takes (topic, handler); a driver's one-arg
            # subscribe(topic) never gets here because of the arg count
            return True
        if func.attr == "register":
            recv = (_dotted(func.value) or "").rsplit(".", 1)[-1]
            return recv in ("subscription_manager", "manager", "mgr")
        return False

    def _settle_method(self, call: ast.Call) -> str | None:
        func = call.func
        if not isinstance(func, ast.Attribute):
            return None
        if func.attr == "nack":
            return "nack"
        if func.attr == "commit" and not call.args and not call.keywords:
            recv = _dotted(func.value)
            if recv is None:
                return None
            parts = recv.split(".")
            if parts[-1] in self._MSGISH:
                return "commit"
        return None

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        visitor = _FunctionCalls()
        visitor.visit(sf.tree)
        for call, func_name, _depth in visitor.calls:
            if self._is_registration(call):
                name = self._handler_name(call.args[1])
                if name:
                    self._handlers.add(name)
                continue
            method = self._settle_method(call)
            if (
                method is not None
                and func_name is not None
                and not sf.is_suppressed(self.name, call.lineno)
            ):
                self._sites.append((func_name, sf.rel_path, call.lineno, method))
        return []

    def finalize(self) -> list[Finding]:
        return [
            Finding(
                self.name, path, line,
                f"subscriber handler '{func}' calls .{method}() itself — the "
                "framework loop settles every delivered message (commit on "
                "success, nack/DLQ on failure); drop the manual settle or "
                "suppress with a reason",
            )
            for func, path, line, method in self._sites
            if func in self._handlers
        ]


class RouterRetryTypedRule(Rule):
    """``router-retry-untyped``: except clauses inside the router's
    retry-zone functions (ROUTER_RETRY_ZONES) must name only the typed
    retriable error set. ``except Exception``, a bare ``except``, or any
    unlisted type is a finding — the failover path re-submitting a
    request that failed a 400-class or programming error would duplicate
    work (and a non-idempotent stream) silently."""

    name = "router-retry-untyped"

    def _bad_names(self, handler: ast.ExceptHandler) -> list[str]:
        t = handler.type
        if t is None:
            return ["<bare except>"]
        exprs = list(t.elts) if isinstance(t, ast.Tuple) else [t]
        bad: list[str] = []
        for expr in exprs:
            dotted = _dotted(expr)
            if dotted is None:
                bad.append("<computed>")
                continue
            if dotted.rsplit(".", 1)[-1] not in ROUTER_RETRIABLE_NAMES:
                bad.append(dotted)
        return bad

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        funcs = _zone_functions(ROUTER_RETRY_ZONES, sf.rel_path)
        if funcs is None:
            return []
        out: list[Finding] = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if funcs != "*" and node.name not in funcs:
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.ExceptHandler):
                    continue
                bad = self._bad_names(sub)
                if bad and not sf.is_suppressed(self.name, sub.lineno):
                    out.append(
                        Finding(
                            self.name, sf.rel_path, sub.lineno,
                            f"retry path '{node.name}' catches "
                            f"{', '.join(bad)} — only the typed-retriable "
                            "set (RETRIABLE_ERRORS, or its members / "
                            "ErrorDeadlineExceeded) may be handled here",
                        )
                    )
        return out


def default_rules() -> list[Rule]:
    from gofr_tpu.analysis.deadlinecheck import deadlinecheck_rules
    from gofr_tpu.analysis.kernelcheck import kernelcheck_rules
    from gofr_tpu.analysis.leakcheck import leakcheck_rules
    from gofr_tpu.analysis.lockcheck import lockcheck_rules
    from gofr_tpu.analysis.shardcheck import shardcheck_rules

    return [
        BlockingCallRule(), HostSyncRule(), CtypesCheckedRule(), MetricsRule(),
        DaemonLoopHeartbeatRule(), PubSubManualSettleRule(),
        RouterRetryTypedRule(),
        *shardcheck_rules(),
        *lockcheck_rules(),
        *leakcheck_rules(),
        *deadlinecheck_rules(),
        *kernelcheck_rules(),
    ]
