"""shardcheck: SPMD/collective consistency, donation & retrace analysis.

The serving numbers (Llama-3-8B on v5e-8, <200 ms p50 TTFT, >1k req/s)
die silently at the SPMD layer: a collective whose ``axis_name`` does
not match the mesh vocabulary compiles into garbage (or an obscure
unbound-axis error three layers away), a donated buffer read after the
donating dispatch raises "Array has been deleted" only on the backend
that actually donates, and a ``@jit`` function that branches on a traced
value or takes an unhashable static retraces (or dies) per request.
These rules make each of those a lint-time finding:

``mesh-axis-unknown``
    Every string-literal axis — in a ``PartitionSpec``, a collective's
    ``axis_name``, a ``shard_map`` ``axis_names={...}`` binding, or an
    ``axis=``/``axis_name=`` keyword/default — must be declared by the
    mesh construction (``AXIS_ORDER`` in parallel/mesh.py, or a literal
    ``Mesh(..., (axes...))``). A typo ("tpu" for "tp") otherwise ships
    and fails at trace time on the one topology that exercises it.
    Cross-file: only enforced when the linted tree declares a mesh.

``collective-unmapped``
    A collective with a *literal* axis name must run under a mapped
    context: lexically inside a function handed to ``shard_map``/``pmap``
    (directly, via ``functools.partial``, or as a nested def). Axis
    names received as *parameters* are the caller's contract and are
    checked at the wrapper instead — that is exactly the
    ``*_sharded(..., axis_name=...)`` body convention in parallel/ and
    ops/moe.py.

``use-after-donation``
    ``donate_argnums``/``donate_argnames`` on ``jit`` mark buffers whose
    storage the dispatch consumes. Reading the donor variable after the
    call is the round-4 on-TPU crash class ("Array has been deleted"):
    the rule tracks jit-decorated donating functions across the tree and
    flags any load of a donated argument (plain name or dotted
    ``self.x.y`` chain) after the call and before rebinding. Metadata
    reads (``.shape``/``.dtype``/...) are exempt — deleting a buffer
    keeps its aval.

``retrace-hazard``
    In the decode hot path (serving/engine.py, serving/batch.py,
    serving/kv_cache.py, ops/) a ``@jit`` function must compile once per
    shape bucket, never per request: flags Python ``if``/``while``
    branching on traced (non-static) parameters, ``int()``/``float()``/
    ``bool()`` concretization of traced parameters, unhashable
    (list/dict/set) values in *static* positions — at the def (mutable
    default on a static param) and at every call site of a known jit
    function — and ``jax.jit`` invoked inside a hot-path function body
    (a fresh wrapper per call defeats the compile cache entirely).
    ``x is None`` tests, ``isinstance``/``len`` and ``.shape``/``.ndim``
    /``.dtype`` inspection are static under tracing and stay exempt.

All rules honor the standard fix-or-justify suppressions
(``# gofrlint: disable=<rule> -- <reason>``, docs/static-analysis.md).
"""

from __future__ import annotations

import ast
import dataclasses

from gofr_tpu.analysis.core import Finding, Rule, SourceFile

# ---------------------------------------------------------------------------
# shared AST helpers

#: collective -> positional index of its axis-name argument
COLLECTIVES: dict[str, int] = {
    "psum": 1,
    "pmean": 1,
    "pmax": 1,
    "pmin": 1,
    "ppermute": 1,
    "pshuffle": 1,
    "all_gather": 1,
    "all_to_all": 1,
    "psum_scatter": 1,
    "pbroadcast": 1,
    "axis_index": 0,
    "axis_size": 0,
}

SHARD_MAP_NAMES = {"shard_map", "pmap", "xmap"}
PARTITION_SPEC_NAMES = {"P", "PartitionSpec"}

#: attribute reads that survive donation (aval metadata, not the buffer)
BENIGN_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}

#: scope boundaries: statements inside these run at a different time
#: than the block that defines them
_SCOPE_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)

#: decode hot path for the retrace rule (ISSUE 2: engine, batch,
#: kv_cache, ops)
RETRACE_ZONE_FILES = (
    "gofr_tpu/serving/engine.py",
    "gofr_tpu/serving/batch.py",
    "gofr_tpu/serving/stepplan.py",
    "gofr_tpu/serving/kv_cache.py",
    # the adapter-gather rides the donated DecodeState carry through the
    # batch.py kernels; the registry's table swaps must stay functional
    # (.at[].set) and shape-stable or every adapter upload would retrace
    "gofr_tpu/serving/lora.py",
)
RETRACE_ZONE_DIRS = ("gofr_tpu/ops/",)


def _dotted(node: ast.expr) -> str | None:
    """'jax.lax.psum' for Name/Attribute chains; None otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _terminal(node: ast.expr) -> str | None:
    """Last component of a call target: psum for jax.lax.psum."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _collective_axis_arg(call: ast.Call, name: str) -> ast.expr | None:
    for kw in call.keywords:
        if kw.arg == "axis_name":
            return kw.value
    pos = COLLECTIVES[name]
    if len(call.args) > pos:
        return call.args[pos]
    return None


def _literal_axes(node: ast.expr) -> list[tuple[str, int]]:
    """String-literal axis names inside an axis expression: 'tp',
    ('dp', 'fsdp'), {'ep'} — with line numbers."""
    out: list[tuple[str, int]] = []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.append((node.value, node.lineno))
    elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        for elt in node.elts:
            out.extend(_literal_axes(elt))
    return out


def _is_collective(call: ast.Call) -> str | None:
    """Collective name when the call is jax.lax.<c> / lax.<c> / <c>."""
    dotted = _dotted(call.func)
    if dotted is None:
        return None
    name = dotted.rsplit(".", 1)[-1]
    if name not in COLLECTIVES:
        return None
    if dotted in (name, f"lax.{name}", f"jax.lax.{name}"):
        return name
    return None


def _func_params(node: ast.FunctionDef | ast.AsyncFunctionDef) -> list[str]:
    a = node.args
    names = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
    names += [p.arg for p in a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def _positional_params(node: ast.FunctionDef | ast.AsyncFunctionDef) -> list[str]:
    a = node.args
    return [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]


def _int_elts(node: ast.expr | None) -> tuple[int, ...]:
    """(3, 4) / 3 / [3, 4] -> tuple of ints; () when unresolvable."""
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
        return tuple(out)
    return ()


def _str_elts(node: ast.expr | None) -> tuple[str, ...]:
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.append(elt.value)
        return tuple(out)
    return ()


@dataclasses.dataclass
class JitSpec:
    """A jit-wrapped callable the tree defines, as seen by the lint."""

    name: str
    path: str
    line: int
    params: tuple[str, ...]  # positional parameter names ('' when unknown)
    static_argnums: tuple[int, ...]
    static_argnames: tuple[str, ...]
    donate_argnums: tuple[int, ...]
    donate_argnames: tuple[str, ...]

    def donated_positions(self) -> tuple[int, ...]:
        pos = set(self.donate_argnums)
        for name in self.donate_argnames:
            if name in self.params:
                pos.add(self.params.index(name))
        return tuple(sorted(pos))

    def static_positions(self) -> tuple[int, ...]:
        pos = set(self.static_argnums)
        for name in self.static_argnames:
            if name in self.params:
                pos.add(self.params.index(name))
        return tuple(sorted(pos))


def _jit_call_kwargs(call: ast.Call) -> dict[str, ast.expr] | None:
    """kwargs of a jit(...) / partial(jax.jit, ...) expression, or None
    when the expression is not a jit wrapper."""
    dotted = _dotted(call.func)
    if dotted in ("jax.jit", "jit"):
        return {kw.arg: kw.value for kw in call.keywords if kw.arg}
    if dotted in ("partial", "functools.partial") and call.args:
        inner = _dotted(call.args[0])
        if inner in ("jax.jit", "jit"):
            return {kw.arg: kw.value for kw in call.keywords if kw.arg}
    return None


def _spec_from_decorated(
    fn: ast.FunctionDef | ast.AsyncFunctionDef, path: str
) -> JitSpec | None:
    for deco in fn.decorator_list:
        if isinstance(deco, ast.Call):
            kw = _jit_call_kwargs(deco)
        elif _dotted(deco) in ("jax.jit", "jit"):
            kw = {}
        else:
            continue
        if kw is None:
            continue
        return JitSpec(
            name=fn.name,
            path=path,
            line=fn.lineno,
            params=tuple(_positional_params(fn)),
            static_argnums=_int_elts(kw.get("static_argnums")),
            static_argnames=_str_elts(kw.get("static_argnames")),
            donate_argnums=_int_elts(kw.get("donate_argnums")),
            donate_argnames=_str_elts(kw.get("donate_argnames")),
        )
    return None


def _collect_jit_specs(sf: SourceFile) -> list[JitSpec]:
    """Every jit-wrapped callable in the file: decorated defs plus
    ``name = jax.jit(fn, ...)`` module-level assignments."""
    specs: list[JitSpec] = []
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            spec = _spec_from_decorated(node, sf.rel_path)
            if spec is not None:
                specs.append(spec)
        elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            dotted = _dotted(node.value.func)
            if dotted not in ("jax.jit", "jit"):
                continue
            if len(node.targets) != 1 or not isinstance(node.targets[0], ast.Name):
                continue
            kw = {k.arg: k.value for k in node.value.keywords if k.arg}
            specs.append(
                JitSpec(
                    name=node.targets[0].id,
                    path=sf.rel_path,
                    line=node.lineno,
                    params=(),
                    static_argnums=_int_elts(kw.get("static_argnums")),
                    static_argnames=_str_elts(kw.get("static_argnames")),
                    donate_argnums=_int_elts(kw.get("donate_argnums")),
                    donate_argnames=_str_elts(kw.get("donate_argnames")),
                )
            )
    return specs


# ---------------------------------------------------------------------------
# rule 1: mesh/collective axis-name consistency (cross-file)


class MeshAxisRule(Rule):
    """Collects the declared mesh vocabulary (AXIS_ORDER / literal Mesh
    constructions) across the tree, then checks every literal axis usage
    against it in finalize. Skipped entirely when the linted subset
    declares no mesh — a partial lint must not flood."""

    name = "mesh-axis-unknown"
    cross_file = True

    def __init__(self) -> None:
        self._declared: set[str] = set()
        self._usages: list[tuple[str, str, int, str]] = []  # axis, path, line, ctx

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        has_pspec = "PartitionSpec" in sf.source
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and tgt.id == "AXIS_ORDER":
                        self._declared.update(
                            a for a, _ in _literal_axes(node.value)
                        )
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan_axis_defaults(sf, node)
            if not isinstance(node, ast.Call):
                continue
            term = _terminal(node.func)
            if term == "Mesh":
                if len(node.args) >= 2:
                    self._declared.update(
                        a for a, _ in _literal_axes(node.args[1])
                    )
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        self._declared.update(
                            a for a, _ in _literal_axes(kw.value)
                        )
            elif term in PARTITION_SPEC_NAMES and has_pspec:
                for arg in node.args:
                    for axis, line in _literal_axes(arg):
                        self._usages.append(
                            (axis, sf.rel_path, line, "PartitionSpec axis")
                        )
            elif term in SHARD_MAP_NAMES:
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        for axis, line in _literal_axes(kw.value):
                            self._usages.append(
                                (axis, sf.rel_path, line, "shard_map axis binding")
                            )
            else:
                coll = _is_collective(node)
                if coll is not None:
                    axis_arg = _collective_axis_arg(node, coll)
                    if axis_arg is not None:
                        for axis, line in _literal_axes(axis_arg):
                            self._usages.append(
                                (axis, sf.rel_path, line, f"{coll} axis_name")
                            )
                    continue
                # generic axis=/axis_name= keywords on SPMD helpers
                for kw in node.keywords:
                    if kw.arg in ("axis", "axis_name") and isinstance(
                        kw.value, ast.Constant
                    ) and isinstance(kw.value.value, str):
                        self._usages.append(
                            (kw.value.value, sf.rel_path, kw.value.lineno,
                             f"{kw.arg}= keyword")
                        )
        return []

    def _scan_axis_defaults(
        self, sf: SourceFile, fn: ast.FunctionDef | ast.AsyncFunctionDef
    ) -> None:
        a = fn.args
        pos = a.posonlyargs + a.args
        for param, default in zip(pos[len(pos) - len(a.defaults):], a.defaults):
            if param.arg in ("axis", "axis_name") and isinstance(
                default, ast.Constant
            ) and isinstance(default.value, str):
                self._usages.append(
                    (default.value, sf.rel_path, default.lineno,
                     f"default of parameter '{param.arg}'")
                )
        for param, default in zip(a.kwonlyargs, a.kw_defaults):
            if default is not None and param.arg in ("axis", "axis_name") and (
                isinstance(default, ast.Constant)
                and isinstance(default.value, str)
            ):
                self._usages.append(
                    (default.value, sf.rel_path, default.lineno,
                     f"default of parameter '{param.arg}'")
                )

    def finalize(self) -> list[Finding]:
        if not self._declared:
            return []
        out = []
        for axis, path, line, ctx in self._usages:
            if axis not in self._declared:
                out.append(
                    Finding(
                        self.name, path, line,
                        f"axis '{axis}' ({ctx}) is not declared by the mesh "
                        f"(known axes: {', '.join(sorted(self._declared))}) — "
                        "a typo here compiles into a wrong collective or an "
                        "unbound-axis trace error",
                    )
                )
        return out


# ---------------------------------------------------------------------------
# rule 2: collectives outside any mapped context (per-file)


class _MappedCollector(ast.NodeVisitor):
    """Names of functions that run under shard_map/pmap in this file:
    passed directly, via functools.partial, or through a one-step
    ``fn = partial(target, ...)`` alias."""

    def __init__(self) -> None:
        self.mapped: set[str] = set()
        self.mapped_lambdas: set[int] = set()  # id() of Lambda nodes
        self._partial_alias: dict[str, str] = {}

    def visit_Assign(self, node: ast.Assign) -> None:
        if isinstance(node.value, ast.Call):
            dotted = _dotted(node.value.func)
            if dotted in ("partial", "functools.partial") and node.value.args:
                target = _terminal(node.value.args[0])
                if target:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            self._partial_alias[tgt.id] = target
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        if _terminal(node.func) in SHARD_MAP_NAMES and node.args:
            fn = node.args[0]
            if isinstance(fn, ast.Lambda):
                self.mapped_lambdas.add(id(fn))
            elif isinstance(fn, ast.Call) and _dotted(fn.func) in (
                "partial", "functools.partial"
            ) and fn.args:
                inner = _terminal(fn.args[0])
                if inner:
                    self.mapped.add(inner)
            else:
                name = _terminal(fn)
                if name:
                    self.mapped.add(name)
                    self.mapped.add(self._partial_alias.get(name, name))
        self.generic_visit(node)


class _CollectiveVisitor(ast.NodeVisitor):
    """Collective calls with their enclosing function/lambda stack."""

    def __init__(self) -> None:
        # stack entries: (name, params, ast node id)
        self.found: list[
            tuple[ast.Call, str, list[tuple[str, list[str], int]]]
        ] = []
        self._stack: list[tuple[str, list[str], int]] = []

    def _visit_func(self, node):
        self._stack.append((node.name, _func_params(node), id(node)))
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Lambda(self, node: ast.Lambda) -> None:
        params = [p.arg for p in node.args.posonlyargs + node.args.args]
        self._stack.append(("<lambda>", params, id(node)))
        self.generic_visit(node)
        self._stack.pop()

    def visit_Call(self, node: ast.Call) -> None:
        coll = _is_collective(node)
        if coll is not None:
            self.found.append((node, coll, list(self._stack)))
        self.generic_visit(node)


class CollectiveMappedRule(Rule):
    name = "collective-unmapped"

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        if "shard_map" not in sf.source and not any(
            c in sf.source for c in COLLECTIVES
        ):
            return []
        mapper = _MappedCollector()
        mapper.visit(sf.tree)
        visitor = _CollectiveVisitor()
        visitor.visit(sf.tree)
        out: list[Finding] = []
        for call, coll, stack in visitor.found:
            axis_arg = _collective_axis_arg(call, coll)
            if axis_arg is None:
                continue
            # axis received as a parameter: the caller binds it — the
            # *_sharded body convention; the wrapper is checked instead
            if isinstance(axis_arg, ast.Name) and any(
                axis_arg.id in params for _, params, _ in stack
            ):
                continue
            literals = _literal_axes(axis_arg)
            if not literals:
                continue  # computed axis: not statically resolvable
            if any(
                name in mapper.mapped or nid in mapper.mapped_lambdas
                for name, _, nid in stack
            ):
                continue
            axes = ", ".join(a for a, _ in literals)
            where = (
                f"function '{stack[-1][0]}'" if stack else "module scope"
            )
            out.append(
                Finding(
                    self.name, sf.rel_path, call.lineno,
                    f"{coll}('{axes}') in {where} has no enclosing "
                    "shard_map/pmap mapping that axis — outside a mapped "
                    "context the collective fails at trace time (or runs "
                    "on the wrong group); wrap in shard_map or take the "
                    "axis as a parameter bound by the mapped caller",
                )
            )
        return out


# ---------------------------------------------------------------------------
# rule 3: use-after-donation (cross-file)


def _assigned_dotted(stmt: ast.stmt) -> set[str]:
    """Dotted names (re)bound by an assignment statement's targets."""
    out: set[str] = set()
    targets: list[ast.expr] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    flat: list[ast.expr] = []
    while targets:
        t = targets.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            targets.extend(t.elts)
        else:
            flat.append(t)
    for t in flat:
        d = _dotted(t)
        if d:
            out.add(d)
    return out


def _name_events(node: ast.AST, tracked: str) -> list[tuple[str, int]]:
    """('load'|'store', line) events for ``tracked`` (a dotted name) in
    source order. A store to a strict dotted *prefix* (rebinding the root
    object) counts as a store; loads whose only consumer is a benign
    metadata attribute are skipped."""
    events: list[tuple[str, int]] = []

    def matches(expr: ast.expr) -> bool:
        return _dotted(expr) == tracked

    def prefix_store(expr: ast.expr) -> bool:
        d = _dotted(expr)
        return d is not None and tracked.startswith(d + ".")

    def walk(n: ast.AST, benign_parent: bool) -> None:
        if isinstance(n, _SCOPE_NODES):
            return  # nested def/class: executes at another time
        if isinstance(n, (ast.Name, ast.Attribute)):
            ctx = getattr(n, "ctx", None)
            if matches(n) or (
                isinstance(ctx, (ast.Store, ast.Del)) and prefix_store(n)
            ):
                if isinstance(ctx, (ast.Store, ast.Del)):
                    events.append(("store", n.lineno))
                elif not benign_parent:
                    events.append(("load", n.lineno))
                return  # don't descend into our own chain
        benign = isinstance(n, ast.Attribute) and n.attr in BENIGN_ATTRS
        # AST field order puts assignment targets BEFORE the value; the
        # value executes first (`cache = cache + 1` loads, then stores) —
        # emit events in execution order or the store masks the load
        if isinstance(n, ast.Assign):
            walk(n.value, benign)
            for t in n.targets:
                walk(t, benign)
            return
        if isinstance(n, (ast.AugAssign, ast.AnnAssign)):
            if getattr(n, "value", None) is not None:
                walk(n.value, benign)
            if isinstance(n, ast.AugAssign) and _dotted(n.target) == tracked:
                # the augmented target is read-then-written: x += 1 loads x
                events.append(("load", n.target.lineno))
            walk(n.target, benign)
            return
        for child in ast.iter_child_nodes(n):
            walk(child, benign)

    walk(node, False)
    return events


def _local_function_names(tree: ast.AST) -> set[str]:
    return {
        n.name
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }


def _header_exprs(stmt: ast.stmt) -> list[ast.expr]:
    """Expressions a compound statement evaluates BEFORE its blocks run
    (if/while tests, for iterables, with context managers)."""
    out: list[ast.expr] = []
    if isinstance(stmt, (ast.If, ast.While)):
        out.append(stmt.test)
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        out.append(stmt.iter)
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        out.extend(item.context_expr for item in stmt.items)
    subject = getattr(stmt, "subject", None)  # match (3.10+)
    if subject is not None:
        out.append(subject)
    return out


class DonationRule(Rule):
    """Registers every donating jit function in the tree, then flags
    loads of donated arguments after the donating call. Registry matches
    are by bare terminal name; a file defining its OWN non-donating
    function of that name shadows the registry there (no import-graph
    resolution — precision over recall at module boundaries).

    ALIAS tracking (the dispatch shape that escaped this rule and crashed
    the round-4 TPU engine bench with ``Array has been deleted
    (int32[32])``): a reference to the soon-donated buffer captured into
    another name BEFORE the donating call — a plain copy
    (``alias = x``) or a constructor capture (``rec = Inflight(x, ...)``)
    — reads the deleted buffer when loaded after the call, even though
    the donated name itself was correctly rebound. Captures are collected
    from the statements preceding the call in the same block, and loads
    of the alias (or any of its attributes) after the call are flagged
    until the alias is rebound."""

    name = "use-after-donation"
    cross_file = True

    def __init__(self) -> None:
        self._registry: dict[str, JitSpec] = {}
        self._files: list[tuple[str, ast.AST, set[str]]] = []

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        donating_here: set[str] = set()
        for spec in _collect_jit_specs(sf):
            if spec.donate_argnums or spec.donate_argnames:
                self._registry[spec.name] = spec
                donating_here.add(spec.name)
        if "(" in sf.source:  # every file with calls participates
            shadowed = _local_function_names(sf.tree) - donating_here
            self._files.append((sf.rel_path, sf.tree, shadowed))
        return []

    def finalize(self) -> list[Finding]:
        out: list[Finding] = []
        for rel_path, tree, shadowed in self._files:
            self._shadowed = shadowed
            self._check_blocks(rel_path, tree, out)
        return out

    def _donated_vars(self, call: ast.Call, spec: JitSpec) -> list[str]:
        donated: list[str] = []
        for pos in spec.donated_positions():
            if pos < len(call.args) and not isinstance(
                call.args[pos], ast.Starred
            ):
                d = _dotted(call.args[pos])
                if d:
                    donated.append(d)
        for kw in call.keywords:
            if kw.arg and kw.arg in spec.donate_argnames:
                d = _dotted(kw.value)
                if d:
                    donated.append(d)
        return donated

    def _check_blocks(self, rel_path: str, tree: ast.AST, out: list[Finding]) -> None:
        for node in ast.walk(tree):
            is_loop = isinstance(node, (ast.For, ast.AsyncFor, ast.While))
            loop_targets: set[str] = set()
            if isinstance(node, (ast.For, ast.AsyncFor)):
                # the iteration variable is rebound from the iterator each
                # pass — donating it is donating a FRESH buffer every time
                stack = [node.target]
                while stack:
                    t = stack.pop()
                    if isinstance(t, (ast.Tuple, ast.List)):
                        stack.extend(t.elts)
                    else:
                        d = _dotted(t)
                        if d:
                            loop_targets.add(d)
            for field in ("body", "orelse", "finalbody"):
                block = getattr(node, field, None)
                if isinstance(block, list) and block and isinstance(
                    block[0], ast.stmt
                ):
                    self._check_block(
                        rel_path, block, out,
                        in_loop=is_loop and field == "body",
                        loop_targets=loop_targets,
                    )

    def _donating_calls(self, stmt: ast.stmt) -> list[tuple[ast.Call, JitSpec]]:
        """Donating calls executed BY this statement — nested def/class
        bodies run at another time and are analyzed at their own block."""
        calls: list[tuple[ast.Call, JitSpec]] = []

        def walk(node: ast.AST) -> None:
            if isinstance(node, _SCOPE_NODES):
                return
            if isinstance(node, ast.Call):
                term = _terminal(node.func)
                if term in self._registry and term not in self._shadowed:
                    calls.append((node, self._registry[term]))
            for child in ast.iter_child_nodes(node):
                walk(child)

        walk(stmt)
        return calls

    def _check_block(
        self, rel_path: str, block: list[ast.stmt], out: list[Finding],
        *, in_loop: bool = False, loop_targets: set[str] | None = None,
    ) -> None:
        loop_targets = loop_targets or set()
        for i, stmt in enumerate(block):
            if hasattr(stmt, "body"):
                # compound statement (if/for/with/try): calls in its BLOCKS
                # are analyzed when those blocks are walked, where inner
                # rebinds (`if full: k = flush(k)`) are visible — scanning
                # them from out here would miss those and false-positive.
                # Calls in its HEADER (test/iter/context expr) belong to no
                # block, so handle them here: flag later reads unless the
                # compound rebinds the variable somewhere inside.
                for expr in _header_exprs(stmt):
                    for call, spec in self._donating_calls(expr):
                        for var in self._donated_vars(call, spec):
                            if any(
                                kind == "store"
                                for kind, _ in _name_events(stmt, var)
                            ):
                                continue
                            self._scan_after(
                                rel_path, block[i + 1:], var, spec,
                                call.lineno, out,
                            )
                continue
            for call, spec in self._donating_calls(stmt):
                donated = self._donated_vars(call, spec)
                if not donated:
                    continue
                rebound = _assigned_dotted(stmt)
                for var in donated:
                    # aliases captured BEFORE the call die with the buffer
                    # whether or not the donated name itself is rebound
                    for alias, cap_line in self._alias_captures(
                        block[:i], var
                    ):
                        self._scan_after_alias(
                            rel_path, block[i + 1:], alias, var, cap_line,
                            spec, call.lineno, out,
                        )
                    if var in rebound or any(
                        var.startswith(r + ".") for r in rebound
                    ):
                        continue  # x = f(x): the donation idiom
                    self._scan_after(
                        rel_path, block[i + 1:], var, spec, call.lineno, out
                    )
                    rebound_by_loop = var in loop_targets or any(
                        var.startswith(t + ".") for t in loop_targets
                    )
                    if in_loop and not rebound_by_loop and not (
                        self._stored_in_block(block, var)
                    ):
                        # the NEXT iteration re-reads the donated buffer
                        # through the call's own argument
                        out.append(
                            Finding(
                                self.name, rel_path, call.lineno,
                                f"'{var}' is donated to {spec.name}() inside "
                                "a loop and never rebound in the loop body — "
                                "the next iteration reads the deleted buffer "
                                "('Array has been deleted' on donating "
                                "backends); rebind the result or hoist the "
                                "call",
                            )
                        )

    @staticmethod
    def _alias_captures(
        preceding: list[ast.stmt], var: str
    ) -> list[tuple[str, int]]:
        """(alias, line) pairs: names assigned in the statements BEFORE the
        donating call whose value expression captures ``var`` — a direct
        copy, a tuple/list containing it, or a constructor/call argument
        (``rec = Inflight(x, ...)`` keeps a live reference to x's buffer).
        Captures later re-bound before the donating call drop out (the
        rebind sheds the reference)."""

        def captures(expr: ast.expr) -> bool:
            if isinstance(expr, (ast.Name, ast.Attribute)):
                return _dotted(expr) == var
            if isinstance(expr, ast.Call):
                return any(
                    captures(a) for a in expr.args
                    if not isinstance(a, ast.Starred)
                ) or any(
                    kw.value is not None and captures(kw.value)
                    for kw in expr.keywords
                )
            if isinstance(expr, (ast.Tuple, ast.List)):
                return any(captures(e) for e in expr.elts)
            return False

        found: dict[str, int] = {}
        for stmt in preceding:
            if not isinstance(stmt, ast.Assign):
                continue
            is_capture = captures(stmt.value)
            for t in stmt.targets:
                d = _dotted(t)
                if not d or d == var:
                    continue
                if is_capture:
                    found[d] = stmt.lineno
                else:
                    found.pop(d, None)  # re-bound: the reference is shed
        return list(found.items())

    def _scan_after_alias(
        self,
        rel_path: str,
        rest: list[ast.stmt],
        alias: str,
        var: str,
        cap_line: int,
        spec: JitSpec,
        call_line: int,
        out: list[Finding],
    ) -> None:
        """Flag the first load of ``alias`` (or any ``alias.<attr>`` chain)
        after the donating call, before the alias is rebound. Events come
        from ONE walker that matches the outermost alias-rooted node and
        never descends into its own chain — so ``rec.steps = 2`` is a
        store (the inner ``rec`` Name's Load ctx must NOT masquerade as a
        read of the captured buffer), in execution order (an Assign's
        value before its targets)."""
        events: list[tuple[str, int]] = []

        def walk(n: ast.AST) -> None:
            if isinstance(n, _SCOPE_NODES):
                return  # nested def/class: executes at another time
            if isinstance(n, (ast.Name, ast.Attribute)):
                d = _dotted(n)
                if d and (d == alias or d.startswith(alias + ".")
                          or alias.startswith(d + ".")):
                    ctx = getattr(n, "ctx", None)
                    if isinstance(ctx, (ast.Store, ast.Del)):
                        # exact/extension stores rebind or overwrite the
                        # alias; a strict-PREFIX store rebinds its root
                        events.append(("store", n.lineno))
                    elif d == alias or d.startswith(alias + "."):
                        events.append(("load", n.lineno))
                    return  # never descend into our own chain
            if isinstance(n, ast.Assign):
                walk(n.value)
                for t in n.targets:
                    walk(t)
                return
            if isinstance(n, (ast.AugAssign, ast.AnnAssign)):
                if getattr(n, "value", None) is not None:
                    walk(n.value)
                if isinstance(n, ast.AugAssign):
                    d = _dotted(n.target)
                    if d and (d == alias or d.startswith(alias + ".")):
                        # augmented target is read-then-written
                        events.append(("load", n.target.lineno))
                walk(n.target)
                return
            for child in ast.iter_child_nodes(n):
                walk(child)

        for stmt in rest:
            events.clear()
            walk(stmt)
            for kind, line in events:
                if kind == "store":
                    return
                out.append(
                    Finding(
                        self.name, rel_path, line,
                        f"'{alias}' (captured from '{var}' on line "
                        f"{cap_line}) aliases a buffer donated to "
                        f"{spec.name}() on line {call_line} and is read "
                        "after the donation — on donating backends this "
                        "raises 'Array has been deleted'; re-derive the "
                        "value from the call's outputs or capture after "
                        "the call",
                    )
                )
                return

    @staticmethod
    def _stored_in_block(block: list[ast.stmt], var: str) -> bool:
        return any(
            kind == "store"
            for stmt in block
            for kind, _ in _name_events(stmt, var)
        )

    def _scan_after(
        self,
        rel_path: str,
        rest: list[ast.stmt],
        var: str,
        spec: JitSpec,
        call_line: int,
        out: list[Finding],
    ) -> None:
        for stmt in rest:
            for kind, line in _name_events(stmt, var):
                if kind == "store":
                    return
                out.append(
                    Finding(
                        self.name, rel_path, line,
                        f"'{var}' was donated to {spec.name}() on line "
                        f"{call_line} (donate_argnums) and read again before "
                        "rebinding — on donating backends this raises 'Array "
                        "has been deleted'; rebind the result or drop the "
                        "donation",
                    )
                )
                return


# ---------------------------------------------------------------------------
# rule 4: retrace hazards in the decode hot path (per-file + call sites)


def _in_retrace_zone(rel_path: str) -> bool:
    if any(rel_path.endswith(f) for f in RETRACE_ZONE_FILES):
        return True
    return any(d in rel_path for d in RETRACE_ZONE_DIRS)


def _hazard_roots(test: ast.expr) -> list[tuple[str, int]]:
    """Root names whose runtime *value* the test depends on. Subtrees
    that are static under tracing are skipped: ``is (not) None``
    comparisons, isinstance/len/hasattr calls, and ``.shape``/``.ndim``/
    ``.dtype``/``.size`` attribute inspection."""
    roots: list[tuple[str, int]] = []

    STATIC_CALLS = {"isinstance", "len", "hasattr", "getattr", "type"}

    def walk(n: ast.AST) -> None:
        if isinstance(n, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops
        ):
            return
        if isinstance(n, ast.Call):
            if _terminal(n.func) in STATIC_CALLS:
                return
            # other calls: conservative — inspect their arguments
        if isinstance(n, ast.Attribute):
            if n.attr in BENIGN_ATTRS:
                return
            root = n
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name):
                roots.append((root.id, n.lineno))
            return
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
            roots.append((n.id, n.lineno))
            return
        for child in ast.iter_child_nodes(n):
            walk(child)

    walk(test)
    return roots


class _JitBodyChecker(ast.NodeVisitor):
    """Hazards inside one jit-decorated function."""

    def __init__(self, spec: JitSpec, fn: ast.AST, rel_path: str) -> None:
        self.spec = spec
        self.rel_path = rel_path
        static = set(spec.static_positions())
        self.traced = {
            p for i, p in enumerate(spec.params) if i not in static
        } - set(spec.static_argnames)
        self.findings: list[Finding] = []
        self._fn = fn

    def run(self) -> list[Finding]:
        for stmt in self._fn.body:  # type: ignore[attr-defined]
            self.visit(stmt)
        return self.findings

    def _check_test(self, node: ast.If | ast.While | ast.IfExp) -> None:
        for name, line in _hazard_roots(node.test):
            if name in self.traced:
                self.findings.append(
                    Finding(
                        "retrace-hazard", self.rel_path, line,
                        f"Python branch on traced parameter '{name}' inside "
                        f"@jit function {self.spec.name}() — forces "
                        "concretization (TracerBoolConversionError at best, "
                        "a per-request recompile at worst); use jnp.where/"
                        "lax.cond, or mark the parameter static",
                    )
                )
                break

    def visit_If(self, node: ast.If) -> None:
        self._check_test(node)
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._check_test(node)
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp) -> None:
        self._check_test(node)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        if _dotted(node.func) in ("int", "float", "bool") and node.args:
            arg = node.args[0]
            root = arg
            while isinstance(root, (ast.Attribute, ast.Subscript)):
                root = root.value
            if isinstance(root, ast.Name) and root.id in self.traced:
                self.findings.append(
                    Finding(
                        "retrace-hazard", self.rel_path, node.lineno,
                        f"{_dotted(node.func)}() concretizes traced parameter "
                        f"'{root.id}' inside @jit function "
                        f"{self.spec.name}() — a host sync per call and a "
                        "retrace per distinct value",
                    )
                )
        self.generic_visit(node)


_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)


class RetraceRule(Rule):
    """Per-request recompilation hazards in the decode hot path. Also
    cross-checks call sites of known jit functions for unhashable values
    in static positions (finalize)."""

    name = "retrace-hazard"
    cross_file = True  # the static-position call-site check in finalize

    def __init__(self) -> None:
        self._registry: dict[str, JitSpec] = {}
        self._zone_files: list[tuple[str, ast.AST, set[str]]] = []

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        specs = _collect_jit_specs(sf)
        static_here: set[str] = set()
        for spec in specs:
            if spec.static_argnums or spec.static_argnames:
                self._registry[spec.name] = spec
                static_here.add(spec.name)
        if not _in_retrace_zone(sf.rel_path):
            return []
        # a same-named local plain function shadows the registry here
        shadowed = _local_function_names(sf.tree) - static_here
        self._zone_files.append((sf.rel_path, sf.tree, shadowed))
        out: list[Finding] = []
        spec_by_line = {s.line: s for s in specs}
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                spec = spec_by_line.get(node.lineno)
                if spec is not None and spec.params:
                    out.extend(_JitBodyChecker(spec, node, sf.rel_path).run())
                    out.extend(self._check_static_defaults(sf, node, spec))
        out.extend(self._check_jit_in_body(sf))
        return out

    def _check_static_defaults(
        self, sf: SourceFile, fn: ast.FunctionDef | ast.AsyncFunctionDef,
        spec: JitSpec,
    ) -> list[Finding]:
        out = []
        a = fn.args
        pos = a.posonlyargs + a.args
        offset = len(pos) - len(a.defaults)
        static = set(spec.static_positions())
        for i, default in enumerate(a.defaults):
            idx = offset + i
            if idx in static and isinstance(default, _UNHASHABLE):
                out.append(
                    Finding(
                        self.name, sf.rel_path, default.lineno,
                        f"static parameter '{pos[idx].arg}' of @jit function "
                        f"{fn.name}() has an unhashable default — jit's "
                        "compile cache requires hashable statics (use a "
                        "tuple/frozenset)",
                    )
                )
        return out

    def _check_jit_in_body(self, sf: SourceFile) -> list[Finding]:
        """jax.jit(...) under a function body in a hot-path file: a fresh
        wrapper per call defeats the compile cache (decorators are
        evaluated at module scope and stay exempt)."""
        out: list[Finding] = []

        class V(ast.NodeVisitor):
            def __init__(self) -> None:
                self.depth = 0

            def _visit_func(self, node):
                for deco in node.decorator_list:
                    self.visit(deco)  # decorator runs in the outer scope
                self.depth += 1
                for stmt in node.body:
                    self.visit(stmt)
                self.depth -= 1

            visit_FunctionDef = _visit_func
            visit_AsyncFunctionDef = _visit_func

            def visit_Call(self, node: ast.Call) -> None:
                if self.depth > 0 and _dotted(node.func) in ("jax.jit", "jit"):
                    out.append(
                        Finding(
                            "retrace-hazard", sf.rel_path, node.lineno,
                            "jax.jit() called inside a hot-path function — "
                            "each call builds a fresh wrapper with an empty "
                            "compile cache (a retrace per request); hoist "
                            "the jit to module scope",
                        )
                    )
                self.generic_visit(node)

        V().visit(sf.tree)
        return out

    def finalize(self) -> list[Finding]:
        out: list[Finding] = []
        for rel_path, tree, shadowed in self._zone_files:
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                term = _terminal(node.func)
                if term in shadowed:
                    continue
                spec = self._registry.get(term or "")
                if spec is None:
                    continue
                for pos in spec.static_positions():
                    if pos < len(node.args) and isinstance(
                        node.args[pos], _UNHASHABLE
                    ):
                        out.append(
                            Finding(
                                self.name, rel_path, node.args[pos].lineno,
                                f"unhashable literal in static position {pos} "
                                f"of {spec.name}() — jit raises on unhashable "
                                "static arguments (pass a tuple, or make the "
                                "argument traced)",
                            )
                        )
                for kw in node.keywords:
                    if kw.arg in spec.static_argnames and isinstance(
                        kw.value, _UNHASHABLE
                    ):
                        out.append(
                            Finding(
                                self.name, rel_path, kw.value.lineno,
                                f"unhashable literal for static argument "
                                f"'{kw.arg}' of {spec.name}() — jit raises on "
                                "unhashable static arguments",
                            )
                        )
        return out


def shardcheck_rules() -> list[Rule]:
    return [
        MeshAxisRule(),
        CollectiveMappedRule(),
        DonationRule(),
        RetraceRule(),
    ]
