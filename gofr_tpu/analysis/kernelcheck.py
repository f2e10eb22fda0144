"""kernelcheck — device-contract analysis for the jitted kernel layer.

The serving data plane runs on unwritten contracts: ``decode_block_paged``
returns ONE packed ``int32 [B, steps+2]`` array whose columns the host
slices by offset, the donated ``DecodeState`` carry is constructed at
three independent sites that must agree field-for-field, and every
``shard_map``/``PartitionSpec`` pair must match the arrays it shards.
:mod:`gofr_tpu.analysis.kernel_contracts` makes those contracts a
committed table; this module makes drift from the table a lint failure
(ROADMAP items 2 and 3 rewrite exactly these layouts — against the
table, not against convention). Rule families:

- ``pack-layout-drift`` — kernel side: every contract entry with a
  declared packed layout must build it through the declared pack helper
  (and the helper's concatenate order must match the declared columns);
  host side: unpack sites (``engine._consume_block``, ``_spec_step``)
  may slice a ``_block_sync``-tainted packed array only at offsets the
  layout declares, binding names must match the column they read, and
  every declared scalar column must be consumed — so a kernel-side pack
  edit without a matching unpack edit fails loud.
- ``dtype-discipline`` — hot-zone dtype hygiene: dtype-less
  ``jnp.asarray``/``jnp.array`` of Python literals (weak-type promotion
  re-traces and upcasts), any 64-bit jnp dtype, and scatter/gather index
  ``arange`` with a non-int32 dtype.
- ``carry-field-drift`` — every DecodeState construction site (the
  dataclass, ``tree_flatten``, ``make_decode_state`` incl. per-field
  dtypes, ``admit_decode_state`` incl. full-field scatter coverage,
  engine's ``_pending_admit`` tuple arity) must agree with the declared
  carry spec.
- ``spec-rank-mismatch`` — ``shard_map`` in_specs arity vs the wrapped
  function's positional arity vs the immediate call's argument count,
  ``out_specs`` structure vs the returned tuple, and ``P(...)`` arity vs
  the parameter's declared rank (trailing ``# [B, S, H, D]`` comments).
- ``kernel-contract-coverage`` — the zone-drift audit: every module-level
  jitted def in the declared kernel files must carry a contract whose
  params / donation set / static set match the decorator, stale contract
  entries and vanished unpack-site functions fail the build.

The runtime twin (:mod:`gofr_tpu.analysis.kerneltrace`) ``eval_shape``\\ s
every contract entry and ``--check-kernel-table`` verifies the export
against the same table (:func:`check_kernel_table`).
"""

from __future__ import annotations

import ast
import re

from gofr_tpu.analysis import kernel_contracts as kc
from gofr_tpu.analysis.core import Finding, Rule, SourceFile

# --------------------------------------------------------------- helpers


def _terminal(node: ast.AST) -> str | None:
    """Last component of a Name/Attribute chain (``jax.jit`` -> ``jit``)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _dotted(node: ast.AST) -> str | None:
    """Full dotted name (``jnp.asarray``) or None for non-chains."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _int_const(node: ast.AST) -> int | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    return None


def _const_ints(node: ast.AST) -> tuple[int, ...] | None:
    """static_argnums/donate_argnums value: int or tuple of ints."""
    one = _int_const(node)
    if one is not None:
        return (one,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            v = _int_const(e)
            if v is None:
                return None
            out.append(v)
        return tuple(out)
    return None


def _const_strs(node: ast.AST) -> tuple[str, ...] | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, str)):
                return None
            out.append(e.value)
        return tuple(out)
    return None


def _positional_params(fn: ast.FunctionDef) -> list[str]:
    return [a.arg for a in fn.args.posonlyargs + fn.args.args]


def _all_params(fn: ast.FunctionDef) -> list[str]:
    return _positional_params(fn) + [a.arg for a in fn.args.kwonlyargs]


class JitInfo:
    """Parsed jit decoration of a module-level def."""

    def __init__(self, fn: ast.FunctionDef) -> None:
        self.jitted = False
        self.static: set[str] = set()
        self.donated: set[str] = set()
        pos = _positional_params(fn)
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            inner = None
            if isinstance(dec, ast.Call) and _terminal(dec.func) == "partial" \
                    and dec.args:
                inner = dec.args[0]
            if _terminal(target) == "jit" or (
                inner is not None and _terminal(inner) == "jit"
            ):
                self.jitted = True
            else:
                continue
            if not isinstance(dec, ast.Call):
                continue
            for kw in dec.keywords:
                nums = _const_ints(kw.value) or ()
                strs = _const_strs(kw.value) or ()
                if kw.arg == "static_argnums":
                    self.static.update(pos[i] for i in nums if i < len(pos))
                elif kw.arg == "donate_argnums":
                    self.donated.update(pos[i] for i in nums if i < len(pos))
                elif kw.arg == "static_argnames":
                    self.static.update(strs)
                elif kw.arg == "donate_argnames":
                    self.donated.update(strs)


def _find_def(tree: ast.AST, name: str) -> ast.FunctionDef | None:
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def _mentions(node: ast.AST, name: str) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id == name:
            return True
        if isinstance(sub, ast.Attribute) and sub.attr == name:
            return True
    return False


# ------------------------------------------------------ pack-layout-drift

_PACK_HELPERS = {"_pack_block": "block", "_pack_ragged": "ragged"}
_CASTS = {"int", "bool", "float", "asarray", "array"}


class PackLayoutRule(Rule):
    """Kernel-side pack construction and host-side packed-column slicing
    must both match the declared :data:`kernel_contracts.PACK_LAYOUTS`."""

    name = "pack-layout-drift"

    # ---- kernel side
    def _check_kernel_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        contracts = kc.contracts_for_file(sf.rel_path)
        for node in sf.tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            if node.name in _PACK_HELPERS:
                out.extend(self._check_helper(sf, node))
            c = contracts.get(node.name)
            if c is None or c.packed is None:
                continue
            called = {
                _terminal(n.func)
                for n in ast.walk(node)
                if isinstance(n, ast.Call)
            }
            if c.pack_helper:
                if c.pack_helper not in called:
                    out.append(Finding(
                        self.name, sf.rel_path, node.lineno,
                        f"kernel '{node.name}' declares packed layout "
                        f"'{c.packed}' but never calls its pack helper "
                        f"{c.pack_helper}() — the host unpack offsets "
                        "are pinned to that helper's column order",
                    ))
                for other, layout in _PACK_HELPERS.items():
                    if other != c.pack_helper and other in called:
                        out.append(Finding(
                            self.name, sf.rel_path, node.lineno,
                            f"kernel '{node.name}' (layout '{c.packed}') "
                            f"calls {other}() which packs layout "
                            f"'{layout}' — packed-column drift",
                        ))
            else:
                out.extend(self._check_inline_pack(sf, node, c))
        return out

    def _concat_elements(self, node: ast.AST) -> list[ast.expr] | None:
        """Elements of a ``jnp.concatenate([...], axis=1)`` call."""
        if not (isinstance(node, ast.Call)
                and _terminal(node.func) == "concatenate" and node.args):
            return None
        seq = node.args[0]
        if isinstance(seq, (ast.List, ast.Tuple)):
            return list(seq.elts)
        return None

    def _check_helper(
        self, sf: SourceFile, fn: ast.FunctionDef
    ) -> list[Finding]:
        """The pack helper's concatenate order IS the layout: element 0
        the token span, then one element per declared scalar column (the
        ragged helper wraps the block helper as its prefix)."""
        layout = kc.PACK_LAYOUTS[_PACK_HELPERS[fn.name]]
        elems = None
        for node in ast.walk(fn):
            elems = self._concat_elements(node)
            if elems is not None:
                break
        if elems is None:
            return [Finding(
                self.name, sf.rel_path, fn.lineno,
                f"pack helper {fn.name}() no longer builds its packed "
                "array with jnp.concatenate — the unpack sites slice "
                f"layout '{layout.name}' by column offset",
            )]
        out: list[Finding] = []
        prefix_helper = None
        if isinstance(elems[0], ast.Call):
            prefix_helper = _terminal(elems[0].func)
        if prefix_helper in _PACK_HELPERS:
            prefix = kc.PACK_LAYOUTS[_PACK_HELPERS[prefix_helper]]
            scalars = layout.scalars[len(prefix.scalars):]
            if layout.scalars[: len(prefix.scalars)] != prefix.scalars:
                out.append(Finding(
                    self.name, sf.rel_path, fn.lineno,
                    f"{fn.name}() extends {prefix_helper}() but layout "
                    f"'{layout.name}' does not start with layout "
                    f"'{prefix.name}'",
                ))
            tail = elems[1:]
        else:
            scalars = layout.scalars
            tail = elems[1:]
        if len(tail) != len(scalars):
            out.append(Finding(
                self.name, sf.rel_path, fn.lineno,
                f"{fn.name}() concatenates {len(tail)} scalar column(s); "
                f"layout '{layout.name}' declares "
                f"{len(scalars)}: {list(scalars)}",
            ))
            return out
        for i, (elem, col) in enumerate(zip(tail, scalars)):
            if not _mentions(elem, col):
                out.append(Finding(
                    self.name, sf.rel_path, elem.lineno,
                    f"{fn.name}() column {layout.span}+{i + len(layout.scalars) - len(scalars)} "
                    f"should carry '{col}' (layout '{layout.name}') but "
                    "the concatenated element never references it",
                ))
        return out

    def _check_inline_pack(
        self, sf: SourceFile, fn: ast.FunctionDef, c
    ) -> list[Finding]:
        """Spec kernels concat (out | n_accept) inline into ``packed``."""
        layout = kc.PACK_LAYOUTS[c.packed]
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign) and node.targets
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == "packed"):
                continue
            elems = self._concat_elements(node.value)
            if elems is None:
                continue
            out: list[Finding] = []
            tail = elems[1:]
            if len(tail) != len(layout.scalars):
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"kernel '{fn.name}' packs {len(tail)} scalar "
                    f"column(s); layout '{layout.name}' declares "
                    f"{len(layout.scalars)}: {list(layout.scalars)}",
                ))
                return out
            for i, (elem, col) in enumerate(zip(tail, layout.scalars)):
                if not _mentions(elem, col):
                    out.append(Finding(
                        self.name, sf.rel_path, elem.lineno,
                        f"kernel '{fn.name}' column {layout.span}+{i} "
                        f"should carry '{col}' but the packed element "
                        "never references it",
                    ))
            return out
        return [Finding(
            self.name, sf.rel_path, fn.lineno,
            f"kernel '{fn.name}' declares packed layout '{c.packed}' but "
            "no `packed = jnp.concatenate([...])` assignment builds it",
        )]

    # ---- host side
    def _classify(self, col: ast.expr, span_names: tuple[str, ...]):
        """Column-index shapes a packed-array subscript may take:
        ('span', delta) | ('neg', c) | 'tokens' | 'span_slice' |
        ('bad_slice', msg) | None (unrecognized)."""
        if isinstance(col, ast.Slice):
            if col.lower is None and col.upper is None:
                return ("bad_slice", "unbounded [:] slice spans the scalar tail")
            if (isinstance(col.upper, ast.UnaryOp)
                    and isinstance(col.upper.op, ast.USub)):
                c = _int_const(col.upper.operand)
                if c is not None:
                    return ("neg_slice", c)
            t = _terminal(col.upper) if col.upper is not None else None
            if t in span_names:
                return "span_slice"
            return None
        term = _terminal(col)
        if term in span_names:
            return ("span", 0)
        if isinstance(col, ast.BinOp) and isinstance(col.op, (ast.Add, ast.Sub)):
            lt = _terminal(col.left)
            d = _int_const(col.right)
            if lt in span_names and d is not None:
                return ("span", d if isinstance(col.op, ast.Add) else -d)
        if isinstance(col, ast.UnaryOp) and isinstance(col.op, ast.USub):
            c = _int_const(col.operand)
            if c is not None:
                return ("neg", c)
        if _int_const(col) is not None or isinstance(col, ast.Name):
            return "tokens"  # absolute / loop-variable token read
        return None

    def _binding_owner(self, name: str) -> str | None:
        for col, vocab in kc.COLUMN_BINDINGS.items():
            if name in vocab:
                return col
        return None

    def _check_unpack_site(
        self, sf: SourceFile, site: kc.UnpackSite
    ) -> list[Finding]:
        fn = _find_def(sf.tree, site.function)
        if fn is None:
            return []  # coverage rule reports the vanished function
        layout = kc.PACK_LAYOUTS[site.layout]
        out: list[Finding] = []
        tainted = {
            node.targets[0].id
            for node in ast.walk(fn)
            if isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Call)
            and _terminal(node.value.func) == "_block_sync"
        }
        if not tainted:
            return []

        def resolve(kind) -> str | None:
            """Scalar column a classified read lands on (None: token span)."""
            if kind == "tokens" or kind == "span_slice":
                return None
            if isinstance(kind, tuple) and kind[0] == "span":
                return layout.column_at(kind[1]) if kind[1] >= 0 else None
            if isinstance(kind, tuple) and kind[0] == "neg":
                c = kind[1]
                if c <= len(layout.scalars):
                    return layout.scalars[len(layout.scalars) - c]
                return None
            return None

        consumed: set[str] = set()
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Subscript)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in tainted):
                continue
            idx = node.slice
            col = idx.elts[-1] if isinstance(idx, ast.Tuple) and idx.elts \
                else idx
            kind = self._classify(col, site.span_names)
            if kind is None:
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"unrecognized packed-column index into layout "
                    f"'{site.layout}' — unpack sites must slice by the "
                    f"declared span symbol {site.span_names} or a "
                    "constant offset so drift stays checkable",
                ))
                continue
            if isinstance(kind, tuple) and kind[0] == "bad_slice":
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"{kind[1]} (layout '{site.layout}' has "
                    f"{len(layout.scalars)} scalar tail column(s))",
                ))
                continue
            if isinstance(kind, tuple) and kind[0] == "neg_slice":
                if kind[1] != len(layout.scalars):
                    out.append(Finding(
                        self.name, sf.rel_path, node.lineno,
                        f"token-span slice [:-{kind[1]}] but layout "
                        f"'{site.layout}' has {len(layout.scalars)} "
                        f"scalar tail column(s) "
                        f"({list(layout.scalars)}) — the span would "
                        "include scalar columns",
                    ))
                else:
                    consumed.add(layout.span_col)
                continue
            if isinstance(kind, tuple) and kind[0] == "span" \
                    and kind[1] >= 0 and resolve(kind) is None:
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"column {layout.span}+{kind[1]} is past layout "
                    f"'{site.layout}' (scalar tail: "
                    f"{list(layout.scalars)}) — kernel/unpack drift",
                ))
                continue
            colname = resolve(kind)
            if colname is not None:
                consumed.add(colname)
            else:
                consumed.add(layout.span_col)
        # binding-name cross-check: `name = cast(packed[row, col])`
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            val = node.value
            while isinstance(val, ast.Call) and len(val.args) == 1 \
                    and _terminal(val.func) in _CASTS:
                val = val.args[0]
            if not (isinstance(val, ast.Subscript)
                    and isinstance(val.value, ast.Name)
                    and val.value.id in tainted):
                continue
            idx = val.slice
            col = idx.elts[-1] if isinstance(idx, ast.Tuple) and idx.elts \
                else idx
            kind = self._classify(col, site.span_names)
            if kind is None or isinstance(kind, tuple) and kind[0] in (
                "bad_slice",
            ):
                continue
            colname = resolve(kind)
            target = node.targets[0].id
            owner = self._binding_owner(target)
            if owner is not None and colname is not None and owner != colname:
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"binding '{target}' reads packed column "
                    f"'{colname}' but its name belongs to column "
                    f"'{owner}' (layout '{site.layout}') — the kernel "
                    "pack order and this unpack site disagree",
                ))
            if owner is not None and colname is None and kind != "span_slice" \
                    and kind != "tokens":
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"binding '{target}' (column '{owner}') reads the "
                    f"token span of layout '{site.layout}'",
                ))
        missing = [c for c in layout.scalars if c not in consumed]
        if missing:
            out.append(Finding(
                self.name, sf.rel_path, fn.lineno,
                f"unpack site {site.function}() never consumes declared "
                f"column(s) {missing} of layout '{site.layout}' — a "
                "kernel-side layout change would go unnoticed here",
            ))
        return out

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        if kc.contracts_for_file(sf.rel_path):
            out.extend(self._check_kernel_file(sf))
        for site in kc.UNPACK_SITES:
            if site.file == sf.rel_path:
                out.extend(self._check_unpack_site(sf, site))
        return [
            f for f in out if not sf.is_suppressed(f.rule, f.line)
        ]


# ------------------------------------------------------- dtype-discipline

# Engine methods on the block dispatch/consume hot path: everything that
# builds device inputs or unpacks device outputs between block syncs.
ENGINE_HOT_FUNCS: frozenset[str] = frozenset({
    "_dispatch_decode", "_dispatch_ragged", "_spec_step",
    "_consume_block", "_make_device_state", "_block_sync",
})
_HOT_ZONE_FILES: tuple[str, ...] = kc.KERNEL_FILES + (
    "gofr_tpu/ops/sampling.py",
)
_WIDE_DTYPES = {"int64", "float64", "uint64", "complex128"}


class DtypeDisciplineRule(Rule):
    """Hot-zone dtype hygiene: no weak-type promotion from dtype-less
    ``jnp.asarray``/``jnp.array`` of Python literals (upcasts and
    re-traces), no 64-bit dtypes (x64 is globally off; a 64-bit request
    silently truncates or doubles HBM), and index ``arange`` stays int32."""

    name = "dtype-discipline"

    def _literal_arg(self, node: ast.expr) -> bool:
        if isinstance(node, ast.Constant) and isinstance(
            node.value, (int, float, bool)
        ):
            return True
        if isinstance(node, (ast.List, ast.Tuple)):
            return all(isinstance(e, ast.Constant) for e in node.elts)
        if isinstance(node, ast.Call) and _terminal(node.func) == "range":
            return True
        if isinstance(node, ast.ListComp):
            return True
        return False

    def _zone_nodes(self, sf: SourceFile):
        if sf.rel_path in _HOT_ZONE_FILES:
            yield from ast.walk(sf.tree)
            return
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.FunctionDef) \
                    and node.name in ENGINE_HOT_FUNCS:
                yield from ast.walk(node)

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        if sf.rel_path not in _HOT_ZONE_FILES \
                and sf.rel_path != "gofr_tpu/serving/engine.py":
            return []
        out: list[Finding] = []
        for node in self._zone_nodes(sf):
            if isinstance(node, ast.Attribute) and node.attr in _WIDE_DTYPES \
                    and _dotted(node) in {
                        f"jnp.{node.attr}", f"np.{node.attr}",
                        f"jax.numpy.{node.attr}", f"numpy.{node.attr}",
                    }:
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"64-bit dtype {_dotted(node)} in a kernel hot zone "
                    "— x64 is globally disabled (silent truncation) and "
                    "the device contract table pins 32-bit widths",
                ))
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if d in ("jnp.asarray", "jnp.array") and node.args \
                    and self._literal_arg(node.args[0]):
                has_dtype = len(node.args) > 1 or any(
                    kw.arg == "dtype" for kw in node.keywords
                )
                if not has_dtype:
                    out.append(Finding(
                        self.name, sf.rel_path, node.lineno,
                        f"dtype-less {d}() of a Python literal in a "
                        "kernel hot zone — weak-type promotion upcasts "
                        "downstream math and changes the traced "
                        "signature; pass an explicit dtype",
                    ))
            if d == "jnp.arange":
                for kw in node.keywords:
                    if kw.arg == "dtype" and _terminal(kw.value) in (
                        _WIDE_DTYPES | {"float32", "float16", "bfloat16"}
                    ):
                        out.append(Finding(
                            self.name, sf.rel_path, node.lineno,
                            "index arange with a non-int32 dtype in a "
                            "kernel hot zone — scatter/gather indices "
                            "are int32 by the device contract",
                        ))
        return [f for f in out if not sf.is_suppressed(f.rule, f.line)]


# ------------------------------------------------------ carry-field-drift


class CarryFieldDriftRule(Rule):
    """Every DecodeState construction/scatter site must agree with the
    declared carry spec (:data:`kernel_contracts.DECODE_STATE_FIELDS`):
    field set, ORDER, and dtypes — PR 15's ``adapter`` column had to be
    threaded through three constructors by hand; this makes a missed one
    a lint failure instead of a shape error on a TPU."""

    name = "carry-field-drift"

    _fields = tuple(n for n, _ in kc.DECODE_STATE_FIELDS)
    _dtypes = dict(kc.DECODE_STATE_FIELDS)

    def _check_classdef(self, sf: SourceFile, cls: ast.ClassDef):
        out: list[Finding] = []
        ann = [
            n.target.id
            for n in cls.body
            if isinstance(n, ast.AnnAssign) and isinstance(n.target, ast.Name)
        ]
        if tuple(ann) != self._fields:
            out.append(Finding(
                self.name, sf.rel_path, cls.lineno,
                f"{kc.CARRY_CLASS} fields {ann} != declared carry spec "
                f"{list(self._fields)} — update kernel_contracts."
                "DECODE_STATE_FIELDS and every construction site together",
            ))
        flat = _find_def(cls, "tree_flatten")
        if flat is not None:
            for node in ast.walk(flat):
                if not isinstance(node, ast.Return):
                    continue
                if not (isinstance(node.value, ast.Tuple) and node.value.elts):
                    continue
                children = node.value.elts[0]
                if not isinstance(children, ast.Tuple):
                    continue
                order = [
                    n.attr for n in children.elts
                    if isinstance(n, ast.Attribute)
                ]
                if tuple(order) != self._fields:
                    out.append(Finding(
                        self.name, sf.rel_path, node.lineno,
                        f"tree_flatten order {order} != declared carry "
                        f"spec {list(self._fields)} — the donated carry "
                        "pytree would silently permute",
                    ))
        return out

    def _check_make(self, sf: SourceFile, fn: ast.FunctionDef):
        """make_decode_state's DecodeState(...) call: per-field dtypes."""
        out: list[Finding] = []
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and _terminal(node.func) == kc.CARRY_CLASS):
                continue
            for i, arg in enumerate(node.args):
                if i >= len(self._fields):
                    break
                want = self._dtypes[self._fields[i]]
                if want == "key":
                    continue
                if isinstance(arg, ast.Call) \
                        and _terminal(arg.func) == "asarray" \
                        and len(arg.args) >= 2:
                    got = _terminal(arg.args[1])
                    if got is not None and got != want:
                        out.append(Finding(
                            self.name, sf.rel_path, arg.lineno,
                            f"carry field '{self._fields[i]}' uploaded "
                            f"as {got}; the declared carry dtype is "
                            f"{want}",
                        ))
        return out

    def _check_admit(self, sf: SourceFile, fn: ast.FunctionDef):
        """admit_decode_state must fold EVERY carry field: each one is
        either scattered or passed through from ``state.<field>``."""
        out: list[Finding] = []
        state_param = fn.args.args[0].arg if fn.args.args else "state"
        touched = {
            n.attr
            for n in ast.walk(fn)
            if isinstance(n, ast.Attribute)
            and isinstance(n.value, ast.Name)
            and n.value.id == state_param
            and n.attr in self._fields
        }
        missing = [f for f in self._fields if f not in touched]
        if missing:
            out.append(Finding(
                self.name, sf.rel_path, fn.lineno,
                f"admit_decode_state never references carry field(s) "
                f"{missing} of the donated state — an admission would "
                "drop them from the carry",
            ))
        return out

    def _check_ctor_calls(self, sf: SourceFile):
        out: list[Finding] = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            if _terminal(node.func) != kc.CARRY_CLASS:
                continue
            if any(isinstance(a, ast.Starred) for a in node.args):
                continue  # tree_unflatten's cls(*children)
            n_args = len(node.args) + len(node.keywords)
            bad_kw = [
                kw.arg for kw in node.keywords
                if kw.arg is not None and kw.arg not in self._fields
            ]
            if n_args != len(self._fields) or bad_kw:
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"{kc.CARRY_CLASS}(...) constructed with {n_args} of "
                    f"{len(self._fields)} declared carry fields"
                    + (f" (unknown: {bad_kw})" if bad_kw else "")
                    + " — every construction site must bind the full "
                    "field set explicitly (carry-field drift)",
                ))
        return out

    def _check_pending_admit(self, sf: SourceFile):
        out: list[Finding] = []
        arity = len(kc.ADMIT_TUPLE_FIELDS)
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Subscript) \
                    and _terminal(node.targets[0].value) \
                    == kc.ADMIT_TUPLE_ATTR:
                if isinstance(node.value, ast.Tuple) \
                        and len(node.value.elts) != arity:
                    out.append(Finding(
                        self.name, sf.rel_path, node.lineno,
                        f"{kc.ADMIT_TUPLE_ATTR} entry built with "
                        f"{len(node.value.elts)} element(s); the declared "
                        f"admit tuple is {list(kc.ADMIT_TUPLE_FIELDS)}",
                    ))
            if isinstance(node, ast.AnnAssign) \
                    and isinstance(node.target, ast.Attribute) \
                    and node.target.attr == kc.ADMIT_TUPLE_ATTR:
                for sub in ast.walk(node.annotation):
                    if isinstance(sub, ast.Subscript) \
                            and _terminal(sub.value) == "tuple" \
                            and isinstance(sub.slice, ast.Tuple) \
                            and len(sub.slice.elts) != arity:
                        out.append(Finding(
                            self.name, sf.rel_path, node.lineno,
                            f"{kc.ADMIT_TUPLE_ATTR} annotated as a "
                            f"{len(sub.slice.elts)}-tuple; the declared "
                            f"admit tuple has {arity} fields "
                            f"{list(kc.ADMIT_TUPLE_FIELDS)}",
                        ))
        return out

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        if sf.rel_path == kc.CARRY_FILE:
            for node in sf.tree.body:
                if isinstance(node, ast.ClassDef) \
                        and node.name == kc.CARRY_CLASS:
                    out.extend(self._check_classdef(sf, node))
                if isinstance(node, ast.FunctionDef):
                    if node.name == "make_decode_state":
                        out.extend(self._check_make(sf, node))
                    if node.name == "admit_decode_state":
                        out.extend(self._check_admit(sf, node))
        out.extend(self._check_ctor_calls(sf))
        if sf.rel_path == kc.ADMIT_TUPLE_FILE:
            out.extend(self._check_pending_admit(sf))
        return [f for f in out if not sf.is_suppressed(f.rule, f.line)]


# ------------------------------------------------------ spec-rank-mismatch

_SHAPE_COMMENT = re.compile(r"#\s*\[([^\]]+)\]")


class SpecRankRule(Rule):
    """``shard_map`` plumbing consistency: in_specs arity vs the wrapped
    function's positional arity vs the immediate call's argument count,
    out_specs structure vs the returned tuple, and ``P(...)`` arity vs
    each parameter's declared rank (trailing shape comments) — the item-3
    TP engine multiplies these sites; rank drift here is a runtime
    sharding error only a TPU run would catch."""

    name = "spec-rank-mismatch"

    def _spec_arity(self, node: ast.expr, env: dict[str, int]) -> int | None:
        """Arity of a PartitionSpec expression (None: unresolvable)."""
        if isinstance(node, ast.Call) and _terminal(node.func) == "P":
            return len(node.args)
        if isinstance(node, ast.Name):
            return env.get(node.id)
        return None

    def _param_rank(self, sf: SourceFile, fn: ast.FunctionDef,
                    index: int) -> int | None:
        """Rank declared by the trailing ``# [B, S, H, D]`` comment on
        the parameter's signature line."""
        pos = fn.args.posonlyargs + fn.args.args
        if index >= len(pos):
            return None
        lines = sf.source.splitlines()
        ln = getattr(pos[index], "lineno", None)
        if ln is None or ln > len(lines):
            return None
        m = _SHAPE_COMMENT.search(lines[ln - 1])
        if m is None:
            return None
        return len([p for p in m.group(1).split(",") if p.strip()])

    def _resolve_inner(
        self, defs: dict[str, ast.FunctionDef],
        assigns: dict[str, ast.expr], node: ast.expr,
    ) -> tuple[ast.FunctionDef | None, int]:
        """The wrapped per-device function and how many of its positional
        params a ``functools.partial`` already bound."""
        bound = 0
        for _ in range(4):  # follow name -> partial -> name chains
            if isinstance(node, ast.Name):
                if node.id in defs:
                    return defs[node.id], bound
                nxt = assigns.get(node.id)
                if nxt is None:
                    return None, bound
                node = nxt
                continue
            if isinstance(node, ast.Call) \
                    and _terminal(node.func) == "partial" and node.args:
                bound += len(node.args) - 1
                node = node.args[0]
                continue
            return None, bound
        return None, bound

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        defs: dict[str, ast.FunctionDef] = {}
        assigns: dict[str, ast.expr] = {}
        spec_env: dict[str, int] = {}
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.FunctionDef):
                defs.setdefault(node.name, node)
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                assigns.setdefault(node.targets[0].id, node.value)
                a = self._spec_arity(node.value, {})
                if a is not None:
                    spec_env.setdefault(node.targets[0].id, a)
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and _terminal(node.func) == "shard_map"
                    and node.args):
                continue
            kw = {k.arg: k.value for k in node.keywords}
            in_specs = kw.get("in_specs")
            out_specs = kw.get("out_specs")
            inner, bound = self._resolve_inner(defs, assigns, node.args[0])
            n_in = None
            if isinstance(in_specs, (ast.Tuple, ast.List)):
                n_in = len(in_specs.elts)
            elif in_specs is not None and self._spec_arity(
                in_specs, spec_env
            ) is not None:
                n_in = 1
            if inner is not None and n_in is not None:
                n_pos = len(inner.args.posonlyargs + inner.args.args) - bound
                if n_pos != n_in:
                    out.append(Finding(
                        self.name, sf.rel_path, node.lineno,
                        f"shard_map in_specs has {n_in} spec(s) but "
                        f"'{inner.name}' takes {n_pos} positional "
                        "array(s) — the mapping would mis-shard or fail "
                        "only at trace time",
                    ))
                elif isinstance(in_specs, (ast.Tuple, ast.List)):
                    for i, spec in enumerate(in_specs.elts):
                        arity = self._spec_arity(spec, spec_env)
                        rank = self._param_rank(sf, inner, i + bound)
                        if arity is not None and rank is not None \
                                and arity > rank:
                            out.append(Finding(
                                self.name, sf.rel_path, spec.lineno,
                                f"in_specs[{i}] has {arity} axes but "
                                f"'{inner.name}' declares its parameter "
                                f"as rank {rank} — PartitionSpec arity "
                                "exceeds the array rank",
                            ))
            if inner is not None and out_specs is not None:
                rets = [
                    n.value for n in ast.walk(inner)
                    if isinstance(n, ast.Return) and n.value is not None
                ]
                arities = {
                    len(r.elts) if isinstance(r, ast.Tuple) else 1
                    for r in rets
                }
                if len(arities) == 1:
                    r_arity = arities.pop()
                    o_arity = len(out_specs.elts) if isinstance(
                        out_specs, (ast.Tuple, ast.List)
                    ) else 1
                    if r_arity != o_arity:
                        out.append(Finding(
                            self.name, sf.rel_path, node.lineno,
                            f"shard_map out_specs declares {o_arity} "
                            f"output spec(s) but '{inner.name}' returns "
                            f"{r_arity} value(s) — the output pytree "
                            "structure would not match",
                        ))
            # immediate-call arity: shard_map(...)(a, b, c)
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Call)
                    and _terminal(node.func.func) == "shard_map"):
                continue
            kw = {k.arg: k.value for k in node.func.keywords}
            in_specs = kw.get("in_specs")
            if not isinstance(in_specs, (ast.Tuple, ast.List)):
                continue
            if any(isinstance(a, ast.Starred) for a in node.args):
                continue
            if len(node.args) != len(in_specs.elts):
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"shard_map called with {len(node.args)} array(s) "
                    f"but in_specs declares {len(in_specs.elts)} — "
                    "argument/spec drift",
                ))
        return [f for f in out if not sf.is_suppressed(f.rule, f.line)]


# ------------------------------------------------- kernel-contract-coverage


class KernelContractCoverageRule(Rule):
    """The zone-drift audit for the contract table: every module-level
    jitted def in :data:`kernel_contracts.KERNEL_FILES` needs a declared
    contract matching its params / donation / static sets; contracts and
    unpack sites pointing at vanished functions fail too."""

    name = "kernel-contract-coverage"
    cross_file = True

    def __init__(
        self,
        anchor: str | None = "gofr_tpu/serving/engine.py",
        anchor_symbol: str = "ServingEngine",
    ) -> None:
        # a fixture tree can materialize files NAMED like the kernel
        # files (the sibling analyzers' suites do); requiring the
        # anchor file to also DEFINE the marker symbol pins the whole
        # rule to the real tree — same gate as deadlinecheck's
        # ZoneDriftRule. Tests pass anchor=None to un-gate.
        self._anchor = anchor
        self._anchor_symbol = anchor_symbol
        self._anchor_seen = anchor is None
        self._buffered: list[Finding] = []
        self._seen_kernel_files: dict[str, set[str]] = {}
        self._seen_defs: dict[str, set[str]] = {}

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        if (self._anchor is not None
                and sf.rel_path.endswith(self._anchor)
                and any(isinstance(n, ast.ClassDef)
                        and n.name == self._anchor_symbol
                        for n in sf.tree.body)):
            self._anchor_seen = True
        interesting = sf.rel_path in kc.KERNEL_FILES or any(
            u.file == sf.rel_path for u in kc.UNPACK_SITES
        )
        if not interesting:
            return []
        self._seen_defs[sf.rel_path] = {
            n.name for n in ast.walk(sf.tree)
            if isinstance(n, ast.FunctionDef)
        }
        if sf.rel_path not in kc.KERNEL_FILES:
            return []
        out: list[Finding] = []
        contracts = kc.contracts_for_file(sf.rel_path)
        jitted: set[str] = set()
        for node in sf.tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            info = JitInfo(node)
            if not info.jitted:
                continue
            jitted.add(node.name)
            c = contracts.get(node.name)
            if c is None:
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"jitted kernel entry '{node.name}' has no declared "
                    "contract — add it to kernel_contracts.KERNELS "
                    "(params, donation set, packed layout, return "
                    "signatures) before it ships",
                ))
                continue
            params = tuple(_all_params(node))
            if params != c.params:
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"kernel '{node.name}' signature {list(params)} != "
                    f"declared contract params {list(c.params)}",
                ))
            if info.donated != set(c.donated):
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"kernel '{node.name}' donates "
                    f"{sorted(info.donated)} but the contract declares "
                    f"{sorted(c.donated)} — donated-carry drift (an "
                    "undeclared donation is a use-after-free the moment "
                    "a host reference survives the call)",
                ))
            if info.static != set(c.static):
                out.append(Finding(
                    self.name, sf.rel_path, node.lineno,
                    f"kernel '{node.name}' static args "
                    f"{sorted(info.static)} != declared "
                    f"{sorted(c.static)} — retrace/semantics drift",
                ))
        self._seen_kernel_files[sf.rel_path] = jitted
        # buffered until finalize: findings only count on the real tree
        self._buffered.extend(
            f for f in out if not sf.is_suppressed(f.rule, f.line)
        )
        return []

    def finalize(self) -> list[Finding]:
        if not self._anchor_seen:
            self._buffered = []
            self._seen_kernel_files = {}
            self._seen_defs = {}
            return []
        out: list[Finding] = list(self._buffered)
        self._buffered = []
        for rel, jitted in self._seen_kernel_files.items():
            for c in kc.KERNELS:
                if c.file == rel and c.name not in jitted:
                    out.append(Finding(
                        self.name, rel, 1,
                        f"contract table entry '{c.name}' matches no "
                        f"jitted def in {rel} — stale contract (the "
                        "kernel moved or was renamed; update "
                        "kernel_contracts.KERNELS)",
                    ))
        for site in kc.UNPACK_SITES:
            defs = self._seen_defs.get(site.file)
            if defs is not None and site.function not in defs:
                out.append(Finding(
                    self.name, site.file, 1,
                    f"declared unpack site '{site.function}' no longer "
                    f"exists in {site.file} — kernel_contracts."
                    "UNPACK_SITES drifted from the tree",
                ))
        self._seen_kernel_files = {}
        self._seen_defs = {}
        self._anchor_seen = self._anchor is None
        return out


def kernelcheck_rules() -> list[Rule]:
    return [
        PackLayoutRule(),
        DtypeDisciplineRule(),
        CarryFieldDriftRule(),
        SpecRankRule(),
        KernelContractCoverageRule(),
    ]


# ------------------------------------------------ static <-> runtime twin


def _eval_dim(expr: str, env: dict[str, int]) -> int | None:
    try:
        return int(eval(expr, {"__builtins__": {}}, dict(env)))  # noqa: S307
    except NameError:
        return None
    except Exception:
        return None


def check_kernel_table(runtime: dict, contracts=None) -> list[str]:
    """Verify a runtime export (:mod:`gofr_tpu.analysis.kerneltrace` —
    the eval_shape matrix or the live engine observer) against the
    static contract table. Returns human-readable divergences; empty
    means the runtime twin and the committed table agree."""
    contracts = contracts if contracts is not None else kc.CONTRACTS
    div: list[str] = []
    exercised: set[str] = set()
    for v in runtime.get("violations", []):
        div.append(f"runtime violation: {v}")
    for case in runtime.get("cases", []):
        name = case.get("kernel", "?")
        label = f"{name}[{case.get('variant', '?')}]"
        c = contracts.get(name)
        if c is None:
            div.append(
                f"{label}: observed kernel has no declared contract "
                "(kernel_contracts.KERNELS)"
            )
            continue
        exercised.add(name)
        env: dict[str, int] = {}
        for k, v in case.get("statics", {}).items():
            if isinstance(v, bool):
                continue
            if isinstance(v, int):
                env[k] = v
        inputs = case.get("inputs", {})
        for param, sym in c.arg_shapes:
            sig = inputs.get(param)
            if not sig or len(sig.get("leaves", [])) != 1:
                continue
            dims = sig["leaves"][0][0]
            syms = [s.strip() for s in sym.split(",")]
            if len(syms) != len(dims):
                div.append(
                    f"{label}: input '{param}' rank {len(dims)} != "
                    f"declared '{sym}'"
                )
                continue
            for s, d in zip(syms, dims):
                if s == "_":
                    continue
                if s.isdigit():
                    if int(s) != d:
                        div.append(
                            f"{label}: input '{param}' dim {s} observed "
                            f"as {d}"
                        )
                elif s in env:
                    if env[s] != d:
                        div.append(
                            f"{label}: dim symbol {s} bound to {env[s]} "
                            f"but input '{param}' carries {d}"
                        )
                else:
                    env[s] = d
        outs = case.get("outputs", [])
        if len(outs) != len(c.returns):
            div.append(
                f"{label}: kernel returned {len(outs)} output(s); the "
                f"contract declares {len(c.returns)}"
            )
            continue
        for ret, got in zip(c.returns, outs):
            if ret.like:
                want = inputs.get(ret.like)
                if want is None:
                    div.append(
                        f"{label}: passthrough output '{ret.name}' has "
                        f"no recorded input '{ret.like}' to compare "
                        "against"
                    )
                elif got != want:
                    div.append(
                        f"{label}: output '{ret.name}' signature {got} "
                        f"!= its declared twin input '{ret.like}' "
                        f"{want} — donated-carry drift"
                    )
                continue
            leaves = got.get("leaves", [])
            if len(leaves) != 1:
                div.append(
                    f"{label}: output '{ret.name}' is a "
                    f"{len(leaves)}-leaf pytree; the contract declares "
                    "one array"
                )
                continue
            shape, dtype = leaves[0]
            exprs = [s.strip() for s in (ret.shape or "").split(",")]
            if len(exprs) != len(shape):
                div.append(
                    f"{label}: output '{ret.name}' rank {len(shape)} != "
                    f"declared '{ret.shape}'"
                )
                continue
            for expr, d in zip(exprs, shape):
                want_d = _eval_dim(expr, env)
                if want_d is None:
                    if expr.isidentifier():
                        env[expr] = d  # bind-on-first-use, then pinned
                        continue
                    div.append(
                        f"{label}: output '{ret.name}' dim '{expr}' "
                        "uses symbols the case never bound"
                    )
                elif want_d != d:
                    div.append(
                        f"{label}: output '{ret.name}' dim '{expr}' = "
                        f"{want_d} by the contract, observed {d}"
                    )
            if ret.dtype is not None and dtype != ret.dtype:
                div.append(
                    f"{label}: output '{ret.name}' dtype {dtype}; the "
                    f"contract declares {ret.dtype}"
                )
    if runtime.get("mode") == "matrix":
        required = {
            k.name for k in kc.KERNELS if k.file == kc.CARRY_FILE
        }
        for missing in sorted(required - exercised):
            div.append(
                f"matrix coverage: contract entry '{missing}' was never "
                "exercised by the eval_shape matrix"
            )
    return div
