"""Reading HLO text (``lowered.compiler_ir("hlo").as_hlo_text()``, or a
compiled program's ``as_text()``) in the tests: which computation holds
which instruction, and what runs only inside a branch of a ``conditional``."""

from __future__ import annotations

import re

# a header is not indented and ends in an open brace; a compiled program's names its parameters in between
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) (?:\(.*)?\{$")
_CALLEE = re.compile(r"(?:to_apply|body|condition|calls|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def computations(hlo: str) -> tuple[dict[str, list[str]], str]:
    """HLO text -> each computation's instruction lines, and the entry's name."""
    comps: dict[str, list[str]] = {}
    entry = name = None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
            if line.startswith("ENTRY"):
                entry = name
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    assert entry is not None
    return comps, entry


def reached_outside_a_branch(comps: dict[str, list[str]], entry: str) -> set[str]:
    """Computations that run whenever the program does: reached from the
    entry without passing into a branch of a ``conditional``."""
    seen, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo.extend(c for c in _CALLEE.findall(line) if "conditional(" not in line or c not in branches(line))
    return seen


def branches(line: str) -> set[str]:
    named = {c.strip().lstrip("%") for m in _BRANCHES.findall(line) for c in m.split(",")}
    return named | set(re.findall(r"(?:true|false)_computation=%?([\w.\-]+)", line))


def holds(comps: dict[str, list[str]], op: str, having: str = "") -> set[str]:
    """The computations with an ``op`` instruction whose line holds ``having`` (a shape, say)."""
    return {name for name, lines in comps.items()
            if any(re.search(rf"\b{op}\(", line) and having in line for line in lines)}


def operand_closure(lines: list[str], start: str) -> list[str]:
    """The opcodes an instruction's value depends on, inside its computation."""
    defs = {}
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([a-z][\w\-]*)\((.*)", line)
        if m:
            defs[m.group(1)] = (m.group(2), re.findall(r"%?([A-Za-z_][\w.\-]*)", m.group(3).split("), ")[0]))
    ops, todo, seen = [], [start], set()
    while todo:
        name = todo.pop()
        if name in seen or name not in defs:
            continue
        seen.add(name)
        ops.append(defs[name][0])
        todo.extend(defs[name][1])
    return ops
