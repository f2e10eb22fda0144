"""From a profiler trace to numbers. The reduction is code kept with the
benchmark, so every PR computes the same number in the same way.

A trace is reduced first to a flat list of events ``(plane, line, name,
start_ns, dur_ns)`` (``load_xplane``); everything else works on that list,
so the arithmetic is tested on a small recording
(``benchmarks/testdata/``) with no profiler and no chip.

What the v5e trace looks like (looked at by hand, PR 25 — see PERF.md):
a device plane ``/device:TPU:<n>`` has a line ``XLA Modules`` with one
event per execution of a jitted program, named ``jit_<function>(<id>)``,
and a line ``XLA Ops`` with one event per HLO operation inside it, named
by the whole instruction text (``%paged_decode_attention.5 = bf16[...]
custom-call(...)`` is the Mosaic paged kernel; the ``while`` ops enclose
the scanned layers). ``Async XLA Ops`` holds copy-start/-done pairs and is
not counted as busy. The host's ``TraceAnnotation`` events are on the planes ``/host:*``.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Iterable, NamedTuple

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, keep_host: Iterable[str] = ("bench.",)) -> list[Event]:
    """Device-plane events, and the host events whose names start with one
    of ``keep_host`` (the harness's own markers)."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    keep = tuple(keep_host)
    out: list[Event] = []
    for plane in data.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(keep):
                    out.append(Event(plane.name, line.name, ev.name,
                                     int(ev.start_ns), int(ev.duration_ns)))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def save_events(events: list[Event], path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as fh:
        json.dump([list(e) for e in events], fh)


def load_events(path: str) -> list[Event]:
    with gzip.open(path, "rt", encoding="utf-8") as fh:
        return [Event(*row) for row in json.load(fh)]


def program_name(event_name: str) -> str:
    """``jit_decode_block_paged(123)`` -> ``decode_block_paged``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction,
    ``%fusion.3 = bf16[...] fusion(...)``: keep the instruction's own
    name, so that an operand's name is never mistaken for it."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_planes(events: list[Event]) -> list[str]:
    return sorted({e.plane for e in events if is_device_plane(e.plane)})


def clip(events: Iterable[Event], t0_ns: int, t1_ns: int) -> list[Event]:
    """Events cut to [t0, t1): an event that straddles an edge keeps the
    part inside."""
    out = []
    for e in events:
        a, b = max(e.start_ns, t0_ns), min(e.start_ns + e.dur_ns, t1_ns)
        if b > a:
            out.append(e._replace(start_ns=a, dur_ns=b - a))
    return out


def union_ns(intervals: Iterable[tuple[int, int]]) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def busy_seconds(events: list[Event], t0_ns: int, t1_ns: int) -> float:
    """Seconds in which an operation ran on the device: the union of the
    ``XLA Ops`` intervals per device plane, averaged over the planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = 0
    for plane in planes:
        ops = clip((e for e in events if e.plane == plane and e.line == OPS_LINE), t0_ns, t1_ns)
        total += union_ns((e.start_ns, e.start_ns + e.dur_ns) for e in ops)
    return total / len(planes) / 1e9


def program_times(events: list[Event], t0_ns: int, t1_ns: int) -> dict[str, dict[str, float]]:
    """Per jitted program: executions that STARTED in the window and the
    device seconds of the parts inside it."""
    out: dict[str, dict[str, float]] = {}
    for e in events:
        if e.line != MODULE_LINE or not is_device_plane(e.plane):
            continue
        a, b = max(e.start_ns, t0_ns), min(e.start_ns + e.dur_ns, t1_ns)
        if b <= a:
            continue
        slot = out.setdefault(program_name(e.name), {"count": 0, "seconds": 0.0})
        slot["seconds"] += (b - a) / 1e9
        if t0_ns <= e.start_ns < t1_ns:
            slot["count"] += 1
    return out


def op_times(events: list[Event], t0_ns: int, t1_ns: int) -> dict[str, dict[str, float]]:
    """Per HLO operation name on the ``XLA Ops`` line: count and seconds."""
    out: dict[str, dict[str, float]] = {}
    for e in clip((e for e in events if e.line == OPS_LINE and is_device_plane(e.plane)), t0_ns, t1_ns):
        slot = out.setdefault(op_name(e.name), {"count": 0, "seconds": 0.0})
        slot["count"] += 1
        slot["seconds"] += e.dur_ns / 1e9
    return out


def leaf_op_times(events: list[Event], t0_ns: int, t1_ns: int) -> dict[str, dict[str, float]]:
    """Like :func:`op_times`, but an operation that encloses others (a
    ``while`` around a scanned layer body) is charged only the time its
    children do not cover, so the parts add up to the busy time."""
    per_plane: dict[str, list[Event]] = {}
    for e in clip((e for e in events if e.line == OPS_LINE and is_device_plane(e.plane)), t0_ns, t1_ns):
        per_plane.setdefault(e.plane, []).append(e)
    out: dict[str, dict[str, float]] = {}
    for evs in per_plane.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        stack: list[list[Any]] = []  # [event, self_ns]

        def close(upto: int) -> None:
            while stack and stack[-1][0].start_ns + stack[-1][0].dur_ns <= upto:
                ev, self_ns = stack.pop()
                slot = out.setdefault(op_name(ev.name), {"count": 0, "seconds": 0.0})
                slot["count"] += 1
                slot["seconds"] += max(self_ns, 0) / 1e9

        for e in evs:
            close(e.start_ns)
            if stack:
                stack[-1][1] -= e.dur_ns
            stack.append([e, e.dur_ns])
        close(1 << 62)
    return out


def idle_gaps(events: list[Event], t0_ns: int, t1_ns: int, top: int = 10) -> list[tuple[str, float]]:
    """The longest gaps of the first device plane in which no operation
    ran, each named after the program that ran next (what the device was
    waiting for)."""
    planes = device_planes(events)
    if not planes:
        return []
    ops = sorted(clip((e for e in events if e.plane == planes[0] and e.line == OPS_LINE), t0_ns, t1_ns),
                 key=lambda e: e.start_ns)
    mods = sorted((e for e in events if e.plane == planes[0] and e.line == MODULE_LINE),
                  key=lambda e: e.start_ns)
    gaps: list[tuple[int, int]] = []
    end = t0_ns
    for e in ops:
        if e.start_ns > end:
            gaps.append((end, e.start_ns))
        end = max(end, e.start_ns + e.dur_ns)
    if t1_ns > end:
        gaps.append((end, t1_ns))
    named: dict[str, float] = {}
    for a, b in gaps:
        nxt = next((m for m in mods if m.start_ns >= b - 1000), None)
        key = "before " + (program_name(nxt.name) if nxt else "end of window")
        named[key] = named.get(key, 0.0) + (b - a) / 1e9
    return sorted(named.items(), key=lambda kv: -kv[1])[:top]
