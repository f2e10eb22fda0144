"""The engine's own spans in a run's trace, and what the per-layer readers
make of them.

The engine's step loop (``gofr_tpu/serving/engine.py``, ``_phase``) wraps
every phase of an iteration in a ``jax.profiler.TraceAnnotation`` named
``gofr.step`` (one loop iteration) or ``gofr.step.<phase>`` (``preempt``,
``plan``, ``admit``, ``prefill``, ``prefill_sync``, ``fold``, ``dispatch``,
``sync``, ``commit``, ``wait``). They land on a ``/host:*`` plane of the
profiler's trace, one line a thread, on the clock of ``/device:TPU:0``.

What the v5e trace shows (looked at by hand, PR 26): the annotation's
keywords survive as the event's stats (``blk``, ``rows``, ``steps`` ... as
integers, ``kind`` and ``route`` as strings) and the event's name is the
bare span name. ``trace_reduce.load_xplane`` keeps names only, so this
module reads the host planes itself and folds the keywords back into the
name the way TraceMe writes them, ``gofr.step.dispatch#blk=7,rows=3#``: a
recording then keeps them in ``trace_reduce.Event``'s five fields.

A program without these spans (the parent of PR 26) gives no events, and
every function here then returns None, never 0.
"""

from __future__ import annotations

import bisect
import os
import sys
from typing import Any, Iterable, NamedTuple

from benchmarks.harness import trace_reduce
from benchmarks.harness.runner import TRACE_DIR

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PREFIX = "gofr.step"
# the phases in which the engine thread waits: on the device (the block's
# one sync, a bucketed prefill's first-token read) or for work
WAITS = ("sync", "prefill_sync", "wait")
NO_SPAN = "(no span)"


class Span(NamedTuple):
    thread: str              # the trace line the span's thread writes
    phase: str               # "step" for gofr.step, else what follows gofr.step.
    start_ns: int
    dur_ns: int
    kw: dict[str, Any]

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def load_host_events(path: str) -> list[trace_reduce.Event]:
    """The ``gofr.step*`` events of the host planes of one ``.xplane.pb``,
    keywords folded into the names."""
    import jax.profiler

    out: list[trace_reduce.Event] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    kw = ",".join(f"{k}={v}" for k, v in ev.stats)
                    out.append(trace_reduce.Event(
                        plane.name, f"{line.name}#{n}", f"{ev.name}#{kw}#" if kw else ev.name,
                        int(ev.start_ns), int(ev.duration_ns)))
    return out


def parse(event: trace_reduce.Event) -> Span:
    name, _, kw = event.name.partition("#")
    pairs = (item.split("=", 1) for item in kw.rstrip("#").split(",") if "=" in item)
    return Span(f"{event.plane}/{event.line}", name[len(PREFIX) + 1:] or "step", event.start_ns, event.dur_ns,
                {k: int(v) if v.lstrip("-").isdigit() else v for k, v in pairs})


def _all(run: Any) -> list[Span]:
    """Every engine span of the run, parsed once: from the events the run
    already holds (a recording), else from the run's own xplane, which is
    still on disk while the readers run."""
    if "host_spans" not in run.cache:
        events = [e for e in run.events if e.name.startswith(PREFIX)]
        if not events:
            try:
                events = load_host_events(trace_reduce.find_xplane(os.path.join(ROOT, TRACE_DIR)))
            except FileNotFoundError:
                events = []
        run.cache["host_spans"] = sorted((parse(e) for e in events), key=lambda s: (s.start_ns, -s.dur_ns))
    return run.cache["host_spans"]


def whole_iterations(run: Any) -> tuple[int, int] | None:
    """The part of the traced sub-window the spans can speak for: from the
    start of its first ``gofr.step`` to the end of its last, whole loop
    iterations. The profiler writes a span only if a session was on when
    it opened and still on when it closed, so the iteration under way at
    either edge of a trace (about one decode block's time each) leaves no
    ``gofr.step`` and, at the start, no span for the wait it was in."""
    if "host_whole" not in run.cache:
        sub = run.traced_ns()
        steps = [s for s in _all(run) if s.phase == "step"] if sub is not None else []
        whole = None
        if steps:
            a, b = max(min(s.start_ns for s in steps), sub[0]), min(max(s.end_ns for s in steps), sub[1])
            whole = (a, b) if b > a else None
        run.cache["host_whole"] = whole
    return run.cache["host_whole"]


def spans(run: Any) -> list[Span] | None:
    """The engine's spans cut to :func:`whole_iterations`, by start."""
    window = whole_iterations(run)
    if window is None:
        return None
    out = []
    for s in _all(run):
        a, b = max(s.start_ns, window[0]), min(s.end_ns, window[1])
        if b > a:
            out.append(s._replace(start_ns=a, dur_ns=b - a))
    return out


def self_segments(thread_spans: Iterable[Span]) -> list[tuple[int, int, str]]:
    """One thread's properly nested spans as ``(start, end, phase)``
    pieces that do not overlap: every instant belongs to the innermost
    span open over it."""
    out: list[tuple[int, int, str]] = []
    stack: list[Span] = []
    cursor = 0

    def emit(upto: int) -> None:
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1].phase))
        cursor = max(cursor, upto)

    for s in sorted(thread_spans, key=lambda s: (s.start_ns, -s.dur_ns)):
        while stack and stack[-1].end_ns <= s.start_ns:
            emit(stack[-1].end_ns)
            stack.pop()
        emit(s.start_ns)
        stack.append(s)
    while stack:
        emit(stack[-1].end_ns)
        stack.pop()
    return out


def segments(run: Any) -> list[tuple[int, int, str]] | None:
    found = spans(run)
    if not found:
        return None
    if "host_segments" not in run.cache:
        threads: dict[str, list[Span]] = {}
        for s in found:
            threads.setdefault(s.thread, []).append(s)
        run.cache["host_segments"] = sorted(seg for group in threads.values() for seg in self_segments(group))
    return run.cache["host_segments"]


def self_seconds_by_phase(run: Any) -> dict[str, float] | None:
    """Seconds of the whole iterations per phase, a span's children taken
    out of it: the parts add up to the time the engine thread was inside
    any span."""
    segs = segments(run)
    if segs is None:
        return None
    out: dict[str, float] = {}
    for a, b, phase in segs:
        out[phase] = out.get(phase, 0.0) + (b - a) / 1e9
    return out


def blocks(run: Any) -> list[Span] | None:
    """The dispatch spans that started in the whole iterations and
    dispatched a block (they carry its number, ``blk``), uncut."""
    window = whole_iterations(run)
    if window is None:
        return None
    return [s for s in _all(run)
            if s.phase == "dispatch" and "blk" in s.kw and window[0] <= s.start_ns < window[1]]


def device_gaps(run: Any) -> list[tuple[int, int]] | None:
    """The intervals of the whole iterations in which no operation ran on
    the first device plane."""
    window = whole_iterations(run)
    planes = trace_reduce.device_planes(run.events)
    if window is None or not planes:
        return None
    ops = sorted(trace_reduce.clip((e for e in run.events if e.plane == planes[0] and e.line == trace_reduce.OPS_LINE),
                                   *window), key=lambda e: e.start_ns)
    gaps, end = [], window[0]
    for e in ops:
        if e.start_ns > end:
            gaps.append((end, e.start_ns))
        end = max(end, e.start_ns + e.dur_ns)
    if window[1] > end:
        gaps.append((end, window[1]))
    return gaps


def idle_by_span(run: Any) -> dict[str, float] | None:
    """Each device gap charged to the innermost engine span open over it,
    split where spans change; what no span covers goes to ``(no span)``.
    The values add up to the whole iterations less the device's busy
    seconds in them."""
    segs, gaps = segments(run), device_gaps(run)
    if segs is None or gaps is None:
        return None
    starts = [a for a, _, _ in segs]
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        covered = 0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(segs) and segs[i][0] < g1:
            a, b = max(segs[i][0], g0), min(segs[i][1], g1)
            if b > a:
                out[segs[i][2]] = out.get(segs[i][2], 0.0) + (b - a) / 1e9
                covered += b - a
            i += 1
        if g1 - g0 > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (g1 - g0 - covered) / 1e9
    return out


def _say_once(run: Any, title: str, table: dict[str, float]) -> None:
    """A reader's table on standard error, once a run."""
    if title not in run.cache.setdefault("host_spans_said", set()):
        run.cache["host_spans_said"].add(title)
        print(f"{title}: " + ", ".join(f"{k} {v:.4f}s" for k, v in sorted(table.items(), key=lambda kv: -kv[1])),
              file=sys.stderr, flush=True)


# ------------------------------------------------------- the readers' numbers
def host_ms_per_block(run: Any) -> float | None:
    """Host work of the step loop per dispatched block: self time of every
    span but the waits, over the blocks dispatched, in the sub-window's
    whole iterations."""
    by_phase, found = self_seconds_by_phase(run), blocks(run)
    if by_phase is None or not found:
        return None
    _say_once(run, "engine thread seconds by phase", by_phase)
    return 1e3 * sum(s for phase, s in by_phase.items() if phase not in WAITS) / len(found)


def admit_blocked_ms(run: Any) -> float | None:
    """Mean time one admission holds the engine thread: the duration of
    ``gofr.step.prefill``, with the first-token read ``prefill_sync`` that
    ends a bucketed one inside it. No block can be queued meanwhile. (The
    read alone takes 1.5-1.9 ms in ``mistral7b.chat``: the thread's wait for
    the block in flight lies earlier in the span, in the eager sampler's
    program dispatches — my chip runs, PR 26.)"""
    held = [s.dur_ns for s in spans(run) or () if s.phase == "prefill"]
    return sum(held) / len(held) / 1e6 if held else None


def slot_use_pct(run: Any) -> float | None:
    """Decode positions the dispatches served over those they computed:
    rows x steps against slots x steps."""
    found = blocks(run)
    if not found:
        return None
    slots = int(run.cell["engine"]["max_slots"])
    return 100.0 * sum(s.kw["rows"] * s.kw["steps"] for s in found) / sum(slots * s.kw["steps"] for s in found)


def idle_host_bound_pct(run: Any) -> float | None:
    """Share of the sub-window in which the device ran nothing while the
    engine thread was at work, inside any span but ``wait``. Only the
    whole iterations can be charged, and the share is of the whole
    sub-window, so it cannot pass the device's idle share."""
    idle, sub = idle_by_span(run), run.traced_ns()
    if idle is None:
        return None
    _say_once(run, "device idle seconds by engine span", idle)
    charged = sum(s for phase, s in idle.items() if phase not in ("wait", NO_SPAN))
    return 100.0 * charged / ((sub[1] - sub[0]) / 1e9)
