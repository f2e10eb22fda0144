"""Token-budget step scheduling for continuous batching.

The engine loop used to alternate monolithic bucketed prefills with decode
blocks, so one long prompt's prefill head-of-line-blocked every decoding
row (ROADMAP item 1). This module is the policy half of the fix: each
engine iteration a :class:`StepPlanner` assembles ONE :class:`StepPlan`
that mixes

- every live decode row (decode is reserved FIRST — the starvation
  guarantee: however much prefill work is queued, the next N-step decode
  block always dispatches), and
- up to ``prefill_chunk_tokens`` of prefill-chunk work, split across the
  oldest partially-prefilled requests (their :class:`ChunkCursor` carries
  the per-request chunk position between iterations), plus an admission
  quota for fresh requests.

The mechanism half — running the granted chunks and the decode block in
one unified ragged dispatch against the KV pool — lives in
``serving/batch.py`` (``ragged_step_paged``) and ``serving/engine.py``
(Ragged Paged Attention, arXiv:2604.15464).

Budget policy (docs/performance.md "Continuous batching"):

- ``step_token_budget == 0`` (auto, the default) reserves the decode
  block implicitly and grants ONE WHOLE CHUNK TO EVERY CURSOR THAT HAS
  WORK (not blocked, tokens left to dispatch), oldest first, up to
  ``block_steps`` cursors an iteration. A slot that holds a cursor
  decodes nothing, so a cursor left waiting is a row of every decode
  step bought and thrown away: with one chunk an iteration (the policy
  until PR 36) a cell of long prompts kept 10-13 of 32 slots in waiting
  cursors. The bound is derived, not configured: an iteration carries at
  most as many chunk rows as its block has decode steps, so the longest
  a decoding row waits between two blocks is bounded by what the engine
  is built around, and the decode block still dispatches in EVERY
  iteration. (Half that bound was measured beside it on the v5e and
  served 8 % fewer tokens a second: a burst of arrivals drains at the
  bound's pace. PERF.md section 6, PR 36.) With no cursor, or one, the
  budget is one chunk, and a plan for traffic that never chunks is what
  it always was, field for field.
- An explicit ``step_token_budget`` is a hard per-iteration token target:
  decode rows (``rows * block_steps`` tokens) are subtracted first and
  prefill chunks fill whatever remains. Setting it at or below the decode
  reservation is an explicit decode-priority stance — prefill then only
  progresses in iterations with idle slots.
- Chunk grants go to cursors OLDEST FIRST (FIFO over admission order
  within a priority class), one grant a cursor an iteration, so a long
  prompt drains steadily and, past the bound, the newest arrivals are
  the ones that wait; admission of new requests is gated on leftover
  budget so a saturated step admits nothing it cannot serve.

This module is pure policy: no device work, no locks — the engine thread
is the only caller. ``plan`` is a ``sched.plan`` chaos point (a fault
while assembling a step plan exercises the engine's per-step recovery).
"""

from __future__ import annotations

import dataclasses
from typing import Any

from gofr_tpu import chaos


@dataclasses.dataclass
class ChunkCursor:
    """Per-request chunked-prefill carry: which prefix of the prompt is
    already committed to KV, and how far ahead dispatched-but-unconsumed
    chunk work runs (the device writes ahead of the committed host mirror
    by the in-flight ragged dispatches, exactly like decode's
    dispatched-ahead gap)."""

    req: Any                 # the engine's _Request
    slot: int
    total: int               # prompt tokens to prefill
    seq: int                 # admission order (FIFO grant order)
    priority: int = 0        # tenant priority (lower first): grants walk
    #                          (priority, seq), so a batch-class long
    #                          prompt cannot absorb the chunk budget
    #                          ahead of an interactive one
    committed: int = 0       # tokens confirmed resident at a consume
    dispatched: int = 0      # tokens handed to a ragged dispatch
    chunk_index: int = 0     # next chunk ordinal (timeline/span labels)
    prefix_hit: int = 0      # tokens skipped via cached chunk prefixes
    allocated: bool = False  # paged: slot pages claimed
    blocked: bool = False    # KV-pool pressure: requeue once not in flight
    # chunk-boundary prefix-cache keys, computed once per tenancy by the
    # engine ((start, end) -> key); None when chunk caching is off
    cache_keys: dict | None = None

    @property
    def remaining(self) -> int:
        return self.total - self.dispatched

    @property
    def in_flight(self) -> int:
        return self.dispatched - self.committed

    @property
    def done(self) -> bool:
        return self.committed >= self.total


@dataclasses.dataclass
class StepPlan:
    """One iteration's work assignment, assembled before any dispatch."""

    decode_rows: int                       # live rows the block serves
    decode_tokens: int                     # rows * block_steps (reserved)
    prefill_budget: int                    # chunk+admission tokens granted
    grants: list[tuple[int, int]]          # (slot, tokens) chunk grants
    admit_cap: int                         # fresh admissions this step
    budget_left: int                       # after chunk grants

    @property
    def prefill_tokens(self) -> int:
        return sum(n for _, n in self.grants)


class StepPlanner:
    """Assembles one :class:`StepPlan` per engine iteration."""

    def __init__(
        self,
        *,
        chunk_tokens: int,
        block_steps: int,
        step_token_budget: int = 0,
        max_admissions: int = 4,
    ) -> None:
        if chunk_tokens <= 0:
            raise ValueError("prefill_chunk_tokens must be positive")
        self.chunk_tokens = int(chunk_tokens)
        self.block_steps = max(1, int(block_steps))
        self.step_token_budget = max(0, int(step_token_budget))
        self.max_admissions = max(1, int(max_admissions))

    def plan(
        self,
        *,
        decode_rows: int,
        cursors: list[ChunkCursor],
        free_slots: int,
        queue_depth: int,
    ) -> StepPlan:
        """Decode first, then chunk grants oldest-cursor-first, then an
        admission quota out of the leftover budget."""
        chaos.maybe_fail("sched.plan")
        decode_tokens = decode_rows * self.block_steps
        # priority-aware grant order (multi-tenant plane, docs/serving.md
        # "Multi-tenancy"): higher classes (lower priority number) drain
        # first; FIFO within a class — the PR 10 starvation guarantee
        # (decode reserved first) is unchanged, only the PREFILL budget
        # walk became class-aware. A blocked or fully dispatched cursor
        # asks for nothing.
        waiting = [
            cur for cur in sorted(cursors, key=lambda c: (c.priority, c.seq))
            if not cur.blocked and cur.remaining > 0
        ]
        if self.step_token_budget:
            prefill_budget = max(0, self.step_token_budget - decode_tokens)
        else:
            # auto: decode is implicitly reserved (the block dispatches
            # regardless); every waiting cursor holds a slot that decodes
            # nothing, so each gets a chunk, up to as many chunk rows as
            # the block has decode steps (module docstring) — and with
            # none waiting the budget is the one chunk admission is
            # gated on
            waiting = waiting[:self.block_steps]
            prefill_budget = self.chunk_tokens * max(1, len(waiting))
        budget = prefill_budget
        grants: list[tuple[int, int]] = []
        for cur in waiting:
            if budget <= 0:
                break
            # grants are WHOLE chunks (or the prompt's final ragged tail),
            # never budget-truncated partials: chunk boundaries double as
            # page-grid write boundaries and chunk-prefix cache keys, so a
            # mid-chunk split would misalign both. A cursor whose next
            # chunk does not fit the remaining budget waits an iteration
            # instead of fragmenting it.
            grant = min(self.chunk_tokens, cur.remaining)
            if grant > budget:
                continue
            grants.append((cur.slot, grant))
            budget -= grant
        # fresh admissions scale with leftover budget and free slots;
        # single-chunk (bucketed) prefills are additionally bounded by the
        # native scheduler's own per-admit token budget, so one iteration
        # can never absorb an unbounded monolithic prefill burst. The
        # quota NEVER drops below one while the queue is non-empty:
        # canceled-but-queued requests are only ever delivered (and
        # settled) through an admit() call, and a zero-cap iteration
        # would strand them behind a saturated batch forever.
        admit_cap = 0
        if queue_depth > 0:
            admit_cap = 1
            if free_slots > 0 and budget > 0:
                admit_cap = min(self.max_admissions, max(free_slots, 1))
        return StepPlan(
            decode_rows=decode_rows,
            decode_tokens=decode_tokens,
            prefill_budget=prefill_budget,
            grants=grants,
            admit_cap=admit_cap,
            budget_left=budget,
        )
