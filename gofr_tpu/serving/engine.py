"""The continuous-batching serving engine.

Replaces the reference's per-request isolation model (handler.go:55-113, one
goroutine per request) with slot-based continuous batching: requests are
admitted into rows of a persistent device cache between decode steps, every
step serves all active rows, finished/canceled rows free their slot
immediately. The worker runs in a dedicated thread (device steps block);
tokens cross into asyncio land through ``loop.call_soon_threadsafe``.

Observability (SURVEY §5.5): queue depth, batch occupancy, TTFT and TPOT
histograms, KV slot gauge — all through the standard metrics Manager.
Backpressure: admission beyond ``max_queue`` raises ErrorTooManyRequests
(429) instead of queueing unboundedly.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu import chaos
from gofr_tpu.http.errors import (
    ErrorDeadlineExceeded,
    ErrorEntityNotFound,
    ErrorInvalidParam,
    ErrorRequestEntityTooLarge,
    ErrorServiceUnavailable,
    ErrorStaleEpoch,
    ErrorTooManyRequests,
)
from gofr_tpu.models import llama
from gofr_tpu.native.runtime import QueueFull, Scheduler
from gofr_tpu.ops import moe as moe_ops
from gofr_tpu.ops.backend import configure_compile_cache, require_requested_backend
from gofr_tpu.ops.sampling import SAMPLER_PATHS, sampler_path
from gofr_tpu.serving import batch as batch_ops
from gofr_tpu.serving.dedup import DedupEntry, DedupRegistry, ReplayGap, ReplayStream
from gofr_tpu.serving.shed import QueueWaitEstimator
from gofr_tpu.serving.stepplan import ChunkCursor, StepPlan, StepPlanner
from gofr_tpu.serving.timeline import TimelineRecorder
from gofr_tpu.serving.tokenizer import ByteTokenizer, Tokenizer

DEFAULT_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048)


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 8
    max_seq_len: int = 1024
    max_new_tokens_default: int = 128
    max_queue: int = 256
    prefill_buckets: tuple[int, ...] = DEFAULT_BUCKETS
    # DEPRECATED alias (continuous batching, docs/performance.md): caps
    # fresh admissions per step plan — the planner's max_admissions
    admission_per_step: int = 4
    # DEPRECATED alias: the native scheduler's per-admit token gate; the
    # per-iteration prefill pacing now lives in prefill_chunk_tokens /
    # step_token_budget (serving/stepplan.py)
    prefill_token_budget: int = 4096
    # continuous batching: prompts longer than this prefill in chunks of
    # this many tokens, interleaved with decode blocks in one ragged
    # dispatch — one long prefill can no longer head-of-line-block the
    # decoding rows. When step_token_budget is 0 (auto) an iteration's
    # prefill budget is one such chunk for every cursor that has work,
    # at most as many chunk rows as the block has decode steps
    # (serving/stepplan.py).
    prefill_chunk_tokens: int = 256
    # explicit per-iteration token target: decode rows (rows*block_steps)
    # are reserved FIRST, prefill chunks fill the remainder. 0 = auto
    # (decode implicitly reserved + one chunk a waiting cursor, bounded:
    # a slot that holds a waiting cursor decodes nothing).
    step_token_budget: int = 0
    idle_sleep_s: float = 0.002
    # KV layout: "dense" reserves [slots, max_seq] rows; "paged" commits HBM
    # by resident tokens through the pooled page table (serving/kv_cache.py)
    kv_layout: str = "dense"
    kv_page_size: int = 16
    kv_num_pages: int | None = None  # default: slots*max_seq worth of pages
    # the KV element type: "bf16" is the only value, checked at
    # construction. The field exists because the benchmark's cell files
    # name the key (ROADMAP D11)
    kv_dtype: str = "bf16"
    # decode tokens per device dispatch (dense AND paged layouts), i.e.
    # the N of the CPU-free N-step block: sampling + stop-condition
    # evaluation run on device, so a row that stops mid-block freezes
    # there and the host syncs ONCE per block. None = default (4; 1 when
    # spec_tokens chunks instead). docs/performance.md.
    multi_step: int | None = None
    # outstanding decode blocks before the host materializes the oldest
    # one (double-buffer depth): 1 = dispatch k+1, then consume k
    decode_sync_every: int = 1
    # prompt-prefill (prefix) cache entries; 0 disables. A repeated prompt
    # skips its entire prefill forward pass (serving/prefix_cache.py).
    # The byte bound caps HBM regardless of bucket sizes.
    prefix_cache_entries: int = 0
    prefix_cache_bytes: int = 256 * 1024 * 1024
    # host-RAM spill tier under the device prefix cache (serving/
    # kv_spill.py, docs/performance.md "KV reuse tiers"): entries the
    # device LRU evicts spill to pinned host arrays instead of dropping,
    # and a hit re-uploads asynchronously. 0 disables the tier.
    kv_spill_bytes: int = 0
    # speculative decoding (prompt-lookup drafting): K draft tokens are
    # verified per dispatch; greedy rows commit the accepted prefix + a
    # bonus token (LOSSLESS vs plain greedy), sampled rows take normal
    # single-token steps through the same chunk executable. 0 disables.
    # Mutually exclusive with multi_step > 1 (both are chunking policies).
    spec_tokens: int = 0
    spec_ngram: int = 3
    # /requestz flight recorder: completed request timelines retained in
    # the bounded ring (in-flight ones are always all visible)
    requestz_capacity: int = 256
    # load shedding: reject at submit when the EWMA queue-wait estimate
    # exceeds this many seconds (0 disables the threshold; deadline-aware
    # shedding is always on for requests that carry a deadline)
    shed_max_wait_s: float = 0.0
    # cold-start service-time prior for the shed estimator (seconds): the
    # EWMA is seeded only by completed requests, so the first burst after
    # startup otherwise estimates 0.0 wait at any queue depth and sheds
    # nothing until the queue is already doomed. 0 keeps never-shed-blind.
    shed_cold_prior_s: float = 0.0
    # graceful drain: how long in-flight generations get to finish before
    # the remainder is failed with a retriable error
    drain_deadline_s: float = 30.0
    # disaggregation role (serving/membership.py ROLES): "unified" serves
    # whole generations; "prefill" computes prompt KV and hands it off;
    # "decode" admits handed-off KV chains and streams. The role rides
    # the membership heartbeat (ReplicaAnnouncer reads engine.role) and
    # drives the router's role-split policy — the engine itself stays
    # capable of both phases (the crash-safety degrade path re-prefills
    # on a decode replica when a handoff source dies).
    role: str = "unified"
    # preemptible capacity class (docs/robustness.md "The reclamation
    # plane"): True marks this replica as running on reclaimable
    # (spot) capacity — the flag rides the membership heartbeat, the
    # router steers interactive-class tenants off it when on-demand
    # candidates exist, and `begin_reclaim` is expected to arrive.
    preemptible: bool = False
    # reclamation drain: fraction of the notice budget reserved for the
    # bulk KV evacuation AFTER in-flight work drains (the push must not
    # start with zero wire budget left)
    reclaim_evacuate_frac: float = 0.35
    # multi-tenant preemption (serving/tenancy.py, docs/serving.md
    # "Multi-tenancy"): when a strictly higher class waits and the batch
    # is full (slots or KV pages), pause the lowest-priority decode row —
    # its committed KV pages out through the prefix-cache/host-spill tier
    # and the row resumes warm with its emitted tokens intact. Off = the
    # A/B control: a tenant storm then starves higher classes.
    tenant_preempt: bool = True
    # HA plane (docs/robustness.md "The HA plane"): bounded per-request
    # emitted-frame ring for idempotency-keyed requests — a client (or a
    # second router) re-attaching after a router/transport death replays
    # the acked-but-unseen suffix token-identically instead of re-running
    # the generation. Sized in frames (tokens + 1 terminal).
    stream_replay_tokens: int = 512
    # terminal entries retained in the idempotency dedup registry (LRU);
    # live entries are bounded by in-flight requests and don't count
    idem_capacity: int = 1024
    # grace window after a keyed stream's client vanishes mid-generation:
    # the request keeps running this long awaiting a resume re-attach
    # before it is canceled like an unkeyed disconnect would be
    stream_orphan_grace_s: float = 10.0

    @classmethod
    def from_config(cls, config: Any) -> "EngineConfig":
        """Every knob is env-tunable (VERDICT r2 weak #8: ops must be able
        to trade TTFT vs TPOT — admission cadence, buckets, idle sleep —
        without a code change)."""
        num_pages = config.get("TPU_KV_NUM_PAGES")
        buckets = config.get("TPU_BATCH_PREFILL_BUCKETS")
        multi_step = config.get("TPU_BATCH_MULTI_STEP")
        return cls(
            max_slots=int(config.get_or_default("TPU_BATCH_MAX_SLOTS", "8")),
            max_seq_len=int(config.get_or_default("TPU_BATCH_MAX_TOKENS", "1024")),
            max_new_tokens_default=int(
                config.get_or_default("TPU_MAX_NEW_TOKENS_DEFAULT", "128")
            ),
            max_queue=int(config.get_or_default("TPU_BATCH_MAX_QUEUE", "256")),
            prefill_buckets=(
                tuple(int(b) for b in buckets.split(",") if b.strip())
                if buckets else DEFAULT_BUCKETS
            ),
            admission_per_step=int(
                config.get_or_default("TPU_BATCH_ADMISSION_PER_STEP", "4")
            ),
            prefill_token_budget=int(
                config.get_or_default("TPU_BATCH_PREFILL_BUDGET", "4096")
            ),
            prefill_chunk_tokens=int(
                config.get_or_default("TPU_PREFILL_CHUNK_TOKENS", "256")
            ),
            step_token_budget=int(
                config.get_or_default("TPU_STEP_TOKEN_BUDGET", "0")
            ),
            idle_sleep_s=float(config.get_or_default("TPU_IDLE_SLEEP_S", "0.002")),
            kv_layout=config.get_or_default("TPU_KV_LAYOUT", "dense"),
            kv_page_size=int(config.get_or_default("TPU_KV_PAGE_SIZE", "16")),
            kv_num_pages=int(num_pages) if num_pages else None,
            kv_dtype=config.get_or_default("TPU_KV_DTYPE", "bf16"),
            multi_step=int(multi_step) if multi_step else None,
            decode_sync_every=int(
                config.get_or_default("TPU_DECODE_SYNC_EVERY", "1")
            ),
            prefix_cache_entries=int(
                config.get_or_default("TPU_PREFIX_CACHE_ENTRIES", "0")
            ),
            prefix_cache_bytes=int(
                config.get_or_default("TPU_PREFIX_CACHE_BYTES",
                                      str(256 * 1024 * 1024))
            ),
            kv_spill_bytes=int(
                config.get_or_default("TPU_KV_SPILL_BYTES", "0")
            ),
            spec_tokens=int(config.get_or_default("TPU_SPEC_TOKENS", "0")),
            spec_ngram=int(config.get_or_default("TPU_SPEC_NGRAM", "3")),
            requestz_capacity=int(
                config.get_or_default("TPU_REQUESTZ_CAPACITY", "256")
            ),
            shed_max_wait_s=float(config.get_or_default("TPU_SHED_MAX_WAIT_S", "0")),
            shed_cold_prior_s=float(
                config.get_or_default("TPU_SHED_COLD_PRIOR_S", "0")
            ),
            drain_deadline_s=float(
                config.get_or_default("TPU_DRAIN_DEADLINE_S", "30")
            ),
            role=config.get_or_default("TPU_REPLICA_ROLE", "unified"),
            preemptible=config.get_or_default(
                "TPU_REPLICA_PREEMPTIBLE", "0"
            ) not in ("0", "false", "off"),
            reclaim_evacuate_frac=float(config.get_or_default(
                "TPU_RECLAIM_EVACUATE_FRAC", "0.35"
            )),
            tenant_preempt=config.get_or_default(
                "TPU_TENANT_PREEMPT", "1"
            ) not in ("0", "false", "off"),
            stream_replay_tokens=int(
                config.get_or_default("TPU_STREAM_REPLAY_TOKENS", "512")
            ),
            idem_capacity=int(config.get_or_default("TPU_IDEM_CAPACITY", "1024")),
            stream_orphan_grace_s=float(
                config.get_or_default("TPU_STREAM_ORPHAN_GRACE_S", "10")
            ),
        )


@dataclasses.dataclass
class GenerationResult:
    request_id: int
    text: str
    token_ids: list[int]
    prompt_tokens: int
    completion_tokens: int
    finish_reason: str  # "stop" | "length" | "kv_exhausted" | "cancel" | "deadline_exceeded" | "error"
    ttft_s: float
    duration_s: float


class _RequeueRequest(Exception):
    """Raised inside _prefill_into when a transient resource (KV pages) is
    unavailable: the request goes back to the queue head, not to an error."""


class _ThreadRetired(BaseException):
    """Raised on the engine loop thread when it discovers it has been
    replaced (a warm restart that could not join it quarantine-leaked its
    resources and started a successor). BaseException on purpose: the
    per-step ``except Exception`` recovery must NOT catch it — a retired
    thread settling futures, mutating rebuilt state, or running _fail_all
    would race the replacement thread over state it no longer owns."""


class _Request:
    __slots__ = (
        "id", "prompt_ids", "max_new_tokens", "temperature", "top_k", "top_p",
        "stream_cb", "future", "created", "first_token_at", "tokens", "slot",
        "canceled", "stop_ids", "priority", "dispatched", "deadline",
        "kv_exhausted", "timeline", "trace_ctx", "prefill_only",
        "handoff_from", "tenant", "adapter_id", "adapter_slot", "preemptions",
        "idem_key", "replay",
    )

    def __init__(self, rid: int, prompt_ids: list[int], max_new_tokens: int,
                 temperature: float, top_k: int, top_p: float,
                 stream_cb: Callable | None, future: Any, stop_ids: set[int],
                 deadline: float | None = None) -> None:
        self.id = rid
        self.prompt_ids = prompt_ids
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.stream_cb = stream_cb
        self.future = future
        self.created = time.perf_counter()
        self.first_token_at: float | None = None
        self.tokens: list[int] = []
        self.slot: int | None = None
        self.canceled = False
        self.stop_ids = stop_ids
        self.priority = 0
        self.dispatched = 0  # decode steps dispatched (pipelined, ≥ consumed)
        # the row was cut short by KV-pool pressure, not by its own token
        # budget: the limit-check retire reports "kv_exhausted", a signal
        # distinct from a legitimate max-tokens "length" stop
        self.kv_exhausted = False
        # observability rails: the request's flight-recorder timeline and
        # the caller's trace context (a Span the lifecycle spans hang off)
        self.timeline: Any = None
        self.trace_ctx: Any = None
        # disaggregated serving (docs/robustness.md "The disaggregation
        # plane"): a prefill_only request retires at the first-token
        # commit with finish_reason "handoff" (its prompt KV stays in the
        # prefix cache for the decode replica to pull); handoff_from
        # names the prefill replica whose cache this request's admission
        # should pull its KV chain from, under the kv.handoff 2PC fetch.
        self.prefill_only = False
        self.handoff_from: str | None = None
        # multi-tenant plane (serving/tenancy.py + serving/lora.py):
        # tenant name (timeline/span/metric label + preemption class),
        # the request's named LoRA adapter and its pinned device-table
        # slot (0 = base), and how many times this row was preempted
        self.tenant: str | None = None
        self.adapter_id: str | None = None
        self.adapter_slot = 0
        self.preemptions = 0
        # HA plane: the request's Idempotency-Key (duplicates attach
        # instead of dispatching) and its bounded emitted-frame ring
        # (serving/dedup.py ReplayStream); both None for unkeyed requests
        self.idem_key: str | None = None
        self.replay: Any = None
        # absolute perf_counter time the caller stops caring; None = forever
        self.deadline = (self.created + deadline) if deadline else None

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now > self.deadline

    def remaining(self, now: float) -> float | None:
        """Seconds left of the deadline (None when deadline-less),
        clamped at 0.0 — the budget handed to a downstream wait is
        never negative."""
        if self.deadline is None:
            return None
        return max(self.deadline - now, 0.0)

    @property
    def serve_ids(self) -> list[int]:
        """The token sequence a (re-)admission must make KV-resident:
        the prompt plus every token already emitted. Fresh requests have
        no tokens, so this IS the prompt; a preempted request resumes by
        prefilling (warm, via the chunk-boundary cache) its whole
        generated context and sampling the NEXT token — emitted tokens
        are preserved, never re-run."""
        return self.prompt_ids + self.tokens

    @property
    def new_budget(self) -> int:
        """Tokens the request may still emit (max_new minus what is
        already out) — the admission-time budget for fresh AND resumed
        requests."""
        return self.max_new_tokens - len(self.tokens)


class _Inflight:
    """A dispatched-but-not-consumed N-step decode block: the packed
    device-side result ([B, steps+2] — token columns, done flag, n_valid;
    batch_ops._pack_block) plus the (slot, request) snapshot the dispatch
    was built from. The snapshot is what makes pipelining safe — by
    consume time a slot may have been retired and even re-admitted, and
    ``slots[slot] is req`` detects that and discards the stale tokens.
    ``packed`` is the block's ONLY host-read device value, and it is
    never donated anywhere — holding it here cannot alias a donated
    carry (the round-4 use-after-donate shape)."""

    __slots__ = ("packed", "rows", "dispatched_at", "steps", "blk",
                 "prefill_rows", "last_logits")

    def __init__(self, packed: Any, rows: list, dispatched_at: float,
                 steps: int = 1, blk: int = 0,
                 prefill_rows: list | None = None,
                 last_logits: Any = None) -> None:
        self.packed = packed
        self.rows = rows
        self.dispatched_at = dispatched_at
        self.steps = steps
        # the block's sequence number: what joins its gofr.step.dispatch,
        # .sync and .commit spans, and a request's first_blk/last_blk
        self.blk = blk
        # ragged dispatches only: the prefill-chunk rows this block ran —
        # (slot, req, cursor, start, n_tokens, final, chunk_index) — plus
        # the device-resident last-position logits (retained ONLY for the
        # chunk-prefix cache; never synced here)
        self.prefill_rows = prefill_rows or []
        self.last_logits = last_logits


def _block_sync(value: Any) -> np.ndarray:
    """THE decode loop's one sanctioned host-device synchronization point:
    materialize a dispatched block's packed result. Everything the host
    needs from N device steps comes through this single call — tests
    monkeypatch it to count syncs, and gofrlint's host-sync rule keeps any
    other materialization out of the hot functions."""
    return np.asarray(value)  # gofrlint: disable=host-sync -- the one sanctioned block-sync point


def _moe_path(cfg: Any, params: dict, rows: int) -> str | None:
    """The branch ``ops/moe.held_experts`` takes at a block's ``rows``
    decode rows (``moe.path``: the program's own test of the rows and of
    the stacks' storage), or None for a model without sparse experts. The
    routed stacks are the ``experts`` of the params' group that holds them
    (``moe`` or ``layers``)."""
    if not getattr(cfg, "held_experts", 0):
        return None
    experts = next(group["experts"] for group in params.values() if isinstance(group, dict) and "experts" in group)
    return moe_ops.path(rows, cfg.n_experts, cfg.top_k, experts)


# the step loop's phases (docs/observability.md "Engine step spans"):
# "step" is one loop iteration, the others nest inside it, and a dotted
# name is a part of the phase before the dot, nested inside it. Each is a
# gofr.step[.<phase>] span on the profiler's clock and a key of
# ServingEngine._phase_s and ._phase_cpu_s
STEP_PHASES = ("step", "preempt", "plan", "admit", "prefill", "prefill_sync",
               "fold", "dispatch", "dispatch.rows", "dispatch.launch",
               "dispatch.count", "sync", "commit", "commit.rows",
               "commit.chunks", "commit.stats", "wait")
_SPAN_NAMES = {p: "gofr.step" if p == "step" else f"gofr.step.{p}"
               for p in STEP_PHASES}
# a dispatch span's dev_idle (ServingEngine._launch_idle) as the launch
# label of app_engine_blocks_total
LAUNCHES = ("queued", "idle", "none")


class _StepPhase:
    """One open phase of the step loop (``ServingEngine._phase``): a
    ``jax.profiler.TraceAnnotation`` — an event on the ``/host:*`` plane of
    any profiler session, on the device trace's clock, and an inactive
    TraceMe otherwise — and a segment of the engine's phase accumulator.
    Keyword values are integers (or short constant strings) the caller
    already holds: a span never reads the device. A span that closes
    carries ``cpu_us``, the CPU time of its thread between its two ends
    (its children's included): what its duration holds beyond that, the
    thread spent off the CPU — waiting for the GIL, a lock or a call
    blocked in the runtime."""

    __slots__ = ("_engine", "_phase", "_outer", "_span", "_cpu0")

    def __init__(self, engine: "ServingEngine", phase: str, kw: dict) -> None:
        self._engine = engine
        self._phase = phase
        self._span = jax.profiler.TraceAnnotation(_SPAN_NAMES[phase], **kw)

    def __enter__(self) -> "_StepPhase":
        self._span.__enter__()
        self._outer = self._engine._phase_state[0]
        self._cpu0 = self._engine._phase_switch(self._phase)
        return self

    def set(self, **kw: Any) -> None:
        """Keywords known only once the span's work is under way."""
        self._span.set_metadata(**kw)

    def __exit__(self, *exc: Any) -> None:
        cpu_ns = self._engine._phase_switch(self._outer) - self._cpu0
        self._span.set_metadata(cpu_us=cpu_ns // 1000)
        self._span.__exit__(*exc)


class ServingEngine:
    """Owns model params + slot cache + the step loop thread."""

    def __init__(
        self,
        cfg: Any,  # a served model's config: LlamaConfig, Cohere2MoeConfig
        params: dict,
        engine_config: EngineConfig | None = None,
        tokenizer: Tokenizer | None = None,
        *,
        metrics: Any = None,
        logger: Any = None,
        tracer: Any = None,
        seed: int = 0,
        prefix_cache: Any = None,
        kv_migrator: Any = None,
        lora: Any = None,
        tenants: Any = None,
        device: Any = None,
    ) -> None:
        # a server that quietly serves from a backend nobody asked for
        # (jax drops to CPU when libtpu finds no chip) is refused here
        require_requested_backend()
        configure_compile_cache()
        self.model_cfg = cfg
        # the one device this engine lives on: weights are copied there
        # (a no-op when already resident) and everything the engine
        # builds — KV, DecodeState, RNG, re-uploaded cache entries, the
        # loop thread's default device — follows through _device_scope.
        # None leaves placement to jax (its default device, or whatever
        # shardings the param leaves carry).
        self._device = device
        self.params = (
            params if device is None else jax.device_put(params, device)
        )
        self.config = engine_config or EngineConfig()
        if self.config.role not in ("prefill", "decode", "unified"):
            raise ValueError(
                f"TPU_REPLICA_ROLE={self.config.role!r}: must be "
                "prefill, decode or unified"
            )
        # read by the membership announcer (heartbeat role) and /routerz
        self.role = self.config.role
        # preemptible capacity class (docs/robustness.md "The reclamation
        # plane"): rides the heartbeat; begin_reclaim() is the notice path
        self.preemptible = self.config.preemptible
        self._reclaiming = False
        self._reclaim_deadline: float | None = None  # absolute monotonic
        self._reclaim_swept = False  # batch shed done for this notice
        self.tokenizer: Tokenizer = tokenizer or ByteTokenizer(cfg.vocab_size)
        self._metrics = metrics
        self._logger = logger
        self._tracer = tracer
        if prefix_cache is not None:
            self._prefix_cache = prefix_cache  # any container Cache impl
        elif self.config.prefix_cache_entries > 0:
            if self.config.kv_spill_bytes > 0:
                # two-tier: device LRU over a host-RAM spill pool —
                # capacity evictions demote instead of dropping
                # (docs/performance.md "KV reuse tiers")
                from gofr_tpu.serving.kv_spill import TieredPrefixCache

                self._prefix_cache = TieredPrefixCache(
                    self.config.prefix_cache_entries,
                    max_bytes=self.config.prefix_cache_bytes,
                    spill_bytes=self.config.kv_spill_bytes,
                    metrics=metrics,
                    # demotion by timeline-observed reuse, not raw LRU:
                    # late-bound closure — self.timeline is built below
                    reuse_score=lambda key: self.timeline.reuse_count(key),
                )
            else:
                from gofr_tpu.serving.prefix_cache import PrefixCache

                self._prefix_cache = PrefixCache(
                    self.config.prefix_cache_entries,
                    max_bytes=self.config.prefix_cache_bytes,
                )
        else:
            self._prefix_cache = None
        # cluster-wide KV reuse (serving/prefix_index.py): when wired, a
        # local cache miss consults the distributed prefix index and
        # migrates the advertised slabs from the owning replica instead
        # of re-prefilling — advisory, every failure degrades to compute
        self._kv_migrator = kv_migrator
        # multi-tenant plane (docs/serving.md "Multi-tenancy"): the LoRA
        # adapter registry (serving/lora.py — per-request adapter_id,
        # heterogeneous-adapter batched decode) and the tenant policy
        # registry (serving/tenancy.py — priority/deadline classes,
        # token-rate budgets, the preemption ladder). Both optional; an
        # engine without them is byte-identical to the pre-tenancy one.
        self._lora = lora
        self._tenants = tenants
        if self._lora is not None and self.config.spec_tokens > 0:
            raise ValueError(
                "TPU_SPEC_TOKENS and a LoRA adapter registry are mutually "
                "exclusive: the speculative verify path predates the "
                "adapter gather (serve adapters from non-spec replicas)"
            )

        if self.config.kv_dtype != "bf16":
            raise ValueError(
                f"TPU_KV_DTYPE={self.config.kv_dtype!r}: must be bf16 — the "
                "KV cache has one element type, the model's; int8 KV left "
                "in PR 31 (ROADMAP R10) and is refused here, not served "
                "at twice the memory"
            )
        # the model's module (batch_ops.model_of) says what it has no
        # program for yet, in a sentence, before anything is built
        model = batch_ops.model_of(cfg)
        refusal = model.unserved(self.config, lora, cfg)
        if refusal:
            raise ValueError(refusal)
        # int32 counters the model's paged step adds to a block's packed
        # result (rows per held expert and the held experts read; a model
        # that names its own, step_stats(cfg), has them set on the commit
        # span under those names); 0 for most
        self._stats_len = model.step_stats_len(cfg)
        names = getattr(model, "step_stats", None)
        self._stats_names = names(cfg) if names else None
        # a sparse-expert model's branch of held_experts at a block's
        # decode rows, set as moe_path on every block's dispatch span
        self._moe_path = _moe_path(cfg, self.params, self.config.max_slots)
        # a model whose layers above its one shared cache run on a prompt's
        # last position alone: its prefill spans say how many positions
        # each half ran, and an admission resets a recurrent state
        self._upper_on_last = bool(getattr(model, "CHUNK_TAKES_FINISH", False))
        # a model whose chunk reads only the context that holds its end:
        # the contexts it may read, mirrored as chunk_ctx on ragged spans
        self._chunk_contexts = getattr(model, "chunk_contexts", None)
        if self.config.spec_tokens < 0:
            raise ValueError("TPU_SPEC_TOKENS must be >= 0")
        if (self.config.multi_step is not None and self.config.multi_step > 1
                and self.config.spec_tokens > 0):
            raise ValueError(
                "TPU_SPEC_TOKENS and TPU_BATCH_MULTI_STEP>1 are both "
                "chunking policies; enable one"
            )
        # resolve the N-step block size: an explicit TPU_BATCH_MULTI_STEP
        # wins; speculative mode chunks through the verify executable
        # instead (one position per draft); otherwise the CPU-free default
        # is a 4-step block (ROADMAP item 4 — one host sync per 4 tokens)
        if self.config.multi_step is not None:
            self._block_steps = max(1, int(self.config.multi_step))
        elif self.config.spec_tokens > 0:
            self._block_steps = 1
        else:
            self._block_steps = 4
        self._sync_every = max(1, int(self.config.decode_sync_every))
        # continuous batching (serving/stepplan.py, docs/performance.md):
        # prompts longer than one chunk prefill through the unified ragged
        # dispatch, interleaved with decode blocks. Speculative mode keeps
        # monolithic prefills — spec chunking and prefill chunking are
        # both per-dispatch chunking policies and the spec path is
        # unpipelined by design.
        self._chunk_enabled = self.config.spec_tokens == 0
        chunk = max(1, int(self.config.prefill_chunk_tokens))
        if self.config.kv_layout == "paged":
            # chunk boundaries double as chunk-prefix-cache boundaries,
            # and cached slabs scatter through whole pages — align the
            # chunk size down to the page grid
            page = max(1, int(self.config.kv_page_size))
            chunk = max(page, (chunk // page) * page)
        self._chunk_tokens = min(chunk, self.config.max_seq_len)
        self._planner = StepPlanner(
            chunk_tokens=self._chunk_tokens,
            block_steps=self._block_steps,
            step_token_budget=self.config.step_token_budget,
            max_admissions=self.config.admission_per_step,
        )
        # the /requestz flight recorder: per-request lifecycle timelines,
        # stamped only with host-side data already materialized at the
        # existing sync points (docs/observability.md). Process-lifetime
        # like the detok executor — a warm restart must not erase the
        # record of the requests it swept.
        self.timeline = TimelineRecorder(self.config.requestz_capacity)
        # the step loop's one time account (_phase): seconds the loop
        # thread spent in each phase, every instant charged to the
        # innermost phase open. _phase_state is (open phase, when it
        # opened or resumed, seconds closed outside "wait") — one tuple,
        # swapped whole by the loop thread, so busy_seconds() reads a
        # consistent snapshot from any thread without a lock
        self._phase_s = dict.fromkeys(STEP_PHASES, 0.0)
        self._phase_state: tuple[str | None, float, float] = (
            None, time.monotonic(), 0.0)
        # the same account in the loop thread's own CPU time
        # (time.thread_time_ns at every switch): a phase's wall seconds
        # less these are the seconds its thread was off the CPU
        self._phase_cpu_s = dict.fromkeys(STEP_PHASES, 0.0)
        self._phase_cpu_ns = 0  # the thread's CPU clock at the last switch
        # what the two counters have (app_engine_phase[_cpu]_seconds_total)
        self._phase_counted = (dict(self._phase_s), dict(self._phase_cpu_s))
        self._step_iter = 0  # loop iterations: gofr.step's iter=
        self._blk_seq = 0    # dispatched blocks: blk= on their spans
        # of those, the blocks launched while the newest block in flight
        # had its result ready (the device had run dry and idled until the
        # launch landed), while it had not, and with none in flight
        # (_launch_idle)
        self._launched = dict.fromkeys(LAUNCHES, 0)
        if self._metrics:  # every series from the start, at zero
            for phase in STEP_PHASES:
                self._metrics.add_counter("app_engine_phase_seconds_total", 0.0, phase=phase)
                self._metrics.add_counter("app_engine_phase_cpu_seconds_total", 0.0, phase=phase)
            for launch in LAUNCHES:
                self._metrics.add_counter("app_engine_blocks_total", 0.0, launch=launch)
        self._retires = 0    # rows retired: a commit span's retired=
        # optional DeviceTelemetry poller backref: health_check embeds its
        # last sample, the membership announcer reads HBM headroom off it
        self.device_telemetry: Any = None
        # executable-level runtime state (KV storage, per-slot arrays,
        # pipelined-decode device state, admission scheduler) — built by
        # the shared helper so the supervisor's warm restart rebuilds
        # EXACTLY this, never a hand-copied drift of it
        self._init_runtime_state()
        with self._device_scope():
            self.rng = jax.random.PRNGKey(seed)
            # per-REQUEST key root for prefill first-token sampling. The
            # shared self.rng stream is split by decode/spec dispatches
            # too, so a request's draw would depend on how many device
            # steps interleaved before its admission — which depends on
            # jit-cache warmth and thread timing (the test_spec_concurrent
            # flake: warm caches shift the interleave and a sampled row
            # draws EOS as its first prefill token). fold_in(root, rid)
            # pins each request's first token to its id alone: same submit
            # order → same tokens, standalone or mid-suite, and a
            # requeued/warm-restarted request re-prefills to the identical
            # first token.
            self._rng_root = jax.random.PRNGKey(seed)
        # detokenization + stream emission run OFF the engine thread on
        # this single-worker executor, so a slow tokenizer or a blocking
        # stream_cb overlaps the device block instead of stalling it. ONE
        # worker on purpose: per-request frame order (tokens, then the
        # terminal done frame) is the transports' contract. Process-
        # lifetime (NOT rebuilt by warm_restart — pending emissions for
        # swept requests settle harmlessly; _try_resolve is race-tolerant).
        self._detok = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="serving-detok"
        )
        self._detok_depth = 0  # emissions queued, for the backlog gauge
        self._detok_mu = threading.Lock()
        # set whenever the detok queue is empty: drain() waits on it — the
        # drain contract is "generations FINISHED", and terminal
        # settlement (done frames, future resolution) rides this executor
        self._detok_idle = threading.Event()
        self._detok_idle.set()
        # speculative-decode counters (observable uplift: emitted /
        # dispatches > 1 means drafts are being accepted)
        self.spec_stats = {"dispatches": 0, "accepted": 0, "emitted": 0}
        self._by_id: dict[int, _Request] = {}  # queued + active, by request id
        self._count_lock = threading.Lock()
        self._next_id = 0
        self._running = False
        self._thread: threading.Thread | None = None
        self._wake = threading.Event()
        # request-lifecycle robustness state: the queue-wait estimator
        # behind load shedding, and the drain/wedge lifecycle flags
        self._shed = QueueWaitEstimator(
            cold_prior_s=self.config.shed_cold_prior_s
        )
        self._draining = False
        self._wedged = False
        self._stop_requested = False  # distinguishes "stopped" from "not yet started"
        self._idle = threading.Event()  # set by the loop when drained dry
        # -- engine supervision state (serving/supervisor.py) --------------
        # the loop stamps this monotonic heartbeat every iteration; the
        # supervisor's watchdog reads heartbeat_age() to detect a hung
        # dispatch that no exception will ever surface
        self.heartbeat = time.monotonic()
        self.loop_crashed = False  # the loop thread died with _running set
        self.device_poisonings = 0  # _fail_all runs that found KV poisoned
        self._restarting = False  # warm_restart in progress: submit 503s
        # first dispatch of a signature jit-compiles — slow but MOVING, and
        # the heartbeat cannot show it (the stamp lands only when the
        # dispatch returns). _cold_dispatch marks those sections so the
        # watchdog widens its stall threshold to TPU_ENGINE_COMPILE_GRACE_S
        # instead of restarting a healthy engine mid-compile. _warmed is
        # per-process knowledge (the jit cache is process-global), so it
        # deliberately survives warm_restart.
        self._warmed: set[tuple] = set()
        self._cold_key: tuple | None = None
        # serializes warm_restart against stop()/drain(): exactly one of
        # them owns the teardown — a drain racing a restart must never
        # interleave their native-resource frees. RLock: stop() may run
        # while the same thread already holds it through warm_restart's
        # failure path.
        self._lifecycle_mu = threading.RLock()
        # makes submit's register+enqueue atomic w.r.t. warm_restart's
        # request sweep and _restarting flips (see submit). Lock order:
        # _lifecycle_mu → _submit_mu → _count_lock.
        self._submit_mu = threading.Lock()
        self._supervisor: Any = None  # EngineSupervisor backref (health)
        # -- HA plane (docs/robustness.md "The HA plane") ------------------
        # fence epoch: monotonic, bumped by warm_restart / begin_reclaim /
        # announcer re-register and gossiped on the heartbeat. A caller
        # presenting fence_epoch != current is acting on a pre-restart view
        # of this replica and is rejected (ErrorStaleEpoch) before any
        # scheduler state is touched — the zombie-router fence.
        self.epoch = 1
        # idempotency dedup registry: the replica-side exactly-once
        # authority — duplicates attach to the live future or replay the
        # stored terminal; _try_resolve stays the one terminal gate.
        self._dedup = DedupRegistry(self.config.idem_capacity)

    @classmethod
    def from_checkpoint(
        cls,
        cfg: Any,
        checkpoint_dir: str,
        *,
        step: int | None = None,
        sharding: Any = None,
        seed_key: Any = None,
        **kw: Any,
    ) -> "ServingEngine":
        """Warm restart (SURVEY §5.4): build an engine whose weights come
        from the newest committed checkpoint step (or ``step``), optionally
        placed straight onto a sharding pytree. Falls back to random init
        only when ``seed_key`` is given and no checkpoint exists."""
        from gofr_tpu.checkpoint import CheckpointError, CheckpointManager

        mgr = CheckpointManager(checkpoint_dir)
        abstract = jax.eval_shape(
            lambda: batch_ops.model_of(cfg).init_params(cfg, jax.random.PRNGKey(0)))
        if mgr.latest_step() is None:  # raises on a corrupt manifest
            if seed_key is None:
                raise CheckpointError(
                    f"no committed checkpoints in {checkpoint_dir} "
                    "(pass seed_key for random-init fallback)"
                )
            params = batch_ops.model_of(cfg).init_params(cfg, seed_key)
        else:
            # corruption in an EXISTING checkpoint propagates: silently
            # serving random weights would be worse than failing startup
            params = mgr.restore(abstract, step=step, sharding=sharding)
        return cls(cfg, params, **kw)

    @classmethod
    def from_hf(
        cls,
        path: str,
        *,
        dtype: Any = None,
        sharding: Any = None,
        fs: Any = None,
        tokenizer: Any = None,
        **kw: Any,
    ) -> "ServingEngine":
        """Serve a real externally-produced checkpoint: HF-layout
        safetensors weights + the tokenizer asset next to them
        (tokenizer.json or tokenizer.model). This is the production
        startup path — VERDICT round-1 item 3."""
        from gofr_tpu.models.hf_import import load_llama_from_hf

        cfg, params = load_llama_from_hf(
            path, dtype=dtype, sharding=sharding, fs=fs
        )
        if tokenizer is None:
            from gofr_tpu.tokenizer import load_tokenizer

            try:
                tokenizer = load_tokenizer(path, fs=fs)
            except FileNotFoundError:
                tokenizer = None  # fall through to ByteTokenizer default
        return cls(cfg, params, tokenizer=tokenizer, **kw)

    # ------------------------------------------------------------- lifecycle
    def start(self) -> None:
        if self._running:
            return
        self._draining = False
        self._wedged = False
        self._stop_requested = False
        self.loop_crashed = False
        self._start_loop_thread()
        if self._logger:
            self._logger.info(
                f"serving engine started: slots={self.config.max_slots} "
                f"max_seq={self.config.max_seq_len}"
            )

    def _start_loop_thread(self) -> None:
        """Spawn the engine loop thread — shared by start() and
        warm_restart so the ordering invariants live in ONE place:
        the heartbeat is pre-stamped before the thread exists (a watchdog
        polling the gap must not see a stale age), and self._thread is
        assigned BEFORE _running flips — a thawing wedged/quarantined
        predecessor re-checks `me is self._thread` and retires, where the
        reverse order would let it pass both loop guards and run an
        iteration it no longer owns."""
        self.heartbeat = time.monotonic()
        self._idle.clear()
        thread = threading.Thread(
            target=self._loop, name="serving-engine", daemon=True
        )
        self._thread = thread
        self._running = True
        thread.start()

    def stop(self, join_timeout: float = 10.0) -> None:
        self._stop_requested = True  # BEFORE the sweep: see submit's re-check
        with self._lifecycle_mu:  # a mid-flight warm_restart finishes first
            self._stop_inner(join_timeout)

    def _stop_inner(self, join_timeout: float) -> None:
        self._running = False
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=join_timeout)
            if self._thread.is_alive():
                # a wedged engine thread is an incident, not a shrug: keep
                # the thread reference (health reports WEDGED, not DOWN)
                # and do NOT destroy the scheduler/pools it may still be
                # touching — that would turn a hang into a use-after-free
                self._wedged = True
                if self._logger:
                    self._logger.error(
                        f"serving engine thread failed to exit within "
                        f"{join_timeout:g}s; native resources left allocated, "
                        "health will report WEDGED"
                    )
                # the hung thread can never settle what's registered, and
                # a wedged engine never will either — fail every future
                # retriable NOW rather than strand its caller forever.
                # (Pure host-side future settlement, safe under a live
                # thread — unlike the native frees below, which stay
                # skipped; _try_resolve is idempotent if the thread thaws
                # mid-settle.)
                with self._count_lock:
                    leftovers = list(self._by_id.values())
                    self._by_id.clear()
                for req in leftovers:
                    # the registry outlives this engine: pins must not
                    self._lora_release(req)
                    self._settle_future(req, ErrorServiceUnavailable(
                        "engine wedged; retry on another replica",
                        retry_after=1.0,
                    ))
                # the host-side executors are still OURS under a wedged
                # engine thread (leakcheck's sweep found this path) —
                # only the NATIVE resources stay quarantined: those the
                # hung thread may be inside.
                self._shutdown_host_executors()
                return
            self._thread = None
            self._wedged = False  # a later stop() that joins clean recovers
        # the engine is terminal: stop accepting emissions BEFORE the
        # sweep, so no settle task enqueues behind the shutdown
        self._shutdown_host_executors()
        # the loop thread has exited: anything still registered can never
        # reach a terminal state through it (e.g. a submit that raced the
        # drain flag and enqueued after the loop's last scan) — fail it
        # retriable rather than leave its caller hanging forever
        with self._count_lock:
            leftovers = list(self._by_id.values())
            self._by_id.clear()
        for req in leftovers:
            # the adapter registry outlives this engine: release pins so
            # a successor engine sharing it can still recycle slots
            self._lora_release(req)
            self._settle_future(req, ErrorServiceUnavailable(
                "engine stopped before the request was served; retry",
                retry_after=1.0,
            ))
        try:
            self._sched.close()  # fallible: destroy status is checked
        finally:
            if self.paged_cache is not None:
                self.paged_cache.close()

    def _shutdown_host_executors(self) -> None:
        """Stop the engine's HOST-side workers accepting new work — the
        one shutdown sequence shared by the clean stop and the wedged
        stop (under a hung engine thread these are still ours; only the
        native scheduler/pools get quarantined). ``wait=False`` on
        purpose: already-queued detok/settle tasks and spills still run
        to completion (ThreadPoolExecutor drains its queue), so no
        retired request's future is stranded and stop() never blocks
        behind a client stream_cb. The spill tier is matched by
        isinstance, NOT duck-typed: an injected container cache may
        expose close() with datasource semantics the engine must never
        invoke on a shared resource."""
        self._detok.shutdown(wait=False)
        from gofr_tpu.serving.kv_spill import TieredPrefixCache

        if isinstance(self._prefix_cache, TieredPrefixCache):
            self._prefix_cache.close()

    def drain(self, deadline_s: float | None = None, *,
              join_timeout: float = 10.0) -> bool:
        """Coordinated graceful drain: stop admitting (submit raises a
        retriable 503), let queued + in-flight generations finish within
        ``deadline_s`` (config drain_deadline_s by default), fail whatever
        remains with a retriable ErrorServiceUnavailable, then stop the
        engine thread. Returns True when everything finished inside the
        deadline. Runs from any thread; called on SIGTERM via the app's
        shutdown hooks and from the admin drain trigger."""
        if not self._running:
            # never started (or already stopped): nothing to wait for, but
            # stop() must still run — it sweeps queued submissions and
            # releases the native scheduler + KV pools (both closes are
            # idempotent), which the old on_shutdown(engine.stop) hook did
            # unconditionally
            self.stop(join_timeout=join_timeout)
            return True
        deadline_s = (
            self.config.drain_deadline_s if deadline_s is None else deadline_s
        )
        self._draining = True
        self._idle.clear()
        self._wake.set()
        if self._logger:
            self._logger.info(f"serving engine draining (deadline {deadline_s:g}s)")
        drain_start = time.monotonic()
        drained = self._idle.wait(timeout=deadline_s)
        if drained:
            # the loop went dry, but terminal settlement (done frames,
            # future resolution, full-text detok) rides the detok
            # executor — "drained" means generations FINISHED, so the
            # queue must land inside the same deadline
            remaining = deadline_s - (time.monotonic() - drain_start)
            drained = self._detok_idle.wait(timeout=max(remaining, 0.0))
        if not drained:
            with self._count_lock:
                remainder = list(self._by_id.values())
            for req in remainder:
                # the engine thread may resolve this future concurrently;
                # _settle_future tolerates losing that race
                self._settle_future(req, ErrorServiceUnavailable(
                    "server draining; retry on another replica",
                    retry_after=1.0,
                ))
                req.canceled = True  # loop frees slot/KV through the cancel path
                try:
                    self._sched.cancel(req.id)
                except KeyError:
                    pass
            if self._logger and remainder:
                self._logger.warn(
                    f"drain deadline passed with {len(remainder)} request(s) "
                    "in flight; failed them with a retriable error"
                )
            self._wake.set()
            # give the loop a short window to reclaim the canceled slots
            # before the thread is asked to exit
            # gofrlint: disable=deadline-dropped -- post-deadline cleanup grace: the drain budget already elapsed, this constant bounds slot reclaim, not a request
            self._idle.wait(timeout=5.0)
        self.stop(join_timeout=join_timeout)
        return drained

    # ------------------------------------------------------- reclamation plane
    def reclaim_remaining_s(self) -> float | None:
        """Remaining seconds of an in-progress reclamation notice (None
        when not reclaiming) — the membership announcer puts this on the
        heartbeat so the router/autoscaler read the budget without asking
        the doomed replica a second question."""
        deadline = self._reclaim_deadline
        if not self._reclaiming or deadline is None:
            return None
        return max(deadline - time.monotonic(), 0.0)

    def begin_reclaim(self, deadline_s: float | None = None, *,
                      join_timeout: float = 10.0) -> dict[str, Any]:
        """The reclamation-notice ladder (docs/robustness.md "The
        reclamation plane"): the provider takes this machine back in
        ``deadline_s`` seconds whether or not we finish, so every second
        of the budget is spent in strict value order —

        1. stop admitting (health flips RECLAIMING — zero new routes —
           and ``submit`` raises a retriable 503 the router's candidate
           walk retries on a survivor);
        2. shed batch-class rows NOW via the preemption ladder's warm
           page-out (:meth:`_reclaim_sweep`, engine thread) — their
           committed chunks join the evacuation, the requests settle
           retriable;
        3. drain: in-flight interactive/standard streams finish inside
           the drain share of the budget, the remainder fails retriable
           (exactly :meth:`drain`'s contract);
        4. bulk-evacuate committed KV (prefix chains + paged-out spans)
           to a survivor over the migration transport
           (:meth:`_evacuate_kv`, two-phase: partial pushes are
           discarded whole);
        5. stop — the pool driver reaps a drained replica, never a
           serving one.

        Runs from any thread (the pool driver's notice thread); returns
        a summary dict. ``reclaim_evacuate_frac`` reserves the tail of
        the notice for step 4 so the push never starts with zero wire
        budget."""
        if deadline_s is None:
            deadline_s = self.config.drain_deadline_s
        notice_t0 = time.monotonic()
        with self._lifecycle_mu:
            if self._reclaiming or self._stop_requested or self._wedged:
                return {"accepted": False, "reason": "lifecycle-owned"}
            self._reclaim_deadline = notice_t0 + max(float(deadline_s), 0.0)
            self._reclaiming = True
            self._reclaim_swept = False
            # fence bump: from this instant a router still acting on the
            # pre-notice epoch is stale — its submits/cancels/KV-fetches
            # are rejected at the wire (the heartbeat gossips the new one)
            self.epoch += 1
        if self._metrics:
            self._metrics.increment_counter("app_replica_reclamations_total")
        if self._logger:
            self._logger.warn(
                f"reclamation notice: {deadline_s:g}s to drain + evacuate"
            )
        # stamp every in-flight timeline: /requestz shows which requests
        # a notice touched, whatever their terminal state turns out to be
        with self._count_lock:
            inflight = list(self._by_id.values())
        for req in inflight:
            tl = req.timeline
            if tl is not None:
                tl.stamp("reclaim")
        summary: dict[str, Any] = {
            "accepted": True, "deadline_s": float(deadline_s),
            "inflight": len(inflight),
        }
        if not self._running:
            # never started / already stopped: nothing drains, but the
            # committed cache may still hold chains worth saving
            summary["drained"] = True
            summary["evacuation"] = self._evacuate_kv(
                self._reclaim_deadline - time.monotonic()
            )
            self.stop(join_timeout=join_timeout)
            self._reclaiming = False
            self._reclaim_deadline = None
            return summary
        # drain share of the notice: the evacuation reserve comes off the
        # top so the push starts with real wire budget left
        evac_frac = min(max(self.config.reclaim_evacuate_frac, 0.0), 0.9)
        drain_budget = max(float(deadline_s) * (1.0 - evac_frac), 0.0)
        self._draining = True
        self._idle.clear()
        self._wake.set()
        drained = self._idle.wait(timeout=drain_budget)
        if drained:
            remaining = drain_budget - (time.monotonic() - notice_t0)
            drained = self._detok_idle.wait(timeout=max(remaining, 0.0))
        if not drained:
            # same contract as drain() past its deadline: the remainder
            # fails retriable — never killed mid-write, never stranded
            with self._count_lock:
                remainder = list(self._by_id.values())
            for req in remainder:
                self._settle_future(req, ErrorServiceUnavailable(
                    "replica reclaiming; retry on another replica",
                    retry_after=0.5,
                ))
                req.canceled = True
                try:
                    self._sched.cancel(req.id)
                except KeyError:
                    pass
            if self._logger and remainder:
                self._logger.warn(
                    f"reclaim drain budget passed with {len(remainder)} "
                    "request(s) in flight; failed them retriable"
                )
            self._wake.set()
            # bounded slot-reclaim grace, same as drain(): the notice
            # deadline still caps the whole ladder
            # gofrlint: disable=deadline-dropped -- post-budget cleanup grace; the evacuation step below re-derives its budget from the absolute notice deadline
            self._idle.wait(timeout=min(
                2.0, max(self._reclaim_deadline - time.monotonic(), 0.0)
            ))
        summary["drained"] = drained
        summary["evacuation"] = self._evacuate_kv(
            self._reclaim_deadline - time.monotonic()
        )
        self.stop(join_timeout=join_timeout)
        if self._metrics:
            self._metrics.record_histogram(
                "app_reclaim_drain_seconds", time.monotonic() - notice_t0
            )
        self._reclaiming = False
        self._reclaim_deadline = None
        return summary

    def _reclaim_sweep(self) -> bool:
        """Engine-thread arm of the notice ladder: shed batch-class work
        immediately so the drain budget serves interactive streams.
        Queued batch requests fail retriable without prefilling; active
        batch rows take the preemption ladder's warm page-out
        (``_preempt(reclaim=True)``) — their committed chunk spans land
        in the prefix cache, whence the bulk evacuation carries them to
        a survivor. Rows with device work in flight are skipped this
        iteration and swept on the next (preempting under an in-flight
        block would free pages the dispatched device work still
        writes)."""
        from gofr_tpu.serving.tenancy import DEADLINE_CLASSES

        threshold = DEADLINE_CLASSES["batch"][0]
        did = False
        with self._count_lock:
            queued = [
                r for r in self._by_id.values()
                if r.slot is None and not r.canceled
                and r.priority >= threshold
            ]
        for req in queued:
            self._settle_future(req, ErrorServiceUnavailable(
                "replica reclaiming; retry on another replica",
                retry_after=0.5,
            ))
            req.canceled = True
            try:
                self._sched.cancel(req.id)
            except KeyError:
                pass
            did = True
        for slot, req in enumerate(self.slots):
            if req is None or req.priority < threshold or req.canceled:
                continue
            cursor = self._cursors.get(slot)
            if self._slot_in_flight(slot, req) or (
                cursor is not None and cursor.in_flight > 0
            ):
                continue  # pipeline drains first; next iteration sweeps
            self._preempt(slot, reclaim=True)
            did = True
        return did

    def _evacuate_kv(self, deadline: float | None) -> dict[str, Any]:
        """Bulk-evacuate the committed prefix-cache contents (prefill
        chains, chunk spans, paged-out rows — device AND host tiers) to
        one surviving replica through the migrator's push side
        (:meth:`KVMigrator.evacuate_chain`). Two-phase by construction:
        the survivor commits the batch whole or not at all, so an
        interrupted push degrades to re-prefill — never a corrupt chain
        believed complete. Advisory end to end: every failure returns an
        outcome, nothing raises past here."""
        cache = self._prefix_cache
        migrator = self._kv_migrator
        out: dict[str, Any] = {"entries": 0, "committed": 0,
                               "target": None, "outcome": "skipped"}
        if (cache is None or migrator is None
                or not hasattr(migrator, "evacuate_chain")):
            if self._metrics:
                self._metrics.increment_counter(
                    "app_kv_evacuations_total", outcome="skipped"
                )
            return out
        entries: list[tuple[Any, Any]] = []
        try:
            # PrefixCache and TieredPrefixCache both enumerate via
            # keys() (the tiered one spans device + host); an injected
            # container cache without it simply has nothing to evacuate
            keys = list(cache.keys()) if hasattr(cache, "keys") else []
            reader = cache.peek if hasattr(cache, "peek") else cache.get
            for key in keys:
                val = reader(key)
                if val is None:
                    continue
                entries.append((key, val))
        except Exception:
            out["outcome"] = "harvest_error"
            if self._metrics:
                self._metrics.increment_counter(
                    "app_kv_evacuations_total", outcome="harvest_error"
                )
            return out
        out["entries"] = len(entries)
        if not entries:
            out["outcome"] = "empty"
            if self._metrics:
                self._metrics.increment_counter(
                    "app_kv_evacuations_total", outcome="empty"
                )
            return out
        try:
            committed = migrator.evacuate_chain(entries, deadline=deadline)
        except Exception:
            committed = None
        if committed:
            target, n = committed
            out.update(committed=int(n), target=target, outcome="committed")
        else:
            # no survivor accepted (all reclaiming/down, deadline spent,
            # or a chaos fault tore the push): survivors re-prefill
            out["outcome"] = "degraded"
        if self._metrics:
            self._metrics.increment_counter(
                "app_kv_evacuations_total", outcome=out["outcome"]
            )
        if self._logger:
            self._logger.info(
                f"kv evacuation: {out['outcome']} "
                f"({out['committed']}/{out['entries']} entries"
                + (f" -> {out['target']}" if out["target"] else "") + ")"
            )
        return out

    def warm_restart(self, join_timeout: float = 5.0) -> bool:
        """Self-healing restart, driven by the supervisor's watchdog when
        the loop thread hung, crashed, or keeps poisoning its device state.

        Contract (docs/robustness.md "The engine plane"):

        - in-flight generations fail RETRIABLE (503 + Retry-After /
          UNAVAILABLE) — their partial KV is gone with the pools;
        - queued, never-prefilled requests are requeued with their
          original deadlines (``_Request.deadline`` is absolute) and
          priority/FIFO order, and complete on the rebuilt engine;
        - native resources (scheduler, page allocator) are destroyed only
          when the old thread actually joined; under a still-hung thread
          they are deliberately QUARANTINE-LEAKED — same rationale as
          stop()'s wedge path: a leak is recoverable, a use-after-free
          is not;
        - executable-level state (KV pools, device-resident decode state,
          prefix cache) is rebuilt exactly the way __init__ built it.

        Returns True when the engine is serving again. Returns False
        without touching anything when drain()/stop() already owns the
        lifecycle — a restart racing a drain resolves to ONE winner.
        """
        with self._lifecycle_mu:
            if self._draining or self._stop_requested or self._wedged:
                return False  # drain/stop won the race: stand down
            # BEFORE the sweep, under the submit mutex: any submit section
            # that already registered has fully enqueued (the sweep below
            # sees it); any later one observes the flag and fails
            # retriable without touching the doomed scheduler. BOUNDED
            # acquire: a submit thread wedged inside a hung scheduler call
            # can hold the mutex forever — the healing plane must heal
            # past it, not deadlock behind it (that thread is lost to the
            # same hang being quarantined; its registered request is swept
            # and requeued like any other).
            locked = self._submit_mu.acquire(timeout=max(join_timeout, 1.0))
            try:
                self._restarting = True
                # fence bump under the same mutex: no submit can observe
                # the new scheduler with the old epoch — a caller fenced
                # on the pre-restart epoch is rejected from here on
                self.epoch += 1
            finally:
                if locked:
                    self._submit_mu.release()
            try:
                old_thread = self._thread
                self._running = False
                self._wake.set()
                joined = True
                if old_thread is not None:
                    old_thread.join(timeout=join_timeout)
                    joined = not old_thread.is_alive()
                # partition everything registered: queued-never-prefilled
                # requests survive the restart, in-flight ones cannot (their
                # KV residency dies with the pools) and fail retriable
                with self._count_lock:
                    pending = list(self._by_id.values())
                    self._by_id.clear()
                requeue: list[_Request] = []
                for req in pending:
                    # whatever the partition verdict, the row's adapter
                    # pin dies with the old batch (a requeued request
                    # re-acquires at its re-admission)
                    self._lora_release(req)
                    if not req.tokens and not req.canceled:
                        # never emitted a token: still queued, OR
                        # partially-prefilled behind a chunk cursor — its
                        # committed chunks die with the pools either way,
                        # so it requeues and re-prefills FROM CHUNK 0 on
                        # the rebuilt engine (the chunk-prefix cache, when
                        # on, makes the re-prefill cheap)
                        req.slot = None  # the old slot died with the pools
                        requeue.append(req)
                    else:
                        self._settle_future(req, ErrorServiceUnavailable(
                            "engine restarting; retry", retry_after=1.0,
                        ))
                old_sched, old_paged = self._sched, self.paged_cache
                if joined:
                    self._thread = None
                    try:
                        old_sched.close()
                    except Exception:
                        pass
                    if old_paged is not None:
                        try:
                            old_paged.close()
                        except Exception:
                            pass
                else:
                    # the hung thread may still be inside these objects:
                    # mark them abandoned, never destroy them — the loop's
                    # thread-identity guard retires the thread when it thaws
                    old_sched.leak()
                    if old_paged is not None:
                        old_paged.leak()
                    if self._logger:
                        self._logger.error(
                            f"engine thread failed to join within "
                            f"{join_timeout:g}s during warm restart; old "
                            "scheduler/KV pool quarantine-leaked"
                        )
                # the old thread's compile-grace claim dies with it: if it
                # is hung inside a cold dispatch, the key describes leaked
                # state — and the identity-gated clear in _cold_dispatch
                # means nobody else will ever drop it
                self._cold_key = None
                try:
                    # rebuild EXACTLY what __init__ built — the shared
                    # helper means a field added there cannot be missed here
                    self._init_runtime_state()
                    self._reset_prefix_cache()
                except Exception:
                    # the rebuild itself failed (a real device loss can
                    # leave the allocator refusing KV pools for a while):
                    # the requeued requests live ONLY in this local list
                    # now — settle them retriable before the failure
                    # escapes, or they'd strand forever while the
                    # supervisor retries over an empty queue
                    for req in requeue:
                        self._settle_future(req, ErrorServiceUnavailable(
                            "engine restart failed; retry", retry_after=1.0,
                        ))
                    raise
                for req in requeue:  # _by_id iteration preserved FIFO order
                    with self._count_lock:
                        self._by_id[req.id] = req
                    try:
                        self._sched.submit(
                            req.id, len(req.prompt_ids), req.max_new_tokens,
                            req.priority,
                        )
                    except Exception:
                        with self._count_lock:
                            self._by_id.pop(req.id, None)
                        self._settle_future(req, ErrorServiceUnavailable(
                            "engine restarting; retry", retry_after=1.0,
                        ))
                self.loop_crashed = False
            finally:
                # under the (bounded) mutex: a submit section sequenced
                # after this flip sees the REBUILT scheduler, never the
                # old one
                locked = self._submit_mu.acquire(
                    timeout=max(join_timeout, 1.0)
                )
                try:
                    self._restarting = False
                finally:
                    if locked:
                        self._submit_mu.release()
            # resume: a fresh loop thread over the rebuilt state
            self._start_loop_thread()
            if self._logger:
                self._logger.warn(
                    f"engine warm restart complete: {len(requeue)} queued "
                    f"request(s) requeued, {len(pending) - len(requeue)} "
                    "in-flight failed retriable"
                )
            return True

    @property
    def draining(self) -> bool:
        return self._draining

    def heartbeat_age(self) -> float:
        """Seconds since the loop thread last stamped its heartbeat. Only
        meaningful while the engine is running — the supervisor's watchdog
        compares it against TPU_ENGINE_STALL_S."""
        return time.monotonic() - self.heartbeat

    def busy_seconds(self) -> float:
        """Cumulative seconds the loop thread spent doing work (not
        waiting): every closed phase but ``wait``, plus the elapsed part
        of the phase open now — so a caller that saw its request complete
        sees its work here already. The device-telemetry poller derives
        the engine duty cycle from the delta over its poll interval."""
        phase, since, busy = self._phase_state
        if phase is not None and phase != "wait":
            busy += time.monotonic() - since
        return busy

    def _phase(self, phase: str, **kw: Any) -> _StepPhase:
        """``with self._phase("dispatch", blk=n) as span:`` — one phase of
        the step loop as a span on the profiler's clock and a segment of
        ``_phase_s``. Engine thread only; closes on any unwind."""
        return _StepPhase(self, phase, kw)

    def _phase_switch(self, phase: str | None) -> int:
        """Charge the time since the last switch — on the wall and on the
        calling thread's CPU clock — to the phase that was open and make
        ``phase`` the open one; returns that CPU clock's reading. Only the
        loop's owner keeps the account: a retired thread unwinding through
        its spans must not write into its replacement's (with no loop
        thread at all — direct calls, tests — the caller owns it)."""
        now = time.monotonic()
        cpu_ns = time.thread_time_ns()
        if self._thread is None or threading.current_thread() is self._thread:
            was, since, busy = self._phase_state
            if was is not None:
                self._phase_s[was] += now - since
                self._phase_cpu_s[was] += (cpu_ns - self._phase_cpu_ns) * 1e-9
                if was != "wait":
                    busy += now - since
            self._phase_cpu_ns = cpu_ns
            self._phase_state = (phase, now, busy)
        return cpu_ns

    def _count_phases(self) -> None:
        """Carry the account into app_engine_phase_seconds_total and
        app_engine_phase_cpu_seconds_total, once a loop iteration rather
        than at every span."""
        for name, account, counted in zip(
                ("app_engine_phase_seconds_total",
                 "app_engine_phase_cpu_seconds_total"),
                (self._phase_s, self._phase_cpu_s), self._phase_counted):
            for phase, total in account.items():
                delta = total - counted[phase]
                if delta > 0.0:
                    counted[phase] = total
                    self._metrics.add_counter(name, delta, phase=phase)

    def loop_account(self) -> dict[str, Any]:
        """The step loop's whole account in one read: blocks dispatched,
        how many of them were launched onto an idle device and how many
        behind a block still running (_launch_idle), the seconds and the
        CPU seconds of the loop thread by phase as of its last phase
        switch, and the clock. Any thread may take it: every value only
        grows, and a read torn across two phases is off by one segment."""
        return {"t": time.monotonic(), "blocks": self._blk_seq,
                "launched_idle": self._launched["idle"],
                "launched_queued": self._launched["queued"],
                "phase_s": dict(self._phase_s),
                "cpu_s": dict(self._phase_cpu_s)}

    def _launch_idle(self) -> int:
        """Asked once a dispatch, before anything of it is launched: is
        the device waiting for this launch? 1 — the newest block in flight
        has its result ready, so everything this engine queued has run
        (one stream a device: a bucketed prefill queued after that block
        is behind it) and the device idles until the launch lands; 0 — it
        is still running, the launch queues behind it; 2 — no block is in
        flight (the first after a wait or a restart, and every speculative
        chunk, which is read before the next is built): the device idled
        for want of work, not of host. ``is_ready`` asks the runtime for
        the buffer's event and neither waits nor transfers."""
        if not self._inflight_q:
            return 2
        return 1 if self._inflight_q[-1].packed.is_ready() else 0

    def _count_launch(self, span: _StepPhase, dev_idle: int) -> None:
        """A launched block's ``dev_idle`` on its dispatch span, in the
        loop's account and in app_engine_blocks_total{launch}."""
        span.set(dev_idle=dev_idle)
        launch = LAUNCHES[dev_idle]
        self._launched[launch] += 1
        if self._metrics:
            self._metrics.add_counter("app_engine_blocks_total", 1, launch=launch)

    @property
    def in_cold_dispatch(self) -> bool:
        """True while the loop is inside a dispatch whose signature has
        never completed before — i.e. one that may be jit-compiling. The
        watchdog widens its stall threshold to compile_grace_s for these:
        a multi-second first compile is progress, not a hang."""
        return self._cold_key is not None

    @contextlib.contextmanager
    def _cold_dispatch(self, *key: Any) -> Any:
        """Context manager marking a possibly-compiling dispatch section
        (keyed by executable signature); yields whether it is the
        signature's first use. The key is warmed only when the section
        completes, so a dispatch that faults keeps its grace."""
        if key in self._warmed:
            yield False
            return
        self._cold_key = key
        try:
            yield True
        finally:
            # only the loop's current owner may clear the marker: a
            # retired (quarantined) thread thawing out of its dispatch
            # here must not strip the REPLACEMENT thread's in-flight
            # compile grace — the watchdog would read a healthy first
            # compile as a stall and burn restart budget on it. (With no
            # loop thread at all — direct calls, tests — the caller owns
            # the marker and clears it.)
            if self._thread is None or threading.current_thread() is self._thread:
                self._cold_key = None
        # warming is process-global truth (the jit cache outlives the
        # thread), so even a retired thread's completed compile counts
        self._warmed.add(key)

    def _check_retired(self) -> None:
        """Quarantine guard for the loop thread: after a warm restart that
        could not join it, self._thread names a successor — the old thread
        must unwind NOW (without settling futures or touching rebuilt
        state), not at the next iteration top."""
        if threading.current_thread() is not self._thread:
            raise _ThreadRetired()

    def health_check(self) -> dict[str, Any]:
        active = sum(1 for s in self.slots if s is not None)
        stats = self._sched.stats()
        details: dict[str, Any] = {
            "slots_active": active,
            "slots_total": self.config.max_slots,
            "queue_depth": stats["queue_depth"],
            "scheduler_backend": self._sched.backend,
            "total_admitted": stats["total_admitted"],
            "kv_layout": self.config.kv_layout,
            "shed": self._shed.snapshot(),
            # HA plane: the fence epoch rides the heartbeat so routers
            # fence their per-attempt calls on the replica's current view
            "epoch": self.epoch,
            "dedup": self._dedup.stats(),
        }
        if self._running:
            details["heartbeat_age_s"] = round(self.heartbeat_age(), 3)
        if self.paged_cache is not None and self._running:
            details["kv_pages"] = self.paged_cache.stats()
        if self._prefix_cache is not None:
            details["prefix_cache"] = self._prefix_cache.stats()
        if self._lora is not None:
            details["lora"] = self._lora.residency()
        if self._tenants is not None:
            details["tenants"] = self._tenants.snapshot()
        # the flight recorder's compact latency view: median TTFT /
        # queue-wait / e2e over the completed ring (phase detail per
        # request lives at /requestz)
        details["request_latency"] = self.timeline.latency_summary()
        if self.device_telemetry is not None:
            # per-device HBM used/limit + engine duty cycle, as last
            # polled (serving/device_telemetry.py) — the heartbeat
            # announcer reads its HBM headroom from the same sample
            details["device"] = self.device_telemetry.last_sample()
        if self.preemptible:
            details["preemptible"] = True
        if self._reclaiming:
            remaining = self.reclaim_remaining_s()
            details["reclaim"] = {
                "deadline_s": round(remaining, 3)
                if remaining is not None else None,
            }
        sup = self._supervisor
        if sup is not None:
            details["supervisor"] = sup.snapshot()
        sup_state = sup.state if sup is not None else None
        # UP → DRAINING → DOWN is the normal lifecycle; WEDGED means stop()
        # timed out joining the engine thread OR the supervisor spent its
        # restart budget — the process needs replacing, which is exactly
        # why it must not masquerade as a clean DOWN. SUSPECT/RESTARTING
        # are the supervisor's self-healing window.
        if self._wedged or sup_state == "WEDGED":
            status = "WEDGED"
        elif self._restarting or sup_state == "RESTARTING":
            status = "RESTARTING"
        elif not self._running:
            status = "DOWN"
        elif self._reclaiming:
            # a reclamation notice outranks a plain drain: same zero-new-
            # routes contract, plus a hard external deadline the router
            # and autoscaler read off the beat
            status = "RECLAIMING"
        elif self._draining:
            status = "DRAINING"
        elif sup_state == "SUSPECT":
            status = "SUSPECT"
        else:
            status = "UP"
        return {"status": status, "details": details}

    # ------------------------------------------------------------- submission
    def submit(
        self,
        prompt: str | list[int],
        *,
        max_new_tokens: int | None = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        priority: int = 0,
        deadline: float | None = None,
        stream_cb: Callable[[int, str, bool], None] | None = None,
        trace_ctx: Any = None,
        prefill_only: bool = False,
        handoff_from: str | None = None,
        tenant: str | None = None,
        adapter_id: str | None = None,
        idempotency_key: str | None = None,
        fence_epoch: int | None = None,
    ) -> Any:
        """Thread-safe submit. Returns a concurrent Future resolving to
        GenerationResult. ``stream_cb(token_id, text_piece, done)`` fires per
        token from the engine thread. Lower ``priority`` runs first.
        ``deadline`` is the caller's remaining budget in seconds (from the
        HTTP ``X-Request-Timeout`` header or the gRPC deadline): a request
        still queued when it passes is dropped without prefilling (504), one
        mid-stream retires with finish reason ``deadline_exceeded``.
        ``trace_ctx`` is the caller's parent Span (the HTTP/gRPC server
        span or the router's attempt span): the request's lifecycle spans
        (queue → prefill/decode/detok) hang off it, and the trace id lands
        in the request's ``/requestz`` timeline.

        HA plane: ``idempotency_key`` makes the submit exactly-once — a
        duplicate attaches to the live request's future (and replays the
        emitted-frame suffix into its ``stream_cb``) or replays the stored
        terminal; it never dispatches twice. ``fence_epoch`` is checked
        against ``self.epoch`` BEFORE any other gate: a stale caller is
        rejected (409) without touching scheduler state."""
        import concurrent.futures

        # the fence is absolutely first: a zombie router acting on a
        # pre-restart membership view must not observe queue depth, charge
        # tenant budgets, or allocate a request id
        self.check_fence(fence_epoch)
        idem_key = str(idempotency_key) if idempotency_key else None
        if idem_key:
            # duplicate fast path BEFORE the draining/restarting/shed
            # gates: attaching to (or replaying) work this replica already
            # owns is not new work — a draining replica still honors it
            entry = self._dedup.lookup(idem_key)
            if entry is not None:
                return self._attach_duplicate(entry, stream_cb)

        if self._draining:
            # retriable: the LB should route the retry to another replica
            raise ErrorServiceUnavailable(
                "server draining; retry on another replica", retry_after=1.0
            )
        if self._restarting:
            # the supervisor is mid warm-restart: the scheduler/KV pools are
            # being replaced under us — retriable, the restart is seconds
            raise ErrorServiceUnavailable(
                "engine restarting; retry", retry_after=1.0
            )

        # -- tenancy gates (serving/tenancy.py, docs/serving.md) -----------
        # resolve the tenant's SLO class FIRST: its priority drives the
        # scheduler + preemption ladder, its deadline class fills in a
        # missing deadline (so expired-while-queued and mid-stream expiry
        # work for every tenant), and its token-rate budget rejects an
        # over-budget tenant in microseconds with 429 + Retry-After — the
        # same shed contract clients and routers already key on.
        # TENANTLESS requests are untouched: naming a tenant is the
        # opt-in — merely wiring a registry must not inject deadlines or
        # demote priority on existing anonymous traffic.
        if self._tenants is not None and tenant:
            policy = self._tenants.policy(tenant)
            if priority == 0:
                priority = int(policy.priority or 0)
            if deadline is None and policy.deadline_s:
                deadline = float(policy.deadline_s)
        if adapter_id and (
            self._lora is None or not self._lora.known(adapter_id)
        ):
            # a client error either way: no registry, or an id the
            # registry has never seen — 400, never a retriable
            raise ErrorInvalidParam("adapter_id")

        # load shedding BEFORE any per-request work: rejecting here costs
        # microseconds; admitting a request that will wait past its
        # deadline costs a 504 after seconds of queueing. ONE stats
        # snapshot serves both the estimate and the queue-depth gauge —
        # stats() takes the scheduler mutex the engine thread contends on.
        depth = self._sched.stats()["queue_depth"]
        shed_depth = depth
        if self._tenants is not None:
            # CLASS-AWARE wait estimate: the priority queue admits this
            # request ahead of every lower class, so only same-or-higher
            # class waiters stand between it and a slot — a batch-tenant
            # flood must raise the batch class's estimate (and shed IT),
            # never shed the interactive tenant the flood cannot delay
            # (the preemption ladder frees the slot itself)
            with self._count_lock:
                shed_depth = sum(
                    1 for r in self._by_id.values()
                    if r.slot is None and not r.canceled
                    and r.priority <= priority
                )
        est_wait = self._shed.estimate_wait(shed_depth, self.config.max_slots)
        if self._metrics:
            self._metrics.set_gauge("app_estimated_queue_wait_seconds", est_wait)
        shed_cap = self.config.shed_max_wait_s
        if (deadline is not None and 0 < deadline < est_wait) or (
            shed_cap > 0 and est_wait > shed_cap
        ):
            if self._metrics:
                self._metrics.increment_counter("app_requests_shed_total")
            raise ErrorTooManyRequests(
                f"estimated queue wait {est_wait:.2f}s exceeds "
                + (f"request deadline {deadline:.2f}s"
                   if deadline is not None and 0 < deadline < est_wait
                   else f"shed threshold {shed_cap:.2f}s"),
                retry_after=est_wait,
            )

        with self._count_lock:
            self._next_id += 1
            rid = self._next_id

        prompt_ids = (
            self.tokenizer.encode(prompt) if isinstance(prompt, str) else list(prompt)
        )
        # keep the TAIL within the sequence budget. Short prompts keep the
        # additional largest-bucket clamp (the monolithic prefill path
        # cannot scatter past its biggest bucket); prompts that route
        # through chunked prefill have no bucket — any length up to the
        # sequence cap chunks through (docs/performance.md).
        max_prompt = self.config.max_seq_len - 1
        if not self._route_chunked(min(len(prompt_ids), max_prompt)):
            max_prompt = min(max_prompt, max(self._buckets()))
        prompt_ids = prompt_ids[-max_prompt:]
        budget = self.config.max_seq_len - len(prompt_ids)
        max_new = min(max_new_tokens or self.config.max_new_tokens_default, budget)

        if self._tenants is not None:
            # token-rate budget: prompt + requested generation charged
            # against the tenant's bucket — over budget is a 429 the
            # retry ladder (and the router's candidate walk) understands
            ok, retry_after = self._tenants.admit(
                tenant, len(prompt_ids) + max_new
            )
            if not ok:
                if self._metrics:
                    self._metrics.increment_counter(
                        "app_requests_shed_total",
                        tenant=tenant or "default",
                    )
                raise ErrorTooManyRequests(
                    f"tenant {tenant or 'default'} over its token-rate "
                    "budget",
                    retry_after=max(retry_after, 0.05),
                )

        if adapter_id:
            from gofr_tpu.serving.lora import UnknownAdapter

            try:
                # submit-time prefetch AFTER every rejection gate: the
                # async upload (lora-upload worker, lora.upload chaos
                # point) runs while the request queues, so admission
                # normally finds the adapter resident — and shed/over-
                # budget traffic never touches (or thrashes) the device
                # adapter table
                self._lora.prefetch(adapter_id)
            except UnknownAdapter:  # deregistered since the gate above
                raise ErrorInvalidParam("adapter_id") from None

        claim_entry: DedupEntry | None = None
        if idem_key:
            # the atomic claim, AFTER the admission gates a fresh request
            # must pass: exactly one concurrent submit per key owns the
            # dispatch; a racer that lost between the lookup above and
            # here attaches to the owner instead
            owner, claim_entry = self._dedup.claim(idem_key)
            if not owner:
                return self._attach_duplicate(claim_entry, stream_cb)

        req: _Request | None = None
        try:
            future: Any = concurrent.futures.Future()
            future.request_id = rid
            req = _Request(
                rid, prompt_ids, max_new, temperature, top_k, top_p, stream_cb, future,
                stop_ids={self.tokenizer.eos_id}, deadline=deadline,
            )
            req.priority = priority
            if claim_entry is not None:
                # every emission path (detok token frames, all done-frame
                # settlement paths) flows through the bounded seq-numbered
                # ring so a resume can replay the acked-but-unseen suffix;
                # the original stream_cb still sees the plain 3-arg wire
                req.idem_key = idem_key
                req.replay = ReplayStream(self.config.stream_replay_tokens)
                req.stream_cb = req.replay.wrap(stream_cb)
                claim_entry.publish(rid, future, req.replay)
            req.prefill_only = bool(prefill_only)
            req.handoff_from = handoff_from
            req.tenant = tenant
            req.adapter_id = adapter_id or None
            # flight-recorder timeline + the queue span, BEFORE any admission
            # gate that can still reject: a shed/stopped request leaves a
            # terminal timeline too (the chaos tier audits exactly-one-
            # terminal over every accepted request id)
            tl = self.timeline.begin(rid, prompt_tokens=len(prompt_ids))
            tl.tenant = tenant
            req.timeline = tl
            req.trace_ctx = trace_ctx
            if self._tracer is not None:
                qspan = self._tracer.start_span(
                    "engine.queue", parent=trace_ctx, kind="internal",
                    activate=False,
                )
                qspan.set_attribute("request.id", rid)
                qspan.set_attribute("tokens.prompt", len(prompt_ids))
                if tenant:
                    qspan.set_attribute("tenant", tenant)
                if adapter_id:
                    qspan.set_attribute("lora.adapter", adapter_id)
                tl.open_span("queue", qspan)
            elif trace_ctx is not None:
                tl.trace_id = trace_ctx.trace_id
            # registration + enqueue are ATOMIC w.r.t. warm_restart (same
            # mutex): either the restart's sweep sees this request and
            # requeues/settles it, or this section observes _restarting and
            # fails retriable BEFORE touching the scheduler the restart is
            # about to replace. Without the mutex a submit could register
            # after the sweep yet enqueue into the old (about-to-be-leaked)
            # scheduler — stranding a deadline-less future forever — or
            # enqueue the same rid into the rebuilt scheduler a second time.
            # _restarting cannot flip while this section holds the mutex:
            # warm_restart flips it under the same lock.
            # bounded acquire: if another submit is wedged INSIDE a hung
            # scheduler call while holding the mutex, fail fast and retriable
            # instead of piling every client thread up behind it forever
            # gofrlint: disable=deadline-dropped -- deliberate constant: bounds a wedged-scheduler pile-up with a fast retriable 503; the request's own deadline is enforced by expired-while-queued
            if not self._submit_mu.acquire(timeout=5.0):
                raise ErrorServiceUnavailable(
                    "engine busy; retry on another replica", retry_after=1.0
                )
            try:
                if self._restarting:
                    raise ErrorServiceUnavailable(
                        "engine restarting; retry", retry_after=1.0
                    )
                with self._count_lock:
                    self._by_id[rid] = req
                try:
                    self._sched.submit(rid, len(prompt_ids), max_new, priority)
                except QueueFull:
                    with self._count_lock:
                        self._by_id.pop(rid, None)
                    if self._metrics:
                        self._metrics.increment_counter("app_requests_shed_total")
                    raise ErrorTooManyRequests(
                        retry_after=max(est_wait, 1.0)
                    ) from None
                except RuntimeError:
                    # "scheduler closed": lost the race against a concurrent
                    # stop()
                    with self._count_lock:
                        self._by_id.pop(rid, None)
                    raise ErrorServiceUnavailable(
                        "server stopped; retry on another replica",
                        retry_after=1.0,
                    ) from None
                if self._stop_requested:
                    # raced a concurrent stop(): the flag (monotonic, unlike
                    # _restarting) flips BEFORE the leftover sweep, so either
                    # that sweep saw this registration or this re-check sees
                    # the flip — the request cannot strand. (A not-yet-started
                    # engine is fine: submit-then-start is supported.)
                    with self._count_lock:
                        self._by_id.pop(rid, None)
                    try:
                        self._sched.cancel(rid)
                    except Exception:
                        pass
                    raise ErrorServiceUnavailable(
                        "server stopped; retry on another replica",
                        retry_after=1.0,
                    )
            finally:
                self._submit_mu.release()
        except Exception as exc:
            # the caller gets the raise, but the accepted request id still
            # owes a terminal timeline — settle the (discarded) future
            # through the same gate every other path uses. _try_resolve is
            # exactly-once (a stop/restart sweep that already settled this
            # registration cannot double-mark the terminal) AND the one
            # place a keyed failure forgets its dedup entry — the try
            # opens right at the claim-to-publish window, so a failure
            # ANYWHERE after the claim (request construction, timeline
            # begin, tracer spans, the scheduler section) cannot strand a
            # live entry with a never-resolving future that every later
            # duplicate of this key would attach to and hang on.
            if req is not None:
                self._try_resolve(req, exc=exc)
            if claim_entry is not None and (req is None or req.idem_key is None):
                # failed before the key was wired onto the request:
                # forget directly so the next submit re-runs fresh
                self._dedup.forget(idem_key)
            raise
        self._observe_queue(depth + 1)  # this request just joined the queue
        self._wake.set()
        return future

    async def generate(self, prompt: str | list[int], **kw: Any) -> GenerationResult:
        """Asyncio-friendly submit + await."""
        future = self.submit(prompt, **kw)
        return await asyncio.wrap_future(future)

    async def stream(self, prompt: str | list[int], *,
                     on_result: Callable[[GenerationResult], None] | None = None,
                     **kw: Any):
        """Async iterator of (token_id, text_piece) tuples. ``on_result``
        fires with the final GenerationResult after the last token, so
        transports can emit a terminal frame (finish reason, usage) without
        re-plumbing the future."""
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()

        def cb(token_id: int, piece: str, done: bool) -> None:
            loop.call_soon_threadsafe(q.put_nowait, (token_id, piece, done))

        future = self.submit(prompt, stream_cb=cb, **kw)
        try:
            while True:
                token_id, piece, done = await q.get()
                if done:
                    break
                yield token_id, piece
            result = await asyncio.wrap_future(future)
            if on_result is not None:
                on_result(result)
        finally:
            # client disconnected mid-stream (GeneratorExit) or consumer
            # stopped: free the slot instead of decoding into the void —
            # the reference's ErrorClientClosedRequest analogue for batched
            # serving (http/errors.go 499)
            if not future.done():
                self.cancel(future.request_id)

    def cancel(self, request_id: int, *, fence_epoch: int | None = None) -> None:
        """Mark a queued or running request canceled; a running one frees
        its slot on the next step, a queued one resolves at admission.
        ``fence_epoch`` rejects a stale caller (409) before any state is
        touched — a fenced zombie router must not cancel work a current
        router legitimately owns."""
        self.check_fence(fence_epoch)
        with self._count_lock:
            req = self._by_id.get(request_id)
        if req is not None:
            req.canceled = True
        try:
            self._sched.cancel(request_id)  # no-op if already admitted
        except KeyError:
            pass
        self._wake.set()

    # --------------------------------------------------- HA plane (resume)
    def check_fence(self, fence_epoch: int | None) -> None:
        """Reject a caller whose fence epoch is not this engine's current
        one. The epoch bumps on warm_restart / begin_reclaim / announcer
        re-register and gossips on the heartbeat; ``None`` (an unfenced
        caller) always passes — fencing is the router tier's opt-in."""
        if fence_epoch is not None and int(fence_epoch) != self.epoch:
            raise ErrorStaleEpoch(
                f"fence epoch {int(fence_epoch)} != engine epoch "
                f"{self.epoch}; refresh membership"
            )

    def _attach_duplicate(self, entry: DedupEntry, stream_cb: Callable | None,
                          last_seq: int = 0) -> Any:
        """A duplicate idempotency-keyed submit: attach, never dispatch.

        Live entry → the ORIGINAL future (exactly one terminal, one
        ``_try_resolve`` win) with the unseen frame suffix replayed into
        ``stream_cb``; terminal entry → a resolved future replaying the
        stored result. A live generation whose suffix fell out of the
        bounded replay window attaches WITHOUT replay — truncated stream,
        full result via the future — because the keyed-submit contract is
        "a retry dedups safely", never a hard error; the 404 on an
        evicted window belongs to the explicit ``Last-Event-ID`` resume
        wire only (``resume``), where the client asked for a
        token-identical suffix by name. The claim-to-publish window is
        closed by waiting on ``entry.ready``."""
        import concurrent.futures

        # bounds only the owner's claim-to-publish window (microseconds
        # of admission code); failure is a fast retriable 503
        if not entry.ready.wait(timeout=5.0) or (
            entry.future is None and not entry.terminal
        ):
            # the owner is still admitting (or its admission failed and
            # the key was forgotten): retriable — the retry re-runs fresh
            raise ErrorServiceUnavailable(
                "idempotent twin still admitting; retry", retry_after=0.5
            )
        if entry.terminal:
            fut: Any = concurrent.futures.Future()
            fut.request_id = entry.rid
            if stream_cb is not None:
                self._replay_result(
                    entry, last_seq,
                    lambda _seq, tid, piece, done: stream_cb(tid, piece, done),
                )
            fut.set_result(entry.result)
            return fut
        if stream_cb is not None and entry.replay is not None:

            def wire(_seq: int, tid: int, piece: str, done: bool) -> None:
                stream_cb(tid, piece, done)

            try:
                entry.replay.attach(last_seq, wire)
            except ReplayGap:
                # truncated live attach: frames from NOW on flow to this
                # client, and the shared future still resolves with the
                # FULL result. The mirror future carries the attach point
                # (``stream_base_seq``) so the SSE transport can stamp
                # TRUE engine sequence numbers on the truncated stream —
                # a later Last-Event-ID from this client then names real
                # frames, preserving exactly-once wire delivery. A fresh
                # mirror (not the shared owner future) keeps the
                # attribute per-attachment: concurrent gap-attaches at
                # different ring positions must not clobber each other.
                base = entry.replay.subscribe(wire)
                owner_future = entry.future
                fut = concurrent.futures.Future()
                fut.request_id = entry.rid
                fut.stream_base_seq = base

                def _mirror(src: Any) -> None:
                    try:
                        src_exc = src.exception()
                        if src_exc is not None:
                            fut.set_exception(src_exc)
                        else:
                            fut.set_result(src.result())
                    except Exception:
                        pass  # mirror already settled / owner canceled

                owner_future.add_done_callback(_mirror)
                return fut
        return entry.future

    def _replay_result(self, entry: DedupEntry, last_seq: int,
                       cb: Callable[[int, int, str, bool], None]) -> None:
        """Replay a stored terminal's token frames past ``last_seq``.

        Ring seq i+1 is provably token_ids[i]: the ring is fed by the
        single detok worker in emission order, stop tokens are never
        emitted as frames, and the terminal frame takes seq N+1. Pieces
        come from the entry's ``ReplayStream``, which retained every
        emitted piece — the replay is TEXT-identical to the original
        stream, not merely token-identical (a per-token re-decode can
        differ from incremental detok on multi-token unicode/byte
        sequences). The re-decode survives only as a defensive fallback
        for entries with no retained pieces (injected doubles)."""
        result = entry.result
        token_ids = list(result.token_ids)
        pieces: list[str] | None = None
        if entry.replay is not None and len(entry.replay.pieces) == len(token_ids):
            pieces = list(entry.replay.pieces)
        for i, tid in enumerate(token_ids):
            seq = i + 1
            if seq > last_seq:
                piece = (
                    pieces[i] if pieces is not None
                    else self.tokenizer.decode([tid])
                )
                cb(seq, tid, piece, False)
        done_seq = len(token_ids) + 1
        if done_seq > last_seq:
            cb(done_seq, -1, "", True)

    def resume(self, idempotency_key: str, *, last_seq: int = 0,
               stream_cb: Callable[[int, int, str, bool], None] | None = None,
               fence_epoch: int | None = None) -> Any:
        """Re-attach to an idempotency-keyed request's token stream.

        The resume wire (``Last-Event-ID`` re-attach): replays every
        frame with ``seq > last_seq`` — token-identically, from the
        bounded ring (live) or the stored terminal — then rides the
        still-running generation. ``stream_cb`` here is the 4-arg frame
        wire ``(seq, token_id, piece, done)`` so transports can stamp
        ``id:`` lines without re-counting. Unknown key → 404 (nothing to
        resume — the client must submit, which dedups safely anyway);
        evicted suffix → 404 on the replay window (a token-identical
        resume is impossible and the engine will not re-generate)."""
        chaos.maybe_fail("stream.resume")
        self.check_fence(fence_epoch)
        key = str(idempotency_key)
        entry = self._dedup.lookup(key)
        if entry is None:
            raise ErrorEntityNotFound("idempotency_key", key)
        # bounds only the owner's claim-to-publish window; failure is a
        # fast retriable 503
        if not entry.ready.wait(timeout=5.0) or (
            entry.future is None and not entry.terminal
        ):
            raise ErrorServiceUnavailable(
                "request still admitting; retry", retry_after=0.5
            )
        import concurrent.futures

        if entry.terminal:
            if stream_cb is not None:
                self._replay_result(entry, int(last_seq), stream_cb)
            fut: Any = concurrent.futures.Future()
            fut.request_id = entry.rid
            fut.set_result(entry.result)
            return fut
        if stream_cb is not None:
            try:
                entry.replay.attach(int(last_seq), stream_cb)
            except ReplayGap:
                raise ErrorEntityNotFound("replay window", key) from None
        return entry.future

    def orphan(self, request_id: int, grace_s: float | None = None) -> None:
        """ONE resumable (keyed) client vanished mid-stream: release its
        subscription and, if it was the last one, park the generation for
        a bounded grace window instead of canceling.

        A keyed request can have several live attachments at once — the
        owner's stream plus duplicate/resume attachments through any
        router — and one client's disconnect must never kill another
        client's in-flight generation: the reaper stands down while ANY
        subscriber remains attached. A resume within the window
        re-attaches and rides on; if nobody is attached when the timer
        fires (and no newer attach superseded this orphaning), it cancels
        the request exactly like an unkeyed disconnect. Unkeyed requests
        don't come here — their transports cancel directly."""
        grace = grace_s if grace_s is not None else self.config.stream_orphan_grace_s
        with self._count_lock:
            req = self._by_id.get(request_id)
        if req is None:
            return
        if req.replay is None:
            self.cancel(request_id)
            return
        remaining = req.replay.release()
        if remaining > 0:
            return  # another client still rides this generation
        if grace <= 0:
            self.cancel(request_id)
            return
        attaches_at_orphan = req.replay.attaches

        def _reap() -> None:
            if req.future.done():
                return
            if req.replay.attaches > attaches_at_orphan:
                return  # someone resumed; their disconnect re-orphans
            if req.replay.subscribers > 0:
                return  # a client re-attached and is still connected
            self.cancel(request_id)

        timer = threading.Timer(grace, _reap)
        timer.daemon = True
        timer.start()

    def dedup_stats(self) -> dict[str, int]:
        """Registry counters for /routerz-style introspection and tests."""
        return self._dedup.stats()

    # ------------------------------------------------------------- the loop
    def _device_scope(self) -> Any:
        """Context in which arrays this engine creates land on its
        device. ``jax.default_device`` is thread-local, so every thread
        that builds engine state (constructor, loop thread, a
        supervisor's warm restart) enters it. A no-op without a device."""
        return jax.default_device(self._device)

    def _loop(self) -> None:
        me = threading.current_thread()
        try:
            with self._device_scope():
                self._loop_body(me)
        except _ThreadRetired:
            return  # quarantined thread thawed: exit, touch nothing
        except BaseException as exc:
            # an escape from the body (the engine.step chaos point sits
            # OUTSIDE the per-step recovery, like a C-extension aborting
            # mid-dispatch would) is an unhandled loop exit: flag it so the
            # supervisor's watchdog can tell "crashed" from "stopped"
            if self._running and me is self._thread:
                self.loop_crashed = True
                if self._logger:
                    import traceback

                    self._logger.error(
                        "serving engine loop thread died",
                        stack=traceback.format_exc(limit=20),
                    )
            if not isinstance(exc, Exception):
                raise  # SystemExit/KeyboardInterrupt must propagate
            # ordinary exceptions end here: the crash flag + log ARE the
            # signal — re-raising would only spam the thread excepthook

    def _loop_body(self, me: threading.Thread) -> None:
        cfg = self.config
        # a replaced thread may have left a phase open in the account:
        # this thread's first gofr.step is a root
        self._phase_state = (None, time.monotonic(), self._phase_state[2])
        # the identity guard retires a quarantined thread: after a warm
        # restart that could not join it, self._thread points at the NEW
        # loop thread — the old one must exit the moment it thaws instead
        # of racing the replacement over rebuilt state
        while self._running and me is self._thread:
            self.heartbeat = time.monotonic()
            chaos.maybe_fail("engine.step")
            if not self._running or me is not self._thread:
                # stopped or replaced while hung at the chaos point: re-check
                # the loop condition instead of running one doomed iteration
                # (a warm_restart waiting in join() has already swept the
                # queue this iteration would admit from)
                continue
            self._step_iter += 1
            try:
                # one gofr.step a loop iteration; mono_ns puts RequestTimeline's
                # clock (and /requestz's stamps) beside the profiler's, so an
                # operator's trace aligns without a harness
                with self._phase("step", iter=self._step_iter,
                                 mono_ns=time.monotonic_ns()):
                    self._loop_step()
                if self._metrics:
                    self._count_phases()
            except Exception as exc:  # the step must never kill the loop
                # a retired thread's step error is noise from quarantined
                # state — it must not _fail_all (that would sweep the
                # REPLACEMENT engine's requests) or keep looping
                self._check_retired()
                if self._logger:
                    import traceback

                    self._logger.error(
                        f"serving engine step error: {exc}",
                        stack=traceback.format_exc(limit=20),
                    )
                self._fail_all(exc)
                # gofrlint: disable=blocking-call -- error backoff in the
                # dedicated engine thread, bounded by idle_sleep_s
                time.sleep(cfg.idle_sleep_s)

    def _loop_step(self) -> None:
        """One iteration's work, each part a phase (_phase). What the
        iteration spends outside a named phase is charged to "step"."""
        # the preemption ladder runs BEFORE the plan: a freed slot is
        # admitted in this same iteration, so a waiting higher class
        # pays at most one loop latency
        with self._phase("preempt"):
            did_work = self._maybe_preempt()
            if self._reclaiming:
                # a reclamation notice sheds batch-class rows NOW
                # (warm page-out, retriable failure) so the remaining
                # drain budget serves interactive streams only
                did_work |= self._reclaim_sweep()
        plan = self._plan_step()
        with self._phase("admit") as span:
            did_work |= self._admit(plan, span)
        if any(s is not None for s in self.slots):
            did_work |= self._decode_step(plan)
        elif self._inflight_q:
            # drain: every row of the in-flight blocks retired while
            # they ran; their tokens are stale by construction
            self._consume_block(self._inflight_q.popleft())
            did_work = True
        else:
            self._last_consume_t = None  # idle gap must not skew TPOT
        if not did_work:
            if (self._draining and not self._inflight_q
                    and not any(s is not None for s in self.slots)
                    and self._sched.stats()["queue_depth"] == 0):
                # drained dry: every accepted request reached a
                # terminal state; drain() is waiting on this
                self._idle.set()
            # the one phase busy_seconds() leaves out: the telemetry
            # poller divides the busy delta by wall time
            # (app_engine_duty_cycle)
            with self._phase("wait"):
                self._wake.wait(timeout=0.05)
            self._wake.clear()

    # -- admission -------------------------------------------------------------
    def _plan_step(self) -> StepPlan:
        """Assemble this iteration's step plan (serving/stepplan.py):
        decode rows reserved first, chunk grants for partially-prefilled
        cursors, an admission quota out of the leftover budget."""
        with self._phase("plan") as span:
            decode_rows = sum(
                1 for slot, req in enumerate(self.slots)
                if req is not None and slot not in self._cursors
            )
            free_slots = sum(1 for s in self.slots if s is None)
            queue_depth = self._sched.pending()
            span.set(decode_rows=decode_rows, cursors=len(self._cursors),
                     queue=queue_depth)
            plan = self._planner.plan(
                decode_rows=decode_rows,
                cursors=list(self._cursors.values()),
                free_slots=free_slots,
                queue_depth=queue_depth,
            )
            span.set(grants=len(plan.grants))
            if self._metrics:
                # set on CHANGE (including the drop back to zero at idle —
                # a frozen non-zero gauge would report phantom load
                # forever), skipped in steady state to keep per-iteration
                # host cost flat
                snapshot = (plan.prefill_tokens, decode_rows,
                            len(self._cursors))
                if snapshot != self._plan_gauges:
                    self._plan_gauges = snapshot
                    self._metrics.set_gauge(
                        "app_step_plan_prefill_tokens", plan.prefill_tokens
                    )
                    self._metrics.set_gauge(
                        "app_step_plan_decode_rows", decode_rows
                    )
                    self._metrics.set_gauge(
                        "app_step_plan_cursors", len(self._cursors)
                    )
        return plan

    def _route_chunked(self, prompt_len: int) -> bool:
        """True when a prompt prefills through chunk cursors + the ragged
        dispatch instead of one monolithic bucketed prefill: longer than a
        chunk, or longer than every bucket (the monolithic path cannot
        scatter past its biggest bucket)."""
        if not self._chunk_enabled:
            return False
        return (prompt_len > self._chunk_tokens
                or prompt_len > max(self._buckets()))

    def _admit(self, plan: StepPlan, span: _StepPhase) -> bool:
        # bind ONCE: a warm restart that replaces this thread mid-admit
        # swaps self._sched for a rebuilt one — the pairs delivered below
        # belong to THIS scheduler, and releases/requeues must never land
        # on the replacement's
        sched = self._sched
        if not sched.pending():
            # admit cadence: nothing queued (canceled requests stay queued
            # until delivered, so they keep the depth nonzero) — skip the
            # native admit round trip entirely; per-block host overhead is
            # the budget this loop is built around
            return False
        # the plan's quota is never 0 while the queue is non-empty (a
        # canceled-but-queued request resolves only through an admit
        # delivery); max(…, 1) covers a submit that raced in after the
        # plan read its queue depth
        pairs, canceled_ids = sched.admit(max(plan.admit_cap, 1))
        span.set(admitted=len(pairs))
        # the admit call itself can hang (native mutex held under a wedged
        # step); a thread thawing out of it retired would otherwise process
        # the old scheduler's pairs against the REPLACEMENT engine's state
        # — releasing its slots, allocating its pages for requeued rids
        self._check_retired()
        for rid in canceled_ids:
            with self._count_lock:
                req = self._by_id.pop(rid, None)
            if req is not None:
                self._finish(req, "cancel")
        for rid, slot in pairs:
            with self._count_lock:
                req = self._by_id.get(rid)
            if req is None:  # should not happen; release the slot defensively
                sched.release(slot)
                continue
            if req.canceled:  # canceled between admit() and here
                sched.release(slot)
                with self._count_lock:
                    self._by_id.pop(rid, None)
                self._finish(req, "cancel")
                continue
            if req.expired(time.perf_counter()):
                # expired while queued: NEVER prefill it — the answer is
                # already useless, the prefill would only steal TTFT from
                # live requests. 504 / DEADLINE_EXCEEDED to the caller.
                sched.release(slot)
                with self._count_lock:
                    self._by_id.pop(rid, None)
                self._expire(req)
                continue
            # admission reached: stamp the queue→batch transition and
            # close the queue span (first stamp wins, so a page-pressure
            # requeue keeps its original queue-wait truth)
            tl = req.timeline
            if tl is not None and "admitted" not in tl.phases:
                now = time.perf_counter()
                tl.stamp("admitted")
                tl.loop_admit = self.loop_account()
                queue_wait = now - req.created
                qspan = tl.spans.get("queue")
                if qspan is not None:
                    qspan.set_attribute("queue.wait_s", round(queue_wait, 6))
                    qspan.end()
                if self._metrics:
                    labels = (
                        {"tenant": req.tenant} if req.tenant else {}
                    )
                    self._metrics.record_histogram(
                        "app_request_queue_wait_seconds", queue_wait,
                        **labels,
                    )
            try:
                if self._lora is not None and req.adapter_id:
                    from gofr_tpu.serving.lora import AdapterBusy

                    try:
                        # pin the adapter's device-table slot for the
                        # life of the row; every table slot pinned (or a
                        # faulted async upload) is TRANSIENT — requeue
                        # exactly like KV-pool pressure. The wait is
                        # clamped to the request's remaining deadline: a
                        # slow upload degrades to AdapterBusy → requeue,
                        # and the expired-while-queued check 504s the
                        # request next round instead of letting the
                        # acquire outlive it
                        budget = 5.0
                        rem = req.remaining(time.perf_counter())
                        if rem is not None:
                            budget = min(budget, rem)
                        req.adapter_slot = self._lora.acquire(
                            req.adapter_id, timeout=budget
                        )
                    except AdapterBusy:
                        raise _RequeueRequest() from None
                if self._route_chunked(len(req.serve_ids)):
                    self._start_cursor(slot, req)
                else:
                    self._prefill_into(slot, req)
            except _RequeueRequest:
                # transient (KV pages exhausted): back to the HEAD of its
                # priority class (it keeps its FIFO position — later smaller
                # requests must not starve it); the REST of the admitted
                # batch still proceeds — their slots are already claimed and
                # the scheduler never re-delivers an admitted pair
                self._check_retired()  # warm_restart already requeued it
                self._lora_release(req)
                sched.release(slot)
                try:
                    sched.submit(
                        rid, len(req.serve_ids), req.max_new_tokens,
                        req.priority, front=True,
                    )
                except Exception:
                    with self._count_lock:
                        self._by_id.pop(rid, None)
                    self._try_resolve(req, exc=ErrorTooManyRequests())
            except Exception as exc:
                # a failed prefill must not leak the slot, its KV pages, or
                # hang the client. A RETIRED thread unwinds instead: its
                # request was already requeued/settled by warm_restart, and
                # slots/pools here belong to the replacement engine.
                self._check_retired()
                self._lora_release(req)
                self.slots[slot] = None
                self.cache_len[slot] = 0
                if self.paged_cache is not None:
                    try:
                        self.paged_cache.free_slot(slot)
                    except Exception:
                        pass
                try:
                    sched.release(slot)
                except KeyError:
                    pass
                with self._count_lock:
                    self._by_id.pop(rid, None)
                self._try_resolve(req, exc=exc)
                if self._logger:
                    self._logger.error(f"prefill failed for request {rid}: {exc}")
                # pure host-side rejections (queue/page-budget limits) never
                # touched the device — don't pay a blocking probe for them
                if not isinstance(
                    exc, (ErrorTooManyRequests, ErrorRequestEntityTooLarge)
                ) and self._kv_unhealthy():
                    # the failing call donated the SHARED cache (insert_slot*/
                    # write_prefill) and died after donation committed: every
                    # active slot's KV is gone, not just this request's —
                    # isolated cleanup would leave the engine raising
                    # "Array has been deleted" on every future step
                    self._fail_all(exc, kv_unhealthy=True)
        self._observe_queue()
        return bool(pairs or canceled_ids)

    def _lora_adjusted(self, req: _Request, last_logits: Any,
                       last_token: int) -> Any:
        """Apply the row's adapter delta to host-path last-position
        logits before first-token sampling (monolithic prefill, full
        chunk-prefix hits). Pure device op, no sync; base rows return
        the logits untouched."""
        if self._lora is None or not req.adapter_slot:
            return last_logits
        factors = self._lora.slot_factors(req.adapter_slot)
        if factors is None:
            return last_logits
        return batch_ops.lora_adjust_logits(
            self.params["embedding"], factors[0], factors[1],
            jnp.int32(last_token), last_logits,
        )

    def _sample_first(self, phase: _StepPhase, req: _Request,
                      last_logits: Any, last_token: int) -> Any:
        """An admission's first token on the host path (bucketed prefill,
        whole-prompt chunk-prefix hit): the request's own parameters, the
        key folded from its id, one program (batch_ops.sample_first_token)
        and no sync — the caller reads ids [1] under ``prefill_sync``.
        ``phase``, the admission's gofr.step.prefill, gets the sampler's
        path."""
        key = jax.random.fold_in(self._rng_root, req.id)
        sampling = (np.float32(req.temperature), np.int32(req.top_k),
                    np.float32(req.top_p))
        self._count_sampler(phase, *sampling, steps=1)
        return batch_ops.sample_first_token(
            self._lora_adjusted(req, last_logits, last_token), key, *sampling
        )

    def _lora_release(self, req: _Request) -> None:
        """Unpin a row's adapter-table slot (no-op for base rows). Every
        path that takes a row out of the batch — retire, requeue,
        preempt, fail-all, the restart sweep — funnels through this so a
        pin can never outlive its row."""
        if req.adapter_slot and self._lora is not None:
            self._lora.release(req.adapter_slot)
            req.adapter_slot = 0

    # -- KV reuse tiers (prefix cache + host spill + cluster migration) --------
    def _cache_lookup(self, key: str) -> tuple[Any, str]:
        """Prefix-cache lookup with tier attribution: ``(value, tier)``
        where tier is ``device`` / ``host`` / ``miss``. Plain (single-
        tier) caches report ``device`` on a hit."""
        cache = self._prefix_cache
        tiered = getattr(cache, "get_with_tier", None)
        if tiered is not None:
            value, tier = tiered(key)
        else:
            value = cache.get(key)
            tier = "device" if value is not None else "miss"
        if value is not None:
            # feed the spill tier's demotion scorer: the flight recorder
            # keeps the per-key reuse counts the byte-pressure eviction
            # orders by (host dict write, zero device work)
            self.timeline.observe_prefix_reuse(key)
            if self._device is not None:
                # a colocated peer's push/fetch hands over arrays on ITS
                # device; a no-op for entries already resident here
                value = jax.device_put(value, self._device)
        return value, tier

    def _record_prefix_tier(self, req: _Request, tier: str) -> None:
        """Stamp the request's warmest-source attribution — the
        ``/requestz`` timeline's ``prefix_tier`` and the per-tier hit
        counter (docs/observability.md). First stamp wins on the
        timeline (a pool-pressure requeue keeps its original truth);
        the counter counts admission walks."""
        tl = req.timeline
        if tl is not None and tl.prefix_tier is None:
            tl.prefix_tier = tier
        if self._metrics:
            self._metrics.increment_counter(
                "app_kv_prefix_hits_total", tier=tier
            )

    def prefix_advertisement(self, limit: int = 128) -> list[list[str]] | None:
        """This replica's bounded [key, tier] advertisement for the
        distributed prefix index (serving/prefix_index.py), carried on
        the membership heartbeat. None when the cache exposes no key
        listing (injected container caches)."""
        cache = self._prefix_cache
        if cache is None:
            return None
        advertised = getattr(cache, "advertised", None)
        if advertised is not None:
            pairs = advertised(limit)
        else:
            keys_fn = getattr(cache, "keys", None)
            if keys_fn is None:
                return None
            pairs = [
                (str(k), "device")
                for k in list(reversed(keys_fn()))[:limit]
            ]
        return [[key, tier] for key, tier in pairs]

    def _prefill_into(self, slot: int, req: _Request) -> None:
        cfg = self.model_cfg
        # serve_ids = prompt + already-emitted tokens: identical to the
        # prompt for a fresh request; a preempted request re-prefills its
        # whole generated context and resumes from the NEXT token
        ids = req.serve_ids
        S = len(ids)
        bucket = batch_ops.pad_bucket(S, self._buckets())
        with self._phase("prefill", rid=req.id, bucket=bucket,
                         tokens=S) as phase:
            tokens = np.full((1, bucket), self.tokenizer.pad_id, np.int32)
            tokens[0, :S] = ids
            seq_len = jnp.array([S], jnp.int32)

            if self.paged_cache is not None:
                # page reservation first: OutOfBlocks must requeue BEFORE any
                # device work (the request keeps its place; pool pressure is a
                # transient, not an error) — unless the prompt can NEVER fit,
                # which must fail the request, not livelock the admit loop
                from gofr_tpu.serving.kv_cache import OutOfBlocks

                if self.paged_cache.pages_needed(bucket) > self.paged_cache.num_pages:
                    # permanent, not transient: however empty the pool gets,
                    # this prompt can NEVER fit — a 429 would invite clients to
                    # retry forever; 413 / FAILED_PRECONDITION says "shrink it"
                    raise ErrorRequestEntityTooLarge(
                        f"prompt needs {self.paged_cache.pages_needed(bucket)} KV pages; "
                        f"pool has {self.paged_cache.num_pages} in total"
                    )
                try:
                    self.paged_cache.alloc_slot(
                        slot, seq_id=req.id, prompt_len=S, reserve_tokens=bucket
                    )
                except OutOfBlocks:
                    raise _RequeueRequest() from None

            cache_key = None
            cached = None
            prefix_tier = None
            if self._prefix_cache is not None:
                # sampling params are NOT in the key: the cached value is the
                # pre-sampling prefill output, shared across temperatures.
                # A STRING key keeps the injected-cache contract (the container
                # Cache protocol declares str keys; a datasource-backed cache
                # can serialize it directly).
                import hashlib as _hashlib

                digest = _hashlib.blake2b(
                    np.asarray(ids, np.int32).tobytes(), digest_size=16
                ).hexdigest()
                # the adapter id is part of the key BY CONSTRUCTION: a
                # cross-adapter KV hit is impossible however the cache is
                # shared/migrated (docs/serving.md "Multi-tenancy")
                cache_key = (
                    f"prefill:{bucket}:{S}:{digest}:{req.adapter_id or '-'}"
                )
                cached, prefix_tier = self._cache_lookup(cache_key)
                if cached is None and self._kv_migrator is not None:
                    # disaggregated handoff first (the router named the
                    # prefill source — no heartbeat-advertisement wait), then
                    # the advisory cluster tier: another replica advertises
                    # this exact prefill — migrate its slabs instead of
                    # recomputing (either failure stays a compute miss)
                    fetched = None
                    # the fetch is bounded by what the request has left: an
                    # expired one degrades to a compute miss without a fetch
                    budget = req.remaining(time.perf_counter())
                    if req.handoff_from is not None:
                        fetched = self._kv_migrator.fetch_one_handoff(
                            cache_key, req.handoff_from, deadline=budget
                        )
                    if fetched is None:
                        fetched = self._kv_migrator.fetch_one(
                            cache_key, deadline=budget
                        )
                    # the fetch can block (remote transport timeout): a warm
                    # restart may have retired this thread meanwhile — the
                    # put below would poison the cache the restart just
                    # reset (the same hazard as the compute-path put)
                    self._check_retired()
                    if fetched is not None:
                        from gofr_tpu.serving.kv_spill import _to_device

                        cached = _to_device(fetched, self._device)
                        prefix_tier = "remote"
                        # pay the transfer once per replica, not per request
                        self._prefix_cache.put(cache_key, cached)
                self._record_prefix_tier(req, prefix_tier)
            phase.set(route="bucketed" if cached is None else "prefix_hit")
            if self._upper_on_last:
                self._count_prefill_positions(phase, S, 1, resets=1)

            tl = req.timeline
            if tl is not None:
                tl.stamp("prefill_start")
            span = self._req_span(
                "prefill",
                f"serve.prefill b{bucket}" + (" (prefix hit)" if cached else ""),
                req,
            )
            if tl is not None:
                pspan = tl.spans.get("prefill")
                if pspan is not None:
                    pspan.set_attribute("prefill.bucket", bucket)
                    pspan.set_attribute("prefill.prefix_hit", cached is not None)
                    pspan.set_attribute("prefix_tier", prefix_tier or "miss")
                    pspan.set_attribute("tokens.prompt", S)
            # bind the KV storage ONCE, before the long dispatch: a warm
            # restart that replaces this thread mid-compute swaps
            # self.paged_cache/self.cache for rebuilt ones — re-reading them
            # after the dispatch would donate the REPLACEMENT engine's pools
            # from a quarantined thread
            pc, dense = self.paged_cache, self.cache
            with span, self._cold_dispatch("prefill", bucket, cached is not None):
                if cached is not None:
                    last_logits, k_slab, v_slab = cached
                else:
                    last_logits, k_slab, v_slab = batch_ops.prefill_compute(
                        cfg, self.params, jnp.asarray(tokens), seq_len
                    )
                self._check_retired()  # replaced during the compute: no writes
                # ...including the prefix cache: a retired thread thawing out
                # of a device-loss hang would insert DEAD slabs into the cache
                # warm_restart just reset, poisoning every future hit on this
                # prefix
                if cached is None and cache_key is not None:
                    # slabs are fresh, never-donated arrays: safe to retain
                    self._prefix_cache.put(cache_key, (last_logits, k_slab, v_slab))
                if pc is not None:
                    pc.write_prefill(slot, k_slab, v_slab)
                else:
                    dense.k, dense.v = batch_ops.insert_slot(
                        dense.k, dense.v, k_slab, v_slab, jnp.int32(slot)
                    )
                # sample the first token with this request's params, keyed by
                # request id (NOT the shared stream — see _rng_root above).
                # The row's LoRA delta applies HERE, at the sampling site —
                # cached entries stay base-model logits (adapter-scoped keys
                # already make cross-adapter hits impossible).
                first = self._sample_first(phase, req, last_logits, ids[-1])
                # the engine thread's other read of the device, after
                # the block's one sync: it returns when the block in
                # flight, this prefill and the sampler's one program have
                # run, and no next block is queued meanwhile.
                with self._phase("prefill_sync", rid=req.id):
                    first_id = int(first[0])

            # the dispatch is back: a warm restart may have replaced this
            # thread while it sat in the compile — commit nothing if so (the
            # request was requeued; the successor thread redoes the prefill)
            self._check_retired()
            # progress stamp: a multi-prefill admission can legitimately
            # outlast TPU_ENGINE_STALL_S in one loop iteration — the watchdog
            # must see "slow but moving", not "hung"; a truly stuck dispatch
            # stamps nothing anywhere (and a first-call jit compile widens the
            # threshold via _cold_dispatch above)
            self.heartbeat = time.monotonic()
            self._commit_prefilled(slot, req, first_id, S)

    def _commit_prefilled(self, slot: int, req: _Request, first_id: int,
                          resident: int) -> None:
        """First-token commit shared by the monolithic prefill path and a
        full chunk-prefix cache hit: slot bookkeeping, the DecodeState
        admission fold, TTFT stamps/metrics, first-token emission and the
        stop/length retire chain."""
        req.slot = slot
        self.slots[slot] = req
        self.cache_len[slot] = resident
        self.last_token[slot] = first_id
        self.temperature[slot] = req.temperature
        self.top_k[slot] = req.top_k
        self.top_p[slot] = req.top_p
        self.adapter_idx[slot] = req.adapter_slot
        # folded into the device-resident DecodeState by one donated
        # scatter at the next dispatch: (first token, resident len,
        # remaining budget, stop id, adapter slot). The budget carries
        # BOTH limits — max_new and the sequence cap (submit already
        # clamped max_new to the sequence budget) — and counts only the
        # REMAINING tokens, so a preempted request resumes with what it
        # has left, not a fresh allowance. A multi-token stop set
        # disables device stop-eval (-1 sentinel); the host's
        # _commit_token still enforces it at each sync.
        self._pending_admit[slot] = (
            first_id, resident, req.new_budget - 1,
            next(iter(req.stop_ids)) if len(req.stop_ids) == 1 else -1,
            req.adapter_slot,
        )
        self._commit_first_token(slot, req, first_id)

    # -- chunked prefill (continuous batching) ---------------------------------
    def _chunk_cache_keys(
        self, prompt_ids: list[int], adapter_id: str | None = None,
    ) -> list[tuple[int, int, str]]:
        """Chunk-prefix cache keys for every chunk boundary of a prompt:
        chunk geometry + the content digest of the FULL prefix up to each
        boundary — two prompts sharing a prefix share its chunk entries,
        and a chunk-size change can never alias. The ADAPTER ID is part
        of the key: same prompt under two adapters is two cache chains,
        so a cross-adapter KV hit is impossible by construction — here,
        in the distributed prefix index, and across disaggregated
        handoffs (the keys are content-addressed everywhere). ONE
        incremental blake2b pass with a copy() snapshot per boundary:
        digesting each prefix from scratch would be quadratic in prompt
        length on the engine thread."""
        import hashlib as _hashlib

        arr = np.asarray(prompt_ids, np.int32)
        aid = adapter_id or "-"
        h = _hashlib.blake2b(digest_size=16)
        out: list[tuple[int, int, str]] = []
        pos, total = 0, len(prompt_ids)
        while pos < total:
            end = min(pos + self._chunk_tokens, total)
            h.update(arr[pos:end].tobytes())
            key = (
                f"chunkpfx:{self._chunk_tokens}:{pos}:{end}:"
                f"{h.copy().hexdigest()}:{aid}"
            )
            out.append((pos, end, key))
            pos = end
        return out

    def _start_cursor(self, slot: int, req: _Request) -> None:
        """Admit a long prompt as a chunk cursor: claim the slot, skip any
        already-cached chunk prefixes, and leave the rest of the prompt to
        the step planner's chunk grants. Raises before touching slot state
        on page pressure (_RequeueRequest) or a never-fits prompt (413) —
        the _admit cleanup contract."""
        ids = req.serve_ids  # prompt + emitted tokens (preempt resume)
        total = len(ids)
        with self._phase("prefill", rid=req.id, bucket=0,
                         tokens=total) as phase:
            pc = self.paged_cache
            if pc is not None and pc.pages_needed(total) > pc.num_pages:
                raise ErrorRequestEntityTooLarge(
                    f"prompt needs {pc.pages_needed(total)} KV pages; "
                    f"pool has {pc.num_pages} in total"
                )

            # probe the prefix cache for the longest chain of cached
            # chunk-boundary prefixes (each entry holds that chunk's K/V delta
            # slab + the prefix's last-position logits). The boundary keys are
            # computed ONCE per tenancy and ride the cursor — the per-chunk
            # PUT at consume reuses them instead of re-digesting the prefix.
            hits: list[tuple[int, int, Any]] = []
            pos = 0
            cache_keys: dict[tuple[int, int], str] | None = None
            tiers: set[str] = set()
            if self._prefix_cache is not None:
                boundaries = self._chunk_cache_keys(ids, req.adapter_id)
                cache_keys = {(s, e): k for s, e, k in boundaries}
                for start, end, key in boundaries:
                    val, tier = self._cache_lookup(key)
                    if val is None:
                        break
                    if end >= total and val[0].shape[-1] != self.model_cfg.vocab_size:
                        # a preemption page-out stored this span with a
                        # PLACEHOLDER logits column (the paged-out row never
                        # had last-position logits to give). Its KV is good
                        # as a NON-final link, but it must never serve as the
                        # chain's final entry — the zero-dispatch admit below
                        # would sample this request's first token from
                        # garbage. Stop the walk; the tail chunk recomputes
                        # and samples fresh.
                        break
                    hits.append((start, end, val))
                    tiers.add(tier)
                    pos = end
                if pos < total and self._kv_migrator is not None:
                    # disaggregated handoff first: the router named the
                    # prefill source, and the fetch runs under the kv.handoff
                    # two-phase-commit discipline — a COMPLETE, contiguity-
                    # audited chain or nothing (a torn handoff must never
                    # commit a partial chain it believed complete). A source
                    # or transport failure returns [] and the normal
                    # advisory tiers below degrade to re-prefill.
                    remaining = [b for b in boundaries if b[0] >= pos]
                    fetched = []
                    # bounded by the request's remaining deadline, exactly
                    # like the monolithic path's handoff/advisory fetches
                    budget = req.remaining(time.perf_counter())
                    if req.handoff_from is not None:
                        fetched = self._kv_migrator.fetch_handoff(
                            remaining, req.handoff_from, deadline=budget
                        )
                    if not fetched:
                        # cluster tier: migrate the longest advertised
                        # chunk-boundary chain from the owning replica. The
                        # fetch is advisory and contiguous-from-pos by
                        # contract — a torn transfer keeps the fetched prefix
                        # and the planner's chunk grants compute the rest
                        # (never a double-prefill: committed spans stay
                        # contiguous).
                        fetched = self._kv_migrator.fetch_chain(
                            remaining, deadline=budget
                        )
                    # the fetch can block (remote transport timeout): a
                    # retired thread must not put dead slabs into the
                    # replacement engine's freshly-reset cache
                    self._check_retired()
                    if fetched:
                        from gofr_tpu.serving.kv_spill import _to_device

                        for start, end, val in fetched:
                            # async upload, no sync
                            val = _to_device(val, self._device)
                            if (end >= total and
                                    val[0].shape[-1] != self.model_cfg.vocab_size):
                                break  # peer's preempt placeholder: same
                                # final-entry guard as the local walk above
                            hits.append((start, end, val))
                            pos = end
                            # pay the transfer once per replica: later
                            # requests sharing this prefix hit locally
                            self._prefix_cache.put(
                                cache_keys[(start, end)], val
                            )
                        tiers.add("remote")
                self._record_prefix_tier(
                    req,
                    "remote" if "remote" in tiers
                    else "host" if "host" in tiers
                    else "device" if hits else "miss",
                )

            from gofr_tpu.serving.kv_cache import OutOfBlocks

            if hits and pc is not None:
                try:
                    pc.alloc_slot(slot, seq_id=req.id, prompt_len=0,
                                  reserve_tokens=pos)
                except OutOfBlocks:
                    raise _RequeueRequest() from None

            tl = req.timeline
            if tl is not None:
                tl.stamp("prefill_start")
            for start, end, (_logits, k_slab, v_slab) in hits:
                if pc is not None:
                    pc.write_span(slot, start, k_slab, v_slab)
                else:
                    dense = self.cache
                    dense.k, dense.v = batch_ops.insert_chunk(
                        dense.k, dense.v, k_slab, v_slab,
                        jnp.int32(slot), jnp.int32(start),
                    )
            if hits:
                if pc is not None:
                    pc.advance_slot(slot, pos)
                if tl is not None:
                    tl.chunk(0, pos, prefix_hit=True)
                if self._metrics:
                    self._metrics.record_histogram(
                        "app_prefill_chunk_tokens", pos, kind="prefix_hit",
                    )

            phase.set(route="chunked" if pos < total else "prefix_hit")
            if pos >= total:
                # the WHOLE prompt was cached at chunk boundaries: sample the
                # first token from the cached last-position logits and admit
                # straight to decode — zero prefill dispatches (the admission-
                # path sync mirrors the monolithic prefix-hit path)
                span = self._req_span("prefill", "serve.prefill chunked (prefix hit)", req)
                if tl is not None:
                    pspan = tl.spans.get("prefill")
                    if pspan is not None:
                        pspan.set_attribute("prefill.prefix_hit", True)
                        pspan.set_attribute(
                            "prefix_tier", tl.prefix_tier or "device"
                        )
                with span:
                    last_logits = hits[-1][2][0]
                    first = self._sample_first(phase, req, last_logits, ids[-1])
                    with self._phase("prefill_sync", rid=req.id):
                        first_id = int(first[0])
                self._check_retired()
                self._commit_prefilled(slot, req, first_id, total)
                return

            cursor = ChunkCursor(req=req, slot=slot, total=total,
                                 seq=self._cursor_seq, priority=req.priority)
            self._cursor_seq += 1
            cursor.cache_keys = cache_keys
            cursor.committed = cursor.dispatched = pos
            cursor.prefix_hit = pos
            cursor.chunk_index = 1 if hits else 0
            cursor.allocated = bool(hits and pc is not None)
            req.slot = slot
            self.slots[slot] = req
            self.cache_len[slot] = pos
            self.last_token[slot] = 0
            self.temperature[slot] = req.temperature
            self.top_k[slot] = req.top_k
            self.top_p[slot] = req.top_p
            self.adapter_idx[slot] = req.adapter_slot
            self._cursors[slot] = cursor

    def _cursor_requeue(self, slot: int, req: _Request,
                        cursor: ChunkCursor) -> None:
        """Transient KV-pool pressure mid-chunked-prefill: give the pages
        back and requeue the request from chunk 0 at the head of its
        priority class — prefill pressure is a transient, not an error.
        Only legal with nothing in flight for the cursor (an in-flight
        ragged dispatch still writes through this slot's pages)."""
        self._cursors.pop(slot, None)
        self.slots[slot] = None
        self.cache_len[slot] = 0
        req.slot = None
        self._lora_release(req)
        if self.paged_cache is not None:
            try:
                self.paged_cache.free_slot(slot)
            except Exception:
                pass
        sched = self._sched
        try:
            sched.release(slot)
        except KeyError:
            pass
        try:
            # gofrlint: disable=retry-unbudgeted -- expiry is gated upstream: _cursor_health checks req.expired before every pressure requeue, and admission re-checks it next round (504)
            sched.submit(
                req.id, len(req.serve_ids), req.max_new_tokens,
                req.priority, front=True,
            )
        except Exception:
            with self._count_lock:
                self._by_id.pop(req.id, None)
            self._try_resolve(req, exc=ErrorTooManyRequests())

    def _cursor_health(self, slot: int, req: _Request, cursor: ChunkCursor,
                       now: float) -> None:
        """Mid-chunk retirement/requeue gate, run at each dispatch scan:
        cancel and deadline expiry retire the partially-prefilled row;
        pool pressure requeues it from chunk 0 — all deferred while a
        dispatched ragged chunk is still in flight for the slot (its
        writes ride the page tables snapshotted at dispatch; freeing the
        pages under it would hand them to another row)."""
        if cursor.in_flight > 0:
            return
        if req.canceled:
            self._retire(slot, "cancel")
        elif req.expired(now):
            self._retire(slot, "deadline_exceeded")
        elif cursor.blocked:
            self._cursor_requeue(slot, req, cursor)

    # -- tenant preemption (docs/serving.md "Multi-tenancy") -------------------
    def _maybe_preempt(self) -> bool:
        """The preemption ladder: when a STRICTLY higher class (lower
        priority number) waits and the batch cannot take it — no free
        slot, or (paged) the pool cannot cover its prompt — pause the
        lowest-priority decode row. Its committed KV pages out through
        the prefix-cache/host-spill tier (:meth:`_preempt`), the slot
        frees, and the row resumes warm later with its emitted tokens
        intact. Equal classes never preempt each other (no ping-pong: a
        resumed row keeps its priority, so it can never evict what
        evicted it). Engine-thread only; a few dict walks per iteration
        and only when something is actually waiting."""
        if not self.config.tenant_preempt or self._tenants is None:
            return False
        if self.config.spec_tokens > 0:
            return False  # spec rows carry un-resumable draft state
        with self._count_lock:
            waiting = [
                r for r in self._by_id.values()
                if r.slot is None and not r.canceled
            ]
        if not waiting:
            self._preempt_pending.clear()
            return False
        best = min(r.priority for r in waiting)
        slot_pressure = all(s is not None for s in self.slots)
        page_pressure = False
        if not slot_pressure and self.paged_cache is not None:
            need = min(
                self.paged_cache.pages_needed(len(r.serve_ids))
                for r in waiting if r.priority == best
            )
            page_pressure = (
                need > self.paged_cache.free_pages()
            )
        if not slot_pressure and not page_pressure:
            self._preempt_pending.clear()  # the pressure passed: resume
            return False
        # a pending victim preempts the moment its pipelined blocks drain
        # (the dispatch loop stopped feeding it when it went pending —
        # preempting under an in-flight block would free pages the
        # dispatched device work still writes through)
        for slot in sorted(self._preempt_pending):
            req = self.slots[slot]
            if req is None or req.priority <= best:
                self._preempt_pending.discard(slot)
                continue
            cursor = self._cursors.get(slot)
            if self._slot_in_flight(slot, req) or (
                cursor is not None and cursor.in_flight > 0
            ):
                return False  # draining: the consume side lands first
            self._preempt_pending.discard(slot)
            self._preempt(slot)
            return True
        victim = None
        for slot, req in enumerate(self.slots):
            if req is None or req.priority <= best:
                continue  # never preempt an equal-or-higher class
            if victim is None or (
                (req.priority, len(req.tokens))
                > (self.slots[victim].priority, len(self.slots[victim].tokens))
            ):
                # lowest class first; ties pick the row with MORE tokens
                # out (its resume is warmest — every committed chunk is
                # already in the cache chain)
                victim = slot
        if victim is None:
            return False
        cursor = self._cursors.get(victim)
        req = self.slots[victim]
        if self._slot_in_flight(victim, req) or (
            cursor is not None and cursor.in_flight > 0
        ):
            # stop feeding the row and preempt once the pipeline drains
            self._preempt_pending.add(victim)
            return False
        self._preempt(victim)
        return True

    def _preempt(self, slot: int, *, reclaim: bool = False) -> None:
        """Pause one decode row: page its committed whole-chunk KV spans
        out into the prefix cache (whence device-LRU pressure demotes
        them to the PR 11 host-RAM spill tier), free the slot + pages,
        and requeue the request. Resume is the ordinary re-admission of
        ``serve_ids`` (prompt + emitted tokens): the boundary walk finds
        the paged-out chunks and warm-restores them, the tail chunk
        recomputes, and the NEXT token samples — emitted tokens are
        preserved and never re-emitted. The ``tenant.preempt`` chaos
        point makes the policy advisory by construction: a fault there
        skips this preemption, never corrupts the row.

        ``reclaim=True`` is the reclamation-notice variant
        (:meth:`_reclaim_sweep`): same warm page-out — the spans then
        ride the bulk evacuation to a survivor — but the row settles
        RETRIABLE instead of requeueing (this replica is doomed; the
        router's retry lands on a survivor whose re-prefill the
        evacuated chunks make warm). Not advisory: the chaos point for
        the notice path is ``replica.reclaim`` at delivery."""
        req = self.slots[slot]
        if req is None:
            return
        if not reclaim:
            try:
                chaos.maybe_fail("tenant.preempt")
            except Exception:
                return  # advisory: a faulted preemption is a skipped one
        ids = req.serve_ids
        resident = int(self.cache_len[slot])
        # page out whole chunk-boundary spans below the resident length —
        # and strictly below the total, so the resume always computes at
        # least the final tail chunk (whose logits seed the next token).
        if self._prefix_cache is not None and not req.prefill_only:
            boundaries = self._chunk_cache_keys(ids, req.adapter_id)
            for start, end, key in boundaries:
                if end > resident or end >= len(ids):
                    break
                if self.paged_cache is not None:
                    k_slab, v_slab = self.paged_cache.read_span(
                        slot, start, end
                    )
                else:
                    k_slab = self.cache.k[:, slot, start:end]
                    v_slab = self.cache.v[:, slot, start:end]
                # the span entry's logits column is never read: the walk
                # stops before the prompt's end by construction (see
                # above), so a placeholder keeps the (logits, k, v)
                # value shape without retaining a live buffer
                self._prefix_cache.put(
                    key, (jnp.zeros((1, 1), jnp.float32), k_slab, v_slab)
                )
        req.preemptions += 1
        tl = req.timeline
        if tl is not None:
            tl.stamp("reclaim-preempted" if reclaim
                     else f"preempted:{req.preemptions}")
        if self._metrics and not reclaim:
            self._metrics.increment_counter(
                "app_tenant_preemptions_total",
                tenant=req.tenant or "default",
            )
        if self._logger:
            self._logger.info(
                f"preempted request {req.id} (tenant "
                f"{req.tenant or 'default'}, priority {req.priority}) "
                f"after {len(req.tokens)} tokens; {resident} resident "
                "tokens paged out"
            )
        # nothing is in flight for the slot (the caller checked): free it
        # and requeue. The consume-side identity checks make any stale
        # record harmless, exactly like a cancel retire.
        self._cursors.pop(slot, None)
        self.slots[slot] = None
        self.cache_len[slot] = 0
        self.adapter_idx[slot] = 0
        req.slot = None
        req.dispatched = max(len(req.tokens) - 1, 0)
        self._lora_release(req)
        if self.paged_cache is not None:
            try:
                self.paged_cache.free_slot(slot)
            except Exception:
                pass
        sched = self._sched
        try:
            sched.release(slot)
        except KeyError:
            pass
        if reclaim:
            # doomed replica: never requeue here — settle retriable so
            # the router's candidate walk retries on a survivor (whose
            # boundary walk finds the evacuated spans)
            req.canceled = True
            self._settle_future(req, ErrorServiceUnavailable(
                "replica reclaiming; retry on another replica",
                retry_after=0.5,
            ))
            return
        try:
            sched.submit(
                req.id, len(req.serve_ids), req.max_new_tokens,
                req.priority,
            )
        except Exception:
            with self._count_lock:
                self._by_id.pop(req.id, None)
            self._try_resolve(req, exc=ErrorTooManyRequests())

    # -- decode (pipelined N-step blocks) --------------------------------------
    def _decode_step(self, plan: StepPlan | None = None) -> bool:
        """Dispatch the NEXT N-step device block — a plain decode block,
        or the unified ragged dispatch when the step plan granted prefill
        chunks — then materialize the OLDEST outstanding one. The dispatch
        feeds on the device-resident DecodeState carry directly, so the
        device never waits for host bookkeeping; the host's single block
        sync overlaps the next block's compute (double-buffered — depth =
        decode_sync_every)."""
        self._check_retired()  # replaced during a long _admit: unwind first
        if self.config.spec_tokens > 0:
            return self._spec_step()
        with self._phase("dispatch") as span:
            inflight = self._dispatch_decode(plan, span)
        if inflight is not None:
            self._inflight_q.append(inflight)
        did = inflight is not None
        if self._inflight_q and (
            inflight is None or len(self._inflight_q) > self._sync_every
        ):
            self._consume_block(self._inflight_q.popleft())
            did = True
        return did

    def _spec_step(self) -> bool:
        """Speculative decode step (VERDICT r4 item #3): host drafts up to
        K tokens per greedy row by prompt lookup over (prompt + output),
        one fused dispatch verifies the whole chunk across all slots and
        samples the bonus token, and the host commits each row's accepted
        prefix. LOSSLESS for greedy rows (acceptance is exact argmax
        equality); sampled rows ride the same executable as plain steps.
        Unpipelined by design — drafting needs the newest consumed tokens,
        and the chunk already amortizes dispatch latency the way
        multi_step does, multiplied by accepted drafts. Works on both
        cache layouts (dense and paged); ref
        models/llama.py:speculative_generate for the library-level twin.

        Declared unpack site (kernel_contracts.UNPACK_SITES): the
        [:, :-1] / [:, -1] slices below are checked against the 'spec'
        pack layout — out | n_accept — by kernelcheck."""
        cfg = self.model_cfg
        chaos.maybe_fail("decode.dispatch")
        self._maybe_device_loss()
        # a hang at the chaos point can outlive a warm restart: re-check
        # ownership BEFORE reading slots/pools that may since be rebuilt
        self._check_retired()
        K = self.config.spec_tokens
        T = K + 1
        max_seq = self.config.max_seq_len
        self._pending_admit.clear()  # host state is authoritative in spec mode

        with self._phase("dispatch") as span:
            with self._phase("dispatch.rows"):
                rows: list[tuple[int, _Request]] = []
                now = time.perf_counter()
                for slot, req in enumerate(self.slots):
                    if req is None:
                        continue
                    if req.canceled:
                        self._retire(slot, "cancel")
                        continue
                    if req.expired(now):
                        # abandon mid-stream: free the slot for live requests and
                        # resolve with the tokens produced so far
                        self._retire(slot, "deadline_exceeded")
                        continue
                    if (len(req.tokens) >= req.max_new_tokens
                            or len(req.prompt_ids) + len(req.tokens) >= max_seq):
                        continue  # retires at the next consume's limit checks
                    rows.append((slot, req))
                if not rows:
                    return False

                B = self.config.max_slots
                chunk = np.full((B, T), -1, np.int32)
                for slot, req in rows:
                    chunk[slot, 0] = self.last_token[slot]
                    room = min(
                        req.max_new_tokens - len(req.tokens),
                        max_seq - 1 - (len(req.prompt_ids) + len(req.tokens)),
                    )
                    if req.temperature == 0 and room > 1 and K > 0:
                        draft = llama._prompt_lookup_draft(
                            req.prompt_ids + req.tokens, self.config.spec_ngram,
                            min(K, room - 1),
                        )
                        chunk[slot, 1 : 1 + len(draft)] = draft

                pc = self.paged_cache
                if pc is not None:
                    slot_ids = [s for s, _ in rows]
                    if not pc.try_reserve_chunk(slot_ids, T):
                        # pool pressure: fall back to single-position coverage per
                        # row (chunk tails spill to the trash page; zero drafts
                        # still verify position 0 = a plain decode step). A row
                        # that can't even cover one more token retires with what
                        # it has, like the non-spec path.
                        kept = []
                        for slot, req in rows:
                            if pc.try_reserve_chunk([slot], 1):
                                chunk[slot, 1:] = -1
                                kept.append((slot, req))
                            else:
                                if self._logger:
                                    self._logger.warn(
                                        f"KV pool exhausted; retiring request "
                                        f"{req.id} early"
                                    )
                                req.kv_exhausted = True
                                self._retire(slot, "kv_exhausted")
                        rows = kept
                        if not rows:
                            return True

            mask = np.zeros(B, bool)
            for slot, _ in rows:
                mask[slot] = True
            # counted AFTER the reservation fallback may have cleared drafts
            drafted_total = int((chunk[mask, 1:] >= 0).sum())
            # spec mode re-uploads the [B] sampling params per chunk: three
            # tiny host→device copies (no sync) against a K+1-position verify
            # forward — not worth a dirty-tracking cache
            temp_d = jnp.asarray(self.temperature.copy())
            topk_d = jnp.asarray(self.top_k.copy())
            topp_d = jnp.asarray(self.top_p.copy())
            if self._mask_host is None or not np.array_equal(mask, self._mask_host):
                self._mask_dev = jnp.asarray(mask)
                self._mask_host = mask
            chunk_d = jnp.asarray(chunk)
            start_d = jnp.asarray(np.maximum(self.cache_len, 1))

            with self._phase("dispatch.launch"):
                t0 = time.perf_counter()
                with self._cold_dispatch(
                    "spec", "paged" if pc is not None else "dense",
                ) as cold:
                    if pc is not None:
                        cap = np.zeros(B, np.int32)
                        for slot, _ in rows:
                            cap[slot] = pc.owned_capacity(slot)
                        cap_d = jnp.asarray(cap)
                        # unpack into LOCALS (and the pre-bound pc, which a
                        # restart never mutates): a retired thread's unpack must
                        # not clobber the replacement engine's state — self.*
                        # commits happen only after the retirement check below
                        (packed, pc.k_pool, pc.v_pool, new_rng) = (
                            batch_ops.verify_and_sample_paged(
                                cfg, self.params, pc.k_pool, pc.v_pool,
                                pc.tables_device(), chunk_d, start_d,
                                self._mask_dev, cap_d,
                                temp_d, topk_d, topp_d, self.rng,
                            )
                        )
                        new_cache = self.cache  # dense path untouched
                    else:
                        packed, new_cache, new_rng = batch_ops.verify_and_sample(
                            cfg, self.params, self.cache, chunk_d, start_d,
                            temp_d, topk_d, topp_d, self.rng,
                        )

            with self._phase("dispatch.count"):
                self._blk_seq += 1
                blk = self._blk_seq
                span.set(blk=blk, kind="spec", rows=len(rows), steps=T,
                         kv_tokens=int(self.cache_len[mask].sum()),
                         chunk_rows=0, chunk_tokens=0, cold=int(cold))
                self._count_launch(span, self._launch_idle())
                self._count_step_tokens(len(rows) * T, 0, B * T)
        # accepted tokens + per-row accept count come back as ONE packed
        # [B, T+1] array: one sync per chunk, like the plain path's one
        # sync per block
        with self._phase("sync", blk=blk):
            packed_np = _block_sync(packed)
        with self._phase("commit", blk=blk) as commit:
            # the sync returned: a warm restart may have replaced this thread
            # while the chunk verified — commit nothing to rebuilt state if so
            self._check_retired()
            out_np = packed_np[:, :-1]
            na_np = packed_np[:, -1]
            self.cache, self.rng = new_cache, new_rng
            self.heartbeat = time.monotonic()  # the sync returned: progress
            step_time = time.perf_counter() - t0

            n_active = 0
            accepted_total = 0
            emitted_total = 0
            retires = self._retires
            for slot, req in rows:
                n_active += 1
                accepted_total += int(na_np[slot])
                committed = 0
                for i in range(int(na_np[slot]) + 1):
                    committed += 1
                    self._commit_token(slot, req, int(out_np[slot, i]))
                    if self.slots[slot] is not req:
                        break  # retired mid-chunk: discard the tail
                emitted_total += committed
                if req.timeline is not None:
                    req.timeline.block(committed, blk=blk)
                # chunk position 0 (the previously emitted token) plus the
                # accepted drafts are now resident KV; the bonus token commits
                # as the NEXT chunk's position 0 — so residency advances by the
                # emitted count even when the row retired mid-chunk (harmless:
                # the slot was freed)
                if self.slots[slot] is req:
                    self.cache_len[slot] += committed
                    if pc is not None:
                        pc.advance_slot(slot, committed)
            commit.set(tokens=emitted_total, retired=self._retires - retires)
            self.spec_stats["dispatches"] += 1
            self.spec_stats["accepted"] += accepted_total
            self.spec_stats["emitted"] += emitted_total
            if self._metrics and n_active:
                self._metrics.record_histogram(
                    "app_tpot_seconds", step_time / max(emitted_total / n_active, 1)
                )
                self._metrics.record_histogram(
                    "app_decode_block_seconds", step_time
                )
                self._metrics.set_gauge(
                    "app_batch_occupancy", n_active / self.config.max_slots
                )
                if drafted_total:
                    # rate over tokens actually DRAFTED — sampled rows and
                    # draft-less lookups must not dilute the tuning signal
                    self._metrics.set_gauge(
                        "app_spec_accept_rate", accepted_total / drafted_total
                    )
        return True

    def _slot_in_flight(self, slot: int, req: _Request) -> bool:
        """True when a dispatched-but-unmaterialized block may still carry
        tokens for this (slot, request) pair — retiring it now would drop
        tokens the client paid for; the consume path retires it instead."""
        return any(
            any(s == slot and r is req for s, r in rec.rows)
            for rec in self._inflight_q
        )

    def _make_device_state(self):
        """Build the device-resident DecodeState from the host mirrors —
        the cold path (first dispatch, post-_fail_all rebuild). Only valid
        with no blocks in flight: the mirrors ARE the truth then."""
        B = self.config.max_slots
        budget = np.zeros(B, np.int32)
        done = np.ones(B, bool)
        stop = np.full(B, -1, np.int32)
        for slot, req in enumerate(self.slots):
            if req is None or slot in self._cursors:
                # a mid-chunked-prefill row is not decoding: it stays
                # frozen (done) until its final chunk's on-device fold
                continue
            remaining = req.max_new_tokens - len(req.tokens)
            budget[slot] = max(remaining, 0)
            done[slot] = remaining <= 0
            if len(req.stop_ids) == 1:
                stop[slot] = next(iter(req.stop_ids))
        self.rng, sub = jax.random.split(self.rng)
        self._pending_admit.clear()  # the mirrors already cover these rows
        return batch_ops.make_decode_state(
            self.last_token, np.maximum(self.cache_len, 1), done, budget,
            stop, self.temperature, self.top_k, self.top_p, sub,
            self.adapter_idx,
        )

    def _dispatch_decode(self, plan: StepPlan | None,
                         span: _StepPhase) -> _Inflight | None:
        """Build and launch the next block inside ``span``, the
        iteration's gofr.step.dispatch: a span that dispatched a block
        carries its number (blk) and what the block holds; one that found
        no row to run carries nothing."""
        cfg = self.model_cfg
        chaos.maybe_fail("decode.dispatch")
        self._maybe_device_loss()
        # a hang at the chaos point can outlive a warm restart: re-check
        # ownership BEFORE reading slots/pools that may since be rebuilt
        self._check_retired()

        with self._phase("dispatch.rows"):
            rows, chunk_rows = self._dispatch_rows(plan)
        if not rows and not chunk_rows:
            return None
        N = self._block_steps
        pc = self.paged_cache

        mask = np.zeros(self.config.max_slots, bool)
        for slot, _ in rows:
            mask[slot] = True

        dev_idle = self._launch_idle()
        # the device-side carry: build cold, or fold admissions in with ONE
        # donated scatter — steady state uploads nothing per block
        state = self._dec_state
        if state is None:
            with self._phase("fold", n=len(rows)):
                state = self._make_device_state()
        elif self._pending_admit:
            items = sorted(self._pending_admit.items())
            self._pending_admit.clear()
            with self._phase("fold", n=len(items)):
                idx = np.fromiter((s for s, _ in items), np.int32, len(items))
                state = batch_ops.admit_decode_state(
                    state, jnp.asarray(idx),
                    jnp.asarray(np.fromiter((v[0] for _, v in items),
                                            np.int32, len(items))),
                    jnp.asarray(np.fromiter((v[1] for _, v in items),
                                            np.int32, len(items))),
                    jnp.asarray(np.fromiter((v[2] for _, v in items),
                                            np.int32, len(items))),
                    jnp.asarray(np.fromiter((v[3] for _, v in items),
                                            np.int32, len(items))),
                    jnp.asarray(self.temperature[idx]),
                    jnp.asarray(self.top_k[idx]),
                    jnp.asarray(self.top_p[idx]),
                    jnp.asarray(np.fromiter((v[4] for _, v in items),
                                            np.int32, len(items))),
                )
        # NOTE: self._dec_state is NOT updated here — the scatter donated
        # the old buffers, and the commit happens in one place after the
        # block dispatch (a failed dispatch resets it via _fail_all)

        if self._mask_host is None or not np.array_equal(mask, self._mask_host):
            self._mask_dev = jnp.asarray(mask)
            self._mask_host = mask
        mask_d = self._mask_dev

        with self._phase("dispatch.launch"):
            t0 = time.perf_counter()
            # unpack into LOCALS (and the pre-bound pc, which a restart never
            # mutates): a retired thread returning from a hung dispatch must
            # not clobber the replacement engine's state at assignment time —
            # self.* commits happen only after the retirement check
            prefill_rows: list = []
            last_logits = None
            lora = self._lora.tables() if self._lora is not None else None
            if chunk_rows:
                (packed, last_logits, new_cache, new_state, prefill_rows,
                 cold) = self._dispatch_ragged(
                    cfg, pc, state, mask_d, chunk_rows, N)
            elif pc is not None:
                tables_d = pc.tables_device()
                with self._cold_dispatch("decode", "paged", N,
                                         lora is not None) as cold:
                    (packed, pc.k_pool, pc.v_pool, new_state) = (
                        batch_ops.decode_block_paged(
                            cfg, self.params, pc.k_pool, pc.v_pool, state,
                            tables_d, mask_d, N, lora=lora,
                        )
                    )
                new_cache = self.cache  # dense path untouched
            else:
                with self._cold_dispatch("decode", "dense", N,
                                         lora is not None) as cold:
                    packed, new_cache, new_state = batch_ops.decode_block(
                        cfg, self.params, self.cache, state, mask_d, N,
                        lora=lora,
                    )
        with self._phase("dispatch.count"):
            self._check_retired()  # commit to self only as the loop's owner
            self.cache = new_cache
            self._dec_state = new_state
            for _, req in rows:
                req.dispatched += N
            # the last-position chunk logits are retained ONLY when the
            # chunk-prefix cache will store them at consume (device ref, no
            # sync); otherwise drop the reference so the buffer can free
            keep_logits = (
                last_logits
                if prefill_rows and self._prefix_cache is not None else None
            )
            self._blk_seq += 1
            chunk_tokens = sum(n for *_, n in chunk_rows)
            # cache_len is the committed mirror: rows with a block in flight
            # are resident N positions further on the device
            span.set(blk=self._blk_seq, kind="ragged" if chunk_rows else "decode",
                     rows=len(rows), steps=N, kv_tokens=int(self.cache_len[mask].sum()),
                     chunk_rows=len(chunk_rows), chunk_tokens=chunk_tokens,
                     cold=int(cold))
            self._count_launch(span, dev_idle)
            window = getattr(cfg, "sliding_window", None)
            if window:  # rows whose window layers no longer see their first key
                span.set(win_rows=int((self.cache_len[mask] > window).sum()))
            for pool in (pc.ring_pools if pc is not None else ()):
                # a window pool kept as a ring: pages the rows' tables address,
                # and pages that fell behind the window since the last dispatch
                held, freed = pc.window_turnover(pool, mask)
                span.set(win_pages_held=held, win_pages_freed=freed)
            if chunk_rows and self._upper_on_last:
                self._count_prefill_positions(
                    span, chunk_tokens, sum(1 for row in prefill_rows if row[5]),
                    resets=sum(1 for _, _, _, start_pos, _ in chunk_rows if start_pos == 0))
            if chunk_rows and pc is not None and self._chunk_contexts is not None:
                span.set(chunk_ctx=self._chunk_ctx(pc, chunk_rows))
            topk = getattr(cfg, "index_topk", None)
            if topk:  # rows whose sparse selection binds: attention reads index_topk of them
                span.set(dsa_rows=int((self.cache_len[mask] > topk).sum()))
            self._count_sampler(span, self.temperature[mask], self.top_k[mask],
                                self.top_p[mask], steps=N)
            if self._moe_path is not None:
                span.set(moe_path=self._moe_path)
                if self._metrics:
                    self._metrics.add_counter("app_moe_path_blocks_total", 1, path=self._moe_path)
            self._count_step_tokens(
                len(rows) * N, chunk_tokens,
                self.config.max_slots * (N + (self._chunk_tokens if chunk_rows else 0)),
            )
        return _Inflight(
            packed, rows, t0, steps=N, blk=self._blk_seq,
            prefill_rows=prefill_rows, last_logits=keep_logits,
        )

    def _chunk_ctx(self, pc: Any, chunk_rows: list) -> int:
        """The positions the dispatch's chunk rows read, summed: each the
        first of the model's ``chunk_contexts`` that holds the row's chunk
        end, by the program's own test of ``start + T``."""
        C = self._chunk_tokens
        contexts = self._chunk_contexts(C, pc.page_size, pc.max_pages_per_seq * pc.page_size)
        return sum(contexts[sum(n < start_pos + C for n in contexts[:-1])]
                   for _, _, _, start_pos, _ in chunk_rows)

    def _dispatch_rows(self, plan: StepPlan | None) -> tuple[list, list]:
        """The next block's rows (gofr.step.dispatch.rows): the slots that
        decode, each with page coverage for the whole block reserved, and
        the step plan's granted chunk rows with theirs. Rows whose exit is
        due — canceled, expired, out of pages — leave here."""
        rows: list[tuple[int, _Request]] = []
        now = time.perf_counter()
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            cursor = self._cursors.get(slot)
            if cursor is not None:
                # mid-chunked-prefill: not a decode row. Cancel/deadline/
                # pool-pressure exits run here, deferred while a ragged
                # chunk is still in flight for the slot.
                self._cursor_health(slot, req, cursor, now)
                continue
            if req.canceled:
                # retire immediately; pending in-flight tokens (if any) are
                # discarded at consume via the snapshot identity check
                self._retire(slot, "cancel")
                continue
            if req.expired(now):
                # deadline passed mid-stream (possibly mid-block): abandon
                # the row at this sync boundary, free the slot
                self._retire(slot, "deadline_exceeded")
                continue
            if req.kv_exhausted:
                # pool-clamped: dispatch nothing further; the tokens still
                # in flight are delivered at the next sync, then the row
                # retires there with finish_reason kv_exhausted
                continue
            if slot in self._preempt_pending:
                # marked for preemption: stop feeding the row so its
                # pipelined blocks drain — the preemption ladder pages it
                # out the moment nothing is in flight for the slot
                continue
            rows.append((slot, req))

        N = self._block_steps
        pc = self.paged_cache
        if pc is not None and rows:
            # page coverage for the whole block up front, per row and
            # INCLUDING the dispatched-not-yet-consumed gap (the device
            # runs ahead of the committed host mirror by the in-flight
            # blocks). A row the pool cannot cover is clamped, not
            # stalled: the rest of the batch proceeds.
            kept = []
            for slot, req in rows:
                in_flight = req.dispatched - (len(req.tokens) - 1)
                if pc.try_reserve_slot(slot, in_flight + N):
                    kept.append((slot, req))
                else:
                    if self._logger:
                        self._logger.warn(
                            f"KV pool exhausted; retiring request {req.id} early"
                        )
                    req.kv_exhausted = True
                    if not self._slot_in_flight(slot, req):
                        self._retire(slot, "kv_exhausted")
                    # else: tokens the client paid for are still in flight —
                    # commit them at the next sync and retire there
            rows = kept

        # -- prefill-chunk rows: the step plan's grants, page coverage
        # reserved up front (including each cursor's dispatched-ahead gap,
        # like decode's). A cursor the pool cannot cover is BLOCKED, not
        # stalled — the rest of the plan proceeds and the blocked cursor
        # requeues from chunk 0 once nothing is in flight for it.
        chunk_rows: list[tuple[int, ChunkCursor, _Request, int, int]] = []
        if plan is not None and plan.grants and self._cursors:
            from gofr_tpu.serving.kv_cache import OutOfBlocks

            for slot, grant in plan.grants:
                cursor = self._cursors.get(slot)
                if cursor is None or cursor.blocked or cursor.done:
                    continue
                req = cursor.req
                if req.canceled or req.expired(now):
                    continue  # _cursor_health retires it at the next scan
                n = min(grant, cursor.remaining)
                if n <= 0:
                    continue
                if pc is not None:
                    if not cursor.allocated:
                        try:
                            pc.alloc_slot(slot, seq_id=req.id, prompt_len=0,
                                          reserve_tokens=n)
                            cursor.allocated = True
                        except OutOfBlocks:
                            cursor.blocked = True
                            continue
                    elif not pc.try_reserve_slot(
                        slot, cursor.in_flight + n
                    ):
                        cursor.blocked = True
                        continue
                chunk_rows.append((slot, cursor, req, cursor.dispatched, n))

        return rows, chunk_rows

    def _count_step_stats(self, span: _StepPhase, stats: Any) -> None:
        """A block's model counters, read with its tokens: row-expert
        pairs the held experts took over the block's decode steps and
        layers (``moe_rows``), the fullest expert's (``moe_max``), and
        app_moe_expert_rows_total by the expert's published index; after
        them the held experts whose matrices the steps read
        (``moe_reached``, app_moe_experts_read_total:
        ``ops/moe.held_experts`` counts them). A model that names its
        counters (``step_stats(cfg)``) has them set under those names: all
        of them, or — for a sparse-expert model — those after the
        experts'. A sparse selection's two (``deepseek_v32``'s
        ``dsa_scored``, ``dsa_selected``: the positions its indexer scored
        and its attention read) also count in app_dsa_positions_total."""
        held = getattr(self.model_cfg, "held_experts", 0)
        named = {}
        if self._stats_names is not None:
            # after the experts' rows and reads, where the model has experts
            named = dict(zip(self._stats_names, (stats[held + 1:] if held else stats).tolist()))
            if not held:
                span.set(**named)
                return
        rows, reached = stats[:held], int(stats[held])
        if self._metrics:
            for kind in ("scored", "selected"):
                if named.get(f"dsa_{kind}"):
                    self._metrics.add_counter("app_dsa_positions_total", named[f"dsa_{kind}"], kind=kind)
        span.set(moe_rows=int(rows.sum()), moe_max=int(rows.max()), moe_reached=reached, **named)
        if self._metrics:
            if reached:
                self._metrics.add_counter("app_moe_experts_read_total", reached)
            first = self.model_cfg.first_expert
            for e, n in enumerate(rows.tolist()):
                if n:
                    self._metrics.add_counter(
                        "app_moe_expert_rows_total", n, expert=str(first + e))

    def _count_sampler(self, span: _StepPhase, temperature: Any, top_k: Any,
                       top_p: Any, *, steps: int) -> None:
        """Which path of ``ops.sampling.sample_logits`` the rows' sampling
        parameters select, as ``sampler=greedy|sample|filter`` on ``span``
        and ``steps`` more in app_sampler_steps_total{path}: the host
        mirrors under the predicate the program branches on (a row that
        stopped on the device inside a block not yet read back still
        counts here, so the span can read dearer than what ran)."""
        path = SAMPLER_PATHS[int(sampler_path(temperature, top_k, top_p))]
        span.set(sampler=path)
        if self._metrics:
            self._metrics.add_counter("app_sampler_steps_total", steps, path=path)

    def _count_prefill_positions(self, span: _StepPhase, self_tokens: int,
                                 cross_tokens: int, *, resets: int) -> None:
        """For a model whose upper layers run on a prompt's last position
        alone: positions the layers up to its shared cache ran
        (``self_tokens``) and positions the layers above ran
        (``cross_tokens``: 1 a finished prompt, 0 a chunk that finishes
        none) on ``span`` and in app_prefill_positions_total{part}; and
        the slots whose recurrent state this dispatch starts anew, in
        app_ssm_state_resets_total."""
        span.set(self_tokens=self_tokens, cross_tokens=cross_tokens)
        if self._metrics:
            for part, n in (("self", self_tokens), ("cross", cross_tokens)):
                if n:
                    self._metrics.add_counter("app_prefill_positions_total", n, part=part)
            if resets:
                self._metrics.add_counter("app_ssm_state_resets_total", resets)

    def _count_step_tokens(self, decode: int, prefill: int, issued: int) -> None:
        """app_step_tokens_total at the point of issue: of the positions a
        dispatch computes, those that serve a decode row, those that are
        prompt, and the padding that is neither."""
        if self._metrics:
            for kind, n in (("decode", decode), ("prefill", prefill),
                            ("padding", issued - decode - prefill)):
                if n:
                    self._metrics.add_counter("app_step_tokens_total", n, kind=kind)

    def _dispatch_ragged(self, cfg: Any, pc: Any, state: Any, mask_d: Any,
                         chunk_rows: list, N: int) -> tuple:
        """Assemble and launch ONE unified ragged dispatch: the granted
        prefill chunks (per-row slices of their prompts, ragged within the
        fixed [B, C] chunk buffer) plus the N-step decode block, against
        the same slot cache / page pool — batch_ops.ragged_step*. Rows
        whose chunk completes the prompt get their first token sampled on
        device and are folded into the donated DecodeState inside the
        dispatch; the host reads everything back at the block's single
        sync. The tuple's last member says whether this was the
        executable's first use."""
        B = self.config.max_slots
        C = self._chunk_tokens
        chunk = np.full((B, C), -1, np.int32)
        # non-chunk rows aim their (masked/inactive) chunk writes past the
        # dense cache bound so the scatter drops them; paged rows divert
        # to the trash page via the active mask instead
        start = np.full(B, self.config.max_seq_len, np.int32)
        finish = np.zeros(B, bool)
        cactive = np.zeros(B, bool)
        new_len = np.zeros(B, np.int32)
        budgets = np.zeros(B, np.int32)
        stops = np.full(B, -1, np.int32)
        rids = np.zeros(B, np.int32)
        kvcap = np.zeros(B, np.int32)
        adapters = np.zeros(B, np.int32)
        for slot, cursor, req, start_pos, n in chunk_rows:
            serve = req.serve_ids  # prompt + emitted (preempt resume)
            chunk[slot, :n] = serve[start_pos : start_pos + n]
            start[slot] = start_pos
            cactive[slot] = True
            finish[slot] = start_pos + n >= cursor.total
            new_len[slot] = start_pos + n
            budgets[slot] = req.new_budget - 1
            stops[slot] = (
                next(iter(req.stop_ids)) if len(req.stop_ids) == 1 else -1
            )
            rids[slot] = req.id
            adapters[slot] = req.adapter_slot
            if pc is not None:
                kvcap[slot] = pc.owned_capacity(slot)
        chunk_d = jnp.asarray(chunk)
        start_d = jnp.asarray(start)
        finish_d = jnp.asarray(finish)
        newlen_d = jnp.asarray(new_len)
        budgets_d = jnp.asarray(budgets)
        stops_d = jnp.asarray(stops)
        rids_d = jnp.asarray(rids)
        # ragged dispatches re-upload the [B] sampling params (three tiny
        # host→device copies, no sync) — chunk traffic is a small fraction
        # of decode traffic, not worth a dirty-tracking cache
        temps_d = jnp.asarray(self.temperature.copy())
        topks_d = jnp.asarray(self.top_k.copy())
        topps_d = jnp.asarray(self.top_p.copy())
        adapters_d = jnp.asarray(adapters)
        lora = self._lora.tables() if self._lora is not None else None
        if pc is not None:
            tables_d = pc.tables_device()
            cactive_d = jnp.asarray(cactive)
            kvcap_d = jnp.asarray(kvcap)
            with self._cold_dispatch("ragged", "paged", N,
                                     lora is not None) as cold:
                (packed, last_logits, pc.k_pool, pc.v_pool,
                 new_state) = batch_ops.ragged_step_paged(
                    cfg, self.params, pc.k_pool, pc.v_pool, state,
                    tables_d, chunk_d, start_d, cactive_d, kvcap_d,
                    finish_d, newlen_d, budgets_d, stops_d, temps_d,
                    topks_d, topps_d, rids_d, self._rng_root,
                    mask_d, N, adapters=adapters_d, lora=lora,
                )
            new_cache = self.cache  # dense path untouched
        else:
            with self._cold_dispatch("ragged", "dense", N,
                                     lora is not None) as cold:
                (packed, last_logits, new_cache,
                 new_state) = batch_ops.ragged_step(
                    cfg, self.params, self.cache, state, chunk_d, start_d,
                    finish_d, newlen_d, budgets_d, stops_d, temps_d,
                    topks_d, topps_d, rids_d, self._rng_root, mask_d, N,
                    adapters=adapters_d, lora=lora,
                )
        prefill_rows = []
        for slot, cursor, req, start_pos, n in chunk_rows:
            idx = cursor.chunk_index
            cursor.chunk_index += 1
            cursor.dispatched = start_pos + n
            fin = bool(finish[slot])
            prefill_rows.append((slot, req, cursor, start_pos, n, fin, idx))
            if self._tracer is not None and req.timeline is not None:
                span = self._req_span(
                    f"prefill_chunk:{idx}", "serve.prefill_chunk", req
                )
                span.set_attribute("chunk.index", idx)
                span.set_attribute("chunk.tokens", n)
                span.set_attribute("chunk.start", start_pos)
                span.set_attribute("chunk.final", fin)
                # warm-transfer attribution: which tier served this
                # request's cached prefix (miss = fully computed)
                span.set_attribute(
                    "prefix_tier", req.timeline.prefix_tier or "miss"
                )
        return packed, last_logits, new_cache, new_state, prefill_rows, cold

    def _consume_block(self, rec: _Inflight) -> None:
        # declared unpack site (kernel_contracts.UNPACK_SITES): the
        # column offsets below are checked against the 'ragged' pack
        # layout — tokens | done | n_valid | first — by kernelcheck
        with self._phase("sync", blk=rec.blk):
            packed = _block_sync(rec.packed)  # THE one sync for N device steps
        with self._phase("commit", blk=rec.blk) as span:
            # the sync returned: a warm restart may have replaced this thread
            # while it waited — its tokens belong to requests already settled
            # or requeued, so commit nothing (and don't stamp a heartbeat that
            # would mask the REPLACEMENT thread's health)
            self._check_retired()
            self.heartbeat = time.monotonic()  # the sync returned: progress
            now = time.perf_counter()
            step_time = now - (
                self._last_consume_t if self._last_consume_t is not None
                else rec.dispatched_at
            )
            self._last_consume_t = now

            n_active = tokens = 0
            retires = self._retires
            with self._phase("commit.rows"):
                for slot, req in rec.rows:
                    if self.slots[slot] is not req:
                        continue  # retired (and possibly re-admitted) since dispatch
                    n_active += 1
                    n_valid = int(packed[slot, rec.steps + 1])
                    device_done = bool(packed[slot, rec.steps])
                    committed = 0
                    for i in range(n_valid):
                        self._commit_token(slot, req, int(packed[slot, i]))
                        committed += 1
                        if self.slots[slot] is not req:
                            break  # retired mid-block: discard the tail tokens
                    if req.timeline is not None:
                        # flight-recorder stamp at the block's ONE host sync:
                        # COMMITTED tokens only (a mid-block retire discards the
                        # tail — the spec path's `committed` twin), no extra
                        # device read, and no timestamp passed (`now` is
                        # perf_counter; the timeline's clock is monotonic)
                        req.timeline.block(committed, blk=rec.blk)
                    tokens += committed
                    if self.slots[slot] is not req:
                        continue
                    # committed residency advances by what the device actually
                    # emitted (the device carry already did)
                    self.cache_len[slot] += n_valid
                    if self.paged_cache is not None:
                        self.paged_cache.advance_slot(slot, n_valid)
                    if req.kv_exhausted:
                        # clamped at dispatch time: retire with the pool-pressure
                        # reason, but only once NO younger in-flight block still
                        # carries tokens for this row (decode_sync_every >= 2 can
                        # have several) — retiring earlier would discard tokens
                        # the client paid for via the consume identity check
                        if not self._slot_in_flight(slot, req):
                            self._retire(slot, "kv_exhausted")
                    elif device_done:
                        # defensive: _commit_token's own stop/limit chain normally
                        # retired the row on its last committed token already —
                        # this catches a host/device divergence rather than
                        # leaving a device-frozen row parked in a slot forever
                        self._retire(
                            slot,
                            "stop" if req.tokens and req.tokens[-1] in req.stop_ids
                            else "length",
                        )

            # -- prefill-chunk rows (ragged dispatches only): commit each
            # chunk's residency, feed the chunk-prefix cache, and admit rows
            # whose prompt just finished — their device-sampled first token
            # rides the same packed sync in the trailing column
            if rec.prefill_rows:
                with self._phase("commit.chunks"):
                    for slot, req, cursor, start_pos, n, fin, idx in rec.prefill_rows:
                        if (self.slots[slot] is not req
                                or self._cursors.get(slot) is not cursor):
                            continue  # retired/requeued since dispatch: stale chunk
                        n_active += 1
                        cursor.committed = start_pos + n
                        self.cache_len[slot] = cursor.committed
                        if self.paged_cache is not None:
                            self.paged_cache.advance_slot(slot, n)
                        tl = req.timeline
                        if tl is not None:
                            tl.chunk(idx, n, prefix_hit=False, start=start_pos)
                            tl.end_span(f"prefill_chunk:{idx}")
                        if self._metrics:
                            self._metrics.record_histogram(
                                "app_prefill_chunk_tokens", n, kind="compute",
                            )
                        # only whole-chunk-aligned spans have a precomputed key: the
                        # lookup walk probes exactly (k*C, k*C+C|total), and the paged
                        # extraction needs a page-aligned start — the planner
                        # guarantees this shape; a missing key (future policy drift)
                        # skips the put instead of failing the engine loop
                        put_key = (
                            cursor.cache_keys.get((start_pos, start_pos + n))
                            if cursor.cache_keys is not None else None
                        )
                        if (self._prefix_cache is not None
                                and rec.last_logits is not None and put_key is not None):
                            # chunk-prefix cache PUT: the chunk's K/V just became
                            # resident — extract its slab (pure device reads, no sync;
                            # the slices/gathers are fresh buffers safe to retain) and
                            # store it with the prefix's last-position logits, so a
                            # later prompt sharing this prefix skips the chunk
                            if self.paged_cache is not None:
                                k_slab, v_slab = self.paged_cache.read_span(
                                    slot, start_pos, start_pos + n
                                )
                            else:
                                k_slab = self.cache.k[:, slot, start_pos : start_pos + n]
                                v_slab = self.cache.v[:, slot, start_pos : start_pos + n]
                            self._prefix_cache.put(
                                put_key,
                                (rec.last_logits[slot : slot + 1], k_slab, v_slab),
                            )
                        if fin:
                            self._cursors.pop(slot, None)
                            first_id = int(packed[slot, rec.steps + 2])
                            self._commit_first_token(slot, req, first_id)

            with self._phase("commit.stats"):
                span.set(tokens=tokens, retired=self._retires - retires)
                if self._stats_len:
                    self._count_step_stats(span, batch_ops.block_stats(
                        packed, self.config.max_slots, self._stats_len))
                if self._metrics and n_active:
                    self._metrics.record_histogram(
                        "app_tpot_seconds", step_time / rec.steps
                    )
                    self._metrics.record_histogram(
                        "app_decode_block_seconds", step_time
                    )
                    self._metrics.set_gauge(
                        "app_batch_occupancy", n_active / self.config.max_slots
                    )
                    if self.paged_cache is not None:
                        kv = self.paged_cache.stats()
                        self._metrics.set_gauge(
                            "app_kv_cache_pages_used",
                            kv["total_blocks"] - kv["free_blocks"],
                        )
                        for pool, n in kv.get("pools", {}).items():
                            for state in ("used", "total"):
                                self._metrics.set_gauge(
                                    "app_kv_pool_pages", n[state], pool=pool, state=state)
                    self._metrics.set_gauge("app_decode_block_size", rec.steps)
                    with self._detok_mu:
                        depth = self._detok_depth
                    self._metrics.set_gauge("app_detok_queue_depth", depth)

    def _commit_first_token(self, slot: int, req: _Request,
                            first_id: int) -> None:
        """THE first-token commit tail, shared by the monolithic prefill
        path (_commit_prefilled, which scatters the _pending_admit fold
        first) and the ragged chunked path (where the token was sampled
        on device and folded into the DecodeState inside the dispatch):
        TTFT stamps/metrics, emission, and the ONE stop/length retire
        chain — a divergence between the two admission routes is exactly
        the bug class sharing this prevents."""
        self.last_token[slot] = first_id
        resumed = req.first_token_at is not None  # preempt/resume round trip
        if not resumed:
            req.first_token_at = time.perf_counter()
            ttft = req.first_token_at - req.created
            self._shed.observe_ttft(ttft)
        tl = req.timeline
        if tl is not None:
            # prefill end + first token share the commit instant: the
            # sampled first token IS the prefill's last output. First
            # stamp wins, so a resumed request keeps its original TTFT.
            tl.stamp("prefill_end")
            tl.stamp("first_token")
            tl.end_span("prefill")  # no-op on the chunked path (per-chunk
            # spans end at their own consumes)
        if self._metrics and not resumed:
            self._metrics.record_histogram("app_ttft_seconds", ttft)
            # tenant rides as an EXTRA labeled series (tenant-less
            # traffic keeps the bare source=engine series, so existing
            # scrapes and the hedge-floor percentile read unchanged)
            labels = {"source": "engine"}
            if req.tenant:
                labels["tenant"] = req.tenant
            self._metrics.record_histogram(
                "app_request_ttft_seconds", ttft, **labels
            )
        if req.prefill_only:
            # disaggregated prefill phase: the prompt KV (and the cached
            # last-position logits) are what the caller wanted — they sit
            # in the prefix cache for the decode replica's handoff fetch.
            # Retire NOW, before any decode step or token emission: the
            # DECODE replica samples the identical first token from the
            # migrated logits, so emitting here would double-serve it.
            self._retire(slot, "handoff")
            return
        self._emit_token(req, first_id)
        self._check_retired()  # stream_cb may have blocked across a restart
        if first_id in req.stop_ids:
            self._retire(slot, "stop")
        elif len(req.tokens) >= req.max_new_tokens:
            self._retire(slot, "length")
        elif tl is not None and self._tracer is not None:
            self._req_span("decode", "serve.decode", req)

    # -- bookkeeping -----------------------------------------------------------
    def _commit_token(self, slot: int, req: _Request, token_id: int) -> None:
        """Deliver one decoded token and run the retire chain — the ONE
        place stop/limit semantics live for both the pipelined consume
        and the speculative commit paths."""
        self.last_token[slot] = token_id
        self._emit_token(req, token_id)
        # a stream_cb is client code and can block for minutes: a warm
        # restart may have replaced this thread while it sat inside the
        # emit — the retire chain below would free the REPLACEMENT
        # engine's slot/pages, so a retired thread unwinds here instead
        self._check_retired()
        if req.canceled:
            self._retire(slot, "cancel")
        elif req.expired(time.perf_counter()):
            self._retire(slot, "deadline_exceeded")
        elif token_id in req.stop_ids:
            self._retire(slot, "stop")
        elif len(req.tokens) >= req.max_new_tokens:
            # a pool-pressure clamp reports its own reason: "length" must
            # stay unambiguous — "the request's own token budget ran out"
            self._retire(slot, "kv_exhausted" if req.kv_exhausted else "length")
        elif len(req.prompt_ids) + len(req.tokens) >= self.config.max_seq_len:
            self._retire(slot, "length")

    def _emit_token(self, req: _Request, token_id: int) -> None:
        req.tokens.append(token_id)
        if req.stream_cb is not None and token_id not in req.stop_ids:
            self._emit_async(req, token_id)

    def _emit_async(self, req: _Request, token_id: int) -> None:
        """Queue detokenization + stream emission on the single-worker
        executor: a stream_cb is client code and can block for seconds —
        the decode loop must overlap the device block, never wait on the
        client (ROADMAP item 4). One worker keeps per-request frame order;
        a callback failure cancels the request like the inline path did."""

        def task() -> None:
            try:
                req.stream_cb(token_id, self.tokenizer.decode([token_id]), False)
            except Exception:
                req.canceled = True

        # executor already shut down (stop() raced the emit): the token
        # frame is dropped — nobody can read it from a stopped engine
        self._submit_detok(task)

    def _submit_detok(self, task: Callable[[], None]) -> bool:
        """Queue work on the detok executor with depth accounting (the
        backlog gauge + the idle event drain() waits on). Returns False
        when the executor is already shut down — the caller decides
        whether to run inline (terminal settlement) or drop (a stream
        frame nobody can read anymore)."""
        with self._detok_mu:
            self._detok_depth += 1
            self._detok_idle.clear()

        def run() -> None:
            try:
                task()
            finally:
                self._detok_done()

        try:
            self._detok.submit(run)
            return True
        except RuntimeError:
            self._detok_done()
            return False

    def _detok_done(self) -> None:
        with self._detok_mu:
            self._detok_depth -= 1
            if self._detok_depth == 0:
                self._detok_idle.set()

    def _retire(self, slot: int, reason: str) -> None:
        req = self.slots[slot]
        if req is not None and req.timeline is not None:
            # the loop's account as the row leaves: with the one taken at
            # admission, what the loop did while this request was served
            req.timeline.loop_end = self.loop_account()
            # final residency facts for the decode span, read from the
            # host mirrors BEFORE the slot is reclaimed (zero device reads)
            dspan = req.timeline.spans.get("decode")
            if dspan is not None:
                resident = int(self.cache_len[slot])
                dspan.set_attribute(
                    "batch.size",
                    sum(1 for s in self.slots if s is not None),
                )
                dspan.set_attribute("kv.resident_tokens", resident)
                if self.paged_cache is not None:
                    page = self.config.kv_page_size
                    dspan.set_attribute(
                        "kv.pages", (resident + page - 1) // page
                    )
        self._retires += 1
        self.slots[slot] = None
        self.cache_len[slot] = 0
        self.adapter_idx[slot] = 0
        self._preempt_pending.discard(slot)
        self._cursors.pop(slot, None)  # a mid-chunked-prefill retire
        if self.paged_cache is not None:
            self.paged_cache.free_slot(slot)
        try:
            self._sched.release(slot)
        except KeyError:
            pass
        if req is not None:
            self._lora_release(req)
            with self._count_lock:
                self._by_id.pop(req.id, None)
            self._finish(req, reason)

    def _try_resolve(self, req: _Request, value: Any = None,
                     exc: Exception | None = None) -> bool:
        """Settle a request's future, tolerant of a concurrent settler:
        done()-then-set is check-then-act, and BOTH sides race — the engine
        thread (_finish/_expire/_fail_all) against drain()/stop() sweeps.
        Losing must never raise InvalidStateError: on the engine thread
        that would escalate a benign lost race into _fail_all.

        This is ALSO the one terminal gate for the flight recorder: the
        settlement winner (and only the winner) marks the request's
        timeline terminal and force-ends its remaining spans — which is
        what makes "exactly one terminal phase per request" and "zero
        leaked spans after drain" chaos-auditable invariants instead of
        per-call-site discipline."""
        if req.future.done():
            return False
        try:
            if exc is not None:
                req.future.set_exception(exc)
            else:
                req.future.set_result(value)
        except Exception:
            return False  # the other settler won the race
        self._record_terminal(req, value, exc)
        # HA plane: the settlement winner (and only the winner) flips the
        # dedup registry. A successful result is retained for duplicate
        # replay; an exception terminal forgets the key so a genuine
        # client retry re-runs as a fresh request.
        if req.idem_key is not None:
            if exc is None and value is not None:
                self._dedup.settle(req.idem_key, value)
            else:
                self._dedup.forget(req.idem_key)
        return True

    @staticmethod
    def _terminal_reason(value: Any, exc: Exception | None) -> str:
        if value is not None:
            return getattr(value, "finish_reason", "stop")
        if isinstance(exc, ErrorDeadlineExceeded):
            return "deadline_exceeded"
        if isinstance(exc, ErrorTooManyRequests):
            return "shed"
        if isinstance(exc, ErrorServiceUnavailable):
            return "unavailable"
        if isinstance(exc, ErrorRequestEntityTooLarge):
            return "too_large"
        return "error"

    def _record_terminal(self, req: _Request, value: Any,
                         exc: Exception | None) -> None:
        tl = req.timeline
        if tl is None:
            return
        if tl.loop_end is None:
            # settled without the loop thread retiring it (a sweep or a
            # cancel that won, a deadline in the queue): the account as
            # this thread can read it
            tl.loop_end = self.loop_account()
        dspan = tl.spans.get("decode")
        if dspan is not None:
            dspan.set_attribute("tokens.out", len(req.tokens))
            dspan.set_attribute("decode.blocks", tl.decode_blocks)
        reason = self._terminal_reason(value, exc)
        # snapshot: the engine thread can be opening a span concurrently
        # with a sweep thread settling (the lost opener re-closes, above)
        for span in list(tl.spans.values()):
            if span.end_ns is None:  # ended spans are already exported
                span.set_attribute("request.finish_reason", reason)
        self.timeline.finish(tl, reason)

    def _settle_future(self, req: _Request, exc: Exception) -> None:
        """Fail a request's future from OUTSIDE the engine thread. Fires
        the stream's done callback so consumers blocked on the token queue
        wake up."""
        if self._try_resolve(req, exc=exc) and req.stream_cb is not None:
            try:
                req.stream_cb(-1, "", True)
            except Exception:
                pass

    def _expire(self, req: _Request) -> None:
        """Terminal state for a request whose deadline passed while still
        queued: it never prefilled, so there is no partial result — the
        caller gets 504 / DEADLINE_EXCEEDED."""
        if self._metrics:
            self._metrics.increment_counter("app_requests_deadline_exceeded_total")
        if req.stream_cb is not None:
            try:
                req.stream_cb(-1, "", True)
            except Exception:
                pass
        self._try_resolve(req, exc=ErrorDeadlineExceeded())

    def _finish(self, req: _Request, reason: str) -> None:
        now = time.perf_counter()
        self._shed.observe_request(now - req.created)
        if reason == "deadline_exceeded" and self._metrics:
            self._metrics.increment_counter("app_requests_deadline_exceeded_total")
        if reason == "kv_exhausted" and self._metrics:
            self._metrics.increment_counter("app_requests_kv_exhausted_total")
        out_ids = [t for t in req.tokens if t not in req.stop_ids]
        ttft = (req.first_token_at - req.created) if req.first_token_at else 0.0
        duration = now - req.created
        if self._metrics:
            labels = {"tenant": req.tenant} if req.tenant else {}
            self._metrics.record_histogram(
                "app_request_e2e_seconds", duration, **labels
            )
        # the detok/settlement span covers the off-engine-thread tail:
        # full-text detokenization, the terminal stream frame, future
        # resolution — it ends at the terminal mark inside _try_resolve
        if self._tracer is not None:
            self._req_span("detok", "serve.detok", req)

        def settle() -> None:
            # full-text detokenization + terminal frame + future settlement
            # run behind any still-queued token frames (same single-worker
            # executor: the done frame can never overtake a token frame)
            result = GenerationResult(
                request_id=req.id,
                text=self.tokenizer.decode(out_ids),
                token_ids=out_ids,
                prompt_tokens=len(req.prompt_ids),
                completion_tokens=len(out_ids),
                finish_reason=reason,
                ttft_s=ttft,
                duration_s=duration,
            )
            if req.stream_cb is not None:
                try:
                    req.stream_cb(-1, "", True)
                except Exception:
                    pass
            if req.timeline is not None:
                req.timeline.stamp("detok_done")
            if not self._try_resolve(req, value=result) and \
                    req.timeline is not None:
                # a drain/stop sweep won the settlement race and closed
                # the spans BEFORE this path opened its decode/detok
                # spans — close again so nothing opened after the
                # sweep's pass can leak (close_spans is idempotent)
                req.timeline.close_spans()

        if not self._submit_detok(settle):
            # executor already shut down (stopping engine): settle inline —
            # a terminal state must never be lost to a lifecycle race
            settle()

    def _reset_prefix_cache(self) -> None:
        """A DEVICE-level failure may have poisoned cached prefill slabs
        the same way it poisoned the live KV (host-only exceptions can't,
        so the cache survives those); a cold prefix cache only costs
        recompute, a dead one fails every hit forever. Injected caches
        follow the container Cache protocol, which has no clear() — drop
        an unclearable cache rather than keep serving poisoned entries
        out of it."""
        if self._prefix_cache is None:
            return
        clear = getattr(self._prefix_cache, "clear", None)
        try:
            if clear is not None:
                clear()
            else:
                self._prefix_cache = None
        except Exception:
            self._prefix_cache = None

    def _maybe_device_loss(self) -> None:
        """The ``device.loss`` chaos point: when the schedule says this
        dispatch loses the device, the persistent KV buffers are POISONED
        for real (deleted, exactly what a failed-after-donation dispatch
        leaves behind) before the fault propagates — so recovery exercises
        the genuine rebuild path, not a pretend one."""
        try:
            chaos.maybe_fail("device.loss")
        except Exception:
            self._poison_device()
            raise

    def _poison_device(self) -> None:
        try:
            if self.cache is not None:
                self.cache.k.delete()
                self.cache.v.delete()
            elif self.paged_cache is not None:
                self.paged_cache.delete_pools()
        except Exception:
            pass  # already deleted / backend gone: the poison took either way

    def _kv_unhealthy(self) -> bool:
        """True when the persistent KV storage cannot serve another step:
        donated-and-deleted buffers (a dispatch that failed AFTER its
        donation committed), or error-state outputs (an async dispatch that
        failed after its output was already rebound — ``is_deleted()`` is
        False on those, so a one-element sync probe is the only reliable
        detector). Either way every subsequent step would raise forever.
        CPU runs delete donated buffers too (jax 0.9), so tests exercise
        the donation half for real."""
        arr = None
        if self.cache is not None:
            arr = self.cache.k
        elif self.paged_cache is not None:
            arr = self.paged_cache.probe()
        if arr is None:
            return False
        try:
            if arr.is_deleted():
                return True
            float(arr[(0,) * arr.ndim])  # sync probe: poisoned arrays raise
            return False
        except Exception:
            return True

    def _make_dense_cache(self) -> llama.KVCache:
        """The one dense slot-cache constructor, shared by __init__ and
        donation-failure recovery so the rebuilt cache can never drift
        from the one the engine started with."""
        return batch_ops.model_of(self.model_cfg).KVCache.create(
            self.model_cfg, self.config.max_slots,
            max_len=self.config.max_seq_len,
        )

    def _make_paged_cache(self):
        """The one paged pool constructor, shared by __init__ and the
        supervisor's warm restart so a rebuilt pool can never drift from
        the one the engine started with."""
        from gofr_tpu.serving.kv_cache import PagedKVCache

        B, S = self.config.max_slots, self.config.max_seq_len
        page = self.config.kv_page_size
        num_pages = self.config.kv_num_pages or (B * S + page - 1) // page
        # what the model stores: pools by layer kind and a per-slot state
        # (cache_spec), or what a page of the one pool pair holds
        model = batch_ops.model_of(self.model_cfg)
        if hasattr(model, "cache_spec"):
            stored = {"spec": model.cache_spec(self.model_cfg, page)}
        else:
            stored = {"page_shapes": model.page_shapes(self.model_cfg, page)}
        return PagedKVCache(
            self.model_cfg, num_pages=num_pages, page_size=page,
            max_slots=B, max_seq_len=S, **stored,
        )

    def _init_runtime_state(self) -> None:
        """Executable-level mutable state, built HERE and only here so
        __init__ and the supervisor's warm restart can never drift: the KV
        storage, the per-slot sampling/length arrays, the pipelined-decode
        device state, and the admission scheduler. A field added to one
        construction path but not the other would survive a restart with
        stale shape or contents and only fail on the first post-restart
        batch — sharing the constructor makes that class of bug impossible.

        Admission policy lives in the native scheduler (native/runtime/
        gofr_runtime.cc; Python fallback when no toolchain): priority +
        FIFO queue, free-slot assignment, per-step prefill token budget.

        CPU-free decode state (ROADMAP item 4, Blink arXiv:2604.07609):
        the device owns the per-row carry (batch_ops.DecodeState — last
        token, resident length, done flag, token budget, stop id, sampling
        params, RNG), sampling AND stop evaluation run inside the N-step
        block executable, and the host's single materialization per block
        (_block_sync) overlaps the next block's compute. The numpy arrays
        here are host MIRRORS: authoritative for admission/recovery
        rebuilds, advanced at each consume."""
        B = self.config.max_slots
        with self._device_scope():
            if self.config.kv_layout == "paged":
                self.paged_cache = self._make_paged_cache()
                self.cache = None
            else:
                self.paged_cache = None
                self.cache = self._make_dense_cache()
        self.cache_len = np.zeros(B, np.int32)  # host mirror (committed tokens)
        self.last_token = np.zeros(B, np.int32)
        self.temperature = np.ones(B, np.float32)
        self.top_k = np.zeros(B, np.int32)
        self.top_p = np.ones(B, np.float32)
        # per-slot LoRA adapter-table slot (0 = base): the host mirror of
        # DecodeState.adapter, authoritative for recovery rebuilds
        self.adapter_idx = np.zeros(B, np.int32)
        self.slots: list[_Request | None] = [None] * B
        # the pipelined-block queue: dispatched-but-unmaterialized blocks,
        # oldest first; depth bounded by decode_sync_every
        self._inflight_q: collections.deque[_Inflight] = collections.deque()
        # device-resident DecodeState carry (batch_ops.DecodeState): the
        # host never reads it; None = rebuild from the host mirrors at the
        # next dispatch (cold start / post-failure)
        self._dec_state: Any = None
        # slots prefilled since the last dispatch, folded into the device
        # state by ONE donated scatter: slot → (first token, resident len,
        # remaining budget, stop id, adapter slot)
        self._pending_admit: dict[int, tuple[int, int, int, int, int]] = {}
        self._mask_dev: Any = None  # cached device active mask
        self._mask_host: Any = None  # host copy the cache was built from
        self._last_consume_t: float | None = None
        # continuous batching: per-slot chunk cursors for prompts mid-
        # chunked-prefill (serving/stepplan.py). A slot with a live cursor
        # holds its request but is NOT a decode row yet; the cursor's
        # committed/dispatched carry the chunk position between
        # iterations. Rebuilt empty on warm restart — partially-prefilled
        # requests requeue from chunk 0 (their KV died with the pools).
        self._cursors: dict[int, ChunkCursor] = {}
        self._cursor_seq = 0
        # decode rows marked for preemption: no further blocks dispatch
        # for them; the ladder pages them out once their pipeline drains
        self._preempt_pending: set[int] = set()
        self._plan_gauges: tuple | None = None  # last-exported step-plan gauges
        self._sched = Scheduler(
            self.config.max_slots, self.config.max_queue,
            self.config.prefill_token_budget,
        )

    def _rebuild_kv(self) -> None:
        """Reallocate the persistent KV storage after donated buffers were
        lost mid-dispatch. Every slot's residency is gone, so this only
        runs on the _fail_all path where all active requests already
        failed; fresh zeroed storage restores a servable engine."""
        if self.cache is not None:
            self.cache = self._make_dense_cache()
        elif self.paged_cache is not None:
            self.paged_cache.reset_pools()
        if self._logger:
            self._logger.warn(
                "KV storage rebuilt after a failed dispatch deleted the "
                "donated device buffers"
            )

    def _fail_all(self, exc: Exception, kv_unhealthy: bool | None = None) -> None:
        # pipeline state is unrecoverable mid-step: drop the in-flight
        # record and force re-upload of device-resident state. Chunk
        # cursors die with it — their rows fail through the slot sweep
        # below like any other active request.
        self._inflight_q.clear()
        self._cursors.clear()
        self._preempt_pending.clear()
        self._pending_admit.clear()
        self._dec_state = None  # rebuilt from host mirrors at next dispatch
        self._mask_dev = None
        self._mask_host = None
        self._last_consume_t = None
        if kv_unhealthy is None:
            kv_unhealthy = self._kv_unhealthy()  # callers pass a fresh verdict
        if kv_unhealthy:
            # visible to the supervisor's watchdog: repeated poisonings in a
            # short window mean the in-place KV rebuild is not sticking —
            # escalate to a full warm restart instead of thrashing here
            self.device_poisonings += 1
            try:
                self._rebuild_kv()
            except Exception as rebuild_exc:
                # backend still down: keep the loop thread alive — the next
                # failure re-enters _fail_all and retries the rebuild
                if self._logger:
                    self._logger.error(f"KV rebuild failed: {rebuild_exc}")
            self._reset_prefix_cache()
        for slot, req in enumerate(self.slots):
            if req is not None:
                self.slots[slot] = None
                self.cache_len[slot] = 0
                self._lora_release(req)
                if self.paged_cache is not None:
                    try:
                        self.paged_cache.free_slot(slot)
                    except Exception:
                        pass
                try:
                    self._sched.release(slot)
                except KeyError:
                    pass
                with self._count_lock:
                    self._by_id.pop(req.id, None)
                self._try_resolve(req, exc=exc)

    def _buckets(self) -> tuple[int, ...]:
        return tuple(
            b for b in self.config.prefill_buckets if b <= self.config.max_seq_len
        ) or (self.config.max_seq_len,)

    def _observe_queue(self, depth: int | None = None) -> None:
        if self._metrics:
            if depth is None:
                depth = self._sched.stats()["queue_depth"]
            self._metrics.set_gauge("app_batch_queue_depth", depth)

    def _req_span(self, key: str, name: str, req: _Request) -> Any:
        """Open a lifecycle span for one request, parented on its queue
        span (the tree reads caller → engine.queue → prefill/decode/detok)
        or the caller's trace context. Registered on the request's
        timeline so terminal settlement force-ends whatever a fault path
        left open. Returns a context manager either way (nullcontext when
        tracing is off); ``activate=False`` keeps the engine thread's
        contextvars untouched."""
        if self._tracer is None:
            return contextlib.nullcontext()
        tl = req.timeline
        parent = (
            tl.spans.get("queue") if tl is not None else None
        ) or req.trace_ctx
        span = self._tracer.start_span(
            name, parent=parent, kind="internal", activate=False
        )
        span.set_attribute("request.id", req.id)
        if req.tenant:
            # per-tenant SLO attainment is scraped straight off the
            # serve.* spans (docs/serving.md "Multi-tenancy")
            span.set_attribute("tenant", req.tenant)
        if req.adapter_id:
            span.set_attribute("lora.adapter", req.adapter_id)
        if tl is not None:
            tl.open_span(key, span)
        return span
