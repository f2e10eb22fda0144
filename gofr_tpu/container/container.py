"""The dependency-injection Container.

Reference parity: pkg/gofr/container/container.go:43-177 — owns Logger,
Metrics, tracer, Services (inter-service HTTP clients), PubSub, Redis, SQL,
KVStore, File, WSManager; builds them from Config (PUBSUB_BACKEND selection
:132-172, remote logger :101-113); registers framework metrics (:252-284);
``close()`` tears everything down (:179-199). Health aggregation lives in
health.py (container/health.go:8-98).

TPU-build addition: the container owns the ``tpu`` datasource and the serving
engine reaches every datasource through it, so ``ctx.tpu.execute(...)`` works
inside ordinary handlers (BASELINE.json north_star).
"""

from __future__ import annotations

import os
import threading
from typing import Any

from gofr_tpu.config import Config, EnvConfig
from gofr_tpu.container.datasources import wire_provider
from gofr_tpu.logging import Level, Logger, new_logger, start_remote_level_poller
from gofr_tpu.logging.level import parse_level
from gofr_tpu.metrics import Manager, new_metrics_manager
from gofr_tpu.tracing import BatchSpanProcessor, Tracer, build_exporter, new_tracer
from gofr_tpu import version


class Container:
    """Holds every cross-cutting dependency handlers may use."""

    def __init__(self, config: Config | None = None, logger: Logger | None = None) -> None:
        self.config: Config = config if config is not None else EnvConfig()
        self.app_name = self.config.get_or_default("APP_NAME", "gofr-app")
        self.app_version = self.config.get_or_default("APP_VERSION", "dev")

        if logger is not None:
            self.logger = logger
        else:
            level = parse_level(self.config.get_or_default("LOG_LEVEL", "INFO"))
            self.logger = new_logger(level)
            remote_url = self.config.get("REMOTE_LOG_URL")
            if remote_url:
                interval = float(
                    self.config.get_or_default("REMOTE_LOG_FETCH_INTERVAL", "15")
                )
                self._remote_log_thread = start_remote_level_poller(
                    self.logger, remote_url, interval
                )

        self.metrics_manager: Manager = new_metrics_manager(self.logger)
        self.tracer: Tracer = self._build_tracer()
        # live trace sample-ratio adjustment: the sibling of the remote
        # log-level poller (logging/remote.py) — an incident responder
        # raises sampling on a live fleet without a restart
        ratio_url = self.config.get("REMOTE_TRACE_RATIO_URL")
        if ratio_url:
            from gofr_tpu.logging.remote import start_remote_trace_ratio_poller

            interval = float(
                self.config.get_or_default("REMOTE_TRACE_RATIO_INTERVAL", "15")
            )
            self._remote_trace_thread = start_remote_trace_ratio_poller(
                self.tracer, ratio_url, interval, logger=self.logger
            )

        # datasources (nil until wired by App.add_* / configure)
        self.tpu: Any = None
        self.sql: Any = None
        self.redis: Any = None
        self.pubsub: Any = None
        self.kv_store: Any = None
        self.file: Any = None
        self.cache: Any = None
        self.services: dict[str, Any] = {}
        self.ws_manager: Any = None
        self.extra_datasources: dict[str, Any] = {}
        self.serving: Any = None  # continuous-batching engine (serving/)
        # request-lifecycle drain flag: flipped by App.drain()/shutdown();
        # HTTP dispatch, the gRPC interceptor and the WS upgrader all
        # reject new work with a retriable status while it is set
        self.draining = False

        self._closed = False
        self._lock = threading.Lock()

        self.register_framework_metrics()

    # -- construction helpers -------------------------------------------------
    def _build_tracer(self) -> Tracer:
        exporter = build_exporter(self.config, self.logger)
        processor = BatchSpanProcessor(exporter) if exporter is not None else None
        ratio = float(self.config.get_or_default("TRACER_RATIO", "1"))
        return new_tracer(self.app_name, processor, ratio)

    def register_framework_metrics(self) -> None:
        """Framework metric registration (container/container.go:252-284),
        with the TPU-serving additions from SURVEY §5.5."""
        m = self.metrics_manager
        m.new_gauge("app_info", "Info for app_name and app_version")
        m.set_gauge("app_info", 1, app_name=self.app_name, app_version=self.app_version,
                    framework_version=version.FRAMEWORK)
        m.new_gauge("app_go_routines", "Number of live threads (goroutine analogue)")
        m.new_gauge("app_sys_memory_alloc", "Resident memory of the process in bytes")
        gauge = m.get("app_go_routines")
        if gauge is not None:
            gauge.observe_with(lambda: {(): float(threading.active_count())})
        mem_gauge = m.get("app_sys_memory_alloc")
        if mem_gauge is not None:
            mem_gauge.observe_with(lambda: {(): float(_rss_bytes())})
        m.new_histogram("app_http_response", "Response time of HTTP requests in seconds")
        m.new_histogram("app_http_service_response", "Response time of HTTP service requests in seconds")
        m.new_histogram("app_sql_stats", "Response time of SQL queries in milliseconds")
        m.new_gauge("app_sql_open_connections", "Number of open SQL connections")
        m.new_gauge("app_sql_inuse_connections", "Number of inuse SQL connections")
        m.new_histogram("app_redis_stats", "Response time of Redis commands in milliseconds")
        m.new_histogram("app_file_stats", "Duration of file-system operations in milliseconds")
        m.new_counter("app_pubsub_publish_total_count", "Number of total publish operations")
        m.new_counter("app_pubsub_publish_success_count", "Number of successful publish operations")
        m.new_counter("app_pubsub_subscribe_total_count", "Number of total subscribe operations")
        m.new_counter("app_pubsub_subscribe_success_count", "Number of successful subscribe operations")
        # delivery-reliability plane (docs/datasources.md "Delivery semantics")
        m.new_counter(
            "app_pubsub_commit_fail_count",
            "Commits that failed after a successful handler run (the broker redelivers)",
        )
        m.new_counter(
            "app_pubsub_redeliveries_total",
            "Messages delivered more than once to this consumer group",
        )
        m.new_counter(
            "app_pubsub_dlq_total",
            "Messages dead-lettered after exhausting their delivery budget",
        )
        m.new_gauge(
            "app_pubsub_consumer_lag",
            "Undelivered backlog behind this consumer group, per topic",
        )
        m.new_histogram(
            "app_pubsub_handler_duration_seconds",
            "Subscriber handler execution time",
        )
        # TPU serving metrics (SURVEY §5.5)
        m.new_gauge("app_tpu_hbm_used_bytes", "HBM bytes in use per device")
        m.new_gauge("app_tpu_hbm_limit_bytes", "HBM capacity per device")
        m.new_gauge("app_tpu_duty_cycle", "Fraction of wall time the TPU executed in the last window")
        m.new_counter(
            "app_tpu_devices_excluded_total",
            "Devices excluded from the mesh by the sick-chip breaker",
        )
        m.new_gauge("app_batch_queue_depth", "Requests waiting for batch admission")
        m.new_gauge("app_batch_occupancy", "Fraction of batch slots occupied")
        m.new_gauge("app_kv_cache_pages_used", "Paged KV-cache pages in use")
        # cluster-wide KV reuse tiers (serving/kv_spill.py +
        # serving/prefix_index.py, docs/performance.md "KV reuse tiers"):
        # which tier served each admission's cached prefix, the host
        # spill pool's residency, and cross-replica warm migrations
        m.new_counter(
            "app_kv_prefix_hits_total",
            "Prefix-cache admission lookups by warmest serving tier "
            "(label tier=device|host|remote|miss)",
        )
        m.new_gauge(
            "app_kv_spill_bytes",
            "Bytes resident in the host-RAM KV spill tier",
        )
        m.new_counter(
            "app_kv_migrations_total",
            "Warm KV prefix migrations fetched from another replica",
        )
        # disaggregated prefill/decode serving (docs/robustness.md "The
        # disaggregation plane"): prefill→decode KV handoffs that passed
        # the two-phase-commit contiguity audit, and the autoscaler's
        # pool-sizing actions
        m.new_counter(
            "app_kv_handoffs_total",
            "Prefill→decode KV handoff chains admitted complete "
            "(contiguity-audited; a torn handoff re-prefills instead)",
        )
        m.new_gauge(
            "app_autoscaler_replicas",
            "Autoscaler's current replica count per pool (label role)",
        )
        m.new_counter(
            "app_autoscaler_scale_events_total",
            "Autoscaler scale actions taken (label direction=up|down)",
        )
        m.new_histogram("app_ttft_seconds", "Time to first token")
        m.new_histogram(
            "app_tpot_seconds", "Time per output token",
            buckets=(0.001, 0.0025, 0.005, 0.0075, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5),
        )
        m.new_gauge(
            "app_spec_accept_rate",
            "Speculative-decode draft acceptance rate over drafted tokens",
        )
        # the step loop's own account (serving/engine.py _phase, docs/
        # observability.md "Engine step spans"): where the loop thread's
        # time goes — on the wall and on its own CPU clock, so the host's
        # cost per block and per phase is a rate of these two —, whether
        # a block found the device waiting, and what each dispatch issues
        m.new_counter(
            "app_engine_phase_seconds_total",
            "Seconds the engine loop thread spent in each phase of the "
            "step loop, each instant charged to the innermost phase open "
            "(label phase=step|preempt|plan|admit|prefill|prefill_sync|"
            "fold|dispatch|dispatch.rows|dispatch.launch|dispatch.count|"
            "sync|commit|commit.rows|commit.chunks|commit.stats|wait)",
        )
        m.new_counter(
            "app_engine_phase_cpu_seconds_total",
            "CPU seconds of the engine loop thread (its own thread clock) "
            "in each phase of the step loop: a phase's seconds less these "
            "are what the thread spent off the CPU — the GIL, a lock, a "
            "call blocked in the runtime (label phase= as "
            "app_engine_phase_seconds_total)",
        )
        m.new_counter(
            "app_engine_blocks_total",
            "Blocks launched while the newest block in flight had its "
            "result ready — the device had run dry and waited for the "
            "host (launch=idle) —, while it still ran (launch=queued), or "
            "with none in flight, after a wait for work (launch=none)",
        )
        m.new_counter(
            "app_step_tokens_total",
            "Token positions issued to the device per dispatch (label "
            "kind=decode|prefill|padding)",
        )
        m.new_counter(
            "app_sampler_steps_total",
            "Sampling steps issued, by the path of ops.sampling.sample_logits "
            "the rows' parameters select: a block's decode steps, and one "
            "for an admission's first token (label path=greedy|sample|filter)",
        )
        m.new_counter(
            "app_moe_path_blocks_total",
            "Blocks dispatched, by the branch of ops.moe.held_experts their "
            "decode rows take (label path=kernel|loop|grouped; sparse-expert "
            "models only)",
        )
        m.new_counter(
            "app_moe_expert_rows_total",
            "Rows routed to each routed expert this replica holds, over "
            "decode steps and layers, read with each block's tokens (label "
            "expert=the expert's published index; sparse-expert models only)",
        )
        m.new_counter(
            "app_moe_experts_read_total",
            "Held routed experts whose matrices a decode step read, over "
            "decode steps and expert layers, read with each block's tokens: "
            "over steps x expert layers x held experts it is the share of "
            "the held experts a step and layer reads (sparse-expert models only)",
        )
        m.new_counter(
            "app_dsa_positions_total",
            "Context positions a learned sparse attention's indexer scored "
            "and its attention read, over decode steps, rows and layers, "
            "read with each block's tokens (label kind=scored|selected; "
            "models with an index_topk only)",
        )
        m.new_gauge(
            "app_kv_pool_pages",
            "Pages of each pool of a cache that holds several (label pool="
            "the pool's name, state=used|total), set with each consumed "
            "block; models with a cache_spec only",
        )
        m.new_counter(
            "app_prefill_positions_total",
            "Prompt positions a model whose upper layers run on a prompt's "
            "last position alone computed (label part=self: the layers up to "
            "its shared cache, part=cross: the layers above)",
        )
        m.new_counter(
            "app_ssm_state_resets_total",
            "Slots whose recurrent state an admission started anew (a "
            "bucketed prefill's write, a chunk at position 0)",
        )
        m.new_gauge(
            "app_decode_block_size",
            "Decode steps fused per device dispatch (TPU_BATCH_MULTI_STEP)",
        )
        m.new_gauge(
            "app_detok_queue_depth",
            "Detokenization/stream emissions queued behind the off-engine-"
            "thread executor",
        )
        # continuous batching (serving/stepplan.py, docs/performance.md):
        # per-chunk prefill sizes and the step plan the engine assembled
        # each iteration — decode reserved first, chunks fill the rest
        m.new_histogram(
            "app_prefill_chunk_tokens",
            "Prompt tokens per committed prefill chunk (label "
            "kind=compute|prefix_hit)",
            buckets=(16, 32, 64, 128, 256, 512, 1024),
        )
        m.new_gauge(
            "app_step_plan_prefill_tokens",
            "Prefill-chunk tokens granted by the latest step plan",
        )
        m.new_gauge(
            "app_step_plan_decode_rows",
            "Decode rows reserved first by the latest step plan",
        )
        m.new_gauge(
            "app_step_plan_cursors",
            "Partially-prefilled requests carrying a live chunk cursor",
        )
        m.new_counter(
            "app_requests_shed_total",
            "Requests rejected by admission control (queue full or "
            "estimated wait past deadline/threshold)",
        )
        m.new_counter(
            "app_requests_deadline_exceeded_total",
            "Requests whose deadline passed before completion",
        )
        m.new_gauge(
            "app_estimated_queue_wait_seconds",
            "EWMA-estimated queue wait for a newly submitted request",
        )
        m.new_counter(
            "app_requests_kv_exhausted_total",
            "Rows retired mid-decode by KV-pool exhaustion (finish_reason "
            "kv_exhausted) — pool pressure, not a legitimate max-tokens stop",
        )
        # engine supervision plane (serving/supervisor.py)
        m.new_counter(
            "app_engine_restarts_total",
            "Completed self-healing engine warm restarts",
        )
        m.new_gauge(
            "app_engine_heartbeat_age_seconds",
            "Seconds since the engine loop last stamped its heartbeat",
        )
        m.new_gauge(
            "app_engine_supervisor_state",
            "Engine supervisor state: 0 UP, 1 SUSPECT, 2 RESTARTING, 3 WEDGED",
        )
        m.new_gauge(
            "app_service_breaker_state",
            "Circuit-breaker state per downstream service address: "
            "0 closed, 1 open",
        )
        # router tier (serving/router.py, docs/robustness.md "The router
        # plane"): per-replica state, failover/hedge counters, and the
        # tier-level queue-wait autoscaling signal
        m.new_gauge(
            "app_router_replica_state",
            "Router's view of each replica: 0 UP, 1 SUSPECT, 2 RESTARTING, "
            "3 DRAINING, 4 WEDGED, 5 DOWN",
        )
        m.new_counter(
            "app_router_failovers_total",
            "Requests re-routed to another replica after a retriable "
            "pre-first-token failure",
        )
        m.new_counter(
            "app_router_hedges_total",
            "Prefill admissions hedged on a second replica after the "
            "p99-based delay",
        )
        m.new_counter(
            "app_router_last_resort_routes_total",
            "Routes dispatched into a SUSPECT-only candidate pool (no UP "
            "replica anywhere: best-effort routing, the tier is coasting)",
        )
        m.new_gauge(
            "app_router_queue_wait_seconds",
            "Mean reported queue-wait EWMA across live replicas (the "
            "tier-level autoscaling signal)",
        )
        # request-lifecycle phase histograms (docs/observability.md): the
        # standard serving evaluation lens — TTFT, queue wait, end-to-end,
        # and the decode-block cadence the CPU-free hot loop ticks at.
        # TTFT carries source=engine (admission→first token) and
        # source=router (client submit→first token; the hedge p99 floor).
        ttft_buckets = (
            0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30,
        )
        m.new_histogram(
            "app_request_ttft_seconds",
            "Time to first token per request (label source=engine|router)",
            buckets=ttft_buckets,
        )
        m.new_histogram(
            "app_request_queue_wait_seconds",
            "Submit-to-admission queue wait per request",
            buckets=ttft_buckets,
        )
        m.new_histogram(
            "app_request_e2e_seconds",
            "Submit-to-terminal end-to-end latency per request",
        )
        m.new_histogram(
            "app_decode_block_seconds",
            "Wall time of one fused N-step decode block (dispatch to sync)",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1, 2.5),
        )
        # TPU device telemetry (serving/device_telemetry.py): HBM
        # occupancy per device + the engine loop's duty cycle — the
        # instrument panel the membership heartbeat's headroom fields and
        # the router's HBM-pressure spill read from
        m.new_gauge(
            "app_tpu_hbm_bytes",
            "Device HBM bytes (labels: device, kind=used|limit)",
        )
        m.new_gauge(
            "app_tpu_hbm_util",
            "Fraction of device HBM in use, per device",
        )
        m.new_gauge(
            "app_engine_duty_cycle",
            "Fraction of wall time the engine loop spent doing work (its "
            "phase account but wait, over the telemetry poll interval)",
        )
        # multi-tenant serving plane (serving/tenancy.py + serving/
        # lora.py, docs/serving.md "Multi-tenancy"): preemptions of
        # low-priority decode rows under pressure, and how many LoRA
        # adapters are resident in the device factor tables
        m.new_counter(
            "app_tenant_preemptions_total",
            "Decode rows paused by the preemption ladder so a higher "
            "class could run (label tenant = the PREEMPTED tenant)",
        )
        m.new_gauge(
            "app_lora_adapter_residency",
            "LoRA adapters resident in the device factor tables",
        )
        # the reclamation plane (serving/engine.py begin_reclaim +
        # prefix_index.py evacuate_chain, docs/robustness.md "The
        # reclamation plane"): provider notices honored, committed KV
        # moved to survivors, and how much of each notice deadline the
        # drain ladder actually consumed
        m.new_counter(
            "app_replica_reclamations_total",
            "Reclamation notices accepted by this replica's drain ladder",
        )
        m.new_counter(
            "app_kv_evacuations_total",
            "KV evacuation batches pushed to survivors during reclaim "
            "(label outcome = committed|failed|skipped)",
        )
        m.new_histogram(
            "app_reclaim_drain_seconds",
            "Wall time from reclamation notice to engine stop",
            buckets=(0.1, 0.25, 0.5, 1, 2, 5, 10, 30, 60, 120),
        )

    # -- accessors mirroring the reference's API ------------------------------
    @property
    def metrics(self) -> Manager:
        return self.metrics_manager

    def get_http_service(self, name: str) -> Any:
        """container.GetHTTPService (container/container.go:286-292)."""
        return self.services.get(name)

    def get_publisher(self) -> Any:
        """container/container.go:294-300."""
        return self.pubsub

    def get_subscriber(self) -> Any:
        return self.pubsub

    def register_datasource(self, name: str, ds: Any) -> None:
        """Wire + connect any provider-pattern datasource (external_db.go
        Add* analogue)."""
        wire_provider(ds, self.logger, self.metrics_manager, self.tracer)
        if name in ("tpu", "sql", "redis", "pubsub", "kv_store", "file", "cache"):
            setattr(self, name, ds)
        else:
            self.extra_datasources[name] = ds

    def datasource_pairs(self) -> list[tuple[str, Any]]:
        pairs = [
            ("tpu", self.tpu),
            ("sql", self.sql),
            ("redis", self.redis),
            ("pubsub", self.pubsub),
            ("kv_store", self.kv_store),
            ("file", self.file),
            ("cache", self.cache),
        ]
        pairs.extend(self.extra_datasources.items())
        return pairs

    def health(self) -> dict[str, Any]:
        from gofr_tpu.container.health import aggregate_health

        return aggregate_health(self)

    def close(self) -> None:
        """container/container.go:179-199."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        for name, ds in self.datasource_pairs():
            closer = getattr(ds, "close", None)
            if callable(closer):
                try:
                    closer()
                except Exception as exc:
                    self.logger.debug(f"error closing {name}: {exc}")
        if self.serving is not None and hasattr(self.serving, "stop"):
            try:
                self.serving.stop()
            except Exception:
                pass
        self.tracer.shutdown()
        for attr in ("_remote_log_thread", "_remote_trace_thread"):
            thread = getattr(self, attr, None)
            if thread is not None:
                thread._gofr_stop.set()


def _rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def new_container(config: Config | None = None, **kw: Any) -> Container:
    return Container(config, **kw)
