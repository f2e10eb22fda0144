"""The system under test: the FULL serving stack, assembled for loadlab.

Everything real, nothing stubbed: real :class:`ServingEngine` replicas
(role-split prefill/decode by default, so the PR 14 two-phase disagg
submit path is live), the real :class:`Router` with heartbeats over the
real :class:`InMemoryBroker`, per-replica :class:`KVMigrator` peers for
warm prefix migration, a shared :class:`TenantRegistry` carrying the
PR 15 SLO classes, per-engine :class:`AdapterRegistry` LoRA tables, and
the real :class:`Autoscaler` over a :class:`SimulatedPoolDriver` — every
replica, including the initial pool, is built through the driver's
factory, so the scaler genuinely owns the pool it resizes.

The one concession to harness-hood: :meth:`ServingStack.kill` is an
ABRUPT death (announcer silenced like a dead process, engine
hard-stopped). The router is told nothing — it must discover the kill
through missed beats and typed-retriable submission errors, exactly the
discovery path tests/test_router_chaos.py pins on stub replicas.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any

from gofr_tpu.datasource.pubsub import InMemoryBroker
from gofr_tpu.serving import (
    ByteTokenizer,
    EngineConfig,
    KVMigrator,
    LocalReplica,
    ReplicaAnnouncer,
    Router,
    RouterConfig,
    ServingEngine,
    local_engine_fetcher,
    local_engine_store,
)
from gofr_tpu.serving.autoscaler import (
    Autoscaler,
    AutoscalerConfig,
    SimulatedPoolDriver,
)
from gofr_tpu.serving.lora import AdapterRegistry, make_adapter
from gofr_tpu.serving.tenancy import TenantPolicy, TenantRegistry


@dataclasses.dataclass
class StackConfig:
    """Shape of the tier. The defaults build the smallest stack that
    still exercises every plane: one prefill + two decode replicas
    (role-split disagg active — the router's two-phase submit needs both
    roles present), autoscaler on the decode pool, prefix cache + host
    spill on, heartbeats at CI cadence."""

    roles: tuple[str, ...] = ("prefill", "decode", "decode")
    max_slots: int = 8
    max_seq_len: int = 128
    prefill_buckets: tuple[int, ...] = (16,)
    prefill_chunk_tokens: int = 16
    max_queue: int = 256
    prefix_cache_entries: int = 64
    kv_spill_bytes: int = 64 << 20
    shed_cold_prior_s: float = 0.0
    shed_max_wait_s: float = 0.0
    heartbeat_s: float = 0.05
    suspect_after_s: float = 0.6
    down_after_s: float = 3.0
    autoscale: bool = True
    autoscale_roles: tuple[str, ...] = ("decode",)
    autoscale_max: int = 4
    autoscale_up_wait_s: float = 0.35
    autoscale_up_stable_s: float = 0.5
    autoscale_interval_s: float = 0.25
    # role -> how many of that role's INITIAL replicas are preemptible
    # capacity (reclamation notices only ever target these; scale-up
    # backfill is always on-demand). {} = all-on-demand fleet.
    preemptible: dict[str, int] = dataclasses.field(default_factory=dict)
    # notice budget handed to ServingEngine.begin_reclaim when a
    # reclamation notice lands (docs/robustness.md "The reclamation
    # plane")
    notice_deadline_s: float = 2.0
    # tenant -> slo class for the shared registry; adapter ids registered
    # on every engine's LoRA table
    tenants: dict[str, str] = dataclasses.field(default_factory=dict)
    adapters: tuple[str, ...] = ()
    # directory for per-replica timeline JSONL exports (None = in-memory
    # ring only; the scorer then audits engine.timeline directly)
    export_dir: str | None = None
    # warm-up wave before the trace clock starts: JIT compiles (prefill
    # buckets, decode batch shapes, adapter variants) are process-wide
    # one-time costs; paying them during open-loop replay builds a
    # backlog the horizon never drains
    warmup: bool = True
    warmup_concurrency: int = 8
    # HA pair (docs/robustness.md "The HA plane"): build a SECOND router
    # over its own consumer-group view of the same heartbeat log, so
    # both routers observe every beat all run long. crash_router()
    # promotes it by pointer swap — the replica-side dedup registry and
    # epoch fence are what make the pair safe, not router coordination.
    standby_router: bool = False


class ServingStack:
    """Builder + lifecycle owner for the tier. Use as a context manager:

        with ServingStack(cfg, params, config) as stack:
            result = run_trace(stack, trace, plan=plan)
    """

    def __init__(self, cfg: Any, params: Any,
                 config: StackConfig | None = None) -> None:
        self.model_cfg = cfg
        self.params = params
        self.config = config or StackConfig()
        self.broker = InMemoryBroker(consumer_group="loadlab-router")
        router_cfg = RouterConfig(
            heartbeat_s=self.config.heartbeat_s,
            suspect_after_s=self.config.suspect_after_s,
            down_after_s=self.config.down_after_s,
            spill_wait_s=0.25,
        )
        self.router = Router(router_cfg, broker=self.broker)
        self.tenant_registry = TenantRegistry()
        # the router steers interactive-class traffic off preemptible
        # capacity; it needs the registry to resolve a request's class
        self.router.use_tenants(self.tenant_registry)
        # the HA pair: the standby consumes the SAME heartbeat log under
        # its own consumer group (both routers see every beat), stays
        # warm all run, and is promoted by crash_router()'s pointer swap
        self.standby: Router | None = None
        self.routers: list[Router] = [self.router]
        self.router_crashes = 0
        if self.config.standby_router:
            self.standby = Router(
                RouterConfig(
                    heartbeat_s=self.config.heartbeat_s,
                    suspect_after_s=self.config.suspect_after_s,
                    down_after_s=self.config.down_after_s,
                    spill_wait_s=0.25,
                ),
                broker=self.broker.group_view("loadlab-router-b"),
            )
            self.standby.use_tenants(self.tenant_registry)
            self.routers.append(self.standby)
        for name, slo_class in self.config.tenants.items():
            self.tenant_registry.set_policy(
                TenantPolicy(name=name, deadline_class=slo_class)
            )
        self._mu = threading.Lock()
        self.engines: dict[str, ServingEngine] = {}
        self.announcers: dict[str, ReplicaAnnouncer] = {}
        self.migrators: dict[str, KVMigrator] = {}
        self.exporters: dict[str, Any] = {}
        self.killed: list[str] = []
        self._replicas_built = 0  # picks each new replica's device
        self.pool = SimulatedPoolDriver(
            self.router, self._build_replica, on_reap=self._on_reap
        )
        self.autoscaler: Autoscaler | None = None
        if self.config.autoscale:
            counts = {
                role: self.config.roles.count(role)
                for role in self.config.autoscale_roles
            }
            self.autoscaler = Autoscaler(
                self.router, self.pool,
                AutoscalerConfig(
                    interval_s=self.config.autoscale_interval_s,
                    min_replicas=max(min(counts.values() or [1]), 1),
                    max_replicas=self.config.autoscale_max,
                    scale_up_wait_s=self.config.autoscale_up_wait_s,
                    up_stable_s=self.config.autoscale_up_stable_s,
                    cooldown_s=1.0,
                    down_stable_s=30.0,  # never scale down inside a run
                ),
                roles=self.config.autoscale_roles,
            )
        self._started = False

    # -- the pool factory (runs on the autoscaler thread too) ---------------
    def _build_replica(self, role: str, rid: str,
                       preemptible: bool = False) -> LocalReplica:
        import jax

        migrator = KVMigrator(rid, self.router.prefix_index)
        # one replica per device, round-robin: each engine copies the
        # weights onto its own chip instead of sharing one params tree
        # (on a one-device host they all land on that device)
        devices = jax.local_devices()
        with self._mu:
            device = devices[self._replicas_built % len(devices)]
            self._replicas_built += 1
        lora = None
        if self.config.adapters:
            lora = AdapterRegistry(
                max_active=max(len(self.config.adapters) + 1, 2),
                device=device,
            )
            for i, adapter_id in enumerate(self.config.adapters):
                lora.register(make_adapter(
                    self.model_cfg, adapter_id, rank=2, seed=1000 + i
                ))
        engine = ServingEngine(
            self.model_cfg, self.params,
            EngineConfig(
                max_slots=self.config.max_slots,
                max_seq_len=self.config.max_seq_len,
                prefill_buckets=self.config.prefill_buckets,
                prefill_chunk_tokens=self.config.prefill_chunk_tokens,
                max_queue=self.config.max_queue,
                prefix_cache_entries=self.config.prefix_cache_entries,
                kv_spill_bytes=self.config.kv_spill_bytes,
                shed_cold_prior_s=self.config.shed_cold_prior_s,
                shed_max_wait_s=self.config.shed_max_wait_s,
                role=role,
                preemptible=preemptible,
            ),
            ByteTokenizer(self.model_cfg.vocab_size),
            kv_migrator=migrator,
            lora=lora,
            tenants=self.tenant_registry,
            device=device,
        )
        exporter = None
        if self.config.export_dir:
            exporter = engine.timeline.export_jsonl(
                os.path.join(self.config.export_dir, f"{rid}.timelines.jsonl")
            )
        with self._mu:
            # warm-migration mesh: full peering, both directions — pull
            # fetchers for handoff/affinity migration AND push stores
            # for reclamation evacuation (serving/prefix_index.py)
            for other_rid, other_engine in self.engines.items():
                migrator.add_peer(other_rid, local_engine_fetcher(other_engine))
                migrator.add_push_peer(
                    other_rid, local_engine_store(other_engine)
                )
                self.migrators[other_rid].add_peer(
                    rid, local_engine_fetcher(engine)
                )
                self.migrators[other_rid].add_push_peer(
                    rid, local_engine_store(engine)
                )
            self.engines[rid] = engine
            self.migrators[rid] = migrator
            if exporter is not None:
                self.exporters[rid] = exporter
        engine.start()
        announcer = ReplicaAnnouncer(
            rid, engine, self.broker, interval_s=self.config.heartbeat_s,
            role=role,
        )
        announcer.start()
        with self._mu:
            self.announcers[rid] = announcer
            standby = self.standby
        if standby is not None:
            # the standby needs its own handle registered (the pool
            # driver only registers with the primary); membership state
            # still comes from the shared heartbeat stream
            standby.add_replica(LocalReplica(rid, engine, role=role))
        return LocalReplica(rid, engine, role=role)

    def _on_reap(self, handle: Any) -> None:
        """Autoscaler scale-down teardown: silence the announcer, stop
        the engine (already drained by the pool driver)."""
        rid = handle.replica_id
        with self._mu:
            announcer = self.announcers.get(rid)
        if announcer is not None:
            announcer.stop(final_beat=True)
        handle.engine.stop()

    # -- lifecycle ----------------------------------------------------------
    def start(self, ready_timeout_s: float = 30.0) -> "ServingStack":
        if self._started:
            return self
        self._started = True
        for router in self.routers:
            router.start()
        for role in dict.fromkeys(self.config.roles):
            total = self.config.roles.count(role)
            spot = min(self.config.preemptible.get(role, 0), total)
            if total - spot:
                self.pool.scale_up(role, total - spot)
            if spot:
                self.pool.scale_up(role, spot, preemptible=True)
        import time as _time

        deadline = _time.monotonic() + ready_timeout_s
        # candidates(role=None) excludes prefill specialists by design,
        # so readiness is judged per role
        want = {
            role: self.config.roles.count(role)
            for role in dict.fromkeys(self.config.roles)
        }
        while _time.monotonic() < deadline:
            # EVERY router in the HA pair must see the full tier: a
            # standby promoted before its membership warmed would route
            # into a half-known fleet
            have = {
                role: min(
                    len(r.membership.candidates(role=role))
                    for r in self.routers
                )
                for role in want
            }
            if all(have[role] >= n for role, n in want.items()):
                break
            _time.sleep(0.01)
        else:
            raise RuntimeError(f"stack never became routable: {have}/{want}")
        if self.config.warmup:
            self.warm()
        if self.autoscaler is not None:
            self.autoscaler.start()
        return self

    def warm(self, concurrency: int | None = None,
             timeout_s: float = 120.0) -> None:
        """Pre-trace warm-up through the ROUTER (so the disagg two-phase
        path compiles too): a concurrent wave to populate every decode
        batch shape, plus one request per registered adapter for the
        LoRA jaxpr variants. Blocks until the wave settles."""
        n = concurrency or self.config.warmup_concurrency
        futs = []
        for i in range(n):
            futs.append(self.router.submit(
                f"warmup {i} " + "x" * 24, max_new_tokens=4, temperature=0.0
            ))
        for adapter_id in self.config.adapters:
            futs.append(self.router.submit(
                f"warmup adapter {adapter_id} " + "x" * 24,
                max_new_tokens=4, temperature=0.0, adapter_id=adapter_id,
            ))
        for fut in futs:
            try:
                fut.result(timeout=timeout_s)
            except Exception:  # noqa: BLE001 - warm-up best-effort
                pass

    def stop(self) -> None:
        if not self._started:
            return
        self._started = False
        if self.autoscaler is not None:
            self.autoscaler.stop()
        with self._mu:
            announcers = list(self.announcers.values())
            engines = list(self.engines.items())
            exporters = list(self.exporters.values())
        for announcer in announcers:
            announcer.stop(final_beat=False)
        for router in self.routers:
            router.stop()
        for rid, engine in engines:
            if rid not in self.killed:
                engine.stop()
        for exporter in exporters:
            exporter.close()

    def __enter__(self) -> "ServingStack":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.stop()

    # -- chaos action surface ------------------------------------------------
    def kill(self, rid: str | None = None) -> str:
        """Abrupt replica death. Picks the first live decode replica (the
        role with siblings) when ``rid`` is None; the announcer dies
        silent and the engine hard-stops — queued + in-flight work fails
        retriable (the PR 5 stop contract), and the ROUTER must discover
        the death on its own."""
        with self._mu:
            if rid is None:
                live_decode = [
                    r for r in self.pool.replica_ids("decode")
                    if r not in self.killed
                ]
                pool = live_decode or [
                    r for r in self.engines if r not in self.killed
                ]
                if not pool:
                    raise RuntimeError("no live replica to kill")
                rid = sorted(pool)[0]
            engine = self.engines[rid]
            announcer = self.announcers.get(rid)
            self.killed.append(rid)
        if announcer is not None:
            announcer.stop(final_beat=False)  # dies silent, like a process
        engine.stop()
        return rid

    def crash_router(self) -> str:
        """Abrupt death of the ACTIVE router (docs/robustness.md "The HA
        plane"). The standby — warm on the same heartbeat stream under
        its own consumer group all run — is promoted by pointer swap
        FIRST (the driver reads ``stack.router`` per submit, so the very
        next arrival rides the survivor), then the dead router is
        hard-stopped. Requests in flight on the dead router keep
        settling (their replica attempts are live; settlement callbacks
        run on replica threads), but its failover machinery dies with
        it — exactly a process crash's blast radius. The replica-side
        dedup registry + epoch fence are what make the promoted router
        safe against double-serving, not any router-to-router handshake."""
        with self._mu:
            if self.standby is None:
                raise RuntimeError(
                    "no standby router (StackConfig.standby_router=False, "
                    "or already crashed once)"
                )
            old, self.router = self.router, self.standby
            self.standby = None
            self.router_crashes += 1
        old.stop()
        return "router"

    def notice(self, rid: str | None = None,
               deadline_s: float | None = None) -> str | None:
        """Reclamation notice: the cloud provider wants a preemptible
        replica back in ``deadline_s`` seconds. Unlike :meth:`kill` this
        is the ORDERLY path — the pool driver delivers the notice (a
        chaos fault at ``replica.reclaim`` models a LOST notice, never a
        kill) and the engine runs its drain → evacuate → stop ladder.
        Picks the first live preemptible replica when ``rid`` is None;
        returns the target id (None when no preemptible replica is
        live)."""
        if deadline_s is None:
            deadline_s = self.config.notice_deadline_s
        with self._mu:
            if rid is None:
                spot = [
                    r for r in self.pool.preemptible_ids()
                    if r not in self.killed
                ]
                if not spot:
                    return None
                rid = sorted(spot)[0]
        self.pool.notice(rid, deadline_s=deadline_s)
        return rid

    def notice_storm(self, deadline_s: float | None = None) -> list[str]:
        """Every live preemptible replica noticed at once — the
        worst-case reclamation event the batch-goodput-only degradation
        claim is asserted against."""
        if deadline_s is None:
            deadline_s = self.config.notice_deadline_s
        with self._mu:
            spot = sorted(
                r for r in self.pool.preemptible_ids()
                if r not in self.killed
            )
        for rid in spot:
            self.pool.notice(rid, deadline_s=deadline_s)
        return spot

    # -- audit surface -------------------------------------------------------
    def timelines(self) -> list[Any]:
        """Every RequestTimeline the tier ever recorded — all replicas,
        including killed and scaled-up ones (in-flight + completed-ring;
        the JSONL exporters hold the unbounded history)."""
        with self._mu:
            engines = list(self.engines.values())
        out: list[Any] = []
        for engine in engines:
            out.extend(engine.timeline.all())
        return out

    def snapshot(self) -> dict[str, Any]:
        with self._mu:
            rids = list(self.engines)
            killed = list(self.killed)
            migrators = list(self.migrators.values())
        return {
            "replicas": rids,
            "killed": killed,
            "scale_ups": (
                self.autoscaler.scale_ups_total if self.autoscaler else 0
            ),
            "scale_downs": (
                self.autoscaler.scale_downs_total if self.autoscaler else 0
            ),
            "routed_total": sum(r.routed_total for r in self.routers),
            "failovers_total": sum(r.failovers_total for r in self.routers),
            "router_crashes": self.router_crashes,
            "preemptible": sorted(self.pool.preemptible_ids()),
            "notices_total": self.pool.notices_total,
            "notices_dropped_total": self.pool.notices_dropped_total,
            "kv_evacuations_total": sum(
                m.evacuations_total for m in migrators
            ),
            "kv_evacuations_failed_total": sum(
                m.failed_evacuations_total for m in migrators
            ),
        }
