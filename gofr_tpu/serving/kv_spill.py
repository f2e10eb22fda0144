"""Host-RAM KV spill tier: the DRAM layer under device HBM.

Prefix-cache entries (whole-prompt prefills AND chunk-boundary K/V delta
slabs) are bounded on device by the :class:`PrefixCache` LRU — before
this module, capacity eviction simply dropped them, so a repeated system
prompt whose slabs aged out of HBM paid a full re-prefill. AIBrix-style
multi-tier KV pooling (arXiv:2504.03648) says the next tier down is
nearly free: host DRAM is ~100x the size of the device prefix budget and
a re-upload is an async host→device copy the engine never waits on.

:class:`TieredPrefixCache` implements the container ``Cache`` contract
over two tiers:

- **device** — the existing :class:`PrefixCache` LRU of device arrays;
- **host** — :class:`HostSpillTier`, a byte-bounded LRU of the same
  pytrees as pinned host ``numpy`` arrays.

Eviction from the device tier *offers* the entry to a single-worker
spill executor; the worker materializes the slabs host-side
(``np.asarray`` — the device→host sync happens on the spill thread,
never the engine thread) and files them in the host LRU. A device-tier
miss that hits the host tier re-uploads via ``jnp.asarray`` — an async
host→device put that overlaps the in-flight decode block and commits at
the block's existing sync, so the one-sync-per-block contract
(docs/performance.md) is untouched — and promotes the entry back into
the device tier for the next hit.

The ``kv.spill`` chaos point sits on the spill worker: a fault there
drops the entry (the tier is advisory — a lost spill degrades to a
compute miss later, never an error).

Lock discipline (make lock-order, docs/static-analysis.md): the device
tier's lock and the host tier's lock are both LEAF-ONLY — neither tier
calls into the other, or into any callback, while holding its own lock.
"""

from __future__ import annotations

import concurrent.futures
import threading
from collections import OrderedDict
from typing import Any, Hashable

import numpy as np

from gofr_tpu import chaos
from gofr_tpu.serving.prefix_cache import PrefixCache, _tree_leaves

__all__ = ["HostSpillTier", "TieredPrefixCache"]


def _to_host(value: Any) -> Any:
    """Materialize a pytree of device arrays as host numpy arrays —
    structure-preserving for the (logits, k_slab, v_slab) tuples the
    prefix cache stores. Runs on the spill worker thread only."""
    if isinstance(value, tuple):
        return tuple(_to_host(v) for v in value)
    if isinstance(value, list):
        return [_to_host(v) for v in value]
    if isinstance(value, dict):
        return {k: _to_host(v) for k, v in value.items()}
    return np.asarray(value)


def _to_device(value: Any, device: Any = None) -> Any:
    """Put a pytree of host (or another device's) arrays on ``device``
    (None: the calling thread's default device). ``jax.device_put`` is
    ASYNC (no sync) — safe on the engine thread; the transfer overlaps
    the in-flight block and lands by its sync."""
    import jax

    return jax.device_put(value, device)


def _host_bytes(value: Any) -> int:
    return sum(
        int(getattr(leaf, "nbytes", 0)) for leaf in _tree_leaves(value)
    )


class HostSpillTier:
    """Byte-bounded LRU of host (numpy) KV pytrees. Thread-safe; the
    lock is leaf-only (never held across a call out — ``score`` is read
    BEFORE taking it).

    ``score`` (optional) upgrades the byte-pressure eviction order from
    raw LRU to timeline-observed reuse (serving/timeline.py
    ``TimelineRecorder.reuse_count``): among resident entries the LOWEST
    (score, LRU-age) evicts first, so a hot system prompt's slabs
    outlive a one-shot prompt's even when the one-shot was touched more
    recently — demotion follows what the request timelines actually
    observed being reused, not access recency alone."""

    def __init__(self, max_bytes: int,
                 score: Any = None) -> None:
        self.max_bytes = max_bytes
        self._score = score  # Callable[[key], number] | None
        self._entries: "OrderedDict[Hashable, Any]" = OrderedDict()
        self._sizes: dict[Hashable, int] = {}
        self._total_bytes = 0
        self._mu = threading.Lock()
        self._hits = 0
        self._misses = 0

    def put(self, key: Hashable, host_value: Any) -> None:
        size = _host_bytes(host_value)
        if size > self.max_bytes:
            return  # cannot ever fit: don't flush the tier for it
        scores: dict[Hashable, float] = {}
        if self._score is not None:
            # snapshot the resident keys UNDER the tier lock (engine
            # threads pop/touch the dict concurrently — iterating it
            # unlocked can raise mid-iteration), then score OUTSIDE it
            # (the scorer takes the timeline recorder's own leaf lock).
            # Keys racing in behind the snapshot default to 0 — a
            # brand-new entry has no observed reuse yet by definition.
            with self._mu:
                resident = list(self._entries.keys())
            for k in resident:
                try:
                    scores[k] = float(self._score(k))
                except Exception:
                    scores[k] = 0.0
        with self._mu:
            if key in self._entries:
                self._total_bytes -= self._sizes.get(key, 0)
            self._entries[key] = host_value
            self._sizes[key] = size
            self._total_bytes += size
            self._entries.move_to_end(key)
            while self._entries and self._total_bytes > self.max_bytes:
                if scores:
                    # reuse-scored demotion: lowest observed reuse goes
                    # first; ties fall back to LRU order (dict order is
                    # LRU; min() keeps the first == oldest on ties). The
                    # just-inserted key is exempt — evicting what we are
                    # inserting would thrash.
                    victims = [k for k in self._entries if k != key]
                    if not victims:
                        break
                    old_key = min(
                        victims, key=lambda k: scores.get(k, 0.0)
                    )
                    self._entries.pop(old_key, None)
                else:
                    old_key, _ = self._entries.popitem(last=False)
                self._total_bytes -= self._sizes.pop(old_key, 0)

    def get(self, key: Hashable) -> Any | None:
        with self._mu:
            value = self._entries.get(key)
            if value is None:
                self._misses += 1
                return None
            self._entries.move_to_end(key)
            self._hits += 1
            return value

    def pop(self, key: Hashable) -> Any | None:
        with self._mu:
            value = self._entries.pop(key, None)
            if value is not None:
                self._total_bytes -= self._sizes.pop(key, 0)
                self._hits += 1
            else:
                self._misses += 1
            return value

    def keys(self) -> list[Hashable]:
        with self._mu:
            return list(self._entries.keys())

    @property
    def total_bytes(self) -> int:
        with self._mu:
            return self._total_bytes

    def clear(self) -> None:
        with self._mu:
            self._entries.clear()
            self._sizes.clear()
            self._total_bytes = 0

    def stats(self) -> dict[str, Any]:
        with self._mu:
            return {
                "entries": len(self._entries),
                "bytes": self._total_bytes,
                "max_bytes": self.max_bytes,
                "hits": self._hits,
                "misses": self._misses,
            }


class TieredPrefixCache:
    """Two-tier prefix cache: a device :class:`PrefixCache` LRU over a
    host-RAM :class:`HostSpillTier`, presenting the same ``Cache``
    contract (get/put/evict/clear/stats) the engine already consumes —
    plus :meth:`get_with_tier` for per-request tier attribution
    (``/requestz`` ``prefix_tier``, ``app_kv_prefix_hits_total``).

    The spill executor is single-worker and process-cheap: device→host
    copies are serialized behind it, so a burst of evictions can never
    fan out sync pressure, and ``flush()`` gives tests/drain a
    deterministic settle point. A bounded backlog refuses NEW offers
    while full (counted in ``spill_dropped_total``) — the tier is
    advisory, and a spill queue growing without bound would just be a
    slower way to lose entries.
    """

    MAX_PENDING = 64

    def __init__(
        self,
        max_entries: int = 32,
        max_bytes: int = 256 * 1024 * 1024,
        spill_bytes: int = 1024 * 1024 * 1024,
        *,
        metrics: Any = None,
        reuse_score: Any = None,
    ) -> None:
        self._device = PrefixCache(
            max_entries, max_bytes=max_bytes, on_evict=self._offer
        )
        # reuse_score (Callable[[key], number], typically the timeline
        # recorder's reuse_count) upgrades host-tier demotion from raw
        # LRU to timeline-observed reuse ordering
        self._host = HostSpillTier(spill_bytes, score=reuse_score)
        self._metrics = metrics
        self._exec = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="kv-spill"
        )
        self._pending = 0
        self._pending_mu = threading.Lock()
        self._idle = threading.Event()
        self._idle.set()
        self.spilled_total = 0
        self.dropped_total = 0

    # -- Cache contract --------------------------------------------------------
    def get(self, key: Hashable) -> Any | None:
        value, _tier = self.get_with_tier(key)
        return value

    def get_with_tier(self, key: Hashable) -> tuple[Any | None, str]:
        """Lookup across tiers: returns ``(value, tier)`` with tier one
        of ``device`` / ``host`` / ``miss``. A host hit re-uploads (async
        put, engine thread never syncs), promotes the entry back into
        the device tier, and removes the host copy — if the promotion is
        evicted again it re-spills through the normal path."""
        value = self._device.get(key)
        if value is not None:
            return value, "device"
        host_value = self._host.pop(key)
        if host_value is None:
            return None, "miss"
        device_value = _to_device(host_value)
        self._device.put(key, device_value)
        return device_value, "host"

    def put(self, key: Hashable, value: Any) -> None:
        self._device.put(key, value)

    def peek(self, key: Hashable) -> Any | None:
        """NON-MUTATING cross-tier read for peer-serving fetches
        (``/kv/fetch``, ``local_engine_fetcher``): no host-tier pop, no
        device promotion — a remote replica reading this cache must not
        thrash the owner's device LRU or delete its only host copy.
        Returns device arrays from the device tier or host numpy arrays
        from the spill tier; the fetching side handles either."""
        value = self._device.get(key)
        if value is not None:
            return value
        return self._host.get(key)

    def keys(self) -> list[Hashable]:
        """All resident keys, device tier first (LRU order within each
        tier) — the bulk-evacuation walk enumerates both tiers: a
        paged-out span demoted to host RAM is exactly the KV a doomed
        replica most needs to push out."""
        device = self._device.keys()
        seen = set(device)
        return device + [k for k in self._host.keys() if k not in seen]

    def evict(self, key: Hashable) -> None:
        self._device.evict(key)
        self._host.pop(key)

    def clear(self) -> None:
        """Drop BOTH tiers (the engine's device-poison recovery path —
        a host copy of a poisoned slab would fail its re-upload probe
        anyway, and a cold tier only costs recompute)."""
        self._device.clear()
        self._host.clear()

    def stats(self) -> dict[str, Any]:
        out = self._device.stats()
        out["host"] = self._host.stats()
        out["spilled_total"] = self.spilled_total
        out["spill_dropped_total"] = self.dropped_total
        return out

    # -- the distributed index reads this --------------------------------------
    def advertised(self, limit: int = 128) -> list[tuple[str, str]]:
        """(key, tier) pairs for the gossip advertisement
        (serving/prefix_index.py), newest-first per tier, device tier
        first — bounded so a heartbeat stays a heartbeat."""
        out: list[tuple[str, str]] = []
        for key in reversed(self._device.keys()):
            out.append((str(key), "device"))
            if len(out) >= limit:
                return out
        for key in reversed(self._host.keys()):
            out.append((str(key), "host"))
            if len(out) >= limit:
                break
        return out

    # -- spill path (device-tier eviction → host tier) --------------------------
    def _offer(self, key: Hashable, value: Any) -> None:
        """Device-tier eviction hook: hand the dropped entry to the
        spill worker. Engine-thread side does NO device reads — the
        device→host materialization happens on the worker."""
        with self._pending_mu:
            if self._pending >= self.MAX_PENDING:
                self.dropped_total += 1
                return
            self._pending += 1
            self._idle.clear()
        try:
            self._exec.submit(self._spill_task, key, value)
        except RuntimeError:  # executor shut down: the tier is closing
            self._spill_done()

    def _spill_task(self, key: Hashable, value: Any) -> None:
        try:
            chaos.maybe_fail("kv.spill")
            host_value = _to_host(value)
            self._host.put(key, host_value)
            with self._pending_mu:
                self.spilled_total += 1
            if self._metrics is not None:
                self._metrics.set_gauge(
                    "app_kv_spill_bytes", float(self._host.total_bytes)
                )
        except Exception:
            # a poisoned device array (its dispatch died after donation)
            # raises out of np.asarray; an injected kv.spill fault lands
            # here too — either way the entry is dropped and a future
            # lookup degrades to a compute miss
            with self._pending_mu:
                self.dropped_total += 1
        finally:
            self._spill_done()

    def _spill_done(self) -> None:
        with self._pending_mu:
            self._pending -= 1
            if self._pending <= 0:
                self._idle.set()

    def flush(self, timeout: float = 5.0) -> bool:
        """Wait for every queued spill to settle (tests, drain)."""
        return self._idle.wait(timeout=timeout)

    def close(self) -> None:
        self._exec.shutdown(wait=False)
