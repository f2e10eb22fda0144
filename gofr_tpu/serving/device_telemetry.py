"""TPU device-state telemetry: HBM occupancy + engine duty cycle.

The serving data plane is only as observable as its device is
(ROADMAP north star: "TPU device/HBM state surfaced through the existing
health/metrics/tracing middleware"). This poller samples, on its own
daemon thread and NEVER on the engine thread:

- **per-device HBM** via ``device.memory_stats()`` (PJRT exposes
  ``bytes_in_use`` / ``bytes_limit`` on TPU; backends without stats —
  CPU — simply report no devices), exported as ``app_tpu_hbm_bytes``
  (labels ``device``, ``kind=used|limit``) and ``app_tpu_hbm_util``;
- **engine duty cycle** from the loop thread's cumulative busy counter
  (``ServingEngine.busy_seconds()``: the step loop's phase account but
  ``wait``):
  Δbusy/Δwall over the poll interval, exported as
  ``app_engine_duty_cycle``.

The sample is embedded in ``engine.health_check()`` (``details.device``)
and the membership announcer reads :meth:`hbm_headroom` into the
heartbeat's ``hbm_free_frac`` — so the router's spill policy reacts to
real HBM pressure (serving/router.py ``spill_hbm_frac``).

Reading ``memory_stats()`` is a host-side PJRT query — allocator
counters, not a device computation: it forces no sync with in-flight
dispatches, so polling cannot perturb the CPU-free decode loop.
"""

from __future__ import annotations

import threading
import time
from typing import Any


class DeviceTelemetry:
    """Background device-state poller. ``start()`` spawns the daemon
    thread; ``sample()`` is also callable inline (tests, one-shot
    health probes)."""

    def __init__(
        self,
        engine: Any = None,
        *,
        metrics: Any = None,
        logger: Any = None,
        interval_s: float = 5.0,
    ) -> None:
        self.engine = engine
        self._metrics = metrics
        self._logger = logger
        self.interval_s = max(0.1, float(interval_s))
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._mu = threading.Lock()
        self._last: dict[str, Any] = {}
        # duty-cycle window: (busy_seconds, monotonic) at the last poll
        self._duty_mark: tuple[float, float] | None = None
        if engine is not None:
            # health_check embeds last_sample(); the announcer finds the
            # poller for its heartbeat headroom through this backref
            engine.device_telemetry = self

    # -- sampling --------------------------------------------------------------
    @staticmethod
    def _device_stats() -> list[dict[str, Any]]:
        try:
            import jax

            devices = jax.local_devices()
        except Exception:
            return []
        out: list[dict[str, Any]] = []
        for dev in devices:
            entry: dict[str, Any] = {
                "id": int(getattr(dev, "id", len(out))),
                "platform": str(getattr(dev, "platform", "unknown")),
            }
            try:
                stats = dev.memory_stats() or {}
            except Exception:
                stats = {}
            used = stats.get("bytes_in_use")
            limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
            if used is not None:
                entry["hbm_used_bytes"] = int(used)
            if limit:
                entry["hbm_limit_bytes"] = int(limit)
                if used is not None:
                    entry["hbm_util"] = round(int(used) / int(limit), 4)
            out.append(entry)
        return out

    def _duty_cycle(self, now: float) -> float | None:
        engine = self.engine
        if engine is None or not hasattr(engine, "busy_seconds"):
            return None
        busy = float(engine.busy_seconds())
        mark = self._duty_mark
        self._duty_mark = (busy, now)
        if mark is None:
            return None  # first poll: no window yet
        busy0, t0 = mark
        wall = now - t0
        if wall <= 0:
            return None
        return max(0.0, min(1.0, (busy - busy0) / wall))

    def sample(self) -> dict[str, Any]:
        """Take one sample, export the gauges, cache it for health."""
        now = time.monotonic()
        devices = self._device_stats()
        duty = self._duty_cycle(now)
        out: dict[str, Any] = {"devices": devices, "sampled_unix": time.time()}
        if duty is not None:
            out["engine_duty_cycle"] = round(duty, 4)
        hbm = self._headroom_of(devices)
        if hbm is not None:
            out["hbm_free_frac"] = round(hbm, 4)
        if self._metrics is not None:
            for dev in devices:
                dev_label = str(dev["id"])
                used = dev.get("hbm_used_bytes")
                limit = dev.get("hbm_limit_bytes")
                if used is not None:
                    self._metrics.set_gauge(
                        "app_tpu_hbm_bytes", used,
                        device=dev_label, kind="used",
                    )
                    self._metrics.set_gauge(
                        "app_tpu_hbm_used_bytes", used, device=dev_label,
                    )
                if limit is not None:
                    self._metrics.set_gauge(
                        "app_tpu_hbm_bytes", limit,
                        device=dev_label, kind="limit",
                    )
                    self._metrics.set_gauge(
                        "app_tpu_hbm_limit_bytes", limit, device=dev_label,
                    )
                if dev.get("hbm_util") is not None:
                    self._metrics.set_gauge(
                        "app_tpu_hbm_util", dev["hbm_util"], device=dev_label,
                    )
            if duty is not None:
                # ONLY app_engine_duty_cycle: app_tpu_duty_cycle belongs
                # to TPUClient's execute-duty window (datasource/tpu) —
                # two writers with different semantics would make the
                # unlabeled series flap between meanings
                self._metrics.set_gauge("app_engine_duty_cycle", duty)
        with self._mu:
            self._last = out
        return out

    @staticmethod
    def _headroom_of(devices: list[dict[str, Any]]) -> float | None:
        """The tightest device's free-HBM fraction — what the membership
        heartbeat publishes as ``hbm_free_frac``."""
        fracs = [
            1.0 - dev["hbm_util"]
            for dev in devices
            if dev.get("hbm_util") is not None
        ]
        return min(fracs) if fracs else None

    # -- consumers -------------------------------------------------------------
    def last_sample(self) -> dict[str, Any]:
        with self._mu:
            return dict(self._last)

    def hbm_headroom(self) -> float | None:
        """Free-HBM fraction of the tightest local device, from the last
        poll (never samples inline — the announcer calls this per beat)."""
        with self._mu:
            return self._last.get("hbm_free_frac")

    # -- lifecycle -------------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self.sample()  # prime: health/heartbeats see data before interval 1
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="device-telemetry",
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.sample()
            except Exception as exc:
                if self._logger is not None:
                    self._logger.debug(f"device telemetry poll failed: {exc}")

    def stop(self) -> None:
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=2.0)
        self._thread = None
