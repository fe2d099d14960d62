"""TPU device registry — chip enumeration, HBM stats, health.

SURVEY §7 stage 4: the device registry lives in the container
(``ctx.tpu``) and feeds chip/HBM state into the same health and
metrics surfaces every other datasource uses (health aggregation
container/health.go:8-98; the reference has no device analog).

Design points:
- enumeration runs in a worker thread with a deadline — a device
  runtime that hangs (a wedged driver, a chip another process holds)
  makes health report DOWN instead of hanging the health endpoint;
- results are cached with a TTL so /health and the metrics poller
  don't hammer the backend;
- ``jax`` imports lazily, keeping ``import gofr_tpu`` light.
"""

from __future__ import annotations

import concurrent.futures
import threading
import time
from typing import Any

#: how long device enumeration may take before health reports DOWN
PROBE_TIMEOUT_S = 10.0
#: cached device info remains fresh this long
CACHE_TTL_S = 10.0


class DeviceRegistry:
    def __init__(self, logger: Any = None, metrics: Any = None,
                 probe_timeout_s: float = PROBE_TIMEOUT_S,
                 cache_ttl_s: float = CACHE_TTL_S) -> None:
        self.logger = logger
        self.metrics = metrics
        self.probe_timeout_s = probe_timeout_s
        self.cache_ttl_s = cache_ttl_s
        self.engines: dict[str, Any] = {}
        self._lock = threading.Lock()
        self._cache: list[dict] | None = None
        self._cache_at = 0.0
        self._last_error: str | None = None

    # ------------------------------------------------------- enumeration
    @staticmethod
    def _probe() -> list[dict]:
        """Runs on a worker thread: enumerate devices + memory stats."""
        import jax
        out = []
        for d in jax.devices():
            info: dict[str, Any] = {
                "id": d.id,
                "platform": d.platform,
                "kind": getattr(d, "device_kind", ""),
                "process_index": getattr(d, "process_index", 0),
            }
            coords = getattr(d, "coords", None)
            if coords is not None:
                info["coords"] = list(coords)
            stats_fn = getattr(d, "memory_stats", None)
            if stats_fn is not None:
                try:
                    stats = stats_fn() or {}
                    info["hbm_bytes_in_use"] = stats.get("bytes_in_use")
                    info["hbm_bytes_limit"] = stats.get(
                        "bytes_limit", stats.get("bytes_reservable_limit"))
                except Exception:
                    pass
            out.append(info)
        return out

    def devices(self, refresh: bool = False) -> list[dict]:
        """Cached device info; empty list when the backend is
        unreachable (``last_error`` says why)."""
        with self._lock:
            fresh = (self._cache is not None
                     and time.time() - self._cache_at < self.cache_ttl_s)
            if fresh and not refresh:
                return list(self._cache)
        # bounded probe off-thread; the pool is not reused because a
        # stuck probe thread must not block later probes
        pool = concurrent.futures.ThreadPoolExecutor(
            1, thread_name_prefix="tpu-probe")
        try:
            future = pool.submit(self._probe)
            devices = future.result(self.probe_timeout_s)
            error = None
        except concurrent.futures.TimeoutError:
            devices, error = None, \
                f"device probe exceeded {self.probe_timeout_s}s"
        except Exception as exc:
            devices, error = None, repr(exc)
        finally:
            pool.shutdown(wait=False)
        with self._lock:
            self._last_error = error
            if devices is not None:
                self._cache = devices
                self._cache_at = time.time()
            # on error keep serving the stale cache (if any): health
            # flags DOWN via last_error while details stay useful
            return list(self._cache or [])

    @property
    def last_error(self) -> str | None:
        return self._last_error

    def device_count(self) -> int:
        return len(self.devices())

    # ---------------------------------------------------------- engines
    def register_engine(self, name: str, engine: Any) -> None:
        self.engines[name] = engine

    # ----------------------------------------------------------- health
    def health_check(self) -> dict:
        devices = self.devices()
        status = "UP" if devices and self._last_error is None else "DOWN"
        details: dict[str, Any] = {
            "devices": devices,
            "device_count": len(devices),
        }
        if self._last_error:
            details["error"] = self._last_error
            if devices:
                status = "DEGRADED"  # stale cache still served
        if self.engines:
            engine_health = {
                name: (e.health_check() if hasattr(e, "health_check")
                       else {"status": "UP"})
                for name, e in self.engines.items()}
            details["engines"] = engine_health
            # a stalled or crashed engine must surface at the slot
            # level — the aggregate health endpoint only reads status
            ranks = {"UP": 0, "DEGRADED": 1, "DOWN": 2}
            worst = max((h.get("status", "UP") for h in
                         engine_health.values()),
                        key=lambda s: ranks.get(s, 1))
            if ranks.get(worst, 0) > ranks.get(status, 0):
                status = worst
        return {"status": status, "details": details}

    # ---------------------------------------------------------- metrics
    def publish_metrics(self) -> None:
        """Push device gauges (app_tpu_device_count /
        app_tpu_hbm_bytes_used, registered in container.py)."""
        if self.metrics is None:
            return
        devices = self.devices()
        self.metrics.set_gauge("app_tpu_device_count", len(devices))
        for d in devices:
            used = d.get("hbm_bytes_in_use")
            if used is not None:
                self.metrics.set_gauge("app_tpu_hbm_bytes_used", used,
                                       device=str(d["id"]))

    async def metrics_loop(self, interval_s: float = 15.0) -> None:
        """Background task App.start runs: periodic gauge refresh."""
        import asyncio
        while True:
            try:
                self.publish_metrics()
            except Exception as exc:
                if self.logger is not None:
                    self.logger.debug(f"tpu metrics refresh failed: {exc}")
            await asyncio.sleep(interval_s)

    def close(self) -> None:
        pass
