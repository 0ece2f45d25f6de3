"""TPU device telemetry for the engine's ``/metrics`` surface.

The scheduler can only make decisions the telemetry lets it see: ROADMAP
item 3 (saturation-driven autoscaling) and item 4 (on-chip prefill retuning)
both need continuously-exported device state — HBM pressure, KV-pool
occupancy against the remaining headroom, compile activity, and how much of
wall time the engine loop actually spends inside device programs. This module
samples all of it lazily on scrape (no background thread, no work between
scrapes) and renders Prometheus exposition lines the engine API server
appends to ``/metrics``.

Exported series (docs/observability.md has the reference table):

- ``vllm:tpu_hbm_bytes_in_use{device=...}`` / ``vllm:tpu_hbm_bytes_limit``
  — per-device memory via ``jax.local_devices()[i].memory_stats()``. On
  backends without device memory stats (CPU tests, some interpret modes)
  the sampler degrades to one ``device="host"`` row backed by process RSS /
  total host RAM, so dashboards keep a live series instead of a hole.
- ``vllm:hbm_headroom_bytes`` — sum(limit) - sum(in_use): what is left for
  KV growth, staging buffers, and compile workspaces.
- ``vllm:kv_pool_device_bytes`` / ``vllm:kv_pool_used_bytes`` — the paged KV
  pool's device footprint and its in-use share (occupancy x footprint), the
  pair the "HBM headroom" dashboard panel charts against headroom.
- ``vllm:compile_seconds_total`` / ``vllm:compile_events_total`` — cumulative
  XLA backend-compile wall time, hooked via ``jax.monitoring`` (the same
  listener feeds the flight recorder's ``compile`` events): a serving pod
  spending minutes here mid-traffic is retracing, which is exactly the
  regression the shape-bucketing scheduler exists to prevent.
- ``vllm:compile_cache_entries`` / ``vllm:compile_cache_bytes`` — persistent
  compilation-cache size on disk (utils/compile_cache.py), sampled at most
  every 30 s.
- ``vllm:engine_step_duty_cycle`` — fraction of wall time the engine loop
  spent inside device dispatches since the previous scrape (delta of
  ``loop_seconds["step"]`` over delta wall): ~1.0 means the device is the
  bottleneck, ~0.0 under load means the host side is.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from production_stack_tpu.utils.logging import init_logger

logger = init_logger(__name__)

# -- JAX compile listener -----------------------------------------------------
#
# jax.monitoring reports, on the thread that dispatches, how long a jitted
# call spent tracing the Python function, lowering the jaxpr to an MLIR module,
# and in the backend compile (a real compile or a load from the persistent
# cache alike). One process-global listener accumulates the backend-compile
# totals and mirrors each into the flight recorder, so a compile stall shows up
# in an anomaly dump next to the scheduler events it starved; while a
# ``capture_first_dispatch()`` is open on the calling thread it also splits
# that call's wall by phase (engine/runner.py ``_dispatch``).

_compile_lock = threading.Lock()
_compile_seconds_total = 0.0
_compile_events_total = 0
_listener_installed = False

_PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_capture = threading.local()


class capture_first_dispatch:
    """``with capture_first_dispatch() as phases:`` around ONE jitted call:
    ``phases`` ends up holding the seconds JAX reported on this thread for
    ``trace`` / ``lower`` / ``compile`` and the persistent cache's
    ``cache_hits`` / ``cache_misses`` (a miss is counted when the entry is
    written). Zeros when the monitoring API is unavailable."""

    def __enter__(self) -> dict:
        _capture.phases = phases = {
            "trace": 0.0, "lower": 0.0, "compile": 0.0,
            "cache_hits": 0, "cache_misses": 0,
        }
        return phases

    def __exit__(self, *exc) -> None:
        _capture.phases = None


def _on_event_duration(name: str, duration: float, **_kw) -> None:
    global _compile_seconds_total, _compile_events_total
    phase = _PHASE_OF_EVENT.get(name)
    if phase is None:
        return
    phases = getattr(_capture, "phases", None)
    if phases is not None:
        if phase == "trace":
            # jitted helpers called by the step function trace INSIDE its
            # trace and report first: the outermost duration holds them all
            phases["trace"] = max(phases["trace"], duration)
        else:
            phases[phase] += duration
    if phase != "compile":
        return
    with _compile_lock:
        _compile_seconds_total += duration
        _compile_events_total += 1
    try:
        from production_stack_tpu.tracing import get_flightrecorder

        get_flightrecorder().record(
            "compile", event="backend_compile", seconds=round(duration, 4)
        )
    except Exception:  # noqa: BLE001 - telemetry must never break a compile
        pass


def _on_event(name: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(name)
    phases = getattr(_capture, "phases", None)
    if key is not None and phases is not None:
        phases[key] += 1


def install_compile_listener() -> bool:
    """Register the jax.monitoring listeners once per process. Idempotent;
    returns whether they are active (False when JAX's monitoring API is
    unavailable — telemetry then reports zeros)."""
    global _listener_installed
    if _listener_installed:
        return True
    try:
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(_on_event_duration)
        monitoring.register_event_listener(_on_event)
    except Exception as e:  # noqa: BLE001 - monitoring API may be absent
        logger.warning("jax compile telemetry unavailable (%s)", e)
        return False
    _listener_installed = True
    return True


def compile_totals() -> tuple[float, int]:
    with _compile_lock:
        return _compile_seconds_total, _compile_events_total


class DeviceMonitor:
    """Lazy on-scrape sampler. Holds a reference to the engine (duck-typed:
    fake/test engines without a KV manager or loop_seconds degrade to the
    host-memory row and zero KV gauges) and caches device samples briefly so
    a scrape storm cannot turn telemetry into load."""

    SAMPLE_MAX_AGE_S = 1.0
    CACHE_SCAN_MAX_AGE_S = 30.0

    def __init__(self, engine=None):
        self.engine = engine
        self._mem_sample: tuple[float, list] = (0.0, [])
        self._cache_sample: tuple[float, int, int] = (0.0, 0, 0)
        self._cache_scanning = False
        self._duty_prev: Optional[tuple[float, float]] = None

    # -- device memory ------------------------------------------------------

    def _device_memory(self) -> list[dict]:
        """[{device, bytes_in_use, bytes_limit}] — per accelerator when the
        backend exposes memory_stats, else one host-memory fallback row."""
        now = time.monotonic()
        ts, cached = self._mem_sample
        if cached and now - ts < self.SAMPLE_MAX_AGE_S:
            return cached
        rows: list[dict] = []
        try:
            import jax

            for d in jax.local_devices():
                stats = None
                try:
                    stats = d.memory_stats()
                except Exception:  # noqa: BLE001 - backend-dependent API
                    stats = None
                if not stats:
                    continue
                rows.append({
                    "device": f"{d.platform}:{d.id}",
                    "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                    "bytes_limit": int(
                        stats.get("bytes_limit")
                        or stats.get("bytes_reservable_limit")
                        or 0
                    ),
                })
        except Exception:  # noqa: BLE001 - no jax / no devices: host fallback
            rows = []
        if not rows:
            rows = [self._host_memory_row()]
        self._mem_sample = (now, rows)
        return rows

    @staticmethod
    def _host_memory_row() -> dict:
        """CPU fallback: the process's RSS against total host RAM. Not HBM,
        but it keeps the dashboard series alive and the headroom math sane
        on CPU test rigs."""
        try:
            import psutil

            vm = psutil.virtual_memory()
            return {
                "device": "host",
                "bytes_in_use": int(psutil.Process().memory_info().rss),
                "bytes_limit": int(vm.total),
            }
        except Exception:  # noqa: BLE001 - psutil missing: zero row
            return {"device": "host", "bytes_in_use": 0, "bytes_limit": 0}

    def _pool_shards(self) -> "list[tuple[str, int]]":
        """Per-mesh-device KV pool footprint from the runner's static pool
        sharding (engine/runner.py kv_pool_shard_layout) — live buffers are
        donated every step and must not be introspected from the scrape
        thread. Fake/test engines without a runner degrade to no rows."""
        runner = getattr(self.engine, "runner", None)
        layout = getattr(runner, "kv_pool_shard_layout", None)
        if layout is None:
            return []
        try:
            return list(layout())
        except Exception:  # noqa: BLE001 - telemetry must never break a scrape
            return []

    # -- compile cache ------------------------------------------------------

    def _compile_cache_size(self) -> tuple[int, int]:
        """(entries, bytes) of the persistent XLA cache directory. The walk
        can touch thousands of files, and /metrics is served on the aiohttp
        event loop — so the scrape always returns the CACHED value and, when
        it is older than CACHE_SCAN_MAX_AGE_S, kicks a background refresh
        (first scrape reports zeros until the first walk lands)."""
        now = time.monotonic()
        ts, entries, size = self._cache_sample
        if (
            now - ts >= self.CACHE_SCAN_MAX_AGE_S or ts == 0.0
        ) and not self._cache_scanning:
            self._cache_scanning = True
            threading.Thread(target=self._scan_compile_cache, daemon=True).start()
        return entries, size

    def _scan_compile_cache(self) -> None:
        entries = size = 0
        try:
            from production_stack_tpu.utils import compile_cache

            root = compile_cache._enabled_dir
            if root and os.path.isdir(root):
                for dirpath, _dirs, files in os.walk(root):
                    for name in files:
                        try:
                            size += os.path.getsize(os.path.join(dirpath, name))
                            entries += 1
                        except OSError:
                            continue
        except Exception:  # noqa: BLE001 - cache dir races are harmless
            pass
        self._cache_sample = (time.monotonic(), entries, size)
        self._cache_scanning = False

    # -- duty cycle ---------------------------------------------------------

    def _duty_cycle(self) -> float:
        """d(step seconds)/d(wall) since the previous scrape; 0.0 when the
        engine does not account loop sections (fakes) or on the first
        scrape."""
        loop_seconds = getattr(self.engine, "loop_seconds", None)
        if not isinstance(loop_seconds, dict):
            return 0.0
        now = time.monotonic()
        step = float(loop_seconds.get("step", 0.0))
        prev = self._duty_prev
        self._duty_prev = (now, step)
        if prev is None or now - prev[0] <= 0:
            return 0.0
        return min(1.0, max(0.0, (step - prev[1]) / (now - prev[0])))

    # -- exposition ---------------------------------------------------------

    def metrics_lines(self, model: str) -> list[str]:
        labels = f'model_name="{model}"'
        lines = [
            "# TYPE vllm:tpu_hbm_bytes_in_use gauge",
            "# TYPE vllm:tpu_hbm_bytes_limit gauge",
        ]
        total_use = total_limit = 0
        for row in self._device_memory():
            dl = f'{labels},device="{row["device"]}"'
            lines.append(f"vllm:tpu_hbm_bytes_in_use{{{dl}}} {row['bytes_in_use']}")
            lines.append(f"vllm:tpu_hbm_bytes_limit{{{dl}}} {row['bytes_limit']}")
            total_use += row["bytes_in_use"]
            total_limit += row["bytes_limit"]
        lines += [
            "# TYPE vllm:hbm_headroom_bytes gauge",
            f"vllm:hbm_headroom_bytes{{{labels}}} {max(0, total_limit - total_use)}",
        ]
        kv = getattr(self.engine, "kv", None)
        page_bytes = int(getattr(self.engine, "kv_page_bytes", 0) or 0)
        if kv is not None and page_bytes:
            pool_bytes = kv.num_pages * page_bytes
            used = int(pool_bytes * kv.usage())
            lines += [
                "# TYPE vllm:kv_pool_device_bytes gauge",
                f"vllm:kv_pool_device_bytes{{{labels}}} {pool_bytes}",
                "# TYPE vllm:kv_pool_used_bytes gauge",
                f"vllm:kv_pool_used_bytes{{{labels}}} {used}",
            ]
            # per-mesh-device pool footprint: under tensor parallelism each
            # chip holds its kv-head shard of every page, so the per-shard
            # series (≈ pool/tp each) is what the per-shard HBM-headroom
            # panel charts — a device-0-only row would claim N× the real
            # per-chip load (docs/multichip-serving.md)
            shards = self._pool_shards()
            if shards:
                lines.append("# TYPE vllm:kv_pool_shard_bytes gauge")
                for dev, nbytes in shards:
                    dl = f'{labels},device="{dev}"'
                    lines.append(
                        f"vllm:kv_pool_shard_bytes{{{dl}}} {nbytes}"
                    )
        secs, events = compile_totals()
        entries, cache_bytes = self._compile_cache_size()
        lines += [
            "# TYPE vllm:compile_seconds_total counter",
            f"vllm:compile_seconds_total{{{labels}}} {round(secs, 4)}",
            "# TYPE vllm:compile_events_total counter",
            f"vllm:compile_events_total{{{labels}}} {events}",
            "# TYPE vllm:compile_cache_entries gauge",
            f"vllm:compile_cache_entries{{{labels}}} {entries}",
            "# TYPE vllm:compile_cache_bytes gauge",
            f"vllm:compile_cache_bytes{{{labels}}} {cache_bytes}",
            "# TYPE vllm:engine_step_duty_cycle gauge",
            f"vllm:engine_step_duty_cycle{{{labels}}} "
            f"{round(self._duty_cycle(), 4)}",
        ]
        return lines
