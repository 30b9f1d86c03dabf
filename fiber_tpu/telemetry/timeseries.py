"""Continuous metrics time-series: the per-process monitor sampler.

The registry (:mod:`.metrics`) holds *current* values; ``fiber-tpu
metrics`` renders them point-in-time. What an operator watching a
long-lived cluster actually needs is the **derivative**: tasks/s right
now, bytes/s over the last interval, whether the queue is growing.
This module is that layer — a sampler thread snapshots a small, fixed
set of load-bearing instruments every ``monitor_interval_s`` seconds
into bounded rings of ``(wall, monotonic, value)`` points and derives
rates from consecutive points. The anomaly watchdog
(:mod:`.monitor`) rides the same tick, ``fiber-tpu top`` renders the
per-host snapshots, and ``fiber-tpu metrics --watch`` reuses the rate
math between its polls.

Design constraints, mirrored from the rest of the plane:

* **Near-zero when off** — ``monitor_enabled=False`` means no thread,
  no rings, no per-tick work; :func:`MonitorSampler.configure` is the
  only cost (one call per ``telemetry.refresh``).
* **Bounded** — every series is a ring of ``monitor_history`` points;
  a week-long master holds the same memory as a minute-long one.
* **Dual clocks** — each point carries wall time (comparable across
  hosts, subject to NTP) and the process monotonic clock (immune to
  wall steps, meaningless across processes). Rates are derived on the
  monotonic axis; cross-host merges order on the wall axis with the
  monotonic value as a same-process tiebreak (see flightrec
  ``order_events``).
"""

from __future__ import annotations

import atexit
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from fiber_tpu.telemetry import tracing
from fiber_tpu.utils.logging import get_logger

logger = get_logger()

#: Instruments the sampler tracks: series name -> registry metric. The
#: set is deliberately small and fixed — the monitor answers "is the
#: cluster healthy", not "what is every counter doing" (that is the
#: registry snapshot's job).
TRACKED_COUNTERS = {
    "tasks_completed": "pool_tasks_completed",
    "tasks_submitted": "pool_tasks_submitted",
    "bytes_tx": "transport_bytes_tx",
    "bytes_rx": "transport_bytes_rx",
    # the device plane's calls (telemetry/device.py ``step``), all fns
    # together: what a training job's throughput reads on either side
    # of a stall
    "device_steps": "device_steps",
    "device_step_units": "device_step_units",
}
TRACKED_GAUGES = {
    "queue_depth": "pool_queue_depth",
    "inflight": "pool_inflight_tasks",
    "tx_queue_bytes": "transport_evloop_tx_queue_bytes",
    # Device telemetry plane (docs/observability.md "Device telemetry"):
    # gauges only ever set when a device runtime reports them (CPU and
    # agent processes leave them unset -> 0 in the series; the honest
    # null lives in device_snapshot / the hbm_fill rule's probe).
    "hbm_bytes_in_use": "device_hbm_bytes_in_use",
    "live_array_bytes": "device_live_array_bytes",
}
#: Counter series whose per-second rate rides the sample dict (the
#: ``fiber-tpu top`` columns).
RATE_SERIES = {
    "tasks_completed": "tasks_per_s",
    "bytes_tx": "bytes_tx_per_s",
    "bytes_rx": "bytes_rx_per_s",
    "device_steps": "steps_per_s",
    "device_step_units": "step_units_per_s",
}


class SeriesRing:
    """Bounded FIFO of ``(wall, mono, value)`` points (oldest fall out
    past capacity). Lock-free appends are fine — only the sampler
    thread writes; readers copy under the sampler's lock."""

    __slots__ = ("_points", "capacity")

    def __init__(self, capacity: int = 600) -> None:
        self.capacity = max(2, int(capacity))
        self._points: List[Tuple[float, float, float]] = []

    def add(self, wall: float, mono: float, value: float) -> None:
        self._points.append((wall, mono, float(value)))
        if len(self._points) > self.capacity:
            del self._points[: len(self._points) - self.capacity]

    def points(self) -> List[Tuple[float, float, float]]:
        return list(self._points)

    def last(self) -> Optional[Tuple[float, float, float]]:
        return self._points[-1] if self._points else None

    def rate(self) -> float:
        """Per-second delta between the two newest points (counter
        series; negative deltas — a registry reset — clamp to 0)."""
        if len(self._points) < 2:
            return 0.0
        (_, m0, v0), (_, m1, v1) = self._points[-2], self._points[-1]
        dt = m1 - m0
        if dt <= 0:
            return 0.0
        return max(0.0, (v1 - v0) / dt)

    def resize(self, capacity: int) -> None:
        self.capacity = max(2, int(capacity))
        if len(self._points) > self.capacity:
            del self._points[: len(self._points) - self.capacity]

    def __len__(self) -> int:
        return len(self._points)


def _metric_total(registry, name: str) -> Optional[float]:
    """Sum of every label set of one scalar metric, or None when the
    metric was never registered in this process."""
    inst = registry.get(name)
    if inst is None:
        return None
    with registry._lock:
        try:
            return float(sum(inst._series.values()))
        except TypeError:  # histogram series are lists; not tracked
            return None


class MonitorSampler:
    """Samples the registry into rings on a daemon thread and fans each
    sample out to observers (the anomaly watchdog). Probes run first so
    pull-style gauges (pool queue depth) are fresh at sample time."""

    def __init__(self, capacity: int = 600, interval: float = 1.0) -> None:
        self.enabled = False
        self._interval = float(interval)
        self._capacity = int(capacity)
        self._lock = threading.Lock()
        self._series: Dict[str, SeriesRing] = {}
        self._probes: List[Callable[[], None]] = []
        self._observers: List[Callable[[Dict[str, Any]], None]] = []
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self.samples = 0          # lifetime ticks taken
        self._last_sample: Dict[str, Any] = {}

    # -- wiring --------------------------------------------------------
    def configure(self, enabled: bool, interval: float,
                  capacity: int) -> None:
        """Follow the config knobs (called from telemetry.refresh).
        Disabling stops the thread; the rings are kept so a bounce
        doesn't lose history. An interval change restarts the thread —
        the old one may be mid-wait on the old period."""
        interval = max(0.02, float(interval))
        capacity = int(capacity)
        with self._lock:
            if capacity != self._capacity:
                self._capacity = capacity
                for ring in self._series.values():
                    ring.resize(capacity)
        restart = bool(enabled) and (not self.enabled
                                     or interval != self._interval)
        self._interval = interval
        if not restart and bool(enabled) == self.enabled:
            return
        self.stop()
        if bool(enabled):
            self.enabled = True
            self._wake = threading.Event()
            self._thread = threading.Thread(
                target=self._loop, args=(self._wake, interval),
                name="fiber-monitor-sampler", daemon=True)
            self._thread.start()

    def stop(self, join_s: float = 0.0) -> None:
        """Stop whatever thread is running (it checks ``enabled`` and
        its private wake event after every wait); with ``join_s`` also
        wait that long for it to finish its tick."""
        self.enabled = False
        self._wake.set()
        thread, self._thread = self._thread, None
        if (join_s > 0 and thread is not None
                and thread is not threading.current_thread()):
            thread.join(join_s)

    def add_probe(self, probe: Callable[[], None]) -> None:
        """Register a callable run before every sample (pools push
        their queue-depth/inflight gauges here so the sampler never
        reads a stale value). Bound methods are held WEAKLY — the
        sampler must never pin an abandoned Pool alive past its
        ``__del__`` safety net."""
        ref = (weakref.WeakMethod(probe)
               if hasattr(probe, "__self__") else
               (lambda p=probe: p))
        with self._lock:
            if probe not in [r() for r in self._probes]:
                self._probes.append(ref)

    def remove_probe(self, probe: Callable[[], None]) -> None:
        with self._lock:
            self._probes = [r for r in self._probes
                            if r() is not None and r() != probe]

    def add_observer(self,
                     observer: Callable[[Dict[str, Any]], None]) -> None:
        with self._lock:
            if observer not in self._observers:
                self._observers.append(observer)

    # -- sampling ------------------------------------------------------
    def _ring(self, name: str) -> SeriesRing:
        ring = self._series.get(name)
        if ring is None:
            ring = self._series[name] = SeriesRing(self._capacity)
        return ring

    def sample_once(self) -> Dict[str, Any]:
        """Take one sample NOW (the thread's tick; also callable from
        tests and the agent's monitor op for an extra-fresh point)."""
        from fiber_tpu import telemetry

        with self._lock:
            self._probes = [r for r in self._probes if r() is not None]
            probes = [r() for r in self._probes]
            observers = list(self._observers)
        for probe in probes:
            if probe is None:
                continue
            try:
                probe()
            except Exception:  # noqa: BLE001 - a dying pool's probe
                pass
        wall = time.time()
        mono = time.monotonic()
        registry = telemetry.REGISTRY
        sample: Dict[str, Any] = {"wall": wall, "mono": mono}
        with self._lock:
            for name, metric in TRACKED_COUNTERS.items():
                total = _metric_total(registry, metric)
                if total is None:
                    continue
                ring = self._ring(name)
                ring.add(wall, mono, total)
                sample[name] = total
                rate_key = RATE_SERIES.get(name)
                if rate_key:
                    sample[rate_key] = round(ring.rate(), 3)
            for name, metric in TRACKED_GAUGES.items():
                total = _metric_total(registry, metric)
                if total is None:
                    total = 0.0
                self._ring(name).add(wall, mono, total)
                sample[name] = total
            # Heartbeat freshness from every live failure detector in
            # this process (health.py): the oldest peer silence.
            try:
                from fiber_tpu import health

                ages = health.heartbeat_ages()
                sample["heartbeat_age_s"] = (
                    round(max(ages.values()), 3) if ages else 0.0)
                sample["peers"] = len(ages)
            except Exception:  # noqa: BLE001 - sampling must not fail
                sample["heartbeat_age_s"] = 0.0
                sample["peers"] = 0
            self._ring("heartbeat_age_s").add(
                wall, mono, sample["heartbeat_age_s"])
            self.samples += 1
            self._last_sample = sample
        for observer in observers:
            try:
                observer(sample)
            except Exception:  # noqa: BLE001
                logger.exception("monitor: observer failed")
        return sample

    def _loop(self, wake: threading.Event, interval: float) -> None:
        # The wake event and interval are THIS thread's own (passed at
        # start): a configure() that replaces them cannot leave a
        # superseded thread waiting on the new generation's event.
        while not wake.wait(interval):
            if not self.enabled or wake is not self._wake:
                return
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - keep sampling
                logger.exception("monitor: sample failed")

    def tick(self) -> Dict[str, Any]:
        """One pass of the sampler thread under its ``monitor.tick``
        span: when a tick ran and how long it took, next to the step
        calls it may have intruded on, and where the device plane's
        caller was as it began (``open`` / ``open_ns`` or ``idle_ns``,
        ``at``: telemetry/device.py ``StepCalls.caller_now``), so that a
        stalled period is crossed by ticks that name where it sat."""
        with tracing.span("monitor.tick") as sp:
            if sp is not None:
                from fiber_tpu.telemetry.device import CALLS

                sp.update(CALLS.caller_now())
            return self.sample_once()

    # -- read side -----------------------------------------------------
    def last_sample(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._last_sample)

    def snapshot(self, last: int = 0) -> Dict[str, Any]:
        """Picklable dump: rings (optionally only the newest ``last``
        points), the latest derived sample, and sampler state — the
        payload of the host agent's ``monitor_snapshot`` op."""
        with self._lock:
            series = {}
            for name, ring in self._series.items():
                pts = ring.points()
                series[name] = pts[-last:] if last > 0 else pts
            return {
                "enabled": self.enabled,
                "interval_s": self._interval,
                "samples": self.samples,
                "series": series,
                "last": dict(self._last_sample),
            }

    def clear(self) -> None:
        with self._lock:
            self._series.clear()
            self._last_sample = {}
            self.samples = 0


#: Process-wide sampler (knobs follow ``monitor_*`` via
#: telemetry.refresh()).
TIMESERIES = MonitorSampler()



def stop_at_exit() -> None:
    """(Re-)register the exit hook that stops the sampler and waits for
    its thread. The sampler's probes call into JAX (live arrays, memory
    stats), and a daemon thread that the interpreter's finalization
    catches inside JAX aborts the process ("FATAL: exception not
    rethrown", rc -6). Exit hooks run last-in first-out and jax
    registers its own (it clears the backends) when it is imported, so
    the device plane calls this again once it sees jax: the sampler
    stops before the backends go."""
    atexit.unregister(TIMESERIES.stop)
    atexit.register(TIMESERIES.stop, join_s=10.0)


stop_at_exit()


# ---------------------------------------------------------------------------
# Shared rate math (``fiber-tpu metrics --watch`` and ``top``)
# ---------------------------------------------------------------------------


def snapshot_deltas(prev: Dict[str, dict], cur: Dict[str, dict],
                    dt: float) -> Dict[str, Dict[str, Any]]:
    """Per-series deltas/rates between two ``registry.snapshot()``
    dicts taken ``dt`` seconds apart. Counters become
    ``{"delta", "rate"}``; gauges ``{"value", "delta"}``; histograms
    ``{"delta", "rate"}`` over their observation count. Series with no
    change are omitted — the --watch output shows what *moved*."""
    out: Dict[str, Dict[str, Any]] = {}
    if dt <= 0:
        return out
    for name, entry in cur.items():
        kind = entry.get("type")
        prev_series = (prev.get(name) or {}).get("series", {})
        for labels, value in entry.get("series", {}).items():
            before = prev_series.get(labels)
            if kind == "histogram":
                count = value[-1]
                prev_count = before[-1] if before else 0
                delta = count - prev_count
                if delta == 0:
                    continue
                key = f"{name}{{{labels}}}" if labels else name
                out[key] = {"kind": kind, "delta": delta,
                            "rate": round(delta / dt, 3)}
                continue
            before_v = float(before) if before is not None else 0.0
            delta = float(value) - before_v
            key = f"{name}{{{labels}}}" if labels else name
            if kind == "counter":
                if delta == 0:
                    continue
                out[key] = {"kind": kind, "delta": round(delta, 6),
                            "rate": round(max(0.0, delta) / dt, 3)}
            else:  # gauge / untyped: show level + movement
                if delta == 0:
                    continue
                out[key] = {"kind": "gauge", "value": float(value),
                            "delta": round(delta, 6)}
    return out
