"""Tracing / profiling hooks.

The reference has no instrumentation beyond debug logs (SURVEY.md §5:
"Tracing/profiling: none ... add JAX profiler hooks as the idiomatic
equivalent — this is a gap, not a port target"). fiber_tpu provides:

* ``trace(path)`` — context manager wrapping ``jax.profiler.trace`` so a
  device-plane region (ES generations, device_map calls) produces a
  TensorBoard-loadable XLA trace;
* ``annotate`` — the telemetry plane's ``tracing.span`` under its old
  name: the one span primitive already writes every span into an active
  capture (docs/observability.md "Unified timeline");
* ``Timer`` / ``timed`` — lightweight host-plane timing with aggregated
  stats. The process-wide ``global_timer`` mirrors every section into
  the telemetry registry's ``timer_seconds`` histogram (label:
  ``section``), so there is ONE timing surface: ``Pool.stats()`` reads
  the timer, exporters read the registry, and both see the same
  sections.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict
from typing import Dict, Iterator, Optional

from fiber_tpu.telemetry.tracing import span as annotate  # noqa: F401


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture an XLA/host trace of the enclosed region into ``log_dir``
    (view with TensorBoard's profile plugin). The whole region is one
    ``xla.capture`` span, held by the span store and by the capture
    alike, and the capture's location is noted with the device
    telemetry plane: a later ``Pool.trace_dump`` merges the device
    timeline beside the host spans, aligned on the spans both hold
    (docs/observability.md "Unified timeline")."""
    import jax

    from fiber_tpu.telemetry import tracing
    from fiber_tpu.telemetry.device import DEVICE

    jax.profiler.start_trace(log_dir)
    try:
        with tracing.span("xla.capture", log_dir=str(log_dir)):
            yield
    finally:
        jax.profiler.stop_trace()
        DEVICE.note_xla_trace(log_dir)


class Timer:
    """Aggregating wall-clock timer: ``with timer.section("pickle"): ...``;
    ``timer.stats()`` returns {section: (count, total_s, mean_s)}.

    ``mirror=True`` (the process-wide ``global_timer``) additionally
    feeds each observation into the telemetry registry's
    ``timer_seconds`` histogram so the one set of sections reaches the
    Prometheus/Snapshot exporters too."""

    def __init__(self, mirror: bool = False) -> None:
        self._totals: Dict[str, float] = defaultdict(float)
        self._counts: Dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()
        self._mirror = mirror
        self._hist = None

    def _observe_mirror(self, name: str, seconds: float) -> None:
        if not self._mirror:
            return
        if self._hist is None:
            from fiber_tpu import telemetry

            self._hist = telemetry.histogram(
                "timer_seconds",
                "global_timer sections (one timing surface: "
                "Timer.stats() and this histogram see the same data)")
        self._hist.observe(seconds, section=name)

    @contextlib.contextmanager
    def section(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            elapsed = time.perf_counter() - t0
            with self._lock:
                self._totals[name] += elapsed
                self._counts[name] += 1
            self._observe_mirror(name, elapsed)

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._totals[name] += seconds
            self._counts[name] += 1
        self._observe_mirror(name, seconds)

    def stats(self) -> Dict[str, tuple]:
        with self._lock:
            return {
                name: (
                    self._counts[name],
                    round(total, 6),
                    round(total / self._counts[name], 6),
                )
                for name, total in self._totals.items()
            }

    def reset(self) -> None:
        with self._lock:
            self._totals.clear()
            self._counts.clear()


#: Process-wide timer the pool and transport report into.
global_timer = Timer(mirror=True)


@contextlib.contextmanager
def timed(name: str, timer: Optional[Timer] = None) -> Iterator[None]:
    with (timer or global_timer).section(name):
        yield
