"""Cluster flight recorder: a per-process ring buffer of structured
events from every infrastructure plane (docs/observability.md).

The metrics registry answers *how much* and the span store answers *what
happened to this map* — but neither answers "what was this process doing
in its last seconds" when a worker dies or "why did the scheduler make
that call" when a map runs slow. The flight recorder is that layer: each
plane emits one small dict per *decision or anomaly* (pool submit /
dispatch / resubmit / backpressure, scheduler locality / speculation /
park with the reason, store put / fetch / spill / miss, transport
connect / retry / stall / park, health suspect / revive / breaker
transitions) into a bounded deque — the black box an aircraft carries.

Design constraints, mirrored from the span store:

* **Near-zero when disabled** — every hook starts with one attribute
  read + branch on :attr:`FlightRecorder.enabled`; fully off, the hot
  paths pay a single load.
* **Lock-cheap when enabled** — one lock around a ``deque.append``; no
  I/O, no serialization, no per-event syscalls.
* **Bounded** — capacity follows ``flightrec_buffer_size``; the oldest
  events fall out and are counted in :attr:`FlightRecorder.dropped`.

Events are plain dicts (picklable, JSON-able)::

    {"ts": <epoch s>, "plane": "sched", "kind": "speculate",
     "seq": 3, "base": 64, "reason": "age 1.2s > 4.0x median 0.1s"}

They leave the process only on demand: ``Pool.flight_dump`` writes the
master's buffer as a JSON artifact, the host agent's ``postmortem`` op
ships an agent's buffer to the operator, and the crash handler
(:mod:`fiber_tpu.telemetry.postmortem`) flushes a dying worker's buffer
into a black-box bundle under the staging root.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

#: Planes the hooks report under (documentation + explain.py grouping;
#: record() does not enforce membership — a new plane must not need a
#: central registry edit to start reporting).
PLANES = ("pool", "sched", "store", "transport", "health", "agent",
          "policy")


class FlightRecorder:
    """Bounded FIFO of flight events (oldest fall out past capacity)."""

    def __init__(self, capacity: int = 2048, enabled: bool = True) -> None:
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._events: "collections.deque" = collections.deque(
            maxlen=max(1, int(capacity)))
        self.dropped = 0    # lifetime events evicted by the ring bound
        self.recorded = 0   # lifetime events accepted

    def record(self, plane: str, kind: str, **attrs: Any) -> Optional[str]:
        """Append one event and return its id (None when disabled).
        Call sites on hot paths should guard with ``if FLIGHT.enabled:``
        so the kwargs dict is never built when the recorder is off.

        The id is ``"<pid>-<n>"`` with ``n`` this recorder's lifetime
        accept count: stable, per-process monotonic, and unique across
        the processes whose buffers a postmortem merge concatenates —
        so a ``cause_id`` link (the policy plane's anomaly -> action ->
        outcome chain) survives ``order_events`` re-sorting."""
        if not self.enabled:
            return None
        # Dual clocks on every event: "ts" (wall) is comparable across
        # hosts but subject to NTP steps; "mono" orders events from ONE
        # process exactly. Cross-process merges (explain --flight, the
        # monitor plane) sort on (ts, mono) — wall first, monotonic as
        # the same-process tiebreak (see order_events).
        event: Dict[str, Any] = {"ts": time.time(),
                                 "mono": time.monotonic(),
                                 "plane": plane, "kind": kind}
        if attrs:
            event.update(attrs)
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self.recorded += 1
            eid = f"{os.getpid()}-{self.recorded}"
            event["id"] = eid
            self._events.append(event)
        return eid

    def snapshot(self, last: int = 0) -> List[Dict[str, Any]]:
        """Copy of the buffered events, oldest first (``last`` > 0
        limits to the newest N — the postmortem pull)."""
        with self._lock:
            events = list(self._events)
        return events[-last:] if last > 0 else events

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            out = list(self._events)
            self._events.clear()
            return out

    def resize(self, capacity: int) -> None:
        with self._lock:
            self._events = collections.deque(
                self._events, maxlen=max(1, int(capacity)))

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


#: Process-wide flight recorder (capacity/enablement follow the
#: ``flightrec_*`` config knobs via telemetry.refresh()).
FLIGHT = FlightRecorder()


def record(plane: str, kind: str, **attrs: Any) -> Optional[str]:
    """Module-level convenience for cold call sites."""
    return FLIGHT.record(plane, kind, **attrs)


def order_events(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Merge-order flight events from one or many processes: wall
    clock first (the only axis comparable across hosts), monotonic
    clock as the tiebreak (exact within a process, where wall-clock
    resolution or an NTP step can produce equal/backwards ``ts``).
    Events recorded before the dual-clock stamp sort by wall alone."""
    return sorted(events, key=lambda e: (float(e.get("ts", 0.0)),
                                         float(e.get("mono", 0.0))))
