"""Wall-clock sampling profiler (py-spy style, in-process).

A daemon thread wakes ``profiler_hz`` times per second, walks every
thread's current Python frame stack via ``sys._current_frames()``, and
aggregates **collapsed stacks**: ``root;caller;…;leaf -> sample
count``, the flamegraph folded format (Gregg's ``flamegraph.pl``,
speedscope, and Perfetto's flamegraph view all ingest it). Because
sampling reads frames without tracing, the profiled code pays nothing
between samples — at the default-off setting it pays nothing at all.

Cluster story (docs/observability.md):

* every process runs its own profiler, armed by the ``profiler_hz``
  config knob (shipped to workers in the spawn preparation);
* pool workers drain their folded samples after each chunk and ship
  them on the existing result stream (``("prof", …)`` frames beside
  heartbeats and spans); the master folds them into
  :data:`AGGREGATE`, so ``Pool.profile_dump`` writes a cluster-wide
  profile;
* the host agent's ``profile_dump`` op samples the agent process on
  demand (``TpuBackend.collect_profiles``), and ``fiber-tpu profile
  script.py --out prof.folded`` runs a whole program under the
  profiler.

``fiber-tpu explain`` consumes the folded output: a ``primary=compute``
verdict names the top frames instead of stopping at "compute".
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from fiber_tpu.utils.logging import get_logger

logger = get_logger()

#: Stack depth kept per sample (deeper frames are folded into the
#: root-most entry) — bounds folded-key size on pathological recursion.
MAX_STACK_DEPTH = 64

#: Hard cap on distinct collapsed stacks kept per process; beyond it,
#: new stacks fold into one overflow key (same posture as the metrics
#: registry's label bound).
MAX_STACKS = 4096

_OVERFLOW_STACK = "(other stacks)"


def _frame_label(frame) -> str:
    code = frame.f_code
    return (f"{code.co_name} "
            f"({os.path.basename(code.co_filename)}:{code.co_firstlineno})")


def _collapse(frame) -> str:
    """One thread's current stack as ``root;…;leaf``."""
    parts: List[str] = []
    while frame is not None and len(parts) < MAX_STACK_DEPTH:
        parts.append(_frame_label(frame))
        frame = frame.f_back
    parts.reverse()
    return ";".join(parts)


class SamplingProfiler:
    """Aggregating wall-clock sampler for THIS process's threads."""

    def __init__(self, hz: float = 0.0) -> None:
        self.hz = float(hz)
        self._lock = threading.Lock()
        self._folded: Dict[str, int] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.samples = 0        # lifetime samples taken
        self._skip_threads = {-1}

    @property
    def active(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def set_hz(self, hz: float) -> None:
        """Follow the ``profiler_hz`` knob (telemetry.refresh): > 0
        starts the sampler at that rate, <= 0 stops it. The aggregate
        survives a stop so the operator can still dump it."""
        hz = max(0.0, float(hz))
        if hz == self.hz and (self.active == (hz > 0)):
            return
        self.hz = hz
        if self.active:
            self._stop.set()
            self._thread = None
        if hz > 0:
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._loop, name="fiber-profiler", daemon=True)
            self._thread.start()

    def _loop(self) -> None:
        # The sampler must never profile itself: its own thread id is
        # excluded from every frame walk.
        self._skip_threads = {threading.get_ident()}
        period = 1.0 / self.hz if self.hz > 0 else 0.01
        while not self._stop.wait(period):
            try:
                self.sample()
            except Exception:  # noqa: BLE001 - keep sampling
                logger.exception("profiler: sample failed")

    def sample(self) -> None:
        """Take one sample of every thread now."""
        frames = sys._current_frames()
        skip = self._skip_threads
        with self._lock:
            for tid, frame in frames.items():
                if tid in skip:
                    continue
                stack = _collapse(frame)
                if stack not in self._folded \
                        and len(self._folded) >= MAX_STACKS:
                    stack = _OVERFLOW_STACK
                self._folded[stack] = self._folded.get(stack, 0) + 1
            self.samples += 1

    # -- read side -----------------------------------------------------
    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._folded)

    def drain(self) -> Dict[str, int]:
        """Pop the aggregate (worker-side shipping: each ``("prof",…)``
        frame carries only samples the master hasn't seen)."""
        with self._lock:
            out = self._folded
            self._folded = {}
            return out

    def clear(self) -> None:
        with self._lock:
            self._folded.clear()
            self.samples = 0

    def sample_for(self, seconds: float, hz: float = 97.0) -> Dict[str, int]:
        """Blocking bounded burst: sample this process for ``seconds``
        at ``hz`` into a PRIVATE aggregate (the agent's on-demand
        ``profile_dump`` op — it must not disturb the knob-armed
        aggregate)."""
        seconds = min(max(0.0, float(seconds)), 30.0)
        hz = min(max(1.0, float(hz)), 1000.0)
        burst = SamplingProfiler()
        burst._skip_threads = {threading.get_ident()}
        deadline = time.monotonic() + seconds
        period = 1.0 / hz
        while time.monotonic() < deadline:
            burst.sample()
            time.sleep(period)
        return burst.snapshot()


#: Process-wide profiler (armed by ``profiler_hz`` via
#: telemetry.refresh()).
PROFILER = SamplingProfiler()


class ProfileAggregate:
    """Master-side merge of worker-shipped folded profiles, keyed by a
    ``host:pid`` source label so `fiber-tpu top`-style tooling can
    still attribute samples per worker."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sources: Dict[str, Dict[str, int]] = {}

    def merge(self, source: str, folded: Dict[str, int]) -> None:
        with self._lock:
            slot = self._sources.setdefault(str(source), {})
            for stack, count in folded.items():
                if stack not in slot and len(slot) >= MAX_STACKS:
                    stack = _OVERFLOW_STACK
                slot[stack] = slot.get(stack, 0) + int(count)

    def snapshot(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {src: dict(folded)
                    for src, folded in self._sources.items()}

    def merged(self) -> Dict[str, int]:
        with self._lock:
            return merge_folded(*self._sources.values())

    def clear(self) -> None:
        with self._lock:
            self._sources.clear()


#: Cluster profile aggregate in the master process (fed by the pool's
#: result loop).
AGGREGATE = ProfileAggregate()


# ---------------------------------------------------------------------------
# Folded-format helpers
# ---------------------------------------------------------------------------


def merge_folded(*folded_dicts: Dict[str, int]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for folded in folded_dicts:
        for stack, count in (folded or {}).items():
            out[stack] = out.get(stack, 0) + int(count)
    return out


def folded_text(folded: Dict[str, int]) -> str:
    """Render ``stack -> count`` as flamegraph folded lines, highest
    count first (``flamegraph.pl prof.folded > prof.svg``)."""
    lines = [f"{stack} {count}" for stack, count in
             sorted(folded.items(), key=lambda kv: (-kv[1], kv[0]))]
    return "\n".join(lines) + ("\n" if lines else "")


def parse_folded(text: str) -> Dict[str, int]:
    """Inverse of :func:`folded_text` (tolerates blank lines)."""
    out: Dict[str, int] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        stack, _, count_s = line.rpartition(" ")
        if not stack or not count_s.lstrip("-").isdigit():
            raise ValueError(f"malformed folded line: {line!r}")
        out[stack] = out.get(stack, 0) + int(count_s)
    return out


#: Leaf-frame prefixes that mean "off-CPU, parked in a blocking
#: primitive" (a wall-clock sampler sees every thread, and a process
#: full of heartbeat/transport threads is MOSTLY parked threads). The
#: py-spy posture: idle samples are excluded from hot-frame rankings
#: unless nothing else exists.
IDLE_LEAF_PREFIXES = (
    "wait (threading", "wait (", "select (selectors", "select (",
    "accept (socket", "poll (", "recv (", "recv_into (", "readinto (",
    "sleep (", "channel_recv (", "_recv (", "epoll (",
)


def is_idle_stack(stack: str) -> bool:
    leaf = stack.rsplit(";", 1)[-1]
    return leaf.startswith(IDLE_LEAF_PREFIXES)


def top_frames(folded: Dict[str, int], n: int = 5,
               self_time: bool = True,
               exclude_idle: bool = True) -> List[Tuple[str, int]]:
    """The ``n`` hottest frames. ``self_time=True`` attributes each
    sample to its LEAF frame (where the CPU actually was); False
    attributes to every frame on the stack (inclusive time). Stacks
    parked in blocking primitives are excluded by default (falling
    back to everything when the whole profile is idle) so a compute
    verdict names code, not ``wait (threading.py)``."""
    stacks = dict(folded or {})
    if exclude_idle:
        busy = {s: c for s, c in stacks.items() if not is_idle_stack(s)}
        if busy:
            stacks = busy
    totals: Dict[str, int] = {}
    for stack, count in stacks.items():
        frames = stack.split(";")
        chosen = frames[-1:] if self_time else set(frames)
        for frame in chosen:
            totals[frame] = totals.get(frame, 0) + int(count)
    return sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))[:n]


def profile_chrome_trace(folded: Dict[str, int],
                         hz: float = 97.0) -> Dict[str, Any]:
    """Folded profile -> a Chrome trace-event flamegraph: the sample
    tree laid out as nested complete events on one synthetic timeline
    where 1 sample = 1/hz seconds (load in Perfetto / chrome://tracing
    next to the span trace)."""
    period_us = 1e6 / max(1.0, float(hz))
    # Build the prefix tree: node = {child_label: [count, children]}.
    root: Dict[str, list] = {}
    for stack, count in (folded or {}).items():
        node = root
        for label in stack.split(";"):
            slot = node.setdefault(label, [0, {}])
            slot[0] += int(count)
            node = slot[1]
    events: List[Dict[str, Any]] = []

    def emit(node: Dict[str, list], ts: float) -> None:
        cursor = ts
        for label in sorted(node):
            count, children = node[label]
            dur = count * period_us
            events.append({
                "name": label, "ph": "X", "ts": cursor, "dur": dur,
                "pid": 1, "tid": 1, "cat": "profile",
                "args": {"samples": count},
            })
            emit(children, cursor)
            cursor += dur

    emit(root, 0.0)
    meta = [{"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
             "args": {"name": "sampling profile (1 sample = "
                               f"{1.0 / max(1.0, float(hz)):.4f}s)"}}]
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def write_chrome_profile(path: str, folded: Dict[str, int],
                         hz: float = 97.0) -> str:
    with open(path, "w") as fh:
        json.dump(profile_chrome_trace(folded, hz), fh)
    return path


def load_folded(path: str) -> Dict[str, int]:
    """Folded profile from a file (the ``explain --profile`` input)."""
    with open(path) as fh:
        return parse_folded(fh.read())
