"""From a profiler trace to numbers: busy and idle time, kernel time by
name, collective time with no compute under it, and idle gaps attributed
to what the host was doing.

The reduction works on plain data, so that it can be checked on a
hand-built trace (``tests/test_trace_reduce.py``):

    Trace(device={chip: [Event(name, start_ns, dur_ns), ...]},
          host=[Event, ...], window=(start_ns, end_ns))

``load_xplane`` builds that from the ``.xplane.pb`` that
``jax.profiler`` writes, as read off a v5e trace by hand (PERF.md):
device planes are named ``/device:TPU:<n>``; their line ``XLA Ops``
holds one event per executed HLO op, named by the op's full text
(``%fusion.95 = ...``), with a ``while`` op spanning its body's ops; the
host plane ``/host:CPU`` holds the harness's ``TraceAnnotation`` spans on
the same clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

#: ops that move data between chips (sync or async halves alike)
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|send|recv)")
#: ops that only enclose others; their time is their children's
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\s=]")


@dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns
    dur: int    # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Trace:
    device: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)
    window: Optional[Tuple[int, int]] = None


def short_name(name: str) -> str:
    """``%fusion.95 = (f32[...]) fusion(...)`` -> ``%fusion.95 fusion``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    m = re.search(r"\s([a-z][\w\-]*)\(", " " + rest)
    return f"{head} {m.group(1)}" if m else head[:80]


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Tuple[int, int]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals, window) -> List[Tuple[int, int]]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def measure(intervals) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a, b) -> List[Tuple[int, int]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def window_of(trace: Trace) -> Tuple[int, int]:
    if trace.window is not None:
        return trace.window
    evs = [e for chip in trace.device.values() for e in chip]
    if not evs:
        raise ValueError("trace holds no device event")
    return min(e.start for e in evs), max(e.end for e in evs)


def busy_intervals(trace: Trace, chip: int) -> List[Tuple[int, int]]:
    """Union of the intervals in which an op ran. A container's own event
    spans its body's gaps too, so only the ops inside it count."""
    return merge(clip(((e.start, e.end) for e in trace.device[chip]
                       if not CONTAINER.match(e.name)), window_of(trace)))


def busy_seconds(trace: Trace) -> Dict[int, float]:
    """Per chip: seconds of the window in which some op ran."""
    return {chip: measure(busy_intervals(trace, chip)) / 1e9
            for chip in trace.device}


def idle_share(trace: Optional[Trace]) -> Optional[float]:
    """1 - busy / window on the idlest chip; None without a device event."""
    if trace is None or not trace.device:
        return None
    return 1.0 - min(busy_seconds(trace).values()) / window_seconds(trace)


def window_seconds(trace: Trace) -> float:
    lo, hi = window_of(trace)
    return (hi - lo) / 1e9


def kernel_seconds(trace: Trace, pattern: str) -> Dict[int, float]:
    """Per chip: summed duration of the events whose name matches.
    A chip with no such event is left out."""
    rx = re.compile(pattern)
    window = window_of(trace)
    out = {}
    for chip, evs in trace.device.items():
        hit = clip(((e.start, e.end) for e in evs if rx.search(e.name)),
                   window)
        if hit:
            out[chip] = measure(hit) / 1e9
    return out


def exposed_collective_seconds(trace: Trace, chip: int) -> Optional[float]:
    """Seconds in collective ops during which no compute op ran on the
    chip; None where the chip ran no collective."""
    window = window_of(trace)
    coll, comp = [], []
    for e in trace.device[chip]:
        if CONTAINER.match(e.name):
            continue
        (coll if COLLECTIVE.match(e.name) else comp).append((e.start, e.end))
    coll = merge(clip(coll, window))
    if not coll:
        return None
    return measure(subtract(coll, merge(clip(comp, window)))) / 1e9


def top_ops(trace: Trace, chip: int, n: int = 10) -> List[List]:
    """The ops that took most time, by short name, containers left out."""
    window = window_of(trace)
    total: Dict[str, int] = {}
    for e in trace.device[chip]:
        if CONTAINER.match(e.name):
            continue
        got = measure(clip([(e.start, e.end)], window))
        if got:
            key = short_name(e.name)
            total[key] = total.get(key, 0) + got
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, chip: int, n: int = 10) -> List[List]:
    """The longest idle gaps of the window, each named by the host span
    that covers most of it (``(none)`` where no span does)."""
    lo, hi = window_of(trace)
    gaps = subtract([(lo, hi)], busy_intervals(trace, chip))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        best, cover = "(none)", 0
        for s in trace.host:
            got = min(b, s.end) - max(a, s.start)
            if got > cover:
                best, cover = s.name, got
        out.append([best, (b - a) / 1e9])
    return out


#: rehearsal only: the CPU backend has no device plane; its thunks run on
#: these host threads, which stand in for chip 0 so that the reduction
#: runs end to end without a chip
CPU_OP_LINE = "tf_XLAPjRtCpuClient"
CPU_NOT_OPS = ("ThreadpoolListener", "ThunkExecutor", "end: ")


def load_xplane(trace_dir: str, host_spans: Iterable[str],
                window_span: Optional[str] = None,
                platform: str = "tpu") -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``. ``host_spans``
    are the names of the harness's own spans to keep from the host plane;
    ``window_span``, if it is found there, gives the window."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    keep = set(host_spans)
    trace = Trace()
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    trace.device[int(m.group(1))] = [
                        Event(e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if platform == "cpu" and line.name.startswith(CPU_OP_LINE):
                    trace.device.setdefault(0, []).extend(
                        Event(e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                        if not e.name.startswith(CPU_NOT_OPS))
                    continue
                for e in line.events:
                    if e.name == window_span:
                        start = int(e.start_ns)
                        trace.window = (start, start + int(e.duration_ns))
                    elif e.name in keep:
                        trace.host.append(Event(
                            e.name, int(e.start_ns), int(e.duration_ns)))
    return trace
