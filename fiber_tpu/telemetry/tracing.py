"""Task-lifecycle tracing: spans, trace context, and the per-process
ring-buffer span store.

The model is Dapper's (Sigelman et al., 2010): a **trace id** names one
logical operation end to end (here: one ``Pool.map``); every timed
region inside it is a **span** carrying the trace id and its parent span
id. The master samples a trace per map (``trace_sample_rate``), stamps
``(trace_id, parent_span_id)`` into each task envelope, and workers
adopt that context so their spans — ref-resolve, user fn, result-pickle
— join the same trace. Finished spans land in :data:`SPANS`, a bounded
ring buffer; pool workers drain it and ship the spans back on the result
stream (pool.py), so the master's store ends up holding the whole
cluster's view of its traces.

Spans are plain dicts (picklable, JSON-able)::

    {"name": "worker.execute", "trace": "6fa1…", "span": "03bc…",
     "parent": "9d2e…" | None, "ts": <epoch s>, "dur": <s>,
     "start_ns": <epoch ns>, "end_ns": <epoch ns>,
     "host": "<hostname>", "pid": <os pid>, ...attrs}

:func:`span` is the ONE way to time a region. In a process that has
loaded jax it also enters a ``jax.profiler.TraceAnnotation`` of the same
name (carrying the span id), so during any profiler capture the span
sits in the capture's host plane on the device ops' own clock, and
``export.merge_xla_trace`` aligns the two timelines on the spans both
sides hold. This module never imports jax itself: lite workers and host
agents must not pay for it.
"""

from __future__ import annotations

import collections
import contextlib
import os
import socket
import sys
import threading
import time
import uuid
from typing import Dict, Iterator, List, Optional, Tuple

_tls = threading.local()

_host_cache: Optional[str] = None


def host_id() -> str:
    """Stable host label for spans and log context: FIBER_HOST_ID env
    override, else the hostname."""
    global _host_cache
    if _host_cache is None:
        _host_cache = (os.environ.get("FIBER_HOST_ID")
                       or socket.gethostname() or "host")
    return _host_cache


def new_id() -> str:
    return uuid.uuid4().hex[:16]


class SpanStore:
    """Bounded FIFO of finished spans (oldest fall out past capacity)."""

    def __init__(self, capacity: int = 4096) -> None:
        self._lock = threading.Lock()
        self._spans: "collections.deque" = collections.deque(
            maxlen=max(1, int(capacity)))
        self.dropped = 0  # lifetime spans evicted by the ring bound

    def add(self, span: Dict) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)

    def add_all(self, spans: List[Dict]) -> None:
        with self._lock:
            for span in spans:
                if len(self._spans) == self._spans.maxlen:
                    self.dropped += 1
                self._spans.append(span)

    def drain(self) -> List[Dict]:
        """Pop every stored span (worker-side shipping)."""
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
            return out

    def snapshot(self) -> List[Dict]:
        with self._lock:
            return list(self._spans)

    def resize(self, capacity: int) -> None:
        with self._lock:
            self._spans = collections.deque(
                self._spans, maxlen=max(1, int(capacity)))

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


#: Process-wide finished-span buffer (capacity follows
#: ``span_buffer_size`` via telemetry.refresh()).
SPANS = SpanStore()


def current() -> Optional[Tuple[str, Optional[str]]]:
    """Ambient ``(trace_id, span_id)`` of this thread, or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def current_trace_id() -> Optional[str]:
    ctx = current()
    return ctx[0] if ctx else None


@contextlib.contextmanager
def trace_context(trace_id: str,
                  span_id: Optional[str] = None) -> Iterator[None]:
    """Adopt a propagated trace context (worker side: the envelope's
    ``(trace, parent_span)``) for the enclosed region, so nested
    :func:`span` calls join that trace."""
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append((trace_id, span_id))
    try:
        yield
    finally:
        stack.pop()


_annotation = None
_NO_ANNOTATION = contextlib.nullcontext()


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once this process has loaded
    jax, else None. Looked up in ``sys.modules``, never imported."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def _new_span(name: str, trace: Optional[str], parent: Optional[str],
              attrs: Dict) -> Dict:
    """A span dict under the ambient context (or rooting a fresh
    trace), not yet timed or stored."""
    if trace is None:
        ctx = current()
        if ctx is not None:
            trace = ctx[0]
            if parent is None:
                parent = ctx[1]
        else:
            trace = new_id()
    sp: Dict = {
        "name": name,
        "trace": trace,
        "span": new_id(),
        "parent": parent,
        "ts": 0.0,
        "dur": 0.0,
        "start_ns": 0,
        "end_ns": 0,
        "host": host_id(),
        "pid": os.getpid(),
    }
    if attrs:
        sp.update(attrs)
    return sp


@contextlib.contextmanager
def span(name: str, trace: Optional[str] = None,
         parent: Optional[str] = None, store: Optional[SpanStore] = None,
         **attrs) -> Iterator[Optional[Dict]]:
    """Record one timed span into the process span store (no-op when
    telemetry is disabled — yields None). Trace/parent default to the
    ambient context; with neither, the span roots a fresh trace.
    Yields the span dict so callers can read ``span["span"]`` to use as
    the parent id for propagated work. Start and end are epoch
    nanoseconds (``time.time_ns``), ``dur`` is the monotonic clock's."""
    from fiber_tpu import telemetry

    if not telemetry.tracing_active():
        yield None
        return
    sp = _new_span(name, trace, parent, attrs)
    annotation = _trace_annotation()
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append((sp["trace"], sp["span"]))
    sp["start_ns"] = time.time_ns()
    sp["ts"] = sp["start_ns"] / 1e9
    t0 = time.perf_counter()
    try:
        with (annotation(name, span=sp["span"]) if annotation is not None
              else _NO_ANNOTATION):
            yield sp
    finally:
        sp["dur"] = time.perf_counter() - t0
        sp["end_ns"] = time.time_ns()
        stack.pop()
        (store or SPANS).add(sp)


def record(name: str, start_ns: int, end_ns: int,
           **attrs) -> Optional[Dict]:
    """Store a region that already ended and was timed by someone else
    (JAX's compile events report their own start and end), as a child
    of the ambient span. None when telemetry is disabled."""
    from fiber_tpu import telemetry

    if not telemetry.tracing_active():
        return None
    sp = _new_span(name, None, None, attrs)
    sp["start_ns"], sp["end_ns"] = int(start_ns), int(end_ns)
    sp["ts"] = sp["start_ns"] / 1e9
    sp["dur"] = (sp["end_ns"] - sp["start_ns"]) / 1e9
    SPANS.add(sp)
    return sp
