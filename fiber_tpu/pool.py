"""Distributed pools: ``Pool`` and ``ResilientPool``.

Reference parity: fiber/pool.py (ZPool / ResilientZPool — the reference's
default). Architecture:

* The master binds two transport endpoints: a **task stream** (push
  round-robin for ``Pool``; REQ/REP handout for ``ResilientPool``) and a
  **result stream** (pull, fair-merged).
* Worker processes are fiber_tpu Processes started lazily on first use
  (reference: fiber/pool.py:1118-1137) and maintained by a handler thread
  that joins exited workers and repopulates (fiber/pool.py:975-1082).
* Tasks are chunked (default 32 items — the reference's load-bearing
  constant, fiber/pool.py:1169-1170); in-flight items are capped at 20,000
  (explicit backpressure, fiber/pool.py:904) because the transport won't
  block the way a full nanomsg socket would.
* ``ResilientPool`` additionally keeps a per-worker pending table and
  resubmits a dead worker's outstanding chunks (fiber/pool.py:1490-1659);
  retry is only safe for idempotent task functions.

TPU-native extension: a function marked ``@meta(device=True)`` short-cuts
``map`` onto the on-device ``shard_map`` path (fiber_tpu/parallel) instead
of the host worker path.
"""

from __future__ import annotations

import contextlib
import hashlib
import itertools
from collections import deque
import os
import queue as pyqueue
import sys
import threading
import time
import traceback
import uuid
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from fiber_tpu import serialization, telemetry
from fiber_tpu.meta import get_meta
from fiber_tpu.sched import Scheduler, local_host_key
from fiber_tpu.store.core import ObjectRef
from fiber_tpu.store.plane import StoreFetchError
from fiber_tpu.telemetry import accounting, tracing
from fiber_tpu.telemetry.accounting import COSTS, CostBudget  # noqa: F401
from fiber_tpu.telemetry.flightrec import FLIGHT
from fiber_tpu.testing import chaos
from fiber_tpu.transport import Endpoint, TransportClosed
from fiber_tpu.utils.logging import get_logger
from fiber_tpu.utils.profiling import global_timer

logger = get_logger()

# Pool task-loop metrics (docs/observability.md). Registry instruments
# are process-global; per-Pool exact counts live on the Pool instance
# (Pool.stats()) so tests and operators can attribute them.
_m_tasks_submitted = telemetry.counter(
    "pool_tasks_submitted", "Task items submitted to host pools")
_m_tasks_completed = telemetry.counter(
    "pool_tasks_completed", "Task results received from workers")
_m_chunks_dispatched = telemetry.counter(
    "pool_chunks_dispatched", "Task chunks handed to workers")
_m_chunks_resubmitted = telemetry.counter(
    "pool_chunks_resubmitted",
    "Chunks requeued after worker death or suspect declaration")
_m_backpressure_waits = telemetry.counter(
    "pool_backpressure_waits",
    "Dispatches that blocked on the MAX_INFLIGHT_TASKS gate")
_m_store_fallbacks = telemetry.counter(
    "pool_store_inline_fallbacks",
    "Chunks resent inline after a worker store-fetch failure")
_g_queue_depth = telemetry.gauge(
    "pool_queue_depth", "Chunks queued for dispatch")
_g_inflight = telemetry.gauge(
    "pool_inflight_tasks", "Task items submitted but not yet completed")
_m_stream_admit_waits = telemetry.counter(
    "pool_stream_admit_waits",
    "Stream admission park episodes (consumer slower than producer)")
_g_stream_window_fill = telemetry.gauge(
    "pool_stream_window_fill",
    "Admitted-but-unyielded task items across active streams")

DEFAULT_CHUNKSIZE = 32
MAX_INFLIGHT_TASKS = 20000
# Smallest shared array the device map lifts onto the mesh as a
# broadcast arg (docs/objectstore.md "Device tier"): under this, the
# stack-and-shard path is cheaper than content-addressing.
_DEVICE_BCAST_MIN = 64 << 10

#: Process-wide map-id source for accounting billing keys: unique per
#: submitted map across every pool in this master process.
_MAP_IDS = itertools.count(1)

_UNSET = object()
#: A result slot whose value has been handed to the consumer. The slot
#: stays occupied (duplicate fills from speculation losers / death
#: resubmits still dedup against it) but the payload reference is gone —
#: the sliding-window release that keeps a streaming master O(window).
_YIELDED = object()

#: Consecutive failed worker starts (with zero live workers and pending
#: work) before the pool gives up and fails the pending maps.
_SPAWN_FAIL_LIMIT = 25


class WorkerStartError(Exception):
    """The backend persistently refused to start pool workers while work
    was pending (e.g. an unsatisfiable resource reservation). Raised so a
    map fails loudly instead of waiting forever for workers that can never
    exist; transient start failures are absorbed and retried as before
    (reference posture: fiber/pool.py:96-104 safe_start)."""


class JobPreemptedError(Exception):
    """The serve tier preempted this map mid-flight (budget enforcement,
    docs/serving.md): its journaled progress is intact in the ledger and
    the job is resumable via ``fiber-tpu resume`` / daemon replay. Raised
    into the map's waiters so a blocked ``pool.map`` call unblocks with a
    recognizable, non-fatal verdict rather than hanging."""


class RemoteError(Exception):
    """An exception raised inside a pool worker, with remote traceback."""

    def __init__(self, exc: BaseException, tb: str) -> None:
        super().__init__(str(exc))
        self.original = exc
        self.remote_traceback = tb

    def __str__(self) -> str:
        return f"{self.original!r}\n\nRemote traceback:\n{self.remote_traceback}"


# ---------------------------------------------------------------------------
# Result bookkeeping (reference: the Inventory, fiber/pool.py:644-728)
# ---------------------------------------------------------------------------


class _Entry:
    __slots__ = ("values", "remaining", "total", "callbacks", "yielded",
                 "stream", "finalized", "bits", "pending")

    def __init__(self, n: int, stream: bool = False) -> None:
        #: Classic entries hold a full slot list (the caller asked for
        #: every result at once). Stream entries instead keep a dedup
        #: BITMAP (1 bit per admitted slot) plus a dict of
        #: filled-but-unyielded values: live payloads stay
        #: O(stream_window) and per-task bookkeeping is ~0.125 bytes —
        #: a million-task stream costs the master ~128KB, not an
        #: O(n) pointer list. That IS the constant-memory claim
        #: (tests/test_stream.py holds the window bound).
        self.values: List[Any] = [] if stream else [_UNSET] * n
        self.bits: Optional[bytearray] = bytearray() if stream else None
        self.pending: Optional[Dict[int, Any]] = {} if stream else None
        self.remaining = n
        self.total = n
        self.callbacks: List[Callable] = []
        self.yielded = 0
        #: Stream entries grow via extend() and complete only once the
        #: admission loop finalizes them — remaining == 0 alone means
        #: "caught up", not "done".
        self.stream = stream
        self.finalized = not stream

    def done_locked(self) -> bool:
        return self.remaining == 0 and self.finalized

    def filled_locked(self, idx: int) -> bool:
        """Has slot ``idx`` ever filled (yielded or still pending)?"""
        if self.stream:
            return bool((self.bits[idx >> 3] >> (idx & 7)) & 1)
        return self.values[idx] is not _UNSET


class ResultStore:
    """Sequence-keyed store of in-flight map results with ordered and
    unordered iteration.

    Two entry shapes share the bookkeeping: classic map entries are born
    with their full slot count, and *stream* entries (``add_stream``)
    start empty and grow chunk-by-chunk via ``extend`` as the admission
    loop pulls from the caller's iterator — completion requires both
    ``remaining == 0`` and ``finalize()``. Stream iteration releases
    each yielded slot's payload reference immediately (``_YIELDED``
    tombstone), so the store holds O(un-yielded window) payloads, never
    O(stream length); duplicate fills still dedup against tombstones."""

    def __init__(self) -> None:
        self._entries: Dict[int, _Entry] = {}
        self._seq = itertools.count()
        self._cond = threading.Condition()
        self._completion_log: Dict[int, deque] = {}

    def add(self, n: int) -> int:
        seq = next(self._seq)
        with self._cond:
            self._entries[seq] = _Entry(n)
            self._completion_log[seq] = deque()
        return seq

    def add_stream(self) -> int:
        """Open a growable stream entry (zero slots until ``extend``)."""
        seq = next(self._seq)
        with self._cond:
            self._entries[seq] = _Entry(0, stream=True)
            self._completion_log[seq] = deque()
        return seq

    def extend(self, seq: int, n: int) -> int:
        """Grow a stream entry by ``n`` slots; returns the base index of
        the new chunk. Raising the outstanding count needs no notify —
        only downward transitions matter to any waiter's predicate."""
        with self._cond:
            entry = self._entries[seq]
            if not entry.stream or entry.finalized:
                raise ValueError("extend() on a non-stream or finalized seq")
            base = entry.total
            entry.total += n
            entry.remaining += n
            need = (entry.total + 7) >> 3
            if len(entry.bits) < need:
                entry.bits.extend(b"\x00" * (need - len(entry.bits)))
        return base

    def finalize(self, seq: int) -> None:
        """The admission loop exhausted the source iterator: no more
        slots will be added. Completion callbacks fire once every
        admitted slot has also filled."""
        callbacks: List[Callable] = []
        with self._cond:
            entry = self._entries.get(seq)
            if entry is None or entry.finalized:
                return
            entry.finalized = True
            if entry.remaining == 0:
                callbacks = list(entry.callbacks)
            self._cond.notify_all()
        self._drain_callbacks(callbacks)

    def stream_fill_state(self, seq: int) -> Tuple[int, int, bool]:
        """(admitted_total, yielded, finalized) for window accounting."""
        with self._cond:
            entry = self._entries.get(seq)
            if entry is None:
                return (0, 0, True)
            return (entry.total, entry.yielded, entry.finalized)

    def wait_stream_capacity(self, seq: int, max_unyielded: int,
                             timeout: Optional[float] = None) -> bool:
        """Park the admission loop until the consumer has drained the
        window: un-yielded slots (admitted − yielded) <= ``max_unyielded``.
        Rides the store condition — every fill/fail/yield notifies — so
        a slow consumer parks admission with zero busy-wait, which parks
        dispatch, which lets transport credits drain (the end-to-end
        backpressure chain, docs/streaming.md). True when capacity is
        available (or the entry is gone/failed — the caller re-checks)."""
        def _have_room() -> bool:
            entry = self._entries.get(seq)
            if entry is None or entry.done_locked():
                return True
            return (entry.total - entry.yielded) <= max_unyielded
        with self._cond:
            return self._cond.wait_for(_have_room, timeout)

    def fill(self, seq: int, base: int, values: List[Any]) -> int:
        """Fill result slots; duplicates (speculation losers, death
        resubmits) are dropped here. Returns the number of NEWLY filled
        slots — the accounting plane's exactly-once billing gate: a
        task is billed when its slot first fills, so a duplicate
        execution never re-bills it."""
        newly = 0
        with self._cond:
            entry = self._entries.get(seq)
            if entry is None:
                return 0
            if base < 0 or base + len(values) > entry.total:
                raise ValueError(
                    f"result frame out of range: base={base} "
                    f"n={len(values)} total={entry.total}"
                )
            if entry.stream:
                bits = entry.bits
                for offset, value in enumerate(values):
                    idx = base + offset
                    if not (bits[idx >> 3] >> (idx & 7)) & 1:
                        bits[idx >> 3] |= 1 << (idx & 7)
                        entry.pending[idx] = value
                        entry.remaining -= 1
                        newly += 1
                        self._completion_log[seq].append(idx)
            else:
                for offset, value in enumerate(values):
                    idx = base + offset
                    if entry.values[idx] is _UNSET:
                        entry.values[idx] = value
                        entry.remaining -= 1
                        newly += 1
                        self._completion_log[seq].append(idx)
            callbacks = (list(entry.callbacks)
                         if entry.done_locked() else [])
            self._cond.notify_all()
        for cb in callbacks:
            try:
                cb()
            except Exception:
                logger.exception("pool callback failed")
        return newly

    def ready(self, seq: int) -> bool:
        with self._cond:
            entry = self._entries[seq]
            return entry.done_locked()

    def wait(self, seq: int, timeout: Optional[float] = None) -> List[Any]:
        with self._cond:
            ok = self._cond.wait_for(
                lambda: self._entries[seq].done_locked(), timeout
            )
            if not ok:
                raise TimeoutError("pool result wait timed out")
            return self._pop(seq)

    def _pop(self, seq: int) -> List[Any]:
        entry = self._entries.pop(seq)
        self._completion_log.pop(seq, None)
        return entry.values

    def add_callback(self, seq: int, cb: Callable) -> None:
        with self._cond:
            entry = self._entries.get(seq)
            if entry is None or entry.done_locked():
                fire = True
            else:
                entry.callbacks.append(cb)
                fire = False
        if fire:
            cb()

    def iter_ordered(self, seq: int):
        """Yield results in submission order as they become available.
        Each yielded slot's payload reference is dropped at grab time
        (stream: popped from the pending dict; classic: ``_YIELDED``
        tombstone) and the store condition notified, which is what
        advances a stream's admission window as the ordered head moves.
        The whole contiguous ready run is grabbed under ONE lock
        acquire — per-item lock+notify is measurable at 1M tasks — and
        the local batch is bounded by the un-yielded window, so memory
        stays O(window)."""
        i = 0
        while True:
            batch: List[Any] = []
            with self._cond:
                entry = self._entries.get(seq)
                if entry is None:
                    return
                if i >= entry.total and entry.finalized:
                    self._pop(seq)
                    return

                def _head_ready() -> bool:
                    e = self._entries.get(seq)
                    if e is None:
                        return True
                    if i < e.total:
                        return e.filled_locked(i) and (
                            not e.stream or i in e.pending)
                    return e.finalized  # stream: past the admitted tail
                self._cond.wait_for(_head_ready)
                entry = self._entries.get(seq)
                if entry is None:
                    return
                if i >= entry.total:  # finalized with no more slots
                    self._pop(seq)
                    return
                if entry.stream:
                    pending = entry.pending
                    while i < entry.total and i in pending:
                        batch.append(pending.pop(i))
                        entry.yielded += 1
                        i += 1
                else:
                    vals = entry.values
                    while i < entry.total and vals[i] is not _UNSET:
                        batch.append(vals[i])
                        vals[i] = _YIELDED
                        entry.yielded += 1
                        i += 1
                if batch:
                    self._cond.notify_all()
            for value in batch:
                yield value

    def iter_unordered(self, seq: int):
        """Yield results in completion order. The completion log is a
        deque consumed by popleft, so it too stays O(un-yielded window)
        on a stream; yielded slots release their payload reference at
        grab time like iter_ordered, and the log is drained in one
        batch per lock acquire."""
        while True:
            batch: List[Any] = []
            with self._cond:
                entry = self._entries.get(seq)
                if entry is None:
                    return
                log = self._completion_log.get(seq)
                if not log and entry.yielded >= entry.total \
                        and entry.finalized:
                    self._pop(seq)
                    return

                def _have_result() -> bool:
                    e = self._entries.get(seq)
                    if e is None:
                        return True
                    lg = self._completion_log.get(seq)
                    return bool(lg) or (e.finalized
                                        and e.yielded >= e.total)
                self._cond.wait_for(_have_result)
                entry = self._entries.get(seq)
                log = self._completion_log.get(seq)
                if entry is None:
                    return
                if not log:  # finalized, everything already yielded
                    self._pop(seq)
                    return
                if entry.stream:
                    # Detach the whole log under an O(1) lock hold and
                    # pop the values OUTSIDE the lock: fill() only ever
                    # ADDS distinct keys (dedup rides the bitmap, not
                    # the dict), so per-key dict ops need no lock, and
                    # the result loop's fills never stall behind a
                    # windowful of consumer pops.
                    detached = log
                    self._completion_log[seq] = deque()
                    entry.yielded += len(log)
                    self._cond.notify_all()
                    pending = entry.pending
                else:
                    detached = None
                    vals = entry.values
                    while log:
                        idx = log.popleft()
                        batch.append(vals[idx])
                        vals[idx] = _YIELDED
                        entry.yielded += 1
                    self._cond.notify_all()
            if detached is not None:
                batch = [pending.pop(idx) for idx in detached]
            for value in batch:
                yield value

    def _fail_entry_locked(self, seq: int, entry: "_Entry",
                           exc: BaseException, reason: str,
                           direct: bool) -> List[Callable]:
        """Fail an entry's unset slots (caller holds the lock); returns
        the completion callbacks to fire outside the lock."""
        log = self._completion_log.get(seq)
        if log is None:
            log = self._completion_log[seq] = deque()
        if entry.stream:
            bits = entry.bits
            for i in range(entry.total):
                if not (bits[i >> 3] >> (i & 7)) & 1:
                    bits[i >> 3] |= 1 << (i & 7)
                    entry.pending[i] = _Failure(exc, reason,
                                                direct=direct)
                    log.append(i)  # unblock iter_unordered too
        else:
            for i, v in enumerate(entry.values):
                if v is _UNSET:
                    entry.values[i] = _Failure(exc, reason, direct=direct)
                    log.append(i)  # unblock iter_unordered consumers too
        # A failed stream admits nothing more: finalize it here so
        # iterators terminate after draining the failure markers and the
        # admission loop's capacity wait falls through.
        fresh_fail = entry.remaining > 0 or not entry.finalized
        entry.finalized = True
        if fresh_fail:
            entry.remaining = 0
            # Completion callbacks must fire on failure paths too, or
            # map_async consumers waiting on a callback (rather than
            # .get()) hang through the very failure being surfaced.
            return list(entry.callbacks)
        return []

    @staticmethod
    def _drain_callbacks(callbacks: List[Callable]) -> None:
        for cb in callbacks:
            try:
                cb()
            except Exception:
                logger.exception("pool callback failed")

    def fail(self, seq: int, exc: BaseException,
             reason: str = "dispatch failed", direct: bool = True) -> None:
        """Fail every unset slot of ONE entry (device-dispatch errors);
        fires the entry's completion callbacks."""
        with self._cond:
            entry = self._entries.get(seq)
            if entry is None:
                return
            callbacks = self._fail_entry_locked(seq, entry, exc, reason,
                                                direct)
            self._cond.notify_all()
        self._drain_callbacks(callbacks)

    def _outstanding_locked(self) -> int:
        """Unfilled slots, plus one phantom unit per open (unfinalized)
        stream — a caught-up stream between admissions must still hold
        ``join()``/drain gates open, or the pool would release workers
        mid-stream. The phantom is noise to the 20k-item inflight gate."""
        return (sum(e.remaining for e in self._entries.values())
                + sum(1 for e in self._entries.values()
                      if e.stream and not e.finalized))

    def outstanding(self) -> int:
        with self._cond:
            return self._outstanding_locked()

    def wait_outstanding_below(self, limit: int,
                               timeout: Optional[float] = None) -> bool:
        """Block until the in-flight item count is <= ``limit`` (True)
        or ``timeout`` elapses (False). Rides the store's condition —
        every fill/fail notifies it — so backpressure waits cost no
        idle CPU. Only downward transitions matter to the predicate, so
        submissions (which raise the count without notifying) can't
        strand a waiter on a stale True."""
        with self._cond:
            return self._cond.wait_for(
                lambda: self._outstanding_locked() <= limit,
                timeout,
            )

    def is_done(self, seq: int) -> bool:
        """True when ``seq`` has completed or failed — its chunks are
        dead weight and must not be handed to (or resubmitted at)
        workers. A caught-up but unfinalized stream is NOT done: more
        chunks are coming."""
        with self._cond:
            entry = self._entries.get(seq)
            return entry is None or entry.done_locked()

    def abort_all(self, exc: BaseException,
                  reason: str = "pool terminated",
                  direct: bool = False) -> None:
        """Fail every unset slot with ``exc``. ``direct=True`` raises the
        exception itself from result getters (catchable by its own type)
        instead of wrapping it in RemoteError — for local failures like
        worker-start escalation, which never happened on a remote."""
        callbacks: List[Callable] = []
        with self._cond:
            for seq, entry in self._entries.items():
                callbacks.extend(
                    self._fail_entry_locked(seq, entry, exc, reason,
                                            direct))
            self._cond.notify_all()
        self._drain_callbacks(callbacks)


class _Failure:
    """Marker wrapping a failed result slot. Remote failures re-raise as
    RemoteError (with the remote traceback); local failures
    (``direct=True``) re-raise the original exception so callers can
    catch it by type."""

    __slots__ = ("exc", "tb", "direct")

    def __init__(self, exc: BaseException, tb: str,
                 direct: bool = False) -> None:
        self.exc = exc
        self.tb = tb
        self.direct = direct

    def raise_(self) -> None:
        if self.direct:
            raise self.exc from None
        raise RemoteError(self.exc, self.tb) from None


def _resolve(value: Any) -> Any:
    if isinstance(value, _Failure):
        value.raise_()
    return value


class AsyncResult:
    """Handle returned by apply_async (reference: fiber/pool.py:731-757)."""

    def __init__(self, store: ResultStore, seq: int, single: bool) -> None:
        self._store = store
        self._seq = seq
        self._single = single
        self._value: Any = _UNSET
        # Serializes concurrent fetches (user .get() vs. callback firing):
        # the store entry can only be popped once.
        self._fetch_lock = threading.Lock()

    def _fetch(self, timeout: Optional[float]) -> None:
        with self._fetch_lock:
            if self._value is _UNSET:
                with global_timer.section("pool.result_wait"):
                    self._value = self._store.wait(self._seq, timeout)

    def get(self, timeout: Optional[float] = None) -> Any:
        self._fetch(timeout)
        if self._single:
            return _resolve(self._value[0])
        return [_resolve(v) for v in self._value]

    def wait(self, timeout: Optional[float] = None) -> None:
        try:
            self._fetch(timeout)
        except TimeoutError:
            pass

    def ready(self) -> bool:
        return self._value is not _UNSET or self._store.ready(self._seq)

    def successful(self) -> bool:
        if not self.ready():
            raise ValueError("result is not ready")
        self._fetch(None)
        values = self._value if not self._single else [self._value[0]]
        return not any(isinstance(v, _Failure) for v in values)


MapResult = AsyncResult


def _register_async_callbacks(store: ResultStore, seq: int,
                              result: AsyncResult,
                              callback: Optional[Callable],
                              error_callback: Optional[Callable]) -> None:
    """Wire multiprocessing-style completion callbacks to a store entry:
    success values go to ``callback``, failures — RemoteError from worker
    code or direct local failures (WorkerStartError, device-dispatch
    errors) — to ``error_callback``. Fires on whichever thread completes
    the entry, never the submitting one."""
    if callback is None and error_callback is None:
        return

    def fire() -> None:
        try:
            value = result.get(0)
        except TimeoutError:
            return  # not actually complete; a later fill refires
        except Exception as err:  # noqa: BLE001
            if error_callback is not None:
                error_callback(err)
            return
        if callback is not None:
            callback(value)

    store.add_callback(seq, fire)


class _ResultIterator:
    """imap iterator: an item whose task raised re-raises RemoteError at
    consumption, and the iterator remains usable for the items after it
    (multiprocessing IMapIterator semantics)."""

    def __init__(self, inner) -> None:
        self._inner = inner

    def __iter__(self) -> "_ResultIterator":
        return self

    def __next__(self) -> Any:
        return _resolve(next(self._inner))


# ---------------------------------------------------------------------------
# By-reference payloads (fiber_tpu/store): args/results above
# store_inline_max travel as ObjectRefs; workers resolve them through the
# per-host store so a broadcast arg crosses the wire once per host, not
# once per task (docs/objectstore.md).
# ---------------------------------------------------------------------------


def _payload_size_hint(obj: Any) -> Optional[int]:
    """Cheap serialized-size estimate, or None when only a real pickle
    can tell. The point is to never pay a probe pickle for the common
    small scalars nor for the numpy/jax arrays whose size is a field
    read; unknown container types fall through to the probe."""
    if obj is None or isinstance(obj, (bool, int, float, complex)):
        return 0
    if isinstance(obj, (bytes, bytearray, memoryview, str)):
        return len(obj)
    try:
        nbytes = getattr(obj, "nbytes", None)  # numpy / jax arrays
        if nbytes is not None:
            return int(nbytes)
    except Exception:  # noqa: BLE001 - exotic objects; just probe
        pass
    return None


def _chunk_spans(n_items: int, chunksize: int) -> List[Tuple[int, int]]:
    """Balanced remainder chunking: split ``n_items`` into
    ``ceil(n/chunksize)`` near-equal spans (sizes differ by at most 1,
    none above ``chunksize``) instead of fixed-size chunks plus one
    small straggler tail. ``chunksize`` keeps its explicit-override
    meaning as the chunk-size CAP; only the remainder is rebalanced —
    an evenly divisible length produces exactly the classic chunks.
    Returns ``[(base, size), ...]``."""
    chunksize = max(1, int(chunksize))
    nchunks = max(1, -(-n_items // chunksize))
    base_size, rem = divmod(n_items, nchunks)
    spans: List[Tuple[int, int]] = []
    offset = 0
    for i in range(nchunks):
        size = base_size + (1 if i < rem else 0)
        spans.append((offset, size))
        offset += size
    return spans


def _chunk_digests(chunk: List[Any]) -> List[str]:
    """Object digests this chunk's items reference (top level or one
    tuple level deep — exactly where the encoder puts refs); the
    scheduler's locality key set."""
    digs: List[str] = []
    for item in chunk:
        if isinstance(item, ObjectRef):
            digs.append(item.digest)
        elif type(item) is tuple:
            digs.extend(e.digest for e in item
                        if isinstance(e, ObjectRef))
    return digs


def _chunk_has_refs(chunk: List[Any]) -> bool:
    for item in chunk:
        if isinstance(item, ObjectRef):
            return True
        if type(item) is tuple and any(
                isinstance(e, ObjectRef) for e in item):
            return True
    return False


def _resolve_item(item: Any, client) -> Any:
    """Replace ObjectRefs (top level, or one tuple level deep — exactly
    where the encoder puts them) with the resolved objects. Raises
    StoreFetchError when a ref cannot be resolved from any tier.
    Device-hinted refs resolve through the store's device tier, so
    co-located workers share one replicated copy per digest."""
    if isinstance(item, ObjectRef):
        return client.resolve(
            item, device=getattr(item, "device_hint", False))
    if type(item) is tuple and any(
            isinstance(e, ObjectRef) for e in item):
        return tuple(
            client.resolve(e, device=getattr(e, "device_hint", False))
            if isinstance(e, ObjectRef) else e
            for e in item)
    return item


def _encode_results(values: List[Any], get_client, store_addr: str,
                    inline_max: int) -> List[Any]:
    """Worker-side result encoding: push results above the threshold to
    the master's store and ship the ref. Every failure falls back to
    inline shipping — the store is an optimization, never a correctness
    dependency."""
    for i, v in enumerate(values):
        if isinstance(v, (_Failure, ObjectRef)):
            continue
        hint = _payload_size_hint(v)
        if hint is not None and hint <= inline_max:
            continue
        try:
            data = serialization.dumps(v)
        except Exception:  # noqa: BLE001 - let the inline path raise it
            continue
        if len(data) <= inline_max:
            continue
        try:
            values[i] = get_client().push(data, store_addr)
        except Exception:  # noqa: BLE001
            logger.warning("store: result push failed; shipping inline",
                           exc_info=True)
    return values


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------

_EXIT = ("exit",)
#: Sentinel the task-fetch thread enqueues when its connection died —
#: distinct from a clean exit so the crash surfaces as reason="error".
_FETCH_FAILED = object()


class _FuncCache:
    """Unpickle each shipped function once per worker (functions travel as
    bytes keyed by digest so repeated chunks are cheap)."""

    def __init__(self) -> None:
        self._cache: Dict[bytes, Callable] = {}

    def get(self, digest: bytes, blob: Optional[bytes]) -> Callable:
        fn = self._cache.get(digest)
        if fn is None:
            if blob is None:
                raise RuntimeError("worker missing function blob")
            fn = serialization.loads(blob)
            self._cache[digest] = fn
        return fn


def _run_chunk(fn: Callable, chunk: List[Any], star: bool) -> List[Any]:
    out: List[Any] = []
    for args in chunk:
        try:
            if star:
                out.append(fn(*args))
            else:
                out.append(fn(args))
        except BaseException as exc:  # noqa: BLE001 - shipped to master
            out.append(_Failure(exc, traceback.format_exc()))
    return out


# Exit codes a packed sub-worker uses so the packing parent can tell a
# clean maxtasksperchild recycle (17) and a transport failure (19) apart
# from "the pool is shutting down" (0) and from a crash (anything else).
_SUBWORKER_RECYCLE = 17
_SUBWORKER_XPORT_ERR = 19


def _subworker_main(
    ident: bytes,
    task_addr: str,
    result_addr: str,
    resilient: bool,
    initializer: Optional[Callable],
    initargs: Tuple,
    maxtasksperchild: Optional[int],
    store_addr: Optional[str],
) -> None:
    reason = _pool_worker_core(
        task_addr, result_addr, resilient, initializer, initargs,
        maxtasksperchild, ident=ident, store_addr=store_addr,
    )
    if reason == "recycle":
        sys.exit(_SUBWORKER_RECYCLE)
    if reason == "error":
        # A dropped connection is NOT a drain: the parent must report the
        # ident (its handed-out chunk may be stranded in the pending
        # table) and respawn — exit 0 here would silently eat both.
        sys.exit(_SUBWORKER_XPORT_ERR)


def pool_worker(
    task_addr: str,
    result_addr: str,
    resilient: bool,
    initializer: Optional[Callable],
    initargs: Tuple,
    maxtasksperchild: Optional[int],
    n_local: int = 1,
    ctl_addr: Optional[str] = None,
    store_addr: Optional[str] = None,
    dispatch_mode: str = "direct",
) -> None:
    """Body of one pool worker process. With ``n_local > 1`` the process
    packs that many OS sub-workers, each dialing the master independently
    (reference: fiber/pool.py:144-173 cpu_per_job packing).

    With ``dispatch_mode="hier"`` (resilient packed jobs only) the
    process instead becomes this host's sub-master: it fetches chunk
    RANGES from the master, fans them to local sub-workers, and streams
    results back aggregated (fiber_tpu/sched/hier.py).

    Unlike the reference — where a dead sub-worker's pending chunks
    strand until the WHOLE job exits (job-level ``is_alive`` is the only
    death signal) — the packing parent here monitors each child: a crash
    is reported to the resilient master's dedicated control endpoint as
    a ``("subdead", ident)`` frame (the master resubmits exactly that
    sub-worker's pending chunks) and the child is respawned in place, so
    the job never silently loses capacity. Clean maxtasksperchild
    recycling (exit code ``_SUBWORKER_RECYCLE``) respawns the slot and
    reports ``("subgone", ident)`` so the master can retire the old
    ident's bookkeeping; exit 0 means the pool is draining — no respawn."""
    if n_local > 1:
        if dispatch_mode == "hier" and resilient:
            from fiber_tpu.sched.hier import HostDispatcher

            HostDispatcher(
                task_addr, result_addr, n_local, initializer, initargs,
                maxtasksperchild, store_addr,
            ).run()
            return
        import multiprocessing

        from fiber_tpu.transport.tcp import connect_transport

        ctx = multiprocessing.get_context("fork")

        def spawn(i: int):
            ident = uuid.uuid4().bytes
            c = ctx.Process(
                target=_subworker_main,
                args=(ident, task_addr, result_addr, resilient,
                      initializer, initargs, maxtasksperchild,
                      store_addr),
                name=f"fiber-subworker-{i}",
                daemon=True,
            )
            c.start()
            return ident, c

        def try_report(kind: str, ident: bytes) -> bool:
            # Reports ride the resilient master's DEDICATED control
            # endpoint (ctl_addr; None on the plain pool, which has no
            # pending table to repair). Not the result channel — that
            # would inflate the peer count wait_workers() reads as
            # "workers connected" — and not the REQ/REP task channel,
            # whose single-threaded loop can be parked in its
            # task-handout wait (a deadlock: resubmission needs the
            # report processed, the report waits behind the handout).
            # The credit-based send IS the delivery confirmation (it
            # only completes against a consumer-granted credit); a
            # failed send stays queued and is retried — a lost report
            # must not strand the dead sub-worker's pending chunks
            # forever, because the respawned slot keeps the job alive,
            # so the job-death backstop would never fire.
            try:
                # native=False: only the Python Endpoint honors the send
                # deadline (the C client blocks on the credit wait); a
                # report into a half-dead connection must fail (and be
                # retried) rather than freeze the monitor loop — this is
                # the parent's only thread. Reports are rare and tiny,
                # so the native fast path buys nothing here.
                # retries=0: the transport's connect backoff would turn
                # "master unreachable" into ~1s of doomed redials per
                # attempt on this single-threaded monitor; the 1s tick
                # gate is the retry policy here.
                ep = connect_transport("w", ctl_addr, native=False,
                                       retries=0)
                try:
                    ep.send(serialization.dumps((kind, ident)),
                            timeout=10.0)
                    return True
                finally:
                    ep.close()
            except Exception:
                logger.warning("subworker monitor: %s report failed "
                               "(will retry)", kind)
                return False

        children = {ident: (c, time.monotonic())
                    for ident, c in (spawn(i) for i in range(n_local))}
        draining = False
        fail_streak = 0
        pending_reports: List[Tuple[str, bytes]] = []
        last_report_attempt = 0.0
        while children:
            time.sleep(0.1)
            if pending_reports and ctl_addr \
                    and time.monotonic() - last_report_attempt >= 1.0:
                # Drain until the first failure: successful sends are
                # cheap, so a healthy master absorbs a death burst
                # immediately; with the master unreachable the first
                # attempt fails after its connect timeout and the 1s
                # tick gate keeps the monitor reaping/respawning
                # instead of starving in doomed connect() calls.
                last_report_attempt = time.monotonic()
                while pending_reports and try_report(*pending_reports[0]):
                    pending_reports.pop(0)
            for ident, (c, born) in list(children.items()):
                code = c.exitcode
                if code is None:
                    continue
                del children[ident]
                c.join()
                if code == 0:
                    draining = True  # master released this worker
                    continue
                if ctl_addr:
                    # Clean recycle ("subgone"): master drops the old
                    # ident's bookkeeping. Crash ("subdead"): master
                    # resubmits the ident's pending chunks NOW rather
                    # than when the whole job dies. Under a long master
                    # outage only disposable "subgone" entries (pure
                    # bookkeeping cleanup) are shed; "subdead" reports
                    # are NEVER dropped — a lost one would strand its
                    # ident's pending chunks forever, since the
                    # respawned slot keeps the job (and its death
                    # backstop) alive. Each entry is ~50 bytes, so the
                    # worst case is bounded by the crash count.
                    kind = ("subgone" if code == _SUBWORKER_RECYCLE
                            else "subdead")
                    pending_reports.append((kind, ident))
                    if len(pending_reports) > 512:
                        keep = [r for r in pending_reports
                                if r[0] == "subdead"]
                        pending_reports = keep
                    last_report_attempt = 0.0
                if draining:
                    continue
                if code != _SUBWORKER_RECYCLE:
                    # Exponential backoff on rapid crash loops (failing
                    # initializer, master gone hard): a child that died
                    # within 5s of spawn escalates the delay, a child
                    # that survived longer resets it.
                    if time.monotonic() - born < 5.0:
                        fail_streak += 1
                    else:
                        fail_streak = 0
                    time.sleep(min(0.1 * (2 ** fail_streak), 2.0))
                new_ident, new_c = spawn(len(children))
                children[new_ident] = (new_c, time.monotonic())
        # Final flush so a crash right at drain time still gets
        # reported; stop at the first failure — an unreachable master
        # must not hold the exiting parent for one connect timeout per
        # queued report (job death is the backstop then anyway).
        for kind, ident in pending_reports:
            if not try_report(kind, ident):
                break
        return
    _pool_worker_core(
        task_addr, result_addr, resilient, initializer, initargs,
        maxtasksperchild, store_addr=store_addr,
    )


def _pool_worker_core(
    task_addr: str,
    result_addr: str,
    resilient: bool,
    initializer: Optional[Callable],
    initargs: Tuple,
    maxtasksperchild: Optional[int],
    ident: Optional[bytes] = None,
    store_addr: Optional[str] = None,
) -> str:
    from fiber_tpu import process as fprocess

    if initializer is not None:
        initializer(*initargs)

    ident = ident or uuid.uuid4().bytes
    fiber_pid = fprocess.current_process().pid or os.getpid()
    funcs = _FuncCache()

    if FLIGHT.enabled:
        # Black-box posture (docs/observability.md): a dying worker
        # flushes its flight buffer + stack dump into a postmortem
        # bundle under the staging root — on SIGTERM/SIGABRT via the
        # handler, and on the chaos harness's hard-kill via its
        # pre-exit crash_flush hook.
        from fiber_tpu.telemetry import postmortem

        postmortem.install_crash_handler()

    from fiber_tpu.transport.tcp import connect_transport

    result_ep = connect_transport("w", result_addr)
    if resilient:
        task_ep = connect_transport("req", task_addr)
    else:
        # prefetch=2: the transport pulls the next chunk while the
        # current one computes (one parked frame at most — the plain
        # pool has no resubmission, so the bound stays tight). With
        # maxtasksperchild the window must collapse to pure demand
        # (prefetch=1): a standing window parks one granted chunk in
        # the inbox of a worker that breaks at its task budget, and
        # the plain pool has no pending table to resubmit it — the
        # chunk would be silently lost and map() would hang (advisor,
        # round 3). prefetch=1 grants credit only to a reader blocked
        # in recv(), so a recycle break strands nothing.
        task_ep = connect_transport(
            "r", task_addr,
            prefetch=1 if maxtasksperchild else 2,
        )

    completed_chunks = 0
    reason = "error"
    next_task = None
    heartbeater = None
    # Last device-telemetry revision shipped to the master (list so the
    # per-chunk _ship_device closure can update it).
    dev_shipped = [0]
    # Last accounting-ledger revision shipped (same posture: cumulative
    # ("cost", ...) frames ride the result stream only when this moved).
    cost_shipped = [0]
    # By-reference payloads: the store client is built lazily on the
    # first ref actually seen (most workers in small maps never pay the
    # import), shared across chunks so broadcast args resolve once per
    # worker process. Result-side threshold mirrors the master's config
    # (shipped in the spawn preparation).
    store_client = None
    store_inline_max = 0
    if store_addr:
        from fiber_tpu import config as _wcfg

        _c = _wcfg.get()
        if _c.store_enabled:
            store_inline_max = int(_c.store_inline_max)

    def get_store_client():
        nonlocal store_client
        if store_client is None:
            from fiber_tpu import store as storemod

            store_client = storemod.client()
        return store_client
    if resilient:
        # Health plane: beat on the result stream (the master's result
        # loop already fair-merges it; no extra sockets) so the failure
        # detector can declare this worker dead on silence — a hung
        # host stops beating long before TCP notices. Plain pools skip
        # it: with no pending table there is nothing a declaration
        # could resubmit.
        from fiber_tpu import config as fconfig
        from fiber_tpu.health import Heartbeater

        hb_interval = float(fconfig.get().heartbeat_interval or 0)
        if hb_interval > 0:
            hb_payload = serialization.dumps(("hb", ident))

            def _emit_beat() -> None:
                result_ep.send(hb_payload, timeout=hb_interval)

            heartbeater = Heartbeater(
                _emit_beat, hb_interval, gate=chaos.heartbeats_allowed,
            ).start()
        # Pipelined REQ/REP handout: a fetch thread keeps exactly one
        # chunk staged locally so the ready->task round trip overlaps
        # compute instead of serializing with it (the reference's REQ
        # loop pays the round trip per chunk on the critical path —
        # fiber/pool.py:783-790; this closed most of the measured 10ms
        # overhead gap vs multiprocessing). Strict send/recv alternation
        # is preserved — only this thread touches task_ep. The depth-1
        # queue bounds a dead worker's blast radius to three chunks —
        # computing + queued + one the fetch thread may hold while
        # blocked in put — all tracked in the pending table.
        # With maxtasksperchild the thread stops fetching at the budget,
        # so recycling can never strand a staged chunk.
        next_task = pyqueue.Queue(maxsize=1)
        # Placement identity rides every "ready" frame so the master's
        # scheduler can route ref-bearing chunks to the hosts that
        # already cache their objects (docs/scheduling.md). Backends
        # that pick the host stamp FIBER_HOST_KEY into the job env;
        # local workers share the machine's host id.
        host_key = local_host_key()

        def fetch_loop() -> None:
            fetched = 0
            try:
                while True:
                    task_ep.send(
                        serialization.dumps(
                            ("ready", ident, fiber_pid, host_key))
                    )
                    msg = serialization.loads(task_ep.recv())
                    next_task.put(msg)
                    if msg[0] == "exit":
                        return
                    fetched += 1
                    if maxtasksperchild and fetched >= maxtasksperchild:
                        return
            except BaseException:
                # NOT the clean ("exit",) sentinel: a dropped connection
                # (or any decode failure) must surface as reason="error"
                # so a packed parent reports subdead and the master
                # resubmits this ident's pending chunks — mapping it to
                # "exit" would read as pool drain and silently eat both
                # (see _subworker_main). Broad catch: a dead fetch
                # thread with no sentinel would park the main loop in
                # next_task.get() forever.
                next_task.put(_FETCH_FAILED)

        fetcher = threading.Thread(target=fetch_loop,
                                   name="fiber-task-fetch", daemon=True)
        fetcher.start()
    try:
        while True:
            if resilient:
                msg = next_task.get()
                if msg is _FETCH_FAILED:
                    break  # reason stays "error": crash, not drain
            else:
                msg = serialization.loads(task_ep.recv())
            if msg[0] == "exit":
                reason = "exit"
                break
            # 7-tuple envelopes predate the telemetry plane; the trace
            # context rides as an optional 8th field and the accounting
            # billing key as an optional 9th, so replayed/stored
            # payloads of any shape decode.
            seq, base, digest, blob, chunk, star = msg[1:7]
            tctx = msg[7] if len(msg) > 7 else None
            bkey = (tuple(msg[8]) if len(msg) > 8 and msg[8] is not None
                    else None)
            if FLIGHT.enabled:
                # One event per chunk: the dead-worker bundle must show
                # what the worker was chewing on when it died.
                FLIGHT.record("pool", "chunk", seq=seq, base=base,
                              items=len(chunk))

            def _wspan(name: str, **attrs):
                # Spans only for traced chunks (the master sampled this
                # map): an unsampled map must not fill the ring buffer
                # with spans nobody will ship.
                if tctx is None:
                    return contextlib.nullcontext()
                return tracing.span(name, seq=seq, base=base, **attrs)

            def _ship_spans() -> None:
                if tctx is None:
                    return
                finished = tracing.SPANS.drain()
                if not finished:
                    return
                try:
                    # Spans ride the existing result stream (like the
                    # health plane's heartbeats) — no extra sockets; a
                    # lost spans frame costs observability, never
                    # results.
                    result_ep.send(serialization.dumps(
                        ("spans", ident, finished, bkey)))
                except (TransportClosed, OSError):
                    pass

            def _ship_profile() -> None:
                # Sampling-profiler stacks ride the result stream too
                # (docs/observability.md "Sampling profiler"): drain so
                # each frame carries only samples the master hasn't
                # seen. Unlike spans this is NOT tied to the map's
                # trace sampling — the profiler has its own hz knob.
                from fiber_tpu.telemetry.profiler import PROFILER

                if not PROFILER.active:
                    return
                folded = PROFILER.drain()
                if not folded:
                    return
                try:
                    result_ep.send(serialization.dumps(
                        ("prof", ident,
                         f"{tracing.host_id()}:{fiber_pid}", folded,
                         bkey)))
                except (TransportClosed, OSError):
                    pass

            def _ship_device() -> None:
                # Device-plane counters (transfer accounting, compile
                # observability — docs/observability.md "Device
                # telemetry") ride the result stream like spans and
                # profiles, but as a CUMULATIVE snapshot keyed host:pid
                # (latest wins on the master) — shipped only when the
                # revision moved so idle workers cost nothing.
                from fiber_tpu.telemetry.device import DEVICE

                if not DEVICE.enabled \
                        or DEVICE.revision == dev_shipped[0]:
                    return
                snap = DEVICE.snapshot()
                dev_shipped[0] = snap["revision"]
                try:
                    result_ep.send(serialization.dumps(
                        ("dev", ident,
                         f"{tracing.host_id()}:{fiber_pid}", snap,
                         bkey)))
                except (TransportClosed, OSError):
                    pass

            def _ship_cost() -> None:
                # Accounting plane (docs/observability.md "Resource
                # accounting"): this worker's per-billing-key cost
                # vectors (chunk busy-seconds, store fetches, device
                # transfers) ride the result stream as a CUMULATIVE
                # snapshot keyed host:pid — the device-frame posture:
                # latest wins on the master, shipped only when the
                # ledger revision moved so idle workers cost nothing.
                if not COSTS.enabled \
                        or COSTS.revision == cost_shipped[0]:
                    return
                snap = COSTS.snapshot()
                cost_shipped[0] = snap["revision"]
                try:
                    result_ep.send(serialization.dumps(
                        ("cost", ident,
                         f"{tracing.host_id()}:{fiber_pid}", snap)))
                except (TransportClosed, OSError):
                    pass
            plan = chaos._plan
            if plan is not None:
                # Hang BEFORE compute (the held chunk is what the
                # detector must get resubmitted); kill AFTER a result
                # (so the death strands staged/queued chunks, the
                # resubmission case worth inducing). A slow token turns
                # this worker into a living straggler — heartbeats keep
                # flowing, the scheduler's speculation is what must
                # route around it.
                plan.maybe_hang_worker(completed_chunks)
                plan.maybe_slow_worker(completed_chunks)
            chunk_t0 = time.perf_counter()
            with contextlib.ExitStack() as tstack:
                if tctx is not None:
                    # Adopt the master's trace so every span below
                    # shares its trace id, parented on the map's
                    # serialize span.
                    tstack.enter_context(
                        tracing.trace_context(tctx[0], tctx[1]))
                if bkey is not None and COSTS.enabled:
                    # Ambient billing key for the whole chunk: store
                    # fetches and device transfers inside it bill to
                    # the map that caused them, not to overhead.
                    tstack.enter_context(COSTS.context(bkey))
                if _chunk_has_refs(chunk):
                    try:
                        with _wspan("worker.resolve_refs"), \
                                global_timer.section("pool.store_resolve"):
                            client = get_store_client()
                            chunk = [_resolve_item(it, client)
                                     for it in chunk]
                    except StoreFetchError as err:
                        # Degrade, don't fail: ask the master to resend
                        # this chunk with inline payloads (the store is
                        # an optimization, never a correctness
                        # dependency).
                        logger.warning(
                            "store: fetch failed (%s); requesting inline "
                            "resend of chunk seq=%s base=%s",
                            err, seq, base)
                        result_ep.send(serialization.dumps(
                            ("storemiss", seq, base, len(chunk), ident)))
                        if bkey is not None and COSTS.enabled:
                            # The failed resolve was still work this
                            # map caused; no tasks executed though.
                            COSTS.charge(bkey, cpu_s=(
                                time.perf_counter() - chunk_t0))
                        _ship_spans()
                        _ship_cost()
                        # The handout is consumed even though nothing
                        # ran: the resilient fetch thread budgets
                        # FETCHED chunks (maxtasksperchild), so skipping
                        # this increment would leave the main loop
                        # waiting on a chunk the fetcher will never
                        # deliver.
                        completed_chunks += 1
                        if maxtasksperchild \
                                and completed_chunks >= maxtasksperchild:
                            reason = "recycle"
                            break
                        continue
                fn = funcs.get(digest, blob)
                with _wspan("worker.execute", items=len(chunk)):
                    values = _run_chunk(fn, chunk, star)
                if store_inline_max > 0:
                    with _wspan("worker.encode_results"):
                        values = _encode_results(values, get_store_client,
                                                 store_addr,
                                                 store_inline_max)
            if bkey is not None and COSTS.enabled:
                # Chunk busy-seconds (resolve + execute + encode wall)
                # and executions INCLUDING duplicates — the master's
                # first-fill `tasks` count is the exactly-once side;
                # the difference is the duplicate count.
                COSTS.charge(bkey,
                             cpu_s=time.perf_counter() - chunk_t0,
                             tasks_executed=len(chunk))
            result_ep.send(
                serialization.dumps(("result", seq, base, values, ident))
            )
            _ship_spans()
            _ship_profile()
            _ship_device()
            _ship_cost()
            completed_chunks += 1
            if plan is not None:
                plan.maybe_kill_worker(completed_chunks)
            if maxtasksperchild and completed_chunks >= maxtasksperchild:
                reason = "recycle"
                break
    except (TransportClosed, OSError):
        pass  # master went away; the watchdog handles hard exits
    finally:
        if heartbeater is not None:
            heartbeater.stop()
        task_ep.close()
        result_ep.close()
    return reason


# ---------------------------------------------------------------------------
# Master side
# ---------------------------------------------------------------------------


class Pool:
    """Round-robin push pool (reference ZPool, fiber/pool.py:881-1422)."""

    _resilient = False

    def __init__(
        self,
        processes: Optional[int] = None,
        initializer: Optional[Callable] = None,
        initargs: Tuple = (),
        maxtasksperchild: Optional[int] = None,
    ) -> None:
        from fiber_tpu import config
        from fiber_tpu.backends import get_backend

        cfg = config.get()
        # Config may have changed since import (fiber_tpu.init); the
        # telemetry plane follows the pool's view of it.
        telemetry.refresh()
        #: Per-pool exact counts surfaced by Pool.stats() (the registry
        #: twins aggregate across every pool in the process).
        self._n_submitted = 0
        self._n_completed = 0
        self._n_resubmitted = 0
        #: Latest device-telemetry snapshot per worker (host:pid), from
        #: the ("dev", ...) result-stream frames — Pool.device_stats().
        self._device_workers: Dict[str, dict] = {}
        #: Accounting plane (docs/observability.md "Resource
        #: accounting"): latest cumulative cost snapshot per worker
        #: (host:pid) from ("cost", ...) frames; seq -> billing key for
        #: this pool's in-flight maps; seq -> map-start perf_counter
        #: (wall_s billing); completed billing key -> job_id so a cost
        #: frame landing AFTER the last result still refreshes the
        #: persisted per-job record.
        self._cost_workers: Dict[str, dict] = {}
        self._seq_bill: Dict[int, Tuple[str, str, str]] = {}
        self._map_wall0: Dict[int, float] = {}
        self._job_records: Dict[Tuple[str, str, str], str] = {}
        self._map_budgets: Dict[Tuple[str, str, str], CostBudget] = {}
        #: raw-content digest -> store-space digest for device-map
        #: broadcast args (_device_broadcast_split): repeat generations
        #: skip the serialize copy, paying one zero-copy hash.
        self._bcast_digests: Dict[str, str] = {}
        if processes is None:
            processes = get_backend().default_pool_size()
        if processes < 1:
            raise ValueError("processes must be >= 1")
        self._n_workers = processes
        self._initializer = initializer
        self._initargs = initargs
        self._maxtasksperchild = maxtasksperchild
        # Workers are packed cpu_per_job sub-workers per job, the last job
        # taking the remainder (reference: fiber/pool.py:1009-1057).
        self._cpu_per_job = max(1, int(cfg.cpu_per_job))
        # Hierarchical dispatch (docs/architecture.md "Hierarchical
        # dispatch"): with dispatch_mode="hier" each packed job runs a
        # per-host sub-master that fetches whole chunk RANGES (one
        # REQ/REP frame per range) and returns results aggregated, so
        # master frame count scales with hosts instead of workers. Only
        # meaningful on the resilient pool (ranges live in the pending
        # table); packed jobs that lose their sub-master degrade to
        # direct per-worker dispatch on respawn.
        self._dispatch_mode = str(getattr(cfg, "dispatch_mode", "direct"))
        self._range_chunks = max(1, int(getattr(cfg,
                                                "dispatch_range_chunks",
                                                16)))
        self._hier_degraded = False
        from fiber_tpu.health import CircuitBreaker

        #: Health plane (fiber_tpu/health.py). The detector is armed by
        #: ResilientPool only — a plain pool has no pending table, so a
        #: death declaration would have nothing to resubmit. The spawn
        #: breaker gates _maintain_workers: a refusing backend is
        #: retried on exponential backoff instead of every 0.2s tick
        #: (the terminal _SPAWN_FAIL_LIMIT escalation below remains).
        self._detector = None
        self._spawn_key = "spawn"
        self._spawn_breaker = CircuitBreaker(
            fail_threshold=int(cfg.spawn_breaker_threshold),
            base_backoff=float(cfg.spawn_breaker_backoff),
            max_backoff=float(cfg.spawn_breaker_backoff_max),
        )

        ip, _, _ = get_backend().get_listen_addr()
        self._task_ep = Endpoint("rep" if self._resilient else "w")
        self._task_addr = self._task_ep.bind(ip)
        self._result_ep = Endpoint("r")
        self._result_addr = self._result_ep.bind(ip)

        # By-reference data plane (fiber_tpu/store): args/results above
        # store_inline_max ride as ObjectRefs against this process's
        # store server. Failure to bring the store up only costs the
        # optimization — everything ships inline.
        self._store_inline_max = (
            int(cfg.store_inline_max) if cfg.store_enabled else 0
        )
        self._objstore = None
        self._store_server = None
        self._store_addr = None
        if self._store_inline_max > 0:
            try:
                from fiber_tpu import store as storemod

                self._store_server, self._store_addr = \
                    storemod.ensure_server(ip)
                self._objstore = self._store_server.store
            except Exception:  # noqa: BLE001
                logger.warning(
                    "object store unavailable; pool ships payloads "
                    "inline", exc_info=True)
                self._store_inline_max = 0
        #: seq -> (func_digest, func_blob, star, original items): kept
        #: while a ref-bearing map is in flight so a worker that cannot
        #: resolve a ref gets its chunk resent INLINE (storemiss path)
        #: instead of failing tasks.
        self._seq_ctx: Dict[int, Tuple] = {}
        self._seq_ctx_lock = threading.Lock()
        self._store_fallbacks = 0
        #: Durable-map ledger plane (docs/robustness.md): seq -> open
        #: MapLedger for maps submitted with job_id=. The result loop
        #: journals each completed chunk through it; resume restores
        #: journaled chunks without re-execution.
        self._ledgers: Dict[int, Any] = {}
        self._ledger_local = None   # fallback LocalStore when _objstore off
        self._ledger_last: Dict[str, Any] = {}
        self._n_restored = 0
        #: Streaming data plane (docs/streaming.md): seq -> live
        #: admission window in chunks (the policy plane's
        #: shrink_stream_window knob mutates it mid-stream), seq ->
        #: pre-shrink window for the owned revert, (seq, base) ->
        #: (raw chunk items, store digests) — the storemiss-resend
        #: source once the producer iterator has moved past the chunk,
        #: released as each chunk fills so it stays O(window). Stream
        #: seqs in _stream_lazy defer oversized-result resolution to
        #: yield time, so spilled results park in the store's tiers
        #: instead of master RAM.
        self._stream_windows: Dict[int, int] = {}
        self._stream_window_orig: Dict[int, int] = {}
        self._stream_ctx: Dict[Tuple[int, int], Tuple] = {}
        self._stream_lazy: set = set()
        self._stream_admit_waits = 0

        self._store = ResultStore()
        # Scheduler plane (fiber_tpu/sched, docs/scheduling.md): the
        # task queue IS the per-pool scheduler — items stay
        # (payload, (seq, base)) tuples and every existing requeue path
        # (death reclaim, storemiss resend, reply-failure) routes
        # through policy unchanged. Speculation only arms on the
        # resilient pool: it needs the pending table + dedup-on-fill
        # machinery that makes duplicate execution safe.
        #: ident -> host placement key self-reported in "ready" frames.
        self._ident_hosts: Dict[bytes, Optional[str]] = {}
        self._host_suspect_fn = getattr(get_backend(), "host_suspect",
                                        None)
        self._sched = Scheduler(
            n_workers=processes,
            policy=str(cfg.sched_policy),
            locality=bool(cfg.locality_enabled),
            speculation=bool(cfg.speculation_enabled) and self._resilient,
            speculation_quantile=float(cfg.speculation_quantile),
            is_done=self._store.is_done,
            on_new_work=self._on_sched_work,
        )
        self._taskq = self._sched

        self._workers: List = []
        self._workers_lock = threading.Lock()
        self._spawning_slots = 0   # sub-worker slots with spawns in flight
        self._spawn_fail_streak = 0  # consecutive failed worker starts
        self._last_spawn_error: Optional[str] = None
        self._reaped = False       # join() finished reaping; no late adds
        self._closed = False
        self._terminated = False
        self._workers_started = False
        self._pool_meta: Optional[Dict[str, Any]] = None

        # Continuous monitor plane (docs/observability.md): the sampler
        # pulls queue-depth/inflight through this probe each tick so
        # the time-series (and the watchdog's queue-growth rule) never
        # read a stale gauge. Registered unconditionally — with the
        # monitor off the probe list is simply never walked.
        from fiber_tpu.telemetry.timeseries import TIMESERIES

        self._monitor_probe = self._update_monitor_gauges
        TIMESERIES.add_probe(self._monitor_probe)

        # Policy plane (docs/observability.md "Autonomous operations"):
        # registering makes this pool's maps throttleable by billing
        # key when the accounting watchdog raises budget_exceeded.
        # Weak registration — the engine never pins a closed pool.
        try:
            from fiber_tpu.telemetry import policy as policymod

            policymod.register_pool(self)
        except Exception:  # noqa: BLE001 - observability, never fatal
            pass

        self._result_thread = threading.Thread(
            target=self._result_loop, name="fiber-pool-results", daemon=True
        )
        self._result_thread.start()
        self._task_thread = threading.Thread(
            target=self._task_loop, name="fiber-pool-tasks", daemon=True
        )
        self._task_thread.start()
        self._worker_thread: Optional[threading.Thread] = None

    # -- worker management (lazy) -----------------------------------------
    def _ensure_workers(self, func: Callable) -> None:
        hints = {
            k: v for k, v in get_meta(func).items() if k in ("cpu", "mem", "gpu")
        }
        if self._pool_meta is None:
            self._pool_meta = hints
        elif hints and hints != self._pool_meta:
            raise ValueError(
                "all functions used with one Pool must share resource meta "
                f"(pool started with {self._pool_meta}, got {hints})"
            )
        self._start_worker_thread()

    def _start_worker_thread(self) -> None:
        if self._workers_started:
            return
        self._workers_started = True
        self._worker_thread = threading.Thread(
            target=self._worker_loop, name="fiber-pool-workers", daemon=True
        )
        self._worker_thread.start()

    def _spawn_worker(self, n_local: int):
        from fiber_tpu.process import Process

        # Hierarchical dispatch needs a packed resilient job; after a
        # sub-master death the pool degrades new jobs to direct
        # per-worker dispatch (_hier_degraded) — the proven path.
        mode = ("hier" if (self._dispatch_mode == "hier"
                           and n_local > 1
                           and self._resilient
                           and not self._hier_degraded)
                else "direct")
        p = Process(
            target=pool_worker,
            args=(
                self._task_addr,
                self._result_addr,
                self._resilient,
                self._initializer,
                self._initargs,
                self._maxtasksperchild,
                n_local,
                getattr(self, "_ctl_addr", None),
                self._store_addr,
                mode,
            ),
            name=f"PoolWorker-{uuid.uuid4().hex[:8]}",
            daemon=True,
        )
        try:
            p.start()
            p._n_local = n_local
            with self._workers_lock:
                self._spawn_fail_streak = 0
                self._last_spawn_error = None
            self._spawn_breaker.record_success(self._spawn_key)
            return p
        except Exception as exc:
            logger.warning("pool worker start failed; will retry",
                           exc_info=True)
            with self._workers_lock:
                self._spawn_fail_streak += 1
                self._last_spawn_error = f"{type(exc).__name__}: {exc}"
            if self._spawn_breaker.record_failure(self._spawn_key):
                logger.warning(
                    "pool: spawn breaker OPEN for %r after repeated "
                    "start failures; backing off", self._spawn_key)
            return None

    def _worker_loop(self) -> None:
        """Maintain the worker population; reap the dead, start missing
        (reference: fiber/pool.py:975-1082). Keeps running through a
        close() drain so deaths mid-drain are still repaired."""
        while not self._terminated and (
            not self._closed or self._store.outstanding() > 0
        ):
            self._maintain_workers()
            time.sleep(0.2)

    def _draining_done(self) -> bool:
        return self._closed and self._store.outstanding() == 0

    def _maintain_workers(self) -> None:
        with self._workers_lock:
            dead = [p for p in self._workers if p is not None and not p.is_alive()]
            for p in dead:
                self._workers.remove(p)
                self._on_worker_death(p)
            # Sub-worker slots still covered by live jobs (plus spawns in
            # flight); jobs pack cpu_per_job sub-workers each, the last
            # one the remainder.
            covered = (
                sum(getattr(p, "_n_local", 1) for p in self._workers)
                + self._spawning_slots
            )
        missing_subs = self._n_workers - covered
        if missing_subs <= 0:
            return
        # Respawning continues through a close() drain (resubmitted chunks
        # need somewhere to run) and stops only once drained.
        if self._terminated or self._draining_done():
            return
        # Breaker open: the target refused spawns repeatedly — skip this
        # tick instead of hammering it; the open period (exponential
        # backoff + jitter) is the retry schedule. The escalation check
        # below already ran in the tick that opened the breaker, so a
        # ripe streak can never be stranded behind an open breaker.
        if not self._spawn_breaker.allow(self._spawn_key):
            return
        plan = []
        while missing_subs > 0:
            n_local = min(self._cpu_per_job, missing_subs)
            plan.append(n_local)
            missing_subs -= n_local
        # Spawn concurrently: worker launch is ~1s of interpreter boot +
        # handshake each, and serial spawn would put that on the critical
        # path of the first map. Each thread registers (or reaps) its own
        # worker, so a spawn outliving the pacing join below can never
        # leave an untracked live process, and a terminate() that raced
        # the spawn reaps it immediately.
        with self._workers_lock:
            self._spawning_slots += sum(plan)

        def spawn_one(n_local: int) -> None:
            try:
                p = self._spawn_worker(n_local)
            except BaseException:
                p = None
            finally:
                with self._workers_lock:
                    self._spawning_slots -= n_local
            if p is None:
                return
            with self._workers_lock:
                if not self._terminated and not self._reaped:
                    self._workers.append(p)
                    return
            # Stragglers that finished after terminate()/join() reaped the
            # pool are shut down immediately, never left untracked.
            p.terminate()

        threads = [
            threading.Thread(target=spawn_one, args=(n,), daemon=True)
            for n in plan
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        # Escalation: transient start failures are retried forever with
        # live workers still draining the queue, but a backend that has
        # refused EVERY start since the last success — with zero workers
        # alive to make progress — is a permanent condition (bad image,
        # unsatisfiable reservation): fail pending maps loudly rather
        # than hang them. Streak threshold comfortably exceeds the
        # transient-failure fault-injection the suite pins
        # (TimeoutBackend-style: a few failures, then success).
        with self._workers_lock:
            streak = self._spawn_fail_streak
            alive = any(p.is_alive() for p in self._workers)
            last_err = self._last_spawn_error
        if streak >= _SPAWN_FAIL_LIMIT and not alive \
                and self._store.outstanding() > 0:
            logger.error(
                "pool: %d consecutive worker start failures with no live "
                "workers; failing pending work (last error: %s)",
                streak, last_err,
            )
            self._store.abort_all(
                WorkerStartError(
                    f"workers could not be started after {streak} "
                    f"consecutive attempts (last error: {last_err})"
                ),
                reason="worker start failure",
                direct=True,
            )

    def _on_worker_death(self, proc) -> None:
        logger.debug("pool worker %s died", proc.name)

    def resize(self, processes: int) -> int:
        """Retarget the worker count in place — the serve tier's warm
        pool (docs/serving.md) scales one long-lived pool elastically
        instead of paying cold spawn per tenant.

        Scale-UP spawns immediately (and starts the maintain loop if no
        map has run yet, so standby capacity is warm BEFORE the first
        chunk needs it). Scale-DOWN terminates excess workers without
        touching the books: the maintain loop's existing dead-sweep
        observes the exits and runs the normal death path — for the
        resilient pool that reclaims + resubmits anything a victim
        still owed, so callers that scale down under load degrade to a
        resubmit, never a loss (callers are expected to scale down only
        when idle anyway). Returns the new target."""
        target = max(1, int(processes))
        victims = []
        with self._workers_lock:
            self._n_workers = target
            covered = (
                sum(getattr(p, "_n_local", 1) for p in self._workers)
                + self._spawning_slots
            )
            excess = covered - target
            if excess > 0:
                for p in self._workers:
                    if excess <= 0:
                        break
                    n_local = getattr(p, "_n_local", 1)
                    if n_local > excess:
                        continue  # would overshoot below the target
                    victims.append(p)
                    excess -= n_local
        self._sched.set_n_workers(target)
        for p in victims:
            try:
                p.terminate()
            except Exception:  # noqa: BLE001 - already-dead is fine
                pass
        if not self._closed and not self._terminated:
            self._start_worker_thread()
            self._maintain_workers()
        return target

    # -- scheduler plane hooks (fiber_tpu/sched) ---------------------------
    def _on_sched_work(self) -> None:
        """The speculation monitor queued a duplicate: parked requests'
        reservation gates may now clear — nudge the handout loop (same
        posture as the submit/result-side wake twins)."""
        if getattr(self, "_parked_count", 0):
            try:
                self._task_ep.wake()
            except (TransportClosed, OSError):
                pass

    def _suspect_defers(self, ident: bytes) -> bool:
        """Health-plane placement input: True when this requester's host
        is currently suspect (backend failure detector / open spawn
        breaker) AND healthier workers exist AND work is scarce enough
        that giving the suspect host a chunk risks stranding it. With
        chunks plentiful even a suspect host helps; with every host
        suspect, serving beats a placement deadlock."""
        fn = self._host_suspect_fn
        if fn is None:
            return False
        host = self._ident_hosts.get(ident)
        if host is None:
            return False
        try:
            if not fn(host):
                return False
        except Exception:  # noqa: BLE001 - health probe must never wedge
            return False
        if self._taskq.qsize() > self._n_workers:
            return False
        for other_host in self._ident_hosts.values():
            if other_host is None or other_host == host:
                continue
            try:
                if not fn(other_host):
                    FLIGHT.record(
                        "sched", "park", ident=ident.hex()[:8],
                        host=host,
                        reason="host suspect while healthier workers "
                               "exist and work is scarce")
                    return True
            except Exception:  # noqa: BLE001
                continue
        return False

    # -- accounting plane (docs/observability.md "Resource accounting") ----
    def _bill_frame(self, seq: Optional[int], tx: int = 0, rx: int = 0,
                    dispatch_s: float = 0.0,
                    bkey: Optional[Tuple] = None) -> None:
        """Bill one pool frame's wire bytes (payload length -> framing
        wire size) and optional dispatch seconds to its map — by
        ``seq`` (the master's seq -> key table), by an explicit
        worker-tagged ``bkey``, or to the overhead bucket when neither
        attributes it (heartbeats, frames of completed maps). The
        master is the authoritative wire observation point: every pool
        frame crosses its endpoints exactly once."""
        if not COSTS.enabled:
            return
        key = tuple(bkey) if bkey else (
            self._seq_bill.get(seq) if seq is not None else None)
        fields: Dict[str, float] = {}
        if tx:
            fields["wire_tx"] = accounting.wire_size(tx)
        if rx:
            fields["wire_rx"] = accounting.wire_size(rx)
        if dispatch_s:
            fields["dispatch_s"] = dispatch_s
        if fields:
            COSTS.charge(key, **fields)

    # -- task egress -------------------------------------------------------
    def _task_loop(self) -> None:
        """Move tasks from the local queue onto the wire with explicit
        flow control (reference hot loop: fiber/pool.py:952-963)."""
        while True:
            item = self._taskq.get()
            _g_queue_depth.set(self._taskq.qsize())
            if item is None:
                return
            payload, _key = item
            # Backpressure waits on the store's condition (woken by
            # every completion) instead of a 10ms poll; the timeout
            # only bounds how long a terminate() can go unnoticed.
            waited_t0 = None
            while not self._store.wait_outstanding_below(
                    MAX_INFLIGHT_TASKS, timeout=0.5):
                if waited_t0 is None:
                    waited_t0 = time.perf_counter()
                if self._terminated:
                    return
            if waited_t0 is not None:
                _m_backpressure_waits.inc()
                FLIGHT.record(
                    "pool", "backpressure", seq=item[1][0],
                    wait_s=round(time.perf_counter() - waited_t0, 4))
            while True:
                if self._terminated:
                    return
                try:
                    t0 = time.perf_counter()
                    self._task_ep.send(payload, timeout=1.0)
                    # add(), not section(): a timed-out send retry is a
                    # wait for peers, not dispatch cost — only the
                    # successful handout is recorded.
                    global_timer.add("pool.dispatch",
                                     time.perf_counter() - t0)
                    self._bill_frame(item[1][0], tx=len(payload),
                                     dispatch_s=time.perf_counter() - t0)
                    _m_chunks_dispatched.inc()
                    if FLIGHT.enabled:
                        FLIGHT.record("pool", "dispatch",
                                      seq=item[1][0], base=item[1][1])
                    break
                except TimeoutError:
                    continue
                except (TransportClosed, OSError):
                    return

    def _result_loop(self) -> None:
        while True:
            try:
                data = self._result_ep.recv()
            except (TransportClosed, OSError):
                return
            # A malformed frame must not kill the loop — that silently
            # hangs every outstanding .get() (advisor, round 1).
            try:
                with global_timer.section("pool.deserialize"):
                    msg = serialization.loads(data)
                detector = self._detector
                if msg[0] == "hb":
                    if detector is not None:
                        detector.beat(msg[1])
                    # Heartbeats are traffic no map causes: the
                    # explicit overhead bucket.
                    self._bill_frame(None, rx=len(data))
                    continue
                if msg[0] == "spans":
                    # Worker-side trace spans riding the result stream
                    # (same transport posture as heartbeats): fold them
                    # into the master's ring buffer, where trace_dump
                    # assembles the cluster-wide timeline. The optional
                    # 4th field is the causing chunk's billing key.
                    if detector is not None:
                        detector.beat(msg[1])
                    tracing.SPANS.add_all(msg[2])
                    self._bill_frame(None, rx=len(data),
                                     bkey=msg[3] if len(msg) > 3 else None)
                    continue
                if msg[0] == "prof":
                    # Worker-side sampling-profiler stacks (same
                    # posture as spans): merge into the master's
                    # cluster aggregate, keyed by the worker's
                    # host:pid label (Pool.profile_dump renders it).
                    ident, label, folded = msg[1], msg[2], msg[3]
                    if detector is not None:
                        detector.beat(ident)
                    from fiber_tpu.telemetry.profiler import AGGREGATE

                    AGGREGATE.merge(label, folded)
                    self._bill_frame(None, rx=len(data),
                                     bkey=msg[4] if len(msg) > 4 else None)
                    continue
                if msg[0] == "dev":
                    # Worker-side device-telemetry snapshots (transfer
                    # accounting, compiles — docs/observability.md
                    # "Device telemetry"): cumulative per worker, so
                    # latest wins; Pool.device_stats() renders them.
                    ident, label, snap = msg[1], msg[2], msg[3]
                    if detector is not None:
                        detector.beat(ident)
                    self._device_workers[str(label)] = snap
                    self._bill_frame(None, rx=len(data),
                                     bkey=msg[4] if len(msg) > 4 else None)
                    continue
                if msg[0] == "cost":
                    # Worker cost frames (accounting plane): cumulative
                    # per worker, latest wins; Pool.cost() merges them
                    # over the master's own ledger. Their own wire cost
                    # is accounting traffic -> overhead.
                    ident, label, snap = msg[1], msg[2], msg[3]
                    if detector is not None:
                        detector.beat(ident)
                    self._on_cost_frame(str(label), snap)
                    self._bill_frame(None, rx=len(data))
                    continue
                if msg[0] == "storemiss":
                    _, seq, base, n, ident = msg
                    if detector is not None:
                        detector.beat(ident)  # a report proves liveness
                    self._bill_frame(seq, rx=len(data))
                    self._on_store_miss(seq, base, n, ident)
                    continue
                if msg[0] == "fbatch":
                    # Children's per-chunk telemetry ("spans"/"prof"/
                    # "dev"/"cost"), batched by a per-host sub-master so
                    # master ingress scales with hosts rather than
                    # chunks. The outer frame's wire cost bills once as
                    # overhead; the inner messages carried no wire of
                    # their own (billed wire must still equal endpoint
                    # counters for Pool.cost() reconciliation).
                    _, raws, ident = msg
                    if detector is not None:
                        detector.beat(ident)
                    self._bill_frame(None, rx=len(data))
                    for raw in raws:
                        try:
                            inner = serialization.loads(raw)
                            k = inner[0]
                            if k == "spans":
                                tracing.SPANS.add_all(inner[2])
                            elif k == "prof":
                                from fiber_tpu.telemetry.profiler import (
                                    AGGREGATE)

                                AGGREGATE.merge(inner[2], inner[3])
                            elif k == "dev":
                                self._device_workers[str(inner[2])] = (
                                    inner[3])
                            elif k == "cost":
                                self._on_cost_frame(str(inner[2]),
                                                    inner[3])
                        except Exception:
                            logger.exception(
                                "pool: dropping malformed fbatch entry")
                    continue
                if msg[0] == "rbatch":
                    # Aggregated results from a per-host sub-master
                    # (hierarchical dispatch): one frame, many chunks.
                    # Billed ONCE against the first chunk's map — billed
                    # wire must equal actual wire for Pool.cost()
                    # reconciliation.
                    _, entries, ident = msg
                    if detector is not None:
                        detector.beat(ident)
                    self._bill_frame(entries[0][0] if entries else None,
                                     rx=len(data))
                    for seq, base, values in entries:
                        if (seq not in self._stream_lazy
                                and any(isinstance(v, ObjectRef)
                                        for v in values)):
                            with global_timer.section(
                                    "pool.store_resolve"):
                                values = self._resolve_result_refs(
                                    values)
                        self._n_completed += len(values)
                        _m_tasks_completed.inc(len(values))
                        self._on_result(seq, base, values, ident)
                        if self._ledgers:
                            self._journal_chunk(seq, base, values)
                        bill_key = (self._seq_bill.get(seq)
                                    if COSTS.enabled else None)
                        newly = self._store.fill(seq, base, values)
                        if newly and bill_key is not None:
                            COSTS.charge(bill_key, tasks=newly)
                        if self._stream_windows:
                            self._release_stream_chunk(seq, base)
                    _g_inflight.set(self._store.outstanding())
                    continue
                if msg[0] != "result":
                    continue
                _, seq, base, values, ident = msg
                if detector is not None:
                    # Results prove liveness as well as any beat: a
                    # worker mid-long-GIL-hold may miss beats while
                    # still making progress, and progress must never
                    # read as death.
                    detector.beat(ident)
                self._bill_frame(seq, rx=len(data))
                if (seq not in self._stream_lazy
                        and any(isinstance(v, ObjectRef)
                                for v in values)):
                    # Stream seqs without a journal skip the eager
                    # resolve: the refs stay in the store's RAM/disk
                    # tiers (which spill under pressure) and resolve at
                    # YIELD time — incremental result spill, master RAM
                    # stays O(window) even with oversized results.
                    with global_timer.section("pool.store_resolve"):
                        values = self._resolve_result_refs(values)
                self._n_completed += len(values)
                _m_tasks_completed.inc(len(values))
                self._on_result(seq, base, values, ident)
                if self._ledgers:
                    # Durable maps: one buffered append on this hot
                    # loop; the ledger's writer thread owns the
                    # serialize + disk persist + fsync.
                    self._journal_chunk(seq, base, values)
                # Billing key captured BEFORE the fill: the fill that
                # completes the map fires the completion callbacks
                # (which seal and release the key) synchronously, and
                # the final chunk's tasks must still bill.
                bill_key = (self._seq_bill.get(seq) if COSTS.enabled
                            else None)
                newly = self._store.fill(seq, base, values)
                if newly and bill_key is not None:
                    # Exactly-once task billing: the first fill of each
                    # slot bills it; a speculation duplicate or
                    # death/storemiss resubmit fills nothing new and
                    # bills nothing.
                    COSTS.charge(bill_key, tasks=newly)
                if self._stream_windows:
                    # A filled stream chunk's raw-items context (and its
                    # encoded-arg store refs) are dead weight: release
                    # now, not at stream end — O(window) master state.
                    self._release_stream_chunk(seq, base)
                _g_inflight.set(self._store.outstanding())
            except Exception:
                logger.exception("pool: dropping malformed result frame")

    def _on_result(self, seq, base, values, ident) -> None:
        pass

    # -- by-reference payloads (fiber_tpu/store) ---------------------------
    def _encode_items(self, items: List[Any], seq_digests: List[str],
                      bkey=None, device_hint: bool = False) -> List[Any]:
        """Replace large args with ObjectRefs (top level and one tuple
        level deep, which covers map-over-tuples and starmap). The memo
        keys on object identity so the classic broadcast pattern — the
        same params object in every item — is hashed and stored ONCE
        per map, not once per task. ``bkey`` bills each stored payload
        to the submitting map (accounting plane); ``device_hint`` makes
        refs SHARED across items (the broadcast idiom, detected via the
        memo) device-destined so resolving workers route them through
        the shared device tier (one H2D per host per digest). Per-item
        payloads never get the hint: mesh-replicating every distinct
        item would cost n_dev x HBM per item and churn the tier's LRU
        out of the actual broadcast params."""
        memo: Dict[int, Tuple[Any, Any]] = {}
        return [self._encode_item(it, memo, seq_digests, bkey,
                                  device_hint)
                for it in items]

    def _encode_item(self, item, memo, seq_digests, bkey=None,
                     device_hint: bool = False):
        if type(item) is tuple:
            return tuple(self._encode_obj(e, memo, seq_digests, bkey,
                                          device_hint)
                         for e in item)
        return self._encode_obj(item, memo, seq_digests, bkey,
                                device_hint)

    def _encode_obj(self, obj, memo, seq_digests, bkey=None,
                    device_hint: bool = False):
        if isinstance(obj, ObjectRef):
            return obj  # user pre-put it; ships as-is
        key = id(obj)
        hit = memo.get(key)
        if hit is not None:
            enc = hit[1]
            if device_hint and isinstance(enc, ObjectRef) \
                    and not enc.device_hint:
                # Second sighting of the same object: this ref is a
                # broadcast shared across items, the only shape worth
                # mesh replication. One shared instance rides every
                # item, so flipping it here marks them all (chunks are
                # serialized after encoding finishes).
                enc.device_hint = True
            return enc
        hint = _payload_size_hint(obj)
        if hint is not None and hint <= self._store_inline_max:
            return obj
        try:
            data = serialization.dumps(obj)
        except Exception:  # noqa: BLE001
            return obj  # let the inline path raise the real error
        if len(data) <= self._store_inline_max:
            memo[key] = (obj, obj)
            return obj
        ref = self._objstore.put_bytes(data, refs=1,
                                       owner=self._store_addr)
        seq_digests.append(ref.digest)
        if bkey is not None:
            COSTS.charge(bkey, store_put_bytes=len(data))
        # The memo holds the original object alive so its id() cannot
        # be recycled mid-encode.
        memo[key] = (obj, ref)
        return ref

    def _arm_store_fallback(self, seq, digest, blob, star, items,
                            seq_digests, tctx, bkey=None) -> None:
        """Keep enough context to resend any chunk inline (storemiss),
        and release the map's store refs when it completes (success,
        failure or abort — completion callbacks fire on all three)."""
        with self._seq_ctx_lock:
            self._seq_ctx[seq] = (digest, blob, star, items, tctx, bkey)
        # The active broadcast is precious while the map is in flight:
        # the replication hook copies it off a suspect host so recovery
        # (and late locality fetches) never need the dead one.
        from fiber_tpu.store.replicate import REPLICATOR

        REPLICATOR.note(seq_digests)

        def _cleanup() -> None:
            with self._seq_ctx_lock:
                self._seq_ctx.pop(seq, None)
            REPLICATOR.forget(seq_digests)
            for d in seq_digests:
                self._objstore.release(d)

        self._store.add_callback(seq, _cleanup)

    def _probe_ref_locations(self, digests: List[str]) -> None:
        """Ask the backend which hosts already cache these objects
        (host-agent ``store_has``, the path ``put_object`` prestages
        through) and feed the scheduler's locality map. Bounded to a
        handful of digests per map and entirely best-effort: a slow or
        dead agent costs the optimization, never the submit."""
        if not self._sched.locality:
            return
        from fiber_tpu.backends import get_backend

        locate = getattr(get_backend(), "locate_object", None)
        if locate is None:
            return
        for dig in list(dict.fromkeys(digests))[:4]:
            try:
                for host in locate(dig):
                    self._sched.note_host_has(host, (dig,))
            except Exception:  # noqa: BLE001 - locality is optional
                return

    def _on_store_miss(self, seq, base, n, ident) -> None:
        """A worker could not resolve this chunk's refs (store down,
        object evicted unspilled, injected chaos): resend the chunk
        with INLINE payloads. Dedup on fill makes double delivery
        harmless; a done map is simply dropped."""
        with self._seq_ctx_lock:
            ctx = self._seq_ctx.get(seq)
        if ctx is None or self._store.is_done(seq):
            return
        fdigest, blob, star, items, tctx, bkey = ctx
        if items is None:
            # Stream: the source iterator moved on long ago; the
            # per-chunk context table holds the only raw-items copy
            # (released when the chunk fills — a filled chunk never
            # storemisses meaningfully, dedup drops the resend).
            with self._seq_ctx_lock:
                sctx = self._stream_ctx.get((seq, base))
            if sctx is None:
                return
            chunk = sctx[0][:n]
        else:
            chunk = items[base:base + n]
        # Same trace context (and billing key) as the original handout:
        # the inline resend is one more hop of the same logical task,
        # not a new trace — and its duplicate wire bytes bill to the
        # map that caused them.
        payload = serialization.dumps(
            ("task", seq, base, fdigest, blob, chunk, star, tctx, bkey)
        )
        self._store_fallbacks += 1
        _m_store_fallbacks.inc()
        FLIGHT.record("store", "storemiss", seq=seq, base=base,
                      ident=ident.hex()[:8],
                      reason="worker could not resolve refs; "
                             "resending inline")
        logger.warning(
            "store: worker %s could not resolve refs (seq=%d base=%d); "
            "resending chunk inline", ident.hex()[:8], seq, base)
        self._taskq.put((payload, (seq, base)))

    def _resolve_result_refs(self, values: List[Any]) -> List[Any]:
        """Master-side resolution of by-reference results: this process
        owns the store the workers pushed to, so resolution is a local
        read + lifecycle release. A missing/corrupt object fails ONLY
        the affected slot, catchably."""
        out = []
        for v in values:
            if not isinstance(v, ObjectRef):
                out.append(v)
                continue
            data = (self._objstore.get_bytes(v.digest)
                    if self._objstore is not None else None)
            if data is None:
                out.append(_Failure(
                    StoreFetchError(
                        f"result object {v.digest[:12]} missing from "
                        "the master store"), "", direct=True))
                continue
            try:
                out.append(serialization.loads(data))
            except Exception as err:  # noqa: BLE001
                out.append(_Failure(err, traceback.format_exc(),
                                    direct=True))
            finally:
                self._objstore.release(v.digest)
        return out

    # -- durable maps (fiber_tpu/store/ledger, docs/robustness.md) ---------
    def _ledger_store(self):
        """Store the journaled result payloads persist into: the pool's
        own object store when the by-reference plane is up, else the
        process LocalStore (its disk tier works regardless — durability
        must not depend on the wire plane being enabled)."""
        if self._objstore is not None:
            return self._objstore
        if self._ledger_local is None:
            from fiber_tpu import store as storemod

            self._ledger_local = storemod.local_store()
        return self._ledger_local

    def _ledger_open(self, job_id: str, func: Callable, items: List[Any],
                     chunksize: int, star: bool,
                     trace_id: Optional[str]):
        """Open (or resume) the job's write-ahead ledger. Returns
        ``(ledger|None, completed, chunksize, trace_id)`` — on resume
        the recorded chunking and trace id override the caller's, so
        chunk spans line up with the journal and resubmitted chunks
        keep their trace (envelope-reuse rule)."""
        from fiber_tpu import config as _config
        from fiber_tpu.store import ledger as ledgermod
        from fiber_tpu.store.replicate import REPLICATOR

        cfg = _config.get()
        if not bool(cfg.ledger_enabled):
            return None, {}, chunksize, trace_id
        path = ledgermod.job_path(job_id)
        tdigest = ledgermod.task_digest(func, len(items), star)
        store = self._ledger_store()
        fsync_s = float(cfg.ledger_fsync_s)

        def note_chunk(digest: str) -> None:
            # Journaled results are PRECIOUS: the replication hook
            # copies them off a suspect host (docs/robustness.md).
            REPLICATOR.note((digest,))

        if os.path.exists(path):
            try:
                header, completed, _done = ledgermod.load(path)
            except ValueError:
                # A crash between file creation and the header fsync
                # leaves a headerless file: nothing was dispatched under
                # it, so the job simply starts fresh (appending — load
                # skips any torn garbage before the new header).
                logger.warning("ledger: %s has no readable header; "
                               "starting job %r fresh", path, job_id)
                header = None
            if header is not None:
                if header.get("task_digest") != tdigest:
                    raise ValueError(
                        f"job_id {job_id!r} was journaled by a "
                        "different task spec (function / item count / "
                        "call shape changed); pick a new job_id or "
                        f"delete {path}")
                chunksize = int(header.get("chunksize") or chunksize)
                led = ledgermod.MapLedger(path, store,
                                          fsync_interval=fsync_s,
                                          on_chunk=note_chunk)
                led.adopt(completed)
                REPLICATOR.note(d for _, d in completed.values())
                if header.get("trace") and trace_id is not None:
                    trace_id = str(header["trace"])
                FLIGHT.record("store", "ledger", job=job_id,
                              event="resume", completed=len(completed))
                return led, completed, chunksize, trace_id
        led = ledgermod.MapLedger(path, store, fsync_interval=fsync_s,
                                  on_chunk=note_chunk)
        spec_digest = None
        try:
            # Resumable spec payload: `fiber-tpu resume <job_id>` runs
            # from a dead master's ledger alone, so the call itself must
            # be reconstructible. The function is cloudpickled BY VALUE:
            # a plain pickle of a `__main__`-defined function is a
            # by-reference pointer only the dead master's re-imported
            # main module could resolve — the resume CLI is a different
            # __main__. Persisted to the disk tier like the chunk
            # payloads; an unpicklable spec only loses the CLI path
            # (re-calling map with the job_id still resumes).
            try:
                import cloudpickle as _cp

                func_blob = _cp.dumps(func)
            except Exception:  # noqa: BLE001 - no cloudpickle / exotic fn
                func_blob = serialization.dumps(func)
            spec_data = serialization.dumps(
                (func_blob, list(items), bool(star), int(chunksize)))
            spec_digest = store.put_bytes(
                spec_data, refs=1, persist=True).digest
        except Exception:  # noqa: BLE001
            logger.warning(
                "ledger: spec payload for job %r not serializable; "
                "`fiber-tpu resume` needs the original call site",
                job_id, exc_info=True)
        led.write_header({
            "job_id": job_id, "task_digest": tdigest,
            "spec": spec_digest, "n_items": len(items),
            "chunksize": int(chunksize), "star": bool(star),
            "trace": trace_id,
        })
        return led, {}, chunksize, trace_id

    def _ledger_restore_all(self, job_id,
                            completed) -> Dict[int, List[Any]]:
        """Fetch every journaled chunk's result values; a payload lost
        from every tier just re-executes its chunk (lineage posture:
        recompute only what was lost)."""
        out: Dict[int, List[Any]] = {}
        for base, (n, digest) in completed.items():
            values = self._ledger_restore(digest, n)
            if values is None:
                logger.warning(
                    "ledger: job %r chunk base=%d payload %s lost from "
                    "every store tier; re-executing that chunk",
                    job_id, base, digest[:12])
                FLIGHT.record("store", "ledger", job=job_id,
                              event="lost", base=base, digest=digest[:8])
                continue
            out[base] = values
        return out

    def _ledger_restore(self, digest: str,
                        n: int) -> Optional[List[Any]]:
        store = self._ledger_store()
        data = store.get_bytes(digest)
        if data is None:
            # Master disk lost the payload (new machine, wiped staging):
            # the per-host caches are the second line — exactly what the
            # suspect-time replication hook keeps populated.
            from fiber_tpu.backends import get_backend

            fetch = getattr(get_backend(), "fetch_object", None)
            if fetch is not None:
                try:
                    data = fetch(digest)
                except Exception:  # noqa: BLE001
                    data = None
            if data is not None:
                try:  # republish so the next resume reads local disk
                    store.put_bytes(data, persist=True, digest=digest)
                except Exception:  # noqa: BLE001
                    pass
        if data is None:
            return None
        try:
            values = serialization.loads(data)
        except Exception:  # noqa: BLE001 - corrupt payload == lost
            return None
        if not isinstance(values, list) or len(values) != n:
            return None
        return values

    def _journal_chunk(self, seq: int, base: int,
                       values: List[Any]) -> None:
        led = self._ledgers.get(seq)
        if led is None or led.has(base):
            return
        if any(isinstance(v, _Failure) for v in values):
            # Failed slots are not completions: the chunk re-executes on
            # resume (idempotent tasks; a deterministic failure simply
            # fails again, visibly).
            return
        led.record_chunk(base, len(values), values)

    def _ledger_done(self, seq: int) -> None:
        """Map completion: close the journal with a ``done`` record and
        release the job's precious-digest registrations."""
        led = self._ledgers.pop(seq, None)
        if led is None:
            return
        from fiber_tpu.store.replicate import REPLICATOR

        led.record_done()
        led.close()
        REPLICATOR.forget(led.digests)

    def ledger_stats(self) -> Dict[str, Any]:
        """Durability counters: the last job_id map's restore/pending
        split (the exactly-once proof surface — restored + executed ==
        total), lifetime restored-task count, and the replication
        registry snapshot."""
        from fiber_tpu.store.replicate import REPLICATOR

        out = dict(self._ledger_last)
        out["tasks_restored_total"] = self._n_restored
        out["active_ledgers"] = len(self._ledgers)
        out["replication"] = REPLICATOR.snapshot()
        return out

    def put_object(self, obj: Any) -> ObjectRef:
        """Explicitly stage one object in the pool's store and get the
        ref back: pass it (alone, or inside arg tuples) to any map/apply
        and workers resolve it through the per-host cache. For payloads
        the automatic threshold already catches this is redundant — it
        exists for pinning very hot broadcasts across many maps without
        re-probing, and for sub-threshold objects you still want
        deduplicated. Held for the pool's lifetime (spilled, not
        dropped, under memory pressure)."""
        if self._objstore is None:
            raise ValueError(
                "object store is disabled (store_enabled=False or "
                "store_inline_max=0)")
        return self._objstore.put(obj, refs=1, owner=self._store_addr)

    def store_stats(self) -> Dict[str, Any]:
        """Operator counters for the by-reference plane (exposed next to
        the backend's host_health): hit/miss/bytes from this process's
        store server plus the pool's inline-fallback count."""
        out: Dict[str, Any] = {
            "enabled": self._objstore is not None,
            "inline_fallbacks": self._store_fallbacks,
        }
        if self._store_server is not None:
            out.update(self._store_server.stats())
        return out

    # -- accounting plane read side ----------------------------------------
    def _on_cost_frame(self, label: str, snap: dict) -> None:
        """One worker's cumulative cost snapshot landed: latest wins per
        worker. Budgets re-check with the worker-observed fields merged
        in (cpu_s lives only on workers), and persisted per-job records
        of already-completed jobs the frame touches are refreshed — the
        final chunk's cost frame always lands AFTER the last result."""
        self._cost_workers[label] = snap
        if not COSTS.enabled:
            return
        workers = accounting.merge_worker_costs(self._cost_workers)
        for kstr in (snap.get("costs") or {}):
            key = accounting.parse_key(kstr)
            if key[2] == "overhead":
                continue
            COSTS.check_budget(key, extra=workers.get(kstr))
            job_id = self._job_records.get(key)
            if job_id is not None:
                accounting.write_job_record(job_id,
                                            self._cost_report_for(key))

    def _cost_report_for(self, key) -> Dict[str, Any]:
        kstr = accounting.key_str(key)
        workers = accounting.merge_worker_costs(self._cost_workers)
        return accounting.build_report(
            key, COSTS.vector(key), workers.get(kstr, {}),
            self._map_budgets.get(tuple(key)))

    def _finish_billing(self, seq: int, job_id, ledger, budget) -> None:
        """Map completion (success, failure or abort): seal the map's
        cost — wall clock, final ledger disk bytes — release its budget
        state and per-job metric label slots, and persist the per-job
        cost record beside the PR-7 ledger when the map was durable."""
        key = self._seq_bill.pop(seq, None)
        if key is None:
            return
        t0 = self._map_wall0.pop(seq, None)
        if t0 is not None:
            COSTS.charge(key, wall_s=time.perf_counter() - t0)
        if ledger is not None:
            COSTS.charge(key, ledger_bytes=ledger.bytes_written)
        COSTS.release_key(key)
        if job_id is not None:
            # Remembered (bounded) so a cost frame landing after the
            # last result still refreshes the record (_on_cost_frame).
            self._job_records[key] = job_id
            while len(self._job_records) > 16:
                self._job_records.pop(next(iter(self._job_records)))
            accounting.write_job_record(job_id,
                                        self._cost_report_for(key))

    def throttle_billing_key(self, key, factor: float = 4.0) -> int:
        """Cut the WDRR weight of every in-flight map billed to
        ``key`` (a (tenant, job, map) tuple — the policy plane's
        budget_exceeded remediation). The maps keep progressing at the
        scheduler's 0.25 weight floor; they just stop crowding out
        in-budget tenants. Returns how many maps were throttled."""
        key = tuple(key)
        seqs = [seq for seq, bk in list(self._seq_bill.items())
                if bk == key]
        return sum(1 for seq in seqs
                   if self._sched.throttle_map(seq, factor))

    def unthrottle_billing_key(self, key) -> int:
        """Restore the original weights (budget anomaly's clear-edge
        revert). Maps that completed meanwhile already restored via
        release_map; this covers the ones still running."""
        key = tuple(key)
        seqs = [seq for seq, bk in list(self._seq_bill.items())
                if bk == key]
        return sum(1 for seq in seqs
                   if self._sched.unthrottle_map(seq))

    def preempt_map(self, seq: int) -> bool:
        """Stop one in-flight map NOW, keeping it resumable (the serve
        tier's budget-enforcement escalation past WDRR throttling,
        docs/serving.md). Order matters:

        1. pop the ledger and close it WITHOUT a ``done`` record — the
           journal keeps every chunk completed so far, and the missing
           ``done`` is exactly what makes ``fiber-tpu resume`` (and the
           serve daemon's replay) pick the job back up;
        2. fail the map's unset slots with :class:`JobPreemptedError` —
           the completion callbacks this fires do the actual reclaim:
           ``release_map`` drops the map's queued AND in-flight chunks
           from the scheduler (late results for a released seq are
           already ignored), ``_ledger_done`` no-ops (ledger popped in
           step 1), ``_finish_billing`` seals and persists the cost
           record so the tenant is billed for what actually ran.

        Returns False when ``seq`` already completed (nothing to do)."""
        if self._store.is_done(seq):
            return False
        led = self._ledgers.pop(seq, None)
        if led is not None:
            from fiber_tpu.store.replicate import REPLICATOR

            led.close()
            REPLICATOR.forget(led.digests)
        self._store.fail(
            seq,
            JobPreemptedError(
                f"map seq={seq} preempted by the serve tier "
                "(budget enforcement); journaled progress kept — "
                "resumable via `fiber-tpu resume`"),
            reason="preempted", direct=True)
        return True

    def preempt_billing_key(self, key) -> int:
        """Preempt every in-flight map billed to ``key`` (a
        ``(tenant, job, map)`` tuple). Returns how many maps were
        actually stopped."""
        key = tuple(key)
        seqs = [seq for seq, bk in list(self._seq_bill.items())
                if bk == key]
        return sum(1 for seq in seqs if self.preempt_map(seq))

    def preempt_job(self, job_id: str) -> int:
        """Preempt every in-flight map billed to ``job_id`` regardless
        of tenant/map component. Returns how many maps were stopped."""
        seqs = [seq for seq, bk in list(self._seq_bill.items())
                if len(bk) >= 2 and bk[1] == job_id]
        return sum(1 for seq in seqs if self.preempt_map(seq))

    def cost(self, job_id: Optional[str] = None) -> Dict[str, Any]:
        """Per-map/per-tenant CostReports (docs/observability.md
        "Resource accounting"): the process cost ledger's keys merged
        with every worker's shipped cost frames, each field taken from
        its authoritative observation point (wire/tasks: master;
        cpu/store-fetch/device-transfer: workers). ``job_id=`` filters
        to that job's maps and adds an aggregated ``job`` summary.
        The ``overhead`` buckets (master and workers) are explicit —
        per-key wire bytes + overhead always sum to ``totals``."""
        snap = COSTS.snapshot()
        workers = accounting.merge_worker_costs(self._cost_workers)
        over_str = accounting.key_str(accounting.OVERHEAD_KEY)
        reports = []
        for kstr in sorted(snap["costs"]):
            key = accounting.parse_key(kstr)
            if key[2] == "overhead":
                continue
            if job_id is not None and key[1] != job_id:
                continue
            reports.append(accounting.build_report(
                key, snap["costs"][kstr], workers.get(kstr, {}),
                self._map_budgets.get(key)))
        out: Dict[str, Any] = {
            "reports": reports,
            "overhead": dict(snap["costs"].get(over_str) or {}),
            "worker_overhead": dict(workers.get(over_str) or {}),
            "totals": COSTS.totals(),
            "cost_workers": len(self._cost_workers),
            # Exact framing-boundary counters of this pool's endpoints:
            # billed wire (per-key + overhead) reconciles against these
            # — the remainder is credit/flow-control traffic the pool
            # layer never sees, reported here instead of silently
            # dropped.
            "transport": {
                "task_ep": {"bytes_tx": self._task_ep.bytes_tx,
                            "bytes_rx": self._task_ep.bytes_rx},
                "result_ep": {"bytes_tx": self._result_ep.bytes_tx,
                              "bytes_rx": self._result_ep.bytes_rx},
            },
        }
        if job_id is not None:
            job_total: Dict[str, float] = {}
            for rep in reports:
                for field, n in rep["total"].items():
                    job_total[field] = job_total.get(field, 0.0) + n
            out["job"] = {"job_id": job_id, "maps": len(reports),
                          "total": {k: round(v, 6)
                                    for k, v in sorted(job_total.items())}}
        return out

    # -- telemetry (docs/observability.md) ---------------------------------
    def stats(self) -> Dict[str, Any]:
        """Aggregated pool introspection: the global_timer's ``pool.*``
        sections (count, total_s, mean_s) plus this pool's exact task
        counters — the one timing surface (the same sections also land
        in the registry's ``timer_seconds`` histogram)."""
        return {
            "timers": {name: stat for name, stat
                       in global_timer.stats().items()
                       if name.startswith("pool.")},
            "tasks_submitted": self._n_submitted,
            "tasks_completed": self._n_completed,
            "tasks_restored": self._n_restored,
            "chunks_resubmitted": self._n_resubmitted,
            "store_fallbacks": self._store_fallbacks,
            "stream_admit_waits": self._stream_admit_waits,
            "streams_active": len(self._stream_windows),
            "queue_depth": self._taskq.qsize(),
            "outstanding": self._store.outstanding(),
            "workers": len(self._workers),
            "sched": self._sched.snapshot(),
            # Accounting-plane summary (full reports: Pool.cost()).
            "costs": {
                kstr: {"tasks": vec.get("tasks", 0.0),
                       "wire_tx": vec.get("wire_tx", 0.0),
                       "wire_rx": vec.get("wire_rx", 0.0)}
                for kstr, vec in COSTS.snapshot()["costs"].items()
            } if COSTS.enabled else {},
        }

    def metrics(self) -> Dict[str, dict]:
        """Snapshot of the process metrics registry (every plane's
        counters, not just this pool's) — the master-side sibling of the
        host agent's ``telemetry_snapshot`` op."""
        self._update_monitor_gauges()
        return telemetry.REGISTRY.snapshot()

    def _update_monitor_gauges(self) -> None:
        """Push this pool's pull-style state into the registry gauges
        (the monitor sampler's per-tick probe; also run by metrics())."""
        _g_queue_depth.set(self._taskq.qsize())
        _g_inflight.set(self._store.outstanding())
        if self._stream_windows:
            fill = 0
            for seq in list(self._stream_windows):
                total, yielded, _fin = self._store.stream_fill_state(seq)
                fill += max(0, total - yielded)
            _g_stream_window_fill.set(fill)

    def timeseries(self) -> Dict[str, Any]:
        """This process's continuous-monitor surface: the sampled
        time-series rings, the latest derived rates (tasks/s, bytes/s,
        heartbeat age) and the anomaly watchdog's state — the
        master-side sibling of the host agent's ``monitor_snapshot``
        op (docs/observability.md "Continuous monitoring")."""
        from fiber_tpu.telemetry.monitor import monitor_payload
        from fiber_tpu.telemetry.timeseries import TIMESERIES

        self._update_monitor_gauges()
        if TIMESERIES.enabled:
            # Extra-fresh tick (same posture as the agent's
            # monitor_snapshot op): results that landed since the last
            # interval must be in the surface the caller reads NOW.
            TIMESERIES.sample_once()
        return monitor_payload()

    def profiles(self) -> Dict[str, int]:
        """Merged cluster profile (flamegraph folded stacks -> sample
        counts): this process's sampler aggregate plus every profile
        frame the workers shipped back on the result stream. Empty
        unless ``profiler_hz`` > 0 (docs/observability.md "Sampling
        profiler")."""
        from fiber_tpu.telemetry import profiler as profmod

        return profmod.merge_folded(profmod.PROFILER.snapshot(),
                                    profmod.AGGREGATE.merged())

    def profile_dump(self, path: str, chrome: bool = False) -> str:
        """Write the merged cluster profile — flamegraph folded text by
        default (``flamegraph.pl``/speedscope/Perfetto ingest it), or
        the Chrome-trace flame view with ``chrome=True``. Returns
        ``path``."""
        from fiber_tpu.telemetry import profiler as profmod

        folded = self.profiles()
        if chrome:
            from fiber_tpu import config as _cfg

            hz = float(_cfg.get().profiler_hz) or 97.0
            return profmod.write_chrome_profile(path, folded, hz)
        with open(path, "w") as fh:
            fh.write(profmod.folded_text(folded))
        return path

    def device_stats(self) -> Dict[str, Any]:
        """Device telemetry plane surface (docs/observability.md
        "Device telemetry"): per-process transfer bytes+seconds (by
        site), compile count+seconds, recompile state, HBM and
        live-array stats (honest ``None`` on CPU), and the last live
        MFU — for the master, every worker that shipped ``("dev", …)``
        frames on the result stream, and every cluster host (the
        backend's ``cluster_devices`` agent sweep, same host keys as
        ``host_health``/``store_stats``)."""
        from fiber_tpu.backends import get_backend
        from fiber_tpu.telemetry.device import DEVICE

        out: Dict[str, Any] = {
            "master": DEVICE.snapshot(),
            "workers": {k: dict(v)
                        for k, v in self._device_workers.items()},
        }
        cluster = getattr(get_backend(), "cluster_devices", None)
        if cluster is not None:
            try:
                out["hosts"] = cluster()
            except Exception as exc:  # noqa: BLE001 - operator surface
                out["hosts"] = {"error": repr(exc)}
        return out

    def trace_dump(self, path: str,
                   xla_dir: Optional[str] = None) -> str:
        """Write the process span store — master spans plus every worker
        span shipped back on the result stream — as Chrome trace-event
        JSON loadable in Perfetto / chrome://tracing (pid = host,
        tid = worker pid). When an XLA profiler capture exists —
        ``xla_dir=`` names its log directory, or a
        ``utils.profiling.trace`` region ran in this process (the
        device plane notes the newest capture) — its device ops merge
        in beside the host spans, aligned on the spans both hold
        (docs/observability.md "Unified timeline"). Returns ``path``."""
        from fiber_tpu.telemetry import export
        from fiber_tpu.telemetry.device import DEVICE

        spans = tracing.SPANS.snapshot()
        if xla_dir is None:
            # the merge aligns on spans both sides hold, so a capture
            # that shares none with this dump (a profiling.trace region
            # from minutes ago) merges nothing
            xla_dir = DEVICE.last_xla_trace()
        return export.write_chrome_trace(path, spans, xla_dir=xla_dir)

    def flight_dump(self, path: str) -> str:
        """Write this process's flight-recorder buffer (pool submits and
        dispatches, scheduler decisions, store/transport/health
        anomalies) as JSON — the companion artifact ``fiber-tpu
        explain`` joins with the trace. Returns ``path``."""
        import json

        from fiber_tpu.utils.logging import LOG_RING

        with open(path, "w") as fh:
            json.dump({"host": tracing.host_id(), "pid": os.getpid(),
                       "dropped": FLIGHT.dropped,
                       "events": FLIGHT.snapshot(),
                       # Log-ring tail: `fiber-tpu explain --flight`
                       # shows what the process was LOGGING next to the
                       # events it blames (docs/observability.md).
                       "logs": LOG_RING.tail(200)}, fh, default=str)
        return path

    # -- submission --------------------------------------------------------
    def _submit(
        self,
        func: Callable,
        iterable: Iterable[Any],
        chunksize: Optional[int],
        star: bool,
        callback: Optional[Callable] = None,
        error_callback: Optional[Callable] = None,
        single: bool = False,
        priority: float = 1.0,
        job_id: Optional[str] = None,
        budget: Optional[CostBudget] = None,
        tenant: Optional[str] = None,
    ) -> AsyncResult:
        if self._closed or self._terminated:
            raise ValueError("Pool not running")
        self._ensure_workers(func)
        items = list(iterable)
        seq = self._store.add(len(items))
        result = AsyncResult(self._store, seq, single=single)
        _register_async_callbacks(self._store, seq, result,
                                  callback, error_callback)
        if not items:
            return result
        # Accounting plane (docs/observability.md "Resource
        # accounting"): every map gets a (tenant, job, map) billing key
        # that rides the task envelope tail; the map's serialize /
        # dispatch / wire / fill observations bill to it, workers bill
        # their chunk costs to the same key, and an optional CostBudget
        # raises the budget_exceeded anomaly when crossed.
        mid = next(_MAP_IDS)
        # tenant= overrides the process-wide COSTS.tenant: the serve
        # daemon multiplexes many tenants' jobs through ONE pool, so
        # billing identity must be per-map, not per-process.
        bill_key = (tenant if tenant else COSTS.tenant,
                    job_id if job_id is not None else f"map-{mid}",
                    f"m{mid}")
        if COSTS.enabled:
            self._seq_bill[seq] = bill_key
            self._map_wall0[seq] = time.perf_counter()
            if budget is not None:
                COSTS.set_budget(bill_key, budget)
                self._map_budgets[bill_key] = budget
                while len(self._map_budgets) > 64:
                    self._map_budgets.pop(next(iter(self._map_budgets)))
        elif budget is not None:
            logger.warning("accounting disabled; budget for job %r is "
                           "not enforced", job_id)
        if chunksize is None:
            # Ceil division (multiprocessing's formula): floor leaves a
            # remainder chunk that lands as one worker's straggler tail —
            # at 200 tasks x 4 workers that is a 17th chunk computing
            # alone while three workers idle, most of the measured 10ms
            # overhead gap vs mp. Capped so huge maps still stream
            # (reference fixed chunk: fiber/pool.py:1169-1170).
            chunksize = max(1, min(DEFAULT_CHUNKSIZE,
                                   -(-len(items) // (self._n_workers * 4))))
        # One trace per sampled map: its id + the serialize span's id
        # ride every task envelope so worker spans join the same trace
        # (docs/observability.md). Unsampled maps ship tctx=None and the
        # workers record nothing. Sampled BEFORE the ledger opens: the
        # header records the id, and a resumed map adopts the recorded
        # one — resubmitted-after-crash chunks keep their trace (the
        # envelope-reuse rule, same as storemiss/death resubmission).
        trace_id = telemetry.maybe_start_trace()
        # Durable-map ledger (docs/robustness.md): with job_id= the map
        # is journaled write-ahead and resumable across master crashes.
        # A pre-existing ledger for this job_id means THIS call is the
        # resume: restore its journaled chunks, run only the remainder.
        ledger = None
        completed: Dict[int, Tuple[int, str]] = {}
        if job_id is not None:
            try:
                ledger, completed, chunksize, trace_id = \
                    self._ledger_open(job_id, func, items, chunksize,
                                      star, trace_id)
            except ValueError:
                self._store.fail(seq, RuntimeError("ledger rejected"),
                                 reason="ledger spec mismatch")
                raise
            except Exception:  # noqa: BLE001 - durability best-effort
                logger.warning(
                    "ledger: journaling disabled for job %r (open "
                    "failed); the map runs but is not resumable",
                    job_id, exc_info=True)
                ledger, completed = None, {}
        restorable: Dict[int, List[Any]] = {}
        if completed:
            restore_t0 = time.perf_counter()
            restorable = self._ledger_restore_all(job_id, completed)
            if COSTS.enabled:
                # Restored chunks bill RESTORE cost, never execute
                # cost: the journaled results are fetched, not re-run
                # (tasks_restored is charged at the fill below).
                COSTS.charge(bill_key, restore_s=(
                    time.perf_counter() - restore_t0))
        # Scheduler registration before any chunk is queued: priority is
        # the WDRR weight across concurrently active maps; the map's
        # state (queued duplicates included) is dropped at completion.
        self._sched.register_map(seq, priority)
        self._store.add_callback(
            seq, lambda: self._sched.release_map(seq))
        if ledger is not None:
            self._ledgers[seq] = ledger
            self._store.add_callback(seq,
                                     lambda: self._ledger_done(seq))
        if COSTS.enabled:
            # Registered AFTER the ledger-done callback so the writer
            # thread has closed (bytes_written is final) when the
            # map's cost is sealed and its job record persisted.
            self._store.add_callback(
                seq, lambda: self._finish_billing(seq, job_id, ledger,
                                                  budget))
        self._n_submitted += len(items)
        _m_tasks_submitted.inc(len(items))
        spans = _chunk_spans(len(items), chunksize)
        pending = [s for s in spans if s[0] not in restorable]
        if ledger is not None:
            self._ledger_last = {
                "job_id": job_id, "seq": seq, "trace": trace_id,
                "chunks": len(spans),
                "restored_chunks": len(restorable),
                "pending_chunks": len(pending),
                "restored_tasks": sum(len(v)
                                      for v in restorable.values()),
            }
        FLIGHT.record("pool", "submit", seq=seq, items=len(items),
                      trace=trace_id, job=job_id,
                      restored_chunks=len(restorable) or None)
        root_span = (tracing.span("pool.serialize", trace=trace_id,
                                  seq=seq, items=len(items))
                     if trace_id and pending else contextlib.nullcontext())
        if pending:
            ser_t0 = time.perf_counter()
            env_key = bill_key if COSTS.enabled else None
            with global_timer.section("pool.serialize"), root_span as sp:
                tctx = (trace_id, sp["span"]) if sp is not None else None
                blob = serialization.dumps(func)
                digest = hashlib.md5(blob).digest()
                enc_items = items
                if self._objstore is not None and self._store_inline_max:
                    seq_digests: List[str] = []
                    # Accelerator-destined maps (@meta tpu/gpu/device)
                    # mark their BROADCAST refs (shared across items —
                    # the encoder's memo detects sharing) so resolving
                    # workers route those through the shared device
                    # tier — one H2D per host per digest, not per
                    # worker. Per-item refs stay unhinted.
                    fmeta = get_meta(func)
                    dev_hint = bool(fmeta.get("tpu") or fmeta.get("gpu")
                                    or fmeta.get("device"))
                    try:
                        with global_timer.section("pool.store_encode"):
                            enc_items = self._encode_items(
                                items, seq_digests, env_key,
                                device_hint=dev_hint)
                    except Exception:  # noqa: BLE001 - optimization only
                        logger.warning(
                            "store: arg encoding failed; shipping inline",
                            exc_info=True)
                        enc_items = items
                        seq_digests = []
                    if seq_digests:
                        self._arm_store_fallback(seq, digest, blob, star,
                                                 items, seq_digests, tctx,
                                                 env_key)
                        # Locality seed: this host's store owns the refs,
                        # and the backend may know other hosts that
                        # already cache them (prestaged via put_object).
                        self._sched.note_host_has(local_host_key(),
                                                  seq_digests)
                        self._probe_ref_locations(seq_digests)
                for base, size in pending:
                    chunk = enc_items[base:base + size]
                    digs = _chunk_digests(chunk)
                    if digs:
                        self._sched.register_chunk((seq, base), digs)
                    payload = serialization.dumps(
                        ("task", seq, base, digest, blob, chunk, star,
                         tctx, env_key)
                    )
                    self._taskq.put((payload, (seq, base)))
            if COSTS.enabled:
                COSTS.charge(bill_key, serialize_s=(
                    time.perf_counter() - ser_t0))
        if restorable:
            # Journaled chunks fill directly — never re-executed, never
            # re-dispatched; exactly one result per task is the ledger's
            # contract. Fills run after the remainder is queued so a
            # fully-restored map completes (and fires its callbacks)
            # only once everything is registered.
            n_restored = 0
            for base, values in restorable.items():
                self._store.fill(seq, base, values)
                n_restored += len(values)
            self._n_restored += n_restored
            if COSTS.enabled:
                # Exactly-once across crashes: restored tasks bill as
                # tasks_restored, never as executed/billed tasks (the
                # result loop only bills frames, and restored chunks
                # never cross the wire again).
                COSTS.charge(bill_key, tasks_restored=n_restored)
            logger.warning(
                "ledger: job %r resumed — restored %d/%d chunks "
                "(%d tasks) from the journal; executing %d chunks",
                job_id, len(restorable), len(spans), n_restored,
                len(pending))
        _g_queue_depth.set(self._taskq.qsize())
        if self._resilient and getattr(self, "_parked_count", 0):
            # New chunks can clear parked requests' reservation gates.
            # Narrow except: only shutdown races are benign — wake()'s
            # wrong-mode RuntimeError must stay loud.
            try:
                self._task_ep.wake()
            except (TransportClosed, OSError):
                pass
        return result

    # -- streaming data plane (docs/streaming.md) --------------------------
    def _submit_stream(self, func: Callable, iterable: Iterable[Any],
                       chunksize: Optional[int], star: bool,
                       priority: float = 1.0,
                       job_id: Optional[str] = None,
                       budget: Optional[CostBudget] = None,
                       windowed: bool = True,
                       ordered: bool = True):
        """Open a streaming map: a background admission loop pulls from
        the caller's iterator lazily, keeping at most ``stream_window``
        chunks encoded + in flight + un-yielded at any instant, so the
        master never materializes the task list. Returns
        ``(seq, ledger, chunksize)`` for the imap variants to build
        their consumer iterator around."""
        from fiber_tpu import config as _config

        if self._closed or self._terminated:
            raise ValueError("Pool not running")
        self._ensure_workers(func)
        cfg = _config.get()
        it = iter(iterable)
        seq = self._store.add_stream()
        mid = next(_MAP_IDS)
        bill_key = (COSTS.tenant,
                    job_id if job_id is not None else f"map-{mid}",
                    f"m{mid}")
        if COSTS.enabled:
            self._seq_bill[seq] = bill_key
            self._map_wall0[seq] = time.perf_counter()
            if budget is not None:
                COSTS.set_budget(bill_key, budget)
                self._map_budgets[bill_key] = budget
                while len(self._map_budgets) > 64:
                    self._map_budgets.pop(next(iter(self._map_budgets)))
        elif budget is not None:
            logger.warning("accounting disabled; budget for job %r is "
                           "not enforced", job_id)
        # No length to divide: the streaming default is the chunk cap
        # itself (a short stream just produces few chunks).
        chunksize = max(1, int(chunksize if chunksize is not None
                               else DEFAULT_CHUNKSIZE))
        trace_id = telemetry.maybe_start_trace()
        # Stream journal (docs/streaming.md "Stream ledger"): admits
        # (input payloads, resumable without the producer), result
        # chunks, and the consumer's cursor — `fiber-tpu resume` works
        # on a half-consumed stream from these alone.
        ledger = None
        completed: Dict[int, Tuple[int, str]] = {}
        if job_id is not None:
            try:
                ledger, completed, chunksize, trace_id = \
                    self._stream_ledger_open(job_id, func, chunksize,
                                             star, trace_id)
            except ValueError:
                self._store.fail(seq, RuntimeError("ledger rejected"),
                                 reason="ledger spec mismatch")
                raise
            except Exception:  # noqa: BLE001 - durability best-effort
                logger.warning(
                    "ledger: journaling disabled for stream job %r "
                    "(open failed); the stream runs but is not "
                    "resumable", job_id, exc_info=True)
                ledger, completed = None, {}
        window = (max(1, int(cfg.stream_window)) if windowed
                  else 1 << 30)
        self._stream_windows[seq] = window
        if ledger is None:
            # Without a journal the master never needs result VALUES on
            # the hot loop: oversized results stay ObjectRefs in the
            # store's spillable tiers and resolve at yield time.
            self._stream_lazy.add(seq)
        self._sched.register_map(seq, priority)
        if windowed:
            # Window-aware handout: a hier sub-master's range must not
            # swallow the whole admission window — other hosts would
            # starve inside it.
            self._sched.note_stream(seq, max(1, window // 4))
        self._store.add_callback(
            seq, lambda: self._sched.release_map(seq))
        self._store.add_callback(
            seq, lambda: self._stream_cleanup(seq))
        if ledger is not None:
            self._ledgers[seq] = ledger
            self._store.add_callback(seq,
                                     lambda: self._ledger_done(seq))
        if COSTS.enabled:
            self._store.add_callback(
                seq, lambda: self._finish_billing(seq, job_id, ledger,
                                                  budget))
        blob = serialization.dumps(func)
        fdigest = hashlib.md5(blob).digest()
        env_key = bill_key if COSTS.enabled else None
        if trace_id:
            with tracing.span("pool.stream_open", trace=trace_id,
                              seq=seq) as sp:
                tctx = (trace_id, sp["span"])
        else:
            tctx = None
        # Storemiss context for streams: items=None marks "per-chunk,
        # see _stream_ctx" (the iterator can't be replayed).
        with self._seq_ctx_lock:
            self._seq_ctx[seq] = (fdigest, blob, star, None, tctx,
                                  env_key)
        self._store.add_callback(
            seq, lambda: self._seq_ctx.pop(seq, None))
        FLIGHT.record("pool", "stream", seq=seq, event="open",
                      window=window if windowed else None,
                      chunksize=chunksize, trace=trace_id, job=job_id,
                      restored_chunks=len(completed) or None)
        threading.Thread(
            target=self._stream_admit,
            args=(seq, it, fdigest, blob, star, chunksize, tctx,
                  env_key, ledger, completed, job_id),
            name=f"fiber-stream-admit-{seq}", daemon=True,
        ).start()
        return seq, ledger, chunksize

    def _stream_admit(self, seq, it, fdigest, blob, star, chunksize,
                      tctx, env_key, ledger, completed, job_id) -> None:
        """The windowed admission loop (one daemon thread per stream):
        pull one chunk from the producer, park while the window is full
        (condition-variable on the ResultStore — the same no-busy-wait
        posture as ``_task_loop``'s inflight gate), encode, journal the
        admit, dispatch. Exhaustion finalizes the stream entry."""
        from fiber_tpu.store.replicate import REPLICATOR

        admitted_chunks = 0
        restored_tasks = 0
        restored_chunks = 0
        try:
            while True:
                if self._terminated or self._store.is_done(seq):
                    return  # aborted/failed mid-stream; no finalize
                window = self._stream_windows.get(seq, 1)
                # "At most `window` chunks un-yielded at any instant":
                # admitting the next chunk is legal once the backlog is
                # a chunk short of the window.
                limit = max(0, window - 1) * chunksize
                waited_t0 = None
                # First probe is non-blocking so even a sub-tick park
                # registers as an episode (the gauge the slow-consumer
                # drills read); subsequent waits ride the condition
                # with a bounded tick, _task_loop posture.
                while not self._store.wait_stream_capacity(
                        seq, limit,
                        timeout=(0.0 if waited_t0 is None else 0.5)):
                    if waited_t0 is None:
                        waited_t0 = time.perf_counter()
                        self._stream_admit_waits += 1
                        _m_stream_admit_waits.inc()
                    if self._terminated:
                        return
                    if self._closed:
                        break
                    # Re-read per wait tick: a policy-plane
                    # shrink/restore takes effect mid-park.
                    window = self._stream_windows.get(seq, window)
                    limit = max(0, window - 1) * chunksize
                if waited_t0 is not None and FLIGHT.enabled:
                    FLIGHT.record(
                        "pool", "stream", seq=seq, event="admit_wait",
                        wait_s=round(time.perf_counter() - waited_t0, 4),
                        reason="window full; consumer slower than "
                               "producer — admission parked")
                if self._closed:
                    # close() mid-admission is producer EOF: the
                    # consumer abandoned the iterator (or the operator
                    # is shutting down). Truncate here — join()'s drain
                    # must see a finalized entry, not an admission loop
                    # parked forever on capacity no consumer will free.
                    logger.warning(
                        "stream: pool closed with stream seq=%d still "
                        "admitting; truncating after %d chunk(s)", seq,
                        admitted_chunks)
                    break
                if self._store.is_done(seq):
                    return
                # Admit a BURST: every chunk the current window has
                # room for rides one capacity check (one lock acquire,
                # one park/wake cycle per windowful instead of per
                # chunk — measurable at 1M tasks). The burst respects
                # the same invariant as chunk-at-a-time admission:
                # un-yielded slots never exceed window * chunksize.
                total, yielded, _fin = self._store.stream_fill_state(seq)
                room = limit - max(0, total - yielded)
                burst = max(1, room // chunksize + 1)
                exhausted = False
                for _ in range(burst):
                    chunk = list(itertools.islice(it, chunksize))
                    if not chunk:
                        exhausted = True  # producer done
                        break
                    base = self._store.extend(seq, len(chunk))
                    admitted_chunks += 1
                    self._n_submitted += len(chunk)
                    _m_tasks_submitted.inc(len(chunk))
                    rec = completed.get(base) if completed else None
                    if rec is not None and rec[0] == len(chunk):
                        values = self._ledger_restore(rec[1], rec[0])
                        if values is not None:
                            # Journaled on a previous run: fill
                            # directly, never re-execute (exactly-once
                            # across crashes; billed as
                            # tasks_restored).
                            self._store.fill(seq, base, values)
                            self._n_restored += len(values)
                            restored_tasks += len(values)
                            restored_chunks += 1
                            if env_key is not None:
                                COSTS.charge(env_key,
                                             tasks_restored=len(values))
                            continue
                    ser_t0 = time.perf_counter()
                    enc_chunk = chunk
                    chunk_digs: List[str] = []
                    if (self._objstore is not None
                            and self._store_inline_max):
                        try:
                            with global_timer.section(
                                    "pool.store_encode"):
                                enc_chunk = self._encode_items(
                                    chunk, chunk_digs, env_key)
                        except Exception:  # noqa: BLE001 - optimization
                            logger.warning("store: stream arg encoding "
                                           "failed; shipping inline",
                                           exc_info=True)
                            enc_chunk = chunk
                            chunk_digs = []
                    if chunk_digs:
                        REPLICATOR.note(chunk_digs)
                        self._sched.note_host_has(local_host_key(),
                                                  chunk_digs)
                    with self._seq_ctx_lock:
                        self._stream_ctx[(seq, base)] = (chunk,
                                                         tuple(chunk_digs))
                    if ledger is not None:
                        # Admit record BEFORE dispatch (write-ahead):
                        # the input payload persists so `fiber-tpu
                        # resume` can re-execute this chunk without
                        # the producer.
                        ledger.record_admit(base, len(chunk), chunk)
                    digs = _chunk_digests(enc_chunk)
                    if digs:
                        self._sched.register_chunk((seq, base), digs)
                    payload = serialization.dumps(
                        ("task", seq, base, fdigest, blob, enc_chunk,
                         star, tctx, env_key))
                    if env_key is not None:
                        COSTS.charge(env_key, serialize_s=(
                            time.perf_counter() - ser_t0))
                    self._taskq.put((payload, (seq, base)))
                    if self._resilient and getattr(self, "_parked_count",
                                                   0):
                        try:
                            self._task_ep.wake()
                        except (TransportClosed, OSError):
                            pass
                _g_queue_depth.set(self._taskq.qsize())
                total, yielded, _fin = self._store.stream_fill_state(seq)
                _g_stream_window_fill.set(max(0, total - yielded))
                if exhausted:
                    break  # producer exhausted
        except Exception as err:  # noqa: BLE001 - producer raised
            logger.exception("stream: producer/admission failed for "
                             "seq=%d", seq)
            self._store.fail(seq, err, reason="stream producer raised",
                             direct=True)
            return
        if ledger is not None:
            self._ledger_last = {
                "job_id": job_id, "seq": seq, "stream": True,
                "chunks": admitted_chunks,
                "restored_chunks": restored_chunks,
                "pending_chunks": admitted_chunks - restored_chunks,
                "restored_tasks": restored_tasks,
            }
        FLIGHT.record("pool", "stream", seq=seq, event="finalize",
                      chunks=admitted_chunks,
                      restored_chunks=restored_chunks or None)
        self._store.finalize(seq)

    def _stream_ledger_open(self, job_id: str, func: Callable,
                            chunksize: int, star: bool,
                            trace_id: Optional[str]):
        """Open (or resume) a STREAM journal: ``kind="stream"`` header
        keyed by a length-free task digest (the item count is unknowable
        up front), admit records carrying the input payloads, result
        chunks, and the consumer cursor. Returns
        ``(ledger|None, completed, chunksize, trace_id)``."""
        from fiber_tpu import config as _config
        from fiber_tpu.store import ledger as ledgermod
        from fiber_tpu.store.replicate import REPLICATOR

        cfg = _config.get()
        if not bool(cfg.ledger_enabled):
            return None, {}, chunksize, trace_id
        path = ledgermod.job_path(job_id)
        tdigest = ledgermod.stream_task_digest(func, star)
        store = self._ledger_store()
        fsync_s = float(cfg.ledger_fsync_s)

        def note_chunk(digest: str) -> None:
            REPLICATOR.note((digest,))

        completed: Dict[int, Tuple[int, str]] = {}
        admits: Dict[int, Tuple[int, str]] = {}
        header = None
        if os.path.exists(path):
            try:
                header, admits, completed, _cursor, _done = \
                    ledgermod.load_stream(path)
            except ValueError:
                logger.warning("ledger: %s has no readable header; "
                               "starting stream job %r fresh", path,
                               job_id)
                header = None
        if header is not None:
            if header.get("kind") != "stream":
                raise ValueError(
                    f"job_id {job_id!r} was journaled as a classic map, "
                    "not a stream; pick a new job_id, or resume it via "
                    "map(..., job_id=)")
            if header.get("task_digest") != tdigest:
                raise ValueError(
                    f"stream job_id {job_id!r} was journaled by a "
                    "different task spec (function / call shape "
                    f"changed); pick a new job_id or delete {path}")
            # Recorded chunking wins: admit/result bases only line up
            # against the journal under the original chunk size.
            chunksize = int(header.get("chunksize") or chunksize)
            if header.get("trace") and trace_id is not None:
                trace_id = str(header["trace"])
            led = ledgermod.MapLedger(path, store,
                                      fsync_interval=fsync_s,
                                      on_chunk=note_chunk)
            led.adopt(completed)
            led.adopt_admits(admits)
            REPLICATOR.note(d for _, d in completed.values())
            FLIGHT.record("store", "ledger", job=job_id,
                          event="stream_resume",
                          admits=len(admits), completed=len(completed))
            return led, completed, chunksize, trace_id
        led = ledgermod.MapLedger(path, store, fsync_interval=fsync_s,
                                  on_chunk=note_chunk)
        func_digest = None
        try:
            # The function travels BY VALUE (cloudpickle) like the
            # classic spec payload, so the resume CLI can re-execute
            # admitted chunks from a dead master's journal alone.
            try:
                import cloudpickle as _cp

                func_blob = _cp.dumps(func)
            except Exception:  # noqa: BLE001
                func_blob = serialization.dumps(func)
            spec_data = serialization.dumps(
                (func_blob, bool(star), int(chunksize)))
            func_digest = store.put_bytes(
                spec_data, refs=1, persist=True).digest
        except Exception:  # noqa: BLE001
            logger.warning(
                "ledger: stream spec for job %r not serializable; "
                "`fiber-tpu resume` needs the original call site",
                job_id, exc_info=True)
        led.write_header({
            "kind": "stream", "job_id": job_id, "task_digest": tdigest,
            "spec": func_digest, "chunksize": int(chunksize),
            "star": bool(star), "trace": trace_id,
        })
        return led, {}, chunksize, trace_id

    def _release_stream_chunk(self, seq: int, base: int) -> None:
        """A stream chunk filled: its raw-items storemiss context and
        encoded-arg store refs are dead weight — drop them now so
        master state stays O(window), not O(stream length)."""
        from fiber_tpu.store.replicate import REPLICATOR

        with self._seq_ctx_lock:
            sctx = self._stream_ctx.pop((seq, base), None)
        if sctx is None:
            return
        digs = sctx[1]
        if digs:
            REPLICATOR.forget(digs)
            if self._objstore is not None:
                for d in digs:
                    self._objstore.release(d)

    def _stream_cleanup(self, seq: int) -> None:
        """Stream completion (success, failure or abort): drop every
        per-stream table entry and release any chunk contexts that
        never filled (failure paths)."""
        self._stream_windows.pop(seq, None)
        self._stream_window_orig.pop(seq, None)
        self._stream_lazy.discard(seq)
        with self._seq_ctx_lock:
            leftover = [k for k in self._stream_ctx if k[0] == seq]
        for (_s, base) in leftover:
            self._release_stream_chunk(seq, base)
        if not self._stream_windows:
            _g_stream_window_fill.set(0)

    def _stream_results(self, seq: int, ordered: bool, lazy: bool,
                        ledger, chunksize: int):
        """Consumer-side iterator for a stream: resolves deferred
        by-reference results at yield time (the incremental-spill leg)
        and, on an ordered durable stream, journals the consumer cursor
        at chunk boundaries so `fiber-tpu resume` can skip the consumed
        prefix."""
        inner = (self._store.iter_ordered(seq) if ordered
                 else self._store.iter_unordered(seq))
        if ledger is None or not ordered:
            # Unordered consumption records no cursor: a count cannot
            # say WHICH results were consumed; resume re-emits every
            # journaled result instead. With no per-item bookkeeping
            # left, delegate — at 1M tiny tasks an extra Python-level
            # loop body per item is measurable.
            if not lazy:
                yield from inner
                return
            for v in inner:
                if isinstance(v, ObjectRef):
                    v = self._resolve_result_refs([v])[0]
                yield v
            return
        consumed = 0
        for v in inner:
            if lazy and isinstance(v, ObjectRef):
                v = self._resolve_result_refs([v])[0]
            yield v
            consumed += 1
            if consumed % chunksize == 0:
                ledger.record_cursor(consumed)

    def shrink_stream_window(self, factor: float = 0.5) -> int:
        """Policy-plane hook (queue_growth -> shrink_stream_window):
        cut every active stream's admission window, throttling a
        runaway producer at the source. The pre-shrink width is kept
        for the owned revert; floor one chunk so streams always
        progress. Returns how many streams were shrunk."""
        factor = min(1.0, max(0.05, float(factor)))
        n = 0
        for seq, win in list(self._stream_windows.items()):
            new = max(1, int(win * factor))
            if new < win:
                self._stream_window_orig.setdefault(seq, win)
                self._stream_windows[seq] = new
                n += 1
        return n

    def restore_stream_window(self) -> int:
        """Clear-edge revert of shrink_stream_window: restore every
        still-active stream's original window. Streams that completed
        meanwhile already dropped their state via _stream_cleanup."""
        n = 0
        for seq, orig in list(self._stream_window_orig.items()):
            if self._stream_windows.get(seq, orig) != orig:
                self._stream_windows[seq] = orig
                n += 1
            self._stream_window_orig.pop(seq, None)
        return n

    # -- public API --------------------------------------------------------
    def apply(self, func: Callable, args: Tuple = (), kwds: Optional[Dict] = None):
        return self.apply_async(func, args, kwds).get()

    def apply_async(
        self,
        func: Callable,
        args: Tuple = (),
        kwds: Optional[Dict] = None,
        callback: Optional[Callable] = None,
        error_callback: Optional[Callable] = None,
        priority: float = 1.0,
    ) -> AsyncResult:
        if kwds:
            import functools

            func = functools.partial(func, **kwds)
        return self._submit(func, [tuple(args)], 1, True,
                            callback, error_callback, single=True,
                            priority=priority)

    def _device_dispatch(
        self, func: Callable, items: List[Any], star: bool
    ) -> Optional[List[Any]]:
        """Run a @meta(device=True) function on the mesh; None if the
        function isn't device-hinted. Enforces the same pool-state
        contract as the host path."""
        if not self._wants_device(func):
            return None
        return self._run_device(func, items, star)

    def _wants_device(self, func: Callable) -> bool:
        """Pool-state check happens here so state errors always surface at
        the submit site, distinct from errors the user function raises."""
        if not get_meta(func).get("device"):
            return False
        if self._closed or self._terminated:
            raise ValueError("Pool not running")
        return True

    def _run_device(self, func: Callable, items: List[Any],
                    star: bool) -> List[Any]:
        try:
            from fiber_tpu.parallel import device_map
        except ImportError as err:  # pragma: no cover
            raise RuntimeError(
                "@meta(device=True) requires the fiber_tpu.parallel "
                "device path"
            ) from err
        t0 = time.perf_counter()
        items, bcast, bpos = self._device_broadcast_split(items, star)
        if bcast:
            out = device_map(func, items, star=star, broadcast=bcast,
                             broadcast_positions=bpos)
        else:
            # No split: keep the pre-device-tier call shape so stubs
            # and older device_map signatures stay compatible.
            out = device_map(func, items, star=star)
        wall = time.perf_counter() - t0
        flops_meta = get_meta(func).get("flops")
        if COSTS.enabled and items:
            # Device maps bill too: one mesh call, no wire — device
            # seconds, task count and (when @meta declares the analytic
            # cost) FLOPs, under a key of their own.
            mid = next(_MAP_IDS)
            dev_key = (COSTS.tenant, f"map-{mid}", f"m{mid}")
            fields: Dict[str, float] = {
                "device_s": wall, "wall_s": wall,
                "tasks": float(len(items)),
            }
            if flops_meta:
                fields["flops"] = float(flops_meta) * len(items)
            COSTS.charge(dev_key, **fields)
            COSTS.release_key(dev_key)
        # Live MFU (docs/observability.md "Device telemetry"): a
        # function declaring its analytic cost (@meta(device=True,
        # flops=<per item>) — utils/flops.py counters supply the
        # number) lands its achieved MFU in the pool_map_mfu gauge
        # whenever the device peak resolves; CPU runs record None
        # honestly.
        if flops_meta and items:
            from fiber_tpu.telemetry.device import DEVICE

            DEVICE.note_map_flops(float(flops_meta) * len(items),
                                  wall, len(items))
        return out

    def _device_broadcast_split(
        self, items: List[Any], star: bool
    ) -> "Tuple[List[Any], tuple, tuple]":
        """Detect broadcast args in a device map and lift them onto the
        mesh ONCE (docs/objectstore.md "Device tier").

        A position of every star-tuple holding the IDENTICAL array
        object (id-identity — the ES/POET idiom ``[(params, s) for s
        in seeds]``) is a broadcast: instead of stacking pop-size
        copies and paying pop-size x nbytes of H2D per call, the array
        is content-addressed, replicated across the mesh through the
        store's device tier (accounted under the ``ici`` site), and
        passed unbatched. Repeat generations with the same digest hit
        the tier: zero wire bytes, zero H2D. Returns ``(items with the
        positions stripped, broadcast args, positions)`` — unchanged
        inputs when nothing qualifies. With the tier off/demoted the
        qualifying args still pass unbatched (never stacked) but
        un-cached: every call re-pays the mesh transfer."""
        if not star or len(items) < 2:
            return items, (), ()
        first = items[0]
        if not isinstance(first, tuple) or len(first) < 2:
            return items, (), ()
        import numpy as np

        width = len(first)
        positions = []
        for j in range(width):
            cand = first[j]
            if not isinstance(cand, np.ndarray) or \
                    cand.nbytes < _DEVICE_BCAST_MIN:
                continue
            if all(isinstance(it, tuple) and len(it) == width
                   and it[j] is cand for it in items):
                positions.append(j)
        # At least one per-item position must remain — an all-broadcast
        # map has nothing to shard over the pool axis.
        if not positions or len(positions) == width:
            return items, (), ()
        from fiber_tpu import store as storemod

        tier = storemod.device_store_tier()
        bcast = []
        digests = []
        for j in positions:
            arr = first[j]
            if tier is None:
                bcast.append(arr)
                continue
            dig = self._bcast_store_digest(arr)
            bcast.append(tier.put(dig, arr))
            digests.append(dig)
        if digests:
            # Locality seed: the scheduler's host->digest map learns
            # this host holds the broadcast content, so a host-path map
            # of the same payload prefers these workers.
            try:
                self._sched.note_host_has(local_host_key(), digests)
            except Exception:  # noqa: BLE001 - placement hint only
                pass
        pos_set = set(positions)
        stripped = [tuple(a for j, a in enumerate(it)
                          if j not in pos_set) for it in items]
        return stripped, tuple(bcast), tuple(positions)

    def _bcast_store_digest(self, arr) -> str:
        """STORE-space digest (digest_of over serialization.dumps —
        the space ObjectRefs live in, so the locality seed matches
        host-path refs of the identical payload; a raw dtype|shape|
        bytes digest never intersects it) with a content-addressed
        shortcut: the raw buffer is hashed zero-copy and mapped to the
        serialized-form digest, so repeat generations of the ES
        broadcast idiom skip the serialize copy. Sound under in-place
        mutation — both sides of the cache are pure content
        addresses."""
        import hashlib

        import numpy as np

        from fiber_tpu.store.core import digest_of

        buf = np.ascontiguousarray(arr)
        h = hashlib.sha256()
        h.update(f"{arr.dtype}|{arr.shape}|".encode())
        h.update(memoryview(buf).cast("B"))
        raw = h.hexdigest()
        dig = self._bcast_digests.get(raw)
        if dig is None:
            dig = digest_of(serialization.dumps(arr))
            self._bcast_digests[raw] = dig
            while len(self._bcast_digests) > 32:
                self._bcast_digests.pop(next(iter(self._bcast_digests)))
        return dig

    def _dispatch_async(self, func, items, star, chunksize,
                        callback, error_callback, priority=1.0,
                        job_id=None, budget=None, tenant=None):
        """Device-or-host submission shared by every map variant, with
        async error contracts preserved on the device path (user-function
        errors reach error_callback / .get(); only pool-state errors
        surface at the submit site, like the host path).

        The device dispatch runs on a background thread: ``map_async``
        returns before the mesh result exists and callbacks fire off the
        submitting thread — the same contract as the host path (round-2
        verdict, Weak #4: the old inline dispatch blocked the caller for
        the whole mesh run). Each dispatch gets a private ResultStore so
        device work never feeds host-path flow control
        (MAX_INFLIGHT_TASKS) or worker-start escalation."""
        if not self._wants_device(func):
            return self._submit(func, items, chunksize, star,
                                callback, error_callback,
                                priority=priority, job_id=job_id,
                                budget=budget, tenant=tenant)
        if job_id is not None:
            # Device dispatch is one mesh call, not a chunk stream —
            # there is nothing partial to journal or resume.
            logger.warning("ledger: job_id %r ignored for "
                           "@meta(device=True) dispatch", job_id)
        store = ResultStore()
        seq = store.add(len(items))
        result = AsyncResult(store, seq, single=False)
        _register_async_callbacks(store, seq, result,
                                  callback, error_callback)
        if not items:
            return result

        trace_id = telemetry.maybe_start_trace()

        def run() -> None:
            dev_span = (tracing.span("pool.device_dispatch",
                                     trace=trace_id, items=len(items))
                        if trace_id else contextlib.nullcontext())
            with dev_span:
                try:
                    out = list(self._run_device(func, items, star))
                except Exception as err:  # noqa: BLE001
                    store.fail(seq, err, reason="device dispatch failed")
                    return
            store.fill(seq, 0, out)

        threading.Thread(target=run, name="fiber-device-dispatch",
                         daemon=True).start()
        return result

    def map(
        self,
        func: Callable,
        iterable: Iterable[Any],
        chunksize: Optional[int] = None,
        priority: float = 1.0,
        job_id: Optional[str] = None,
        budget: Optional[CostBudget] = None,
        tenant: Optional[str] = None,
    ) -> List[Any]:
        """``job_id=`` makes the map durable (docs/robustness.md): the
        task spec and every completed chunk are journaled write-ahead
        under ``<staging>/ledger/<job_id>``, and a master crash is
        survivable — ``fiber-tpu resume <job_id>`` (or re-calling map
        with the same job_id) restores completed results and re-executes
        only the remainder. Tasks must be idempotent (the resilient-pool
        contract already requires this).

        ``budget=`` sets soft :class:`CostBudget` caps for the map
        (docs/observability.md "Resource accounting"): crossing any cap
        raises the ``budget_exceeded`` watchdog anomaly + flight event.
        Measurement, not enforcement — the map keeps running."""
        return self.map_async(func, iterable, chunksize,
                              priority=priority, job_id=job_id,
                              budget=budget, tenant=tenant).get()

    def map_async(
        self,
        func: Callable,
        iterable: Iterable[Any],
        chunksize: Optional[int] = None,
        callback: Optional[Callable] = None,
        error_callback: Optional[Callable] = None,
        priority: float = 1.0,
        job_id: Optional[str] = None,
        budget: Optional[CostBudget] = None,
        tenant: Optional[str] = None,
    ):
        return self._dispatch_async(func, list(iterable), False, chunksize,
                                    callback, error_callback, priority,
                                    job_id=job_id, budget=budget,
                                    tenant=tenant)

    def starmap(
        self,
        func: Callable,
        iterable: Iterable[Tuple],
        chunksize: Optional[int] = None,
        priority: float = 1.0,
        job_id: Optional[str] = None,
        budget: Optional[CostBudget] = None,
        tenant: Optional[str] = None,
    ) -> List[Any]:
        return self.starmap_async(func, iterable, chunksize,
                                  priority=priority, job_id=job_id,
                                  budget=budget, tenant=tenant).get()

    def starmap_async(
        self,
        func: Callable,
        iterable: Iterable[Tuple],
        chunksize: Optional[int] = None,
        callback: Optional[Callable] = None,
        error_callback: Optional[Callable] = None,
        priority: float = 1.0,
        job_id: Optional[str] = None,
        budget: Optional[CostBudget] = None,
        tenant: Optional[str] = None,
    ):
        return self._dispatch_async(func, [tuple(t) for t in iterable],
                                    True, chunksize, callback,
                                    error_callback, priority,
                                    job_id=job_id, budget=budget,
                                    tenant=tenant)

    def imap(
        self,
        func: Callable,
        iterable: Iterable[Any],
        chunksize: Optional[int] = None,
        priority: float = 1.0,
        job_id: Optional[str] = None,
        budget: Optional[CostBudget] = None,
    ):
        """Ordered lazy map over ANY iterable (docs/streaming.md).

        With ``stream_enabled`` (the default) this is a true streaming
        pipeline: a windowed admission loop pulls from ``iterable``
        lazily — at most ``stream_window`` chunks are encoded + in
        flight + un-yielded at any instant — so master memory is
        O(window), not O(n), and a slow consumer backpressures
        admission (which parks dispatch, which drains transport
        credits). ``job_id=`` journals the *stream*: admitted input
        chunks, completed result chunks, and the consumer's cursor, so
        ``fiber-tpu resume`` works on a half-consumed stream.

        With ``stream_enabled=False`` the map still accepts any
        iterable and dispatches without a window; the input is only
        materialized up front when ``job_id`` + ``ledger_enabled``
        demand the classic fixed task digest (ledger identity is
        ``f(func, n_items)``, which needs the full length — the
        tradeoff is O(n) master RAM in exchange for the classic
        whole-map journal format)."""
        return self._imap_impl(func, iterable, chunksize, priority,
                               job_id, budget, ordered=True)

    def imap_unordered(
        self,
        func: Callable,
        iterable: Iterable[Any],
        chunksize: Optional[int] = None,
        priority: float = 1.0,
        job_id: Optional[str] = None,
        budget: Optional[CostBudget] = None,
    ):
        """Unordered variant of :meth:`imap` — results yield as chunks
        complete, and each yielded slot's payload reference is released
        immediately, so master RSS stays flat across arbitrarily long
        streams (large results spill through the object store and are
        resolved at yield time). Same streaming / fallback /
        materialization rules as :meth:`imap`; an unordered durable
        stream journals results but no consumer cursor (a position
        count cannot identify WHICH unordered results were consumed —
        resume re-emits every journaled result)."""
        return self._imap_impl(func, iterable, chunksize, priority,
                               job_id, budget, ordered=False)

    def _imap_impl(self, func, iterable, chunksize, priority, job_id,
                   budget, ordered: bool):
        from fiber_tpu import config as _config

        if self._wants_device(func):
            # Device maps run as one mesh dispatch over the whole
            # batch; they are the one shape that genuinely needs the
            # materialized list.
            return iter(self._run_device(func, list(iterable),
                                         star=False))
        cfg = _config.get()
        windowed = bool(cfg.stream_enabled)
        if (not windowed and job_id is not None
                and bool(cfg.ledger_enabled)):
            # Classic durable path: the whole-map ledger's identity is
            # f(func, n_items), so the length must be known up front.
            items = list(iterable)
            res = self._submit(func, items, chunksize, False,
                               priority=priority, job_id=job_id,
                               budget=budget)
            inner = (self._store.iter_ordered(res._seq) if ordered
                     else self._store.iter_unordered(res._seq))
            return _ResultIterator(inner)
        seq, ledger, csz = self._submit_stream(
            func, iterable, chunksize, False, priority=priority,
            job_id=job_id if windowed else None, budget=budget,
            windowed=windowed, ordered=ordered)
        return _ResultIterator(self._stream_results(
            seq, ordered, seq in self._stream_lazy, ledger, csz))

    # -- lifecycle ---------------------------------------------------------
    def wait_workers(self, n: Optional[int] = None,
                     timeout: Optional[float] = None) -> bool:
        """Block until n (default: all) worker connections are up
        (reference: fiber/pool.py:1405-1422). Starts the (normally lazy)
        worker population if needed."""
        self._start_worker_thread()
        if n is None:
            n = self._n_workers
            if (self._dispatch_mode == "hier" and self._resilient
                    and self._cpu_per_job > 1
                    and not self._hier_degraded):
                # Hierarchical dispatch: one upstream result connection
                # per sub-master JOB, not per sub-worker.
                n = -(-self._n_workers // self._cpu_per_job)
        return self._result_ep.wait_for_peers(n, timeout)

    def close(self) -> None:
        """No new tasks; workers exit once submitted work drains (the
        release itself happens in join(), deterministically)."""
        self._closed = True

    def _release_workers(self) -> None:
        """Send one exit message per connected task consumer; strict
        round-robin delivers exactly one to each."""
        exit_payload = serialization.dumps(_EXIT)
        for _ in range(self._task_ep.peer_count()):
            try:
                self._task_ep.send(exit_payload, timeout=5.0)
            except (TimeoutError, TransportClosed, OSError):
                break

    def join(self) -> None:
        if not self._closed and not self._terminated:
            raise ValueError("join() before close()/terminate()")
        # 1. Drain all submitted work.
        while self._store.outstanding() > 0 and not self._terminated:
            time.sleep(0.05)
        # 2. Stop the maintainer so the worker list can no longer change.
        if self._worker_thread is not None:
            self._worker_thread.join(60)
        # 3. Release and reap the workers.
        if not self._terminated and not self._resilient:
            self._release_workers()
        with self._workers_lock:
            self._reaped = True  # late spawn stragglers self-terminate
            workers = list(self._workers)
        for p in workers:
            p.join(10)
            if p.is_alive():
                logger.warning("pool worker %s did not exit; terminating",
                               p.name)
                p.terminate()
                p.join(10)
        with self._workers_lock:
            self._workers = []
        self._shutdown_transport()

    def terminate(self) -> None:
        self._terminated = True
        self._closed = True
        with self._workers_lock:
            workers = list(self._workers)
        for p in workers:
            try:
                p.terminate()
            except Exception:
                pass
        for p in workers:
            try:
                p.join(10)
            except Exception:
                pass
        with self._workers_lock:
            self._workers = []
        self._store.abort_all(RuntimeError("pool terminated"))
        self._shutdown_transport()

    def _shutdown_transport(self) -> None:
        from fiber_tpu.telemetry.timeseries import TIMESERIES

        TIMESERIES.remove_probe(self._monitor_probe)
        self._taskq.put(None)
        self._sched.close()
        self._task_ep.close()
        self._result_ep.close()
        # Incomplete job ledgers stay on disk (that IS the durability
        # contract — `fiber-tpu resume` picks them up); only the writer
        # threads are stopped, after a final drain.
        for led in list(self._ledgers.values()):
            try:
                led.close()
            except Exception:  # noqa: BLE001
                pass
        self._ledgers.clear()

    def __enter__(self) -> "Pool":
        return self

    def __exit__(self, *exc_info) -> None:
        if not self._closed:
            self.close()
        self.join()

    def __del__(self) -> None:  # pragma: no cover - GC safety net
        try:
            if not self._terminated and not self._closed:
                self.terminate()
        except Exception:
            pass


class PoisonChunkError(Exception):
    """One chunk killed every worker that received it (e.g. its payload
    cannot deserialize in the worker); the map fails instead of
    crash-looping the pool forever."""


#: Consecutive death-resubmissions of ONE chunk before its map fails.
_POISON_CAP = 8


class ResilientPool(Pool):
    """REQ/REP pool with a pending table and resubmission on worker death
    (reference ResilientZPool, fiber/pool.py:1425-1688) — the default
    ``fiber_tpu.Pool``. Only safe for idempotent task functions."""

    _resilient = True

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        # ident -> {(seq, base): (payload, nitems)}
        self._pending: Dict[bytes, Dict[Tuple[int, int], Tuple[bytes, int]]] = {}
        #: len() of the handout loop's parked-request table, mirrored
        #: here (single-writer: the task loop) so result/submit paths
        #: can skip the wake nudge when nothing is waiting on a gate.
        self._parked_count = 0
        #: (seq, base) -> how many workers died holding that chunk; a
        #: chunk that keeps killing workers is POISON (e.g. its payload
        #: cannot deserialize in the worker) and must fail the map
        #: rather than crash-loop the pool forever.
        self._chunk_deaths: Dict[Tuple[int, int], int] = {}
        #: seq -> consecutive worker deaths attributed to that map with
        #: NO completed chunk in between (any result resets it). Catches
        #: the every-chunk-is-poison map, where per-chunk counts spread
        #: across the whole map and would take chunks*cap deaths to fire.
        self._seq_deaths: Dict[int, int] = {}
        self._pid_to_idents: Dict[int, set] = {}
        self._reaped_pids: set = set()
        # Dead-ident guard against stale "ready"s queued before a
        # sub-worker's death was processed. The window is short, so the
        # set is bounded: oldest entries fall out once the deque is full
        # (a long-lived die-heavy pool must not leak one entry per crash).
        self._dead_idents: set = set()
        self._dead_idents_order: "deque[bytes]" = deque(maxlen=4096)
        self._pending_lock = threading.Lock()
        #: Idents that declared themselves sub-masters ("hier" 5th field
        #: on their ready frames): their handouts are packed into ranges.
        self._hier_idents: set = set()
        super().__init__(*args, **kwargs)
        # Health plane: workers beat on the result stream; silence past
        # suspect_timeout declares the ident dead and reclaims its
        # pending chunks through the SAME path as an observed process
        # death — so a hung host (no FIN, no exit code) is survived
        # before TCP would notice. Declared idents are permanent: pool
        # idents are never reused, and a falsely-declared (merely slow)
        # worker is told to exit on its next "ready", its duplicate
        # results deduped by ResultStore.fill. Workers can't connect
        # before this point (they spawn lazily at first submit), so no
        # beat can precede the detector.
        from fiber_tpu import config as _config
        from fiber_tpu.health import FailureDetector

        _cfg = _config.get()
        if float(_cfg.heartbeat_interval or 0) > 0 \
                and float(_cfg.suspect_timeout or 0) > 0:
            self._detector = FailureDetector(
                float(_cfg.suspect_timeout), self._on_peer_suspect,
                permanent=True, name="fiber-pool-detector",
            ).start()
        # Dedicated control endpoint for packing-parent sub-worker
        # reports. Deliberately NOT the result endpoint (its peer count
        # is what wait_workers() reads as "workers connected") and NOT
        # the REQ/REP task endpoint (its single-threaded loop parks in
        # the task-handout wait, which would deadlock against a
        # resubmission-bearing report). Only packed jobs ever report,
        # so unpacked pools skip the listener + thread entirely.
        self._ctl_ep = None
        self._ctl_addr = None
        if self._cpu_per_job > 1:
            from fiber_tpu.backends import get_backend

            ip, _, _ = get_backend().get_listen_addr()
            self._ctl_ep = Endpoint("r")
            self._ctl_addr = self._ctl_ep.bind(ip)
            self._ctl_thread = threading.Thread(
                target=self._ctl_loop, name="fiber-pool-ctl", daemon=True
            )
            self._ctl_thread.start()

    def _ctl_loop(self) -> None:
        while True:
            try:
                data = self._ctl_ep.recv()
            except (TransportClosed, OSError):
                return
            try:
                msg = serialization.loads(data)
                if msg[0] == "subdead":
                    self._on_subworker_death(msg[1])
                elif msg[0] == "subgone":
                    self._on_subworker_gone(msg[1])
            except Exception:
                logger.exception("pool: dropping malformed control frame")

    def _shutdown_transport(self) -> None:
        super()._shutdown_transport()
        if self._detector is not None:
            self._detector.stop()
        if self._ctl_ep is not None:
            self._ctl_ep.close()

    def _on_peer_suspect(self, ident: bytes) -> None:
        """Failure-detector declaration: treat the silent ident exactly
        like a reported death (resubmit its pending chunks, block
        future handouts to it). Runs on the detector thread."""
        host = self._ident_hosts.get(ident)
        n = self._reclaim_ident(ident)
        if FLIGHT.enabled:
            # Black-box capture off the detector thread: the master's
            # own flight view of the dead ident, plus a best-effort pull
            # of the peer host's postmortem op (docs/observability.md).
            threading.Thread(
                target=self._capture_postmortem,
                args=(ident, host, n, "suspect"),
                name="fiber-postmortem", daemon=True,
            ).start()
        if n:
            logger.warning(
                "health: worker ident %s silent past suspect_timeout; "
                "declared dead, resubmitted %d pending chunks",
                ident.hex()[:8], n)
            # Resubmitted chunks can clear parked requests' gates.
            if self._parked_count:
                try:
                    self._task_ep.wake()
                except (TransportClosed, OSError):
                    pass
        else:
            logger.info(
                "health: idle worker ident %s silent past "
                "suspect_timeout; declared dead (nothing to resubmit)",
                ident.hex()[:8])

    def _capture_postmortem(self, ident: bytes, host, resubmitted: int,
                            reason: str) -> None:
        """Write the black-box bundle for one declared-dead worker: the
        master's flight events (which carry the ident's dispatch /
        resubmit history) plus, when the backend knows the peer's host,
        that host agent's ``postmortem`` op — its flight buffer, stack
        dump and any crash bundles workers on that host flushed.
        Entirely best-effort: postmortem capture must never take the
        health plane down with it."""
        from fiber_tpu.telemetry import postmortem

        peer = None
        if host is not None:
            try:
                from fiber_tpu.backends import get_backend

                collect = getattr(get_backend(), "collect_postmortem",
                                  None)
                if collect is not None:
                    peer = collect(host)
            except Exception:  # noqa: BLE001 - peer pull is optional
                logger.warning("postmortem: peer pull for %s failed",
                               host, exc_info=True)
        try:
            path = postmortem.capture_and_write(
                reason, ident=ident.hex(), peer_host=host,
                chunks_resubmitted=resubmitted, peer=peer)
            logger.warning("postmortem: bundle for worker %s written "
                           "to %s", ident.hex()[:8], path)
        except Exception:  # noqa: BLE001
            logger.warning("postmortem: bundle write failed",
                           exc_info=True)

    def _mark_ident_dead(self, ident: bytes) -> None:
        # Caller holds _pending_lock.
        if ident in self._dead_idents:
            return
        if len(self._dead_idents_order) == self._dead_idents_order.maxlen:
            self._dead_idents.discard(self._dead_idents_order[0])
        self._dead_idents_order.append(ident)
        self._dead_idents.add(ident)

    # Task handout: answer each worker's "ready" request with a task and
    # record it in the pending table until its result arrives.
    #
    # Reservation gate (reference regression, fiber
    # tests/test_pool.py:179-234): the worker-side fetch thread
    # pipelines — it requests chunk N+1 while chunk N computes — so
    # without a gate a fast worker's SECOND request can win a scarce
    # chunk over a sibling's FIRST, serializing two tasks that must run
    # concurrently (interlocked workloads then deadlock). A repeat
    # request (ident already has unfinished chunks) is therefore parked
    # whenever the queued chunks don't exceed one-per-potentially-idle
    # worker; parked requests are re-evaluated every loop turn and
    # answered out of order via the rep endpoint's recv_req/reply.
    # With chunks plentiful (the normal pipelined regime) the gate
    # passes immediately, so the REQ/REP overlap that closed the 10 ms
    # overhead gap is untouched.

    def _gate_allows(self, ident: bytes) -> bool:
        # Serve if the requester is idle (no unfinished chunks), or if
        # enough chunks remain to leave one for every worker that has
        # none. qsize() is approximate; the gate re-evaluates each turn.
        # Health-plane placement: a requester on a suspect host is
        # parked while healthier workers exist and work is scarce —
        # parked requests re-evaluate every turn, so a revived host
        # (the backend detector is non-permanent) resumes service.
        if self._suspect_defers(ident):
            return False
        with self._pending_lock:
            if not self._pending.get(ident):
                return True
            busy = sum(1 for t in self._pending.values() if t)
        reserve = max(0, self._n_workers - busy)
        return self._taskq.qsize() > reserve

    def _task_loop(self) -> None:
        # Runs until the pool's transport shuts down (join/terminate close
        # the endpoints → recv raises). During a close() drain it keeps
        # answering "ready" requests — with remaining tasks first, then
        # with exit messages so every worker is released.
        parked: Dict[bytes, Tuple[Any, int]] = {}  # ident -> (chan, pid)

        def sync_parked() -> None:
            # SINGLE-WRITER INVARIANT: _parked_count is written only
            # here, on the task loop's thread. submit/_on_result threads
            # read it unlocked (_gate_allows) — that is safe only
            # because a stale read degrades to the 0.5 s recv-timeout
            # retry, never to a lost task. If the loop is ever
            # restructured to mutate parked from another thread, this
            # must become a locked counter.
            self._parked_count = len(parked)

        def drain_done() -> bool:
            return self._draining_done() and self._taskq.empty()

        def reply_exit(chan) -> None:
            try:
                payload = serialization.dumps(_EXIT)
                self._task_ep.reply(chan, payload)
                self._bill_frame(None, tx=len(payload))
            except (TransportClosed, OSError):
                pass

        def serve(ident: bytes, fiber_pid: int, chan) -> None:
            """Hand the next chunk (or exit) to one cleared requester;
            re-parks nothing — the caller already passed the gate."""
            host = self._ident_hosts.get(ident)
            item = None
            while item is None:
                if self._terminated:
                    return
                if drain_done():
                    reply_exit(chan)
                    return
                try:
                    # Scheduler handout (docs/scheduling.md): WDRR map
                    # choice + locality scan for this requester; never
                    # hands a worker its own chunk's speculative dup.
                    item = self._taskq.get_for(ident, host, timeout=0.5)
                except pyqueue.Empty:
                    continue
                if item is None:
                    return
                if self._store.is_done(item[1][0]):
                    # Leftover chunk of a completed/poison-failed map:
                    # handing it out would burn workers on a map whose
                    # error already surfaced.
                    item = None
            items = [item]
            if ident in self._hier_idents and self._range_chunks > 1:
                # Hierarchical handout: top the range up with whatever
                # else is immediately available (never blocking — the
                # first chunk already waited its turn), bounded by the
                # knob. One frame then carries the whole range, so the
                # master's frame count and encode CPU scale with hosts.
                range_cap = self._range_chunks
                if item is not None:
                    # Streaming maps cap the range (window-aware
                    # handout): a whole admission window inside one
                    # sub-master's range would starve other hosts and
                    # defeat backpressure granularity.
                    cap = self._taskq.range_cap(item[1][0])
                    if cap:
                        range_cap = min(range_cap, cap)
                while len(items) < range_cap:
                    try:
                        extra = self._taskq.get_for(ident, host,
                                                    timeout=0)
                    except pyqueue.Empty:
                        break
                    if extra is None:
                        break
                    if self._store.is_done(extra[1][0]):
                        continue
                    items.append(extra)
            with self._pending_lock:
                # The worker may have been reaped while we waited for a
                # task — its pending table is gone and nobody would
                # ever resubmit these chunks. Requeue for the next
                # "ready".
                if (fiber_pid in self._reaped_pids
                        or ident in self._dead_idents):
                    for it in items:
                        self._taskq.put(it)
                    return
                table = self._pending.setdefault(ident, {})
                for payload, key in items:
                    table[key] = payload
            if len(items) == 1 and ident not in self._hier_idents:
                wire = items[0][0]
            else:
                # Range envelope: raw chunk payloads ride untouched
                # (encoded once at submit; the sub-master never decodes
                # them), tagged with their pending keys.
                wire = serialization.dumps(
                    ("range", [(key[0], key[1], payload)
                               for payload, key in items]))
                self._sched.note_range(len(items))
            first_key = items[0][1]
            try:
                t0 = time.perf_counter()
                self._task_ep.reply(chan, wire)
                global_timer.add("pool.dispatch",
                                 time.perf_counter() - t0)
                # One billed frame for the whole range: billed wire
                # must equal actual wire (Pool.cost() reconciliation).
                self._bill_frame(first_key[0], tx=len(wire),
                                 dispatch_s=time.perf_counter() - t0)
                _m_chunks_dispatched.inc(len(items))
                if FLIGHT.enabled:
                    FLIGHT.record("pool", "dispatch", seq=first_key[0],
                                  base=first_key[1],
                                  ident=ident.hex()[:8],
                                  chunks=len(items))
                _g_queue_depth.set(self._taskq.qsize())
                # Service-time clock starts at the successful handout;
                # the speculation monitor ages these entries.
                for payload, key in items:
                    self._sched.dispatched(key, ident, host, payload)
            except (TransportClosed, OSError):
                # Requester died between asking and receiving; put the
                # chunks back for the next "ready" and keep serving.
                # Counted as resubmissions: same cause (worker death),
                # different observation path than the pending reclaim.
                with self._pending_lock:
                    table = self._pending.get(ident, {})
                    for _, key in items:
                        table.pop(key, None)
                for it in items:
                    self._taskq.put(it)
                self._n_resubmitted += len(items)
                _m_chunks_resubmitted.inc(len(items))

        while True:
            # Re-evaluate parked requests first: results arriving or
            # chunks queueing since last turn may have cleared gates.
            for ident in list(parked):
                chan, pid = parked[ident]
                with self._pending_lock:
                    stale = (pid in self._reaped_pids
                             or ident in self._dead_idents)
                if stale or not chan.alive:
                    del parked[ident]
                    sync_parked()
                    if stale:
                        reply_exit(chan)
                    continue
                if drain_done():
                    del parked[ident]
                    sync_parked()
                    reply_exit(chan)
                    continue
                if self._gate_allows(ident):
                    del parked[ident]
                    sync_parked()
                    serve(ident, pid, chan)
            try:
                req, chan = self._task_ep.recv_req(timeout=0.5)
            except TimeoutError:
                if self._terminated:
                    return
                continue
            except (TransportClosed, OSError):
                return
            # Handout-control traffic no single map causes: the
            # explicit overhead bucket, never silently dropped.
            self._bill_frame(None, rx=len(req))
            msg = serialization.loads(req)
            if msg[0] != "ready":
                continue
            ident, fiber_pid = msg[1], msg[2]
            # 3-tuple readys predate the scheduler plane; the placement
            # host key rides as an optional 4th field (same back-compat
            # posture as the task envelope's trace context). A 5th field
            # of "hier" marks a per-host sub-master, whose handouts are
            # packed into chunk ranges.
            if len(msg) > 3:
                self._ident_hosts[ident] = msg[3]
            if len(msg) > 4 and msg[4] == "hier":
                self._hier_idents.add(ident)
            # A stale "ready" from a worker that was already reaped must
            # not receive (and thereby strand) a task: its pending table is
            # gone and nobody would ever resubmit the chunk. Same for an
            # ident whose sub-worker death was already processed.
            with self._pending_lock:
                stale = (fiber_pid in self._reaped_pids
                         or ident in self._dead_idents)
            if stale:
                reply_exit(chan)
                continue
            with self._pending_lock:
                self._pending.setdefault(ident, {})
                self._pid_to_idents.setdefault(fiber_pid, set()).add(ident)
            if self._terminated:
                return
            if drain_done():
                reply_exit(chan)
                continue
            if self._gate_allows(ident):
                serve(ident, fiber_pid, chan)
            else:
                parked[ident] = (chan, fiber_pid)
                sync_parked()

    def _on_result(self, seq, base, values, ident) -> None:
        # Scheduler bookkeeping first: the first result retires every
        # in-flight copy of the chunk (speculation's first-result-wins;
        # the loser's late duplicate is a no-op here and its values are
        # deduped by ResultStore.fill) and contributes the service-time
        # sample + organic locality knowledge.
        self._sched.completed((seq, base), ident,
                              self._ident_hosts.get(ident))
        with self._pending_lock:
            table = self._pending.get(ident)
            if table is not None:
                table.pop((seq, base), None)
            # Completed chunks can't be poison; drop any death count so
            # the table stays bounded by in-flight chunks. Progress on
            # a map also clears its no-progress death streak.
            self._chunk_deaths.pop((seq, base), None)
            self._seq_deaths.pop(seq, None)
        # A completed chunk can clear a parked request's gate (the
        # requester is now idle) — nudge the handout loop instead of
        # letting it notice at its next recv timeout. Skipped entirely
        # while nothing is parked (the hot path of a plentiful-chunk
        # map must not pay an inbox put per result).
        if self._parked_count:
            # Narrow except: shutdown races only (see submit-side twin).
            try:
                self._task_ep.wake()
            except (TransportClosed, OSError):
                pass

    def _on_store_miss(self, seq, base, n, ident) -> None:
        """Resilient twist on the inline resend: the reporting worker's
        pending entry for this chunk is retired first, so a later death
        of that worker doesn't also resubmit the ref-bearing payload it
        couldn't resolve (dedup would absorb it, but the doomed handout
        would burn a fetch cycle). New chunks can clear parked
        requests' reservation gates — nudge the handout loop."""
        self._sched.abandon((seq, base), ident)
        with self._pending_lock:
            table = self._pending.get(ident)
            if table is not None:
                table.pop((seq, base), None)
        super()._on_store_miss(seq, base, n, ident)
        if self._parked_count:
            try:
                self._task_ep.wake()
            except (TransportClosed, OSError):
                pass

    def _reclaim_ident(self, ident: bytes) -> int:
        """Retire one sub-worker ident: block future handouts to it, drop
        its bookkeeping, and requeue whatever it still owed. Returns the
        number of chunks resubmitted. Duplicate executions this can cause
        are safe: resilient-pool tasks must be idempotent and duplicate
        results are deduped by ResultStore.fill."""
        if self._detector is not None:
            # Death observed (or declared): the detector must never
            # post-mortem-suspect this ident, and late beats from a
            # not-actually-dead declaree must not resurrect it.
            self._detector.forget(ident)
        # Scheduler: the dead ident's chunk copies stop aging (their
        # payloads re-enter the queue below; a copy whose chunk already
        # completed — e.g. a speculation winner beat the death — is
        # dropped at put() instead of burning another worker).
        self._sched.abandon_ident(ident)
        self._ident_hosts.pop(ident, None)
        with self._pending_lock:
            self._mark_ident_dead(ident)
            table = self._pending.pop(ident, {})
            for idents in self._pid_to_idents.values():
                idents.discard(ident)
            resubmit = []
            poisoned = []
            # Death attribution is a heuristic: the OLDEST held chunk
            # (handout order = dict insertion order) is the one being
            # executed — or the only one, when a payload dies during
            # decode. Younger staged chunks are bystanders and are NOT
            # counted; a staged decode-poison gets counted on a later
            # cycle when it lands first. A false positive needs one
            # innocent chunk to be the oldest across CAP+1 consecutive
            # deaths without ever completing — and the error is direct
            # and catchable either way.
            counted = False
            for key, payload in table.items():
                if self._store.is_done(key[0]):
                    # Leftover of a completed/already-failed map: no
                    # counting (no result will ever pop the entries)
                    # and no resubmission.
                    self._chunk_deaths.pop(key, None)
                    self._seq_deaths.pop(key[0], None)
                    continue
                if not counted:
                    counted = True
                    deaths = self._chunk_deaths.get(key, 0) + 1
                    self._chunk_deaths[key] = deaths
                    seq_deaths = self._seq_deaths.get(key[0], 0) + 1
                    self._seq_deaths[key[0]] = seq_deaths
                    if (deaths > _POISON_CAP
                            or seq_deaths > 3 * _POISON_CAP):
                        poisoned.append(key)
                        self._chunk_deaths.pop(key, None)
                        self._seq_deaths.pop(key[0], None)
                        continue
                resubmit.append((payload, key))
        # Fail poisoned maps BEFORE requeueing, so this very call's
        # bystander chunks of a just-poisoned seq are dropped by the
        # is_done filter instead of burning further workers.
        for seq, base in poisoned:
            logger.error(
                "map seq=%d is killing workers without progress "
                "(latest culprit chunk base=%d) — failing it as poison",
                seq, base)
            self._store.fail(
                seq,
                PoisonChunkError(
                    f"map chunks keep killing workers with no progress "
                    f"(last culprit at base {base}; does the task "
                    "function/payload deserialize and run in the "
                    "worker?)"),
                reason="poison chunk", direct=True)
        requeued = 0
        for payload, key in resubmit:
            if self._store.is_done(key[0]):
                continue  # e.g. failed by this call's poison path
            self._taskq.put((payload, key))
            requeued += 1
        if requeued:
            self._n_resubmitted += requeued
            _m_chunks_resubmitted.inc(requeued)
            FLIGHT.record("pool", "resubmit", ident=ident.hex()[:8],
                          chunks=requeued,
                          reason="worker death / suspect reclaim")
        return requeued

    def _on_subworker_death(self, ident: bytes) -> None:
        """Resubmit one crashed sub-worker's pending chunks while its job
        keeps running (finer-grained than the reference, whose blast
        radius with cpu_per_job>1 is the whole job: fiber/pool.py:1612-1659
        only fires on job death). The packing parent respawns the
        sub-worker in place, so capacity is repaired too."""
        n = self._reclaim_ident(ident)
        if n:
            logger.info("resubmitted %d chunks from dead sub-worker", n)

    def _on_subworker_gone(self, ident: bytes) -> None:
        """A packed sub-worker retired cleanly (maxtasksperchild): drop its
        bookkeeping (normally empty; a crash-at-exit loses nothing)."""
        self._reclaim_ident(ident)

    def _on_worker_death(self, proc) -> None:
        """Resubmit everything the dead worker still owed
        (reference: fiber/pool.py:1612-1659) — through the same
        poison-counting reclaim as sub-worker death, so a chunk that
        kills whole workers escalates identically."""
        pid = proc.pid
        if (getattr(proc, "_n_local", 1) > 1
                and self._dispatch_mode == "hier"
                and not self._hier_degraded):
            # A dead packed job under hierarchical dispatch was a
            # sub-master. Its pending range is reclaimed below like any
            # death, but the REPLACEMENT jobs run direct per-worker
            # dispatch: repeated sub-master loss must converge on the
            # proven path, not crash-loop the hierarchy.
            self._hier_degraded = True
            logger.warning(
                "hier: sub-master job %s died; degrading this pool to "
                "direct per-worker dispatch", proc.name)
            FLIGHT.record("hier", "degrade", job=proc.name,
                          reason="sub-master death; respawns use "
                                 "direct dispatch")
        with self._pending_lock:
            self._reaped_pids.add(pid)
            idents = self._pid_to_idents.pop(pid, set())
        n = sum(self._reclaim_ident(ident) for ident in idents)
        if n:
            logger.info(
                "resubmitted %d chunks from dead worker %s",
                n, proc.name,
            )

