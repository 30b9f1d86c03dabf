"""Adaptive per-pool scheduler: placement, speculation, fair queueing.

The pool's original handout was an implicit FIFO — a single
``queue.Queue`` drained in arrival order regardless of *who* is asking
or *what else* is queued (reference: fiber/pool.py:1546-1585 hands
chunks to whichever worker's "ready" arrives first). This module makes
the handout an explicit policy object with three decisions
(docs/scheduling.md):

* **Placement (locality)** — a chunk whose args travel as ObjectRefs is
  preferentially handed to a worker on a host whose store already holds
  those objects (seeded by the master's own encode, by backend
  ``store_has`` probes, and organically by completions), so a broadcast
  payload is fetched where it already lives instead of crossing the
  wire again.
* **Straggler speculation** — per-chunk service times (dispatch →
  result arrival) feed the ``pool_chunk_duration_seconds`` histogram
  and a per-map reservoir; when a dispatched chunk's age exceeds
  ``speculation_quantile`` × the map's median while workers sit idle
  with an empty queue, the SAME payload is re-queued as a speculative
  duplicate. First result wins: ``ResultStore.fill`` already dedupes
  slots, the loser's result is discarded idempotently, and the reused
  envelope keeps the chunk's trace id — exactly the death-resubmit
  contract, so the two paths compose.
* **Fair multi-map queueing** — weighted deficit round-robin across the
  pool's concurrently active maps (``priority=`` in the map API sets
  the weight), so a small interactive map is not starved behind a
  10k-task ES generation.

The scheduler IS the pool's task queue: it keeps the ``put`` /
``get(timeout)`` / ``qsize`` / ``empty`` surface the dispatch loops
already speak (items stay ``(payload, (seq, base))`` tuples, ``None``
stays the shutdown sentinel), so the resubmission paths — death
reclaim, storemiss inline resend, reply-failure requeue — route through
policy unchanged. ``policy="fifo"`` degrades to a plain queue
(tests/test_sched.py holds its arrival order).
"""

from __future__ import annotations

import os
import queue as pyqueue
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from fiber_tpu import telemetry
from fiber_tpu.telemetry.flightrec import FLIGHT
from fiber_tpu.utils.logging import get_logger

logger = get_logger()

# Scheduler observability (docs/scheduling.md): every policy decision is
# a counted event, so placement/speculation claims are assertable from
# Pool.metrics() / the Prometheus endpoint instead of being folklore.
_m_decisions = telemetry.counter(
    "sched_decisions",
    "Scheduler policy decisions, by kind "
    "(locality|speculate|fair|range)")
_h_chunk_duration = telemetry.histogram(
    "pool_chunk_duration_seconds",
    "Chunk service time, handout to result arrival, seconds")
_g_host_inflight = telemetry.gauge(
    "sched_host_inflight_chunks",
    "Chunks currently dispatched and unfinished, by worker host")

#: How deep into the chosen map's queue the locality scan looks for a
#: chunk whose refs are already cached on the requesting host.
LOCALITY_SCAN = 16

#: Completed-chunk samples a map needs before speculation math runs —
#: below this the median is noise, not a signal.
SPEC_MIN_SAMPLES = 3

#: Absolute age floor for speculation, seconds: sub-threshold maps
#: (microbenchmark-sized chunks) must never speculate on scheduler
#: jitter alone.
SPEC_MIN_AGE = 0.05

#: Speculation monitor tick, seconds.
SPEC_TICK = 0.05

#: Recent per-chunk durations kept per map for the median estimate.
_DURATION_WINDOW = 64

_EMPTY_SET: frozenset = frozenset()

#: Live schedulers in this process, for telemetry.snapshot() — weak so
#: a GC'd pool drops out without bookkeeping.
_LIVE: "weakref.WeakSet[Scheduler]" = weakref.WeakSet()


def local_host_key() -> str:
    """This process's placement identity. Backends that pick the host at
    job-creation time stamp it into the job env (``FIBER_HOST_KEY``,
    keyed like their host tables); everything else falls back to the
    tracing plane's host id, so workers sharing a machine share a key."""
    key = os.environ.get("FIBER_HOST_KEY")
    if key:
        return key
    from fiber_tpu.telemetry import tracing

    return tracing.host_id()


def snapshots() -> List[Dict[str, Any]]:
    """Snapshots of every live scheduler in this process (the payload
    ``telemetry.snapshot()`` ships beside metrics/timers)."""
    out = []
    for sched in list(_LIVE):
        try:
            if not sched.closed:
                out.append(sched.snapshot())
        except Exception:  # noqa: BLE001 - operator snapshot
            continue
    return out


class _MapState:
    """Per-map scheduling state: its chunk queue, WDRR credit, ref
    digests per chunk, completed-chunk keys (to drop stale speculative
    duplicates), and the service-time reservoir."""

    __slots__ = ("seq", "weight", "queue", "credit", "digests",
                 "done_keys", "durations")

    def __init__(self, seq: int) -> None:
        self.seq = seq
        self.weight = 1.0
        self.queue: "deque[Tuple[bytes, Tuple[int, int]]]" = deque()
        self.credit = 0.0
        self.digests: Dict[Tuple[int, int], frozenset] = {}
        self.done_keys: set = set()
        self.durations: "deque[float]" = deque(maxlen=_DURATION_WINDOW)


class Scheduler:
    """One pool's handout policy. Thread-safe: the dispatch loop,
    submitting threads, the result loop, the failure detector's reclaim
    and the speculation monitor all call in concurrently."""

    def __init__(self, n_workers: int, policy: str = "adaptive",
                 locality: bool = True, speculation: bool = False,
                 speculation_quantile: float = 4.0,
                 is_done: Optional[Callable[[int], bool]] = None,
                 on_new_work: Optional[Callable[[], None]] = None) -> None:
        if policy not in ("adaptive", "fifo"):
            raise ValueError(f"unknown sched_policy {policy!r} "
                             "(want 'adaptive' or 'fifo')")
        self.policy = policy
        self.locality = bool(locality) and policy == "adaptive"
        self.speculation = bool(speculation) and policy == "adaptive"
        self._quantile = max(1.0, float(speculation_quantile))
        self._n_workers = int(n_workers)
        self._is_done = is_done
        self._on_new_work = on_new_work
        self._cond = threading.Condition()
        self._maps: Dict[int, _MapState] = {}
        self._ring: "deque[int]" = deque()  # active (queued-chunk) maps
        #: fifo policy only: one global arrival-order queue (the
        #: reference's handout), bypassing the ring entirely.
        self._fifo: "deque[Tuple[bytes, Tuple[int, int]]]" = deque()
        self._queued = 0
        self._sentinels = 0
        self.closed = False
        #: host -> set of object digests its store tier is known to hold.
        self._host_digests: Dict[str, set] = {}
        #: (seq, base) -> {ident: dispatch_t0}; a speculated chunk has
        #: two holders until the first result retires the key.
        self._inflight: Dict[Tuple[int, int], Dict[bytes, float]] = {}
        self._inflight_payload: Dict[Tuple[int, int], bytes] = {}
        self._inflight_host: Dict[Tuple[Tuple[int, int], bytes],
                                  Optional[str]] = {}
        self._speculated: set = set()
        #: seq -> original weight, for maps the policy plane throttled
        #: (budget_exceeded remediation — telemetry/policy.py). The
        #: original weight restores on unthrottle or map release.
        self._throttled: Dict[int, float] = {}
        #: streaming maps' hier range cap (seq -> max chunks per range
        #: handout, docs/streaming.md); popped on release_map.
        self._range_caps: Dict[int, int] = {}
        #: saved speculation quantile while the policy plane's
        #: straggler remediation holds it boosted (None = not boosted).
        self._quantile_base: Optional[float] = None
        #: exact per-pool decision counts (the registry twins aggregate
        #: across pools; tests and Pool.stats() read these).
        self.decisions: Dict[str, int] = {
            "locality": 0, "speculate": 0, "fair": 0, "range": 0}
        self._spec_stop = threading.Event()
        self._spec_thread: Optional[threading.Thread] = None
        if self.speculation:
            self._spec_thread = threading.Thread(
                target=self._spec_loop, name="fiber-sched-speculate",
                daemon=True)
            self._spec_thread.start()
        _LIVE.add(self)

    # -- queue surface (what the pool dispatch loops speak) -------------
    def put(self, item) -> None:
        with self._cond:
            if item is None:
                self._sentinels += 1
                self._cond.notify_all()
                return
            _payload, key = item
            if self._is_done is not None and self._is_done(key[0]):
                # Requeue of a completed/failed map's chunk (late death
                # reclaim): its state was already released — dropping
                # here keeps a resurrected seq from leaking map state.
                return
            st = self._ensure_map_locked(key[0])
            if key in st.done_keys:
                # Stale requeue (speculation loser's death-resubmit, or
                # a reclaim of an already-won chunk): the slot is filled,
                # re-running it would only burn a worker.
                return
            if self.policy == "fifo":
                self._fifo.append(item)
            else:
                st.queue.append(item)
                if key[0] not in self._ring:
                    self._ring.append(key[0])
            self._queued += 1
            self._cond.notify()

    def get(self, timeout: Optional[float] = None):
        """Next chunk in pure policy order (no requester identity — the
        plain push pool's egress loop)."""
        return self._get(None, None, timeout)

    def get_for(self, ident: Optional[bytes], host: Optional[str],
                timeout: Optional[float] = None):
        """Next chunk for one requesting worker: WDRR map choice, then a
        locality scan within the chosen map; never hands a worker its
        own chunk's speculative duplicate."""
        return self._get(ident, host, timeout)

    def qsize(self) -> int:
        with self._cond:
            return self._queued

    def empty(self) -> bool:
        return self.qsize() == 0

    def set_n_workers(self, n: int) -> None:
        """Retarget the worker-count estimate used by the speculation
        idle heuristic. Called by :meth:`fiber_tpu.pool.Pool.resize`
        (the serve tier's warm pool) — handout itself is demand-driven
        per requesting worker, so no queued state needs rebuilding."""
        with self._cond:
            self._n_workers = max(1, int(n))

    def load(self) -> Tuple[int, int]:
        """``(inflight_chunks, queued_chunks)`` snapshot — the warm
        pool's scaling signal (the same numbers the
        ``sched_host_inflight_chunks`` gauge and ``qsize`` export, read
        in one lock hold so the pair is consistent)."""
        with self._cond:
            return sum(len(h) for h in self._inflight.values()), \
                self._queued

    def _get(self, ident, host, timeout):
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._cond:
            while True:
                if self._sentinels:
                    self._sentinels -= 1
                    return None
                item = self._pick_locked(ident, host)
                if item is not None:
                    return item
                if deadline is None:
                    self._cond.wait(1.0)
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise pyqueue.Empty
                self._cond.wait(remaining)

    # -- map/chunk registration (pool._submit) ---------------------------
    def register_map(self, seq: int, priority: float = 1.0) -> None:
        # Weights are clamped to >= 1: the credit refill then always
        # clears the serve threshold in one ring visit, so a lone
        # low-priority map can never stall its own handout waiting for
        # fractional credit to accumulate. Boost hot maps ABOVE 1
        # instead of shrinking cold ones below it. (The ONE exception
        # is throttle_map below — a deliberate sub-1 weight from the
        # policy plane, bounded at 0.25 so the map still progresses.)
        with self._cond:
            st = self._ensure_map_locked(seq)
            st.weight = max(float(priority), 1.0)

    # -- policy-plane hooks (telemetry/policy.py remediations) -----------
    def throttle_map(self, seq: int, factor: float = 4.0) -> bool:
        """Cut one map's WDRR weight by ``factor`` (budget_exceeded
        remediation): the map keeps progressing — weight floors at
        0.25, so it gets one chunk per ~4 ring cycles — but stops
        crowding out in-budget tenants. Idempotent per map: a second
        throttle re-divides the ORIGINAL weight, not the throttled
        one. Returns whether the map exists."""
        factor = max(1.0, min(float(factor), 4.0))
        with self._cond:
            st = self._maps.get(seq)
            if st is None:
                return False
            original = self._throttled.setdefault(seq, st.weight)
            st.weight = max(0.25, original / factor)
            return True

    def unthrottle_map(self, seq: int) -> bool:
        """Restore a throttled map's original weight (the anomaly's
        clear-edge revert)."""
        with self._cond:
            original = self._throttled.pop(seq, None)
            st = self._maps.get(seq)
            if original is None or st is None:
                return False
            st.weight = original
            return True

    def boost_speculation(self, factor: float = 0.5) -> bool:
        """Lower the speculation quantile (straggler remediation):
        duplicates fire at ``factor``× the configured age threshold.
        Only meaningful when speculation is already on — the monitor
        thread isn't started retroactively, and duplicates are only
        safe for idempotent task functions (the pool's speculation
        opt-in contract), so the policy plane must not force them on.
        Returns whether a boost took effect."""
        with self._cond:
            if not self.speculation or self.closed:
                return False
            if self._quantile_base is None:
                self._quantile_base = self._quantile
            self._quantile = max(
                1.0, self._quantile_base * max(0.1, float(factor)))
            return True

    def restore_speculation(self) -> bool:
        """Undo boost_speculation (clear-edge revert)."""
        with self._cond:
            if self._quantile_base is None:
                return False
            self._quantile = self._quantile_base
            self._quantile_base = None
            return True

    def register_chunk(self, key: Tuple[int, int],
                       digests: Iterable[str]) -> None:
        digs = frozenset(digests)
        if not digs:
            return
        with self._cond:
            self._ensure_map_locked(key[0]).digests[key] = digs

    def note_stream(self, seq: int, cap: int) -> None:
        """Mark ``seq`` as a STREAMING map with a per-handout range cap
        (docs/streaming.md "window-aware handout"): hierarchical range
        top-ups for this map stop at ``cap`` chunks, so one sub-master
        can never swallow a whole admission window and starve the other
        hosts inside it."""
        with self._cond:
            self._range_caps[seq] = max(1, int(cap))

    def range_cap(self, seq: int) -> Optional[int]:
        """The hier range-chunk cap for ``seq`` (None: not a stream —
        the configured ``dispatch_range_chunks`` applies unbounded)."""
        with self._cond:
            return self._range_caps.get(seq)

    def release_map(self, seq: int) -> None:
        """Drop one completed/failed map's state: queued leftovers
        (speculative duplicates, late resubmits), inflight entries, and
        metadata. Fired from the map's completion callback."""
        with self._cond:
            st = self._maps.pop(seq, None)
            self._throttled.pop(seq, None)
            self._range_caps.pop(seq, None)
            if st is not None:
                self._queued -= len(st.queue)
                st.queue.clear()
            try:
                self._ring.remove(seq)
            except ValueError:
                pass
            if self._fifo:
                kept = deque(it for it in self._fifo
                             if it[1][0] != seq)
                self._queued -= len(self._fifo) - len(kept)
                self._fifo = kept
            for key in [k for k in self._inflight if k[0] == seq]:
                self._drop_inflight_locked(key)
            self._speculated = {k for k in self._speculated
                                if k[0] != seq}
            self._cond.notify_all()

    # -- locality knowledge ----------------------------------------------
    def note_host_has(self, host: Optional[str],
                      digests: Iterable[str]) -> None:
        if not host or not self.locality:
            return
        with self._cond:
            known = self._host_digests.setdefault(host, set())
            if len(known) > 8192:
                # Bound the locality map on long-lived pools: stale
                # knowledge costs one ordinary (non-local) handout,
                # never correctness.
                known.clear()
            known.update(digests)

    def note_range(self, n_chunks: int) -> None:
        """Count one hierarchical-dispatch range handout (``n_chunks``
        chunks left in ONE frame to a per-host sub-master instead of
        ``n_chunks`` frames to individual workers — docs/scheduling.md,
        docs/architecture.md hierarchical dispatch)."""
        self.decisions["range"] = self.decisions.get("range", 0) + 1
        _m_decisions.inc(kind="range")
        if FLIGHT.enabled:
            FLIGHT.record("sched", "range", chunks=n_chunks,
                          reason="hierarchical handout: one frame, "
                                 f"{n_chunks} chunk(s)")

    # -- dispatch lifecycle (pool serve/result/reclaim hooks) ------------
    def dispatched(self, key: Tuple[int, int], ident: bytes,
                   host: Optional[str], payload) -> None:
        with self._cond:
            self._inflight.setdefault(key, {})[ident] = time.monotonic()
            self._inflight_payload[key] = payload
            self._inflight_host[(key, ident)] = host
        _g_host_inflight.inc(host=host or "unknown")

    def completed(self, key: Tuple[int, int], ident: bytes,
                  host: Optional[str] = None) -> None:
        """First result for ``key`` retires every holder (the
        speculation loser's late duplicate finds nothing and is a
        no-op); the winner's copy contributes the duration sample."""
        duration = None
        digests = None
        with self._cond:
            holders = self._inflight.get(key)
            if holders is not None:
                t0 = holders.get(ident)
                if t0 is not None:
                    duration = time.monotonic() - t0
                self._drop_inflight_locked(key)
            st = self._maps.get(key[0])
            if st is not None:
                st.done_keys.add(key)
                if duration is not None:
                    st.durations.append(duration)
                digests = st.digests.get(key)
        if duration is not None:
            _h_chunk_duration.observe(duration)
            if FLIGHT.enabled:
                # Per-chunk service time (handout -> result): the
                # explain layer's straggler signal — outliers vs the
                # map's median are the blamed seconds.
                FLIGHT.record("sched", "chunk_done", seq=key[0],
                              base=key[1], dur=round(duration, 6),
                              host=host)
        if digests:
            # Organic locality learning: the completing host resolved
            # (and its store tier now caches) these objects.
            self.note_host_has(host, digests)

    def abandon(self, key: Tuple[int, int], ident: bytes) -> None:
        """One holder's copy is coming back to the queue (storemiss
        resend, reply failure) — retire its inflight entry without a
        duration sample."""
        with self._cond:
            self._drop_holder_locked(key, ident)

    def abandon_ident(self, ident: bytes) -> None:
        """A worker died: every chunk copy it held stops aging (the
        pool's reclaim re-queues the payloads through put())."""
        with self._cond:
            for key in [k for k, holders in self._inflight.items()
                        if ident in holders]:
                self._drop_holder_locked(key, ident)

    def _drop_holder_locked(self, key, ident) -> None:
        holders = self._inflight.get(key)
        if holders is None or ident not in holders:
            return
        del holders[ident]
        host = self._inflight_host.pop((key, ident), None)
        _g_host_inflight.dec(host=host or "unknown")
        if not holders:
            del self._inflight[key]
            self._inflight_payload.pop(key, None)

    def _drop_inflight_locked(self, key) -> None:
        holders = self._inflight.pop(key, {})
        for ident in holders:
            host = self._inflight_host.pop((key, ident), None)
            _g_host_inflight.dec(host=host or "unknown")
        self._inflight_payload.pop(key, None)

    # -- core policy ------------------------------------------------------
    def _ensure_map_locked(self, seq: int) -> _MapState:
        st = self._maps.get(seq)
        if st is None:
            st = self._maps[seq] = _MapState(seq)
        return st

    def _pick_locked(self, ident, host):
        if self.policy == "fifo":
            return self._pick_fifo_locked()
        if self._queued <= 0 or not self._ring:
            return None
        # WDRR over active maps: the head map serves while its credit
        # lasts (credit += weight on each refill visit, -1 per chunk),
        # then rotates — so over one full ring cycle map i gets
        # weight_i chunks. A map that is ineligible for THIS requester
        # (only its own speculative dup queued) is skipped uncharged.
        # The loop bound covers throttled maps too: a 0.25-weight map
        # needs 4 refill visits before it can serve, so a ring of
        # nothing but throttled maps must still hand out within one
        # call.
        for _ in range(4 * len(self._ring) + 8):
            if not self._ring:
                return None
            seq = self._ring[0]
            st = self._maps.get(seq)
            if st is None or not self._purge_head_locked(st):
                self._ring.popleft()
                if st is not None:
                    st.credit = 0.0
                continue
            if st.credit < 1.0:
                st.credit += st.weight
                if st.credit < 1.0:
                    self._ring.rotate(-1)
                    continue
            item = self._take_from_map_locked(st, ident, host)
            if item is None:
                self._ring.rotate(-1)
                continue
            st.credit -= 1.0
            if not st.queue:
                self._ring.popleft()
                st.credit = 0.0
            elif st.credit < 1.0:
                self._ring.rotate(-1)
            self._queued -= 1
            if any(s < seq and self._maps[s].queue
                   for s in self._ring if s in self._maps):
                # Fairness actively reordered: an older map still has
                # queued chunks but this one's turn came first.
                self.decisions["fair"] += 1
                _m_decisions.inc(kind="fair")
            return item
        return None

    def _pick_fifo_locked(self):
        # Strict arrival order across maps (the reference's handout).
        while self._fifo:
            item = self._fifo.popleft()
            self._queued -= 1
            st = self._maps.get(item[1][0])
            if st is not None and item[1] in st.done_keys:
                continue
            return item
        return None

    def _purge_head_locked(self, st: _MapState) -> bool:
        """Drop completed chunks off the queue head (speculation
        leftovers); True while the map still has live work."""
        while st.queue and st.queue[0][1] in st.done_keys:
            st.queue.popleft()
            self._queued -= 1
        return bool(st.queue)

    def _take_from_map_locked(self, st: _MapState, ident, host):
        """Pick one chunk from ``st``: the first eligible, unless the
        locality scan finds a chunk whose refs the requesting host
        already caches. Never returns a chunk the requester itself is
        already computing (its own speculative duplicate)."""
        q = st.queue
        host_set = (self._host_digests.get(host, _EMPTY_SET)
                    if (self.locality and host) else _EMPTY_SET)
        fallback = None
        chosen = None
        for i in range(min(len(q), LOCALITY_SCAN)):
            key = q[i][1]
            if key in st.done_keys:
                continue
            holders = self._inflight.get(key)
            if ident is not None and holders and ident in holders:
                continue
            if fallback is None:
                fallback = i
            digs = st.digests.get(key)
            if digs and digs <= host_set:
                chosen = i
                break
            if not host_set and fallback is not None:
                break  # no locality dimension: first eligible wins
        idx = chosen if chosen is not None else fallback
        if idx is None:
            return None
        item = q[idx]
        del q[idx]
        digs = st.digests.get(item[1])
        if digs and host and digs <= self._host_digests.get(host,
                                                            _EMPTY_SET):
            self.decisions["locality"] += 1
            _m_decisions.inc(kind="locality")
            if FLIGHT.enabled:
                FLIGHT.record(
                    "sched", "locality", seq=item[1][0], base=item[1][1],
                    host=host,
                    reason=f"host caches {len(digs)} ref digest(s)")
        return item

    # -- straggler speculation --------------------------------------------
    def _spec_loop(self) -> None:
        while not self._spec_stop.wait(SPEC_TICK):
            try:
                self.speculate_once()
            except Exception:
                logger.exception("sched: speculation tick failed")

    def speculate_once(self) -> int:
        """One monitor pass: re-queue a duplicate of every dispatched
        chunk whose age exceeds ``speculation_quantile`` × its map's
        median service time, while spare workers are idle and the queue
        is drained (tail-of-map — the only regime where a duplicate
        buys wall-clock instead of burning it). Each chunk is
        speculated at most once. Returns how many duplicates fired."""
        now = time.monotonic()
        fired = 0
        with self._cond:
            if self._queued > 0:
                return 0
            busy = set()
            for holders in self._inflight.values():
                busy.update(holders)
            idle = self._n_workers - len(busy)
            if idle <= 0:
                return 0
            for key, holders in list(self._inflight.items()):
                if key in self._speculated:
                    continue
                st = self._maps.get(key[0])
                if st is None or key in st.done_keys:
                    continue
                if self._is_done is not None and self._is_done(key[0]):
                    continue
                if len(st.durations) < SPEC_MIN_SAMPLES:
                    continue
                durs = sorted(st.durations)
                median = durs[len(durs) // 2]
                threshold = max(self._quantile * median, SPEC_MIN_AGE)
                if now - min(holders.values()) < threshold:
                    continue
                payload = self._inflight_payload.get(key)
                if payload is None:
                    continue
                # Head of the line: the duplicate is the oldest work in
                # the pool. Same payload bytes = same envelope = same
                # trace id (the death-resubmit envelope-reuse rule).
                st.queue.appendleft((payload, key))
                self._queued += 1
                if key[0] not in self._ring:
                    self._ring.append(key[0])
                self._speculated.add(key)
                self.decisions["speculate"] += 1
                FLIGHT.record(
                    "sched", "speculate", seq=key[0], base=key[1],
                    age=round(now - min(holders.values()), 4),
                    reason=(f"age > {self._quantile:g}x median "
                            f"{median:.4f}s with {idle} idle worker(s)"))
                fired += 1
                idle -= 1
                if idle <= 0:
                    break
            if fired:
                self._cond.notify_all()
        if fired:
            _m_decisions.inc(fired, kind="speculate")
            logger.info("sched: speculated %d straggler chunk(s)", fired)
            cb = self._on_new_work
            if cb is not None:
                try:
                    cb()
                except Exception:
                    logger.exception("sched: on_new_work callback failed")
        return fired

    # -- operator surface --------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Picklable operator view: queue depths, per-host in-flight
        chunk counts, decision totals (rides telemetry.snapshot() and
        the ``fiber-tpu status``/``metrics`` CLI)."""
        with self._cond:
            hosts: Dict[str, int] = {}
            for (_key, _ident), host in self._inflight_host.items():
                hk = host or "unknown"
                hosts[hk] = hosts.get(hk, 0) + 1
            return {
                "policy": self.policy,
                "locality": self.locality,
                "speculation": self.speculation,
                "queued": self._queued,
                "inflight": sum(len(h) for h in self._inflight.values()),
                "hosts": hosts,
                "maps": {str(seq): len(st.queue)
                         for seq, st in self._maps.items() if st.queue},
                "decisions": dict(self.decisions),
            }

    def close(self) -> None:
        self.closed = True
        self._spec_stop.set()
        with self._cond:
            self._cond.notify_all()
