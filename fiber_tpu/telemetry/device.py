"""Device telemetry plane: visibility into the JAX device boundary
(docs/observability.md "Device telemetry").

The five CPU planes are deeply observable, but the thing this framework
exists to drive — the device plane — was a black box: every
``jax.device_put`` untimed, HBM usage invisible, a recompile storm
indistinguishable from slow compute, MFU computed nowhere.
This module is the missing instrument panel:

* **Transfer accounting** — :func:`transfer` wraps the host→device
  boundary (store resolution, serialization deserialize, the device_map
  plan, checkpoint restore) and records per-site
  ``device_transfer_seconds`` / ``device_transfer_bytes`` histograms,
  a tracing span when a trace context is ambient, and a flight event —
  so ``fiber-tpu explain`` can grow a ``transfer`` blame category.
* **Compile observability** — ``jax.monitoring`` event/duration
  listeners count compiles, compile seconds and persistent-cache hits,
  and a fingerprint-keyed recompile detector feeds the watchdog's
  ``recompile_storm`` rule: the SAME logical function compiling over
  and over is shape churn, not progress. The time-span listener stores
  each phase (``jax.trace``, ``jax.lower``, ``jax.backend_compile``) as
  a span under the ambient one, with JAX's ``fun_name`` and, on the
  backend phase, whether the persistent cache hit — so a compile
  inside call k hangs from call k's span and says which function it
  was. A trace inside another phase is folded into that phase's span.
* **Step calls** — :func:`step` is the span and the two counters
  (``device_steps``, ``device_step_units``) around one call of a
  device-plane step function (``es.run_fused``, ``lm.train_step``).
  The span carries the host's account of the call (:class:`StepCalls`):
  what the calling thread did inside it and since its last one (CPU
  time, collector time), the
  ``step_stall`` rule over the periods from call to call, and the call
  in flight for the sampler's ``monitor.tick`` to name.
* **Device gauges** — per-process HBM ``memory_stats()``
  (bytes_in_use / limit; honestly ``None`` on CPU),
  live-array count/bytes, pushed into the registry each monitor tick
  so the PR-8 time-series and the ``hbm_fill`` anomaly rule see them.
* **Live MFU** — whenever a device peak resolves
  (:mod:`fiber_tpu.utils.flops`), per-map achieved FLOP/s divide into
  the ``pool_map_mfu`` gauge; CPU runs record ``None`` honestly.

Design constraints, mirrored from the rest of the plane:

* **Near-zero when off** — ``device_telemetry_enabled=False`` (or the
  telemetry master switch) reduces every hook to one attribute check.
* **Null-safe everywhere** — no probe may *initialize* a jax backend
  (``jax`` absent from ``sys.modules`` means every device field is
  ``None``), and a CPU ``memory_stats()`` returning None/empty records
  ``None`` honestly instead of raising.
* **Picklable snapshots** — :func:`snapshot` is the payload of the
  host agent's ``device_snapshot`` op, ``cluster_devices()`` on both
  backends, the worker's ``("dev", …)`` result-stream frames, and
  ``Pool.device_stats()``.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import gc
import os
import statistics
import sys
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from fiber_tpu import telemetry
from fiber_tpu.telemetry import tracing
from fiber_tpu.telemetry.flightrec import FLIGHT

# Registry twins (docs/observability.md metric catalog). Histograms for
# both axes: the bucket shape answers "are transfers many-small or
# few-huge" and sum/count give the totals the snapshots expose.
_m_transfer_seconds = telemetry.histogram(
    "device_transfer_seconds",
    "Host->device transfer boundary seconds, by site",
    buckets=(0.0001, 0.001, 0.01, 0.1, 1.0, 10.0))
_m_transfer_bytes = telemetry.histogram(
    "device_transfer_bytes",
    "Host->device transfer boundary payload bytes, by site",
    buckets=(1 << 10, 1 << 16, 1 << 20, 1 << 24, 1 << 28))
_m_compiles = telemetry.counter(
    "device_compiles", "XLA compilations observed in this process")
_m_compile_seconds = telemetry.counter(
    "device_compile_seconds", "XLA compilation seconds in this process")
_m_steps = telemetry.counter(
    "device_steps", "Calls of a device-plane step function, by fn")
_m_step_units = telemetry.counter(
    "device_step_units",
    "Work those calls asked for, by fn (ES: generations x population; "
    "LM: tokens)")
_m_step_stalls = telemetry.counter(
    "device_step_stalls",
    "Periods from one call of a device-plane step function to the next "
    "that the step_stall rule found stalled, by fn")
_m_rollout_traces = telemetry.counter(
    "policy_rollout_traces",
    "Env rollouts traced, by policy class and params: 'prepared' "
    "(unflattened once, before the step scan), 'pair' (an antithetic "
    "pair's parts unflattened there, summed in the step: the ES engine "
    "reads each pair's noise once) or 'flat' (cut on every step: the "
    "callable's owner offers no unflatten)")
_m_moe_traces = telemetry.counter(
    "moe_layers_traced",
    "Sparse-expert layers traced, by experts held here, experts in "
    "all, experts a token takes and the form of the backward pass's "
    "dispatch walk ('first+loop': the first chunk on its own, a loop "
    "over the rest)")
_m_ssm_traces = telemetry.counter(
    "ssm_layers_traced",
    "State-space layers traced, by heads, state size, groups, chunk, "
    "whether the backward pass recomputes the mixer and the form the "
    "scan runs in (kernel or plain)")
_m_passes_traces = telemetry.counter(
    "lm_passes_traced",
    "Forward passes traced of a model whose stack of layers runs more "
    "than once or whose step recomputes, by passes over the stack, "
    "layers in it, what the backward pass recomputes and what it keeps "
    "of a layer application")
_m_latent_traces = telemetry.counter(
    "latent_layers_traced",
    "Latent-attention (MLA) layer applications traced, by heads, the "
    "query and key-value ranks and the query/key and value widths")
_m_conv_traces = telemetry.counter(
    "conv_layers_traced",
    "Gated short-convolution layer applications traced, by the "
    "convolution's positions, the model's width and the form it runs in "
    "(kernel or plain)")
_m_mtp_traces = telemetry.counter(
    "mtp_traced",
    "Losses traced with a multi-token-prediction term, by the module's "
    "depth and the term's weight")
_m_flash_grid_steps = telemetry.counter(
    "flash_grid_steps",
    "Inner grid steps a head makes in the flash-attention programs "
    "built (for dkv a kv-head), by kernel, grid ('folded': the causal "
    "triangle's rows paired, 'band', 'square' without a causal mask) "
    "and state: 'run' computes, 'idle' only exists")
_m_ring_rotations = telemetry.counter(
    "ring_rotations_traced",
    "Rotations of the ring-attention programs built, summed over the "
    "ring's chips, by the order the rows lie in ('zigzag' or "
    "'contiguous') and state: 'run' attends, 'skip' is a rotation the "
    "causal mask leaves a chip nothing of")
_g_moe_load_max = telemetry.gauge(
    "moe_expert_load_max",
    "Tokens of the last probed batch on the most loaded held expert, "
    "by expert layer")
_g_moe_load_mean = telemetry.gauge(
    "moe_expert_load_mean",
    "Tokens of the last probed batch on a held expert, mean over the "
    "held experts, by expert layer")
_g_moe_chunks = telemetry.gauge(
    "moe_chunks",
    "Chunks the dispatch walks make over the held pairs of the last "
    "probed batch, by expert layer: at 1 the backward pass's loop over "
    "the chunks after the first makes no trip")
_g_hbm_in_use = telemetry.gauge(
    "device_hbm_bytes_in_use", "HBM bytes in use on the first local device")
_g_hbm_limit = telemetry.gauge(
    "device_hbm_bytes_limit", "HBM byte capacity of the first local device")
_g_live_arrays = telemetry.gauge(
    "device_live_arrays", "Live jax.Array count in this process")
_g_live_array_bytes = telemetry.gauge(
    "device_live_array_bytes", "Live jax.Array bytes in this process")
_g_map_mfu = telemetry.gauge(
    "pool_map_mfu",
    "MFU of the last device map whose device peak resolved")


#: JAX's compile phases (jax/_src/dispatch.py) -> span names
_COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
}


class DeviceTelemetry:
    """Per-process device-plane aggregate; see module docstring."""

    def __init__(self) -> None:
        self.enabled = True
        self._lock = threading.Lock()
        # site -> [count, seconds, bytes]
        self._transfers: Dict[str, list] = {}
        #: Bumped on every recorded transfer/compile — workers ship a
        #: fresh snapshot on the result stream only when this moved.
        self.revision = 0
        # compile observability
        self._compiles = 0
        self._compile_seconds = 0.0
        self._cache_hits = 0  # persistent compilation cache hits
        self._fingerprints: Dict[str, int] = {}
        self._recompiles: "collections.deque" = collections.deque(
            maxlen=256)  # (mono, fingerprint)
        self.storm_count = 4
        self.storm_window_s = 30.0
        self._listeners_installed = False
        self._install_lock = threading.Lock()
        # per compiling thread: the last persistent-cache outcome
        # (``cache``, consumed by the backend-compile span that
        # encloses it) and the compile phases in progress (``phases``)
        self._compiling = threading.local()
        # last live-MFU observation (None values are honest nulls)
        self._mfu: Dict[str, Any] = {
            "mfu": None, "flops_per_sec": None, "peak_row": None,
            "items": None, "wall_s": None,
        }
        # last gauge probe (kept so snapshots are cheap + honest)
        self._hbm: Dict[str, Optional[int]] = {
            "bytes_in_use": None, "bytes_limit": None}
        self._live: Dict[str, Optional[int]] = {
            "count": None, "bytes": None}
        # last XLA profiler capture (utils/profiling.trace notes it so
        # trace_dump can merge the device timeline without being told)
        self._xla_trace: Optional[str] = None

    # -- transfer accounting -------------------------------------------
    @contextlib.contextmanager
    def transfer(self, site: str, nbytes: int = 0) -> Iterator[None]:
        """Time one host→device boundary crossing. Off, the cost is one
        attribute check; on, the observation lands in the registry
        histograms, the flight recorder, and (when a trace context is
        ambient — i.e. inside a traced chunk) a ``device.transfer``
        span so the transfer shows up in the map's timeline."""
        if not self.enabled:
            yield
            return
        span_ctx = (tracing.span("device.transfer", site=site,
                                 bytes=int(nbytes))
                    if tracing.current() is not None
                    else contextlib.nullcontext())
        t0 = time.perf_counter()
        try:
            with span_ctx:
                yield
        finally:
            self.add_transfer(site, time.perf_counter() - t0, nbytes)

    def add_transfer(self, site: str, seconds: float,
                     nbytes: int = 0) -> None:
        """Record one completed transfer (the non-context form)."""
        if not self.enabled:
            return
        nbytes = int(nbytes)
        with self._lock:
            agg = self._transfers.get(site)
            if agg is None:
                agg = self._transfers[site] = [0, 0.0, 0]
            agg[0] += 1
            agg[1] += seconds
            agg[2] += nbytes
            self.revision += 1
        _m_transfer_seconds.observe(seconds, site=site)
        _m_transfer_bytes.observe(float(nbytes), site=site)
        # Accounting plane: the transfer bills the map whose chunk is
        # ambient (the worker's store_resolve path), else overhead. The
        # `ici` site (device-tier placement/fan-out) bills its own field
        # too, so Pool.cost()/explain split blame: bytes that rode the
        # mesh vs bytes that crossed sockets.
        from fiber_tpu.telemetry.accounting import COSTS

        if site == "ici":
            COSTS.bill_ambient(device_transfer_bytes=nbytes,
                               device_transfer_s=seconds,
                               ici_bytes=nbytes)
        else:
            COSTS.bill_ambient(device_transfer_bytes=nbytes,
                               device_transfer_s=seconds)
        if FLIGHT.enabled:
            FLIGHT.record("device", "transfer", site=site,
                          bytes=nbytes, s=round(seconds, 6))

    # -- compile observability -----------------------------------------
    def install_listeners(self) -> bool:
        """Register the jax.monitoring compile listeners (idempotent).
        NEVER imports jax: a process that hasn't loaded it (lite pool
        workers, host agents) must not pay a multi-second interpreter
        tax for telemetry — False means deferred, and installation is
        retried from the gauge probe and compile notes once jax shows
        up."""
        if self._listeners_installed:
            return True
        if "jax" not in sys.modules:
            return False
        # The sampler thread's probe and the main thread both come
        # here: registered twice, every compile would count twice.
        with self._install_lock:
            if self._listeners_installed:
                return True
            from jax import monitoring

            monitoring.register_event_listener(self._on_jax_event)
            monitoring.register_event_duration_secs_listener(
                self._on_jax_duration)
            monitoring.register_event_time_span_listener(
                self._on_jax_time_span)
            monitoring.register_scalar_listener(self._on_jax_scalar)
            self._listeners_installed = True
        # jax is fully imported by now, its exit hooks registered: the
        # sampler's (whose probes call into jax) goes in after them
        from fiber_tpu.telemetry.timeseries import stop_at_exit

        stop_at_exit()
        return True

    def _on_jax_event(self, event: str, **kwargs: Any) -> None:
        # jax emits many event kinds; only compilation concerns us. A
        # persistent-cache hit is precisely not a compilation: it is
        # counted on its own. A miss is one, but the event names no
        # program, so it moves the totals and stays out of the
        # fingerprint-keyed storm detector (every program's miss would
        # otherwise look like ONE function recompiling).
        if event == "/jax/compilation_cache/cache_hits":
            self._compiling.cache = "hit"
            with self._lock:
                self._cache_hits += 1
            return
        if event != "/jax/compilation_cache/cache_misses":
            return
        self._compiling.cache = "miss"
        if not self.enabled:
            return
        with self._lock:
            self._compiles += 1
            self.revision += 1
        _m_compiles.inc()

    def _on_jax_duration(self, event: str, duration: float,
                         **kwargs: Any) -> None:
        # Trace/lower/backend-compile durations; the persistent cache's
        # own durations (time SAVED by a hit, retrieval time) are not
        # compilation seconds.
        if "compil" not in event or "compilation_cache" in event:
            return
        if not self.enabled:
            return
        with self._lock:
            self._compile_seconds += float(duration)
            self.revision += 1
        _m_compile_seconds.inc(float(duration))
        from fiber_tpu.telemetry.accounting import COSTS

        COSTS.bill_ambient(compile_s=float(duration))

    def _on_jax_scalar(self, event: str, value: float,
                       **kwargs: Any) -> None:
        # JAX announces the start of a compile phase as a scalar (its
        # start time): the phases in progress on this thread, each with
        # the count and the seconds of the traces folded into it.
        if event in _COMPILE_SPANS:
            phases = getattr(self._compiling, "phases", None)
            if phases is None:
                phases = self._compiling.phases = []
            phases.append([0, 0.0])

    def _on_jax_time_span(self, event: str, start: float, end: float,
                          **kwargs: Any) -> None:
        # The same three events as _on_jax_duration, with their epoch
        # start and end and JAX's name of the function: one span each,
        # a child of whatever span the compiling thread is inside.
        # Traces nest: every jitted function met while another is
        # traced is traced in turn (a thousand small ones under one
        # train step), and lowering a Pallas kernel traces hundreds
        # more. A trace that ends inside another phase is folded into
        # that phase's ``nested`` / ``nested_s`` (compile_seconds counts
        # it twice: for itself and inside the outer one's duration).
        name = _COMPILE_SPANS.get(event)
        if name is None:
            return
        phases = getattr(self._compiling, "phases", None)
        nested, nested_s = phases.pop() if phases else (0, 0.0)
        if not self.enabled:
            return
        if name == "jax.trace" and phases:
            phases[-1][0] += 1 + nested
            phases[-1][1] += (end - start) + nested_s
            return
        attrs = {"fun_name": str(kwargs.get("fun_name", ""))}
        if nested:
            attrs.update(nested=nested, nested_s=nested_s)
        if name == "jax.backend_compile":
            attrs["cache"] = getattr(self._compiling, "cache", None)
            self._compiling.cache = None
        tracing.record(name, int(start * 1e9), int(end * 1e9), **attrs)

    def note_compile(self, fingerprint: str) -> None:
        """One compilation (or compile-cache miss) of the logical
        program named by ``fingerprint``. The device_map plan calls this
        on every compile-cache miss; the jax.monitoring listener calls
        it with the event key. The same fingerprint recurring inside
        ``storm_window_s`` is the recompile-storm signal."""
        if not self.enabled:
            return
        self.install_listeners()  # a compile implies jax is loaded
        now = time.monotonic()
        with self._lock:
            self._compiles += 1
            self._fingerprints[fingerprint] = \
                self._fingerprints.get(fingerprint, 0) + 1
            if len(self._fingerprints) > 128:
                # Bound the table; a storm is about repeats, not breadth.
                self._fingerprints.pop(next(iter(self._fingerprints)))
            self._recompiles.append((now, fingerprint))
            self.revision += 1
        _m_compiles.inc()
        if FLIGHT.enabled:
            FLIGHT.record("device", "compile",
                          fingerprint=str(fingerprint)[:48],
                          count=self._fingerprints.get(fingerprint, 1))

    def recompile_state(self) -> Dict[str, Any]:
        """The watchdog's per-tick probe: is any single fingerprint
        compiling repeatedly inside the storm window?"""
        cutoff = time.monotonic() - float(self.storm_window_s)
        with self._lock:
            recent: Dict[str, int] = {}
            for mono, fp in self._recompiles:
                if mono >= cutoff:
                    recent[fp] = recent.get(fp, 0) + 1
        if not recent:
            return {"storm": False, "fingerprint": None, "count": 0}
        fp = max(recent, key=recent.get)
        return {"storm": recent[fp] >= int(self.storm_count),
                "fingerprint": fp, "count": recent[fp],
                "window_s": float(self.storm_window_s)}

    # -- device gauges --------------------------------------------------
    def update_gauges(self) -> None:
        """Refresh HBM / live-array gauges (the monitor sampler's
        per-tick probe). Never initializes a jax backend: with jax not
        yet imported every field stays None — honest, not zero."""
        if not self.enabled:
            return
        self.install_listeners()  # retry once jax appears (no-op else)
        hbm = _hbm_stats()
        live = _live_array_stats()
        with self._lock:
            self._hbm = hbm
            self._live = live
        if hbm["bytes_in_use"] is not None:
            _g_hbm_in_use.set(float(hbm["bytes_in_use"]))
        if hbm["bytes_limit"] is not None:
            _g_hbm_limit.set(float(hbm["bytes_limit"]))
        if live["count"] is not None:
            _g_live_arrays.set(float(live["count"]))
            _g_live_array_bytes.set(float(live["bytes"] or 0))

    # -- live MFU -------------------------------------------------------
    def note_map_flops(self, flops: float, wall_s: float,
                       items: int) -> Optional[float]:
        """One device map finished having executed ``flops`` analytic
        FLOPs in ``wall_s``. When the device peak resolves
        (utils/flops.py — a TPU kind in its table), the MFU lands in
        the ``pool_map_mfu`` gauge; otherwise the observation records
        ``mfu: None`` honestly (CPU posture). A TPU missing from the
        table raises. Returns the MFU or None."""
        if not self.enabled or wall_s <= 0:
            return None
        from fiber_tpu.utils import flops as flopsmod

        value = None
        fps = float(flops) / wall_s
        peak = {"peak_row": None}
        devices = _devices()
        if devices:
            value = flopsmod.mfu(fps, devices)
            peak = flopsmod.peak_report(devices)
        with self._lock:
            self._mfu = {"mfu": value, "flops_per_sec": fps,
                         "peak_row": peak.get("peak_row"),
                         "items": int(items), "wall_s": round(wall_s, 6)}
            self.revision += 1
        if value is not None:
            _g_map_mfu.set(float(value))
        if FLIGHT.enabled:
            FLIGHT.record("device", "mfu", mfu=value,
                          flops_per_sec=round(fps, 3),
                          peak_row=peak.get("peak_row"))
        return value

    # -- unified timeline ----------------------------------------------
    def note_xla_trace(self, log_dir: str) -> None:
        """utils/profiling.trace records where the XLA profiler wrote
        its capture, so ``Pool.trace_dump`` can merge the device
        timeline beside the host spans without being told the
        directory."""
        with self._lock:
            self._xla_trace = str(log_dir)

    def last_xla_trace(self) -> Optional[str]:
        with self._lock:
            return self._xla_trace

    # -- read side ------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Picklable per-process device-plane surface (agent
        ``device_snapshot`` op / ``Pool.device_stats()`` / worker
        ``("dev", …)`` frames). Null fields are honest: this process
        has no device runtime, not 'zero bytes of HBM'."""
        with self._lock:
            transfers = {site: {"count": agg[0],
                                "seconds": round(agg[1], 6),
                                "bytes": agg[2]}
                         for site, agg in self._transfers.items()}
            out = {
                "host": tracing.host_id(),
                "pid": os.getpid(),
                "enabled": self.enabled,
                "revision": self.revision,
                "transfers": transfers,
                "transfer_bytes": sum(a[2]
                                      for a in self._transfers.values()),
                "transfer_seconds": round(
                    sum(a[1] for a in self._transfers.values()), 6),
                "compiles": self._compiles,
                "compile_seconds": round(self._compile_seconds, 6),
                "compile_cache_hits": self._cache_hits,
                "compile_fingerprints": dict(self._fingerprints),
                "hbm": dict(self._hbm),
                "live_arrays": dict(self._live),
                "mfu": dict(self._mfu),
            }
        out["recompile"] = self.recompile_state()
        out["platform"] = _platform()
        out["jax_monitoring"] = bool(self._listeners_installed)
        return out

    def configure(self, cfg) -> None:
        """Follow the config knobs (telemetry.refresh)."""
        self.enabled = bool(cfg.telemetry_enabled) \
            and bool(cfg.device_telemetry_enabled)
        self.storm_count = max(2, int(cfg.anomaly_recompile_count))
        self.storm_window_s = max(1.0,
                                  float(cfg.anomaly_recompile_window_s))
        if self.enabled:
            self.install_listeners()

    def clear(self) -> None:
        with self._lock:
            self._transfers.clear()
            self._compiles = 0
            self._compile_seconds = 0.0
            self._cache_hits = 0
            self._fingerprints.clear()
            self._recompiles.clear()
            self.revision = 0
            self._mfu = {"mfu": None, "flops_per_sec": None,
                         "peak_row": None, "items": None, "wall_s": None}
            self._hbm = {"bytes_in_use": None, "bytes_limit": None}
            self._live = {"count": None, "bytes": None}
            self._xla_trace = None
        CALLS.clear()


# ---------------------------------------------------------------------------
# Null-safe device probes (never initialize a backend, never raise)
# ---------------------------------------------------------------------------


def _devices():
    """Local jax devices, or None when jax was never imported here —
    probing must not pay (or trigger) a backend initialization in a
    process that does no device work (host agents, lite workers)."""
    if "jax" not in sys.modules:
        return None
    try:
        import jax

        return jax.local_devices()
    except Exception:  # noqa: BLE001 - no backend is a valid state
        return None


def _platform() -> Optional[str]:
    devices = _devices()
    if not devices:
        return None
    return getattr(devices[0], "platform", None)


def _hbm_stats() -> Dict[str, Optional[int]]:
    """First-local-device memory stats: ``{"bytes_in_use", "bytes_limit"}``,
    both None when unavailable (CPU backends return None or an empty
    dict from ``memory_stats()``)."""
    devices = _devices()
    if not devices:
        return {"bytes_in_use": None, "bytes_limit": None}
    try:
        stats = devices[0].memory_stats()
    except Exception:  # noqa: BLE001 - a PJRT error must not kill the probe
        stats = None
    if not stats:
        return {"bytes_in_use": None, "bytes_limit": None}
    return {
        "bytes_in_use": _maybe_int(stats.get("bytes_in_use")),
        "bytes_limit": _maybe_int(stats.get("bytes_limit")
                                  or stats.get("bytes_reservable_limit")),
    }


def _maybe_int(value) -> Optional[int]:
    try:
        return int(value) if value is not None else None
    except (TypeError, ValueError):
        return None


def _live_array_stats() -> Dict[str, Optional[int]]:
    if "jax" not in sys.modules:
        return {"count": None, "bytes": None}
    try:
        import jax

        arrays = jax.live_arrays()
        total = 0
        for arr in arrays:
            try:
                total += int(arr.nbytes)
            except Exception:  # noqa: BLE001 - deleted/donated buffers
                continue
        return {"count": len(arrays), "bytes": total}
    except Exception:  # noqa: BLE001
        return {"count": None, "bytes": None}


#: Process-wide device telemetry (knobs follow ``device_telemetry_*``
#: via telemetry.refresh()).
DEVICE = DeviceTelemetry()


def transfer(site: str, nbytes: int = 0):
    """Module-level convenience: ``with device.transfer("dmap", n): …``"""
    return DEVICE.transfer(site, nbytes)


# ---------------------------------------------------------------------------
# The host's account of a step call
# ---------------------------------------------------------------------------

#: ``step_stall`` (docs/observability.md "Anomaly rules"): the periods
#: (start of a call to the start of the next) kept for each fn, how many
#: must be held before one is judged, and what makes one a stall: over
#: this many medians AND over the median by this much. Constants: a
#: threshold nobody has had to tune is no knob.
STALL_HELD = 32
STALL_MIN_HELD = 8
STALL_RATIO = 1.5
STALL_EXCESS_NS = 50_000_000

#: what a call span says of the call itself; the same names under
#: ``since_`` say it of the time since the thread's last call of that fn
ACCOUNT_FIELDS = ("cpu_ns", "gc_ns", "gc_runs")
_SINCE_FIELDS = tuple("since_" + name for name in ACCOUNT_FIELDS)


def _between(sp: Dict, keys: tuple, a: tuple, b: tuple) -> None:
    """Write into the span what moved from snapshot ``a`` to ``b``
    (``StepCalls._snapshot``), under ``keys``."""
    sp[keys[0]] = b[1] - a[1]
    sp[keys[1]] = b[2] - a[2]
    sp[keys[2]] = b[3] - a[3]


class _Held:
    """What the ``step_stall`` rule holds for one fn."""

    __slots__ = ("periods", "start_ns", "span")

    def __init__(self) -> None:
        self.periods: "collections.deque" = collections.deque(
            maxlen=STALL_HELD)
        self.start_ns: Optional[int] = None  # the last call's start
        self.span: Optional[Dict] = None     # and its span


def _seconds(ns: Optional[int]) -> str:
    return "-" if ns is None else f"{ns / 1e9:.3f}"


class StepCalls:
    """The host side of the device-plane calls that go through
    :func:`step`: the account each call span carries, the ``step_stall``
    rule, and the call in flight. Touched only where spans are recorded
    at all (``telemetry.tracing_active()``)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # by thread: {fn: the snapshot its last call span ended with}
        self._tls = threading.local()
        # the process's collector, summed from start to stop by one
        # gc.callbacks hook that the first call installs
        self._gc_hooked = False
        self._gc_t0 = 0
        self.gc_ns = 0
        self.gc_runs = 0
        self._held: Dict[str, _Held] = {}
        self._stalled: set = set()
        #: the last call begun: (thread id, fn, span id, start, end or
        #: None while it is open), on ``time.perf_counter_ns``'s clock;
        #: one tuple, replaced whole, so the sampler reads it unlocked
        self.in_flight: Optional[tuple] = None
        # (when, where) the sampler's last ticks found the caller
        self._ticks: "collections.deque" = collections.deque(maxlen=64)

    # -- sources --------------------------------------------------------
    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
        elif self._gc_t0:
            self.gc_ns += time.perf_counter_ns() - self._gc_t0
            self.gc_runs += 1
            self._gc_t0 = 0

    def _ended(self) -> Dict[str, tuple]:
        ended = getattr(self._tls, "ended", None)
        if ended is None:
            with self._lock:
                if not self._gc_hooked:
                    gc.callbacks.append(self._on_gc)
                    self._gc_hooked = True
            ended = self._tls.ended = {}
        return ended

    def _snapshot(self) -> tuple:
        """The calling thread now: (monotonic ns, thread CPU ns,
        collector ns, collector runs). The CPU time is as fine as the
        kernel keeps it: by the 10 ms tick on the chip's machine, so a
        reader sums it over many calls."""
        return (time.perf_counter_ns(), time.thread_time_ns(),
                self.gc_ns, self.gc_runs)

    # -- the two ends of a call span ------------------------------------
    def begin(self, fn: str, sp: Dict) -> tuple:
        """The span ``sp`` of ``fn`` has just started on this thread:
        record what the thread did since its last one ended, publish
        the call, and judge the period that this start closes. Gives
        the start's snapshot and what :func:`step` is to report once
        the span has closed (a callable, or None)."""
        ended = self._ended()
        start = self._snapshot()
        last = ended.get(fn)
        if last is not None:
            sp["since_ns"] = start[0] - last[0]
            _between(sp, _SINCE_FIELDS, last, start)
        self.in_flight = (threading.get_ident(), fn, sp["span"],
                          start[0], None)
        return start, self._judge_period(fn, start[0], sp)

    def end(self, sp: Dict, start: tuple) -> None:
        """The call is over: record what the thread did inside it. This
        snapshot is also where the next ``since_*`` starts, so a call
        costs two snapshots, not four."""
        end = self._snapshot()
        _between(sp, ACCOUNT_FIELDS, start, end)
        self._ended()[sp["name"]] = end
        flight = self.in_flight
        if flight is not None and flight[2] == sp["span"]:
            self.in_flight = flight[:4] + (end[0],)

    # -- step_stall -----------------------------------------------------
    def _judge_period(self, fn: str, start_ns: int, sp: Dict):
        """The start of ``sp`` closes a period of ``fn``: hold it, or
        find it stalled. Gives what is to be reported: the stall, or
        the first sound period after one, which clears the rule."""
        with self._lock:
            held = self._held.get(fn)
            if held is None:
                held = self._held[fn] = _Held()
            last_ns, last_span = held.start_ns, held.span
            held.start_ns, held.span = start_ns, sp
            if last_ns is None:
                return None
            period = start_ns - last_ns
            if len(held.periods) >= STALL_MIN_HELD:
                median = statistics.median(held.periods)
                if (period > STALL_RATIO * median
                        and period - median >= STALL_EXCESS_NS):
                    # not added to the held periods: one stall must
                    # not raise the median it is judged against
                    self._stalled.add(fn)
                    return functools.partial(
                        self._raise_stall, fn, last_ns, start_ns, median,
                        last_span, sp)
            held.periods.append(period)
            if fn in self._stalled:
                self._stalled.discard(fn)
                if not self._stalled:
                    return self._clear_stall
        return None

    def _raise_stall(self, fn: str, last_ns: int, start_ns: int,
                     median: float, last_span: Dict, sp: Dict) -> None:
        """One stalled period of ``fn``, from ``last_span``'s start to
        ``sp``'s: the counter, and the rule's event with both halves of
        the account and where the ticks inside found the caller."""
        from fiber_tpu.telemetry.monitor import WATCHDOG

        period = start_ns - last_ns
        call = {k: last_span[k] for k in ACCOUNT_FIELDS if k in last_span}
        call["ns"] = int(last_span.get("dur", 0.0) * 1e9)
        since = {k: sp["since_" + k] for k in ("ns",) + ACCOUNT_FIELDS
                 if "since_" + k in sp}
        at: List[str] = []
        for when, where in list(self._ticks):
            if last_ns <= when <= start_ns and where not in at:
                at.append(where)
        _m_step_stalls.inc(fn=fn)
        WATCHDOG.external_breach(
            "step_stall", _stall_line(fn, period, median, call, since, at),
            fn=fn, period_s=round(period / 1e9, 6),
            median_s=round(median / 1e9, 6), call=call, since=since, at=at)

    @staticmethod
    def _clear_stall() -> None:
        from fiber_tpu.telemetry.monitor import WATCHDOG

        WATCHDOG.external_clear("step_stall")

    # -- the sampler's side ---------------------------------------------
    def caller_now(self) -> Dict[str, Any]:
        """What a ``monitor.tick`` span says of the device plane's
        caller: ``open`` (the fn of the call span open now, or None)
        with ``open_ns`` (its age), else ``idle_ns`` (since the last one
        ended); and ``at``, the innermost Python frame of the thread
        that made the last call, ``file:function:line``."""
        flight = self.in_flight
        out: Dict[str, Any] = {"open": None}
        if flight is None:
            return out
        ident, fn, _, start_ns, end_ns = flight
        now = time.perf_counter_ns()
        if end_ns is None:
            out["open"], out["open_ns"] = fn, now - start_ns
        else:
            out["idle_ns"] = now - end_ns
        frame = sys._current_frames().get(ident)
        if frame is not None:
            code = frame.f_code
            out["at"] = (f"{os.path.basename(code.co_filename)}:"
                         f"{code.co_name}:{frame.f_lineno}")
            self._ticks.append((now, out["at"]))
        return out

    def clear(self) -> None:
        with self._lock:
            self._held.clear()
            self._stalled.clear()
            self._ticks.clear()
            self.in_flight = None
            self._tls = threading.local()


def _stall_line(fn: str, period: int, median: float, call: Dict,
                since: Dict, at: List[str]) -> str:
    """The one line of a ``step_stall``: the period against the median,
    then where the period went: inside the call that began it, and
    between that call's end and the next one's start."""
    return (f"{fn} period {period / 1e9:.2f} s (median "
            f"{median / 1e9:.2f}): in call {_seconds(call.get('ns'))} s "
            f"(cpu {_seconds(call.get('cpu_ns'))}), since "
            f"{_seconds(since.get('ns'))} s "
            f"(cpu {_seconds(since.get('cpu_ns'))}, "
            f"gc {_seconds(since.get('gc_ns'))} / "
            f"{since.get('gc_runs', 0)} runs); sampler saw "
            + (", ".join(at) if at else "no tick inside"))


#: Process-wide account of the device-plane step calls.
CALLS = StepCalls()


@contextlib.contextmanager
def step(fn: str, units: int, **attrs) -> Iterator[Optional[Dict]]:
    """One call of the device-plane step function ``fn``: the span that
    device idle time is charged to and that compile spans hang from,
    with the host's account of the call on it (:class:`StepCalls`), and
    the operator's counters of calls made and ``units`` of work asked
    for. Host-side only; touches no device array. With spans off it
    yields None and reads nothing."""
    report = None
    with tracing.span(fn, **attrs) as sp:
        if sp is None:
            yield None
        else:
            start, report = CALLS.begin(fn, sp)
            try:
                yield sp
            finally:
                CALLS.end(sp, start)
    if report is not None:
        # a stall found, or cleared: said once the span has closed, so
        # that no call's length or ``cpu_ns`` holds the saying of it
        # (the next call's ``since_cpu_ns`` does: one log line a stall)
        report()
    _m_steps.inc(fn=fn)
    _m_step_units.inc(units, fn=fn)


def rollout_traced(policy: str, params: str) -> None:
    """One env rollout was traced (``models/envs.py``): with the
    policy's parameters unflattened before the step scan
    (``params="prepared"``), with an antithetic pair's parts unflattened
    there and summed in the step (``"pair"``), or with the flat vector
    (``"flat"``). Counts traces, not calls: a jitted rollout moves it
    once per compilation."""
    _m_rollout_traces.inc(policy=policy, params=params)


def moe_traced(held: int, total: int, top_k: int, walk: str) -> None:
    """One sparse-expert layer was traced (``ops/moe.py``). Counts
    traces, not calls, like ``rollout_traced``: an operator reads off it
    which share of the experts the compiled program holds, and the form
    its backward pass's dispatch walks in (``walk``)."""
    _m_moe_traces.inc(held=str(held), total=str(total), top_k=str(top_k),
                      walk=walk)


def ssm_traced(heads: int, state: int, groups: int, chunk: int,
               recompute: bool, scan: str) -> None:
    """One state-space layer was traced (``models/transformer.py``).
    Counts traces, not calls, like ``moe_traced``; a recomputed mixer's
    forward pass is traced once (``jax.checkpoint`` replays the traced
    equations), so recomputation does not move it twice. ``scan`` is the
    form its scan runs in (``ops/ssm.py`` ``scan_path``): ``kernel``, the
    two Pallas kernels, or ``plain``; an operator who reads ``plain`` on
    a TPU has a shape the kernels were not written for (a block that is
    not 128, a state or a group's heads not whole in 128 lanes), and the
    scan's seventy fusions a layer back."""
    _m_ssm_traces.inc(heads=str(heads), state=str(state),
                      groups=str(groups), chunk=str(chunk),
                      recompute=str(bool(recompute)).lower(), scan=scan)


def passes_traced(passes: int, layers: int, recompute: str,
                  kept: str) -> None:
    """One forward of a model was traced whose ``layers`` layers run
    ``passes`` times over the same weights, or whose step recomputes
    (``models/transformer.py``; ``recompute`` and ``kept`` as the
    ``lm.train_step`` span has them: ``kept="input+attn_out+lse"`` says
    the checkpoint around a layer keeps the flash kernel's output and
    row statistics, so the backward pass holds no forward kernel;
    ``"input"`` that it recomputes the whole layer). Counts traces, not
    calls, like ``ssm_traced``: the passes are one ``lax.scan`` and a
    checkpointed layer replays its traced equations, so one traced
    forward moves it once whatever ``passes`` and ``recompute`` are."""
    _m_passes_traces.inc(passes=str(passes), layers=str(layers),
                         recompute=recompute, kept=kept)


def latent_traced(heads: int, q_rank: int, kv_rank: int, qk_dim: int,
                  v_dim: int) -> None:
    """One application of a latent-attention layer was traced
    (``models/transformer.py``; the MTP module's layer too). Counts
    traces, not calls, like ``passes_traced``: a recomputed layer
    replays its traced equations, so one traced step moves it once a
    layer application."""
    _m_latent_traces.inc(heads=str(heads), q_rank=str(q_rank),
                         kv_rank=str(kv_rank), qk_dim=str(qk_dim),
                         v_dim=str(v_dim))


def conv_traced(kernel: int, width: int, path: str) -> None:
    """One application of a gated short-convolution layer was traced
    (``models/transformer.py`` ``_conv_mix``): ``kernel`` positions over
    ``width`` features, and ``path`` the form it runs in
    (``ops/short_conv.py`` ``conv_path``): ``kernel``, the two Pallas
    kernels ``short_conv_fwd`` / ``short_conv_bwd``, or ``plain``, which
    on a TPU says the shapes are none the kernels fit. Counts traces,
    not calls, like ``latent_traced``: a recomputed layer replays its
    traced equations, so one traced step moves it once a layer."""
    _m_conv_traces.inc(kernel=str(kernel), width=str(width), path=path)


def mtp_traced(depth: int, weight: float) -> None:
    """One loss with a multi-token-prediction term was traced
    (``BlockLM.loss``). Counts traces, not calls: an operator reads off
    it that the compiled step trains the module, and at which weight."""
    _m_mtp_traces.inc(depth=str(depth), weight=f"{weight:g}")


def flash_grid_built(kernel: str, grid: str, run: int, idle: int) -> None:
    """One flash-attention kernel was built
    (``ops/pallas_attention.py``) whose inner grid axes make, for one
    head, ``run`` steps that compute and ``idle`` steps that only
    exist, on a ``grid`` that is ``folded``, ``band`` or ``square``.
    Counts programs built, not calls: an operator reads off it whether
    a new shape (other blocks, a window of one block) fell back to a
    band."""
    _m_flash_grid_steps.inc(run, kernel=kernel, grid=grid, state="run")
    _m_flash_grid_steps.inc(idle, kernel=kernel, grid=grid, state="idle")


def ring_built(layout: str, pairs) -> None:
    """One ring-attention program was built (``ops/ring_attention.py``)
    under the static schedule ``ring_schedule`` gives: ``pairs[chip]
    [rotation]`` (query, key) pairs, 0 where the mask leaves the chip
    nothing. Moves ``run`` and ``skip`` by the rotations of all the
    ring's chips together, so a balanced ring of n chips reads n x n
    and 0. Counts programs built, not calls: an operator who reads
    ``contiguous`` with ``skip`` above 0 on a causal run has a length
    that is not whole in 2n half-blocks, and chips that wait."""
    run = sum(1 for chip in pairs for n in chip if n)
    skip = sum(len(chip) for chip in pairs) - run
    _m_ring_rotations.inc(run, layout=layout, state="run")
    _m_ring_rotations.inc(skip, layout=layout, state="skip")


def moe_load(loads) -> None:
    """The load of each held expert on one probed batch
    (``BlockLM.probe_routing``): ``loads[layer][expert]`` tokens."""
    for layer, load in enumerate(loads):
        load = [float(x) for x in load]
        _g_moe_load_max.set(max(load), layer=str(layer))
        _g_moe_load_mean.set(sum(load) / len(load), layer=str(layer))


def moe_chunks(chunks) -> None:
    """The chunks each expert layer's dispatch walk makes on one probed
    batch (``BlockLM.probe_routing``): ``chunks[layer]``."""
    for layer, walked in enumerate(chunks):
        _g_moe_chunks.set(float(walked), layer=str(layer))


def snapshot() -> Dict[str, Any]:
    return DEVICE.snapshot()
