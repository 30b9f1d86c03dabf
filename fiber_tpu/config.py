"""Layered configuration for fiber_tpu.

Three layers with rising priority (reference parity: fiber/config.py:15-65):

1. config file — ``.fiberconfig`` in the current working directory (or a
   path passed as ``conf_file=``), INI format, ``[default]`` section;
2. environment — ``FIBER_<KEY>`` variables;
3. code — ``fiber_tpu.init(key=value)`` / ``fiber_tpu.config.init(...)``.

Unknown keys in the config file raise ``ValueError`` (reference:
fiber/config.py:149-153). The resolved config is serialized into the spawn
preparation data and re-applied inside every child process so the whole
process tree sees one config (reference: fiber/spawn.py:59-60).
"""

from __future__ import annotations

import configparser
import copy
import os
from typing import Any, Dict, Optional

DEFAULT_CONFIG_FILE = ".fiberconfig"
ENV_PREFIX = "FIBER_"

#: Default values; also the schema (key set + types) for file/env coercion.
DEFAULTS: Dict[str, Any] = {
    # --- scheduling / backend ---
    "backend": "",           # "" = auto-select (local unless on a TPU pod)
    "image": "",             # container/VM image for remote backends
    "cpu_per_job": 1,        # local worker processes packed per job
    "mem_per_job": 0,        # MB; 0 = backend default
    # --- logging ---
    "log_level": "INFO",
    "log_file": "/tmp/fiber_tpu.log",   # "stdout" = log to stdout
    # --- control plane (admin channel) ---
    "ipc_active": True,      # worker dials master (False: master dials worker)
    "ipc_admin_master_port": 0,     # 0 = random
    "ipc_admin_worker_port": 8000,  # used only in passive mode
    # --- health plane (docs/robustness.md) ---
    # Worker/agent heartbeat period, seconds; 0 disables heartbeats AND
    # the deadline failure detector (silence then only surfaces via TCP
    # or process reaping).
    "heartbeat_interval": 1.0,
    # Seconds of peer silence before the failure detector declares it
    # dead and triggers the pool's resubmit path. Must comfortably
    # exceed heartbeat_interval (10x by default).
    "suspect_timeout": 10.0,
    # Consecutive spawn failures that open the per-target circuit
    # breaker; while open, the pool stops hammering the target.
    "spawn_breaker_threshold": 3,
    # First open period, seconds (doubles per re-open, + jitter) and its
    # cap. Deliberately small: the terminal _SPAWN_FAIL_LIMIT escalation
    # in pool.py must still fire within ~a minute on a dead backend.
    "spawn_breaker_backoff": 0.25,
    "spawn_breaker_backoff_max": 2.0,
    # --- scheduler plane (docs/scheduling.md) ---
    # Pool handout policy: "adaptive" = locality-aware placement + fair
    # multi-map queueing (and, when enabled below, straggler
    # speculation); "fifo" = the reference's plain arrival-order
    # handout.
    "sched_policy": "adaptive",
    # Prefer handing ref-bearing chunks to workers on hosts whose store
    # already caches the referenced objects.
    "locality_enabled": True,
    # Launch a speculative duplicate of a straggling chunk (first
    # result wins; the loser is discarded idempotently). Off by
    # default: duplicates are only safe for idempotent task functions
    # WITHOUT side effects — stricter than the resilient pool's
    # baseline contract, which duplicates only on worker death.
    "speculation_enabled": False,
    # A dispatched chunk older than this multiple of its map's median
    # service time (with spare workers idle and the queue drained) is
    # speculated.
    "speculation_quantile": 4.0,
    # --- hierarchical dispatch (docs/architecture.md) ---
    # "direct": the master hands one chunk per worker request (the
    # reference shape). "hier": packed workers (cpu_per_job > 1,
    # ResilientPool) promote their packing parent to a per-host
    # sub-master — the master hands out whole chunk RANGES (one frame,
    # encoded once) and the sub-master fans individual chunks to its
    # local workers and streams results back aggregated, so master
    # frame count and encode CPU scale with hosts rather than workers.
    # A sub-master death degrades respawned hosts to "direct".
    "dispatch_mode": "direct",
    # Upper bound on chunks handed out per range frame in "hier" mode.
    "dispatch_range_chunks": 16,
    # --- data plane ---
    "use_push_queue": True,
    # --- transport I/O core (docs/transport.md) ---
    # "selector": one selectors-driven poller thread per process owns
    # every channel socket — non-blocking incremental frame decode,
    # scatter-gather (sendmsg) sends, small-frame coalescing; socket
    # threads are O(1) in connection count. "threads": the blocking
    # thread-per-connection fallback (one reader thread per channel).
    # "shm": same-host zero-copy — each connection auto-negotiates a
    # pair of mmap'd ring buffers when both peers share a host key
    # (frames move through /dev/shm with one copy per side) and falls
    # back to plain TCP otherwise; counters and chaos semantics are
    # identical across all three engines (docs/transport.md).
    "transport_io": "selector",
    # Per-direction shm ring capacity in KiB (transport_io="shm"). Each
    # negotiated channel maps two rings of this size; frames larger
    # than the ring stream through it in chunks.
    "transport_shm_ring_kb": 4096,
    # Upper bound on bytes the selector loop gathers into one coalesced
    # sendmsg flush; small control frames (credit, hb, spans, storemiss)
    # queued between poller wakeups leave in a single syscall up to this
    # size. Large payloads are never split — a frame bigger than the cap
    # still goes out as one vectored send.
    "transport_coalesce_max": 256 * 1024,
    # Standing credit window a bound r-endpoint grants each peer (fan-in
    # ingress like pool result streams): how many frames a sender may
    # run ahead of the consumer. Large enough to never throttle by
    # default; lower it to bound per-peer master memory (window x frame
    # size).
    "transport_credit_window": 4096,
    # --- object store (docs/objectstore.md) ---
    # By-reference task data plane: pool args/results whose serialized
    # size exceeds store_inline_max bytes travel as ObjectRefs through
    # the per-host object store instead of riding every task frame.
    # 0 disables the store (everything ships inline), as does
    # store_enabled=False.
    "store_enabled": True,
    "store_inline_max": 512 * 1024,
    # Host-RAM LRU capacity of the local store, MB; colder objects spill
    # to disk under store_dir.
    "store_capacity_mb": 512,
    # Content-addressed object directory shared by every fiber process
    # on a host (fetch dedup + spill). "" = <staging root>/objects,
    # where the staging root is FIBER_AGENT_STAGING or
    # ~/.fiber_tpu/staging (utils/staging.py / host_agent.py).
    "store_dir": "",
    # Device-resident store tier (docs/objectstore.md "Device tier"):
    # device-destined payloads are cached ON the accelerator (digest ->
    # replicated jax.Array + sharding metadata) so repeat resolutions
    # of the same content ride ICI instead of re-paying wire + H2D.
    # Demoted to the host tiers by the `hbm_fill` watchdog rule
    # (closed-loop remediation; re-promoted when the rule clears).
    "store_device_enabled": True,
    # HBM budget of the device tier, MB. Colder entries are dropped LRU
    # past it (safe: the host RAM/disk tiers still hold the bytes);
    # pinned entries are untouchable.
    "store_device_capacity_mb": 256,
    # --- streaming data plane (docs/streaming.md) ---
    # Windowed streaming admission for imap/imap_unordered: the master
    # pulls from the caller's iterator lazily and keeps at most
    # stream_window chunks encoded + in flight + un-yielded at any
    # instant, so master memory is O(window) instead of O(n). A slow
    # consumer parks admission (condition-variable), which parks
    # dispatch, which lets transport credits drain — backpressure is
    # end-to-end. Off, imap still avoids materializing the iterable but
    # admission is unwindowed (legacy posture; the ledger path then
    # needs a full materialization for its fixed task digest).
    "stream_enabled": True,
    # Admission window in CHUNKS (not tasks): encoded-but-unyielded
    # chunks the master will hold at once. Also the policy plane's
    # `queue_growth` -> shrink_stream_window knob target. 128 keeps
    # streamed throughput within a few percent of a materialized map
    # (each admission park/wake cycle briefly starves dispatch, so the
    # window must cover several consumer batches); halve it per level
    # of memory pressure instead of shrinking the default.
    "stream_window": 128,
    # --- durability (docs/robustness.md "Durable maps") ---
    # Write-ahead map ledger: Pool.map(..., job_id=...) journals the
    # task spec + every completed chunk's result digest under
    # ledger_dir, making the map resumable across master crashes
    # (`fiber-tpu resume <job_id>` / re-calling map with the job_id).
    # Off, job_id is accepted but nothing is journaled.
    "ledger_enabled": True,
    # Ledger directory. "" = <staging root>/ledger, beside the objects/
    # cache the journaled result payloads persist into.
    "ledger_dir": "",
    # Accumulation window of the ledger writer thread, seconds: chunk
    # records queued within it land in one write + one fsync. The hot
    # result loop only ever pays a buffered append.
    "ledger_fsync_s": 0.05,
    # Re-replicate precious digests (ledger-journaled results, active
    # broadcasts) to a second healthy host when the health plane
    # declares their holder suspect — recovery then never needs the
    # dead host.
    "store_replicate": True,
    # --- telemetry plane (docs/observability.md) ---
    # Master switch for the metrics registry + span tracing. Off, every
    # instrument call is a single attribute check and nothing is
    # recorded.
    "telemetry_enabled": True,
    # Fraction of Pool maps that get a trace id stamped into their task
    # envelopes (workers then record + ship spans for those chunks).
    # 1.0 = trace everything (default; the bench pins full-tracing
    # overhead < 5% on the small-task microbench), 0.0 = metrics only.
    "trace_sample_rate": 1.0,
    # Per-process finished-span ring buffer: oldest spans fall out past
    # this many (bounds memory on long-lived masters/workers).
    "span_buffer_size": 4096,
    # Port for the authenticated Prometheus exposition endpoint
    # (telemetry.serve_metrics / the host agent's sidecar). 0 = off.
    "metrics_port": 0,
    # Flight recorder (docs/observability.md): per-process ring buffer
    # of structured plane events (pool/sched/store/transport/health) —
    # the black box `fiber-tpu explain` and postmortem bundles read.
    # Near-zero when off. Requires telemetry_enabled too (one master
    # switch for the whole plane).
    "flightrec_enabled": True,
    # Events kept in the ring before the oldest fall out (each is a
    # small dict; 2048 bounds a long-lived master to ~1 MB).
    "flightrec_buffer_size": 2048,
    # --- continuous monitor plane (docs/observability.md) ---
    # Per-process sampler thread that snapshots the hot instruments
    # (tasks/s, bytes/s, queue depth, inflight, heartbeat age) every
    # monitor_interval_s into bounded time-series rings, and the
    # anomaly watchdog that rides it. Off: no thread, no rings, the
    # only cost is one check per telemetry.refresh(). Requires
    # telemetry_enabled (one master switch for the plane).
    "monitor_enabled": True,
    "monitor_interval_s": 1.0,
    # Points kept per series ring (600 x 1s = a 10-minute window).
    "monitor_history": 600,
    # Wall-clock sampling profiler (telemetry/profiler.py): > 0 arms a
    # per-process sampler at this many stack samples per second,
    # aggregated as flamegraph folded stacks; pool workers ship theirs
    # back on the result stream. 0 (default) = off, zero cost.
    "profiler_hz": 0.0,
    # Anomaly watchdog rules (telemetry/monitor.py). tasks/s dropping
    # more than this fraction below its trailing-window mean (with
    # work in flight) raises `throughput_drop`:
    "anomaly_drop_pct": 0.5,
    # Consecutive samples of monotonic queue-depth growth that raise
    # `queue_growth`:
    "anomaly_queue_intervals": 5,
    # Transport egress queue bytes (MB) past which `tx_queue_high`
    # raises (half the 32 MiB per-channel TX_HIGH_WATER block):
    "anomaly_tx_queue_mb": 16.0,
    # Store disk-tier fill fraction (of max_disk_bytes) past which
    # `store_disk_fill` raises:
    "anomaly_disk_fill_pct": 0.9,
    # --- device telemetry plane (docs/observability.md) ---
    # Transfer accounting at the host->device boundary (store resolve,
    # deserialize, device_map plan, checkpoint restore), jax.monitoring
    # compile listeners, HBM/live-array gauges and the live pool_map_mfu
    # gauge. Requires telemetry_enabled; off, every hook is one
    # attribute check.
    "device_telemetry_enabled": True,
    # Recompiles of ONE fingerprint inside the window that raise the
    # `recompile_storm` watchdog rule (shape churn, not progress):
    "anomaly_recompile_count": 4,
    "anomaly_recompile_window_s": 30.0,
    # HBM fill fraction (bytes_in_use / bytes_limit, when the device
    # reports memory_stats) past which `hbm_fill` raises:
    "anomaly_hbm_fill_pct": 0.92,
    # --- policy plane (docs/observability.md "Autonomous operations") ---
    # Watchdog anomalies -> remediation actions (telemetry/policy.py):
    # every action is a `policy` flight event linked to its anomaly via
    # cause_id, and policy_verify_s later the engine re-samples the
    # rule and records the outcome (resolved/persisted/worsened).
    # Requires telemetry_enabled.
    "policy_enabled": True,
    # Record what WOULD be done without acting (planning/audit mode).
    "policy_dry_run": False,
    # Per-rule cooldown between repeated actions, seconds (a flapping
    # rule must not re-fire its remediation every edge). The hbm_fill
    # demote/promote pair is exempt: its hysteresis is the watchdog
    # edge itself.
    "policy_cooldown_s": 30.0,
    # Delay before the engine re-samples a rule and classifies its
    # action's outcome:
    "policy_verify_s": 3.0,
    # Comma-separated rule allowlist for the engine; "all" = every
    # registered policy.
    "policy_rules": "all",
    # --- accounting plane (docs/observability.md "Resource accounting") ---
    # Per-map/per-tenant cost attribution: billing keys ride the task
    # envelope tail, workers ship cumulative ("cost", ...) frames, and
    # Pool.cost()/`fiber-tpu cost` render per-job CostReports. Requires
    # telemetry_enabled; off, every hook is one attribute check.
    "accounting_enabled": True,
    # Tenant label billed for every map this process submits (the serve
    # tier will stamp it per client); bounded per-job metric labels ride
    # it (cost_tasks_total{tenant=,job=}).
    "tenant": "default",
    # Per-job cost record directory. "" = <staging root>/costs, beside
    # the ledger/ directory `fiber-tpu jobs` reads.
    "cost_dir": "",
    # --- serving tier (docs/serving.md) ---
    # `fiber-tpu serve` daemon: a long-lived multi-tenant front door
    # multiplexing many clients' jobs onto one shared pool, with
    # admission control, budget preemption and a warm worker pool.
    # RPC port the daemon listens on (authenticated with
    # FIBER_CLUSTER_KEY, same plane as the host agents).
    "serve_port": 7070,
    # Worker-slot ceiling for the shared pool; 0 = cpu_count().
    "serve_processes": 0,
    # Warm pool floor: standby workers kept spawned even when idle, so
    # a newly admitted tenant's first chunk skips cold spawn latency.
    "serve_warm_floor": 2,
    # Warm pool ceiling; 0 = serve_processes (fully elastic in range).
    "serve_warm_ceiling": 0,
    # Idle seconds (zero in-flight + zero queued chunks) before the
    # warm pool scales back down to the floor.
    "serve_warm_idle_s": 5.0,
    # Daemon housekeeping tick, seconds: admission escalation sweep +
    # warm pool scaling decisions.
    "serve_tick_s": 0.5,
    # Per-tenant admission quotas; 0 = unlimited. Checked at submit
    # against the accounting plane's live cost vectors.
    "serve_tenant_jobs": 0,        # concurrent running jobs per tenant
    "serve_tenant_tasks": 0,       # cumulative submitted tasks per tenant
    "serve_tenant_cpu_s": 0.0,     # cumulative worker CPU seconds per tenant
    # Watchdog anomaly rules whose STANDING (active) state refuses new
    # admissions; comma-separated.
    "serve_deny_rules": "store_disk_fill,hbm_fill",
    # Grace period, seconds, between a tenant's budget_exceeded anomaly
    # (WDRR throttle, the policy plane's first response) and escalation
    # to actual preemption (job parked resumable, chunks reclaimed).
    "serve_preempt_grace_s": 2.0,
    # Serve-tier job journal directory. "" = <staging root>/serve,
    # beside ledger/ and costs/.
    "serve_dir": "",
    # --- observability archive (docs/observability.md "SLOs and the
    # archive") ---
    # Persist monitor samples, flight/anomaly/policy events and per-job
    # cost/SLO observations into time-partitioned segment files each
    # sampler tick. Off by default: the serve daemon arms it
    # process-locally on startup (ARCHIVE.enable()), so pool workers
    # never inherit an archive writer through config adoption; set True
    # to archive any process.
    "archive_enabled": False,
    # Archive directory. "" = <staging root>/archive, beside ledger/,
    # costs/ and serve/.
    "archive_dir": "",
    # Segment roll interval, seconds: one file per window keeps
    # time-range queries from scanning the whole history.
    "archive_segment_s": 300.0,
    # Longest interval, seconds, an accepted record may sit in the OS
    # page cache before fsync (the ledger_fsync_s posture: batched
    # durability, bounded loss window).
    "archive_fsync_s": 0.2,
    # Retention horizon, seconds: segments whose window ended earlier
    # are pruned on roll.
    "archive_retention_s": 604800.0,
    # Size cap, MB: oldest segments pruned first once the archive
    # exceeds it.
    "archive_max_mb": 256,
    # --- per-tenant SLOs (serve daemon; docs/observability.md) ---
    # Declarative targets over the serve tier's per-tenant SLIs. A
    # latency/queue target of 0 disables that objective; the error-rate
    # objective is always on (its budget is serve_slo_error_pct). The
    # burn-rate evaluation is multi-window: `slo_burn` raises only when
    # BOTH the fast and the slow window burn past serve_slo_burn.
    "serve_slo_latency_s": 0.0,    # submit->done latency target, seconds
    "serve_slo_queue_s": 0.0,      # queue-wait target, seconds
    "serve_slo_p": 0.95,           # percentile the latency targets bound
    "serve_slo_error_pct": 0.01,   # error budget: allowed bad-job fraction
    "serve_slo_window_s": 3600.0,  # slow burn window, seconds
    "serve_slo_fast_window_s": 300.0,  # fast burn window, seconds
    "serve_slo_burn": 2.0,         # burn-rate threshold (both windows)
    # --- TPU backend ---
    "tpu_name": "",
    "tpu_zone": "",
    "tpu_project": "",
    "tpu_hosts": "",          # comma-separated host list override / sim hosts
    # Ship the master's cwd source tree to cluster hosts at spawn (the
    # Docker-image role in the reference): "auto" = on for backends with
    # staging support (tpu agents), "off" = never.
    "code_staging": "auto",
    "mesh_shape": "",         # e.g. "8" or "4x2"; "" = all local devices
    # --- misc ---
    "debug": False,
}

_VALID_KEYS = frozenset(DEFAULTS)


def _coerce(key: str, value: Any) -> Any:
    """Coerce a string from file/env to the type of the default value."""
    default = DEFAULTS[key]
    if isinstance(value, str):
        if isinstance(default, bool):
            return value.strip().lower() in ("1", "true", "yes", "on")
        if isinstance(default, int) and not isinstance(default, bool):
            return int(value)
        if isinstance(default, float):
            return float(value)
    return value


class Config:
    """A resolved configuration: defaults < file < env < code kwargs."""

    def __init__(self, conf_file: Optional[str] = None, **kwargs: Any) -> None:
        self._values: Dict[str, Any] = copy.deepcopy(DEFAULTS)
        self._load_file(conf_file)
        self._load_env()
        self.update(**kwargs)

    def _load_file(self, conf_file: Optional[str]) -> None:
        path = conf_file or os.path.join(os.getcwd(), DEFAULT_CONFIG_FILE)
        if not os.path.exists(path):
            if conf_file:
                raise ValueError(f"config file not found: {conf_file}")
            return
        parser = configparser.ConfigParser()
        parser.read(path)
        if not parser.has_section("default"):
            return
        for key, raw in parser.items("default"):
            if key not in _VALID_KEYS:
                raise ValueError(
                    f"invalid key in config file {path!r}: {key!r}"
                )
            self._values[key] = _coerce(key, raw)

    def _load_env(self) -> None:
        for key in _VALID_KEYS:
            env = os.environ.get(ENV_PREFIX + key.upper())
            if env is not None:
                self._values[key] = _coerce(key, env)

    def update(self, **kwargs: Any) -> None:
        for key, value in kwargs.items():
            if key == "conf_file":
                continue
            if key not in _VALID_KEYS:
                raise ValueError(f"invalid config key: {key!r}")
            self._values[key] = _coerce(key, value)

    def __getattr__(self, key: str) -> Any:
        try:
            return self.__dict__["_values"][key]
        except KeyError:
            raise AttributeError(key) from None

    def __setattr__(self, key: str, value: Any) -> None:
        if key.startswith("_"):
            super().__setattr__(key, value)
        else:
            self.update(**{key: value})

    def as_dict(self) -> Dict[str, Any]:
        return dict(self._values)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"Config({self._values!r})"


_current: Config = Config()


def get() -> Config:
    """Return the process-wide config object."""
    return _current


def init(conf_file: Optional[str] = None, **kwargs: Any) -> Config:
    """Rebuild the process-wide config: defaults < file < env < kwargs."""
    global _current
    _current = Config(conf_file=conf_file, **kwargs)
    return _current


def init_from(values: Dict[str, Any]) -> Config:
    """Adopt a fully-resolved config dict (used by the worker bootstrap so a
    child sees exactly the parent's config — reference: fiber/spawn.py:59-60).
    """
    global _current
    cfg = Config.__new__(Config)
    cfg._values = copy.deepcopy(DEFAULTS)
    cfg._values.update({k: v for k, v in values.items() if k in _VALID_KEYS})
    _current = cfg
    return _current


def reset() -> Config:
    """Reset to pure defaults (no file/env), mainly for tests."""
    global _current
    cfg = Config.__new__(Config)
    cfg._values = copy.deepcopy(DEFAULTS)
    _current = cfg
    return _current


def __getattr__(name: str) -> Any:
    """Module-level attribute access proxies the current config
    (``fiber_tpu.config.backend`` etc., reference exposes module globals)."""
    if name in _VALID_KEYS:
        return getattr(_current, name)
    raise AttributeError(name)
