"""Benchmark: ES policy-evaluations per second on the attached accelerator.

Prints ONE JSON line:
    {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Workload (BASELINE.json north star): OpenAI-ES on CartPole-v1 with an MLP
policy — full 500-step episode evaluations, antithetic perturbations drawn
on-chip, centered-rank shaping, psum'd gradient. The north-star target is
10,000 evals/sec on a v5e-64; ``vs_baseline`` is measured evals/sec divided
by this chip's proportional share (10_000 / 64 per chip).

Run ``python bench.py --platform cpu`` to exercise the same path on the
virtual CPU mesh.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

NORTH_STAR_EVALS_PER_SEC = 10_000.0
NORTH_STAR_CHIPS = 64

#: Max allowed fiber/mp wall ratio at the 1 ms-task point (the
#: reference's signature overhead benchmark); enforced by `make bench`.
_POOL_1MS_BUDGET = 1.1


def _round_mfu(value):
    """mfu fields are fractions of peak spanning ~1e-7 (branchy VPU-bound
    ES eval loops) to ~0.5 (flash attention) — 4 significant figures
    keeps both regimes readable; fixed decimals would collapse the small
    ones to 0.0. None (unknown peak, e.g. CPU) passes through."""
    return None if value is None else float(f"{value:.4g}")


#: Bench-trajectory recording (``--record``): every emitted metric line
#: also appends to BENCH_history.jsonl with run identity, so the perf
#: trajectory across commits is visible (the BENCH_*.json files
#: overwrite in place). scripts/bench_check.py flags gated-ratio
#: regressions against the best recorded value.
_RECORD: dict = {"path": None, "sha": "", "argv": ""}

HISTORY_PATH = "BENCH_history.jsonl"


def _arm_record(path: str = HISTORY_PATH) -> None:
    import subprocess
    import time as _time

    sha = ""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
    except Exception:  # noqa: BLE001 - recording is best-effort
        pass
    _RECORD.update(path=path, sha=sha,
                   argv=" ".join(sys.argv[1:]) or "(default)",
                   ts=_time.time())


def _emit(result: dict) -> None:
    print(json.dumps(result), flush=True)
    if _RECORD["path"]:
        entry = {"ts": round(float(_RECORD.get("ts") or 0.0), 3),
                 "sha": _RECORD["sha"], "bench": _RECORD["argv"]}
        entry.update(result)
        try:
            with open(_RECORD["path"], "a") as fh:
                fh.write(json.dumps(entry) + "\n")
        except OSError:
            print("bench: could not append to history file",
                  file=sys.stderr)


def _watchdog(seconds: float, payload: dict):
    """If backend init or a compile hangs past ``seconds``: emit the
    failure line and hard-exit non-zero (a hung run must still produce
    a JSON line, and must not look like a pass)."""

    def fire():
        _emit(payload)
        os._exit(2)

    timer = threading.Timer(seconds, fire)
    timer.daemon = True
    timer.start()
    return timer


def _store_bench(args) -> int:
    """Object-store microbench (docs/objectstore.md). Emits one JSON
    line per metric; `make bench-store` tees them into
    BENCH_store.json next to the driver's BENCH records.

    Sections: (1) LocalStore put/get throughput (serialization envelope
    + content addressing included — that IS the put cost); (2) wire
    fetch throughput through the chunked store plane on loopback;
    (3) the headline: broadcast bytes-per-task over a real Pool.map
    with the by-reference plane ON vs OFF, plus wall-clock for both."""
    import time

    import numpy as np

    payload_mb = float(args.store_mb)
    arr = np.random.default_rng(0).standard_normal(
        int(payload_mb * (1 << 20) / 4)).astype(np.float32)

    from fiber_tpu import serialization
    from fiber_tpu.store import LocalStore
    from fiber_tpu.store.plane import StoreClient, StoreServer

    # -- 1) local tier ------------------------------------------------
    blob = serialization.dumps(arr)
    st = LocalStore(capacity_bytes=512 << 20)
    reps = 8
    t0 = time.perf_counter()
    for i in range(reps):
        # vary one byte so content addressing can't dedup the timing
        st.put_bytes(blob[:-1] + bytes([i]))
    put_s = (time.perf_counter() - t0) / reps
    ref = st.put_bytes(blob)
    t0 = time.perf_counter()
    for _ in range(reps):
        st.get_bytes(ref.digest)
    get_s = (time.perf_counter() - t0) / reps
    _emit({"metric": "store_put_mb_per_sec",
           "value": round(payload_mb / put_s, 1), "unit": "MB/s",
           "payload_mb": payload_mb})
    _emit({"metric": "store_get_mb_per_sec",
           "value": round(payload_mb / get_s, 1), "unit": "MB/s",
           "payload_mb": payload_mb})

    # -- 2) wire plane ------------------------------------------------
    server = StoreServer(st, "127.0.0.1")
    client = StoreClient(LocalStore(capacity_bytes=512 << 20))
    wire_ref = type(ref)(ref.digest, ref.size, server.addr)
    t0 = time.perf_counter()
    client.fetch_bytes(wire_ref)
    wire_s = time.perf_counter() - t0
    _emit({"metric": "store_wire_fetch_mb_per_sec",
           "value": round(payload_mb / wire_s, 1), "unit": "MB/s",
           "payload_mb": payload_mb})
    client.close()
    server.close()

    # -- 3) broadcast bytes-per-task, pool path on vs off -------------
    import fiber_tpu
    from tests import targets  # arr_sum_plus: importable in workers

    n_tasks = int(args.store_tasks)
    items = [(arr, i) for i in range(n_tasks)]
    record = {}
    for mode in ("off", "on"):
        fiber_tpu.init(store_enabled=(mode == "on"))
        with fiber_tpu.Pool(2) as pool:
            before = pool.store_stats()
            t0 = time.perf_counter()
            out = pool.starmap(targets.arr_sum_plus, items, chunksize=2)
            wall = time.perf_counter() - t0
            after = pool.store_stats()
        assert len(out) == n_tasks
        if mode == "off":
            # Inline wire cost per task: the actual chunk frame bytes
            # (the broadcast arg is re-pickled into EVERY chunk).
            chunk = serialization.dumps(items[:2])
            record["before_bytes"] = len(chunk) / 2
            record["before_wall"] = wall
        else:
            served = after.get("bytes_served", 0) - \
                before.get("bytes_served", 0)
            record["after_bytes"] = served / n_tasks
            record["after_wall"] = wall
    fiber_tpu.init()
    _emit({"metric": "store_broadcast_bytes_per_task_before",
           "value": round(record["before_bytes"], 1), "unit": "bytes",
           "tasks": n_tasks, "payload_mb": payload_mb,
           "wall_s": round(record["before_wall"], 3)})
    _emit({"metric": "store_broadcast_bytes_per_task_after",
           "value": round(record["after_bytes"], 1), "unit": "bytes",
           "tasks": n_tasks, "payload_mb": payload_mb,
           "wall_s": round(record["after_wall"], 3),
           "reduction_x": round(
               record["before_bytes"] / max(record["after_bytes"], 1),
               1)})
    return 0


#: Max allowed full-tracing/telemetry-off wall ratio on the small-task
#: pool microbench; `make bench-telemetry` fails past it.
_TELEMETRY_BUDGET = 1.05


def _telemetry_bench(args, only=None) -> int:
    """Telemetry-plane overhead microbench (docs/observability.md):
    pool throughput on the reference's signature small-task workload
    with telemetry off / metrics-only / full tracing. Emits one JSON
    line per mode plus a summary line; exits nonzero when full-tracing
    overhead exceeds the 5% budget. Best-of-N walls so a CI scheduler
    hiccup can't fail the gate. ``only`` restricts the arm set — the
    ``--accounting`` shortcut runs just (off, accounting)."""
    os.environ["FIBER_BACKEND"] = "local"
    import fiber_tpu

    n_tasks, duration, workers = 600, 0.001, 4
    # Each arm isolates ONE layer's marginal cost: the lower modes pin
    # everything above them OFF so "tracing" keeps measuring exactly
    # what it measured before the recorder existed, "flightrec" is
    # tracing + the recorder fully on (every plane hook emitting),
    # "monitor" adds the continuous sampler + anomaly watchdog at a
    # 4x-tighter-than-default interval, "accounting" adds the cost
    # ledger (billing keys on every envelope, per-frame wire billing,
    # worker cost frames), and "profiler" adds the ~100 Hz stack
    # sampler in the master AND every worker.
    modes = (
        ("off", dict(telemetry_enabled=False)),
        ("metrics", dict(telemetry_enabled=True, trace_sample_rate=0.0,
                         flightrec_enabled=False,
                         monitor_enabled=False,
                         device_telemetry_enabled=False,
                         accounting_enabled=False)),
        ("tracing", dict(telemetry_enabled=True, trace_sample_rate=1.0,
                         flightrec_enabled=False,
                         monitor_enabled=False,
                         device_telemetry_enabled=False,
                         accounting_enabled=False)),
        ("flightrec", dict(telemetry_enabled=True, trace_sample_rate=1.0,
                           flightrec_enabled=True,
                           monitor_enabled=False,
                           device_telemetry_enabled=False,
                           accounting_enabled=False)),
        ("monitor", dict(telemetry_enabled=True, trace_sample_rate=1.0,
                         flightrec_enabled=True, monitor_enabled=True,
                         monitor_interval_s=0.25,
                         device_telemetry_enabled=False,
                         accounting_enabled=False)),
        # device = monitor + the device telemetry plane fully on:
        # transfer accounting armed on every worker's resolve path and
        # the HBM/live-array gauge probe riding the 0.25s sampler tick.
        ("device", dict(telemetry_enabled=True, trace_sample_rate=1.0,
                        flightrec_enabled=True, monitor_enabled=True,
                        monitor_interval_s=0.25,
                        device_telemetry_enabled=True,
                        accounting_enabled=False)),
        # accounting = monitor + the cost ledger fully on: billing key
        # on every task envelope, per-frame wire attribution on the
        # master's hot loops, per-chunk busy-second billing and
        # cumulative cost frames on every worker.
        ("accounting", dict(telemetry_enabled=True,
                            trace_sample_rate=1.0,
                            flightrec_enabled=True, monitor_enabled=True,
                            monitor_interval_s=0.25,
                            device_telemetry_enabled=False,
                            accounting_enabled=True)),
        ("profiler", dict(telemetry_enabled=True, trace_sample_rate=1.0,
                          flightrec_enabled=True, monitor_enabled=True,
                          monitor_interval_s=0.25, profiler_hz=97.0,
                          device_telemetry_enabled=False,
                          accounting_enabled=False)),
    )
    if only:
        modes = tuple((m, o) for m, o in modes if m in only)
    walls = {}
    for mode, overrides in modes:
        fiber_tpu.init(**overrides)
        best = None
        for _ in range(int(args.telemetry_reps)):
            with fiber_tpu.Pool(workers) as pool:
                pool.map(_timed_task, [0.0] * workers)  # spin-up barrier
                t0 = time.perf_counter()
                pool.map(_timed_task, [duration] * n_tasks)
                wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        walls[mode] = best
        _emit({"metric": f"pool_telemetry_{mode}_tasks_per_sec",
               "value": round(n_tasks / best, 1), "unit": "tasks/s",
               "tasks": n_tasks, "task_s": duration,
               "wall_s": round(best, 4)})
    fiber_tpu.init()
    overheads = {mode: round(walls[mode] / walls["off"], 4)
                 for mode in walls if mode != "off"}
    gated = tuple(m for m in ("tracing", "flightrec", "monitor",
                              "device", "accounting", "profiler")
                  if m in overheads)
    over = {mode: overheads[mode] > _TELEMETRY_BUDGET for mode in gated}
    if only:
        # Focused gate (`make bench-accounting`): one summary line per
        # measured arm vs off.
        for mode in gated:
            _emit({"metric": f"pool_{mode}_overhead",
                   "value": overheads[mode], "unit": "x vs off",
                   "budget": _TELEMETRY_BUDGET,
                   "over_budget": over[mode]})
    else:
        _emit({"metric": "pool_telemetry_overhead",
               "value": overheads["tracing"], "unit": "x vs off",
               "metrics_only_overhead": overheads["metrics"],
               "flightrec_overhead": overheads["flightrec"],
               "monitor_overhead": overheads["monitor"],
               "device_overhead": overheads["device"],
               "accounting_overhead": overheads["accounting"],
               "profiler_overhead": overheads["profiler"],
               "budget": _TELEMETRY_BUDGET,
               "over_budget": any(over.values())})
    for mode in gated:
        if over[mode]:
            print(f"FAIL: {mode} overhead {overheads[mode]} exceeds "
                  f"budget {_TELEMETRY_BUDGET}", file=sys.stderr)
    return 1 if any(over.values()) else 0


#: Minimum straggler-scenario speedup (speculation on vs off) the
#: `make bench-sched` gate demands, and the max uniform-workload wall
#: ratio (adaptive scheduler vs plain fifo handout) it tolerates.
_SCHED_SPEEDUP_FLOOR = 1.3
_SCHED_OVERHEAD_BUDGET = 1.05


def _sched_bench(args) -> int:
    """Scheduler-plane microbench (docs/scheduling.md), two scenarios:

    * **uniform** — evenly-sized tasks, healthy workers: the adaptive
      scheduler (locality + WDRR, speculation off) must stay within 5%
      of the plain fifo handout;
    * **straggler** — one chaos-slowed worker (``slow_worker`` knob:
      alive, heartbeating, just slow): speculation ON must beat
      speculation OFF by >= 1.3x map wall-clock, because duplicated
      straggler chunks complete on idle workers instead of serializing
      behind the slow host.

    Emits one JSON line per measurement plus a summary; exits nonzero
    when either gate fails. Best-of-N walls so a CI scheduler hiccup
    can't fail the gate."""
    import tempfile

    os.environ["FIBER_BACKEND"] = "local"
    import fiber_tpu
    from fiber_tpu.testing import chaos as chaosmod

    workers, reps = 4, int(args.sched_reps)

    def run_uniform(policy: str) -> float:
        fiber_tpu.init(sched_policy=policy,
                       speculation_enabled=False)
        best = None
        for _ in range(reps):
            with fiber_tpu.Pool(workers) as pool:
                pool.map(_timed_task, [0.0] * workers)  # spin-up barrier
                t0 = time.perf_counter()
                pool.map(_timed_task, [0.002] * 400)
                wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        return best

    def run_straggler(speculate: bool) -> float:
        best = None
        for _ in range(reps):
            # Fresh token dir per repetition: exactly one worker claims
            # the slow token after the spin-up barrier (its 1st chunk)
            # and straggles for the whole timed map.
            plan = chaosmod.ChaosPlan(
                seed=7,
                token_dir=tempfile.mkdtemp(prefix="fiber-bench-sched-"),
                slow_worker_after_chunks=1, slow_worker_s=0.75,
                slow_worker_times=1)
            chaosmod.install(plan)
            try:
                fiber_tpu.init(sched_policy="adaptive",
                               speculation_enabled=speculate,
                               speculation_quantile=2.0)
                with fiber_tpu.Pool(workers) as pool:
                    pool.map(_timed_task, [0.0] * workers)
                    t0 = time.perf_counter()
                    pool.map(_timed_task, [0.004] * 160, chunksize=2)
                    wall = time.perf_counter() - t0
            finally:
                chaosmod.uninstall()
            best = wall if best is None else min(best, wall)
        return best

    fifo = run_uniform("fifo")
    adaptive = run_uniform("adaptive")
    overhead = round(adaptive / fifo, 4)
    for mode, wall in (("fifo", fifo), ("adaptive", adaptive)):
        _emit({"metric": f"sched_uniform_{mode}_tasks_per_sec",
               "value": round(400 / wall, 1), "unit": "tasks/s",
               "wall_s": round(wall, 4)})
    spec_off = run_straggler(False)
    spec_on = run_straggler(True)
    fiber_tpu.init()
    speedup = round(spec_off / spec_on, 4)
    for mode, wall in (("off", spec_off), ("on", spec_on)):
        _emit({"metric": f"sched_straggler_speculation_{mode}_wall_s",
               "value": round(wall, 4), "unit": "s",
               "tasks": 160, "slow_worker_s": 0.75})
    over = overhead > _SCHED_OVERHEAD_BUDGET
    slow = speedup < _SCHED_SPEEDUP_FLOOR
    _emit({"metric": "sched_gates",
           "straggler_speedup": speedup,
           "speedup_floor": _SCHED_SPEEDUP_FLOOR,
           "uniform_overhead": overhead,
           "overhead_budget": _SCHED_OVERHEAD_BUDGET,
           "over_budget": bool(over), "under_speedup": bool(slow)})
    if over:
        print(f"FAIL: adaptive-scheduler uniform overhead {overhead} "
              f"exceeds budget {_SCHED_OVERHEAD_BUDGET}",
              file=sys.stderr)
    if slow:
        print(f"FAIL: straggler speculation speedup {speedup} below "
              f"floor {_SCHED_SPEEDUP_FLOOR}", file=sys.stderr)
    return 1 if (over or slow) else 0


#: `make bench-autonomy` gates (docs/observability.md "Autonomous
#: operations"): every injected fault class must yield a COMPLETE
#: narrated flight chain (anomaly -> cause_id-linked action -> verified
#: outcome), the policy-enabled chaos soak must lose zero tasks, and
#: the engine on-but-idle may cost <= 5% on the signature small-task
#: workload (it rides hooks that already fired; idle it must be free).
_AUTONOMY_BUDGET = 1.05


def _autonomy_bench(args) -> int:
    """Policy-plane (autonomous operations) bench, three phases:

    1. **chain drills** — one synthetic breach per fault class
       (tx_queue_high, heartbeat_age, store_disk_fill,
       recompile_storm, budget_exceeded) against a fresh watchdog with
       the engine live; each must leave a complete flight chain — the
       anomaly event, at least one policy action linked by ``cause_id``,
       and a verified outcome event.
    2. **chaos soak** — the signature echo map under slow-worker +
       worker-kill chaos with the policy engine ENABLED: every result
       must come back exactly once (the engine throttling/boosting
       mid-map must never lose a task).
    3. **on-but-idle overhead** — small-task pool throughput with the
       full monitor plane on, engine off vs on (no anomalies firing):
       the engine may cost <= 5%.

    Emits one JSON line per measurement plus a gate summary; exits
    nonzero when any gate fails."""
    import tempfile

    os.environ["FIBER_BACKEND"] = "local"
    import fiber_tpu
    from fiber_tpu import config
    from fiber_tpu.telemetry import explain as explainmod
    from fiber_tpu.telemetry import monitor as monitormod
    from fiber_tpu.telemetry import policy as policymod
    from fiber_tpu.telemetry.flightrec import FLIGHT
    from fiber_tpu.telemetry.monitor import AnomalyWatchdog, WATCHDOG
    from fiber_tpu.telemetry.policy import POLICY
    from fiber_tpu.telemetry.timeseries import TIMESERIES
    from fiber_tpu.testing import chaos as chaosmod
    from tests import targets

    def _reset():
        TIMESERIES.clear()
        WATCHDOG.clear()
        FLIGHT.clear()
        POLICY.reset()

    def _dog(**overrides) -> AnomalyWatchdog:
        fiber_tpu.init(policy_verify_s=0.1, policy_cooldown_s=0.0,
                       **overrides)
        dog = AnomalyWatchdog()
        dog.configure(config.get())
        return dog

    def _sample(**kw):
        base = {"wall": time.time(), "mono": time.monotonic(),
                "tasks_per_s": 0.0, "inflight": 0.0,
                "queue_depth": 0.0, "heartbeat_age_s": 0.0,
                "tx_queue_bytes": 0.0}
        base.update(kw)
        return base

    # -- phase 1: per-fault-class chain drills -------------------------
    def drill_tx(dog):
        dog.observe(_sample(tx_queue_bytes=float(64 << 20)))
        return None

    def drill_heartbeat(dog):
        from fiber_tpu.sched.core import Scheduler
        from fiber_tpu.store.replicate import REPLICATOR

        sched = Scheduler(n_workers=2, policy="adaptive",
                          speculation=True, speculation_quantile=4.0)
        REPLICATOR.register_driver(lambda reason: 1)
        REPLICATOR.note(["d" * 64])
        dog.observe(_sample(heartbeat_age_s=9.0))

        def cleanup():
            REPLICATOR.register_driver(None)
            REPLICATOR.forget(["d" * 64])
            sched.close()
        return cleanup

    def drill_store(dog):
        from fiber_tpu import store as storemod
        from fiber_tpu.store.core import LocalStore

        st = LocalStore(
            capacity_bytes=1 << 20,
            root=tempfile.mkdtemp(prefix="fiber-bench-autonomy-"),
            max_disk_bytes=100 << 10)
        prev = storemod._store
        storemod._store = st
        for i in range(12):
            st.put_bytes(bytes([i]) * (8 << 10), persist=True)
        dog.observe(_sample())

        def cleanup():
            storemod._store = prev
        return cleanup

    def drill_recompile(dog):
        storm = {"storm": True, "fingerprint": "bench.fn@" + "x" * 60,
                 "count": 9, "window_s": 30}
        prev = monitormod._recompile_state
        monitormod._recompile_state = lambda: dict(storm)
        dog.observe(_sample())

        def cleanup():
            monitormod._recompile_state = prev
        return cleanup

    def drill_budget(dog):
        class _Billed:
            def throttle_billing_key(self, key, factor=4.0):
                return 1

            def unthrottle_billing_key(self, key):
                return 1

        pool = _Billed()
        policymod.register_pool(pool)
        dog.external_breach("budget_exceeded",
                            detail="tenant over budget",
                            key="tenant/job/m1", observed=2.0)
        return lambda p=pool: None  # closure keeps the stub referenced

    drills = (
        ("tx_queue_high", {}, drill_tx),
        ("heartbeat_age", {"suspect_timeout": 10.0}, drill_heartbeat),
        ("store_disk_fill", {}, drill_store),
        ("recompile_storm", {}, drill_recompile),
        ("budget_exceeded", {}, drill_budget),
    )
    chain_fail = []
    for rule, overrides, drill in drills:
        _reset()
        dog = _dog(**overrides)
        cleanup = drill(dog)
        try:
            POLICY.poll(now=time.monotonic() + 60.0)  # force the verify
            chains = explainmod.policy_chains(FLIGHT.snapshot())
            chain = next(
                (c for c in chains if c["anomaly"] is not None
                 and c["anomaly"].get("kind") == rule), None)
            linked = (
                chain is not None and len(chain["actions"]) >= 1
                and len(chain["outcomes"]) >= 1
                and all(e.get("cause_id") == chain["cause_id"]
                        for e in chain["actions"] + chain["outcomes"]))
            _emit({"metric": f"autonomy_chain_{rule}",
                   "value": int(bool(linked)), "unit": "linked",
                   "action": (chain["actions"][0].get("kind")
                              if chain and chain["actions"] else None),
                   "applied": (bool(chain["actions"][0].get("applied"))
                               if chain and chain["actions"] else False),
                   "outcome": (chain["outcomes"][0].get("outcome")
                               if chain and chain["outcomes"] else None)})
            if not linked:
                chain_fail.append(rule)
        finally:
            if cleanup is not None:
                cleanup()
    _reset()

    # -- phase 2: chaos soak with the engine live ----------------------
    fiber_tpu.init(telemetry_enabled=True,
                   trace_sample_rate=0.0, flightrec_enabled=True,
                   monitor_enabled=True, monitor_interval_s=0.25,
                   policy_enabled=True, policy_verify_s=0.5,
                   policy_cooldown_s=0.0, speculation_enabled=True,
                   speculation_quantile=2.0)
    soak_tasks, workers = 120, 4
    plan = chaosmod.install(chaosmod.ChaosPlan(
        seed=13, token_dir=tempfile.mkdtemp(prefix="fiber-bench-autonomy-"),
        slow_worker_after_chunks=1, slow_worker_s=0.4,
        slow_worker_times=1, kill_after_chunks=2, kill_times=1))
    try:
        with fiber_tpu.Pool(workers) as pool:
            pool.map(_timed_task, [0.0] * workers)  # spin-up barrier
            t0 = time.perf_counter()
            out = pool.map(targets.sleep_echo, list(range(soak_tasks)),
                           chunksize=2)
            soak_wall = time.perf_counter() - t0
    finally:
        chaosmod.uninstall()
    lost = sum(1 for i, v in enumerate(out) if v != i) \
        + max(0, soak_tasks - len(out))
    _emit({"metric": "autonomy_soak_lost_tasks",
           "value": lost, "unit": "tasks",
           "tasks": soak_tasks, "wall_s": round(soak_wall, 3),
           "worker_killed": plan.spent("kill"),
           "slow_worker_claimed": plan.spent("slow"),
           "policy_actions": int(POLICY.actions_total)})
    _reset()

    # -- phase 3: on-but-idle overhead ---------------------------------
    n_tasks, duration = 600, 0.001
    walls = {}
    for mode, on in (("off", False), ("on", True)):
        fiber_tpu.init(telemetry_enabled=True,
                       trace_sample_rate=0.0, flightrec_enabled=True,
                       monitor_enabled=True, monitor_interval_s=0.25,
                       device_telemetry_enabled=False,
                       accounting_enabled=False, policy_enabled=on)
        best = None
        for _ in range(int(args.autonomy_reps)):
            with fiber_tpu.Pool(workers) as pool:
                pool.map(_timed_task, [0.0] * workers)
                t0 = time.perf_counter()
                pool.map(_timed_task, [duration] * n_tasks)
                wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
        walls[mode] = best
        _emit({"metric": f"pool_policy_{mode}_tasks_per_sec",
               "value": round(n_tasks / best, 1), "unit": "tasks/s",
               "tasks": n_tasks, "task_s": duration,
               "wall_s": round(best, 4)})
    fiber_tpu.init()
    overhead = round(walls["on"] / walls["off"], 4)

    # -- gates ---------------------------------------------------------
    over = overhead > _AUTONOMY_BUDGET
    lossy = lost > 0
    broken = bool(chain_fail)
    _emit({"metric": "autonomy_gates",
           "chains_linked": len(drills) - len(chain_fail),
           "chains_total": len(drills),
           "chains_broken": chain_fail,
           "soak_lost_tasks": lost,
           "idle_overhead": overhead,
           "overhead_budget": _AUTONOMY_BUDGET,
           "over_budget": bool(over), "lossy": bool(lossy),
           "chain_fail": broken})
    if broken:
        print(f"FAIL: fault class(es) {chain_fail} left no complete "
              "anomaly -> action -> outcome flight chain",
              file=sys.stderr)
    if lossy:
        print(f"FAIL: policy-enabled chaos soak lost {lost} of "
              f"{soak_tasks} tasks", file=sys.stderr)
    if over:
        print(f"FAIL: policy-engine idle overhead {overhead} exceeds "
              f"budget {_AUTONOMY_BUDGET}", file=sys.stderr)
    return 1 if (broken or lossy or over) else 0


#: `make bench-recovery` gates (docs/robustness.md "Durable maps"): the
#: write-ahead ledger must cost <= 5% on the NO-CRASH path (the common
#: case pays for the rare one, bounded), and resuming a 75%-journaled
#: job must take well under the full run's wall — recovery time scales
#: with the REMAINING tasks, not the total (Ray's lineage posture:
#: recompute only what was lost).
_RECOVERY_OVERHEAD_BUDGET = 1.05
_RECOVERY_PARTIAL_MAX = 0.6


def _recovery_bench(args) -> int:
    """Durable-map recovery microbench (docs/robustness.md):

    * **overhead** — the signature small-task map with ``job_id=``
      (full journaling: header fsync + per-chunk result persist +
      batched record fsyncs) vs without; gated <= 5%;
    * **proportionality** — complete a ledgered run, truncate its
      journal to 75% of the chunk records (exactly the state a master
      crash at that point leaves), resume: the resumed wall must be
      <= ``_RECOVERY_PARTIAL_MAX`` of the full wall, and the
      restored/executed split must reconcile to exactly one result per
      task (ledger + pool counters).

    Best-of-N walls so a CI scheduler hiccup can't fail the gate."""
    import json as _json
    import tempfile

    os.environ["FIBER_BACKEND"] = "local"
    # Private staging root: the bench's ledgers/objects must not land in
    # (or read from) the operator's real ~/.fiber_tpu.
    os.environ["FIBER_AGENT_STAGING"] = tempfile.mkdtemp(
        prefix="fiber-bench-recovery-")
    import fiber_tpu
    from fiber_tpu.store import ledger as ledgermod

    workers = 4
    n_tasks, task_s, chunksize = int(args.recovery_tasks), 0.004, 4
    reps = max(1, int(args.recovery_reps))
    fiber_tpu.init()
    uid = os.getpid()

    def run_map(job_id):
        with fiber_tpu.Pool(workers) as pool:
            pool.map(_timed_task, [0.0] * workers)  # spin-up barrier
            before = pool.stats()
            t0 = time.perf_counter()
            pool.map(_timed_task, [task_s] * n_tasks,
                     chunksize=chunksize, job_id=job_id)
            wall = time.perf_counter() - t0
            after = pool.stats()
        # Diff around the timed map so the barrier's tasks don't
        # pollute the exactly-once reconciliation.
        stats = {"tasks_completed": (after["tasks_completed"]
                                     - before["tasks_completed"]),
                 "tasks_restored": (after["tasks_restored"]
                                    - before["tasks_restored"])}
        return wall, stats

    # 1. No-crash ledger overhead (paired reps so box drift cancels).
    plain = ledgered = None
    for rep in range(reps):
        w, _ = run_map(None)
        plain = w if plain is None else min(plain, w)
        w, _ = run_map(f"bench-recovery-{uid}-{rep}")
        ledgered = w if ledgered is None else min(ledgered, w)
    overhead = round(ledgered / plain, 4)
    for mode, wall in (("off", plain), ("on", ledgered)):
        _emit({"metric": f"recovery_ledger_{mode}_tasks_per_sec",
               "value": round(n_tasks / wall, 1), "unit": "tasks/s",
               "tasks": n_tasks, "task_s": task_s,
               "wall_s": round(wall, 4)})

    # 2. Recovery wall proportional to the REMAINING tasks.
    keep_frac = 0.75
    full = resume = None
    restored = executed = 0
    exact = True
    for rep in range(reps):
        job = f"bench-resume-{uid}-{rep}"
        w_full, _ = run_map(job)
        path = ledgermod.job_path(job)
        with open(path) as fh:
            records = [_json.loads(ln) for ln in fh if ln.strip()]
        header = [r for r in records if r.get("kind") == "map"]
        chunks = [r for r in records if r.get("kind") == "chunk"]
        keep = chunks[:int(len(chunks) * keep_frac)]
        with open(path, "w") as fh:
            for rec in header + keep:
                fh.write(_json.dumps(rec) + "\n")
        w_resume, stats = run_map(job)
        restored = stats["tasks_restored"]
        executed = stats["tasks_completed"]
        exact = exact and (restored + executed == n_tasks)
        full = w_full if full is None else min(full, w_full)
        resume = w_resume if resume is None else min(resume, w_resume)
    ratio = round(resume / full, 4)
    fiber_tpu.init()
    _emit({"metric": "recovery_resume_wall_s", "value": round(resume, 4),
           "unit": "s", "full_wall_s": round(full, 4),
           "journaled_frac": keep_frac,
           "restored_tasks": restored, "executed_tasks": executed})
    over = overhead > _RECOVERY_OVERHEAD_BUDGET
    slow = ratio > _RECOVERY_PARTIAL_MAX
    _emit({"metric": "recovery_gates",
           "ledger_overhead": overhead,
           "overhead_budget": _RECOVERY_OVERHEAD_BUDGET,
           "resume_ratio": ratio, "ratio_max": _RECOVERY_PARTIAL_MAX,
           "exactly_once": bool(exact),
           "over_budget": bool(over), "over_ratio": bool(slow)})
    if over:
        print(f"FAIL: no-crash ledger overhead {overhead} exceeds "
              f"budget {_RECOVERY_OVERHEAD_BUDGET}", file=sys.stderr)
    if slow:
        print(f"FAIL: resume of a {keep_frac:.0%}-journaled job took "
              f"{ratio}x the full wall (max {_RECOVERY_PARTIAL_MAX}) — "
              "recovery is not proportional to the remainder",
              file=sys.stderr)
    if not exact:
        print("FAIL: restored + executed != total tasks — the "
              "exactly-once ledger contract broke", file=sys.stderr)
    return 1 if (over or slow or not exact) else 0


#: `make bench-cluster` gates (docs/observability.md, ROADMAP item 5):
#: the full-stack macro bench must sustain this many end-to-end evals
#: per second through the WHOLE stack at once (sim multi-host pool +
#: store broadcasts + tracing + flight recorder), and the per-task wire
#: cost of an 8MB-class broadcast must stay by-reference-shaped (the
#: ship-by-value cost would be ~8MB/task). Floors are deliberately
#: conservative — the gate exists to catch cross-plane regressions
#: (sched x store x transport) that hide in green unit suites, not to
#: race the hardware.
_CLUSTER_EVALS_FLOOR = 20.0
_CLUSTER_BYTES_PER_TASK_MAX = 1 << 20


def _cluster_bench(args) -> int:
    """Full-stack macro bench (ROADMAP item 5): one measurement that
    exercises every infrastructure plane at once — a simulated
    multi-host pod (host agents on localhost), per-generation 8MB
    broadcasts through the object store, straggler + worker-kill chaos,
    and full tracing + flight recorder on. Three phases:

    1. **throughput** (no chaos): ``--cluster-gens`` generations of
       ``--cluster-tasks`` evals over a fresh ``--cluster-mb`` broadcast
       each — gates end-to-end evals/s and wire bytes-per-task, and
       wires utils/flops.py so ``mfu``/``peak_row`` are populated
       whenever a device peak resolves (CPU runs record null honestly);
    2. **straggler** (chaos slow worker, speculation on): the traced map
       plus the flight buffer are archived into RUNS/ as the Perfetto +
       flight artifacts, and ``fiber-tpu explain``'s classifier must
       attribute the injected straggler to the straggler category;
    3. **worker-kill** (chaos hard kill): the map must complete via
       resubmission AND the dead worker's crash handler must have
       flushed a postmortem bundle carrying its flight events and stack
       dump.

    Emits one JSON line per phase plus a gate summary;
    `make bench-cluster` tees them into BENCH_cluster.json and fails on
    any missed gate."""
    import tempfile

    import numpy as np

    os.environ["FIBER_BACKEND"] = "tpu"
    os.environ["FIBER_TPU_HOSTS"] = f"sim:{int(args.cluster_hosts)}"
    import fiber_tpu
    from fiber_tpu.telemetry import explain as explainmod
    from fiber_tpu.telemetry import postmortem, tracing
    from fiber_tpu.testing import chaos as chaosmod
    from tests import targets

    runs_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "RUNS")
    os.makedirs(runs_dir, exist_ok=True)
    run_id = int(time.time())
    workers = 4
    gens = int(args.cluster_gens)
    tasks = int(args.cluster_tasks)
    payload_mb = float(args.cluster_mb)
    n_elems = int(payload_mb * (1 << 20) / 4)
    # Seeded by the run id, NOT a fixed seed: the host object cache
    # persists across runs (that is its job), and a byte-identical
    # payload would resolve from disk with zero wire traffic — turning
    # the bytes-per-task gate into a vacuous 0.
    base_arr = np.random.default_rng(run_id).standard_normal(
        n_elems).astype(np.float32)

    fiber_tpu.init(telemetry_enabled=True,
                   trace_sample_rate=1.0, flightrec_enabled=True,
                   store_enabled=True, speculation_enabled=True,
                   speculation_quantile=2.0)

    # -- phase 1: end-to-end throughput + bytes-per-task --------------
    with fiber_tpu.Pool(workers) as pool:
        pool.map(_timed_task, [0.0] * workers)  # spin-up barrier
        before = pool.store_stats()
        t0 = time.perf_counter()
        for gen in range(gens):
            # A FRESH broadcast per generation (params change every ES
            # step): each one must cross the wire by reference, once
            # per host cache, never once per task.
            arr = base_arr + np.float32(gen)
            out = pool.starmap(targets.arr_sum_plus,
                               [(arr, i) for i in range(tasks)],
                               chunksize=max(1, tasks // (workers * 4)))
            assert len(out) == tasks
        wall = time.perf_counter() - t0
        after = pool.store_stats()
    total_evals = gens * tasks
    evals_per_sec = total_evals / wall
    bytes_per_task = (after.get("bytes_served", 0)
                      - before.get("bytes_served", 0)) / total_evals

    # MFU accounting (utils/flops.py): the eval is a full-array
    # reduction + scalar mix — n_elems FLOPs per eval, analytically.
    # On CPU the peak is unknown and mfu records null honestly; any
    # resolved device peak (real TPU, or FIBER_PEAK_FLOPS) populates
    # it, which the gate below asserts.
    import jax

    devices = jax.devices()
    from fiber_tpu.utils import flops as flopsmod

    model_fps = evals_per_sec * float(n_elems)
    mfu = flopsmod.mfu(model_fps, devices)
    peak = flopsmod.peak_report(devices)
    mfu_broken = peak.get("peak_row") is not None and mfu is None
    _emit({"metric": "cluster_evals_per_sec",
           "value": round(evals_per_sec, 2), "unit": "evals/s",
           "hosts": int(args.cluster_hosts), "workers": workers,
           "generations": gens, "tasks_per_gen": tasks,
           "payload_mb": payload_mb, "wall_s": round(wall, 3),
           "model_flops_per_sec": round(model_fps, 1),
           "mfu": _round_mfu(mfu), **peak,
           "platform": devices[0].platform})
    _emit({"metric": "cluster_bytes_per_task",
           "value": round(bytes_per_task, 1), "unit": "bytes",
           "budget": _CLUSTER_BYTES_PER_TASK_MAX,
           "ship_by_value_bytes": int(payload_mb * (1 << 20))})

    # -- phase 1b: device-path map with analytic FLOPs -----------------
    # Same pod, but the eval is @meta(device=True, flops=…): the map
    # lowers onto the mesh, the broadcast param rides the device store
    # tier (docs/objectstore.md "Device tier"), and the pool feeds
    # DEVICE.note_map_flops so live MFU is recorded per map. Under
    # FIBER_PEAK_FLOPS (or a real TPU kind) mfu must be non-null; HBM
    # stays an honest null wherever memory_stats() is unavailable.
    from fiber_tpu import store as storemod
    from fiber_tpu.meta import meta as fmeta
    from fiber_tpu.telemetry.device import DEVICE as devplane

    dev_eval = fmeta(device=True, flops=2.0 * n_elems)(_ici_eval)
    dev_items = [(base_arr, np.float32(i)) for i in range(tasks)]
    with fiber_tpu.Pool(workers) as pool:
        out = pool.starmap(dev_eval, dev_items)  # compile + tier fill
        t0 = time.perf_counter()
        for _ in range(gens):
            out = pool.starmap(dev_eval, dev_items)
        dev_wall = time.perf_counter() - t0
        assert len(out) == tasks
    dsnap = devplane.snapshot()
    dev_mfu = (dsnap.get("mfu") or {}).get("mfu")
    dev_peak_row = (dsnap.get("mfu") or {}).get("peak_row")
    hbm = dsnap.get("hbm") or {}
    ici_site = (dsnap.get("transfers") or {}).get("ici") or {}
    tier = storemod._dtier  # peek: never instantiate from a bench read
    tier_stats = tier.stats() if tier is not None else {}
    dev_mfu_broken = dev_peak_row is not None and dev_mfu is None
    _emit({"metric": "cluster_device_mfu",
           "value": _round_mfu(dev_mfu), "unit": "mfu",
           "peak_row": dev_peak_row,
           "flops_per_item": 2.0 * n_elems,
           "generations": gens, "tasks_per_gen": tasks,
           "payload_mb": payload_mb, "wall_s": round(dev_wall, 3),
           "hbm_bytes_in_use": hbm.get("bytes_in_use"),
           "hbm_bytes_limit": hbm.get("bytes_limit"),
           "ici_transfer_bytes": int(ici_site.get("bytes", 0)),
           "device_tier_hits": int(tier_stats.get("hits", 0)),
           "device_tier_bytes": int(tier_stats.get("bytes", 0))})

    # -- phase 2: straggler chaos + explain ----------------------------
    from fiber_tpu.telemetry.flightrec import FLIGHT

    tracing.SPANS.clear()
    FLIGHT.clear()
    plan = chaosmod.install(chaosmod.ChaosPlan(
        seed=11, token_dir=tempfile.mkdtemp(prefix="fiber-bench-cluster-"),
        slow_worker_after_chunks=1, slow_worker_s=0.6,
        slow_worker_times=1))
    try:
        with fiber_tpu.Pool(workers) as pool:
            pool.map(_timed_task, [0.0] * workers)
            t0 = time.perf_counter()
            out = pool.map(targets.sleep_echo, list(range(120)),
                           chunksize=2)
            straggler_wall = time.perf_counter() - t0
            assert out == list(range(120))
            # Let the last workers' span batches land on the result
            # stream before the artifact is cut.
            deadline = time.time() + 5
            while time.time() < deadline and len(
                    [s for s in tracing.SPANS.snapshot()
                     if s["name"] == "worker.execute"]) < 60:
                time.sleep(0.05)
            trace_path = os.path.join(
                runs_dir, f"cluster_trace_{run_id}.json")
            flight_path = os.path.join(
                runs_dir, f"cluster_flight_{run_id}.json")
            pool.trace_dump(trace_path)
            pool.flight_dump(flight_path)
    finally:
        chaosmod.uninstall()
    verdict = explainmod.explain_trace(
        explainmod.load_spans(trace_path),
        explainmod.load_events(flight_path), quantile=2.0)
    _emit({"metric": "cluster_explain",
           "value": verdict["primary"], "unit": "category",
           "slow_worker_claimed": plan.spent("slow"),
           "straggler_blame_s": verdict["budget"]["straggler"],
           "speculations": verdict["evidence"]["straggler"][
               "speculations"],
           "wall_s": round(straggler_wall, 3),
           "trace_artifact": trace_path,
           "flight_artifact": flight_path})

    # -- phase 3: worker-kill chaos + postmortem bundle ----------------
    pm_dir = postmortem.bundle_dir()
    bundles_before = set(postmortem.list_bundles(pm_dir))
    plan = chaosmod.install(chaosmod.ChaosPlan(
        seed=12, token_dir=tempfile.mkdtemp(prefix="fiber-bench-cluster-"),
        kill_after_chunks=2, kill_times=1))
    try:
        with fiber_tpu.Pool(workers) as pool:
            pool.map(_timed_task, [0.0] * workers)
            out = pool.map(targets.sleep_echo, list(range(80)),
                           chunksize=2)
            assert out == list(range(80))
    finally:
        chaosmod.uninstall()
    fiber_tpu.init()
    new_bundles = sorted(set(postmortem.list_bundles(pm_dir))
                         - bundles_before)
    bundle = {}
    for path in reversed(new_bundles):
        try:
            candidate = postmortem.read_bundle(path)
        except (OSError, ValueError):
            continue
        if candidate.get("reason") == "chaos-kill":
            bundle = candidate
            bundle["_path"] = path
            break
    bundle_ok = bool(bundle.get("flight")) and bool(bundle.get("stacks"))
    _emit({"metric": "cluster_postmortem",
           "value": len(new_bundles), "unit": "bundles",
           "worker_killed": plan.spent("kill"),
           "bundle_has_flight": bool(bundle.get("flight")),
           "bundle_has_stacks": bool(bundle.get("stacks")),
           "bundle_path": bundle.get("_path", "")})

    # -- gates ---------------------------------------------------------
    slow = evals_per_sec < _CLUSTER_EVALS_FLOOR
    fat = bytes_per_task > _CLUSTER_BYTES_PER_TASK_MAX
    misattributed = verdict["primary"] != "straggler"
    _emit({"metric": "cluster_gates",
           "evals_per_sec": round(evals_per_sec, 2),
           "evals_floor": _CLUSTER_EVALS_FLOOR,
           "bytes_per_task": round(bytes_per_task, 1),
           "bytes_budget": _CLUSTER_BYTES_PER_TASK_MAX,
           "explain_primary": verdict["primary"],
           "postmortem_ok": bundle_ok,
           "mfu_broken": bool(mfu_broken),
           "device_mfu_broken": bool(dev_mfu_broken),
           "under_floor": bool(slow), "over_budget": bool(fat),
           "misattributed": bool(misattributed)})
    rc = 0
    if slow:
        print(f"FAIL: cluster evals/s {evals_per_sec:.1f} below floor "
              f"{_CLUSTER_EVALS_FLOOR}", file=sys.stderr)
        rc = 1
    if fat:
        print(f"FAIL: cluster bytes/task {bytes_per_task:.0f} exceeds "
              f"budget {_CLUSTER_BYTES_PER_TASK_MAX}", file=sys.stderr)
        rc = 1
    if misattributed:
        print(f"FAIL: explain attributed the injected straggler to "
              f"{verdict['primary']!r}, not 'straggler'",
              file=sys.stderr)
        rc = 1
    if not bundle_ok:
        print("FAIL: chaos worker-kill produced no postmortem bundle "
              "with flight events + stack dump", file=sys.stderr)
        rc = 1
    if mfu_broken:
        print("FAIL: device peak resolved but mfu is null — "
              "utils/flops.py wiring broke", file=sys.stderr)
        rc = 1
    if dev_mfu_broken:
        print("FAIL: device peak resolved but the @meta(device=True, "
              "flops=…) map recorded a null mfu — "
              "DEVICE.note_map_flops wiring broke", file=sys.stderr)
        rc = 1
    return rc


#: `make bench-transport` gates (docs/transport.md): the selector I/O
#: core must beat the thread-per-connection path by this much on
#: small-frame I/O-engine throughput (batched decode + coalescing is
#: the whole point) while giving up at most 5% on large-frame wall
#: throughput (scatter-gather must not regress the tensor path).
_TRANSPORT_SMALL_FLOOR = 1.5
_TRANSPORT_LARGE_FLOOR = 0.95


#: Worker-role pusher run by _transport_ingest in a subprocess: dials
#: ``conns`` connections to the master's bound endpoint and blasts
#: ``frames_per_conn`` frames of ``size`` bytes round-robin down each.
#: Always transport_io=threads on the worker side so the ONLY variable
#: between scenarios is the master's I/O engine.
_TRANSPORT_PRODUCER = r"""
import os
import sys
import time

sys.path.insert(0, sys.argv[1])
addr, conns, frames_per_conn, size, start_file = (
    sys.argv[2], int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5]),
    sys.argv[6])
from fiber_tpu.transport.tcp import Endpoint

payload = b"\x5a" * size
eps = [Endpoint("w", io="threads").connect(addr) for _ in range(conns)]
# Start barrier: connect, then hold fire until the master opens its
# timed window (it creates start_file after wait_for_peers). Without
# this, a scheduling-dependent slice of the ingest lands BEFORE the
# master's clocks start and the measurement swings run to run.
deadline = time.time() + 120
while not os.path.exists(start_file):
    if time.time() > deadline:
        sys.exit(2)
    time.sleep(0.003)
for _ in range(frames_per_conn):
    for ep in eps:
        ep.send(payload, timeout=180)
time.sleep(600)  # hold connections open; the master kills us when done
"""


def _transport_ingest(io: str, workers: int, per_worker: int,
                      size: int, procs: int = 8,
                      credit_window: int = 0):
    """Master-side ingest measurement (the fiber paper's bottleneck
    shape: one master, a pod-slice of workers): ``workers`` simulated
    worker connections spread over ``procs`` pusher subprocesses fan
    frames into ONE bound endpoint under I/O engine ``io``. Returns
    (wall_s, engine CPU seconds, master CPU seconds, master transport
    thread count). *Engine* CPU is the master's process CPU minus the
    consuming thread's own CPU (``time.thread_time``): the recv() loop
    does identical work under both engines (inbox pop, credit
    replenish), so subtracting it leaves exactly the cost attributable
    to the I/O engine — reader threads' decode + GIL handoff on the
    threads path, the poller on the selector path. The producers run in
    their own processes precisely so every number isolates the master —
    the thing the selector loop exists to fix — instead of mixing in
    sender-side Python."""
    import subprocess
    import tempfile
    import threading

    from fiber_tpu import config as fconfig
    from fiber_tpu.transport.tcp import Endpoint

    repo = os.path.dirname(os.path.abspath(__file__))
    start_file = tempfile.mktemp(prefix="fiber-bench-go-")
    old_window = fconfig.get().transport_credit_window
    if credit_window:
        # Steady-state pacing: a small standing window keeps the pushers
        # streaming against the master's consumption instead of
        # pre-buffering the whole run into socket buffers — the
        # continuous-ingest regime a production master actually faces.
        fconfig.get().update(transport_credit_window=credit_window)
    # Let stragglers from the previous scenario's teardown exit so the
    # thread census below counts only THIS scenario's engine.
    deadline = time.time() + 10
    while (any(t.name.startswith("fiber-chan-")
               for t in threading.enumerate())
           and time.time() < deadline):
        time.sleep(0.05)
    pull = Endpoint("r", io=io)
    addr = pull.bind("127.0.0.1")
    conns = workers // procs
    children = [
        subprocess.Popen(
            [sys.executable, "-c", _TRANSPORT_PRODUCER, repo, addr,
             str(conns), str(per_worker), str(size), start_file],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        for _ in range(procs)
    ]
    try:
        if not pull.wait_for_peers(procs * conns, 120):
            raise RuntimeError("transport bench: pushers missing")
        total = procs * conns * per_worker
        io_threads = sum(
            1 for t in threading.enumerate()
            if t.name.startswith("fiber-chan-")
            or t.name == "fiber-evloop")
        t0 = time.perf_counter()
        c0 = time.process_time()
        s0 = time.thread_time()
        # Clocks armed — release the pushers (they poll for this file).
        with open(start_file, "w"):
            pass
        for _ in range(total):
            pull.recv(120)
        self_cpu = time.thread_time() - s0
        cpu = time.process_time() - c0
        return (time.perf_counter() - t0, max(cpu - self_cpu, 1e-9),
                cpu, io_threads)
    finally:
        fconfig.get().update(transport_credit_window=old_window)
        for child in children:
            child.kill()
            try:
                child.wait(10)
            except Exception:
                pass
        pull.close()
        try:
            os.unlink(start_file)
        except OSError:
            pass


def _transport_bench(args) -> int:
    """Transport I/O-core microbench (docs/transport.md): the selector
    event loop vs the thread-per-connection fallback at the MASTER of a
    64-simulated-worker ingest — (a) small-frame frames per I/O-engine-
    CPU-second, where one poller batching decode + inbox delivery beats
    64 GIL-contending reader threads (the consumer loop's own CPU is
    subtracted: it does identical work under both engines and would
    only dilute the engine difference), and (b) large-frame WALL
    throughput, where scatter-gather and the direct recv_into decode
    must at least hold parity (wall, because the large case is a
    pipeline bottlenecked on memcpy through loopback — stable — while
    its per-engine CPU split swings with kernel burst sizes). Records
    master CPU seconds and the transport thread census per engine.
    Emits one JSON line per metric; `make bench-transport` tees them
    into BENCH_transport.json and fails when a gate is missed.
    Best-of-N so a CI scheduler hiccup can't fail the gate."""
    reps = max(1, int(args.transport_reps))
    workers, per_small, small = 64, 500, 64
    large_frames, large = 48, 8 << 20
    total_small = workers * per_small
    nbytes = large_frames * large
    # PAIRED measurement: each rep runs threads then selector back to
    # back and the gate compares within the pair — a shared CI box
    # drifts (frequency scaling, page cache, neighbors) on a timescale
    # of many seconds, so adjacent runs see the same machine and the
    # drift cancels out of the ratio. The gated ratio is the best pair
    # (the same best-of-N convention every other gate here uses); the
    # full per-pair list is recorded for transparency.
    small_runs = {"threads": [], "selector": []}
    large_runs = {"threads": [], "selector": []}
    small_ratios = []
    large_ratios = []
    for _ in range(reps):
        pair = {io: _transport_ingest(io, workers, per_small, small,
                                      credit_window=64)
                for io in ("threads", "selector")}
        for io, run in pair.items():
            small_runs[io].append(run)
        # engine-CPU seconds, inverted: higher = selector cheaper
        small_ratios.append(pair["threads"][1] / pair["selector"][1])
    for _ in range(max(reps, 5)):
        pair = {io: _transport_ingest(io, 4, large_frames // 4, large,
                                      procs=4)
                for io in ("threads", "selector")}
        for io, run in pair.items():
            large_runs[io].append(run)
        large_ratios.append(pair["threads"][0] / pair["selector"][0])
    fps = {}
    mbs = {}
    for io in ("threads", "selector"):
        runs = small_runs[io]
        wall = min(r[0] for r in runs)
        engine_cpu = min(r[1] for r in runs)
        fps[io] = total_small / engine_cpu
        _emit({"metric": f"transport_{io}_small_frames_per_sec",
               "value": round(fps[io], 1), "unit": "frames/io-engine-cpu-s",
               "workers": workers, "frames": total_small,
               "frame_bytes": small,
               "engine_cpu_s": round(engine_cpu, 3),
               "master_cpu_s": round(min(r[2] for r in runs), 3),
               "master_io_threads": runs[0][3],
               "wall_fps": round(total_small / wall, 1),
               "wall_s": round(wall, 4)})
        runs = large_runs[io]
        wall = min(r[0] for r in runs)
        mbs[io] = nbytes / wall / (1 << 20)
        _emit({"metric": f"transport_{io}_large_mb_per_sec",
               "value": round(mbs[io], 1), "unit": "MiB/s",
               "frames": large_frames, "frame_bytes": large,
               "master_cpu_s": round(min(r[2] for r in runs), 3),
               "master_io_threads": runs[0][3],
               "wall_s": round(wall, 4)})
    small_ratio = round(max(small_ratios), 3)
    large_ratio = round(max(large_ratios), 3)
    slow_small = small_ratio < _TRANSPORT_SMALL_FLOOR
    slow_large = large_ratio < _TRANSPORT_LARGE_FLOOR
    _emit({"metric": "transport_selector_vs_threads",
           "value": small_ratio, "unit": "x small-frame frames/s",
           "large_ratio": large_ratio,
           "small_pair_ratios": [round(r, 3) for r in small_ratios],
           "large_pair_ratios": [round(r, 3) for r in large_ratios],
           "small_floor": _TRANSPORT_SMALL_FLOOR,
           "large_floor": _TRANSPORT_LARGE_FLOOR,
           "under_floor": bool(slow_small or slow_large)})
    if slow_small:
        print(f"FAIL: selector small-frame throughput {small_ratio}x "
              f"below floor {_TRANSPORT_SMALL_FLOOR}x", file=sys.stderr)
    if slow_large:
        print(f"FAIL: selector large-frame throughput {large_ratio}x "
              f"below floor {_TRANSPORT_LARGE_FLOOR}x", file=sys.stderr)
    return 1 if (slow_small or slow_large) else 0


_SCALE_ARM = r"""
import json
import os
import resource
import sys
import time

repo = sys.argv[1]
params = json.loads(sys.argv[2])
sys.path.insert(0, repo)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["FIBER_TRANSPORT_IO"] = params["io"]
os.environ["FIBER_DISPATCH_MODE"] = params["dispatch"]
os.environ["FIBER_CPU_PER_JOB"] = str(params["cpu_per_job"])
if params.get("range_chunks"):
    os.environ["FIBER_DISPATCH_RANGE_CHUNKS"] = str(params["range_chunks"])

import fiber_tpu
fiber_tpu.init()
from fiber_tpu.pool import ResilientPool


def tiny(x):
    return x


pool = ResilientPool(processes=params["processes"])
try:
    # Warm the worker population (and JIT the hot paths) outside the
    # timed window, so the arm measures steady-state dispatch.
    pool.map(tiny, range(256), chunksize=params["chunksize"])
    r0 = resource.getrusage(resource.RUSAGE_SELF)
    t0 = time.perf_counter()
    out = pool.map(tiny, range(params["tasks"]),
                   chunksize=params["chunksize"])
    wall = time.perf_counter() - t0
    r1 = resource.getrusage(resource.RUSAGE_SELF)
    assert len(out) == params["tasks"], "short result"
    assert out[5] == 5 and out[-1] == params["tasks"] - 1, "wrong result"
    st = pool.stats()
    print(json.dumps({
        "wall_s": wall,
        "master_cpu_s": (r1.ru_utime - r0.ru_utime)
                        + (r1.ru_stime - r0.ru_stime),
        "tasks": params["tasks"],
        "range_handouts": st["sched"]["decisions"].get("range", 0),
        "resubmitted": st["chunks_resubmitted"],
    }), flush=True)
finally:
    pool.close()
    pool.join()
"""


def _scale_arm(params: dict, timeout: float = 1800.0) -> dict:
    """Run one --scale arm in a fresh interpreter: the subprocess IS the
    master, so RUSAGE_SELF there is exactly master CPU (workers are its
    children), and the engine/dispatch knobs ride the environment
    without leaking into this process."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, "-c", _SCALE_ARM, repo, json.dumps(params)],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(
            f"scale arm {params['dispatch']}/{params['io']} failed:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


#: --scale gates: the hierarchical+shm arm must beat the single-master
#: direct+selector baseline by >= this factor in master dispatch
#: capacity (tasks per master-CPU-second) and spend <= this fraction of
#: its master CPU per task (ISSUE 12 acceptance).
_SCALE_TPS_FLOOR = 3.0
_SCALE_CPU_CEIL = 0.5


def _scale_bench(args) -> int:
    """Master scale-out macrobench (docs/architecture.md "Hierarchical
    dispatch"): push ``--scale-tasks`` (>= 1M by default) tiny tasks
    through hierarchical per-host dispatch over the same-host shm
    transport, against a single-master direct+selector baseline at the
    same chunksize (default 1 — a million tiny tasks through per-chunk
    REQ/REP on one master is precisely the regime this PR exists to
    escape). The headline ratios are per-TASK so the arms need not run
    the same task count; the baseline runs a calibration-sized slice.

    The throughput gate reads master dispatch CAPACITY — tasks per
    master-CPU-second — not end-to-end wall tasks/s. On a real pod the
    master is the wall-clock bottleneck for tiny tasks, so capacity IS
    the deliverable tasks/s; the CI sim pod serializes master,
    sub-master, and every worker onto one core, where wall time just
    measures total worker compute and dispatch savings only RELOCATE
    between processes. Both arms' raw wall tasks/s are emitted
    alongside so the record keeps the unnormalized numbers. Gates:
    >= ``_SCALE_TPS_FLOOR``x capacity and <= ``_SCALE_CPU_CEIL``x
    master CPU seconds per task. Emits JSON lines; ``make bench-scale``
    tees them into BENCH_scale.json and fails when a gate is missed."""
    chunk = int(args.scale_chunk)
    base_params = {
        "tasks": int(args.scale_base_tasks), "chunksize": chunk,
        "processes": int(args.scale_workers), "cpu_per_job": 1,
        "dispatch": "direct", "io": "selector",
    }
    hier_params = {
        "tasks": int(args.scale_tasks), "chunksize": chunk,
        "processes": int(args.scale_workers),
        "cpu_per_job": int(args.scale_workers),
        "dispatch": "hier", "io": "shm",
        "range_chunks": int(args.scale_range),
    }
    base = _scale_arm(base_params)
    hier = _scale_arm(hier_params)
    base_tps = base["tasks"] / base["wall_s"]
    hier_tps = hier["tasks"] / hier["wall_s"]
    base_cpt = base["master_cpu_s"] / base["tasks"]
    hier_cpt = hier["master_cpu_s"] / hier["tasks"]
    _emit({"metric": "scale_direct_capacity",
           "value": round(1.0 / base_cpt, 1),
           "unit": "tasks/master-cpu-s",
           "tasks": base["tasks"], "chunksize": chunk,
           "workers": base_params["processes"],
           "wall_s": round(base["wall_s"], 3),
           "wall_tasks_per_sec": round(base_tps, 1),
           "master_cpu_s": round(base["master_cpu_s"], 3),
           "master_cpu_us_per_task": round(base_cpt * 1e6, 3)})
    _emit({"metric": "scale_hier_capacity",
           "value": round(1.0 / hier_cpt, 1),
           "unit": "tasks/master-cpu-s",
           "tasks": hier["tasks"], "chunksize": chunk,
           "workers": hier_params["processes"],
           "cpu_per_job": hier_params["cpu_per_job"],
           "range_chunks": hier_params["range_chunks"],
           "wall_s": round(hier["wall_s"], 3),
           "wall_tasks_per_sec": round(hier_tps, 1),
           "master_cpu_s": round(hier["master_cpu_s"], 3),
           "master_cpu_us_per_task": round(hier_cpt * 1e6, 3),
           "range_handouts": hier["range_handouts"],
           "resubmitted": hier["resubmitted"]})
    cap_ratio = base_cpt / hier_cpt
    cpu_ratio = hier_cpt / base_cpt
    slow = cap_ratio < _SCALE_TPS_FLOOR
    hot = cpu_ratio > _SCALE_CPU_CEIL
    _emit({"metric": "scale_hier_vs_direct",
           "value": round(cap_ratio, 3), "unit": "x master capacity",
           "wall_tps_ratio": round(hier_tps / base_tps, 3),
           "master_cpu_per_task_ratio": round(cpu_ratio, 3),
           "capacity_floor": _SCALE_TPS_FLOOR,
           "cpu_ceil": _SCALE_CPU_CEIL,
           "under_floor": bool(slow or hot)})
    if slow:
        print(f"FAIL: hierarchical master capacity {round(cap_ratio, 3)}x "
              f"below floor {_SCALE_TPS_FLOOR}x", file=sys.stderr)
    if hot:
        print(f"FAIL: hierarchical master CPU/task {round(cpu_ratio, 3)}x "
              f"above ceiling {_SCALE_CPU_CEIL}x", file=sys.stderr)
    return 1 if (slow or hot) else 0


_STREAM_ARM = r"""
import json
import os
import resource
import sys
import time

repo = sys.argv[1]
params = json.loads(sys.argv[2])
sys.path.insert(0, repo)
os.environ["JAX_PLATFORMS"] = "cpu"

import fiber_tpu


def tiny(x):
    return x


def gen(n):
    for i in range(n):
        yield i


fiber_tpu.init(stream_window=params["window"])
pool = fiber_tpu.Pool(params["processes"])
try:
    # Warm the worker population outside the timed window (ru_maxrss is
    # a lifetime peak, so warm-up stays tiny).
    pool.map(tiny, range(256), chunksize=params["chunksize"])
    t0 = time.perf_counter()
    if params["mode"] == "stream":
        n = 0
        for _ in pool.imap_unordered(tiny, gen(params["tasks"]),
                                     chunksize=params["chunksize"]):
            n += 1
    else:
        n = len(pool.map(tiny, range(params["tasks"]),
                         chunksize=params["chunksize"]))
    wall = time.perf_counter() - t0
    assert n == params["tasks"], (n, params["tasks"])
    st = pool.stats()
    assert st["tasks_completed"] >= params["tasks"], st["tasks_completed"]
    print(json.dumps({
        "wall_s": wall,
        "tasks": params["tasks"],
        "rss_kb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss,
        "admit_waits": st["stream_admit_waits"],
    }), flush=True)
finally:
    pool.close()
    pool.join()
"""


def _stream_arm(params: dict, timeout: float = 1800.0) -> dict:
    """Run one --stream arm in a fresh interpreter: ru_maxrss is a
    LIFETIME peak, so the O(window)-vs-O(n) master-RSS comparison is
    only honest when every arm starts from a cold process."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, "-c", _STREAM_ARM, repo, json.dumps(params)],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(
            f"stream arm {params['mode']}/{params['tasks']} failed:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


#: `make bench-stream` gates (docs/streaming.md): the >= 1M-task
#: streamed run's master peak RSS may grow at most this factor over a
#: 100x-smaller streamed run (constant-memory claim: retention is
#: O(stream_window), not O(n))...
_STREAM_RSS_CEIL = 1.5
#: ...and streaming may cost at most this much of the materialized
#: map's throughput on the same workload (the window must not starve
#: the cluster).
_STREAM_TPS_FLOOR = 0.9


def _stream_bench(args) -> int:
    """Streaming data plane macrobench (docs/streaming.md): push
    ``--stream-tasks`` (>= 1M by default) tiny tasks through a windowed
    ``imap_unordered`` over a GENERATOR — nothing materialized anywhere
    — and gate on (a) completion, (b) master peak RSS vs a 100x-smaller
    streamed run (the constant-memory claim), (c) wall tasks/s vs a
    materialized ``map`` of the same workload (the window must keep the
    cluster fed). Emits JSON lines; ``make bench-stream`` tees them
    into BENCH_stream.json and fails when a gate is missed."""
    chunk = int(args.stream_chunk)
    common = {"chunksize": chunk, "processes": int(args.stream_workers),
              "window": int(args.stream_window)}
    base = _stream_arm({**common, "mode": "stream",
                        "tasks": int(args.stream_base_tasks)})
    # Throughput arms run best-of-2: single-run wall time on a shared
    # box swings more than the 10% gate margin, and best-of is the
    # standard way to measure the code rather than the neighbours. The
    # RSS gate takes the max instead — a leak must not hide behind a
    # lucky run.
    big_runs = [_stream_arm({**common, "mode": "stream",
                             "tasks": int(args.stream_tasks)})
                for _ in range(2)]
    mat_runs = [_stream_arm({**common, "mode": "map",
                             "tasks": int(args.stream_tasks)})
                for _ in range(2)]
    big = min(big_runs, key=lambda r: r["wall_s"])
    mat = min(mat_runs, key=lambda r: r["wall_s"])
    big_rss_kb = max(r["rss_kb"] for r in big_runs)
    big_tps = big["tasks"] / big["wall_s"]
    mat_tps = mat["tasks"] / mat["wall_s"]
    _emit({"metric": "stream_base_rss_mb",
           "value": round(base["rss_kb"] / 1024.0, 1), "unit": "MB",
           "tasks": base["tasks"], "chunksize": chunk,
           "window": common["window"],
           "wall_s": round(base["wall_s"], 3),
           "admit_waits": base["admit_waits"]})
    _emit({"metric": "stream_tasks_per_sec",
           "value": round(big_tps, 1), "unit": "tasks/s",
           "tasks": big["tasks"], "chunksize": chunk,
           "window": common["window"], "workers": common["processes"],
           "wall_s": round(big["wall_s"], 3),
           "rss_mb": round(big_rss_kb / 1024.0, 1),
           "admit_waits": big["admit_waits"]})
    _emit({"metric": "materialized_tasks_per_sec",
           "value": round(mat_tps, 1), "unit": "tasks/s",
           "tasks": mat["tasks"], "chunksize": chunk,
           "wall_s": round(mat["wall_s"], 3),
           "rss_mb": round(mat["rss_kb"] / 1024.0, 1)})
    rss_ratio = big_rss_kb / max(1, base["rss_kb"])
    tps_ratio = big_tps / max(1e-9, mat_tps)
    short = big["tasks"] < 1_000_000
    fat = rss_ratio > _STREAM_RSS_CEIL
    slow = tps_ratio < _STREAM_TPS_FLOOR
    _emit({"metric": "stream_gates",
           "value": round(rss_ratio, 3), "unit": "x RSS",
           "tasks": big["tasks"],
           "rss_ratio": round(rss_ratio, 3),
           "tps_ratio": round(tps_ratio, 3),
           "rss_ceil": _STREAM_RSS_CEIL,
           "tps_floor": _STREAM_TPS_FLOOR,
           "under_floor": bool(short or fat or slow)})
    if short:
        print(f"FAIL: stream arm ran {big['tasks']} tasks; the headline "
              f"claim needs >= 1,000,000", file=sys.stderr)
    if fat:
        print(f"FAIL: master RSS grew {round(rss_ratio, 3)}x across a "
              f"100x task-count increase (ceiling {_STREAM_RSS_CEIL}x — "
              f"retention is supposed to be O(window))", file=sys.stderr)
    if slow:
        print(f"FAIL: streaming throughput {round(tps_ratio, 3)}x of the "
              f"materialized map (floor {_STREAM_TPS_FLOOR}x)",
              file=sys.stderr)
    return 1 if (short or fat or slow) else 0


#: `make bench-serve` gates (docs/serving.md): equal tenants pushing
#: equal work through ONE daemon must see near-equal mean job latency
#: (WDRR fairness), and a job landing on standby warm workers must
#: start-to-finish in at most half the cold Pool-spawn wall.
_SERVE_FAIRNESS_MAX = 1.6
_SERVE_WARM_RATIO_MAX = 0.5


def _serve_daemon_env(staging: str, repo: str) -> dict:
    env = dict(os.environ)
    env.update(
        FIBER_BACKEND="local",
        JAX_PLATFORMS="cpu",
        FIBER_AGENT_STAGING=staging,
        PYTHONPATH=repo,
        FIBER_SERVE_PROCESSES="4",
        FIBER_SERVE_WARM_FLOOR="2",
        FIBER_SERVE_WARM_CEILING="4",
        FIBER_SERVE_WARM_IDLE_S="1.0",
        FIBER_SERVE_TICK_S="0.1",
        FIBER_SERVE_PREEMPT_GRACE_S="0.5",
    )
    return env


def _serve_spawn(portfile: str, env: dict, repo: str):
    """Spawn one serving daemon on an ephemeral port; return
    (proc, port) once the --port-file lands."""
    import subprocess

    # log to a FILE, not a pipe: a full 64K pipe buffer would wedge a
    # chatty daemon mid-bench
    with open(portfile + ".log", "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "fiber_tpu.serve.daemon",
             "--port", "0", "--port-file", portfile],
            env=env, cwd=repo, stdout=log, stderr=subprocess.STDOUT)
    deadline = time.time() + 180
    while time.time() < deadline:
        if proc.poll() is not None:
            with open(portfile + ".log") as fh:
                raise RuntimeError(
                    "serve daemon died on startup:\n" + fh.read())
        if os.path.exists(portfile):
            with open(portfile) as fh:
                return proc, int(fh.read())
        time.sleep(0.05)
    proc.kill()
    raise RuntimeError("serve daemon never published its port")


def _serve_ledger_chunks(path) -> int:
    from fiber_tpu.store import ledger as ledgermod

    try:
        _, completed, _ = ledgermod.load(path)
        return len(completed)
    except Exception:  # noqa: BLE001 - not written yet
        return 0


def _serve_cost_total(job_id: str, costs_dir: str, want: int,
                      deadline_s: float = 60.0):
    """Retry-poll one job's cost record until tasks + tasks_restored
    reconciles to ``want`` (records are eventually consistent: late
    worker frames rewrite them). Returns the record or None."""
    from fiber_tpu.telemetry import accounting

    deadline = time.time() + deadline_s
    while time.time() < deadline:
        rec = accounting.read_job_record(job_id, directory=costs_dir)
        if rec:
            total = rec.get("total", {})
            billed = (int(total.get("tasks", 0))
                      + int(total.get("tasks_restored", 0)))
            if billed == want:
                return rec
        time.sleep(0.1)
    return None


def _serve_bench(args) -> int:
    """Serving-daemon macrobench (docs/serving.md, `make bench-serve`):
    one daemon, N tenants x M concurrent jobs over the authenticated
    channel, an over-budget tenant that must be throttled then
    PREEMPTED (parked resumable, chunks reclaimed), a client SIGKILLed
    mid-job whose results a fresh client still collects, a daemon
    SIGKILLed mid-jobs whose restart replays everything exactly-once,
    and a warm-vs-cold first-job latency arm. Gates: WDRR fairness
    ratio, warm latency ratio, zero lost tasks, disjoint per-tenant
    cost records reconciling to totals."""
    import shutil
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="fiber-bench-serve-")
    staging = os.path.join(tmp, "staging")
    cold_staging = os.path.join(tmp, "cold-staging")
    os.makedirs(staging)
    os.makedirs(cold_staging)
    # The bench's own cold-Pool arm stays in a private staging dir so
    # it cannot collide with the daemon's ledgers/costs.
    os.environ["FIBER_BACKEND"] = "local"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["FIBER_AGENT_STAGING"] = cold_staging
    import fiber_tpu
    from fiber_tpu.serve.client import ServeClient
    from fiber_tpu.store import ledger as ledgermod
    from tests import targets

    ledger_dir = os.path.join(staging, "ledger")
    costs_dir = os.path.join(staging, "costs")
    env = _serve_daemon_env(staging, repo)
    tenants = [f"tenant{i}" for i in range(max(2, int(args.serve_tenants)))]
    jobs_per = max(2, int(args.serve_jobs))
    n = int(args.serve_tasks)
    failures: list = []
    procs: list = []
    try:
        proc, port = _serve_spawn(os.path.join(tmp, "port1"), env, repo)
        procs.append(proc)
        client = ServeClient(("127.0.0.1", port))

        # -- phase A: fairness + budget preemption ----------------------
        # The hog submits FIRST (2n tasks, a 5-task budget): WDRR must
        # keep it from starving anyone while it lives, and admission
        # must preempt it after the grace window.
        greedy_job = client.submit(
            targets.sleep_echo, list(range(2 * n)), tenant="greedy",
            job_id="greedy-hog", chunksize=1, budget={"tasks": 5})
        fair = {t: [client.submit(targets.sleep_echo, list(range(n)),
                                  tenant=t, chunksize=1)
                    for _ in range(jobs_per)]
                for t in tenants}
        lost = 0
        views = {}
        for t in tenants:
            for j in fair[t]:
                view = client.wait(j, timeout=600)
                views[j] = view
                if view.get("state") != "done":
                    failures.append(f"fair job {j} ended "
                                    f"{view.get('state')}: "
                                    f"{view.get('error')}")
                    lost += n
                    continue
                res = client.results(j)
                ok = sum(1 for a, b in zip(res, range(n)) if a == b)
                lost += n - ok
        gview = client.wait(greedy_job, timeout=600)
        preempted_ok = gview.get("state") == "preempted"
        if not preempted_ok:
            failures.append(f"over-budget job ended "
                            f"{gview.get('state')!r}, wanted preempted")
        gpath = ledgermod.job_path(greedy_job, ledger_dir)
        journaled = _serve_ledger_chunks(gpath)
        if not (0 < journaled < 2 * n):
            failures.append(f"preempted job journaled {journaled} "
                            f"chunks; want 0 < j < {2 * n} (parked "
                            "resumable, chunks reclaimed)")
        status_a = client.status()
        preempted_maps = int(
            status_a["admission"].get("preempted_maps", 0))
        if preempted_maps < 1:
            failures.append("admission reported no preempted maps")
        scaleup_ok = int(status_a["warm_pool"].get("scale_ups", 0)) >= 1
        if not scaleup_ok:
            failures.append("warm pool never scaled above the floor "
                            "under full load")
        means = {}
        for t in tenants:
            lat = [views[j]["finished_at"] - views[j]["submitted_at"]
                   for j in fair[t] if views[j].get("finished_at")]
            means[t] = sum(lat) / len(lat) if lat else float("inf")
        fairness_ratio = (max(means.values()) / max(1e-9,
                                                    min(means.values())))
        _emit({"metric": "serve_fairness_ratio",
               "value": round(fairness_ratio, 3), "unit": "x",
               "tenants": len(tenants), "jobs_per_tenant": jobs_per,
               "tasks_per_job": n,
               "mean_latency_s": {t: round(v, 3)
                                  for t, v in means.items()}})
        # Per-tenant cost records: DISJOINT (each job billed to its own
        # tenant) and reconciling to the grand total.
        billed = 0
        for t in tenants:
            for j in fair[t]:
                rec = _serve_cost_total(j, costs_dir, n)
                if rec is None:
                    failures.append(f"cost record for {j} never "
                                    f"reconciled to {n} tasks")
                    continue
                if rec.get("tenant") != t:
                    failures.append(f"job {j} billed to "
                                    f"{rec.get('tenant')!r}, not {t!r}")
                billed += int(rec["total"].get("tasks", 0))
                billed += int(rec["total"].get("tasks_restored", 0))
        want_billed = len(tenants) * jobs_per * n
        if billed != want_billed:
            failures.append(f"cost records total {billed} tasks across "
                            f"tenants; submitted {want_billed}")

        # -- phase B: client SIGKILLed mid-job --------------------------
        victim_job = "victim-killed-client"
        code = (
            "import sys\n"
            "from fiber_tpu.serve.client import ServeClient\n"
            "from tests import targets\n"
            "port, job, n = (int(sys.argv[1]), sys.argv[2],\n"
            "                int(sys.argv[3]))\n"
            "c = ServeClient(('127.0.0.1', port))\n"
            "c.submit(targets.sleep_echo, list(range(n)),\n"
            "         tenant='victim', job_id=job, chunksize=2)\n"
            "c.wait(job)\n"
        )
        vic = subprocess.Popen(
            [sys.executable, "-c", code, str(port), victim_job, str(n)],
            env=env, cwd=repo)
        vpath = ledgermod.job_path(victim_job, ledger_dir)
        deadline = time.time() + 120
        while (time.time() < deadline
               and _serve_ledger_chunks(vpath) < 2):
            time.sleep(0.05)
        vic.kill()
        vic.wait(timeout=60)
        # the job outlives its submitter: a DIFFERENT client collects
        vview = client.wait(victim_job, timeout=600)
        vres = (client.results(victim_job)
                if vview.get("state") == "done" else [])
        client_survive_ok = vres == list(range(n))
        if not client_survive_ok:
            failures.append(
                f"killed-client job ended {vview.get('state')!r} with "
                f"{len(vres)}/{n} results — submissions must outlive "
                "their submitter")

        # -- phase C: daemon SIGKILLed mid-jobs, restart replays --------
        crash_jobs = {}
        for t in ("carol", "dave"):
            crash_jobs[t] = client.submit(
                targets.sleep_echo, list(range(n)), tenant=t,
                job_id=f"{t}-crash", chunksize=2)
        deadline = time.time() + 120
        while time.time() < deadline and not all(
                _serve_ledger_chunks(
                    ledgermod.job_path(j, ledger_dir)) >= 2
                for j in crash_jobs.values()):
            time.sleep(0.05)
        proc.kill()
        proc.wait(timeout=60)
        client.close()
        time.sleep(1.0)  # let orphaned workers drain
        proc2, port2 = _serve_spawn(os.path.join(tmp, "port2"), env,
                                    repo)
        procs.append(proc2)
        client2 = ServeClient(("127.0.0.1", port2))
        replay_ok = True
        for t, j in crash_jobs.items():
            view = client2.wait(j, timeout=600)
            if not (view.get("state") == "done" and view.get("replayed")
                    and client2.results(j) == list(range(n))):
                replay_ok = False
                failures.append(
                    f"job {j} after daemon kill+restart: state="
                    f"{view.get('state')!r} "
                    f"replayed={view.get('replayed')!r}")
                continue
            rec = _serve_cost_total(j, costs_dir, n)
            if rec is None:
                replay_ok = False
                failures.append(f"replayed job {j} never reconciled "
                                f"to exactly {n} billed tasks")
            elif int(rec["total"].get("tasks_restored", 0)) < 1:
                replay_ok = False
                failures.append(f"replayed job {j} restored 0 chunks "
                                "from its ledger")

        # -- phase D: warm-vs-cold first-job latency --------------------
        # Wait out the idle window: the pool must shrink BACK to the
        # warm floor (elastic down as well as up) before the timed arm.
        scaledown_ok = False
        deadline = time.time() + 60
        while time.time() < deadline:
            warm = client2.status()["warm_pool"]
            if int(warm["workers"]) == int(warm["floor"]):
                scaledown_ok = True
                break
            time.sleep(0.1)
        if not scaledown_ok:
            failures.append("warm pool never scaled back down to the "
                            f"floor when idle ({warm})")
        t0 = time.perf_counter()
        wjob = client2.submit(targets.square, [7], tenant="newcomer")
        wview = client2.wait(wjob, timeout=120, interval=0.01)
        warm_s = time.perf_counter() - t0
        if not (wview.get("state") == "done"
                and client2.results(wjob) == [49]):
            failures.append(f"warm-arm job ended {wview.get('state')!r}")
        fiber_tpu.init()
        t0 = time.perf_counter()
        with fiber_tpu.Pool(2) as pool:
            cold_res = pool.map(targets.square, [7])
        cold_s = time.perf_counter() - t0
        if cold_res != [49]:
            failures.append(f"cold-arm map returned {cold_res!r}")
        warm_ratio = warm_s / max(1e-9, cold_s)
        _emit({"metric": "serve_warm_latency",
               "value": round(warm_ratio, 3), "unit": "x cold spawn",
               "warm_s": round(warm_s, 3), "cold_s": round(cold_s, 3)})
        if warm_ratio > _SERVE_WARM_RATIO_MAX:
            failures.append(
                f"warm first-job latency {round(warm_ratio, 3)}x the "
                f"cold Pool spawn (max {_SERVE_WARM_RATIO_MAX}x) — the "
                "standby workers bought nothing")

        # -- phase E: clean shutdown over the wire ----------------------
        client2.shutdown()
        client2.close()
        try:
            rc = proc2.wait(timeout=120)
        except subprocess.TimeoutExpired:
            rc = None
        if rc != 0:
            failures.append(f"daemon exit code {rc!r} after the "
                            "shutdown verb; want 0")

        if fairness_ratio > _SERVE_FAIRNESS_MAX:
            failures.append(
                f"tenant fairness ratio {round(fairness_ratio, 3)}x "
                f"(max {_SERVE_FAIRNESS_MAX}x) — WDRR is not holding")
        if lost:
            failures.append(f"{lost} task result(s) lost or wrong "
                            "across the fair tenants")
        _emit({"metric": "serve_gates",
               "value": round(fairness_ratio, 3), "unit": "x",
               "fairness_ratio": round(fairness_ratio, 3),
               "warm_latency_ratio": round(warm_ratio, 3),
               "lost_tasks": lost,
               "billed_tasks": billed,
               "preempted_maps": preempted_maps,
               "preempted_ok": preempted_ok,
               "client_survive_ok": client_survive_ok,
               "replay_ok": replay_ok,
               "scaleup_ok": scaleup_ok,
               "scaledown_ok": scaledown_ok,
               "fairness_max": _SERVE_FAIRNESS_MAX,
               "warm_ratio_max": _SERVE_WARM_RATIO_MAX,
               "under_floor": bool(failures)})
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1 if failures else 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=30)
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
        shutil.rmtree(tmp, ignore_errors=True)


#: `make bench-slo` gates (docs/observability.md "SLOs and the
#: archive"): the archive + SLO plane rides sampler ticks and job
#: completions that already happen, so arming it over the plain
#: daemon must be ~free; injected chaos must page within a bounded
#: wall; queries must never return a torn record.
_SLO_OVERHEAD_MAX = 1.05
_SLO_DETECT_MAX_S = 30.0


def _slo_workload(port: int, tenants, jobs_per: int, n: int):
    """The timed unit both overhead arms share: ``jobs_per`` sleep_echo
    jobs per tenant through one daemon, all awaited. Returns
    (wall_s, lost_jobs)."""
    from fiber_tpu.serve.client import ServeClient
    from tests import targets

    client = ServeClient(("127.0.0.1", port))
    try:
        t0 = time.perf_counter()
        jobs = [client.submit(targets.sleep_echo, list(range(n)),
                              tenant=t, chunksize=2)
                for t in tenants for _ in range(jobs_per)]
        lost = 0
        for j in jobs:
            view = client.wait(j, timeout=600)
            if (view.get("state") != "done"
                    or client.results(j) != list(range(n))):
                lost += 1
        return time.perf_counter() - t0, lost
    finally:
        client.close()


def _slo_shutdown(port: int, proc) -> None:
    from fiber_tpu.serve.client import ServeClient

    try:
        with ServeClient(("127.0.0.1", port)) as c:
            c.shutdown()
        proc.wait(timeout=120)
    except Exception:  # noqa: BLE001 - teardown best-effort
        proc.kill()


def _slo_env(staging: str, repo: str, archive: str) -> dict:
    """Daemon env with the SLO plane armed: a deliberately miss-able
    latency target, tight windows so the bench pages in seconds not
    hours, and a fast monitor tick so events archive promptly."""
    env = _serve_daemon_env(staging, repo)
    env.update(
        FIBER_ARCHIVE_DIR=archive,
        FIBER_ARCHIVE_FSYNC_S="0.05",
        FIBER_SERVE_SLO_LATENCY_S="0.2",
        FIBER_SERVE_SLO_P="0.95",
        FIBER_SERVE_SLO_ERROR_PCT="0.01",
        FIBER_SERVE_SLO_WINDOW_S="120",
        FIBER_SERVE_SLO_FAST_WINDOW_S="30",
        FIBER_SERVE_SLO_BURN="2.0",
        FIBER_MONITOR_INTERVAL_S="0.25",
        FIBER_POLICY_VERIFY_S="0.5",
    )
    return env


def _slo_bench(args) -> int:
    """SLO plane + archive macrobench (`make bench-slo`,
    docs/observability.md "SLOs and the archive"). Three arms:

    1. **overhead**: the identical multi-tenant workload through a
       plain daemon (whole telemetry plane off — no archive, no SLO)
       vs one with the archive + SLO plane armed (generous target, not
       burning). Gate: armed <= 1.05x plain, best-of-2 each.
    2. **chaos -> burn -> chain**: slow-worker chaos degrades every
       worker; jobs miss the 0.2 s latency target; `slo_burn` must
       breach and the archive itself must hold the complete
       cause_id-linked anomaly -> boost_and_throttle -> outcome chain.
       Gate: breach within _SLO_DETECT_MAX_S, chain complete.
    3. **SIGKILL + restart**: the burning daemon is SIGKILL'd; a
       successor (chaos off) replays the archive tail. Gates: still
       breached after restart, pre-kill history a prefix of post-kill
       history, zero malformed records returned.
    """
    import shutil
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="fiber-bench-slo-")
    os.environ["FIBER_BACKEND"] = "local"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from fiber_tpu.serve.client import ServeClient
    from tests import targets

    # Long enough that the sleep-dominated wall (~5 s) dwarfs the
    # ~0.1 s chunk-alignment jitter; the 1.05x gate is meaningless at
    # sub-second walls.
    tenants = ["alpha", "beta"]
    jobs_per, n = 2, 96
    failures: list = []
    procs: list = []
    try:
        # -- arm 1: overhead, plain vs armed ----------------------------
        # Both daemons up at once, runs interleaved plain/armed/...,
        # best-of-3 each: machine-load drift hits both arms alike
        # instead of whichever happened to run second.
        ports = {}
        for name in ("plain", "armed"):
            staging = os.path.join(tmp, f"{name}-staging")
            os.makedirs(staging)
            if name == "armed":
                env = _slo_env(staging, repo,
                               os.path.join(staging, "archive"))
                # generous target: armed and observing, NOT burning —
                # the overhead arm times the plane, not the remediation
                env["FIBER_SERVE_SLO_LATENCY_S"] = "30.0"
                # production cadence: the aggressive tick/fsync knobs
                # in _slo_env buy the chaos arm fast paging, they are
                # not the steady-state cost the 1.05x gate is about
                env.pop("FIBER_MONITOR_INTERVAL_S")
                env.pop("FIBER_ARCHIVE_FSYNC_S")
            else:
                env = _serve_daemon_env(staging, repo)
                env["FIBER_TELEMETRY_ENABLED"] = "0"
            proc, port = _serve_spawn(
                os.path.join(tmp, f"port-{name}"), env, repo)
            procs.append(proc)
            ports[name] = (proc, port)
        walls = {"plain": None, "armed": None}
        lost = 0
        for _ in range(3):
            for name in ("plain", "armed"):
                wall, l = _slo_workload(ports[name][1], tenants,
                                        jobs_per, n)
                lost += l
                walls[name] = (wall if walls[name] is None
                               else min(walls[name], wall))
        if lost:
            failures.append(f"overhead arms lost {lost} job(s)")
        # the plane really was on in the armed daemon: obs + archive
        with ServeClient(("127.0.0.1", ports["armed"][1])) as c:
            snap = c.slo()
            want = 3 * len(tenants) * jobs_per
            if snap["observations"] < want:
                failures.append(
                    f"armed daemon observed {snap['observations']} "
                    f"jobs, want >= {want}")
            arch = c.status()["archive"]
            if not (arch["enabled"] and arch["records_written"] > 0):
                failures.append(
                    f"armed daemon's archive not live: {arch}")
        for name in ("plain", "armed"):
            _slo_shutdown(ports[name][1], ports[name][0])
        overhead = walls["armed"] / max(1e-9, walls["plain"])
        _emit({"metric": "slo_overhead",
               "value": round(overhead, 3), "unit": "x plain serve",
               "plain_wall_s": round(walls["plain"], 3),
               "armed_wall_s": round(walls["armed"], 3),
               "tenants": len(tenants), "jobs_per_tenant": jobs_per,
               "tasks_per_job": n})
        if overhead > _SLO_OVERHEAD_MAX:
            failures.append(
                f"archive+SLO overhead {round(overhead, 3)}x the plain "
                f"daemon (max {_SLO_OVERHEAD_MAX}x)")

        # -- arm 2: slow-worker chaos must page -------------------------
        from fiber_tpu.testing import chaos as chaosmod

        staging = os.path.join(tmp, "chaos-staging")
        archive_dir = os.path.join(staging, "archive")
        os.makedirs(staging)
        env = _slo_env(staging, repo, archive_dir)
        plan = chaosmod.ChaosPlan(
            seed=11, token_dir=os.path.join(tmp, "chaos-tokens"),
            slow_worker_after_chunks=1, slow_worker_s=0.5,
            slow_worker_times=16)
        env[chaosmod.ENV_VAR] = plan.to_env()
        proc, port = _serve_spawn(os.path.join(tmp, "port-chaos"), env,
                                  repo)
        procs.append(proc)
        client = ServeClient(("127.0.0.1", port))
        t_chaos = time.perf_counter()
        hot = [client.submit(targets.sleep_echo, list(range(8)),
                             tenant="hot", chunksize=2)
               for _ in range(4)]
        for j in hot:
            view = client.wait(j, timeout=600)
            if view.get("state") != "done":
                failures.append(f"chaos job {j} ended "
                                f"{view.get('state')!r}")
        burn_detect_s = None
        deadline = time.time() + _SLO_DETECT_MAX_S + 30
        while time.time() < deadline:
            if client.slo()["breached"]:
                burn_detect_s = time.perf_counter() - t_chaos
                break
            time.sleep(0.1)
        if burn_detect_s is None:
            burn_detect_s = float("inf")
            failures.append(
                "slow-worker chaos never breached slo_burn (every job "
                "missed a 0.2s latency target under 0.5s/chunk "
                "stragglers)")
        # The chain must be readable out of the ARCHIVE, not just the
        # live flight ring: anomaly -> cause_id-linked action -> outcome.
        anomaly = action = outcome = None
        deadline = time.time() + 60
        while time.time() < deadline and outcome is None:
            events = client.query("event", labels={"plane": "monitor"})
            anomaly = next((e for e in events
                            if e.get("event") == "slo_burn"), None)
            if anomaly is not None:
                pol = client.query(
                    "event", labels={"plane": "policy",
                                     "cause_id": anomaly.get("id")})
                action = next(
                    (e for e in pol
                     if e.get("event") == "boost_and_throttle"), None)
                outcome = next((e for e in pol
                                if e.get("event") == "outcome"), None)
            if outcome is None:
                time.sleep(0.25)
        chain_ok = (anomaly is not None and action is not None
                    and outcome is not None)
        if not chain_ok:
            failures.append(
                "archived slo_burn chain incomplete: anomaly="
                f"{bool(anomaly)} action={bool(action)} "
                f"outcome={bool(outcome)}")
        elif anomaly.get("tenant") != "hot":
            failures.append(f"slo_burn blamed tenant "
                            f"{anomaly.get('tenant')!r}, not the one "
                            "actually burning")
        _emit({"metric": "slo_burn_detect",
               "value": (round(burn_detect_s, 3)
                         if burn_detect_s != float("inf") else None),
               "unit": "s", "chain_ok": chain_ok,
               "detect_max_s": _SLO_DETECT_MAX_S})
        if burn_detect_s > _SLO_DETECT_MAX_S:
            failures.append(
                f"slo_burn took {round(burn_detect_s, 1)}s to page "
                f"(max {_SLO_DETECT_MAX_S}s)")

        # -- arm 3: SIGKILL + restart durability ------------------------
        pre_hist = client.query("slo_obs", labels={"tenant": "hot"})
        pre_ids = [r.get("job_id") for r in pre_hist]
        proc.kill()
        proc.wait(timeout=60)
        client.close()
        time.sleep(1.0)  # orphaned workers drain
        env_r = dict(env)
        env_r.pop(chaosmod.ENV_VAR)  # the successor is healthy
        proc2, port2 = _serve_spawn(os.path.join(tmp, "port-restart"),
                                    env_r, repo)
        procs.append(proc2)
        client2 = ServeClient(("127.0.0.1", port2))
        restart_burn_ok = False
        deadline = time.time() + 30
        while time.time() < deadline:
            if client2.slo()["breached"]:
                restart_burn_ok = True
                break
            time.sleep(0.1)
        if not restart_burn_ok:
            failures.append(
                "burn-window state lost across SIGKILL+restart: the "
                "successor never re-raised slo_burn from the replayed "
                "archive tail")
        post_hist = client2.query("slo_obs", labels={"tenant": "hot"})
        post_ids = [r.get("job_id") for r in post_hist]
        history_consistent = post_ids[:len(pre_ids)] == pre_ids
        if not history_consistent:
            failures.append(
                f"history diverged across restart: pre {pre_ids} vs "
                f"post {post_ids[:len(pre_ids)]}")
        torn_reads = sum(
            1 for r in (pre_hist + post_hist
                        + client2.query("event") + client2.query("cost"))
            if not isinstance(r, dict) or "ts" not in r
            or "kind" not in r)
        if torn_reads:
            failures.append(f"{torn_reads} malformed record(s) came "
                            "back out of archive queries — torn lines "
                            "must be skipped, never returned")
        snap = client2.slo(tenant="hot")
        if snap["tenants"].get("hot", {}).get("latency", {}).get("n", 0) \
                < len(hot):
            failures.append("replayed tenant histograms missing "
                            f"observations: {snap['tenants']}")
        _slo_shutdown(port2, proc2)

        _emit({"metric": "slo_gates",
               "value": round(overhead, 3), "unit": "x",
               "overhead": round(overhead, 3),
               "burn_detect_s": (round(burn_detect_s, 3)
                                 if burn_detect_s != float("inf")
                                 else None),
               "torn_reads": torn_reads,
               "chain_ok": chain_ok,
               "restart_burn_ok": restart_burn_ok,
               "history_consistent": history_consistent,
               "overhead_max": _SLO_OVERHEAD_MAX,
               "detect_max_s": _SLO_DETECT_MAX_S,
               "under_floor": bool(failures)})
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1 if failures else 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=30)
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
        shutil.rmtree(tmp, ignore_errors=True)


#: `make bench-ici` gates (docs/objectstore.md "Device tier"): repeat
#: resolutions of an already-device-resident param may cost at most
#: this many wire bytes (control frames only — the payload must come
#: out of the device tier), and the device-tier broadcast path must
#: beat the tier-off baseline (param stacked per item into the batched
#: transfer) by this wall factor.
_ICI_REPEAT_WIRE_MAX = 4096
_ICI_WALL_RATIO_FLOOR = 1.3


def _ici_eval(params, x):
    """Per-item device eval against a broadcast param vector: one full
    reduction over params mixed with the item scalar. ``params`` rides
    vmap's in_axes=None; with the device tier ON it is mesh-resident
    across generations, OFF it re-pays the host->mesh transfer every
    call."""
    import jax.numpy as jnp

    return jnp.sum(params * params) * jnp.float32(1e-6) + x


def _ici_bench(args) -> int:
    """Device-tier data plane bench (`make bench-ici`,
    docs/objectstore.md "Device tier"). CPU-runnable: the mesh is the
    xla_force_host_platform device set; the Pallas remote-DMA kernels
    are numerics-gated by tests, not timed here. Two arms:

    1. **repeat-resolution wire bytes**: an ``--ici-mb`` param resolved
       ``--ici-gens`` times through the store plane with
       ``device=True``, host caches dropped between generations. Gen 1
       pays one wire fetch plus one mesh replication (billed under the
       ``ici`` transfer site); every repeat generation must come out of
       the device tier with ~zero further wire bytes. The PR-2
       host-cache baseline re-fetches the payload here — its host copy
       is gone, and it has no device-resident tier to fall back on.
    2. **broadcast wall ratio**: ``--ici-gens`` generations of a
       device-path Pool.starmap over a shared ``--ici-mb`` param with
       the device tier ON (collective broadcast: one replication, then
       digest-dedup'd reuse across generations) vs OFF (every map
       re-pays the host->mesh transfer) — gated >= 1.3x, best-of-3
       interleaved."""
    import numpy as np

    import fiber_tpu
    from fiber_tpu import serialization
    from fiber_tpu import store as storemod
    from fiber_tpu.meta import meta
    from fiber_tpu.store import LocalStore
    from fiber_tpu.store.plane import StoreClient, StoreServer
    from fiber_tpu.telemetry.device import DEVICE

    payload_mb = float(args.ici_mb)
    gens = max(2, int(args.ici_gens))
    tasks = int(args.ici_tasks)

    fiber_tpu.init(store_enabled=True)
    storemod.reset()
    tier = storemod.device_store_tier()
    if tier is None:
        print("FAIL: device store tier is disabled "
              "(store_device_enabled=False?)", file=sys.stderr)
        return 1
    arr = np.random.default_rng(7).standard_normal(
        int(payload_mb * (1 << 20) / 4)).astype(np.float32)

    def ici_site_bytes() -> int:
        site = DEVICE.snapshot()["transfers"].get("ici") or {}
        return int(site.get("bytes", 0))

    # -- arm 1: repeat-generation resolution --------------------------
    blob = serialization.dumps(arr)
    st = LocalStore(capacity_bytes=512 << 20)
    server = StoreServer(st, "127.0.0.1")
    ref = st.put_bytes(blob)
    wire_ref = type(ref)(ref.digest, ref.size, server.addr, True)
    ici_before = ici_site_bytes()
    client = StoreClient(LocalStore(capacity_bytes=512 << 20))
    first = client.resolve(wire_ref, device=True)
    client.close()
    served_first = server.stats()["bytes_served"]
    for _ in range(gens - 1):
        # A FRESH client per generation: no host RAM/disk copy
        # survives, so a free repeat resolution can only mean a device
        # tier hit.
        c = StoreClient(LocalStore(capacity_bytes=512 << 20))
        again = c.resolve(wire_ref, device=True)
        c.close()
        assert again is not None
    served_total = server.stats()["bytes_served"]
    server.close()
    repeat_wire = served_total - served_first
    tstats = tier.stats()
    ici_bytes = ici_site_bytes() - ici_before
    # Sanity on the resolved payload, not just the byte counters.
    assert first is not None
    leaves_ok = int(np.asarray(first).shape[0]) == arr.shape[0]
    _emit({"metric": "ici_repeat_wire_bytes", "value": int(repeat_wire),
           "unit": "bytes", "budget": _ICI_REPEAT_WIRE_MAX,
           "generations": gens, "payload_mb": payload_mb,
           "first_gen_wire_bytes": int(served_first),
           "device_tier_hits": int(tstats.get("hits", 0)),
           "ici_transfer_bytes": int(ici_bytes),
           "payload_shape_ok": bool(leaves_ok)})

    # -- arm 2: broadcast wall ratio, tier on vs off -------------------
    ev = meta(device=True)(_ici_eval)
    items = [(arr, np.float32(i)) for i in range(tasks)]
    walls = {"on": None, "off": None}
    for _ in range(3):
        for mode in ("on", "off"):
            fiber_tpu.init(store_device_enabled=(mode == "on"))
            with fiber_tpu.Pool(2) as pool:
                out = pool.starmap(ev, items)  # compile + gen-1 put
                assert len(out) == tasks
                t0 = time.perf_counter()
                for _ in range(gens):
                    out = pool.starmap(ev, items)
                wall = time.perf_counter() - t0
            assert len(out) == tasks
            walls[mode] = wall if walls[mode] is None \
                else min(walls[mode], wall)
    fiber_tpu.init()
    ratio = walls["off"] / max(walls["on"], 1e-9)
    slow = ratio < _ICI_WALL_RATIO_FLOOR
    fat = repeat_wire > _ICI_REPEAT_WIRE_MAX
    starved = tstats.get("hits", 0) < gens - 1
    _emit({"metric": "ici_broadcast_wall_ratio", "value": round(ratio, 3),
           "unit": "x vs tier-off", "floor": _ICI_WALL_RATIO_FLOOR,
           "generations": gens, "tasks": tasks,
           "payload_mb": payload_mb,
           "wall_on_s": round(walls["on"], 4),
           "wall_off_s": round(walls["off"], 4)})
    _emit({"metric": "ici_gates",
           "repeat_wire_bytes": int(repeat_wire),
           "wire_budget": _ICI_REPEAT_WIRE_MAX,
           "wall_ratio": round(ratio, 3),
           "ratio_floor": _ICI_WALL_RATIO_FLOOR,
           "device_tier_hits": int(tstats.get("hits", 0)),
           "over_budget": bool(fat), "under_floor": bool(slow),
           "tier_cold": bool(starved)})
    rc = 0
    if fat:
        print(f"FAIL: repeat-generation wire bytes {repeat_wire} exceed "
              f"budget {_ICI_REPEAT_WIRE_MAX} — repeats are not coming "
              "out of the device tier", file=sys.stderr)
        rc = 1
    if starved:
        print(f"FAIL: device tier hits {tstats.get('hits', 0)} < "
              f"{gens - 1} — repeat resolutions missed the tier",
              file=sys.stderr)
        rc = 1
    if slow:
        print(f"FAIL: device-tier broadcast wall ratio {ratio:.2f}x "
              f"below floor {_ICI_WALL_RATIO_FLOOR}x", file=sys.stderr)
        rc = 1
    return rc


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--platform", default="",
                        help="force a jax platform (e.g. cpu)")
    parser.add_argument("--pop", type=int, default=None,
                        help="population size (default 4096; 1024 with "
                             "--pixels)")
    parser.add_argument("--steps", type=int, default=None,
                        help="episode length (default 500 — CartPole-v1; "
                             "the env max with --pixels)")
    parser.add_argument("--gens", type=int, default=10)
    parser.add_argument("--init-timeout", type=float, default=600.0)
    parser.add_argument("--no-pool-bench", action="store_true",
                        help="skip the host Pool.map overhead section")
    parser.add_argument("--poet", action="store_true",
                        help="run the POET co-evolution workload instead "
                             "of plain ES (the gecco-2020 north-star "
                             "shape); emits a poet metric line")
    parser.add_argument("--pixels", action="store_true",
                        help="run the pixel-observation conv-policy ES "
                             "(the reference's large-batch Atari ES "
                             "shape) instead of MLP CartPole")
    parser.add_argument("--biped", action="store_true",
                        help="run ES on the ParamBipedWalker obstacle "
                             "course (the reference's headline ES "
                             "benchmark env: modified BipedalWalker — "
                             "mkdocs/introduction.md:441-486) instead "
                             "of MLP CartPole")
    parser.add_argument("--attention", action="store_true",
                        help="bench the sequence-parallel plane instead: "
                             "ring attention tokens/sec at --seq tokens "
                             "(beyond-parity metric; the reference has "
                             "no attention at all)")
    parser.add_argument("--seq", type=int, default=16384,
                        help="sequence length for --attention")
    parser.add_argument("--lm", action="store_true",
                        help="bench long-context TRAINING instead: TinyLM "
                             "optimizer steps (fwd+bwd+adamw) with the "
                             "sequence ring-sharded at --seq tokens")
    parser.add_argument("--store", action="store_true",
                        help="bench the object-store data plane instead "
                             "(docs/objectstore.md): local put/get "
                             "throughput, wire fetch throughput, and "
                             "broadcast bytes-per-task with the "
                             "by-reference pool path on vs off; pure "
                             "host plane (runs on JAX_PLATFORMS=cpu)")
    parser.add_argument("--store-mb", type=float, default=8.0,
                        help="broadcast payload size for --store, MB")
    parser.add_argument("--store-tasks", type=int, default=64,
                        help="task count for the --store broadcast "
                             "section")
    parser.add_argument("--telemetry", action="store_true",
                        help="bench the telemetry plane instead "
                             "(docs/observability.md): small-task pool "
                             "throughput with telemetry off / "
                             "metrics-only / full tracing; fails past "
                             "5% full-tracing overhead. Pure host "
                             "plane (runs on JAX_PLATFORMS=cpu)")
    parser.add_argument("--telemetry-reps", type=int, default=3,
                        help="walls per mode for --telemetry (best-of)")
    parser.add_argument("--accounting", action="store_true",
                        help="bench the accounting plane instead "
                             "(docs/observability.md 'Resource "
                             "accounting'): small-task pool throughput "
                             "with the cost ledger fully on vs "
                             "telemetry off; fails past 5%% overhead. "
                             "Pure host plane (runs on "
                             "JAX_PLATFORMS=cpu)")
    parser.add_argument("--record", action="store_true",
                        help="append every emitted metric line to "
                             "BENCH_history.jsonl (ts, git sha, bench "
                             "args) so the perf trajectory survives "
                             "the in-place BENCH_*.json overwrites; "
                             "scripts/bench_check.py flags regressions "
                             "vs the best recorded value")
    parser.add_argument("--sched", action="store_true",
                        help="bench the scheduler plane instead "
                             "(docs/scheduling.md): uniform-workload "
                             "overhead of the adaptive scheduler vs "
                             "fifo, and straggler speculation on vs "
                             "off under a chaos-slowed worker; fails "
                             "past 5% overhead or under 1.3x straggler "
                             "speedup. Pure host plane (runs on "
                             "JAX_PLATFORMS=cpu)")
    parser.add_argument("--sched-reps", type=int, default=3,
                        help="walls per scenario for --sched (best-of)")
    parser.add_argument("--autonomy", action="store_true",
                        help="bench the policy plane instead "
                             "(docs/observability.md 'Autonomous "
                             "operations'): per-fault-class anomaly -> "
                             "action -> outcome chain drills, a "
                             "policy-enabled chaos soak (zero lost "
                             "tasks), and the engine's on-but-idle "
                             "pool overhead; fails past 5%% overhead, "
                             "any lost task, or any unlinked chain. "
                             "Pure host plane (runs on "
                             "JAX_PLATFORMS=cpu)")
    parser.add_argument("--autonomy-reps", type=int, default=3,
                        help="walls per mode for --autonomy (best-of)")
    parser.add_argument("--transport", action="store_true",
                        help="bench the transport I/O core instead "
                             "(docs/transport.md): selector event loop "
                             "vs thread-per-connection on small-frame "
                             "frames/sec, large-frame throughput, and "
                             "a 64-worker fan-in (CPU + thread count); "
                             "fails under 1.5x small-frame or 0.95x "
                             "large-frame. Pure host plane (runs on "
                             "JAX_PLATFORMS=cpu)")
    parser.add_argument("--transport-reps", type=int, default=3,
                        help="walls per case for --transport (best-of)")
    parser.add_argument("--cluster", action="store_true",
                        help="run the full-stack macro bench instead "
                             "(docs/observability.md, ROADMAP item 5): "
                             "simulated multi-host pool, per-generation "
                             "8MB store broadcasts, straggler + "
                             "worker-kill chaos, full tracing + flight "
                             "recorder; gates end-to-end evals/s, "
                             "bytes-per-task, the explain verdict and "
                             "the postmortem bundle, and archives a "
                             "Perfetto trace + flight artifact per run "
                             "into RUNS/. Pure host plane (runs on "
                             "JAX_PLATFORMS=cpu)")
    parser.add_argument("--cluster-hosts", type=int, default=2,
                        help="simulated pod hosts for --cluster")
    parser.add_argument("--cluster-tasks", type=int, default=64,
                        help="evals per generation for --cluster")
    parser.add_argument("--cluster-gens", type=int, default=3,
                        help="generations for --cluster")
    parser.add_argument("--cluster-mb", type=float, default=8.0,
                        help="per-generation broadcast size for "
                             "--cluster, MB")
    parser.add_argument("--recovery", action="store_true",
                        help="run the durable-map recovery bench instead "
                             "(docs/robustness.md): no-crash write-ahead "
                             "ledger overhead (gated <= 5%%) and resume "
                             "wall proportional to the REMAINING tasks "
                             "of a 75%%-journaled job, with an "
                             "exactly-once restored/executed "
                             "reconciliation. Pure host plane (runs on "
                             "JAX_PLATFORMS=cpu)")
    parser.add_argument("--recovery-reps", type=int, default=3,
                        help="walls per case for --recovery (best-of)")
    parser.add_argument("--recovery-tasks", type=int, default=240,
                        help="tasks per map for --recovery")
    parser.add_argument("--scale", action="store_true",
                        help="master scale-out macrobench: >=1M tiny "
                             "tasks through hierarchical per-host "
                             "dispatch over the shm transport vs a "
                             "single-master direct+selector baseline; "
                             "gates on master dispatch capacity and "
                             "master CPU per task "
                             "(docs/architecture.md)")
    parser.add_argument("--scale-tasks", type=int, default=1_000_000,
                        help="tasks through the hierarchical arm")
    parser.add_argument("--scale-base-tasks", type=int, default=100_000,
                        help="tasks through the direct baseline arm "
                             "(ratios are per-task, so the arms need "
                             "not match)")
    parser.add_argument("--scale-chunk", type=int, default=1,
                        help="chunksize for BOTH --scale arms (1 = the "
                             "per-chunk REQ/REP regime the bench "
                             "measures escape from)")
    parser.add_argument("--scale-range", type=int, default=64,
                        help="dispatch_range_chunks for the "
                             "hierarchical arm")
    parser.add_argument("--stream", action="store_true",
                        help="streaming data plane macrobench "
                             "(docs/streaming.md): >= 1M tiny tasks "
                             "through a windowed imap_unordered over a "
                             "generator; gates on completion, master "
                             "peak RSS vs a 100x-smaller streamed run, "
                             "and tasks/s vs a materialized map")
    parser.add_argument("--stream-tasks", type=int, default=1_000_000,
                        help="streamed task count for the headline arm "
                             "(the completion gate needs >= 1M)")
    parser.add_argument("--stream-base-tasks", type=int, default=10_000,
                        help="task count for the small RSS-baseline arm")
    parser.add_argument("--stream-chunk", type=int, default=64,
                        help="chunksize for every --stream arm")
    parser.add_argument("--stream-workers", type=int, default=4,
                        help="worker processes per --stream arm")
    parser.add_argument("--stream-window", type=int, default=128,
                        help="admission window (chunks) for the "
                             "streamed arms (matches the config "
                             "default)")
    parser.add_argument("--scale-workers", type=int, default=4,
                        help="sub-worker count for both --scale arms")
    parser.add_argument("--serve", action="store_true",
                        help="serving-daemon macrobench "
                             "(docs/serving.md): N tenants x M jobs "
                             "through one daemon; gates WDRR fairness, "
                             "budget preemption (parked resumable), "
                             "killed-client and killed-daemon "
                             "exactly-once recovery, disjoint cost "
                             "reconciliation, and warm-vs-cold "
                             "first-job latency")
    parser.add_argument("--serve-tenants", type=int, default=3,
                        help="equal-workload tenants for the --serve "
                             "fairness arm (>= 2)")
    parser.add_argument("--serve-jobs", type=int, default=2,
                        help="concurrent jobs per tenant (>= 2)")
    parser.add_argument("--serve-tasks", type=int, default=40,
                        help="tasks per job for every --serve arm")
    parser.add_argument("--slo", action="store_true",
                        help="SLO plane + observability archive bench "
                             "(docs/observability.md 'SLOs and the "
                             "archive'): armed archive+SLO vs plain "
                             "daemon overhead, slow-worker chaos to "
                             "slo_burn with a cause_id-linked "
                             "anomaly->action->outcome chain read back "
                             "from the archive, and SIGKILL+restart "
                             "burn-window durability with zero torn "
                             "reads")
    parser.add_argument("--ici", action="store_true",
                        help="device-tier data plane bench "
                             "(docs/objectstore.md 'Device tier'): "
                             "repeat-generation param resolutions must "
                             "come out of the device-resident store "
                             "with ~zero wire bytes, and the collective "
                             "broadcast path must beat the tier-off "
                             "re-transfer-every-call baseline by >= "
                             "1.3x wall. Runs on JAX_PLATFORMS=cpu (the "
                             "forced-host-device mesh stands in for "
                             "the pod)")
    parser.add_argument("--ici-mb", type=float, default=8.0,
                        help="broadcast param size for --ici")
    parser.add_argument("--ici-gens", type=int, default=4,
                        help="generations (repeat resolutions / timed "
                             "maps) for --ici")
    parser.add_argument("--ici-tasks", type=int, default=16,
                        help="tasks per generation for the --ici wall "
                             "arm")
    parser.add_argument("--profile", default="",
                        help="write a jax.profiler trace of the timed ES "
                             "section to this directory (inspect with "
                             "tensorboard or xprof)")
    args = parser.parse_args()
    if args.gens < 1:
        parser.error("--gens must be >= 1")
    if sum((args.poet, args.pixels, args.biped, args.attention,
            args.lm, args.store, args.telemetry, args.sched,
            args.transport, args.cluster, args.recovery,
            args.accounting, args.scale, args.ici,
            args.autonomy, args.stream, args.serve, args.slo)) > 1:
        parser.error("--poet/--pixels/--biped/--attention/--lm/--store/"
                     "--telemetry/--sched/--transport/--cluster/"
                     "--recovery/--accounting/--scale/--ici/--autonomy/"
                     "--stream/--serve/--slo are mutually exclusive")
    if args.record:
        _arm_record()
    if args.store:
        # Host-plane only: no accelerator probe, no watchdog — the
        # store bench must run identically on a laptop and a pod host.
        return _store_bench(args)
    if args.telemetry:
        return _telemetry_bench(args)  # host-plane only, like --store
    if args.accounting:
        # Focused accounting-plane gate (`make bench-accounting`): the
        # telemetry bench's off + accounting arms only.
        return _telemetry_bench(args, only=("off", "accounting"))
    if args.sched:
        return _sched_bench(args)  # host-plane only, like --store
    if args.autonomy:
        return _autonomy_bench(args)  # host-plane only, like --store
    if args.transport:
        return _transport_bench(args)  # host-plane only, like --store
    if args.cluster:
        return _cluster_bench(args)  # host-plane only, like --store
    if args.recovery:
        return _recovery_bench(args)  # host-plane only, like --store
    if args.scale:
        return _scale_bench(args)  # host-plane only, like --store
    if args.stream:
        return _stream_bench(args)  # host-plane only, like --store
    if args.serve:
        return _serve_bench(args)  # host-plane only, like --store
    if args.slo:
        return _slo_bench(args)  # host-plane only, like --store
    if args.ici:
        return _ici_bench(args)  # CPU mesh stands in for the pod
    if args.pop is not None and args.pop < 2:
        parser.error("--pop must be >= 2")
    if args.steps is not None and args.steps < 1:
        parser.error("--steps must be >= 1")
    if (args.attention or args.lm) and args.seq < 64:
        parser.error("--seq must be >= 64")

    metric = ("poet_policy_evals_per_sec" if args.poet
              else "es_pixel_evals_per_sec" if args.pixels
              else "es_biped_evals_per_sec" if args.biped
              else "ring_attention_tokens_per_sec" if args.attention
              else "lm_train_tokens_per_sec" if args.lm
              else "es_policy_evals_per_sec")
    fail_payload = {
        "metric": metric,
        "value": 0.0,
        "unit": "tokens/s" if (args.attention or args.lm) else "evals/s",
        "vs_baseline": None if (args.attention or args.lm) else 0.0,
        "error": "accelerator backend initialization timed out",
    }

    # --platform is an explicit request (cpu: rehearse the same path on
    # the virtual CPU mesh), set before jax is imported. With no flag a
    # device mode runs on whatever accelerator JAX finds and FAILS if it
    # finds none: a measurement path never falls back to the CPU.
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    watchdog = _watchdog(args.init_timeout, fail_payload)
    import jax

    devices = jax.devices()
    watchdog.cancel()
    if not args.platform and devices[0].platform == "cpu":
        print("bench: JAX found no accelerator (jax.devices() is cpu) and "
              "no --platform was given; a device mode does not measure "
              "on the CPU by default. Pass --platform cpu to rehearse "
              "there.", file=sys.stderr)
        return 3

    if not (args.pixels or args.attention or args.lm):
        # Constants, not a recorded sweep: a run's population and
        # episode length never depend on a file from an earlier run.
        if args.pop is None:
            args.pop = 4096
        if args.steps is None:
            args.steps = 400 if args.biped else 500
    if args.poet:
        return _poet_bench(args, devices)
    if args.attention:
        return _attention_bench(args, devices)
    if args.lm:
        return _lm_bench(args, devices)

    import numpy as np
    from jax.sharding import Mesh

    from fiber_tpu.models import CartPole, ConvPolicy, MLPPolicy, PixelChase
    from fiber_tpu.ops import EvolutionStrategy

    mesh = Mesh(np.asarray(devices), ("pool",))
    n_dev = len(devices)

    if args.pixels:
        # The reference's "large-batch Atari ES" reproduction config
        # (BASELINE.json): conv policy on a pixel env, the whole
        # render+conv+step loop compiled on-device. Pixel episodes are
        # ~25x heavier per step than CartPole, so the per-mode default
        # pop is smaller; an explicit --pop/--steps always wins (the
        # parser defaults are None sentinels).
        policy = ConvPolicy(PixelChase.obs_shape, PixelChase.act_dim)
        env_name = "PixelChase"
        if args.pop is None:
            args.pop = 1024
        if args.steps is None:
            args.steps = PixelChase.max_steps

        def eval_fn(theta, key):
            return PixelChase.rollout(policy.act, theta, key,
                                      max_steps=args.steps)
    elif args.biped:
        # The reference's headline ES benchmark env (modified
        # BipedalWalker / POET domain, mkdocs/introduction.md:441-486)
        # on its flat default course.
        import jax.numpy as jnp

        from fiber_tpu.models import ParamBipedWalker

        policy = MLPPolicy(ParamBipedWalker.obs_dim,
                           ParamBipedWalker.act_dim, hidden=(32, 32))
        env_name = "ParamBipedWalker"
        flat_course = jnp.asarray(ParamBipedWalker.DEFAULT)

        def eval_fn(theta, key):
            return ParamBipedWalker.rollout_p(
                policy.act, flat_course, theta, key,
                max_steps=args.steps)
    else:
        policy = MLPPolicy(CartPole.obs_dim, CartPole.act_dim,
                           hidden=(32, 32))
        env_name = "CartPole"

        def eval_fn(theta, key):
            return CartPole.rollout(policy.act, theta, key,
                                    max_steps=args.steps)

    # Warmup compiles AND executes the fused N-generation program once
    # (the timed section re-runs the same program, measuring steady
    # state). The watchdog stays armed until the warmup completes — a
    # hung compile must still produce a JSON line.
    compile_watchdog = _watchdog(
        args.init_timeout,
        {**fail_payload, "error": "compile/warmup timed out"},
    )
    es = EvolutionStrategy(
        eval_fn, dim=policy.dim, pop_size=args.pop, sigma=0.1, lr=0.03,
        mesh=mesh,
    )
    params = policy.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)

    key, k = jax.random.split(key)
    params, warm_stats = es.run_fused(params, k, args.gens)
    jax.block_until_ready(warm_stats)
    compile_watchdog.cancel()

    # Timed: all generations as ONE fused XLA program (lax.scan over the
    # step) — no per-generation dispatch overhead. --profile wraps this
    # exact section in a jax.profiler trace.
    from contextlib import nullcontext

    from fiber_tpu.utils.profiling import trace as profiler_trace

    prof = profiler_trace(args.profile) if args.profile else nullcontext()
    with prof:
        t0 = time.perf_counter()
        key, k = jax.random.split(key)
        params, stats_seq = es.run_fused(params, k, args.gens)
        jax.block_until_ready(stats_seq)
        elapsed = time.perf_counter() - t0
    stats = stats_seq[-1]

    from fiber_tpu.utils import flops as flopsmod

    gen_flops = flopsmod.es_flops_per_gen(
        policy, env_name, args.steps, es.pop_size, policy.dim)
    total_evals = es.pop_size * args.gens
    evals_per_sec = total_evals / elapsed
    model_fps = gen_flops * args.gens / elapsed
    per_chip_share = NORTH_STAR_EVALS_PER_SEC / NORTH_STAR_CHIPS
    # The north star (BASELINE.json) is the MLP-CartPole workload; the
    # ~25x-heavier pixel workload and the biped (different env cost)
    # have no published baseline, so their lines carry vs_baseline=null
    # rather than a workload-mismatched ratio.
    vs_baseline = (None if args.pixels or args.biped else
                   round(evals_per_sec / (per_chip_share * n_dev), 3))
    result = {
        "metric": metric,
        "value": round(evals_per_sec, 2),
        "unit": "evals/s",
        "vs_baseline": vs_baseline,
        "pop_size": es.pop_size,
        "episode_steps": args.steps,
        "generations": args.gens,
        "n_devices": n_dev,
        "platform": devices[0].platform,
        "env_steps_per_sec": round(evals_per_sec * args.steps, 1),
        "model_flops_per_sec": round(model_fps, 1),
        "mfu": _round_mfu(flopsmod.mfu(model_fps, devices)),
        **flopsmod.peak_report(devices),
        "mean_fitness": float(jax.device_get(stats)[0]),
        "rollout_unroll": int(os.environ.get("FIBER_ROLLOUT_UNROLL",
                                             "1")),
        "policy_dtype": (os.environ.get("FIBER_POLICY_DTYPE")
                         or "float32"),
    }

    # The host Pool.map section starts worker processes AFTER this
    # process took the chip; they are host-plane workers, which the
    # launcher pins to JAX_PLATFORMS=cpu (one process per chip). A
    # failure here fails the run — no *_error field, no exit 0.
    if not args.no_pool_bench:
        result.update(_pool_bench())

    _emit(result)
    enforce = os.environ.get("FIBER_BENCH_ENFORCE", "").strip().lower()
    if (enforce not in ("", "0", "false", "no")
            and result.get("pool_map_1ms_over_budget")):
        print(
            f"FAIL: pool_map_1ms_overhead_vs_mp "
            f"{result['pool_map_1ms_overhead_vs_mp']} exceeds budget "
            f"{_POOL_1MS_BUDGET}", file=sys.stderr,
        )
        return 1
    return 0


def _attention_bench(args, devices) -> int:
    """Sequence-parallel plane: exact ring attention throughput at
    --seq tokens (sharded over the mesh; blockwise online-softmax on a
    single device). Beyond-parity metric — the reference has no
    attention — so vs_baseline is null."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from fiber_tpu.ops import ring_attention

    n_dev = len(devices)
    mesh = Mesh(np.asarray(devices), ("pool",))
    seq, heads, head_dim = args.seq, 8, 64
    seq = max(seq - seq % max(n_dev, 1), n_dev)
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (seq, heads, head_dim)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)

    watchdog = _watchdog(args.init_timeout, {
        "metric": "ring_attention_tokens_per_sec", "value": 0.0,
        "unit": "tokens/s", "vs_baseline": None,
        "error": "attention compile/warmup timed out",
    })
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    jax.block_until_ready(out)
    watchdog.cancel()

    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        out = ring_attention(q, k, v, mesh=mesh, causal=True)
    jax.block_until_ready(out)
    elapsed = time.perf_counter() - t0

    from fiber_tpu.utils import flops as flopsmod

    attn_flops = flopsmod.attention_flops(seq, heads, head_dim,
                                          causal=True)
    attn_fps = attn_flops * iters / elapsed
    result = {
        "metric": "ring_attention_tokens_per_sec",
        "value": round(seq * iters / elapsed, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "seq_len": seq,
        "heads": heads,
        "head_dim": head_dim,
        "causal": True,
        "dtype": "bfloat16",
        "n_devices": n_dev,
        "platform": devices[0].platform,
        "attn_flops_per_sec": round(attn_fps, 1),
        "mfu": _round_mfu(flopsmod.mfu(attn_fps, devices)),
        **flopsmod.peak_report(devices),
    }
    # The Pallas kernel legs compile through Mosaic, so they run on a
    # TPU only (`--platform cpu` rehearses the ring line alone; the
    # interpreter is not a measurement). A leg that fails — compile
    # error, mismatch against the ring output — fails the run.
    on_tpu = devices[0].platform == "tpu"
    base = jax.device_get(out).astype(np.float32) if on_tpu else None

    def kernel_leg(run):
        """Warm ``run`` under the watchdog, then time ``iters`` calls:
        (host f32 output of the warm call, timed seconds)."""
        watchdog = _watchdog(args.init_timeout, dict(result))
        try:
            warm = run()
            jax.block_until_ready(warm)
        finally:
            watchdog.cancel()
        t0 = time.perf_counter()
        for _ in range(iters):
            leg_out = run()
        jax.block_until_ready(leg_out)
        return (jax.device_get(warm).astype(np.float32),
                time.perf_counter() - t0)

    if on_tpu and n_dev == 1:
        # A/B: the flash kernel on the same workload, same single
        # device. Scores stream through VMEM instead of materializing
        # (h, S, S) in HBM.
        from fiber_tpu.ops.pallas_attention import flash_attention

        got, flash_elapsed = kernel_leg(
            lambda: flash_attention(q, k, v, causal=True))
        max_err = float(np.abs(got - base).max())
        if max_err > 5e-2:
            raise RuntimeError(f"flash kernel mismatch: {max_err}")
        result["flash_tokens_per_sec"] = round(
            seq * iters / flash_elapsed, 1)
        result["flash_speedup"] = round(elapsed / flash_elapsed, 3)
        result["flash_max_err_vs_xla"] = max_err
        result["flash_mfu"] = _round_mfu(flopsmod.mfu(
            attn_flops * iters / flash_elapsed, devices))

        # Windowed flash: the same kernel with a 1024-token sliding
        # window — O(S*window) compute via grid-level block skipping.
        # NOT an apples A/B with the full-attention legs (different
        # attention pattern); its own throughput with the WINDOWED
        # analytic FLOPs. Positions < window attend exactly the keys
        # full causal attention does, so the ring output is an
        # exact-pattern reference for that prefix.
        win = 1024
        got_w, w_elapsed = kernel_leg(
            lambda: flash_attention(q, k, v, causal=True, window=win))
        w_err = float(np.abs(got_w[:win] - base[:win]).max())
        if w_err > 5e-2:
            raise RuntimeError(f"windowed-flash prefix mismatch: {w_err}")
        w_flops = flopsmod.attention_flops(seq, heads, head_dim,
                                           causal=True, window=win)
        result["flash_window"] = win
        result["flash_window_tokens_per_sec"] = round(
            seq * iters / w_elapsed, 1)
        result["flash_window_prefix_err"] = w_err
        result["flash_window_mfu"] = _round_mfu(flopsmod.mfu(
            w_flops * iters / w_elapsed, devices))

    if on_tpu:
        # Ring x flash composition: the Pallas kernel as the ring's
        # per-device block (on one chip: one kernel sweep plus the
        # merge plumbing).
        got, rf_elapsed = kernel_leg(
            lambda: ring_attention(q, k, v, mesh=mesh, causal=True,
                                   local="flash"))
        rf_err = float(np.abs(got - base).max())
        if rf_err > 5e-2:
            raise RuntimeError(f"ring-flash mismatch: {rf_err}")
        result["ring_flash_tokens_per_sec"] = round(
            seq * iters / rf_elapsed, 1)
        result["ring_flash_speedup"] = round(elapsed / rf_elapsed, 3)
        result["ring_flash_mfu"] = _round_mfu(flopsmod.mfu(
            attn_flops * iters / rf_elapsed, devices))

    _emit(result)
    return 0


def _lm_bench(args, devices) -> int:
    """Long-context TRAINING throughput: optimizer steps of TinyLM with
    the sequence sharded over the mesh via ring attention (forward +
    backward + adamw). Beyond-parity metric — the reference trains
    nothing — so vs_baseline is null."""
    import numpy as np

    import jax
    import optax
    from jax.sharding import Mesh

    from fiber_tpu.models import TinyLM, make_train_step

    n_dev = len(devices)
    mesh = Mesh(np.asarray(devices), ("pool",))
    seq = max(args.seq - args.seq % max(n_dev, 1), n_dev)
    dim, heads, layers, vocab = 256, 8, 4, 256
    # Watchdog arms BEFORE any device work: model/optimizer init and
    # the token draw are eager device ops that can wedge on a flaky
    # accelerator just like the compile can.
    watchdog = _watchdog(args.init_timeout, {
        "metric": "lm_train_tokens_per_sec", "value": 0.0,
        "unit": "tokens/s", "vs_baseline": None,
        "error": "lm compile/warmup timed out",
    })
    model = TinyLM(vocab=vocab, dim=dim, heads=heads, layers=layers,
                   max_seq=seq, mesh=mesh, attention="ring")
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    step = make_train_step(model, opt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (seq,), 0, vocab)
    params, opt_state, loss = step(params, opt_state, toks)
    jax.block_until_ready(loss)
    watchdog.cancel()

    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, toks)
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - t0

    from fiber_tpu.utils import flops as flopsmod

    step_flops = flopsmod.tinylm_flops_per_step(model, seq, train=True)
    model_fps = step_flops * iters / elapsed
    result = {
        "metric": "lm_train_tokens_per_sec",
        "value": round(seq * iters / elapsed, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "seq_len": seq,
        "dim": dim,
        "heads": heads,
        "layers": layers,
        "attention": "ring",
        "n_devices": n_dev,
        "platform": devices[0].platform,
        "final_loss": float(jax.device_get(loss)),
        "model_flops_per_step": round(step_flops, 1),
        "model_flops_per_sec": round(model_fps, 1),
        "mfu": _round_mfu(flopsmod.mfu(model_fps, devices)),
        **flopsmod.peak_report(devices),
    }
    # A/B: the same train step through the Pallas flash kernels (fwd +
    # bwd), single device. Mosaic-only, so TPU-only; a failure in the
    # leg fails the run.
    if devices[0].platform == "tpu" and n_dev == 1:
        flash_watchdog = _watchdog(args.init_timeout, dict(result))
        try:
            fmodel = TinyLM(vocab=vocab, dim=dim, heads=heads,
                            layers=layers, max_seq=seq, mesh=mesh,
                            attention="flash")
            fstep = make_train_step(fmodel, opt)
            fparams = fmodel.init(jax.random.PRNGKey(0))
            fopt_state = opt.init(fparams)
            fparams, fopt_state, floss = fstep(fparams, fopt_state, toks)
            jax.block_until_ready(floss)
        finally:
            flash_watchdog.cancel()
        t0 = time.perf_counter()
        for _ in range(iters):
            fparams, fopt_state, floss = fstep(fparams, fopt_state, toks)
        jax.block_until_ready(floss)
        flash_elapsed = time.perf_counter() - t0
        result["flash_tokens_per_sec"] = round(
            seq * iters / flash_elapsed, 1)
        result["flash_train_speedup"] = round(elapsed / flash_elapsed, 3)
        result["flash_final_loss"] = float(jax.device_get(floss))
        result["flash_mfu"] = _round_mfu(flopsmod.mfu(
            step_flops * iters / flash_elapsed, devices))

    _emit(result)
    return 0


def _poet_bench(args, devices) -> int:
    """POET env/agent co-evolution end-to-end (the reference's
    examples/gecco-2020 workload shape): reports evals/s plus the
    co-evolution trajectory (pairs grown, transfers, fitness)."""
    import jax

    from fiber_tpu.models import MLPPolicy
    from fiber_tpu.models.envs import ParamCartPole
    from fiber_tpu.ops.poet import POET

    policy = MLPPolicy(ParamCartPole.obs_dim, ParamCartPole.act_dim,
                       hidden=(16,))
    poet = POET(ParamCartPole, policy, pop_size=args.pop, max_pairs=6,
                rollout_steps=args.steps)
    iters, es_steps = args.gens, 4
    t0 = time.perf_counter()
    history = poet.run(jax.random.PRNGKey(0), iters, es_steps=es_steps)
    elapsed = time.perf_counter() - t0
    total_evals = sum(
        h["pairs"] * poet.pop_size * es_steps
        + h.get("transfer_evals", 0)
        for h in history
    )
    from fiber_tpu.utils import flops as flopsmod

    model_fps = (total_evals * flopsmod.rollout_flops_per_eval(
        policy, "ParamCartPole", args.steps) / elapsed)
    per_chip_share = NORTH_STAR_EVALS_PER_SEC / NORTH_STAR_CHIPS
    result = {
        "metric": "poet_policy_evals_per_sec",
        "value": round(total_evals / elapsed, 2),
        "unit": "evals/s",
        "vs_baseline": round(
            total_evals / elapsed / (per_chip_share * len(devices)), 3),
        "iterations": iters,
        "pop_size": poet.pop_size,
        "rollout_steps": args.steps,
        "platform": devices[0].platform,
        "n_devices": len(devices),
        "model_flops_per_sec": round(model_fps, 1),
        "mfu": _round_mfu(flopsmod.mfu(model_fps, devices)),
        **flopsmod.peak_report(devices),
        "final_pairs": history[-1]["pairs"],
        "total_transfers": sum(h["transfers"] for h in history),
        "fitness_first_iter": round(history[0]["mean_fitness"], 2),
        "fitness_last_iter": round(history[-1]["mean_fitness"], 2),
        "history": history,
    }
    _emit(result)
    return 0


def _timed_task(duration):
    time.sleep(duration)
    return duration


def _dev_square(x):
    return x * x


def _pool_bench() -> dict:
    """Host-plane Pool.map overhead vs stdlib multiprocessing and the
    device-path Pool.map throughput (BASELINE.json's first metric). One
    recorded number replaces the round-1 CHANGELOG/PARITY discrepancy."""
    import multiprocessing

    # The host-pool section always measures the local backend — a
    # leftover FIBER_BACKEND=tpu without hosts would otherwise abort it.
    os.environ["FIBER_BACKEND"] = "local"
    import numpy as np

    import fiber_tpu
    from fiber_tpu.meta import meta

    out: dict = {}
    workers = 4

    def run_one(make_pool, n_tasks, duration):
        with make_pool(workers) as pool:
            pool.map(_timed_task, [0.0] * workers)  # spin-up barrier
            t0 = time.perf_counter()
            pool.map(_timed_task, [duration] * n_tasks)
            return time.perf_counter() - t0

    # Best-of-3 per pool, fiber and mp interleaved per rep — the same
    # convention every other gate here uses. The r05 flight-recorder
    # investigation (BENCH_r06 finding) showed the single-wall ratio
    # swinging 1.06–1.14 across ADJACENT reps on a 1-core box with
    # identical code (master-side cost measured at ~2ms of a ~190ms
    # map): one-shot walls gate scheduler jitter, not the pool.
    for duration, n_tasks, tag in ((0.001, 600, "1ms"), (0.01, 200, "10ms")):
        fib = mp = None
        for _ in range(3):
            f = run_one(lambda w: fiber_tpu.Pool(w), n_tasks, duration)
            m = run_one(
                lambda w: multiprocessing.get_context("spawn").Pool(w),
                n_tasks, duration,
            )
            fib = f if fib is None else min(fib, f)
            mp = m if mp is None else min(mp, m)
        out[f"pool_map_{tag}_tasks_per_sec"] = round(n_tasks / fib, 1)
        out[f"pool_map_{tag}_overhead_vs_mp"] = round(fib / mp, 3)
    # The 1 ms point is the reference's signature benchmark
    # (mkdocs/introduction.md:396-424) — budgeted so drift is caught
    # mechanically (VERDICT r3: 1.029 -> 1.05 went unnoticed). `make
    # bench` (FIBER_BENCH_ENFORCE=1) fails loudly past budget; the
    # driver's plain `python bench.py` still emits its one JSON line.
    out["pool_map_1ms_budget"] = _POOL_1MS_BUDGET
    out["pool_map_1ms_over_budget"] = bool(
        out["pool_map_1ms_overhead_vs_mp"] > _POOL_1MS_BUDGET)

    # Device path: @meta(device=True) lowers Pool.map onto the mesh.
    # The warmup must run at the TIMED shape — jit caches per shape, so
    # the old 64-item warmup left the 4096-item timed call paying a
    # fresh XLA compile (the likely cause of the r03 7,018-tasks/s TPU
    # record vs 105k on CPU; VERDICT r3 weak #3). The first full-shape
    # call is now reported separately as the cold number.
    dev_square = meta(device=True)(_dev_square)
    items = np.arange(4096.0, dtype=np.float32)
    with fiber_tpu.Pool() as pool:
        t0 = time.perf_counter()
        pool.map(dev_square, items)  # trace+compile at the timed shape
        out["pool_map_device_cold_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)
        iters = 5
        t0 = time.perf_counter()
        for _ in range(iters):
            pool.map(dev_square, items)
        out["pool_map_device_tasks_per_sec"] = round(
            len(items) * iters / (time.perf_counter() - t0), 1)
    return out


if __name__ == "__main__":
    sys.exit(main())
