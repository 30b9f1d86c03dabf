"""Continuous monitor plane: time-series sampler, anomaly watchdog,
sampling profiler, and the `fiber-tpu top` / `profile` / `metrics
--watch` CLI verbs (docs/observability.md)."""

import json
import os
import threading
import time

import pytest

import fiber_tpu
from fiber_tpu import config, telemetry
from fiber_tpu.telemetry import monitor as monitormod
from fiber_tpu.telemetry import profiler as profmod
from fiber_tpu.telemetry.flightrec import FLIGHT, order_events
from fiber_tpu.telemetry.monitor import AnomalyWatchdog, WATCHDOG
from fiber_tpu.telemetry.timeseries import (
    TIMESERIES,
    SeriesRing,
    snapshot_deltas,
)
from fiber_tpu.testing import chaos
from tests import targets

SEED = int(os.environ.get("FIBER_CHAOS_SEED", "7"))


@pytest.fixture(autouse=True)
def _monitor_isolation():
    """Each test starts with clean monitor/watchdog/profiler state and
    ends with config overrides dropped (init re-syncs the plane)."""
    TIMESERIES.clear()
    WATCHDOG.clear()
    profmod.PROFILER.clear()
    profmod.AGGREGATE.clear()
    FLIGHT.clear()
    yield
    chaos.uninstall()
    fiber_tpu.init()
    TIMESERIES.clear()
    WATCHDOG.clear()
    profmod.PROFILER.clear()
    profmod.AGGREGATE.clear()


def _fresh_watchdog(**overrides) -> AnomalyWatchdog:
    fiber_tpu.init(**overrides)
    dog = AnomalyWatchdog()
    dog.configure(config.get())
    return dog


def _sample(**kw):
    base = {"wall": time.time(), "mono": time.monotonic(),
            "tasks_per_s": 0.0, "inflight": 0.0, "queue_depth": 0.0,
            "heartbeat_age_s": 0.0, "tx_queue_bytes": 0.0}
    base.update(kw)
    return base


# ---------------------------------------------------------------------------
# ring + rate semantics
# ---------------------------------------------------------------------------


def test_series_ring_is_bounded_with_dual_clock_points():
    ring = SeriesRing(capacity=4)
    for i in range(10):
        ring.add(1000.0 + i, 50.0 + i, float(i * 10))
    assert len(ring) == 4
    pts = ring.points()
    assert pts[0] == (1006.0, 56.0, 60.0)      # oldest survivor
    assert all(len(p) == 3 for p in pts)
    # rate = delta value / delta MONOTONIC between newest two points
    assert ring.rate() == pytest.approx(10.0)
    ring.resize(2)
    assert len(ring) == 2 and ring.last() == (1009.0, 59.0, 90.0)
    # counter reset (value goes backwards) clamps to zero, not negative
    ring.add(1010.0, 60.0, 0.0)
    assert ring.rate() == 0.0


def test_snapshot_deltas_rate_math():
    prev = {
        "c": {"type": "counter", "series": {"": 100.0, "op=x": 5.0}},
        "g": {"type": "gauge", "series": {"": 7.0}},
        "h": {"type": "histogram", "series": {"": [1, 0, 0.5, 3]}},
    }
    cur = {
        "c": {"type": "counter", "series": {"": 150.0, "op=x": 5.0}},
        "g": {"type": "gauge", "series": {"": 9.0}},
        "h": {"type": "histogram", "series": {"": [2, 0, 0.9, 5]}},
    }
    out = snapshot_deltas(prev, cur, dt=2.0)
    assert out["c"] == {"kind": "counter", "delta": 50.0, "rate": 25.0}
    assert "c{op=x}" not in out                 # unmoved series omitted
    assert out["g"] == {"kind": "gauge", "value": 9.0, "delta": 2.0}
    assert out["h"] == {"kind": "histogram", "delta": 2, "rate": 1.0}
    assert snapshot_deltas(prev, cur, dt=0.0) == {}


def test_monitor_off_is_noop():
    fiber_tpu.init(monitor_enabled=False)
    assert not TIMESERIES.enabled
    assert TIMESERIES._thread is None
    before = TIMESERIES.samples
    time.sleep(0.15)
    assert TIMESERIES.samples == before
    assert TIMESERIES.snapshot()["series"] == {}
    # telemetry master switch kills the plane too
    fiber_tpu.init(telemetry_enabled=False)
    assert not TIMESERIES.enabled


def test_monitor_knobs_follow_refresh():
    fiber_tpu.init(monitor_interval_s=0.05, monitor_history=7)
    assert TIMESERIES.enabled
    assert TIMESERIES._interval == pytest.approx(0.05)
    TIMESERIES.sample_once()
    assert all(ring.capacity == 7
               for ring in TIMESERIES._series.values())
    deadline = time.monotonic() + 5.0
    while TIMESERIES.samples < 3 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert TIMESERIES.samples >= 3  # the thread ticks on its own


def test_each_sampler_pass_is_a_monitor_tick_span():
    """The sampler thread's passes leave ``monitor.tick`` spans: when a
    tick ran and how long it took, on the same store as the step calls
    it may have intruded on."""
    from fiber_tpu.telemetry import tracing

    tracing.SPANS.clear()
    fiber_tpu.init(monitor_interval_s=0.02)
    deadline = time.monotonic() + 5.0
    ticks = []
    while len(ticks) < 3 and time.monotonic() < deadline:
        time.sleep(0.02)
        ticks = [s for s in tracing.SPANS.snapshot()
                 if s["name"] == "monitor.tick"]
    assert len(ticks) >= 3
    assert all(t["end_ns"] > t["start_ns"] and t["dur"] > 0
               for t in ticks)
    assert all(t["pid"] == os.getpid() for t in ticks)
    # a pass somebody asks for by hand is not the thread's
    fiber_tpu.init(monitor_enabled=False)
    tracing.SPANS.clear()
    TIMESERIES.sample_once()
    assert tracing.SPANS.snapshot() == []


_EXIT_PROBE = r"""
import atexit, sys, threading
sys.path.insert(0, {repo!r})

def report():   # registered first, so it runs after every other hook
    alive = [t.name for t in threading.enumerate()
             if t.name == "fiber-monitor-sampler" and t.is_alive()]
    print("SAMPLER_ALIVE_AT_EXIT", alive, flush=True)

atexit.register(report)
import fiber_tpu.telemetry
from fiber_tpu.telemetry import TIMESERIES
import jax, jax.numpy as jnp
from fiber_tpu.telemetry.device import DEVICE
DEVICE.install_listeners()
# ticks back to back: the process ends while the thread is inside JAX
# (live arrays, memory stats)
TIMESERIES.configure(enabled=True, interval=0.02, capacity=16)
keep = [jnp.float32(i) for i in range(2000)]
while TIMESERIES.samples < 5:
    pass
print("SAMPLES", TIMESERIES.samples, flush=True)
"""


def test_sampler_thread_is_stopped_and_joined_at_exit():
    """A process that imports ``fiber_tpu.telemetry`` and ends while its
    sampler thread is inside JAX exits 0: the plane's own ``atexit``
    hook stops the thread and waits for it before the interpreter is
    finalized (a daemon thread caught there inside JAX aborts the
    process, rc -6)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, "-c", _EXIT_PROBE.format(repo=repo)],
            capture_output=True, text=True, timeout=180, cwd=repo)
        assert proc.returncode == 0, proc.stdout + proc.stderr[-2000:]
        assert "SAMPLES" in proc.stdout
        assert "SAMPLER_ALIVE_AT_EXIT []" in proc.stdout, proc.stdout


def test_sampler_derives_rates_from_counters():
    # Thread off: the test drives the ticks so the newest two points
    # deterministically straddle a counter increment.
    fiber_tpu.init(monitor_enabled=False)
    counter = telemetry.counter("pool_tasks_completed")
    for _ in range(4):
        counter.inc(50)
        TIMESERIES.sample_once()
        time.sleep(0.02)
    last = TIMESERIES.last_sample()
    assert last["tasks_per_s"] > 0
    pts = TIMESERIES.snapshot()["series"]["tasks_completed"]
    assert len(pts) >= 4
    wall, mono, value = pts[-1]
    assert abs(wall - time.time()) < 5.0
    assert value >= 200


def test_sampler_tracks_the_device_planes_steps():
    """``device_steps`` / ``device_step_units`` ride the rings with
    their rates, so ``top`` shows a training job's throughput."""
    from fiber_tpu.cli import _TOP_HEADER, _render_top_rows
    from fiber_tpu.telemetry import device

    fiber_tpu.init(monitor_enabled=False)
    for _ in range(4):
        for _ in range(3):
            with device.step("lm.train_step", 8192):
                pass
        TIMESERIES.sample_once()
        time.sleep(0.02)
    last = TIMESERIES.last_sample()
    assert last["steps_per_s"] > 0
    assert last["step_units_per_s"] == pytest.approx(
        8192 * last["steps_per_s"], rel=1e-3)
    series = TIMESERIES.snapshot()["series"]
    assert len(series["device_steps"]) >= 4
    assert series["device_step_units"][-1][2] >= 12 * 8192
    pulls = {"h1:7060": {"timeseries": {"last": last},
                         "anomalies": {"active": {"step_stall": {}}}}}
    row = _render_top_rows(pulls)[0]
    assert f"{last['steps_per_s']:.2f}" in row and "step_stall" in row
    assert "STEPS/S" in _TOP_HEADER


def test_tick_names_the_call_in_flight_and_where_its_thread_is():
    """A tick taken while another thread sleeps inside ``device.step``
    carries ``open``, ``open_ns`` and an ``at`` that names the sleeping
    function; once the call is over, ``idle_ns``."""
    from fiber_tpu.telemetry import device, tracing

    fiber_tpu.init(monitor_enabled=False)
    tracing.SPANS.clear()
    TIMESERIES.tick()
    assert tracing.SPANS.snapshot()[-1]["open"] is None  # no call yet
    inside, leave = threading.Event(), threading.Event()

    def sleeping_inside_the_step():
        inside.set()
        while not leave.is_set():
            time.sleep(0.005)

    def job():
        with device.step("lm.train_step", 8):
            sleeping_inside_the_step()

    t = threading.Thread(target=job)
    t.start()
    assert inside.wait(10)
    time.sleep(0.02)
    TIMESERIES.tick()
    leave.set()
    t.join(timeout=10)
    assert not t.is_alive()
    tick = tracing.SPANS.snapshot()[-2]
    assert tick["name"] == "monitor.tick"
    assert tick["open"] == "lm.train_step"
    assert tick["open_ns"] >= 15_000_000 and "idle_ns" not in tick
    path, function, line = tick["at"].split(":")
    assert path == "test_monitor.py" and int(line) > 0
    assert function == "sleeping_inside_the_step"
    time.sleep(0.01)
    TIMESERIES.tick()
    after = tracing.SPANS.snapshot()[-1]
    assert after["open"] is None and after["idle_ns"] >= 10_000_000
    assert "at" not in after  # its thread has ended
    # with spans off the tick reads nothing of the caller
    fiber_tpu.init(telemetry_enabled=False, monitor_enabled=False)
    TIMESERIES.tick()
    assert tracing.SPANS.snapshot()[-1] is after


# ---------------------------------------------------------------------------
# watchdog rules (synthetic samples — exact edge semantics)
# ---------------------------------------------------------------------------


def test_throughput_drop_rule_fires_once_and_clears():
    dog = _fresh_watchdog(anomaly_drop_pct=0.5)
    for _ in range(6):
        dog.observe(_sample(tasks_per_s=100.0, inflight=10.0))
    assert dog.snapshot()["active"] == {}
    dog.observe(_sample(tasks_per_s=10.0, inflight=10.0))
    snap = dog.snapshot()
    assert "throughput_drop" in snap["active"]
    assert snap["total"] == 1
    # still collapsed next tick: the SAME incident, no second event
    dog.observe(_sample(tasks_per_s=10.0, inflight=10.0))
    assert dog.snapshot()["total"] == 1
    # the trailing baseline was frozen during the breach, so recovery
    # is judged against the HEALTHY rate and clears the anomaly
    dog.observe(_sample(tasks_per_s=95.0, inflight=10.0))
    assert "throughput_drop" not in dog.snapshot()["active"]
    rec = dog.snapshot()["recent"][0]
    assert rec["rule"] == "throughput_drop"
    assert "wall" in rec and "mono" in rec


def test_throughput_drop_needs_inflight_work():
    dog = _fresh_watchdog(anomaly_drop_pct=0.5)
    for _ in range(6):
        dog.observe(_sample(tasks_per_s=100.0, inflight=4.0))
    # the map finished: rate 0 with nothing in flight is idle, not sick
    dog.observe(_sample(tasks_per_s=0.0, inflight=0.0))
    assert dog.snapshot()["active"] == {}


def test_queue_growth_rule():
    dog = _fresh_watchdog(anomaly_queue_intervals=4)
    for depth in (1, 2, 3, 4):
        dog.observe(_sample(queue_depth=float(depth)))
    assert dog.snapshot()["active"] == {}      # needs N+1 points
    dog.observe(_sample(queue_depth=5.0))
    assert "queue_growth" in dog.snapshot()["active"]
    dog.observe(_sample(queue_depth=5.0))      # plateau: not growth
    assert "queue_growth" not in dog.snapshot()["active"]


def test_heartbeat_age_and_tx_queue_rules():
    dog = _fresh_watchdog(suspect_timeout=4.0, anomaly_tx_queue_mb=1.0)
    dog.observe(_sample(heartbeat_age_s=2.5,
                        tx_queue_bytes=float(2 << 20)))
    active = dog.snapshot()["active"]
    assert "heartbeat_age" in active           # 2.5 > 4.0 / 2
    assert "tx_queue_high" in active
    dog.observe(_sample(heartbeat_age_s=0.1, tx_queue_bytes=0.0))
    assert dog.snapshot()["active"] == {}


def test_store_disk_fill_rule(monkeypatch):
    dog = _fresh_watchdog(anomaly_disk_fill_pct=0.9)
    monkeypatch.setattr(monitormod, "_store_disk_usage",
                        lambda: (95 << 20, 100 << 20))
    dog.observe(_sample())
    assert "store_disk_fill" in dog.snapshot()["active"]
    monkeypatch.setattr(monitormod, "_store_disk_usage",
                        lambda: (10 << 20, 100 << 20))
    dog.observe(_sample())
    assert dog.snapshot()["active"] == {}


def test_anomalies_land_in_flight_recorder_and_registry():
    fiber_tpu.init()
    dog = _fresh_watchdog(suspect_timeout=4.0)
    before = telemetry.counter("monitor_anomalies").value(
        rule="heartbeat_age")
    dog.observe(_sample(heartbeat_age_s=3.9))
    events = [e for e in FLIGHT.snapshot() if e["plane"] == "monitor"]
    assert events and events[-1]["kind"] == "heartbeat_age"
    assert telemetry.counter("monitor_anomalies").value(
        rule="heartbeat_age") == before + 1


# ---------------------------------------------------------------------------
# dual-clock flight stamps (satellite: cross-process merge ordering)
# ---------------------------------------------------------------------------


def test_flight_events_carry_wall_and_monotonic():
    FLIGHT.record("pool", "submit", seq=1)
    ev = FLIGHT.snapshot()[-1]
    assert "ts" in ev and "mono" in ev
    assert abs(ev["ts"] - time.time()) < 5.0


def test_order_events_merges_on_wall_with_mono_tiebreak():
    events = [
        {"ts": 2.0, "mono": 9.0, "kind": "c"},
        {"ts": 1.0, "mono": 7.0, "kind": "b"},   # same wall, later mono
        {"ts": 1.0, "mono": 3.0, "kind": "a"},
        {"ts": 0.5, "kind": "legacy"},           # pre-stamp event
    ]
    assert [e["kind"] for e in order_events(events)] == \
        ["legacy", "a", "b", "c"]


def test_explain_load_events_merge_orders(tmp_path):
    from fiber_tpu.telemetry import explain

    path = tmp_path / "flight.json"
    path.write_text(json.dumps({"events": [
        {"ts": 5.0, "mono": 2.0, "plane": "pool", "kind": "later"},
        {"ts": 5.0, "mono": 1.0, "plane": "pool", "kind": "earlier"},
    ]}))
    kinds = [e["kind"] for e in explain.load_events(str(path))]
    assert kinds == ["earlier", "later"]


# ---------------------------------------------------------------------------
# chaos-driven rule triggers (the failure modes the rules exist for)
# ---------------------------------------------------------------------------


def _install_chaos(tmp_path, **knobs):
    return chaos.install(chaos.ChaosPlan(
        seed=SEED, token_dir=str(tmp_path / "tokens"), **knobs))


def test_chaos_slow_worker_raises_throughput_drop(tmp_path):
    """Both workers turn into chaos stragglers mid-map (alive and
    heartbeating — the health plane sees nothing): evals/s collapses
    against its trailing window and the watchdog must flag it."""
    plan = _install_chaos(tmp_path, slow_worker_after_chunks=6,
                          slow_worker_s=1.0, slow_worker_times=2)
    fiber_tpu.init(monitor_interval_s=0.1, anomaly_drop_pct=0.5)
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(24))
        out = pool.map(targets.sleep_echo, xs, chunksize=1)
        assert out == xs
    assert plan.spent("slow") == 2
    rules = {r["rule"] for r in WATCHDOG.snapshot()["recent"]}
    assert "throughput_drop" in rules
    kinds = {(e["plane"], e["kind"]) for e in FLIGHT.snapshot()}
    assert ("monitor", "throughput_drop") in kinds


def test_chaos_partition_raises_heartbeat_age(tmp_path):
    """A partition severs one worker's result stream — results AND
    heartbeats. The watchdog flags the growing silence when it crosses
    suspect_timeout/2, HALF a deadline before the failure detector
    declares and reclaims — the early-warning line; the declaration
    then resubmits the severed chunks and the map still completes."""
    plan = _install_chaos(tmp_path, partition_after=6, partition_s=3.0,
                          partition_times=1)
    fiber_tpu.init(monitor_interval_s=0.1, heartbeat_interval=0.2,
                   suspect_timeout=1.5)
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(60))
        out = pool.map(targets.sleep_echo, xs, chunksize=2)
        assert out == xs
        suspected = pool._detector.suspected_total
    assert plan.spent("partition") == 1
    rules = [r["rule"] for r in WATCHDOG.snapshot()["recent"]]
    assert "heartbeat_age" in rules
    # the watchdog's flag came BEFORE (or without) the declaration —
    # the detector may or may not have fired depending on timing, but
    # the anomaly always does
    first = next(r for r in WATCHDOG.snapshot()["recent"]
                 if r["rule"] == "heartbeat_age")
    assert first["age_s"] >= 1.5 / 2.0
    assert suspected >= 0  # map completed either way


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------


def test_profiler_off_by_default_and_knob_follows_refresh():
    fiber_tpu.init()
    assert config.get().profiler_hz == 0.0
    assert not profmod.PROFILER.active
    fiber_tpu.init(profiler_hz=150.0)
    assert profmod.PROFILER.active
    fiber_tpu.init()
    assert not profmod.PROFILER.active


def test_folded_text_roundtrip_and_top_frames():
    folded = {"main;work;inner": 7, "main;idle": 3}
    assert profmod.parse_folded(profmod.folded_text(folded)) == folded
    top = profmod.top_frames(folded, 2)
    assert top == [("inner", 7), ("idle", 3)]
    inclusive = dict(profmod.top_frames(folded, 5, self_time=False))
    assert inclusive["main"] == 10
    with pytest.raises(ValueError):
        profmod.parse_folded("no trailing count here")


def test_top_frames_exclude_parked_threads():
    """A wall-clock sampler sees every parked service thread; hot-frame
    rankings must not crown `wait (threading.py)` over user code."""
    folded = {
        "run (threading.py:1016);wait (threading.py:320)": 900,
        "serve (sock.py:4);accept (socket.py:286)": 400,
        "main (app.py:1);hot_loop (app.py:9)": 50,
    }
    top = profmod.top_frames(folded, 3)
    assert top[0] == ("hot_loop (app.py:9)", 50)
    assert all("wait (" not in f and "accept (" not in f
               for f, _ in top)
    # an all-idle profile still reports something rather than nothing
    idle_only = {"run (t.py:1);wait (threading.py:320)": 9}
    assert profmod.top_frames(idle_only, 1)[0][1] == 9


def test_profile_chrome_trace_view():
    folded = {"a;b": 4, "a;c": 6}
    doc = profmod.profile_chrome_trace(folded, hz=100.0)
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_name = {e["name"]: e for e in events}
    # the parent frame spans its children; 1 sample = 10ms = 1e4 us
    assert by_name["a"]["dur"] == pytest.approx(1e5)
    assert by_name["b"]["dur"] + by_name["c"]["dur"] == \
        pytest.approx(1e5)
    json.dumps(doc)  # serializable


def test_profiler_folded_roundtrip_through_real_map(tmp_path):
    """Workers run the sampler (profiler_hz ships in the spawn prep),
    drain folded stacks onto the result stream, and the master's
    aggregate names the worker-side busy frame."""
    fiber_tpu.init(profiler_hz=200.0)
    with fiber_tpu.Pool(2) as pool:
        pool.map(targets.spin_for, [0.08] * 16, chunksize=1)
        folded = pool.profiles()
        out = pool.profile_dump(str(tmp_path / "prof.folded"))
        chrome = pool.profile_dump(str(tmp_path / "prof.json"),
                                   chrome=True)
    assert folded, "no samples reached the master"
    # worker-shipped stacks are keyed host:pid in the aggregate
    sources = profmod.AGGREGATE.snapshot()
    assert sources, "workers shipped no profile frames"
    merged_workers = profmod.merge_folded(*sources.values())
    assert any("spin_for" in stack for stack in merged_workers), \
        sorted(merged_workers)[:5]
    reloaded = profmod.load_folded(out)
    assert reloaded == folded
    with open(chrome) as fh:
        assert json.load(fh)["traceEvents"]


# ---------------------------------------------------------------------------
# collection plane: agent ops, backend sweeps, CLI verbs
# ---------------------------------------------------------------------------


@pytest.fixture
def embedded_agent(tmp_path):
    from fiber_tpu.host_agent import HostAgent

    agent = HostAgent(0, bind="127.0.0.1", staging_root=str(tmp_path))
    t = threading.Thread(target=agent.serve_forever, daemon=True)
    t.start()
    yield agent
    agent.stop()


def test_agent_monitor_and_profile_ops(embedded_agent):
    from fiber_tpu.backends.tpu import AgentClient

    fiber_tpu.init(monitor_interval_s=0.1)
    client = AgentClient("127.0.0.1", embedded_agent.port)
    try:
        pull = client.call("monitor_snapshot", 16)
        assert pull["host"] and pull["pid"] == os.getpid()
        assert pull["timeseries"]["samples"] >= 1  # fresh sample taken
        assert "active" in pull["anomalies"]
        prof = client.call("profile_dump", 0.2, 150.0)
        assert prof["folded"], "burst profile sampled nothing"
        assert all(isinstance(v, int) for v in prof["folded"].values())
    finally:
        client.close()


def test_local_backend_timeseries_and_profiles():
    from fiber_tpu.backends.local import LocalBackend

    fiber_tpu.init(monitor_interval_s=0.1)
    backend = LocalBackend()
    ts = backend.cluster_timeseries()
    assert set(ts) == {"local"}
    assert "timeseries" in ts["local"] and "anomalies" in ts["local"]
    prof = backend.collect_profiles(seconds=0.1, hz=150.0)
    assert prof["local"]["folded"]


def test_top_cli_renders_live_pool_with_chaos_anomaly(
        tmp_path, embedded_agent, capsys):
    """The acceptance path: a real pool in this process (served to the
    CLI through an embedded host agent, the sim-host pattern), chaos
    slowing every worker mid-map, and `fiber-tpu top` rendering the
    host row with live rates plus the watchdog's anomaly flag."""
    from fiber_tpu import cli

    plan = _install_chaos(tmp_path, slow_worker_after_chunks=6,
                          slow_worker_s=1.0, slow_worker_times=2)
    fiber_tpu.init(monitor_interval_s=0.1, anomaly_drop_pct=0.5)
    hosts = f"127.0.0.1:{embedded_agent.port}"
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(24))
        result = pool.map_async(targets.sleep_echo, xs, chunksize=1)
        # wait for the watchdog to flag the chaos-induced collapse,
        # then render a frame WHILE the map is degraded
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if any(r["rule"] == "throughput_drop"
                   for r in WATCHDOG.snapshot()["recent"]):
                break
            time.sleep(0.1)
        assert cli.main(["top", "--hosts", hosts, "--iterations", "1",
                         "--no-clear"]) == 0
        assert result.get(timeout=120) == xs
    assert plan.spent("slow") == 2
    out = capsys.readouterr().out
    assert "EVALS/S" in out and hosts in out
    assert "throughput_drop" in out          # flagged in the frame
    # the table row itself carries live data (submitted tasks counted)
    assert "DOWN" not in out
    # --json mode ships the raw snapshots
    assert cli.main(["top", "--hosts", hosts, "--iterations", "1",
                     "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc[hosts]["timeseries"]["samples"] >= 1


def test_metrics_watch_prints_rates(embedded_agent, capsys):
    from fiber_tpu import cli

    fiber_tpu.init()
    counter = telemetry.counter("pool_tasks_completed")
    stop = threading.Event()

    def bump():
        while not stop.wait(0.05):
            counter.inc(10)

    t = threading.Thread(target=bump, daemon=True)
    t.start()
    try:
        rc = cli.main(["metrics", "--hosts",
                       f"127.0.0.1:{embedded_agent.port}",
                       "--watch", "0.2", "--count", "2"])
    finally:
        stop.set()
        t.join()
    assert rc == 0
    out = capsys.readouterr().out
    assert "pool_tasks_completed" in out
    assert "/s)" in out                       # rendered as a rate


def test_profile_cli_script_mode(tmp_path, capsys, monkeypatch):
    from fiber_tpu import cli

    script = tmp_path / "busy.py"
    script.write_text(
        "import time\n"
        "deadline = time.perf_counter() + 0.4\n"
        "while time.perf_counter() < deadline:\n"
        "    sum(i * i for i in range(300))\n")
    out = str(tmp_path / "prof.folded")
    chrome = str(tmp_path / "prof.json")
    monkeypatch.setenv("FIBER_PROFILER_HZ", "0")  # sandbox the env write
    # options precede the script: script_args is REMAINDER (like `run`)
    assert cli.main(["profile", "--out", out, "--chrome", chrome,
                     "--hz", "150", str(script)]) == 0
    folded = profmod.load_folded(out)
    assert folded and any("busy.py" in stack for stack in folded)
    with open(chrome) as fh:
        assert json.load(fh)["traceEvents"]
    assert "sample(s)" in capsys.readouterr().err


def test_profile_cli_hosts_mode(tmp_path, embedded_agent, capsys):
    from fiber_tpu import cli

    out = str(tmp_path / "agents.folded")
    assert cli.main(["profile", "--hosts",
                     f"127.0.0.1:{embedded_agent.port}",
                     "--seconds", "0.2", "--hz", "150",
                     "--out", out]) == 0
    folded = profmod.load_folded(out)
    assert folded
    assert all(stack.startswith("host:127.0.0.1:") for stack in folded)


def test_explain_compute_verdict_names_profile_frames(tmp_path, capsys):
    """Satellite: primary=compute + a profile present => the verdict
    appends the top collapsed frames instead of stopping at
    'compute'."""
    from fiber_tpu import cli
    from fiber_tpu.telemetry import explain

    now = time.time()
    spans = [
        {"name": "worker.execute", "trace": "t1", "ts": now + i,
         "dur": 1.0, "seq": 1, "host": "h", "pid": 1}
        for i in range(4)
    ]
    profile = {"main (app.py:1);hot_loop (app.py:9)": 90,
               "main (app.py:1);io_wait (app.py:20)": 10}
    verdict = explain.explain_trace(spans, [], profile=profile)
    assert verdict["primary"] == "compute"
    frames = verdict["evidence"]["compute_frames"]
    assert frames[0]["frame"] == "hot_loop (app.py:9)"
    assert len(frames) <= 5
    rendered = explain.render(verdict)
    assert "hot_loop (app.py:9)" in rendered
    # CLI path: --profile rides beside the trace artifact
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps(spans))
    prof = tmp_path / "prof.folded"
    prof.write_text(profmod.folded_text(profile))
    assert cli.main(["explain", str(trace),
                     "--profile", str(prof)]) == 0
    out = capsys.readouterr().out
    assert "top sampled frames" in out and "hot_loop" in out


def test_pool_timeseries_surface():
    fiber_tpu.init(monitor_interval_s=0.1)
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(32))
        assert pool.map(targets.sleep_echo, xs, chunksize=2) == xs
        time.sleep(0.3)
        ts = pool.timeseries()
    assert ts["pid"] == os.getpid()
    series = ts["timeseries"]["series"]
    assert "tasks_completed" in series
    assert series["tasks_completed"][-1][2] >= 32
    assert "active" in ts["anomalies"]
    assert isinstance(ts["heartbeat_ages"], dict)
