"""Telemetry plane (docs/observability.md): registry semantics, trace
propagation through a real Pool.map, Chrome trace / Prometheus export,
the snapshot op, and the chaos claim that resubmitted tasks keep their
trace id."""

import json
import threading
import time

import pytest

import fiber_tpu
from fiber_tpu import telemetry
from fiber_tpu.telemetry import export, tracing
from fiber_tpu.telemetry.metrics import (
    MAX_LABEL_SETS,
    MetricsRegistry,
    merge_snapshots,
)
from tests import targets


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Each test starts with an empty span buffer and ends with config
    overrides dropped (fiber_tpu.init re-syncs telemetry enablement)."""
    tracing.SPANS.clear()
    yield
    fiber_tpu.init()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_and_gauge_labels():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests")
    c.inc()
    c.inc(2, op="get")
    c.inc(op="get")
    assert c.value() == 1
    assert c.value(op="get") == 3
    g = reg.gauge("depth")
    g.set(5)
    g.dec(2)
    assert g.value() == 3
    snap = reg.snapshot()
    assert snap["reqs"]["type"] == "counter"
    assert snap["reqs"]["series"]["op=get"] == 3
    # re-registration returns the same instrument; kind conflicts raise
    assert reg.counter("reqs") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("reqs")


def test_histogram_fixed_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(5.605)
    series = reg.snapshot()["lat"]["series"][""]
    # per-bucket counts: <=0.01, <=0.1, <=1.0, above
    assert series[:4] == [1, 2, 1, 1]
    assert reg.snapshot()["lat"]["buckets"] == [0.01, 0.1, 1.0]


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("x")
    c.inc(100)
    reg.histogram("h").observe(1.0)
    reg.gauge("g").set(3)
    assert c.value() == 0
    assert all(not e["series"] for e in reg.snapshot().values())


def test_label_sets_are_bounded():
    reg = MetricsRegistry()
    c = reg.counter("wild")
    for i in range(MAX_LABEL_SETS + 50):
        c.inc(key=f"id-{i}")
    series = reg.snapshot()["wild"]["series"]
    assert len(series) == MAX_LABEL_SETS + 1
    assert series["other=overflow"] == 50


def test_merge_snapshots_labels_by_host():
    a = MetricsRegistry()
    a.counter("ops").inc(3)
    b = MetricsRegistry()
    b.counter("ops").inc(4, op="get")
    merged = merge_snapshots({"h1:1": a.snapshot(), "h2:2": b.snapshot()})
    assert merged["ops"]["series"]["host=h1:1"] == 3
    assert merged["ops"]["series"]["host=h2:2,op=get"] == 4


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_prometheus_exposition_renders_and_parses():
    reg = MetricsRegistry()
    reg.counter("pool_tasks", "tasks").inc(7)
    reg.gauge("depth").set(2, queue="tasks")
    reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.05)
    text = export.prometheus_text(reg.snapshot())
    assert "# TYPE fiber_pool_tasks_total counter" in text
    assert "# HELP fiber_pool_tasks_total tasks" in text
    samples = export.parse_prometheus_text(text)
    assert samples["fiber_pool_tasks_total"] == 7
    assert samples['fiber_depth{queue="tasks"}'] == 2
    assert samples['fiber_lat_bucket{le="0.1"}'] == 1
    assert samples['fiber_lat_bucket{le="+Inf"}'] == 1
    assert samples["fiber_lat_count"] == 1


def test_span_records_epoch_nanoseconds_and_record_stores_a_finished_one():
    """A span carries its start and end as integer nanoseconds of the
    epoch beside ``ts``/``dur``, so it can be laid against JAX's own
    epoch-stamped events; ``record`` stores a region someone else timed
    as a child of the ambient span."""
    t0 = time.time_ns()
    with tracing.span("unit.timed", k=1) as sp:
        time.sleep(0.002)
        inner = tracing.record("unit.finished", t0 + 5, t0 + 1_000_005,
                               fun_name="f")
    (stored,) = [s for s in tracing.SPANS.snapshot()
                 if s["name"] == "unit.timed"]
    assert stored is sp and sp["k"] == 1
    assert isinstance(sp["start_ns"], int) and isinstance(sp["end_ns"], int)
    assert t0 <= sp["start_ns"] < sp["end_ns"] <= time.time_ns()
    assert sp["ts"] == pytest.approx(sp["start_ns"] / 1e9)
    assert sp["end_ns"] - sp["start_ns"] >= 2_000_000
    assert sp["dur"] == pytest.approx(
        (sp["end_ns"] - sp["start_ns"]) / 1e9, abs=1e-3)
    assert inner["parent"] == sp["span"] and inner["trace"] == sp["trace"]
    assert (inner["start_ns"], inner["end_ns"]) == (t0 + 5, t0 + 1_000_005)
    assert inner["dur"] == pytest.approx(1e-3) and inner["fun_name"] == "f"
    assert inner in tracing.SPANS.snapshot()
    # Chrome export places it by the nanoseconds
    doc = export.chrome_trace([inner])
    event = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert event["ts"] == pytest.approx((t0 + 5) / 1e3)
    # telemetry off: nothing is recorded
    fiber_tpu.init(telemetry_enabled=False)
    assert tracing.record("unit.finished", t0, t0 + 1) is None


def test_span_lands_in_store_and_in_a_capture_under_the_same_name(
        tmp_path):
    """With jax loaded a span is also a ``TraceAnnotation`` of the same
    name carrying the span id, so a profiler capture (anyone's) holds
    it in its host plane, on the device ops' own clock."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    out = str(tmp_path / "capture")
    jax.profiler.start_trace(out)
    try:
        with tracing.span("unit.captured") as sp:
            jnp.arange(32.0).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert sp in tracing.SPANS.snapshot()
    (path,) = glob.glob(out + "/plugins/profile/*/*.xplane.pb")
    found = [e for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "unit.captured"]
    assert len(found) == 1
    assert dict(found[0].stats)["span"] == sp["span"]
    # the capture's duration is the span's, to the clock reads between
    assert found[0].duration_ns == pytest.approx(
        sp["end_ns"] - sp["start_ns"], abs=2_000_000)


_NO_JAX_PROBE = (
    "import sys; sys.path.insert(0, {repo!r}); "
    "import fiber_tpu.telemetry.tracing as tracing; "
    "import fiber_tpu.utils.profiling as profiling; "
    "assert profiling.annotate is tracing.span; "
    "ctx = tracing.span('probe'); sp = ctx.__enter__(); "
    "ctx.__exit__(None, None, None); "
    "assert sp['end_ns'] >= sp['start_ns'] > 0, sp; "
    "assert tracing.SPANS.snapshot()[-1] is sp; "
    "print('jax' in sys.modules)"
)


def test_span_primitive_does_not_import_jax():
    """Lite workers and host agents must not pay for jax: the span
    primitive (and ``annotate``, which is the same call) looks jax up
    in ``sys.modules`` and never imports it."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _NO_JAX_PROBE.format(repo=repo)],
        capture_output=True, text=True, timeout=120, cwd=repo)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split()[-1] == "False"


def test_one_span_primitive_under_fiber_tpu():
    """The only ``TraceAnnotation(`` call of the package is the span
    primitive's."""
    import os

    root = os.path.dirname(os.path.abspath(fiber_tpu.__file__))
    holders = []
    for base, _dirs, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path) as fh:
                    if "TraceAnnotation(" in fh.read():
                        holders.append(os.path.relpath(path, root))
    assert holders in ([], ["telemetry/tracing.py"]), holders


def test_chrome_trace_json_is_valid(tmp_path):
    with tracing.span("unit.root") as root:
        with tracing.span("unit.child"):
            pass
    assert root["trace"]
    path = str(tmp_path / "trace.json")
    export.write_chrome_trace(path, tracing.SPANS.snapshot())
    with open(path) as fh:
        doc = json.load(fh)
    # (a sampler tick may land in the store beside them)
    events = [e for e in doc["traceEvents"]
              if e["ph"] == "X" and e["name"].startswith("unit.")]
    assert {e["name"] for e in events} == {"unit.root", "unit.child"}
    for event in events:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert key in event
    child = next(e for e in events if e["name"] == "unit.child")
    assert child["args"]["parent"] == root["span"]
    # metadata names the host row
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in doc["traceEvents"])


def test_span_ring_buffer_bounds_memory():
    store = tracing.SpanStore(capacity=8)
    for i in range(20):
        store.add({"name": f"s{i}"})
    assert len(store) == 8
    assert store.dropped == 12
    assert store.snapshot()[0]["name"] == "s12"


# ---------------------------------------------------------------------------
# the tentpole acceptance: one trace id spans master and workers
# ---------------------------------------------------------------------------


def _await_spans(name, n, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = [s for s in tracing.SPANS.snapshot() if s["name"] == name]
        if len(got) >= n:
            return got
        time.sleep(0.05)
    return [s for s in tracing.SPANS.snapshot() if s["name"] == name]


def test_pool_map_trace_spans_master_and_workers(tmp_path):
    """Acceptance: a real Pool.map under trace_sample_rate=1 yields ONE
    trace id covering the master-side serialize span and worker-side
    execute spans (recorded in worker processes — different pids —
    and shipped back on the result stream), and trace_dump writes valid
    Chrome trace-event JSON containing them."""
    import os

    fiber_tpu.init(trace_sample_rate=1.0)
    with fiber_tpu.Pool(2) as pool:
        out = pool.map(targets.square, range(64), chunksize=4)
        assert out == [x * x for x in range(64)]
        execute = _await_spans("worker.execute", 16)
        path = str(tmp_path / "pool_trace.json")
        assert pool.trace_dump(path) == path
    serialize = [s for s in tracing.SPANS.snapshot()
                 if s["name"] == "pool.serialize"]
    assert len(serialize) == 1
    assert len(execute) == 16
    trace_id = serialize[0]["trace"]
    assert {s["trace"] for s in execute} == {trace_id}
    # worker spans were recorded in OTHER processes and parented on the
    # master's serialize span
    assert all(s["pid"] != os.getpid() for s in execute)
    assert {s["parent"] for s in execute} == {serialize[0]["span"]}
    with open(path) as fh:
        doc = json.load(fh)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"pool.serialize", "worker.execute"} <= names


def test_unsampled_map_records_no_spans():
    fiber_tpu.init(trace_sample_rate=0.0)
    with fiber_tpu.Pool(2) as pool:
        assert pool.map(targets.square, range(16)) == \
            [x * x for x in range(16)]
        assert pool.stats()["tasks_completed"] == 16
    assert tracing.SPANS.snapshot() == []


def test_pool_stats_covers_phases():
    """Satellite: global_timer coverage beyond pool.serialize, surfaced
    through Pool.stats() (count/total/mean per section)."""
    from fiber_tpu.utils.profiling import global_timer

    global_timer.reset()
    with fiber_tpu.Pool(2) as pool:
        pool.map(targets.square, range(32), chunksize=4)
        stats = pool.stats()
    for section in ("pool.serialize", "pool.dispatch",
                    "pool.deserialize", "pool.result_wait"):
        assert section in stats["timers"], section
        assert stats["timers"][section][0] >= 1
    assert stats["tasks_submitted"] == 32
    assert stats["tasks_completed"] == 32
    assert stats["outstanding"] == 0
    # the same sections reach the registry's histogram (one surface)
    hist = telemetry.REGISTRY.snapshot()["timer_seconds"]
    assert any("section=pool.serialize" in k for k in hist["series"])


def test_pool_metrics_and_prometheus_agree():
    """Pool.metrics() and the Prometheus endpoint render the same
    counters (the acceptance's 'same counters' leg, master side)."""
    with fiber_tpu.Pool(2) as pool:
        pool.map(targets.square, range(8))
        snap = pool.metrics()
    submitted = snap["pool_tasks_submitted"]["series"][""]
    samples = export.parse_prometheus_text(
        export.prometheus_text(snap))
    assert samples["fiber_pool_tasks_submitted_total"] == submitted
    assert "fiber_transport_bytes_tx_total" in samples
    assert samples["fiber_transport_frames_rx_total"] > 0


# ---------------------------------------------------------------------------
# snapshot op / cluster metrics / CLI / endpoint
# ---------------------------------------------------------------------------


def test_local_backend_cluster_metrics():
    """Satellite: the snapshot op over the local backend — same shape
    as the tpu backend's per-host map, one 'local' host."""
    from fiber_tpu.backends.local import LocalBackend

    telemetry.counter("unit_local_probe").inc()
    snap = LocalBackend().cluster_metrics()
    assert set(snap) == {"local"}
    assert snap["local"]["enabled"] is True
    assert snap["local"]["metrics"]["unit_local_probe"]["series"][""] == 1
    assert "timers" in snap["local"]


def test_agent_snapshot_cli_and_endpoint_render_same_counters(
        tmp_path, capsys):
    """Acceptance: `fiber-tpu metrics` and the authenticated Prometheus
    endpoint expose the SAME counters the agent's telemetry_snapshot op
    reports (all three read one process registry here: the agent and
    the endpoint are embedded)."""
    from multiprocessing.connection import Client

    from fiber_tpu import cli
    from fiber_tpu.host_agent import HostAgent, cluster_authkey

    agent = HostAgent(0, bind="127.0.0.1", staging_root=str(tmp_path))
    t = threading.Thread(target=agent.serve_forever, daemon=True)
    t.start()
    server = telemetry.serve_metrics()
    try:
        hosts = f"127.0.0.1:{agent.port}"
        # one ping via the CLI path bumps agent_ops{op=ping}
        assert cli.main(["status", "--hosts", hosts]) == 0
        capsys.readouterr()

        assert cli.main(["metrics", "--hosts", hosts]) == 0
        human = capsys.readouterr().out
        assert "agent_ops{op=ping}" in human

        assert cli.main(["metrics", "--hosts", hosts, "--prom"]) == 0
        prom_cli = export.parse_prometheus_text(capsys.readouterr().out)
        key = ('fiber_agent_ops_total'
               f'{{host="{hosts}",op="ping"}}')
        assert prom_cli[key] >= 1

        conn = Client(("127.0.0.1", server.port),
                      authkey=cluster_authkey())
        try:
            conn.send(("metrics",))
            ok, text = conn.recv()
            assert ok
            endpoint = export.parse_prometheus_text(text)
            assert endpoint['fiber_agent_ops_total{op="ping"}'] == \
                prom_cli[key]
            conn.send(("snapshot",))
            ok, snap = conn.recv()
            assert ok and "metrics" in snap
        finally:
            conn.close()
    finally:
        server.stop()
        agent.stop()


def test_metrics_cli_down_host(capsys):
    from fiber_tpu import cli

    assert cli.main(["metrics", "--hosts", "127.0.0.1:1"]) == 1
    assert "DOWN" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# chaos: resubmitted tasks keep their trace id
# ---------------------------------------------------------------------------


def test_resubmitted_chunks_keep_trace_id(tmp_path):
    """A worker hard-killed mid-map forces resubmission; the resent
    chunks carry the ORIGINAL envelope (trace context included), so
    every execute span of the map — including post-resubmit ones —
    shares the one trace id."""
    import os

    from fiber_tpu.testing import chaos

    seed = int(os.environ.get("FIBER_CHAOS_SEED", "7"))
    plan = chaos.install(chaos.ChaosPlan(
        seed=seed, token_dir=str(tmp_path / "tokens"),
        kill_after_chunks=2, kill_times=1))
    try:
        fiber_tpu.init(trace_sample_rate=1.0)
        with fiber_tpu.Pool(2) as pool:
            xs = list(range(120))
            assert pool.map(targets.square, xs, chunksize=4) == \
                [x * x for x in xs]
            execute = _await_spans("worker.execute", 30)
            stats = pool.stats()
    finally:
        chaos.uninstall()
    assert plan.spent("kill") == 1
    assert stats["chunks_resubmitted"] >= 1
    serialize = [s for s in tracing.SPANS.snapshot()
                 if s["name"] == "pool.serialize"]
    assert len(serialize) == 1
    assert {s["trace"] for s in execute} == {serialize[0]["trace"]}
    # the kill + resubmission is visible in the health/pool metrics too
    assert telemetry.REGISTRY.snapshot()[
        "pool_chunks_resubmitted"]["series"][""] >= 1


def test_concurrent_wdrr_maps_trace_export_with_speculation(tmp_path):
    """Satellite (ISSUE 6): trace export under two concurrently active
    WDRR-interleaved maps with straggler speculation armed — the Chrome
    artifact stays valid JSON, every execute span (speculative
    duplicates included) carries its OWN map's trace id, and per-map
    span counts reconcile with the scheduler's decision counters:
    chunks <= executes <= chunks + speculations (each speculative
    duplicate that actually ran adds one execute span to the original
    trace, never a new trace)."""
    import os

    from fiber_tpu.testing import chaos

    seed = int(os.environ.get("FIBER_CHAOS_SEED", "7"))
    plan = chaos.install(chaos.ChaosPlan(
        seed=seed, token_dir=str(tmp_path / "tokens"),
        slow_worker_after_chunks=1, slow_worker_s=0.5,
        slow_worker_times=1))
    try:
        fiber_tpu.init(trace_sample_rate=1.0, speculation_enabled=True,
                       speculation_quantile=2.0)
        with fiber_tpu.Pool(4) as pool:
            pool.map(targets.identity, range(4))  # spin-up barrier
            r1 = pool.map_async(targets.sleep_echo, range(40),
                                chunksize=2, priority=3.0)
            r2 = pool.map_async(targets.sleep_echo, range(40),
                                chunksize=2, priority=1.0)
            assert r1.get(120) == list(range(40))
            assert r2.get(120) == list(range(40))
            execute = _await_spans("worker.execute", 2 + 20 + 20)
            speculations = pool._sched.decisions["speculate"]
            path = str(tmp_path / "wdrr_trace.json")
            pool.trace_dump(path)
    finally:
        chaos.uninstall()
    assert plan.spent("slow") == 1
    serialize = {s["seq"]: s for s in tracing.SPANS.snapshot()
                 if s["name"] == "pool.serialize"}
    map_seqs = [seq for seq, s in serialize.items() if s["items"] == 40]
    assert len(map_seqs) == 2
    total_executes = 0
    for seq in map_seqs:
        mine = [s for s in execute if s["seq"] == seq]
        total_executes += len(mine)
        # one trace id per map, speculative duplicates included
        assert {s["trace"] for s in mine} == {serialize[seq]["trace"]}
        assert len(mine) >= 20  # every chunk ran at least once
    assert total_executes <= 40 + speculations
    # the Chrome artifact is valid and complete
    with open(path) as fh:
        doc = json.load(fh)
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    for event in events:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert key in event
    dumped_execs = [e for e in events if e["name"] == "worker.execute"
                    and e["args"].get("seq") in map_seqs]
    assert len(dumped_execs) == total_executes


# ---------------------------------------------------------------------------
# structured log context
# ---------------------------------------------------------------------------


def test_log_records_carry_trace_context(tmp_path):
    """Satellite: the logging ContextFilter stamps host/job/trace onto
    every record (dash when absent), so one trace id greps across the
    cluster's log files."""
    import logging

    from fiber_tpu.utils import logging as flogging

    fiber_tpu.init(log_file=str(tmp_path / "ctx.log"))
    logger = flogging.get_logger()
    logger.info("outside any trace")
    with tracing.trace_context("feedface00000001"):
        logger.info("inside the trace")
    for handler in logger.handlers:
        handler.flush()
    path = next(tmp_path.glob("ctx.log.*"))
    lines = path.read_text().splitlines()
    outside = next(ln for ln in lines if "outside any trace" in ln)
    inside = next(ln for ln in lines if "inside the trace" in ln)
    assert " -]" in outside  # no trace -> dash placeholder
    assert "feedface00000001" in inside
    assert tracing.host_id() in inside
    # plain logging API still works for records missing the filter
    assert logging.getLogger("fiber_tpu").filters
