"""Accounting plane: per-map/per-tenant cost attribution, exactly-once
billing under chaos, soft budgets, the collection plane (worker cost
frames, agent op, backends, CLI) and the per-metric label-bound fix
(docs/observability.md "Resource accounting")."""

import json
import os
import time

import pytest

import fiber_tpu
from fiber_tpu import config
from fiber_tpu.store import ledger as ledgermod
from fiber_tpu.telemetry import accounting
from fiber_tpu.telemetry.accounting import (
    COSTS,
    OVERHEAD_KEY,
    CostBudget,
    CostLedger,
    combine,
    key_str,
    parse_key,
    wire_size,
)
from fiber_tpu.telemetry.flightrec import FLIGHT
from fiber_tpu.telemetry.metrics import MetricsRegistry
from fiber_tpu.telemetry.monitor import WATCHDOG
from fiber_tpu.testing import chaos
from tests import targets

SEED = int(os.environ.get("FIBER_CHAOS_SEED", "7"))


@pytest.fixture(autouse=True)
def _accounting_isolation():
    """Clean ledger/watchdog state per test; config overrides dropped."""
    COSTS.clear()
    WATCHDOG.clear()
    FLIGHT.clear()
    yield
    chaos.uninstall()
    fiber_tpu.init()
    COSTS.clear()
    WATCHDOG.clear()


def _wait(pred, timeout=15.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# ledger semantics
# ---------------------------------------------------------------------------


def test_charge_ambient_and_overhead_bucket():
    led = CostLedger()
    key = ("t", "job", "m1")
    led.charge(key, tasks=3, cpu_s=0.5)
    led.bill_ambient(wire_rx=100)          # no ambient key -> overhead
    with led.context(key):
        led.bill_ambient(store_fetch_bytes=42)
    assert led.vector(key) == {"tasks": 3.0, "cpu_s": 0.5,
                               "store_fetch_bytes": 42.0}
    assert led.vector(OVERHEAD_KEY) == {"wire_rx": 100.0}
    # per-key + overhead always sum to the totals — the reconciliation
    # invariant (untaggable traffic is explicit, never dropped)
    assert led.totals()["wire_rx"] == 100.0
    assert led.totals()["tasks"] == 3.0


def test_unknown_cost_field_raises():
    led = CostLedger()
    with pytest.raises(ValueError, match="unknown cost field"):
        led.charge(("t", "j", "m"), typo_bytes=1)


def test_disabled_ledger_is_noop():
    led = CostLedger()
    led.enabled = False
    led.charge(("t", "j", "m"), tasks=1)
    led.bill_ambient(wire_rx=5)
    assert led.snapshot()["costs"] == {}
    assert led.revision == 0


def test_key_str_roundtrip_and_wire_size():
    key = ("tenant-a", "job.b", "m17")
    assert parse_key(key_str(key)) == key
    assert parse_key("short") == ("short", "-", "-")
    # framing boundary: 8-byte length header + 1-byte type tag
    assert wire_size(100) == 109


def test_combine_takes_each_field_from_its_authoritative_side():
    master = {"tasks": 10.0, "wire_tx": 500.0, "cpu_s": 99.0}
    workers = {"cpu_s": 2.5, "tasks_executed": 12.0, "wire_tx": 777.0}
    total = combine(master, workers)
    # wire/tasks from the master, cpu from the workers — the shared
    # traffic both sides observed is never double-billed
    assert total["tasks"] == 10.0
    assert total["wire_tx"] == 500.0
    assert total["cpu_s"] == 2.5
    assert total["tasks_executed"] == 12.0


def test_budget_violation_math():
    b = CostBudget(cpu_s=1.0, wire_mb=1.0, tasks=10)
    assert b.violations({"cpu_s": 0.5, "wire_tx": 0.0}) == []
    viols = b.violations({"cpu_s": 2.0,
                          "wire_tx": 3 << 20, "wire_rx": 0.0,
                          "tasks": 11.0})
    assert {v[0] for v in viols} == {"cpu_s", "wire_mb", "tasks"}


def test_budget_breach_is_edge_triggered_and_clears_on_release():
    key = ("t", "budget-job", "m9")
    COSTS.set_budget(key, CostBudget(cpu_s=0.1))
    COSTS.charge(key, cpu_s=0.2)   # breach fires
    COSTS.charge(key, cpu_s=0.2)   # still breached: no second edge
    snap = WATCHDOG.snapshot()
    assert "budget_exceeded" in snap["active"]
    assert sum(1 for r in snap["recent"]
               if r["rule"] == "budget_exceeded") == 1
    assert any(e["kind"] == "budget_exceeded"
               for e in FLIGHT.snapshot() if e["plane"] == "monitor")
    COSTS.release_key(key)
    assert "budget_exceeded" not in WATCHDOG.snapshot()["active"]


def test_job_record_write_read_roundtrip(tmp_path):
    fiber_tpu.init(cost_dir=str(tmp_path / "costs"))
    report = accounting.build_report(("t", "jobx", "m1"),
                                     {"tasks": 4.0, "wire_tx": 100.0},
                                     {"cpu_s": 0.5},
                                     CostBudget(cpu_s=0.1))
    path = accounting.write_job_record("jobx", report)
    assert path and os.path.exists(path)
    record = accounting.read_job_record("jobx")
    assert record["total"]["tasks"] == 4.0
    assert record["budget_violations"][0]["limit"] == "cpu_s"
    rendered = accounting.render_report(record)
    assert "BUDGET EXCEEDED" in rendered and "jobx" in rendered
    assert accounting.read_job_record("no-such-job") is None


# ---------------------------------------------------------------------------
# metrics label-bound fix (satellite): per-metric override + LRU
# eviction of completed-job series
# ---------------------------------------------------------------------------


def test_metric_label_bound_override_and_retire_keeps_live_jobs():
    """A 100-job sequence against a bound-8 metric: retiring each
    completed job's series frees its slot, so the LIVE job's series
    survives intact instead of folding into other=overflow."""
    reg = MetricsRegistry(enabled=True)
    m = reg.counter("jobs_done", max_label_sets=8)
    m.inc(7, job="live")            # a long-running job, never retired
    for i in range(100):
        m.inc(job=f"j{i}")
        m.inc(7, job="live")
        reg.retire_series(job=f"j{i}")   # job i completed
    series = m._snapshot_series()
    assert series["job=live"] == 7 * 101     # intact, never folded
    assert "other=overflow" not in series    # retired slots absorbed all
    assert len(series) <= 8


def test_metric_without_retire_still_folds_to_overflow():
    reg = MetricsRegistry(enabled=True)
    m = reg.counter("unbounded_labels", max_label_sets=4)
    for i in range(10):
        m.inc(job=f"j{i}")
    series = m._snapshot_series()
    assert series.get("other=overflow") == 6.0
    assert len(series) == 5  # 4 live + overflow


def test_reobserved_retired_series_becomes_live_again():
    reg = MetricsRegistry(enabled=True)
    m = reg.counter("relive", max_label_sets=2)
    m.inc(job="a")
    reg.retire_series(job="a")
    m.inc(job="a")                  # re-observed: live again
    m.inc(job="b")
    m.inc(job="c")                  # full, no retired left -> overflow
    series = m._snapshot_series()
    assert series["job=a"] == 2.0
    assert series.get("other=overflow") == 1.0


# ---------------------------------------------------------------------------
# exactly-once billing through real pools (chaos drills)
# ---------------------------------------------------------------------------


def _single_report(pool, job_id):
    c = pool.cost(job_id=job_id)
    assert len(c["reports"]) == 1, c["reports"]
    return c


def test_kill_worker_resubmit_bills_each_task_exactly_once(tmp_path):
    """Death resubmission re-runs chunks, but a task is billed when its
    result slot FIRST fills — billed tasks == map size exactly, and the
    duplicate traffic still reconciles: billed wire (per-key +
    overhead) equals the pool endpoints' framing-boundary counters."""
    plan = chaos.install(chaos.ChaosPlan(
        seed=SEED, token_dir=str(tmp_path / "tokens"),
        kill_after_chunks=2, kill_times=1))
    try:
        fiber_tpu.init()
        with fiber_tpu.Pool(2) as pool:
            xs = list(range(60))
            assert pool.map(targets.square, xs, chunksize=4,
                            job_id="acct-kill") == [x * x for x in xs]
            _wait(lambda: _single_report(pool, "acct-kill")["reports"]
                  [0]["total"].get("tasks") == 60.0,
                  what="all 60 tasks billed")
            c = _single_report(pool, "acct-kill")
            totals = c["totals"]
            xp = c["transport"]
            # wire reconciliation: every billed byte is a real frame
            billed_tx = totals.get("wire_tx", 0.0)
            billed_rx = totals.get("wire_rx", 0.0)
            wire_tx = xp["task_ep"]["bytes_tx"]
            wire_rx = (xp["task_ep"]["bytes_rx"]
                       + xp["result_ep"]["bytes_rx"])
            assert billed_tx == wire_tx, (billed_tx, wire_tx)
            # frames still in flight (heartbeats, the workers' trailing
            # cost frames) may land between the two reads: bounded
            # positive slack, never a deficit
            assert 0 <= wire_rx - billed_rx <= 8192, \
                (billed_rx, wire_rx)
            # the overhead bucket is explicit and non-trivial (ready
            # frames, heartbeats)
            assert c["overhead"].get("wire_rx", 0) > 0
    finally:
        chaos.uninstall()
    assert plan.spent("kill") == 1  # the fault actually fired


@pytest.mark.parametrize("io", ["threads", "selector", "shm"])
def test_wire_reconciliation_across_io_engines(io):
    """Billed wire equals the pool endpoints' framing-boundary counters
    under every transport engine — the regression bar for swapping the
    I/O core beneath the accounting plane. Under shm this also proves
    the doorbell wake frames stay off both ledgers (they are dropped
    before the counting ingress by design)."""
    fiber_tpu.init(transport_io=io)
    job = f"acct-io-{io}"
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(40))
        assert pool.map(targets.square, xs, chunksize=2,
                        job_id=job) == [x * x for x in xs]
        _wait(lambda: _single_report(pool, job)["reports"]
              [0]["total"].get("tasks") == 40.0,
              what="all 40 tasks billed")
        c = _single_report(pool, job)
        totals = c["totals"]
        xp = c["transport"]
        billed_tx = totals.get("wire_tx", 0.0)
        billed_rx = totals.get("wire_rx", 0.0)
        wire_tx = xp["task_ep"]["bytes_tx"]
        wire_rx = (xp["task_ep"]["bytes_rx"]
                   + xp["result_ep"]["bytes_rx"])
        assert billed_tx == wire_tx, (io, billed_tx, wire_tx)
        # in-flight trailing frames (heartbeats, late cost frames):
        # bounded positive slack, never a deficit
        assert 0 <= wire_rx - billed_rx <= 8192, \
            (io, billed_rx, wire_rx)


def test_speculation_first_result_wins_bills_once(tmp_path):
    """A speculative duplicate executes the chunk twice; the loser's
    fill dedups — billed tasks stays exactly the map size while the
    workers' execution count shows the duplicates."""
    plan = chaos.install(chaos.ChaosPlan(
        seed=SEED, token_dir=str(tmp_path / "tokens"),
        slow_worker_after_chunks=1, slow_worker_s=1.0,
        slow_worker_times=1))
    try:
        fiber_tpu.init(speculation_enabled=True,
                       speculation_quantile=2.0)
        with fiber_tpu.Pool(3) as pool:
            pool.map(targets.identity, range(3))  # spin-up barrier
            xs = list(range(36))
            assert pool.map(targets.sleep_echo, xs, chunksize=2,
                            job_id="acct-spec") == xs
            speculations = pool._sched.decisions["speculate"]
            _wait(lambda: _single_report(pool, "acct-spec")["reports"]
                  [0]["total"].get("tasks") == 36.0,
                  what="all 36 tasks billed")
            # the workers' cumulative cost frames carry the duplicate
            # executions (first-result-wins dedup happens on the master)
            _wait(lambda: _single_report(pool, "acct-spec")["reports"]
                  [0]["workers"].get("tasks_executed", 0) >= 36.0,
                  what="worker cost frames")
            rep = _single_report(pool, "acct-spec")["reports"][0]
            executed = rep["workers"]["tasks_executed"]
            assert 36.0 <= executed <= 36.0 + 2 * speculations
            assert rep["total"]["tasks"] == 36.0
    finally:
        chaos.uninstall()
    assert plan.spent("slow") == 1


def test_resume_bills_restored_tasks_as_restore_not_execute():
    """The PR-7 resume path: journaled chunks restore (tasks_restored),
    only the remainder executes (tasks) — restored + executed == total,
    billed under the SAME job id across both runs."""
    job = f"acct-resume-{os.getpid()}"
    xs = list(range(48))
    with fiber_tpu.Pool(2) as pool:
        want = pool.map(targets.square, xs, chunksize=4, job_id=job)
    path = ledgermod.job_path(job)
    with open(path) as fh:
        records = [json.loads(ln) for ln in fh if ln.strip()]
    header = [r for r in records if r["kind"] == "map"]
    chunks = [r for r in records if r["kind"] == "chunk"]
    with open(path, "w") as fh:
        for rec in header + chunks[:8]:     # crash state: 8/12 durable
            fh.write(json.dumps(rec) + "\n")
    COSTS.clear()   # the resumed run bills fresh
    with fiber_tpu.Pool(2) as pool2:
        got = pool2.map(targets.square, xs, chunksize=4, job_id=job)
        assert got == want
        _wait(lambda: _single_report(pool2, job)["reports"][0]["total"]
              .get("tasks") == 16.0, what="remainder billed")
        rep = _single_report(pool2, job)["reports"][0]
    assert rep["total"]["tasks_restored"] == 32.0
    assert rep["total"]["tasks"] == 16.0    # executed remainder only
    assert rep["total"].get("restore_s", 0.0) >= 0.0
    # the persisted record shows the same exactly-once split
    record = accounting.read_job_record(job)
    assert record["total"]["tasks_restored"] == 32.0
    assert record["total"]["tasks"] == 16.0


def test_budget_exceeded_fires_on_capped_map_and_record_persists():
    """The acceptance budget drill: a budget-capped map crosses its
    cpu_s cap -> one budget_exceeded anomaly (watchdog + flight +
    counter), the map still completes, and `fiber-tpu cost <job_id>`
    renders the persisted report with the violation."""
    from fiber_tpu import cli, telemetry

    fiber_tpu.init()
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(12))
        out = pool.map(targets.sleep_echo, xs, chunksize=2,
                       job_id="acct-budget",
                       budget=CostBudget(cpu_s=0.01))
        assert out == xs
        _wait(lambda: any(r["rule"] == "budget_exceeded"
                          for r in WATCHDOG.snapshot()["recent"]),
              what="budget_exceeded anomaly")
    assert telemetry.REGISTRY.get("cost_budget_breaches") \
        .value(field="cpu_s") >= 1
    _wait(lambda: (accounting.read_job_record("acct-budget") or {})
          .get("budget_violations"), what="persisted violation")
    record = accounting.read_job_record("acct-budget")
    assert record["budget"]["cpu_s"] == 0.01
    assert record["budget_violations"][0]["limit"] == "cpu_s"
    # the CLI renders the same record
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert cli.main(["cost", "acct-budget"]) == 0
    rendered = buf.getvalue()
    assert "BUDGET EXCEEDED" in rendered and "acct-budget" in rendered


def test_device_map_bills_device_seconds_and_flops(monkeypatch):
    """@meta(device=True, flops=...) maps bill device_s / tasks / flops
    under their own key (no wire: one mesh call)."""
    import fiber_tpu.parallel as parallel

    monkeypatch.setattr(parallel, "device_map",
                        lambda fn, items, star=False:
                        [fn(x) for x in items])

    @fiber_tpu.meta(device=True, flops=100.0)
    def f(x):
        return x + 1

    fiber_tpu.init()
    with fiber_tpu.Pool(2) as pool:
        assert pool.map(f, [1, 2, 3]) == [2, 3, 4]
    snap = COSTS.snapshot()["costs"]
    dev = [v for v in snap.values() if "device_s" in v]
    assert dev, snap
    assert dev[0]["tasks"] == 3.0
    assert dev[0]["flops"] == 300.0
    assert dev[0]["device_s"] > 0.0


def test_two_concurrent_maps_disjoint_reports_over_sim_pool(monkeypatch):
    """The acceptance drill on a real sim:2 pod: two concurrently
    active maps with different job_ids yield DISJOINT CostReports —
    exact per-map task counts, per-map wire bytes — whose sum (plus the
    explicit overhead bucket) reconciles with the pool's global
    transport and task counters; `fiber-tpu cost` renders both jobs
    live, and the backend's cluster_costs sweep answers per host."""
    from fiber_tpu.backends import get_backend, reset_backends

    monkeypatch.setenv("FIBER_BACKEND", "tpu")
    old = config.get().tpu_hosts
    config.get().update(tpu_hosts="sim:2")
    reset_backends()
    try:
        fiber_tpu.init(backend="tpu",
                       tpu_hosts="sim:2")
        with fiber_tpu.Pool(4) as pool:
            pool.map(targets.identity, range(4))  # spin-up barrier
            r1 = pool.map_async(targets.sleep_echo, range(30),
                                chunksize=2, job_id="acct-sim-a")
            r2 = pool.map_async(targets.sleep_echo, range(20),
                                chunksize=2, job_id="acct-sim-b")
            assert r1.get(120) == list(range(30))
            assert r2.get(120) == list(range(20))
            _wait(lambda: _single_report(pool, "acct-sim-a")["reports"]
                  [0]["total"].get("tasks") == 30.0,
                  what="map a fully billed")
            _wait(lambda: _single_report(pool, "acct-sim-b")["reports"]
                  [0]["total"].get("tasks") == 20.0,
                  what="map b fully billed")
            c = pool.cost()
            by_job = {r["job_id"]: r for r in c["reports"]}
            rep_a = by_job["acct-sim-a"]
            rep_b = by_job["acct-sim-b"]
            # disjoint keys, exact exactly-once task counts
            assert rep_a["key"] != rep_b["key"]
            assert rep_a["total"]["tasks"] == 30.0
            assert rep_b["total"]["tasks"] == 20.0
            # each map was billed real wire traffic of its own
            for rep in (rep_a, rep_b):
                assert rep["total"]["wire_tx"] > 0
                assert rep["total"]["wire_rx"] > 0
            # reconciliation: per-key + overhead == ledger totals ==
            # the endpoints' framing-boundary counters (positive slack
            # only for frames still in flight)
            totals = c["totals"]
            summed_tx = sum(r["total"].get("wire_tx", 0.0)
                            for r in c["reports"])
            summed_rx = sum(r["total"].get("wire_rx", 0.0)
                            for r in c["reports"])
            assert summed_tx + c["overhead"].get("wire_tx", 0.0) \
                == totals["wire_tx"]
            assert summed_rx + c["overhead"].get("wire_rx", 0.0) \
                == totals["wire_rx"]
            xp = c["transport"]
            assert totals["wire_tx"] == xp["task_ep"]["bytes_tx"]
            wire_rx = (xp["task_ep"]["bytes_rx"]
                       + xp["result_ep"]["bytes_rx"])
            assert 0 <= wire_rx - totals["wire_rx"] <= 8192
            # pool counters agree with the billed task totals (the
            # barrier map bills under its synthetic map-N job)
            stats = pool.stats()
            billed_tasks = sum(v["tasks"]
                               for v in stats["costs"].values())
            assert billed_tasks == stats["tasks_completed"] == 54
            # workers shipped cost frames from both sim hosts
            _wait(lambda: len(pool._cost_workers) >= 2,
                  what="worker cost frames from the sim hosts")
            # the backend sweep answers per host, keyed like host_health
            costs = get_backend().cluster_costs()
            assert len(costs) == 2
            for snap in costs.values():
                assert "costs" in snap and "error" not in snap
    finally:
        try:
            get_backend("tpu").shutdown_sim_cluster()
        except Exception:  # noqa: BLE001
            pass
        config.get().update(tpu_hosts=old)
        reset_backends()
    # both jobs persisted their cost records (readable post-join)
    for job, n in (("acct-sim-a", 30), ("acct-sim-b", 20)):
        record = accounting.read_job_record(job)
        assert record is not None
        assert record["total"]["tasks"] == float(n)


# ---------------------------------------------------------------------------
# collection plane: agent op, backends, CLI
# ---------------------------------------------------------------------------


@pytest.fixture
def embedded_agent(tmp_path):
    import threading

    from fiber_tpu.host_agent import HostAgent

    agent = HostAgent(0, bind="127.0.0.1", staging_root=str(tmp_path))
    t = threading.Thread(target=agent.serve_forever, daemon=True)
    t.start()
    yield agent
    agent.stop()


def test_agent_cost_op_backends_and_top_costs_cli(embedded_agent,
                                                  capsys):
    from fiber_tpu import cli
    from fiber_tpu.backends.local import LocalBackend
    from fiber_tpu.backends.tpu import AgentClient

    fiber_tpu.init()
    COSTS.charge(("t", "cli-job", "m1"), tasks=5, cpu_s=1.25,
                 wire_tx=100)
    client = AgentClient("127.0.0.1", embedded_agent.port)
    try:
        snap = client.call("cost_snapshot")
    finally:
        client.close()
    assert snap["costs"]["t/cli-job/m1"]["tasks"] == 5.0
    local = LocalBackend().cluster_costs()
    assert set(local) == {"local"}
    assert local["local"]["costs"]["t/cli-job/m1"]["cpu_s"] == 1.25
    hosts = f"127.0.0.1:{embedded_agent.port}"
    # top --costs renders the billing keys beside the monitor table
    assert cli.main(["top", "--hosts", hosts, "--iterations", "1",
                     "--no-clear", "--costs"]) == 0
    out = capsys.readouterr().out
    assert "costs (per billing key" in out
    assert "t/cli-job/m1" in out
    # cost --hosts live mode filters by job id
    assert cli.main(["cost", "cli-job", "--hosts", hosts]) == 0
    out = capsys.readouterr().out
    assert "matching_keys=1" in out


def test_telemetry_snapshot_carries_costs():
    from fiber_tpu import telemetry

    COSTS.charge(("t", "snap-job", "m1"), tasks=1)
    snap = telemetry.snapshot()
    assert snap["costs"]["costs"]["t/snap-job/m1"]["tasks"] == 1.0


def test_accounting_disabled_pool_bills_nothing():
    fiber_tpu.init(accounting_enabled=False)
    with fiber_tpu.Pool(2) as pool:
        assert pool.map(targets.square, list(range(8))) == \
            [x * x for x in range(8)]
        c = pool.cost()
        assert c["reports"] == []
        assert pool.stats()["costs"] == {}


# ---------------------------------------------------------------------------
# log ring (satellite): postmortem bundles + explain --flight tail
# ---------------------------------------------------------------------------


def test_log_ring_tail_in_postmortem_and_explain(tmp_path, capsys):
    from fiber_tpu import cli
    from fiber_tpu.telemetry import explain as explainmod
    from fiber_tpu.telemetry import postmortem
    from fiber_tpu.utils.logging import LOG_RING, get_logger

    logger = get_logger()
    for i in range(5):
        logger.warning("accounting-test log line %d", i)
    tail = LOG_RING.tail(3)
    assert len(tail) == 3
    assert "accounting-test log line 4" in tail[-1]
    assert "[" in tail[-1]  # ContextFilter [host job trace] stamps
    # bundles carry the tail (the logs pillar beside flight + stacks)
    bundle = postmortem.capture("test")
    assert any("accounting-test log line" in ln
               for ln in bundle["logs"])
    # flight artifacts carry it too, and explain renders it beside the
    # verdict
    artifact = tmp_path / "flight.json"
    artifact.write_text(json.dumps({
        "events": [], "logs": ["one log line", "two log line"]}))
    assert explainmod.load_logs(str(artifact)) == ["one log line",
                                                   "two log line"]
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps([
        {"name": "worker.execute", "trace": "t1", "ts": 0.0,
         "dur": 1.0, "seq": 1}]))
    assert cli.main(["explain", str(trace),
                     "--flight", str(artifact)]) == 0
    out = capsys.readouterr().out
    assert "recent log tail" in out and "two log line" in out


def test_log_ring_is_bounded():
    from fiber_tpu.utils.logging import LogRing

    ring = LogRing(capacity=4)
    import logging

    for i in range(10):
        ring.emit(logging.LogRecord("x", logging.INFO, "f", 1,
                                    f"line {i}", (), None))
    assert len(ring.tail(100)) == 4
    assert ring.dropped == 6
    assert ring.tail(100)[-1].endswith("line 9")
