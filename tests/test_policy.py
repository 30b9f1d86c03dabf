"""Policy plane: autonomous remediation riding the anomaly watchdog —
per-rule action drills, dry-run, cooldown suppression, outcome
classification, the WDRR throttle, and the explain/CLI narration
(docs/observability.md "Autonomous operations")."""

import json
import time

import pytest

import fiber_tpu
from fiber_tpu import config
from fiber_tpu.telemetry import explain as explainmod
from fiber_tpu.telemetry import monitor as monitormod
from fiber_tpu.telemetry.flightrec import FLIGHT, order_events
from fiber_tpu.telemetry.monitor import AnomalyWatchdog, WATCHDOG
from fiber_tpu.telemetry.policy import POLICY
from fiber_tpu.telemetry.timeseries import TIMESERIES
from tests import targets
from tests._chains import assert_linked_chain


@pytest.fixture(autouse=True)
def _policy_isolation():
    """Clean watchdog/flight/policy state per test; overrides dropped
    (init re-syncs every plane, including the policy engine)."""
    TIMESERIES.clear()
    WATCHDOG.clear()
    FLIGHT.clear()
    POLICY.reset()
    yield
    fiber_tpu.init()
    TIMESERIES.clear()
    WATCHDOG.clear()
    POLICY.reset()


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


def _policy_events(kind=None):
    evts = [e for e in FLIGHT.snapshot() if e.get("plane") == "policy"]
    if kind is not None:
        evts = [e for e in evts if e.get("kind") == kind]
    return evts


# ---------------------------------------------------------------------------
# engine gating: off, dry-run, rule filter
# ---------------------------------------------------------------------------


def test_engine_off_is_noop():
    dog = _fresh_watchdog(policy_enabled=False)
    assert not POLICY.enabled
    dog.external_breach("budget_exceeded", detail="over", key="t/j/m1",
                        observed=2.0)
    assert POLICY.actions_total == 0
    assert _policy_events() == []
    # the anomaly itself still raised — detection is independent
    assert "budget_exceeded" in dog.snapshot()["active"]


def test_dry_run_records_without_acting():
    from fiber_tpu.transport import evloop

    dog = _fresh_watchdog(policy_dry_run=True)
    before = int(evloop.TX_HIGH_WATER)
    dog.observe(_sample(tx_queue_bytes=float(64 << 20)))
    assert int(evloop.TX_HIGH_WATER) == before  # nothing acted
    acts = POLICY.recent_actions()
    assert len(acts) == 1
    assert acts[0]["rule"] == "tx_queue_high"
    assert acts[0]["dry_run"] and not acts[0]["applied"]
    assert "would tighten" in acts[0]["detail"]
    # the dry-run act still links to its anomaly and still verifies
    anomaly = dog.snapshot()["active"]["tx_queue_high"]
    assert acts[0]["cause_id"] == anomaly["id"]


def test_rules_filter_limits_the_engine():
    dog = _fresh_watchdog(policy_rules="hbm_fill")
    dog.external_breach("budget_exceeded", detail="over", key="t/j/m1",
                        observed=2.0)
    assert POLICY.actions_total == 0


# ---------------------------------------------------------------------------
# per-rule action drills
# ---------------------------------------------------------------------------


def test_tx_queue_high_tightens_then_reverts_on_clear():
    from fiber_tpu.transport import evloop

    dog = _fresh_watchdog()
    before = int(evloop.TX_HIGH_WATER)
    dog.observe(_sample(tx_queue_bytes=float(64 << 20)))
    assert int(evloop.TX_HIGH_WATER) == max(4 << 20, before // 2)
    act = POLICY.recent_actions()[-1]
    assert act["rule"] == "tx_queue_high" and act["applied"]
    assert_linked_chain("tx_queue_high")
    # clear edge restores the previous high-water
    dog.observe(_sample(tx_queue_bytes=0.0))
    assert int(evloop.TX_HIGH_WATER) == before
    assert [e["kind"] for e in _policy_events("revert")] == ["revert"]


def test_recompile_storm_pins_and_unpins_fingerprint(monkeypatch):
    from fiber_tpu.parallel import dmap

    storm = {"storm": True, "fingerprint": "mod.fn@((('pool', 8),))",
             "count": 9, "window_s": 30}
    monkeypatch.setattr(monitormod, "_recompile_state", lambda: dict(storm))
    dog = _fresh_watchdog()
    dog.observe(_sample())
    # the record truncates the fingerprint to 48 chars; the pin is a
    # prefix so the full cache fingerprint still matches
    pins = dmap.pinned_fingerprints()
    assert pins == [storm["fingerprint"][:48]]
    assert dmap._pinned_locked(storm["fingerprint"])
    assert_linked_chain("recompile_storm")
    storm["storm"] = False
    dog.observe(_sample())
    assert dmap.pinned_fingerprints() == []


def test_store_disk_fill_sheds_to_target(tmp_path):
    from fiber_tpu import store as storemod
    from fiber_tpu.store.core import LocalStore

    st = LocalStore(capacity_bytes=1 << 20, root=str(tmp_path),
                    max_disk_bytes=100 << 10)
    monkey_prev = storemod._store
    storemod._store = st
    try:
        # fill the disk tier past the 90% watchdog threshold
        for i in range(12):
            st.put_bytes(bytes([i]) * (8 << 10), persist=True)
        assert st.disk_usage() > int(0.9 * st.max_disk_bytes)
        dog = _fresh_watchdog()
        dog.observe(_sample())
        act = POLICY.recent_actions()[-1]
        assert act["rule"] == "store_disk_fill" and act["applied"]
        assert st.disk_usage() <= int(0.7 * st.max_disk_bytes)
        assert_linked_chain("store_disk_fill")
    finally:
        storemod._store = monkey_prev


def test_straggler_rules_boost_speculation_and_drive_replication():
    from fiber_tpu.sched.core import Scheduler
    from fiber_tpu.store.replicate import REPLICATOR

    sched = Scheduler(n_workers=2, policy="adaptive", speculation=True,
                      speculation_quantile=4.0)
    calls = []
    REPLICATOR.register_driver(lambda reason: calls.append(reason) or 0)
    REPLICATOR.note(["d" * 64])
    try:
        dog = _fresh_watchdog(suspect_timeout=10.0)
        dog.observe(_sample(heartbeat_age_s=9.0))
        act = POLICY.recent_actions()[-1]
        assert act["rule"] == "heartbeat_age"
        assert act["action"] == "replicate_and_boost" and act["applied"]
        assert sched._quantile == pytest.approx(2.0)  # 4.0 * 0.5
        deadline = time.monotonic() + 5.0
        while not calls and time.monotonic() < deadline:
            time.sleep(0.01)
        assert calls == ["heartbeat_age"]  # throwaway-thread drive ran
        assert_linked_chain("heartbeat_age")
        dog.observe(_sample(heartbeat_age_s=0.0))     # clear edge
        assert sched._quantile == pytest.approx(4.0)  # restored
    finally:
        REPLICATOR.forget(["d" * 64])
        REPLICATOR.register_driver(None)
        sched.close()


def test_budget_exceeded_throttles_registered_pools():
    from fiber_tpu.telemetry import policy as policymod

    class FakePool:
        def __init__(self):
            self.throttled = []
            self.restored = []

        def throttle_billing_key(self, key, factor=4.0):
            self.throttled.append((key, factor))
            return 2

        def unthrottle_billing_key(self, key):
            self.restored.append(key)
            return 2

    pool = FakePool()
    policymod.register_pool(pool)
    dog = _fresh_watchdog()
    dog.external_breach("budget_exceeded", detail="over budget",
                        key="acme/train-7/m3", limit="cpu_s",
                        observed=2.0)
    assert pool.throttled == [(("acme", "train-7", "m3"), 4.0)]
    act = POLICY.recent_actions()[-1]
    assert act["applied"] and "2 in-flight map(s)" in act["detail"]
    assert_linked_chain("budget_exceeded")
    dog.external_clear("budget_exceeded")
    assert pool.restored == [("acme", "train-7", "m3")]


def test_queue_growth_shrinks_stream_window_then_reverts():
    """queue_growth -> shrink_stream_window (docs/streaming.md): a
    sustained queue-depth breach halves every ACTIVE stream's admission
    window (admission parks sooner, the queue stops growing at the
    source); the clear edge restores the original windows via the
    policy's owned revert."""

    def gen():
        for i in range(200):
            yield i

    dog = _fresh_watchdog(stream_window=8)
    with fiber_tpu.Pool(2) as pool:
        # window 8 x chunk 4 admits at most ~36 of the 200 items while
        # the consumer sits at 8 — the stream is live mid-drill
        it = pool.imap(targets.square, gen(), chunksize=4)
        for _ in range(8):
            next(it)
        [seq] = list(pool._stream_windows)
        assert pool._stream_windows[seq] == 8
        dog.external_breach("queue_growth",
                            detail="depth 100 over 3 ticks",
                            depth=100.0)
        assert pool._stream_windows[seq] == 4
        act = POLICY.recent_actions()[-1]
        assert act["rule"] == "queue_growth" and act["applied"]
        assert act["action"] == "shrink_stream_window"
        # the live admission loop re-reads the window each tick, so
        # the shrink takes effect without touching the stream
        dog.external_clear("queue_growth")
        assert pool._stream_windows[seq] == 8
        assert [e["kind"] for e in _policy_events("revert")] == ["revert"]
        # the stream still makes progress after shrink + revert —
        # drain it fully so join() sees nothing outstanding
        assert next(it) == 8 * 8
        assert list(it) == [i * i for i in range(9, 200)]


def test_queue_growth_without_streams_declines():
    dog = _fresh_watchdog()
    dog.external_breach("queue_growth", detail="depth 100", depth=100.0)
    act = POLICY.recent_actions()[-1]
    assert act["rule"] == "queue_growth" and not act["applied"]
    assert "no active streaming map" in act["detail"]


# ---------------------------------------------------------------------------
# cooldown + outcome classification
# ---------------------------------------------------------------------------


def test_cooldown_suppresses_refire_within_window():
    dog = _fresh_watchdog(policy_cooldown_s=60.0)
    dog.external_breach("budget_exceeded", detail="over", key="t/j/m1",
                        observed=2.0)
    dog.external_clear("budget_exceeded")
    dog.external_breach("budget_exceeded", detail="again", key="t/j/m1",
                        observed=2.0)
    assert POLICY.actions_total == 1
    assert POLICY.suppressed_total == 1
    sup = _policy_events("suppressed")
    assert len(sup) == 1 and "cooldown" in sup[0]["reason"]
    # the suppression links to the SECOND anomaly's event
    second = dog.snapshot()["active"]["budget_exceeded"]
    assert sup[0]["cause_id"] == second["id"]


def test_outcome_resolved_persisted_worsened():
    dog = _fresh_watchdog(policy_cooldown_s=0.0)

    # resolved: the rule cleared before verification
    dog.external_breach("budget_exceeded", detail="over", key="t/j/m1",
                        observed=2.0)
    dog.external_clear("budget_exceeded")
    assert POLICY.poll(now=time.monotonic() + 10.0) == 1
    assert POLICY.recent_actions()[-1]["outcome"] == "resolved"

    # persisted: still active, severity flat
    dog.external_breach("budget_exceeded", detail="over", key="t/j/m1",
                        observed=2.0)
    assert POLICY.poll(now=time.monotonic() + 10.0) == 1
    assert POLICY.recent_actions()[-1]["outcome"] == "persisted"
    dog.external_clear("budget_exceeded")

    # worsened: the standing record's severity attr degraded >= 5%
    dog.external_breach("budget_exceeded", detail="over", key="t/j/m1",
                        observed=2.0)
    dog.external_breach("budget_exceeded", detail="worse", key="t/j/m1",
                        observed=3.0)  # refreshes the standing record
    assert POLICY.poll(now=time.monotonic() + 10.0) == 1
    assert POLICY.recent_actions()[-1]["outcome"] == "worsened"
    counts = _policy_events("outcome")
    assert [e["outcome"] for e in counts] == \
        ["resolved", "persisted", "worsened"]


def test_revert_guarded_by_raising_watchdog():
    from fiber_tpu.transport import evloop

    dog = _fresh_watchdog()
    before = int(evloop.TX_HIGH_WATER)
    dog.observe(_sample(tx_queue_bytes=float(64 << 20)))
    assert int(evloop.TX_HIGH_WATER) < before
    # another watchdog instance clearing the same rule name must NOT
    # undo this one's remediation
    other = AnomalyWatchdog()
    other.configure(config.get())
    POLICY.on_clear(other, "tx_queue_high")
    assert int(evloop.TX_HIGH_WATER) < before  # still tightened
    dog.observe(_sample(tx_queue_bytes=0.0))
    assert int(evloop.TX_HIGH_WATER) == before


# ---------------------------------------------------------------------------
# WDRR throttle mechanics (scheduler level)
# ---------------------------------------------------------------------------


def test_scheduler_throttle_shifts_handout_ratio():
    from fiber_tpu.sched.core import Scheduler

    sched = Scheduler(n_workers=2, policy="adaptive")
    sched.register_map(1, priority=1.0)
    sched.register_map(2, priority=1.0)
    for i in range(40):
        sched.put((b"a", (1, i)))
        sched.put((b"b", (2, i)))
    assert sched.throttle_map(2, factor=4.0)
    served = [sched.get(timeout=1.0)[1][0] for _ in range(20)]
    # map 2 at weight 0.25 gets ~1 chunk per 4 of map 1's
    assert served.count(1) >= 3 * served.count(2)
    assert served.count(2) >= 1  # floor: still progressing, not starved
    assert sched.unthrottle_map(2)
    assert sched._maps[2].weight == pytest.approx(1.0)
    sched.close()


def test_scheduler_all_throttled_ring_still_serves():
    from fiber_tpu.sched.core import Scheduler

    sched = Scheduler(n_workers=1, policy="adaptive")
    sched.register_map(1, priority=1.0)
    sched.put((b"a", (1, 0)))
    assert sched.throttle_map(1)
    # a ring of nothing but 0.25-weight maps must hand out in one call
    assert sched.get(timeout=1.0)[1] == (1, 0)
    sched.close()


def test_scheduler_throttle_idempotent_and_released():
    from fiber_tpu.sched.core import Scheduler

    sched = Scheduler(n_workers=1, policy="adaptive")
    sched.register_map(1, priority=2.0)
    sched.put((b"a", (1, 0)))
    sched.throttle_map(1, factor=4.0)
    sched.throttle_map(1, factor=4.0)  # re-divides the ORIGINAL weight
    assert sched._maps[1].weight == pytest.approx(0.5)
    sched.release_map(1)
    assert 1 not in sched._throttled  # no leak across map lifetimes
    sched.close()


# ---------------------------------------------------------------------------
# event ids + the explain chain + CLI
# ---------------------------------------------------------------------------


def test_flight_event_ids_are_stable_across_merges(tmp_path):
    ids = [FLIGHT.record("pool", "submit", seq=i) for i in range(3)]
    assert all(ids) and len(set(ids)) == 3
    evts = FLIGHT.snapshot()
    # merge-ordering (the cross-process artifact path) preserves ids
    merged = order_events(list(reversed(evts)))
    assert [e["id"] for e in merged] == ids
    art = tmp_path / "flight.json"
    art.write_text(json.dumps({"events": evts}))
    loaded = explainmod.load_events(str(art))
    assert [e["id"] for e in loaded] == ids


def test_explain_narrates_the_full_chain():
    dog = _fresh_watchdog()
    dog.observe(_sample(tx_queue_bytes=float(64 << 20)))
    POLICY.poll(now=time.monotonic() + 10.0)
    chains = explainmod.policy_chains(FLIGHT.snapshot())
    assert len(chains) == 1
    chain = chains[0]
    assert chain["anomaly"]["kind"] == "tx_queue_high"
    assert chain["actions"][0]["kind"] == "tighten_tx_highwater"
    assert chain["outcomes"][0]["cause_id"] == chain["cause_id"]
    text = explainmod.render_chains(chains)
    assert "anomaly tx_queue_high" in text
    assert "-> action tighten_tx_highwater (applied)" in text
    assert "=> outcome" in text
    dog.observe(_sample(tx_queue_bytes=0.0))  # restore the high-water


def test_policies_cli_local_snapshot(capsys):
    from fiber_tpu import cli

    fiber_tpu.init()
    dog = AnomalyWatchdog()
    dog.configure(config.get())
    dog.external_breach("budget_exceeded", detail="over", key="t/j/m1",
                        observed=2.0)
    assert cli.main(["policies"]) == 0
    out = capsys.readouterr().out
    assert "policy engine: enabled" in out
    assert "budget_exceeded" in out and "throttle_tenant" in out
    assert "recent actions" in out
    assert cli.main(["policies", "--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["actions_total"] == 1
    assert {p["rule"] for p in snap["policies"]} >= {
        "hbm_fill", "recompile_storm", "budget_exceeded"}


def test_policies_cli_flight_artifact(tmp_path, capsys):
    from fiber_tpu import cli

    dog = _fresh_watchdog()
    dog.external_breach("budget_exceeded", detail="over", key="t/j/m1",
                        observed=2.0)
    art = tmp_path / "flight.json"
    art.write_text(json.dumps({"events": FLIGHT.snapshot()}))
    assert cli.main(["policies", "--flight", str(art)]) == 0
    out = capsys.readouterr().out
    assert "anomaly budget_exceeded" in out
    assert "-> action throttle_tenant" in out
    assert "outcome pending" in out  # verification hadn't run yet
