"""Hierarchical per-host dispatch (docs/architecture.md): the packed
job's sub-master fetches chunk ranges, fans them to local sub-workers,
and streams results/telemetry back aggregated — with the direct-dispatch
semantics (correctness, death recovery, exactly-once billing) intact.
"""

import time

import pytest

import fiber_tpu
from fiber_tpu import telemetry
from fiber_tpu.telemetry.accounting import COSTS
from tests import targets


@pytest.fixture(autouse=True)
def _hier_isolation():
    # COSTS is the process-wide ledger: the billed-wire reconciliation
    # below compares its totals against per-pool endpoint counters, so
    # every test starts from an empty ledger.
    COSTS.clear()
    yield
    fiber_tpu.init()  # drop the dispatch_mode/cpu_per_job overrides
    COSTS.clear()


def _hier_pool(n=2, **over):
    fiber_tpu.init(cpu_per_job=2,
                   dispatch_mode="hier", **over)
    return fiber_tpu.Pool(n)


def test_hier_map_correct_and_ranges_handed_out():
    """A hier pool returns exactly the direct pool's results, the
    sub-master announces itself (its ident lands in _hier_idents), and
    handouts are counted as range scheduling decisions."""
    ranges0 = telemetry.REGISTRY.counter("sched_decisions").value(
        kind="range")
    with _hier_pool(2) as pool:
        xs = list(range(300))
        assert pool.map(targets.square, xs, chunksize=1) == \
            [x * x for x in xs]
        assert pool._hier_idents, "no sub-master ever declared itself"
        assert not pool._hier_degraded
    assert telemetry.REGISTRY.counter("sched_decisions").value(
        kind="range") > ranges0


def test_hier_imap_unordered_and_multiple_maps():
    """Range dispatch survives consecutive maps on one pool (the
    pending table and sub-master ready/range loop reset cleanly
    between seqs)."""
    with _hier_pool(2) as pool:
        xs = list(range(120))
        assert sorted(pool.imap_unordered(targets.square, xs,
                                          chunksize=2)) == \
            sorted(x * x for x in xs)
        assert pool.map(targets.identity, xs, chunksize=4) == xs


def test_hier_submaster_kill9_loses_zero_tasks():
    """kill -9 of the sub-master mid-map: every chunk of its held
    ranges is reclaimed through the pending table and resubmitted, the
    map completes complete-and-correct, and the pool degrades that
    host to direct per-worker dispatch (the proven path) rather than
    crash-looping the hierarchy."""
    with _hier_pool(2) as pool:
        xs = list(range(240))
        res = pool.map_async(targets.sleep_echo, xs, chunksize=2)
        deadline = time.monotonic() + 30
        # Kill once the sub-master demonstrably holds work: it has
        # declared itself AND results are flowing.
        while time.monotonic() < deadline and (
                not pool._hier_idents or pool._n_completed < 10):
            time.sleep(0.02)
        assert pool._hier_idents and pool._n_completed >= 10
        with pool._workers_lock:
            victim = pool._workers[0]
        victim.kill()  # SIGKILL, no cleanup
        got = res.get(240)
        assert got == xs, "tasks lost across the sub-master kill"
        assert pool._hier_degraded, \
            "sub-master death must degrade the pool to direct dispatch"
        assert pool.stats()["chunks_resubmitted"] > 0


def test_hier_billed_wire_reconciles():
    """Accounting under hierarchical dispatch: results arrive as
    rbatch frames and telemetry as fbatch frames, yet billed wire
    (per-key + overhead) still equals the endpoints' framing-boundary
    counters — the inner fbatch messages carried no wire of their own
    and must not be double-billed."""
    with _hier_pool(2) as pool:
        xs = list(range(80))
        assert pool.map(targets.square, xs, chunksize=1,
                        job_id="acct-hier") == [x * x for x in xs]
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            c = pool.cost(job_id="acct-hier")
            if c["reports"] and \
                    c["reports"][0]["total"].get("tasks") == 80.0:
                break
            time.sleep(0.05)
        c = pool.cost(job_id="acct-hier")
        assert len(c["reports"]) == 1
        assert c["reports"][0]["total"].get("tasks") == 80.0
        totals = c["totals"]
        xp = c["transport"]
        billed_tx = totals.get("wire_tx", 0.0)
        billed_rx = totals.get("wire_rx", 0.0)
        wire_tx = xp["task_ep"]["bytes_tx"]
        wire_rx = (xp["task_ep"]["bytes_rx"]
                   + xp["result_ep"]["bytes_rx"])
        assert billed_tx == wire_tx, (billed_tx, wire_tx)
        assert 0 <= wire_rx - billed_rx <= 8192, (billed_rx, wire_rx)


def test_hier_rides_the_shm_engine():
    """The composed tentpole: hierarchical dispatch with the shm
    transport engine end-to-end. Same-host negotiation puts the
    sub-master's upstream channels on rings; correctness and the
    exact result count are unchanged."""
    with _hier_pool(2, transport_io="shm") as pool:
        xs = list(range(200))
        assert pool.map(targets.square, xs, chunksize=1) == \
            [x * x for x in xs]
        assert pool._hier_idents
        assert not pool._hier_degraded
