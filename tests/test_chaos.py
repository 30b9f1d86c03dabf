"""Chaos harness integration: seeded fault injection pinning the
robustness claims end-to-end (docs/robustness.md).

``make chaos`` runs this file under three fixed seeds via
FIBER_CHAOS_SEED; un-marked tests also run in tier 1 with the default
seed. Each test installs a ChaosPlan with a per-test token_dir (tmp_path)
so fault budgets reset between tests and between seeds."""

import os
import time

import pytest

import fiber_tpu
from fiber_tpu.testing import chaos
from tests import targets

SEED = int(os.environ.get("FIBER_CHAOS_SEED", "7"))

#: Aggressive-but-safe detector settings for chaos runs: the suspect
#: timeout is 6x the beat period, and both are far above scheduler
#: jitter on a loaded CI box.
HB_INTERVAL = 0.2
SUSPECT_TIMEOUT = 1.5


@pytest.fixture
def chaos_plan(tmp_path):
    """Install a ChaosPlan (returned factory) and guarantee teardown of
    both the plan (module global + FIBER_CHAOS env) and any config
    overrides the test applied via fiber_tpu.init."""
    def _install(**knobs):
        plan = chaos.ChaosPlan(
            seed=SEED, token_dir=str(tmp_path / "tokens"), **knobs)
        return chaos.install(plan)

    yield _install
    chaos.uninstall()
    fiber_tpu.init()  # drop any per-test config overrides


def test_plan_env_roundtrip(tmp_path):
    plan = chaos.ChaosPlan(seed=SEED, kill_after_chunks=3, kill_times=2,
                           hang_s=1.5, token_dir=str(tmp_path))
    clone = chaos.ChaosPlan.from_env(plan.to_env())
    assert clone.seed == SEED
    assert clone.kill_after_chunks == 3 and clone.kill_times == 2
    assert clone.hang_s == 1.5 and clone.token_dir == str(tmp_path)


def test_plan_rejects_unknown_knob():
    with pytest.raises(ValueError, match="unknown chaos knob"):
        chaos.ChaosPlan.from_env("seed=1,typo_knob=3")


def test_budget_tokens_are_cluster_wide(tmp_path):
    """O_EXCL token files arbitrate budgets across processes: exactly
    ``limit`` acquisitions ever succeed for one token_dir."""
    plan = chaos.ChaosPlan(seed=SEED, token_dir=str(tmp_path / "t"))
    wins = [plan.acquire("kill", 2) for _ in range(5)]
    assert wins == [True, True, False, False, False]
    # a plan reconstructed from env (another process's view) sees the
    # same exhausted budget
    clone = chaos.ChaosPlan.from_env(plan.to_env())
    assert not clone.acquire("kill", 2)
    assert clone.spent("kill") == 2


def test_install_exports_plan_to_children(chaos_plan):
    chaos_plan(kill_after_chunks=9)
    assert chaos.ENV_VAR in os.environ
    clone = chaos.ChaosPlan.from_env(os.environ[chaos.ENV_VAR])
    assert clone.kill_after_chunks == 9
    chaos.uninstall()
    assert chaos.ENV_VAR not in os.environ and chaos._plan is None


def test_worker_killed_mid_map_completes(chaos_plan):
    """(a) A worker hard-killed mid-map (after its N-th chunk) strands
    nothing: the pending table resubmits and the map returns complete,
    correct, in-order results. Pinned to transport_io=selector (the
    default) so the pool-kill recovery path is exercised through the
    event-loop data plane even if the default ever flips."""
    plan = chaos_plan(kill_after_chunks=2, kill_times=1)
    fiber_tpu.init(transport_io="selector")
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(120))
        assert pool.map(targets.square, xs, chunksize=4) == \
            [x * x for x in xs]
    assert plan.spent("kill") == 1  # the fault actually fired


def test_spawn_failure_burst_breaker_opens_then_closes(chaos_plan):
    """(b) Spawn fails k < _SPAWN_FAIL_LIMIT times then succeeds: the
    breaker opens (stops the hammering), half-opens, closes on the
    first success, and the map completes."""
    plan = chaos_plan(fail_local_spawn=4)
    fiber_tpu.init(spawn_breaker_threshold=3, spawn_breaker_backoff=0.1,
                   spawn_breaker_backoff_max=0.5)
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(40))
        assert pool.map(targets.square, xs) == [x * x for x in xs]
        assert pool._spawn_breaker.opened_total >= 1
        assert pool._spawn_breaker.state(pool._spawn_key) == "closed"
    assert plan.spent("fail-local_spawn") == 4


def test_hung_worker_declared_dead_and_chunks_resubmitted(chaos_plan):
    """A hung host (compute AND heartbeats frozen — kernel reports
    nothing) is declared dead by the failure detector before TCP would
    notice; its held chunks are resubmitted and the map completes. The
    hung worker's late duplicate results are deduped."""
    chaos_plan(hang_after_chunks=1, hang_s=4.0, hang_times=1)
    fiber_tpu.init(heartbeat_interval=HB_INTERVAL,
                   suspect_timeout=SUSPECT_TIMEOUT)
    t0 = time.monotonic()
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(80))
        assert pool.map(targets.square, xs, chunksize=2) == \
            [x * x for x in xs]
        # the declaration (not the 4s wake-up) is what unblocked the map
        assert time.monotonic() - t0 < 4.0
        assert pool._detector.suspected_total >= 1


def test_ingress_stall_longer_than_suspect_timeout_resubmits(chaos_plan):
    """(c) A silent network stall — one result-stream channel's frames
    delayed longer than suspect_timeout — fires the detector (silence is
    indistinguishable from death, by design) and the stalled worker's
    chunks are resubmitted; the late frames dedupe on arrival."""
    chaos_plan(stall_recv_after=4, stall_recv_s=3.0, stall_recv_times=1)
    fiber_tpu.init(heartbeat_interval=HB_INTERVAL,
                   suspect_timeout=1.2)
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(60))
        assert pool.map(targets.square, xs, chunksize=2) == \
            [x * x for x in xs]
        assert pool._detector.suspected_total >= 1


@pytest.mark.parametrize("io", ["threads", "selector", "shm"])
def test_transport_drop_frames_endpoint_level(chaos_plan, io):
    """Bound-r ingress frame DROP at the Endpoint boundary: lost frames
    stay lost (loss model), the rest keep flowing, and the sender's
    credit window is compensated so throughput doesn't decay.

    Parametrized over every I/O engine (docs/transport.md): the chaos
    plan consults one counter per channel (`recv_frame_actions`), so the
    drop schedule AND the credit compensation must be observably
    identical under the selector event loop, the thread-per-connection
    fallback and the shm ring engine — asserted below down to the
    exact credit-frame count."""
    from fiber_tpu import serialization
    from fiber_tpu.transport.tcp import Endpoint

    chaos_plan(drop_recv_every=3)
    server = Endpoint("r", io=io)
    addr = server.bind("127.0.0.1")
    client = Endpoint("w", io=io).connect(addr)
    try:
        n = 30
        for i in range(n):
            client.send(serialization.dumps(i), timeout=10.0)
        got = []
        while True:
            try:
                got.append(serialization.loads(server.recv(timeout=1.0)))
            except TimeoutError:
                break
        # every 3rd frame dropped, order preserved for the survivors
        assert got == [i for i in range(n) if (i + 1) % 3 != 0]
        # Credit handed back for every dropped frame: the server sent
        # exactly 1 window grant + n/3 compensation credits (the 20
        # delivered recvs stay below the 32-frame replenish batch), the
        # same under both engines.
        assert server.frames_tx == 1 + n // 3
    finally:
        client.close()
        server.close()


def test_connect_retry_survives_late_listener(chaos_plan):
    """Transport hardening: connect() retries with backoff across the
    window where the listener isn't up yet (restarting master, slow
    accept backlog) instead of failing on the first RST. The probed
    port can be stolen by an unrelated process between release and the
    late bind — that attempt proves nothing either way, so it is
    retried on a fresh port."""
    import socket as pysocket
    import threading

    from fiber_tpu.transport.tcp import Endpoint

    for _ in range(3):
        probe = pysocket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()  # port now free (and refusing) until we bind it

        box = {}

        def late_bind():
            time.sleep(0.3)
            server = Endpoint("r")
            try:
                server.bind("127.0.0.1", port)
            except OSError:
                return  # port stolen; box stays empty
            box["ep"] = server

        t = threading.Thread(target=late_bind, daemon=True)
        t.start()
        client = Endpoint("w")
        try:
            # would RST right now; the backoff spans the 0.3s gap with
            # generous headroom for a loaded CI box
            client.connect(f"tcp://127.0.0.1:{port}", retries=8)
        except OSError:
            client.close()
            t.join(10)
            if "ep" not in box:
                continue  # stolen port: rerun on a fresh one
            box["ep"].close()
            raise
        t.join(10)
        if "ep" not in box:
            client.close()  # connected to the thief, not our server
            continue
        try:
            assert box["ep"].wait_for_peers(1, timeout=10.0)
        finally:
            client.close()
            box["ep"].close()
        return
    pytest.fail("probed port stolen on every attempt")


def test_endpoint_last_rx_observes_traffic(chaos_plan):
    from fiber_tpu.transport.tcp import Endpoint

    server = Endpoint("r")
    addr = server.bind("127.0.0.1")
    client = Endpoint("w").connect(addr)
    try:
        assert server.last_rx is None
        client.send(b"x", timeout=10.0)
        assert server.recv(timeout=10.0) == b"x"
        assert server.last_rx is not None
        assert time.monotonic() - server.last_rx < 5.0
    finally:
        client.close()
        server.close()


def test_chaos_map_survives_kill_spawnfail_and_freeze(chaos_plan):
    """The acceptance criterion: one map over >= 200 tasks survives an
    induced worker kill, an induced spawn-failure burst, AND an induced
    heartbeat freeze (hung host), returning complete and correct
    results — pinned under fixed seeds by `make chaos`."""
    plan = chaos_plan(kill_after_chunks=3, kill_times=1,
                      fail_local_spawn=2,
                      hang_after_chunks=5, hang_s=3.0, hang_times=1)
    fiber_tpu.init(heartbeat_interval=HB_INTERVAL,
                   suspect_timeout=SUSPECT_TIMEOUT)
    with fiber_tpu.Pool(3) as pool:
        xs = list(range(240))
        assert pool.map(targets.square, xs, chunksize=2) == \
            [x * x for x in xs]
        assert pool._detector.suspected_total >= 1
    # every scheduled fault actually fired
    assert plan.spent("kill") == 1
    assert plan.spent("fail-local_spawn") == 2
    assert plan.spent("hang") == 1


@pytest.mark.slow
def test_chaos_soak_repeated_kills(chaos_plan):
    """Soak: every worker generation dies after 4 chunks, repeatedly
    (budget 6), across a 600-task map — progress interleaves with
    deaths, so the no-progress poison escalation must never fire and
    the map must still complete exactly."""
    chaos_plan(kill_after_chunks=4, kill_times=6)
    fiber_tpu.init(heartbeat_interval=HB_INTERVAL,
                   suspect_timeout=SUSPECT_TIMEOUT)
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(600))
        assert pool.map(targets.square, xs, chunksize=4) == \
            [x * x for x in xs]


@pytest.mark.parametrize("io", ["threads", "selector", "shm"])
def test_partition_severs_then_heals_endpoint_level(chaos_plan, io):
    """Network partition at the Endpoint boundary, every I/O engine:
    from the N-th frame the host pair is CUT — every frame (data,
    results, heartbeats) is severed for partition_s — then flow
    resumes. The schedule comes from the same `recv_frame_actions`
    every engine consults, so it cannot diverge between them."""
    from fiber_tpu import serialization
    from fiber_tpu.transport.tcp import Endpoint

    chaos_plan(partition_after=4, partition_s=1.0, partition_times=1)
    server = Endpoint("r", io=io)
    addr = server.bind("127.0.0.1")
    client = Endpoint("w", io=io).connect(addr)
    try:
        t0 = time.monotonic()
        for i in range(10):
            client.send(serialization.dumps(i), timeout=10.0)
        got = [serialization.loads(server.recv(timeout=5.0))
               for _ in range(3)]
        assert got == [0, 1, 2]  # pre-partition frames flow
        # frames 3..9 landed inside the partition window: severed
        with pytest.raises(TimeoutError):
            server.recv(timeout=0.3)
        # heal, then traffic flows again — the peer was never dead
        time.sleep(max(0.0, t0 + 1.2 - time.monotonic()))
        client.send(serialization.dumps("after"), timeout=10.0)
        assert serialization.loads(server.recv(timeout=5.0)) == "after"
    finally:
        client.close()
        server.close()


def test_partition_suspect_not_dead_map_completes(chaos_plan):
    """Suspect != dead, proven: one worker's result stream is severed
    (results AND heartbeats) for longer than suspect_timeout. The
    failure detector declares it dead — correctly, silence IS the
    signal — and its chunks are resubmitted to the surviving worker;
    the partitioned worker is still alive, and whatever it sends after
    the heal is deduped. The map completes with exactly one result per
    task."""
    plan = chaos_plan(partition_after=6, partition_s=3.0,
                      partition_times=1)
    fiber_tpu.init(heartbeat_interval=HB_INTERVAL,
                   suspect_timeout=1.2)
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(60))
        assert pool.map(targets.square, xs, chunksize=2) == \
            [x * x for x in xs]
        assert pool._detector.suspected_total >= 1
    assert plan.spent("partition") == 1


@pytest.mark.slow
def test_soak_partition_plus_master_kill_then_resume(chaos_plan,
                                                     tmp_path):
    """The full durability gauntlet under one seed (docs/robustness.md):
    a subprocess master runs a durable map while (a) one worker's
    result stream is partitioned past suspect_timeout and (b) the
    seeded kill_master knob SIGKILLs the master once >= 4 chunks are
    journaled. `fiber-tpu`-style resume (re-entering map with the same
    job_id) then completes the job: exactly one result per task,
    journaled chunks restored, only the remainder re-executed."""
    import json
    import subprocess
    import sys

    from fiber_tpu.store import ledger as ledgermod

    job = f"soak-part-{os.getpid()}-{SEED}"
    plan = chaos_plan(partition_after=6, partition_s=2.5,
                      partition_times=1,
                      kill_master_after_chunks=4, kill_master_times=1)
    script = (
        "import fiber_tpu\n"
        "from tests import targets\n"
        "fiber_tpu.init(heartbeat_interval=0.2,\n"
        "               suspect_timeout=1.2)\n"
        "with fiber_tpu.Pool(2) as pool:\n"
        f"    pool.map(targets.sleep_echo, list(range(64)), chunksize=2,\n"
        f"             job_id={job!r})\n"
    )
    env = dict(os.environ, FIBER_BACKEND="local")
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-2000:])
    assert plan.spent("kill-master") == 1
    header, completed, done = ledgermod.load(ledgermod.job_path(job))
    assert not done and len(completed) >= 4
    journaled = len(completed)
    chaos.uninstall()
    time.sleep(1.0)  # orphaned subprocess workers notice and exit
    with fiber_tpu.Pool(2) as pool:
        out = pool.map(targets.sleep_echo, list(range(64)), chunksize=2,
                       job_id=job)
        stats = pool.stats()
    assert out == list(range(64))
    assert stats["tasks_restored"] >= 2 * journaled
    assert stats["tasks_restored"] + stats["tasks_completed"] == 64
    _, completed_after, done_after = ledgermod.load(
        ledgermod.job_path(job))
    assert done_after and len(completed_after) == 32
