"""Durable maps (docs/robustness.md): write-ahead task ledger, master
crash recovery, and partition/host-loss tolerance for the object store.

Coverage map:
* MapLedger unit semantics: header/chunk/done records, torn-tail
  tolerance, duplicate-chunk dedup, job-id path safety;
* Pool.map(job_id=) journaling + same-process resume: exactly one
  result per task, zero re-execution of journaled chunks, partial
  ledgers re-execute only the remainder, spec-mismatch rejection;
* the headline crash drill: a SUBPROCESS master SIGKILL'd mid-map by
  the seeded ``kill_master_after_chunks`` knob, recovered by
  ``fiber-tpu resume`` — ledger + pool counters prove the
  exactly-once split and the trace id survives (envelope-reuse rule);
* LocalStore disk-tier digest verification (corrupt spill/cache files
  degrade to a refetch, never a wrong payload) + the seeded
  ``corrupt_store_disk`` pool drill;
* the precious-digest Replicator and the host-revive breaker clear.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import fiber_tpu
from fiber_tpu import serialization
from fiber_tpu.store import LocalStore
from fiber_tpu.store import ledger as ledgermod
from fiber_tpu.store.core import digest_of
from fiber_tpu.store.replicate import Replicator
from fiber_tpu.testing import chaos
from tests import targets

SEED = int(os.environ.get("FIBER_CHAOS_SEED", "7"))


def _unique_job(tag: str) -> str:
    return f"{tag}-{os.getpid()}-{int.from_bytes(os.urandom(4), 'big')}"


# ---------------------------------------------------------------------------
# MapLedger unit semantics
# ---------------------------------------------------------------------------


def test_job_id_path_safety():
    with pytest.raises(ValueError):
        ledgermod.check_job_id("../evil")
    with pytest.raises(ValueError):
        ledgermod.check_job_id("")
    with pytest.raises(ValueError):
        ledgermod.check_job_id("a/b")
    assert ledgermod.check_job_id("es-gen_42.A") == "es-gen_42.A"


def test_ledger_roundtrip_dedup_and_torn_tail(tmp_path):
    store = LocalStore(root=str(tmp_path / "objects"))
    path = str(tmp_path / "j.ledger")
    led = ledgermod.MapLedger(path, store, fsync_interval=0.0)
    led.write_header({"job_id": "j", "task_digest": "td",
                      "n_items": 8, "chunksize": 2, "star": False,
                      "trace": "abc"})
    assert led.record_chunk(0, 2, [1, 2])
    assert not led.record_chunk(0, 2, [1, 2])  # duplicate: journaled once
    assert led.record_chunk(2, 2, [3, 4])
    assert led.flush(10.0)
    assert led.chunks_journaled == 2
    led.close()
    # Torn tail: the crash landed mid-append — the partial record is
    # skipped, everything before it loads.
    with open(path, "a") as fh:
        fh.write('{"kind": "chunk", "base": 4, "n"')
    header, completed, done = ledgermod.load(path)
    assert header["trace"] == "abc" and header["chunksize"] == 2
    assert sorted(completed) == [0, 2] and not done
    # the journaled payloads are restorable by digest from the store
    for base, (n, digest) in completed.items():
        values = serialization.loads(store.get_bytes(digest))
        assert len(values) == n


def test_ledger_done_record(tmp_path):
    store = LocalStore(root=str(tmp_path / "objects"))
    path = str(tmp_path / "d.ledger")
    led = ledgermod.MapLedger(path, store, fsync_interval=0.0)
    led.write_header({"job_id": "d", "task_digest": "t", "n_items": 2,
                      "chunksize": 2, "star": False, "trace": None})
    led.record_chunk(0, 2, ["a", "b"])
    led.record_done()
    led.close()
    _, completed, done = ledgermod.load(path)
    assert done and list(completed) == [0]


# ---------------------------------------------------------------------------
# Pool journaling + resume (same-process)
# ---------------------------------------------------------------------------


def test_map_with_job_id_journals_every_chunk():
    job = _unique_job("journal")
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(40))
        assert pool.map(targets.square, xs, chunksize=4, job_id=job) == \
            [x * x for x in xs]
    header, completed, done = ledgermod.load(ledgermod.job_path(job))
    assert done and len(completed) == 10
    assert header["n_items"] == 40 and header["chunksize"] == 4


def test_resume_restores_all_without_reexecution():
    """A completed job's ledger restores every result: the resumed pool
    executes ZERO tasks (exactly-once, proven by the completed/restored
    counters) and returns identical results."""
    job = _unique_job("resume-full")
    xs = list(range(40))
    with fiber_tpu.Pool(2) as pool:
        first = pool.map(targets.square, xs, chunksize=4, job_id=job)
    with fiber_tpu.Pool(2) as pool2:
        second = pool2.map(targets.square, xs, chunksize=4, job_id=job)
        stats = pool2.stats()
    assert second == first
    assert stats["tasks_completed"] == 0
    assert stats["tasks_restored"] == len(xs)


def test_resume_partial_ledger_executes_only_remainder():
    """Truncating the journal to K chunk records (exactly the state a
    crash at that point leaves) makes resume execute total-K chunks —
    wall-time and work proportional to the REMAINDER."""
    job = _unique_job("resume-part")
    xs = list(range(48))
    with fiber_tpu.Pool(2) as pool:
        want = pool.map(targets.square, xs, chunksize=4, job_id=job)
    path = ledgermod.job_path(job)
    with open(path) as fh:
        records = [json.loads(ln) for ln in fh if ln.strip()]
    header = [r for r in records if r["kind"] == "map"]
    chunks = [r for r in records if r["kind"] == "chunk"]
    keep = chunks[:8]  # 12 chunks total; 4 remain
    with open(path, "w") as fh:
        for rec in header + keep:
            fh.write(json.dumps(rec) + "\n")
    with fiber_tpu.Pool(2) as pool2:
        got = pool2.map(targets.square, xs, chunksize=4, job_id=job)
        stats = pool2.stats()
        info = pool2.ledger_stats()
    assert got == want
    assert stats["tasks_restored"] == 8 * 4
    assert stats["tasks_completed"] == len(xs) - 8 * 4
    assert info["restored_chunks"] == 8 and info["pending_chunks"] == 4
    # the resumed run journaled the remainder: the ledger is whole again
    _, completed, done = ledgermod.load(path)
    assert done and len(completed) == 12


def test_resume_rejects_different_task_spec():
    job = _unique_job("resume-reject")
    with fiber_tpu.Pool(2) as pool:
        pool.map(targets.square, list(range(8)), job_id=job)
        with pytest.raises(ValueError, match="different task spec"):
            # same job_id, different item count: refuse rather than
            # resume the wrong workload
            pool.map(targets.square, list(range(9)), job_id=job)


def test_headerless_ledger_starts_fresh():
    """A crash between ledger-file creation and the header fsync leaves
    an empty (or torn) file; re-submitting with that job_id must start
    the job fresh, not fail it."""
    job = _unique_job("headerless")
    path = ledgermod.job_path(job)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write('{"kind": "chu')  # torn first append, no header
    with fiber_tpu.Pool(2) as pool:
        xs = list(range(8))
        assert pool.map(targets.square, xs, job_id=job) == \
            [x * x for x in xs]
    header, completed, done = ledgermod.load(path)
    assert done and header["n_items"] == 8 and len(completed) >= 1


def test_ledger_disabled_config_journals_nothing():
    fiber_tpu.init(ledger_enabled=False)
    try:
        job = _unique_job("disabled")
        with fiber_tpu.Pool(2) as pool:
            xs = list(range(8))
            assert pool.map(targets.square, xs, job_id=job) == \
                [x * x for x in xs]
        assert not os.path.exists(ledgermod.job_path(job))
    finally:
        fiber_tpu.init()


# ---------------------------------------------------------------------------
# the headline crash drill: subprocess master SIGKILL + fiber-tpu resume
# ---------------------------------------------------------------------------


def test_master_sigkill_mid_map_then_cli_resume(tmp_path, capsys):
    """Acceptance criteria drill: a subprocess master running a durable
    map is SIGKILL'd by the seeded ``kill_master_after_chunks`` knob
    once >= 3 chunks are journaled (fsync'd first — the records are
    durable when it dies). ``fiber-tpu resume <job_id>`` then completes
    the map with exactly one result per task; the ledger + pool
    counters prove journaled chunks were restored, not re-executed,
    and the trace id recorded in the header survives the resume
    (envelope-reuse rule)."""
    job = _unique_job("crash")
    plan = chaos.install(chaos.ChaosPlan(
        seed=SEED, token_dir=str(tmp_path / "tokens"),
        kill_master_after_chunks=3, kill_master_times=1))
    # sleep_echo (50ms/task) paces the map so chunk completions
    # interleave with the ledger writer's batches — the kill must land
    # MID-map, not after a single batch journaled everything.
    script = (
        "import fiber_tpu\n"
        "from tests import targets\n"
        "fiber_tpu.init()\n"
        "with fiber_tpu.Pool(2) as pool:\n"
        f"    pool.map(targets.sleep_echo, list(range(48)), chunksize=2,\n"
        f"             job_id={job!r})\n"
    )
    env = dict(os.environ, FIBER_BACKEND="local")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(
                __file__))),
            capture_output=True, text=True, timeout=180)
    finally:
        chaos.uninstall()
    # SIGKILL, not a clean exit — the hardest master loss there is.
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-2000:])
    assert plan.spent("kill-master") == 1
    header, completed, done = ledgermod.load(ledgermod.job_path(job))
    assert not done
    journaled = len(completed)
    assert 3 <= journaled < 24  # died mid-map with durable progress
    # give the orphaned subprocess workers a beat to notice the dead
    # master and exit before the resume spins up fresh ones
    time.sleep(1.0)
    from fiber_tpu import cli

    out_path = str(tmp_path / "results.bin")
    rc = cli.main(["resume", job, "--processes", "2",
                   "--out", out_path])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # exactly one result per task: restored + executed == total, with
    # zero re-execution of the journaled chunks
    assert summary["tasks"] == 48
    assert summary["restored_chunks"] == journaled
    assert summary["restored_tasks"] == 2 * journaled
    assert summary["executed_tasks"] == 48 - 2 * journaled
    # trace ids survive resume (the envelope-reuse rule)
    assert summary["trace"] == header["trace"]
    with open(out_path, "rb") as fh:
        results = serialization.loads(fh.read())
    assert results == list(range(48))
    # the resumed run completed the journal
    _, completed_after, done_after = ledgermod.load(
        ledgermod.job_path(job))
    assert done_after and len(completed_after) == 24


# ---------------------------------------------------------------------------
# disk-tier digest verification (corrupt spill / host cache)
# ---------------------------------------------------------------------------


def test_read_disk_verifies_digest_and_quarantines(tmp_path):
    store = LocalStore(root=str(tmp_path / "objects"))
    data = b"payload-bytes" * 100
    ref = store.put_bytes(data, persist=True)
    path = store._path(ref.digest)
    assert os.path.exists(path)
    # drop the entry (RAM + disk), then plant a corrupt file at its
    # content address: the next read must detect the mismatch,
    # quarantine the file and report a miss
    store.delete(ref.digest)
    with open(path, "wb") as fh:
        fh.write(b"\xff" + data[1:])
    assert store.get_bytes(ref.digest) is None
    assert store.stats()["disk_corrupt"] == 1
    assert not os.path.exists(path)  # quarantined: a refetch republishes
    # republication straight-up works afterwards
    store.put_bytes(data, persist=True, digest=ref.digest)
    assert store.get_bytes(ref.digest) == data


def test_corrupt_cache_degrades_to_refetch_zero_lost_tasks(tmp_path):
    """Seeded corrupt_store_disk drill: the first disk publication of
    the broadcast writes corrupted bytes (one budget token, cluster
    wide). The digest check turns that into a miss + wire refetch — the
    map completes with every task correct and no inline fallback."""
    chaos.install(chaos.ChaosPlan(seed=SEED,
                                  token_dir=str(tmp_path / "tokens"),
                                  corrupt_store_disk=1))
    try:
        rng = np.random.default_rng(int.from_bytes(os.urandom(8), "big"))
        arr = rng.standard_normal(512 * 1024).astype(np.float32)  # 2MB
        with fiber_tpu.Pool(2) as pool:
            out = pool.starmap(targets.arr_sum_plus,
                               [(arr, i) for i in range(24)],
                               chunksize=2)
            stats = pool.store_stats()
        want = float(arr.sum())
        assert [round(v - want) for v in out] == list(range(24))
        assert chaos.active().spent("corrupt-disk") == 1
        # the corrupt publication forced at least one extra wire fetch
        # (degrade-to-refetch), and nothing fell back to inline resend
        assert stats["gets"] >= 2
        assert stats["inline_fallbacks"] == 0
    finally:
        chaos.uninstall()
        fiber_tpu.init()


# ---------------------------------------------------------------------------
# precious-digest replication + host revive
# ---------------------------------------------------------------------------


def test_replicator_copies_precious_to_healthy_host():
    rep = Replicator()
    payloads = {digest_of(b"a" * 64): b"a" * 64,
                digest_of(b"b" * 64): b"b" * 64}
    rep.note(payloads)
    hosts = {"h2": {}, "h3": {digest_of(b"b" * 64): b"b" * 64}}
    copied = rep.replicate_for_suspect(
        "h1", ["h2", "h3"],
        get_bytes=payloads.get,
        host_has=lambda h, d: d in hosts[h],
        host_put=lambda h, d, data: hosts[h].__setitem__(d, data),
    )
    # digest "a": copied to h2; digest "b": h2 lacks it -> copied there
    # too (the first healthy host that lacks it gets the replica)
    assert copied == 2
    assert set(hosts["h2"]) == set(payloads)
    assert rep.snapshot()["replicated"] == 2
    # refcounted forget: noted once, forgotten once -> empty registry
    rep.forget(payloads)
    assert rep.snapshot()["precious"] == 0


def test_replicator_skips_digests_with_live_replicas():
    rep = Replicator()
    d = digest_of(b"x" * 32)
    rep.note([d])
    hosts = {"h2": {d: b"x" * 32}}
    copied = rep.replicate_for_suspect(
        "h1", ["h2"],
        get_bytes={d: b"x" * 32}.get,
        host_has=lambda h, dd: dd in hosts[h],
        host_put=lambda h, dd, data: hosts[h].__setitem__(dd, data),
    )
    assert copied == 0 and rep.snapshot()["failed"] == 0


def test_backend_replicates_precious_on_suspect_and_revive_clears_breaker(
        tmp_path):
    """TpuBackend wiring, end to end against embedded agents: noting a
    precious digest + declaring one host suspect copies the payload
    into the OTHER host's cache (agent store_put); a later beat revives
    the host and clears its spawn breaker (the satellite regression —
    a recovered host must not stay parked behind an open breaker)."""
    import threading

    from fiber_tpu import config, store as storemod
    from fiber_tpu.backends.tpu import TpuBackend
    from fiber_tpu.host_agent import HostAgent
    from fiber_tpu.store.replicate import REPLICATOR

    agents = [HostAgent(0, bind="127.0.0.1",
                        staging_root=str(tmp_path / f"host{i}"))
              for i in range(2)]
    for a in agents:
        threading.Thread(target=a.serve_forever, daemon=True).start()
    hosts = ",".join(f"127.0.0.1:{a.port}" for a in agents)
    old_hosts = config.get().tpu_hosts
    # Big breaker backoff: allow() must stay False until the REVIVE
    # clears it — an expired open period would make the assertion
    # vacuous.
    config.get().update(tpu_hosts=hosts, heartbeat_interval=0.1,
                        suspect_timeout=0.5,
                        spawn_breaker_backoff=30.0,
                        spawn_breaker_backoff_max=60.0)
    backend = TpuBackend()
    # The prober would keep beating these perfectly healthy embedded
    # agents; stop it so silence (a "down" host) can accrue on demand.
    backend._prober.stop()
    try:
        payload = b"precious-result-payload" * 10
        digest = digest_of(payload)
        storemod.local_store().put_bytes(payload, digest=digest)
        REPLICATOR.note([digest])
        suspect, healthy = backend._hosts
        # direct call (the detector's on_suspect runs the same method on
        # a thread): the healthy host's cache must gain the payload
        assert backend._replicate_precious(suspect) == 1
        assert backend._agent(healthy).call("store_has", digest)
        assert bytes(backend.fetch_object(digest)) == payload
        REPLICATOR.forget([digest])

        # revive path: open the breaker for the suspect host, declare it
        # suspect via the detector, then beat — on_revive must clear the
        # breaker so placement resumes immediately
        detector = backend._detector
        assert detector is not None
        for _ in range(8):
            backend._host_breaker.record_failure(suspect)
        assert not backend._host_breaker.allow(suspect)
        detector.beat(suspect)
        deadline = time.monotonic() + 5.0
        while not detector.is_suspect(suspect) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert detector.is_suspect(suspect)
        detector.beat(suspect)  # the host answers again
        assert not detector.is_suspect(suspect)
        assert backend._host_breaker.allow(suspect)
        assert backend.host_health()[f"{suspect[0]}:{suspect[1]}"] == "ok"
    finally:
        backend.shutdown_sim_cluster()
        config.get().update(tpu_hosts=old_hosts)
        fiber_tpu.init()
        for a in agents:
            a.stop()
