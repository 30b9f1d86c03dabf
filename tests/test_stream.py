"""Streaming data plane (docs/streaming.md): windowed admission,
end-to-end backpressure, incremental result spill, and the stream
ledger + cursor resume.

Coverage map:
* ordered/unordered streaming over plain GENERATORS — nothing is
  materialized, results are exact, accounting bills streamed tasks
  exactly-once under the map's billing key;
* windowed admission + backpressure: a slow consumer parks the
  admission loop (``pool_stream_admit_waits`` > 0) and the task queue
  never grows past the window — no unbounded buffering anywhere;
* slot release: an unordered stream frees each yielded slot's payload
  reference immediately (popped from the entry's pending dict; the
  dedup bitmap is all that remains), and stream chunk contexts (the
  storemiss/resubmit source) drop as chunks fill;
* chaos drills: a worker hard-killed mid-stream loses nothing and
  duplicates nothing; a straggler-for-life provokes speculation on a
  stream chunk whose source items are no longer reachable from the
  iterator (the encoded payload is the only copy — envelope-reuse);
* durability: the stream ledger journals admits/results/cursor; a
  SUBPROCESS master SIGKILL'd mid-stream at ~60% consumed is resumed
  by ``fiber-tpu resume`` — journaled results restore, only
  unjournaled admitted chunks re-execute, and the consumed prefix plus
  the emitted suffix covers the admitted stream exactly once;
* the non-streaming fallback (``stream_enabled=False``) still accepts
  any iterable and only materializes when the classic ledger demands a
  fixed task digest.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import fiber_tpu
from fiber_tpu import serialization
from fiber_tpu.pool import RemoteError
from fiber_tpu.store import ledger as ledgermod
from fiber_tpu.testing import chaos
from tests import targets

SEED = int(os.environ.get("FIBER_CHAOS_SEED", "7"))


def _unique_job(tag: str) -> str:
    return f"{tag}-{os.getpid()}-{int.from_bytes(os.urandom(4), 'big')}"


def _gen(n):
    """A one-shot generator: the streaming path must never need len()
    or a second pass."""
    for i in range(n):
        yield i


@pytest.fixture(autouse=True)
def _config_restore():
    yield
    fiber_tpu.init()


# ---------------------------------------------------------------------------
# streaming basics: ordered, unordered, exact accounting
# ---------------------------------------------------------------------------


def test_imap_streams_a_generator_ordered():
    fiber_tpu.init(stream_window=4)
    pulled = [0]

    def counted(n):
        for i in _gen(n):
            pulled[0] += 1
            yield i

    with fiber_tpu.Pool(2) as pool:
        out, ahead = [], 0
        for v in pool.imap(targets.square, counted(300), chunksize=8):
            ahead = max(ahead, pulled[0] - len(out))
            out.append(v)
        assert out == [i * i for i in range(300)]
        # the window bounds what is pulled from the producer and not
        # yet handed to the consumer: at most `stream_window` chunks,
        # and the one the admission loop holds while it waits for room
        assert ahead <= (4 + 1) * 8, ahead
        st = pool.stats()
        assert st["tasks_submitted"] == 300
        assert st["tasks_completed"] == 300
        # the stream's per-map state is gone once it completes
        assert st["streams_active"] == 0
        assert not pool._stream_ctx and not pool._stream_windows


def test_imap_unordered_streams_a_generator():
    fiber_tpu.init(stream_window=4)
    with fiber_tpu.Pool(2) as pool:
        out = sorted(pool.imap_unordered(targets.square, _gen(200),
                                         chunksize=8))
        assert out == sorted(i * i for i in range(200))


def test_stream_bills_tasks_exactly_once():
    """Acceptance criteria: streamed tasks reconcile exactly-once
    against tasks_executed under the map's billing key."""
    fiber_tpu.init(stream_window=4)
    job = _unique_job("bill")
    with fiber_tpu.Pool(2) as pool:
        out = list(pool.imap(targets.square, _gen(120), chunksize=8,
                             job_id=job))
        assert out == [i * i for i in range(120)]
        # the final chunk's charge lands in the result-loop thread just
        # after the fill that woke this consumer — accounting is
        # eventually-consistent by a hair (worker cost frames land
        # late too), so reconcile with a short grace window.
        deadline = time.time() + 5.0
        while time.time() < deadline:
            total = pool.cost(job_id=job)["job"]["total"]
            if total.get("tasks") == 120:
                break
            time.sleep(0.02)
        assert total.get("tasks") == 120, total
        st = pool.stats()
        assert st["tasks_completed"] == 120


def test_stream_error_surfaces_at_consumption():
    """A task failure raises RemoteError at its slot; the iterator
    stays usable past the failed slot (IMapIterator semantics survive
    streaming)."""
    fiber_tpu.init(stream_window=4)
    with fiber_tpu.Pool(2) as pool:
        it = pool.imap(targets.raise_on_even, iter([1, 3, 2, 5]),
                       chunksize=1)
        assert next(it) == 1
        assert next(it) == 3
        with pytest.raises(RemoteError):
            next(it)
        assert next(it) == 5


def test_stream_producer_exception_fails_the_stream():
    def bad_gen():
        yield 1
        yield 2
        raise RuntimeError("producer exploded")

    fiber_tpu.init(stream_window=4)
    with fiber_tpu.Pool(2) as pool:
        it = pool.imap(targets.square, bad_gen(), chunksize=1)
        with pytest.raises(Exception):
            list(it)
        # the failed stream must not wedge the pool
        assert pool.map(targets.square, [3]) == [9]


# ---------------------------------------------------------------------------
# windowed admission + end-to-end backpressure
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_slow_consumer_parks_admission_and_bounds_the_queue():
    fiber_tpu.init(stream_window=2)
    with fiber_tpu.Pool(2) as pool:
        it = pool.imap(targets.square, _gen(200), chunksize=4)
        max_depth = 0
        out = []
        for v in it:
            if len(out) < 20:
                time.sleep(0.01)  # consumer slower than the cluster
            max_depth = max(max_depth, pool._taskq.qsize())
            out.append(v)
        assert out == [i * i for i in range(200)]
        st = pool.stats()
        assert st["stream_admit_waits"] > 0, \
            "admission never parked despite a slow consumer"
        # the queue holds at most the admitted-but-unhandled window,
        # never O(n): 200 tasks / 4 = 50 chunks were NOT all queued.
        assert max_depth <= 2 + 1, max_depth
        # the park episodes surfaced on the metrics plane too
        snap = pool.metrics()
        waits = snap["pool_stream_admit_waits"]["series"]
        assert sum(waits.values()) > 0, waits


def test_unwindowed_fallback_still_lazy():
    """stream_enabled=False: any iterable is accepted and dispatch is
    still admission-driven (no list() materialization) — only the
    classic durable path may materialize."""
    fiber_tpu.init(stream_enabled=False)

    class NoLen:
        def __iter__(self):
            return iter(range(50))

        def __len__(self):  # pragma: no cover - must never be called
            raise AssertionError("imap materialized the iterable")

    with fiber_tpu.Pool(2) as pool:
        out = list(pool.imap(targets.square, NoLen(), chunksize=4))
        assert out == [i * i for i in range(50)]
        # no admission window was enforced
        assert pool.stats()["stream_admit_waits"] == 0


def test_fallback_materializes_only_for_classic_ledger():
    """stream_enabled=False + job_id + ledger_enabled: the classic
    whole-map ledger needs f(func, n_items), so the iterable is
    materialized — and the resulting ledger is a classic map journal,
    not a stream."""
    fiber_tpu.init(stream_enabled=False)
    job = _unique_job("classic")
    with fiber_tpu.Pool(2) as pool:
        out = list(pool.imap(targets.square, _gen(40), chunksize=4,
                             job_id=job))
        assert out == [i * i for i in range(40)]
    header, completed, done = ledgermod.load(ledgermod.job_path(job))
    assert header["kind"] == "map" and done
    assert header["n_items"] == 40


def test_abandoned_stream_iterator_does_not_deadlock_close():
    """A consumer that breaks out of a streamed imap and exits the pool
    must not deadlock join(): close() is producer EOF — the admission
    loop truncates the stream instead of parking forever on capacity no
    consumer will ever free."""
    fiber_tpu.init(stream_window=2)
    t0 = time.time()
    with fiber_tpu.Pool(2) as pool:
        it = pool.imap(targets.square, _gen(10000), chunksize=4)
        for i in range(6):
            assert next(it) == i * i
        # abandon the iterator; the `with` exit is the assertion
    assert time.time() - t0 < 60


# ---------------------------------------------------------------------------
# incremental spill + slot release (satellite 2)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_unordered_yield_releases_slot_payload():
    """A stream entry never holds an O(n) slot list: filled-but-
    unyielded values live in a dict bounded by the window (popped at
    grab — the payload reference is gone the moment the consumer takes
    it) and fill dedup rides a bitmap, ~0.125 bytes per task."""
    fiber_tpu.init(stream_window=4)
    with fiber_tpu.Pool(2) as pool:
        seqs = []
        orig_add_stream = pool._store.add_stream

        def spy_add_stream():
            seq = orig_add_stream()
            seqs.append(seq)
            return seq

        pool._store.add_stream = spy_add_stream
        try:
            it = pool.imap_unordered(
                targets.big_result, iter([1 << 20] * 24), chunksize=2)
            peak_pending = 0
            n = 0
            for v in it:
                n += 1
                assert v.nbytes == 1 << 20
                [seq] = seqs
                entry = pool._store._entries.get(seq)
                if entry is not None:
                    assert entry.stream and entry.values == []
                    assert isinstance(entry.bits, bytearray)
                    peak_pending = max(peak_pending,
                                       len(entry.pending))
            assert n == 24
            # live (1MB) payloads in the store never exceeded the
            # window, regardless of stream length
            assert peak_pending <= 4 * 2 + 2, peak_pending
        finally:
            pool._store.add_stream = orig_add_stream
        # chunk contexts (resubmit source) released as chunks filled
        assert not pool._stream_ctx


@pytest.mark.slow
def test_master_rss_stays_flat_across_big_result_stream():
    """Satellite-2 regression: master peak RSS for a LONG unordered
    stream of 1MB results is bounded by the window, not the stream —
    compared against a SHORT run in its own interpreter (ru_maxrss is a
    lifetime peak, so each arm needs a fresh process)."""
    script = (
        "import sys, resource, fiber_tpu\n"
        "from tests import targets\n"
        "n = int(sys.argv[1])\n"
        "fiber_tpu.init(stream_window=4)\n"
        "with fiber_tpu.Pool(2) as pool:\n"
        "    k = 0\n"
        "    for v in pool.imap_unordered(targets.big_result,\n"
        "                                 iter([1 << 20] * n),\n"
        "                                 chunksize=2):\n"
        "        k += 1\n"
        "    assert k == n, (k, n)\n"
        "print(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)\n"
    )
    env = dict(os.environ, FIBER_BACKEND="local")
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def rss(n: int) -> int:
        proc = subprocess.run(
            [sys.executable, "-c", script, str(n)], env=env, cwd=cwd,
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return int(proc.stdout.strip().splitlines()[-1])

    short, long_ = rss(16), rss(256)
    # 256MB of results flowed through the long arm; O(n) retention
    # would add ~240MB over the short arm. O(window) keeps them close.
    assert long_ <= short * 1.5 + 64 * 1024, (short, long_)


# ---------------------------------------------------------------------------
# chaos drills: worker kill, speculation on a stream chunk
# ---------------------------------------------------------------------------


def test_worker_killed_mid_stream_loses_and_duplicates_nothing(tmp_path):
    plan = chaos.install(chaos.ChaosPlan(
        seed=SEED, token_dir=str(tmp_path / "tokens"),
        kill_after_chunks=2, kill_times=1))
    try:
        fiber_tpu.init(stream_window=8)
        with fiber_tpu.Pool(2) as pool:
            out = list(pool.imap(targets.square, _gen(120),
                                 chunksize=4))
            # ordered equality == zero lost AND zero duplicate yields
            assert out == [i * i for i in range(120)]
        assert plan.spent("kill") == 1
    finally:
        chaos.uninstall()


@pytest.mark.slow
def test_speculation_fires_on_stream_chunk(tmp_path):
    """A straggler-for-life holds a stream chunk whose source items are
    long gone from the producer iterator — speculation must duplicate
    from the scheduler's retained payload (envelope-reuse rule) and the
    dedup at fill keeps results exact."""
    plan = chaos.install(chaos.ChaosPlan(
        seed=SEED, token_dir=str(tmp_path / "tokens"),
        slow_worker_after_chunks=4, slow_worker_s=2.0,
        slow_worker_times=1))
    try:
        fiber_tpu.init(stream_window=16, speculation_enabled=True,
                       speculation_quantile=1.2)
        with fiber_tpu.Pool(2) as pool:
            out = list(pool.imap(targets.sleep_echo, _gen(40),
                                 chunksize=1))
            assert out == list(range(40))
            assert pool._sched.decisions.get("speculate", 0) >= 1, \
                pool._sched.decisions
        assert plan.spent("slow") == 1
    finally:
        chaos.uninstall()


# ---------------------------------------------------------------------------
# stream ledger: journal format, pool-level resume, CLI crash resume
# ---------------------------------------------------------------------------


def test_stream_ledger_journal_and_cursor():
    fiber_tpu.init(stream_window=4)
    job = _unique_job("journal")
    with fiber_tpu.Pool(2) as pool:
        out = list(pool.imap(targets.square, _gen(96), chunksize=8,
                             job_id=job))
        assert out == [i * i for i in range(96)]
    path = ledgermod.job_path(job)
    header, admits, completed, cursor, done = ledgermod.load_stream(path)
    assert header["kind"] == "stream"
    assert header["task_digest"] == ledgermod.stream_task_digest(
        targets.square, False)
    assert "n_items" not in header  # stream identity is length-free
    assert len(admits) == 12 and len(completed) == 12 and done
    assert set(completed) <= set(admits)
    # cursor only tracks consumption while the ledger is open (the
    # writer may close the journal before a fast consumer catches up;
    # record_cursor after close is a documented no-op)
    assert 0 <= cursor <= 96 and cursor % 8 == 0
    # classic load() reads the header too (cmd_resume branches on kind)
    h2, _, done2 = ledgermod.load(path)
    assert h2["kind"] == "stream" and done2


def test_stream_cursor_is_last_wins(tmp_path):
    path = str(tmp_path / "c.ledger")
    with open(path, "w") as fh:
        fh.write(json.dumps({"kind": "stream", "v": 1, "job_id": "j",
                             "task_digest": "t", "spec": "s",
                             "chunksize": 2, "star": False}) + "\n")
        fh.write(json.dumps({"kind": "cursor", "consumed": 90}) + "\n")
        # a fresh consumer restarted from zero: its lower positions
        # must supersede the dead run's high-water mark
        fh.write(json.dumps({"kind": "cursor", "consumed": 10}) + "\n")
    _, _, _, cursor, _ = ledgermod.load_stream(path)
    assert cursor == 10


@pytest.mark.slow
def test_stream_resume_in_process_restores_journaled_chunks():
    """Re-calling imap with the same job_id replays the journal: the
    already-journaled chunks restore (billed tasks_restored, never
    re-executed) and only the remainder runs."""
    fiber_tpu.init(stream_window=4)
    job = _unique_job("replay")
    with fiber_tpu.Pool(2) as pool:
        out = list(pool.imap(targets.square, _gen(64), chunksize=8,
                             job_id=job))
        assert out == [i * i for i in range(64)]
    # strip the done record so the replay sees an open stream
    path = ledgermod.job_path(job)
    lines = [ln for ln in open(path)
             if json.loads(ln).get("kind") != "done"]
    open(path, "w").writelines(lines)
    with fiber_tpu.Pool(2) as pool:
        out = list(pool.imap(targets.square, _gen(64), chunksize=8,
                             job_id=job))
        assert out == [i * i for i in range(64)]
        st = pool.stats()
        assert st["tasks_restored"] == 64  # all journaled; none re-ran


@pytest.mark.slow
def test_stream_job_id_rejects_different_task_spec():
    fiber_tpu.init(stream_window=4)
    job = _unique_job("mismatch")
    with fiber_tpu.Pool(2) as pool:
        list(pool.imap(targets.square, _gen(16), chunksize=4,
                       job_id=job))
    with fiber_tpu.Pool(2) as pool:
        with pytest.raises(ValueError, match="different task spec"):
            list(pool.imap(targets.sleep_echo, _gen(16), chunksize=4,
                           job_id=job))


@pytest.mark.slow
def test_master_sigkill_mid_stream_then_cli_resume(tmp_path, capsys):
    """The headline stream crash drill: a subprocess master streaming a
    durable imap is SIGKILL'd once >= 6 result chunks are journaled,
    with the consumer ~at pace (it logs every yielded value). Resume
    restores journaled results, re-executes ONLY unjournaled admitted
    chunks from their journaled input payloads, and emits everything
    past the journaled cursor: consumed-prefix + emitted-suffix covers
    the admitted stream exactly once."""
    job = _unique_job("skill")
    consumed_path = str(tmp_path / "consumed.txt")
    plan = chaos.install(chaos.ChaosPlan(
        seed=SEED, token_dir=str(tmp_path / "tokens"),
        kill_master_after_chunks=6, kill_master_times=1))
    script = (
        "import fiber_tpu\n"
        "from tests import targets\n"
        "fiber_tpu.init(stream_window=8)\n"
        "def gen():\n"
        "    for i in range(96):\n"
        "        yield i\n"
        "with fiber_tpu.Pool(2) as pool:\n"
        f"    with open({consumed_path!r}, 'w') as fh:\n"
        "        for v in pool.imap(targets.sleep_echo, gen(),\n"
        f"                           chunksize=2, job_id={job!r}):\n"
        "            fh.write(f'{v}\\n')\n"
        "            fh.flush()\n"
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
    assert proc.returncode == -9, (proc.returncode, proc.stderr[-2000:])
    assert plan.spent("kill-master") == 1
    header, admits, completed, cursor, done = ledgermod.load_stream(
        ledgermod.job_path(job))
    assert not done
    assert 6 <= len(completed) < 48  # died mid-stream, progress durable
    assert set(completed) <= set(admits)
    consumed = [int(x) for x in open(consumed_path).read().split()]
    # ordered stream: the consumed prefix is exact and duplicate-free
    assert consumed == list(range(len(consumed)))
    assert cursor <= len(consumed)
    time.sleep(1.0)  # let orphaned workers notice the dead master
    from fiber_tpu import cli

    out_path = str(tmp_path / "resumed.bin")
    rc = cli.main(["resume", job, "--processes", "2",
                   "--out", out_path])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    n_admitted = sum(n for n, _ in admits.values())
    assert summary["kind"] == "stream"
    assert summary["tasks"] == n_admitted
    assert summary["restored_chunks"] == len(completed)
    assert summary["restored_tasks"] == 2 * len(completed)
    assert summary["executed_tasks"] == n_admitted - 2 * len(completed)
    assert summary["consumed"] == cursor
    with open(out_path, "rb") as fh:
        emitted = serialization.loads(fh.read())
    # exactly-once over the admitted stream: journaled-consumed prefix
    # + emitted suffix == every admitted task's result, no dup, no gap
    assert consumed[:cursor] + emitted == list(range(n_admitted))
    # the resumed run completed the journal
    _, _, completed_after, _, done_after = ledgermod.load_stream(
        ledgermod.job_path(job))
    assert done_after and len(completed_after) == len(admits)
