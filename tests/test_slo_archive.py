"""Persistent observability archive + per-tenant SLO plane
(docs/observability.md "SLOs and the archive").

Coverage map:
* archive write/read roundtrip: record kinds, sample-field point
  queries, label filters, time-range filters;
* the ledger posture inherited wholesale: torn-tail lines skipped and
  counted (never returned), newer-version segments refused, segment
  roll + age/size retention (the live segment is never pruned), a
  restarted writer appending BESIDE its predecessor's segments;
* fixed-bucket histogram quantile math;
* burn-rate math (bad-fraction / budget over fast + slow windows), the
  edge-triggered ``slo_burn`` raise/clear through the watchdog, job-id
  dedup, and archive replay rebuilding windows + the dedup set;
* daemon integration: ``slo``/``query`` verbs, status summary, the
  SIGSTOP-free in-process restart drill (stop daemon, wipe the SLO
  plane, restart — replay restores the tenant's history);
* serve protocol version-mismatch posture: an unknown/newer verb gets
  a structured ``(False, ...)`` reply on a connection that stays
  usable — no hang, no kill;
* ``fiber-tpu slo`` / ``history`` / ``jobs --json`` CLI surfaces and
  the ``scripts/check_docs_nav.py`` lint guard.
"""

import contextlib
import json
import os
import subprocess
import sys
import time
from multiprocessing.connection import Client

import pytest

import fiber_tpu
from fiber_tpu import config
from fiber_tpu.cli import build_parser
from fiber_tpu.host_agent import cluster_authkey
from fiber_tpu.serve import protocol
from fiber_tpu.serve.client import ServeClient
from fiber_tpu.serve.daemon import ServeDaemon
from fiber_tpu.serve.jobs import JobRunner
from fiber_tpu.telemetry.archive import (ARCHIVE, ARCHIVE_VERSION,
                                         MetricsArchive)
from fiber_tpu.telemetry.flightrec import FLIGHT
from fiber_tpu.telemetry.monitor import WATCHDOG
from fiber_tpu.telemetry.policy import POLICY
from fiber_tpu.telemetry.slo import SLO, _Hist, BUCKETS, SloTracker
from tests import targets
from tests._chains import assert_linked_chain

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _slo_isolation():
    """Pristine singletons per test (archive writer disarmed, SLO
    windows and watchdog state empty), restored on the way out."""
    ARCHIVE.disable()
    ARCHIVE.clear()
    SLO.clear()
    WATCHDOG.clear()
    FLIGHT.clear()
    POLICY.reset()
    yield
    ARCHIVE.disable()
    ARCHIVE.clear()
    SLO.clear()
    WATCHDOG.clear()
    fiber_tpu.init()


@contextlib.contextmanager
def _cfg(**knobs):
    cfg = config.get()
    old = {k: getattr(cfg, k) for k in knobs}
    cfg.update(**knobs)
    try:
        yield
    finally:
        cfg.update(**old)


@contextlib.contextmanager
def _daemon(tmp_path, processes=2, **knobs):
    """In-process daemon with a PRIVATE journal + archive directory."""
    knobs.setdefault("archive_dir", str(tmp_path / "archive"))
    with _cfg(**knobs):
        runner = JobRunner(processes=processes,
                           journal_dir=str(tmp_path / "serve-journal"))
        daemon = ServeDaemon(port=0, runner=runner)
        daemon.start_background()
        client = ServeClient(("127.0.0.1", daemon.port))
        try:
            yield daemon, client
        finally:
            client.close()
            daemon.stop(terminate_pool=True)


def _poll(predicate, deadline_s=30.0, interval=0.05, what="condition"):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


def _unique_job(tag: str) -> str:
    return f"{tag}-{os.getpid()}-{int.from_bytes(os.urandom(4), 'big')}"


# ---------------------------------------------------------------------------
# archive: write/read roundtrip
# ---------------------------------------------------------------------------


def test_archive_kinds_labels_and_ranges(tmp_path):
    # no sampler thread: a tick of its own between enable and query
    # archives a second ``tasks_per_s`` point (seen once in a whole run)
    fiber_tpu.init(monitor_enabled=False)
    ARCHIVE.enable(str(tmp_path / "arch"))
    now = time.time()
    ARCHIVE.append("slo_obs", {"tenant": "alice", "state": "done",
                               "ts": now - 30})
    ARCHIVE.append("slo_obs", {"tenant": "bob", "state": "failed",
                               "ts": now - 20})
    ARCHIVE.append("slo_obs", {"tenant": "alice", "state": "done",
                               "ts": now - 10})
    ARCHIVE.append("cost", {"job_id": "j1", "total": 4.2})
    FLIGHT.record("policy", "outcome", cause_id="c-1", outcome="resolved")
    ARCHIVE.on_sample({"wall": now, "tasks_per_s": 7.5,
                       "note": "non-numeric fields are dropped"})

    obs = ARCHIVE.query("slo_obs")
    assert [o["tenant"] for o in obs] == ["alice", "bob", "alice"]
    assert all(o["kind"] == "slo_obs" for o in obs)
    # label filter: subset equality
    assert len(ARCHIVE.query("slo_obs", labels={"tenant": "alice"})) == 2
    assert len(ARCHIVE.query("slo_obs",
                             labels={"tenant": "bob",
                                     "state": "failed"})) == 1
    assert ARCHIVE.query("slo_obs", labels={"tenant": "nobody"}) == []
    # time range: [since, until] on the record ts
    mid = ARCHIVE.query("slo_obs", since=now - 25, until=now - 15)
    assert [o["tenant"] for o in mid] == ["bob"]
    # a sample FIELD query returns {ts, value} points
    pts = ARCHIVE.query("tasks_per_s")
    assert len(pts) == 1 and pts[0]["value"] == 7.5
    assert set(pts[0]) == {"ts", "value"}
    # non-numeric sample fields never landed
    assert ARCHIVE.query("note") == []
    assert len(ARCHIVE.query("cost")) == 1
    # the tick drained the flight ring: a chain's event reads back out
    # of the archive with its link
    evs = ARCHIVE.query("event", labels={"plane": "policy",
                                         "cause_id": "c-1"})
    assert [e["event"] for e in evs] == ["outcome"]
    stats = ARCHIVE.stats()
    assert stats["enabled"] and stats["segments"] == 1
    assert stats["torn_lines"] == 0


def test_archive_disabled_is_a_noop(tmp_path):
    fresh = MetricsArchive()
    assert fresh.append("slo_obs", {"tenant": "x"}) is False
    assert fresh.query("slo_obs") == [] or True  # no dir -> no records


def test_archive_torn_tail_skipped_and_counted(tmp_path):
    ARCHIVE.enable(str(tmp_path / "arch"))
    for i in range(3):
        ARCHIVE.append("slo_obs", {"tenant": "alice", "i": i})
    ARCHIVE.flush()
    # SIGKILL mid-write leaves a partial final line
    live = ARCHIVE._fh.name
    with open(live, "a") as fh:
        fh.write('{"kind": "slo_obs", "tenant": "alice", "i"')
    got = ARCHIVE.query("slo_obs")
    assert [r["i"] for r in got] == [0, 1, 2]  # torn record NOT returned
    assert ARCHIVE.torn_lines == 1
    assert ARCHIVE.stats()["torn_lines"] == 1
    # a second query does not re-count into returned records
    assert len(ARCHIVE.query("slo_obs")) == 3


def test_archive_refuses_newer_version_segments(tmp_path):
    d = tmp_path / "arch"
    ARCHIVE.enable(str(d))
    ARCHIVE.append("slo_obs", {"tenant": "old", "ts": time.time()})
    # a segment written by a FUTURE format version
    alien = d / f"seg-{int(time.time()) - 5}-99999.jsonl"
    with open(alien, "w") as fh:
        fh.write(json.dumps({"kind": "header",
                             "v": ARCHIVE_VERSION + 1}) + "\n")
        fh.write(json.dumps({"kind": "slo_obs", "tenant": "future",
                             "ts": time.time()}) + "\n")
    got = ARCHIVE.query("slo_obs")
    assert [r["tenant"] for r in got] == ["old"]
    assert ARCHIVE.refused_segments == 1


def test_archive_segment_roll_and_retention(tmp_path):
    ARCHIVE.enable(str(tmp_path / "arch"))
    ARCHIVE.segment_s = 0.05
    ARCHIVE.fsync_s = 0.0  # flush every append: mtime == append time
    ARCHIVE.append("slo_obs", {"tenant": "a"})
    time.sleep(0.12)
    ARCHIVE.append("slo_obs", {"tenant": "b"})
    assert ARCHIVE.stats()["segments"] == 2
    # age prune: everything whose window closed past the horizon dies
    # on the next roll — except the live segment
    ARCHIVE.retention_s = 0.01
    time.sleep(0.12)
    ARCHIVE.append("slo_obs", {"tenant": "c"})
    assert ARCHIVE.stats()["segments"] == 1
    assert ARCHIVE.segments_pruned >= 2
    assert [r["tenant"] for r in ARCHIVE.query("slo_obs")] == ["c"]
    # size prune: oldest-first until under the cap, live survives
    ARCHIVE.retention_s = 3600.0
    ARCHIVE.max_bytes = 1
    time.sleep(0.12)
    ARCHIVE.append("slo_obs", {"tenant": "d"})
    assert ARCHIVE.stats()["segments"] == 1
    assert [r["tenant"] for r in ARCHIVE.query("slo_obs")] == ["d"]


def test_archive_restarted_writer_appends_beside(tmp_path):
    """A second writer (new daemon pid after SIGKILL) must merge the
    predecessor's segments into its queries, never truncate them."""
    d = str(tmp_path / "arch")
    ARCHIVE.enable(d)
    ARCHIVE.append("slo_obs", {"tenant": "before", "ts": time.time()})
    ARCHIVE.flush()
    first_segs = {s["path"] for s in ARCHIVE._segments()}
    successor = MetricsArchive()
    successor.enable(d)
    successor.append("slo_obs", {"tenant": "after", "ts": time.time()})
    tenants = [r["tenant"] for r in successor.query("slo_obs")]
    assert tenants == ["before", "after"]
    assert first_segs <= {s["path"] for s in successor._segments()}
    successor.disable()


# ---------------------------------------------------------------------------
# histogram + burn-rate math
# ---------------------------------------------------------------------------


def test_hist_bucket_quantiles():
    h = _Hist()
    assert h.quantile(0.95) is None
    for _ in range(95):
        h.add(0.04)          # -> 0.05 bucket
    for _ in range(5):
        h.add(4.0)           # -> 5.0 bucket
    assert h.quantile(0.50) == 0.05
    assert h.quantile(0.95) == 0.05
    assert h.quantile(0.99) == 5.0
    snap = h.snapshot()
    assert snap["n"] == 100 and snap["p50"] == 0.05
    # overflow reports the last finite bound (an honest floor)
    over = _Hist()
    over.add(10_000.0)
    assert over.quantile(0.5) == BUCKETS[-1]


def _tracker(**knobs):
    with _cfg(**knobs):
        t = SloTracker()
        t.configure(config.get())
    return t


def test_burn_rate_math_multi_window():
    t = _tracker(serve_slo_error_pct=0.1, serve_slo_latency_s=1.0,
                 serve_slo_p=0.9, serve_slo_window_s=600.0,
                 serve_slo_fast_window_s=60.0, serve_slo_burn=2.0)
    now = time.time()
    for i in range(10):  # bob: 4/10 failed inside the fast window
        t.observe("bob", "failed" if i < 4 else "done", latency=0.1,
                  job_id=f"b{i}", ts=now - 30, archive=False)
    for i in range(5):   # alice: every job misses the latency target
        t.observe("alice", "done", latency=2.0, job_id=f"a{i}",
                  ts=now - 30, archive=False)
    burns = t.burn_rates(now)
    # error burn = bad fraction / budget = 0.4 / 0.1
    assert burns["bob"]["error"]["burn_fast"] == pytest.approx(4.0)
    assert burns["bob"]["error"]["burn_slow"] == pytest.approx(4.0)
    # latency burn = 1.0 / (1 - p) = 1.0 / 0.1
    assert burns["alice"]["latency"]["burn_fast"] == pytest.approx(10.0)
    assert burns["alice"]["error"]["burn_fast"] == pytest.approx(0.0)
    # the aggregate pseudo-tenant pools every observation
    assert burns["*"]["error"]["burn_fast"] == pytest.approx(
        (4 / 15) / 0.1)
    # an observation OUTSIDE the fast window splits the two windows
    t.observe("carol", "failed", job_id="c0", ts=now - 300,
              archive=False)
    carol = t.burn_rates(now)["carol"]["error"]
    assert carol["burn_fast"] is None       # nothing recent
    assert carol["burn_slow"] == pytest.approx(10.0)


def test_evaluate_raises_refreshes_and_clears_slo_burn():
    t = _tracker(serve_slo_error_pct=0.1, serve_slo_latency_s=1.0,
                 serve_slo_p=0.9, serve_slo_window_s=600.0,
                 serve_slo_fast_window_s=60.0, serve_slo_burn=2.0)
    now = time.time()
    for i in range(10):
        t.observe("bob", "failed" if i < 4 else "done", latency=2.0,
                  job_id=f"b{i}", ts=now - 10, archive=False)
    worst = t.evaluate(now)
    # the worst objective wins: latency burns 10x vs error's 4x
    assert worst == {"tenant": "bob", "sli": "latency", "burn": 10.0,
                     "burn_fast": 10.0, "burn_slow": 10.0}
    active = WATCHDOG.snapshot()["active"]
    assert "slo_burn" in active
    assert active["slo_burn"]["tenant"] == "bob"
    assert active["slo_burn"]["burn"] == 10.0
    # the policy plane answered, and the ring holds the whole chain:
    # slo_burn -> boost_and_throttle -> outcome, linked by cause_id
    chain = assert_linked_chain("slo_burn")
    assert chain["actions"][0]["kind"] == "boost_and_throttle"
    # still burning -> refresh (no second anomaly), then age out -> clear
    assert t.evaluate(now + 1) is not None
    assert t.evaluate(now + 3600) is None
    assert "slo_burn" not in WATCHDOG.snapshot()["active"]
    raised = [e for e in FLIGHT.snapshot()
              if e.get("plane") == "monitor"
              and e.get("kind") == "slo_burn"]
    assert len(raised) == 1  # edge-triggered: one raise, not per-tick
    cleared = [e for e in FLIGHT.snapshot()
               if e.get("kind") == "clear"
               and e.get("rule") == "slo_burn"]
    assert len(cleared) == 1
    assert cleared[0]["cause_id"] == raised[0]["id"]


def test_observe_dedups_by_job_id_and_replay_restores(tmp_path):
    ARCHIVE.enable(str(tmp_path / "arch"))
    knobs = dict(serve_slo_error_pct=0.1, serve_slo_window_s=600.0,
                 serve_slo_fast_window_s=60.0, serve_slo_burn=2.0)
    t = _tracker(**knobs)
    now = time.time()
    t.observe("alice", "done", latency=0.5, queue_wait=0.1, tasks=8,
              job_id="dup", ts=now - 5)
    t.observe("alice", "done", latency=0.5, job_id="dup", ts=now - 5)
    for i in range(3):
        t.observe("bob", "failed", latency=0.2, job_id=f"b{i}",
                  ts=now - 5)
    assert t.observations == 4  # the duplicate never landed
    # a fresh tracker (daemon restarted after SIGKILL) replays the tail
    fresh = _tracker(**knobs)
    assert fresh.replay(now) == 4
    snap = fresh.snapshot()
    assert snap["window_jobs"] == 4 and snap["observations"] == 4
    assert snap["tenants"]["bob"]["error_rate"] == pytest.approx(1.0)
    assert snap["tenants"]["alice"]["latency"]["n"] == 1
    assert snap["tenants"]["alice"]["tasks"] == 8
    # replayed observations restore the dedup set too
    fresh.observe("alice", "done", latency=0.5, job_id="dup",
                  ts=now - 5, archive=False)
    assert fresh.snapshot()["observations"] == 4
    # burn carried across the "restart"
    assert fresh.burn_rates(now)["bob"]["error"][
        "burn_fast"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# daemon integration
# ---------------------------------------------------------------------------


def test_daemon_slo_and_query_verbs(tmp_path):
    with _daemon(tmp_path, serve_warm_floor=1,
                 serve_tick_s=0.05) as (daemon, client):
        a = client.submit(targets.square, range(6), tenant="alice",
                          job_id=_unique_job("slo-a"))
        assert client.wait(a, timeout=60)["state"] == protocol.DONE
        # the tick thread folds the terminal job into the SLIs
        snap = _poll(
            lambda: (s := client.slo())["tenants"]
            and "alice" in s["tenants"] and s,
            what="slo observation")
        alice = snap["tenants"]["alice"]
        assert alice["jobs"] == {protocol.DONE: 1}
        assert alice["error_rate"] == 0.0
        assert alice["latency"]["n"] == 1 and alice["tasks"] == 6
        assert snap["breached"] is False
        # tenant filter + validation
        only = client.slo(tenant="alice")
        assert set(only["tenants"]) == {"alice"}
        with pytest.raises(Exception):
            client.slo(tenant="not a tenant!")
        # the observation is durably archived and queryable
        recs = _poll(lambda: client.query(
            "slo_obs", labels={"tenant": "alice"}),
            what="archived slo_obs")
        assert recs[0]["job_id"] == a and recs[0]["state"] == "done"
        assert recs[0]["latency"] is not None
        # sampled numeric history comes back as {ts, value} points
        # (monitor sampler tick feeds the archive observer)
        pts = _poll(lambda: client.query("tasks_per_s"),
                    what="sampled points")
        assert set(pts[0]) == {"ts", "value"}
        # status carries the compact summaries for `top --serve`
        st = client.status()
        assert st["slo"]["window_jobs"] >= 1
        assert st["archive"]["enabled"] is True
        assert st["archive"]["torn_lines"] == 0


def test_daemon_restart_replays_burn_windows(tmp_path):
    """Stop the daemon, wipe the in-memory SLO plane (what a SIGKILL
    does), start a successor on the same archive: the tenant's history
    and dedup state must come back from the replay."""
    knobs = dict(serve_warm_floor=1, serve_tick_s=0.05,
                 archive_dir=str(tmp_path / "archive"))
    with _daemon(tmp_path, **knobs) as (daemon, client):
        a = client.submit(targets.square, range(4), tenant="alice",
                          job_id=_unique_job("slo-replay"))
        assert client.wait(a, timeout=60)["state"] == protocol.DONE
        _poll(lambda: client.slo()["tenants"].get("alice"),
              what="pre-restart observation")
        pre = client.query("slo_obs", labels={"tenant": "alice"})
        assert pre
    SLO.clear()  # the successor process starts empty...
    assert SLO.snapshot()["window_jobs"] == 0
    with _daemon(tmp_path, **knobs) as (daemon2, client2):
        snap = client2.slo()
        # ...and replay rebuilt the windows before serving
        assert snap["tenants"]["alice"]["jobs"] == {protocol.DONE: 1}
        assert snap["window_jobs"] >= 1
        # history is consistent across the restart (same records, no
        # torn reads, predecessor segments merged)
        post = client2.query("slo_obs", labels={"tenant": "alice"})
        assert [r["job_id"] for r in post][:len(pre)] == \
            [r["job_id"] for r in pre]
        assert client2.status()["archive"]["torn_lines"] == 0


def test_protocol_unknown_verb_structured_error(tmp_path):
    """Version-mismatch posture: a verb this daemon does not know
    (e.g. a NEWER client's new op) must produce a structured
    ``(False, ...)`` reply — not a hang, not a dropped connection —
    and the connection stays usable for known verbs."""
    with _daemon(tmp_path, serve_warm_floor=0,
                 serve_tick_s=0.2) as (daemon, client):
        conn = Client(("127.0.0.1", daemon.port),
                      authkey=cluster_authkey())
        try:
            conn.send(("frobnicate", {}))  # bypasses client validation
            assert conn.poll(10), "daemon hung on unknown verb"
            ok, detail = conn.recv()
            assert ok is False
            assert "unknown serve op" in detail
            assert "frobnicate" in detail
            # malformed (non-tuple) request: same structured posture
            conn.send(["not", "a", "request", "tuple"])
            assert conn.poll(10)
            ok, detail = conn.recv()
            assert ok is False and "malformed" in detail
            # the connection survived both rejections
            conn.send(("ping", {}))
            assert conn.poll(10)
            assert conn.recv() == (True, "pong")
        finally:
            conn.close()
        # a current client still validates locally before sending
        with pytest.raises(ValueError, match="unknown serve op"):
            protocol.request("frobnicate")


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------


def test_cli_slo_and_history(tmp_path, capsys):
    parser = build_parser()
    with _daemon(tmp_path, serve_warm_floor=1,
                 serve_tick_s=0.05) as (daemon, client):
        a = client.submit(targets.square, range(3), tenant="alice",
                          job_id=_unique_job("slo-cli"))
        assert client.wait(a, timeout=60)["state"] == protocol.DONE
        _poll(lambda: client.slo()["tenants"].get("alice"),
              what="cli observation")
        addr = f"127.0.0.1:{daemon.port}"
        # fiber-tpu slo --json
        args = parser.parse_args(["slo", "--serve", addr, "--json"])
        assert args.fn(args) == 0  # not breached -> exit 0
        snap = json.loads(capsys.readouterr().out)
        assert snap["tenants"]["alice"]["jobs"] == {protocol.DONE: 1}
        # fiber-tpu slo (text table)
        args = parser.parse_args(["slo", "--serve", addr])
        assert args.fn(args) == 0
        out = capsys.readouterr().out
        assert "targets:" in out and "alice" in out and "ok" in out
        # fiber-tpu history <kind> --since --label
        args = parser.parse_args(
            ["history", "slo_obs", "--since", "3600",
             "--label", "tenant=alice", "--serve", addr, "--json"])
        assert args.fn(args) == 0
        recs = json.loads(capsys.readouterr().out)
        assert recs and all(r["tenant"] == "alice" for r in recs)
        # text mode renders sample-field queries as points
        _poll(lambda: client.query("tasks_per_s"), what="points")
        args = parser.parse_args(
            ["history", "tasks_per_s", "--serve", addr])
        assert args.fn(args) == 0
        assert capsys.readouterr().out.strip()


def test_cli_jobs_json(tmp_path, capsys):
    parser = build_parser()
    args = parser.parse_args(
        ["jobs", "--ledger-dir", str(tmp_path / "empty"), "--json"])
    assert args.fn(args) == 0
    assert json.loads(capsys.readouterr().out) == []


# ---------------------------------------------------------------------------
# docs-nav lint guard
# ---------------------------------------------------------------------------


def test_check_docs_nav_flags_orphan_pages(tmp_path):
    script = os.path.join(REPO_ROOT, "scripts", "check_docs_nav.py")
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "wired.md").write_text("# wired\n")
    (tmp_path / "mkdocs.yml").write_text(
        "site_name: x\nnav:\n  - Home: wired.md\n")
    ok = subprocess.run([sys.executable, script, str(tmp_path)],
                        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stderr
    # an orphan page (never added to the nav) fails the gate, by name
    (docs / "orphan.md").write_text("# lost\n")
    bad = subprocess.run([sys.executable, script, str(tmp_path)],
                         capture_output=True, text=True)
    assert bad.returncode == 1
    assert "orphan.md" in bad.stderr


def test_check_docs_nav_passes_on_this_repo():
    script = os.path.join(REPO_ROOT, "scripts", "check_docs_nav.py")
    run = subprocess.run([sys.executable, script, REPO_ROOT],
                         capture_output=True, text=True)
    assert run.returncode == 0, run.stderr
