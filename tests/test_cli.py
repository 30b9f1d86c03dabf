"""CLI surface (reference: fiber/cli.py behavior, TPU-flavored)."""

import subprocess
import sys

import pytest

from fiber_tpu.cli import build_parser, main


def test_parser_subcommands():
    parser = build_parser()
    for cmd in ("run", "sim", "agent", "up", "status", "cp"):
        args = {
            "run": ["run", "x.py"],
            "sim": ["sim", "2", "x.py"],
            "agent": ["agent"],
            "up": ["up", "--hosts", "a,b"],
            "status": ["status", "--hosts", "a"],
            "cp": ["cp", "a", "b", "--hosts", "h"],
        }[cmd]
        parsed = parser.parse_args(args)
        assert parsed.command == cmd


def test_up_dry_run(capsys):
    rc = main(["up", "--hosts", "10.0.0.1,10.0.0.2", "--port", "7070",
               "--dry-run"])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.count("ssh") == 2
    assert "--port 7070" in out


def test_up_gcloud_dry_run(capsys):
    rc = main(["up", "--tpu", "my-pod", "--zone", "us-central2-b",
               "--dry-run"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "gcloud compute tpus tpu-vm ssh" in out
    assert "--worker all" in out


def test_status_down_host(capsys):
    rc = main(["status", "--hosts", "127.0.0.1:1"])  # nothing listening
    assert rc == 1
    assert "DOWN" in capsys.readouterr().out


def test_doctor_healthy_and_down_agent(capsys):
    """fiber-tpu doctor: reports selection/config/devices, passes with a
    live agent, fails (rc 1, FAIL line) on a dead one."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "fiber_tpu.host_agent", "--port", "0",
         "--announce"],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        port = int(proc.stdout.readline().split()[1])
        rc = main(["doctor", "--hosts", f"127.0.0.1:{port}",
                   "--timeout", "60"])
        out = capsys.readouterr().out
        assert "backend selection" in out
        assert f"agent 127.0.0.1:{port}" in out
        # Only the agent/cluster side is asserted here; the device
        # probe's verdict depends on the machine.
        assert "FAIL] agent" not in out
    finally:
        proc.terminate()
        proc.wait(10)

    rc = main(["doctor", "--hosts", "127.0.0.1:1", "--timeout", "60"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "FAIL] agent 127.0.0.1:1" in out


def test_status_and_cp_against_sim_agent(tmp_path, capsys):
    proc = subprocess.Popen(
        [sys.executable, "-m", "fiber_tpu.host_agent", "--port", "0",
         "--announce"],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        port = int(proc.stdout.readline().split()[1])
        hosts = f"127.0.0.1:{port}"

        rc = main(["status", "--hosts", hosts])
        assert rc == 0
        assert "up" in capsys.readouterr().out

        src = tmp_path / "src.txt"
        src.write_text("stage me")
        dst = str(tmp_path / "dst.txt")
        rc = main(["cp", str(src), dst, "--hosts", hosts])
        assert rc == 0
        assert open(dst).read() == "stage me"

        fetched = str(tmp_path / "fetched.txt")
        rc = main(["cp", f"127.0.0.1:{dst}", fetched, "--hosts", hosts])
        assert rc == 0
        assert open(fetched).read() == "stage me"
    finally:
        proc.terminate()
        proc.wait(10)


def test_sim_runs_script(tmp_path):
    script = tmp_path / "prog.py"
    out = tmp_path / "out.txt"
    script.write_text(
        "import fiber_tpu, sys\n"
        "def w(path):\n"
        "    open(path, 'w').write('ran on sim cluster')\n"
        "if __name__ == '__main__':\n"
        f"    p = fiber_tpu.Process(target=w, args=({str(out)!r},))\n"
        "    p.start(); p.join(60)\n"
        "    assert p.exitcode == 0\n"
    )
    result = subprocess.run(
        [sys.executable, "-m", "fiber_tpu.cli", "sim", "2", str(script)],
        capture_output=True, text=True, timeout=120,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert out.read_text() == "ran on sim cluster"


def _fake_bin(tmp_path, name, record):
    """A PATH-shadowing fake for ssh/gcloud that records its argv."""
    script = tmp_path / name
    script.write_text(
        "#!/bin/sh\n"
        f'echo "$@" >> {record}\n'
    )
    script.chmod(0o755)
    return script


def test_up_executes_ssh_per_host(tmp_path, monkeypatch):
    """`fiber-tpu up` (execution is the default now): one ssh per host
    carrying the agent start command, a generated cluster key, and a
    non-loopback bind (production bring-up path, reference role:
    fiber/cli.py:338-414). The fake ssh starts nothing, so the
    wait-for-agents step must fail loudly."""
    import os

    from fiber_tpu.cli import main

    record = tmp_path / "ssh.log"
    _fake_bin(tmp_path, "ssh", record)
    monkeypatch.setenv("PATH", f"{tmp_path}:{os.environ['PATH']}")
    monkeypatch.delenv("FIBER_CLUSTER_KEY", raising=False)

    rc = main(["up", "--hosts", "10.0.0.1:7071,10.0.0.2:7071",
               "--wait", "0.5"])
    assert rc == 1  # driver ran, agents never answered
    lines = record.read_text().strip().splitlines()
    assert len(lines) == 2
    for line, host in zip(lines, ("10.0.0.1", "10.0.0.2")):
        assert line.startswith(host)
        assert "FIBER_CLUSTER_KEY=" in line
        assert "fiber-tpu-cluster" not in line  # generated, not default
        assert "-m fiber_tpu.host_agent" in line
        assert "--bind 0.0.0.0" in line


def _fake_gcloud(tmp_path, record, describe_stdout):
    """PATH-shadowing gcloud: records every call; `describe` prints the
    canned payload (the seam for worker-address derivation)."""
    script = tmp_path / "gcloud"
    payload = tmp_path / "describe.json"
    payload.write_text(describe_stdout)
    script.write_text(
        "#!/bin/sh\n"
        f'echo "$@" >> {record}\n'
        'case "$*" in *describe*) cat ' + str(payload) + ";; esac\n"
    )
    script.chmod(0o755)
    return script


def test_up_tpu_derives_probe_hosts_and_fails_when_agents_down(
        tmp_path, monkeypatch, capsys):
    """`fiber-tpu up --tpu NAME` without --hosts must DERIVE the worker
    addresses from `gcloud describe` and still verify (VERDICT r4 #5:
    an `up` that confirmed nothing may not return 0). The fake gcloud
    starts no agents, so the derived-address probe must fail."""
    import json as _json
    import os

    from fiber_tpu.cli import main

    record = tmp_path / "gcloud.log"
    endpoints = {"networkEndpoints": [
        {"ipAddress": "10.164.0.2",
         "accessConfig": {"externalIp": "127.0.0.1"}},
    ]}
    _fake_gcloud(tmp_path, record, _json.dumps(endpoints))
    monkeypatch.setenv("PATH", f"{tmp_path}:{os.environ['PATH']}")
    monkeypatch.delenv("FIBER_CLUSTER_KEY", raising=False)

    rc = main(["up", "--tpu", "my-pod", "--zone", "us-central2-b",
               "--port", "7199", "--wait", "0.5"])
    assert rc == 1  # derived 127.0.0.1:7199, probed it, nobody home
    lines = record.read_text()
    assert "compute tpus tpu-vm ssh my-pod" in lines
    assert "--worker all" in lines
    assert "compute tpus tpu-vm describe my-pod" in lines
    assert "--zone us-central2-b" in lines
    err = capsys.readouterr().err
    # the failure is the PROBE timing out, not a skipped verification
    assert "could NOT be verified" not in err


def test_up_tpu_derivation_failure_is_loud(tmp_path, monkeypatch,
                                           capsys):
    """If `gcloud describe` yields nothing usable, `up --tpu` must say
    the agents are unverified and exit nonzero — never silently 0."""
    import os

    from fiber_tpu.cli import main

    record = tmp_path / "gcloud.log"
    _fake_gcloud(tmp_path, record, "not json at all")
    monkeypatch.setenv("PATH", f"{tmp_path}:{os.environ['PATH']}")
    monkeypatch.delenv("FIBER_CLUSTER_KEY", raising=False)

    rc = main(["up", "--tpu", "my-pod", "--wait", "0.5"])
    assert rc == 1
    assert "could NOT be verified" in capsys.readouterr().err


def test_down_tpu_derives_hosts_and_stops_agent(tmp_path, monkeypatch):
    """`down --tpu NAME` (no --hosts): derives worker addresses via the
    same gcloud-describe seam as `up` and stops the real agent through
    its shutdown RPC."""
    import json as _json
    import os
    import socket
    import time as _time

    from fiber_tpu import cli

    key = "down-derive-key-0123456789abcdef0123456789ab"
    monkeypatch.setenv("FIBER_CLUSTER_KEY", key)
    monkeypatch.delenv("FIBER_TPU_HOSTS", raising=False)

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = subprocess.Popen(
        [sys.executable, "-m", "fiber_tpu.host_agent",
         "--port", str(port), "--bind", "127.0.0.1"],
        env=dict(os.environ, FIBER_CLUSTER_KEY=key),
    )

    def fake_capture(cmd):
        assert "describe my-pod" in cmd
        return 0, _json.dumps({"networkEndpoints": [
            {"accessConfig": {"externalIp": "127.0.0.1"}},
        ]}), ""

    monkeypatch.setattr(cli, "_run_shell_capture", fake_capture)
    try:
        deadline = _time.time() + 30
        while _time.time() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                break
            except OSError:
                _time.sleep(0.1)
        rc = cli.main(["down", "--tpu", "my-pod", "--port", str(port)])
        assert rc == 0
        deadline = _time.time() + 30
        while proc.poll() is None and _time.time() < deadline:
            _time.sleep(0.2)
        assert proc.poll() is not None
    finally:
        if proc.poll() is None:
            proc.terminate()
            proc.wait(10)


def test_down_port_applies_to_portless_hosts(monkeypatch):
    """`down --hosts IP --port P` must dial P (same meaning --port has
    for `up`), not silently fall back to the default agent port and
    report a healthy agent unreachable."""
    import threading

    from fiber_tpu import cli
    from fiber_tpu.host_agent import HostAgent

    agent = HostAgent(0, bind="127.0.0.1")
    t = threading.Thread(target=agent.serve_forever, daemon=True)
    t.start()
    try:
        rc = cli.main(["down", "--hosts", "127.0.0.1",
                       "--port", str(agent.port)])
        assert rc == 0
    finally:
        agent.stop()


def test_status_tpu_derives_hosts(monkeypatch, capsys):
    """`status --tpu NAME` resolves worker addresses through the shared
    resolver (every agent-facing subcommand speaks --tpu now)."""
    import json as _json
    import threading

    from fiber_tpu import cli
    from fiber_tpu.host_agent import HostAgent

    agent = HostAgent(0, bind="127.0.0.1")
    t = threading.Thread(target=agent.serve_forever, daemon=True)
    t.start()

    def fake_capture(cmd):
        assert "describe my-pod" in cmd
        return 0, _json.dumps({"networkEndpoints": [
            {"accessConfig": {"externalIp": "127.0.0.1"}},
        ]}), ""

    monkeypatch.setattr(cli, "_run_shell_capture", fake_capture)
    monkeypatch.delenv("FIBER_TPU_HOSTS", raising=False)
    try:
        rc = cli.main(["status", "--tpu", "my-pod",
                       "--port", str(agent.port)])
        assert rc == 0
        assert f"127.0.0.1:{agent.port}  up" in capsys.readouterr().out
    finally:
        agent.stop()


def test_up_tpu_derived_probe_succeeds_against_real_agent(
        tmp_path, monkeypatch, capsys):
    """The full no---hosts gcloud path: mocked shell seam starts a REAL
    local agent for the ssh leg, the describe leg derives 127.0.0.1,
    and `up` verifies it end to end (rc 0)."""
    import json as _json
    import os
    import re
    import socket

    from fiber_tpu import cli

    key = "derive-test-key-0123456789abcdef0123456789ab"
    monkeypatch.setenv("FIBER_CLUSTER_KEY", key)

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []

    def fake_shell(cmd):
        m = re.search(r"--port (\d+)", cmd)
        assert m, cmd
        env = dict(os.environ, FIBER_CLUSTER_KEY=key)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "fiber_tpu.host_agent",
             "--port", m.group(1), "--bind", "127.0.0.1"],
            env=env,
        ))
        return 0

    def fake_capture(cmd):
        assert "describe my-pod" in cmd
        return 0, _json.dumps({"networkEndpoints": [
            {"accessConfig": {"externalIp": "127.0.0.1"}},
        ]}), ""

    monkeypatch.setattr(cli, "_run_shell", fake_shell)
    monkeypatch.setattr(cli, "_run_shell_capture", fake_capture)
    import shutil

    monkeypatch.setattr(shutil, "which", lambda name: f"/usr/bin/{name}")
    try:
        rc = cli.main(["up", "--tpu", "my-pod", "--port", str(port),
                       "--wait", "60"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert f"127.0.0.1:{port}" in out  # derived address in next-steps
        assert len(procs) == 1
        assert cli.main(["down", "--hosts",
                         f"127.0.0.1:{port}"]) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
                p.wait(10)


def test_up_run_cp_down_end_to_end(tmp_path, monkeypatch, capsys):
    """The full bring-up story with the cloud driver mocked at the
    _run_shell seam (VERDICT r3 #6): `up` starts a REAL local agent
    (standing in for the TPU-VM worker), waits until it answers,
    `status`/`doctor` verify it, `cp` stages a file, a job runs on it
    through the agent spawn path, and `down` stops it via the shutdown
    RPC."""
    import os
    import re
    import socket
    import time as _time

    from fiber_tpu import cli

    key = "e2e-test-key-0123456789abcdef0123456789abcdef"
    monkeypatch.setenv("FIBER_CLUSTER_KEY", key)

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []

    def fake_shell(cmd):
        # Stand-in for `ssh host '... nohup python -m host_agent ...'`:
        # start the agent HERE, bound to loopback, same key and port.
        m = re.search(r"--port (\d+)", cmd)
        assert m, cmd
        assert f"FIBER_CLUSTER_KEY={key}" in cmd
        env = dict(os.environ, FIBER_CLUSTER_KEY=key)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "fiber_tpu.host_agent",
             "--port", m.group(1), "--bind", "127.0.0.1"],
            env=env,
        ))
        return 0

    monkeypatch.setattr(cli, "_run_shell", fake_shell)
    # This box has no ssh client; the driver-availability gate must not
    # disable the mocked seam.
    import shutil

    monkeypatch.setattr(shutil, "which", lambda name: f"/usr/bin/{name}")
    hosts = f"127.0.0.1:{port}"
    try:
        # up: mocked driver, real agent, real wait/verify
        rc = cli.main(["up", "--hosts", hosts, "--port", str(port),
                       "--wait", "60"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "agent live" in out
        assert len(procs) == 1

        # status + doctor against the created state
        assert cli.main(["status", "--hosts", hosts]) == 0
        out = capsys.readouterr().out
        assert "up" in out
        rc = cli.main(["doctor", "--hosts", hosts, "--timeout", "60"])
        out = capsys.readouterr().out
        assert f"agent 127.0.0.1:{port}" in out
        assert "FAIL] agent" not in out

        # cp: stage a file onto the "pod host"
        src = tmp_path / "payload.txt"
        src.write_text("to the pod")
        dst = str(tmp_path / "staged.txt")
        assert cli.main(["cp", str(src), dst, "--hosts", hosts]) == 0
        assert open(dst).read() == "to the pod"

        # run: a job through the same agent spawn path the backend uses
        from fiber_tpu.backends.tpu import AgentClient

        client = AgentClient("127.0.0.1", port)
        marker = str(tmp_path / "ran.txt")
        jid, _log = client.call(
            "spawn",
            [sys.executable, "-c",
             f"open({marker!r}, 'w').write('job ran')"],
            str(tmp_path), {}, "e2e-job",
        )
        assert client.call("wait", jid, 60) == 0
        client.close()
        assert open(marker).read() == "job ran"

        # down: shutdown RPC stops the agent process
        assert cli.main(["down", "--hosts", hosts]) == 0
        deadline = _time.time() + 30
        while procs[0].poll() is None and _time.time() < deadline:
            _time.sleep(0.2)
        assert procs[0].poll() is not None
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
                p.wait(10)


def test_backend_discovers_agents_from_tpu_worker_hostnames(monkeypatch):
    """On a pod slice, TPU_WORKER_HOSTNAMES is the host source: the
    backend must dial those agents and run jobs on them."""
    import sys
    import threading

    from fiber_tpu import config
    from fiber_tpu.backends.tpu import TpuBackend
    from fiber_tpu.core import JobSpec
    from fiber_tpu.host_agent import HostAgent

    agents = [HostAgent(0, bind="127.0.0.1") for _ in range(2)]
    for a in agents:
        threading.Thread(target=a.serve_forever, daemon=True).start()
    names = ",".join(f"127.0.0.1:{a.port}" for a in agents)

    monkeypatch.delenv("FIBER_TPU_HOSTS", raising=False)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", names)
    old = config.get().tpu_hosts
    config.get().update(tpu_hosts="")
    backend = None
    try:
        backend = TpuBackend()
        assert backend._hosts == [
            ("127.0.0.1", agents[0].port), ("127.0.0.1", agents[1].port)
        ]
        job = backend.create_job(
            JobSpec(command=[sys.executable, "-c", "print('pod-ok')"])
        )
        assert backend.wait_for_job(job, 15) == 0
        assert "pod-ok" in backend.get_job_logs(job)
    finally:
        config.get().update(tpu_hosts=old)
        if backend is not None:
            # Stop the health-plane prober/detector too: a leaked
            # prober keeps pinging these (stopped-listener but
            # live-connection) embedded agents ~2/s for the REST of
            # the suite — burning CPU and making any later test that
            # compares agent_ops counters across two reads racy.
            backend.shutdown_sim_cluster()
        for a in agents:
            a.stop()


def test_run_submit_launches_master_in_cluster(tmp_path, monkeypatch):
    """`fiber-tpu run --submit --follow`: the master itself becomes a
    cluster job, running from the staged snapshot, and its own Processes
    land on the same cluster (reference: fiber/cli.py:346-414)."""
    import os
    import subprocess as sp
    import sys

    proj = tmp_path / "proj"
    proj.mkdir()
    (proj / "job_main.py").write_text(
        "import os\n"
        "import fiber_tpu\n"
        "def leaf(q):\n"
        "    q.put(os.getcwd())\n"
        "if __name__ == '__main__':\n"
        "    q = fiber_tpu.SimpleQueue()\n"
        "    p = fiber_tpu.Process(target=leaf, args=(q,))\n"
        "    p.start()\n"
        "    print('LEAF_CWD', q.get(60))\n"
        "    p.join(30)\n"
        "    print('MASTER_DONE', os.getcwd())\n"
    )
    env = dict(os.environ)
    env.update({
        "FIBER_BACKEND": "tpu",
        "FIBER_TPU_HOSTS": "sim:2",
        "FIBER_AGENT_STAGING": str(tmp_path / "stage"),
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.getcwd() + os.pathsep
        + env_get_pythonpath(),
    })
    out = sp.run(
        [sys.executable, "-m", "fiber_tpu.cli", "run", "--submit",
         "--follow", "job_main.py"],
        cwd=str(proj), env=env, capture_output=True, text=True,
        timeout=240,
    )
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "submitted master job" in out.stdout
    assert "MASTER_DONE" in out.stdout, out.stdout
    # master ran from the staged snapshot, not the submit cwd
    master_cwd = [l for l in out.stdout.splitlines()
                  if "MASTER_DONE" in l][0].split(" ", 1)[1]
    assert str(tmp_path / "stage") in master_cwd, master_cwd


def env_get_pythonpath():
    import os

    return os.environ.get("PYTHONPATH", "")


def test_logs_fetches_job_tail():
    """fiber-tpu logs host:port/jid prints the job's log tail."""
    import sys
    import threading
    import time

    import pytest as _pytest

    from fiber_tpu.backends.tpu import AgentClient
    from fiber_tpu.cli import main
    from fiber_tpu.host_agent import HostAgent

    agent = HostAgent(0, bind="127.0.0.1")
    threading.Thread(target=agent.serve_forever, daemon=True).start()
    client = AgentClient("127.0.0.1", agent.port)
    try:
        jid, _ = client.call(
            "spawn", [sys.executable, "-c", "print('log-line-42')"],
            None, {}, "logjob", None,
        )
        client.call("wait", jid, 10)
        time.sleep(0.1)
        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = main(["logs", f"127.0.0.1:{agent.port}/{jid}"])
        assert rc == 0
        assert "log-line-42" in buf.getvalue()

        with _pytest.raises(SystemExit, match="jid must look like"):
            main(["logs", "nonsense"])
    finally:
        client.close()
        agent.stop()
