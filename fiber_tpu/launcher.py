"""Master-side launch protocol: turn a Process object into a running job.

Reference parity: fiber/popen_fiber_spawn.py (the Popen class). The launch
sequence is:

1. ensure the admin server (one accept loop per master) is running;
2. build the worker command line (``python -m fiber_tpu.worker``) and a
   JobSpec, merging the target function's ``@meta`` hints;
3. ``backend.create_job(spec)``  — the process/machine boundary;
4. wait for the worker to dial back with our launch ident (active mode) or
   dial the worker ourselves (passive mode, ``ipc_active=False``);
5. ship two pickled frames over the admin socket: preparation data (config,
   sys.path, main-module info) and the Process object itself;
6. keep the socket: its fd is the selectable sentinel, its closure is what
   the worker-side watchdog reacts to.
"""

from __future__ import annotations

import hashlib
import os
import socket
import sys
import threading
import time
from typing import Any, Dict, Optional

from fiber_tpu import config
from fiber_tpu import serialization, telemetry
from fiber_tpu.admin import AdminServer, send_ident
from fiber_tpu.backends import get_backend
from fiber_tpu.core import Job, JobSpec, ProcessStatus
from fiber_tpu.framing import send_frame
from fiber_tpu.meta import get_meta
from fiber_tpu.testing import chaos
from fiber_tpu.utils.logging import get_logger

logger = get_logger()


def next_launch_ident() -> int:
    """Unguessable 64-bit capability token for one launch: the worker
    proves it is the process we launched by echoing it on connect-back.
    Sequential idents — even from a random starting point — would let
    a network peer who ever learns one predict every later one and
    race the real worker for the master's pickled process state; fully
    random per-launch idents make the connect-back a bearer
    capability. (Collision odds across a master's lifetime are ~2^-64
    per pair — ignorable.)"""
    return int.from_bytes(os.urandom(8), "big") or 1


def get_pid_from_jid(jid: Any) -> int:
    """Stable pseudo-pid in [1, 32749) derived from the backend job id
    (reference: fiber/popen_fiber_spawn.py:153-156; the <32768 range is a
    tested contract)."""
    digest = hashlib.md5(str(jid).encode()).hexdigest()
    return int(digest, 16) % 32749 + 1


class ProcessStartError(RuntimeError):
    pass


class JobLauncher:
    """One per started Process; owns the job handle and the admin socket."""

    def __init__(self, process_obj) -> None:
        self.returncode: Optional[int] = None
        self.conn: Optional[socket.socket] = None
        self.job: Optional[Job] = None
        self.backend = get_backend(process_obj._backend_name)
        self._launch(process_obj)

    # ------------------------------------------------------------------
    def _launch(self, process_obj) -> None:
        t_spawn = time.monotonic()
        cfg = config.get()
        ip, _, _ = self.backend.get_listen_addr()
        ident = next_launch_ident()
        active = bool(cfg.ipc_active)

        if active:
            admin = AdminServer.ensure(ip, cfg.ipc_admin_master_port)
            waiter = admin.expect(ident)
            master_addr = "{}:{}".format(*admin.address())
        else:
            admin = None
            waiter = None
            master_addr = ""

        cmd = [
            sys.executable,
            "-m",
            "fiber_tpu.worker",
        ]
        if active:
            cmd += ["--master", master_addr]
        else:
            cmd += ["--listen", str(cfg.ipc_admin_worker_port)]

        spec = self._job_spec(process_obj, cmd)
        # The ident rides the job ENV, never argv: /proc/<pid>/cmdline
        # is world-readable on shared hosts, and the ident is the
        # bearer capability for the master's pickled process state.
        spec.env["FIBER_LAUNCH_IDENT"] = str(ident)
        try:
            plan = chaos._plan
            if plan is not None:
                plan.fail_point("launch")
            self.job = self.backend.create_job(spec)
        except Exception:
            if admin is not None:
                admin.cancel(ident)
            raise
        self.pid = get_pid_from_jid(self.job.jid)

        try:
            if active:
                conn = self._await_connect_back(waiter, ident, admin)
            else:
                conn = self._dial_worker(ident, cfg.ipc_admin_worker_port)
        except Exception:
            self.backend.terminate_job(self.job)
            raise

        # Spawn latency = job creation through worker connect-back (the
        # whole interpreter-boot + handshake critical path a first map
        # pays per worker).
        telemetry.histogram(
            "launch_spawn_seconds",
            "Process launch latency: create_job to admin connect-back",
        ).observe(time.monotonic() - t_spawn)
        telemetry.counter(
            "launch_spawns", "Processes launched through JobLauncher",
        ).inc()

        # Stamp the pseudo-pid before pickling so the worker's
        # current_process().pid matches what the master sees.
        process_obj._pid = self.pid
        prep = self._preparation_data(process_obj)
        send_frame(conn, serialization.dumps(prep))
        send_frame(conn, serialization.dumps(process_obj))
        self.conn = conn
        self.sentinel = conn.fileno()

    def _job_spec(self, process_obj, cmd) -> JobSpec:
        cfg = config.get()
        hints: Dict[str, Any] = (
            getattr(process_obj, "meta_hints", None)
            or (get_meta(process_obj._target) if process_obj._target else {})
        )
        needs_device_hint = bool(
            hints.get("tpu") or hints.get("gpu") or hints.get("device")
        )
        # Device jobs get no default cpu reservation (their host runtime
        # needs every core unless the user explicitly caps it).
        cpu = hints.get(
            "cpu", None if needs_device_hint else cfg.cpu_per_job
        )
        mem = hints.get("mem", cfg.mem_per_job or None)
        # The worker interpreter must be able to import fiber_tpu *before*
        # the preparation frame (which carries the full sys.path) arrives,
        # so the package root rides PYTHONPATH in the job environment.
        from fiber_tpu.utils.misc import package_pythonpath

        env = {"FIBER_WORKER": "1", "PYTHONPATH": package_pythonpath()}
        active_plan = chaos._plan
        if active_plan is not None:
            # The active fault schedule rides the job env explicitly.
            # Inheriting the master's os.environ only works for
            # direct-subprocess backends: agent-spawned jobs get the
            # AGENT's environment, captured at agent boot — a plan
            # installed after that would silently never reach the
            # workers (and a chaos run would be vacuously green).
            env[chaos.ENV_VAR] = active_plan.to_env()
        if cfg.code_staging != "off":
            staged = self._ensure_code_staged()
            if staged:
                # Placeholder resolved by each host agent to ITS staging
                # root; the worker puts the snapshot first on sys.path.
                env["FIBER_STAGED_CODE"] = staged
                env["PYTHONPATH"] = staged + os.pathsep + env["PYTHONPATH"]
        if not needs_device_hint:
            # One process per chip: the process that calls the device
            # plane holds every chip of its host, and a second process
            # that initialises a TPU backend there fails or hangs. A
            # job without a device hint is a host-plane worker, so its
            # JAX is pinned to the CPU — it cannot take the chip even
            # if user code imports jax, and a pickled jax.Array it
            # unpickles lands on its CPU device. Jobs whose @meta hints
            # request a device inherit the launching environment.
            env["JAX_PLATFORMS"] = "cpu"
        env.update(self.backend.child_env())
        return JobSpec(
            command=cmd,
            image=cfg.image or None,
            name=process_obj.name.replace("_", "-").lower(),
            cpu=cpu,
            mem=mem,
            gpu=hints.get("gpu"),
            tpu=hints.get("tpu"),
            env=env,
            cwd=os.getcwd(),
            host_hint=getattr(process_obj, "_host_hint", None),
        )

    def _ensure_code_staged(self) -> str:
        """Worker-side staged-snapshot path (placeholder form), or ""."""
        from fiber_tpu.utils.staging import stage_workspace

        try:
            return stage_workspace(self.backend)
        except Exception:
            logger.exception("code staging failed; workers rely on a "
                             "shared filesystem for user modules")
            return ""

    def _preparation_data(self, process_obj) -> Dict[str, Any]:
        """Config + main-module info the worker needs before unpickling the
        Process (so targets defined in the user's __main__ resolve)."""
        child_cfg = config.get().as_dict()
        child_cfg.update(self.backend.child_config())
        from fiber_tpu.sched import local_host_key

        prep: Dict[str, Any] = {
            "fiber_config": child_cfg,
            "name": process_obj.name,
            "sys_path": list(sys.path),
            "sys_argv": list(sys.argv),
            "cwd": os.getcwd(),
            "authkey": bytes(process_obj.authkey or b""),
            # The master's placement key: lets a remote worker see at
            # bootstrap that same-host shm rings cannot engage with the
            # master (docs/transport.md negotiation rules).
            "master_host_key": local_host_key(),
        }
        main_path = getattr(
            sys.modules.get("__main__"), "__file__", None
        )
        if main_path and os.path.basename(main_path) != "ipython":
            main_mod = sys.modules["__main__"]
            if getattr(main_mod, "__spec__", None) is not None:
                prep["init_main_from_name"] = main_mod.__spec__.name
            else:
                prep["init_main_from_path"] = os.path.abspath(main_path)
        return prep

    def _await_connect_back(self, waiter, ident, admin) -> socket.socket:
        """Poll for the worker's dial-in, aborting early (with job logs) if
        the job already died (reference: popen_fiber_spawn.py:439-461)."""
        while True:
            conn = waiter.wait(0.5)
            if conn is not None:
                return conn
            status = self.backend.get_job_status(self.job)
            if status == ProcessStatus.STOPPED:
                admin.cancel(ident)
                logs = ""
                try:
                    logs = self.backend.get_job_logs(self.job)
                except Exception:
                    pass
                raise ProcessStartError(
                    f"job {self.job.jid} exited before connecting back; "
                    f"logs:\n{logs}"
                )

    def _dial_worker(self, ident: int, port: int) -> socket.socket:
        """Passive mode: master dials the worker's fixed admin port
        (reference: popen_fiber_spawn.py passive branch, config
        ipc_active=False)."""
        deadline = time.monotonic() + 60.0
        while True:
            self.job.update()
            host = self.job.host
            if host:
                conn = None
                try:
                    conn = socket.create_connection((host, port), timeout=2.0)
                    conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    send_ident(conn, ident)
                    # Wait for the worker's ident echo so a dial that landed
                    # on some *other* worker's listener (shared fixed port)
                    # is detected instead of shipping frames into a dead
                    # connection.
                    from fiber_tpu.admin import recv_ident

                    conn.settimeout(5.0)
                    if recv_ident(conn) == ident:
                        conn.settimeout(None)
                        return conn
                    conn.close()
                except OSError:
                    if conn is not None:
                        conn.close()
            status = self.backend.get_job_status(self.job)
            if status == ProcessStatus.STOPPED:
                raise ProcessStartError(
                    f"job {self.job.jid} exited before the master could dial it"
                )
            if time.monotonic() > deadline:
                raise ProcessStartError(
                    f"timed out dialing worker {host}:{port} (passive mode)"
                )
            time.sleep(0.2)

    #: Synthetic exit code for a job whose backend became unreachable
    #: (host agent died, cluster torn down): its real status is
    #: unknowable, and the health-plane posture is that a dead agent's
    #: jobs are dead.
    LOST_RETURNCODE = -255

    # ------------------------------------------------------------------
    def poll(self) -> Optional[int]:
        if self.returncode is None:
            try:
                self.returncode = self.backend.wait_for_job(self.job, 0)
            except Exception as err:
                # Backend unreachable: declare the job lost instead of
                # propagating into every is_alive()/active_children()
                # caller (pre-fix, one dead sim agent turned every later
                # liveness check in the process into a raised
                # ConnectionRefusedError).
                logger.warning(
                    "poll: backend unreachable for job %s (%s); "
                    "declaring it lost", getattr(self.job, "jid", "?"),
                    err)
                self.returncode = self.LOST_RETURNCODE
        return self.returncode

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        if self.returncode is None:
            try:
                self.returncode = self.backend.wait_for_job(
                    self.job, timeout)
            except Exception as err:
                logger.warning(
                    "wait: backend unreachable for job %s (%s); "
                    "declaring it lost", getattr(self.job, "jid", "?"),
                    err)
                self.returncode = self.LOST_RETURNCODE
        return self.returncode

    def terminate(self) -> None:
        if self.returncode is None and self.job is not None:
            try:
                self.backend.terminate_job(self.job)
            except Exception as err:  # job may have raced to exit
                logger.debug("terminate_job failed: %s", err)

    def kill(self) -> None:
        """SIGKILL semantics — survives targets that ignore SIGTERM."""
        if self.returncode is None and self.job is not None:
            try:
                self.backend.kill_job(self.job)
            except Exception as err:
                logger.debug("kill_job failed: %s", err)

    def close(self) -> None:
        """Release the admin socket (invalidates the sentinel fd)."""
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None
