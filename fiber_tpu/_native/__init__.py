"""Native extension loader: builds pump.cpp on first use with the system
g++ (no pip involved), caches the .so next to the source, and exposes a
ctypes binding. ``FIBER_NATIVE=0`` disables the native path entirely; every
consumer has a pure-Python fallback.

The artifact's file name carries the hash of the source it was built
from, so a stale .so copied beside a newer checkout's pump.cpp (file
times say nothing across copies and checkouts) is never loaded — it is
simply not the file the loader looks for.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "pump.cpp")
_LOCK_PATH = os.path.join(_HERE, "libfiberpump.so.lock")

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_lock = threading.Lock()


def _so_path() -> str:
    """The one artifact that matches THIS pump.cpp, by content."""
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"libfiberpump-{digest}.so")


def _build(so: str) -> bool:
    """Compile under an exclusive file lock: many processes (concurrent
    pool-worker spawns) may race here, and exactly one must publish the
    .so atomically (per-pid temp name + os.replace). Artifacts of other
    source versions are swept once the new one is in place."""
    import fcntl

    cxx = os.environ.get("CXX", "g++")
    tmp = f"{so}.tmp.{os.getpid()}"
    try:
        lock_fd = os.open(_LOCK_PATH, os.O_CREAT | os.O_RDWR, 0o644)
    except OSError:
        return False
    try:
        fcntl.flock(lock_fd, fcntl.LOCK_EX)
        if os.path.exists(so):
            return True  # another process already built it
        proc = subprocess.run(
            [cxx, "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
             _SRC, "-o", tmp],
            capture_output=True, text=True, timeout=120,
        )
        if proc.returncode != 0:
            from fiber_tpu.utils.logging import get_logger

            get_logger().warning(
                "native pump build failed; using the Python pump:\n%s",
                proc.stderr[-2000:],
            )
            return False
        os.replace(tmp, so)
        for stale in glob.glob(os.path.join(_HERE, "libfiberpump*.so")):
            if stale != so:
                try:
                    os.unlink(stale)
                except OSError:
                    pass
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        try:
            fcntl.flock(lock_fd, fcntl.LOCK_UN)
        except OSError:
            pass
        os.close(lock_fd)


def load() -> Optional[ctypes.CDLL]:
    """The pump library, building it if needed; None if unavailable."""
    global _lib, _load_attempted
    if os.environ.get("FIBER_NATIVE", "1") in ("0", "false"):
        return None
    with _lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        try:
            so = _so_path()
        except OSError:
            return None  # no source beside the package: Python pump
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            # A corrupt artifact must not poison future runs.
            try:
                os.unlink(so)
            except OSError:
                pass
            return None
        lib.fiber_pump_create.restype = ctypes.c_void_p
        lib.fiber_pump_create.argtypes = [
            ctypes.c_int,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.fiber_pump_close.restype = None
        lib.fiber_pump_close.argtypes = [ctypes.c_void_p]
        lib.fiber_pump_peers.restype = ctypes.c_int
        lib.fiber_pump_peers.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.nq_set_prefetch.restype = None
        lib.nq_set_prefetch.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.nq_connect.restype = ctypes.c_void_p
        lib.nq_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                   ctypes.c_int, ctypes.c_int,
                                   ctypes.c_char_p, ctypes.c_int]
        lib.nq_shutdown.restype = None
        lib.nq_shutdown.argtypes = [ctypes.c_void_p]
        lib.nq_send.restype = ctypes.c_int
        lib.nq_send.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint64]
        lib.nq_recv.restype = ctypes.c_int
        lib.nq_recv.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.nq_free.restype = None
        lib.nq_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        lib.nq_poll.restype = ctypes.c_int
        lib.nq_poll.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.nq_fileno.restype = ctypes.c_int
        lib.nq_fileno.argtypes = [ctypes.c_void_p]
        lib.nq_close.restype = None
        lib.nq_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


class NativePump:
    """One native device: two bound ports + an epoll forwarder thread in
    C++. Speaks the transport wire protocol exactly."""

    def __init__(self, duplex: bool, bind_ip: str = "") -> None:
        lib = load()
        if lib is None:
            raise RuntimeError("native pump unavailable")
        in_port = ctypes.c_int(0)
        out_port = ctypes.c_int(0)
        key = _data_plane_key()
        handle = lib.fiber_pump_create(
            1 if duplex else 0,
            bind_ip.encode(),
            key,
            len(key),
            ctypes.byref(in_port),
            ctypes.byref(out_port),
        )
        if not handle:
            raise RuntimeError("fiber_pump_create failed")
        self._lib = lib
        self._handle = handle
        self.in_port = in_port.value
        self.out_port = out_port.value

    def peers(self, side: str) -> int:
        """Live connection count: side 'in' (producers) or 'out'
        (consumers)."""
        if not self._handle:
            return 0
        return self._lib.fiber_pump_peers(
            self._handle, 0 if side == "in" else 1
        )

    def close(self) -> None:
        if self._handle:
            self._lib.fiber_pump_close(self._handle)
            self._handle = None

    def __del__(self) -> None:  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


def _data_plane_key() -> bytes:
    """Handshake key for the native transport (empty = auth disabled);
    must agree with the Python endpoints' fiber_tpu.auth settings."""
    from fiber_tpu import auth

    return auth.cluster_key() if auth.auth_enabled() else b""


def available() -> bool:
    return load() is not None


_MODE_CODES = {"r": 0, "w": 1, "rw": 2}


class NativeClient:
    """Connection-side native transport: framing, socket IO, and the
    credit protocol all in C (one ctypes call per send/recv; the GIL is
    released during blocking calls). Modes r/w/rw.

    Thread semantics match ``multiprocessing.connection.Connection``: one
    operation at a time (serialized by an internal lock). ``close()`` is
    safe while another thread is blocked in recv/send — the blocked call
    wakes with OSError before the handle is freed."""

    CONNECT_TIMEOUT_MS = 30_000

    def __init__(self, host: str, port: int, mode: str,
                 prefetch: int = 1) -> None:
        lib = load()
        if lib is None:
            raise RuntimeError("native client unavailable")
        code = _MODE_CODES.get(mode)
        if code is None:
            raise ValueError(f"native client does not support mode {mode!r}")
        key = _data_plane_key()
        handle = lib.nq_connect(host.encode(), port, code,
                                self.CONNECT_TIMEOUT_MS, key, len(key))
        if not handle:
            raise OSError(f"nq_connect failed for {host}:{port}")
        if prefetch > 1:
            lib.nq_set_prefetch(handle, int(prefetch))  # r-mode credits
        self._lib = lib
        self._handle = handle
        self._op_lock = threading.Lock()
        self._closed = False

    def send(self, payload: bytes,
             timeout: Optional[float] = None) -> None:
        # ``timeout`` is accepted for signature parity with
        # Endpoint.send; the native path already fails fast (nq_send
        # returns nonzero the moment the peer closes) rather than
        # blocking indefinitely, so no deadline plumbing is needed.
        with self._op_lock:
            if self._closed:
                raise OSError("connection closed")
            if self._lib.nq_send(self._handle, payload, len(payload)) != 0:
                raise OSError("native send failed (peer closed)")

    def recv(self, timeout: Optional[float] = None) -> bytes:
        timeout_ms = -1 if timeout is None else max(0, int(timeout * 1000))
        with self._op_lock:
            if self._closed:
                raise OSError("connection closed")
            out = ctypes.POINTER(ctypes.c_uint8)()
            out_len = ctypes.c_uint64()
            rc = self._lib.nq_recv(self._handle, timeout_ms,
                                   ctypes.byref(out), ctypes.byref(out_len))
            if rc == 0:
                raise TimeoutError("recv timed out")
            if rc != 1:
                raise OSError("native recv failed (peer closed)")
            try:
                return ctypes.string_at(out, out_len.value)
            finally:
                self._lib.nq_free(out)

    def poll(self, timeout: Optional[float] = 0.0) -> bool:
        timeout_ms = -1 if timeout is None else max(0, int(timeout * 1000))
        with self._op_lock:
            if self._closed:
                return False
            return self._lib.nq_poll(self._handle, timeout_ms) == 1

    def fileno(self) -> int:
        return self._lib.nq_fileno(self._handle)

    def close(self) -> None:
        if self._closed or not self._handle:
            return
        self._closed = True
        # Wake any blocked operation first (shutdown is handle-safe), then
        # free once the in-flight call has released the lock.
        self._lib.nq_shutdown(self._handle)
        with self._op_lock:
            self._lib.nq_close(self._handle)
            self._handle = None

    def __del__(self) -> None:  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
