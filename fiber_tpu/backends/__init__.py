"""Backend registry + auto-selection.

Reference parity: fiber/backend.py:24-76 (memoizing factory; auto-selection
sniffs the environment). fiber_tpu ships two backends:

* ``local`` — jobs are subprocess children of this machine;
* ``tpu``   — jobs are processes on TPU-VM pod-slice hosts (with a
  single-host simulation mode for CI).

Selection order: explicit ``name`` argument > ``FIBER_BACKEND`` env >
config ``backend`` key > sniffing (TPU metadata/env) > ``local``.
"""

from __future__ import annotations

import importlib
import os
import threading
import time
from typing import Dict, Optional

from fiber_tpu import config
from fiber_tpu.core import Backend

available_backends = ("local", "tpu")

_BACKEND_MODULES: Dict[str, str] = {
    "local": "fiber_tpu.backends.local",
    "tpu": "fiber_tpu.backends.tpu",
}

_backends: Dict[str, Backend] = {}
# Sniffed selections that probed unavailable -> monotonic deadline after
# which the probe is retried (agents may simply not be up YET on a real
# pod; a single transient failure must not pin a long-lived driver to
# the local backend forever). Until the deadline, later get_backend()
# calls skip the probe cost. Explicit selection (FIBER_BACKEND / config
# / name argument) bypasses this; reset_backends() clears it.
_failed_sniffs: Dict[str, float] = {}
_SNIFF_RETRY_S = 60.0
_lock = threading.Lock()
_build_locks: Dict[str, threading.Lock] = {}


def _on_tpu_pod() -> bool:
    """True when running on a TPU-VM host of a pod slice."""
    if os.environ.get("TPU_WORKER_HOSTNAMES") or os.environ.get(
        "TPU_WORKER_ID"
    ):
        return True
    return bool(config.get().tpu_name or config.get().tpu_hosts)


def auto_select_backend() -> str:
    name, _ = _select_backend()
    return name


def _select_backend():
    """Returns (name, explicit). Explicit selections (env var / config key)
    must not be silently substituted; only sniffed ones may fall back."""
    env = os.environ.get("FIBER_BACKEND")
    if env:
        return env, True
    cfg_backend = config.get().backend
    if cfg_backend:
        return cfg_backend, True
    if _on_tpu_pod():
        return "tpu", False
    return "local", False


def get_backend(name: Optional[str] = None) -> Backend:
    """Memoized backend factory (reference: fiber/backend.py:56-76).

    A backend requested explicitly (``name`` argument, ``FIBER_BACKEND``
    env, or the config ``backend`` key) raises if it can't be loaded; only
    a *sniffed* selection falls back to ``local`` with a warning, so
    running on exotic hosts never hard-fails process creation.
    """
    sniffed = False
    if name is None:
        name, explicit = _select_backend()
        sniffed = not explicit
        if sniffed:
            deadline = _failed_sniffs.get(name)
            if deadline is not None:
                if time.monotonic() < deadline:
                    return get_backend("local")
                _failed_sniffs.pop(name, None)  # retry the probe
    try:
        with _lock:
            backend = _backends.get(name)
            if backend is not None:
                return backend
            # Per-name build lock so construction and (for sniffed
            # selections) the reachability probe — up to 2s of connect
            # timeout per host — never run under the registry lock:
            # concurrent get_backend("local") calls must not stall
            # behind a slow tpu probe.
            build_lock = _build_locks.setdefault(name, threading.Lock())
        with build_lock:
            with _lock:
                backend = _backends.get(name)
                if backend is not None:
                    return backend
            modname = _BACKEND_MODULES.get(name)
            if modname is None:
                raise ValueError(
                    f"unknown backend {name!r}; "
                    f"available: {available_backends}"
                )
            module = importlib.import_module(modname)
            backend = module.make_backend()
            if sniffed:
                # A sniffed selection must actually work before it is
                # memoized: TPU-shaped environments exist where no
                # host agent runs (a single TPU VM with TPU_WORKER_ID
                # set and no `fiber-tpu up`), and
                # accepting the backend there turns every Process
                # start into a connection-refused retry loop. An
                # explicit selection skips the probe — the operator
                # said tpu, so failing loudly at create_job is right.
                probe = getattr(backend, "probe_available", None)
                if probe is not None:
                    probe()
            with _lock:
                _backends[name] = backend
            return backend
    except Exception:
        if not sniffed or name == "local":
            raise
        from fiber_tpu.utils.logging import get_logger

        get_logger().warning(
            "auto-selected backend %r unavailable; falling back to 'local'",
            name, exc_info=True,
        )
        _failed_sniffs[name] = time.monotonic() + _SNIFF_RETRY_S
        return get_backend("local")


def reset_backends() -> None:
    """Drop memoized backends (tests)."""
    with _lock:
        _backends.clear()
    _failed_sniffs.clear()
