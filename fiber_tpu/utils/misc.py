"""Small shared helpers: fork registry, finalizers, interactive detection.

Reference parity: fiber/util.py:33-67 (register_after_fork / Finalize) and
fiber/util.py:127-131 (interactive-console detection, which selects
cloudpickle over the stdlib reducer for shipping __main__-less closures —
fiber/popen_fiber_spawn.py:348-354).
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import weakref
from typing import Any, Callable, Optional

_afterfork_registry: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()
_afterfork_counter = itertools.count()


def register_after_fork(obj: Any, func: Callable[[Any], None]) -> None:
    _afterfork_registry[(next(_afterfork_counter), id(obj), func)] = obj


def run_after_forkers() -> None:
    items = list(_afterfork_registry.items())
    items.sort()
    for (_, _, func), obj in items:
        try:
            func(obj)
        except Exception:
            pass


class Finalize:
    """Callback run at object GC or process exit, priority ordered."""

    _registry: dict = {}
    _counter = itertools.count()
    _lock = threading.Lock()

    def __init__(self, obj, callback, args=(), kwargs=None, exitpriority=None):
        self._callback = callback
        self._args = args
        self._kwargs = kwargs or {}
        self._key = (exitpriority, next(self._counter))
        self._weakref = (
            weakref.ref(obj, self) if obj is not None else None
        )
        with self._lock:
            self._registry[self._key] = self

    def __call__(self, wr=None):
        with self._lock:
            if self._registry.pop(self._key, None) is None:
                return None
        callback, args, kwargs = self._callback, self._args, self._kwargs
        self._callback = None
        return callback(*args, **kwargs)

    def cancel(self) -> None:
        with self._lock:
            self._registry.pop(self._key, None)
        self._callback = None

    def still_active(self) -> bool:
        return self._callback is not None

    @classmethod
    def run_all(cls, minpriority: Optional[int] = None) -> None:
        with cls._lock:
            items = sorted(cls._registry.items(), reverse=True)
        for key, finalizer in items:
            prio = key[0]
            if prio is None:
                continue
            if minpriority is not None and prio < minpriority:
                continue
            finalizer()


def is_in_interactive_console() -> bool:
    """True in a REPL / notebook, where __main__ has no file and functions
    defined at the prompt can only travel via cloudpickle (selects the
    serializer — see fiber_tpu/serialization.py)."""
    main = sys.modules.get("__main__")
    return main is None or not hasattr(main, "__file__")


def mib(nbytes: int) -> float:
    return nbytes / (1024.0 * 1024.0)


def getenv_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def package_pythonpath() -> str:
    """PYTHONPATH value that lets a child interpreter ``import fiber_tpu``
    regardless of its cwd: the package root prepended to the current
    PYTHONPATH. Used by every process-spawning seam (launcher jobs, sim
    agents) — workers must import the framework before any preparation
    payload arrives."""
    import fiber_tpu

    pkg_root = os.path.dirname(
        os.path.dirname(os.path.abspath(fiber_tpu.__file__))
    )
    pythonpath = os.environ.get("PYTHONPATH", "")
    if pkg_root not in pythonpath.split(os.pathsep):
        pythonpath = (
            pkg_root + os.pathsep + pythonpath if pythonpath else pkg_root
        )
    return pythonpath


#: XLA CPU in-process collectives abort() the WHOLE interpreter via an
#: absl FATAL when a rendezvous participant misses the terminate
#: deadline (core-dump-verified cause of an earlier sim-tier SIGABRT).
#: The deadline exists because a missing participant IS possible —
#: async dispatch can interleave two program generations over the CPU
#: client's fixed thread pool (the library serializes its own
#: multi-step CPU-mesh loops to close that window: make_train_step /
#: EvolutionStrategy.step). These values widen the deadline enough that
#: transient 1-core starvation never kills a healthy run (defaults are
#: tens of seconds), while a REAL deadlock still dies in bounded time
#: with XLA's message naming the rendezvous rather than hanging forever.
#: The installed jaxlib (0.9.0) takes all three; they are cpu-backend
#: flags, and XLA aborts on a flag it does not know, so they are never
#: handed to another platform's XLA build (libtpu is its own).
_CPU_COLLECTIVE_TIMEOUT_FLAGS = (
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=120",
    "--xla_cpu_collective_call_terminate_timeout_seconds=600",
    "--xla_cpu_collective_timeout_seconds=600",
)


def ensure_cpu_collective_timeout_flags() -> None:
    """Append the CPU-collective timeout policy to ``XLA_FLAGS`` —
    per flag, and only where the caller has not already set that flag
    (an explicit caller policy must win). A no-op unless the process
    is pinned to the CPU platform (``JAX_PLATFORMS=cpu``). Call BEFORE
    the first jax backend initialization; every CPU-mesh entry point
    (test conftest, the driver graft entry, record scripts) routes
    through here."""
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    added = [f for f in _CPU_COLLECTIVE_TIMEOUT_FLAGS
             if f.split("=", 1)[0] not in flags]
    if added:
        os.environ["XLA_FLAGS"] = (flags + " " + " ".join(added)).strip()
