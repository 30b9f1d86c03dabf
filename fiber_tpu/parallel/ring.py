"""``Ring`` — SPMD process topology builder.

Reference parity: fiber/experimental/ring.py (RingNode/Ring: N processes
running the same function with (rank, size), rendezvous through a Manager
list; the reference then delegates collective setup to torch.distributed /
Horovod via the user initializer — examples/ring.py:141-174).

fiber_tpu is self-contained and TPU-first:

* ``default_initializer`` wires a ``HostRing`` (fiber_tpu.ops.HostRing)
  over the rendezvous addresses, so ``current_ring().allreduce(grads)``
  works with zero external frameworks — the gloo-equivalent path.
* ``jax_distributed_initializer`` instead calls
  ``jax.distributed.initialize(coordinator, size, rank)`` so each rank
  becomes a JAX process in one multi-host runtime and reductions lower to
  ``lax.psum`` over ICI — the TPU pod path.
"""

from __future__ import annotations

import random
import time
from typing import Callable, List, Optional, Tuple


class RingNode:
    def __init__(self, rank: int, ip: str = "", port: int = 0) -> None:
        self.rank = rank
        self.ip = ip
        self.port = port

    def __repr__(self) -> str:
        return f"RingNode(rank={self.rank}, ip={self.ip!r}, port={self.port})"


_current_ring = None
_pending_listener = None  # pre-bound rendezvous listener for this rank


def take_pending_listener():
    """The listener this rank bound before advertising its port (consumed
    by default_initializer; None if the rendezvous didn't pre-bind)."""
    global _pending_listener
    listener, _pending_listener = _pending_listener, None
    return listener


def current_ring():
    """The HostRing built by default_initializer in this rank's process."""
    if _current_ring is None:
        raise RuntimeError("no HostRing in this process "
                           "(did the Ring use default_initializer?)")
    return _current_ring


def default_initializer(rank: int, size: int,
                        addrs: List[Tuple[str, int]]) -> None:
    """Build the host-plane ring collective group for this rank."""
    global _current_ring
    from fiber_tpu.ops.collectives import HostRing

    _current_ring = HostRing(rank, size, addrs,
                             listener=take_pending_listener())


# Marks initializers that consume the pre-bound rendezvous listener; all
# others (e.g. jax_distributed_initializer, whose coordinator must bind
# the advertised port itself) get an unbound advertised port instead.
default_initializer._prebind = True  # type: ignore[attr-defined]


def jax_distributed_initializer(rank: int, size: int,
                                addrs: List[Tuple[str, int]]) -> None:
    """Join all ranks into one JAX distributed runtime (TPU pod path):
    rank 0's address is the coordinator; afterwards jax.devices() spans
    every host and collectives ride ICI/DCN.

    Ranks are ONE PER HOST. A chip belongs to one process at a time and
    a JAX process reaches for every chip of its host; the launcher
    gives every rank the same environment, so it cannot hand each rank
    a chip of its own. Tried on one four-chip v5e host (PR 21): rank 0
    took all four chips and ranks 1-3 died with "The TPU is already in
    use by process with pid N". (Ranks CAN share a host if the caller's
    own initializer sets libtpu's per-process variables —
    ``TPU_VISIBLE_CHIPS``, ``TPU_CHIPS_PER_PROCESS_BOUNDS``,
    ``TPU_PROCESS_BOUNDS``, ``TPU_PROCESS_ADDRESSES``,
    ``TPU_PROCESS_PORT``, ``CLOUD_TPU_TASK_ID`` — from ``rank`` before
    jax is imported, then calls this function: that ran, four processes
    with one chip each and a global psum. It is the caller's code, not
    a launcher feature.) On one host the supported path is the
    single-process mesh (``jax.devices()`` in the process that calls
    the device plane). Mark the rank function
    ``@fiber_tpu.meta(device=True)``: jobs without a device hint are
    host-plane workers, whose JAX the launcher pins to the CPU.

    On CPU hosts (tests, dev boxes) cross-process collectives need the
    gloo implementation selected before the backend initializes; on TPU
    the ICI fabric needs nothing extra. Verified end-to-end by
    tests/test_ring.py::test_jax_distributed_ring_psum (2 processes x 4
    CPU devices, global psum) — the contract the reference delegates to
    torch.distributed/Horovod (examples/ring.py:141-174)."""
    import jax

    if jax.config.jax_platforms == "cpu":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    coordinator = f"{addrs[0][0]}:{addrs[0][1]}"
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=size,
        process_id=rank,
    )


def _ring_target(rank: int, size: int, nodes_proxy, func: Callable,
                 initializer: Optional[Callable]) -> None:
    import socket as pysocket

    from fiber_tpu.backends import get_backend

    global _pending_listener

    ip, _, _ = get_backend().get_listen_addr()
    if getattr(initializer, "_prebind", False):
        # Bind BEFORE advertising: the reference advertises a random port
        # and binds later (ring.py:91-98), which races when ranks share a
        # machine. Only for initializers that consume the listener.
        listener = pysocket.socket(pysocket.AF_INET, pysocket.SOCK_STREAM)
        listener.setsockopt(pysocket.SOL_SOCKET, pysocket.SO_REUSEADDR, 1)
        listener.bind(("", 0))
        listener.listen(2)
        port = listener.getsockname()[1]
        _pending_listener = listener
    else:
        # The consumer (e.g. jax.distributed's coordinator) binds the
        # advertised port itself — it must be free, not squatted.
        port = random.randint(30000, 50000)
    nodes_proxy[rank] = RingNode(rank, ip, port)

    deadline = time.monotonic() + 120
    while True:
        nodes = list(nodes_proxy)
        if all(n is not None for n in nodes):
            break
        if time.monotonic() > deadline:
            raise TimeoutError(f"rank {rank}: ring rendezvous timed out")
        time.sleep(0.05)
    nodes.sort(key=lambda n: n.rank)
    addrs = [(n.ip, n.port) for n in nodes]

    if initializer is not None:
        initializer(rank, size, addrs)
    leftover = take_pending_listener()
    if leftover is not None:  # initializer didn't consume it: release
        leftover.close()
    func(rank, size)


class Ring:
    """Launch ``size`` processes all running ``func(rank, size)`` after
    ``initializer(rank, size, addrs)`` has wired the collective group."""

    def __init__(self, size: int, func: Callable,
                 initializer: Optional[Callable] = default_initializer,
                 ) -> None:
        if size < 1:
            raise ValueError("ring size must be >= 1")
        self.size = size
        self.func = func
        self.initializer = initializer
        self.procs: list = []
        self._manager = None

    def run(self, join: bool = True) -> None:
        import fiber_tpu
        from fiber_tpu.meta import get_meta
        from fiber_tpu.process import Process

        self._manager = fiber_tpu.Manager()
        nodes = self._manager.list([None] * self.size)
        # Rank processes inherit the user function's @meta hints (cpu/mem/
        # tpu) even though their direct target is the rendezvous shim
        # (reference forwards them the same way, experimental/ring.py:78-82).
        hints = get_meta(self.func)
        self.procs = [
            Process(
                target=_ring_target,
                args=(rank, self.size, nodes, self.func, self.initializer),
                name=f"RingRank-{rank}",
                meta_hints=hints or None,
            )
            for rank in range(self.size)
        ]
        for p in self.procs:
            p.start()
        if join:
            self.join()

    def join(self, timeout: Optional[float] = None) -> None:
        try:
            for p in self.procs:
                p.join(timeout)
                if p.exitcode not in (0, None):
                    raise RuntimeError(
                        f"ring rank process {p.name} exited with "
                        f"{p.exitcode}"
                    )
        finally:
            if self._manager is not None:
                self._manager.shutdown()
                self._manager = None
