"""Module-level task functions shipped to worker processes by the tests
(must be importable by reference in the child interpreter)."""

from __future__ import annotations

import os
import sys
import time


def noop() -> None:
    pass


def sleep_for(seconds: float) -> None:
    time.sleep(seconds)


def sleep_echo(x):
    """Small fixed-cost task returning its input — the scheduler-plane
    tests' unit of work (idempotent AND side-effect free, so straggler
    speculation may duplicate it)."""
    time.sleep(0.05)
    return x


def sleep_forever() -> None:
    while True:
        time.sleep(3600)


def spin_for(seconds: float):
    """CPU-bound busy loop (the sampling-profiler tests' unit of work:
    the worker must be ON-cpu so wall-clock samples land in it)."""
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        sum(i * i for i in range(200))
    return seconds


def exit_with(code: int) -> None:
    sys.exit(code)


def raise_error() -> None:
    raise ValueError("intentional test error")


def write_file(path: str, content: str) -> None:
    with open(path, "w") as fh:
        fh.write(content)


def write_process_name(path: str) -> None:
    import fiber_tpu

    with open(path, "w") as fh:
        fh.write(fiber_tpu.current_process().name)


def write_config_value(path: str, key: str) -> None:
    from fiber_tpu import config

    with open(path, "w") as fh:
        fh.write(str(getattr(config.get(), key)))


def arr_sum_plus(arr, i):
    """Broadcast-style task: reduce a (possibly store-resolved) shared
    array and mix in the per-task index."""
    return float(arr.sum()) + i


def arr_item(args):
    """map-over-tuples variant of arr_sum_plus: one positional arg that
    IS the (array, index) tuple."""
    arr, i = args
    return float(arr.sum()) + i


def big_result(nbytes: int):
    """Return a result large enough to travel by reference."""
    import numpy as np

    n = nbytes // 8
    return np.arange(n, dtype=np.float64)


def square(x: int) -> int:
    return x * x


def add(a, b):
    return a + b


def identity(x):
    return x


def random_error(x):
    """Fails ~5% of the time — resilient-pool stress helper (reference:
    tests/test_pool.py random_error_worker)."""
    import random

    if random.random() < 0.05:
        raise ValueError("injected random failure")
    return x


def pipe_echo(conn):
    """Duplex pipe child: echo objects back until None arrives."""
    while True:
        obj = conn.recv()
        if obj is None:
            break
        conn.send(("echo", obj))


def queue_worker(q_in, q_out):
    """Read tasks from q_in, square them into q_out, stop on None."""
    while True:
        item = q_in.get()
        if item is None:
            break
        q_out.put(item * item)


def queue_consume_n(q, n, q_result, tag):
    """Consume exactly n messages then report (tag, count)."""
    count = 0
    for _ in range(n):
        q.get()
        count += 1
    q_result.put((tag, count))


def mp_queue_producer(q, items):
    """Runs inside a *plain multiprocessing* process: fiber queues must
    work there too (reference: tests/test_queue.py:90-139)."""
    for item in items:
        q.put(item)


def raise_on_even(x):
    if x % 2 == 0:
        raise ValueError(f"even input: {x}")
    return x


_POOL_INIT_VALUE = None


def pool_initializer(value):
    global _POOL_INIT_VALUE
    _POOL_INIT_VALUE = value


def read_initialized(_):
    return _POOL_INIT_VALUE


def _die_once(x, trigger, marker_name):
    """Hard-kill the worker the first time ``x == trigger`` runs; the
    marker file keeps the resubmitted retry alive — exercises
    resubmission. One body shared by every die-once target so the crash
    simulation can't drift between tests."""
    import os
    import tempfile

    if x == trigger:
        marker = os.path.join(tempfile.gettempdir(), marker_name)
        if not os.path.exists(marker):
            with open(marker, "w") as fh:
                fh.write("died")
            os._exit(42)
    return x


def die_once_marker(x):
    return _die_once(x, 7, "fiber_die_once_marker")


def pi_inside(n):
    import random

    count = 0
    for _ in range(n):
        x, y = random.random(), random.random()
        if x * x + y * y <= 1.0:
            count += 1
    return count


def manager_list_appender(proxy, n):
    """Mutate a managed list from a remote process."""
    for i in range(n):
        proxy.append(i)


def manager_queue_consumer(qproxy, out_q, n):
    total = 0
    for _ in range(n):
        total += qproxy.get()
    out_q.put(total)


def slow_manager_call(x):
    import time

    time.sleep(1.0)
    return x * 2


class SlowWorker:
    """User class registered on an AsyncManager (RL-env style)."""

    def step(self, x):
        import time

        time.sleep(1.0)
        return x + 100


def ring_allreduce_check(rank, size):
    """Each rank contributes rank+1; allreduce must equal sum(1..size)."""
    import numpy as np

    from fiber_tpu.parallel.ring import current_ring

    ring = current_ring()
    arr = np.full(257, float(rank + 1), dtype=np.float32)  # odd size: chunk
    out = ring.allreduce(arr)
    expected = size * (size + 1) / 2
    assert np.allclose(out, expected), (rank, out[:4], expected)
    mean = ring.allreduce(np.ones(4, dtype=np.float32), op="mean")
    assert np.allclose(mean, 1.0)
    ring.close()


def ring_sgd_step(rank, size):
    """Mini data-parallel SGD: per-rank gradient, ring-averaged update
    (the reference's examples/ring.py workload without torch/gloo)."""
    import numpy as np

    from fiber_tpu.parallel.ring import current_ring

    ring = current_ring()
    w = np.zeros(8, dtype=np.float32)
    for _ in range(3):
        grad = np.full(8, float(rank + 1), dtype=np.float32)
        avg = ring.allreduce(grad, op="mean")
        w -= 0.1 * avg
    expected = -0.3 * (size + 1) / 2
    assert np.allclose(w, expected), (rank, w[0], expected)
    ring.close()


def jax_array_doubler(q_in, q_out):
    """Receives jax arrays through a queue (custom reducer path),
    computes, ships back."""
    import jax.numpy as jnp

    while True:
        item = q_in.get()
        if item is None:
            return
        q_out.put(jnp.asarray(item) * 2)


def locked_increment(lock, ns, n):
    """Read-modify-write under a distributed manager lock."""
    for _ in range(n):
        with lock:
            ns.counter = ns.counter + 1


def barrier_then_report(barrier, q, tag):


    t0 = time.time()
    barrier.wait()
    q.put((tag, time.time() - t0))


def condition_consumer(cond, ns, out_q):
    with cond:
        while not ns.ready:
            cond.wait(30)
    out_q.put("saw ready")


def jax_distributed_psum_check(rank, size):
    """Each rank joins one jax.distributed runtime (the TPU pod path on a
    CPU mesh): devices must span all processes and a global shard_map
    psum must see every process's shard."""
    import numpy as np

    import jax

    # The initializer already ran jax.distributed.initialize; the mesh
    # below spans BOTH processes' devices.
    assert jax.process_count() == size, jax.process_count()
    n = len(jax.devices())
    assert n == size * len(jax.local_devices()), (n, jax.local_devices())

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from fiber_tpu.utils.jaxcompat import shard_map

    mesh = Mesh(np.array(jax.devices()), ("x",))
    sharding = NamedSharding(mesh, P("x"))
    x = jax.make_array_from_callback(
        (n,), sharding, lambda idx: np.arange(n, dtype=np.float32)[idx]
    )
    f = jax.jit(shard_map(
        lambda v: jax.lax.psum(v, "x"),
        mesh=mesh, in_specs=P("x"), out_specs=P(),
    ))
    y = f(x)
    local = np.asarray(y.addressable_shards[0].data)
    expected = n * (n - 1) / 2  # sum over the global arange
    assert float(local.ravel()[0]) == expected, (local, expected)
    jax.distributed.shutdown()


def die_once_sub(x):
    """die_once_marker with its own marker file — used by the
    cpu_per_job packing tests so the two tests can't interfere."""
    return _die_once(x, 5, "fiber_die_once_sub")


def die_randomly(x):
    """~7% chance of hard-killing the worker per execution — churn
    stress for sub-worker-granular resubmission (every chunk must still
    complete eventually; tasks are idempotent)."""
    import os

    if os.urandom(1)[0] < 18:  # 18/256 ≈ 7%
        os._exit(43)
    return x * 3


def jax_distributed_es_step(rank, size):
    """The REAL pod training path, not just a bare psum: a fused
    EvolutionStrategy step over the GLOBAL mesh spanning every rank's
    devices. All ranks run the same SPMD program; the resulting params
    (replicated) must be finite and identical across processes."""
    import numpy as np

    import jax

    assert jax.process_count() == size
    from jax.sharding import Mesh

    from fiber_tpu.models import CartPole, MLPPolicy
    from fiber_tpu.ops import EvolutionStrategy

    mesh = Mesh(np.array(jax.devices()), ("pool",))
    policy = MLPPolicy(CartPole.obs_dim, CartPole.act_dim, hidden=(8,))

    def eval_fn(theta, key):
        return CartPole.rollout(policy.act, theta, key, max_steps=20)

    es = EvolutionStrategy(
        eval_fn, dim=policy.dim, pop_size=4 * len(jax.devices()),
        sigma=0.1, lr=0.05, mesh=mesh,
    )
    params = policy.init(jax.random.PRNGKey(0))
    params, stats_seq = es.run_fused(params, jax.random.PRNGKey(1), 2)
    jax.block_until_ready(stats_seq)
    local_stats = np.asarray(jax.device_get(stats_seq))
    assert local_stats.shape == (2, 3), local_stats.shape
    assert np.isfinite(local_stats).all(), local_stats
    # Params are replicated over the global mesh: every process must
    # hold the same vector (divergence means the psum didn't span
    # processes). Verify through the mesh itself: the pmax-pmin spread
    # of a per-device params digest must be zero across ALL devices of
    # ALL processes.
    local_params = np.asarray(
        jax.device_get(params.addressable_shards[0].data)
    ).ravel()
    digest = float(np.sum(local_params * np.arange(1, len(local_params) + 1)))
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fiber_tpu.utils.jaxcompat import shard_map

    n = len(jax.devices())
    sharding = NamedSharding(mesh, P("pool"))
    digests = jax.make_array_from_callback(
        (n,), sharding,
        lambda idx: np.full((1,), digest, dtype=np.float32),
    )
    spread_fn = jax.jit(shard_map(
        lambda v: jax.lax.pmax(v.ravel()[0], "pool")
        - jax.lax.pmin(v.ravel()[0], "pool"),
        mesh=mesh, in_specs=P("pool"), out_specs=P(),
    ))
    spread = float(np.asarray(jax.device_get(
        spread_fn(digests).addressable_shards[0].data
    )).ravel()[0])
    scale = max(1.0, abs(digest))
    assert spread / scale < 1e-6, (spread, digest)
    jax.distributed.shutdown()


def interlocked_queue_worker(args):
    """One end of an interlocked queue pair (reference chunk-size
    regression, fiber tests/test_pool.py:179-234): announces READY,
    then blocks for instructions that the master only sends after ALL
    workers announced — so the map deadlocks unless every task landed
    on a DISTINCT concurrently-running worker (chunksize accounting
    and fair handout are both load-bearing here)."""
    i, (instructions, returns) = args
    returns.put(("READY", i))
    while True:
        ins = instructions.get(timeout=120)
        if ins == "QUIT":
            return i
        returns.put(("ACK", i))


def _explode_on_load():
    raise RuntimeError("poison payload refused to deserialize")


class PoisonOnLoad:
    """Pickles fine on the master, raises on UNpickling — lands in the
    worker's task-decode path and kills the process, modeling any
    payload that can never deserialize remotely (version skew,
    un-importable __main__, corrupted blob)."""

    def __reduce__(self):
        return (_explode_on_load, ())


def arr_sum_plus_accel(arr, i):
    """arr_sum_plus with an accelerator hint: @meta(tpu=1) marks the
    task device-destined, so its broadcast refs carry device_hint and
    the worker resolves them through the device store tier
    (docs/objectstore.md "Device tier")."""
    return float(arr.sum()) + i


# Decorated at import so master and worker agree on the meta; the
# import stays below the function to keep targets importable before
# fiber_tpu config exists in exotic child bootstraps.
from fiber_tpu.meta import meta as _meta  # noqa: E402

arr_sum_plus_accel = _meta(tpu=1)(arr_sum_plus_accel)


def jax_worker_view(arr):
    """What a host-plane worker sees of JAX: its platform pin, and where
    a pickled jax.Array landed when it was unpickled here (the one-
    process-per-chip rule — tests/test_chip_smoke.py)."""
    import jax

    return {
        "pid": os.getpid(),
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
        "is_jax_array": isinstance(arr, jax.Array),
        "platforms": sorted({d.platform for d in arr.devices()}),
        "default_backend": jax.default_backend(),
        "sum": float(arr.sum()),
    }
