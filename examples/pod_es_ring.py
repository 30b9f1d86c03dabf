"""The full pod topology, end-to-end: Ring rank processes launched as
CLUSTER JOBS through the tpu backend's host agents, joined into ONE
multi-process JAX mesh, running a fused EvolutionStrategy over it.

This is the composition the framework exists for (reference: ring ranks
as real cluster jobs — fiber/experimental/ring.py:103-129 over
kubernetes_backend.py:104-174 — which then hand off to
torch.distributed; here the hand-off is jax.distributed + lax
collectives). On a real pod slice each rank lands on a TPU-VM host and
the mesh rides ICI; with --sim the identical code runs on simulated
hosts and a virtual CPU mesh.

Run:  python examples/pod_es_ring.py --sim 2          # simulated hosts
      FIBER_BACKEND=tpu FIBER_TPU_HOSTS=h1,h2 python examples/pod_es_ring.py

Ranks are device jobs (``@fiber_tpu.meta(device=True)``): one rank per
HOST, each taking every chip of its host — a chip belongs to one process
at a time, so on ONE multi-chip host the supported path is the
single-process mesh (``jax.devices()`` in the master), not N ranks (see
``jax_distributed_initializer``'s docstring for what happens if you
try). Host-plane workers, by contrast, are pinned to
``JAX_PLATFORMS=cpu`` by the launcher.

To force the sim run onto a virtual CPU mesh (no accelerator), export
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4``.
Rank stdout lands in the per-job agent logs — fetch with
``fiber-tpu logs <jid>``; rank 0's generation table shows there.
"""

import os as _os
import sys as _sys

_sys.path.insert(
    0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse

from fiber_tpu.meta import meta


@meta(device=True)  # a rank takes its host's chips: a device job
def train_rank(rank, size):
    """Runs identically on every rank AFTER jax.distributed joined them:
    one SPMD ES program over the global mesh."""
    import numpy as np

    import jax

    assert jax.process_count() == size
    from jax.sharding import Mesh

    from fiber_tpu.models import CartPole, MLPPolicy
    from fiber_tpu.ops import EvolutionStrategy

    mesh = Mesh(np.array(jax.devices()), ("pool",))
    policy = MLPPolicy(CartPole.obs_dim, CartPole.act_dim, hidden=(16,))

    def eval_fn(theta, key):
        return CartPole.rollout(policy.act, theta, key, max_steps=100)

    es = EvolutionStrategy(
        eval_fn, dim=policy.dim, pop_size=8 * len(jax.devices()),
        sigma=0.1, lr=0.03, mesh=mesh,
    )
    params = policy.init(jax.random.PRNGKey(0))
    params, stats = es.run_fused(params, jax.random.PRNGKey(1), 5)
    stats = jax.device_get(stats)
    if rank == 0:
        for g, (mean_f, max_f, _) in enumerate(stats):
            print(f"gen {g}: mean fitness {mean_f:8.2f}  max {max_f:8.2f}")
    jax.distributed.shutdown()


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--size", type=int, default=2,
                        help="ring size (one rank per pod host)")
    parser.add_argument("--sim", type=int, default=0, metavar="N",
                        help="run against N simulated localhost agents")
    args = parser.parse_args()

    if args.sim:
        os_env = _os.environ
        os_env["FIBER_BACKEND"] = "tpu"
        os_env["FIBER_TPU_HOSTS"] = f"sim:{args.sim}"

    import fiber_tpu  # noqa: F401  (backend selected by env)
    from fiber_tpu.parallel.ring import Ring, jax_distributed_initializer

    ring = Ring(args.size, train_rank,
                initializer=jax_distributed_initializer)
    ring.run()
    print("all ranks joined cleanly")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
