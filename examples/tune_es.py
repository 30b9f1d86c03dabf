"""Sweep ES population sizes on the attached accelerator and report the
best operating point (evals/sec rises with population until the chip
saturates; the north-star metric rewards raw eval throughput).

Run:  python examples/tune_es.py [--pops 2048,4096,8192,16384]
      [--steps 500] [--gens 5] [--json OUT.json]

Used by the round harness to pick bench.py's --pop on real hardware.
"""

import argparse
import json
import os as _os
import sys as _sys
import time

_sys.path.insert(
    0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--pops", default="2048,4096,8192,16384")
    parser.add_argument("--steps", type=int, default=500)
    parser.add_argument("--gens", type=int, default=5)
    parser.add_argument("--platform", default="")
    parser.add_argument("--json", default="")
    args = parser.parse_args()
    if args.platform:
        # nothing preloads jax, so the environment variable is enough
        _os.environ["JAX_PLATFORMS"] = args.platform

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from fiber_tpu.models import CartPole, MLPPolicy
    from fiber_tpu.ops import EvolutionStrategy

    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("pool",))
    policy = MLPPolicy(CartPole.obs_dim, CartPole.act_dim, hidden=(32, 32))

    def eval_fn(theta, key):
        return CartPole.rollout(policy.act, theta, key,
                                max_steps=args.steps)

    rows = []
    for pop in (int(p) for p in args.pops.split(",")):
        es = EvolutionStrategy(eval_fn, dim=policy.dim, pop_size=pop,
                               sigma=0.1, lr=0.03, mesh=mesh)
        params = policy.init(jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        t0 = time.perf_counter()
        params, stats = es.run_fused(params, key, args.gens)
        jax.block_until_ready(stats)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        params, stats = es.run_fused(params, jax.random.PRNGKey(2),
                                     args.gens)
        jax.block_until_ready(stats)
        dt = time.perf_counter() - t0
        evals_s = es.pop_size * args.gens / dt
        rows.append({
            "pop": es.pop_size,
            "evals_per_sec": round(evals_s, 1),
            "env_steps_per_sec": round(evals_s * args.steps, 1),
            "steady_s": round(dt, 3),
            "compile_s": round(compile_s, 1),
        })
        print(f"pop={es.pop_size:6d}  {evals_s:10.1f} evals/s  "
              f"(steady {dt:.3f}s, compile {compile_s:.1f}s)", flush=True)

    best = max(rows, key=lambda r: r["evals_per_sec"])
    out = {
        "platform": devices[0].platform,
        "n_devices": len(devices),
        "episode_steps": args.steps,
        "generations": args.gens,
        "rows": rows,
        "best_pop": best["pop"],
        "best_evals_per_sec": best["evals_per_sec"],
    }
    print(json.dumps(out))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    _sys.exit(main())
