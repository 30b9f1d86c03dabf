"""Reproduce the deceptive-maze comparison records (novelty-search
family vs plain ES, and MAP-Elites illumination) and write them to
RUNS/novelty_maze_r{N}.json / RUNS/qd_maze_r{N}.json.

Exists so the headline claims ("plain ES pins at the wall; the NS
family escapes; MAP-Elites illuminates past it") are re-validated
whenever the maze physics change — round 3 tightened the wall to park
blocked steps at the intersection point (no lateral slide), so the
round-2 records needed re-measuring under strict physics.

Run:  python scripts/maze_records.py [--round 3] [--pop 128] [--gens 60]
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--round", type=int, default=3)
    parser.add_argument("--pop", type=int, default=128)
    parser.add_argument("--gens", type=int, default=60)
    parser.add_argument("--cells", type=int, default=12)
    parser.add_argument("--nsra-extended", type=int, default=150,
                        help="extra NSRA-ES arm at this longer horizon "
                             "(0 disables) — under strict wall physics "
                             "the adaptive slow-starter needs ~2x the "
                             "generations to escape")
    args = parser.parse_args()

    # Pin the platform AND the virtual device count BEFORE anything
    # initializes a backend (jax.default_backend() would cache it):
    # cpu with the 8-device plane the checked-in records were measured
    # on, unless the caller asked for an accelerator via JAX_PLATFORMS.
    # Per-device RNG folds and the gather topology depend on the device
    # count, so reproduction requires the same plane.
    # Only an explicit cpu/tpu request is honored; anything else
    # reproduces on the cpu plane.
    platform = os.environ.get("JAX_PLATFORMS", "")
    if platform not in ("cpu", "tpu"):
        platform = "cpu"
    os.environ["JAX_PLATFORMS"] = platform
    if platform == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        # Shared-core virtual mesh: a starved collective participant
        # must be slow, not abort() the interpreter.
        from fiber_tpu.utils.misc import (
            ensure_cpu_collective_timeout_flags,
        )

        ensure_cpu_collective_timeout_flags()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fiber_tpu.models import DeceptiveMaze, MLPPolicy
    from fiber_tpu.ops import EvolutionStrategy, MAPElites, NoveltyES

    policy = MLPPolicy(DeceptiveMaze.obs_dim, DeceptiveMaze.act_dim,
                       hidden=(16,))
    p0 = policy.init(jax.random.PRNGKey(0))
    goal = jnp.asarray(DeceptiveMaze.GOAL)

    def fitness_fn(theta, key):
        return DeceptiveMaze.rollout(policy.apply, theta, key)

    def eval_bc_fn(theta, key):
        pos = DeceptiveMaze.rollout_xy(policy.apply, theta, key)
        return -jnp.sqrt(jnp.sum((pos - goal) ** 2)), pos

    def best_ever(stepper, state, key, gens):
        best, at = -float("inf"), -1
        for g in range(gens):
            key, k = jax.random.split(key)
            state, stats = stepper(state, k)
            cur = float(jax.device_get(stats)[1])
            if cur > best:
                best, at = cur, g
        return best, at, state

    results = {}
    es = EvolutionStrategy(fitness_fn, dim=policy.dim,
                           pop_size=args.pop, sigma=0.1, lr=0.05)
    b, at, _ = best_ever(es.step, p0, jax.random.PRNGKey(1), args.gens)
    results["plain_es"] = {"best_ever": round(b, 3)}
    print(f"plain ES: best {b:.3f}", flush=True)

    def nsra_arm(name, w, adaptive, gens):
        nes = NoveltyES(eval_bc_fn, dim=policy.dim, bc_dim=2,
                        pop_size=args.pop, sigma=0.1, lr=0.05,
                        archive_size=128, k=10, reward_weight=w,
                        adaptive=adaptive, weight_delta=0.1, patience=5)
        state = nes.init_state(p0, jax.random.PRNGKey(2))
        b, at, state = best_ever(nes.step, state, jax.random.PRNGKey(3),
                                 gens)
        results[name] = {"best_ever": round(b, 3), "at_gen": at,
                         "final_w": round(float(state.w), 3)}
        print(f"{name}: best {b:.3f} at gen {at}", flush=True)

    nsra_arm("ns_es", 0.0, False, args.gens)
    nsra_arm("nsr_es", 0.5, False, args.gens)
    nsra_arm("nsra_es", 1.0, True, args.gens)
    if args.nsra_extended and args.nsra_extended > args.gens:
        nsra_arm(f"nsra_es_{args.nsra_extended}gens", 1.0, True,
                 args.nsra_extended)
        results[f"nsra_es_{args.nsra_extended}gens"]["note"] = (
            "adaptive slow-starter at a longer horizon: stagnation "
            "anneals the weight toward pure novelty and the archive "
            "carries it around the wall")

    n_dev = len(jax.devices())
    record = {
        "metric": "novelty_search_maze",
        "env": "DeceptiveMaze",
        "wall_physics": "strict (blocked steps park at the "
                        "intersection point; round-2 advisor finding, "
                        "fixed in round 3)",
        "pop": es.pop_size, "generations": args.gens,
        "platform": jax.devices()[0].platform, "n_devices": n_dev,
        "scoring": "best candidate ever found (deceptive-domain "
                   "convention); 0 = at goal, -1.0 = pinned at the wall",
        "results": results,
    }
    out = os.path.join(REPO, "RUNS", f"novelty_maze_r{args.round:02d}.json")
    with open(out, "w") as fh:
        json.dump(record, fh, indent=1)
    print("wrote", out, flush=True)

    # ---- MAP-Elites illumination (same (fitness, behavior) eval) ----
    me = MAPElites(eval_bc_fn, dim=policy.dim, bc_dim=2,
                   bc_low=(-4.0, -4.0), bc_high=(4.0, 4.0),
                   cells_per_dim=args.cells, batch_size=256, sigma=0.2)
    state = me.init_state(p0, jax.random.PRNGKey(4))
    key = jax.random.PRNGKey(5)
    history = []
    for gen in range(args.gens):
        key, k = jax.random.split(key)
        state, stats = me.step(state, k)
        if gen % 10 == 0 or gen == args.gens - 1:
            history.append({"gen": gen,
                            "qd": round(float(stats[0]), 1),
                            "coverage": round(float(stats[1]), 3),
                            "best": round(float(stats[2]), 3)})
            print(f"gen {gen}: coverage {float(stats[1]):.1%} "
                  f"best {float(stats[2]):.3f}", flush=True)
    best_fit = float(jax.device_get(state.fitness.max()))
    beyond = int(np.asarray(jax.device_get(
        (state.behaviors[:, 1] > 1.0)
        & jnp.isfinite(state.fitness))).sum())
    qd_record = {
        "metric": "map_elites_maze",
        "env": "DeceptiveMaze",
        "wall_physics": record["wall_physics"],
        "cells": args.cells ** 2,
        "batch": int(getattr(me, "batch_size", 256)),
        "generations": args.gens,
        "platform": jax.devices()[0].platform, "n_devices": n_dev,
        "final_coverage": round(float(stats[1]), 3),
        "best_elite_fitness": round(best_fit, 3),
        "maze_solved": best_fit > -0.5,
        "cells_beyond_wall": beyond,
        "history_every10": history,
    }
    out = os.path.join(REPO, "RUNS", f"qd_maze_r{args.round:02d}.json")
    with open(out, "w") as fh:
        json.dump(qd_record, fh, indent=1)
    print("wrote", out, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
