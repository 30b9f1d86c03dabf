"""Runner kind ``es_fused``: ``EvolutionStrategy.run_fused`` back to back.

The timed call is ``es.run_fused(params, key_i, G)`` followed by
``block_until_ready``; the first ``checked_calls`` calls of the same
object are made in set-up (they are the warm-up) and are what the plain
reference follows afterwards.
"""

from __future__ import annotations

import importlib

import numpy as np


def make_es(cfg, devices, policy_dtype=None):
    """The program's object for the configuration: (EvolutionStrategy,
    policy), on a mesh of ``devices``."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from fiber_tpu.models import MLPPolicy, ParamBipedWalker
    from fiber_tpu.ops.es import EvolutionStrategy

    if cfg["environment"] != "ParamBipedWalker":
        raise ValueError(f"no environment {cfg['environment']!r} here")
    policy = MLPPolicy(cfg["obs_size"], cfg["action_count"],
                       hidden=tuple(cfg["hidden"]),
                       compute_dtype=policy_dtype)
    if policy.dim != cfg["parameter_count"]:
        raise ValueError(
            f"policy has {policy.dim} parameters, the configuration "
            f"states {cfg['parameter_count']}")
    course = jnp.asarray(cfg["course"], jnp.float32)
    steps = int(cfg["episode_steps"])

    def eval_fn(theta, key):
        return ParamBipedWalker.rollout_p(
            policy.act, course, theta, key, steps)

    es = EvolutionStrategy(
        eval_fn, policy.dim, int(cfg["population"]),
        sigma=cfg["sigma"], lr=cfg["learning_rate"],
        weight_decay=cfg["weight_decay"], optimizer=cfg["optimizer"],
        mesh=Mesh(np.asarray(devices), ("pool",)))
    return es, policy


def aot_lower(cfg, traffic, devices):
    """The cell's program lowered for ``devices`` (described, not
    attached): what ``run_fused`` compiles, from shapes alone."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from fiber_tpu.ops.es import build_fused_runner

    es, policy = make_es(cfg, devices)
    everywhere = NamedSharding(es.mesh, PartitionSpec())
    vec = jax.ShapeDtypeStruct((policy.dim,), jnp.float32,
                               sharding=everywhere)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=everywhere)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=everywhere)
    fused = build_fused_runner(es._device_step_fn, es.mesh, 4,
                               int(traffic["generations_per_call"]))
    return fused.lower(vec, vec, vec, scalar, key)


class Runner:
    rate = "es_evals_per_s"   # the end-to-end metric: rollouts a second

    def __init__(self, cfg, traffic, key, seed, devices, spans,
                 rehearsal=False):
        self.cfg, self.traffic = cfg, traffic
        self.key = key
        self.devices = devices
        self.span = spans
        self.gens = int(traffic["generations_per_call"])
        self.checked = int(traffic["checked_calls"])
        self.hidden = tuple(cfg["hidden"])
        self.calls_made = 0
        self.program = {}

    # -- set-up ----------------------------------------------------------
    def build(self, policy_dtype=None):
        """``policy_dtype`` switches on the program's own lower-precision
        policy path: the control's, never a benchmark run's."""
        import jax

        self.es, policy = make_es(self.cfg, self.devices, policy_dtype)
        init_key, self.call_key = jax.random.split(self.key)
        self.init_key = init_key
        self.params = jax.jit(policy.init)(init_key)
        # every call's key, drawn once: nothing is traced inside the window
        self.keys = np.asarray(jax.random.split(self.call_key, 4096))

    def units_per_call(self) -> int:
        return self.gens * int(self.cfg["population"])

    def one_call(self):
        """The window's own call; returns the per-generation stats."""
        import jax
        import jax.numpy as jnp

        with self.span("dispatch"):
            key = jnp.asarray(self.keys[self.calls_made % len(self.keys)])
            self.params, stats = self.es.run_fused(self.params, key, self.gens)
        with self.span("wait"):
            jax.block_until_ready((self.params, stats))
        self.calls_made += 1
        return stats

    def checked_steps(self):
        """Drive the first calls and keep the program's side of the check:
        mean fitness of each generation, the first gradient as Adam got it
        (m / (1 - b1) after one update), the parameters after the calls."""
        p0 = self.params
        fitness, grad = [], None
        for i in range(self.checked):
            stats = self.one_call()
            fitness.extend(float(x) for x in np.asarray(stats)[:, 0])
            if i == 0 and self.gens == 1:
                grad = np.asarray(self.es._opt_state[0]) / (1.0 - 0.9)
        self.program = {
            "fitness": fitness, "grad": grad,
            "update": np.asarray(self.params - p0),
        }

    # -- window ----------------------------------------------------------
    def call(self) -> int:
        self.one_call()
        return self.units_per_call()

    def free(self):
        self.es = None
        self.params = None

    # -- the check ---------------------------------------------------------
    def reference(self, policy_dtype=None, members=None):
        """The plain reference over the checked generations, from the same
        keys. ``policy_dtype`` and ``members`` are the control's and a
        fault's; the benchmark's own runs pass neither."""
        import jax.numpy as jnp

        ref = importlib.import_module(self.cfg["reference"])

        cfg = self.cfg
        params = ref.init_policy(self.init_key, self.hidden)
        p0 = params
        m = v = jnp.zeros_like(params)
        t = jnp.asarray(0.0)
        fitness, grad0 = [], None
        if self.gens != 1:
            raise ValueError("the check follows calls of one generation")
        for i in range(self.checked):
            params, m, v, t, grad, fit = ref.generation(
                params, m, v, t, jnp.asarray(self.keys[i]),
                pop=int(cfg["population"]), sigma=cfg["sigma"],
                lr=cfg["learning_rate"], hidden=self.hidden,
                steps=int(cfg["episode_steps"]), course=cfg["course"],
                block=int(self.traffic["reference_block"]),
                policy_dtype=policy_dtype, members=members)
            fitness.append(float(fit.mean()))
            if i == 0:
                grad0 = np.asarray(grad)
        return {"fitness": fitness, "grad": grad0,
                "update": np.asarray(params - p0)}

    def compare(self, program, reference):
        """[(name, value), ...]: the numbers that ``limits`` bounds."""
        out = []
        for i, (a, b) in enumerate(zip(program["fitness"],
                                       reference["fitness"])):
            out.append((f"fit{i + 1}", abs(a - b) / max(abs(b), 1e-6)))
        gp, gr = program["grad"], reference["grad"]
        n_r = float(np.linalg.norm(gr))
        out.append(("grad", abs(float(np.linalg.norm(gp)) - n_r) / n_r))
        out.append(("grad_dir", float(np.linalg.norm(gp - gr)) / n_r))
        u_r = float(np.linalg.norm(reference["update"]))
        out.append(("update", abs(float(np.linalg.norm(program["update"]))
                                  - u_r) / u_r))
        return out
