"""Evolution strategies, TPU-native.

The north-star workload (BASELINE.json: OpenAI-ES / POET at ≥10k policy
evals/sec): where the reference evaluates its population by shipping pickled
tasks to cluster workers through fiber.Pool (examples/gecco-2020/es.py is a
Pool(40).map loop), fiber_tpu compiles the *entire generation* into one SPMD
program over the device mesh:

* the population axis is sharded over the mesh's ``pool`` axis;
* each device draws its own antithetic perturbations on-chip (threefry
  fold-in of the replicated generation key — no noise table in HBM traffic,
  no host RNG shipping);
* policy rollouts run vmapped per device (the (pop, dim) perturbation and
  (pop,) fitness tensors are MXU/VPU-shaped);
* fitness is all-gathered (tiny), centered-rank shaping is computed
  redundantly on every device (cheaper than communicating ranks);
* the gradient estimate is one ``lax.psum`` over ICI;
* the update happens on-device; parameters stay replicated across the mesh
  between generations — nothing round-trips through the host.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple


def run_steps(step, state, key, generations: int):
    """Shared generation driver for the state-based ES family (PGPE,
    SepCMAES): N `step(state, key)` calls, returning (state, stats
    history)."""
    import jax

    history = []
    for _ in range(generations):
        key, sub = jax.random.split(key)
        state, stats = step(state, sub)
        history.append(stats)
    return state, history


def build_fused_runner(device_step, mesh, n_state: int,
                       generations: int):
    """N generations as ONE XLA program: a lax.scan over a per-device
    step inside a single shard_map — per-generation dispatch overhead
    disappears (it dominates small-population steps on real
    accelerators). Shared by every algorithm family.

    ``device_step(*state, key) -> (*state, stats)`` must be the raw
    per-device function (the body normally wrapped in shard_map), with
    ``n_state`` replicated state slots. The returned runner maps
    ``(*state, key) -> (*state, stats_seq)`` with
    ``stats_seq.shape[0] == generations``.
    """
    import jax
    from fiber_tpu.utils.jaxcompat import shard_map
    from jax.sharding import PartitionSpec as P

    def device_run(*args):
        state, key = args[:-1], args[-1]

        def body(carry, _):
            st, key = carry[:-1], carry[-1]
            key, sub = jax.random.split(key)
            out = device_step(*st, sub)
            return (*out[:-1], key), out[-1]

        carry, stats_seq = jax.lax.scan(
            body, (*state, key), None, length=generations
        )
        return (*carry[:-1], stats_seq)

    spec = (P(),) * (n_state + 1)
    return jax.jit(shard_map(
        device_run,
        mesh=mesh,
        in_specs=spec,
        out_specs=spec,
        check_vma=False,
    ))


class _FusedRunMixin:
    """run_fused() for the state-tuple families. Requires
    ``self._device_step_fn`` (raw per-device step), ``self.mesh``,
    ``self.pop_size`` and the ``step``/``run`` contract ``state = tuple``
    (or NamedTuple). Compiled runners are cached per generation count.
    The runner's call is the ``es.run_fused`` span
    (docs/observability.md): compile spans hang from it."""

    def run_fused(self, state, key, generations: int):
        """Run N generations as one XLA program. Returns
        (state, stats_seq (generations, k)) — same trajectory as N
        ``step`` calls with the per-generation key splits."""
        from fiber_tpu.telemetry import device as device_telemetry

        cache = getattr(self, "_fused_runner_cache", None)
        if cache is None:
            cache = self._fused_runner_cache = {}
        fn = cache.get(generations)
        built = fn is None
        if built:
            fn = build_fused_runner(
                self._device_step_fn, self.mesh, len(tuple(state)),
                generations,
            )
            cache[generations] = fn
        with device_telemetry.step(
                "es.run_fused", generations * self.pop_size,
                generations=generations, pop=self.pop_size, built=built):
            out = fn(*tuple(state), key)
        new_state, stats_seq = out[:-1], out[-1]
        if hasattr(type(state), "_make"):  # NamedTuple states
            new_state = type(state)._make(new_state)
        return new_state, stats_seq


def apply_es_update(params, grad, m, v, t, *, lr, wd, adam,
                    b1=0.9, b2=0.999, eps=1e-8):
    """Shared ES parameter update (ascent direction): plain SGD or
    bias-corrected Adam on the estimated gradient, with decoupled
    (AdamW-style) weight decay applied to params directly, never routed
    through the adaptive moments. The ONE copy of this math — used by
    both the SPMD device step and :class:`AskTellES`, so the two paths
    cannot drift. Returns ``(new_params, m, v, t)``; in sgd mode the
    moment slots pass through untouched (zero-size placeholders)."""
    import jax.numpy as jnp

    if adam:
        t = t + 1.0
        m = b1 * m + (1 - b1) * grad
        v = b2 * v + (1 - b2) * grad * grad
        m_hat = m / (1 - b1 ** t)
        v_hat = v / (1 - b2 ** t)
        update = lr * m_hat / (jnp.sqrt(v_hat) + eps)
    else:
        update = lr * grad
    return params + update - lr * wd * params, m, v, t


def centered_rank(x):
    """Map fitness to centered ranks in [-0.5, 0.5] (OpenAI-ES shaping)."""
    import jax.numpy as jnp

    n = x.shape[0]
    order = jnp.argsort(x)
    ranks = jnp.empty_like(order).at[order].set(jnp.arange(n))
    return ranks.astype(jnp.float32) / (n - 1) - 0.5


def pair_fitness(eval_fn, params, eps, sigma, keys):
    """Fitness ``(2 * pairs,)`` of the members ``params +- sigma * eps``,
    rows ``[plus half; minus half]``, member ``i`` under ``keys[i]``,
    with each member handed to ``eval_fn`` as a ``PairParams`` (shared
    ``params``, its pair's ``eps`` row, ``+-sigma``) and ``thetas`` never
    formed: a ``vmap`` over the pairs (noise and the pair's two keys
    batched, ``params`` not) of a ``vmap`` over the sign (scale and key
    batched, the noise **not**), so a policy that sums the parts inside
    its layer product reads a pair's noise once for both members.
    Returns ``None`` where ``eval_fn`` cannot take the pair apart (it
    computes on ``theta`` itself, or its policy's ``unflatten`` refuses
    it): that is a ``TypeError`` / ``AttributeError`` at trace time,
    from the first thing done to ``theta``."""
    import jax
    import jax.numpy as jnp

    from fiber_tpu.models.policies import PairParams

    pairs = eps.shape[0]
    scales = jnp.asarray([sigma, -sigma], eps.dtype)

    def one_pair(noise, keys2):
        return jax.vmap(
            lambda scale, key: eval_fn(PairParams(params, noise, scale), key)
        )(scales, keys2)

    try:
        return jax.vmap(one_pair, in_axes=(0, 1), out_axes=1)(
            eps, keys.reshape(2, pairs, *keys.shape[1:])).reshape(-1)
    except (TypeError, AttributeError):
        return None


class EvolutionStrategy(_FusedRunMixin):
    """OpenAI-ES with antithetic sampling and rank shaping, compiled as one
    jitted SPMD step over a mesh.

    ``eval_fn(theta, key) -> scalar fitness`` must be pure and jittable
    (e.g. a policy rollout from fiber_tpu.models). ``theta`` is one
    member. Where ``eval_fn`` hands it untouched to a rollout of
    ``models/envs.py`` whose policy can take an antithetic pair apart
    (``MLPPolicy.act`` / ``.apply``), it is a ``PairParams``: the
    rollout's step reads the pair's noise once for both members and the
    ``(pop, dim)`` matrix of perturbed vectors is never formed
    (:func:`pair_fitness`). Anything else (arithmetic on ``theta``, a
    plain ``act`` function, ``ConvPolicy``, ``GRUPolicy``) gets the flat
    ``(dim,)`` float32 vector ``params +- sigma * eps``. The step
    observes which at trace time; fitness, ranks, gradient and update
    are the same numbers either way.
    """

    def __init__(
        self,
        eval_fn: Callable,
        dim: int,
        pop_size: int,
        sigma: float = 0.1,
        lr: float = 0.02,
        mesh=None,
        weight_decay: float = 0.0,
        optimizer: str = "sgd",
    ) -> None:
        import numpy as np

        from fiber_tpu.parallel.mesh import default_mesh

        if optimizer not in ("sgd", "adam"):
            raise ValueError(f"unknown optimizer {optimizer!r}")
        self.optimizer = optimizer
        self._opt_state = None  # adam (m, v, t), device-resident
        self.eval_fn = eval_fn
        self.dim = dim
        self.sigma = float(sigma)
        self.lr = float(lr)
        self.weight_decay = float(weight_decay)
        self.mesh = mesh or default_mesh()
        self.n_dev = int(np.prod(list(self.mesh.shape.values())))
        # pop must be even (antithetic pairs) and divisible by the mesh
        quantum = 2 * self.n_dev
        self.pop_size = max(quantum, (pop_size // quantum) * quantum)
        self.pairs_per_dev = self.pop_size // quantum
        # Noise is plain jax.random.normal: a Pallas fused-noise
        # experiment (regenerate eps instead of storing it) lived here
        # through round 4 but the on-chip fused-program A/B measured it
        # ~30x SLOWER end-to-end at bench shapes (custom-call grids
        # serialize inside the rollout scan while XLA fuses threefry
        # noise into it; HBM was never the bottleneck) — deleted in
        # round 5 on that standing record (`git log -- fiber_tpu/ops/
        # pallas_es.py` has the kernels).
        self._step = self._build_step()

    # ------------------------------------------------------------------
    def _build_step(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from fiber_tpu.utils.jaxcompat import shard_map

        eval_fn = self.eval_fn
        sigma = self.sigma
        lr = self.lr
        wd = self.weight_decay
        pairs = self.pairs_per_dev
        pop = self.pop_size
        dim = self.dim

        adam = self.optimizer == "adam"

        def device_step(params, m, v, t, key):
            # params (dim,) replicated; key replicated. In sgd mode the
            # (m, v, t) slots are zero-size placeholders (see step()) so
            # no dead state rides the jitted program.
            # The named scopes are metadata (every op's op_name in a
            # profile starts with its phase); they add no operation.
            my = jax.lax.axis_index("pool")
            with jax.named_scope("es.perturb"):
                dev_key = jax.random.fold_in(key, my)
                eps_key, eval_key = jax.random.split(dev_key)

                eps = jax.random.normal(eps_key, (pairs, dim))
            with jax.named_scope("es.rollout"):
                eval_keys = jax.random.split(eval_key, 2 * pairs)
                fitness = pair_fitness(eval_fn, params, eps, sigma,
                                       eval_keys)           # (2*pairs,)
            if fitness is None:  # eval_fn wants the vector itself
                with jax.named_scope("es.perturb"):
                    thetas = jnp.concatenate(
                        [params + sigma * eps, params - sigma * eps], axis=0
                    )  # (2*pairs, dim)
                with jax.named_scope("es.rollout"):
                    fitness = jax.vmap(eval_fn)(thetas, eval_keys)

            # Global rank shaping: gather all fitness (tiny), rank
            # identically on every device.
            with jax.named_scope("es.rank"):
                # (ndev, 2*pairs)
                all_fit = jax.lax.all_gather(fitness, "pool")
                flat_fit = all_fit.reshape(-1)
                ranks = centered_rank(flat_fit).reshape(all_fit.shape)
                my_ranks = ranks[my]                       # (2*pairs,)
                w = my_ranks[:pairs] - my_ranks[pairs:]    # antithetic weights

            with jax.named_scope("es.gradient"):
                g_local = w @ eps                          # (dim,) on the MXU
                grad = jax.lax.psum(g_local, "pool") / (pop * sigma)
            # Optimizer state is replicated like params; the update
            # math is the shared apply_es_update (one copy, also used
            # by AskTellES).
            with jax.named_scope("es.update"):
                new_params, m_new, v_new, t_new = apply_es_update(
                    params, grad, m, v, t, lr=lr, wd=wd, adam=adam,
                )
            stats = jnp.stack([
                flat_fit.mean(),
                flat_fit.max(),
                jax.lax.pmean(fitness.mean(), "pool"),
            ])
            return new_params, m_new, v_new, t_new, stats

        self._device_step_fn = device_step  # reused by the fused runner
        stepped = shard_map(
            device_step,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False,
        )
        return jax.jit(stepped)

    def run_fused(self, params, key, generations: int):
        """Run N generations in one XLA program. Returns
        (params, stats_history (generations, 3)); optimizer state
        advances exactly as with per-step run(). (Public signature
        takes bare params — the optimizer state is internal — so this
        wraps the shared mixin runner around the full state tuple.)"""
        m, v, t = self._ensure_opt_state(params)
        state, stats_seq = _FusedRunMixin.run_fused(
            self, (params, m, v, t), key, generations)
        params, m, v, t = state
        if self.optimizer == "adam":
            self._opt_state = (m, v, t)
        return params, stats_seq

    # ------------------------------------------------------------------
    def _ensure_opt_state(self, params):
        import jax.numpy as jnp

        if self.optimizer != "adam":
            # sgd carries no state: zero-size placeholders keep the step
            # signature uniform; cached so the hot loop allocates nothing.
            if self._opt_state is None:
                zero = jnp.zeros((0,), jnp.float32)
                self._opt_state = (zero, zero, jnp.asarray(0.0))
            return self._opt_state
        if params.shape != (self.dim,):
            # Validate before touching state: a bad call must not poison
            # the instance for subsequent correct calls.
            raise ValueError(
                f"params shape {params.shape} != ({self.dim},)"
            )
        if self._opt_state is None:
            zeros = jnp.zeros_like(params)
            self._opt_state = (zeros, zeros, jnp.asarray(0.0))
        elif self._opt_state[0].shape != params.shape:
            raise ValueError(
                "optimizer state shape "
                f"{self._opt_state[0].shape} does not match params "
                f"{params.shape}: one EvolutionStrategy instance tracks "
                "ONE population's Adam state — call reset_optimizer() "
                "when switching populations, or use separate instances"
            )
        return self._opt_state

    def reset_optimizer(self) -> None:
        self._opt_state = None

    def step(self, params, key):
        """One generation: returns (new_params, stats) where stats is
        [mean_fitness, max_fitness, mean_fitness_again]. Adam state lives
        on the mesh inside this object and is keyed to ONE population —
        don't interleave different parameter vectors through a shared
        adam-mode instance (POET shares an instance but uses sgd)."""
        m, v, t = self._ensure_opt_state(params)
        new_params, m, v, t, stats = self._step(params, m, v, t, key)
        if self.optimizer == "adam":
            self._opt_state = (m, v, t)
        from fiber_tpu.parallel.mesh import cpu_step_barrier

        cpu_step_barrier(self.mesh, (new_params, stats))
        return new_params, stats

    def run(self, params, key, generations: int,
            log_every: int = 0) -> Tuple[object, list]:
        """Run N generations on-device; parameters never leave the mesh."""
        import jax

        history = []
        for gen in range(generations):
            key, step_key = jax.random.split(key)
            params, stats = self.step(params, step_key)
            if log_every and (gen % log_every == 0 or gen == generations - 1):
                host = jax.device_get(stats)
                history.append((gen, float(host[0]), float(host[1])))
        return params, history


class AskTellES:
    """OpenAI-ES behind an ask/tell interface — for eval functions that
    are NOT jittable (external simulators, subprocess rollouts, gym
    envs). This is the reference's actual user workflow: its gecco-2020
    example samples perturbations centrally and farms evaluation
    through ``fiber.Pool(40).map`` of arbitrary Python
    (/root/reference/examples/gecco-2020/es.py); here the same loop is

        es = AskTellES(dim, pop_size)
        thetas = es.ask(key)                  # (pop, dim) numpy
        fits = pool.map(simulate, thetas)     # any Python you like
        es.tell(fits)                         # rank-shape + update

    Sampling and the update run as jitted device programs (antithetic
    gaussian pairs, centered-rank shaping, SGD or Adam — identical math
    to :class:`EvolutionStrategy`); only the candidate matrix crosses
    the host boundary, because the evaluator lives there by definition.
    For jittable eval_fns use :class:`EvolutionStrategy` — the whole
    generation stays on the mesh.
    """

    def __init__(
        self,
        dim: int,
        pop_size: int,
        sigma: float = 0.1,
        lr: float = 0.02,
        weight_decay: float = 0.0,
        optimizer: str = "sgd",
        params0=None,
    ) -> None:
        import jax
        import jax.numpy as jnp

        if optimizer not in ("sgd", "adam"):
            raise ValueError(f"unknown optimizer {optimizer!r}")
        if pop_size < 2:
            raise ValueError("pop_size must be >= 2")
        self.dim = int(dim)
        self.pairs = max(1, pop_size // 2)
        self.pop_size = 2 * self.pairs
        self.sigma = float(sigma)
        self.lr = float(lr)
        self.weight_decay = float(weight_decay)
        self.optimizer = optimizer
        self.params = (jnp.zeros((dim,), jnp.float32) if params0 is None
                       else jnp.asarray(params0, jnp.float32))
        if self.params.shape != (self.dim,):
            raise ValueError(
                f"params0 shape {self.params.shape} != ({dim},)")
        # Same convention as EvolutionStrategy: sgd carries zero-size
        # moment placeholders so no dead (dim,) state rides the update.
        zeros = (jnp.zeros_like(self.params) if optimizer == "adam"
                 else jnp.zeros((0,), jnp.float32))
        self._m, self._v, self._t = zeros, zeros, jnp.asarray(0.0)
        self._eps = None  # set by ask(), consumed by tell()

        sigma_c, lr_c, wd = self.sigma, self.lr, self.weight_decay
        pairs, pop = self.pairs, self.pop_size
        adam = optimizer == "adam"

        @jax.jit
        def sample(params, key):
            eps = jax.random.normal(key, (pairs, dim))
            thetas = jnp.concatenate(
                [params + sigma_c * eps, params - sigma_c * eps], axis=0
            )
            return thetas, eps

        @jax.jit
        def update(params, eps, fitness, m, v, t):
            ranks = centered_rank(fitness)
            w = ranks[:pairs] - ranks[pairs:]
            grad = (w @ eps) / (pop * sigma_c)
            return apply_es_update(
                params, grad, m, v, t, lr=lr_c, wd=wd, adam=adam,
            )

        self._sample = sample
        self._update = update

    def ask(self, key):
        """Draw the next antithetic population: (pop_size, dim) numpy
        array, rows [plus-half; minus-half]."""
        import jax
        import numpy as np

        if self._eps is not None:
            raise RuntimeError("ask() called twice without tell()")
        thetas, eps = self._sample(self.params, key)
        self._eps = eps
        return np.asarray(jax.device_get(thetas))

    def tell(self, fitnesses) -> dict:
        """Report fitnesses (len pop_size, ask()'s row order; higher is
        better) and apply the update. Returns summary stats."""
        import jax.numpy as jnp

        if self._eps is None:
            raise RuntimeError("tell() called before ask()")
        fits = jnp.asarray(fitnesses, jnp.float32).reshape(-1)
        if fits.shape[0] != self.pop_size:
            raise ValueError(
                f"need {self.pop_size} fitnesses, got {fits.shape[0]}")
        self.params, self._m, self._v, self._t = self._update(
            self.params, self._eps, fits, self._m, self._v, self._t)
        self._eps = None
        return {
            "mean_fitness": float(fits.mean()),
            "max_fitness": float(fits.max()),
        }
