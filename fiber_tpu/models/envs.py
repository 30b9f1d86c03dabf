"""Pure-JAX environments: physics as jittable step functions, rollouts as
``lax.scan`` — the whole episode compiles into one XLA program with static
shapes (no Python in the loop), which is what lets a TPU evaluate whole
populations of policies in data-parallel lockstep.

CartPole matches the classic Gym CartPole-v1 dynamics (the north-star
OpenAI-ES workload, BASELINE.json configs); Pendulum is the continuous
control smoke env.
"""

from __future__ import annotations

from typing import Callable


def _prepared(act, flat_params):
    """The parameters a rollout's step scan closes over. ``act`` is
    what the rollout will call on every step with them: where its owner
    (a bound method's policy, or the policy itself) offers
    ``unflatten``, the flat vector is cut into its layers here, once,
    and the scan body reads the layers in place; a plain function keeps
    the flat vector and cuts it on every step. A trace-time counter
    says which of the two a rollout got (docs/observability.md).

    ``flat_params`` is the member's flat ``(dim,)`` vector or, from
    ``ops/es.py``, the member as a ``PairParams`` (shared base, the
    antithetic pair's noise, the signed sigma), which only an
    ``unflatten`` can take apart: an owner whose ``unflatten`` refuses
    it, and a plain function, raise ``TypeError`` here, before anything
    is counted, and the engine hands the rollout dense vectors instead.
    The counter's third value, ``pair``, says the pair went through."""
    from fiber_tpu.models.policies import PairParams
    from fiber_tpu.telemetry import device

    owner = getattr(act, "__self__", act)
    unflatten = getattr(owner, "unflatten", None)
    pair = isinstance(flat_params, PairParams)
    if unflatten is not None:
        prepared, form = unflatten(flat_params), "pair" if pair else "prepared"
    elif pair:
        raise TypeError(f"{type(owner).__name__} offers no unflatten to "
                        "take a PairParams apart")
    else:
        prepared, form = flat_params, "flat"
    device.rollout_traced(type(owner).__name__, form)
    return prepared


def _mutate_bounded(env_params, key, low, high, scale):
    """Shared POET env mutation: clip-bounded gaussian perturbation of
    the parameter vector (one implementation for every Param* env)."""
    import jax
    import jax.numpy as jnp

    low = jnp.asarray(low)
    high = jnp.asarray(high)
    noise = jax.random.normal(key, low.shape) * scale * (high - low)
    return jnp.clip(jnp.asarray(env_params) + noise, low, high)


def _survival_scan(step_fn, act_step_fn, state0, carry0, steps):
    """THE masked episode loop for survival-reward envs: +1 per step
    until termination, with static shapes (no early exit — finished
    episodes freeze their state and stop scoring). One implementation
    shared by every rollout variant so the masking/termination
    convention can't drift between them.

    ``act_step_fn(policy_carry, state) -> (policy_carry', action)``
    (stateless policies pass ``carry0=()``);
    ``step_fn(state, action) -> (state', terminated: bool)``.
    """
    import jax
    import jax.numpy as jnp

    def scan_step(carry, _):
        state, pc, done, total = carry
        new_pc, action = act_step_fn(pc, state)
        with jax.named_scope("env.step"):
            next_state, terminated = step_fn(state, action)
        reward = jnp.where(done, 0.0, 1.0)
        new_done = done | terminated
        # tree.map on BOTH freezes so pytree env states work the same
        # as pytree policy carries.
        keep_state = jax.tree.map(
            lambda old, new: jnp.where(done, old, new), state, next_state
        )
        keep_pc = jax.tree.map(
            lambda old, new: jnp.where(done, old, new), pc, new_pc
        )
        return (keep_state, keep_pc, new_done, total + reward), None

    (_, _, _, total), _ = jax.lax.scan(
        scan_step,
        (state0, carry0, jnp.asarray(False), jnp.asarray(0.0)),
        None, length=steps,
    )
    return total


class CartPole:
    obs_dim = 4
    act_dim = 2
    max_steps = 500

    # physics constants (Gym CartPole-v1)
    gravity = 9.8
    masscart = 1.0
    masspole = 0.1
    length = 0.5          # half pole length
    force_mag = 10.0
    tau = 0.02
    theta_threshold = 12 * 3.141592653589793 / 180.0
    x_threshold = 2.4

    @classmethod
    def reset(cls, key):
        import jax

        return jax.random.uniform(key, (4,), minval=-0.05, maxval=0.05)

    @classmethod
    def step(cls, state, action):
        """One physics step. action in {0, 1}. Returns (state, terminated)."""
        import jax.numpy as jnp

        x, x_dot, theta, theta_dot = state
        force = jnp.where(action == 1, cls.force_mag, -cls.force_mag)
        costheta = jnp.cos(theta)
        sintheta = jnp.sin(theta)
        total_mass = cls.masscart + cls.masspole
        polemass_length = cls.masspole * cls.length

        temp = (force + polemass_length * theta_dot**2 * sintheta) / total_mass
        thetaacc = (cls.gravity * sintheta - costheta * temp) / (
            cls.length * (4.0 / 3.0 - cls.masspole * costheta**2 / total_mass)
        )
        xacc = temp - polemass_length * thetaacc * costheta / total_mass

        x = x + cls.tau * x_dot
        x_dot = x_dot + cls.tau * xacc
        theta = theta + cls.tau * theta_dot
        theta_dot = theta_dot + cls.tau * thetaacc
        new_state = jnp.stack([x, x_dot, theta, theta_dot])
        terminated = (
            (jnp.abs(x) > cls.x_threshold)
            | (jnp.abs(theta) > cls.theta_threshold)
        )
        return new_state, terminated

    @classmethod
    def rollout(cls, act_fn: Callable, flat_params, key,
                max_steps: int | None = None):
        """Total episode reward for a deterministic policy; fully jittable.

        ``act_fn(flat_params, obs) -> action``. Termination is handled by
        masking inside the scan (static shapes, no early exit).
        """
        steps = max_steps or cls.max_steps
        params = _prepared(act_fn, flat_params)
        return _survival_scan(
            cls.step,
            lambda carry, state: (carry, act_fn(params, state)),
            cls.reset(key), (), steps,
        )


class ParamCartPole(CartPole):
    """CartPole with mutable physics — the substrate for POET-style
    env/agent co-evolution (the reference's POET example evolves
    BipedalWalker terrains; here the evolvable environment parameters are
    the physics vector [gravity, pole_half_length, force_mag, masspole],
    harder configs = heavier/longer pole, weaker cart).

    ``env_params`` rides through rollouts as a jax array so a whole
    population of (env, agent) pairs can evaluate in one SPMD program.
    """

    #: default physics vector (matches CartPole-v1)
    DEFAULT = (9.8, 0.5, 10.0, 0.1)
    PARAM_LOW = (4.0, 0.25, 4.0, 0.05)
    PARAM_HIGH = (19.0, 1.5, 14.0, 0.6)

    @classmethod
    def step_p(cls, env_params, state, action):
        import jax.numpy as jnp

        gravity, length, force_mag, masspole = (
            env_params[0], env_params[1], env_params[2], env_params[3]
        )
        x, x_dot, theta, theta_dot = state
        force = jnp.where(action == 1, force_mag, -force_mag)
        costheta = jnp.cos(theta)
        sintheta = jnp.sin(theta)
        total_mass = cls.masscart + masspole
        polemass_length = masspole * length

        temp = (force + polemass_length * theta_dot**2 * sintheta) / total_mass
        thetaacc = (gravity * sintheta - costheta * temp) / (
            length * (4.0 / 3.0 - masspole * costheta**2 / total_mass)
        )
        xacc = temp - polemass_length * thetaacc * costheta / total_mass

        x = x + cls.tau * x_dot
        x_dot = x_dot + cls.tau * xacc
        theta = theta + cls.tau * theta_dot
        theta_dot = theta_dot + cls.tau * thetaacc
        new_state = jnp.stack([x, x_dot, theta, theta_dot])
        terminated = (
            (jnp.abs(x) > cls.x_threshold)
            | (jnp.abs(theta) > cls.theta_threshold)
        )
        return new_state, terminated

    @classmethod
    def rollout_p(cls, act_fn, env_params, flat_params, key,
                  max_steps: int | None = None):
        """Episode reward under a specific physics vector; jittable and
        vmappable over (env_params, flat_params) pairs."""
        steps = max_steps or cls.max_steps
        params = _prepared(act_fn, flat_params)
        return _survival_scan(
            lambda state, action: cls.step_p(env_params, state, action),
            lambda carry, state: (carry, act_fn(params, state)),
            cls.reset(key), (), steps,
        )

    @classmethod
    def mutate(cls, env_params, key, scale: float = 0.15):
        """Perturb the physics vector within bounds (POET env mutation)."""
        return _mutate_bounded(env_params, key, cls.PARAM_LOW,
                               cls.PARAM_HIGH, scale)


class Pendulum:
    obs_dim = 3
    act_dim = 1
    max_steps = 200

    max_speed = 8.0
    max_torque = 2.0
    dt = 0.05
    g = 10.0
    m = 1.0
    length = 1.0

    @classmethod
    def reset(cls, key):
        import jax
        import jax.numpy as jnp

        hi = jnp.asarray([3.141592653589793, 1.0])
        thetadot = jax.random.uniform(key, (2,), minval=-hi, maxval=hi)
        return thetadot  # (theta, theta_dot)

    @classmethod
    def obs(cls, state):
        import jax.numpy as jnp

        theta, theta_dot = state
        return jnp.stack([jnp.cos(theta), jnp.sin(theta), theta_dot])

    @classmethod
    def step(cls, state, torque):
        import jax.numpy as jnp

        theta, theta_dot = state
        u = jnp.clip(torque, -cls.max_torque, cls.max_torque)
        cost = (
            _angle_normalize(theta) ** 2
            + 0.1 * theta_dot**2
            + 0.001 * u**2
        )
        new_theta_dot = theta_dot + (
            3 * cls.g / (2 * cls.length) * jnp.sin(theta)
            + 3.0 / (cls.m * cls.length**2) * u
        ) * cls.dt
        new_theta_dot = jnp.clip(new_theta_dot, -cls.max_speed, cls.max_speed)
        new_theta = theta + new_theta_dot * cls.dt
        return jnp.stack([new_theta, new_theta_dot]), -cost

    @classmethod
    def rollout(cls, act_fn: Callable, flat_params, key,
                max_steps: int | None = None):
        import jax
        import jax.numpy as jnp

        steps = max_steps or cls.max_steps
        state0 = cls.reset(key)
        params = _prepared(act_fn, flat_params)

        def scan_step(carry, _):
            state, total = carry
            torque = act_fn(params, cls.obs(state))
            torque = jnp.reshape(torque, ())
            with jax.named_scope("env.step"):
                new_state, reward = cls.step(state, torque)
            return (new_state, total + reward), None

        (_, total), _ = jax.lax.scan(
            scan_step, (state0, jnp.asarray(0.0)), None, length=steps,
        )
        return total


class PixelChase:
    """Procedural pixel-observation env for ConvNet-policy ES (stands in
    for the reference's Atari large-batch ES config — no ROMs needed, and
    the whole env renders/steps inside XLA).

    The agent (one blob) chases a target (another blob) on an H×W grid;
    observations are rendered single-channel images; actions are the four
    moves + stay; reward is negative distance (closing in scores higher).
    """

    H = 24
    W = 24
    obs_shape = (24, 24, 1)
    act_dim = 5
    max_steps = 60

    _MOVES = ((0, 0), (0, 1), (0, -1), (1, 0), (-1, 0))

    @classmethod
    def _render(cls, agent_yx, target_yx):
        import jax.numpy as jnp

        ys = jnp.arange(cls.H)[:, None]
        xs = jnp.arange(cls.W)[None, :]
        agent_img = jnp.exp(
            -((ys - agent_yx[0]) ** 2 + (xs - agent_yx[1]) ** 2) / 4.0
        )
        target_img = -jnp.exp(
            -((ys - target_yx[0]) ** 2 + (xs - target_yx[1]) ** 2) / 4.0
        )
        return (agent_img + target_img)[..., None]

    @classmethod
    def rollout(cls, act_fn, flat_params, key,
                max_steps: int | None = None):
        import jax
        import jax.numpy as jnp

        steps = max_steps or cls.max_steps
        k1, k2 = jax.random.split(key)
        agent0 = jax.random.uniform(
            k1, (2,), minval=2.0, maxval=cls.H - 3.0
        )
        target = jax.random.uniform(
            k2, (2,), minval=2.0, maxval=cls.H - 3.0
        )
        moves = jnp.asarray(cls._MOVES, dtype=jnp.float32)
        params = _prepared(act_fn, flat_params)

        def scan_step(carry, _):
            agent, total = carry
            with jax.named_scope("env.step"):
                obs = cls._render(agent, target)
            action = act_fn(params, obs)
            with jax.named_scope("env.step"):
                agent = jnp.clip(
                    agent + moves[action], 0.0, float(cls.H - 1)
                )
                dist = jnp.sqrt(jnp.sum((agent - target) ** 2))
                reward = -dist / cls.H
            return (agent, total + reward), None

        (_, total), _ = jax.lax.scan(
            scan_step, (agent0, jnp.asarray(0.0)), None, length=steps,
        )
        return total


class DeceptiveMaze:
    """Deceptive point maze — the novelty-search lineage's canonical
    domain (NS-ES/NSR-ES were demonstrated on mazes where the fitness
    gradient points into a wall, so reaching the goal requires first
    moving AWAY from it).

    A point agent starts at the origin; the goal sits directly above,
    behind a wall spanning ``|x| <= WALL_HALF`` at ``y = WALL_Y``.
    Greedy distance-minimization presses into the middle of the wall;
    the only way through is around either end. Observations are the
    position and the goal offset; actions are a continuous velocity
    (``policy.apply`` output, tanh-squashed). ``rollout_xy`` returns
    the final position — callers derive fitness (negative goal
    distance) and the behavior characterization (the position itself,
    the paper's BC) from it.
    """

    obs_dim = 4
    act_dim = 2  # (vx, vy), tanh-squashed continuous
    max_steps = 64

    GOAL = (0.0, 2.0)
    SPEED = 0.15
    WALL_Y = 1.0
    WALL_HALF = 1.0

    @classmethod
    def rollout_xy(cls, apply_fn, flat_params, key,
                   max_steps: int | None = None):
        """Final (x, y) after ``max_steps`` of policy-driven motion;
        jittable and vmappable."""
        import jax
        import jax.numpy as jnp

        steps = max_steps or cls.max_steps
        pos0 = 0.05 * jax.random.normal(key, (2,))
        gx, gy = cls.GOAL
        params = _prepared(apply_fn, flat_params)

        def scan_step(pos, _):
            obs = jnp.stack([pos[0], pos[1], gx - pos[0], gy - pos[1]])
            v = jnp.tanh(apply_fn(params, obs)) * cls.SPEED
            with jax.named_scope("env.step"):
                new = pos + v
                # The wall blocks any step whose path crosses WALL_Y inside
                # |x| <= WALL_HALF. The test point is the x where the
                # segment intersects the wall plane (NOT the endpoint x —
                # that would let diagonal steps cut the corner by up to
                # SPEED). Park blocked steps just on the starting side.
                dy = new[1] - pos[1]
                t = jnp.where(jnp.abs(dy) > 1e-12,
                              (cls.WALL_Y - pos[1]) / jnp.where(
                                  jnp.abs(dy) > 1e-12, dy, 1.0),
                              2.0)  # parallel to wall: no crossing (t>1)
                x_cross = pos[0] + t * (new[0] - pos[0])
                crosses = (t >= 0.0) & (t <= 1.0) \
                    & (jnp.abs(x_cross) <= cls.WALL_HALF)
                stop_y = jnp.where(pos[1] < cls.WALL_Y,
                                   cls.WALL_Y - 1e-3, cls.WALL_Y + 1e-3)
                # Blocked steps park at the intersection point (x_cross,
                # stop_y), not (new_x, stop_y): keeping the full lateral
                # displacement would re-open the corner cut over two steps
                # (advisor, round 2) — strict wall physics is what makes the
                # maze deceptive for plain ES.
                new_x = jnp.where(crosses, x_cross, new[0])
                new_y = jnp.where(crosses, stop_y, new[1])
            return jnp.stack([new_x, new_y]), None

        pos, _ = jax.lax.scan(scan_step, pos0, None, length=steps)
        return pos

    @classmethod
    def rollout(cls, apply_fn, flat_params, key,
                max_steps: int | None = None):
        """Fitness-only rollout: negative final distance to the goal."""
        import jax.numpy as jnp

        pos = cls.rollout_xy(apply_fn, flat_params, key, max_steps)
        goal = jnp.asarray(cls.GOAL)
        return -jnp.sqrt(jnp.sum((pos - goal) ** 2))


def _angle_normalize(x):
    import jax.numpy as jnp

    return ((x + jnp.pi) % (2 * jnp.pi)) - jnp.pi


class ParamHillWalker:
    """Terrain-parameterized 1-D walker — the POET paper's co-evolution
    shape (the reference's gecco-2020 example evolves BipedalWalker
    terrains; this is that substrate as compiled XLA: the terrain IS the
    evolvable environment).

    A point mass drives along a height field
    ``h(x) = Σ aᵢ·sin(fᵢ·x)`` whose amplitude vector ``aᵢ`` is the
    environment's parameter vector. Observations are local terrain
    perception (velocity + slope at/ahead of the agent) — translation
    invariant, so agents generalize across terrains the way POET needs.
    Fitness is distance travelled; steeper evolved terrain = harder env.
    """

    obs_dim = 4
    act_dim = 3  # push back / coast / push forward
    max_steps = 200

    dt = 0.05
    friction = 0.5
    force_mag = 4.0
    gravity = 9.8

    #: fixed incommensurate bump frequencies; env params are amplitudes
    FREQS = (0.5, 0.9, 1.4, 2.1, 3.1, 4.3)
    DEFAULT = (0.0, 0.0, 0.0, 0.0, 0.0, 0.0)  # flat ground
    PARAM_LOW = (-1.2,) * 6
    PARAM_HIGH = (1.2,) * 6

    @classmethod
    def slope(cls, env_params, x):
        """dh/dx at position x (analytic — no finite differences)."""
        import jax.numpy as jnp

        freqs = jnp.asarray(cls.FREQS)
        amps = jnp.asarray(env_params)
        return jnp.sum(amps * freqs * jnp.cos(freqs * x))

    @classmethod
    def rollout_p(cls, act_fn, env_params, flat_params, key,
                  max_steps: int | None = None):
        """Distance travelled under a specific terrain; jittable and
        vmappable over (env_params, flat_params) pairs — same contract
        as ParamCartPole.rollout_p."""
        import jax
        import jax.numpy as jnp

        steps = max_steps or cls.max_steps
        x0 = 0.1 * jax.random.normal(key, ())
        v0 = jnp.asarray(0.0)
        params = _prepared(act_fn, flat_params)

        def scan_step(carry, _):
            x, v = carry
            obs = jnp.stack([
                v,
                cls.slope(env_params, x),
                cls.slope(env_params, x + 0.5),
                cls.slope(env_params, x + 1.0),
            ])
            action = act_fn(params, obs)
            with jax.named_scope("env.step"):
                force = (action.astype(jnp.float32) - 1.0) * cls.force_mag
                acc = force - cls.gravity * cls.slope(env_params, x) \
                    - cls.friction * v
                v = v + cls.dt * acc
                x = x + cls.dt * v
            return (x, v), None

        (x, _v), _ = jax.lax.scan(
            scan_step, (x0, v0), None, length=steps,
        )
        return x

    @classmethod
    def mutate(cls, env_params, key, scale: float = 0.15):
        """Perturb the terrain amplitudes within bounds (POET env
        mutation)."""
        return _mutate_bounded(env_params, key, cls.PARAM_LOW,
                               cls.PARAM_HIGH, scale)


class ParamBipedWalker:
    """Planar biped on a parameterized obstacle course — the published
    POET domain shape (modified BipedalWalker-Hardcore: the reference's
    gecco-2020 workload evolves terrain roughness / stump / gap
    parameters) rebuilt as compiled XLA.

    Simplified articulated model that keeps the domain's control
    problem: a hull (x, y, vx, vy, phi, omega) rides two massless
    telescoping legs (world-frame hip angles theta_i, lengths L_i) with
    spring-damper ground contact; contact forces torque the hull, so the
    agent must coordinate both legs to move forward without toppling.
    Actions are bang-bang: 16 discrete combos of (hip1, hip2, dL1, dL2)
    rate signs — argmax-policy compatible (same ``policy.act`` contract
    POET drives, fiber_tpu/ops/poet.py:78).

    Env params = (4 roughness amplitudes, stump height, gap depth): the
    POET paper's difficulty axes. All zeros = flat ground. Fitness is
    forward distance; episodes freeze on termination (static shapes).
    """

    obs_dim = 14
    act_dim = 16
    max_steps = 400

    dt = 0.025
    gravity = 9.8
    mass = 1.0
    inertia = 0.5
    hip_rate = 3.0       # rad/s
    len_rate = 1.5       # m/s
    theta_lim = 0.9
    len_low, len_high = 0.5, 1.2
    k_contact = 120.0
    d_contact = 6.0
    k_friction = 4.0
    omega_damp = 1.0

    FREQS = (0.4, 0.8, 1.5, 2.7)
    DEFAULT = (0.0,) * 6
    PARAM_LOW = (0.0,) * 6
    PARAM_HIGH = (0.4, 0.4, 0.3, 0.2, 0.5, 0.6)

    @classmethod
    def height(cls, env_params, x):
        """Terrain height: roughness + periodic stumps - periodic gaps.
        Analytic (jittable); obstacles start ~3m from spawn."""
        import jax.numpy as jnp

        p = jnp.asarray(env_params)
        freqs = jnp.asarray(cls.FREQS)
        rough = jnp.sum(p[:4] * jnp.sin(freqs * x))
        stump = p[4] * jnp.exp(-jnp.sin(0.5 * (x - 3.0)) ** 2 / 0.01)
        gap = p[5] * jnp.exp(-jnp.sin(0.35 * (x - 5.0)) ** 2 / 0.02)
        return rough + stump - gap

    @classmethod
    def _slope(cls, env_params, x):
        return (cls.height(env_params, x + 0.1)
                - cls.height(env_params, x - 0.1)) / 0.2

    @classmethod
    def rollout_p(cls, act_fn, env_params, flat_params, key,
                  max_steps: int | None = None):
        """Forward distance on a specific course; jittable/vmappable —
        same contract as ParamCartPole/ParamHillWalker.rollout_p."""
        import jax
        import jax.numpy as jnp

        steps = max_steps or cls.max_steps
        y0 = cls.height(env_params, 0.0) + 1.0
        jitter = 0.02 * jax.random.normal(key, (2,))

        # state: x, y, vx, vy, phi, omega, th1, th2, L1, L2
        state0 = jnp.asarray([
            0.0, y0, 0.0, 0.0, jitter[0], 0.0,
            0.15 + jitter[1], -0.15, 1.0, 1.0,
        ])
        params = _prepared(act_fn, flat_params)

        def leg_forces(x, y, vx, vy, th, L, dth, dL, env):
            fx_pos = x + L * jnp.sin(th)
            fy_pos = y - L * jnp.cos(th)
            vfx = vx + dL * jnp.sin(th) + L * jnp.cos(th) * dth
            vfy = vy - dL * jnp.cos(th) + L * jnp.sin(th) * dth
            pen = cls.height(env, fx_pos) - fy_pos
            contact = pen > 0.0
            normal = jnp.where(
                contact,
                jnp.maximum(cls.k_contact * pen - cls.d_contact * vfy,
                            0.0),
                0.0)
            friction = jnp.where(
                contact,
                jnp.clip(-cls.k_friction * vfx, -0.8 * normal,
                         0.8 * normal),
                0.0)
            rx, ry = fx_pos - x, fy_pos - y
            torque = rx * normal - ry * friction
            return friction, normal, torque, contact

        def scan_step(carry, _):
            state, done, best_x = carry
            x, y, vx, vy, phi, om, th1, th2, L1, L2 = state

            with jax.named_scope("env.step"):
                obs = jnp.stack([
                    vx / 3.0, vy / 3.0, om, jnp.sin(phi), jnp.cos(phi),
                    th1, th2, L1, L2,
                    # previous-step contact proxies: current penetration
                    jnp.asarray(
                        cls.height(env_params, x + L1 * jnp.sin(th1))
                        >= y - L1 * jnp.cos(th1), jnp.float32),
                    jnp.asarray(
                        cls.height(env_params, x + L2 * jnp.sin(th2))
                        >= y - L2 * jnp.cos(th2), jnp.float32),
                    cls._slope(env_params, x + 0.3),
                    cls._slope(env_params, x + 0.8),
                    y - cls.height(env_params, x),
                ])
            action = act_fn(params, obs)
            with jax.named_scope("env.step"):
                bit = lambda k: 2.0 * jnp.asarray(
                    (action >> k) & 1, jnp.float32) - 1.0
                dth1 = bit(3) * cls.hip_rate
                dth2 = bit(2) * cls.hip_rate
                dL1 = bit(1) * cls.len_rate
                dL2 = bit(0) * cls.len_rate

                f1x, f1y, t1, _c1 = leg_forces(x, y, vx, vy, th1, L1,
                                               dth1, dL1, env_params)
                f2x, f2y, t2, _c2 = leg_forces(x, y, vx, vy, th2, L2,
                                               dth2, dL2, env_params)

                ax = (f1x + f2x) / cls.mass
                ay = (f1y + f2y) / cls.mass - cls.gravity
                alpha = (t1 + t2) / cls.inertia - cls.omega_damp * om

                nvx = vx + cls.dt * ax
                nvy = vy + cls.dt * ay
                nom = om + cls.dt * alpha
                nx = x + cls.dt * nvx
                ny = y + cls.dt * nvy
                nphi = phi + cls.dt * nom
                nth1 = jnp.clip(th1 + cls.dt * dth1, -cls.theta_lim,
                                cls.theta_lim)
                nth2 = jnp.clip(th2 + cls.dt * dth2, -cls.theta_lim,
                                cls.theta_lim)
                nL1 = jnp.clip(L1 + cls.dt * dL1, cls.len_low, cls.len_high)
                nL2 = jnp.clip(L2 + cls.dt * dL2, cls.len_low, cls.len_high)

                new_state = jnp.stack([
                    nx, ny, nvx, nvy, nphi, nom, nth1, nth2, nL1, nL2,
                ])
                fell = ((ny - cls.height(env_params, nx) < 0.3)
                        | (jnp.abs(nphi) > 1.2))
                keep = jnp.where(done, state, new_state)
                new_best = jnp.where(done, best_x, jnp.maximum(best_x, nx))
            return (keep, done | fell, new_best), None

        # The loop starts from the walker's own state: not yet fallen
        # (|phi| is the jitter's 0.02 sigma) and its best x the x it
        # stands at (0). The values are the constants False and 0.0;
        # read off ``state0`` they are batched wherever the state is, so
        # a ``vmap`` batches the loop's body in one pass and not two
        # (its rule re-batches the body until the carry's batching
        # stops changing), per level of ``vmap``: the ES engine's pair
        # nests two, and set-up pays each pass in tracing time.
        (_, _, best_x), _ = jax.lax.scan(
            scan_step, (state0, jnp.abs(state0[4]) > 1.2, state0[0]),
            None, length=steps,
        )
        return best_x

    @classmethod
    def mutate(cls, env_params, key, scale: float = 0.15):
        """Perturb the course parameters within bounds (POET env
        mutation; difficulty grows from flat ground)."""
        return _mutate_bounded(env_params, key, cls.PARAM_LOW,
                               cls.PARAM_HIGH, scale)


def rollout_recurrent(env_cls, policy, flat_params, key,
                      max_steps: int | None = None):
    """Episode reward for a RECURRENT policy (``init_carry``/``act_step``
    interface, e.g. GRUPolicy) on a CARTPOLE-STYLE env: ``reset(key)``
    plus ``step(state, action) -> (state, terminated:bool)`` with
    survival (+1/step until termination) reward — CartPole and direct
    subclasses. Envs with shaped rewards (Pendulum) or parameterized
    steps (ParamCartPole.rollout_p) need their own recurrent variant.
    Same masked-scan loop as the stateless rollouts (shared
    ``_survival_scan``), with the policy's hidden state threaded through
    the carry — fully jittable and vmappable over (flat_params, key)."""
    steps = max_steps or env_cls.max_steps
    params = _prepared(policy, flat_params)
    return _survival_scan(
        env_cls.step,
        lambda h, state: policy.act_step(params, h, state),
        env_cls.reset(key), policy.init_carry(), steps,
    )
