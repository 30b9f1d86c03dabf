"""Plain reference of one OpenAI-ES generation on the planar biped walker.

Written from the published algorithm (Salimans et al. 2017: antithetic
pairs, centered ranks, Adam on the estimated gradient) and from the
walker's equations of motion. It imports nothing of ``fiber_tpu`` and
takes nothing the program has made: the noise, the perturbed policies,
the rollouts, the ranks, the gradient and the Adam update are all worked
out here from the generation key.

Everything is float32 with exact float32 products (the policy layers are
multiply-and-sum, not a matmul whose precision the backend may lower).
``policy_dtype="bfloat16"`` computes the policy the way a lower-precision
path would (operands, bias, tanh and logits in bfloat16): that is the
control of the comparison, never the reference.

The noise stream is part of the task's definition, so that the same key
gives the same population:

    gen_key -> (_, sub) = split(gen_key)          one generation of a fused call
    dev_key = fold_in(sub, 0)                     chip 0 of one
    eps_key, eval_key = split(dev_key)
    eps = normal(eps_key, (pop/2, dim))           antithetic pairs
    rollout keys = split(eval_key, pop)           rows: [+eps ; -eps]
    start jitter = 0.02 * normal(rollout key, (2,))
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Walker constants (SI units), as the environment's description gives them.
DT = 0.025
GRAVITY = 9.8
MASS = 1.0
INERTIA = 0.5
HIP_RATE = 3.0
LEN_RATE = 1.5
THETA_LIM = 0.9
LEN_LOW, LEN_HIGH = 0.5, 1.2
K_CONTACT = 120.0
D_CONTACT = 6.0
K_FRICTION = 4.0
OMEGA_DAMP = 1.0
TERRAIN_FREQS = (0.4, 0.8, 1.5, 2.7)
OBS_DIM = 14
ACT_DIM = 16


def layer_sizes(hidden):
    return (OBS_DIM, *hidden, ACT_DIM)


def policy_dim(hidden) -> int:
    sizes = layer_sizes(hidden)
    return sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))


def init_policy(key, hidden):
    """Flat tanh-MLP parameters: weights normal / sqrt(fan_in), biases 0,
    layer by layer as [W.ravel(), b]."""
    sizes = layer_sizes(hidden)
    parts = []
    for n_in, n_out in zip(sizes[:-1], sizes[1:]):
        key, wk = jax.random.split(key)
        parts.append((jax.random.normal(wk, (n_in, n_out))
                      / jnp.sqrt(n_in)).ravel())
        parts.append(jnp.zeros((n_out,)))
    return jnp.concatenate(parts)


def _policy_logits(thetas, obs, hidden, policy_dtype):
    """thetas (B, dim), obs (B, obs) -> logits (B, act) float32."""
    sizes = layer_sizes(hidden)
    x = obs
    if policy_dtype is not None:
        x = x.astype(policy_dtype)
        thetas = thetas.astype(policy_dtype)
    offset = 0
    last = len(sizes) - 2
    for i, (n_in, n_out) in enumerate(zip(sizes[:-1], sizes[1:])):
        w = thetas[:, offset:offset + n_in * n_out].reshape(-1, n_in, n_out)
        offset += n_in * n_out
        b = thetas[:, offset:offset + n_out]
        offset += n_out
        if policy_dtype is None:
            x = jnp.sum(x[:, :, None] * w, axis=1) + b
        else:
            x = jnp.einsum("bi,bio->bo", x, w).astype(policy_dtype) + b
        if i < last:
            x = jnp.tanh(x)
    return x.astype(jnp.float32)


def terrain_height(course, x):
    """Roughness + periodic stumps - periodic gaps; all zeros is flat."""
    freqs = jnp.asarray(TERRAIN_FREQS)
    rough = jnp.sum(course[:4] * jnp.sin(freqs * x[..., None]), axis=-1)
    stump = course[4] * jnp.exp(-jnp.sin(0.5 * (x - 3.0)) ** 2 / 0.01)
    gap = course[5] * jnp.exp(-jnp.sin(0.35 * (x - 5.0)) ** 2 / 0.02)
    return rough + stump - gap


def _terrain_slope(course, x):
    return (terrain_height(course, x + 0.1)
            - terrain_height(course, x - 0.1)) / 0.2


def _leg(course, x, y, vx, vy, th, L, dth, dL):
    foot_x = x + L * jnp.sin(th)
    foot_y = y - L * jnp.cos(th)
    foot_vx = vx + dL * jnp.sin(th) + L * jnp.cos(th) * dth
    foot_vy = vy - dL * jnp.cos(th) + L * jnp.sin(th) * dth
    pen = terrain_height(course, foot_x) - foot_y
    contact = pen > 0.0
    normal = jnp.where(
        contact, jnp.maximum(K_CONTACT * pen - D_CONTACT * foot_vy, 0.0), 0.0)
    friction = jnp.where(
        contact,
        jnp.clip(-K_FRICTION * foot_vx, -0.8 * normal, 0.8 * normal), 0.0)
    torque = (foot_x - x) * normal - (foot_y - y) * friction
    return friction, normal, torque


@functools.partial(jax.jit, static_argnames=("hidden", "steps", "policy_dtype"))
def rollout_block(thetas, keys, course, *, hidden, steps, policy_dtype=None):
    """Forward distance of B policies, B walkers in lockstep.
    thetas (B, dim), keys (B,) rollout keys -> (B,) best x reached."""
    n = thetas.shape[0]
    jitter = 0.02 * jax.vmap(lambda k: jax.random.normal(k, (2,)))(keys)
    zeros = jnp.zeros((n,))
    y0 = terrain_height(course, zeros) + 1.0
    state0 = (zeros, y0, zeros, zeros, jitter[:, 0], zeros,
              0.15 + jitter[:, 1], zeros - 0.15, zeros + 1.0, zeros + 1.0)

    def step(carry, _):
        state, done, best_x = carry
        x, y, vx, vy, phi, om, th1, th2, L1, L2 = state
        touch1 = (terrain_height(course, x + L1 * jnp.sin(th1))
                  >= y - L1 * jnp.cos(th1)).astype(jnp.float32)
        touch2 = (terrain_height(course, x + L2 * jnp.sin(th2))
                  >= y - L2 * jnp.cos(th2)).astype(jnp.float32)
        obs = jnp.stack([
            vx / 3.0, vy / 3.0, om, jnp.sin(phi), jnp.cos(phi),
            th1, th2, L1, L2, touch1, touch2,
            _terrain_slope(course, x + 0.3), _terrain_slope(course, x + 0.8),
            y - terrain_height(course, x),
        ], axis=1)
        action = jnp.argmax(
            _policy_logits(thetas, obs, hidden, policy_dtype), axis=1)

        def sign(bit):
            return 2.0 * ((action >> bit) & 1).astype(jnp.float32) - 1.0

        dth1, dth2 = sign(3) * HIP_RATE, sign(2) * HIP_RATE
        dL1, dL2 = sign(1) * LEN_RATE, sign(0) * LEN_RATE
        f1x, f1y, t1 = _leg(course, x, y, vx, vy, th1, L1, dth1, dL1)
        f2x, f2y, t2 = _leg(course, x, y, vx, vy, th2, L2, dth2, dL2)
        ax = (f1x + f2x) / MASS
        ay = (f1y + f2y) / MASS - GRAVITY
        alpha = (t1 + t2) / INERTIA - OMEGA_DAMP * om
        nvx = vx + DT * ax
        nvy = vy + DT * ay
        nom = om + DT * alpha
        nx = x + DT * nvx
        ny = y + DT * nvy
        nphi = phi + DT * nom
        new = (nx, ny, nvx, nvy, nphi, nom,
               jnp.clip(th1 + DT * dth1, -THETA_LIM, THETA_LIM),
               jnp.clip(th2 + DT * dth2, -THETA_LIM, THETA_LIM),
               jnp.clip(L1 + DT * dL1, LEN_LOW, LEN_HIGH),
               jnp.clip(L2 + DT * dL2, LEN_LOW, LEN_HIGH))
        fell = ((ny - terrain_height(course, nx) < 0.3)
                | (jnp.abs(nphi) > 1.2))
        kept = tuple(jnp.where(done, old, cur) for old, cur in zip(state, new))
        best = jnp.where(done, best_x, jnp.maximum(best_x, nx))
        return (kept, done | fell, best), None

    (_, _, best_x), _ = jax.lax.scan(
        step, (state0, jnp.zeros((n,), bool), zeros), None, length=steps)
    return best_x


def centered_ranks(fitness):
    """Rank 0 for the worst, n-1 for the best (ties: lower index first),
    scaled to [-0.5, 0.5]."""
    n = fitness.shape[0]
    ranks = jnp.argsort(jnp.argsort(fitness))
    return ranks.astype(jnp.float32) / (n - 1) - 0.5


@functools.partial(jax.jit, static_argnames=("pairs", "dim"))
def _draw(gen_key, *, pairs, dim):
    _, sub = jax.random.split(gen_key)
    eps_key, eval_key = jax.random.split(jax.random.fold_in(sub, 0))
    eps = jax.random.normal(eps_key, (pairs, dim))
    return eps, jax.random.split(eval_key, 2 * pairs)


@functools.partial(jax.jit, static_argnames=("lr", "sigma"))
def _update(params, m, v, t, eps, fitness, *, lr, sigma,
            b1=0.9, b2=0.999, adam_eps=1e-8):
    pairs = eps.shape[0]
    ranks = centered_ranks(fitness)
    w = ranks[:pairs] - ranks[pairs:]
    grad = jnp.einsum("p,pd->d", w, eps,
                      precision=jax.lax.Precision.HIGHEST) / (2 * pairs * sigma)
    t = t + 1.0
    m = b1 * m + (1 - b1) * grad
    v = b2 * v + (1 - b2) * grad * grad
    step = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + adam_eps)
    return params + step, m, v, t, grad


def generation(params, m, v, t, gen_key, *, pop, sigma, lr, hidden, steps,
               course, block=1000, policy_dtype=None, members=None):
    """One generation from ``gen_key``. Returns (params, m, v, t, grad,
    fitness). ``members`` (a fault for the tests, never the reference)
    keeps only the first ``members`` of each antithetic half."""
    pairs = pop // 2
    eps, keys = _draw(gen_key, pairs=pairs, dim=params.shape[0])
    if members is not None:
        eps = eps[:members]
        keys = jnp.concatenate([keys[:members], keys[pairs:pairs + members]])
        pairs = members
    course = jnp.asarray(course, jnp.float32)
    fits = []
    for sign, key_rows in ((1.0, keys[:pairs]), (-1.0, keys[pairs:])):
        for lo in range(0, pairs, block):
            thetas = params + sign * sigma * eps[lo:lo + block]
            fits.append(rollout_block(
                thetas, key_rows[lo:lo + block], course, hidden=hidden,
                steps=steps, policy_dtype=policy_dtype))
    fitness = jnp.concatenate(fits)
    params, m, v, t, grad = _update(params, m, v, t, eps, fitness,
                                    lr=lr, sigma=sigma)
    return params, m, v, t, grad, fitness
