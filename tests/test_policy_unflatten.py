"""The ``unflatten`` contract of a policy (docs/api.md "Policies"): its
methods take the flat vector or its layers and compute the same thing bit
for bit; every rollout of ``models/envs.py`` cuts the layers once, before
its step scan, when handed a bound method of such a policy, and a plain
function keeps the flat vector. CPU."""

import types

import numpy as np
import pytest

from fiber_tpu import telemetry
from fiber_tpu.models import (
    CartPole, ConvPolicy, DeceptiveMaze, GRUPolicy, MLPPolicy,
    ParamBipedWalker, ParamCartPole, ParamHillWalker, Pendulum, PixelChase,
    rollout_recurrent,
)

MEMBERS = 8


def _mlp(**kw):
    policy = MLPPolicy(6, 5, hidden=(16, 16), **kw)
    return policy, policy.apply, lambda key: _normal(key, (6,))


def _conv(**kw):
    policy = ConvPolicy((12, 12, 1), 5, channels=(4, 8), hidden=16, **kw)
    return policy, policy.apply, lambda key: _normal(key, (12, 12, 1))


def _gru():
    policy = GRUPolicy(6, 5, hidden=16)

    def step(params, obs):
        return policy.step(params, policy.init_carry() + 0.1, obs)
    return policy, step, lambda key: _normal(key, (6,))


def _normal(key, shape):
    import jax

    return jax.random.normal(key, shape)


def _members(policy, n=MEMBERS):
    import jax

    return jax.vmap(policy.init)(jax.random.split(jax.random.PRNGKey(3), n))


def _same(a, b):
    import jax

    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(np.asarray(x), np.asarray(y)), (x, y)


@pytest.mark.parametrize("batched", [False, True], ids=["alone", "vmap8"])
@pytest.mark.parametrize("make", [
    _mlp, _conv, _gru,
    lambda: _mlp(compute_dtype="bfloat16"),
    lambda: _conv(compute_dtype="bfloat16"),
], ids=["mlp", "conv", "gru", "mlp_bf16", "conv_bf16"])
def test_layers_compute_what_the_flat_vector_computes(make, batched):
    """``apply`` / ``step`` of ``unflatten(p)`` equals that of ``p`` bit
    for bit, for one member and under ``vmap`` over 8."""
    import jax

    policy, fn, draw_obs = make()
    params = _members(policy)
    obs = jax.vmap(draw_obs)(jax.random.split(jax.random.PRNGKey(4),
                                              MEMBERS))
    flat = jax.jit(jax.vmap(fn) if batched else fn)
    cut = jax.jit(jax.vmap(lambda p, o: fn(policy.unflatten(p), o))
                  if batched else lambda p, o: fn(policy.unflatten(p), o))
    if not batched:
        params, obs = params[0], obs[0]
    assert isinstance(policy.unflatten(params[0] if batched else params),
                      tuple)
    _same(flat(params, obs), cut(params, obs))


def _without_unflatten(policy):
    """``policy`` as a custom recurrent policy that offers no
    ``unflatten``: the same arithmetic from the flat vector."""
    return types.SimpleNamespace(
        init_carry=policy.init_carry,
        act_step=lambda p, h, o: policy.act_step(p, h, o))


def _rollouts():
    """name -> (policy, hoisted rollout, flat rollout), each rollout a
    function of (flat_params, key)."""
    import jax.numpy as jnp

    def pair(policy, method, rollout):
        return (policy,
                lambda p, k: rollout(method, p, k),
                lambda p, k: rollout(lambda q, o: method(q, o), p, k))

    def mlp(env, act_dim=None, hidden=(16, 16)):
        return MLPPolicy(env.obs_dim, act_dim or env.act_dim, hidden=hidden)

    def mid(env):
        return (jnp.asarray(env.PARAM_LOW) + jnp.asarray(env.PARAM_HIGH)) / 2

    out = {}
    p = mlp(CartPole)
    out["cartpole"] = pair(
        p, p.act, lambda f, q, k: CartPole.rollout(f, q, k, max_steps=40))
    p = mlp(ParamCartPole)
    out["param_cartpole"] = pair(
        p, p.act, lambda f, q, k: ParamCartPole.rollout_p(
            f, mid(ParamCartPole), q, k, max_steps=40))
    p = mlp(Pendulum, act_dim=1)
    out["pendulum"] = pair(
        p, p.apply, lambda f, q, k: Pendulum.rollout(f, q, k, max_steps=40))
    p = ConvPolicy(PixelChase.obs_shape, PixelChase.act_dim,
                   channels=(4, 8), hidden=16)
    out["pixel_chase"] = pair(
        p, p.act, lambda f, q, k: PixelChase.rollout(f, q, k, max_steps=10))
    p = mlp(DeceptiveMaze)
    out["maze_xy"] = pair(
        p, p.apply,
        lambda f, q, k: DeceptiveMaze.rollout_xy(f, q, k, max_steps=40))
    p = mlp(ParamHillWalker)
    out["hill_walker"] = pair(
        p, p.act, lambda f, q, k: ParamHillWalker.rollout_p(
            f, mid(ParamHillWalker), q, k, max_steps=40))
    p = mlp(ParamBipedWalker)
    out["biped_walker"] = pair(
        p, p.act, lambda f, q, k: ParamBipedWalker.rollout_p(
            f, mid(ParamBipedWalker), q, k, max_steps=60))
    p = GRUPolicy(CartPole.obs_dim, CartPole.act_dim, hidden=16)
    out["recurrent"] = (
        p,
        lambda q, k: rollout_recurrent(CartPole, p, q, k, max_steps=40),
        lambda q, k: rollout_recurrent(CartPole, _without_unflatten(p), q, k,
                                       max_steps=40))
    return out


ROLLOUTS = ("cartpole", "param_cartpole", "pendulum", "pixel_chase",
            "maze_xy", "hill_walker", "biped_walker", "recurrent")


def _traces(policy_class):
    counter = telemetry.counter("policy_rollout_traces")
    return tuple(counter.value(policy=policy_class, params=form)
                 for form in ("prepared", "flat"))


@pytest.mark.parametrize("name", ROLLOUTS)
def test_hoisted_rollout_equals_flat_rollout(name):
    """Fitness of 8 members through a bound method of the policy
    (layers cut once, before the scan) equals fitness through a plain
    function around it (flat vector cut on every step) bit for bit, and
    the engage counter says which trace was which."""
    import jax

    policy, hoisted, flat = _rollouts()[name]
    params = _members(policy)
    keys = jax.random.split(jax.random.PRNGKey(5), MEMBERS)
    plain = "SimpleNamespace" if name == "recurrent" else "function"
    before = _traces(type(policy).__name__), _traces(plain)
    a = jax.jit(jax.vmap(hoisted))(params, keys)
    b = jax.jit(jax.vmap(flat))(params, keys)
    _same(a, b)
    assert np.all(np.isfinite(np.asarray(a)))
    after = _traces(type(policy).__name__), _traces(plain)
    assert after[0] == (before[0][0] + 1, before[0][1])
    assert after[1] == (before[1][0], before[1][1] + 1)
