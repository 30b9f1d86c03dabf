"""The ES engine's antithetic pair (``ops/es.py`` ``pair_fitness``,
``models/policies.py`` ``PairParams``): a rollout whose policy can take
the pair apart gets the member as (shared base, the pair's noise, the
signed sigma) and computes what the dense ``params +- sigma * eps`` row
computes, bit for bit (the sum is formed in the layer's product and
rounded as the row was); an ``eval_fn`` that computes on ``theta`` itself,
a plain ``act`` function and the other policy classes get the dense rows,
as before. CPU."""

import numpy as np
import pytest

from fiber_tpu import telemetry
from fiber_tpu.models import (
    CartPole, ConvPolicy, GRUPolicy, MLPPolicy, ParamBipedWalker, PixelChase,
    rollout_recurrent,
)
from fiber_tpu.models.policies import PairParams
from fiber_tpu.ops.es import EvolutionStrategy, centered_rank, pair_fitness

SIGMA = 0.1
PAIRS = 16


def _walker(steps=80, **kw):
    """(policy, rollout, a starting vector that walks): a flat course
    and a start on which the 32 members' distances all differ."""
    import jax
    import jax.numpy as jnp

    policy = MLPPolicy(ParamBipedWalker.obs_dim, ParamBipedWalker.act_dim,
                       hidden=(32, 32), **kw)
    course = jnp.zeros((len(ParamBipedWalker.PARAM_LOW),), jnp.float32)
    start = policy.init(jax.random.split(jax.random.PRNGKey(2), 16)[11])
    return policy, (lambda theta, key: ParamBipedWalker.rollout_p(
        policy.act, course, theta, key, steps)), start


def _cartpole(**kw):
    import jax

    policy = MLPPolicy(CartPole.obs_dim, CartPole.act_dim, hidden=(16, 16),
                       **kw)
    return policy, (lambda theta, key: CartPole.rollout(
        policy.act, theta, key, max_steps=100)), \
        policy.init(jax.random.PRNGKey(0))


CASES = {
    "walker": _walker,
    "cartpole": _cartpole,
    "cartpole_bf16": lambda: _cartpole(compute_dtype="bfloat16"),
}


def _dense(eval_fn):
    """``eval_fn`` as one that wants the vector itself: ``asarray`` of a
    ``PairParams`` is a ``TypeError``, of an array the array."""
    import jax.numpy as jnp

    return lambda theta, key: eval_fn(jnp.asarray(theta), key)


def _thetas(params, eps, sigma=SIGMA):
    import jax.numpy as jnp

    return jnp.concatenate([params + sigma * eps, params - sigma * eps])


def _draw(dim, pairs=PAIRS):
    import jax

    return (jax.random.normal(jax.random.PRNGKey(1), (pairs, dim)),
            jax.random.split(jax.random.PRNGKey(2), 2 * pairs))


def _mesh(n):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:n]), ("pool",))


def _traces(policy_class):
    counter = telemetry.counter("policy_rollout_traces")
    return tuple(counter.value(policy=policy_class, params=form)
                 for form in ("pair", "prepared", "flat"))


@pytest.mark.parametrize("case", sorted(CASES))
def test_pair_fitness_is_the_dense_rows_fitness_bit_for_bit(case):
    """The fitness vector of 16 pairs handed over as ``PairParams`` is
    that of the 32 dense rows, in the rows' order ``[plus; minus]`` and
    under the rows' keys, bit for bit: spelling (a) forms ``base + scale
    * noise`` in the step, which rounds as ``params +- sigma * eps``
    does, so no tolerance is needed."""
    import jax

    policy, eval_fn, params = CASES[case]()
    eps, keys = _draw(policy.dim)
    pair = jax.jit(
        lambda p, e, k: pair_fitness(eval_fn, p, e, SIGMA, k))(
            params, eps, keys)
    dense = jax.jit(jax.vmap(eval_fn))(_thetas(params, eps), keys)
    assert pair.shape == (2 * PAIRS,)
    assert np.array_equal(np.asarray(pair), np.asarray(dense))
    # the members differ, and the two halves are not one another's copy
    assert len(set(np.asarray(pair).tolist())) >= 4
    assert not np.array_equal(np.asarray(pair[:PAIRS]),
                              np.asarray(pair[PAIRS:]))


@pytest.mark.parametrize("devices", [1, 8], ids=["one_device", "mesh8"])
@pytest.mark.parametrize("case", ["walker", "cartpole"])
def test_pair_step_is_the_dense_step_bit_for_bit(case, devices):
    """Two Adam generations through the pair equal two through dense
    rows bit for bit in the parameters and in the first gradient as
    Adam kept it, on one device and with the pairs spread over the
    suite's 8 CPU devices; the statistics (means and best of the same
    fitnesses, summed by two programs in two orders) to rounding."""
    import jax

    policy, eval_fn, params = CASES[case]()
    out = []
    for fn, form in ((eval_fn, 0), (_dense(eval_fn), 1)):
        before = _traces("MLPPolicy")
        es = EvolutionStrategy(fn, policy.dim, 2 * PAIRS * devices // 2,
                               sigma=SIGMA, lr=0.05, optimizer="adam",
                               mesh=_mesh(devices))
        p1, stats1 = es.step(params, jax.random.PRNGKey(7))
        after = _traces("MLPPolicy")  # one trace so far: one count
        assert after[form] == before[form] + 1
        assert sum(after) == sum(before) + 1
        grad1 = np.asarray(es._opt_state[0])
        p2, stats2 = es.step(p1, jax.random.PRNGKey(8))
        out.append([np.asarray(x) for x in (p1, p2, grad1, stats1, stats2)])
    for a, b in list(zip(*out))[:3]:
        assert np.array_equal(a, b)
    for a, b in list(zip(*out))[3:]:
        np.testing.assert_allclose(a, b, rtol=1e-6)
    assert np.any(out[0][2] != 0.0) and np.any(out[0][1] != out[0][0])


def _generation(eval_fn, params, key, pairs, sigma, lr):
    """One sgd generation on one device as the engine has always
    computed it from dense rows: the parent's formula, written out."""
    import jax

    eps_key, eval_key = jax.random.split(jax.random.fold_in(key, 0))
    eps = jax.random.normal(eps_key, (pairs, params.shape[0]))
    fitness = jax.vmap(eval_fn)(_thetas(params, eps, sigma),
                                jax.random.split(eval_key, 2 * pairs))
    ranks = centered_rank(fitness)
    grad = ((ranks[:pairs] - ranks[pairs:]) @ eps) / (2 * pairs * sigma)
    return params + lr * grad, fitness


def _plain_act_rollout():
    import jax.numpy as jnp

    policy = MLPPolicy(CartPole.obs_dim, CartPole.act_dim, hidden=(16,))

    def act(theta, obs):  # a plain function: it cuts the vector itself
        return jnp.argmax(policy.apply(theta, obs))
    return policy.dim, lambda theta, key: CartPole.rollout(
        act, theta, key, max_steps=60)


def _conv_rollout():
    policy = ConvPolicy(PixelChase.obs_shape, PixelChase.act_dim,
                        channels=(4, 8), hidden=16)
    return policy.dim, lambda theta, key: PixelChase.rollout(
        policy.act, theta, key, max_steps=6)


def _gru_rollout():
    policy = GRUPolicy(CartPole.obs_dim, CartPole.act_dim, hidden=8)
    return policy.dim, lambda theta, key: rollout_recurrent(
        CartPole, policy, theta, key, max_steps=40)


def _arithmetic():
    import jax.numpy as jnp

    return 24, lambda theta, key: -jnp.sum((theta - 0.5) ** 2)


def _wrapped_walker():
    policy, eval_fn, _ = _walker(steps=30)
    return policy.dim, lambda theta, key: eval_fn(theta * 1.0, key)


# name -> (maker, (owner class, counter form) of its one traced rollout)
DENSE_CALLERS = {
    "arithmetic_on_theta": (_arithmetic, None),
    "arithmetic_then_rollout": (_wrapped_walker, ("MLPPolicy", 1)),
    "plain_act_function": (_plain_act_rollout, ("function", 2)),
    "conv_policy": (_conv_rollout, ("ConvPolicy", 1)),
    "gru_policy": (_gru_rollout, ("GRUPolicy", 1)),
}


@pytest.mark.parametrize("name", sorted(DENSE_CALLERS))
def test_other_eval_fns_get_dense_rows_and_the_old_values(name):
    """An ``eval_fn`` that is not an ``MLPPolicy`` rollout is refused the
    pair at trace time (``pair_fitness`` returns ``None``) and the step
    gives what the dense formula gives; the trace-time counter moves
    once, under ``prepared`` or ``flat``, never under ``pair``."""
    import jax
    import jax.numpy as jnp

    make, counted = DENSE_CALLERS[name]
    dim, eval_fn = make()
    params = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (dim,))
    eps, keys = _draw(dim, 4)
    assert pair_fitness(eval_fn, params, eps, SIGMA, keys) is None

    owners = ("MLPPolicy", "ConvPolicy", "GRUPolicy", "function")
    before = {o: _traces(o) for o in owners}
    es = EvolutionStrategy(eval_fn, dim, 16, sigma=SIGMA, lr=0.05,
                           mesh=_mesh(1))
    key = jax.random.PRNGKey(11)
    got, stats = es.step(params, key)
    moved = {(o, i) for o in owners for i in range(3)
             if _traces(o)[i] != before[o][i]}
    assert moved == ({counted} if counted else set())
    if counted:
        assert _traces(counted[0])[counted[1]] \
            == before[counted[0]][counted[1]] + 1

    want, fitness = jax.jit(
        lambda p, k: _generation(eval_fn, p, k, 8, SIGMA, 0.05))(params, key)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # the statistics to rounding: two programs sum a row in two orders
    assert float(stats[0]) == pytest.approx(float(jnp.mean(fitness)),
                                            rel=1e-6)
    assert float(stats[1]) == pytest.approx(float(jnp.max(fitness)),
                                            rel=1e-6)


def test_the_three_counter_values_for_the_three_kinds_of_eval_fn():
    """``policy_rollout_traces`` reads ``pair`` for a rollout through
    ``MLPPolicy.act``, ``prepared`` for one that did arithmetic on
    ``theta`` first and ``flat`` for a plain ``act`` function: one count
    per traced rollout, whatever was tried before it."""
    import jax

    policy, eval_fn, params = _cartpole()
    plain_dim, plain = _plain_act_rollout()
    seen = []
    for fn, dim, owner in ((eval_fn, policy.dim, "MLPPolicy"),
                           (lambda t, k: eval_fn(t + 0.0, k), policy.dim,
                            "MLPPolicy"),
                           (plain, plain_dim, "function")):
        before = _traces(owner)
        es = EvolutionStrategy(fn, dim, 8, mesh=_mesh(1))
        es.step(jax.numpy.zeros((dim,)), jax.random.PRNGKey(0))
        seen.append(tuple(a - b for a, b in zip(_traces(owner), before)))
    assert seen == [(1, 0, 0), (0, 1, 0), (0, 0, 1)]


def _lowered_step(eval_fn, dim, pop):
    import jax
    import jax.numpy as jnp

    es = EvolutionStrategy(eval_fn, dim, pop, optimizer="adam",
                           mesh=_mesh(1))
    vec = jnp.zeros((dim,))
    return es._step.lower(vec, vec, vec, jnp.asarray(0.0),
                          jax.random.PRNGKey(0)).as_text()


@pytest.mark.parametrize("dense", [False, True], ids=["pair", "dense"])
def test_the_pair_step_never_forms_thetas(dense):
    """The lowered text of the pair step holds no float32 array of
    ``(2 * pairs, dim)`` nor of ``(2 * pairs, n_in, n_out)``: neither
    ``thetas`` nor its unflattened layers exist. The same step from
    dense rows holds all of them, so the search does find such arrays;
    and the pair step does hold the noise's layers, ``(pairs, n_in,
    n_out)``."""
    policy, eval_fn, _ = _walker(steps=5)
    pairs = 12
    text = _lowered_step(_dense(eval_fn) if dense else eval_fn,
                         policy.dim, 2 * pairs)
    layers = list(zip(policy.sizes, policy.sizes[1:]))
    whole = [f"tensor<{2 * pairs}x{policy.dim}xf32>"] + [
        f"tensor<{2 * pairs}x{n_in}x{n_out}xf32>" for n_in, n_out in layers]
    found = [shape in text for shape in whole]
    assert found == [dense] * len(whole), dict(zip(whole, found))
    if not dense:
        for n_in, n_out in layers:
            assert f"tensor<{pairs}x{n_in}x{n_out}xf32>" in text


def test_pair_params_is_a_pytree_and_offers_no_arithmetic():
    """``PairParams`` passes through ``jit`` as three leaves and cuts
    into layers of the same three parts; arithmetic on it, indexing and
    ``asarray`` are ``TypeError``s, which is what sends an ``eval_fn``
    that computes on ``theta`` to the dense rows."""
    import jax
    import jax.numpy as jnp

    policy = MLPPolicy(3, 2, hidden=(4,))
    base = policy.init(jax.random.PRNGKey(0))
    noise = jax.random.normal(jax.random.PRNGKey(1), (policy.dim,))
    pair = PairParams(base, noise, jnp.asarray(-0.5))
    assert len(jax.tree.leaves(pair)) == 3
    obs = jnp.ones((3,))
    got = jax.jit(policy.apply)(pair, obs)
    assert np.array_equal(np.asarray(got),
                          np.asarray(policy.apply(base - 0.5 * noise, obs)))
    (w0, b0), (w1, b1) = policy.unflatten(pair)
    assert [x.noise.shape for x in (w0, b0, w1, b1)] \
        == [(3, 4), (4,), (4, 2), (2,)]
    assert w1.scale is pair.scale
    for misuse in (lambda p: p ** 2, lambda p: p[:2], lambda p: p + 1.0,
                   jnp.asarray, jnp.sum):
        with pytest.raises(TypeError):
            misuse(pair)
