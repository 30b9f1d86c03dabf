"""Policy networks for ES/POET workloads, exposed in the flat-vector form
evolution strategies need (perturbations are dense vectors living on the
MXU-friendly path: one (pop, dim) matmul-shaped tensor, not a pytree zoo).

Reference parity: the reference's ES examples use small torch MLPs
(examples/gecco-2020); here policies are pure JAX with a
``ravel``/``unravel`` pair so a whole population of parameter vectors is a
single 2-D array.

The ``unflatten`` contract (docs/api.md "Policies"): a policy offers
``unflatten(flat_params)``, which cuts the flat vector into its layers
(with the ``compute_dtype`` cast) once, and every method that takes
parameters (``apply`` / ``act``, ``step`` / ``act_step``) takes either
the flat vector or what ``unflatten`` returned. The rollouts of
``models/envs.py`` unflatten once, before their step scan, when handed a
bound method of such a policy; a plain function gets the flat vector on
every step, as before. With ``compute_dtype`` unset the layer products
are float32 on every backend (:func:`_dense`).
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple


def _policy_apply_scope(method):
    """Trace ``method`` under ``jax.named_scope("policy.apply")``: every
    op it makes says so in a profile (``es.rollout/policy.apply/...``),
    and nothing else changes."""
    @functools.wraps(method)
    def scoped(*args, **kwargs):
        import jax

        with jax.named_scope("policy.apply"):
            return method(*args, **kwargs)
    return scoped


def _compute_dtype(explicit):
    """Policy matmul precision: the ``compute_dtype`` keyword, else
    float32. bfloat16 halves policy HBM/MXU cost on TPU; params/logits
    stay float32 at the boundary."""
    import jax.numpy as jnp

    return jnp.dtype(explicit) if explicit else None


class PairParams:
    """One member of an antithetic pair, kept as its parts: the member's
    flat vector is ``base + scale * noise``, never formed whole. ``base``
    is the population's shared vector, ``noise`` the pair's draw (both
    ``(dim,)`` float32) and ``scale`` the member's signed sigma.
    ``ops/es.py`` hands a rollout this where it used to hand a row of
    ``thetas``; :meth:`MLPPolicy.unflatten` cuts it into layers of the
    same three parts, and the layer product (:func:`_value`) adds them
    inside the rollout's step, so a ``vmap`` over the sign with ``noise``
    held unbatched reads each pair's noise once for both members. A
    pytree, so it passes through ``jit`` / ``vmap`` / ``scan`` like an
    array; it offers no arithmetic of its own, so code that computes on
    ``theta`` directly fails with a ``TypeError`` at trace time and the
    engine hands it dense ``thetas`` instead."""

    __slots__ = ("base", "noise", "scale")

    def __init__(self, base, noise, scale):
        _register_pair_pytree()
        self.base, self.noise, self.scale = base, noise, scale

    def cut(self, shapes):
        """One ``PairParams`` per shape, in order: ``base`` and ``noise``
        cut alike, ``scale`` shared."""
        return tuple(
            PairParams(b, n, self.scale)
            for b, n in zip(_cut(self.base, shapes), _cut(self.noise, shapes)))


@functools.cache
def _register_pair_pytree():
    # on first use, not at import: nothing here loads jax before it has to
    import jax

    jax.tree_util.register_pytree_node(
        PairParams,
        lambda p: ((p.base, p.noise, p.scale), None),
        lambda _, parts: PairParams(*parts))


def _value(w, dt):
    """The array a layer's product reads: ``w`` itself, or for one cut of
    a :class:`PairParams` the sum of its parts, rounded as the dense
    ``params + sigma * eps`` is (float32 sum, then the compute-dtype
    cast), formed where it is used so that it lives only inside the
    step's fusion."""
    if not isinstance(w, PairParams):
        return w
    value = w.base + w.scale * w.noise
    return value if dt is None else value.astype(dt)


def _layers(policy, params):
    """What ``policy.unflatten`` returns, whichever form ``params`` has:
    the flat vector is an array (or a :class:`PairParams`), the layers
    are a tuple."""
    return params if isinstance(params, tuple) else policy.unflatten(params)


def _cut(flat, shapes):
    """``flat`` cut into arrays of ``shapes``, in order."""
    import math

    out, offset = [], 0
    for shape in shapes:
        n = math.prod(shape)
        out.append(flat[offset:offset + n].reshape(shape))
        offset += n
    return tuple(out)


def _weights_and_biases(flat, weight_shapes, dt, cut=_cut):
    """``((w, b), ...)``, one pair per weight shape (the bias is as long
    as the weight's last dimension), cast to the compute dtype; ``cut``
    is what cuts ``flat`` into arrays of the shapes."""
    if dt is not None:
        flat = flat.astype(dt)
    shapes = []
    for shape in weight_shapes:
        shapes += [tuple(shape), tuple(shape[-1:])]
    parts = cut(flat, shapes)
    return tuple(zip(parts[::2], parts[1::2]))


def _precision(dt):
    """With no compute dtype a layer product is pinned to float32: at
    default precision it is the backend's to lower (the TPU rounds
    loop-invariant float32 operands to bfloat16), and float32 policy
    arithmetic is the contract."""
    import jax

    return jax.lax.Precision.HIGHEST if dt is None else None


def _dense(x, w, dt):
    """``x (..., n_in) @ w (n_in, n_out)`` in the compute dtype."""
    import jax.numpy as jnp

    return jnp.dot(x, _value(w, dt), precision=_precision(dt))


class MLPPolicy:
    """Tanh MLP: obs -> hidden* -> logits, as flat parameter vectors.

    ``compute_dtype`` runs the matmuls
    in reduced precision (e.g. "bfloat16") while params and outputs
    stay float32."""

    def __init__(self, obs_dim: int, act_dim: int,
                 hidden: Sequence[int] = (32, 32),
                 compute_dtype: str | None = None) -> None:
        self.obs_dim = obs_dim
        self.act_dim = act_dim
        self.compute_dtype = compute_dtype
        self.sizes = (obs_dim, *hidden, act_dim)
        self.dim = sum(
            self.sizes[i] * self.sizes[i + 1] + self.sizes[i + 1]
            for i in range(len(self.sizes) - 1)
        )

    def init(self, key):
        """Flat parameter vector (dim,)."""
        import jax
        import jax.numpy as jnp

        parts = []
        for i in range(len(self.sizes) - 1):
            key, wk = jax.random.split(key)
            fan_in = self.sizes[i]
            w = jax.random.normal(
                wk, (self.sizes[i], self.sizes[i + 1])
            ) / jnp.sqrt(fan_in)
            b = jnp.zeros((self.sizes[i + 1],))
            parts.append(w.ravel())
            parts.append(b)
        return jnp.concatenate(parts)

    def unflatten(self, flat_params):
        """``((w, b), ...)`` per layer, cast to the compute dtype: the
        cutting ``apply`` would do, done once. A :class:`PairParams` is
        cut part by part and keeps float32: its cast follows its sum, in
        the layer's product."""
        shapes = zip(self.sizes, self.sizes[1:])
        if isinstance(flat_params, PairParams):
            return _weights_and_biases(flat_params, shapes, None,
                                       cut=PairParams.cut)
        return _weights_and_biases(
            flat_params, shapes, _compute_dtype(self.compute_dtype))

    @_policy_apply_scope
    def apply(self, params, obs):
        """Logits for one observation; jittable / vmappable. ``params``
        is the flat vector or ``unflatten`` of it."""
        import jax.numpy as jnp

        dt = _compute_dtype(self.compute_dtype)
        layers = _layers(self, params)
        x = obs if dt is None else obs.astype(dt)
        for i, (w, b) in enumerate(layers):
            x = _dense(x, w, dt) + _value(b, dt)
            if i < len(layers) - 1:
                x = jnp.tanh(x)
        return x.astype(jnp.float32)

    def act(self, params, obs):
        """Deterministic discrete action."""
        import jax.numpy as jnp

        return jnp.argmax(self.apply(params, obs))


class ConvPolicy:
    """Small conv policy for image observations (Atari-style ES), kept in
    NHWC with bf16-friendly channel sizes so convs tile onto the MXU."""

    def __init__(self, obs_shape: Tuple[int, int, int], act_dim: int,
                 channels: Sequence[int] = (16, 32),
                 hidden: int = 128,
                 compute_dtype: str | None = None) -> None:
        self.obs_shape = obs_shape  # (H, W, C)
        self.act_dim = act_dim
        self.channels = tuple(channels)
        self.hidden = hidden
        self.compute_dtype = compute_dtype
        h, w, c = obs_shape
        self._specs = []
        in_c = c
        for out_c in self.channels:
            self._specs.append(("conv", (3, 3, in_c, out_c)))
            in_c = out_c
            h, w = (h + 1) // 2, (w + 1) // 2  # stride-2 convs
        self._flat_len = h * w * in_c
        self._specs.append(("dense", (self._flat_len, hidden)))
        self._specs.append(("dense", (hidden, act_dim)))
        self.dim = sum(
            int(__import__("numpy").prod(shape)) + shape[-1]
            for _, shape in self._specs
        )

    def init(self, key):
        import jax
        import jax.numpy as jnp
        import numpy as np

        parts = []
        for kind, shape in self._specs:
            key, wk = jax.random.split(key)
            fan_in = int(np.prod(shape[:-1]))
            w = jax.random.normal(wk, shape) / jnp.sqrt(fan_in)
            parts.append(w.ravel())
            parts.append(jnp.zeros((shape[-1],)))
        return jnp.concatenate(parts)

    def unflatten(self, flat_params):
        """``((w, b), ...)`` per layer, cast to the compute dtype."""
        return _weights_and_biases(
            flat_params, (shape for _, shape in self._specs),
            _compute_dtype(self.compute_dtype))

    @_policy_apply_scope
    def apply(self, params, obs):
        """Logits for one image; ``params`` is the flat vector or
        ``unflatten`` of it."""
        import jax
        import jax.numpy as jnp

        dt = _compute_dtype(self.compute_dtype)
        layers = _layers(self, params)
        x = obs[None]  # NHWC with N=1
        if dt is not None:
            x = x.astype(dt)
        for i, ((kind, _), (w, b)) in enumerate(zip(self._specs, layers)):
            if kind == "conv":
                x = jax.lax.conv_general_dilated(
                    x, w, window_strides=(2, 2), padding="SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    precision=_precision(dt),
                )
                x = jnp.tanh(x + b)
            else:
                if x.ndim > 2:
                    x = x.reshape(x.shape[0], -1)
                x = _dense(x, w, dt) + b
                if i < len(layers) - 1:
                    x = jnp.tanh(x)
        return x[0].astype(jnp.float32)

    def act(self, params, obs):
        import jax.numpy as jnp

        return jnp.argmax(self.apply(params, obs))


class GRUPolicy:
    """Single-layer GRU with a linear readout, as flat parameter vectors —
    the recurrent model family for partially-observable ES tasks (the
    reference's ES examples are feed-forward only; memory policies are
    the standard extension for masked/occluded observations).

    Contract: ``init_carry()`` gives the zero hidden state;
    ``act_step(flat_params, carry, obs) -> (carry', action)`` advances
    one step. Use ``fiber_tpu.models.rollout_recurrent`` to evaluate on
    any env with the reset/step interface; everything stays jittable and
    vmappable (a population of GRUs is one (pop, dim) tensor, same as
    the MLP path)."""

    def __init__(self, obs_dim: int, act_dim: int,
                 hidden: int = 32) -> None:
        self.obs_dim = obs_dim
        self.act_dim = act_dim
        self.hidden = hidden
        # 3 gates x (W: obs->h, U: h->h, b) + readout (h->act, b)
        self.dim = (
            3 * (obs_dim * hidden + hidden * hidden + hidden)
            + hidden * act_dim + act_dim
        )

    def init(self, key):
        import jax
        import jax.numpy as jnp

        o, h, a = self.obs_dim, self.hidden, self.act_dim
        parts = []
        for fan_in, shape in (
            (o, (o, h)), (h, (h, h)), (None, (h,)),   # z gate
            (o, (o, h)), (h, (h, h)), (None, (h,)),   # r gate
            (o, (o, h)), (h, (h, h)), (None, (h,)),   # candidate
            (h, (h, a)), (None, (a,)),                # readout
        ):
            if fan_in is None:
                parts.append(jnp.zeros(shape))
            else:
                key, wk = jax.random.split(key)
                parts.append(
                    (jax.random.normal(wk, shape) / jnp.sqrt(fan_in)).ravel()
                )
        return jnp.concatenate(parts)

    def init_carry(self):
        import jax.numpy as jnp

        return jnp.zeros((self.hidden,))

    def unflatten(self, flat_params):
        """The eleven arrays of the three gates and the readout."""
        o, h, a = self.obs_dim, self.hidden, self.act_dim
        return _cut(flat_params, [(o, h), (h, h), (h,)] * 3 + [(h, a), (a,)])

    @_policy_apply_scope
    def step(self, params, carry, obs):
        """(carry', logits) for one step; jittable/vmappable. ``params``
        is the flat vector or ``unflatten`` of it."""
        import jax
        import jax.numpy as jnp

        (wz, uz, bz, wr, ur, br, wh, uh, bh, wo, bo) = _layers(self, params)
        dot = functools.partial(_dense, dt=None)
        z = jax.nn.sigmoid(dot(obs, wz) + dot(carry, uz) + bz)
        r = jax.nn.sigmoid(dot(obs, wr) + dot(carry, ur) + br)
        cand = jnp.tanh(dot(obs, wh) + dot(r * carry, uh) + bh)
        new_carry = (1.0 - z) * carry + z * cand
        return new_carry, dot(new_carry, wo) + bo

    def act_step(self, params, carry, obs):
        import jax.numpy as jnp

        new_carry, logits = self.step(params, carry, obs)
        return new_carry, jnp.argmax(logits)
