"""State-space mixer (Mamba-2, arXiv:2405.21060): the selective scan in its
chunked form, the causal depthwise convolution before it, the gated group
norm after it, and the same recurrence one position at a time for decode.

One head ``h`` of group ``g`` keeps a state ``H`` (P, N) and reads, per
position, ``x[t]`` (P,), a step ``dt[t] > 0`` and its group's ``B[t]``,
``C[t]`` (N,); with ``A[h] < 0``::

    H[t] = exp(dt[t] A) H[t-1] + dt[t] x[t] (outer) B[t]
    y[t] = H[t] C[t] + D x[t]

``ssd_scan`` computes that in blocks of ``chunk`` positions. With ``a[t] =
dt[t] A``, ``s[l]`` the sum of ``a`` over a block's positions up to ``l``
and ``xd[t] = dt[t] x[t]``: inside a block ``y_in[l] = sum over m <= l of
(C[l] . B[m]) exp(s[l] - s[m]) xd[m]`` (a masked, decay-weighted ``C B^T``
applied to ``xd``); the block's own state ``S_c = sum over m of exp(s[last]
- s[m]) xd[m] (outer) B[m]``; carried over the blocks ``H_c = exp(s[last])
H_(c-1) + S_c`` from zero (a short ``lax.scan``); from the earlier blocks
``y_out[l] = exp(s[l]) H_(c-1) C[l]``. Decays are summed in log space (the
``exp`` of a difference of cumulative sums, never a product of ``chunk``
factors); ``dt``, ``A``, the sums and the carried state are float32; ``B``
and ``C`` stay (positions, groups, N) and are never repeated out to the
heads. Plain ``jax.numpy``: the backward pass is jax's, and a caller that
cannot keep a block's (heads, chunk, chunk) intermediates alive recomputes
(``jax.checkpoint`` around the mixer: ``models/transformer.py``).

``ssd_step`` is the recurrence itself for one position: the program's
second, independent form of the scan (``BlockLM.generate``).
"""

from __future__ import annotations


def causal_conv(v, w, b):
    """Causal depthwise convolution over positions: ``v`` (S, C), ``w``
    (C, K), ``b`` (C,) -> ``out[t, c] = b[c] + sum_j w[c, j] v[t - (K-1)
    + j, c]``, zeros before position 0."""
    import jax.numpy as jnp

    S, K = v.shape[0], w.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros((K - 1, v.shape[1]), v.dtype), v], axis=0)
    out = b
    for j in range(K):
        out = out + padded[j:j + S] * w[:, j]
    return out


def gated_group_norm(y, z, gain, groups: int, eps: float):
    """``y * silu(z)``, RMS-normalised in ``groups`` groups of the last
    axis, times ``gain``."""
    import jax
    import jax.numpy as jnp

    y = y * jax.nn.silu(z)
    shape = y.shape
    y = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return gain * y.reshape(shape)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int):
    """The chunked scan. ``x`` (S, H, P), ``dt`` (S, H) positive, ``A``
    (H,) negative, ``B`` / ``C`` (S, G, N) with ``H = G * R`` (head ``h``
    reads group ``h // R``), ``D`` (H,) -> ``y`` (S, H, P). ``S`` must be
    whole chunks. See the module's head for the equations."""
    import jax
    import jax.numpy as jnp

    S, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    if S % chunk:
        raise ValueError(
            f"{S} positions are not whole chunks of {chunk}")
    if H % G:
        raise ValueError(f"{H} heads do not divide into {G} groups")
    R, nc, L = H // G, S // chunk, chunk
    f32 = jnp.float32
    dt = dt.astype(f32)
    a = (dt * A.astype(f32)).reshape(nc, L, G, R)
    s = jnp.cumsum(a, axis=1)                               # (nc, L, G, R)
    xd = (x * dt[..., None].astype(x.dtype)).reshape(nc, L, G, R, P)
    Bc, Cc = B.reshape(nc, L, G, N), C.reshape(nc, L, G, N)

    # inside a block: (C B^T) x decay, masked to m <= l, applied to xd
    cb = jnp.einsum("clgn,cmgn->cglm", Cc, Bc,
                    preferred_element_type=f32)             # (nc, G, L, L)
    s_h = jnp.moveaxis(s, 1, -1)                            # (nc, G, R, L)
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(
        causal, s_h[..., :, None] - s_h[..., None, :], -jnp.inf))
    y = jnp.einsum("cgrlm,cmgrp->clgrp",
                   (cb[:, :, None] * decay).astype(x.dtype), xd)

    # the block's own state, and the state carried over the blocks
    last = s[:, -1]                                         # (nc, G, R)
    to_end = jnp.exp(last[:, None] - s)                     # (nc, L, G, R)
    own = jnp.einsum("cmgrp,cmgn->cgrpn",
                     xd * to_end[..., None].astype(x.dtype), Bc,
                     preferred_element_type=f32)            # (nc,G,R,P,N)

    def carry(h, inp):
        keep, own_c = inp
        return keep[..., None, None] * h + own_c, h

    _, before = jax.lax.scan(carry, jnp.zeros((G, R, P, N), f32),
                             (jnp.exp(last), own))
    y = y + jnp.einsum("clgn,cgrpn->clgrp", Cc, before.astype(x.dtype)) \
        * jnp.exp(s)[..., None].astype(x.dtype)
    return y.reshape(S, H, P) + D.astype(x.dtype)[:, None] * x


def ssd_step(state, x, dt, A, B, C, D):
    """One position of the recurrence: ``state`` (H, P, N) float32, ``x``
    (H, P), ``dt`` (H,), ``B`` / ``C`` (G, N) -> (new state, ``y`` (H,
    P))."""
    import jax.numpy as jnp

    H, G = x.shape[0], B.shape[0]
    f32 = jnp.float32
    dt = dt.astype(f32)
    Bh = jnp.repeat(B, H // G, axis=0).astype(f32)          # (H, N): one row
    Ch = jnp.repeat(C, H // G, axis=0).astype(f32)
    state = (jnp.exp(dt * A.astype(f32))[:, None, None] * state
             + (dt[:, None] * x.astype(f32))[:, :, None] * Bh[:, None, :])
    y = jnp.einsum("hpn,hn->hp", state, Ch).astype(x.dtype)
    return state, y + D.astype(x.dtype)[:, None] * x
