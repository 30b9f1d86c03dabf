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
heads.

``ssd_scan`` is the one door and has two forms. Where the backend compiles
Mosaic (a TPU), or the caller asks for the Pallas interpreter, and the
shapes are the ones they were written for (``scan_path``), it is two Pallas
kernels joined by a ``custom_vjp``: ``ssd_scan_fwd`` walks a group's blocks
with the state in VMEM, ``ssd_scan_bwd`` walks them back with the state's
gradient there, from each block's kept state before it; no
(chunk, chunk) decay and no (P, N) state but those reaches HBM. Everywhere
else it is ``ssd_scan_plain``: plain ``jax.numpy`` with jax's own backward
pass, the CPU's path, the odd shapes' path and the kernels' oracle, whose
caller recomputes if it cannot keep a block's (heads, chunk, chunk)
intermediates alive (``jax.checkpoint`` around the mixer:
``models/transformer.py``, which both forms run under).

``ssd_step`` is the recurrence itself for one position: the program's
second, independent form of the scan (``BlockLM.generate``).
"""

from __future__ import annotations

import functools

#: the TPU's lane count: the kernels' block of positions and their tiles
_LANES = 128


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
    axis, times ``gain``. A group's mean and its way back out to the
    group's features are products with the groups' membership matrix (at
    ``HIGHEST``: float32 sums), not a reshape to (..., groups, width): on
    the TPU that reshape is another tiling of the (positions, features)
    array, and XLA copied the whole array there and back around it."""
    import jax
    import jax.numpy as jnp

    y = y * jax.nn.silu(z)
    width = y.shape[-1] // groups
    member = (jnp.arange(y.shape[-1])[:, None] // width
              == jnp.arange(groups)).astype(y.dtype)
    exact = jax.lax.Precision.HIGHEST
    mean = jnp.matmul(y * y, member, precision=exact) / width
    scale = jnp.matmul(jax.lax.rsqrt(mean + eps), member.T, precision=exact)
    return gain * (y * scale)


def scan_path(S: int, H: int, P: int, G: int, N: int, chunk: int,
              interpret: bool = False) -> str:
    """Which form ``ssd_scan`` runs on these shapes: ``"kernel"`` (the two
    Pallas kernels) where they can run and were written for, else
    ``"plain"``. They can run where the backend compiles Mosaic (a TPU) or
    the caller asked for the Pallas interpreter; they were written for
    blocks of 128 positions, a state whole in 128 lanes, and a group whose
    ``R`` heads (at most 128) of ``P`` features fill whole tiles of 128
    lanes with whole heads (the cell's 8 heads of 64 are 4 tiles of 2)."""
    import jax

    fits = (chunk == _LANES and S % chunk == 0 and H % G == 0
            and N % _LANES == 0 and _LANES % P == 0
            and (H // G * P) % _LANES == 0 and H // G <= _LANES)
    runs = interpret or jax.default_backend() == "tpu"
    return "kernel" if fits and runs else "plain"


def ssd_scan(x, dt, A, B, C, D, *, chunk: int, interpret: bool = False):
    """The chunked scan. ``x`` (S, H, P), ``dt`` (S, H) positive, ``A``
    (H,) negative, ``B`` / ``C`` (S, G, N) with ``H = G * R`` (head ``h``
    reads group ``h // R``), ``D`` (H,) -> ``y`` (S, H, P). ``S`` must be
    whole chunks. See the module's head for the equations. The one door:
    the Pallas kernels where ``scan_path`` says they run (``interpret``:
    in the Pallas interpreter, for tests on the CPU), else the plain
    form."""
    S, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
    if S % chunk:
        raise ValueError(
            f"{S} positions are not whole chunks of {chunk}")
    if H % G:
        raise ValueError(f"{H} heads do not divide into {G} groups")
    if scan_path(S, H, P, G, N, chunk, interpret) == "kernel":
        return _scan_kernels(S, H, P, G, N, str(x.dtype), interpret)(
            x, dt, A, B, C, D)
    return ssd_scan_plain(x, dt, A, B, C, D, chunk=chunk)


def ssd_scan_plain(x, dt, A, B, C, D, *, chunk: int):
    """``ssd_scan`` in plain ``jax.numpy`` with jax's own backward pass:
    the CPU's path, the odd shapes' path and the kernels' oracle."""
    import jax
    import jax.numpy as jnp

    S, H, P = x.shape
    G, N = B.shape[1], B.shape[2]
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


# -- the scan as two Pallas kernels -------------------------------------------
#
# One grid step holds one block of L = 128 positions of one group: ``x`` as
# the lane-dense (L, R P) slab of the (S, H P) array, cut into tiles of 128
# lanes (``q = 128 // P`` whole heads each), ``B`` and ``C`` as (L, N), and
# ``dt`` for the group's R heads with the positions in the lanes, (R, L).
# The state between blocks is a float32 VMEM scratch of (N, R P), a head's
# (P, N) transposed so that every head's state sits under that head's lanes
# of ``x``; blocks are the grid's inner, sequential axis. Per-position,
# per-head numbers (``dt``, the cumulative sums ``s``) are needed with the
# positions in the lanes (the decays' columns) and in the sublanes (the
# decays' rows, and every factor of a row of ``x``): the second comes from
# the first through one (128, 128) transpose, and goes out to a tile's
# lanes by ``_spread``. Sums that must be float32 but run on the MXU (the
# cumulative sums as a product with a triangle of ones; a head's sum over
# its P lanes as a product with a matrix of ones and zeros) split the
# float32 factor into bfloat16 parts, each of which the MXU multiplies by 0
# or 1 exactly (``_exact_dot``). Every other product is one bfloat16 pass
# with float32 accumulation, the TPU's default for the plain form too; in
# the interpreter (the CPU) the factors stay float32, as the plain form's
# do there.


def _dot(a, b, dims=((1,), (0,))):
    """``a @ b`` (``dims``: the contracted axis of each) accumulated in
    float32."""
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _exact_dot(v, ones, parts: int):
    """``v @ ones`` for float32 ``v`` and a matrix of zeros and ones, to
    ``8 * parts`` bits of ``v``: on the MXU a float32 factor is rounded to
    bfloat16, so ``v`` goes in as a sum of bfloat16 parts (float32
    ``ones``, the interpreter's: one exact product)."""
    import jax.numpy as jnp

    if ones.dtype == jnp.float32:
        return _dot(v, ones)
    out = None
    for _ in range(parts):
        part = v.astype(ones.dtype)
        v = v - part.astype(jnp.float32)
        out = _dot(part, ones) if out is None else out + _dot(part, ones)
    return out


def _iota(shape, axis):
    import jax
    import jax.numpy as jnp

    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _spread(cols, first: int, q: int, P: int):
    """From ``cols`` (L, 128), one head a lane, the tile whose lanes are
    ``q`` heads of ``P`` features from head ``first`` on: lane ``k`` gets
    head ``first + k // P``'s column."""
    import jax.numpy as jnp

    shape = cols.shape
    out = jnp.broadcast_to(cols[:, first:first + 1], shape)
    for i in range(1, q):
        out = jnp.where(
            _iota(shape, 1) >= i * P,
            jnp.broadcast_to(cols[:, first + i:first + i + 1], shape), out)
    return out


def _by_head(parts, P: int):
    """One (L, 128) tile from ``q`` of them: head ``i``'s ``P`` lanes from
    ``parts[i]``."""
    import jax.numpy as jnp

    out = parts[0]
    for i in range(1, len(parts)):
        out = jnp.where(_iota(out.shape, 1) >= i * P, parts[i], out)
    return out


def _head_sums(t: int, q: int, P: int, dtype):
    """The (128, 128) matrix of zeros and ones that sums tile ``t``'s
    lanes by head: lane ``k`` of the tile goes to lane ``t q + k // P``."""
    shape = (_LANES, _LANES)
    start = (_iota(shape, 1) - t * q) * P
    k = _iota(shape, 0)
    return ((k >= start) & (k < start + P)).astype(dtype)


def _block_sums(dt_ref, a_ref, mxu):
    """A block's ``dt`` and cumulative sums ``s`` of ``dt A`` for the
    group's R heads: (``s`` with the positions in the lanes, (128, L), row
    ``r`` head ``r``; ``s`` and ``dt`` with the positions in the sublanes,
    (L, 128), lane ``r`` head ``r``; ``dt`` (R, L))."""
    import jax.numpy as jnp

    dt = dt_ref[...]
    R, L = dt.shape

    def rows(v):
        if R == _LANES:
            return v
        return jnp.concatenate(
            [v, jnp.zeros((_LANES - R, L), jnp.float32)], axis=0)

    upto = (_iota((L, L), 0) <= _iota((L, L), 1)).astype(mxu)
    s = _exact_dot(rows(dt * a_ref[...]), upto, 3)
    return s, s.T, rows(dt).T, dt


def _last_row(v):
    """``v``'s last row, (1, lanes), as a masked sum: a slice of a tile
    that ``_spread`` made of one column is a broadcast both ways to
    Mosaic, which it does not take."""
    import jax.numpy as jnp

    return jnp.sum(
        jnp.where(_iota(v.shape, 0) == v.shape[0] - 1, v, 0.0), axis=0,
        keepdims=True)


def _decay(s_rows, s_cols, r: int):
    """Head ``r``'s (L, L) decays ``exp(s[l] - s[m])`` for ``m <= l``, 0
    above the diagonal: masked before the ``exp``, where the difference is
    positive and large."""
    import jax.numpy as jnp

    L = s_cols.shape[0]
    diff = s_cols[:, r:r + 1] - s_rows[r:r + 1, :]
    return jnp.exp(jnp.where(_iota((L, L), 0) >= _iota((L, L), 1),
                             diff, -jnp.inf))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref, *rest,
                P: int, mxu, keep: bool):
    """One block of one group, forward: ``y`` and the carried state; with
    ``keep`` the state before the block goes out too (the backward walk's
    ``H_prev``)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    state = rest[-1]
    f32 = jnp.float32
    q = _LANES // P

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if keep:
        rest[0][...] = state[...]
    s_rows, s_cols, dt_cols, _ = _block_sums(dt_ref, a_ref, mxu)
    b = b_ref[...].astype(f32)
    c = c_ref[...].astype(mxu)
    cb = _dot(c, b.astype(mxu), ((1,), (1,)))               # (L, L)
    b_t = b.T.astype(mxu)                                   # (N, L)
    for t in range(x_ref.shape[1] // _LANES):
        at = slice(t * _LANES, (t + 1) * _LANES)
        x = x_ref[:, at].astype(f32)
        s = _spread(s_cols, t * q, q, P)
        s_last = _last_row(s)
        es = jnp.exp(s)
        xd = x * _spread(dt_cols, t * q, q, P)
        xd_m = xd.astype(mxu)
        inside = _by_head([
            _dot((cb * _decay(s_rows, s_cols, t * q + i)).astype(mxu), xd_m)
            for i in range(q)], P)
        before = state[:, at]
        y = (inside + es * _dot(c, before.astype(mxu))
             + d_ref[:, at] * x)
        y_ref[:, at] = y.astype(y_ref.dtype)
        to_end = jnp.exp(s_last - s)
        state[:, at] = (jnp.exp(s_last) * before
                        + _dot(b_t, (xd * to_end).astype(mxu)))


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, dy_ref, h_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref, dstate, *,
                P: int, mxu):
    """One block of one group, backward, the blocks walked from the last
    to the first: ``dstate`` carries the gradient of the state after the
    block. The gradient of ``s[l]`` is, from the decays, row ``l``'s sum
    of ``dM * M`` less column ``l``'s (both from the same (L, L) product,
    so what cancels in them cancels to the last bit: summed from ``k``
    on, as the gradient of ``dt A`` is, only the pairs across ``k`` are
    left, and a sum over a whole sequence, ``dt_bias``'s and ``A``'s,
    keeps no rounding that does not cancel); from the carried state, a
    head's sum over its lanes of ``dy y_out - xd dxd_state``, plus, at
    the block's last position, what the state's update gives."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    L = b_ref.shape[0]
    R = dt_ref.shape[0]
    q = _LANES // P

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    s_rows, s_cols, dt_cols, dt = _block_sums(dt_ref, a_ref, mxu)
    b = b_ref[...].astype(mxu)
    c = c_ref[...].astype(f32)
    c_t = c.T.astype(mxu)                                   # (N, L)
    c = c.astype(mxu)
    cb = _dot(c, b, ((1,), (1,)))
    dcb = jnp.zeros((L, L), f32)
    db = jnp.zeros(b_ref.shape, f32)
    dc = jnp.zeros(c_ref.shape, f32)
    ds_cols = jnp.zeros((L, _LANES), f32)
    ds_rows = jnp.zeros((_LANES, L), f32)
    ddt_cols = jnp.zeros((L, _LANES), f32)
    for t in range(x_ref.shape[1] // _LANES):
        at = slice(t * _LANES, (t + 1) * _LANES)
        x = x_ref[:, at].astype(f32)
        dy = dy_ref[:, at].astype(f32)
        s = _spread(s_cols, t * q, q, P)
        dt_t = _spread(dt_cols, t * q, q, P)
        s_last = _last_row(s)
        es, last = jnp.exp(s), jnp.exp(s_last)
        to_end = jnp.exp(s_last - s)
        xd = x * dt_t
        xd_m, dy_m = xd.astype(mxu), dy.astype(mxu)
        dh, before = dstate[:, at], h_ref[:, at]
        dh_m, before_m = dh.astype(mxu), before.astype(mxu)
        from_state = to_end * _dot(b, dh_m)     # xd's gradient by B dH^T
        parts, lane = [], _iota(dy.shape, 1)
        for i in range(q):
            r = t * q + i
            decay = _decay(s_rows, s_cols, r)
            own = dy_m if q == 1 else jnp.where(
                (lane >= i * P) & (lane < (i + 1) * P), dy_m, 0)
            dm = _dot(own, xd_m, ((1,), (1,))) * decay      # dy xd^T, masked
            dcb = dcb + dm
            g = dm * cb                                     # dM * M
            ds_cols = ds_cols + jnp.where(
                lane == r, jnp.sum(g, axis=1, keepdims=True), 0.0)
            ds_rows = ds_rows - jnp.where(
                _iota(ds_rows.shape, 0) == r,
                jnp.sum(g, axis=0, keepdims=True), 0.0)
            parts.append(_dot((cb * decay).astype(mxu), dy_m,
                              ((0,), (0,))))                # M^T dy
        dxd = _by_head(parts, P) + from_state
        d = d_ref[:, at]
        dx_ref[:, at] = (dxd * dt_t + d * dy).astype(dx_ref.dtype)
        at_last = (jnp.sum(xd * from_state, axis=0, keepdims=True)
                   + last * jnp.sum(dh * before, axis=0, keepdims=True))
        es_dy = es * dy
        ds = (es_dy * _dot(c, before_m) - xd * from_state
              + jnp.where(_iota(x.shape, 0) == L - 1, at_last, 0.0))
        ones = _head_sums(t, q, P, mxu)
        ds_cols = ds_cols + _exact_dot(ds, ones, 2)
        ddt_cols = ddt_cols + _exact_dot(x * dxd, ones, 2)
        dd_ref[:, at] += jnp.sum(dy * x, axis=0, keepdims=True)
        es_dy = es_dy.astype(mxu)
        dc = dc + _dot(es_dy, before_m, ((1,), (1,)))
        db = db + _dot((xd * to_end).astype(mxu), dh_m, ((1,), (1,)))
        dstate[:, at] = last * dh + _dot(c_t, es_dy)
    dc_ref[...] = (dc + _dot(dcb.astype(mxu), b)).astype(dc_ref.dtype)
    db_ref[...] = (db + _dot(dcb.T.astype(mxu), c)).astype(db_ref.dtype)
    # s is the running sum of dt A: the gradient of dt A at k is the sum
    # of s's gradient from k on
    from_k = (_iota((L, L), 0) >= _iota((L, L), 1)).astype(mxu)
    da = _exact_dot(ds_cols.T + ds_rows, from_k, 3)[:R]
    ddt_ref[...] = ddt_cols.T[:R] + a_ref[...] * da
    da_ref[...] += da * dt


@functools.lru_cache(maxsize=16)
def _scan_kernels(S: int, H: int, P: int, G: int, N: int, dtype: str,
                  interpret: bool):
    """``ssd_scan`` on the two kernels for one set of shapes (``scan_path``
    says which fit): ``(x, dt, A, B, C, D) -> y`` with a ``custom_vjp``
    that keeps, besides its arguments, each block's state before it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    L, R, nc, RP = _LANES, H // G, S // _LANES, H // G * P
    mxu = f32 if interpret else jnp.bfloat16

    def specs(block):
        """The block specs the kernels share, the grid's second axis
        mapped to a block of positions by ``block``."""
        return dict(
            x=pl.BlockSpec((L, RP), lambda g, c: (block(c), g)),
            bc=pl.BlockSpec((L, N), lambda g, c: (block(c), g)),
            dt=pl.BlockSpec((None, R, L), lambda g, c: (g, 0, block(c))),
            a=pl.BlockSpec((None, R, 1), lambda g, c: (g, 0, 0)),
            d=pl.BlockSpec((1, RP), lambda g, c: (0, g)),
            h=pl.BlockSpec((None, None, N, RP),
                           lambda g, c: (block(c), g, 0, 0)))

    states = jax.ShapeDtypeStruct((nc, G, N, RP), f32)

    def forward_call(keep: bool):
        sp = specs(lambda c: c)
        y = jax.ShapeDtypeStruct((S, H * P), dtype)
        return jax.jit(pl.pallas_call(
            functools.partial(_fwd_kernel, P=P, mxu=mxu, keep=keep),
            grid=(G, nc),
            in_specs=[sp["x"], sp["bc"], sp["bc"], sp["dt"], sp["a"],
                      sp["d"]],
            out_specs=[sp["x"], sp["h"]] if keep else sp["x"],
            out_shape=[y, states] if keep else y,
            scratch_shapes=[pltpu.VMEM((N, RP), f32)],
            name="ssd_scan_fwd", interpret=interpret))

    # under jit, and built once: a kernel's body is traced once a process
    # and not once a call (a model's every layer and every program that
    # holds it, about a third of a second each on the chip's host)
    forward = {keep: forward_call(keep) for keep in (False, True)}

    sp = specs(lambda c: nc - 1 - c)
    sums = pl.BlockSpec((None, R, L), lambda g, c: (g, 0, 0))
    bwd_call = jax.jit(pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, mxu=mxu),
        grid=(G, nc),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["dt"], sp["a"], sp["d"],
                  sp["x"], sp["h"]],
        out_specs=[sp["x"], sp["bc"], sp["bc"], sp["dt"], sums, sp["d"]],
        out_shape=[jax.ShapeDtypeStruct((S, H * P), dtype),
                   jax.ShapeDtypeStruct((S, G * N), dtype),
                   jax.ShapeDtypeStruct((S, G * N), dtype),
                   jax.ShapeDtypeStruct((G, R, S), f32),
                   jax.ShapeDtypeStruct((G, R, L), f32),
                   jax.ShapeDtypeStruct((1, H * P), f32)],
        scratch_shapes=[pltpu.VMEM((N, RP), f32)],
        name="ssd_scan_bwd", interpret=interpret))

    def laid_out(x, dt, A, B, C, D):
        """The kernels' arguments: the heads' features and the groups'
        states flat in the lanes, ``dt`` with the positions last."""
        return (x.reshape(S, H * P), B.reshape(S, G * N),
                C.reshape(S, G * N), dt.astype(f32).T.reshape(G, R, S),
                A.astype(f32).reshape(G, R, 1),
                jnp.repeat(D.astype(f32), P).reshape(1, H * P))

    @jax.custom_vjp
    def scan(x, dt, A, B, C, D):
        return forward[False](*laid_out(x, dt, A, B, C, D)).reshape(
            S, H, P)

    def scan_fwd(x, dt, A, B, C, D):
        y, before = forward[True](*laid_out(x, dt, A, B, C, D))
        return y.reshape(S, H, P), (x, dt, A, B, C, D, before)

    def scan_bwd(kept, dy):
        x, dt, A, B, C, D, before = kept
        dx, dB, dC, ddt, dA, dD = bwd_call(
            *laid_out(x, dt, A, B, C, D), dy.reshape(S, H * P), before)
        return (dx.reshape(S, H, P),
                ddt.reshape(H, S).T.astype(dt.dtype),
                dA.sum(-1).reshape(H).astype(A.dtype),
                dB.reshape(S, G, N), dC.reshape(S, G, N),
                dD.reshape(H, P).sum(-1).astype(D.dtype))

    scan.defvjp(scan_fwd, scan_bwd)
    return scan


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
