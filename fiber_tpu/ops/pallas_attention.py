"""Flash attention as Pallas TPU kernels — the per-device block of the
long-context plane, forward AND backward.

Motivation (round-2 verdict: "make one kernel earn its keep"): the
XLA-path local attention (`ring_attention._block_attn`) materializes the
full (heads, sq, skv) score tensor in HBM per KV block — at 8k tokens
single-chip that is gigabytes of HBM traffic, and past ~16k it simply
does not fit. These kernels stream KV blocks through VMEM with online
softmax accumulators, so scores never touch HBM: O(S) memory instead of
O(S**2), and the matmuls stay on the MXU back-to-back.

Differentiable: ``flash_attention`` carries a ``jax.custom_vjp`` whose
backward runs two more Pallas kernels (dq sweep over KV blocks; dk/dv
sweep over Q blocks) from the saved (q, k, v, out, logsumexp) residuals
— the FlashAttention-2 recurrence. Exact — not an approximation: output
and gradients match the full-matrix reference to numerical tolerance,
pinned by tests in interpret mode on CPU and, compiled through Mosaic,
by ``chip_smoke.py`` on the chip.

Values may be narrower or wider than queries and keys (latent attention
attends over 192 features and returns 128): q and k are (S, H, Dqk), v and
the output (S, H, Dv), and the scale is ``Dqk ** -0.5``. Where the two widths
are equal the kernels are built as they always were.

Under a checkpoint: the forward rules give the two residuals the kernel
produced, ``out`` (S, H, Dv) and the compact ``lse`` (H, S), the names
``KEPT_NAMES`` (``jax.ad_checkpoint.checkpoint_name``); q, k and v get
none. A ``jax.checkpoint`` whose policy saves those two names keeps
them and recomputes q, k, v and all else, and then its backward pass
has no use for the forward kernel and holds no call of it
(``BlockLM(recompute="layer")`` is that caller). Anywhere else a name
lowers to nothing.

The reference framework has no kernels and no attention (SURVEY.md §5);
this is the repo's own TPU-native bar, not a parity item.
"""

from __future__ import annotations

import functools

_NEG_INF = -1e30  # large-negative instead of -inf: avoids inf-inf NaNs

#: What the forward rules name of their residuals: the forward kernel's
#: output and its row statistics (not the (H, S, 1) column the kernels
#: read, which pads to 128 lanes).
KEPT_NAMES = ("flash_attn_out", "flash_attn_lse")


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _run_window(iq, ik, block_q, block_kv, causal, window):
    """Static-shape block-skip predicate: False when the (q-block,
    kv-block) pair can contribute nothing — above the causal diagonal,
    or (with a sliding window) entirely older than every q row's
    window. `_kv_span` / `_q_span` are its runs, which are contiguous."""
    import jax.numpy as jnp

    if not causal:
        return jnp.bool_(True)
    run = ik * block_kv < (iq + 1) * block_q
    if window is not None:
        # Block's newest kv index >= the oldest position any q row in
        # this block may attend: (ik+1)*bk - 1 >= iq*bq - window + 1.
        run = run & ((ik + 1) * block_kv > iq * block_q - window + 1)
    return run


def _keep_mask(iq, ik, block_q, block_kv, window):
    """Elementwise causal(+window) keep mask for one score tile."""
    import jax
    import jax.numpy as jnp

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kv_pos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    keep = q_pos >= kv_pos
    if window is not None:
        keep = keep & (q_pos - kv_pos < window)
    return keep


# The band: the blocks `_run_window` lets run for one q-block (or, in
# dkv, for one kv-block) are contiguous, so each kernel's innermost grid
# axis spans only the widest such run, and inner step j names block
# first + j. The spans below are `_run_window` solved for the other
# index, on program ids, in the kernels and in the index maps alike.
# They bind `lax` primitives directly: a kernel and its index maps
# evaluate a span a dozen times a layer, and each `jnp` operator on a
# tracer is a jit lookup (it showed as two seconds of set-up).


def _kv_span(iq, block_q, block_kv, causal, window):
    """Oldest and newest kv-block that q-block ``iq`` attends; None
    without a causal mask (every block, in the grid's own order)."""
    from jax import lax

    if not causal:
        return None
    row0 = lax.mul(iq, block_q)
    last = lax.div(lax.add(row0, block_q - 1), block_kv)
    if window is None:
        return 0, last
    return lax.div(lax.max(lax.sub(row0, window - 1), 0), block_kv), last


def _q_span(ik, block_q, block_kv, n_q, causal, window):
    """Earliest and latest q-block that attends kv-block ``ik``; None
    without a causal mask."""
    from jax import lax

    if not causal:
        return None
    col0 = lax.mul(ik, block_kv)
    first = lax.div(col0, block_q)
    if window is None:
        return first, n_q - 1
    return first, lax.min(
        lax.div(lax.add(col0, block_kv + window - 2), block_q), n_q - 1)


def _band_block(j, span):
    """Inner grid step ``j`` of a band -> (block index, live). A step
    past the end of a short span (early rows of a window, steps above
    the causal diagonal) repeats the span's last block: its index equals
    its neighbour's, so the pipeline fetches nothing, and ``live`` is
    False, so nothing is computed. With no span, step j is block j."""
    from jax import lax

    if span is None:
        return j, True
    first, last = span
    at = lax.add(j, first)
    return lax.min(at, last), lax.le(at, last)


def _grid_extents(n_q, n_kv, block_q, block_kv, causal, window):
    """(fold of the q-blocks' rows, fold of the kv-blocks' rows, block
    pairs that run), from `_run_window` itself over all pairs. A fold is
    `_fold_of` the blocks each row runs, its triangle first: the
    q-blocks in order, the kv-blocks from the last."""
    import numpy as np

    if not causal:
        return (0, 0, n_kv), (0, 0, n_q), n_q * n_kv
    run = _run_window(np.arange(n_q)[:, None], np.arange(n_kv)[None, :],
                      block_q, block_kv, causal, window)
    return (_fold_of(run.sum(axis=1)), _fold_of(run.sum(axis=0)[::-1]),
            int(run.sum()))


# The fold: where the first T rows of a causal band span 1, 2, ..., T
# blocks (the triangle) and every later row spans T + 1, row p pairs
# with row T - 1 - p into one folded row of exactly T + 1 steps, so no
# inner step lies above the diagonal: a full causal layer of n blocks
# (T = n, no later rows) makes n(n+1)/2 steps a head for n^2. A folded
# row runs its first row's blocks, then its mate's; each half
# accumulates its own output block in ascending order, as the band
# did. The rows past the triangle keep their order, one to a folded
# row. An odd triangle leaves its middle row alone, with its idle
# steps; one of a single row (window 512 at 512-blocks) folds nothing
# and keeps the band.


def _fold_of(lengths):
    """(pairs, odd, width) for rows running ``lengths`` blocks, the
    triangle's rows first: the folded grid is ``len(lengths) - pairs``
    rows of ``width`` steps. ``pairs`` 0 is the band, its width the
    widest row. Checked on the lengths alone, since a step names its
    block as its row's span's first plus its offset in the half."""
    import numpy as np

    lengths = np.asarray(lengths)
    n = len(lengths)
    tri = np.arange(1, n + 1)
    fits = [t for t in range(n + 1)
            if (lengths[:t] == tri[:t]).all() and (lengths[t:] == t + 1).all()]
    for t in sorted(fits, key=lambda t: t % 2):      # even triangles first
        if t // 2:
            return t // 2, t % 2, t + 1
    return 0, 0, int(lengths.max())


def _fold_step(pr, j, fold):
    """Folded row ``pr``, inner step ``j`` -> (row, offset in the row's
    span, the first step of the row's half, its last step, in the
    second half)."""
    from jax import lax

    pairs, odd, width = fold
    first, last = lax.eq(j, 0), lax.eq(j, width - 1)
    if not pairs:
        return pr, j, first, last, False
    paired = lax.lt(pr, pairs)
    mate = lax.bitwise_and(paired, lax.gt(j, pr))   # second half of a pair
    alone = lax.add(pr, pairs)                      # rows past the triangle
    if odd:
        alone = lax.select(lax.eq(pr, pairs), pr, alone)
    row = lax.select(paired, lax.select(mate, lax.sub(2 * pairs + odd - 1, pr),
                                        pr), alone)
    turn = lax.add(pr, 1)                           # the mate's first step
    return (row, lax.select(mate, lax.sub(j, turn), j),
            lax.bitwise_or(first, lax.bitwise_and(paired, lax.eq(j, turn))),
            lax.bitwise_or(last, lax.bitwise_and(paired, lax.eq(j, pr))),
            mate)


def _q_sweep(pr, j, fold, block_q, block_kv, causal, window):
    """fwd / dq grid step -> (q-block, kv-block, live, first, last)."""
    iq, t, first, last, _ = _fold_step(pr, j, fold)
    ik, live = _band_block(t, _kv_span(iq, block_q, block_kv, causal, window))
    return iq, ik, live, first, last


def _kv_sweep(pr, j, fold, n_q, n_kv, block_q, block_kv, causal, window):
    """dkv grid step -> (kv-block, q-block, live, first, last, in the
    second half). A folded grid takes the kv-blocks from the last,
    whose spans are the triangle."""
    from jax import lax

    row, t, first, last, mate = _fold_step(pr, j, fold)
    ik = lax.sub(n_kv - 1, row) if fold[0] else row
    iq, live = _band_block(
        t, _q_span(ik, block_q, block_kv, n_q, causal, window))
    return ik, iq, live, first, last, mate


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                acc_ref, *, block_q: int, block_kv: int, fold,
                causal: bool, scale: float, window=None):
    """One (head, folded row, step) grid step.

    Grid = (heads, folded rows, width), a folded row's kv-blocks
    innermost (`_q_sweep`): the VMEM scratch accumulators (m, l, acc)
    persist across the kv sweep of one (head, q-block) and are
    re-initialized at its first step. At its last step the normalized
    output block and the logsumexp (the backward residual) are written
    once.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    iq, ik, live, first, last = _q_sweep(
        pl.program_id(1), pl.program_id(2), fold, block_q, block_kv,
        causal, window)

    @pl.when(first)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: KV blocks strictly above the diagonal contribute nothing;
    # a sliding window also skips blocks entirely older than the
    # window. (Skipped BLOCKS; boundary blocks mask elementwise.)
    run = live & _run_window(iq, ik, block_q, block_kv, causal, window)

    @pl.when(run)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)            # (block_q, d)
        k = k_ref[0].astype(jnp.float32)            # (block_kv, d)
        v = v_ref[0].astype(jnp.float32)            # (block_kv, dv)
        s = jax.lax.dot_general(                     # (block_q, block_kv)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        keep = None
        if causal:
            keep = _keep_mask(iq, ik, block_q, block_kv, window)
            s = jnp.where(keep, s, _NEG_INF)

        m_prev = m_ref[:]                            # (block_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # (block_q, block_kv)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        corr = jnp.exp(m_prev - m_new)               # (block_q, 1)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(last)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)         # fully-masked rows
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        # logsumexp residual for the backward pass: exp(s - lse) is the
        # already-normalized softmax weight. Stored as a (block_q, 1)
        # column — the layout the statistics already have — so no
        # sublane-to-lane relayout is asked of Mosaic.
        lse_ref[0] = m_ref[:] + jnp.log(safe_l)


# ---------------------------------------------------------------------------
# Backward (FlashAttention-2 recurrence)
#
#   p_ij   = exp(s_ij - lse_i)                (softmax weights, normalized)
#   dv_j   = sum_i p_ij^T do_i
#   dp_ij  = do_i v_j^T
#   ds_ij  = p_ij * (dp_ij - delta_i),  delta_i = rowsum(do_i * o_i)
#   dq_i   = scale * sum_j ds_ij k_j
#   dk_j   = scale * sum_i ds_ij^T q_i
# ---------------------------------------------------------------------------


def _bwd_p_ds(q, k, v, do, lse, delta, iq, ik, *, block_q, block_kv,
              causal, scale, window=None):
    """Shared recompute: softmax weights p and score grads ds for one
    (q-block, kv-block) pair, all f32. ``lse`` and ``delta`` are
    (block_q, 1) columns."""
    import jax
    import jax.numpy as jnp

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    p = jnp.exp(s - lse)                             # (bq, bkv)
    if causal:
        p = jnp.where(_keep_mask(iq, ik, block_q, block_kv, window),
                      p, 0.0)
    dp = jax.lax.dot_general(                        # do @ v^T
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta)
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, block_q: int, block_kv: int,
                   fold, causal: bool, scale: float, window=None):
    """Grid (heads, folded rows, width), as the forward's: accumulate dq
    for one q-block across its KV sweep."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    iq, ik, live, first, last = _q_sweep(
        pl.program_id(1), pl.program_id(2), fold, block_q, block_kv,
        causal, window)

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = live & _run_window(iq, ik, block_q, block_kv, causal, window)

    @pl.when(run)
    def _accumulate():
        import jax

        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        _, ds = _bwd_p_ds(q, k, v, do, lse_ref[0], delta_ref[0], iq, ik,
                          block_q=block_q, block_kv=block_kv,
                          causal=causal, scale=scale, window=window)
        dq_acc[:] += jax.lax.dot_general(            # ds @ k
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(last)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                    block_kv: int, n_q: int, n_kv: int, fold, group: int,
                    causal: bool, scale: float, window=None):
    """Grid (kv_heads, folded rows, group, width), (group, folded row's
    q-blocks) innermost: accumulate dk and dv for one kv-block across
    the Q sweep of EVERY query head sharing that KV head (GQA: ``group``
    query heads per KV head; MHA is group == 1). A folded row holds two
    kv-blocks, which the group's sweeps take turns at, so each half
    keeps its accumulators in a slot of its own (two slots where a fold
    meets a group): a kv-block's sums run in the band's order, and it is
    written at its half's last step of the group's last head, where the
    output index map (`_build_calls`) turns to it — the TPU
    accumulation-grid rule that keeps each output block's revisits
    contiguous."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    g = pl.program_id(2)
    ik, iq, live, first, last, mate = _kv_sweep(
        pl.program_id(1), pl.program_id(3), fold, n_q, n_kv, block_q,
        block_kv, causal, window)
    slot = (lax.convert_element_type(mate, jnp.int32)
            if dk_acc.shape[0] == 2 else 0)

    @pl.when(first & (g == 0))
    def _init():
        dk_acc[slot] = jnp.zeros(dk_acc.shape[1:], dk_acc.dtype)
        dv_acc[slot] = jnp.zeros(dv_acc.shape[1:], dv_acc.dtype)

    run = live & _run_window(iq, ik, block_q, block_kv, causal, window)

    @pl.when(run)
    def _accumulate():
        import jax

        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, ds = _bwd_p_ds(q, k, v, do, lse_ref[0], delta_ref[0], iq, ik,
                          block_q=block_q, block_kv=block_kv,
                          causal=causal, scale=scale, window=window)
        dv_acc[slot] += jax.lax.dot_general(         # p^T @ do
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[slot] += jax.lax.dot_general(         # ds^T @ q
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(last & (g == group - 1))
    def _finalize():
        dk_ref[0] = (dk_acc[slot] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[slot].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Builder / public API
# ---------------------------------------------------------------------------


def _pick_block(s: int, want: int) -> int:
    """Largest divisor of ``s`` that is <= want and a multiple of 128
    (lane tiling), falling back to s itself for short sequences."""
    if s <= want:
        return s
    b = (want // 128) * 128
    while b >= 128:
        if s % b == 0:
            return b
        b -= 128
    return s  # no aligned divisor: single block (caller gates size)


def flash_attention(q, k, v, *, causal: bool = False,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: bool = False, window=None):
    """Exact attention, O(S) memory, differentiable. q:
    (S, heads, head_dim); k: (S, kv_heads, head_dim) where kv_heads
    divides heads — kv_heads < heads is grouped-query attention (each
    group of heads/kv_heads query heads shares one KV head; the kernel
    index maps do the sharing, so repeated KV never materializes); v:
    (S, kv_heads, v_dim), where the value width may differ from the
    query/key width (latent attention). Returns (S, heads, v_dim) in
    q's dtype; the scale is ``head_dim ** -0.5``.

    ``window`` (requires ``causal=True``) restricts every position to
    the last ``window`` tokens (self included): the kernels' grids hold
    only the band of KV blocks inside the window, so compute and the
    blocks moved drop from O(S^2) to O(S*window) — the standard
    local-attention layer of sliding-window transformers. Composes with
    GQA.

    The kernels compile through Mosaic and need a TPU: off-TPU the
    default raises (Pallas refuses to compile for the platform).
    ``interpret=True`` is the caller's explicit request for the Pallas
    interpreter (CPU-testable, slow) — the test suite passes it; the
    library never selects it. The compiled program is cached per
    (shape, dtype, flags).
    """
    fn = _build(q.shape, str(q.dtype), causal, block_q, block_kv,
                interpret, _kv_heads_of(q, k), window, _kv_len_of(q, k),
                _v_dim_of(q, v))
    return fn(q, k, v)


def _kv_heads_of(q, k):
    """None for plain MHA (cache-key stability), kv head count for GQA."""
    return None if k.shape[1] == q.shape[1] else k.shape[1]


def _kv_len_of(q, k):
    """None where keys and queries are as many (cache-key stability),
    else the number of keys: a non-causal call may be rectangular (the
    zigzag ring attends all its rows to half a visiting block, and half
    its rows to a whole one)."""
    return None if k.shape[0] == q.shape[0] else k.shape[0]


def _v_dim_of(q, v):
    """None where values are as wide as queries and keys (cache-key
    stability: the programs of equal widths are built as they always
    were), else the value width."""
    return None if v.shape[-1] == q.shape[-1] else v.shape[-1]


def flash_attention_lse(q, k, v, *, causal: bool = False,
                        block_q: int = 512, block_kv: int = 512,
                        interpret: bool = False, window=None):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp ``(heads, S) float32`` — the residual that makes partial
    attentions MERGEABLE (ring composition:
    :func:`fiber_tpu.ops.ring_attention.ring_attention_local` with
    ``local="flash"`` combines per-rotation (out, lse) pairs exactly).

    Differentiable in BOTH outputs: the lse cotangent enters the
    FlashAttention-2 backward as ``ds += dlse * p``, which folds into
    the existing delta term (``delta - dlse``) at zero extra kernel
    cost. Supports GQA and ``window`` like :func:`flash_attention` —
    but note that with a window the lse is the WINDOWED logsumexp, so
    merging partials is only exact over KV sets that respect the same
    window (the ring composition does not pass a window).
    """
    fn = _build_lse(q.shape, str(q.dtype), causal, block_q, block_kv,
                    interpret, _kv_heads_of(q, k), window,
                    _kv_len_of(q, k), _v_dim_of(q, v))
    return fn(q, k, v)


def flash_attention_lse_bwd(q, k, v, out, lse, dout, dlse, *,
                            causal: bool = False, block_q: int = 512,
                            block_kv: int = 512,
                            interpret: bool = False):
    """:func:`flash_attention_lse`'s backward pass from that call's own
    ``(out, lse)`` and their cotangents: ``(dq, dk, dv)``. For a caller
    with a VJP of its own that already keeps ``out`` and ``lse`` (the
    ring keeps each rotation's partial once, for its merge and for
    this); everyone else differentiates :func:`flash_attention_lse`."""
    _, bwd = _cores(q.shape, str(q.dtype), causal, block_q, block_kv,
                    interpret, _kv_heads_of(q, k), None, _kv_len_of(q, k),
                    _v_dim_of(q, v))
    return bwd(q, k, v, out, lse, dout, dlse)


@functools.lru_cache(maxsize=64)
def _build_calls(shape, dtype, causal, block_q, block_kv, interpret,
                 kv_heads=None, window=None, kv_len=None, v_dim=None):
    """The three pallas_call programs (fwd, dq, dkv) for one config —
    shared by the out-only and the (out, lse) entry points.

    ``window`` (causal only) restricts attention to the last
    ``window`` positions. Each kernel's inner grid axes hold only the
    blocks that `_run_window` lets run, so blocks outside every q row's
    window cost neither arithmetic nor a copy: O(S*window) for O(S^2).
    Where a causal band's rows fold (`_fold_of`: full causal, or a
    window of two blocks or more, at equal blocks) every step runs: 136
    a head at 8,192 tokens and 512-blocks, 108 for window 4,096.
    Otherwise the innermost axis spans the band (2 kv-blocks for window
    512), its short rows' steps idle. A non-causal call has the square
    grid.

    ``kv_heads`` < heads enables grouped-query attention: K/V carry
    kv_heads heads and every group of ``heads // kv_heads`` query heads
    reads the same KV block (the index maps do the sharing — no
    repeated KV ever materializes); dk/dv accumulate across the group
    inside the kernel.

    ``kv_len`` is the number of keys where it is not the number of
    queries (non-causal only: the causal mask is in local coordinates
    and takes row i for key i).

    ``v_dim`` is the width of values and output where it is not that of
    queries and keys (``shape[2]``): v, o and do travel in blocks of
    that width, the forward's accumulator and dv's are that wide, and
    the scale stays the query/key width's. None builds the programs of
    one width exactly as before."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from fiber_tpu.telemetry import device as device_telemetry
    from fiber_tpu.utils.jaxcompat import ensure_compile_cache

    ensure_compile_cache()
    s, h, d = shape
    kvh = kv_heads or h
    if kvh < 1 or h % kvh:
        raise ValueError(
            f"kv_heads {kvh} must be >= 1 and divide heads {h}")
    group = h // kvh
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    s_kv = s if kv_len is None else kv_len
    if causal and s_kv != s:
        raise ValueError(
            f"causal attention of {s} queries over {s_kv} keys: the "
            "mask takes row i for key i and needs as many of each")
    bq = _pick_block(s, block_q)
    bk = _pick_block(s_kv, block_kv)
    n_q = s // bq
    n_kv = s_kv // bk
    scale = 1.0 / (d ** 0.5)
    dv = d if v_dim is None else v_dim

    fold_q, fold_kv, run_pairs = _grid_extents(
        n_q, n_kv, bq, bk, causal, window)
    grid_q = (n_q - fold_q[0], fold_q[2])
    grid_kv = (n_kv - fold_kv[0], fold_kv[2])
    # flash_grid_steps{kernel, grid, state}: the inner steps one head
    # makes (dkv: one kv-head, its group's sweeps), run and idle.
    for kernel, fold, run, steps in (
            ("flash_attn_fwd", fold_q, run_pairs, grid_q[0] * grid_q[1]),
            ("flash_attn_dq", fold_q, run_pairs, grid_q[0] * grid_q[1]),
            ("flash_attn_dkv", fold_kv, group * run_pairs,
             group * grid_kv[0] * grid_kv[1])):
        kind = "folded" if fold[0] else "band" if causal else "square"
        device_telemetry.flash_grid_built(kernel, kind, run, steps - run)

    def q_sweep(pr, j):
        return _q_sweep(pr, j, fold_q, bq, bk, causal, window)

    def q_rows(width):
        return pl.BlockSpec((1, bq, width),
                            lambda ih, pr, j: (ih, q_sweep(pr, j)[0], 0))

    def kv_rows(width):
        return pl.BlockSpec(
            (1, bk, width),
            lambda ih, pr, j: (ih // group, q_sweep(pr, j)[1], 0))

    qkv_spec_q, qkv_spec_k = q_rows(d), kv_rows(d)
    o_spec, v_spec = ((qkv_spec_q, qkv_spec_k) if dv == d
                      else (q_rows(dv), kv_rows(dv)))
    # Per-row statistics (lse, delta) travel as (h, s, 1) columns:
    # Mosaic wants the last two block dims divisible by (8, 128) or
    # equal to the array's, which a (1, bq) row block of an (h, s)
    # array is not — and the kernels consume them as columns anyway.
    row_spec_q = q_rows(1)

    fwd_call = pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=bq, block_kv=bk,
                          fold=fold_q, causal=causal, scale=scale,
                          window=window),
        grid=(h, *grid_q),
        in_specs=[qkv_spec_q, qkv_spec_k, v_spec],
        out_specs=[o_spec, row_spec_q],
        out_shape=[jax.ShapeDtypeStruct((h, s, dv), dtype),
                   jax.ShapeDtypeStruct((h, s, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # denominator l
            pltpu.VMEM((bq, dv), jnp.float32),   # numerator acc
        ],
        interpret=interpret,
        name="flash_attn_fwd",
    )

    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=bq, block_kv=bk,
                          fold=fold_q, causal=causal, scale=scale,
                          window=window),
        grid=(h, *grid_q),
        in_specs=[qkv_spec_q, qkv_spec_k, v_spec, o_spec,
                  row_spec_q, row_spec_q],
        out_specs=qkv_spec_q,
        out_shape=jax.ShapeDtypeStruct((h, s, d), dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attn_dq",
    )

    # dkv grid is (kv_heads, folded rows, group, width): program ids
    # land as (ikv, pr, g, j); (g, j) innermost so each (ikv, ik) output
    # block's revisits are contiguous. A folded row's two kv-blocks take
    # turns over the group's heads, so where a fold meets a group its
    # output block stays the first half's until the group's last head
    # reaches the second.
    def kv_sweep(pr, j):
        return _kv_sweep(pr, j, fold_kv, n_q, n_kv, bq, bk, causal, window)

    def dkv_q_rows(width):
        return pl.BlockSpec(
            (1, bq, width),
            lambda ikv, pr, g, j: (ikv * group + g, kv_sweep(pr, j)[1], 0))

    def dkv_k_rows(width):
        return pl.BlockSpec(
            (1, bk, width), lambda ikv, pr, g, j: (ikv, kv_sweep(pr, j)[0], 0))

    slots = 2 if fold_kv[0] and group > 1 else 1

    def dkv_out_rows(width):
        if slots == 1:
            return dkv_k_rows(width)
        return pl.BlockSpec(
            (1, bk, width),
            lambda ikv, pr, g, j: (ikv, kv_sweep(pr, lax.select(
                lax.eq(g, group - 1), j, lax.full_like(j, 0)))[0], 0))

    dkv_q_spec, dkv_k_spec = dkv_q_rows(d), dkv_k_rows(d)
    dkv_do_spec, dkv_v_spec = ((dkv_q_spec, dkv_k_spec) if dv == d
                               else (dkv_q_rows(dv), dkv_k_rows(dv)))
    dkv_row_spec = dkv_q_rows(1)
    dkv_call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, block_kv=bk,
                          n_q=n_q, n_kv=n_kv, fold=fold_kv, group=group,
                          causal=causal, scale=scale, window=window),
        grid=(kvh, grid_kv[0], group, grid_kv[1]),
        in_specs=[dkv_q_spec, dkv_k_spec, dkv_v_spec, dkv_do_spec,
                  dkv_row_spec, dkv_row_spec],
        out_specs=[dkv_out_rows(d), dkv_out_rows(dv)],
        out_shape=[jax.ShapeDtypeStruct((kvh, s_kv, d), dtype),
                   jax.ShapeDtypeStruct((kvh, s_kv, dv), dtype)],
        scratch_shapes=[pltpu.VMEM((slots, bk, d), jnp.float32),
                        pltpu.VMEM((slots, bk, dv), jnp.float32)],
        interpret=interpret,
        name="flash_attn_dkv",
    )
    return fwd_call, dq_call, dkv_call


@functools.lru_cache(maxsize=64)
def _cores(shape, dtype, causal, block_q, block_kv, interpret,
           kv_heads=None, window=None, kv_len=None, v_dim=None):
    """(forward, backward) over the public (S, H, D) layout for one
    config: ``forward(q, k, v) -> (out, lse)`` and ``backward(q, k, v,
    out, lse, dout, dlse) -> (dq, dk, dv)``."""
    import jax.numpy as jnp

    fwd_call, dq_call, dkv_call = _build_calls(
        shape, dtype, causal, block_q, block_kv, interpret, kv_heads,
        window, kv_len, v_dim)

    def _fwd_core(q, k, v):
        """(S,H,D) API -> (H,S,D) kernels and back; the kernel's
        (H,S,1) logsumexp column leaves as the public (H,S)."""
        out, lse = fwd_call(jnp.swapaxes(q, 0, 1), jnp.swapaxes(k, 0, 1),
                            jnp.swapaxes(v, 0, 1))
        return jnp.swapaxes(out, 0, 1), lse[..., 0]

    def _bwd_core(q, k, v, out, lse, dout, dlse):
        # ds_ij = p_ij * (dp_ij - delta_i + dlse_i): the lse cotangent
        # is exactly a -dlse shift of delta (d lse_i / d s_ij = p_ij),
        # so both backward kernels run unchanged.
        delta = jnp.einsum(
            "shd,shd->hs", dout.astype(jnp.float32),
            out.astype(jnp.float32))
        if dlse is not None:
            delta = delta - dlse.astype(jnp.float32)
        qt, kt, vt = (jnp.swapaxes(x, 0, 1) for x in (q, k, v))
        dot = jnp.swapaxes(dout, 0, 1)
        lse, delta = lse[..., None], delta[..., None]   # kernel columns
        dq = dq_call(qt, kt, vt, dot, lse, delta)
        dk, dv = dkv_call(qt, kt, vt, dot, lse, delta)
        return tuple(jnp.swapaxes(g, 0, 1) for g in (dq, dk, dv))

    return _fwd_core, _bwd_core


def _make_attn(shape, dtype, causal, block_q, block_kv, interpret,
               with_lse: bool, kv_heads=None, window=None, kv_len=None,
               v_dim=None):
    import jax
    from jax.ad_checkpoint import checkpoint_name

    _fwd_core, _bwd_core = _cores(
        shape, dtype, causal, block_q, block_kv, interpret, kv_heads,
        window, kv_len, v_dim)

    def _named_fwd(q, k, v):
        """The forward rules' kernel call: the two residuals the kernel
        produced carry KEPT_NAMES (q, k, v none)."""
        out, lse = _fwd_core(q, k, v)
        return (checkpoint_name(out, KEPT_NAMES[0]),
                checkpoint_name(lse, KEPT_NAMES[1]))

    if not with_lse:
        @jax.custom_vjp
        def attn(q, k, v):
            out, _ = _fwd_core(q, k, v)
            return out

        def attn_fwd(q, k, v):
            out, lse = _named_fwd(q, k, v)
            return out, (q, k, v, out, lse)

        def attn_bwd(res, dout):
            q, k, v, out, lse = res
            return _bwd_core(q, k, v, out, lse, dout, None)

        attn.defvjp(attn_fwd, attn_bwd)
        return jax.jit(attn)

    @jax.custom_vjp
    def attn_lse(q, k, v):
        return _fwd_core(q, k, v)

    def attn_lse_fwd(q, k, v):
        out, lse = _named_fwd(q, k, v)
        return (out, lse), (q, k, v, out, lse)

    def attn_lse_bwd(res, cots):
        q, k, v, out, lse = res
        dout, dlse = cots
        return _bwd_core(q, k, v, out, lse, dout, dlse)

    attn_lse.defvjp(attn_lse_fwd, attn_lse_bwd)
    return jax.jit(attn_lse)


@functools.lru_cache(maxsize=64)
def _build(shape, dtype, causal, block_q, block_kv, interpret,
           kv_heads=None, window=None, kv_len=None, v_dim=None):
    return _make_attn(shape, dtype, causal, block_q, block_kv,
                      interpret, with_lse=False, kv_heads=kv_heads,
                      window=window, kv_len=kv_len, v_dim=v_dim)


@functools.lru_cache(maxsize=64)
def _build_lse(shape, dtype, causal, block_q, block_kv, interpret,
               kv_heads=None, window=None, kv_len=None, v_dim=None):
    return _make_attn(shape, dtype, causal, block_q, block_kv,
                      interpret, with_lse=True, kv_heads=kv_heads,
                      window=window, kv_len=kv_len, v_dim=v_dim)
