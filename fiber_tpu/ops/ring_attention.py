"""Ring attention: exact attention over sequences sharded across the mesh.

The reference framework has no sequence parallelism of any kind (SURVEY.md
§5 — it predates it and is not a model trainer). fiber_tpu provides it as
a first-class device-plane op so long-context workloads scale the same way
the rest of the framework does: shard the sequence over the ``pool`` axis
and let the KV blocks ride ICI.

Algorithm (Ring Attention / blockwise online softmax): each device owns a
query block and its local KV block; KV blocks rotate around the ring via
``lax.ppermute`` while every device maintains an online-softmax
accumulator (running max ``m``, denominator ``l``, numerator ``o``) — so
the full (S, S) score matrix never materializes anywhere and peak memory
per device is O(S_local · S_local) instead of O(S²). After ``n_devices``
rotations the result equals exact softmax attention.

Causal masking uses global positions derived from ``axis_index``, so the
mask stays correct as blocks rotate.

The order of the rows on the chips (:func:`ring_order`). Contiguous
blocks leave a causal ring unbalanced: chip ``i`` attends ``i`` whole
blocks and half of its own while the ring steps in lockstep, so every
rotation costs what the last chip's costs (3.5 block-units on four
chips for a mean of 2). A causal ring over ``n > 1`` chips whose length
is whole in ``2n`` half-blocks therefore holds them in zigzag: chip
``i`` has half-blocks ``i`` and ``2n - 1 - i``, early half first, and
every chip attends two half-block pairs in every rotation
(:func:`ring_schedule`). Nobody chooses the order: it follows from
(length, chips, causal). :func:`ring_attention` takes and returns the
natural order and reorders at its own door. The LM trainer
(``models/transformer.py`` ``BlockLM``) orders the token ids instead,
once a step, gives ropes and the position table the rows' true
positions, calls :func:`ring_attention_ordered`, and restores the
natural order only where it returns by position (``apply``,
``token_losses``, ``routing``): its loss is a mean and needs none.
:func:`ring_attention_local`, the body for a caller's own
``shard_map``, the non-causal ring and Ulysses keep contiguous blocks.
"""

from __future__ import annotations

from typing import Optional


def _acc_dtype(dtype):
    """Softmax-statistic dtype: at least f32 (advisor, round 3: in-dtype
    accumulators let the bf16 denominator degrade in 8 mantissa bits at
    long context), but never narrower than the input — f64 inputs keep
    f64 statistics (``preferred_element_type`` rejects narrowing)."""
    import jax.numpy as jnp

    return jnp.promote_types(dtype, jnp.float32)


def _block_attn(q, k, mask):
    """Scores for one (query-block, kv-block) pair.

    q: (sq, h, d)   k: (skv, h, d)   mask: (sq, skv) or None
    returns s: (h, sq, skv) in the accumulator dtype (>= f32) — the QK
    matmul still runs on the MXU in the input dtype but accumulates
    wide, and every downstream softmax statistic stays wide.
    """
    import jax.numpy as jnp

    acc = _acc_dtype(q.dtype)
    d = q.shape[-1]
    s = jnp.einsum("qhd,khd->hqk", q, k, preferred_element_type=acc)
    s = s / jnp.sqrt(jnp.asarray(d, acc))
    if mask is not None:
        s = jnp.where(mask[None, :, :], s, jnp.finfo(s.dtype).min)
    return s


_compiled_cache: dict = {}

#: Max kv-chunk a device materializes scores against at once (tokens).
_KV_CHUNK = 1024


def ring_order(seq: int, n_dev: int, causal: bool):
    """The positions of a sequence's rows as the ring holds them:
    ``order[r]`` is the position of row ``r`` and chip ``i`` has rows
    ``[i * seq / n_dev, (i + 1) * seq / n_dev)``. A causal ring over
    more than one chip whose length is whole in ``2 n_dev`` half-blocks
    holds them in zigzag (chip ``i``: half-blocks ``i`` and ``2 n_dev -
    1 - i``, early half first), as a numpy array; any other ring keeps
    the natural order, which is ``None``. ``numpy.argsort(order)`` puts
    rows back by position."""
    if not causal or n_dev < 2 or seq % (2 * n_dev):
        return None
    import numpy as np

    halves = np.arange(seq).reshape(2 * n_dev, seq // (2 * n_dev))
    return np.stack([halves[:n_dev], halves[:n_dev - 1:-1]],
                    axis=1).reshape(seq)


def ring_schedule(seq: int, n_dev: int, causal: bool):
    """(layout, pairs): the order :func:`ring_order` gives a ring of
    this length (``"zigzag"`` or ``"contiguous"``) and, from the mask
    alone, the (query, key) pairs that chip ``i`` attends in rotation
    ``t`` (``pairs[i][t]``; rotation ``t`` brings the keys of chip ``(i
    - t) % n_dev``; 0 is a rotation the flash engine skips). What the
    ``ring_rotations_traced`` counter reads; no device needed."""
    blk = seq // n_dev
    diagonal = blk * (blk + 1) // 2
    zigzag = ring_order(seq, n_dev, causal) is not None

    def attended(chip, src):
        if not causal:
            return blk * blk
        if src == chip:
            return diagonal
        if zigzag:      # all rows on an early half, or late rows on all
            return blk * (blk // 2)
        return blk * blk if src < chip else 0

    return ("zigzag" if zigzag else "contiguous",
            [[attended(i, (i - t) % n_dev) for t in range(n_dev)]
             for i in range(n_dev)])


def _local_positions(my, sq: int, n_dev: int, zigzag: bool):
    """Global positions of the ``sq`` rows chip ``my`` (traced or not)
    holds under the ring's order."""
    import jax.numpy as jnp

    if not zigzag:
        return my * sq + jnp.arange(sq)
    half = sq // 2
    return jnp.concatenate([
        my * half + jnp.arange(half),
        (2 * n_dev - 1 - my) * half + jnp.arange(half)])


def _accumulate_block(q_blk, q_pos, k_cur, v_cur, kv_pos, m, l, o,
                      causal: bool):
    """Online-softmax update of (m, l, o) with one KV block, internally
    chunked so the materialized score slab is bounded at
    (h, sq, _KV_CHUNK) — shared by the ring body (per rotation) and
    :func:`blockwise_attention` (single block = whole sequence).

    q_blk: (sq, h, d); k_cur/v_cur: (skv, h, d); q_pos: (sq,) and
    kv_pos: (skv,) global positions of the query and key rows.
    m, l: (h, sq); o: (sq, h, d) — all in ``_acc_dtype`` (>= f32),
    allocated by :func:`_acc_init`: with bf16 inputs the denominator l
    sums tens of thousands of terms, which 8 mantissa bits cannot carry
    (the Pallas kernel accumulates f32 for the same reason). The p·V
    matmul runs in the value dtype on the MXU but accumulates wide.
    """
    import jax
    import jax.numpy as jnp

    acc = _acc_dtype(q_blk.dtype)

    # Rematerialized: under jax AD the backward pass recomputes a
    # chunk's scores from (k_c, v_c, m, l, o) instead of keeping every
    # chunk's (h, sq, chunk) score and weight slabs alive until then.
    # Without it the O(sq x chunk) bound holds for the forward only — a
    # 16k-token TinyLM train step asked one v5e chip for 90 GB of HBM.
    @jax.checkpoint
    def one_chunk(k_c, v_c, kv_pos, m, l, o):
        mask = None
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]
        s = _block_attn(q_blk, k_c, mask)            # (h, sq, skv) wide
        m_new = jnp.maximum(m, s.max(axis=-1))
        # Guard -inf - -inf (fully masked rows) producing NaN.
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])           # wide
        if mask is not None:
            p = jnp.where(mask[None, :, :], p, 0.0)
        corr = jnp.where(
            jnp.isinf(m), 0.0, jnp.exp(m - m_safe)
        )                                            # (h, sq) wide
        l_new = l * corr + p.sum(axis=-1)
        o_corr = o * corr.transpose(1, 0)[:, :, None]
        o_new = o_corr + jnp.einsum(
            "hqk,khd->qhd", p.astype(v_c.dtype), v_c,
            preferred_element_type=acc,
        )
        return m_new, l_new, o_new

    skv = k_cur.shape[0]
    if skv <= _KV_CHUNK:
        return one_chunk(k_cur, v_cur, kv_pos, m, l, o)
    # Divisible prefix via scan; any remainder as one short tail chunk —
    # the O(sq x _KV_CHUNK) score bound must hold for ARBITRARY skv,
    # not just multiples (a 33k-token call must never silently fall
    # back to the full slab).
    n_chunks = skv // _KV_CHUNK
    main = n_chunks * _KV_CHUNK
    k_ch = k_cur[:main].reshape(n_chunks, _KV_CHUNK, *k_cur.shape[1:])
    v_ch = v_cur[:main].reshape(n_chunks, _KV_CHUNK, *v_cur.shape[1:])
    pos_ch = kv_pos[:main].reshape(n_chunks, _KV_CHUNK)

    def chunk_body(carry, inp):
        m, l, o = carry
        kc, vc, pos = inp
        return one_chunk(kc, vc, pos, m, l, o), None

    (m, l, o), _ = jax.lax.scan(
        chunk_body, (m, l, o), (k_ch, v_ch, pos_ch))
    if skv > main:
        m, l, o = one_chunk(k_cur[main:], v_cur[main:], kv_pos[main:],
                            m, l, o)
    return m, l, o


def _acc_init(q):
    """Fresh (m, l, o) online-softmax accumulators for a (sq, h, d)
    query block, in the wide statistic dtype."""
    import jax.numpy as jnp

    sq, h, _ = q.shape
    acc = _acc_dtype(q.dtype)
    m0 = jnp.full((h, sq), -jnp.inf, acc)
    l0 = jnp.zeros((h, sq), acc)
    o0 = jnp.zeros(q.shape, acc)
    return m0, l0, o0


def _acc_finalize(o, l, out_dtype):
    """o / l with fully-masked rows (l == 0) left as zeros, cast back to
    the caller-visible dtype."""
    import jax.numpy as jnp

    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l.transpose(1, 0)[:, :, None]).astype(out_dtype)


def blockwise_attention(q, k, v, causal: bool = False):
    """Exact single-device attention with the score slab bounded at
    (h, sq, _KV_CHUNK) — the memory-safe local plane for long context
    without a kernel (differentiable everywhere; on TPU the Pallas
    :func:`fiber_tpu.ops.pallas_attention.flash_attention` is the
    faster equivalent). q, k, v: (S, heads, head_dim)."""
    import jax.numpy as jnp

    sq = q.shape[0]
    q_pos = jnp.arange(sq)
    m0, l0, o0 = _acc_init(q)
    m, l, o = _accumulate_block(q, q_pos, k, v, jnp.arange(k.shape[0]),
                                m0, l0, o0, causal)
    return _acc_finalize(o, l, q.dtype)


def _merge_partials(o1, lse1, o2, lse2):
    """Exactly combine two partial attentions over disjoint KV sets.

    Each partial is (o: (sq, h, d) f32 — softmax-normalized over its own
    KV set, lse: (h, sq) f32 — that set's logsumexp). The merge is the
    standard flash rescaling; associative, so rotation order doesn't
    matter. A skipped contribution carries lse = -1e30, making its
    weight exp(-1e30 - m) == 0 (never NaN — the other side is finite
    because the diagonal block always contributes)."""
    import jax.numpy as jnp

    m = jnp.maximum(lse1, lse2)                     # (h, sq)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = w1 + w2
    w1t = (w1 / denom).transpose(1, 0)[:, :, None]  # (sq, h, 1)
    w2t = (w2 / denom).transpose(1, 0)[:, :, None]
    return o1 * w1t + o2 * w2t, m + jnp.log(denom)


def _kv_rotate(k_cur, v_cur, *, axis: str, n_dev: int,
               use_dma_ring: bool, interpret: bool):
    """One ring rotation of the KV pair. ``use_dma_ring=True`` swaps
    the synchronous ``ppermute`` pair for the Pallas async remote-DMA
    exchange (ops/dma_ring): both blocks' DMAs are in flight at once
    and the copy engine runs beside compute instead of serializing the
    program on each transfer. Forward-only (no VJP) — callers needing
    gradients keep the default. ``interpret=True`` runs the exchange
    in the Pallas interpreter; False compiles it (TPU only)."""
    import jax

    with jax.named_scope("ring.rotate"):
        if use_dma_ring:
            from fiber_tpu.ops.dma_ring import ring_exchange

            k_cur, v_cur = ring_exchange(
                (k_cur, v_cur), axis=axis, n_dev=n_dev,
                interpret=interpret)
            return k_cur, v_cur
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        return (jax.lax.ppermute(k_cur, axis, perm),
                jax.lax.ppermute(v_cur, axis, perm))


def _ring_flash_local(q_blk, k_blk, v_blk, *, axis: str, n_dev: int,
                      causal: bool, interpret: bool,
                      use_dma_ring: bool = False, zigzag: bool = False):
    """Ring attention with the Pallas flash kernel as the per-device
    block: each rotation runs flash over (local Q, visiting KV) and the
    (out, lse) partials merge exactly (:func:`_merge_partials`).

    Causality with rotating KV blocks is a THREE-WAY split on the
    visiting block's chip ``src`` — the kernel's own causal flag only
    knows local coordinates. The own block (``src == my``) runs the
    causal kernel in either order: in zigzag's local coordinates
    early-on-early is causal, late-on-early full, late-on-late causal
    and early-on-late masked, which is the causal mask of the chip's
    rows as they lie. A visiting block is past or future, picked at
    runtime by ``lax.cond`` on the traced index:

    - contiguous blocks: a fully-past block (``src < my``) runs the
      unmasked kernel, a fully-future block is skipped (lse = -1e30
      zeroes it in the merge), so the last chip computes in every
      rotation and the first in one;
    - zigzag (``zigzag=True``: the rows lie in :func:`ring_order`): for
      ``src < my`` both local halves come after ``src``'s early half
      and before its late half, so all rows attend ``k[:half]``
      unmasked; for ``src > my`` the early half sees nothing of ``src``
      and the late half comes after both of its halves, so rows
      ``[half:]`` attend all of ``k`` unmasked and the early rows carry
      lse = -1e30. Two half-block pairs on every chip in every
      rotation; nothing is skipped.

    Differentiable end to end: the own block through
    flash_attention_lse's custom VJP, a visiting block through one of
    its own (``visiting`` below).
    """
    import jax
    import jax.numpy as jnp

    from fiber_tpu.ops.pallas_attention import (
        flash_attention_lse, flash_attention_lse_bwd)

    sq, h, _ = q_blk.shape
    half = sq // 2
    my = jax.lax.axis_index(axis)

    def attend(q, k, v, causal):
        o, lse = flash_attention_lse(q, k, v, causal=causal,
                                     interpret=interpret)
        return o.astype(jnp.float32), lse

    def rows_padded(x, before=0, after=0):
        return jnp.pad(x, [(before, after)] + [(0, 0)] * (x.ndim - 1))

    def backward(q, k, v, o, lse, do, dlse):
        return flash_attention_lse_bwd(
            q, k, v, o.astype(q.dtype), lse, do.astype(q.dtype), dlse,
            interpret=interpret)

    # What a visiting block from chip ``src != my`` adds, as the
    # partial (o, lse) over all local rows, and its backward pass from
    # that partial. A past block (``src < my``) offers its early half's
    # keys in zigzag and all of them in contiguous blocks.
    past_keys = half if zigzag else sq

    def past_fwd(q, k, v):
        return attend(q, k[:past_keys], v[:past_keys], False)

    def past_bwd(q, k, v, o, lse, do, dlse):
        dq, dk, dv = backward(q, k[:past_keys], v[:past_keys], o, lse,
                              do, dlse)
        return (dq, rows_padded(dk, after=sq - past_keys),
                rows_padded(dv, after=sq - past_keys))

    def future_fwd(q, k, v):
        if not zigzag:          # no row sees any key: lse = -1e30
            return (jnp.zeros(q.shape, jnp.float32),    # zeroes it in
                    jnp.full((h, sq), -1e30, jnp.float32))  # the merge
        o, lse = attend(q[half:], k, v, False)
        return (rows_padded(o, before=half),
                jnp.pad(lse, [(0, 0), (half, 0)], constant_values=-1e30))

    def future_bwd(q, k, v, o, lse, do, dlse):
        if not zigzag:
            return jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)
        dq, dk, dv = backward(q[half:], k, v, o[half:], lse[:, half:],
                              do[half:], dlse[:, half:])
        return rows_padded(dq, before=half), dk, dv

    # A VJP of the rotation's own: differentiated through ``lax.cond``
    # each branch would keep its kernel's q, out and lse as residuals
    # of its own beside the partial the merge keeps (1.5 more arrays of
    # the rows' size a rotation and layer); here the partial is the one
    # residual, and each branch's backward pass reads its part of it.
    @jax.custom_vjp
    def visiting(q, k_cur, v_cur, past):
        return jax.lax.cond(past, past_fwd, future_fwd, q, k_cur, v_cur)

    def visiting_fwd(q, k_cur, v_cur, past):
        o, lse = visiting(q, k_cur, v_cur, past)
        return (o, lse), (q, k_cur, v_cur, past, o, lse)

    def visiting_bwd(res, cotangents):
        *res, past, o, lse = res
        return (*jax.lax.cond(past, past_bwd, future_bwd, *res, o, lse,
                              *cotangents), None)

    visiting.defvjp(visiting_fwd, visiting_bwd)

    with jax.named_scope("ring.block"):     # local block first
        o, lse = attend(q_blk, k_blk, v_blk, causal)

    def body(carry, _):
        k_cur, v_cur, src, o, lse = carry
        k_cur, v_cur = _kv_rotate(k_cur, v_cur, axis=axis, n_dev=n_dev,
                                  use_dma_ring=use_dma_ring,
                                  interpret=interpret)
        src = (src - 1) % n_dev         # never ``my`` in here
        with jax.named_scope("ring.block"):
            if causal:
                o2, lse2 = visiting(q_blk, k_cur, v_cur, src < my)
            else:
                o2, lse2 = attend(q_blk, k_cur, v_cur, False)
        with jax.named_scope("ring.merge"):
            o, lse = _merge_partials(o, lse, o2, lse2)
        return (k_cur, v_cur, src, o, lse), None

    if n_dev > 1:
        (_, _, _, o, lse), _ = jax.lax.scan(
            body, (k_blk, v_blk, my, o, lse), None, length=n_dev - 1)
    return o.astype(q_blk.dtype)


def _ring_local(q_blk, k_blk, v_blk, *, axis: str, n_dev: int,
                causal: bool, local: str, interpret: bool,
                use_dma_ring: bool, zigzag: bool):
    """The per-device ring body on blocks of rows that lie contiguous
    or, with ``zigzag``, in :func:`ring_order`."""
    import jax
    import jax.numpy as jnp

    if local == "flash":
        return _ring_flash_local(q_blk, k_blk, v_blk, axis=axis,
                                 n_dev=n_dev, causal=causal,
                                 interpret=interpret,
                                 use_dma_ring=use_dma_ring, zigzag=zigzag)
    # "blockwise" is ulysses_attention's name for the same chunked
    # online-softmax engine — accepted here so the two sequence-parallel
    # planes share an engine vocabulary.
    if local not in ("xla", "blockwise"):
        raise ValueError(f"unknown local attention engine {local!r}")
    sq = q_blk.shape[0]
    my = jax.lax.axis_index(axis)
    # global positions, of the query rows and of a visiting block's
    q_pos = _local_positions(my, sq, n_dev, zigzag)

    # Per rotation, the KV block is accumulated via the shared
    # intra-block-chunked recurrence (_accumulate_block): one device's
    # kv block can itself be huge (single-chip long context: n_dev=1
    # means skv == S), and chunking bounds the materialized score slab
    # at (h, sq, _KV_CHUNK) instead of (h, sq, skv) — without it, 32k
    # tokens on one chip needs tens of GB for scores. Differentiable
    # and exact: the chunk loop is the same online-softmax recurrence
    # the ring itself uses.
    @jax.named_scope("ring.block")
    def accumulate(k_cur, v_cur, src_dev, m, l, o):
        return _accumulate_block(
            q_blk, q_pos, k_cur, v_cur,
            _local_positions(src_dev, k_cur.shape[0], n_dev, zigzag),
            m, l, o, causal)

    m0, l0, o0 = _acc_init(q_blk)

    def body(carry, step):
        # rotate first, then accumulate: the scan covers rotations
        # 1..n_dev-1, the local block is accumulated outside — so no
        # final wasted KV rotation ships around the ring.
        k_cur, v_cur, src_dev, m, l, o = carry
        k_cur, v_cur = _kv_rotate(k_cur, v_cur, axis=axis, n_dev=n_dev,
                                  use_dma_ring=use_dma_ring,
                                  interpret=interpret)
        src_dev = (src_dev - 1) % n_dev
        m, l, o = accumulate(k_cur, v_cur, src_dev, m, l, o)
        return (k_cur, v_cur, src_dev, m, l, o), None

    m, l, o = accumulate(k_blk, v_blk, my, m0, l0, o0)
    if n_dev > 1:
        (_, _, _, m, l, o), _ = jax.lax.scan(
            body, (k_blk, v_blk, my, m, l, o),
            jnp.arange(n_dev - 1),
        )
    return _acc_finalize(o, l, q_blk.dtype)


def ring_attention_local(q_blk, k_blk, v_blk, *, axis: str,
                         n_devices: int | None = None,
                         causal: bool = False,
                         local: str = "xla",
                         interpret: bool = False,
                         use_dma_ring: bool = False):
    """The raw per-device ring-attention body, for COMPOSITION inside a
    caller's own ``shard_map``.

    ``local`` picks the per-device block engine: ``"xla"`` (chunked
    online-softmax in plain jnp — differentiable everywhere) or
    ``"flash"`` (the Pallas flash kernels — the flagship long-context
    configuration: scores stream through VMEM on every rotation;
    ``interpret=True`` runs them in the Pallas interpreter for
    CPU-mesh tests).

    ``q_blk/k_blk/v_blk`` are this device's (seq/n_devices, heads,
    head_dim) shards along a mesh axis named ``axis``, contiguous
    blocks of the sequence in their natural order (the balanced order
    of a causal ring, :func:`ring_order`, is :func:`ring_attention`'s
    and the trainer's); the KV blocks rotate around that axis with
    ``ppermute`` + online softmax. Because
    collectives bind by AXIS NAME, this composes freely with other mesh
    axes — e.g. 2-D data x sequence parallelism: an outer shard_map
    over ("data", "seq") vmaps this body (axis="seq") over the local
    batch shard, and every sequence still spans the full seq axis. It
    also composes with ``vmap`` and jax AD (gradient parity with full
    attention is pinned in tests). ``n_devices`` defaults to the bound
    axis's true size (``jax.lax.axis_size``) — pass it only to
    override, and beware a mismatch silently drops KV blocks.

    ``use_dma_ring=True`` rotates KV via the Pallas async remote-DMA
    exchange (ops/dma_ring) instead of ``ppermute`` — both blocks'
    transfers overlap each other and the per-rotation compute.
    Forward-only (the DMA primitive has no VJP); numerics are pinned
    against the ppermute path in tests.
    """
    import jax

    n_dev = (jax.lax.axis_size(axis) if n_devices is None
             else n_devices)
    return _ring_local(q_blk, k_blk, v_blk, axis=axis, n_dev=n_dev,
                       causal=causal, local=local, interpret=interpret,
                       use_dma_ring=use_dma_ring, zigzag=False)


def _build_ring_attention(mesh, axis: str, causal: bool,
                          local: str = "xla", interpret: bool = False,
                          use_dma_ring: bool = False,
                          ordered: bool = False):
    import functools

    import jax
    import numpy as np
    from fiber_tpu.telemetry import device as device_telemetry
    from fiber_tpu.utils.jaxcompat import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = mesh.shape[axis]
    spec = P(axis)

    def run(q, k, v):
        # Traced once per length: the order follows from it.
        order = ring_order(q.shape[0], n_dev, causal)
        device_telemetry.ring_built(
            *ring_schedule(q.shape[0], n_dev, causal))
        ring = shard_map(
            functools.partial(
                _ring_local, axis=axis, n_dev=n_dev, causal=causal,
                local=local, interpret=interpret,
                use_dma_ring=use_dma_ring, zigzag=order is not None),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        if ordered or order is None:
            return ring(q, k, v)
        # back by position, and sharded as the ring's own result is
        return jax.lax.with_sharding_constraint(
            ring(q[order], k[order], v[order])[np.argsort(order)],
            NamedSharding(mesh, spec))

    return jax.jit(run)


def _ring_program(mesh, axis, causal, local, interpret, use_dma_ring,
                  ordered):
    from fiber_tpu.parallel.mesh import default_mesh

    mesh = mesh or default_mesh()
    # Mesh hashes by value (devices + axis names): no id-aliasing after GC,
    # and equal meshes share the compiled program.
    key = (mesh, axis, causal, local, interpret, use_dma_ring, ordered)
    fn = _compiled_cache.get(key)
    if fn is None:
        fn = _build_ring_attention(mesh, axis, causal, local, interpret,
                                   use_dma_ring, ordered)
        _compiled_cache[key] = fn
    return fn


def ring_attention(
    q,
    k,
    v,
    mesh=None,
    axis: str = "pool",
    causal: bool = False,
    local: str = "xla",
    interpret: bool = False,
    use_dma_ring: bool = False,
):
    """Exact attention with sequence sharded over the mesh.

    q, k, v: (seq, heads, head_dim) — ``seq`` must divide evenly over the
    axis. Returns (seq, heads, head_dim) with the same sharding, rows
    in their natural order in and out: where the ring holds another
    (:func:`ring_order`) the rows are put in it and back inside the
    program.
    ``local="flash"`` runs the Pallas flash kernels as the per-device
    block (``interpret=True`` for CPU-mesh testing).
    ``use_dma_ring=True`` rotates KV with the Pallas async remote-DMA
    exchange instead of ``ppermute`` (forward-only — see
    :func:`ring_attention_local`). The compiled program is cached per
    (mesh, axis, causal, local, interpret, use_dma_ring); shapes re-use
    jit's own cache.
    """
    return _ring_program(mesh, axis, causal, local, interpret,
                         use_dma_ring, False)(q, k, v)


def ring_attention_ordered(q, k, v, mesh=None, axis: str = "pool",
                           causal: bool = False, local: str = "xla",
                           interpret: bool = False):
    """:func:`ring_attention` on rows that already lie in
    ``ring_order(seq, chips, causal)``, returned in that order: no row
    moves between chips. For a caller that can order its sequence at
    no cost and acts on single rows elsewhere (the LM trainer orders
    the token ids)."""
    return _ring_program(mesh, axis, causal, local, interpret, False,
                         True)(q, k, v)


def reference_attention(q, k, v, causal: bool = False):
    """Naive exact attention for testing (full score matrix)."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if causal:
        sq = q.shape[0]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :]
        s = jnp.where(mask[None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)
