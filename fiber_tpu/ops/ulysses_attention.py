"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The second long-context strategy next to ``ops/ring_attention``. Ring
attention pipelines K/V blocks around the mesh with ``ppermute`` (memory
scales with the local block; latency hides behind compute). The Ulysses
layout instead runs TWO ``all_to_all`` collectives: inputs arrive
sequence-sharded, the first all-to-all redistributes them so each device
holds the FULL sequence for ``heads / n_dev`` heads, attention runs
locally and exactly (no online-softmax machinery), and the second
all-to-all restores sequence sharding. On TPU both collectives ride ICI;
for moderate sequence lengths this is usually faster than the ring
because the matmuls stay as one large MXU-friendly batch per head.

Trade-offs (why both exist):

* ulysses needs ``heads % n_dev == 0`` and materializes the full
  (seq, seq) score matrix per local head — memory grows with global
  sequence length squared;
* ring never materializes full scores and has no head-count constraint,
  but pays the online-softmax rescaling and a ppermute chain.

No counterpart exists in the reference (it has no model-parallel or
sequence-parallel machinery at all — SURVEY.md §"Parallelism
strategies"); this is part of the TPU-native long-context mandate.
"""

from __future__ import annotations

# (mesh, axis, causal, ...) -> jitted program. Same policy as
# ring_attention: meshes hash by value, there are only ever a handful
# per process, so a plain dict is the right cache.
_compiled_cache: dict = {}


def ulysses_attention_local(q_blk, k_blk, v_blk, *, axis: str,
                            causal: bool = False,
                            local: str = "reference",
                            use_dma_ring: bool = False,
                            interpret: bool = False):
    """The raw per-device Ulysses body, for COMPOSITION inside a
    caller's own ``shard_map`` (the all-to-alls bind by axis NAME, so
    it composes with other mesh axes exactly like
    :func:`fiber_tpu.ops.ring_attention_local` — e.g. a
    ("data", "seq") 2-D mesh with the body vmapped over the local
    batch shard). Shards are (seq/n, heads, head_dim);
    ``heads % axis_size == 0`` required.

    ``local`` picks the per-device attention over the gathered
    sequence: ``"reference"`` (full score matrix — fastest at moderate
    seq, O(S^2) memory), ``"blockwise"`` (KV-chunked online softmax —
    O(S·chunk) memory, differentiable everywhere), or ``"flash"``
    (the Pallas kernels — TPU, forward+backward). ``interpret=True``
    runs every Pallas piece (flash kernels, DMA ring) in the Pallas
    interpreter for CPU-mesh tests; the default compiles them and
    raises off-TPU."""
    import jax

    from fiber_tpu.ops.ring_attention import (
        blockwise_attention,
        reference_attention,
    )

    if local not in ("reference", "blockwise", "flash"):
        raise ValueError(f"unknown local attention {local!r}")

    # all-to-all #1: scatter heads, gather sequence ->
    # (seq, heads/n, head_dim); every device now sees the whole
    # sequence for its head slice.
    def seq_to_heads(x):
        return _a2a(x, axis, 1, 0, use_dma_ring, interpret)

    qh = seq_to_heads(q_blk)
    kh = seq_to_heads(k_blk)
    vh = seq_to_heads(v_blk)
    if local == "flash":
        from fiber_tpu.ops.pallas_attention import flash_attention

        out = flash_attention(qh, kh, vh, causal=causal,
                              interpret=interpret)
    elif local == "blockwise":
        out = blockwise_attention(qh, kh, vh, causal=causal)
    else:
        out = reference_attention(qh, kh, vh, causal=causal)
    # all-to-all #2: scatter sequence, gather heads — back to the
    # input layout.
    return _a2a(out, axis, 0, 1, use_dma_ring, interpret)


def _a2a(x, axis: str, split_axis: int, concat_axis: int,
         use_dma_ring: bool, interpret: bool):
    """The tiled all-to-all both Ulysses swaps run: XLA's native
    collective by default, or the Pallas async remote-DMA ring
    (ops/dma_ring — forward-only) when ``use_dma_ring`` is set."""
    import jax

    if use_dma_ring:
        from fiber_tpu.ops.dma_ring import ring_all_to_all

        return ring_all_to_all(x, axis=axis, split_axis=split_axis,
                               concat_axis=concat_axis,
                               interpret=interpret)
    return jax.lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True,
    )


def _build(mesh, axis: str, causal: bool, local: str,
           use_dma_ring: bool = False, interpret: bool = False):
    import functools

    import jax
    from fiber_tpu.utils.jaxcompat import shard_map
    from jax.sharding import PartitionSpec as P

    local_fn = functools.partial(
        ulysses_attention_local, axis=axis, causal=causal, local=local,
        use_dma_ring=use_dma_ring, interpret=interpret,
    )

    spec = P(axis)
    return jax.jit(shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    ))


def ulysses_attention(q, k, v, mesh=None, axis: str = "pool",
                      causal: bool = False, local: str = "reference",
                      use_dma_ring: bool = False,
                      interpret: bool = False):
    """Exact attention with the sequence dim sharded over ``axis``.

    q, k, v: (seq, heads, head_dim); ``seq`` and ``heads`` must both
    divide evenly by the mesh axis size. Returns (seq, heads, head_dim)
    with the same sharding. ``local`` picks the per-device attention
    (see :func:`ulysses_attention_local`) — ``"blockwise"`` or
    ``"flash"`` lift the O(S^2) local-memory constraint.
    ``use_dma_ring=True`` runs both swaps over the Pallas async
    remote-DMA ring (forward-only; numerics pinned against the native
    collective in tests). ``interpret=True`` runs the Pallas pieces in
    the interpreter (CPU-mesh tests). Mesh keys hash by value, so the
    compiled program is shared across equal meshes (no id-aliasing)."""
    from fiber_tpu.parallel.mesh import default_mesh

    mesh = mesh or default_mesh()
    n_dev = mesh.shape[axis]
    seq, heads = q.shape[0], q.shape[1]
    if seq % n_dev:
        raise ValueError(
            f"seq {seq} must be divisible by the mesh axis size {n_dev}"
        )
    if heads % n_dev:
        raise ValueError(
            f"ulysses needs heads % n_dev == 0 (got {heads} heads over "
            f"{n_dev} devices); use ring_attention for odd head counts"
        )
    key = (mesh, axis, causal, local, use_dma_ring, interpret)
    fn = _compiled_cache.get(key)
    if fn is None:
        fn = _build(mesh, axis, causal, local, use_dma_ring, interpret)
        _compiled_cache[key] = fn
    return fn(q, k, v)
