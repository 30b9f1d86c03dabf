"""Pallas remote-DMA ring primitives: neighbor exchange that OVERLAPS.

``lax.ppermute`` is a synchronous collective: the program (and with it
the per-rotation attention math in ops/ring_attention) serializes on
the full block transfer every step. The TPU's inter-chip interconnect
is RDMA — a chip can copy a buffer into a neighbor's HBM while both
keep computing — and Pallas exposes it as
``pltpu.make_async_remote_copy``: start() issues the DMA, wait()
blocks only when the data is actually needed. This module wraps that
primitive into the two exchange shapes the sequence-parallel ops use:

* :func:`ring_exchange` — rotate one or more arrays a step around a
  mesh axis. All copies are STARTED before any is awaited, so the K
  and V blocks of a ring-attention rotation ride the wire together
  instead of back-to-back.
* :func:`ring_all_to_all` — ``lax.all_to_all(tiled=True)`` semantics
  built from n-1 ring rotations, for the Ulysses head/sequence swap.

Both run inside ``shard_map`` like the collectives they replace. They
compile through Mosaic by default and raise off-TPU; ``interpret=True``
is the caller's explicit request for the Pallas interpreter (CPU-mesh
numerics tests). Interpreter caveat (jax 0.9.0): interpret mode
requires a SCALAR ``device_id`` where compiled Mosaic takes the
documented 1-tuple — ``_device_id`` papers over it.

Forward-only: ``make_async_remote_copy`` defines no VJP, so the
``use_dma_ring=`` flags in ring/ulysses attention are for inference
and ES-style gradient-free evaluation paths; differentiable callers
keep the default ``ppermute``/``all_to_all`` engines.

See /opt/skills/guides/pallas_guide.md and the distributed-Pallas
pattern this ports (SNIPPETS.md [2]/[3]).
"""

from __future__ import annotations

from typing import List, Sequence


def _device_id(right, interpret: bool):
    # Compiled Mosaic takes the mesh coordinate as a 1-tuple; the
    # interpreter's discharge rule chokes on tuples and wants the raw
    # scalar (dma_start_discharge_rule compares against all_gather of
    # a scalar id).
    return right if interpret else (right,)


def ring_exchange(arrays: Sequence, *, axis: str, n_dev: int = None,
                  interpret: bool = False) -> List:
    """Rotate every array in ``arrays`` one step right along ``axis``
    (device i's block lands on device i+1 — identical semantics to
    ``lax.ppermute`` with ``[(i, (i+1) % n)]``) via async remote DMA,
    all transfers in flight at once. Call inside ``shard_map``."""
    import jax

    arrays = list(arrays)
    if n_dev is None:
        n_dev = jax.lax.axis_size(axis)
    if n_dev <= 1 or not arrays:
        return arrays

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k = len(arrays)

    def kernel(*refs):
        ins, outs, sems = refs[:k], refs[k:2 * k], refs[2 * k:]
        my = jax.lax.axis_index(axis)
        right = jax.lax.rem(my + 1, n_dev)
        if not interpret:
            # Neighbor barrier BEFORE any remote write (the documented
            # right-permute discipline): a remote DMA lands in the
            # receiver's buffer whether or not it has entered the
            # kernel yet, so without this handshake a fast sender can
            # scribble into memory the neighbor's previous step is
            # still using. Signal both neighbors, wait for both — the
            # left one because it writes into US. Compiled-only:
            # interpret mode has no remote-signal lowering (jax 0.9.0)
            # and no race either — its DMA discharge rule runs the
            # per-device programs lockstep via all_gather.
            left = jax.lax.rem(my + n_dev - 1, n_dev)
            barrier = pltpu.get_barrier_semaphore()
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=_device_id(left, interpret),
                device_id_type=pltpu.DeviceIdType.MESH)
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=_device_id(right, interpret),
                device_id_type=pltpu.DeviceIdType.MESH)
            pltpu.semaphore_wait(barrier, 2)
        copies = [
            pltpu.make_async_remote_copy(
                src_ref=ins[i],
                dst_ref=outs[i],
                send_sem=sems[2 * i],
                recv_sem=sems[2 * i + 1],
                device_id=_device_id(right, interpret),
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            for i in range(k)
        ]
        # Issue every DMA before awaiting any: K and V (and whatever
        # else the caller batched) share the interconnect instead of
        # serializing — the overlap this module exists for.
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        # ANY keeps the blocks in HBM: the DMA engine reads/writes HBM
        # directly, no VMEM staging of multi-MB KV blocks.
        in_specs=[pl.BlockSpec(memory_space=pl.ANY) for _ in range(k)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY) for _ in range(k)],
        scratch_shapes=[pltpu.SemaphoreType.DMA] * (2 * k),
    )
    kwargs = {}
    if not interpret:
        # get_barrier_semaphore needs a collective_id so concurrent
        # collective kernels never share one barrier; every ring
        # rotation in a program runs sequentially, so one id is safe.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            collective_id=0)
    out = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in arrays],
        grid_spec=grid_spec,
        interpret=interpret,
        **kwargs,
    )(*arrays)
    return list(out)


def ring_all_to_all(x, *, axis: str, split_axis: int, concat_axis: int,
                    n_dev: int = None, interpret: bool = False):
    """``lax.all_to_all(x, axis, split_axis, concat_axis, tiled=True)``
    semantics over the DMA ring: the full local array rotates n-1
    steps; at each step the device slices out its own block of the
    visiting shard and lays it at the source device's slot. Call
    inside ``shard_map``; ``x.shape[split_axis]`` must divide by the
    axis size. Moves (n-1)x the array per device where the native
    collective is optimal — the point is the async overlap pattern
    (and a building block where no native all-to-all exists), not
    beating XLA's scheduler at its own collective."""
    import jax
    import jax.numpy as jnp

    if n_dev is None:
        n_dev = jax.lax.axis_size(axis)
    if n_dev <= 1:
        return x
    if x.shape[split_axis] % n_dev:
        raise ValueError(
            f"split axis {split_axis} ({x.shape[split_axis]}) must "
            f"divide by the ring size {n_dev}")

    my = jax.lax.axis_index(axis)
    seg = x.shape[split_axis] // n_dev
    cat = x.shape[concat_axis]
    out_shape = list(x.shape)
    out_shape[split_axis] = seg
    out_shape[concat_axis] = cat * n_dev
    out0 = jnp.zeros(tuple(out_shape), x.dtype)

    def place(out, cur, step):
        # After ``step`` right-rotations this device holds the shard
        # of device (my - step); its split-block ``my`` belongs at the
        # source's slot along the concat axis.
        src = jax.lax.rem(my - step + n_dev, n_dev)
        blk = jax.lax.dynamic_slice_in_dim(cur, my * seg, seg,
                                           split_axis)
        return jax.lax.dynamic_update_slice_in_dim(
            out, blk, src * cat, concat_axis)

    out = place(out0, x, 0)

    def body(carry, step):
        cur, out = carry
        (cur,) = ring_exchange((cur,), axis=axis, n_dev=n_dev,
                               interpret=interpret)
        out = place(out, cur, step)
        return (cur, out), None

    (_, out), _ = jax.lax.scan(body, (x, out),
                               jnp.arange(1, n_dev))
    return out
