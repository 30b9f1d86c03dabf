"""Pallas remote-DMA ring (ops/dma_ring.py): interpreter-mode numerics
pinned against the synchronous collectives it replaces — ppermute for
the rotation, all_to_all for the Ulysses swap — plus the
``use_dma_ring=`` composition through ring/ulysses attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from fiber_tpu.ops.dma_ring import ring_all_to_all, ring_exchange
from fiber_tpu.ops.ring_attention import reference_attention
from fiber_tpu.utils.jaxcompat import shard_map


def _mesh(n=8):
    return Mesh(np.asarray(jax.devices()[:n]), ("pool",))


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def test_ring_exchange_matches_ppermute():
    """One right-rotation == lax.ppermute [(i, (i+1) % n)] == a global
    np.roll by one shard."""
    mesh = _mesh()
    n = mesh.devices.size
    x = _rand((128, 16), seed=1)

    def dma(blk):
        (out,) = ring_exchange((blk,), axis="pool", interpret=True)
        return out

    def sync(blk):
        return jax.lax.ppermute(blk, "pool",
                                [(i, (i + 1) % n) for i in range(n)])

    kw = dict(mesh=mesh, in_specs=(P("pool"),), out_specs=P("pool"),
              check_vma=False)
    got = np.asarray(jax.device_get(shard_map(dma, **kw)(x)))
    want = np.asarray(jax.device_get(shard_map(sync, **kw)(x)))
    np.testing.assert_array_equal(got, want)
    # and the global picture: device i's shard landed on device i+1
    np.testing.assert_array_equal(
        got, np.roll(np.asarray(x), x.shape[0] // n, axis=0))


def test_ring_exchange_batched_pair():
    """K and V ride the same call (all DMAs started before any wait):
    both arrays rotate, independently, by exactly one shard."""
    mesh = _mesh()
    n = mesh.devices.size
    k = _rand((128, 4, 8), seed=2)
    v = _rand((128, 4, 8), seed=3)

    def dma(kb, vb):
        ko, vo = ring_exchange((kb, vb), axis="pool", interpret=True)
        return ko, vo

    ko, vo = shard_map(
        dma, mesh=mesh, in_specs=(P("pool"), P("pool")),
        out_specs=(P("pool"), P("pool")), check_vma=False)(k, v)
    shard = k.shape[0] // n
    np.testing.assert_array_equal(
        np.asarray(ko), np.roll(np.asarray(k), shard, axis=0))
    np.testing.assert_array_equal(
        np.asarray(vo), np.roll(np.asarray(v), shard, axis=0))


def test_ring_exchange_single_device_noop():
    mesh = _mesh(1)
    x = _rand((32, 8), seed=4)
    out = shard_map(
        lambda b: ring_exchange((b,), axis="pool", interpret=True)[0],
        mesh=mesh, in_specs=(P("pool"),), out_specs=P("pool"),
        check_vma=False)(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_ring_all_to_all_matches_native():
    """n-1 rotations + slice/placement == lax.all_to_all(tiled=True):
    the Ulysses seq<->head swap semantics."""
    mesh = _mesh()
    x = _rand((128, 8, 16), seed=5)  # (seq, heads, dim), heads split

    def dma(blk):
        return ring_all_to_all(blk, axis="pool", split_axis=1,
                               concat_axis=0, interpret=True)

    def native(blk):
        return jax.lax.all_to_all(blk, "pool", 1, 0, tiled=True)

    kw = dict(mesh=mesh, in_specs=(P("pool"),), out_specs=P(None, "pool"),
              check_vma=False)
    got = np.asarray(jax.device_get(shard_map(dma, **kw)(x)))
    want = np.asarray(jax.device_get(shard_map(native, **kw)(x)))
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_ring_all_to_all_rejects_indivisible():
    mesh = _mesh()
    x = _rand((128, 6, 16), seed=6)  # 6 heads on an 8-ring
    fn = shard_map(
        lambda blk: ring_all_to_all(blk, axis="pool", split_axis=1,
                                    concat_axis=0, interpret=True),
        mesh=mesh, in_specs=(P("pool"),), out_specs=P(None, "pool"),
        check_vma=False)
    with pytest.raises(ValueError, match="divide"):
        fn(x)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_dma_matches_reference(causal):
    """use_dma_ring=True swaps the KV rotation from ppermute onto the
    async-copy ring — numerics must stay pinned to the full-matrix
    reference (tolerance-gated like every other plane)."""
    from fiber_tpu.ops.ring_attention import ring_attention

    q = _rand((128, 2, 16), seed=7)
    k = _rand((128, 2, 16), seed=8)
    v = _rand((128, 2, 16), seed=9)
    got = np.asarray(jax.device_get(ring_attention(
        q, k, v, causal=causal, interpret=True, use_dma_ring=True)))
    want = np.asarray(jax.device_get(
        reference_attention(q, k, v, causal=causal)))
    assert np.abs(got - want).max() < 2e-5


def test_ulysses_attention_dma_matches_reference():
    """use_dma_ring=True routes both all-to-alls (seq->head and back)
    over the rotation-built ring; 8 heads so the swap divides on the
    8-device mesh."""
    from fiber_tpu.ops.ulysses_attention import ulysses_attention

    q = _rand((128, 8, 16), seed=10)
    k = _rand((128, 8, 16), seed=11)
    v = _rand((128, 8, 16), seed=12)
    got = np.asarray(jax.device_get(ulysses_attention(
        q, k, v, causal=True, use_dma_ring=True, interpret=True)))
    want = np.asarray(jax.device_get(
        reference_attention(q, k, v, causal=True)))
    assert np.abs(got - want).max() < 2e-5
