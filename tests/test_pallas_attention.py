"""Flash-attention Pallas kernel: exactness against the full-matrix
reference, via the Pallas interpreter on CPU (Mosaic compilation:
tests/test_chip_smoke.py; on the chip: chip_smoke.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fiber_tpu.ops.pallas_attention import _pick_block, flash_attention
from fiber_tpu.ops.ring_attention import reference_attention


def _rand_qkv(s, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    return (jax.random.normal(kq, (s, h, d), dtype),
            jax.random.normal(kk, (s, h, d), dtype),
            jax.random.normal(kv, (s, h, d), dtype))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _rand_qkv(256, 2, 64)
    got = jax.device_get(flash_attention(
        q, k, v, causal=causal, block_q=128, block_kv=128,
        interpret=True))
    want = jax.device_get(reference_attention(q, k, v, causal=causal))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_flash_uneven_blocks_and_multi_sweep():
    """block_q != block_kv, several kv sweeps per q block, odd-length
    grid — the accumulator re-init across (head, q-block) boundaries is
    what this pins."""
    q, k, v = _rand_qkv(384, 3, 64)
    got = jax.device_get(flash_attention(
        q, k, v, causal=True, block_q=384, block_kv=128,
        interpret=True))
    want = jax.device_get(reference_attention(q, k, v, causal=True))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_flash_bf16_inputs():
    """bf16 in, bf16 out, f32 accumulation inside."""
    q, k, v = _rand_qkv(256, 2, 64, jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=128, block_kv=128,
                          interpret=True)
    assert got.dtype == jnp.bfloat16
    want = reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32))
    err = np.abs(np.asarray(jax.device_get(got), dtype=np.float32)
                 - np.asarray(jax.device_get(want))).max()
    assert err < 3e-2  # bf16 quantization of inputs/outputs


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    """The backward kernels (FlashAttention-2 recurrence: dq sweep over
    KV blocks, dk/dv sweep over Q blocks, from the saved logsumexp)
    produce the same dq/dk/dv as differentiating the full-matrix
    reference."""
    import numpy as np

    q, k, v = _rand_qkv(256, 2, 64)
    tgt = jax.random.normal(jax.random.PRNGKey(11), q.shape)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=128,
                            block_kv=128, interpret=True)
        return jnp.sum((o - tgt) ** 2)

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, causal=causal)
        return jnp.sum((o - tgt) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        a = np.asarray(jax.device_get(a))
        b = np.asarray(jax.device_get(b))
        rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
        assert rel < 1e-4, rel


def test_tiny_lm_flash_attention_parity():
    """TinyLM(attention="flash") — the LM training path through the
    Pallas kernels — matches the reference plane in loss AND gradient."""
    import numpy as np

    from fiber_tpu.models import TinyLM

    kwargs = dict(vocab=64, dim=32, heads=2, layers=1, max_seq=128)
    lm_flash = TinyLM(attention="flash", interpret=True, **kwargs)
    lm_ref = TinyLM(attention="reference", **kwargs)
    params = lm_flash.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (128,), 0, 64)

    lf, gf = jax.value_and_grad(lm_flash.loss)(params, tokens)
    lr, gr = jax.value_and_grad(lm_ref.loss)(params, tokens)
    assert abs(float(lf) - float(lr)) < 1e-4
    flat_f = jax.tree_util.tree_leaves(gf)
    flat_r = jax.tree_util.tree_leaves(gr)
    for a, b in zip(flat_f, flat_r):
        a = np.asarray(jax.device_get(a))
        b = np.asarray(jax.device_get(b))
        assert np.abs(a - b).max() < 5e-4, np.abs(a - b).max()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_composition_matches_reference(causal):
    """ring_attention(local="flash"): the Pallas kernel as the
    per-device block, partial (out, lse) pairs merged across rotations
    (VERDICT r3 #4 — the flagship long-context plane must run the
    flagship kernel). Exact vs the full-matrix reference on the
    8-device CPU mesh, interpret mode."""
    from fiber_tpu.ops.ring_attention import ring_attention

    q, k, v = _rand_qkv(256, 4, 16)
    got = np.asarray(jax.device_get(ring_attention(
        q, k, v, causal=causal, local="flash", interpret=True)))
    want = np.asarray(jax.device_get(
        reference_attention(q, k, v, causal=causal)))
    assert np.abs(got - want).max() < 2e-5


def test_ring_flash_gradients_match_reference():
    """The lse cotangent path (flash_attention_lse custom VJP: delta -
    dlse) composed through the ring merge produces exact dq/dk/dv."""
    from fiber_tpu.ops.ring_attention import ring_attention

    q, k, v = _rand_qkv(256, 4, 16)

    def loss_flash(q, k, v):
        o = ring_attention(q, k, v, causal=True, local="flash",
                           interpret=True)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        a = np.asarray(jax.device_get(a))
        b = np.asarray(jax.device_get(b))
        rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
        assert rel < 1e-4, rel


def _pool_mesh(n):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:n]), ("pool",))


@pytest.mark.parametrize("n_dev", [4, 2])
@pytest.mark.parametrize("local", ["flash", "xla"])
def test_zigzag_ring_matches_reference(local, n_dev):
    """A causal ring over n chips holds the rows in zigzag (chip i:
    half-blocks i and 2n-1-i), so every chip attends two half-block
    pairs in every rotation; ring_attention still takes and returns the
    natural order. Output and the gradients of q, k, v against the
    full-matrix reference, for both engines; a length that is not whole
    in 2n half-blocks keeps contiguous blocks and still matches."""
    from fiber_tpu.ops.ring_attention import ring_attention, ring_order

    mesh = _pool_mesh(n_dev)
    for seq, zigzag in ((256, True), (33 * n_dev, False)):
        assert (ring_order(seq, n_dev, True) is not None) == zigzag
        q, k, v = _rand_qkv(seq, 4, 16)

        def loss_ring(q, k, v):
            o = ring_attention(q, k, v, mesh=mesh, causal=True,
                               local=local, interpret=True)
            return jnp.sum(o ** 2), o

        def loss_ref(q, k, v):
            o = reference_attention(q, k, v, causal=True)
            return jnp.sum(o ** 2), o

        (_, got), gf = jax.value_and_grad(
            loss_ring, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        (_, want), gr = jax.value_and_grad(
            loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
        # by position again, and sharded over the ring as before
        assert {s.data.shape for s in got.addressable_shards} == {
            (seq // n_dev, 4, 16)}
        for a, b in zip(gf, gr):
            a, b = np.asarray(a), np.asarray(b)
            rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
            assert rel < 1e-4, (seq, rel)


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_ring_order_and_its_inverse_round_trip(n_dev):
    """ring_order is a permutation of the positions whose argsort puts
    rows back; chip i's block is half-blocks i and 2n-1-i, early half
    first; the non-causal ring, one chip and a length that does not
    halve keep the natural order (None)."""
    from fiber_tpu.ops.ring_attention import ring_order

    seq = 16 * n_dev
    order = ring_order(seq, n_dev, True)
    assert sorted(order) == list(range(seq))
    x = np.arange(seq) * 3 + 1
    assert np.array_equal(x[order][np.argsort(order)], x)
    half = seq // (2 * n_dev)
    for chip, rows in enumerate(order.reshape(n_dev, 2, half)):
        assert list(rows[0]) == list(range(chip * half, (chip + 1) * half))
        late = 2 * n_dev - 1 - chip
        assert list(rows[1]) == list(range(late * half, (late + 1) * half))
    assert ring_order(seq, n_dev, False) is None
    assert ring_order(seq, 1, True) is None
    assert ring_order(seq + n_dev, n_dev, True) is None


@pytest.mark.parametrize("n_dev", [4, 8])
def test_ring_schedule_balances_the_causal_ring(n_dev):
    """The static schedule the ring_rotations_traced counter reads, no
    device needed: in zigzag every chip runs every rotation and all
    chips attend the same number of (query, key) pairs in a rotation;
    the busiest chip's total is S^2/2n + S/2n where contiguous blocks
    give S^2 (2n-1)/2n^2 + S/2n: 2.0 block-units on the critical path
    of four chips for 3.5."""
    from fiber_tpu.ops.ring_attention import ring_schedule

    seq = 4096 * n_dev
    layout, pairs = ring_schedule(seq, n_dev, True)
    assert layout == "zigzag"
    assert all(n > 0 for chip in pairs for n in chip)
    for rotation in zip(*pairs):
        assert len(set(rotation)) == 1
    assert {sum(chip) for chip in pairs} == {
        seq * seq // (2 * n_dev) + seq // (2 * n_dev)}

    # one row short of whole half-blocks: today's contiguous blocks
    odd = seq + n_dev
    layout, pairs = ring_schedule(odd, n_dev, True)
    assert layout == "contiguous"
    assert [sum(1 for n in chip if n) for chip in pairs] == list(
        range(1, n_dev + 1))
    # S^2 (2n-1)/2n^2 + S/2n, doubled to stay in whole numbers
    assert 2 * n_dev * n_dev * max(sum(chip) for chip in pairs) == (
        odd * odd * (2 * n_dev - 1) + odd * n_dev)
    block = (seq // n_dev) ** 2
    zigzag_units = max(sum(c) for c in ring_schedule(seq, n_dev, True)[1])
    assert round(zigzag_units / block, 2) == n_dev / 2
    assert round(max(sum(chip) for chip in pairs) / block, 1) == n_dev - 0.5

    layout, pairs = ring_schedule(seq, n_dev, False)
    assert layout == "contiguous"
    assert {n for chip in pairs for n in chip} == {block}


def test_ring_rotations_traced_counter():
    """Building a ring program moves ring_rotations_traced{layout,
    state} by the rotations of all its chips: a causal ring of four
    chips reads zigzag 16 run and 0 skip; a length that does not halve
    reads contiguous 10 and 6, which is what an operator looks for."""
    import fiber_tpu
    from fiber_tpu import telemetry
    from fiber_tpu.ops.ring_attention import ring_attention

    fiber_tpu.init()
    counter = telemetry.counter("ring_rotations_traced")

    def read():
        return {(lay, st): counter.value(layout=lay, state=st)
                for lay in ("zigzag", "contiguous")
                for st in ("run", "skip")}

    def built(seq, causal):
        before = read()
        q, k, v = _rand_qkv(seq, 2, 8)
        ring_attention(q, k, v, mesh=_pool_mesh(4), causal=causal)
        after = read()
        return {key: int(after[key] - before[key])
                for key in after if after[key] != before[key]}

    assert built(72, True) == {("zigzag", "run"): 16}
    assert built(68, True) == {("contiguous", "run"): 10,
                               ("contiguous", "skip"): 6}
    assert built(76, False) == {("contiguous", "run"): 16}


def test_flash_rectangular_matches_reference():
    """Fewer or more keys than queries (non-causal): what the zigzag
    ring asks of the kernels, all rows on half a visiting block and half
    the rows on a whole one. Values, lse and gradients; a causal call
    needs as many keys as queries and says so."""
    from fiber_tpu.ops.pallas_attention import flash_attention_lse

    q, k, v = _rand_qkv(256, 4, 16)
    for rows, keys in ((256, 128), (128, 256)):
        qs, ks, vs = q[:rows], k[:keys, :2], v[:keys, :2]

        def ref(q, k, v):
            kr, vr = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
            s = jnp.einsum("qhd,khd->hqk", q, kr) / 4.0
            return (jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vr),
                    jax.nn.logsumexp(s, axis=-1))

        def loss(fn):
            def f(q, k, v):
                o, lse = fn(q, k, v)
                return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))
            return jax.grad(f, argnums=(0, 1, 2))

        def flash(q, k, v):
            return flash_attention_lse(q, k, v, block_q=128, block_kv=128,
                                       interpret=True)

        for got, want in zip(flash(qs, ks, vs), ref(qs, ks, vs)):
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
        for a, b in zip(loss(flash)(qs, ks, vs), loss(ref)(qs, ks, vs)):
            a, b = np.asarray(a), np.asarray(b)
            assert np.abs(a - b).max() / np.abs(b).max() < 1e-4
    with pytest.raises(ValueError, match="as many"):
        flash_attention_lse(q, k[:128], v[:128], causal=True,
                            interpret=True)


@pytest.mark.parametrize("pos", ["rope", "learned"])
@pytest.mark.parametrize("attention", ["flash", "ring"])
def test_tiny_lm_on_the_ring_matches_one_device(attention, pos):
    """On a 4-device mesh the trainer takes the token ids in the ring's
    zigzag order and gives ropes / the position table the rows' true
    positions: loss and gradients equal the one-device model's; apply
    returns logits and token_losses losses BY POSITION (one altered
    token moves the rows from its position on and none before it)."""
    from fiber_tpu.models import TinyLM

    kwargs = dict(vocab=64, dim=32, heads=4, kv_heads=2, layers=2,
                  max_seq=128, pos=pos)
    lm = TinyLM(attention=attention, mesh=_pool_mesh(4), interpret=True,
                **kwargs)
    lm_ref = TinyLM(attention="reference", **kwargs)
    order = lm._ring_order()
    assert order is not None and list(order[:32]) == (
        list(range(16)) + list(range(112, 128)))
    assert lm_ref._ring_order() is None
    params = lm_ref.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (128,), 0, 64)

    lf, gf = jax.value_and_grad(lm.loss)(params, tokens)
    lr, gr = jax.value_and_grad(lm_ref.loss)(params, tokens)
    assert abs(float(lf) - float(lr)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gr)):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 5e-4

    logits = np.asarray(lm.apply(params, tokens))
    assert np.abs(logits - np.asarray(lm_ref.apply(params, tokens))
                  ).max() < 1e-4
    losses = np.asarray(lm.token_losses(params, tokens))
    assert np.abs(losses - np.asarray(
        lm_ref.token_losses(params, tokens))).max() < 1e-4
    altered = tokens.at[70].set((tokens[70] + 1) % 64)
    moved = np.abs(np.asarray(lm.apply(params, altered)) - logits).max(1)
    assert moved[:70].max() == 0.0 and (moved[70:] > 0.0).all()
    moved = np.abs(np.asarray(lm.token_losses(params, altered)) - losses)
    # position 69's loss is its target's: token 70 itself
    assert moved[:69].max() == 0.0 and (moved[69:] > 0.0).all()


def test_flash_attention_lse_values():
    """flash_attention_lse's second output IS the softmax logsumexp
    (scaled scores), the mergeable residual."""
    from fiber_tpu.ops.pallas_attention import flash_attention_lse

    q, k, v = _rand_qkv(256, 2, 64)
    out, lse = flash_attention_lse(q, k, v, causal=False, block_q=128,
                                   block_kv=128, interpret=True)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], jnp.float32))
    want_lse = jax.nn.logsumexp(s, axis=-1)          # (h, sq)
    assert np.abs(np.asarray(lse) - np.asarray(want_lse)).max() < 2e-5
    want_out = reference_attention(q, k, v, causal=False)
    assert np.abs(np.asarray(out) - np.asarray(want_out)).max() < 2e-5


def test_tiny_lm_multi_device_flash_trains():
    """TinyLM(attention="flash") on a multi-device mesh — previously a
    construction-time error — now trains through ring+flash with the
    sequence sharded over all 8 devices, loss/grad parity with the
    reference plane."""
    from fiber_tpu.models import TinyLM, make_train_step
    from fiber_tpu.parallel import default_mesh

    mesh = default_mesh()
    kwargs = dict(vocab=64, dim=32, heads=2, layers=1, max_seq=128)
    lm_flash = TinyLM(attention="flash", mesh=mesh, interpret=True,
                      **kwargs)
    lm_ref = TinyLM(attention="reference", **kwargs)
    params = lm_flash.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (128,), 0, 64)

    lf, gf = jax.value_and_grad(lm_flash.loss)(params, tokens)
    lr, gr = jax.value_and_grad(lm_ref.loss)(params, tokens)
    assert abs(float(lf) - float(lr)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gr)):
        a = np.asarray(jax.device_get(a))
        b = np.asarray(jax.device_get(b))
        assert np.abs(a - b).max() < 5e-4, np.abs(a - b).max()

    # And an optimizer step actually runs end to end on the mesh.
    import optax

    opt = optax.adamw(1e-3)
    step = make_train_step(lm_flash, opt)
    p2, _, loss = step(params, opt.init(params), tokens)
    assert np.isfinite(float(loss))
    assert any(
        not np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(p2)))


def test_tiny_lm_rejects_poolless_multi_device_mesh():
    """A multi-device mesh without the 'pool' axis must fail loudly at
    construction (the planes shard over 'pool'; the old failure was a
    KeyError deep inside the first apply)."""
    from jax.sharding import Mesh

    from fiber_tpu.models import TinyLM

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("data",))
    with pytest.raises(ValueError, match="pool"):
        TinyLM(attention="flash", mesh=mesh)


def _gqa_reference(q, k, v, causal):
    """GQA semantics via explicit KV broadcast + full-matrix attention."""
    reps = q.shape[1] // k.shape[1]
    return reference_attention(
        q, jnp.repeat(k, reps, axis=1), jnp.repeat(v, reps, axis=1),
        causal=causal)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_broadcast_reference(causal):
    """Grouped-query attention: kv_heads=2 serving 8 query heads via
    kernel index maps (no repeated KV materialized) must equal the
    broadcast-KV full-matrix reference."""
    S, H, KVH, D = 256, 8, 2, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (S, H, D))
    k = jax.random.normal(kk, (S, KVH, D))
    v = jax.random.normal(kv, (S, KVH, D))
    got = jax.device_get(flash_attention(
        q, k, v, causal=causal, block_q=128, block_kv=128,
        interpret=True))
    want = jax.device_get(_gqa_reference(q, k, v, causal))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_flash_gqa_gradients_match_broadcast_reference():
    """dk/dv must ACCUMULATE across each query-head group (the dkv
    kernel's (kv_heads, n_kv, group, n_q) accumulation grid) — plus
    dq per query head."""
    S, H, KVH, D = 256, 4, 2, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(kq, (S, H, D))
    k = jax.random.normal(kk, (S, KVH, D))
    v = jax.random.normal(kv, (S, KVH, D))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=128,
                            block_kv=128, interpret=True)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_gqa_reference(q, k, v, True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        a = np.asarray(jax.device_get(a))
        b = np.asarray(jax.device_get(b))
        assert a.shape == b.shape, (name, a.shape, b.shape)
        rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-9)
        assert rel < 1e-4, (name, rel)


def test_tiny_lm_gqa_trains_all_planes():
    """TinyLM(kv_heads=2): the flash plane reads the small KV natively,
    the XLA planes broadcast — same loss to reference at matched
    params, and a train step runs on the mesh."""
    from fiber_tpu.models import TinyLM, make_train_step
    from fiber_tpu.parallel import default_mesh

    kwargs = dict(vocab=64, dim=32, heads=4, layers=1, max_seq=128,
                  kv_heads=2)
    lm_ref = TinyLM(attention="reference", **kwargs)
    params = lm_ref.init(jax.random.PRNGKey(0))
    assert "wkv" in params["blocks"][0] and \
        "wqkv" not in params["blocks"][0]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (128,), 0, 64)
    l_ref = float(lm_ref.loss(params, tokens))

    lm_flash = TinyLM(attention="flash", interpret=True, **kwargs)
    assert abs(float(lm_flash.loss(params, tokens)) - l_ref) < 1e-4

    mesh = default_mesh()
    lm_ring = TinyLM(attention="ring", mesh=mesh, **kwargs)
    assert abs(float(lm_ring.loss(params, tokens)) - l_ref) < 1e-4

    import optax

    opt = optax.adamw(1e-3)
    step = make_train_step(lm_ring, opt)
    p2, _, loss = step(params, opt.init(params), tokens)
    assert np.isfinite(float(loss))


def test_tiny_lm_gqa_multi_device_ring_flash():
    """The flagship advertised configuration: GQA + multi-device
    ring x flash — q blocks carry all heads while the ROTATING KV
    blocks carry only kv_heads, the one path where the kernel's GQA
    index maps, the three-way causal split, and the lse merge all
    compose. Loss and gradient parity with the reference plane."""
    from fiber_tpu.models import TinyLM
    from fiber_tpu.parallel import default_mesh

    kwargs = dict(vocab=32, dim=32, heads=4, layers=1, max_seq=128,
                  kv_heads=2)
    lm_ref = TinyLM(attention="reference", **kwargs)
    lm_rf = TinyLM(attention="flash", mesh=default_mesh(),
                   interpret=True, **kwargs)
    params = lm_ref.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (128,), 0, 32)

    lr, gr = jax.value_and_grad(lm_ref.loss)(params, tokens)
    lf, gf = jax.value_and_grad(lm_rf.loss)(params, tokens)
    assert abs(float(lf) - float(lr)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gr)):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 5e-4


def test_kv_heads_validation():
    """kv_heads=0 must not silently mean full MHA; negatives must fail
    at construction, not deep inside init()."""
    from fiber_tpu.models import TinyLM

    for bad in (0, -2):
        with pytest.raises(ValueError, match="kv_heads"):
            TinyLM(heads=8, dim=64, kv_heads=bad)
    with pytest.raises(ValueError, match="kv_heads"):
        TinyLM(heads=8, dim=64, kv_heads=3)  # non-divisor


def test_ring_intra_block_chunking_exact():
    """The kv-chunked accumulate (what makes single-chip long context
    fit in HBM: scores bounded at (h, sq, _KV_CHUNK)) stays exact and
    differentiable — forced on by shrinking the chunk threshold."""
    import importlib

    import numpy as np
    from jax.sharding import Mesh

    ra = importlib.import_module("fiber_tpu.ops.ring_attention")
    old = ra._KV_CHUNK
    ra._KV_CHUNK = 64
    # per-(mesh,axis,causal) cache would hand back a program compiled
    # with the old chunking
    ra._compiled_cache.clear()
    try:
        devs = jax.devices()[:4]
        mesh = Mesh(np.asarray(devs), ("pool",))
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
        S, H, D = 512, 2, 32          # 128 kv/device -> 2 chunks of 64
        q = jax.random.normal(kq, (S, H, D))
        k = jax.random.normal(kk, (S, H, D))
        v = jax.random.normal(kv, (S, H, D))
        got = jax.device_get(ra.ring_attention(q, k, v, mesh=mesh,
                                               causal=True))
        want = jax.device_get(reference_attention(q, k, v, causal=True))
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5

        def f_ring(q):
            return jnp.sum(ra.ring_attention(q, k, v, mesh=mesh,
                                             causal=True) ** 2)

        def f_ref(q):
            return jnp.sum(reference_attention(q, k, v,
                                               causal=True) ** 2)

        g1 = jax.device_get(jax.grad(f_ring)(q))
        g2 = jax.device_get(jax.grad(f_ref)(q))
        assert np.abs(np.asarray(g1) - np.asarray(g2)).max() < 5e-5
    finally:
        ra._KV_CHUNK = old
        ra._compiled_cache.clear()


def test_blockwise_attention_exact_and_differentiable():
    """blockwise_attention (the shared KV-chunked recurrence, factored
    from the ring body) matches the full-matrix reference in value and
    gradient with chunking forced on."""
    import importlib

    import numpy as np

    ra = importlib.import_module("fiber_tpu.ops.ring_attention")
    old = ra._KV_CHUNK
    ra._KV_CHUNK = 64
    try:
        q, k, v = _rand_qkv(256, 2, 32)
        for causal in (False, True):
            got = jax.device_get(ra.blockwise_attention(q, k, v,
                                                        causal=causal))
            want = jax.device_get(reference_attention(q, k, v,
                                                      causal=causal))
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5

        def f_block(q):
            return jnp.sum(ra.blockwise_attention(q, k, v,
                                                  causal=True) ** 2)

        def f_ref(q):
            return jnp.sum(reference_attention(q, k, v,
                                               causal=True) ** 2)

        g1 = np.asarray(jax.device_get(jax.grad(f_block)(q)))
        g2 = np.asarray(jax.device_get(jax.grad(f_ref)(q)))
        assert np.abs(g1 - g2).max() < 5e-5
    finally:
        ra._KV_CHUNK = old


def test_blockwise_attention_remainder_chunk():
    """The O(sq x chunk) bound holds for ANY length: a sequence that is
    not a multiple of _KV_CHUNK takes the scan + tail-chunk path, not a
    silent full-slab fallback."""
    import importlib

    import numpy as np

    ra = importlib.import_module("fiber_tpu.ops.ring_attention")
    old = ra._KV_CHUNK
    ra._KV_CHUNK = 64
    try:
        q, k, v = _rand_qkv(200, 2, 32)   # 200 = 3*64 + 8 tail
        for causal in (False, True):
            got = jax.device_get(
                ra.blockwise_attention(q, k, v, causal=causal))
            want = jax.device_get(
                reference_attention(q, k, v, causal=causal))
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    finally:
        ra._KV_CHUNK = old


def test_ulysses_flash_local_exact():
    """ulysses(local=\"flash\"): the all-to-all head/seq swap composed
    with the Pallas kernels (interpret mode off-TPU) stays exact."""
    import numpy as np
    from jax.sharding import Mesh

    from fiber_tpu.ops.ulysses_attention import ulysses_attention

    devs = jax.devices()[:2]
    mesh = Mesh(np.asarray(devs), ("pool",))
    q, k, v = _rand_qkv(256, 2, 32)
    got = jax.device_get(ulysses_attention(
        q, k, v, mesh=mesh, causal=True, local="flash",
        interpret=True))
    want = jax.device_get(reference_attention(q, k, v, causal=True))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_ulysses_blockwise_local_exact():
    """ulysses_attention(local=\"blockwise\"): the all-to-all head/seq
    swap with a memory-bounded per-device attention stays exact."""
    import importlib

    import numpy as np
    from jax.sharding import Mesh

    from fiber_tpu.ops.ulysses_attention import ulysses_attention

    ra = importlib.import_module("fiber_tpu.ops.ring_attention")
    old = ra._KV_CHUNK
    ra._KV_CHUNK = 64
    try:
        devs = jax.devices()[:4]
        mesh = Mesh(np.asarray(devs), ("pool",))
        q, k, v = _rand_qkv(512, 4, 32)
        got = jax.device_get(ulysses_attention(
            q, k, v, mesh=mesh, causal=True, local="blockwise"))
        want = jax.device_get(reference_attention(q, k, v, causal=True))
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    finally:
        ra._KV_CHUNK = old


def test_pick_block():
    assert _pick_block(4096, 512) == 512
    assert _pick_block(384, 512) == 384       # short seq: one block
    assert _pick_block(640, 512) == 128       # aligned divisor under cap
    assert _pick_block(8192, 512) == 512

def test_generate_kv_cache_matches_full_apply():
    """Autoregressive decode with per-layer KV caches must produce
    exactly the tokens that naive full re-apply greedy decoding picks
    (incremental attention == full causal attention), GQA included."""
    from fiber_tpu.models import TinyLM

    model = TinyLM(vocab=32, dim=32, heads=4, kv_heads=2, layers=2,
                   max_seq=64, attention="reference")
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 32)

    out = model.generate(params, prompt, steps=12)
    assert out.shape == (20,)
    assert np.array_equal(np.asarray(out[:8]), np.asarray(prompt))

    toks = [int(t) for t in prompt]
    for _ in range(12):
        padded = jnp.zeros((64,), jnp.int32).at[: len(toks)].set(
            jnp.asarray(toks, jnp.int32))
        logits = model.apply(params, padded)[len(toks) - 1]
        toks.append(int(jnp.argmax(logits)))
    assert [int(t) for t in out] == toks

    # Sampling smoke: temperature > 0 with a key stays in-vocab and
    # respects the prompt; temperature > 0 without a key is loud.
    sampled = model.generate(params, prompt, steps=6,
                             key=jax.random.PRNGKey(7), temperature=1.0)
    assert sampled.shape == (14,)
    assert 0 <= int(np.asarray(sampled).min()) \
        and int(np.asarray(sampled).max()) < 32
    with pytest.raises(ValueError, match="needs a key"):
        model.generate(params, prompt, steps=2, temperature=0.5)
    with pytest.raises(ValueError, match="exceeds"):
        model.generate(params, prompt, steps=64)


def _windowed_reference(q, k, v, window):
    """Causal sliding-window attention via explicit masking."""
    d = q.shape[-1]
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    sq = q.shape[0]
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sq)[None, :]
    keep = (qpos >= kpos) & (qpos - kpos < window)
    s = jnp.where(keep[None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


def _windowed_lse(q, k, v, window):
    """The windowed logsumexp (heads, S) of the scaled scores."""
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype))
    qpos = jnp.arange(q.shape[0])[:, None]
    kpos = jnp.arange(q.shape[0])[None, :]
    keep = (qpos >= kpos) & (qpos - kpos < window)
    return jax.nn.logsumexp(jnp.where(keep[None], s, -jnp.inf), axis=-1)


def _rand_gqa(s, h, kvh, d, seed=13):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (s, h, d)),
            jax.random.normal(kk, (s, kvh, d)),
            jax.random.normal(kv, (s, kvh, d)))


# (S, block_q, block_kv, window): the first three are one-block-wide
# sequences of 4 blocks; in the others the band of blocks that run is
# narrower than the row (n_band < n_kv), with aligned (128), unaligned
# (100, 300) windows and block_q != block_kv both ways.
_WINDOW_CASES = [
    (512, 128, 128, 64), (512, 128, 128, 100), (512, 128, 128, 256),
    (1024, 128, 128, 128), (1024, 128, 128, 100), (1024, 128, 128, 300),
    (1024, 128, 256, 100), (1024, 256, 128, 100), (1024, 128, 256, 300),
    (1024, 256, 128, 300),
]


@pytest.mark.parametrize("s,block_q,block_kv,window", _WINDOW_CASES)
def test_flash_sliding_window_matches_reference(s, block_q, block_kv,
                                                window):
    """window= restricts attention to the last `window` positions;
    block-aligned, unaligned and wider-than-one-block windows must all
    match explicit masking — the band's offset and clamp, the block-skip
    predicate AND the elementwise boundary mask are all load-bearing."""
    q, k, v = _rand_qkv(s, 2, 32)
    got = np.asarray(flash_attention(
        q, k, v, causal=True, window=window, block_q=block_q,
        block_kv=block_kv, interpret=True))
    want = np.asarray(_windowed_reference(q, k, v, window))
    assert np.abs(got - want).max() < 2e-5


@pytest.mark.parametrize(
    "s,block_q,block_kv,window",
    [(384, 128, 128, 100)] + _WINDOW_CASES[3:])
def test_flash_sliding_window_gradients(s, block_q, block_kv, window):
    """Windowed backward: dq/dk/dv match differentiating the explicit
    mask (neither the skip predicate nor the band's clamp may drop a
    boundary contribution, in dq's kv sweep or in dkv's q sweep)."""
    q, k, v = _rand_qkv(s, 2, 32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, window=window,
                            block_q=block_q, block_kv=block_kv,
                            interpret=True)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_windowed_reference(q, k, v, window) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert rel < 1e-4, rel


def test_flash_window_validation():
    q, k, v = _rand_qkv(256, 2, 32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64,
                        interpret=True)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0, interpret=True)


@pytest.mark.parametrize("s,h,kvh,window,block_q,block_kv", [
    (256, 4, 2, 96, 128, 128),
    (1024, 8, 2, 100, 128, 128),
    (1024, 4, 1, 300, 256, 128),
])
def test_flash_window_with_gqa(s, h, kvh, window, block_q, block_kv):
    """Sliding window composes with grouped-query attention, forward
    and backward: dkv's (group, band) sweep accumulates every query
    head of the group over the band of q-blocks that reach a kv-block."""
    group = h // kvh
    q, k, v = _rand_gqa(s, h, kvh, 32)

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=window, block_q=block_q,
            block_kv=block_kv, interpret=True)

    def ref(q, k, v):
        return _windowed_reference(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
            window)

    assert np.abs(np.asarray(flash(q, k, v))
                  - np.asarray(ref(q, k, v))).max() < 2e-5
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        assert a.shape == b.shape, (name, a.shape, b.shape)
        rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert rel < 1e-4, (name, rel)


@pytest.mark.parametrize("s,h,kvh,window,block_q,block_kv", [
    (1024, 2, 2, 100, 128, 128),
    (1024, 4, 1, 300, 128, 256),
])
def test_flash_window_lse_and_its_cotangent(s, h, kvh, window, block_q,
                                            block_kv):
    """flash_attention_lse under a window: the lse is the windowed
    logsumexp, and a non-zero lse cotangent reaches dq, dk and dv
    through the banded backward kernels (delta - dlse)."""
    from fiber_tpu.ops.pallas_attention import flash_attention_lse

    group = h // kvh
    q, k, v = _rand_gqa(s, h, kvh, 32, seed=21)
    w = jax.random.normal(jax.random.PRNGKey(5), (h, s))

    def loss_flash(q, k, v):
        o, lse = flash_attention_lse(
            q, k, v, causal=True, window=window, block_q=block_q,
            block_kv=block_kv, interpret=True)
        return jnp.sum(o ** 2) + jnp.sum(w * lse)

    def loss_ref(q, k, v):
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
        return (jnp.sum(_windowed_reference(q, k, v, window) ** 2)
                + jnp.sum(w * _windowed_lse(q, k, v, window)))

    _, lse = flash_attention_lse(
        q, k, v, causal=True, window=window, block_q=block_q,
        block_kv=block_kv, interpret=True)
    want = _windowed_lse(q, jnp.repeat(k, group, axis=1), None, window)
    assert np.abs(np.asarray(lse) - np.asarray(want)).max() < 2e-5
    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert rel < 1e-4, (name, rel)


@pytest.mark.parametrize("block_q,block_kv", [
    (128, 128), (128, 256), (256, 128), (384, 128), (128, 384), (512, 512)])
def test_band_spans_are_the_runs_of_run_window(block_q, block_kv):
    """`_kv_span` / `_q_span` (what the kernels and index maps evaluate
    on program ids) name exactly the blocks `_run_window` lets run, for
    every q-block and kv-block, aligned or not, and `_band_extents`
    (what sizes the grids) is their widest."""
    from fiber_tpu.ops.pallas_attention import (
        _band_extents, _kv_span, _q_span, _run_window)

    s = 3072
    n_q, n_kv = s // block_q, s // block_kv
    iq, ik = np.arange(n_q, dtype=np.int32), np.arange(n_kv, dtype=np.int32)
    for window in (None, 1, 2, 100, 127, 128, 129, 256, 300, 513, 1000,
                   3071, 3072, 5000):
        run = np.asarray(_run_window(iq[:, None], ik[None, :], block_q,
                                     block_kv, True, window))
        first, last = (np.broadcast_to(np.asarray(x), iq.shape) for x in
                       _kv_span(jnp.asarray(iq), block_q, block_kv, True, window))
        want = (ik[None, :] >= first[:, None]) & (ik[None, :] <= last[:, None])
        assert np.array_equal(run, want), ("kv", window)
        first, last = (np.broadcast_to(np.asarray(x), ik.shape) for x in
                       _q_span(jnp.asarray(ik), block_q, block_kv, n_q, True,
                               window))
        want = (iq[:, None] >= first[None, :]) & (iq[:, None] <= last[None, :])
        assert np.array_equal(run, want), ("q", window)
        assert _band_extents(n_q, n_kv, block_q, block_kv, True, window) == (
            run.sum(1).max(), run.sum(0).max(), run.sum())
    assert _band_extents(n_q, n_kv, block_q, block_kv, False, None) == (
        n_kv, n_q, n_q * n_kv)


def _inner_extents(s, h, kvh, causal, window, block=512):
    """The innermost grid extent of the three built programs."""
    from fiber_tpu.ops.pallas_attention import _build_calls

    calls = _build_calls((s, h, 128), "float32", causal, block, block,
                         True, kvh, window)
    x = jax.ShapeDtypeStruct((h, s, 128), jnp.float32)
    kv = jax.ShapeDtypeStruct((kvh or h, s, 128), jnp.float32)
    col = jax.ShapeDtypeStruct((h, s, 1), jnp.float32)
    args = [(x, kv, kv), (x, kv, kv, x, col, col), (x, kv, kv, x, col, col)]
    grids = [jax.make_jaxpr(call)(*a).eqns[0].params["grid_mapping"].grid
             for call, a in zip(calls, args)]
    assert grids[0][:2] == grids[1][:2] == (h, s // block)
    assert grids[2][:3] == (kvh or h, s // block, h // (kvh or h))
    return [g[-1] for g in grids]


@pytest.mark.parametrize("causal,window,extent", [
    (True, 512, 2), (True, 4096, 9), (True, 500, 2), (True, 513, 2), (True, 514, 3),
    (True, None, 16), (False, None, 16),
])
def test_flash_grids_are_the_band(causal, window, extent):
    """At 8,192 tokens and 512-blocks the inner axis of all three grids
    spans the band: 2 blocks for window 512, 9 for 4,096, all 16 without
    a window (causal: the diagonal row needs them; non-causal: the grid
    it always had). An unaligned window costs one more block at most."""
    assert _inner_extents(8192, 8, 2, causal, window) == [extent] * 3


def test_flash_grid_steps_counter():
    """Building a program adds its inner steps a head to
    flash_grid_steps{kernel, state}: window 512 at 8,192 tokens runs 31
    of the band's 32 steps (256 on the square grid), dkv the same for
    each of its kv-head's 4 query heads."""
    import fiber_tpu
    from fiber_tpu import telemetry
    from fiber_tpu.ops.pallas_attention import _build_calls

    fiber_tpu.init()
    counter = telemetry.counter("flash_grid_steps")
    kernels = ("flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv")

    def read():
        return {(k, st): counter.value(kernel=k, state=st)
                for k in kernels for st in ("run", "idle")}

    def built(window, causal=True):
        _build_calls.cache_clear()
        before = read()
        _build_calls((8192, 8, 128), "float32", causal, 512, 512, True, 2,
                     window)
        after = read()
        return [(int(after[k, "run"] - before[k, "run"]),
                 int(after[k, "idle"] - before[k, "idle"]))
                for k in kernels]

    assert built(512) == [(31, 1), (31, 1), (124, 4)]
    assert built(4096) == [(108, 36), (108, 36), (432, 144)]
    assert built(None) == [(136, 120), (136, 120), (544, 480)]
    assert built(None, causal=False) == [(256, 0), (256, 0), (1024, 0)]


def _square_grid_calls(shape, causal, bq, bk, kv_heads, window):
    """The three programs as they were built before the band: every
    kernel on the full (n_q, n_kv) square, a step outside the band
    skipped by ``pl.when`` alone. Kept here, and not in the library, as
    the yardstick the banded programs must equal bit for bit. Shares
    the library's per-step arithmetic (`_run_window`, `_keep_mask`,
    `_bwd_p_ds`), which the band did not touch."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from fiber_tpu.ops.pallas_attention import (
        _NEG_INF, _bwd_p_ds, _keep_mask, _run_window)

    s, h, d = shape
    kvh = kv_heads or h
    group = h // kvh
    n_q, n_kv = s // bq, s // bk
    scale = 1.0 / (d ** 0.5)
    f32 = jnp.float32
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=f32)
    step = dict(block_q=bq, block_kv=bk, causal=causal, scale=scale,
                window=window)

    def fwd(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref):
        iq, ik = pl.program_id(1), pl.program_id(2)

        @pl.when(ik == 0)
        def _():
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        @pl.when(_run_window(iq, ik, bq, bk, causal, window))
        def _():
            q, k, v = (r[0].astype(f32) for r in (q_ref, k_ref, v_ref))
            sc = dot(q, k, (((1,), (1,)), ((), ()))) * scale
            keep = None
            if causal:
                keep = _keep_mask(iq, ik, bq, bk, window)
                sc = jnp.where(keep, sc, _NEG_INF)
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            if keep is not None:
                p = jnp.where(keep, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[:] = acc_ref[:] * corr + dot(
                p, v, (((1,), (0,)), ((), ())))
            m_ref[:] = m_new

        @pl.when(ik == n_kv - 1)
        def _():
            l = l_ref[:]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
            lse_ref[0] = m_ref[:] + jnp.log(safe_l)

    def dq(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc):
        iq, ik = pl.program_id(1), pl.program_id(2)

        @pl.when(ik == 0)
        def _():
            acc[:] = jnp.zeros_like(acc)

        @pl.when(_run_window(iq, ik, bq, bk, causal, window))
        def _():
            q, k, v, do = (r[0].astype(f32)
                           for r in (q_ref, k_ref, v_ref, do_ref))
            _, ds = _bwd_p_ds(q, k, v, do, lse_ref[0], delta_ref[0], iq,
                              ik, **step)
            acc[:] += dot(ds, k, (((1,), (0,)), ((), ())))

        @pl.when(ik == n_kv - 1)
        def _():
            dq_ref[0] = (acc[:] * scale).astype(dq_ref.dtype)

    def dkv(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
            dv_ref, dk_acc, dv_acc):
        ik, g, iq = (pl.program_id(i) for i in (1, 2, 3))

        @pl.when((g == 0) & (iq == 0))
        def _():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        @pl.when(_run_window(iq, ik, bq, bk, causal, window))
        def _():
            q, k, v, do = (r[0].astype(f32)
                           for r in (q_ref, k_ref, v_ref, do_ref))
            p, ds = _bwd_p_ds(q, k, v, do, lse_ref[0], delta_ref[0], iq,
                              ik, **step)
            dv_acc[:] += dot(p, do, (((0,), (0,)), ((), ())))
            dk_acc[:] += dot(ds, q, (((0,), (0,)), ((), ())))

        @pl.when((g == group - 1) & (iq == n_q - 1))
        def _():
            dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    spec_q = pl.BlockSpec((1, bq, d), lambda ih, iq, ik: (ih, iq, 0))
    spec_k = pl.BlockSpec((1, bk, d), lambda ih, iq, ik: (ih // group, ik, 0))
    row_q = pl.BlockSpec((1, bq, 1), lambda ih, iq, ik: (ih, iq, 0))
    hsd = jax.ShapeDtypeStruct((h, s, d), f32)
    kvsd = jax.ShapeDtypeStruct((kvh, s, d), f32)
    fwd_call = pl.pallas_call(
        fwd, grid=(h, n_q, n_kv), in_specs=[spec_q, spec_k, spec_k],
        out_specs=[spec_q, row_q],
        out_shape=[hsd, jax.ShapeDtypeStruct((h, s, 1), f32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), f32), pltpu.VMEM((bq, 1), f32),
                        pltpu.VMEM((bq, d), f32)],
        interpret=True)
    dq_call = pl.pallas_call(
        dq, grid=(h, n_q, n_kv),
        in_specs=[spec_q, spec_k, spec_k, spec_q, row_q, row_q],
        out_specs=spec_q, out_shape=hsd,
        scratch_shapes=[pltpu.VMEM((bq, d), f32)], interpret=True)
    dkv_q = pl.BlockSpec(
        (1, bq, d), lambda ikv, ik, g, iq: (ikv * group + g, iq, 0))
    dkv_k = pl.BlockSpec((1, bk, d), lambda ikv, ik, g, iq: (ikv, ik, 0))
    dkv_row = pl.BlockSpec(
        (1, bq, 1), lambda ikv, ik, g, iq: (ikv * group + g, iq, 0))
    dkv_call = pl.pallas_call(
        dkv, grid=(kvh, n_kv, group, n_q),
        in_specs=[dkv_q, dkv_k, dkv_k, dkv_q, dkv_row, dkv_row],
        out_specs=[dkv_k, dkv_k], out_shape=[kvsd, kvsd],
        scratch_shapes=[pltpu.VMEM((bk, d), f32), pltpu.VMEM((bk, d), f32)],
        interpret=True)
    return fwd_call, dq_call, dkv_call


@pytest.mark.parametrize("causal,window,block_q,block_kv", [
    (True, 100, 128, 128),      # windowed GQA, band 2 of 8
    (True, 300, 128, 256),      # block_q != block_kv, unaligned window
    (True, 300, 256, 128),
    (True, None, 128, 128),     # full causal: steps above the diagonal
    (False, None, 128, 128),    # the ring's past blocks: grid unchanged
])
def test_banded_kernels_equal_square_grid_bit_for_bit(causal, window,
                                                      block_q, block_kv):
    """The band changes which grid steps exist, never a sum or its
    order: out, lse, dq, dk and dv of the banded programs equal the
    square-grid programs' bit for bit (interpret mode, GQA group 2)."""
    from fiber_tpu.ops.pallas_attention import _build_calls

    S, H, KVH, D = 1024, 4, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, do = (jax.random.normal(key, (H, S, D)) for key in keys[:2])
    k, v = (jax.random.normal(key, (KVH, S, D)) for key in keys[2:4])
    dlse = jax.random.normal(keys[4], (H, S, 1))
    outs = []
    for fwd_call, dq_call, dkv_call in (
            _build_calls((S, H, D), "float32", causal, block_q, block_kv,
                         True, KVH, window),
            _square_grid_calls((S, H, D), causal, block_q, block_kv, KVH,
                               window)):
        out, lse = fwd_call(q, k, v)
        delta = jnp.sum(do * out, axis=-1, keepdims=True) - dlse
        dq = dq_call(q, k, v, do, lse, delta)
        dk, dv = dkv_call(q, k, v, do, lse, delta)
        outs.append([np.asarray(x) for x in (out, lse, dq, dk, dv)])
    for name, got, want in zip(("out", "lse", "dq", "dk", "dv"), *outs):
        assert np.isfinite(want).all() and np.abs(want).max() > 0, name
        assert np.array_equal(got, want), name


def test_tiny_lm_rope_planes_and_decode():
    """pos="rope": no learned position table in the params, rotary q/k
    per layer — identical logits across attention planes, KV-cache
    decode parity (the cache stores post-rotation keys), and training
    still learns."""
    from fiber_tpu.models import TinyLM, make_train_step

    kwargs = dict(vocab=32, dim=32, heads=4, kv_heads=2, layers=2,
                  max_seq=64, pos="rope")
    lm_ref = TinyLM(attention="reference", **kwargs)
    params = lm_ref.init(jax.random.PRNGKey(0))
    assert "pos" not in params
    tokens = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, 32)

    l_ref = float(lm_ref.loss(params, tokens))
    lm_flash = TinyLM(attention="flash", interpret=True, **kwargs)
    assert abs(float(lm_flash.loss(params, tokens)) - l_ref) < 1e-4

    # decode parity: incremental rope == full-apply rope
    prompt = tokens[:8]
    out = lm_ref.generate(params, prompt, steps=8)
    toks = [int(t) for t in prompt]
    for _ in range(8):
        padded = jnp.zeros((64,), jnp.int32).at[: len(toks)].set(
            jnp.asarray(toks, jnp.int32))
        logits = lm_ref.apply(params, padded)[len(toks) - 1]
        toks.append(int(jnp.argmax(logits)))
    assert [int(t) for t in out] == toks

    # and it trains
    import optax

    opt = optax.adamw(3e-3)
    step = make_train_step(lm_ref, opt)
    opt_state = opt.init(params)
    first = None
    for _ in range(15):
        params, opt_state, loss = step(params, opt_state, tokens)
        first = first if first is not None else float(loss)
    assert float(loss) < first

    with pytest.raises(ValueError, match="positional"):
        TinyLM(pos="alibi")
    with pytest.raises(ValueError, match="even"):
        TinyLM(dim=63 * 3, heads=9, pos="rope")  # head_dim 21, odd


def test_tiny_lm_window_trains_and_decodes():
    """TinyLM(window=): sliding-window training through the flash
    kernels, decode masked to the SAME window (inference must run the
    model training built), and loud validation for planes without a
    windowed engine."""
    from fiber_tpu.models import TinyLM
    from fiber_tpu.parallel import default_mesh

    model = TinyLM(vocab=32, dim=32, heads=4, layers=1, max_seq=64,
                   attention="flash", window=8,
                   interpret=True)  # window < decoded length, so
    # late positions genuinely DROP early context in both paths
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, 32)
    loss, grads = jax.value_and_grad(model.loss)(params, tokens)
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))

    # decode parity against full apply AT THE SAME WINDOW
    prompt = tokens[:8]
    out = model.generate(params, prompt, steps=8)
    toks = [int(t) for t in prompt]
    for _ in range(8):
        padded = jnp.zeros((64,), jnp.int32).at[: len(toks)].set(
            jnp.asarray(toks, jnp.int32))
        logits = model.apply(params, padded)[len(toks) - 1]
        toks.append(int(jnp.argmax(logits)))
    assert [int(t) for t in out] == toks

    with pytest.raises(ValueError, match="flash"):
        TinyLM(attention="ring", window=16)
    with pytest.raises(ValueError, match="single-device"):
        TinyLM(attention="flash", window=16, mesh=default_mesh())
    with pytest.raises(ValueError, match="window"):
        TinyLM(attention="flash", window=0)


def test_kernels_raise_off_tpu_instead_of_interpreting():
    """Interpret mode is something a caller asks for, never something
    the library picks: with the default (compile through Mosaic) the
    kernels, the planes that compose them and the LM that trains through
    them all refuse a CPU instead of quietly running the interpreter."""
    from fiber_tpu.models import TinyLM
    from fiber_tpu.ops.pallas_attention import flash_attention_lse
    from fiber_tpu.ops.ring_attention import ring_attention
    from fiber_tpu.ops.ulysses_attention import ulysses_attention
    from fiber_tpu.parallel import default_mesh

    q, k, v = _rand_qkv(128, 2, 16)
    q8, k8, v8 = _rand_qkv(128, 8, 16)
    for call in (
        lambda: flash_attention(q, k, v, causal=True),
        lambda: flash_attention(q, k, v, causal=True, interpret=False),
        lambda: flash_attention_lse(q, k, v, causal=True),
        lambda: ring_attention(q, k, v, causal=True, local="flash"),
        lambda: ulysses_attention(q8, k8, v8, causal=True,
                                  local="flash"),
    ):
        with pytest.raises(ValueError, match="[Ii]nterpret"):
            jax.block_until_ready(call())

    for mesh in (None, default_mesh()):
        with pytest.raises(ValueError, match="needs a TPU.*interpret=True"):
            TinyLM(attention="flash", mesh=mesh)
    # the explicit request is honored, and recorded on the model
    assert TinyLM(attention="flash", interpret=True).interpret is True
    # planes with no kernel in them are unaffected
    assert TinyLM(attention="ring").interpret is False


# -- a value width of its own (latent attention: q, k at 192, v at 128) -------
def _rand_qkv_widths(s, h, dqk, dv):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(17), 3)
    return (jax.random.normal(kq, (s, h, dqk)),
            jax.random.normal(kk, (s, h, dqk)),
            jax.random.normal(kv, (s, h, dv)))


@pytest.mark.parametrize("with_lse", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_value_width_of_its_own(causal, with_lse):
    """q and k 48 wide, v 32: the output is (S, H, 32), scaled by 48^-0.5,
    and the output, the lse and all three gradients (an lse cotangent
    too) equal plain attention's, over several blocks each way."""
    from fiber_tpu.ops.pallas_attention import flash_attention_lse

    q, k, v = _rand_qkv_widths(256, 2, 48, 32)
    keys = jax.random.split(jax.random.PRNGKey(19), 2)
    tgt = jax.random.normal(keys[0], (256, 2, 32))
    w_lse = jax.random.normal(keys[1], (2, 256))

    def plain(q, k, v):
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(48.0)
        if causal:
            keep = jnp.arange(256)[:, None] >= jnp.arange(256)[None, :]
            s = jnp.where(keep[None], s, -jnp.inf)
        return (jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v),
                jax.nn.logsumexp(s, axis=-1))

    def flash(q, k, v):
        kw = dict(causal=causal, block_q=128, block_kv=64, interpret=True)
        if with_lse:
            return flash_attention_lse(q, k, v, **kw)
        return flash_attention(q, k, v, **kw), None

    def loss(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            total = jnp.sum((out - tgt) ** 2)
            return total + (jnp.sum(w_lse * lse) if with_lse else 0.0)
        return f

    out, lse = flash(q, k, v)
    want_out, want_lse = plain(q, k, v)
    assert out.shape == (256, 2, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               atol=2e-5)
    if with_lse:
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                   atol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape, name
        rel = np.abs(np.asarray(a - b)).max() / np.abs(np.asarray(b)).max()
        assert rel < 1e-4, (name, rel)


def test_flash_equal_widths_keep_their_cache_key(monkeypatch):
    """A call whose values are as wide as its queries hands the builder
    None for the value width (as MHA hands it None for kv_heads), so its
    programs are the ones one width always built; another width is
    named."""
    from fiber_tpu.ops import pallas_attention as pa

    seen = []
    real = pa._build

    def build(*key):
        seen.append(key)
        return real(*key)

    monkeypatch.setattr(pa, "_build", build)
    q, k, v = _rand_qkv(128, 2, 32)
    pa.flash_attention(q, k, v, causal=True, interpret=True)
    pa.flash_attention(q, k, v[..., :16], causal=True, interpret=True)
    assert seen[0] == ((128, 2, 32), "float32", True, 512, 512, True,
                       None, None, None, None)
    assert seen[1][:-1] == seen[0][:-1] and seen[1][-1] == 16
