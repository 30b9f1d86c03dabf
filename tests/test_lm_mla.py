"""Latent attention (``Block(mixer="latent")``), the pairwise rope and the
multi-token-prediction module (``BlockLM(mtp=...)``): against the plain
reference the benchmark checks the chip runs with
(``perfbench/reference/lm_mla_plain.py``, which imports nothing of the
program), against a hand-written rotation of adjacent pairs, and as a chip's
share of an expert-parallel layer. Small sizes, seeded weights, the CPU; the
flash kernels run in the Pallas interpreter.
"""

import importlib.util
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from fiber_tpu.models import (  # noqa: E402
    MTP, Block, BlockLM, Experts, Latent, Rope, make_train_step)


def _load_reference():
    path = os.path.join(ROOT, "perfbench", "reference", "lm_mla_plain.py")
    spec = importlib.util.spec_from_file_location("lm_mla_plain", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

S, DIM, HEADS, VOCAB = 32, 32, 4, 61
LAT = dict(q_rank=24, kv_rank=16, nope=8, rope_dim=4, v_dim=8)   # qk 12, v 8
EXPERTS = dict(total=8, top_k=2, width=16, shared_width=16, scale=2.5)
BASE, WEIGHT = 1e4, 0.3


def plain_spec(share=(1, 2), weight=WEIGHT):
    experts = {"ffn": "experts", "experts": dict(EXPERTS)}
    return {"vocab": VOCAB, "dim": DIM, "heads": HEADS, **LAT,
            "rope_base": BASE, "interleaved": True, "norm_eps": 1e-6,
            "share": share,
            "layers": [{"ffn": "gated", "width": 40}, experts, experts],
            "mtp": {"depth": 1, "weight": weight, "layer": experts}}


def block_of(spec, layer):
    e = layer.get("experts")
    return Block(heads=spec["heads"], mixer="latent", latent=Latent(**LAT),
                 rope=Rope(base=spec["rope_base"], interleaved=True),
                 ffn=layer["ffn"], width=layer.get("width", 0),
                 experts=(Experts(share=spec["share"], chunk_rows=16, **e)
                          if e else None))


def model_of(spec=None, *, attention="reference", mtp=True, **kw):
    spec = spec or plain_spec()
    m = spec["mtp"]
    return BlockLM([block_of(spec, layer) for layer in spec["layers"]],
                   vocab=spec["vocab"], dim=spec["dim"], head_dim=12,
                   kv_heads=spec["heads"], max_seq=S, attention=attention,
                   interpret=attention == "flash",
                   mtp=(MTP(block=block_of(spec, m["layer"]),
                            weight=m["weight"]) if mtp else None), **kw)


def tokens_of(i):
    return jax.random.randint(jax.random.PRNGKey(100 + i), (S,), 0, VOCAB)


def assert_close(ours, theirs, rel=2e-4):
    """Leaf by leaf, against the largest element of the reference's leaf."""
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ours)[0],
                            jax.tree.leaves(theirs)):
        top = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=rel * top + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("attention,recompute,head_block", [
    ("reference", None, None), ("flash", "layer", 16)])
def test_loss_and_gradients_are_the_references(attention, recompute,
                                               head_block):
    """The same weights from the same key (the init stream), then the loss
    (main term plus the MTP term) and the gradient of every leaf, the MTP
    module's too; with the flash kernels at q/k 12 and v 8, each layer
    recomputed and both heads blocked."""
    model = model_of(attention=attention, recompute=recompute,
                     head_block=head_block)
    spec = plain_spec()
    key = jax.random.PRNGKey(3)
    params, theirs = model.init(key), ref.init_params(key, spec)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(theirs))
    assert_close(params, theirs, rel=0)
    tokens = tokens_of(0)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params,
                                                              tokens)
        (want, _), want_g = jax.jit(jax.value_and_grad(
            lambda p, t: ref.sequence_loss(p, t, spec, row_block=16),
            has_aux=True))(theirs, tokens)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert_close(grads, want_g)
    assert float(jnp.abs(grads["mtp"]["eh_proj"]).max()) > 0


def test_the_mtp_term_apart():
    """The loss's second term alone: the program's loss at weight 0.3 less
    its loss at weight 0 is 0.3 times the MTP head's mean cross-entropy
    against the token after next, as the reference has it (its loss less
    its loss without the term)."""
    tokens = tokens_of(1)
    key = jax.random.PRNGKey(4)
    spec = plain_spec()
    with jax.default_matmul_precision("highest"):
        with_term = model_of()
        params = with_term.init(key)
        ours = (jax.jit(with_term.loss)(params, tokens)
                - jax.jit(model_of(plain_spec(weight=0.0)).loss)(params,
                                                                 tokens))
        theirs = jax.jit(lambda p, t: (
            ref.sequence_loss(p, t, spec)[0]
            - ref.sequence_loss(p, t, spec, faults=("no_mtp",))[0]))(
            params, tokens)
    assert float(ours) == pytest.approx(float(theirs), rel=1e-5)
    assert float(ours) / WEIGHT == pytest.approx(np.log(VOCAB), rel=0.1)


def test_one_adamw_step_is_the_references():
    """One step of ``make_train_step`` (optax's AdamW) against the
    reference's written-out AdamW: the loss and every leaf after it."""
    import optax

    hyper = dict(weight_decay=1e-4, b1=0.9, b2=0.999, eps=1e-8)
    model = model_of()
    params = model.init(jax.random.PRNGKey(5))
    tokens = tokens_of(2)
    with jax.default_matmul_precision("highest"):
        opt = optax.adamw(3e-4, **hyper)
        new, _, loss = make_train_step(model, opt)(params, opt.init(params),
                                                   tokens)
        step = ref.make_train_step(plain_spec(), lr=3e-4, row_block=16,
                                   **hyper)
        copy = jax.tree.map(jnp.array, params)
        want, _, want_loss, _, _ = step(copy, ref.adamw_init(copy), tokens)
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    moved = jax.tree.map(lambda a, b: a - b, new, params)
    assert_close(moved, jax.tree.map(lambda a, b: a - b, want, params),
                 rel=2e-3)


def test_the_rope_turns_adjacent_pairs():
    """``Rope(interleaved=True)`` turns features (2j, 2j+1) by position x
    base^(-2j/r), written out pair by pair; the scores it gives equal those
    of transformers' reading (permute both to the half-split order, then
    rotate_half), and the half-split rope's differ."""
    r, base = 8, 1e4
    x = jax.random.normal(jax.random.PRNGKey(6), (S, 3, r))
    y = jax.random.normal(jax.random.PRNGKey(7), (S, 3, r))
    model = model_of()
    cos, sin = model._rope_angles(jnp.arange(S), r, base)
    cos, sin = cos[:, None, :], sin[:, None, :]
    got = np.asarray(BlockLM._rope_rotate(x, cos, sin, True))
    want = np.zeros_like(got)
    xs = np.asarray(x)
    for i in range(S):
        for j in range(r // 2):
            a = i * base ** (-2.0 * j / r)
            c, s = np.cos(a), np.sin(a)
            want[i, :, 2 * j] = xs[i, :, 2 * j] * c - xs[i, :, 2 * j + 1] * s
            want[i, :, 2 * j + 1] = (xs[i, :, 2 * j + 1] * c
                                     + xs[i, :, 2 * j] * s)
    np.testing.assert_allclose(got, want, atol=2e-5)

    def scores(f):
        return np.einsum("shd,thd->hst", f(x), f(y))

    perm = np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])
    pairwise = scores(lambda v: BlockLM._rope_rotate(v, cos, sin, True))
    np.testing.assert_allclose(
        pairwise,
        scores(lambda v: BlockLM._rope_rotate(v[..., perm], cos, sin)),
        rtol=1e-4, atol=1e-4)
    assert not np.allclose(
        pairwise, scores(lambda v: BlockLM._rope_rotate(v, cos, sin)),
        atol=1e-2)


def test_the_shares_add_up_to_the_uncut_layer():
    """A latent + expert layer over 8 experts cut into 8 shares of one: what
    the 8 programs give, less the part every share computes alike (the
    stream, attention and the shared expert, counted once), is the uncut
    reference layer's."""
    n = EXPERTS["total"]
    uncut = plain_spec(share=(0, 1))
    layer = uncut["layers"][1]
    key = jax.random.PRNGKey(8)
    blk = ref.init_params(key, uncut)["blocks"][1]
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (S, DIM))
    positions = jnp.arange(S)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.layer_apply(x, blk, layer, uncut, positions, 16)

        def program(share, weights):
            spec = plain_spec(share=share)
            model = model_of(spec, mtp=False)
            ropes = {k: (c[:, None, :], s[:, None, :]) for k, (c, s)
                     in model._rope_tables(positions).items()}
            return model._layer(model.blocks[1], weights, x, ropes)

        parts = [program((i, n), {
            k: (v[i:i + 1] if k.startswith("experts_") else v)
            for k, v in blk.items()}) for i in range(n)]
        alike = program((0, n), {
            k: (jnp.zeros_like(v[:1]) if k.startswith("experts_") else v)
            for k, v in blk.items()})
    got = sum(parts) - (n - 1) * alike
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_refusals():
    """What the model cannot run yet is refused, with the reason: decode,
    a mesh of more than one device, passes, another attention plane, an
    MTP of depth 2, a latent layer with a window or without its rope."""
    from jax.sharding import Mesh

    model = model_of()
    with pytest.raises(ValueError, match="do not decode"):
        model.init_caches(jnp.float32)
    with pytest.raises(ValueError, match="do not decode"):
        model.generate(model.init(jax.random.PRNGKey(0)), jnp.arange(3), 2)
    with pytest.raises(ValueError, match="do not decode"):
        model_of(mtp=False).init_caches(jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("pool",))
    for kw, match in (
            (dict(mesh=mesh), "one device"),
            (dict(passes=2), "passes"),
            (dict(attention="ring"), "flash kernels or the reference"),
            (dict(attention="ulysses"), "flash kernels or the reference")):
        with pytest.raises(ValueError, match=match):
            model_of(**kw)
    spec = plain_spec()
    with pytest.raises(ValueError, match="depth 2"):
        BlockLM([block_of(spec, spec["layers"][0])], vocab=VOCAB, dim=DIM,
                head_dim=12, kv_heads=HEADS, max_seq=S,
                attention="reference",
                mtp=MTP(block=block_of(spec, spec["layers"][0]), depth=2))
    base = block_of(spec, spec["layers"][0])
    for bad, match in ((dict(window=8), "window"), (dict(rope=None), "rope"),
                       (dict(latent=None), "latent=")):
        import dataclasses

        with pytest.raises(ValueError, match=match):
            BlockLM([dataclasses.replace(base, **bad)], vocab=VOCAB,
                    dim=DIM, head_dim=12, kv_heads=HEADS, max_seq=S,
                    attention="reference")


def test_span_fields_and_the_two_counters():
    """The step's span says the latent widths and the MTP's depth and
    weight; a traced loss moves ``latent_layers_traced`` once a layer
    application (three layers and the module's) and ``mtp_traced`` once."""
    import fiber_tpu
    from fiber_tpu import telemetry

    fiber_tpu.init()
    model = model_of(recompute="layer")
    fields = model.span_fields
    assert fields["latent"] == "q24/kv16/qk8+4/v8"
    assert fields["mtp"] == "1/0.3"
    assert fields["layers"] == "latent/gated,latent/experts,latent/experts"
    latent = telemetry.counter("latent_layers_traced")
    mtp = telemetry.counter("mtp_traced")
    labels = dict(heads="4", q_rank="24", kv_rank="16", qk_dim="12",
                  v_dim="8")
    before = latent.value(**labels), mtp.value(depth="1", weight="0.3")
    params = model.init(jax.random.PRNGKey(0))
    jax.jit(jax.grad(model.loss)).lower(params, tokens_of(0))
    assert latent.value(**labels) - before[0] == 4
    assert mtp.value(depth="1", weight="0.3") - before[1] == 1


def test_routing_taps_the_mtp_expert_layer():
    """``routing`` gives the two expert layers of the stack and, last, the
    module's: 3 expert layers, as the reference's taken ids."""
    model = model_of()
    params = model.init(jax.random.PRNGKey(2))
    tokens = tokens_of(3)
    with jax.default_matmul_precision("highest"):
        found = jax.jit(model.routing)(params, tokens)
        _, taken = jax.jit(lambda p, t: ref.sequence_loss(
            p, t, plain_spec()))(params, tokens)
    assert found["ids"].shape == (3, S, EXPERTS["top_k"])
    np.testing.assert_array_equal(np.sort(np.asarray(found["ids"]), -1),
                                  np.asarray(taken))
