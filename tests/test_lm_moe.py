"""A model built from a description of its layers (``BlockLM``) and the
sparse-expert feed-forward (``fiber_tpu.ops.moe``), against the plain
reference the benchmark checks the chip runs with
(``perfbench/reference/lm_moe_plain.py``, which imports nothing of the
program). Small sizes, seeded weights, the CPU; the flash kernels run in
the Pallas interpreter where a layer has a window.
"""

import importlib.util
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from fiber_tpu.models import (  # noqa: E402
    Block, BlockLM, Experts, Rope, TinyLM, Yarn, make_train_step)
from fiber_tpu.ops import moe  # noqa: E402


def _load_reference():
    path = os.path.join(ROOT, "perfbench", "reference", "lm_moe_plain.py")
    spec = importlib.util.spec_from_file_location("lm_moe_plain", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

# -- a small model with every kind of layer ---------------------------------
S, DIM, DH, KVH, VOCAB, WINDOW = 64, 32, 16, 2, 48, 16
YARN = dict(factor=8.0, original_max_position=16, beta_fast=4.0,
            beta_slow=1.0, attention_factor=None)
FULL_ROPE = dict(base=500.0, rotary=8, yarn=YARN)
WINDOW_ROPE = dict(base=10000.0, rotary=None, yarn=None)
EXPERTS = dict(total=16, top_k=4, width=8, shared_width=8, scale=2.5)


def plain_spec(share=(0, 2)):
    """Full + dense, window + experts, full + experts; 6 and 8 query heads
    over 2 KV heads; two ropes."""
    return {"vocab": VOCAB, "dim": DIM, "head_dim": DH, "kv_heads": KVH,
            "share": share, "layers": [
                {"heads": 6, "window": None, "rope": FULL_ROPE,
                 "ffn": "gated", "width": 40},
                {"heads": 8, "window": WINDOW, "rope": WINDOW_ROPE,
                 "ffn": "experts", "experts": EXPERTS},
                {"heads": 6, "window": None, "rope": FULL_ROPE,
                 "ffn": "experts", "experts": EXPERTS}]}


def model_of(spec, chunk_rows=32, attention="flash"):
    def rope(r):
        return Rope(base=r["base"], rotary=r["rotary"],
                    yarn=Yarn(**r["yarn"]) if r["yarn"] else None)

    blocks = [Block(heads=layer["heads"], window=layer["window"],
                    rope=rope(layer["rope"]), ffn=layer["ffn"],
                    width=layer.get("width", 0),
                    experts=(Experts(share=spec["share"],
                                     chunk_rows=chunk_rows,
                                     **layer["experts"])
                             if layer["ffn"] == "experts" else None))
              for layer in spec["layers"]]
    return BlockLM(blocks, vocab=spec["vocab"], dim=spec["dim"],
                   head_dim=spec["head_dim"], kv_heads=spec["kv_heads"],
                   max_seq=S, attention=attention, interpret=True)


def tokens_of(seed):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, (S,), dtype=np.int32))


def leaves(tree):
    return {jax.tree_util.keystr(path): np.asarray(x) for path, x
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def assert_trees_close(a, b, rtol, atol):
    a, b = leaves(a), leaves(b)
    assert a.keys() == b.keys()
    for name in a:
        np.testing.assert_allclose(a[name], b[name], rtol=rtol, atol=atol,
                                   err_msg=name)


# -- (a) the model against the plain reference --------------------------------
def test_init_follows_the_documented_stream():
    """Program and reference draw the same leaves from one key: the same
    names, the same bits."""
    spec = plain_spec()
    key = jax.random.PRNGKey(3)
    ours, theirs = leaves(model_of(spec).init(key)), leaves(
        ref.init_params(key, spec))
    assert ours.keys() == theirs.keys()
    assert all((ours[n] == theirs[n]).all() for n in ours)
    assert ours["['blocks'][1]['experts_wg']"].shape == (8, DIM, 8)
    assert ours["['blocks'][1]['router']"].shape == (DIM, 16)
    assert ours["['blocks'][1]['wq']"].shape == (DIM, 8 * DH)
    assert ours["['blocks'][0]['wq']"].shape == (DIM, 6 * DH)


@pytest.mark.parametrize("share", [(0, 2), (1, 2), (0, 1)])
def test_loss_gradients_and_one_adamw_step_match_the_reference(share):
    import optax

    spec = plain_spec(share)
    model = model_of(spec)
    key = jax.random.PRNGKey(11)
    tokens = tokens_of(5)
    params = model.init(key)

    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
    (ref_loss, ref_ids), ref_grads = jax.jit(jax.value_and_grad(
        lambda p, t: ref.sequence_loss(p, t, spec, row_block=32),
        has_aux=True))(ref.init_params(key, spec), tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-6)
    assert_trees_close(grads, ref_grads, rtol=2e-4, atol=2e-7)

    hyper = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)
    opt = optax.adamw(3e-4, **hyper)
    step = make_train_step(model, opt)
    new, _, step_loss = step(params, opt.init(params), tokens)
    ref_step = ref.make_train_step(spec, lr=3e-4, row_block=32, **hyper)
    p_ref = ref.init_params(key, spec)
    ref_new, _, ref_step_loss, _, _ = ref_step(
        p_ref, ref.adamw_init(p_ref), tokens)
    np.testing.assert_allclose(float(step_loss), float(ref_step_loss),
                               rtol=2e-6)
    # one Adam step moves every weight by about lr, whatever its gradient
    # (an element whose gradient is near eps may move differently): compare
    # each leaf's move as a whole
    moved = leaves(jax.tree.map(lambda a, b: a - b, new, model.init(key)))
    ref_moved = leaves(jax.tree.map(lambda a, b: a - b, ref_new,
                                    ref.init_params(key, spec)))
    for name, move in ref_moved.items():
        assert (np.linalg.norm(moved[name] - move)
                <= 5e-3 * np.linalg.norm(move)), name

    # the taken experts, and the load of each held one
    found = model.probe_routing(params, tokens)
    assert found["ids"].shape == (2, S, 4)
    assert (np.sort(found["ids"], axis=-1) == np.asarray(ref_ids)).all()
    first, count = moe.held_experts(16, share)
    for layer in range(2):
        expect = np.bincount(found["ids"][layer].ravel(),
                             minlength=16)[first:first + count]
        assert (found["load"][layer] == expect).all()


# -- (b) the share tied to the model -------------------------------------------
def layer_weights(key, total=16, dim=DIM, width=8):
    ks = jax.random.split(key, 8)

    def normal(k, *shape):
        return 0.3 * jax.random.normal(k, shape)
    return {"router": normal(ks[0], dim, total),
            "shared_wg": normal(ks[1], dim, width),
            "shared_wu": normal(ks[2], dim, width),
            "shared_wd": normal(ks[3], width, dim),
            "experts_wg": normal(ks[4], total, dim, width),
            "experts_wu": normal(ks[5], total, dim, width),
            "experts_wd": normal(ks[6], total, width, dim),
            "h": jax.random.normal(ks[7], (S, dim))}


def share_of(blk, share, total=16):
    first, count = moe.held_experts(total, share)
    return dict(blk, **{name: blk[name][first:first + count] for name in
                        ("experts_wg", "experts_wu", "experts_wd")})


def layer_of(blk, share, chunk_rows=32, total=16, **kw):
    first, _ = moe.held_experts(total, share)
    return moe.moe_ffn(blk["h"], share_of(blk, share, total), total=total,
                       top_k=4, scale=2.5, first=first,
                       chunk_rows=chunk_rows, **kw)


@pytest.mark.parametrize("shares", [1, 2, 4, 16])
def test_the_shares_parts_add_up_to_the_uncut_layer(shares):
    """What every share computes of its own experts, plus the shared expert
    counted once, is the whole layer as the uncut reference gives it."""
    blk = layer_weights(jax.random.PRNGKey(shares))
    whole, _ = ref.expert_layer(blk["h"], blk, EXPERTS, (0, 1))
    shared = moe.swiglu(blk["h"], blk["shared_wg"], blk["shared_wu"],
                        blk["shared_wd"])
    parts = [layer_of(blk, (i, shares)) - shared for i in range(shares)]
    np.testing.assert_allclose(np.asarray(shared + sum(parts)),
                               np.asarray(whole), rtol=2e-5, atol=2e-6)
    # and each share alone is the reference's for that share
    for i in range(shares):
        theirs, _ = ref.expert_layer(blk["h"], share_of(blk, (i, shares)),
                                     EXPERTS, (i, shares))
        np.testing.assert_allclose(np.asarray(shared + parts[i]),
                                   np.asarray(theirs), rtol=2e-5, atol=2e-6)


def test_a_share_that_does_not_divide_the_experts_is_refused():
    with pytest.raises(ValueError, match="does not divide"):
        moe.held_experts(16, (0, 3))
    with pytest.raises(ValueError, match="does not divide"):
        moe.held_experts(16, (2, 2))
    assert moe.held_experts(256, (3, 32)) == (24, 8)


# -- (c) dropless ----------------------------------------------------------------
def rigged(blk, experts):
    """Router weights under which every token takes exactly ``experts``:
    rows made positive, those columns +1 and the others -1."""
    column = -np.ones((16,), np.float32)
    column[list(experts)] = 1.0
    return dict(blk, h=jnp.abs(blk["h"]) + 0.1,
                router=jnp.asarray(column)[None, :]
                * (1.0 + jnp.arange(16) * 1e-3)[None, :]
                * jnp.ones((DIM, 1)))


@pytest.mark.parametrize("chunk_rows", [16, 32, 4096])
def test_no_token_is_dropped_whatever_the_routing(chunk_rows):
    """Every token on held experts (all S x top_k pairs here, many chunks),
    no token on a held expert (no pair, no chunk), and fresh weights: one
    compiled layer serves all three and equals the reference."""
    blk = layer_weights(jax.random.PRNGKey(2))
    share = (0, 2)                                   # holds experts 0..7
    cases = {"all": rigged(blk, (0, 1, 2, 3)),
             "none": rigged(blk, (8, 9, 10, 11)),
             "some": blk}

    @jax.jit
    def value_and_grads(blk):
        def f(b):
            return jnp.sum(layer_of(dict(blk, **b), share,
                                    chunk_rows=chunk_rows) ** 2)
        return jax.value_and_grad(f)(
            {k: blk[k] for k in blk if k != "router"})

    def ref_value_and_grads(blk):
        def f(b):
            y, _ = ref.expert_layer(b["h"], share_of(dict(blk, **b), share),
                                    EXPERTS, share)
            return jnp.sum(y ** 2)
        return jax.value_and_grad(f)(
            {k: blk[k] for k in blk if k != "router"})

    for name, case in cases.items():
        load = moe.expert_load(moe.route(case["h"], case["router"],
                                         top_k=4)[0], 0, 8)
        assert int(load.sum()) == {"all": S * 4, "none": 0}.get(
            name, int(load.sum())), name
        value, grads = value_and_grads(case)
        ref_value, ref_grads = ref_value_and_grads(case)
        np.testing.assert_allclose(float(value), float(ref_value),
                                   rtol=1e-5, err_msg=name)
        scale = max(float(np.abs(g).max()) for g in leaves(ref_grads).values())
        assert_trees_close(grads, ref_grads, rtol=1e-4, atol=1e-5 * scale)
    assert value_and_grads._cache_size() == 1


def test_rows_that_no_group_owns_are_never_read(monkeypatch):
    """The TPU's grouped kernel leaves the rows past the last group as it
    found them, in a product and in the gradient of its rows alike; the
    CPU zeroes them. A grouped product that poisons those rows, both
    ways, changes neither the layer nor any gradient."""
    real = moe._grouped

    def poison(rows, sizes):
        past = jnp.arange(rows.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], 1e9, rows)

    @jax.custom_vjp
    def poisoned(rows, matrices, sizes):
        return poison(real(rows, matrices, sizes), sizes)

    def fwd(rows, matrices, sizes):
        return poisoned(rows, matrices, sizes), (rows, matrices, sizes)

    def bwd(res, ct):
        rows, matrices, sizes = res
        d_rows, d_matrices = jax.vjp(
            lambda r, m: real(r, m, sizes), rows, matrices)[1](ct)
        return poison(d_rows, sizes), d_matrices, None

    poisoned.defvjp(fwd, bwd)
    blk = layer_weights(jax.random.PRNGKey(7))

    def value_and_grads(blk):
        def f(b):
            return jnp.sum(layer_of(b, (0, 2), chunk_rows=48) ** 2)
        return jax.value_and_grad(f)(blk)

    clean = value_and_grads(blk)
    monkeypatch.setattr(moe, "_grouped", poisoned)
    moe._routed.cache_clear()
    dirty = value_and_grads(blk)
    moe._routed.cache_clear()
    assert float(clean[0]) == float(dirty[0])
    assert_trees_close(clean[1], dirty[1], rtol=0, atol=0)
    # and the poison is there to be read: the bare product shows it
    sizes = jnp.asarray([3, 0, 2], jnp.int32)
    out = poisoned(jnp.ones((8, DIM)), jnp.ones((3, DIM, 4)), sizes)
    assert float(out[5:].min()) == 1e9 and float(out[:5].max()) == DIM


def test_the_routers_weights_get_their_gradient():
    """The weights' path (scores, the renormalised taken, the scale)
    carries gradient to the router through the dispatch."""
    blk = layer_weights(jax.random.PRNGKey(4))

    def ours(router):
        return jnp.sum(layer_of(dict(blk, router=router), (1, 2)) ** 2)

    def theirs(router):
        b = share_of(dict(blk, router=router), (1, 2))
        return jnp.sum(ref.expert_layer(blk["h"], b, EXPERTS, (1, 2))[0] ** 2)

    g, g_ref = jax.grad(ours)(blk["router"]), jax.grad(theirs)(blk["router"])
    assert float(jnp.abs(g_ref).max()) > 0
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=2e-4,
                               atol=1e-5 * float(jnp.abs(g_ref).max()))


# -- (d) the ropes ---------------------------------------------------------------
def test_yarn_table_at_closed_form_frequencies():
    """Laguna-XS.2's full-attention rope: 64 of 128 features, theta
    500,000, factor 64, original length 4,096, beta 64 and 1. The ramp
    runs from index 5 to 16: frequency 0 is the rope's own, frequency 31
    is divided by 64, frequency 10 is 5/11 of the way."""
    rope = Rope(base=500000.0, rotary=64, yarn=Yarn(
        factor=64.0, original_max_position=4096, beta_fast=64.0,
        beta_slow=1.0, attention_factor=1.4158883083359672))
    r, inv, factor = rope.table(128)
    assert r == 64 and inv.shape == (32,)
    plain = lambda i: 500000.0 ** (-2.0 * i / 64)            # noqa: E731
    assert inv[0] == pytest.approx(1.0, rel=1e-6)
    assert inv[31] == pytest.approx(plain(31) / 64, rel=1e-6)
    assert inv[10] == pytest.approx(
        plain(10) * (6 / 11) + plain(10) / 64 * (5 / 11), rel=1e-6)
    assert factor == pytest.approx(0.1 * np.log(64.0) + 1.0, rel=1e-9)
    # the reference computes the same table its own way
    _, theirs, their_factor = ref.rope_frequencies(
        dict(base=500000.0, rotary=64, yarn=dict(
            factor=64.0, original_max_position=4096, beta_fast=64.0,
            beta_slow=1.0, attention_factor=None)), 128)
    np.testing.assert_allclose(inv, theirs, rtol=1e-6)
    assert their_factor == pytest.approx(1.4158883083359672, rel=1e-9)
    # no attention factor given: 0.1 ln(factor) + 1
    assert Rope(yarn=Yarn(factor=64.0, original_max_position=4096)).table(
        128)[2] == pytest.approx(1.4158883083359672, rel=1e-9)


def test_partial_rotation_leaves_the_other_features_untouched():
    x = jax.random.normal(jax.random.PRNGKey(0), (S, 3, DH))
    cos, sin = BlockLM._rope_angles(jnp.arange(S), 8, 500.0)
    out = BlockLM._rope_rotate(x, cos[:, None, :], sin[:, None, :])
    assert (np.asarray(out[..., 8:]) == np.asarray(x[..., 8:])).all()
    whole = BlockLM._rope_rotate(x[..., :8], cos[:, None, :],
                                 sin[:, None, :])
    assert (np.asarray(out[..., :8]) == np.asarray(whole)).all()
    assert not np.allclose(np.asarray(out[1:, :, :8]),
                           np.asarray(x[1:, :, :8]))
    theirs = ref._rope(x, jnp.arange(S), dict(base=500.0, rotary=8, yarn=None))
    np.testing.assert_allclose(np.asarray(out), np.asarray(theirs),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rotary", [3, 0, 18])
def test_a_rope_that_does_not_fit_the_head_is_refused(rotary):
    with pytest.raises(ValueError, match="rope rotates"):
        BlockLM([Block(heads=2, rope=Rope(rotary=rotary), width=8)],
                vocab=8, dim=16, head_dim=16, kv_heads=2, max_seq=8,
                attention="reference")


# -- (e) TinyLM is the uniform description ------------------------------------------
def _tinylm_init_before(key, *, vocab, dim, heads, kv_heads, layers,
                        mlp_mult, max_seq, learned):
    """TinyLM.init as it was before the model became a description."""
    k_emb, k_pos, k_out, key = jax.random.split(key, 4)
    scale = 0.02
    params = {"embed": scale * jax.random.normal(k_emb, (vocab, dim)),
              "out": scale * jax.random.normal(k_out, (dim, vocab)),
              "final_norm": jnp.ones((dim,)), "blocks": []}
    if learned:
        params["pos"] = scale * jax.random.normal(k_pos, (max_seq, dim))
    for _ in range(layers):
        keys = jax.random.split(key, 7)
        key = keys[6]
        d, h = dim, mlp_mult * dim
        blk = {"norm1": jnp.ones((d,)),
               "wo": scale * jax.random.normal(keys[1], (d, d)),
               "norm2": jnp.ones((d,)),
               "w1": scale * jax.random.normal(keys[2], (d, h)),
               "b1": jnp.zeros((h,)),
               "w2": scale * jax.random.normal(keys[3], (h, d)),
               "b2": jnp.zeros((d,))}
        if kv_heads == heads:
            blk["wqkv"] = scale * jax.random.normal(keys[0], (d, 3 * d))
        else:
            kv_dim = kv_heads * (dim // heads)
            blk["wq"] = scale * jax.random.normal(keys[0], (d, d))
            blk["wkv"] = scale * jax.random.normal(keys[4], (d, 2 * kv_dim))
        params["blocks"].append(blk)
    return params


@pytest.mark.parametrize("kv_heads,pos", [(8, "learned"), (2, "rope"),
                                          (8, "rope")])
def test_tinylm_init_gives_the_same_leaves_bit_for_bit(kv_heads, pos):
    model = TinyLM(vocab=40, dim=64, heads=8, layers=3, max_seq=16,
                   mlp_mult=3, kv_heads=kv_heads, pos=pos,
                   attention="reference")
    key = jax.random.PRNGKey(9)
    now = leaves(model.init(key))
    before = leaves(_tinylm_init_before(
        key, vocab=40, dim=64, heads=8, kv_heads=kv_heads, layers=3,
        mlp_mult=3, max_seq=16, learned=pos == "learned"))
    assert now.keys() == before.keys()
    assert all(now[n].dtype == before[n].dtype
               and (now[n] == before[n]).all() for n in now)


def test_tinylm_is_the_uniform_description():
    model = TinyLM(vocab=40, dim=64, heads=8, layers=3, max_seq=16,
                   kv_heads=2, pos="rope", attention="flash", window=8,
                   interpret=True)
    assert isinstance(model, BlockLM) and len(model.blocks) == 3
    assert set(model.blocks) == {Block(heads=8, window=8, rope=Rope(),
                                       ffn="mlp", width=256)}
    assert (model.heads, model.window, model.mlp_mult, model.layers,
            model.head_dim) == (8, 8, 4, 3, 8)
    assert model.span_fields == {"layers": ",".join(["window/mlp"] * 3)}
    # the same layers spelt out compute the same logits
    spelt = BlockLM(model.blocks, vocab=40, dim=64, head_dim=8, kv_heads=2,
                    max_seq=16, attention="flash", interpret=True)
    params = model.init(jax.random.PRNGKey(1))
    tokens = jnp.arange(16, dtype=jnp.int32) % 40
    assert (np.asarray(model.apply(params, tokens))
            == np.asarray(spelt.apply(params, tokens))).all()


# -- construction ---------------------------------------------------------------------
@pytest.mark.parametrize("block,match", [
    (Block(heads=3, width=8), "not divisible by kv_heads"),
    (Block(heads=2, window=4, width=8), "needs attention='flash'"),
    (Block(heads=2, window=0, width=8), "window must be"),
    (Block(heads=2, rope=None, width=8), "pos='rope' gives every block"),
    (Block(heads=2, ffn="gated"), "feed-forward width"),
    (Block(heads=2, ffn="experts"), "comes with experts="),
    (Block(heads=2, ffn="glu", width=8), "unknown feed-forward"),
    (Block(heads=2, ffn="experts", experts=Experts(
        total=6, top_k=2, width=4, shared_width=4, share=(0, 4))),
     "does not divide"),
])
def test_a_block_the_model_cannot_run_is_refused(block, match):
    with pytest.raises(ValueError, match=match):
        BlockLM([block], vocab=8, dim=16, head_dim=8, kv_heads=2, max_seq=8,
                attention="reference")


def test_a_batch_through_an_expert_layer_is_refused():
    import optax

    with pytest.raises(ValueError, match="one sequence a step"):
        make_train_step(model_of(plain_spec()), optax.adamw(1e-3),
                        batched=True)


def test_routing_needs_an_expert_layer():
    model = TinyLM(vocab=8, dim=16, heads=2, layers=1, max_seq=8,
                   attention="reference")
    with pytest.raises(ValueError, match="no expert layer"):
        model.routing(model.init(jax.random.PRNGKey(0)),
                      jnp.zeros((8,), jnp.int32))


# -- decode ------------------------------------------------------------------------------
def test_decode_follows_apply_through_every_kind_of_layer():
    """Position by position through the KV caches (windows masked, ropes
    partial, one token through the expert layer) against one full pass."""
    spec = plain_spec()
    model = model_of(spec)
    params = model.init(jax.random.PRNGKey(6))
    tokens = tokens_of(8)
    full = model.apply(params, tokens)
    caches = [{"k": jnp.zeros((S, KVH, DH)), "v": jnp.zeros((S, KVH, DH))}
              for _ in model.blocks]

    def one(caches, inp):
        return model._decode_step(params, caches, *inp)

    _, logits = jax.lax.scan(one, caches, (jnp.arange(S), tokens))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               rtol=2e-4, atol=2e-5)


# -- donation, spans, counters, scopes -------------------------------------------------------
def _step_and_state(donate):
    import optax

    model = model_of(plain_spec())
    opt = optax.adamw(3e-4)
    step = make_train_step(model, opt, donate=donate)
    params = model.init(jax.random.PRNGKey(0))
    return model, step, params, opt.init(params)


def test_donate_aliases_every_leaf_of_the_state_and_the_default_none():
    _, step, params, state = _step_and_state(donate=True)
    n_leaves = len(jax.tree.leaves((params, state)))
    text = step.lower(params, state, tokens_of(1)).as_text()
    donated = text.count("tf.aliasing_output") + text.count(
        "jax.buffer_donor")
    assert donated == n_leaves
    _, plain, _, _ = _step_and_state(donate=False)
    text = plain.lower(params, state, tokens_of(1)).as_text()
    assert "tf.aliasing_output" not in text
    assert "jax.buffer_donor" not in text


def test_a_donating_step_computes_what_the_plain_one_does():
    _, step, params, state = _step_and_state(donate=True)
    _, plain, params2, state2 = _step_and_state(donate=False)
    tokens = tokens_of(2)
    new, _, loss = step(params, state, tokens)
    new2, _, loss2 = plain(params2, state2, tokens)
    assert float(loss) == float(loss2)
    assert_trees_close(new, new2, rtol=0, atol=0)
    assert step.__name__ == plain.__name__ == "step"
    assert callable(step.lower)
    # the old state is gone where the backend donates, and kept otherwise
    assert all(not x.is_deleted() for x in jax.tree.leaves(params2))


def test_span_fields_counter_and_load_gauges():
    import fiber_tpu
    from fiber_tpu import telemetry
    from fiber_tpu.telemetry import tracing

    fiber_tpu.init()
    model, step, params, state = _step_and_state(donate=False)
    counter = telemetry.counter("moe_layers_traced")
    labels = dict(held="8", total="16", top_k="4")
    before = counter.value(**labels)
    tracing.SPANS.clear()
    tokens = tokens_of(3)
    step(params, state, tokens)
    (span,) = [s for s in tracing.SPANS.snapshot()
               if s["name"] == "lm.train_step"]
    assert span["tokens"] == S
    assert span["layers"] == "full/gated,window/experts,full/experts"
    assert (span["experts_held"], span["experts_total"],
            span["top_k"]) == (8, 16, 4)
    assert counter.value(**labels) >= before + 2        # two expert layers
    found = model.probe_routing(params, tokens)
    for layer in range(2):
        assert telemetry.gauge("moe_expert_load_max").value(
            layer=str(layer)) == found["load"][layer].max()
        assert telemetry.gauge("moe_expert_load_mean").value(
            layer=str(layer)) == pytest.approx(found["load"][layer].mean())


def test_scopes_of_the_expert_layer_reach_the_lowered_program():
    _, step, params, state = _step_and_state(donate=False)
    text = step.lower(params, state, tokens_of(1)).as_text(debug_info=True)
    # a scope under a transformation reads ``jvp(lm.moe)/dispatch/...``
    for scope in ("lm.attn)/window/qkv/", "lm.attn)/full/kernel/",
                  "lm.attn)/window/out/", "lm.mlp", "lm.moe)/router/",
                  "lm.moe)/dispatch/", "lm.moe)/while/body/dispatch/",
                  "lm.moe)/while/body/experts/ragged_dot_general",
                  "lm.moe)/while/body/combine/", "lm.moe)/combine/",
                  "lm.moe)/shared/", "lm.head_loss", "lm.optimizer"):
        assert scope in text, scope
