"""A model whose stack of layers runs several times over the same weights
(``BlockLM(passes=...)``): sandwich norms, the exit gate and the
expected-exit loss, layer-by-layer recomputation, the row-blocked head loss
and decode through a cache per pass and layer; against a written-out loop of
layer calls and against the plain reference the benchmark checks the chip
runs with (``perfbench/reference/lm_loop_plain.py``, which imports nothing of
the program and writes the ``passes x layers`` applications out). Small
sizes, seeded weights, the CPU; the flash kernels run in the Pallas
interpreter.
"""

import importlib.util
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from fiber_tpu.models import (  # noqa: E402
    Block, BlockLM, ExitGate, Experts, Rope, StateSpace, make_train_step)


def _load_reference():
    path = os.path.join(ROOT, "perfbench", "reference", "lm_loop_plain.py")
    spec = importlib.util.spec_from_file_location("lm_loop_plain", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

S, DIM, DH, VOCAB, WIDTH, LAYERS, PASSES = 32, 32, 8, 61, 48, 2, 3
BASE, EPS, BETA = 1e6, 1e-6, 0.05
FAULTS = ("three_passes", "no_pass_norm", "no_post_norm", "last_pass_loss",
          "no_entropy", "first_pass_logits")


def plain_spec(kv_heads=4, passes=PASSES):
    return {"vocab": VOCAB, "dim": DIM, "heads": 4, "kv_heads": kv_heads,
            "head_dim": DH, "width": WIDTH, "layers": LAYERS,
            "passes": passes, "rope_base": BASE, "norm_eps": EPS,
            "beta": BETA}


def model_of(spec=None, *, attention="reference", gate=True, post_norm=True,
             **kw):
    spec = spec or plain_spec()
    block = Block(heads=spec["heads"], rope=Rope(base=spec["rope_base"]),
                  ffn="gated", width=spec["width"], post_norm=post_norm)
    kw.setdefault("passes", spec["passes"])
    if gate and kw["passes"] > 1:
        kw.setdefault("exit_gate", ExitGate(beta=spec["beta"]))
    return BlockLM([block] * spec["layers"], vocab=spec["vocab"],
                   dim=spec["dim"], head_dim=spec["head_dim"],
                   kv_heads=spec["kv_heads"], max_seq=S, attention=attention,
                   interpret=attention == "flash", norm_eps=spec["norm_eps"],
                   **kw)


def tokens_of(i):
    return jax.random.randint(jax.random.PRNGKey(100 + i), (S,), 0, VOCAB)


def assert_trees_close(a, b, **kw):
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   err_msg=jax.tree_util.keystr(path), **kw)


def assert_grads_close(ours, theirs, rel=2e-4):
    """Leaf by leaf, against the largest element of the reference's leaf."""
    for (path, g), h in zip(jax.tree_util.tree_flatten_with_path(ours)[0],
                            jax.tree.leaves(theirs)):
        top = float(jnp.max(jnp.abs(h)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(h), rtol=0,
                                   atol=rel * top + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))


# -- the loop, written out -------------------------------------------------------
def written_out(model, per_pass, tokens):
    """The model's layers applied ``passes x layers`` times by a Python
    loop, pass ``t`` with the weights ``per_pass[t]``: (each pass's normed
    rows, the expected-exit loss by hand)."""
    once = model_of(passes=1, gate=False, attention=model.attention)
    positions = jnp.arange(S)
    ropes = {rope: (cos[:, None, :], sin[:, None, :]) for rope, (cos, sin)
             in once._rope_tables(positions).items()}
    x = per_pass[0]["embed"][tokens]
    rows, ce, lam = [], [], []
    for params in per_pass:
        for spec, blk in zip(once.blocks, params["blocks"]):
            x = once._layer(spec, blk, x, ropes)
        x = once._rms(x, params["final_norm"])
        rows.append(x)
        logp = jax.nn.log_softmax(x[:-1] @ params["out"], axis=-1)
        ce.append(-jnp.take_along_axis(logp, tokens[1:, None], axis=1)[:, 0])
        lam.append(jax.nn.sigmoid(x[:-1] @ params["gate_w"]
                                  + params["gate_b"]))
    left, loss = jnp.ones(S - 1), 0.0
    for t in range(len(per_pass)):
        p = left if t == len(per_pass) - 1 else lam[t] * left
        left = left * (1.0 - lam[t])
        loss = loss + p * ce[t] + BETA * p * jnp.log(p)
    return jnp.stack(rows), jnp.mean(loss)


@pytest.mark.parametrize("attention,recompute,head_block", [
    ("reference", None, None), ("reference", "layer", 16),
    ("flash", "layer", None)])
def test_the_scanned_passes_are_the_written_out_loop(attention, recompute,
                                                     head_block):
    """Values, and the gradient of every leaf: a shared weight's is the sum
    of the gradients of the passes' own copies."""
    model = model_of(attention=attention, recompute=recompute,
                     head_block=head_block)
    params = model.init(jax.random.PRNGKey(3))
    tokens = tokens_of(0)
    with jax.default_matmul_precision("highest"):
        rows, loss = written_out(model, [params] * PASSES, tokens)
        np.testing.assert_allclose(
            np.asarray(model._normed_rows(params, tokens)), np.asarray(rows),
            rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(
            np.asarray(model.apply(params, tokens)),
            np.asarray(rows[-1] @ params["out"]), rtol=2e-5, atol=2e-6)
        assert float(model.loss(params, tokens)) == pytest.approx(
            float(loss), rel=2e-6)
        grads = jax.grad(model.loss)(params, tokens)
        per_pass = jax.grad(
            lambda copies: written_out(model, copies, tokens)[1])(
            [params] * PASSES)
    summed = jax.tree.map(lambda *g: sum(g), *per_pass)
    assert_grads_close(grads, summed)
    # and no pass's share is nothing: the sum is of PASSES live terms
    wo = [float(jnp.max(jnp.abs(g["blocks"][0]["wo"]))) for g in per_pass]
    assert min(wo) > 0.05 * max(wo)


def test_one_pass_with_no_new_field_lowers_to_what_it_did():
    """The description gained fields; a model that sets none of them is the
    program it was: the forward and the loss written here as they stood
    before the fields lower to the same text."""
    block = Block(heads=4, rope=Rope(), ffn="gated", width=WIDTH)
    model = BlockLM([block, Block(heads=4, rope=Rope(), ffn="mlp",
                                  width=WIDTH)], vocab=VOCAB, dim=DIM,
                    head_dim=DH, kv_heads=2, max_seq=S,
                    attention="reference")
    params = model.init(jax.random.PRNGKey(0))
    tokens = tokens_of(0)

    def forward_as_it_was(params, tokens):
        with jax.named_scope("lm.embed"):
            x = params["embed"][tokens]
            ropes = {rope: (cos[:, None, :], sin[:, None, :])
                     for rope, (cos, sin)
                     in model._rope_tables(jnp.arange(S)).items()}
        for spec, blk in zip(model.blocks, params["blocks"]):
            h = model._rms(x, blk["norm1"])
            q, k, v = model._project_qkv(blk, h)
            q = q.reshape(S, spec.heads, DH)
            k = k.reshape(S, 2, DH)
            v = v.reshape(S, 2, DH)
            q = model._rope_rotate(q, *ropes[spec.rope])
            k = model._rope_rotate(k, *ropes[spec.rope])
            mixed = model._attend(q, k, v, None).reshape(S, -1)
            x = x + mixed @ blk["wo"]
            h = model._rms(x, blk["norm2"])
            if spec.ffn == "gated":
                x = x + (jax.nn.silu(h @ blk["wg"]) * (h @ blk["wu"])) \
                    @ blk["wd"]
            else:
                x = x + jax.nn.gelu(h @ blk["w1"] + blk["b1"]) \
                    @ blk["w2"] + blk["b2"]
        return model._rms(x, params["final_norm"]) @ params["out"]

    def loss_as_it_was(params, tokens):
        logits = forward_as_it_was(params, tokens)[:-1]
        targets = tokens[1:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=1))

    def lowered(f):
        return jax.jit(jax.value_and_grad(f)).lower(params, tokens).as_text()

    assert model.span_fields == {"layers": "full/gated,full/mlp"}
    assert lowered(model.loss).replace("loss_as_it_was", "loss") == \
        lowered(loss_as_it_was).replace("loss_as_it_was", "loss")


# -- the head ----------------------------------------------------------------------
@pytest.mark.parametrize("passes,head_block", [(3, 16), (3, 20), (1, 10),
                                               (3, 4096)])
def test_the_blocked_head_loss_is_the_materialised_one(passes, head_block):
    """Values and gradients; a row count that is whole blocks (3 x 32 rows
    in blocks of 16), that is not (blocks of 20; 32 rows in blocks of 10),
    and a block larger than all the rows."""
    spec = plain_spec(passes=passes)
    whole, blocked = model_of(spec), model_of(spec, head_block=head_block)
    params = whole.init(jax.random.PRNGKey(5))
    tokens = tokens_of(1)
    if passes == 1:
        # one pass and no block: the loss as it always lowered
        whole_ce = whole.token_losses(params, tokens)[None]
    else:
        whole_ce = whole.pass_losses(params, tokens)[0]
    ce, _ = blocked.pass_losses(params, tokens)
    assert ce.shape == (passes, S - 1)
    np.testing.assert_allclose(np.asarray(ce), np.asarray(whole_ce),
                               rtol=2e-6, atol=2e-6)
    assert float(blocked.loss(params, tokens)) == pytest.approx(
        float(whole.loss(params, tokens)), rel=2e-6)
    assert_grads_close(jax.grad(blocked.loss)(params, tokens),
                       jax.grad(whole.loss)(params, tokens), rel=2e-5)


def test_no_logits_outlive_their_block():
    """The jaxpr of the blocked step holds no (rows, vocab) array larger
    than a block's."""
    model = model_of(head_block=16, recompute="layer")
    params = model.init(jax.random.PRNGKey(5))
    text = str(jax.make_jaxpr(jax.grad(model.loss))(params, tokens_of(1)))
    assert f"f32[16,{VOCAB}]" in text
    # ((S, vocab) would also be the head's own (dim, vocab): S == DIM here)
    for rows in (S - 1, PASSES * S, PASSES * (S - 1)):
        assert f"f32[{rows},{VOCAB}]" not in text
    for rows in (S, S - 1):
        assert f"f32[{PASSES},{rows},{VOCAB}]" not in text


# -- the exits ---------------------------------------------------------------------
def test_the_exit_distribution_and_the_loss_by_hand():
    model = model_of()
    params = model.init(jax.random.PRNGKey(7))
    # a gate that does something: a bias and a larger weight
    params["gate_w"] = 20 * params["gate_w"]
    params["gate_b"] = jnp.asarray(-0.3)
    tokens = tokens_of(2)
    ce, p = model.pass_losses(params, tokens)
    assert ce.shape == (PASSES, S - 1) and p.shape == (PASSES, S)
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0,
                               rtol=0, atol=2e-6)
    assert float(jnp.min(p)) > 0 and float(jnp.std(p[0])) > 0.05
    # the product formula, from the gate's own numbers
    rows = model._normed_rows(params, tokens)
    lam = jax.nn.sigmoid(rows @ params["gate_w"] + params["gate_b"])
    by_hand = jnp.stack([lam[0], lam[1] * (1 - lam[0]),
                         (1 - lam[0]) * (1 - lam[1])])
    np.testing.assert_allclose(np.asarray(p), np.asarray(by_hand),
                               rtol=2e-5, atol=2e-6)
    q = p[:, :-1]
    loss = jnp.mean(jnp.sum(q * ce, axis=0)
                    + BETA * jnp.sum(q * jnp.log(q), axis=0))
    assert float(model.loss(params, tokens)) == pytest.approx(
        float(loss), rel=2e-6)
    # the last pass's are what token_losses and apply give
    np.testing.assert_allclose(
        np.asarray(model.token_losses(params, tokens)), np.asarray(ce[-1]),
        rtol=2e-6)
    logp = jax.nn.log_softmax(model.apply(params, tokens)[:-1], axis=-1)
    np.testing.assert_allclose(
        np.asarray(-jnp.take_along_axis(logp, tokens[1:, None], axis=1)[:, 0]),
        np.asarray(ce[-1]), rtol=2e-5, atol=2e-6)


def test_a_pass_no_position_leaves_by_costs_no_nan():
    model = model_of()
    params = model.init(jax.random.PRNGKey(7))
    params["gate_b"] = jnp.asarray(-200.0)        # lambda underflows to 0
    loss, grads = jax.value_and_grad(model.loss)(params, tokens_of(2))
    assert np.isfinite(float(loss))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


def test_passes_without_a_gate_take_the_last_passes_loss():
    model = model_of(gate=False)
    params = model.init(jax.random.PRNGKey(7))
    assert "gate_w" not in params
    ce, p = model.pass_losses(params, tokens_of(2))
    assert p is None
    assert float(model.loss(params, tokens_of(2))) == pytest.approx(
        float(jnp.mean(ce[-1])), rel=1e-6)


# -- decode ------------------------------------------------------------------------
def test_decode_follows_apply_through_a_cache_per_pass_and_layer():
    model = model_of()
    params = model.init(jax.random.PRNGKey(6))
    tokens = tokens_of(8)
    full = model.apply(params, tokens)
    caches = model.init_caches(jnp.float32)
    assert len(caches) == PASSES and all(len(c) == LAYERS for c in caches)
    assert caches[0][0]["k"].shape == (S, 4, DH)

    def one(caches, inp):
        return model._decode_step(params, caches, *inp)

    caches, logits = jax.lax.scan(one, caches, (jnp.arange(S), tokens))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               rtol=2e-4, atol=2e-5)
    # a pass attends its own keys: the passes' caches differ
    gap = jnp.abs(caches[0][0]["k"] - caches[1][0]["k"])
    assert float(jnp.max(gap)) > 0.01


def test_generate_runs_the_passes():
    model = model_of()
    params = model.init(jax.random.PRNGKey(6))
    out = model.generate(params, tokens_of(1)[:5], 4)
    assert out.shape == (9,)
    assert (np.asarray(out[:5]) == np.asarray(tokens_of(1)[:5])).all()
    logits = model.apply(params, jnp.pad(out, (0, S - 9)))
    assert int(out[5]) == int(jnp.argmax(logits[4]))


# -- against the plain reference ---------------------------------------------------
def test_init_follows_the_documented_stream():
    spec = plain_spec()
    key = jax.random.PRNGKey(9)
    ours, theirs = model_of(spec).init(key), ref.init_params(key, spec)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert_trees_close(ours, theirs, rtol=0, atol=0)
    assert sorted(ours["blocks"][0]) == [
        "norm1", "norm2", "post_norm1", "post_norm2", "wd", "wg", "wo",
        "wqkv", "wu"]
    # the new leaves draw nothing of the old stream: the same model without
    # them has the same matrices
    bare = model_of(spec, gate=False, post_norm=False).init(key)
    np.testing.assert_array_equal(np.asarray(bare["blocks"][1]["wu"]),
                                  np.asarray(ours["blocks"][1]["wu"]))
    assert ours["gate_w"].shape == (DIM,) and float(ours["gate_b"]) == 0.0


@pytest.mark.parametrize("kv_heads,attention,recompute,head_block", [
    (4, "reference", "layer", 16), (2, "flash", None, None)])
def test_three_steps_follow_the_reference(kv_heads, attention, recompute,
                                          head_block):
    """Losses of three AdamW steps, the first gradient and the whole update
    leaf by leaf, the per-pass losses and the exit distribution."""
    import optax

    spec = plain_spec(kv_heads)
    model = model_of(spec, attention=attention, recompute=recompute,
                     head_block=head_block)
    key = jax.random.PRNGKey(11)
    params = model.init(key)
    opt = optax.adamw(3e-4, weight_decay=1e-4)
    step = make_train_step(model, opt)
    state = opt.init(params)
    theirs = ref.init_params(key, spec)
    their_step = ref.make_train_step(spec, lr=3e-4, weight_decay=1e-4,
                                     row_block=16)
    their_state = ref.adamw_init(theirs)
    with jax.default_matmul_precision("highest"):
        ce, p = model.pass_losses(params, tokens_of(0))
        their_ce, their_p = ref.pass_losses(theirs, tokens_of(0), spec,
                                            row_block=16)
        grads = jax.grad(model.loss)(params, tokens_of(0))
        their_grads = jax.grad(ref.sequence_loss)(theirs, tokens_of(0), spec,
                                                  row_block=16)
        for i in range(3):
            params, state, loss = step(params, state, tokens_of(i))
            theirs, their_state, their_loss, _ = their_step(
                theirs, their_state, tokens_of(i))
            assert float(loss) == pytest.approx(float(their_loss), rel=2e-6)
    np.testing.assert_allclose(np.asarray(ce), np.asarray(their_ce),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(p), np.asarray(their_p),
                               rtol=2e-5, atol=2e-6)
    assert_grads_close(grads, their_grads)
    assert_trees_close(params, theirs, rtol=0, atol=2e-5)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_of_the_references_faults_is_another_model(fault):
    """The planted faults the benchmark's check is held against change what
    is computed: the loss, and the gradient of some leaf by 3% of it."""
    spec = plain_spec()
    params = ref.init_params(jax.random.PRNGKey(4), spec)
    params["gate_w"] = 20 * params["gate_w"]
    sound, grads = jax.value_and_grad(ref.sequence_loss)(
        params, tokens_of(0), spec, row_block=16)
    loss, faulty = jax.value_and_grad(ref.sequence_loss)(
        params, tokens_of(0), spec, row_block=16, faults=(fault,))
    assert abs(float(loss) - float(sound)) > 1e-4 * float(sound)
    gaps = [float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(b)) + 1e-12)
            for a, b in zip(jax.tree.leaves(faulty), jax.tree.leaves(grads))]
    assert max(gaps) > 0.03


# -- construction ------------------------------------------------------------------
ATTENTION = Block(heads=4, rope=Rope(), ffn="gated", width=WIDTH)
BASIC = dict(vocab=VOCAB, dim=DIM, head_dim=DH, kv_heads=4, max_seq=S,
             attention="reference")


@pytest.mark.parametrize("blocks,kw,match", [
    ([ATTENTION], dict(exit_gate=ExitGate()), "an exit gate chooses among"),
    ([ATTENTION], dict(passes=0), "passes must be >= 1"),
    ([ATTENTION], dict(recompute="pass"), "unknown recomputation"),
    ([ATTENTION], dict(head_block=0), "head_block must be >= 1"),
    ([Block(mixer=None, ffn="experts", experts=Experts(
        total=4, top_k=2, width=8, shared_width=8))], dict(passes=2),
     "an expert layer's routing"),
    ([Block(mixer="ssm", ffn=None, ssm=StateSpace(
        heads=4, head_dim=8, state=16, chunk=8))],
     dict(passes=2, pos="none"), "carried state have no pass"),
])
def test_what_is_not_computed_is_refused(blocks, kw, match):
    with pytest.raises(ValueError, match=match):
        BlockLM(blocks, **{**BASIC, **kw})


@pytest.mark.parametrize("kw", [dict(passes=2), dict(head_block=16)])
def test_passes_on_a_mesh_of_several_chips_are_refused(kw):
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("one device")
    mesh = Mesh(np.asarray(devices[:2]), ("pool",))
    with pytest.raises(ValueError, match="carries no pass"):
        BlockLM([ATTENTION], **{**BASIC, "attention": "ring", "mesh": mesh,
                                **kw})


# -- spans, counter, scopes ----------------------------------------------------------
def _step_and_state(**kw):
    import optax

    model = model_of(**kw)
    params = model.init(jax.random.PRNGKey(2))
    opt = optax.adamw(3e-4)
    return model, make_train_step(model, opt), params, opt.init(params)


def test_span_fields_and_the_passes_counter():
    import fiber_tpu
    from fiber_tpu import telemetry
    from fiber_tpu.telemetry import tracing

    fiber_tpu.init()
    counter = telemetry.counter("lm_passes_traced")
    labels = dict(passes="3", layers="2", recompute="layer+head",
                  kept="input")
    before = counter.value(**labels)
    model, step, params, state = _step_and_state(recompute="layer",
                                                 head_block=16)
    assert model.span_fields == {"layers": "full/gated,full/gated",
                                 "passes": 3, "recompute": "layer+head",
                                 "kept": "input"}
    tracing.SPANS.clear()
    step(params, state, tokens_of(3))
    step(params, state, tokens_of(4))
    spans = [s for s in tracing.SPANS.snapshot()
             if s["name"] == "lm.train_step"]
    assert len(spans) == 2
    assert (spans[0]["layers"], spans[0]["passes"], spans[0]["recompute"],
            spans[0]["kept"], spans[0]["tokens"]) == (
                "full/gated,full/gated", 3, "layer+head", "input", S)
    # one trace of the step moves it once: the passes are one scan, a
    # checkpointed layer replays its equations, a second call traces nothing
    assert counter.value(**labels) == before + 1
    fields = model_of(gate=False).span_fields
    assert (fields["recompute"], fields["kept"]) == ("none", "all")


def test_the_new_scopes_reach_the_lowered_program():
    _, step, params, state = _step_and_state(recompute="layer", head_block=16)
    text = step.lower(params, state, tokens_of(1)).as_text(debug_info=True)
    for scope in ("lm.pass", "lm.pass/checkpoint/lm.attn/full/qkv",
                  "lm.pass/checkpoint/lm.attn/full/kernel",
                  "lm.pass/checkpoint/lm.attn/full/out",
                  "lm.pass/checkpoint/lm.mlp", "rematted_computation/lm.mlp",
                  "lm.exit_gate", "lm.head_loss", "lm.optimizer"):
        assert scope in text.replace(")/", "/"), scope


# -- what a recomputed layer keeps -------------------------------------------------
def kernel_calls(jaxpr, counts=None):
    """How often each Pallas kernel is called in a jaxpr, by its name,
    every nested jaxpr (jit, scan, checkpoint, custom VJP) included."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] = counts.get(eqn.params["name"], 0) + 1
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    kernel_calls(sub, counts)
    return counts


def with_bare_checkpoint(model):
    """The same model, each layer application under a ``jax.checkpoint``
    with no policy: the wrapping as it was, which keeps a layer's input
    alone and runs the forward kernel again in the backward pass."""
    def walk(blocks, x, ropes, taps=None):
        for spec, blk in zip(model.blocks, blocks):
            x = jax.checkpoint(
                lambda blk, x, spec=spec: model._layer(spec, blk, x, ropes)
            )(blk, x)
        return x

    bare = model_of(plain_spec(kv_heads=model.kv_heads),
                    attention=model.attention, recompute="layer",
                    passes=model.passes)
    bare._walk = walk
    return bare


@pytest.mark.parametrize("wrapping,forward", [("kept", 1), ("bare", 2)])
def test_a_kept_layer_runs_the_forward_kernel_once(wrapping, forward):
    """Two passes over two layers: four layer applications, two in each
    scan's body. The differentiated loss holds one forward kernel call a
    layer of the body (with the bare checkpoint two: the backward scan's
    replay), and dq and dkv once each either way."""
    model = model_of(attention="flash", recompute="layer", passes=2)
    if wrapping == "bare":
        model = with_bare_checkpoint(model)
    params = model.init(jax.random.PRNGKey(1))
    jaxpr = jax.make_jaxpr(jax.grad(model.loss))(params, tokens_of(1))
    assert kernel_calls(jaxpr.jaxpr) == {
        "flash_attn_fwd": forward * LAYERS, "flash_attn_dq": LAYERS,
        "flash_attn_dkv": LAYERS}


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_a_kept_layer_computes_what_the_recomputed_one_did(kv_heads):
    """Loss and every gradient leaf bit for bit those of the bare
    checkpoint (the kept output is what the second run would have
    written), and those of no recomputation to rounding."""
    spec = plain_spec(kv_heads=kv_heads, passes=2)
    model = model_of(spec, attention="flash", recompute="layer")
    params = model.init(jax.random.PRNGKey(4))
    tokens = tokens_of(2)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
    bare_loss, bare_grads = jax.jit(jax.value_and_grad(
        with_bare_checkpoint(model).loss))(params, tokens)
    assert float(loss) == float(bare_loss)
    for (path, g), h in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(bare_grads)):
        assert np.array_equal(np.asarray(g), np.asarray(h)), \
            jax.tree_util.keystr(path)
    whole = model_of(spec, attention="flash")
    whole_loss, whole_grads = jax.value_and_grad(whole.loss)(params, tokens)
    assert float(loss) == pytest.approx(float(whole_loss), rel=2e-6)
    assert_grads_close(grads, whole_grads)


@pytest.mark.parametrize("with_lse", [False, True])
def test_the_kernels_under_the_keeping_policy(with_lse):
    """The two entry points on their own under a checkpoint whose policy
    saves ``KEPT_NAMES``: gradients (the lse's cotangent included) those
    of the bare checkpoint bit for bit, and no second forward kernel."""
    from fiber_tpu.ops.pallas_attention import (
        KEPT_NAMES, flash_attention, flash_attention_lse)

    keys = jax.random.split(jax.random.PRNGKey(8), 4)
    q, k, v = (jax.random.normal(key, (S, 4, DH)) for key in keys[:3])
    w = jax.random.normal(keys[3], (4, S))

    def f(q, k, v):
        if not with_lse:
            return jnp.sum(jnp.sin(flash_attention(
                2.0 * q, k, v, causal=True, interpret=True)))
        out, lse = flash_attention_lse(2.0 * q, k, v, causal=True,
                                       interpret=True)
        return jnp.sum(jnp.sin(out)) + jnp.sum(w * lse)

    policy = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)
    kept = jax.grad(jax.checkpoint(f, policy=policy), argnums=(0, 1, 2))
    bare = jax.grad(jax.checkpoint(f), argnums=(0, 1, 2))
    for g, h in zip(kept(q, k, v), bare(q, k, v)):
        assert np.array_equal(np.asarray(g), np.asarray(h))
    assert kernel_calls(jax.make_jaxpr(kept)(q, k, v).jaxpr)[
        "flash_attn_fwd"] == 1
    assert kernel_calls(jax.make_jaxpr(bare)(q, k, v).jaxpr)[
        "flash_attn_fwd"] == 2


@pytest.mark.parametrize("program", ["model", "lse"])
def test_outside_a_checkpoint_a_name_is_no_op(program, monkeypatch):
    """A model without ``recompute`` (and the lse entry point the ring
    calls) lowers to the same StableHLO with the names and without."""
    from fiber_tpu.ops import pallas_attention

    model = model_of(attention="flash", passes=1, gate=False)
    params = model.init(jax.random.PRNGKey(6))
    q = jax.random.normal(jax.random.PRNGKey(9), (S, 4, DH))

    def lowered():
        # (a fresh build of the kernels' functions, and of what calls them)
        pallas_attention._build.cache_clear()
        pallas_attention._build_lse.cache_clear()
        if program == "model":
            f, args = (lambda p, t: model.loss(p, t)), (params, tokens_of(0))
        else:
            f, args = (lambda q: jnp.sum(jnp.prod(jnp.stack(
                [jnp.sum(part) for part in
                 pallas_attention.flash_attention_lse(
                     q, q, q, causal=True, interpret=True)])))), (q,)
        # (the counter jax ends a repeated private function's symbol with
        # is no part of the program: it moves by one with the names)
        return re.sub(r"(@[A-Za-z_][\w.]*?)_\d+\b", r"\1",
                      jax.jit(jax.grad(f)).lower(*args).as_text())

    named = lowered()
    seen = []
    monkeypatch.setattr(jax.ad_checkpoint, "checkpoint_name",
                        lambda x, name: seen.append(name) or x)
    unnamed = lowered()
    pallas_attention._build.cache_clear()
    pallas_attention._build_lse.cache_clear()
    assert sorted(set(seen)) == sorted(pallas_attention.KEPT_NAMES)
    assert named == unnamed


@pytest.mark.parametrize("attention,recompute,kept", [
    ("flash", "layer", "input+attn_out+lse"), ("reference", "layer", "input"),
    ("flash", None, "all")])
def test_the_passes_counter_says_what_is_kept(attention, recompute, kept):
    import fiber_tpu
    from fiber_tpu import telemetry

    fiber_tpu.init()
    counter = telemetry.counter("lm_passes_traced")
    labels = dict(passes="2", layers="2", recompute=recompute or "none",
                  kept=kept)
    before = counter.value(**labels)
    model = model_of(attention=attention, recompute=recompute, passes=2)
    assert model.span_fields["kept"] == kept
    jax.make_jaxpr(jax.grad(model.loss))(
        model.init(jax.random.PRNGKey(2)), tokens_of(5))
    assert counter.value(**labels) == before + 1
