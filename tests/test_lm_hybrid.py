"""A model whose layers are one part each (``BlockLM``): state-space mixers
(``fiber_tpu.ops.ssm``), attention without a position scheme, ungated relu^2
experts of which a share is held, against the plain reference the benchmark
checks the chip runs with (``perfbench/reference/lm_hybrid_plain.py``, which
imports nothing of the program and computes the state-space layer token by
token). Small sizes, seeded weights, the CPU; the flash kernels run in the
Pallas interpreter.
"""

import importlib.util
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from fiber_tpu.models import (  # noqa: E402
    Block, BlockLM, Experts, StateSpace, make_train_step)
from fiber_tpu.ops import moe, ssm  # noqa: E402


def _load_reference():
    path = os.path.join(ROOT, "perfbench", "reference", "lm_hybrid_plain.py")
    spec = importlib.util.spec_from_file_location("lm_hybrid_plain", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

# -- a small model with the three kinds of layer -------------------------------
S, DIM, DH, KVH, VOCAB, EPS = 64, 32, 16, 2, 48, 1e-5
SSM = dict(heads=4, head_dim=8, state=16, groups=2, conv=4, chunk=16,
           dt_min=0.001, dt_max=0.1, dt_floor=1e-4)
EXPERTS = dict(total=16, top_k=4, width=8, shared_width=24, scale=2.5)


def plain_spec(share=(0, 2)):
    """ssm, experts, attention (6 query heads over 2), ssm, experts."""
    return {"vocab": VOCAB, "dim": DIM, "head_dim": DH, "kv_heads": KVH,
            "norm_eps": EPS, "share": share, "layers": [
                dict(kind="ssm", **SSM), dict(kind="experts", **EXPERTS),
                dict(kind="attention", heads=6),
                dict(kind="ssm", **SSM), dict(kind="experts", **EXPERTS)]}


def model_of(spec, chunk_rows=32, recompute=True, **kw):
    def block(layer):
        layer = dict(layer)
        kind = layer.pop("kind")
        if kind == "ssm":
            return Block(mixer="ssm", ffn=None,
                         ssm=StateSpace(recompute=recompute, **layer))
        if kind == "attention":
            return Block(heads=layer["heads"], rope=None, ffn=None)
        return Block(mixer=None, ffn="experts", experts=Experts(
            share=spec["share"], chunk_rows=chunk_rows, kind="relu2",
            **layer))

    kw.setdefault("max_seq", S)
    return BlockLM([block(layer) for layer in spec["layers"]],
                   vocab=spec["vocab"], dim=spec["dim"],
                   head_dim=spec["head_dim"], kv_heads=spec["kv_heads"],
                   attention="flash", pos="none", interpret=True,
                   norm_eps=spec["norm_eps"], **kw)


def tokens_of(seed):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, (S,), dtype=np.int32))


def assert_trees_close(a, b, rtol, atol):
    la, lb = jax.tree_util.tree_flatten_with_path(a)[0], jax.tree.leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path))


# -- the scan -------------------------------------------------------------------
def scan_inputs(seed, decay):
    """Inputs whose per-position decay ``exp(dt A)`` is near 1 (``slow``:
    the state remembers everything), near 0 (``fast``: it forgets within a
    position) or spread as the model's initial values spread it."""
    H, P, G, N = 4, 8, 2, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    log_a = {"slow": (-9.0, -7.0), "fast": (0.5, 2.0),
             "spread": (-7.0, 2.0)}[decay]
    a = jnp.exp(jax.random.uniform(k[0], (S, H), minval=log_a[0],
                                   maxval=log_a[1]))    # -dt A, per position
    A_log = jnp.log(jnp.arange(1.0, H + 1.0))
    return dict(x=jax.random.normal(k[1], (S, H, P)),
                dt=a / jnp.exp(A_log), A_log=A_log,
                B=jax.random.normal(k[2], (S, G, N)),
                C=jax.random.normal(k[3], (S, G, N)),
                D=jax.random.normal(k[4], (H,)))


def chunked(inp, chunk):
    return ssm.ssd_scan(inp["x"], inp["dt"], -jnp.exp(inp["A_log"]),
                        inp["B"], inp["C"], inp["D"], chunk=chunk)


def token_by_token(inp, step=None):
    """The recurrence itself, by the reference's scan or the program's
    ``ssd_step``."""
    A = -jnp.exp(inp["A_log"])
    if step is None:
        return ref.recurrence(inp["x"], inp["dt"], A, inp["B"], inp["C"],
                              inp["D"], block=16)

    def one(state, at):
        x, dt, B, C = at
        return step(state, x, dt, A, B, C, inp["D"])

    _, y = jax.lax.scan(one, jnp.zeros((4, 8, 16)),
                        (inp["x"], inp["dt"], inp["B"], inp["C"]))
    return y


@pytest.mark.parametrize("chunk", [8, 32])
@pytest.mark.parametrize("decay", ["slow", "fast", "spread"])
def test_the_chunked_scan_is_the_recurrence_in_value_and_gradient(chunk,
                                                                  decay):
    # a block's decays are exp of a difference of cumulative sums: with
    # sums of some hundreds (fast decay) float32 leaves them 1e-4 apart
    tol = {"slow": 2e-5, "spread": 1e-4, "fast": 5e-4}[decay]
    inp = scan_inputs(3, decay)
    weight = jax.random.normal(jax.random.PRNGKey(9), (S, 4, 8))
    with jax.default_matmul_precision("highest"):
        want = token_by_token(inp)
        got = chunked(inp, chunk)
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=tol * scale)
        g_got = jax.grad(lambda i: jnp.sum(weight * chunked(i, chunk)))(inp)
        g_want = jax.grad(lambda i: jnp.sum(weight * token_by_token(i)))(inp)
    assert set(g_got) == {"x", "dt", "A_log", "B", "C", "D"}
    for name in g_want:
        top = float(jnp.max(jnp.abs(g_want[name])))
        np.testing.assert_allclose(
            np.asarray(g_got[name]), np.asarray(g_want[name]), rtol=0,
            atol=tol * top, err_msg=name)


def test_the_programs_one_step_form_is_the_recurrence():
    inp = scan_inputs(4, "spread")
    np.testing.assert_allclose(
        np.asarray(token_by_token(inp, ssm.ssd_step)),
        np.asarray(token_by_token(inp)), rtol=1e-5, atol=1e-5)


def test_the_state_between_blocks_is_carried():
    """With every block from zero (the reference's fault) the result differs
    from the first position of the second block on."""
    inp = scan_inputs(5, "slow")
    A = -jnp.exp(inp["A_log"])
    cut = ref.recurrence(inp["x"], inp["dt"], A, inp["B"], inp["C"],
                         inp["D"], block=16, carry_state=False)
    whole = np.asarray(chunked(inp, 16))
    np.testing.assert_allclose(np.asarray(cut)[:16], whole[:16], rtol=1e-4,
                               atol=1e-4)
    assert np.abs(np.asarray(cut)[16:] - whole[16:]).max() > 0.01


def test_a_sequence_that_is_not_whole_chunks_is_refused_by_the_scan():
    with pytest.raises(ValueError, match="not whole chunks"):
        chunked(scan_inputs(1, "spread"), 24)


# -- convolution and norm -------------------------------------------------------
def test_the_causal_convolution_is_the_written_out_sum():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    v = np.asarray(jax.random.normal(k[0], (10, 5)))
    w = np.asarray(jax.random.normal(k[1], (5, 4)))
    b = np.asarray(jax.random.normal(k[2], (5,)))
    want = np.zeros((10, 5))
    for t in range(10):
        for c in range(5):
            want[t, c] = b[c] + sum(
                w[c, j] * v[t - 3 + j, c] for j in range(4) if t - 3 + j >= 0)
    for conv in (ssm.causal_conv, ref.causal_conv):
        np.testing.assert_allclose(np.asarray(conv(v, w, b)), want,
                                   rtol=1e-5, atol=1e-6)


def test_the_gated_group_norm():
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    y = np.asarray(jax.random.normal(k[0], (6, 12)), np.float64)
    z = np.asarray(jax.random.normal(k[1], (6, 12)), np.float64)
    gain = np.asarray(jax.random.normal(k[2], (12,)), np.float64)
    gated = (y * z / (1 + np.exp(-z))).reshape(6, 3, 4)
    want = gain * (gated / np.sqrt(
        (gated ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(6, 12)
    got = ssm.gated_group_norm(jnp.asarray(y, jnp.float32),
                               jnp.asarray(z, jnp.float32),
                               jnp.asarray(gain, jnp.float32), 3, 1e-5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


# -- the model against the plain reference --------------------------------------
def test_init_follows_the_documented_stream():
    spec = plain_spec()
    key = jax.random.PRNGKey(7)
    ours = model_of(spec).init(key)
    theirs = ref.init_params(key, spec)
    assert_trees_close(ours, theirs, rtol=0, atol=0)
    first = ours["blocks"][0]
    decays = np.exp(-np.exp(np.asarray(first["A_log"]))
                    * np.log1p(np.exp(np.asarray(first["dt_bias"]))))
    assert ((decays > 0) & (decays < 1)).all()
    assert set(ours["blocks"][1]) == {
        "norm2", "router", "shared_wu", "shared_wd", "experts_wu",
        "experts_wd"}
    assert set(ours["blocks"][2]) == {"norm1", "wq", "wkv", "wo"}


@pytest.mark.parametrize("share,recompute", [((0, 2), True), ((1, 2), False),
                                             ((0, 1), True)])
def test_three_steps_follow_the_reference(share, recompute):
    """Losses of three AdamW steps, the first gradient and the whole update,
    leaf by leaf."""
    import optax

    spec = plain_spec(share)
    model = model_of(spec, recompute=recompute)
    key = jax.random.PRNGKey(11)
    params = model.init(key)
    opt = optax.adamw(3e-4, weight_decay=1e-4)
    step = make_train_step(model, opt)
    state = opt.init(params)
    theirs = ref.init_params(key, spec)
    their_step = ref.make_train_step(spec, lr=3e-4, weight_decay=1e-4,
                                     row_block=32)
    their_state = ref.adamw_init(theirs)
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(model.loss)(params, tokens_of(0))
        their_grads = jax.grad(
            lambda p: ref.sequence_loss(p, tokens_of(0), spec,
                                        row_block=32)[0])(theirs)
        for i in range(3):
            params, state, loss = step(params, state, tokens_of(i))
            theirs, their_state, their_loss, _, _ = their_step(
                theirs, their_state, tokens_of(i))
            assert float(loss) == pytest.approx(float(their_loss), rel=2e-6)
    for (path, g), h in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(their_grads)):
        top = float(jnp.max(jnp.abs(h)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(h), rtol=0,
                                   atol=2e-4 * top + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))
    assert_trees_close(params, theirs, rtol=0, atol=2e-5)


@pytest.mark.parametrize("share", [(0, 2), (0, 1)])
def test_token_losses_follow_the_reference_position_by_position(share):
    """``token_losses`` is ``loss`` before its mean, and the reference's
    position by position (what the benchmark's ``positions`` compares)."""
    spec = plain_spec(share)
    model = model_of(spec)
    key = jax.random.PRNGKey(13)
    params = model.init(key)
    with jax.default_matmul_precision("highest"):
        ours = model.token_losses(params, tokens_of(0))
        mean = model.loss(params, tokens_of(0))
        theirs = ref.position_losses(
            ref.init_params(key, spec), tokens_of(0), spec, row_block=32)
    assert ours.shape == (S - 1,)
    assert float(jnp.mean(ours)) == pytest.approx(float(mean), rel=1e-6)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shares", [1, 4, 16])
def test_the_shares_parts_add_up_to_the_uncut_layer(shares):
    """Ungated experts: what all the shares give, the shared expert counted
    once, is the uncut reference's whole layer."""
    total, top_k, d = 16, 4, DIM
    e = dict(EXPERTS)
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    h = jax.random.normal(k[0], (S, d))
    whole = {"router": jax.random.normal(k[1], (d, total)),
             "shared_wu": 0.2 * jax.random.normal(k[2], (d, 24)),
             "shared_wd": 0.2 * jax.random.normal(k[3], (24, d)),
             "experts_wu": 0.2 * jax.random.normal(k[4], (total, d, 8)),
             "experts_wd": 0.2 * jax.random.normal(k[5], (total, 8, d))}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(h, whole, e, (0, 1))
        shared = moe.relu2(h, whole["shared_wu"], whole["shared_wd"])
        parts = jnp.zeros_like(h)
        for index in range(shares):
            first, count = moe.held_experts(total, (index, shares))
            blk = dict(whole,
                       experts_wu=whole["experts_wu"][first:first + count],
                       experts_wd=whole["experts_wd"][first:first + count])
            parts = parts + moe.moe_ffn(
                h, blk, total=total, top_k=top_k, scale=2.5, first=first,
                chunk_rows=32, kind="relu2") - shared
    np.testing.assert_allclose(np.asarray(shared + parts), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_the_ungated_expert_is_relu_squared():
    h = jnp.asarray([[1.0, -2.0]])
    wu = jnp.asarray([[1.0, -1.0, 2.0], [0.5, 1.0, 0.0]])
    wd = jnp.asarray([[1.0], [10.0], [100.0]])
    # h wu = [0, -3, 2] -> relu^2 = [0, 0, 4] -> 400
    assert float(moe.relu2(h, wu, wd)[0, 0]) == 400.0


# -- decode ---------------------------------------------------------------------
def test_decode_follows_apply_through_the_three_kinds_of_layer():
    """Position by position: the state-space layers by the recurrence itself
    (the convolution's last three inputs and the state carried), attention
    through its KV cache, one token through the expert layer; against one
    full pass, whose state-space layers run the chunked scan."""
    model = model_of(plain_spec())
    params = model.init(jax.random.PRNGKey(6))
    tokens = tokens_of(8)
    full = model.apply(params, tokens)
    caches = model.init_caches(jnp.float32)
    assert [sorted(c) for c in caches] == [
        ["conv", "state"], [], ["k", "v"], ["conv", "state"], []]
    assert caches[0]["conv"].shape == (3, 4 * 8 + 2 * 2 * 16)
    assert caches[0]["state"].shape == (4, 8, 16)

    def one(caches, inp):
        return model._decode_step(params, caches, *inp)

    _, logits = jax.lax.scan(one, caches, (jnp.arange(S), tokens))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               rtol=2e-4, atol=2e-5)


def test_generate_runs_through_the_three_kinds_of_layer():
    model = model_of(plain_spec())
    params = model.init(jax.random.PRNGKey(6))
    out = model.generate(params, tokens_of(1)[:5], 4)
    assert out.shape == (9,)
    assert (np.asarray(out[:5]) == np.asarray(tokens_of(1)[:5])).all()
    logits = model.apply(params, jnp.pad(out, (0, S - 9)))
    assert int(out[5]) == int(jnp.argmax(logits[4]))


# -- construction -----------------------------------------------------------------
GOOD_SSM = StateSpace(heads=4, head_dim=8, state=16, groups=2, chunk=8)


@pytest.mark.parametrize("block,kw,match", [
    (Block(mixer=None, ffn=None), {}, "a layer with no part"),
    (Block(mixer="ssm", ffn=None), {}, "comes with ssm="),
    (Block(heads=2, ssm=GOOD_SSM, width=8), {}, "comes with ssm="),
    (Block(mixer="ssm", ffn=None, ssm=GOOD_SSM), dict(max_seq=12),
     "not whole chunks of 8"),
    (Block(mixer="ssm", ffn=None, ssm=StateSpace(
        heads=4, head_dim=8, state=16, groups=3, chunk=8)), {},
     "do not divide into 3 groups"),
    (Block(mixer="conv", width=8), {}, "unknown mixer"),
    (Block(heads=2, rope=None, width=8), {}, "pos='rope' gives every block"),
    (Block(heads=2, width=8), dict(pos="none"),
     "pos='rope' gives every block"),
    (Block(heads=0, rope=None, ffn=None), dict(pos="none"),
     "not divisible by kv_heads"),
    (Block(mixer=None, ffn="experts", experts=Experts(
        total=4, top_k=2, width=4, shared_width=4, kind="geglu")), {},
     "unknown expert kind"),
])
def test_a_block_the_model_cannot_run_is_refused(block, kw, match):
    kw = dict(dict(max_seq=16, pos="rope"), **kw)
    with pytest.raises(ValueError, match=match):
        BlockLM([block], vocab=8, dim=16, head_dim=8, kv_heads=2,
                attention="reference", **kw)


def test_a_state_space_layer_on_a_mesh_of_several_chips_is_refused():
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("one device")
    mesh = Mesh(np.asarray(devices[:2]), ("pool",))
    with pytest.raises(ValueError, match="runs on one device"):
        BlockLM([Block(mixer="ssm", ffn=None, ssm=GOOD_SSM)], vocab=8,
                dim=16, head_dim=8, kv_heads=2, max_seq=16,
                attention="ring", pos="none", mesh=mesh)


def test_a_model_without_attention_needs_no_kernel_platform():
    """``attention='flash'`` asks for a TPU only where a layer attends."""
    model = BlockLM([Block(mixer="ssm", ffn="gated", width=8, ssm=GOOD_SSM)],
                    vocab=8, dim=16, head_dim=8, kv_heads=2, max_seq=16,
                    pos="none")
    params = model.init(jax.random.PRNGKey(0))
    assert set(params["blocks"][0]) >= {"norm1", "norm2", "in_proj", "wg"}
    assert model.apply(params, jnp.zeros((16,), jnp.int32)).shape == (16, 8)
    assert model.span_fields == {"layers": "ssm/gated"}


def test_the_norm_epsilon_is_the_models():
    x = jnp.full((4,), 1e-3)
    for eps in (1e-6, 1e-5):
        model = BlockLM([Block(heads=2, width=8)], vocab=8, dim=4,
                        head_dim=2, kv_heads=2, max_seq=8,
                        attention="reference", norm_eps=eps)
        want = 1e-3 / np.sqrt(1e-6 + eps)
        assert float(model._rms(x, jnp.ones((4,)))[0]) == pytest.approx(
            want, rel=1e-5)


# -- spans, counters, scopes ----------------------------------------------------------
def _step_and_state():
    import optax

    model = model_of(plain_spec())
    opt = optax.adamw(3e-4)
    step = make_train_step(model, opt)
    params = model.init(jax.random.PRNGKey(0))
    return model, step, params, opt.init(params)


def test_span_fields_and_the_state_space_counter():
    import fiber_tpu
    from fiber_tpu import telemetry
    from fiber_tpu.telemetry import tracing

    fiber_tpu.init()
    counter = telemetry.counter("ssm_layers_traced")
    # blocks of 16 positions and a state of 16 are no shape of the scan's
    # kernels (tests/test_ssm_kernels.py has a model that takes them)
    labels = dict(heads="4", state="16", groups="2", chunk="16",
                  recompute="true", scan="plain")
    before = counter.value(**labels)
    model, step, params, state = _step_and_state()
    tracing.SPANS.clear()
    step(params, state, tokens_of(3))
    (span,) = [s for s in tracing.SPANS.snapshot()
               if s["name"] == "lm.train_step"]
    assert span["layers"] == "ssm,experts,full,ssm,experts"
    assert (span["experts_held"], span["experts_total"],
            span["top_k"]) == (8, 16, 4)
    # one trace of the step: each state-space layer once, recomputed or not
    assert counter.value(**labels) == before + 2


def test_scopes_of_the_state_space_layer_reach_the_lowered_program():
    _, step, params, state = _step_and_state()
    text = step.lower(params, state, tokens_of(1)).as_text(debug_info=True)
    for scope in ("lm.ssm)/in_proj/", "lm.ssm)/conv/", "lm.ssm)/scan/",
                  "lm.ssm)/gate_norm/", "lm.ssm)/out/",
                  "lm.ssm)/checkpoint/rematted_computation/scan/",
                  "lm.attn)/full/kernel/", "lm.moe)/router/",
                  "lm.moe)/shared/", "lm.head_loss", "lm.optimizer"):
        assert scope in text, scope
    assert "lm.mlp" not in text
