"""The state-space scan as two Pallas kernels (``fiber_tpu.ops.ssm``
``ssd_scan`` where ``scan_path`` says ``kernel``) against the plain form in
the same file, which is their oracle: on the CPU, through the Pallas
interpreter, at the smallest shapes the kernels take (blocks of 128
positions, a state of 128, a group's heads whole in tiles of 128 lanes).
The model with such layers against the plain reference the benchmark checks
the chip runs with (``perfbench/reference/lm_hybrid_plain.py``).
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
from fiber_tpu.ops import ssm  # noqa: E402

CHUNK, N = 128, 128


def scan_inputs(seed, blocks, H, P, G):
    """Decays as wide as the benchmark's cell has them: ``A`` from -1 to
    -64 over the heads and ``dt`` log-uniform in [1e-3, 0.1], so a block's
    cumulative sums reach -800 and a difference taken the wrong way round
    overflows ``exp``."""
    S = blocks * CHUNK
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        x=jax.random.normal(k[0], (S, H, P)),
        dt=jnp.exp(jax.random.uniform(k[1], (S, H), minval=np.log(1e-3),
                                      maxval=np.log(0.1))),
        A_log=jnp.log(jnp.linspace(1.0, 64.0, H)),
        B=jax.random.normal(k[2], (S, G, N)),
        C=jax.random.normal(k[3], (S, G, N)),
        D=jax.random.normal(k[4], (H,)))


def scanned(inp, chunk=CHUNK, **kw):
    return ssm.ssd_scan(inp["x"], inp["dt"], -jnp.exp(inp["A_log"]),
                        inp["B"], inp["C"], inp["D"], chunk=chunk, **kw)


def plain(inp, chunk=CHUNK):
    return ssm.ssd_scan_plain(inp["x"], inp["dt"], -jnp.exp(inp["A_log"]),
                              inp["B"], inp["C"], inp["D"], chunk=chunk)


# heads, head_dim, groups: one head a group (one tile of one head), two (one
# tile of two), eight in one group (two tiles of four)
SHAPES = {"one_head_a_group": (2, 128, 2), "two_heads_a_group": (4, 64, 2),
          "eight_heads_a_group": (8, 32, 1)}


@pytest.mark.parametrize("blocks", [2, 4])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernels_are_the_plain_scan_in_value_and_gradient(shape, blocks):
    """Two blocks carry the state and its gradient once, four carry them
    on; the gradient of all six inputs."""
    H, P, G = SHAPES[shape]
    assert ssm.scan_path(blocks * CHUNK, H, P, G, N, CHUNK,
                         interpret=True) == "kernel"
    inp = scan_inputs(3, blocks, H, P, G)
    weight = jax.random.normal(jax.random.PRNGKey(9), inp["x"].shape)
    want, got = plain(inp), scanned(inp, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=0,
        atol=1e-5 * float(jnp.max(jnp.abs(want))))
    g_got = jax.grad(
        lambda i: jnp.sum(weight * scanned(i, interpret=True)))(inp)
    g_want = jax.grad(lambda i: jnp.sum(weight * plain(i)))(inp)
    assert set(g_got) == {"x", "dt", "A_log", "B", "C", "D"}
    for name in g_want:
        np.testing.assert_allclose(
            np.asarray(g_got[name]), np.asarray(g_want[name]), rtol=0,
            atol=2e-4 * float(jnp.max(jnp.abs(g_want[name]))), err_msg=name)


def test_the_kernels_carry_the_state_between_blocks():
    """Each block scanned alone (from a zero state) differs from the whole
    from the second block on."""
    inp = scan_inputs(5, 2, 4, 64, 2)
    inp["A_log"] = inp["A_log"] - 6.0       # slow decays: the state matters
    whole = np.asarray(scanned(inp, interpret=True))
    second = np.asarray(scanned(
        {k: v[CHUNK:] if v.shape[0] == 2 * CHUNK else v
         for k, v in inp.items()}, interpret=True))
    assert np.abs(whole[CHUNK:] - second).max() > 0.01


@pytest.mark.parametrize("why,H,P,G,state,chunk,interpret", [
    ("a block that is not 128 positions", 4, 64, 2, 128, 64, True),
    ("a state that is not whole in 128 lanes", 4, 64, 2, 64, 128, True),
    ("a group's heads not whole in 128 lanes", 2, 64, 2, 128, 128, True),
    ("a head wider than a tile", 2, 256, 2, 128, 128, True),
    ("the CPU without the interpreter", 4, 64, 2, 128, 128, False),
])
def test_the_door_falls_back_to_the_plain_form(why, H, P, G, state, chunk,
                                               interpret):
    S = 256
    assert ssm.scan_path(S, H, P, G, state, chunk, interpret) == "plain", why
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    args = (jax.random.normal(k[0], (S, H, P)),
            0.01 + 0.05 * jax.random.uniform(k[1], (S, H)),
            -jnp.arange(1.0, H + 1.0),
            jax.random.normal(k[2], (S, G, state)),
            jax.random.normal(k[3], (S, G, state)), jnp.ones((H,)))
    text = str(jax.make_jaxpr(lambda *a: ssm.ssd_scan(
        *a, chunk=chunk, interpret=interpret))(*args))
    assert "pallas_call" not in text
    np.testing.assert_array_equal(
        np.asarray(ssm.ssd_scan(*args, chunk=chunk, interpret=interpret)),
        np.asarray(ssm.ssd_scan_plain(*args, chunk=chunk)))


def test_the_kernels_are_in_the_traced_program_where_they_fit():
    inp = scan_inputs(1, 2, 4, 64, 2)
    text = str(jax.make_jaxpr(
        jax.grad(lambda i: jnp.sum(scanned(i, interpret=True))))(inp))
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text


# -- the model on the kernels against the plain reference ---------------------
def _load_reference():
    path = os.path.join(ROOT, "perfbench", "reference", "lm_hybrid_plain.py")
    spec = importlib.util.spec_from_file_location("lm_hybrid_plain", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


S, VOCAB = 256, 48
SSM = dict(heads=4, head_dim=64, state=N, groups=2, conv=4, chunk=CHUNK,
           dt_min=0.001, dt_max=0.1, dt_floor=1e-4)
EXPERTS = dict(total=8, top_k=2, width=8, shared_width=24, scale=2.5)
SPEC = {"vocab": VOCAB, "dim": 32, "head_dim": 16, "kv_heads": 2,
        "norm_eps": 1e-5, "share": (0, 2), "layers": [
            dict(kind="ssm", **SSM), dict(kind="experts", **EXPERTS),
            dict(kind="attention", heads=4), dict(kind="ssm", **SSM)]}


def model_of(recompute, interpret=True):
    def block(layer):
        layer = dict(layer)
        kind = layer.pop("kind")
        if kind == "ssm":
            return Block(mixer="ssm", ffn=None,
                         ssm=StateSpace(recompute=recompute, **layer))
        if kind == "attention":
            return Block(heads=layer["heads"], rope=None, ffn=None)
        return Block(mixer=None, ffn="experts", experts=Experts(
            share=SPEC["share"], chunk_rows=64, kind="relu2", **layer))

    return BlockLM([block(layer) for layer in SPEC["layers"]],
                   vocab=VOCAB, dim=SPEC["dim"], head_dim=SPEC["head_dim"],
                   kv_heads=SPEC["kv_heads"], max_seq=S,
                   attention="flash" if interpret else "reference", pos="none",
                   interpret=interpret, norm_eps=SPEC["norm_eps"])


def tokens_of(seed):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, VOCAB, (S,), dtype=np.int32))


@pytest.mark.parametrize("recompute", [True, False])
def test_three_steps_on_the_kernels_follow_the_reference(recompute):
    """As ``test_lm_hybrid.py``'s: losses of three AdamW steps, the first
    gradient and the whole update, leaf by leaf; the counter says the
    scans ran as kernels."""
    import optax

    from fiber_tpu import telemetry

    ref = _load_reference()
    counter = telemetry.counter("ssm_layers_traced")
    labels = dict(heads="4", state="128", groups="2", chunk="128",
                  recompute=str(recompute).lower(), scan="kernel")
    before = counter.value(**labels)
    model = model_of(recompute)
    key = jax.random.PRNGKey(11)
    params = model.init(key)
    opt = optax.adamw(3e-4, weight_decay=1e-4)
    step = make_train_step(model, opt)
    state = opt.init(params)
    theirs = ref.init_params(key, SPEC)
    their_step = ref.make_train_step(SPEC, lr=3e-4, weight_decay=1e-4,
                                     row_block=64)
    their_state = ref.adamw_init(theirs)
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(model.loss)(params, tokens_of(0))
        their_grads = jax.grad(
            lambda p: ref.sequence_loss(p, tokens_of(0), SPEC,
                                        row_block=64)[0])(theirs)
        for i in range(3):
            params, state, loss = step(params, state, tokens_of(i))
            theirs, their_state, their_loss, _, _ = their_step(
                theirs, their_state, tokens_of(i))
            assert float(loss) == pytest.approx(float(their_loss), rel=2e-6)
    assert counter.value(**labels) == before + 4        # grad + step, x 2
    for (path, g), h in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(their_grads)):
        top = float(jnp.max(jnp.abs(h)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(h), rtol=0,
                                   atol=2e-4 * top + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree.leaves(theirs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_model_without_the_interpreter_takes_the_plain_scan_on_the_cpu():
    from fiber_tpu import telemetry

    counter = telemetry.counter("ssm_layers_traced")
    labels = dict(heads="4", state="128", groups="2", chunk="128",
                  recompute="true", scan="plain")
    before = counter.value(**labels)
    model = model_of(True, interpret=False)
    params = model.init(jax.random.PRNGKey(2))
    text = str(jax.make_jaxpr(model.loss)(params, tokens_of(0)))
    assert "pallas_call" not in text
    assert counter.value(**labels) == before + 2
