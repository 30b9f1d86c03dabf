"""Plain reference of a decoder-only LM whose every layer is ONE part with
its own RMSNorm and residual, ``x + part(norm(x))``: a Mamba-2 state-space
mixer, causal attention without any position scheme, or a sparse-expert
feed-forward of ungated relu^2 experts of which a share is held here (loss,
gradients, AdamW).

Straightforward ``jax.numpy`` in float32 with exact float32 matrix products
(``precision=HIGHEST``). The state-space layer is its recurrence **token by
token** (a ``lax.scan`` over positions), never a chunked form; attention is
a full masked score matrix per head; the experts are a loop over the held
ones with a mask. It imports nothing of ``fiber_tpu`` and takes nothing the
program has made: weights are drawn here from the seed, by the stream
``init_params`` states.

The model is handed over as plain data (``spec``): ``vocab``, ``dim``,
``head_dim``, ``kv_heads``, ``norm_eps``, ``share`` = (index, shares) of the
experts held here, and ``layers``, one dict a layer, by ``kind``:
``"ssm"`` {``heads``, ``head_dim``, ``state``, ``groups``, ``conv``,
``chunk``, ``dt_min``, ``dt_max``, ``dt_floor``}, ``"attention"``
{``heads``} or ``"experts"`` {``total``, ``top_k``, ``width``,
``shared_width``, ``scale``}.

``"ssm"``, on h = RMSNorm(x) (S, dim), with d_inner = heads x head_dim and
conv_dim = d_inner + 2 x groups x state:  [z | xBC | dt] = h W_in;
xBC <- silu(conv(xBC)), conv(v)[t, c] = b[c] + sum_{j<K} w[c, j] v[t - (K-1)
+ j, c] with zeros before position 0; xBC splits into x (heads, head_dim), B
and C (groups, state); dt = softplus(dt + dt_bias); A = -exp(A_log). For
head h of group g = h // (heads / groups), with state H (head_dim, state)
from zero:  H[t] = exp(dt[t,h] A[h]) H[t-1] + dt[t,h] x[t,h] (outer) B[t,g];
y[t,h] = H[t] C[t,g] + D[h] x[t,h]. Then y <- y * silu(z), RMS-normalised
in ``groups`` groups of features, times a gain; x += y W_out.
``"attention"``: q = h Wq, k, v = h Wkv, causal softmax(q k^T / sqrt(dh)) v
over all positions, query head j reading KV head j // (heads / kv_heads),
no rotation of q or k; x += attn Wo. ``"experts"``: s = sigmoid(h Wr) over
all experts, the ``top_k`` largest taken, w_e = scale * s_e / (sum of the
taken s), y = shared(h) + sum over the taken e held here of w_e *
expert_e(h), every expert ``relu(h Wu)^2 Wd``. What absent experts would add
is left out (one chip's share of an expert-parallel layer), and that partial
x goes on. Final RMSNorm, untied head, mean next-token cross-entropy.

Departures from the published description (NVIDIA-Nemotron-3-Nano-30B-A3B's
``config.json`` and the ``nemotron_h`` modelling code), the same as the
program's and listed in the configuration's file under ``assumed``: no
rotary embedding in the attention layers; d_inner = heads x head size (not
``expand`` x hidden); the gated norm as y * silu(z) first, then RMS in
groups; no clamp on dt; no auxiliary loss; the router's selection bias
(zero at initialisation, not trained by gradient) left out;
``rescale_prenorm_residual`` (a rule of initialisation) not applied.

Memory is held down by recomputing (``jax.checkpoint``) layer by layer, the
recurrence in blocks of ``chunk`` positions (8,192 saved states of 64 x 64 x
128 would be 17 GB), attention head by head and block of rows by block of
rows, and expert by expert, which changes no arithmetic.
``dtype=jnp.bfloat16`` (``cast``) stores weights, activations, the carried
state and optimizer state in bfloat16: the control of the comparison, never
the reference. ``faults`` (a tuple of names) are for the tests and the
readings, never the reference: ``half_loss`` (the loss over the first half
of the positions), ``no_carry`` (the state between blocks of ``chunk``
positions left out: every block from zero), ``no_conv`` (the convolution
left out: xBC <- silu(xBC)), ``no_routed`` (the held experts' part left
out).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
INIT_SCALE = 0.02


def held_range(total, share):
    index, shares = share
    count = total // shares
    return index * count, count


def init_params(key, spec):
    """Matrices 0.02 * normal, gains 1. The stream: split the key in four
    (embed, unused, out, rest); per layer split ``rest`` in seven (6 is the
    next layer's rest). ``"attention"``: 0 wq, 1 wo, 4 wkv. ``"ssm"``: 0
    split in five: in_proj, conv_w, conv_b (both uniform in +-conv^-0.5),
    the step sizes (dt log-uniform in [dt_min, dt_max], floored at dt_floor;
    ``dt_bias`` is its inverse softplus), out_proj; ``A_log = log(1 ..
    heads)``, ``D = 1``. ``"experts"``: 5 split in seven: 0 router, 2 shared
    wu, 3 shared wd, 5 and 6 the held experts' wu and wd (each one draw of
    the stacked shape)."""
    dim, dh, kvh = spec["dim"], spec["head_dim"], spec["kv_heads"]

    def normal(k, *shape):
        return INIT_SCALE * jax.random.normal(k, shape)

    k_emb, _, k_out, key = jax.random.split(key, 4)
    params = {"embed": normal(k_emb, spec["vocab"], dim),
              "out": normal(k_out, dim, spec["vocab"]),
              "final_norm": jnp.ones((dim,)), "blocks": []}
    for layer in spec["layers"]:
        ks = jax.random.split(key, 7)
        key = ks[6]
        if layer["kind"] == "attention":
            q_dim = layer["heads"] * dh
            blk = {"norm1": jnp.ones((dim,)),
                   "wq": normal(ks[0], dim, q_dim),
                   "wkv": normal(ks[4], dim, 2 * kvh * dh),
                   "wo": normal(ks[1], q_dim, dim)}
        elif layer["kind"] == "ssm":
            heads, conv = layer["heads"], layer["conv"]
            inner = heads * layer["head_dim"]
            conv_dim = inner + 2 * layer["groups"] * layer["state"]
            k_in, k_w, k_b, k_dt, k_o = jax.random.split(ks[0], 5)
            bound = conv ** -0.5
            dt = jnp.exp(jax.random.uniform(
                k_dt, (heads,), minval=math.log(layer["dt_min"]),
                maxval=math.log(layer["dt_max"])))
            dt = jnp.maximum(dt, layer["dt_floor"])
            blk = {"norm1": jnp.ones((dim,)),
                   "in_proj": normal(k_in, dim, inner + conv_dim + heads),
                   "conv_w": jax.random.uniform(
                       k_w, (conv_dim, conv), minval=-bound, maxval=bound),
                   "conv_b": jax.random.uniform(
                       k_b, (conv_dim,), minval=-bound, maxval=bound),
                   "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                   "A_log": jnp.log(jnp.arange(1.0, heads + 1.0)),
                   "D": jnp.ones((heads,)),
                   "ssm_norm": jnp.ones((inner,)),
                   "out_proj": normal(k_o, inner, dim)}
        else:
            held = held_range(layer["total"], spec["share"])[1]
            w, sw = layer["width"], layer["shared_width"]
            sub = jax.random.split(ks[5], 7)
            blk = {"norm2": jnp.ones((dim,)),
                   "router": normal(sub[0], dim, layer["total"]),
                   "shared_wu": normal(sub[2], dim, sw),
                   "shared_wd": normal(sub[3], sw, dim),
                   "experts_wu": normal(sub[5], held, dim, w),
                   "experts_wd": normal(sub[6], held, w, dim)}
        params["blocks"].append(blk)
    return params


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, gain, eps):
    return gain * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


# -- the state-space layer ---------------------------------------------------
def causal_conv(v, w, b):
    """v (S, C), w (C, K), b (C,): out[t] = b + sum_j w[:, j] v[t-(K-1)+j],
    with zeros before position 0: tap j reads v shifted down by K-1-j."""
    S, K = v.shape[0], w.shape[1]
    out = jnp.broadcast_to(b, v.shape)
    for j in range(K):
        back = K - 1 - j
        shifted = v if back == 0 else jnp.concatenate(
            [jnp.zeros((back, v.shape[1]), v.dtype), v[:S - back]])
        out = out + w[:, j] * shifted
    return out


def recurrence(x, dt, A, B, C, D, *, block, carry_state=True):
    """The selective scan, one position at a time. x (S, H, P), dt (S, H),
    A (H,), B / C (S, G, N), D (H,) -> y (S, H, P). Recomputed in blocks of
    ``block`` positions; ``carry_state=False`` is the fault ``no_carry``."""
    S, H, P = x.shape
    G, N = B.shape[1:]

    def one(state, inp):
        xt, dtt, Bt, Ct = inp              # (H, P), (H,), (G, N), (G, N)
        Bh = jnp.repeat(Bt, H // G, axis=0)
        Ch = jnp.repeat(Ct, H // G, axis=0)
        state = (jnp.exp(dtt * A)[:, None, None] * state
                 + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :])
        return state, jnp.sum(state * Ch[:, None, :], axis=-1) \
            + D[:, None] * xt

    def positions(state, inp):
        if not carry_state:
            state = jnp.zeros_like(state)
        return jax.lax.scan(one, state, inp)

    nb = S // block
    inputs = jax.tree.map(lambda a: a.reshape((nb, block) + a.shape[1:]),
                          (x, dt, B, C))
    _, y = jax.lax.scan(jax.checkpoint(positions),
                        jnp.zeros((H, P, N), x.dtype), inputs)
    return y.reshape(S, H, P)


def ssm_layer(h, blk, layer, eps, faults=()):
    S = h.shape[0]
    heads, P = layer["heads"], layer["head_dim"]
    G, N = layer["groups"], layer["state"]
    inner = heads * P
    zxbcdt = _mm(h, blk["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * G * N], axis=-1)
    if "no_conv" not in faults:
        xbc = causal_conv(xbc, blk["conv_w"], blk["conv_b"])
    xbc = jax.nn.silu(xbc)
    x, B, C = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt + blk["dt_bias"])
    y = recurrence(x.reshape(S, heads, P), dt, -jnp.exp(blk["A_log"]),
                   B.reshape(S, G, N), C.reshape(S, G, N), blk["D"],
                   block=layer["chunk"],
                   carry_state="no_carry" not in faults)
    y = y.reshape(S, inner) * jax.nn.silu(z)
    y = y.reshape(S, G, inner // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return _mm(blk["ssm_norm"] * y.reshape(S, inner), blk["out_proj"])


# -- attention ---------------------------------------------------------------
def _attention(q, k, v, *, row_block):
    """q (S, H, dh), k/v (S, KVH, dh) -> (S, H, dh). Full masked score
    matrix, one head and ``row_block`` query rows at a time."""
    S, H, dh = q.shape
    group = H // k.shape[1]
    scale = 1.0 / (dh ** 0.5)
    kv_pos = jnp.arange(S)
    nb = S // row_block

    def one_head(args):
        qh, kh, vh = args

        def rows(inp):
            qb, pos = inp
            s = _mm(qb, kh.T).astype(jnp.float32) * scale
            s = jnp.where(kv_pos[None, :] <= pos[:, None], s, -jnp.inf)
            return _mm(jax.nn.softmax(s, axis=-1).astype(vh.dtype), vh)

        out = jax.lax.map(jax.checkpoint(rows),
                          (qh.reshape(nb, row_block, dh),
                           kv_pos.reshape(nb, row_block)))
        return out.reshape(S, dh)

    qh = jnp.swapaxes(q, 0, 1)                          # (H, S, dh)
    kh = jnp.repeat(jnp.swapaxes(k, 0, 1), group, axis=0)
    vh = jnp.repeat(jnp.swapaxes(v, 0, 1), group, axis=0)
    out = jax.lax.map(jax.checkpoint(one_head), (qh, kh, vh))
    return jnp.swapaxes(out, 0, 1)


def attention_layer(h, blk, layer, spec, row_block):
    S = h.shape[0]
    heads, dh, kvh = layer["heads"], spec["head_dim"], spec["kv_heads"]
    q = _mm(h, blk["wq"]).reshape(S, heads, dh)
    k, v = jnp.split(_mm(h, blk["wkv"]), 2, axis=-1)
    attn = _attention(q, k.reshape(S, kvh, dh), v.reshape(S, kvh, dh),
                      row_block=row_block)
    return _mm(attn.reshape(S, heads * dh), blk["wo"])


# -- experts -----------------------------------------------------------------
def _relu2(h, wu, wd):
    return _mm(jnp.square(jax.nn.relu(_mm(h, wu))), wd)


def taken_experts(h, router, top_k):
    """Scores over all experts and which each token takes: (scores (S, E)
    float32, taken (S, E) bool, ids (S, top_k) ascending)."""
    scores = jax.nn.sigmoid(_mm(h.astype(jnp.float32),
                                router.astype(jnp.float32)))
    ids = jnp.argsort(-scores, axis=-1, stable=True)[:, :top_k]
    taken = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], ids].set(True)
    return scores, taken, jnp.sort(ids, axis=-1).astype(jnp.int32)


def expert_layer(h, blk, e, share, faults=()):
    """(shared(h) + the held experts' part, taken ids (S, top_k))."""
    first, count = held_range(e["total"], share)
    scores, taken, ids = taken_experts(h, blk["router"], e["top_k"])
    total = jnp.sum(jnp.where(taken, scores, 0.0), axis=-1, keepdims=True)
    weights = jnp.where(taken, e["scale"] * scores / total, 0.0)
    y = _relu2(h, blk["shared_wu"], blk["shared_wd"])
    if "no_routed" in faults:
        return y, ids
    here = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)

    def one(y, args):
        wu, wd, w = args
        return y + w[:, None].astype(h.dtype) * _relu2(h, wu, wd), None

    y, _ = jax.lax.scan(jax.checkpoint(one), y,
                        (blk["experts_wu"], blk["experts_wd"], here.T))
    return y, ids


# -- the model ---------------------------------------------------------------
def _picked_log_probs(params, tokens, spec, row_block, faults):
    """(each position's log-probability of its next token (S - 1,), the
    taken expert ids of each expert layer (expert layers, S, top_k))."""
    S = tokens.shape[0]
    eps = spec["norm_eps"]
    row_block = min(row_block or 2048, S)
    x = params["embed"][tokens]
    taken = []

    def part(layer, x, blk):
        if layer["kind"] == "ssm":
            return x + ssm_layer(_rms(x, blk["norm1"], eps), blk, layer,
                                 eps, faults), None
        if layer["kind"] == "attention":
            return x + attention_layer(_rms(x, blk["norm1"], eps), blk,
                                       layer, spec, row_block), None
        y, ids = expert_layer(_rms(x, blk["norm2"], eps), blk, layer,
                              spec["share"], faults)
        return x + y, ids

    for layer, blk in zip(spec["layers"], params["blocks"]):
        x, ids = jax.checkpoint(
            lambda x, blk, layer=layer: part(layer, x, blk))(x, blk)
        if ids is not None:
            taken.append(ids)
    logits = _mm(_rms(x, params["final_norm"], eps), params["out"])[:-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=1)[:, 0]
    return picked, jnp.stack(taken)


def sequence_loss(params, tokens, spec, *, row_block=None, faults=()):
    """(mean next-token cross-entropy of one sequence of tokens (S,), the
    taken expert ids of each expert layer (expert layers, S, top_k))."""
    picked, taken = _picked_log_probs(params, tokens, spec, row_block, faults)
    if "half_loss" in faults:
        picked = picked[:tokens.shape[0] // 2]
    return -jnp.mean(picked), taken


def position_losses(params, tokens, spec, *, row_block=None, faults=()):
    """``sequence_loss`` before its mean: the cross-entropy of each
    position 0..S-2, (S - 1,). The mean hides where in the sequence a
    result goes wrong (a scan whose blocks each start from zero is right up
    to the first block's end); this does not."""
    return -_picked_log_probs(params, tokens, spec, row_block, faults)[0]


def adamw_init(params):
    return {"mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def make_train_step(spec, *, lr, weight_decay, b1=0.9, b2=0.999, eps=1e-8,
                    row_block=None, faults=()):
    """One AdamW step (decoupled decay added to the Adam direction, then
    scaled by -lr), jitted: (params, opt, tokens) -> (params, opt, loss,
    per-leaf gradient norms, taken ids)."""

    def step(params, opt, tokens):
        (loss, taken), grads = jax.value_and_grad(
            sequence_loss, has_aux=True)(params, tokens, spec,
                                         row_block=row_block, faults=faults)
        count = opt["count"] + 1
        t = count.astype(jnp.float32)

        def moments(g, mu, nu):
            g32 = g.astype(jnp.float32)
            return ((b1 * mu + (1 - b1) * g32).astype(mu.dtype),
                    (b2 * nu + (1 - b2) * g32 * g32).astype(nu.dtype))

        def apply(p, mu, nu):
            direction = ((mu.astype(jnp.float32) / (1 - b1 ** t))
                         / (jnp.sqrt(nu.astype(jnp.float32) / (1 - b2 ** t))
                            + eps))
            step = -lr * (direction + weight_decay * p.astype(jnp.float32))
            return (p.astype(jnp.float32) + step).astype(p.dtype)

        new = jax.tree.map(moments, grads, opt["mu"], opt["nu"])
        mu = jax.tree.map(lambda g, mn: mn[0], grads, new)
        nu = jax.tree.map(lambda g, mn: mn[1], grads, new)
        params = jax.tree.map(apply, params, mu, nu)
        gnorms = jax.tree.map(
            lambda g: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))),
            grads)
        return (params, {"mu": mu, "nu": nu, "count": count}, loss, gnorms,
                taken)

    return jax.jit(step, donate_argnums=(0, 1))


def cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)
