"""Plain reference of a decoder-only LM whose stack of layers runs several
times over the same weights, with an exit gate (the LoopLM of "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741, as the
``ouro`` modelling code computes it): loss, gradients, AdamW.

Straightforward ``jax.numpy`` in float32 with exact float32 matrix products
(``precision=HIGHEST``). The passes are a **written-out Python loop**:
``passes x layers`` layer applications one after another, no scan over the
passes; attention is a full masked score matrix head by head; every pass has
its own head and cross-entropy; the exit distribution is its product
formula. It imports nothing of ``fiber_tpu`` and takes nothing the program
has made: weights are drawn here from the seed, by the stream
``init_params`` states.

The model is handed over as plain data (``spec``): ``vocab``, ``dim``,
``heads``, ``kv_heads``, ``head_dim``, ``width``, ``layers``, ``passes``,
``rope_base``, ``norm_eps``, ``beta``. With ``N`` an RMSNorm with a gain of
its own:

* a layer, on rows h (S, dim): ``h <- h + N2(Attn(N1(h)))``, then ``h <- h +
  N4(MLP(N3(h)))`` (a norm before and behind each part). ``Attn``: q, k, v =
  u Wq, u Wk, u Wv, no bias; rope over the whole head (half-split pairing,
  ``rotate_half``) at ``rope_base``; causal ``softmax(q k^T / sqrt(dh)) v``
  over all positions, query head j reading KV head j // (heads / kv_heads);
  then Wo. ``MLP``: ``(silu(u Wg) * (u Wu)) Wd``.
* the loop: ``x_0 = E[tokens]``; for t = 1..passes: h = x_(t-1) through
  layers 0..L-1, ``x_t = N_f(h)``: the final norm closes every pass, and its
  output is what the next pass starts from.
* exits: ``logits_t = x_t W_out``, ``lambda_t = sigmoid(x_t w_g + b_g)``;
  ``p_1 = lambda_1``, ``p_t = lambda_t prod_(j<t) (1 - lambda_j)``, and the
  last pass takes what is left, ``prod_(j<passes) (1 - lambda_j)``.
* loss, the mean over positions 0..S-2 of ``sum_t p_t CE(logits_t, next
  token) - beta H(p)``, ``H(p) = -sum_t p_t log p_t``.

Departures from the published description (ByteDance/Ouro-2.6B's
``config.json`` and the ``ouro`` modelling code), the same as the program's
and listed in the configuration's file under ``assumed``: the training loss
is the paper's first-stage objective with a uniform prior (entropy form) and
``beta`` 0.05 (no config states it); no second-stage gate training; every
matrix 0.02 x normal, the gate's too.

Memory is held down by recomputing (``jax.checkpoint``) layer application
by layer application, attention head by head and block of rows by block of
rows, and each head's cross-entropy block of rows by block of rows, which
changes no arithmetic. ``dtype=jnp.bfloat16`` (``cast``) stores weights,
activations and optimizer state in bfloat16: the control of the comparison,
never the reference. ``faults`` (a tuple of names) are for the tests and the
readings, never the reference: ``three_passes`` (the last pass left out: the
exits are over one pass fewer), ``no_pass_norm`` (the final norm between
passes left out: a pass starts from the rows before it; heads and gate still
read the normed rows), ``no_post_norm`` (the norms behind the parts left
out), ``last_pass_loss`` (the loss is the last pass's cross-entropy: no exit
weights, no entropy), ``no_entropy`` (the entropy term left out),
``first_pass_logits`` (every pass's cross-entropy read from pass 1's rows),
``half_loss`` (the loss over the first half of the positions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
INIT_SCALE = 0.02


def init_params(key, spec):
    """Matrices 0.02 * normal, gains 1, the gate's bias 0. The stream: split
    the key in four (embed, unused, out, rest); per layer split ``rest`` in
    seven: 0 wqkv (one draw of (dim, 3 x heads x head_dim): q, k and v are
    its thirds; with fewer KV heads 0 is wq and 4 wkv), 1 wo, 2 wg, 3 wd,
    5 wu, 6 the next layer's rest; the gate's ``gate_w`` is drawn from the
    rest the last layer leaves."""
    dim, dh = spec["dim"], spec["head_dim"]
    q_dim, kv_dim = spec["heads"] * dh, spec["kv_heads"] * dh

    def normal(k, *shape):
        return INIT_SCALE * jax.random.normal(k, shape)

    k_emb, _, k_out, key = jax.random.split(key, 4)
    params = {"embed": normal(k_emb, spec["vocab"], dim),
              "out": normal(k_out, dim, spec["vocab"]),
              "final_norm": jnp.ones((dim,)), "blocks": []}
    for _ in range(spec["layers"]):
        ks = jax.random.split(key, 7)
        key = ks[6]
        blk = {"norm1": jnp.ones((dim,)), "post_norm1": jnp.ones((dim,)),
               "norm2": jnp.ones((dim,)), "post_norm2": jnp.ones((dim,)),
               "wo": normal(ks[1], q_dim, dim),
               "wg": normal(ks[2], dim, spec["width"]),
               "wd": normal(ks[3], spec["width"], dim),
               "wu": normal(ks[5], dim, spec["width"])}
        if spec["kv_heads"] == spec["heads"]:
            blk["wqkv"] = normal(ks[0], dim, 3 * q_dim)
        else:
            blk["wq"] = normal(ks[0], dim, q_dim)
            blk["wkv"] = normal(ks[4], dim, 2 * kv_dim)
        params["blocks"].append(blk)
    params["gate_w"] = normal(key, dim)
    params["gate_b"] = jnp.zeros(())
    return params


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, gain, eps):
    return gain * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, base):
    """x (S, H, dh): rotate the two halves of every head by position."""
    dh = x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, dh, 2) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, row_block):
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


def layer(h, blk, spec, row_block, faults=()):
    """One application of one layer to the rows h (S, dim)."""
    S = h.shape[0]
    heads, kvh, dh = spec["heads"], spec["kv_heads"], spec["head_dim"]
    eps = spec["norm_eps"]
    positions = jnp.arange(S)

    def behind(y, gain):
        return y if "no_post_norm" in faults else _rms(y, gain, eps)

    u = _rms(h, blk["norm1"], eps)
    if "wqkv" in blk:
        q, k, v = jnp.split(_mm(u, blk["wqkv"]), 3, axis=-1)
    else:
        q = _mm(u, blk["wq"])
        k, v = jnp.split(_mm(u, blk["wkv"]), 2, axis=-1)
    attn = _attention(
        _rope(q.reshape(S, heads, dh), positions, spec["rope_base"]),
        _rope(k.reshape(S, kvh, dh), positions, spec["rope_base"]),
        v.reshape(S, kvh, dh), row_block)
    h = h + behind(_mm(attn.reshape(S, heads * dh), blk["wo"]),
                   blk["post_norm1"])
    u = _rms(h, blk["norm2"], eps)
    mlp = _mm(jax.nn.silu(_mm(u, blk["wg"])) * _mm(u, blk["wu"]), blk["wd"])
    return h + behind(mlp, blk["post_norm2"])


def head_losses(x, targets, out, row_block):
    """One head: the cross-entropy of each row of x (N, dim) against its
    target, (N,) float32, block of rows by block of rows."""
    n, dim = x.shape

    def rows(inp):
        xb, tb = inp
        logp = jax.nn.log_softmax(_mm(xb, out).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=1)[:, 0]

    pad = -n % row_block
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    tp = jnp.pad(targets, (0, pad))
    ce = jax.lax.map(jax.checkpoint(rows),
                     (xp.reshape(-1, row_block, dim),
                      tp.reshape(-1, row_block)))
    return ce.reshape(-1)[:n]


def exit_distribution(gates):
    """gates: one (S,) array of ``lambda_t`` a pass -> (passes, S): the
    product formula, the last pass taking what is left."""
    p, left = [], jnp.ones_like(gates[0])
    for lam in gates[:-1]:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])


def pass_losses(params, tokens, spec, *, row_block=None, faults=()):
    """(each pass's cross-entropy of each position 0..S-2, (passes, S - 1);
    the exit distribution of each position, (passes, S)), float32."""
    S = tokens.shape[0]
    eps = spec["norm_eps"]
    row_block = min(row_block or 2048, S)
    passes = spec["passes"] - ("three_passes" in faults)
    h = params["embed"][tokens]
    ce, gates = [], []
    for t in range(passes):
        for blk in params["blocks"]:
            h = jax.checkpoint(
                lambda h, blk: layer(h, blk, spec, row_block, faults))(h, blk)
        x = _rms(h, params["final_norm"], eps)
        if "no_pass_norm" not in faults:
            h = x
        if t == 0 or "first_pass_logits" not in faults:
            read = x
        ce.append(head_losses(read[:-1], tokens[1:], params["out"],
                              row_block))
        gates.append(jax.nn.sigmoid(
            jnp.sum(x.astype(jnp.float32) * params["gate_w"], axis=-1)
            + params["gate_b"]))
    return jnp.stack(ce), exit_distribution(gates)


def sequence_loss(params, tokens, spec, *, row_block=None, faults=()):
    """The expected-exit loss of one sequence of tokens (S,)."""
    ce, p = pass_losses(params, tokens, spec, row_block=row_block,
                        faults=faults)
    if "last_pass_loss" in faults:
        return jnp.mean(ce[-1])
    p = p[:, :-1]
    if "half_loss" in faults:
        ce, p = ce[:, :tokens.shape[0] // 2], p[:, :tokens.shape[0] // 2]
    expected = jnp.sum(p * ce, axis=0)
    if "no_entropy" in faults:
        return jnp.mean(expected)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(expected - spec["beta"] * entropy)


def adamw_init(params):
    return {"mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def make_train_step(spec, *, lr, weight_decay, b1=0.9, b2=0.999, eps=1e-8,
                    row_block=None, faults=()):
    """One AdamW step (decoupled decay added to the Adam direction, then
    scaled by -lr), jitted: (params, opt, tokens) -> (params, opt, loss,
    per-leaf gradient norms)."""

    def step(params, opt, tokens):
        loss, grads = jax.value_and_grad(sequence_loss)(
            params, tokens, spec, row_block=row_block, faults=faults)
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
        return params, {"mu": mu, "nu": nu, "count": count}, loss, gnorms

    return jax.jit(step, donate_argnums=(0, 1))


def cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)
