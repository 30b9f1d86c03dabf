"""Plain reference of the decoder-only LM train step (loss, gradients, AdamW).

Straightforward ``jax.numpy`` in float32 with exact float32 matrix
products (``precision=HIGHEST``), a full score matrix with a mask and no
kernel, no ring and no batching trick. It imports nothing of ``fiber_tpu``
and takes nothing the program has made: weights are drawn here from the
seed, by the stream the configuration's file states.

Block: x + Wo.attn(rope(q), rope(k), v) over RMSNorm(x), then
x + W2.gelu_tanh(W1.h + b1) + b2 over RMSNorm(x); grouped-query heads
(query head h reads KV head h // group); causal, and with ``window`` each
position sees the last ``window`` positions, itself included. Loss: mean
next-token cross-entropy over positions 0..S-2. Memory is held down by
recomputing (``jax.checkpoint``) layer by layer, head by head and block of
rows by block of rows, which changes no arithmetic.

``dtype=jnp.bfloat16`` stores weights, activations and optimizer state in
bfloat16: the control of the comparison, never the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
INIT_SCALE = 0.02
ROPE_BASE = 10000.0
NORM_EPS = 1e-6


def init_params(key, *, vocab, dim, heads, kv_heads, layers, mlp_mult):
    """Weights 0.02 * normal, gains 1, biases 0. The stream: split the key
    in four (embed, unused, out, rest); per layer split ``rest`` in seven
    (wq, wo, w1, w2, wkv, unused, rest)."""
    head_dim = dim // heads
    k_emb, _, k_out, key = jax.random.split(key, 4)
    params = {
        "embed": INIT_SCALE * jax.random.normal(k_emb, (vocab, dim)),
        "out": INIT_SCALE * jax.random.normal(k_out, (dim, vocab)),
        "final_norm": jnp.ones((dim,)),
        "blocks": [],
    }
    hid = mlp_mult * dim
    for _ in range(layers):
        ks = jax.random.split(key, 7)
        key = ks[6]
        params["blocks"].append({
            "norm1": jnp.ones((dim,)),
            "wq": INIT_SCALE * jax.random.normal(ks[0], (dim, dim)),
            "wkv": INIT_SCALE * jax.random.normal(
                ks[4], (dim, 2 * kv_heads * head_dim)),
            "wo": INIT_SCALE * jax.random.normal(ks[1], (dim, dim)),
            "norm2": jnp.ones((dim,)),
            "w1": INIT_SCALE * jax.random.normal(ks[2], (dim, hid)),
            "b1": jnp.zeros((hid,)),
            "w2": INIT_SCALE * jax.random.normal(ks[3], (hid, dim)),
            "b2": jnp.zeros((dim,)),
        })
    return params


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, gain):
    return gain * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + NORM_EPS)


def _rope(x, positions):
    """x (S, H, dh): rotate the two halves of every head by position."""
    dh = x.shape[-1]
    inv = 1.0 / (ROPE_BASE ** (jnp.arange(0, dh, 2) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, *, window, seq_block, row_block):
    """q (S, H, dh), k/v (S, KVH, dh) -> (S, H, dh). Full masked score
    matrix, one head and ``row_block`` query rows at a time.
    ``seq_block`` (a fault for the tests, never the reference) cuts the
    sequence into blocks that do not see each other."""
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
            keep = kv_pos[None, :] <= pos[:, None]
            if window is not None:
                keep &= kv_pos[None, :] > pos[:, None] - window
            if seq_block is not None:
                keep &= (kv_pos[None, :] // seq_block
                         == pos[:, None] // seq_block)
            s = jnp.where(keep, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(vh.dtype)
            return _mm(p, vh)

        out = jax.lax.map(jax.checkpoint(rows),
                          (qh.reshape(nb, row_block, dh),
                           kv_pos.reshape(nb, row_block)))
        return out.reshape(S, dh)

    qh = jnp.swapaxes(q, 0, 1)                          # (H, S, dh)
    kh = jnp.repeat(jnp.swapaxes(k, 0, 1), group, axis=0)
    vh = jnp.repeat(jnp.swapaxes(v, 0, 1), group, axis=0)
    out = jax.lax.map(jax.checkpoint(one_head), (qh, kh, vh))
    return jnp.swapaxes(out, 0, 1)


def sequence_loss(params, tokens, *, heads, kv_heads, window=None,
                  seq_block=None, row_block=None, loss_tokens=None):
    """Mean next-token cross-entropy of one sequence of tokens (S,).
    ``loss_tokens`` (a fault for the tests) averages over the first
    ``loss_tokens`` positions only."""
    S = tokens.shape[0]
    dim = params["embed"].shape[1]
    dh = dim // heads
    row_block = min(row_block or 2048, S)
    positions = jnp.arange(S)
    x = params["embed"][tokens]

    def block(x, blk):
        h = _rms(x, blk["norm1"])
        q = _mm(h, blk["wq"]).reshape(S, heads, dh)
        k, v = jnp.split(_mm(h, blk["wkv"]), 2, axis=-1)
        k = k.reshape(S, kv_heads, dh)
        v = v.reshape(S, kv_heads, dh)
        attn = _attention(_rope(q, positions), _rope(k, positions), v,
                          window=window, seq_block=seq_block,
                          row_block=row_block)
        x = x + _mm(attn.reshape(S, dim), blk["wo"])
        h = _rms(x, blk["norm2"])
        up = jax.nn.gelu(_mm(h, blk["w1"]) + blk["b1"], approximate=True)
        return x + _mm(up, blk["w2"]) + blk["b2"]

    for blk in params["blocks"]:
        x = jax.checkpoint(block)(x, blk)
    logits = _mm(_rms(x, params["final_norm"]), params["out"])[:-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=1)[:, 0]
    if loss_tokens is not None:
        picked = picked[:loss_tokens]
    return -jnp.mean(picked)


def batch_loss(params, tokens, **kw):
    """tokens (S,) one sequence, or (B, S): the mean over the rows, one
    row after another (so that one row's residuals are live at a time)."""
    if tokens.ndim == 1:
        return sequence_loss(params, tokens, **kw)
    return jnp.mean(jax.lax.map(
        lambda row: sequence_loss(params, row, **kw), tokens))


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


@functools.partial(
    jax.jit, donate_argnums=(0, 1),
    static_argnames=("heads", "kv_heads", "window", "seq_block", "row_block",
                     "loss_tokens", "lr", "weight_decay"))
def train_step(params, opt, tokens, *, lr, weight_decay, heads, kv_heads,
               window=None, seq_block=None, row_block=None, loss_tokens=None,
               b1=0.9, b2=0.999, eps=1e-8):
    """One AdamW step (decoupled decay added to the Adam direction, then
    scaled by -lr). Returns (params, opt, loss, per-leaf gradient norms)."""
    loss, grads = jax.value_and_grad(batch_loss)(
        params, tokens, heads=heads, kv_heads=kv_heads, window=window,
        seq_block=seq_block, row_block=row_block, loss_tokens=loss_tokens)
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
        lambda g: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))), grads)
    return params, {"mu": mu, "nu": nu, "count": count}, loss, gnorms


def cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)
