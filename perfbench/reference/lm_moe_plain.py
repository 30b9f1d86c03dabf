"""Plain reference of a decoder-only LM whose layers differ: window and full
attention layers with their own query-head counts and ropes, a gated dense
MLP or a sparse-expert feed-forward of which a share of the experts is held
here (loss, gradients, AdamW).

Straightforward ``jax.numpy`` in float32 with exact float32 matrix products
(``precision=HIGHEST``), a full masked score matrix per head, a loop over
the held experts with a mask (no sort, no grouped product, no kernel). It
imports nothing of ``fiber_tpu`` and takes nothing the program has made:
weights are drawn here from the seed, by the stream ``init_params`` states.

The model is handed over as plain data (``spec``): ``vocab``, ``dim``,
``head_dim``, ``kv_heads``, ``share`` = (index, shares) of the experts held
here, and ``layers``, one dict a layer: ``heads``, ``window`` (or None),
``rope`` = {``base``, ``rotary``, ``yarn`` or None}, ``ffn`` ("gated" or
"experts"), ``width`` (gated) or ``experts`` = {``total``, ``top_k``,
``width``, ``shared_width``, ``scale``}.

Per layer, on x (S, dim):  h = RMSNorm(x); q = h Wq as (S, heads, dh),
k, v = h Wkv as (S, kv_heads, dh); rope on the first ``rotary`` features of
each head of q and k (half-split pairing), the rest pass; causal attention
at scale dh^-0.5, query head j reading KV head j // (heads / kv_heads), the
last ``window`` positions only where the layer has a window; x += attn Wo.
Then h2 = RMSNorm(x) and x += (silu(h2 Wg) * (h2 Wu)) Wd, or the expert
layer: s = sigmoid(h2 Wr) over all experts, the ``top_k`` largest taken,
w_e = scale * s_e / (sum of the taken s), y = shared(h2) + sum over the
taken e held here of w_e * expert_e(h2). What absent experts would add is
left out (one chip's share of an expert-parallel layer), and that partial x
goes on. Final RMSNorm, untied head, mean next-token cross-entropy.

Departures from the published description (Laguna-XS.2's ``config.json``),
the same as the program's and listed in the configuration's file: the gate
of ``gating: true`` is read as the gated (SwiGLU) MLP; sigmoid scores
renormalised over the taken, times 2.5, no groups, no selection bias; no
QK norm; YaRN as ``transformers`` computes it (NTK-by-parts ramp,
``truncate`` on).

Memory is held down by recomputing (``jax.checkpoint``) layer by layer,
head by head, block of rows by block of rows and expert by expert, which
changes no arithmetic. ``dtype=jnp.bfloat16`` stores weights, activations
and optimizer state in bfloat16: the control of the comparison, never the
reference. ``faults`` (a tuple of names) are for the tests and the
readings, never the reference: ``half_loss`` (the loss over the first half
of the positions), ``no_routed`` (the held experts' part left out),
``held_norm`` (weights normalised over the taken experts held here only),
``no_window`` (the window layers see everything).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_SCALE = 0.02
NORM_EPS = 1e-6


def held_range(total, share):
    index, shares = share
    count = total // shares
    return index * count, count


def init_params(key, spec):
    """Weights 0.02 * normal, gains 1. The stream: split the key in four
    (embed, unused, out, rest); per layer split ``rest`` in seven: 0 wq,
    1 wo, 2 wg, 3 wd, 4 wkv, 5 wu of a gated layer or, for an expert layer,
    split in seven again (router, shared wg, wu, wd, held experts' wg, wu,
    wd, each one draw of the stacked shape; 2 and 3 unused), 6 rest."""
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
        q_dim = layer["heads"] * dh
        blk = {"norm1": jnp.ones((dim,)), "norm2": jnp.ones((dim,)),
               "wq": normal(ks[0], dim, q_dim),
               "wkv": normal(ks[4], dim, 2 * kvh * dh),
               "wo": normal(ks[1], q_dim, dim)}
        if layer["ffn"] == "gated":
            w = layer["width"]
            blk.update(wg=normal(ks[2], dim, w), wd=normal(ks[3], w, dim),
                       wu=normal(ks[5], dim, w))
        else:
            e = layer["experts"]
            held = held_range(e["total"], spec["share"])[1]
            sub = jax.random.split(ks[5], 7)
            blk.update(
                router=normal(sub[0], dim, e["total"]),
                shared_wg=normal(sub[1], dim, e["shared_width"]),
                shared_wu=normal(sub[2], dim, e["shared_width"]),
                shared_wd=normal(sub[3], e["shared_width"], dim),
                experts_wg=normal(sub[4], held, dim, e["width"]),
                experts_wu=normal(sub[5], held, dim, e["width"]),
                experts_wd=normal(sub[6], held, e["width"], dim))
        params["blocks"].append(blk)
    return params


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, gain):
    return gain * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + NORM_EPS)


def rope_frequencies(rope, head_dim):
    """(features that rotate, their inverse frequencies (r/2,), factor on
    cos and sin). YaRN (arXiv:2309.00071, NTK-by-parts): frequency i of
    base^(-2i/r) makes L * f_i / 2pi turns over the original length L;
    those with more than ``beta_fast`` turns stay, those with fewer than
    ``beta_slow`` are divided by ``factor``, a linear ramp (over the index,
    its ends rounded outwards to whole indices) between."""
    r = head_dim if rope["rotary"] is None else rope["rotary"]
    index = np.arange(r // 2, dtype=np.float64)
    plain = rope["base"] ** (-2.0 * index / r)
    yarn = rope.get("yarn")
    if yarn is None:
        return r, plain.astype(np.float32), 1.0

    def index_of(turns):
        return (r * math.log(yarn["original_max_position"]
                             / (turns * 2.0 * math.pi))
                / (2.0 * math.log(rope["base"])))

    low = max(math.floor(index_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(index_of(yarn["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    scaled = np.clip((index - low) / (high - low), 0.0, 1.0)
    inv = plain * (1.0 - scaled) + plain / yarn["factor"] * scaled
    factor = yarn.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(yarn["factor"]) + 1.0
    return r, inv.astype(np.float32), float(factor)


def _rope(x, positions, rope):
    """x (S, H, dh): rotate the two halves of the leading ``rotary``
    features of every head by position; the rest pass."""
    r, inv, factor = rope_frequencies(rope, x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv)
    cos = (factor * jnp.cos(ang))[:, None, :]
    sin = (factor * jnp.sin(ang))[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]],
        axis=-1).astype(x.dtype)


def _attention(q, k, v, *, window, row_block):
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
            keep = kv_pos[None, :] <= pos[:, None]
            if window is not None:
                keep &= kv_pos[None, :] > pos[:, None] - window
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


def _swiglu(h, wg, wu, wd):
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


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
    counted = taken
    if "held_norm" in faults:
        here = (jnp.arange(e["total"]) >= first) & (
            jnp.arange(e["total"]) < first + count)
        counted = taken & here[None, :]
    total = jnp.sum(jnp.where(counted, scores, 0.0), axis=-1, keepdims=True)
    weights = jnp.where(taken, e["scale"] * scores
                        / jnp.where(total > 0, total, 1.0), 0.0)
    y = _swiglu(h, blk["shared_wg"], blk["shared_wu"], blk["shared_wd"])
    if "no_routed" in faults:
        return y, ids
    here = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)

    def one(y, args):
        wg, wu, wd, w = args
        return y + w[:, None].astype(h.dtype) * _swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(
        jax.checkpoint(one), y,
        (blk["experts_wg"], blk["experts_wu"], blk["experts_wd"], here.T))
    return y, ids


def sequence_loss(params, tokens, spec, *, row_block=None, faults=()):
    """(mean next-token cross-entropy of one sequence of tokens (S,), the
    taken expert ids of each expert layer (expert layers, S, top_k))."""
    S = tokens.shape[0]
    dh, kvh = spec["head_dim"], spec["kv_heads"]
    row_block = min(row_block or 2048, S)
    positions = jnp.arange(S)
    x = params["embed"][tokens]
    taken = []

    def block(layer, x, blk):
        heads = layer["heads"]
        h = _rms(x, blk["norm1"])
        q = _mm(h, blk["wq"]).reshape(S, heads, dh)
        k, v = jnp.split(_mm(h, blk["wkv"]), 2, axis=-1)
        k = k.reshape(S, kvh, dh)
        v = v.reshape(S, kvh, dh)
        window = None if "no_window" in faults else layer["window"]
        attn = _attention(_rope(q, positions, layer["rope"]),
                          _rope(k, positions, layer["rope"]), v,
                          window=window, row_block=row_block)
        x = x + _mm(attn.reshape(S, heads * dh), blk["wo"])
        h = _rms(x, blk["norm2"])
        if layer["ffn"] == "gated":
            return x + _swiglu(h, blk["wg"], blk["wu"], blk["wd"]), None
        y, ids = expert_layer(h, blk, layer["experts"], spec["share"], faults)
        return x + y, ids

    for layer, blk in zip(spec["layers"], params["blocks"]):
        x, ids = jax.checkpoint(
            lambda x, blk, layer=layer: block(layer, x, blk))(x, blk)
        if ids is not None:
            taken.append(ids)
    logits = _mm(_rms(x, params["final_norm"]), params["out"])[:-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=1)[:, 0]
    if "half_loss" in faults:
        picked = picked[:S // 2]
    return -jnp.mean(picked), jnp.stack(taken)


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
