"""Plain reference of a decoder-only LM of latent-attention layers (MLA) with
a leading dense layer, sparse-expert layers of which a share of the experts is
held here, and a multi-token-prediction module (loss, gradients, AdamW).

Straightforward ``jax.numpy`` in float32 with exact float32 matrix products
(``precision=HIGHEST``, what ``jax.default_matmul_precision("highest")``
gives), a full masked score matrix per head and block of query rows, the
expert layer of ``lm_moe_plain.py`` (a loop over the held experts with a
mask). It imports nothing of ``fiber_tpu`` and takes nothing the program has
made: weights are drawn here from the seed, by the stream ``init_params``
states.

The model is handed over as plain data (``spec``, ``workmodel_mla.describe``):
``vocab``, ``dim``, ``heads``, ``q_rank``, ``kv_rank``, ``nope``,
``rope_dim``, ``v_dim``, ``rope_base``, ``norm_eps``, ``share`` = (index,
shares) of the experts held here, ``layers`` (one dict a layer: ``ffn``
"gated" with ``width``, or "experts" with ``experts`` = {``total``,
``top_k``, ``width``, ``shared_width``, ``scale``}) and ``mtp`` = {``depth``,
``weight``, ``layer``}.

Per layer, on x (S, dim), DeepSeek-V3 (arXiv:2412.19437) section 2.1.1:

    h = RMSNorm(x)
    q = RMSNorm_q(h W_qa) W_qb as (S, H, nope + rope) = [q_nope ; q_pe]
    [c_kv ; k_pe] = h W_kva                       (kv_rank + rope)
    [k_nope ; v] = RMSNorm_kv(c_kv) W_kvb as (S, H, nope + v)
    q_pe, k_pe turned by position: adjacent pairs (2j, 2j + 1) by
        i * base^(-2j / rope)
    k_h = [k_nope_h ; k_pe]                       (one k_pe for all heads)
    o_h = softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h
    x += [o_1 .. o_H] W_o

Then h2 = RMSNorm(x) and x += (silu(h2 Wg) * (h2 Wu)) Wd, or the expert
layer (``lm_moe_plain.expert_layer``: sigmoid scores over all experts, the
top_k largest, weights renormalised over the taken and times scale, the
shared expert plus the held experts' part; what absent experts would add is
left out). Final RMSNorm, untied head, mean next-token cross-entropy over
positions 0..S-2.

The MTP module (section 2.2, one module): m = W_eh [RMSNorm_e(Emb(t_{i+1}))
; RMSNorm_h(h_i)] with h the stream after the last layer before the final
norm and a zero embedding on the last row; one whole layer on m; RMSNorm and
the main head; weight times the mean cross-entropy against t_{i+2} over
positions 0..S-3 is added to the loss.

Memory is held down by recomputing (``jax.checkpoint``) layer by layer, head
by head, block of rows by block of rows and expert by expert, which changes
no arithmetic. ``dtype=jnp.bfloat16`` stores weights, activations and
optimizer state in bfloat16: the control of the comparison, never the
reference. ``faults`` (a tuple of names) are for the tests and the readings,
never the reference: ``no_mtp`` (the MTP term left out of the loss),
``no_kv_norm`` (c_kv enters W_kvb without its norm), ``half_rope`` (the rope
pairs the two halves of q_pe and k_pe, not adjacent features).
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp


def _sibling(name):
    """A module of this directory, by its file (this one is loaded by name
    or by path alike)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("mla_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


moe = _sibling("lm_moe_plain")
INIT_SCALE = moe.INIT_SCALE
adamw_init = moe.adamw_init
cast = moe.cast
_mm = moe._mm


def _rms(x, gain, eps):
    return gain * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _normal(k, *shape):
    return INIT_SCALE * jax.random.normal(k, shape)


def _init_layer(spec, layer, ks):
    """One layer's leaves from its seven keys: 0 split in two (wq_a, wq_b),
    1 wo, 2 wg, 3 wd, 4 split in two (wkv_a, wkv_b), 5 wu of a dense layer
    or the expert layer's seven draws (router, shared wg, wu, wd, held
    experts' wg, wu, wd)."""
    dim, H = spec["dim"], spec["heads"]
    qk = spec["nope"] + spec["rope_dim"]
    k_qa, k_qb = jax.random.split(ks[0])
    k_kva, k_kvb = jax.random.split(ks[4])
    blk = {"norm1": jnp.ones((dim,)), "norm2": jnp.ones((dim,)),
           "wq_a": _normal(k_qa, dim, spec["q_rank"]),
           "q_norm": jnp.ones((spec["q_rank"],)),
           "wq_b": _normal(k_qb, spec["q_rank"], H * qk),
           "wkv_a": _normal(k_kva, dim, spec["kv_rank"] + spec["rope_dim"]),
           "kv_norm": jnp.ones((spec["kv_rank"],)),
           "wkv_b": _normal(k_kvb, spec["kv_rank"],
                            H * (spec["nope"] + spec["v_dim"])),
           "wo": _normal(ks[1], H * spec["v_dim"], dim)}
    if layer["ffn"] == "gated":
        w = layer["width"]
        blk.update(wg=_normal(ks[2], dim, w), wd=_normal(ks[3], w, dim),
                   wu=_normal(ks[5], dim, w))
        return blk
    e = layer["experts"]
    held = moe.held_range(e["total"], spec["share"])[1]
    sub = jax.random.split(ks[5], 7)
    blk.update(
        router=_normal(sub[0], dim, e["total"]),
        shared_wg=_normal(sub[1], dim, e["shared_width"]),
        shared_wu=_normal(sub[2], dim, e["shared_width"]),
        shared_wd=_normal(sub[3], e["shared_width"], dim),
        experts_wg=_normal(sub[4], held, dim, e["width"]),
        experts_wu=_normal(sub[5], held, dim, e["width"]),
        experts_wd=_normal(sub[6], held, e["width"], dim))
    return blk


def init_params(key, spec):
    """Weights 0.02 * normal, gains 1. The stream: split the key in four
    (embed, unused, out, rest); per layer split ``rest`` in seven
    (``_init_layer`` takes the first six, 6 is the next rest); the MTP module
    splits the last rest in two: eh_proj, then its layer's seven keys."""
    dim = spec["dim"]
    k_emb, _, k_out, key = jax.random.split(key, 4)
    params = {"embed": _normal(k_emb, spec["vocab"], dim),
              "out": _normal(k_out, dim, spec["vocab"]),
              "final_norm": jnp.ones((dim,)), "blocks": []}
    for layer in spec["layers"]:
        ks = jax.random.split(key, 7)
        key = ks[6]
        params["blocks"].append(_init_layer(spec, layer, ks))
    k_eh, k_blk = jax.random.split(key)
    params["mtp"] = {"enorm": jnp.ones((dim,)), "hnorm": jnp.ones((dim,)),
                     "eh_proj": _normal(k_eh, 2 * dim, dim),
                     "norm": jnp.ones((dim,)),
                     "block": _init_layer(spec, spec["mtp"]["layer"],
                                          jax.random.split(k_blk, 7))}
    return params


def rope(x, positions, base, faults=()):
    """x (S, heads, r): feature pairs (2j, 2j + 1) turned by position x
    base^(-2j / r); with the fault ``half_rope`` the pairs (j, j + r/2)."""
    r = x.shape[-1]
    inv = base ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if "half_rope" in faults:
        a, b = x[..., :r // 2], x[..., r // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1).astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _attention(q, k, v, row_block):
    """q, k (S, H, dqk), v (S, H, dv) -> (S, H, dv): causal, at scale
    dqk^-0.5, one head and ``row_block`` query rows at a time."""
    S, _, dqk = q.shape
    dv = v.shape[-1]
    scale = 1.0 / (dqk ** 0.5)
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
                          (qh.reshape(nb, row_block, dqk),
                           kv_pos.reshape(nb, row_block)))
        return out.reshape(S, dv)

    out = jax.lax.map(jax.checkpoint(one_head),
                      tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
    return jnp.swapaxes(out, 0, 1)


def latent_attention(x, blk, spec, positions, row_block, faults=()):
    """What a latent layer adds to the stream x (S, dim)."""
    S, H, eps = x.shape[0], spec["heads"], spec["norm_eps"]
    nope, r = spec["nope"], spec["rope_dim"]
    h = _rms(x, blk["norm1"], eps)
    q = _mm(_rms(_mm(h, blk["wq_a"]), blk["q_norm"], eps),
            blk["wq_b"]).reshape(S, H, nope + r)
    ckv_pe = _mm(h, blk["wkv_a"])
    c_kv, k_pe = ckv_pe[:, :spec["kv_rank"]], ckv_pe[:, spec["kv_rank"]:]
    if "no_kv_norm" not in faults:
        c_kv = _rms(c_kv, blk["kv_norm"], eps)
    kv = _mm(c_kv, blk["wkv_b"]).reshape(S, H, nope + spec["v_dim"])
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, spec["rope_base"],
                             faults)], axis=-1)
    k_pe = rope(k_pe[:, None, :], positions, spec["rope_base"], faults)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (S, H, r))],
                        axis=-1)
    o = _attention(q, k, kv[..., nope:], row_block)
    return _mm(o.reshape(S, H * spec["v_dim"]), blk["wo"])


def layer_apply(x, blk, layer, spec, positions, row_block, faults=()):
    """One whole layer: (x after it, taken expert ids or None)."""
    x = x + latent_attention(x, blk, spec, positions, row_block, faults)
    h = _rms(x, blk["norm2"], spec["norm_eps"])
    if layer["ffn"] == "gated":
        return x + moe._swiglu(h, blk["wg"], blk["wu"], blk["wd"]), None
    y, ids = moe.expert_layer(h, blk, layer["experts"], spec["share"])
    return x + y, ids


def _head_losses(rows, targets, out, row_block):
    """Cross-entropy of each row of ``rows`` (N, dim) against ``targets``
    under ``out``, in blocks of rows, each recomputed."""
    n = rows.shape[0]
    pad = -n % row_block
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    targets = jnp.pad(targets, (0, pad))

    def block(args):
        r, t = args
        logp = jax.nn.log_softmax(_mm(r, out).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=1)[:, 0]

    ce = jax.lax.map(jax.checkpoint(block),
                     (rows.reshape(-1, row_block, rows.shape[-1]),
                      targets.reshape(-1, row_block)))
    return ce.reshape(-1)[:n]


def sequence_loss(params, tokens, spec, *, row_block=None, faults=()):
    """(the loss of one sequence of tokens (S,): main next-token
    cross-entropy plus the MTP term, the taken expert ids of each expert
    layer, the MTP module's last (expert layers, S, top_k))."""
    S = tokens.shape[0]
    eps = spec["norm_eps"]
    row_block = min(row_block or 2048, S)
    positions = jnp.arange(S)
    x = params["embed"][tokens]
    taken = []

    def run(layer, x, blk):
        return jax.checkpoint(
            lambda x, blk: layer_apply(x, blk, layer, spec, positions,
                                       row_block, faults))(x, blk)

    for layer, blk in zip(spec["layers"], params["blocks"]):
        x, ids = run(layer, x, blk)
        if ids is not None:
            taken.append(ids)
    main = jnp.mean(_head_losses(_rms(x, params["final_norm"], eps)[:-1],
                                 tokens[1:], params["out"], row_block))
    m = params["mtp"]
    nxt = jnp.concatenate([params["embed"][tokens[1:]],
                           jnp.zeros((1, x.shape[1]), x.dtype)])
    z = _mm(jnp.concatenate([_rms(nxt, m["enorm"], eps),
                             _rms(x, m["hnorm"], eps)], axis=-1),
            m["eh_proj"])
    z, ids = run(spec["mtp"]["layer"], z, m["block"])
    if ids is not None:
        taken.append(ids)
    extra = jnp.mean(_head_losses(_rms(z, m["norm"], eps)[:-2], tokens[2:],
                                  params["out"], row_block))
    loss = main if "no_mtp" in faults else (
        main + spec["mtp"]["weight"] * extra)
    return loss, jnp.stack(taken)


# One AdamW step (decoupled decay added to the Adam direction, then scaled by
# -lr), jitted: (params, opt, tokens) -> (params, opt, loss, per-leaf
# gradient norms, taken ids). It is ``lm_moe_plain``'s, run on this loss: the
# copy of that module loaded here (``_sibling``) is this file's own, and its
# step reads ``sequence_loss`` from it.
moe.sequence_loss = sequence_loss
make_train_step = moe.make_train_step
