"""Sparse-expert feed-forward: a router over all experts, the part of the
result that the experts held here give, a dispatch that drops no token.

One layer of a sparse-expert transformer sends each token to its
``top_k`` of ``total`` experts. Under expert parallelism a chip holds a
contiguous share of them (``held_experts``): it routes over all
``total`` (so every chip agrees on who takes what), computes its own
experts for the tokens that took them and leaves the rest to the other
chips. On one chip the layer runs without the exchange that would add
the other chips' parts; nothing here stands in for them.

Shapes are static and no token is dropped: every (token, held expert)
pair is computed whatever the routing. The pairs are sorted by expert
and walked in chunks of ``chunk_rows`` rows: gather the chunk's token
rows, the expert's grouped products (``jax.lax.ragged_dot``, which XLA's
TPU backend lowers to a grouped Mosaic kernel of its own,
``ragged-dot-*`` in a profile: three for a gated expert, two for an
ungated one), scatter-add. The loops run over the chunks that *exist*
(``ceil(pairs / chunk_rows)``, known on the device), so memory follows
the chunk and time follows the pairs; the worst case (every token on a
held expert) only makes the loop longer. The backward pass is written
out (``jax.custom_vjp``) as the same walk over the forward pass's
sorted pairs, recomputing each chunk, so no residual grows with the
chunk count either. It computes the first chunk on its own, where any
pair is held at all, and that chunk's gradients start the loop over the
others: the matrices' gradients are never a zero-filled array that the
first chunk's are added to, and where one chunk holds every pair the
loop makes no trip; where none is held, no product runs.
"""

from __future__ import annotations

import functools
from typing import Tuple


def held_experts(total: int, share: Tuple[int, int]) -> Tuple[int, int]:
    """(first, count) of the experts that share ``(index, shares)`` of
    an expert-parallel layer holds: ``[index * total / shares,
    (index + 1) * total / shares)``. ``(0, 1)`` is the whole layer."""
    index, shares = share
    if shares < 1 or total % shares or not 0 <= index < shares:
        raise ValueError(
            f"share {share!r} does not divide {total} experts")
    count = total // shares
    return index * count, count


def route(h, router, *, top_k: int, scale: float = 1.0, eps: float = 0.0):
    """Which experts each row of ``h`` (S, d) takes, and with what
    weight: scores ``sigmoid(h @ router)`` over all experts (the
    product in exact float32: a rounded score flips which experts a
    token takes), the ``top_k`` largest, weights ``scale * s / (sum of
    the taken s + eps)`` (the sum over all taken, held here or not;
    LFM2's router adds 1e-6, the others nothing, and with ``eps`` 0 no
    term is added). Returns (ids (S, top_k) int32, weights (S,
    top_k))."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.dot(
        h, router, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    taken, ids = jax.lax.top_k(scores, top_k)
    weighted = scale * taken
    total = jnp.sum(taken, axis=-1, keepdims=True)
    if eps:
        total = total + eps
    return ids.astype(jnp.int32), (weighted / total).astype(h.dtype)


def _held_key(ids, first: int, count: int):
    """Flat (S * top_k,): the held expert's index ``0 .. count - 1`` of
    each (token, expert) pair, ``count`` for a pair on an absent one."""
    import jax.numpy as jnp

    local = ids.reshape(-1) - first
    return jnp.where((local >= 0) & (local < count), local, count)


def expert_load(ids, first: int, count: int):
    """(count,) int32: how many rows of ``ids`` took each held expert."""
    import jax.numpy as jnp

    return jnp.bincount(_held_key(ids, first, count),
                        length=count + 1)[:count].astype(jnp.int32)


def _plan(ids, first: int, count: int, rows: int):
    """The (token, expert) pairs sorted by held expert, pairs on absent
    experts last: (order (padded to whole chunks of ``rows``), ends
    (count,) of each expert's run in the sorted list, pairs on held
    experts)."""
    import jax.numpy as jnp

    order = jnp.argsort(_held_key(ids, first, count),
                        stable=True).astype(jnp.int32)
    pad = -order.shape[0] % rows
    if pad:
        order = jnp.concatenate([order, jnp.zeros((pad,), jnp.int32)])
    ends = jnp.cumsum(expert_load(ids, first, count))
    return order, ends, ends[-1]


def _chunk_of(c, order, ends, pairs, rows: int, top_k: int):
    """Chunk ``c`` of the sorted pairs: (pair index of each row, its
    token, whether the row is a pair on a held expert, rows of the chunk
    that each held expert owns)."""
    import jax
    import jax.numpy as jnp

    lo = c * rows
    pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
    valid = lo + jnp.arange(rows, dtype=jnp.int32) < pairs
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    sizes = (jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows))
    return pair, pair // top_k, valid, sizes


def _grouped(rows, matrices, sizes):
    """Grouped product: the first ``sizes[0]`` of ``rows`` (R, k) times
    ``matrices[0]`` (k, n), the next ``sizes[1]`` times ``matrices[1]``,
    ... -> (R, n). The TPU's kernel works tile by tile and never visits
    a tile past the last group: what the rows that no group owns hold
    afterwards is not defined there (the CPU zeroes them), here and in
    the rows' gradient alike. Callers mask both."""
    import jax

    return jax.lax.ragged_dot(rows, matrices, sizes)


def _expert_hidden(kind: str, products):
    """An expert's hidden activation from its first products:
    ``"swiglu"`` ``silu(x Wg) * (x Wu)`` (gated), ``"relu2"`` ``relu(x
    Wu)^2`` (ungated)."""
    import jax
    import jax.numpy as jnp

    if kind == "swiglu":
        gate, up = products
        return jax.nn.silu(gate) * up
    if kind == "relu2":
        (up,) = products
        return jnp.square(jax.nn.relu(up))
    raise ValueError(f"unknown expert kind {kind!r}")


def _chunk_experts(x, weight, valid, sizes, mats, kind: str = "swiglu"):
    """Rows ``x`` (R, d), sorted by expert with ``sizes`` rows each:
    ``weight * expert_e(x)`` a row, ``mats`` the stacked matrices of the
    expert ``kind`` (``(Wg, Wu, Wd)`` gated, ``(Wu, Wd)`` ungated); 0 in
    the rows that are no pair on a held expert (``valid`` false), which
    no group owns. Every grouped product's result is masked where it is
    made, and ``x`` on the way in, so that its gradient is masked too."""
    import jax
    import jax.numpy as jnp

    keep = valid[:, None]
    with jax.named_scope("experts"):
        x = jnp.where(keep, x, 0.0)
        first = [jnp.where(keep, _grouped(x, m, sizes), 0.0)
                 for m in mats[:-1]]
        down = _grouped(_expert_hidden(kind, first), mats[-1], sizes)
        return jnp.where(keep, down * weight[:, None], 0.0)


def _walk_rows(chunk_rows: int, ids) -> int:
    """Rows of a chunk of the walk over ``ids``' (token, expert) pairs."""
    return min(chunk_rows, ids.size)


def _chunks(pairs, rows: int):
    """Chunks the walk makes over ``pairs`` held pairs."""
    return (pairs + rows - 1) // rows


def routed_experts(h, ids, weights, *mats, first: int, chunk_rows: int,
                   kind: str = "swiglu"):
    """``sum over the taken experts e held here of weights_e *
    expert_e(h)`` for every row of ``h`` (S, d): ``ids`` / ``weights``
    (S, top_k) as ``route`` gives them, ``mats`` the held experts
    ``first .. first + E - 1`` stacked: ``wg`` / ``wu`` (E, d, w) and
    ``wd`` (E, w, d) for ``kind="swiglu"``, ``wu`` and ``wd`` for
    ``"relu2"``. Dropless at static shapes; see the module's head."""
    return _routed(int(first), int(chunk_rows), kind)(h, ids, weights, *mats)


@functools.lru_cache(maxsize=None)
def _routed(first: int, chunk_rows: int, kind: str = "swiglu"):
    """The differentiable walk for one (first held expert, chunk, expert
    kind). The backward pass computes the first chunk on its own and
    starts its loop over the other chunks from that chunk's results: the
    rows' gradient is its scatter, the matrices' gradients are its own,
    with no zeros to add them to."""
    import jax
    import jax.numpy as jnp

    def fwd(h, ids, weights, *mats):
        top_k = ids.shape[1]
        rows = _walk_rows(chunk_rows, ids)
        with jax.named_scope("dispatch"):
            order, ends, pairs = _plan(ids, first, mats[0].shape[0], rows)
        flat_w = weights.reshape(-1)

        def body(c, out):
            with jax.named_scope("dispatch"):
                pair, token, valid, sizes = _chunk_of(
                    c, order, ends, pairs, rows, top_k)
                x = h[token]
            y = _chunk_experts(x, flat_w[pair], valid, sizes, mats, kind)
            with jax.named_scope("combine"):
                return out.at[token].add(y)

        out = jax.lax.fori_loop(0, _chunks(pairs, rows), body,
                                jnp.zeros_like(h))
        return out, (h, weights, mats, order, ends, pairs)

    def bwd(res, d_out):
        h, weights, mats, order, ends, pairs = res
        top_k = weights.shape[1]
        rows = _walk_rows(chunk_rows, weights)
        flat_w = weights.reshape(-1)

        # traced once for the first chunk and the loop's body both
        @functools.partial(jax.jit, inline=True)
        def chunk(c, h, d_out, flat_w, order, ends, pairs, mats):
            with jax.named_scope("dispatch"):
                pair, token, valid, sizes = _chunk_of(
                    c, order, ends, pairs, rows, top_k)
                x, d_y = h[token], d_out[token]
            _, vjp = jax.vjp(
                lambda x, w, *m: _chunk_experts(x, w, valid, sizes, m, kind),
                x, flat_w[pair], *mats)
            d_x, d_row, *g_mats = vjp(d_y)
            return token, d_x, d_row, tuple(g_mats)

        walked = (h, d_out, flat_w, order, ends, pairs, mats)

        def body(c, carry):
            d_h, d_w, d_mats = carry
            token, d_x, d_row, g_mats = chunk(c, *walked)
            with jax.named_scope("combine"):
                d_h = d_h.at[token].add(d_x)
                d_w = jax.lax.dynamic_update_slice(d_w, d_row, (c * rows,))
            return d_h, d_w, tuple(d + g for d, g in zip(d_mats, g_mats))

        def walk():
            token, d_x, d_row, g_mats = chunk(0, *walked)
            with jax.named_scope("combine"):
                d_h = jnp.zeros_like(h).at[token].add(d_x)
                d_w = jnp.zeros(order.shape, weights.dtype).at[:rows].set(
                    d_row)
            return jax.lax.fori_loop(1, _chunks(pairs, rows), body,
                                     (d_h, d_w, g_mats))

        def empty():
            return (jnp.zeros_like(h), jnp.zeros(order.shape, weights.dtype),
                    tuple(jnp.zeros_like(m) for m in mats))

        d_h, d_w, d_mats = jax.lax.cond(pairs > 0, walk, empty)
        with jax.named_scope("combine"):
            # back from sorted order to (token, slot); rows past the held
            # pairs were never written and stay 0 (the padding of
            # ``order`` points at pair 0 and adds those zeros)
            d_weights = jnp.zeros_like(flat_w).at[order].add(d_w)
        return (d_h, None, d_weights.reshape(weights.shape)) + d_mats

    routed = jax.custom_vjp(lambda *a: fwd(*a)[0])
    routed.defvjp(fwd, bwd)
    return routed


def swiglu(h, wg, wu, wd):
    """``(silu(h Wg) * (h Wu)) Wd``: the gated feed-forward."""
    import jax

    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def relu2(h, wu, wd):
    """``relu(h Wu)^2 Wd``: the ungated squared-relu feed-forward."""
    return _expert_hidden("relu2", [h @ wu]) @ wd


#: the parameter names of an expert kind's matrices, in product order
EXPERT_MATRICES = {"swiglu": ("wg", "wu", "wd"), "relu2": ("wu", "wd")}


def moe_ffn(h, blk, *, total: int, top_k: int, scale: float,
            first: int, chunk_rows: int, kind: str = "swiglu", taps=None,
            renorm_eps: float = 0.0):
    """The expert layer on rows ``h`` (S, d): ``shared(h) + routed part
    of the experts held here``. ``blk`` holds ``router`` (d, total) and,
    per matrix of the expert ``kind`` (``EXPERT_MATRICES``), the held
    experts' stacked ``experts_<m>`` and the shared expert's
    ``shared_<m>`` (of its own width); a layer with no shared expert has
    no ``shared_*`` leaf, and its result is the routed part alone, with
    no product and no sum for the shared one. ``renorm_eps`` is
    ``route``'s ``eps``. ``taps``, a list, is given (taken ids, load of
    each held expert, chunks the walk makes over the held pairs)."""
    import jax

    from fiber_tpu.telemetry import device as device_telemetry

    names = EXPERT_MATRICES[kind]
    count = blk["experts_wd"].shape[0]
    device_telemetry.moe_traced(count, total, top_k, walk="first+loop")
    with jax.named_scope("lm.moe"):
        with jax.named_scope("router"):
            ids, weights = route(h, blk["router"], top_k=top_k, scale=scale,
                                 eps=renorm_eps)
        if taps is not None:
            load = expert_load(ids, first, count)
            taps.append((ids, load, _chunks(
                load.sum(), _walk_rows(chunk_rows, ids))))
        routed = routed_experts(
            h, ids, weights, *(blk["experts_" + m] for m in names),
            first=first, chunk_rows=chunk_rows, kind=kind)
        if "shared_" + names[-1] not in blk:
            return routed
        with jax.named_scope("shared"):
            dense = swiglu if kind == "swiglu" else relu2
            shared = dense(h, *(blk["shared_" + m] for m in names))
        with jax.named_scope("combine"):
            return shared + routed
