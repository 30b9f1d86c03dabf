"""Operations and bytes that the algorithm needs, from shapes alone.

Copied arithmetic (``fiber_tpu/utils/flops.py`` counts the same way; the
copy lives here so that no later PR can change the yardstick): a product
(m, k) x (k, n) is 2*m*k*n operations; a train step is forward plus twice
forward; recomputation, softmax, norms and the optimizer's elementwise
work are not counted. The attention functions count the pairs the
*algorithm* attends (causal, window), KV shared by a query group read
once, so they read the same whatever implements the attention.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

F32 = 4  # bytes; both configurations run float32 storage


def matmul_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def attended_pairs(seq: int, window: Optional[int] = None) -> float:
    """(query, key) pairs of one causal head: position i sees
    min(i + 1, window) keys."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


# -- attention kernels ---------------------------------------------------
def flash_fwd_work(seq: int, heads: int, kv_heads: int, head_dim: int,
                   window: Optional[int] = None, batch: int = 1,
                   chips: int = 1) -> Tuple[float, float]:
    """(operations, bytes) of one forward attention over ``batch``
    sequences: Q.K^T and P.V over the attended pairs; Q read and O and
    the row statistics written once, K and V read once per KV head. On a
    ring of ``chips`` a chip reads the K and V blocks up to its own:
    chips*(chips+1)/2 block reads where one chip makes chips."""
    pairs = attended_pairs(seq, window)
    flops = batch * heads * 2 * 2.0 * pairs * head_dim
    kv_reads = (chips + 1) / 2.0
    nbytes = batch * F32 * (2 * seq * heads * head_dim + seq * heads
                            + kv_reads * 2 * seq * kv_heads * head_dim)
    return flops, nbytes


def flash_bwd_work(seq: int, heads: int, kv_heads: int, head_dim: int,
                   window: Optional[int] = None, batch: int = 1,
                   chips: int = 1) -> Tuple[float, float]:
    """(operations, bytes) of the backward: the four products it needs
    (dV = P^T.dO, dP = dO.V^T, dQ = dS.K, dK = dS^T.Q), never the
    recomputed Q.K^T; reads Q, O, dO, the row statistics, K, V; writes
    dQ, dK, dV."""
    pairs = attended_pairs(seq, window)
    flops = batch * heads * 4 * 2.0 * pairs * head_dim
    kv_reads = (chips + 1) / 2.0
    nbytes = batch * F32 * (4 * seq * heads * head_dim + 2 * seq * heads
                            + (kv_reads * 2 + 2) * seq * kv_heads * head_dim)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict,
                  chips: int = 1) -> Tuple[float, str]:
    """The least time ``chips`` chips could take, and which bound sets it."""
    t_flops = flops / (chips * peak["flops_bf16"])
    t_bytes = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


# -- the LM train step ---------------------------------------------------
def lm_train_flops(*, seq: int, dim: int, heads: int, kv_heads: int,
                   layers: int, vocab: int, mlp_hidden: int,
                   window: Optional[int] = None, batch: int = 1) -> float:
    """One optimizer step over ``batch`` sequences of ``seq`` tokens:
    q, kv, out and MLP projections, attention over the attended pairs,
    the unembedding; embeddings are lookups. Forward + 2x backward."""
    head_dim = dim // heads
    per_block = (matmul_flops(seq, dim, dim)                       # wq
                 + matmul_flops(seq, dim, 2 * kv_heads * head_dim)  # wkv
                 + matmul_flops(seq, dim, dim)                     # wo
                 + matmul_flops(seq, dim, mlp_hidden)
                 + matmul_flops(seq, mlp_hidden, dim)
                 + heads * 2 * 2.0 * attended_pairs(seq, window) * head_dim)
    fwd = layers * per_block + matmul_flops(seq, dim, vocab)
    return 3.0 * batch * fwd


# -- one ES generation ---------------------------------------------------
#: scalar operations of one walker step outside the policy (the program's
#: own table says 600; kept, since it is under 0.5% of a policy pass)
WALKER_STEP_FLOPS = 600.0


def mlp_policy_flops(sizes: Sequence[int]) -> float:
    return sum(matmul_flops(1, a, b) for a, b in zip(sizes[:-1], sizes[1:]))


def es_generation_flops(*, sizes: Sequence[int], pop: int, steps: int,
                        dim: int) -> float:
    """``pop`` rollouts of ``steps`` policy passes and walker steps, the
    (1, pop) x (pop, dim) gradient product, and 4 operations a
    perturbation element (noise scale, +, -, update)."""
    rollout = steps * (mlp_policy_flops(sizes) + WALKER_STEP_FLOPS)
    return pop * rollout + matmul_flops(1, pop, dim) + 4.0 * pop * dim
