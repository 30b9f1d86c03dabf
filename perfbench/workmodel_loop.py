"""Operations and bytes that a model needs whose stack of layers runs several
times over the same weights (a looped LM with an exit gate), from shapes
alone.

``describe`` reads the configuration's published keys (``ouro``'s) into plain
data (what the runner builds the program from and hands the plain reference).
The counting follows ``workmodel.py`` (imported, not copied): a product (m,
k) x (k, n) is 2*m*k*n operations, a train step is forward plus twice
forward, attention counts the causal pairs; recomputation (a layer
application or a block of the head run again in the backward pass), softmax,
norms and elementwise work are not counted: a recomputed forward pass is
time, not work. Every pass is counted: the passes share weights, not work.
"""

from __future__ import annotations

from typing import Tuple

from workmodel import F32, attended_pairs, matmul_flops


def describe(cfg):
    """The model as plain data (what ``reference/lm_loop_plain.py`` takes):
    the first ``num_hidden_layers`` layers, all of one kind."""
    layers = int(cfg["num_hidden_layers"])
    if cfg["layer_types"] != ["full_attention"] * layers:
        raise ValueError("full-attention layers only, one a layer")
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]:
        raise ValueError("a gated silu MLP and an untied head only")
    if cfg["sliding_window"] is not None or cfg["rope_scaling"] is not None:
        raise ValueError("no window and no rope scaling here")
    if float(cfg["early_exit_threshold"]) != 1.0:
        raise ValueError("an exit before the last pass is not computed")
    return {"vocab": int(cfg["vocab_size"]), "dim": int(cfg["hidden_size"]),
            "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "width": int(cfg["intermediate_size"]), "layers": layers,
            "passes": int(cfg["total_ut_steps"]),
            "rope_base": float(cfg["rope_theta"]),
            "norm_eps": float(cfg["rms_norm_eps"]),
            "beta": float(cfg["exit_loss"]["beta"])}


def layer_forward_flops(spec, seq: int) -> float:
    """One application of one layer: the four projections, attention over
    the causal pairs, the gated MLP's three products."""
    dim, dh = spec["dim"], spec["head_dim"]
    q_dim, kv_dim = spec["heads"] * dh, spec["kv_heads"] * dh
    return (matmul_flops(seq, dim, q_dim + 2 * kv_dim)
            + matmul_flops(seq, q_dim, dim)
            + spec["heads"] * 2 * 2.0 * attended_pairs(seq) * dh
            + 3 * matmul_flops(seq, dim, spec["width"]))


def head_forward_flops(spec, seq: int) -> float:
    """One pass's head and its gate."""
    return (matmul_flops(seq, spec["dim"], spec["vocab"])
            + matmul_flops(seq, spec["dim"], 1))


def train_flops(spec, seq: int) -> float:
    """One optimizer step over one sequence: ``passes`` x (every layer, a
    head, the gate). Forward + 2x backward."""
    return 3.0 * spec["passes"] * (
        spec["layers"] * layer_forward_flops(spec, seq)
        + head_forward_flops(spec, seq))


def head_loss_work(spec, seq: int) -> Tuple[float, float]:
    """(operations, bytes) of one step's heads and cross-entropies, the
    model's whatever implements them: per pass the (S, dim) x (dim, vocab)
    product forward and its two backward; the float32 logits written and
    read once forward (the product writes, the softmax reads) and their
    gradient written and read once backward; the head's matrix read once
    each way and its gradient written once a step. A block recomputed in the
    backward pass is time and not work."""
    logits = F32 * seq * spec["vocab"]
    matrix = F32 * spec["dim"] * spec["vocab"]
    flops = 3.0 * spec["passes"] * matmul_flops(seq, spec["dim"],
                                                spec["vocab"])
    return flops, spec["passes"] * 4 * logits + 3 * matrix
