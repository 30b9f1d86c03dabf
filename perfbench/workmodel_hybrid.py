"""Operations and bytes that a model of one-part layers needs, from shapes
alone: state-space mixers (Mamba-2), attention layers without a position
scheme, sparse-expert layers of ungated experts of which a share is held.

``describe`` reads the configuration's published keys (``nemotron_h``'s)
into plain data (one dict a layer; what the runner builds the program from
and hands the plain reference). The counting follows ``workmodel.py``
(imported, not copied): a product (m, k) x (k, n) is 2*m*k*n operations, a
train step is forward plus twice forward, recomputation, softmax, norms and
elementwise work are not counted, attention counts the causal pairs. The
routed experts are counted at the pairs the routing *expects* on the
experts held here (tokens x top_k x held / total).

The scan is counted in its chunked form at the configuration's block
(``scan_work``): per block of L positions and per head the masked ``C B^T``
(once a group) and its product with ``x`` over the L (L + 1) / 2 causal
pairs, the block's own state, the earlier blocks' part of ``y`` and the
carry. Its bytes are the model's, whatever implements it: x, B, C, dt read
and y written once forward; those, dy and the four gradients once backward.
"""

from __future__ import annotations

from typing import Tuple

from workmodel import F32, attended_pairs, matmul_flops

KINDS = {"M": "ssm", "E": "experts", "*": "attention"}


def describe(cfg):
    """The model as plain data (what ``reference/lm_hybrid_plain.py``
    takes): the first ``num_hidden_layers`` layers of the published
    pattern."""
    total = int(cfg["published"]["n_routed_experts"])
    held = int(cfg["n_routed_experts"])
    parallel = cfg["expert_parallel"]
    if held * int(parallel["shares"]) != total:
        raise ValueError("experts held x shares is not the published count")
    if int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1:
        raise ValueError("no router groups here")
    if cfg["mlp_hidden_act"] != "relu2" or cfg["mamba_hidden_act"] != "silu":
        raise ValueError("relu2 experts and silu mixers only")
    if int(cfg["n_shared_experts"]) != 1:
        raise ValueError("one shared expert only")
    layers = []
    for letter in cfg["hybrid_override_pattern"][
            :int(cfg["num_hidden_layers"])]:
        kind = KINDS[letter]
        if kind == "ssm":
            layers.append({
                "kind": kind, "heads": int(cfg["mamba_num_heads"]),
                "head_dim": int(cfg["mamba_head_dim"]),
                "state": int(cfg["ssm_state_size"]),
                "groups": int(cfg["n_groups"]),
                "conv": int(cfg["conv_kernel"]),
                "chunk": int(cfg["chunk_size"]),
                "dt_min": float(cfg["time_step_min"]),
                "dt_max": float(cfg["time_step_max"]),
                "dt_floor": float(cfg["time_step_floor"])})
        elif kind == "attention":
            layers.append({"kind": kind,
                           "heads": int(cfg["num_attention_heads"])})
        else:
            layers.append({
                "kind": kind, "total": total,
                "top_k": int(cfg["num_experts_per_tok"]),
                "width": int(cfg["moe_intermediate_size"]),
                "shared_width": int(
                    cfg["moe_shared_expert_intermediate_size"]),
                "scale": float(cfg["routed_scaling_factor"])})
    return {"vocab": int(cfg["vocab_size"]), "dim": int(cfg["hidden_size"]),
            "head_dim": int(cfg["head_dim"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "norm_eps": float(cfg["norm_eps"]),
            "share": (int(parallel["index"]), int(parallel["shares"])),
            "layers": layers}


def ssm_layers(spec):
    return [layer for layer in spec["layers"] if layer["kind"] == "ssm"]


def scan_forward_flops(layer, seq: int) -> float:
    """The chunked scan of one layer, forward."""
    L, H, P = layer["chunk"], layer["heads"], layer["head_dim"]
    G, N = layer["groups"], layer["state"]
    pairs = attended_pairs(L)
    per_block = (G * 2.0 * pairs * N           # C B^T, once a group
                 + H * 2.0 * pairs * P         # (C B^T x decay) xd
                 + 2 * matmul_flops(L, N, H * P)   # own state; y from before
                 + 2.0 * H * P * N)            # the carry
    return (seq // L) * per_block


def scan_work(layer, seq: int) -> Tuple[float, float]:
    """(operations, bytes) of one layer's scan in one train step: forward
    plus twice forward; x, B, C, dt read and y written forward, and x, B, C,
    dt, dy read and the four gradients written backward (a recomputed
    forward pass is time, not work)."""
    inner = layer["heads"] * layer["head_dim"]
    bc = 2 * layer["groups"] * layer["state"]
    inputs = inner + bc + layer["heads"]
    nbytes = F32 * seq * ((inputs + inner) + (inputs + inner) + inputs)
    return 3.0 * scan_forward_flops(layer, seq), nbytes


def expected_pairs(seq: int, layer: dict, shares: int) -> float:
    """(token, held expert) pairs a step expects on one share."""
    return seq * layer["top_k"] / float(shares)


def layer_forward_flops(spec, layer, seq: int) -> float:
    dim = spec["dim"]
    if layer["kind"] == "ssm":
        inner = layer["heads"] * layer["head_dim"]
        conv_dim = inner + 2 * layer["groups"] * layer["state"]
        return (matmul_flops(seq, dim, inner + conv_dim + layer["heads"])
                + 2.0 * seq * conv_dim * layer["conv"]
                + scan_forward_flops(layer, seq)
                + matmul_flops(seq, inner, dim))
    if layer["kind"] == "attention":
        dh = spec["head_dim"]
        q_dim = layer["heads"] * dh
        return (matmul_flops(seq, dim, q_dim)
                + matmul_flops(seq, dim, 2 * spec["kv_heads"] * dh)
                + matmul_flops(seq, q_dim, dim)
                + layer["heads"] * 2 * 2.0 * attended_pairs(seq) * dh)
    pairs = expected_pairs(seq, layer, spec["share"][1])
    return (matmul_flops(seq, dim, layer["total"])                 # router
            + 2 * matmul_flops(seq, dim, layer["shared_width"])
            + 2 * matmul_flops(pairs, dim, layer["width"]))


def train_flops(spec, seq: int) -> float:
    """One optimizer step over one sequence: every layer by its own
    description, the unembedding. Forward + 2x backward."""
    fwd = sum(layer_forward_flops(spec, layer, seq)
              for layer in spec["layers"])
    return 3.0 * (fwd + matmul_flops(seq, spec["dim"], spec["vocab"]))


def grouped_work(spec, chunk_rows: int, chunks: int,
                 layer_steps: int) -> Tuple[float, float]:
    """(operations, bytes) that ``chunks`` chunks of the ungated experts'
    grouped products over ``layer_steps`` runs of an expert layer need
    *whatever the routing was* (``workmodel_moe.grouped_work``'s reckoning
    for an expert of two matrices): the pairs a step has are decided on the
    device and are in no trace, so only what the chunks' existence implies
    is counted, and the share read from it is a floor of the true share.

    A chunk is the two forward products and each one's two backward
    products (the rows' and the weights' gradients), six in all; the two
    recomputed in the backward pass are time and not work. A weights'
    gradient writes every held expert's matrix, empty groups as zeros (8 x
    2,688 x 1,856 floats, 160 MB, in this cell); a product over rows reads
    at least one expert's matrix. A layer's chunks are full but its last,
    so at least ``chunks - layer_steps`` chunks hold ``chunk_rows`` rows,
    which each of the six products reads and writes."""
    e = next(layer for layer in spec["layers"] if layer["kind"] == "experts")
    count = e["total"] // spec["share"][1]
    dim, width = spec["dim"], e["width"]
    rows = max(0, chunks - layer_steps) * chunk_rows
    matrix = F32 * dim * width
    flops = 6 * matmul_flops(rows, dim, width)
    nbytes = (chunks * (2 * count + 4) * matrix
              + 6 * F32 * rows * (dim + width))
    return flops, nbytes
