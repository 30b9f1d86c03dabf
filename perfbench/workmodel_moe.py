"""Operations and bytes that a model of differing layers needs, from shapes
alone: window and full attention layers with their own head counts, gated
MLPs, sparse-expert layers of which a share of the experts is held here.

``describe`` reads the configuration's published keys into plain data (one
dict a layer; what the runner builds the program from and hands the plain
reference). The counting follows ``workmodel.py`` (imported, not copied): a
product (m, k) x (k, n) is 2*m*k*n operations, a train step is forward plus
twice forward, recomputation, softmax, norms and elementwise work are not
counted, attention counts the pairs the algorithm attends. In the whole
step's operations the routed experts are counted at the pairs the routing
*expects* on the experts held here (tokens x top_k x held / total; under
1% of the step); the grouped products' own roofline counts only what is
certain (``grouped_work``), and the measured load is a metric of its own
(``moe_load_max_over_mean``).
"""

from __future__ import annotations

from typing import Tuple

import workmodel
from workmodel import F32, attended_pairs, matmul_flops


def describe(cfg, use_window=True):
    """The model as plain data (what ``reference/lm_moe_plain.py`` takes)."""
    dh = int(cfg["head_dim"])
    total = int(cfg["published"]["num_experts"])
    held = int(cfg["num_experts"])
    parallel = cfg["expert_parallel"]
    if held * int(parallel["shares"]) != total:
        raise ValueError("experts held x shares is not the published count")
    layers = []
    for i in range(int(cfg["num_hidden_layers"])):
        kind = cfg["layer_types"][i]
        rp = cfg["rope_parameters"][kind]
        rotary = int(round(dh * float(rp["partial_rotary_factor"])))
        yarn = None
        if rp["rope_type"] == "yarn":
            yarn = {"factor": float(rp["factor"]),
                    "original_max_position": int(
                        rp["original_max_position_embeddings"]),
                    "beta_fast": float(rp["beta_fast"]),
                    "beta_slow": float(rp["beta_slow"]),
                    "attention_factor": float(rp["attention_factor"])}
        elif rp["rope_type"] != "default":
            raise ValueError(f"no rope {rp['rope_type']!r} here")
        layer = {"heads": int(cfg["num_attention_heads_per_layer"][i]),
                 "window": (int(cfg["sliding_window"])
                            if kind == "sliding_attention" and use_window
                            else None),
                 "rope": {"base": float(rp["rope_theta"]),
                          "rotary": None if rotary == dh else rotary,
                          "yarn": yarn}}
        if cfg["mlp_layer_types"][i] == "dense":
            layer.update(ffn="gated", width=int(cfg["intermediate_size"]))
        else:
            layer.update(ffn="experts", experts={
                "total": total, "top_k": int(cfg["num_experts_per_tok"]),
                "width": int(cfg["moe_intermediate_size"]),
                "shared_width": int(cfg["shared_expert_intermediate_size"]),
                "scale": float(cfg["moe_routed_scaling_factor"])})
        layers.append(layer)
    return {"vocab": int(cfg["vocab_size"]), "dim": int(cfg["hidden_size"]),
            "head_dim": dh, "kv_heads": int(cfg["num_key_value_heads"]),
            "share": (int(parallel["index"]), int(parallel["shares"])),
            "layers": layers}


def expected_pairs(seq: int, experts: dict, shares: int) -> float:
    """(token, held expert) pairs a step expects on one share."""
    return seq * experts["top_k"] / float(shares)


def layer_forward_flops(spec, layer, seq: int) -> float:
    dim, dh = spec["dim"], spec["head_dim"]
    q_dim = layer["heads"] * dh
    flops = (matmul_flops(seq, dim, q_dim)                           # wq
             + matmul_flops(seq, dim, 2 * spec["kv_heads"] * dh)     # wkv
             + matmul_flops(seq, q_dim, dim)                         # wo
             + layer["heads"] * 2 * 2.0
             * attended_pairs(seq, layer["window"]) * dh)
    if layer["ffn"] == "gated":
        return flops + 3 * matmul_flops(seq, dim, layer["width"])
    e = layer["experts"]
    pairs = expected_pairs(seq, e, spec["share"][1])
    return (flops + matmul_flops(seq, dim, e["total"])               # router
            + 3 * matmul_flops(seq, dim, e["shared_width"])
            + 3 * matmul_flops(pairs, dim, e["width"]))


def train_flops(spec, seq: int) -> float:
    """One optimizer step over one sequence: every layer's projections with
    its own head count, its attended pairs with its own window, gated MLPs
    as three products, router, shared expert, the routed pairs expected on
    the held experts, the unembedding. Forward + 2x backward."""
    fwd = sum(layer_forward_flops(spec, layer, seq)
              for layer in spec["layers"])
    return 3.0 * (fwd + matmul_flops(seq, spec["dim"], spec["vocab"]))


def attention_work(spec, seq: int, windowed: bool) -> Tuple[float, float, int]:
    """(operations, bytes, layers) of the forward and backward attention
    kernels of one step's window layers, or of its full layers."""
    flops = nbytes = 0.0
    layers = 0
    for layer in spec["layers"]:
        if (layer["window"] is not None) != windowed:
            continue
        layers += 1
        for work in (workmodel.flash_fwd_work, workmodel.flash_bwd_work):
            f, b = work(seq, layer["heads"], spec["kv_heads"],
                        spec["head_dim"], window=layer["window"])
            flops, nbytes = flops + f, nbytes + b
    return flops, nbytes, layers


def grouped_work(spec, chunk_rows: int, chunks: int,
                 layer_steps: int) -> Tuple[float, float]:
    """(operations, bytes) that ``chunks`` chunks of grouped expert
    products over ``layer_steps`` runs of an expert layer need *whatever
    the routing was*: how many (token, held expert) pairs a step has is
    decided by the router on the device and is in no trace, so only what
    the chunks' existence implies is counted, and the share of the
    roofline read from it is a floor of the true share, never above it.

    A chunk is the three forward products and each one's two backward
    products (the rows' and the weights' gradients; the forward recomputed
    in the backward pass is not counted). A weights' gradient writes every
    held expert's matrix, empty groups as zeros; a product over rows reads
    at least one expert's matrix. A layer's chunks are full but its last,
    so of ``chunks`` chunks at least ``chunks - layer_steps`` hold
    ``chunk_rows`` rows, which each of the nine products reads and
    writes."""
    e = next(layer["experts"] for layer in spec["layers"]
             if layer["ffn"] == "experts")
    count = e["total"] // spec["share"][1]
    dim, width = spec["dim"], e["width"]
    rows = max(0, chunks - layer_steps) * chunk_rows
    matrix = F32 * dim * width
    flops = 9 * matmul_flops(rows, dim, width)
    nbytes = (chunks * (3 * count + 6) * matrix
              + 9 * F32 * rows * (dim + width))
    return flops, nbytes
