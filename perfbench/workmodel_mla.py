"""Operations and bytes that a model of latent-attention layers with sparse
experts and a multi-token-prediction module needs, from shapes alone
(DeepSeek-V3's key set: ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``num_nextn_predict_layers``).

``describe`` reads the configuration's published keys into plain data (what
the runner builds the program from and hands the plain reference). The
counting follows ``workmodel.py`` (imported, not copied): a product (m, k) x
(k, n) is 2*m*k*n operations, a train step is forward plus twice forward,
attention counts the causal pairs, recomputation, softmax, norms and
elementwise work are not counted. Query/key and value widths differ here, so
attention's two products are counted each at its own width. The routed
experts are counted at the pairs the routing *expects* on the experts held
here (tokens x top_k x held / total), as ``workmodel_moe`` counts them.
"""

from __future__ import annotations

from typing import Tuple

from workmodel import F32, attended_pairs, matmul_flops


def describe(cfg):
    """The model as plain data (what ``reference/lm_mla_plain.py`` takes):
    the first ``num_hidden_layers`` layers, the leading
    ``first_k_dense_replace`` of them with a dense gated MLP, the rest and
    the MTP module's layer with experts."""
    heads = int(cfg["num_attention_heads"])
    checks = {
        "hidden_act": "silu", "scoring_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True, "attention_bias": False,
        "tie_word_embeddings": False, "rope_scaling": None,
        "n_shared_experts": 1, "moe_layer_freq": 1,
        "num_key_value_heads": heads,
    }
    for key, want in checks.items():
        if cfg[key] != want:
            raise ValueError(f"{key} {cfg[key]!r}: only {want!r} is computed")
    if int(cfg["num_nextn_predict_layers"]) != 1:
        raise ValueError("one multi-token-prediction module only")
    total = int(cfg["published"]["n_routed_experts"])
    held = int(cfg["n_routed_experts"])
    parallel = cfg["expert_parallel"]
    if held * int(parallel["shares"]) != total:
        raise ValueError("experts held x shares is not the published count")
    experts = {"ffn": "experts", "experts": {
        "total": total, "top_k": int(cfg["num_experts_per_tok"]),
        "width": int(cfg["moe_intermediate_size"]),
        "shared_width": int(cfg["moe_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        "scale": float(cfg["routed_scaling_factor"])}}
    dense = {"ffn": "gated", "width": int(cfg["intermediate_size"])}
    first = int(cfg["first_k_dense_replace"])
    return {"vocab": int(cfg["vocab_size"]), "dim": int(cfg["hidden_size"]),
            "heads": heads, "q_rank": int(cfg["q_lora_rank"]),
            "kv_rank": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope_dim": int(cfg["qk_rope_head_dim"]),
            "v_dim": int(cfg["v_head_dim"]),
            "rope_base": float(cfg["rope_theta"]),
            "interleaved": bool(cfg["rope_interleave"]),
            "norm_eps": float(cfg["rms_norm_eps"]),
            "share": (int(parallel["index"]), int(parallel["shares"])),
            "layers": [dict(dense) if i < first else dict(experts)
                       for i in range(int(cfg["num_hidden_layers"]))],
            "mtp": {"depth": 1, "weight": float(cfg["mtp_loss_weight"]),
                    "layer": dict(experts)}}


def attention_flops(spec, seq: int) -> float:
    """Q.K^T at the query/key width and P.V at the value width over the
    causal pairs, every head, one layer forward."""
    qk = spec["nope"] + spec["rope_dim"]
    return spec["heads"] * 2.0 * attended_pairs(seq) * (qk + spec["v_dim"])


def latent_projection_flops(spec, seq: int) -> float:
    """A latent layer's five products: W_qa, W_qb, W_kva, W_kvb, W_o."""
    dim, h = spec["dim"], spec["heads"]
    qk = spec["nope"] + spec["rope_dim"]
    return (matmul_flops(seq, dim, spec["q_rank"])
            + matmul_flops(seq, spec["q_rank"], h * qk)
            + matmul_flops(seq, dim, spec["kv_rank"] + spec["rope_dim"])
            + matmul_flops(seq, spec["kv_rank"], h * (spec["nope"]
                                                      + spec["v_dim"]))
            + matmul_flops(seq, h * spec["v_dim"], dim))


def ffn_flops(spec, layer, seq: int) -> float:
    dim = spec["dim"]
    if layer["ffn"] == "gated":
        return 3 * matmul_flops(seq, dim, layer["width"])
    e = layer["experts"]
    pairs = seq * e["top_k"] / float(spec["share"][1])
    return (matmul_flops(seq, dim, e["total"])                       # router
            + 3 * matmul_flops(seq, dim, e["shared_width"])
            + 3 * matmul_flops(pairs, dim, e["width"]))


def layer_forward_flops(spec, layer, seq: int) -> float:
    return (latent_projection_flops(spec, seq) + attention_flops(spec, seq)
            + ffn_flops(spec, layer, seq))


def train_flops(spec, seq: int) -> float:
    """One optimizer step over one sequence: every layer (projections,
    attention at both widths, dense MLP or router, shared expert and the
    expected routed pairs), the MTP module (``eh_proj``, its layer, its
    head) and the main head. Forward + 2x backward."""
    dim = spec["dim"]
    fwd = sum(layer_forward_flops(spec, layer, seq)
              for layer in spec["layers"])
    fwd += (matmul_flops(seq, 2 * dim, dim)                          # eh_proj
            + layer_forward_flops(spec, spec["mtp"]["layer"], seq))
    fwd += 2 * matmul_flops(seq, dim, spec["vocab"])                 # heads
    return 3.0 * fwd


def flash_work(seq: int, heads: int, qk_dim: int, v_dim: int
               ) -> Tuple[Tuple[float, float], Tuple[float, float]]:
    """((operations, bytes) of one forward, of one backward) of the flash
    kernels over one causal layer whose queries and keys are ``qk_dim``
    wide and values ``v_dim``, every head with keys and values of its own
    (``workmodel.flash_fwd_work`` / ``flash_bwd_work``, the two widths
    apart). Forward: Q.K^T and P.V; reads Q, K, V, writes O and the row
    statistics. Backward: dV = P^T.dO and dP = dO.V^T at the value width,
    dQ = dS.K and dK = dS^T.Q at the query/key width, never the recomputed
    Q.K^T; reads Q, K, V, O, dO and the two row statistics, writes dQ, dK,
    dV."""
    pairs = attended_pairs(seq)
    rows = seq * heads
    fwd = (heads * 2.0 * pairs * (qk_dim + v_dim),
           F32 * rows * (2 * qk_dim + 2 * v_dim + 1))
    bwd = (heads * 4.0 * pairs * (qk_dim + v_dim),
           F32 * rows * (4 * qk_dim + 4 * v_dim + 2))
    return fwd, bwd
