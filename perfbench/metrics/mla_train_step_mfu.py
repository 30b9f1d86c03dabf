"""mla_train_step_mfu: the whole step's share of the chip's bf16 peak for a
model of latent-attention layers with sparse experts and a
multi-token-prediction module: the operations one step needs
(workmodel_mla.train_flops: each latent layer's five projections, attention
over the causal pairs at the query/key width for Q.K^T and at the value width
for P.V, the dense MLP or router, shared expert and the routed pairs expected
on the experts held here, eh_proj, the MTP module's layer and both heads;
three times forward; a recomputed forward pass is time and not work) times
the steps of the window, over window seconds times chips times the peak. A
configuration without latent attention: nothing."""
import workmodel_mla


def read(run):
    if "kv_lora_rank" not in run["cfg"]:
        return None
    spec = workmodel_mla.describe(run["cfg"])
    steps = run["units"] / run["units_per_call"]
    return 100.0 * workmodel_mla.train_flops(spec, run["traffic"]["seq"]) \
        * steps / (run["window_s"] * run["chips"] * run["peak"]["flops_bf16"])
