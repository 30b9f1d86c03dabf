"""hybrid_train_step_mfu: the whole step's share of the chip's bf16 peak for a
model of one-part layers: the operations one step needs
(workmodel_hybrid.train_flops: each state-space mixer's two projections, its
convolution and its chunked scan at the configuration's block, attention over
the causal pairs, router, shared expert, the routed pairs expected on the
experts held here, the head; three times forward) times the steps of the
window, over window seconds times chips times the peak. A configuration that
is no such model: nothing."""
import workmodel_hybrid


def read(run):
    if "hybrid_override_pattern" not in run["cfg"]:
        return None
    spec = workmodel_hybrid.describe(run["cfg"])
    steps = run["units"] / run["units_per_call"]
    return 100.0 * workmodel_hybrid.train_flops(spec, run["traffic"]["seq"]) \
        * steps / (run["window_s"] * run["chips"] * run["peak"]["flops_bf16"])
