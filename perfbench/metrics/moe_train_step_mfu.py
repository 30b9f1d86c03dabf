"""moe_train_step_mfu: the whole step's share of the chip's bf16 peak for a
model whose layers differ: the operations one step needs
(workmodel_moe.train_flops: each layer's own head count and window, gated
MLPs as three products, router, shared expert, the routed pairs expected on
the experts held here, the head) times the steps of the window, over window
seconds times chips times the peak."""
import workmodel_moe


def read(run):
    spec = workmodel_moe.describe(run["cfg"],
                                  bool(run["traffic"]["use_window"]))
    steps = run["units"] / run["units_per_call"]
    return 100.0 * workmodel_moe.train_flops(spec, run["traffic"]["seq"]) \
        * steps / (run["window_s"] * run["chips"] * run["peak"]["flops_bf16"])
