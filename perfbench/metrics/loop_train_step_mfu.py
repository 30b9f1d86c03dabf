"""loop_train_step_mfu: the whole step's share of the chip's bf16 peak for a
model whose stack of layers runs several times over the same weights: the
operations one step needs (workmodel_loop.train_flops: ``passes`` x each
layer's four projections, attention over the causal pairs and the gated
MLP's three products, ``passes`` heads and gates; three times forward; a
recomputed forward pass is time and not work) times the steps of the
window, over window seconds times chips times the peak. ``passes`` is what
the program's ``lm.train_step`` spans of the window say it ran: where they
say nothing, or another number than the configuration's ``total_ut_steps``,
or the configuration is no such model: nothing."""
import program_spans
import workmodel_loop


def read(run):
    if "total_ut_steps" not in run["cfg"]:
        return None
    calls = program_spans.window_calls(run, program_spans.spans_of(run))
    spec = workmodel_loop.describe(run["cfg"])
    ran = {call.get("passes") for call in calls or [{}]}
    if ran != {spec["passes"]}:
        print(f"loop_train_step_mfu: the program's spans say passes "
              f"{sorted(map(str, ran))}, the configuration "
              f"{spec['passes']}: nothing", flush=True)
        return None
    steps = run["units"] / run["units_per_call"]
    return 100.0 * workmodel_loop.train_flops(spec, run["traffic"]["seq"]) \
        * steps / (run["window_s"] * run["chips"] * run["peak"]["flops_bf16"])
