"""moe_relu2_grouped_roofline: the ungated experts' grouped products' share
of their roofline, as a floor (``moe_grouped_roofline``'s reading for an
expert of two matrices): the least time for what the traced chunks of
products need whatever the routing was (workmodel_hybrid.grouped_work: per
chunk six products, two forward and four backward, never the two recomputed;
the weights' gradients write every held expert's matrix and each other
product reads at least one; the chunks past a layer's first are full) over
the summed device time of the ops that implement them. How many (token, held
expert) pairs a step has is decided on the device and is in no trace.

``jax.lax.ragged_dot`` compiles for the v5e to a grouped Mosaic kernel of
XLA's own: custom-calls named ``%ragged-dot-none[.N]`` (read off the
compiled v5e program of ``nemotron3_nano_train_8k``: 32 of them, 8 a chunk
and one chunk an expert layer as compiled; the loop over chunks runs each 8
once a chunk), beside a scalar helper ``%ragged-dot-metadata[.N]``, which
is not counted. Another runner kind's cell, or no such event: nothing."""
import re

import trace_reduce
import workmodel
import workmodel_hybrid

KERNEL = r"^%ragged-dot-(?!metadata)[\w.-]* = .*custom-call\("
PER_CHUNK = 8       # 2 forward, 2 recomputed, 2 rows' and 2 weights' gradients


def read(run):
    trace = run["trace"]
    if trace is None or "hybrid_override_pattern" not in run["cfg"]:
        return None
    seconds = trace_reduce.kernel_seconds(trace, KERNEL)
    if not seconds:
        return None
    rx = re.compile(KERNEL)
    lo, hi = trace_reduce.window_of(trace)
    events = sum(1 for e in trace.device[min(seconds)]
                 if rx.search(e.name) and e.end > lo and e.start < hi)
    spec = workmodel_hybrid.describe(run["cfg"])
    layers = sum(layer["kind"] == "experts" for layer in spec["layers"])
    chunks = events // PER_CHUNK
    flops, nbytes = workmodel_hybrid.grouped_work(
        spec, int(run["cfg"]["dispatch_chunk_rows"]), chunks,
        layers * run["traffic"]["trace_calls"])
    least, bound = workmodel.least_seconds(flops, nbytes, run["peak"])
    total = sum(seconds.values())
    print(f"moe_relu2_grouped_roofline: a floor, bound by {bound}; "
          f"{total:.4f} s of kernel time in {events} products, {chunks} "
          f"chunks", flush=True)
    return 100.0 * least / total
