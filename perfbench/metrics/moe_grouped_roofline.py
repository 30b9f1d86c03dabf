"""moe_grouped_roofline: the grouped expert products' share of their
roofline, as a floor: the least time for what the traced chunks of products
need whatever the routing was (workmodel_moe.grouped_work: per chunk the
weights' gradients write every held expert's matrix and each other product
reads at least one; the chunks past a layer's first are full) over the
summed device time of the ops that implement them. How many (token, held
expert) pairs a step has is decided on the device and is in no trace: with
the pairs the routing expects (2,048 a layer) the share read 32%, 56% and
128% on three seeds whose routers put more and fewer pairs here (PERF.md).

``jax.lax.ragged_dot`` compiles for the v5e to a grouped Mosaic kernel of
XLA's own: custom-calls named ``%ragged-dot-none[.N]`` (forward, the rows'
gradient and the weights' gradient alike; read off the compiled v5e program
of this cell: 48 of them, 12 a chunk and one chunk an expert layer as
compiled; the loop over chunks runs each 12 once a chunk), beside a scalar
helper ``%ragged-dot-metadata[.N]``, which is not counted. No such event:
nothing."""
import re

import trace_reduce
import workmodel
import workmodel_moe

KERNEL = r"^%ragged-dot-(?!metadata)[\w.-]* = .*custom-call\("
PER_CHUNK = 12      # 3 forward, 3 recomputed, 3 rows' and 3 weights' gradients


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    seconds = trace_reduce.kernel_seconds(trace, KERNEL)
    if not seconds:
        return None
    rx = re.compile(KERNEL)
    lo, hi = trace_reduce.window_of(trace)
    events = sum(1 for e in trace.device[min(seconds)]
                 if rx.search(e.name) and e.end > lo and e.start < hi)
    spec = workmodel_moe.describe(run["cfg"],
                                  bool(run["traffic"]["use_window"]))
    layers = sum(layer["ffn"] == "experts" for layer in spec["layers"])
    chunks = events // PER_CHUNK
    flops, nbytes = workmodel_moe.grouped_work(
        spec, int(run["cfg"]["dispatch_chunk_rows"]), chunks,
        layers * run["traffic"]["trace_calls"])
    least, bound = workmodel.least_seconds(flops, nbytes, run["peak"])
    total = sum(seconds.values())
    print(f"moe_grouped_roofline: a floor, bound by {bound}; {total:.4f} s "
          f"of kernel time in {events} products, {chunks} chunks",
          flush=True)
    return 100.0 * least / total
