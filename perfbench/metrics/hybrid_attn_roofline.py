"""hybrid_attn_roofline: the flash kernels' (forward, dq, dkv) share of their
roofline on the attention layers of a model of one-part layers:
workmodel.flash_fwd_work + flash_bwd_work at the layers' head counts, full
causal, over the three kernels' summed device time. The kernels are
recognised by their text in the trace as ``flash_fwd_roofline.py`` and
``attn_kind_roofline.py`` say (custom-calls named ``...attn...``; the
forward's outputs ``(f32[H,S,D], f32[H,S,1])``, dq's ``f32[H,S,D]``, dkv's two
``f32[KVH,S,D]``): every attention layer here has one head count and no
window, so all three are taken whatever their heads. No such event, or a
count that is no whole multiple of three a layer-step: nothing."""
import re

import trace_reduce
import workmodel
import workmodel_hybrid

_LAYOUT = r"(?:\{[^}]*\})?"
_SHAPE = r"f32\[\d+,\d+,\d+\]" + _LAYOUT
KERNEL = (r"^%(?:\w*_)?attn[\w.]* = (?:\(" + _SHAPE + r", "
          + _SHAPE + r"\)|" + _SHAPE + r") custom-call\(")


def read(run):
    trace = run["trace"]
    if trace is None or "hybrid_override_pattern" not in run["cfg"]:
        return None
    seconds = trace_reduce.kernel_seconds(trace, KERNEL)
    if not seconds:
        return None
    spec = workmodel_hybrid.describe(run["cfg"])
    seq, steps = run["traffic"]["seq"], run["traffic"]["trace_calls"]
    flops = nbytes = 0.0
    layers = 0
    for layer in spec["layers"]:
        if layer["kind"] != "attention":
            continue
        layers += 1
        for work in (workmodel.flash_fwd_work, workmodel.flash_bwd_work):
            f, b = work(seq, layer["heads"], spec["kv_heads"],
                        spec["head_dim"])
            flops, nbytes = flops + f, nbytes + b
    rx = re.compile(KERNEL)
    lo, hi = trace_reduce.window_of(trace)
    events = sum(1 for e in trace.device[min(seconds)]
                 if rx.search(e.name) and e.end > lo and e.start < hi)
    total = sum(seconds.values())
    if not layers or events % (3 * layers * steps):
        print(f"hybrid_attn_roofline: {events} kernel events are no whole "
              f"multiple of 3 x {layers * steps} layer-steps: nothing",
              flush=True)
        return None
    least, bound = workmodel.least_seconds(flops * steps, nbytes * steps,
                                           run["peak"])
    print(f"hybrid_attn_roofline: bound by {bound}; {total:.4f} s of kernel "
          f"time in {events} events for {layers * steps} layer-steps",
          flush=True)
    return 100.0 * least / total
