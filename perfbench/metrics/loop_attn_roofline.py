"""loop_attn_roofline: the flash kernels' share of their roofline in a model
whose stack of layers runs several times and whose step recomputes: the
least time the chip could take for the attention kernels *that ran* in the
traced steps over their summed device time.

Under ``jax.checkpoint`` a layer application runs the forward kernel twice
(once again in the backward pass), so a layer-step is no fixed three events
as in ``hybrid_attn_roofline.py``. The three kernels are told apart by their
text in the trace (custom-calls named ``...attn...``, as
``attn_kind_roofline.py`` says): the forward's outputs are ``(f32[H,S,D],
f32[H,S,1])``, dq's is one ``f32[H,S,D]``, dkv's are two of one shape
``f32[KVH,S,D]``. Work is reckoned **per traced event**:
``workmodel.flash_fwd_work`` for each forward event, ``flash_bwd_work`` for
each pair of a dq and a dkv event, at the configuration's head counts, full
causal; the summed least time over the summed kernel time. Another rung of
the recomputation ladder changes the events counted, never what the share
means: how near the kernels that ran are to their roofline.

The reader prints the three counts. No such event, or dq and dkv events that
do not pair: nothing."""
import re

import trace_reduce
import workmodel
import workmodel_loop

_LAYOUT = r"(?:\{[^}]*\})?"
_NAME = r"^%(?:\w*_)?attn[\w.]* = "
_HSD = r"f32\[\d+,\d+,\d+\]" + _LAYOUT
FWD = _NAME + rf"\({_HSD}, f32\[\d+,\d+,1\]{_LAYOUT}\) custom-call\("
DQ = _NAME + rf"{_HSD} custom-call\("
DKV = (_NAME + rf"\(f32\[\d+,\d+,(\d+)\]{_LAYOUT}, "
       rf"f32\[\d+,\d+,\1\]{_LAYOUT}\) custom-call\(")


def read(run):
    trace = run["trace"]
    if trace is None or "total_ut_steps" not in run["cfg"]:
        return None
    lo, hi = trace_reduce.window_of(trace)
    chip = min(trace.device)
    counts, spent = {}, {}
    for kind, pattern in (("fwd", FWD), ("dq", DQ), ("dkv", DKV)):
        rx = re.compile(pattern)
        counts[kind] = sum(1 for e in trace.device[chip] if rx.search(e.name)
                           and e.end > lo and e.start < hi)
        spent[kind] = sum(trace_reduce.kernel_seconds(trace, pattern).values())
    seconds = sum(spent.values())
    if not any(counts.values()):
        return None
    if counts["dq"] != counts["dkv"]:
        print(f"loop_attn_roofline: {counts['dq']} dq and {counts['dkv']} "
              "dkv events do not pair: nothing", flush=True)
        return None
    spec = workmodel_loop.describe(run["cfg"])
    shape = (run["traffic"]["seq"], spec["heads"], spec["kv_heads"],
             spec["head_dim"])
    fwd, bound = workmodel.least_seconds(
        *workmodel.flash_fwd_work(*shape), run["peak"])
    bwd, _ = workmodel.least_seconds(
        *workmodel.flash_bwd_work(*shape), run["peak"])
    least = run["chips"] * (counts["fwd"] * fwd + counts["dq"] * bwd)
    steps = run["traffic"]["trace_calls"]

    def share(events, each, kinds):
        # a side with no event has no share: the line says so
        took = sum(spent[k] for k in kinds)
        return (f"{100.0 * run['chips'] * events * each / took:.2f}%"
                if took else "none")

    print(f"loop_attn_roofline: forward bound by {bound}; {seconds:.4f} s of "
          f"kernel time in {counts['fwd']} forward, {counts['dq']} dq and "
          f"{counts['dkv']} dkv events of {steps} traced steps "
          f"({counts['fwd'] / steps:g} / {counts['dq'] / steps:g} / "
          f"{counts['dkv'] / steps:g} a step); apart, forward "
          f"{share(counts['fwd'], fwd, ('fwd',))} and backward "
          f"{share(counts['dq'], bwd, ('dq', 'dkv'))} of their own rooflines",
          flush=True)
    return 100.0 * least / seconds
