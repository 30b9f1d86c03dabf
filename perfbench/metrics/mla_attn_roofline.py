"""mla_attn_roofline: the flash kernels' share of their roofline on latent
attention, whose queries and keys are wider than its values (192 and 128 in
the JoyAI cell): the least time the chip could take for the attention kernels
*that ran* in the traced steps over their summed device time.

The three kernels are told apart by their text in the trace (custom-calls
named ``...attn...``, as ``attn_kind_roofline.py`` says) at the
configuration's own widths: the forward's outputs are ``(f32[H,S,Dv],
f32[H,S,1])``, dq's is one ``f32[H,S,Dqk]``, dkv's are ``(f32[H,S,Dqk],
f32[H,S,Dv])``. Work is reckoned per traced event, as
``loop_attn_roofline.py`` reckons it: ``workmodel_mla.flash_work``'s forward
for each forward event, its backward for each pair of a dq and a dkv event.
The line printed gives the events a step (6 latent applications a step in the
JoyAI cell: 5 layers and the MTP module's, each kept once forward by the
checkpoint) beside the program's ``latent_layers_traced`` counter, which
counts traced applications (the step's and the routing probe's).

No such event, or dq and dkv events that do not pair: nothing."""
import re
import sys

import trace_reduce
import workmodel
import workmodel_mla

_LAYOUT = r"(?:\{[^}]*\})?"
_NAME = r"^%(?:\w*_)?attn[\w.]* = "


def patterns(heads, seq, qk, v):
    """(forward, dq, dkv): each kernel's text at these widths."""
    def shape(width):
        return rf"f32\[{heads},{seq},{width}\]{_LAYOUT}"
    return (_NAME + rf"\({shape(v)}, f32\[{heads},{seq},1\]{_LAYOUT}\) "
            r"custom-call\(",
            _NAME + rf"{shape(qk)} custom-call\(",
            _NAME + rf"\({shape(qk)}, {shape(v)}\) custom-call\(")


def traced_applications(run):
    """The program's ``latent_layers_traced`` counter, summed over its
    series (a hand-built record brings it under ``program_counters``)."""
    if "program_counters" in run:
        series = run["program_counters"].get("latent_layers_traced", {})
    else:
        telemetry = sys.modules.get("fiber_tpu.telemetry")
        if telemetry is None or not hasattr(telemetry, "REGISTRY"):
            return None
        series = telemetry.REGISTRY.snapshot().get(
            "latent_layers_traced", {}).get("series", {})
    return sum(series.values()) if series else None


def read(run):
    trace = run["trace"]
    if trace is None or "kv_lora_rank" not in run["cfg"]:
        return None
    spec = workmodel_mla.describe(run["cfg"])
    seq, steps = run["traffic"]["seq"], run["traffic"]["trace_calls"]
    qk = spec["nope"] + spec["rope_dim"]
    lo, hi = trace_reduce.window_of(trace)
    chip = min(trace.device)
    counts, spent = {}, {}
    for kind, pattern in zip(("fwd", "dq", "dkv"),
                             patterns(spec["heads"], seq, qk, spec["v_dim"])):
        rx = re.compile(pattern)
        counts[kind] = sum(1 for e in trace.device[chip] if rx.search(e.name)
                           and e.end > lo and e.start < hi)
        spent[kind] = sum(trace_reduce.kernel_seconds(trace, pattern).values())
    if not any(counts.values()):
        return None
    if counts["dq"] != counts["dkv"]:
        print(f"mla_attn_roofline: {counts['dq']} dq and {counts['dkv']} "
              "dkv events do not pair: nothing", flush=True)
        return None
    fwd_work, bwd_work = workmodel_mla.flash_work(seq, spec["heads"], qk,
                                                  spec["v_dim"])
    fwd, bound = workmodel.least_seconds(*fwd_work, run["peak"])
    bwd, _ = workmodel.least_seconds(*bwd_work, run["peak"])
    least = run["chips"] * (counts["fwd"] * fwd + counts["dq"] * bwd)
    seconds = sum(spent.values())
    print(f"mla_attn_roofline: bound by {bound}; {seconds:.4f} s of kernel "
          f"time in {counts['fwd']} forward, {counts['dq']} dq and "
          f"{counts['dkv']} dkv events of {steps} traced steps "
          f"({counts['fwd'] / steps:g} / {counts['dq'] / steps:g} / "
          f"{counts['dkv'] / steps:g} a step; latent_layers_traced "
          f"{traced_applications(run)})", flush=True)
    return 100.0 * least / seconds
