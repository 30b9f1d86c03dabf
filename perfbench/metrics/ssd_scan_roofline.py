"""ssd_scan_roofline: the state-space layers' scan's share of its roofline:
the least time the chip could take for the scans of the traced steps
(workmodel_hybrid.scan_work: operations of the chunked form at the
configuration's block, forward plus twice forward; bytes x, B, C, dt read and
y written once forward, and those, dy and the four gradients once backward;
a recomputed forward pass is time and not work) over the summed device time
of the ops that implement it.

The program's scan is plain ``jax.numpy`` (``fiber_tpu/ops/ssm.py``), so on
the v5e it is some tens of XLA fusions and layout copies a layer, forward,
recomputed and backward, and no kernel of its own (a kernel named ``ssd_*``
would be taken by name). The trace keeps no ``op_name`` (PERF.md section 7),
so an op is recognised by the scan's own shapes in its result, read off the
compiled v5e program of ``nemotron3_nano_train_8k`` by hand and built here
from the configuration (S positions in nc blocks of L, G groups of R heads of
P features, state N):

* ``[nc, L, G, R(, P)]`` the block form of dt, the cumulative sums and x;
  ``[nc, G, R(, ...)]`` the decays, the (L, L) products and the blocks'
  states ``[nc, G, R, P, N]``; ``[nc, 1, G, R]`` a block's last sum;
* ``[nc, G, L, L]`` the ``C B^T`` of a group and ``[nc, L, G, N(, 1)]`` B
  and C in blocks; ``[G, R, P, N]`` and ``[G, R]`` the state and its decay
  inside the loop over the blocks;
* ``[S, H, P]`` x by heads, ``[S, G N]`` B or C, ``[S, H]`` dt;
* ``[S, H P]``, the mixer's inner width: under the scan's scope the backward
  pass has two such results a layer (x's gradient sums, fused with the
  ``D x`` term's), and the forward pass's ``D x`` term is fused into the
  gate's first op. The trace cannot tell these from the other ops of that
  shape (the gate's and its norm's, the out-projection's backward product;
  in this cell the attention layer's query projection and its output's
  gradient too, 32 heads of 128 being 4,096 as well), and XLA schedules
  other layers' ops between a mixer's, so there is no span to cut by:
  **every** op with such a result is taken. The share can therefore not
  read too high for an op of the scan left out; it reads too low by the
  time of the ops of other scopes it takes (26 instructions a step, of
  which 8 are the scan's: ``perfbench/tests/test_hybrid_readers.py`` holds
  both lists against the compile, ``perfbench/tests/data/``). The reader
  prints that time apart, so the share over the scan's own shapes alone,
  which reads too high, can be reckoned beside it.

Not taken: results that are vectors of H or H P elements (the gradients of
``A_log``, ``D``, ``dt_bias``: kilobytes) and the block's triangular mask,
made once.

The reader prints the events matched, for how many layer-steps, and which
bound holds. No matched event, or a count that is no whole multiple of the
traced steps (a window that cuts a step): nothing. The count a step is
pinned in the test against the compile (each instruction times how often a
step runs it), so a program or compiler that changes the scan's ops shows
there before a reading is believed."""
import re

import trace_reduce
import workmodel
import workmodel_hybrid

_LAYOUT = r"(?:\{[^}]*\})?"
#: ops that run as events and move data: not the containers, not the
#: scalar arithmetic of a loop's counter, not the runtime's
#: ``AllocateBuffer`` custom-calls (no time, and not one a step)
_RUNS = r"(?:fusion|copy|broadcast|convolution|dot|reduce|transpose)\("


def scan_shapes(layer, seq: int):
    """(the dimension lists, as text, that only the scan's ops have; the
    one it shares with its neighbours, ``[S, H P]``)."""
    L, H, P = layer["chunk"], layer["heads"], layer["head_dim"]
    G, N = layer["groups"], layer["state"]
    R, nc = H // G, seq // layer["chunk"]
    dims = lambda *d: ",".join(str(x) for x in d)           # noqa: E731
    own = [dims(nc, L, G, R) + r"(?:," + str(P) + r")?",
           dims(nc, G, R) + r"(?:,\d+)*",
           dims(nc, 1, G, R),
           dims(nc, G, L, L),
           dims(nc, L, G, N) + r"(?:,1)?",
           dims(G, R, P, N), dims(G, R),
           dims(seq, H, P), dims(seq, G * N), dims(seq, H)]
    return own, dims(seq, H * P)


def pattern(layer, seq: int, shared: bool = True) -> str:
    """A regular expression on an op's text as the trace shows it: a kernel
    named ``ssd_*``, or an op whose result (one shape, or any of a tuple's)
    is one of the scan's shapes; ``shared=False`` leaves ``[S, H P]`` out."""
    own, inner = scan_shapes(layer, seq)
    shape = (r"(?:f32|bf16|pred|s32)\[(?:"
             + "|".join(own + [inner] * shared) + r")\]" + _LAYOUT)
    other = r"\w+\[[\d,]*\]" + _LAYOUT
    result = (rf"(?:{shape}|\((?:{other}, )*{shape}(?:, {other})*\))")
    return rf"^%(?:ssd_[\w.]* = |[\w.\-]+ = {result} {_RUNS})"


def read(run):
    trace = run["trace"]
    if trace is None or "hybrid_override_pattern" not in run["cfg"]:
        return None
    spec = workmodel_hybrid.describe(run["cfg"])
    layers = workmodel_hybrid.ssm_layers(spec)
    if not layers:
        return None
    seq, steps = run["traffic"]["seq"], run["traffic"]["trace_calls"]
    kernel = pattern(layers[0], seq)
    seconds = trace_reduce.kernel_seconds(trace, kernel)
    if not seconds:
        return None
    rx = re.compile(kernel)
    own = re.compile(pattern(layers[0], seq, shared=False))
    lo, hi = trace_reduce.window_of(trace)
    matched = [e for e in trace.device[min(seconds)]
               if rx.search(e.name) and e.end > lo and e.start < hi]
    events, layer_steps = len(matched), len(layers) * steps
    if events % steps:
        print(f"ssd_scan_roofline: {events} matched events are no whole "
              f"multiple of the {steps} traced steps: nothing", flush=True)
        return None
    flops = nbytes = 0.0
    for layer in layers:
        f, b = workmodel_hybrid.scan_work(layer, seq)
        flops, nbytes = flops + f * steps, nbytes + b * steps
    least, bound = workmodel.least_seconds(flops, nbytes, run["peak"])
    total = sum(seconds.values())
    shared = [e for e in matched if not own.search(e.name)]
    print(f"ssd_scan_roofline: bound by {bound}; {total:.4f} s of device "
          f"time in {events} matched events for {layer_steps} layer-steps "
          f"({events / layer_steps:.1f} a layer-step), of it "
          f"{sum(e.dur for e in shared) / 1e9:.4f} s in {len(shared)} events "
          f"that share the mixers' [S, H P] with other scopes", flush=True)
    return 100.0 * least / total
