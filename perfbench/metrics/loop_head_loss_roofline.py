"""loop_head_loss_roofline: the heads' and cross-entropies' share of their
roofline in a model that takes its loss over every pass's logits: the least
time the chip could take for the traced steps' head and loss
(workmodel_loop.head_loss_work: per pass the (S, dim) x (dim, vocab) product
forward and its two backward; the float32 logits written and read once each
way; the model's work whatever implements it: a block of rows recomputed in
the backward pass is time and not work) over the summed device time of the
ops that implement it.

The program's head is plain ``jax.numpy`` in blocks of rows under
``jax.lax.map`` (``fiber_tpu/models/transformer.py`` ``_head_losses``), so on
the v5e it is two loops (forward; recomputed forward and backward), each some
XLA fusions a block, and no kernel of its own. The trace keeps no
``op_name`` (PERF.md section 7), so an op is taken

* by its place: it ran inside a ``while`` whose own text carries the
  vocabulary's size (the head's matrix ``[dim, vocab]`` or its gradient is in
  the loop's tuple; the loop over the passes carries the layers' weights
  only). That takes the reductions over the vocabulary too, whose results
  ``[rows]`` carry no such size;
* or by its result: some result shape carries the vocabulary's size, the
  embedding's table shape ``[vocab, dim]`` left out (its gather's gradient
  and its AdamW: not the head's; the head's matrix is ``[dim, vocab]``). That
  takes the head's own AdamW and the sum of its gradient, which are not in
  ``head_loss_work``: the share reads lower for them, never higher.

``perfbench/tests/data/<cell>.head_ops.txt`` lists, from a sandbox compile,
every instruction under the scope ``lm.head_loss`` beside what this takes
(``tools/loop_head_ops.py``; ``tests/test_loop_readers.py`` holds the two
against each other). The reader prints the events matched, by place and by
result, and which bound holds. No matched event: nothing. An op is inside a
loop if its midpoint is (an op at a loop's edge can end a few nanoseconds
past the loop's own event), and the count a step is printed, not required to
be whole: the ops taken by their result run at a step's two ends (the head's
cast first, its AdamW last), where the traced window's edge may cut one off,
and every op's time is clipped to the window (my three traced runs, PR 33,
matched 9,240, 9,239 and, under the first rule of whole containment, 9,214
inside the loops for 9,216)."""
import re

import trace_reduce
import workmodel
import workmodel_loop

_LAYOUT = r"(?:\{[^}]*\})?"


def patterns(spec):
    """(a loop of the head's: a ``while`` whose text carries the
    vocabulary's size; an op with a result shape that carries it, the
    embedding's table shape left out)."""
    vocab, dim = spec["vocab"], spec["dim"]
    carries = rf"\w+\[(?:\d+,)*{vocab}(?:,\d+)*\]"
    table = rf"\w+\[{vocab},{dim}\]"
    other = r"\w+\[[\d,]*\]" + _LAYOUT
    mine = rf"(?!{table}){carries}{_LAYOUT}"
    result = rf"(?:{mine}|\((?:{other}, )*{mine}(?:, {other})*\))"
    return (re.compile(rf"^%[\w.\-]+ = \(.*{carries}.*\) while\("),
            re.compile(rf"^%[\w.\-]+ = {result} [a-z][\w\-]*\("))


def read(run):
    trace = run["trace"]
    if trace is None or "total_ut_steps" not in run["cfg"]:
        return None
    spec = workmodel_loop.describe(run["cfg"])
    loop, result = patterns(spec)
    lo, hi = trace_reduce.window_of(trace)
    chip = min(trace.device)
    events = [e for e in trace.device[chip] if e.end > lo and e.start < hi]
    loops = [e for e in events if loop.search(e.name)]
    by_place, by_result = [], []
    for e in events:
        if trace_reduce.CONTAINER.match(e.name):
            continue
        if any(w.start <= e.start + e.dur // 2 <= w.end for w in loops):
            by_place.append(e)
        elif result.search(e.name):
            by_result.append(e)
    matched = by_place + by_result
    steps = run["traffic"]["trace_calls"]
    if not matched:
        return None
    seconds = trace_reduce.measure(trace_reduce.merge(trace_reduce.clip(
        ((e.start, e.end) for e in matched), (lo, hi)))) / 1e9
    flops, nbytes = workmodel_loop.head_loss_work(spec, run["traffic"]["seq"])
    least, bound = workmodel.least_seconds(flops * steps, nbytes * steps,
                                           run["peak"])
    print(f"loop_head_loss_roofline: bound by {bound}; {seconds:.4f} s of "
          f"device time in {len(matched)} matched events of {steps} traced "
          f"steps ({len(matched) / steps:g} a step): {len(by_place)} inside "
          f"{len(loops)} loops of the head's, {len(by_result)} by their "
          f"result ({sum(e.dur for e in by_result) / 1e9:.4f} s)", flush=True)
    return 100.0 * least / seconds
