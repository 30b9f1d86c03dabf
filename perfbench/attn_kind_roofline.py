"""The attention kernels' share of their roofline in a model whose layers
differ, one kind of layer at a time: the least time the chip could take for
the forward and backward attention of the traced steps' window layers (or
full layers) over the summed device time of those layers' three kernels.

The kinds are told apart by the query-head count in the kernel's text as the
trace shows it (read off the compiled v5e program and a v5e trace by hand;
names as ``flash_fwd_roofline.py`` says): the forward's outputs are
``(f32[H,S,D], f32[H,S,1])``, dq's is ``f32[H,S,D]``; dkv's are two
``f32[KVH,S,D]``, the same for both kinds, so there the first operand's shape
after ``custom-call(`` (q's, ``f32[H,S,D]``, an operand or the first of the
``operand_layout_constraints``) says which. Kinds with the same head count
cannot be told apart: nothing is reported then."""
import trace_reduce
import workmodel
import workmodel_moe

_LAYOUT = r"(?:\{[^}]*\})?"
_NAME = r"^%(?:\w*_)?attn[\w.]* = "


def kernels(heads: int) -> str:
    """A pattern for the three kernels of the layers with ``heads`` query
    heads."""
    h = rf"f32\[{heads},\d+,\d+\]" + _LAYOUT
    fwd = rf"\({h}, f32\[{heads},\d+,1\]{_LAYOUT}\) custom-call\("
    dq = rf"{h} custom-call\("
    dkv = (rf"\(f32\[\d+,\d+,(\d+)\]{_LAYOUT}, f32\[\d+,\d+,\1\]{_LAYOUT}\) "
           rf"custom-call\([^[]*{h}")
    return _NAME + rf"(?:{fwd}|{dq}|{dkv})"


def read(run, windowed: bool, label: str):
    trace = run["trace"]
    if trace is None:
        return None
    spec = workmodel_moe.describe(run["cfg"], bool(run["traffic"]["use_window"]))
    mine = {layer["heads"] for layer in spec["layers"]
            if (layer["window"] is not None) == windowed}
    others = {layer["heads"] for layer in spec["layers"]
              if (layer["window"] is not None) != windowed}
    if len(mine) != 1 or mine & others:
        return None
    seconds = trace_reduce.kernel_seconds(trace, kernels(mine.pop()))
    if not seconds:
        return None
    flops, nbytes, layers = workmodel_moe.attention_work(
        spec, run["traffic"]["seq"], windowed)
    steps = run["traffic"]["trace_calls"]
    least, bound = workmodel.least_seconds(flops * steps, nbytes * steps,
                                           run["peak"])
    total = sum(seconds.values())
    print(f"{label}: bound by {bound}; {total:.4f} s of kernel time for "
          f"{layers * steps} layer-steps", flush=True)
    return 100.0 * least / total
