"""A kernel's share of its roofline, from the device trace: the least time
the chips could take for the work the traced steps need (operations over
the bf16 peak or bytes over the HBM peak, whichever is larger) over the
summed device time of the kernel's events, all chips together."""
import trace_reduce
import workmodel


def attention_roofline(run, kernel: str, work, label: str):
    """``kernel``: a regular expression on the op's text as the trace shows
    it; ``work(seq, heads, kv_heads, head_dim, window=, batch=, chips=)``
    gives (operations, bytes) of one layer's call. No event: None."""
    trace = run["trace"]
    if trace is None:
        return None
    seconds = trace_reduce.kernel_seconds(trace, kernel)
    if not seconds:
        return None
    cfg, traffic = run["cfg"], run["traffic"]
    heads = cfg["num_attention_heads"]
    flops, nbytes = work(
        traffic["seq"], heads, cfg["num_key_value_heads"],
        cfg["hidden_size"] // heads,
        window=cfg["sliding_window"] if traffic["use_window"] else None,
        batch=max(traffic["batch"], 1), chips=run["chips"])
    calls = cfg["num_hidden_layers"] * traffic["trace_calls"]
    least, bound = workmodel.least_seconds(
        flops * calls, nbytes * calls, run["peak"])
    total = sum(seconds.values())
    print(f"{label}: bound by {bound}; {total:.4f} s of kernel time on "
          f"{len(seconds)} chip(s) for {calls} layer-steps", flush=True)
    return 100.0 * least / total
