"""attn_window_roofline: the flash kernels' (forward, dq, dkv) share of their
roofline on the sliding-window layers of a model whose layers differ
(attn_kind_roofline.py: workmodel.flash_fwd_work + flash_bwd_work at the
window layers' head count and window, over those kernels' device time)."""
import attn_kind_roofline


def read(run):
    return attn_kind_roofline.read(run, True, "attn_window_roofline")
