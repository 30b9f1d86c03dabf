"""attn_full_roofline: the flash kernels' (forward, dq, dkv) share of their
roofline on the full-attention layers of a model whose layers differ
(attn_kind_roofline.py: workmodel.flash_fwd_work + flash_bwd_work at the full
layers' head count, over those kernels' device time)."""
import attn_kind_roofline


def read(run):
    return attn_kind_roofline.read(run, False, "attn_full_roofline")
