"""flash_bwd_roofline: the backward attention kernels' (dq and dkv together)
share of their roofline: the four products the backward needs, never the
recomputed scores (workmodel.flash_bwd_work), over the device time of the
custom-calls named ``%transpose_jvp_jit_attn...`` on one chip,
``%transpose_jvp_vmap_jit_attn...`` under a batch and ``%attn_lse...`` on
the ring, whose output is dQ, one (.., H, S, D) array, or dK and dV, two
(.., KVH, S, D) arrays. No such event: nothing."""
import kernel_roofline
import workmodel

_LAYOUT = r"(?:\{[^}]*\})?"
KERNEL = (r"^%(?:\w*_)?attn[\w.]* = (?:f32\[[\d,]+,(?!1\])\d+\]" + _LAYOUT
          + r"|\(f32\[[\d,]+,(\d+)\]" + _LAYOUT + r", f32\[[\d,]+,\1\]"
          + _LAYOUT + r"\)) custom-call\(")


def read(run):
    return kernel_roofline.attention_roofline(
        run, KERNEL, workmodel.flash_bwd_work, "flash_bwd_roofline")
