"""flash_fwd_roofline: the forward attention kernel's share of its roofline
(workmodel.flash_fwd_work over the kernel's device time). The kernel is
found by what the trace shows (read off v5e traces and compiled programs by
hand): a custom-call named ``%jvp_jit_attn...`` on one chip,
``%jvp_vmap_jit_attn...`` under a batch and ``%attn_lse...`` on the ring,
whose outputs are the (.., H, S, D) result and its (.., H, S, 1) column of
row statistics. No such event: nothing."""
import kernel_roofline
import workmodel

_LAYOUT = r"(?:\{[^}]*\})?"
KERNEL = (r"^%(?:\w*_)?attn[\w.]* = \(f32\[[\d,]+\]" + _LAYOUT
          + r", f32\[[\d,]+,1\]" + _LAYOUT + r"\) custom-call\(")


def read(run):
    return kernel_roofline.attention_roofline(
        run, KERNEL, workmodel.flash_fwd_work, "flash_fwd_roofline")
