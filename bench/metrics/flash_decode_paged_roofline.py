"""Roofline share of the ``flash_decode_paged`` kernel inside the decode
step program: the least time at the chip's bf16 peak and HBM bandwidth to
read every live slot's context (cushion included) in every layer, summed
over the window's steps, over the kernel's device time."""
import costs
import devtrace as TR


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.window.steps:
        return None
    dev_s = TR.kernel_seconds(ctx.trace, "flash_decode_paged", "jit_step")
    if dev_s <= 0:
        return None
    p = ctx.peaks
    least = sum(costs.bound_s(*costs.flash_decode_step(ctx.cfg, B, c),
                              p["bf16_flops"], p["hbm_bytes_s"])
                for B, c in ctx.window.step_contexts)
    return 100.0 * least / (dev_s / ctx.trace.n_devices)
