"""Roofline share of the ``w8a8_matmul`` kernel inside the decode step
program (``jit_step``): the least time its calls could take at the chip's
int8 peak and HBM bandwidth, with operations and bytes from the
projections' shapes and the pool's slots, over their device time. That
time includes the ops that stage each layer's int8 weights out of the
stacked weights into fast memory, where the kernel then reads them: the
weights' HBM traffic happens there."""
import costs
import devtrace as TR


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    dev_s = (TR.kernel_seconds(ctx.trace, "w8a8_matmul", "jit_step")
             + TR.staged_weight_seconds(ctx.trace, "jit_step", "s8"))
    steps = TR.module_count(ctx.trace, "jit_step")
    if dev_s <= 0 or not steps:
        return None
    p = ctx.peaks
    per_step = sum(costs.bound_s(o, b, p["int8_ops"], p["hbm_bytes_s"])
                   for o, b in costs.w8a8_step_calls(ctx.cfg, ctx.n_slots))
    return 100.0 * steps * per_step / (dev_s / ctx.trace.n_devices)
