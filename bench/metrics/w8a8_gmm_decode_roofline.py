"""Roofline share of the grouped expert kernel ``w8a8_gmm`` inside the
decode step program (``jit_step``): the least time of the window's steps'
expert work at the chip's int8 peak and HBM bandwidth, from the rows each
step routed and the experts they reached (the program's counters under
each ``serve.step`` span), over the kernel's device time."""
import moe_costs


def read(ctx):
    return moe_costs.roofline(ctx, "jit_step")
