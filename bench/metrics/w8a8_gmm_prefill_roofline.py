"""Roofline share of the grouped expert kernel ``w8a8_gmm`` inside the
admission prefill program (``jit_prefill``): the least time of the window's
admissions' expert work at the chip's int8 peak and HBM bandwidth, from the
rows each prompt routed and the experts they reached (the program's
counters under each ``serve.admit`` span), over the kernel's device time."""
import moe_costs


def read(ctx):
    return moe_costs.roofline(ctx, "jit_prefill")
