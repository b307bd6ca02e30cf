"""Seconds from the start of the run to the opening of the window: loading,
drawing the weights, calibration, compilation, warm-up and the fill."""


def read(ctx):
    return ctx.phases["setup_s"]
