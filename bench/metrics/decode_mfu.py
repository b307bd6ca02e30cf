"""Model operations of the decode tokens of the traced window, over the
window, against the chip's int8 peak."""
import costs


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.window.steps:
        return None
    w = ctx.window
    ops = costs.decode_flops(ctx.cfg, w.decode_tokens, w.sum_ctx)
    return 100.0 * ops / ctx.trace.window_s / ctx.peaks["int8_ops"]
