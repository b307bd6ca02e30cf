"""Model operations of the prompts admitted in the traced window, over the
device time of the admission prefill program (``jit_prefill``), against
the chip's int8 peak."""
import costs
import devtrace as TR


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.window.prompts:
        return None
    dev_s = TR.module_seconds(ctx.trace, "jit_prefill")
    if dev_s <= 0:
        return None
    ops = sum(costs.prefill_flops(ctx.cfg, T, ctx.cushion_len)
              for T in ctx.window.prompts)
    return 100.0 * ops / dev_s / ctx.peaks["int8_ops"]
