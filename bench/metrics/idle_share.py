"""Share of the traced window in which no op ran on the device."""
import devtrace as TR


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - TR.busy_s(ctx.trace) / ctx.trace.window_s)
