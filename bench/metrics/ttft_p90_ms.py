"""90th percentile, over every request submitted inside the window, of the
time from its submission (its client's previous request retiring) to its
first token, on the host clock."""
import numpy as np


def read(ctx):
    t = ctx.window.ttft_ms
    return float(np.percentile(t, 90)) if t else None
