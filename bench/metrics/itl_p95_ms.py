"""95th percentile of every gap between consecutive tokens of a request,
both inside the window, on the host clock."""
import numpy as np


def read(ctx):
    gaps = ctx.window.itl_ms
    return float(np.percentile(gaps, 95)) if gaps else None
