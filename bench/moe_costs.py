"""Operations and bytes of the grouped expert kernel (``w8a8_gmm``), and
the routing counters its readers take them from.

A sparse-expert layer multiplies each (token, expert) row by its expert's
up, gate and down weights in three ``w8a8_gmm`` calls. The program counts,
for each decode step (``jit_step``) and blocking admission prefill
(``jit_prefill``), the rows it routed and the experts those rows reached,
summed over the layers, and records them in ``repro.monitoring`` under the
id of the call's ``serve.step`` / ``serve.admit`` span. A reader finds the
window's calls through the spans ``progspans.aligned`` places in it, and
reports nothing where the program keeps no such counters.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

import devtrace as TR
import model
import progspans

KERNEL = "w8a8_gmm"
COUNTS = {"jit_step": (progspans.STEP, "moe.jit_step"),
          "jit_prefill": (progspans.ADMIT, "moe.jit_prefill")}


def gmm_layer(d: int, ff: int, rows: int, experts: int
              ) -> Tuple[float, float]:
    """Operations and bytes of the three expert calls of one layer (or of
    several, with rows and experts summed over them): 2 x rows x 3 d ff
    operations; every reached expert's int8 weights with an int32 column
    sum and a float32 scale per output column; each call's int8 rows in
    and float32 rows out."""
    ops = 2.0 * rows * 3 * d * ff
    weights = experts * (3.0 * d * ff + 8.0 * (2 * ff + d))
    acts = rows * ((d + 4.0 * ff) * 2 + ff + 4.0 * d)
    return ops, weights + acts


def call_counts(ctx, program: str) -> Optional[List[dict]]:
    """Routing counters of the window's calls of ``program``, or None where
    the spans do not align or the program records none."""
    a = progspans.aligned(ctx)
    if a is None:
        return None
    try:
        from repro import monitoring
    except ImportError:
        return None
    read = getattr(monitoring, "counts", None)
    if read is None:
        return None
    span, name = COUNTS[program]
    by_span = {c.span_id: c.values for c in read() if c.name == name}
    found = [by_span[r.span_id] for r in progspans.roots(a, span)
             if r.span_id in by_span]
    return found or None


def staged_expert_seconds(trace, program: str, experts: int) -> float:
    """Device seconds of ops inside ``program`` that slice a layer's int8
    expert stack, ``s8[E,K,N]``, out of the stack of every layer: where
    XLA stages the experts so, the kernel then reads them from there."""
    pat = re.compile(rf"s8\[{experts},\d+,\d+\]")
    return sum(o.dur_ns for o in trace.ops
               if o.module == program and "dynamic-slice" in TR.op_base(o.name)
               and pat.fullmatch(o.name.partition(" ")[2])) * 1e-9


def roofline(ctx, program: str) -> Optional[float]:
    """Roofline share of ``w8a8_gmm`` inside ``program`` over the traced
    window: the least time of each call's expert work at the chip's int8
    peak and HBM bandwidth, summed over the window's calls, over the
    kernel's device time and its staging slices."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    calls = call_counts(ctx, program)
    if calls is None:
        return None
    n = model.dims(ctx.cfg)
    experts = int(ctx.cfg["num_experts"])
    dev_s = (TR.kernel_seconds(ctx.trace, KERNEL, program)
             + staged_expert_seconds(ctx.trace, program, experts))
    if dev_s <= 0:
        return None
    p = ctx.peaks
    least = 0.0
    for c in calls:
        ops, nbytes = gmm_layer(n["d"], n["ff"], c["rows"], c["experts"])
        least += max(ops / p["int8_ops"], nbytes / p["hbm_bytes_s"])
    return 100.0 * least / (dev_s / ctx.trace.n_devices)
