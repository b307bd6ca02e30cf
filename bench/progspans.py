"""The program's own host spans, placed on a traced window's clock.

The program keeps its recent spans in a ring (``repro.monitoring.spans()``:
records ``(name, start_ns, end_ns, span_id, parent_id, uid)`` on
``time.perf_counter_ns()``); the profiler trace has a clock of its own.
Every ``bench.step`` span of the window wraps exactly one call of
``ContinuousEngine.step``, whose root span is ``serve.step``, and the
window's steps are the last ``serve.step`` roots the ring holds: the cell
makes no step after the window. The offset between the clocks is the
median, over those pairs, of the difference between the two spans'
midpoints.

A pair's residual is the smaller of its two edges' distances from that
offset: a collection or a preemption between the harness's span and the
program's moves one edge of one pair, never both. Where the ring holds
fewer ``serve.step`` roots than the window has steps, or a pair's residual
exceeds ``MAX_RESIDUAL_NS``, there is no alignment and the readers report
nothing rather than a wrong number; so they do for a program that keeps no
spans.
"""
from __future__ import annotations

import dataclasses
import gc
import statistics
from typing import Dict, List, Optional, Tuple

import devtrace as TR

HARNESS_STEP = "bench.step"
STEP = "serve.step"
ADMIT = "serve.admit"
WAIT_SUFFIX = ".wait"
MAX_RESIDUAL_NS = 1e6


@dataclasses.dataclass
class Rec:
    """One program span on the trace clock."""
    name: str
    start_ns: float
    end_ns: float
    span_id: int
    parent_id: Optional[int]
    uid: Optional[int]


@dataclasses.dataclass
class Aligned:
    spans: List[Rec]            # every program span the ring holds
    offset_ns: float            # trace clock minus program clock
    residual_ns: float          # the largest pair residual
    window: Tuple[float, float]


def program_spans() -> Optional[list]:
    """The program's span records, or None where it keeps none."""
    try:
        from repro import monitoring
    except ImportError:
        return None
    read = getattr(monitoring, "spans", None)
    if read is None:
        return None
    # the program's gc hook appends a span to the ring on any thread; a
    # collection while the ring is copied mutates it mid-iteration
    enabled = gc.isenabled()
    gc.disable()
    try:
        return [tuple(s) for s in read()]
    finally:
        if enabled:
            gc.enable()


def align(trace, records) -> Optional[Aligned]:
    """Program spans on ``trace``'s clock, or None (see the module)."""
    if trace is None or not records:
        return None
    harness = sorted((s for s in trace.spans if s.name == HARNESS_STEP),
                     key=lambda s: s.start_ns)
    steps = [r for r in records if r[0] == STEP and r[4] is None]
    n = len(harness)
    if not n or len(steps) < n:
        return None
    pairs = list(zip(harness, steps[-n:]))
    offset = statistics.median(
        0.5 * (h.start_ns + h.end_ns) - 0.5 * (r[1] + r[2])
        for h, r in pairs)
    residual = max(min(abs(h.start_ns - r[1] - offset),
                       abs(h.end_ns - r[2] - offset)) for h, r in pairs)
    if residual > MAX_RESIDUAL_NS:
        return None
    return Aligned(spans=[Rec(r[0], r[1] + offset, r[2] + offset, r[3],
                              r[4], r[5]) for r in records],
                   offset_ns=offset, residual_ns=residual,
                   window=tuple(trace.window))


def aligned(ctx) -> Optional[Aligned]:
    if ctx.trace is None:
        return None
    return align(ctx.trace, program_spans())


def _waits(a: Aligned) -> Dict[int, List[Rec]]:
    out: Dict[int, List[Rec]] = {}
    for s in a.spans:
        if s.name.endswith(WAIT_SUFFIX) and s.parent_id is not None:
            out.setdefault(s.parent_id, []).append(s)
    return out


def roots(a: Aligned, name: str) -> List[Rec]:
    """The window's roots named ``name``: those that meet the window."""
    w0, w1 = a.window
    return [s for s in a.spans if s.name == name and s.parent_id is None
            and s.end_ns > w0 and s.start_ns < w1]


def host_ms(ctx, name: str) -> Optional[float]:
    """Median, over the window's ``name`` roots, of each root's duration
    less its ``.wait`` child: the host's own time in that phase."""
    a = aligned(ctx)
    if a is None:
        return None
    rs = roots(a, name)
    if not rs:
        return None
    waits = _waits(a)
    return statistics.median(
        (r.end_ns - r.start_ns - sum(w.end_ns - w.start_ns
                                     for w in waits.get(r.span_id, ())))
        * 1e-6 for r in rs)


def host_pieces(a: Aligned) -> List[Tuple[float, float]]:
    """Union of the window's ``serve.step`` and ``serve.admit`` roots less
    their ``.wait`` children, clipped to the window."""
    waits = _waits(a)
    w0, w1 = a.window
    pieces = []
    for r in roots(a, STEP) + roots(a, ADMIT):
        t = r.start_ns
        for w in sorted(waits.get(r.span_id, ()), key=lambda w: w.start_ns):
            pieces.append((t, w.start_ns))
            t = w.end_ns
        pieces.append((t, r.end_ns))
    out: List[List[float]] = []
    for s, e in sorted((max(s, w0), min(e, w1)) for s, e in pieces):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def idle_pieces(trace, device: int = 0) -> List[Tuple[float, float]]:
    """The window less the intervals in which an op ran on ``device``."""
    busy = TR.busy_intervals(trace, device)
    edges = [trace.window[0]] + [x for iv in busy for x in iv] \
        + [trace.window[1]]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def overlap_ns(xs, ys) -> float:
    """Total length of the intersection of two sorted disjoint lists of
    intervals."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        s, e = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if e > s:
            tot += e - s
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot
