"""Profiler trace of a window, reduced to plain lists.

``capture`` brackets a window with ``jax.profiler`` and ``load`` reads the
``.xplane.pb`` it writes with ``jax.profiler.ProfileData``: the device
planes' op events (each with the jitted program it ran in), the program
events, and the host spans the benchmark opens (``bench.*``). ``Trace``
keeps only those, so the per-layer readers work on a small object, and a
recorded one (``to_json``) can be checked by a test without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(slots=True)
class Op:
    name: str
    module: str
    start_ns: float
    dur_ns: float
    device: int = 0

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]          # bench.window span, trace clock
    ops: List[Op]                        # device ops inside the window
    modules: List[Op]                    # device program executions
    spans: List[Op]                      # host bench.* spans
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def to_json(self) -> dict:
        row = lambda o: [o.name, o.module, o.start_ns, o.dur_ns, o.device]
        return {"window": list(self.window), "n_devices": self.n_devices,
                "ops": [row(o) for o in self.ops],
                "modules": [row(o) for o in self.modules],
                "spans": [row(o) for o in self.spans]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ops = lambda rows: [Op(*r) for r in rows]
        return cls(window=tuple(d["window"]), ops=ops(d["ops"]),
                   modules=ops(d["modules"]), spans=ops(d["spans"]),
                   n_devices=int(d["n_devices"]))


def module_name(raw: str) -> str:
    """``jit_step(42)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", raw or "").strip()


def op_name(raw: str) -> str:
    """An op event's HLO text, ``%w8a8_matmul.37 = f32[32,3072]{...}
    custom-call(...)``, shortened to its instruction and result type:
    ``w8a8_matmul.37 f32[32,3072]``."""
    head, _, rest = raw.partition(" = ")
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + m.group(1) if m else "")


def op_base(name: str) -> str:
    """``w8a8_matmul.37 f32[32,3072]`` -> ``w8a8_matmul``."""
    return re.sub(r"\.\d+$", "", name.split(" ")[0])


# ops that hold other ops of the same program (a scanned layer stack runs
# inside a ``while``); their time is their body's, so they are not ranked
CONTAINERS = ("while", "conditional", "call")


def _device_index(plane_name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError(f"no profiler trace under {logdir}")
    data = ProfileData.from_file(paths[-1])
    spans, ops, modules = [], [], []
    devices = set()
    for plane in data.planes:
        dev = _device_index(plane.name)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Op(ev.name, "", ev.start_ns,
                                        ev.duration_ns))
            continue
        if dev is None:
            continue
        devices.add(dev)
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    modules.append(Op(module_name(ev.name), "", ev.start_ns,
                                      ev.duration_ns, dev))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    ops.append(Op(op_name(ev.name), "", ev.start_ns,
                                  ev.duration_ns, dev))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise RuntimeError("trace holds no bench.window span")
    w0, w1 = win[-1].start_ns, win[-1].end_ns
    inside = lambda o: o.end_ns > w0 and o.start_ns < w1
    ops = [o for o in ops if inside(o)]
    modules = [m for m in modules if inside(m)]
    _attribute_modules(ops, modules)
    return Trace(window=(w0, w1), ops=ops, modules=modules,
                 spans=[s for s in spans if inside(s)],
                 n_devices=max(1, len(devices)))


def _attribute_modules(ops: List[Op], modules: List[Op]) -> None:
    """Give each op the program whose execution holds it on its device."""
    by_dev: Dict[int, List[Op]] = {}
    for m in modules:
        by_dev.setdefault(m.device, []).append(m)
    starts = {}
    for d, ms in by_dev.items():
        ms.sort(key=lambda m: m.start_ns)
        starts[d] = [m.start_ns for m in ms]
    for o in ops:
        ms = by_dev.get(o.device)
        if not ms:
            continue
        i = bisect.bisect_right(starts[o.device], o.start_ns) - 1
        if i >= 0 and o.start_ns < ms[i].end_ns:
            o.module = ms[i].name


def _clip(o: Op, w: Tuple[float, float]) -> Tuple[float, float]:
    return max(o.start_ns, w[0]), min(o.end_ns, w[1])


def busy_intervals(trace: Trace, device: int) -> List[Tuple[float, float]]:
    """Union of the intervals in which an op ran on ``device``."""
    iv = sorted(_clip(o, trace.window) for o in trace.ops
                if o.device == device)
    out: List[List[float]] = []
    for a, b in iv:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran, averaged over the devices."""
    devs = sorted({o.device for o in trace.ops}) or [0]
    tot = sum(b - a for d in devs for a, b in busy_intervals(trace, d))
    return tot * 1e-9 / len(devs)


def idle_gaps(trace: Trace, device: int = 0) -> List[Tuple[str, float]]:
    """Idle gaps of ``device`` inside the window, longest first, each named
    by the innermost host span that holds the gap's midpoint."""
    busy = busy_intervals(trace, device)
    edges = [trace.window[0]] + [x for iv in busy for x in iv] \
        + [trace.window[1]]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = 0.5 * (a + b)
            holders = [s for s in trace.spans
                       if s.start_ns <= mid < s.end_ns
                       and s.name != WINDOW_SPAN]
            name = (min(holders, key=lambda s: s.dur_ns).name if holders
                    else "host")
            gaps.append((name, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return gaps


def staged_weight_seconds(trace: Trace, module: str, dtype: str) -> float:
    """Device seconds of the ops inside ``module`` that slice a 2-D
    ``dtype`` operand out of a stacked array: in a scanned layer stack XLA
    stages each layer's weights into fast memory this way, and the kernel
    that multiplies them then reads them from there."""
    return sum(o.dur_ns for o in trace.ops
               if o.module == module and "dynamic-slice" in op_base(o.name)
               and re.fullmatch(dtype + r"\[\d+,\d+\]",
                                o.name.partition(" ")[2])) * 1e-9


def kernel_seconds(trace: Trace, kernel: str,
                   module: Optional[str] = None) -> float:
    """Device seconds of the calls of ``kernel`` (optionally only those
    inside program ``module``), summed over devices."""
    return sum(o.dur_ns for o in trace.ops
               if op_base(o.name) == kernel
               and (module is None or o.module == module)) * 1e-9


def module_seconds(trace: Trace, module: str) -> float:
    return sum(m.dur_ns for m in trace.modules if m.name == module) * 1e-9


def module_count(trace: Trace, module: str, device: int = 0) -> int:
    return sum(1 for m in trace.modules
               if m.name == module and m.device == device)


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Device ops that took most time, by program and op name."""
    tot: Dict[str, float] = {}
    for o in trace.ops:
        if op_base(o.name) in CONTAINERS:
            continue
        base = op_base(o.name) + o.name[len(o.name.split(" ")[0]):]
        key = f"{o.module}/{base}" if o.module else base
        tot[key] = tot.get(key, 0.0) + o.dur_ns * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
