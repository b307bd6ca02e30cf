#!/usr/bin/env python3
"""Serving benchmark: runs one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``); the configuration names its weight
layout (``bench/layouts/<layout>.py``) and its plain reference
(``bench/references/<reference>.py``), and each metric has a reader of its
own (``bench/metrics/<metric>.py``), all found by name. With ``--trace 0`` the result
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window.

Earlier lines of standard output give the set-up phases, the compile cache
and the window's own counts (compiles inside it, occupancy, the length
multiset); the last lines of standard error give each number compared with
its limit; the last line of standard output is the result. The run exits
with 2 and prints no result where JAX finds no TPU or fewer chips than the
cell asks for, or where the program's sources are missing.

JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
where that is set, else in ``.bench_cache/jax`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
# the program's own switches that would take the kernels off the timed path
KERNEL_ENV = ("REPRO_DECODE_KERNEL", "REPRO_W8A8_KERNEL", "REPRO_W4A8_KERNEL")


def load_spec(path: str = os.path.join(ROOT, "BENCHMARK.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(spec: dict, cell: str, kind: str) -> list:
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Ctx:
    """What a metric reader may read."""
    window: object
    trace: object
    cfg: dict
    peaks: Optional[dict]
    n_slots: int
    cushion_len: int
    phases: dict


def setup_paths() -> None:
    for p in (SRC, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at its directory (left off
    where the caller has turned the cache off, as the CPU tests do)."""
    import jax
    if not jax.config.jax_enable_compilation_cache:
        return None
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return d


class CompileLog:
    """Compile seconds and persistent-cache hits over the whole run."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.hits, self.misses = 0.0, 0, 0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)


def verdict(gap: float, limit: Optional[float]) -> bool:
    """``correct``: the widest gap is a number and within the limit."""
    return bool(limit is not None and gap == gap and gap <= limit)


def multiset_digest(pairs) -> str:
    return hashlib.sha1(json.dumps(sorted(pairs)).encode()).hexdigest()[:16]


def execute(spec: dict, workload: str, seed: int, seconds: float,
            trace: bool, *, require_chip: bool = True, fault=None,
            config_dir: Optional[str] = None,
            traffic_dir: Optional[str] = None,
            t_start: Optional[float] = None, out=None, err=None):
    """Run one cell and return (exit code, result dict or None). Prints
    the earlier lines and the check lines to ``out`` / ``err``."""
    out = out or sys.stdout
    err = err or sys.stderr
    w = cell_spec(spec, workload)
    forced = [k for k in KERNEL_ENV if os.environ.get(k, "auto") != "auto"]
    if forced:
        print(f"bench: {forced} take the kernels off the timed path",
              file=err)
        return 2, None
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: the program's sources are missing ({SRC})", file=err)
        return 2, None
    setup_paths()
    cache_dir = enable_cache()
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < int(w["chips"])):
        print(f"bench: cell {workload} needs {w['chips']} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)", file=err)
        return 2, None
    log = CompileLog()

    import cell as CL
    import devtrace as TR
    import model as M
    import traffic as TF

    cfg = (M.load_config(w["config"]) if config_dir is None
           else M.load_config(w["config"], config_dir))
    mix = (TF.load_mix(w["traffic"]) if traffic_dir is None
           else TF.load_mix(w["traffic"], traffic_dir))
    peaks = None
    if devs[0].platform == "tpu":
        with open(os.path.join(BENCH, "peaks.json")) as f:
            table = json.load(f)["devices"]
        if devs[0].device_kind not in table:
            print(f"bench: no peaks for {devs[0].device_kind!r}", file=err)
            return 2, None
        peaks = table[devs[0].device_kind]

    o = CL.run(cfg, mix, seed, seconds, profile=trace, fault=fault,
               t_start=T_START if t_start is None else t_start)
    win = o.window
    limit = cfg["correct"]["widest_gap_limit"]
    gap = o.check["widest_gap"]
    ctx = Ctx(window=win, trace=o.trace, cfg=cfg, peaks=peaks,
              n_slots=o.n_slots,
              cushion_len=int(cfg["serving"]["cushion_len"]),
              phases=o.phases)

    print(json.dumps({"setup": o.phases, "compile": {
        "cache_dir": cache_dir, "seconds": log.seconds,
        "cache_hits": log.hits, "cache_misses": log.misses}}), file=out)
    print(json.dumps({"window": {
        "seconds": win.seconds, "steps": win.steps, "tokens": win.tokens,
        "admissions": win.admissions,
        "compiles_in_window": win.compiles,
        "occupancy_pct": 100.0 * win.live_slot_steps
        / max(1, win.steps * o.n_slots),
        "backpressure": win.backpressure,
        "round_multiset": multiset_digest(TF.round_pairs(mix)),
        "served_requests": len(o.served),
        "requests_compared": o.check["requests_compared"],
        "tokens_compared": o.check["tokens_compared"]}}), file=out)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(spec, workload, kind):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": o.memory_peak_bytes}
    result = {"correct": verdict(gap, limit),
              "attempted": o.attempted, "failed": o.failed,
              "metrics": metrics, "device": device}
    if o.trace is not None:
        device["busy_s"] = TR.busy_s(o.trace)
        device["window_s"] = o.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in TR.top_ops(o.trace, 10)],
            "idle_gaps": [list(x) for x in TR.idle_gaps(o.trace)[:10]]}
    result["checks"] = {"widest_gap": {"value": gap, "limit": limit}}
    print(f"check widest_gap {gap!r} limit {limit!r} "
          f"(tokens {o.check['tokens_compared']}, "
          f"requests {o.check['requests_compared']})", file=err)
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    code, result = execute(load_spec(), args.workload, args.seed,
                           args.seconds, bool(args.trace))
    sys.stderr.flush()
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
