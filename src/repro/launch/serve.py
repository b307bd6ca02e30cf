"""Serving launcher: batched generation under a quantization mode with an
optional CushionCache artifact.

    python -m repro.launch.serve --arch paper_tiny --quant pt_static \
        --cushion artifacts/cushion --tokens 64

The default (static) mode runs one Engine batch: device-resident decode
(one jitted lax.scan — no per-token host sync); --kv-dtype int8 serves
from a quantized KV cache with the cushion prefix kept intact in fp.

--cushion DIR loads the latest tuned-cushion artifact written by
``launch/tune.py`` (a ``checkpoint.store`` versioned directory). The
content fingerprint is recomputed over the restored bytes and checked
against the manifest — a corrupt or mismatched artifact fails loudly at
load, never as silently drifted activations. If the artifact carries
pt_static scales (tune --with-scales) and --quant pt_static, those scales
serve directly (no load-time calibration) wrapped with their cushion
fingerprint so ``plan_quantization`` can reject a stale pairing; without
stored scales, pt_static calibrates at load *under the loaded cushion*.

--quant pt_static serves the calibrated true-int8 W8A8 deployment path:
site scales are calibrated at engine load over --calib-batches synthetic
batches (under the cushion when one is attached), and --prequant makes the
weights int8-resident ({w_int, w_scale, colsum} dicts; decode streams
1 byte/weight through the Pallas w8a8_matmul path on TPU):

    python -m repro.launch.serve --arch paper_tiny --quant pt_static \
        --prequant --bench-json results/BENCH_w8a8.json

--mode continuous replays a Poisson-arrival request trace through the
continuous-batching scheduler (``serving.scheduler.ContinuousEngine``):
requests arrive at --rate req/s, are admitted into a pool of --slots cache
slots as they free up, and decode in lock-step with per-slot positions.
Prints per-request TTFT/TPOT plus aggregate tokens/s, latency percentiles
and slot occupancy. --bench-json PATH appends a trajectory point for perf
regression tracking in either mode. The trace is fully seedable:
--trace-seed (default --seed) fixes arrivals, prompts and budgets, so two
runs with the same seeds replay the identical workload.

--replicas N serves the trace through the fault-tolerant replica router
(``serving.router.ReplicaRouter``): N data-parallel ContinuousEngine
replicas behind one bounded admission queue with least-loaded dispatch,
health tracking, retry/failover and graceful drain. --chaos injects
deterministic faults (``kind@site:step`` specs, e.g.
``crash@replica1.step:12`` — see distributed/fault_injection.py) to
exercise failover on a live trace:

    python -m repro.launch.serve --arch paper_tiny --smoke \
        --mode continuous --replicas 3 --chaos crash@replica1.step:6

--paged swaps the continuous pool's dense per-slot rows for the paged KV
layout (``serving/paging.py``): a flat page store plus per-slot page
tables, the fp cushion held once (batch-free) instead of per slot, pages
allocated on demand as decode appends and returned at retirement.
--page-size sets the page granularity (must divide max_seq), --pages caps
the physical pool (defaults to worst-case, i.e. no admission ever
backpressures on pages), and --prefix-cache turns on content-addressed
prompt-stem page sharing (fp pools only): repeated prompt stems map the
donor's pages read-only and only prefill the tail. The final stats block
gains the page-pool gauges (pages total/free/shared, cushion page refs,
prefix hit/miss, pool bytes):

    python -m repro.launch.serve --arch paper_tiny --smoke \
        --mode continuous --paged --page-size 32 --prefix-cache

Graceful shutdown (continuous + router modes): SIGTERM and ctrl-C drain
instead of dying mid-step — admission stops, live slots decode to
completion, and the final ServeStats/RouterStats are printed for the
completed prefix of the trace.

A continuous run ends with one ``[serve] spans:`` line: for each host span
the engines recorded (``serve.step`` and its ``.pages`` / ``.wait`` /
``.retire`` phases, ``serve.admit`` and its ``.alloc`` / ``.wait`` /
``.book`` phases, ``python.gc``), its count and p50/p99 self-time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _sniff_int_arg(name: str) -> int:
    try:
        if name in sys.argv:
            return int(sys.argv[sys.argv.index(name) + 1])
        return next(int(a.split("=", 1)[1]) for a in sys.argv
                    if a.startswith(name + "="))
    except (IndexError, ValueError, StopIteration):
        return 1


def _force_host_devices_for_tp() -> None:
    """--tp N (x --replicas R) on CPU needs N*R XLA host devices, and the
    flag only takes effect before jax initializes — sniff argv at import
    time (same pattern as launch/dryrun.py)."""
    from repro.flags import force_host_device_count
    n = _sniff_int_arg("--tp") * _sniff_int_arg("--replicas")
    if n > 1:
        force_host_device_count(n)


_force_host_devices_for_tp()

import jax
import jax.numpy as jnp
import numpy as np

from repro import monitoring as MON
from repro.checkpoint.store import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import QuantConfig, get_config, reduced
from repro.data.pipeline import Pipeline, SyntheticCorpus
from repro.models.registry import build
from repro.serving.engine import Engine
from repro.serving.scheduler import ContinuousEngine, Request


def poisson_trace(api, rng_seed: int, n_requests: int, rate: float,
                  prompt_lens, budgets) -> list:
    """Poisson-arrival request trace: exponential inter-arrival gaps at
    ``rate`` req/s, prompts cycling through ``prompt_lens`` (total
    positions) and budgets through ``budgets``. Fully seedable: everything
    — arrival gaps, prompt contents, budget assignment — derives from
    ``rng_seed``, so the same seed replays the identical workload (the
    chaos parity checks depend on this)."""
    rs = np.random.RandomState(rng_seed)
    t = 0.0
    reqs = []
    for i in range(n_requests):
        t += float(rs.exponential(1.0 / rate)) if rate > 0 else 0.0
        reqs.append(Request(
            uid=i,
            batch=api.make_batch(jax.random.PRNGKey(rng_seed + 7 * i + 1), 1,
                                 int(prompt_lens[i % len(prompt_lens)])),
            max_new_tokens=int(budgets[i % len(budgets)]),
            arrival_s=t))
    return reqs


def load_cushion_artifact(path: str, api):
    """Load the latest cushion artifact from a ``launch/tune.py``
    --out-dir. Returns ``(cushion, tagged_scales | None, extra)``.

    Trust-but-verify: the content fingerprint is recomputed over the
    restored (device) arrays and compared to the manifest's — bit-rot,
    a truncated copy, or a hand-edited artifact dies here with a clear
    message instead of serving subtly wrong prefix KV. The arch name is
    checked too (a smoke artifact only serves a smoke config: `reduced`
    renames the config, so the mismatch is caught, not silently shaped
    in). Stored scales come back as ``calibration.CalibratedScales``
    carrying the fingerprint of the cushion they were calibrated under,
    which `plan_quantization` enforces against the cushion actually
    served."""
    from repro.core.calibration import CalibratedScales, scales_from_plain
    from repro.core.cushioncache import cushion_fingerprint

    store = CheckpointManager(path)
    version = store.latest_step()
    if version is None:
        raise SystemExit(f"[serve] no cushion artifact under {path}")
    tree, manifest = store.restore_tree(version)
    extra = manifest.get("extra", {})
    if extra.get("kind") != "cushion":
        raise SystemExit(f"[serve] {path} v{version} is not a cushion "
                         f"artifact (kind={extra.get('kind')!r}); expected "
                         f"a launch/tune.py --out-dir")
    if extra.get("arch") and extra["arch"] != api.cfg.name:
        raise SystemExit(f"[serve] cushion artifact was tuned for arch "
                         f"{extra['arch']!r} but serving {api.cfg.name!r}")
    cushion = jax.tree_util.tree_map(jnp.asarray, tree["cushion"])
    got = cushion_fingerprint(cushion)
    want = extra.get("fingerprint")
    if want and got != want:
        raise SystemExit(f"[serve] cushion artifact fingerprint mismatch: "
                         f"manifest says {want[:12]} but restored bytes "
                         f"hash to {got[:12]} — artifact corrupt")
    scales = None
    if "scales" in tree:
        scales = CalibratedScales(scales_from_plain(tree["scales"]),
                                  extra.get("scales_cushion_fp", got))
    print(f"[serve] cushion artifact v{version} from {path}: "
          f"prefix_ids={extra.get('prefix_ids')} "
          f"fingerprint={got[:12]} scales="
          f"{'stored' if scales is not None else 'none'}")
    return cushion, scales, extra


def install_sigterm_drain() -> None:
    """Map SIGTERM onto KeyboardInterrupt so orchestrator shutdowns take
    the same graceful-drain path as ctrl-C: stop admitting, decode live
    slots to completion, print final stats. No-op off the main thread
    (pytest workers)."""
    import signal

    def _handler(signum, frame):
        raise KeyboardInterrupt("SIGTERM")

    try:
        signal.signal(signal.SIGTERM, _handler)
    except ValueError:      # not the main thread
        pass


def run_continuous(api, params, qcfg, args, bench_path=None, mesh=None,
                   calib_batches=None, cushion=None, scales=None):
    install_sigterm_drain()
    reqs = poisson_trace(api, args.trace_seed, args.n_requests, args.rate,
                         prompt_lens=(args.prompt_len, args.prompt_len + 8),
                         budgets=(args.tokens, max(1, args.tokens // 2)))
    eng = ContinuousEngine(api, params, qcfg, n_slots=args.slots,
                           max_seq=args.prompt_len + 8 + args.tokens + 32,
                           cushion=cushion, scales=scales, mesh=mesh,
                           kv_dtype=None if args.kv_dtype == "fp"
                           else args.kv_dtype,
                           calib_batches=calib_batches,
                           prequant=args.prequant,
                           weight_bits=args.weight_bits,
                           paged=args.paged, page_size=args.page_size,
                           n_pages=args.pages,
                           prefix_cache=args.prefix_cache,
                           chunk_tokens=args.chunk_tokens)
    if eng.chunk_auto:
        print(f"[serve] chunked prefill: adaptive budget "
              f"(decode-pressure-scaled, max {eng.chunk_tokens} "
              f"tokens/chunk)")
    elif eng.chunk_tokens:
        print(f"[serve] chunked prefill: {eng.chunk_tokens} tokens/chunk "
              f"(budget bucketed from --chunk-tokens {args.chunk_tokens})")
    if cushion is not None:
        print(f"[serve] serving cushion {eng.cushion_fp[:12]} "
              f"(prefix_len={eng.prefix_len})")
    print(f"[serve] resident weights: "
          f"fp={eng.stats.weight_bytes_fp / 2 ** 20:.1f} MiB "
          f"int8={eng.stats.weight_bytes_int8 / 2 ** 20:.1f} MiB "
          f"int4={eng.stats.weight_bytes_int4 / 2 ** 20:.1f} MiB")
    if args.paged:
        st = eng.stats
        print(f"[serve] paged pool: {st.pages_total} pages x "
              f"{args.page_size} positions, "
              f"{st.pool_bytes / 2 ** 20:.2f} MiB resident "
              f"(cushion refs {st.cushion_page_refs})")
    if bench_path:
        eng.run(reqs)           # warm/compile pass; measure steady state
    outs = eng.run(reqs)
    for o in outs:
        print(f"[serve]   req {o.uid}: slot {o.slot} n={len(o.tokens)} "
              f"TTFT={o.ttft_ms:.1f}ms TPOT={o.tpot_ms:.2f}ms "
              f"latency={o.latency_s * 1e3:.0f}ms")
    if eng.stats.interrupted:
        print(f"[serve] DRAINED: interrupted after {len(outs)} of "
              f"{len(reqs)} requests; live slots completed, queued "
              f"remainder dropped")
    print(f"[serve] final stats: {eng.stats.as_dict()}")
    if args.paged:
        st = eng.stats
        print(f"[serve] page pool: total={st.pages_total} "
              f"free={st.pages_free} shared={st.pages_shared} "
              f"cushion_refs={st.cushion_page_refs} "
              f"prefix_hits={st.prefix_hits} "
              f"prefix_misses={st.prefix_misses} "
              f"positions_exhausted={st.positions_exhausted} "
              f"pool_bytes={st.pool_bytes}")
    if not outs:
        return outs
    total = sum(len(o.tokens) for o in outs)
    span = max(o.finished_s for o in outs) - min(r.arrival_s for r in reqs)
    lat = np.asarray([o.latency_s for o in outs])
    tps = total / max(span, 1e-9)
    occ = eng.stats.occupancy()
    print(f"[serve] continuous: {len(outs)} reqs, {total} tokens, "
          f"{tps:.1f} tok/s, p50={np.percentile(lat, 50) * 1e3:.0f}ms "
          f"p99={np.percentile(lat, 99) * 1e3:.0f}ms occupancy={occ:.2f}")
    if bench_path:
        point = {"mode": "continuous", "arch": args.arch,
                 "quant": args.quant, "prequant": args.prequant,
                 "weight_bits": args.weight_bits,
                 "paged": args.paged, "page_size": args.page_size,
                 "prefix_cache": args.prefix_cache,
                 "kv_dtype": args.kv_dtype, "slots": args.slots,
                 "rate": args.rate, "n_requests": args.n_requests,
                 "tokens_per_s": tps,
                 "p50_latency_s": float(np.percentile(lat, 50)),
                 "p99_latency_s": float(np.percentile(lat, 99)),
                 "occupancy": occ, **eng.stats.as_dict()}
        _append_point(bench_path, point)
    return outs


def print_span_summary() -> None:
    """One line of the host spans the engines recorded (monitoring.span):
    per span name, its count and p50/p99 self-time, the span's time less
    the spans inside it. ``serve.step.wait`` is the step's token read;
    ``serve.step`` alone is the host's own share of a step."""
    parts = [f"{name} n={v['count']} p50={v['p50_ms']:.3f}ms "
             f"p99={v['p99_ms']:.3f}ms"
             for name, v in MON.span_summary().items()]
    print("[serve] spans: " + "; ".join(parts))


def run_router(api, params, qcfg, args, bench_path=None, calib_batches=None,
               cushion=None, scales=None):
    """--replicas N: the trace goes through the fault-tolerant replica
    router instead of a single engine. --chaos arms deterministic fault
    injection; rejections, retries, failovers and per-replica health land
    in the printed RouterStats."""
    from repro.distributed.fault_injection import FaultInjector
    from repro.serving.router import ReplicaRouter, RouterConfig

    install_sigterm_drain()
    meshes = None
    if args.tp > 1 or len(jax.devices()) >= args.replicas:
        # every replica on its own device group (one device at tp=1)
        from repro.launch.mesh import make_replica_meshes
        meshes = make_replica_meshes(args.replicas, args.tp)
        print(f"[serve] {args.replicas} replicas x tp={args.tp} on disjoint "
              f"device groups")
    else:
        print(f"[serve] {args.replicas} replicas share "
              f"{jax.devices()[0]} ({len(jax.devices())} device(s))")
    injector = None
    if args.chaos:
        injector = FaultInjector.parse(args.chaos, seed=args.chaos_seed)
        print(f"[serve] chaos armed: {args.chaos} (seed {args.chaos_seed})")
    reqs = poisson_trace(api, args.trace_seed, args.n_requests, args.rate,
                         prompt_lens=(args.prompt_len, args.prompt_len + 8),
                         budgets=(args.tokens, max(1, args.tokens // 2)))
    router = ReplicaRouter(
        api, params, qcfg, n_replicas=args.replicas,
        cfg=RouterConfig(max_queue=args.max_queue), meshes=meshes,
        n_slots=args.slots, max_seq=args.prompt_len + 8 + args.tokens + 32,
        cushion=cushion, scales=scales,
        kv_dtype=None if args.kv_dtype == "fp" else args.kv_dtype,
        calib_batches=calib_batches, prequant=args.prequant,
        weight_bits=args.weight_bits,
        paged=args.paged, page_size=args.page_size, n_pages=args.pages,
        prefix_cache=args.prefix_cache, chunk_tokens=args.chunk_tokens)
    res = router.run(reqs, injector=injector)
    for o in res.outputs:
        retry = f" attempts={o.attempts}" if o.attempts > 1 else ""
        print(f"[serve]   req {o.uid}: replica {o.replica} slot {o.slot} "
              f"n={len(o.tokens)} TTFT={o.ttft_ms:.1f}ms "
              f"TPOT={o.tpot_ms:.2f}ms "
              f"latency={o.latency_s * 1e3:.0f}ms{retry}")
    for r in res.rejected:
        print(f"[serve]   req {r.uid}: REJECTED ({r.reason})")
    st = res.stats
    print(f"[serve] router: {st.completed}/{st.submitted} completed, "
          f"{st.rejected} rejected, {st.retries} retries, "
          f"{st.failovers} failovers, {st.replica_deaths} deaths, "
          f"queue peak {st.queue_depth_peak}, states "
          f"{[p['state'] for p in st.per_replica]}")
    if st.drained:
        print("[serve] DRAINED: graceful shutdown completed the live slots")
    if res.outputs:
        lat = np.asarray([o.latency_s for o in res.outputs])
        print(f"[serve] p50={np.percentile(lat, 50) * 1e3:.0f}ms "
              f"p99={np.percentile(lat, 99) * 1e3:.0f}ms")
    print(f"[serve] final stats: {st.as_dict()}")
    if bench_path:
        _append_point(bench_path, {
            "mode": "router", "arch": args.arch, "quant": args.quant,
            "replicas": args.replicas, "chaos": args.chaos or "",
            "slots": args.slots, "rate": args.rate,
            "n_requests": args.n_requests, **st.as_dict()})
    return res


def _append_point(path: str, point: dict) -> None:
    hist = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            hist = prev if isinstance(prev, list) else [prev]
        except (json.JSONDecodeError, OSError) as e:
            print(f"[serve] WARNING: could not read {path} "
                  f"({e}); starting a fresh trajectory")
    hist.append(point)
    with open(path, "w") as f:
        json.dump(hist, f, indent=1)
    print(f"[serve] bench point -> {path}")


def _chunk_tokens_arg(v: str):
    """--chunk-tokens value: an int budget or 'auto' (adaptive)."""
    if v == "auto":
        return v
    return int(v)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_tiny")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="none")
    ap.add_argument("--mode", default="static",
                    choices=["static", "continuous"],
                    help="static: one Engine batch; continuous: Poisson "
                         "trace through the slot-pool scheduler")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous mode: cache-slot pool size")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="continuous mode: Poisson arrival rate (req/s)")
    ap.add_argument("--n-requests", type=int, default=8,
                    help="continuous mode: trace length")
    ap.add_argument("--replicas", type=int, default=1,
                    help="continuous mode: serve through the replica "
                         "router over N data-parallel engine replicas "
                         "(health checks, retries, backpressure, drain)")
    ap.add_argument("--chaos", default=None,
                    help="router mode: comma-separated fault specs "
                         "kind@site:step[:stall_s], e.g. "
                         "crash@replica1.step:12 (kinds: crash, stall, "
                         "heartbeat, interrupt)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for randomized fault schedules")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="router mode: bounded admission queue size "
                         "(overflow -> explicit queue_full rejection)")
    ap.add_argument("--trace-seed", type=int, default=None,
                    help="seed for the Poisson trace (arrivals, prompts, "
                         "budgets); defaults to --seed. Same seed = "
                         "identical workload replay")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from latest checkpoint")
    ap.add_argument("--cushion", default=None,
                    help="serve the latest tuned-cushion artifact from "
                         "this launch/tune.py --out-dir (fingerprint "
                         "verified at load; stored pt_static scales serve "
                         "directly when --quant pt_static)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width: shard params (serve rules) "
                         "and the KV pool heads axis over a (data=1, tp=N) "
                         "mesh; works on CPU via forced host devices (set "
                         "automatically at import) and on real accelerator "
                         "meshes alike")
    ap.add_argument("--paged", action="store_true",
                    help="continuous mode: paged KV pool — flat page store "
                         "+ per-slot page tables, the fp cushion held once "
                         "batch-free instead of copied per slot, pages "
                         "allocated on decode appends and returned at "
                         "retirement (serving/paging.py)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="paged mode: positions per KV page (must divide "
                         "max_seq, multiple of 8)")
    ap.add_argument("--pages", type=int, default=None,
                    help="paged mode: physical page count; default sizes "
                         "the pool for the worst case so admission never "
                         "backpressures on pages — pass less to realize "
                         "the memory win on overlapping workloads")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged fp pools: content-addressed prompt-stem "
                         "page sharing — repeated stems map the donor's "
                         "pages read-only and only prefill the tail")
    ap.add_argument("--kv-dtype", default="fp", choices=["fp", "int8"],
                    help="KV-cache storage precision (int8 halves decode "
                         "HBM traffic; cushion prefix stays fp; the "
                         "continuous pool calibrates per-slot scales at "
                         "each admission prefill)")
    ap.add_argument("--prequant", action="store_true",
                    help="serve int8-resident weights: calibrate pt_static "
                         "site scales at load, prequantize the param tree "
                         "(1 byte/weight streamed into the W8A8 matmul "
                         "path); requires --quant pt_static")
    ap.add_argument("--weight-bits", type=int, default=8, choices=[8, 4],
                    help="resident weight precision with --prequant: 8 = "
                         "int8 w_int (W8A8), 4 = nibble-packed w_packed "
                         "(W4A8, 0.5 byte/weight through the unpack-in-"
                         "VMEM kernel); activations stay int8 either way")
    ap.add_argument("--calib-batches", type=int, default=2,
                    help="pt_static: number of calibration batches drawn "
                         "from the synthetic pipeline at engine load")
    ap.add_argument("--chunk-tokens", type=_chunk_tokens_arg, default=None,
                    help="chunked admission prefill: per-step token budget "
                         "(bucketed to a power of two); prompts longer "
                         "than one budget prefill one chunk per decode "
                         "step instead of blocking the whole pool — short "
                         "prompts admit between a long prompt's chunks. "
                         "'auto' adapts the budget to decode pressure "
                         "(big chunks when idle, small when slots are "
                         "near-full)")
    ap.add_argument("--bench-json", default=None,
                    help="append a trajectory point to this file")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.chunk_tokens is not None and args.mode != "continuous":
        ap.error("--chunk-tokens requires --mode continuous (chunked "
                 "admission lives in the slot scheduler)")
    if args.prequant and args.quant != "pt_static":
        ap.error("--prequant requires --quant pt_static (int8-resident "
                 "weights serve the per-tensor static deployment path)")
    if args.weight_bits == 4 and not args.prequant:
        ap.error("--weight-bits 4 requires --prequant (the int4-packed "
                 "format only exists as resident serving weights)")
    if (args.replicas > 1 or args.chaos) and args.mode != "continuous":
        ap.error("--replicas/--chaos require --mode continuous (the "
                 "router fronts ContinuousEngine replicas)")
    if args.paged and args.mode != "continuous":
        ap.error("--paged requires --mode continuous (the paged pool "
                 "lives in the slot scheduler)")
    if args.prefix_cache and not args.paged:
        ap.error("--prefix-cache requires --paged (stems are shared at "
                 "page granularity)")
    if args.prefix_cache and args.kv_dtype != "fp":
        ap.error("--prefix-cache shares fp pages only (int8 pages carry "
                 "the donor's per-slot scales)")
    if args.trace_seed is None:
        args.trace_seed = args.seed

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg, dtype="float32")
    api = build(cfg)
    rng = jax.random.PRNGKey(args.seed)
    params = api.init_params(rng)
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir)
        step = ckpt.latest_step()
        if step is not None:
            from repro.optim.adamw import AdamW, constant_lr
            opt_state = AdamW(lr=constant_lr(1e-3)).init(params)
            like = {"params": params, "opt": opt_state._asdict()}
            params = ckpt.restore(step, like=like)["params"]
            print(f"[serve] restored step {step}")

    # pt_static serves the true-int8 deployment path (the one --prequant
    # makes int8-resident); dynamic modes keep the fake-quant fidelity path
    qcfg = QuantConfig(mode=args.quant,
                       true_int8=args.quant == "pt_static")
    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(args.tp)
        print(f"[serve] tp={args.tp} mesh over "
              f"{[str(d) for d in mesh.devices.flat]}")

    cushion, art_scales = None, None
    if args.cushion:
        cushion, art_scales, _ = load_cushion_artifact(args.cushion, api)
        if art_scales is not None and args.quant != "pt_static":
            art_scales = None       # stored scales only apply to pt_static

    corpus = SyntheticCorpus(cfg.vocab_size, seed=args.seed)
    pipe = Pipeline(corpus, batch=args.batch, seq_len=args.prompt_len,
                    seed=args.seed + 1)
    calib = None
    if args.quant == "pt_static":
        if art_scales is not None:
            print("[serve] pt_static: serving the artifact's stored scales "
                  f"(calibrated under cushion "
                  f"{art_scales.cushion_fp[:12]}) — no load-time "
                  "calibration")
        else:
            calib = [{k: jnp.asarray(v)
                      for k, v in pipe.get_batch(1000 + i).items()}
                     for i in range(args.calib_batches)]
            print(f"[serve] pt_static: calibrating site scales over "
                  f"{len(calib)} batches at engine load"
                  + (" (under the loaded cushion)" if cushion is not None
                     else ""))

    if args.mode == "continuous":
        if args.replicas > 1 or args.chaos:
            out = run_router(api, params, qcfg, args,
                             bench_path=args.bench_json,
                             calib_batches=calib, cushion=cushion,
                             scales=art_scales)
        else:
            out = run_continuous(api, params, qcfg, args,
                                 bench_path=args.bench_json, mesh=mesh,
                                 calib_batches=calib, cushion=cushion,
                                 scales=art_scales)
        print_span_summary()
        return out

    batch = {k: jnp.asarray(v) for k, v in pipe.get_batch(0).items()}

    eng = Engine(api, params, qcfg,
                 max_seq=args.prompt_len + args.tokens + 32,
                 cushion=cushion, scales=art_scales,
                 kv_dtype=None if args.kv_dtype == "fp" else args.kv_dtype,
                 mesh=mesh, calib_batches=calib, prequant=args.prequant,
                 weight_bits=args.weight_bits)
    print(f"[serve] resident weights: "
          f"fp={eng.weight_bytes_fp / 2 ** 20:.1f} MiB "
          f"int8={eng.weight_bytes_int8 / 2 ** 20:.1f} MiB "
          f"int4={eng.weight_bytes_int4 / 2 ** 20:.1f} MiB")
    if args.bench_json:
        eng.generate(batch, args.tokens)     # warm/compile: the recorded
        # point must measure steady-state decode, not scan-loop tracing
    res = eng.generate(batch, args.tokens)
    print(f"[serve] B={args.batch} prompt={args.prompt_len} "
          f"gen={args.tokens} kv={args.kv_dtype} tp={args.tp} "
          f"TTFT={res.ttft_ms:.1f}ms TPOT={res.tpot_ms:.2f}ms")
    print("[serve] sample:", res.tokens[0][:16].tolist())
    if args.bench_json:
        _append_point(args.bench_json, {
            "mode": "static", "arch": args.arch, "quant": args.quant,
            "prequant": args.prequant, "weight_bits": args.weight_bits,
            "kv_dtype": args.kv_dtype,
            "batch": args.batch, "tp": args.tp,
            "prompt_len": args.prompt_len, "tokens": args.tokens,
            "weight_bytes_fp": eng.weight_bytes_fp,
            "weight_bytes_int8": eng.weight_bytes_int8,
            "weight_bytes_int4": eng.weight_bytes_int4,
            "ttft_ms": res.ttft_ms, "tpot_ms": res.tpot_ms})
    return res


if __name__ == "__main__":
    main()
