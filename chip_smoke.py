#!/usr/bin/env python3
"""On-chip smoke test of the cushioned W8A8/W4A8 serving path (TPU only).

    python chip_smoke.py              # one chip: tune -> serve, four modes
    python chip_smoke.py --chips 4    # tp=4 and 4-replica serving only

One process drives everything through the user entry points
(``repro.launch.tune.main`` and ``repro.launch.serve.main``) at the
published width of Qwen1.5-0.5B (24 layers, d=1024, 16 heads, vocab
151936, bf16), with random weights drawn from ``--seed``.

One chip, in order:

1. ``tune``: greedy cushion search + a few prefix-tuning steps, with
   pt_static scales calibrated under the tuned cushion, saved as a
   fingerprinted artifact under ``.chip_smoke/`` (git-ignored).
2. ``serve_fp``: static ``Engine``, fp weights, fp KV.
3. ``serve_w8a8``: static, ``--quant pt_static --prequant``, int8 KV.
4. ``serve_paged_w8a8``: ``--mode continuous --paged --chunk-tokens auto``,
   W8A8.
5. ``serve_w4a8``: static, ``--weight-bits 4``.

Each serve phase checks that its lowered decode step and (quantized modes)
its prefill carry the phase's Pallas kernels as ``tpu_custom_call`` ops,
then reruns the same command with that kernel's module flag
(``repro.flags``) set to ``jnp`` and compares greedy tokens: identical for
fp and W8A8 (an integer product), at least ``W4A8_TOP1_FLOOR`` of the
positions equal for W4A8 (its jnp route folds the group scales in f32).

``--chips 4`` runs only: continuous paged W8A8 at ``--tp 4`` against the
same trace at tp=1 on one chip of the host, and ``--replicas 4`` one-chip
replicas behind the router against one engine. Both compare tokens per
request; the router run must show no deaths, retries or rejections, and
four distinct devices.

Every phase prints one JSON line (tokens, compile seconds, persistent-cache
hits, peak HBM bytes). A failed phase makes the script exit nonzero. The
last line is ``{"ok": true, "device": {...}}`` only when every phase
passed. The script exits nonzero without a result on a non-TPU backend and
when a ``REPRO_*_KERNEL`` variable forces the jnp path.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")
ARCH = "qwen1.5-0.5b"
# share of (row, step) greedy tokens where the W4A8 kernel and its jnp
# route must agree
W4A8_TOP1_FLOOR = 0.75
KERNEL_ENV = ("REPRO_DECODE_KERNEL", "REPRO_W8A8_KERNEL", "REPRO_W4A8_KERNEL")


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def forced_jnp(env) -> list:
    """The REPRO_*_KERNEL variables in ``env`` that force the jnp route."""
    return [k for k in KERNEL_ENV if env.get(k, "auto") == "jnp"]


def check_router_health(stats) -> None:
    """A no-chaos router run must not hide a failure as a failover: no
    replica death, no retry, no rejection, every request completed."""
    check(stats.replica_deaths == 0, f"{stats.replica_deaths} replica deaths")
    check(stats.retries == 0, f"{stats.retries} retries")
    check(stats.rejected == 0, f"{stats.rejected} rejections "
          f"{stats.rejections}")
    check(stats.completed == stats.submitted,
          f"{stats.completed}/{stats.submitted} requests completed")


def token_agreement(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    check(a.shape == b.shape, f"token shapes differ: {a.shape} vs {b.shape}")
    return float((a == b).mean())


def by_uid(outputs) -> dict:
    return {o.uid: [int(t) for t in o.tokens] for o in outputs}


class Smoke:
    """Runs the phases in one process and keeps the per-phase counters."""

    def __init__(self, seed: int):
        import jax
        from jax import monitoring

        self.jax = jax
        self.seed = seed
        self.results = []
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    # -- instrumentation ---------------------------------------------------

    def ir_dir(self, phase: str, run: str) -> str:
        d = os.path.join(WORK, "ir", phase, run)
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
        self.jax.config.update("jax_dump_ir_to", d)
        return d

    def kernels_in(self, ir_dir: str, module: str, kernels) -> None:
        """Assert each kernel is a ``tpu_custom_call`` in a lowered module
        whose name contains ``module`` (e.g. ``jit_gen_loop``)."""
        files = [f for f in glob.glob(os.path.join(ir_dir, "*.mlir"))
                 if module in os.path.basename(f)]
        check(files, f"no lowered {module} module in {ir_dir}")
        found = set()
        for f in files:
            with open(f) as fh:
                for line in fh:
                    if "tpu_custom_call" not in line:
                        continue
                    found.update(k for k in kernels
                                 if f'kernel_name = "{k}"' in line)
        missing = sorted(set(kernels) - found)
        check(not missing, f"{module}: no tpu_custom_call for {missing}")

    def peak_hbm(self):
        stats = self.jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def phase(self, name: str, fn) -> None:
        t0 = time.perf_counter()
        c0, h0, m0 = self.compile_s, self.cache_hits, self.cache_misses
        rec = {"phase": name}
        try:
            rec.update(fn() or {})
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 — reported, then the run fails
            traceback.print_exc()
            rec.update(ok=False, error=f"{type(e).__name__}: {e}")
        finally:
            self.jax.config.update("jax_dump_ir_to", "")
        rec.update(seconds=time.perf_counter() - t0,
                   compile_s=self.compile_s - c0,
                   cache_hits=self.cache_hits - h0,
                   cache_misses=self.cache_misses - m0,
                   peak_hbm_bytes=self.peak_hbm())
        self.results.append(rec)
        print(json.dumps(rec), flush=True)

    # -- entry points ------------------------------------------------------

    def serve(self, args, flag=None):
        """``repro.launch.serve.main(args)``, optionally with one kernel
        flag forced to ``jnp`` for the call."""
        from repro import flags
        from repro.launch import serve
        old = getattr(flags, flag) if flag else None
        if flag:
            setattr(flags, flag, "jnp")
        try:
            return serve.main([str(a) for a in args])
        finally:
            if flag:
                setattr(flags, flag, old)

    def base(self) -> list:
        return ["--arch", ARCH, "--seed", self.seed]

    # -- one chip ------------------------------------------------------------

    def tune(self):
        from repro.launch import tune
        shutil.rmtree(self.artifact, ignore_errors=True)
        path = tune.main([str(a) for a in self.base() + [
            "--out-dir", self.artifact, "--with-scales",
            "--max-prefix-len", 2, "--candidates", 8, "--sample-len", 32,
            "--steps", 4, "--log-every", 2, "--batch", 2, "--seq-len", 32,
            "--eval-batches", 1, "--calib-batches", 2]])
        return {"artifact": os.path.relpath(path, ROOT)}

    def static_phase(self, name, extra, flag, kernels, prefill_kernels=()):
        args = self.base() + ["--cushion", self.artifact, "--batch", 4,
                              "--prompt-len", 64, "--tokens", 16] + extra
        ir = self.ir_dir(name, "kernel")
        res = self.serve(args)
        self.kernels_in(ir, "jit_gen_loop", kernels)
        if prefill_kernels:
            self.kernels_in(ir, "jit_prefill", prefill_kernels)
        self.ir_dir(name, "jnp")
        ref = self.serve(args, flag=flag)
        agree = token_agreement(res.tokens, ref.tokens)
        floor = W4A8_TOP1_FLOOR if flag == "W4A8_KERNEL" else 1.0
        check(agree >= floor, f"kernel vs jnp greedy tokens agree on "
              f"{agree:.3f} of positions (< {floor})")
        return {"tokens": int(res.tokens.size), "agreement": agree,
                "floor": floor, "ttft_ms": res.ttft_ms,
                "tpot_ms": res.tpot_ms}

    def paged_phase(self):
        name = "serve_paged_w8a8"
        args = self.base() + [
            "--cushion", self.artifact, "--mode", "continuous", "--paged",
            "--chunk-tokens", "auto", "--quant", "pt_static", "--prequant",
            "--slots", 4, "--n-requests", 6, "--rate", 0,
            "--prompt-len", 64, "--tokens", 16]
        ir = self.ir_dir(name, "kernel")
        outs = self.serve(args)
        self.kernels_in(ir, "jit_step", ["flash_decode_paged",
                                         "w8a8_matmul"])
        self.kernels_in(ir, "jit_prefill", ["w8a8_matmul"])
        check(len(outs) == 6, f"{len(outs)}/6 requests completed")
        self.ir_dir(name, "jnp")
        ref = self.serve(args, flag="W8A8_KERNEL")
        check(by_uid(outs) == by_uid(ref),
              "paged W8A8 kernel vs jnp greedy tokens differ")
        return {"tokens": sum(len(o.tokens) for o in outs)}

    def run_one_chip(self) -> None:
        self.artifact = os.path.join(WORK, "cushion")
        self.phase("tune", self.tune)
        self.phase("serve_fp", lambda: self.static_phase(
            "serve_fp", [], "DECODE_KERNEL", ["flash_decode"]))
        self.phase("serve_w8a8", lambda: self.static_phase(
            "serve_w8a8", ["--quant", "pt_static", "--prequant",
                           "--kv-dtype", "int8"], "W8A8_KERNEL",
            ["flash_decode", "w8a8_matmul"], ["w8a8_matmul"]))
        self.phase("serve_paged_w8a8", self.paged_phase)
        self.phase("serve_w4a8", lambda: self.static_phase(
            "serve_w4a8", ["--quant", "pt_static", "--prequant",
                           "--weight-bits", 4], "W4A8_KERNEL",
            ["flash_decode", "w4a8_matmul"], ["w4a8_matmul"]))

    # -- four chips ----------------------------------------------------------

    def continuous_args(self) -> list:
        return self.base() + [
            "--mode", "continuous", "--paged", "--chunk-tokens", "auto",
            "--quant", "pt_static", "--prequant", "--slots", 4,
            "--n-requests", 8, "--rate", 0, "--prompt-len", 64,
            "--tokens", 16]

    def tp4_phase(self):
        args = self.continuous_args()
        ir = self.ir_dir("serve_tp4", "tp4")
        outs = self.serve(args + ["--tp", 4])
        self.kernels_in(ir, "jit_step", ["flash_decode_paged",
                                         "w8a8_matmul"])
        ref = self.serve(args)
        check(by_uid(outs) == by_uid(ref), "tp=4 vs tp=1 tokens differ")
        return {"tokens": sum(len(o.tokens) for o in outs)}

    def replicas_phase(self):
        args = self.continuous_args()
        res = self.serve(args + ["--replicas", 4])
        check_router_health(res.stats)
        devices = [tuple(p["devices"]) for p in res.stats.per_replica]
        check(len(set(devices)) == 4, f"replica devices not distinct: "
              f"{devices}")
        ref = self.serve(args)
        check(by_uid(res.outputs) == by_uid(ref),
              "4 replicas vs one engine tokens differ")
        return {"tokens": sum(len(o.tokens) for o in res.outputs),
                "devices": [list(d) for d in devices]}

    def run_four_chips(self) -> None:
        check(len(self.jax.devices()) >= 4,
              f"--chips 4 needs 4 devices, found {len(self.jax.devices())}")
        self.phase("serve_tp4", self.tp4_phase)
        self.phase("serve_replicas4", self.replicas_phase)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    forced = forced_jnp(os.environ)
    if forced:
        print(f"chip_smoke: {forced} force the jnp route; the smoke checks "
              "the kernels", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    print(json.dumps({"compile_cache": cache_dir}), flush=True)

    smoke = Smoke(args.seed)
    if args.chips == 4:
        smoke.run_four_chips()
    else:
        smoke.run_one_chip()
    shutil.rmtree(os.path.join(WORK, "ir"), ignore_errors=True)
    if not all(r["ok"] for r in smoke.results):
        print("chip_smoke: failed phases: "
              f"{[r['phase'] for r in smoke.results if not r['ok']]}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
