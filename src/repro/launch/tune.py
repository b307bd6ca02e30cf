"""CushionCache tuning launcher: discover → tune → save a versioned
cushion artifact the serving stack can consume.

    python -m repro.launch.tune --arch paper_tiny --steps 60 \
        --out-dir artifacts/cushion --with-scales

The paper's two-stage pipeline, end-to-end:

  1. greedy token search (`core.cushioncache.greedy_search`, compile-once
     fast path) over calibration samples;
  2. extract the prefix KV/state artifact in the model dtype
     (`ModelAPI.extract_cushion`);
  3. gradient prefix tuning of the cushion KV block
     (`core.cushioncache.prefix_tune`: CE + λ·activation-range
     regularizer, compile-once donated step, periodic metric host syncs).
     ``--dp N`` shards tuning batches over a data mesh axis (CPU hosts get
     forced XLA devices automatically, like serve's --tp);
  4. ``--with-scales``: calibrate pt_static site scales under the *tuned*
     cushion (`core.calibration.calibrate_tagged`) and store them with
     their cushion fingerprint;
  5. save a versioned artifact via `checkpoint.store.CheckpointManager`:
     tree ``{"cushion": ..., "scales": ...}`` with the cushion content
     fingerprint and tuning metadata in the manifest ``extra``.

``launch/serve.py --cushion <dir>`` loads the latest version, re-verifies
the fingerprint against the restored bytes, and serves the tuned cushion
through Engine / ContinuousEngine / the replica router;
`serving.engine.plan_quantization` hard-fails if the stored scales'
fingerprint does not match the cushion actually being served.

Before/after quality numbers (last-block max-activation top-1, held-out
perplexity) print at the end and land in ``--report-json``.
"""
from __future__ import annotations

import argparse
import json
import sys


def _sniff_int_arg(name: str) -> int:
    try:
        if name in sys.argv:
            return int(sys.argv[sys.argv.index(name) + 1])
        return next(int(a.split("=", 1)[1]) for a in sys.argv
                    if a.startswith(name + "="))
    except (IndexError, ValueError, StopIteration):
        return 1


def _force_host_devices_for_dp() -> None:
    """--dp N on CPU needs N XLA host devices; the flag only takes effect
    before jax initializes — sniff argv at import time (same pattern as
    launch/serve.py's --tp)."""
    from repro.flags import force_host_device_count
    n = _sniff_int_arg("--dp")
    if n > 1:
        force_host_device_count(n)


_force_host_devices_for_dp()

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import CushionConfig, Family, QuantConfig, get_config, \
    reduced
from repro.core import cushioncache as CC
from repro.core import outliers as OUT
from repro.data.pipeline import Pipeline, SyntheticCorpus
from repro.models.registry import build
from repro.train.trainer import eval_ppl


def _make_batch_fns(api, cfg, args):
    """(sample_fn for search, tune batch generator, held-out eval batches).
    Token-only families draw from the synthetic pipeline (deterministic,
    disjoint step ranges for search/tune/eval); families with extra inputs
    (vlm patches, encdec frames) use `ModelAPI.make_batch`, which generates
    the full batch dict."""
    extras = cfg.family in (Family.VLM, Family.ENCDEC)
    if extras:
        sample_fn = lambda i: api.make_batch(
            jax.random.PRNGKey(args.seed * 7919 + i), 1, args.sample_len)

        def tune_batches():
            i = 0
            while True:
                yield api.make_batch(
                    jax.random.PRNGKey(args.seed * 104729 + 3000 + i),
                    args.batch, args.seq_len)
                i += 1

        eval_batches = [api.make_batch(
            jax.random.PRNGKey(args.seed * 7 + 7000 + i), args.batch,
            args.seq_len) for i in range(args.eval_batches)]
        return sample_fn, tune_batches(), eval_batches

    corpus = SyntheticCorpus(cfg.vocab_size, seed=args.seed)
    sample_pipe = Pipeline(corpus, batch=1, seq_len=args.sample_len,
                           seed=args.seed + 1)
    tune_pipe = Pipeline(corpus, batch=args.batch, seq_len=args.seq_len,
                         seed=args.seed + 2)
    as_dev = lambda b: {k: jnp.asarray(v) for k, v in b.items()}
    sample_fn = lambda i: as_dev(sample_pipe.get_batch(i))

    def tune_batches():
        i = 0
        while True:
            yield as_dev(tune_pipe.get_batch(3000 + i))
            i += 1

    eval_batches = [as_dev(tune_pipe.get_batch(7000 + i))
                    for i in range(args.eval_batches)]
    return sample_fn, tune_batches(), eval_batches


def _quality(api, params, cushion, eval_batches):
    """(max-activation top-1 of the last block input, held-out ppl)."""
    qnone = QuantConfig(mode="none")
    top1 = OUT.last_block_input_stats(api, params, eval_batches[0], qnone,
                                      cushion=cushion)["top1"]
    ppl = eval_ppl(api, params, eval_batches, qnone, cushion=cushion)
    return top1, ppl


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_tiny")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (matches serve --smoke so a smoke "
                         "artifact serves against smoke params)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", required=True,
                    help="artifact store (checkpoint.store versioned dir)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore trained params from latest checkpoint "
                         "(same layout as launch/serve.py)")
    # search stage
    ap.add_argument("--max-prefix-len", type=int, default=8)
    ap.add_argument("--candidates", type=int, default=64)
    ap.add_argument("--tau", type=float, default=1.0)
    ap.add_argument("--sample-len", type=int, default=64,
                    help="calibration sample length for the greedy search")
    # tune stage
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lam", type=float, default=0.05,
                    help="λ on the activation-range regularizer (eq. 11)")
    ap.add_argument("--log-every", type=int, default=10,
                    help="tuning metric host-sync cadence (steps per "
                         "blocking transfer)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=48,
                    help="tuning/eval batch sequence length")
    ap.add_argument("--dp", type=int, default=1,
                    help="shard tuning batches over a data mesh axis of "
                         "this width (cushion/optimizer state replicated)")
    ap.add_argument("--quant", default="pt_dynamic",
                    help="quantized-forward mode the tuning loss runs "
                         "under (straight-through fake quant)")
    ap.add_argument("--eval-batches", type=int, default=4)
    # artifact contents
    ap.add_argument("--with-scales", action="store_true",
                    help="calibrate pt_static site scales under the tuned "
                         "cushion and store them (fingerprint-tagged) in "
                         "the artifact")
    ap.add_argument("--calib-batches", type=int, default=2)
    ap.add_argument("--report-json", default=None,
                    help="write the search/tune log + quality numbers here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg, dtype="float32")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir)
        step = ckpt.latest_step()
        if step is not None:
            from repro.optim.adamw import AdamW, constant_lr
            opt_state = AdamW(lr=constant_lr(1e-3)).init(params)
            like = {"params": params, "opt": opt_state._asdict()}
            params = ckpt.restore(step, like=like)["params"]
            print(f"[tune] restored step {step}")

    qcfg = QuantConfig(mode=args.quant)
    ccfg = CushionConfig(max_prefix_len=args.max_prefix_len, tau=args.tau,
                         sample_len=args.sample_len,
                         n_candidates=args.candidates, seed_tokens=(1,),
                         lam=args.lam, tune_steps=args.steps,
                         tune_lr=args.lr, log_every=args.log_every)
    mesh = None
    if args.dp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(1, data=args.dp)
        if args.batch % args.dp:
            ap.error(f"--batch {args.batch} must divide over --dp {args.dp}")
        print(f"[tune] data-parallel tuning over "
              f"{[str(d) for d in mesh.devices.flat]}")

    sample_fn, tune_iter, eval_batches = _make_batch_fns(api, cfg, args)

    # stage 1: greedy search + artifact extraction (model dtype)
    greedy, sr, _ = CC.discover(api, params, sample_fn, iter(()), qcfg,
                                ccfg, jax.random.PRNGKey(args.seed + 2),
                                skip_tune=True)
    print(f"[tune] greedy prefix {sr.prefix_ids.tolist()} "
          f"({sr.wall_time_s:.1f}s, {len(sr.history)} iterations)")
    g_top1, g_ppl = _quality(api, params, greedy, eval_batches)

    # stage 2: gradient prefix tuning of the cushion KV block
    tr = CC.prefix_tune(api, params, greedy, tune_iter, qcfg, ccfg,
                        mesh=mesh)
    tuned = tr.cushion
    t_top1, t_ppl = _quality(api, params, tuned, eval_batches)
    print(f"[tune] {args.steps} steps in {tr.wall_time_s:.1f}s; "
          f"max-activation top1 {g_top1:.1f} -> {t_top1:.1f}, "
          f"held-out ppl {g_ppl:.2f} -> {t_ppl:.2f}")

    fp = CC.cushion_fingerprint(tuned)
    tree = {"cushion": tuned}
    extra = {"kind": "cushion", "arch": cfg.name,
             "family": str(cfg.family), "dtype": cfg.dtype,
             "fingerprint": fp,
             "prefix_ids": [int(t) for t in sr.prefix_ids],
             "quant_mode": args.quant, "tune_steps": args.steps,
             "lam": args.lam, "lr": args.lr, "smoke": bool(args.smoke),
             "maxact_top1": {"greedy": g_top1, "tuned": t_top1},
             "ppl": {"greedy": g_ppl, "tuned": t_ppl}}
    if args.with_scales:
        from repro.core.calibration import calibrate_tagged, scales_to_plain
        qstat = QuantConfig(mode="pt_static", true_int8=True)
        calib = [b for _, b in zip(range(args.calib_batches), tune_iter)]
        tagged, _ = calibrate_tagged(api, params, calib, qstat,
                                     cushion=tuned)
        tree["scales"] = scales_to_plain(tagged.scales)
        extra["scales_cushion_fp"] = tagged.cushion_fp
        print(f"[tune] pt_static scales calibrated under the tuned cushion "
              f"({len(calib)} batches)")

    store = CheckpointManager(args.out_dir)
    version = (store.latest_step() or 0) + 1
    path = store.save(version, tree, extra=extra)
    print(f"[tune] artifact v{version} -> {path} "
          f"(fingerprint {fp[:12]}, scales="
          f"{'yes' if 'scales' in tree else 'no'})")

    if args.report_json:
        with open(args.report_json, "w") as f:
            json.dump({"search": sr.history, "tune_log": tr.log,
                       "artifact": path, **extra}, f, indent=1)
        print(f"[tune] report -> {args.report_json}")
    return path


if __name__ == "__main__":
    main()
