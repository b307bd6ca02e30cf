"""Fault-tolerant training launcher.

    python -m repro.launch.train --arch paper_tiny --steps 300 \
        [--smoke] [--ckpt-dir /tmp/ckpt] [--resume] [--quant pt_static]

On CPU this trains the reduced/paper-scale configs; on a pod the identical
entrypoint compiles against the production mesh (--mesh single|multi).
The Supervisor provides retry/restore, straggler flagging, and deterministic
data replay from the checkpointed step.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import QuantConfig, RunConfig, get_config, reduced
from repro.data.pipeline import Pipeline, SyntheticCorpus
from repro.distributed import sharding as SH
from repro.distributed.fault_tolerance import Supervisor
from repro.models.registry import build
from repro.optim.adamw import AdamW, cosine_lr
from repro.train.trainer import eval_ppl, make_optimizer, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_tiny")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config of the arch family")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--quant", default="none")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-batches", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg, dtype="float32")
    elif args.arch == "paper_tiny":
        pass
    api = build(cfg)
    run = RunConfig(model=cfg, quant=QuantConfig(mode=args.quant),
                    seq_len=args.seq, global_batch=args.batch, lr=args.lr,
                    train_steps=args.steps,
                    warmup_steps=max(10, args.steps // 20))

    corpus = SyntheticCorpus(cfg.vocab_size, seed=args.seed)
    pipe = Pipeline(corpus, batch=args.batch, seq_len=args.seq,
                    seed=args.seed)

    rng = jax.random.PRNGKey(args.seed)
    params = api.init_params(rng)
    opt = make_optimizer(run)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(api, run, opt))

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    state = {"params": params, "opt": opt_state._asdict()}
    step0 = 0
    if args.resume and ckpt.latest_step() is not None:
        step0 = ckpt.latest_step()
        state = ckpt.restore(step0, like=state)
        print(f"[train] resumed from step {step0}")

    from repro.optim.adamw import AdamWState
    sup = Supervisor(ckpt, save_every=args.save_every)
    log = []

    def do_step(state, step):
        batch = {k: jnp.asarray(v) for k, v in pipe.get_batch(step).items()}
        p, o, metrics = step_fn(state["params"],
                                AdamWState(**state["opt"]), batch)
        return {"params": p, "opt": o._asdict()}, metrics

    def on_metrics(step, metrics):
        if step % 20 == 0:
            rec = {"step": step, **{k: float(v) for k, v in metrics.items()}}
            log.append(rec)
            print(f"[train] step={step} loss={rec['loss']:.4f} "
                  f"lr={rec.get('lr', 0):.2e}")

    t0 = time.time()
    state, report = sup.run(state, step0, args.steps - step0, do_step,
                            on_metrics=on_metrics)
    wall = time.time() - t0

    eval_batches = [
        {k: jnp.asarray(v) for k, v in pipe.get_batch(10_000 + i).items()}
        for i in range(args.eval_batches)]
    ppl = eval_ppl(api, state["params"], eval_batches, run.quant)
    print(f"[train] done steps={report.completed_steps} wall={wall:.1f}s "
          f"eval_ppl={ppl:.3f} failures={report.failures} "
          f"stragglers={len(report.stragglers)}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"ppl": ppl, "wall_s": wall, "log": log,
                       "report": dataclasses.asdict(report)}, f)
    return state, ppl


if __name__ == "__main__":
    main()
