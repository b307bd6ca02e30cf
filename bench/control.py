#!/usr/bin/env python3
"""Readings for the limit of ``correct``: the program against its control.

    python3 bench/control.py --workload NAME --seconds S --seeds 11 12 13

For each seed this runs the cell as ``run.py`` does (set-up, the window,
the sample) and reads two numbers over the same sampled positions against
the plain reference: the widest gap of the tokens the program served
(``widest_gap``, the lower reading) and the widest gap of the tokens that
the control puts first (``control_gap``, the upper reading). The control is
the configuration one precision step down, on the program's own path: the
same engine's weights prequantized to int4 (``weight_bits=4``, W4A8), run
over the prompt and the served tokens by the program's full-sequence
forward, with the program's own choice of W4A8 route (the Pallas kernel on
a TPU). Each reading goes through the verdict that decides ``correct``:
the control's has to come out false. One JSON line per seed goes to
standard output.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as R


def w4a8_control(cfg, mix, seed):
    """The program's W4A8 path over the same sequences: returns
    ``first(seq, rows)``, the token it puts first at each row."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import QuantConfig
    from repro.models import registry
    from repro.serving.engine import plan_quantization

    import cell as CL
    import model as M
    api = registry.build(M.program_config(cfg))
    params = M.make_weights(cfg, seed)
    qcfg = QuantConfig(mode="pt_static", true_int8=True)
    cushion = jax.jit(lambda p, t: api.extract_cushion(
        p, t, None, QuantConfig()))(params,
                                    jnp.asarray(M.cushion_tokens(cfg, seed)))
    m = int(cfg["serving"]["cushion_len"])
    p4, scales = plan_quantization(
        api, params, qcfg, cushion=cushion,
        calib_batches=M.calibration_tokens(cfg, seed), prequant=True,
        weight_bits=4)
    del params

    @jax.jit
    def first(p, toks, rows):
        logits, _ = api.forward(p, {"tokens": toks[None]}, qcfg,
                                scales=scales, cushion=cushion, remat=False)
        return jnp.argmax(logits[0, rows].astype(jnp.float32), axis=-1)

    positions, _ = CL.pool_pages(cfg, mix)
    length = -(-positions // 128) * 128 - m

    n_rows = CL.ref_rows(mix)

    def call(seq, rows):
        body = seq[m:]
        toks = jnp.zeros((length,), jnp.int32).at[:len(body)].set(body)
        r = np.full((n_rows,), rows[-1] - m, np.int32)
        r[:len(rows)] = rows - m
        return jax.device_get(first(p4, toks, jnp.asarray(r)))[:len(rows)]

    return call


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    R.setup_paths()
    R.enable_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    import cell as CL
    import model as M
    import traffic as TF
    spec = R.load_spec()
    w = R.cell_spec(spec, args.workload)
    cfg, mix = M.load_config(w["config"]), TF.load_mix(w["traffic"])
    limit = cfg["correct"]["widest_gap_limit"]
    for seed in args.seeds:
        o = CL.run(cfg, mix, seed, args.seconds, control=w4a8_control)
        print(json.dumps({
            "workload": args.workload, "seed": seed, **o.check,
            "limit": limit,
            "correct": R.verdict(o.check["widest_gap"], limit),
            "control_correct": R.verdict(o.check["control_gap"], limit),
            "setup_s": o.phases["setup_s"], "check_s": o.phases["check_s"],
            "window_tokens": o.window.tokens,
            "compiles_in_window": o.window.compiles,
            "occupancy_pct": 100.0 * o.window.live_slot_steps
            / max(1, o.window.steps * o.n_slots),
            "memory_peak_bytes": o.memory_peak_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
