"""Benchmark harness. One function per paper table (1-6, 8, 9) plus kernel
microbenches. Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run              # everything
    PYTHONPATH=src python -m benchmarks.run --only table5_magnitudes
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def kernel_microbench():
    """Per-call timings of the kernel oracles AND the real Pallas
    ``w8a8_matmul`` kernel (interpret mode on CPU, native on TPU) — the
    serving matmul path is bench-covered, not just test-covered. The
    kernel run is parity-checked against the oracle before its timing is
    emitted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import emit
    from repro.kernels import ref as R
    from repro.kernels.w8a8_matmul import w8a8_matmul

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(-127, 128, (512, 1024)), jnp.int8)
    w = jnp.asarray(rng.randint(-127, 128, (1024, 1024)), jnp.int8)
    f = jax.jit(lambda x, w: R.w8a8_matmul_ref(x, w, jnp.float32(0.01),
                                               jnp.float32(2.0),
                                               jnp.float32(0.02)))
    f(x, w).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        f(x, w).block_until_ready()
    emit("kernel_w8a8_ref_512x1024x1024",
         (time.perf_counter() - t0) / 10 * 1e6, "int8 matmul oracle")

    interpret = jax.default_backend() != "tpu"
    g = lambda x, w: w8a8_matmul(x, w, 0.01, 2.0, 0.02,
                                 interpret=interpret)
    out = g(x, w)
    out.block_until_ready()
    np.testing.assert_allclose(np.asarray(out), np.asarray(f(x, w)),
                               rtol=1e-6, atol=1e-5)
    t0 = time.perf_counter()
    for _ in range(10):
        g(x, w).block_until_ready()
    emit("kernel_w8a8_pallas_512x1024x1024",
         (time.perf_counter() - t0) / 10 * 1e6,
         f"Pallas kernel ({'interpret' if interpret else 'tpu'}), "
         f"parity-checked vs oracle")

    q = jnp.asarray(rng.randn(1, 8, 512, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 8, 528, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 8, 528, 64).astype(np.float32))
    g = jax.jit(lambda q, k, v: R.flash_attention_ref(q, k, v, True, 16))
    g(q, k, v).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        g(q, k, v).block_until_ready()
    emit("kernel_flash_ref_B1H8S512", (time.perf_counter() - t0) / 5 * 1e6,
         "prefix flash oracle")


def decode_bench():
    """Serving decode-path bench: TPOT at several cache fills, fp vs int8
    KV, scanned loop vs legacy per-token host loop. Emits CSV rows and the
    ``results/BENCH_decode.json`` trajectory artifact future PRs regress
    against."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import emit
    from repro.configs import QuantConfig, get_config
    from repro.models.registry import build
    from repro.serving.engine import Engine

    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    n_gen, B = 16, 2
    points = []
    for fill in (64, 192):
        rs = np.random.RandomState(fill)
        batch = {"tokens": jnp.asarray(
            rs.randint(0, cfg.vocab_size, (B, fill)), jnp.int32)}
        for kv_dtype in (None, "int8"):
            eng = Engine(api, params, QuantConfig(mode="none"),
                         max_seq=fill + n_gen + 8, kv_dtype=kv_dtype)
            eng.generate(batch, n_gen)            # warm/compile
            res = eng.generate(batch, n_gen)
            eng.generate_py(batch, n_gen)         # warm/compile
            res_py = eng.generate_py(batch, n_gen)
            tag = f"decode_fill{fill}_{kv_dtype or 'fp'}"
            emit(f"{tag}_tpot", res.tpot_ms * 1e3, "scanned decode loop")
            emit(f"{tag}_tpot_pyloop", res_py.tpot_ms * 1e3,
                 "per-token host-sync loop")
            points.append({"fill": fill, "kv_dtype": kv_dtype or "fp",
                           "batch": B, "n_gen": n_gen,
                           "ttft_ms": res.ttft_ms, "tpot_ms": res.tpot_ms,
                           "tpot_ms_pyloop": res_py.tpot_ms})
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "BENCH_decode.json"), "w") as f:
        json.dump({"bench": "decode", "points": points}, f, indent=1)


def search_bench():
    """Greedy-search fast-path bench: wall time + compile count of the
    compile-once KV-reuse search (`greedy_search`) vs the reference
    full-forward search (`greedy_search_ref`) on paper_tiny with planted
    outliers. Emits CSV rows and the ``results/BENCH_search.json``
    trajectory artifact future PRs regress against.

    Uses per-token dynamic activation quantization, where the two scorers
    are mathematically identical — the emitted ``prefix_match`` asserts the
    searched prefixes agree token for token."""
    import json
    import os

    import jax
    import numpy as np
    from benchmarks.common import emit
    from repro.configs import CushionConfig, QuantConfig, get_config
    from repro.core import cushioncache as CC
    from repro.models.registry import build
    from repro.monitoring import count_compiles

    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    # plant the paper's massive-activation pathology so candidate ranking
    # is meaningful (same surgery as tests/test_cushion.py)
    w = params["layers"]["mlp"]["w_down"]
    params["layers"]["mlp"]["w_down"] = w.at[0, :8, 5].set(300.0)

    qcfg = QuantConfig(mode="ptoken_dynamic")
    ccfg = CushionConfig(max_prefix_len=16, tau=1.5, n_candidates=64,
                         sample_len=48, seed_tokens=(1,))

    def sample(i):
        return api.make_batch(jax.random.PRNGKey(1000 + i), 1,
                              ccfg.sample_len)

    runs = {}
    for name, fn in (("fast", CC.greedy_search),
                     ("ref", CC.greedy_search_ref)):
        with count_compiles() as c:
            t0 = time.perf_counter()
            res = fn(api, params, sample, qcfg, ccfg, jax.random.PRNGKey(0),
                     chunk=8, verbose=False)
            wall = time.perf_counter() - t0
        runs[name] = {"wall_s": wall, "compiles": c.count,
                      "prefix": [int(t) for t in res.prefix_ids],
                      "iters": len(res.history)}
        emit(f"search_{name}_wall", wall * 1e6,
             f"{c.count} compiles, {len(res.history)} iters")

    speedup = runs["ref"]["wall_s"] / max(runs["fast"]["wall_s"], 1e-9)
    match = runs["fast"]["prefix"] == runs["ref"]["prefix"]
    emit("search_speedup", speedup * 1e6, f"prefix_match={match}")
    point = {"model": cfg.name, "quant_mode": qcfg.mode,
             "max_prefix_len": ccfg.max_prefix_len,
             "n_candidates": ccfg.n_candidates,
             "sample_len": ccfg.sample_len,
             "wall_s_fast": runs["fast"]["wall_s"],
             "wall_s_ref": runs["ref"]["wall_s"],
             "compiles_fast": runs["fast"]["compiles"],
             "compiles_ref": runs["ref"]["compiles"],
             "speedup": speedup, "prefix_match": match,
             "prefix_fast": runs["fast"]["prefix"],
             "prefix_ref": runs["ref"]["prefix"]}
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "BENCH_search.json"), "w") as f:
        json.dump({"bench": "search", "points": [point]}, f, indent=1)
    if not match:
        raise SystemExit(
            f"search fast path diverged from reference: "
            f"{runs['fast']['prefix']} vs {runs['ref']['prefix']}")


def serve_bench(tp: int = 1):
    """Continuous-batching serve bench: replay one Poisson-arrival trace
    through the slot-pool scheduler (``ContinuousEngine``) and through
    sequential per-request ``Engine.generate``, on paper_tiny with a
    cushion prefix. Asserts the cross-path parity oracle (greedy tokens
    identical request-for-request) and that continuous batching delivers
    higher aggregate tokens/s; emits CSV rows and the
    ``results/BENCH_serve.json`` trajectory artifact (tokens/s, p50/p99
    request latency, slot occupancy from ``monitoring.ServeStats``).

    ``tp > 1`` (``--tp``) reruns the whole bench on a (data=1, tp) mesh —
    params under the serve rules, KV pool sharded on its heads axis — and
    additionally asserts the sharded static Engine generates token-for-token
    what the unsharded one does; the point then lands in
    ``results/BENCH_tp.json`` so the tp trajectory regresses separately."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import emit
    from repro.configs import QuantConfig, get_config
    from repro.launch.serve import poisson_trace
    from repro.models.registry import build
    from repro.serving.engine import Engine
    from repro.serving.scheduler import ContinuousEngine

    mesh = None
    if tp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(tp)

    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    qcfg = QuantConfig(mode="none")
    cushion = api.extract_cushion(params, jnp.asarray([1, 2, 3], jnp.int32),
                                  None, qcfg)
    n_slots, n_requests, rate = 8, 16, 60.0
    prompt_lens, budgets = (48, 64), (32, 24)
    max_seq = 64 + 32 + 32
    reqs = poisson_trace(api, 0, n_requests, rate, prompt_lens, budgets)

    ce = ContinuousEngine(api, params, qcfg, n_slots=n_slots,
                          max_seq=max_seq, cushion=cushion, mesh=mesh)
    eng = Engine(api, params, qcfg, cushion=cushion, max_seq=max_seq,
                 mesh=mesh)

    if mesh is not None:
        # tp parity gate: the sharded engine must reproduce the unsharded
        # engine token-for-token before any throughput number is recorded
        eng1 = Engine(api, params, qcfg, cushion=cushion, max_seq=max_seq)
        r = reqs[0]
        if not np.array_equal(eng.generate(r.batch, r.max_new_tokens).tokens,
                              eng1.generate(r.batch, r.max_new_tokens).tokens):
            raise SystemExit(f"tp={tp} generation diverged from tp=1")
        del eng1

    first_arrival = min(r.arrival_s for r in reqs)

    def run_sequential():
        t0 = time.perf_counter()
        outs = []
        for r in sorted(reqs, key=lambda r: r.arrival_s):
            wait = r.arrival_s - (time.perf_counter() - t0)
            if wait > 0:            # requests can't start before they arrive
                time.sleep(wait)
            res = eng.generate(r.batch, r.max_new_tokens)
            outs.append((r, res, time.perf_counter() - t0))
        # span on the same basis as the continuous path: first arrival ->
        # last completion (excludes the idle lead-in before any work exists)
        span = outs[-1][2] - first_arrival
        lat = np.asarray([done - r.arrival_s for r, _, done in outs])
        return outs, span, lat

    # warm both paths: the bench measures steady-state serving, not tracing
    ce.run(reqs)
    run_sequential()

    cont = ce.run(reqs)
    span_c = max(o.finished_s for o in cont) - first_arrival
    lat_c = np.asarray([o.latency_s for o in cont])
    total = sum(len(o.tokens) for o in cont)
    tps_c = total / span_c

    seq, span_s, lat_s = run_sequential()
    tps_s = total / span_s

    # poisson_trace emits uids in arrival order, so seq[i] is request uid i
    match = all(o.uid == r.uid and np.array_equal(o.tokens, res.tokens[0])
                for o, (r, res, _) in zip(cont, seq))
    occ = ce.stats.occupancy()
    emit("serve_continuous_tokens_per_s", tps_c * 1e6,
         f"{n_slots} slots, occupancy={occ:.2f}")
    emit("serve_sequential_tokens_per_s", tps_s * 1e6,
         "per-request Engine.generate")
    emit("serve_speedup", tps_c / tps_s * 1e6, f"parity_match={match}")

    point = {"model": cfg.name, "tp": tp, "n_slots": n_slots,
             "n_requests": n_requests, "rate_req_s": rate,
             "prompt_lens": list(prompt_lens), "budgets": list(budgets),
             "total_tokens": total,
             "tokens_per_s_continuous": tps_c,
             "tokens_per_s_sequential": tps_s,
             "speedup": tps_c / tps_s,
             "p50_latency_s_continuous": float(np.percentile(lat_c, 50)),
             "p99_latency_s_continuous": float(np.percentile(lat_c, 99)),
             "p50_latency_s_sequential": float(np.percentile(lat_s, 50)),
             "p99_latency_s_sequential": float(np.percentile(lat_s, 99)),
             "parity_match": match, **ce.stats.as_dict()}
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out_dir, exist_ok=True)
    fname, bname = (("BENCH_tp.json", "serve_tp") if tp > 1
                    else ("BENCH_serve.json", "serve"))
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump({"bench": bname, "points": [point]}, f, indent=1)
    if not match:
        raise SystemExit("continuous scheduler diverged from per-request "
                         "Engine.generate (parity oracle failed)")
    if tps_c <= tps_s:
        raise SystemExit(
            f"continuous batching did not beat sequential serving: "
            f"{tps_c:.1f} vs {tps_s:.1f} tok/s")

    if tp == 1:
        # ------------------------------------------------------------------
        # Chunked-prefill head-of-line point: one long prompt arrives first
        # on a high-rate Poisson trace of short interactive requests. With
        # blocking admission every short behind the long waits out its whole
        # prefill; with chunked admission shorts admit between the long's
        # chunks. Gate: p99 arrival->first-token TTFT of the short class
        # strictly below the blocking baseline, at token-for-token parity.
        # ------------------------------------------------------------------
        long_len, short_len, n_long = 448, 32, 2
        lens = ((long_len,) + (short_len,) * 7) * n_long
        reqs2 = poisson_trace(api, 1, len(lens), 200.0, lens, (8,))
        arrivals = {r.uid: r.arrival_s for r in reqs2}
        short_uids = {r.uid for r in reqs2
                      if r.batch["tokens"].shape[1] == short_len}

        def arrival_ttft(outs, uids):
            return np.asarray([o.admitted_s - arrivals[o.uid]
                               for o in outs if o.uid in uids])

        kw = dict(n_slots=16, max_seq=512, cushion=cushion)
        blocking = ContinuousEngine(api, params, qcfg, **kw)
        chunked = ContinuousEngine(api, params, qcfg, chunk_tokens=64, **kw)
        blocking.run(reqs2)         # warm/compile (incl. per-chunk shapes)
        chunked.run(reqs2)
        out_b = blocking.run(reqs2)
        out_c = chunked.run(reqs2)
        match2 = all(a.uid == b.uid and np.array_equal(a.tokens, b.tokens)
                     for a, b in zip(out_b, out_c))
        p99_b = float(np.percentile(arrival_ttft(out_b, short_uids), 99))
        p99_c = float(np.percentile(arrival_ttft(out_c, short_uids), 99))
        all_b = arrival_ttft(out_b, arrivals)
        all_c = arrival_ttft(out_c, arrivals)
        emit("serve_chunked_p99_ttft_short_blocking", p99_b * 1e6,
             f"{n_long}x{long_len}-tok long prompt ahead")
        emit("serve_chunked_p99_ttft_short_chunked", p99_c * 1e6,
             f"chunk=64, {chunked.stats.prefill_chunks} chunks, "
             f"parity={match2}")
        point2 = {"model": cfg.name, "tp": tp, "mode": "chunked_prefill",
                  "n_slots": 16, "n_requests": len(lens),
                  "rate_req_s": 200.0, "chunk_tokens": 64,
                  "long_prompt_len": long_len, "short_prompt_len": short_len,
                  "n_long": n_long, "parity_match": match2,
                  "prefill_chunks": chunked.stats.prefill_chunks,
                  "p99_ttft_s_short_blocking": p99_b,
                  "p99_ttft_s_short_chunked": p99_c,
                  "p50_ttft_s_all_blocking": float(np.percentile(all_b, 50)),
                  "p99_ttft_s_all_blocking": float(np.percentile(all_b, 99)),
                  "p50_ttft_s_all_chunked": float(np.percentile(all_c, 50)),
                  "p99_ttft_s_all_chunked": float(np.percentile(all_c, 99))}
        with open(os.path.join(out_dir, "BENCH_serve.json")) as f:
            doc = json.load(f)
        doc["points"].append(point2)
        with open(os.path.join(out_dir, "BENCH_serve.json"), "w") as f:
            json.dump(doc, f, indent=1)
        if not match2:
            raise SystemExit("chunked admission diverged from blocking "
                             "admission (parity oracle failed)")
        if p99_c >= p99_b:
            raise SystemExit(
                f"chunked prefill did not beat blocking admission on "
                f"short-request p99 TTFT: {p99_c * 1e3:.1f}ms vs "
                f"{p99_b * 1e3:.1f}ms (head-of-line block not relieved)")


def w8a8_bench():
    """Calibrated W8A8 serving bench: fp vs per-tensor-static int8 serving
    TTFT/TPOT on one paper_tiny trace, parity-gated. Three engines share
    one calibration: the fp baseline (mode=none), the fp-weight true-int8
    pt_static path (weights quantized on the fly inside the jit), and the
    int8-resident prequantized path (--prequant; decode streams
    1 byte/weight). The gate asserts prequantized greedy tokens equal the
    fp-weight pt_static tokens bit-for-bit — identical int math, only the
    weight residency differs — before any number lands in the checked-in
    ``results/BENCH_w8a8.json`` trajectory."""
    import json
    import os

    import jax
    import numpy as np
    from benchmarks.common import emit
    from repro.configs import QuantConfig, get_config
    from repro.core.calibration import calibrate
    from repro.models.registry import build
    from repro.serving.engine import Engine

    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    qfp = QuantConfig(mode="none")
    qw8 = QuantConfig(mode="pt_static", true_int8=True)
    cal = [api.make_batch(jax.random.PRNGKey(100 + i), 2, 48)
           for i in range(2)]
    scales, _ = calibrate(api, params, cal, qw8)
    B, prompt, n_gen = 4, 64, 32
    batch = api.make_batch(jax.random.PRNGKey(7), B, prompt)
    max_seq = prompt + n_gen + 32

    engines = {
        "fp": Engine(api, params, qfp, max_seq=max_seq),
        "w8a8": Engine(api, params, qw8, max_seq=max_seq, scales=scales),
        "w8a8_prequant": Engine(api, params, qw8, max_seq=max_seq,
                                scales=scales, prequant=True),
    }
    results = {}
    for name, eng in engines.items():
        eng.generate(batch, n_gen)          # warm/compile pass
        res = eng.generate(batch, n_gen)
        results[name] = res
        emit(f"w8a8_{name}_ttft", res.ttft_ms * 1e3, "prefill wall")
        emit(f"w8a8_{name}_tpot", res.tpot_ms * 1e3, "per-token wall")

    match = bool(np.array_equal(results["w8a8_prequant"].tokens,
                                results["w8a8"].tokens))
    emit("w8a8_parity", float(match) * 1e6,
         "prequant tokens == fp-weight pt_static tokens")
    ttft_ratio = results["w8a8_prequant"].ttft_ms / results["fp"].ttft_ms
    emit("w8a8_prequant_ttft_ratio", ttft_ratio * 1e6, "prequant/fp TTFT")
    point = {"model": cfg.name, "batch": B, "prompt_len": prompt,
             "n_gen": n_gen, "parity_match": match,
             "ttft_ratio_prequant_vs_fp": ttft_ratio,
             "weight_bytes_fp": engines["fp"].weight_bytes_fp,
             "weight_bytes_int8_resident":
                 engines["w8a8_prequant"].weight_bytes_int8}
    for name, res in results.items():
        point[f"ttft_ms_{name}"] = res.ttft_ms
        point[f"tpot_ms_{name}"] = res.tpot_ms
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "BENCH_w8a8.json"), "w") as f:
        json.dump({"bench": "w8a8", "points": [point]}, f, indent=1)
    if not match:
        raise SystemExit(
            "int8-resident (prequantized) serving diverged from the "
            "fp-weight pt_static path (parity oracle failed)")
    # TTFT regression gate: prequantized prefill once ran ~3.9x fp on this
    # bench (CPU int8 dot_general scalarizes; the kernel path padded ragged
    # M to the tile). With the tiled ragged-M kernel and the exact f32-GEMM
    # CPU product, prefill must stay in the same ballpark as fp. The 1.5x
    # bound leaves room for quantize/dequant overhead but fails the bench
    # if pad-to-max (or the scalarized int8 product) ever comes back.
    if ttft_ratio > 1.5:
        raise SystemExit(
            f"prequantized TTFT regression: {ttft_ratio:.2f}x fp "
            f"({results['w8a8_prequant'].ttft_ms:.1f}ms vs "
            f"{results['fp'].ttft_ms:.1f}ms), gate is 1.5x")


def w4a8_bench(tp: int = 1):
    """W4A8 serving bench (``results/BENCH_w4a8.json``): int4-packed
    resident weights under the cushion prefix, gated four ways before any
    number lands in the trajectory:

    * route parity — the Pallas unpack-in-VMEM kernel (interpret mode off
      TPU) and the exact jnp fallback must generate greedy tokens
      token-for-token identical from the same packed tree;
    * residency — int4-packed bytes must be <= 0.55x the int8-resident
      W8A8 bytes (the 2x pack, with headroom for group scales);
    * TTFT — prequantized W4A8 prefill <= 1.5x fp (same regression gate
      as w8a8_bench: pad-to-max or a scalarized product would blow this);
    * quality under the cushion — greedy top-1 agreement vs fp and 4-bit
      fake-quant qerr, cushioned vs uncushioned (each calibrated under its
      own deployment distribution): the cushion must not lose top-1
      agreement and must reduce qerr, on the planted-outlier paper_tiny
      (same ``w_down`` surgery as cushion_bench).

    ``tp > 1`` (``--tp``) additionally asserts the sharded packed tree
    (serve rules; packed K-axis replicated) generates token-for-token what
    the unsharded engine does. The point records the weight-streaming
    roofline (predicted vs measured decode speedup from resident-byte
    ratios, ``benchmarks.roofline.weight_stream_point``)."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import emit
    from benchmarks.roofline import weight_stream_point
    from repro import flags
    from repro.configs import QuantConfig, get_config
    from repro.core import quantization as Q
    from repro.core.calibration import calibrate
    from repro.models import transformer as TMOD
    from repro.models.registry import build
    from repro.serving.engine import Engine

    mesh = None
    if tp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(tp)

    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    # plant the massive-activation pathway the cushion mitigates (same
    # surgery as cushion_bench) so the quality A/B measures the paper's
    # mechanism, not random-init noise
    w = params["layers"]["mlp"]["w_down"]
    params["layers"]["mlp"]["w_down"] = w.at[0, :8, 5].set(300.0)

    qfp = QuantConfig(mode="none")
    qw = QuantConfig(mode="pt_static", true_int8=True)
    cushion = api.extract_cushion(params, jnp.asarray([1, 2, 3], jnp.int32),
                                  None, qfp)
    cal = [api.make_batch(jax.random.PRNGKey(100 + i), 2, 48)
           for i in range(2)]
    scales, _ = calibrate(api, params, cal, qw, cushion=cushion)
    B, prompt, n_gen = 4, 64, 32
    batch = api.make_batch(jax.random.PRNGKey(7), B, prompt)
    max_seq = prompt + n_gen + 32

    def quant_engine(**kw):
        return Engine(api, params, qw, max_seq=max_seq, cushion=cushion,
                      scales=scales, prequant=True, **kw)

    engines = {
        "fp": Engine(api, params, qfp, max_seq=max_seq, cushion=cushion),
        "w8a8": quant_engine(),
        "w4a8": quant_engine(weight_bits=4),
    }
    results, ttft_ms, tpot_ms = {}, {}, {}
    for name, eng in engines.items():
        eng.generate(batch, n_gen)          # warm/compile pass
        runs = [eng.generate(batch, n_gen) for _ in range(3)]
        results[name] = runs[-1]
        # best-of-3 wall times: the TTFT regression gate compares two
        # ~20ms CPU prefills, so a single scheduler hiccup would flake it
        ttft_ms[name] = min(r.ttft_ms for r in runs)
        tpot_ms[name] = min(r.tpot_ms for r in runs)
        emit(f"w4a8_{name}_ttft", ttft_ms[name] * 1e3, "prefill wall")
        emit(f"w4a8_{name}_tpot", tpot_ms[name] * 1e3, "per-token wall")

    # route parity: jnp fallback vs Pallas kernel on the same packed tree.
    # Off TPU the kernel runs in interpret mode, so this gate exercises the
    # real kernel body (nibble unpack, group-scale accumulate, colsum
    # epilogue) on every CI run.
    old_route = flags.W4A8_KERNEL
    try:
        flags.W4A8_KERNEL = "jnp"
        toks_jnp = quant_engine(weight_bits=4).generate(batch, n_gen).tokens
        flags.W4A8_KERNEL = "pallas"
        toks_pal = quant_engine(weight_bits=4).generate(batch, n_gen).tokens
    finally:
        flags.W4A8_KERNEL = old_route
    route_match = bool(np.array_equal(toks_jnp, toks_pal))
    emit("w4a8_route_parity", float(route_match) * 1e6,
         "pallas kernel tokens == jnp fallback tokens")

    # quality under the cushion: teacher-forced greedy top-1 agreement vs
    # fp, and the paper's 4-bit fake-quant qerr, each A/B'd against the
    # uncushioned deployment (calibrated without the cushion)
    eval_batches = [api.make_batch(jax.random.PRNGKey(7000 + i), 2, 48)
                    for i in range(4)]
    qd4 = QuantConfig(mode="pt_dynamic", w_bits=4)

    def quality(c):
        sc, _ = calibrate(api, params, cal, qw, cushion=c)
        pq = Q.prequantize_tree(params, qw, weight_bits=4)
        tot = hit = 0
        for b in eval_batches:
            lf, _ = api.forward(params, b, qfp, cushion=c)
            lq, _ = api.forward(pq, b, qw, cushion=c, scales=sc)
            tot += lf.shape[0] * lf.shape[1]
            hit += int((jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).sum())
        _, taps = api.forward(params, eval_batches[0], qd4, cushion=c,
                              collect=True)
        return hit / tot, float(TMOD.total_qerr(taps))

    agree_c, qerr_c = quality(cushion)
    agree_n, qerr_n = quality(None)
    emit("w4a8_top1_vs_fp_cushion", agree_c * 1e6,
         f"uncushioned={agree_n:.4f}")
    emit("w4a8_qerr4_cushion", qerr_c * 1e3, f"uncushioned={qerr_n:.2f}")

    tp_match = None
    if mesh is not None:
        eng_tp = quant_engine(weight_bits=4, mesh=mesh)
        tp_match = bool(np.array_equal(eng_tp.generate(batch, n_gen).tokens,
                                       results["w4a8"].tokens))
        emit("w4a8_tp_parity", float(tp_match) * 1e6,
             f"tp={tp} packed-tree tokens == unsharded tokens")

    e4, e8, efp = engines["w4a8"], engines["w8a8"], engines["fp"]
    bytes_ratio = e4.weight_bytes_int4 / e8.weight_bytes_int8
    ttft_ratio = ttft_ms["w4a8"] / ttft_ms["fp"]
    emit("w4a8_bytes_ratio_vs_int8", bytes_ratio * 1e6, "packed/int8 bytes")
    emit("w4a8_prequant_ttft_ratio", ttft_ratio * 1e6, "w4a8/fp TTFT")

    roofline = weight_stream_point(
        {"fp": efp.weight_bytes_fp,
         "w8a8": e8.weight_bytes_fp + e8.weight_bytes_int8,
         "w4a8": e4.weight_bytes_fp + e4.weight_bytes_int4},
        dict(tpot_ms))

    point = {"model": cfg.name, "tp": tp, "batch": B, "prompt_len": prompt,
             "n_gen": n_gen, "group_size": qw.w_group,
             "route_parity_match": route_match, "tp_parity_match": tp_match,
             "bytes_ratio_int4_vs_int8": bytes_ratio,
             "ttft_ratio_prequant_vs_fp": ttft_ratio,
             "weight_bytes_fp": efp.weight_bytes_fp,
             "weight_bytes_int8_resident": e8.weight_bytes_int8,
             "weight_bytes_int4_resident": e4.weight_bytes_int4,
             "top1_vs_fp": {"cushion": agree_c, "none": agree_n},
             "qerr_w4_fakequant": {"cushion": qerr_c, "none": qerr_n},
             "roofline": roofline}
    for name in results:
        point[f"ttft_ms_{name}"] = ttft_ms[name]
        point[f"tpot_ms_{name}"] = tpot_ms[name]
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out_dir, exist_ok=True)
    fname = "BENCH_w4a8.json" if tp == 1 else "BENCH_w4a8_tp.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump({"bench": "w4a8", "points": [point]}, f, indent=1,
                  default=float)

    if not route_match:
        raise SystemExit("w4a8 Pallas kernel diverged from the exact jnp "
                         "fallback on the same packed tree (route parity "
                         "oracle failed)")
    if tp_match is False:
        raise SystemExit(f"tp={tp} sharded packed tree diverged from the "
                         f"unsharded w4a8 engine (tp parity oracle failed)")
    if bytes_ratio > 0.55:
        raise SystemExit(f"int4-packed residency regression: packed bytes "
                         f"are {bytes_ratio:.2f}x the int8-resident bytes, "
                         f"gate is 0.55x")
    # the TTFT regression gate is a single-process CPU wall-time bound;
    # under --tp the forced host-device split divides the XLA thread pool
    # and penalizes the heavier unpack prefill disproportionately, so the
    # tp run gates parity only and the dense run owns the perf gate
    if tp == 1 and ttft_ratio > 1.5:
        raise SystemExit(
            f"w4a8 prequantized TTFT regression: {ttft_ratio:.2f}x fp "
            f"({ttft_ms['w4a8']:.1f}ms vs {ttft_ms['fp']:.1f}ms), "
            f"gate is 1.5x")
    if agree_c < agree_n:
        raise SystemExit(f"cushion lost w4a8 greedy top-1 agreement vs fp: "
                         f"{agree_c:.4f} cushioned vs {agree_n:.4f} "
                         f"uncushioned")
    if qerr_c >= qerr_n:
        raise SystemExit(f"cushion does not reduce 4-bit quantization "
                         f"error: {qerr_c:.2f} vs {qerr_n:.2f} uncushioned")


def router_bench(replicas: int = 2):
    """Fault-tolerant replica-router bench: one Poisson trace through
    ``ReplicaRouter`` twice — a no-fault run, then the same trace with a
    deterministic chaos kill of one replica mid-trace
    (``crash@replica1.step``). The parity gate asserts the chaos run
    completes every request with greedy tokens token-for-token identical
    to the no-fault run (the cushion prefix is replicated bit-identically
    on every replica, and greedy decode is batch-composition independent,
    so failover retries are exact); retries/failovers/deaths must be
    visible in RouterStats. Emits CSV rows and the checked-in
    ``results/BENCH_router.json`` artifact with p50/p99 latency and TTFT
    for both runs."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import emit
    from repro.configs import QuantConfig, get_config
    from repro.distributed.fault_injection import FailPoint, FaultInjector
    from repro.launch.serve import poisson_trace
    from repro.models.registry import build
    from repro.serving.router import ReplicaRouter, RouterConfig

    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    qcfg = QuantConfig(mode="none")
    cushion = api.extract_cushion(params, jnp.asarray([1, 2, 3], jnp.int32),
                                  None, qcfg)
    n_slots, n_requests, rate = 4, 16, 60.0
    reqs = poisson_trace(api, 0, n_requests, rate,
                         prompt_lens=(48, 64), budgets=(32, 24))
    router = ReplicaRouter(api, params, qcfg, n_replicas=replicas,
                           cfg=RouterConfig(max_queue=n_requests),
                           cushion=cushion, n_slots=n_slots,
                           max_seq=64 + 32 + 32)

    router.run(reqs)                    # warm/compile pass
    base = router.run(reqs)             # no-fault measured run
    kill = FaultInjector([FailPoint(site="replica1.step", kind="crash",
                                    at_step=6)])
    chaos = router.run(reqs, injector=kill)

    def _pcts(res):
        lat = np.asarray([o.latency_s for o in res.outputs])
        ttft = np.asarray([o.ttft_ms for o in res.outputs])
        return {"p50_latency_s": float(np.percentile(lat, 50)),
                "p99_latency_s": float(np.percentile(lat, 99)),
                "p50_ttft_ms": float(np.percentile(ttft, 50)),
                "p99_ttft_ms": float(np.percentile(ttft, 99))}

    want = {o.uid: o.tokens for o in base.outputs}
    match = (len(base.outputs) == n_requests == len(chaos.outputs)
             and not base.rejected and not chaos.rejected
             and all(np.array_equal(o.tokens, want[o.uid])
                     for o in chaos.outputs))
    cs = chaos.stats
    fault_visible = (cs.replica_deaths == 1 and cs.failovers >= 1
                     and cs.retries >= 1)
    bp, cp = _pcts(base), _pcts(chaos)
    emit("router_nofault_p50_latency", bp["p50_latency_s"] * 1e6,
         f"{replicas} replicas x {n_slots} slots")
    emit("router_chaos_p50_latency", cp["p50_latency_s"] * 1e6,
         f"kill replica1 mid-trace; deaths={cs.replica_deaths} "
         f"failovers={cs.failovers} retries={cs.retries}")
    emit("router_parity", float(match) * 1e6,
         "chaos tokens == no-fault tokens for every request")

    point = {"model": cfg.name, "replicas": replicas, "n_slots": n_slots,
             "n_requests": n_requests, "rate_req_s": rate,
             "parity_match": match, "fault_visible": fault_visible,
             "nofault": {**bp, **base.stats.as_dict()},
             "chaos": {"kill": "crash@replica1.step:6", **cp,
                       **cs.as_dict()}}
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "BENCH_router.json"), "w") as f:
        json.dump({"bench": "router", "points": [point]}, f, indent=1)
    if not match:
        raise SystemExit("chaos run diverged from no-fault run "
                         "(router failover parity oracle failed)")
    if not fault_visible:
        raise SystemExit(
            f"injected kill left no trace in RouterStats: deaths="
            f"{cs.replica_deaths} failovers={cs.failovers} "
            f"retries={cs.retries}")


def page_bench(tp: int = 1):
    """Paged-KV-pool bench (``serving/paging.py``): one Poisson trace on
    paper_tiny with a cushion prefix, served by the dense per-slot pool and
    by the paged pool at matched ``n_slots``/``max_seq``. Parity-gated on
    four axes before anything lands in ``results/BENCH_pages.json``:

    * token-for-token identity paged vs contiguous on the same seeded trace
    * pool bytes reduced >= 2x at matched slots (the page store + tables +
      batch-free cushion vs the dense rows)
    * higher sustainable ``n_slots`` at fixed memory: a 2x-slot paged pool
      fitting inside the dense pool's byte budget serves the same trace
      token-for-token (greedy decode is batch-composition independent)
    * prefix caching: a stem-sharing trace hits the content-addressed page
      registry (hits >= 1) and still matches the dense pool token-for-token

    tokens/s for both pools is recorded and gated to "within noise or
    better" (paged >= 0.8x contiguous on this CPU-scale model; the win is
    memory, the gate guards against a pathological slowdown). ``tp > 1``
    (``--tp``) additionally runs the paged pool on a (data=1, tp) mesh —
    pages sharded on the heads axis — and gates its tokens against the
    unsharded dense run, landing ``tp_parity`` in the same artifact."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import emit
    from repro.configs import QuantConfig, get_config
    from repro.launch.serve import poisson_trace
    from repro.models.registry import build
    from repro.serving.scheduler import ContinuousEngine

    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    qcfg = QuantConfig(mode="none")
    cushion = api.extract_cushion(params, jnp.asarray([1, 2, 3], jnp.int32),
                                  None, qcfg)
    n_slots, n_requests, rate = 8, 16, 60.0
    prompt_lens, budgets = (48, 64), (32, 24)
    max_seq, ps = 384, 32
    # worst case here is 3 content pages per slot (prompt 64 + budget 24
    # under a 3-token cushion); 36 pages hold every slot's worst case with
    # headroom while the dense pool must provision 8 * 384 positions
    n_pages = 36
    reqs = poisson_trace(api, 0, n_requests, rate, prompt_lens, budgets)

    def run_engine(eng):
        eng.run(reqs)                       # warm/compile pass
        outs = eng.run(reqs)
        span = (max(o.finished_s for o in outs)
                - min(r.arrival_s for r in reqs))
        total = sum(len(o.tokens) for o in outs)
        return outs, total / span

    dense = ContinuousEngine(api, params, qcfg, n_slots=n_slots,
                             max_seq=max_seq, cushion=cushion)
    outs_d, tps_d = run_engine(dense)
    bytes_d = dense.stats.pool_bytes

    paged = ContinuousEngine(api, params, qcfg, n_slots=n_slots,
                             max_seq=max_seq, cushion=cushion, paged=True,
                             page_size=ps, n_pages=n_pages)
    outs_p, tps_p = run_engine(paged)
    bytes_p = paged.stats.pool_bytes

    want = {o.uid: o.tokens for o in outs_d}
    match = (len(outs_d) == n_requests == len(outs_p)
             and all(np.array_equal(o.tokens, want[o.uid])
                     for o in outs_p))
    ratio = bytes_d / bytes_p
    emit("page_dense_tokens_per_s", tps_d * 1e6,
         f"{n_slots} slots, pool {bytes_d} B")
    emit("page_paged_tokens_per_s", tps_p * 1e6,
         f"{n_pages} pages x {ps}, pool {bytes_p} B")
    emit("page_pool_bytes_ratio", ratio * 1e6, f"parity_match={match}")

    # fixed-memory scaling: double the slots, keep the paged pool inside
    # the dense pool's byte budget, and serve the identical trace
    big = ContinuousEngine(api, params, qcfg, n_slots=2 * n_slots,
                          max_seq=max_seq, cushion=cushion, paged=True,
                          page_size=ps, n_pages=2 * n_pages)
    outs_b, _ = run_engine(big)
    bytes_b = big.stats.pool_bytes
    match_b = (len(outs_b) == n_requests
               and all(np.array_equal(o.tokens, want[o.uid])
                       for o in outs_b))
    emit("page_2x_slots_pool_bytes", bytes_b,
         f"{2 * n_slots} paged slots vs {bytes_d} B dense "
         f"{n_slots}-slot pool, parity={match_b}")

    # prefix caching: 6 requests sharing a 62-token prompt stem (two full
    # 32-position pages under the 3-token cushion), divergent tails
    stem_reqs = poisson_trace(api, 1, 6, rate, (64,), (24,))
    t0 = np.asarray(stem_reqs[0].batch["tokens"])
    for r in stem_reqs[1:]:
        t = np.array(r.batch["tokens"])
        t[:, :62] = t0[:, :62]
        r.batch["tokens"] = jnp.asarray(t)
    dense.run(stem_reqs)                    # warm the new shapes
    outs_sd = dense.run(stem_reqs)
    pfx = ContinuousEngine(api, params, qcfg, n_slots=n_slots,
                           max_seq=max_seq, cushion=cushion, paged=True,
                           page_size=ps, n_pages=n_pages,
                           prefix_cache=True)
    pfx.run(stem_reqs)
    outs_sp = pfx.run(stem_reqs)
    hits, misses = pfx.stats.prefix_hits, pfx.stats.prefix_misses
    want_s = {o.uid: o.tokens for o in outs_sd}
    match_s = (len(outs_sd) == len(stem_reqs) == len(outs_sp)
               and all(np.array_equal(o.tokens, want_s[o.uid])
                       for o in outs_sp))
    emit("page_prefix_hits", hits * 1e6,
         f"misses={misses} parity={match_s}")

    tp_parity = None
    if tp > 1:
        from repro.launch.mesh import make_tp_mesh
        tpe = ContinuousEngine(api, params, qcfg, n_slots=n_slots,
                               max_seq=max_seq, cushion=cushion, paged=True,
                               page_size=ps, n_pages=n_pages,
                               mesh=make_tp_mesh(tp))
        outs_t, _ = run_engine(tpe)
        tp_parity = (len(outs_t) == n_requests
                     and all(np.array_equal(o.tokens, want[o.uid])
                             for o in outs_t))
        emit("page_tp_parity", float(tp_parity) * 1e6,
             f"tp={tp} paged tokens == dense tp=1 tokens")

    point = {"model": cfg.name, "tp": tp, "n_slots": n_slots,
             "n_requests": n_requests, "rate_req_s": rate,
             "prompt_lens": list(prompt_lens), "budgets": list(budgets),
             "max_seq": max_seq, "page_size": ps, "n_pages": n_pages,
             "parity_match": match,
             "pool_bytes_dense": bytes_d, "pool_bytes_paged": bytes_p,
             "pool_bytes_ratio": ratio,
             "tokens_per_s_dense": tps_d, "tokens_per_s_paged": tps_p,
             "tps_ratio": tps_p / tps_d,
             "slots_2x_fixed_memory": {
                 "n_slots": 2 * n_slots, "n_pages": 2 * n_pages,
                 "pool_bytes": bytes_b, "fits_dense_budget":
                     bool(bytes_b <= bytes_d), "parity_match": match_b},
             "prefix_cache": {"n_requests": len(stem_reqs),
                              "stem_tokens": 62, "hits": hits,
                              "misses": misses, "parity_match": match_s},
             "tp_parity": tp_parity,
             **{k: v for k, v in paged.stats.as_dict().items()
                if k.startswith(("pages_", "prefix_", "cushion_",
                                 "positions_"))}}
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "BENCH_pages.json"), "w") as f:
        json.dump({"bench": "pages", "points": [point]}, f, indent=1)
    if not match:
        raise SystemExit("paged pool diverged from the dense pool "
                         "(token parity oracle failed)")
    if ratio < 2.0:
        raise SystemExit(f"paged pool bytes not reduced >= 2x at matched "
                         f"slots: {bytes_d} -> {bytes_p} ({ratio:.2f}x)")
    if not (bytes_b <= bytes_d and match_b):
        raise SystemExit(
            f"2x-slot paged pool failed the fixed-memory gate: "
            f"{bytes_b} B vs dense {bytes_d} B, parity={match_b}")
    if not (match_s and hits >= 1):
        raise SystemExit(f"prefix cache gate failed: hits={hits} "
                         f"parity={match_s}")
    if tps_p < 0.8 * tps_d:
        raise SystemExit(f"paged tokens/s outside noise vs dense: "
                         f"{tps_p:.1f} vs {tps_d:.1f}")
    if tp > 1 and not tp_parity:
        raise SystemExit(f"tp={tp} paged serving diverged from the "
                         f"unsharded dense run")


def cushion_bench(tp: int = 1):
    """CushionCache stage-2 quality gate (``results/BENCH_cushion.json``):
    the full discover -> tune -> serve pipeline on paper_tiny with planted
    activation outliers, measured at three points — no cushion, greedy
    search only, gradient-tuned — and gated so the tuned artifact is never
    worse than what stage 1 already delivered:

    * last-block max-activation top-1 and held-out perplexity per variant;
      tuned must stay within 1.05x of greedy on both (from the greedy
      start, tuning optimizes CE + λ·range — it must not walk quality or
      the outlier suppression backwards)
    * W8A8 accuracy margin (pt_static true-int8 next-token accuracy minus
      fp accuracy), scales calibrated per cushion via ``calibrate_tagged``;
      tuned margin must hold within 0.05 of greedy's
    * the tuning loop's host syncs are counted and bounded at
      steps/log_every + 1 (the per-step-sync regression this pipeline
      fixed)
    * the tuned cushion round-trips through a versioned
      ``checkpoint.store`` artifact fingerprint-identically
    * the restored artifact serves token-for-token identically through the
      static Engine and the continuous scheduler, dense and paged (shared
      cushion block); ``tp > 1`` adds a tensor-parallel continuous run
      (replicated-per-shard cushion) against the same oracle."""
    import json
    import os
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.common import emit
    from repro import monitoring as MON
    from repro.checkpoint.store import CheckpointManager
    from repro.configs import CushionConfig, QuantConfig, get_config
    from repro.core import cushioncache as CC
    from repro.core import outliers as OUT
    from repro.core.calibration import calibrate_tagged
    from repro.launch.serve import poisson_trace
    from repro.models.registry import build
    from repro.serving.engine import Engine
    from repro.serving.scheduler import ContinuousEngine
    from repro.train.trainer import eval_next_token_acc, eval_ppl

    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    # plant the massive-activation pathway the paper mitigates (same
    # construction as tests/test_cushion.py)
    w = params["layers"]["mlp"]["w_down"]
    params["layers"]["mlp"]["w_down"] = w.at[0, :8, 5].set(300.0)

    qd = QuantConfig(mode="pt_dynamic")
    qn = QuantConfig(mode="none")
    qs = QuantConfig(mode="pt_static", true_int8=True)
    sample = lambda i: api.make_batch(jax.random.PRNGKey(100 + i), 1, 48)
    tune_b = lambda i: api.make_batch(jax.random.PRNGKey(3000 + i), 2, 48)
    eval_batches = [api.make_batch(jax.random.PRNGKey(7000 + i), 2, 48)
                    for i in range(4)]
    calib = [tune_b(100 + i) for i in range(2)]

    steps, log_every = 40, 10
    ccfg = CushionConfig(max_prefix_len=4, tau=1.0, n_candidates=16,
                         seed_tokens=(1,), lam=0.1, tune_steps=steps,
                         tune_lr=1e-3, log_every=log_every)
    greedy, sr, _ = CC.discover(api, params, sample, iter(()), qd, ccfg,
                                jax.random.PRNGKey(1), skip_tune=True,
                                verbose=False)

    def batches():
        i = 0
        while True:
            yield tune_b(i)
            i += 1

    with MON.count_host_syncs() as sync:
        tr = CC.prefix_tune(api, params, greedy, batches(), qd, ccfg,
                            verbose=False)
    tuned = tr.cushion

    from repro.models import transformer as TMOD
    variants = {"none": None, "greedy": greedy, "tuned": tuned}
    metrics = {}
    for name, c in variants.items():
        top1 = OUT.last_block_input_stats(api, params, eval_batches[0],
                                          qn, cushion=c)["top1"]
        ppl = eval_ppl(api, params, eval_batches, qn, cushion=c)
        # total per-site quantization error — the quantity the cushion
        # exists to reduce (paper Table 1's mechanism at CPU scale)
        _, taps = api.forward(params, eval_batches[0], qd, cushion=c,
                              collect=True)
        qerr = float(TMOD.total_qerr(taps))
        tagged, _ = calibrate_tagged(api, params, calib, qs, cushion=c)
        acc_fp = eval_next_token_acc(api, params, eval_batches, qn,
                                     cushion=c)
        acc_w8 = eval_next_token_acc(api, params, eval_batches, qs,
                                     cushion=c, scales=tagged.scales)
        metrics[name] = {"maxact_top1": top1, "ppl": ppl, "qerr": qerr,
                         "acc_fp": acc_fp, "acc_w8": acc_w8,
                         "w8a8_margin": acc_w8 - acc_fp}
        emit(f"cushion_{name}_qerr", qerr * 1e3,
             f"maxact={top1:.1f} ppl={ppl:.2f} "
             f"w8a8_margin={acc_w8 - acc_fp:+.4f}")

    # artifact round trip: the fingerprint survives save/restore
    fp = CC.cushion_fingerprint(tuned)
    with tempfile.TemporaryDirectory() as td:
        store = CheckpointManager(td)
        store.save(1, {"cushion": tuned},
                   extra={"kind": "cushion", "fingerprint": fp})
        tree, _ = store.restore_tree(1)
        restored = jax.tree_util.tree_map(jnp.asarray, tree["cushion"])
    roundtrip_ok = CC.cushion_fingerprint(restored) == fp

    # serving parity on the restored artifact: Engine is the oracle
    reqs = poisson_trace(api, 0, 6, 60.0, (20, 26), (5, 3))
    eng = Engine(api, params, qn, cushion=restored, max_seq=128)
    want = {r.uid: eng.generate(r.batch, r.max_new_tokens).tokens[0]
            for r in reqs}

    def parity(**kw):
        ce = ContinuousEngine(api, params, qn, n_slots=2, max_seq=128,
                              cushion=restored, **kw)
        outs = ce.run(reqs)
        return (len(outs) == len(reqs)
                and all(np.array_equal(o.tokens, want[o.uid])
                        for o in outs))

    par = {"dense": parity(), "paged": parity(paged=True, page_size=32)}
    if tp > 1:
        from repro.launch.mesh import make_tp_mesh
        par[f"tp{tp}"] = parity(mesh=make_tp_mesh(tp))
    emit("cushion_serving_parity",
         float(all(par.values())) * 1e6, str(par))

    sync_bound = steps // log_every + 1
    point = {"model": cfg.name, "tp": tp,
             "prefix_ids": [int(t) for t in sr.prefix_ids],
             "tune_steps": steps, "tune_lr": ccfg.tune_lr,
             "lam": ccfg.lam, "log_every": log_every,
             "tune_host_syncs": sync.count,
             "tune_host_sync_bound": sync_bound,
             "tune_wall_s": tr.wall_time_s,
             "fingerprint": fp, "artifact_roundtrip": roundtrip_ok,
             "metrics": metrics, "serving_parity": par}
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "BENCH_cushion.json"), "w") as f:
        json.dump({"bench": "cushion", "points": [point]}, f, indent=1,
                  default=float)

    g, t = metrics["greedy"], metrics["tuned"]
    if sync.count > sync_bound:
        raise SystemExit(f"tuning host-synced {sync.count}x, bound is "
                         f"{sync_bound} (per-step sync regression)")
    if t["maxact_top1"] > 1.05 * g["maxact_top1"]:
        raise SystemExit(f"tuned max-activation regressed vs greedy: "
                         f"{t['maxact_top1']:.1f} vs {g['maxact_top1']:.1f}")
    if t["ppl"] > 1.05 * g["ppl"]:
        raise SystemExit(f"tuned perplexity regressed vs greedy: "
                         f"{t['ppl']:.2f} vs {g['ppl']:.2f}")
    if t["qerr"] >= metrics["none"]["qerr"]:
        raise SystemExit(f"tuned cushion does not reduce quantization "
                         f"error vs no cushion: {t['qerr']:.2f} vs "
                         f"{metrics['none']['qerr']:.2f}")
    if t["qerr"] > 1.05 * g["qerr"]:
        raise SystemExit(f"tuned qerr regressed vs greedy: "
                         f"{t['qerr']:.2f} vs {g['qerr']:.2f}")
    if t["w8a8_margin"] < g["w8a8_margin"] - 0.05:
        raise SystemExit(f"tuned W8A8 accuracy margin collapsed: "
                         f"{t['w8a8_margin']:+.4f} vs greedy "
                         f"{g['w8a8_margin']:+.4f}")
    if not roundtrip_ok:
        raise SystemExit("tuned cushion artifact did not round-trip "
                         "fingerprint-identically")
    if not all(par.values()):
        raise SystemExit(f"tuned-cushion serving parity failed: {par}")


EXTRA_BENCHES = {"kernel_microbench": kernel_microbench,
                 "decode_bench": decode_bench,
                 "search_bench": search_bench,
                 "serve_bench": serve_bench,
                 "w8a8_bench": w8a8_bench,
                 "w4a8_bench": w4a8_bench,
                 "router_bench": router_bench,
                 "page_bench": page_bench,
                 "cushion_bench": cushion_bench}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single bench/table function by name")
    ap.add_argument("--skip-paper", action="store_true",
                    help="kernel microbenches only (fast)")
    ap.add_argument("--tp", type=int, default=1,
                    help="serve_bench/page_bench: tensor-parallel width "
                         "(forces that many XLA host devices on CPU; "
                         "serve_bench emits results/BENCH_tp.json instead "
                         "of BENCH_serve.json; page_bench adds the tp "
                         "paged-parity gate to BENCH_pages.json)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="router_bench only: replica count behind the "
                         "fault-tolerant router")
    args = ap.parse_args()

    # must land before the lazy `import jax` inside the bench fns
    from repro.flags import force_host_device_count
    force_host_device_count(args.tp)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    if args.only in EXTRA_BENCHES:
        kw = {}
        if args.only in ("serve_bench", "page_bench", "cushion_bench",
                         "w4a8_bench"):
            kw = {"tp": args.tp}
        elif args.only == "router_bench":
            kw = {"replicas": args.replicas}
        EXTRA_BENCHES[args.only](**kw)
        return
    kernel_microbench()
    if args.skip_paper:
        return
    if not args.only:
        decode_bench()
        search_bench()
        w8a8_bench()
    from benchmarks import paper_tables as PT
    fns = PT.ALL
    if args.only:
        fns = [f for f in PT.ALL if f.__name__ == args.only]
    failed = []
    for fn in fns:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report every table, then fail
            from benchmarks.common import emit
            emit(fn.__name__, 0.0, f"ERROR {type(e).__name__}: {e}")
            failed.append(fn.__name__)
    if failed:
        raise SystemExit(f"{len(failed)} bench function(s) failed: "
                         f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
