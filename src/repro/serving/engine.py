"""Serving engine: batched prefill + decode with a CushionCache prefix and
configurable quantized execution (the paper's deployment story — per-tensor
*static* W8A8 is the fastest mode and the one CushionCache rescues).

The generation loop is device-resident: decode runs as one jitted
``lax.scan`` over the requested token budget, with greedy/categorical
sampling under the scan and the token trajectory accumulated on device.
The host syncs exactly twice per request — once after prefill (TTFT) and
once after the whole scan (TPOT) — instead of once per generated token.
``generate_py`` keeps the legacy per-token host loop as the A/B baseline
for the decode benchmarks.

KV cache precision is selectable (``kv_dtype="int8"`` halves decode HBM
traffic, the dominant roofline term at generation time); the cushion/sink
prefix block always stays full-precision (KVSink/IntactKV rule).

Latency accounting (TTFT/TPOT) feeds the Table-8 benchmark.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import QuantConfig
from repro.core import quantization as Q
from repro.core.calibration import CalibratedScales
from repro.core.cushioncache import cushion_fingerprint
from repro.distributed import sharding as SH
from repro.models.registry import ModelAPI
from repro.monitoring import resident_weight_bytes


def shard_params_for_serving(params, mesh):
    """Lay params out for inference on a tp mesh: TP-only serve rules
    (weights replicated over data/pod axes — FSDP sharding would all-gather
    every weight per decoded token). Prequantized {w_int | w_packed,
    w_scale, colsum} leaves ride the same rules: the int weight shards like
    its fp parent, colsum follows the parent's output axis, scales
    replicate (sharding.rules_pspec)."""
    return jax.device_put(
        params, SH.params_shardings(params, mesh, SH.serve_rules()))


def plan_quantization(api, params, qcfg: QuantConfig, cushion=None,
                      scales=None, calib_batches=None,
                      prequant: bool = False, weight_bits: int = 8):
    """Load-time quantization plan shared by ``Engine`` and
    ``ContinuousEngine``. Returns (params, scales) ready to serve:

    * ``pt_static`` with no precomputed ``scales`` calibrates them here via
      ``core.calibration.calibrate`` over ``calib_batches`` — under the
      cushion prefix when one is attached, because static scales must
      describe the *deployment* activation distribution (the cushioned
      one). Refuses to proceed with neither scales nor calibration data:
      serving pt_static on placeholder scales silently produces garbage
      logits, the exact failure this path exists to prevent.
    * ``prequant`` converts every qdot-consumed weight matrix to an
      int8-resident {w_int, w_scale, colsum} dict
      (``core.quantization.prequantize_tree``) so decode streams
      1 byte/weight; requires the pt_static deployment mode. The fp-weight
      path (prequant=False) stays available as the A/B baseline.
    * precomputed ``scales`` carrying cushion provenance
      (``core.calibration.CalibratedScales`` — `calibrate_tagged`, tune
      artifacts) are fingerprint-checked against the cushion actually
      being served and REJECTED on mismatch. A tuned cushion shifts the
      activation distribution the static ranges were fit to; serving the
      stale pair produces silently-wrong ranges, so the plan hard-fails
      and demands recalibration (or the matching artifact) instead.
    """
    if isinstance(scales, CalibratedScales):
        want, got = scales.cushion_fp, cushion_fingerprint(cushion)
        if want != got:
            raise ValueError(
                f"stale pt_static scales: calibrated under cushion "
                f"{want[:12]} but asked to serve cushion {got[:12]}; "
                f"recalibrate under the serving cushion (pass "
                f"calib_batches=) or load the matching tune artifact — "
                f"refusing to serve mismatched static ranges")
        scales = scales.scales
    if qcfg.mode == "pt_static" and scales is None:
        if calib_batches is None:
            raise ValueError(
                "pt_static serving needs calibrated site scales: pass "
                "scales= (core.calibration.calibrate) or calib_batches= "
                "to calibrate at engine load; refusing to serve on "
                "placeholder scales (silent garbage logits)")
        from repro.core.calibration import calibrate
        scales, _ = calibrate(api, params, calib_batches, qcfg,
                              cushion=cushion)
    if weight_bits not in (8, 4):
        raise ValueError(f"weight_bits must be 8 or 4, got {weight_bits}")
    if weight_bits == 4 and not prequant:
        raise ValueError(
            "weight_bits=4 is the int4-packed resident format and only "
            "exists prequantized; pass prequant=True (fp and W8A8 remain "
            "the A/B baselines)")
    if prequant:
        if qcfg.mode != "pt_static":
            raise ValueError(
                f"prequant (int8-resident weights) serves the pt_static "
                f"deployment mode only, got mode={qcfg.mode!r}")
        params = Q.prequantize_tree(params, qcfg, weight_bits=weight_bits)
    return params, scales


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, n_gen)
    ttft_ms: float
    tpot_ms: float


def cache_seq_len(max_seq: int) -> int:
    """Round a KV-cache length up to a multiple of 128 so the decode
    kernel's KV chunking divides it evenly (a ragged tail would cost a full
    cache copy per decode step). Shared by the static Engine and the
    continuous-batching pool — the invariant lives here."""
    return -(-max_seq // 128) * 128


def cushion_prefix_len(cushion) -> int:
    """Length m of the cushion/sink prefix block in a cushion artifact
    (0 when absent or stateless)."""
    if cushion is not None and "kv" in cushion:
        return int(cushion["kv"]["k"].shape[1])
    return 0


def bucket_steps(n_steps: int) -> int:
    """Round a decode-step budget up to the next power of two (min 8).

    The scanned generation loop compiles one executable per distinct step
    count; bucketing maps a varying-budget frontend onto a handful of
    executables instead of one per request size. The surplus steps run and
    are sliced away — scan steps are sequential, so the first ``n_steps``
    outputs are unaffected (cache writes past ``max_seq`` clamp into the
    last row, which only ever corrupts positions read by the discarded
    surplus steps)."""
    if n_steps <= 0:
        return 0
    b = 8
    while b < n_steps:
        b *= 2
    return b


class Engine:
    """Holds compiled prefill/decode executables for one (model, quant,
    cushion, kv_dtype) configuration.

    ``calib_batches`` / ``prequant``: the load-time quantization plan
    (``plan_quantization``). For pt_static serving, site scales are
    calibrated here (under the cushion prefix) unless precomputed ones are
    passed; ``prequant=True`` additionally converts qdot-consumed weights
    to int8-resident {w_int, w_scale, colsum} dicts so decode streams
    1 byte/weight through the W8A8 matmul path — or, with
    ``weight_bits=4``, to int4-packed {w_packed, w_scale, colsum} dicts
    (0.5 byte/weight, W4A8). ``weight_bytes_fp`` / ``weight_bytes_int8`` /
    ``weight_bytes_int4`` report the resulting resident layout.

    ``mesh``: optional tp mesh (launch/mesh.py ``make_tp_mesh``). When set,
    params are laid out with the TP-only serve rules, the KV cache shards
    along its heads axis (models/*.cache_roles), and prefill/decode trace
    under the mesh so the ``constrain`` hints in model code bind — the
    whole generation loop then runs as sharding-constrained jit with the
    pool resident across devices (no per-step host transfer; same
    compile-once/donation properties as the single-device path)."""

    def __init__(self, api: ModelAPI, params, qcfg: QuantConfig,
                 cushion=None, scales=None, max_seq: int = 2048,
                 kv_dtype=None, mesh=None, calib_batches=None,
                 prequant: bool = False, weight_bits: int = 8):
        self.api = api
        self.mesh = mesh
        params, scales = plan_quantization(
            api, params, qcfg, cushion=cushion, scales=scales,
            calib_batches=calib_batches, prequant=prequant,
            weight_bits=weight_bits)
        self.params = (shard_params_for_serving(params, mesh)
                       if mesh is not None else params)
        (self.weight_bytes_fp, self.weight_bytes_int8,
         self.weight_bytes_int4) = resident_weight_bytes(self.params)
        self.qcfg = qcfg
        self.cushion = cushion
        self.scales = scales
        self.max_seq = cache_seq_len(max_seq)
        self.kv_dtype = kv_dtype
        self.prefix_len = cushion_prefix_len(cushion)
        # served-cushion provenance, for logs and artifact cross-checks
        self.cushion_fp = cushion_fingerprint(cushion)
        # named step functions: the names label the compiled programs
        def prefill(p, b, c):
            return api.prefill(p, b, c, qcfg, cushion=cushion, scales=scales)

        def decode(p, t, pos, c):
            return api.decode_step(p, t, pos, c, qcfg, scales=scales)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode)

        def gen_loop(p, tok0, pos0, cache, rng, n_steps: int, greedy: bool):
            def step(carry, _):
                tok, pos, cache, rng = carry
                logits, cache = api.decode_step(p, tok, pos, cache, qcfg,
                                                scales=scales)
                if greedy:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    rng, k = jax.random.split(rng)
                    nxt = jax.random.categorical(k, logits).astype(jnp.int32)
                return (nxt, pos + 1, cache, rng), nxt

            carry, toks = jax.lax.scan(step, (tok0, pos0, cache, rng),
                                       None, length=n_steps)
            return jnp.concatenate([tok0[None], toks], axis=0)

        # n_steps/greedy are static: each distinct scan length compiles its
        # own executable. `generate` buckets the requested budget
        # (bucket_steps) so a varying-budget frontend compiles one scan per
        # bucket, not per request size.
        self._gen_loop = jax.jit(gen_loop, static_argnums=(5, 6))

    def _init_cache(self, batch: int):
        cache = self.api.init_cache(batch, self.max_seq,
                                    kv_dtype=self.kv_dtype,
                                    prefix_len=self.prefix_len)
        if self.mesh is not None:
            cache = jax.device_put(cache, SH.cache_shardings(
                self.api.cache_roles(self.kv_dtype), cache, self.mesh))
        return cache

    def _run_prefill(self, batch: Dict[str, Any]):
        """Prefill + first token. Returns (tok, pos, cache, ttft_ms)."""
        B = batch["tokens"].shape[0]
        with SH.use_mesh(self.mesh):
            cache = self._init_cache(B)
            t0 = time.perf_counter()
            logits, cache, pos = self._prefill(self.params, batch, cache)
            logits = logits[:, -1] if logits.ndim == 3 else logits
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok.block_until_ready()
        return tok, pos, cache, (time.perf_counter() - t0) * 1e3

    def generate(self, batch: Dict[str, Any], n_tokens: int,
                 greedy: bool = True, rng=None) -> GenerationResult:
        tok, pos, cache, ttft = self._run_prefill(batch)
        t1 = time.perf_counter()
        g = bool(greedy or rng is None)
        key = rng if rng is not None else jax.random.PRNGKey(0)
        n_steps = max(0, n_tokens - 1)
        # bucketed scan length: requests in the same bucket share one
        # compiled executable; surplus steps are sliced away below.
        with SH.use_mesh(self.mesh):
            toks = self._gen_loop(self.params, tok, pos, cache, key,
                                  bucket_steps(n_steps), g)
        if toks.shape[0] > 1 + n_steps:
            toks = toks[:1 + n_steps]
        toks.block_until_ready()    # single host sync for the whole loop
        # tpot charges the (bucket-padded) loop to the *delivered* tokens —
        # honest latency per useful token, slightly pessimistic off-bucket.
        # A <=1-token request has no "per subsequent token" latency: report
        # 0.0 instead of the 0-step scan's dispatch overhead.
        tpot = (0.0 if n_tokens <= 1
                else (time.perf_counter() - t1) * 1e3 / (n_tokens - 1))
        return GenerationResult(tokens=np.asarray(toks).T, ttft_ms=ttft,
                                tpot_ms=tpot)

    def generate_py(self, batch: Dict[str, Any], n_tokens: int,
                    greedy: bool = True, rng=None) -> GenerationResult:
        """Legacy per-token host loop (one device->host sync per token);
        kept as the reference/baseline for the decode benchmarks and the
        scan-equivalence tests."""
        tok, pos, cache, ttft = self._run_prefill(batch)
        out = [np.asarray(tok)]
        t1 = time.perf_counter()
        with SH.use_mesh(self.mesh):
            for _ in range(n_tokens - 1):
                logits, cache = self._decode(self.params, tok, pos, cache)
                if greedy or rng is None:
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    rng, k = jax.random.split(rng)
                    tok = jax.random.categorical(k, logits).astype(jnp.int32)
                pos = pos + 1
                out.append(np.asarray(tok))
        jax.block_until_ready(tok)
        tpot = (0.0 if n_tokens <= 1
                else (time.perf_counter() - t1) * 1e3 / (n_tokens - 1))
        return GenerationResult(tokens=np.stack(out, 1), ttft_ms=ttft,
                                tpot_ms=tpot)
