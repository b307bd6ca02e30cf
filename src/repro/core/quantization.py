"""Quantization core (paper §3).

Implements linear (affine) quantization with the paper's configuration space:

* activations: asymmetric, with three granularities
    - ``pt_static``      per-tensor, static range (calibrated scales)
    - ``pt_dynamic``     per-tensor, range computed on the fly
    - ``ptoken_dynamic`` per-token, range computed on the fly
* weights: symmetric group-wise (group along the contracting dim)

Two execution paths:

* **fake-quant** (quantize->dequantize in float, straight-through gradients):
  used for fidelity experiments, calibration, the greedy search and the
  quantization-aware prefix tuning.
* **true-int8** (``lax.dot_general`` on int8 with ``preferred_element_type=
  int32`` and a fused scalar epilogue): the deployment/serving path, also
  what the Pallas ``w8a8_matmul`` kernel implements on TPU.

All functions are pure; static ranges live in a ``scales`` pytree threaded
through the model forward.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig

Array = jax.Array


# ---------------------------------------------------------------------------
# Quantization parameters (scale / zero-point), eq. (3)-(4)
# ---------------------------------------------------------------------------

def qrange(bits: int, symmetric: bool) -> Tuple[int, int]:
    if symmetric:
        return -(2 ** (bits - 1) - 1), 2 ** (bits - 1) - 1
    return 0, 2 ** bits - 1


def params_from_minmax(mn: Array, mx: Array, bits: int, symmetric: bool
                       ) -> Tuple[Array, Array]:
    """scale, zero_point from observed (min, max). Shapes broadcast."""
    qmin, qmax = qrange(bits, symmetric)
    if symmetric:
        amax = jnp.maximum(jnp.abs(mn), jnp.abs(mx))
        scale = amax / qmax
        zero = jnp.zeros_like(scale)
    else:
        mn = jnp.minimum(mn, 0.0)
        mx = jnp.maximum(mx, 0.0)
        scale = (mx - mn) / (qmax - qmin)
        zero = qmin - mn / jnp.where(scale == 0, 1.0, scale)
        zero = jnp.round(jnp.clip(zero, qmin, qmax))
    scale = jnp.where(scale <= 0, 1.0, scale)
    return scale, zero


def quantize(x: Array, scale: Array, zero: Array, bits: int,
             symmetric: bool) -> Array:
    qmin, qmax = qrange(bits, symmetric)
    return jnp.clip(jnp.round(x / scale + zero), qmin, qmax)


def dequantize(xq: Array, scale: Array, zero: Array) -> Array:
    return (xq - zero) * scale


def fake_quant(x: Array, scale: Array, zero: Array, bits: int,
               symmetric: bool) -> Array:
    """Quantize->dequantize with straight-through gradient (the rounding is
    invisible to autodiff; scale/zero receive no gradient — the paper's
    stop-grad on quantizer parameters)."""
    scale = jax.lax.stop_gradient(scale)
    zero = jax.lax.stop_gradient(zero)
    y = dequantize(quantize(x, scale, zero, bits, symmetric), scale, zero)
    y = y.astype(x.dtype)     # fp32 scales must not promote bf16 activations
    return x + jax.lax.stop_gradient(y - x)


# ---------------------------------------------------------------------------
# Activation quantization per granularity
# ---------------------------------------------------------------------------

def act_minmax(x: Array, per_token: bool) -> Tuple[Array, Array]:
    if per_token:
        mn = jnp.min(x, axis=-1, keepdims=True)
        mx = jnp.max(x, axis=-1, keepdims=True)
    else:
        mn = jnp.min(x)
        mx = jnp.max(x)
    return mn, mx


def act_fake_quant(x: Array, cfg: QuantConfig,
                   static_scale: Optional[Array] = None,
                   static_zero: Optional[Array] = None) -> Array:
    """Apply the configured activation quantizer (fake-quant path)."""
    if cfg.mode == "none":
        return x
    if cfg.mode == "pt_static":
        assert static_scale is not None, "static mode needs calibrated scales"
        return fake_quant(x, static_scale, static_zero, cfg.a_bits,
                          cfg.symmetric_a)
    per_token = cfg.mode == "ptoken_dynamic"
    mn, mx = act_minmax(jax.lax.stop_gradient(x), per_token)
    scale, zero = params_from_minmax(mn, mx, cfg.a_bits, cfg.symmetric_a)
    return fake_quant(x, scale, zero, cfg.a_bits, cfg.symmetric_a)


# ---------------------------------------------------------------------------
# Weight quantization: symmetric, group-wise along contracting dim
# ---------------------------------------------------------------------------

def weight_fake_quant(w: Array, cfg: QuantConfig) -> Array:
    """w: (..., d_in, d_out); groups tile the d_in (contracting) axis."""
    if cfg.mode == "none" and not cfg.true_int8:
        return w
    if cfg.w_bits >= 16:
        return w
    d_in = w.shape[-2]
    g = cfg.w_group if cfg.w_group and d_in % cfg.w_group == 0 else d_in
    shp = w.shape
    wg = w.reshape(*shp[:-2], d_in // g, g, shp[-1])
    amax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)
    scale, zero = params_from_minmax(-amax, amax, cfg.w_bits, True)
    wq = fake_quant(wg, scale, zero, cfg.w_bits, True)
    return wq.reshape(shp)


def weight_quant_int(w: Array, cfg: QuantConfig) -> Tuple[Array, Array]:
    """True-int path needs a single per-tensor weight scale so the dequant is
    one scalar multiply in the matmul epilogue (per-tensor static deployment).
    Returns (w_int8, scale).

    Sub-8-bit range convention: every quantizer here goes through ``qrange``,
    whose symmetric range is *restricted* — [-(2^(b-1)-1), 2^(b-1)-1], i.e.
    [-7, 7] at 4 bits, never the full two's-complement [-8, 7]. The int4
    packed format stores nibbles that could hold -8, but the quantizers never
    emit it; tests/test_quantization.py pins this so fake-quant calibration
    and true packed inference stay on the same grid."""
    amax = jnp.max(jnp.abs(w))
    scale, _ = params_from_minmax(-amax, amax, cfg.w_bits, True)
    wq = quantize(w, scale, jnp.zeros(()), cfg.w_bits, True).astype(jnp.int8)
    return wq, scale


def weight_quant_int4(w: Array, cfg: QuantConfig
                      ) -> Tuple[Array, Array, int]:
    """Group-wise symmetric int4 weight quantization (the W4A8 true path).

    Unlike ``weight_quant_int`` (per-tensor — fine at 8 bits), 4-bit needs
    the *same group-wise scales as* ``weight_fake_quant``: a single
    per-tensor scale loses too much range, and — the satellite-1 fix — a
    granularity mismatch between calibration (fake-quant, group-wise) and
    serving (true packed) would make the two paths disagree. Using the
    identical group/amax/scale computation makes
    ``dequant(unpack(pack(wq))) == weight_fake_quant(w)`` bit-for-bit.

    w: (d_in, d_out). Returns (wq, scale, group_size) with wq (d_in, d_out)
    int8 holding values in the restricted [-7, 7] range and scale
    (n_groups, d_out) fp32. Groups tile d_in; ``cfg.w_group`` is used when
    it divides d_in, else one group spans the whole axis (mirroring
    ``weight_fake_quant``)."""
    d_in, d_out = w.shape
    g = cfg.w_group if cfg.w_group and d_in % cfg.w_group == 0 else d_in
    wg = w.reshape(d_in // g, g, d_out)
    amax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)        # (G,1,N)
    scale, zero = params_from_minmax(-amax, amax, 4, True)
    wq = quantize(wg, scale, zero, 4, True).astype(jnp.int8)
    return wq.reshape(d_in, d_out), scale[:, 0, :], g


# ---------------------------------------------------------------------------
# int4 packing: two nibbles per byte along the contracting dim
# ---------------------------------------------------------------------------

def pack_int4(wq: Array) -> Array:
    """Pack int4 values (int8 storage, [-8, 7]) along axis 0, two per byte:
    element 2i lands in the LOW nibble of byte i, element 2i+1 in the HIGH
    nibble (interleaved layout — unpack is a stack+reshape, no shuffle).
    Odd-length axes get a zero nibble of padding; ``unpack_int4(p, k)``
    slices it back off. Returns int8 of shape (ceil(K/2), ...)."""
    K = wq.shape[0]
    if K % 2:
        wq = jnp.pad(wq, [(0, 1)] + [(0, 0)] * (wq.ndim - 1))
    lo = jax.lax.bitcast_convert_type(wq[0::2], jnp.uint8) & 0xF
    hi = jax.lax.bitcast_convert_type(wq[1::2], jnp.uint8) & 0xF
    return jax.lax.bitcast_convert_type(lo | (hi << 4), jnp.int8)


def unpack_int4(packed: Array, k: int) -> Array:
    """Inverse of ``pack_int4``: (ceil(k/2), ...) int8 -> (k, ...) int8 with
    sign-extended nibbles. Arithmetic shifts in int32 recover both nibbles:
    the low one via sign-extension from bit 3, the high one via
    floor-division (arithmetic >> 4 of the two's-complement byte)."""
    p = packed.astype(jnp.int32)
    lo = (p << 28) >> 28
    hi = p >> 4
    w = jnp.stack([lo, hi], axis=1)                  # (Kp, 2, ...)
    w = w.reshape(w.shape[0] * 2, *packed.shape[1:])
    return w[:k].astype(jnp.int8)


# ---------------------------------------------------------------------------
# Quantized linear
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SiteScale:
    """Calibrated static range for one activation site (pytree)."""
    scale: Array
    zero: Array


jax.tree_util.register_pytree_node(
    SiteScale,
    lambda s: ((s.scale, s.zero), None),
    lambda _, c: SiteScale(*c),
)


def _use_w8a8_kernel() -> bool:
    """Route int8 matmuls through the Pallas ``w8a8_matmul`` kernel? "auto"
    enables it on TPU backends only (lax.dot_general is the CPU oracle);
    "pallas" forces interpret-mode execution off-TPU (validation)."""
    from repro import flags
    if flags.W8A8_KERNEL == "pallas":
        return True
    if flags.W8A8_KERNEL == "jnp":
        return False
    return jax.default_backend() == "tpu"


def _use_w4a8_kernel() -> bool:
    """Same routing contract for the int4-packed ``w4a8_matmul`` kernel
    (REPRO_W4A8_KERNEL=auto|pallas|jnp)."""
    from repro import flags
    if flags.W4A8_KERNEL == "pallas":
        return True
    if flags.W4A8_KERNEL == "jnp":
        return False
    return jax.default_backend() == "tpu"


def _tile(n: int, target: int) -> int:
    """Block for a static weight dim: the largest power-of-two multiple of
    128 <= target that divides n, else n itself (a full-dim block is always
    legal for the TPU compiler; a 64-wide one is not)."""
    t = target
    while t >= 128:
        if n % t == 0:
            return t
        t //= 2
    return n


def _tp_mesh():
    """The serving tensor-parallel mesh active around this trace, if its
    ``tp`` axis is wider than one device. Pallas kernels cannot be
    partitioned by the compiler, so the kernel routes shard_map themselves
    over it."""
    from repro.distributed.sharding import active_mesh
    mesh = active_mesh()
    if mesh is None or "tp" not in mesh.axis_names or mesh.shape["tp"] == 1:
        return None
    return mesh


def _tp_kernel(mesh, fn, x, w, cols, rest, row_parallel: bool,
               row_partial=None, rows=()):
    """Run the 2-D matmul kernel ``fn(x, w, *cols, *rest)`` per tp shard.

    Column-parallel weights (output dim sharded: the serve rules for every
    weight that reads the residual stream) give each shard its output
    columns; ``cols`` are per-output-column operands sharded alongside.
    Row-parallel weights (contraction dim sharded: ``wo``, ``w_down``, ...)
    run ``row_partial(x, w, *rows, *rest)`` per shard, with ``rows`` split
    along their first dim like the weight, and psum the partials.
    ``rest`` is replicated. A dim that does not divide the mesh falls back
    to one replicated call per device (still ``row_partial`` for a
    row-parallel weight, so the caller's epilogue is the same)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import shard_map
    tp = mesh.shape["tp"]
    rep = tuple(P() for _ in rest)
    if row_parallel:
        args = (x, w) + tuple(rows) + tuple(rest)
        if all(a.shape[0] % tp == 0 for a in (x.T, w) + tuple(rows)):
            return shard_map(
                lambda *a: jax.lax.psum(row_partial(*a), "tp"), mesh,
                in_specs=(P(None, "tp"), P("tp", None))
                + tuple(P("tp", None) for _ in rows) + rep,
                out_specs=P())(*args)
        return shard_map(row_partial, mesh, in_specs=(P(),) * len(args),
                         out_specs=P())(*args)
    args = (x, w) + tuple(cols) + tuple(rest)
    if w.shape[1] % tp == 0:
        col_specs = tuple(P(*([None] * (c.ndim - 1)), "tp") for c in cols)
        return shard_map(fn, mesh,
                         in_specs=(P(), P(None, "tp")) + col_specs + rep,
                         out_specs=P(None, "tp"))(*args)
    return shard_map(fn, mesh, in_specs=(P(),) * len(args),
                     out_specs=P())(*args)


_F32_EXACT_K = 1024  # 1024 * 128 * 128 == 2**24: f32 partial sums stay exact


def _int_product_f32_exact(xq: Array, w_int: Array) -> Array:
    """Bit-exact int8 x int8 -> int32 product for CPU backends.

    XLA:CPU scalarizes int8 ``dot_general`` (no int8 GEMM in Eigen), which
    made prequantized *prefill* ~4x slower than fp on the CPU bench. Casting
    to f32 routes the product through the vectorized f32 GEMM instead, and
    chunking the contraction at ``_F32_EXACT_K`` keeps it exact: every
    partial sum is bounded by 1024*128*128 = 2**24, the largest integer
    magnitude f32 represents exactly, so each chunk's f32 accumulation is
    integer-exact and the int32 chunk sum matches the int32 dot bit for
    bit."""
    K = w_int.shape[0]
    cdim = xq.ndim - 1
    xf = xq.astype(jnp.float32)
    wf = w_int.astype(jnp.float32)
    acc = None
    for k0 in range(0, K, _F32_EXACT_K):
        k1 = min(k0 + _F32_EXACT_K, K)
        part = jax.lax.dot_general(
            jax.lax.slice_in_dim(xf, k0, k1, axis=cdim),
            jax.lax.slice_in_dim(wf, k0, k1, axis=0),
            (((cdim,), (0,)), ((), ()))).astype(jnp.int32)
        acc = part if acc is None else acc + part
    return acc


def _int8_matmul(xq: Array, w_int: Array, s_x, z_x, s_w,
                 colsum: Array, out_dtype, row_parallel: bool = False
                 ) -> Array:
    """Shared int8 x int8 epilogue-fused matmul behind ``true_int_dot`` and
    ``prequantized_int_dot``:

      (X_int - z) @ W_int * s_x s_w
        = (X_int @ W_int) * s_x s_w  -  z * colsum(W_int) * s_x s_w

    colsum(W_int) is precomputable per weight; it folds into one rank-1
    subtract (cheap, fuses). On TPU (or with REPRO_W8A8_KERNEL=pallas) the
    whole product+epilogue runs in the Pallas ``w8a8_matmul`` kernel —
    int8 MXU tiles with the scalar dequant fused in the kernel epilogue and
    ragged M padded/sliced inside the kernel wrapper — so every 2-D
    ``qlinear`` site (prefill *and* the jitted decode scan) hits the
    MXU-int8 fast path. Scalar (per-tensor static) scales only.

    Under a tp mesh the kernel is shard_mapped (``_tp_kernel``):
    ``row_parallel`` says the weight's contraction dim is the sharded one.
    Row-parallel shards return raw int32 partials, psum them exactly, and
    apply the epilogue once — the same values as the unsharded kernel."""
    if _use_w8a8_kernel() and w_int.ndim == 2 and jnp.ndim(s_x) == 0:
        from repro.kernels.w8a8_matmul import w8a8_matmul
        interpret = jax.default_backend() != "tpu"
        lead = xq.shape[:-1]
        x2 = xq.reshape(-1, xq.shape[-1])

        def run(x, w, cs, s_x, z_x, s_w, epilogue=True):
            K, N = w.shape
            return w8a8_matmul(x, w, s_x, z_x, s_w, colsum=cs, bm=256,
                               bn=_tile(N, 512), bk=_tile(K, 256),
                               epilogue=epilogue, interpret=interpret)

        scalars = tuple(jnp.asarray(v, jnp.float32) for v in (s_x, z_x, s_w))
        mesh = _tp_mesh()
        if mesh is None:
            out = run(x2, w_int, colsum, *scalars)
        elif row_parallel:
            acc = _tp_kernel(
                mesh, run, x2, w_int, (colsum,), scalars, True,
                row_partial=lambda x, w, *sc: run(x, w, None, *sc,
                                                  epilogue=False))
            out = (acc.astype(jnp.float32) - scalars[1]
                   * colsum.astype(jnp.float32)) * (scalars[0] * scalars[2])
        else:
            out = _tp_kernel(mesh, run, x2, w_int, (colsum,), scalars, False)
        return out.reshape(*lead, -1).astype(out_dtype)
    if jax.default_backend() != "tpu":
        acc = _int_product_f32_exact(xq, w_int)
    else:
        acc = jax.lax.dot_general(
            xq, w_int, (((xq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    acc = acc.astype(jnp.float32) - jnp.asarray(z_x, jnp.float32) \
        * colsum.astype(jnp.float32)
    return (acc * (jnp.asarray(s_x, jnp.float32)
                   * jnp.asarray(s_w, jnp.float32))).astype(out_dtype)


def _int4_matmul(xq: Array, w_packed: Array, s_x, z_x, s_w,
                 colsum: Array, out_dtype, row_parallel: bool = False
                 ) -> Array:
    """int8 activations x int4-packed weights with group-wise weight scales:

      out = s_x * ( sum_g s_w[g,:] * (X_int[:, g] @ W_int[g, :])
                    - z_x * colsum_scaled )

    where g ranges over contiguous groups of the contracting dim and
    ``colsum_scaled[n] = sum_g s_w[g,n] * colsum_g[n]`` is precomputed at
    prequantize time (the zero-point correction already carries the group
    scales, so the epilogue stays one rank-1 subtract exactly like W8A8).

    On TPU (or REPRO_W4A8_KERNEL=pallas) the unpack + product + epilogue run
    in the Pallas ``w4a8_matmul`` kernel — nibbles stream HBM->VMEM at
    0.5 byte/weight and are sign-extended in VMEM. The jnp fallback unpacks,
    folds the group scales into the weight columns once per call, and runs a
    single f32 GEMM — the same product shape as the W8A8 CPU path, so
    prefill TTFT stays in the fp ballpark (a grouped batched einsum was
    ~1.6x fp on the bench). Folding trades the grouped path's integer
    exactness for one extra f32 rounding per weight element (~1e-7
    relative); the kernel accumulates per-group like the grouped form, and
    the two routes agree to f32-accumulation tolerance, not bit-identically.
    Under a tp mesh the kernel is shard_mapped like ``_int8_matmul``;
    row-parallel shards psum f32 partials (group scales apply per shard),
    so tp and unsharded results agree to f32 rounding, not bit for bit.
    """
    K = xq.shape[-1]
    G = s_w.shape[0]
    assert K % G == 0, f"groups ({G}) must tile the contracting dim ({K})"
    group = K // G
    N = w_packed.shape[-1]
    lead = xq.shape[:-1]
    if _use_w4a8_kernel() and w_packed.ndim == 2 and jnp.ndim(s_x) == 0:
        from repro.kernels.w4a8_matmul import w4a8_matmul
        interpret = jax.default_backend() != "tpu"
        x2 = xq.reshape(-1, K)

        def run(x, w, sw, cs, s_x, z_x):
            return w4a8_matmul(x, w, s_x, z_x, sw, cs, group_size=group,
                               bm=256, bn=_tile(w.shape[1], 512),
                               interpret=interpret)

        scalars = (jnp.asarray(s_x, jnp.float32),
                   jnp.asarray(z_x, jnp.float32))
        mesh = _tp_mesh()
        if mesh is None:
            out = run(x2, w_packed, s_w, colsum, *scalars)
        elif row_parallel:
            # shards sum their groups' s_w[g] * (x_g @ w_g) (unit s_x, zero
            # z_x, zero colsum); the zero-point correction and s_x apply once
            # after the psum
            acc = _tp_kernel(
                mesh, run, x2, w_packed, (s_w, colsum), scalars, True,
                row_partial=lambda x, w, sw, *_: run(
                    x, w, sw, jnp.zeros((w.shape[1],), jnp.float32),
                    jnp.float32(1), jnp.float32(0)),
                rows=(s_w,))
            out = (acc - scalars[1] * colsum.astype(jnp.float32)) \
                * scalars[0]
        else:
            out = _tp_kernel(mesh, run, x2, w_packed, (s_w, colsum),
                             scalars, False)
        return out.reshape(*lead, N).astype(out_dtype)
    wq = unpack_int4(w_packed, K)                          # (K, N) int8
    wdq = wq.astype(jnp.float32).reshape(G, group, N) \
        * s_w.astype(jnp.float32)[:, None, :]
    acc = jnp.einsum("...k,kn->...n", xq.astype(jnp.float32),
                     wdq.reshape(K, N))
    acc = acc - jnp.asarray(z_x, jnp.float32) * colsum.astype(jnp.float32)
    return (acc * jnp.asarray(s_x, jnp.float32)).astype(out_dtype)


def true_int_dot(x: Array, w: Array, cfg: QuantConfig,
                 site: Optional[SiteScale], row_parallel: bool = False
                 ) -> Array:
    """int8 x int8 -> int32 matmul with scalar-epilogue dequant (see
    ``_int8_matmul`` for the zero-point algebra and the Pallas routing).
    Weights are quantized on the fly (constant-folds under jit when ``w``
    is a weight); ``prequantized_int_dot`` is the int8-resident variant."""
    wq, s_w = weight_quant_int(w, cfg)
    xq, s_x, z_x = _int8_acts(x, cfg, site)
    colsum = jnp.sum(wq.astype(jnp.int32), axis=0)
    return _int8_matmul(xq, wq, s_x, z_x, s_w, colsum, x.dtype, row_parallel)


def _int8_acts(x: Array, cfg: QuantConfig, site: Optional[SiteScale]
               ) -> Tuple[Array, Array, Array]:
    """Activations as int8 for the int matmuls: (x_int8, s_x, z_x), the
    site's static range under ``pt_static``, else one from x itself."""
    if cfg.mode == "pt_static":
        assert site is not None
        s_x, z_x = site.scale, site.zero
    else:
        mn, mx = act_minmax(x, cfg.mode == "ptoken_dynamic")
        s_x, z_x = params_from_minmax(mn, mx, cfg.a_bits, cfg.symmetric_a)
    xq = quantize(x, s_x, z_x, cfg.a_bits, cfg.symmetric_a)
    if not cfg.symmetric_a:
        # asymmetric range is [0, 2^b-1]; offset by -2^(b-1) to store in
        # int8 and fold the offset into the zero-point correction
        off = 2 ** (cfg.a_bits - 1)
        xq = xq - off
        z_x = z_x - off
    return xq.astype(jnp.int8), s_x, z_x


def prequantized_int_dot(x: Array, w: Dict[str, Array], cfg: QuantConfig,
                         site: Optional[SiteScale],
                         row_parallel: bool = False) -> Array:
    """Serving path with int8-resident weights: HBM streams 1 byte/weight
    (2x less than bf16) straight into the int8 MXU matmul — no on-the-fly
    weight requantization, no bf16 dequant materialization. The stored
    colsum feeds the zero-point correction without re-reducing the weight.
    Requires calibrated static scales (``site``): per-tensor static W8A8 is
    the deployment configuration the CushionCache prefix rescues.

    Two resident formats, distinguished by key: ``w_int`` (int8, 1 B/weight)
    routes through ``_int8_matmul``; ``w_packed`` (int4 nibbles, 0.5
    B/weight, group-wise scales) through ``_int4_matmul``. Activations are
    int8 in both — W4A8 narrows the weights only."""
    if cfg.mode != "pt_static" or site is None:
        raise ValueError(
            "prequantized (int8-resident) weights serve the pt_static "
            "deployment path only and need calibrated site scales; got "
            f"mode={cfg.mode!r}, site={'set' if site is not None else None}")
    xq, s_x, z_x = _int8_acts(x, cfg, site)
    if "w_packed" in w:
        return _int4_matmul(xq, w["w_packed"], s_x, z_x, w["w_scale"],
                            w["colsum"], x.dtype, row_parallel)
    return _int8_matmul(xq, w["w_int"], s_x, z_x, w["w_scale"],
                        w["colsum"], x.dtype, row_parallel)


# ---------------------------------------------------------------------------
# Grouped (expert) matmuls: rows sorted by expert
# ---------------------------------------------------------------------------

def expert_quant_int(w: Array, cfg: QuantConfig) -> Tuple[Array, Array]:
    """Symmetric int8 weights of an (..., E, K, N) expert stack with one
    scale per (expert, output column): (w_int8, scale (..., E, N)). The
    column scales ride the grouped kernel's epilogue, so every expert
    keeps its own range."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2)
    scale, _ = params_from_minmax(-amax, amax, cfg.w_bits, True)
    wq = quantize(wf, scale[..., None, :], jnp.zeros(()), cfg.w_bits, True)
    return wq.astype(jnp.int8), scale


@functools.partial(jax.jit, static_argnums=(1, 2))
def prequantize_experts(w: Array, cfg: QuantConfig,
                        weight_bits: int = 8) -> Any:
    """An (..., E, K, N) expert stack in its resident serving form:
    {"w_int" int8, "w_scale" (..., E, N), "colsum" (..., E, N) int32}.
    At ``weight_bits=4`` (the W4A8 control) the experts keep float storage
    holding the group-wise 4-bit values (``weight_fake_quant``): the
    grouped path multiplies them as it multiplies any float expert. One
    jitted call per stack, so no float32 copy of a stack is made whole."""
    if weight_bits == 4:
        return weight_fake_quant(w, dataclasses.replace(cfg, w_bits=4))
    wq, scale = expert_quant_int(w, cfg)
    return {"w_int": wq, "w_scale": scale,
            "colsum": jnp.sum(wq.astype(jnp.int32), axis=-2)}


def _row_experts(group_sizes: Array, M: int) -> Array:
    """The expert of each of M rows sorted by expert (E for rows past the
    groups)."""
    return jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(M),
                            side="right")


def _grouped_dense(x: Array, w: Array, group_sizes: Array,
                   preferred_element_type=None) -> Array:
    """Rows sorted by expert times their experts' weights in plain jnp:
    one product per expert over every row, each row keeping its own
    expert's. E times the work of a grouped kernel, but it runs anywhere,
    under vmap and under autodiff (the calibration, the search and the
    prefix tuning), and adds only exact zeros to each row's product."""
    expert = _row_experts(group_sizes, x.shape[0])

    def body(acc, ew):
        e, we = ew
        y = jnp.matmul(x, we, preferred_element_type=preferred_element_type)
        return acc + jnp.where((expert == e)[:, None], y, 0).astype(
            acc.dtype), None

    dt = preferred_element_type or jnp.result_type(x, w)
    acc, _ = jax.lax.scan(body, jnp.zeros((x.shape[0], w.shape[-1]), dt),
                          (jnp.arange(w.shape[0]), w))
    return acc


def _int8_gmm(xq: Array, w: Dict[str, Array], group_sizes: Array, s_x,
              z_x, out_dtype) -> Array:
    """int8 rows sorted by expert times their experts' int8 weights, with
    the per-column epilogue  (X_g @ W_g - z colsum_g) s_x s_w[g].

    ``w`` holds (E, K, N) arrays, or the (L, E, K, N) stack of every layer
    with the layer to read under "layer". On TPU (or with
    REPRO_W8A8_KERNEL=pallas) the Pallas ``w8a8_gmm`` kernel runs it,
    reading only the experts that have rows, and only the given layer of a
    stack; elsewhere ``_grouped_dense`` does, integer-exact. Under a tp
    mesh the kernel runs whole on every device."""
    w_int, s_w, colsum = w["w_int"], w["w_scale"], w["colsum"]
    layer = w.get("layer")
    M, E = xq.shape[0], group_sizes.shape[0]
    if _use_w8a8_kernel() and jnp.ndim(s_x) == 0:
        from repro.kernels.w8a8_gmm import w8a8_gmm
        run = functools.partial(
            w8a8_gmm, tm=32 if M <= 32 * E else 128,
            tn=_tile(w_int.shape[-1], 1024),
            interpret=jax.default_backend() != "tpu")
        args = (xq, w_int, group_sizes, jnp.asarray(s_x, jnp.float32),
                jnp.asarray(z_x, jnp.float32), s_w, colsum,
                jnp.int32(0) if layer is None else layer)
        mesh = _tp_mesh()
        if mesh is None:
            out = run(*args)
        else:
            from jax.sharding import PartitionSpec as P

            from repro.distributed.sharding import shard_map
            out = shard_map(run, mesh, in_specs=(P(),) * len(args),
                            out_specs=P())(*args)
        return out.astype(out_dtype)
    if layer is not None:
        w_int, s_w, colsum = (a[layer] for a in (w_int, s_w, colsum))
    if jax.default_backend() != "tpu":
        # XLA:CPU has no int8 GEMM: f32 products in contraction chunks of
        # _F32_EXACT_K stay integer-exact (see _int_product_f32_exact)
        K = xq.shape[-1]
        xf, wf = xq.astype(jnp.float32), w_int.astype(jnp.float32)
        acc = sum(_grouped_dense(xf[:, k0:k0 + _F32_EXACT_K],
                                 wf[:, k0:k0 + _F32_EXACT_K],
                                 group_sizes).astype(jnp.int32)
                  for k0 in range(0, K, _F32_EXACT_K))
    else:
        acc = _grouped_dense(xq, w_int, group_sizes, jnp.int32)
    expert = jnp.minimum(_row_experts(group_sizes, M), E - 1)
    acc = acc.astype(jnp.float32) - jnp.asarray(z_x, jnp.float32) \
        * colsum[expert].astype(jnp.float32)
    return (acc * (jnp.asarray(s_x, jnp.float32)
                   * s_w[expert].astype(jnp.float32))).astype(out_dtype)


def grouped_qdot(x: Array, w: Any, group_sizes: Array, cfg: QuantConfig,
                 site: Optional[SiteScale] = None) -> Array:
    """Quantized grouped product, ``qdot``'s counterpart for an expert
    stack: x holds rows sorted by expert, ``group_sizes`` (E,) their count
    per expert, and row r of expert g comes out as q(x_r) @ q(W_g). ``w``
    is an (E, K, N) stack or its prequantized dict
    (``prequantize_experts``), whose arrays may be the (L, E, K, N) stack
    of every layer with the layer under "layer"."""
    if isinstance(w, dict):
        if cfg.mode != "pt_static" or site is None:
            raise ValueError(
                "prequantized (int8-resident) experts serve the pt_static "
                "deployment path only and need calibrated site scales; got "
                f"mode={cfg.mode!r}, site={'set' if site is not None else None}")
        xq, s_x, z_x = _int8_acts(x, cfg, site)
        return _int8_gmm(xq, w, group_sizes, s_x, z_x, x.dtype)
    if cfg.mode == "none":
        return _grouped_dense(x, w, group_sizes)
    if cfg.true_int8 and cfg.a_bits == 8 and cfg.w_bits == 8:
        xq, s_x, z_x = _int8_acts(x, cfg, site)
        return _int8_gmm(xq, prequantize_experts(w, cfg), group_sizes, s_x,
                         z_x, x.dtype)
    xq = act_fake_quant(x, cfg, site.scale if site is not None else None,
                        site.zero if site is not None else None)
    return _grouped_dense(xq, weight_fake_quant(w, cfg), group_sizes)


def prequantize(w: Array, cfg: QuantConfig,
                weight_bits: int = 8) -> Dict[str, Array]:
    """Quantize one (d_in, d_out) weight into its resident serving dict.

    weight_bits=8: {"w_int" int8 (K,N), "w_scale" scalar, "colsum" (N,)
    int32} — per-tensor scale, raw column sums.
    weight_bits=4: {"w_packed" int8 (ceil(K/2),N) nibble-packed, "w_scale"
    (G,N) group-wise, "colsum" (N,) f32 *scaled* column sums
    sum_g s_w[g,n]*colsum_g[n]} — the scales ride in the colsum so the
    kernel epilogue stays a rank-1 subtract."""
    if weight_bits == 4:
        wq, scale, g = weight_quant_int4(w, cfg)
        G = w.shape[0] // g
        colsum_g = jnp.sum(
            wq.astype(jnp.int32).reshape(G, g, -1), axis=1)    # (G, N)
        colsum = jnp.sum(colsum_g.astype(jnp.float32) * scale, axis=0)
        return {"w_packed": pack_int4(wq), "w_scale": scale,
                "colsum": colsum}
    if weight_bits != 8:
        raise ValueError(f"weight_bits must be 8 or 4, got {weight_bits}")
    wq, scale = weight_quant_int(w, cfg)
    return {"w_int": wq, "w_scale": scale,
            "colsum": jnp.sum(wq.astype(jnp.int32), axis=0)}


_PREQUANT_KEYS = ("wqkv", "wo", "w_up", "w_gate", "w_down", "w_in", "w_out",
                  "w_proj")
# an MoE layer's expert stacks, read by ``grouped_qdot``
EXPERT_KEYS = ("w_up", "w_gate", "w_down")


def prequantize_tree(params: Any, cfg: QuantConfig,
                     min_ndim: int = 2, weight_bits: int = 8) -> Any:
    """Replace qdot-consumed weight matrices with int-resident Quantized
    dicts (int8 ``w_int`` or, with ``weight_bits=4``, nibble-packed
    ``w_packed``), and an MoE layer's expert stacks with their grouped form
    (``prequantize_experts``: int8 per (expert, output column)). The router
    stays fp, as do embeddings (gather lookups); the Arctic dense residual
    branch under ``moe/residual`` is a dense MLP and converts like one.
    Hybrid period params nest their sublayers in lists; those are descended
    too."""
    if weight_bits not in (8, 4):
        raise ValueError(f"weight_bits must be 8 or 4, got {weight_bits}")

    def eligible(k, v, path):
        if not (hasattr(v, "ndim") and v.ndim >= min_ndim):
            return False
        if "embed" in path:
            return False
        if path and path[-1] == "moe":
            return k in EXPERT_KEYS
        if k in _PREQUANT_KEYS:
            return True
        return k == "w" and path and path[-1] == "head"

    def convert(v):
        if v.ndim == 2:
            return prequantize(v, cfg, weight_bits=weight_bits)
        # stacked over layers/periods: quantize per layer slice
        if weight_bits == 4:
            return jax.vmap(
                lambda a: prequantize(a, cfg, weight_bits=4))(v)
        wq, scale = jax.vmap(lambda a: weight_quant_int(a, cfg))(v)
        return {"w_int": wq, "w_scale": scale,
                "colsum": jnp.sum(wq.astype(jnp.int32), axis=-2)}

    def visit(d, path=()):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = visit(v, path + (k,))
            elif isinstance(v, (list, tuple)):
                out[k] = [visit(e, path + (k, i)) if isinstance(e, dict)
                          else e for i, e in enumerate(v)]
            elif eligible(k, v, path):
                out[k] = (prequantize_experts(v, cfg, weight_bits)
                          if path and path[-1] == "moe" else convert(v))
            else:
                out[k] = v
        return out
    return visit(params)


def qdot(x: Array, w: Any, cfg: QuantConfig,
         site: Optional[SiteScale] = None, row_parallel: bool = False
         ) -> Array:
    """Quantized x @ w. ``w`` is (d_in, d_out) / (..., d_in, d_out), or a
    prequantized {"w_int" | "w_packed", "w_scale", "colsum"} dict.
    ``row_parallel``: under a tp mesh the weight's contraction dim is the
    sharded one (the int kernels shard_map accordingly)."""
    if isinstance(w, dict):
        return prequantized_int_dot(x, w, cfg, site, row_parallel)
    if cfg.mode == "none":
        return x @ w
    if cfg.true_int8 and w.ndim == 2 and cfg.a_bits == 8 and cfg.w_bits == 8:
        return true_int_dot(x, w, cfg, site, row_parallel)
    xq = act_fake_quant(x, cfg,
                        site.scale if site is not None else None,
                        site.zero if site is not None else None)
    wq = weight_fake_quant(w, cfg)
    return xq @ wq


# ---------------------------------------------------------------------------
# Quantization error L_q, eq. (6), + site statistics for calibration/analysis
# ---------------------------------------------------------------------------

def site_qerr(x: Array, cfg: QuantConfig, site: Optional[SiteScale],
              n_skip: int = 0, valid: Optional[Array] = None) -> Array:
    """||X - q(X)||^2 over the token part (positions >= n_skip along axis -2).
    ``valid`` (rows of a 2-D x) keeps only the rows where it is True: the
    rows of an MoE's sorted expert buffer whose tokens lie past n_skip.

    For dynamic modes the scale is derived from the same (token-part) tensor,
    mirroring deployment; for static mode the calibrated scale is used.
    """
    if n_skip:
        x = x[..., n_skip:, :]
    # NOTE: qerr stays differentiable w.r.t. x (prefix-tuning needs the
    # gradient); only the quantizer parameters are stop-grad'ed below.
    if cfg.mode == "pt_static" and site is not None:
        scale, zero = site.scale, site.zero
    else:
        per_token = cfg.mode == "ptoken_dynamic"
        xs = jax.lax.stop_gradient(x)
        if valid is None or per_token:
            mn, mx = act_minmax(xs, per_token)
        else:
            st = site_stats(xs, valid=valid)
            mn, mx = st["amin"], st["amax"]
        scale, zero = params_from_minmax(mn, mx, cfg.a_bits, cfg.symmetric_a)
    scale = jax.lax.stop_gradient(scale)
    zero = jax.lax.stop_gradient(zero)
    xq = dequantize(quantize(x, scale, zero, cfg.a_bits, cfg.symmetric_a),
                    scale, zero)
    err = jnp.square((x - xq).astype(jnp.float32))
    if valid is not None:
        err = jnp.where(valid[:, None], err, 0.0)
    return jnp.sum(err)


def site_stats(x: Array, n_skip: int = 0,
               valid: Optional[Array] = None) -> Dict[str, Array]:
    """Reduced statistics for calibration & Table-5-style analysis, over
    the rows ``valid`` keeps where it is given (see ``site_qerr``)."""
    if n_skip:
        x = x[..., n_skip:, :]
    xf = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    if valid is None:
        return {"amin": jnp.min(xf), "amax": jnp.max(xf),
                "absmax_ch": jnp.max(jnp.abs(xf), axis=axes)}
    v = valid[:, None]
    return {"amin": jnp.min(jnp.where(v, xf, jnp.inf)),
            "amax": jnp.max(jnp.where(v, xf, -jnp.inf)),
            "absmax_ch": jnp.max(jnp.where(v, jnp.abs(xf), 0.0), axis=axes)}


def scales_from_stats(stats: Any, cfg: QuantConfig) -> Any:
    """Turn a pytree of {amin, amax, absmax_ch} leaves (one dict per site)
    into a pytree of SiteScale for pt_static deployment."""
    def one(site: Dict[str, Array]) -> SiteScale:
        scale, zero = params_from_minmax(site["amin"], site["amax"],
                                         cfg.a_bits, cfg.symmetric_a)
        return SiteScale(scale=scale, zero=zero)
    is_site = lambda d: isinstance(d, dict) and "amin" in d
    return jax.tree_util.tree_map(one, stats, is_leaf=is_site)


def merge_stats(a: Any, b: Any) -> Any:
    """Running union of two stats pytrees (min of mins, max of maxes)."""
    if a is None:
        return b

    def one(sa, sb):
        return {"amin": jnp.minimum(sa["amin"], sb["amin"]),
                "amax": jnp.maximum(sa["amax"], sb["amax"]),
                "absmax_ch": jnp.maximum(sa["absmax_ch"], sb["absmax_ch"])}
    is_site = lambda d: isinstance(d, dict) and "amin" in d
    return jax.tree_util.tree_map(one, a, b, is_leaf=is_site)
