"""Shared model components: norms, RoPE, quantized linear with activation
taps, GQA attention (full-sequence & single-token-decode, cushion-prefix
aware), MLPs.

Conventions
-----------
* params are nested dicts of arrays; stacked over layers for lax.scan.
* every linear runs through `qlinear`, which applies the configured
  activation/weight quantizer and (optionally) records activation taps
  (quant error L_q + order statistics) for calibration / search / analysis.
* `scales` is a pytree mirroring the taps structure holding `SiteScale`
  leaves for pt_static deployment; placeholder (ignored) otherwise.
* the cushion prefix enters attention as per-layer KV (`prefix_kv`:
  dict(k=(m, K, hd), v=(m, K, hd))), fully visible to every query —
  exactly "inserted as a prefix KV cache" (paper eq. 8).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, QuantConfig
from repro.core import quantization as Q
from repro.distributed.sharding import constrain

Array = jax.Array
Params = Dict[str, Any]


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 1.0) -> Array:
    std = scale / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    p = {"g": jnp.ones((d,), dtype_of(cfg))}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((d,), dtype_of(cfg))
    return p


def apply_norm(p: Params, x: Array, cfg: ModelConfig) -> Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        y = y * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["g"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (half-rotation / llama convention)
# ---------------------------------------------------------------------------

def rope_cos_sin(positions: Array, d_head: int, theta: float
                 ) -> Tuple[Array, Array]:
    """positions: (...,) -> cos/sin (..., d_head//2), fp32."""
    inv = 1.0 / (theta ** (np.arange(0, d_head, 2) / d_head))
    ang = positions.astype(jnp.float32)[..., None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (..., n_heads, d_head); cos/sin broadcast over the head axis."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Quantized linear with taps
# ---------------------------------------------------------------------------

def get_site(scales: Optional[Params], name: str) -> Optional[Q.SiteScale]:
    if scales is None:
        return None
    return scales.get(name)


# sites whose weight reads a tp-sharded input (contraction dim on "M" in
# the serve rules: attn/wo, mlp/w_down, mamba/w_out, xlstm/w_proj)
ROW_PARALLEL_SITES = frozenset({"o", "xo", "down", "mamba_out", "m_out",
                                "s_out"})


def qlinear(x: Array, w: Array, b: Optional[Array], qcfg: QuantConfig,
            scales: Optional[Params], site: str, taps: Optional[Dict],
            n_skip: int = 0) -> Array:
    """y = q(x) @ q(w) + b, recording taps for `site` when collecting."""
    if taps is not None:
        taps[site] = {
            "qerr": Q.site_qerr(x, qcfg, get_site(scales, site), n_skip),
            **Q.site_stats(x, n_skip),
        }
    y = Q.qdot(x, w, qcfg, get_site(scales, site),
               row_parallel=site in ROW_PARALLEL_SITES)
    if b is not None:
        y = y + b
    return y


def placeholder_scales(sites: Tuple[str, ...], n_layers: int) -> Params:
    """Stacked (L,)-leaf SiteScale tree (used when no calibration is loaded;
    values are ignored unless qcfg.mode == 'pt_static')."""
    one = lambda: Q.SiteScale(scale=jnp.ones((n_layers,), jnp.float32),
                              zero=jnp.zeros((n_layers,), jnp.float32))
    return {s: one() for s in sites}


def resolve_scales(scales: Optional[Params], sites: Tuple[str, ...],
                   n_layers: int, qcfg: QuantConfig) -> Params:
    """Per-layer scales tree for a forward: the calibrated tree when given,
    else placeholders. Refuses ``pt_static`` with no calibrated scales —
    the placeholder (scale=1, zero=0) tree would silently clip every
    activation to [0, 255] and produce garbage logits, which is exactly the
    failure mode a served model must never hit. Callers that only need a
    quantized *lowering* (dry-runs) pass ``placeholder_all_scales``
    explicitly and bypass this guard."""
    if scales is not None:
        return {s: scales[s] for s in sites}
    if qcfg.mode == "pt_static":
        raise ValueError(
            "pt_static forward without calibrated scales: per-tensor static "
            "quantization needs site scales from core.calibration.calibrate "
            "(serve.py runs it at engine load via --calib-batches); refusing "
            "to run on placeholder scales, which would produce wrong logits "
            "silently")
    return placeholder_scales(sites, n_layers)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

ATTN_SITES = ("qkv", "o")


def attn_init(key, cfg: ModelConfig) -> Params:
    hd, H, K = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    k1, k2 = jax.random.split(key)
    dt = dtype_of(cfg)
    p = {
        "wqkv": dense_init(k1, cfg.d_model, (H + 2 * K) * hd, dt),
        "wo": dense_init(k2, H * hd, cfg.d_model, dt,
                         scale=1.0 / np.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qkv_bias:
        p["bqkv"] = jnp.zeros(((H + 2 * K) * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = {"g": jnp.ones((H * hd,), dt)}
        p["k_norm"] = {"g": jnp.ones((K * hd,), dt)}
    return p


def _split_qkv(qkv: Array, cfg: ModelConfig,
               p: Optional[Params] = None) -> Tuple[Array, Array, Array]:
    """Heads of the fused projection; with ``cfg.qk_norm`` the whole q and
    k projections are RMS-normalized (gains ``p["q_norm"]``,
    ``p["k_norm"]``) before the head split."""
    hd, H, K = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q, k, v = jnp.split(qkv, [H * hd, (H + K) * hd], axis=-1)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, cfg)
        k = apply_norm(p["k_norm"], k, cfg)
    q = q.reshape(*q.shape[:-1], H, hd)
    k = k.reshape(*k.shape[:-1], K, hd)
    v = v.reshape(*v.shape[:-1], K, hd)
    return q, k, v


FLASH_THRESHOLD = 4096 * 4096   # S*T above this -> chunked online softmax
FLASH_Q_CHUNK = 512
FLASH_KV_CHUNK = 1024


def _sdpa_dense(q: Array, k: Array, v: Array, mask: Optional[Array],
                cfg: ModelConfig) -> Array:
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    q = q.reshape(B, S, K, G, hd)
    if S > 1:
        q = constrain(q, "B", None, "M")
    logits = jnp.einsum("bskgh,btkh->bkgst", q, k,
                        preferred_element_type=jnp.float32)
    # heads (kv-head axis) on "M" at prefill AND decode, matching the
    # serve-pool layout (models/*.cache_roles): per-head attention is
    # shard-local, softmax over T needs no collective, and only the
    # o-projection psums. Sharding the KV-seq axis instead (split-KV)
    # would force a per-layer reshard of the heads-sharded cache.
    logits = constrain(logits, "B", "M")
    logits = logits / np.sqrt(hd)
    if mask is not None:
        if mask.ndim == 3:
            m = mask[:, None, None, :, :]
        else:
            m = mask[None, None, None, :, :]
        logits = jnp.where(m, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out.reshape(B, S, H, hd)


def flash_attention_jnp(q: Array, k: Array, v: Array, cfg: ModelConfig,
                        causal: bool, prefix_len: int = 0,
                        q_chunk: int = FLASH_Q_CHUNK,
                        kv_chunk: int = FLASH_KV_CHUNK,
                        prefix_valid: Optional[Array] = None) -> Array:
    """Chunked online-softmax attention (pure jnp; memory O(chunk^2) instead
    of O(S*T)). Also the oracle for the Pallas flash kernel.

    q: (B,S,H,hd); k/v: (B,T,K,hd) where T = prefix_len + S for causal
    self-attention with a cushion prefix (prefix positions fully visible).
    prefix_valid: optional (prefix_len,) bool — live-length mask for a
    *padded* prefix (the compile-once search path); False rows are invisible
    to every query.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    nq = -(-S // q_chunk)
    nk = -(-T // kv_chunk)
    Sp, Tp = nq * q_chunk, nk * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    qh = qp.reshape(B, nq, q_chunk, K, G, hd)
    kh = kp.reshape(B, nk, kv_chunk, K, hd)
    vh = vp.reshape(B, nk, kv_chunk, K, hd)
    scale = 1.0 / np.sqrt(hd)
    kv_ok = None
    if prefix_valid is not None:
        kv_ok = jnp.pad(jnp.concatenate(
            [prefix_valid, jnp.ones((T - prefix_len,), bool)]), (0, Tp - T))

    def q_block(qi, qc):
        # qc: (B, q_chunk, K, G, hd); online softmax over kv chunks
        acc0 = jnp.zeros((B, q_chunk, K, G, hd), jnp.float32)
        m0 = jnp.full((B, K, G, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)

        def kv_block(carry, ki):
            acc, m, l = carry
            kc = kh[:, ki]
            vc = vh[:, ki]
            s = jnp.einsum("bskgh,btkh->bkgst", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            iq = qi * q_chunk + jnp.arange(q_chunk)
            jk = ki * kv_chunk + jnp.arange(kv_chunk)
            valid = (jk < T)[None, :]
            if kv_ok is not None:
                valid = valid & kv_ok[jk][None, :]
            if causal:
                vis = (jk[None, :] < prefix_len) | \
                      (jk[None, :] <= iq[:, None] + prefix_len)
                valid = valid & vis
            s = jnp.where(valid[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(valid[None, None, None], p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * jnp.transpose(alpha, (0, 3, 1, 2))[..., None] \
                + jnp.einsum("bkgst,btkh->bskgh", p, vc.astype(jnp.float32))
            return (acc, m_new, l), ()

        (acc, m, l), _ = jax.lax.scan(kv_block, (acc0, m0, l0),
                                      jnp.arange(nk))
        lT = jnp.transpose(l, (0, 3, 1, 2))[..., None]
        return acc / jnp.maximum(lT, 1e-30)

    out = jax.lax.map(lambda i: q_block(i, qh[:, i]), jnp.arange(nq))
    out = jnp.transpose(out, (1, 0, 2, 3, 4, 5)) \
        .reshape(B, Sp, K * G * hd)[:, :S]
    return out.reshape(B, S, H, hd).astype(v.dtype)


def _sdpa(q: Array, k: Array, v: Array, mask: Optional[Array],
          cfg: ModelConfig) -> Array:
    """q: (B,S,H,hd); k/v: (B,T,K,hd); mask: (S,T) or (B,S,T) bool or None.
    GQA: H = K * G. Returns (B,S,H,hd). Dispatches to the chunked flash
    path for large S*T (the mask is then re-derived from causal+prefix
    structure by the callers that need it)."""
    return _sdpa_dense(q, k, v, mask, cfg)


def attention_full(p: Params, x: Array, cfg: ModelConfig, qcfg: QuantConfig,
                   scales: Optional[Params], taps: Optional[Dict],
                   positions: Array,
                   prefix_kv: Optional[Params] = None,
                   causal: bool = True,
                   n_skip: int = 0,
                   return_kv: bool = False,
                   prefix_valid: Optional[Array] = None):
    """Full-sequence attention (train / prefill).

    positions: (S,) absolute positions of x's tokens (already offset past the
    cushion prefix). prefix_kv: dict(k,v) of shape (m, K, hd) — the
    CushionCache for this layer; fully visible to all queries.
    prefix_valid: optional (m,) bool live-length mask for a prefix_kv padded
    to a fixed shape (the compile-once greedy-search scoring path): rows
    where it is False are masked out of every query's visibility.
    """
    B, S, _ = x.shape
    qkv = qlinear(x, p["wqkv"], p.get("bqkv"), qcfg, scales, "qkv", taps,
                  n_skip)
    q, k, v = _split_qkv(qkv, cfg, p)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    k = constrain(k, "B", None, "M")
    v = constrain(v, "B", None, "M")
    new_kv = (k, v)

    m = 0
    if prefix_kv is not None:
        m = prefix_kv["k"].shape[0]
        pk = jnp.broadcast_to(prefix_kv["k"][None], (B, m) + prefix_kv["k"].shape[1:])
        pv = jnp.broadcast_to(prefix_kv["v"][None], (B, m) + prefix_kv["v"].shape[1:])
        k = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v = jnp.concatenate([pv.astype(v.dtype), v], axis=1)

    T = k.shape[1]
    if S * T >= FLASH_THRESHOLD:
        out = flash_attention_jnp(q, k, v, cfg, causal=causal, prefix_len=m,
                                  prefix_valid=prefix_valid)
    else:
        if causal:
            i = jnp.arange(S)[:, None]
            j = jnp.arange(m + S)[None, :]
            mask = j < (i + m + 1)      # prefix (j<m) always visible
            if prefix_valid is not None:
                kv_ok = jnp.concatenate([prefix_valid, jnp.ones((S,), bool)])
                mask = mask & kv_ok[None, :]
        elif prefix_valid is not None:
            kv_ok = jnp.concatenate([prefix_valid, jnp.ones((S,), bool)])
            mask = jnp.broadcast_to(kv_ok[None, :], (S, m + S))
        else:
            mask = None
        out = _sdpa(q, k, v, mask, cfg)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    y = qlinear(out, p["wo"], None, qcfg, scales, "o", taps, n_skip)
    if return_kv:
        return y, new_kv
    return y


def _use_decode_kernel() -> bool:
    """Route decode attention through the Pallas split-KV kernel? "auto"
    enables it on TPU backends only (the jnp path is the CPU oracle)."""
    from repro.flags import DECODE_KERNEL
    if DECODE_KERNEL == "pallas":
        return True
    if DECODE_KERNEL == "jnp":
        return False
    return jax.default_backend() == "tpu"


def quantize_kv(x: Array, scale: Array) -> Array:
    """Symmetric per-head int8 KV quantization (the core quantizer with a
    per-head scale). x: (..., K, hd); scale: (K,) fp32 — or per-row (B, K)
    against x (B, S, K, hd) (continuous batching: every cache slot carries
    the scales its own admission prefill calibrated)."""
    if scale.ndim == 2 and x.ndim == 4:
        scale = scale[:, None, :, None]          # (B,K) -> (B,1,K,1)
    else:
        scale = scale[..., :, None]
    q = Q.quantize(x.astype(jnp.float32), scale,
                   jnp.zeros(()), bits=8, symmetric=True)
    return q.astype(jnp.int8)


def kv_scales_from(k: Array, head_axis: int = -2) -> Array:
    """Per-kv-head static dequant scale from observed KV (symmetric amax
    rule from the quantization core, with a floor). Reduces over every axis
    except `head_axis`."""
    axes = tuple(a for a in range(k.ndim) if a != head_axis % k.ndim)
    amax = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=axes)
    scale, _ = Q.params_from_minmax(-amax, amax, bits=8, symmetric=True)
    return jnp.maximum(scale, 1e-6)


def attention_decode_kv(p: Params, x: Array, kv: Params, pos: Array,
                        cfg: ModelConfig, qcfg: QuantConfig,
                        scales: Optional[Params], taps: Optional[Dict]
                        ) -> Tuple[Array, Params]:
    """Single-token decode over one layer's KV-cache dict (the serving fast
    path). x: (B,1,D); pos: () shared absolute write position, or (B,)
    per-row positions (continuous batching: every cache slot carries its own
    decode position — RoPE, the cache write and the attention mask are all
    per-row). Rows must keep pos within [0, Smax): the scheduler freezes a
    retired slot's pos at its last value (>= cushion length) so its dummy
    writes keep landing on its own scratch position and never touch the
    cushion block; its masked output is discarded.

    kv is either the fp cache {"k","v": (B,Smax,K,hd)} (cushion rows live
    in-cache at [0:m)) or the int8 cache
        {"k","v": int8 (B,Smax,K,hd), "k_scale","v_scale": (K,) fp32,
         "kc","vc": (m,K,hd) fp}
    where the cushion/sink block is kept intact in fp (KVSink/IntactKV rule)
    and the int8 tensors hold content positions [m:Smax) only. The new
    token's KV is quantized with the static per-(layer,head) scales derived
    at prefill; per-slot scales (B, K) are accepted too (the continuous
    pool calibrates each slot's scales at its own admission prefill —
    quantization, dequant and the kernel read are then all per-row).

    A third layout is the PAGED pool (serving/paging.py): kv carries
    "page_table" (B, P) int32 and "layer" () int32, and k/v are the whole
    lane-dense page store of every layer, (L, n_pages, ps // r, K, r*hd)
    with r positions side by side in each head's lanes
    (``flash_decode.pack_pages``), shared by all rows (``decode_layers``
    carries it through the layer scan); logical positions are unchanged
    (pos//ps selects the logical page, the table the physical one) and the
    shared fp cushion rides in batch-free kc/vc refs for BOTH fp and int8
    pools. Writes scatter into the mapped page of the layer in place;
    reads route through flash_decode_paged (TPU) or a gather of the layer
    + the contiguous CPU paths.

    Attention runs on the Pallas split-KV flash-decode kernel on TPU, or
    the jnp oracle elsewhere. Returns (y, updated kv dict).
    """
    B = x.shape[0]
    qkv = qlinear(x, p["wqkv"], p.get("bqkv"), qcfg, scales, "qkv", taps)
    q, k, v = _split_qkv(qkv, cfg, p)
    per_row = jnp.ndim(pos) == 1
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    cos, sin = rope_cos_sin(posv[:, None], cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)         # cos/sin: (B, 1, hd/2)
    k = apply_rope(k, cos, sin)

    quantized = "k_scale" in kv
    paged = "page_table" in kv
    if quantized:
        ks, vs = kv["k_scale"], kv["v_scale"]
        k_wr = quantize_kv(k, ks)
        v_wr = quantize_kv(v, vs)
    else:
        k_wr = k.astype(kv["k"].dtype)
        v_wr = v.astype(kv["v"].dtype)
    if paged:
        # paged pool (serving/paging.py): k/v are the lane-dense
        # (L,n_pages,ps/r,K,r*hd) page store and page_table (B,P) maps row
        # b's logical page posv//ps to a physical page. Retired rows keep a
        # frozen pos AND a zeroed table row, so their dummy writes land on
        # the reserved scratch page 0 — never on a page the allocator may
        # have recycled.
        from repro.kernels.flash_decode import write_pages
        pt, lyr = kv["page_table"], kv["layer"]
        wpos = jnp.maximum(posv, 0)     # no negative page/offset wraps
        cache_k = write_pages(kv["k"], lyr, pt, wpos, k_wr[:, 0])
        cache_v = write_pages(kv["v"], lyr, pt, wpos, v_wr[:, 0])
        cache_k = constrain(cache_k, None, None, None, "M")
        cache_v = constrain(cache_v, None, None, None, "M")
    elif per_row:
        # each row writes at its own position (vmapped update -> scatter)
        row_wr = jax.vmap(
            lambda c, u, p_: jax.lax.dynamic_update_slice(c, u, (p_, 0, 0)))
        cache_k = row_wr(kv["k"], k_wr, posv)
        cache_v = row_wr(kv["v"], v_wr, posv)
    else:
        cache_k = jax.lax.dynamic_update_slice(kv["k"], k_wr, (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(kv["v"], v_wr, (0, pos, 0, 0))
    if not paged:
        # keep the written cache in the serve-pool layout (heads on "M") so
        # the per-step update is a shard-local update, never a reshard
        cache_k = constrain(cache_k, "B", None, "M")
        cache_v = constrain(cache_v, "B", None, "M")
    new = dict(kv)
    new["k"], new["v"] = cache_k, cache_v

    q1 = q[:, 0]                        # (B, H, hd)
    if _use_decode_kernel():
        from repro.distributed.sharding import active_mesh
        from repro.kernels.ops import (decode_attention_paged,
                                       decode_attention_pallas,
                                       decode_attention_tp,
                                       decode_attention_tp_paged)
        mesh = active_mesh()
        tp = (mesh.shape["tp"] if mesh is not None
              and "tp" in mesh.axis_names else 1)
        interpret = jax.default_backend() != "tpu"
        if tp > 1 and cfg.n_kv_heads % tp == 0:
            # shard_map the kernel over the tp axis: each shard runs
            # flash-decode on its local head slice (local q heads, local KV
            # heads, local int8 scales; the replicated cushion block is
            # sliced per shard on entry) — no collectives inside attention
            if paged:
                out = decode_attention_tp_paged(
                    q1, cache_k, cache_v, kv["page_table"], posv,
                    kv["layer"], mesh,
                    k_scale=ks if quantized else None,
                    v_scale=vs if quantized else None,
                    kc=kv.get("kc"), vc=kv.get("vc"), interpret=interpret)
            else:
                out = decode_attention_tp(
                    q1, cache_k, cache_v, posv, mesh,
                    k_scale=ks if quantized else None,
                    v_scale=vs if quantized else None,
                    kc=kv.get("kc"), vc=kv.get("vc"), interpret=interpret)
        elif paged:
            out = decode_attention_paged(
                q1, cache_k, cache_v, kv["page_table"], posv, kv["layer"],
                k_scale=ks if quantized else None,
                v_scale=vs if quantized else None,
                kc=kv.get("kc"), vc=kv.get("vc"), interpret=interpret)
        else:
            out = decode_attention_pallas(
                q1, cache_k, cache_v, posv,
                k_scale=ks if quantized else None,
                v_scale=vs if quantized else None,
                kc=kv.get("kc"), vc=kv.get("vc"), interpret=interpret)
    elif paged:
        # jnp fallback for paged pools: gather the page table into the
        # dense layout and reuse the contiguous CPU paths verbatim — the
        # gathered values equal the contiguous pool's at every visible
        # position and the masked tail underflows to exactly zero weight,
        # so paged-vs-contiguous tokens stay bit-identical on CPU too.
        from repro.kernels.ref import (flash_decode_ref, gather_pages,
                                       layer_pages)
        hd = cfg.head_dim
        kd = gather_pages(layer_pages(cache_k, kv["layer"], hd),
                          kv["page_table"])
        vd = gather_pages(layer_pages(cache_v, kv["layer"], hd),
                          kv["page_table"])
        if quantized:
            out = flash_decode_ref(q1, kd, vd, posv, k_scale=ks, v_scale=vs,
                                   kc=kv.get("kc"), vc=kv.get("vc"))
        else:
            mc = 0 if "kc" not in kv else kv["kc"].shape[0]
            if mc:
                # splice the shared fp cushion over the scratch-mapped
                # positions [0:m) so the dense math matches the contiguous
                # fp pool (which holds the cushion in-cache) bit-for-bit
                kcb = jnp.broadcast_to(kv["kc"].astype(kd.dtype)[None],
                                       (B,) + kv["kc"].shape)
                vcb = jnp.broadcast_to(kv["vc"].astype(vd.dtype)[None],
                                       (B,) + kv["vc"].shape)
                kd = jnp.concatenate([kcb, kd[:, mc:]], axis=1)
                vd = jnp.concatenate([vcb, vd[:, mc:]], axis=1)
            Smax = kd.shape[1]
            mask = jnp.arange(Smax)[None, :] <= posv[:, None]
            out = _sdpa(q, kd, vd, mask[:, None, :], cfg)[:, 0]
            out = jnp.where((posv >= 0)[:, None, None], out,
                            0.0).astype(out.dtype)
    elif quantized:
        from repro.kernels.ref import flash_decode_ref
        out = flash_decode_ref(q1, cache_k, cache_v, posv, k_scale=ks,
                               v_scale=vs, kc=kv.get("kc"), vc=kv.get("vc"))
    else:
        Smax = cache_k.shape[1]
        mask = jnp.arange(Smax)[None, :] <= posv[:, None]   # (B, Smax)
        out = _sdpa(q, cache_k, cache_v, mask[:, None, :], cfg)[:, 0]
        # retired rows (pos < 0, nothing visible): zeros, matching the
        # kernel and flash_decode_ref instead of softmax's uniform average
        out = jnp.where((posv >= 0)[:, None, None], out, 0.0).astype(out.dtype)
    out = out.reshape(B, 1, cfg.n_heads * cfg.head_dim)
    y = qlinear(out, p["wo"], None, qcfg, scales, "o", taps)
    return y, new


def decode_layers(block, x: Array, layers: Params, lscales: Params,
                  cache: Params) -> Tuple[Array, Params]:
    """The decode layer scan: ``block(lp, lsc, h, kv) -> (h, kv)`` over the
    stacked layer params, scales and cache. A contiguous cache scans every
    leaf per layer (xs in, ys out). A paged cache (``page_table`` present)
    carries its k/v page stores whole through the scan and hands the block
    the layer index instead, so each step writes the stores in place and
    never slices a layer out or stacks it back; its small per-layer leaves
    (page table, int8 scales, cushion) stay in xs."""
    if "page_table" not in cache:
        def body(h, xs):
            lp, lsc, kv = xs
            return block(lp, lsc, h, kv)
        return jax.lax.scan(body, x, (layers, lscales, cache))

    stores = {key: cache[key] for key in ("k", "v")}
    per_layer = {key: v for key, v in cache.items() if key not in stores}

    def body(carry, xs):
        h, st = carry
        lp, lsc, kv, layer = xs
        h, kv = block(lp, lsc, h, {**kv, **st, "layer": layer})
        return (h, {key: kv[key] for key in st}), None

    n_layers = cache["page_table"].shape[0]
    (x, stores), _ = jax.lax.scan(
        body, (x, stores),
        (layers, lscales, per_layer, jnp.arange(n_layers, dtype=jnp.int32)))
    return x, {**per_layer, **stores}


def attention_decode(p: Params, x: Array, cache_k: Array, cache_v: Array,
                     pos: Array, cfg: ModelConfig, qcfg: QuantConfig,
                     scales: Optional[Params], taps: Optional[Dict]):
    """Single-token decode over bare fp cache arrays (legacy signature;
    encdec's self-attention still uses it). Delegates to
    attention_decode_kv."""
    y, new = attention_decode_kv(p, x, {"k": cache_k, "v": cache_v}, pos,
                                 cfg, qcfg, scales, taps)
    return y, new["k"], new["v"]


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

MLP_SITES = ("mlp_in", "down")


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_up": dense_init(k1, cfg.d_model, d_ff, dt),
         "w_down": dense_init(k2, d_ff, cfg.d_model, dt,
                              scale=1.0 / np.sqrt(2 * cfg.n_layers))}
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(k3, cfg.d_model, d_ff, dt)
    return p


def apply_mlp(p: Params, x: Array, cfg: ModelConfig, qcfg: QuantConfig,
              scales: Optional[Params], taps: Optional[Dict],
              n_skip: int = 0) -> Array:
    up = qlinear(x, p["w_up"], None, qcfg, scales, "mlp_in", taps, n_skip)
    if cfg.gated_mlp:
        # gate shares the "mlp_in" site (same input tensor -> same scale);
        # taps recorded once on the up projection.
        gate = qlinear(x, p["w_gate"], None, qcfg, scales, "mlp_in", None,
                       n_skip)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    h = constrain(h, "B", None, "M")
    return qlinear(h, p["w_down"], None, qcfg, scales, "down", taps, n_skip)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg)
    p = {"embed": {"w": (jax.random.normal(key, (cfg.vocab_size, cfg.d_model),
                                           jnp.float32) * 0.02).astype(dt)}}
    if not cfg.tie_embeddings:
        p["head"] = {"w": dense_init(jax.random.fold_in(key, 1), cfg.d_model,
                                     cfg.vocab_size, dt)}
    return p


def embed_tokens(p: Params, tokens: Array, cfg: ModelConfig) -> Array:
    x = jnp.take(p["embed"]["w"], tokens, axis=0)
    return constrain(x, "B")


def lm_head(p: Params, x: Array, cfg: ModelConfig, qcfg: QuantConfig,
            scales: Optional[Params], taps: Optional[Dict],
            n_skip: int = 0) -> Array:
    w = p["embed"]["w"].T if cfg.tie_embeddings else p["head"]["w"]
    site = {"head": scales["head"]} if (scales is not None and "head" in scales) else None
    if taps is not None:
        taps["head"] = {"qerr": Q.site_qerr(x, qcfg, get_site(site, "head"),
                                            n_skip),
                        **Q.site_stats(x, n_skip)}
    logits = Q.qdot(x, w, qcfg, get_site(site, "head"))
    return constrain(logits, "B", None, "M")


def cross_entropy(logits: Array, labels: Array) -> Array:
    """Mean next-token CE; logits (B,S,V) (vocab possibly model-sharded),
    labels (B,S) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
