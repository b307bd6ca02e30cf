"""jit'd wrappers routing model-level ops through the Pallas kernels
(TPU execution path; `interpret=True` everywhere on CPU for validation).

`qdot_pallas` is the drop-in for core.quantization.true_int_dot when
ParallelConfig.use_pallas is set: fused act-quant kernel -> int8 MXU matmul
kernel with the static-scale epilogue. `attention_pallas` replaces the jnp
flash path (it expects GQA-expanded heads).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.core import quantization as Q
from repro.kernels.act_quant import act_quant_ptoken, act_quant_static
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.w8a8_matmul import w8a8_matmul


def _pad_to(x: jax.Array, mult: int, axis: int) -> Tuple[jax.Array, int]:
    n = x.shape[axis]
    target = -(-n // mult) * mult
    if target == n:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - n)
    return jnp.pad(x, pad), n


def qdot_pallas(x: jax.Array, w: jax.Array, cfg: QuantConfig,
                site: Optional[Q.SiteScale] = None,
                interpret: bool = True) -> jax.Array:
    """x: (..., K) fp; w: (K, N) fp. Full W8A8 per-tensor-static path on the
    Pallas kernels: quantize activations (fused kernel), quantize weights
    (host-side constant fold under jit), int8 matmul with scalar epilogue.

    The int8 storage is offset by -128 in act_quant; the equivalent
    zero-point seen by the matmul is z - 128.
    """
    assert cfg.mode == "pt_static" and site is not None
    orig_shape = x.shape
    M = 1
    for d in orig_shape[:-1]:
        M *= d
    K = orig_shape[-1]
    x2 = x.reshape(M, K)
    x2, M0 = _pad_to(x2, 128, 0)

    wq, s_w = Q.weight_quant_int(w, cfg)
    xq = act_quant_static(x2, site.scale, site.zero, bits=cfg.a_bits,
                          bm=min(128, x2.shape[0]), interpret=interpret)
    out = w8a8_matmul(xq, wq, site.scale, site.zero - 128.0, s_w,
                      bm=min(128, xq.shape[0]), bn=min(512, w.shape[1]),
                      bk=min(256, K), interpret=interpret)
    out = out[:M0].reshape(*orig_shape[:-1], w.shape[1])
    return out.astype(x.dtype)


def attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                     causal: bool = True, prefix_len: int = 0,
                     interpret: bool = True) -> jax.Array:
    """q: (B,S,H,hd); k/v: (B,T,Kh,hd). GQA kv-heads are indexed natively
    inside the flash kernel's BlockSpec index maps — no G× head expansion is
    ever materialized in HBM. Returns (B,S,H,hd)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    qh = jnp.transpose(q, (0, 2, 1, 3))
    kh = jnp.transpose(k, (0, 2, 1, 3))
    vh = jnp.transpose(v, (0, 2, 1, 3))
    o = flash_attention(qh, kh, vh, causal=causal, prefix_len=prefix_len,
                        bq=min(256, S), bkv=min(512, T),
                        interpret=interpret)
    return jnp.transpose(o, (0, 2, 1, 3))


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, pos,
                            k_scale: jax.Array | None = None,
                            v_scale: jax.Array | None = None,
                            kc: jax.Array | None = None,
                            vc: jax.Array | None = None,
                            interpret: bool = False) -> jax.Array:
    """Model-level entry for the split-KV decode kernel. q: (B,H,hd);
    k/v: the (B,Smax,K,hd) cache (int8 when scales given, cushion block in
    kc/vc); pos: () shared or (B,) per-row decode positions (continuous
    batching — rows with pos < 0 are retired/compute-masked). Returns
    (B,H,hd)."""
    return flash_decode(q, k, v, pos, k_scale=k_scale, v_scale=v_scale,
                        kc=kc, vc=vc, interpret=interpret)


def decode_attention_tp(q: jax.Array, k: jax.Array, v: jax.Array, pos,
                        mesh, axis: str = "tp",
                        k_scale: jax.Array | None = None,
                        v_scale: jax.Array | None = None,
                        kc: jax.Array | None = None,
                        vc: jax.Array | None = None,
                        interpret: bool = False) -> jax.Array:
    """Tensor-parallel split-KV decode: ``shard_map`` the flash-decode
    kernel over the mesh's ``axis`` with per-shard head slicing.

    Sharding contract (requires K % tp == 0; callers fall back to the
    unsharded entry otherwise):
      q        (B, H, hd)      heads axis sharded — H = K*G splits on KV-head
                               boundaries, so each shard's G-groups stay
                               aligned with its local KV heads
      k/v      (B, Smax, K, hd) KV-heads axis sharded (the serve-pool layout
                               from models/*.cache_roles)
      k/v_scale (K,) or (B,K)  sharded with the heads they dequantize
                               (per-slot scales keep batch replicated)
      kc/vc    (m, K, hd)      stored replicated (cushion bit-identity per
                               shard); sliced to the local heads on entry
      pos      () or (B,)      replicated

    Each shard runs the whole split-KV kernel on its local heads — per-head
    attention is embarrassingly parallel, so the body needs no collectives;
    the surrounding o-projection (wo sharded ("M", None)) contributes the
    one psum per layer. Returns q-sharded (B, H, hd)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import shard_map

    quantized = k_scale is not None
    pos_spec = P() if jnp.ndim(pos) == 0 else P(None)
    hs = P(None, axis, None)             # (B, H, hd) heads-sharded
    kvs = P(None, None, axis, None)      # (B, Smax, K, hd) kv-heads-sharded
    if quantized:
        sspec = P(None, axis) if jnp.ndim(k_scale) == 2 else P(axis)
        def body(q, k, v, pos, ksc, vsc, kc, vc):
            return flash_decode(q, k, v, pos, k_scale=ksc, v_scale=vsc,
                                kc=kc, vc=vc, interpret=interpret)
        f = shard_map(
            body, mesh,
            in_specs=(hs, kvs, kvs, pos_spec, sspec, sspec,
                      P(None, axis, None), P(None, axis, None)),
            out_specs=hs)
        return f(q, k, v, pos, k_scale, v_scale, kc, vc)

    def body(q, k, v, pos):
        return flash_decode(q, k, v, pos, interpret=interpret)
    f = shard_map(body, mesh, in_specs=(hs, kvs, kvs, pos_spec),
                         out_specs=hs)
    return f(q, k, v, pos)


def decode_attention_paged(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array, pos,
                           layer,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           kc: jax.Array | None = None,
                           vc: jax.Array | None = None,
                           interpret: bool = False) -> jax.Array:
    """Model-level entry for the paged split-KV decode kernel. q: (B,H,hd);
    k/v_pages: the (L, n_pages, ps // r, K, r*hd) lane-dense page store of
    every layer (``flash_decode.pack_pages``; int8 when scales given);
    page_table: (B, P) int32 slot page tables and layer: () int32 (both
    scalar-prefetched into the kernel's index maps); pos: () or (B,)
    logical decode positions; kc/vc: the shared batch-free cushion block
    (fp AND int8 pools — paging stores the cushion once, outside the
    pages). Returns (B,H,hd)."""
    return flash_decode_paged(q, k_pages, v_pages, page_table, pos, layer,
                              k_scale=k_scale, v_scale=v_scale,
                              kc=kc, vc=vc, interpret=interpret)


def decode_attention_tp_paged(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, page_table: jax.Array,
                              pos, layer, mesh, axis: str = "tp",
                              k_scale: jax.Array | None = None,
                              v_scale: jax.Array | None = None,
                              kc: jax.Array | None = None,
                              vc: jax.Array | None = None,
                              interpret: bool = False) -> jax.Array:
    """Tensor-parallel paged decode: ``shard_map`` ``flash_decode_paged``
    over ``axis`` with per-shard head slicing, exactly as
    ``decode_attention_tp`` — the page store shards its K axis
    ((L, n_pages, ps // r, K, r*hd), serving pool roles), the page table
    and layer index
    are replicated (layout metadata, identical per shard), and the shared
    cushion block is replicated and sliced to local heads on entry.
    Requires K % tp == 0."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import shard_map

    quantized = k_scale is not None
    pos_spec = P() if jnp.ndim(pos) == 0 else P(None)
    hs = P(None, axis, None)              # (B, H, hd) heads-sharded
    pgs = P(None, None, None, axis, None)     # (L, n_pages, ps/r, K, r*hd)
    pts = P(None, None)                   # (B, P) replicated
    cus = P(None, axis, None)             # (m, K, hd) sliced per shard
    layer = jnp.asarray(layer, jnp.int32)
    if quantized:
        sspec = P(None, axis) if jnp.ndim(k_scale) == 2 else P(axis)
        def body(q, k, v, pt, pos, lyr, ksc, vsc, kc, vc):
            return flash_decode_paged(q, k, v, pt, pos, lyr, k_scale=ksc,
                                      v_scale=vsc, kc=kc, vc=vc,
                                      interpret=interpret)
        f = shard_map(
            body, mesh,
            in_specs=(hs, pgs, pgs, pts, pos_spec, P(), sspec, sspec, cus,
                      cus),
            out_specs=hs)
        return f(q, k_pages, v_pages, page_table, pos, layer, k_scale,
                 v_scale, kc, vc)
    if kc is not None:
        def body(q, k, v, pt, pos, lyr, kc, vc):
            return flash_decode_paged(q, k, v, pt, pos, lyr, kc=kc, vc=vc,
                                      interpret=interpret)
        f = shard_map(
            body, mesh,
            in_specs=(hs, pgs, pgs, pts, pos_spec, P(), cus, cus),
            out_specs=hs)
        return f(q, k_pages, v_pages, page_table, pos, layer, kc, vc)

    def body(q, k, v, pt, pos, lyr):
        return flash_decode_paged(q, k, v, pt, pos, lyr, interpret=interpret)
    f = shard_map(body, mesh,
                         in_specs=(hs, pgs, pgs, pts, pos_spec, P()),
                         out_specs=hs)
    return f(q, k_pages, v_pages, page_table, pos, layer)
