"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_decode import unpack_pages


def w8a8_matmul_ref(x_int: jax.Array, w_int: jax.Array, s_x: jax.Array,
                    z_x: jax.Array, s_w: jax.Array) -> jax.Array:
    """(X_int - z_x) @ W_int * s_x*s_w  in fp32. x_int: (M,K) int8,
    w_int: (K,N) int8, scalars fp32."""
    acc = jax.lax.dot_general(
        x_int, w_int, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)
    colsum = jnp.sum(w_int.astype(jnp.int32), axis=0).astype(jnp.float32)
    acc = acc - z_x * colsum[None, :]
    return acc * (s_x * s_w)


def w4a8_matmul_ref(x_int: jax.Array, w_packed: jax.Array, s_x: jax.Array,
                    z_x: jax.Array, s_w: jax.Array,
                    group_size: int) -> jax.Array:
    """Oracle for the int4-packed kernel: dense unpack, per-group int32
    products, f32 scale combine. x_int: (M,K) int8; w_packed: (K//2,N) int8
    nibble pairs (core.quantization.pack_int4 layout); s_x/z_x scalar;
    s_w: (K//group_size, N) group scales. Returns fp32
    (M,N) = s_x * sum_g s_w[g] * (x[:,g] - z_x) @ w[g]."""
    from repro.core.quantization import unpack_int4
    M, K = x_int.shape
    N = w_packed.shape[1]
    G = K // group_size
    w_int = unpack_int4(w_packed, K)                       # (K, N) int8
    xg = x_int.reshape(M, G, group_size)
    wg = w_int.reshape(G, group_size, N)
    parts = jax.lax.dot_general(
        xg, wg, (((2,), (1,)), ((1,), (0,))),              # (G, M, N)
        preferred_element_type=jnp.int32).astype(jnp.float32)
    colsum_g = jnp.sum(wg.astype(jnp.int32), axis=1)       # (G, N)
    parts = parts - z_x * colsum_g[:, None, :].astype(jnp.float32)
    return s_x * jnp.einsum("gmn,gn->mn", parts, s_w.astype(jnp.float32))


def act_quant_ref(x: jax.Array, bits: int = 8, per_token: bool = False):
    """Asymmetric quantize; returns (x_int8, scale, zero). Static path takes
    precomputed scale/zero via act_quant_static_ref."""
    qmax = 2 ** bits - 1
    if per_token:
        mn = jnp.min(x, axis=-1, keepdims=True)
        mx = jnp.max(x, axis=-1, keepdims=True)
    else:
        mn = jnp.min(x)
        mx = jnp.max(x)
    mn = jnp.minimum(mn, 0.0)
    mx = jnp.maximum(mx, 0.0)
    scale = jnp.maximum((mx - mn) / qmax, 1e-8)
    zero = jnp.round(jnp.clip(-mn / scale, 0, qmax))
    xq = jnp.clip(jnp.round(x / scale + zero), 0, qmax) - 128
    return xq.astype(jnp.int8), scale, zero


def act_quant_static_ref(x: jax.Array, scale: jax.Array, zero: jax.Array,
                         bits: int = 8) -> jax.Array:
    qmax = 2 ** bits - 1
    xq = jnp.clip(jnp.round(x / scale + zero), 0, qmax) - 128
    return xq.astype(jnp.int8)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, prefix_len: int = 0
                        ) -> jax.Array:
    """q: (B,H,S,hd); k/v: (B,Kh,T,hd) with Kh | H (GQA); T = prefix_len + S
    when causal. Prefix positions fully visible (the CushionCache block)."""
    B, H, S, hd = q.shape
    Kh, T = k.shape[1], k.shape[2]
    if Kh != H:
        k = jnp.repeat(k, H // Kh, axis=1)
        v = jnp.repeat(v, H // Kh, axis=1)
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(hd)
    if causal:
        i = jnp.arange(S)[:, None]
        j = jnp.arange(T)[None, :]
        mask = (j < prefix_len) | (j <= i + prefix_len)
        logits = jnp.where(mask[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array, pos,
                     k_scale: jax.Array | None = None,
                     v_scale: jax.Array | None = None,
                     kc: jax.Array | None = None,
                     vc: jax.Array | None = None) -> jax.Array:
    """Oracle for the split-KV decode kernel (also the CPU/jnp decode path
    for quantized caches).

    q: (B,H,hd); k/v: (B,Smax,K,hd) — fp, or int8 with per-head dequant
    scales k_scale/v_scale (K,), or per-row (B,K) slot scales (continuous
    batching: each slot's scales come from its own admission prefill).
    kc/vc: (m,K,hd) fp cushion block covering
    absolute positions [0:m) (int8 caches keep the sink block intact; the
    block is visible to every row regardless of pos — the sink is never
    evicted). pos: () or (B,) — row b attends positions [0:pos[b]] (plus
    the cushion block when present). pos[b] < 0 marks a retired row: with
    no cushion it attends nothing and outputs zeros. Returns (B,H,hd) in
    q.dtype.
    """
    B, H, hd = q.shape
    Smax, K = k.shape[1], k.shape[2]
    G = H // K
    m = 0 if kc is None else kc.shape[0]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        ks = k_scale.astype(jnp.float32)
        vs = v_scale.astype(jnp.float32)
        if ks.ndim == 2:                       # per-row (B, K)
            kf = kf * ks[:, None, :, None]
            vf = vf * vs[:, None, :, None]
        else:
            kf = kf * ks[None, None, :, None]
            vf = vf * vs[None, None, :, None]
    if m:
        kcb = jnp.broadcast_to(kc.astype(jnp.float32)[None], (B,) + kc.shape)
        vcb = jnp.broadcast_to(vc.astype(jnp.float32)[None], (B,) + vc.shape)
        kf = jnp.concatenate([kcb, kf[:, m:]], axis=1)
        vf = jnp.concatenate([vcb, vf[:, m:]], axis=1)
    qg = q.reshape(B, K, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,btkh->bkgt", qg, kf) / np.sqrt(hd)
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    idx = jnp.arange(Smax)
    valid = idx[None, :] <= posv[:, None]              # (B, Smax)
    if m:
        valid = valid | (idx < m)[None, :]             # cushion never masked
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", w, vf)
    # fully-masked rows (retired, no cushion): zeros, not a uniform average
    out = jnp.where(jnp.any(valid, axis=1)[:, None, None, None], out, 0.0)
    return out.reshape(B, H, hd).astype(q.dtype)


def layer_pages(store: jax.Array, layer, hd: int) -> jax.Array:
    """One layer of the lane-dense paged store (L, n_pages, ps // r, K,
    r*hd) as (n_pages, ps, K, hd) (``flash_decode.unpack_pages``)."""
    return unpack_pages(store[layer], hd)


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize a paged KV pool as the dense per-row layout:
    pages (n_pages, ps, K, hd) + page_table (B, P) -> (B, P*ps, K, hd).
    Row b's positions [j*ps, (j+1)*ps) come from physical page
    page_table[b, j]; unmapped entries read the scratch page 0, whose
    content is masked by pos / the cushion boundary downstream."""
    B, P = page_table.shape
    ps = pages.shape[1]
    g = pages[page_table]                       # (B, P, ps, K, hd)
    return g.reshape(B, P * ps, *pages.shape[2:])


def flash_decode_paged_ref(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array, pos,
                           layer,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           kc: jax.Array | None = None,
                           vc: jax.Array | None = None) -> jax.Array:
    """Oracle for ``flash_decode_paged``: gather the layer's pages through
    the page table into the dense layout, then score with
    ``flash_decode_ref`` (the paging oracle — paged attention IS dense
    attention over the gathered cache). fp pools may carry a cushion block
    here (see flash_decode_paged)."""
    hd = q.shape[2]
    return flash_decode_ref(
        q, gather_pages(layer_pages(k_pages, layer, hd), page_table),
        gather_pages(layer_pages(v_pages, layer, hd), page_table), pos,
        k_scale=k_scale, v_scale=v_scale, kc=kc, vc=vc)
