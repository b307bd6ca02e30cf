"""Pallas TPU kernel: split-KV (flash-decoding style) single-query attention
for the serving decode hot path.

Decode is HBM-bound: every generated token streams the whole KV cache once,
so the kernel's job is (a) never materialize anything bigger than a KV tile
in VMEM, (b) read the cache at its storage precision (int8 halves the
dominant roofline term), and (c) never expand GQA kv-heads in HBM.

Layout / grid
-------------
The KV cache keeps the model's native (B, Smax, K, hd) layout, and each
grid program reads one ``(1, bkv, K, hd)`` chunk holding *all* K kv-heads
of a batch row: the block's last two dims are then the array's full
(K, hd) dims, which is what the TPU compiler accepts for this layout (a
per-head ``(1, bkv, 1, hd)`` block has a second-minor dim of 1, neither
8-aligned nor the full K, and is refused). Grid = (B, Smax/bkv) with the
KV-chunk axis innermost and sequential: online-softmax partial (max, sum,
acc) statistics live in VMEM scratch per (query group, kv-head) and are
combined across chunks exactly like flash-decoding's split-KV reduction.
Scores are a VPU multiply + lane reduction over hd per kv-head — a decode
query is one row per head, far too thin for the MXU — so GQA needs no
`jnp.repeat`, no head materialization and no transpose of the cache: q is
regrouped to (B, G, K, hd) and each of the G query heads sharing a kv-head
is folded against the same chunk. The chunk index map clamps chunks past
the row's ``pos`` onto its last live chunk, so they are neither computed
nor fetched again.

Masking comes from the live ``pos`` value — a scalar shared by the batch or
a per-row ``(B,)`` vector (the continuous-batching scheduler gives every
cache slot its own decode position), scalar-prefetched into SMEM: chunks
entirely beyond the row's ``pos`` skip their compute via ``pl.when`` and
their DMA via the clamped index map, and the tail chunk is masked
per-position. A row
with ``pos < 0`` is *retired*: it attends to nothing (fp mode -> zeros) or
to the always-visible cushion block only (int8+cushion mode). The
continuous-batching scheduler compute-masks dead slots by *freezing* their
pos (a negative pos would make the slot's cache write clamp onto the
cushion rows); pos < 0 is the kernel-level contract for callers that
never write, and the jnp fallback/oracle honor the same semantics.

int8-KV variant
---------------
When per-(layer,head) scales are provided, k/v refs are int8 and are
dequantized in-kernel (one per-head multiply per tile, fused on the VPU).
The cushion/sink prefix block [0:m) is NOT quantized: following
KVSink/IntactKV, sink-token KV must stay intact or the whole softmax
distribution degrades. It is read from a separate full-precision ref
(``kc``/``vc``, batch-free — the cushion is shared across the batch) and
folded into the online softmax as the first block; the int8 cache holds
content positions only, and positions below the cushion length are masked
out of the int8 read.

Paged variant
-------------
``flash_decode_paged`` reads the same online-softmax body through a page
table instead of dense per-row caches. The KV store is the whole page pool
of every layer, lane-dense: ``(L, n_pages, ps // r, K, r*hd)`` with
``r = page_rows(hd)`` consecutive positions side by side in the lanes of
each head (two for hd 64, filling the 128 lanes of a vreg row), so XLA keeps
it row-major with no lane padding and neither the decode step nor the
admission scatter re-lays it out (``pack_pages`` / ``unpack_pages``
convert). Each batch row owns a ``(P,)`` row of the scalar-prefetched
``page_table`` mapping logical page ``j`` (cache positions
``[j*ps, (j+1)*ps)``) to a physical page; the layer is one more
scalar-prefetch operand. The k/v BlockSpec is
``(None, None, ps // r, K, r*hd)`` at ``(layer, page_table[b, j], 0, 0, 0)``:
one page of one layer. Inside the kernel the ``r`` positions of each lane
row are split by static ``hd``-lane slices stacked on a major axis, which
merges into the ``(ps, K, hd)`` tile of the contiguous kernel without a
sublane relayout, and feed the unchanged fold. The grid, masking
arithmetic (``kj`` stays the *logical* position) and scratch reduction are
those of the contiguous entry, so a page table that happens to be the
identity reproduces the contiguous kernel bit-for-bit at matched chunk
size. Unmapped logical pages point at the reserved scratch page 0; their
positions are always masked (beyond ``pos`` or below the cushion), so
scratch content is don't-care. Unlike the contiguous entry, fp pools may
pass a cushion block here: paging moves the cushion out of the per-slot
rows into one shared batch-free ref for every dtype (serving/paging.py).

Tensor parallelism
------------------
The kernel is head-parallel by construction (the grid never mixes kv
heads), so a tp mesh shards it by slicing heads per device —
``kernels/ops.py:decode_attention_tp`` shard_maps this entry over the
``tp`` axis with q/KV/scales sliced along their heads axes and the
replicated fp cushion block sliced to local heads on entry (the stored
block stays whole on every shard; see models/*.cache_roles). Requires
K % tp == 0; model code falls back to the unsharded entry otherwise.
``decode_attention_tp_paged`` does the same for the paged entry with the
page table and layer index replicated (page ids are shard-local row
metadata, identical on every shard) and the store's K axis sharded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# VMEM budget for one f32 (bkv, K, hd) chunk temporary: the contiguous
# kernel shrinks its chunk until the tile fits (hd pads to 128 lanes)
_CHUNK_F32_BYTES = 1 << 20


def page_rows(hd: int) -> int:
    """Positions one lane row of the paged store holds per head: as many as
    fill the 128 lanes of a vreg row (two for hd 64), else one."""
    return 128 // hd if hd < 128 and 128 % hd == 0 else 1


def pack_pages(x: jax.Array) -> jax.Array:
    """Positions ``(..., ps, K, hd)`` -> the paged store's lane-dense rows
    ``(..., ps // r, K, r*hd)``: position ``i*r + s`` of head h sits at row
    i, lanes ``[s*hd, (s+1)*hd)``."""
    *lead, ps, K, hd = x.shape
    r = page_rows(hd)
    x = x.reshape(*lead, ps // r, r, K, hd)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, ps // r, K, r * hd)


def unpack_pages(x: jax.Array, hd: int) -> jax.Array:
    """Inverse of ``pack_pages``: ``(..., rows, K, r*hd)`` ->
    ``(..., rows*r, K, hd)``."""
    *lead, rows, K, width = x.shape
    r = width // hd
    x = x.reshape(*lead, rows, K, r, hd)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, rows * r, K, hd)


def write_pages(store: jax.Array, layer, page_table: jax.Array,
                pos: jax.Array, x: jax.Array) -> jax.Array:
    """Write one position per row into the paged store: row b's (K, hd)
    ``x[b]`` goes to logical position ``pos[b]`` (>= 0) of ``layer``,
    through the row's page table. The lane row holding that position is
    read, its hd lanes replaced and the row written back whole, which XLA
    applies to a donated store in place (live rows own distinct pages, so
    no two rows share a lane row; retired rows all land on scratch page 0)."""
    B, _, hd = x.shape
    r = store.shape[-1] // hd
    ps = store.shape[2] * r
    phys = page_table[jnp.arange(B), pos // ps]
    row = (pos % ps) // r
    old = store[layer, phys, row]                        # (B, K, r*hd)
    mine = (jnp.arange(r * hd) // hd)[None, None] == (pos % r)[:, None, None]
    new = jnp.where(mine, jnp.tile(x.astype(store.dtype), (1, 1, r)), old)
    return store.at[layer, phys, row].set(new)


def _kernel(pos_ref, q_ref, k_ref, v_ref, *refs, bkv: int, n_kv: int,
            cushion_m: int, quantized: bool, lane_dense: bool, scale: float):
    i = 0
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        i = 2
    if cushion_m:
        kc_ref, vc_ref = refs[i], refs[i + 1]
        i += 2
    o_ref = refs[i]
    m_ref, l_ref, acc_ref = refs[i + 1:i + 4]

    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    n_groups = q_ref.shape[1]

    def tile(ref):
        """This grid point's (bkv, K, hd) f32 chunk of the k or v ref. A
        lane-dense (bkv // r, K, r*hd) page splits its r positions per lane
        row by static hd-lane slices, stacked on a major axis that merges
        into the position axis (no sublane relayout)."""
        if not lane_dense:
            return ref[0].astype(jnp.float32)
        t = ref[...].astype(jnp.float32)
        hd = q_ref.shape[-1]
        r = t.shape[-1] // hd
        x = jnp.stack([t[..., s * hd:(s + 1) * hd] for s in range(r)],
                      axis=1)                            # (bkv/r, r, K, hd)
        return x.reshape(bkv, t.shape[1], hd)

    def fold(g, k, v, valid):
        """Fold one (T, K, hd) block into query group g's online softmax."""
        qg = q_ref[0, g].astype(jnp.float32)             # (K, hd)
        s = jnp.sum(k * qg[None], axis=-1, keepdims=True) * scale  # (T,K,1)
        if valid is not None:
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[g]                                # (K, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=0)
        acc_ref[g] = acc_ref[g] * alpha + jnp.sum(p * v, axis=0)
        m_ref[g] = m_new

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if cushion_m:
            # the protected fp cushion block [0:m) is the first
            # online-softmax block (every decode query sees the sink block)
            kc = kc_ref[...].astype(jnp.float32)         # (m, K, hd)
            vc = vc_ref[...].astype(jnp.float32)
            for g in range(n_groups):
                fold(g, kc, vc, None)

    @pl.when(j * bkv <= pos)       # chunks fully beyond pos: skip compute
    def _chunk():
        k = tile(k_ref)                                  # (bkv, K, hd)
        v = tile(v_ref)
        if quantized:
            k = k * ks_ref[0][None]                      # (1, K, 1) scales
            v = v * vs_ref[0][None]
        kj = j * bkv + jax.lax.broadcasted_iota(jnp.int32, k.shape[:2] + (1,),
                                                0)
        valid = kj <= pos
        if cushion_m:
            valid &= kj >= cushion_m      # [0:m) lives in the fp cushion ref
        for g in range(n_groups):
            fold(g, k, v, valid)

    @pl.when(j == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _decode_call(q, k, v, pos, k_scale, v_scale, kc, vc, *, K, bkv, n_kv,
                 kv_index, prefetch, lane_dense, name, interpret):
    """Build and run the decode pallas_call over K kv-heads.
    ``kv_index(b, j, *prefetch, pos_ref)`` maps a grid point to the k/v
    block: a ``(1, bkv, K, hd)`` chunk of a dense cache, or, ``lane_dense``,
    a ``(bkv // r, K, r*hd)`` page of one layer of the paged store
    (``pack_pages``). ``prefetch`` holds the scalar-prefetch operands the
    index map reads ahead of ``pos`` (the paged entry's layer and page
    table)."""
    B, H, hd = q.shape
    G = H // K
    quantized = k_scale is not None
    m = 0 if kc is None else kc.shape[0]
    qg = q.reshape(B, K, G, hd).transpose(0, 2, 1, 3)     # (B, G, K, hd)
    # scalar pos -> broadcast; (B,) pos -> one entry per batch row
    posa = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    prefetch = list(prefetch) + [posa]

    r = page_rows(hd)
    kv_spec = pl.BlockSpec((None, None, bkv // r, K, r * hd) if lane_dense
                           else (1, bkv, K, hd), kv_index)
    in_specs = [
        pl.BlockSpec((1, G, K, hd), lambda b, j, *_: (b, 0, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    args = [qg, k, v]
    if quantized:
        if jnp.ndim(k_scale) == 2:      # per-row (B, K) slot scales
            sspec = pl.BlockSpec((1, K, 1), lambda b, j, *_: (b, 0, 0))
        else:                           # (K,) shared by the batch
            sspec = pl.BlockSpec((1, K, 1), lambda b, j, *_: (0, 0, 0))
        in_specs += [sspec, sspec]
        args += [jnp.asarray(s, jnp.float32).reshape(-1, K, 1)
                 for s in (k_scale, v_scale)]
    if m:
        cspec = pl.BlockSpec((m, K, hd), lambda b, j, *_: (0, 0, 0))
        in_specs += [cspec, cspec]
        args += [kc, vc]

    def kernel(*refs):
        # drop the index maps' own operands (layer, page table); keep pos
        _kernel(*refs[len(prefetch) - 1:], bkv=bkv, n_kv=n_kv, cushion_m=m,
                quantized=quantized, lane_dense=lane_dense,
                scale=1.0 / float(np.sqrt(hd)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, K, hd), lambda b, j, *_: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, K, 1), jnp.float32),
                        pltpu.VMEM((G, K, 1), jnp.float32),
                        pltpu.VMEM((G, K, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name=name,
        out_shape=jax.ShapeDtypeStruct((B, G, K, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *args)
    return out.transpose(0, 2, 1, 3).reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, pos,
                 k_scale: jax.Array | None = None,
                 v_scale: jax.Array | None = None,
                 kc: jax.Array | None = None,
                 vc: jax.Array | None = None,
                 bkv: int = 512, interpret: bool = False) -> jax.Array:
    """Single-token decode attention over a (possibly int8) KV cache.

    q: (B, H, hd) — the one new query per sequence.
    k/v: (B, Smax, K, hd) cache in storage layout; fp, or int8 when
        k_scale/v_scale are given — (K,) fp32 per-head dequant scales
        shared by the batch, or per-row (B, K) scales (the continuous
        pool calibrates each slot's scales at its own admission prefill;
        the index map then routes row b's scales to its programs).
    pos: () or (B,) int32 — absolute position of each row's just-written
        token; only cache positions <= pos[b] are attended by row b. A
        scalar is shared by the whole batch; a vector gives every row its
        own decode position (continuous batching: slots prefilled at
        different times decode in lock-step). pos[b] < 0 marks a retired
        row: it attends nothing (fp) or the cushion block only (int8).
    kc/vc: (m, K, hd) fp cushion prefix block covering absolute positions
        [0:m) (int8 caches only; requires pos >= m for live rows; the block
        stays visible to retired rows). Batch-free — the CushionCache is
        shared across sequences.

    ``bkv`` is an upper bound on the chunk: it shrinks until one f32
    (bkv, K, hd) tile fits ``_CHUNK_F32_BYTES`` and until it divides Smax.
    Returns (B, H, hd).
    """
    Smax, K, hd = k.shape[1], k.shape[2], k.shape[3]
    assert k_scale is not None or kc is None, \
        "fp caches hold the cushion in-cache"
    bkv = min(bkv, Smax)
    row_bytes = K * (-(-hd // 128) * 128) * 4
    while bkv > 8 and (Smax % bkv or bkv * row_bytes > _CHUNK_F32_BYTES):
        # prefer a chunk size that divides Smax: a ragged tail would force a
        # jnp.pad — a full HBM copy of the cache EVERY decode step (callers
        # size caches to multiples of 128, so this normally stops at a
        # power-of-two >= 128)
        bkv //= 2
    Tp = -(-Smax // bkv) * bkv
    if Tp != Smax:
        pad = ((0, 0), (0, Tp - Smax), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    def kv_index(b, j, pos_ref):
        # chunks past the row's pos repeat its last live chunk: an
        # unchanged block index is not fetched again
        return (b, jnp.minimum(j, jnp.maximum(pos_ref[b], 0) // bkv), 0, 0)

    return _decode_call(q, k, v, pos, k_scale, v_scale, kc, vc, K=K,
                        bkv=bkv, n_kv=Tp // bkv, kv_index=kv_index,
                        prefetch=(), lane_dense=False, name="flash_decode",
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       page_table: jax.Array, pos, layer,
                       k_scale: jax.Array | None = None,
                       v_scale: jax.Array | None = None,
                       kc: jax.Array | None = None,
                       vc: jax.Array | None = None,
                       interpret: bool = False) -> jax.Array:
    """Single-token decode attention over one layer of a paged (possibly
    int8) KV pool.

    q: (B, H, hd) — one new query per pool slot.
    k_pages/v_pages: (L, n_pages, ps // r, K, r*hd) lane-dense page store
        of every layer (``pack_pages``: r = page_rows(hd) positions per
        lane row); fp, or int8 when k_scale/v_scale are given ((K,) shared
        or per-row (B, K) scales, exactly as in ``flash_decode``).
    page_table: (B, P) int32 — row b's logical page j holds cache positions
        [j*ps, (j+1)*ps) and lives at physical page page_table[b, j].
        P * ps = the pool's max_seq. The table is scalar-prefetched: the
        k/v BlockSpec index maps dereference it, so each grid program DMAs
        exactly its row's physical page for chunk j. Entry 0 is the scratch
        page (unmapped logical pages; always masked).
    pos: () or (B,) int32 decode positions in *logical* coordinates —
        identical semantics to the contiguous kernel, including pos < 0
        retired rows.
    layer: () int32 — the layer of the store to read, scalar-prefetched
        beside the page table (the decode layer scan passes its index, so
        the store is never sliced per layer).
    kc/vc: (m, K, hd) fp cushion covering logical positions [0:m). Allowed
        for BOTH fp and int8 pools: the paged layout stores the shared
        cushion once, batch-free, never in pages (pages below m stay
        scratch-mapped and masked via ``kj >= m``).

    The chunk size is the page size, so against ``flash_decode(bkv=ps)`` on
    the layer's gathered dense cache the online-softmax block sequence is
    identical and the result is bit-exact (the paging property test's
    gate). Returns (B, H, hd).
    """
    ps = k_pages.shape[2] * (k_pages.shape[4] // q.shape[2])
    assert ps % 8 == 0, "page_size must be sublane-aligned (multiple of 8)"
    return _decode_call(
        q, k_pages, v_pages, pos, k_scale, v_scale, kc, vc,
        K=k_pages.shape[3], bkv=ps, n_kv=page_table.shape[1],
        kv_index=lambda b, j, lyr, pt, pos_ref: (lyr[0], pt[b, j], 0, 0, 0),
        prefetch=(jnp.asarray(layer, jnp.int32).reshape(1),
                  jnp.asarray(page_table, jnp.int32)),
        lane_dense=True, name="flash_decode_paged", interpret=interpret)
