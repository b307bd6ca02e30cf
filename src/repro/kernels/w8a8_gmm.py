"""Pallas TPU kernel: grouped W8A8 matmul over experts (the MoE expert FFN).

The rows of int8 activations arrive sorted by expert, each expert's rows
contiguous, with the count of rows per expert (``group_sizes``). The weights
are the int8 expert stack, (E, K, N) or (L, E, K, N) with a layer index, with
a weight scale and an int32 column sum per (expert, output column). Row r of
expert g comes out as

    ((x_r - z_x) @ W_g) * s_x * s_w[g]  =  (x_r @ W_g - z_x colsum_g) s_x s_w[g]

in float32: the same asymmetric-activation epilogue as ``w8a8_matmul``, with
per-column weight scales.

The grid walks (row tile, expert) work items in expert order. A row tile
that holds the end of one expert's rows and the start of the next is visited
once for each, consecutively, so its output block stays in VMEM and each
visit stores only its own expert's rows. An expert with no rows has no work
item, so its weights are never read; consecutive items of one expert keep
the same weight block index, so its weights are read once per column pass.
The stacked weights are indexed by layer inside the kernel: a layer scan
hands over the whole stack and the index, and no layer's experts are copied
out of it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def work_items(group_sizes: jax.Array, m: int, tm: int):
    """(row tile, expert) work items over ``m`` rows in tiles of ``tm``:
    ``(offsets (E+1,), expert (T,), tile (T,), n_active)`` with T the
    static bound ``ceil(m / tm) + E - 1``. Items past ``n_active`` repeat
    the last active item."""
    E = group_sizes.shape[0]
    gs = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(gs)
    starts = ends - gs
    first = starts // tm
    n_tiles = jnp.where(gs > 0, (ends - 1) // tm - first + 1, 0)
    cum = jnp.cumsum(n_tiles)
    n_active = cum[-1]
    T = -(-m // tm) + E - 1
    item = jnp.minimum(jnp.arange(T, dtype=jnp.int32),
                       jnp.maximum(n_active - 1, 0))
    expert = jnp.searchsorted(cum, item, side="right").astype(jnp.int32)
    expert = jnp.minimum(expert, E - 1)
    tile = first[expert] + item - (cum[expert] - n_tiles[expert])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, expert, tile.astype(jnp.int32), n_active


def _kernel(offsets_ref, expert_ref, tile_ref, layer_ref, x_ref, w_ref,
            colsum_ref, scale_ref, s_ref, o_ref, *, tm: int):
    del layer_ref
    i = pl.program_id(1)
    g = expert_ref[i]
    start, end = offsets_ref[g], offsets_ref[g + 1]
    acc = jax.lax.dot_general(x_ref[...], w_ref[...],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    # s_ref (SMEM) = [s_x, z_x]; colsum and scale are (1, tn) rows
    val = (acc.astype(jnp.float32)
           - s_ref[1] * colsum_ref[...].astype(jnp.float32)) \
        * (s_ref[0] * scale_ref[...])
    rows = tile_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, val.shape,
                                                       0)
    mine = (rows >= start) & (rows < end)
    o_ref[...] = jnp.where(mine, val, o_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def w8a8_gmm(x_int: jax.Array, w_int: jax.Array, group_sizes: jax.Array,
             s_x, z_x, w_scale: jax.Array, colsum: jax.Array, layer=0, *,
             tm: int = 128, tn: int = 1024,
             interpret: bool = False) -> jax.Array:
    """x_int: (M, K) int8 rows sorted by expert; group_sizes: (E,) int32
    rows per expert, summing to at most M; w_int: (E, K, N) or (L, E, K, N)
    int8, ``layer`` picking the layer of a stack; w_scale (f32) and colsum
    (int32): (E, N) or (L, E, N); s_x, z_x: the rows' per-tensor scale and
    zero point. Returns f32 (M, N); rows past sum(group_sizes) are left
    undefined.

    ``tm`` (a multiple of 32, the int8 sublane tile) is the row tile: a
    small one for decode, where each expert gets a few rows. M may be
    ragged: the last row tile is a boundary block. N must tile by ``tn``
    (a multiple of 128, or N itself); K is one block."""
    if w_int.ndim == 3:
        w_int, w_scale, colsum = w_int[None], w_scale[None], colsum[None]
    L, E, K, N = w_int.shape
    M, K2 = x_int.shape
    assert K == K2 and group_sizes.shape == (E,)
    tn = min(tn, N)
    assert N % tn == 0, f"N={N} must tile by {tn}"
    offsets, expert, tile, n_active = work_items(group_sizes, M, tm)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    scalars = jnp.stack([jnp.asarray(s_x, jnp.float32),
                         jnp.asarray(z_x, jnp.float32)]).reshape(2)
    cols = lambda a, dt: a.astype(dt).reshape(L, E, 1, N)

    def x_map(j, i, off, ex, ti, ly):
        return ti[i], 0

    def w_map(j, i, off, ex, ti, ly):
        return ly[0], ex[i], 0, j

    def o_map(j, i, off, ex, ti, ly):
        return ti[i], j

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        name="w8a8_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, n_active),
            in_specs=[
                pl.BlockSpec((tm, K), x_map),
                pl.BlockSpec((None, None, K, tn), w_map),
                pl.BlockSpec((None, None, 1, tn), w_map),
                pl.BlockSpec((None, None, 1, tn), w_map),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((tm, tn), o_map)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(offsets, expert, tile, layer, x_int, w_int, cols(colsum, jnp.int32),
      cols(w_scale, jnp.float32), scalars)
