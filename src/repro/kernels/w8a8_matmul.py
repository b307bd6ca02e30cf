"""Pallas TPU kernel: W8A8 per-tensor-static matmul.

int8 x int8 tiles stream HBM->VMEM, accumulate on the MXU in int32, and the
epilogue applies the single fused scalar dequant s_x*s_w plus the asymmetric
zero-point correction  -z_x * colsum(W)  — the whole point of per-tensor
*static* quantization: no per-channel/per-token scale traffic anywhere near
the contracting dimension (DESIGN.md §3), and int8 doubles MXU throughput.

Block shapes default to (256, 512, 256): MXU-aligned (multiples of 128);
VMEM working set = bm*bk + bk*bn + bm*bn*4B ≈ 0.85 MB « 16 MB VMEM, leaving
room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(x_ref, w_ref):
    return jax.lax.dot_general(x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _kernel(x_ref, w_ref, colsum_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(x_ref, w_ref)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        # zero-point correction: (X - z)W = XW - z * colsum(W)
        # s_ref (SMEM) = [s_x * s_w, z_x]; colsum is a (1, bn) row
        acc = acc - s_ref[1] * colsum_ref[...].astype(jnp.float32)
        o_ref[...] = acc * s_ref[0]


def _raw_kernel(x_ref, w_ref, o_ref):
    # int32 product only: the output block stays resident across k
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += _dot(x_ref, w_ref)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "epilogue",
                                             "interpret"))
def w8a8_matmul(x_int: jax.Array, w_int: jax.Array, s_x, z_x, s_w,
                colsum: jax.Array | None = None,
                bm: int = 256, bn: int = 512, bk: int = 256,
                epilogue: bool = True,
                interpret: bool = False) -> jax.Array:
    """x_int: (M,K) int8; w_int: (K,N) int8; s_x/z_x/s_w scalar fp32.
    Returns fp32 (M,N) = (x - z_x) @ w * s_x * s_w.

    M may be ragged (serving token counts): the grid tiles M with a fixed
    block and the LAST tile is a partial boundary block — Pallas masks its
    out-of-bounds store rows and pads its out-of-bounds load rows, whose
    garbage never lands anywhere. No pad-to-max copy of the activations is
    ever materialized (the old path zero-padded (M,K) up to the tile in
    HBM, which at prefill sizes cost more than the matmul it fed). K/N are
    weight dimensions — static per checkpoint — and must tile exactly.

    colsum: optional precomputed (N,) or (1, N) int32 column sums of
    ``w_int`` — the prequantized serving path stores them with the int8
    weights so the zero-point correction never re-reduces the weight per
    call. The kernel reads them as a (1, bn) row (a rank-1 block would not
    match the TPU's layout for the array) and the two scalars from SMEM.

    ``epilogue=False`` returns the raw int32 product X_int @ W_int and
    ignores the scalars and colsum: a contraction split across devices
    sums these partials exactly and applies the epilogue once after."""
    M, K = x_int.shape
    K2, N = w_int.shape
    assert K == K2
    bn, bk = min(bn, N), min(bk, K)
    assert N % bn == 0 and K % bk == 0, \
        f"weight dims ({K},{N}) must tile by ({bk},{bn})"
    # fixed M tile, sublane-aligned (int8 min tile is (32, 128)): small M
    # (decode) gets one snug block, large M (prefill) a grid of full tiles
    # plus one masked boundary block
    bm = min(bm, -(-M // 32) * 32)
    n_k = K // bk
    grid = (-(-M // bm), N // bn, n_k)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    if not epilogue:
        return pl.pallas_call(
            _raw_kernel, grid=grid, name="w8a8_matmul",
            in_specs=[x_spec, w_spec], out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
            interpret=interpret,
        )(x_int, w_int)
    if colsum is None:
        colsum = jnp.sum(w_int.astype(jnp.int32), axis=0)   # (N,), tiny
    colsum = colsum.astype(jnp.int32).reshape(1, N)
    scalars = jnp.stack([
        jnp.asarray(s_x, jnp.float32) * jnp.asarray(s_w, jnp.float32),
        jnp.asarray(z_x, jnp.float32)]).reshape(2)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        name="w8a8_matmul",
        in_specs=[
            x_spec, w_spec,
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_int, w_int, colsum, scalars)
