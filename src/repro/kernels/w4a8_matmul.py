"""Pallas TPU kernel: W4A8 matmul — int4-packed weights unpacked in VMEM.

Weights stream HBM->VMEM as nibble-packed int8 (0.5 byte/weight — half the
W8A8 traffic, the whole point at bandwidth-bound decode), are sign-extended
to int8 values *in VMEM* (two arithmetic shifts + a sublane concatenation,
VPU work that overlaps the MXU), and feed the same int8 MXU product as
``w8a8_matmul``.
Weight scales are group-wise along the contracting dim: each k-block sits
inside exactly one group (``bk`` must divide ``group_size``), so the block's
int32 partial product is scaled by one (1, bn) scale row (read from the
scales viewed as (G, 1, N): a (1, bn) block of the (G, N) array is refused
by the TPU compiler) and accumulated in an f32 VMEM scratch. The epilogue applies the activation scale and the
asymmetric zero-point correction  -z_x * colsum  where ``colsum`` is the
*scale-weighted* column sum  sum_g s_w[g,n] * colsum_g[n]  precomputed at
prequantize time — group scales never touch the epilogue's rank-1 subtract.

Packing layout (``core.quantization.pack_int4``): byte i of a packed column
holds element 2i in its low nibble and 2i+1 in its high nibble. Unpacking
that in VMEM would be a sublane interleave, which Mosaic does not lower;
instead the wrapper permutes the *activation* columns of every k-block to
[even | odd] order (a cheap XLA shuffle of the int8 activations), so the
block's weight rows are simply [low nibbles; high nibbles] — an aligned
sublane concatenation. The ragged-M grid is inherited from ``w8a8_matmul``:
fixed sublane-aligned M tile, masked boundary block, no pad-to-max copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wp_ref, scale_ref, colsum_ref, s_ref, o_ref, acc_ref, *,
            n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # unpack the (bk//2, bn) nibble block to (bk, bn) in VMEM: low nibble
    # sign-extends from bit 3, high nibble is the arithmetic floor-division
    # of the two's-complement byte; rows come out as [even k; odd k], the
    # order the wrapper gave the activation columns
    p = wp_ref[...].astype(jnp.int32)
    lo = (p << 28) >> 28
    hi = p >> 4
    w_blk = jnp.concatenate([lo, hi], axis=0)
    blk = jax.lax.dot_general(
        x_ref[...], w_blk.astype(jnp.int8), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)    # int8 x int8 on the MXU
    # one group scale row per k-block (bk divides group_size)
    acc_ref[...] += blk.astype(jnp.float32) * scale_ref[...]

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        # zero-point correction: (X - z)W = XW - z * colsum(W); colsum
        # already carries the group scales, so only s_x remains
        acc = acc_ref[...] - s_ref[0] * colsum_ref[...]
        o_ref[...] = acc * s_ref[1]


@functools.partial(jax.jit, static_argnames=("group_size", "bm", "bn", "bk",
                                             "interpret"))
def w4a8_matmul(x_int: jax.Array, w_packed: jax.Array, s_x, z_x, s_w,
                colsum: jax.Array, group_size: int,
                bm: int = 256, bn: int = 512, bk: int = 256,
                interpret: bool = False) -> jax.Array:
    """x_int: (M,K) int8; w_packed: (K//2,N) int8 nibble pairs; s_x/z_x
    scalar fp32; s_w: (K//group_size, N) fp32 group scales; colsum: (N,)
    or (1, N) fp32 scale-weighted column sums. Returns fp32
    (M,N) = s_x * (sum_g s_w[g] * (x[:,g] - z_x) @ w[g]).

    M may be ragged (serving token counts): fixed sublane-aligned M tile,
    partial boundary block masked by Pallas — same grid as ``w8a8_matmul``.
    K and N are weight dims, static per checkpoint: K must be even and
    groups must tile it; ``bk`` is clamped to a power-of-two block that
    divides ``group_size`` so every k-block reads exactly one scale row.
    """
    M, K = x_int.shape
    Kp, N = w_packed.shape
    assert K % 2 == 0 and Kp * 2 == K, \
        f"packed contracting dim mismatch: K={K}, packed rows={Kp}"
    G = s_w.shape[0]
    assert G * group_size == K, \
        f"groups ({G} x {group_size}) must tile the contracting dim ({K})"
    bn = min(bn, N)
    while N % bn:
        bn //= 2
    # largest power-of-two k-block <= bk that divides the group (so the
    # scale row is constant per block) and keeps the packed rows even
    bk = min(bk, group_size)
    while group_size % bk or bk % 2:
        bk //= 2
    assert bk >= 2, f"group_size ({group_size}) must be even"
    bm = min(bm, -(-M // 32) * 32)
    n_k = K // bk
    spg = group_size // bk                       # k-blocks per scale row
    scale = jnp.asarray(s_w, jnp.float32).reshape(G, 1, N)
    scalars = jnp.stack([jnp.asarray(z_x, jnp.float32).reshape(()),
                         jnp.asarray(s_x, jnp.float32).reshape(())])
    # within each k-block, even activation columns first, then odd ones:
    # they meet the low and the high nibbles of the packed rows
    x_eo = x_int.reshape(M, K // bk, bk // 2, 2).swapaxes(2, 3).reshape(M, K)

    grid = (-(-M // bm), N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        name="w4a8_matmul",
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((None, 1, bn), lambda i, j, k: (k // spg, 0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x_eo, w_packed, scale, colsum.astype(jnp.float32).reshape(1, N),
      scalars)
