"""Compile the serving kernels for a described TPU v5e, without a chip.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: blocks whose last two dims are neither tile-aligned nor full,
rank-1 blocks against the array's layout, unsupported relayouts, VMEM
overuse. These tests lower each kernel at Qwen1.5-0.5B widths (B=8,
K=H=16 heads, head_dim 64, max_seq 2048, page 64, d=1024, d_ff=2816, fused
qkv N=3072, weight group 128) against a ``v5e:2x2`` topology description
and compile it, asserting the kernel reaches the program as a
``tpu_custom_call``. The topology is built inside module fixtures, never at
import, so only the worker that runs this file loads the TPU compiler. The
persistent compilation cache is off around the compiles: a TPU executable
written here could not be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quantization import _tile
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.w4a8_matmul import w4a8_matmul
from repro.kernels.w8a8_matmul import w8a8_matmul

B, HEADS, HD, SMAX, PS, CUSHION = 8, 16, 64, 2048, 64, 8
N_PAGES = B * SMAX // PS + 1
GROUP = 128
# (K, N) of the quantized linears: qkv, mlp up/gate, mlp down
LINEARS = [(1024, 3072), (1024, 2816), (2816, 1024)]
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip):
    """compile_tpu(fn, *(shape, dtype)) -> compiled text, cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kv(dtype, paged=False):
    shape = (N_PAGES, PS, HEADS, HD) if paged else (B, SMAX, HEADS, HD)
    return [(shape, dtype), (shape, dtype)]


def test_flash_decode_fp(compile_tpu):
    text = compile_tpu(lambda q, k, v, pos: flash_decode(q, k, v, pos),
                       ((B, HEADS, HD), BF16), *_kv(BF16), ((B,), I32))
    assert "tpu_custom_call" in text


def test_flash_decode_int8_cushion(compile_tpu):
    text = compile_tpu(
        lambda q, k, v, pos, ks, vs, kc, vc: flash_decode(
            q, k, v, pos, k_scale=ks, v_scale=vs, kc=kc, vc=vc),
        ((B, HEADS, HD), BF16), *_kv(I8), ((B,), I32),
        ((B, HEADS), F32), ((B, HEADS), F32),
        ((CUSHION, HEADS, HD), BF16), ((CUSHION, HEADS, HD), BF16))
    assert "tpu_custom_call" in text


def test_flash_decode_paged_fp_cushion(compile_tpu):
    text = compile_tpu(
        lambda q, k, v, pt, pos, kc, vc: flash_decode_paged(
            q, k, v, pt, pos, kc=kc, vc=vc),
        ((B, HEADS, HD), BF16), *_kv(BF16, paged=True),
        ((B, SMAX // PS), I32), ((B,), I32),
        ((CUSHION, HEADS, HD), BF16), ((CUSHION, HEADS, HD), BF16))
    assert "tpu_custom_call" in text


def test_flash_decode_paged_int8(compile_tpu):
    text = compile_tpu(
        lambda q, k, v, pt, pos, ks, vs, kc, vc: flash_decode_paged(
            q, k, v, pt, pos, k_scale=ks, v_scale=vs, kc=kc, vc=vc),
        ((B, HEADS, HD), BF16), *_kv(I8, paged=True),
        ((B, SMAX // PS), I32), ((B,), I32),
        ((B, HEADS), F32), ((B, HEADS), F32),
        ((CUSHION, HEADS, HD), BF16), ((CUSHION, HEADS, HD), BF16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("K,N", LINEARS)
@pytest.mark.parametrize("M", [8, 500], ids=["decode", "prefill"])
def test_w8a8_matmul(compile_tpu, M, K, N):
    """Blocks as the serving route picks them (core.quantization)."""
    text = compile_tpu(
        lambda x, w, cs: w8a8_matmul(x, w, 0.02, 3.0, 0.01, colsum=cs,
                                     bn=_tile(N, 512), bk=_tile(K, 256)),
        ((M, K), I8), ((K, N), I8), ((N,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("K,N", LINEARS)
@pytest.mark.parametrize("M", [8, 500], ids=["decode", "prefill"])
def test_w4a8_matmul(compile_tpu, M, K, N):
    text = compile_tpu(
        lambda x, w, sw, cs: w4a8_matmul(x, w, 0.02, 3.0, sw, cs,
                                         group_size=GROUP,
                                         bn=_tile(N, 512)),
        ((M, K), I8), ((K // 2, N), I8), ((K // GROUP, N), F32),
        ((N,), F32))
    assert "tpu_custom_call" in text
