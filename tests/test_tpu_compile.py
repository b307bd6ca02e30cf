"""Compile the serving kernels for a described TPU v5e, without a chip.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: blocks whose last two dims are neither tile-aligned nor full,
rank-1 blocks against the array's layout, unsupported relayouts, VMEM
overuse. These tests lower each kernel at Qwen1.5-0.5B widths (B=8,
K=H=16 heads, head_dim 64, max_seq 2048, page 64, d=1024, d_ff=2816, fused
qkv N=3072, weight group 128) against a ``v5e:2x2`` topology description
and compile it, asserting the kernel reaches the program as a
``tpu_custom_call``. The paged decode step and the admission's page
scatter compile at the same widths with two layers and 1057 pages, and
must write the lane-dense page store in place: no copy, slice or
slice-update of the store or of one layer of it, and temporaries under
one layer's store. At OLMoE-1B-7B widths (16 KV heads at head_dim 128, 64
experts of width 1024, 833 pages) the int8 decode step and the admission
must do the same, and no op may copy, slice or stack a whole layer's
expert stack: the grouped kernel reads each layer's experts out of the
stack by index. The topology is built inside module fixtures, never at
import, so only the worker that runs this file loads the TPU compiler. The
persistent compilation cache is off around the compiles: a TPU executable
written here could not be read back without a chip.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import QuantConfig, get_config
from repro.core import quantization as Q
from repro.core.quantization import _tile
from repro.kernels.flash_decode import (flash_decode, flash_decode_paged,
                                        page_rows)
from repro.kernels.w4a8_matmul import w4a8_matmul
from repro.kernels.w8a8_gmm import w8a8_gmm
from repro.kernels.w8a8_matmul import w8a8_matmul
from repro.models.registry import build
from repro.serving.scheduler import scatter_pages

B, HEADS, HD, SMAX, PS, CUSHION = 8, 16, 64, 2048, 64, 8
# the paged store: two layers of the decode cell's 1057-page pool
LAYERS, N_PAGES = 2, 1057
ROWS = page_rows(HD)
STORE = (LAYERS, N_PAGES, PS // ROWS, HEADS, ROWS * HD)
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

    def run(fn, *shapes, donate=(), compiled=False):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        c = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        return c if compiled else c.as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kv(dtype, paged=False):
    shape = STORE if paged else (B, SMAX, HEADS, HD)
    return [(shape, dtype), (shape, dtype)]


def _store_moves(text, store=STORE):
    """The compiled program's copies, slices and slice-updates whose result
    has the page store's shape or one layer's, with or without a unit
    layer axis: what writing in place rules out."""
    shapes = {",".join(map(str, store)), ",".join(map(str, store[1:])),
              ",".join(map(str, (1,) + store[1:]))}
    return _moves(text, shapes)


def _moves(text, shapes):
    pat = re.compile(r"= \w+\[([\d,]+)\]\S* "
                     r"(copy|dynamic-slice|dynamic-update-slice)\(")
    return [line.strip() for line in text.splitlines()
            if (m := pat.search(line)) and m.group(1) in shapes]


LAYER_BYTES = N_PAGES * PS * HEADS * HD * 2     # one bf16 layer of the store


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
    """The layer-indexed kernel over the stacked lane-dense store."""
    text = compile_tpu(
        lambda q, k, v, pt, pos, layer, kc, vc: flash_decode_paged(
            q, k, v, pt, pos, layer, kc=kc, vc=vc),
        ((B, HEADS, HD), BF16), *_kv(BF16, paged=True),
        ((B, SMAX // PS), I32), ((B,), I32), ((), I32),
        ((CUSHION, HEADS, HD), BF16), ((CUSHION, HEADS, HD), BF16))
    assert "tpu_custom_call" in text


def test_flash_decode_paged_int8(compile_tpu):
    text = compile_tpu(
        lambda q, k, v, pt, pos, layer, ks, vs, kc, vc: flash_decode_paged(
            q, k, v, pt, pos, layer, k_scale=ks, v_scale=vs, kc=kc, vc=vc),
        ((B, HEADS, HD), BF16), *_kv(I8, paged=True),
        ((B, SMAX // PS), I32), ((B,), I32), ((), I32),
        ((B, HEADS), F32), ((B, HEADS), F32),
        ((CUSHION, HEADS, HD), BF16), ((CUSHION, HEADS, HD), BF16))
    assert "tpu_custom_call" in text


def test_paged_decode_step_writes_store_in_place(compile_tpu, monkeypatch):
    """``decode_step`` on a paged cache, jitted with the cache donated, at
    Qwen1.5-0.5B widths: the layer scan carries the store and the kernel
    reads it by layer index, so nothing copies, slices out or stacks back
    the store or a layer of it."""
    # the described chip is not the backend JAX runs on here: route decode
    # attention to the compiled kernel as it is routed on a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b"), n_layers=LAYERS)
    api = build(cfg)
    params = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0)))
    leaves, tree = jax.tree_util.tree_flatten(params)
    slots, pages = 24, 44
    cache = {"k": STORE, "v": STORE, "page_table": (LAYERS, slots, pages),
             "kc": (LAYERS, 16, HEADS, HD), "vc": (LAYERS, 16, HEADS, HD)}
    dtypes = {"page_table": I32}
    names = list(cache)

    def step(*args):
        p = jax.tree_util.tree_unflatten(tree, args[:len(leaves)])
        tok, pos = args[len(leaves):len(leaves) + 2]
        c = dict(zip(names, args[len(leaves) + 2:]))
        return api.decode_step(p, tok, pos, c, QuantConfig(mode="none"))

    donate = tuple(range(len(leaves) + 2, len(leaves) + 2 + len(names)))
    compiled = compile_tpu(
        step, *[(x.shape, x.dtype) for x in leaves], ((slots,), I32),
        ((slots,), I32), *[(cache[n], dtypes.get(n, BF16)) for n in names],
        donate=donate, compiled=True)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _store_moves(text) == []
    assert compiled.memory_analysis().temp_size_in_bytes < LAYER_BYTES


def test_admit_paged_scatter_in_place(compile_tpu):
    """The admission's page scatter of a B=1 row into the donated store:
    only the row is re-laid out, the store is updated in place."""
    max_seq = 44 * PS
    compiled = compile_tpu(
        lambda k, v, rk, rv, idx: (scatter_pages(k, rk, idx),
                                   scatter_pages(v, rv, idx)),
        *_kv(BF16, paged=True), ((LAYERS, 1, max_seq, HEADS, HD), BF16),
        ((LAYERS, 1, max_seq, HEADS, HD), BF16), ((max_seq // PS,), I32),
        donate=(0, 1), compiled=True)
    assert _store_moves(compiled.as_text()) == []
    assert compiled.memory_analysis().temp_size_in_bytes < LAYER_BYTES


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


# OLMoE-1B-7B: 16/16 heads at head_dim 128 (one position per lane row), 64
# experts of width 1024 at d=2048, the code cell's 16 slots over 833 pages
OLMOE_STORE = (LAYERS, 833, PS // page_rows(128), 16, 128)
OLMOE_LAYER_BYTES = 833 * PS * 16 * 128 * 2
EXPERTS, D_MODEL, D_FF = 64, 2048, 1024


def _expert_moves(text):
    """Copies, slices and slice-updates of a whole layer's experts (int8 or
    bf16, with or without a unit layer axis) or of the stack."""
    shapes = set()
    for k, n in ((D_MODEL, D_FF), (D_FF, D_MODEL)):
        for lead in ((EXPERTS,), (1, EXPERTS), (LAYERS, EXPERTS)):
            shapes.add(",".join(map(str, lead + (k, n))))
    return _moves(text, shapes)


def test_olmoe_decode_step_in_place(compile_tpu, monkeypatch):
    """The W8A8 decode step at OLMoE widths (two layers, QK-norm, int8
    experts, the routing counters): the store is written in place and no
    op copies an expert stack."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("olmoe-1b-7b"), n_layers=LAYERS)
    api = build(cfg)
    qcfg = QuantConfig(mode="pt_static", true_int8=True)
    params = jax.eval_shape(lambda: Q.prequantize_tree(
        api.init_params(jax.random.PRNGKey(0)), qcfg))
    scales = api.mod.placeholder_all_scales(cfg)
    leaves, tree = jax.tree_util.tree_flatten(params)
    slots, pages = 16, 52
    cache = {"k": OLMOE_STORE, "v": OLMOE_STORE,
             "page_table": (LAYERS, slots, pages),
             "kc": (LAYERS, 16, 16, 128), "vc": (LAYERS, 16, 16, 128)}
    dtypes = {"page_table": I32}
    names = list(cache)

    def step(*args):
        p = jax.tree_util.tree_unflatten(tree, args[:len(leaves)])
        tok, pos = args[len(leaves):len(leaves) + 2]
        c = dict(zip(names, args[len(leaves) + 2:]))
        return api.decode_step(p, tok, pos, c, qcfg, scales=scales,
                               route_counts=True)

    donate = tuple(range(len(leaves) + 2, len(leaves) + 2 + len(names)))
    compiled = compile_tpu(
        step, *[(x.shape, x.dtype) for x in leaves], ((slots,), I32),
        ((slots,), I32), *[(cache[n], dtypes.get(n, BF16)) for n in names],
        donate=donate, compiled=True)
    text = compiled.as_text()
    assert "w8a8_gmm" in text and "flash_decode_paged" in text
    assert _store_moves(text, OLMOE_STORE) == []
    assert _expert_moves(text) == []
    assert compiled.memory_analysis().temp_size_in_bytes < OLMOE_LAYER_BYTES


def test_olmoe_admit_paged_scatter_in_place(compile_tpu):
    max_seq = 52 * PS
    compiled = compile_tpu(
        lambda k, v, rk, rv, idx: (scatter_pages(k, rk, idx),
                                   scatter_pages(v, rv, idx)),
        (OLMOE_STORE, BF16), (OLMOE_STORE, BF16),
        ((LAYERS, 1, max_seq, 16, 128), BF16),
        ((LAYERS, 1, max_seq, 16, 128), BF16), ((max_seq // PS,), I32),
        donate=(0, 1), compiled=True)
    assert _store_moves(compiled.as_text(), OLMOE_STORE) == []
    assert compiled.memory_analysis().temp_size_in_bytes < OLMOE_LAYER_BYTES


@pytest.mark.parametrize("K,N", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
@pytest.mark.parametrize("M,tm", [(128, 32), (25856, 128)],
                         ids=["decode", "prefill"])
def test_w8a8_gmm(compile_tpu, M, tm, K, N):
    """The grouped kernel over a stacked layer of experts, with the row
    tiles the serving route picks for 16 slots x 8 experts and for a
    3232-token prompt."""
    text = compile_tpu(
        lambda x, w, gs, sc, cs, ly: w8a8_gmm(x, w, gs, 0.02, 3.0, sc, cs,
                                              ly, tm=tm, tn=_tile(N, 1024)),
        ((M, K), I8), ((LAYERS, EXPERTS, K, N), I8), ((EXPERTS,), I32),
        ((LAYERS, EXPERTS, N), F32), ((LAYERS, EXPERTS, N), I32), ((), I32))
    assert "tpu_custom_call" in text
