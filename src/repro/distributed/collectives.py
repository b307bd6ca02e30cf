"""Collective-communication optimizations.

`compressed_psum`: int8-quantized gradient all-reduce — the paper's
per-tensor-static-quantization insight applied to *training* comms: one
fp32 scale per tensor (one tiny all-reduce) plus an int8 payload cuts
DCN/pod-axis gradient traffic ~4x vs fp32 (~2x vs bf16).

`dp_train_step_compressed`: a shard_map data-parallel step using it.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import shard_map


def compressed_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce-mean with int8 payload + per-tensor fp32 scale.

    1. all-reduce(max |x|)  — scalar
    2. quantize to int8 symmetric with that global scale
    3. all-reduce int32 accumulate, dequantize, divide by world size
    """
    n = jax.lax.psum(1, axis_name)
    amax = jax.lax.pmax(jnp.max(jnp.abs(x.astype(jnp.float32))), axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                  ).astype(jnp.int8)
    acc = jax.lax.psum(xq.astype(jnp.int32), axis_name)
    return (acc.astype(jnp.float32) * scale / n).astype(x.dtype)


def dp_train_step_compressed(grad_fn: Callable, mesh: Mesh,
                             axis_name: str = "data"):
    """Data-parallel gradient computation with compressed all-reduce.

    grad_fn(params, batch) -> (loss, grads) computed on the local shard;
    params replicated, batch split along `axis_name`. Returns a callable
    (params, batch) -> (loss_mean, grads_mean) with int8 gradient comms.
    """
    def local(params, batch):
        loss, grads = grad_fn(params, batch)
        loss = jax.lax.pmean(loss, axis_name)
        grads = jax.tree_util.tree_map(
            lambda g: compressed_psum(g, axis_name), grads)
        return loss, grads

    batch_spec = P(axis_name)
    return shard_map(local, mesh, in_specs=(P(), batch_spec),
                            out_specs=(P(), P()))


def collective_bytes_of_hlo(hlo_text: str) -> dict:
    """Parse optimized HLO, summing result-shape bytes of every collective
    op — the §Roofline collective term source."""
    import re
    dtype_bytes = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                   "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1,
                   "u8": 1, "pred": 1, "c64": 8, "f8e4m3fn": 1,
                   "f8e5m2": 1}
    ops = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
           "collective-permute")
    totals = {op: 0 for op in ops}
    counts = {op: 0 for op in ops}
    # e.g.:  %all-gather.1 = bf16[8,128,2048]{...} all-gather(...)
    pat = re.compile(
        r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^)=]*?\s("
        + "|".join(ops) + r")(?:-start|-done)?\(")
    for m in pat.finditer(hlo_text):
        dt, shape_s, op = m.group(1), m.group(2), m.group(3)
        if dt == "tuple":
            continue
        nelem = 1
        if shape_s:
            for d in shape_s.split(","):
                nelem *= int(d)
        totals[op] += nelem * dtype_bytes.get(dt, 4)
        counts[op] += 1
    totals["total"] = sum(totals[o] for o in ops)
    totals["counts"] = counts
    return totals
