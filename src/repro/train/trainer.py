"""Distributed training loop: pjit train step with FSDP/TP shardings,
gradient accumulation (scan over microbatches), remat-in-scan, ZeRO-1
optimizer states, and the quantization-aware-training path (fake-quant
forward) used by the paper's prefix tuning at framework scale.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import QuantConfig, RunConfig
from repro.distributed import sharding as SH
from repro.models.registry import ModelAPI, build
from repro.optim.adamw import AdamW, AdamWState, cosine_lr


@dataclasses.dataclass
class TrainState:
    params: Any
    opt: AdamWState
    step: int


def make_optimizer(run: RunConfig) -> AdamW:
    return AdamW(lr=cosine_lr(run.lr, run.warmup_steps, run.train_steps),
                 weight_decay=run.weight_decay, grad_clip=run.grad_clip)


def make_train_step(api: ModelAPI, run: RunConfig, opt: AdamW,
                    microbatches: int = 1,
                    cushion: Any = None, scales: Any = None
                    ) -> Callable:
    """Builds train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). With microbatches > 1, the global batch is split and gradients
    accumulated in a scan (memory-bound shapes)."""
    qcfg = run.quant

    def loss(params, batch):
        l, aux = api.loss_fn(params, batch, qcfg, cushion=cushion,
                             scales=scales, remat=run.parallel.remat)
        return l, aux

    def grads_of(params, batch):
        (l, aux), g = jax.value_and_grad(loss, has_aux=True)(params, batch)
        return l, aux, g

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            l, aux, g = grads_of(params, batch)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mb = jax.tree_util.tree_map(split, batch)

            def body(carry, b):
                acc, lsum = carry
                li, _, gi = grads_of(params, b)
                acc = jax.tree_util.tree_map(jnp.add, acc, gi)
                return (acc, lsum + li), ()

            zeros = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), params)
            (g, lsum), _ = jax.lax.scan(body, (zeros, jnp.zeros(())), mb)
            g = jax.tree_util.tree_map(lambda a: a / microbatches, g)
            l = lsum / microbatches
            aux = {}
        params, opt_state, om = opt.update(g, opt_state, params)
        metrics = {"loss": l, **{k: v for k, v in om.items()}}
        if isinstance(aux, dict) and "ce" in aux:
            metrics["ce"] = aux["ce"]
        return params, opt_state, metrics

    return train_step


def replicated_shardings(tree: Any, mesh: Mesh) -> Any:
    """Every leaf fully replicated across `mesh` — the layout for small
    trainable trees (the cushion KV block and its optimizer moments) that
    ride a data axis for batch parallelism only."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda _: rep, tree)


def shard_update_step(step_fn: Callable, mesh: Mesh, var_shardings: Any,
                      opt_shardings: Any, batch_like: Any = None,
                      n_extra: int = 0):
    """jit-compile an ``(vars, opt_state, batch) -> (vars, opt_state,
    metrics)`` update step for `mesh`: carried state in/out under the given
    shardings and DONATED (compile-once, no per-step copies), batch leaves
    split on the "data" axis when `batch_like` (arrays or ShapeDtypeStructs;
    only ndim matters) is given. ``n_extra`` trailing arguments (frozen
    params) keep the sharding they arrive with and are not donated. Shared
    by `shard_train_step` (FSDP param shardings) and
    `cushioncache.prefix_tune` (replicated cushion)."""
    if batch_like is None:
        b_sh = None
    else:
        b_sh = jax.tree_util.tree_map(
            lambda x: SH.batch_sharding(mesh, x.ndim), batch_like)
    return jax.jit(
        step_fn,
        in_shardings=(var_shardings, opt_shardings, b_sh)
        + (None,) * n_extra,
        out_shardings=(var_shardings, opt_shardings, None),
        donate_argnums=(0, 1))


def shard_train_step(api: ModelAPI, run: RunConfig, opt: AdamW, mesh: Mesh,
                     params_abstract: Any, microbatches: int = 1,
                     cushion: Any = None, scales: Any = None):
    """pjit-compile the train step for `mesh` with the partition rules.
    Returns (jitted_fn, param_shardings, batch_shardings)."""
    p_sh = SH.params_shardings(params_abstract, mesh)
    opt_abstract = jax.eval_shape(opt.init, params_abstract)
    o_sh = AdamWState(
        step=NamedSharding(mesh, P()),
        mu=SH.params_shardings(opt_abstract.mu, mesh),
        nu=SH.params_shardings(opt_abstract.nu, mesh))
    step_fn = make_train_step(api, run, opt, microbatches, cushion, scales)
    fn = shard_update_step(step_fn, mesh, p_sh, o_sh)
    return fn, p_sh, o_sh


def eval_ppl(api: ModelAPI, params, batches, qcfg: QuantConfig,
             cushion=None, scales=None) -> float:
    """Perplexity over an eval set (paper Tables 1/4 metric)."""
    fn = jax.jit(lambda p, b: api.loss_fn(
        p, b, qcfg, cushion=cushion, scales=scales, remat=False)[1]["ce"])
    tot, n = 0.0, 0
    for b in batches:
        tot += float(fn(params, b))
        n += 1
    return float(np.exp(tot / max(n, 1)))


def eval_next_token_acc(api: ModelAPI, params, batches, qcfg: QuantConfig,
                        cushion=None, scales=None) -> float:
    """Next-token top-1 accuracy — the zero-shot-accuracy stand-in for
    Table 2 at CPU scale."""
    @jax.jit
    def fn(p, b):
        logits, _ = api.forward(p, b, qcfg, cushion=cushion, scales=scales,
                                remat=False)
        # pipeline labels are pre-shifted: labels[:, i] = tokens[:, i+1]
        pred = jnp.argmax(logits, axis=-1)
        return jnp.mean((pred == b["labels"]).astype(jnp.float32))
    vals = [float(fn(params, b)) for b in batches]
    return float(np.mean(vals))
