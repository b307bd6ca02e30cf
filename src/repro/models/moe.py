"""Mixture-of-Experts decoder (olmoe: 64 experts, top-8; arctic: 128
experts, top-2, with a dense residual branch).

Dispatch is dropless and sorted: each token's top-k (token, expert) rows
are sorted by expert, every expert multiplies its contiguous run of rows in
one grouped matmul (``core.quantization.grouped_qdot``: the Pallas
``w8a8_gmm`` kernel for int8 experts on TPU, a plain jnp product
elsewhere), and the rows go back to their tokens, summed with their router
weights. No token is dropped and no buffer holds padding rows, in prefill,
decode or calibration alike.

``prefill`` and ``decode_step`` take ``route_counts=True`` and then also
return int32 [rows routed, experts reached, rows dropped], summed over the
layers (``ROUTE_COUNTS``): the serving engine reads them with the step's
tokens.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, QuantConfig
from repro.core import quantization as Q
from repro.distributed.sharding import constrain
from repro.models import common as C
from repro.models import transformer as T

Array = jax.Array
Params = Dict[str, Any]

SITES = C.ATTN_SITES + ("mlp_in", "down")

# Attention-KV-only prefix artifact -> eligible for the greedy-search
# KV-reuse scoring fast path (ModelAPI.score_candidates). Dispatch is per
# token, so a token's expert output does not depend on the other tokens of
# the sequence: scoring [candidate; sample] against the cached prefix KV
# gives the sample's tokens the expert outputs a full forward of
# [prefix; candidate; sample] gives them, and the "down" site, like every
# other, leaves out the first n_skip positions of each sequence.
SUPPORTS_PREFIX_KV_SCORING = True
ROUTE_COUNTS = True


def moe_init(key, cfg: ModelConfig) -> Params:
    moe = cfg.moe
    dt = C.dtype_of(cfg)
    E, D, F = moe.num_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    std_in = 1.0 / np.sqrt(D)
    std_out = 1.0 / np.sqrt(F) / np.sqrt(2 * cfg.n_layers)
    p = {
        "router": (jax.random.normal(ks[0], (D, E), jnp.float32) * std_in),
        "w_up": (jax.random.normal(ks[1], (E, D, F), jnp.float32) * std_in).astype(dt),
        "w_gate": (jax.random.normal(ks[2], (E, D, F), jnp.float32) * std_in).astype(dt),
        "w_down": (jax.random.normal(ks[3], (E, F, D), jnp.float32) * std_out).astype(dt),
    }
    if moe.dense_residual_ff:
        p["residual"] = C.mlp_init(ks[4], cfg, d_ff=moe.dense_residual_ff)
    return p


def route(p: Params, x: Array, cfg: ModelConfig
          ) -> Tuple[Array, Array, Array]:
    """x: (T, D) -> (top-k weights (T, K) f32, their experts (T, K) int32,
    router probabilities (T, E)). The weights are the softmax
    probabilities, renormalized over the top k where ``norm_topk_prob``."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"], axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.moe.top_k)
    if cfg.moe.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_w, top_idx, probs


def apply_moe(p: Params, x: Array, cfg: ModelConfig, qcfg: QuantConfig,
              scales: Optional[Params], taps: Optional[Dict],
              n_skip: int = 0) -> Tuple[Array, Array, Array]:
    """Returns (y, load_balance_loss, counts): counts is int32 [rows
    routed, experts reached, rows dropped]. The expert weights may be the
    stacked view ``{**stack, "layer": i}`` the serving scans pass."""
    moe = cfg.moe
    B, S, D = x.shape
    E, K = moe.num_experts, moe.top_k
    xt = x.reshape(B * S, D)
    top_w, top_idx, probs = route(p, xt, cfg)

    # rows t*K + k sorted by expert; group_sizes counts each expert's rows
    flat = top_idx.reshape(-1)
    onehot = flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :]
    group_sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    order = jnp.argsort(flat, stable=True)
    tok = order // K
    back = jnp.argsort(order)

    # Switch-style load-balance loss: E * sum_e frac_e * mean(prob_e)
    frac = group_sizes.astype(jnp.float32) / (B * S)
    lb = E * jnp.sum(frac * jnp.mean(probs, axis=0))

    if taps is not None:
        taps["mlp_in"] = {
            "qerr": Q.site_qerr(x, qcfg, C.get_site(scales, "mlp_in"), n_skip),
            **Q.site_stats(x, n_skip)}

    qs = C.get_site(scales, "mlp_in")
    xr = xt[tok]
    up = Q.grouped_qdot(xr, p["w_up"], group_sizes, qcfg, qs)
    gate = Q.grouped_qdot(xr, p["w_gate"], group_sizes, qcfg, qs)
    h = jax.nn.silu(gate) * up
    if taps is not None:
        # every row is a real (token, expert) row; those of the first
        # n_skip positions of each sequence are left out, as at every site
        valid = (tok % S) >= n_skip if n_skip else None
        site = C.get_site(scales, "down")
        taps["down"] = {"qerr": Q.site_qerr(h, qcfg, site, valid=valid),
                        **Q.site_stats(h, valid=valid)}
    yr = Q.grouped_qdot(h, p["w_down"], group_sizes, qcfg,
                        C.get_site(scales, "down"))
    y = jnp.sum(yr[back].reshape(B * S, K, D).astype(jnp.float32)
                * top_w[..., None], axis=1)
    y = constrain(y.reshape(B, S, D).astype(x.dtype), "B")

    if "residual" in p:
        # Arctic: dense FFN branch in parallel with the MoE branch
        y = y + C.apply_mlp(p["residual"], x, cfg, qcfg, scales, None, n_skip)
    rows = jnp.sum(group_sizes)
    counts = jnp.stack([rows, jnp.sum(group_sizes > 0),
                        B * S * K - rows]).astype(jnp.int32)
    return y, lb, counts


def serving_layers(layers: Params):
    """The layer params a serving scan runs over, with every int8-resident
    expert stack taken out and the layer index put in, and ``bind(lp)``,
    which hands a layer's params its experts back as views of the whole
    stacks, ``{**stack, "layer": i}``. The grouped kernel reads a layer's
    experts out of the stack by index, so the scan never slices a layer's
    experts out of it (a copy of every expert's weights, each layer, each
    step). Float experts stay in the scanned params."""
    moe = layers["moe"]
    stacks = {k: v for k, v in moe.items()
              if k in Q.EXPERT_KEYS and isinstance(v, dict)}
    if not stacks:
        return layers, lambda lp: lp
    n_layers = jax.tree_util.tree_leaves(stacks)[0].shape[0]
    xs = {**layers, "moe": {k: v for k, v in moe.items() if k not in stacks},
          "layer": jnp.arange(n_layers, dtype=jnp.int32)}

    def bind(lp):
        lp = dict(lp)
        i = lp.pop("layer")
        lp["moe"] = {**lp["moe"],
                     **{k: {**st, "layer": i} for k, st in stacks.items()}}
        return lp

    return xs, bind


def layer_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln1": C.norm_init(cfg), "attn": C.attn_init(k1, cfg),
            "ln2": C.norm_init(cfg), "moe": moe_init(k2, cfg)}


def init_params(cfg: ModelConfig, rng) -> Params:
    k_emb, k_layers = jax.random.split(rng)
    layers = jax.vmap(lambda k: layer_init(k, cfg))(
        jax.random.split(k_layers, cfg.n_layers))
    p = C.embed_init(k_emb, cfg)
    p["layers"] = layers
    p["ln_f"] = C.norm_init(cfg)
    return p


def _empty_prefix(cfg: ModelConfig, dtype) -> Params:
    return {"k": jnp.zeros((cfg.n_layers, 0, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((cfg.n_layers, 0, cfg.n_kv_heads, cfg.head_dim), dtype)}


def forward(params: Params, tokens: Array, cfg: ModelConfig,
            qcfg: QuantConfig, *, scales: Optional[Params] = None,
            cushion: Optional[Params] = None, collect: bool = False,
            n_skip: int = 0, prepend_embeds: Optional[Array] = None,
            remat: bool = True, prefix_valid: Optional[Array] = None,
            pos_offset: Optional[Array] = None) -> Tuple[Array, Dict]:
    x = C.embed_tokens(params, tokens, cfg)
    if prepend_embeds is not None:
        x = jnp.concatenate([prepend_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    m = 0 if cushion is None else cushion["kv"]["k"].shape[1]
    positions = (m if pos_offset is None else pos_offset) + jnp.arange(S)
    lscales = C.resolve_scales(scales, SITES, cfg.n_layers, qcfg)
    pre = cushion["kv"] if cushion is not None else _empty_prefix(cfg, x.dtype)

    def body(h, xs):
        lp, lsc, lpre = xs
        taps: Optional[Dict] = {} if collect else None
        hn = C.apply_norm(lp["ln1"], h, cfg)
        if collect:
            taps["block_in"] = Q.site_stats(h, n_skip)
        a = C.attention_full(lp["attn"], hn, cfg, qcfg, lsc, taps, positions,
                             prefix_kv=lpre, causal=True, n_skip=n_skip,
                             prefix_valid=prefix_valid)
        h = h + a
        hn = C.apply_norm(lp["ln2"], h, cfg)
        y, lb, _ = apply_moe(lp["moe"], hn, cfg, qcfg, lsc, taps, n_skip)
        h = constrain(h + y, "B")
        return h, ((taps if collect else {}), lb)

    if remat:
        body = jax.checkpoint(body)
    x, (layer_taps, lbs) = jax.lax.scan(body, x, (params["layers"], lscales, pre))
    x = C.apply_norm(params["ln_f"], x, cfg)
    head_taps: Optional[Dict] = {} if collect else None
    logits = C.lm_head(params, x, cfg, qcfg, scales, head_taps, n_skip)
    taps: Dict = {}
    if collect:
        taps = {"layers": layer_taps, **(head_taps or {}),
                "final_in": Q.site_stats(x, n_skip)}
    taps["lb_loss"] = jnp.mean(lbs)
    return logits, taps


init_cache = T.init_cache
cushion_zeros = T.cushion_zeros
write_cushion_to_cache = T.write_cushion_to_cache
finalize_staged_kv = T.finalize_staged_kv
cache_roles = T.cache_roles
placeholder_all_scales = T.placeholder_all_scales
CACHE_BATCH_AXES = T.CACHE_BATCH_AXES
PAGED_KV_LEAVES = T.PAGED_KV_LEAVES
SUPPORTS_CHUNKED_PREFILL = T.SUPPORTS_CHUNKED_PREFILL


def prefill(params: Params, tokens: Array, cache: Params, cfg: ModelConfig,
            qcfg: QuantConfig, *, scales: Optional[Params] = None,
            cushion: Optional[Params] = None,
            prepend_embeds: Optional[Array] = None,
            remat: bool = False,
            pos_offset: Optional[int] = None,
            route_counts: bool = False):
    """(logits, cache, pos), and the routing counts last with
    ``route_counts`` (see the module)."""
    x = C.embed_tokens(params, tokens, cfg)
    if prepend_embeds is not None:
        x = jnp.concatenate([prepend_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    if pos_offset is not None:
        # chunk-resume (see transformer.prefill): read the cushion + earlier
        # chunks back out of the B=1 fp staging row as the visible prefix
        if cushion is not None:
            raise ValueError("chunk-resume prefill attaches the cushion on "
                             "chunk 0 only (pos_offset excludes cushion)")
        if "k_scale" in cache or cache["k"].shape[1] != 1:
            raise ValueError("chunk-resume prefill needs a B=1 fp staging row")
        m = int(pos_offset)
        pre = {"k": jax.lax.slice_in_dim(cache["k"], 0, m, axis=2)[:, 0],
               "v": jax.lax.slice_in_dim(cache["v"], 0, m, axis=2)[:, 0]}
    else:
        cache, m = write_cushion_to_cache(cache, cushion)
        pre = (cushion["kv"] if cushion is not None
               else _empty_prefix(cfg, x.dtype))
    positions = m + jnp.arange(S)
    lscales = C.resolve_scales(scales, SITES, cfg.n_layers, qcfg)
    layers, bind = serving_layers(params["layers"])

    def body(h, xs):
        lp, lsc, lpre = xs
        lp = bind(lp)
        hn = C.apply_norm(lp["ln1"], h, cfg)
        a, kv = C.attention_full(lp["attn"], hn, cfg, qcfg, lsc, None,
                                 positions, prefix_kv=lpre, causal=True,
                                 return_kv=True)
        h = h + a
        hn = C.apply_norm(lp["ln2"], h, cfg)
        y, _, counts = apply_moe(lp["moe"], hn, cfg, qcfg, lsc, None)
        h = constrain(h + y, "B")
        return h, (kv, counts)

    if remat:
        body = jax.checkpoint(body)
    x, ((ks, vs), counts) = jax.lax.scan(body, x, (layers, lscales, pre))
    cache = T.write_prompt_kv(cache, ks, vs, m)
    x = C.apply_norm(params["ln_f"], x, cfg)
    logits = C.lm_head(params, x[:, -1:], cfg, qcfg, scales, None)
    out = (logits, cache, jnp.asarray(m + S, jnp.int32))
    return out + (jnp.sum(counts, axis=0),) if route_counts else out


def decode_step(params: Params, token: Array, pos: Array, cache: Params,
                cfg: ModelConfig, qcfg: QuantConfig, *,
                scales: Optional[Params] = None, route_counts: bool = False):
    """One decode step; pos may be () shared or (B,) per-row (continuous
    batching). Dispatch is per token, so lock-step decode of independent
    slots stays row-local. Returns (logits, cache), and the routing counts
    last with ``route_counts`` (see the module)."""
    x = C.embed_tokens(params, token[:, None], cfg)
    lscales = C.resolve_scales(scales, SITES, cfg.n_layers, qcfg)
    layers, bind = serving_layers(params["layers"])

    def block(lp, lsc, carry, kvc):
        h, total = carry
        lp = bind(lp)
        hn = C.apply_norm(lp["ln1"], h, cfg)
        a, kvc = C.attention_decode_kv(lp["attn"], hn, kvc, pos, cfg, qcfg,
                                       lsc, None)
        h = h + a
        hn = C.apply_norm(lp["ln2"], h, cfg)
        y, _, counts = apply_moe(lp["moe"], hn, cfg, qcfg, lsc, None)
        return (h + y, total + counts), kvc

    (x, counts), cache = C.decode_layers(
        block, (x, jnp.zeros((3,), jnp.int32)), layers, lscales, cache)
    x = C.apply_norm(params["ln_f"], x, cfg)
    logits = C.lm_head(params, x, cfg, qcfg, scales, None)
    if route_counts:
        return logits[:, 0], cache, counts
    return logits[:, 0], cache


def loss_fn(params: Params, tokens: Array, labels: Array, cfg: ModelConfig,
            qcfg: QuantConfig, *, scales=None, cushion=None,
            collect: bool = False, n_skip: int = 0, remat: bool = True,
            lam: float = 0.0):
    logits, taps = forward(params, tokens, cfg, qcfg, scales=scales,
                           cushion=cushion, collect=collect or lam > 0,
                           n_skip=n_skip, remat=remat)
    if n_skip:
        logits = logits[:, n_skip:]
        labels = labels[:, n_skip:]
    ce = C.cross_entropy(logits, labels)
    loss = ce + cfg.moe.load_balance_coef * taps["lb_loss"]
    aux = {"ce": ce, "taps": taps, "lb": taps["lb_loss"]}
    if lam > 0 or collect:
        qerr = T.total_qerr(taps)
        aux["qerr"] = qerr
        if lam > 0:
            loss = loss + lam * qerr
    return loss, aux
