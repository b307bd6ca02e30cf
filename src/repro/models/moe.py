"""Mixture-of-Experts decoder (olmoe top-8; arctic 128e top-2 + dense
residual branch).

Expert dispatch is GShard/Switch-style capacity-based dense dispatch — the
canonical partitionable formulation under GSPMD: experts shard on `model`,
tokens on batch axes; the dispatch einsums lower to all-to-all-like
collectives. Capacity factor 1.25, dropped tokens pass through the residual.
"""
from __future__ import annotations

import functools
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
# KV-reuse scoring fast path (ModelAPI.score_candidates). Note the scoring
# contract for MoE: expert capacity is derived from the *scored* sequence
# ([candidate; sample]), and the "down" site qerr covers only that
# sequence's expert traffic — prefix tokens never re-enter the experts,
# matching deployment (the reference full-forward scorer routes prefix
# tokens through the experts as a side effect of recomputing them).
SUPPORTS_PREFIX_KV_SCORING = True


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


def capacity(seq: int, cfg: ModelConfig) -> int:
    moe = cfg.moe
    c = int(np.ceil(seq * moe.top_k / moe.num_experts * moe.capacity_factor))
    c = min(c, seq * moe.top_k)
    return max(4, int(np.ceil(c / 4)) * 4)


def apply_moe(p: Params, x: Array, cfg: ModelConfig, qcfg: QuantConfig,
              scales: Optional[Params], taps: Optional[Dict],
              n_skip: int = 0) -> Tuple[Array, Array]:
    """Returns (y, load_balance_loss)."""
    moe = cfg.moe
    B, S, D = x.shape
    E, K = moe.num_experts, moe.top_k
    Cp = capacity(S, cfg)

    gate_logits = x.astype(jnp.float32) @ p["router"]          # (B,S,E)
    gate_logits = constrain(gate_logits, "B", None, "M")
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, K)                    # (B,S,K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # Switch-style load-balance loss: E * sum_e mean(frac_e) * mean(prob_e)
    onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)      # (B,S,K,E)
    frac = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))       # (E,)
    lb = E * jnp.sum(frac * jnp.mean(probs, axis=(0, 1)))

    # capacity assignment: position of each (token, k) within its expert
    flat = onehot.reshape(B, S * K, E)
    pos = jnp.cumsum(flat, axis=1) - 1.0                         # (B,S*K,E)
    keep = (pos < Cp) * flat
    slot = jax.nn.one_hot(pos, Cp, dtype=jnp.float32) * keep[..., None]
    disp = slot.reshape(B, S, K, E, Cp).astype(x.dtype)          # (B,S,K,E,C)
    disp = constrain(disp, "B", None, None, "M", None)
    comb = jnp.einsum("bsk,bskec->bsec", top_w.astype(x.dtype), disp)
    disp_tok = jnp.sum(disp, axis=2)                             # (B,S,E,C)

    if taps is not None:
        taps["mlp_in"] = {
            "qerr": Q.site_qerr(x, qcfg, C.get_site(scales, "mlp_in"), n_skip),
            **Q.site_stats(x, n_skip)}

    xin = jnp.einsum("bsec,bsd->ebcd", disp_tok, x)              # (E,B,C,D)
    xin = constrain(xin, "M", "B", None, None)
    qs = C.get_site(scales, "mlp_in")
    xq = Q.act_fake_quant(xin, qcfg, qs.scale if qs else None,
                          qs.zero if qs else None)
    up = jnp.einsum("ebcd,edf->ebcf", xq, Q.weight_fake_quant(p["w_up"], qcfg))
    gate = jnp.einsum("ebcd,edf->ebcf", xq,
                      Q.weight_fake_quant(p["w_gate"], qcfg))
    h = jax.nn.silu(gate) * up
    h = constrain(h, "M", "B", None, None)
    if taps is not None:
        taps["down"] = {
            "qerr": Q.site_qerr(h, qcfg, C.get_site(scales, "down"), 0),
            **Q.site_stats(h, 0)}
    qs2 = C.get_site(scales, "down")
    hq = Q.act_fake_quant(h, qcfg, qs2.scale if qs2 else None,
                          qs2.zero if qs2 else None)
    out = jnp.einsum("ebcf,efd->ebcd", hq,
                     Q.weight_fake_quant(p["w_down"], qcfg))
    y = jnp.einsum("bsec,ebcd->bsd", comb, out)
    y = constrain(y, "B")

    if "residual" in p:
        # Arctic: dense FFN branch in parallel with the MoE branch
        y = y + C.apply_mlp(p["residual"], x, cfg, qcfg, scales, None, n_skip)
    return y, lb


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
        y, lb = apply_moe(lp["moe"], hn, cfg, qcfg, lsc, taps, n_skip)
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
            pos_offset: Optional[int] = None) -> Tuple[Array, Params, Array]:
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

    def body(h, xs):
        lp, lsc, lpre = xs
        hn = C.apply_norm(lp["ln1"], h, cfg)
        a, kv = C.attention_full(lp["attn"], hn, cfg, qcfg, lsc, None,
                                 positions, prefix_kv=lpre, causal=True,
                                 return_kv=True)
        h = h + a
        hn = C.apply_norm(lp["ln2"], h, cfg)
        y, _ = apply_moe(lp["moe"], hn, cfg, qcfg, lsc, None)
        h = constrain(h + y, "B")
        return h, kv

    if remat:
        body = jax.checkpoint(body)
    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], lscales, pre))
    cache = T.write_prompt_kv(cache, ks, vs, m)
    x = C.apply_norm(params["ln_f"], x, cfg)
    logits = C.lm_head(params, x[:, -1:], cfg, qcfg, scales, None)
    return logits, cache, jnp.asarray(m + S, jnp.int32)


def decode_step(params: Params, token: Array, pos: Array, cache: Params,
                cfg: ModelConfig, qcfg: QuantConfig, *,
                scales: Optional[Params] = None) -> Tuple[Array, Params]:
    """One decode step; pos may be () shared or (B,) per-row (continuous
    batching). Expert capacity/dispatch is per-row at S=1, so lock-step
    decode of independent slots stays row-local."""
    x = C.embed_tokens(params, token[:, None], cfg)
    lscales = C.resolve_scales(scales, SITES, cfg.n_layers, qcfg)

    def block(lp, lsc, h, kvc):
        hn = C.apply_norm(lp["ln1"], h, cfg)
        a, kvc = C.attention_decode_kv(lp["attn"], hn, kvc, pos, cfg, qcfg,
                                       lsc, None)
        h = h + a
        hn = C.apply_norm(lp["ln2"], h, cfg)
        y, _ = apply_moe(lp["moe"], hn, cfg, qcfg, lsc, None)
        h = h + y
        return h, kvc

    x, cache = C.decode_layers(block, x, params["layers"], lscales, cache)
    x = C.apply_norm(params["ln_f"], x, cfg)
    logits = C.lm_head(params, x, cfg, qcfg, scales, None)
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
