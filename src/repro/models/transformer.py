"""Dense decoder-only transformer (llama-style; qwen QKV-bias variant via
config). Defines the canonical model API all families follow:

    init_params(cfg, rng)                     -> params
    forward(params, tokens, cfg, qcfg, ...)   -> (logits, taps)   # full seq
    init_cache(cfg, B, Smax, ...)             -> cache
    prefill(params, tokens, cache, ...)       -> (logits, cache, pos)
    decode_step(params, token, pos, cache,..) -> (logits, cache)

The layer stack is a `lax.scan` over stacked per-layer params so the lowered
HLO is O(1) in depth (critical for the 95-layer dry-runs), with optional
remat on the scan body.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, QuantConfig
from repro.core import quantization as Q
from repro.distributed.sharding import constrain
from repro.models import common as C

Array = jax.Array
Params = Dict[str, Any]

SITES = C.ATTN_SITES + C.MLP_SITES  # ("qkv", "o", "mlp_in", "down")

# The prefix deployment artifact is pure attention KV, so the greedy-search
# fast path can prefill the shared prefix once and score every candidate
# against the cached block (ModelAPI.score_candidates).
SUPPORTS_PREFIX_KV_SCORING = True

# prefill() accepts pos_offset to resume a partially-written fp cache row:
# the scheduler's chunked admission replays a prompt chunk-by-chunk, reading
# everything before the chunk (cushion included) back out of the row as the
# fully-visible prefix. Families whose prompt pass is not a pure causal
# attention-KV scan (ssm state, encdec cross-KV, vlm patch prepend) stay on
# blocking admission.
SUPPORTS_CHUNKED_PREFILL = True

# Continuous-batching slot layout: batch axis of every per-request cache
# leaf (init_cache puts batch second, after the layer axis). The scheduler
# scatters a B=1 prefilled cache row into its slot along these axes and
# relies on decode_step accepting a (B,) per-row pos vector.
CACHE_BATCH_AXES = {"k": 1, "v": 1}

# Leaves the paged pool (ContinuousEngine(paged=True)) re-lays into a flat
# page store + per-slot page table instead of slot-scattering; every other
# CACHE_BATCH_AXES entry keeps its dense per-slot row. Families without
# this marker (ssm, encdec) have no pageable sequence cache.
PAGED_KV_LEAVES = ("k", "v")


def layer_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln1": C.norm_init(cfg), "attn": C.attn_init(k1, cfg),
            "ln2": C.norm_init(cfg), "mlp": C.mlp_init(k2, cfg)}


def init_params(cfg: ModelConfig, rng) -> Params:
    k_emb, k_layers = jax.random.split(rng)
    layers = jax.vmap(lambda k: layer_init(k, cfg))(
        jax.random.split(k_layers, cfg.n_layers))
    p = C.embed_init(k_emb, cfg)
    p["layers"] = layers
    p["ln_f"] = C.norm_init(cfg)
    return p


def _block(lp: Params, x: Array, cfg: ModelConfig, qcfg: QuantConfig,
           lsc: Optional[Params], lpre: Optional[Params], positions: Array,
           collect: bool, n_skip: int,
           prefix_valid: Optional[Array] = None) -> Tuple[Array, Dict]:
    taps: Optional[Dict] = {} if collect else None
    h = C.apply_norm(lp["ln1"], x, cfg)
    if collect:
        taps["block_in"] = Q.site_stats(x, n_skip)
    a = C.attention_full(lp["attn"], h, cfg, qcfg, lsc, taps, positions,
                         prefix_kv=lpre, causal=True, n_skip=n_skip,
                         prefix_valid=prefix_valid)
    x = x + a
    h = C.apply_norm(lp["ln2"], x, cfg)
    m = C.apply_mlp(lp["mlp"], h, cfg, qcfg, lsc, taps, n_skip)
    x = x + m
    x = constrain(x, "B")
    return x, (taps if collect else {})


def forward(params: Params, tokens: Array, cfg: ModelConfig,
            qcfg: QuantConfig, *, scales: Optional[Params] = None,
            cushion: Optional[Params] = None, collect: bool = False,
            n_skip: int = 0, prepend_embeds: Optional[Array] = None,
            remat: bool = True, prefix_valid: Optional[Array] = None,
            pos_offset: Optional[Array] = None) -> Tuple[Array, Dict]:
    """Full-sequence causal forward. cushion: {"kv": {"k": (L,m,K,hd), ...}}.
    prepend_embeds (B,P,D): extra embeddings placed before the token
    embeddings (VLM patches / greedy-search candidate activations).

    prefix_valid / pos_offset serve the compile-once search scoring path:
    the cushion KV is padded to a fixed shape, prefix_valid ((m,) bool)
    masks the dead rows, and pos_offset (dynamic scalar) replaces the static
    prefix length as the RoPE position origin of x's tokens."""
    x = C.embed_tokens(params, tokens, cfg)
    if prepend_embeds is not None:
        x = jnp.concatenate([prepend_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    m = 0 if cushion is None else cushion["kv"]["k"].shape[1]
    positions = (m if pos_offset is None else pos_offset) + jnp.arange(S)

    lscales = C.resolve_scales(scales, SITES, cfg.n_layers, qcfg)
    head_sc = scales

    def body(h, xs):
        lp, lsc, lpre = xs
        h, taps = _block(lp, h, cfg, qcfg, lsc, lpre, positions, collect,
                         n_skip, prefix_valid=prefix_valid)
        return h, taps

    if remat:
        body = jax.checkpoint(body)
    pre = cushion["kv"] if cushion is not None else None
    xs = (params["layers"], lscales, pre)
    if pre is None:
        # scan needs uniform xs; replace None with per-layer empty marker
        xs = (params["layers"], lscales,
              {"k": jnp.zeros((cfg.n_layers, 0, cfg.n_kv_heads, cfg.head_dim),
                              x.dtype),
               "v": jnp.zeros((cfg.n_layers, 0, cfg.n_kv_heads, cfg.head_dim),
                              x.dtype)})
    x, layer_taps = jax.lax.scan(body, x, xs)
    x = C.apply_norm(params["ln_f"], x, cfg)
    head_taps: Optional[Dict] = {} if collect else None
    logits = C.lm_head(params, x, cfg, qcfg, head_sc, head_taps, n_skip)
    if collect:
        taps = {"layers": layer_taps, **(head_taps or {}),
                "final_in": Q.site_stats(x, n_skip)}
    else:
        taps = {}
    return logits, taps


# ---------------------------------------------------------------------------
# Serving: prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None, kv_dtype=None, prefix_len: int = 0,
               per_slot_scales: bool = False) -> Params:
    """kv_dtype None -> fp cache {"k","v"}. kv_dtype "int8" -> quantized
    cache: int8 k/v storage (halves decode HBM traffic) + per-(layer,head)
    dequant scales + a full-precision cushion block kc/vc of `prefix_len`
    rows — the sink/pivot-token KV stays intact (KVSink/IntactKV) while the
    int8 tensors hold content positions [prefix_len:max_seq).

    per_slot_scales gives every batch row its own (layer, head) scales —
    shape (L, batch, K) — for the continuous-batching pool, where slots
    admitted at different times each calibrate scales from their own
    admission prefill."""
    dt = dtype or C.dtype_of(cfg)
    K, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    if kv_dtype is None:
        return {"k": jnp.zeros((L, batch, max_seq, K, hd), dt),
                "v": jnp.zeros((L, batch, max_seq, K, hd), dt)}
    if kv_dtype not in ("int8", jnp.int8):
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
    sshape = (L, batch, K) if per_slot_scales else (L, K)
    return {"k": jnp.zeros((L, batch, max_seq, K, hd), jnp.int8),
            "v": jnp.zeros((L, batch, max_seq, K, hd), jnp.int8),
            "k_scale": jnp.ones(sshape, jnp.float32),
            "v_scale": jnp.ones(sshape, jnp.float32),
            "kc": jnp.zeros((L, prefix_len, K, hd), dt),
            "vc": jnp.zeros((L, prefix_len, K, hd), dt)}


def cache_roles(cfg: ModelConfig, kv_dtype=None,
                per_slot_scales: bool = False) -> Params:
    """KV-cache sharding roles: (L, B, S, K, hd) — batch on B-axes, the
    KV-heads axis on "M" (tensor parallel). Head sharding makes decode
    attention collective-free: each shard attends its local heads against
    its local KV slice and only the o-projection psums, matching the
    flash-decode per-shard head slicing contract (kernels/ops.py
    ``decode_attention_tp``). When the head count doesn't divide the tp
    width the role resolver falls back to replicated for that leaf
    (sharding.roles_pspec). int8 scales shard with their (L, K) heads axis;
    the fp cushion block kc/vc stays REPLICATED — every shard holds the
    full sink block bit-identically (KVSink/IntactKV: the protected prefix
    must survive sharding exactly; consumers slice it per shard on entry)."""
    kv = (None, "B", None, "M", None)
    roles = {"k": kv, "v": kv}
    if kv_dtype is not None:
        sc = (None, "B", "M") if per_slot_scales else (None, "M")
        roles.update({"k_scale": sc, "v_scale": sc, "kc": (), "vc": ()})
    return roles


def write_cushion_to_cache(cache: Params, cushion: Optional[Params]) -> Tuple[Params, int]:
    if cushion is None:
        return cache, 0
    kv = cushion["kv"]
    m = kv["k"].shape[1]
    if "kc" in cache:
        # quantized cache: the cushion block is protected — stored fp,
        # never quantized (init_cache must have been given prefix_len == m)
        assert cache["kc"].shape[1] == m, \
            f"cache prefix_len {cache['kc'].shape[1]} != cushion len {m}"
        cache = dict(cache)
        cache["kc"] = kv["k"].astype(cache["kc"].dtype)
        cache["vc"] = kv["v"].astype(cache["vc"].dtype)
        return cache, m
    k = jnp.broadcast_to(kv["k"][:, None], (kv["k"].shape[0], cache["k"].shape[1]) + kv["k"].shape[1:])
    v = jnp.broadcast_to(kv["v"][:, None], (kv["v"].shape[0], cache["v"].shape[1]) + kv["v"].shape[1:])
    cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0)),
    }
    return cache, m


def write_prompt_kv(cache: Params, ks: Array, vs: Array, m: int) -> Params:
    """Write prefill KV (stacked (L,B,S,K,hd) fp) into the cache at absolute
    positions [m:m+S]. For int8 caches this also derives the static
    per-(layer,head) dequant scales from the prompt KV — decode steps reuse
    them (new tokens are clipped into the calibrated range). A cache with
    per-slot scale leaves ((L,B,K); continuous-batching admission rows)
    calibrates each batch row's scales from its own prompt instead."""
    if "k_scale" in cache:
        if cache["k_scale"].ndim == 3:      # per-slot (L, B, K)
            per_row = jax.vmap(jax.vmap(C.kv_scales_from))
            k_scale = per_row(ks)
            v_scale = per_row(vs)
            kq = jax.vmap(jax.vmap(C.quantize_kv))(ks, k_scale)
            vq = jax.vmap(jax.vmap(C.quantize_kv))(vs, v_scale)
        else:
            k_scale = jax.vmap(C.kv_scales_from)(ks)    # (L, K)
            v_scale = jax.vmap(C.kv_scales_from)(vs)
            kq = jax.vmap(C.quantize_kv)(ks, k_scale)
            vq = jax.vmap(C.quantize_kv)(vs, v_scale)
        cache = dict(cache)
        cache["k"] = jax.lax.dynamic_update_slice(cache["k"], kq,
                                                  (0, 0, m, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(cache["v"], vq,
                                                  (0, 0, m, 0, 0))
        cache["k_scale"], cache["v_scale"] = k_scale, v_scale
        return cache
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype), (0, 0, m, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype), (0, 0, m, 0, 0))
    return cache


def finalize_staged_kv(row: Params, cache: Params, cushion: Optional[Params],
                       S: int) -> Params:
    """Rebuild the admission row a *blocking* prefill would have produced
    from a chunk-staged fp row: slice the prompt KV [m:m+S) back out of the
    staging row and write it through the normal write_prompt_kv path, so an
    int8 cache calibrates its per-slot dequant scales from the WHOLE prompt
    (not per chunk — bit-identical to blocking admission) and the protected
    fp cushion block lands in kc/vc untouched."""
    cache, m = write_cushion_to_cache(cache, cushion)
    ks = jax.lax.slice_in_dim(row["k"], m, m + S, axis=2)
    vs = jax.lax.slice_in_dim(row["v"], m, m + S, axis=2)
    return write_prompt_kv(cache, ks, vs, m)


def prefill(params: Params, tokens: Array, cache: Params, cfg: ModelConfig,
            qcfg: QuantConfig, *, scales: Optional[Params] = None,
            cushion: Optional[Params] = None,
            prepend_embeds: Optional[Array] = None,
            remat: bool = False,
            pos_offset: Optional[int] = None) -> Tuple[Array, Params, Array]:
    """Process the prompt, fill the KV cache (cushion at [0:m], prompt at
    [m:m+S]). Returns (last-position logits, cache, next_pos).

    pos_offset (static int) resumes a chunked prefill: positions [0:pos_offset)
    of the B=1 fp cache row already hold the cushion plus every earlier chunk
    (written by a previous prefill call on the same row), and are read back as
    the fully-visible prefix for this chunk's tokens. The cushion must NOT be
    re-attached (chunk 0 only), and the row must be fp — int8 admission rows
    are rebuilt from the finished staging row by finalize_staged_kv so the
    per-slot scales still calibrate over the whole prompt."""
    x = C.embed_tokens(params, tokens, cfg)
    if prepend_embeds is not None:
        x = jnp.concatenate([prepend_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    if pos_offset is not None:
        if cushion is not None:
            raise ValueError("chunk-resume prefill attaches the cushion on "
                             "chunk 0 only (pos_offset excludes cushion)")
        if "k_scale" in cache:
            raise ValueError("chunk-resume prefill needs an fp staging row")
        if cache["k"].shape[1] != 1:
            raise ValueError("chunk-resume prefill is B=1 only")
        m = int(pos_offset)
        pre = {"k": jax.lax.slice_in_dim(cache["k"], 0, m, axis=2)[:, 0],
               "v": jax.lax.slice_in_dim(cache["v"], 0, m, axis=2)[:, 0]}
    else:
        cache, m = write_cushion_to_cache(cache, cushion)
        pre = cushion["kv"] if cushion is not None else {
            "k": jnp.zeros((cfg.n_layers, 0, cfg.n_kv_heads, cfg.head_dim),
                           x.dtype),
            "v": jnp.zeros((cfg.n_layers, 0, cfg.n_kv_heads, cfg.head_dim),
                           x.dtype)}
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
        h = h + C.apply_mlp(lp["mlp"], hn, cfg, qcfg, lsc, None)
        h = constrain(h, "B")
        return h, kv

    if remat:
        body = jax.checkpoint(body)
    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], lscales, pre))
    # ks: (L, B, S, K, hd) -> write into cache at [m : m+S] (int8 caches
    # also calibrate their per-(layer,head) scales here)
    cache = write_prompt_kv(cache, ks, vs, m)
    x = C.apply_norm(params["ln_f"], x, cfg)
    logits = C.lm_head(params, x[:, -1:], cfg, qcfg,
                       scales if scales is not None else None, None)
    return logits, cache, jnp.asarray(m + S, jnp.int32)


def decode_step(params: Params, token: Array, pos: Array, cache: Params,
                cfg: ModelConfig, qcfg: QuantConfig, *,
                scales: Optional[Params] = None) -> Tuple[Array, Params]:
    """One decode step. token: (B,) int32; pos: () int32 shared absolute
    position, or (B,) int32 per-row positions (cushion occupies [0:m),
    prompt/generated next). Per-row pos serves the continuous-batching
    scheduler: each cache slot decodes at its own offset, with RoPE, cache
    writes and attention masking all per-row (see attention_decode_kv)."""
    x = C.embed_tokens(params, token[:, None], cfg)
    lscales = C.resolve_scales(scales, SITES, cfg.n_layers, qcfg)

    def block(lp, lsc, h, kvc):
        hn = C.apply_norm(lp["ln1"], h, cfg)
        a, kvc = C.attention_decode_kv(lp["attn"], hn, kvc, pos, cfg, qcfg,
                                       lsc, None)
        h = h + a
        hn = C.apply_norm(lp["ln2"], h, cfg)
        h = h + C.apply_mlp(lp["mlp"], hn, cfg, qcfg, lsc, None)
        return h, kvc

    # every cache leaf is stacked over L; a paged pool's stores ride the
    # scan carry, written in place (common.decode_layers)
    x, cache = C.decode_layers(block, x, params["layers"], lscales, cache)
    x = C.apply_norm(params["ln_f"], x, cfg)
    logits = C.lm_head(params, x, cfg, qcfg,
                       scales if scales is not None else None, None)
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# Cushion KV parameter shape (for prefix tuning)
# ---------------------------------------------------------------------------

def cushion_zeros(cfg: ModelConfig, m: int, dtype=None) -> Params:
    # default to the model compute dtype: the artifact must match what
    # extract_cushion emits so serving's bit-identical cushion-rewrite
    # guarantee holds (a bf16 model keeps a bf16 cushion)
    dtype = C.dtype_of(cfg) if dtype is None else dtype
    K, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    return {"kv": {"k": jnp.zeros((L, m, K, hd), dtype),
                   "v": jnp.zeros((L, m, K, hd), dtype)}}


def loss_fn(params: Params, tokens: Array, labels: Array, cfg: ModelConfig,
            qcfg: QuantConfig, *, scales=None, cushion=None,
            collect: bool = False, n_skip: int = 0, remat: bool = True,
            lam: float = 0.0):
    """Next-token CE (+ optional λ·L_q when collecting)."""
    logits, taps = forward(params, tokens, cfg, qcfg, scales=scales,
                           cushion=cushion, collect=collect or lam > 0,
                           n_skip=n_skip, remat=remat)
    if n_skip:
        # loss on the token part only (prefix positions excluded)
        logits = logits[:, n_skip:]
        labels = labels[:, n_skip:]
    ce = C.cross_entropy(logits, labels)
    loss = ce
    aux = {"ce": ce, "taps": taps}
    if lam > 0 or collect:
        qerr = total_qerr(taps)
        aux["qerr"] = qerr
        if lam > 0:
            loss = loss + lam * qerr
    return loss, aux


def total_qerr(taps: Dict) -> Array:
    """Sum of L_q over all sites and layers (paper eq. 6, summed over
    blocks)."""
    leaves = []

    def visit(d):
        if isinstance(d, dict):
            if "qerr" in d:
                leaves.append(jnp.sum(d["qerr"]))
            else:
                for v in d.values():
                    visit(v)
    visit(taps)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return functools.reduce(jnp.add, leaves)


def placeholder_all_scales(cfg: ModelConfig) -> Params:
    """Full placeholder scales tree (incl. head) for quantized lowering
    without a calibration artifact (dry-runs)."""
    sc = C.placeholder_scales(SITES, cfg.n_layers)
    sc["head"] = Q.SiteScale(scale=jnp.ones(()), zero=jnp.zeros(()))
    return sc
