"""Jamba-style hybrid: periods of `period` layers, attention at
`attn_at` indices, Mamba elsewhere; each layer followed by an MLP — MoE on
layers with index % moe_every == moe_offset, dense otherwise.

Layer stack is a scan over *periods* (stacked period params), with the
period's sub-layers unrolled — HLO is O(period), not O(n_layers).

Cushion: attention layers get the paper's prefix-KV; Mamba layers get the
CushionState analogue (trainable initial state). See DESIGN.md §5.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, QuantConfig
from repro.core import quantization as Q
from repro.distributed.sharding import constrain
from repro.models import common as C
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import transformer as T

Array = jax.Array
Params = Dict[str, Any]

SITES = ("qkv", "o", "mamba_in", "mamba_out", "mlp_in", "down")

# Greedy-search scoring fallback: the hybrid prefix artifact includes Mamba
# recurrent state, and a fixed-shape padded prefix cannot be masked out of a
# recurrence (dead rows would corrupt the state). The search therefore falls
# back to `cushioncache.greedy_search_ref` (full forward per candidate,
# shapes grow with the prefix — one recompile per appended token).
SUPPORTS_PREFIX_KV_SCORING = False

# Continuous batching IS supported: attention leaves batch on axis 1, Mamba
# state leaves on axis 2 (after period & sublayer axes); slot admission
# scatters the whole per-request row (KV + recurrent state) at once.
CACHE_BATCH_AXES = {"k": 1, "v": 1, "h": 2, "conv": 2}

# Attention KV pages; Mamba state stays a dense per-slot row (fixed-size
# recurrent state has nothing to page).
PAGED_KV_LEAVES = ("k", "v")


def layout(cfg: ModelConfig):
    h = cfg.hybrid
    assert cfg.n_layers % h.period == 0
    n_periods = cfg.n_layers // h.period
    kinds = []
    for i in range(h.period):
        mixer = "attn" if i in h.attn_at else "mamba"
        mlp = "moe" if i % h.moe_every == h.moe_offset else "dense"
        kinds.append((mixer, mlp))
    return n_periods, kinds


def period_init(key, cfg: ModelConfig) -> Params:
    _, kinds = layout(cfg)
    p: Params = {"sub": []}
    ks = jax.random.split(key, len(kinds))
    for k, (mixer, mlp) in zip(ks, kinds):
        k1, k2 = jax.random.split(k)
        sub = {"ln1": C.norm_init(cfg), "ln2": C.norm_init(cfg)}
        if mixer == "attn":
            sub["attn"] = C.attn_init(k1, cfg)
        else:
            sub["mamba"] = SSM.mamba_init(k1, cfg)
        if mlp == "moe":
            sub["moe"] = MOE.moe_init(k2, cfg)
        else:
            sub["mlp"] = C.mlp_init(k2, cfg)
        p["sub"].append(sub)
    return p


def init_params(cfg: ModelConfig, rng) -> Params:
    n_periods, _ = layout(cfg)
    k_emb, k_layers = jax.random.split(rng)
    layers = jax.vmap(lambda k: period_init(k, cfg))(
        jax.random.split(k_layers, n_periods))
    p = C.embed_init(k_emb, cfg)
    p["layers"] = layers
    p["ln_f"] = C.norm_init(cfg)
    return p


def _merge_taps(acc: Optional[Dict], new: Optional[Dict]) -> Optional[Dict]:
    if new is None:
        return acc
    if acc is None:
        acc = {}
    for site, st in new.items():
        if site not in acc:
            acc[site] = st
        else:
            a = acc[site]
            merged = {
                "amin": jnp.minimum(a["amin"], st["amin"]),
                "amax": jnp.maximum(a["amax"], st["amax"]),
                "absmax_ch": jnp.maximum(a["absmax_ch"], st["absmax_ch"])
                if a["absmax_ch"].shape == st["absmax_ch"].shape else a["absmax_ch"],
            }
            if "qerr" in a and "qerr" in st:
                merged["qerr"] = a["qerr"] + st["qerr"]
            acc[site] = merged
    return acc


def _period_apply(pp: Params, x: Array, cfg: ModelConfig, qcfg: QuantConfig,
                  lsc: Optional[Params], positions, prefix_kv,
                  mamba_states, collect: bool, n_skip: int,
                  return_states: bool):
    """Apply one period. prefix_kv: dict(k,v) (m,K,hd) or None — shared by
    the period's attention layers. mamba_states: list aligned to mamba
    sublayers (or None)."""
    _, kinds = layout(cfg)
    taps_acc: Optional[Dict] = {} if collect else None
    lb_total = jnp.zeros((), jnp.float32)
    new_states = []
    mi = 0
    for j, (mixer, mlp) in enumerate(kinds):
        sub = pp["sub"][j]
        taps: Optional[Dict] = {} if collect else None
        hn = C.apply_norm(sub["ln1"], x, cfg)
        if collect:
            taps["block_in"] = Q.site_stats(x, n_skip)
        if mixer == "attn":
            o = C.attention_full(sub["attn"], hn, cfg, qcfg, lsc, taps,
                                 positions, prefix_kv=prefix_kv, causal=True,
                                 n_skip=n_skip)
        else:
            st = mamba_states[mi] if mamba_states is not None else None
            if return_states:
                o, new_st = SSM.apply_mamba(sub["mamba"], hn, cfg, qcfg, lsc,
                                            taps, n_skip, init_state=st,
                                            return_state=True)
                new_states.append(new_st)
            else:
                o = SSM.apply_mamba(sub["mamba"], hn, cfg, qcfg, lsc, taps,
                                    n_skip, init_state=st)
            mi += 1
        x = x + o
        hn = C.apply_norm(sub["ln2"], x, cfg)
        if mlp == "moe":
            y, lb, _ = MOE.apply_moe(sub["moe"], hn, cfg, qcfg, lsc, taps,
                                     n_skip)
            lb_total = lb_total + lb
        else:
            y = C.apply_mlp(sub["mlp"], hn, cfg, qcfg, lsc, taps, n_skip)
        x = constrain(x + y, "B")
        if collect:
            taps_acc = _merge_taps(taps_acc, taps)
    return x, taps_acc, lb_total, new_states


def n_mamba_per_period(cfg: ModelConfig) -> int:
    _, kinds = layout(cfg)
    return sum(1 for m, _ in kinds if m == "mamba")


def cushion_zeros(cfg: ModelConfig, m: int, dtype=None) -> Params:
    """Prefix KV for the attention layers + initial states for the Mamba
    layers (batch-free; broadcast at use). Defaults to the model compute
    dtype (see transformer.cushion_zeros)."""
    dtype = C.dtype_of(cfg) if dtype is None else dtype
    n_periods, _ = layout(cfg)
    K, hd = cfg.n_kv_heads, cfg.head_dim
    nm = n_mamba_per_period(cfg)
    inner, d_state, d_conv, _ = SSM.dims(cfg)
    return {
        "kv": {"k": jnp.zeros((n_periods, m, K, hd), dtype),
               "v": jnp.zeros((n_periods, m, K, hd), dtype)},
        "state": {"h": jnp.zeros((n_periods, nm, inner, d_state), dtype),
                  "conv": jnp.zeros((n_periods, nm, d_conv - 1, inner), dtype)},
    }


def forward(params: Params, tokens: Array, cfg: ModelConfig,
            qcfg: QuantConfig, *, scales: Optional[Params] = None,
            cushion: Optional[Params] = None, collect: bool = False,
            n_skip: int = 0, prepend_embeds: Optional[Array] = None,
            remat: bool = True, return_cache: bool = False):
    x = C.embed_tokens(params, tokens, cfg)
    if prepend_embeds is not None:
        x = jnp.concatenate([prepend_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    n_periods, kinds = layout(cfg)
    nm = n_mamba_per_period(cfg)
    m = 0 if cushion is None else cushion["kv"]["k"].shape[1]
    positions = m + jnp.arange(S)
    lscales = C.resolve_scales(scales, SITES, n_periods, qcfg)

    if cushion is not None:
        pre_kv = cushion["kv"]
        mstates = cushion["state"]
    else:
        K, hd = cfg.n_kv_heads, cfg.head_dim
        pre_kv = {"k": jnp.zeros((n_periods, 0, K, hd), x.dtype),
                  "v": jnp.zeros((n_periods, 0, K, hd), x.dtype)}
        mstates = None

    def body(h, xs):
        if mstates is None:
            pp, lsc, pkv = xs
            mst = None
        else:
            pp, lsc, pkv, mst_raw = xs
            mst = [{"h": mst_raw["h"][i], "conv": mst_raw["conv"][i]}
                   for i in range(nm)]
        h, taps, lb, new_st = _period_apply(
            pp, h, cfg, qcfg, lsc, positions, pkv, mst, collect, n_skip,
            return_states=return_cache)
        ys = ((taps if collect else {}), lb)
        if return_cache:
            ys = ys + ({"h": jnp.stack([s["h"] for s in new_st]),
                        "conv": jnp.stack([s["conv"] for s in new_st])},)
        return h, ys

    if remat:
        body = jax.checkpoint(body)
    xs = (params["layers"], lscales, pre_kv)
    if mstates is not None:
        xs = xs + (mstates,)
    x, ys = jax.lax.scan(body, x, xs)
    layer_taps, lbs = ys[0], ys[1]
    x = C.apply_norm(params["ln_f"], x, cfg)
    head_taps: Optional[Dict] = {} if collect else None
    logits = C.lm_head(params, x, cfg, qcfg, scales, head_taps, n_skip)
    taps: Dict = {"lb_loss": jnp.mean(lbs)}
    if collect:
        taps.update({"layers": layer_taps, **(head_taps or {}),
                     "final_in": Q.site_stats(x, n_skip)})
    if return_cache:
        return logits, taps, ys[2]
    return logits, taps


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None,
               kv_dtype=None, prefix_len: int = 0,
               per_slot_scales: bool = False) -> Params:
    """kv_dtype "int8": attention KV stored int8 with per-(period,head)
    scales — per-slot (P, batch, K) when ``per_slot_scales`` (continuous
    pool) — and a protected fp cushion block (see transformer.init_cache);
    Mamba states always stay fp."""
    dt = dtype or C.dtype_of(cfg)
    n_periods, _ = layout(cfg)
    nm = n_mamba_per_period(cfg)
    K, hd = cfg.n_kv_heads, cfg.head_dim
    inner, d_state, d_conv, _ = SSM.dims(cfg)
    cache = {
        "k": jnp.zeros((n_periods, batch, max_seq, K, hd), dt),
        "v": jnp.zeros((n_periods, batch, max_seq, K, hd), dt),
        "h": jnp.zeros((n_periods, nm, batch, inner, d_state), jnp.float32),
        "conv": jnp.zeros((n_periods, nm, batch, d_conv - 1, inner), dt),
    }
    if kv_dtype is not None:
        if kv_dtype not in ("int8", jnp.int8):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
        cache["k"] = cache["k"].astype(jnp.int8)
        cache["v"] = cache["v"].astype(jnp.int8)
        sshape = ((n_periods, batch, K) if per_slot_scales
                  else (n_periods, K))
        cache.update({
            "k_scale": jnp.ones(sshape, jnp.float32),
            "v_scale": jnp.ones(sshape, jnp.float32),
            "kc": jnp.zeros((n_periods, prefix_len, K, hd), dt),
            "vc": jnp.zeros((n_periods, prefix_len, K, hd), dt)})
    return cache


def cache_roles(cfg: ModelConfig, kv_dtype=None,
                per_slot_scales: bool = False) -> Params:
    """Serve-pool sharding roles (see transformer.cache_roles): attention
    KV (P, B, S, K, hd) shards its heads axis on "M"; the Mamba state
    shards its channel axes — h (P, nm, B, inner, d_state) on inner, conv
    (P, nm, B, d_conv-1, inner) on inner — mirroring the mamba/w_x "M"
    param rules so the recurrence stays shard-local. int8 scales shard
    with their heads axis; the fp cushion block is replicated."""
    kv = (None, "B", None, "M", None)
    roles = {"k": kv, "v": kv,
             "h": (None, None, "B", "M", None),
             "conv": (None, None, "B", None, "M")}
    if kv_dtype is not None:
        sc = (None, "B", "M") if per_slot_scales else (None, "M")
        roles.update({"k_scale": sc, "v_scale": sc, "kc": (), "vc": ()})
    return roles


def prefill(params: Params, tokens: Array, cache: Params, cfg: ModelConfig,
            qcfg: QuantConfig, *, scales: Optional[Params] = None,
            cushion: Optional[Params] = None,
            prepend_embeds: Optional[Array] = None, remat: bool = False):
    """Full-pass prefill that also materializes the cache. For simplicity it
    recomputes per-period KV by re-running attention sublayers with
    return_kv; batch sizes at prefill are modest."""
    x = C.embed_tokens(params, tokens, cfg)
    if prepend_embeds is not None:
        x = jnp.concatenate([prepend_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    n_periods, kinds = layout(cfg)
    nm = n_mamba_per_period(cfg)
    m = 0 if cushion is None else cushion["kv"]["k"].shape[1]
    positions = m + jnp.arange(S)
    lscales = C.resolve_scales(scales, SITES, n_periods, qcfg)
    K, hd = cfg.n_kv_heads, cfg.head_dim
    if cushion is not None:
        pre_kv = cushion["kv"]
        mst0 = cushion["state"]
    else:
        pre_kv = {"k": jnp.zeros((n_periods, 0, K, hd), x.dtype),
                  "v": jnp.zeros((n_periods, 0, K, hd), x.dtype)}
        mst0 = None

    def body(h, xs):
        if mst0 is None:
            pp, lsc, pkv = xs
            mst = None
        else:
            pp, lsc, pkv, msr = xs
            mst = [{"h": msr["h"][i], "conv": msr["conv"][i]}
                   for i in range(nm)]
        new_kv = None
        new_states = []
        mi = 0
        for j, (mixer, mlp) in enumerate(kinds):
            sub = pp["sub"][j]
            hn = C.apply_norm(sub["ln1"], h, cfg)
            if mixer == "attn":
                o, new_kv = C.attention_full(sub["attn"], hn, cfg, qcfg, lsc,
                                             None, positions, prefix_kv=pkv,
                                             causal=True, return_kv=True)
            else:
                st = mst[mi] if mst is not None else None
                o, nst = SSM.apply_mamba(sub["mamba"], hn, cfg, qcfg, lsc,
                                         None, 0, init_state=st,
                                         return_state=True)
                new_states.append(nst)
                mi += 1
            h = h + o
            hn = C.apply_norm(sub["ln2"], h, cfg)
            if mlp == "moe":
                y, _, _ = MOE.apply_moe(sub["moe"], hn, cfg, qcfg, lsc, None)
            else:
                y = C.apply_mlp(sub["mlp"], hn, cfg, qcfg, lsc, None)
            h = constrain(h + y, "B")
        ys = (new_kv,
              {"h": jnp.stack([s["h"] for s in new_states]),
               "conv": jnp.stack([s["conv"] for s in new_states])})
        return h, ys

    if remat:
        body = jax.checkpoint(body)
    xs = (params["layers"], lscales, pre_kv)
    if mst0 is not None:
        xs = xs + (mst0,)
    x, ((ks, vs), mstates) = jax.lax.scan(body, x, xs)

    # write cushion kv then prompt kv into cache
    if cushion is not None:
        if "kc" in cache:
            # quantized cache: cushion block protected in fp (kc/vc)
            assert cache["kc"].shape[1] == m, \
                f"cache prefix_len {cache['kc'].shape[1]} != cushion len {m}"
            cache = dict(cache)
            cache["kc"] = cushion["kv"]["k"].astype(cache["kc"].dtype)
            cache["vc"] = cushion["kv"]["v"].astype(cache["vc"].dtype)
        else:
            ck = jnp.broadcast_to(cushion["kv"]["k"][:, None],
                                  (n_periods, B, m, K, hd)).astype(cache["k"].dtype)
            cv = jnp.broadcast_to(cushion["kv"]["v"][:, None],
                                  (n_periods, B, m, K, hd)).astype(cache["v"].dtype)
            cache = dict(cache)
            cache["k"] = jax.lax.dynamic_update_slice(cache["k"], ck, (0, 0, 0, 0, 0))
            cache["v"] = jax.lax.dynamic_update_slice(cache["v"], cv, (0, 0, 0, 0, 0))
    cache = T.write_prompt_kv(cache, ks, vs, m)
    cache["h"] = mstates["h"]
    cache["conv"] = mstates["conv"].astype(cache["conv"].dtype)
    x = C.apply_norm(params["ln_f"], x, cfg)
    logits = C.lm_head(params, x[:, -1:], cfg, qcfg, scales, None)
    return logits, cache, jnp.asarray(m + S, jnp.int32)


def _attn_decode(p: Params, x: Array, kvd: Params, pos: Array,
                 cfg: ModelConfig, qcfg: QuantConfig, lsc: Params):
    """Decode one attention sublayer over its period's cache leaves. Paged
    pools keep their per-period xs here (attention runs only every few
    layers): the period's page store goes in as a one-layer stack read at
    layer 0."""
    if "page_table" not in kvd:
        return C.attention_decode_kv(p, x, kvd, pos, cfg, qcfg, lsc, None)
    one = {**kvd, "k": kvd["k"][None], "v": kvd["v"][None],
           "layer": jnp.zeros((), jnp.int32)}
    o, new = C.attention_decode_kv(p, x, one, pos, cfg, qcfg, lsc, None)
    return o, {**kvd, "k": new["k"][0], "v": new["v"][0]}


def decode_step(params: Params, token: Array, pos: Array, cache: Params,
                cfg: ModelConfig, qcfg: QuantConfig, *,
                scales: Optional[Params] = None):
    """One decode step; pos may be () shared or (B,) per-row. Attention
    sublayers mask/write per-row (attention_decode_kv); Mamba recurrences
    are position-free and advance every row — a retired slot's state takes
    dummy-token updates and is rebuilt wholesale at recycle by prefill."""
    x = C.embed_tokens(params, token[:, None], cfg)
    n_periods, kinds = layout(cfg)
    nm = n_mamba_per_period(cfg)
    lscales = C.resolve_scales(scales, SITES, n_periods, qcfg)

    kv_keys = [k for k in ("k", "v", "k_scale", "v_scale", "kc", "vc",
                           "page_table")
               if k in cache]

    def body(h, xs):
        pp, lsc, kvd, mh, mconv = xs
        mi = 0
        for j, (mixer, mlp) in enumerate(kinds):
            sub = pp["sub"][j]
            hn = C.apply_norm(sub["ln1"], h, cfg)
            if mixer == "attn":
                o, kvd = _attn_decode(sub["attn"], hn, kvd, pos, cfg, qcfg,
                                      lsc)
            else:
                st = {"h": mh[mi], "conv": mconv[mi]}
                o, nst = SSM.decode_mamba(sub["mamba"], hn, st, cfg, qcfg,
                                          lsc)
                mh = mh.at[mi].set(nst["h"])
                mconv = mconv.at[mi].set(nst["conv"].astype(mconv.dtype))
                mi += 1
            h = h + o
            hn = C.apply_norm(sub["ln2"], h, cfg)
            if mlp == "moe":
                y, _, _ = MOE.apply_moe(sub["moe"], hn, cfg, qcfg, lsc, None)
            else:
                y = C.apply_mlp(sub["mlp"], hn, cfg, qcfg, lsc, None)
            h = h + y
        return h, (kvd, mh, mconv)

    x, (kvs, mh, mconv) = jax.lax.scan(
        body, x, (params["layers"], lscales,
                  {k: cache[k] for k in kv_keys},
                  cache["h"], cache["conv"]))
    cache = dict(kvs)
    cache["h"], cache["conv"] = mh, mconv
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


def placeholder_all_scales(cfg: ModelConfig) -> Params:
    n_periods, _ = layout(cfg)
    sc = C.placeholder_scales(SITES, n_periods)
    sc["head"] = Q.SiteScale(scale=jnp.ones(()), zero=jnp.zeros(()))
    return sc
