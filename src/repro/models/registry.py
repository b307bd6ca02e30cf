"""Uniform model API over all families: build(cfg) -> ModelAPI.

Batch dicts:
  dense/moe         {"tokens", "labels"}
  ssm/hybrid        {"tokens", "labels"}
  encdec            + {"frames":  (B, T_enc, D)}   (stub audio frontend)
  vlm               + {"patches": (B, P, D)}       (stub vision frontend)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import Family, ModelConfig, QuantConfig
from repro.models import common as C
from repro.models import encdec as ED
from repro.models import hybrid as HY
from repro.models import moe as MO
from repro.models import ssm  # noqa: F401  (used inside hybrid)
from repro.models import transformer as TR
from repro.models import vlm as VL
from repro.models import xlstm as XL

Params = Dict[str, Any]


def family_module(cfg: ModelConfig):
    return {
        Family.DENSE: TR, Family.MOE: MO, Family.SSM: XL,
        Family.HYBRID: HY, Family.ENCDEC: ED, Family.VLM: VL,
    }[cfg.family]


def _extra_kwargs(cfg: ModelConfig, batch: Dict[str, Any]) -> Dict[str, Any]:
    if cfg.family == Family.ENCDEC:
        return {"frames": batch["frames"]}
    if cfg.family == Family.VLM:
        return {"patches": batch["patches"]}
    return {}


@dataclasses.dataclass
class ModelAPI:
    cfg: ModelConfig
    mod: Any

    @property
    def sites(self) -> Tuple[str, ...]:
        if self.cfg.family == Family.ENCDEC:
            return ED.DEC_SITES
        return self.mod.SITES

    def init_params(self, rng) -> Params:
        return self.mod.init_params(self.cfg, rng)

    def loss_fn(self, params, batch, qcfg: QuantConfig, **kw):
        return self.mod.loss_fn(params, batch["tokens"], batch["labels"],
                                self.cfg, qcfg,
                                **_extra_kwargs(self.cfg, batch), **kw)

    def forward(self, params, batch, qcfg: QuantConfig, **kw):
        return self.mod.forward(params, batch["tokens"], self.cfg, qcfg,
                                **_extra_kwargs(self.cfg, batch), **kw)

    def init_cache(self, batch: int, max_seq: int, dtype=None,
                   kv_dtype=None, prefix_len: int = 0,
                   per_slot_scales: bool = False):
        """kv_dtype "int8" requests quantized KV storage (attention-cache
        families only); prefix_len sizes the protected fp cushion block;
        per_slot_scales gives every batch row its own dequant scales
        (continuous-batching pools, calibrated per admission prefill)."""
        if kv_dtype is None:
            return self.mod.init_cache(self.cfg, batch, max_seq, dtype=dtype)
        if self.cfg.family not in (Family.DENSE, Family.MOE, Family.VLM,
                                   Family.HYBRID):
            raise ValueError(
                f"kv_dtype={kv_dtype!r} unsupported for {self.cfg.family}")
        return self.mod.init_cache(self.cfg, batch, max_seq, dtype=dtype,
                                   kv_dtype=kv_dtype, prefix_len=prefix_len,
                                   per_slot_scales=per_slot_scales)

    def prefill(self, params, batch, cache, qcfg: QuantConfig, **kw):
        return self.mod.prefill(params, batch["tokens"], cache, self.cfg,
                                qcfg, **_extra_kwargs(self.cfg, batch), **kw)

    def decode_step(self, params, token, pos, cache, qcfg: QuantConfig, **kw):
        """pos: () shared absolute position, or (B,) per-row positions for
        continuous batching (each cache slot decodes at its own offset)."""
        return self.mod.decode_step(params, token, pos, cache, self.cfg,
                                    qcfg, **kw)

    def cache_roles(self, kv_dtype=None,
                    per_slot_scales: bool = False) -> Dict[str, Tuple]:
        """Sharding-role template of every cache leaf (leaf name -> axis
        roles), consumed by ``distributed.sharding.cache_shardings`` to lay
        a serving pool out over a tp mesh. Families without a template
        (ssm's shape-polymorphic state, encdec) serve replicated."""
        fn = getattr(self.mod, "cache_roles", None)
        if fn is None:
            return {}
        return fn(self.cfg, kv_dtype=kv_dtype,
                  per_slot_scales=per_slot_scales)

    @property
    def cache_batch_axes(self) -> Dict[str, int]:
        """Batch axis of every per-request cache leaf — the continuous-
        batching scheduler's slot-scatter map. Entries may be nested dicts
        (per-leaf axes for state trees — ssm's stacked mLSTM/sLSTM states).
        Every registry family defines one: dense/moe/vlm (flat KV), hybrid
        (KV + Mamba state), ssm (recurrent state tree), encdec (self- +
        cross-attention KV)."""
        axes = getattr(self.mod, "CACHE_BATCH_AXES", None)
        if axes is None:
            raise NotImplementedError(
                f"{self.cfg.family}: no continuous-batching slot layout; "
                "use serving.engine.Engine (static batch)")
        return axes

    @property
    def paged_kv_leaves(self) -> Tuple[str, ...]:
        """Cache leaves the paged continuous pool re-lays into a flat page
        store + per-slot page table (``ContinuousEngine(paged=True)``).
        Empty for families without a pageable sequence cache (ssm's
        recurrent state, encdec's per-request cross-KV)."""
        return tuple(getattr(self.mod, "PAGED_KV_LEAVES", ()))

    @property
    def counts_routes(self) -> bool:
        """True when prefill() and decode_step() take ``route_counts=True``
        and then return the routing counters last (models/moe.py)."""
        return bool(getattr(self.mod, "ROUTE_COUNTS", False))

    @property
    def supports_chunked_prefill(self) -> bool:
        """True when prefill() accepts pos_offset to resume a partially
        staged B=1 fp row (chunked admission). Families whose prompt pass
        is not a pure causal attention-KV scan (ssm, encdec, hybrid's Mamba
        leaves, vlm's patch prepend) admit blocking instead."""
        return bool(getattr(self.mod, "SUPPORTS_CHUNKED_PREFILL", False))

    def finalize_staged_kv(self, row, cache, cushion, S: int):
        """Rebuild the admission row a blocking prefill would have produced
        from a finished chunk-staged fp row (int8 pools recalibrate their
        per-slot scales over the whole prompt here)."""
        return self.mod.finalize_staged_kv(row, cache, cushion, S)

    def cushion_zeros(self, m: int, dtype=None):
        """Zero cushion artifact; dtype=None follows the model compute
        dtype — the same dtype `extract_cushion` emits, so a zeros
        template and a real artifact are always interchangeable."""
        return self.mod.cushion_zeros(self.cfg, m, dtype=dtype)

    def forward_with_token_prefix(self, params, prefix_ids, batch,
                                  qcfg: QuantConfig, **kw):
        """Forward with a prefix of *real tokens* placed where the cushion
        will sit at deployment (greedy search, paper §4.1). prefix_ids: (m,)
        int32. Returns (logits, taps); callers pass collect/n_skip via kw."""
        cfg = self.cfg
        m = prefix_ids.shape[0]
        if cfg.family == Family.VLM:
            # cushion sits before the patches: prepend embed(prefix)+patches
            pre = jnp.take(params["embed"]["w"], prefix_ids, axis=0)[None]
            pre = jnp.broadcast_to(
                pre, (batch["patches"].shape[0],) + pre.shape[1:])
            pre = jnp.concatenate(
                [pre.astype(batch["patches"].dtype), batch["patches"]], axis=1)
            return TR.forward(params, batch["tokens"], cfg, qcfg,
                              prepend_embeds=pre, **kw)
        toks = jnp.concatenate(
            [jnp.broadcast_to(prefix_ids[None],
                              (batch["tokens"].shape[0], m)),
             batch["tokens"]], axis=1)
        nb = dict(batch)
        nb["tokens"] = toks
        return self.forward(params, nb, qcfg, **kw)

    # ------------------------------------------------------------------
    # Greedy-search scoring fast path (KV reuse; paper §4.1)
    # ------------------------------------------------------------------
    #
    # Scoring contract: for families whose prefix artifact is pure attention
    # KV (dense / moe / vlm), the shared prefix is prefilled into a KV cache
    # ONCE per search iteration (`prefix_kv`); every candidate is then scored
    # by forwarding [candidate; sample] against that cached block
    # (`score_candidates`), and the no-candidate baseline by forwarding the
    # sample alone (`prefix_qerr`). All three take a prefix padded to a fixed
    # length plus a live-length scalar, so one compiled executable serves the
    # whole search. Recurrent/cross-attention families (ssm / hybrid /
    # encdec) cannot mask a padded prefix out of their state and fall back to
    # `cushioncache.greedy_search_ref` (full forward per candidate).

    @property
    def supports_kv_scoring(self) -> bool:
        return bool(getattr(self.mod, "SUPPORTS_PREFIX_KV_SCORING", False))

    def prefix_kv(self, params, prefix_ids, qcfg: QuantConfig,
                  scales: Optional[Params] = None) -> Params:
        """Stacked per-layer KV {"k","v": (L, m, K, hd)} of a token prefix —
        the shared artifact the scoring fast path prefills once per search
        iteration. With a padded prefix, rows past the live length hold
        garbage by construction; downstream consumers mask them via
        `prefix_valid`."""
        if not self.supports_kv_scoring:
            raise NotImplementedError(
                f"{self.cfg.family}: prefix artifact is not pure attention "
                "KV; use cushioncache.greedy_search_ref")
        cfg = self.cfg
        mod = MO if cfg.family == Family.MOE else TR
        m = prefix_ids.shape[0]
        cache = mod.init_cache(cfg, 1, m)
        _, cache, _ = mod.prefill(params, prefix_ids[None], cache, cfg, qcfg,
                                  scales=scales)
        return {"k": cache["k"][:, 0], "v": cache["v"][:, 0]}

    def prefix_qerr(self, params, prefix_kv, live_len, batch,
                    qcfg: QuantConfig, scales: Optional[Params] = None):
        """L_q of the calibration sample given the cached prefix (the
        search's base error). live_len: dynamic scalar — number of live rows
        in the padded prefix_kv."""
        valid = jnp.arange(prefix_kv["k"].shape[1]) < live_len
        _, taps = self.forward(params, batch, qcfg, scales=scales,
                               cushion={"kv": prefix_kv}, collect=True,
                               n_skip=0, prefix_valid=valid,
                               pos_offset=live_len, remat=False)
        return TR.total_qerr(taps)

    def score_candidates(self, params, prefix_kv, live_len, cand_ids, batch,
                         qcfg: QuantConfig, scales: Optional[Params] = None):
        """(N,) L_q of each candidate-extended prefix, reusing the shared
        prefix KV: per candidate, one forward of [candidate; sample] with
        the cached prefix attached (vmapped over candidates with the cache
        unbatched — no O(N·m) prefix recompute, no N× cache copy). The
        candidate position is excluded from L_q (n_skip=1), matching the
        reference scorer's exclusion of all prefix positions."""
        if not self.supports_kv_scoring:
            raise NotImplementedError(
                f"{self.cfg.family}: KV-reuse scoring unavailable; use "
                "cushioncache.greedy_search_ref")
        cfg = self.cfg
        valid = jnp.arange(prefix_kv["k"].shape[1]) < live_len

        def one(cand):
            nb = dict(batch)
            if cfg.family == Family.VLM:
                # candidate sits between the cushion and the patches
                ce = jnp.take(params["embed"]["w"], cand[None], axis=0)[None]
                ce = jnp.broadcast_to(ce, (batch["patches"].shape[0],)
                                      + ce.shape[1:])
                nb["patches"] = jnp.concatenate(
                    [ce.astype(batch["patches"].dtype), batch["patches"]],
                    axis=1)
            else:
                nb["tokens"] = jnp.concatenate(
                    [jnp.broadcast_to(cand[None, None],
                                      (batch["tokens"].shape[0], 1)),
                     batch["tokens"]], axis=1)
            _, taps = self.forward(params, nb, qcfg, scales=scales,
                                   cushion={"kv": prefix_kv}, collect=True,
                                   n_skip=1, prefix_valid=valid,
                                   pos_offset=live_len, remat=False)
            return TR.total_qerr(taps)

        return jax.vmap(one)(cand_ids)

    def extract_cushion(self, params, prefix_ids, batch,
                        qcfg: QuantConfig) -> Params:
        """Turn a searched token prefix into the deployment Cushion artifact
        (per-layer KV for attention archs; recurrent states for SSM/hybrid)
        by running the prefix through the model once (paper: 'their keys and
        values are cached and reused at inference', eq. 8)."""
        cfg = self.cfg
        m = int(prefix_ids.shape[0])
        toks = prefix_ids[None]
        if cfg.family == Family.SSM:
            _, _, states = XL.forward(params, toks, cfg, qcfg,
                                      return_cache=True, remat=False)
            return {"state": jax.tree_util.tree_map(lambda a: a[:, 0], states)}
        if cfg.family == Family.HYBRID:
            cache = HY.init_cache(cfg, 1, m)
            _, cache, _ = HY.prefill(params, toks, cache, cfg, qcfg)
            return {"kv": {"k": cache["k"][:, 0, :m], "v": cache["v"][:, 0, :m]},
                    "state": {"h": cache["h"][:, :, 0],
                              "conv": cache["conv"][:, :, 0]}}
        if cfg.family == Family.ENCDEC:
            # null acoustic context for the prefix pass (DESIGN.md §5)
            frames = jnp.zeros((1, cfg.encdec.encoder_seq, cfg.d_model),
                               C.dtype_of(cfg))
            cache = ED.init_cache(cfg, 1, m)
            _, cache, _ = ED.prefill(params, toks, cache, cfg, qcfg,
                                     frames=frames)
            return {"kv": {"k": cache["k"][:, 0, :m],
                           "v": cache["v"][:, 0, :m]}}
        mod = MO if cfg.family == Family.MOE else TR
        cache = mod.init_cache(cfg, 1, m)
        _, cache, _ = mod.prefill(params, toks, cache, cfg, qcfg)
        return {"kv": {"k": cache["k"][:, 0, :m], "v": cache["v"][:, 0, :m]}}

    # ------------------------------------------------------------------
    # Input construction
    # ------------------------------------------------------------------

    def make_batch(self, rng, batch: int, seq_len: int) -> Dict[str, Any]:
        """Concrete random batch (smoke tests / CPU experiments)."""
        cfg = self.cfg
        text_len = self.text_len(seq_len)
        toks = jax.random.randint(rng, (batch, text_len + 1), 0,
                                  cfg.vocab_size, dtype=jnp.int32)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if cfg.family == Family.ENCDEC:
            out["frames"] = jax.random.normal(
                rng, (batch, cfg.encdec.encoder_seq, cfg.d_model),
                C.dtype_of(cfg)) * 0.02
        if cfg.family == Family.VLM:
            out["patches"] = jax.random.normal(
                rng, (batch, cfg.vlm.num_patches, cfg.d_model),
                C.dtype_of(cfg)) * 0.02
        return out

    def text_len(self, seq_len: int) -> int:
        """Token count such that total positions == seq_len."""
        if self.cfg.family == Family.VLM:
            return max(1, seq_len - self.cfg.vlm.num_patches)
        return seq_len

    def input_specs(self, batch: int, seq_len: int) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input (dry-run)."""
        cfg = self.cfg
        text_len = self.text_len(seq_len)
        sds = jax.ShapeDtypeStruct
        out = {"tokens": sds((batch, text_len), jnp.int32),
               "labels": sds((batch, text_len), jnp.int32)}
        if cfg.family == Family.ENCDEC:
            out["frames"] = sds((batch, cfg.encdec.encoder_seq, cfg.d_model),
                                C.dtype_of(cfg))
        if cfg.family == Family.VLM:
            out["patches"] = sds((batch, cfg.vlm.num_patches, cfg.d_model),
                                 C.dtype_of(cfg))
        return out


def build(cfg: ModelConfig) -> ModelAPI:
    return ModelAPI(cfg=cfg, mod=family_module(cfg))
