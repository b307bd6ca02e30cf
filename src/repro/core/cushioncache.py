"""CushionCache (paper §4): discover a prefix KV cache that mitigates
activation outliers in subsequent tokens.

Two stages:
  1. `greedy_search`   — Algorithm 1: grow a hard-token prompt one token at a
     time, each chosen (over a candidate subset of the embedding table, by
     batched inference) to minimize L_q(t | p, p'), with early stopping at
     improvement ratio tau.
  2. `prefix_tune`     — quantization-aware prefix tuning: freeze the model,
     train the cushion KV block (the only trainable leaves) on
     L = L_pred + lambda * L_range (paper eq. 11; `core.outliers`'
     differentiable activation-range penalty as the regularizer) with a
     straight-through quantized forward and stop-grad quantizer
     parameters. Compile-once donated step, periodic metric host syncs,
     optional data-axis batch sharding — see the function docstring.

The searched prefix is converted to the deployment artifact with
`ModelAPI.extract_cushion` (KV for attention archs, recurrent state for
SSM/hybrid — see DESIGN.md §5).

Search fast path
----------------
`greedy_search` is a compile-once, device-resident implementation for
families with a pure attention-KV prefix artifact (dense/moe/vlm):

* the prefix is padded to ``ccfg.max_prefix_len`` and a live-length scalar
  is threaded through attention masking, so ONE compiled executable serves
  every iteration (the reference recompiles per appended token);
* the shared prefix is prefilled into a KV cache once per iteration
  (``ModelAPI.prefix_kv``) and every candidate is scored against the cached
  block (``ModelAPI.score_candidates``) — no O(N·m) prefix recompute;
* candidates are scored by ``lax.map`` over fixed-size chunks with an
  on-device argmin, so each iteration costs one host sync instead of
  ``n_candidates / chunk``.

`greedy_search_ref` keeps the original full-forward implementation: it is
the parity oracle for the fast path, the scorer for families whose prefix
artifact is not pure attention KV (ssm/hybrid/encdec — `greedy_search`
falls back to it automatically), and the baseline for
``benchmarks/run.py search_bench``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CushionConfig, QuantConfig
from repro.models import transformer as T

Params = Dict[str, Any]


def cushion_fingerprint(cushion: Optional[Params]) -> str:
    """Content fingerprint of a cushion artifact: sha256 over every leaf's
    path, dtype, shape and exact bytes (``"none"`` for no cushion).

    This is the provenance tie between a cushion and everything derived
    under it: `launch/tune.py` stamps it into the artifact manifest (load
    integrity), `calibration.CalibratedScales` carries the fingerprint of
    the cushion its pt_static scales were calibrated under, and
    `serving.engine.plan_quantization` hard-fails when the two diverge —
    static ranges describe ONE cushioned activation distribution and
    silently serve garbage under another.
    """
    if cushion is None:
        return "none"
    h = hashlib.sha256()
    flat, _ = jax.tree_util.tree_flatten_with_path(cushion)
    for kp, leaf in flat:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(kp).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# L_q evaluation
# ---------------------------------------------------------------------------

def make_qerr_fn(api, qcfg: QuantConfig, scales: Optional[Params] = None
                 ) -> Callable:
    """Returns jit'd fn(params, prefix_ids (m,), batch) -> L_q of the token
    part (scales for dynamic modes derived from the token part only —
    matching deployment, where prefix tokens never re-enter the linears)."""

    def f(params, prefix_ids, batch):
        m = prefix_ids.shape[0]
        _, taps = api.forward_with_token_prefix(
            params, prefix_ids, batch, qcfg, scales=scales, collect=True,
            n_skip=m, remat=False)
        return T.total_qerr(taps)

    return jax.jit(f)


def make_batched_qerr_fn(api, qcfg: QuantConfig,
                         scales: Optional[Params] = None) -> Callable:
    """fn(params, prefixes (N, m), batch) -> (N,) L_q per candidate prefix —
    the paper's 'batched inference' for the argmin over the embedding table.
    """
    def one(params, prefix_ids, batch):
        m = prefix_ids.shape[0]
        _, taps = api.forward_with_token_prefix(
            params, prefix_ids, batch, qcfg, scales=scales, collect=True,
            n_skip=m, remat=False)
        return T.total_qerr(taps)

    return jax.jit(jax.vmap(one, in_axes=(None, 0, None)))


# ---------------------------------------------------------------------------
# Stage 1: greedy prefix search (Algorithm 1)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SearchResult:
    prefix_ids: np.ndarray
    history: List[Dict[str, float]]
    wall_time_s: float


# always-included nonsemantic candidates (<bos>-like low ids); also the
# sizing basis for the fast path's fixed candidate-pool shape
SPECIAL_TOKENS = (0, 1, 2, 3, 10, 13, 32, 198)


def _specials(vocab_size: int, seed_tokens: Tuple[int, ...]) -> np.ndarray:
    s = np.unique(np.array(list(seed_tokens) + list(SPECIAL_TOKENS)))
    return s[s < vocab_size]


def candidate_pool(rng, vocab_size: int, n: int,
                   seed_tokens: Tuple[int, ...] = ()) -> np.ndarray:
    """Random subset of the embedding table + always-included nonsemantic
    candidates, standing in for the full-table argmin (eq. 9) at CPU
    scale."""
    n_rand = max(0, n - len(SPECIAL_TOKENS))
    cands = jax.random.choice(rng, vocab_size, (n_rand,), replace=False)
    specials = _specials(vocab_size, seed_tokens)
    return np.unique(np.concatenate([np.asarray(cands), specials]))


def greedy_search_ref(api, params, sample_fn: Callable[[int], Dict[str, Any]],
                      qcfg: QuantConfig, ccfg: CushionConfig, rng,
                      chunk: int = 16, verbose: bool = True) -> SearchResult:
    """Algorithm 1, reference implementation (full forward per candidate).

    sample_fn(i) -> calibration batch (batch 1, length n). Each iteration
    draws a fresh sample t ~ D, evaluates all candidates p' by batched
    inference, and appends the argmin if it improves L_q by the factor tau
    (eq. 10); stops otherwise or at max length.

    Every iteration recompiles both scorers (the prefix shape grows by one
    token) and pays a host round-trip per candidate chunk. Kept as the
    parity oracle / benchmark baseline for `greedy_search`, and as the
    scorer for families without KV-reuse support (ssm/hybrid/encdec).
    """
    t0 = time.time()
    qerr_fn = make_qerr_fn(api, qcfg)
    batched_fn = make_batched_qerr_fn(api, qcfg)
    prefix: List[int] = list(ccfg.seed_tokens)
    history: List[Dict[str, float]] = []

    it = 0
    while len(prefix) < ccfg.max_prefix_len:
        rng, k1, k2 = jax.random.split(rng, 3)
        batch = sample_fn(it)
        base_ids = jnp.asarray(prefix, jnp.int32)
        base_err = float(qerr_fn(params, base_ids, batch))

        cands = candidate_pool(k1, api.cfg.vocab_size, ccfg.n_candidates,
                               ccfg.seed_tokens)
        best_err, best_tok = np.inf, -1
        for s in range(0, len(cands), chunk):
            cs = cands[s:s + chunk]
            if len(cs) < chunk:   # pad to keep one compiled shape
                cs = np.concatenate([cs, np.repeat(cs[-1:], chunk - len(cs))])
            pref = jnp.concatenate(
                [jnp.broadcast_to(base_ids[None], (chunk, len(prefix))),
                 jnp.asarray(cs, jnp.int32)[:, None]], axis=1)
            errs = np.asarray(batched_fn(params, pref, batch))
            j = int(np.argmin(errs))
            if errs[j] < best_err:
                best_err, best_tok = float(errs[j]), int(cs[j])

        history.append({"iter": it, "len": len(prefix), "base_err": base_err,
                        "best_err": best_err, "best_tok": best_tok,
                        "ratio": best_err / max(base_err, 1e-30)})
        if verbose:
            print(f"[greedy] it={it} len={len(prefix)} L_q={base_err:.4g} "
                  f"-> {best_err:.4g} (tok={best_tok}, "
                  f"ratio={best_err / max(base_err, 1e-30):.3f})")
        if best_err > ccfg.tau * base_err:
            break                      # eq. (10) early stop
        prefix.append(best_tok)
        it += 1

    return SearchResult(prefix_ids=np.asarray(prefix, np.int32),
                        history=history, wall_time_s=time.time() - t0)


def _pool_pad_len(vocab_size: int, ccfg: CushionConfig, chunk: int) -> int:
    """Static upper bound on `candidate_pool`'s (variable) length, rounded
    up to a chunk multiple — the fixed shape the compile-once search step is
    built for."""
    cap = max(0, ccfg.n_candidates - len(SPECIAL_TOKENS)) \
        + len(_specials(vocab_size, ccfg.seed_tokens))
    return max(chunk, -(-cap // chunk) * chunk)


def make_search_step_fn(api, qcfg: QuantConfig,
                        scales: Optional[Params] = None) -> Callable:
    """One fused greedy-search iteration, jitted once for the whole search:

        step(params, padded_prefix (max_m,), live_len (), cands
             (n_chunks, chunk), batch) -> (base_err, best_err, best_tok)

    Prefills the shared (padded) prefix into a KV cache, computes the base
    L_q, scores every candidate chunk via `lax.map` over the vmapped
    KV-reuse scorer, and argmins on device — all shapes are independent of
    the live prefix length, so the executable compiles exactly once.
    """
    def step(params, padded_prefix, live_len, cands, batch):
        pkv = api.prefix_kv(params, padded_prefix, qcfg, scales=scales)
        base = api.prefix_qerr(params, pkv, live_len, batch, qcfg,
                               scales=scales)
        errs = jax.lax.map(
            lambda cs: api.score_candidates(params, pkv, live_len, cs,
                                            batch, qcfg, scales=scales),
            cands).reshape(-1)
        j = jnp.argmin(errs)
        return base, errs[j], cands.reshape(-1)[j]

    return jax.jit(step)


def greedy_search(api, params, sample_fn: Callable[[int], Dict[str, Any]],
                  qcfg: QuantConfig, ccfg: CushionConfig, rng,
                  chunk: int = 16, verbose: bool = True) -> SearchResult:
    """Algorithm 1, compile-once fast path (see module docstring).

    Produces the same candidate pools in the same order as
    `greedy_search_ref` (identical rng schedule), scores them via KV reuse,
    and delegates to the reference implementation for families without an
    attention-KV-only prefix artifact.
    """
    if not api.supports_kv_scoring:
        if verbose:
            print(f"[greedy] {api.cfg.family}: no KV-reuse scoring; "
                  "falling back to greedy_search_ref")
        return greedy_search_ref(api, params, sample_fn, qcfg, ccfg, rng,
                                 chunk=chunk, verbose=verbose)

    t0 = time.time()
    max_m = ccfg.max_prefix_len
    step_fn = make_search_step_fn(api, qcfg)
    n_pool = _pool_pad_len(api.cfg.vocab_size, ccfg, chunk)
    prefix: List[int] = list(ccfg.seed_tokens)
    padded = np.zeros((max_m,), np.int32)
    padded[:len(prefix)] = prefix
    history: List[Dict[str, float]] = []

    it = 0
    while len(prefix) < max_m:
        rng, k1, k2 = jax.random.split(rng, 3)
        batch = sample_fn(it)
        cands = candidate_pool(k1, api.cfg.vocab_size, ccfg.n_candidates,
                               ccfg.seed_tokens).astype(np.int32)
        # pad to the fixed pool size by repeating the tail candidate:
        # duplicates tie in L_q and argmin keeps the first occurrence, so
        # the winner matches the reference's strict-improvement scan.
        cands = np.concatenate(
            [cands, np.repeat(cands[-1:], n_pool - len(cands))])
        base, best, tok = step_fn(params, jnp.asarray(padded),
                                  np.int32(len(prefix)),
                                  jnp.asarray(cands.reshape(-1, chunk)),
                                  batch)
        base_err, best_err, best_tok = float(base), float(best), int(tok)

        history.append({"iter": it, "len": len(prefix), "base_err": base_err,
                        "best_err": best_err, "best_tok": best_tok,
                        "ratio": best_err / max(base_err, 1e-30)})
        if verbose:
            print(f"[greedy] it={it} len={len(prefix)} L_q={base_err:.4g} "
                  f"-> {best_err:.4g} (tok={best_tok}, "
                  f"ratio={best_err / max(base_err, 1e-30):.3f})")
        if best_err > ccfg.tau * base_err:
            break                      # eq. (10) early stop
        padded[len(prefix)] = best_tok
        prefix.append(best_tok)
        it += 1

    return SearchResult(prefix_ids=np.asarray(prefix, np.int32),
                        history=history, wall_time_s=time.time() - t0)


# ---------------------------------------------------------------------------
# Stage 2: quantization-aware prefix tuning (paper §4.2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TuneResult:
    cushion: Params
    log: List[Dict[str, float]]
    wall_time_s: float


def _partition_cushion(cushion0: Params):
    """(frozen path substrings, stop-grad wrapper) for a family's cushion
    tree. The paper tunes the cached prefix KV, so the "kv" kc/vc block is
    the only trainable subtree; anything alongside it (the hybrid family's
    recurrent "state" leaves) is frozen — stop_gradient in the loss plus
    the AdamW `frozen` mask keeps those leaves bit-identical through
    tuning. Families whose whole artifact is recurrent state (ssm: no "kv"
    key) train the full tree."""
    if "kv" not in cushion0:
        return (), lambda c: c
    frozen = tuple(k for k in cushion0 if k != "kv")
    if not frozen:
        return (), lambda c: c

    def stop_grad_frozen(c):
        return {k: (v if k == "kv"
                    else jax.tree_util.tree_map(jax.lax.stop_gradient, v))
                for k, v in c.items()}

    return frozen, stop_grad_frozen


def prefix_tune(api, params, cushion0: Params,
                batch_iter: Iterable[Dict[str, Any]],
                qcfg: QuantConfig, ccfg: CushionConfig,
                scales: Optional[Params] = None,
                mesh=None, verbose: bool = True) -> TuneResult:
    """Freeze the model; train the cushion KV on
    L = L_pred + lambda * L_range (eq. 11, with `core.outliers`'
    differentiable activation-range penalty as the quantization
    regularizer). The quantized forward uses straight-through estimation;
    quantizer scale/zero-points are stop-grad'ed inside the quantizers
    (fake_quant), matching Jacob et al. QAT practice as cited by the paper.

    Pipeline properties:

    * the step jits ONCE and DONATES both the cushion and the optimizer
      state — fixed shapes, no per-step buffer copies;
    * only the "kv" block trains (`_partition_cushion`): hybrid recurrent
      state leaves come out bit-identical, preserving the serving pools'
      cushion-rewrite guarantee;
    * per-step metrics stay on device; the log drains to host every
      ``ccfg.log_every`` steps through `monitoring.host_sync` (ONE
      blocking transfer per drain — `count_host_syncs` bounds it in
      tests), while still recording every step;
    * ``mesh=`` shards batches over the mesh's "data" axis with the
      cushion/optimizer state replicated, `shard_update_step`-style
      (the batch size must divide the data axis).
    """
    from repro import monitoring as MON
    from repro.core import outliers as OUT
    from repro.optim.adamw import AdamW, constant_lr

    t0 = time.time()
    frozen, stop_grad_frozen = _partition_cushion(cushion0)
    opt = AdamW(lr=constant_lr(ccfg.tune_lr), weight_decay=0.0,
                grad_clip=1.0, frozen=frozen)
    state = opt.init(cushion0)

    # the frozen params are an argument of the step, never a closure: a
    # closed-over model would be baked into the executable as constants
    def loss(cush, batch, params):
        cush = stop_grad_frozen(cush)
        _, aux = api.loss_fn(params, batch, qcfg, scales=scales,
                             cushion=cush, collect=True, remat=False)
        reg = OUT.activation_range_penalty(aux["taps"])
        total = aux["ce"] + ccfg.lam * reg
        return total, {"ce": aux["ce"], "range": reg,
                       "qerr": aux.get("qerr", jnp.zeros(()))}

    def step(cush, state, batch, params):
        (l, aux), g = jax.value_and_grad(loss, has_aux=True)(cush, batch,
                                                             params)
        cush, state, om = opt.update(g, state, cush)
        return cush, state, {"loss": l, **aux, "gnorm": om["grad_norm"]}

    # the donated step consumes its carry buffers, including the very first
    # ones — train on a private copy so the caller's cushion0 stays alive
    cushion = jax.tree_util.tree_map(jnp.array, cushion0)
    it = iter(batch_iter)
    try:
        first = next(it)
    except StopIteration:
        return TuneResult(cushion=cushion, log=[],
                          wall_time_s=time.time() - t0)

    if mesh is None:
        step_fn = jax.jit(step, donate_argnums=(0, 1))
    else:
        from repro.train.trainer import replicated_shardings, \
            shard_update_step
        c_sh = replicated_shardings(cushion0, mesh)
        o_sh = replicated_shardings(jax.eval_shape(opt.init, cushion0),
                                    mesh)
        step_fn = shard_update_step(step, mesh, c_sh, o_sh, first,
                                    n_extra=1)
        cushion = jax.device_put(cushion, c_sh)
        state = jax.device_put(state, o_sh)

    log: List[Dict[str, float]] = []
    pending: List[Tuple[int, Dict[str, Any]]] = []
    log_every = max(1, int(getattr(ccfg, "log_every", 10)))
    print_every = max(1, ccfg.tune_steps // 10)

    def drain():
        if not pending:
            return
        fetched = MON.host_sync([m for _, m in pending])
        for (j, _), mv in zip(pending, fetched):
            rec = {k: float(v) for k, v in mv.items()}
            rec["step"] = j
            log.append(rec)
            if verbose and j % print_every == 0:
                print(f"[tune] step={j} loss={rec['loss']:.4f} "
                      f"ce={rec['ce']:.4f} range={rec['range']:.4g} "
                      f"L_q={rec['qerr']:.4g}")
        pending.clear()

    for i, batch in enumerate(itertools.chain([first], it)):
        if i >= ccfg.tune_steps:
            break
        cushion, state, m = step_fn(cushion, state, batch, params)
        pending.append((i, m))
        if len(pending) >= log_every:
            drain()
    drain()
    return TuneResult(cushion=cushion, log=log,
                      wall_time_s=time.time() - t0)


# ---------------------------------------------------------------------------
# End-to-end pipeline
# ---------------------------------------------------------------------------

def discover(api, params, sample_fn: Callable[[int], Dict[str, Any]],
             batch_iter: Iterable[Dict[str, Any]], qcfg: QuantConfig,
             ccfg: CushionConfig, rng, skip_tune: bool = False,
             mesh=None, verbose: bool = True):
    """greedy search -> extract KV/state -> quantization-aware tuning.
    Returns (cushion, SearchResult, TuneResult|None).

    The artifact keeps the dtype `extract_cushion` emits (the model's
    cache/compute dtype): a bf16 model gets a bf16 cushion, so the serving
    pools' bit-identical cushion-rewrite-on-recycle guarantee holds without
    casts. (An earlier version force-cast to fp32 here, which broke that
    guarantee for bf16 models; AdamW keeps fp32 moments internally and
    casts the update back per leaf, so tuning preserves the dtype too.)"""
    sr = greedy_search(api, params, sample_fn, qcfg, ccfg, rng,
                       verbose=verbose)
    prefix_ids = jnp.asarray(sr.prefix_ids, jnp.int32)
    if prefix_ids.size == 0:
        prefix_ids = jnp.asarray([0], jnp.int32)
    cushion = api.extract_cushion(params, prefix_ids, None, qcfg)
    if skip_tune:
        return cushion, sr, None
    tr = prefix_tune(api, params, cushion, batch_iter, qcfg, ccfg,
                     mesh=mesh, verbose=verbose)
    return tr.cushion, sr, tr
