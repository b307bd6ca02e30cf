"""Continuous-batching serving scheduler: a fixed pool of cache slots that
requests flow through independently (admit -> prefill -> lock-step decode ->
retire -> recycle), instead of the static Engine's all-start-together batch.

Design
------
* The pool is ONE device cache of ``n_slots`` rows plus three per-row
  vectors: ``pos`` ((B,) int32 decode positions), ``tok`` ((B,) int32 last
  sampled tokens) and a host-side ``live`` mask. Decode runs one jitted
  step over the whole pool regardless of how many slots are live — dead
  rows are *compute-masked* (their pos is frozen, their sampled token
  forced to 0, their output discarded), never resized away, so the step
  executable compiles exactly once.
* Admission prefills the request alone (B=1, cushion attached) and
  scatters the full prefilled cache row into its slot along the family's
  ``CACHE_BATCH_AXES``. Scattering the *whole* row re-writes the cushion
  block [0:m) bit-identically on every recycle (KVSink/IntactKV: the fp
  sink block is never evicted and never inherited stale from the previous
  occupant) and leaves any stale content KV beyond the new request's
  extent masked off by the slot's own ``pos``. Axes entries may be nested
  dicts (a per-leaf batch-axis subtree) for families whose cache is a
  state *tree* rather than flat arrays — ssm's per-pair mLSTM/sLSTM
  states scatter exactly like hybrid's Mamba leaves.
* Per-row positions are threaded down to the attention kernel: RoPE
  offsets, cache writes and masking are all per-slot
  (``common.attention_decode_kv`` / ``kernels/flash_decode.py``), so slots
  prefilled at different times decode together in one lock-step batch.
  Recurrent families (ssm, hybrid's Mamba leaves) ignore ``pos``; their
  dead rows advance garbage state that the full-row admission scatter
  overwrites before the slot is ever read again.
* EOS/budget retirement happens host-side on the one per-step sync that
  reads the sampled tokens; the freed slot is recycled by the next
  admission. TTFT/TPOT are tracked per request; pool occupancy lands in
  ``monitoring.ServeStats``.
* The blocking paths record host spans (``monitoring.span``): each decode
  step is a ``serve.step`` whose children are ``.pages`` (page mapping and
  the page-table upload), ``.wait`` (the token read) and ``.retire``; the
  dispatch is its self-time. Each blocking admission is a ``serve.admit``
  carrying the request's uid, with ``.alloc`` (slot and page claim),
  ``.wait`` (the first-token read) and ``.book``; the row, the prefill and
  the scatter dispatch are its self-time. Both reads go through
  ``monitoring.host_sync``, one counted sync each.

Incremental API (the replica router's contract, serving/router.py):
``start()`` resets the pool and opens a serving session; ``try_admit(req)``
admits into a free slot (False when the pool is full — the caller owns
queueing/backpressure); ``step()`` runs one lock-step decode and retires
finished slots; ``cancel(uid)`` frees a live slot without a result
(deadline expiry / failover); ``pop_finished()`` drains completed outputs.
``run(trace)`` — the single-engine trace replay — is built entirely on
these hooks, and drains gracefully on ``KeyboardInterrupt``: admission
stops, live slots decode to completion, and partial results are returned
with ``stats.interrupted`` set.

Tensor parallelism: pass a ``mesh`` (launch/mesh.py ``make_tp_mesh``) and
the pool shards along the family's ``cache_roles`` axes (KV heads, Mamba
channels) with params under the TP-only serve rules; admission rows share
the pool layout so the slot scatter stays shard-local, and the lock-step
decode runs as one sharding-constrained jitted step with the pool resident
across devices (the per-step host sync still reads only the (B,) sampled
tokens, never the pool).

Quantization: the engine shares the static ``Engine``'s load-time plan
(``serving.engine.plan_quantization``) — pt_static site scales calibrated
under the cushion at construction, optionally with ``prequant=True``
int8-resident weights. ``kv_dtype="int8"`` serves a quantized KV pool with
*per-slot* dequant scales: every admission's B=1 prefill calibrates
per-(layer,head) scales from its own prompt (``write_prompt_kv``), the
slot scatter carries them into (L, n_slots, K) pool leaves alongside the
KV rows, and decode quantizes/dequantizes each row with its own scales
(kernels/flash_decode.py per-row scale routing). The fp cushion block
kc/vc is batch-free and rewritten bit-identically on every admission
(KVSink/IntactKV).

Paged KV pool (``paged=True``): the dense per-slot rows become a flat,
lane-dense page store ``(L, n_pages, page_size // r, K, r*hd)`` (r
positions side by side in each head's lanes, ``flash_decode.pack_pages``,
so XLA keeps it row-major and unpadded, and the decode step writes it in
place) plus a per-slot page table — KV
memory then scales with *live tokens*, not ``n_slots * max_seq``, so more
slots fit a fixed HBM budget. The host-side allocator (serving/paging.py
``PagePool``) reserves every page a request can need at admission (mid-
decode exhaustion is impossible; a full pool backpressures exactly like a
full slot pool), maps prompt pages immediately (the admission scatter
routes each logical page of the B=1 row to its physical page) and decode
pages lazily as positions cross page boundaries. The fp cushion block
leaves the per-slot rows entirely: it lives ONCE in batch-free ``kc``/
``vc`` pool leaves written at pool reset and only ever read afterwards —
the refcounted, read-only cushion page every slot maps — so recycling a
slot re-scatters content pages but never copies the sink block again.
Reads route through ``kernels/flash_decode.flash_decode_paged`` (scalar-
prefetched page table) on TPU or a gather + the contiguous jnp paths on
CPU; either way paged and contiguous pools decode token-for-token
identical traces. ``prefix_cache=True`` (fp pools only) additionally
content-addresses full prompt-stem pages so a repeated stem maps the
donor's pages read-only (refcount++) and prefills only the tail against
an extended cushion — pages are write-once, so copy-on-write degenerates
to copy-never.

Chunked prefill (``chunk_tokens``): blocking admission runs the whole B=1
prompt prefill inline, so one long prompt stalls every live decode slot —
the p99 killer under heavy traffic. With a per-step chunk budget set
(power-of-two bucketed, min 8), a prompt longer than one budget becomes a
PREFILLING *stream* instead: the slot (and, paged, the full page
reservation) is claimed up front, and the prompt is replayed one chunk per
``step()`` — round-robin across streams — into a B=1 fp staging row,
interleaved with the pool's lock-step decode. Chunk 0 attaches the cushion
(or the prefix-cache extended cushion); later chunks resume with a static
``pos_offset``, reading the cushion + earlier chunks back out of the row
as the fully-visible prefix. Only the final chunk touches the pool, via
the SAME admit scatter as blocking admission (int8 pools requantize the
finished fp row in one shot so per-slot scales still calibrate over the
whole prompt) — chunked admission is therefore token-for-token identical
to blocking, it just stops starving decode (smooth TPOT) and stops
head-of-line blocking short prompts behind long ones (p99 TTFT).
Deadlines are enforced between chunks: an expired stream frees its slot
without a result (``stats.deadline_prefill``; the router drains the uids
via ``pop_expired``). Families whose prompt pass is not a pure causal
attention-KV scan (ssm, encdec, vlm, hybrid) keep blocking admission.

Scope: greedy decoding for every registry family with a
``CACHE_BATCH_AXES`` slot layout — dense / moe / vlm / hybrid (KV pools,
int8-capable) plus ssm and encdec (fp state/KV pools; no paged mode —
nothing to page). When every request starts together with one shared
budget, prefer the static ``Engine``: its device-resident scan syncs twice
per request instead of once per token.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import QuantConfig
from repro.distributed import sharding as SH
from repro.kernels.flash_decode import pack_pages, page_rows, unpack_pages
from repro.models.registry import ModelAPI
from repro.monitoring import (ServeStats, count, host_sync,
                              resident_weight_bytes, span)
from repro.serving.engine import (bucket_steps, cache_seq_len,
                                  cushion_fingerprint, cushion_prefix_len,
                                  plan_quantization,
                                  shard_params_for_serving)
from repro.serving.paging import PagePool


@dataclasses.dataclass
class Request:
    """One generation request. batch: B=1 model inputs ({"tokens": (1, S)}
    plus "patches"/"frames" where the family needs them). arrival_s is the
    trace-relative arrival time (0.0 = available immediately).
    deadline_s, when set, is the trace-relative instant after which the
    request is worthless — the router rejects it from the queue or cancels
    it mid-decode once the deadline passes."""
    uid: int
    batch: Dict[str, Any]
    max_new_tokens: int
    eos_id: Optional[int] = None
    arrival_s: float = 0.0
    deadline_s: Optional[float] = None


@dataclasses.dataclass
class RequestOutput:
    uid: int
    tokens: np.ndarray          # (n_gen,) int32 — includes EOS if emitted
    ttft_ms: float              # admission -> first token (prefill wall)
    tpot_ms: float              # mean wall per subsequent token (0.0 if <2)
    slot: int
    admitted_s: float           # trace-relative admission completion
    finished_s: float           # trace-relative retirement
    latency_s: float            # arrival -> retirement


class _Slot:
    __slots__ = ("req", "tokens", "t_first", "t_admit", "used")

    def __init__(self) -> None:
        self.req: Optional[Request] = None
        self.tokens: List[int] = []
        self.t_first = 0.0
        self.t_admit = 0.0
        self.used = False       # has ever held a request (recycle counter)


class _PrefillStream:
    """A partially-admitted request (the PREFILLING slot state): its prompt
    is replayed chunk-by-chunk into a B=1 fp staging row between decode
    steps. The slot (and, paged, the full page reservation) is claimed at
    stream start; the pool itself is only touched once, at finalize, by the
    same admit scatter the blocking path uses — so a chunked admission is
    token-for-token identical to a blocking one."""
    __slots__ = ("req", "slot", "row", "toks", "base", "shared", "scatter",
                 "stem_tokens", "prefill_end", "tpf", "done", "logits",
                 "rpos")

    def __init__(self, req: Request, slot: int, row, toks, base: int,
                 shared, scatter, stem_tokens, prefill_end: int) -> None:
        self.req = req
        self.slot = slot
        self.row = row              # B=1 fp staging cache
        self.toks = toks            # (1, total) prompt tokens (stem-trimmed)
        self.base = base            # chunk 0 position origin (prefix / stem)
        self.shared = shared        # prefix-cache donor pages (chunk 0)
        self.scatter = scatter      # paged admission scatter vector
        self.stem_tokens = stem_tokens
        self.prefill_end = prefill_end
        self.tpf = time.perf_counter()
        self.done = 0               # prompt tokens prefilled so far
        self.logits = None          # last chunk's logits (first token)
        self.rpos = None

    @property
    def total(self) -> int:
        return int(self.toks.shape[1])


def scatter_pages(store, row, scatter_idx):
    """Write a B=1 admission row (L, 1, max_seq, K, hd) into the lane-dense
    page store (L, n_pages, ps // r, K, r*hd): logical page j of the row
    lands on physical page scatter_idx[j]. Only the row is re-laid out;
    the store is updated in place when its buffer is donated."""
    L, _, rows, K, width = store.shape
    hd = row.shape[-1]
    ps = rows * (width // hd)
    pages = pack_pages(row[:, 0].reshape(L, -1, ps, K, hd))
    return store.at[:, scatter_idx].set(pages.astype(store.dtype))


def _scatter_row(dst, src, spec, slot):
    """Write a B=1 admission row into pool slot ``slot``. ``spec`` is the
    family's batch-axis entry: an int (flat cache leaf) or a nested dict
    of per-leaf axes (state trees — ssm's stacked mLSTM/sLSTM states)."""
    if isinstance(spec, dict):
        return {k: (_scatter_row(dst[k], src[k], spec[k], slot)
                    if k in spec else dst[k]) for k in dst}
    return jax.lax.dynamic_update_slice_in_dim(
        dst, src.astype(dst.dtype), slot, axis=spec)


# adaptive chunked-prefill budget bounds (chunk_tokens="auto"): the
# per-step budget slides between these with decode pressure — both ends of
# the power-of-two bucket family, so auto mode compiles the same chunk
# executables a fixed budget would
_AUTO_CHUNK_MAX = 256
_AUTO_CHUNK_MIN = 8


class ContinuousEngine:
    """Continuous-batching counterpart of ``Engine`` (one compiled step
    executable shared by every pool composition; see module docstring)."""

    def __init__(self, api: ModelAPI, params, qcfg: QuantConfig,
                 n_slots: int = 4, max_seq: int = 2048, cushion=None,
                 scales=None, stats: Optional[ServeStats] = None,
                 mesh=None, kv_dtype=None, calib_batches=None,
                 prequant: bool = False, weight_bits: int = 8,
                 paged: bool = False,
                 page_size: int = 64, n_pages: Optional[int] = None,
                 prefix_cache: bool = False,
                 chunk_tokens: Optional[Union[int, str]] = None):
        self.api = api
        self.mesh = mesh
        params, scales = plan_quantization(
            api, params, qcfg, cushion=cushion, scales=scales,
            calib_batches=calib_batches, prequant=prequant,
            weight_bits=weight_bits)
        self.params = (shard_params_for_serving(params, mesh)
                       if mesh is not None else params)
        self.qcfg = qcfg
        self.n_slots = n_slots
        self.max_seq = cache_seq_len(max_seq)
        self.cushion = cushion
        self.scales = scales
        self.kv_dtype = kv_dtype
        self.prefix_len = cushion_prefix_len(cushion)
        # served-cushion provenance (matches Engine.cushion_fp, so a router
        # or launcher can assert every replica serves the same artifact)
        self.cushion_fp = cushion_fingerprint(cushion)
        axes = dict(api.cache_batch_axes)   # raises for unsupported families
        # recurrent-only caches (ssm) have no sequence axis: the pool never
        # runs out of positions — the max_seq admission capacity check only
        # applies to families with a sequence cache
        self._seq_cache = any(k in axes for k in ("k", "v"))
        if kv_dtype is not None:
            # per-slot dequant scales travel with their KV rows: the slot
            # scatter writes the admission prefill's (L,1,K) scales into
            # the pool's (L,n_slots,K) leaves at the same batch axis
            axes.update({"k_scale": 1, "v_scale": 1})
        self._axes = axes

        self.paged = bool(paged)
        self.page_size = page_size
        self._paged_leaves = api.paged_kv_leaves
        if self.paged:
            if not self._paged_leaves:
                raise ValueError(
                    "paged=True needs a pageable sequence cache "
                    "(PAGED_KV_LEAVES); this family's cache is per-request "
                    "state with nothing to page")
            if page_size % 8 or page_size % page_rows(api.cfg.head_dim):
                raise ValueError(
                    f"page_size {page_size} must be sublane-aligned "
                    f"(multiple of 8) and hold whole lane rows of the "
                    f"store ({page_rows(api.cfg.head_dim)} positions)")
            if self.max_seq % page_size:
                raise ValueError(
                    f"page_size {page_size} must divide the pool max_seq "
                    f"{self.max_seq}")
            if prefix_cache and kv_dtype is not None:
                raise ValueError(
                    "prefix_cache shares fp pages only: int8 donor pages "
                    "are quantized with the donor slot's dequant scales "
                    "and cannot be read under another slot's")
        self._P = self.max_seq // page_size
        c0 = self.prefix_len // page_size
        if n_pages is None:
            # worst case every slot owns all its content pages: paging then
            # never backpressures where the dense pool wouldn't (benchmarks
            # pass a smaller pool to realize the memory win)
            n_pages = n_slots * (self._P - c0) + 1
        self.n_pages = n_pages
        self._prefix_cache = bool(prefix_cache)
        # non-paged leaves (int8 scales, hybrid's Mamba state) keep their
        # dense per-slot rows and the plain slot scatter
        self._paged_axes = {k: v for k, v in axes.items()
                            if k not in self._paged_leaves}

        self.stats = stats if stats is not None else ServeStats(n_slots=n_slots)
        self.stats.n_slots = n_slots
        (self.stats.weight_bytes_fp, self.stats.weight_bytes_int8,
         self.stats.weight_bytes_int4) = resident_weight_bytes(self.params)

        # families that route through experts (models/moe.py) return their
        # routing counters from the blocking admission prefill and the
        # decode step; they come back with the tokens each call reads
        routes = {"route_counts": True} if api.counts_routes else {}

        # named step functions: the names label the compiled programs
        def prefill(p, b, c):
            return api.prefill(p, b, c, qcfg, cushion=cushion, scales=scales,
                               **routes)

        # prefix-cache tail prefill: the cushion is a traced argument (the
        # shared stem extends it), one compile per (stem pages, tail) shape
        def prefill_stem(p, b, c, cu):
            return api.prefill(p, b, c, qcfg, cushion=cu, scales=scales)

        # chunked admission: chunk k>0 replays tokens [done:done+c) on the
        # B=1 fp staging row with a static pos_offset — the cushion and all
        # earlier chunks are read back out of the row as the visible prefix.
        # One compile per (pos_offset, chunk shape) pair, the same profile
        # as the prefix-cache tail path above.
        def prefill_chunk(p, b, c, po):
            return api.prefill(p, b, c, qcfg, scales=scales, pos_offset=po)

        self._prefill = jax.jit(prefill)
        self._prefill_cu = jax.jit(prefill_stem)
        self._prefill_re = jax.jit(prefill_chunk, static_argnums=(3,))
        self._finalize_int8 = jax.jit(
            lambda row, S: api.finalize_staged_kv(
                row, self._init_cache(1), cushion, S),
            static_argnums=(1,))
        self.chunk_tokens: Optional[int] = None
        self.chunk_auto = False
        if chunk_tokens == "auto":
            # adaptive budget: the per-chunk token budget tracks decode
            # pressure (see _chunk_budget) — big chunks when the pool
            # idles (fast TTFT), small chunks when decode slots are
            # near-full (each chunk stalls every live decoder, so a busy
            # pool trades the prefiller's TTFT for the pool's TPOT)
            self.chunk_auto = True
            self.chunk_tokens = _AUTO_CHUNK_MAX
        elif chunk_tokens is not None:
            if isinstance(chunk_tokens, str) or chunk_tokens < 1:
                raise ValueError(f"chunk_tokens {chunk_tokens!r} must be "
                                 f">= 1 or the string 'auto'")
            # the per-step prefill token budget, bucketed to the power-of-
            # two family (min 8, PR 2's bucketing) so chunk executables are
            # shared across prompt lengths; prompts at or under one budget
            # admit blocking (a stream would only add staging overhead).
            # Families without chunk-resumable prefill (ssm, encdec, vlm,
            # hybrid) silently keep blocking admission.
            self.chunk_tokens = bucket_steps(int(chunk_tokens))

        def admit(cache, row, slot, pos, tok, rpos, tok0):
            cache = dict(cache)
            for key, ax in axes.items():
                cache[key] = _scatter_row(cache[key], row[key], ax, slot)
            for key in ("kc", "vc"):
                # batch-free fp cushion block: rewritten wholesale from the
                # admission row — bit-identical on every recycle, exactly
                # the KVSink/IntactKV rule the fp pools honour via the
                # full-row scatter
                if key in cache:
                    cache[key] = row[key].astype(cache[key].dtype)
            return (cache, pos.at[slot].set(jnp.asarray(rpos, jnp.int32)),
                    tok.at[slot].set(jnp.asarray(tok0, jnp.int32)))

        def admit_paged(cache, row, slot, pos, tok, rpos, tok0, scatter_idx):
            # route each logical page of the B=1 row to its physical page:
            # owned prompt pages land at their allocator-assigned index,
            # everything else (cushion positions, shared donor pages, pages
            # beyond the prompt) at the don't-care scratch page 0. The
            # shared kc/vc cushion leaves are deliberately untouched —
            # written once at pool reset, read-only ever after.
            cache = dict(cache)
            for key in self._paged_leaves:
                cache[key] = scatter_pages(cache[key], row[key], scatter_idx)
            for key, ax in self._paged_axes.items():
                cache[key] = _scatter_row(cache[key], row[key], ax, slot)
            return (cache, pos.at[slot].set(jnp.asarray(rpos, jnp.int32)),
                    tok.at[slot].set(jnp.asarray(tok0, jnp.int32)))

        def step(p, tok, pos, live, cache, cu):
            # cu: the paged pool's shared read-only cushion block, passed
            # OUTSIDE the donated cache so its buffers are never consumed —
            # the same two device arrays serve every step of the engine's
            # lifetime (empty dict for contiguous pools, whose cushion
            # lives inside the cache rows / kc leaves)
            full = dict(cache)
            full.update(cu)
            logits, full, *counts = api.decode_step(p, tok, pos, full, qcfg,
                                                    scales=scales, **routes)
            out_cache = {k: full[k] for k in cache}
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nxt = jnp.where(live, nxt, 0)          # dead rows feed token 0
            pos = jnp.where(live, pos + 1, pos)    # freeze retired offsets
            return (nxt, pos, out_cache, *counts)

        # donate the pool cache: the old buffer is dead once self.cache is
        # rebound, and without donation every per-layer cache write would
        # materialize a pool-sized copy per decode step (and 2x peak HBM).
        # Backends that can't donate (CPU) just ignore the hint.
        self._admit = jax.jit(admit, donate_argnums=(0,))
        self._admit_paged = jax.jit(admit_paged, donate_argnums=(0,))
        self._step = jax.jit(step, donate_argnums=(4,))
        self.start()

    # ------------------------------------------------------------------
    # Pool state
    # ------------------------------------------------------------------

    def _init_cache(self, batch: int):
        return self.api.init_cache(batch, self.max_seq,
                                   kv_dtype=self.kv_dtype,
                                   prefix_len=self.prefix_len,
                                   per_slot_scales=self.kv_dtype is not None)

    def _staging_row(self):
        """B=1 fp staging row for chunked admission. int8 pools stage fp
        too: finalize_staged_kv requantizes the finished row in one shot so
        the per-slot dequant scales calibrate over the WHOLE prompt, exactly
        like a blocking admission prefill."""
        if self.kv_dtype is None:
            return self._shard_cache(self._init_cache(1))
        row = self.api.init_cache(1, self.max_seq)
        if self.mesh is None:
            return row
        return jax.device_put(row, SH.cache_shardings(
            self.api.cache_roles(None), row, self.mesh))

    def _reset_pool(self) -> None:
        if self.paged:
            self._reset_pool_paged()
        else:
            self.cache = self._shard_cache(self._init_cache(self.n_slots))
            self.cushion_block = {}
        self.stats.pool_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(
                (self.cache, self.cushion_block)))
        self.pos = jnp.zeros((self.n_slots,), jnp.int32)
        self.tok = jnp.zeros((self.n_slots,), jnp.int32)
        self.live = np.zeros((self.n_slots,), bool)
        self._slots = [_Slot() for _ in range(self.n_slots)]

    def _reset_pool_paged(self) -> None:
        """Build the paged pool: the dense (L, n_slots, max_seq, K, hd) KV
        leaves become a flat lane-dense (L, n_pages, ps/r, K, r*hd) store
        + an (L, n_slots, P) page table; every other leaf (int8 scales,
        hybrid's Mamba state) keeps its dense per-slot row. The fp cushion
        block is written ONCE here into batch-free kc/vc leaves — the
        refcounted, read-only cushion page every slot maps — and never
        copied again."""
        shapes = jax.eval_shape(lambda: self._init_cache(self.n_slots))
        ps = self.page_size
        pool = {}
        for key, sd in shapes.items():
            if key in self._paged_leaves:
                L, _, _, K, hd = sd.shape
                store = jax.eval_shape(pack_pages, jax.ShapeDtypeStruct(
                    (L, self.n_pages, ps, K, hd), sd.dtype))
                pool[key] = jnp.zeros(store.shape, sd.dtype)
            elif key not in ("kc", "vc"):
                pool[key] = jnp.zeros(sd.shape, sd.dtype)
        cu = {}
        if self.prefix_len:
            kvc = self.cushion["kv"]
            dt = (shapes["kc"].dtype if "kc" in shapes
                  else pool[self._paged_leaves[0]].dtype)
            cu = {"kc": jnp.asarray(kvc["k"]).astype(dt),
                  "vc": jnp.asarray(kvc["v"]).astype(dt)}
        self._pt_layers = int(pool[self._paged_leaves[0]].shape[0])
        self._pool = PagePool(self.n_slots, self.max_seq, ps, self.n_pages,
                              cushion_m=self.prefix_len,
                              prefix_cache=self._prefix_cache)
        pool["page_table"] = jnp.zeros(
            (self._pt_layers, self.n_slots, self._P), jnp.int32)
        self._pool.dirty = False            # device table == host (all 0)
        self.cache = self._shard_cache(pool, paged=True)
        # the shared cushion block lives OUTSIDE self.cache: it is never
        # passed through a donated jit, so these exact device buffers are
        # read (never copied, never consumed) by every decode step and
        # survive every admission/recycle — the "one refcounted, read-only
        # cushion page". PagePool.gauges() counts its logical refs.
        self.cushion_block = self._shard_cache(cu, paged=True)
        self._hpos = np.zeros((self.n_slots,), np.int64)

    def _shard_cache(self, cache, paged: bool = False):
        """Lay a pool (or B=1 admission row) out over the tp mesh along the
        family's cache_roles axes (heads / Mamba channels; see
        models/*.cache_roles). The admission row shares the pool's layout so
        the slot scatter is shard-local, never a reshard. The paged pool
        keeps the KV-heads axis of its page store on "M" (pages replace the
        batch/seq dims, heads stay sharded: (L, n_pages, ps/r, K, r*hd));
        the page table and the shared cushion block replicate."""
        if self.mesh is None:
            return cache
        roles = self.api.cache_roles(self.kv_dtype,
                                     per_slot_scales=self.kv_dtype is not None)
        if paged:
            roles = dict(roles)
            for key in self._paged_leaves:
                r = tuple(roles.get(key, ())) + (None,) * 5
                # (L,B,S,K,hd) role -> (L,n_pages,ps/r,K,r*hd): keep the
                # layer and heads/head-dim entries, pages/rows replicate
                roles[key] = (r[0], None, None, r[3], r[4])
        return jax.device_put(cache, SH.cache_shardings(roles, cache,
                                                        self.mesh))

    def _sync_page_table(self) -> None:
        """Mirror the allocator's host table to the device pool, stacked
        over the layer axis (decode_step scans the cache layer-wise, so
        every pool leaf is L-leading; the table itself is identical per
        layer). Replicated under a mesh — page ids are layout metadata."""
        pt = np.broadcast_to(self._pool.table[None],
                             (self._pt_layers,) + self._pool.table.shape)
        arr = jnp.asarray(pt)
        if self.mesh is not None:
            arr = jax.device_put(arr, jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()))
        cache = dict(self.cache)
        cache["page_table"] = arr
        self.cache = cache
        self._pool.dirty = False
        self.stats.page_table_syncs += 1

    def _publish_gauges(self) -> None:
        g = self._pool.gauges()
        st = self.stats
        st.pages_total = g["pages_total"]
        st.pages_free = g["pages_free"]
        st.pages_shared = g["pages_shared"]
        st.cushion_page_refs = g["cushion_page_refs"]
        st.prefix_hits = self._pool.prefix_hits
        st.prefix_misses = self._pool.prefix_misses

    def _positions_needed(self, req: Request) -> int:
        S = req.batch["tokens"].shape[1]
        if "patches" in req.batch:
            S += req.batch["patches"].shape[1]
        return self.prefix_len + S + req.max_new_tokens

    # ------------------------------------------------------------------
    # Incremental serving API (the replica router's contract)
    # ------------------------------------------------------------------

    def start(self) -> None:
        """Open a serving session: reset the pool, the occupancy stats and
        the result buffers. Compiled executables are kept."""
        with SH.use_mesh(self.mesh):
            self._reset_pool()
        self.stats.reset()
        if self.paged:
            self._publish_gauges()
        self._results: Dict[int, RequestOutput] = {}
        self._ttft: Dict[int, float] = {}
        self._streams: collections.deque = collections.deque()
        self._expired: List[int] = []
        self._t0 = time.perf_counter()

    def now(self) -> float:
        """Seconds since ``start()`` (the session-relative clock every
        timestamp in ``RequestOutput`` is expressed in)."""
        return time.perf_counter() - self._t0

    def free_slots(self) -> List[int]:
        return [int(i) for i in np.flatnonzero(~self.live)
                if self._slots[i].req is None]

    @property
    def live_count(self) -> int:
        return int(self.live.sum())

    @property
    def prefilling(self) -> int:
        """Admission streams currently mid-prefill (PREFILLING slots). The
        router must keep stepping an engine whose only work is a stream."""
        return len(self._streams)

    def is_prefilling(self, uid: int) -> bool:
        """True while ``uid`` is a PREFILLING slot (partially-admitted).
        The engine itself enforces deadlines between chunks for these
        (``pop_expired``); the router leaves them out of its mid-decode
        deadline sweep so the rejection reason stays ``deadline-prefill``."""
        return any(st.req.uid == uid for st in self._streams)

    def pop_expired(self) -> List[int]:
        """Drain uids of streams retired between chunks for blowing their
        deadline (no result was produced; the router maps these to
        ``deadline-prefill`` rejections and clears its inflight entry)."""
        out, self._expired = self._expired, []
        return out

    def live_requests(self) -> List[Request]:
        """Requests currently occupying a slot — live decoders AND
        partially-prefilled streams (the router fails these over to
        surviving replicas when this engine dies)."""
        return [s.req for s in self._slots if s.req is not None]

    def try_admit(self, req: Request) -> bool:
        """Admit ``req`` into a free slot (B=1 prefill + full-row scatter,
        or the page scatter on a paged pool). Returns False when no slot is
        free — or, paged, when the page pool can't host the request right
        now — queueing and backpressure are the caller's job, the pool
        itself never buffers. Raises ValueError (and counts
        ``stats.positions_exhausted``) for a request whose prompt+budget
        can NEVER fit the pool: that's a permanent rejection, not
        backpressure.

        With ``chunk_tokens`` set (and a chunk-capable family), a prompt
        longer than one chunk budget starts a PREFILLING stream instead of
        prefilling here: the slot (and pages) are claimed now, the prompt
        is replayed one chunk per ``step()`` between decodes, and the pool
        admit happens at the final chunk — True means the request is this
        engine's responsibility either way."""
        free = self.free_slots()
        if not free:
            return False
        if (self.chunk_tokens is not None
                and self.api.supports_chunked_prefill
                and not ({"patches", "frames"} & set(req.batch))
                and req.batch["tokens"].shape[1] > self._chunk_budget()):
            return self._start_stream(req, free[0])
        with span("serve.admit", uid=req.uid) as sid:
            return self._admit_request(req, free[0], sid)

    def _chunk_budget(self) -> int:
        """Per-step prefill token budget. Fixed ``chunk_tokens`` unless
        auto mode: then it shrinks with decode pressure — every chunk
        stalls every live decoder for the chunk's prefill, so a near-full
        pool runs small chunks (protect TPOT) while an idle pool runs big
        ones (fewer interleave steps, better TTFT). Scales linearly from
        ``_AUTO_CHUNK_MAX`` at 0 live decoders to ``_AUTO_CHUNK_MIN`` at a
        full pool, bucketed to the same power-of-two executables as fixed
        budgets."""
        if not self.chunk_auto:
            return self.chunk_tokens
        pressure = float(self.live.sum()) / max(1, self.n_slots)
        want = int(round(_AUTO_CHUNK_MAX * (1.0 - pressure)))
        return bucket_steps(max(_AUTO_CHUNK_MIN, want))

    def step(self) -> List[int]:
        """Runs one prefill chunk of the oldest pending admission stream
        (chunked admission; no-op without streams), then one lock-step
        decode over the whole pool, retiring slots that hit EOS or budget.
        Returns the uids retired by the decode (their outputs are ready in
        ``pop_finished``). No-op when nothing is live or prefilling."""
        if self._streams:
            self._advance_stream()
        if not self.live.any():
            return []
        with span("serve.step") as sid:
            live_idx = np.flatnonzero(self.live)
            if self.paged:
                # map this step's write page for every live slot from its
                # admission reservation (lazy allocate-on-append), then
                # mirror any table change to the device before the kernel
                # reads it
                with span("serve.step.pages"):
                    for slot in live_idx:
                        self._pool.ensure_mapped(int(slot),
                                                 int(self._hpos[slot]))
                    if self._pool.dirty:
                        self._sync_page_table()
            with SH.use_mesh(self.mesh):
                self.tok, self.pos, self.cache, *counts = self._step(
                    self.params, self.tok, self.pos, jnp.asarray(self.live),
                    self.cache, self.cushion_block)
            if self.paged:
                self._hpos[live_idx] += 1   # mirror the device pos advance
            # the one host sync per step: the tokens, and the routing
            # counters of an expert family in the same transfer
            if counts:
                toks, counts = host_sync((self.tok, counts))
                self._count_routes("jit_step", sid, counts)
            else:
                toks = host_sync(self.tok)
            with span("serve.step.retire"):
                self.stats.steps += 1
                self.stats.live_slot_steps += int(self.live.sum())
                retired: List[int] = []
                for slot in live_idx:
                    s = self._slots[slot]
                    req = s.req
                    s.tokens.append(int(toks[slot]))
                    if (len(s.tokens) >= req.max_new_tokens
                            or (req.eos_id is not None
                                and s.tokens[-1] == req.eos_id)):
                        retired.append(req.uid)
                        self._retire(int(slot))
            return retired

    def cancel(self, uid: int) -> bool:
        """Free the slot holding ``uid`` without producing a result
        (deadline expiry mid-decode, failover bookkeeping). The slot's
        stale KV needs no scrubbing: the next admission's full-row scatter
        overwrites it. A PREFILLING stream is dropped the same way (its
        staged row is discarded, its page reservation returned). Returns
        False if ``uid`` is not live here."""
        for st in self._streams:
            if st.req.uid == uid:
                self._streams.remove(st)
                self.stats.canceled += 1
                self._abort_stream(st, expired=False)
                return True
        for slot, s in enumerate(self._slots):
            if s.req is not None and s.req.uid == uid:
                self.live[slot] = False
                s.req = None
                self._ttft.pop(uid, None)
                self.stats.canceled += 1
                if self.paged:
                    # return the slot's pages; its frozen-pos dead writes
                    # land on the scratch page once the table row is zeroed
                    self._pool.release(slot)
                    self._publish_gauges()
                return True
        return False

    def pop_finished(self) -> List[RequestOutput]:
        """Drain completed outputs (uid-sorted) accumulated since the last
        call."""
        out = [self._results[u] for u in sorted(self._results)]
        self._results = {}
        return out

    # ------------------------------------------------------------------
    # Admission / retirement internals
    # ------------------------------------------------------------------

    def _check_capacity(self, req: Request) -> int:
        need = self._positions_needed(req)
        if self._seq_cache and need > self.max_seq:
            # permanent rejection (the request can NEVER fit this pool) —
            # counted explicitly instead of silently running out of
            # positions mid-decode. run() drops the request; the router
            # maps the raise to an "invalid" rejection, never a retry.
            self.stats.positions_exhausted += 1
            raise ValueError(
                f"request {req.uid} needs {need} positions "
                f"(prefix {self.prefix_len} + prompt + budget) "
                f"> pool max_seq {self.max_seq}")
        return need

    def _count_routes(self, program: str, span_id: int, counts) -> None:
        """Add one call's routing counters to the totals and record them
        under the call's span."""
        rows, experts, dropped = (int(v) for v in counts[0])
        self.stats.routed_rows += rows
        self.stats.experts_reached += experts
        self.stats.dropped_rows += dropped
        count(f"moe.{program}", span_id, rows=rows, experts=experts,
              dropped=dropped)

    def _admit_request(self, req: Request, slot: int,
                       span_id: Optional[int] = None) -> bool:
        """Blocking admission: B=1 prefill, then the full-row scatter into
        ``slot`` (dense pool) or the page scatter (paged pool). Paged, it
        first claims pages (full reservation — mid-decode exhaustion is
        impossible) and scatters each owned prompt page to its physical
        page. On a prefix-cache hit the donor's read-only stem pages are
        mapped (refcount++) and only the tail is prefilled against the
        extended cushion. Returns False (backpressure) when the page pool
        can't host the request right now."""
        with span("serve.admit.alloc"):
            need = self._check_capacity(req)
            prefill_end = need - req.max_new_tokens     # prefix + prompt
            tokens = None
            shared: List[int] = []
            scatter = None
            if self.paged:
                if (self._prefix_cache
                        and not ({"patches", "frames"} & set(req.batch))):
                    tokens = np.asarray(req.batch["tokens"][0])
                    shared = self._pool.lookup_stem(tokens)
                scatter = self._pool.admit(slot, prefill_end, need,
                                           shared=shared)
                if scatter is None:
                    return False    # page-pool backpressure: retryable
        tpf = time.perf_counter()
        with SH.use_mesh(self.mesh):
            row = self._shard_cache(self._init_cache(1))
            if shared:
                # extended-cushion tail prefill: the donor's stem pages ARE
                # the stem's KV (bit-identical — stem hiddens depend only on
                # cushion+stem), so gather them once and prefill only the
                # uncovered tail at its true absolute positions
                stem_end = (self._pool.c0 + len(shared)) * self.page_size
                cu2 = self._stem_cushion(shared)
                t_skip = stem_end - self.prefix_len  # prompt tokens covered
                b2 = dict(req.batch)
                b2["tokens"] = req.batch["tokens"][:, t_skip:]
                logits, row, rpos = self._prefill_cu(self.params, b2, row,
                                                     cu2)
                counts = []     # the stem prefill is not jit_prefill
            else:
                logits, row, rpos, *counts = self._prefill(
                    self.params, req.batch, row)
            logits = logits[:, -1] if logits.ndim == 3 else logits
            tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
            sl = jnp.asarray(slot, jnp.int32)
            if self.paged:
                self.cache, self.pos, self.tok = self._admit_paged(
                    self.cache, row, sl, self.pos, self.tok, rpos, tok0,
                    jnp.asarray(scatter))
            else:
                self.cache, self.pos, self.tok = self._admit(
                    self.cache, row, sl, self.pos, self.tok, rpos, tok0)
        if counts:
            first, counts = host_sync((tok0, counts))
            self._count_routes("jit_prefill", span_id, counts)
        else:
            first = host_sync(tok0)
        first = int(first)
        with span("serve.admit.book"):
            if tokens is not None:
                self._pool.register_stem(slot, tokens, prefill_end)
            if self.paged:
                self._hpos[slot] = prefill_end
            self._book_admission(req, slot, first, tpf)
            if self.paged:
                self._publish_gauges()
        return True

    def _stem_cushion(self, shared: List[int]):
        """Extended cushion for a prefix-cache hit: the real cushion KV
        concatenated with the donor stem pages gathered from the page store
        (skipping the cushion rows that share the stem's first page)."""
        ps = self.page_size
        c0 = self._pool.c0
        donors = jnp.asarray(shared, jnp.int32)
        hd = self.api.cfg.head_dim
        kp = unpack_pages(self.cache["k"][:, donors], hd)  # (L,h,ps,K,hd)
        vp = unpack_pages(self.cache["v"][:, donors], hd)
        kp = kp.reshape(kp.shape[0], -1, *kp.shape[3:])
        vp = vp.reshape(vp.shape[0], -1, *vp.shape[3:])
        skip = self.prefix_len - c0 * ps            # cushion rows in page c0
        if self.prefix_len:
            kvc = self.cushion["kv"]
            return {"kv": {
                "k": jnp.concatenate(
                    [jnp.asarray(kvc["k"], kp.dtype), kp[:, skip:]], axis=1),
                "v": jnp.concatenate(
                    [jnp.asarray(kvc["v"], vp.dtype), vp[:, skip:]], axis=1)}}
        return {"kv": {"k": kp, "v": vp}}

    # ------------------------------------------------------------------
    # Chunked admission (PREFILLING streams)
    # ------------------------------------------------------------------

    def _start_stream(self, req: Request, slot: int) -> bool:
        """Claim a slot (and, paged, the full page reservation — admission
        backpressure is decided up front, exactly like blocking) and queue
        the prompt for chunk-by-chunk prefill. Nothing touches the pool
        until the final chunk's admit scatter."""
        need = self._check_capacity(req)
        prefill_end = need - req.max_new_tokens     # prefix + prompt
        scatter = None
        shared: List[int] = []
        stem_tokens = None
        if self.paged:
            if self._prefix_cache:
                stem_tokens = np.asarray(req.batch["tokens"][0])
                shared = self._pool.lookup_stem(stem_tokens)
            scatter = self._pool.admit(slot, prefill_end, need, shared=shared)
            if scatter is None:
                return False    # page-pool backpressure: retryable
        toks = req.batch["tokens"]
        base = self.prefix_len
        if shared:
            # donor pages cover the stem; only the uncovered tail is chunked
            base = (self._pool.c0 + len(shared)) * self.page_size
            toks = toks[:, base - self.prefix_len:]
        with SH.use_mesh(self.mesh):
            row = self._staging_row()
        self._slots[slot].req = req     # PREFILLING: slot held, not live
        self._streams.append(_PrefillStream(req, slot, row, toks, base,
                                            shared, scatter, stem_tokens,
                                            prefill_end))
        return True

    def _advance_stream(self) -> None:
        """Run ONE prefill chunk (the per-step token budget) of the oldest
        pending stream, round-robin across streams so short prompts aren't
        head-of-line blocked behind a long one; finalize when the prompt is
        exhausted. Deadlines are enforced between chunks: an expired stream
        frees its slot (and pages) without a result."""
        st = self._streams.popleft()
        req = st.req
        if req.deadline_s is not None and self.now() > req.deadline_s:
            self._abort_stream(st, expired=True)
            return
        c = min(self._chunk_budget(), st.total - st.done)
        chunk = st.toks[:, st.done:st.done + c]
        with SH.use_mesh(self.mesh):
            if st.done == 0:
                b0 = dict(req.batch)
                b0["tokens"] = chunk
                if st.shared:
                    st.logits, st.row, st.rpos = self._prefill_cu(
                        self.params, b0, st.row, self._stem_cushion(st.shared))
                else:
                    st.logits, st.row, st.rpos = self._prefill(
                        self.params, b0, st.row)[:3]
            else:
                st.logits, st.row, st.rpos = self._prefill_re(
                    self.params, {"tokens": chunk}, st.row,
                    st.base + st.done)
        st.done += c
        self.stats.prefill_chunks += 1
        if st.done < st.total:
            self._streams.append(st)
        else:
            self._finalize_stream(st)

    def _finalize_stream(self, st: _PrefillStream) -> None:
        """Admit the finished staging row into the pool — the SAME admit
        scatter (and, int8, the same whole-prompt scale calibration) as the
        blocking path, so chunked and blocking admissions are
        token-for-token identical from the pool's point of view."""
        req, slot = st.req, st.slot
        with SH.use_mesh(self.mesh):
            logits = st.logits[:, -1] if st.logits.ndim == 3 else st.logits
            tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
            row = st.row
            if self.kv_dtype is not None:
                row = self._finalize_int8(row, st.total)
            sl = jnp.asarray(slot, jnp.int32)
            if self.paged:
                self.cache, self.pos, self.tok = self._admit_paged(
                    self.cache, row, sl, self.pos, self.tok, st.rpos, tok0,
                    jnp.asarray(st.scatter))
            else:
                self.cache, self.pos, self.tok = self._admit(
                    self.cache, row, sl, self.pos, self.tok, st.rpos, tok0)
        first = int(jax.block_until_ready(tok0))
        if st.stem_tokens is not None:
            self._pool.register_stem(slot, st.stem_tokens, st.prefill_end)
        if self.paged:
            self._hpos[slot] = st.prefill_end
        self._book_admission(req, slot, first, st.tpf)
        if self.paged:
            self._publish_gauges()

    def _abort_stream(self, st: _PrefillStream, expired: bool) -> None:
        """Drop a PREFILLING stream without a result (deadline blown
        between chunks, cancel, drain): free the slot, return the page
        reservation, discard the staged row."""
        self._slots[st.slot].req = None
        if self.paged:
            self._pool.release(st.slot)
            self._publish_gauges()
        if expired:
            self.stats.deadline_prefill += 1
            self._expired.append(st.req.uid)

    def _book_admission(self, req: Request, slot: int, first: int,
                        tpf: float) -> None:
        now = time.perf_counter()

        s = self._slots[slot]
        if s.used:
            self.stats.recycles += 1
        s.used = True
        s.req = req
        s.tokens = [first]
        s.t_admit = now - self._t0
        s.t_first = now
        self.stats.admitted += 1
        ttft = (now - tpf) * 1e3
        self._ttft[req.uid] = ttft
        done = (req.max_new_tokens <= 1
                or (req.eos_id is not None and first == req.eos_id))
        self.live[slot] = not done
        if done:
            self._retire(slot)

    def _retire(self, slot: int) -> None:
        s = self._slots[slot]
        req = s.req
        assert req is not None
        now = time.perf_counter()
        n = len(s.tokens)
        tpot = 0.0 if n <= 1 else (now - s.t_first) * 1e3 / (n - 1)
        self._results[req.uid] = RequestOutput(
            uid=req.uid, tokens=np.asarray(s.tokens, np.int32),
            ttft_ms=self._ttft[req.uid], tpot_ms=tpot, slot=slot,
            admitted_s=s.t_admit, finished_s=now - self._t0,
            latency_s=(now - self._t0) - req.arrival_s)
        self.live[slot] = False
        s.req = None
        self.stats.finished += 1
        if self.paged:
            # retirement RETURNS pages (free list + refcount decrements on
            # shared donors) instead of re-writing anything; the zeroed
            # table row routes the dead row's frozen-pos writes to scratch
            self._pool.release(slot)
            self._publish_gauges()

    # ------------------------------------------------------------------
    # Trace replay
    # ------------------------------------------------------------------

    def run(self, requests: Sequence[Request]) -> List[RequestOutput]:
        """Replay a trace: admit each request once its arrival time passes
        and a slot is free (FIFO), decode the pool in lock-step, return
        outputs sorted by uid. Re-entrant: the pool and the occupancy
        stats are reset per run (compiled executables are kept).

        ``KeyboardInterrupt`` (ctrl-C / the launcher's SIGTERM handler)
        triggers a graceful drain instead of dying mid-step: admission
        stops, live slots decode to completion, the queued remainder is
        dropped, and the completed outputs are returned with
        ``stats.interrupted`` set. A second interrupt aborts immediately."""
        self.start()
        queue = collections.deque(
            sorted(requests, key=lambda r: (r.arrival_s, r.uid)))
        done: Dict[int, RequestOutput] = {}
        draining = False

        while queue or self.live.any() or self._streams:
            try:
                if draining:
                    # partial admissions are unfinished work, dropped like
                    # the queued remainder (their slots/pages come back)
                    while self._streams:
                        self._abort_stream(self._streams.popleft(),
                                           expired=False)
                    if not self.live.any():
                        break
                else:
                    now = self.now()
                    # admit every arrived request that fits a free slot;
                    # requests that can NEVER fit (prompt+budget > capacity)
                    # are rejected outright — counted in
                    # stats.positions_exhausted, absent from the results —
                    # instead of crashing the whole trace
                    while queue and queue[0].arrival_s <= now:
                        try:
                            if not self.try_admit(queue[0]):
                                break
                        except ValueError:
                            queue.popleft()
                            continue
                        queue.popleft()
                    if not self.live.any() and not self._streams:
                        if queue:   # pool idle, next arrival in the future
                            time.sleep(min(1e-3, max(
                                0.0, queue[0].arrival_s - self.now())))
                        for o in self.pop_finished():
                            done[o.uid] = o
                        continue
                self.step()
                for o in self.pop_finished():
                    done[o.uid] = o
            except KeyboardInterrupt:
                if draining:
                    raise               # second interrupt: stop for real
                draining = True
                self.stats.interrupted = True

        for o in self.pop_finished():
            done[o.uid] = o
        return [done[u] for u in sorted(done)]
