"""Compile-count, host-sync, span and serving-occupancy instrumentation.

Compile counting is built on ``jax.monitoring`` events.

XLA emits a ``/jax/core/compile/backend_compile_duration`` event per backend
compilation. The absolute multiplier per ``jit`` cache miss is a jax-version
detail (helper executables also compile), but the count is deterministic for
a fixed program, which is all the search/bench assertions need: *constant*
compile count independent of prefix length, and fast-path count « reference
count.

Usage::

    with count_compiles() as c:
        run_search(...)
    print(c.count)

Counters nest (each active counter sees every compile event), so a bench can
hold an outer counter while tests open inner ones.

Spans: ``span(name, uid=None)`` times a host phase of the serving loop. Each
span is a ``jax.profiler.TraceAnnotation``, so it sits beside the device ops
in any profiler trace, and one ``Span`` record in a process-wide ring of the
last ``SPAN_RING`` spans, always on (a flight recorder). ``spans()`` returns
the ring, ``span_summary()`` the per-name count and self-time percentiles.
Python's generation-2 collections land in the same ring as ``python.gc``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import itertools
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_active: List["CompileCounter"] = []
_registered = False


@dataclasses.dataclass
class CompileCounter:
    count: int = 0


def _listener(event: str, duration: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        for c in _active:
            c.count += 1


@contextlib.contextmanager
def count_compiles() -> Iterator[CompileCounter]:
    """Count backend compilations that happen inside the ``with`` block.

    The listener registers once per process (jax.monitoring has no
    unregister API across versions); counters activate/deactivate via the
    ``_active`` stack instead.
    """
    global _registered
    if not _registered:
        jax.monitoring.register_event_duration_secs_listener(_listener)
        _registered = True
    c = CompileCounter()
    _active.append(c)
    try:
        yield c
    finally:
        _active.remove(c)


@dataclasses.dataclass
class HostSyncCounter:
    count: int = 0


_sync_active: List["HostSyncCounter"] = []


@contextlib.contextmanager
def count_host_syncs() -> Iterator[HostSyncCounter]:
    """Count blocking device->host transfers routed through `host_sync`
    inside the ``with`` block. jax.monitoring has no transfer event, so
    accounting works by convention: host-loop code that must block on
    device values (the prefix-tuning metric drain) fetches them through
    `host_sync` instead of calling ``float(...)`` / ``np.asarray`` per
    value, and regression tests bound the count. Counters nest like
    `count_compiles`."""
    c = HostSyncCounter()
    _sync_active.append(c)
    try:
        yield c
    finally:
        _sync_active.remove(c)


def host_sync(tree):
    """THE accounting choke point for intentional blocking transfers:
    one call = one device->host round trip (``jax.device_get`` fetches the
    whole tree in a single batch). Dispatch-blocking per-step ``float(v)``
    conversions were the original prefix_tune perf bug — anything tempted
    to sync in a loop should batch values and come through here.

    The wait is a span: ``<caller>.wait`` under the innermost open span
    (``serve.step.wait`` inside ``serve.step``), else ``host_sync``. A
    later ``np.asarray`` of the same array reads the host copy this call
    made, with no second transfer."""
    for c in _sync_active:
        c.count += 1
    caller = RECORDER.current()
    with RECORDER.span(caller + ".wait" if caller else "host_sync"):
        return jax.device_get(tree)


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

SPAN_RING = 1 << 16
GC_SPAN = "python.gc"


class Span(NamedTuple):
    """One closed span: ``time.perf_counter_ns()`` bounds, its id, the id of
    the span open around it on the same thread (None for a root) and the
    request uid it served (None where it serves no one request)."""
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    uid: Optional[int]


class _OpenSpan:
    """A span while it is open (``SpanRecorder.span``). A plain class, not a
    generator, and a plain tuple in the ring: the recorder is always on."""
    __slots__ = ("rec", "name", "uid", "sid", "parent", "stack", "ann", "t0")

    def __init__(self, rec: "SpanRecorder", name: str, uid: Optional[int]):
        self.rec, self.name, self.uid = rec, name, uid

    def __enter__(self) -> int:
        self.stack = stack = self.rec._stack()
        self.sid = sid = next(self.rec._ids)
        self.parent = stack[-1][1] if stack else None
        stack.append((self.name, sid))
        self.ann = TraceAnnotation(self.name)
        self.t0 = time.perf_counter_ns()
        self.ann.__enter__()
        return sid

    def __exit__(self, *exc) -> bool:
        self.ann.__exit__(*exc)
        t1 = time.perf_counter_ns()
        self.stack.pop()
        self.rec._ring.append((self.name, self.t0, t1, self.sid,
                               self.parent, self.uid))
        return False


class SpanRecorder:
    """The last ``maxlen`` closed spans, oldest first. Parents come from a
    per-thread stack of open spans, so spans of two threads never nest."""

    def __init__(self, maxlen: int = SPAN_RING):
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._gc_open: Optional[tuple] = None

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def current(self) -> Optional[str]:
        """Name of the innermost span open on this thread."""
        stack = self._stack()
        return stack[-1][0] if stack else None

    def span(self, name: str, uid: Optional[int] = None) -> _OpenSpan:
        """Context manager timing the ``with`` block as span ``name``;
        entering it gives the span's id."""
        return _OpenSpan(self, name, uid)

    def spans(self) -> List[Span]:
        return [Span._make(r) for r in self._ring]

    def summary(self) -> Dict[str, dict]:
        """Per span name: count, p50 and p99 self-time in ms (duration less
        the spans directly inside it)."""
        recs = self.spans()
        inner: Dict[int, int] = {}
        for s in recs:
            if s.parent_id is not None:
                inner[s.parent_id] = (inner.get(s.parent_id, 0)
                                      + s.end_ns - s.start_ns)
        own: Dict[str, list] = {}
        for s in recs:
            own.setdefault(s.name, []).append(
                s.end_ns - s.start_ns - inner.get(s.span_id, 0))
        return {name: {"count": len(v),
                       "p50_ms": float(np.percentile(v, 50)) * 1e-6,
                       "p99_ms": float(np.percentile(v, 99)) * 1e-6}
                for name, v in sorted(own.items())}

    def on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook: a generation-2 collection becomes a
        ``python.gc`` span inside whatever span its thread has open."""
        if info["generation"] != 2:
            return
        if phase == "start":
            ann = TraceAnnotation(GC_SPAN)
            ann.__enter__()
            self._gc_open = (time.perf_counter_ns(), ann)
        elif self._gc_open is not None:
            t0, ann = self._gc_open
            t1 = time.perf_counter_ns()
            ann.__exit__(None, None, None)
            self._gc_open = None
            stack = self._stack()
            self._ring.append((GC_SPAN, t0, t1, next(self._ids),
                               stack[-1][1] if stack else None, None))


RECORDER = SpanRecorder()
span = RECORDER.span
spans = RECORDER.spans
span_summary = RECORDER.summary
gc.callbacks.append(RECORDER.on_gc)


def resident_weight_bytes(params) -> tuple:
    """(fp_bytes, int8_bytes, int4_bytes) of a served parameter tree — how
    many bytes per weight the decode loop streams from HBM. A prequantized
    tree (core.quantization.prequantize_tree) holds its qdot-consumed
    matrices as int8 ``w_int`` leaves (1 byte/weight vs 2-4 for bf16/fp32)
    or nibble-packed int8 ``w_packed`` leaves (0.5 byte/weight, counted by
    their packed size); everything else (embeddings, norms, scales, MoE
    experts) counts as fp. Surfaced in ``ServeStats`` and printed by
    launch/serve.py so the fp/W8A8/W4A8 A/B shows its memory side, not just
    TTFT/TPOT."""
    fp = i8 = i4 = 0
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in leaves:
        if not hasattr(leaf, "dtype"):
            continue
        n = int(leaf.size) * leaf.dtype.itemsize
        if path and "w_packed" in str(path[-1]):
            i4 += n
        elif str(leaf.dtype) == "int8":
            i8 += n
        else:
            fp += n
    return fp, i8, i4


@dataclasses.dataclass
class ServeStats:
    """Continuous-batching scheduler counters (serving/scheduler.py).

    ``steps`` counts lock-step decode iterations over the slot pool;
    ``live_slot_steps`` accumulates how many of the pool's slots held a live
    request at each step, so ``occupancy()`` is the mean fraction of decode
    compute spent on real tokens (1.0 = perfectly packed, low values =
    the pool idles between arrivals). Retired/empty slots still run
    (compute-masked, outputs discarded) — occupancy is the serve bench's
    measure of that waste.

    ``weight_bytes_fp`` / ``weight_bytes_int8`` record the resident served
    parameter bytes by storage precision (``resident_weight_bytes``) —
    configuration facts set at engine load, preserved across ``reset()``.

    ``canceled`` counts live slots freed without a result (deadline expiry
    mid-decode, router failover bookkeeping); ``interrupted`` records that
    the run ended via the graceful-drain path (ctrl-C / SIGTERM) rather
    than trace exhaustion.

    Page-pool gauges (zero on contiguous pools): ``pages_total`` /
    ``pool_bytes`` are layout facts set at pool construction (preserved
    across ``reset()`` like the weight bytes); ``pages_free`` /
    ``pages_shared`` / ``cushion_page_refs`` mirror the allocator after
    every admission/retirement (shared = refcount > 1, i.e. prefix-cache
    donor pages and registry pins; cushion refs = the pool's pinned
    reference + one per live slot mapping the shared cushion block).
    ``prefix_hits`` / ``prefix_misses`` count prefix-cache lookups at
    admission, and ``positions_exhausted`` counts requests rejected because
    prompt+budget exceeds the pool's position capacity (the admission-time
    check that replaces silently running out of positions mid-decode)."""
    n_slots: int = 0
    steps: int = 0              # lock-step decode iterations
    live_slot_steps: int = 0    # sum over steps of live slots that step
    admitted: int = 0           # requests prefilled into a slot
    finished: int = 0           # requests retired (EOS or budget)
    recycles: int = 0           # admissions into a previously-used slot
    canceled: int = 0           # live slots freed without a result
    interrupted: bool = False   # run ended by graceful drain
    weight_bytes_fp: int = 0    # resident fp param bytes (engine load)
    weight_bytes_int8: int = 0  # resident int8 (prequantized) param bytes
    weight_bytes_int4: int = 0  # resident int4-packed param bytes (W4A8)
    pool_bytes: int = 0         # KV pool bytes (pages or dense rows)
    pages_total: int = 0        # page count incl. the reserved scratch page
    pages_free: int = 0         # allocator free-list size
    pages_shared: int = 0       # pages with refcount > 1 (prefix sharing)
    cushion_page_refs: int = 0  # shared cushion block: pool pin + live slots
    prefix_hits: int = 0        # admissions that mapped cached stem pages
    prefix_misses: int = 0      # eligible admissions with no cached stem
    positions_exhausted: int = 0  # requests rejected: prompt+budget > pool
    prefill_chunks: int = 0     # chunked-admission prefill chunks run
    deadline_prefill: int = 0   # streams aborted between chunks (deadline)
    page_table_syncs: int = 0   # host->device page-table mirrors (paged)

    def reset(self) -> None:
        """Zero every per-run counter, keeping ``n_slots``, the resident
        weight bytes and the pool layout facts (``pool_bytes`` /
        ``pages_total``) — load-time configuration. The scheduler calls
        this at the top of each ``run()`` so a stats object shared across
        traces in one process (serve_bench's warm-up pass, repeated bench
        runs) never leaks occupancy counters from the previous run; it
        re-publishes the live allocator gauges right after."""
        self.steps = self.live_slot_steps = 0
        self.admitted = self.finished = self.recycles = self.canceled = 0
        self.interrupted = False
        self.pages_free = self.pages_shared = self.cushion_page_refs = 0
        self.prefix_hits = self.prefix_misses = 0
        self.positions_exhausted = 0
        self.prefill_chunks = self.deadline_prefill = 0
        self.page_table_syncs = 0

    def occupancy(self) -> float:
        return self.live_slot_steps / max(1, self.steps * self.n_slots)

    def as_dict(self) -> dict:
        return {**dataclasses.asdict(self), "occupancy": self.occupancy()}


@dataclasses.dataclass
class RouterStats:
    """Replica-router counters (serving/router.py).

    ``retries`` counts re-enqueues of a request after a failed attempt
    (admission error, replica crash); ``failovers`` counts requests moved
    off a dying replica specifically. ``rejections`` buckets explicit
    backpressure/deadline rejections by reason string. ``queue_depth_peak``
    is the high-water mark of the bounded admission queue — the
    backpressure signal. ``per_replica`` snapshots each replica's
    ``ServeStats`` (and health state) at collection time."""
    n_replicas: int = 0
    submitted: int = 0          # requests accepted into the admission queue
    completed: int = 0          # requests finished with a result
    retries: int = 0            # re-enqueues after a failed attempt
    failovers: int = 0          # live requests moved off a dying replica
    replica_deaths: int = 0     # replicas transitioned to DEAD
    queue_depth_peak: int = 0   # admission-queue high-water mark
    drained: bool = False       # run ended via graceful drain
    rejections: Dict[str, int] = dataclasses.field(default_factory=dict)
    per_replica: List[dict] = dataclasses.field(default_factory=list)

    def reject(self, reason: str) -> None:
        self.rejections[reason] = self.rejections.get(reason, 0) + 1

    @property
    def rejected(self) -> int:
        return sum(self.rejections.values())

    def reset(self) -> None:
        self.submitted = self.completed = 0
        self.retries = self.failovers = self.replica_deaths = 0
        self.queue_depth_peak = 0
        self.drained = False
        self.rejections = {}
        self.per_replica = []

    def as_dict(self) -> dict:
        return {**dataclasses.asdict(self), "rejected": self.rejected}
