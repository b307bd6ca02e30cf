"""One benchmark cell: set-up, the measured window, and the check.

Set-up draws the weights, the cushion prefix and the calibration batches
from the seed on the device, builds the program's ``ContinuousEngine``
(paged bf16 KV pool, blocking admission, W8A8 weights under ``pt_static``
scales calibrated with the cushion, no prefix cache), warms every shape the
traffic uses (the decode step, the admission scatter, and the admission
prefill at each prompt length of the mix) and fills the pool with every
client's first request.

The window then drives ``try_admit`` and ``step`` from closed-loop clients
with zero think time: a client whose request retires submits its next one
at once. Each call sits in a ``bench.*`` host span. The window closes at
the end of the first step that ends ``seconds`` after it opened; every
token emitted inside it counts. Afterwards the engine's buffers are freed
and the served tokens are compared with the plain reference (``check.py``).
"""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import check as CK
import devtrace as TR
import model as M
import traffic as TF

# the correctness sample: at least this many served tokens and requests,
# at most this many requests (the longest request is always in it); eight
# requests leave a fault that breaks half of the slots unseen with a
# chance of 1 in 256
SAMPLE_TOKENS = 384
SAMPLE_MIN_REQUESTS = 8
SAMPLE_MAX_REQUESTS = 16


class HarnessError(RuntimeError):
    """The harness saw something that makes the run unmeasurable."""


@dataclasses.dataclass
class Rec:
    """One request as the harness saw it."""
    uid: int
    client: int
    prompt: np.ndarray
    t_submit: Optional[float]       # None: submitted by the fill
    slot: int = -1
    t_first: float = 0.0
    first_ref: object = None        # the engine's token vector after admit
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    done: bool = False

    def served(self) -> np.ndarray:
        first = int(np.asarray(self.first_ref)[self.slot])
        return np.asarray([first] + self.tokens, np.int32)


@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    steps: int = 0                  # ServeStats deltas over the window
    live_slot_steps: int = 0
    decode_tokens: int = 0          # tokens the window's steps emitted
    sum_ctx: int = 0                # contexts of those decode tokens
    step_contexts: List[tuple] = dataclasses.field(default_factory=list)
    prompts: List[int] = dataclasses.field(default_factory=list)
    admit_s: float = 0.0
    itl_ms: List[float] = dataclasses.field(default_factory=list)
    ttft_ms: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0
    backpressure: int = 0
    admissions: int = 0             # each gives its first token inside

    @property
    def tokens(self) -> int:
        return self.decode_tokens + self.admissions


def pool_pages(cfg: dict, mix: TF.Mix) -> tuple:
    """(positions, pages): the longest request's positions (cushion
    included) and a pool that holds every client's longest request plus
    the scratch page."""
    sv = cfg["serving"]
    positions = int(sv["cushion_len"]) + mix.max_prompt + mix.max_output
    per = -(-positions // int(sv["page_size"]))
    return positions, mix.clients * per + 1


def build_engine(cfg: dict, mix: TF.Mix, seed: int, phases: dict):
    """The program's engine for this cell, on seeded weights."""
    from repro.configs.base import QuantConfig
    from repro.models import registry
    from repro.serving.scheduler import ContinuousEngine

    sv = cfg["serving"]
    positions, n_pages = pool_pages(cfg, mix)
    api = registry.build(M.program_config(cfg))

    t = time.perf_counter()
    params = jax.block_until_ready(M.make_weights(cfg, seed))
    phases["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    extract = jax.jit(lambda p, toks: api.extract_cushion(
        p, toks, None, QuantConfig()))
    cushion = jax.block_until_ready(
        extract(params, jnp.asarray(M.cushion_tokens(cfg, seed))))
    phases["cushion_s"] = time.perf_counter() - t

    t = time.perf_counter()
    eng = ContinuousEngine(
        api, params, QuantConfig(mode="pt_static", true_int8=True),
        n_slots=mix.clients, max_seq=positions, cushion=cushion,
        calib_batches=M.calibration_tokens(cfg, seed), prequant=True,
        weight_bits=int(sv["weight_bits"]), paged=True,
        page_size=int(sv["page_size"]), n_pages=n_pages,
        prefix_cache=bool(sv["prefix_cache"]), chunk_tokens=None)
    jax.block_until_ready(eng.cache)
    phases["engine_s"] = time.perf_counter() - t
    return eng


def warm(eng, mix: TF.Mix, vocab: int) -> None:
    """Compile every program the window runs: one admission at each prompt
    length of the mix, then decode steps until they retire. The pool is
    left empty, its pages all free."""
    from repro.serving.scheduler import Request
    rng = np.random.default_rng(0)
    for j, T in enumerate(TF.prompt_levels(mix)):
        toks = rng.integers(0, vocab, size=(1, T), dtype=np.int32)
        if not eng.try_admit(Request(uid=-1 - j, batch={"tokens": toks},
                                     max_new_tokens=2)):
            raise HarnessError("warm-up admission refused")
    while eng.live_count:
        eng.step()
    eng.pop_finished()


class Clients:
    """Closed-loop clients over one engine; records every token."""

    def __init__(self, eng, stream: TF.RequestStream, n_clients: int,
                 cushion_len: int):
        self.eng = eng
        self.stream = stream
        self.n = n_clients
        self.m = cushion_len
        self.recs: Dict[int, Rec] = {}
        self.by_slot: Dict[int, Rec] = {}
        self.waiting: List[tuple] = []      # (t_submit, client, draw)
        self.admit_spans: List[tuple] = []
        self.backpressure = 0

    def submit(self, client: int, t_submit: Optional[float]) -> None:
        self.waiting.append((t_submit, client, self.stream.next()))

    def admit_waiting(self) -> None:
        from repro.serving.scheduler import Request
        still = []
        for t_submit, client, d in self.waiting:
            free = set(self.eng.free_slots())
            t0 = time.perf_counter()
            with TraceAnnotation("bench.admit"):
                ok = self.eng.try_admit(Request(
                    uid=d.index, batch={"tokens": d.prompt[None]},
                    max_new_tokens=d.max_new_tokens))
            t1 = time.perf_counter()
            self.admit_spans.append((t0, t1, len(d.prompt), ok))
            if not ok:
                self.backpressure += 1
                still.append((t_submit, client, d))
                continue
            (slot,) = free - set(self.eng.free_slots())
            rec = Rec(uid=d.index, client=client, prompt=d.prompt,
                      t_submit=t_submit,
                      slot=int(slot), t_first=t1, first_ref=self.eng.tok)
            self.recs[rec.uid] = rec
            self.by_slot[rec.slot] = rec
        self.waiting = still

    def step(self) -> tuple:
        """One decode step; returns (live slots, their summed contexts)."""
        with TraceAnnotation("bench.step"):
            retired = self.eng.step()
        t = time.perf_counter()
        toks = np.asarray(self.eng.tok)
        n, ctx = len(self.by_slot), 0
        for slot, rec in self.by_slot.items():
            ctx += self.m + len(rec.prompt) + 1 + len(rec.tokens)
            rec.tokens.append(int(toks[slot]))
            rec.times.append(t)
        for uid in retired:
            rec = self.recs[uid]
            rec.done = True
            del self.by_slot[rec.slot]
            self.submit(rec.client, t)
        return n, ctx, t


def serve_window(clients: Clients, seconds: float, profile_dir=None):
    """Fill the pool, then run the closed loop for ``seconds``. Returns
    (Window, t_open)."""
    from repro.monitoring import count_compiles
    eng = clients.eng
    for c in range(clients.n):
        clients.submit(c, None)
    clients.admit_waiting()
    if eng.live_count != clients.n:
        raise HarnessError(f"fill admitted {eng.live_count} of "
                           f"{clients.n} clients")
    if profile_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
    st = eng.stats
    s0, l0 = st.steps, st.live_slot_steps
    n_spans = len(clients.admit_spans)
    w = Window()
    with count_compiles() as cc, TraceAnnotation(TR.WINDOW_SPAN):
        t_open = time.perf_counter()
        t = t_open
        while t - t_open < seconds:
            clients.admit_waiting()
            n, ctx, t = clients.step()
            if eng.live_count != len(clients.by_slot):
                raise HarnessError("engine and harness disagree on the "
                                   "live slots")
            w.step_contexts.append((n, ctx))
            w.decode_tokens += n
            w.sum_ctx += ctx
    t_close = t
    if profile_dir is not None:
        jax.profiler.stop_trace()
    w.seconds = t_close - t_open
    w.steps = st.steps - s0
    w.live_slot_steps = st.live_slot_steps - l0
    w.compiles = cc.count
    w.backpressure = clients.backpressure
    for t0, t1, T, ok in clients.admit_spans[n_spans:]:
        w.admit_s += t1 - t0
        if ok:
            w.prompts.append(T)
    for rec in clients.recs.values():
        if rec.t_submit is not None:
            w.admissions += 1
            w.ttft_ms.append((rec.t_first - rec.t_submit) * 1e3)
        times = [rec.t_first] + rec.times
        w.itl_ms.extend((b - a) * 1e3 for a, b in zip(times, times[1:])
                        if a >= t_open and b <= t_close)
    return w, t_open


def served_outputs(clients: Clients) -> List[CK.Served]:
    """Every request's served tokens, checked against the engine's own
    record of the requests it finished."""
    finished = {o.uid: np.asarray(o.tokens) for o in
                clients.eng.pop_finished()}
    out = []
    for rec in clients.recs.values():
        toks = rec.served()
        if rec.done and not np.array_equal(finished.get(rec.uid), toks):
            raise HarnessError(f"request {rec.uid}: tokens recorded by the "
                               f"harness differ from the engine's result")
        out.append(CK.Served(uid=rec.uid, prompt=rec.prompt, tokens=toks))
    return out


def free_engine(eng) -> None:
    """Release the engine's device buffers before the reference runs."""
    for leaf in jax.tree_util.tree_leaves((eng.cache, eng.params,
                                           eng.cushion_block)):
        if isinstance(leaf, jax.Array):
            leaf.delete()


@dataclasses.dataclass
class Outcome:
    window: Window
    served: List[CK.Served]
    check: dict
    memory_peak_bytes: Optional[int]
    trace: Optional[TR.Trace]
    phases: dict
    n_slots: int
    attempted: int
    failed: int


def run(cfg: dict, mix: TF.Mix, seed: int, seconds: float,
        profile: bool = False, fault=None, t_start: Optional[float] = None,
        control=None) -> Outcome:
    """Set up, serve the window, check. ``fault`` (tests only) is called
    with the engine before the warm-up to break the timed path.
    ``control(cfg, mix, seed)`` (the control study only) builds the
    lower-precision path read at the same positions."""
    t_start = time.perf_counter() if t_start is None else t_start
    phases: dict = {}
    eng = build_engine(cfg, mix, seed, phases)
    n = M.dims(cfg)
    if fault is not None:
        fault(eng)
    t = time.perf_counter()
    warm(eng, mix, n["V"])
    phases["warm_s"] = time.perf_counter() - t
    stream = TF.RequestStream(mix, seed, n["V"])
    clients = Clients(eng, stream, mix.clients,
                      int(cfg["serving"]["cushion_len"]))
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if profile else None
    try:
        w, t_open = serve_window(clients, seconds, tmp)
        phases["fill_s"] = t_open - t - phases["warm_s"]
        phases["setup_s"] = t_open - t_start
        t = time.perf_counter()
        trace = TR.load(tmp) if profile else None
        phases["trace_read_s"] = time.perf_counter() - t
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    served = served_outputs(clients)
    positions, _ = pool_pages(cfg, mix)
    free_engine(eng)
    del eng, clients.eng
    picked = CK.sample(served, seed, SAMPLE_TOKENS, SAMPLE_MIN_REQUESTS,
                       SAMPLE_MAX_REQUESTS)
    cushion_toks = M.cushion_tokens(cfg, seed)
    t = time.perf_counter()
    ctl = control(cfg, mix, seed) if control is not None else None
    check = (CK.reference_gaps(cfg, seed, picked, cushion_toks,
                               _ref_length(positions),
                               ref_rows(mix), control=ctl)
             if picked else {"widest_gap": float("nan"),
                             "tokens_compared": 0, "requests_compared": 0})
    phases["check_s"] = time.perf_counter() - t
    attempted = len(served) + len(clients.waiting)
    return Outcome(window=w, served=served, check=check,
                   memory_peak_bytes=peak, trace=trace, phases=phases,
                   n_slots=mix.clients, attempted=attempted,
                   failed=w.backpressure)


def _ref_length(positions: int) -> int:
    return -(-positions // 128) * 128


def ref_rows(mix: TF.Mix) -> int:
    """Rows of one reference call: the longest answer, so one compiled
    program serves every request of the cell."""
    return -(-mix.max_output // 64) * 64
