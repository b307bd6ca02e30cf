"""Host spans (``monitoring.span``) and the serving engine's use of them:

* the recorder: nesting and parent ids, request uids, threads, the ring's
  bound, self-time in ``span_summary``, the ``<caller>.wait`` span that
  ``host_sync`` opens, and generation-2 collections as ``python.gc``;
* a tiny ``ContinuousEngine``, paged and dense: each ``serve.step`` holds
  one ``serve.step.wait``, each admitted ``serve.admit`` one
  ``serve.admit.wait`` and its request's uid, every child lies inside its
  parent, and ``count_host_syncs`` counts one sync per step and per
  admission; a read of ``eng.tok`` right after ``step()`` reuses the
  step's host copy;
* the clock: under a profiler trace each ``serve.*`` record keeps one
  offset to its ``TraceAnnotation`` in the ``.xplane.pb``;
* the ``[serve] spans:`` line of the serve launcher.
"""
import gc
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import monitoring as MON
from repro.configs import QuantConfig, get_config
from repro.models.registry import build
from repro.serving import ContinuousEngine, Request
from repro.serving import scheduler as SCHED

QN = QuantConfig(mode="none")


# ---------------------------------------------------------------------------
# The recorder
# ---------------------------------------------------------------------------

def test_recorder_nesting_parents_and_uid():
    rec = MON.SpanRecorder()
    with rec.span("a", uid=3) as a:
        with rec.span("a.b") as b:
            assert rec.current() == "a.b"
        with rec.span("a.c"):
            pass
    assert rec.current() is None
    got = {s.name: s for s in rec.spans()}
    assert got["a"].span_id == a and got["a.b"].span_id == b
    assert got["a"].parent_id is None
    assert got["a.b"].parent_id == a and got["a.c"].parent_id == a
    assert got["a"].uid == 3 and got["a.b"].uid is None
    assert (got["a"].start_ns <= got["a.b"].start_ns <= got["a.b"].end_ns
            <= got["a.c"].start_ns <= got["a.c"].end_ns <= got["a"].end_ns)


def test_recorder_closes_a_span_that_raises():
    rec = MON.SpanRecorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise ValueError("x")
    assert rec.current() is None
    assert [s.name for s in rec.spans()] == ["inner", "outer"]


def test_spans_of_two_threads_never_nest():
    rec = MON.SpanRecorder()
    box = []

    def other():
        with rec.span("t2"):
            pass
        box.append(True)

    with rec.span("t1"):
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=30)
    assert not th.is_alive() and box
    assert all(s.parent_id is None for s in rec.spans())


def test_ring_keeps_the_newest_spans():
    rec = MON.SpanRecorder(maxlen=4)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    assert [s.name for s in rec.spans()] == ["s6", "s7", "s8", "s9"]
    assert MON.RECORDER._ring.maxlen == MON.SPAN_RING == 1 << 16


def test_span_summary_counts_self_time():
    rec = MON.SpanRecorder()
    # (name, start, end, id, parent, uid): two roots of 10 and 20 ms whose
    # children take 4 and 15 ms of them
    rec._ring.extend([
        ("c", 1_000_000, 5_000_000, 2, 1, None),
        ("r", 0, 10_000_000, 1, None, None),
        ("c", 20_000_000, 35_000_000, 4, 3, None),
        ("r", 20_000_000, 40_000_000, 3, None, None)])
    got = rec.summary()
    assert got["r"]["count"] == 2 and got["c"]["count"] == 2
    assert got["r"]["p50_ms"] == pytest.approx(5.5)     # of 6 and 5
    assert got["c"]["p50_ms"] == pytest.approx(9.5)     # of 4 and 15
    assert got["c"]["p99_ms"] == pytest.approx(4 + 0.99 * 11)


def test_host_sync_opens_the_callers_wait_span():
    x = jnp.arange(4)
    with MON.span("phase") as pid:
        np.testing.assert_array_equal(MON.host_sync(x), np.arange(4))
    MON.host_sync(x)
    last = MON.spans()[-3:]
    assert [s.name for s in last] == ["phase.wait", "phase", "host_sync"]
    assert last[0].parent_id == pid and last[2].parent_id is None


def test_generation_two_collections_are_spans():
    gc.disable()        # only the collections made here
    try:
        before = {s.span_id for s in MON.spans()}
        gc.collect(0)
        gc.collect(1)
        assert not [s for s in MON.spans() if s.span_id not in before]
        with MON.span("phase") as pid:
            gc.collect()
        new = [s for s in MON.spans() if s.span_id not in before]
    finally:
        gc.enable()
    assert [s.name for s in new] == [MON.GC_SPAN, "phase"]
    assert new[0].parent_id == pid
    assert new[1].start_ns <= new[0].start_ns <= new[0].end_ns \
        <= new[1].end_ns


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    api = build(get_config("paper_tiny"))
    params = api.init_params(jax.random.PRNGKey(0))
    cushion = api.extract_cushion(params, jnp.asarray([1, 2, 3], jnp.int32),
                                  None, QN)
    return api, params, cushion


def _req(api, uid, n=4):
    return Request(uid=uid,
                   batch=api.make_batch(jax.random.PRNGKey(uid), 1, 20),
                   max_new_tokens=n)


def _new_spans(mark):
    return [s for s in MON.spans() if s.span_id > mark]


def _mark():
    return max((s.span_id for s in MON.spans()), default=0)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_engine_spans_and_syncs(tiny, monkeypatch, paged):
    """Paged with room for one request's page: the second admission is
    refused by the page pool (a ``serve.admit`` with no wait); dense with
    one slot: it is refused before any span. Both admit it once the first
    request retires."""
    api, params, cushion = tiny
    kw = dict(paged=True, page_size=32, n_pages=2) if paged else {}
    ce = ContinuousEngine(api, params, QN, n_slots=2 if paged else 1,
                          max_seq=128, cushion=cushion, **kw)
    reads = []

    def recording_sync(tree):
        reads.append(MON.host_sync(tree))
        return reads[-1]

    monkeypatch.setattr(SCHED, "host_sync", recording_sync)
    mark = _mark()
    with MON.count_host_syncs() as syncs:
        assert ce.try_admit(_req(api, 10))
        assert not ce.try_admit(_req(api, 11))
        steps = 0
        while ce.live_count:
            ce.step()
            steps += 1
            # a caller's read right after step() is the step's own copy
            assert np.shares_memory(np.asarray(ce.tok), reads[-1])
        assert ce.try_admit(_req(api, 11))
        while ce.live_count:
            ce.step()
            steps += 1
    assert syncs.count == steps + 2

    new = _new_spans(mark)
    by_id = {s.span_id: s for s in new}
    kids = {}
    for s in new:
        if s.parent_id is not None:
            parent = by_id[s.parent_id]
            assert parent.start_ns <= s.start_ns <= s.end_ns \
                <= parent.end_ns, (s, parent)
            kids.setdefault(s.parent_id, []).append(s.name)
    step_roots = [s for s in new if s.name == "serve.step"]
    assert len(step_roots) == steps
    want = (["serve.step.pages"] if paged else []) + [
        "serve.step.wait", "serve.step.retire"]
    for r in step_roots:
        assert r.parent_id is None
        assert [k for k in kids[r.span_id] if k != MON.GC_SPAN] == want
    admits = [s for s in new if s.name == "serve.admit"]
    assert [a.uid for a in admits] == ([10, 11, 11] if paged else [10, 11])
    waited = [a for a in admits if "serve.admit.wait" in kids[a.span_id]]
    assert [a.uid for a in waited] == [10, 11]
    for a in waited:
        assert [k for k in kids[a.span_id] if k != MON.GC_SPAN] == [
            "serve.admit.alloc", "serve.admit.wait", "serve.admit.book"]
    if paged:
        assert [k for k in kids[admits[1].span_id]
                if k != MON.GC_SPAN] == ["serve.admit.alloc"]


def test_span_clock_matches_the_profiler_trace(tiny, tmp_path):
    """Every ``serve.*`` record sits at one offset from its twin in the
    profiler's trace, to within 200 us, at both edges."""
    from jax.profiler import ProfileData
    api, params, cushion = tiny
    ce = ContinuousEngine(api, params, QN, n_slots=2, max_seq=128,
                          cushion=cushion, paged=True, page_size=32)
    assert ce.try_admit(_req(api, 0))           # compile outside the trace
    ce.step()
    ce.start()
    mark = _mark()
    gc.disable()        # a collection at a span's edge is not the clock
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for uid in range(3):
                while not ce.try_admit(_req(api, uid, n=3)):
                    ce.step()
            while ce.live_count:
                ce.step()
        finally:
            jax.profiler.stop_trace()
    finally:
        gc.enable()
    recs = [s for s in _new_spans(mark) if s.name.startswith("serve.")]
    assert {s.name for s in recs} >= {"serve.step", "serve.step.wait",
                                      "serve.admit", "serve.admit.wait"}
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    starts, ends = [], []
    for name in {s.name for s in recs}:
        mine = sorted((s.start_ns, s.end_ns) for s in recs
                      if s.name == name)
        theirs = sorted(events.get(name, []))
        assert len(mine) == len(theirs), name
        starts += [t[0] - m[0] for m, t in zip(mine, theirs)]
        ends += [t[1] - m[1] for m, t in zip(mine, theirs)]
    spread = max(starts + ends) - min(starts + ends)
    assert spread <= 200_000, spread


def test_serve_launcher_prints_the_span_line(capsys):
    from repro.launch import serve as serve_mod
    serve_mod.main(["--arch", "paper_tiny", "--smoke", "--mode",
                    "continuous", "--paged", "--page-size", "32",
                    "--tokens", "4", "--prompt-len", "16",
                    "--n-requests", "3", "--rate", "1000"])
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[serve] spans:")]
    assert len(lines) == 1
    for name in ("serve.step ", "serve.step.wait ", "serve.admit ",
                 "serve.admit.wait "):
        assert name in lines[0]
