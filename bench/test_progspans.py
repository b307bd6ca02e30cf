"""The readers of the program's own spans, on the recorded two-step v5e
trace (``testdata/trace_two_steps.json``: three ``bench.step`` spans, the
window from 1473568664 to 1753288888 ns) with span records made up here on
a program clock ``D`` ns behind the trace's."""
import json
import os

import pytest

import devtrace as TR
import progspans
import run as R

TD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
D = 1_234_567_890
READERS = ("step_host_ms", "admit_host_ms", "host_idle_share")

# the trace's bench.step spans, (start, end) on the trace clock
HARNESS = [(1333296761, 1473786946), (1473925376, 1614461341),
           (1614569592, 1755263507)]


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(TD, "trace_two_steps.json")) as f:
        return TR.Trace.from_json(json.load(f))


def records(drop_window_step=None, shift=None):
    """Span records as the program's ring holds them, on its own clock.

    Two warm-up steps and one warm-up admission lie far before the window.
    Each window step starts 2 us after its ``bench.step`` and ends 2 us
    before it; its ``.wait`` leaves host time of 1.3 ms, 2.5 ms and 1.7 ms.
    Admissions in the window leave 0.06 ms and 0.02 ms."""
    out, ids = [], iter(range(1, 1000))

    def rec(name, s, e, parent=None, uid=None):
        sid = next(ids)
        out.append((name, int(s) - D, int(e) - D, sid, parent, uid))
        return sid

    for k in range(2):                          # warm-up steps
        t = 1_000_000_000 + k * 10_000_000
        sid = rec("serve.step", t, t + 5_000_000)
        rec("serve.step.wait", t + 1_000_000, t + 4_000_000, sid)
    sid = rec("serve.admit", 1_100_000_000, 1_110_000_000, uid=-1)
    rec("serve.admit.wait", 1_101_000_000, 1_102_000_000, sid)
    # (host before the wait, host after it) of each window step
    host = [(1_000_000, 300_000), (500_000, 2_000_000),
            (1_000_000, 700_000)]
    for i, ((hs, he), (a, b)) in enumerate(zip(HARNESS, host)):
        s, e = hs + 2000, he - 2000
        if shift is not None and shift[0] == i:
            s, e = s + shift[1], e + shift[2]
        if i == drop_window_step:
            continue
        sid = rec("serve.step", s, e)
        rec("serve.step.pages", s + 10, s + 400, sid)
        rec("serve.step.wait", s + a, e - b, sid)
        rec("serve.step.retire", e - b + 10, e - b + 500, sid)
        if i == 2:
            rec("python.gc", s + 500, s + 900, sid)
        if i == 0:                          # admission between steps 0 and 1
            aid = rec("serve.admit", 1473800000, 1473900000, uid=7)
            rec("serve.admit.alloc", 1473800100, 1473801100, aid)
            rec("serve.admit.wait", 1473850000, 1473890000, aid)
        if i == 1:                          # admission between steps 1 and 2
            aid = rec("serve.admit", 1614470000, 1614560000, uid=8)
            rec("serve.admit.wait", 1614480000, 1614550000, aid)
            rec("serve.admit.book", 1614551000, 1614552000, aid)
    return out


def ctx_for(trace):
    return R.Ctx(window=None, trace=trace, cfg={}, peaks=None, n_slots=1,
                 cushion_len=0, phases={})


def read_all(ctx):
    return {n: R.reader(n)(ctx) for n in READERS}


def test_readers_on_the_recorded_trace(trace, monkeypatch):
    monkeypatch.setattr(progspans, "program_spans", lambda: records())
    got = read_all(ctx_for(trace))
    assert got["step_host_ms"] == pytest.approx(1.7)       # of 1.3, 2.5, 1.7
    assert got["admit_host_ms"] == pytest.approx(0.04)     # of 0.06, 0.02
    # host pieces in device-idle time: step 0's tail over the idle gap at
    # the window's start (202809 ns), step 1's tail inside the 2.58 ms gap
    # between the programs (2000000), step 2's head up to the gap's end
    # (149561) and the second admission's two host pieces (2 x 10000)
    idle_ns = 202809 + 2_000_000 + 149561 + 20000
    assert got["host_idle_share"] == pytest.approx(
        100 * idle_ns / (1753288888 - 1473568664))
    idle_share = R.reader("idle_share")(ctx_for(trace))
    assert 0 < got["host_idle_share"] <= idle_share


def test_offset_is_the_clocks_difference(trace):
    a = progspans.align(trace, records())
    assert a.offset_ns == D and a.residual_ns == 2000   # the 2 us insets
    assert {s.uid for s in progspans.roots(a, "serve.admit")} == {7, 8}


def test_none_without_a_trace(monkeypatch):
    monkeypatch.setattr(progspans, "program_spans", lambda: records())
    assert all(v is None for v in read_all(ctx_for(None)).values())


def test_none_from_a_program_that_keeps_no_spans(trace, monkeypatch):
    monkeypatch.setattr(progspans, "program_spans", lambda: None)
    assert all(v is None for v in read_all(ctx_for(trace)).values())


@pytest.mark.parametrize("drop", [0, 2])
def test_none_when_the_step_counts_disagree(trace, monkeypatch, drop):
    """One window step missing: the last three roots then pair a warm-up
    step with a window step, which no offset fits."""
    monkeypatch.setattr(progspans, "program_spans",
                        lambda: records(drop_window_step=drop))
    assert all(v is None for v in read_all(ctx_for(trace)).values())
    # without the warm-up steps the ring holds fewer roots than the window
    # has steps
    fewer = [r for r in records(drop_window_step=drop)
             if r[1] + D > 1_300_000_000]
    assert progspans.align(trace, fewer) is None


def test_one_edge_moved_still_aligns(trace):
    """A 50 ms pause between the harness's span and the program's moves one
    edge of one pair; a pair whose both edges sit 2 ms off does not fit."""
    a = progspans.align(trace, records(shift=(1, 50_000_000, 0)))
    assert a is not None and a.residual_ns == 2000
    assert progspans.align(trace, records(shift=(1, 2_000_000, 2_000_000))) \
        is None


def test_ring_is_read_with_the_collector_held(monkeypatch):
    """The program's gc hook appends to the ring it keeps: a collection
    while the ring is copied would raise "deque mutated during iteration"
    (one traced decode run of 2 on a TPU v5e did). The copy is made with
    the collector off, and the collector is on again afterwards."""
    import gc
    from repro import monitoring

    def read():
        for r in records():
            if gc.isenabled():
                raise RuntimeError("deque mutated during iteration")
            yield r

    monkeypatch.setattr(monitoring, "spans", read)
    assert gc.isenabled()
    assert progspans.program_spans() == records()
    assert gc.isenabled()
