"""The trace-to-metrics reduction, on two decode steps recorded on a TPU
v5e (``testdata/trace_two_steps.json``: the Qwen1.5-0.5B decode cell's
trace, cut to two ``jit_step`` executions and their longer ops)."""
import json
import os

import pytest

import costs
import devtrace as TR
import model as M
import run as R

TD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_s": 819e9}


@pytest.fixture(scope="module")
def rows():
    with open(os.path.join(TD, "trace_two_steps.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def trace(rows):
    return TR.Trace.from_json(rows)


def test_kernel_seconds_sum_the_kernels_own_calls(rows, trace):
    want = sum(r[3] for r in rows["ops"]
               if r[0].startswith("w8a8_matmul.") and r[1] == "jit_step")
    assert TR.kernel_seconds(trace, "w8a8_matmul", "jit_step") == \
        pytest.approx(want * 1e-9)
    # an op that only consumes the kernel's result is not the kernel
    assert TR.op_base("bitcast_add_fusion.6 bf16[32,1,3072]") != \
        "w8a8_matmul"
    assert TR.module_count(trace, "jit_step") == 2


def test_busy_is_the_union_of_op_intervals(rows, trace):
    w0, w1 = rows["window"]
    iv = sorted((max(r[2], w0), min(r[2] + r[3], w1)) for r in rows["ops"])
    busy, end = 0.0, w0
    for a, b in iv:
        a = max(a, end)
        if b > a:
            busy += b - a
            end = b
    assert TR.busy_s(trace) == pytest.approx(busy * 1e-9)
    assert 0 < TR.busy_s(trace) <= trace.window_s
    gaps = TR.idle_gaps(trace)
    assert sum(g for _, g in gaps) == pytest.approx(
        trace.window_s - TR.busy_s(trace))
    assert gaps[0][0] == "bench.step"


def test_ranking_leaves_out_containers(trace):
    top = TR.top_ops(trace, 10)
    assert len(top) <= 10
    assert not any(name.split("/")[1].startswith("while")
                   for name, _ in top)
    assert top == sorted(top, key=lambda kv: -kv[1])


def test_readers_found_by_name(trace):
    cfg = M.load_config("qwen1.5-0.5b.w8a8")

    class W:
        steps = 2
        live_slot_steps = 64
        decode_tokens = 64
        sum_ctx = 64 * 600
        step_contexts = [(32, 32 * 600), (32, 32 * 600)]
        prompts = []
        seconds = trace.window_s
        admit_s = 0.0

    ctx = R.Ctx(window=W, trace=trace, cfg=cfg, peaks=PEAKS, n_slots=32,
                cushion_len=16, phases={})
    got = {n: R.reader(n)(ctx) for n in (
        "w8a8_matmul_roofline", "flash_decode_paged_roofline",
        "idle_share", "decode_mfu", "occupancy", "prefill_mfu")}
    per_step = sum(costs.bound_s(o, b, PEAKS["int8_ops"],
                                 PEAKS["hbm_bytes_s"])
                   for o, b in costs.w8a8_step_calls(cfg, 32))
    staged = TR.staged_weight_seconds(trace, "jit_step", "s8")
    assert staged > 0
    assert got["w8a8_matmul_roofline"] == pytest.approx(
        100 * 2 * per_step / (staged + TR.kernel_seconds(
            trace, "w8a8_matmul", "jit_step")))
    for name in ("w8a8_matmul_roofline", "flash_decode_paged_roofline",
                 "idle_share", "decode_mfu"):
        assert 0 < got[name] < 100, name
    assert got["occupancy"] == 100.0
    assert got["prefill_mfu"] is None       # no admission in these steps
