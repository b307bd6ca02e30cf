"""The OLMoE configuration in the harness: its layout's shapes and the costs
against counts made by hand, a tiny sparse-expert configuration served and
judged on the CPU, the layout refusing a program that cannot state the
block, and the grouped kernel's roofline readers on a recorded trace.

``testdata/configs/tiny-moe.json`` has OLMoE's layout at d=128 (16 experts
of width 64, top-8, raw softmax weights, QK-norm, untied head). Its limit,
0.55 logits, lies between its sound runs (0.160-0.358 over seeds 1-12) and
its W4A8 control (1.123-2.279 over the same seeds), measured here on the
CPU; served by a program built without QK-norm it read 0.688-0.883 over
seeds 1-3.
"""
import dataclasses
import json
import os

import pytest

import cell as CL
import control as C
import costs
import devtrace as TR
import model as M
import moe_costs
import progspans
import run as R
import test_progspans as TP
import traffic as TF

TD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
OLMOE = M.load_config("olmoe-1b-7b.w8a8")
TINY = M.load_config("tiny-moe", os.path.join(TD, "configs"))
SPEC = {"workloads": [{"name": "tiny-moe.mix", "config": "tiny-moe",
                       "traffic": "tiny-mix", "chips": 1}],
        "end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "w8a8_gmm_decode_roofline", "unit": "%"}]}
SECONDS = 1.5
# OLMoE's widths: hidden, expert width, heads, head size, experts, top-k,
# vocabulary; the cell's 6 layers, 16 slots and 16-position cushion
D, FF, H, HD, E, TOP, V = 2048, 1024, 16, 128, 64, 8, 50304
L, B, M16 = 6, 16, 16


def test_layout_shapes_by_hand():
    qkv, o = (D, 3 * H * HD), (H * HD, D)
    assert costs.linear_shapes(OLMOE) == [qkv, o]
    shapes = M.load_layout(OLMOE).matmul_shapes(OLMOE)
    assert shapes == [qkv, o, (D, E)] + TOP * [(D, FF), (D, FF), (FF, D)]
    per_layer = D * 3 * H * HD + H * HD * D + D * E + TOP * 3 * D * FF
    assert costs.linear_params(OLMOE) == L * per_layer + D * V == 506462208


def test_costs_by_hand_at_the_cells_sizes():
    calls = costs.w8a8_step_calls(OLMOE, B)
    assert len(calls) == 2 * L + 1
    assert sum(op for op, _ in calls) == (
        L * (2 * B * D * 3 * H * HD + 2 * B * H * HD * D) + 2 * B * D * V)
    w8a8_bytes = lambda k, n: B * k + k * n + 4 * B * n + 4 * n
    assert sum(b for _, b in calls) == (
        L * (w8a8_bytes(D, 3 * H * HD) + w8a8_bytes(H * HD, D))
        + w8a8_bytes(D, V))
    ctx = 16 * 1700
    assert costs.flash_decode_step(OLMOE, B, ctx) == (
        L * 4 * H * HD * ctx, L * (2 * H * HD * 2 * ctx + 2 * B * H * HD * 2))
    active = L * (D * 3 * H * HD + H * HD * D + D * E + TOP * 3 * D * FF)
    assert costs.decode_flops(OLMOE, B, ctx) == (
        2 * (active + D * V) * B + 4 * L * H * HD * ctx)
    for T in TF.prompt_levels(TF.load_mix("azure-code-2023")):
        keys = T * M16 + T * (T + 1) / 2
        assert costs.prefill_flops(OLMOE, T, M16) == (
            2 * active * T + 4 * L * H * HD * keys + 2 * D * V)


@pytest.mark.parametrize("rows,experts", [(L * B * TOP, 336),
                                          (L * 3232 * TOP, L * E)],
                         ids=["decode_step", "longest_prompt"])
def test_grouped_kernel_costs_by_hand(rows, experts):
    """Three calls a layer: up and gate read int8 rows of width D and write
    float32 rows of width FF; down reads int8 rows of width FF and writes
    float32 rows of width D; each reached expert's three int8 matrices with
    an int32 column sum and a float32 scale per output column."""
    ops, nbytes = moe_costs.gmm_layer(D, FF, rows, experts)
    assert ops == 2 * rows * (D * FF + D * FF + FF * D)
    assert nbytes == (experts * (3 * D * FF + 8 * (FF + FF + D))
                      + rows * (2 * (D + 4 * FF) + FF + 4 * D))


def execute(seed, spec=SPEC):
    code, res = R.execute(spec, "tiny-moe.mix", seed, SECONDS, False,
                          require_chip=False,
                          config_dir=os.path.join(TD, "configs"),
                          traffic_dir=os.path.join(TD, "traffic"))
    assert code == 0
    return res


def test_tiny_moe_is_served_correct(capsys):
    res = execute(3)
    window = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert window["window"]["compiles_in_window"] == 0
    assert window["window"]["tokens_compared"] > 0
    assert res["correct"] is True
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}


def test_tiny_moe_without_qk_norm_is_not_correct(monkeypatch):
    """The program built without the RMSNorm over q and k, the reference
    as the configuration states the block."""
    layout = M.load_layout(TINY)
    monkeypatch.setattr(M, "program_config", lambda c: dataclasses.replace(
        layout.program_config(c), qk_norm=False))
    res = execute(3)
    assert res["correct"] is False
    check = res["checks"]["widest_gap"]
    assert check["value"] > check["limit"]


def test_tiny_moe_control_fails_the_limit():
    mix = TF.load_mix("tiny-mix", os.path.join(TD, "traffic"))
    o = CL.run(TINY, mix, 4, SECONDS, control=C.w4a8_control)
    limit = TINY["correct"]["widest_gap_limit"]
    assert R.verdict(o.check["widest_gap"], limit) is True
    assert R.verdict(o.check["control_gap"], limit) is False


@pytest.mark.parametrize("cls,field", [("ModelConfig", "norm_eps"),
                                       ("ModelConfig", "qk_norm"),
                                       ("MoEConfig", "norm_topk_prob")])
def test_layout_refuses_a_program_without_the_block(monkeypatch, cls,
                                                    field):
    """A program whose configuration cannot state QK-norm, the eps or the
    raw top-k weights (an older program) is refused before anything is
    built."""
    from repro.configs import base
    orig = getattr(base, cls)
    kept = [(f.name, f.type, f) for f in dataclasses.fields(orig)
            if f.name != field]
    monkeypatch.setattr(base, cls, dataclasses.make_dataclass(cls, kept))
    with pytest.raises(ValueError, match=field):
        M.program_config(OLMOE)


# -- the grouped kernel's roofline readers, on the recorded v5e trace --------

PEAKS = {"int8_ops": 393e12, "hbm_bytes_s": 819e9, "bf16_flops": 197e12}


def counted_ctx(values_by_root, program, gmm_ns, monkeypatch):
    """The recorded two-step trace with ``w8a8_gmm`` ops of ``gmm_ns`` each
    added to ``program``, the made-up span ring of ``test_progspans``, and
    the program's counters under the window's roots of ``values_by_root``
    (span name -> list of value dicts, in window order)."""
    from repro import monitoring
    with open(os.path.join(TD, "trace_two_steps.json")) as f:
        trace = TR.Trace.from_json(json.load(f))
    w0 = trace.window[0]
    trace.ops += [TR.Op(f"w8a8_gmm.{i} f32[128,1024]", program,
                        w0 + 1000 * i, ns) for i, ns in enumerate(gmm_ns)]
    recs = TP.records()
    monkeypatch.setattr(progspans, "program_spans", lambda: recs)
    a = progspans.align(trace, recs)
    counts = []
    for span_name, values in values_by_root.items():
        name = moe_costs.COUNTS[program][1]
        for r, v in zip(progspans.roots(a, span_name), values):
            counts.append(monitoring.Count(name, r.span_id, v))
    monkeypatch.setattr(monitoring, "counts", lambda: counts)
    return R.Ctx(window=None, trace=trace, cfg=OLMOE, peaks=PEAKS,
                 n_slots=B, cushion_len=M16, phases={})


def least_s(rows, experts):
    ops, nbytes = moe_costs.gmm_layer(D, FF, rows, experts)
    return max(ops / PEAKS["int8_ops"], nbytes / PEAKS["hbm_bytes_s"])


def test_decode_roofline_reads_the_steps_counters(monkeypatch):
    steps = [{"rows": L * B * TOP, "experts": e, "dropped": 0}
             for e in (330, 336, 341)]
    ctx = counted_ctx({progspans.STEP: steps}, "jit_step",
                      [3_000_000, 3_200_000, 2_900_000], monkeypatch)
    want = 100 * sum(least_s(s["rows"], s["experts"]) for s in steps) \
        / 9.1e-3
    got = R.reader("w8a8_gmm_decode_roofline")(ctx)
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_prefill_roofline_reads_the_admissions_counters(monkeypatch):
    admits = [{"rows": L * T * TOP, "experts": L * E, "dropped": 0}
              for T in (1504, 704)]
    ctx = counted_ctx({progspans.ADMIT: admits}, "jit_prefill",
                      [20_000_000, 9_000_000], monkeypatch)
    want = 100 * sum(least_s(a["rows"], a["experts"]) for a in admits) \
        / 29e-3
    assert R.reader("w8a8_gmm_prefill_roofline")(ctx) == pytest.approx(want)


def test_roofline_readers_report_nothing_without_counters(monkeypatch):
    """A program that keeps no counters (an older one) or spans that do
    not align: nothing, and nothing raised."""
    from repro import monitoring
    ctx = counted_ctx({}, "jit_step", [3_000_000], monkeypatch)
    for name in ("w8a8_gmm_decode_roofline", "w8a8_gmm_prefill_roofline"):
        assert R.reader(name)(ctx) is None
    monkeypatch.delattr(monitoring, "counts")
    assert R.reader("w8a8_gmm_decode_roofline")(ctx) is None
    monkeypatch.setattr(progspans, "program_spans", lambda: None)
    assert R.reader("w8a8_gmm_prefill_roofline")(ctx) is None
