"""A whole run at a tiny size on the CPU: the window's invariants, the
control, and ``correct`` coming out false with the timed path broken.

The tiny configuration (``testdata/configs/tiny-dense.json``) has the
served models' layout at d=128; its widest-gap limit, 0.15 logits, lies
between the readings of its sound runs (0.040-0.069 over seeds 1-6) and of
its W4A8 control (0.355-0.500), both measured here on the CPU.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import cell as CL
import control as C
import model as M
import run as R
import traffic as TF

TD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
with open(os.path.join(TD, "bench.json")) as f:
    SPEC = json.load(f)
SECONDS = 1.5


def execute(seed, fault=None):
    code, res = R.execute(SPEC, "tiny.mix", seed, SECONDS, False,
                          require_chip=False, fault=fault,
                          config_dir=os.path.join(TD, "configs"),
                          traffic_dir=os.path.join(TD, "traffic"))
    assert code == 0
    return res


def test_sound_run_is_correct(capsys):
    res = execute(3)
    out = capsys.readouterr()
    window = json.loads(out.out.strip().splitlines()[-1])["window"]
    assert window["compiles_in_window"] == 0
    assert window["occupancy_pct"] == 100.0
    assert window["backpressure"] == 0
    assert window["tokens_compared"] > 0
    assert res["correct"] is True
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms",
                                   "ttft_p90_ms", "setup_s"}
    assert out.err.strip().splitlines()[-1].startswith("check widest_gap")
    assert list(res)[-1] == "checks"


def _wrap_step(eng, broken):
    """Break the engine's jitted decode step: ``broken`` sees the step's
    inputs and outputs and returns what the engine gets instead. The step
    donates its cache, so it is given a copy and the input stays valid."""
    orig = eng._step

    def step(p, tok, pos, live, cache, cu):
        nxt, new_pos, new_cache = orig(p, tok, pos, live,
                                       jax.tree.map(jnp.copy, cache), cu)
        return broken(tok, live, nxt, new_pos, cache, new_cache)

    eng._step = step


def token_altered(eng):
    V = eng.api.cfg.vocab_size
    _wrap_step(eng, lambda tok, live, nxt, pos, old, new: (
        jnp.where(live, (nxt + 1) % V, nxt), pos, new))


def state_unchanged(eng):
    _wrap_step(eng, lambda tok, live, nxt, pos, old, new: (nxt, pos, old))


def half_the_batch_left_out(eng):
    def broken(tok, live, nxt, pos, old, new):
        h = nxt.shape[0] // 2
        return jnp.concatenate([nxt[:h], tok[h:]]), pos, new
    _wrap_step(eng, broken)


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_the_batch_left_out],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(fault):
    res = execute(3, fault)
    assert res["correct"] is False
    check = res["checks"]["widest_gap"]
    assert check["value"] > check["limit"]


def test_control_fails_the_limit():
    cfg = M.load_config("tiny-dense", os.path.join(TD, "configs"))
    mix = TF.load_mix("tiny-mix", os.path.join(TD, "traffic"))
    o = CL.run(cfg, mix, 4, SECONDS, control=C.w4a8_control)
    limit = cfg["correct"]["widest_gap_limit"]
    assert R.verdict(o.check["widest_gap"], limit) is True
    assert R.verdict(o.check["control_gap"], limit) is False
