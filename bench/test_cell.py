"""A whole run at a tiny size on the CPU: the window's invariants, the
control, and ``correct`` coming out false with the timed path broken.

The tiny configuration (``testdata/configs/tiny-dense.json``) has the
served models' layout at d=128; its widest-gap limit, 0.15 logits, lies
between the readings of its sound runs (0.040-0.069 over seeds 1-6) and of
its W4A8 control (0.355-0.500), both measured here on the CPU.

``testdata/configs/tiny-untied.json`` brings its layout as a file that only
the tests know (``testdata/layouts/folded_decoder.py``): an LM head of its
own and head_dim 64 at d=128 over 4 heads. Its limit, 0.7 logits, lies
between its sound runs (0.185-0.371 over seeds 1-12) and its W4A8 control
(1.273-2.662 over the same seeds), measured here on the CPU; served while
its reference reads an RMSNorm eps of 1e-3 (the embeddings' mean square is
4e-4), it read 3.98-4.26 over seeds 1-3.
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


def execute(seed, fault=None, workload="tiny.mix",
            config_dir=os.path.join(TD, "configs")):
    code, res = R.execute(SPEC, workload, seed, SECONDS, False,
                          require_chip=False, fault=fault,
                          config_dir=config_dir,
                          traffic_dir=os.path.join(TD, "traffic"))
    assert code == 0
    return res


@pytest.fixture
def testdata_layouts(monkeypatch):
    """Layouts are looked for in testdata/layouts too."""
    monkeypatch.setattr(M, "LAYOUT_DIRS",
                        M.LAYOUT_DIRS + (os.path.join(TD, "layouts"),))


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


def test_layout_in_new_files_is_correct(testdata_layouts):
    res = execute(3, workload="tiny-untied.mix")
    assert res["correct"] is True
    assert res["failed"] == 0


def test_ignored_norm_eps_is_not_correct(testdata_layouts, tmp_path,
                                         monkeypatch):
    """The configuration states an RMSNorm eps of 1e-3 and the program is
    built with 1e-6: the reference reads the configuration's."""
    cfg = M.load_config("tiny-untied", os.path.join(TD, "configs"))
    cfg["rms_norm_eps"] = 1e-3
    (tmp_path / "tiny-untied.json").write_text(json.dumps(cfg))
    layout = M.load_layout(cfg)
    monkeypatch.setattr(M, "program_config", lambda c: layout.program_config(
        dict(c, rms_norm_eps=1e-6)))
    res = execute(3, workload="tiny-untied.mix", config_dir=str(tmp_path))
    assert res["correct"] is False
    check = res["checks"]["widest_gap"]
    assert check["value"] > check["limit"]


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-untied"])
def test_control_fails_the_limit(name, testdata_layouts):
    cfg = M.load_config(name, os.path.join(TD, "configs"))
    mix = TF.load_mix("tiny-mix", os.path.join(TD, "traffic"))
    o = CL.run(cfg, mix, 4, SECONDS, control=C.w4a8_control)
    limit = cfg["correct"]["widest_gap_limit"]
    assert R.verdict(o.check["widest_gap"], limit) is True
    assert R.verdict(o.check["control_gap"], limit) is False
