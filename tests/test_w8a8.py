"""Calibrated W8A8 serving-path contract:

* three-way matmul parity — the Pallas kernel pipeline (``qdot_pallas``),
  the int8-resident serving dot (``prequantized_int_dot``) and the pure-jnp
  oracle (``w8a8_matmul_ref``) agree on ragged token counts and asymmetric
  activation zero-points;
* the ``REPRO_W8A8_KERNEL`` routing flag: Pallas-forced (interpret-mode)
  execution of ``true_int_dot``/``prequantized_int_dot`` matches the
  lax.dot_general path, including under jit (the decode-scan context);
* ``prequantize_tree`` converts exactly the qdot-consumed weights and the
  MoE expert stacks across families (hybrid's list-nested period params
  included; the router and embeddings stay fp);
* the engines' load-time quantization plan: pt_static with neither scales
  nor calibration data refuses to run (the placeholder-scales silent-garbage
  guard), engine-side calibration equals precomputed-scales serving, and
  prequantized (int8-resident) generation is token-for-token identical to
  the fp-weight true-int8 path for dense / moe / vlm / hybrid;
* ``monitoring.resident_weight_bytes`` accounting for the fp-vs-int8 A/B.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.flags as flags
from repro.configs import QuantConfig, get_config, reduced
from repro.core import quantization as Q
from repro.kernels import ref as R
from repro.kernels.ops import qdot_pallas
from repro.kernels.w8a8_matmul import w8a8_matmul
from repro.models.registry import build
from repro.serving import ContinuousEngine, Engine

QW8 = QuantConfig(mode="pt_static", true_int8=True)


def _site_for(x):
    scale, zero = Q.params_from_minmax(jnp.min(x), jnp.max(x), 8, False)
    return Q.SiteScale(scale=scale, zero=zero)


# ---------------------------------------------------------------------------
# Kernel-level parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M", [37, 128, 300])
def test_qdot_pallas_prequantized_ref_three_way_parity(M):
    """qdot_pallas == prequantized_int_dot == w8a8_matmul_ref on ragged M
    with an asymmetric activation zero-point (the deployment configuration:
    asymmetric per-tensor-static activations, symmetric per-tensor
    weights)."""
    rng = np.random.RandomState(M)
    x = jnp.asarray(rng.randn(M, 256).astype(np.float32) * 2 + 0.7)
    w = jnp.asarray(rng.randn(256, 128).astype(np.float32) * 0.1)
    site = _site_for(x)
    assert float(site.zero) != 0.0, "case must exercise the zero-point"

    a = qdot_pallas(x, w, QW8, site)                    # Pallas pipeline
    pq = Q.prequantize(w, QW8)
    b = Q.qdot(x, pq, QW8, site)                        # int8-resident dot

    # oracle: quantize activations exactly as the serving path stores them
    # (int8 offset by -128), then the ref matmul with the shifted zero
    xq = Q.quantize(x, site.scale, site.zero, 8, False) - 128
    wq, s_w = Q.weight_quant_int(w, QW8)
    c = R.w8a8_matmul_ref(xq.astype(jnp.int8), wq,
                          jnp.asarray(site.scale, jnp.float32),
                          jnp.asarray(site.zero - 128.0, jnp.float32),
                          jnp.asarray(s_w, jnp.float32))
    np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(b), np.asarray(c),
                               rtol=1e-5, atol=1e-4)


def test_w8a8_matmul_precomputed_colsum_identical():
    """The stored-colsum fast path (prequantized serving) is bit-identical
    to the kernel's own reduction."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(-127, 128, (64, 256)), jnp.int8)
    w = jnp.asarray(rng.randint(-127, 128, (256, 128)), jnp.int8)
    colsum = jnp.sum(w.astype(jnp.int32), axis=0)
    a = w8a8_matmul(x, w, 0.01, -3.0, 0.02, interpret=True)
    b = w8a8_matmul(x, w, 0.01, -3.0, 0.02, colsum=colsum, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("prequantized", [False, True],
                         ids=["true_int_dot", "prequantized"])
def test_w8a8_kernel_routing_flag(monkeypatch, prequantized):
    """REPRO_W8A8_KERNEL=pallas routes the serving int8 dots through the
    Pallas kernel (interpret mode off-TPU) with the same numbers as the
    lax.dot_general path — outside AND inside jit (the decode scan traces
    qdot under jit, so the routing must hold there too)."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(3, 19, 256).astype(np.float32))
    w = jnp.asarray(rng.randn(256, 128).astype(np.float32) * 0.1)
    site = _site_for(x)
    warg = Q.prequantize(w, QW8) if prequantized else w

    monkeypatch.setattr(flags, "W8A8_KERNEL", "jnp")
    ref = Q.qdot(x, warg, QW8, site)
    monkeypatch.setattr(flags, "W8A8_KERNEL", "pallas")
    out = Q.qdot(x, warg, QW8, site)
    jit_out = jax.jit(lambda x: Q.qdot(x, warg, QW8, site))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(jit_out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# prequantize_tree coverage
# ---------------------------------------------------------------------------

def test_prequantize_tree_hybrid_descends_period_lists():
    """Hybrid period params nest sublayers in lists: attention / mamba /
    dense-mlp weights inside them convert to int8-resident dicts, MoE
    sublayers' expert stacks to their grouped int8 form (the router stays
    fp); embeddings stay fp."""
    cfg = reduced(get_config("jamba-v0.1-52b"), dtype="float32")
    api = build(cfg)
    p = api.init_params(jax.random.PRNGKey(0))
    pq = Q.prequantize_tree(p, QW8)
    subs = pq["layers"]["sub"]
    kinds = {}
    for sub in subs:
        for mixer in ("attn", "mamba", "mlp", "moe"):
            if mixer in sub:
                kinds[mixer] = sub[mixer]
    assert pq["layers"]["sub"] is not p["layers"]["sub"]
    assert "w_int" in kinds["attn"]["wqkv"]
    assert kinds["attn"]["wqkv"]["w_int"].dtype == jnp.int8
    assert "w_int" in kinds["mamba"]["w_in"]
    assert not isinstance(kinds["mamba"]["w_x"], dict)   # raw einsum: fp
    assert "w_int" in kinds["mlp"]["w_down"]
    experts = kinds["moe"]["w_up"]                       # (P, E, D, F)
    assert experts["w_int"].dtype == jnp.int8
    assert experts["w_scale"].shape == experts["colsum"].shape \
        == experts["w_int"].shape[:2] + experts["w_int"].shape[-1:]
    assert not isinstance(kinds["moe"]["router"], dict)  # router: fp
    assert not isinstance(pq["embed"]["w"], dict)
    # stacked-over-periods leaves quantize per period slice
    P = kinds["attn"]["wqkv"]["w_int"].shape[0]
    assert kinds["attn"]["wqkv"]["w_scale"].shape == (P,)
    assert kinds["attn"]["wqkv"]["colsum"].shape == \
        (P, kinds["attn"]["wqkv"]["w_int"].shape[-1])


# ---------------------------------------------------------------------------
# Placeholder-scales guard (silent-garbage prevention)
# ---------------------------------------------------------------------------

def test_pt_static_forward_without_scales_raises():
    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    batch = api.make_batch(jax.random.PRNGKey(1), 2, 16)
    with pytest.raises(ValueError, match="calibrated scales"):
        api.forward(params, batch, QuantConfig(mode="pt_static"))
    # dynamic modes still run on placeholders (values unused)
    api.forward(params, batch, QuantConfig(mode="pt_dynamic"))


def test_pt_static_engines_without_scales_raise():
    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="calib"):
        Engine(api, params, QW8, max_seq=128)
    with pytest.raises(ValueError, match="calib"):
        ContinuousEngine(api, params, QW8, n_slots=1, max_seq=128)
    with pytest.raises(ValueError, match="pt_static"):
        Engine(api, params, QuantConfig(mode="none"), max_seq=128,
               prequant=True)


def test_prequantized_int_dot_requires_static_site():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 64).astype(np.float32))
    w = Q.prequantize(jnp.asarray(rng.randn(64, 32).astype(np.float32)), QW8)
    with pytest.raises(ValueError, match="pt_static"):
        Q.prequantized_int_dot(x, w, QuantConfig(mode="pt_dynamic"), None)
    with pytest.raises(ValueError, match="site"):
        Q.prequantized_int_dot(x, w, QW8, None)


# ---------------------------------------------------------------------------
# End-to-end: load-time plan + generation parity across families
# ---------------------------------------------------------------------------

def _arch_setup(arch):
    cfg = (get_config(arch) if arch == "paper_tiny"
           else reduced(get_config(arch), dtype="float32"))
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    cal = [api.make_batch(jax.random.PRNGKey(100 + i), 2, 32)
           for i in range(2)]
    batch = api.make_batch(jax.random.PRNGKey(7), 2, 24)
    return api, params, cal, batch


@pytest.mark.parametrize("arch", ["paper_tiny", "olmoe-1b-7b",
                                  "internvl2-26b", "jamba-v0.1-52b"])
def test_prequant_generation_parity(arch):
    """int8-resident (prequantized) serving generates token-for-token what
    the fp-weight true-int8 pt_static path generates — same int math, only
    the weight residency differs — for dense / moe / vlm / hybrid, with the
    engine calibrating its own scales at load."""
    api, params, cal, batch = _arch_setup(arch)
    e_fpw = Engine(api, params, QW8, max_seq=128, calib_batches=cal)
    e_pq = Engine(api, params, QW8, max_seq=128, calib_batches=cal,
                  prequant=True)
    r_fpw = e_fpw.generate(batch, 8)
    r_pq = e_pq.generate(batch, 8)
    np.testing.assert_array_equal(r_pq.tokens, r_fpw.tokens)
    assert e_pq.weight_bytes_int8 > 0
    assert e_fpw.weight_bytes_int8 == 0
    # int8 residency strictly shrinks the fp footprint it replaces
    assert e_pq.weight_bytes_fp < e_fpw.weight_bytes_fp


def test_engine_load_time_calibration_matches_precomputed():
    """Engine(calib_batches=...) reproduces Engine(scales=calibrate(...))
    exactly — the load-time plan is the same calibration, just owned by
    the engine."""
    from repro.core.calibration import calibrate
    api, params, cal, batch = _arch_setup("paper_tiny")
    scales, _ = calibrate(api, params, cal, QW8)
    r_pre = Engine(api, params, QW8, max_seq=128,
                   scales=scales).generate(batch, 8)
    r_load = Engine(api, params, QW8, max_seq=128,
                    calib_batches=cal).generate(batch, 8)
    np.testing.assert_array_equal(r_load.tokens, r_pre.tokens)


def test_resident_weight_bytes_accounting():
    from repro.monitoring import resident_weight_bytes
    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    fp0, i80, i40 = resident_weight_bytes(params)
    assert i80 == 0 and i40 == 0 and fp0 > 0
    pq = Q.prequantize_tree(params, QW8)
    fp1, i81, i41 = resident_weight_bytes(pq)
    assert i81 > 0 and i41 == 0
    # every int8 byte replaced >= 1 byte of fp storage (fp32/bf16 params)
    assert fp0 - fp1 >= i81
