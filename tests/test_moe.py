"""Dropless MoE serving (OLMoE-1B-7B's block) at reduced sizes on the CPU:

* the sorted dropless dispatch matches a per-token float reference, with
  routing skewed so that one expert takes every token and others none;
* the grouped W8A8 kernel (interpret mode) and the model's jnp route match
  their oracle on ragged, empty and full groups, one layer of a stack
  read by index;
* ``prequantize_tree`` leaves the experts int8-resident;
* prefill then paged decode through ``ContinuousEngine`` matches a float
  reference forward of OLMoE's equations (QK-norm, raw top-k weights, the
  configured RMSNorm eps), and misses it when the program skips QK-norm or
  renormalizes the top-k weights;
* RMSNorm honours ``norm_eps``;
* the routing counters: every row routed, none dropped, each call's
  counters under its own ``serve.step`` / ``serve.admit`` span.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import monitoring as MON
from repro.configs import QuantConfig, get_config, reduced
from repro.core import quantization as Q
from repro.kernels.ref import w8a8_gmm_ref
from repro.kernels.w8a8_gmm import w8a8_gmm
from repro.models import common as C
from repro.models import moe as MOE
from repro.models.registry import build
from repro.serving.scheduler import ContinuousEngine, Request

QN = QuantConfig(mode="none")
QW8 = QuantConfig(mode="pt_static", true_int8=True)
# (experts, top-k) of the two reduced sizes
SIZES = [(8, 2), (16, 8)]


def olmoe(experts=8, top_k=2, **kw):
    cfg = reduced(get_config("olmoe-1b-7b"), dtype="float32", **kw)
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=experts, top_k=top_k))


def ffn(p, e, x):
    """One expert's SwiGLU, float64."""
    silu = lambda a: a / (1.0 + np.exp(-a))
    return (silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])) @ p["w_down"][e]


def per_token_moe(p, x, cfg):
    """Each token alone: softmax over the router, top-k, the top-k experts
    weighted by their probabilities (renormalized where the config says)."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    out = np.zeros_like(x)
    for t, xt in enumerate(x):
        logits = xt @ p["router"]
        prob = np.exp(logits - logits.max())
        prob /= prob.sum()
        top = np.argsort(-prob, kind="stable")[:cfg.moe.top_k]
        w = prob[top]
        if cfg.moe.norm_topk_prob:
            w = w / w.sum()
        out[t] = sum(wi * ffn(p, e, xt) for wi, e in zip(w, top))
    return out


@pytest.mark.parametrize("experts,top_k", SIZES)
@pytest.mark.parametrize("norm_topk", [False, True])
def test_dropless_moe_matches_per_token_reference(experts, top_k, norm_topk):
    """Every token reaches expert 0 and none reaches the last two: the
    sorted dispatch routes every row, drops none, and gives each token
    the per-token reference's output."""
    cfg = olmoe(experts, top_k)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, norm_topk_prob=norm_topk))
    p = MOE.moe_init(jax.random.PRNGKey(3), cfg)
    D = cfg.d_model
    router = np.array(p["router"])
    router[:, 0] += 4.0 / np.sqrt(D)
    router[:, -2:] -= 50.0 / np.sqrt(D)
    p["router"] = jnp.asarray(router)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 24, D)).astype(np.float32) + 1.0
    y, _, counts = MOE.apply_moe(p, jnp.asarray(x), cfg, QN, None, None)
    want = per_token_moe(p, x.reshape(-1, D).astype(np.float64), cfg)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, D), want,
                               rtol=1e-4, atol=1e-5)
    rows, reached, dropped = (int(v) for v in counts)
    assert (rows, dropped) == (2 * 24 * top_k, 0)
    assert reached <= experts - 2
    _, top_idx, _ = MOE.route(p, jnp.asarray(x.reshape(-1, D)), cfg)
    top_idx = np.asarray(top_idx)
    assert (top_idx == 0).any(axis=1).all()
    assert not np.isin(top_idx, [experts - 2, experts - 1]).any()


GROUPS = {"ragged": [5, 0, 17, 1, 0, 9, 0, 0],
          "empty_ends": [0, 0, 12, 20, 0, 0, 0, 0],
          "one_full": [0, 0, 0, 0, 0, 32, 0, 0]}


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_w8a8_gmm_matches_oracle(name):
    """Interpret-mode kernel, with a 2-layer stack read at layer 1, and the
    model's jnp route against the oracle; rows past the groups are left
    out of the comparison."""
    gs = jnp.asarray(GROUPS[name], jnp.int32)
    M, K, N = 40, 64, 256
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-128, 128, (M, K)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (2, 8, K, N)), jnp.int8)
    sc = jnp.asarray(rng.uniform(1e-3, 1e-2, (2, 8, N)), jnp.float32)
    cs = jnp.sum(w.astype(jnp.int32), axis=2)
    n = int(gs.sum())
    ref = np.asarray(w8a8_gmm_ref(x, w[1], gs, 0.02, -3.0, sc[1]))[:n]
    out = w8a8_gmm(x, w, gs, 0.02, -3.0, sc, cs, 1, tm=32, tn=128,
                   interpret=True)
    np.testing.assert_allclose(np.asarray(out)[:n], ref, rtol=1e-6,
                               atol=1e-4)
    view = {"w_int": w, "w_scale": sc, "colsum": cs, "layer": jnp.int32(1)}
    jnp_route = Q._int8_gmm(x, view, gs, 0.02, -3.0, jnp.float32)
    np.testing.assert_allclose(np.asarray(jnp_route)[:n], ref, rtol=1e-6,
                               atol=1e-4)


def test_experts_int8_resident_after_prequantize():
    cfg = olmoe()
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    pq = Q.prequantize_tree(params, QW8)
    moe = pq["layers"]["moe"]
    L, E, D, F = cfg.n_layers, cfg.moe.num_experts, cfg.d_model, cfg.d_ff
    for key, (k, n) in (("w_up", (D, F)), ("w_gate", (D, F)),
                        ("w_down", (F, D))):
        assert moe[key]["w_int"].dtype == jnp.int8
        assert moe[key]["w_int"].shape == (L, E, k, n)
        assert moe[key]["w_scale"].shape == (L, E, n)
        assert moe[key]["colsum"].dtype == jnp.int32
    assert moe["router"].dtype == jnp.float32
    _, int8_bytes, _ = MON.resident_weight_bytes(pq)
    assert int8_bytes >= 3 * L * E * D * F


def _tiny_engine_parts(cfg):
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    # gains away from 1 and a projection scale away from unit RMS, so that
    # QK-norm changes the attention
    rng = np.random.default_rng(5)
    attn = dict(params["layers"]["attn"])
    for key in ("q_norm", "k_norm"):
        g = attn[key]["g"]
        attn[key] = {"g": jnp.asarray(rng.uniform(0.3, 3.0, g.shape),
                                      g.dtype)}
    attn["wqkv"] = attn["wqkv"] * 3.0
    params["layers"] = {**params["layers"], "attn": attn}
    return api, params


def reference_logits(params, cfg, tokens):
    """OLMoE's forward in float64 numpy over one whole sequence: RMSNorm
    with the config's eps, RMSNorm over the whole q and k projections,
    rope, causal MHA, per-token top-k experts with raw softmax weights,
    untied head."""
    f = lambda a: np.asarray(a, np.float64)
    eps, H, hd = cfg.norm_eps, cfg.n_heads, cfg.head_dim
    rms = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True)
                                   + eps) * g
    S = len(tokens)
    x = f(params["embed"]["w"])[tokens]
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, hd, 2) / hd)
    ang = np.arange(S)[:, None] * inv[None]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]

    def rope(a):
        a1, a2 = np.split(a, 2, axis=-1)
        return np.concatenate([a1 * cos - a2 * sin, a1 * sin + a2 * cos], -1)

    lay = jax.tree_util.tree_map(f, params["layers"])
    for i in range(cfg.n_layers):
        li = jax.tree_util.tree_map(lambda a: a[i], lay)
        h = rms(x, li["ln1"]["g"])
        qkv = h @ li["attn"]["wqkv"]
        q, k, v = np.split(qkv, [H * hd, 2 * H * hd], axis=-1)
        q = rope(rms(q, li["attn"]["q_norm"]["g"]).reshape(S, H, hd))
        k = rope(rms(k, li["attn"]["k_norm"]["g"]).reshape(S, H, hd))
        v = v.reshape(S, H, hd)
        s = np.einsum("shd,thd->hst", q, k) / np.sqrt(hd)
        s = np.where(np.tril(np.ones((S, S), bool))[None], s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        a /= a.sum(-1, keepdims=True)
        o = np.einsum("hst,thd->shd", a, v).reshape(S, H * hd)
        x = x + o @ li["attn"]["wo"]
        x = x + per_token_moe(li["moe"], rms(x, li["ln2"]["g"]), cfg)
    return rms(x, f(params["ln_f"]["g"])) @ f(params["head"]["w"])


# the CPU program runs float32 with a different summation order from the
# float64 reference: its logits agree to ~1e-5; a skipped QK-norm or
# renormalized top-k weights move them by more than 1e-2
LOGIT_TOL = 1e-3


@pytest.mark.parametrize("program", ["as_configured", "qk_norm_skipped",
                                     "topk_renormalized"])
def test_paged_engine_matches_reference(program):
    """A cushion, a prompt prefilled into a paged pool, then decode steps
    through ContinuousEngine; each step's logits (the decode step over the
    very inputs the engine's step gets) against the reference forward of
    cushion + prompt + served tokens."""
    cfg = olmoe(16, 8, n_layers=2)
    api, params = _tiny_engine_parts(cfg)
    served_cfg = {
        "as_configured": cfg,
        "qk_norm_skipped": dataclasses.replace(cfg, qk_norm=False),
        "topk_renormalized": dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, norm_topk_prob=True)),
    }[program]
    served = build(served_cfg)
    cushion_toks = np.asarray([7, 3, 11, 5], np.int32)
    cushion = served.extract_cushion(params, jnp.asarray(cushion_toks), None,
                                     QN)
    eng = ContinuousEngine(served, params, QN, n_slots=2, max_seq=64,
                           cushion=cushion, paged=True, page_size=16)
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, (1, 12),
                                               dtype=np.int32)
    assert eng.try_admit(Request(uid=0, batch={"tokens": prompt},
                                 max_new_tokens=5))
    step_logits = jax.jit(lambda p, t, pos, c: served.decode_step(
        p, t, pos, c, QN)[0])
    got, fed = [], []
    take_step = eng._step

    def step(p, tok, pos, live, cache, cu):
        fed.append(int(np.asarray(tok)[0]))
        got.append(np.asarray(step_logits(p, tok, pos, {**cache, **cu}))[0])
        return take_step(p, tok, pos, live, cache, cu)

    eng._step = step
    while eng.live_count:
        eng.step()
    seq = np.concatenate([cushion_toks, prompt[0], fed])
    ref = reference_logits(params, cfg, seq)
    first = len(cushion_toks) + prompt.shape[1]
    gap = max(np.abs(g - ref[first + j]).max() for j, g in enumerate(got))
    if program == "as_configured":
        assert gap < LOGIT_TOL
    else:
        assert gap > 10 * LOGIT_TOL


@pytest.mark.parametrize("eps", [1e-5, 1e-2])
def test_rms_norm_eps_matches_numpy(eps):
    cfg = dataclasses.replace(get_config("paper_tiny"), norm_eps=eps,
                              dtype="float32")
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((3, cfg.d_model)) * 0.05).astype(np.float32)
    g = rng.uniform(0.5, 1.5, cfg.d_model).astype(np.float32)
    want = x / np.sqrt(np.mean(x * x, -1, keepdims=True, dtype=np.float32)
                       + np.float32(eps)) * g
    got = C.apply_norm({"g": jnp.asarray(g)}, jnp.asarray(x), cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-7)
    other = C.apply_norm({"g": jnp.asarray(g)}, jnp.asarray(x),
                         dataclasses.replace(cfg, norm_eps=eps * 10))
    assert np.abs(np.asarray(other) - want).max() > 1e-3


def test_routing_counters_under_their_spans():
    cfg = olmoe(8, 2)
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    eng = ContinuousEngine(api, params, QN, n_slots=2, max_seq=64,
                           paged=True, page_size=16)
    first_span = max((s.span_id for s in MON.spans()), default=0)
    lens = [9, 14, 5]
    rng = np.random.default_rng(0)
    eng.run([Request(uid=i, batch={"tokens": rng.integers(
        0, cfg.vocab_size, (1, T), dtype=np.int32)}, max_new_tokens=3)
        for i, T in enumerate(lens)])
    spans = {s.span_id: s for s in MON.spans() if s.span_id > first_span}
    counts = [c for c in MON.counts() if c.span_id in spans]
    K, L = cfg.moe.top_k, cfg.n_layers
    steps = [c for c in counts if c.name == "moe.jit_step"]
    admits = [c for c in counts if c.name == "moe.jit_prefill"]
    assert len(steps) == eng.stats.steps
    assert {spans[c.span_id].name for c in steps} == {"serve.step"}
    assert all(c.values["rows"] == 2 * K * L for c in steps)
    assert sorted(c.values["rows"] for c in admits) == sorted(
        T * K * L for T in lens)
    assert [spans[c.span_id].uid for c in admits] == [0, 1, 2]
    assert {spans[c.span_id].name for c in admits} == {"serve.admit"}
    assert all(c.values["dropped"] == 0 for c in counts)
    assert all(0 < c.values["experts"] <= L * cfg.moe.num_experts
               for c in counts)
    st = eng.stats
    assert st.routed_rows == sum(c.values["rows"] for c in counts)
    assert st.experts_reached == sum(c.values["experts"] for c in counts)
    assert st.dropped_rows == 0
