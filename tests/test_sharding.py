"""Tensor-parallel sharded-serving parity suite.

The serving stack accepts a ``(data, tp)`` mesh (launch/mesh.py
``make_tp_mesh``): params lay out under the TP-only serve rules, the KV
pool shards along its heads axis (models/*.cache_roles), and prefill +
decode run as sharding-constrained jit. This suite pins the contract:

* tp=1 vs tp=2/4 ``Engine.generate`` is token-for-token identical on
  paper_tiny-scale models for dense / moe / vlm / hybrid, fp and int8 KV,
  with prefill and decode logits allclose;
* the fp cushion/sink block is bit-identical on EVERY shard of the sharded
  pool (KVSink/IntactKV: the protected prefix must survive sharding
  exactly — int8 pools keep it replicated in kc/vc, fp pools re-broadcast
  it into rows [0:m) of each shard);
* a hypothesis property test: per-row ``pos`` decode (continuous batching)
  matches the unsharded path for ragged position vectors under the mesh;
* the ``ContinuousEngine`` pool serves sharded with the same outputs;
* the decode loop keeps its compile-once property under the mesh and the
  pool stays device-resident (one jitted scan; the only host syncs are the
  post-prefill token and the final trajectory pull — nothing per-step);
* ``kernels.ops.decode_attention_tp`` (shard_map'd flash-decode with
  per-shard head slicing) matches the oracle in fp and int8+cushion modes.

Multi-device cases skip unless the process sees enough XLA host devices;
CI runs them with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(see the tier-1 matrix), and locally::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_sharding.py -q
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import QuantConfig, get_config, reduced
from repro.distributed import sharding as SH
from repro.launch.mesh import make_tp_mesh
from repro.models.registry import build
from repro.serving import ContinuousEngine, Engine, Request

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:     # pragma: no cover
    hypothesis = st = None

QN = QuantConfig(mode="none")
NDEV = jax.device_count()

FAMILY_ARCHS = ("paper_tiny", "olmoe-1b-7b", "internvl2-26b",
                "jamba-v0.1-52b")     # dense / moe / vlm / hybrid


def need_devices(n):
    return pytest.mark.skipif(
        NDEV < n,
        reason=f"needs {n} XLA host devices (run with XLA_FLAGS="
               "--xla_force_host_platform_device_count=8)")


@functools.lru_cache(maxsize=None)
def setup(arch):
    cfg = (get_config(arch) if arch == "paper_tiny"
           else reduced(get_config(arch), dtype="float32"))
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    cushion = api.extract_cushion(params, jnp.asarray([1, 2, 3], jnp.int32),
                                  None, QN)
    return api, params, cushion


@functools.lru_cache(maxsize=None)
def engine(arch, kv, tp):
    """tp=0 -> no mesh (the unsharded reference); tp>=1 -> (1, tp) mesh."""
    api, params, cushion = setup(arch)
    return Engine(api, params, QN, cushion=cushion, max_seq=128,
                  kv_dtype=kv, mesh=make_tp_mesh(tp) if tp else None)


def prefill_logits(eng, batch):
    """Prefill logits + cache under the engine's mesh (Engine only exposes
    the sampled token; the parity contract also wants allclose logits)."""
    B = batch["tokens"].shape[0]
    with SH.use_mesh(eng.mesh):
        cache = eng._init_cache(B)
        logits, cache, pos = eng._prefill(eng.params, batch, cache)
        logits = logits[:, -1] if logits.ndim == 3 else logits
    return logits, cache, pos


# ---------------------------------------------------------------------------
# Token-for-token generation parity + logits allclose
# ---------------------------------------------------------------------------

PARITY_CASES = [(a, kv, 2) for a in FAMILY_ARCHS for kv in (None, "int8")] \
    + [("paper_tiny", kv, 4) for kv in (None, "int8")] \
    + [("olmoe-1b-7b", None, 4)]


@pytest.mark.parametrize("arch,kv,tp", PARITY_CASES,
                         ids=[f"{a}-{kv or 'fp'}-tp{t}"
                              for a, kv, t in PARITY_CASES])
def test_tp_generation_parity(arch, kv, tp):
    """tp=N generation is token-for-token identical to tp=1 and the logits
    agree to fp32-reduction tolerance, for every family and KV precision.
    (vlm/hybrid reduced configs have 2 kv heads: at tp=4 the heads axis
    falls back to replicated per the divisibility contract, so tp=4 runs
    cover the dense/moe 4-kv-head configs.)"""
    if NDEV < tp:
        pytest.skip(f"needs {tp} devices")
    api, _, _ = setup(arch)
    ref = engine(arch, kv, 0)
    tpe = engine(arch, kv, tp)
    batch = api.make_batch(jax.random.PRNGKey(7), 2, 24)

    lg_ref, _, _ = prefill_logits(ref, batch)
    lg_tp, _, _ = prefill_logits(tpe, batch)
    np.testing.assert_allclose(np.asarray(lg_tp), np.asarray(lg_ref),
                               rtol=2e-4, atol=2e-4)

    r = ref.generate(batch, 10)
    o = tpe.generate(batch, 10)
    np.testing.assert_array_equal(o.tokens, r.tokens)


# ---------------------------------------------------------------------------
# Cushion-block bit-identity per shard
# ---------------------------------------------------------------------------

@need_devices(2)
@pytest.mark.parametrize("arch", ["paper_tiny", "jamba-v0.1-52b"])
def test_int8_cushion_block_bit_identical_per_shard(arch):
    """int8 pools keep the protected fp cushion block kc/vc REPLICATED:
    every shard holds the full block, bitwise equal to the searched
    artifact (KVSink/IntactKV under sharding)."""
    api, _, cushion = setup(arch)
    eng = engine(arch, "int8", 2)
    batch = api.make_batch(jax.random.PRNGKey(3), 2, 24)
    _, cache, _ = prefill_logits(eng, batch)
    m = eng.prefix_len
    assert m == 3
    for name, src in (("kc", "k"), ("vc", "v")):
        want = np.asarray(cushion["kv"][src], np.float32)
        shards = cache[name].addressable_shards
        assert len(shards) == eng.mesh.size
        for sh in shards:
            got = np.asarray(sh.data, np.float32)
            assert got.shape == want.shape, "cushion block must be replicated"
            np.testing.assert_array_equal(got, want)


@need_devices(2)
def test_fp_cushion_rows_bit_identical_per_shard():
    """fp pools hold the cushion in-cache at rows [0:m): each shard's local
    slice of those rows equals the corresponding head-slice of the
    artifact, bitwise."""
    api, _, cushion = setup("paper_tiny")
    eng = engine("paper_tiny", None, 2)
    batch = api.make_batch(jax.random.PRNGKey(3), 2, 24)
    _, cache, _ = prefill_logits(eng, batch)
    m = eng.prefix_len
    B = batch["tokens"].shape[0]
    for name in ("k", "v"):
        ck = np.asarray(cushion["kv"][name], np.float32)    # (L, m, K, hd)
        full = np.broadcast_to(ck[:, None], (ck.shape[0], B) + ck.shape[1:])
        assert len(cache[name].addressable_shards) == eng.mesh.size
        for sh in cache[name].addressable_shards:
            got = np.asarray(sh.data)[:, :, :m]
            # shard.index slices the global (L, B, Smax, K, hd); apply the
            # same slices to the broadcast cushion, seq axis := rows [0:m)
            idx = (sh.index[0], sh.index[1], slice(None),
                   sh.index[3], sh.index[4])
            np.testing.assert_array_equal(got, full[idx])


# ---------------------------------------------------------------------------
# Per-row pos decode under sharding (continuous-batching property)
# ---------------------------------------------------------------------------

def _per_row_pos_parity(posv, kv_dtype):
    api, params, _ = setup("paper_tiny")
    cfg = api.cfg
    B, Smax, m = 4, 128, 3
    rng = np.random.RandomState(11)
    cache = api.init_cache(B, Smax, kv_dtype=kv_dtype,
                           prefix_len=m if kv_dtype else 0)
    filled = {}
    for key, leaf in cache.items():
        if leaf.dtype == jnp.int8:
            filled[key] = jnp.asarray(
                rng.randint(-127, 128, leaf.shape), jnp.int8)
        elif key in ("k_scale", "v_scale"):
            filled[key] = jnp.asarray(
                rng.rand(*leaf.shape).astype(np.float32) * 0.05 + 0.01)
        else:
            filled[key] = jnp.asarray(
                rng.randn(*leaf.shape).astype(np.float32) * 0.3)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B,)), jnp.int32)
    pos = jnp.asarray(posv, jnp.int32)

    lg_ref, new_ref = jax.jit(
        lambda t, p, c: api.decode_step(params, t, p, c, QN))(
            toks, pos, filled)

    mesh = make_tp_mesh(2)
    sharded = jax.device_put(filled, SH.cache_shardings(
        api.cache_roles(kv_dtype), filled, mesh))
    sp = jax.device_put(params, SH.params_shardings(
        params, mesh, SH.serve_rules()))
    with SH.use_mesh(mesh):
        lg_tp, new_tp = jax.jit(
            lambda t, p, c: api.decode_step(sp, t, p, c, QN))(
                toks, pos, sharded)
    np.testing.assert_allclose(np.asarray(lg_tp), np.asarray(lg_ref),
                               rtol=2e-4, atol=2e-4)
    # the cache write (per-row scatter) lands identically on the shards
    for key in ("k", "v"):
        np.testing.assert_allclose(np.asarray(new_tp[key]),
                                   np.asarray(new_ref[key]),
                                   rtol=2e-4, atol=2e-4)


_POS_CASES = [
    ([3, 40, 127, 5], None),       # ragged mid-decode pool
    ([3, 3, 3, 3], None),          # uniform (static-Engine equivalence)
    ([3, 70, 9, 127], "int8"),     # ragged int8 pool (cushion at [0:3))
]


@need_devices(2)
@pytest.mark.parametrize("posv,kv", _POS_CASES,
                         ids=["fp-ragged", "fp-uniform", "int8-ragged"])
def test_per_row_pos_sharded_cases(posv, kv):
    """Deterministic per-row pos cases (always run, even without
    hypothesis): a lock-step decode over rows at different positions
    produces the same logits and cache writes sharded as unsharded."""
    _per_row_pos_parity(posv, kv)


if hypothesis is not None:
    @need_devices(2)
    @hypothesis.settings(max_examples=8, deadline=None)
    @hypothesis.example(posv=[3, 40, 127, 5], kv_int8=False)
    @hypothesis.example(posv=[3, 70, 9, 127], kv_int8=True)
    @hypothesis.given(
        posv=st.lists(st.integers(3, 127), min_size=4, max_size=4),
        kv_int8=st.booleans())
    def test_per_row_pos_sharded_property(posv, kv_int8):
        """Hypothesis-driven version of the cases above (positions >= m=3:
        the scheduler never decodes below the cushion boundary)."""
        _per_row_pos_parity(posv, "int8" if kv_int8 else None)


# ---------------------------------------------------------------------------
# ContinuousEngine over the mesh
# ---------------------------------------------------------------------------

@need_devices(2)
def test_continuous_engine_tp_parity():
    """The slot-pool scheduler serves sharded with token-for-token the
    outputs of the unsharded pool, the pool resident across devices, and
    the cushion block intact in every recycled slot."""
    api, params, cushion = setup("paper_tiny")
    reqs = [Request(uid=i,
                    batch=api.make_batch(jax.random.PRNGKey(100 + i), 1,
                                         (20, 26)[i % 2]),
                    max_new_tokens=n)
            for i, n in enumerate([5, 3, 6, 4])]
    ref = ContinuousEngine(api, params, QN, n_slots=2, max_seq=128,
                           cushion=cushion).run(reqs)
    ce = ContinuousEngine(api, params, QN, n_slots=2, max_seq=128,
                          cushion=cushion, mesh=make_tp_mesh(2))
    outs = ce.run(reqs)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert ce.stats.recycles >= 1
    assert len(ce.cache["k"].sharding.device_set) == 2
    m = ce.prefix_len
    want = np.asarray(cushion["kv"]["k"], np.float32)
    for s in range(ce.n_slots):
        np.testing.assert_array_equal(
            np.asarray(ce.cache["k"][:, s, :m]), want)


# ---------------------------------------------------------------------------
# Prequantized (int8-resident) serving under the mesh
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _w8a8_setup():
    from repro.core.calibration import calibrate
    api, params, cushion = setup("paper_tiny")
    qw8 = QuantConfig(mode="pt_static", true_int8=True)
    cal = tuple(api.make_batch(jax.random.PRNGKey(100 + i), 2, 32)
                for i in range(2))
    scales, _ = calibrate(api, params, list(cal), qw8, cushion=cushion)
    return api, params, cushion, qw8, scales


@need_devices(2)
def test_tp_prequant_generation_parity():
    """Calibrated pt_static serving with int8-resident weights shards:
    tp=2 generation is token-for-token identical to the unsharded
    prequantized engine AND to the unsharded fp-weight true-int8 path —
    the {w_int, w_scale, colsum} leaves lay out under the serve rules
    (w_int like its fp parent, colsum on the output axis, scales
    replicated) without perturbing a single logit argmax."""
    api, params, cushion, qw8, scales = _w8a8_setup()
    batch = api.make_batch(jax.random.PRNGKey(7), 2, 24)
    ref_fpw = Engine(api, params, qw8, cushion=cushion, scales=scales,
                     max_seq=128)
    ref_pq = Engine(api, params, qw8, cushion=cushion, scales=scales,
                    max_seq=128, prequant=True)
    tp_pq = Engine(api, params, qw8, cushion=cushion, scales=scales,
                   max_seq=128, prequant=True, mesh=make_tp_mesh(2))
    r = ref_pq.generate(batch, 10)
    np.testing.assert_array_equal(r.tokens,
                                  ref_fpw.generate(batch, 10).tokens)
    np.testing.assert_array_equal(tp_pq.generate(batch, 10).tokens,
                                  r.tokens)
    # int8 weights actually sharded: each shard holds half the columns
    w = tp_pq.params["layers"]["attn"]["wqkv"]
    assert w["w_int"].dtype == jnp.int8
    shard = next(iter(w["w_int"].addressable_shards))
    assert shard.data.shape[-1] == w["w_int"].shape[-1] // 2
    cshard = next(iter(w["colsum"].addressable_shards))
    assert cshard.data.shape[-1] == w["colsum"].shape[-1] // 2


@need_devices(2)
def test_tp_continuous_int8_per_slot_scales_parity():
    """The int8 continuous pool (per-slot dequant scales calibrated at each
    admission prefill) serves sharded with the unsharded pool's tokens;
    the per-slot scale leaves shard along heads with batch replicated."""
    api, params, cushion = setup("paper_tiny")
    reqs = [Request(uid=i,
                    batch=api.make_batch(jax.random.PRNGKey(100 + i), 1,
                                         (20, 26)[i % 2]),
                    max_new_tokens=n)
            for i, n in enumerate([5, 3, 6, 4])]
    ref = ContinuousEngine(api, params, QN, n_slots=2, max_seq=128,
                           cushion=cushion, kv_dtype="int8").run(reqs)
    ce = ContinuousEngine(api, params, QN, n_slots=2, max_seq=128,
                          cushion=cushion, kv_dtype="int8",
                          mesh=make_tp_mesh(2))
    outs = ce.run(reqs)
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    assert ce.stats.recycles >= 1
    assert ce.cache["k_scale"].shape == \
        (api.cfg.n_layers, ce.n_slots, api.cfg.n_kv_heads)


def test_prequantized_param_specs_follow_parent_rules():
    """Sharding-rule units for prequantized leaves (single-device: specs
    are computed, not executed): w_int inherits its fp parent's serve
    rules, colsum follows the parent's OUTPUT axis, w_scale replicates."""
    from repro.core import quantization as Q
    from repro.distributed.sharding import params_shardings, serve_rules
    api, params, _ = setup("paper_tiny")
    pq = Q.prequantize_tree(params, QuantConfig(mode="pt_static",
                                                true_int8=True))
    sh = params_shardings(pq, make_tp_mesh(1), serve_rules())
    wqkv = sh["layers"]["attn"]["wqkv"]
    assert wqkv["w_int"].spec == jax.sharding.PartitionSpec(None, None, "tp")
    assert wqkv["colsum"].spec == jax.sharding.PartitionSpec(None, "tp")
    assert wqkv["w_scale"].spec == jax.sharding.PartitionSpec()
    wo = sh["layers"]["attn"]["wo"]
    assert wo["w_int"].spec == jax.sharding.PartitionSpec(None, "tp", None)
    assert wo["colsum"].spec == jax.sharding.PartitionSpec(None, None), \
        "wo's output axis is d_model (unsharded at serve): colsum replicates"


# ---------------------------------------------------------------------------
# Compile-once + device-resident pool under the mesh
# ---------------------------------------------------------------------------

@need_devices(2)
def test_tp_decode_loop_compile_once_and_device_resident():
    """The sharded generation loop keeps PR-1/2's properties: the whole
    decode runs as ONE jitted scan (zero recompiles on a second request of
    the same bucket — so no per-step host round-trip can exist by
    construction), and the KV pool it consumes is a committed multi-device
    array, never pulled to host between steps."""
    from repro.monitoring import count_compiles
    api, _, _ = setup("paper_tiny")
    eng = engine("paper_tiny", None, 2)
    batch = api.make_batch(jax.random.PRNGKey(21), 2, 24)
    eng.generate(batch, 9)      # compile prefill + the 8-step bucket
    tok, pos, cache, _ = eng._run_prefill(batch)
    assert len(cache["k"].sharding.device_set) == 2
    assert len(cache["v"].sharding.device_set) == 2
    with count_compiles() as c:
        out = eng.generate(api.make_batch(jax.random.PRNGKey(22), 2, 24), 9)
    assert c.count == 0, "sharded decode loop must not recompile per request"
    assert out.tokens.shape == (2, 9)


# ---------------------------------------------------------------------------
# shard_map'd flash-decode kernel (per-shard head slicing)
# ---------------------------------------------------------------------------

@need_devices(2)
@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_decode_attention_tp_matches_oracle(quantized):
    """kernels.ops.decode_attention_tp — the shard_map'd split-KV kernel
    with local head slices, sharded int8 scales and the replicated cushion
    block sliced per shard — matches flash_decode_ref row-for-row
    (interpret mode; per-row pos with a retired row included)."""
    from repro.kernels import ref as R
    from repro.kernels.ops import decode_attention_tp

    B, K, G, HD, SMAX, M = 2, 4, 2, 16, 64, 8
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(B, K * G, HD).astype(np.float32))
    pos = jnp.asarray([33, -1], jnp.int32)
    mesh = make_tp_mesh(2)
    if quantized:
        k = jnp.asarray(rs.randint(-127, 128, (B, SMAX, K, HD)), jnp.int8)
        v = jnp.asarray(rs.randint(-127, 128, (B, SMAX, K, HD)), jnp.int8)
        ks = jnp.asarray(rs.rand(K).astype(np.float32) * 0.05 + 0.01)
        vs = jnp.asarray(rs.rand(K).astype(np.float32) * 0.05 + 0.01)
        kc = jnp.asarray(rs.randn(M, K, HD).astype(np.float32))
        vc = jnp.asarray(rs.randn(M, K, HD).astype(np.float32))
        out = decode_attention_tp(q, k, v, pos, mesh, k_scale=ks, v_scale=vs,
                                  kc=kc, vc=vc, interpret=True)
        ref = R.flash_decode_ref(q, k, v, pos, k_scale=ks, v_scale=vs,
                                 kc=kc, vc=vc)
    else:
        k = jnp.asarray(rs.randn(B, SMAX, K, HD).astype(np.float32))
        v = jnp.asarray(rs.randn(B, SMAX, K, HD).astype(np.float32))
        out = decode_attention_tp(q, k, v, pos, mesh, interpret=True)
        ref = R.flash_decode_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@need_devices(2)
@pytest.mark.parametrize("kv", [None, "int8"], ids=["fp", "int8"])
def test_model_decode_routes_through_tp_kernel(monkeypatch, kv):
    """Model-level routing: with the Pallas kernel forced (interpret mode)
    under a tp mesh, ``attention_decode_kv`` takes the shard_map'd
    per-shard-heads path (paper_tiny: 4 kv heads % tp=2 == 0) and produces
    the jnp fallback's logits."""
    import repro.flags as F
    api, params, cushion = setup("paper_tiny")
    eng = Engine(api, params, QN, cushion=cushion, max_seq=128,
                 kv_dtype=kv, mesh=make_tp_mesh(2))
    batch = api.make_batch(jax.random.PRNGKey(13), 2, 24)
    tok, pos, cache, _ = eng._run_prefill(batch)
    with SH.use_mesh(eng.mesh):
        lg_jnp, _ = jax.jit(lambda t, p, c: api.decode_step(
            eng.params, t, p, c, QN))(tok, pos, cache)
        monkeypatch.setattr(F, "DECODE_KERNEL", "pallas")
        lg_tp, _ = jax.jit(lambda t, p, c: api.decode_step(
            eng.params, t, p, c, QN))(tok, pos, cache)
    np.testing.assert_allclose(np.asarray(lg_tp), np.asarray(lg_jnp),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Single-device contract pieces (always run in plain tier-1)
# ---------------------------------------------------------------------------

def test_trivial_tp1_mesh_matches_no_mesh():
    """A (1, 1) tp mesh exercises the whole sharded code path (device_put
    with NamedShardings, use_mesh tracing, cache_shardings) and must be a
    bit-exact no-op vs the mesh-free engine."""
    api, params, cushion = setup("paper_tiny")
    batch = api.make_batch(jax.random.PRNGKey(9), 2, 24)
    ref = engine("paper_tiny", None, 0).generate(batch, 8)
    out = engine("paper_tiny", None, 1).generate(batch, 8)
    np.testing.assert_array_equal(out.tokens, ref.tokens)


def test_tp_role_resolution_and_cache_shardings():
    """"M" resolves to the tp axis on serving meshes and to model on
    training meshes; cache_shardings lays every pool leaf out per the
    family template with indivisible axes dropped to replicated."""
    from jax.sharding import Mesh, PartitionSpec as P
    tp_mesh = make_tp_mesh(1)
    assert SH.to_pspec(("M",), tp_mesh) == P("tp")
    assert SH.to_pspec(("B",), tp_mesh) == P("data")
    train_mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                      ("data", "model"))
    assert SH.to_pspec(("M",), train_mesh) == P("model")

    api, _, _ = setup("paper_tiny")
    cache = api.init_cache(2, 128, kv_dtype="int8", prefix_len=3)
    sh = SH.cache_shardings(api.cache_roles("int8"), cache, tp_mesh)
    assert set(sh) == set(cache)
    assert sh["k"].spec == P(None, "data", None, "tp", None)
    # cushion block replicated (no mesh axis anywhere in its spec)
    assert not any(ax is not None for ax in sh["kc"].spec)
    assert sh["k_scale"].spec == P(None, "tp")

    # indivisible dims fall back to replicated instead of GSPMD padding
    assert SH.roles_pspec(("M",), (7,), tp_mesh) == P("tp")   # 7 % 1 == 0
    assert SH.roles_pspec((None, "M"), (4, 6), tp_mesh) == P(None, "tp")


@need_devices(2)
def test_roles_pspec_drops_indivisible_axes():
    api, _, _ = setup("paper_tiny")
    from jax.sharding import PartitionSpec as P
    mesh = make_tp_mesh(2)
    assert SH.roles_pspec(("M",), (8,), mesh) == P("tp")
    assert SH.roles_pspec(("M",), (7,), mesh) == P(None)
    # vlm/hybrid smoke configs: 2 kv heads over tp=2 shard; over tp=4 they
    # would be dropped (covered implicitly by the tp=4 parity cases)
    assert SH.roles_pspec((None, "B", None, "M"), (4, 2, 64, 2), mesh) \
        == P(None, "data", None, "tp")


def test_cache_roles_uniform_across_families():
    """Every family answers ModelAPI.cache_roles (uniform kv_dtype kwarg —
    regression: xlstm/encdec used to TypeError), and cache_shardings lays
    out nested state trees (xlstm) and untemplated leaves without error."""
    from jax.sharding import NamedSharding
    mesh = make_tp_mesh(1)
    for arch in ("xlstm-350m", "whisper-base", "jamba-v0.1-52b"):
        api = build(reduced(get_config(arch), dtype="float32"))
        roles = api.cache_roles()
        assert isinstance(roles, dict) and roles
        cache = jax.eval_shape(lambda a=api: a.init_cache(2, 64))
        sh = SH.cache_shardings(roles, cache, mesh)
        flat_c = jax.tree_util.tree_leaves(cache)
        flat_s = jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: isinstance(x, NamedSharding))
        assert len(flat_s) == len(flat_c)
        assert all(isinstance(s, NamedSharding) for s in flat_s)
    # roles template missing entries entirely -> everything replicated
    sh = SH.cache_shardings({}, {"a": jax.ShapeDtypeStruct((4, 4), jnp.float32)},
                            mesh)
    assert not any(ax is not None for ax in sh["a"].spec)


def test_make_tp_mesh_validates_device_count():
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_tp_mesh(NDEV + 1)


# ---------------------------------------------------------------------------
# Pallas matmul kernels under a tp mesh (shard_mapped per shard)
# ---------------------------------------------------------------------------

@need_devices(4)
@pytest.mark.parametrize("row_parallel", [False, True], ids=["col", "row"])
@pytest.mark.parametrize("bits", [8, 4])
def test_int_kernels_tp4_match_unsharded(monkeypatch, bits, row_parallel):
    """The int matmul kernels shard_map over tp=4 (the compiler cannot
    partition a Pallas call): column-parallel shards keep their output
    columns, row-parallel shards psum partials. W8A8 psums raw int32 and is
    exact; W4A8 psums f32 partials and matches to f32 rounding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import flags
    from repro.core import quantization as Q
    monkeypatch.setattr(flags, "W8A8_KERNEL", "pallas")
    monkeypatch.setattr(flags, "W4A8_KERNEL", "pallas")
    qcfg = QuantConfig(mode="pt_static", true_int8=True)
    site = Q.SiteScale(scale=jnp.float32(0.05), zero=jnp.float32(7.0))
    rs = np.random.RandomState(bits + row_parallel)
    x = jnp.asarray(rs.randn(8, 512).astype(np.float32))
    pq = Q.prequantize(jnp.asarray(rs.randn(512, 384).astype(np.float32)
                                   * 0.05), qcfg, weight_bits=bits)
    ref = Q.qdot(x, pq, qcfg, site)
    mesh = make_tp_mesh(4)
    key = "w_int" if bits == 8 else "w_packed"
    pq[key] = jax.device_put(pq[key], NamedSharding(
        mesh, P("tp", None) if row_parallel else P(None, "tp")))
    x = jax.device_put(x, NamedSharding(
        mesh, P(None, "tp") if row_parallel else P()))

    def f(x, pq):
        with SH.use_mesh(mesh):
            return Q.qdot(x, pq, qcfg, site, row_parallel=row_parallel)
    out = jax.jit(f)(x, pq)
    if bits == 8 or not row_parallel:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)
