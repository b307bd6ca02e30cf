"""Substrate tests: data pipeline, checkpoint store, fault-tolerance
supervisor, compressed collectives, smoothquant, partition rules."""
import os

import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:          # optional dev dep: only one test needs it
    hypothesis = st = None

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointManager
from repro.configs import QuantConfig, get_config
from repro.data.pipeline import Pipeline, SyntheticCorpus, calibration_batches
from repro.distributed.collectives import (compressed_psum,
                                           dp_train_step_compressed)
from repro.distributed.fault_tolerance import Supervisor


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_resumable():
    c = SyntheticCorpus(128, seed=3)
    p = Pipeline(c, batch=4, seq_len=32, seed=7)
    b1 = p.get_batch(5)
    b2 = p.get_batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    it = p.iter_from(5)
    b3 = next(it)
    np.testing.assert_array_equal(b1["tokens"], b3["tokens"])


def test_pipeline_host_disjoint():
    c = SyntheticCorpus(128, seed=3)
    a = Pipeline(c, batch=4, seq_len=32, seed=7, host=0, n_hosts=2)
    b = Pipeline(c, batch=4, seq_len=32, seed=7, host=1, n_hosts=2)
    assert not np.array_equal(a.get_batch(0)["tokens"],
                              b.get_batch(0)["tokens"])


def test_labels_are_shifted_tokens():
    c = SyntheticCorpus(128, seed=0)
    p = Pipeline(c, batch=2, seq_len=16, seed=0)
    b = p.get_batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_corpus_is_learnable():
    """Bigram structure: successor entropy must be far below uniform."""
    c = SyntheticCorpus(64, seed=0)
    assert c.successors.shape[1] < 64


def test_corpus_at_published_vocab_has_distinct_successors():
    """A 151936-token vocabulary (Qwen1.5) builds in bulk: every row holds
    distinct in-range successors, and the same seed gives the same rows."""
    a = SyntheticCorpus(151936, seed=4)
    assert a.successors.shape == (151936, a.branching)
    assert 0 <= a.successors.min() and a.successors.max() < 151936
    s = np.sort(a.successors, axis=1)
    assert not (s[:, 1:] == s[:, :-1]).any()
    np.testing.assert_array_equal(a.successors[:64],
                                  SyntheticCorpus(151936, seed=4)
                                  .successors[:64])


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    cm.save(10, tree, extra={"note": "x"})
    out = cm.restore(10, like=tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert out["b"]["c"].dtype == np.asarray(tree["b"]["c"]).dtype


def test_checkpoint_keep_k_gc(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.zeros((2,))}
    for s in [1, 2, 3, 4]:
        cm.save(s, tree)
    assert cm.steps() == [3, 4]


def test_checkpoint_integrity_detection(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=3)
    tree = {"a": jnp.zeros((128,))}
    path = cm.save(5, tree)
    with open(os.path.join(path, "arrays.npz"), "r+b") as f:
        f.seek(100)
        f.write(b"\x00corrupt\x00")
    with pytest.raises(IOError):
        cm.restore(5, like=tree)


def test_checkpoint_reshard_on_restore(tmp_path):
    """Elastic restore: device_put with new shardings (1-device here, but
    exercises the reshard path)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cm = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    cm.save(1, tree)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    sh = {"w": NamedSharding(mesh, P())}
    out = cm.restore(1, like=tree, shardings=sh)
    assert out["w"].sharding == sh["w"]


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_supervisor_restores_after_failure(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=3)
    state0 = {"x": jnp.zeros(())}
    calls = {"n": 0}

    failed = {"done": False}

    def do_step(state, step):
        calls["n"] += 1
        if step == 7 and not failed["done"]:   # fail once at step 7
            failed["done"] = True
            raise RuntimeError("injected node failure")
        return {"x": state["x"] + 1}, {"loss": float(state["x"])}

    sup = Supervisor(cm, save_every=5, max_retries=3)
    state, report = sup.run(state0, 0, 10, do_step)
    assert report.failures == 1
    assert report.restores == 1
    # deterministic replay: x counts exactly the 10 logical steps
    assert float(state["x"]) == 10.0


def test_supervisor_gives_up_without_checkpoint(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    sup = Supervisor(cm, save_every=100)

    def bad(state, step):
        raise RuntimeError("dead on arrival")

    with pytest.raises(RuntimeError):
        sup.run({"x": jnp.zeros(())}, 0, 5, bad)


# ---------------------------------------------------------------------------
# compressed collectives
# ---------------------------------------------------------------------------

def _check_compressed_psum(seed):
    from jax.sharding import Mesh
    from repro.distributed.sharding import shard_map
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(1, 64).astype(np.float32))
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    out = shard_map(lambda v: compressed_psum(v, "data"), mesh,
                    in_specs=jax.sharding.PartitionSpec("data"),
                    out_specs=jax.sharding.PartitionSpec("data"))(x)
    scale = np.abs(np.asarray(x)).max() / 127.0
    assert np.abs(np.asarray(out) - np.asarray(x)).max() <= scale * 0.51 + 1e-7


if hypothesis is not None:
    @hypothesis.settings(max_examples=10, deadline=None)
    @hypothesis.given(st.integers(0, 2 ** 31 - 1))
    def test_compressed_psum_close_to_exact(seed):
        _check_compressed_psum(seed)
else:
    def test_compressed_psum_close_to_exact():
        _check_compressed_psum(0)       # single deterministic example


def test_dp_train_step_compressed_runs():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def grad_fn(params, batch):
        loss = jnp.mean((batch @ params) ** 2)
        return loss, jax.grad(lambda p: jnp.mean((batch @ p) ** 2))(params)

    fn = dp_train_step_compressed(grad_fn, mesh)
    params = jnp.ones((8, 4))
    batch = jnp.ones((2, 8))
    loss, grads = fn(params, batch)
    assert np.isfinite(float(loss))
    assert grads.shape == params.shape


# ---------------------------------------------------------------------------
# smoothquant & partition rules
# ---------------------------------------------------------------------------

def test_smoothquant_flattens_activations():
    from repro.core.calibration import calibrate
    from repro.core.smoothquant import apply_smoothquant
    from repro.models.registry import build
    cfg = get_config("paper_tiny")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    # plant a hot input channel for the mlp
    g = params["layers"]["ln2"]["g"]
    params["layers"]["ln2"]["g"] = g.at[:, 3].set(50.0)
    batches = [api.make_batch(jax.random.PRNGKey(i), 2, 32) for i in range(2)]
    qs = QuantConfig(mode="pt_static")
    _, stats = calibrate(api, params, batches, qs)
    before = np.asarray(stats["layers"]["mlp_in"]["absmax_ch"])
    sm = apply_smoothquant(params, stats, cfg, alpha=0.8)
    _, stats2 = calibrate(api, sm, batches, qs)
    after = np.asarray(stats2["layers"]["mlp_in"]["absmax_ch"])
    assert after.max() < before.max()


def test_partition_rules_divisibility():
    from jax.sharding import Mesh
    from repro.distributed.sharding import params_shardings
    from repro.models.registry import build
    from repro.configs import reduced
    cfg = reduced(get_config("smollm-360m"), dtype="float32")
    api = build(cfg)
    p_abs = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0)))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    sh = params_shardings(p_abs, mesh)
    # every leaf got a sharding without error
    assert len(jax.tree_util.tree_leaves(sh)) == \
        len(jax.tree_util.tree_leaves(p_abs))


def test_serve_rules_drop_fsdp_axis():
    from repro.distributed.sharding import DEFAULT_RULES, serve_rules
    sr = dict(serve_rules())
    dr = dict(DEFAULT_RULES)
    assert sr[r"attn/wqkv$"] == (None, "M")
    assert dr[r"attn/wqkv$"] == ("D", "M")


def test_placeholder_all_scales_every_family():
    from repro.configs import ARCH_IDS, get_config, reduced
    from repro.models.registry import build
    for arch in ARCH_IDS:
        cfg = reduced(get_config(arch), dtype="float32")
        api = build(cfg)
        sc = api.mod.placeholder_all_scales(cfg)
        assert "head" in sc, arch
