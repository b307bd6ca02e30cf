"""Coverage for distributed/collectives.py and distributed/fault_tolerance.py
beyond the substrate smoke tests:

* ``collective_bytes_of_hlo``: the §Roofline collective-term parser — op
  byte/count accounting, async -start/-done forms, dtype widths, tuple
  results skipped;
* ``compressed_psum`` / ``dp_train_step_compressed`` on a REAL multi-device
  mesh (the substrate tests only run the degenerate 1-device reduction):
  int8-payload all-reduce-mean stays within quantization error of the exact
  fp32 mean, and the shard_map'd DP step averages gradients across shards;
* ``Supervisor`` straggler detection and retry exhaustion (the substrate
  tests cover restore-and-replay only).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointManager
from repro.distributed.collectives import (collective_bytes_of_hlo,
                                           compressed_psum,
                                           dp_train_step_compressed)
from repro.distributed.fault_tolerance import Supervisor

NDEV = jax.device_count()

need2 = pytest.mark.skipif(
    NDEV < 2, reason="needs >=2 XLA host devices (run with XLA_FLAGS="
    "--xla_force_host_platform_device_count=8)")


# ---------------------------------------------------------------------------
# HLO collective-bytes parser
# ---------------------------------------------------------------------------

_HLO = """
HloModule test
  %ag = bf16[8,128]{1,0} all-gather(%x), dimensions={0}
  %ar = f32[16]{0} all-reduce-start(%y), to_apply=%add
  %ard = f32[16]{0} all-reduce-done(%ar)
  %rs = s8[64]{0} reduce-scatter(%z), dimensions={0}
  %tup = (f32[4]{0}, f32[4]{0}) tuple(%a, %b)
  %cp = f32[32,2]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
  %scalar = f32[] add(%p, %q)
"""


def test_collective_bytes_of_hlo_accounting():
    out = collective_bytes_of_hlo(_HLO)
    assert out["all-gather"] == 8 * 128 * 2          # bf16
    # -start and -done both match; the parser sums result-shape bytes of
    # every collective *op line* (the double count is deliberate: both ops
    # carry the buffer in the optimized HLO)
    assert out["all-reduce"] == 2 * 16 * 4           # f32, start + done
    assert out["reduce-scatter"] == 64               # s8
    assert out["collective-permute"] == 32 * 2 * 4
    assert out["total"] == sum(out[k] for k in
                               ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute"))
    assert out["counts"]["all-gather"] == 1
    assert out["counts"]["all-reduce"] == 2
    assert out["counts"]["all-to-all"] == 0


def test_collective_bytes_skips_tuples_and_plain_ops():
    out = collective_bytes_of_hlo(
        "%t = (f32[1024]{0}, f32[1024]{0}) all-reduce(%a, %b)\n"
        "%m = f32[1024]{0} multiply(%a, %b)\n")
    assert out["total"] == 0
    assert all(v == 0 for v in out["counts"].values())


# ---------------------------------------------------------------------------
# compressed collectives on a real multi-device mesh
# ---------------------------------------------------------------------------

@need2
def test_compressed_psum_multi_device_mean():
    """int8-payload all-reduce-mean across 2 real shards: every shard sees
    the same result, equal to the fp32 mean within the shared-scale int8
    quantization error bound."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed.sharding import shard_map

    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(2, 256).astype(np.float32) * 3.0)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    f = shard_map(lambda v: compressed_psum(v, "data"), mesh,
                  in_specs=P("data"), out_specs=P("data"))
    out = np.asarray(jax.jit(f)(x))
    exact = np.asarray(x).mean(axis=0)
    # each shard holds the mean; scale bound: amax/127 per element, halved
    # by the /2 mean plus rounding
    scale = np.abs(np.asarray(x)).max() / 127.0
    assert np.abs(out[0] - exact).max() <= scale + 1e-6
    np.testing.assert_array_equal(out[0], out[1])


@need2
def test_dp_train_step_compressed_averages_grads():
    """The shard_map'd DP step returns (replicated) loss/grad means that
    match the per-shard fp32 average within int8 comms error."""
    from jax.sharding import Mesh

    def grad_fn(params, batch):
        loss = jnp.mean((batch @ params) ** 2)
        return loss, jax.grad(lambda p: jnp.mean((batch @ p) ** 2))(params)

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    fn = dp_train_step_compressed(grad_fn, mesh)
    rs = np.random.RandomState(0)
    params = jnp.asarray(rs.randn(8, 4).astype(np.float32))
    batch = jnp.asarray(rs.randn(4, 8).astype(np.float32))
    loss, grads = fn(params, batch)

    # exact reference: mean of the per-shard losses/grads
    l0, g0 = grad_fn(params, batch[:2])
    l1, g1 = grad_fn(params, batch[2:])
    np.testing.assert_allclose(float(loss), (float(l0) + float(l1)) / 2,
                               rtol=1e-5)
    exact = (np.asarray(g0) + np.asarray(g1)) / 2
    scale = max(np.abs(np.asarray(g0)).max(),
                np.abs(np.asarray(g1)).max()) / 127.0
    assert np.abs(np.asarray(grads) - exact).max() <= scale + 1e-6


# ---------------------------------------------------------------------------
# Supervisor: stragglers and retry exhaustion
# ---------------------------------------------------------------------------

def test_supervisor_flags_stragglers(tmp_path):
    """A step much slower than the rolling median is recorded (the hot-spare
    swap trigger on real pods). The detector needs >= 8 timed steps of
    history before it arms."""
    cm = CheckpointManager(str(tmp_path))
    slow_at = 10

    def do_step(state, step):
        if step == slow_at:
            time.sleep(0.25)
        return {"x": state["x"] + 1}, {"loss": 0.0}

    sup = Supervisor(cm, save_every=100, straggler_factor=3.0)
    _, report = sup.run({"x": jnp.zeros(())}, 0, 14, do_step)
    assert slow_at in report.stragglers
    assert report.failures == 0


def test_supervisor_exhausts_retries(tmp_path):
    """With a checkpoint available, a persistently-failing step is retried
    max_retries times from the restore point and then re-raised."""
    cm = CheckpointManager(str(tmp_path))
    calls = {"fails": 0}

    def do_step(state, step):
        if step == 4:
            calls["fails"] += 1
            raise RuntimeError("hard node failure")
        return {"x": state["x"] + 1}, {"loss": 0.0}

    sup = Supervisor(cm, save_every=2, max_retries=3)
    with pytest.raises(RuntimeError, match="hard node failure"):
        sup.run({"x": jnp.zeros(())}, 0, 8, do_step)
    assert calls["fails"] == sup.max_retries + 1
    assert sup.restores == sup.max_retries


def test_supervisor_retry_budget_is_consecutive(tmp_path):
    """Regression: the retry budget counts *consecutive* failures, not
    lifetime ones. A long run with more total recovered incidents than
    max_retries — each followed by successful steps — must complete; only
    max_retries+1 failures in a row may raise. (The old lifetime counter
    killed week-long runs that had absorbed a handful of spread-out node
    losses.)"""
    cm = CheckpointManager(str(tmp_path))
    failed_at = set()

    def do_step(state, step):
        # 4 transient one-shot failures, spread across the run: each step
        # fails exactly once, succeeds on replay
        if step in (3, 7, 11, 15) and step not in failed_at:
            failed_at.add(step)
            raise RuntimeError("transient node loss")
        return {"x": state["x"] + 1}, {"loss": 0.0}

    sup = Supervisor(cm, save_every=2, max_retries=3,
                     backoff_base_s=0.0)     # keep the test instant
    _, report = sup.run({"x": jnp.zeros(())}, 0, 20, do_step)
    assert len(failed_at) == 4 > sup.max_retries, \
        "trace must exceed the old lifetime budget"
    assert report.completed_steps == 20
    assert report.failures == 4              # lifetime count still reported
    assert sup.health.consecutive_errors == 0


def test_supervisor_backs_off_between_restores(tmp_path, monkeypatch):
    """Restore attempts are separated by capped exponential backoff
    (base * 2**(k-1), k = consecutive failures so far), so a flapping node
    is not hammered with restore/replay cycles."""
    import repro.distributed.fault_tolerance as FT
    cm = CheckpointManager(str(tmp_path))
    sleeps = []
    monkeypatch.setattr(FT.time, "sleep", sleeps.append)
    calls = {"fails": 0}

    def do_step(state, step):
        if step == 4 and calls["fails"] < 3:
            calls["fails"] += 1
            raise RuntimeError("flapping")
        return {"x": state["x"] + 1}, {"loss": 0.0}

    sup = Supervisor(cm, save_every=2, max_retries=3,
                     backoff_base_s=0.1, backoff_cap_s=0.15)
    _, report = sup.run({"x": jnp.zeros(())}, 0, 8, do_step)
    assert report.completed_steps == 8
    # 0.1, 0.2->capped 0.15, 0.4->capped 0.15
    assert sleeps == [pytest.approx(0.1), pytest.approx(0.15),
                      pytest.approx(0.15)]


def test_supervisor_reports_metrics(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    seen = []
    state, report = Supervisor(cm, save_every=100).run(
        {"x": jnp.zeros(())}, 3, 5,
        lambda s, i: ({"x": s["x"] + 1}, {"loss": float(i)}),
        on_metrics=lambda step, m: seen.append((step, m["loss"])))
    assert report.completed_steps == 5
    assert seen == [(i, float(i)) for i in range(3, 8)]
