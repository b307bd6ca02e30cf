"""``chip_smoke.py`` refuses to report a result it did not earn: no TPU, a
forced jnp kernel route, a checkout without the package, or a no-chaos
router run that lost a replica, retried or rejected a request."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.monitoring import RouterStats  # noqa: E402


def _run(cwd, script, **env):
    full = {k: v for k, v in os.environ.items()
            if not k.startswith("REPRO_")}
    full.update(JAX_PLATFORMS="cpu", **env)
    r = subprocess.run([sys.executable, script], cwd=cwd, env=full,
                       capture_output=True, text=True, timeout=300)
    return r.returncode, r.stdout


def test_fails_without_tpu():
    rc, out = _run(ROOT, "chip_smoke.py")
    assert rc != 0
    assert '"ok": true' not in out


@pytest.mark.parametrize("var", chip_smoke.KERNEL_ENV)
def test_fails_when_a_kernel_is_forced_to_jnp(var):
    rc, out = _run(ROOT, "chip_smoke.py", **{var: "jnp"})
    assert rc != 0
    assert '"ok": true' not in out


def test_fails_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    rc, out = _run(str(tmp_path), "chip_smoke.py")
    assert rc != 0
    assert '"ok": true' not in out


@pytest.mark.parametrize("field,value", [
    ("replica_deaths", 1), ("retries", 2), ("completed", 7)])
def test_router_health_rejects_failover(field, value):
    stats = RouterStats(n_replicas=4, submitted=8, completed=8)
    setattr(stats, field, value)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_router_health(stats)


def test_router_health_rejects_rejections():
    stats = RouterStats(n_replicas=4, submitted=8, completed=7)
    stats.reject("queue_full")
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_router_health(stats)


def test_router_health_passes_clean_run():
    chip_smoke.check_router_health(
        RouterStats(n_replicas=4, submitted=8, completed=8))
