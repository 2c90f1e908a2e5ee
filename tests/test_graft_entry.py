"""The driver contract: __graft_entry__ must work as invoked by the driver.

Round-1 regression: dryrun_multichip asserted on jax.device_count() instead
of provisioning virtual devices, so the driver's multi-chip check failed on
a one-device machine. These tests run the entry exactly the way the driver
does — `python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"`
from the repo root — including from a CPU parent that only sees ONE
device, which forces the subprocess rehearsal path. From a parent whose
backend is an accelerator that path must NOT be taken: too few chips is an
error there, not a rehearsal.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _driver_env(n_parent_devices: int) -> dict:
    """Env for a parent process that sees n CPU devices (no TPU grab)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # Strip any inherited forced-device-count so the parent sees exactly n.
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_parent_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def test_dryrun_multichip_self_provisions_from_one_device():
    """CPU parent sees 1 device -> dryrun_multichip(8) must still pass,
    and every line it prints names the platform it ran on."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; jax.config.update('jax_platforms', 'cpu');"
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        env=_driver_env(1), cwd=REPO, capture_output=True, text=True,
        timeout=900,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "dryrun_multichip(8) on cpu: one gtopk step OK" in proc.stdout


def test_dryrun_multichip_from_a_chip_parent_does_not_hide_the_device(
        monkeypatch):
    """Parent backend is the chip, fewer chips than asked -> raises; no
    virtual CPU devices, no subprocess, no 'step OK'."""
    sys.path.insert(0, REPO)
    import __graft_entry__ as entry

    monkeypatch.setattr(entry.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(entry.jax, "device_count", lambda: 1)
    monkeypatch.setattr(entry.subprocess, "run", lambda *a, **k: pytest.fail(
        "spawned a child from a parent that holds the chip"))
    with pytest.raises(RuntimeError, match="only 1 tpu device"):
        entry.dryrun_multichip(4)


@pytest.mark.slow  # ~43 s subprocess; the self-provisioning variant
# below exercises the same dryrun step plus the re-exec path, so this
# direct-path twin is the redundant half of the pair
def test_dryrun_multichip_direct_path():
    """Parent already has >= 8 devices -> runs in-process."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; jax.config.update('jax_platforms', 'cpu');"
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        env=_driver_env(8), cwd=REPO, capture_output=True, text=True,
        timeout=900,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "one gtopk step OK" in proc.stdout
