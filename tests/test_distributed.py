"""Multi-device (8 fake CPU devices) integration tests, run in a subprocess
so the XLA device-count override never leaks into this process."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_distributed_suite():
    env = dict(os.environ)
    # fake CPU devices for structure checks: the child must never take
    # the accelerator, which the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_dist_worker.py")],
        env=env, capture_output=True, text=True, timeout=2400)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0
    assert "ALL_DIST_OK" in proc.stdout
