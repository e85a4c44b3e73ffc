"""The output check against its control and the faults a cell can have:
each drives the rest of a run at a tiny size on the CPU, with the timed
path broken underneath, and sees ``correct`` come out false.  The sound
program, at the same size, comes out true.

The slab cell's faults run on four virtual CPU devices in a child process
(``four_devices.py``), since the device count is fixed when JAX starts."""

import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench.tests import faults, helpers


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    helpers.patch_for_cpu(monkeypatch)
    jax.clear_caches()
    yield helpers.tiny_root(tmp_path)
    jax.clear_caches()


def _failed(res, prefix="rel_err"):
    return [k for k, c in res["checks"].items()
            if k.startswith(prefix) and not c["value"] <= c["limit"]]


@pytest.mark.parametrize("workload", ["tiny_r2c.fwd", "tiny_c2c3d.fwd"])
def test_sound_program_is_correct(tiny, workload):
    _, _, res = helpers.run_tiny(tiny, workload)
    assert res["correct"] is True and res["failed"] == 0


@pytest.mark.parametrize("workload", ["tiny_r2c.fwd", "tiny_c2c3d.fwd"])
@pytest.mark.parametrize("fault", ["control", "answer_altered",
                                   "half_left_out"])
def test_planted_fault_is_not_correct(tiny, monkeypatch, workload, fault):
    faults.plant(monkeypatch, fault)
    _, _, res = helpers.run_tiny(tiny, workload)
    assert res["correct"] is False
    assert res["failed"] >= 1
    assert _failed(res)          # it is the comparison that catches it


def test_control_reads_between_program_and_fault(tiny, monkeypatch):
    """The control's error lies well above the program's: the limit has
    room on both sides at this size too."""
    _, _, sound = helpers.run_tiny(tiny, "tiny_r2c.fwd")
    jax.clear_caches()
    faults.plant(monkeypatch, "control")
    _, _, control = helpers.run_tiny(tiny, "tiny_r2c.fwd")
    s = sound["checks"]["rel_err.last.forward"]
    c = control["checks"]["rel_err.last.forward"]
    assert s["value"] * 5 < s["limit"] < c["value"]


def test_four_devices_sound_and_exchange_left_out(tmp_path):
    out = tmp_path / "four.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(helpers.REPO / "src"), str(helpers.REPO)])
    proc = subprocess.run(
        [sys.executable, str(helpers.BENCH / "tests" / "four_devices.py"),
         str(out)], env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    report = json.loads(out.read_text())
    sound, broken = report["sound"], report["exchange_left_out"]
    for r in (sound, broken):
        assert r["rc"] == 0
        assert "decomp=slab" in r["plan"][0]
        assert "comm=('pipelined',)" in r["plan"][0]
    assert sound["result"]["correct"] is True
    assert sound["result"]["checks"]["outputs_not_over_mesh"]["value"] == 0
    assert broken["result"]["correct"] is False
    assert _failed(broken["result"])


def test_unknown_fault_is_refused(monkeypatch):
    with pytest.raises(ValueError):
        faults.plant(monkeypatch, "gremlins")
