"""chip_smoke.py at a tiny size on the CPU: its phases run and check, and
its entry point refuses to run without a TPU.

The four-chip phases on four (fake) devices run in tests/_dist_worker.py;
here every mesh has the one device of the test process.
"""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.core import Planner

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name,kind,shape", [
    ("r2c_2d", "r2c", (64, 64)),
    ("c2c_3d", "c2c", (16, 16, 16)),
    ("r2c_2d_odd", "r2c", (12, 15)),
])
def test_local_phase(chip_smoke, name, kind, shape):
    mesh = jax.make_mesh((1,), ("fft",))
    out = chip_smoke.run_phase(name, kind, shape, mesh, Planner())
    assert out["err"] < chip_smoke.TOL
    assert out["roundtrip_err"] < chip_smoke.TOL


@pytest.mark.parametrize("decomp,kind,shape,mesh_shape,spec", [
    ("slab", "r2c", (64, 64), (1,), ("a", None)),
    ("pencil", "c2c", (16, 16, 16), (1, 1), ("a", "b", None)),
    ("factor1d", "c2c", (1 << 12,), (1,), ("a",)),
])
def test_distributed_phase_on_one_device(chip_smoke, decomp, kind, shape,
                                         mesh_shape, spec):
    names = ("a", "b")[:len(mesh_shape)]
    mesh = jax.make_mesh(mesh_shape, names)
    out = chip_smoke.run_phase(decomp, kind, shape, mesh, Planner(),
                               decomp=decomp,
                               in_spec=jax.sharding.PartitionSpec(*spec))
    assert out["err"] < chip_smoke.TOL


def test_phase_fails_over_tolerance(chip_smoke):
    mesh = jax.make_mesh((1,), ("fft",))
    with pytest.raises(AssertionError, match="forward error"):
        chip_smoke.run_phase("tight", "r2c", (32, 32), mesh, Planner(),
                             tol=1e-12)


def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_tpu():
    proc = _run_script(SCRIPT, ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_fails_outside_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    proc = _run_script(str(alone), str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
