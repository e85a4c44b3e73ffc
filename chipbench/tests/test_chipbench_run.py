"""The command's path at a tiny size on the CPU, and its refusals.

The look for a chip is skipped by ``helpers.patch_for_cpu`` in these tests
only; the command itself has no option that would let it run elsewhere."""

import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests import helpers

E2E = {"transform_ms", "transform_p95_ms", "peak_hbm_gib", "setup_s"}


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    helpers.patch_for_cpu(monkeypatch)
    return helpers.tiny_root(tmp_path)


def _no_result(stdout: str) -> bool:
    return not any(ln.lstrip().startswith("{") for ln in stdout.splitlines())


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "r2c2d_16384.local", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=helpers.REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert _no_result(proc.stdout)


def test_command_refuses_in_a_bare_checkout(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    holds no system under test: no result."""
    shutil.copy(helpers.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(helpers.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", "traces",
                                                  "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "r2c2d_16384.local", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)


def test_tiny_run_end_to_end(tiny, capfd):
    rc, text, res = helpers.run_tiny(tiny, "tiny_r2c.fwd", seed=2 ** 33 + 5)
    assert rc == 0
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == E2E
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["metrics"]["transform_ms"]["unit"] == "ms"
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    checks = res["checks"]
    assert checks["compiles_in_window"] == {"value": 0, "limit": 0}
    assert checks["rel_err.last.forward"]["value"] < 1e-6
    assert "plan: decomp=local" in text
    err = capfd.readouterr().err.strip().splitlines()
    assert len(err) >= len(checks)
    assert all(ln.startswith("check ") for ln in err[-len(checks):])


def test_mix_and_config_dropped_in_are_found_by_name(tiny):
    """A 3D c2c configuration and a round-trip mix, added as files only."""
    rc, _, res = helpers.run_tiny(tiny, "tiny_c2c3d.roundtrip")
    assert rc == 0 and res["correct"] is True
    assert {k.split(".")[-1] for k in res["checks"]
            if k.startswith("rel_err")} == {"forward", "inverse"}


def test_metric_dropped_in_is_found_by_name(tiny):
    (tiny / "chipbench" / "metrics" / "steps_seen.py").write_text(
        "def read(trace, ctx):\n    return float(trace.steps)\n")
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    bench["per_layer"].append(
        {"name": "steps_seen", "unit": "steps", "better": "higher",
         "source": "device_trace", "layer": "device",
         "moves": "transform_ms", "workloads": ["tiny_r2c.fwd"]})
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell(tiny, "tiny_r2c.fwd")
    from chipbench import xplane
    trace = xplane.load(helpers.BENCH / "tests" / "data"
                        / "r2c2d_16384.local.xplane.pb")
    out = harness.read_per_layer(cell, trace, {"dispatch_s": [1e-3]})
    assert out == {"steps_seen": {"value": 3.0, "unit": "steps"}}


def test_traced_run_without_device_ops_prints_no_result(tiny, capsys):
    """On the CPU the trace holds no TPU plane: the run refuses."""
    with pytest.raises(SystemExit, match="no device operation"):
        helpers.run_tiny(tiny, "tiny_r2c.fwd", trace=1)
    assert _no_result(capsys.readouterr().out)


def test_unknown_workload_and_wrong_chip_count(tiny):
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(tiny, "nope.fwd")
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == "tiny_r2c.fwd":
            w["chips"] = 4
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="cell asks for 4"):
        harness.load_cell(tiny, "tiny_r2c.fwd")


def test_seed_key_keeps_every_bit():
    def data(seed):
        return jax.random.key_data(harness.seed_key(seed)).tolist()
    seeds = [0, 1, 2 ** 31 + 3, 2 ** 32 + 1, 2 ** 33 + 1, 2 ** 40 + 1]
    keys = [tuple(data(s)) for s in seeds]
    assert len(set(keys)) == len(seeds)
    assert data(2 ** 33 + 1) == data(2 ** 33 + 1)


def test_same_seed_same_input(tiny):
    cell = harness.load_cell(tiny, "tiny_r2c.fwd")
    mesh = harness.make_mesh(cell.config, jax.devices()[:1])
    a, b, c = (np.asarray(harness.make_input(cell, mesh, s))
               for s in (2 ** 31 + 9, 2 ** 31 + 9, 2 ** 31 + 10))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.float32 and a.shape == (64, 48)


def test_compile_cache_is_a_fixed_directory_in_the_checkout(tmp_path):
    assert harness.compile_cache_dir(tmp_path) == (
        tmp_path / "chipbench" / ".jax_cache")


def test_p95_is_the_statistics_quantile():
    values = [float(v) for v in range(1, 101)]
    assert harness.p95(values) == pytest.approx(95.95)
    assert harness.p95([3.0]) == 3.0
