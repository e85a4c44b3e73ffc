"""A tiny copy of the benchmark for CPU tests: the harness's files copied
under a temporary root, with tiny configurations and cells added as data.

The look for a chip and the device's memory counter are replaced here,
in the tests, never through an option of the command."""

from __future__ import annotations

import contextlib
import io
import json
import shutil
from pathlib import Path

from chipbench import harness, run, yardstick

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "chipbench"

TINY_CONFIGS = {
    "tiny_r2c": {"shape": [64, 48], "kind": "r2c",
                 "mesh": {"shape": [1], "axes": ["fft"],
                          "input_spec": [None, None]}},
    "tiny_c2c3d": {"shape": [8, 12, 16], "kind": "c2c",
                   "mesh": {"shape": [1], "axes": ["fft"],
                            "input_spec": [None, None, None]}},
    "tiny_slab4": {"shape": [1024, 1024], "kind": "r2c",
                   "mesh": {"shape": [4], "axes": ["fft"],
                            "input_spec": ["fft", None]}},
}

ROUNDTRIP = {"why": "a round trip per step", "calls": ["forward", "inverse"],
             "plan": {"mode": "estimate"}, "warmup_steps": 2,
             "trace_steps": 3, "check_sample_steps": 4}


def tiny_root(tmp: Path, limit: float | None = None) -> Path:
    """A checkout-like root under ``tmp``: the harness's data copied, the
    tiny configurations, a ``roundtrip`` mix and their cells added."""
    root = tmp / "checkout"
    dst = root / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, dst / sub)
    shutil.copy(BENCH / "peaks.json", dst / "peaks.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    real = json.loads((BENCH / "configs" / "r2c2d_16384.json").read_text())
    if limit is not None:
        real["check"]["max_rel_err"] = {"forward": limit, "inverse": limit}
    else:
        real["check"]["max_rel_err"].setdefault(
            "inverse", real["check"]["max_rel_err"]["forward"])
    (dst / "traffic" / "roundtrip.json").write_text(json.dumps(ROUNDTRIP))
    for name, over in TINY_CONFIGS.items():
        cfg = dict(real, name=name, **over)
        path = f"chipbench/configs/{name}.json"
        (root / path).write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "tiny", "file": path,
                                 "reduced": [], "why": "CPU test"})
        chips = cfg["mesh"]["shape"][0]
        for mix in ("fwd", "roundtrip"):
            bench["workloads"].append(
                {"name": f"{name}.{mix}", "config": name, "traffic": mix,
                 "chips": chips, "why": "CPU test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def patch_for_cpu(monkeypatch) -> None:
    """Skip the look for a chip, plan and price as for a v5e, and stand
    in for the peak memory, which the CPU does not report."""
    import jax
    import repro.core
    from chipbench import work
    peaks = work.peaks_for
    monkeypatch.setattr(harness, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(repro.core, "hardware_for",
                        lambda kind: repro.core.TPU_V5E)
    monkeypatch.setattr(work, "peaks_for",
                        lambda kind: peaks("TPU v5 lite"))
    monkeypatch.setattr(harness, "enable_compile_cache",
                        harness.compile_cache_dir)
    monkeypatch.setattr(yardstick, "peak_bytes", lambda devices: 1)


def run_tiny(root: Path, workload: str, seed: int = 2 ** 31 + 17,
             seconds: float = 0.3, trace: int = 0):
    """Drive ``run.main`` in this process; return (rc, stdout, result)."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root)
    text = out.getvalue()
    return rc, text, json.loads(text.strip().splitlines()[-1])
