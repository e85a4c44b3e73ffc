#!/usr/bin/env python3
"""The readings that the output check's limits are set from, on the chip,
at the cell's own size, in one process.

    python3 chipbench/limits.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6

For each seed it makes the cell's input, runs the cell's step for as many
steps as a run's check samples from, keeps the sampled and the last
answers as a run does, and compares them with the float64 reference:
first with the program as it is, then with the control planted underneath
(the DFT matmuls at three bf16 passes, ``tests/faults.py``).  One JSON
line per seed and variant; the last line gives the largest reading of the
program and the smallest of the control.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def read(cell, devices, seed: int) -> dict:
    from chipbench import harness
    mesh = harness.make_mesh(cell.config, devices)
    x = harness.make_input(cell, mesh, seed)
    _, step = harness.make_step(cell, mesh, devices[0].device_kind)
    jax = harness.jax
    jax.block_until_ready(step(x))
    steps = int(cell.mix["check_sample_steps"])
    win = harness.run_window(step, x, steps=steps,
                             sample=harness._sample_step(seed, cell.mix,
                                                         steps))
    t0 = time.perf_counter()
    checks = harness.check_outputs(cell, mesh, x, win.kept)
    errs = [c["value"] for k, c in checks.items() if k.startswith("rel_err")]
    return {"seed": seed, "max_rel_err": max(errs), "checks": checks,
            "step_ms": min(win.step_s) * 1e3,
            "check_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import pytest
    from chipbench import harness
    from chipbench.tests import faults
    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.require_accelerator(cell.chips)
    harness.enable_compile_cache(ROOT)
    summary = {}
    for variant, seeds in (("program", args.seeds),
                           ("control", args.control_seeds)):
        if not seeds:
            continue
        mp = pytest.MonkeyPatch()
        try:
            if variant == "control":
                faults.plant(mp, "control")
                harness.jax.clear_caches()
            vals = []
            for seed in seeds:
                rec = dict(read(cell, devices, seed), variant=variant)
                vals.append(rec["max_rel_err"])
                print(json.dumps(rec), flush=True)
        finally:
            mp.undo()
            harness.jax.clear_caches()
        summary[variant] = {"seeds": len(vals), "max": max(vals),
                            "min": min(vals)}
    print(json.dumps({"workload": cell.name, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
