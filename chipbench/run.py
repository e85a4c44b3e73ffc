#!/usr/bin/env python3
"""The on-chip benchmark of the planned N-D FFT: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  With ``--trace 0`` the run times a closed loop of the
public eager front end for ``--seconds`` and reports the cell's
end-to-end metrics; with ``--trace 1`` it traces a short window of the
same loop and reports the per-layer metrics read from the trace.  Either
way it then checks the answers the window produced against a float64
reference on the host.  The last line of standard output is one JSON
object; the numbers compared, each beside its limit, are the last lines
of standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    from chipbench import harness
    cell = harness.load_cell(root, args.workload)
    devices = harness.require_accelerator(cell.chips)
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)} "
          f"(JAX sees {len(jax.devices())})", flush=True)
    cache = harness.enable_compile_cache(root)
    print(f"compile cache: {cache}", flush=True)
    result = harness.run_cell(cell, devices, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS)
    harness.report_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)                 # import chipbench as a package
    sys.path.insert(1, str(ROOT / "src"))   # the system under test
    sys.exit(main())
