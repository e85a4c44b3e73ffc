"""CI-sized benchmark subset + regression gate (the perf trajectory).

Runs a small, fixed set of distributed-FFT cases on the 8-way fake-device
CPU mesh (the fig2 decomposition verdicts + fig6-style timed executions,
including the PR-4 additions: multi-axis 4D pencil, the factor-split
distributed 1D, and the planned transposed slab layout) and emits
``BENCH_ci.json``:

* per-case **best-of-12 ms** (the min is the regression-gate statistic:
  robust to scheduler noise spikes) and the executed plan's verdict
  (decomp / mesh axes / comm / output layout / factors);
* a **calibration** time (one planned local 2D FFT) and each case's
  ``rel = ms / calib_ms`` — informational context for the artifact.

Gate semantics (``--baseline benchmarks/baseline_ci.json``): each case's
ms ratio vs baseline is compared against the MEDIAN ratio across cases
(the machine-speed factor), so a uniformly slower CI runner trips
nothing — only a case that regressed by more than ``--tolerance``
(default 25%) *relative to its peers* fails, and a missing case always
fails.  ``BENCH_SKIP_GATE=1`` reports without
failing (the CI override label sets it); ``--write-baseline`` refreshes
the committed baseline; ``--inject-slowdown CASE:FACTOR`` multiplies one
case's measurement after the fact — the knob used to demonstrate the gate
trips (see benchmarks/README.md).

The measurement runs in a subprocess (the fake-device-count override is
process-local), exactly like fig6.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

SCHEMA = "repro-bench-ci"
VERSION = 1
DEFAULT_TOLERANCE = 0.25


def _worker(out_path: str) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import time as _time

    from repro.core import api, plan

    def best_of(fn, *args, reps: int = 12, warmup: int = 3) -> float:
        """Best-of-k wall seconds per call: the min is the right statistic
        for a regression gate (robust to scheduler noise spikes, which on
        shared CI runners dwarf the median's jitter at ms scale)."""
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(reps):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, _time.perf_counter() - t0)
        return best

    planner = plan.Planner(mode="estimate", backends=("jnp",))
    rng = np.random.default_rng(0)
    mesh8 = jax.make_mesh((8,), ("fft",))
    mesh42 = jax.make_mesh((4, 2), ("mx", "my"))
    mesh222 = jax.make_mesh((2, 2, 2), ("ma", "mb", "mc"))

    def timed(nd, mesh, x):
        if nd.kind == "c2c" and not isinstance(x, tuple):
            x = (x, np.zeros_like(x))
        if isinstance(x, tuple):
            arrs = tuple(jax.numpy.asarray(a) for a in x)
            fn = jax.jit(lambda a, b, _p=nd: api.execute_nd(
                _p, (a, b), mesh=mesh, planner=planner))
        else:
            arrs = (jax.numpy.asarray(x),)
            fn = jax.jit(lambda a, _p=nd: api.execute_nd(
                _p, a, mesh=mesh, planner=planner))
        return best_of(fn, *arrs) * 1e3          # best-of ms

    def plan_record(nd):
        return {"decomp": nd.decomp, "mesh_axes": list(nd.mesh_axes),
                "comm": list(nd.comm), "output_layout": nd.output_layout,
                "factors": list(nd.factors)}

    # calibration: one planned local 2D r2c FFT on a single device —
    # everything else is reported relative to this machine-speed probe
    x256 = rng.standard_normal((256, 256)).astype(np.float32)
    nd_cal = api.plan_nd((256, 256), "r2c", planner=planner)
    calib_ms = timed(nd_cal, None, x256)

    cases = {}

    def case(name, nd, mesh, x):
        ms = timed(nd, mesh, x)
        cases[name] = {"ms": ms, "rel": ms / calib_ms,
                       "plan": plan_record(nd)}

    xs = jax.device_put(x256, NamedSharding(mesh8, P("fft", None)))
    case("slab_r2c_256",
         api.plan_nd((256, 256), "r2c", mesh=mesh8, planner=planner,
                     decomp="slab", axes=("fft",), comm="collective"),
         mesh8, xs)
    case("slab_r2c_256_transposed",
         api.plan_nd((256, 256), "r2c", mesh=mesh8, planner=planner,
                     decomp="slab", axes=("fft",), comm="collective",
                     output_layout="transposed"),
         mesh8, xs)

    x3 = rng.standard_normal((32, 64, 64)).astype(np.float32)
    pair3 = tuple(jax.device_put(a, NamedSharding(mesh42,
                                                  P("mx", "my", None)))
                  for a in (x3, np.zeros_like(x3)))
    case("pencil_c2c_32x64x64",
         api.plan_nd((32, 64, 64), "c2c", mesh=mesh42, planner=planner,
                     decomp="pencil", axes=("mx", "my"), comm="auto"),
         mesh42, pair3)

    x4 = rng.standard_normal((16, 16, 32, 32)).astype(np.float32)
    pair4 = tuple(jax.device_put(a, NamedSharding(
        mesh222, P("ma", "mb", "mc", None)))
        for a in (x4, np.zeros_like(x4)))
    case("pencil4d_c2c_16x16x32x32_k3",
         api.plan_nd((16, 16, 32, 32), "c2c", mesh=mesh222, planner=planner,
                     decomp="pencil", axes=("ma", "mb", "mc"),
                     comm="collective"),
         mesh222, pair4)

    n1d = 1 << 16
    x1 = rng.standard_normal((n1d,)).astype(np.float32)
    pair1 = tuple(jax.device_put(a, NamedSharding(mesh8, P("fft")))
                  for a in (x1, np.zeros_like(x1)))
    case("factor1d_c2c_65536",
         api.plan_nd((n1d,), "c2c", mesh=mesh8, planner=planner,
                     decomp="factor1d", axes=("fft",), comm="collective"),
         mesh8, pair1)

    # free-choice planner verdicts (no timing): the fig2 decomposition
    # column at CI scale — a planner change that flips one of these shows
    # up in the artifact diff even when the timings sit inside tolerance
    verdicts = {}
    for tag, shape, kind, mesh in (
            ("slab_1024sq", (1024, 1024), "r2c", {"fft": 8}),
            ("pencil_128cube", (128, 128, 128), "c2c", {"mx": 4, "my": 2}),
            ("factor1d_1M", (1 << 20,), "c2c", {"fft": 8}),
            ("local_64sq", (64, 64), "r2c", {"fft": 8})):
        nd = api.plan_nd(shape, kind, mesh=mesh, planner=planner)
        verdicts[tag] = nd.decomp

    out = {"schema": SCHEMA, "version": VERSION, "calib_ms": calib_ms,
           "cases": cases, "verdicts": verdicts}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")


def _gate(results: dict, baseline: dict, tolerance: float) -> int:
    """Compare per-case ms ratios vs baseline against the MEDIAN ratio (the
    machine-speed factor): a uniformly slower runner shifts every ratio and
    trips nothing; one case that regressed relative to its peers exceeds
    ``median * (1 + tolerance)``.  A BACKSTOP guards the median itself:
    if it drifted more than ``2 * tolerance`` beyond the single-device
    calibration ratio, the mesh cases slowed down *as a group* relative to
    local compute (e.g. a shared exchange-layer regression) and the run
    fails even though no case stands out from its peers.  Returns the
    violation count (missing cases count)."""
    bad = 0
    ratios = {}
    for name, base in baseline.get("cases", {}).items():
        got = results["cases"].get(name)
        if got is not None:
            ratios[name] = got["ms"] / base["ms"]
    speed = sorted(ratios.values())[len(ratios) // 2] if ratios else 1.0
    calib_ratio = results["calib_ms"] / baseline["calib_ms"] \
        if baseline.get("calib_ms") else 1.0
    print(f"bench_ci gate: machine-speed factor {speed:.2f} "
          f"(median of {len(ratios)} case ratios; "
          f"calib ratio {calib_ratio:.2f})")
    backstop = calib_ratio * (1.0 + 2.0 * tolerance)
    if speed > backstop:
        print(f"BENCH GATE: the mesh cases slowed down as a group — median "
              f"ratio {speed:.2f} exceeds calibration-drift backstop "
              f"{backstop:.2f} (uniform regressions cannot hide behind "
              "the median normalization)")
        bad += 1
    for name, base in sorted(baseline.get("cases", {}).items()):
        got = results["cases"].get(name)
        if got is None:
            print(f"BENCH GATE: case {name!r} missing from results")
            bad += 1
            continue
        limit = speed * (1.0 + tolerance)
        verdict = "FAIL" if ratios[name] > limit else "ok"
        print(f"bench_ci {name}: {got['ms']:.2f} ms vs baseline "
              f"{base['ms']:.2f} ms -> ratio {ratios[name]:.2f} "
              f"(limit {limit:.2f}) [{verdict}]")
        if ratios[name] > limit:
            bad += 1
    for name in sorted(set(results["cases"]) - set(baseline.get("cases", {}))):
        print(f"bench_ci {name}: new case (no baseline) "
              f"{results['cases'][name]['ms']:.2f} ms")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_ci.json")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON to gate against")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="allowed relative regression (0.25 = 25%%)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="refresh the baseline file instead of gating")
    ap.add_argument("--inject-slowdown", default=None, metavar="CASE:FACTOR",
                    help="multiply case measurements (gate-trip demo); "
                         "comma-separate entries, or use CASE '*' to slow "
                         "every case (backstop demo)")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.worker:
        _worker(args.out)
        return 0

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # fake CPU devices for structure checks: the child must never take
    # the accelerator, which the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out_path = os.path.abspath(args.out)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_ci", "--worker",
         "--out", out_path],
        env=env, cwd=root, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError("bench_ci worker failed")
    with open(out_path) as f:
        results = json.load(f)

    if args.inject_slowdown:
        for entry in args.inject_slowdown.split(","):
            name, _, factor = entry.partition(":")
            names = list(results["cases"]) if name == "*" else [name]
            for n in names:
                results["cases"][n]["ms"] *= float(factor)
                results["cases"][n]["rel"] *= float(factor)
            print(f"bench_ci: injected x{factor} slowdown into {names}")
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
            f.write("\n")

    print(f"bench_ci: calib {results['calib_ms']:.2f} ms; "
          f"verdicts {results['verdicts']}")
    if args.write_baseline:
        base_path = args.baseline or os.path.join(root, "benchmarks",
                                                  "baseline_ci.json")
        with open(base_path, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"bench_ci: baseline written to {base_path}")
        return 0
    if args.baseline:
        if not os.path.exists(args.baseline):
            # fail closed: a forgotten/renamed baseline must not silently
            # disable the gate
            print(f"bench_ci: baseline {args.baseline!r} not found — "
                  "regenerate with scripts/bench_ci.sh --write-baseline")
            return 1
        with open(args.baseline) as f:
            baseline = json.load(f)
        bad = _gate(results, baseline, args.tolerance)
        if bad and os.environ.get("BENCH_SKIP_GATE"):
            print(f"bench_ci: {bad} regression(s) IGNORED "
                  "(BENCH_SKIP_GATE set)")
        elif bad:
            print(f"bench_ci: {bad} regression(s) beyond "
                  f"{args.tolerance:.0%} — failing (set BENCH_SKIP_GATE=1 "
                  "or apply the 'bench-regression-ok' label to override; "
                  "refresh with scripts/bench_ci.sh --write-baseline)")
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
